"""bucket_fill_pct.sat: requests over padded batch rows, summed over the
window's dispatches (dispatch span attributes)."""


def read(run):
    if not run.dispatches:
        return None
    rows = sum(d["b_pad"] for d in run.dispatches)
    return 100.0 * sum(d["n_requests"] for d in run.dispatches) / rows
