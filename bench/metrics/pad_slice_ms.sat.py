"""pad_slice_ms.sat: host time per dispatch spent zero-padding the requests
into the bucket and slicing the answers back out (pad + slice spans), the
mean over the window's dispatches."""


def read(run):
    if not run.dispatches:
        return None
    return 1e3 * sum(d["pad_s"] + d["slice_s"] for d in run.dispatches) \
        / len(run.dispatches)
