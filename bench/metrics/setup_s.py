"""setup_s: from the start of the benchmark process to the first request of
the window: JAX start-up, weights, mapping and packing, loading or compiling
the cell's buckets, and the warm-up traffic (host clock)."""


def read(run):
    return float(run.setup_s)
