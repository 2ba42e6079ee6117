"""p95_latency_ms: 95th percentile over every request due in the window, from its due
time to its answer at the client (host clock); a request that was rejected or
never answered counts as infinitely late."""

import numpy as np

from bench.harness import percentile


def read(run):
    req = run.req
    lat = np.where(req["status"] == 0, req["done"] - req["due"], np.inf)
    return percentile(lat, 95) * 1e3
