"""queue_wait_p95_ms.tail: 95th percentile of the scheduler's queue span
(admission to dispatch, StreamServer) over the window's requests."""

from bench.harness import percentile


def read(run):
    if run.queue_s is None or not len(run.queue_s):
        return None
    return percentile(run.queue_s, 95) * 1e3
