"""dispatch_p95_ms.tail: 95th percentile over the window's dispatches of the
dispatch span (execute_plan: pad, run_sharded through to the device-to-host
copy of the outputs, slice)."""

from bench.harness import percentile


def read(run):
    if not run.dispatches:
        return None
    return percentile([d["dispatch_s"] for d in run.dispatches], 95) * 1e3
