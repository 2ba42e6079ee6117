"""synapse_roofline_pct.sat: the least time the window's synapse work needs
at the chip's roofline (bench/roofline.py: 2 ops per input event and
destination, the weight matrix once per call per chip plus events and
currents) over the synapse kernels' device time."""

from bench.roofline import least_time

KERNEL_PATTERN = "event_synapse"


def read(run):
    if run.device is None or not run.work:
        return None
    kernel_s = run.device.op_seconds(KERNEL_PATTERN)
    if kernel_s <= 0:
        return None
    return 100.0 * least_time(run.work, run.peaks) / kernel_s
