"""synapse_roofline_pct.sat: the least time the window's work on the synapse
kernel needs at the chip's roofline (bench/roofline.py: 2 ops per input event
and destination it reaches, the stored weights once per call per chip plus
events and currents), over the layers that run on that kernel, against the
kernel's device time."""

from bench.roofline import SYNAPSE_KERNEL, least_time


def read(run):
    if run.device is None or not run.work:
        return None
    kernel_s = run.device.op_seconds(SYNAPSE_KERNEL)
    if kernel_s <= 0:
        return None
    return 100.0 * least_time(run.work, run.peaks, SYNAPSE_KERNEL) / kernel_s
