"""synapse_share_pct.sat: device time of the synapse Pallas kernels
(kernels/event_synapse.py) over device busy time, summed over the chips."""

KERNEL_PATTERN = "event_synapse"


def read(run):
    if run.device is None:
        return None
    kernel_s = run.device.op_seconds(KERNEL_PATTERN)
    busy = run.device.busy_s * run.n_chips
    if kernel_s <= 0 or busy <= 0:
        return None
    return 100.0 * kernel_s / busy
