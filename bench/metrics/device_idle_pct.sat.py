"""device_idle_pct.sat: share of the traced window in which no operation ran
on the device (1 - union of op intervals / window), averaged over the cell's
chips; the per-chip values are on an earlier line of the run."""


def read(run):
    if run.device is None or run.device.busy_s <= 0:
        return None
    return 100.0 * (1.0 - run.device.busy_s / run.device.window_s)
