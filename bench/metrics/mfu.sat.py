"""mfu.sat: synaptic operations of the window (2 per input event and
destination it reaches, every layer, bench/roofline.py) per second of the
traced window, over the peak FLOP/s of the cell's chips."""


def read(run):
    if run.device is None or run.device.busy_s <= 0 or not run.work:
        return None
    ops = sum(w[0] for w in run.work)
    return 100.0 * ops / (run.device.window_s * run.n_chips
                          * run.peaks["flops_per_s"])
