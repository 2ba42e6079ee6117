"""Operation and byte counts of the synapse layers on hand-checked shapes,
the window's work at the benchmark's configurations, the peaks table, and
the host-clock metric readers."""

import numpy as np
import pytest

from _bench_tiny import harness
from bench.models import snn_mlp
from bench.roofline import (conv2d, dense, layer_work, least_time, peaks_for,
                            sum_pool2d)


def brute_pairs(in_shape, c_out, kh, kw, stride, padding, groups):
    """(source, destination) pairs of a convolution whose every tap is 1,
    counted by walking every output position and tap."""
    c_in, h, w = in_shape
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    hit = np.zeros((c_in, h, w, c_out, oh, ow), dtype=bool)
    per_in, per_out = c_in // groups, c_out // groups
    for co in range(c_out):
        g = co // per_out
        for oy in range(oh):
            for ox in range(ow):
                for ky in range(kh):
                    for kx in range(kw):
                        y = oy * stride + ky - padding
                        x = ox * stride + kx - padding
                        if 0 <= y < h and 0 <= x < w:
                            hit[g * per_in:(g + 1) * per_in, y, x,
                                co, oy, ox] = True
    return int(hit.sum()), c_in * h * w


# Every case: 100 events in 7 real rows, 3 calls on each of 2 chips, 8-bit
# weights.  Bytes: the stored weights once per call per chip, 4 B per event,
# 4 B per real row and destination.
WORK_CASES = {
    # 20 -> 10 dense: 2 * 100 * 10 ops; the 20 x 10 matrix, 200 B a call
    "dense": (dense(20, 10), None, 10, 200, 10,
              2000, 3 * 2 * 200 + 400 + 7 * 10 * 4),
    # 2x6x6 -> 4 channels, 3x3, stride 1, padding 1: along each axis
    # 6 * 3 = 18 (output, tap) pairs less 2 in the padding, 16 over 6
    # positions; 4 * 16 * 16 * 2 = 2,048 pairs over 72 sources
    "conv3x3_pad1": (conv2d((2, 6, 6), 4, 3, 3, stride=1, padding=1),
                     ((2, 6, 6), 4, 3, 3, 1, 1, 1), 2048 / 72, 72, 144,
                     2 * 100 * 2048 / 72, 3 * 2 * 72 + 400 + 7 * 144 * 4),
    # 2x8x8 -> 3 channels, 3x3, stride 2, no padding: 3 outputs x 3 taps
    # along each axis over 8 positions (the last one is read by none)
    "conv3x3_stride2": (conv2d((2, 8, 8), 3, 3, 3, stride=2, padding=0),
                        ((2, 8, 8), 3, 3, 3, 2, 0, 1), 3 * 9 * 9 / 64, 54,
                        27, 2 * 100 * 3 * 81 / 64,
                        3 * 2 * 54 + 400 + 7 * 27 * 4),
    # 2x2 sum-pooling of 3x4x4: each event reaches one destination;
    # 3 channels x 4 fixed taps stored
    "sum_pool2x2": (sum_pool2d((3, 4, 4), 2), ((3, 4, 4), 3, 2, 2, 2, 0, 3),
                    1, 12, 12, 200, 3 * 2 * 12 + 400 + 7 * 12 * 4),
}


@pytest.mark.parametrize("case", list(WORK_CASES))
def test_layer_work_by_hand(case):
    layer, conv, fan_out, n_weights, n_dest, want_ops, want_bytes = \
        WORK_CASES[case]
    assert layer.fan_out == pytest.approx(fan_out, rel=1e-12)
    assert (layer.n_weights, layer.n_dest) == (n_weights, n_dest)
    assert layer.kernel == "event_synapse"
    if conv is not None:
        pairs, n_src = brute_pairs(*conv)
        assert layer.fan_out == pytest.approx(pairs / n_src, rel=1e-12)
    ops, nbytes = layer_work(layer, events=100, rows=7, calls=3, n_chips=2,
                             weight_bits=8)
    assert ops == pytest.approx(want_ops, rel=1e-12)
    assert nbytes == want_bytes


@pytest.mark.parametrize("kernel,want", [(None, 10.0 + 5.0),
                                         ("event_synapse", 10.0)])
def test_least_time_takes_the_binding_roof_per_layer(kernel, want):
    # the second layer runs on another kernel: a pattern leaves it out
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    work = [(1000.0, 10.0, "event_synapse"), (10.0, 50.0, "conv_synapse")]
    assert least_time(work, peaks, kernel) == want


@pytest.mark.parametrize("config,n_chips", [("cifar10dvs_mlp", 1),
                                            ("nmnist_mlp", 1),
                                            ("nmnist_mlp", 4)])
def test_window_work_of_the_mlps_is_the_dense_count(config, n_chips):
    """At the configurations' full widths, with synthetic window counts, the
    work handed to the readers is the dense formula's, bit for bit."""
    cfg = harness.Spec().config(config)
    rng = np.random.default_rng(5)
    n_req, steps, calls = 3001, int(cfg["sensor"]["num_steps"]), 187
    rec = {"status": np.zeros(n_req, np.int8),
           "events_in": rng.integers(0, 5000, n_req)}
    n_layers = len(cfg["hidden_sizes"])
    cmp = {"layer_events": [int(e) for e in
                            rng.integers(1, 10**7, n_layers)],
           "steps": 128 * steps}
    got = harness.window_work(cfg, snn_mlp, rec, cmp, calls, n_chips)

    down = cfg["input_downsample"]
    sizes = [2 * (cfg["sensor"]["height"] // down)
             * (cfg["sensor"]["width"] // down), *cfg["hidden_sizes"]]
    rows = n_req * steps
    events = [float(rec["events_in"].sum())] + [
        e / cmp["steps"] * rows for e in cmp["layer_events"][1:]]
    want = [(2.0 * ev * n_dest,
             calls * n_chips * n_src * n_dest * cfg["quant_bits"] / 8
             + 4.0 * ev + 4.0 * rows * n_dest)
            for n_src, n_dest, ev in zip(sizes[:-1], sizes[1:], events)]
    assert [(ops, nbytes) for ops, nbytes, _ in got] == want
    assert {kernel for _, _, kernel in got} == {"event_synapse"}


def test_peaks_table():
    p = peaks_for("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="no peaks"):
        peaks_for("TPU v9 imaginary")


def run_of(due, done, status, t0=0.0, t1=2.0):
    return harness.Run(cell={}, cfg={}, mix={}, n_chips=1,
                       setup_s=3.5, window=(t0, t1),
                       req={"due": np.asarray(due, float),
                            "done": np.asarray(done, float),
                            "status": np.asarray(status)})


def test_host_clock_readers():
    spec = harness.Spec()
    run = run_of(due=[0.0, 0.5, 1.0, 1.5], done=[0.1, 0.7, 2.5, np.nan],
                 status=[0, 0, 0, 2])
    # answers inside [0, 2]: two, over 2 s
    assert spec.reader("served_rps")(run) == 1.0
    # latencies 100, 200, 1500 ms and one unanswered (infinite)
    assert spec.reader("p95_latency_ms")(run) == np.inf
    assert spec.reader("setup_s")(run) == 3.5
    # 20 answered requests 1..20 ms late: nearest rank 19 of 20
    run = run_of(due=np.zeros(20), done=np.arange(1, 21) / 1e3,
                 status=np.zeros(20))
    assert spec.reader("p95_latency_ms")(run) == pytest.approx(19.0)


def test_span_readers():
    spec = harness.Spec()
    run = run_of([0.0], [0.1], [0])
    run.queue_s = np.array([0.01, 0.02, 0.03, 0.2])
    run.dispatches = [
        {"b_pad": 16, "n_requests": 16, "dispatch_s": 0.05,
         "pad_s": 0.001, "slice_s": 0.0005},
        {"b_pad": 4, "n_requests": 3, "dispatch_s": 0.02,
         "pad_s": 0.0004, "slice_s": 0.0001}]
    assert spec.reader("queue_wait_p95_ms.tail")(run) == pytest.approx(200)
    assert spec.reader("dispatch_p95_ms.tail")(run) == pytest.approx(50)
    assert spec.reader("bucket_fill_pct.sat")(run) == pytest.approx(95.0)
    assert spec.reader("pad_slice_ms.sat")(run) == pytest.approx(1.0)
