"""Operation and byte counts of the synapse layers on hand-checked shapes,
the peaks table, and the host-clock metric readers."""

import numpy as np
import pytest

from _bench_tiny import harness
from bench.roofline import layer_work, least_time, peaks_for


def test_layer_work_by_hand():
    # 100 events into 10 destinations: 2 * 100 * 10 ops; bytes: the 8-bit
    # 20 x 10 matrix once per call per chip (3 calls x 2 chips x 200 B),
    # 4 B per event, 4 B per real row and destination (7 rows)
    ops, nbytes = layer_work(n_src=20, n_dest=10, events=100, rows=7,
                             calls=3, n_chips=2, weight_bits=8)
    assert ops == 2000
    assert nbytes == 3 * 2 * 200 + 400 + 7 * 10 * 4


def test_least_time_takes_the_binding_roof_per_layer():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time([(1000.0, 10.0), (10.0, 50.0)], peaks) == 10.0 + 5.0


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
