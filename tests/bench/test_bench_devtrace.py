"""The reduction from a profiler trace to device busy, idle, kernel time and
the breakdown, on a small synthetic profile."""

import pytest

from _bench_tiny import harness  # noqa: F401
from bench.devtrace import DeviceTrace, Op, from_planes, gaps, union_ns

MS = 1_000_000


def planes():
    chip0 = [Op("fusion.1", 0, 2 * MS),
             Op("event_synapse_kernel", 2 * MS, 5 * MS),
             Op("inner", 3 * MS, 1 * MS),            # nested: counts once
             Op("scan", 10 * MS, 2 * MS)]
    chip1 = [Op("event_synapse_kernel", 1 * MS, 4 * MS)]
    return [
        ("/host:CPU", [("python", [Op("dispatch", 0, 50 * MS)])]),
        ("/device:TPU:0", [("XLA Ops", chip0),
                           ("XLA Modules", [Op("jit_fwd", 0, 12 * MS)])]),
        ("/device:TPU:1", [("XLA Ops", chip1)]),
        ("/device:TPU:2", [("XLA Ops", [Op("other", 0, 9 * MS)])]),
        ("/device:TPU:0 SparseCore 0", [("XLA Ops", [Op("x", 0, MS)])]),
    ]


def test_union_and_gaps():
    ops = planes()[1][1][0][1]
    assert union_ns(ops) == 9 * MS
    assert gaps(ops) == [("idle before scan", 3 * MS)]
    assert union_ns([]) == 0


def test_device_planes_and_op_line_only():
    tr = from_planes(planes(), n_chips=2, window_s=0.02)
    assert len(tr.chips) == 2
    assert tr.per_chip_busy_s == pytest.approx([0.009, 0.004])
    assert tr.busy_s == pytest.approx(0.0065)
    assert tr.op_seconds("event_synapse") == pytest.approx(0.009)
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["event_synapse_kernel",
                                   pytest.approx(0.009)]
    assert bd["idle_gaps"] == [["idle before scan", pytest.approx(0.003)]]
    assert len(bd["device_ops"]) <= 10


def test_device_metric_readers_on_the_synthetic_trace():
    spec = harness.Spec()
    tr = from_planes(planes(), n_chips=2, window_s=0.02)
    run = harness.Run(cell={}, cfg={}, mix={}, n_chips=2,
                      setup_s=1.0, req={}, window=(0.0, 1.0), device=tr,
                      work=[(2e6, 1e6, "event_synapse")],
                      peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    assert spec.reader("device_idle_pct.sat")(run) == pytest.approx(67.5)
    assert spec.reader("synapse_share_pct.sat")(run) == pytest.approx(
        100 * 0.009 / 0.013)
    # least time 1e6 B / 1e9 B/s = 1 ms against 9 ms of kernel
    assert spec.reader("synapse_roofline_pct.sat")(run) == pytest.approx(
        100 / 9)
    assert spec.reader("mfu.sat")(run) == pytest.approx(
        100 * 2e6 / (0.02 * 2 * 1e12))


def test_roofline_reads_only_its_kernels_layers_and_mfu_every_layer():
    spec = harness.Spec()
    tr = from_planes(planes(), n_chips=2, window_s=0.02)
    run = harness.Run(cell={}, cfg={}, mix={}, n_chips=2,
                      setup_s=1.0, req={}, window=(0.0, 1.0), device=tr,
                      work=[(2e6, 1e6, "event_synapse"),
                            (4e6, 5e6, "conv_synapse")],
                      peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})
    # the conv_synapse layer's 5 ms are not the event_synapse ops' work
    assert spec.reader("synapse_roofline_pct.sat")(run) == pytest.approx(
        100 / 9)
    assert spec.reader("mfu.sat")(run) == pytest.approx(
        100 * 6e6 / (0.02 * 2 * 1e12))


def test_readers_find_nothing_without_a_device():
    spec = harness.Spec()
    empty = DeviceTrace(window_s=1.0, chips=[])
    run = harness.Run(cell={}, cfg={}, mix={}, n_chips=1,
                      setup_s=1.0, req={}, window=(0.0, 1.0), device=empty,
                      work=[(1.0, 1.0, "event_synapse")],
                      peaks={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    for name in ("device_idle_pct.sat", "synapse_share_pct.sat",
                 "synapse_roofline_pct.sat", "mfu.sat"):
        assert spec.reader(name)(run) is None
