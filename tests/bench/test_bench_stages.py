"""The reduction of the server's stages and the forward's scopes, on a small
synthetic profile, and the window and stages read back from a CPU profile."""

import re

import jax
import pytest

from _bench_tiny import harness  # noqa: F401
from bench import devtrace, stages
from bench.stages import Event, from_planes

MS = 1_000_000


def st(name, t0, t1, **attrs):
    return Event(f"serve.{name}", t0 * MS, (t1 - t0) * MS, attrs)


def op(name, t0, t1):
    return Event(name, int(t0 * MS), int((t1 - t0) * MS))


def host_line():
    return [
        Event("bench.window", 0, 100 * MS),
        st("wait", -5, 10, queued=0),
        st("read", 10, 12),
        st("admit", 12, 40),
        st("dispatch", 14, 38, seq=0, b_pad=16),
        st("pad", 14, 16),
        st("upload", 16, 18),
        st("launch", 18, 19),
        st("fetch", 19, 35),
        st("slice", 35, 37),
        st("encode", 40, 42),
        # 42 .. 50: no stage open
        st("wait", 50, 80, queued=3),
        st("poll", 80, 100),
    ]


def chip0():
    return [op("fusion", 19.5, 25), op("event_synapse.5", 25, 33),
            op("while.1", 33, 34), op("copy.1", 60, 62)]


SCOPES = {16: {"fusion": "jit(fwd)/layer0/mem_e/sort",
               "event_synapse.5": "jit(fwd)/layer0/synapse/pallas_call",
               "while.1": "jit(fwd)/layer0/lif/while",
               "copy.1": "jit(fwd)/layer0/lif/copy"}}


def planes(extra_chip=None):
    out = [("/host:CPU", [("main", host_line()[:1]),
                          ("serving", host_line()[1:])]),
           ("/device:TPU:0", [("XLA Ops", chip0()),
                              ("XLA Modules", [op("jit_fwd", 19.5, 34)])])]
    if extra_chip is not None:
        out.append(("/device:TPU:1", [("XLA Ops", extra_chip)]))
    return out


IDLE_MS = {"wait[empty]": 10, "read": 2, "admit": 4, "dispatch": 1,
           "pad": 2, "upload": 2, "launch": 1, "fetch": 1.5, "slice": 2,
           "encode": 2, "none": 8, "wait[queued]": 28, "poll": 20}


def test_idle_goes_to_the_innermost_stage():
    tr = from_planes(planes(), n_chips=1)
    assert tr.window == (0, 100 * MS)
    idle = tr.idle_by_stage()
    assert set(idle) == set(IDLE_MS)
    for k, ms in IDLE_MS.items():
        assert idle[k] == pytest.approx(ms * 1e-3), k
    assert sum(idle.values()) == pytest.approx(0.1 - 0.0165)


def test_idle_is_averaged_over_the_chips():
    busy_all_window = [op("x", 0, 100)]
    tr = from_planes(planes(busy_all_window), n_chips=2)
    idle = tr.idle_by_stage()
    for k, ms in IDLE_MS.items():
        assert idle[k] == pytest.approx(ms * 1e-3 / 2), k
    # a chip beyond n_chips is left out
    assert from_planes(planes(busy_all_window), n_chips=1) \
        .idle_by_stage() == pytest.approx(
            {k: ms * 1e-3 for k, ms in IDLE_MS.items()})


def test_host_time_counts_and_totals():
    tr = from_planes(planes(), n_chips=1)
    own = tr.self_seconds()
    assert own["admit"] == pytest.approx(0.004)       # 12-14 and 38-40
    assert own["fetch"] == pytest.approx(0.016)
    assert own["wait[empty]"] == pytest.approx(0.010)  # clipped at 0
    assert sum(own.values()) == pytest.approx(0.1)
    counts = tr.counts()
    assert counts["admit"] == counts["dispatch"] == 1
    assert "wait[empty]" not in counts                 # starts before 0
    assert tr.total_s("admit") == pytest.approx(0.028)


def test_scope_seconds():
    tr = from_planes(planes(), n_chips=1, scopes=SCOPES)
    assert tr.scope_seconds(stages.MEM_E) == pytest.approx(0.0055)
    assert tr.scope_seconds(stages.SYNAPSE) == pytest.approx(0.008)
    assert tr.scope_seconds(stages.LIF) == pytest.approx(0.001)
    assert tr.scope_seconds(r"layer1/") == 0
    # copy.1 runs outside every dispatch; a bucket with no scopes scopes
    # nothing
    assert tr.op_scopes(chip0())[3] == ""
    tr.scopes = {4: SCOPES[16]}
    assert tr.scope_seconds(stages.MEM_E) == 0


def test_clock_offsets():
    tr = from_planes(planes(), n_chips=1)
    assert tr.clock_offsets() == [-1 * MS]            # inside 18 .. 35
    late = from_planes(planes(), n_chips=1)
    late.modules = [[op("jit_fwd", 19.5, 35.2), op("jit_fwd", 1, 2)]]
    off = late.clock_offsets()
    assert off[0] == pytest.approx(0.2 * MS)           # ends after fetch
    assert off[1] == 17 * MS                           # starts before launch
    late.stages = [s for s in late.stages if s.name != "serve.fetch"]
    assert late.clock_offsets() == [100 * MS] * 2      # no fetch to match


def test_report_of_the_synthetic_window():
    tr = from_planes(planes(), n_chips=1, scopes=SCOPES)
    dev = devtrace.from_planes(planes(), 1, 0.1)
    rep = stages.report(tr, dev)
    assert rep["idle_pct"] == pytest.approx(83.5)
    assert rep["idle_host_pct"] == pytest.approx(
        2 + 4 + 20 + 2 + 2 + 1 + 1.5 + 2 + 2)
    assert rep["idle_held_pct"] == pytest.approx(28)
    assert rep["upload_ms"] == pytest.approx(2.0)
    assert rep["frontend_us"] == pytest.approx(6000)   # admit 4 + encode 2
    busy = 0.0165
    assert rep["mem_e_share_pct"] == pytest.approx(
        100 * 0.0055 / busy)
    assert rep["unscoped_ops_s"] == [["copy.1", pytest.approx(0.002)]]
    assert rep["clock"] == {"runs": 1, "inside_share": 1.0,
                            "median_us": pytest.approx(-1000),
                            "worst_us": pytest.approx(-1000)}


HLO = """\
HloModule jit_fwd, is_scheduled=true

%fused_computation.5 (param_0: s32[8]) -> s32[8] {
  %param_0 = s32[8]{0} parameter(0)
  ROOT %cumsum.1 = s32[8]{0} add(%param_0, %param_0), metadata={op_type="add" op_name="jit(fwd)/layer0/mem_e/cumsum" stack_frame_id=3}
}

%fused_computation.6 (param_0.1: s32[8]) -> s32[8] {
  %param_0.1 = s32[8]{0} parameter(0)
  %transpose.4 = s32[8]{0} transpose(%param_0.1), dimensions={0}, metadata={op_name="jit(fwd)/layer1/mem_e/broadcast_in_dim"}
  ROOT %scatter.1 = s32[8]{0} scatter(%param_0.1, %transpose.4, %transpose.4), to_apply=%region_1
}

ENTRY %main.9 (Arg_0.1: s32[8]) -> s32[8] {
  %Arg_0.1 = s32[8]{0} parameter(0)
  %fusion = s32[8]{0} fusion(%Arg_0.1), kind=kCustom, calls=%fused_computation.5
  %fusion.1 = s32[8]{0} fusion(%fusion), kind=kCustom, calls=%fused_computation.6
  %copy.2 = s32[8]{0} copy(%fusion.1)
  ROOT %event_synapse.4 = s32[8]{0} custom-call(%copy.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(fwd)/layer0/synapse/jit(event_synapse)/pallas_call" stack_frame_id=7}
}
"""


def test_hlo_scopes_give_a_fusion_its_bodys_scope():
    scopes = stages.hlo_scopes(HLO)
    assert scopes["fusion"] == "jit(fwd)/layer0/mem_e/cumsum"
    # a root with no op_name: the first scoped instruction of the body
    assert scopes["fusion.1"] == "jit(fwd)/layer1/mem_e/broadcast_in_dim"
    assert scopes["event_synapse.4"].startswith("jit(fwd)/layer0/synapse/")
    assert scopes["copy.2"] == "" and scopes["Arg_0.1"] == ""


def test_hlo_scopes_of_the_compiled_forward():
    """The forward compiled on the CPU: each layer's three scopes are
    found."""
    from repro.engine import batched_run as br
    from repro.launch.serve_snn import build_demo_model

    packed = build_demo_model("mlp", smoke=True, seed=0).pack()
    spikes = jax.ShapeDtypeStruct((2, 8, packed.n_in), "float32")
    scopes = stages.hlo_scopes(
        br._forward.lower(packed, spikes, None).compile().as_text())
    for i in range(len(packed.layers)):
        for rx in (stages.MEM_E, stages.SYNAPSE, stages.LIF):
            assert any(re.search(rx, v) and f"layer{i}/" in v
                       for v in scopes.values()), (i, rx)


def test_window_and_stages_from_a_cpu_profile(tmp_path):
    from repro.engine.tracing import stage

    start = jax.profiler.start_trace
    with stages.marked_window():
        jax.profiler.start_trace(str(tmp_path))
        with stage("dispatch", seq=7, b_pad=4):
            with stage("pad"):
                pass
        jax.profiler.stop_trace()
    assert jax.profiler.start_trace is start
    tr = stages.load(str(tmp_path), n_chips=1)
    w0, w1 = tr.window
    assert [s.name for s in tr.stages] == ["serve.dispatch", "serve.pad"]
    assert tr.stages[0].stats["seq"] == 7
    assert all(w0 <= s.start_ns and s.end_ns <= w1 for s in tr.stages)
    assert tr.chips == []
