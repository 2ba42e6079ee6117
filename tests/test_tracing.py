"""Span tracer + flight recorder: schema locks, replay determinism, and
zero observer effect.

The observability layer's contracts (ISSUE 9 / docs/OBSERVABILITY.md):

  * the span/anomaly/histogram taxonomies are schema-locked tuples, like
    METRIC_KEYS — dashboards parse dumps by these names;
  * every request's life is covered by typed spans in lifecycle order,
    timestamped off the *server's* clock;
  * two VirtualClock replays of the same chaos scenario produce
    byte-identical ``FlightRecorder.dump_json()`` output, and every
    injected fault lands as a typed anomaly;
  * tracing changes nothing it observes: a tracer-on run is bit-exact
    (results and metrics) with a tracer-off run, and adds zero jit traces;
  * the socket ADMIN ``metrics`` / ``trace`` verbs round-trip the
    schema-locked snapshot and the recorder over a live connection.
"""

import json
import math

import numpy as np
import pytest

from repro.engine import (ANOMALY_KINDS, HIST_KEYS, METRIC_KEYS, SCENARIOS,
                          SPAN_KINDS, BucketPolicy, FlightRecorder,
                          Histogram, ServerMetrics, StreamServer,
                          VirtualClock, run_scenario, trace_count)
from repro.engine.tracing import RATIO_EDGES, TIME_EDGES
from repro.launch.serve_snn import build_demo_model


@pytest.fixture(scope="module")
def packed():
    return build_demo_model("mlp", smoke=True, seed=0).pack()


def _stream(packed, t=6, seed=0, p=0.2):
    rng = np.random.default_rng(seed)
    return (rng.random((t, packed.n_in)) < p).astype(np.float32)


def _server(packed, recorder, **kw):
    kw.setdefault("policy", BucketPolicy(batch_sizes=(2,), time_steps=(8,)))
    kw.setdefault("clock", VirtualClock())
    kw.setdefault("service_model", lambda b, t: 0.001)
    return StreamServer(packed, tracer=recorder, **kw)


# ------------------------------------------------------------ schema locks

def test_span_and_anomaly_schemas_locked():
    """The taxonomy tuples are a dashboard contract, locked here and in
    docs/OBSERVABILITY.md (tests/test_docs.py)."""
    assert SPAN_KINDS == ("admit", "queue", "schedule", "pad", "dispatch",
                          "slice", "hw", "complete")
    assert ANOMALY_KINDS == ("reject", "shed", "policy_extension",
                             "deadline_miss", "device_loss", "hot_swap_pin",
                             "noise_disagreement")
    assert HIST_KEYS == ("ttfd_s", "service_s", "latency_s", "fill")
    rec = FlightRecorder()
    assert tuple(rec.hist) == HIST_KEYS
    with pytest.raises(AssertionError):
        rec.anomaly("not_a_kind", t=0.0)


# -------------------------------------------------------------- histograms

def test_histogram_percentiles_deterministic():
    h = Histogram(TIME_EDGES)
    assert h.percentile(50) == 0.0 and h.n == 0
    for v in (0.001, 0.002, 0.002, 0.004, 10.0):
        h.add(v)
    assert h.n == 5 and h.mean == pytest.approx(np.mean(
        [0.001, 0.002, 0.002, 0.004, 10.0]))
    # the percentile is the upper edge of the sample's bucket: an upper
    # bound within one bucket width (8 buckets/decade -> ~33%)
    for q, v in ((10, 0.001), (50, 0.002), (90, 10.0)):
        p = h.percentile(q)
        assert v <= p <= v * 10 ** (1 / 8) * (1 + 1e-9), (q, v, p)
    # overflow clamps to the last edge instead of emitting inf
    h2 = Histogram(TIME_EDGES)
    h2.add(1e6)
    assert h2.percentile(99) == TIME_EDGES[-1]
    # identical sample streams -> identical serialized histograms
    a, b = Histogram(RATIO_EDGES), Histogram(RATIO_EDGES)
    for v in (0.1, 0.5, 0.5, 1.0):
        a.add(v)
        b.add(v)
    assert json.dumps(a.to_dict()) == json.dumps(b.to_dict())


def test_server_metrics_percentiles_from_histograms():
    """Satellite: p50/p99 survive beyond the bounded window.  A value seen
    once, long ago, still shapes the lifetime percentile but not the
    recent_* one."""
    m = ServerMetrics()
    m.observe_latency(5.0)                 # the early outlier
    for _ in range(m.latency_s.maxlen):    # ...pushed out of the window
        m.observe_latency(0.001)
    snap = m.snapshot()
    assert 5.0 not in m.latency_s
    assert snap["recent_p99_latency_s"] < 0.01      # window forgot it
    assert snap["p50_latency_s"] < 0.01             # median unaffected
    assert m.latency_hist.n == m.latency_s.maxlen + 1


# ------------------------------------------------------------ span lifecycle

def test_trace_covers_request_lifecycle(packed):
    rec = FlightRecorder()
    srv = _server(packed, rec, with_stats=True)
    rid0 = srv.submit(_stream(packed, seed=1))
    rid1 = srv.submit(_stream(packed, seed=2))   # fills the 2-bucket
    assert len(srv.collect()) == 2
    tr = rec.trace(rid0)
    assert tr is not None and tr.completed and rec.last().rid == rid1
    kinds = [sp.kind for sp in tr.spans]
    # lifecycle order, with per-layer hw sub-spans between slice и complete
    assert kinds[:6] == ["admit", "queue", "schedule", "pad", "dispatch",
                         "slice"]
    assert kinds[-1] == "complete" and "hw" in kinds
    assert all(k in SPAN_KINDS for k in kinds)
    for sp in tr.spans:
        assert sp.t1 >= sp.t0
    dispatch = next(sp for sp in tr.spans if sp.kind == "dispatch")
    # the deterministic union of the telemetry record (seconds excluded)
    for k in ("seq", "b_pad", "t_pad", "n_requests", "events",
              "loop_fill", "out_spikes", "model", "generation"):
        assert k in dispatch.attrs, k
    assert "seconds" not in dispatch.attrs
    # with_stats=True: per-layer hardware roll-up + energy attribution
    hw = next(sp for sp in tr.spans if sp.kind == "hw")
    assert hw.attrs["engine_ops"] > 0 and 0 <= hw.attrs["util_mean"] <= 1
    assert dispatch.attrs["energy_j"] > 0
    # histograms saw the dispatch
    assert rec.hist["latency_s"].n == 2 and rec.hist["fill"].n == 1
    # dump is valid sorted-keys json
    d = json.loads(rec.dump_json())
    assert d["n_completed"] == 2 and len(d["completed"]) == 2


def test_schedule_span_says_why(packed):
    """The scheduler's *why*: a full bucket vs a deadline-forced partial
    dispatch are distinguishable from the trace alone."""
    rec = FlightRecorder()
    srv = _server(packed, rec)
    srv.submit(_stream(packed, seed=1))
    srv.submit(_stream(packed, seed=2))          # full bucket
    t0 = srv.now()                               # clock moved past service
    rid = srv.submit(_stream(packed, seed=3), slack=0.05)
    srv.clock.advance(0.06)
    srv.poll()                                    # deadline fires
    full = next(sp for sp in rec.trace(0).spans if sp.kind == "schedule")
    forced = next(sp for sp in rec.trace(rid).spans if sp.kind == "schedule")
    assert full.attrs["why"] == "full_bucket"
    assert forced.attrs["why"] == "deadline"
    assert forced.attrs["group_deadline"] == pytest.approx(t0 + 0.05)


def test_anomalies_reject_shed_miss_extension(packed):
    rec = FlightRecorder()
    srv = _server(packed, rec, queue_capacity=1,
                  backpressure="shed_oldest", overlong="extend",
                  default_slack=0.0005)           # everything misses
    rid0 = srv.submit(_stream(packed, seed=1))
    srv.submit(_stream(packed, t=12, seed=2))     # sheds rid0, extends grid
    srv.flush()
    c = rec.anomaly_counts
    assert c["shed"] == 1 and c["policy_extension"] == 1
    assert c["deadline_miss"] == 1
    # the shed trace is aborted into the anomalous ring, never completed
    tr = rec.trace(rid0)
    assert not tr.completed and tr.anomalies[0]["kind"] == "shed"
    assert any(t.rid == rid0 for t in rec.anomalous)
    # pre-admission rejection -> server-level event (no rid to attach to)
    srv2 = _server(packed, FlightRecorder(), overlong="reject")
    srv2.submit(_stream(packed, t=99, seed=3))
    ev = srv2.tracer.events[-1]
    assert ev["kind"] == "reject" and ev["rid"] is None
    assert srv2.tracer.anomaly_counts["reject"] == 1


# --------------------------------------------------- determinism contracts

@pytest.mark.parametrize("name", ["slo_shed", "analog_noise", "multi_tenant"])
def test_scenario_replays_byte_identical(packed, name):
    """Tentpole acceptance: same scenario, same VirtualClock -> the flight
    recorder dumps are byte-identical, and the injected faults all appear
    as typed anomalies matching the metrics."""
    sc = SCENARIOS[name]
    rec1, rec2 = FlightRecorder(), FlightRecorder()
    _, _, m1 = run_scenario(packed, sc, recorder=rec1)
    _, _, m2 = run_scenario(packed, sc, recorder=rec2)
    assert m1 == m2
    assert rec1.dump_json() == rec2.dump_json()
    c = rec1.anomaly_counts
    assert c.get("deadline_miss", 0) == m1["deadline_misses"]
    assert c.get("shed", 0) == m1["shed"]
    assert c.get("reject", 0) == m1["rejected"]
    assert c.get("hot_swap_pin", 0) == m1["hot_swaps"]
    exp_flips = m1["noise_probes"] - round(m1["noise_agreement"]
                                           * m1["noise_probes"])
    assert c.get("noise_disagreement", 0) == exp_flips


def test_tracer_off_is_bit_exact(packed):
    """Observer effect = zero: tracing must not change a single served bit
    or metric."""
    sc = SCENARIOS["adversarial"]
    res_on, rids_on, m_on = run_scenario(packed, sc,
                                         recorder=FlightRecorder())
    res_off, rids_off, m_off = run_scenario(packed, sc)
    assert m_on == m_off and rids_on == rids_off
    assert set(res_on) == set(res_off)
    for rid in res_off:
        assert np.array_equal(res_on[rid].out_spikes,
                              res_off[rid].out_spikes)


def test_tracing_adds_no_jit_traces(packed):
    """Spanning every request must not perturb the jit cache: a warm
    bucket stays warm under tracing."""
    warm = _server(packed, None)
    warm.submit(_stream(packed, seed=1))
    warm.flush()                                  # compile the (2, 8) bucket
    rec = FlightRecorder()
    n0 = trace_count()
    srv = _server(packed, rec)
    srv.submit(_stream(packed, seed=2))
    srv.submit(_stream(packed, seed=3))
    srv.collect()
    assert trace_count() == n0, "tracing must not retrace warm buckets"


# --------------------------------------------------------- wire round-trip

def test_socket_admin_metrics_and_trace(packed):
    """ADMIN `metrics` returns the schema-locked snapshot and `trace
    <rid>|last` returns span traces over a live socket."""
    from repro.launch.socket_serve import (SpikeClient, SpikeSocketServer,
                                           serving_thread)
    srv = SpikeSocketServer(
        packed, policy=BucketPolicy(batch_sizes=(2,), time_steps=(8,)))
    host, port = srv.address
    with serving_thread(srv, idle_flush_s=0.05):
        cli = SpikeClient(host, port)
        for seed in range(4):
            cli.send(_stream(packed, seed=seed))
        cli.recv_all()                  # all results in -> traces completed
        assert len(cli.results) == 4
        met = cli.admin({"op": "metrics"})
        last = cli.admin({"op": "trace", "last": True})
        dump = cli.admin({"op": "trace"})
        bad = cli.admin({"op": "trace", "rid": 10 ** 9})
        cli.recv_all()
        cli.close()
    mrep = cli.admin_replies[met]
    # json sorts keys on the wire: same key *set*, values by name
    assert mrep["ok"] and set(mrep["metrics"]) == set(METRIC_KEYS)
    assert mrep["metrics"]["completed"] == 4
    trep = cli.admin_replies[last]
    assert trep["ok"] and trep["trace"]["completed"]
    kinds = [sp["kind"] for sp in trep["trace"]["spans"]]
    assert "dispatch" in kinds and kinds[0] == "admit"
    drep = cli.admin_replies[dump]
    assert drep["ok"] and drep["dump"]["n_completed"] == 4
    assert not cli.admin_replies[bad]["ok"]
    assert "no trace for rid" in cli.admin_replies[bad]["error"]


# ----------------------------------------------------------- recorder edges

def test_recorder_rings_bounded_and_late_anomalies():
    rec = FlightRecorder(keep_completed=2, keep_anomalous=4)
    for rid in range(5):
        rec.start(rid, model="m", generation=1, t=0.0)
        rec.complete(rid, 1.0)
    assert [t.rid for t in rec.completed] == [3, 4]   # ring keeps last 2
    assert rec.n_started == rec.n_completed == 5
    # a late anomaly (noise probe after completion) promotes the trace
    # into the anomalous ring exactly once
    rec.anomaly("noise_disagreement", t=2.0, rid=4)
    rec.anomaly("noise_disagreement", t=2.5, rid=4)
    assert [t.rid for t in rec.anomalous] == [4]
    assert len(rec.trace(4).anomalies) == 2
    # unknown rids are no-ops, not crashes, and land as server events
    rec.span(999, "queue", 0.0, 1.0)
    rec.complete(999, 1.0)
    rec.anomaly("deadline_miss", t=3.0, rid=999)
    assert rec.events[-1]["rid"] == 999
    assert math.isfinite(json.loads(rec.dump_json())["anomaly_counts"]
                         ["noise_disagreement"])
