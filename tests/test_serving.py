"""Continuous-batching front end: bucket policy, scheduler, and the
per-request bit-exactness + jit-cache-stability contracts.

The two serving guarantees under test (engine/serving.py):

  * every request's result — spikes, per-step DispatchStats, utilization,
    overflow, energy — is bit-identical to running that request alone on
    the numpy oracle, despite batch/time padding; and
  * a stream of mixed-shape requests costs at most ``policy.n_buckets``
    jit traces (the cache-churn regression), and a second stream hitting
    the same buckets costs zero.
"""

import numpy as np
import pytest

from repro.core.accelerator import map_model, run
from repro.core.energy import AcceleratorSpec
from repro.core.layers import Conv2d, Dense, SumPool2d
from repro.core.lif import LIFParams
from repro.engine import (METRIC_KEYS, BucketPolicy, FlightRecorder,
                          OverlongRequestError, ServerMetrics, StreamServer,
                          TELEMETRY_KEYS, VirtualClock, plan_batches,
                          run_bucketed, trace_count)
from repro.engine.serving import BatchPlan, loop_fill

SPEC = AcceleratorSpec("serve-test", n_cores=3, n_engines=4, n_caps=8,
                       weight_mem_bytes=1 << 18)


def _dense_model(rng, sizes=(14, 12, 6), density=0.6):
    ws = []
    for i in range(len(sizes) - 1):
        w = rng.normal(0, 0.5, (sizes[i], sizes[i + 1])).astype(np.float32)
        w[rng.random(w.shape) > density] = 0
        ws.append(w)
    return map_model(ws, SPEC, lif=LIFParams(beta=0.8, threshold=0.7))


def _conv_model(rng):
    k = rng.normal(0, 0.8, (2, 1, 3, 3)).astype(np.float32)
    k[rng.random(k.shape) > 0.6] = 0
    conv = Conv2d(kernel=k, in_shape=(1, 6, 6), stride=1, padding=1)
    pool = SumPool2d(conv.out_shape, 2)
    head = rng.normal(0, 0.5, (int(np.prod(pool.out_shape)), 5)) \
        .astype(np.float32)
    return map_model([conv, pool, Dense(w=head)], SPEC,
                     lif=LIFParams(beta=0.8, threshold=0.7))


def _streams(rng, n_in, lengths, p=0.35):
    return [(rng.random((t, n_in)) < p).astype(np.float32) for t in lengths]


def _assert_request_matches_oracle(req, model, stream, max_events=None):
    oracle = run(model, stream, max_events=max_events)
    np.testing.assert_array_equal(req.out_spikes, oracle.out_spikes)
    for li, (a, b) in enumerate(zip(req.stats, oracle.per_layer_stats)):
        for f in ("cycles", "rows_touched", "engine_ops", "events",
                  "sn_bytes_touched"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f),
                                          err_msg=f"layer {li} {f}")
        assert a.mem_e_peak == b.mem_e_peak, f"layer {li} mem_e_peak"
        np.testing.assert_array_equal(req.util[li],
                                      oracle.per_layer_util[li])
        np.testing.assert_array_equal(req.overflow[li], oracle.overflow[li])
    assert req.energy() == oracle.energy


# ------------------------------------------------------------------ policy

def test_policy_bucket_selection():
    p = BucketPolicy(batch_sizes=(1, 4, 16), time_steps=(8, 16, 32))
    assert p.t_bucket(1) == 8 and p.t_bucket(8) == 8 and p.t_bucket(9) == 16
    assert p.t_bucket(32) == 32
    with pytest.raises(ValueError, match="exceeds the largest time bucket"):
        p.t_bucket(33)
    assert p.b_bucket(1) == 1 and p.b_bucket(2) == 4 and p.b_bucket(16) == 16
    assert p.max_batch == 16 and p.n_buckets == 9


def test_policy_validation():
    with pytest.raises(AssertionError):
        BucketPolicy(batch_sizes=(4, 1), time_steps=(8,))
    with pytest.raises(AssertionError):
        BucketPolicy(batch_sizes=(1,), time_steps=())


def test_policy_for_mesh_divisibility():
    p = BucketPolicy.for_mesh(3, batch_sizes=(1, 4, 16))
    assert all(b % 3 == 0 for b in p.batch_sizes)


def test_policy_covering():
    p = BucketPolicy.covering([3, 17, 9], n_shards=2, max_batch=8)
    assert p.time_steps[-1] >= 17
    assert all(b % 2 == 0 for b in p.batch_sizes)
    assert p.max_batch >= 8


def test_policy_fits_and_extension():
    p = BucketPolicy(batch_sizes=(1, 4), time_steps=(8, 16))
    assert p.fits(16) and not p.fits(17) and not p.fits(0)
    assert p.with_time_bucket(12) is p            # already covered
    q = p.with_time_bucket(40)                    # 16 -> 32 -> 64
    assert q.time_steps == (8, 16, 64) and q.fits(40)
    assert p.time_steps == (8, 16)                # original untouched


# --------------------------------------------------------------- scheduler

def test_plan_batches_partitions_all_requests():
    policy = BucketPolicy(batch_sizes=(1, 2, 4), time_steps=(4, 8))
    lengths = [3, 7, 5, 8, 2, 8, 1, 4, 6, 8, 8]
    plans = plan_batches(lengths, policy)
    seen = [i for p in plans for i in p.indices]
    assert sorted(seen) == list(range(len(lengths)))
    for p in plans:
        assert p.b_pad in policy.batch_sizes and p.t_pad in policy.time_steps
        assert len(p.indices) <= p.b_pad
        for i in p.indices:
            assert lengths[i] <= p.t_pad


def test_plan_batches_chunks_at_max_batch():
    policy = BucketPolicy(batch_sizes=(2,), time_steps=(8,))
    plans = plan_batches([5] * 7, policy)
    assert [len(p.indices) for p in plans] == [2, 2, 2, 1]
    assert all(p.b_pad == 2 for p in plans)


def test_plan_batches_deterministic():
    policy = BucketPolicy(batch_sizes=(1, 4), time_steps=(4, 16))
    lengths = [10, 2, 16, 4, 9, 1]
    assert plan_batches(lengths, policy) == plan_batches(lengths, policy)
    assert plan_batches(lengths, policy)[0] == BatchPlan(
        indices=(1, 3, 5), b_pad=4, t_pad=4)


# -------------------------------------------------- per-request equivalence

def test_bucketed_matches_oracle_dense(rng):
    model = _dense_model(rng)
    streams = _streams(rng, 14, [3, 7, 5, 8, 2, 8, 1])
    res = run_bucketed(model, streams,
                       policy=BucketPolicy(batch_sizes=(1, 2, 4),
                                           time_steps=(4, 8)))
    for req, s in zip(res, streams):
        _assert_request_matches_oracle(req, model, s)


def test_bucketed_matches_oracle_conv(rng):
    model = _conv_model(rng)
    n_in = model.layers[0].n_src
    streams = _streams(rng, n_in, [2, 6, 4, 5], p=0.25)
    res = run_bucketed(model, streams,
                       policy=BucketPolicy(batch_sizes=(2, 4),
                                           time_steps=(4, 8)))
    for req, s in zip(res, streams):
        _assert_request_matches_oracle(req, model, s)


def test_bucketed_max_events_cap(rng):
    """The MEM_E cap threads through padding: overflow counts and truncated
    downstream spikes still match the oracle under the same cap."""
    model = _dense_model(rng, density=0.9)
    streams = _streams(rng, 14, [3, 6, 5], p=0.7)
    res = run_bucketed(model, streams, max_events=2,
                       policy=BucketPolicy(batch_sizes=(4,), time_steps=(8,)))
    for req, s in zip(res, streams):
        _assert_request_matches_oracle(req, model, s, max_events=2)
        assert sum(o.sum() for o in req.overflow) > 0


def test_bucketed_empty_and_single(rng):
    model = _dense_model(rng)
    assert run_bucketed(model, []) == []
    streams = _streams(rng, 14, [5])
    req = run_bucketed(model, streams)[0]
    _assert_request_matches_oracle(req, model, streams[0])


def test_bucketed_without_stats(rng):
    model = _dense_model(rng)
    streams = _streams(rng, 14, [4, 9])
    res = run_bucketed(model, streams, with_stats=False,
                       policy=BucketPolicy(batch_sizes=(2,),
                                           time_steps=(4, 16)))
    for req, s in zip(res, streams):
        assert req.stats == [] and req.util == []
        np.testing.assert_array_equal(req.out_spikes,
                                      run(model, s).out_spikes)


def test_bucketed_telemetry(rng):
    model = _dense_model(rng)
    streams = _streams(rng, 14, [4, 9, 3])
    telemetry = []
    run_bucketed(model, streams, telemetry=telemetry,
                 policy=BucketPolicy(batch_sizes=(2,), time_steps=(4, 16)))
    assert len(telemetry) == 2
    assert sum(t["n_requests"] for t in telemetry) == 3
    assert sum(t["events"] for t in telemetry) \
        == int(sum((s > 0).sum() for s in streams))


@pytest.mark.parametrize("max_events", [None, 3], ids=["full_depth", "cap3"])
def test_telemetry_loop_fill(rng, max_events):
    """``loop_fill`` is layer 0's walked MEM_E slots over the full walk:
    each row stops at its last event, so it is the events the depth admits
    over ``b_pad * t_pad * depth`` — at full depth exactly ``events`` over
    that — and lies in [0, 1]; the served server carries it on the record
    and on its dispatch and per-layer ``hw`` spans."""
    model = _dense_model(rng)
    streams = _streams(rng, 14, [4, 9, 3])
    policy = BucketPolicy(batch_sizes=(2,), time_steps=(4, 16))
    telemetry = []
    run_bucketed(model, streams, telemetry=telemetry, policy=policy,
                 max_events=max_events)
    depth = 14 if max_events is None else max_events
    for t in telemetry:
        slots = t["b_pad"] * t["t_pad"] * depth
        assert 0.0 < t["loop_fill"] <= 1.0
        if max_events is None:
            assert t["loop_fill"] == t["events"] / slots
        else:
            assert t["loop_fill"] < t["events"] / slots   # rows overflow
    rec = FlightRecorder()
    server = StreamServer(model, clock=VirtualClock(), policy=policy,
                          max_events=max_events, with_stats=True,
                          tracer=rec)
    rid = server.submit(streams[0])
    server.flush()
    (record,) = server.telemetry
    assert record["loop_fill"] == loop_fill(
        (streams[0] > 0).sum(axis=1), depth,
        record["b_pad"] * record["t_pad"])
    spans = rec.trace(rid).spans
    dispatch = next(sp for sp in spans if sp.kind == "dispatch")
    assert dispatch.attrs["loop_fill"] == record["loop_fill"]
    hw = [sp.attrs for sp in spans if sp.kind == "hw"]
    assert hw[0]["loop_fill"] == record["loop_fill"]
    assert all(0.0 <= h["loop_fill"] <= 1.0 for h in hw)


# -------------------------------------------------- metrics schema locks

def test_telemetry_schema_locked(rng):
    """The per-engine-call telemetry record keys are a dashboard contract
    (BENCH_serving.json): adding/renaming fields must update TELEMETRY_KEYS
    and this test together.  ``seq``/``ts`` make records shared through one
    ``telemetry=`` list self-ordering across dispatch rounds."""
    assert TELEMETRY_KEYS == ("seq", "ts", "b_pad", "t_pad", "n_requests",
                              "events", "loop_fill", "out_spikes", "seconds")
    model = _dense_model(rng)
    telemetry = []
    run_bucketed(model, _streams(rng, 14, [4, 9]), telemetry=telemetry,
                 policy=BucketPolicy(batch_sizes=(2,), time_steps=(4, 16)))
    for t in telemetry:
        assert tuple(t.keys()) == TELEMETRY_KEYS
    # per-call monotonic ordinals
    assert [t["seq"] for t in telemetry] == list(range(len(telemetry)))
    # the async server emits the same records, stamped with its clock
    server = StreamServer(model, clock=VirtualClock(),
                          policy=BucketPolicy(batch_sizes=(2,),
                                              time_steps=(4, 16)))
    server.submit(_streams(rng, 14, [4])[0])
    server.flush()
    rec = server.telemetry[0]
    assert tuple(rec.keys()) == TELEMETRY_KEYS
    assert rec["seq"] == 0 and rec["ts"] == 0.0  # VirtualClock dispatch time


def test_server_metrics_schema_locked():
    """ServerMetrics.snapshot() keys are the BENCH_async_serving.json
    surface — locked so dashboards don't silently break.  ``p50/p99_*``
    come from lifetime cumulative histograms; the windowed deque values
    survive under the explicit ``recent_*`` keys."""
    assert METRIC_KEYS == (
        "submitted", "admitted", "rejected", "shed", "completed",
        "deadline_misses", "deadline_miss_rate", "dispatches",
        "forced_dispatches", "policy_extensions", "queue_depth",
        "max_queue_depth", "bucket_fill_ratio", "p50_ttfd_s", "p99_ttfd_s",
        "p50_latency_s", "p99_latency_s", "recent_p50_ttfd_s",
        "recent_p99_ttfd_s", "recent_p50_latency_s", "recent_p99_latency_s",
        "device_losses", "slo_switches", "slo_shedding", "noise_probes",
        "noise_agreement", "models", "hot_swaps", "per_model")
    snap = ServerMetrics().snapshot()
    assert tuple(snap.keys()) == METRIC_KEYS
    assert snap["deadline_miss_rate"] == 0.0      # no div-by-zero when idle
    assert snap["noise_agreement"] == 1.0         # no probes = no evidence
    assert snap["per_model"] == {} and snap["models"] == 0
    assert snap["p50_latency_s"] == 0.0 and snap["recent_p99_ttfd_s"] == 0.0


# ------------------------------------------------- over-long requests

def test_bucketed_overlong_error_names_requests(rng):
    """An over-long request fails at admission with a per-request error,
    not mid-plan after other requests already ran."""
    model = _dense_model(rng)
    streams = _streams(rng, 14, [4, 40, 3, 99])
    policy = BucketPolicy(batch_sizes=(2,), time_steps=(4, 8))
    with pytest.raises(OverlongRequestError) as ei:
        run_bucketed(model, streams, policy=policy)
    assert ei.value.requests == [(1, 40), (3, 99)]
    assert "request 1: 40 steps" in str(ei.value)


def test_bucketed_overlong_extend_matches_oracle(rng):
    model = _dense_model(rng)
    streams = _streams(rng, 14, [4, 40, 3])
    policy = BucketPolicy(batch_sizes=(2,), time_steps=(4, 8))
    res = run_bucketed(model, streams, policy=policy, overlong="extend")
    for req, s in zip(res, streams):
        _assert_request_matches_oracle(req, model, s)


# ------------------------------------------------- jit-cache churn (bugfix)

def test_mixed_shape_stream_bounded_traces(rng):
    """The regression the bucketing layer fixes: a stream of requests with
    many distinct (B, T) shapes must cost at most n_buckets traces, and a
    second mixed stream hitting the same buckets must cost zero."""
    model = _dense_model(rng)
    packed = model.pack()
    policy = BucketPolicy(batch_sizes=(2, 4), time_steps=(4, 8, 16))
    lengths_a = [1, 2, 3, 5, 7, 9, 11, 13, 15, 16, 4, 8]
    lengths_b = [16, 1, 6, 10, 2, 12, 3, 14]
    assert len(set(lengths_a)) > policy.n_buckets // 2   # genuinely mixed
    n0 = trace_count()
    run_bucketed(packed, _streams(rng, 14, lengths_a), policy=policy)
    run_bucketed(packed, _streams(rng, 14, lengths_b), policy=policy)
    total = trace_count() - n0
    assert 0 < total <= policy.n_buckets, \
        f"{total} traces for {len(lengths_a) + len(lengths_b)} " \
        f"mixed-shape requests > {policy.n_buckets} buckets"
    n1 = trace_count()
    run_bucketed(packed, _streams(rng, 14, lengths_b), policy=policy)
    run_bucketed(packed, _streams(rng, 14, lengths_a), policy=policy)
    assert trace_count() == n1, "repeat streams retraced the jit"


def test_request_shape_validation(rng):
    model = _dense_model(rng)
    with pytest.raises(AssertionError, match="expected \\[T, 14\\]"):
        run_bucketed(model, [np.zeros((4, 9), np.float32)])
