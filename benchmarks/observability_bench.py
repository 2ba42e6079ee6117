"""Observability-cost benchmark: tracing must be (nearly) free and change
nothing.

Measures what attaching a :class:`repro.engine.tracing.FlightRecorder` to
the serving fabric costs, and proves the observer effect is zero — the
gates behind docs/OBSERVABILITY.md's "strictly passive" claim.  Writes
``BENCH_observability.json``.

  PYTHONPATH=src python benchmarks/observability_bench.py [--smoke] \
      [--out BENCH_observability.json] [--spoof-devices 2]

Gates (CI fails loudly on regression):
  * tracer overhead <= 5% wall time (+20 ms absolute floor for timer
    noise on sub-second smoke runs), min-of-N repeats of the same warmed
    scenario replay with the recorder on vs off;
  * ZERO new jit traces with tracing enabled on warmed buckets;
  * a tracer-on replay is bit-exact with a tracer-off replay (metrics and
    every served spike train);
  * two traced replays produce byte-identical ``dump_json()`` and every
    injected fault lands as a typed anomaly;
  * the socket ADMIN ``metrics`` / ``trace`` verbs round-trip the
    schema-locked snapshot and a span trace over a live TCP connection.
"""

from __future__ import annotations

import argparse
import json
import time

from repro.launch._spoof import (assert_spoof_applied,
                                 spoof_devices_from_argv)

_SPOOFED = spoof_devices_from_argv()  # before any jax import in this process

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.engine import (BucketPolicy, FlightRecorder,  # noqa: E402
                          METRIC_KEYS, SCENARIOS, trace_count,
                          run_scenario)
from repro.engine.sharded_run import snn_serve_mesh  # noqa: E402
from repro.launch.serve_snn import build_demo_model  # noqa: E402

# overhead gate: tracing may cost at most 5% of the untraced wall time,
# with a small absolute floor so sub-second smoke runs don't gate on
# scheduler jitter
OVERHEAD_REL = 0.05
OVERHEAD_ABS_S = 0.02

# scenarios exercised; all run on a single device so the bench works on
# any host (device-loss scenarios live in soak_bench)
_SCENARIOS = ("baseline", "adversarial", "slo_shed", "analog_noise",
              "multi_tenant")


def _time_replays(packed, sc, *, recorder_factory, repeats: int) -> float:
    """Min wall seconds over ``repeats`` replays of one scenario (min, not
    mean: the quantity under test is deterministic work, so the minimum is
    the least-noise estimate)."""
    best = float("inf")
    for _ in range(repeats):
        rec = recorder_factory()
        t0 = time.perf_counter()
        run_scenario(packed, sc, recorder=rec)
        best = min(best, time.perf_counter() - t0)
    return best


def bench_overhead(packed, *, smoke: bool) -> dict:
    """The ≤5% gate: warmed scenario replays with the recorder on vs
    off."""
    sc = SCENARIOS["adversarial"]
    repeats = 3 if smoke else 5
    run_scenario(packed, sc)     # warm every bucket (compiles excluded)
    off_s = _time_replays(packed, sc, recorder_factory=lambda: None,
                          repeats=repeats)
    on_s = _time_replays(packed, sc, recorder_factory=FlightRecorder,
                         repeats=repeats)
    budget = off_s * (1.0 + OVERHEAD_REL) + OVERHEAD_ABS_S
    assert on_s <= budget, \
        f"tracing overhead gate: {on_s:.3f}s traced vs {off_s:.3f}s " \
        f"untraced (budget {budget:.3f}s)"
    overhead = on_s / off_s - 1.0 if off_s > 0 else 0.0
    print(f"observability/overhead: off {off_s*1e3:.0f} ms | on "
          f"{on_s*1e3:.0f} ms | {overhead*100:+.1f}% (gate "
          f"{OVERHEAD_REL*100:.0f}% + {OVERHEAD_ABS_S*1e3:.0f} ms)")
    return {"scenario": sc.name, "repeats": repeats, "untraced_s": off_s,
            "traced_s": on_s, "overhead_frac": overhead}


def bench_zero_observer_effect(packed) -> list[dict]:
    """Bit-exactness, replay determinism, anomaly typing, and the
    zero-retrace gate, per scenario."""
    rows = []
    for name in _SCENARIOS:
        sc = SCENARIOS[name]
        run_scenario(packed, sc)            # warm this scenario's buckets
        n0 = trace_count()
        rec1, rec2 = FlightRecorder(), FlightRecorder()
        res1, rids1, m1 = run_scenario(packed, sc, recorder=rec1)
        _, _, m2 = run_scenario(packed, sc, recorder=rec2)
        assert trace_count() == n0, \
            f"{name}: tracing added jit traces on warmed buckets"
        assert m1 == m2 and rec1.dump_json() == rec2.dump_json(), \
            f"{name}: traced replay is not deterministic"
        res0, rids0, m0 = run_scenario(packed, sc)   # tracer off
        assert m0 == m1 and rids0 == rids1, \
            f"{name}: tracing changed the served metrics"
        for rid in res0:
            assert np.array_equal(res0[rid].out_spikes,
                                  res1[rid].out_spikes), \
                f"{name}: tracing changed served bits (rid {rid})"
        n_anom = sum(rec1.anomaly_counts.values())
        print(f"observability/{name}: {m1['completed']} served | "
              f"{n_anom} anomalies "
              f"{dict(sorted(rec1.anomaly_counts.items()))} | dump "
              f"{len(rec1.dump_json())} bytes")
        rows.append({"scenario": name, "completed": m1["completed"],
                     "anomalies": dict(sorted(rec1.anomaly_counts.items())),
                     "dump_bytes": len(rec1.dump_json())})
    return rows


def bench_wire_roundtrip(packed) -> dict:
    """ADMIN ``metrics`` and ``trace`` over a live socket: the CI smoke
    job's liveness check for the wire-exported observability surface."""
    from repro.launch.socket_serve import (SpikeClient, SpikeSocketServer,
                                           serving_thread)
    rng = np.random.default_rng(0)
    srv = SpikeSocketServer(
        packed, policy=BucketPolicy(batch_sizes=(2,), time_steps=(8,)))
    host, port = srv.address
    with serving_thread(srv, idle_flush_s=0.05):
        cli = SpikeClient(host, port)
        for _ in range(4):
            cli.send((rng.random((6, packed.n_in)) < 0.2)
                     .astype(np.float32))
        cli.recv_all()
        met = cli.admin({"op": "metrics"})
        last = cli.admin({"op": "trace", "last": True})
        dump = cli.admin({"op": "trace"})
        cli.recv_all()
        cli.close()
    mrep = cli.admin_replies[met]
    assert mrep.get("ok") and set(mrep["metrics"]) == set(METRIC_KEYS), \
        "ADMIN metrics reply is not schema-locked"
    assert mrep["metrics"]["completed"] == 4
    trep = cli.admin_replies[last]
    assert trep.get("ok") and trep["trace"]["completed"], \
        "ADMIN trace last did not return a completed trace"
    kinds = [sp["kind"] for sp in trep["trace"]["spans"]]
    assert kinds[0] == "admit" and "dispatch" in kinds
    drep = cli.admin_replies[dump]
    assert drep.get("ok") and drep["dump"]["n_completed"] == 4
    print(f"observability/wire: metrics({len(mrep['metrics'])} keys) + "
          f"trace({len(kinds)} spans) + dump round-tripped")
    return {"served": 4, "metric_keys": len(mrep["metrics"]),
            "trace_spans": len(kinds),
            "dump_completed": drep["dump"]["n_completed"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_observability.json")
    ap.add_argument("--model", default="mlp", choices=["mlp", "conv"])
    ap.add_argument("--spoof-devices", type=int, default=None)
    args = ap.parse_args()
    assert_spoof_applied(_SPOOFED)
    snn_serve_mesh(None)    # parity with sibling benches on spoofed hosts
    packed = build_demo_model(args.model, smoke=args.smoke).pack()
    scenarios = bench_zero_observer_effect(packed)
    overhead = bench_overhead(packed, smoke=args.smoke)
    wire = bench_wire_roundtrip(packed)
    blob = {"bench": "observability", "smoke": args.smoke,
            "model": args.model, "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "overhead_gate_rel": OVERHEAD_REL,
            "overhead_gate_abs_s": OVERHEAD_ABS_S,
            "overhead": overhead, "scenarios": scenarios, "wire": wire}
    with open(args.out, "w") as f:
        json.dump(blob, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
