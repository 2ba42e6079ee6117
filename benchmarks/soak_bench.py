"""Soak-and-chaos benchmark for the always-on serving stack.

Two halves, one JSON (``BENCH_soak.json``), both driven by the same
scenario scripts (:mod:`repro.engine.chaos`):

  * **Deterministic scenario replays** — every named scenario (diurnal and
    adversarial arrivals, device loss mid-serving, serving-time analog
    noise, SLO shed-vs-extend switching, the combined blackout) runs twice
    on a VirtualClock and must produce *identical* metrics: the replay
    determinism the tier-1 suite locks, re-checked here on the benchmark
    topology.
  * **Live socket soak** — a real client paces an adversarial arrival
    trace over TCP (:mod:`repro.launch.socket_serve`, the ingest protocol)
    into a WallClock server configured with serving-time analog noise and
    a scripted device loss.  The server must answer *every* request
    (result or reasoned rejection), recover onto the shrunken mesh, keep
    probing accuracy-under-noise, and stay bit-exact against the
    single-device engine.

  PYTHONPATH=src python benchmarks/soak_bench.py [--smoke] \
      [--out BENCH_soak.json] [--spoof-devices 2]

Gates (CI fails loudly on regression):
  * every scenario replay is deterministic (two runs, identical metrics
    AND byte-identical flight-recorder ``dump_json()`` — the tracing
    determinism contract of docs/OBSERVABILITY.md);
  * request conservation everywhere: completed + rejected + shed ==
    submitted — no request ever silently vanishes, chaos or not;
  * scripted faults actually landed: device-loss scenarios shrink the
    mesh with zero admitted requests lost, noise scenarios populate
    ``noise_agreement``, the SLO scenario flips to shedding — and every
    fault appears in the recorder as a typed anomaly whose count matches
    the corresponding metric;
  * the live soak serves through the socket with every request answered,
    a spot request bit-exact vs ``run_batched`` on the same (noisy)
    device instance, and the ADMIN ``metrics`` / ``trace`` verbs
    round-tripping the schema-locked snapshot and recorder dump live.
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

from repro.core.noise import AnalogNoise  # noqa: E402
from repro.engine import (BucketPolicy, FlightRecorder,  # noqa: E402
                          METRIC_KEYS, run_batched, run_sharded,
                          trace_count)
from repro.engine.chaos import (SCENARIOS, make_chaos_hook,  # noqa: E402
                                run_scenario, synth_arrival_trace)
from repro.engine.sharded_run import snn_serve_mesh  # noqa: E402
from repro.launch.serve_snn import build_demo_model  # noqa: E402
from repro.launch.socket_serve import (SpikeClient,  # noqa: E402
                                       SpikeSocketServer, serving_thread)

# the live soak scripts one device loss at this dispatch ordinal (skipped on
# single-device meshes, where there is nothing to recover onto)
_LIVE_LOSS = ((1, 1),)


def _conserved(m: dict) -> bool:
    return m["completed"] + m["rejected"] + m["shed"] == m["submitted"]


def _anomalies_match(tag: str, counts: dict, m: dict) -> None:
    """Every fault the metrics counted must appear in the flight recorder
    as a typed anomaly, one for one (docs/OBSERVABILITY.md anomaly
    table)."""
    flips = m["noise_probes"] - round(m["noise_agreement"]
                                      * m["noise_probes"])
    for kind, want in (("reject", m["rejected"]), ("shed", m["shed"]),
                       ("policy_extension", m["policy_extensions"]),
                       ("deadline_miss", m["deadline_misses"]),
                       ("device_loss", m["device_losses"]),
                       ("hot_swap_pin", m["hot_swaps"]),
                       ("noise_disagreement", flips)):
        got = counts.get(kind, 0)
        assert got == want, \
            f"{tag}: recorder saw {got} {kind} anomalies, metrics say {want}"


def _scenario_row(m: dict) -> dict:
    keep = ("scenario", "requests", "submitted", "admitted", "completed",
            "rejected", "shed", "deadline_misses", "deadline_miss_rate",
            "dispatches", "forced_dispatches", "device_losses",
            "mesh_size_start", "mesh_size_end", "slo_switches",
            "slo_shedding", "noise_probes", "noise_agreement",
            "bucket_fill_ratio", "max_queue_depth", "makespan_s",
            "hot_swaps", "per_model")
    return {k: m[k] for k in keep}


def bench_scenarios(packed, mesh) -> list[dict]:
    """Replay every named scenario twice; gate on determinism and on the
    scripted fault actually landing."""
    rows = []
    for name, sc in SCENARIOS.items():
        if sc.needs_mesh and (mesh is None or mesh.size < 2):
            print(f"soak/scenario/{name}: SKIP (needs >= 2 devices)")
            rows.append({"scenario": name, "skipped": True})
            continue
        rec1, rec2 = FlightRecorder(), FlightRecorder()
        _, _, m1 = run_scenario(packed, sc, mesh=mesh, recorder=rec1)
        _, _, m2 = run_scenario(packed, sc, mesh=mesh, recorder=rec2)
        assert m1 == m2, f"{name}: scenario replay is not deterministic"
        assert rec1.dump_json() == rec2.dump_json(), \
            f"{name}: flight-recorder dump is not replay-deterministic"
        _anomalies_match(name, rec1.anomaly_counts, m1)
        assert _conserved(m1), f"{name}: request leak {m1}"
        if sc.lose_devices:
            assert m1["device_losses"] == len(sc.lose_devices), \
                f"{name}: scripted loss never fired"
            assert m1["mesh_size_end"] < m1["mesh_size_start"]
            assert m1["served_all_admitted"], \
                f"{name}: admitted requests lost to device loss"
        if sc.noise_sigma > 0:
            assert m1["noise_probes"] > 0, f"{name}: no noise probes ran"
        if name == "slo_shed":    # the one scenario engineered to overload
            assert m1["slo_switches"] >= 1, \
                f"{name}: SLO controller never switched"
        if sc.tenants:
            per = m1["per_model"]
            assert set(per) == {t.name for t in sc.tenants}
            for t in sc.tenants:   # conservation holds tenant by tenant
                mm = per[t.name]
                assert mm["submitted"] == mm["admitted"] + mm["rejected"] \
                    and mm["admitted"] == mm["completed"] + mm["shed"], \
                    f"{name}: tenant {t.name} leaked requests: {mm}"
            if sc.swap_tenant:
                assert m1["hot_swaps"] == 1 and \
                    per[sc.swap_tenant]["hot_swaps"] == 1, \
                    f"{name}: scripted hot-swap never fired"
                assert per[sc.swap_tenant]["deadline_miss_rate"] <= 0.05, \
                    f"{name}: burst starved the swap tenant's deadlines"
        print(f"soak/scenario/{name}: {m1['completed']}/{m1['requests']} "
              f"served | miss {m1['deadline_miss_rate']:.3f} | mesh "
              f"{m1['mesh_size_start']}->{m1['mesh_size_end']} | slo_sw "
              f"{m1['slo_switches']} | agree {m1['noise_agreement']:.3f}")
        row = _scenario_row(m1)
        row["anomalies"] = dict(sorted(rec1.anomaly_counts.items()))
        rows.append(row)
    return rows


def _warm_buckets(packed, policy: BucketPolicy, mesh) -> float:
    """Compile every bucket the policy can dispatch and return the slowest
    warm engine-call time — the live soak's deadline-slack yardstick."""
    worst = 0.0
    for b in policy.batch_sizes:
        for t in policy.time_steps:
            zeros = np.zeros((b, t, packed.n_in), dtype=np.float32)
            for _ in range(2):     # first call compiles; second measures
                t0 = time.perf_counter()
                if mesh is None:
                    run_batched(packed, zeros, with_stats=False)
                else:
                    run_sharded(packed, zeros, mesh=mesh, with_stats=False)
                dt = time.perf_counter() - t0
            worst = max(worst, dt)
    return worst


def live_soak(packed, mesh, *, smoke: bool, seed: int = 0) -> dict:
    """Sustained adversarial offered load over a real TCP socket, with
    analog noise on the served weights and (on multi-device meshes) a
    scripted mid-soak device loss."""
    n_req = 24 if smoke else 96
    noise = AnalogNoise(weight_sigma=0.05)
    lose = _LIVE_LOSS if mesh is not None and mesh.size >= 2 else ()
    trace = synth_arrival_trace(n_req, packed.n_in, mode="adversarial",
                                rate=150.0, slack=1.0, t_lo=3, t_hi=12,
                                seed=seed + 1)
    policy = BucketPolicy.covering([s.shape[0] for _, s, _ in trace],
                                   n_shards=mesh.size if mesh else 1,
                                   max_batch=4 * (mesh.size if mesh else 1))
    worst_s = _warm_buckets(packed, policy, mesh)
    # pace arrivals so one warm engine call fits inside a flood's tight
    # quarter-slack deadline; recovery compiles mid-soak still cause
    # (measured, reported) misses — that is the point of a soak
    scale = max(1.0, 8.0 * worst_s / 0.25)
    n0 = trace_count()
    srv = SpikeSocketServer(
        packed, policy=policy, mesh=mesh, port=0,
        queue_capacity=max(n_req, 32), noise=noise, noise_key=seed,
        noise_probe_every=1, chaos_hook=make_chaos_hook(lose) if lose
        else None)
    host, port = srv.address
    t0 = time.monotonic()
    with serving_thread(srv):
        cli = SpikeClient(host, port)
        for t_a, stream, deadline in trace:
            delay = t_a * scale - (time.monotonic() - t0)
            if delay > 0:
                time.sleep(delay)
            cli.send(stream, slack=(deadline - t_a) * scale)
        cli.recv_all()
        # observability round-trip while the server is still live: the
        # schema-locked metrics snapshot and the full recorder dump
        met = cli.admin({"op": "metrics"})
        trc = cli.admin({"op": "trace"})
        cli.recv_all()
        cli.close()
    wall = time.monotonic() - t0
    m = srv.server.metrics.snapshot()
    mrep = cli.admin_replies[met]
    assert mrep.get("ok") and set(mrep["metrics"]) == set(METRIC_KEYS), \
        "live soak: ADMIN metrics reply is not schema-locked"
    trep = cli.admin_replies[trc]
    assert trep.get("ok") and trep["dump"]["n_completed"] == \
        m["completed"], "live soak: ADMIN trace dump disagrees with metrics"
    _anomalies_match("live soak", srv.tracer.anomaly_counts, m)
    answered = len(cli.results) + len(cli.rejections)
    assert answered == n_req, \
        f"live soak: {answered}/{n_req} requests answered over the socket"
    assert _conserved(m), f"live soak: request leak {m}"
    assert m["completed"] == len(cli.results) > 0
    assert m["noise_probes"] > 0, "live soak: no noise probes ran"
    if lose:
        assert m["device_losses"] == len(lose), \
            "live soak: scripted device loss never fired"
        assert srv.server.mesh.size == mesh.size - 1
    # bit-exactness through the full wire: longest answered request,
    # replayed alone through run_batched on the same noisy device instance
    served = [i for i in range(n_req) if i in cli.results]
    spot = max(served, key=lambda i: trace[i][1].shape[0])
    alone = run_batched(srv.server.packed, trace[spot][1][None],
                        with_stats=False)
    assert np.array_equal(cli.results[spot], alone.out_spikes[0]), \
        "live soak: socket-served result != run_batched"
    m.update({
        "requests": n_req, "answered": answered,
        "results": len(cli.results), "rejections": len(cli.rejections),
        "wall_s": wall, "throughput_rps": m["completed"] / max(wall, 1e-9),
        "pace_scale": scale, "worst_bucket_s": worst_s,
        "new_traces_during_soak": trace_count() - n0,
        "mesh_size_start": mesh.size if mesh else 1,
        "mesh_size_end": srv.server.mesh.size if srv.server.mesh else 1,
    })
    print(f"soak/live: {m['completed']}/{n_req} served "
          f"(+{len(cli.rejections)} rejected) in {wall:.1f}s | miss "
          f"{m['deadline_miss_rate']:.3f} | mesh {m['mesh_size_start']}->"
          f"{m['mesh_size_end']} | agree {m['noise_agreement']:.3f} "
          f"({m['noise_probes']} probes) | p99 "
          f"{m['p99_latency_s']*1e3:.0f} ms")
    return m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out", default="BENCH_soak.json")
    ap.add_argument("--model", default="mlp", choices=["mlp", "conv"])
    ap.add_argument("--data", type=int, default=None)
    ap.add_argument("--spoof-devices", type=int, default=None)
    args = ap.parse_args()
    assert_spoof_applied(_SPOOFED)
    mesh = snn_serve_mesh(args.data)
    packed = build_demo_model(args.model, smoke=args.smoke).pack()
    scenarios = bench_scenarios(packed, mesh)
    live = live_soak(packed, mesh, smoke=args.smoke)
    blob = {"bench": "soak", "smoke": args.smoke, "model": args.model,
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()), "n_shards": mesh.size,
            "scenarios": scenarios, "live": live}
    with open(args.out, "w") as f:
        json.dump(blob, f, indent=2)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
