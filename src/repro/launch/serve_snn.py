"""MENAGE serving launcher: continuous batching of DVS event streams over a
data-parallel host mesh — closed-list or always-on async.

  PYTHONPATH=src python -m repro.launch.serve_snn --model both --requests 48 \
      [--data 2] [--spoof-devices 2] [--smoke] \
      [--arrivals poisson|bursty|diurnal|adversarial --rate 200 --slack 0.25] \
      [--noise-sigma 0.05] [--scenario blackout|all|...]

Requests are variable-length spike trains; the front end
(:mod:`repro.engine.serving`) pads them into the policy's fixed ``(B, T)``
bucket grid (bounded jit cache, verified via ``trace_count``) and
:func:`repro.engine.sharded_run.run_sharded` fans each bucket batch out over
the mesh — batch axis sharded, control memories replicated, input buffers
donated between steps on accelerator backends.

``--arrivals poisson|bursty|diurnal|adversarial`` switches from the
closed-list ``run_bucketed`` pass to the always-on loop
(:mod:`repro.engine.stream_server`): a time-stamped arrival process
(:func:`repro.engine.chaos.synth_arrival_trace`) replays through a
:class:`StreamServer` on a virtual clock, with per-request deadlines
(``--slack``) forcing partial bucket dispatches and a bounded arrival queue
applying backpressure.  ``--noise-sigma`` serves through a deterministic
noisy device instance (accuracy-under-noise shadow probes), and
``--scenario NAME|all`` replays named chaos scripts from
:data:`repro.engine.chaos.SCENARIOS` instead (device loss, SLO shedding,
the combined blackout).

``--spoof-devices N`` emulates an N-device host on CPU (sets
``XLA_FLAGS=--xla_force_host_platform_device_count`` before jax initializes;
must be the launcher that imports jax first, hence the sys.argv peek below).
"""

from __future__ import annotations

import argparse
import time

from repro.launch._spoof import (assert_spoof_applied,
                                 spoof_devices_from_argv)

_SPOOFED = spoof_devices_from_argv()  # before any jax import in this process

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.accelerator import MappedModel, map_model  # noqa: E402
from repro.core.energy import AcceleratorSpec  # noqa: E402
from repro.core.layers import Conv2d, Dense, SumPool2d  # noqa: E402
from repro.core.lif import LIFParams  # noqa: E402
from repro.core.noise import AnalogNoise  # noqa: E402
from repro.engine import (BucketPolicy, StreamServer,  # noqa: E402
                          VirtualClock, run_bucketed, serve_trace,
                          trace_count)
# arrival synthesis lives with the chaos scenarios now; re-exported here so
# existing imports (benchmarks/async_serving_bench.py) keep working
from repro.engine.chaos import (ARRIVAL_MODES, SCENARIOS,  # noqa: E402,F401
                                run_scenario, synth_arrival_trace)
from repro.engine.sharded_run import snn_serve_mesh  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def build_demo_model(kind: str, *, smoke: bool = False,
                     seed: int = 0) -> MappedModel:
    """A servable mapped model with random pruned weights (training is not
    the point of the serving path; spike statistics are).  ``mlp`` mirrors
    the paper's N-MNIST-style stack, ``conv`` the conv/pool/dense lowering."""
    rng = np.random.default_rng(seed)
    spec = AcceleratorSpec("serve-demo", n_cores=4, n_engines=8, n_caps=16,
                           weight_mem_bytes=1 << 20)
    lif = LIFParams(beta=0.85, threshold=0.6)
    if kind == "mlp":
        sizes = (64, 48, 10) if smoke else (256, 128, 64, 10)
        ws = []
        for i in range(len(sizes) - 1):
            w = rng.normal(0, 0.4, (sizes[i], sizes[i + 1])).astype(np.float32)
            w[np.abs(w) < np.quantile(np.abs(w), 0.6)] = 0
            ws.append(w)
        return map_model(ws, spec, lif=lif)
    if kind == "conv":
        c, side = (2, 6) if smoke else (2, 10)
        k = rng.normal(0, 0.6, (4, c, 3, 3)).astype(np.float32)
        k[rng.random(k.shape) > 0.6] = 0
        conv = Conv2d(kernel=k, in_shape=(c, side, side), stride=1, padding=1)
        pool = SumPool2d(conv.out_shape, 2)
        head = rng.normal(0, 0.4, (int(np.prod(pool.out_shape)), 10)) \
            .astype(np.float32)
        head[np.abs(head) < np.quantile(np.abs(head), 0.4)] = 0
        return map_model([conv, pool, Dense(w=head)], spec, lif=lif)
    raise ValueError(f"unknown model kind {kind!r} (mlp|conv)")


def synth_requests(n: int, n_in: int, *, t_lo: int = 4, t_hi: int = 30,
                   rate: float = 0.15, seed: int = 0) -> list[np.ndarray]:
    """A stream of n variable-length DVS-style requests ``[T_i, n_in]``."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(t_lo, t_hi + 1, size=n)
    return [(rng.random((int(t), n_in)) < rate).astype(np.float32)
            for t in lengths]


def serve_async(model, trace, *, policy: BucketPolicy, mesh,
                queue_capacity: int = 256, backpressure: str = "reject",
                service_model=None, max_events: int | None = None,
                with_stats: bool = False, donate: bool | None = None,
                noise=None, noise_key=0, tracer=None):
    """One async serving pass over an arrival trace (virtual clock);
    returns ``(results, rids, metrics)``.  ``metrics`` is the
    ``ServerMetrics`` snapshot plus the trajectory numbers
    ``BENCH_async_serving.json`` records: offered load, simulated-time
    throughput, wall seconds, and the jit-trace delta.  ``tracer`` (a
    :class:`~repro.engine.tracing.FlightRecorder`) enables per-request span
    tracing — the overhead benchmark's on/off comparison surface."""
    server = StreamServer(model, policy=policy, mesh=mesh,
                          clock=VirtualClock(),
                          queue_capacity=queue_capacity,
                          backpressure=backpressure,
                          service_model=service_model,
                          max_events=max_events, with_stats=with_stats,
                          donate=donate, noise=noise, noise_key=noise_key,
                          tracer=tracer)
    n0 = trace_count()
    t0 = time.perf_counter()
    results, rids = serve_trace(server, trace)
    wall = time.perf_counter() - t0
    snap = server.metrics.snapshot()
    makespan = max(server.now(), 1e-9)
    span = max(trace[-1][0] - trace[0][0], 1e-9) if len(trace) > 1 else 1e-9
    events = sum(t["events"] for t in server.telemetry)
    snap.update({
        "requests": len(trace),
        "offered_rps": len(trace) / span,
        "throughput_rps": snap["completed"] / makespan,
        "events_per_s": events / max(wall, 1e-9),
        "makespan_s": makespan,
        "wall_s": wall,
        "new_traces": trace_count() - n0,
        "n_buckets": server.policy.n_buckets,
    })
    return results, rids, snap


def serve_stream(model, streams, *, policy: BucketPolicy, mesh,
                 max_events: int | None = None, with_stats: bool = False):
    """One serving pass; returns (results, metrics).  Metrics are the
    serving-trajectory numbers BENCH_serving.json records: events/s,
    spikes/s, p50/p99 per-bucket step latency, and the jit-trace count."""
    telemetry: list[dict] = []
    n0 = trace_count()
    t0 = time.perf_counter()
    results = run_bucketed(model, streams, policy=policy, mesh=mesh,
                           max_events=max_events, with_stats=with_stats,
                           telemetry=telemetry)
    wall = time.perf_counter() - t0
    lat_ms = np.asarray([t["seconds"] for t in telemetry]) * 1e3
    events = sum(t["events"] for t in telemetry)
    spikes = sum(t["out_spikes"] for t in telemetry)
    metrics = {
        "requests": len(streams),
        "engine_steps": len(telemetry),
        "wall_s": wall,
        "events_per_s": events / max(wall, 1e-9),
        "spikes_per_s": spikes / max(wall, 1e-9),
        "p50_step_ms": float(np.percentile(lat_ms, 50)) if len(lat_ms) else 0.0,
        "p99_step_ms": float(np.percentile(lat_ms, 99)) if len(lat_ms) else 0.0,
        "new_traces": trace_count() - n0,
        "n_buckets": policy.n_buckets,
    }
    return results, metrics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mlp", choices=["mlp", "conv", "both"])
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--data", type=int, default=None,
                    help="mesh data-axis extent (default: all devices)")
    ap.add_argument("--spoof-devices", type=int, default=None,
                    help="emulate N CPU devices (set before jax init)")
    ap.add_argument("--max-events", type=int, default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--arrivals", default="closed",
                    choices=["closed", *ARRIVAL_MODES],
                    help="closed: drain a fixed request list (run_bucketed);"
                         " otherwise: always-on async loop over a synthetic"
                         " arrival process (StreamServer) — poisson, bursty,"
                         " diurnal (day/night load swing), adversarial"
                         " (flood/famine with tight deadlines)")
    ap.add_argument("--noise-sigma", type=float, default=0.0,
                    help="serving-time analog noise: C2C-ladder gain error "
                         "sigma (core/noise.py); async arrivals only")
    ap.add_argument("--scenario", default=None,
                    help="replay a named chaos scenario from "
                         f"repro.engine.chaos ({', '.join(SCENARIOS)}) "
                         "or 'all'; overrides --arrivals")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="mean offered load for async arrivals, requests/s")
    ap.add_argument("--slack", type=float, default=0.25,
                    help="per-request deadline slack, seconds after arrival")
    ap.add_argument("--queue-capacity", type=int, default=256,
                    help="async arrival-queue bound (backpressure kicks in)")
    ap.add_argument("--donate", default="auto", choices=["auto", "on", "off"],
                    help="donate the padded bucket buffer to each engine "
                         "call (auto: on unless the backend is CPU)")
    args = ap.parse_args()
    donate = None if args.donate == "auto" else args.donate == "on"
    assert_spoof_applied(_SPOOFED)
    enable_compile_cache()

    mesh = snn_serve_mesh(args.data)
    n_shards = mesh.size
    kinds = ["mlp", "conv"] if args.model == "both" else [args.model]
    n_req = min(args.requests, 16) if args.smoke else args.requests
    t_hi = 12 if args.smoke else 30
    if args.scenario is not None:
        names = list(SCENARIOS) if args.scenario == "all" else \
            [args.scenario]
        for kind in kinds:
            packed = build_demo_model(kind, smoke=args.smoke).pack()
            for name in names:
                sc = SCENARIOS[name]
                if sc.needs_mesh and n_shards < 2:
                    print(f"chaos/{kind}/{name}: SKIP (needs >= 2 devices; "
                          f"use --spoof-devices)")
                    continue
                _, _, m = run_scenario(packed, sc, mesh=mesh)
                print(f"chaos/{kind}/{name}: {m['completed']}/{m['requests']}"
                      f" served | miss rate {m['deadline_miss_rate']:.3f} | "
                      f"shed {m['shed']} rejected {m['rejected']} | mesh "
                      f"{m['mesh_size_start']}->{m['mesh_size_end']} | "
                      f"slo switches {m['slo_switches']} | noise agreement "
                      f"{m['noise_agreement']:.3f} "
                      f"({m['noise_probes']} probes)")
        return

    for kind in kinds:
        model = build_demo_model(kind, smoke=args.smoke)
        packed = model.pack()
        if args.arrivals != "closed":
            trace = synth_arrival_trace(n_req, packed.n_in,
                                        mode=args.arrivals, rate=args.rate,
                                        slack=args.slack, t_hi=t_hi, seed=1)
            policy = BucketPolicy.covering([s.shape[0] for _, s, _ in trace],
                                           n_shards=n_shards,
                                           max_batch=4 * n_shards)
            # instantaneous-service simulation: batch formation then depends
            # only on the (fixed) trace, so the warm replay compiles exactly
            # the buckets the hot replay hits and the retrace gate below is
            # deterministic (the bench calibrates real service times instead)
            svc = lambda b, t: 0.0  # noqa: E731
            noise = (AnalogNoise(weight_sigma=args.noise_sigma)
                     if args.noise_sigma > 0 else None)
            serve_async(packed, trace, policy=policy, mesh=mesh,
                        queue_capacity=args.queue_capacity,
                        service_model=svc, max_events=args.max_events,
                        donate=donate, noise=noise)
            results, rids, m = serve_async(
                packed, trace, policy=policy, mesh=mesh,
                queue_capacity=args.queue_capacity,
                service_model=svc, max_events=args.max_events,
                donate=donate, noise=noise)
            assert m["new_traces"] == 0, "hot async pass retraced the jit!"
            preds = [int(results[r].out_spikes.sum(axis=0).argmax())
                     for r in rids[:8] if r is not None and r in results]
            print(f"serve-async/{kind} [{args.arrivals}]: "
                  f"{m['completed']}/{m['requests']} reqs over "
                  f"{n_shards}-way mesh | offered {m['offered_rps']:.0f} "
                  f"rps, served {m['throughput_rps']:.0f} rps | latency "
                  f"p50 {m['p50_latency_s']*1e3:.1f} ms p99 "
                  f"{m['p99_latency_s']*1e3:.1f} ms | miss rate "
                  f"{m['deadline_miss_rate']:.3f} | fill "
                  f"{m['bucket_fill_ratio']:.2f} | forced "
                  f"{m['forced_dispatches']}/{m['dispatches']} | "
                  f"buckets<= {m['n_buckets']} | sample preds {preds}")
            continue
        streams = synth_requests(n_req, packed.n_in, t_hi=t_hi, seed=1)
        policy = BucketPolicy.covering([s.shape[0] for s in streams],
                                       n_shards=n_shards,
                                       max_batch=4 * n_shards)
        # warm every bucket this stream touches, then measure a hot pass
        serve_stream(packed, streams, policy=policy, mesh=mesh,
                     max_events=args.max_events)
        results, m = serve_stream(packed, streams, policy=policy, mesh=mesh,
                                  max_events=args.max_events)
        assert m["new_traces"] == 0, "hot serving pass retraced the jit!"
        preds = [int(r.out_spikes.sum(axis=0).argmax()) for r in results[:8]]
        print(f"serve/{kind}: {m['requests']} reqs over {n_shards}-way mesh "
              f"in {m['wall_s']*1e3:.0f} ms | "
              f"{m['events_per_s']/1e3:.1f}k events/s, "
              f"{m['spikes_per_s']/1e3:.1f}k spikes/s | "
              f"step p50 {m['p50_step_ms']:.1f} ms p99 "
              f"{m['p99_step_ms']:.1f} ms | "
              f"buckets<= {m['n_buckets']} | sample preds {preds}")


if __name__ == "__main__":
    main()
