"""Smoke run of the serving path on a TPU, at the paper's CIFAR10-DVS widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the data-parallel path on four chips

One chip: the CIFAR10-DVS MLP (2048 -> 1000/500/200/100/10, ``CIFAR_SNN``)
with seeded random weights, pruned to 50% and quantized to 8 bits, is mapped
onto Accel_2 and packed, served by a ``SpikeSocketServer`` on a one-chip
mesh, and sent variable-length requests by a ``SpikeClient``.  Every result
must equal ``run_batched`` on the same request and the packed-operand route
(8-bit codes), the two shortest must equal the numpy cycle-level oracle bit
for bit, and the compiled forward must hold the Pallas kernel as a
``tpu_custom_call``.

Four chips: ``run_sharded`` on a 4-device mesh against ``run_batched`` on one
device (the batch really split 4 ways, output shards on 4 devices), and two
data-parallel training steps against one device at the same
``grad_shards=4`` (parameters bit-equal).  No other phase runs.

The script runs in one process and starts no other.  It exits non-zero, and
prints no result line, when JAX finds no TPU.  The last line of its output
is ``{"ok": true, "device": {...}}``.  Times it prints are smoke timings of
one run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
N_REQUESTS = 8
BUCKET = (8, 32)        # (B, T): one bucket holds the whole burst
ORACLE_CHECKS = 2
TRAIN_BATCH = 8
GRAD_SHARDS = 4
WEIGHT_GAIN = 2.0       # Kaiming init x2: at x1 the deepest layers are silent
KERNEL_MARKER = "tpu_custom_call"   # a Pallas kernel compiled by Mosaic


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def require_tpu(n_chips: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"JAX found no TPU (platform {devs[0].platform!r})")
    check(len(devs) >= n_chips,
          f"{n_chips} chips asked for, JAX sees {len(devs)}")
    return devs


def build_model(seed: int = SEED):
    """Seeded random CIFAR_SNN weights -> 50% L1 prune -> 8-bit quantize ->
    map onto Accel_2 -> pack.  Returns ``(mapped, packed)``."""
    import jax
    import numpy as np

    from repro.configs.menage_paper import ACCEL_2, CIFAR_SNN
    from repro.core.accelerator import map_model
    from repro.core.prune import prune_pytree
    from repro.core.quant import quantize_pytree
    from repro.engine import model_for

    t0 = time.perf_counter()
    params = model_for(CIFAR_SNN).init(jax.random.key(seed), CIFAR_SNN)
    params = [WEIGHT_GAIN * w for w in params]
    pruned, _ = prune_pytree(params, 0.5)
    _, dq = quantize_pytree(pruned)
    dq = [np.asarray(w) for w in dq]
    t1 = time.perf_counter()
    mapped = map_model(dq, ACCEL_2, lif=CIFAR_SNN.lif)
    t2 = time.perf_counter()
    packed = mapped.pack()
    t3 = time.perf_counter()
    log(f"model set-up: weights (init, prune, quantize) {t1 - t0:.3f} s, "
        f"map_model {t2 - t1:.3f} s, pack {t3 - t2:.3f} s (smoke timing)")
    return mapped, packed


def make_requests(n: int, seed: int = SEED):
    """``n`` CIFAR10-DVS-like rasters ``[T_i, 2048]`` of seeded lengths."""
    import jax
    import numpy as np

    from repro.configs.menage_paper import CIFAR_DATA
    from repro.data.events import synthetic_event_dataset

    per_class = -(-n // CIFAR_DATA.num_classes)
    spikes, _ = synthetic_event_dataset(CIFAR_DATA, per_class,
                                        jax.random.key(seed + 1))
    lengths = np.random.default_rng(seed).integers(
        6, CIFAR_DATA.num_steps + 1, size=n)
    return [spikes[i, :int(t)] for i, t in enumerate(lengths)]


def serve_phase() -> None:
    """One chip: socket serving checked against run_batched and the oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.accelerator import run
    from repro.engine import (BucketPolicy, run_batched, run_sharded,
                              should_donate, snn_serve_mesh)
    from repro.engine.sharded_run import _sharded_forward, batch_spec
    from repro.launch.socket_serve import (SpikeClient, SpikeSocketServer,
                                           serving_thread)

    mapped, packed = build_model()
    requests = make_requests(N_REQUESTS)
    log(f"request lengths {[r.shape[0] for r in requests]}")

    mesh = snn_serve_mesh(1)
    b, t = BUCKET
    # compile the served bucket before serving: the first dispatch traces
    # and compiles, the second runs the cached executable
    zeros = np.zeros((b, t, packed.n_in), np.float32)
    t0 = time.perf_counter()
    run_sharded(packed, zeros, mesh=mesh, with_stats=False)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_sharded(packed, zeros, mesh=mesh, with_stats=False)
    steady = time.perf_counter() - t0
    log(f"bucket {BUCKET} first dispatch {first:.3f} s, steady dispatch "
        f"{steady:.4f} s, compile ~{first - steady:.3f} s (smoke timing)")

    spec = batch_spec(mesh, zeros.shape)
    fwd = _sharded_forward(mesh, spec, should_donate(None))
    hlo = fwd.lower(packed, jax.ShapeDtypeStruct(zeros.shape, jnp.float32),
                    None).compile().as_text()
    check(KERNEL_MARKER in hlo,
          "the compiled forward holds no tpu_custom_call: the synapse "
          "kernel was not compiled for the chip")
    log("compiled forward holds the Pallas kernel (tpu_custom_call)")

    policy = BucketPolicy.for_mesh(mesh.size, batch_sizes=(b,),
                                   time_steps=(t,))
    srv = SpikeSocketServer(packed, policy=policy, mesh=mesh)
    host, port = srv.address
    with serving_thread(srv, max_requests=len(requests)):
        cli = SpikeClient(host, port, timeout=600.0)
        t0 = time.perf_counter()
        ids = [cli.send(r) for r in requests]
        cli.recv_all()
        round_trip = time.perf_counter() - t0
        cli.close()
    check(not cli.rejections, f"requests rejected: {cli.rejections}")
    check(sorted(cli.results) == sorted(ids),
          f"served {len(cli.results)}/{len(ids)} requests")
    lat_ms = np.sort(np.asarray(srv.server.metrics.latency_s)) * 1e3
    log(f"served {len(ids)} requests over the socket; server-side latency "
        f"per request {np.round(lat_ms, 3).tolist()} ms, client burst round "
        f"trip {round_trip * 1e3:.3f} ms (smoke timing, not a benchmark)")

    active = 0
    for i, req in zip(ids, requests):
        ref = run_batched(packed, req[None], with_stats=False).out_spikes[0]
        got = cli.results[i]
        check(got.shape == ref.shape and np.array_equal(got, ref),
              f"request {i}: served result differs from run_batched")
        active += int(got.sum())
    check(active > 0, "every output spike train is silent; the comparison "
                      "checked nothing")
    log(f"all {len(ids)} results equal run_batched ({active} output spikes)")

    # the packed-operand route (int8 codes, event_synapse_packed) on the
    # same requests, padded into the served bucket
    codes = mapped.pack(packed_ops=True)
    batch = np.zeros((b, t, packed.n_in), np.float32)
    for row, req in enumerate(requests):
        batch[row, :req.shape[0]] = req
    out = run_batched(codes, batch, with_stats=False).out_spikes
    for row, (i, req) in enumerate(zip(ids, requests)):
        check(np.array_equal(out[row, :req.shape[0]], cli.results[i]),
              f"request {i}: packed-operand route differs from the "
              f"served result")
    log("the packed-operand route (8-bit codes) gives the served results")

    shortest = sorted(range(len(requests)),
                      key=lambda k: requests[k].shape[0])[:ORACLE_CHECKS]
    spikes = 0
    for k in shortest:
        oracle = run(mapped, requests[k]).out_spikes
        check(np.array_equal(cli.results[ids[k]], oracle),
              f"request {ids[k]}: served result differs from the numpy "
              f"oracle")
        spikes += int(oracle.sum())
    check(spikes > 0, "the oracle-checked results are silent")
    log(f"{len(shortest)} results equal the numpy oracle bit for bit "
        f"({spikes} output spikes)")


def four_chip_phase(n_chips: int) -> None:
    """run_sharded and data-parallel training on ``n_chips`` chips against
    one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.menage_paper import CIFAR_DATA, CIFAR_SNN
    from repro.data.events import event_batch_at, synthetic_event_dataset
    from repro.engine import (MLP_MODEL, SNNTrainConfig, run_batched,
                              run_sharded, should_donate, snn_serve_mesh,
                              snn_train_mesh, train_snn_model)
    from repro.engine.sharded_run import (_sharded_forward, batch_spec,
                                          n_batch_shards)

    _, packed = build_model()
    spikes, labels = synthetic_event_dataset(CIFAR_DATA, 1,
                                             jax.random.key(SEED + 1))
    x = spikes[:2 * n_chips]                              # [8, 25, 2048]
    mesh = snn_serve_mesh(n_chips)
    check(n_batch_shards(mesh, x.shape[0]) == n_chips,
          f"batch {x.shape[0]} splits {n_batch_shards(mesh, x.shape[0])} "
          f"ways on the {n_chips}-device mesh")
    t0 = time.perf_counter()
    sharded = run_sharded(packed, x, mesh=mesh, with_stats=False).out_spikes
    log(f"run_sharded on {n_chips} chips, first call "
        f"{time.perf_counter() - t0:.3f} s (smoke timing)")
    single = run_batched(packed, x, with_stats=False).out_spikes
    check(np.array_equal(sharded, single),
          "run_sharded differs from run_batched")
    check(single.sum() > 0, "every output spike train is silent")
    outs = _sharded_forward(mesh, batch_spec(mesh, x.shape),
                            should_donate(None))(packed, jnp.asarray(x), None)
    shards = outs[-1].addressable_shards
    devices = {s.device for s in shards}
    check(len(devices) == n_chips
          and all(s.data.shape[0] == x.shape[0] // n_chips for s in shards),
          f"output shards on {len(devices)} devices, shapes "
          f"{[s.data.shape for s in shards]}")
    log(f"run_sharded == run_batched over batch {x.shape[0]} split "
        f"{n_chips} ways; output shards on {len(devices)} devices")

    def data(step):
        return event_batch_at(spikes, labels, TRAIN_BATCH, step)

    def train(mesh):
        cfg = SNNTrainConfig(steps=2, mesh=mesh, grad_shards=GRAD_SHARDS,
                             log_every=1000)
        t0 = time.perf_counter()
        params, _ = train_snn_model(MLP_MODEL, CIFAR_SNN, data, cfg,
                                    key=jax.random.key(SEED),
                                    log_fn=lambda s: None)
        n = 1 if mesh is None else mesh.size
        log(f"2 training steps on {n} device(s) "
            f"{time.perf_counter() - t0:.3f} s (smoke timing)")
        return [np.asarray(p) for p in params]

    dp = train(snn_train_mesh(n_chips))
    one = train(None)
    init = [np.asarray(p) for p in MLP_MODEL.init(jax.random.key(SEED),
                                                  CIFAR_SNN)]
    check(all(np.array_equal(a, b) for a, b in zip(dp, one)),
          "data-parallel training parameters differ from one device")
    check(not all(np.array_equal(a, b) for a, b in zip(dp, init)),
          "training left the parameters unchanged")
    log(f"2 training steps at grad_shards={GRAD_SHARDS}: parameters on "
        f"{n_chips} chips bit-equal to one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel serving and "
                         "training path on four chips")
    args = ap.parse_args(argv)
    try:
        devs = require_tpu(args.chips)
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        log(f"device_kind {devs[0].device_kind!r}, {len(devs)} device(s), "
            f"compile cache {cache} ({warm} entries at start)")
        if args.chips == 1:
            serve_phase()
        else:
            four_chip_phase(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
