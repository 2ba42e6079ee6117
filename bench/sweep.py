"""Find the knee of an open-loop cell: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <cell> --rates 150,200,250 --seconds 10

One process builds the cell's model once and serves its traffic mix at each
rate in turn (the mix's own ``rate_rps`` replaced).  A rate is sustained when
no request is rejected or unanswered, answers arrive at the offered rate
(within 3%), and the backlog does not grow: the median latency of the last
quarter of the window is at most 1.5 times that of the first quarter.  Prints
one JSON line per rate.  The knee is found once, on the chip, and written
into the cell's traffic file as a number; the benchmark's runs do not sweep.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402
from bench.traffic import input_sensor  # noqa: E402


def verdict(rec: dict, rate: float, seconds: float) -> dict:
    ok = rec["status"] == 0
    lat = np.where(ok, rec["done"] - rec["due"], np.inf)
    q = max(1, len(lat) // 4)
    first, last = np.median(lat[:q]), np.median(lat[-q:])
    t0, t1 = rec["window"]
    answered = ok & (rec["done"] <= t1)
    out = {"rate_rps": rate, "requests": int(len(lat)),
           "failed": int((~ok).sum()),
           "answered_rps": float(answered.sum() / (t1 - t0)),
           "p50_ms": harness.percentile(lat, 50) * 1e3,
           "p95_ms": harness.percentile(lat, 95) * 1e3,
           "first_quarter_p50_ms": float(first * 1e3),
           "last_quarter_p50_ms": float(last * 1e3)}
    out["sustained"] = bool(out["failed"] == 0
                            and out["answered_rps"] >= 0.97 * len(lat)
                            / seconds
                            and last <= 1.5 * first)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec = harness.Spec(ROOT)
    cell = spec.cell(args.workload)
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    from jax.sharding import Mesh

    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    model_mod, _ = harness.model_modules(cfg)
    packed = model_mod.build(cfg, model_mod.make_weights(cfg, args.seed))
    mesh = Mesh(np.asarray(devices[:cell["chips"]]), ("data",))
    harness.warm_buckets(packed, mesh, cfg["sensor"]["num_steps"])
    for rate in (float(r) for r in args.rates.split(",")):
        t = time.monotonic()
        spec_t = {"sensor": input_sensor(cfg), "seed": args.seed,
                  "seconds": args.seconds, "mix": dict(mix, rate_rps=rate)}
        rec, summary, _, _, compiled = harness.serve(
            packed, mesh, spec_t, False, None)
        out = verdict(rec, rate, args.seconds)
        out.update(lateness_ms=summary.get("lateness_ms"),
                   compiled=compiled, wall_s=time.monotonic() - t)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
