"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Reads ``BENCHMARK.json`` at the checkout's root.  With ``--trace 0`` the last
line of standard output is the result with the cell's end-to-end metrics;
with ``--trace 1`` the run also records the JAX profiler over its window and
reports the cell's per-layer metrics, ``busy_s``, ``window_s`` and a
``breakdown``.  The numbers that decide ``correct`` are the last lines of
standard error and the last key of the result.  Exits non-zero, with no
result, where JAX finds no accelerator or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.Spec(ROOT)
    cell = spec.cell(args.workload)
    try:
        devices = harness.require_chips(cell["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    result, checks = harness.run_cell(spec, args.workload, args.seed,
                                      args.seconds, bool(args.trace),
                                      T_START, devices)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
