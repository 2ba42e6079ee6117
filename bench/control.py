"""Readings for the limits of ``correct``: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 3

For each seed, one run of the cell through the timed path (a short window at
the cell's own load) and, over the same sampled answers, the control: the
plain reference put in the program's place and computed with its weights in
bfloat16, the precision below the float32 the configurations state.  Both go
through the same checks and limits that decide ``correct``.  Prints one JSON
line per seed: the program's ``correct`` and check values (the lower
readings) and the control's (the upper readings; its ``correct`` has to come
out false).  The benchmark's own runs do not run the control.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    spec = harness.Spec(ROOT)
    try:
        devices = harness.require_chips(spec.cell(args.workload)["chips"])
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks = harness.run_cell(
            spec, args.workload, seed, args.seconds, False, time.monotonic(),
            devices, with_control=True)
        ctl = result["control"]
        print(json.dumps({
            "seed": seed,
            "program": {"correct": result["correct"],
                        **{k: c["value"] for k, c in checks.items()}},
            "control": {"correct": ctl["correct"],
                        **{k: c["value"] for k, c in ctl["checks"].items()}},
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
