"""Shared set-up of the benchmark's CPU tests: the repository root on the
path, and configurations and mixes cut to a size a test run can hold."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

STUB_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def tiny_config(spec: "harness.Spec", name: str) -> dict:
    cfg = spec.config(name)
    cfg["input_downsample"] = 1
    cfg["sensor"].update(height=6, width=6, num_steps=6)
    cfg["hidden_sizes"] = [24, 12, 10]
    return cfg


def tiny_spec(root: pathlib.Path = ROOT) -> "harness.Spec":
    """The benchmark at ``root`` with its mixes shortened: one second of
    warm-up and a lighter load."""
    spec = harness.Spec(root)
    full = spec.traffic

    def traffic(name):
        mix = full(name)
        mix["warm_s"] = 1.0
        if "rate_rps" in mix:
            mix["rate_rps"] = 40.0
        if "outstanding" in mix:
            mix["outstanding"] = 12
        return mix

    spec.traffic = traffic
    return spec
