"""The benchmark harness end to end on the CPU at a tiny size: it refuses to
run without an accelerator, picks up configurations, mixes and metrics added
as files, serves a cell correctly, and reports ``correct`` false when the
timed path is broken underneath."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from _bench_tiny import ROOT, STUB_PEAKS, harness, tiny_config, tiny_spec


def run_tiny(spec, cell, seed, trace=False, seconds=1.5):
    import jax
    cfg = tiny_config(spec, spec.cell(cell)["config"])
    return harness.run_cell(spec, cell, seed, seconds, trace,
                            time.monotonic(), jax.devices(), cfg=cfg)


def test_no_accelerator_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench/run.py"), "--workload",
         "cifar_mlp.closed128", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


@pytest.mark.parametrize("cell,trace", [("cifar_mlp.closed128", True),
                                        ("cifar_mlp.poisson_tail", False)])
def test_tiny_cell_is_served_correctly(monkeypatch, cell, trace):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: STUB_PEAKS)
    spec = tiny_spec()
    result, checks = run_tiny(spec, cell, 2**31 + 77, trace=trace)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in spec.metrics(cell, trace)}
    if trace:
        # no device plane on the CPU: the device readers find nothing
        want -= {"device_idle_pct.sat", "synapse_share_pct.sat",
                 "synapse_roofline_pct.sat", "mfu.sat"}
        assert "busy_s" in result["device"]
    assert set(result["metrics"]) == want
    assert list(checks) == ["wrong_answers", "unanswered",
                            "silent_reference"]


def test_an_answer_altered_where_it_is_produced_is_caught(monkeypatch):
    from repro.engine import serving
    real = serving._slice_request

    def altered(res, row, t, with_stats):
        out = real(res, row, t, with_stats)
        out.out_spikes = out.out_spikes.copy()
        out.out_spikes[0, 0] = 1.0 - out.out_spikes[0, 0]
        return out

    monkeypatch.setattr(serving, "_slice_request", altered)
    result, checks = run_tiny(tiny_spec(), "nmnist_mlp.closed128", 5)
    assert not result["correct"]
    assert checks["wrong_answers"]["value"] > checks["wrong_answers"]["limit"]


DP4_FAULT = """
import sys, time
sys.path.insert(0, {tests!r})
sys.path.insert(0, {src!r})
import jax
from _bench_tiny import harness, tiny_config, tiny_spec
from repro.engine import serving, sharded_run
real = sharded_run.run_sharded
def one_chip_only(packed, x, *, mesh=None, **kw):
    res = real(packed, x, mesh=mesh, **kw)
    if {fault}:
        res.out_spikes = res.out_spikes.copy()
        res.out_spikes[x.shape[0] // mesh.size:] = 0   # shards 1..3 lost
    return res
serving.run_sharded = one_chip_only
spec = tiny_spec()
spec.data["workloads"].append({{"name": "dp4", "config": "cifar10dvs_mlp",
                               "traffic": "closed128", "chips": 4}})
cfg = tiny_config(spec, "cifar10dvs_mlp")
result, checks = harness.run_cell(spec, "dp4", 9, 1.5,
                                  False, time.monotonic(), jax.devices(),
                                  cfg=cfg)
print("RESULT", result["correct"], checks["wrong_answers"]["value"])
"""


@pytest.mark.parametrize("fault", [False, True])
def test_four_chip_cell_and_its_exchange_left_out(fault):
    """A four-chip cell (the closed loop on a 4-device mesh) is served
    correctly, and the rows of chips 1-3 left out are caught."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = DP4_FAULT.format(tests=str(ROOT / "tests/bench"),
                            src=str(ROOT / "src"), fault=fault)
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT")]
    assert line, proc.stderr[-3000:]
    _, correct, wrong = line[0].split()
    assert correct == str(not fault)
    assert (int(wrong) > 0) == fault


def test_config_mix_and_metric_added_as_files_are_picked_up(
        tmp_path, monkeypatch):
    """A later change adds a configuration, a mix and a per-layer metric by
    adding files and BENCHMARK.json entries alone."""
    monkeypatch.setattr(harness, "peaks_for", lambda kind: STUB_PEAKS)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/nmnist_mlp.json").read_text())
    cfg["name"] = "nmnist_mlp_gain3"
    cfg["assumed"]["weight_gain"] = 3.0
    (tmp_path / "bench/configs/nmnist_mlp_gain3.json").write_text(
        json.dumps(cfg))
    mix = json.loads((ROOT / "bench/traffic/closed128.json").read_text())
    mix["outstanding"] = 8
    (tmp_path / "bench/traffic/closed8.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/answered_share_pct.new.py").write_text(
        "def read(run):\n"
        "    return 100.0 * float((run.req['status'] == 0).mean())\n")
    data["configs"].append({"name": "nmnist_mlp_gain3", "source": "x",
                            "file": "bench/configs/nmnist_mlp_gain3.json",
                            "reduced": [], "why": "x"})
    data["workloads"].append({"name": "new.closed8",
                              "config": "nmnist_mlp_gain3",
                              "traffic": "closed8", "chips": 1, "why": "x"})
    for m in data["end_to_end"]:
        if m["name"] == "served_rps":
            m["workloads"].append("new.closed8")
    data["per_layer"].append({"name": "answered_share_pct.new", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "scheduler", "moves": "served_rps",
                              "workloads": ["new.closed8"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    spec = tiny_spec(tmp_path)
    result, checks = run_tiny(spec, "new.closed8", 3, trace=True)
    assert result["correct"], checks
    assert result["metrics"]["answered_share_pct.new"]["value"] == 100.0
    assert "bucket_fill_pct.sat" not in result["metrics"]
    result, _ = run_tiny(spec, "new.closed8", 3, trace=False)
    assert set(result["metrics"]) == {"served_rps", "setup_s"}


CONV_FILES = ROOT / "tests/bench/conv_files"
CONV_CELL = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness
assert harness.__file__.startswith({root!r}), harness.__file__
harness.peaks_for = lambda kind: {peaks!r}
result, checks = harness.run_cell(harness.Spec(harness.ROOT), "conv.closed12",
                                  {seed}, 1.5, True, time.monotonic(),
                                  jax.devices())
print("RESULT " + json.dumps(dict(result, checks=checks)))
"""


def _digests(root):
    import hashlib
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_conv_config_added_as_files_is_counted_by_its_geometry(tmp_path):
    """A convolutional configuration (``tests/bench/conv_files``: a model
    module, its reference, a configuration, a mix and a metric reader) is
    added to a copy of the benchmark as new files and BENCHMARK.json entries
    alone, served there by the copy's own harness, read correct, and counted
    with the convolution's fan-out, not the dense one."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    had = _digests(tmp_path)
    added = _digests(CONV_FILES)
    assert added and not set(added) & set(had)
    shutil.copytree(CONV_FILES, tmp_path, dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    data = json.loads((tmp_path / "BENCHMARK.json").read_text())
    data["configs"].append({"name": "tiny_conv", "source": "x",
                            "file": "bench/configs/tiny_conv.json",
                            "reduced": [], "why": "x"})
    data["workloads"].append({"name": "conv.closed12", "config": "tiny_conv",
                              "traffic": "closed12", "chips": 1, "why": "x"})
    for m in data["end_to_end"]:
        if m["name"] == "served_rps":
            m["workloads"].append("conv.closed12")
    data["per_layer"].append({"name": "conv_fan_out.new", "unit": "1",
                              "better": "higher", "source": "program_counter",
                              "layer": "kernels", "moves": "served_rps",
                              "workloads": ["conv.closed12"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = CONV_CELL.format(root=str(tmp_path), src=str(ROOT / "src"),
                            peaks=STUB_PEAKS, seed=2**31 + 613)
    proc = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")]
    assert line, proc.stderr[-3000:]
    result = json.loads(line[0][len("RESULT "):])
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    # 2x6x6 -> 4 channels, 3x3, padding 1: 2,048 (source, destination)
    # pairs over 72 sources, where the dense count would give all 144
    fan_out = result["metrics"]["conv_fan_out.new"]["value"]
    assert fan_out == pytest.approx(2048 / 72, rel=1e-12)
    # of the files the copy had, only BENCHMARK.json was edited
    after = _digests(tmp_path)
    assert [k for k in had if after[k] != had[k]] == [
        pathlib.Path("BENCHMARK.json")]
