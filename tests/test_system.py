"""End-to-end system behaviour: the full Algorithm-1 pipeline and the
dry-run/roofline machinery on an emulated multi-device mesh."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ, PYTHONPATH="src")
    pre = (f'import os; os.environ["XLA_FLAGS"] = '
           f'"--xla_force_host_platform_device_count={devices}"\n')
    p = subprocess.run([sys.executable, "-c", pre + script],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=timeout)
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-4000:])
    return p.stdout


def test_algorithm1_end_to_end(tmp_path):
    """Train -> prune -> quantize -> ILP map -> execute -> energy report,
    with the accelerator twin bit-exact vs the dense reference."""
    out = _run("""
import jax, numpy as np
from repro.core.accelerator import map_model, reference_forward, run
from repro.core.energy import AcceleratorSpec
from repro.core.prune import prune_pytree
from repro.core.quant import quantize_pytree
from repro.data.events import EventDatasetConfig, event_batches, synthetic_event_dataset
from repro.engine import MLP_MODEL, SNNTrainConfig, train_snn_model
from repro.snn.mlp import SNNConfig

data_cfg = EventDatasetConfig("sys", 10, 10, num_steps=12, base_rate=0.02,
                              signal_rate=0.5)
snn = SNNConfig(layer_sizes=(data_cfg.n_in, 32, 10), num_steps=12)
spikes, labels = synthetic_event_dataset(data_cfg, 8, jax.random.key(0))
params, _ = train_snn_model(MLP_MODEL, snn,
                            event_batches(spikes, labels, 16),
                            SNNTrainConfig(steps=60, log_every=1000),
                            key=jax.random.key(1), log_fn=lambda s: None)
pruned, _ = prune_pytree(params, 0.5)
_, dq = quantize_pytree(pruned)
spec = AcceleratorSpec("sys", 2, 4, 16, 1 << 20)
model = map_model([np.asarray(w) for w in dq], spec, lif=snn.lif)
res = run(model, spikes[0])
ref = reference_forward([l.w_q for l in model.layers], snn.lif, spikes[0])
assert np.array_equal(res.out_spikes, ref)
assert res.energy.tops_per_w > 0
print("OK", res.energy.tops_per_w)
""", devices=1)
    assert "OK" in out


def test_dryrun_machinery_small_mesh(tmp_path):
    """The dry-run path (lower -> compile -> loop-aware analysis) works end
    to end on a small emulated mesh with a smoke-scale config."""
    out = _run("""
import jax
import repro.launch.dryrun as D
from repro.configs.common import ShapeSpec
import repro.configs.internlm2_1_8b as mod

mod.CONFIG = mod.SMOKE
D.SHAPES = dict(D.SHAPES)
D.SHAPES["train_4k"] = ShapeSpec("train_4k", 64, 8, "train")

mesh = jax.make_mesh((4, 2), ("data", "model"))
compiled, lowered, meta = D.lower_cell("internlm2_1_8b", "train_4k", mesh)
rec = D.analyze(compiled, lowered, meta, 8)
assert rec["roofline"]["compute_s"] > 0
assert rec["loop_aware"]["flops"] > 0
raw = rec["cost_analysis_raw"].get("flops", 0.0)
assert rec["loop_aware"]["flops"] > raw, (rec["loop_aware"]["flops"], raw)
print("OK", rec["roofline"]["dominant"])
""", devices=8)
    assert "OK" in out


def test_hlo_flops_analyzer_exact_on_scan():
    """The loop-aware analyzer counts scanned matmul FLOPs exactly (raw
    cost_analysis counts the body once)."""
    out = _run("""
import jax, jax.numpy as jnp
from repro.launch.hlo_flops import analyze_hlo

def g(a, b):
    def body(x, _):
        return jnp.tanh(x @ b), None
    x, _ = jax.lax.scan(body, a, None, length=11)
    return x

a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
b = jax.ShapeDtypeStruct((128, 128), jnp.float32)
c = jax.jit(g).lower(a, b).compile()
cost = analyze_hlo(c.as_text())
expect = 11 * 2 * 64 * 128 * 128
assert abs(cost.dot_flops - expect) / expect < 1e-6, (cost.dot_flops, expect)
raw = c.cost_analysis()["flops"]
assert cost.dot_flops > 5 * raw
print("OK")
""", devices=1)
    assert "OK" in out


def test_collective_bytes_counted_with_loop_multiplier():
    """Collectives inside a scanned body are multiplied by the trip count."""
    out = _run("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.hlo_flops import analyze_hlo

mesh = jax.make_mesh((4,), ("x",))

def f(a):
    def body(x, _):
        y = jax.shard_map(lambda v: jax.lax.psum(v, "x"), mesh=mesh,
                          in_specs=P("x"), out_specs=P())(x)
        return jnp.tanh(x * jnp.mean(y)), None
    x, _ = jax.lax.scan(body, a, None, length=5)
    return x

a = jax.ShapeDtypeStruct((16, 64), jnp.float32)
c = jax.jit(f).lower(a).compile()
cost = analyze_hlo(c.as_text())
assert cost.coll_counts["all-reduce"] >= 5, cost.coll_counts
print("OK", cost.coll_counts)
""", devices=4)
    assert "OK" in out
