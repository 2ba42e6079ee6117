import os
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove every (architecture x shape x mesh) cell lowers,
SPMD-partitions, and compiles on the production meshes — and extract the
roofline inputs (FLOPs, bytes, collective traffic, per-device memory) from
the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b \
      --shape train_4k [--multi-pod] [--rules sp] [--out results/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init) — hence its position.
"""

import argparse   # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (SHAPES, applicable_shapes, get_config,  # noqa: E402
                           ARCH_IDS)
from repro.launch.hlo_analysis import (collective_bytes,  # noqa: E402
                                       roofline_terms)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.layers import abstract_params  # noqa: E402
from repro.optim.adamw import AdamWConfig  # noqa: E402
from repro.parallel.sharding import (DECODE_RULES, DECODE_RULES_SP,  # noqa: E402
                                     TRAIN_RULES, ShardingRules, activate)


def _abstract_opt(params_abs):
    return {"m": params_abs, "v": params_abs,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _axes_opt(axes):
    return {"m": axes, "v": axes, "step": ()}


def _is_axes_leaf(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None)))
                                        for e in x)


def _shardings_for(rules: ShardingRules, axes_tree, abs_tree):
    return jax.tree.map(
        lambda ax, ab: rules.sharding(tuple(ax), tuple(ab.shape)),
        axes_tree, abs_tree, is_leaf=_is_axes_leaf)


def lower_cell(arch: str, shape_name: str, mesh, rules_name: str = "base",
               attn_impl: str = "baseline"):
    """Lower + compile one cell.  Returns (compiled, lowered, meta dict)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    bundle = build_model(cfg)
    kind = shape.kind

    rule_map = {"base": TRAIN_RULES if kind == "train" else DECODE_RULES,
                "sp": DECODE_RULES_SP}[rules_name]

    with activate(mesh, rule_map) as rules:
        params_abs = bundle.abstract_params(
            jnp.float32 if kind == "train" else jnp.bfloat16)
        axes = bundle.param_axes()
        p_shard = _shardings_for(rules, axes, params_abs)
        inputs_abs, in_axes = bundle.input_specs(shape)
        in_shard = {k: rules.sharding(tuple(in_axes[k]),
                                      tuple(inputs_abs[k].shape))
                    for k in inputs_abs}

        if kind == "train":
            from repro.engine.train_loop import make_train_step
            # §Perf iteration 9: gradient accumulation sized so per-device
            # activations fit HBM — big archs split the global batch
            import math as _math
            n_params_b = sum(_math.prod(s.shape)
                             for s in jax.tree.leaves(params_abs)) / 1e9
            micro = 8 if n_params_b > 50 else (4 if n_params_b > 15 else 1)
            if shape.global_batch % max(micro, 1) != 0:
                micro = 1
            step = make_train_step(bundle.loss, AdamWConfig(),
                                   microbatches=micro)
            state_abs = {"params": params_abs, "opt": _abstract_opt(params_abs)}
            state_shard = {"params": p_shard,
                           "opt": {"m": p_shard, "v": p_shard,
                                   "step": rules.sharding(())}}
            fn = jax.jit(step,
                         in_shardings=(state_shard, in_shard),
                         out_shardings=(state_shard, None),
                         donate_argnums=(0,))
            lowered = fn.lower(state_abs, inputs_abs)
        elif kind == "prefill":
            fn = jax.jit(bundle.prefill,
                         in_shardings=(p_shard, in_shard),
                         out_shardings=None)
            lowered = fn.lower(params_abs, inputs_abs)
        else:  # decode
            cache_abs, cache_axes = bundle.cache_spec(shape.global_batch,
                                                      shape.seq_len)
            c_shard = _shardings_for(rules, cache_axes, cache_abs)

            def decode(params, cache, batch):
                if attn_impl == "sp":
                    from repro.parallel.decode import make_sp_attention
                    impl = make_sp_attention(rules.mesh)
                    return bundle.decode(params, cache, batch, attn_impl=impl)
                return bundle.decode(params, cache, batch)

            fn = jax.jit(decode,
                         in_shardings=(p_shard, c_shard, in_shard),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
            lowered = fn.lower(params_abs, cache_abs, inputs_abs)

    t0 = time.monotonic()
    compiled = lowered.compile()
    compile_s = time.monotonic() - t0
    return compiled, lowered, {"arch": arch, "shape": shape_name,
                               "kind": kind, "mesh": list(mesh.devices.shape),
                               "rules": rules_name, "attn": attn_impl,
                               "compile_s": compile_s}


def analyze(compiled, lowered, meta, n_devices: int) -> dict:
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # loop-aware re-analysis: cost_analysis() counts while bodies once (see
    # hlo_flops.py) — with scan-over-layers that undercounts by ~n_layers.
    from repro.launch.hlo_flops import analyze_hlo
    loop_cost = analyze_hlo(hlo)
    coll = collective_bytes(hlo)
    terms = roofline_terms(
        {"flops": loop_cost.flops, "bytes accessed": loop_cost.bytes},
        loop_cost, n_devices)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = getattr(ma, attr)
    except Exception as e:  # pragma: no cover - backend-dependent
        mem["error"] = str(e)
    return {**meta,
            "cost_analysis_raw": {k: float(v) for k, v in cost.items()
                                  if isinstance(v, (int, float))},
            "loop_aware": {"flops": loop_cost.flops,
                           "dot_flops": loop_cost.dot_flops,
                           "bytes": loop_cost.bytes},
            "collectives": {"bytes": loop_cost.coll_bytes,
                            "counts": loop_cost.coll_counts},
            "memory": mem,
            "roofline": terms.to_dict()}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             rules_name: str = "auto", attn_impl: str = "auto",
             verbose: bool = True) -> dict:
    # production defaults: SP flash-decode for decode cells (§Perf iter. 5)
    is_decode = SHAPES[shape_name].kind == "decode"
    explicit = (rules_name != "auto" or attn_impl != "auto")
    if rules_name == "auto":
        rules_name = "sp" if is_decode else "base"
    if attn_impl == "auto":
        attn_impl = "sp" if is_decode else "baseline"
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    compiled, lowered, meta = lower_cell(arch, shape_name, mesh,
                                         rules_name, attn_impl)
    rec = analyze(compiled, lowered, meta, n_dev)
    tag = "multipod" if multi_pod else "pod"
    suffix = f"_{rules_name}_{attn_impl}" if explicit else ""
    os.makedirs(os.path.join(out_dir, tag), exist_ok=True)
    path = os.path.join(out_dir, tag,
                        f"{arch}_{shape_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if verbose:
        r = rec["roofline"]
        print(f"[dryrun OK] {arch} x {shape_name} mesh={meta['mesh']} "
              f"compile={meta['compile_s']:.1f}s "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"dominant={r['dominant']}")
        try:
            ma = compiled.memory_analysis()
            print(f"  memory_analysis: args={getattr(ma, 'argument_size_in_bytes', '?')} "
                  f"out={getattr(ma, 'output_size_in_bytes', '?')} "
                  f"temp={getattr(ma, 'temp_size_in_bytes', '?')}")
        except Exception:
            pass
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default="auto", choices=["auto", "base", "sp"],
                    help="auto = sp flash-decode for decode cells (the "
                         "production config after §Perf iteration 5), base "
                         "elsewhere; 'base' reproduces the pre-iteration "
                         "baseline")
    ap.add_argument("--attn", default="auto",
                    choices=["auto", "baseline", "sp"])
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in applicable_shapes(get_config(a)):
                cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = []
    for a, s in cells:
        try:
            run_cell(a, s, args.multi_pod, args.out, args.rules, args.attn)
        except Exception:
            failures.append((a, s))
            print(f"[dryrun FAIL] {a} x {s}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: {failures}")
    print(f"all {len(cells)} cells passed")


if __name__ == "__main__":
    main()
