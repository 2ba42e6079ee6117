"""shard_map MoE: locality-exact expert dispatch (§Perf iteration 4).

The GSPMD baseline (transformer.moe_ffn) expresses dispatch as a global
sort + scatter; the partitioner cannot prove the scatter local and inserts
all-gathers of the (E, cap, d) dispatch buffers — the dominant collective
cost of both MoE train cells (mixtral train_4k: 212 s collective term).

This implementation exploits a structural fact of our sharding: at the FFN
input, activations x[B,S,d] are sharded over batch only — every ``model``
shard already holds all of its tokens.  So each model shard can run the
whole dispatch *locally* for its slice of the expert computation:

  * EP mode  (E %% model == 0, qwen3):  shard owns E/model experts (full f);
  * TP mode  (otherwise, mixtral):      shard owns all experts' f-slice;

and the ONLY collective is the down-projection partial-sum psum over
``model`` — identical to a dense TP FFN.  Per-shard capacity replaces global
capacity (drop decisions become shard-local; same capacity_factor).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _local_moe(xf, router, wg, wu, wd, *, n_experts: int, top_k: int,
               capacity_factor: float, ep_mode: bool, model_axis: str,
               batch_axes: tuple[str, ...], mesh: Mesh):
    """Runs inside shard_map.  xf [t_loc, d] (this shard's tokens, replicated
    over model); router [d, E] replicated; expert weights sliced over
    ``model`` (experts in EP mode, f in TP mode)."""
    t, d = xf.shape
    e, k = n_experts, top_k
    logits = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balance aux (local estimate; batch-mean via psum below)
    me = probs.mean(axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[expert_idx.reshape(-1)].add(
        1.0 / (t * k))
    aux = e * jnp.sum(me * ce)
    # mean over batch shards (it is already invarying across model shards —
    # the router inputs are replicated over the model axis)
    if batch_axes:
        aux = jax.lax.pmean(aux, batch_axes)

    if ep_mode:
        # keep only pairs routed to this shard's experts
        e_loc = wg.shape[0]
        shard = jax.lax.axis_index(model_axis)
        lo = shard * e_loc
        local = (expert_idx >= lo) & (expert_idx < lo + e_loc)
        eff_idx = jnp.where(local, expert_idx - lo, e_loc)  # e_loc = drop row
        n_disp_experts = e_loc
    else:
        local = jnp.ones_like(expert_idx, dtype=bool)
        eff_idx = expert_idx
        n_disp_experts = e

    # §Perf iteration 7: round capacity to a 128-multiple (MXU-aligned),
    # not a power of two — pow2 rounding padded qwen3's dispatch 1.6x
    cap = int(math.ceil(t * k / n_experts * capacity_factor / 128.0)) * 128
    cap = max(min(cap, t), 1)

    flat_e = eff_idx.reshape(-1)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    grp_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
    pos_in_e = jnp.arange(t * k) - grp_start
    keep = (pos_in_e < cap) & (sorted_e < n_disp_experts)
    slot = jnp.where(keep, sorted_e * cap + pos_in_e, n_disp_experts * cap)
    token_of = order // k

    disp = jnp.zeros((n_disp_experts * cap, d), xf.dtype)
    disp = disp.at[slot].add(xf[token_of], mode="drop")
    disp = disp.reshape(n_disp_experts, cap, d)

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, wg,
                               preferred_element_type=jnp.float32))
    u = jnp.einsum("ecd,edf->ecf", disp, wu,
                   preferred_element_type=jnp.float32)
    out = jnp.einsum("ecf,efd->ecd", (g * u).astype(xf.dtype), wd,
                     preferred_element_type=jnp.float32)
    out = out.reshape(n_disp_experts * cap, d)

    contrib = out[jnp.where(keep, slot, 0)] * (
        keep * gate.reshape(-1)[order]).astype(out.dtype)[:, None]
    y = jnp.zeros((t, d), out.dtype).at[token_of].add(contrib)
    # partial sums over the model axis: EP -> each shard contributed only its
    # experts; TP -> each shard contributed its f-slice.  Same combine:
    y = jax.lax.psum(y, model_axis)
    return y.astype(xf.dtype), aux


def moe_ffn_sharded(x: jax.Array, lp: dict, cfg, mesh: Mesh,
                    capacity_factor: float = 1.25,
                    model_axis: str = "model",
                    batch_axes: tuple[str, ...] = ("pod", "data")):
    """Drop-in for transformer.moe_ffn under an active mesh.  x [B,S,d]."""
    b, s, d = x.shape
    e = cfg.n_experts
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    # batch must divide the batch shards; otherwise replicate batch
    n_b = 1
    for a in b_axes:
        n_b *= mesh.shape[a]
    if b % max(n_b, 1) != 0:
        b_axes, n_b = (), 1
    ep_mode = (model_axis in mesh.axis_names
               and e % mesh.shape[model_axis] == 0)

    xb = P(b_axes or None, None, None)
    if ep_mode:
        # weights sliced over experts: wg/wu (E, d, f); wd (E, f, d)
        wg_spec = P(model_axis, None, None)
        wd_spec = P(model_axis, None, None)
    else:
        # weights sliced over f: TP inside each expert
        wg_spec = P(None, None, model_axis)
        wd_spec = P(None, model_axis, None)

    body = partial(_local_moe, n_experts=e, top_k=cfg.top_k,
                   capacity_factor=capacity_factor, ep_mode=ep_mode,
                   model_axis=model_axis, batch_axes=b_axes, mesh=mesh)

    def wrapper(x3, router, wg, wu, wd):
        t_loc = x3.shape[0] * x3.shape[1]
        y, aux = body(x3.reshape(t_loc, d), router, wg, wu, wd)
        return y.reshape(x3.shape), aux

    y, aux = jax.shard_map(
        wrapper, mesh=mesh,
        in_specs=(xb, P(None, None), wg_spec, wg_spec, wd_spec),
        out_specs=(xb, P()),
    )(x, lp["router"], lp["we_gate"], lp["we_up"], lp["we_down"])
    return y, aux
