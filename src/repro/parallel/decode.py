"""Sequence-parallel flash-decoding (beyond-paper optimization, §Perf).

Baseline decode replicates the KV cache over the ``model`` axis whenever
n_kv_heads doesn't divide it (GQA kv=4/8 on a 16-way axis) — wasting HBM and
turning cache reads into the memory-roofline bottleneck.  This module shards
the cache **sequence** axis over ``model`` instead and computes attention as
a two-pass online softmax with `psum` combines (flash-decoding):

  pass 1 (local):  m_i = max score over the local seq shard
                   l_i = sum exp(s - m), o_i = sum exp(s - m) v
  combine:         m = psum-max(m_i);  rescale l_i, o_i by exp(m_i - m);
                   l = psum(l_i), o = psum(o_i);  out = o / l

Works for ANY kv-head count, cuts per-device cache bytes by the model-axis
size, and its collective cost is O(B·H·hd) — negligible next to the cache
read it parallelizes.  The new token's K/V is written only by the shard that
owns the slot.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _sp_attention_local(q, ck, cv, slot_pos, pos, window, axis: str):
    """Runs INSIDE shard_map: ck/cv are the local seq shard
    [B, KH, C_loc, hd]; slot_pos [C_loc] absolute positions (-1 invalid)."""
    b, h, hd = q.shape
    kh = ck.shape[1]
    g = h // kh
    qr = q.reshape(b, kh, g, hd)
    s = jnp.einsum("bhgd,bhcd->bhgc", qr,
                   ck.astype(qr.dtype)) / math.sqrt(hd)
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    if window is not None:
        valid &= (pos - slot_pos) < window
    s = jnp.where(valid[None, None, None, :], s.astype(jnp.float32), -jnp.inf)
    m_loc = jnp.max(s, axis=-1)                                  # [b,kh,g]
    m = jax.lax.pmax(m_loc, axis)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l_loc = jnp.sum(p, axis=-1)
    o_loc = jnp.einsum("bhgc,bhcd->bhgd", p.astype(qr.dtype),
                       cv.astype(qr.dtype)).astype(jnp.float32)
    l = jax.lax.psum(l_loc, axis)
    o = jax.lax.psum(o_loc, axis)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, hd).astype(q.dtype)


def make_sp_attention(mesh: Mesh, axis: str = "model",
                      batch_axes=("pod", "data")):
    """Returns an ``attn_impl`` drop-in for transformer_decode_step: the
    cache seq dim arrives sharded over ``axis``; batch over ``batch_axes``.

    The returned function has the same signature as
    ``transformer.decode_attention(q, ck, cv, slot_pos, pos, window)``.
    """
    all_b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)

    def attn(q, ck, cv, slot_pos, pos, window):
        if ck.shape[2] % mesh.shape[axis] != 0:
            # cache seq not divisible by the model axis (tiny smoke runs):
            # fall back to the baseline attention
            from repro.models.transformer import decode_attention
            return decode_attention(q, ck, cv, slot_pos, pos, window)
        # shard batch only if it divides the batch shards (long_500k has B=1)
        n_b = 1
        for a in all_b_axes:
            n_b *= mesh.shape[a]
        b_axes = all_b_axes if (n_b and q.shape[0] % n_b == 0) else ()

        def body(q_l, ck_l, cv_l, slot_l, pos_l):
            return _sp_attention_local(q_l, ck_l, cv_l, slot_l, pos_l,
                                       window, axis)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(b_axes or None), P(b_axes or None, None, axis),
                      P(b_axes or None, None, axis), P(axis), P()),
            out_specs=P(b_axes or None),
        )(q, ck, cv, slot_pos, pos)

    return attn


def sp_cache_update(ck, cv, k_new, v_new, slot, mesh: Mesh,
                    axis: str = "model", batch_axes=("pod", "data")):
    """Write the new token's K/V into the seq-sharded cache: only the owner
    shard performs the update (masked in-place DUS)."""
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    n_shards = mesh.shape[axis]
    c_total = ck.shape[2]
    c_loc = c_total // n_shards

    def body(ck_l, cv_l, k_l, v_l, slot_l):
        idx = jax.lax.axis_index(axis)
        local = slot_l - idx * c_loc
        in_range = (local >= 0) & (local < c_loc)
        safe = jnp.clip(local, 0, c_loc - 1)
        upd_k = jnp.where(in_range, k_l.astype(ck_l.dtype),
                          jax.lax.dynamic_slice(
                              ck_l, (0, 0, safe, 0),
                              (*ck_l.shape[:2], 1, ck_l.shape[3]))[:, :, 0])
        upd_v = jnp.where(in_range, v_l.astype(cv_l.dtype),
                          jax.lax.dynamic_slice(
                              cv_l, (0, 0, safe, 0),
                              (*cv_l.shape[:2], 1, cv_l.shape[3]))[:, :, 0])
        ck2 = jax.lax.dynamic_update_slice(ck_l, upd_k[:, :, None],
                                           (0, 0, safe, 0))
        cv2 = jax.lax.dynamic_update_slice(cv_l, upd_v[:, :, None],
                                           (0, 0, safe, 0))
        return ck2, cv2

    spec_c = P(b_axes or None, None, axis)
    spec_new = P(b_axes or None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_new, spec_new, P()),
        out_specs=(spec_c, spec_c),
    )(ck, cv, k_new, v_new, slot)
