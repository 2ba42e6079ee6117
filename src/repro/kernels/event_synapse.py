"""Pallas TPU kernel: event-driven synaptic accumulation.

The TPU-native form of MENAGE's A-SYN dispatch (DESIGN.md §2): work is
proportional to *events*, not to the dense n_src x n_dest product.  A padded
event list (the software MEM_E) gathers weight rows from the VMEM-resident
weight tile and accumulates membrane currents.

Tiling: grid = (B, n_dest / BLOCK_D).  Each program instance owns one
(sample, dest-block) pair; the full event list of that sample is in SMEM,
read one scalar index per event, and the [n_src, BLOCK_D] weight tile is in
VMEM, read one row per event.  The inner fori_loop plays the role
of the controller's per-event dispatch cycles; BLOCK_D is the vectorized lane
dimension — the "engine" axis onto which virtual neurons are packed.

The event list is padded to a static length E (MEM_E depth).  Padding entries
are -1 and are masked — the pad factor is the same overflow budget the paper
provisions for the utilization spikes of Figs 6-7.  The loop walks a row's
slots only up to its last valid one (a per-row bound computed from the list
itself and handed to the kernel in SMEM), so a sparse row costs its events,
not E; the trailing padding it skips would only have added ``0.0``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_D = 256
LANES = 128     # TPU vreg lane width: a lane block is this wide or whole


def _lane_block(n: int, want: int) -> int:
    """Width of a last-axis block the TPU tiling accepts: the whole axis, or
    the smallest multiple of 128 lanes >= ``want`` that divides it.  The
    width changes which grid step computes a column, never its value."""
    if want >= n:
        return n
    b = -(-max(want, 1) // LANES) * LANES
    while b < n and n % b:
        b += LANES
    return min(b, n)


def _accumulate(events_ref, bound_ref, read_row, n_planes: int, width: int):
    """The per-event dispatch loop shared by both kernels: for each MEM_E
    slot in order, up to the row's bound (its last valid slot + 1, a scalar
    in SMEM), read the event's source index from SMEM (a scalar read) and
    add the weight row ``read_row(plane, idx)`` ([1, width] f32) of every
    plane — one f32 add per event per column, in ascending source order, and
    an exact ``0.0`` for a ``-1`` padding slot before the bound."""

    def body(e, accs):
        idx = events_ref[0, e]
        valid = idx >= 0
        safe = jnp.where(valid, idx, 0)
        return tuple(acc + jnp.where(valid, read_row(p, safe), 0.0)
                     for p, acc in enumerate(accs))

    zero = jnp.zeros((1, width), jnp.float32)
    return jax.lax.fori_loop(0, bound_ref[0, 0], body, (zero,) * n_planes)


def _event_synapse_kernel(events_ref, bound_ref, weights_ref, out_ref):
    """events [1, E] i32 (SMEM); bound [1, 1] i32 (SMEM); weights
    [n_src, BD] f32; out [1, BD] f32."""
    (acc,) = _accumulate(
        events_ref, bound_ref, lambda _, idx: weights_ref[pl.ds(idx, 1), :],
        1, out_ref.shape[1])
    out_ref[...] = acc


def _event_operands(events: jax.Array) -> tuple[jax.Array, jax.Array]:
    """A row's event list and its loop bound, shaped for :func:`_event_specs`:
    events ``[B, 1, E]`` and, per row, the last valid slot + 1 ``[B, 1, 1]``
    (0 for a row of padding) — the count of events when the list is
    compacted to the front, as ``events_from_spikes`` writes it."""
    b, n_events = events.shape
    slot = jnp.arange(1, n_events + 1, dtype=jnp.int32)
    bound = jnp.max(jnp.where(events >= 0, slot, 0), axis=1)
    return events.reshape(b, 1, n_events), bound.reshape(b, 1, 1)


def _event_specs(n_events: int) -> list[pl.BlockSpec]:
    """One row's event list and loop bound per grid step, in SMEM so the
    loop reads each as a scalar: ``(None, 1, E)`` and ``(None, 1, 1)``
    blocks of the operands :func:`_event_operands` makes."""
    return [pl.BlockSpec((None, 1, width), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.SMEM)
            for width in (n_events, 1)]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def event_synapse(events: jax.Array, weights: jax.Array,
                  block_d: int = DEFAULT_BLOCK_D,
                  interpret: bool = False) -> jax.Array:
    """events [B, E] int32 (pad=-1); weights [n_src, n_dest] f32 ->
    currents [B, n_dest] f32."""
    b, n_events = events.shape
    n_src, n_dest = weights.shape
    if n_events == 0 or b == 0:
        # static zero-depth MEM_E (nothing dispatches) or an empty batch —
        # a zero-size grid still asks pallas for a (1, E) block slice of the
        # (0, E) events operand, so short-circuit before the kernel
        return jnp.zeros((b, n_dest), weights.dtype)
    bd = _lane_block(n_dest, block_d)
    out = pl.pallas_call(
        _event_synapse_kernel,
        grid=(b, n_dest // bd),
        in_specs=[
            *_event_specs(n_events),
            pl.BlockSpec((n_src, bd), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bd), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n_dest), weights.dtype),
        interpret=interpret,
    )(*_event_operands(events), weights)
    return out.reshape(b, n_dest)


def _event_synapse_packed_kernel(events_ref, bound_ref, packed_ref, scale_ref,
                                 out_ref, tile_ref, *, bits: int):
    """events [1, E] i32 (SMEM); bound [1, 1] i32 (SMEM); packed
    [n_src, BDB] int8 (sign-magnitude lanes); scale [1, 1] f32 (SMEM); out
    [L, BDB] f32 with ``L = 8/bits``; tile [L, n_src, BDB] f32 VMEM scratch.

    The weight tile arrives packed — ``bits/32`` of the f32 VMEM footprint,
    the twin of A-SYN storing sub-byte ladder words.  It is unpacked *once
    per grid step*, before the event loop, into ``L`` planes: plane ``s``
    holds sub-word ``s`` of every byte (destination ``byte * L + s``), 1
    sign + ``bits-1`` magnitude bits (the C2C ladder's own format,
    quant.pack_signmag), dequantized by the layer scale — the DAC step at
    the ladder input — and stored to the scratch.  The event loop is then
    gather+add only, with f32 partial sums bit-identical to the dense
    kernel.  The multiply cannot move into the loop: XLA contracts mul+add
    into an FMA, skipping the intermediate rounding the dense path has.
    Planes, not an interleaved tile, because the TPU cannot merge lanes in
    a reshape; the caller interleaves the ``[L, BDB]`` output instead.
    """
    ell = 8 // bits
    mask = (1 << bits) - 1
    mag_mask = (1 << (bits - 1)) - 1
    scale = scale_ref[0, 0]
    r = packed_ref[...].astype(jnp.int32) & 0xFF  # undo int8 sign extension
    for s in range(ell):
        w = (r >> (s * bits)) & mask
        mag = w & mag_mask
        sign = (w >> (bits - 1)) & 1
        q = (mag - 2 * sign * mag).astype(jnp.float32)
        tile_ref[s] = q * scale                   # fl32(q * scale), per elem
    accs = _accumulate(
        events_ref, bound_ref, lambda s, idx: tile_ref[s, pl.ds(idx, 1), :],
        ell, out_ref.shape[1])
    for s, acc in enumerate(accs):
        out_ref[pl.ds(s, 1), :] = acc


@functools.partial(jax.jit, static_argnames=("bits", "block_d", "interpret"))
def event_synapse_packed(events: jax.Array, packed_w: jax.Array,
                         scale: jax.Array, bits: int = 8,
                         block_d: int = DEFAULT_BLOCK_D,
                         interpret: bool = False) -> jax.Array:
    """Packed-operand twin of :func:`event_synapse`.

    events   [B, E] int32 (pad=-1)
    packed_w [n_src, n_dest * bits / 8] int8 — sign-magnitude codes packed
             ``8/bits`` destination lanes per byte (quant.pack_signmag)
    scale    f32 scalar (or [1, 1]) — the layer's symmetric quant scale
    returns  currents [B, n_dest] f32

    The VMEM weight tile per grid point shrinks proportionally to ``bits``
    (int8 codes at 8 bits are already 4x under f32; 4/2-bit lanes are 8x and
    16x).  The grid tiles packed bytes, ``block_d / (8/bits)`` of them per
    step, widened to a width the TPU tiling accepts.
    """
    ell = 8 // bits
    b, n_events = events.shape
    n_src, n_bytes = packed_w.shape
    n_dest = n_bytes * ell
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    if n_events == 0 or b == 0:
        return jnp.zeros((b, n_dest), jnp.float32)
    bdb = _lane_block(n_bytes, min(block_d, n_dest) // ell)
    out = pl.pallas_call(
        functools.partial(_event_synapse_packed_kernel, bits=bits),
        grid=(b, n_bytes // bdb),
        in_specs=[
            *_event_specs(n_events),
            pl.BlockSpec((n_src, bdb), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, ell, bdb), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, ell, n_bytes), jnp.float32),
        scratch_shapes=[pltpu.VMEM((ell, n_src, bdb), jnp.float32)],
        interpret=interpret,
    )(*_event_operands(events), packed_w, scale)
    # plane s of byte j is destination j * ell + s
    return out.transpose(0, 2, 1).reshape(b, n_dest)


def events_from_spikes(spikes: jax.Array, max_events: int) -> jax.Array:
    """Convert a dense spike vector batch [B, n_src] to a padded event list
    [B, max_events] (int32, pad=-1) — the software MEM_E writer.  Events
    beyond max_events are dropped (counted by callers via overflow_count).

    Stable O(n) compaction: each spiking source's slot is its exclusive
    prefix count along the row (cumsum is monotone in source index, so the
    emitted order is ascending — the hardware FIFO write order and the
    accumulation order the oracle equivalence relies on).  Non-spiking and
    overflowing sources scatter into a trash slot that is sliced off, so no
    O(n log n) argsort and no data-dependent shapes.

    A row of ``n`` sources can emit at most ``n`` events, so the event list
    is at most ``n`` wide even when ``max_events`` exceeds it — the same
    clamp the argsort reference inherits from slicing past the row length.
    """
    b, n = spikes.shape
    max_events = min(int(max_events), n)
    spk = spikes > 0
    pos = jnp.cumsum(spk, axis=1, dtype=jnp.int32) - 1    # slot if spiking
    pos = jnp.where(spk & (pos < max_events), pos, max_events)
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    out = jnp.full((b, max_events + 1), -1, jnp.int32)
    out = out.at[jnp.arange(b, dtype=jnp.int32)[:, None], pos].set(idx)
    return out[:, :max_events]


def _events_from_spikes_argsort(spikes: jax.Array, max_events: int) -> jax.Array:
    """The original O(n log n) full-width argsort MEM_E writer — kept as the
    bit-identical reference :func:`events_from_spikes`'s cumsum compaction is
    asserted against (tests/test_kernels.py, kernels_bench timing check)."""
    b, n = spikes.shape
    idx = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (b, n))
    # sort spiking indices to the front: key = (1-spike)*n + arange
    key = jnp.where(spikes > 0, idx, n + idx)
    order = jnp.argsort(key, axis=1)[:, :max_events]
    gathered = jnp.take_along_axis(idx, order, axis=1)
    valid = jnp.take_along_axis(spikes > 0, order, axis=1)
    return jnp.where(valid, gathered, -1).astype(jnp.int32)


def overflow_count(spikes: jax.Array, max_events: int) -> jax.Array:
    """How many events were dropped by the static MEM_E depth."""
    n_spk = (spikes > 0).sum(axis=1)
    return jnp.maximum(n_spk - max_events, 0)
