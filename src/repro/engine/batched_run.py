"""Batched, jit-compiled execution engine for the MENAGE software twin.

The numpy :func:`repro.core.accelerator.run` is the cycle-accurate oracle: it
walks timesteps, rounds, MEM_S&N rows, and engines in Python, which is exactly
right for auditing the silicon and exactly wrong for serving traffic.  This
module executes the *same* mapped model — the same control-memory content —
as a batched JAX program:

  * :func:`pack_model` turns a :class:`MappedModel` into a
    :class:`PackedModel` **pytree**: per round, ``MemTables.to_jax()`` (the
    padded int32 MEM_E2A / MEM_S&N tables) plus the dense effective-weight
    matrix replayed out of those tables and scattered to global destination
    columns (padded to the Pallas block size).
  * :func:`run_batched` executes ``spikes[B, T, n_in]`` through the chain.
    Per layer, the ``B*T`` spike vectors become padded event lists via
    ``events_from_spikes`` (the software MEM_E writer; ``overflow_count``
    reports drops against the static depth), synaptic accumulation routes
    through the ``event_synapse`` Pallas kernel (interpret mode on CPU,
    native on TPU), and the per-timestep LIF loop is a single
    ``jax.lax.scan``.

Equivalence contract (tested): output spikes are **bit-identical** to the
oracle's for every batch element, and the reported :class:`DispatchStats`
aggregates match it field for field.  Sub-ULP care: events are emitted in
ascending source order, matching the oracle's accumulation order, and padding
events add an exact ``0.0`` — so even the float32 partial sums agree.

Data layout (see README "Batched engine"):

  PackedModel.layers[l].rounds[r].tables   PackedTables (padded i32 pytree)
  PackedModel.layers[l].rounds[r].w_dense  f32 [n_src, n_dest_pad]  (dense)
  PackedModel.layers[l].rounds[r].coo_*    i32/f32 [nnz]  (shared-weight /
                                           conv rounds: COO synapse replay,
                                           scattered on device under jit)
  events                                   i32 [B*T, E]   (pad = -1)
  currents                                 f32 [B, T, n_dest_pad]
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accelerator import MappedModel
from repro.core.energy import (FRAME_CYCLES, AcceleratorSpec, EnergyReport,
                               energy_model)
from repro.core.lif import LIFParams, lif_rollout
from repro.core.memories import DispatchStats, PackedTables
from repro.core.quant import check_bits, lanes_per_byte, pack_signmag
from repro.engine.tracing import stage
from repro.kernels import ops
from repro.kernels.event_synapse import DEFAULT_BLOCK_D


def _mem_e_depth(layer: "PackedLayer", max_events: int | None) -> int:
    """Static MEM_E depth for a layer: full fan-in unless capped — shared by
    the kernel dispatch and the overflow accounting, which must agree."""
    return layer.n_src if max_events is None else min(max_events, layer.n_src)


def _pad_dest(n_dest: int, block_d: int) -> int:
    """Smallest dest width event_synapse can tile: unpadded when a single
    block covers the layer, else the next multiple of ``block_d``."""
    if n_dest <= block_d:
        return n_dest
    return -(-n_dest // block_d) * block_d


@dataclasses.dataclass
class PackedRound:
    """One capacitor-assignment round on the device.

    Dense layers carry ``w_dense`` (the replayed effective-weight matrix).
    Shared-weight (conv) layers instead carry a COO indirection —
    ``(coo_src, coo_dest, coo_val)`` synapse triplets replayed from the
    control memories in O(nnz) — so packing never materializes the
    ``n_src x n_dest`` unrolled matrix on the host; the device scatters the
    triplets into the kernel's weight tile under jit.  Exactly one of the
    two representations is set (``None`` fields are empty pytree nodes).

    Compressed models (``map_model(compress=True)``) route EVERY round —
    dense and conv alike — as COO, with ``coo_widx`` (an index into the
    model-wide ``PackedModel.weight_dict``) in place of ``coo_val``: the
    device gathers ``weight_dict[coo_widx]`` and scatters, so the only
    per-synapse float storage on device is the shared dictionary.
    """

    tables: PackedTables
    w_dense: jax.Array | None       # f32 [n_src, n_dest_pad], global columns
    coo_src: jax.Array | None = None    # i32 [nnz]
    coo_dest: jax.Array | None = None   # i32 [nnz], global (padded) columns
    coo_val: jax.Array | None = None    # f32 [nnz]
    coo_widx: jax.Array | None = None   # i32 [nnz] into PackedModel.weight_dict


jax.tree_util.register_dataclass(
    PackedRound,
    data_fields=["tables", "w_dense", "coo_src", "coo_dest", "coo_val",
                 "coo_widx"],
    meta_fields=[])


@dataclasses.dataclass
class PackedLayer:
    rounds: list[PackedRound]
    n_src: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_dest: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_dest_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    # packed-operand path (pack_model(packed_ops=True)): the layer's fused
    # weight tile as sign-magnitude codes packed ``8/bits`` destination lanes
    # per int8 byte (quant.pack_signmag), plus the per-tensor quant scale —
    # event dispatch then routes through the event_synapse_packed kernel and
    # never materializes the f32 [n_src, n_dest_pad] tile on device
    w_packed: jax.Array | None = None   # i8 [n_src, n_dest_pad * bits / 8]
    scale: jax.Array | None = None      # f32 [1, 1]
    bits: int = dataclasses.field(metadata=dict(static=True), default=8)


jax.tree_util.register_dataclass(
    PackedLayer, data_fields=["rounds", "w_packed", "scale"],
    meta_fields=["n_src", "n_dest", "n_dest_pad", "bits"])


@dataclasses.dataclass
class PackedModel:
    layers: list[PackedLayer]
    lif: LIFParams = dataclasses.field(
        metadata=dict(static=True), default=LIFParams())
    spec: AcceleratorSpec | None = dataclasses.field(
        metadata=dict(static=True), default=None)
    block_d: int = dataclasses.field(
        metadata=dict(static=True), default=DEFAULT_BLOCK_D)
    # compressed models: shared f32 [K] dictionary of unique quantized A-SYN
    # words; rounds reference it through ``coo_widx`` (None = uncompressed)
    weight_dict: jax.Array | None = None

    @property
    def n_in(self) -> int:
        return self.layers[0].n_src

    @property
    def n_out(self) -> int:
        return self.layers[-1].n_dest


jax.tree_util.register_dataclass(
    PackedModel, data_fields=["layers", "weight_dict"],
    meta_fields=["lif", "spec", "block_d"])


def _pack_layer_codes(layer, w_host: np.ndarray, bits: int
                      ) -> tuple[jax.Array, jax.Array]:
    """Host-side operand packing for one layer: recover the integer codes
    from the replayed (dequantized) tile and pack them into sign-magnitude
    sub-byte lanes.  Exactness is *asserted*, not assumed: every stored
    table value must equal ``fl32(code * scale)`` bit for bit, which is what
    makes the packed kernel's in-device dequantization reproduce the dense
    path exactly."""
    scale = np.float32(layer.scale)
    q = np.rint(w_host / scale)
    qmax = 2 ** (bits - 1) - 1
    if np.abs(q).max(initial=0) > qmax:
        raise ValueError(
            f"recovered codes exceed the {bits}-bit range [-{qmax}, {qmax}] "
            f"— layer was not quantized at {bits} bits")
    if not (q.astype(np.float32) * scale == w_host).all():
        raise ValueError(
            "packed-operand exactness violated: table values are not "
            "fl32(code * scale) — the layer's stored weights do not come "
            "from quantize_symmetric at this scale")
    w_packed = pack_signmag(q.astype(np.int8), bits)
    return (jnp.asarray(w_packed),
            jnp.asarray(scale, jnp.float32).reshape(1, 1))


def pack_model(model: MappedModel, block_d: int = DEFAULT_BLOCK_D,
               packed_ops: bool = False) -> PackedModel:
    """Build the device-ready pytree from a mapped model.  The effective
    weights are replayed from the control memories (``MemTables
    .dense_weights`` / ``.replay_coo``), not taken from the original
    matrices — the batched engine executes what is actually in the SRAM.
    Shared-weight (conv) layers replay as COO triplets so the host never
    materializes the unrolled ``n_src x n_dest`` matrix per layer.

    ``packed_ops=True`` ships every layer's weight tile as *packed
    sign-magnitude codes* (``8/bits`` destination lanes per int8 byte) plus
    the layer scale: the on-device weight footprint shrinks from 4 bytes to
    ``bits/8`` bytes per synapse slot and dispatch routes through the
    ``event_synapse_packed`` kernel, which unpacks the ladder words next to
    the MACs.  The replayed values still come from the control memories, and
    packing asserts ``fl32(code * scale)`` reproduces them bit for bit, so
    the packed engine stays bit-exact with the unpacked one at every
    bit-width (tested).  ``MappedModel.pack(packed_ops=None)`` auto-selects
    this path when any layer is quantized below 8 bits."""
    compressed = getattr(model, "weight_dict", None) is not None
    wdict_np = np.asarray(model.weight_dict, dtype=np.float32) \
        if compressed else None
    layers = []
    for layer in model.layers:
        # always recorded (prices sample_energy); only packed_ops uses it to
        # select the packed kernel route
        bits = check_bits(int(getattr(layer, "bits", 8)))
        ell = lanes_per_byte(bits)
        n_dest_pad = _pad_dest(layer.n_dest, block_d)
        if packed_ops:
            if block_d % lanes_per_byte(2):
                raise ValueError(
                    f"packed operands need block_d divisible by "
                    f"{lanes_per_byte(2)} byte lanes; got {block_d}")
            # byte lanes must tile evenly: round the padded width up to a
            # whole number of packed bytes (extra columns carry 0-codes,
            # contribute exact 0.0 currents, and are sliced off post-LIF)
            n_dest_pad = -(-n_dest_pad // ell) * ell
        shared = getattr(layer, "shared_weights", False)
        rounds = []
        # packed layers replay the fused tile on the host instead of
        # shipping per-round dense/COO weight data to the device
        w_host = np.zeros((layer.n_src, n_dest_pad), dtype=np.float32) \
            if packed_ops else None
        for rnd in layer.rounds:
            if compressed:
                # every round replays through the shared-dictionary
                # indirection: (src, dest, widx) triplets, values gathered
                # on device from PackedModel.weight_dict under jit
                src, dest_local, widx = rnd.tables.replay_coo_ptr()
                dest = rnd.neuron_ids[dest_local]
                if packed_ops:
                    np.add.at(w_host, (src, dest), wdict_np[widx])
                    rounds.append(PackedRound(tables=rnd.tables.to_jax(),
                                              w_dense=None))
                    continue
                rounds.append(PackedRound(
                    tables=rnd.tables.to_jax(), w_dense=None,
                    coo_src=jnp.asarray(src, dtype=jnp.int32),
                    coo_dest=jnp.asarray(dest, dtype=jnp.int32),
                    coo_widx=jnp.asarray(widx, dtype=jnp.int32)))
            elif shared:
                src, dest_local, vals = rnd.tables.replay_coo()
                dest = rnd.neuron_ids[dest_local]
                if packed_ops:
                    np.add.at(w_host, (src, dest), vals)
                    rounds.append(PackedRound(tables=rnd.tables.to_jax(),
                                              w_dense=None))
                    continue
                rounds.append(PackedRound(
                    tables=rnd.tables.to_jax(), w_dense=None,
                    coo_src=jnp.asarray(src, dtype=jnp.int32),
                    coo_dest=jnp.asarray(dest, dtype=jnp.int32),
                    coo_val=jnp.asarray(vals)))
            else:
                w_local = rnd.tables.dense_weights(len(rnd.neuron_ids))
                if packed_ops:
                    w_host[:, rnd.neuron_ids] += w_local
                    rounds.append(PackedRound(tables=rnd.tables.to_jax(),
                                              w_dense=None))
                    continue
                w_glob = np.zeros((layer.n_src, n_dest_pad), dtype=np.float32)
                w_glob[:, rnd.neuron_ids] = w_local
                rounds.append(PackedRound(tables=rnd.tables.to_jax(),
                                          w_dense=jnp.asarray(w_glob)))
        w_packed = scale = None
        if packed_ops:
            w_packed, scale = _pack_layer_codes(layer, w_host, bits)
        layers.append(PackedLayer(rounds=rounds, n_src=layer.n_src,
                                  n_dest=layer.n_dest, n_dest_pad=n_dest_pad,
                                  w_packed=w_packed, scale=scale, bits=bits))
    wdict = jnp.asarray(wdict_np) if compressed and not packed_ops else None
    return PackedModel(layers=layers, lif=model.lif, spec=model.spec,
                       block_d=block_d, weight_dict=wdict)


# --------------------------------------------------------------- jitted core

_trace_count = 0


def trace_count() -> int:
    """How many times a jitted engine forward has been (re)traced — the jit
    cache-stability probe used by tests and benchmarks.  Covers both the
    single-device ``run_batched`` path and the ``run_sharded`` mesh path
    (:mod:`repro.engine.sharded_run`), which bumps the same counter."""
    return _trace_count


def _bump_trace() -> None:
    """Called from inside traced function bodies: python side effects execute
    exactly once per (re)trace, which is precisely what we want to count."""
    global _trace_count
    _trace_count += 1


def _lif_scan(currents: jax.Array, lif: LIFParams) -> jax.Array:
    """LIF over ``currents[B, T, n]`` via the shared ``lax.scan`` rollout
    (`repro.core.lif`) — operation-for-operation the oracle's update, so
    float32 results match; the unused voltage trace is dead-code-eliminated
    under jit."""
    spikes, _ = lif_rollout(currents.transpose(1, 0, 2), lif)
    return spikes.transpose(1, 0, 2)


def _layer_weights(layer: PackedLayer,
                   weight_dict: jax.Array | None = None) -> jax.Array:
    """Fuse a layer's rounds into one ``[n_src, n_dest_pad]`` weight tile
    for the event_synapse kernel.  Dense rounds add; COO (shared-weight)
    rounds scatter their synapse triplets — on device, under jit, O(nnz);
    compressed rounds gather their values from the model-wide
    ``weight_dict`` first (``coo_widx`` indirection).  Rounds target
    disjoint destination columns and each (src, dest) pair occurs at most
    once, so addition order cannot change any bit."""
    dense = [r.w_dense for r in layer.rounds if r.w_dense is not None]
    coo = [r for r in layer.rounds if r.w_dense is None]
    w = functools.reduce(jnp.add, dense) if dense else \
        jnp.zeros((layer.n_src, layer.n_dest_pad), jnp.float32)
    if coo:
        src = jnp.concatenate([r.coo_src for r in coo])
        dest = jnp.concatenate([r.coo_dest for r in coo])
        val = jnp.concatenate([
            r.coo_val if r.coo_val is not None else weight_dict[r.coo_widx]
            for r in coo])
        w = w.at[src, dest].add(val)
    return w


def _forward_impl(packed: PackedModel, spikes: jax.Array,
                  max_events: int | None) -> list[jax.Array]:
    """Per-layer output spike trains ([B, T, n_dest] each; the last entry is
    the model output).  Dispatch = MEM_E write + event_synapse kernel; LIF =
    one scan per layer.  Pure traced body — shared verbatim by the jitted
    single-device entry below and the per-shard body of
    :func:`repro.engine.sharded_run.run_sharded`, which is what makes the
    mesh path bit-exact by construction."""
    b, t, _ = spikes.shape
    outs = []
    for i, layer in enumerate(packed.layers):
        # stable scope paths (layer<i>/mem_e|synapse|lif) in every op's
        # metadata, so a profile names each step's device time
        with jax.named_scope(f"layer{i}"):
            with jax.named_scope("mem_e"):
                events = ops.events_from_spikes(
                    spikes.reshape(b * t, layer.n_src),
                    _mem_e_depth(layer, max_events))
            with jax.named_scope("synapse"):
                if layer.w_packed is not None:
                    # packed-operand route: the kernel gathers sub-byte
                    # ladder words and dequantizes in-device — no f32
                    # weight tile exists
                    currents = ops.event_synapse_packed(
                        events, layer.w_packed, layer.scale,
                        bits=layer.bits, block_d=packed.block_d)
                else:
                    # rounds target disjoint dest columns -> one fused
                    # kernel call
                    w = _layer_weights(layer, packed.weight_dict)
                    currents = ops.event_synapse(events, w,
                                                 block_d=packed.block_d)
            with jax.named_scope("lif"):
                out = _lif_scan(currents.reshape(b, t, layer.n_dest_pad),
                                packed.lif)
                spikes = out[..., :layer.n_dest]
        outs.append(spikes)
    return outs


@functools.partial(jax.jit, static_argnames=("max_events",))
def _forward(packed: PackedModel, spikes: jax.Array,
             max_events: int | None) -> list[jax.Array]:
    _bump_trace()
    return _forward_impl(packed, spikes, max_events)


@functools.partial(jax.jit, static_argnames=("max_events",),
                   donate_argnums=(1,))
def _forward_donated(packed: PackedModel, spikes: jax.Array,
                     max_events: int | None) -> list[jax.Array]:
    """`_forward` with the input spike buffer donated back to the
    allocator: on accelerator backends the padded bucket buffer a serving
    dispatch uploads is recycled into the outputs instead of surviving the
    call — so back-to-back dispatches of the same bucket never accumulate
    input copies.  A separate jit entry (donation is a property of the
    compiled executable, not the call), chosen by ``run_batched(donate=)``;
    CPU XLA implements no donation, so the single-device default stays off
    there."""
    _bump_trace()
    return _forward_impl(packed, spikes, max_events)


def should_donate(donate: bool | None) -> bool:
    """Resolve a ``donate`` tri-state: ``None`` means "on unless the
    backend is CPU" — the shared default of ``run_batched``,
    ``run_sharded``, and the serving front ends."""
    return jax.default_backend() != "cpu" if donate is None else donate


# ------------------------------------------------------------ batched result

@dataclasses.dataclass
class BatchedDispatchStats:
    """Per-sample, per-step dispatch statistics (``[B, T]`` int64 arrays);
    ``sample(b)`` recovers the oracle's :class:`DispatchStats` exactly."""

    cycles: np.ndarray
    rows_touched: np.ndarray
    engine_ops: np.ndarray
    events: np.ndarray
    sn_bytes_touched: np.ndarray
    mem_e_peak: np.ndarray      # [B]

    def sample(self, b: int) -> DispatchStats:
        return DispatchStats(
            cycles=self.cycles[b], rows_touched=self.rows_touched[b],
            engine_ops=self.engine_ops[b], events=self.events[b],
            sn_bytes_touched=self.sn_bytes_touched[b],
            mem_e_peak=int(self.mem_e_peak[b]))


@dataclasses.dataclass
class BatchedRunResult:
    out_spikes: np.ndarray                       # [B, T, n_out]
    per_layer_stats: list[BatchedDispatchStats]
    per_layer_util: list[np.ndarray]             # [B, T] float64
    overflow: list[np.ndarray]                   # [B, T] events dropped
    spec: AcceleratorSpec | None = None
    per_layer_bits: list[int] | None = None      # stored word widths (energy)

    @property
    def batch(self) -> int:
        return self.out_spikes.shape[0]

    def sample_stats(self, b: int) -> list[DispatchStats]:
        return [s.sample(b) for s in self.per_layer_stats]

    def sample_energy(self, b: int,
                      frame_cycles: int | None = FRAME_CYCLES) -> EnergyReport:
        """Same signature as :func:`repro.core.energy.energy_model`:
        ``frame_cycles`` defaults to the calibrated frame period, ``None``
        means throughput mode.  Mixed-precision models price the C2C MAC
        energy at each layer's stored word width (``per_layer_bits``)."""
        assert self.spec is not None, "pack_model carried no AcceleratorSpec"
        return energy_model(self.spec, self.sample_stats(b),
                            frame_cycles=frame_cycles,
                            per_core_bits=self.per_layer_bits)


def _layer_stats(in_spikes: np.ndarray, layer: PackedLayer,
                 max_events: int | None,
                 sn_capacity_rows: int | None
                 ) -> tuple[BatchedDispatchStats, np.ndarray, np.ndarray]:
    """Vectorized dispatch accounting for one layer: every per-step counter
    is a dot product of the accepted-event raster with a per-source table
    vector, reproducing the oracle's Python accumulation in int64.

    A finite MEM_E depth accepts only the ``depth`` lowest source indices
    per step (FIFO write order) — dropped events arrive (``events``) but
    dispatch nothing, exactly as the kernel path truncates them."""
    sp = (in_spikes > 0)
    b, t, _ = sp.shape
    depth = _mem_e_depth(layer, max_events)
    if depth >= layer.n_src:
        keep = sp                       # cap can never bind
    else:
        keep = sp & (np.cumsum(sp, axis=2) <= depth)
    shape = (b, t)
    cycles = np.zeros(shape, dtype=np.int64)
    rows = np.zeros(shape, dtype=np.int64)
    mac = np.zeros(shape, dtype=np.int64)
    bytes_t = np.zeros(shape, dtype=np.int64)
    util = np.zeros(shape, dtype=np.float64)
    total_rows = sum(r.tables.n_rows for r in layer.rounds)
    cap = sn_capacity_rows or max(total_rows, 1)
    for rnd in layer.rounds:
        rows_v, cyc_v, ops_v = rnd.tables.stats_vectors()
        r_rows = keep @ rows_v
        cycles += keep @ cyc_v
        rows += r_rows
        mac += keep @ ops_v
        bytes_t += r_rows * rnd.tables.row_bytes
        util += r_rows.astype(np.float64) / cap
    events = sp.sum(axis=2, dtype=np.int64)
    overflow = np.maximum(events - depth, 0)
    stats = BatchedDispatchStats(cycles=cycles, rows_touched=rows,
                                 engine_ops=mac, events=events,
                                 sn_bytes_touched=bytes_t,
                                 mem_e_peak=np.minimum(events, depth)
                                 .max(axis=1, initial=0))
    return stats, util, overflow


def _finalize(packed: PackedModel, in_spikes: np.ndarray,
              layer_outs: list[jax.Array], max_events: int | None,
              sn_capacity_rows: int | None,
              with_stats: bool) -> BatchedRunResult:
    """Device outputs -> :class:`BatchedRunResult`, including the host-side
    dispatch accounting.  Shared by ``run_batched`` and ``run_sharded`` so
    the two entry points cannot drift apart on the stats surface."""
    with stage("fetch"):
        out = np.asarray(layer_outs[-1])
    bits = [l.bits for l in packed.layers]
    if not with_stats:
        return BatchedRunResult(out_spikes=out, per_layer_stats=[],
                                per_layer_util=[], overflow=[],
                                spec=packed.spec, per_layer_bits=bits)
    stats_all, util_all, drop_all = [], [], []
    layer_in = np.asarray(in_spikes, dtype=np.float32)
    for li, layer in enumerate(packed.layers):
        stats, util, overflow = _layer_stats(layer_in, layer, max_events,
                                             sn_capacity_rows)
        stats_all.append(stats)
        util_all.append(util)
        drop_all.append(overflow)
        layer_in = np.asarray(layer_outs[li])
    return BatchedRunResult(out_spikes=out, per_layer_stats=stats_all,
                            per_layer_util=util_all, overflow=drop_all,
                            spec=packed.spec, per_layer_bits=bits)


def run_batched(model: MappedModel | PackedModel, in_spikes: np.ndarray,
                *, max_events: int | None = None,
                sn_capacity_rows: int | None = None,
                with_stats: bool = True,
                donate: bool | None = None) -> BatchedRunResult:
    """Execute a batch of spike trains ``[B, T, n_in]`` through the chain.

    Bit-exact vs. the oracle ``run`` called with the same ``max_events``
    (tested, including finite caps).  A tight ``max_events`` models the
    finite MEM_E depth: excess events are dropped lowest-priority-last
    (ascending source index kept) before dispatch, counted per step in
    ``result.overflow``, and the loss propagates to downstream layers
    through the LIF exactly as on the oracle.

    Degenerate shapes are valid inputs: ``B=0`` returns an empty result
    (empty stats arrays, no crash), ``T=1`` and all-silent batches follow
    the ordinary path.  ``with_stats=False`` skips the (host-side)
    accounting — the serving configuration, where only the output spikes
    matter.  ``donate`` hands the uploaded spike buffer to the jit for
    reuse (default: on unless the backend is CPU, which lacks donation).
    """
    packed = model if isinstance(model, PackedModel) else model.pack()
    with stage("upload"):
        spikes = jnp.asarray(np.asarray(in_spikes, dtype=np.float32))
    assert spikes.ndim == 3 and spikes.shape[2] == packed.n_in, \
        f"expected [B, T, {packed.n_in}], got {spikes.shape}"
    fwd = _forward_donated if should_donate(donate) else _forward
    with stage("launch"):
        layer_outs = fwd(packed, spikes, max_events)
    return _finalize(packed, np.asarray(in_spikes, dtype=np.float32),
                     layer_outs, max_events, sn_capacity_rows, with_stats)
