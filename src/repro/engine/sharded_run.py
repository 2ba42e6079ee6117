"""Data-parallel sharded serving of the batched MENAGE engine.

:func:`run_sharded` executes the same packed control-memory pytree as
``run_batched``, but ``shard_map``-ped over a host mesh: the spike batch is
split along the mesh's data axes while the :class:`PackedModel` — the
MEM_E2A / MEM_S&N tables and the replayed A-SYN weights — is replicated on
every device, mirroring how the silicon replicates a full MX-NEURACORE chain
per die.  Which axes shard is decided by the same logical-axis rule
machinery the transformer stack uses (:mod:`repro.parallel.sharding`,
``SNN_SERVE_RULES``): ``event_batch`` maps to ``("pod", "data")``,
``event_time`` and ``neuron`` stay local, and a batch the mesh cannot split
evenly degrades gracefully to replicated execution instead of crashing.

Equivalence contract (tested, ``tests/test_sharded_engine.py``): every
sample's dispatch is independent — the kernel grid is per-(sample,
dest-block) and the LIF scan never mixes batch rows — so sharding the batch
axis cannot change any bit.  ``run_sharded`` returns the identical
:class:`BatchedRunResult` surface (spikes, DispatchStats, utilization,
overflow, energy) as single-device ``run_batched``, and therefore stays
bit-exact against the numpy oracle.

Serving notes:

  * jit cache: one compiled executable per (mesh, partition spec, shapes);
    the front end (:mod:`repro.engine.serving`) pads requests into a small
    fixed set of ``(B, T)`` buckets so the trace count stays bounded — the
    shared ``trace_count()`` probe counts this path too.
  * donation: on accelerator backends the padded input-spike buffer is
    donated back to the allocator between steps (``donate=True`` default
    off-CPU; CPU XLA does not implement buffer donation and would warn).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.engine import batched_run as br
from repro.engine.tracing import stage
from repro.parallel.sharding import SNN_SERVE_RULES, ShardingRules


def snn_serve_mesh(n_data: int | None = None) -> Mesh:
    """A 1-D ``("data",)`` host mesh over ``n_data`` devices (default: all
    visible devices) — the serving topology for pure-DP event streaming."""
    n = len(jax.devices()) if n_data is None else n_data
    return jax.make_mesh((n,), ("data",))


class DeviceLossError(RuntimeError):
    """Devices dropped out mid-serving.  Raised by hardware watchdogs in
    production and by chaos hooks in the soak harness
    (:mod:`repro.engine.chaos`); :class:`repro.engine.stream_server
    .StreamServer` catches it at the dispatch boundary and recovers onto
    the shrunken mesh — the serving-side twin of the train loop's elastic
    restart (checkpoints are sharding-agnostic there; here the replicated
    control memories are, so recovery is re-placement, not reload)."""

    def __init__(self, n_lost: int = 1, detail: str = ""):
        self.n_lost = int(n_lost)
        msg = f"lost {self.n_lost} device(s) mid-serving"
        super().__init__(msg + (f": {detail}" if detail else ""))


def shrink_mesh(mesh: Mesh, n_lost: int) -> Mesh:
    """The serving mesh after ``n_lost`` devices drop: a fresh 1-D data
    mesh over the surviving devices.  Because the :class:`PackedModel` is
    replicated (every device holds the full control-memory chain), any
    subset of survivors can serve — recovery needs no state movement, only
    a re-shard of future batches.  Raises :class:`DeviceLossError` when no
    device survives (nothing to recover onto)."""
    assert len(mesh.axis_names) == 1, \
        f"shrink_mesh handles 1-D serving meshes, got axes {mesh.axis_names}"
    survivors = mesh.size - n_lost
    if survivors < 1:
        raise DeviceLossError(n_lost, f"all {mesh.size} devices lost")
    devs = np.asarray(mesh.devices).reshape(-1)[:survivors]
    return Mesh(devs, mesh.axis_names)


def batch_spec(mesh: Mesh, shape: tuple[int, int, int]) -> PartitionSpec:
    """PartitionSpec for a ``[B, T, n_in]`` spike tensor under the SNN
    serving rules: batch over the mesh's data axes when divisible, else
    dropped (replicated) — the rule machinery's graceful degradation."""
    rules = ShardingRules(mesh, SNN_SERVE_RULES)
    return rules.spec(("event_batch", "event_time", "neuron"), tuple(shape))


def n_batch_shards(mesh: Mesh, batch: int) -> int:
    """How many ways ``batch`` actually splits on ``mesh`` (1 = replicated)."""
    spec = batch_spec(mesh, (batch, 1, 1))
    axes = spec[0]
    if axes is None:
        return 1
    axes = (axes,) if isinstance(axes, str) else axes
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


@functools.lru_cache(maxsize=None)
def _sharded_forward(mesh: Mesh, spec: PartitionSpec, donate: bool):
    """Build (once per mesh/spec/donation mode) the jitted sharded forward.
    The per-shard body is ``batched_run._forward_impl`` — the very same
    traced computation as the single-device path, which is what makes the
    bit-exactness hold by construction rather than by luck."""

    def fwd(packed, spikes, max_events):
        br._bump_trace()
        body = functools.partial(br._forward_impl, max_events=max_events)
        mapped = jax.shard_map(body, mesh=mesh,
                               in_specs=(PartitionSpec(), spec),
                               out_specs=spec, check_vma=False)
        return mapped(packed, spikes)

    kwargs = dict(static_argnames=("max_events",))
    if donate:
        kwargs["donate_argnums"] = (1,)
    return jax.jit(fwd, **kwargs)


def run_sharded(model, in_spikes: np.ndarray, *,
                mesh: Mesh | None = None,
                max_events: int | None = None,
                sn_capacity_rows: int | None = None,
                with_stats: bool = True,
                donate: bool | None = None) -> "br.BatchedRunResult":
    """``run_batched`` over a device mesh: spikes ``[B, T, n_in]`` sharded on
    the batch axis, control memories replicated, results gathered back into
    the identical :class:`BatchedRunResult` surface.

    ``mesh`` defaults to a 1-D data mesh over all visible devices.  ``B``
    should be a multiple of the mesh's data-axis extent for actual
    parallelism (the serving bucket policy guarantees this; see
    ``BucketPolicy.for_mesh``); non-divisible batches run replicated.
    ``donate`` re-uses the input spike buffer on accelerator backends
    (default: on unless the backend is CPU, where XLA lacks donation).
    """
    packed = model if isinstance(model, br.PackedModel) else model.pack()
    spikes_np = np.asarray(in_spikes, dtype=np.float32)
    assert spikes_np.ndim == 3 and spikes_np.shape[2] == packed.n_in, \
        f"expected [B, T, {packed.n_in}], got {spikes_np.shape}"
    if spikes_np.shape[0] == 0:
        # nothing to shard; the single-device path owns the empty-batch case
        return br.run_batched(packed, spikes_np, max_events=max_events,
                              sn_capacity_rows=sn_capacity_rows,
                              with_stats=with_stats)
    mesh = snn_serve_mesh() if mesh is None else mesh
    spec = batch_spec(mesh, spikes_np.shape)
    fwd = _sharded_forward(mesh, spec, br.should_donate(donate))
    with stage("upload"):
        spikes = jnp.asarray(spikes_np)
    with stage("launch"):
        layer_outs = fwd(packed, spikes, max_events)
    return br._finalize(packed, spikes_np, layer_outs, max_events,
                        sn_capacity_rows, with_stats)
