"""Unified sharded SNN training engine.

The paper's evaluation models (§IV-A) train with surrogate gradients; this
module is the single production path for that training, replacing the two
hand-rolled single-device Adam loops that used to live in ``snn/mlp.py`` and
``snn/conv.py``.  One entry point — :func:`train_snn_model` — drives any
:class:`SNNModel` (MLP or conv) through the *same* machinery the transformer
stack trains with:

  * **engine/train_loop.py** — async atomic checkpoints, elastic restart
    onto a different mesh, straggler detection, step-keyed restart-safe
    data.
  * **optim/adamw.py** — :func:`adamw_update` with the base learning rate
    passed as a *dynamic* scalar, so an LR schedule changes the rate every
    step without retracing the jitted train step (the old loops made ``lr``
    a static argname and retraced per value).
  * **parallel/sharding.py** — a new ``SNN_TRAIN_RULES`` table: the spike
    batch shards over the ``("data",)`` mesh exactly like serving, params
    and optimizer state stay replicated, and a batch the mesh cannot split
    degrades gracefully to replicated execution (mirroring ``run_sharded``).

Bit-exactness contract (the serving suite's equivalence discipline, applied
to training): the gradient of a step is *defined* as a fixed-order left fold
over ``grad_shards`` contiguous batch chunks of per-chunk gradients, scaled
by ``1/K``.  The mesh only decides *where* chunks are computed — each device
evaluates its contiguous chunk(s) with the same traced chunk body, the
per-chunk results are ``all_gather``-ed in device order (= global chunk
order) and folded left-to-right, a deterministic psum.  Sharding therefore
cannot change a single bit: training on a 1×N spoofed mesh is bit-exact with
single-device training for the same ``grad_shards`` and data order, and a
checkpoint written on an 8-device mesh resumes on 4 devices onto the *same*
loss trajectory (tested, ``tests/test_snn_train.py``).  ``grad_shards``
defaults to the mesh's split of the batch (1 without a mesh), so the default
single-device configuration pays no chunking overhead.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from typing import Any, Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.engine.batched_run import should_donate
from repro.engine.sharded_run import snn_serve_mesh
from repro.engine.train_loop import (TrainLoopConfig, init_train_state,
                                     resume_or_init, train_loop)
from repro.optim.adamw import AdamWConfig, adamw_update
from repro.parallel.sharding import SNN_TRAIN_RULES, ShardingRules
from repro.snn import conv as _conv
from repro.snn import mlp as _mlp

_log = logging.getLogger(__name__)


# ------------------------------------------------------------ model protocol

@runtime_checkable
class SNNModel(Protocol):
    """What the unified trainer needs from a model family.

    ``spikes`` are time-major ``[T, B, n_in]`` (the ``lax.scan`` training
    layout); ``loss`` returns ``(mean_loss, mean_accuracy)`` over the batch,
    differentiable through the surrogate-gradient LIF; ``layer_specs``
    lowers trained (possibly pruned) params to the ``map_model`` stack.
    """

    name: str

    def init(self, key: jax.Array, cfg) -> Any: ...

    def forward(self, params, spikes: jax.Array, cfg): ...

    def loss(self, params, spikes: jax.Array, labels: jax.Array, cfg): ...

    def layer_specs(self, params, cfg) -> list: ...


class _MLPModel:
    """The paper's spiking MLPs (``snn/mlp.py``) behind the protocol."""

    name = "mlp"

    def init(self, key, cfg: "_mlp.SNNConfig"):
        return _mlp.init_snn(key, cfg)

    def forward(self, params, spikes, cfg: "_mlp.SNNConfig"):
        return _mlp.snn_forward(params, spikes, cfg)

    def loss(self, params, spikes, labels, cfg: "_mlp.SNNConfig"):
        return _mlp.snn_loss(params, spikes, labels, cfg)

    def layer_specs(self, params, cfg: "_mlp.SNNConfig"):
        # bare 2-D matrices; map_model coerces them to Dense specs
        return [np.asarray(w) for w in params]


class _ConvModel:
    """The spiking CNN family (``snn/conv.py``) behind the protocol."""

    name = "conv"

    def init(self, key, cfg: "_conv.ConvSNNConfig"):
        return _conv.init_conv_snn(key, cfg)

    def forward(self, params, spikes, cfg: "_conv.ConvSNNConfig"):
        return _conv.conv_snn_forward(params, spikes, cfg)

    def loss(self, params, spikes, labels, cfg: "_conv.ConvSNNConfig"):
        return _conv.conv_snn_loss(params, spikes, labels, cfg)

    def layer_specs(self, params, cfg: "_conv.ConvSNNConfig"):
        return _conv.layer_specs(params, cfg)


MLP_MODEL: SNNModel = _MLPModel()
CONV_MODEL: SNNModel = _ConvModel()


def model_for(cfg) -> SNNModel:
    """The model family matching a config dataclass."""
    if isinstance(cfg, _conv.ConvSNNConfig):
        return CONV_MODEL
    if isinstance(cfg, _mlp.SNNConfig):
        return MLP_MODEL
    raise TypeError(f"no SNN model family for config {type(cfg).__name__}")


# ------------------------------------------------------------- configuration

@dataclasses.dataclass(frozen=True)
class SNNTrainConfig:
    """Hyperparameters + loop/sharding knobs for :func:`train_snn_model`.

    The defaults are the paper's Table-I Adam (lr=1e-3, b2=0.999, no weight
    decay, no clipping, constant rate).  ``lr`` may be a schedule
    ``step -> rate``; it reaches the step as a dynamic scalar, so schedules
    never retrace.  ``mesh`` turns on data-parallel sharding over the
    ``SNN_TRAIN_RULES`` axes; ``grad_shards`` pins the gradient's chunked
    fold order independent of the mesh (see module docstring) — ``None``
    means "however many ways the mesh splits the batch".  ``checkpoint_dir``
    ``None`` trains ephemerally (no checkpoint I/O at all); a real
    path makes training resume-aware across restarts and mesh sizes.
    """

    steps: int = 100
    lr: "float | Callable[[int], float]" = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = math.inf
    warmup_steps: int = 1
    mesh: Mesh | None = None
    grad_shards: int | None = None
    checkpoint_dir: str | None = None
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    log_every: int = 50
    straggler_factor: float = 3.0
    donate: bool | None = None      # None: on unless the backend is CPU

    def adamw(self) -> AdamWConfig:
        base = self.lr if not callable(self.lr) else self.lr(0)
        return AdamWConfig(lr=float(base), b1=self.b1, b2=self.b2,
                           eps=self.eps, weight_decay=self.weight_decay,
                           grad_clip=self.grad_clip,
                           warmup_steps=self.warmup_steps)


def snn_train_mesh(n_data: int | None = None) -> Mesh:
    """A 1-D ``("data",)`` host mesh over ``n_data`` devices (default: all
    visible) — literally the serving stack's pure-DP topology
    (:func:`repro.engine.sharded_run.snn_serve_mesh`), so training and
    serving can never drift onto different meshes."""
    return snn_serve_mesh(n_data)


# ---------------------------------------------------------------- train step

_train_traces = 0


def snn_train_trace_count() -> int:
    """How many times the unified SNN train step has been (re)traced — the
    regression probe for the dynamic-lr contract (two different learning
    rates through the same step must cost exactly one trace)."""
    return _train_traces


def _bump_train_trace() -> None:
    global _train_traces
    _train_traces += 1


def _batch_split(mesh: Mesh, dims: tuple[int, int, int]):
    """How the training rules shard a ``[T, B, n_in]`` spike batch on
    ``mesh``: returns ``(n_shards, spikes_spec, labels_spec, axes)`` with
    the same graceful degradation as serving — a batch the mesh cannot
    split evenly replicates (``n_shards == 1``) instead of crashing."""
    rules = ShardingRules(mesh, SNN_TRAIN_RULES)
    spec = rules.spec(("event_time", "event_batch", "neuron"), dims)
    axes = spec[1]
    if axes is None:
        return 1, spec, PartitionSpec(), ()
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n, spec, PartitionSpec(spec[1]), axes


def make_snn_train_step(model: SNNModel, cfg, opt_cfg: AdamWConfig, *,
                        mesh: Mesh | None = None,
                        grad_shards: int | None = None,
                        donate: bool | None = None):
    """Build the jitted unified step ``(state_tree, batch) -> (state_tree,
    metrics)`` for :func:`repro.engine.train_loop.train_loop`.

    ``batch`` is ``{"spikes": [T, B, n_in], "labels": [B], "lr": scalar}``
    (``lr`` optional — dynamic base rate for :func:`adamw_update`).  The
    gradient is the fixed-order chunked fold described in the module
    docstring: ``K = grad_shards`` chunks (default: the mesh's split of B,
    1 without a mesh), each chunk's ``value_and_grad`` of the model's mean
    loss, summed left-to-right and scaled by ``1/K``.  With a mesh, the
    chunk work distributes over the devices via ``shard_map`` (params
    replicated per ``SNN_TRAIN_RULES``); ``K`` must be a multiple of the
    mesh's split so every device owns whole chunks.
    """

    def chunk_body(params, chunk):
        spikes, labels = chunk
        (l, a), g = jax.value_and_grad(model.loss, has_aux=True)(
            params, spikes, labels, cfg)
        return l, a, g

    def chunked(params, spikes, labels, k):
        """Stacked per-chunk (loss, acc, grads) over ``k`` contiguous
        batch chunks of a time-major ``[T, b, n]`` shard."""
        t, b, n = spikes.shape
        sc = jnp.moveaxis(spikes.reshape(t, k, b // k, n), 1, 0)
        lc = labels.reshape(k, b // k)
        return jax.lax.map(functools.partial(chunk_body, params), (sc, lc))

    def fold(stacked, k):
        """Left-to-right sum over the leading chunk axis — the
        deterministic psum that fixes the reduction order."""
        chunks = [jax.tree.map(lambda x: x[i], stacked) for i in range(k)]
        return functools.reduce(
            lambda u, v: jax.tree.map(jnp.add, u, v), chunks)

    def step(state: dict, batch: dict):
        _bump_train_trace()
        spikes, labels = batch["spikes"], batch["labels"]
        t, b, n = spikes.shape
        n_split, spikes_spec, labels_spec, axes = (
            _batch_split(mesh, (t, b, n)) if mesh is not None
            else (1, None, None, ()))
        k = n_split if grad_shards is None else grad_shards
        assert b % k == 0, \
            f"batch {b} not divisible into grad_shards={k} chunks"
        # graceful fallbacks replicate instead of crashing, but must not be
        # silent: a user who built a mesh believes they get DP throughput
        # (trace-time python, so each warning logs once per batch shape)
        if k % n_split != 0:
            _log.warning(
                "snn_train: grad_shards=%d is not a multiple of the mesh's "
                "%d-way batch split — training replicated on one device "
                "instead of data-parallel", k, n_split)
            n_split = 1
        elif mesh is not None and mesh.size > 1 and n_split == 1:
            _log.warning(
                "snn_train: batch %d does not split over the %d-device "
                "mesh — training replicated on one device instead of "
                "data-parallel", b, mesh.size)
        if mesh is not None and n_split > 1:
            def body(params, sp, lb):
                local = chunked(params, sp, lb, k // n_split)
                return jax.lax.all_gather(local, axes, tiled=True)

            stacked = jax.shard_map(
                body, mesh=mesh,
                in_specs=(PartitionSpec(), spikes_spec, labels_spec),
                out_specs=PartitionSpec(), check_vma=False)(
                    state["params"], spikes, labels)
        else:
            stacked = chunked(state["params"], spikes, labels, k)
        loss, acc, grads = fold(stacked, k)
        inv = 1.0 / k
        loss, acc = loss * inv, acc * inv
        grads = jax.tree.map(lambda g: g * inv, grads)
        params, opt, metrics = adamw_update(
            opt_cfg, state["params"], state["opt"], grads,
            lr=batch.get("lr"))
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["acc"] = acc
        return {"params": params, "opt": opt}, metrics

    return jax.jit(step,
                   donate_argnums=(0,) if should_donate(donate) else ())


# --------------------------------------------------------------- entry point

def train_snn_model(model: SNNModel, cfg, data_iter,
                    train_cfg: SNNTrainConfig, *,
                    key: jax.Array | None = None, params=None,
                    log_fn: Callable[[str], None] = print):
    """Train an SNN family through the production engine loop.

    ``data_iter`` is either a step-keyed callable ``step -> (spikes
    [T, B, n_in], labels [B])`` — the restart-safe form: resuming from a
    checkpoint replays the exact remaining batches — or any iterator
    yielding such pairs (``data/events.event_batches``), which trains fine
    but cannot guarantee the same batches after a restart.

    Returns ``(params, history)``; ``history`` is the train-loop dict
    (``loss`` / ``acc`` / ``step_time`` / ``stragglers`` /
    ``checkpoints``).
    """
    if params is None:
        params = model.init(key if key is not None else jax.random.key(0),
                            cfg)
    elif should_donate(train_cfg.donate):
        # the jitted step donates its state; copy caller-supplied params so
        # the caller's arrays survive the first update (warm starts,
        # before/after comparisons)
        params = jax.tree.map(lambda p: jnp.array(p, copy=True), params)
    opt_cfg = train_cfg.adamw()
    state = init_train_state(None, params, opt_cfg).as_tree()
    step_fn = make_snn_train_step(model, cfg, opt_cfg, mesh=train_cfg.mesh,
                                  grad_shards=train_cfg.grad_shards,
                                  donate=train_cfg.donate)
    if callable(data_iter):
        data = data_iter
    else:
        it = iter(data_iter)
        data = lambda step: next(it)  # noqa: E731
    lr = train_cfg.lr
    lr_of = lr if callable(lr) else (lambda step: lr)

    def batch_fn(step: int) -> dict:
        spikes, labels = data(step)
        return {"spikes": jnp.asarray(spikes, dtype=jnp.float32),
                "labels": jnp.asarray(labels),
                "lr": jnp.asarray(lr_of(step), dtype=jnp.float32)}

    loop_cfg = TrainLoopConfig(steps=train_cfg.steps,
                               checkpoint_every=train_cfg.checkpoint_every,
                               checkpoint_dir=train_cfg.checkpoint_dir,
                               log_every=train_cfg.log_every,
                               straggler_factor=train_cfg.straggler_factor,
                               keep_checkpoints=train_cfg.keep_checkpoints)
    start = 0
    if train_cfg.checkpoint_dir is not None:
        state, start = resume_or_init(loop_cfg, state)
        if start:
            log_fn(f"[snn_train] resumed {model.name} from step {start} "
                   f"({train_cfg.checkpoint_dir})")
    state, history = train_loop(state, step_fn, batch_fn, loop_cfg,
                                start_step=start, log_fn=log_fn)
    return state["params"], history
