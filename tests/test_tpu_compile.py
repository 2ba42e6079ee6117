"""Compile-only checks for a TPU v5e, with no chip attached.

The synapse kernels and the jitted serving forward are compiled at the
CIFAR10-DVS MLP's widths (``CIFAR_SNN``: 2048 -> 1000/500/200/100/10) for a
described ``v5e:2x2`` topology.  The TPU compiler refuses here what it would
refuse on the chip: block shapes the tiling does not accept, operations
Mosaic cannot lower, more VMEM than a kernel may use.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.menage_paper import CIFAR_SNN
from repro.engine import batched_run as br
from repro.kernels.event_synapse import (DEFAULT_BLOCK_D, event_synapse,
                                         event_synapse_packed)

ROWS = 8 * 32                       # B * T of the (8, 32) serving bucket
LAYERS = list(zip(CIFAR_SNN.layer_sizes[:-1], CIFAR_SNN.layer_sizes[1:]))
KERNEL = "tpu_custom_call"


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_src,n_dest", LAYERS)
def test_event_synapse_compiles_at_cifar_layer(one_chip, n_src, n_dest):
    n_dest_pad = br._pad_dest(n_dest, DEFAULT_BLOCK_D)
    text = _compiled_text(
        event_synapse, _sds(one_chip, (ROWS, n_src), jnp.int32),
        _sds(one_chip, (n_src, n_dest_pad), jnp.float32))
    assert KERNEL in text


def test_event_synapse_compiles_at_bench_layer0(one_chip):
    """Layer 0 of the benchmark's CIFAR10-DVS MLP (2x64x64 input: 8192
    sources -> 1000 destinations, padded to 1024) over the 512 rows of a
    (16, 32) bucket: the event loop's per-row bound, read from SMEM, must
    lower with the kernel."""
    n_src, rows = 8192, 16 * 32
    n_dest_pad = br._pad_dest(LAYERS[0][1], DEFAULT_BLOCK_D)
    text = _compiled_text(
        event_synapse, _sds(one_chip, (rows, n_src), jnp.int32),
        _sds(one_chip, (n_src, n_dest_pad), jnp.float32))
    assert KERNEL in text


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_event_synapse_packed_compiles_at_layer0(one_chip, bits):
    n_src, n_dest = LAYERS[0]
    n_bytes = br._pad_dest(n_dest, DEFAULT_BLOCK_D) * bits // 8
    text = _compiled_text(
        lambda e, w, s: event_synapse_packed(e, w, s, bits=bits),
        _sds(one_chip, (ROWS, n_src), jnp.int32),
        _sds(one_chip, (n_src, n_bytes), jnp.int8),
        _sds(one_chip, (1, 1), jnp.float32))
    assert KERNEL in text


@pytest.fixture(scope="module")
def cifar_mapped():
    """CIFAR_SNN with seeded random weights, pruned to 50%, mapped onto
    Accel_2 (host work only)."""
    from repro.configs.menage_paper import ACCEL_2
    from repro.core.accelerator import map_model

    rng = np.random.default_rng(0)
    ws = []
    for n_src, n_dest in LAYERS:
        w = rng.normal(0, np.sqrt(2 / n_src), (n_src, n_dest))
        w[np.abs(w) < np.quantile(np.abs(w), 0.5)] = 0
        ws.append(w.astype(np.float32))
    return map_model(ws, ACCEL_2, lif=CIFAR_SNN.lif)


@pytest.mark.parametrize("packed_ops", [False, True],
                         ids=["f32_tiles", "int8_codes"])
def test_forward_compiles_at_serving_bucket(one_chip, cifar_mapped,
                                            packed_ops, monkeypatch):
    """The jitted engine forward of the packed CIFAR_SNN model at bucket
    (8, 32), from shapes alone, on both weight layouts.  The kernels pick
    interpret mode from the process's backend, which is the CPU here, so
    the test steers them to the native path."""
    from repro.kernels import ops

    packed = cifar_mapped.pack(packed_ops=packed_ops)
    shapes = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), packed)
    spikes = _sds(one_chip, (8, 32, packed.n_in), jnp.float32)

    monkeypatch.setattr(ops, "_on_cpu", lambda: False)
    text = br._forward.lower(shapes, spikes, None).compile().as_text()
    assert text.count(KERNEL) >= len(LAYERS)
