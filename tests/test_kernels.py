"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.quant import pack_signmag
from repro.kernels import ops
from repro.kernels.event_synapse import (_event_operands,
                                         _events_from_spikes_argsort)
from repro.kernels.ref import (c2c_matmul_ladder_ref, c2c_matmul_ref,
                               event_synapse_packed_ref, event_synapse_ref,
                               lif_update_ref)


# ------------------------------------------------------------ event_synapse

@pytest.mark.parametrize("n_src,n_dest,block_d", [
    (16, 128, 128), (40, 512, 256), (100, 256, 64), (7, 384, 128),
])
def test_event_synapse_shapes(rng, n_src, n_dest, block_d):
    w = jnp.asarray(rng.normal(size=(n_src, n_dest)).astype(np.float32))
    spikes = jnp.asarray((rng.random((3, n_src)) < 0.3).astype(np.float32))
    ev = ops.events_from_spikes(spikes, max_events=n_src)
    out = ops.event_synapse(ev, w, block_d=block_d)
    np.testing.assert_allclose(out, event_synapse_ref(ev, w), atol=1e-5)


def test_event_synapse_all_padding(rng):
    w = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    ev = jnp.full((2, 4), -1, jnp.int32)
    out = ops.event_synapse(ev, w)
    assert np.all(np.asarray(out) == 0)


def test_events_from_spikes_roundtrip(rng):
    spikes = jnp.asarray((rng.random((5, 32)) < 0.4).astype(np.float32))
    ev = ops.events_from_spikes(spikes, max_events=32)
    for b in range(5):
        got = sorted(int(i) for i in np.asarray(ev[b]) if i >= 0)
        want = sorted(np.nonzero(np.asarray(spikes[b]))[0].tolist())
        assert got == want


def test_event_overflow_counting(rng):
    spikes = jnp.ones((1, 32))
    assert int(ops.overflow_count(spikes, 10)[0]) == 22
    ev = ops.events_from_spikes(spikes, 10)
    assert np.all(np.asarray(ev) >= 0) and ev.shape == (1, 10)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), density=st.floats(0.0, 0.9))
def test_event_synapse_property(seed, density):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(24, 256)).astype(np.float32))
    spikes = jnp.asarray((rng.random((2, 24)) < density).astype(np.float32))
    ev = ops.events_from_spikes(spikes, max_events=24)
    out = ops.event_synapse(ev, w)
    # equivalence with the dense matmul (the A-SYN contract)
    np.testing.assert_allclose(out, spikes @ w, atol=1e-4)


# ----------------------------------------------------- event_synapse_packed

def _random_codes(rng, n_src, n_dest, bits):
    qmax = 2 ** (bits - 1) - 1
    return rng.integers(-qmax, qmax + 1, (n_src, n_dest)).astype(np.int8)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("n_src,n_dest,block_d", [
    (16, 128, 128), (40, 256, 64), (7, 64, 32),
])
def test_event_synapse_packed_matches_ref(rng, bits, n_src, n_dest, block_d):
    """Packed sub-byte kernel == unpack-then-dense reference at every
    supported bit-width (tentpole contract; allclose — the reference
    reduces in a different order, bit-exactness is vs the dense kernel)."""
    q = _random_codes(rng, n_src, n_dest, bits)
    packed = jnp.asarray(pack_signmag(q, bits))
    scale = np.float32(0.013)
    spikes = jnp.asarray((rng.random((3, n_src)) < 0.3).astype(np.float32))
    ev = ops.events_from_spikes(spikes, max_events=n_src)
    out = ops.event_synapse_packed(ev, packed, scale, bits=bits,
                                   block_d=block_d)
    ref = event_synapse_packed_ref(ev, packed, scale, bits=bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_event_synapse_packed_bit_exact_vs_dense(rng, bits):
    """The packed kernel is BIT-EXACT against the f32 dense kernel on the
    dequantized weights — the invariant that lets the engine switch operand
    layouts without perturbing a single output spike."""
    q = _random_codes(rng, 24, 128, bits)
    scale = np.float32(0.007)
    w = jnp.asarray(q.astype(np.float32) * scale)
    packed = jnp.asarray(pack_signmag(q, bits))
    spikes = jnp.asarray((rng.random((4, 24)) < 0.4).astype(np.float32))
    ev = ops.events_from_spikes(spikes, max_events=24)
    dense = ops.event_synapse(ev, w)
    pk = ops.event_synapse_packed(ev, packed, scale, bits=bits)
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(dense))


def test_event_synapse_packed_all_padding(rng):
    q = _random_codes(rng, 8, 64, 4)
    packed = jnp.asarray(pack_signmag(q, 4))
    ev = jnp.full((2, 4), -1, jnp.int32)
    out = ops.event_synapse_packed(ev, packed, np.float32(0.1), bits=4)
    assert np.all(np.asarray(out) == 0)


def test_event_synapse_packed_rejects_bad_bits(rng):
    q = _random_codes(rng, 8, 64, 4)
    packed = jnp.asarray(pack_signmag(q, 4))
    ev = jnp.full((1, 2), -1, jnp.int32)
    with pytest.raises(ValueError):
        ops.event_synapse_packed(ev, packed, np.float32(0.1), bits=3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000), bits=st.sampled_from([2, 4, 8]),
       density=st.floats(0.0, 0.9))
def test_event_synapse_packed_property(seed, bits, density):
    """Random dense stacks: packed kernel == dequantized dense matmul at
    every supported width (allclose; the matmul reduces in a different
    order) AND bit-exact vs the gather-order dense kernel."""
    rng = np.random.default_rng(seed)
    q = _random_codes(rng, 20, 192, bits)
    scale = np.float32(0.02)
    w = q.astype(np.float32) * scale
    packed = jnp.asarray(pack_signmag(q, bits))
    spikes = jnp.asarray((rng.random((2, 20)) < density).astype(np.float32))
    ev = ops.events_from_spikes(spikes, max_events=20)
    out = ops.event_synapse_packed(ev, packed, scale, bits=bits)
    np.testing.assert_allclose(np.asarray(out), np.asarray(spikes) @ w,
                               atol=1e-4)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(ops.event_synapse(ev, jnp.asarray(w))))


# ------------------------------------ event loop bounded by a row's events

N_SRC = 24


def _event_lists(case: str) -> np.ndarray:
    """Padded event lists ``[rows, N_SRC]`` whose rows end at different
    slots: ``fills`` mixes an empty row, one event, every slot and a sparse
    compacted row; ``gaps`` holds a ``-1`` before a valid slot, so a row's
    last valid slot + 1 exceeds its count of events; ``padding`` is all
    ``-1``."""
    pad = np.full((4, N_SRC), -1, np.int32)
    if case == "fills":
        pad[1, 0] = 17
        pad[2] = np.arange(N_SRC)
        pad[3, :5] = [0, 3, 4, 11, 23]
    elif case == "gaps":
        pad[0, [0, 2]] = [3, 5]
        pad[1, 4] = 9
        pad[2, [1, N_SRC - 1]] = [2, 20]
        pad[3, :3] = [1, 6, 7]
    return pad


def _full_walk(events: np.ndarray, w: np.ndarray) -> np.ndarray:
    """The loop over every MEM_E slot: one f32 add per slot in order, an
    exact ``0.0`` for a ``-1`` — what the kernels computed before their loop
    stopped at each row's last event."""
    out = np.zeros((events.shape[0], w.shape[1]), np.float32)
    for r, row in enumerate(events):
        for idx in row:
            out[r] = out[r] + (w[idx] if idx >= 0 else np.float32(0.0))
    return out


@pytest.mark.parametrize("case", ["fills", "gaps", "padding"])
@pytest.mark.parametrize("kernel", ["f32", "packed8", "packed4"])
def test_event_loop_bound_bit_exact(rng, kernel, case):
    """Stopping each row's loop at its last valid slot drops only trailing
    ``+0.0`` adds: both kernels return the bits of the full walk, for rows
    of any fill, a ``-1`` before a valid slot, and rows of padding alone;
    the bound is the last valid slot + 1, not the count of events."""
    events = _event_lists(case)
    valid = events >= 0
    last = np.where(valid.any(axis=1),
                    N_SRC - np.argmax(valid[:, ::-1], axis=1), 0)
    _, bound = _event_operands(jnp.asarray(events))
    np.testing.assert_array_equal(np.asarray(bound).ravel(), last)
    if case == "gaps":
        assert np.any(last > valid.sum(axis=1))
    if kernel == "f32":
        w = rng.normal(size=(N_SRC, 256)).astype(np.float32)
        out = ops.event_synapse(jnp.asarray(events), jnp.asarray(w),
                                block_d=128)
    else:
        bits = int(kernel[len("packed"):])
        q = _random_codes(rng, N_SRC, 256, bits)
        scale = np.float32(0.011)
        w = q.astype(np.float32) * scale
        out = ops.event_synapse_packed(jnp.asarray(events),
                                       jnp.asarray(pack_signmag(q, bits)),
                                       scale, bits=bits, block_d=128)
    np.testing.assert_array_equal(np.asarray(out).view(np.uint32),
                                  _full_walk(events, w).view(np.uint32))


# ------------------------------------------------- event-stream compaction

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), density=st.floats(0.0, 1.0),
       max_ev=st.integers(1, 48))
def test_events_cumsum_matches_argsort(seed, density, max_ev):
    """The O(n) cumsum-based stable compaction is bit-identical to the
    full-width argsort it replaced — same events, same order, same padding
    — including under overflow truncation."""
    rng = np.random.default_rng(seed)
    spikes = jnp.asarray((rng.random((3, 40)) < density).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(ops.events_from_spikes(spikes, max_ev)),
        np.asarray(_events_from_spikes_argsort(spikes, max_ev)))


def test_events_cumsum_matches_argsort_edges():
    for spikes in (jnp.zeros((2, 16)), jnp.ones((2, 16))):
        for max_ev in (1, 8, 16, 32):
            np.testing.assert_array_equal(
                np.asarray(ops.events_from_spikes(spikes, max_ev)),
                np.asarray(_events_from_spikes_argsort(spikes, max_ev)))


# ---------------------------------------------------------------- lif_update

@pytest.mark.parametrize("shape,block", [
    ((8, 512), (8, 512)), ((16, 1024), (8, 256)), ((4, 128), (2, 128)),
])
def test_lif_update_shapes(rng, shape, block):
    v = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    i = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    vn, s = ops.lif_update(v, i, beta=0.85, threshold=0.7, v_reset=0.1,
                           block=block)
    vr, sr = lif_update_ref(v, i, 0.85, 0.7, 0.1)
    np.testing.assert_allclose(vn, vr, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_lif_update_matches_core_lif(rng):
    """Kernel forward == core.lif.lif_step forward (shared convention)."""
    from repro.core.lif import LIFParams, lif_step
    p = LIFParams(beta=0.9, threshold=1.0, v_reset=0.0)
    v = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
    i = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
    vn_k, s_k = ops.lif_update(v, i, beta=p.beta, threshold=p.threshold,
                               v_reset=p.v_reset, block=(4, 256))
    vn_c, s_c = lif_step(v, i, p)
    np.testing.assert_allclose(vn_k, vn_c, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s_k), np.asarray(s_c))


# ---------------------------------------------------------------- c2c_matmul

@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (128, 256, 384, 128, 128, 128),
    (64, 128, 128, 64, 64, 128),
    (256, 512, 256, 128, 256, 128),
])
def test_c2c_matmul_shapes(rng, m, k, n, bm, bk, bn):
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)).astype(np.int8))
    scale = jnp.float32(0.02)
    out = ops.c2c_matmul(x, wq, scale, bm=bm, bk=bk, bn=bn)
    np.testing.assert_allclose(out, c2c_matmul_ref(x, wq, scale),
                               rtol=1e-4, atol=1e-3)


def test_c2c_matmul_equals_ideal_ladder(rng):
    """Kernel == bit-serial C2C ladder evaluation (paper eq. (2))."""
    x = jnp.asarray(rng.normal(size=(64, 128)).astype(np.float32))
    wq = jnp.asarray(rng.integers(-127, 128, size=(128, 128)).astype(np.int8))
    scale = jnp.float32(0.013)
    out = ops.c2c_matmul(x, wq, scale, bm=64, bk=128, bn=128)
    np.testing.assert_allclose(out, c2c_matmul_ladder_ref(x, wq, scale),
                               rtol=1e-4, atol=1e-3)


def test_c2c_matmul_int8_extremes():
    x = jnp.ones((8, 128), jnp.float32)
    wq = jnp.full((128, 128), -128, jnp.int8)
    out = ops.c2c_matmul(x, wq, jnp.float32(1.0), bm=8)
    np.testing.assert_allclose(out, x @ (wq.astype(jnp.float32)), rtol=1e-5)
