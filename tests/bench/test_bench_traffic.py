"""The benchmark's traffic generator: deterministic by seed, and the lengths,
arrivals and spike densities its configuration and mix files state."""

import json

import numpy as np
import pytest

from _bench_tiny import ROOT, harness  # noqa: F401
from bench.traffic import Traffic, class_rate_maps, input_sensor

MIX = json.loads((ROOT / "bench/traffic/poisson_tail.json").read_text())
CIFAR = json.loads((ROOT / "bench/configs/cifar10dvs_mlp.json").read_text())
NMNIST = json.loads((ROOT / "bench/configs/nmnist_mlp.json").read_text())


def test_same_seed_same_requests_and_arrivals():
    a = Traffic(input_sensor(NMNIST), MIX, 2**31 + 5)
    b = Traffic(input_sensor(NMNIST), MIX, 2**31 + 5)
    c = Traffic(input_sensor(NMNIST), MIX, 2**31 + 6)
    for i in (0, 7, 1 << 30):
        assert np.array_equal(a.request(i), b.request(i))
    assert not np.array_equal(a.request(3), c.request(3))
    assert np.array_equal(a.due_times(2.0, 1), b.due_times(2.0, 1))


def test_every_seed_gets_the_same_lengths_and_gaps_in_another_order():
    a = Traffic(input_sensor(NMNIST), MIX, 1)
    b = Traffic(input_sensor(NMNIST), MIX, 2)
    assert {a.request(i).shape[0] for i in range(20)} == {a.steps} == {
        b.request(i).shape[0] for i in range(20)}
    da, db = a.due_times(10.0, 1), b.due_times(10.0, 1)
    assert not np.array_equal(da, db)
    assert np.allclose(np.sort(np.diff(da)), np.sort(np.diff(db)))


@pytest.mark.parametrize("cfg", [CIFAR, NMNIST])
def test_lengths_fill_the_buckets_as_the_mix_states(cfg):
    """Every request is one 25-step recording: all land in the deployed
    grid's 32-step bucket."""
    from repro.engine import BucketPolicy
    tr = Traffic(input_sensor(cfg), MIX, 4)
    assert tr.steps == 25
    assert tr.request(0).shape == (25, tr.n_in)
    assert BucketPolicy.for_mesh(1).t_bucket(tr.steps) == 32


@pytest.mark.parametrize("burst", [1, 6])
def test_open_loop_arrivals_at_the_stated_rate(burst):
    mix = dict(MIX, rate_rps=200.0, burst=burst)
    due = Traffic(input_sensor(NMNIST), mix, 3).due_times(20.0, 1)
    assert len(due) == pytest.approx(4000, rel=0.01)
    assert due.min() == 0.0 and due.max() < 20.0
    assert np.all(np.diff(due) >= 0) or burst == 1
    _, counts = np.unique(due, return_counts=True)
    assert set(counts) == {burst}


@pytest.mark.parametrize("cfg,density", [(CIFAR, 0.151), (NMNIST, 0.053)])
def test_spike_density_of_the_rate_maps(cfg, density):
    maps = class_rate_maps(input_sensor(cfg))
    assert maps.shape[1] == 2 * 34 * 34 if cfg is NMNIST else 2 * 64 * 64
    assert maps.mean() == pytest.approx(density, rel=0.05)
    tr = Traffic(input_sensor(cfg), MIX, 9)
    got = np.mean([tr.request(i).mean() for i in range(200)])
    assert got == pytest.approx(density, rel=0.15)
