"""The benchmark's one traffic generator, driven by a mix file and a seed.

A traffic mix (``bench/traffic/<name>.json``) holds only parameters:

* ``loop``: ``"closed"`` (``outstanding`` requests always in flight) or
  ``"open"`` (requests sent at due times, ``rate_rps`` in bursts of
  ``burst`` simultaneous requests, default 1: Poisson arrivals);
* ``slack_s``: the deadline slack each request carries (``null`` means
  best-effort, the server's default);
* ``warm_s``: seconds of the same traffic driven before the measured window.

Every request is one recording of the configuration's sensor,
``num_steps`` time steps long (the datasets' fixed length).  Every seed gets
the same work in another order: open-loop gaps are the exponential quantiles
of the window's request count, permuted by the seed.  Request content is
Bernoulli spikes drawn from the configuration's class rate maps, from
``(seed, request index)``.

Imports numpy only: the load generator's child process uses it without
starting JAX.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

WARM_BASE = 1 << 30         # request indices of the warm-up traffic


def input_sensor(cfg: dict) -> dict:
    """The configuration's sensor as the model sees it: the published
    height and width binned by ``input_downsample``."""
    down = int(cfg.get("input_downsample", 1))
    s = dict(cfg["sensor"])
    s["height"], s["width"] = s["height"] // down, s["width"] // down
    return s


def class_rate_maps(sensor: dict) -> np.ndarray:
    """Per-class spike-probability maps ``[C, 2 * H * W]``: a background
    rate plus Gaussian blobs, one polarity each, clipped to 0.95.  A copy of
    the dataset-like maps of ``src/repro/data/events.py``."""
    h, w = sensor["height"], sensor["width"]
    rng = np.random.default_rng(sensor["map_seed"])
    yy, xx = np.mgrid[0:h, 0:w]
    maps = np.full((sensor["num_classes"], 2, h, w), sensor["base_rate"],
                   dtype=np.float32)
    for c in range(sensor["num_classes"]):
        for _ in range(sensor["blobs_per_class"]):
            cy, cx = rng.uniform(0, h), rng.uniform(0, w)
            sig = rng.uniform(h / 12, h / 5)
            pol = rng.integers(0, 2)
            blob = np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * sig**2))
            maps[c, pol] += sensor["signal_rate"] * blob.astype(np.float32)
    return np.clip(maps, 0.0, 0.95).reshape(sensor["num_classes"], -1)


def _seq(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), *tags])


@dataclasses.dataclass
class Traffic:
    sensor: dict
    mix: dict
    seed: int

    def __post_init__(self):
        self.rates = class_rate_maps(self.sensor)
        self.n_in = self.rates.shape[1]

    @property
    def slack(self) -> float:
        s = self.mix.get("slack_s")
        return math.inf if s is None else float(s)

    @property
    def steps(self) -> int:
        """Every request's length in time steps."""
        return int(self.sensor["num_steps"])

    def request(self, i: int) -> np.ndarray:
        """Request ``i``'s spike raster, bool ``[steps, n_in]``."""
        rng = _seq(self.seed, 2, i)
        cls = int(rng.integers(self.rates.shape[0]))
        u = rng.random((self.steps, self.n_in), dtype=np.float32)
        return u < self.rates[cls]

    def due_times(self, seconds: float, phase: int) -> np.ndarray:
        """Open loop: the offsets in ``[0, seconds)`` at which requests are
        due.  Bursts of ``burst`` requests arrive together; the gaps between
        bursts are the exponential quantiles of their count at
        ``rate_rps / burst`` but the longest, in an order drawn from the seed
        and ``phase`` (0: warm-up, 1: window)."""
        rate = float(self.mix["rate_rps"])
        burst = int(self.mix.get("burst", 1))
        n = max(1, round(rate * seconds / burst))
        q = (np.arange(n) + 0.5) / n
        # n bursts need n - 1 gaps: the longest is left out for every seed
        gaps = (-np.log1p(-q) * burst / rate)[:-1]
        gaps = gaps[_seq(self.seed, 3, phase).permutation(n - 1)]
        starts = np.concatenate([[0.0], np.cumsum(gaps)])
        starts = starts[starts < seconds]
        return np.repeat(starts, burst)
