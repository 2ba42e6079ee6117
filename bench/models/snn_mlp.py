"""The spiking MLP configurations: seeded weights and the served program.

``make_weights`` draws Kaiming-normal weights scaled by the configuration's
``weight_gain`` and prunes each layer by L1 magnitude (``prune_amount``), on
the host with numpy, from the seed alone.  ``build`` hands them to the
program as a user would: ``map_model`` (which quantizes to ``quant_bits`` and
maps onto the named accelerator) and ``.pack()``.  The plain reference that
the served answers are compared with is ``snn_mlp_ref.py`` beside this file.
"""

from __future__ import annotations

import numpy as np

from bench.roofline import Layer, dense
from bench.traffic import input_sensor


def layer_sizes(cfg: dict) -> list[int]:
    s = input_sensor(cfg)
    return [2 * s["height"] * s["width"], *cfg["hidden_sizes"]]


def layers(cfg: dict) -> list[Layer]:
    """Every layer is fully connected and runs on the synapse kernel."""
    sizes = layer_sizes(cfg)
    return [dense(n_src, n_dest)
            for n_src, n_dest in zip(sizes[:-1], sizes[1:])]


def make_weights(cfg: dict, seed: int) -> list[np.ndarray]:
    """Pruned float32 weight matrices ``[n_src, n_dest]``, one per layer."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    sizes = layer_sizes(cfg)
    gain = float(cfg["assumed"]["weight_gain"])
    amount = float(cfg["assumed"]["prune_amount"])
    out = []
    for n_src, n_dest in zip(sizes[:-1], sizes[1:]):
        std = np.float32(gain * np.sqrt(2.0 / n_src))
        w = rng.standard_normal((n_src, n_dest), dtype=np.float32) * std
        k = int(round(amount * w.size))
        if k > 0:
            thresh = np.partition(np.abs(w).ravel(), k - 1)[k - 1]
            w = w * (np.abs(w) > thresh)
        out.append(w.astype(np.float32))
    return out


def build(cfg: dict, weights: list[np.ndarray]):
    """The program's packed model for ``weights``.  Mapping (the host-side
    ILP and the quantization inside it) runs on the CPU backend; the packed
    tables and weight tiles go to the default device."""
    import jax

    from repro.configs import menage_paper
    from repro.core.accelerator import map_model
    from repro.core.lif import LIFParams

    spec = getattr(menage_paper, cfg["accelerator"])
    with jax.default_device(jax.devices("cpu")[0]):
        mapped = map_model(weights, spec, lif=LIFParams(**cfg["lif"]),
                           quant_bits=cfg["quant_bits"])
    packed = mapped.pack()
    jax.block_until_ready(packed)
    return packed
