"""A spiking convnet: a 2-D convolution over the sensor's two polarity
planes, its LIF, then fully connected layers, each with its LIF.

``make_weights`` draws Kaiming-normal weights scaled by ``weight_gain`` from
the seed alone; ``build`` hands the kernel to the program as a ``Conv2d``
spec and the rest as dense matrices, through ``map_model`` and ``.pack()``.
"""

from __future__ import annotations

import numpy as np

from bench.roofline import Layer, conv2d, dense
from bench.traffic import input_sensor


def in_shape(cfg: dict) -> tuple[int, int, int]:
    s = input_sensor(cfg)
    return (2, s["height"], s["width"])


def layers(cfg: dict) -> list[Layer]:
    c = cfg["conv"]
    conv = conv2d(in_shape(cfg), c["channels"], c["kernel"], c["kernel"],
                  stride=c["stride"], padding=c["padding"])
    sizes = [conv.n_dest, *cfg["hidden_sizes"]]
    return [conv, *(dense(a, b) for a, b in zip(sizes[:-1], sizes[1:]))]


def make_weights(cfg: dict, seed: int) -> list[np.ndarray]:
    """The kernel ``[c_out, 2, k, k]`` and the dense ``[n_src, n_dest]``
    matrices, float32."""
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    gain = float(cfg["assumed"]["weight_gain"])
    c = cfg["conv"]
    shape = (c["channels"], 2, c["kernel"], c["kernel"])
    fan_in = 2 * c["kernel"] ** 2
    out = [rng.standard_normal(shape, dtype=np.float32)
           * np.float32(gain * np.sqrt(2.0 / fan_in))]
    sizes = [layers(cfg)[0].n_dest, *cfg["hidden_sizes"]]
    for n_src, n_dest in zip(sizes[:-1], sizes[1:]):
        out.append(rng.standard_normal((n_src, n_dest), dtype=np.float32)
                   * np.float32(gain * np.sqrt(2.0 / n_src)))
    return out


def build(cfg: dict, weights: list[np.ndarray]):
    import jax

    from repro.configs import menage_paper
    from repro.core.accelerator import map_model
    from repro.core.layers import Conv2d
    from repro.core.lif import LIFParams

    c = cfg["conv"]
    specs = [Conv2d(kernel=weights[0], in_shape=in_shape(cfg),
                    stride=c["stride"], padding=c["padding"]),
             *weights[1:]]
    with jax.default_device(jax.devices("cpu")[0]):
        mapped = map_model(specs, getattr(menage_paper, cfg["accelerator"]),
                           lif=LIFParams(**cfg["lif"]),
                           quant_bits=cfg["quant_bits"])
    packed = mapped.pack()
    jax.block_until_ready(packed)
    return packed
