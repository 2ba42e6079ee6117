"""Plain reference of the spiking convnet, in numpy.

The convolution is written out directly: each input position's weight row
holds, for every output position and kernel tap that reads it, that tap's
weight.  Rows are added in ascending source order in float32, then LIF, as
``snn_mlp_ref`` does for a dense layer.  The kernel is quantized per tensor
to ``quant_bits`` bits.  Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from bench.models.snn_mlp_ref import LOWER_PRECISION, accumulate, lif, quantize
from bench.traffic import input_sensor

__all__ = ["LOWER_PRECISION", "forward"]


def conv_rows(kernel: np.ndarray, in_shape, stride: int,
              padding: int) -> np.ndarray:
    """``[c_in * h * w, c_out * oh * ow]`` weight rows of the convolution,
    both sides flattened channel-major."""
    c_out, c_in, kh, kw = kernel.shape
    _, h, w = in_shape
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    rows = np.zeros((c_in, h, w, c_out, oh, ow), dtype=np.float32)
    for oy in range(oh):
        for ox in range(ow):
            for ky in range(kh):
                for kx in range(kw):
                    y = oy * stride + ky - padding
                    x = ox * stride + kx - padding
                    if 0 <= y < h and 0 <= x < w:
                        rows[:, y, x, :, oy, ox] = kernel[:, :, ky, kx].T
    return rows.reshape(c_in * h * w, c_out * oh * ow)


def forward(cfg: dict, weights: list[np.ndarray], rasters: list[np.ndarray],
            weight_dtype=None) -> tuple[list[np.ndarray], list[int]]:
    """Output spikes per request and the input events each layer
    received."""
    c = cfg["conv"]
    s = input_sensor(cfg)
    stored = [quantize(w, cfg["quant_bits"]) for w in weights]
    if weight_dtype is not None:
        stored = [w.astype(weight_dtype).astype(np.float32) for w in stored]
    mats = [conv_rows(stored[0], (2, s["height"], s["width"]), c["stride"],
                      c["padding"]), *stored[1:]]
    lengths = np.array([r.shape[0] for r in rasters])
    x = np.concatenate([np.asarray(r, dtype=bool) for r in rasters])
    p = cfg["lif"]
    events = []
    for m in mats:
        events.append(int(x.sum()))
        x = lif(accumulate(x, m), lengths, p["beta"], p["threshold"],
                p["v_reset"])
    return np.split(x, np.cumsum(lengths)[:-1]), events
