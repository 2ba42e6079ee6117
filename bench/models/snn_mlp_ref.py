"""Plain reference of the spiking MLP, in numpy, independent of the program.

What a served request must answer: per layer, each time step's synaptic
current is the sum of the weight rows of the sources that spiked at that
step, added one by one in ascending source order in float32 (the order a
MEM_E event FIFO dispatches them, which makes the comparison exact); then
leaky integrate-and-fire, ``v = beta * v + I``, a spike where
``v >= threshold``, reset to ``v_reset`` (paper arXiv 2410.08403, §III-A,
eq. (1)).  Weights are quantized symmetrically per tensor to ``bits`` bits,
``scale = max|w| / (2^(bits-1) - 1)`` in float32, codes rounded half to even.

``weight_dtype`` lets the control compute the same thing with its weights
stored in a lower precision (bfloat16).  Imports numpy and ml_dtypes only.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

LOWER_PRECISION = {"float32": ml_dtypes.bfloat16}


def quantize(w: np.ndarray, bits: int) -> np.ndarray:
    """Symmetric per-tensor quantization, dequantized to float32; pruned
    (zero) weights stay zero."""
    w = np.asarray(w, dtype=np.float32)
    qmax = 2 ** (bits - 1) - 1
    amax = np.float32(np.max(np.abs(w)))
    scale = np.maximum(amax, np.float32(1e-12)) / np.float32(qmax)
    q = np.clip(np.round(w / scale), -qmax, qmax).astype(np.int8)
    return (q.astype(np.float32) * scale) * (w != 0)


def accumulate(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """Currents ``[R, n_dest]`` for spike rows ``x`` (bool ``[R, n_src]``):
    each row's spiking sources' weight rows added in ascending source
    order, in float32."""
    cur = np.zeros((x.shape[0], w.shape[1]), dtype=np.float32)
    src, rows = np.nonzero(x.T)                  # sorted by source
    bounds = np.searchsorted(src, np.arange(w.shape[0] + 1))
    for k in np.flatnonzero(np.diff(bounds)):
        cur[rows[bounds[k]:bounds[k + 1]]] += w[k]
    return cur


def lif(cur: np.ndarray, lengths: np.ndarray, beta: float, threshold: float,
        v_reset: float) -> np.ndarray:
    """LIF over each request's own time steps; ``cur`` holds the requests'
    rows back to back.  Returns bool spikes shaped like ``cur``."""
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    t_max = int(lengths.max())
    beta, threshold, v_reset = (np.float32(beta), np.float32(threshold),
                                np.float32(v_reset))
    v = np.zeros((len(lengths), cur.shape[1]), dtype=np.float32)
    out = np.zeros(cur.shape, dtype=bool)
    for t in range(t_max):
        live = np.flatnonzero(lengths > t)
        rows = starts[live] + t
        vi = beta * v[live] + cur[rows]
        s = vi >= threshold
        v[live] = np.where(s, v_reset, vi)
        out[rows] = s
    return out


def forward(cfg: dict, weights: list[np.ndarray], rasters: list[np.ndarray],
            weight_dtype=None) -> tuple[list[np.ndarray], list[int]]:
    """Output spikes (bool ``[T_i, n_out]`` per request) and the number of
    input events each layer received over all the requests."""
    lengths = np.array([r.shape[0] for r in rasters])
    x = np.concatenate([np.asarray(r, dtype=bool) for r in rasters])
    p = cfg["lif"]
    events = []
    for w in weights:
        wq = quantize(w, cfg["quant_bits"])
        if weight_dtype is not None:
            wq = wq.astype(weight_dtype).astype(np.float32)
        events.append(int(x.sum()))
        x = lif(accumulate(x, wq), lengths, p["beta"], p["threshold"],
                p["v_reset"])
    return np.split(x, np.cumsum(lengths)[:-1]), events
