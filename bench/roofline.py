"""Operations, bytes and the least time of the synapse layers, from counts.

Both count the work the traffic needs, whatever implements it: an operation
is a multiply-accumulate of one input event into one destination (2 ops), so
a dense or a matmul route is judged on the same footing.  How many
destinations one event reaches, and how many weights a layer stores, come
from the layer's own equations (:class:`Layer`, one constructor per layer
spec the program maps), not from the nonzero taps or the route that
implements it.  The bytes a layer must move per kernel call are its stored
weights once per chip (at the configuration's stored width), the event list
(4 bytes an event) and the currents it writes (4 bytes per real step and
destination).
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

PEAKS = pathlib.Path(__file__).with_name("peaks.json")
SYNAPSE_KERNEL = "event_synapse"


def peaks_for(device_kind: str) -> dict:
    """The chip's peak FLOP/s and HBM bytes/s; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add it with its source")
    return table[device_kind]


@dataclasses.dataclass(frozen=True)
class Layer:
    """One synapse layer's geometry: ``fan_out`` destinations per input
    event (the mean over its source positions), ``n_weights`` distinct
    stored weights, and ``kernel``, the substring of the names of the device
    ops that compute it (a layer on another kernel is made with
    ``dataclasses.replace``)."""

    n_dest: int
    fan_out: float
    n_weights: int
    kernel: str = SYNAPSE_KERNEL


def dense(n_src: int, n_dest: int) -> Layer:
    """A fully connected layer: every event reaches every destination."""
    return Layer(n_dest=n_dest, fan_out=n_dest, n_weights=n_src * n_dest)


def _taps_per_position(size: int, out: int, k: int, stride: int,
                       padding: int) -> int:
    """``(output position, tap)`` pairs along one axis that land on an
    input position: ``out * k`` less the taps that fall in the padding."""
    return sum(0 <= o * stride + t - padding < size
               for o in range(out) for t in range(k))


def conv2d(in_shape: tuple[int, int, int], c_out: int, kh: int, kw: int,
           stride: int = 1, padding: int = 0, groups: int = 1) -> Layer:
    """A 2-D convolution over a channel-major ``(c_in, h, w)`` input with
    symmetric zero padding; each input channel reaches the ``c_out /
    groups`` output channels of its group."""
    c_in, h, w = in_shape
    if c_in % groups or c_out % groups:
        raise ValueError(f"{groups} groups do not divide {c_in} -> {c_out} "
                         f"channels")
    oh = (h + 2 * padding - kh) // stride + 1
    ow = (w + 2 * padding - kw) // stride + 1
    pairs_h = _taps_per_position(h, oh, kh, stride, padding)
    pairs_w = _taps_per_position(w, ow, kw, stride, padding)
    return Layer(n_dest=c_out * oh * ow,
                 fan_out=(c_out // groups) * pairs_h * pairs_w / (h * w),
                 n_weights=c_out * (c_in // groups) * kh * kw)


def sum_pool2d(in_shape: tuple[int, int, int], pool: int = 2) -> Layer:
    """Sum-pooling: a depthwise ``pool x pool`` convolution of stride
    ``pool`` whose taps are all fixed at 1."""
    return conv2d(in_shape, in_shape[0], pool, pool, stride=pool,
                  groups=in_shape[0])


def layer_work(layer: Layer, events: float, rows: int, calls: int,
               n_chips: int, weight_bits: int) -> tuple[float, float]:
    """``(ops, bytes)`` of one synapse layer over ``calls`` kernel calls on
    each of ``n_chips`` chips that together saw ``events`` input events in
    ``rows`` real (sample, step) rows."""
    ops = 2.0 * events * layer.fan_out
    nbytes = (calls * n_chips * layer.n_weights * weight_bits / 8
              + 4.0 * events + 4.0 * rows * layer.n_dest)
    return ops, nbytes


def least_time(work: list[tuple[float, float, str]], peaks: dict,
               kernel: str | None = None) -> float:
    """Seconds the chips would need at their roofline, summed over chips:
    per layer the larger of ops over peak FLOP/s and bytes over peak
    bandwidth, over the layers that run on ``kernel`` (every layer when it
    is ``None``)."""
    return sum(max(ops / peaks["flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])
               for ops, nbytes, k in work if kernel is None or k == kernel)
