"""Operations, bytes and the least time of the synapse layers, from counts.

Both count the work the traffic needs, whatever implements it: an operation
is a multiply-accumulate of one input event into one destination (2 ops), so
a dense or a matmul route is judged on the same footing.  The bytes a layer
must move per kernel call are its weight matrix once per chip (at the
configuration's stored width), the event list (4 bytes an event) and the
currents it writes (4 bytes per real step and destination).
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def peaks_for(device_kind: str) -> dict:
    """The chip's peak FLOP/s and HBM bytes/s; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; add it with its source")
    return table[device_kind]


def layer_work(n_src: int, n_dest: int, events: float, rows: int,
               calls: int, n_chips: int, weight_bits: int
               ) -> tuple[float, float]:
    """``(ops, bytes)`` of one synapse layer over ``calls`` kernel calls on
    each of ``n_chips`` chips that together saw ``events`` input events in
    ``rows`` real (sample, step) rows."""
    ops = 2.0 * events * n_dest
    nbytes = (calls * n_chips * n_src * n_dest * weight_bits / 8
              + 4.0 * events + 4.0 * rows * n_dest)
    return ops, nbytes


def least_time(work: list[tuple[float, float]], peaks: dict) -> float:
    """Seconds the chips would need at their roofline, summed over chips:
    per layer the larger of ops over peak FLOP/s and bytes over peak
    bandwidth."""
    return sum(max(ops / peaks["flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"])
               for ops, nbytes in work)
