"""The profiler's trace, reduced to what the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote for the traced
window with ``jax.profiler.ProfileData`` and keeps, for each chip
(``/device:TPU:<k>`` planes), the operations of its ``XLA Ops`` line: the
HLO instruction's name (``event_synapse.5`` of ``%event_synapse.5 = f32[...]
custom-call(...)``), start and duration in nanoseconds.  Busy time is the
union of those intervals (nested or overlapping operations count once);
idle is the rest of the window.  Asynchronous copies (the ``Async XLA Ops``
line) overlap compute and are not counted as busy.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
TOP = 10


@dataclasses.dataclass
class Op:
    name: str
    start_ns: int
    dur_ns: int


def union_ns(ops: list[Op]) -> int:
    """Length of the union of the ops' intervals."""
    total, end = 0, None
    for op in sorted(ops, key=lambda o: o.start_ns):
        s, e = op.start_ns, op.start_ns + op.dur_ns
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def gaps(ops: list[Op]) -> list[tuple[str, int]]:
    """Idle gaps between ops, each named by the op that ends it."""
    out, end = [], None
    for op in sorted(ops, key=lambda o: o.start_ns):
        if end is not None and op.start_ns > end:
            out.append((f"idle before {op.name}", op.start_ns - end))
        e = op.start_ns + op.dur_ns
        end = e if end is None else max(end, e)
    return out


@dataclasses.dataclass
class DeviceTrace:
    """Per-chip op intervals of a traced window of ``window_s`` seconds."""

    window_s: float
    chips: list[list[Op]]

    @property
    def per_chip_busy_s(self) -> list[float]:
        return [union_ns(ops) * 1e-9 for ops in self.chips]

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the chips."""
        busy = self.per_chip_busy_s
        return sum(busy) / len(busy) if busy else 0.0

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of ops whose name contains ``pattern``, summed
        over the chips."""
        return sum(op.dur_ns for ops in self.chips for op in ops
                   if pattern in op.name) * 1e-9

    def breakdown(self) -> dict:
        """The ops that took most device time and the longest idle gaps,
        over all chips, in seconds."""
        by_op = collections.Counter()
        idle = collections.Counter()
        for ops in self.chips:
            for op in ops:
                by_op[op.name] += op.dur_ns
            for name, ns in gaps(ops):
                idle[name] = max(idle[name], ns)
        return {"device_ops": [[n, ns * 1e-9]
                               for n, ns in by_op.most_common(TOP)],
                "idle_gaps": [[n, ns * 1e-9]
                              for n, ns in idle.most_common(TOP)]}


def from_planes(planes, n_chips: int, window_s: float) -> DeviceTrace:
    """``planes``: ``(plane name, [(line name, [Op, ...]), ...])`` pairs.
    Keeps the op line of the first ``n_chips`` device planes."""
    chips = {}
    for name, lines in planes:
        m = DEVICE_PLANE.match(name)
        if not m or int(m.group(1)) >= n_chips:
            continue
        chips[int(m.group(1))] = [op for ln, ops in lines if ln == OP_LINE
                                  for op in ops]
    return DeviceTrace(window_s=window_s,
                       chips=[chips[k] for k in sorted(chips)])


def op_name(hlo_text: str) -> str:
    """The instruction name of an op event's HLO text."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def load(profile_dir: str, n_chips: int, window_s: float) -> DeviceTrace:
    import jax

    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, "
                           f"found {len(files)}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    planes = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        planes.append((plane.name, [
            (line.name, [Op(op_name(e.name), int(e.start_ns),
                             int(e.duration_ns))
                         for e in line.events])
            for line in plane.lines]))
    return from_planes(planes, n_chips, window_s)
