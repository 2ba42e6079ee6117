"""The server's stages and the forward's scopes in a traced window, on the
device's clock.

The served path marks its host stages as ``serve.<name>`` annotations in the
JAX profiler's trace (``repro.engine.tracing.stage``), and the forward puts
each layer's steps under ``layer<i>/mem_e``, ``layer<i>/synapse`` and
``layer<i>/lif`` in its ops' metadata.  The profile names a device op by its
HLO instruction only, so each op's scope comes from the compiled forward's
HLO text (``hlo_scopes``) of the bucket whose ``serve.dispatch`` (attribute
``b_pad``) was open when the op started.  This module reduces a
``.xplane.pb`` to:

* ``idle_by_stage``: every idle nanosecond of each chip inside the window,
  given to the innermost ``serve.*`` stage that covers it, or to ``none``;
  ``serve.wait`` (the socket loop's ``select``) splits into ``wait[queued]``
  (requests were waiting for a trigger) and ``wait[empty]``.  Seconds,
  averaged over the chips;
* ``scope_seconds``: device seconds of the ops whose scope matches;
* ``clock_offsets``: for each program run on a chip, how far it strays from
  the ``serve.launch`` .. ``serve.fetch`` interval of its dispatch, which
  shows whether host and device share one clock.

The window is the ``bench.window`` annotation: the profiled window of
``harness.serve``.

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s> \
        [--keep <dir>]

serves the cell as ``bench/run.py --trace 1`` does (no comparison with the
reference) and prints one JSON line with these reductions; ``--keep``
copies the ``.xplane.pb`` there.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import glob
import json
import os
import pathlib
import re
import shutil
import sys
import tempfile

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))

from bench import devtrace  # noqa: E402

HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
STAGE_PREFIX = "serve."
WINDOW = "bench.window"
# stages in which the host works on the served path (wait and the
# dispatch's own bookkeeping are not among them)
HOST_WORK = ("read", "admit", "poll", "pad", "upload", "launch", "fetch",
             "slice", "record", "encode")
MEM_E = r"layer\d+/mem_e(/|$)"
SYNAPSE = r"layer\d+/synapse(/|$)"
LIF = r"layer\d+/lif(/|$)"
CLOCK_TOLERANCE_NS = 100_000

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: int
    dur_ns: int
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def stage_key(ev: Event) -> str:
    """``serve.pad`` -> ``pad``; ``serve.wait`` -> ``wait[queued]`` or
    ``wait[empty]``."""
    name = ev.name[len(STAGE_PREFIX):]
    if name == "wait":
        return "wait[queued]" if int(ev.stats.get("queued", 0)) > 0 \
            else "wait[empty]"
    return name


def hlo_scopes(text: str) -> dict[str, str]:
    """Each instruction of a compiled HLO module's text -> its ``op_name``
    scope path.  An instruction without one (a fusion the compiler formed)
    takes the scope of its called computation's root or, where the root has
    none either, of the first instruction in that computation that has
    one."""
    own, calls, root, body = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            body[computation] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name = m.group(2)
        body[computation].append(name)
        if m.group(1):
            root[computation] = name
        op = _OP_NAME.search(line)
        own[name] = op.group(1) if op else ""
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)

    memo = {}

    def scope(name: str) -> str:
        if name not in memo:
            memo[name] = own.get(name, "")
            comp = calls.get(name)
            if not memo[name] and comp in body:
                inner = [root[comp]] if comp in root else []
                memo[name] = next(
                    filter(None, map(scope, inner + body[comp])), "")
        return memo[name]

    return {name: scope(name) for name in own}


def _busy_fn(ops: list[Event]):
    """``B(t)``: device busy nanoseconds before ``t`` (the union of the ops'
    intervals), vectorized over ``t``."""
    if not ops:
        return lambda t: np.zeros(np.shape(t))
    s = np.array([o.start_ns for o in ops], np.float64)
    e = s + np.array([o.dur_ns for o in ops], np.float64)
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    first = np.r_[True, s[1:] > e[:-1]]
    starts = s[first]
    ends = e[np.r_[np.flatnonzero(first)[1:] - 1, len(s) - 1]]
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    lengths = ends - starts
    before = np.r_[0.0, np.cumsum(lengths)[:-1]]
    xs = np.ravel(np.column_stack([starts, ends]))
    ys = np.ravel(np.column_stack([before, before + lengths]))
    return lambda t: np.interp(t, xs, ys)


@dataclasses.dataclass
class StageTrace:
    """Host stages, the window and per-chip device ops of one profile."""

    window: tuple[int, int] | None      # ns on the profiler's clock
    stages: list[Event]                 # serve.* events, by start
    chips: list[list[Event]]            # per chip: XLA Ops events
    modules: list[list[Event]]          # per chip: XLA Modules events
    scopes: dict[int, dict[str, str]]   # b_pad -> op name -> scope path

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def in_window(self) -> list[Event]:
        w0, w1 = self.window
        return [s for s in self.stages if w0 <= s.start_ns < w1]

    def counts(self) -> collections.Counter:
        """How many stages of each key start inside the window."""
        return collections.Counter(stage_key(s) for s in self.in_window())

    def total_s(self, key: str) -> float:
        """Seconds of the stages of ``key`` that start inside the window,
        nested stages included."""
        return 1e-9 * sum(s.dur_ns for s in self.in_window()
                          if stage_key(s) == key)

    def segments(self):
        """The window cut where any stage starts or ends: ``(starts, ends,
        keys)``, each piece keyed by its innermost stage (the one that
        started last among those still open), or ``none``."""
        w0, w1 = self.window
        evs = []
        for i, st in enumerate(self.stages):
            a, b = max(st.start_ns, w0), min(st.end_ns, w1)
            if a < b:
                evs.append((a, 1, -b, i))   # at one instant: outer first
                evs.append((b, 0, 0, i))    # and ends before starts
        evs.sort()
        starts, ends, keys, open_ = [], [], [], []
        cur = w0
        for t, is_start, _, i in evs:
            if t > cur:
                starts.append(cur)
                ends.append(t)
                keys.append(stage_key(self.stages[open_[-1]])
                            if open_ else "none")
                cur = t
            if is_start:
                open_.append(i)
            else:
                open_.remove(i)
        if cur < w1:
            starts.append(cur)
            ends.append(w1)
            keys.append("none")
        return (np.asarray(starts, np.float64), np.asarray(ends, np.float64),
                np.asarray(keys, dtype=object))

    def _by_key(self, keys, values) -> dict[str, float]:
        names, inv = np.unique(keys.astype(str), return_inverse=True)
        sums = np.bincount(inv, weights=values, minlength=len(names))
        return dict(sorted(((str(n), float(v) * 1e-9)
                            for n, v in zip(names, sums)),
                           key=lambda kv: -kv[1]))

    def self_seconds(self) -> dict[str, float]:
        """Host seconds of the window in which each stage was the innermost
        one (``none``: no stage open)."""
        a, b, keys = self.segments()
        return self._by_key(keys, b - a)

    def idle_by_stage(self) -> dict[str, float]:
        """Device idle seconds of the window given to the innermost stage
        open at the time, averaged over the chips."""
        a, b, keys = self.segments()
        idle = np.zeros(len(a))
        for ops in self.chips:
            busy = _busy_fn(ops)
            idle += (b - a) - (busy(b) - busy(a))
        n = max(len(self.chips), 1)
        return self._by_key(keys, idle / n)

    def op_scopes(self, ops: list[Event]) -> list[str]:
        """Each op's scope path, from the scopes of the bucket whose
        ``serve.dispatch`` was open when the op started (``""`` outside
        every dispatch)."""
        disp = [s for s in self.stages
                if s.name == STAGE_PREFIX + "dispatch"]
        starts = [d.start_ns for d in disp]
        k = np.searchsorted(starts, [op.start_ns for op in ops],
                            side="right") - 1
        out = []
        for op, i in zip(ops, k):
            inside = i >= 0 and op.start_ns < disp[i].end_ns
            names = (self.scopes.get(disp[i].stats.get("b_pad"), {})
                     if inside else {})
            out.append(names.get(op.name, ""))
        return out

    def scope_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose scope matches the regular
        expression ``pattern``, summed over the chips."""
        rx = re.compile(pattern)
        return 1e-9 * sum(op.dur_ns for ops in self.chips
                          for op, scope in zip(ops, self.op_scopes(ops))
                          if rx.search(scope))

    def clock_offsets(self) -> list[int]:
        """For each program run inside the window, on each chip, the larger
        of (its ``serve.launch``'s start - its start) and (its end - the
        matching ``serve.fetch``'s end), in ns: at most zero where the run
        lies inside launch .. fetch.  Of the last launch to start before
        the run and the next one, the run is matched with the one it fits
        best; the fetch is the first to start after that launch.  A run
        with neither reads as the whole window."""
        w0, w1 = self.window
        launch = sorted(s.start_ns for s in self.stages
                        if s.name == STAGE_PREFIX + "launch")
        fetch = sorted((s.start_ns, s.end_ns) for s in self.stages
                       if s.name == STAGE_PREFIX + "fetch")
        fetch_starts = [f[0] for f in fetch]
        out = []
        for mods in self.modules:
            for m in mods:
                if not (w0 <= m.start_ns and m.end_ns <= w1):
                    continue
                k = int(np.searchsorted(launch, m.start_ns, side="right")) - 1
                fits = []
                for i in (k, k + 1):
                    if not 0 <= i < len(launch):
                        continue
                    j = np.searchsorted(fetch_starts, launch[i], side="right")
                    if j < len(fetch):
                        fits.append(max(launch[i] - m.start_ns,
                                        m.end_ns - fetch[j][1]))
                out.append(min(fits) if fits else w1 - w0)
        return out


def from_planes(planes, n_chips: int,
                scopes: dict[int, dict[str, str]] | None = None
                ) -> StageTrace:
    """``planes``: ``(plane name, [(line name, [Event, ...]), ...])``
    pairs.  Keeps the host plane's ``serve.*`` events and its
    ``bench.window``, and the op and module lines of the first ``n_chips``
    device planes; ``scopes`` maps each bucket's ``b_pad`` to its op
    names' scope paths."""
    stages, window, chips, modules = [], None, {}, {}
    for name, lines in planes:
        if name == HOST_PLANE:
            for _, evs in lines:
                for ev in evs:
                    if ev.name.startswith(STAGE_PREFIX):
                        stages.append(ev)
                    elif ev.name == WINDOW:
                        window = (ev.start_ns, ev.end_ns)
            continue
        m = devtrace.DEVICE_PLANE.match(name)
        if not m or int(m.group(1)) >= n_chips:
            continue
        k = int(m.group(1))
        chips[k] = [ev for ln, evs in lines if ln == devtrace.OP_LINE
                    for ev in evs]
        modules[k] = [ev for ln, evs in lines if ln == MODULE_LINE
                      for ev in evs]
    stages.sort(key=lambda s: (s.start_ns, -s.dur_ns))
    return StageTrace(window=window, stages=stages,
                      chips=[chips[k] for k in sorted(chips)],
                      modules=[modules[k] for k in sorted(modules)],
                      scopes=scopes or {})


def load(profile_dir: str, n_chips: int,
         scopes: dict[int, dict[str, str]] | None = None) -> StageTrace:
    import jax

    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {profile_dir}, "
                           f"found {len(files)}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    planes = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE and \
                not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        host = plane.name == HOST_PLANE
        lines = []
        for line in plane.lines:
            if not host and line.name not in (devtrace.OP_LINE,
                                              MODULE_LINE):
                continue
            evs = []
            for e in line.events:
                if host and not (e.name.startswith(STAGE_PREFIX)
                                 or e.name == WINDOW):
                    continue
                evs.append(Event(devtrace.op_name(e.name), int(e.start_ns),
                                 int(e.duration_ns),
                                 dict(e.stats) if host else {}))
            lines.append((line.name, evs))
        planes.append((plane.name, lines))
    return from_planes(planes, n_chips, scopes)


def forward_scopes(packed, mesh, steps: int) -> dict[int, dict[str, str]]:
    """``b_pad`` -> op name -> scope path, from the compiled forward of
    every bucket that ``harness.warm_buckets`` compiles for ``steps``-step
    requests: the programs the profile ran."""
    import jax

    from repro.engine import BucketPolicy
    from repro.engine import batched_run as br
    from repro.engine.sharded_run import _sharded_forward, batch_spec

    bp = BucketPolicy.for_mesh(mesh.size)
    out = {}
    for b in bp.batch_sizes:
        shape = (b, bp.t_bucket(steps), packed.n_in)
        fwd = _sharded_forward(mesh, batch_spec(mesh, shape),
                               br.should_donate(None))
        out[b] = hlo_scopes(fwd.lower(
            packed, jax.ShapeDtypeStruct(shape, np.float32),
            None).compile().as_text())
    return out


def report(st: StageTrace, dev: devtrace.DeviceTrace) -> dict:
    """The reductions of one traced window, as printed by ``main``."""
    window = st.window_s
    counts = st.counts()
    idle = st.idle_by_stage()
    busy = dev.busy_s * len(dev.chips)
    offsets = st.clock_offsets()
    pct = {k: 100.0 * v / window for k, v in idle.items()}
    scoped = {name: st.scope_seconds(rx)
              for name, rx in (("mem_e", MEM_E), ("synapse", SYNAPSE),
                               ("lif", LIF))}
    unscoped = collections.Counter()
    for ops in st.chips:
        for op, scope in zip(ops, st.op_scopes(ops)):
            if not re.search(r"layer\d+/", scope):
                unscoped[op.name] += op.dur_ns
    n_disp = counts.get("dispatch", 0)
    own = st.self_seconds()
    return {
        "window_s": window,
        "dispatches": n_disp,
        "stage_counts": dict(sorted(counts.items())),
        "idle_by_stage_s": idle,
        "idle_by_stage_pct": pct,
        "idle_pct": sum(pct.values()),
        "idle_host_pct": sum(pct.get(k, 0.0) for k in HOST_WORK),
        "idle_held_pct": pct.get("wait[queued]", 0.0),
        "self_s": own,
        "upload_ms": (1e3 * st.total_s("upload") / n_disp
                      if n_disp else None),
        "record_us_per_dispatch": (1e6 * st.total_s("record") / n_disp
                                   if n_disp else None),
        "frontend_us": (1e6 * (own.get("admit", 0.0)
                               + st.total_s("encode")) / counts["admit"]
                        if counts.get("admit") else None),
        **{f"{k}_share_pct": 100.0 * v / busy if busy > 0 else None
           for k, v in scoped.items()},
        "unscoped_ops_s": [[n, ns * 1e-9]
                           for n, ns in unscoped.most_common(8)],
        "clock": {"runs": len(offsets),
                  "inside_share": (sum(o <= CLOCK_TOLERANCE_NS
                                       for o in offsets) / len(offsets)
                                   if offsets else None),
                  "median_us": (float(np.median(offsets)) * 1e-3
                                if offsets else None),
                  "worst_us": max(offsets) * 1e-3 if offsets else None},
    }


@contextlib.contextmanager
def marked_window():
    """``harness.serve`` starts the profiler just before the load generator
    opens the window and stops it just after the window closes; mark that
    span as a ``bench.window`` annotation, on the profiler's clock."""
    import jax

    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    window = contextlib.ExitStack()

    def start_trace(*args, **kwargs):
        start(*args, **kwargs)
        window.enter_context(jax.profiler.TraceAnnotation(WINDOW))

    def stop_trace():
        window.close()
        stop()

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    try:
        yield
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop


def main(argv=None) -> int:
    from jax.sharding import Mesh

    from bench import harness
    from bench.traffic import input_sensor

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)

    spec = harness.Spec(ROOT)
    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    n = cell["chips"]
    try:
        devices = harness.require_chips(n)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache(ROOT)
    model_mod, _ = harness.model_modules(cfg)
    packed = model_mod.build(cfg, model_mod.make_weights(cfg, args.seed))
    mesh = Mesh(np.asarray(devices[:n]), ("data",))
    harness.warm_buckets(packed, mesh, cfg["sensor"]["num_steps"])
    traffic_spec = {"sensor": input_sensor(cfg),
                    "mix": spec.traffic(cell["traffic"]),
                    "seed": args.seed, "seconds": args.seconds}
    profile_dir = tempfile.mkdtemp(prefix="bench-stages-")
    try:
        with marked_window():
            _, summary, _, prof_s, compiled = harness.serve(
                packed, mesh, traffic_spec, True, profile_dir)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            for f in glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                               recursive=True):
                shutil.copy(f, os.path.join(
                    args.keep, f"{args.workload}.{args.seed}.xplane.pb"))
        st = load(profile_dir, n, forward_scopes(
            packed, mesh, cfg["sensor"]["num_steps"]))
        dev = devtrace.load(profile_dir, n, prof_s)
    finally:
        shutil.rmtree(profile_dir, ignore_errors=True)
    out = report(st, dev)
    out.update(workload=args.workload, seed=args.seed,
               compiled_in_window=compiled,
               device_idle_pct=100.0 * (1.0 - dev.busy_s / dev.window_s),
               device_ops_s=dev.breakdown()["device_ops"],
               generator=summary)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
