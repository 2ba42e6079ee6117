"""One benchmark run of one cell: serve the cell's traffic, measure, check.

The path under test is the deployed one, seen from a client: a
``SpikeSocketServer`` (TCP, the ingest wire format) in front of
``StreamServer`` -> ``execute_plan`` -> ``run_sharded`` -> the jitted forward
(MEM_E writer, ``event_synapse`` Pallas kernel, LIF scan, every layer).  The
load generator is a child process (``loadgen.py``) that never starts JAX.

Everything that belongs to one configuration, traffic mix or metric sits in
files of its own, found by the names in ``BENCHMARK.json``:

* configuration ``<c>``: the ``file`` its entry names; its ``model`` field
  names ``bench/models/<model>.py`` (the served program) and
  ``bench/models/<model>_ref.py`` (the plain reference);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by ``traffic.py``;
* metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(run)`` returns the
  value or ``None`` when it finds nothing to read.

A model module provides, for a configuration ``cfg`` (its parsed file):

* ``layers(cfg)``: its synapse layers in order, each a
  ``bench.roofline.Layer`` from ``dense``, ``conv2d`` or ``sum_pool2d``;
  the traced window's operations and bytes are counted from them;
* ``make_weights(cfg, seed)``: the weights, drawn from the seed alone;
* ``build(cfg, weights)``: the program's packed model for them, built as a
  user builds it.

Its reference imports nothing of the program and provides
``forward(cfg, weights, rasters, weight_dtype=None)``, which returns each
raster's output spikes (bool ``[steps, n_out]``) and the input events each
layer received over all of them, and ``LOWER_PRECISION``, the control's
weight type keyed by the configuration's stated one.

The server is built as ``socket_serve`` builds it: ``BucketPolicy.for_mesh``
over the cell's chips and the server's default queue capacity.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from bench import devtrace
from bench.roofline import layer_work, peaks_for
from bench.traffic import Traffic, input_sensor

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
SAMPLE = 128            # answers compared with the reference, drawn by seed
CPUS = sorted(os.sched_getaffinity(0))


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


class Spec:
    """``BENCHMARK.json`` and the files its names lead to."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = root
        self.data = json.loads((root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for c in self.data["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(
            (self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def reader(self, name: str):
        """The ``read`` function of ``bench/metrics/<name>.py``."""
        path = self.root / "bench" / "metrics" / f"{name}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read

    def metrics(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics (``trace`` false) or per-layer
        metrics (``trace`` true).  A metric without ``workloads`` belongs to
        every cell that reports what it ``moves``."""
        e2e = [m for m in self.data["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if cell in m.get("workloads", [cell])
                and m["moves"] in names]


def model_modules(cfg: dict):
    """The configuration's model module and its plain reference."""
    return (importlib.import_module(f"bench.models.{cfg['model']}"),
            importlib.import_module(f"bench.models.{cfg['model']}_ref"))


def require_chips(n: int):
    """The first ``n`` accelerator devices; :class:`NoChip` otherwise."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator (platform cpu)")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def enable_compile_cache(root: pathlib.Path = ROOT) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), for every compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the cell, its configuration and mix as
    run, and what the run measured."""

    cell: dict
    cfg: dict
    mix: dict
    n_chips: int
    setup_s: float
    req: dict                     # per window request, from loadgen
    window: tuple[float, float]   # (t0, t1) on time.monotonic()
    dispatches: list | None = None    # per dispatch of the window (trace)
    queue_s: np.ndarray | None = None  # per request of the window (trace)
    device: devtrace.DeviceTrace | None = None
    work: list | None = None    # per layer (ops, bytes, kernel) (trace)
    peaks: dict | None = None


def _recorder(collect: bool):
    """The server's flight recorder, as deployed; when ``collect`` is set
    it also keeps every trace completed while ``kept`` is a list."""
    from repro.engine.tracing import FlightRecorder

    class WindowRecorder(FlightRecorder):
        kept: list | None = None

        def complete(self, rid, t):
            tr = self.active.get(rid)
            super().complete(rid, t)
            if self.kept is not None and tr is not None:
                self.kept.append(tr)

    return WindowRecorder() if collect else FlightRecorder()


def _read_line(child) -> dict:
    line = child.stdout.readline()
    if not line:
        raise RuntimeError(f"the load generator exited ({child.wait()}) "
                           f"before it reported")
    msg = json.loads(line)
    if msg.get("event") == "error":
        raise RuntimeError(f"load generator: {msg['detail']}")
    return msg


def _own_cpu(child_pid: int) -> None:
    """Give the load generator the last CPU this process started with, and
    every thread of this process the others, so neither side preempts the
    other."""
    if len(CPUS) < 4:
        return
    os.sched_setaffinity(child_pid, CPUS[-1:])
    for tid in os.listdir("/proc/self/task"):
        try:
            os.sched_setaffinity(int(tid), CPUS[:-1])
        except OSError:         # a thread that has just ended
            pass


def serve(packed, mesh, traffic_spec: dict, trace: bool,
          profile_dir: str | None):
    """Serve the traffic through the socket server; returns the load
    generator's record, its summary, the window's traces, the profiler
    window (host seconds) and how many forwards compiled inside the
    window."""
    from repro.engine import BucketPolicy, trace_count
    from repro.launch.socket_serve import SpikeSocketServer, serving_thread

    import jax

    tracer = _recorder(trace)
    srv = SpikeSocketServer(packed, policy=BucketPolicy.for_mesh(mesh.size),
                            mesh=mesh, tracer=tracer)
    out = tempfile.mkdtemp(prefix="bench-loadgen-")
    spec = dict(traffic_spec, port=srv.address[1],
                out=os.path.join(out, "record.npz"))
    prof_s = None
    try:
        with serving_thread(srv):
            child = subprocess.Popen(
                [sys.executable, str(BENCH / "loadgen.py"), json.dumps(spec)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            _own_cpu(child.pid)
            try:
                quiet = _read_line(child)
                compiled0 = trace_count()
                if trace:
                    tracer.kept = []
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    jax.profiler.start_trace(profile_dir,
                                             profiler_options=opts)
                    p0 = time.monotonic()
                child.stdin.write("go\n")
                child.stdin.flush()
                done = _read_line(child)
                if trace:
                    prof_s = time.monotonic() - p0
                    jax.profiler.stop_trace()
                compiled = trace_count() - compiled0
                if child.wait(timeout=60) != 0:
                    raise RuntimeError("the load generator failed")
            finally:
                if child.poll() is None:
                    child.kill()
                    child.wait()
        with np.load(spec["out"]) as z:
            rec = {k: z[k] for k in z.files}
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return rec, dict(done, **quiet), tracer.kept if trace else None, \
        prof_s, compiled


def warm_buckets(packed, mesh, steps: int) -> int:
    """Compile (or load from the cache) the forward of every bucket the
    server dispatches ``steps``-step requests to on ``mesh``; returns how
    many."""
    from repro.engine import BucketPolicy, run_sharded

    bp = BucketPolicy.for_mesh(mesh.size)
    ts = bp.t_bucket(steps)
    for b in bp.batch_sizes:
        run_sharded(packed, np.zeros((b, ts, packed.n_in), np.float32),
                    mesh=mesh, with_stats=False)
    return len(bp.batch_sizes)


def _dispatches(traces):
    """Per dispatch of the window (deduplicated by its ordinal) and each
    request's queue wait, from the spans of the requests completed after
    the window opened (the warm-up traffic has drained by then)."""
    disp, waits = {}, []
    for tr in traces:
        spans = {s.kind: s for s in tr.spans}
        waits.append(spans["queue"].t1 - spans["queue"].t0)
        d = spans["dispatch"]
        seq = d.attrs["seq"]
        if seq not in disp:
            disp[seq] = {"b_pad": d.attrs["b_pad"],
                         "n_requests": d.attrs["n_requests"],
                         "t_pad": d.attrs["t_pad"],
                         "dispatch_s": d.t1 - d.t0,
                         "pad_s": spans["pad"].t1 - spans["pad"].t0,
                         "slice_s": spans["slice"].t1 - spans["slice"].t0}
    return [disp[k] for k in sorted(disp)], np.asarray(waits)


def sample(rec: dict, seed: int) -> np.ndarray:
    """Window requests whose answers are compared with the reference, drawn
    by seed from the answered ones (every request is equally long)."""
    ok = np.flatnonzero(rec["status"] == 0)
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 11])
    return np.sort(rng.choice(ok, size=min(SAMPLE, len(ok)), replace=False))


def served_bits(rec: dict, i: int) -> bytes:
    return rec["bits"][rec["offsets"][i]:rec["offsets"][i + 1]].tobytes()


def compare(cfg, weights, traffic: Traffic, rec: dict, ids, ref_mod,
            weight_dtype=None) -> dict:
    """The reference over the sampled requests against what was served (or,
    with ``weight_dtype``, the control in the program's place)."""
    rasters = [traffic.request(int(i)) for i in ids]
    ref, events = ref_mod.forward(cfg, weights, rasters)
    if weight_dtype is None:
        got = [served_bits(rec, int(i)) for i in ids]
    else:
        ctl, _ = ref_mod.forward(cfg, weights, rasters, weight_dtype)
        got = [np.packbits(o, axis=None).tobytes() for o in ctl]
    wrong = sum(g != np.packbits(r, axis=None).tobytes()
                for g, r in zip(got, ref))
    return {"wrong_answers": int(wrong), "compared": len(ids),
            "ref_out_spikes": int(sum(int(r.sum()) for r in ref)),
            "layer_events": events,
            "steps": int(sum(r.shape[0] for r in rasters))}


def checks_of(cmp: dict, status: np.ndarray) -> dict:
    """The numbers that decide ``correct``, each ``{"value", "limit"}``."""
    return {
        "wrong_answers": {"value": cmp["wrong_answers"], "limit": 0},
        "unanswered": {"value": int((status == 2).sum()), "limit": 0},
        "silent_reference": {"value": int(cmp["ref_out_spikes"] == 0),
                             "limit": 0},
    }


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def window_work(cfg, model_mod, rec: dict, cmp: dict, n_dispatches: int,
                n_chips: int) -> list[tuple[float, float, str]]:
    """Per layer ``(ops, bytes, kernel)`` of the traced window, from the
    model module's ``layers(cfg)``: layer 0's input events are counted
    exactly; deeper layers' are the sample's events per real step times the
    window's real steps."""
    rows = len(rec["status"]) * int(cfg["sensor"]["num_steps"])
    events = [float(rec["events_in"].sum())]
    for e in cmp["layer_events"][1:]:
        events.append(e / cmp["steps"] * rows)
    return [(*layer_work(layer, ev, rows, n_dispatches, n_chips,
                         cfg["quant_bits"]), layer.kernel)
            for layer, ev in zip(model_mod.layers(cfg), events)]


def run_cell(spec: Spec, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, devices, cfg: dict | None = None,
             with_control: bool = False):
    """Run one cell on ``devices``; returns the result object (without
    ``checks``) and the checks, each ``{"value": v, "limit": l}``.
    ``with_control`` adds the control's reading over the same answers:
    the reference in the precision below float32 (``bench/control.py``)."""
    import jax
    from jax.sharding import Mesh

    cell = spec.cell(cell_name)
    cfg = cfg if cfg is not None else spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    n = cell["chips"]
    model_mod, ref_mod = model_modules(cfg)

    t = time.monotonic()
    weights = model_mod.make_weights(cfg, seed)
    packed = model_mod.build(cfg, weights)
    mesh = Mesh(np.asarray(devices[:n]), ("data",))
    t_build = time.monotonic() - t

    t = time.monotonic()
    n_buckets = warm_buckets(packed, mesh, cfg["sensor"]["num_steps"])
    log(f"set-up: weights+map+pack {t_build:.3f} s, {n_buckets} buckets "
        f"warmed {time.monotonic() - t:.3f} s")

    traffic_spec = {"sensor": input_sensor(cfg), "mix": mix, "seed": seed,
                    "seconds": seconds}
    profile_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
    try:
        rec, summary, traces, prof_s, compiled = serve(
            packed, mesh, traffic_spec, trace, profile_dir)
        t0, t1 = (float(x) for x in rec["window"])
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:n])
        dev_trace = (devtrace.load(profile_dir, n, prof_s)
                     if trace else None)
    finally:
        if profile_dir:
            shutil.rmtree(profile_dir, ignore_errors=True)
    del packed
    gc.collect()

    log(f"load generator: {json.dumps(summary, sort_keys=True)}")
    log(f"forwards compiled inside the window: {compiled}")

    traffic = Traffic(input_sensor(cfg), mix, seed)
    ids = sample(rec, seed)
    t = time.monotonic()
    cmp = compare(cfg, weights, traffic, rec, ids, ref_mod)
    log(f"reference over {cmp['compared']} answers: "
        f"{time.monotonic() - t:.3f} s, {cmp['ref_out_spikes']} output "
        f"spikes, events per layer {cmp['layer_events']}")

    run = Run(cell=cell, cfg=cfg, mix=mix, n_chips=n, setup_s=t0 - t_start,
              req=rec, window=(t0, t1))
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": n, "memory_peak_bytes": int(peak)}
    out = {}
    if trace:
        run.dispatches, run.queue_s = _dispatches(traces)
        run.device = dev_trace
        run.peaks = peaks_for(dev0.device_kind)
        run.work = window_work(cfg, model_mod, rec, cmp,
                               len(run.dispatches), n)
        device.update(busy_s=dev_trace.busy_s, window_s=dev_trace.window_s)
        log(f"per-chip device busy {dev_trace.per_chip_busy_s} s over "
            f"{dev_trace.window_s} s")
        out["breakdown"] = dev_trace.breakdown()
    metrics = {}
    for m in spec.metrics(cell_name, trace):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if trace:
        rps = spec.reader("served_rps")(run)
        log(f"traced window served {rps} requests/s")
    status = rec["status"]
    checks = checks_of(cmp, status)
    result = {"correct": is_correct(checks),
              "attempted": int(len(status)),
              "failed": int((status != 0).sum()),
              "metrics": metrics, "device": device, **out}
    if with_control:
        ctl = checks_of(compare(cfg, weights, traffic, rec, ids, ref_mod,
                                ref_mod.LOWER_PRECISION["float32"]), status)
        result["control"] = {"correct": is_correct(ctl), "checks": ctl}
    return result, checks


def emit(result: dict, checks: dict) -> None:
    """The check lines last on standard error, then the result as the last
    line of standard output, with the checks as its last key."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(dict(result, checks=checks)), flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (no answer) sort last."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if not len(v):
        return math.nan
    return float(v[max(0, math.ceil(q / 100 * len(v)) - 1)])
