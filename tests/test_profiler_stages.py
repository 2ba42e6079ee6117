"""The server's stages in the JAX profiler's trace and the forward's named
scopes, on the CPU with the smoke model.

``repro.engine.tracing.stage`` puts each stage of the served path into the
profiler's trace as a ``serve.<name>`` annotation, on the clock the device's
ops use; ``_forward_impl`` names each layer's steps in the ops' metadata.
Neither may change a served bit or compile anything.
"""

import glob
import os

import jax
import numpy as np
import pytest

from repro.engine import (BucketPolicy, FlightRecorder, StreamServer,
                          trace_count)
from repro.engine import batched_run as br
from repro.launch.serve_snn import build_demo_model
from repro.launch.socket_serve import (SpikeClient, SpikeSocketServer,
                                       serving_thread)

POLICY = BucketPolicy(batch_sizes=(2,), time_steps=(8,))


@pytest.fixture(scope="module")
def packed():
    return build_demo_model("mlp", smoke=True, seed=0).pack()


def _stream(packed, t=6, seed=0, p=0.2):
    rng = np.random.default_rng(seed)
    return (rng.random((t, packed.n_in)) < p).astype(np.float32)


def _stages(profile_dir) -> list[tuple[str, int, int, dict]]:
    """``(name, start_ns, end_ns, attributes)`` of the ``serve.*`` events
    on the host plane, outer before inner."""
    (path,) = glob.glob(os.path.join(str(profile_dir), "**",
                                     "*.xplane.pb"), recursive=True)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _profiled(profile_dir, fn):
    jax.profiler.start_trace(str(profile_dir))
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def _serve_over_socket(packed, streams, profile_dir=None):
    """Answers to ``streams`` from a socket server, with the profiler on
    while they are served when ``profile_dir`` is given."""
    srv = SpikeSocketServer(packed, policy=POLICY)
    host, port = srv.address
    with serving_thread(srv, idle_flush_s=0.05):
        cli = SpikeClient(host, port)
        cli.send(streams[0])                      # compile outside the trace
        cli.send(streams[0])
        cli.recv_all()

        def serve():
            ids = [cli.send(s) for s in streams]
            cli.recv_all()
            return [cli.results[i] for i in ids]

        out = serve() if profile_dir is None else _profiled(profile_dir,
                                                            serve)
        cli.close()
    return out


def test_dispatch_stages_nest_in_order(packed, tmp_path):
    """One dispatch: ``serve.dispatch`` holds pad, upload, launch, fetch
    and slice in that order, then the recorder's bookkeeping, and carries
    the dispatch's ordinal, bucket, size and trigger."""
    srv = StreamServer(packed, policy=POLICY, tracer=FlightRecorder())
    srv.submit(_stream(packed, seed=1))
    srv.submit(_stream(packed, seed=2))
    srv.collect()                                  # compile the bucket

    def dispatch():
        srv.submit(_stream(packed, seed=3))
        srv.submit(_stream(packed, seed=4))        # full bucket: dispatches
        return srv.collect()

    assert len(_profiled(tmp_path, dispatch)) == 2
    stages = _stages(tmp_path)
    (disp,) = [s for s in stages if s[0] == "serve.dispatch"]
    _, d0, d1, attrs = disp
    assert attrs["seq"] == 1 and attrs["b_pad"] == 2
    assert attrs["n_requests"] == 2 and attrs["why"] == "full_bucket"
    inner = [s for s in stages if s is not disp and d0 <= s[1] and s[2] <= d1]
    names = [s[0][len("serve."):] for s in inner]
    assert names[:5] == ["pad", "upload", "launch", "fetch", "slice"]
    # the recorder's two blocks: the dispatch's, then one per request
    assert names[5:] == ["record"] * 3
    for a, b in zip(inner, inner[1:]):
        assert a[2] <= b[1], f"{a[0]} overlaps {b[0]}"


def test_socket_stages_cover_the_served_path(packed, tmp_path):
    """Over the socket: the loop's wait, read and poll, one admit and one
    encode per request, and every dispatch inside an admit or a poll."""
    streams = [_stream(packed, seed=10 + i) for i in range(4)]
    _serve_over_socket(packed, streams, tmp_path)
    stages = _stages(tmp_path)
    names = [s[0] for s in stages]
    for name in ("serve.wait", "serve.read", "serve.poll"):
        assert name in names
    assert names.count("serve.admit") == 4
    assert names.count("serve.encode") == 4
    assert all("queued" in s[3] for s in stages if s[0] == "serve.wait")
    outer = [s for s in stages if s[0] in ("serve.admit", "serve.poll")]
    for d in (s for s in stages if s[0] == "serve.dispatch"):
        assert any(o[1] <= d[1] and d[2] <= o[2] for o in outer)


def test_answers_identical_with_the_profiler_on(packed, tmp_path):
    """A profiler session changes no served bit and compiles nothing."""
    streams = [_stream(packed, seed=20 + i) for i in range(4)]
    off = _serve_over_socket(packed, streams)
    n0 = trace_count()
    on = _serve_over_socket(packed, streams, tmp_path)
    assert trace_count() == n0
    assert len(off) == len(on) == 4
    for a, b in zip(off, on):
        assert np.array_equal(a, b)


def test_forward_names_each_layer_step(packed):
    """The compiled forward's op metadata carries ``layer<i>/mem_e``,
    ``layer<i>/synapse`` and ``layer<i>/lif`` for every layer."""
    spikes = jax.ShapeDtypeStruct((2, 8, packed.n_in), np.float32)
    text = br._forward.lower(packed, spikes, None).compile().as_text()
    for i in range(len(packed.layers)):
        for step in ("mem_e", "synapse", "lif"):
            assert f"layer{i}/{step}/" in text, f"layer{i}/{step}"
