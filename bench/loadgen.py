"""The load generator: one TCP client driving the server from a child process.

    python3 bench/loadgen.py '<json spec>'

It imports numpy and the benchmark's own traffic and wire modules, never JAX,
so it neither holds the chip nor shares the server's interpreter lock.  The
spec gives ``port``, ``sensor``, ``mix``, ``seed``, ``seconds`` and ``out``.

Protocol with the parent, one JSON object per line on standard output:
warm-up traffic for the mix's ``warm_s``, drain, ``{"event": "quiet"}``; then
it waits for ``go`` on standard input, drives the measured window for
``seconds``, waits for every answer (at most ``ANSWER_WAIT_S`` past the close),
writes the per-request record to ``out`` (``.npz``) and prints
``{"event": "done", ...}`` with its lateness.

Times are ``time.monotonic()``, the clock the server's spans use.  A request
is due when the closed loop frees a slot, or at its scheduled instant in the
open loop; its latency runs from due to the moment its answer is decoded.
"""

from __future__ import annotations

import gc
import json
import os
import selectors
import socket
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import wire  # noqa: E402
from bench.traffic import WARM_BASE, Traffic  # noqa: E402

ANSWER_WAIT_S = 60.0
OK, REJECTED, MISSING = 0, 1, 2


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


class Client:
    """Non-blocking pipelined client: frames queue in ``outbuf`` and go out
    whenever the socket takes them, so a busy server never deadlocks it."""

    def __init__(self, port: int, traffic: Traffic):
        self.traffic = traffic
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setblocking(False)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sel = selectors.DefaultSelector()
        self.sel.register(self.sock, selectors.EVENT_READ)
        self.dec = wire.Decoder()
        self.outbuf = bytearray()
        self.in_flight = 0
        self.rec: dict[int, list] = {}   # id -> [due, sent, done, status]
        self.bits: dict[int, bytes] = {}
        self.events_in: dict[int, int] = {}
        self._ready: dict[int, bytes] = {}

    def frame(self, i: int) -> bytes:
        f = self._ready.pop(i, None)
        if f is None:
            raster = self.traffic.request(i)
            self.events_in[i] = int(raster.sum())
            f = wire.encode_request(i, raster.shape[0], raster.shape[1],
                                    wire.pack_raster(raster),
                                    self.traffic.slack)
        return f

    def prepare(self, i: int) -> None:
        """Build request ``i``'s frame ahead of its due time."""
        if i not in self._ready:
            self._ready[i] = self.frame(i)

    def send(self, i: int, due: float) -> None:
        self.outbuf += self.frame(i)
        self.rec[i] = [due, time.monotonic(), np.nan, MISSING]
        self.in_flight += 1

    def pump(self, timeout: float) -> None:
        """Write what the socket takes, wait up to ``timeout`` for answers,
        and record each one as it is decoded."""
        if self.outbuf:
            try:
                n = self.sock.send(self.outbuf)
                del self.outbuf[:n]
            except BlockingIOError:
                pass
        ev = selectors.EVENT_READ | (selectors.EVENT_WRITE
                                     if self.outbuf else 0)
        self.sel.modify(self.sock, ev)
        for _, mask in self.sel.select(max(timeout, 0.0)):
            if not mask & selectors.EVENT_READ:
                continue
            chunk = self.sock.recv(1 << 20)
            if not chunk:
                raise ConnectionError("the server closed the connection")
            now = time.monotonic()
            for kind, payload in self.dec.feed(chunk):
                if kind == wire.KIND_RESULT:
                    rid, _, _, bits = wire.decode_result(payload)
                    status = OK
                    self.bits[rid] = bits
                elif kind == wire.KIND_REJECT:
                    rid, _ = wire.decode_reject(payload)
                    status = REJECTED
                else:
                    continue
                r = self.rec[rid]
                r[2], r[3] = now, status
                self.in_flight -= 1

    def drain(self, until: float) -> None:
        while self.in_flight and time.monotonic() < until:
            self.pump(min(0.05, until - time.monotonic()))


def closed_loop(cli: Client, first: int, outstanding: int,
                seconds: float) -> int:
    """Keep ``outstanding`` requests in flight for ``seconds``; returns how
    many were sent."""
    end = time.monotonic() + seconds
    i = first
    while True:
        now = time.monotonic()
        if now >= end:
            return i - first
        while cli.in_flight < outstanding:
            cli.send(i, time.monotonic())
            i += 1
        cli.prepare(i)
        cli.pump(min(0.01, end - now))


def open_loop(cli: Client, first: int, dues: np.ndarray) -> np.ndarray:
    """Send request ``first + k`` at ``dues[k]`` (absolute); returns how late
    each went out."""
    late = np.zeros(len(dues))
    k = 0
    while k < len(dues):
        now = time.monotonic()
        while k < len(dues) and dues[k] <= now:
            cli.send(first + k, float(dues[k]))
            late[k] = cli.rec[first + k][1] - dues[k]
            k += 1
            now = time.monotonic()
        if k < len(dues):
            if dues[k] - now > 0.002:
                cli.prepare(first + k)
            cli.pump(min(0.01, max(dues[k] - time.monotonic(), 0.0)))
    return late


def drive(cli: Client, mix: dict, first: int, seconds: float, phase: int):
    if mix["loop"] == "closed":
        return closed_loop(cli, first, int(mix["outstanding"]), seconds), None
    start = time.monotonic()
    dues = start + cli.traffic.due_times(seconds, phase)
    late = open_loop(cli, first, dues)
    # the window lasts its whole length even when the last burst came early
    while time.monotonic() < start + seconds:
        cli.pump(start + seconds - time.monotonic())
    return len(dues), late


def main(argv: list[str]) -> int:
    # no collector pauses in the generator's timing: this short-lived
    # process makes no reference cycles, and its record only grows
    gc.disable()
    spec = json.loads(argv[0])
    mix, seconds = spec["mix"], float(spec["seconds"])
    traffic = Traffic(spec["sensor"], mix, int(spec["seed"]))
    cli = Client(int(spec["port"]), traffic)

    n_warm, _ = drive(cli, mix, WARM_BASE, float(mix["warm_s"]), 0)
    cli.drain(time.monotonic() + ANSWER_WAIT_S)
    warm_failed = sum(1 for i, r in cli.rec.items() if r[3] != OK)
    if cli.in_flight:
        emit(event="error", detail=f"{cli.in_flight} warm-up requests "
                                   f"unanswered after {ANSWER_WAIT_S} s")
        return 1
    cli.rec.clear()
    cli.bits.clear()
    cli.events_in.clear()
    emit(event="quiet", warm_requests=n_warm, warm_failed=warm_failed)
    if sys.stdin.readline().strip() != "go":
        return 1

    t0 = time.monotonic()
    n, late = drive(cli, mix, 0, seconds, 1)
    t1 = t0 + seconds
    cli.drain(t1 + ANSWER_WAIT_S)

    ids = np.arange(n)
    rec = np.array([cli.rec[i] for i in ids], dtype=np.float64).reshape(n, 4)
    blobs = [cli.bits.get(int(i), b"") for i in ids]
    offsets = np.cumsum([0] + [len(b) for b in blobs])
    np.savez(spec["out"], due=rec[:, 0], sent=rec[:, 1], done=rec[:, 2],
             status=rec[:, 3].astype(np.int8),
             events_in=np.array([cli.events_in[int(i)] for i in ids]),
             bits=np.frombuffer(b"".join(blobs), dtype=np.uint8),
             offsets=offsets, window=np.array([t0, t1]))
    summary = {"event": "done", "requests": n, "unanswered": cli.in_flight}
    lag = rec[:, 1] - rec[:, 0] if late is None else late
    if len(lag):
        summary["lateness_ms"] = {
            "p50": float(np.percentile(lag, 50) * 1e3),
            "p99": float(np.percentile(lag, 99) * 1e3),
            "max": float(lag.max() * 1e3)}
    emit(**summary)
    cli.sock.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
