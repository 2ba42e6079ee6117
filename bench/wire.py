"""The client's side of the spike-stream wire protocol, kept with the benchmark.

A copy of the framing in ``src/repro/engine/ingest.py`` (protocol v2), so the
load generator speaks to the server without importing the program (and so
without importing JAX).  Frame: ``'MG', version u8, kind u8, len u32`` then the
payload.  A REQUEST payload is ``req_id u32, T u32, n_in u32, slack f64,
name_len u8`` followed by the ``[T, n_in]`` raster bit-packed with
``np.packbits``; a RESULT payload is ``req_id u32, T u32, n_out u32`` and the
bit-packed output raster; a REJECT payload is ``req_id u32`` and a reason.
"""

from __future__ import annotations

import struct

import numpy as np

MAGIC = b"MG"
VERSION = 2
KIND_REQUEST, KIND_RESULT, KIND_REJECT = 0, 1, 2

HEADER = struct.Struct(">2sBBI")
REQ_HEAD = struct.Struct(">IIIdB")
RES_HEAD = struct.Struct(">III")
REJ_HEAD = struct.Struct(">I")


def pack_raster(raster: np.ndarray) -> bytes:
    """A 0/1 raster as the wire's bit-packed bytes (row-major)."""
    return np.packbits(np.asarray(raster, dtype=bool), axis=None).tobytes()


def encode_request(req_id: int, t: int, n_in: int, bits: bytes,
                   slack: float) -> bytes:
    payload = REQ_HEAD.pack(req_id, t, n_in, float(slack), 0) + bits
    return HEADER.pack(MAGIC, VERSION, KIND_REQUEST, len(payload)) + payload


class Decoder:
    """Incremental frame parser: ``feed(chunk)`` returns the frames it
    completed as ``(kind, payload)`` pairs."""

    def __init__(self):
        self._buf = bytearray()

    def feed(self, chunk: bytes) -> list[tuple[int, bytes]]:
        self._buf.extend(chunk)
        frames = []
        while len(self._buf) >= HEADER.size:
            magic, _, kind, length = HEADER.unpack_from(self._buf)
            if magic != MAGIC:
                raise ValueError(f"bad magic {magic!r} from the server")
            if len(self._buf) < HEADER.size + length:
                break
            frames.append((kind, bytes(
                self._buf[HEADER.size:HEADER.size + length])))
            del self._buf[:HEADER.size + length]
        return frames


def decode_result(payload: bytes) -> tuple[int, int, int, bytes]:
    """``(req_id, T, n_out, packed bits)`` of a RESULT payload."""
    req_id, t, n_out = RES_HEAD.unpack_from(payload)
    return req_id, t, n_out, payload[RES_HEAD.size:]


def decode_reject(payload: bytes) -> tuple[int, str]:
    (req_id,) = REJ_HEAD.unpack_from(payload)
    return req_id, payload[REJ_HEAD.size:].decode(errors="replace")
