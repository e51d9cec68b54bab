"""ctypes bindings for the native (C++) host runtime.

The compute path is JAX/XLA; the runtime around it — here the entry-payload
arena backing the device's columnar log — is native C++ (see
native/payload_store.cc). The library is built on demand from the tracked
sources with the in-image g++ (no pip deps). A library is trusted only if
it was built here from the sources as they are now: the build records the
sources' sha256 next to it, and a mismatch rebuilds. A failed build raises.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_SO = os.path.join(_DIR, "libraft_tpu_native.so")
_STAMP = _SO + ".sha256"
_SRCS = [os.path.join(_DIR, f) for f in ("payload_store.cc", "raftpb_codec.cc")]
_lock = threading.Lock()
_lib = None


def _sources_digest() -> str:
    h = hashlib.sha256()
    for src in _SRCS:
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def build(force: bool = False) -> None:
    """Build the payload library from the tracked sources (`force`: even
    when the stamp matches). Raises RuntimeError with make's output on a
    failed build."""
    digest = _sources_digest()
    if not force and os.path.exists(_SO) and os.path.exists(_STAMP):
        with open(_STAMP) as f:
            if f.read().strip() == digest:
                return
    # build under a private name, then rename: a concurrent loader (test
    # workers share the tree) never maps a half-written library
    tmp = f"{os.path.basename(_SO)}.{os.getpid()}.tmp"
    r = subprocess.run(
        ["make", "-s", "-B", f"SO={tmp}", tmp],
        cwd=_DIR, capture_output=True, text=True,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"native build failed (make exit {r.returncode}):\n"
            f"{r.stdout}{r.stderr}"
        )
    os.replace(os.path.join(_DIR, tmp), _SO)
    with open(_STAMP + f".{os.getpid()}.tmp", "w") as f:
        f.write(digest + "\n")
    os.replace(_STAMP + f".{os.getpid()}.tmp", _STAMP)


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        build()
        lib = ctypes.CDLL(_SO)
        c = ctypes
        lib.ps_new.restype = c.c_void_p
        lib.ps_new.argtypes = [c.c_int32]
        lib.ps_free.argtypes = [c.c_void_p]
        lib.ps_put.argtypes = [
            c.c_void_p, c.c_int32, c.c_int32, c.c_int32, c.c_int32,
            c.c_char_p, c.c_int32,
        ]
        lib.ps_get_len.restype = c.c_int32
        lib.ps_get_len.argtypes = [
            c.c_void_p, c.c_int32, c.c_int32, c.c_int32, c.POINTER(c.c_int32)
        ]
        lib.ps_get.restype = c.c_int32
        lib.ps_get.argtypes = [
            c.c_void_p, c.c_int32, c.c_int32, c.c_int32, c.c_char_p, c.c_int32
        ]
        lib.ps_truncate_from.argtypes = [c.c_void_p, c.c_int32, c.c_int32]
        lib.ps_compact_below.argtypes = [c.c_void_p, c.c_int32, c.c_int32]
        lib.ps_total_bytes.restype = c.c_int64
        lib.ps_total_bytes.argtypes = [c.c_void_p]
        lib.ps_lane_count.restype = c.c_int32
        lib.ps_lane_count.argtypes = [c.c_void_p, c.c_int32]
        lib.ps_get_batch.restype = c.c_int64
        lib.ps_get_batch.argtypes = [
            c.c_void_p,
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            c.c_int32,
            np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS"),
            c.c_int64,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
            np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS"),
        ]
        _lib = lib
        return _lib


class NativePayloadStore:
    """Drop-in for api.rawnode.EntryStore backed by the C++ arena. Snapshots
    (rare, structured) stay Python-side."""

    def __init__(self, n_lanes: int):
        lib = _load()
        self._lib = lib
        self._h = ctypes.c_void_p(lib.ps_new(n_lanes))
        self._snap = [None] * n_lanes

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.ps_free(h)
            self._h = None

    # EntryStore interface -------------------------------------------------

    def put(self, lane: int, e):
        data = e.data or b""  # nil payloads normalize at the store boundary
        self._lib.ps_put(
            self._h, lane, e.index, e.term, e.type, data, len(data)
        )

    def get(self, lane: int, index: int, term: int):
        t = ctypes.c_int32(0)
        n = self._lib.ps_get_len(self._h, lane, index, term, ctypes.byref(t))
        if n < 0:
            return (0, b"")
        buf = ctypes.create_string_buffer(n)
        self._lib.ps_get(self._h, lane, index, term, buf, n)
        return (int(t.value), buf.raw)

    def truncate_from(self, lane: int, index: int):
        self._lib.ps_truncate_from(self._h, lane, index)

    def compact_below(self, lane: int, index: int):
        self._lib.ps_compact_below(self._h, lane, index)

    def set_snapshot(self, lane: int, snap):
        self._snap[lane] = snap

    def snapshot(self, lane: int):
        return self._snap[lane]

    # batched extras -------------------------------------------------------

    def total_bytes(self) -> int:
        return int(self._lib.ps_total_bytes(self._h))

    def get_batch(self, lanes, indexes, terms):
        """Vectorized lookup: returns (payload bytearray, offsets[int64],
        lens[int32] with -1 for missing, types[int32])."""
        lanes = np.ascontiguousarray(lanes, np.int32)
        indexes = np.ascontiguousarray(indexes, np.int32)
        terms = np.ascontiguousarray(terms, np.int32)
        n = len(lanes)
        offsets = np.zeros(n, np.int64)
        lens = np.zeros(n, np.int32)
        types = np.zeros(n, np.int32)
        cap = 1 << 16
        while True:
            out = np.zeros(cap, np.uint8)
            r = self._lib.ps_get_batch(
                self._h, lanes, indexes, terms, n, out, cap, offsets, lens, types
            )
            if r >= 0:
                return out[:r].tobytes(), offsets, lens, types
            cap = max(cap * 2, int(-r))


def make_payload_store(n_lanes: int) -> NativePayloadStore:
    """The C++ payload arena (built on first use; a failed build raises)."""
    return NativePayloadStore(n_lanes)
