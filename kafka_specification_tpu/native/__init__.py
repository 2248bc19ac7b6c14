"""Native (C++) runtime components, bound via ctypes.

`FpSet` — host-side open-addressing 64-bit fingerprint set (fpset.cpp), the
checker's spill/backstop dedup store (SURVEY.md §2.5): the device-resident
sorted set (ops/dedup.py) is the fast path while fingerprints fit in HBM;
this is the TLC-FPSet-equivalent for runs that outgrow it, and the backend
of engine.check(..., visited_backend="host").

`rows_digest` — the host's one-pass fingerprint-and-digest twin of the
device's hashed fingerprint (resilience/integrity.py calls it).

The shared library is compiled on first use with g++ -O2 (cached next to the
source); environments without a toolchain fall back to a numpy-based set
with the same interface, and to integrity.py's numpy twin.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "fpset.cpp")
_SO = os.path.join(os.path.dirname(__file__), "_fpset.so")
_lock = threading.Lock()
_lib = None
_build_error = None


def _load():
    global _lib, _build_error
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            if (not os.path.exists(_SO)) or os.path.getmtime(_SO) < os.path.getmtime(
                _SRC
            ):
                # built beside the source under a name of this process's
                # own and renamed into place: several processes of a fresh
                # checkout (test workers) build at once, and none may load
                # a library another is still writing
                tmp = f"{_SO}.{os.getpid()}.tmp"
                try:
                    subprocess.run(
                        ["g++", "-O2", "-shared", "-fPIC", "-o", tmp, _SRC],
                        check=True,
                        capture_output=True,
                    )
                    # kspec: allow(durable-io) a build cache, not run state
                    os.replace(tmp, _SO)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            lib = ctypes.CDLL(_SO)
            lib.fpset_create.restype = ctypes.c_void_p
            lib.fpset_create.argtypes = [ctypes.c_uint64]
            lib.fpset_destroy.argtypes = [ctypes.c_void_p]
            lib.fpset_count.restype = ctypes.c_uint64
            lib.fpset_count.argtypes = [ctypes.c_void_p]
            lib.fpset_capacity.restype = ctypes.c_uint64
            lib.fpset_capacity.argtypes = [ctypes.c_void_p]
            lib.fpset_insert_batch.restype = ctypes.c_uint64
            lib.fpset_insert_batch.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.fpset_insert_compact.restype = ctypes.c_uint64
            lib.fpset_insert_compact.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int32),
            ]
            lib.fpset_contains_batch.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_uint8),
            ]
            lib.fpset_dump.restype = ctypes.c_uint64
            lib.fpset_dump.argtypes = [
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.c_uint64,
            ]
            lib.rows_digest.restype = None
            lib.rows_digest.argtypes = [
                ctypes.POINTER(ctypes.c_uint32),
                ctypes.c_uint64,
                ctypes.c_uint64,
                ctypes.c_uint32,
                ctypes.c_uint32,
                ctypes.POINTER(ctypes.c_uint64),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            _lib = lib
        except Exception as e:  # no toolchain -> numpy fallback
            _build_error = e
        return _lib


def native_available() -> bool:
    return _load() is not None


def rows_digest(rows: np.ndarray, seed_hi: int, seed_lo: int,
                want_fps: bool):
    """One native pass over C-contiguous ``uint32[n, K]`` packed states in
    hashed mode (fpset.cpp ``rows_digest``) -> ``(fps, (count, xor, sum))``
    with ``fps`` the ``uint64[n]`` fingerprints, or None where they were not
    asked for.  Returns None where the library did not load: the caller
    (resilience/integrity.py, which also holds the seeds) then runs its
    numpy twin."""
    lib = _load()
    if lib is None:
        return None
    if (rows.dtype != np.uint32 or rows.ndim != 2
            or not rows.flags.c_contiguous):
        raise ValueError("rows_digest wants C-contiguous uint32[n, K]")
    n, k = rows.shape
    fps = np.empty(n, np.uint64) if want_fps else None
    digest = np.zeros(3, np.uint64)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    lib.rows_digest(
        rows.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        n,
        k,
        seed_hi,
        seed_lo,
        fps.ctypes.data_as(u64p) if want_fps else None,
        digest.ctypes.data_as(u64p),
    )
    return fps, tuple(int(v) for v in digest)


class FpSet:
    """64-bit fingerprint set. insert(fps) -> bool mask of novel entries."""

    def __init__(self, initial_capacity: int = 1 << 16):
        self._lib = _load()
        if self._lib is not None:
            self._h = self._lib.fpset_create(initial_capacity)
            if not self._h:
                raise MemoryError("fpset_create failed")
        else:
            self._py = set()

    def insert(self, fps: np.ndarray) -> np.ndarray:
        fps = np.ascontiguousarray(fps, dtype=np.uint64)
        out = np.empty(fps.shape[0], dtype=np.uint8)
        if self._lib is not None:
            rc = self._lib.fpset_insert_batch(
                self._h,
                fps.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                fps.shape[0],
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            )
            if rc == np.iinfo(np.uint64).max:
                raise MemoryError("fpset grow failed")
        else:
            for i, fp in enumerate(fps.tolist()):
                new = fp not in self._py
                if new:
                    self._py.add(fp)
                out[i] = new
        return out.astype(bool)

    @property
    def native(self) -> bool:
        return self._lib is not None

    def insert_compact(
        self,
        hi: np.ndarray,
        lo: np.ndarray,
        rows: np.ndarray,
        parent: np.ndarray,
        parent_base: int,
        act: np.ndarray,
        arena_rows: np.ndarray,
        arena_parent: np.ndarray,
        arena_act: np.ndarray,
    ) -> int:
        """Fused insert + novel-row compaction (engine/bfs host backend).

        Inserts fp = hi<<32|lo per candidate; for novel ones appends
        rows[i] / parent[i]+parent_base / act[i] into the arena slices
        (which must have >= len(hi) rows of headroom).  Returns the number
        of rows appended.  One C pass — no u64 temp, no novelty-mask
        gather, no per-level concatenate.  Requires the native library
        (callers fall back to insert() + masking when `native` is False).
        """
        n = hi.shape[0]
        assert self._lib is not None
        assert rows.flags.c_contiguous and arena_rows.flags.c_contiguous
        # every arena slice needs headroom for the all-novel worst case —
        # the C pass writes unchecked
        assert (
            arena_rows.shape[0] >= n
            and arena_parent.shape[0] >= n
            and arena_act.shape[0] >= n
        )
        u32p = ctypes.POINTER(ctypes.c_uint32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        w = self._lib.fpset_insert_compact(
            self._h,
            hi.ctypes.data_as(u32p),
            lo.ctypes.data_as(u32p),
            n,
            rows.ctypes.data_as(u32p),
            rows.shape[1],
            parent.ctypes.data_as(i32p),
            parent_base,
            act.ctypes.data_as(i32p),
            arena_rows.ctypes.data_as(u32p),
            arena_parent.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            arena_act.ctypes.data_as(i32p),
        )
        if w == np.iinfo(np.uint64).max:
            raise MemoryError("fpset grow failed")
        return int(w)

    def contains(self, fps: np.ndarray) -> np.ndarray:
        fps = np.ascontiguousarray(fps, dtype=np.uint64)
        out = np.empty(fps.shape[0], dtype=np.uint8)
        if self._lib is not None:
            self._lib.fpset_contains_batch(
                self._h,
                fps.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
                fps.shape[0],
                out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            )
        else:
            for i, fp in enumerate(fps.tolist()):
                out[i] = fp in self._py
        return out.astype(bool)

    def __len__(self):
        if self._lib is not None:
            return int(self._lib.fpset_count(self._h))
        return len(self._py)

    def dump(self) -> np.ndarray:
        if self._lib is None:
            return np.fromiter(self._py, dtype=np.uint64, count=len(self._py))
        n = len(self)
        out = np.empty(n, dtype=np.uint64)
        w = self._lib.fpset_dump(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), n
        )
        return out[:w]

    def __del__(self):
        lib = getattr(self, "_lib", None)
        h = getattr(self, "_h", None)
        if lib is not None and h:
            lib.fpset_destroy(h)
            self._h = None
