"""ctypes binding for the native snapshot maintainer (native/snapshot.cpp).

Builds the shared library on first use with g++ (cached under
``native/_build`` by content hash, see :func:`build_native_lib`);
degrades gracefully to a pure-numpy implementation when no compiler is
available, so the framework never hard-depends on the toolchain.
"""

from __future__ import annotations

import ctypes
import functools
import glob
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "snapshot.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "_build")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False


@functools.cache
def _toolchain_identity() -> bytes:
    """Compiler version + host CPU identity: ``-march=native`` output is
    only valid on the CPU it was built for, and a different g++ may lay
    out the same source differently."""
    version = subprocess.run(
        ["g++", "--version"], check=True, capture_output=True
    ).stdout
    cpu = [platform.machine().encode()]
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"model name", b"flags", b"Features")):
                    cpu.append(line.strip())
                    if len(cpu) >= 3:
                        break
    except OSError:
        pass
    return version + b"\0" + b"\0".join(cpu)


def build_native_lib(src: str, name: str, flags: list[str]) -> ctypes.CDLL:
    """Shared compile-on-first-use machinery for the native libraries.

    The built file is named by a hash of the source bytes, the flags,
    the compiler version and the host CPU identity, so the library that
    loads was compiled on this host from this source: a ``.so`` carried
    over from another machine, another source revision or other flags
    has a different name and is never picked up.  Built via an atomic
    tmp+rename so concurrent processes never CDLL-load a partially
    written file.  Raises on failure — callers wrap with their own
    degrade policy."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read())
    digest.update("\0".join(flags).encode())
    digest.update(_toolchain_identity())
    lib_path = os.path.join(_BUILD_DIR, f"lib{name}-{digest.hexdigest()[:16]}.so")
    if not os.path.exists(lib_path):
        os.makedirs(_BUILD_DIR, exist_ok=True)
        tmp = lib_path + f".tmp.{os.getpid()}"
        subprocess.run(
            ["g++", *flags, "-shared", "-fPIC", "-std=c++17", src, "-o", tmp],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, lib_path)
        # builds of older sources / other hosts are dead weight now
        for stale in glob.glob(os.path.join(_BUILD_DIR, f"lib{name}-*.so")):
            if stale != lib_path:
                try:
                    os.unlink(stale)
                except OSError:
                    pass
    return ctypes.CDLL(lib_path)


def _build_and_load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            lib = build_native_lib(_SRC, "snapshot", ["-O2"])
            lib.snap_create.restype = ctypes.c_void_p
            lib.snap_create.argtypes = [ctypes.c_int64]
            lib.snap_destroy.argtypes = [ctypes.c_void_p]
            lib.snap_size.restype = ctypes.c_int64
            lib.snap_size.argtypes = [ctypes.c_void_p]
            lib.snap_load.restype = ctypes.c_int
            lib.snap_load.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
            lib.snap_apply_deltas.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
            ]
            lib.snap_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            lib.snap_scale_int32.restype = ctypes.c_int
            lib.snap_scale_int32.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            lib.snap_scale_rows.restype = ctypes.c_int
            lib.snap_scale_rows.argtypes = [
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            lib.snap_rows_diff.restype = ctypes.c_int64
            lib.snap_rows_diff.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
            ]
            lib.snap_group_rows.restype = ctypes.c_int64
            lib.snap_group_rows.argtypes = [
                ctypes.c_void_p,
                ctypes.c_void_p,
                ctypes.c_int64,
                ctypes.c_void_p,
            ]
            _lib = lib
        except Exception:
            logger.warning("native snapshot library unavailable; using numpy fallback",
                           exc_info=True)
            _lib_failed = True
    return _lib


def native_available() -> bool:
    return _build_and_load() is not None


class SnapshotMaintainer:
    """Incrementally-maintained availability tensor with int32 scaling.

    The per-request marshal path uses the stateless
    :func:`scale_rows_int32` below; this class adds the steady-state mode
    (load once, apply reservation deltas as pods bind/die, scale per
    request) for event-driven snapshot maintenance.
    """

    def __init__(self, avail_rows: np.ndarray):
        avail_rows = np.ascontiguousarray(avail_rows, dtype=np.int64)
        self._n = avail_rows.shape[0]
        self._lib = _build_and_load()
        self._handle = None
        if self._lib is not None:
            handle = self._lib.snap_create(self._n)
            if handle and self._lib.snap_load(
                ctypes.c_void_p(handle), avail_rows.ctypes.data_as(ctypes.c_void_p), self._n
            ):
                self._handle = ctypes.c_void_p(handle)
            elif handle:
                self._lib.snap_destroy(ctypes.c_void_p(handle))
        if self._handle is None:
            self._np = avail_rows.copy()

    def __del__(self):
        if getattr(self, "_handle", None) is not None and self._lib is not None:
            self._lib.snap_destroy(self._handle)
            self._handle = None

    @property
    def backend(self) -> str:
        return "native" if self._handle is not None else "numpy"

    @property
    def n_nodes(self) -> int:
        return self._n

    def apply_deltas(self, node_idx: np.ndarray, deltas: np.ndarray) -> None:
        """avail[idx] -= delta (use negative deltas to release)."""
        node_idx = np.ascontiguousarray(node_idx, dtype=np.int32)
        deltas = np.ascontiguousarray(deltas, dtype=np.int64)
        if self._handle is not None:
            self._lib.snap_apply_deltas(
                self._handle,
                node_idx.ctypes.data_as(ctypes.c_void_p),
                deltas.ctypes.data_as(ctypes.c_void_p),
                len(node_idx),
            )
        else:
            valid = (node_idx >= 0) & (node_idx < self._n)
            np.subtract.at(self._np, node_idx[valid], deltas[valid])

    def read(self) -> np.ndarray:
        if self._handle is not None:
            out = np.empty((self._n, 3), dtype=np.int64)
            self._lib.snap_read(self._handle, out.ctypes.data_as(ctypes.c_void_p))
            return out
        return self._np.copy()

    def scale_int32(
        self, demand_rows: np.ndarray, node_bucket: int
    ) -> Tuple[bool, np.ndarray, np.ndarray, np.ndarray]:
        """(ok, scaled_avail[node_bucket,3] int32, scaled_demands, scale[3])."""
        demand_rows = np.ascontiguousarray(demand_rows, dtype=np.int64)
        n_demands = demand_rows.shape[0]
        if self._handle is not None:
            out_avail = np.zeros((node_bucket, 3), dtype=np.int32)
            out_demands = np.zeros((max(n_demands, 1), 3), dtype=np.int32)
            out_scale = np.ones(3, dtype=np.int64)
            ok = self._lib.snap_scale_int32(
                self._handle,
                demand_rows.ctypes.data_as(ctypes.c_void_p),
                n_demands,
                node_bucket,
                out_avail.ctypes.data_as(ctypes.c_void_p),
                out_demands.ctypes.data_as(ctypes.c_void_p),
                out_scale.ctypes.data_as(ctypes.c_void_p),
            )
            return bool(ok), out_avail, out_demands[:n_demands], out_scale
        return _numpy_scale_int32(self._np, demand_rows, node_bucket)


def rows_equal(a: np.ndarray, b: np.ndarray) -> bool:
    """Exact equality of two [n, 3] int64 row blocks — the delta-solve
    engine's warm-basis check.  Native memcmp when the library carries
    snap_rows_diff, numpy otherwise; both are exact."""
    a = np.ascontiguousarray(a, dtype=np.int64)
    b = np.ascontiguousarray(b, dtype=np.int64)
    if a.shape != b.shape:
        return False
    n = a.shape[0]
    if n == 0:
        return True
    lib = _build_and_load()
    if lib is not None:
        diff = lib.snap_rows_diff(
            a.ctypes.data_as(ctypes.c_void_p),
            b.ctypes.data_as(ctypes.c_void_p),
            n,
        )
        return diff < 0
    return bool(np.array_equal(a, b))


def group_rows(rows: np.ndarray, flags: Optional[np.ndarray] = None
               ) -> Tuple[int, np.ndarray]:
    """Equivalence-class grouping of [n, 3] int64 rows (plus an optional
    per-row uint8 flag, e.g. schedulability): returns (class count,
    class id per row in first-occurrence order).  The capacity
    observatory's per-class headroom/frag lanes use it to collapse a
    100k-node scan to a few dozen class probes.  Native one-pass hash
    when the library carries snap_group_rows, numpy otherwise; the class
    id assignment is identical (first-occurrence order) either way."""
    rows = np.ascontiguousarray(rows, dtype=np.int64)
    n = rows.shape[0]
    out = np.zeros(n, dtype=np.int32)
    if n == 0:
        return 0, out
    if flags is not None:
        flags = np.ascontiguousarray(flags, dtype=np.uint8)
    lib = _build_and_load()
    if lib is not None:
        n_classes = lib.snap_group_rows(
            rows.ctypes.data_as(ctypes.c_void_p),
            flags.ctypes.data_as(ctypes.c_void_p) if flags is not None else None,
            n,
            out.ctypes.data_as(ctypes.c_void_p),
        )
        return int(n_classes), out
    seen: dict = {}
    for i in range(n):
        key = (int(rows[i, 0]), int(rows[i, 1]), int(rows[i, 2]),
               int(flags[i]) if flags is not None else 0)
        cid = seen.get(key)
        if cid is None:
            cid = len(seen)
            seen[key] = cid
        out[i] = cid
    return len(seen), out


def scale_rows_int32(avail_rows: np.ndarray, demand_rows: np.ndarray, node_bucket: int):
    """Stateless per-request scaling (no handle allocation): the marshal
    path's entry point.  Native-backed when available."""
    avail_rows = np.ascontiguousarray(avail_rows, dtype=np.int64)
    demand_rows = np.ascontiguousarray(demand_rows, dtype=np.int64)
    lib = _build_and_load()
    if lib is None:
        return _numpy_scale_int32(avail_rows, demand_rows, node_bucket)
    n = avail_rows.shape[0]
    n_demands = demand_rows.shape[0]
    out_avail = np.zeros((node_bucket, 3), dtype=np.int32)
    out_demands = np.zeros((max(n_demands, 1), 3), dtype=np.int32)
    out_scale = np.ones(3, dtype=np.int64)
    ok = lib.snap_scale_rows(
        avail_rows.ctypes.data_as(ctypes.c_void_p),
        n,
        demand_rows.ctypes.data_as(ctypes.c_void_p),
        n_demands,
        node_bucket,
        out_avail.ctypes.data_as(ctypes.c_void_p),
        out_demands.ctypes.data_as(ctypes.c_void_p),
        out_scale.ctypes.data_as(ctypes.c_void_p),
    )
    return bool(ok), out_avail, out_demands[:n_demands], out_scale


def _numpy_scale_int32(avail: np.ndarray, demand_rows: np.ndarray, node_bucket: int):
    INT32_SAFE = 2**31 - 1
    n = avail.shape[0]
    out_avail = np.zeros((max(node_bucket, 0), 3), dtype=np.int32)
    out_demands = np.zeros((demand_rows.shape[0], 3), dtype=np.int32)
    scale = np.ones(3, dtype=np.int64)
    if node_bucket < n:  # same contract as snapshot.cpp:101
        return False, out_avail, out_demands, scale
    for d in range(3):
        values = np.concatenate([avail[:, d], demand_rows[:, d]])
        g = int(np.gcd.reduce(np.abs(values))) if len(values) else 1
        g = max(g, 1)
        scale[d] = g
        sa = avail[:, d] // g
        sd = demand_rows[:, d] // g
        if (np.abs(sa) > INT32_SAFE).any() or (len(sd) and (np.abs(sd) > INT32_SAFE).any()):
            return False, out_avail, out_demands, scale
        out_avail[:n, d] = sa
        out_demands[:, d] = sd
    return True, out_avail, out_demands, scale
