"""ctypes binding for the native FIFO queue solver (native/fifo_solver.cpp).

The host-CPU lane of the batch solver: bit-exact decisions vs
ops/batch_solver.solve_queue (tightly-pack / distribute-evenly), at
native speed for deployments without an accelerator.  Build-on-first-use
with graceful degradation, same pattern as the snapshot maintainer.
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(_REPO_ROOT, "native", "fifo_solver.cpp")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_failed = False

_P = ctypes.c_void_p


def _build_and_load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            from . import build_native_lib

            lib = build_native_lib(
                _SRC,
                "fifosolver",
                [
                    "-O3", "-march=native", "-funroll-loops",
                    # IEEE semantics preserved; only errno/trap
                    # bookkeeping dropped so divpd vectorizes cleanly
                    "-fno-math-errno", "-fno-trapping-math",
                    # the delta-solve session's sharded cold pass runs a
                    # small std::thread pool
                    "-pthread",
                ],
            )
            lib.fifo_solve_queue.restype = ctypes.c_int
            lib.fifo_solve_queue.argtypes = [
                ctypes.c_int64, ctypes.c_int64, _P, _P, _P, _P, _P, _P, _P,
                ctypes.c_int, _P, _P,
            ]
            lib.fifo_solve_app.restype = ctypes.c_int
            lib.fifo_solve_app.argtypes = [
                ctypes.c_int64, _P, _P, _P, _P, _P, ctypes.c_int32,
                _P, _P, _P, _P,
            ]
            lib.fifo_solve_queue_minfrag.restype = ctypes.c_int
            lib.fifo_solve_queue_minfrag.argtypes = [
                ctypes.c_int64, ctypes.c_int64, _P, _P, _P, _P, _P, _P, _P,
                _P, _P,
            ]
            lib.seq_sum_f64.restype = ctypes.c_double
            lib.seq_sum_f64.argtypes = [_P, ctypes.c_int64]
            lib.fifo_solve_queue_single_az.restype = ctypes.c_int
            lib.fifo_solve_queue_single_az.argtypes = [
                ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, _P, _P, _P,
                _P, _P, _P, _P, _P, _P, _P, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, _P, _P, _P,
            ]
            # delta-solve session API
            lib.fifo_sess_create.restype = _P
            lib.fifo_sess_create.argtypes = []
            lib.fifo_sess_destroy.restype = None
            lib.fifo_sess_destroy.argtypes = [_P]
            lib.fifo_sess_load.restype = ctypes.c_int
            lib.fifo_sess_load.argtypes = [
                _P, ctypes.c_int64, _P, _P, _P, ctypes.c_int,
                ctypes.c_int64, ctypes.c_int, ctypes.c_int64,
            ]
            lib.fifo_sess_solve.restype = ctypes.c_int64
            lib.fifo_sess_solve.argtypes = [
                _P, ctypes.c_int64, _P, _P, _P, _P,
            ]
            lib.fifo_sess_mem_bytes.restype = ctypes.c_int64
            lib.fifo_sess_mem_bytes.argtypes = [_P]
            # equivalence-class compressed lanes
            lib.fifo_solve_queue_classes.restype = ctypes.c_int
            lib.fifo_solve_queue_classes.argtypes = [
                ctypes.c_int64, ctypes.c_int64, _P, _P, _P, _P,
                ctypes.c_int, _P, _P, _P,
            ]
            lib.fifo_sess_set_classes.restype = None
            lib.fifo_sess_set_classes.argtypes = [_P, ctypes.c_int]
            lib.fifo_sess_class_stats.restype = None
            lib.fifo_sess_class_stats.argtypes = [_P, _P]
            # decision-provenance explainer
            lib.fifo_explain_queue.restype = ctypes.c_int
            lib.fifo_explain_queue.argtypes = [
                ctypes.c_int64, ctypes.c_int64, _P, _P, _P, _P,
                ctypes.c_int, ctypes.c_int64, _P, _P,
            ]
            # capacity-observatory probes
            lib.fifo_probe_headroom.restype = ctypes.c_int
            lib.fifo_probe_headroom.argtypes = [
                ctypes.c_int64, _P, _P, _P, ctypes.c_int64, _P,
                ctypes.c_int32, _P, _P, _P,
            ]
            lib.fifo_frag_report.restype = ctypes.c_int
            lib.fifo_frag_report.argtypes = [ctypes.c_int64, _P, _P, _P]
            _lib = lib
        except Exception:
            logger.warning(
                "native fifo solver unavailable; device/XLA lanes only",
                exc_info=True,
            )
            _lib_failed = True
    return _lib


def native_fifo_available() -> bool:
    return _build_and_load() is not None


def _c(arr: np.ndarray) -> ctypes.c_void_p:
    return arr.ctypes.data_as(_P)


def solve_queue_native(
    avail: np.ndarray,        # [N, 3] int32 (not mutated)
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    drivers: np.ndarray,      # [A, 3] int32
    executors: np.ndarray,    # [A, 3] int32
    counts: np.ndarray,       # [A] int32
    app_valid: np.ndarray,    # [A] bool
    evenly: bool = False,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(feasible[A] bool, driver_idx[A] int32, avail_after[N,3] int32) —
    decision-identical to solve_queue(..., with_placements=False)."""
    lib = _build_and_load()
    if lib is None:
        raise RuntimeError("native fifo solver not available")
    avail_io = np.ascontiguousarray(avail, dtype=np.int32).copy()
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    drv = np.ascontiguousarray(drivers, dtype=np.int32)
    exe = np.ascontiguousarray(executors, dtype=np.int32)
    cnt = np.ascontiguousarray(counts, dtype=np.int32)
    val = np.ascontiguousarray(app_valid, dtype=np.uint8)
    nb, na = avail_io.shape[0], drv.shape[0]
    feas = np.zeros(na, dtype=np.uint8)
    didx = np.zeros(na, dtype=np.int32)
    lib.fifo_solve_queue(
        nb, na, _c(avail_io), _c(rank), _c(eok), _c(drv), _c(exe), _c(cnt),
        _c(val), int(evenly), _c(feas), _c(didx),
    )
    return feas.astype(bool), didx, avail_io


def solve_queue_min_frag_native(
    avail: np.ndarray,        # [N, 3] int32 (not mutated)
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    drivers: np.ndarray,      # [A, 3] int32
    executors: np.ndarray,    # [A, 3] int32
    counts: np.ndarray,       # [A] int32
    app_valid: np.ndarray,    # [A] bool
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(feasible[A] bool, driver_idx[A] int32, avail_after[N,3] int32) —
    decision-identical to batch_solver.solve_queue_min_frag(...,
    with_placements=False) on MF-sentinel-safe inputs (the same guard the
    device lanes hold, batch_solver.mf_sentinel_safe)."""
    lib = _build_and_load()
    if lib is None:
        raise RuntimeError("native fifo solver not available")
    avail_io = np.ascontiguousarray(avail, dtype=np.int32).copy()
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    drv = np.ascontiguousarray(drivers, dtype=np.int32)
    exe = np.ascontiguousarray(executors, dtype=np.int32)
    cnt = np.ascontiguousarray(counts, dtype=np.int32)
    val = np.ascontiguousarray(app_valid, dtype=np.uint8)
    nb, na = avail_io.shape[0], drv.shape[0]
    feas = np.zeros(na, dtype=np.uint8)
    didx = np.zeros(na, dtype=np.int32)
    lib.fifo_solve_queue_minfrag(
        nb, na, _c(avail_io), _c(rank), _c(eok), _c(drv), _c(exe), _c(cnt),
        _c(val), _c(feas), _c(didx),
    )
    return feas.astype(bool), didx, avail_io


def solve_queue_single_az_native(
    avail: np.ndarray,        # [N, 3] int32 (not mutated)
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    zone_id: np.ndarray,      # [N] int32, -1 = in no candidate zone
    drivers: np.ndarray,      # [A, 3] int32
    executors: np.ndarray,    # [A, 3] int32
    counts: np.ndarray,       # [A] int32
    app_valid: np.ndarray,    # [A] bool
    sched_base: np.ndarray,   # [N, 3] int64 base-unit schedulable rows
    scale: np.ndarray,        # [3] int64 tensorize scale vector
    n_zones: int,
    az_aware: bool = False,
    minfrag: bool = False,
    strict: bool = True,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """(feasible[A] bool, zone_idx[A] int32, driver_idx[A] int32,
    avail_after[N,3] int32) — the single-AZ FIFO pass with the zone
    chosen by EXACT float64 average packing efficiency: decision-
    identical to TpuSingleAzFifoSolver's host lane (pack_one +
    _choose_best_result), with no fixed-point uncertainty valve.
    zone_idx: chosen zone, n_zones = cross-zone fallback, -1 = none."""
    lib = _build_and_load()
    if lib is None:
        raise RuntimeError("native fifo solver not available")
    avail_io = np.ascontiguousarray(avail, dtype=np.int32).copy()
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    zid = np.ascontiguousarray(zone_id, dtype=np.int32)
    drv = np.ascontiguousarray(drivers, dtype=np.int32)
    exe = np.ascontiguousarray(executors, dtype=np.int32)
    cnt = np.ascontiguousarray(counts, dtype=np.int32)
    val = np.ascontiguousarray(app_valid, dtype=np.uint8)
    nb, na = avail_io.shape[0], drv.shape[0]
    sched = np.zeros((nb, 3), dtype=np.int64)
    sb = np.asarray(sched_base, dtype=np.int64)
    sched[: sb.shape[0]] = sb[:nb]
    scl = np.ascontiguousarray(scale, dtype=np.int64)
    feas = np.zeros(na, dtype=np.uint8)
    zone = np.zeros(na, dtype=np.int32)
    didx = np.zeros(na, dtype=np.int32)
    lib.fifo_solve_queue_single_az(
        nb, na, int(n_zones), _c(avail_io), _c(rank), _c(eok), _c(zid),
        _c(drv), _c(exe), _c(cnt), _c(val), _c(sched), _c(scl),
        int(az_aware), int(minfrag), int(strict), _c(feas), _c(zone),
        _c(didx),
    )
    return feas.astype(bool), zone, didx, avail_io


def seq_sum_f64_native(values: np.ndarray) -> Optional[float]:
    """CPython-sum-compatible float64 reduction — bit-identical to
    builtin sum() of the list (Neumaier-compensated in Python 3.12),
    or None when the lib is unavailable.  The drop-in for any host loop
    of the form ``sum(list)`` a lane wants to move to C without changing
    a bit; the same symbol as :func:`neumaier_sum_f64_native`."""
    return neumaier_sum_f64_native(values)


def neumaier_sum_f64_native(values: np.ndarray) -> Optional[float]:
    """Neumaier-compensated float64 sum (the seq_sum_f64 symbol,
    interpreter-independent): the packing-efficiency gauge uses this
    because its cross-lane bit-equality contract needs an order-robust
    sum — the host lane accumulates the same per-node maxes in metadata
    order, the tensor lanes in node-priority order, and compensation
    recovers the same rounded value where plain sequential addition
    diverges by an ulp.  None when unavailable."""
    lib = _build_and_load()
    if lib is None:
        return None
    v = np.ascontiguousarray(values, dtype=np.float64)
    return float(lib.seq_sum_f64(_c(v), v.shape[0]))


# queue policy codes shared with native/fifo_solver.cpp::FifoSession
POLICY_TIGHTLY = 0
POLICY_EVENLY = 1
POLICY_MINFRAG = 2


def solve_packed_cold(
    policy_code: int,
    avail: np.ndarray,        # [N, 3] int32 basis (not mutated)
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    apps_packed: np.ndarray,  # [A, 8] int32: d0..2 e0..2 count valid
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stateless cold solve of a session-format packed queue under the
    given policy code — ONE dispatch shared by the delta-solve engine's
    warm≠cold parity guard and the flight-recorder bundle replay, so the
    policy-code → solver mapping can never diverge between the two
    mechanisms whose job is proving solver equivalence."""
    drv = apps_packed[:, 0:3]
    exe = apps_packed[:, 3:6]
    cnt = apps_packed[:, 6]
    val = apps_packed[:, 7].astype(bool)
    if policy_code == POLICY_MINFRAG:
        return solve_queue_min_frag_native(
            avail, driver_rank, exec_ok, drv, exe, cnt, val
        )
    return solve_queue_native(
        avail, driver_rank, exec_ok, drv, exe, cnt, val,
        evenly=(policy_code == POLICY_EVENLY),
    )


def native_classes_available() -> bool:
    lib = _build_and_load()
    return lib is not None


def solve_packed_classes(
    policy_code: int,
    avail: np.ndarray,        # [N, 3] int32 basis (not mutated)
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    apps_packed: np.ndarray,  # [A, 8] int32: d0..2 e0..2 count valid
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, dict]:
    """Stateless class-compressed solve of a session-format packed queue
    (fifo_solver.cpp ``fifo_solve_queue_classes``): byte-identical
    verdicts and post-queue availability to :func:`solve_packed_cold` at
    the same inputs, with per-app cost O(classes + diverged overlay)
    instead of O(nodes).  The fourth element is the compression evidence:
    ``{"classes_initial", "rebuilds", "overlay_peak", "classes_last"}``."""
    lib = _build_and_load()
    if lib is None:
        raise RuntimeError("native class-compressed solver not available")
    avail_io = np.ascontiguousarray(avail, dtype=np.int32).copy()
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    apps = np.ascontiguousarray(apps_packed, dtype=np.int32)
    nb, na = avail_io.shape[0], apps.shape[0]
    feas = np.zeros(max(na, 1), dtype=np.uint8)
    didx = np.zeros(max(na, 1), dtype=np.int32)
    stats = np.zeros(4, dtype=np.int64)
    lib.fifo_solve_queue_classes(
        nb, na, _c(avail_io), _c(rank), _c(eok), _c(apps),
        int(policy_code), _c(feas), _c(didx), _c(stats),
    )
    evidence = {
        "classes_initial": int(stats[0]),
        "rebuilds": int(stats[1]),
        "overlay_peak": int(stats[2]),
        "classes_last": int(stats[3]),
    }
    return feas[:na].astype(bool), didx[:na], avail_io, evidence


def native_session_available() -> bool:
    lib = _build_and_load()
    return lib is not None


class NativeFifoSession:
    """Persistent native solver session: the scaled availability basis,
    the rank-sorted driver candidates, and the prefix-feasibility
    checkpoints stay resident in the C++ extension between Filter
    requests (fifo_solver.cpp ``fifo_sess_*``).

    ``solve`` self-verifies the queue prefix byte-for-byte inside the
    extension, so callers may pass whatever they believe the queue is —
    a wrong belief costs a deeper re-solve, never a wrong decision.
    Not thread-safe; the owning engine serializes access."""

    def __init__(self, threads: int = 0, min_pool_nodes: int = 8192):
        lib = _build_and_load()
        if lib is None:
            raise RuntimeError("native fifo session not available")
        self._lib = lib
        self._handle = ctypes.c_void_p(lib.fifo_sess_create())
        if not self._handle:
            raise RuntimeError("fifo_sess_create failed")
        self._threads = int(threads)
        self._min_pool_nodes = int(min_pool_nodes)
        self.nb = 0

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.fifo_sess_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def load(
        self,
        avail: np.ndarray,        # [Nb, 3] int32 scaled basis
        driver_rank: np.ndarray,  # [Nb] int32
        exec_ok: np.ndarray,      # [Nb] bool
        policy: int,
        stride: int = 64,
    ) -> None:
        av = np.ascontiguousarray(avail, dtype=np.int32)
        rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
        eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
        nb = av.shape[0]
        ok = self._lib.fifo_sess_load(
            self._handle, nb, _c(av), _c(rank), _c(eok), int(policy),
            int(stride), self._threads, self._min_pool_nodes,
        )
        if not ok:
            raise RuntimeError("fifo_sess_load failed")
        self.nb = int(nb)

    def solve(
        self, apps_packed: np.ndarray  # [A, 8] int32: d0..2 e0..2 count valid
    ) -> Tuple[int, np.ndarray, np.ndarray, np.ndarray]:
        """(resume_index, feasible[A] bool, driver_idx[A] int32,
        avail_after[Nb, 3] int32)."""
        apps = np.ascontiguousarray(apps_packed, dtype=np.int32)
        na = apps.shape[0]
        feas = np.zeros(max(na, 1), dtype=np.uint8)
        didx = np.zeros(max(na, 1), dtype=np.int32)
        avail_after = np.zeros((self.nb, 3), dtype=np.int32)
        resume = self._lib.fifo_sess_solve(
            self._handle, na, _c(apps), _c(feas), _c(didx), _c(avail_after)
        )
        if resume < 0:
            raise RuntimeError("fifo_sess_solve on an unloaded session")
        return int(resume), feas[:na].astype(bool), didx[:na], avail_after

    def mem_bytes(self) -> int:
        if not getattr(self, "_handle", None):
            return 0
        return int(self._lib.fifo_sess_mem_bytes(self._handle))

    def set_classes(self, enable: bool) -> bool:
        """Toggle equivalence-class compressed stepping (ROADMAP 2).
        Verdicts and planes stay byte-identical either way; returns
        True (the mode is always available once the library loads)."""
        self._lib.fifo_sess_set_classes(self._handle, int(bool(enable)))
        return True

    def class_stats(self) -> dict:
        """Compression evidence of the session's class partition:
        ``{"classes_last", "rebuilds", "overlay_peak", "overlay_now"}``
        (zeros until class mode has stepped)."""
        out = np.zeros(4, dtype=np.int64)
        if getattr(self, "_handle", None):
            self._lib.fifo_sess_class_stats(self._handle, _c(out))
        return {
            "classes_last": int(out[0]),
            "rebuilds": int(out[1]),
            "overlay_peak": int(out[2]),
            "overlay_now": int(out[3]),
        }


def native_explain_available() -> bool:
    lib = _build_and_load()
    return lib is not None


class ExplainResult:
    """Decoded ``fifo_explain_queue`` output (provenance/explain.py).

    ``flip`` is the queue position whose step turned the target
    infeasible (-1 = feasible at its own position, -2 = infeasible even
    against the empty basis); ``blockers`` is the per-position blocker
    mask; the rest decompose the target-position probe (see the C++
    entry-point comment for exact semantics)."""

    __slots__ = (
        "flip", "feasible", "cap_total", "dim_totals", "max_cap",
        "max_node", "driver_fit", "tightest_dim", "shortfall_execs",
        "blockers",
    )

    def __init__(self, info: np.ndarray, blockers: np.ndarray):
        self.flip = int(info[0])
        self.feasible = bool(info[1])
        self.cap_total = int(info[2])
        self.dim_totals = (int(info[3]), int(info[4]), int(info[5]))
        self.max_cap = int(info[6])
        self.max_node = int(info[7])
        self.driver_fit = int(info[8])
        self.tightest_dim = int(info[9])
        self.shortfall_execs = int(info[10])
        self.blockers = blockers

    @property
    def blocker_count(self) -> int:
        return int(self.blockers.sum())


def explain_queue_native(
    avail: np.ndarray,        # [N, 3] int32 basis (queue position 0)
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    apps_packed: np.ndarray,  # [A, 8] int32: d0..2 e0..2 count valid
    policy: int,
    target: int,
) -> Optional[ExplainResult]:
    """Shortfall vector + blocker set for the app at queue position
    ``target`` (see fifo_solver.cpp fifo_explain_queue), or None when
    the library is unavailable or the inputs are degenerate.  Diagnostic only — never a decision
    input."""
    lib = _build_and_load()
    if lib is None:
        return None
    av = np.ascontiguousarray(avail, dtype=np.int32)
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    apps = np.ascontiguousarray(apps_packed, dtype=np.int32)
    nb, na = av.shape[0], apps.shape[0]
    if nb <= 0 or na <= 0 or not (0 <= target < na):
        return None
    blockers = np.zeros(na, dtype=np.uint8)
    info = np.zeros(12, dtype=np.int64)
    ok = lib.fifo_explain_queue(
        nb, na, _c(av), _c(rank), _c(eok), _c(apps),
        int(policy), int(target), _c(blockers), _c(info),
    )
    if not ok:
        return None
    return ExplainResult(info, blockers.astype(bool))


def native_probe_available() -> bool:
    lib = _build_and_load()
    return lib is not None


def probe_headroom_native(
    avail: np.ndarray,        # [N, 3] int32 scaled availability basis
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    shapes: np.ndarray,       # [S, 6] int32: d0..2 e0..2 (scaled units)
    k_max: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """(headroom[S] int64, usable[S,3] int64, probes[S] int64) — per
    shape, the largest gang size the solver would admit at queue
    position 0 against this basis (fifo_probe_headroom), or None when
    the library is unavailable.  Read-only diagnostic —
    never a decision input."""
    lib = _build_and_load()
    if lib is None:
        return None
    av = np.ascontiguousarray(avail, dtype=np.int32)
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    shp = np.ascontiguousarray(shapes, dtype=np.int32)
    nb, ns = av.shape[0], shp.shape[0]
    if nb <= 0 or ns <= 0 or k_max <= 0:
        return None
    headroom = np.zeros(ns, dtype=np.int64)
    usable = np.zeros((ns, 3), dtype=np.int64)
    probes = np.zeros(ns, dtype=np.int64)
    ok = lib.fifo_probe_headroom(
        nb, _c(av), _c(rank), _c(eok), ns, _c(shp),
        ctypes.c_int32(int(k_max)), _c(headroom), _c(usable), _c(probes),
    )
    if not ok:
        return None
    return headroom, usable, probes


def frag_report_native(
    avail: np.ndarray,   # [N, 3] int32 scaled availability
    exec_ok: np.ndarray, # [N] bool
) -> Optional[np.ndarray]:
    """[3, 4] int64 per-dimension (total free, largest chunk, free
    nodes, overdrawn nodes) over the eligible rows, or None when the
    library is unavailable."""
    lib = _build_and_load()
    if lib is None:
        return None
    av = np.ascontiguousarray(avail, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    out = np.zeros(12, dtype=np.int64)
    if not lib.fifo_frag_report(av.shape[0], _c(av), _c(eok), _c(out)):
        return None
    return out.reshape(3, 4)


def solve_app_native(
    avail: np.ndarray,        # [N, 3] int32
    driver_rank: np.ndarray,  # [N] int32
    exec_ok: np.ndarray,      # [N] bool
    driver: np.ndarray,       # [3] int32
    executor: np.ndarray,     # [3] int32
    k: int,
) -> Tuple[bool, int, np.ndarray, np.ndarray]:
    """(feasible, driver_idx, exec_counts[N], exec_capacity[N]) —
    decision-identical to batch_solver.solve_app (tightly-pack fill
    counts + post-driver-placement capacities)."""
    lib = _build_and_load()
    if lib is None:
        raise RuntimeError("native fifo solver not available")
    av = np.ascontiguousarray(avail, dtype=np.int32)
    rank = np.ascontiguousarray(driver_rank, dtype=np.int32)
    eok = np.ascontiguousarray(exec_ok, dtype=np.uint8)
    drv = np.ascontiguousarray(driver, dtype=np.int32)
    exe = np.ascontiguousarray(executor, dtype=np.int32)
    nb = av.shape[0]
    feas = np.zeros(1, dtype=np.uint8)
    didx = np.zeros(1, dtype=np.int32)
    counts = np.zeros(nb, dtype=np.int32)
    caps = np.zeros(nb, dtype=np.int32)
    lib.fifo_solve_app(
        nb, _c(av), _c(rank), _c(eok), _c(drv), _c(exe),
        ctypes.c_int32(int(k)), _c(feas), _c(didx), _c(counts), _c(caps),
    )
    return bool(feas[0]), int(didx[0]), counts, caps
