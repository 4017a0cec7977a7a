"""Incremental delta-solve engine: persistent native solver sessions +
prefix-feasibility reuse for the earlier-drivers-fit loop.

The paper's core guarantee — a driver schedules only if the whole gang
fits and every earlier driver fits first — was re-proved from scratch on
every Filter request: a full snapshot marshal, the AZ-aware sorts, GCD
scaling, and an O(queue × nodes) native queue solve (~17-21 ms at
10k × 1k on one CPU core).  Between consecutive decisions almost
nothing changes (the Firmament observation), so the warm path here costs
O(what changed):

- **Persistent native session** (``native/fifo_solver.cpp`` FifoSession
  via :class:`..native.fifo.NativeFifoSession`): the scaled availability
  basis, rank-sorted driver candidates, and the last-solved queue stay
  resident in the C++ extension, keyed by the snapshot *structure
  revision* plus the request's affinity/candidate identity (the same
  exact key the fast-path prep cache uses — ``fast_path.build_prep_keyed``).
- **Prefix-feasibility cache**: the session checkpoints the post-prefix
  availability carry every ``stride`` queue positions; the next request
  resumes from the nearest checkpoint at or below the first changed
  queue index.  The prefix match is verified byte-for-byte inside the
  extension — Python-side bookkeeping is an optimization, never a
  correctness input.
- **Sharded cold-solve fallback**: when the session is cold or
  invalidated (failover, journal replay, content change, inexact
  snapshot), the dim-at-a-time capacity sweeps can shard over node
  ranges on a small native thread pool (``DELTASOLVE_THREADS``); on
  small hosts the pool stays off and the cold solve is the plain serial
  native pass.

Invalidation rules (docs/design.md has the operator-facing version):

1. *Structure* — the session key embeds ``snap.structure_key`` and the
   candidate-list tuple; any node add/remove/relabel/cordon or a
   different candidate set simply misses the session map.
2. *Content* — a warm hit requires the idx-selected availability AND
   schedulable rows to equal the session basis exactly.  The O(1) fast
   path is the change-feed sequence (``snap.content_key``): unchanged
   sequence ⟹ unchanged world.  A changed sequence falls back to an
   exact memcmp (``native.rows_equal``) — churn that cancelled out (a
   probe reservation created then released) still warms.
3. *Scale* — warm reuse requires every demand row to divide the cached
   scale vector exactly and fit int32 after division; decisions are
   scale-invariant (capacities are exact integer quotients), so solving
   in the cached units is bit-identical to a fresh GCD rescale.
4. *Failover / journal replay* — replayed reservation intents flow
   through the store observers into the tensor mirror, bumping the feed
   and changing content, so rule 2 invalidates; a fresh process starts
   with an empty session map by construction.

Every miss reason is counted (``…tpu.deltasolve.warm.miss.count``) and
warm resumes record their depth (``…tpu.deltasolve.resume.depth``).
"""

from __future__ import annotations

import logging
import os
import threading
from collections import OrderedDict, deque
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..analysis import racecheck
from ..analysis.guarded import guarded_by
from ..metrics import names as mnames
from ..tracing import spans as tracing
from ..tracing.profiling import default_profiler
from .fifo_solver import FifoOutcome, gate_overhead_rows
from .tensorize import INT32_SAFE, ScaledProblem

logger = logging.getLogger(__name__)

# checkpoint stride: 1k-app queues keep ~16 live checkpoints (the C++
# side doubles the stride past 24, so memory stays bounded either way)
_DEFAULT_STRIDE = 64
# sharded cold pass: below this node count the per-pass dispatch
# round-trip exceeds the sweep itself (see fifo_solver.cpp SweepPool)
_POOL_MIN_NODES = 8192


def _default_threads() -> int:
    env = os.environ.get("DELTASOLVE_THREADS")
    if env is not None:
        try:
            return max(0, int(env))
        except ValueError:
            return 0
    return min(4, os.cpu_count() or 1)


@dataclass
class _Session:
    """One resident (cluster basis, policy) problem."""

    native: object            # NativeFifoSession
    policy_code: int
    avail64: np.ndarray       # [M, 3] int64 idx-selected availability basis
    sched64: np.ndarray       # [M, 3] int64 idx-selected schedulable basis
    cluster: object           # ClusterTensor built against the basis
    zones: Dict[str, str]
    scale: np.ndarray         # [3] int64
    scaled_avail: np.ndarray  # [Nb, 3] int32 (pre-queue, padded)
    driver_rank: np.ndarray   # [Nb] int32
    exec_ok: np.ndarray       # [Nb] bool
    nb: int
    content_key: tuple        # snapshot content sequence last verified
    # class-digest warm tier (state/classindex.py): the XOR content
    # digest + class-structure revision of the snapshot this basis was
    # built from.  (-1, -1) = snapshot didn't carry a digest (tests
    # building bare TensorSnapshots); the tier then stands aside.
    class_digest: tuple = (-1, -1)
    class_rev: int = -1
    # class-compressed solve mode: on for big fleets only (min_nodes);
    # last_rebuilds tracks the native partition-rebuild counter so the
    # tpu.classes.rebuild.count metric gets deltas, not running totals
    use_classes: bool = False
    last_rebuilds: int = 0


@guarded_by("_lock", "_sessions", "_stats", "_resume_depths", "_parity_count")
class DeltaSolveEngine:
    """Serves the whole FIFO driver decision from resident native state
    when it can, falling back (``solve`` → None) to the per-request
    build + cold solve otherwise.  Decisions are bit-identical to the
    cold path — the per-app queue step is literally the same C++
    function (tests/test_deltasolve.py replays random delta streams
    against cold solves to prove it)."""

    MAX_SESSIONS = 4

    def __init__(self, metrics=None, threads: Optional[int] = None,
                 stride: int = _DEFAULT_STRIDE):
        self._metrics = metrics
        self._threads = _default_threads() if threads is None else threads
        self._stride = stride
        self._lock = threading.Lock()
        self._sessions: OrderedDict = OrderedDict()
        self._stats = {"warm_hits": 0, "cold_solves": 0, "misses": {}}
        self._resume_depths = deque(maxlen=1024)
        self._native_ok: Optional[bool] = None
        # decision provenance (provenance/tracker.py): wiring points the
        # sink at ProvenanceTracker.capture when provenance is enabled.
        # None (the default) keeps the warm path entirely free of
        # capture work.  All three are set before serving starts and
        # only read here — no lock needed.
        self.capture_sink = None
        # warm≠cold parity guard: every Nth warm hit re-runs the queue
        # through the stateless cold solver and fires the flight
        # recorder on divergence.  0 = off (a full cold solve per check).
        self.parity_interval = 0
        self.parity_hooks = None  # (on_ok, on_mismatch) callables
        self._parity_count = 0
        # equivalence-class aggregation (Install.classes): the O(1)
        # digest warm tier below and the native session's class-
        # compressed solve mode.  Set at wiring before serving starts,
        # only read here — no lock needed.
        self.classes_enabled = True
        self.classes_min_nodes = 20000

    # -- availability --------------------------------------------------------

    def _native_available(self) -> bool:
        if self._native_ok is None:
            try:
                from ..native.fifo import native_session_available

                self._native_ok = native_session_available()
            except Exception:
                self._native_ok = False
        return self._native_ok

    def _solver_supported(self, solver) -> bool:
        """The session lane serves the plain-FIFO solver's native host
        lane: on accelerator-backed deployments the pallas queue kernel
        keeps the carry VMEM-resident and this engine stands aside."""
        from .fifo_solver import _native_selected, _pallas_selected

        backend = getattr(solver, "backend", None)
        if backend is None or not hasattr(solver, "_tensorize_with_cache"):
            return False
        if _pallas_selected(backend):
            return False
        try:
            return _native_selected(backend)
        except RuntimeError:
            return False

    # -- bookkeeping ---------------------------------------------------------

    def _miss(self, reason: str) -> None:
        with self._lock:
            racecheck.note_access(self, "_stats")
            self._stats["misses"][reason] = (
                self._stats["misses"].get(reason, 0) + 1
            )
        if self._metrics is not None:
            self._metrics.counter(
                mnames.DELTASOLVE_WARM_MISSES, {"reason": reason}
            )

    def _record_warm(self, resume: int) -> None:
        with self._lock:
            racecheck.note_access(self, "_stats")
            self._stats["warm_hits"] += 1
            self._resume_depths.append(int(resume))
        if self._metrics is not None:
            self._metrics.counter(mnames.DELTASOLVE_WARM_HITS)
            self._metrics.histogram(
                mnames.DELTASOLVE_RESUME_DEPTH, float(resume)
            )

    def _record_cold(self) -> None:
        with self._lock:
            racecheck.note_access(self, "_stats")
            self._stats["cold_solves"] += 1

    def stats(self) -> dict:
        with self._lock:
            depths = sorted(self._resume_depths)
            hits = self._stats["warm_hits"]
            cold = self._stats["cold_solves"]
            digest_hits = self._stats.get("digest_hits", 0)
            misses = dict(self._stats["misses"])
            sessions = len(self._sessions)
            session_bytes = sum(
                s.native.mem_bytes() for s in self._sessions.values()
            )
        total = hits + cold + sum(misses.values())
        return {
            "warm_hits": hits,
            "cold_solves": cold,
            "digest_hits": digest_hits,
            "misses": misses,
            "warm_hit_rate": (hits / total) if total else 0.0,
            "resume_depth_p50": (
                float(depths[len(depths) // 2]) if depths else None
            ),
            "sessions": sessions,
            "session_bytes": session_bytes,
        }

    def latest_basis(self):
        """(node_names, avail64 [N,3] int64, exec_ok [N] bool,
        driver_rank [N] int64) of the most recently used session's
        cluster view, or None when no session is resident.  The policy
        engine's what-if victim validation rides this warm basis — the
        post-build availability the last solve actually ran against —
        instead of re-deriving one from the raw snapshot."""
        with self._lock:
            racecheck.note_access(self, "_sessions")
            if not self._sessions:
                return None
            sess = next(reversed(self._sessions.values()))
        c = sess.cluster
        return (
            list(c.node_names),
            np.asarray(c.avail, dtype=np.int64),
            np.asarray(c.exec_ok, dtype=bool),
            np.asarray(c.driver_rank, dtype=np.int64),
        )

    def invalidate(self) -> None:
        """Drop every session (tests / explicit failover hooks; organic
        invalidation flows through the content rules in the docstring).
        Native handles are NOT destroyed here: a Filter request may hold
        a dropped session mid-solve (solve() runs outside the engine
        lock), so handles retire via refcounting — NativeFifoSession.
        __del__ frees the C++ state once the last reference drops."""
        with self._lock:
            racecheck.note_access(self, "_sessions")
            self._sessions.clear()

    def _publish_gauges(self) -> None:
        if self._metrics is None:
            return
        with self._lock:
            n = len(self._sessions)
            b = sum(s.native.mem_bytes() for s in self._sessions.values())
        self._metrics.gauge(mnames.DELTASOLVE_SESSIONS, float(n))
        self._metrics.gauge(mnames.DELTASOLVE_SESSION_BYTES, float(b))

    # -- the solve -----------------------------------------------------------

    def solve(
        self,
        snap,
        driver_pod,
        candidate_names,
        node_sorter,
        earlier_apps: List,
        earlier_skip_allowed: List[bool],
        current_app,
        solver,
    ) -> Optional[Tuple[FifoOutcome, Dict[str, str]]]:
        """(FifoOutcome, node→zone map) or None when this lane cannot
        serve the request exactly (the caller then runs the per-request
        build + solve path)."""
        from .batch_solver import queue_policy_code

        policy_code = queue_policy_code(solver.assignment_policy)
        if policy_code is None or not self._solver_supported(solver):
            self._miss("unsupported")
            return None
        if not self._native_available():
            self._miss("no-native")
            return None
        if not snap.exact:
            self._miss("inexact")
            return None

        from .fast_path import build_prep_keyed

        # candidate_names passes through verbatim: on the HTTP path it is
        # the interned tuple (serde.intern_node_names), so the prep/session
        # key shares ONE string set across requests instead of pinning a
        # fresh 10k-string copy per cache entry (the r5 soak's RSS churn)
        prep, key = build_prep_keyed(
            snap,
            driver_pod,
            candidate_names,
            node_sorter.driver_label_priority,
            node_sorter.executor_label_priority,
        )
        if key is None:
            self._miss("affinity-shape")
            return None
        skey = (key, policy_code)

        apps = solver._tensorize_with_cache(list(earlier_apps), current_app)
        if not apps.exact:
            self._miss("apps-inexact")
            return None
        n_earlier = len(earlier_apps)

        with self._lock:
            racecheck.note_access(self, "_sessions")
            sess = self._sessions.get(skey)
            if sess is not None:
                self._sessions.move_to_end(skey)

        warm = False
        scaled = None
        if sess is not None:
            snap_digest = getattr(snap, "class_digest", (-1, -1))
            if sess.content_key == snap.content_key:
                warm = True
            elif (
                self.classes_enabled
                and sess.class_digest != (-1, -1)
                and snap_digest == sess.class_digest
            ):
                # O(1) class-digest tier (state/classindex.py): the XOR
                # node-content digest cancelled back to the session's —
                # same-class node churn (create/release, cordon/uncordon
                # round trips) warms without the O(N) row compare.  The
                # digest hashes a superset of what rows_equal checks, so
                # equality ⟹ equal rows up to 64-bit XOR collisions;
                # the warm≠cold parity guard audits the conclusion.
                warm = True
                sess.content_key = snap.content_key
                sess.class_rev = getattr(snap, "class_rev", -1)
                with self._lock:
                    racecheck.note_access(self, "_stats")
                    self._stats["digest_hits"] = (
                        self._stats.get("digest_hits", 0) + 1
                    )
            else:
                from ..native import rows_equal

                avail64 = snap.avail[prep.idx]
                sched64 = snap.schedulable[prep.idx]
                if rows_equal(avail64, sess.avail64) and rows_equal(
                    sched64, sess.sched64
                ):
                    # churn cancelled out (e.g. a reservation created
                    # then released): the basis is still exact
                    warm = True
                    sess.content_key = snap.content_key
                    sess.class_digest = snap_digest
                    sess.class_rev = getattr(snap, "class_rev", -1)
        if warm:
            scaled = self._scale_apps(apps, sess.scale, sess.nb)
            if scaled is None:
                # the cached units no longer represent these demands
                # exactly — rebuild with a fresh GCD
                warm = False

        if not warm:
            sess, scaled = self._cold_build(
                snap, driver_pod, candidate_names, node_sorter, prep, skey,
                policy_code, apps,
            )
            if sess is None:
                return None
            self._record_cold()

        driver_s, executor_s, count_s = scaled
        packed = np.empty((n_earlier, 8), dtype=np.int32)
        packed[:, 0:3] = driver_s[:n_earlier]
        packed[:, 3:6] = executor_s[:n_earlier]
        packed[:, 6] = count_s[:n_earlier]
        packed[:, 7] = 1

        solver.last_queue_lane = "native-session"
        with tracing.child_span(
            "fifo_gate",
            {"lane": "native-session", "earlierApps": n_earlier,
             "overheadRows": gate_overhead_rows()},
            cpu=True,
        ) as gate_span:
            with default_profiler.profile(
                "fifo_queue", lane="native-session", jit=False
            ):
                resume, feasible, didx, avail_after = sess.native.solve(
                    packed
                )
            gate_span.tag("resumeFrom", int(resume))
            gate_span.tag("warm", warm)
            if sess.use_classes and self._metrics is not None:
                try:
                    st = sess.native.class_stats()
                    delta = st["rebuilds"] - sess.last_rebuilds
                    if delta > 0:
                        sess.last_rebuilds = st["rebuilds"]
                        self._metrics.counter(
                            mnames.CLASSES_REBUILD_COUNT, inc=float(delta)
                        )
                except Exception:
                    pass
            if warm:
                self._record_warm(resume)
                if self.parity_interval:
                    # counted under the engine lock: solve() already runs
                    # concurrently in tests and will for real once the
                    # extender lock splits (ROADMAP-1) — an unguarded
                    # += here was the PR 9 vector-clock detector's first
                    # real finding
                    with self._lock:
                        racecheck.note_access(self, "_parity_count")
                        self._parity_count += 1
                        parity_due = (
                            self._parity_count % self.parity_interval == 0
                        )
                    if parity_due:
                        self._verify_parity(
                            sess, packed, feasible, didx, avail_after
                        )
            if self.capture_sink is not None:
                self._capture(
                    sess, snap, policy_code, packed, driver_s, executor_s,
                    count_s, n_earlier, feasible, didx, resume,
                    avail_after, earlier_skip_allowed,
                )
            if n_earlier:
                blocked = ~feasible & ~np.asarray(
                    earlier_skip_allowed, dtype=bool
                )
                if blocked.any():
                    gate_span.tag("earlierOk", False)
                    return (
                        FifoOutcome(supported=True, earlier_ok=False),
                        sess.zones,
                    )
            gate_span.tag("earlierOk", True)

        problem = ScaledProblem(
            avail=sess.scaled_avail,
            driver_rank=sess.driver_rank,
            exec_ok=sess.exec_ok,
            driver=driver_s,
            executor=executor_s,
            count=count_s,
            app_valid=np.ones(len(count_s), dtype=bool),
            scale=sess.scale,
            ok=True,
        )
        outcome = solver._pack_current(
            sess.cluster, problem, avail_after, n_earlier, current_app
        )
        return outcome, sess.zones

    # -- internals -----------------------------------------------------------

    @staticmethod
    def _session_artifacts(
        sess, packed, n_earlier, feasible, didx, resume, avail_after,
        lane, skip_allowed=(), content_key=None, feed_seq=None,
    ):
        """One SolveArtifacts construction from session fields, shared
        by the capture sink and the parity guard so the two bundles the
        subsystem emits can never drift apart field-by-field.  Arrays
        are referenced, not copied — the session's basis arrays are
        replaced on rebuild, never mutated in place."""
        from ..provenance.tracker import SolveArtifacts

        return SolveArtifacts(
            policy_code=sess.policy_code,
            lane=lane,
            basis=sess.scaled_avail,
            driver_rank=sess.driver_rank,
            exec_ok=sess.exec_ok,
            packed=packed,
            n_earlier=n_earlier,
            feasible=np.asarray(feasible, dtype=bool),
            didx=np.asarray(didx, dtype=np.int32),
            resume=int(resume),
            avail_after=np.asarray(avail_after, dtype=np.int32),
            scale=sess.scale,
            node_names=sess.cluster.node_names,
            zone_names=sess.cluster.zone_names,
            zone_id=sess.cluster.zone_id,
            skip_allowed=list(skip_allowed),
            content_key=content_key,
            feed_seq=feed_seq,
        )

    def _capture(
        self, sess, snap, policy_code, packed, driver_s, executor_s,
        count_s, n_earlier, feasible, didx, resume, avail_after,
        earlier_skip_allowed,
    ) -> None:
        """Hand the decision's full native inputs + verdicts to the
        provenance sink."""
        try:
            packed_full = np.empty((n_earlier + 1, 8), dtype=np.int32)
            packed_full[:n_earlier] = packed
            packed_full[n_earlier, 0:3] = driver_s[n_earlier]
            packed_full[n_earlier, 3:6] = executor_s[n_earlier]
            packed_full[n_earlier, 6] = count_s[n_earlier]
            packed_full[n_earlier, 7] = 1
            self.capture_sink(self._session_artifacts(
                sess, packed_full, n_earlier, feasible, didx, resume,
                avail_after, lane="native-session",
                skip_allowed=earlier_skip_allowed,
                content_key=snap.content_key,
                feed_seq=int(snap.content_key[1]),
            ))
        except Exception:
            logger.exception("provenance capture failed (diagnostic only)")

    def _verify_parity(
        self, sess, packed, feasible, didx, avail_after
    ) -> None:
        """Warm≠cold parity guard: the stateless cold solver run on the
        same basis + queue must reproduce the session's verdicts
        byte-for-byte (the PR 5 shared-step-function guarantee, now
        checked in the wild).  Divergence fires the flight recorder."""
        try:
            from ..native.fifo import solve_packed_cold

            cold_f, cold_d, cold_after = solve_packed_cold(
                sess.policy_code, sess.scaled_avail, sess.driver_rank,
                sess.exec_ok, packed,
            )
            ok = (
                cold_f.tobytes() == np.asarray(feasible, dtype=bool).tobytes()
                and cold_d.tobytes() == np.asarray(didx, np.int32).tobytes()
                and cold_after.tobytes()
                == np.asarray(avail_after, np.int32).tobytes()
            )
            hooks = self.parity_hooks
            if ok:
                if hooks is not None and hooks[0] is not None:
                    hooks[0]()
                return
            detail = {
                "policy": sess.policy_code,
                "n_apps": int(packed.shape[0]),
                "feasible_equal": bool(
                    cold_f.tobytes()
                    == np.asarray(feasible, dtype=bool).tobytes()
                ),
            }
            logger.error("deltasolve warm/cold parity mismatch: %s", detail)
            if hooks is not None and hooks[1] is not None:
                # ship the DIVERGING solve itself: the persisted bundle
                # must contain the anomaly, not just the decisions that
                # preceded it (the tracker notes these artifacts into
                # the recorder ring before persisting)
                try:
                    detail["artifacts"] = self._session_artifacts(
                        sess, packed, int(packed.shape[0]), feasible,
                        didx, 0, avail_after, lane="native-session-parity",
                    )
                except Exception:
                    pass
                hooks[1](detail)
        except Exception:
            logger.exception("parity guard failed to run (diagnostic only)")

    @staticmethod
    def _scale_apps(apps, scale: np.ndarray, nb: int):
        """(driver_s, executor_s, count_s) int32 in the session's units,
        or None when the cached scale cannot represent these demands
        exactly inside the session's numeric bounds.  Decisions are
        scale-invariant, so any exact representation matches the cold
        solve bit-for-bit."""
        d = apps.driver
        e = apps.executor
        if (d % scale).any() or (e % scale).any():
            return None
        ds = d // scale
        es = e // scale
        if (np.abs(ds) > INT32_SAFE).any() or (np.abs(es) > INT32_SAFE).any():
            return None
        counts = apps.count
        max_k = int(counts.max()) if counts.size else 0
        if max_k > INT32_SAFE or (max_k > 0 and nb * max_k > INT32_SAFE):
            # same int32 sum-overflow guard scale_problem applies
            return None
        return (
            ds.astype(np.int32),
            es.astype(np.int32),
            np.minimum(counts, INT32_SAFE).astype(np.int32),
        )

    def _cold_build(
        self, snap, driver_pod, candidate_names, node_sorter, prep, skey,
        policy_code, apps,
    ):
        """Build + load a fresh session (the full per-request path, plus
        one basis upload).  Returns (session, scaled apps) or (None, _)
        when the request can't be represented natively at all."""
        from ..native.fifo import NativeFifoSession
        from .batch_solver import mf_sentinel_safe
        from .fast_path import build_cluster_tensor
        from .tensorize import scale_problem

        built = build_cluster_tensor(
            snap,
            driver_pod,
            candidate_names,
            driver_label_priority=node_sorter.driver_label_priority,
            executor_label_priority=node_sorter.executor_label_priority,
        )
        if built is None:
            self._miss("inexact")
            return None, None
        cluster, zones = built
        problem = scale_problem(cluster, apps)
        if not problem.ok:
            self._miss("scale")
            return None, None
        if policy_code == 2 and not mf_sentinel_safe(problem.avail):
            self._miss("mf-sentinel")
            return None, None

        # reuse the evictee's native handle when this key is being
        # rebuilt: load() replaces all resident state, and an unchanged
        # worker count keeps the sharded pool's threads alive instead of
        # churning a pool per rebuild.  The stale entry is POPPED before
        # its handle is reloaded — if anything below raises, no mapping
        # survives whose Python-side basis disagrees with the basis now
        # resident in the shared handle (the next request cold-builds).
        with self._lock:
            racecheck.note_access(self, "_sessions")
            prior = self._sessions.pop(skey, None)
        if prior is not None:
            native = prior.native
        else:
            native = NativeFifoSession(
                threads=self._threads, min_pool_nodes=_POOL_MIN_NODES
            )
        native.load(
            problem.avail, problem.driver_rank, problem.exec_ok,
            policy_code, stride=self._stride,
        )
        # class-compressed solve mode at scale: partition upkeep only
        # pays for itself on big fleets, so small clusters (and the 10k
        # perf-gate lanes) keep the row-level step functions verbatim.
        # Decisions are byte-identical either way (PR 20 parity suite).
        use_classes = False
        if hasattr(native, "set_classes"):
            want = (
                self.classes_enabled
                and int(problem.avail.shape[0]) >= self.classes_min_nodes
            )
            # always called (even want=False): a reused evictee handle
            # must not carry the previous build's class mode
            supported = native.set_classes(want)
            use_classes = want and supported
        na = apps.driver.shape[0]
        sess = _Session(
            native=native,
            policy_code=policy_code,
            avail64=snap.avail[prep.idx],
            sched64=snap.schedulable[prep.idx],
            cluster=cluster,
            zones=zones,
            scale=problem.scale.astype(np.int64),
            scaled_avail=problem.avail,
            driver_rank=problem.driver_rank,
            exec_ok=problem.exec_ok,
            nb=int(problem.avail.shape[0]),
            content_key=snap.content_key,
            class_digest=getattr(snap, "class_digest", (-1, -1)),
            class_rev=getattr(snap, "class_rev", -1),
            use_classes=use_classes,
        )
        with self._lock:
            racecheck.note_access(self, "_sessions")
            self._sessions[skey] = sess  # stale entry already popped above
            while len(self._sessions) > self.MAX_SESSIONS:
                # evictees are dropped, not closed: another thread's
                # in-flight solve may still hold one (solve() runs
                # outside this lock); the native buffers free via
                # NativeFifoSession.__del__ when the last ref drops
                self._sessions.popitem(last=False)
        self._publish_gauges()
        # the scaled app block comes straight from the cold scaling
        scaled = (
            problem.driver[:na],
            problem.executor[:na],
            problem.count[:na],
        )
        return sess, scaled
