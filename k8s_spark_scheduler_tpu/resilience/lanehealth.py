"""Per-kernel-lane health tracking with hysteresis.

The extender's device lanes (tensor-snapshot driver path, device FIFO
queue solve, tensor executor reschedule) each fall back to the exact
host path on any exception — silently, *per request*.  A wedged xla or
pallas lane therefore taxes every request with a doomed attempt (and
its timeout / compiler stall) forever.  This tracker scores each lane:

- ``failure_threshold`` consecutive failures — or successes slower than
  ``latency_budget_seconds`` (a deadline blowout is as bad as a fault) —
  **demote** the lane: the extender skips it entirely and dispatches the
  host/native path directly;
- after ``cooloff_seconds`` one request is allowed to **re-probe** the
  demoted lane; success promotes it back, failure restarts the cooloff.

Hysteresis means a single hiccup never flaps the lane, and a demoted
lane never costs more than one probe per cooloff.  Time flows through
:func:`..timesource.now` (virtual in the simulator, wall in prod).
"""

from __future__ import annotations

import logging
import threading
from typing import Dict, List, Optional

from .. import timesource
from ..analysis import racecheck
from ..analysis.guarded import guarded_by

logger = logging.getLogger(__name__)

HEALTHY = "healthy"
DEMOTED = "demoted"

_STATE_VALUE = {HEALTHY: 0.0, DEMOTED: 1.0}


class _Lane:
    __slots__ = (
        "state", "consecutive_failures", "failures", "demoted_at", "probe_in_flight",
    )

    def __init__(self):
        self.state = HEALTHY
        self.consecutive_failures = 0
        self.failures = 0  # lifetime total, never reset
        self.demoted_at = 0.0
        self.probe_in_flight = False


@guarded_by("_lock", "_lanes")
class LaneHealth:
    def __init__(
        self,
        failure_threshold: int = 3,
        cooloff_seconds: float = 60.0,
        latency_budget_seconds: Optional[float] = 5.0,
        metrics=None,
    ):
        self.failure_threshold = max(int(failure_threshold), 1)
        self.cooloff_seconds = cooloff_seconds
        self.latency_budget_seconds = latency_budget_seconds
        self._metrics = metrics
        self._lock = threading.Lock()
        self._lanes: Dict[str, _Lane] = {}

    def _lane(self, name: str) -> _Lane:
        racecheck.note_access(self, "_lanes")
        lane = self._lanes.get(name)
        if lane is None:
            lane = self._lanes[name] = _Lane()  # schedlint: disable=LK001 -- private helper, every caller holds _lock
        return lane

    # -- dispatch-side -------------------------------------------------------

    def allow(self, name: str) -> bool:
        """Should the extender attempt this lane?  Demoted lanes admit
        one re-probe per elapsed cooloff."""
        with self._lock:
            lane = self._lane(name)
            if lane.state == HEALTHY:
                return True
            if (
                not lane.probe_in_flight
                and timesource.now() - lane.demoted_at >= self.cooloff_seconds
            ):
                lane.probe_in_flight = True
                return True
            return False

    def record_success(self, name: str, duration_s: Optional[float] = None) -> None:
        budget = self.latency_budget_seconds
        if budget is not None and duration_s is not None and duration_s > budget:
            # a deadline blowout counts against the lane even though the
            # result was usable — the NEXT caller shouldn't pay it again
            self.record_failure(name, reason="latency")
            return
        with self._lock:
            lane = self._lane(name)
            lane.consecutive_failures = 0
            lane.probe_in_flight = False
            if lane.state == DEMOTED:
                self._set_state(name, lane, HEALTHY)
                logger.info("kernel lane %s re-promoted after successful probe", name)

    def release_probe(self, name: str) -> None:
        """The attempt ended neutrally — the lane declined the work
        (unsupported shape, inexact snapshot) rather than succeeding or
        failing.  Free the probe slot so the next request may probe;
        without this a demoted lane whose re-probe hit an unsupported
        request would stay demoted forever."""
        with self._lock:
            self._lane(name).probe_in_flight = False

    def record_failure(self, name: str, reason: str = "error") -> None:
        with self._lock:
            lane = self._lane(name)
            lane.consecutive_failures += 1
            lane.failures += 1
            if lane.state == DEMOTED:
                # failed probe: restart the cooloff
                lane.demoted_at = timesource.now()
                lane.probe_in_flight = False
                return
            if lane.consecutive_failures >= self.failure_threshold:
                lane.demoted_at = timesource.now()
                lane.probe_in_flight = False
                self._set_state(name, lane, DEMOTED)
                logger.warning(
                    "kernel lane %s demoted after %d consecutive %s failures; "
                    "re-probing after %.0fs",
                    name,
                    lane.consecutive_failures,
                    reason,
                    self.cooloff_seconds,
                )
                if self._metrics is not None:
                    from ..metrics import names as mnames

                    self._metrics.counter(
                        mnames.RESILIENCE_LANE_DEMOTIONS,
                        {"lane": name, "reason": reason},
                    )

    # -- introspection -------------------------------------------------------

    def demoted_lanes(self) -> List[str]:
        with self._lock:
            return sorted(n for n, l in self._lanes.items() if l.state == DEMOTED)

    def failure_totals(self) -> Dict[str, int]:
        """Lifetime failures per lane (errors and over-budget successes),
        lanes with none omitted.  chip_smoke.py asserts it is empty: a lane
        that failed even once did not serve every answer."""
        with self._lock:
            return {n: l.failures for n, l in self._lanes.items() if l.failures}

    def state_of(self, name: str) -> str:
        with self._lock:
            return self._lane(name).state

    def _set_state(self, name: str, lane: _Lane, state: str) -> None:
        # caller holds the lock
        lane.state = state
        if self._metrics is not None:
            from ..metrics import names as mnames

            self._metrics.gauge(
                mnames.RESILIENCE_LANE_STATE, _STATE_VALUE[state], {"lane": name}
            )
