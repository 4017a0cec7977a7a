"""Crash-point injection + recovery matrix.

The unit half pins the crashpoint registry semantics (one-shot arming,
BaseException severity, disabled-path shape); the integration half runs
every crash-matrix cell through the real server stack, each gang
admitted by ``extender.predicate``: kill -9 at the armed point,
cold-restart a successor on the same API server and journal files,
audit invariants + exactly-once intent delivery.
"""

import pytest

from k8s_spark_scheduler_tpu.ha import crashpoint

# a SimulatedCrash killing an async worker thread is the scenario under
# test, not a leak
pytestmark = pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
from k8s_spark_scheduler_tpu.ha.crashmatrix import CrashMatrix
from k8s_spark_scheduler_tpu.ha.crashpoint import SimulatedCrash


@pytest.fixture(autouse=True)
def _disarmed():
    crashpoint.disarm()
    yield
    crashpoint.disarm()


# -- registry semantics ------------------------------------------------------


def test_registry_covers_every_pipeline():
    points = crashpoint.registered_points()
    assert len(points) == 10
    for prefix in ("writeback.", "journal.", "preempt.", "lease."):
        assert any(p.startswith(prefix) for p in points), prefix


def test_arm_unknown_point_rejected():
    with pytest.raises(ValueError):
        crashpoint.arm("no.such.point")


def test_disabled_traversal_is_a_no_op():
    crashpoint.maybe_crash(crashpoint.WRITEBACK_PRE_COMMIT)  # nothing armed


def test_armed_point_fires_once_then_disarms():
    crashpoint.arm(crashpoint.JOURNAL_POST_APPEND)
    # other points pass through untouched
    crashpoint.maybe_crash(crashpoint.WRITEBACK_PRE_COMMIT)
    assert crashpoint.armed() == crashpoint.JOURNAL_POST_APPEND
    with pytest.raises(SimulatedCrash) as e:
        crashpoint.maybe_crash(crashpoint.JOURNAL_POST_APPEND)
    assert e.value.point == crashpoint.JOURNAL_POST_APPEND
    # one-shot: recovery re-traversing the same point must not re-die
    assert crashpoint.armed() is None
    crashpoint.maybe_crash(crashpoint.JOURNAL_POST_APPEND)


def test_simulated_crash_skips_except_exception():
    """The whole point of BaseException: the async worker's
    ``except Exception`` drain-keeper must not survive a kill."""
    assert not issubclass(SimulatedCrash, Exception)
    crashpoint.arm(crashpoint.WRITEBACK_POST_COMMIT)
    with pytest.raises(SimulatedCrash):
        try:
            crashpoint.maybe_crash(crashpoint.WRITEBACK_POST_COMMIT)
        except Exception:  # noqa: BLE001 - the handler under test
            pytest.fail("SimulatedCrash was caught by `except Exception`")


# -- matrix cells through the real server stack ------------------------------

@pytest.mark.parametrize("point", crashpoint.registered_points())
def test_crash_point_recovery(point):
    report = CrashMatrix(nodes=2).run_point(point)
    assert report["crashed"], f"{point}: crash never fired"
    assert report["ok"], f"{point}: {report['violations']}"
    # the successor took over at the next epoch and drained both
    # journals: every intent landed exactly once across the restart
    assert report["recoveredEpoch"] == 2
    assert report["journalDepth"] == 0
    assert report["evictJournalDepth"] == 0
    assert report["staleCommits"] == 0
    if point.startswith("writeback."):
        # all-or-nothing is audited on the reservation's own write
        assert "reservationPresent" in report


def test_mid_preemption_crash_finishes_the_eviction():
    """The sharpest cell: death between the first and second victim pod
    delete.  The successor must finish the half-evicted gang — pods
    gone AND reservation gone — never leave it straddled."""
    report = CrashMatrix(nodes=2).run_point(crashpoint.PREEMPT_MID_EXECUTE)
    assert report["ok"], report["violations"]
    assert report["victimPods"], "cell never scheduled its victim gang"
