"""PC protocol rules: broken-twin fixtures, fixed-twin counterparts,
and runtime regressions for the true positives the pass surfaced.

Each PC003–PC006 rule must catch its deliberately broken twin of real
code at a *pinned* file:line (the fixtures under
``tests/fixtures/protocol/``), while the corrected shape — the one now
living in the package — stays clean.  The real finding the first run
produced (the unfenced eviction replay in
``PreemptionCoordinator.recover``) gets a behavioral regression test
here; the package-wide ``--strict`` self-check in ``test_schedlint.py``
keeps it fixed statically.
"""

import os

import pytest

from k8s_spark_scheduler_tpu.analysis import AnalysisConfig, analyze_paths
from k8s_spark_scheduler_tpu.ha.fencing import (
    FencedWriter,
    FenceState,
    StaleEpochError,
)
from k8s_spark_scheduler_tpu.policy.preempt import EVICT_KIND, PreemptionCoordinator

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "protocol")


def _analyze_fixture(name):
    path = os.path.join(FIXTURES, name)
    config = AnalysisConfig(select=("PC",), use_default_allowlist=False)
    return analyze_paths([path], config=config, root=FIXTURES)


def _analyze_snippet(tmp_path, source):
    f = tmp_path / "snippet.py"
    f.write_text(source)
    config = AnalysisConfig(select=("PC",), use_default_allowlist=False)
    return analyze_paths([str(f)], config=config, root=str(tmp_path))


# -- the seeded broken twins, pinned file:line --------------------------------


def test_pc003_catches_unfenced_write_twin():
    findings = _analyze_fixture("broken_unfenced_write.py")
    assert [(f.rule, f.file, f.line, f.symbol) for f in findings] == [
        ("PC003", "broken_unfenced_write.py", 16, "BrokenCoordinator._execute"),
    ]
    # the message names the unfenced *path*, not just the write
    assert "BrokenCoordinator.recover" in findings[0].message
    assert "BrokenCoordinator._execute" in findings[0].message


def test_pc004_catches_journal_ack_twin():
    findings = _analyze_fixture("broken_journal_ack.py")
    assert [(f.rule, f.file, f.line, f.symbol) for f in findings] == [
        ("PC004", "broken_journal_ack.py", 13, "BrokenWorker.run_one"),
    ]


def test_pc005_catches_span_and_lock_leak_twin():
    findings = _analyze_fixture("broken_span_leak.py")
    assert [(f.rule, f.file, f.line, f.symbol) for f in findings] == [
        ("PC005", "broken_span_leak.py", 8, "BrokenHandler.handle"),
        ("PC005", "broken_span_leak.py", 8, "BrokenHandler.handle"),
        ("PC005", "broken_span_leak.py", 16, "BrokenHandler.try_lock"),
    ]
    msgs = " | ".join(f.message for f in findings[:2])
    assert "a fall-through path" in msgs and "an exception path" in msgs


def test_pc006_catches_phase_skip_twin():
    findings = _analyze_fixture("broken_phase_skip.py")
    assert [(f.rule, f.file, f.line, f.symbol) for f in findings] == [
        ("PC006", "broken_phase_skip.py", 12, "BrokenExtender.select"),
    ]
    assert "binpack" in findings[0].message


# -- the fixed shapes stay clean ----------------------------------------------


FIXED_RECOVER = """\
# schedlint: entrypoints=Coordinator.recover
class Coordinator:
    def _execute(self, ns, app_id):
        self._api.delete("Pod", ns, app_id)

    def recover(self):
        gate = self.fence_gate
        if gate is not None:
            gate.check("preempt.recover")
        for intent in self._journal.pending():
            self._execute(intent["ns"], intent["name"])
"""

FIXED_WORKER = """\
class Worker:
    def run_one(self, r):
        self._journal.record("create", r.kind, r.ns, r.name, r.obj)
        self._client.create(r.kind, r.ns, r.obj)
        self._journal.ack("create", r.ns, r.name)
"""

FIXED_HANDLER = """\
class Handler:
    def handle(self, req):
        span = self._tracer.span("request")
        span.__enter__()
        try:
            if req.bad:
                return None
            return self._process(req)
        finally:
            span.__exit__(None, None, None)
"""

FIXED_PHASES = """\
class Extender:
    def select(self, ctx):
        self._check_deadline("fifo-gate")
        fitted = self._try_device_fifo(ctx)
        if fitted is None:
            fitted = self._fit_earlier_drivers(ctx)
        self._check_deadline("binpack")
        with self._tracer.span("binpack"):
            plan = self.binpacker.binpack(ctx)
        self._check_deadline("reservation-writeback")
        self._rrm.create_reservations(plan)
        return plan
"""


@pytest.mark.parametrize(
    "source",
    [
        FIXED_RECOVER,
        FIXED_WORKER,
        FIXED_HANDLER,
        FIXED_PHASES,
    ],
    ids=["recover", "worker", "handler", "phases"],
)
def test_fixed_twin_is_clean(tmp_path, source):
    assert _analyze_snippet(tmp_path, source) == []


# -- PC004: exits in the recorded state are "left pending", not findings ------


LEFT_PENDING = """\
class Worker:
    def run_one(self, r):
        self._journal.record("create", r.kind, r.ns, r.name, r.obj)
        self._client.create(r.kind, r.ns, r.obj)
"""


def test_pc004_allows_intent_left_pending(tmp_path):
    # a crash between record and ack leaves the intent for replay —
    # that IS the journal contract, not a violation
    assert _analyze_snippet(tmp_path, LEFT_PENDING) == []


MOOT_ACK = """\
class Worker:
    def replay(self, intents):
        for it in intents:
            self._journal.ack("create", it.ns, it.name)
"""


def test_pc004_allows_moot_acks_in_replay(tmp_path):
    # replay paths ack intents whose op already landed; no record in
    # scope means nothing can be lost
    assert _analyze_snippet(tmp_path, MOOT_ACK) == []


# -- runtime regression for the real finding --------------------------------


class _RecordingApi:
    def __init__(self):
        self.deletes = []

    def delete(self, kind, ns, name):
        self.deletes.append((kind, ns, name))


class _RecordingCache:
    def __init__(self):
        self.deletes = []

    def delete(self, ns, app_id):
        self.deletes.append((ns, app_id))


def test_recover_is_fenced_after_deposition(tmp_path):
    """The PC003 finding made real: a deposed replica replaying its
    evict journal must be refused before it deletes a single pod."""
    api = _RecordingApi()
    coord = PreemptionCoordinator(
        api, _RecordingCache(), journal_path=str(tmp_path / "evict")
    )
    coord._journal.record(
        "delete", EVICT_KIND, "ns1", "app-a", {"pods": ["p1", "p2"]}
    )

    deposed = FenceState()
    deposed.grant(1)
    deposed.observe(2)  # a newer leader exists
    coord.install_fence(FencedWriter(deposed))
    with pytest.raises(StaleEpochError):
        coord.recover()
    assert api.deletes == [], "deposed replica executed an eviction"

    # the live leader replays the same intent exactly once
    live = FenceState()
    live.grant(3)
    coord.install_fence(FencedWriter(live))
    assert coord.recover() == 1
    assert [d[1:] for d in api.deletes] == [("ns1", "p1"), ("ns1", "p2")]
    assert coord.recover() == 0  # acked: nothing left to replay


def test_recover_without_fence_still_replays_at_boot(tmp_path):
    """Wiring calls recover() before install_fence — the guard must be
    a no-op on the single-replica boot path."""
    api = _RecordingApi()
    coord = PreemptionCoordinator(
        api, _RecordingCache(), journal_path=str(tmp_path / "evict")
    )
    coord._journal.record("delete", EVICT_KIND, "ns1", "app-a", {"pods": ["p1"]})
    assert coord.recover() == 1
    assert len(api.deletes) == 1
