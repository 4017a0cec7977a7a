"""schedlint: project-specific static analysis + runtime race detection.

The scheduler's correctness rests on three families of invariants that
ordinary linters cannot see:

- **TS/DT determinism** — every *semantic* clock read goes through
  :mod:`..timesource` (the simulator swaps in a virtual clock), and
  every random stream is explicitly seeded.  A stray ``time.time()`` or
  unseeded ``random.random()`` silently breaks sim reproducibility.
- **LK lock discipline** — the mutable state behind the extender lock
  (write-back stores, soft reservations, resilience components) is
  declared with :func:`guarded_by`; mutations outside the declared
  ``with lock:`` scope are flagged at lint time and observed at runtime
  by the lockset race detector (:mod:`.racecheck`).
- **JX tracer safety** — the ``ops/`` JAX kernels must not branch on
  traced values, concretize tracers, or close over mutable state: each
  of those is a silent-retrace (or outright crash) hazard on the
  binpack hot path.
- **PC protocol discipline** — flow-sensitive typestate over a real CFG
  (:mod:`.flow`): kube mutations are dominated by a fencing check
  from their entry points, journal intents are never acked before their
  execute, spans/locks close path-completely, and the extender's phase
  ladder re-arms its deadline at each boundary (:mod:`.rules_protocol`).

Run it::

    python -m k8s_spark_scheduler_tpu.analysis --strict

Suppressions are inline pragmas with a mandatory justification in
strict mode::

    deadline = time.monotonic() + t  # schedlint: disable=TS002 -- bounded infra wait, must not freeze with the sim clock

See docs/development.md for the rule catalogue.
"""

from __future__ import annotations

from .core import (
    DEFAULT_ALLOWLIST,
    AnalysisConfig,
    AnalysisResult,
    Finding,
    SuppressedFinding,
    analyze_package,
    analyze_paths,
    analyze_paths_detailed,
    load_allowlist,
    package_root,
)
from .guarded import guarded_by, guarded_fields
from .reporters import render_json, render_text

__all__ = [
    "AnalysisConfig",
    "AnalysisResult",
    "DEFAULT_ALLOWLIST",
    "Finding",
    "SuppressedFinding",
    "analyze_package",
    "analyze_paths",
    "analyze_paths_detailed",
    "guarded_by",
    "guarded_fields",
    "load_allowlist",
    "package_root",
    "render_json",
    "render_text",
]
