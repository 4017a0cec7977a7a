#!/usr/bin/env python3
"""run.py with a fault planted in the *reference*, which the comparison
has to call incorrect:

    python3 benchmarks/tests/planted_fault.py first-feasible-zone --workload ... (run.py's arguments)

``first-feasible-zone``: the single-AZ policy takes the first zone that
fits the gang, in zone order, instead of the one with the highest average
packing efficiency.  The program is untouched; where it is right, the
faulty reference disagrees with it wherever the best zone is not the
first.  Used by ``test_single_az.py`` on the CPU and by hand on the chip."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)


def plant(fault: str):
    """Plant ``fault``; returns the function that takes it out again."""
    import plugins

    if fault != "first-feasible-zone":
        raise SystemExit(f"planted_fault.py: no fault {fault!r} (there is: first-feasible-zone)")
    policy = plugins.load("policies", "single-az-tightly-pack")
    real = policy.best_zone
    policy.best_zone = lambda candidates: candidates[0][1] if candidates else None

    def unplant():
        policy.best_zone = real

    return unplant


def main(argv) -> int:
    import run as run_mod

    plant(argv[0])
    return run_mod.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
