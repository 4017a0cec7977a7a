#!/usr/bin/env python3
"""run.py with a fault planted in the single-AZ dynamic-allocation
*reference* (``references/fifo-gangs-single-az-dynalloc.py``), which the
comparison has to call incorrect:

    python3 benchmarks/tests/planted_single_az_dynalloc.py <fault> --workload ... (run.py's arguments)

``zone-blind``        the install key off: an executor beyond min may go to
                      any zone
``attraction-blind``  no min-frag choice: an executor beyond min goes to
                      the first node that fits, in executor priority order,
                      inside its application's zone

The program is untouched; where it is right, the faulty reference
disagrees with it.  Used by ``test_single_az_dynalloc.py`` on the CPU and
by hand on the chip."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

FAULTS = ("zone-blind", "attraction-blind")


def plant(fault: str):
    """Plant ``fault``; returns the function that takes it out again."""
    import plugins

    cls = plugins.load("references", "fifo-gangs-single-az-dynalloc").Reference
    if fault == "zone-blind":
        name, planted = "_common_zone", lambda self, app: None
    elif fault == "attraction-blind":
        name, planted = "_leading_keys", lambda self, app, rows, capacity: ()
    else:
        raise SystemExit(f"planted_single_az_dynalloc.py: no fault {fault!r} (there are: {', '.join(FAULTS)})")
    real = cls.__dict__[name]
    setattr(cls, name, planted)
    return lambda: setattr(cls, name, real)


def main(argv) -> int:
    import run as run_mod

    plant(argv[0])
    return run_mod.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
