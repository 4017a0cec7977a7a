#!/usr/bin/env python3
"""run.py with a fault planted in the dynamic-allocation *reference*
(``references/fifo-gangs-dynalloc.py``), which the comparison has to call
incorrect:

    python3 benchmarks/tests/planted_dynalloc.py <fault> --workload ... (run.py's arguments)

``soft-blind``      what is free leaves the soft reservations out: an extra
                    executor is placed as if the ones before it held nothing
``drivers-at-max``  a driver, and every driver ahead of it, is packed with
                    its max executors, as under static allocation
``never-compacts``  an executor's death moves no soft-reserved executor
                    onto the freed slot

The program is untouched; where it is right, the faulty reference
disagrees with it.  Used by ``test_dynalloc.py`` on the CPU and by hand
on the chip."""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

FAULTS = ("soft-blind", "drivers-at-max", "never-compacts")


def plant(fault: str):
    """Plant ``fault``; returns the function that takes it out again."""
    import plugins

    model = plugins.load("references", "fifo-gangs-dynalloc")
    cls = model.Reference
    if fault == "soft-blind":
        name, real = "_free", cls._free

        def planted(self, *overhead):
            cpu, mem = real(self, *overhead)
            for app in self._apps.values():  # give back what the soft reservations took
                for node in app.soft.values():
                    cpu[self._index[node]] += app.gang.executor_cpu * 1000
                    mem[self._index[node]] += app.gang.executor_mem_gi * model.GI
            return cpu, mem

    elif fault == "drivers-at-max":
        name, real, planted = "_at_min", cls.__dict__["_at_min"], staticmethod(lambda gang: gang)
    elif fault == "never-compacts":
        name, real, planted = "_compact", cls._compact, lambda self: None
    else:
        raise SystemExit(f"planted_dynalloc.py: no fault {fault!r} (there are: {', '.join(FAULTS)})")
    setattr(cls, name, planted)
    return lambda: setattr(cls, name, real)


def main(argv) -> int:
    import run as run_mod

    plant(argv[0])
    return run_mod.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
