"""Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name a data file gives it:

=================  ==========================  ================================
named by           key                         file
=================  ==========================  ================================
a traffic mix      each entry of ``steps``     ``traffic/steps/<verb>.py``
a configuration    ``generator``               ``generators/<name>.py``
a configuration    ``objects``                 ``objects/<name>.py``
a configuration    ``reference.model``         ``references/<name>.py``
a configuration    ``reference.policy``        ``policies/<name>.py``
a metric file      ``reader``                  ``readers/<name>.py``
=================  ==========================  ================================

A later PR adds a verb, a cluster or gang shape, a policy, a reference
model or a reader as a new file and edits nothing that is there.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("traffic/steps", "generators", "objects", "references", "policies", "readers")
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@functools.lru_cache(maxsize=None)
def load(kind: str, name: str):
    """The module ``<kind>/<name>.py`` under ``benchmarks/``."""
    if kind not in KINDS:
        raise ValueError(f"no plug-in directory {kind!r} (there are {KINDS})")
    if not _NAME.match(name):
        raise ValueError(f"{name!r} is not a name")
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        have = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, kind)) if f.endswith(".py"))
        raise ValueError(f"no {kind}/{name}.py (there are {have}): add it as a new file")
    module_name = "benchmark_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module  # dataclasses look their module up there
    spec.loader.exec_module(module)
    return module
