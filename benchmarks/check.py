"""The comparison that decides ``correct``.

Once the window has closed, every gang of the window is replayed, in
order, through the plain reference, step by step as the traffic ran it:
each step's own ``compare`` (``traffic/steps/<verb>.py``) asks the
reference the same thing and counts where the timed path said otherwise,
one answer at a time.  All comparisons are exact, so every limit is 0;
each step names the numbers it compares (``CHECKS``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence

from traffic import BlockRecord, step_modules


@dataclass
class Comparison:
    """What the steps' ``compare`` functions share."""

    reference: object
    node_names: Sequence[str]
    wrong: Dict[str, int]
    compared: int = 0
    grant: Optional[object] = None  # the reference's grant for the gang in hand
    limits: Dict[str, int] = field(default_factory=dict)


def compare(
    window: Sequence[BlockRecord], reference, node_names: Sequence[str], steps: Sequence[str],
) -> Dict[str, Dict[str, Optional[int]]]:
    """{number compared: {"value", "limit"}} plus how many were compared."""
    modules = step_modules(steps)
    limits = {"answers_missing": 0}
    for module in modules:
        limits.update(getattr(module, "CHECKS", {}))
    c = Comparison(reference, node_names, {name: 0 for name in limits}, limits=limits)
    for block in window:
        for rec in block.gangs:
            c.grant = None
            for module in modules:
                if hasattr(module, "compare"):
                    module.compare(rec, c)
    out = {name: {"value": c.wrong[name], "limit": limits[name]} for name in limits}
    out["answers_compared"] = {"value": c.compared, "limit": None}
    return out


def is_correct(checks: Dict[str, Dict[str, Optional[int]]]) -> bool:
    return all(
        c["value"] <= c["limit"] for c in checks.values() if c["limit"] is not None
    ) and checks["answers_compared"]["value"] > 0
