"""The one general traffic generator: a closed loop over whole blocks.

A traffic mix is a data file (``traffic/<name>.json``): block size, warm-up
blocks, and the ``steps`` each gang of a block goes through.  A step is a
verb of kube-scheduler or of the API machinery around a Spark gang, and a
file of its own, ``traffic/steps/<verb>.py``, found by its name:

``run(s)``                     do it, for the gang of ``s`` (a ``GangRun``)
``compare(rec, c)``            optional: replay it through the reference
                               and count what the timed path got wrong
``CHECKS``                     optional: {number compared: limit}

One client, closed loop: the next request leaves when the last one came
back, as kube-scheduler runs its scheduling cycle serially.
"""

from __future__ import annotations

import json
import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import plugins
from blocks import Gang

Answer = Tuple[float, str, bytes]  # seconds by the client's clock, trace id, response body


@dataclass
class GangRecord:
    """What the timed path answered for one gang: the timed requests by
    kind (``driver``, ``executor``, ...) and what was read back besides
    (``reservation``, ``api_reservation``, ``lane``, ...)."""

    gang: Gang
    answers: Dict[str, List[Answer]] = field(default_factory=dict)
    read: Dict[str, object] = field(default_factory=dict)


@dataclass
class BlockRecord:
    start: float
    end: float
    gangs: List[GangRecord]

    @property
    def pods(self) -> int:
        """Pods the scheduler answered for in this block."""
        return sum(len(a) for g in self.gangs for a in g.answers.values())

    @property
    def filter_seconds(self) -> float:
        """Sum of the timed requests' client-side times."""
        return sum(a[0] for g in self.gangs for kind in g.answers.values() for a in kind)


@dataclass
class GangRun:
    """What the steps of one gang share while it runs."""

    client: object
    objects: object  # the configuration's adapter: pods(gang)
    gang: Gang
    rec: GangRecord
    annotate: Callable[[str], object]
    pods: list = field(default_factory=list)  # the program's objects for the gang
    created: list = field(default_factory=list)  # those the API server holds
    node: Optional[str] = None  # where the driver was granted

    def answered(self, kind: str, answer: Answer) -> Optional[str]:
        """Keep a timed answer; returns the node it grants, if any."""
        self.rec.answers.setdefault(kind, []).append(answer)
        return granted(answer[2])


def step_modules(steps: Sequence[str]) -> list:
    """The steps' modules, in order; an unknown verb is an error that
    names the file to add."""
    return [plugins.load("traffic/steps", verb) for verb in steps]


def answered_nodes(body: bytes) -> List[str]:
    """``NodeNames`` of a Filter response: one node, or none for a refusal."""
    return json.loads(body).get("NodeNames") or []


def granted(body: bytes) -> Optional[str]:
    names = answered_nodes(body)
    return names[0] if names else None


def no_annotation(name: str):
    return nullcontext()


def run_block(
    client, objects, block: Sequence[Gang], steps: Sequence[str],
    annotate: Callable[[str], object] = no_annotation,
) -> BlockRecord:
    """One whole block through the client; ``annotate(name)`` wraps each
    step in a profiler annotation in a traced run."""
    modules = step_modules(steps)
    start = time.perf_counter()
    records: List[GangRecord] = []
    for gang in block:
        run = GangRun(client, objects, gang, GangRecord(gang), annotate)
        for module in modules:
            module.run(run)
        records.append(run.rec)
    return BlockRecord(start, time.perf_counter(), records)


def run_window(
    next_block: Iterator[List[Gang]], seconds: float, run_one: Callable[[Sequence[Gang]], BlockRecord],
    on_block: Optional[Callable[[BlockRecord, float], None]] = None,
) -> List[BlockRecord]:
    """Whole blocks from a block boundary until the first block boundary
    at or after ``seconds``.  ``on_block`` (traced runs only) is told of
    each boundary."""
    out: List[BlockRecord] = []
    opened = time.perf_counter()
    while True:
        rec = run_one(next(next_block))
        out.append(rec)
        since_open = rec.end - opened
        if on_block is not None:
            on_block(rec, since_open)
        if since_open >= seconds:
            return out


def answers(window: Sequence[BlockRecord], kind: Optional[str] = None) -> List[Answer]:
    """All timed answers of the window of one kind (or of every kind), in order."""
    return [
        a for b in window for g in b.gangs
        for k, found in g.answers.items() if kind is None or k == kind
        for a in found
    ]


def kinds(window: Sequence[BlockRecord]) -> List[str]:
    return sorted({k for b in window for g in b.gangs for k in g.answers})


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile of all ``values`` (q in 0..100)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("no values")
    pos = (len(ordered) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)
