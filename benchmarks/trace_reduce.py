"""From a profiler trace to device metrics.

``load_events`` reads the ``.xplane.pb`` the JAX profiler wrote into plain
events; ``reduce`` turns events into the numbers the per-layer readers
use.  The reducer sees only the plain events, so it is checked against a
small recorded trace kept as JSON (``tests/data/small_trace.json``).

An event is ``{"plane", "line", "name", "start_ns", "dur_ns"}``.  Device
operations are the events of the ``XLA Ops`` lines of ``/device:TPU:n``
planes; the step each belongs to is the ``XLA Modules`` event that holds
its start.  The client's steps are the ``client.*`` annotations the
harness wrote on the host.  The traced window runs from the first
annotation's start to the last one's end.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLIENT_PREFIX = "client."
Event = Dict[str, object]
Interval = Tuple[int, int]


def load_events(trace_dir: str) -> List[Event]:
    """Device-plane events and ``client.*`` host annotations of the one
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    events: List[Event] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        on_device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            for ev in line.events:
                if on_device or ev.name.startswith(CLIENT_PREFIX):
                    events.append({
                        "plane": plane.name, "line": line.name, "name": short_name(ev.name),
                        "start_ns": int(ev.start_ns), "dur_ns": int(ev.duration_ns),
                    })
    return events


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO line
    (``%fusion.1 = (s32[...]) fusion(...)``): keep what is left of ``=``."""
    return name.split(" = ")[0].lstrip("%")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[Interval] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def _clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def _span(ev: Event) -> Interval:
    return int(ev["start_ns"]), int(ev["start_ns"]) + int(ev["dur_ns"])


def reduce(events: Sequence[Event]) -> Dict[str, object]:
    """busy/idle seconds per device plane, per-operation device seconds,
    idle gaps by what the client was doing, and how often each client step
    ran, over the traced window."""
    client = [e for e in events if str(e["name"]).startswith(CLIENT_PREFIX)]
    if not client:
        raise ValueError("the trace holds no client.* annotation: no window to reduce")
    lo = min(_span(e)[0] for e in client)
    hi = max(_span(e)[1] for e in client)
    planes = sorted({str(e["plane"]) for e in events if str(e["plane"]).startswith(DEVICE_PLANE)})
    ops = [e for e in events if e["line"] == OPS_LINE and str(e["plane"]).startswith(DEVICE_PLANE)]
    modules = sorted(
        (e for e in events if e["line"] == MODULES_LINE), key=lambda e: (str(e["plane"]), _span(e)[0])
    )
    held = defaultdict(list)
    for m in modules:
        held[str(m["plane"])].append(m)
    held_starts = {plane: [_span(m)[0] for m in ms] for plane, ms in held.items()}

    def module_of(op: Event) -> str:
        plane = str(op["plane"])
        i = bisect.bisect_right(held_starts.get(plane, []), _span(op)[0]) - 1
        if i >= 0 and _span(held[plane][i])[1] >= _span(op)[0]:
            return str(held[plane][i]["name"]).split("(")[0]
        return ""

    busy_by_plane: Dict[str, List[Interval]] = {}
    op_seconds: Dict[str, float] = defaultdict(float)
    for plane in planes:
        mine = [e for e in ops if e["plane"] == plane]
        busy_by_plane[plane] = _clip(union(_span(e) for e in mine), lo, hi)
        for e in mine:
            s, t = _span(e)
            inside = min(t, hi) - max(s, lo)
            if inside > 0:
                module = module_of(e)
                op_seconds[f"{module}/{e['name']}" if module else str(e["name"])] += inside / 1e9
    busy_s = [sum(t - s for s, t in iv) / 1e9 for iv in busy_by_plane.values()]
    window_s = (hi - lo) / 1e9

    # idle gaps of the first device, by the client step under way
    gaps: Dict[str, float] = defaultdict(float)
    if planes:
        idle = _complement(busy_by_plane[planes[0]], lo, hi)
        # the client is one thread: its steps follow one another
        steps = sorted((_span(e) + (str(e["name"]),) for e in client))
        starts = [s for s, _, _ in steps]
        for g0, g1 in idle:
            covered = 0
            for s, t, name in steps[max(bisect.bisect_right(starts, g0) - 1, 0):]:
                if t <= g0:
                    continue
                if s >= g1:
                    break
                part = min(t, g1) - max(s, g0)
                gaps[name] += part / 1e9
                covered += part
            gaps["client.other"] += max(g1 - g0 - covered, 0) / 1e9
    calls: Dict[str, int] = defaultdict(int)
    for e in client:
        calls[str(e["name"])] += 1
    return {
        "window_s": window_s,
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "device_planes": planes,
        "op_seconds": dict(op_seconds),
        "idle_gaps": {k: v for k, v in gaps.items() if v > 0},
        "client_calls": dict(calls),
    }


def _complement(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, at = [], lo
    for s, t in busy:
        if s > at:
            out.append((at, s))
        at = max(at, t)
    if hi > at:
        out.append((at, hi))
    return out


def top(table: Dict[str, float], n: int = 10) -> List[List[object]]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
