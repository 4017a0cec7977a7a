"""Metrics as data: ``metrics/<name>.json`` names a reader
(``readers/<reader>.py``, a module with ``read(context, **params)``) and
its parameters, for the end-to-end metrics and the per-layer ones alike.
A later PR adds a metric as a JSON file, a new reader as a new file, and
edits nothing here.  A reader that finds nothing to read returns None and
the metric is left out of the result line.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Optional, Sequence

import plugins
from traffic import BlockRecord

HERE = os.path.dirname(os.path.abspath(__file__))


def listed(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def end_to_end_names(bench: dict, cell: str) -> list:
    """The end-to-end metrics the cell reports."""
    return [m["name"] for m in bench["end_to_end"] if listed(m, cell)]


def window_context(window: Sequence[BlockRecord], setup_s: float, compiles_in_window: int,
                   config: dict, device: dict) -> Dict[str, object]:
    """What every run gives its readers, as plain data; a traced run adds
    the spans, the collector's pauses and the reduced trace."""
    return {
        "window": window,
        "setup_s": setup_s,
        "blocks_s": window[-1].end - window[0].start,  # all the time of the window
        "in_blocks_s": sum(b.end - b.start for b in window),  # less what fell between blocks
        "filter_s": sum(b.filter_seconds for b in window),
        "compiles_in_window": compiles_in_window,
        "config": config,
        "device": device,
    }


def read(name: str, context: Dict[str, object]) -> Optional[float]:
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        spec = json.load(f)
    return plugins.load("readers", spec["reader"]).read(context, **spec.get("params", {}))


def report(entries: Sequence[dict], context: Dict[str, object]) -> Dict[str, Dict[str, object]]:
    out: Dict[str, Dict[str, object]] = {}
    for entry in entries:
        value = read(entry["name"], context)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def end_to_end(bench: dict, cell: str, context: Dict[str, object]) -> Dict[str, Dict[str, object]]:
    """The cell's end-to-end metrics: over all the requests and all the
    time of the window's whole blocks, no median of chunks."""
    return report([m for m in bench["end_to_end"] if listed(m, cell)], context)


def per_layer(bench: dict, cell: str, context: Dict[str, object]) -> Dict[str, Dict[str, object]]:
    """The cell's per-layer metrics, each from its own reader: those that
    list the cell, and those that list none and move an end-to-end metric
    the cell reports."""
    reported = end_to_end_names(bench, cell)
    return report(
        [m for m in bench["per_layer"] if listed(m, cell) and m["moves"] in reported], context
    )
