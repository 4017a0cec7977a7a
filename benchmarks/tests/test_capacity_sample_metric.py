"""``capacity_sample_s``: the capacity sampler's root span read as the
marker's scan is, and left out where the program has no such span."""

import json
import os

import pytest

import plugins

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sample(ms):
    return {"total": {"capacity.sample": ms}, "self": {"capacity.sample": ms}, "fifo_gate": {}}


def spec():
    with open(os.path.join(ROOT, "benchmarks", "metrics", "capacity_sample_s.json")) as f:
        return json.load(f)


def test_capacity_sample_s_is_the_median_sample_in_seconds_and_none_without_the_span():
    reader = plugins.load("readers", spec()["reader"])
    params = spec()["params"]
    context = {
        "kinds": {"req-1": "driver"},
        "requests": {
            "req-1": {"total": {"predicate": 30.0, "capacity.sample": 9_000.0}, "self": {}, "fifo_gate": {}},
            "s-1": sample(31.0),
            "s-2": sample(27.0),
            "s-3": sample(45.0),
        },
    }
    assert reader.read(context, **params) == pytest.approx(0.031)
    for key in ("s-1", "s-2", "s-3"):
        del context["requests"][key]
    assert reader.read(context, **params) is None  # a parent: no sample is a trace of its own


def test_capacity_sample_s_is_listed_in_every_cell_as_a_host_runtime_metric():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "capacity_sample_s"]
    assert entry["layer"] == "host runtime" and entry["source"] == "program_span"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"] if "workloads" not in m}
