"""Scenario-spec validation contract (satellite of the policy lab PR).

``Scenario.from_dict`` must fail FAST with a dotted-path message naming
the offending key — not let a typo'd scenario run for minutes and die
in a deep runner traceback (or worse, run to completion with the typo'd
block silently ignored, which is what unknown keys used to do).
"""

import glob
import json
import pathlib

import pytest

from k8s_spark_scheduler_tpu.sim.scenario import Scenario, ScenarioError

REPO = pathlib.Path(__file__).resolve().parents[1]


def _base():
    return {
        "name": "v",
        "seed": 1,
        "duration": 300,
        "cluster": {"nodes": 4, "cpu": "16", "memory": "32Gi"},
        "workload": {
            "process": "poisson",
            "rate_per_min": 2,
            "executors": {"min": 1, "max": 4},
            "lifetime": {"min": 60, "max": 120},
        },
        "faults": [{"at": 100, "kind": "node_kill", "count": 1}],
    }


@pytest.mark.parametrize(
    "mutate, fragment",
    [
        # top level
        (lambda d: d.update(workloads=d.pop("workload")), "scenario: unknown keys ['workloads']"),
        (lambda d: d.update(duration="long"), "scenario.duration: expected a number, got 'long'"),
        (lambda d: d.update(seed=-1), "scenario.seed: must be >= 0"),
        # cluster
        (
            lambda d: d["cluster"].update(cpus="16"),
            "scenario.cluster: unknown keys ['cpus']",
        ),
        (
            lambda d: d.update(cluster=["n1"]),
            "scenario.cluster: expected an object, got list",
        ),
        (
            lambda d: d["cluster"].update(nodes="four"),
            "scenario.cluster.nodes: expected a number, got 'four'",
        ),
        # autoscaler
        (
            lambda d: d.update(autoscaler={"lag": 30}),
            "scenario.autoscaler: unknown keys ['lag']",
        ),
        # workload
        (
            lambda d: d["workload"].update(arrival={"rate_per_min": 2}),
            "scenario.workload: unknown keys ['arrival']",
        ),
        (
            lambda d: d["workload"].update(process="weibull"),
            "scenario.workload.process: unknown process 'weibull'",
        ),
        (
            lambda d: d["workload"].update(executors={"lo": 1}),
            "scenario.workload.executors: unknown keys ['lo']",
        ),
        (
            lambda d: d["workload"].update(executors={"min": 4, "max": 1}),
            "scenario.workload.executors: max 1 < min 4",
        ),
        (
            lambda d: d["workload"].update(lifetime={"min": "60"}),
            "scenario.workload.lifetime.min: expected a number",
        ),
        (
            lambda d: d["workload"].update(dynamic_fraction=1.5),
            "scenario.workload.dynamic_fraction: must be <= 1.0",
        ),
        (
            lambda d: d["workload"].update(trace=42),
            "scenario.workload.trace: expected a path string",
        ),
        # faults
        (
            lambda d: d.update(faults={"at": 1}),
            "scenario.faults: expected a list, got dict",
        ),
        (
            lambda d: d.update(faults=["node_kill"]),
            "scenario.faults[0]: expected an object, got str",
        ),
        (
            lambda d: d.update(faults=[{"at": 1, "kind": "meteor_strike"}]),
            "scenario.faults[0].kind: unknown fault kind 'meteor_strike'",
        ),
        (
            lambda d: d.update(faults=[{"at": 1}]),
            "scenario.faults[0]: missing required key 'kind'",
        ),
        (
            lambda d: d.update(faults=[{"kind": "failover"}]),
            "scenario.faults[0]: missing required key 'at'",
        ),
        (
            lambda d: d.update(
                faults=[{"at": 1, "kind": "failover"}, {"at": -5, "kind": "node_kill"}]
            ),
            "scenario.faults[1].at: must be >= 0",
        ),
        (
            lambda d: d.update(faults=[{"at": 1, "kind": "node_kill", "nodes": 2}]),
            "scenario.faults[0]: unknown keys ['nodes']",
        ),
        # policy / ha blocks
        (
            lambda d: d.update(policy=["priority"]),
            "scenario.policy: expected an object, got list",
        ),
        (lambda d: d.update(ha=True), "scenario.ha: expected an object, got bool"),
    ],
)
def test_actionable_validation_errors(mutate, fragment):
    d = _base()
    mutate(d)
    with pytest.raises(ScenarioError) as exc:
        Scenario.from_dict(d)
    assert fragment in str(exc.value), str(exc.value)


def test_scenario_with_concurrent_key_is_rejected():
    """The ``concurrent`` block went with the engine it configured: a
    scenario that still carries it fails up front like any unknown key,
    instead of running with the block silently ignored."""
    d = _base()
    d["concurrent"] = {"enabled": True}
    with pytest.raises(ScenarioError) as exc:
        Scenario.from_dict(d)
    assert "scenario: unknown keys ['concurrent']" in str(exc.value)
    assert "'concurrent'" not in str(exc.value).split("(known:")[1]


def test_non_dict_scenario():
    with pytest.raises(ScenarioError, match="scenario: expected an object, got list"):
        Scenario.from_dict([])


def test_valid_scenario_still_parses():
    sc = Scenario.from_dict(_base())
    assert sc.cluster.nodes == 4
    assert sc.faults[0].kind == "node_kill"
    # round-trip: to_dict() output is itself a valid scenario document
    again = Scenario.from_dict(json.loads(json.dumps(sc.to_dict())))
    assert again.to_dict() == sc.to_dict()


def test_sim_cli_writes_run_manifest(tmp_path, capsys):
    """Satellite: every ``sim --out`` directory carries a
    run_manifest.json naming the seed, the event/scenario digests, and
    a sha256 per sibling artifact — a sim run is auditable without the
    command line that produced it."""
    import hashlib

    from k8s_spark_scheduler_tpu.sim.__main__ import main as sim_main
    from k8s_spark_scheduler_tpu.sim.manifest import MANIFEST_NAME, MANIFEST_SCHEMA

    scenario = tmp_path / "tiny.json"
    scenario.write_text(
        json.dumps(
            {
                "name": "manifest-probe",
                "seed": 5,
                "duration": 120,
                "cluster": {"nodes": 2},
                "workload": {"process": "poisson", "rate_per_min": 2},
            }
        )
    )
    out = tmp_path / "out"
    assert sim_main(["--scenario", str(scenario), "--out", str(out), "--quiet"]) == 0
    capsys.readouterr()

    manifest = json.loads((out / MANIFEST_NAME).read_text())
    assert manifest["schema"] == MANIFEST_SCHEMA
    assert manifest["kind"] == "sim-run"
    assert manifest["seed"] == 5
    assert manifest["scenario"] == "manifest-probe"
    assert set(manifest["digests"]) == {"events", "scenario"}
    summary = json.loads((out / "summary.json").read_text())
    assert manifest["digests"]["events"] == summary["digest"]

    listed = {a["name"]: a for a in manifest["artifacts"]}
    assert {"events.jsonl", "summary.json", "scorecard.json"} <= set(listed)
    assert MANIFEST_NAME not in listed  # never hashes itself
    for name, entry in listed.items():
        body = (out / name).read_bytes()
        assert hashlib.sha256(body).hexdigest() == entry["sha256"], name
        assert entry["bytes"] == len(body)


def test_every_bundled_example_scenario_validates():
    """The examples are the documentation — they must stay inside the
    validated key sets (and validation must stay permissive enough for
    every shipped scenario: chaos, degraded, failover, preemption)."""
    paths = sorted(glob.glob(str(REPO / "examples" / "sim" / "*.json")))
    assert len(paths) >= 4
    for path in paths:
        sc = Scenario.from_file(path)
        assert sc.duration > 0, path
