"""The plain reference against the program's host oracle (the reference
policies in exact Quantity arithmetic, ``ops/packers.py``) served over
HTTP, at a size the oracle answers in seconds.  The reference imports
nothing of the program; this test is where the two meet."""

import json
import os
import time

import pytest

import plugins
import stack as stack_mod
import traffic as traffic_mod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_reference_imports_nothing_of_the_program():
    plain = ["blocks.py", "packing.py", "check.py", "roofline.py", "trace_reduce.py", "traffic.py", "plugins.py"]
    for kind in ("references", "policies", "generators"):
        plain += [os.path.join(kind, f) for f in os.listdir(os.path.join(BENCH, kind)) if f.endswith(".py")]
    assert len(plain) >= 11
    for name in plain:
        with open(os.path.join(BENCH, name)) as f:
            assert "k8s_spark_scheduler_tpu" not in f.read(), name


@pytest.mark.parametrize("policy", ["tightly-pack", "minimal-fragmentation"])
@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_reference_answers_as_the_host_oracle_does(policy, seed):
    with open(os.path.join(BENCH, "configs", "fifo10k-tightly.json")) as f:
        config = json.load(f)
    config["cluster"].update(nodes=96, backlog=12)
    with open(os.path.join(BENCH, "traffic", "spark-mix.json")) as f:
        mix = json.load(f)
    generator = plugins.load("generators", config["generator"])
    objects = plugins.load("objects", config["objects"])
    cluster = generator.make_cluster(config, seed, time.time())
    stream = generator.blocks(config, mix, seed, cluster.base_ts)
    reference = plugins.load("references", config["reference"]["model"]).Reference(cluster, policy)
    # a host policy: no device lane
    oracle = stack_mod.start_stack(cluster, objects, {"binpack_algo": policy, "fifo": True})
    try:
        client = stack_mod.Client(oracle, cluster.names)
        rec = traffic_mod.run_block(client, objects, next(stream), mix["steps"])
    finally:
        oracle.stop()
    granted = 0
    for g in rec.gangs:
        grant = reference.filter_driver(g.gang)
        want = (grant.driver_node, grant.executor_nodes) if grant else None
        assert g.read["reservation"] == want, g.gang
        assert g.read["api_reservation"] == want, g.gang
        if grant:
            granted += 1
            for answer in g.answers["executor"]:
                node = reference.filter_executor(g.gang, cluster.names)
                assert json.loads(answer[2])["NodeNames"] == [node]
        reference.retire(g.gang)
    assert granted >= 1
