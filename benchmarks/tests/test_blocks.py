"""Every seed gives the same amount of work, differently arranged."""

import json
import os
from collections import Counter
from itertools import islice

import pytest

import plugins

stratified = plugins.load("generators", "stratified")
NOW = stratified.BACKLOG_AGE_S  # so that the oldest pending driver was created at 0

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 7, 2**31 + 11, 3_000_000_017)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "fifo10k-tightly.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["drivers", "spark-mix"])
def mix(request):
    with open(os.path.join(BENCH, "traffic", request.param + ".json")) as f:
        return json.load(f)


def test_every_block_of_every_seed_holds_the_same_work(config, mix):
    totals = set()
    for seed in SEEDS:
        for block in islice(stratified.blocks(config, mix, seed, 0.0), 12):
            assert len(block) == mix["block_gangs"]
            totals.add((len(block), sum(g.executors for g in block)))
            lo, hi = config["gang"]["executors"]
            assert all(lo <= g.executors <= hi for g in block)
            # one gang from each equal-width stratum of the executor count
            assert sorted((g.executors - lo) // 4 for g in block) == list(range(8))
            for g in block:
                assert config["gang"]["executor_cpu"][0] <= g.executor_cpu <= config["gang"]["executor_cpu"][1]
                assert config["gang"]["executor_mem_gi"][0] <= g.executor_mem_gi <= config["gang"]["executor_mem_gi"][1]
    assert len(totals) == 1  # same pods and same executor total, whatever the seed and block


def test_seeds_differ_in_arrangement_not_in_amount(config, mix):
    first = [next(stratified.blocks(config, mix, seed, 0.0)) for seed in SEEDS]
    shapes = {tuple((g.executors, g.executor_cpu, g.executor_mem_gi) for g in b) for b in first}
    assert len(shapes) == len(SEEDS)
    again = next(stratified.blocks(config, mix, SEEDS[1], 0.0))
    assert again == first[1]  # the same seed gives the same stream


def test_every_executor_count_of_the_range_is_reached(config, mix):
    seen = {g.executors for b in islice(stratified.blocks(config, mix, 5, 0.0), 200) for g in b}
    assert seen == set(range(1, 32))


def test_clusters_share_their_histograms(config):
    made = [stratified.make_cluster(config, seed, NOW) for seed in SEEDS[:3]]
    for hist in (
        lambda c: Counter(c.cpu.tolist()),
        lambda c: Counter(c.mem_gi.tolist()),
        lambda c: Counter(g.executors for g in c.backlog),
        lambda c: Counter(g.executor_cpu for g in c.backlog),
        lambda c: Counter(g.executor_mem_gi for g in c.backlog),
    ):
        assert hist(made[0]) == hist(made[1]) == hist(made[2])
    assert made[0].cpu.tolist() != made[1].cpu.tolist()
    c = made[0]
    assert len(c.names) == 10_000 and len(c.backlog) == 1_000
    assert (c.cpu.min(), c.cpu.max(), c.mem_gi.min(), c.mem_gi.max()) == (4, 95, 8, 255)
    created = [g.created for g in c.backlog]
    assert created == sorted(created) and len(set(created)) == len(created)
    newest = next(stratified.blocks(config, {"block_gangs": 8}, 1, c.base_ts))
    assert min(g.created for g in newest) > max(created)  # every new driver is behind the backlog
    # the source's ages: 10,000 s old at the head, a second apart
    assert (c.base_ts, created[0], created[1] - created[0]) == (0.0, 0.0, 1.0)
