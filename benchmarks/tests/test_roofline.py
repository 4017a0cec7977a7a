"""roofline.py's counts against a hand count at 1,024 x 64."""

import pytest

import roofline


def test_ops_and_bytes_at_1024_by_64():
    # 15 integer operations per (app, node) under tightly-pack, 23 under min-frag
    assert roofline.queue_pass_ops("tightly-pack", 1024, 64) == 15 * 1024 * 64 == 983_040
    assert roofline.queue_pass_ops("minimal-fragmentation", 1024, 64) == 23 * 65_536 == 1_507_328
    # read 3 int32 per node + 5 per app, write 2 per node + 1 per app
    assert roofline.queue_pass_bytes(1024, 64) == 4 * (3 * 1024 + 5 * 64) + 4 * (2 * 1024 + 64) == 22_016


def test_least_time_names_its_bound():
    least = roofline.least_seconds("tightly-pack", 10_240, 1_024, "TPU v5 lite")
    assert least["compute_s"] == pytest.approx(15 * 10_240 * 1_024 / 197e12)
    assert least["memory_s"] == pytest.approx(roofline.queue_pass_bytes(10_240, 1_024) / 819e9)
    assert least["seconds"] == max(least["compute_s"], least["memory_s"])
    assert least["bound"].startswith("compute")


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        roofline.least_seconds("tightly-pack", 1024, 64, "TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.peaks_for("cpu")
