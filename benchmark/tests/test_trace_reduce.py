"""trace_reduce: busy time is a union (overlapping ops count once), idle gaps
carry the host span they fall in, and a trace recorded on the chip
(testdata/tpu_small.xplane.pb, benchmark/tests/record_trace.py) reduces."""

import os
import shutil

import pytest

from benchmark.trace_reduce import gaps, reduce_events, reduce_trace, union

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_merges_overlaps():
    assert union([(0, 10), (5, 15), (20, 30), (30, 31)]) == [(0, 15),
                                                               (20, 31)]


def test_gaps_within_window():
    assert gaps([(10, 20), (30, 40)], 0, 50) == [(0, 10), (20, 30), (40, 50)]
    assert gaps([(-5, 5), (45, 60)], 0, 50) == [(5, 45)]


def test_busy_counts_overlapping_ops_once():
    ops = [(100, 300, "fusion.1"), (200, 400, "fusion.2"),
           (700, 800, "fusion.1")]
    host = [(400, 650, "create"), (650, 700, "feed")]
    r = reduce_events([ops], host, 0, 1000)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    per = dict(r["device_ops"])
    assert per["fusion.1"] == pytest.approx(300e-9)
    assert per["fusion.2"] == pytest.approx(200e-9)
    # gaps: [400,700) mostly under `create`; [0,100) and [800,1000) none
    assert r["idle_gaps"][0] == ["create", pytest.approx(300e-9)]
    assert {g[0] for g in r["idle_gaps"][1:]} == {"other"}


def test_busy_is_averaged_over_devices():
    r = reduce_events([[(0, 500, "a")], [(0, 100, "a")]], [], 0, 1000)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["devices"] == 2


def test_recorded_chip_trace(tmp_path):
    src = os.path.join(TESTDATA, "tpu_small.xplane.pb")
    shutil.copy(src, tmp_path / "t.xplane.pb")
    r = reduce_trace(str(tmp_path), ("create", "feed"))
    assert r is not None and r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["device_ops"][0][1] > 0
    # the host sleeps inside `create` (4 ms) and `feed` (2 ms) leave the
    # device idle: the longest gaps carry those names
    labels = [name for name, _ in r["idle_gaps"][:8]]
    assert labels[:4].count("create") == 4
    assert "feed" in labels
