"""The trace reduction, on a trace recorded on a TPU v5e chip (a key-set
run of the gap-urand cell cut to scale 12, ``--trace 1``) and on
hand-made intervals.

    python -m pytest bench/tests
"""
import os

import pytest

import run

tr = run.tr
RECORDED = os.path.join(run.HERE, "testdata", "keysets-scale12.xplane.pb")


def test_recorded_chip_trace_reduces_to_the_window():
    host, devices = tr.read(RECORDED)
    assert list(devices) == ["/device:TPU:0"]
    assert len(host[tr.WINDOW]) == 1 and len(host["bench.submit"]) == 4
    red = tr.reduce(host, devices)
    lo, hi = host[tr.WINDOW][0]
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["idle_share"] == pytest.approx(
        1 - red["busy_s"] / red["window_s"])
    # busy is a union: never more than the sum of the ops inside the window
    inside = sum(min(e, hi) - max(s, lo) for s, e, _ in
                 devices["/device:TPU:0"] if e > lo and s < hi)
    assert red["busy_s"] <= inside * 1e-9 + 1e-12
    assert 0 < len(red["device_ops"]) <= tr.TOP
    # innermost ops only: their sum cannot pass the busy time
    assert sum(v for _, v in red["device_ops"]) <= red["busy_s"] + 1e-9
    assert all(" " not in name for name, _ in red["device_ops"])
    gaps = [s for _, s in red["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= tr.TOP
    assert {name for name, _ in red["idle_gaps"]} <= {"bench.submit", "idle"}


def test_reduce_takes_union_gaps_and_names_them():
    s = 1_000_000_000     # ns
    host = {tr.WINDOW: [(0, 10 * s)], "bench.poll": [(4 * s, 6 * s)],
            "bench.generator": [(8 * s, 10 * s)]}
    ops = [(1 * s, 3 * s, "fusion.1"), (2 * s, 4 * s, "while.2"),
           (6 * s, 8 * s, "while.4"), (6 * s, 7 * s, "fusion.1"),
           (7 * s, 8 * s, "fusion.1"), (9 * s, 12 * s, "copy.3")]
    devices = {"/device:TPU:0": ops, "/device:TPU:1": [(0, 10 * s, "x")]}
    red = tr.reduce(host, devices)
    # chip 0: busy 1-4, 6-8, 9-10 = 6 s; chip 1: 10 s; mean 8 s
    assert red["busy_s"] == pytest.approx(8.0)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["idle_share"] == pytest.approx(0.2)
    assert red["idle_gaps"] == [["bench.poll", pytest.approx(2.0)],
                                ["idle", pytest.approx(1.0)],
                                ["bench.generator", pytest.approx(1.0)]]
    top = dict(red["device_ops"])
    # while.4 holds the two fusions of its body: only they count
    assert "while.4" not in top
    assert top["x"] == pytest.approx(5.0) and top["fusion.1"] == \
        pytest.approx(2.0)


def test_reduce_finds_nothing_without_a_window_or_device_ops():
    assert tr.reduce({}, {"/device:TPU:0": [(0, 1, "a")]}) is None
    assert tr.reduce({tr.WINDOW: [(0, 10)]}, {}) is None
    assert tr.op_name("%fusion.2 = s32[4] fusion(x)") == "fusion.2"
