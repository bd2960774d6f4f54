"""The reduction from a device trace to busy time, idle share, gaps and
device ops, on a small trace recorded on a TPU v5e (the first 30 ms of
a traced ``dp_suite_drain`` window) and on hand-made ones."""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import tracing  # noqa: E402

RECORDED = ROOT / "bench" / "tests" / "data" / \
    "recorded_dp_suite_drain_30ms.json"


def host(*spans):
    return {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [list(s) for s in spans]}]}


def device(i, modules, ops=()):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [list(m) for m in modules]},
        {"name": "XLA Ops", "events": [list(o) for o in ops]}]}


def test_union_merges_and_clips():
    assert tracing.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12) == \
        [(1, 4), (5, 8), (9, 12)]
    assert tracing.union([(0, 1)], 2, 3) == []


def test_busy_idle_and_gaps_on_a_hand_made_trace():
    planes = [
        host(("bench.window", 0, 100), ("bench.submit", 0, 30),
             ("bench.drain", 30, 70)),
        device(0, [("m", 10, 20), ("m", 25, 40), ("m", 90, 20)],
               [("op.a", 10, 15), ("op.b", 25, 40), ("op.a", 95, 5)]),
        device(1, [("m", 0, 50)]),
        {"name": "/device:CUSTOM:Megascale Trace", "lines": []},
    ]
    r = tracing.reduce(planes)
    assert r["window_s"] == pytest.approx(100e-9)
    # device 0: [10, 65) and [90, 100) -> 65 ns; device 1: 50 ns
    assert r["busy_s_per_device"] == pytest.approx([65e-9, 50e-9])
    assert r["busy_s"] == pytest.approx(57.5e-9)
    assert r["device_ops"][0] == ["op.b", pytest.approx(40e-9)]
    assert r["device_ops"][1] == ["op.a", pytest.approx(20e-9)]
    gaps = {(lbl, round(s * 1e9)) for lbl, s in r["idle_gaps"]}
    assert gaps == {("bench.drain", 50), ("bench.drain", 25),
                    ("bench.submit", 10)}


def test_no_device_plane_reads_nothing():
    assert tracing.reduce([host(("bench.window", 0, 10))]) is None


def test_no_window_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tracing.reduce([host(("bench.drain", 0, 10)),
                        device(0, [("m", 0, 5)])])


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def test_recorded_trace_busy_time_matches_a_brute_force_count(recorded):
    r = tracing.reduce(recorded)
    lo, hi = tracing.window_of(recorded)
    assert r["window_s"] == pytest.approx(0.030)
    grid = np.zeros(hi - lo, bool)              # one cell per ns
    dev = tracing.device_planes(recorded)[0]
    for s, e in tracing.busy_intervals(dev):
        grid[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    assert r["busy_s"] == pytest.approx(grid.sum() / 1e9, abs=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0 < idle < 1
    assert sum(g for _, g in r["idle_gaps"]) <= (r["window_s"] - r["busy_s"]
                                                 + 1e-12)


def test_recorded_trace_gaps_and_ops(recorded):
    r = tracing.reduce(recorded)
    assert 0 < len(r["idle_gaps"]) <= tracing.TOP
    assert {lbl for lbl, _ in r["idle_gaps"]} <= {
        "other", "bench.submit", "bench.drain", "bench.collect",
        "bench.inputs"}
    secs = [s for _, s in r["idle_gaps"]]
    assert secs == sorted(secs, reverse=True)
    ops = [s for _, s in r["device_ops"]]
    assert ops == sorted(ops, reverse=True) and len(ops) == tracing.TOP
