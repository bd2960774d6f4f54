"""The program's spans and kernels in a device trace: ``idle_by_stage``,
tier-kernel time, ``device_modules``, the readers of
``bench/stages.py``, and the attribution tool, on hand-made traces, on
a tiny cell on the CPU, on two traces recorded on a TPU v5e (the first
30 ms of a traced ``dp_suite_drain`` and ``dp_short_serve`` window),
and on an older recorded trace of a program without the spans, which
reads ``None``."""
import json
import pathlib
import random
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import attribute, stages, tracing  # noqa: E402

DATA = ROOT / "bench" / "tests" / "data"
OLD = DATA / "recorded_dp_suite_drain_30ms.json"
RECORDED = {"drain": DATA / "recorded_dp_suite_drain_egpu_30ms.json",
            "serve": DATA / "recorded_dp_short_serve_egpu_30ms.json"}


def host(*lines):
    return {"name": "/host:CPU", "lines": [
        {"name": f"thread{i}", "events": [list(s) for s in spans]}
        for i, spans in enumerate(lines)]}


def device(i, modules):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [list(m) for m in modules]}]}


K1 = "jit_egpu_superblock_0a1b2c3d(123)"
K2 = "jit_egpu_interp(77)"


def hand_made():
    """Window [0, 100).  Main thread: drain [10, 90) holding dispatch
    [20, 30), device_sync [30, 50) and collect [50, 70); client time
    outside the drain.  A second thread sits in serve.wait [0, 100).
    Device 0 runs [25, 45) and [60, 65); device 1 runs [0, 100)."""
    return [
        host([("bench.window", 0, 100), ("egpu.drain", 10, 80),
              ("egpu.dispatch", 20, 10), ("egpu.device_sync", 30, 20),
              ("egpu.collect", 50, 20), ("bench.drain", 10, 80)],
             [("egpu.serve.wait", 0, 100)]),
        device(0, [(K1, 25, 20), ("jit_other(5)", 60, 5)]),
        device(1, [(K2, 0, 100)]),
    ]


def test_idle_by_stage_on_a_hand_made_trace():
    idle = stages.idle_by_stage(hand_made())
    # device 0 idles [0, 25), [45, 60), [65, 100); device 1 never.  The
    # second thread's wait began first, so the main thread's spans are
    # innermost wherever they run.
    # [0, 25): wait 10, drain 10, dispatch 5; [45, 60): device_sync 5,
    # collect 10; [65, 100): collect 5, drain 20, wait 10
    want = {"serve.wait": 20, "drain": 30, "dispatch": 5,
            "device_sync": 5, "collect": 15}
    assert idle == pytest.approx({k: v / 1e9 for k, v in want.items()})
    assert list(idle) == sorted(idle, key=lambda k: -idle[k])


def test_idle_is_none_where_no_span_covers_it():
    planes = hand_made()
    planes[0]["lines"].pop()            # no serve.wait thread
    idle = stages.idle_by_stage(planes)
    assert idle[stages.NONE] == pytest.approx(20e-9)   # [0, 10), [90, 100)
    assert sum(idle.values()) == pytest.approx(75e-9)


def test_modules_on_a_hand_made_trace():
    m = stages.modules(hand_made())
    assert m["kernel_s_per_device"] == pytest.approx([20e-9, 100e-9])
    assert m["device_modules"] == [
        ["jit_egpu_interp", pytest.approx(100e-9)],
        ["jit_egpu_superblock_0a1b2c3d", pytest.approx(20e-9)],
        ["jit_other", pytest.approx(5e-9)]]


def test_a_trace_without_program_spans_or_kernels_reads_none():
    planes = [host([("bench.window", 0, 100), ("bench.drain", 0, 50)]),
              device(0, [("jit_run(8)", 10, 20)])]
    assert stages.idle_by_stage(planes) is None
    assert stages.modules(planes) is None
    red = dict(tracing.reduce(planes), **stages.reduce(planes))
    assert red["idle_by_stage"] is None
    assert red["kernel_s_per_device"] is None
    assert red["device_modules"] is None
    ctx = {"batch_size": 32, "trace": red, "lane_steps": 1000,
           "registry": {"batches": 4, "dispatched_jobs": 9,
                        "collect_s": None, "queue_wait_s": None,
                        "compile_misses": None}}
    assert all(read(ctx) is None for read in stages.METRICS.values())


def test_the_older_recorded_trace_reads_none():
    planes = json.loads(OLD.read_text())
    assert stages.reduce(planes) == {"idle_by_stage": None,
                                     "kernel_s_per_device": None,
                                     "device_modules": None}


def test_stage_pieces_match_a_brute_force_labelling():
    rng = random.Random(5)
    for _ in range(20):
        spans = []
        for _ in range(rng.randint(1, 12)):
            s = rng.randrange(0, 200)
            spans.append((f"s{len(spans)}", s, s + rng.randint(1, 80)))
        lo, hi = 20, 180
        pieces = stages.stage_pieces(spans, lo, hi)
        assert pieces[0][0] == lo and pieces[-1][1] == hi
        for (_, b, _), (a, _, _) in zip(pieces, pieces[1:]):
            assert b == a
        for a, b, name in pieces:
            for t in range(a, b):
                cover = [sp for sp in spans if sp[1] <= t < sp[2]]
                want = (max(cover, key=lambda sp: (sp[1], -sp[2]))[0]
                        if cover else stages.NONE)
                assert name == want


def test_readers_on_a_known_context():
    red = dict(tracing.reduce(hand_made()), **stages.reduce(hand_made()))
    reg = {"batches": 4, "dispatched_jobs": 10, "collect_s": 0.002,
           "queue_wait_s": 0.3, "compile_misses": 0.0}
    ctx = {"batch_size": 32, "trace": red, "lane_steps": 60,
           "registry": reg}
    idle = red["idle_by_stage"]
    work = (idle["drain"] + idle["dispatch"] + idle["device_sync"]
            + idle["collect"])
    assert stages.read_idle_in_program_ms_per_batch(ctx) == \
        pytest.approx(1e3 * work / 4)
    assert stages.read_collect_ms_per_batch(ctx) == pytest.approx(0.5)
    assert stages.read_queue_wait_ms(ctx) == pytest.approx(30.0)
    assert stages.read_kernel_ns_per_lane_step(ctx) == \
        pytest.approx(1e9 * 120e-9 / 60)
    assert set(stages.METRICS) == {
        "idle_in_program_ms_per_batch.drain",
        "idle_in_program_ms_per_batch.serve",
        "collect_ms_per_batch.drain", "queue_wait_ms.serve",
        "kernel_ns_per_lane_step.drain"}


def test_registry_extra_and_its_delta():
    from repro.obs.metrics import MetricsRegistry
    reg = MetricsRegistry()
    assert stages.registry_extra(reg) == {
        "collect_s": None, "queue_wait_s": None, "compile_misses": None}
    reg.counter("fleet_collect_seconds_total")
    reg.counter("fleet_compile_cache_total", "", ("result",))
    a = stages.registry_extra(reg)
    reg.inc("fleet_collect_seconds_total", 0.25)
    reg.inc("fleet_compile_cache_total", result="miss")
    reg.inc("fleet_compile_cache_total", result="hit")
    d = stages.delta(a, stages.registry_extra(reg))
    assert d == {"collect_s": 0.25, "queue_wait_s": None,
                 "compile_misses": 1.0}


def test_the_kernel_pattern_is_the_programs():
    from repro.core.executor import KERNEL_MODULE_RE
    assert stages.KERNEL.pattern == KERNEL_MODULE_RE.pattern


def test_crop_keeps_the_first_ms_of_the_window():
    planes = [host([("bench.window", 0, 5_000_000),
                    ("egpu.drain", 100, 2_000_000),
                    ("egpu.collect", 1_500_000, 10), ("other", 0, 10)]),
              device(0, [(K1, 50, 10), (K1, 1_200_000, 10)])]
    out = attribute.crop(planes, 1.0)
    ev = {ln["name"]: ln["events"] for p in out for ln in p["lines"]}
    assert ev["thread0"] == [["bench.window", 0, 1_000_000],
                             ["egpu.drain", 100, 2_000_000]]
    assert ev["XLA Modules"] == [[K1, 50, 10]]
    assert tracing.window_of(out) == (0, 1_000_000)


# ------------------------------------------------------------------
# traces recorded on the chip, with the program's spans and kernels
# ------------------------------------------------------------------

@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request):
    return request.param, json.loads(RECORDED[request.param].read_text())


def test_recorded_spans_and_modules_are_named(recorded):
    kind, planes = recorded
    names = {n for n, _, _ in stages.program_spans(planes)}
    want = ({"drain", "partition", "batch", "residency", "dispatch",
             "device_sync", "collect"} if kind == "drain" else
            {"serve.cohort", "serve.dispatch", "serve.wait", "drain",
             "dispatch", "collect"})
    assert want <= names
    mods = stages.modules(planes)
    assert mods is not None and all(x > 0 for x in
                                    mods["kernel_s_per_device"])
    assert all(stages.KERNEL.fullmatch(n) for n, _ in mods["device_modules"])


def test_recorded_idle_by_stage_matches_a_brute_force_count(recorded):
    """Paint a 100 ns grid with each span in turn, innermost last, and
    count the idle cells under each label."""
    _, planes = recorded
    idle = stages.idle_by_stage(planes)
    lo, hi = tracing.window_of(planes)
    step = 100
    t = lo + step * np.arange((hi - lo) // step) + step // 2
    busy = np.zeros(t.size, bool)
    for s, e in tracing.busy_intervals(tracing.device_planes(planes)[0]):
        busy |= (t >= s) & (t < e)
    spans = sorted(stages.program_spans(planes),
                   key=lambda sp: (sp[1], -sp[2]))
    names = [stages.NONE] + [n for n, _, _ in spans]
    label = np.zeros(t.size, int)
    for i, (_, s, e) in enumerate(spans, 1):
        label[(t >= s) & (t < e)] = i
    got: dict = {}
    for i in label[~busy]:
        got[names[i]] = got.get(names[i], 0) + step / 1e9
    assert set(got) <= set(idle)
    edges = 2 * (len(spans) + len(tracing.busy_intervals(
        tracing.device_planes(planes)[0])))
    for name, v in idle.items():
        assert got.get(name, 0.0) == pytest.approx(v, abs=edges * step
                                                   / 1e9)
    red = tracing.reduce(planes)
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


# ------------------------------------------------------------------
# the attribution tool end to end, at a tiny size on the CPU
# ------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_spec(tmp_path_factory):
    from bench.tests import bench_tiny
    return bench_tiny.write_spec(tmp_path_factory.mktemp("spec"))


@pytest.mark.parametrize("driver", ["drain", "serve"])
def test_attribute_a_tiny_cell(driver, tiny_spec, tmp_path, monkeypatch):
    import jax
    from bench.drivers import drain, serve
    from bench.tests import bench_tiny
    monkeypatch.setattr(attribute, "RECORD_MS", 1000.0)   # the whole window
    before = (drain.registry_totals, serve.delta)
    rec = tmp_path / "rec.json"
    line = attribute.attribute(
        bench_tiny.cell(driver), seed=bench_tiny.SEED, seconds=0.6,
        devs=jax.devices()[:1], doc=bench_tiny.DOC,
        traffic=bench_tiny.traffic(driver), spec_root=tiny_spec,
        record=rec)
    assert (drain.registry_totals, serve.delta) == before   # restored
    assert line["correct"] is True
    assert line["registry"]["compile_misses"] == 0         # warm window
    assert line["registry"]["collect_s"] > 0
    m = line["metrics"]
    if driver == "drain":
        assert m["collect_ms_per_batch.drain"] > 0
        assert line["registry"]["queue_wait_s"] is None   # no service
    else:
        assert m["queue_wait_ms.serve"] > 0
    # the CPU has no device plane: the trace's readers find nothing
    assert line["idle_by_stage"] is None
    names = {n for n, _, _ in stages.program_spans(
        json.loads(rec.read_text()))}
    assert {"drain", "dispatch", "collect"} <= names
