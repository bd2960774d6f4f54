"""The per-layer readers' arithmetic, and that each reads nothing
(``None``) where its cell gives it nothing to read."""
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import manifest  # noqa: E402


def ctx(**kw):
    reg = {"wall_s": 2.0, "sync_s": 1.5, "batches": 100.0,
           "dispatches": 40.0, "dispatched_jobs": 320.0}
    reg.update(kw.pop("registry", {}))
    trace = {"window_s": 4.0, "busy_s": 3.0,
             "busy_s_per_device": [3.0], "device_ops": [],
             "idle_gaps": []}
    base = {"batch_size": 32, "registry": reg, "trace": trace,
            "lane_steps": 3_000_000_000}
    base.update(kw)
    return base


@pytest.mark.parametrize("name", ["host_ms_per_batch.drain",
                                  "host_ms_per_batch.serve"])
def test_host_ms_per_batch(name):
    read = manifest.metric_reader(name)
    assert read(ctx()) == pytest.approx(5.0)      # 0.5 s over 100
    assert read(ctx(registry={"batches": 0.0})) is None


def test_cohort_fill():
    read = manifest.metric_reader("cohort_fill.serve")
    assert read(ctx()) == pytest.approx(25.0)     # 320 / (40 * 32)
    assert read(ctx(registry={"dispatches": 0.0})) is None


def test_device_ns_per_lane_step_sums_devices():
    read = manifest.metric_reader("device_ns_per_lane_step.drain")
    assert read(ctx()) == pytest.approx(1.0)      # 3e9 ns / 3e9 steps
    four = ctx(trace={"window_s": 4.0, "busy_s": 2.0,
                      "busy_s_per_device": [1.0, 2.0, 2.0, 3.0]})
    assert read(four) == pytest.approx(8e9 / 3e9)
    assert read(ctx(trace=None)) is None
    assert read(ctx(lane_steps=None)) is None
    assert read(ctx(lane_steps=0)) is None


@pytest.mark.parametrize("name", ["device_idle_share.drain",
                                  "device_idle_share.serve"])
def test_device_idle_share(name):
    read = manifest.metric_reader(name)
    assert read(ctx()) == pytest.approx(25.0)     # 1 - 3 / 4
    assert read(ctx(trace=None)) is None


def test_every_per_layer_metric_has_a_reader():
    for m in manifest.load().per_layer:
        assert callable(manifest.metric_reader(m.name))
