"""``correct`` comes out false when the timed path is broken underneath:
the whole run after the device gate, at a tiny size on the CPU, with
one fault planted in the fleet's compiled dispatch at a time.

* ``unchanged``: every core returns its state as it came in;
* ``half``: the second half of every batch is left out (returned as it
  came in), the first half run;
* ``altered``: one answer per batch is altered where it is produced;
* ``exchange`` (four devices): a megabatch slab keeps only the first
  device's share, the other devices' rows never come back.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.tests import bench_tiny  # noqa: E402
from repro.core import blockc  # noqa: E402
from repro.fleet import scheduler  # noqa: E402


def _dispatch(monkeypatch, fault):
    """Break the compiled dispatch: ``fault(inputs, outputs)``."""
    real = blockc.CompiledProgram.run_light_dev

    def broken(self, shared, tdx_dim, device=None):
        out, cycles, halted = real(self, shared, tdx_dim, device)
        return fault(jnp.asarray(shared, jnp.uint32), out), cycles, halted

    monkeypatch.setattr(blockc.CompiledProgram, "run_light_dev", broken)


def unchanged(monkeypatch):
    _dispatch(monkeypatch, lambda shared_in, shared_out: shared_in)


def altered(monkeypatch):
    _dispatch(monkeypatch, lambda shared_in, shared_out: shared_out.at[0].set(
        shared_out[0] ^ jnp.uint32(0x00400000)))


def half(monkeypatch):
    """Of the jobs a batch really carries, the second half never run:
    their rows come back unwritten."""
    real_collect = scheduler.FleetScheduler._collect_light

    def collect(self, cp, shared_dev, batch, real, wall, results):
        out = np.array(shared_dev)
        out[real // 2:real] = 0
        return real_collect(self, cp, out, batch, real, wall, results)

    monkeypatch.setattr(scheduler.FleetScheduler, "_collect_light",
                        collect)


@pytest.fixture(scope="module")
def spec_root(tmp_path_factory):
    return bench_tiny.write_spec(tmp_path_factory.mktemp("spec"))


@pytest.mark.parametrize("driver", ["drain", "serve"])
def test_sound_run_is_correct(spec_root, driver):
    line = bench_tiny.run(driver, spec_root)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("driver", ["drain", "serve"])
@pytest.mark.parametrize("fault", [unchanged, half, altered],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(spec_root, monkeypatch, driver, fault):
    fault(monkeypatch)
    line = bench_tiny.run(driver, spec_root)
    assert not line["correct"], line["checks"]


def test_exchange_left_out_on_four_devices_is_not_correct(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, str(pathlib.Path(__file__).with_name(
            "bench_fault_4dev.py")), str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out
    assert out["sound"]["mesh_jobs"] > 0 and out["sound"]["lane_jobs"] > 0
    assert not out["exchange"]["correct"], out
