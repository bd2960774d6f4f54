"""The benchmark refuses to run anywhere but on the chips a cell asks
for, and prints no result line when it refuses."""
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run  # noqa: E402

ARGS = ["--workload", "dp_suite_drain", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def _run(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS],
                          cwd=cwd, capture_output=True, text=True,
                          env=env, timeout=300)


def test_gate_refuses_the_cpu_in_process():
    with pytest.raises(run.GateError, match="no TPU"):
        run.device_gate(1)
    with pytest.raises(run.GateError, match="no TPU"):
        run.device_gate(4)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.GateError, match="peaks.json"):
        run.peaks_for("TPU v99")
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_command_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode == 1
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_command_refuses_an_unknown_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "nope", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, capture_output=True, text=True, env=env,
                       timeout=300)
    assert p.returncode == 2 and p.stdout.strip() == ""
