"""``chip_smoke.py`` on the CPU: its device gate refuses the host, its
phases pass their own checks at a tiny size, and the compile-cache
helper it calls picks the right directory."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.core import EGPUConfig
from repro.fleet import enable_compile_cache
from repro.fleet.devices import DEFAULT_COMPILE_CACHE
from repro.programs import (build_bitonic, build_fft, build_matmul,
                            build_reduction, build_transpose)

ROOT = pathlib.Path(__file__).resolve().parents[1]

CFG = EGPUConfig(max_threads=64, regs_per_thread=32, shared_kb=32,
                 alu_bits=32, shift_bits=32, predicate_levels=2,
                 has_dot=True, has_invsqr=True)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def refs(smoke):
    return smoke.References(jax.devices("cpu")[0])


def test_gate_refuses_the_cpu_in_process(smoke, capsys):
    assert smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


def test_script_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env,
                       timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU" in p.stderr


def test_gate_refuses_four_chips_of_cpu(smoke):
    with pytest.raises(smoke.SmokeError, match="no TPU"):
        smoke.device_gate(4)


def test_batch_phase_runs_every_tier(smoke, refs, capsys):
    rep = smoke.batch_phase(
        "tiny", CFG,
        [build_reduction(CFG, 32), build_transpose(CFG, 16),
         build_bitonic(CFG, 16)],
        pair=[build_reduction(CFG, 16)],
        singles=[build_matmul(CFG, 8), build_reduction(CFG, 32,
                                                       use_dot=True)],
        refs=refs, batch_size=4)
    assert rep["jobs"] == 3 * 4 + 2 + 2
    assert rep["tiers"] == {"superblock": 12, "blocks": 2, "interp": 2}
    out = capsys.readouterr().out
    assert "bitonic_16_dp: integer, bit-identical to the CPU" in out
    assert "reduction_32_dp: float, max ULP vs CPU 0" in out


def test_serve_phase(smoke, refs):
    rep = smoke.serve_phase(
        "tiny-serve", CFG, [build_reduction(CFG, 32), build_fft(CFG, 32)],
        8, refs=refs, batch_size=4)
    assert rep["jobs"] == 8


def test_multichip_phase_on_the_visible_devices(smoke):
    rep = smoke.multichip_phase(
        CFG, mega=[build_reduction(CFG, 32)],
        mix=[build_transpose(CFG, 16), build_reduction(CFG, 16),
             build_reduction(CFG, 32, use_dot=True), build_bitonic(CFG, 16)],
        batch_size=4)
    slab = 4 * len(jax.devices())
    per = min(4, slab - 1)          # jobs per mix program: under one slab
    assert rep["jobs"] == slab + 4 * per + len(range(0, 4 * per, 3))


def test_phase_check_fails_on_a_wrong_result(smoke, refs):
    b = build_reduction(CFG, 32)
    st = refs.chip(b)
    bad = st._replace(cycles=st.cycles + 1)

    class R:
        hazard_violations, tier = 0, "superblock"
        cycles, steps = int(bad.cycles), int(st.steps)
        stat_cycles, stat_instrs = st.stat_cycles, st.stat_instrs

        def shared_u32(self):
            return st.shared

    with pytest.raises(smoke.SmokeError, match="cycles differs"):
        smoke.same_result(R(), st, "tiny")


def test_max_ulp_counts_representable_steps(smoke):
    import numpy as np
    a = np.array([1.0, -0.0, -2.0], np.float32)
    b = np.array([np.nextafter(np.float32(1), np.float32(2)), 0.0,
                  np.nextafter(np.float32(-2), np.float32(0))], np.float32)
    assert smoke.max_ulp(a, a) == 0
    assert smoke.max_ulp(a, b) == 1


@pytest.fixture
def cache_config():
    """Restore JAX's cache settings after a test changes them."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])
        cc.reset_cache()


def test_cache_helper_honours_the_environment(cache_config, monkeypatch,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_cache_helper_defaults_to_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(ROOT / ".jax_cache") == str(DEFAULT_COMPILE_CACHE)
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_result_line_is_one_json_object(smoke, monkeypatch, capsys):
    """With the gate and phases stubbed, the last stdout line is exactly
    the JSON object the contract asks for."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(smoke, "device_gate", lambda chips: [Dev()])
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "/c")
    monkeypatch.setattr(smoke, "run_one_chip", lambda: None)
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert last == ('{"ok": true, "device": {"platform": "tpu", '
                    '"kind": "TPU v5 lite", "count": 1}}')
