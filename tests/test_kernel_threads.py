"""The compiled tiers trace the program's own thread count.

A program compiled for ``threads`` runtime threads runs its kernels on a
thread axis of ``threads``, not ``cfg.max_threads``: every state tensor,
LOD gather, STO scatter and DOT/SUM reduction is sized to the threads
the program uses.  The contract under test:

* every tier and runner stays bit-identical to ``run_program`` (which
  still runs at ``max_threads``), the full runners' padded registers and
  predicate state included;
* DOT/SUM keep the interpreter's ``+0.0`` where the masked-off
  wavefronts past ``threads`` would have added it (signed zeros);
* a program at ``max_threads`` lowers to exactly the text it did
  before the change;
* the fleet's lane-step counters read offered = traced on the compiled
  tiers.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Asm, EGPUConfig, compile_program, run_program
from repro.fleet import FleetScheduler
from repro.obs import Tracer
from repro.programs import (build_bitonic, build_fft, build_matmul,
                            build_reduction, build_transpose)

CFG = EGPUConfig(max_threads=64, regs_per_thread=32, shared_kb=8,
                 alu_bits=32, shift_bits=32, predicate_levels=4,
                 has_dot=True, has_invsqr=True)

#: programs that run fewer threads than ``CFG.max_threads``
NARROW = {
    "reduction_dot_32": lambda: build_reduction(CFG, 32, use_dot=True),
    "reduction_16": lambda: build_reduction(CFG, 16),
    "fft_32": lambda: build_fft(CFG, 32),
    "bitonic_32": lambda: build_bitonic(CFG, 32),
    "matmul_dot_16": lambda: build_matmul(CFG, 16, use_dot=True),
    "matmul_dot_32": lambda: build_matmul(CFG, 32, use_dot=True),
}

#: sha256 of ``jax.jit(cp.light_fn()).lower(...).as_text()`` for
#: programs at ``max_threads`` (batch of 3), recorded before the thread
#: axis was narrowed: their kernels must not move
WIDE_TEXT = {
    ("transpose", "superblock"):
        "49dd5fc5b5df673225bd06193b9f0931cc541288a58c0f07dbca89f5543df4cc",
    ("transpose", "blocks"):
        "65737a1436e5a86b609f403c257d45346060c249b1b239c2f39850db41f9e329",
    ("matmul", "superblock"):
        "5db064e393665e405d63461cffb8b612e9933abe6630a04a9ba39c8780ee90ff",
    ("matmul", "blocks"):
        "b8bd9e031dac6372d8778c710c2819c36cebad1bdf567d0e4e3d685e655c5385",
}
WIDE = {"transpose": lambda: build_transpose(CFG, 16),
        "matmul": lambda: build_matmul(CFG, 8)}

B = 3


def _lowered(cp, batch: int = B) -> str:
    return jax.jit(cp.light_fn()).lower(
        jax.ShapeDtypeStruct((batch, CFG.shared_words), jnp.uint32),
        jax.ShapeDtypeStruct((batch,), jnp.int32)).as_text()


def _inputs(b):
    """Three cores' shared images: the builder's data, reversed and
    rolled, so the batch rows differ."""
    x = np.asarray(b.shared_init)
    return [x, x[::-1].copy(), np.roll(x, 7)]


@pytest.mark.parametrize("runner", ["full", "light"])
@pytest.mark.parametrize("mode", ["superblock", "blocks"])
@pytest.mark.parametrize("name", sorted(NARROW))
def test_narrow_kernels_bit_identical(name, mode, runner):
    b = NARROW[name]()
    assert b.image.threads_active < CFG.max_threads
    cp = compile_program(b.image, mode=mode, batch_hint=B)
    assert cp.mode == mode
    assert cp.kernel_threads == b.image.threads_active
    xs = _inputs(b)
    refs = [run_program(b.image, shared_init=x, tdx_dim=b.tdx_dim)
            for x in xs]
    if runner == "full":
        got = cp.run_batch(xs, [b.tdx_dim] * B)
        for i, ref in enumerate(refs):
            for leaf in ref._fields:
                r = np.asarray(getattr(ref, leaf))
                g = np.asarray(getattr(got, leaf))[i]
                assert r.shape == g.shape, (leaf, r.shape, g.shape)
                assert np.array_equal(r, g), f"core {i}: {leaf} differs"
    else:
        sh, cyc, halted = cp.run_batch_light(xs, [b.tdx_dim] * B)
        for i, ref in enumerate(refs):
            assert np.array_equal(np.asarray(sh)[i], np.asarray(ref.shared))
            assert int(cyc[i]) == int(ref.cycles)
            assert bool(halted[i]) == bool(ref.halted)


def _zero_sum_program(threads: int):
    """DOT (against ``y = 1.0``) and SUM of ``x`` over ``threads`` lanes,
    written to words 300 and 301."""
    a = Asm(CFG)
    a.tdx(1)
    a.lod(2, 1, 0)                      # x[k]
    a.lod(3, 1, CFG.max_threads)        # y[k] = 1.0
    a.dot(4, 2, 3)
    a.sum_(5, 2)
    a.lodi(6, 0, tsc="mcu")
    a.sto(4, 6, 300, tsc="mcu")
    a.sto(5, 6, 301, tsc="mcu")
    a.stop()
    return a.assemble(threads_active=threads)


def _zero_sum_data(case: str):
    x = np.zeros(512, np.float32)
    x[CFG.max_threads:2 * CFG.max_threads] = 1.0
    if case == "negzero":               # every term -0.0
        x[:CFG.max_threads] = -0.0
    else:                               # each lane's two wavefronts sum
        x[:16] = -1.5e-38               # to a negative denormal, which a
        x[16:32] = 1.0e-38              # backend that flushes denormals
    return x                            # (the TPU) turns into -0.0


@pytest.mark.parametrize("case", ["negzero", "underflow"])
@pytest.mark.parametrize("mode", ["superblock", "blocks"])
@pytest.mark.parametrize("threads", [16, 32])
def test_signed_zero_dot_sum(threads, mode, case):
    """At full width the masked-off wavefronts add ``+0.0``: a ``-0.0``
    sum, or one flushed from a denormal, comes out ``+0.0``.  The
    compiled tiers must write the same without those wavefronts."""
    img, x = _zero_sum_program(threads), _zero_sum_data(case)
    ref = np.asarray(run_program(img, shared_init=x, tdx_dim=16).shared)
    if case == "negzero":
        assert np.array_equal(ref[300:302], [0, 0]), ref[300:302]
    cp = compile_program(img, mode=mode)
    assert cp.kernel_threads == threads
    full = cp.run(shared_init=x, tdx_dim=16)
    light, _, _ = cp.run_light(shared_init=x, tdx_dim=16)
    for got in (np.asarray(full.shared), np.asarray(light)):
        assert np.array_equal(got[300:302], ref[300:302]), got[300:302]
        assert np.array_equal(got, ref)


def _tensor_dims(text: str) -> set[int]:
    dims = set()
    for shape in re.findall(r"tensor<((?:\d+x)+)", text):
        dims.update(int(d) for d in shape.rstrip("x").split("x"))
    return dims


@pytest.mark.parametrize("mode", ["superblock", "blocks"])
def test_narrow_kernel_has_no_max_threads_axis(mode):
    """A 32-thread program's kernel holds no tensor on a ``max_threads``
    thread axis (nor the ``batch * max_threads`` flattened one); the
    batch of 5 keeps the flattened size clear of the program's own
    sizes (its padded length is 192)."""
    b = NARROW["matmul_dot_32"]()
    cp = compile_program(b.image, mode=mode, batch_hint=B)
    dims = _tensor_dims(_lowered(cp, 5))
    assert {5 * 32, 32} <= dims
    assert CFG.max_threads not in dims
    assert 5 * CFG.max_threads not in dims


@pytest.mark.parametrize("mode", ["superblock", "blocks"])
@pytest.mark.parametrize("name", sorted(WIDE))
def test_max_threads_kernel_text_unchanged(name, mode):
    b = WIDE[name]()
    cp = compile_program(b.image, mode=mode, batch_hint=B)
    assert cp.threads == cp.kernel_threads == CFG.max_threads
    digest = hashlib.sha256(_lowered(cp).encode()).hexdigest()
    assert digest == WIDE_TEXT[name, mode]


@pytest.mark.parametrize("use_compiler", [True, False])
def test_lane_step_counters(use_compiler):
    """A drain of a 32-thread program: offered = traced on the compiled
    tier; the interpreter traces ``max_threads`` lanes a step."""
    b = NARROW["reduction_dot_32"]()
    fleet = FleetScheduler(CFG, batch_size=2, use_compiler=use_compiler)
    for x in _inputs(b)[:2]:
        fleet.submit(b.image, x, tdx_dim=b.tdx_dim)
    with Tracer("t") as tr:
        fleet.drain()
    reg = fleet.stats.registry
    offered = reg.total("fleet_lane_steps_offered_total")
    traced = reg.total("fleet_lane_steps_traced_total")
    per_job = compile_program(b.image).event_counters().lane_steps_offered
    assert offered == 2 * per_job > 0
    interp = reg.total("fleet_lane_steps_offered_total", tier="interp")
    assert interp == (0 if use_compiler else offered)
    if use_compiler:
        assert traced == offered
        kt = [e["args"]["kernel_threads"] for e in tr.events
              if e.get("name") == "dispatch"]
        assert kt == [32]
    else:
        assert traced * 32 == offered * CFG.max_threads
