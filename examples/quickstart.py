"""Quickstart: assemble and run an eGPU program, inspect cycles/profile.

  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import Asm, benchmark_config, machine, profile, run_program
from repro.fleet import enable_compile_cache

enable_compile_cache()

# 1. Configure an eGPU instance (static scalability: every knob is a
#    configuration-time parameter, paper Tables 4-6).
cfg = benchmark_config("dp", has_dot=True)     # 512 threads, 32 regs, 128KB
print(f"eGPU: {cfg.max_threads} threads x {cfg.regs_per_thread} regs, "
      f"{cfg.shared_kb}KB shared, Fmax {cfg.fmax_mhz} MHz")

from repro.core import resources
r = resources(cfg)
print(f"resources: {r.alms} ALMs, {r.dsps} DSPs, {r.m20ks} M20Ks "
      f"(normalized cost {r.normalized_cost})")

# 2. Write a kernel in eGPU assembly: y[i] = a[i] * b[i] + a[i],
#    then a SUM reduction written back with a 1-cycle MCU store
#    (dynamic scalability, paper §3.1).
a = Asm(cfg)
a.tdx(1)                       # r1 = thread id
a.lod(2, 1, 0)                 # r2 = a[i]        (shared[0:256])
a.lod(3, 1, 256)               # r3 = b[i]        (shared[256:512])
a.fmul(4, 2, 3)                # r4 = a*b
a.fadd(4, 4, 2)                # r4 += a
a.sto(4, 1, 512)               # y[i] = r4
a.sum_(5, 4)                   # SP0.r5 = sum(y)  (dot-product unit)
a.lodi(6, 768, tsc="mcu")
a.sto(5, 6, 0, tsc="mcu")      # shared[768] = total, single-cycle write
a.stop()

img = a.assemble(threads_active=256)
print(f"\nprogram: {img.n} instructions "
      f"(incl. auto-inserted hazard NOPs), IW={img.words[0]:011x}...")

# 3. Load data, run, verify.
rng = np.random.default_rng(0)
av, bv = rng.standard_normal(256).astype(np.float32), \
    rng.standard_normal(256).astype(np.float32)
st = run_program(img, shared_init=np.concatenate([av, bv]), tdx_dim=256)

y = machine.shared_as_f32(st)[512:768]
total = machine.shared_as_f32(st)[768]
assert np.allclose(y, av * bv + av, atol=1e-5)
assert np.isclose(total, (av * bv + av).sum(), rtol=1e-4)
print(f"correct. cycles={int(st.cycles)} "
      f"({cfg.cycles_to_us(int(st.cycles)):.3f} us at {cfg.fmax_mhz} MHz), "
      f"hazard violations={int(st.hazard_violations)}")
print("profile:", {k: v for k, v in profile(st).items() if v[1]})
