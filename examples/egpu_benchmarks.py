"""Run the paper's five benchmarks (Table 7/8) and the dynamic-scaling
ablation end to end, printing the comparison against the paper.

  PYTHONPATH=src python examples/egpu_benchmarks.py
"""
from repro.core import benchmark_config
from repro.fleet import enable_compile_cache
from repro.programs import (build_bitonic, build_fft, build_matmul,
                            build_reduction, build_transpose, run_bench)

enable_compile_cache()

PAPER = {"reduction": 202, "transpose": 5529, "matmul": 26278,
         "bitonic": 3728, "fft": 1695}

print(f"{'benchmark':<14} {'cycles':>8} {'us':>8} {'ok':>4} {'NOPs%':>6}")
for name, builder, n, kw in [
        ("reduction", build_reduction, 64, {}),
        ("transpose", build_transpose, 64, {}),
        ("matmul", build_matmul, 32, {}),
        ("bitonic", build_bitonic, 64, {"pred": 2}),
        ("fft", build_fft, 64, {})]:
    cfg = benchmark_config("dp", predicate_levels=kw.pop("pred", 0))
    r = run_bench(builder(cfg, n, **kw))
    total = sum(c for c, _ in r.profile.values())
    nops = 100 * r.profile["NOPC"][0] / max(1, total)
    print(f"{name:<14} {r.cycles:>8} {r.time_us:>8.2f} "
          f"{'yes' if r.correct else 'NO':>4} {nops:>5.1f}%")

print("\ndynamic scalability (reduction-64): ", end="")
dyn = run_bench(build_reduction(benchmark_config("dp"), 64))
nod = run_bench(build_reduction(
    benchmark_config("dp", predicate_levels=4), 64, no_dynamic=True))
print(f"TSC {dyn.cycles} cycles vs predicated {nod.cycles} "
      f"-> {nod.cycles/dyn.cycles:.1f}x win")
