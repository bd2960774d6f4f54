"""Admission-lint overhead benchmark.

Measures the latency of :func:`repro.fleet.scheduler.check_job` with and
without the static verifier on the warm path (``analyze_cached`` makes
repeated submits of the same program a dict lookup), plus the cold
one-shot cost of a full ``analyze`` per suite program.

Acceptance criterion for the admission wiring: warm-path ``check_job``
with lint enabled is within 5% of ``lint=False``.

  PYTHONPATH=src python -m benchmarks.analysis [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.analysis import analyze, analyze_cached  # noqa: E402
from repro.analysis.lint import _default_config, suite  # noqa: E402
from repro.fleet.scheduler import check_job  # noqa: E402
from repro.fleet import enable_compile_cache  # noqa: E402


def _time_paired(fn_a, fn_b, reps: int, rounds: int = 9):
    """Best-of-N for two functions, interleaved so clock drift and
    frequency scaling hit both equally; returns (sec_a, sec_b) per call."""
    best_a = best_b = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            fn_a()
        best_a = min(best_a, (time.perf_counter() - t0) / reps)
        t0 = time.perf_counter()
        for _ in range(reps):
            fn_b()
        best_b = min(best_b, (time.perf_counter() - t0) / reps)
    return best_a, best_b


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="fewer reps (CI gate)")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    enable_compile_cache()

    cfg = _default_config()
    benches = suite(cfg)
    reps = 200 if args.smoke else 2000

    # cold analyze cost per program (one-shot, amortised by the cache)
    cold = {}
    for b in benches:
        t0 = time.perf_counter()
        analyze(b.image, b.image.threads_active, tdx_dim=b.tdx_dim)
        cold[b.name] = time.perf_counter() - t0

    # warm the admission cache, then time the steady-state submit path
    for b in benches:
        analyze_cached(b.image, b.image.threads_active, tdx_dim=b.tdx_dim)

    def warm_with_lint():
        for b in benches:
            check_job(cfg, b.image, b.shared_init,
                      b.image.threads_active, tdx_dim=b.tdx_dim)

    def warm_without_lint():
        for b in benches:
            check_job(cfg, b.image, b.shared_init,
                      b.image.threads_active, tdx_dim=b.tdx_dim,
                      lint=False)

    t_off, t_on = _time_paired(warm_without_lint, warm_with_lint, reps)
    overhead = (t_on - t_off) / t_off if t_off > 0 else 0.0

    result = {
        "programs": len(benches),
        "reps": reps,
        "check_job_lint_off_us": t_off * 1e6,
        "check_job_lint_on_us": t_on * 1e6,
        "warm_overhead_pct": overhead * 100.0,
        "cold_analyze_ms": {k: v * 1e3 for k, v in cold.items()},
        "pass_5pct_budget": overhead <= 0.05,
    }
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(f"admission lint overhead over {len(benches)} suite programs "
              f"({reps} reps):")
        print(f"  check_job lint=False : {t_off * 1e6:9.2f} us/sweep")
        print(f"  check_job lint=True  : {t_on * 1e6:9.2f} us/sweep")
        print(f"  warm overhead        : {overhead * 100.0:9.2f} %"
              f"   (budget: 5%)")
        print(f"  cold analyze         : "
              f"{sum(cold.values()) * 1e3:9.2f} ms total, "
              f"worst {max(cold.values()) * 1e3:.2f} ms "
              f"({max(cold, key=lambda k: cold[k])})")
    return 0 if result["pass_5pct_budget"] else 1


if __name__ == "__main__":
    sys.exit(main())
