"""Block-compiler benchmark: the two execution tiers, head to head.

Single core: every paper-suite program through

  * the interpreter (``run_program``, hazard checker + stats on — the
    default tier),
  * the fast interpreter (``validate=False``: no checker, no counters),
  * the block compiler (``run_compiled`` — straight-line fused blocks,
    hazards baked statically),

with results asserted bit-identical before any timing.  Fleet: the
suite job mix through the scheduler with the compiled lock-step tier on
vs off.  Everything is persisted to ``BENCH_compiled.json``.

  PYTHONPATH=src python -m benchmarks.compiled             # full
  PYTHONPATH=src python -m benchmarks.compiled --smoke     # CI gate

``--smoke`` runs a reduced mix and **fails the build** (exit 1) when the
compiled tier regresses below the gate thresholds, so a speedup
regression cannot rot silently.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

import numpy as np  # noqa: E402

from benchmarks.fleet import build_jobs, fleet_config  # noqa: E402
from repro.core import compile_program, run_compiled, run_program  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.fleet import enable_compile_cache  # noqa: E402
from repro.programs import (build_bitonic, build_fft, build_matmul,  # noqa: E402
                            build_reduction, build_transpose)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: --smoke gate: the compiled tier must keep at least this aggregate
#: single-core speedup over the default interpreter ...
SMOKE_MIN_SPEEDUP = 2.0
#: ... and at least this fraction of the interpreter fleet's jobs/sec
#: (in practice it is several times faster; 1.0 still leaves margin).
SMOKE_MIN_FLEET_RATIO = 1.0


def _suite(cfg, smoke: bool):
    if smoke:
        return [build_reduction(cfg, 32), build_fft(cfg, 16),
                build_matmul(cfg, 8)]
    return [build_reduction(cfg, 32),
            build_reduction(cfg, 32, use_dot=True),
            build_reduction(cfg, 32, no_dynamic=True),
            build_transpose(cfg, 16), build_matmul(cfg, 8),
            build_bitonic(cfg, 16), build_bitonic(cfg, 32),
            build_fft(cfg, 16), build_fft(cfg, 32)]


def _assert_bit_identical(b):
    ref = run_program(b.image, shared_init=b.shared_init, tdx_dim=b.tdx_dim)
    got = run_compiled(b.image, shared_init=b.shared_init,
                       tdx_dim=b.tdx_dim, fallback=False)
    for leaf in ref._fields:
        assert np.array_equal(np.asarray(getattr(ref, leaf)),
                              np.asarray(getattr(got, leaf))), \
            f"{b.name}: {leaf} differs between tiers"


def _time(f, repeats: int) -> float:
    f()                                    # warm the jit cache
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_single_core(cfg, smoke: bool, repeats: int) -> list[dict]:
    rows = []
    tot = {"interp": 0.0, "interp_fast": 0.0, "compiled": 0.0}
    for b in _suite(cfg, smoke):
        _assert_bit_identical(b)
        cp = compile_program(b.image)
        run = dict(shared_init=b.shared_init, tdx_dim=b.tdx_dim)
        ti = _time(lambda: run_program(b.image, **run), repeats)
        tf = _time(lambda: run_program(b.image, validate=False, **run),
                   repeats)
        tc = _time(lambda: run_compiled(b.image, **run), repeats)
        tot["interp"] += ti
        tot["interp_fast"] += tf
        tot["compiled"] += tc
        rows.append({
            "name": b.name, "blocks": len(cp.blocks),
            "steps": cp.sim.steps,
            "interp_us": round(ti * 1e6, 1),
            "interp_fast_us": round(tf * 1e6, 1),
            "compiled_us": round(tc * 1e6, 1),
            "speedup": round(ti / tc, 2),
            "speedup_vs_fast": round(tf / tc, 2),
            "bit_identical": True,
        })
    rows.append({
        "name": "aggregate",
        "interp_us": round(tot["interp"] * 1e6, 1),
        "interp_fast_us": round(tot["interp_fast"] * 1e6, 1),
        "compiled_us": round(tot["compiled"] * 1e6, 1),
        "speedup": round(tot["interp"] / tot["compiled"], 2),
        "speedup_vs_fast": round(tot["interp_fast"] / tot["compiled"], 2),
    })
    return rows


def _drain_jobs_per_sec(cfg, jobs, batch, use_compiler, repeats) -> float:
    from repro.fleet import Fleet

    def once():
        fleet = Fleet(cfg, batch_size=batch, use_compiler=use_compiler)
        for b in jobs:
            fleet.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim,
                         weight=b.image.static_cycle_estimate())
        t0 = time.perf_counter()
        fleet.drain()
        return time.perf_counter() - t0

    once()                                 # warm compiles
    return len(jobs) / min(once() for _ in range(repeats))


def bench_fleet(cfg, smoke: bool, batch: int, repeats: int) -> list[dict]:
    rows = []
    mixes = ("suite",) if smoke else ("light", "suite")
    rounds = 2 if smoke else 8
    for mix in mixes:
        jobs = build_jobs(cfg, batch * rounds, mix)
        jps_i = _drain_jobs_per_sec(cfg, jobs, batch, False, repeats)
        jps_c = _drain_jobs_per_sec(cfg, jobs, batch, True, repeats)
        rows.append({
            "mix": mix, "batch": batch, "jobs": len(jobs),
            "interp_jobs_per_sec": round(jps_i, 1),
            "compiled_jobs_per_sec": round(jps_c, 1),
            "speedup": round(jps_c / jps_i, 2),
        })
    return rows


def bench(smoke: bool = False, batch: int = 32,
          repeats: int | None = None, include_fleet: bool = True) -> dict:
    cfg = fleet_config()
    repeats = repeats or (2 if smoke else 5)
    out = {"single_core": bench_single_core(cfg, smoke, repeats)}
    if include_fleet:
        out["fleet"] = bench_fleet(cfg, smoke, batch,
                                   max(2, repeats // 2))
    return out


def rows_csv(out: dict) -> list[tuple]:
    """``(name, us_per_call, derived)`` rows for the harness CSV contract
    (shared with benchmarks/run.py so the two outputs cannot drift)."""
    rows = []
    for r in out["single_core"]:
        rows.append((f"compiled/{r['name']}", r["compiled_us"],
                     f"interp_us={r['interp_us']};speedup={r['speedup']}x;"
                     f"vs_fast={r['speedup_vs_fast']}x"))
    for r in out.get("fleet", ()):
        rows.append((f"compiled_fleet/{r['mix']}_batch{r['batch']}",
                     round(1e6 / r["compiled_jobs_per_sec"], 1),
                     f"jobs_per_sec={r['compiled_jobs_per_sec']};"
                     f"interp_jobs_per_sec={r['interp_jobs_per_sec']};"
                     f"speedup={r['speedup']}x"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced mix; exit 1 on speedup regression")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--json", default=os.path.join(_REPO_ROOT,
                                                   "BENCH_compiled.json"))
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a repro.obs trace of the whole run")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = Tracer("bench-compiled") if args.trace else None
    with (tracer if tracer is not None else contextlib.nullcontext()):
        out = bench(args.smoke, args.batch, args.repeats)
    if tracer is not None:
        tracer.save(args.trace)
        print(f"# wrote trace {args.trace}", file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived in rows_csv(out):
        print(f"{name},{us},{derived}")

    if not args.smoke:      # CI pass: don't clobber the tracked numbers
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)

    agg = out["single_core"][-1]["speedup"]
    fleet_ratio = min(r["speedup"] for r in out["fleet"])
    print(f"# aggregate single-core speedup: {agg}x; "
          f"worst fleet ratio: {fleet_ratio}x", file=sys.stderr)
    if args.smoke:
        ok = agg >= SMOKE_MIN_SPEEDUP and fleet_ratio >= SMOKE_MIN_FLEET_RATIO
        if not ok:
            print(f"# SMOKE FAIL: need >= {SMOKE_MIN_SPEEDUP}x single-core "
                  f"and >= {SMOKE_MIN_FLEET_RATIO}x fleet", file=sys.stderr)
            sys.exit(1)
        print("# smoke gate passed", file=sys.stderr)


if __name__ == "__main__":
    main()
