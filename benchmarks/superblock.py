"""Superblock benchmark: LOOP back-edges, tier costs, and auto-selection.

The loop-heavy half of the suite is where the basic-block driver pays a
``lax.switch`` dispatch on every LOOP back-edge; the superblock tier
folds the static path and pays none — but its fixed per-call cost
(state assembly + launch) can *lose* below a few hundred back-edges.
Three tiers, head to head, on a loop-heavy program mix:

  * the interpreter (``run_program`` — reference semantics),
  * the basic-block driver (``mode="blocks"`` — PR-2 behaviour),
  * the superblock runner (``mode="superblock"``),

plus the ``mode="auto"`` :class:`~repro.core.blockc.TierPolicy` pick,
with results asserted bit-identical before any timing, and a fleet
drain of same-program loop jobs to exercise the scheduler's superblock
tier.  The **crossover sweep** (``bench_auto_tier``) times blocks vs
superblock vs auto through the light path over back-edge counts
8 -> 2048, records the measured crossover point and the per-tier fixed
overheads, and **asserts the auto tier stays within
``AUTO_TOLERANCE`` of the faster tier on both sides** of the crossover.
Results are merged into ``BENCH_compiled.json`` under the
``"superblock"`` and ``"auto_tier"`` keys.

  PYTHONPATH=src python -m benchmarks.superblock            # full
  PYTHONPATH=src python -m benchmarks.superblock --smoke    # CI gate

Both modes **fail the build** (exit 1) when the auto tier misses the
crossover; ``--smoke`` additionally fails when a loop-heavy program
stops being superblock-eligible (a dispatch-count regression: its
switch dispatches must be 0 under the forced superblock tier while the
blocks tier's are > 0) or when the aggregate superblock speedup over
the basic-block tier regresses below the gate threshold.
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

from benchmarks.fleet import fleet_config  # noqa: E402
from repro.core import Asm, compile_program, run_program  # noqa: E402
from repro.core.blockc import (DEFAULT_TIER_POLICY, _sched_insts,  # noqa: E402
                               _trace_cost)
from repro.fleet import Fleet, enable_compile_cache  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.programs import build_matmul, build_transpose  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: --smoke gate: the superblock tier must keep at least this aggregate
#: speedup over the basic-block driver on the loop-heavy mix ...
SMOKE_MIN_SPEEDUP = 1.2
#: ... and every mix program must land on the superblock tier (its
#: switch-dispatch count is 0 by construction; the blocks tier's > 0).

#: the auto tier must stay within this factor of the faster forced tier
#: at every swept back-edge count (acceptance: within 5%)
AUTO_TOLERANCE = 1.05

#: inter-tier gaps below this are within the observed run-to-run jitter
#: of a loaded CPU host (which tier "wins" flips between runs near the
#: true crossover): when the two tiers measure this close, either pick
#: satisfies the within-5%-of-faster contract to the extent it is
#: measurable, so such points pass the gate
NOISE_FLOOR_US = 150.0

#: crossover sweep: LOOP back-edge counts (full mode; smoke uses a
#: reduced two-point sweep, one on each side of the crossover)
SWEEP_BACKEDGES = (8, 16, 32, 64, 128, 256, 512, 1024, 2048)
SMOKE_BACKEDGES = (64, 1024)


class _Bench:
    def __init__(self, name, image, shared_init=None, tdx_dim=16):
        self.name = name
        self.image = image
        self.shared_init = shared_init
        self.tdx_dim = tdx_dim


def _loop_saxpy(cfg, iters: int) -> _Bench:
    """y[t] = a*y[t] + x[t], ``iters`` times — one LOOP back-edge per
    iteration, the pure back-edge-dispatch stress test."""
    a = Asm(cfg)
    a.tdx(1)
    a.lod(2, 1, 0)                  # x[t]
    a.lod(3, 1, 32)                 # y[t]
    with a.loop(iters):
        a.fmul(3, 3, 4)
        a.fadd(3, 3, 2)
    a.sto(3, 1, 32)
    a.stop()
    rng = np.random.default_rng(iters)
    data = rng.standard_normal(64).astype(np.float32)
    return _Bench(f"loop_saxpy_{iters}", a.assemble(threads_active=32),
                  shared_init=data, tdx_dim=32)


def _loop_nested(cfg, outer: int, inner: int) -> _Bench:
    """Nested LOOPs: the folded schedule is a repeat inside a repeat."""
    a = Asm(cfg)
    a.tdx(1)
    a.lod(2, 1, 0)
    a.lodi(5, 3)
    with a.loop(outer):
        with a.loop(inner):
            a.add(2, 2, 5)
        a.xor(2, 2, 1)
    a.sto(2, 1, 0)
    a.stop()
    data = np.arange(32, dtype=np.uint32)
    return _Bench(f"loop_nested_{outer}x{inner}",
                  a.assemble(threads_active=32), shared_init=data,
                  tdx_dim=32)


def _suite(cfg, smoke: bool) -> list[_Bench]:
    """Loop-heavy mix: every program's executed path crosses a LOOP
    back-edge many times (the regime the superblock tier targets)."""
    mm = build_matmul(cfg, 8)
    tr = build_transpose(cfg, 16)
    out = [
        _Bench(mm.name, mm.image, mm.shared_init, mm.tdx_dim),
        _Bench(tr.name, tr.image, tr.shared_init, tr.tdx_dim),
        _loop_saxpy(cfg, 512),
    ]
    if not smoke:
        # the small-iteration cases document the crossover: below a few
        # hundred back-edges the fixed trace overhead can eat the
        # dispatch win on CPU (the full JSON keeps both data points)
        out += [_loop_saxpy(cfg, 64), _loop_saxpy(cfg, 1024),
                _loop_nested(cfg, 32, 16)]
    return out


def _assert_bit_identical(b, cps):
    ref = run_program(b.image, shared_init=b.shared_init, tdx_dim=b.tdx_dim)
    for label, cp in cps.items():
        got = cp.run(shared_init=b.shared_init, tdx_dim=b.tdx_dim)
        for leaf in ref._fields:
            assert np.array_equal(np.asarray(getattr(ref, leaf)),
                                  np.asarray(getattr(got, leaf))), \
                f"{b.name}/{label}: {leaf} differs from the interpreter"


def _compile_super_or_auto(image):
    """``mode="superblock"`` when eligible; if the program ever stops
    fitting the trace budget, fall back to ``mode="auto"`` — which then
    compiles to the blocks tier with switch_dispatches > 0, and the
    smoke gate reports a dispatch regression instead of crashing."""
    from repro.core import BlockCompileError
    try:
        return compile_program(image, mode="superblock")
    except BlockCompileError:
        return compile_program(image, mode="auto")


def bench_single_core(cfg, smoke: bool, repeats: int) -> list[dict]:
    rows = []
    tot = {"interp": 0.0, "blocks": 0.0, "super": 0.0}
    for b in _suite(cfg, smoke):
        cps = {
            "blocks": compile_program(b.image, mode="blocks"),
            "super": _compile_super_or_auto(b.image),
        }
        auto = compile_program(b.image)        # the TierPolicy pick
        _assert_bit_identical(b, cps)
        run = dict(shared_init=b.shared_init, tdx_dim=b.tdx_dim)
        t = _time_interleaved({
            "interp": lambda: run_program(b.image, **run),
            "blocks": lambda: cps["blocks"].run(**run),
            "super": lambda: cps["super"].run(**run),
        }, repeats)
        ti, tb, ts = t["interp"], t["blocks"], t["super"]
        tot["interp"] += ti
        tot["blocks"] += tb
        tot["super"] += ts
        sched = cps["super"].schedule
        rows.append({
            "name": b.name,
            "steps": cps["super"].sim.steps,
            "dispatches_blocks": cps["blocks"].switch_dispatches,
            "dispatches_super": cps["super"].switch_dispatches,
            "sched_insts": _sched_insts(sched) if sched else None,
            "trace_cost": _trace_cost(sched) if sched else None,
            "auto_tier": auto.mode,
            "interp_us": round(ti * 1e6, 1),
            "blocks_us": round(tb * 1e6, 1),
            "super_us": round(ts * 1e6, 1),
            "speedup_vs_blocks": round(tb / ts, 2),
            "speedup_vs_interp": round(ti / ts, 2),
            "bit_identical": True,
        })
    rows.append({
        "name": "aggregate",
        "interp_us": round(tot["interp"] * 1e6, 1),
        "blocks_us": round(tot["blocks"] * 1e6, 1),
        "super_us": round(tot["super"] * 1e6, 1),
        "speedup_vs_blocks": round(tot["blocks"] / tot["super"], 2),
        "speedup_vs_interp": round(tot["interp"] / tot["super"], 2),
    })
    return rows


def _time_interleaved(fns: dict, repeats: int) -> dict:
    """Best-of-``repeats`` per entry, rounds interleaved across entries
    so drift (thermal, scheduler) hits every tier alike — what keeps a
    5%-tolerance comparison honest on a shared machine."""
    for f in fns.values():
        f()                                    # warm every jit cache
    best = {k: float("inf") for k in fns}
    for _ in range(repeats):
        for k, f in fns.items():
            t0 = time.perf_counter()
            f()
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


def bench_auto_tier(cfg, smoke: bool, repeats: int) -> dict:
    """The crossover sweep: blocks vs superblock vs the auto pick, over
    LOOP back-edge counts, all through the light path
    (:meth:`CompiledProgram.run_light` — these callers only read
    shared/cycles).  Records the measured crossover and the per-tier
    fixed overheads; asserts the auto tier is within
    :data:`AUTO_TOLERANCE` of the faster tier at every point."""
    rows = []
    for n in (SMOKE_BACKEDGES if smoke else SWEEP_BACKEDGES):
        b = _loop_saxpy(cfg, n)
        cb = compile_program(b.image, mode="blocks")
        cs = compile_program(b.image, mode="superblock")
        ca = compile_program(b.image)          # auto, default policy
        # light == full on the leaves the light path returns
        ref = run_program(b.image, shared_init=b.shared_init,
                          tdx_dim=b.tdx_dim)
        for cp in (cb, cs, ca):
            sh, cyc, halted = cp.run_light(shared_init=b.shared_init,
                                           tdx_dim=b.tdx_dim)
            assert np.array_equal(np.asarray(ref.shared), np.asarray(sh))
            assert int(ref.cycles) == cyc and bool(ref.halted) == halted
        run = dict(shared_init=b.shared_init, tdx_dim=b.tdx_dim)
        t = _time_interleaved({
            "blocks": lambda: cb.run_light(**run),
            "super": lambda: cs.run_light(**run),
            "auto": lambda: ca.run_light(**run),
        }, repeats)
        faster = "blocks" if t["blocks"] <= t["super"] else "superblock"
        # the gate judges the *decision*: the tier auto chose, measured
        # through its forced twin, against the faster tier.  (auto_us is
        # the same computation as its chosen tier behind a separately
        # jitted object, so gating on auto_us directly would mostly
        # measure jit-instance timing noise, not the policy.)
        chosen = t["blocks"] if ca.mode == "blocks" else t["super"]
        ratio = chosen / min(t["blocks"], t["super"])
        gap_us = abs(t["blocks"] - t["super"]) * 1e6
        rows.append({
            "backedges": n,
            "dispatches": cb.switch_dispatches,
            "execd": cb.sim.steps,
            "trace_cost": _trace_cost(cs.schedule),
            "blocks_us": round(t["blocks"] * 1e6, 1),
            "super_us": round(t["super"] * 1e6, 1),
            "auto_us": round(t["auto"] * 1e6, 1),
            "auto_tier": ca.mode,
            "faster_tier": faster,
            "auto_vs_faster": round(ratio, 3),
            "tier_gap_us": round(gap_us, 1),
            "auto_ok": bool(ratio <= AUTO_TOLERANCE
                            or gap_us <= NOISE_FLOOR_US),
        })

    # the measured crossover: the first swept back-edge count from which
    # the superblock tier stays faster (None if it never takes over)
    crossover = None
    for i, r in enumerate(rows):
        if all(x["faster_tier"] == "superblock" for x in rows[i:]):
            crossover = r["backedges"]
            break

    # per-tier fixed overhead, from the fori-regime points (backedges >=
    # 16): a linear fit of per-call time against the quantity each
    # driver's marginal cost scales with (blocks: switch dispatches;
    # superblock: executed instructions through the fused fori body)
    fori = [r for r in rows if r["backedges"] >= 16]
    fit = {}
    if len(fori) >= 2:
        bd = np.polyfit([r["dispatches"] for r in fori],
                        [r["blocks_us"] for r in fori], 1)
        sd = np.polyfit([r["execd"] for r in fori],
                        [r["super_us"] for r in fori], 1)
        fit = {
            "blocks_fixed_us": round(float(bd[1]), 1),
            "blocks_per_dispatch_us": round(float(bd[0]), 3),
            "super_fixed_us": round(float(sd[1]), 1),
            "super_per_exec_us": round(float(sd[0]), 4),
        }
    return {
        "sweep": rows,
        "crossover_backedges": crossover,
        "auto_tolerance": AUTO_TOLERANCE,
        "noise_floor_us": NOISE_FLOOR_US,
        "policy_table": {k: v for k, v
                         in DEFAULT_TIER_POLICY.table.items()},
        **fit,
    }


def bench_fleet(cfg, smoke: bool, batch: int, repeats: int) -> dict:
    """Same-program loop jobs through the scheduler: all of them must
    land on the superblock tier (stats.superblock_jobs == jobs)."""
    b = _loop_saxpy(cfg, 64)
    n_jobs = batch * (2 if smoke else 8)
    rng = np.random.default_rng(0)
    datas = [rng.standard_normal(64).astype(np.float32)
             for _ in range(n_jobs)]

    def once():
        fleet = Fleet(cfg, batch_size=batch)
        for d in datas:
            fleet.submit(b.image, d, tdx_dim=b.tdx_dim)
        t0 = time.perf_counter()
        fleet.drain()
        assert fleet.stats.superblock_jobs == n_jobs
        return time.perf_counter() - t0

    once()                                 # warm compiles
    jps = n_jobs / min(once() for _ in range(repeats))
    return {"mix": "loop_saxpy", "batch": batch, "jobs": n_jobs,
            "superblock_jobs_per_sec": round(jps, 1)}


def bench(smoke: bool = False, batch: int = 32,
          repeats: int | None = None) -> dict:
    cfg = fleet_config()
    repeats = repeats or (2 if smoke else 5)
    return {
        "single_core": bench_single_core(cfg, smoke, repeats),
        "fleet": [bench_fleet(cfg, smoke, batch, max(2, repeats // 2))],
        "auto_tier": bench_auto_tier(cfg, smoke, max(5, repeats)),
    }


def rows_csv(out: dict) -> list[tuple]:
    rows = []
    for r in out["single_core"]:
        rows.append((f"superblock/{r['name']}", r["super_us"],
                     f"blocks_us={r['blocks_us']};"
                     f"interp_us={r['interp_us']};"
                     f"vs_blocks={r['speedup_vs_blocks']}x;"
                     f"vs_interp={r['speedup_vs_interp']}x"))
    for r in out.get("fleet", ()):
        rows.append((f"superblock_fleet/{r['mix']}_batch{r['batch']}",
                     round(1e6 / r["superblock_jobs_per_sec"], 1),
                     f"jobs_per_sec={r['superblock_jobs_per_sec']}"))
    for r in out.get("auto_tier", {}).get("sweep", ()):
        rows.append((f"auto_tier/loop_saxpy_{r['backedges']}",
                     r["auto_us"],
                     f"blocks_us={r['blocks_us']};"
                     f"super_us={r['super_us']};tier={r['auto_tier']};"
                     f"vs_faster={r['auto_vs_faster']}x"))
    return rows


def _merge_json(path: str, out: dict) -> None:
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["superblock"] = {k: v for k, v in out.items() if k != "auto_tier"}
    data["auto_tier"] = out["auto_tier"]
    with open(path, "w") as f:
        json.dump(data, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced mix; exit 1 on dispatch/speedup "
                         "regression")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--json", default=os.path.join(_REPO_ROOT,
                                                   "BENCH_compiled.json"))
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a repro.obs trace of the whole run")
    args = ap.parse_args()
    enable_compile_cache()

    tracer = Tracer("bench-superblock") if args.trace else None
    with (tracer if tracer is not None else contextlib.nullcontext()):
        out = bench(args.smoke, args.batch, args.repeats)
    if tracer is not None:
        tracer.save(args.trace)
        print(f"# wrote trace {args.trace}", file=sys.stderr)

    print("name,us_per_call,derived")
    for name, us, derived in rows_csv(out):
        print(f"{name},{us},{derived}")

    if not args.smoke:      # CI pass: don't clobber the tracked numbers
        _merge_json(args.json, out)
        print(f"# merged into {args.json}", file=sys.stderr)

    per_prog = out["single_core"][:-1]
    agg = out["single_core"][-1]["speedup_vs_blocks"]
    bad_dispatch = [r["name"] for r in per_prog
                    if r["dispatches_super"] != 0
                    or r["dispatches_blocks"] <= 0]
    sweep = out["auto_tier"]["sweep"]
    bad_auto = [r["backedges"] for r in sweep if not r["auto_ok"]]
    print(f"# aggregate superblock-vs-blocks speedup: {agg}x; "
          f"dispatch regressions: {bad_dispatch or 'none'}; "
          f"crossover: {out['auto_tier']['crossover_backedges']} "
          f"back-edges; auto-tier misses: {bad_auto or 'none'}",
          file=sys.stderr)
    # the auto-tier contract gates BOTH modes: mode="auto" must stay
    # within AUTO_TOLERANCE of the faster tier on both sides of the
    # measured crossover, or the cost model has rotted
    if bad_auto:
        print(f"# FAIL: auto tier more than "
              f"{round((AUTO_TOLERANCE - 1) * 100)}% off the faster "
              f"tier at back-edge counts {bad_auto}", file=sys.stderr)
        sys.exit(1)
    if args.smoke:
        if bad_dispatch:
            print(f"# SMOKE FAIL: {bad_dispatch} not on the superblock "
                  f"tier (switch dispatches must drop to 0)",
                  file=sys.stderr)
            sys.exit(1)
        if agg < SMOKE_MIN_SPEEDUP:
            print(f"# SMOKE FAIL: need >= {SMOKE_MIN_SPEEDUP}x over the "
                  f"basic-block tier", file=sys.stderr)
            sys.exit(1)
        print("# smoke gate passed", file=sys.stderr)


if __name__ == "__main__":
    main()
