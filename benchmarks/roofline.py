"""GPGPU roofline: achieved instruction throughput per execution tier.

Classic rooflines bound FLOPs against memory traffic; a soft GPGPU's
equivalent bounds *architectural instruction throughput* against the
machine's issue and data-parallel limits.  For every suite program the
host path simulation's :class:`~repro.obs.EventCounters` give the exact
retired-instruction and issue-cycle counts (bit-identical to the
interpreter's counters), so dividing by each tier's measured
steady-state wall time yields achieved instrs/sec per tier — and two
utilization terms bound how much of the paper's scaling headroom each
program actually uses:

* **lane utilization** — active / offered vector lane-steps: the
  fraction of the SIMT data-parallel roof not lost to predicated-off
  lanes and partial warps (TSC masks);
* **issue efficiency** — retired instructions / issue cycles: the
  fraction of the dual-issue roof not lost to hazard NOP padding.

Rows are printed in the harness CSV contract and merged into
``BENCH_compiled.json`` under the ``"roofline"`` key (next to the
``"superblock"`` / ``"auto_tier"`` sections), so the trend pipeline can
track throughput per tier release over release.

  PYTHONPATH=src python -m benchmarks.roofline             # full
  PYTHONPATH=src python -m benchmarks.roofline --smoke     # quick pass
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

from benchmarks.fleet import fleet_config  # noqa: E402
from benchmarks.superblock import _loop_nested, _loop_saxpy  # noqa: E402
from repro.core import compile_program, run_program  # noqa: E402
from repro.core.blockc import BlockCompileError  # noqa: E402
from repro.fleet import enable_compile_cache  # noqa: E402
from repro.programs import (build_bitonic, build_fft, build_matmul,  # noqa: E402
                            build_reduction, build_transpose)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _suite(cfg, smoke: bool):
    """Straight-line *and* loop-heavy programs: the former exercise the
    blocks tier's fused superinstructions, the latter the superblock
    tier's folded back-edges."""
    out = [build_reduction(cfg, 32), build_transpose(cfg, 16),
           build_matmul(cfg, 8), _loop_saxpy(cfg, 512)]
    if not smoke:
        out += [build_reduction(cfg, 32, use_dot=True),
                build_bitonic(cfg, 16), build_fft(cfg, 16),
                _loop_saxpy(cfg, 1024), _loop_nested(cfg, 32, 16)]
    return out


def _time(f, repeats: int) -> float:
    f()                                    # warm the jit/compile caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def _tier_times(b, repeats: int) -> dict[str, float | None]:
    run = dict(shared_init=b.shared_init, tdx_dim=b.tdx_dim)
    times: dict[str, float | None] = {
        "interp": _time(lambda: run_program(b.image, **run), repeats)}
    cp_b = compile_program(b.image, mode="blocks")
    times["blocks"] = _time(lambda: cp_b.run(**run), repeats)
    try:
        cp_s = compile_program(b.image, mode="superblock")
    except BlockCompileError:
        times["superblock"] = None         # no foldable static path
    else:
        times["superblock"] = _time(lambda: cp_s.run(**run), repeats)
    return times


def bench(smoke: bool = False, repeats: int | None = None) -> dict:
    cfg = fleet_config()
    repeats = repeats or (2 if smoke else 5)
    rows = []
    for b in _suite(cfg, smoke):
        ec = compile_program(b.image).event_counters()
        times = _tier_times(b, repeats)
        row = {
            "name": b.name,
            "instrs": ec.instrs, "cycles": ec.cycles,
            "loop_backedges": ec.loop_backedges,
            "lane_utilization": round(ec.lane_utilization, 4),
            "issue_efficiency": round(ec.instrs / ec.cycles, 4)
            if ec.cycles else 1.0,
            "tiers": {},
        }
        for tier, t in times.items():
            if t is None:
                continue
            row["tiers"][tier] = {
                "us": round(t * 1e6, 1),
                "minstrs_per_sec": round(ec.instrs / t / 1e6, 3),
            }
        rows.append(row)

    # the roof per tier: the best throughput any program achieved on it
    roof = {}
    for tier in ("interp", "blocks", "superblock"):
        vals = [r["tiers"][tier]["minstrs_per_sec"]
                for r in rows if tier in r["tiers"]]
        if vals:
            roof[tier] = {"peak_minstrs_per_sec": max(vals),
                          "programs": len(vals)}
    offered = sum(r["instrs"] / max(r["lane_utilization"], 1e-9)
                  for r in rows if r["lane_utilization"] > 0)
    active = sum(r["instrs"] for r in rows if r["lane_utilization"] > 0)
    return {"programs": rows, "roof": roof,
            "suite_lane_utilization":
                round(active / offered, 4) if offered else 1.0}


def rows_csv(out: dict) -> list[tuple]:
    rows = []
    for r in out["programs"]:
        for tier, t in r["tiers"].items():
            rows.append((f"roofline/{r['name']}_{tier}", t["us"],
                         f"minstrs_per_sec={t['minstrs_per_sec']};"
                         f"lane_util={r['lane_utilization']};"
                         f"issue_eff={r['issue_efficiency']}"))
    return rows


def _merge_json(path: str, out: dict) -> None:
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["roofline"] = out
    with open(path, "w") as f:
        json.dump(data, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced suite, no json write")
    ap.add_argument("--repeats", type=int, default=None)
    ap.add_argument("--json", default=os.path.join(_REPO_ROOT,
                                                   "BENCH_compiled.json"))
    args = ap.parse_args()
    enable_compile_cache()

    out = bench(args.smoke, args.repeats)

    print("name,us_per_call,derived")
    for name, us, derived in rows_csv(out):
        print(f"{name},{us},{derived}")

    roof = ", ".join(f"{t}={v['peak_minstrs_per_sec']}"
                     for t, v in out["roof"].items())
    print(f"# peak Minstrs/s per tier: {roof}; suite lane utilization: "
          f"{out['suite_lane_utilization']}", file=sys.stderr)
    if not args.smoke:      # CI pass: don't clobber the tracked numbers
        _merge_json(args.json, out)
        print(f"# merged into {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
