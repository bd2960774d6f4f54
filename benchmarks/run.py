"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness contract), where
``derived`` carries the table-specific payload (cycles, vs-paper ratio,
normalized cost, roofline terms ...), and persists every row to
``BENCH_paper_tables.json`` at the repo root (plus ``BENCH_fleet.json``
for the fleet throughput section) so the perf trajectory is tracked
across PRs.

  PYTHONPATH=src python -m benchmarks.run            # all tables
  PYTHONPATH=src python -m benchmarks.run --full     # + matmul-128 etc.
  PYTHONPATH=src python -m benchmarks.run --no-fleet # skip fleet section
  PYTHONPATH=src python -m benchmarks.run --smoke    # quick CI pass
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from benchmarks import paper_tables  # noqa: E402
from repro.fleet import enable_compile_cache  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROWS: list[dict] = []


_PERSIST = True          # --smoke disables writing the tracked BENCH files


def emit(name, us, derived):
    print(f"{name},{us},{derived}")
    _ROWS.append({"name": name, "us_per_call": us, "derived": derived})


def _dump(path, obj):
    if not _PERSIST:
        return
    with open(os.path.join(_REPO_ROOT, path), "w") as f:
        json.dump(obj, f, indent=2)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--no-fleet", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="smallest sizes / fewest rounds, for CI")
    args = ap.parse_args()
    enable_compile_cache()
    global _PERSIST
    _PERSIST = not args.smoke

    print("name,us_per_call,derived")

    # Tables 4/5/6 — area model (no runtime: us = 0)
    for row in paper_tables.table_area():
        emit(f"table4_5/{row['config']}", 0,
             f"alm={row['alms']}(paper {row['alms_paper']});"
             f"m20k={row['m20ks']}(paper {row['m20ks_paper']});"
             f"dsp={row['dsps']};fmax={row['fmax']}")
    for row in paper_tables.table6_alu():
        emit(f"table6/{row['alu'].replace(' ', '_')}", 0,
             f"alm={row['alms']};ff={row['ffs']}")

    # Table 7
    sizes = (32,) if args.smoke else (32, 64, 128) if args.full else (32, 64)
    for row in paper_tables.table7(sizes):
        emit(f"table7/{row['bench']}_{row['n']}_{row['variant']}",
             row["time_us"],
             f"cycles={row['cycles']};paper={row['paper_cycles']};"
             f"x_paper={row['cycles_vs_paper']};correct={row['correct']};"
             f"nios_speedup={row['ratio_time_vs_nios']};"
             f"normalized={row['normalized_vs_nios']}")

    # Table 8
    sizes8 = (32,) if args.smoke \
        else (32, 64, 128, 256) if args.full else (32, 64)
    for row in paper_tables.table8(sizes8):
        emit(f"table8/{row['bench']}_{row['n']}_{row['variant']}",
             row["time_us"],
             f"cycles={row['cycles']};paper={row['paper_cycles']};"
             f"x_paper={row['cycles_vs_paper']};correct={row['correct']};"
             f"nios_speedup={row['ratio_time_vs_nios']};"
             f"normalized={row['normalized_vs_nios']}")

    # Fig. 6 profile
    for row in paper_tables.profile_mix():
        payload = ";".join(f"{k}={v}" for k, v in row.items()
                           if k.startswith("pct_"))
        emit(f"fig6/{row['bench']}_{row['n']}", 0, payload)

    # Dynamic-scalability ablation
    for row in paper_tables.dynamic_scaling(
            (32,) if args.smoke else (32, 64) if not args.full
            else (32, 64, 128)):
        emit(f"dynamic_scaling/reduction_{row['n']}", 0,
             f"tsc={row['tsc_cycles']};predicated={row['predicated_cycles']};"
             f"speedup={row['dynamic_speedup']}x")

    # Roofline (from the dry-run + calibration batches, if present)
    rl = "results/roofline/roofline.json"
    if os.path.exists(rl):
        for row in json.load(open(rl)):
            emit(f"roofline/{row['arch']}__{row['shape']}",
                 round(max(row['t_compute_s'], row['t_memory_s'],
                           row['t_collective_s']) * 1e6, 1),
                 f"dom={row['dominant']};comp={row['t_compute_s']:.2e};"
                 f"mem={row['t_memory_s']:.2e};coll={row['t_collective_s']:.2e};"
                 f"useful={row['useful_flops_ratio']:.2f}")

    # persist the paper tables before the fleet section so a fleet
    # failure can't discard the rows already collected
    _dump("BENCH_paper_tables.json", _ROWS)

    # Fleet throughput (batched multi-core engine vs serial loop)
    if not args.no_fleet:
        from benchmarks import fleet as fleet_bench
        rounds = 8 if args.full else 1 if args.smoke else 2
        fleet_rows = fleet_bench.bench(batch=32, rounds=rounds,
                                       mixes=("light", "suite"))
        for r in fleet_rows:
            emit(f"fleet/{r['mix']}_batch{r['batch']}",
                 round(1e6 * r["fleet_s"] / r["jobs"], 1),
                 f"jobs_per_sec={r['fleet_jobs_per_sec']};"
                 f"serial_jobs_per_sec={r['serial_jobs_per_sec']};"
                 f"speedup={r['speedup']}x")
        _dump("BENCH_fleet.json", fleet_rows)
        _dump("BENCH_paper_tables.json", _ROWS)  # + the fleet rows

    # Block compiler vs interpreter (single core; + fleet tiers unless
    # --no-fleet, which skips every fleet-engine benchmark)
    from benchmarks import compiled as compiled_bench
    comp = compiled_bench.bench(smoke=args.smoke,
                                include_fleet=not args.no_fleet)
    for name, us, derived in compiled_bench.rows_csv(comp):
        emit(name, us, derived)
    if not args.no_fleet:       # only persist the complete two-section file
        _dump("BENCH_compiled.json", comp)
    _dump("BENCH_paper_tables.json", _ROWS)      # + the compiled-tier rows


if __name__ == "__main__":
    main()
