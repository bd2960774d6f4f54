"""Jobs per second against the batch size: a one-off sweep on the chip
that chose each drain configuration's ``batch_size``; its lines go into
``PERF.md``.

    python3 bench/sweep_batch.py --workload dp_suite_drain \\
        --batches 32 64 128 256 512 --seconds 6 --seed 9

One process.  For each batch size ``B`` in turn, the cell's drain mix
with every program's job count set to ``B`` (one full batch of each
program a round, as the cell runs at its own size) is driven exactly as
a run drives it: one warm round, which compiles, then whole rounds
until ``--seconds`` have passed.  Prints one JSON line per size: jobs a
second, seconds a round, the warm round's seconds, and the device
memory in use and at its peak so far.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="dp_suite_drain")
    ap.add_argument("--batches", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=9)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import drivers, manifest, programs, run
    cell = manifest.load().cell(args.workload)
    try:
        devs = run.device_gate(cell.chips)
    except run.GateError as e:
        run.log(f"sweep refused: {e}")
        return 1
    from repro.fleet import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    enable_compile_cache()
    doc = manifest.load_config(cell.config)
    traffic = manifest.load_traffic(cell.traffic)
    if traffic["driver"] != "drain":
        raise SystemExit(f"{cell.name} is not a drain cell")
    cfg = drivers.make_egpu(doc)
    progs = {n: programs.build(cfg, n) for n, _ in traffic["jobs"]}
    for b in args.batches:
        mix = dict(traffic, jobs=[[n, b] for n, _ in traffic["jobs"]])
        env = drivers.Env(cfg=cfg, batch_size=b, chips=cell.chips,
                          programs=progs, traffic=mix, seed=args.seed,
                          window_s=args.seconds,
                          counter=drivers.CompileCounter().install())
        t0 = time.perf_counter()
        out = drivers.load("drain").run(env)
        rounds = out.attempted // (b * len(progs))
        stats = [d.memory_stats() or {} for d in devs]
        print(json.dumps({
            "workload": cell.name, "batch_size": b,
            "jobs_per_round": b * len(progs), "rounds": rounds,
            "jobs_per_s": out.metrics["jobs_per_s"],
            "round_s": b * len(progs) / out.metrics["jobs_per_s"],
            "warm_s": out.t_window - t0,
            "failed": out.failed,
            "compiles_in_window": env.counter.compiles,
            "bytes_in_use": max(int(s.get("bytes_in_use", 0))
                                for s in stats),
            "peak_bytes_in_use": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats),
            "notes": out.notes}), flush=True)
        del out
    return 0


if __name__ == "__main__":
    sys.exit(main())
