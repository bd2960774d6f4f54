"""Find the highest rate a serve cell sustains: a one-off sweep on the
chip, whose result is written into the mix's ``rate_per_s`` (at about
four fifths of the knee) and into ``PERF.md``.

    python3 bench/sweep_knee.py --workload dp_short_serve \\
        --rates 200 300 400 500 600 --seconds 8 --seed 5

One process, one ``FleetService``, warmed once; then the cell's
open-loop mix at each rate in turn, each for ``--seconds``.  A rate is
sustained when the backlog does not grow over the window: the median
latency of the last fifth of the requests (by due time) is at most
twice that of the first fifth, or within 20 ms of it.  Prints one JSON
line per rate and the knee last; stops after two rates in a row that
are not sustained.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def growing(lat: np.ndarray) -> bool:
    """Whether latencies (in due order) grow over the window."""
    k = max(1, len(lat) // 5)
    first, last = np.median(lat[:k]), np.median(lat[-k:])
    return bool(last > 2 * first and last - first > 0.020)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="dp_short_serve")
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import drivers, manifest, programs, run
    from bench.drivers import serve
    cell = manifest.load().cell(args.workload)
    try:
        run.device_gate(cell.chips)
    except run.GateError as e:
        run.log(f"sweep refused: {e}")
        return 1
    from repro.fleet import FleetService, enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    enable_compile_cache()
    doc = manifest.load_config(cell.config)
    traffic = manifest.load_traffic(cell.traffic)
    cfg = drivers.make_egpu(doc)
    progs = [programs.build(cfg, n) for n in traffic["programs"]]
    key = drivers.seed_key(args.seed)
    knee, misses = None, 0
    with FleetService(cfg, int(doc["batch_size"])) as svc:
        serve.open_loop(svc, progs, serve.plan(progs, 50.0, 1.0, [0, 2]))
        for i, rate in enumerate(args.rates):
            pl = serve.plan(progs, rate, args.seconds, [key, 3, i])
            rows, due, done, late, t0 = serve.open_loop(svc, progs, pl)
            lat = done - due
            grow = growing(lat)
            ok = bool(np.isfinite(lat).all()) and not grow
            print(json.dumps({
                "rate_per_s": rate, "requests": len(lat),
                "p50_ms": 1e3 * drivers.percentile(lat, 50),
                "p95_ms": 1e3 * drivers.percentile(lat, 95),
                "completed_per_s": float(np.isfinite(lat).sum()
                                         / (np.nanmax(np.where(
                                             np.isfinite(done), done,
                                             np.nan)) - t0)),
                "late_p95_ms": 1e3 * drivers.percentile(late, 95),
                "backlog_grows": grow, "sustained": ok}), flush=True)
            if ok:
                knee, misses = rate, 0
            else:
                misses += 1
                if misses == 2:
                    break
    print(json.dumps({"workload": cell.name, "knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
