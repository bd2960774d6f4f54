"""Observability gate: trace coverage, zero-overhead-when-disabled
tracing, and bounded-overhead always-on telemetry.

Three contracts, enforced as a CI gate:

* **Coverage** — a traced fleet drain must produce a Chrome/Perfetto
  trace whose span tree accounts for >= ``MIN_COVERAGE`` of the drain's
  wall time (the spans are not decorative: if a phase went missing the
  trace lies about where time goes).
* **Trace overhead** — the tracing-*disabled* path must not be
  measurably slower than the enabled path: instrumentation is one
  contextvar read per span site when off, so a regression here means
  someone put real work outside the ``sp.active`` guard.  Drains with
  tracing off and on are interleaved best-of-N; the gate fails when
  ``best_off > OVERHEAD_TOLERANCE * best_on`` (plus an absolute noise
  floor so microsecond jitter cannot flake the build).
* **Telemetry overhead** — unlike the tracer, the metrics registry and
  flight recorder stay ON in production, so their contract is bounded
  cost, not zero cost: an interleaved best-of-N serving run with full
  telemetry must stay within ``METRICS_OVERHEAD_TOLERANCE`` (3%) of
  the stripped-telemetry run, and the two runs' results must be
  bit-identical.

``--trace OUT.json`` writes the traced drain's Perfetto JSON (CI uploads
it as an artifact); ``--smoke`` shrinks the workload for the PR gate.
Any failure exits 1.

  PYTHONPATH=src python -m benchmarks.obs --smoke --trace trace.json
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from benchmarks.fleet import build_jobs, fleet_config  # noqa: E402
from repro.fleet import Fleet, FleetService, enable_compile_cache  # noqa: E402
from repro.obs import Tracer, aggregate  # noqa: E402
from repro.obs.report import build_tree, coverage  # noqa: E402

#: the drain span tree must account for this fraction of drain wall time
MIN_COVERAGE = 0.95
#: tracing-disabled drains may not be slower than enabled ones by more
#: than this factor ...
OVERHEAD_TOLERANCE = 1.03
#: ... beyond this absolute noise floor (seconds): sub-millisecond
#: jitter on a loaded CI runner is not a tracing regression
OVERHEAD_FLOOR_S = 1e-3
#: the full always-on telemetry stack (registry + histograms + gauges +
#: flight recorder) may cost at most this factor of serve throughput ...
METRICS_OVERHEAD_TOLERANCE = 1.03
#: ... beyond this absolute floor: serve walls are tens of milliseconds
#: and carry thread-scheduling jitter a drain microbenchmark doesn't
METRICS_OVERHEAD_FLOOR_S = 0.01


def _submit_all(fleet: Fleet, jobs) -> list[int]:
    return [fleet.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim,
                         weight=b.image.static_cycle_estimate())
            for b in jobs]


def traced_drain(cfg, jobs, batch: int) -> tuple[Tracer, dict]:
    """One warmed, traced drain; returns the tracer and its results."""
    warm = Fleet(cfg, batch_size=batch)
    _submit_all(warm, jobs)
    warm.drain()

    fleet = Fleet(cfg, batch_size=batch, trace=True)
    _submit_all(fleet, jobs)
    results = fleet.drain()
    return fleet.tracer, results


def check_coverage(tracer: Tracer) -> dict:
    events = tracer.to_chrome()["traceEvents"]
    roots = build_tree(events)
    fracs = coverage(roots, name="drain")
    if not fracs:
        raise AssertionError("trace has no drain span")
    cov = min(fracs)
    n_spans = sum(1 for e in events if e.get("ph") == "X")
    return {"drains": len(fracs), "spans": n_spans,
            "min_coverage": round(cov, 4), "ok": cov >= MIN_COVERAGE}


def check_identity(cfg, jobs, batch: int) -> bool:
    """Tracing must never change results: bit-compare a traced drain
    against an untraced one, shared memory and cycles both."""
    import numpy as np

    def run(trace):
        fleet = Fleet(cfg, batch_size=batch, trace=trace)
        handles = _submit_all(fleet, jobs)
        results = fleet.drain()
        return [results[h] for h in handles]

    ref, got = run(False), run(True)
    for b, r0, r1 in zip(jobs, ref, got):
        assert np.array_equal(r0.shared_u32(), r1.shared_u32()), b.name
        assert r0.cycles == r1.cycles, b.name
    return True


def bench_overhead(cfg, jobs, batch: int, repeats: int) -> dict:
    """Interleaved best-of-N drain times, tracing off vs on."""
    fleets = {"off": Fleet(cfg, batch_size=batch),
              "on": Fleet(cfg, batch_size=batch, trace=True)}
    for f in fleets.values():            # warm compile + residency caches
        _submit_all(f, jobs)
        f.drain()

    best = {"off": float("inf"), "on": float("inf")}
    for _ in range(repeats):
        for mode, f in fleets.items():   # interleave: shared noise hits both
            _submit_all(f, jobs)
            t0 = time.perf_counter()
            f.drain()
            best[mode] = min(best[mode], time.perf_counter() - t0)
    ok = best["off"] <= best["on"] * OVERHEAD_TOLERANCE + OVERHEAD_FLOOR_S
    return {"off_us": round(best["off"] * 1e6, 1),
            "on_us": round(best["on"] * 1e6, 1),
            "ratio": round(best["off"] / best["on"], 3), "ok": ok}


def bench_metrics_overhead(cfg, jobs, batch: int, repeats: int) -> dict:
    """Interleaved best-of-N serving walls, telemetry on vs off.

    ``telemetry=False`` keeps the counters (they are the stats store)
    but strips the latency histograms, gauges and flight recorder —
    exactly the delta the 3% budget covers.  Results from the two
    regimes are also bit-compared against a plain drain's: always-on
    telemetry must never touch an answer."""
    import numpy as np

    from benchmarks.fleet import run_fleet

    _, truth = run_fleet(cfg, jobs, batch)      # ground truth + warmup

    def serve(tm):
        svc = FleetService(cfg, batch, max_delay_s=0.002, telemetry=tm,
                           slo_latency_s=0.1)
        t0 = time.perf_counter()
        futs = [svc.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim,
                           weight=b.image.static_cycle_estimate())
                for b in jobs]
        svc.close()
        wall = time.perf_counter() - t0
        return wall, [f.result() for f in futs]

    serve(True)                                 # absorb serve-path warmup
    serve(False)
    best = {True: float("inf"), False: float("inf")}
    results = {}
    for _ in range(repeats):
        for tm in (False, True):                # interleave: shared noise
            wall, res = serve(tm)
            best[tm] = min(best[tm], wall)
            results[tm] = res
    for tm in (False, True):
        for i, (r, t) in enumerate(zip(results[tm], truth)):
            assert np.array_equal(r.shared, t.shared), \
                f"job {i} diverged with telemetry={tm}"
    n = len(jobs)
    ok = best[True] <= (best[False] * METRICS_OVERHEAD_TOLERANCE
                        + METRICS_OVERHEAD_FLOOR_S)
    return {"off_jobs_per_sec": round(n / best[False], 1),
            "on_jobs_per_sec": round(n / best[True], 1),
            "off_ms": round(best[False] * 1e3, 2),
            "on_ms": round(best[True] * 1e3, 2),
            "ratio": round(best[True] / best[False], 3), "ok": ok}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=2,
                    help="jobs = rounds * batch")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--mix", default="suite")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workload for the CI gate")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write the traced drain's Perfetto JSON here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.smoke:
        args.rounds, args.repeats, args.mix = 1, 3, "light"
    cfg = fleet_config()
    jobs = build_jobs(cfg, args.batch * args.rounds, args.mix)

    tracer, results = traced_drain(cfg, jobs, args.batch)
    if args.trace:
        tracer.save(args.trace)
        print(f"# wrote trace {args.trace}", file=sys.stderr)

    cov = check_coverage(tracer)
    agg = aggregate(r.counters for r in results.values())
    ident = check_identity(cfg, jobs, args.batch)
    over = bench_overhead(cfg, jobs, args.batch, args.repeats)
    mover = bench_metrics_overhead(cfg, jobs, args.batch, args.repeats)

    print("name,us_per_call,derived")
    print(f"obs/coverage_{args.mix}_{args.batch},0.0,"
          f"min_coverage={cov['min_coverage']};spans={cov['spans']}")
    print(f"obs/overhead_{args.mix}_{args.batch},"
          f"{over['on_us'] / len(jobs):.1f},"
          f"off_us={over['off_us']};on_us={over['on_us']};"
          f"ratio={over['ratio']}")
    print(f"obs/metrics_overhead_{args.mix}_{args.batch},"
          f"{mover['on_ms'] * 1e3 / len(jobs):.1f},"
          f"off_jobs_per_sec={mover['off_jobs_per_sec']};"
          f"on_jobs_per_sec={mover['on_jobs_per_sec']};"
          f"ratio={mover['ratio']}")
    if agg is not None:
        print(f"obs/counters_{args.mix}_{args.batch},0.0,"
              f"instrs={agg.instrs};backedges={agg.loop_backedges};"
              f"lane_util={agg.lane_utilization:.3f}")

    ok = cov["ok"] and over["ok"] and mover["ok"] and ident
    if not cov["ok"]:
        print(f"# FAIL: drain span coverage {cov['min_coverage']} "
              f"< {MIN_COVERAGE}", file=sys.stderr)
    if not over["ok"]:
        print(f"# FAIL: tracing-disabled drain {over['off_us']}us is "
              f">{round((OVERHEAD_TOLERANCE - 1) * 100)}% slower than "
              f"enabled {over['on_us']}us", file=sys.stderr)
    if not mover["ok"]:
        print(f"# FAIL: full-telemetry serve {mover['on_ms']}ms is "
              f">{round((METRICS_OVERHEAD_TOLERANCE - 1) * 100)}% slower "
              f"than stripped {mover['off_ms']}ms", file=sys.stderr)
    if ok:
        print("# obs gate passed (coverage, trace overhead, telemetry "
              "overhead, bit-identity)", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
