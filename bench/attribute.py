"""Where a cell's traced window goes, by the program's own spans and
kernels, run on the chip; its lines go into ``PERF.md``.

    python3 bench/attribute.py --workload dp_suite_drain --seed 7 \\
        [--record FILE] [--span-cost N]

Runs the cell's traced window (``bench/run.py``'s ``TRACE_SECONDS``)
under the profiler as ``bench/run.py --trace 1`` does and prints one
JSON line: the cell's per-layer metrics (its readers in
``bench/metrics/``), the ``bench/stages.py`` metrics of its driver, the
trace's ``idle_by_stage`` and ``device_modules`` beside the
existing breakdown, the program's compile-cache misses in the window,
and ``correct``.  ``--record`` also writes the first ``RECORD_MS`` of
the window's trace (the device planes, and the host's ``bench.*`` and
``egpu.*`` spans) as test data.  ``--span-cost N`` first times ``N``
calls of ``repro.obs.span`` with nothing installed and with a flight
recorder installed, each with the profiler off and capturing, and
prints the nanoseconds per call as a line of its own.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
#: milliseconds of the window that ``--record`` keeps as test data
RECORD_MS = 30.0


def span_cost(n: int) -> dict:
    """Nanoseconds per ``with span(...)`` call: nothing installed and
    a flight recorder installed, with the profiler off and on."""
    import jax
    from repro.obs import FlightRecorder, span

    def per_call() -> float:
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with span("cost"):
                pass
        return (time.perf_counter_ns() - t0) / n

    out = {}
    rec = FlightRecorder(4096)
    with tempfile.TemporaryDirectory(prefix="span-cost-") as tmp:
        for profiling in (False, True):
            if profiling:
                jax.profiler.start_trace(tmp)
            out[f"bare_profiler_{'on' if profiling else 'off'}_ns"] = \
                min(per_call() for _ in range(3))
            with rec.installed():
                out[f"recorder_profiler_{'on' if profiling else 'off'}"
                    f"_ns"] = min(per_call() for _ in range(3))
            if profiling:
                jax.profiler.stop_trace()
    return out


def crop(planes: list[dict], ms: float) -> list[dict]:
    """The first ``ms`` of the window: the device planes' events that
    start in it, and the host's ``bench.*``/``egpu.*`` spans that
    overlap it, with the window span cut to its length."""
    from bench import tracing
    lo, _ = tracing.window_of(planes)
    hi = lo + int(ms * 1e6)
    devs = {p["name"] for p in tracing.device_planes(planes)}
    out = []
    for p in planes:
        lines = []
        for ln in p["lines"]:
            if p["name"] in devs:
                ev = [e for e in ln["events"] if lo <= e[1] < hi]
            else:
                ev = [[n, s, min(d, hi - s) if n == tracing.WINDOW else d]
                      for n, s, d in ln["events"]
                      if n.startswith(("bench.", "egpu."))
                      and s < hi and s + d > lo]
            if ev:
                lines.append({"name": ln["name"], "events": ev})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out


@contextlib.contextmanager
def extended_totals():
    """The drivers' window deltas, with ``stages.registry_extra``'s
    counters added, for the duration of the block.  A stand-in until the
    benchmark reads these counters itself: ``bench.drivers.registry_totals``
    then takes them, and this, ``attribute`` and ``stages.delta`` go."""
    from bench import drivers, stages
    from bench.drivers import drain, serve

    def totals(reg):
        return {**drivers.registry_totals(reg),
                **stages.registry_extra(reg)}

    saved = [(m, m.registry_totals, m.delta) for m in (drain, serve)]
    for m, _, _ in saved:
        m.registry_totals, m.delta = totals, stages.delta
    try:
        yield
    finally:
        for m, t, d in saved:
            m.registry_totals, m.delta = t, d


def attribute(cell, *, seed: int, seconds: float, devs,
              doc: dict | None = None, traffic: dict | None = None,
              spec_root: pathlib.Path | None = None,
              record: pathlib.Path | None = None) -> dict:
    """One traced window of ``cell``; returns the result line.  The
    configuration and traffic come from their files unless given."""
    from bench import check, drivers, manifest, programs, run, stages
    from bench import tracing
    doc = doc if doc is not None else manifest.load_config(cell.config)
    traffic = (traffic if traffic is not None
               else manifest.load_traffic(cell.traffic))
    cfg = drivers.make_egpu(doc)
    driver = drivers.load(traffic["driver"])
    names = sorted(set(driver.program_names(traffic)))
    progs = {n: programs.build(cfg, n) for n in names}
    specs = {(p.kind, p.n): check.load_spec(spec_root or manifest.BENCH,
                                            cell.config, n)
             for n, p in progs.items()}
    prof = run.Profile()
    env = drivers.Env(
        cfg=cfg, batch_size=int(doc["batch_size"]), chips=cell.chips,
        programs=progs, traffic=traffic, seed=seed, window_s=seconds,
        counter=drivers.CompileCounter().install(), profile=prof)
    with extended_totals():
        out = driver.run(env)
    for line in out.notes:
        run.log(line)
    planes = prof.read()
    if record is not None:
        record.write_text(json.dumps(crop(planes, RECORD_MS)))
    red = tracing.reduce(planes)
    if red is not None:
        red.update(stages.reduce(planes))
    ctx = {"batch_size": env.batch_size, "registry": out.registry,
           "trace": red, "lane_steps": out.lane_steps}
    metrics = {m.name: manifest.metric_reader(m.name)(ctx)
               for m in cell.per_layer}
    suffix = "." + traffic["driver"]
    metrics.update({name: read(ctx) for name, read in
                    stages.METRICS.items() if name.endswith(suffix)})
    numbers = check.compare(out.blocks, specs)
    keys = ("busy_s", "window_s", "idle_by_stage", "device_modules",
            "device_ops", "idle_gaps")
    return {"workload": cell.name, "seed": seed,
            "correct": check.verdict(numbers), "metrics": metrics,
            **{k: red and red[k] for k in keys},
            "registry": out.registry,
            "compiles_in_window": env.counter.compiles,
            "device": devs[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", type=pathlib.Path)
    ap.add_argument("--span-cost", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import manifest, run
    cell = manifest.load().cell(args.workload)
    try:
        devs = run.device_gate(cell.chips)
    except run.GateError as e:
        run.log(f"attribution refused: {e}")
        return 1
    from repro.fleet import enable_compile_cache
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    enable_compile_cache()
    if args.span_cost:
        print(json.dumps({"span_cost": span_cost(args.span_cost)}),
              flush=True)
    line = attribute(cell, seed=args.seed, seconds=run.TRACE_SECONDS,
                     devs=devs, record=args.record)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
