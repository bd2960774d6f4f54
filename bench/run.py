"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload dp_suite_drain --seed 7 \\
        --seconds 30 --trace 0

Steps: refuse anything but the TPU chips the cell asks for; load the
cell's configuration and traffic mix by name; build the programs; warm
every shape the window uses; measure for ``--seconds``; hold every job
of the window to the plain reference; print one JSON line.

With ``--trace 0`` the line's metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler for
``TRACE_SECONDS`` and the metrics are the cell's per-layer
metrics, read by ``bench/metrics/<name>.py``.  The numbers compared for
``correct`` are printed last on standard error and last in the line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, manifest  # noqa: E402

#: the persistent compilation cache, at a fixed path in the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: the window of a ``--trace 1`` run, in seconds
TRACE_SECONDS = 3.0


class GateError(RuntimeError):
    """No chip, or not the chips the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_gate(chips: int):
    """The devices the cell runs on, after refusing anything but TPUs
    and fewer of them than ``chips``."""
    import jax
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    if d0.platform != "tpu":
        raise GateError(f"no TPU: JAX's default device is "
                        f"{d0.platform!r}; the benchmark runs only on the "
                        f"chip")
    if len(devs) < chips:
        raise GateError(f"the cell asks for {chips} chips, JAX sees "
                        f"{len(devs)}")
    return devs[:chips]


def peaks_for(kind: str) -> dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise GateError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _num(v: float) -> float:
    """A JSON-safe number (infinity reads as 1e308)."""
    return v if math.isfinite(v) else 1e308


class Profile:
    """``jax.profiler`` around the window, into a temporary directory
    that is read and removed afterwards."""

    def __init__(self):
        self._tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # annotations only, no call tree
        jax.profiler.start_trace(self._tmp.name, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def read(self) -> list:
        from bench import tracing
        try:
            return tracing.load(tracing.find_xplane(self._tmp.name))
        finally:
            self._tmp.cleanup()


def run_cell(cell: manifest.Cell, *, seed: int, seconds: float,
             trace: bool, devs, doc: dict | None = None,
             traffic: dict | None = None,
             spec_root: pathlib.Path = manifest.BENCH) -> dict:
    """Everything after the device gate; returns the result line.  The
    configuration and traffic come from their files unless given."""
    from bench import drivers, programs
    doc = doc if doc is not None else manifest.load_config(cell.config)
    traffic = (traffic if traffic is not None
               else manifest.load_traffic(cell.traffic))
    cfg = drivers.make_egpu(doc)
    driver = drivers.load(traffic["driver"])
    names = sorted(set(driver.program_names(traffic)))
    progs = {n: programs.build(cfg, n) for n in names}
    specs = {(p.kind, p.n): check.load_spec(spec_root, cell.config, n)
             for n, p in progs.items()}
    prof = Profile() if trace else None
    env = drivers.Env(
        cfg=cfg, batch_size=int(doc["batch_size"]), chips=cell.chips,
        programs=progs, traffic=traffic, seed=seed,
        window_s=TRACE_SECONDS if trace else seconds,
        counter=drivers.CompileCounter().install(), profile=prof)
    out = driver.run(env)
    setup_s = out.t_window - T_START
    mem = memory_peak(devs)
    for line in out.notes:
        log(line)
    log(f"compiles in window: {env.counter.compiles} "
        f"(jaxpr traces {env.counter.traces})")
    numbers = check.compare(out.blocks, specs)
    out.blocks.clear()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    line: dict = {"correct": check.verdict(numbers),
                  "attempted": out.attempted, "failed": out.failed}
    if not trace:
        values = dict(out.metrics, setup_s=setup_s)
        metrics = {m.name: {"value": _num(values[m.name]), "unit": m.unit}
                   for m in cell.end_to_end}
    else:
        from bench import tracing
        red = tracing.reduce(prof.read())
        ctx = {"batch_size": env.batch_size, "registry": out.registry,
               "trace": red, "lane_steps": out.lane_steps}
        metrics = {}
        for m in cell.per_layer:
            v = manifest.metric_reader(m.name)(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            line["breakdown"] = {"device_ops": red["device_ops"],
                                 "idle_gaps": red["idle_gaps"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {k: {"value": _num(v["value"]), "limit": v["limit"]}
                      for k, v in check.as_json(numbers).items()}
    for s in check.lines(numbers):
        log(s)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = manifest.load().cell(args.workload)
        sys.path.insert(0, str(ROOT / "src"))
        import repro.fleet  # noqa: F401  (the system under test)
    except (manifest.ManifestError, ImportError, OSError) as e:
        log(f"benchmark cannot start: {e}")
        return 2
    try:
        devs = device_gate(cell.chips)
        peaks_for(devs[0].device_kind)
    except GateError as e:
        log(f"benchmark refused: {e}")
        return 1
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    log(f"compile cache: {repro.fleet.enable_compile_cache()}")
    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devs=devs)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
