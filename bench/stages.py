"""The program's own spans and kernels in a device trace, and the
per-layer numbers that read them.

While a profiler session captures, the program (``repro.obs`` spans)
annotates the host's timeline with ``egpu.<stage>`` (``egpu.drain``,
``egpu.dispatch``, ``egpu.serve.wait``, ...), and each tier kernel is
an XLA module named ``jit_egpu_<tier>[_<program digest>]``.  From the
plain data of :func:`bench.tracing.load` this reduces a trace to:

* ``idle_by_stage``: the device-idle seconds of the window, summed over
  the devices, split by the innermost ``egpu.*`` span covering each
  instant (the one that began last, on any thread), or ``none`` where
  no program span covers it;
* ``kernel_s_per_device``: device seconds in ``jit_egpu_*`` modules;
* ``device_modules``: the modules that took most device time, named
  without their fingerprint.

Each is ``None`` where the trace has no program span or no tier kernel,
as the trace of a program without this instrumentation has none.  The
``read_*`` functions are per-layer metrics on the context a run gives
its readers, with the registry totals of :func:`registry_extra` added;
each returns ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import re

from . import tracing

PREFIX = "egpu."
NONE = "none"
#: spans in which the program waits for work: device idle time there is
#: not the program's.  ``device_sync`` is not one: the device idles under
#: it while the batch's input copy, which the program issued, lands, and
#: where the host blocks on that copy varies from run to run
WAITS = frozenset({"serve.wait"})
#: the XLA module name of a tier kernel, as the program gives it; a copy
#: of ``repro.core.executor.KERNEL_MODULE_RE``, kept equal by
#: ``bench/tests/test_bench_stages.py::test_the_kernel_pattern_is_the_programs``
KERNEL = re.compile(
    r"jit_egpu_(superblock|blocks|interp|mega_superblock|mega_blocks)"
    r"(?:_([0-9a-f]{8}))?")
TOP = tracing.TOP


def program_spans(planes: list[dict]) -> list[tuple[str, int, int]]:
    """The program's spans on the host, ``(stage, start, end)``, the
    stage without its ``egpu.`` prefix."""
    devs = {p["name"] for p in tracing.device_planes(planes)}
    return [(n[len(PREFIX):], s, s + d) for p in planes
            if p["name"] not in devs
            for ln in p["lines"] for n, s, d in ln["events"]
            if n.startswith(PREFIX)]


def stage_pieces(spans, lo: int, hi: int) -> list[tuple[int, int, str]]:
    """``[lo, hi)`` cut at every span edge, each piece labelled with the
    innermost span covering it: the latest to begin, and of those the
    first to end; ``none`` where no span covers it."""
    edges = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                               if lo < x < hi})
    order = sorted(spans, key=lambda sp: sp[1])
    i, active, out = 0, [], []
    for a, b in zip(edges, edges[1:]):
        while i < len(order) and order[i][1] <= a:
            active.append(order[i])
            i += 1
        active = [sp for sp in active if sp[2] > a]
        name = (max(active, key=lambda sp: (sp[1], -sp[2]))[0]
                if active else NONE)
        out.append((a, b, name))
    return out


def _holes(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def idle_by_stage(planes: list[dict]) -> dict[str, float] | None:
    """Device-idle seconds in the window, summed over the devices, by
    the innermost program span covering them, largest first."""
    spans = program_spans(planes)
    devs = tracing.device_planes(planes)
    if not spans or not devs:
        return None
    lo, hi = tracing.window_of(planes)
    pieces = stage_pieces(spans, lo, hi)
    out: dict[str, int] = {}
    for p in devs:
        busy = tracing.union(tracing.busy_intervals(p), lo, hi)
        j = 0
        for s, e in _holes(busy, lo, hi):
            while pieces[j][1] <= s:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < e:
                a, b, name = pieces[k]
                out[name] = out.get(name, 0) + min(b, e) - max(a, s)
                k += 1
    return {n: v / 1e9 for n, v in
            sorted(out.items(), key=lambda kv: -kv[1])}


def _module(name: str) -> str:
    """A module's name without the fingerprint the trace appends."""
    return name.split("(", 1)[0]


def modules(planes: list[dict]) -> dict | None:
    """Device seconds in tier kernels per device, and the top modules
    by device time; ``None`` where no module is a tier kernel."""
    devs = tracing.device_planes(planes)
    if not devs:
        return None
    lo, hi = tracing.window_of(planes)
    per_dev, by_name = [], {}
    for p in devs:
        ln = next((x for x in p["lines"] if x["name"] == "XLA Modules"),
                  None)
        kern = 0
        for n, s, d in (ln["events"] if ln else []):
            t = min(s + d, hi) - max(s, lo)
            if t <= 0:
                continue
            m = _module(n)
            by_name[m] = by_name.get(m, 0) + t
            if KERNEL.fullmatch(m):
                kern += t
        per_dev.append(kern / 1e9)
    if not any(KERNEL.fullmatch(m) for m in by_name):
        return None
    return {"kernel_s_per_device": per_dev,
            "device_modules": [[n, d / 1e9] for n, d in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:TOP]]}


def reduce(planes: list[dict]) -> dict:
    """The keys this module adds to :func:`bench.tracing.reduce`'s."""
    mods = modules(planes) or {}
    return {"idle_by_stage": idle_by_stage(planes),
            "kernel_s_per_device": mods.get("kernel_s_per_device"),
            "device_modules": mods.get("device_modules")}


def registry_extra(reg) -> dict[str, float | None]:
    """The program counters the readers below use, each ``None`` where
    the registry has no such family."""
    names = {m["name"] for m in reg.snapshot().metrics}

    def total(name, **labels):
        return reg.total(name, **labels) if name in names else None

    return {"collect_s": total("fleet_collect_seconds_total"),
            "queue_wait_s": total("serve_queue_wait_seconds_total"),
            "compile_misses": total("fleet_compile_cache_total",
                                    result="miss")}


def delta(a: dict, b: dict) -> dict:
    """``b - a`` per key, ``None`` where either side is.  A stand-in for
    ``bench.drivers.delta`` until the benchmark reads these counters."""
    return {k: None if a[k] is None or b[k] is None else b[k] - a[k]
            for k in a}


def read_idle_in_program_ms_per_batch(ctx) -> float | None:
    """Device-idle ms per batch under a program span: any innermost
    span but a wait for work (``WAITS``), and not outside every program
    span."""
    t, r = ctx["trace"], ctx["registry"]
    idle = t and t.get("idle_by_stage")
    if not idle or not r.get("batches"):
        return None
    work = sum(v for n, v in idle.items() if n not in WAITS and n != NONE)
    return 1e3 * work / r["batches"]


def read_collect_ms_per_batch(ctx) -> float | None:
    """Host ms per batch collecting its results."""
    r = ctx["registry"]
    if r.get("collect_s") is None or not r.get("batches"):
        return None
    return 1e3 * r["collect_s"] / r["batches"]


def read_queue_wait_ms(ctx) -> float | None:
    """Mean ms a dispatched request waited in the service's queue."""
    r = ctx["registry"]
    if r.get("queue_wait_s") is None or not r.get("dispatched_jobs"):
        return None
    return 1e3 * r["queue_wait_s"] / r["dispatched_jobs"]


def read_kernel_ns_per_lane_step(ctx) -> float | None:
    """Device ns in tier kernels, summed over the chips, per simulated
    lane-step offered."""
    t, steps = ctx["trace"], ctx["lane_steps"]
    kern = t and t.get("kernel_s_per_device")
    if kern is None or not steps:
        return None
    return 1e9 * sum(kern) / steps


#: metric name -> reader, as ``BENCHMARK.json`` would name them
METRICS = {
    "idle_in_program_ms_per_batch.drain": read_idle_in_program_ms_per_batch,
    "idle_in_program_ms_per_batch.serve": read_idle_in_program_ms_per_batch,
    "collect_ms_per_batch.drain": read_collect_ms_per_batch,
    "queue_wait_ms.serve": read_queue_wait_ms,
    "kernel_ns_per_lane_step.drain": read_kernel_ns_per_lane_step,
}
