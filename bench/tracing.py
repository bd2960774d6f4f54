"""Profiler capture and the reduction from a device trace to numbers.

The run wraps its steps in ``jax.profiler.TraceAnnotation``s of its own
(``bench.window`` around the traced window, ``bench.submit``,
``bench.drain``, ``bench.collect``, ``bench.wait`` inside it), so the
host's spans land on the trace's clock next to the device's.

The reduction works on plain data, ``[{"name", "lines": [{"name",
"events": [[name, start_ns, duration_ns], ...]}]}]``, so it can be
tested on a small recorded trace:

* busy time of a device: the union of its ``XLA Modules`` events (its
  ``XLA Ops`` where a plane has no module line), clipped to the window;
* idle gaps: the holes in that union, each labelled with the innermost
  ``bench.*`` span on the host that covers the gap's midpoint;
* device ops: ``XLA Ops`` time summed by op name.
"""
from __future__ import annotations

import pathlib
import re

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
TOP = 10


def load(path: str | pathlib.Path) -> list[dict]:
    """The planes of an ``.xplane.pb`` file, as plain data."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [[e.name, int(e.start_ns),
                                    int(e.duration_ns)]
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in pd.planes]


def find_xplane(log_dir: str | pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_planes(planes: list[dict]) -> list[dict]:
    return [p for p in planes if _DEVICE.match(p["name"])]


def _line(plane: dict, name: str):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln
    return None


def busy_intervals(plane: dict) -> list[tuple[int, int]]:
    ln = _line(plane, "XLA Modules") or _line(plane, "XLA Ops")
    if ln is None:
        return []
    return [(s, s + d) for _, s, d in ln["events"]]


def host_spans(planes: list[dict]) -> list[tuple[str, int, int]]:
    """The benchmark's own annotations, ``(name, start, end)``."""
    return [(n, s, s + d) for p in planes if not _DEVICE.match(p["name"])
            for ln in p["lines"] for n, s, d in ln["events"]
            if n.startswith("bench.")]


def window_of(planes: list[dict]) -> tuple[int, int]:
    w = [(s, e) for n, s, e in host_spans(planes) if n == WINDOW]
    if not w:
        raise ValueError(f"the trace holds no {WINDOW} span")
    return min(s for s, _ in w), max(e for _, e in w)


def _label(spans, t: float) -> str:
    inner = [(s, n) for n, s, e in spans if s <= t < e and n != WINDOW]
    return max(inner)[1] if inner else "other"


def reduce(planes: list[dict]) -> dict | None:
    """Busy seconds per device, the window's length, the device ops
    that took most time and the longest idle gaps; ``None`` when the
    trace holds no device plane."""
    devs = device_planes(planes)
    if not devs:
        return None
    lo, hi = window_of(planes)
    spans = host_spans(planes)
    busy, gaps = [], []
    ops: dict[str, int] = {}
    for p in devs:
        u = union(busy_intervals(p), lo, hi)
        busy.append(sum(e - s for s, e in u) / 1e9)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
        ln = _line(p, "XLA Ops")
        for name, s, d in (ln["events"] if ln else []):
            if s < hi and s + d > lo:
                ops[name] = ops.get(name, 0) + d
    gaps.sort(reverse=True)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s_per_device": busy,
        "busy_s": sum(busy) / len(busy),
        "device_ops": [[n, d / 1e9] for n, d in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(spans, (s + e) / 2), g / 1e9]
                      for g, s, e in gaps[:TOP]],
    }
