"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); its per-layer metrics
are readers in ``bench/metrics/<metric>.py``.  Adding a cell is a new
file or two and an entry in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent

_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
_UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
         "end_to_end", "per_layer"}


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


def _name(v, what: str) -> str:
    if not isinstance(v, str) or not _NAME.fullmatch(v):
        raise ManifestError(f"{what} {v!r} is not a valid name")
    return v


def _line(v, what: str) -> str:
    if (not isinstance(v, str) or not 1 <= len(v) <= 200 or "\n" in v
            or "\t" in v):
        raise ManifestError(f"{what} must be one line of 1-200 characters")
    return v


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple[str, ...] | None
    layer: str | None = None
    moves: str | None = None
    bound: float | None = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _metric(m: dict, per_layer: bool) -> Metric:
    name = _name(m.get("name"), "metric name")
    unit = m.get("unit")
    if not isinstance(unit, str) or not _UNIT.fullmatch(unit):
        raise ManifestError(f"{name}: unit {unit!r} is not valid")
    if m.get("better") not in ("lower", "higher"):
        raise ManifestError(f"{name}: better must be lower or higher")
    sources = (("device_trace", "program_span", "program_counter",
                "host_clock") if per_layer
               else ("host_clock", "device_trace"))
    if m.get("source") not in sources:
        raise ManifestError(f"{name}: source {m.get('source')!r}")
    wl = m.get("workloads")
    if wl is not None:
        wl = tuple(_name(w, f"{name} workload") for w in wl)
    if per_layer:
        return Metric(name, unit, m["better"], m["source"], wl,
                      layer=_line(m.get("layer"), f"{name} layer"),
                      moves=_name(m.get("moves"), f"{name} moves"))
    bound = m.get("bound")
    if not isinstance(bound, (int, float)) or not 0 < bound <= 0.25:
        raise ManifestError(f"{name}: bound must be in (0, 0.25]")
    return Metric(name, unit, m["better"], m["source"], wl,
                  bound=float(bound))


class Manifest:
    def __init__(self, doc: dict):
        if set(doc) != _KEYS:
            raise ManifestError(f"keys must be exactly {sorted(_KEYS)}")
        self.doc = doc
        self.run_seconds = int(doc["run_seconds"])
        self.configs = {_name(c["name"], "config"): c
                        for c in doc["configs"]}
        self.end_to_end = [_metric(m, False) for m in doc["end_to_end"]]
        self.per_layer = [_metric(m, True) for m in doc["per_layer"]]
        names = [m.name for m in self.end_to_end + self.per_layer]
        if len(set(names)) != len(names):
            raise ManifestError("two metrics share a name")
        e2e = {m.name for m in self.end_to_end}
        for m in self.per_layer:
            if m.moves not in e2e:
                raise ManifestError(f"{m.name} moves unknown {m.moves!r}")
        self.cells: dict[str, Cell] = {}
        for w in doc["workloads"]:
            name = _name(w["name"], "workload")
            if name in self.cells:
                raise ManifestError(f"two cells are named {name}")
            if w["config"] not in self.configs:
                raise ManifestError(f"{name}: unknown config "
                                    f"{w['config']!r}")
            if w.get("chips") not in (1, 4):
                raise ManifestError(f"{name}: chips must be 1 or 4")
            _line(w.get("why"), f"{name} why")
            here = [m for m in self.end_to_end
                    if m.workloads is None or name in m.workloads]
            here_names = {m.name for m in here}
            layer = [m for m in self.per_layer
                     if (m.workloads is None and m.moves in here_names)
                     or (m.workloads is not None and name in m.workloads)]
            self.cells[name] = Cell(
                name=name, config=w["config"],
                traffic=_name(w["traffic"], f"{name} traffic"),
                chips=w["chips"], end_to_end=tuple(here),
                per_layer=tuple(layer))

    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise ManifestError(
                f"no workload {name!r}; cells are {sorted(self.cells)}"
            ) from None


def load(root: pathlib.Path = ROOT) -> Manifest:
    path = root / "BENCHMARK.json"
    try:
        doc = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ManifestError(f"cannot read {path}: {e}") from e
    return Manifest(doc)


def load_config(name: str, bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "configs" / f"{name}.json").read_text())


def load_traffic(name: str, bench: pathlib.Path = BENCH) -> dict:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def metric_reader(name: str, bench: pathlib.Path = BENCH):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise ManifestError(f"no reader {path} for metric {name}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
