"""Traffic drivers, found by name, and what they share.

A traffic mix is a data file (``bench/traffic/<mix>.json``) whose
``driver`` key names a module here, ``bench/drivers/<driver>.py``.  It
has ``program_names(traffic)``, the programs the mix runs, and
``run(env)``, which drives the cell's window and returns an
:class:`Outcome`.
A new arrival or measurement shape is a new file here plus a mix that
names it; nothing else changes.

Every job carries its own data, drawn from the seed
(``reference.make_inputs``).  A driver warms every program at the
shapes the window uses before the window opens, and keeps what the
check needs: each job's answer words and simulated statistics.
"""
from __future__ import annotations

import collections
import dataclasses
import importlib
import json
import math
import pathlib
import re

import numpy as np

from .. import check, reference
from ..manifest import ManifestError

HERE = pathlib.Path(__file__).resolve().parent
WINDOW = "bench.window"


def load(name: str):
    """The driver module ``bench/drivers/<name>.py``."""
    if not (isinstance(name, str) and re.fullmatch(r"[a-z][a-z0-9_]*", name)
            and (HERE / f"{name}.py").is_file()):
        raise ManifestError(f"no traffic driver {name!r} in {HERE}")
    return importlib.import_module(f"{__name__}.{name}")


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def seed_key(seed: int) -> int:
    """``--seed`` as a non-negative 64-bit seed-sequence word."""
    return int(seed) % 2**64


def make_egpu(doc: dict):
    """The ``EGPUConfig`` a configuration file states."""
    from repro.core.config import CostParams, EGPUConfig
    fields = dict(doc["egpu"])
    cost = CostParams(**fields.pop("cost", {}))
    return EGPUConfig(cost=cost, **fields)


class CompileCounter:
    """Counts XLA compiles and jaxpr traces while ``armed``."""

    def __init__(self):
        self.armed = False
        self.compiles = 0
        self.traces = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if not self.armed:
            return
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1

    def install(self) -> "CompileCounter":
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self)
        return self


def registry_totals(reg) -> dict[str, float]:
    """The fleet and service counters the per-layer readers use."""
    sync = 0.0
    for m in reg.snapshot().metrics:
        if m["name"] == "fleet_device_sync_seconds":
            sync = sum(s["sum"] for s in m["samples"])
    return {"wall_s": reg.total("fleet_wall_seconds_total"),
            "sync_s": sync,
            "batches": reg.total("fleet_batches_total"),
            "dispatches": reg.total("serve_dispatches_total"),
            "dispatched_jobs": reg.total("serve_dispatched_jobs_total")}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


@dataclasses.dataclass
class Env:
    """What a driver needs: the cell's instance, programs and mix, and
    the run's seed, window and counters."""

    cfg: object                       # EGPUConfig
    batch_size: int
    chips: int
    programs: dict                    # name -> bench.programs.Program
    traffic: dict
    seed: int
    window_s: float
    counter: CompileCounter
    profile: object = None            # context manager around the window


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: dict                     # end-to-end name -> value
    blocks: list                      # check.Block per program chunk
    registry: dict                    # counter deltas over the window
    lane_steps: int | None
    notes: list                       # lines printed before the result
    t_window: float                   # perf_counter at the first job


class Rows:
    """Answer words and simulated statistics of ``k`` jobs of one
    program, filled in as results arrive."""

    def __init__(self, prog, k: int):
        self.prog = prog
        self.got: list = [None] * k
        self.sim: list = [None] * k
        self.tiers: collections.Counter = collections.Counter()

    def put(self, j: int, res) -> None:
        self.got[j] = reference.result_words(self.prog.kind, self.prog.n,
                                             res.shared)
        self.sim[j] = check.sim_row(res)
        self.tiers[res.tier] += 1

    def block(self, inputs: np.ndarray) -> check.Block:
        k = len(self.got)
        done = np.array([g is not None for g in self.got], bool)
        got = np.zeros((k, reference.result_size(self.prog.kind,
                                                 self.prog.n)), np.uint32)
        width = next((len(r) for r in self.sim if r is not None), 0)
        sim = np.zeros((k, width), np.int64)
        for j in np.flatnonzero(done):
            got[j], sim[j] = self.got[j], self.sim[j]
        return check.Block(self.prog.kind, self.prog.n, inputs, got, sim,
                           done)


def tier_line(rows) -> str:
    """Which execution tiers carried the window's jobs."""
    total = sum((r.tiers for r in rows), collections.Counter())
    return "jobs per tier: " + json.dumps(dict(sorted(total.items())))


def percentile(values: np.ndarray, q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 100]) of all values."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, math.ceil(q / 100 * len(v)) - 1)])
