"""The open-loop serve: ``FleetService.submit`` on a Poisson schedule at
the mix's ``rate_per_s`` over ``--seconds``, each request one of the
mix's ``programs``, through a ``FleetService`` at its defaults.

Every seed gets the same inter-arrival gaps and the same number of
requests of each program, in an order drawn from the seed.  A request
is timed from when it was due to when its future resolved; one that
fails counts as infinitely late.  Before the window, one request of
each program compiles it, and ``WARM_SECONDS`` of the mix at its rate
bring the service to its steady state.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time

import numpy as np

from .. import reference
from . import (WINDOW, Env, Outcome, Rows, annotate, delta, percentile,
               registry_totals, seed_key, tier_line)

WARM_SECONDS = 1.0
#: seconds past the window's close that a run waits for answers
LATE_WAIT_S = 60.0


def program_names(traffic: dict) -> list[str]:
    return list(traffic["programs"])


@dataclasses.dataclass
class Plan:
    """An open-loop schedule: request ``i`` is due ``offsets[i]``
    seconds after the start and is job ``row[i]`` of program
    ``prog[i]``, whose inputs are ``inputs[prog[i]][row[i]]``."""

    offsets: np.ndarray
    prog: np.ndarray
    row: np.ndarray
    inputs: list


def plan(progs, rate: float, seconds: float, key) -> Plan:
    """``round(rate * seconds)`` requests.  The gaps are the quantiles
    of an exponential distribution of mean ``1 / rate`` and the
    programs come round-robin, so every seed gets the same gaps and the
    same count of each program; the seed only shuffles both orders and
    draws the data."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng(key)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    prog = np.resize(np.arange(len(progs)), n)
    rng.shuffle(prog)
    row = np.zeros(n, np.int64)
    counts = np.zeros(len(progs), np.int64)
    for i, p in enumerate(prog):
        row[i] = counts[p]
        counts[p] += 1
    inputs = [reference.make_inputs(p.kind, p.n,
                                    np.random.default_rng(key + [i]),
                                    int(counts[i]))
              for i, p in enumerate(progs)]
    return Plan(np.cumsum(gaps), prog, row, inputs)


def open_loop(svc, progs, pl: Plan):
    """Drive ``svc`` on ``pl``; returns (rows, due, done, late, t0)."""
    n = len(pl.offsets)
    rows = [Rows(p, len(x)) for p, x in zip(progs, pl.inputs)]
    done_t = np.full(n, np.inf)
    late = np.zeros(n)
    lock = threading.Lock()
    left = [n]
    all_done = threading.Event()

    def on_done(i, p, j, fut):
        t = time.perf_counter()
        try:
            # a request that raised stays at an infinite latency and
            # without an answer, which the check counts as missing
            if fut.exception() is None:
                done_t[i] = t
                rows[p].put(j, fut.result())
        finally:
            with lock:
                left[0] -= 1
                if not left[0]:
                    all_done.set()

    t0 = time.perf_counter() + 0.001
    due = t0 + pl.offsets
    for i in range(n):
        wait = due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        p, j = int(pl.prog[i]), int(pl.row[i])
        late[i] = time.perf_counter() - due[i]
        with annotate("bench.submit"):
            fut = svc.submit(progs[p].image, pl.inputs[p][j],
                             tdx_dim=progs[p].tdx_dim)
        fut.add_done_callback(functools.partial(on_done, i, p, j))
    with annotate("bench.wait"):
        all_done.wait(LATE_WAIT_S + 1.0)
    return rows, due, done_t, late, t0


def run(env: Env) -> Outcome:
    from repro.fleet import FleetService
    progs = [env.programs[name] for name in env.traffic["programs"]]
    rate = float(env.traffic["rate_per_s"])
    seed = seed_key(env.seed)
    svc = FleetService(env.cfg, env.batch_size,
                       devices="all" if env.chips > 1 else None)
    try:
        with annotate("bench.warm"):
            futs = [svc.submit(p.image, x[0], tdx_dim=p.tdx_dim)
                    for p, x in zip(progs, plan(progs, len(progs), 1.0,
                                                [0, 0]).inputs)]
            for f in futs:
                f.result(timeout=1200)
            open_loop(svc, progs, plan(progs, rate, WARM_SECONDS, [0, 1]))
        pl = plan(progs, rate, env.window_s, [seed, 1])
        before = registry_totals(svc.metrics)
        env.counter.armed = True
        with (env.profile or contextlib.nullcontext()), annotate(WINDOW):
            rows, due, done_t, late, t0 = open_loop(svc, progs, pl)
        env.counter.armed = False
        moved = delta(before, registry_totals(svc.metrics))
    finally:
        svc.close()
    lat = done_t - due
    blocks = [r.block(x) for r, x in zip(rows, pl.inputs)]
    failed = int(np.count_nonzero(~np.isfinite(lat)))
    return Outcome(
        attempted=len(lat), failed=failed,
        metrics={"p50_ms": 1e3 * percentile(lat, 50)},
        blocks=blocks, registry=moved, lane_steps=None,
        notes=[f"serve: {len(lat)} requests at {rate} /s over "
               f"{pl.offsets[-1]:.6f} s of arrivals; latency p95 "
               f"{1e3 * percentile(lat, 95):.6f} ms, p99 "
               f"{1e3 * percentile(lat, 99):.6f} ms",
               f"generator lateness: p50 {1e3 * percentile(late, 50):.6f} "
               f"ms, p95 {1e3 * percentile(late, 95):.6f} ms, max "
               f"{1e3 * float(late.max()):.6f} ms",
               tier_line(rows)],
        t_window=t0)
