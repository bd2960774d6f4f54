"""The closed-loop drain: rounds of ``Fleet.submit`` for every
``[program, jobs]`` pair of the mix's ``jobs``, then ``Fleet.drain()``.

The window is a whole number of rounds, and the last round starts
before ``--seconds`` has run out.  ``jobs_per_s`` is the jobs of those
rounds over their wall time.  One warm round, with data of its own,
compiles every program at the window's shapes first.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from .. import reference
from . import (WINDOW, Env, Outcome, Rows, annotate, delta, registry_totals,
               seed_key, tier_line)

WARM_ROUNDS = 1


def program_names(traffic: dict) -> list[str]:
    return [name for name, _ in traffic["jobs"]]


def round_inputs(mix, key) -> list[np.ndarray]:
    """The inputs of one drain round: for the ``i``-th ``(program,
    jobs)`` pair of ``mix``, ``jobs`` images drawn from ``key + [i]``."""
    return [reference.make_inputs(p.kind, p.n,
                                  np.random.default_rng(key + [i]), k)
            for i, (p, k) in enumerate(mix)]


def run(env: Env) -> Outcome:
    from repro.fleet import Fleet
    fleet = Fleet(env.cfg, env.batch_size,
                  devices="all" if env.chips > 1 else None)
    mix = [(env.programs[name], int(k)) for name, k in env.traffic["jobs"]]
    per_round = sum(k for _, k in mix)
    seed = seed_key(env.seed)
    lane_steps = 0

    def one_round(inputs):
        nonlocal lane_steps
        with annotate("bench.submit"):
            handles = [[fleet.submit(p.image, x[j], tdx_dim=p.tdx_dim)
                        for j in range(k)]
                       for (p, k), x in zip(mix, inputs)]
        with annotate("bench.drain"):
            res = fleet.drain()
        with annotate("bench.collect"):
            rows = []
            for (p, k), hs in zip(mix, handles):
                r = Rows(p, k)
                for j, h in enumerate(hs):
                    out = res.get(h)
                    if out is not None:
                        r.put(j, out)
                        c = out.counters
                        lane_steps = (None if c is None or lane_steps is None
                                      else lane_steps + c.lane_steps_offered)
                rows.append(r)
        return rows

    for w in range(WARM_ROUNDS):
        one_round(round_inputs(mix, [0, 0, w]))
    lane_steps = 0
    before = registry_totals(fleet.metrics)
    env.counter.armed = True
    rounds: list = []
    with (env.profile or contextlib.nullcontext()), annotate(WINDOW):
        t0 = time.perf_counter()
        while not rounds or time.perf_counter() - t0 < env.window_s:
            with annotate("bench.inputs"):
                inputs = round_inputs(mix, [seed, 1, len(rounds)])
            rounds.append(one_round(inputs))
        t1 = time.perf_counter()
    env.counter.armed = False
    moved = delta(before, registry_totals(fleet.metrics))
    blocks = []
    for r, rows in enumerate(rounds):
        inputs = round_inputs(mix, [seed, 1, r])
        blocks += [rw.block(x) for rw, x in zip(rows, inputs)]
    failed = sum(int(np.count_nonzero(~b.done)) for b in blocks)
    jobs = len(rounds) * per_round
    return Outcome(
        attempted=jobs, failed=failed,
        metrics={"jobs_per_s": jobs / (t1 - t0)},
        blocks=blocks, registry=moved, lane_steps=lane_steps,
        notes=[f"drain: {len(rounds)} rounds of {per_round} jobs in "
               f"{t1 - t0:.6f} s",
               tier_line(rw for rows in rounds for rw in rows)],
        t_window=t0)
