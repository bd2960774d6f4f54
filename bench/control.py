"""The control: the plain reference in bfloat16, put in the program's
place, which ``check.py`` has to find not correct.

The configurations state float32 arithmetic; bfloat16 is the step
below it that would tempt a later change.  ``control_words`` computes
every answer of a job set with ``jax.numpy`` in bfloat16 (inputs,
arithmetic and result), on the default device.  The FFT is a DFT
matrix product, as no FFT runs in bfloat16.

    python3 bench/control.py --workload dp_suite_drain --rounds 10 \\
        --seeds 1 2 3

builds, for each seed, the jobs that a run of the cell checks (``--rounds``
drain rounds, or ``--seconds`` of a serve mix's schedule), puts the
control's answers and the spec's statistics in place of the program's,
and prints the numbers ``check.py`` compares with their limits.  It
runs on whatever device JAX gives it; the readings in ``PERF.md`` are
from the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _bf16(x):
    import jax.numpy as jnp
    return jnp.asarray(x).astype(jnp.bfloat16)


def _out(y) -> np.ndarray:
    import jax.numpy as jnp
    return np.asarray(y.astype(jnp.float32)).view(np.uint32)


def control_words(kind: str, n: int, inputs: np.ndarray) -> np.ndarray:
    """``(k, m)`` uint32 answer words of ``kind`` in bfloat16."""
    import jax.numpy as jnp
    x = np.asarray(inputs, np.uint32)
    k = x.shape[0]
    if kind == "bitonic":
        v = _bf16(x[:, :n].view(np.int32).astype(np.float32))
        s = jnp.sort(v, axis=1).astype(jnp.float32)
        return np.asarray(s).astype(np.int32).view(np.uint32)
    f = x.view(np.float32)
    if kind in ("reduction", "reduction_dot"):
        return _out(jnp.sum(_bf16(f[:, :n]), axis=1, keepdims=True,
                            dtype=jnp.bfloat16))
    if kind == "transpose":
        m = _bf16(f[:, :n * n]).reshape(k, n, n)
        return _out(jnp.transpose(m, (0, 2, 1)).reshape(k, n * n))
    if kind in ("matmul", "matmul_dot"):
        a = _bf16(f[:, :n * n]).reshape(k, n, n)
        b = _bf16(f[:, n * n:2 * n * n]).reshape(k, n, n)
        c = jnp.matmul(a, b, preferred_element_type=jnp.bfloat16)
        return _out(c.reshape(k, n * n))
    if kind == "fft":
        j = np.arange(n)
        ang = -2 * np.pi * np.outer(j, j) / n
        wr, wi = _bf16(np.cos(ang)), _bf16(np.sin(ang))
        xr, xi = _bf16(f[:, :n]), _bf16(f[:, n:2 * n])
        mm = lambda p, q: jnp.matmul(p, q,                  # noqa: E731
                                     preferred_element_type=jnp.bfloat16)
        yr = mm(xr, wr) - mm(xi, wi)
        yi = mm(xr, wi) + mm(xi, wr)
        return _out(jnp.concatenate([yr, yi], axis=1))
    raise ValueError(f"unknown program kind {kind!r}")


def control_numbers(jobs, specs) -> dict:
    """``check.compare`` with the control in the program's place.
    ``jobs`` is ``[(kind, n, inputs)]``."""
    from bench import check
    blocks = []
    for kind, n, inputs in jobs:
        k = inputs.shape[0]
        got = control_words(kind, n, inputs)
        sim = np.tile(check.spec_row(specs[(kind, n)]), (k, 1))
        blocks.append(check.Block(kind, n, inputs, got, sim,
                                  np.ones(k, bool)))
    return check.compare(blocks, specs)


def cell_jobs(progs: dict, traffic: dict, seed: int, rounds: int,
              seconds: float) -> list:
    """The jobs a run of the cell with this seed checks, as
    ``[(kind, n, inputs)]``: the same inputs the run draws."""
    from bench import drivers
    from bench.drivers import drain, serve
    key = drivers.seed_key(seed)
    if traffic["driver"] == "drain":
        mix = [(progs[name], int(k)) for name, k in traffic["jobs"]]
        return [(p.kind, p.n, x) for r in range(rounds)
                for (p, _), x in zip(mix, drain.round_inputs(
                    mix, [key, 1, r]))]
    ps = [progs[name] for name in traffic["programs"]]
    pl = serve.plan(ps, float(traffic["rate_per_s"]), seconds, [key, 1])
    return [(p.kind, p.n, x) for p, x in zip(ps, pl.inputs)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rounds", type=int, default=10,
                    help="drain rounds per seed (a run's count)")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="seconds of a serve mix's schedule")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import check, drivers, manifest, programs
    cell = manifest.load().cell(args.workload)
    cfg = drivers.make_egpu(manifest.load_config(cell.config))
    traffic = manifest.load_traffic(cell.traffic)
    names = sorted(set(drivers.load(traffic["driver"]).program_names(
        traffic)))
    progs = {n: programs.build(cfg, n) for n in names}
    specs = {(p.kind, p.n): check.load_spec(manifest.BENCH, cell.config, n)
             for n, p in progs.items()}
    d0 = jax.devices()[0]
    for seed in args.seeds:
        jobs = cell_jobs(progs, traffic, seed, args.rounds, args.seconds)
        nums = control_numbers(jobs, specs)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": "bfloat16", "device": d0.device_kind,
                          "jobs": sum(x.shape[0] for _, _, x in jobs),
                          "correct": check.verdict(nums),
                          "numbers": nums}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
