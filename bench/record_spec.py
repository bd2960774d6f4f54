"""Record the simulated-statistics spec of programs on a configuration.

    python3 bench/record_spec.py --config egpu_dp_paper \\
        --programs reduction_32 matmul_dot_64 ...

Runs each program once through ``run_program`` (the interpreter, the
repository's definition of cycles, steps, hazards and instruction mix)
on the host CPU, and writes ``bench/spec/<config>/<program>.json``.
These numbers depend on the program and the configuration alone, not
on the data, so the check holds every job of a run to them.  A file
that exists is never overwritten: the spec is frozen once recorded.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def spec_of(cfg, name: str) -> dict:
    """``run_program``'s simulated statistics of one program, run on
    the host CPU over data drawn from seed 0."""
    import jax
    import numpy as np
    from bench import programs, reference
    from repro.core import run_program
    p = programs.build(cfg, name)
    x = reference.make_inputs(p.kind, p.n, np.random.default_rng(0), 1)
    with jax.default_device(jax.devices("cpu")[0]):
        st = run_program(p.image, shared_init=x[0], tdx_dim=p.tdx_dim)
    return {"cycles": int(st.cycles), "steps": int(st.steps),
            "hazard_violations": int(st.hazard_violations),
            "stat_cycles": [int(v) for v in np.asarray(st.stat_cycles)],
            "stat_instrs": [int(v) for v in np.asarray(st.stat_instrs)]}


def record(config: str, names: list[str]) -> list[pathlib.Path]:
    from bench import drivers, manifest

    cfg = drivers.make_egpu(manifest.load_config(config))
    out_dir = manifest.BENCH / "spec" / config
    out_dir.mkdir(parents=True, exist_ok=True)
    written = []
    for name in names:
        path = out_dir / f"{name}.json"
        if path.exists():
            continue
        doc = dict(program=name, config=config, **spec_of(cfg, name))
        path.write_text(json.dumps(doc, indent=1) + "\n")
        written.append(path)
    return written


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--programs", nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    for path in record(args.config, args.programs):
        print(path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
