"""A tiny cell for CPU tests: a 64-thread instance, batches of 4, the
corpus at n = 16 and 32, and a spec recorded on the spot.  It drives
everything a chip run does after the device gate."""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import drivers, manifest, record_spec  # noqa: E402

CONFIG = "tiny"
DOC = {"egpu": {"max_threads": 64, "regs_per_thread": 32, "shared_kb": 32,
                "predicate_levels": 2, "has_dot": True,
                "has_invsqr": True},
       "batch_size": 4}
PROGRAMS = ["reduction_32", "reduction_dot_32", "transpose_16",
            "matmul_16", "matmul_dot_16", "bitonic_32", "fft_32"]
SEED = 2**31 + 17


def traffic(driver: str, jobs: int = 4) -> dict:
    if driver == "drain":
        return {"driver": "drain", "jobs": [[p, jobs] for p in PROGRAMS]}
    return {"driver": "serve", "programs": PROGRAMS, "rate_per_s": 150}


def cell(driver: str, chips: int = 1) -> manifest.Cell:
    m = ["jobs_per_s"] if driver == "drain" else ["p50_ms"]
    e2e = tuple(manifest.Metric(n, "x", "lower", "host_clock", None,
                                bound=0.1) for n in m + ["setup_s"])
    return manifest.Cell(f"tiny_{driver}", CONFIG, "tiny", chips, e2e, ())


def write_spec(root: pathlib.Path) -> pathlib.Path:
    """Record the tiny instance's spec under ``root/spec/tiny``."""
    cfg = drivers.make_egpu(DOC)
    out = root / "spec" / CONFIG
    out.mkdir(parents=True, exist_ok=True)
    for p in PROGRAMS:
        (out / f"{p}.json").write_text(json.dumps(
            record_spec.spec_of(cfg, p)))
    return root


def run(driver: str, spec_root: pathlib.Path, *, seconds: float = 0.6,
        chips: int = 1, jobs: int = 4, seed: int = SEED,
        mix: dict | None = None) -> dict:
    import jax
    from bench import run as bench_run
    return bench_run.run_cell(
        cell(driver, chips), seed=seed, seconds=seconds, trace=False,
        devs=jax.devices()[:chips], doc=DOC,
        traffic=mix or traffic(driver, jobs), spec_root=spec_root)
