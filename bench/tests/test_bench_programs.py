"""The frozen program corpus and its reference are pinned: every
program of every cell assembles to the same image, and the reference
gives the same answers for data drawn from a fixed seed, as recorded
in ``bench/programs/golden.json``.

Regenerate the golden file (only in a benchmark change) with
``python3 bench/tests/test_bench_programs.py``."""
import hashlib
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import drivers, manifest, programs, reference  # noqa: E402

GOLDEN = ROOT / "bench" / "programs" / "golden.json"


def cell_programs() -> list[tuple[str, str]]:
    """Every (config, program) pair some cell runs."""
    man = manifest.load()
    pairs = set()
    for cell in man.cells.values():
        t = manifest.load_traffic(cell.traffic)
        for name in ([n for n, _ in t.get("jobs", [])]
                     + t.get("programs", [])):
            pairs.add((cell.config, name))
    return sorted(pairs)


def digest(config: str, name: str) -> dict:
    cfg = drivers.make_egpu(manifest.load_config(config))
    p = programs.build(cfg, name)
    x = reference.make_inputs(p.kind, p.n, np.random.default_rng(0), 2)
    want, scale = reference.expected(p.kind, p.n, x)
    # float answers are pinned at float32: the float64 sums' last bits
    # depend on the host's BLAS
    h = hashlib.sha256(np.ascontiguousarray(
        want if scale is None else want.astype(np.float32)).tobytes())
    if scale is not None:
        h.update(np.ascontiguousarray(scale.astype(np.float32)).tobytes())
    return {"image": hashlib.sha256(p.image.words.tobytes()).hexdigest(),
            "tdx_dim": p.tdx_dim,
            "inputs": hashlib.sha256(x.tobytes()).hexdigest(),
            "reference": h.hexdigest()}


@pytest.fixture(scope="module")
def golden():
    return json.loads(GOLDEN.read_text())


@pytest.mark.parametrize("config,name", cell_programs())
def test_program_matches_golden(golden, config, name):
    assert digest(config, name) == golden[f"{config}/{name}"]


def test_every_golden_entry_is_used(golden):
    assert set(golden) == {f"{c}/{n}" for c, n in cell_programs()}


def test_unknown_program_is_refused():
    cfg = drivers.make_egpu(manifest.load_config("egpu_dp_paper"))
    with pytest.raises(ValueError, match="unknown program"):
        programs.build(cfg, "sort_32")
    with pytest.raises(ValueError):
        programs.build(cfg, "matmul_dot_x")


if __name__ == "__main__":
    GOLDEN.write_text(json.dumps(
        {f"{c}/{n}": digest(c, n) for c, n in cell_programs()},
        indent=1, sort_keys=True) + "\n")
    print(GOLDEN)
