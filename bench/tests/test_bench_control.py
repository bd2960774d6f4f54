"""The control, the reference in bfloat16 in the program's place, comes
out as not correct, at a tiny size on the CPU; and the comparison's own
arithmetic."""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, control, drivers, programs, reference  # noqa: E402
from bench.tests import bench_tiny  # noqa: E402


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = bench_tiny.write_spec(tmp_path_factory.mktemp("spec"))
    cfg = drivers.make_egpu(bench_tiny.DOC)
    progs = {n: programs.build(cfg, n) for n in bench_tiny.PROGRAMS}
    specs = {(p.kind, p.n): check.load_spec(root, bench_tiny.CONFIG, n)
             for n, p in progs.items()}
    return progs, specs


@pytest.mark.parametrize("driver", ["drain", "serve"])
def test_control_is_not_correct(tiny, driver):
    progs, specs = tiny
    jobs = control.cell_jobs(progs, bench_tiny.traffic(driver),
                             bench_tiny.SEED, rounds=3, seconds=1.0)
    nums = control.control_numbers(jobs, specs)
    assert not check.verdict(nums)
    assert nums["float_gap_u"] > 3 * check.LIMITS["float_gap_u"]
    assert nums["exact_words_off"] > 0
    assert nums["missing"] == 0 and nums["spec_off"] == 0


@pytest.mark.parametrize("kind,n", [("reduction", 32), ("matmul", 16),
                                    ("fft", 32)])
def test_reference_itself_reads_a_small_gap(kind, n):
    x = reference.make_inputs(kind, n, np.random.default_rng(1), 8)
    want, scale = reference.expected(kind, n, x)
    got = want.astype(np.float32).view(np.uint32)
    assert check.float_gap_u(got, want, scale) <= 1.0


def test_float_gap_units_and_non_finite():
    want = np.array([[1.0, -2.0]])
    scale = np.array([[1.0, 4.0]])
    got = np.array([[1.0 + 2**-22, -2.0]], np.float32).view(np.uint32)
    assert check.float_gap_u(got, want, scale) == pytest.approx(4.0)
    nan = np.array([[np.nan, -2.0]], np.float32).view(np.uint32)
    assert check.float_gap_u(nan, want, scale) == np.inf


def test_compare_counts_each_kind_of_miss(tiny):
    progs, specs = tiny
    p = progs["bitonic_32"]
    x = reference.make_inputs(p.kind, p.n, np.random.default_rng(2), 4)
    want, _ = reference.expected(p.kind, p.n, x)
    got = want.copy()
    got[1, 3] ^= 1
    sim = np.tile(check.spec_row(specs[(p.kind, p.n)]), (4, 1))
    sim[2, 0] += 1                                   # one cycle off
    done = np.array([True, True, True, False])
    nums = check.compare([check.Block(p.kind, p.n, x, got, sim, done)],
                         specs)
    assert nums == {"missing": 1, "exact_words_off": 1, "spec_off": 1,
                    "float_gap_u": 0.0}
    assert not check.verdict(nums)
