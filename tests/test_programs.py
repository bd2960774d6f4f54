"""Benchmark-program tests: correctness vs numpy + cycle fidelity vs the
paper's Tables 7/8 + the dynamic-scalability ablation."""
import pytest

from repro.core import benchmark_config
from repro.programs import (build_bitonic, build_fft, build_matmul,
                            build_reduction, build_transpose, run_bench)

# (name, n, column) -> paper cycles; column in {dp, qp, dot}
PAPER = {
    ("reduction", 32, "dp"): 168, ("reduction", 32, "qp"): 160,
    ("reduction", 64, "dp"): 202, ("reduction", 128, "dp"): 216,
    ("transpose", 32, "dp"): 1720, ("transpose", 32, "qp"): 1208,
    ("transpose", 64, "dp"): 5529,
    ("bitonic", 32, "dp"): 1742, ("bitonic", 64, "dp"): 3728,
    ("fft", 32, "dp"): 876, ("fft", 64, "dp"): 1695,
    ("fft", 64, "qp"): 1312,
}
TOL = 0.5   # +/-50% band: the paper's assembly is unpublished; trends and
            # ratios are validated tightly below, absolutes loosely here.


def _run(builder, n, mode="dp", **kw):
    cfg = benchmark_config(mode, has_dot=kw.pop("has_dot", False),
                           predicate_levels=kw.pop("pred", 0))
    r = run_bench(builder(cfg, n, **kw))
    assert r.correct, f"{r.name} produced wrong results"
    assert r.hazard_violations == 0, f"{r.name} has RAW hazards"
    return r


@pytest.mark.parametrize("n", [32, 64])
def test_reduction_correct_and_in_band(n):
    r = _run(build_reduction, n)
    p = PAPER[("reduction", n, "dp")]
    assert abs(r.cycles - p) / p < TOL


def test_reduction_qp_saves_write_cycles():
    dp = _run(build_reduction, 32, "dp")
    qp = _run(build_reduction, 32, "qp")
    assert qp.cycles < dp.cycles            # doubled write ports


def test_reduction_dot_unit_matches_paper_ratio():
    dp = _run(build_reduction, 64, "dp")
    dot = _run(build_reduction, 64, "dp", has_dot=True, use_dot=True)
    # paper: 94/202 = 0.47x; ours should be at least that good
    assert dot.cycles / dp.cycles < 0.5


def test_dynamic_scaling_beats_predicated_masking():
    """The paper's core claim: TSC thread-space subsetting vs running all
    threads with predicate write-masking."""
    dyn = _run(build_reduction, 64, "dp")
    nodyn = _run(build_reduction, 64, "dp", pred=4, no_dynamic=True)
    assert nodyn.cycles / dyn.cycles > 2.0   # we measure ~3.4x


@pytest.mark.parametrize("n", [32, 64])
def test_transpose_cycles_model(n):
    r = _run(build_transpose, n)
    p = PAPER[("transpose", n, "dp")]
    assert abs(r.cycles - p) / p < 0.25
    # paper: QP writes two elements per clock -> ~40% fewer cycles
    rq = _run(build_transpose, n, "qp")
    assert 0.55 < rq.cycles / r.cycles < 0.8


def test_matmul_correct_and_dot_speedup():
    plain = _run(build_matmul, 32)
    dot = _run(build_matmul, 32, has_dot=True, use_dot=True)
    assert dot.cycles < plain.cycles
    # our tiled assembly beats the paper's 111546; sanity: within 5x below
    assert plain.cycles < 111546


@pytest.mark.parametrize("n", [32, 64])
def test_bitonic_sort(n):
    r = _run(build_bitonic, n, pred=2)
    p = PAPER[("bitonic", n, "dp")]
    assert abs(r.cycles - p) / p < 0.35


@pytest.mark.parametrize("n", [32, 64])
def test_fft(n):
    r = _run(build_fft, n)
    p = PAPER[("fft", n, "dp")]
    assert abs(r.cycles - p) / p < 0.35


def test_fft_16_runs_one_wavefront_for_eight_butterflies():
    """n = 16 needs 8 butterflies but a whole 16-thread wavefront runs;
    the extra threads must not corrupt the result (``_run`` checks it
    against the NumPy oracle)."""
    _run(build_fft, 16)


def test_fft_qp_ratio_matches_paper():
    dp = _run(build_fft, 64)
    qp = _run(build_fft, 64, "qp")
    # paper table 8: 1312/1695 = 0.77 in cycles
    assert 0.6 < qp.cycles / dp.cycles < 0.9


def test_profile_memory_dominates_fft():
    """Fig. 6: memory ops dominate; FP ~10% of cycles."""
    cfg = benchmark_config("dp")
    r = run_bench(build_fft(cfg, 64))
    total = sum(c for c, _ in r.profile.values())
    mem = r.profile["MEM_RD"][0] + r.profile["MEM_WR"][0]
    fp = r.profile["FP"][0]
    assert mem / total > 0.4
    assert fp / total < 0.25
