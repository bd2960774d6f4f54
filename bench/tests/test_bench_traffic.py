"""Traffic generation: the seed fixes the jobs and their data, every
seed gets the same work in another order, and the open loop times each
request from when it was due, so a stall shows in the tail."""
import pathlib
import sys
import threading
import time
import types
from concurrent.futures import Future

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import drivers, programs, reference  # noqa: E402
from bench.drivers import drain, serve  # noqa: E402

PROGS = [programs.Program("reduction_32", "reduction", 32, None, 32),
         programs.Program("bitonic_16", "bitonic", 16, None, 16),
         programs.Program("fft_16", "fft", 16, None, 8)]


def test_same_seed_same_plan_and_data():
    a = serve.plan(PROGS, 300.0, 2.0, [7, 1])
    b = serve.plan(PROGS, 300.0, 2.0, [7, 1])
    assert np.array_equal(a.offsets, b.offsets)
    assert np.array_equal(a.prog, b.prog)
    for x, y in zip(a.inputs, b.inputs):
        assert np.array_equal(x, y)


def test_other_seed_same_work_in_another_order():
    a = serve.plan(PROGS, 300.0, 2.0, [7, 1])
    b = serve.plan(PROGS, 300.0, 2.0, [2**31 + 11, 1])
    assert len(a.offsets) == len(b.offsets) == 600
    assert np.allclose(np.sort(np.diff(a.offsets, prepend=0)),
                       np.sort(np.diff(b.offsets, prepend=0)))
    assert np.array_equal(np.bincount(a.prog), np.bincount(b.prog))
    assert not np.array_equal(a.prog, b.prog)
    assert not np.array_equal(a.inputs[0], b.inputs[0])
    # mean inter-arrival gap is 1 / rate
    assert np.diff(a.offsets, prepend=0).mean() == pytest.approx(
        1 / 300.0, rel=0.05)


def test_every_job_gets_its_own_data():
    x = reference.make_inputs("matmul_dot", 16, np.random.default_rng(3),
                              32)
    assert len({row.tobytes() for row in x}) == 32
    f = x.view(np.float32)
    assert np.all(np.abs(f) >= np.finfo(np.float32).tiny)


def test_drain_rounds_are_drawn_from_the_seed():
    mix = [(PROGS[0], 4), (PROGS[1], 4)]
    a = drain.round_inputs(mix, [5, 1, 0])
    b = drain.round_inputs(mix, [5, 1, 0])
    c = drain.round_inputs(mix, [5, 1, 1])
    d = drain.round_inputs(mix, [6, 1, 0])
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], d[0])


class FakeService:
    """Answers each request after ``work_s``, in order, on one thread;
    ``stall_at`` makes it (and its ``submit``) stop for ``stall_s``
    once, as a stuck dispatcher holding the admission lock would."""

    def __init__(self, work_s=0.0005, stall_at=None, stall_s=0.0):
        self.work_s, self.stall_at, self.stall_s = work_s, stall_at, stall_s
        self.q: list = []
        self.cv = threading.Condition()
        self.admit = threading.Lock()
        self.n = 0
        self.stop = False
        self.th = threading.Thread(target=self._loop, daemon=True)
        self.th.start()

    def submit(self, image, data, tdx_dim=16):
        with self.admit:
            fut = Future()
            with self.cv:
                self.q.append((fut, np.asarray(data)))
                self.cv.notify()
            return fut

    def _loop(self):
        while True:
            with self.cv:
                while not self.q and not self.stop:
                    self.cv.wait()
                if self.stop and not self.q:
                    return
                fut, data = self.q.pop(0)
            self.n += 1
            if self.n == self.stall_at:
                with self.admit:
                    time.sleep(self.stall_s)
            time.sleep(self.work_s)
            shared = np.zeros(256, np.uint32)
            shared[:data.size] = data
            fut.set_result(types.SimpleNamespace(
                shared=shared, cycles=1, steps=1, hazard_violations=0,
                stat_cycles=np.zeros(9), stat_instrs=np.zeros(9),
                tier="superblock"))

    def close(self):
        with self.cv:
            self.stop = True
            self.cv.notify()
        self.th.join(10)
        assert not self.th.is_alive()


def _p95(svc):
    progs = [PROGS[0]]
    pl = serve.plan(progs, 400.0, 1.0, [1, 1])
    try:
        rows, due, done, late, _ = serve.open_loop(svc, progs, pl)
    finally:
        svc.close()
    assert np.isfinite(done).all()
    assert all(g is not None for g in rows[0].got)
    return drivers.percentile(done - due, 95), late


def test_a_stall_raises_the_due_time_tail():
    calm, _ = _p95(FakeService())
    stalled, late = _p95(FakeService(stall_at=100, stall_s=0.25))
    assert calm < 0.05
    assert stalled > 0.1 and stalled > 4 * calm
    # the stall held up submit too: the generator ran late, and timing
    # from the due time (not from submit) is what keeps that wait in
    assert late.max() > 0.1


def test_percentile_is_nearest_rank_over_all_values():
    v = np.arange(1, 101, dtype=float)
    assert drivers.percentile(v, 50) == 50
    assert drivers.percentile(v, 95) == 95
    assert drivers.percentile(np.append(v[:99], np.inf), 100) == np.inf


@pytest.mark.parametrize("kind,n", [("reduction", 32), ("transpose", 16),
                                    ("matmul", 16), ("bitonic", 32),
                                    ("fft", 32)])
def test_kept_answers_do_not_hold_the_batch(kind, n):
    batch = np.zeros((4, 8192), np.uint32)
    words = reference.result_words(kind, n, batch[1])
    assert not np.shares_memory(words, batch)
    assert words.shape == (reference.result_size(kind, n),)
