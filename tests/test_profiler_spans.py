"""The program on the profiler's clock: ``repro.obs`` spans as
``egpu.*`` profiler annotations, a stable XLA module name per tier
kernel, and the collect and queue-wait counters.

The contracts under test:

* **capture is enough** — while ``jax.profiler`` captures, every span
  lands on the trace's host timeline as ``egpu.<name>``, with no
  tracer or flight recorder installed; without a session (and nothing
  installed) ``span()`` is still the shared no-op;
* **tracer work stays off** — capture turns on no tracer-gated work
  (span arguments, interpreter event counters);
* **kernel names** — every tier kernel lowers to a module matching
  ``KERNEL_MODULE_RE``;
* **counters** — ``fleet_collect_seconds_total`` and
  ``serve_queue_wait_seconds_total`` move by what a known drain and a
  known request spend, and ``fleet_compile_cache_total`` counts a
  cold drain's misses and a warm one's hits.
"""
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Asm, EGPUConfig, compile_program
from repro.core.executor import KERNEL_MODULE_RE, _make_runner
from repro.fleet import Fleet, FleetService
from repro.fleet.devices import make_job_mesh
from repro.fleet.engine import _make_fleet_runner, _pack_programs
from repro.fleet.scheduler import FleetJob, _batch_init_state
from repro.fleet.sharded import mega_light_fn
from repro.obs import NULL_SPAN, span
from repro.programs import build_reduction, build_transpose

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

CFG = EGPUConfig(max_threads=32, regs_per_thread=32, shared_kb=4,
                 alu_bits=32, shift_bits=32, predicate_levels=4,
                 has_dot=True, has_invsqr=True)


def _jobs():
    return [build_reduction(CFG, 32), build_transpose(CFG, 16)]


def _loop_program(iters: int):
    """A program whose kernel no other test compiles: one LOOP
    back-edge per iteration, a count nothing else uses."""
    a = Asm(CFG)
    a.tdx(1)
    a.lod(2, 1, 0)
    with a.loop(iters):
        a.fadd(2, 2, 2)
    a.sto(2, 1, 32)
    a.stop()
    return (a.assemble(threads_active=32),
            np.arange(64, dtype=np.float32) / 7.0)


def _submit(target, jobs):
    return [target.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim)
            for b in jobs]


def _host_names(planes) -> set[str]:
    devs = {p["name"] for p in tracing.device_planes(planes)}
    return {n for p in planes if p["name"] not in devs
            for ln in p["lines"] for n, _, _ in ln["events"]}


class _Capture:
    """``jax.profiler`` around a block, its planes read afterwards."""

    def __init__(self, tmp_path):
        self.dir = tmp_path / "prof"

    def __enter__(self):
        jax.profiler.start_trace(str(self.dir))
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def planes(self):
        return tracing.load(tracing.find_xplane(self.dir))


# ------------------------------------------------------------------
# spans on the profiler's clock
# ------------------------------------------------------------------

def test_capture_puts_fleet_and_service_spans_on_the_host_plane(tmp_path):
    fleet = Fleet(CFG, batch_size=4)
    _submit(fleet, _jobs() * 2)
    fleet.drain()                        # compile outside the capture
    with FleetService(CFG, 4) as svc:
        for f in _submit(svc, _jobs()):
            f.result(timeout=600)
        with _Capture(tmp_path) as cap:
            _submit(fleet, _jobs() * 2)
            fleet.drain()
            for f in _submit(svc, _jobs() * 2):
                f.result(timeout=600)
            time.sleep(0.02)             # the dispatcher leaves resolve
    names = _host_names(cap.planes())
    assert {"egpu.drain", "egpu.partition", "egpu.dispatch",
            "egpu.device_sync", "egpu.collect", "egpu.serve.cohort",
            "egpu.serve.dispatch", "egpu.serve.resolve"} <= names
    # the bare name: no arguments folded into the event's name
    assert all(n.startswith("egpu.") and "#" not in n and "=" not in n
               for n in names if "egpu." in n)


def test_span_is_the_shared_noop_without_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert span("drain", jobs=3) is NULL_SPAN


def test_a_profiler_only_span_is_inert(tmp_path):
    with _Capture(tmp_path):
        sp = span("drain", jobs=3)
        assert sp is not NULL_SPAN and sp.active is False
        with sp as inner:
            assert inner.set(delivered=1) is inner
    assert span("drain") is NULL_SPAN
    events = [e for p in tracing.load(tracing.find_xplane(tmp_path
                                                          / "prof"))
              for ln in p["lines"] for e in ln["events"]]
    assert [e[0] for e in events if e[0].startswith("egpu.")] == \
        ["egpu.drain"]


def test_capture_turns_on_no_tracer_work(tmp_path):
    """The interpreter tier's event counters are built only for a
    tracer; capture alone leaves them off."""
    fleet = Fleet(CFG, batch_size=4, use_compiler=False)
    with _Capture(tmp_path):
        hs = _submit(fleet, _jobs())
        res = fleet.drain()
    assert fleet.tracer is None
    assert all(res[h].tier == "interp" and res[h].counters is None
               for h in hs)


def test_service_spans_reach_the_tracer():
    with FleetService(CFG, 4, trace=True) as svc:
        for f in _submit(svc, _jobs()):
            f.result(timeout=600)
    names = {e["name"] for e in svc.tracer.events if e.get("ph") == "X"}
    assert {"serve.cohort", "serve.dispatch", "serve.resolve",
            "drain"} <= names


# ------------------------------------------------------------------
# a stable module name per tier kernel
# ------------------------------------------------------------------

def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split(" ", 1)[0]


def _light(mode):
    cp = compile_program(_jobs()[0].image, mode=mode)
    sh = jnp.zeros((4, CFG.shared_words), jnp.uint32)
    td = jnp.full((4,), 16, jnp.int32)
    return jax.jit(cp.light_fn()).lower(sh, td), cp


def _full(mode):
    cp = compile_program(_jobs()[0].image, mode=mode)
    sh = jnp.zeros((CFG.shared_words,), jnp.uint32)
    return cp._run_jit.lower(sh, jnp.int32(16)), cp


def _fleet_interp():
    jobs = [FleetJob(i, b.image, b.shared_init, b.image.threads_active,
                     b.tdx_dim) for i, b in enumerate(_jobs())]
    progs, length, ops = _pack_programs([j.image for j in jobs])
    states = _batch_init_state(CFG, jobs)
    return _make_fleet_runner(CFG, length, ops).lower(progs, states), None


def _single_interp():
    from repro.core.executor import pad_image
    from repro.core.machine import init_state
    b = _jobs()[0]
    prog, length = pad_image(b.image)
    st = init_state(CFG, threads=b.image.threads_active)
    return _make_runner(CFG, length).lower(jnp.asarray(prog), st), None


def _mega():
    cp = compile_program(_jobs()[0].image, mode="superblock")
    sh = jnp.zeros((4, CFG.shared_words), jnp.uint32)
    td = jnp.full((4,), 16, jnp.int32)
    mesh = make_job_mesh(jax.devices()[:1])
    return jax.jit(mega_light_fn(cp, mesh)).lower(sh, td), cp


@pytest.mark.parametrize("case, tier", [
    (lambda: _light("superblock"), "superblock"),
    (lambda: _light("blocks"), "blocks"),
    (lambda: _full("superblock"), "superblock"),
    (lambda: _full("blocks"), "blocks"),
    (_fleet_interp, "interp"),
    (_single_interp, "interp"),
    (_mega, "mega_superblock"),
], ids=["superblock_light", "blocks_light", "superblock_full",
        "blocks_full", "fleet_interp", "single_interp",
        "mega_superblock"])
def test_each_tier_kernel_lowers_to_its_module_name(case, tier):
    lowered, cp = case()
    m = KERNEL_MODULE_RE.fullmatch(_module_name(lowered))
    assert m is not None and m.group(1) == tier
    if cp is None:
        assert m.group(2) is None
    else:
        from repro.core.blockc import program_digest
        assert m.group(2) == program_digest(cp.image)


# ------------------------------------------------------------------
# counters
# ------------------------------------------------------------------

def test_collect_and_compile_counters_on_a_known_drain():
    fleet = Fleet(CFG, batch_size=4)
    reg = fleet.metrics

    def totals():
        return (reg.total("fleet_collect_seconds_total"),
                reg.total("fleet_batches_total"),
                reg.total("fleet_compile_cache_total", result="miss"),
                reg.total("fleet_compile_cache_total", result="hit"))

    # two programs, 4 jobs each; one of them compiled by no other test
    image, data = _loop_program(389)
    jobs = _jobs()[:1] * 4
    assert totals() == (0.0, 0.0, 0.0, 0.0)

    def drain():
        for _ in range(4):
            fleet.submit(image, data, tdx_dim=32)
        _submit(fleet, jobs)
        fleet.drain()

    t0 = time.perf_counter()
    drain()
    cold_wall = time.perf_counter() - t0
    collect, batches, misses, hits = totals()
    assert batches == 2                 # one batch of 4 per program
    assert 0.0 < collect < cold_wall
    assert misses + hits == batches and misses >= 1
    drain()
    collect2, batches2, misses2, hits2 = totals()
    assert batches2 - batches == 2 and collect2 > collect
    assert misses2 == misses and hits2 - hits == 2   # warm: all hits


def test_queue_wait_counter_on_a_known_request():
    with FleetService(CFG, 4, max_delay_s=0.05) as svc:
        (f,) = _submit(svc, _jobs()[:1])  # a lone job waits out the delay
        f.result(timeout=600)
        wait = svc.metrics.total("serve_queue_wait_seconds_total")
        assert svc.metrics.total("serve_dispatched_jobs_total") == 1
    assert 0.05 <= wait < 5.0
