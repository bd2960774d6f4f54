"""Compile the main path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, compiles each program for a ``v5e:2x2`` topology that is only
described.  That catches what the chip's compiler would refuse (shapes,
layouts, memory, partitioning) at the real size — the paper's §7
instance, 32 cores per batch — without chip time.  The topology is
described inside a fixture, never while a module is imported, so every
test worker collects the same tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import benchmark_config
from repro.core.blockc import compile_program
from repro.fleet.devices import make_job_mesh
from repro.fleet.engine import _make_fleet_runner, _pack_programs
from repro.fleet.scheduler import FleetJob, _batch_init_state
from repro.fleet.sharded import mega_light_fn
from repro.programs import build_matmul, build_reduction

B = 32
CFG = benchmark_config("dp", has_dot=True, predicate_levels=2)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back; keep it out of any cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("build,n,tier", [
    (build_reduction, 32, "superblock"),      # short, straight-line
    (build_matmul, 64, "superblock"),         # loop-heavy: fori repeats
    # the drains' hot kernel, traced on its own 64 of 512 threads
    pytest.param(functools.partial(build_matmul, use_dot=True), 64,
                 "superblock", id="build_matmul_dot-64-superblock"),
])
def test_superblock_light_path_compiles_for_one_chip(
        no_persistent_cache, one_chip, build, n, tier):
    b = build(CFG, n)
    cp = compile_program(b.image, batch_hint=B)
    assert cp.mode == tier
    exe = jax.jit(cp.light_fn()).lower(
        _spec((B, CFG.shared_words), jnp.uint32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    assert exe.memory_analysis() is not None


def test_interpreter_fleet_runner_compiles_for_one_chip(
        no_persistent_cache, one_chip):
    b = build_reduction(CFG, 32)
    jobs = [FleetJob(handle=i, image=b.image, shared_init=None,
                     threads=b.image.threads_active, tdx_dim=b.tdx_dim)
            for i in range(B)]
    progs, length, ops = _pack_programs([b.image] * B)
    states = jax.eval_shape(lambda: _batch_init_state(CFG, jobs))
    states = jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip), states)
    runner = _make_fleet_runner(CFG, length, ops)
    runner.lower(_spec(progs.shape, progs.dtype, one_chip),
                 states).compile()


def test_megabatch_compiles_over_four_chips(no_persistent_cache, topo):
    assert len(topo.devices) == 4
    mesh = make_job_mesh(topo.devices)
    cp = compile_program(build_reduction(CFG, 64).image, batch_hint=B)
    slab = len(topo.devices) * B
    exe = jax.jit(mega_light_fn(cp, mesh)).lower(
        _spec((slab, CFG.shared_words), jnp.uint32,
              NamedSharding(mesh, P("jobs", None))),
        _spec((slab,), jnp.int32, NamedSharding(mesh, P("jobs")))).compile()
    # each chip holds one batch of the slab: the job axis is split, not
    # replicated
    shard = exe.input_shardings[0][0].shard_shape((slab, CFG.shared_words))
    assert shard == (B, CFG.shared_words)
