"""Dynamically-masked block matmul — the paper's technique on the MXU.

C[M, N] = A[M, K] @ B[K, N], where row-tiles of M carry an activity
bitmap (scalar-prefetched, like the TSC field).  Inactive tiles skip the
whole K-loop: no MXU issue, no VMEM accumulation — the direct analogue of
the eGPU skipping wavefronts ("subset write can be 16x faster").

Used for MoE expert compute, where M is the token dimension grouped by
expert and most groups are ragged (tokens-per-expert << capacity).

Block sizes are MXU-native (128x128) with a K-major accumulation loop in
a VMEM scratch accumulator (fp32), B streamed K-tile by K-tile.
"""
from __future__ import annotations

import functools

import jax
from jax import numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 128
TILE_N = 128
TILE_K = 128


def _kernel(active_ref, a_ref, b_ref, o_ref, acc_ref):
    mi = pl.program_id(0)
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    is_active = active_ref[mi] != 0

    @pl.when(is_active & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(is_active)
    def _accum():
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = jnp.where(is_active, acc_ref[...].astype(o_ref.dtype),
                               jnp.zeros_like(o_ref))


@functools.partial(jax.jit, static_argnames=("interpret",))
def wavefront_matmul(a: jnp.ndarray, b: jnp.ndarray,
                     row_active: jnp.ndarray,
                     interpret: bool = False) -> jnp.ndarray:
    m, kdim = a.shape
    k2, n = b.shape
    assert kdim == k2
    assert m % TILE_M == 0 and n % TILE_N == 0 and kdim % TILE_K == 0
    grid = (m // TILE_M, n // TILE_N, kdim // TILE_K)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((TILE_M, TILE_K), lambda i, j, k, act: (i, k),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((TILE_K, TILE_N), lambda i, j, k, act: (k, j),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((TILE_M, TILE_N), lambda i, j, k, act: (i, j),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((TILE_M, TILE_N), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(row_active.astype(jnp.int32), a, b)
