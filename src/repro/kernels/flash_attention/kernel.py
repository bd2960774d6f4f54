"""Flash attention with dynamic KV-tile skipping (Pallas TPU).

The online-softmax KV loop is the attention analogue of the eGPU's
wavefront depth: for a causal (or ragged-length) row block, only a prefix
of the KV tiles is live.  We compute that prefix bound from the
scalar-prefetched per-batch lengths and `pl.when`-skip everything beyond
it — the instruction-level "first 1/2 / first 1/4 wavefronts" codings of
Table 3, generalised to an exact per-row-block bound.

Grid: (batch*heads, q tiles, kv tiles); scratch: running max m, running
sum l, fp32 accumulator — all VMEM-resident across the KV loop.
"""
from __future__ import annotations

import functools

import jax
from jax import numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE_Q = 128
DEFAULT_TILE_K = 128
NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            tile_q: int, tile_k: int, causal: bool, sq: int, sk: int,
            heads: int):
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    b = bh // heads

    kv_len = len_ref[b]
    # last kv position this q tile may see (decode-style causal offset)
    q_last = iq * tile_q + (tile_q - 1) + (sk - sq) if causal else sk - 1
    limit = jnp.minimum(kv_len, q_last + 1) if causal else kv_len
    live = (ik * tile_k) < limit           # wavefront-depth subsetting

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)      # (tile_q, d)
        k = k_ref[0].astype(jnp.float32)      # (tile_k, d)
        v = v_ref[0].astype(jnp.float32)
        d = q.shape[-1]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) \
            * (1.0 / (d ** 0.5))              # (tile_q, tile_k)

        qpos = iq * tile_q + jax.lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 0)
        kpos = ik * tile_k + jax.lax.broadcasted_iota(
            jnp.int32, (tile_q, tile_k), 1)
        mask = kpos < kv_len
        if causal:
            mask &= kpos <= qpos + (sk - sq)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                  # (tile_q, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)               # (tile_q, tile_k)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "tile_q", "tile_k",
                                             "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    lengths: jnp.ndarray | None = None,
                    causal: bool = True,
                    tile_q: int = DEFAULT_TILE_Q,
                    tile_k: int = DEFAULT_TILE_K,
                    interpret: bool = False) -> jnp.ndarray:
    b, h, sq, d = q.shape
    sk = k.shape[2]
    assert sq % tile_q == 0 and sk % tile_k == 0
    if lengths is None:
        lengths = jnp.full((b,), sk, jnp.int32)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    grid = (b * h, sq // tile_q, sk // tile_k)
    out = pl.pallas_call(
        functools.partial(_kernel, tile_q=tile_q, tile_k=tile_k,
                          causal=causal, sq=sq, sk=sk, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tile_q, d), lambda bh, iq, ik, lens: (bh, iq, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tile_k, d), lambda bh, iq, ik, lens: (bh, ik, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, tile_k, d), lambda bh, iq, ik, lens: (bh, ik, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, tile_q, d),
                                   lambda bh, iq, ik, lens: (bh, iq, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((tile_q, 1), jnp.float32),
                pltpu.VMEM((tile_q, 1), jnp.float32),
                pltpu.VMEM((tile_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, h, sq, d)
