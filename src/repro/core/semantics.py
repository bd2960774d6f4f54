"""Per-opcode semantics of the eGPU ISA, shared by every execution tier.

The interpreter (:mod:`repro.core.executor`), the basic-block compiler
(:mod:`repro.core.blockc`) and the vmapped fleet engine all execute the
same instruction semantics; this module is the single definition they
share.  It has two layers:

* :func:`build_spec` — the per-opcode *value/condition* functions.
  ``spec[op] = (value_fn | None, cond_fn | None)``: the register value an
  instruction produces, and (for IF.cc) the predicate condition it
  pushes.  The functions close over an :class:`OpEnv` whose fields may be
  **traced** scalars (the interpreter gathers ``op/typ/imm/...`` from the
  program image at run time) or **Python constants** (the block compiler
  bakes the static program in at trace time, so e.g. ``signed`` folds and
  the dead branch disappears).  Thread-space arrays carry an optional
  leading batch axis — every function is written against the *last*
  axes, so the same code serves one core ``(T,)`` and a fleet ``(B, T)``.

* structural-update helpers — predicate stacks, call/loop stacks, the
  deterministic DOT/SUM reduction.  Each takes an ``en`` gate that may be
  the Python constant ``True`` (compiler: the update statically applies)
  or a traced bool (interpreter: mask-gated select).

Bit-exactness is the contract: all integer results live in a uint32
register file, FP32 values are bitcast in and out of the FP units, and
the DOT/SUM reduction order is fixed (sequential over wavefronts,
pairwise tree within the 16-lane wavefront) so every tier produces
identical bits.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np
from jax import lax

from .config import EGPUConfig
from .isa import NUM_OPCODES, Op

_I32 = jnp.int32
_U32 = jnp.uint32


# ---------------------------------------------------------------------------
# Bit-exact integer/FP helpers (uint32 register file)
# ---------------------------------------------------------------------------

def _i(x):
    return x.astype(jnp.int32)


def _u(x):
    return x.astype(_U32)


def _f(x):
    return lax.bitcast_convert_type(x, jnp.float32)


def _bits(x):
    return lax.bitcast_convert_type(x.astype(jnp.float32), _U32)


def _sext16(x_u32):
    """Sign-extend the low 16 bits."""
    x = _i(x_u32 & _U32(0xFFFF))
    return jnp.where(x >= 1 << 15, x - (1 << 16), x)


def _sext24(x_u32):
    x = _i(x_u32 & _U32(0xFFFFFF))
    return jnp.where(x >= 1 << 23, x - (1 << 24), x)


def _bit_reverse32(x):
    x = ((x & _U32(0x55555555)) << 1) | ((x >> 1) & _U32(0x55555555))
    x = ((x & _U32(0x33333333)) << 2) | ((x >> 2) & _U32(0x33333333))
    x = ((x & _U32(0x0F0F0F0F)) << 4) | ((x >> 4) & _U32(0x0F0F0F0F))
    x = ((x & _U32(0x00FF00FF)) << 8) | ((x >> 8) & _U32(0x00FF00FF))
    x = (x << 16) | (x >> 16)
    return x


def _mul24(a_u32, b_u32, signed):
    """24x24 -> 48-bit product as (hi24, lo24) uint32 limb pair.

    Implemented in 32-bit limbs (the container runs with x64 disabled,
    and the hardware is a 24-bit multiplier anyway).
    """
    if signed:
        sa = _sext24(a_u32)
        sb = _sext24(b_u32)
        neg = (sa < 0) ^ (sb < 0)
        a = _u(jnp.abs(sa))
        b = _u(jnp.abs(sb))
    else:
        neg = jnp.zeros(a_u32.shape, jnp.bool_)
        a = a_u32 & _U32(0xFFFFFF)
        b = b_u32 & _U32(0xFFFFFF)
    m12 = _U32((1 << 12) - 1)
    m24 = _U32((1 << 24) - 1)
    ah, al = a >> 12, a & m12
    bh, bl = b >> 12, b & m12
    low = al * bl                       # < 2^24
    mid = ah * bl + al * bh             # < 2^25
    t = mid + (low >> 12)               # < 2^26
    hi = ah * bh + (t >> 12)            # bits [47:24]
    lo = ((t & m12) << 12) | (low & m12)  # bits [23:0]
    # two's-complement negate the 48-bit (hi, lo) pair where requested
    nlo = (-lo) & m24
    borrow = (lo != 0).astype(_U32)
    nhi = ((~hi) & m24) + _U32(1) - borrow
    nhi = nhi & m24
    hi = jnp.where(neg, nhi, hi)
    lo = jnp.where(neg, nlo, lo)
    return hi, lo, neg


def _sel(c, a, b):
    """``jnp.where`` that folds when the predicate is a Python constant.

    The block compiler bakes ``typ`` in, so ``signed`` is a plain bool and
    the dead branch never enters the jaxpr; the interpreter passes a
    traced bool and gets the usual select.
    """
    if isinstance(c, (bool, np.bool_)):
        return a if c else b
    return jnp.where(c, a, b)


def det_sum(v, num_sps: int = 16, width: int | None = None):
    """Deterministic thread-space reduction (DOT/SUM extension unit).

    Sequential over wavefronts, pairwise tree within the 16-lane
    wavefront, like the hardware's accumulator — so the interpreter, the
    block compiler and the vmapped fleet produce bit-identical sums
    (``jnp.sum`` may associate differently under vmap/batching).  ``v``
    is ``(..., T)``; returns ``(...)``.

    ``width`` is the thread space the sum stands for when ``v`` holds
    only its first ``T`` threads (the compiled tiers trace the program's
    own thread count).  Each wavefront past ``T`` is masked off and
    would add ``+0.0``, which turns a ``-0.0`` sum into ``+0.0`` and
    flushes a denormal where the device does; since ``(x + 0) + 0 ==
    x + 0`` for every float, one add of ``+0.0`` reproduces them all.
    The zero passes an optimization barrier, or XLA folds ``x + 0.0``
    away.  The same zero is added to every term first: that changes
    only the sign of a zero term (and flushes a denormal one), which the
    last add erases, and it keeps the CPU backend from contracting a
    DOT product into the first add as an FMA (one rounding where the
    interpreter, whose mask is traced, rounds twice).
    """
    T = v.shape[-1]
    zero = None
    if width is not None and T < width:
        zero = lax.optimization_barrier(jnp.zeros((), v.dtype))
        v = v + zero
    m = v.reshape(v.shape[:-1] + (T // num_sps, num_sps))
    acc = m[..., 0, :]
    for i in range(1, T // num_sps):
        acc = acc + m[..., i, :]
    if zero is not None:
        acc = acc + zero
    s = num_sps // 2
    while s >= 1:
        acc = acc[..., :s] + acc[..., s:2 * s]
        s //= 2
    return acc[..., 0]


# ---------------------------------------------------------------------------
# The operand environment
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpEnv:
    """Everything an opcode's value function reads.

    ``rav/rbv/rdv`` are the Ra/Rb/Rd operand columns ``(..., T)`` uint32;
    ``signed``/``imm`` are the decoded type/immediate fields — traced
    scalars under the interpreter, Python constants under the block
    compiler; ``mask`` is the active-thread mask (TSC x predicates) that
    gates the DOT/SUM reduction; ``shared`` is ``(..., S)`` and
    ``tdx_dim`` a scalar or ``(...,)`` per-core vector.
    """

    cfg: EGPUConfig
    rav: Any
    rbv: Any
    rdv: Any
    signed: Any               # traced bool or Python bool
    imm: Any                  # traced int32 or Python int
    mask: Any                 # (..., T) bool
    tid: Any                  # (T,) int32
    shared: Any               # (..., S)
    tdx_dim: Any              # scalar or (...,) int32

    @property
    def alu_mask(self):
        bits = self.cfg.alu_bits
        return _U32((1 << bits) - 1 if bits < 32 else 0xFFFFFFFF)

    def imask(self, v):
        """Integer ALU precision (16-bit ALU configs clip to alu_bits)."""
        return v.astype(_U32) & self.alu_mask

    @property
    def addr(self):
        """LOD/STO effective address: Ra + offset, per thread."""
        return _i(self.rav) + self.imm

    def load(self, addr):
        """Shared-memory gather with the hardware's address clamp."""
        S = self.shared.shape[-1]
        a = jnp.clip(addr, 0, S - 1)
        if self.shared.ndim == 1:
            return self.shared[a]
        return jnp.take_along_axis(self.shared, a, axis=-1)


def store(shared, sidx, val):
    """The one true scatter: STO to shared memory.

    ``sidx`` is the per-thread target index with inactive/out-of-range
    threads already pointed at ``S`` (dropped).  Batched shared memory
    ``(B, S)`` is written as a single flattened scatter — a per-core
    batched scatter is the slowest op on the CPU backend by an order of
    magnitude.
    """
    S = shared.shape[-1]
    if shared.ndim == 1:
        return shared.at[sidx].set(val, mode="drop")
    n = shared.shape[0]
    core = jnp.arange(n, dtype=_I32).reshape((n,) + (1,) * (sidx.ndim - 1))
    flat = jnp.where(sidx < S, core * S + sidx, n * S).ravel()
    return shared.ravel().at[flat].set(val.ravel(),
                                       mode="drop").reshape(shared.shape)


# ---------------------------------------------------------------------------
# Per-opcode value / condition functions
# ---------------------------------------------------------------------------

def build_spec(env: OpEnv) -> list:
    """``spec[op] = (value_fn | None, cond_fn | None)`` over all opcodes.

    Control ops carry no value function (their register write is gated
    off by the ``writes_rd`` table / never emitted by the compiler).
    """
    cfg = env.cfg
    rav, rbv = env.rav, env.rbv
    signed = env.signed
    imask = env.imask

    def shift_amt():
        return rbv & _U32(cfg.alu_bits - 1 if cfg.shift_bits > 1 else 1)

    def f_add(): return imask(rav + rbv)
    def f_sub(): return imask(rav - rbv)
    def f_negi(): return imask(_u(-_i(rav)))
    def f_absi(): return imask(_u(jnp.abs(_i(rav))))

    def f_mul16lo():
        p_s = _sext16(rav) * _sext16(rbv)
        p_u = _i((rav & _U32(0xFFFF)) * (rbv & _U32(0xFFFF)))
        return imask(_u(_sel(signed, p_s, p_u)))

    def f_mul16hi():
        p_s = (_sext16(rav) * _sext16(rbv)) >> 16
        p_u = _u((rav & _U32(0xFFFF)) * (rbv & _U32(0xFFFF))) >> 16
        return imask(_sel(signed, _u(p_s), p_u))

    def f_mul24lo():
        hi, lo, _ = _mul24(rav, rbv, False)
        hi_s, lo_s, _ = _mul24(rav, rbv, True)
        # low 32 bits of the 48-bit product
        u = (lo | (hi << 24))
        s = (lo_s | (hi_s << 24))
        return imask(_sel(signed, s, u))

    def f_mul24hi():
        hi, lo, _ = _mul24(rav, rbv, False)
        hi_s, lo_s, neg = _mul24(rav, rbv, True)
        # arithmetic >>24 of the 48-bit product: extend from bit 47
        # (== bit 23 of hi24) — NOT from the sign flag, which is also
        # set for zero products of opposite-signed operands
        s = jnp.where((hi_s & _U32(0x800000)) != 0,
                      hi_s | _U32(0xFF000000), hi_s)
        return imask(_sel(signed, s, hi))

    def f_and(): return imask(rav & rbv)
    def f_or(): return imask(rav | rbv)
    def f_xor(): return imask(rav ^ rbv)
    def f_not(): return imask(~rav)
    def f_cnot(): return imask(jnp.where(rav == 0, _U32(1), _U32(0)))
    def f_bvs(): return imask(_bit_reverse32(rav))

    def f_shl(): return imask(rav << shift_amt())

    def f_shr():
        log = rav >> shift_amt()
        ari = _u(_i(rav) >> _i(shift_amt()))
        return imask(_sel(signed, ari, log))

    def f_pop(): return imask(lax.population_count(rav))

    def f_max():
        s = jnp.where(_i(rav) > _i(rbv), rav, rbv)
        u = jnp.where(rav > rbv, rav, rbv)
        return imask(_sel(signed, s, u))

    def f_min():
        s = jnp.where(_i(rav) < _i(rbv), rav, rbv)
        u = jnp.where(rav < rbv, rav, rbv)
        return imask(_sel(signed, s, u))

    # FP (bitcast through the uint32 register file)
    def f_fadd(): return _bits(_f(rav) + _f(rbv))
    def f_fsub(): return _bits(_f(rav) - _f(rbv))
    def f_fneg(): return rav ^ _U32(0x80000000)
    def f_fabs(): return rav & _U32(0x7FFFFFFF)
    def f_fmul(): return _bits(_f(rav) * _f(rbv))
    def f_fmax(): return _bits(jnp.maximum(_f(rav), _f(rbv)))
    def f_fmin(): return _bits(jnp.minimum(_f(rav), _f(rbv)))

    # memory / immediates / thread ids.  LODI/TDX/TDY results are
    # produced by the integer datapath, so a 16-bit ALU clips them to
    # ``alu_bits`` like any other integer result; LOD is *not* masked
    # (the shared memory is a full 32-bit datapath) and neither are the
    # FP units (bitcast results bypass the integer ALU entirely).
    def f_lod():
        return env.load(env.addr)

    def f_lodi():
        return imask(jnp.broadcast_to(_u(jnp.int32(env.imm)), rav.shape))

    def f_tdx():
        d = jnp.asarray(env.tdx_dim, _I32)
        return imask(_u(jnp.broadcast_to(env.tid % d[..., None], rav.shape)))

    def f_tdy():
        d = jnp.asarray(env.tdx_dim, _I32)
        return imask(_u(jnp.broadcast_to(env.tid // d[..., None], rav.shape)))

    # extension units: DOT/SUM land in thread 0's Rd.
    def f_dot():
        s = det_sum(jnp.where(env.mask, _f(rav) * _f(rbv), 0.0),
                    cfg.num_sps, cfg.max_threads)
        return jnp.broadcast_to(_bits(s)[..., None], rav.shape)

    def f_sum():
        s = det_sum(jnp.where(env.mask, _f(rav), 0.0), cfg.num_sps,
                    cfg.max_threads)
        return jnp.broadcast_to(_bits(s)[..., None], rav.shape)

    def f_invsqr(): return _bits(lax.rsqrt(_f(rav)))

    fa, fb = _f(rav), _f(rbv)
    spec: list = [None] * NUM_OPCODES
    for o, f in [(Op.ADD, f_add), (Op.SUB, f_sub), (Op.NEG, f_negi),
                 (Op.ABS, f_absi), (Op.MUL16LO, f_mul16lo),
                 (Op.MUL16HI, f_mul16hi), (Op.MUL24LO, f_mul24lo),
                 (Op.MUL24HI, f_mul24hi), (Op.AND, f_and), (Op.OR, f_or),
                 (Op.XOR, f_xor), (Op.NOT, f_not), (Op.CNOT, f_cnot),
                 (Op.BVS, f_bvs), (Op.SHL, f_shl), (Op.SHR, f_shr),
                 (Op.POP, f_pop), (Op.MAX, f_max), (Op.MIN, f_min),
                 (Op.FADD, f_fadd), (Op.FSUB, f_fsub), (Op.FNEG, f_fneg),
                 (Op.FABS, f_fabs), (Op.FMUL, f_fmul), (Op.FMAX, f_fmax),
                 (Op.FMIN, f_fmin), (Op.LOD, f_lod), (Op.LODI, f_lodi),
                 (Op.TDX, f_tdx), (Op.TDY, f_tdy), (Op.DOT, f_dot),
                 (Op.SUM, f_sum), (Op.INVSQR, f_invsqr)]:
        spec[o] = (f, None)
    for o, f in [(Op.IF_EQ, lambda: rav == rbv),
                 (Op.IF_NE, lambda: rav != rbv),
                 (Op.IF_LT, lambda: _i(rav) < _i(rbv)),
                 (Op.IF_LO, lambda: rav < rbv),
                 (Op.IF_LE, lambda: _i(rav) <= _i(rbv)),
                 (Op.IF_LS, lambda: rav <= rbv),
                 (Op.IF_GT, lambda: _i(rav) > _i(rbv)),
                 (Op.IF_HI, lambda: rav > rbv),
                 (Op.IF_GE, lambda: _i(rav) >= _i(rbv)),
                 (Op.IF_HS, lambda: rav >= rbv),
                 (Op.IF_FEQ, lambda: fa == fb),
                 (Op.IF_FNE, lambda: fa != fb),
                 (Op.IF_FLT, lambda: fa < fb),
                 (Op.IF_FLE, lambda: fa <= fb),
                 (Op.IF_FGT, lambda: fa > fb),
                 (Op.IF_FGE, lambda: fa >= fb),
                 (Op.IF_Z, lambda: rav == 0),
                 (Op.IF_NZ, lambda: rav != 0)]:
        spec[o] = (None, f)
    return spec


# ---------------------------------------------------------------------------
# Structural updates: predicate stacks (divergence, Fig. 2)
# ---------------------------------------------------------------------------
#
# ``pstack`` is (..., T, D) bool, ``pdepth`` (T,) or (..., T) int32.  The
# ``en`` gate may be the Python constant True (block compiler: the op
# statically executes) or a traced bool (interpreter: mask-gated).

def pred_ok(pstack, pdepth, D: int):
    """Threads whose every pushed predicate level is True: ``(..., T)``."""
    lvl = jnp.arange(D, dtype=_I32)
    return jnp.all(pstack | (lvl >= pdepth[..., :, None]), axis=-1)


def pred_push(pstack, pdepth, cond, tsc_mask, D: int, en=True):
    """IF.cc: push ``cond`` at the current depth for TSC-active threads."""
    lvl = jnp.arange(D, dtype=_I32)
    oh = (lvl == pdepth[..., :, None]) & tsc_mask[..., :, None] & en
    ps = jnp.where(oh, cond[..., :, None], pstack)
    pd = pdepth + jnp.where(tsc_mask & (pdepth < D) & en, 1, 0)
    return ps, pd


def pred_else(pstack, pdepth, tsc_mask, D: int, en=True):
    """ELSE: flip the top predicate level of TSC-active threads."""
    lvl = jnp.arange(D, dtype=_I32)
    oh = (lvl == (pdepth[..., :, None] - 1)) & tsc_mask[..., :, None] \
        & (pdepth[..., :, None] > 0) & en
    return pstack ^ oh


def pred_pop(pdepth, tsc_mask, en=True):
    """ENDIF: pop one predicate level from TSC-active threads."""
    return pdepth - jnp.where(tsc_mask & (pdepth > 0) & en, 1, 0)


# ---------------------------------------------------------------------------
# Structural updates: sequencer (call/loop stacks)
# ---------------------------------------------------------------------------

def call_push(cstack, csp, ret_pc, en=True):
    """JSR: push the return address (write dropped when the stack is
    full; the pointer still moves, mirroring the one-hot select)."""
    idx = jnp.arange(cstack.shape[-1], dtype=_I32)
    cm = (idx == csp) & en
    return jnp.where(cm, ret_pc, cstack), csp + jnp.where(en, 1, 0)


def call_top(cstack, csp):
    """RTS target: the last pushed return address.

    The index follows JAX dynamic-gather semantics exactly (negative
    wraps once, then clamps) so an unbalanced RTS reads the same slot in
    every execution tier.
    """
    return cstack[csp - 1]


def loop_init(lctr, lsp, count, en=True):
    """INIT: push a loop counter (write dropped when out of range; the
    pointer still moves)."""
    idx = jnp.arange(lctr.shape[-1], dtype=_I32)
    lm = (idx == lsp) & en
    return jnp.where(lm, count, lctr), lsp + jnp.where(en, 1, 0)


def loop_top(lctr, lsp):
    """The counter LOOP tests: top of the loop stack (JAX dynamic-gather
    index semantics, like :func:`call_top`)."""
    return lctr[lsp - 1]


def loop_step(lctr, lsp, en=True):
    """LOOP: decrement the top counter; returns (lctr', taken, lsp_pop)
    where ``lsp_pop`` is the stack pointer after a not-taken pop."""
    lsp1 = lsp - 1
    ltop = loop_top(lctr, lsp)
    taken = ltop > 0
    idx = jnp.arange(lctr.shape[-1], dtype=_I32)
    lctr2 = jnp.where((idx == lsp1) & en, ltop - 1, lctr)
    return lctr2, taken, lsp1
