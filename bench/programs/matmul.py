"""Matrix-matrix multiply (paper §7, Table 7).  A at word 0, B at word
n*n; C overwrites A.

* ``build``: one thread per output element, the k-loop in registers;
* ``build_dot``: the dot-product unit folds a whole <a-row, b-col>
  inner product per issue, results written back by 1-cycle MCU
  stores, software-pipelined 8 DOTs deep.
"""
from __future__ import annotations

from repro.core.assembler import Asm


def _check(cfg, n: int) -> int:
    if n & (n - 1) or n < 8:
        raise ValueError(f"n={n} must be a power of two >= 8")
    if 2 * n * n > cfg.shared_words:
        raise ValueError("A+B do not fit shared memory")
    return n.bit_length() - 1


def build(cfg, n: int):
    ln = _check(cfg, n)
    t = cfg.max_threads
    rpp = t // n
    passes = n // rpp
    a = Asm(cfg)
    (R_J, R_IL, R_IG, R_PB, R_A, R_B, R_AV, R_BV, R_P, R_ACC, R_ONE,
     R_N, R_SH, R_C, R_RPP) = range(1, 16)
    a.tdx(R_J)
    a.tdy(R_IL)
    a.lodi(R_PB, 0)
    a.lodi(R_ONE, 1)
    a.lodi(R_N, n)
    a.lodi(R_SH, ln)
    a.lodi(R_RPP, rpp)
    with a.loop(passes):
        a.add(R_IG, R_IL, R_PB)
        a.shl(R_A, R_IG, R_SH)
        a.add(R_C, R_A, R_J)
        a.or_(R_B, R_J, R_J)        # b addr = j (register copy)
        a.lodi(R_ACC, 0)
        with a.loop(n):
            a.lod(R_AV, R_A, 0)
            a.lod(R_BV, R_B, n * n)
            a.fmul(R_P, R_AV, R_BV)
            a.fadd(R_ACC, R_ACC, R_P)
            a.add(R_A, R_A, R_ONE)
            a.add(R_B, R_B, R_N)
        a.sto(R_ACC, R_C, 0)
        a.add(R_PB, R_PB, R_RPP)
    a.stop()
    return a.assemble(threads_active=t), n


def build_dot(cfg, n: int):
    ln = _check(cfg, n)
    a = Asm(cfg)
    (R_K, R_A, R_B, R_BV, R_AROW, R_N, R_SH, R_C) = range(1, 9)
    dot_regs = list(range(16, 24))      # 8-deep software pipeline
    groups = n // len(dot_regs)
    a.tdx(R_K)                          # k  (tdx_dim = n)
    a.lodi(R_N, n)
    a.lodi(R_SH, ln)
    a.add(R_A, R_K, 0)                  # a addr = 0*n + k
    a.lodi(R_C, 0, tsc="mcu")           # C writeback cursor (SP0)
    with a.loop(n):                     # rows i
        a.lod(R_AROW, R_A, 0)           # a[i, :] across threads
        a.shl(R_B, R_K, R_SH)           # b addr = k*n (+j below)
        with a.loop(groups):            # 8-column groups
            for g, rdot in enumerate(dot_regs):
                a.lod(R_BV, R_B, n * n + g)   # b[k, j+g]
                a.dot(rdot, R_AROW, R_BV)
            for g, rdot in enumerate(dot_regs):
                a.sto(rdot, R_C, g, tsc="mcu")
            a.lodi(R_BV, len(dot_regs))
            a.add(R_B, R_B, R_BV)
            a.add(R_C, R_C, R_BV, tsc="mcu")
        a.add(R_A, R_A, R_N)
    a.stop()
    return a.assemble(threads_active=n), n
