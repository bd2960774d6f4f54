"""Radix-2 DIT FFT (paper §7, Table 8), one thread per butterfly.

Layout (32-bit words): re [0,n), im [n,2n), twiddle-re [2n, 2n+n/2),
twiddle-im [2n+n/2, 3n), scratch-re [3n, 4n), scratch-im [4n, 5n).
The input is bit-reverse permuted (BVS) into scratch, then log2(n)
in-place butterfly stages run there.
"""
from __future__ import annotations

from repro.core.assembler import Asm


def build(cfg, n: int):
    if n & (n - 1) or n < 16:
        raise ValueError(f"n={n} must be a power of two >= 16")
    ln = n.bit_length() - 1
    threads = max(16, n // 2)
    if threads > cfg.max_threads or 5 * n > cfg.shared_words:
        raise ValueError("FFT size out of range")
    TW_RE, TW_IM = 2 * n, 2 * n + n // 2
    S_RE, S_IM = 3 * n, 4 * n

    a = Asm(cfg)
    (R_TID, R_E, R_REV, R_SH, R_V, R_OFF,
     R_I, R_TW, R_POS, R_GRP, R_DM,
     R_AR, R_AI, R_BR, R_BI, R_WR, R_WI,
     R_M1, R_M2, R_TR, R_TI, R_O) = range(1, 23)

    a.tdx(R_TID)
    a.lodi(R_SH, 32 - ln)
    for off in (0, n // 2):             # bit-reversal reorder
        a.lodi(R_OFF, off)
        a.add(R_E, R_TID, R_OFF)
        a.bvs(R_REV, R_E)
        a.shr(R_REV, R_REV, R_SH)
        a.lod(R_V, R_REV, 0)
        a.sto(R_V, R_E, S_RE)
        a.lod(R_V, R_REV, n)
        a.sto(R_V, R_E, S_IM)
    for s in range(ln):                 # butterfly stages
        d = 1 << s
        a.lodi(R_DM, d - 1)
        a.and_(R_POS, R_TID, R_DM)      # pos = t & (d-1)
        a.lodi(R_SH, s)
        a.shr(R_GRP, R_TID, R_SH)       # grp = t >> s
        a.lodi(R_SH, s + 1)
        a.shl(R_I, R_GRP, R_SH)
        a.add(R_I, R_I, R_POS)          # i = grp*2d + pos   (j = i + d)
        a.lodi(R_SH, ln - 1 - s)
        a.shl(R_TW, R_POS, R_SH)        # twiddle index = pos * n/(2d)
        a.lod(R_AR, R_I, S_RE)
        a.lod(R_AI, R_I, S_IM)
        a.lod(R_BR, R_I, S_RE + d)
        a.lod(R_BI, R_I, S_IM + d)
        a.lod(R_WR, R_TW, TW_RE)
        a.lod(R_WI, R_TW, TW_IM)
        a.fmul(R_M1, R_BR, R_WR)
        a.fmul(R_M2, R_BI, R_WI)
        a.fsub(R_TR, R_M1, R_M2)        # tr = br*wr - bi*wi
        a.fmul(R_M1, R_BR, R_WI)
        a.fmul(R_M2, R_BI, R_WR)
        a.fadd(R_TI, R_M1, R_M2)        # ti = br*wi + bi*wr
        a.fadd(R_O, R_AR, R_TR)
        a.sto(R_O, R_I, S_RE)           # re[i] = ar + tr
        a.fadd(R_O, R_AI, R_TI)
        a.sto(R_O, R_I, S_IM)
        a.fsub(R_O, R_AR, R_TR)
        a.sto(R_O, R_I, S_RE + d)       # re[j] = ar - tr
        a.fsub(R_O, R_AI, R_TI)
        a.sto(R_O, R_I, S_IM + d)
    a.stop()
    # a TDX grid of n/2: at n = 16 threads 8-15 duplicate threads 0-7
    return a.assemble(threads_active=threads), n // 2
