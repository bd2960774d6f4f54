"""Matrix transpose (paper §7, Table 7): an n x n matrix at word 0,
its transpose written at word n*n, in chunks of the thread space."""
from __future__ import annotations

from repro.core.assembler import Asm


def build(cfg, n: int):
    t = cfg.max_threads
    if n * n % t or n & (n - 1):
        raise ValueError("matrix must tile by the thread space")
    if 2 * n * n > cfg.shared_words:
        raise ValueError("matrix pair does not fit shared memory")
    chunks = max(1, n * n // t)
    ln = n.bit_length() - 1
    dst_base = n * n

    a = Asm(cfg)
    (R_E, R_ROW, R_COL, R_DST, R_SHIFT, R_MASK, R_V, R_DSTEP, R_SSTEP,
     R_T) = range(1, 11)
    a.tdx(R_E)                     # element index = tid
    a.lodi(R_SHIFT, ln)
    a.lodi(R_MASK, n - 1)
    a.shr(R_ROW, R_E, R_SHIFT)     # row = e >> log2 n
    a.and_(R_COL, R_E, R_MASK)     # col = e & (n-1)
    a.shl(R_DST, R_COL, R_SHIFT)   # dst = col * n
    a.add(R_DST, R_DST, R_ROW)     # dst += row
    a.lodi(R_T, dst_base)
    a.add(R_DST, R_DST, R_T)       # dst += dst_base
    a.lodi(R_SSTEP, t)             # src chunk stride
    a.lodi(R_DSTEP, t >> ln)       # dst chunk stride = t / n
    if chunks > 1:
        with a.loop(chunks):
            a.lod(R_V, R_E, 0)
            a.sto(R_V, R_DST, 0)
            a.add(R_E, R_E, R_SSTEP)
            a.add(R_DST, R_DST, R_DSTEP)
    else:
        a.lod(R_V, R_E, 0)
        a.sto(R_V, R_DST, 0)
    a.stop()
    return a.assemble(threads_active=t), t
