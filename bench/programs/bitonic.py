"""Bitonic sort (paper §7, Table 8), one thread per element, Batcher's
network unrolled; MIN or MAX chosen with the predicate stack.  int32
x[0:n] in, sorted in place."""
from __future__ import annotations

from repro.core import isa
from repro.core.assembler import Asm


def build(cfg, n: int):
    if not cfg.has_predicates:
        raise ValueError("bitonic sort requires predicates")
    if n % 16 or n > cfg.max_threads or n & (n - 1):
        raise ValueError("n must be a power of two, a multiple of 16, "
                         "within the thread space")
    a = Asm(cfg)
    (R_TID, R_J, R_K, R_P, R_V, R_PV, R_TJ, R_TK, R_OUT) = range(1, 10)
    a.tdx(R_TID)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            a.lodi(R_J, j)
            a.lodi(R_K, k)
            a.xor(R_P, R_TID, R_J)          # partner index
            a.lod(R_V, R_TID, 0)
            a.lod(R_PV, R_P, 0)
            a.and_(R_TJ, R_TID, R_J)
            a.and_(R_TK, R_TID, R_K)
            a.cnot(R_TJ, R_TJ)              # 1 iff lower partner
            a.cnot(R_TK, R_TK)              # 1 iff ascending block
            a.if_("eq", R_TJ, R_TK)         # lower==asc -> keep MIN
            a.min_(R_OUT, R_V, R_PV, typ=isa.Typ.I32)
            a.else_()
            a.max_(R_OUT, R_V, R_PV, typ=isa.Typ.I32)
            a.endif()
            a.sto(R_OUT, R_TID, 0)
            j //= 2
        k *= 2
    a.stop()
    return a.assemble(threads_active=n), n
