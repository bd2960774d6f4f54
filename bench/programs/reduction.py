"""Vector reduction (paper §7, Table 7): a TSC-subset tree, and the
variant on the SUM extension unit.  x[0:n] in, the sum in word 0."""
from __future__ import annotations

from repro.core import isa
from repro.core.assembler import Asm


def _strides(n: int):
    s = n // 2
    while s >= 1:
        yield s
        s //= 2


def _tsc_for_stride(s: int, n: int):
    """The cheapest TSC coding whose active set covers threads < s."""
    wfs = n // 16
    if s >= 16:
        need = s // 16
        if need == wfs:
            return isa.TSC_FULL
        if 2 * need == wfs:
            return (isa.WIDTH_ALL, isa.DEPTH_HALF)
        if 4 * need == wfs:
            return (isa.WIDTH_ALL, isa.DEPTH_QUARTER)
        return (isa.WIDTH_ALL, isa.DEPTH_WF0) if need == 1 else isa.TSC_FULL
    if s > 4:
        return (isa.WIDTH_ALL, isa.DEPTH_WF0)      # 16 lanes, garbage tail
    if s > 1:
        return (isa.WIDTH_QUARTER, isa.DEPTH_WF0)  # 4 lanes
    return (isa.WIDTH_ONE, isa.DEPTH_WF0)          # MCU


def _check(cfg, n: int) -> None:
    if n % 16 or n > cfg.max_threads:
        raise ValueError(f"n={n} must be a multiple of 16 <= "
                         f"{cfg.max_threads}")


def build(cfg, n: int):
    _check(cfg, n)
    a = Asm(cfg)
    R_TID, R_ACC, R_T = 1, 2, 3
    a.tdx(R_TID)
    a.lod(R_ACC, R_TID, 0)             # acc = x[tid]
    for s in _strides(n):
        tsc = _tsc_for_stride(s, n)
        a.lod(R_T, R_TID, s, tsc=tsc)
        a.fadd(R_ACC, R_ACC, R_T, tsc=tsc)
        a.sto(R_ACC, R_TID, 0, tsc=tsc)
    a.stop()
    return a.assemble(threads_active=max(16, n)), n


def build_dot(cfg, n: int):
    _check(cfg, n)
    a = Asm(cfg)
    R_TID, R_ACC, R_OUT = 1, 2, 5
    a.tdx(R_TID)
    a.lod(R_ACC, R_TID, 0)
    a.sum_(R_OUT, R_ACC)               # thread0.R_OUT = sum over threads
    a.lodi(R_TID, 0, tsc="mcu")
    a.sto(R_OUT, R_TID, 0, tsc="mcu")  # x[0] = result
    a.stop()
    return a.assemble(threads_active=max(16, n)), n
