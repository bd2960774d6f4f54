"""The benchmark's frozen copy of the paper's §7 program corpus.

Each builder assembles one program through the public assembler
(``repro.core.assembler.Asm``) and returns its image and TDX grid
width.  The copies are pinned by golden digests
(``bench/programs/golden.json``), so a change to the repository's own
corpus under ``src/repro/programs/`` never changes the benchmark's
traffic.  What a job reads and writes, and what it should produce, is
the plain reference's business (``bench/reference.py``), not this
package's.

A program is named ``<kind>_<n>``: ``reduction_32``,
``matmul_dot_64``, ``fft_32`` ...
"""
from __future__ import annotations

import dataclasses

from . import bitonic, fft, matmul, reduction, transpose

#: kind -> builder(cfg, n) -> (image, tdx_dim)
BUILDERS = {
    "reduction": reduction.build,
    "reduction_dot": reduction.build_dot,
    "transpose": transpose.build,
    "matmul": matmul.build,
    "matmul_dot": matmul.build_dot,
    "bitonic": bitonic.build,
    "fft": fft.build,
}


@dataclasses.dataclass(frozen=True)
class Program:
    """One assembled program of the corpus."""

    name: str
    kind: str
    n: int
    image: object           # repro.core.assembler.ProgramImage
    tdx_dim: int


def parse(name: str) -> tuple[str, int]:
    """``"matmul_dot_64"`` -> ``("matmul_dot", 64)``."""
    kind, _, n = name.rpartition("_")
    if kind not in BUILDERS or not n.isdigit():
        raise ValueError(f"unknown program {name!r}; kinds are "
                         f"{sorted(BUILDERS)} followed by _<n>")
    return kind, int(n)


def build(cfg, name: str) -> Program:
    kind, n = parse(name)
    image, tdx_dim = BUILDERS[kind](cfg, n)
    return Program(name=name, kind=kind, n=n, image=image, tdx_dim=tdx_dim)
