"""The plain reference for the program corpus, in NumPy alone.

For each program kind it knows three things, independently of the
simulator: how a job's shared-memory image is laid out and drawn from a
seeded generator (``make_inputs``), which words of the final image hold
the answer (``result_words``), and what the answer is (``expected``),
computed in float64 or exactly.  Nothing here imports the program under
test.

Float answers come with a per-word ``scale``: the sum of the magnitudes
of the terms that make up that word (``sum |x|`` for a sum,
``|A| @ |B|`` for a product, ``sum |x|`` for each FFT bin).  A float32
computation of the word, in any order, lies within a few multiples of
``scale * 2**-24`` of the exact value, which is what ``check.py``
compares against.
"""
from __future__ import annotations

import numpy as np

#: float32 unit roundoff
U32 = 2.0 ** -24
#: smallest normal float32: inputs below it are nudged up, so no job
#: starts from a denormal
_TINY = np.float32(np.finfo(np.float32).tiny)


def _normal(rng, k: int, m: int, scale: float = 1.0) -> np.ndarray:
    x = rng.standard_normal((k, m), dtype=np.float32)
    if scale != 1.0:
        x *= np.float32(scale)
    x[np.abs(x) < _TINY] = _TINY
    return x


def _twiddles(n: int) -> np.ndarray:
    m = np.arange(n // 2)
    return np.concatenate([np.cos(2 * np.pi * m / n),
                           -np.sin(2 * np.pi * m / n)]).astype(np.float32)


def make_inputs(kind: str, n: int, rng: np.random.Generator,
                k: int) -> np.ndarray:
    """``k`` jobs' shared-memory images, as ``(k, words)`` uint32."""
    if kind in ("reduction", "reduction_dot"):
        x = _normal(rng, k, n)
    elif kind == "transpose":
        x = _normal(rng, k, n * n)
    elif kind in ("matmul", "matmul_dot"):
        x = _normal(rng, k, 2 * n * n, 1.0 / np.sqrt(n))
    elif kind == "bitonic":
        return rng.integers(-(2**30), 2**30, size=(k, n),
                            dtype=np.int32).view(np.uint32)
    elif kind == "fft":
        x = np.zeros((k, 5 * n), np.float32)
        x[:, :2 * n] = _normal(rng, k, 2 * n)
        x[:, 2 * n:3 * n] = _twiddles(n)
    else:
        raise ValueError(f"unknown program kind {kind!r}")
    return x.view(np.uint32)


def _span(kind: str, n: int) -> list[tuple[int, int]]:
    """Word ranges of the final image that hold the answer."""
    if kind in ("reduction", "reduction_dot"):
        return [(0, 1)]
    if kind == "transpose":
        return [(n * n, 2 * n * n)]
    if kind in ("matmul", "matmul_dot"):
        return [(0, n * n)]
    if kind == "bitonic":
        return [(0, n)]
    if kind == "fft":
        return [(3 * n, 4 * n), (4 * n, 5 * n)]
    raise ValueError(f"unknown program kind {kind!r}")


def result_size(kind: str, n: int) -> int:
    """How many words the answer has."""
    return sum(b - a for a, b in _span(kind, n))


def result_words(kind: str, n: int, shared: np.ndarray) -> np.ndarray:
    """A copy of the answer's words out of final images ``(k, S)`` or
    ``(S,)``: never a view, which would keep a whole batch's images
    alive for as long as the answer is kept."""
    shared = np.asarray(shared)
    return np.concatenate([shared[..., a:b] for a, b in _span(kind, n)],
                          axis=-1)


def expected(kind: str, n: int, inputs: np.ndarray):
    """``(want, scale)`` for ``(k, words)`` uint32 inputs: ``want`` is
    ``(k, m)`` uint32 for exact kinds (``scale`` is ``None``), else
    ``(k, m)`` float64 with its ``(k, m)`` float64 ``scale``."""
    inputs = np.asarray(inputs, np.uint32)
    k = inputs.shape[0]
    if kind == "bitonic":
        return np.sort(inputs[:, :n].view(np.int32), axis=1).view(
            np.uint32), None
    if kind == "transpose":
        return np.ascontiguousarray(
            inputs[:, :n * n].reshape(k, n, n).transpose(0, 2, 1)
        ).reshape(k, n * n), None
    f = inputs.view(np.float32).astype(np.float64)
    if kind in ("reduction", "reduction_dot"):
        x = f[:, :n]
        return x.sum(axis=1, keepdims=True), np.abs(x).sum(axis=1,
                                                           keepdims=True)
    if kind in ("matmul", "matmul_dot"):
        a = f[:, :n * n].reshape(k, n, n)
        b = f[:, n * n:2 * n * n].reshape(k, n, n)
        return ((a @ b).reshape(k, n * n),
                (np.abs(a) @ np.abs(b)).reshape(k, n * n))
    if kind == "fft":
        z = f[:, :n] + 1j * f[:, n:2 * n]
        sp = np.fft.fft(z, axis=1)
        s = (np.abs(f[:, :n]) + np.abs(f[:, n:2 * n])).sum(axis=1,
                                                           keepdims=True)
        return (np.concatenate([sp.real, sp.imag], axis=1),
                np.broadcast_to(s, (k, 2 * n)))
    raise ValueError(f"unknown program kind {kind!r}")
