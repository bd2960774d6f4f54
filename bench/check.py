"""The comparison that decides ``correct``.

Every job that the measured window ran is held, after the window has
closed, to two yardsticks kept with the benchmark:

* the plain reference (``reference.py``): the answer's words, bit for
  bit for data moves and integer programs, within float32 rounding of
  the float64 answer for float programs;
* the simulated-statistics spec (``spec/<config>/<program>.json``):
  cycles, instructions executed, hazard violations and the per-class
  instruction mix, which are a fixed property of a program on a
  configuration and must come out exactly.

Four numbers come out, each with its limit:

``missing``          jobs of the window that never produced a result
``exact_words_off``  answer words of exact programs that differ
``spec_off``         jobs whose simulated statistics differ from spec
``float_gap_u``      the widest gap of a float answer word from the
                     float64 reference, in units of ``2**-24`` times
                     the sum of the magnitudes of the word's terms
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

from . import reference

#: limits; ``float_gap_u`` is set from the readings in PERF.md (sound
#: runs of the program on the chip, and the bfloat16 control)
LIMITS = {"missing": 0, "exact_words_off": 0, "spec_off": 0,
          "float_gap_u": 1024.0}

def sim_row(res) -> np.ndarray:
    """A job result's simulated statistics as one int64 row."""
    return np.concatenate([
        np.asarray([res.cycles, res.steps, res.hazard_violations],
                   np.int64),
        np.asarray(res.stat_cycles, np.int64),
        np.asarray(res.stat_instrs, np.int64)])


def spec_row(spec: dict) -> np.ndarray:
    return np.concatenate([
        np.asarray([spec["cycles"], spec["steps"],
                    spec["hazard_violations"]], np.int64),
        np.asarray(spec["stat_cycles"], np.int64),
        np.asarray(spec["stat_instrs"], np.int64)])


def load_spec(root: pathlib.Path, config: str, program: str) -> dict:
    path = root / "spec" / config / f"{program}.json"
    if not path.is_file():
        raise FileNotFoundError(
            f"no simulated-statistics spec {path}; record it with "
            f"bench/record_spec.py")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Block:
    """Jobs of one program: their inputs, the answer words they
    produced and their simulated statistics.  ``done`` marks the rows
    that produced a result at all."""

    kind: str
    n: int
    inputs: np.ndarray          # (k, words) uint32
    got: np.ndarray             # (k, m) uint32
    sim: np.ndarray             # (k, F) int64
    done: np.ndarray            # (k,) bool


def float_gap_u(got_u32: np.ndarray, want: np.ndarray,
                scale: np.ndarray) -> float:
    """Widest gap of float32 words from their float64 reference, in
    units of ``2**-24 * scale``; NaN or infinity reads as infinite."""
    if got_u32.size == 0:
        return 0.0
    got = np.ascontiguousarray(got_u32, np.uint32).view(
        np.float32).astype(np.float64)
    with np.errstate(invalid="ignore", over="ignore"):
        gap = np.abs(got - want) / (np.maximum(scale, 1e-300)
                                    * reference.U32)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    return float(gap.max())


def compare(blocks: list[Block], specs: dict[tuple[str, int], dict]
            ) -> dict[str, float]:
    """The four numbers over every job in ``blocks``.  ``specs`` maps
    ``(kind, n)`` to that program's spec."""
    out = {"missing": 0, "exact_words_off": 0, "spec_off": 0,
           "float_gap_u": 0.0}
    for b in blocks:
        out["missing"] += int(np.count_nonzero(~b.done))
        if not b.done.any():
            continue
        inputs, got, sim = b.inputs[b.done], b.got[b.done], b.sim[b.done]
        want, scale = reference.expected(b.kind, b.n, inputs)
        if scale is None:
            out["exact_words_off"] += int(np.count_nonzero(got != want))
        else:
            out["float_gap_u"] = max(out["float_gap_u"],
                                     float_gap_u(got, want, scale))
        ref = spec_row(specs[(b.kind, b.n)])
        out["spec_off"] += int(np.count_nonzero(
            np.any(sim != ref[None, :], axis=1)))
    return out


def verdict(numbers: dict[str, float]) -> bool:
    return all(numbers[k] <= lim for k, lim in LIMITS.items())


def lines(numbers: dict[str, float]) -> list[str]:
    return [f"check {k} {numbers[k]!r} limit {LIMITS[k]!r}"
            for k in LIMITS]


def as_json(numbers: dict[str, float]) -> dict:
    return {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
