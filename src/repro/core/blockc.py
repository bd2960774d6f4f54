"""The eGPU block compiler: specialize execution to the static program.

The interpreter (:mod:`repro.core.executor`) pays full per-instruction
dispatch cost — a program-row gather, an opcode-metadata gather, and a
switch/where-chain over the working set — on every ``while_loop`` trip.
But every :class:`ProgramImage` is completely static, and the eGPU ISA
has **no data-dependent branches**: JMP/JSR/LOOP targets and INIT loop
counts are all immediates, so the entire execution path (and with it the
cycle count, the instruction-mix profile and the RAW hazard checker) is
decodable ahead of time.  This module exploits that:

* the program is decomposed at control-flow boundaries into **basic
  blocks** (leaders: entry, branch/call targets, return addresses,
  fall-throughs past a sequencer op);
* each block is traced with opcodes/registers/immediates/TSC fields as
  *Python constants* — no program gather, no opcode-table gather, no
  switch, no hazard machinery — so the whole block fuses into one
  straight-line XLA computation (per-opcode value semantics come from
  :mod:`repro.core.semantics`, shared with the interpreter);
* a small ``lax.while_loop`` drives block to block through a
  ``lax.switch`` over the block entries, carrying only the architectural
  state;
* hazards, cycles-at-issue and the final hazard bookkeeping are computed
  **once, statically** by simulating the sequencer on the host
  (:func:`_simulate`); the baked results are bit-identical to the
  interpreter's because the simulated path *is* the executed path.

The dynamic state is split in two.  ``_Data`` (registers, shared memory,
predicate stacks, TDX grid) is per-job: under the fleet's compiled tier
it carries a leading batch axis and every same-program core advances in
lock-step through identical blocks.  ``_Seq`` (PC, cycles, stacks,
counters) is data-independent — identical for every core running the
program — so it stays unbatched even in a batched run, and block-to-block
control flow remains *real* control flow (one switch branch executes)
instead of vmap's execute-everything-select-one.

On top of the basic-block tier sits the **superblock** tier: because
LOOP trip counts are INIT immediates, the *entire* execution path is one
static sequence of blocks, and the per-back-edge ``lax.switch`` dispatch
the block driver pays is avoidable.  The path simulator folds the
executed path online into a superblock *schedule* — straight-line pc
runs plus ``(body, count)`` repeat nodes at LOOP back-edges (fold is
equality-guarded, so a first iteration entered mid-body peels off
naturally and the schedule always flattens back to the exact executed
path).  The superblock runner traces that schedule with **no
``while_loop`` and no ``switch`` at all**: repeats small enough for the
trace budget unroll fully into the surrounding straight line; large
repeats become a ``lax.fori_loop`` whose body is the loop trace fused
once.  Every data-independent leaf (PC, cycles, steps, stacks, stats,
hazards) is baked from the simulation; only registers, shared memory and
the predicate state are traced.  Programs whose schedule exceeds the
trace budget fall back to the basic-block driver, and programs the
compiler rejects entirely fall back to the interpreter:
superblock → basic blocks → interpreter, bit-identical at every step.

Results are bit-identical to :func:`repro.core.executor.run_program` —
registers, shared memory, cycles, steps, PC, stats, hazard rows and
violation count — which the equivalence suites (``tests/test_blockc.py``,
``tests/test_superblock.py``) pin across the program suite and
configuration space.

**Tier selection is a static cost decision** (:class:`TierPolicy`): the
same way the paper fixes the pipeline structure from the statically
known fabric, ``mode="auto"`` picks between the basic-block driver and
the superblock runner from the already-computed path simulation —
dispatch counts, executed instructions, the repeat-node trip
distribution and the trace cost — instead of a binary eligibility
check.  The calibration behind the default thresholds lives in
``benchmarks/superblock.py`` (the ``auto_tier`` crossover sweep), and
every threshold is overridable per policy instance.

Callers that only read shared memory and the cycle count (the fleet
scheduler, throughput benchmarks) use the **light path**
(:meth:`CompiledProgram.run_light` / ``run_batch_light`` /
``run_light_dev``): only ``(shared, cycles, halted)`` leave the device,
nothing is donated (so device-resident inputs can be replayed across
drains), and the 18-leaf :class:`MachineState` assembly is skipped
entirely.
"""
from __future__ import annotations

import hashlib
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import cfg as cfg_mod
from . import isa, semantics
from . import machine as machine_mod
from .assembler import ProgramImage
from .config import EGPUConfig
from .executor import (_PF_IMM, _PF_OP, _PF_RA, _PF_RB, _PF_RD, _PF_TSC,
                       _PF_TYP, _TC_CLS, _TC_LAT, _TC_PER_WF0, _TC_READS_RA,
                       _TC_READS_RB, _TC_READS_RD, _TC_SCALAR, _TC_WRITES_PRED,
                       _TC_WRITES_RD, name_kernel, pad_image, tables_np)
from .isa import Op, Typ
from .machine import MachineState
from ..obs import trace as obs_trace

_I32 = jnp.int32
_U32 = jnp.uint32

#: block/trace structure is shared with the static analyzer — see
#: ``repro.core.cfg`` for the definitions
_SEQ_TERM = cfg_mod.SEQ_TERM
_MAX_BLOCK = cfg_mod.MAX_BLOCK
_MAX_TRACE = cfg_mod.MAX_TRACE

#: a repeat whose *executed* size is at most this unrolls fully into the
#: surrounding straight line (maximum fusion); larger repeats run as a
#: ``lax.fori_loop`` over the once-traced body.
_UNROLL_FULL = 256

#: host-side path-simulation bound (a program must halt within
#: ``min(cfg.max_steps, _SIM_CAP)`` to be block-compilable)
_SIM_CAP = 4_000_000


class BlockCompileError(Exception):
    """The program cannot be block-compiled (e.g. it does not halt within
    ``cfg.max_steps``, so interpreter equivalence cannot be guaranteed at
    block granularity).  Callers fall back to the interpreter."""


def _cdiv(a, b):
    return (a + b - 1) // b


def _gidx(i: int, n: int) -> int:
    """JAX dynamic-gather index semantics: negative wraps once, then
    clamps into range (mirrors ``arr[i]`` with a traced ``i``)."""
    if i < 0:
        i += n
    return min(max(i, 0), n - 1)


def _i32wrap(v: int) -> int:
    return ((v + (1 << 31)) & 0xFFFFFFFF) - (1 << 31)


# ---------------------------------------------------------------------------
# Static decode helpers
# ---------------------------------------------------------------------------

def _wfs_table(cfg: EGPUConfig, threads: int) -> list[int]:
    w_rt = _cdiv(threads, cfg.num_sps)
    return [1, w_rt, max(1, _cdiv(w_rt, 2)), max(1, _cdiv(w_rt, 4))]


def _tsc_static(cfg: EGPUConfig, tsc: int, threads: int):
    """(wfs, tsc_mask) for one instruction — everything Table 3 encodes,
    folded to Python/NumPy constants.  The mask spans the program's own
    ``threads``, the thread axis the compiled kernels trace."""
    width_code = (tsc >> 2) & 3
    depth_code = tsc & 3
    wfs = _wfs_table(cfg, threads)[depth_code]
    lanes = isa.WIDTH_LANES[width_code]
    tid = np.arange(threads)
    tsc_mask = (tid % cfg.num_sps < lanes) & (tid // cfg.num_sps < wfs)
    return wfs, tsc_mask


# ---------------------------------------------------------------------------
# CFG decomposition
# ---------------------------------------------------------------------------

#: shared with the static analyzer — extracted to ``repro.core.cfg``
_decompose = cfg_mod.decompose


# ---------------------------------------------------------------------------
# Superblock schedules: the compressed static path
# ---------------------------------------------------------------------------
#
# A *schedule* is a tuple of items; an item is an ``int`` pc (execute
# that instruction) or ``("rep", body, count)`` where ``body`` is itself
# a schedule executed ``count`` times.  Flattening a schedule always
# reproduces the exact executed path — folding is equality-guarded.

def _sched_insts(items) -> int:
    """Instruction slots a schedule *traces* (each repeat body once)."""
    n = 0
    for it in items:
        n += 1 if isinstance(it, (int, np.integer)) else _sched_insts(it[1])
    return n


def _sched_execd(items) -> int:
    """Instructions a schedule *executes* (repeat bodies times count)."""
    n = 0
    for it in items:
        if isinstance(it, (int, np.integer)):
            n += 1
        else:
            n += it[2] * _sched_execd(it[1])
    return n


def _trace_cost(items) -> int:
    """Instructions the superblock runner will actually trace, given the
    full-unroll policy (small repeats inline ``count`` times, large ones
    trace the body once under ``lax.fori_loop``)."""
    c = 0
    for it in items:
        if isinstance(it, (int, np.integer)):
            c += 1
        else:
            ex = it[2] * _sched_execd(it[1])
            c += ex if ex <= _UNROLL_FULL else _trace_cost(it[1])
    return c


class _PlanStats(NamedTuple):
    """What the superblock runner would actually do with a schedule,
    mirroring its unroll policy exactly (see ``_apply_schedule``)."""

    trace_cost: int             # instructions traced (== _trace_cost)
    fori_reps: int              # repeat nodes run as ``lax.fori_loop``
    unrolled_reps: int          # repeat nodes inlined into the trace
    fori_trips: tuple           # trip counts of the fori repeats
    fori_execd: int             # instructions executed inside fori reps


def _plan_stats(items) -> _PlanStats:
    trace = fori = unrolled = fori_execd = 0
    trips: list[int] = []
    for it in items:
        if isinstance(it, (int, np.integer)):
            trace += 1
            continue
        _, body, count = it
        ex = count * _sched_execd(body)
        if ex <= _UNROLL_FULL:
            # the whole subtree inlines: nested repeats unroll with it
            trace += ex
            unrolled += 1 + _count_reps(body)
        else:
            sub = _plan_stats(body)
            trace += sub.trace_cost
            fori += 1 + sub.fori_reps
            unrolled += sub.unrolled_reps
            trips.append(count)
            trips.extend(sub.fori_trips)
            fori_execd += ex
    return _PlanStats(trace_cost=trace, fori_reps=fori,
                      unrolled_reps=unrolled, fori_trips=tuple(trips),
                      fori_execd=fori_execd)


def _count_reps(items) -> int:
    n = 0
    for it in items:
        if not isinstance(it, (int, np.integer)):
            n += 1 + _count_reps(it[1])
    return n


def _sched_rep_trips(items) -> int:
    """Summed trip counts over every repeat node (each node once, like
    ``_PlanStats.fori_trips``) — event-counter bookkeeping."""
    n = 0
    for it in items:
        if not isinstance(it, (int, np.integer)):
            n += it[2] + _sched_rep_trips(it[1])
    return n


def _sched_rep_execd(items) -> int:
    """Instructions executed inside any repeat node (top-level bodies
    times count, nesting included) — event-counter bookkeeping."""
    n = 0
    for it in items:
        if not isinstance(it, (int, np.integer)):
            n += it[2] * _sched_execd(it[1])
    return n


#: default :class:`TierPolicy` threshold table.  Calibrated on the CPU
#: backend by the ``auto_tier`` crossover sweep in
#: ``benchmarks/superblock.py`` (loop_saxpy back-edge counts 8 -> 2048,
#: interleaved best-of timing through the light path, which is what the
#: fleet scheduler and the throughput benchmarks actually run): the
#: basic-block driver's cost grows ~linearly with its ``lax.switch``
#: dispatch count while the superblock runner stays nearly flat, and
#: the superblock's fixed per-call cost — mostly the 18-leaf
#: ``MachineState`` assembly on the full path — shrinks enough on the
#: light path that the measured crossover sits between 16 and 32
#: back-edges.  Batched lock-step runs tilt further: the block driver's
#: per-dispatch carried-state copies scale with the batch width, and at
#: batch >= 4 the superblock tier measured faster (or equal) on every
#: swept program, so wide batches always take an eligible superblock.
_TIER_DEFAULTS: dict[str, int | None] = {
    # hard eligibility bound on the traced-instruction budget
    # (None -> the module-wide ``_MAX_TRACE``)
    "max_trace_cost": None,
    # batches at least this wide always take an eligible superblock
    "batch_superblock_min": 4,
    # single-core: a plan must save at least this many block-driver
    # switch dispatches to amortize the superblock's fixed overhead
    "min_backedge_dispatches": 24,
    # single-core: a plan tracing at least this many instructions wins
    # on cross-block fusion even with few dispatches (bitonic/FFT-like
    # straight-line-heavy programs); below it, short fully-unrolled
    # traces stay on the (cheaper-to-launch) block driver
    "min_trace_fusion": 256,
    # single-core: a plan executing at least this many instructions
    # inside fori repeats amortizes the fixed overhead through the fused
    # loop body regardless of the dispatch count
    "min_fori_execd": 8192,
}


class TierPolicy:
    """The static cost model behind ``mode="auto"`` tier selection.

    Decides basic-block driver vs superblock runner from the host-side
    path simulation alone (:class:`_SimResult`) — no measurement, no
    dynamic feedback — the way the paper fixes processor structure from
    the statically-known resource mix.  The decision procedure, first
    match wins:

    1. no folded schedule, or its trace cost over ``max_trace_cost``
       -> **blocks** (ineligible);
    2. ``batch >= batch_superblock_min`` -> **superblock** (the block
       driver's per-dispatch carried-state copies scale with the batch
       width; measured at batch 32 the superblock tier is faster on
       every swept program);
    3. ``dispatches >= min_backedge_dispatches`` -> **superblock** (the
       dispatch savings amortize the fixed overhead);
    4. ``trace_cost >= min_trace_fusion`` -> **superblock** (cross-block
       fusion of a long trace — whether straight-line or unrolled);
    5. instructions executed inside ``fori``-run repeats
       ``>= min_fori_execd`` -> **superblock**;
    6. otherwise -> **blocks** (small paths — few dispatches, short
       trace: the superblock's fixed per-call cost eats the dispatch
       win).

    Thresholds are overridable per instance (``TierPolicy(
    min_backedge_dispatches=64)``); instances are immutable, hashable
    and usable as compile-cache key components.
    """

    def __init__(self, **overrides: int | None):
        unknown = set(overrides) - set(_TIER_DEFAULTS)
        if unknown:
            raise ValueError(
                f"unknown TierPolicy thresholds {sorted(unknown)}; "
                f"known: {sorted(_TIER_DEFAULTS)}")
        table = dict(_TIER_DEFAULTS)
        table.update(overrides)
        self._table = table
        self._key = tuple(sorted(table.items()))

    @property
    def table(self) -> dict[str, int | None]:
        """A copy of the threshold table (the instance stays immutable)."""
        return dict(self._table)

    def __eq__(self, other) -> bool:
        return isinstance(other, TierPolicy) and self._key == other._key

    def __hash__(self) -> int:
        return hash(self._key)

    def __repr__(self) -> str:
        diff = {k: v for k, v in self._table.items()
                if v != _TIER_DEFAULTS[k]}
        return f"TierPolicy({', '.join(f'{k}={v}' for k, v in diff.items())})"

    # ------------------------------------------------------------ model
    def batch_class(self, batch: int) -> int:
        """Collapse a batch-size hint to the classes the decision can
        distinguish (keeps compile-cache keys from fragmenting across
        every batch shape)."""
        wide = self._table["batch_superblock_min"]
        return wide if batch >= wide else 1

    def features(self, sim: _SimResult,
                 cfg_facts: dict | None = None) -> dict:
        """The decision's inputs, extracted from one path simulation.

        ``cfg_facts`` merges static control-flow-graph facts
        (:func:`repro.core.cfg.summary`) into the feature dict — the
        decision rules ignore keys they don't know, so the extra
        features ride along for logging and offline cost-model
        fitting."""
        cap = self._table["max_trace_cost"]
        cap = _MAX_TRACE if cap is None else cap
        base = {"dispatches": sim.dispatches, "execd": sim.steps}
        if cfg_facts:
            base.update(cfg_facts)
        if sim.schedule is None:
            return {**base, "eligible": False, "trace_cost": None,
                    "fori_reps": 0, "unrolled_reps": 0,
                    "fori_trips": (), "fori_execd": 0}
        ps = _plan_stats(sim.schedule)
        return {**base, "eligible": ps.trace_cost <= cap,
                "trace_cost": ps.trace_cost, "fori_reps": ps.fori_reps,
                "unrolled_reps": ps.unrolled_reps,
                "fori_trips": ps.fori_trips, "fori_execd": ps.fori_execd}

    def choose(self, sim: _SimResult, batch: int = 1, *,
               features: dict | None = None) -> str:
        """``"superblock"`` or ``"blocks"`` for this path at this batch
        width — the cheaper tier under the calibrated cost model.
        ``features`` accepts a precomputed :meth:`features` result so a
        caller that already extracted them doesn't pay the schedule
        walk twice."""
        f = self.features(sim) if features is None else features
        tier, rule = self._decide(f, batch)
        tr = obs_trace.current_tracer()
        if tr is not None:
            feats = {k: list(v) if isinstance(v, tuple) else v
                     for k, v in f.items()}
            tr.event("tier_decision", tier=tier, rule=rule,
                     batch=int(batch), features=feats,
                     thresholds=dict(self._table))
        return tier

    def _decide(self, f: dict, batch: int) -> tuple[str, str]:
        """(tier, first-matching rule) — the loggable decision core."""
        if not f["eligible"]:
            return "blocks", "ineligible (no schedule or over trace cap)"
        t = self._table
        if batch >= t["batch_superblock_min"]:
            return "superblock", (f"batch {batch} >= "
                                  f"batch_superblock_min "
                                  f"{t['batch_superblock_min']}")
        if f["dispatches"] >= t["min_backedge_dispatches"]:
            return "superblock", (f"dispatches {f['dispatches']} >= "
                                  f"min_backedge_dispatches "
                                  f"{t['min_backedge_dispatches']}")
        if f["trace_cost"] >= t["min_trace_fusion"]:
            return "superblock", (f"trace_cost {f['trace_cost']} >= "
                                  f"min_trace_fusion "
                                  f"{t['min_trace_fusion']}")
        if f["fori_execd"] >= t["min_fori_execd"]:
            return "superblock", (f"fori_execd {f['fori_execd']} >= "
                                  f"min_fori_execd {t['min_fori_execd']}")
        return "blocks", "no superblock rule fired"


#: the policy ``mode="auto"`` uses unless a caller overrides it
DEFAULT_TIER_POLICY = TierPolicy()


#: Per-backend threshold tables consulted by
#: :meth:`TierPolicy.for_backend`.  ``"cpu"`` is the measured default
#: (the ``auto_tier`` sweep above).  The ``"gpu"``/``"tpu"`` seeds are
#: *priors*, not measurements: on accelerators the block driver's
#: ``lax.switch`` dispatch is relatively more expensive (each dispatch
#: is a device-side branch over all traced blocks) while the
#: superblock's fixed host-side cost is amortized by the launch, so the
#: crossover moves earlier.  ``benchmarks/calibrate.py`` replaces a
#: seed with a fitted table by running the same sweep on the actual
#: backend and calling :func:`register_backend_table`.
_TIER_TABLES: dict[str, dict[str, int | None]] = {
    "cpu": dict(_TIER_DEFAULTS),
    "gpu": {**_TIER_DEFAULTS, "min_backedge_dispatches": 12,
            "min_trace_fusion": 128, "min_fori_execd": 4096},
    "tpu": {**_TIER_DEFAULTS, "min_backedge_dispatches": 12,
            "min_trace_fusion": 128, "min_fori_execd": 4096},
}


def register_backend_table(kind: str, **thresholds: int | None) -> None:
    """Install a (typically calibration-fitted) threshold table for one
    backend kind (``"cpu"``/``"gpu"``/``"tpu"``).  Unnamed thresholds
    keep the module defaults.  Subsequent
    :meth:`TierPolicy.for_backend`/:func:`default_policy_for_device`
    calls see the new table; already-constructed policies are unchanged
    (instances are immutable)."""
    unknown = set(thresholds) - set(_TIER_DEFAULTS)
    if unknown:
        raise ValueError(
            f"unknown TierPolicy thresholds {sorted(unknown)}; "
            f"known: {sorted(_TIER_DEFAULTS)}")
    _TIER_TABLES[kind] = {**_TIER_DEFAULTS, **thresholds}


def tier_policy_for_backend(kind: str) -> TierPolicy:
    """The :class:`TierPolicy` for a backend kind, from the registered
    (seeded or calibrated) table; unknown kinds fall back to the CPU
    defaults."""
    table = _TIER_TABLES.get(kind)
    if table is None:
        return DEFAULT_TIER_POLICY
    overrides = {k: v for k, v in table.items() if v != _TIER_DEFAULTS[k]}
    return TierPolicy(**overrides) if overrides else DEFAULT_TIER_POLICY


def default_policy_for_device(device) -> TierPolicy:
    """Policy for a concrete jax device (``None`` -> the default
    policy, so unpinned schedulers never touch device state)."""
    if device is None:
        return DEFAULT_TIER_POLICY
    return tier_policy_for_backend(device.platform)


class _PathRecorder:
    """Online fold of the executed path into a superblock schedule.

    Every executed pc is appended to the open schedule; at each LOOP
    back-edge the just-completed iteration is compared against the
    previous one (or an already-open repeat node) and folded when equal.
    A first iteration entered mid-body simply fails the comparison and
    stays inline — a free peel.  All mutations preserve the invariant
    that the schedule flattens to the exact executed path, so bookkeeping
    confusion (unbalanced INIT/LOOP, JMP out of a loop) can only cost
    compression, never correctness.  Recording bails out (``schedule()``
    returns None) when the retained size exceeds the trace budget or a
    LOOP fires with no open loop instance.
    """

    def __init__(self, cap: int):
        self._cap = cap
        self._items: list = []
        self._insts = 0             # instruction slots currently retained
        self._dead = False
        self._loops: list[dict] = []   # parallels the simulator loop stack

    def _bail(self) -> None:
        self._dead = True
        self._items = []
        self._loops = []

    def step(self, pc: int) -> None:
        if self._dead:
            return
        self._items.append(pc)
        self._insts += 1
        if self._insts > 2 * self._cap:
            self._bail()

    def on_init(self) -> None:
        if self._dead:
            return
        self._loops.append({"iter_start": len(self._items), "cand": None,
                            "cand_start": 0, "rep_idx": None})

    def on_loop(self, taken: bool) -> None:
        """Called after the LOOP pc itself was recorded via ``step``."""
        if self._dead:
            return
        if not self._loops:
            self._bail()                 # unbalanced LOOP: give up folding
            return
        inst = self._loops[-1]
        cur = self._items
        seg = tuple(cur[inst["iter_start"]:])
        ri = inst["rep_idx"]
        if ri is not None and cur[ri][1] == seg:
            cur[ri] = ("rep", seg, cur[ri][2] + 1)
            del cur[inst["iter_start"]:]
            self._insts -= _sched_insts(seg)
        elif inst["cand"] == seg:
            del cur[inst["cand_start"]:]
            cur.append(("rep", seg, 2))
            self._insts -= _sched_insts(seg)
            inst["rep_idx"] = len(cur) - 1
            inst["cand"] = None
            inst["iter_start"] = len(cur)
        else:
            inst["cand"] = seg
            inst["cand_start"] = inst["iter_start"]
            inst["rep_idx"] = None
            inst["iter_start"] = len(cur)
        if not taken:
            self._loops.pop()

    def schedule(self) -> tuple | None:
        return None if self._dead else tuple(self._items)


# ---------------------------------------------------------------------------
# Static path simulation: sequencer + cycles + hazard checker, on the host
# ---------------------------------------------------------------------------

class _SimResult(NamedTuple):
    steps: int
    cycles: int
    hazard: np.ndarray          # (R+2, 4) int32 — final checker rows
    violations: int
    pc: int                     # final PC
    halted: bool
    lctr: np.ndarray            # (LD,) int32 — final loop-counter stack
    lsp: int
    cstack: np.ndarray          # (CD,) int32 — final call stack
    csp: int
    stat_cycles: np.ndarray     # (NUM_OP_CLASSES,) int32
    stat_instrs: np.ndarray
    dispatches: int             # block-driver switch dispatches on this path
    schedule: tuple | None      # folded superblock schedule (None: too big)
    # event counters (python ints — unbounded, never wrapped):
    backedges: int = 0          # taken LOOP back-edges on the path
    lane_offered: int = 0       # vector retires x runtime thread count
    lane_active: int = 0        # of which the TSC mask left on


def _simulate(cfg: EGPUConfig, packed: np.ndarray, prog_len: int,
              threads: int, validate: bool, *,
              block_starts: frozenset = frozenset(),
              n_real: int | None = None) -> _SimResult:
    """Walk the (fully static) execution path once, mirroring the
    interpreter's sequencer, cycle accounting and hazard checker
    bit-for-bit, while folding the path into a superblock schedule and
    counting the block-driver dispatches it would cost.  Raises
    :class:`BlockCompileError` if the program does not halt before
    ``cfg.max_steps`` (the interpreter would then stop mid-block, which
    neither compiled driver can reproduce)."""
    t = tables_np(cfg)
    R = cfg.regs_per_thread
    LD, CD = cfg.max_loop_depth, cfg.max_call_depth
    wfs_by_depth = _wfs_table(cfg, threads)
    hz = machine_mod.hazard_init(R).astype(np.int64)
    violations = 0
    lctr = [0] * LD
    cstack = [0] * CD
    lsp = csp = 0
    pc = cycles = steps = 0
    halted = False
    cap = min(cfg.max_steps, _SIM_CAP)
    L = packed.shape[0]
    n_real = prog_len if n_real is None else n_real
    stat_c = [0] * isa.NUM_OP_CLASSES
    stat_i = [0] * isa.NUM_OP_CLASSES
    dispatches = 0
    backedges = 0
    lane_offered = lane_active = 0
    act_lut: dict[int, int] = {}    # tsc code -> active lanes (16 codes)
    rec = _PathRecorder(_MAX_TRACE)

    while (not halted) and steps < cfg.max_steps and 0 <= pc < prog_len:
        if steps >= cap:
            raise BlockCompileError(
                f"program did not halt within {cap} steps")
        op, typ, rd, ra, rb, imm, tsc = (int(v) for v in packed[min(pc, L - 1)])
        width_code = (tsc >> 2) & 3
        depth_code = tsc & 3
        wfs = wfs_by_depth[depth_code]
        per_wf = int(t[op, _TC_PER_WF0 + width_code])
        scalar = bool(t[op, _TC_SCALAR])
        writes_rd = bool(t[op, _TC_WRITES_RD])
        issue = 1 if scalar else per_wf * wfs
        rec.step(pc)
        if pc >= n_real or pc in block_starts:
            dispatches += 1
        stat_c[int(t[op, _TC_CLS])] += issue
        stat_i[int(t[op, _TC_CLS])] += 1
        if not scalar:
            act = act_lut.get(tsc)
            if act is None:
                act = act_lut[tsc] = int(
                    _tsc_static(cfg, tsc, threads)[1].sum())
            lane_offered += threads
            lane_active += act

        if validate:
            rows = [hz[_gidx(ra, R + 2)], hz[_gidx(rb, R + 2)],
                    hz[_gidx(rd, R + 2)], hz[R], hz[R + 1]]
            flags = [bool(t[op, _TC_READS_RA]), bool(t[op, _TC_READS_RB]),
                     bool(t[op, _TC_READS_RD]), op == Op.LOD,
                     cfg.has_predicates and not scalar]
            need = -(1 << 30)
            for (p_start, p_per_wf, p_wfs, p_lat), fl in zip(rows, flags):
                if not fl:
                    continue
                k = min(int(p_wfs), wfs) - 1 if p_per_wf > per_wf else 0
                cons = int(p_start) + int(p_per_wf) * (k + 1) - 1 \
                    + int(p_lat) - per_wf * k
                need = max(need, cons)
            if ((not scalar) or op == Op.LOD) and need > cycles:
                violations += 1
            new_row = (cycles, per_wf, wfs, int(t[op, _TC_LAT]))
            if writes_rd and 0 <= rd < R + 2:
                hz[rd] = new_row
            if op == Op.STO:
                hz[R] = new_row
            if t[op, _TC_WRITES_PRED]:
                hz[R + 1] = new_row

        if op == Op.JMP:
            pc = imm
        elif op == Op.JSR:
            if 0 <= csp < CD:
                cstack[csp] = pc + 1
            csp += 1
            pc = imm
        elif op == Op.RTS:
            pc = cstack[_gidx(csp - 1, CD)]
            csp -= 1
        elif op == Op.LOOP:
            ltop = lctr[_gidx(lsp - 1, LD)]
            if 0 <= lsp - 1 < LD:
                lctr[lsp - 1] = ltop - 1
            if ltop > 0:
                pc = imm
                backedges += 1
            else:
                lsp -= 1
                pc += 1
            rec.on_loop(ltop > 0)
        elif op == Op.INIT:
            if 0 <= lsp < LD:
                lctr[lsp] = imm
            lsp += 1
            pc += 1
            rec.on_init()
        else:
            if op == Op.STOP:
                halted = True
            pc += 1
        cycles = _i32wrap(cycles + issue)
        steps += 1

    if (not halted) and steps >= cfg.max_steps and 0 <= pc < prog_len:
        raise BlockCompileError(
            f"program did not halt within max_steps={cfg.max_steps}")
    return _SimResult(
        steps=steps, cycles=cycles, hazard=hz.astype(np.int32),
        violations=violations, pc=_i32wrap(pc), halted=halted,
        lctr=np.asarray([_i32wrap(v) for v in lctr], np.int32),
        lsp=_i32wrap(lsp),
        cstack=np.asarray([_i32wrap(v) for v in cstack], np.int32),
        csp=_i32wrap(csp),
        stat_cycles=np.asarray([_i32wrap(v) for v in stat_c], np.int32),
        stat_instrs=np.asarray([_i32wrap(v) for v in stat_i], np.int32),
        dispatches=dispatches, schedule=rec.schedule(),
        backedges=backedges, lane_offered=lane_offered,
        lane_active=lane_active)


# ---------------------------------------------------------------------------
# The dynamic state, split by batching behaviour
# ---------------------------------------------------------------------------

class _Data(NamedTuple):
    """Per-job state (batched under the fleet's compiled tier)."""

    regs: Any                  # (..., T, R) uint32
    shared: Any                # (..., S) uint32
    pstack: Any                # (..., T, D) bool
    tdx_dim: Any               # (...,) int32


class _Seq(NamedTuple):
    """Data-independent state — identical for every core running the
    program, so it stays unbatched even in a batched run."""

    pc: Any                    # () int32
    cycles: Any                # () int32
    steps: Any                 # () int32
    halted: Any                # () bool
    pdepth: Any                # (T,) int32
    lctr: Any                  # (LD,) int32
    lsp: Any                   # () int32
    cstack: Any                # (CD,) int32
    csp: Any                   # () int32
    stat_cycles: Any           # (NUM_OP_CLASSES,) int32
    stat_instrs: Any           # (NUM_OP_CLASSES,) int32


# ---------------------------------------------------------------------------
# The compiler
# ---------------------------------------------------------------------------

class CompiledProgram:
    """One program, compiled for one (config, thread-count) pair.

    ``run()`` executes a single core; ``run_batch()`` executes N cores in
    lock-step over batched data (same blocks, different data) — the
    fleet's compiled tier.  Fresh states only: the static path (and the
    baked hazard results) assume execution starts at PC 0 with empty
    stacks and zeroed registers, exactly like :func:`init_state`.

    ``mode`` selects the tier: ``"auto"`` (default) asks the
    :class:`TierPolicy` cost model to pick the cheaper tier for this
    path at this batch width (``batch_hint``); ``"superblock"`` requires
    the superblock runner (raising :class:`BlockCompileError` when the
    folded path is over the trace budget); ``"blocks"`` forces the
    basic-block driver.  The tier actually chosen is exposed as
    ``self.mode`` (the policy's inputs as ``self.tier_features``), and
    ``self.switch_dispatches`` counts the block-driver ``lax.switch``
    dispatches the program pays on this tier (0 on the superblock tier —
    that is the point).

    The kernels trace the thread axis at the program's runtime thread
    count (``self.kernel_threads``), not ``cfg.max_threads``: threads
    past it never act, so they are not traced.  The full runners pad
    registers and predicate state back to ``max_threads`` with the
    zeros the interpreter leaves there.
    """

    def __init__(self, image: ProgramImage, threads: int, *,
                 validate: bool = True, mode: str = "auto",
                 policy: TierPolicy | None = None, batch_hint: int = 1):
        cfg = image.cfg
        if mode not in ("auto", "superblock", "blocks"):
            raise ValueError(f"unknown compile mode {mode!r}")
        if threads < 1 or threads > cfg.max_threads \
                or threads % cfg.num_sps:
            raise ValueError(
                f"runtime threads {threads} invalid for max "
                f"{cfg.max_threads}")
        self.cfg = cfg
        self.image = image
        self.threads = threads
        self.validate = validate
        self.packed, self.prog_len = pad_image(image)
        self.n = image.n
        self.blocks = _decompose(self.packed, self.n)
        self.sim = _simulate(
            cfg, self.packed, self.prog_len, threads, validate,
            block_starts=frozenset(s for s, _ in self.blocks),
            n_real=self.n)
        # NOT gated on cfg.has_predicates: the interpreter emulates a
        # one-level stack even for predicate-less configs (D clamps to 1)
        self.has_preds = any(
            int(o) in isa.PRED_WRITE_OPS for o in image.op)
        # pc -> block index; the padded STOP tail shares one dynamic block
        p2b = np.full((self.prog_len,), len(self.blocks), np.int32)
        for bi, (s, e) in enumerate(self.blocks):
            p2b[s:e] = bi
        self._pc2block = p2b
        self._tables = tables_np(cfg)
        #: the thread axis the kernels trace (see the class docstring)
        self.kernel_threads = threads
        self._tid = np.arange(threads, dtype=np.int32)
        self._tid0 = self._tid == 0
        self.schedule = self.sim.schedule
        self.policy = DEFAULT_TIER_POLICY if policy is None else policy
        self.batch_hint = batch_hint
        self.tier_features = self.policy.features(
            self.sim, cfg_facts=cfg_mod.summary(self.packed, self.n))
        eligible = self.tier_features["eligible"]
        if mode == "superblock" and not eligible:
            cap = self.policy.table["max_trace_cost"]
            cap = _MAX_TRACE if cap is None else cap
            cost = self.tier_features["trace_cost"]
            raise BlockCompileError(
                "program is not superblock-eligible ("
                + ("the path did not fold to a schedule"
                   if cost is None else
                   f"trace cost {cost} exceeds the {cap}-instruction "
                   f"budget") + ")")
        if mode == "auto":
            self.mode = self.policy.choose(
                self.sim, batch=batch_hint, features=self.tier_features)
        else:
            self.mode = mode
        if self.mode == "superblock":
            self.switch_dispatches = 0
            self._run_jit = self._build_super_runner()
        else:
            self.switch_dispatches = self.sim.dispatches
            self._run_jit = self._build_runner()
        self._light_jit = None           # built lazily on first use
        #: AOT-compiled light executables keyed by input shapes — split
        #: so the fleet can attribute XLA compile time separately from
        #: dispatch time (``FleetStats.compile_s`` vs ``wall_s``)
        self._light_execs: dict = {}
        self._counters = None            # EventCounters, built lazily

    # ---------------------------------------------------- event counters
    def event_counters(self):
        """This program's per-core :class:`~repro.obs.EventCounters`,
        baked from the path simulation (exact, free at runtime).  The
        per-class retire/issue counts are bit-identical to the
        interpreter's ``stat_instrs`` / ``stat_cycles``; the plan-shape
        counters (fori vs unrolled repeats) describe the tier this
        compile actually runs."""
        if self._counters is None:
            from ..obs.counters import EventCounters
            sim = self.sim
            f = self.tier_features
            if self.mode == "superblock" and self.schedule is not None:
                rep_trips = _sched_rep_trips(self.schedule)
                rep_execd = _sched_rep_execd(self.schedule)
                fori_trips = sum(f["fori_trips"])
                plan = dict(
                    fori_reps=f["fori_reps"],
                    unrolled_reps=f["unrolled_reps"],
                    fori_trips=fori_trips,
                    unrolled_trips=rep_trips - fori_trips,
                    fori_instrs=f["fori_execd"],
                    unrolled_instrs=rep_execd - f["fori_execd"])
            else:
                plan = dict(fori_reps=0, unrolled_reps=0, fori_trips=0,
                            unrolled_trips=0, fori_instrs=0,
                            unrolled_instrs=0)
            nopc = int(isa.OpClass.NOPC)
            self._counters = EventCounters(
                instrs=int(sim.steps), cycles=int(sim.cycles),
                instrs_by_class=tuple(int(v) for v in sim.stat_instrs),
                cycles_by_class=tuple(int(v) for v in sim.stat_cycles),
                loop_backedges=int(sim.backedges),
                block_dispatches=int(self.switch_dispatches),
                hazard_nop_instrs=int(sim.stat_instrs[nopc]),
                hazard_nop_cycles=int(sim.stat_cycles[nopc]),
                hazard_violations=int(sim.violations),
                lane_steps_offered=int(sim.lane_offered),
                lane_steps_active=int(sim.lane_active), **plan)
        return self._counters

    # ----------------------------------------------------- shared data op
    def _apply_row(self, row, regs, shared, pstack, pdepth, pok, tdx_dim):
        """One instruction's *data* semantics — registers, shared memory,
        predicate state — with every decoded field a Python constant.
        Sequencer ops (JMP/JSR/RTS/LOOP/INIT/STOP/NOP) are data no-ops:
        their effects are either handled by the block terminator (basic
        blocks) or baked statically (superblocks).  ``pok`` is the cached
        predicate mask, invalidated by predicate writers; shared between
        both compiled tiers so their semantics cannot drift."""
        cfg = self.cfg
        R, S = cfg.regs_per_thread, cfg.shared_words
        D = max(1, cfg.predicate_levels)
        t = self._tables
        (op, typ, rd, ra, rb, imm, tsc) = row
        o = Op(op)
        if o in (Op.JMP, Op.JSR, Op.RTS, Op.LOOP, Op.INIT, Op.STOP,
                 Op.NOP):
            return regs, shared, pstack, pdepth, pok

        _, tsc_mask = _tsc_static(cfg, tsc, self.threads)
        if self.has_preds:
            if pok is None:
                pok = semantics.pred_ok(pstack, pdepth, D)
            mask = tsc_mask & pok
        else:
            mask = tsc_mask
        ra_r, rb_r, rd_r = _gidx(ra, R), _gidx(rb, R), _gidx(rd, R)
        env = semantics.OpEnv(
            cfg=cfg, rav=regs[..., ra_r], rbv=regs[..., rb_r],
            rdv=regs[..., rd_r], signed=typ == Typ.I32, imm=imm,
            mask=mask, tid=self._tid, shared=shared, tdx_dim=tdx_dim)
        spec = semantics.build_spec(env)

        if o in isa.IF_OPS:
            cond = spec[op][1]()
            pstack, pdepth = semantics.pred_push(
                pstack, pdepth, cond, tsc_mask, D)
            pok = None
        elif o == Op.ELSE:
            pstack = semantics.pred_else(pstack, pdepth, tsc_mask, D)
            pok = None
        elif o == Op.ENDIF:
            pdepth = semantics.pred_pop(pdepth, tsc_mask)
            pok = None
        elif o == Op.STO:
            addr = env.addr
            sto_ok = mask & (addr >= 0) & (addr < S)
            sidx = jnp.where(sto_ok, addr, S)
            shared = semantics.store(shared, sidx, env.rdv)
        elif t[op, _TC_WRITES_RD]:
            value = spec[op][0]().astype(_U32)
            wmask = self._tid0 if o in (Op.DOT, Op.SUM) else mask
            rd_w = min(max(rd, 0), R - 1)
            col = jnp.where(wmask, value, regs[..., rd_w])
            regs = regs.at[..., rd_w].set(col)
        return regs, shared, pstack, pdepth, pok

    # ------------------------------------------------------------- blocks
    def _block_fn(self, start: int, end: int):
        """Trace ``[start, end)`` as one straight-line computation."""
        cfg = self.cfg
        t = self._tables
        rows = [tuple(int(v) for v in self.packed[i])
                for i in range(start, end)]
        term_op = rows[-1][_PF_OP] if rows[-1][_PF_OP] in _SEQ_TERM else None

        # per-block constants: cycles / instruction-mix increments
        block_cycles = 0
        stat_c = np.zeros((isa.NUM_OP_CLASSES,), np.int32)
        stat_i = np.zeros((isa.NUM_OP_CLASSES,), np.int32)
        for (op, typ, rd, ra, rb, imm, tsc) in rows:
            wfs, _ = _tsc_static(cfg, tsc, self.threads)
            width_code = (tsc >> 2) & 3
            per_wf = int(t[op, _TC_PER_WF0 + width_code])
            issue = 1 if t[op, _TC_SCALAR] else per_wf * wfs
            block_cycles += issue
            stat_c[t[op, _TC_CLS]] += issue
            stat_i[t[op, _TC_CLS]] += 1

        def fn(data: _Data, seq: _Seq):
            regs, shared, pstack = data.regs, data.shared, data.pstack
            pdepth = seq.pdepth
            lctr, lsp = seq.lctr, seq.lsp
            cstack, csp = seq.cstack, seq.csp
            halted = seq.halted
            pc_next = jnp.int32(end)        # fall-through default
            pok = None                      # cached predicate mask

            for row in rows:
                if row[_PF_OP] == Op.INIT:
                    lctr, lsp = semantics.loop_init(lctr, lsp,
                                                    row[_PF_IMM])
                    continue
                regs, shared, pstack, pdepth, pok = self._apply_row(
                    row, regs, shared, pstack, pdepth, pok, data.tdx_dim)

            # --- terminator --------------------------------------------
            imm = rows[-1][_PF_IMM]
            end_pc = end
            if term_op == Op.JMP:
                pc_next = jnp.int32(imm)
            elif term_op == Op.JSR:
                cstack, csp = semantics.call_push(
                    cstack, csp, jnp.int32(end_pc))
                pc_next = jnp.int32(imm)
            elif term_op == Op.RTS:
                pc_next = semantics.call_top(cstack, csp)
                csp = csp - 1
            elif term_op == Op.LOOP:
                lctr, taken, lsp_pop = semantics.loop_step(lctr, lsp)
                lsp = jnp.where(taken, lsp, lsp_pop)
                pc_next = jnp.where(taken, jnp.int32(imm),
                                    jnp.int32(end_pc))
            elif term_op == Op.STOP:
                halted = jnp.bool_(True)
                pc_next = jnp.int32(end_pc)

            seq2 = _Seq(
                pc=pc_next,
                cycles=seq.cycles + jnp.int32(_i32wrap(block_cycles)),
                steps=seq.steps + jnp.int32(len(rows)),
                halted=halted, pdepth=pdepth,
                lctr=lctr, lsp=jnp.asarray(lsp, _I32),
                cstack=cstack, csp=jnp.asarray(csp, _I32),
                stat_cycles=seq.stat_cycles + stat_c if self.validate
                else seq.stat_cycles,
                stat_instrs=seq.stat_instrs + stat_i if self.validate
                else seq.stat_instrs)
            return _Data(regs=regs, shared=shared, pstack=pstack,
                         tdx_dim=data.tdx_dim), seq2

        return fn

    def _pad_stop_fn(self):
        """One shared block for the padded STOP tail ``[n, prog_len)`` —
        the only block whose PC is dynamic."""
        stat_c = np.zeros((isa.NUM_OP_CLASSES,), np.int32)
        stat_i = np.zeros((isa.NUM_OP_CLASSES,), np.int32)
        stat_c[isa.OpClass.BRANCH] = 1
        stat_i[isa.OpClass.BRANCH] = 1

        def fn(data: _Data, seq: _Seq):
            return data, seq._replace(
                pc=seq.pc + 1, cycles=seq.cycles + 1, steps=seq.steps + 1,
                halted=jnp.bool_(True),
                stat_cycles=seq.stat_cycles + stat_c if self.validate
                else seq.stat_cycles,
                stat_instrs=seq.stat_instrs + stat_i if self.validate
                else seq.stat_instrs)

        return fn

    # --------------------------------------------------------- superblock
    def _apply_schedule(self, items, state, tdx_dim):
        """Trace a schedule over the dynamic state — the superblock
        runner's core, shared by the full and light runners.

        Straight-line schedule items trace inline; a repeat node either
        unrolls fully (small executed size — maximal fusion across the
        back-edge) or becomes a ``lax.fori_loop`` whose body is the loop
        trace fused once (the unroll policy ``_plan_stats`` mirrors).
        """
        regs, shared, pstack, pdepth = state
        pok = None
        for it in items:
            if isinstance(it, (int, np.integer)):
                row = tuple(int(v) for v in self.packed[it])
                regs, shared, pstack, pdepth, pok = self._apply_row(
                    row, regs, shared, pstack, pdepth, pok, tdx_dim)
                continue
            _, body, count = it
            st = (regs, shared, pstack, pdepth)
            if count * _sched_execd(body) <= _UNROLL_FULL:
                for _ in range(count):
                    st = self._apply_schedule(body, st, tdx_dim)
            else:
                st = lax.fori_loop(
                    0, count,
                    lambda _, s, _b=body: self._apply_schedule(
                        _b, s, tdx_dim), st)
            regs, shared, pstack, pdepth = st
            pok = None                 # pstack/pdepth may have moved
        return regs, shared, pstack, pdepth

    def _super_final(self, shared, tdx_dim):
        """Traced: fresh state -> final dynamic leaves, per the folded
        static path."""
        cfg = self.cfg
        T, R = self.kernel_threads, cfg.regs_per_thread
        D = max(1, cfg.predicate_levels)
        batch = shared.shape[:-1]              # () or (B,)
        return self._apply_schedule(self.schedule, (
            jnp.zeros(batch + (T, R), jnp.uint32), shared,
            jnp.zeros(batch + (T, D), jnp.bool_),
            jnp.zeros((T,), _I32)), tdx_dim)

    def _build_super_runner(self):
        """The superblock driver: the folded static path, traced as one
        computation with no ``while_loop`` and no ``switch``.

        Every data-independent leaf of the final :class:`MachineState`
        (PC, cycles, steps, loop/call stacks, stats, hazards) is baked
        from the host-side simulation; only registers, shared memory and
        the predicate state flow through the trace.  ``pdepth`` is
        data-independent too but rides along dynamically so unbalanced
        IF/ENDIF inside a folded loop body stays exact across
        iterations.
        """
        sim = self.sim
        threads = self.threads
        zeros = np.zeros((isa.NUM_OP_CLASSES,), np.int32)
        stat_c = sim.stat_cycles if self.validate else zeros
        stat_i = sim.stat_instrs if self.validate else zeros

        def run(shared, tdx_dim):
            batch = shared.shape[:-1]          # () or (B,)
            regs, shared_f, pstack, pdepth = self._super_final(
                shared, tdx_dim)

            def b(x):   # broadcast a baked leaf over the batch axis
                x = jnp.asarray(x)
                return jnp.broadcast_to(x, batch + x.shape)

            pad = self._pad_threads
            return MachineState(
                regs=pad(regs, -2), shared=shared_f, pstack=pad(pstack, -2),
                pdepth=b(pad(pdepth, -1)), lctr=b(jnp.asarray(sim.lctr)),
                lsp=b(jnp.int32(sim.lsp)),
                cstack=b(jnp.asarray(sim.cstack)),
                csp=b(jnp.int32(sim.csp)), pc=b(jnp.int32(sim.pc)),
                cycles=b(jnp.int32(sim.cycles)),
                steps=b(jnp.int32(sim.steps)),
                halted=b(jnp.bool_(sim.halted)),
                threads_active=b(jnp.int32(threads)), tdx_dim=tdx_dim,
                stat_cycles=b(jnp.asarray(stat_c)),
                stat_instrs=b(jnp.asarray(stat_i)),
                hazard=b(jnp.asarray(sim.hazard)),
                hazard_violations=b(jnp.int32(sim.violations)))

        return jax.jit(self._named(run, "superblock"), donate_argnums=(0,))

    def _pad_threads(self, x, axis: int):
        """``x``'s thread ``axis`` zero-padded from the kernel's width to
        ``cfg.max_threads``: what the interpreter leaves in threads at or
        past the runtime count, whose every register, predicate and
        store write is masked off (DOT/SUM write only thread 0)."""
        extra = self.cfg.max_threads - x.shape[axis]
        if not extra:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, extra)
        return jnp.pad(x, widths)

    def _named(self, fn, tier: str):
        """``fn`` named as this program's ``tier`` kernel."""
        return name_kernel(fn, tier, program_digest(self.image))

    # ------------------------------------------------------------- driver
    def _blocks_final(self, shared, tdx_dim):
        """Traced: fresh state -> final ``(_Data, _Seq)`` through the
        ``while_loop`` + ``switch`` block driver — shared by the full
        and light runners."""
        fns = [self._block_fn(s, e) for s, e in self.blocks]
        fns.append(self._pad_stop_fn())
        pc2block = jnp.asarray(self._pc2block)
        cfg = self.cfg
        T, R = self.kernel_threads, cfg.regs_per_thread
        D = max(1, cfg.predicate_levels)
        max_steps = cfg.max_steps
        prog_len = self.prog_len

        def cond(carry):
            _, seq = carry
            return (~seq.halted) & (seq.steps < max_steps) & \
                (seq.pc >= 0) & (seq.pc < prog_len)

        def body(carry):
            data, seq = carry
            return lax.switch(pc2block[seq.pc], fns, data, seq)

        batch = shared.shape[:-1]              # () or (B,)
        z = jnp.int32(0)
        data = _Data(
            regs=jnp.zeros(batch + (T, R), jnp.uint32), shared=shared,
            pstack=jnp.zeros(batch + (T, D), jnp.bool_),
            tdx_dim=tdx_dim)
        seq = _Seq(
            pc=z, cycles=z, steps=z, halted=jnp.bool_(False),
            pdepth=jnp.zeros((T,), _I32),
            lctr=jnp.zeros((cfg.max_loop_depth,), _I32), lsp=z,
            cstack=jnp.zeros((cfg.max_call_depth,), _I32), csp=z,
            stat_cycles=jnp.zeros((isa.NUM_OP_CLASSES,), _I32),
            stat_instrs=jnp.zeros((isa.NUM_OP_CLASSES,), _I32))
        return lax.while_loop(cond, body, (data, seq))

    def _build_runner(self):
        hazard = self.sim.hazard
        violations = self.sim.violations
        threads = self.threads

        # One dispatch per run: the fresh registers/predicate stacks and
        # the fresh sequencer state are constants inside the jit, and the
        # final MachineState (including the statically baked hazard rows)
        # is assembled inside it too.  The shared-memory image is donated.
        def run(shared, tdx_dim):
            batch = shared.shape[:-1]          # () or (B,)
            d, s = self._blocks_final(shared, tdx_dim)

            def b(x):   # broadcast a seq leaf over the batch axis
                x = jnp.asarray(x)
                return jnp.broadcast_to(x, batch + x.shape)

            pad = self._pad_threads
            return MachineState(
                regs=pad(d.regs, -2), shared=d.shared,
                pstack=pad(d.pstack, -2),
                pdepth=b(pad(s.pdepth, -1)), lctr=b(s.lctr), lsp=b(s.lsp),
                cstack=b(s.cstack), csp=b(s.csp), pc=b(s.pc),
                cycles=b(s.cycles), steps=b(s.steps), halted=b(s.halted),
                threads_active=b(jnp.int32(threads)),
                tdx_dim=d.tdx_dim,
                stat_cycles=b(s.stat_cycles), stat_instrs=b(s.stat_instrs),
                hazard=b(jnp.asarray(hazard)),
                hazard_violations=b(jnp.int32(violations)))

        return jax.jit(self._named(run, "blocks"), donate_argnums=(0,))

    def light_fn(self):
        """The *unjitted* light-path function ``(shared, tdx_dim) ->
        (shared, cycles, halted)`` — for callers that wrap their own
        transform around it (the sharded fleet ``shard_map``s it over
        the 1-D job mesh; every row is an independent core, so sharding
        the leading batch axis is bit-identical to the single-device
        call)."""
        sim = self.sim

        if self.mode == "superblock":
            def run(shared, tdx_dim):
                batch = shared.shape[:-1]
                _, shared_f, _, _ = self._super_final(shared, tdx_dim)
                return (shared_f,
                        jnp.broadcast_to(jnp.int32(sim.cycles), batch),
                        jnp.broadcast_to(jnp.bool_(sim.halted), batch))
            return self._named(run, self.mode)

        def run(shared, tdx_dim):
            batch = shared.shape[:-1]
            d, s = self._blocks_final(shared, tdx_dim)
            return (d.shared,
                    jnp.broadcast_to(s.cycles, batch),
                    jnp.broadcast_to(s.halted, batch))
        return self._named(run, self.mode)

    def _build_light_runner(self):
        """The light path: only ``(shared, cycles, halted)`` leave the
        device.  No input donation — the fleet's residency cache replays
        the same device-resident shared image across drains, which a
        donated (consumed) buffer would forbid.  On the superblock tier
        cycles/halted are baked constants; on the blocks tier they fall
        out of the driver loop."""
        return jax.jit(self.light_fn())

    # ------------------------------------------------------------- public
    def run(self, *, shared_init=None, tdx_dim: int = 16) -> MachineState:
        """Execute one core; bit-identical to ``run_program``."""
        S = self.cfg.shared_words
        shared = np.zeros((S,), np.uint32)
        if shared_init is not None:
            buf = machine_mod.pack_shared_init(shared_init, S)
            shared[:buf.size] = buf
        with obs_trace.span("run_compiled", tier=self.mode):
            out = self._run_jit(jnp.asarray(shared), jnp.int32(tdx_dim))
            out.cycles.block_until_ready()
        return out

    def run_batch(self, shared_inits: list, tdx_dims) -> MachineState:
        """Execute N same-program cores in lock-step over batched data;
        returns the batched final state (slice jobs out along axis 0)."""
        S = self.cfg.shared_words
        n = len(shared_inits)
        shared = np.zeros((n, S), np.uint32)
        for i, s0 in enumerate(shared_inits):
            if s0 is None:
                continue
            buf = machine_mod.pack_shared_init(s0, S)
            shared[i, :buf.size] = buf
        with obs_trace.span("run_compiled", tier=self.mode,
                            batch=len(shared_inits)):
            out = self._run_jit(jnp.asarray(shared),
                                jnp.asarray(tdx_dims, _I32))
            out.cycles.block_until_ready()
        return out

    # -------------------------------------------------------- light path
    def light_compile(self, shared, tdx_dim, device=None) -> float:
        """Ensure the light-path executable for these input shapes is
        built and XLA-compiled ahead of time; returns the host seconds
        that took (0.0 when already compiled).  The fleet calls this
        before its timed dispatch so ``FleetStats.compile_s`` carries
        the one-time compile cost instead of ``wall_s``.

        AOT executables are pinned to the devices their inputs were
        lowered on, so ``device`` is part of the cache key: a pinned
        fleet scheduler gets its own entry per device, and ``None``
        (today's unpinned path) keeps the default placement."""
        shared = jnp.asarray(shared, _U32)
        tdx_dim = jnp.asarray(tdx_dim, _I32)
        key = (np.shape(shared), np.shape(tdx_dim), device)
        if key in self._light_execs:
            return 0.0
        if device is not None:
            shared = jax.device_put(shared, device)
            tdx_dim = jax.device_put(tdx_dim, device)
        t0 = time.perf_counter()
        with obs_trace.span("compile", kind="xla_light", tier=self.mode,
                            batch=key[0][:-1]):
            if self._light_jit is None:
                self._light_jit = self._build_light_runner()
            self._light_execs[key] = \
                self._light_jit.lower(shared, tdx_dim).compile()
        return time.perf_counter() - t0

    def run_light_dev(self, shared, tdx_dim, device=None):
        """Raw light entry: device (or host) arrays in — ``(..., S)``
        uint32 shared image, ``(...,)``/scalar int32 TDX — device arrays
        ``(shared, cycles, halted)`` out.  No host sync, no donation:
        the same input buffer can be replayed across calls, which is
        what keeps the fleet's residency cache sound.  Dispatches the
        shape-keyed AOT executable (see :meth:`light_compile`); when
        ``device`` is given inputs are placed there first (a no-op for
        already-resident buffers) and the device-keyed executable runs
        — cross-device replay of a pinned executable is a jax error."""
        shared = jnp.asarray(shared, _U32)
        tdx_dim = jnp.asarray(tdx_dim, _I32)
        if device is not None:
            shared = jax.device_put(shared, device)
            tdx_dim = jax.device_put(tdx_dim, device)
        key = (np.shape(shared), np.shape(tdx_dim), device)
        exe = self._light_execs.get(key)
        if exe is None:
            self.light_compile(shared, tdx_dim, device)
            exe = self._light_execs[key]
        return exe(shared, tdx_dim)

    def run_light(self, *, shared_init=None, tdx_dim: int = 16):
        """Execute one core, returning only ``(shared, cycles, halted)``
        — for callers that never read registers, stacks or stats.  The
        leaves are bit-identical to the same-named :meth:`run` leaves;
        the other 15 ``MachineState`` leaves are never assembled or
        transferred."""
        S = self.cfg.shared_words
        shared = np.zeros((S,), np.uint32)
        if shared_init is not None:
            buf = machine_mod.pack_shared_init(shared_init, S)
            shared[:buf.size] = buf
        sh, cyc, halted = self.run_light_dev(jnp.asarray(shared),
                                             jnp.int32(tdx_dim))
        sh.block_until_ready()
        return sh, int(cyc), bool(halted)

    def run_batch_light(self, shared_inits: list, tdx_dims):
        """Batched light path: N same-program cores in lock-step,
        returning ``(shared (N, S), cycles (N,), halted (N,))`` only."""
        S = self.cfg.shared_words
        n = len(shared_inits)
        shared = np.zeros((n, S), np.uint32)
        for i, s0 in enumerate(shared_inits):
            if s0 is None:
                continue
            buf = machine_mod.pack_shared_init(s0, S)
            shared[i, :buf.size] = buf
        out = self.run_light_dev(jnp.asarray(shared),
                                 jnp.asarray(tdx_dims, _I32))
        out[0].block_until_ready()
        return out


# ---------------------------------------------------------------------------
# Compile cache + convenience drivers
# ---------------------------------------------------------------------------

_CACHE: dict = {}
_CACHE_MAX = 128


def program_key(image: ProgramImage) -> bytes:
    """Content identity of a program (the bit-packed instruction words
    encode every field) — used by the compile cache and the fleet's
    same-program batch grouping."""
    return image.words.tobytes()


def program_digest(image: ProgramImage) -> str:
    """Short content digest of a program: the fleet's ``program``
    metric label and the suffix of its kernels' XLA module names
    (bounded cardinality: one value per distinct program)."""
    return hashlib.blake2b(program_key(image), digest_size=4).hexdigest()


def normalize_threads(image: ProgramImage, threads: int | None) -> int:
    """``None`` means "the count the image was assembled for"; anything
    else must be an explicit valid count.  In particular ``threads=0``
    is rejected rather than silently mapped to the image default (the
    old ``threads or image.threads_active`` idiom did exactly that)."""
    if threads is None:
        return image.threads_active
    threads = int(threads)
    if threads < 1:
        raise ValueError(
            f"invalid runtime thread count {threads}; pass threads=None "
            f"for the image default ({image.threads_active})")
    return threads


def compile_program(image: ProgramImage, threads: int | None = None, *,
                    validate: bool = True, mode: str = "auto",
                    policy: TierPolicy | None = None,
                    batch_hint: int = 1,
                    optimize: bool = False) -> CompiledProgram:
    """Compile ``image`` for a static runtime thread count (default: the
    count it was assembled for).  Compiles are cached on (config,
    program bytes, threads, validate, mode, policy, batch class) with
    LRU eviction — hits move to the back of the queue, so a hot program
    is never evicted to keep a cold (or negative-cached) one.
    Rejections are cached too, so a non-halting program pays its (up to
    ``max_steps``-long) host-side path walk once, not on every fleet
    drain.

    ``mode``: ``"auto"`` asks the :class:`TierPolicy` cost model
    (``policy``, default :data:`DEFAULT_TIER_POLICY`) to pick the
    cheaper tier for this path at ``batch_hint`` lock-step cores;
    ``"superblock"`` and ``"blocks"`` force a tier (the former raising
    :class:`BlockCompileError` when ineligible).  ``batch_hint`` is
    collapsed to the policy's batch classes before keying the cache, so
    fleet drains at different batch sizes share compiles.

    Raises :class:`BlockCompileError` for programs whose static path does
    not halt within ``cfg.max_steps``.

    ``optimize=True`` first runs the verified pre-compile optimizer
    (:func:`repro.analysis.optimizer.optimize_image`, itself cached):
    constant folding + dead-code elimination with hazard NOPs
    re-derived by the scheduler, bit-identical architectural end state
    guaranteed.  The optimized image then keys the compile cache as
    usual (distinct program bytes, distinct entry).
    """
    threads = normalize_threads(image, threads)
    if optimize:
        from ..analysis.optimizer import optimize_image_cached
        image = optimize_image_cached(image, threads).image
    pol = DEFAULT_TIER_POLICY if policy is None else policy
    hint = pol.batch_class(batch_hint) if mode == "auto" else 1
    key = (image.cfg, program_key(image), threads, validate, mode, pol,
           hint)
    hit = _CACHE.pop(key, None)          # pop + reinsert = move-to-end
    with obs_trace.span("compile", cache_hit=hit is not None,
                        mode=mode, threads=threads) as sp:
        if hit is None:
            while len(_CACHE) >= _CACHE_MAX:
                _CACHE.pop(next(iter(_CACHE)))   # oldest entry first (LRU)
            try:
                hit = CompiledProgram(image, threads, validate=validate,
                                      mode=mode, policy=pol,
                                      batch_hint=hint)
            except BlockCompileError as e:
                hit = e                  # negative-cache the rejection
        if sp.active:
            sp.set(program=program_digest(image),
                   tier=getattr(hit, "mode", "rejected"))
    _CACHE[key] = hit
    if isinstance(hit, BlockCompileError):
        raise hit
    return hit


def run_compiled(image: ProgramImage, *, threads: int | None = None,
                 tdx_dim: int = 16, shared_init=None, validate: bool = True,
                 fallback: bool = True, mode: str = "auto",
                 policy: TierPolicy | None = None) -> MachineState:
    """Execute an assembled program through the block compiler.

    Drop-in for ``run_program(image, threads=..., tdx_dim=...,
    shared_init=...)`` — results are bit-identical.  ``fallback=True``
    silently routes programs the compiler rejects (non-halting static
    path, or over-budget traces under ``mode="superblock"``) to the
    interpreter, completing the superblock → basic-block → interpreter
    chain.
    """
    threads = normalize_threads(image, threads)
    try:
        cp = compile_program(image, threads, validate=validate, mode=mode,
                             policy=policy)
    except BlockCompileError:
        if not fallback:
            raise
        from .executor import run_program
        return run_program(image, validate=validate, threads=threads,
                           tdx_dim=tdx_dim, shared_init=shared_init)
    return cp.run(shared_init=shared_init, tdx_dim=tdx_dim)
