"""The eGPU SIMT executor: a jitted ``lax.while_loop`` interpreter.

One ``while_loop`` iteration = one instruction.  All threads execute the
instruction *vectorised* (the hardware issues one 16-lane wavefront per
cycle; we charge cycles through the cost model rather than looping), with
the active-thread mask derived from

  * the instruction's 4-bit thread-space control field (dynamic
    scalability, Table 3),
  * the runtime thread count (static scalability),
  * the per-thread predicate stacks (divergence, Fig. 2).

Cycle accounting matches :mod:`repro.core.cost` exactly, and a built-in
hazard checker counts read-after-write violations (the eGPU has no hazard
hardware; a correct program — i.e. one produced by the assembler's
scheduler — must report zero).

The per-opcode *semantics* (value/condition functions, predicate and
sequencer stack updates) live in :mod:`repro.core.semantics`, shared
with the basic-block compiler (:mod:`repro.core.blockc`) — this module
contributes the per-instruction *dispatch*: gather the instruction from
the program image, select the value through a switch/where-chain, and
apply every architectural update exactly once with mask-gated selects.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import isa, semantics
from .assembler import ProgramImage
from .config import EGPUConfig
from .isa import Op, Typ
from .machine import MachineState, init_state
from ..obs import trace as obs_trace

_I32 = jnp.int32
_U32 = jnp.uint32

# virtual hazard slots
_HZ_MEM = -2
_HZ_PRED = -1

#: the XLA module name of every tier kernel: ``jit_egpu_<tier>``, with
#: ``_<program digest>`` where the kernel runs one program
KERNEL_MODULE_RE = re.compile(
    r"jit_egpu_(superblock|blocks|interp|mega_superblock|mega_blocks)"
    r"(?:_([0-9a-f]{8}))?")


def name_kernel(fn, tier: str, program: str | None = None):
    """Name ``fn`` so that ``jax.jit(fn)`` lowers to the XLA module
    ``jit_egpu_<tier>[_<program>]`` (see :data:`KERNEL_MODULE_RE`): a
    stable name per tier kernel in HLO dumps and profiler traces.
    ``program`` is the program's digest.  Returns ``fn``."""
    name = f"egpu_{tier}" if program is None else f"egpu_{tier}_{program}"
    fn.__name__ = fn.__qualname__ = name
    return fn


# ---------------------------------------------------------------------------
# Constant per-opcode tables (built once per config, baked into the jaxpr).
#
# All per-opcode metadata lives in ONE (NUM_OPCODES, 12) int32 table so the
# step function fetches it with a single dynamic row gather — under the
# vmapped fleet every separate gather is a separate (batched) HLO op, and
# the step is op-dispatch bound on CPU, not FLOP bound.
# ---------------------------------------------------------------------------

# table columns
(_TC_SCALAR, _TC_READS_RA, _TC_READS_RB, _TC_READS_RD, _TC_WRITES_RD,
 _TC_LAT, _TC_CLS, _TC_PER_WF0) = range(8)          # per_wf spans cols 7..10
_TC_WRITES_PRED = 11

# program-image columns (see pad_image)
_PF_OP, _PF_TYP, _PF_RD, _PF_RA, _PF_RB, _PF_IMM, _PF_TSC = range(7)
PROG_FIELDS = ("op", "typ", "rd", "ra", "rb", "imm", "tsc")


def tables_np(cfg: EGPUConfig) -> np.ndarray:
    """The per-opcode metadata table as NumPy (shared with the static
    path simulator in :mod:`repro.core.blockc`)."""
    n = isa.NUM_OPCODES
    t = np.zeros((n, 12), np.int32)
    t[:, _TC_PER_WF0:_TC_PER_WF0 + 4] = 1
    from . import cost as _cost

    for op in Op:
        t[op, _TC_SCALAR] = op in isa.SCALAR_OPS
        t[op, _TC_READS_RA] = op in isa.READS_RA
        t[op, _TC_READS_RB] = op in isa.READS_RB
        t[op, _TC_READS_RD] = op in isa.READS_RD
        t[op, _TC_WRITES_RD] = op in isa.REG_WRITE_OPS
        t[op, _TC_LAT] = _cost.result_latency(op, cfg)
        t[op, _TC_CLS] = isa.OP_CLASS[op]
        t[op, _TC_WRITES_PRED] = op in isa.PRED_WRITE_OPS
        for wc in range(4):
            width = isa.WIDTH_LANES[wc]
            if op == Op.LOD:
                t[op, _TC_PER_WF0 + wc] = -(-width // cfg.cost.sp_read_ports)
            elif op == Op.STO:
                t[op, _TC_PER_WF0 + wc] = -(-width // cfg.write_ports)
    return t


def _tables(cfg: EGPUConfig):
    return jnp.asarray(tables_np(cfg))


def _cdiv(a, b):
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# Step function
# ---------------------------------------------------------------------------

_PAD = 64  # programs are padded to a multiple of this to share compiles


@functools.lru_cache(maxsize=64)
def make_step(cfg: EGPUConfig, prog_len: int,
              ops_subset: frozenset | None = None, *,
              flat_dispatch: bool = False, check_hazards: bool = True,
              collect_stats: bool = True):
    """Build the per-instruction semantics for one eGPU core.

    Returns ``(step, running)``: ``step(state, prog, act=None) ->
    (state, sto_idx, sto_val)`` executes exactly one instruction
    (``prog`` is the packed ``(prog_len, 7)`` image from
    :func:`pad_image`), and ``running(state) -> bool`` is the continue
    predicate.  The split from the ``while_loop`` driver is what lets the
    same semantics power both :func:`run_program` (single core) and the
    vmapped fleet engine (:mod:`repro.fleet.engine`).

    The state update is *flat*: the per-opcode ``lax.switch`` only selects
    the value an instruction produces (a ``(T,)`` vector plus an IF.cc
    condition), and every architectural structure — register file,
    predicate/loop/call stacks, PC — is then updated exactly once with
    mask-gated one-hot selects.  Under ``jax.vmap`` a switch over a
    batched opcode lowers to "execute every branch, select one", and a
    batched scatter is pathologically slow on the CPU backend, so the
    step avoids scatters entirely:

    * small structures (hazard rows, stacks, stat counters) use one-hot
      ``where`` selects, which fuse;
    * the one real scatter — the STO write to shared memory — is
      *deferred*: ``step`` returns ``(state, sto_idx, sto_val)`` and the
      driver applies it (the fleet driver as a single flattened scatter
      for the whole batch, gated on "any core is storing this cycle").

    ``act`` (bool, default True) gates every write, so a halted core
    no-ops without a second freeze pass over the state.

    ``ops_subset`` (a frozenset of opcode ints) specializes the dispatch to
    the instruction working set of the program(s) actually being run —
    opcodes outside the subset map to a dummy branch.  The fleet packs the
    union of its batch's opcodes here, shrinking the vmapped
    all-branches dispatch several-fold.

    ``flat_dispatch`` replaces the ``lax.switch`` with a nested-``where``
    chain: correct in both drivers, but chosen per driver for speed — the
    switch wins single-core (one branch executes), the chain wins vmapped
    (everything fuses into a few kernels instead of per-branch launches).

    ``check_hazards=False`` / ``collect_stats=False`` drop the RAW hazard
    checker / the Fig. 6 instruction-mix counters from the compiled step.
    Neither affects the architectural results (registers, shared memory,
    cycles, PC trace) — the real eGPU has no hazard hardware or counters —
    so throughput-oriented fleet runs can shed their cost.
    """
    T = cfg.max_threads
    R = cfg.regs_per_thread
    S = cfg.shared_words
    D = max(1, cfg.predicate_levels)
    tables = _tables(cfg)
    tid = jnp.arange(T, dtype=_I32)
    lane = tid % cfg.num_sps
    wf = tid // cfg.num_sps
    width_lanes = jnp.asarray(isa.WIDTH_LANES, _I32)

    branch_ops = sorted(ops_subset) if ops_subset is not None \
        else list(range(isa.NUM_OPCODES))
    remap_np = np.full((isa.NUM_OPCODES,), len(branch_ops), np.int32)
    for i, o in enumerate(branch_ops):
        remap_np[o] = i
    remap = jnp.asarray(remap_np)

    def step(st: MachineState, prog, act=None):
        gate = jnp.bool_(True) if act is None else act
        pc = st.pc
        row = prog[pc]                   # one gather for all seven fields
        op = row[_PF_OP]
        typ = row[_PF_TYP]
        rd = row[_PF_RD]
        ra = row[_PF_RA]
        rb = row[_PF_RB]
        imm = row[_PF_IMM]
        tsc = row[_PF_TSC]
        trow = tables[op]                # one gather for all opcode metadata

        width_code = (tsc >> 2) & 3
        depth_code = tsc & 3
        w_rt = _cdiv(st.threads_active, cfg.num_sps)
        wfs = jnp.stack([_I32(1), w_rt, jnp.maximum(1, _cdiv(w_rt, 2)),
                         jnp.maximum(1, _cdiv(w_rt, 4))])[depth_code]
        lanes = width_lanes[width_code]
        per_wf_c = trow[_TC_PER_WF0 + width_code]
        is_scalar = trow[_TC_SCALAR] == 1
        writes_rd = trow[_TC_WRITES_RD] == 1
        issue = jnp.where(is_scalar, _I32(1), per_wf_c * wfs)

        # --- active masks ------------------------------------------------
        tsc_mask = (lane < lanes) & (wf < wfs) & (tid < st.threads_active)
        pred = semantics.pred_ok(st.pstack, st.pdepth, D)
        mask = tsc_mask & pred

        # --- operand reads (one gather) ----------------------------------
        srcs = jnp.stack([ra, rb, rd])
        vals = st.regs[:, srcs]          # (T, 3)
        rav, rbv, rdv = vals[:, 0], vals[:, 1], vals[:, 2]

        # --- hazard checker (RAW), vectorised over the five read slots ---
        hz = st.hazard
        violated = jnp.bool_(False)
        if check_hazards:
            rows = jnp.concatenate([hz[srcs], hz[R:R + 2]])  # ra/rb/rd/mem/pred
            p_start, p_per_wf = rows[:, 0], rows[:, 1]
            p_wfs, p_lat = rows[:, 2], rows[:, 3]
            k_max = jnp.minimum(p_wfs, wfs) - 1
            k = jnp.where(p_per_wf > per_wf_c, k_max, 0)
            cons = p_start + p_per_wf * (k + 1) - 1 + p_lat - per_wf_c * k
            pred_reads = (~is_scalar) if cfg.has_predicates \
                else jnp.bool_(False)
            flags = jnp.stack([trow[_TC_READS_RA] == 1,
                               trow[_TC_READS_RB] == 1,
                               trow[_TC_READS_RD] == 1, op == Op.LOD,
                               pred_reads])
            neg_inf = _I32(-(1 << 30))
            need = jnp.max(jnp.where(flags, cons, neg_inf))
            violated = (~is_scalar | (op == Op.LOD)) & (need > st.cycles)

            # writer bookkeeping: rd / shared-memory / predicate rows as one
            # fused one-hot select (scatters are slow on the vmapped path)
            new_row = jnp.stack([st.cycles, per_wf_c, wfs, trow[_TC_LAT]])
            none = _I32(-9)
            ridx = jnp.arange(R + 2, dtype=_I32)
            hrow = ((ridx == jnp.where(writes_rd, rd, none)) |
                    (ridx == jnp.where(op == Op.STO, _I32(R + 2 + _HZ_MEM),
                                       none)) |
                    (ridx == jnp.where(trow[_TC_WRITES_PRED] == 1,
                                       _I32(R + 2 + _HZ_PRED), none))) & gate
            hz = jnp.where(hrow[:, None], new_row[None, :], hz)

        # --- per-opcode value/condition functions (shared semantics) -----
        env = semantics.OpEnv(cfg=cfg, rav=rav, rbv=rbv, rdv=rdv,
                              signed=typ == Typ.I32, imm=imm, mask=mask,
                              tid=tid, shared=st.shared,
                              tdx_dim=st.tdx_dim)
        spec = semantics.build_spec(env)
        addr = env.addr
        no_cond = jnp.zeros((T,), jnp.bool_)

        if flat_dispatch:
            # nested-where chain over the working set: every elementwise
            # value fuses into a handful of kernels.  A vmapped lax.switch
            # executes all branches anyway (batched opcodes), but as
            # separate computations + select_n — many more kernel launches.
            value, ifcond = rav, no_cond
            for o in branch_ops:
                if spec[o] is None:
                    continue
                vf, cf = spec[o]
                if vf is not None:
                    value = jnp.where(op == o, vf().astype(_U32), value)
                if cf is not None:
                    ifcond = jnp.where(op == o, cf(), ifcond)
        else:
            # real control flow: one branch executes per instruction
            def to_branch(entry):
                if entry is None or entry[0] is None and entry[1] is None:
                    return lambda _: (rav, no_cond)
                vf, cf = entry
                if vf is not None:
                    return lambda _: (vf().astype(_U32), no_cond)
                return lambda _: (rav, cf())

            active = [to_branch(spec[o]) for o in branch_ops] \
                + [to_branch(None)]
            value, ifcond = lax.switch(remap[op], active, _I32(0))

        # --- register writeback (one column update, mask-gated; a batched
        # dynamic_update_slice lowers to an in-place column write) ----------
        ext0 = (op == Op.DOT) | (op == Op.SUM)   # write thread 0 only
        wmask = jnp.where(ext0, tid == 0, mask) & writes_rd & gate
        col = jnp.where(wmask, value, rdv)
        regs = lax.dynamic_update_slice(st.regs, col[:, None],
                                        (jnp.int32(0), rd))

        # --- shared-memory write (STO): deferred to the driver -------------
        sto_ok = (op == Op.STO) & mask & (addr >= 0) & (addr < S) & gate
        sidx = jnp.where(sto_ok, addr, S)   # out-of-range/inactive -> dropped

        # --- predicate stacks ----------------------------------------------
        is_if = ((op >= Op.IF_EQ) & (op <= Op.IF_NZ)) & gate
        is_else = (op == Op.ELSE) & gate
        is_endif = (op == Op.ENDIF) & gate
        ps_push, pd_push = semantics.pred_push(st.pstack, st.pdepth, ifcond,
                                               tsc_mask, D)
        ps_else = semantics.pred_else(st.pstack, st.pdepth, tsc_mask, D)
        pd_pop = semantics.pred_pop(st.pdepth, tsc_mask)
        pstack = jnp.where(is_if, ps_push,
                           jnp.where(is_else, ps_else, st.pstack))
        pdepth = jnp.where(is_if, pd_push,
                           jnp.where(is_endif, pd_pop, st.pdepth))

        # --- sequencer: call/loop stacks and PC ----------------------------
        is_jmp = op == Op.JMP
        is_jsr = (op == Op.JSR) & gate
        is_rts = (op == Op.RTS) & gate
        is_loop = (op == Op.LOOP) & gate
        is_init = (op == Op.INIT) & gate
        is_stop = (op == Op.STOP) & gate

        cstack, csp = semantics.call_push(st.cstack, st.csp, pc + 1,
                                          en=is_jsr)
        csp = csp - jnp.where(is_rts, 1, 0)
        rts_pc = semantics.call_top(st.cstack, st.csp)

        lctr, lsp = semantics.loop_init(st.lctr, st.lsp, imm, en=is_init)
        lctr, taken, lsp_pop = semantics.loop_step(lctr, st.lsp, en=is_loop)
        lsp = jnp.where(is_loop & ~taken, lsp_pop, lsp)

        pc1 = jnp.where(gate, pc + 1, pc)
        pc_next = jnp.where(
            (is_jmp & gate) | is_jsr, imm,
            jnp.where(is_rts, rts_pc,
                      jnp.where(is_loop & taken, imm, pc1)))

        stat_cycles, stat_instrs = st.stat_cycles, st.stat_instrs
        if collect_stats:
            cls = trow[_TC_CLS]
            sm = (jnp.arange(isa.NUM_OP_CLASSES, dtype=_I32) == cls) & gate
            stat_cycles = st.stat_cycles + jnp.where(sm, issue, 0)
            stat_instrs = st.stat_instrs + jnp.where(sm, 1, 0)

        st2 = st._replace(
            regs=regs, pstack=pstack, pdepth=pdepth,
            lctr=lctr, lsp=lsp, cstack=cstack, csp=csp,
            pc=pc_next,
            cycles=st.cycles + jnp.where(gate, issue, 0),
            steps=st.steps + jnp.where(gate, 1, 0),
            halted=st.halted | is_stop,
            hazard=hz,
            hazard_violations=st.hazard_violations
            + (violated & gate).astype(_I32),
            stat_cycles=stat_cycles, stat_instrs=stat_instrs,
        )
        return st2, sidx, rdv

    def running(st: MachineState):
        return (~st.halted) & (st.steps < cfg.max_steps) & \
            (st.pc >= 0) & (st.pc < prog_len)

    return step, running


# ---------------------------------------------------------------------------
# Single-core driver
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _make_runner(cfg: EGPUConfig, prog_len: int,
                 ops_subset: frozenset | None = None,
                 validate: bool = True):
    step, running = make_step(cfg, prog_len, ops_subset,
                              check_hazards=validate,
                              collect_stats=validate)

    def body(carry):
        st, prog = carry
        st2, sidx, rdv = step(st, prog)
        shared = st2.shared.at[sidx].set(rdv, mode="drop")
        return (st2._replace(shared=shared), prog)

    def cond(carry):
        return running(carry[0])

    def run(prog, st):
        final, _ = lax.while_loop(cond, body, (st, prog))
        return final

    # the carried machine state is donated: XLA reuses its buffers
    # in-place instead of copying the register file / shared memory on
    # every dispatch (callers get a fresh state back)
    return jax.jit(name_kernel(run, "interp"), donate_argnums=(1,))


def padded_length(n: int) -> int:
    """Instruction count rounded up to the shared ``_PAD`` compile grid."""
    return n + (-n) % _PAD


def pad_image(image: ProgramImage, prog_len: int | None = None):
    """Pack a program into a ``(padded_len, 7)`` int32 array of decoded
    fields (column order :data:`PROG_FIELDS`), padded with STOP rows.

    Returns ``(packed, padded_len)``; ``padded_len`` is ``prog_len`` if
    given, else the next multiple of ``_PAD`` — the executor/fleet compile
    cache is keyed on that length, so padding to the shared grid reuses
    compiles.
    """
    n = image.n
    length = prog_len if prog_len is not None else padded_length(n)
    if length < n:
        raise ValueError(f"prog_len {length} < program length {n}")
    packed = np.zeros((length, 7), np.int32)
    packed[n:, _PF_OP] = int(Op.STOP)
    for col, field in enumerate(PROG_FIELDS):
        packed[:n, col] = getattr(image, field)
    return packed, length


def image_ops(image: ProgramImage) -> frozenset:
    """The program's instruction working set (incl. the STOP padding),
    used to specialize the interpreter dispatch to the opcodes that can
    actually occur."""
    return frozenset(int(o) for o in np.unique(image.op)) | {int(Op.STOP)}


def run_program(image: ProgramImage, state: MachineState | None = None, *,
                validate: bool = True, **init_kw) -> MachineState:
    """Execute an assembled program to completion (interpreter tier).

    The step is specialized to the program's opcode working set (the
    same specialization the fleet fast path uses), and ``validate=False``
    additionally drops the hazard checker and the Fig. 6 instruction-mix
    counters — architectural results (registers, shared memory, cycles,
    PC) are unchanged either way.

    The initial state's buffers are donated to the dispatch; if you pass
    ``state`` explicitly, treat it as consumed and use the returned one.
    """
    cfg = image.cfg
    if state is None:
        init_kw.setdefault("threads", image.threads_active)
        state = init_state(cfg, **init_kw)
    packed, length = pad_image(image)
    runner = _make_runner(cfg, length, image_ops(image), validate)
    with obs_trace.span("interpret", prog_len=length):
        out = runner(jnp.asarray(packed), state)
        out.cycles.block_until_ready()
    return out
