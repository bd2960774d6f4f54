"""The vmapped fleet runner: N cores, one ``while_loop``, one dispatch.

The single-core executor advances one instruction per ``while_loop``
iteration with real control flow (``lax.switch`` takes one branch).  The
fleet runner vmaps that same step function over a leading core axis:

* the loop condition becomes "any core still running";
* a halted (or faulted/out-of-bounds) core no-ops: its step result is
  discarded leaf-wise, freezing its state — cycles, stats and shared
  memory included — so per-job results are bit-identical to what
  :func:`repro.core.executor.run_program` produces for that job alone;
* all cores share one configuration (homogeneous fleet) and one padded
  program length, but each core carries its *own* program image, runtime
  thread count and shared memory, so the batch is heterogeneous in every
  dynamically-scalable axis of the paper.

The step function is built for this path (``make_step`` with
``flat_dispatch=True``): per-opcode values come from a fused
nested-``where`` chain over the batch's instruction working set, small
state structures update via one-hot selects, and the one true scatter
(STO to shared memory) is applied here as a single flattened batch
scatter gated on "any core stores this cycle" — batched scatters are the
slowest op on the CPU backend by an order of magnitude.
"""
from __future__ import annotations

import functools
import time
import weakref
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import semantics
from ..core.assembler import ProgramImage
from ..core.config import EGPUConfig
from ..core.executor import (make_step, name_kernel, pad_image,
                             padded_length)
from ..core.isa import Op
from ..core.machine import MachineState, init_state
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from . import devices as devices_mod
from . import faults


class ResidencyCache:
    """Device-resident batch inputs for the compiled lock-step tier.

    A drain of N same-program jobs transfers one ``(N, S)`` shared-memory
    image (plus the TDX grid vector) host -> device before launching the
    batched runner.  Serving workloads drain the *same* programs over the
    same inputs repeatedly, so this cache keeps the already-transferred
    device arrays resident across drains: a repeat drain whose key —
    which embeds a content digest of the batch (per-job shared image +
    TDX grid, order-sensitive, length-prefixed) — matches an entry
    replays the resident buffers and pays **zero host -> device
    transfer**.  That is only sound because the compiled
    light path (:meth:`repro.core.blockc.CompiledProgram.run_light_dev`)
    never donates its inputs — a donated buffer is consumed by XLA and
    cannot be replayed.

    Entries are LRU-bounded and **invalidated with the compile cache**:
    each entry holds a weak reference to the :class:`CompiledProgram` it
    was built against, and a lookup whose compiled program is no longer
    that exact object (evicted and recompiled, or garbage-collected)
    rebuilds rather than replays — the compiled program's identity is
    the invalidation token, so the two caches cannot drift apart.
    """

    def __init__(self, max_entries: int = 32):
        if max_entries < 1:
            raise ValueError("max_entries must be >= 1")
        self._max = max_entries
        self._entries: OrderedDict = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        """Drop every resident entry (a later lookup just rebuilds and
        re-transfers — an eviction is always a miss, never an error).
        The ``residency_evict`` fault site exercises exactly this."""
        self._entries.clear()

    def lookup(self, key, cp, build):
        """Return ``(arrays, hit)``: the device-resident input arrays
        for ``key`` (whose content identity the caller encodes in the
        key itself) if the entry was built against this exact ``cp``;
        otherwise call ``build()`` (which must return the device
        arrays), cache, and return them."""
        e = self._entries.get(key)
        if e is not None and e["cp"]() is cp:
            self._entries.move_to_end(key)
            self.hits += 1
            return e["arrays"], True
        arrays = build()
        self._entries[key] = {"cp": weakref.ref(cp), "arrays": arrays}
        self._entries.move_to_end(key)
        while len(self._entries) > self._max:
            self._entries.popitem(last=False)      # LRU eviction
        self.misses += 1
        return arrays, False


def stack_states(states: list[MachineState]) -> MachineState:
    """Stack per-core states along a new leading fleet axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(batched: MachineState, i: int) -> MachineState:
    """Extract core ``i``'s state from a batched fleet state."""
    return jax.tree_util.tree_map(lambda x: x[i], batched)


#: instruction steps per ``while_loop`` trip.  Unrolling amortises the
#: loop-boundary buffer copies XLA inserts around the carried state; the
#: act-gating in the step makes overshooting a core's STOP harmless.
_UNROLL = 8


@functools.lru_cache(maxsize=32)
def _make_fleet_runner(cfg: EGPUConfig, prog_len: int,
                       ops_subset: frozenset | None = None,
                       unroll: int = _UNROLL, validate: bool = True):
    step, running = make_step(cfg, prog_len, ops_subset,
                              flat_dispatch=True, check_hazards=validate,
                              collect_stats=validate)
    S = cfg.shared_words
    vstep = jax.vmap(step)
    vrunning = jax.vmap(running)

    def cond(carry):
        return jnp.any(vrunning(carry[0]))

    def substep(states, progs):
        act = vrunning(states)          # halted cores no-op via the gate
        sts, sidx, rdv = vstep(states, progs, act)

        # the deferred STO writes of the whole batch as ONE flat scatter
        # (semantics.store — shared with the block compiler), skipped
        # entirely on cycles where no core is storing (a batched per-core
        # scatter is the single slowest op on the CPU backend)
        shared = lax.cond(jnp.any(sidx < S),
                          lambda sh: semantics.store(sh, sidx, rdv),
                          lambda sh: sh, sts.shared)
        return sts._replace(shared=shared)

    def body(carry):
        states, progs = carry
        for _ in range(unroll):
            states = substep(states, progs)
        return (states, progs)

    def run(progs, states):
        final, _ = lax.while_loop(cond, body, (states, progs))
        return final

    # donate the carried batch state: XLA reuses the (N, T, R) register
    # files / (N, S) shared memories in place instead of copying them on
    # every dispatch (callers get the final state back)
    return jax.jit(name_kernel(run, "interp"), donate_argnums=(1,))


def _pack_programs(images: list[ProgramImage], prog_len: int | None = None):
    """Pad every image to one shared length, stack to ``(N, L, 7)``, and
    collect the batch's instruction working set (for switch
    specialization)."""
    if prog_len is None:
        prog_len = max(padded_length(im.n) for im in images)
    packed = np.stack([pad_image(im, prog_len)[0] for im in images])
    ops = frozenset(int(o) for im in images for o in np.unique(im.op))
    ops |= {int(Op.STOP)}           # padding rows
    return jnp.asarray(packed), prog_len, ops


#: AOT-compiled fleet executables keyed on (runner, batch shape): the
#: jit wrapper would fold XLA compilation into the first dispatch, which
#: makes the scheduler's wall-time attribution lie — ``lower().compile()``
#: splits it out (``timings["compile_s"]``) without an extra execution.
_FLEET_EXECS: OrderedDict = OrderedDict()
_FLEET_EXECS_MAX = 64


def _fleet_exec(runner, progs, states, device=None):
    """The AOT executable for this (runner, shapes, device), plus the
    host seconds spent compiling it now (0.0 on a cache hit).  AOT
    executables are pinned to the devices their inputs were lowered on,
    so ``device`` (None -> default placement) is part of the key."""
    key = (runner, progs.shape, device)
    exe = _FLEET_EXECS.get(key)
    if exe is not None:
        _FLEET_EXECS.move_to_end(key)
        obs_metrics.inc("fleet_compile_cache_total", result="hit")
        return exe, 0.0
    obs_metrics.inc("fleet_compile_cache_total", result="miss")
    t0 = time.perf_counter()
    with obs_trace.span("compile", kind="fleet_runner",
                        batch=progs.shape[0], prog_len=progs.shape[1]):
        exe = runner.lower(progs, states).compile()
    _FLEET_EXECS[key] = exe
    while len(_FLEET_EXECS) > _FLEET_EXECS_MAX:
        _FLEET_EXECS.popitem(last=False)
    return exe, time.perf_counter() - t0


def fleet_run(images: list[ProgramImage],
              states: list[MachineState] | MachineState | None = None, *,
              prog_len: int | None = None,
              init_kw: list[dict] | None = None,
              validate: bool = True,
              timings: dict | None = None,
              device=None) -> MachineState:
    """Execute one program per core, all cores in one vmapped dispatch.

    ``images`` must share a configuration (homogeneous cores).  ``states``
    — a list of per-core states or an already-batched state — or per-job
    ``init_kw`` dicts for :func:`init_state` supply each core's shared
    memory, runtime thread count and TDX grid.  Returns the batched final
    :class:`MachineState`; slice per-core results out with
    :func:`unstack_state`.

    ``validate=False`` drops the hazard checker and the instruction-mix
    counters from the compiled step (architectural results unchanged) —
    use for throughput runs.

    ``timings``, if given, receives ``{"compile_s": ...}`` — the host
    seconds spent XLA-compiling the runner for this batch shape during
    *this* call (0.0 when warm), so callers timing the dispatch can
    attribute one-time compile cost separately.

    ``device`` pins the dispatch to one jax device: inputs are placed
    there, the AOT executable is compiled against that placement (and
    cached per device), and metrics/fault-site info carry its label.
    ``None`` keeps today's default-device behavior bit-for-bit.
    """
    if not images:
        raise ValueError("empty fleet")
    cfg = images[0].cfg
    for im in images[1:]:
        if im.cfg != cfg:
            raise ValueError("fleet cores must share one EGPUConfig")
    if states is None:
        init_kw = init_kw or [{}] * len(images)
        states = [init_state(cfg, threads=im.threads_active, **kw)
                  for im, kw in zip(images, init_kw)]
    if isinstance(states, list):
        if len(states) != len(images):
            raise ValueError("one state per core required")
        states = stack_states(states)
    progs, length, ops = _pack_programs(images, prog_len)
    if device is not None:
        progs = jax.device_put(progs, device)
        states = jax.device_put(states, device)
    dev_label = devices_mod.device_label(device)
    runner = _make_fleet_runner(cfg, length, ops, validate=validate)
    exe, compile_s = _fleet_exec(runner, progs, states, device)
    if timings is not None:
        timings["compile_s"] = compile_s
    t_disp = time.perf_counter()
    with obs_trace.span("dispatch", cores=len(images), prog_len=length,
                        device=dev_label):
        faults.maybe_raise("dispatch", tier="interp", cores=len(images),
                           device=dev_label)
        out = exe(progs, states)
    t_sync = time.perf_counter()
    with obs_trace.span("device_sync"):
        hang = faults.hang_seconds("device_sync", tier="interp",
                                   device=dev_label)
        if hang:
            time.sleep(hang)
        out.cycles.block_until_ready()
    t_done = time.perf_counter()
    obs_metrics.observe("fleet_dispatch_seconds", t_sync - t_disp,
                        tier="interp", device=dev_label)
    obs_metrics.observe("fleet_device_sync_seconds", t_done - t_sync,
                        tier="interp", device=dev_label)
    return out
