"""Multi-device fleet: shard the job stream across every local device.

:class:`ShardedFleetScheduler` extends :class:`~repro.fleet.scheduler.
FleetScheduler` — same ``submit``/``drain``/``drain_isolated`` API, same
crash-safety and salvage invariants — but executes across a set of jax
devices instead of one:

* **same-program megabatches** — a group big enough to fill every
  device (``>= n_devices * batch_size`` jobs of one program at one
  thread count) is packed into exact slabs of ``n_devices *
  batch_size`` rows and dispatched as ONE ``shard_map`` call over the
  1-D ``("jobs",)`` device mesh: each device runs the compiled light
  path over its ``batch_size``-row shard.  Every row is an independent
  core, so sharding the leading batch axis is bit-identical to the
  single-device dispatch (the degenerate-path equivalence tests pin
  this).  Slab inputs keep their own device-sharded
  :class:`~repro.fleet.engine.ResidencyCache`, and the ``shard_map``
  executable is AOT-compiled and cached per (program, slab shape);
* **heterogeneous mixes** — everything else routes through per-device
  queues: jobs group by program (so one device keeps a program's
  residency and compile caches warm), groups are assigned to the
  least-loaded device by the cost model's per-job estimates
  (:func:`~repro.fleet.devices.balance_units`), and each device's
  private pinned :class:`FleetScheduler` drains its lane on its own
  thread — one dispatch stream per device;
* **shared accounting** — every sub-scheduler reports into this
  scheduler's :class:`~repro.obs.metrics.MetricsRegistry` under its own
  ``device`` label (megabatches report as ``device="mesh"``: one
  dispatch spans every device), so ``stats`` aggregates fleet-wide and
  ``stats.per_device()`` splits it back out.

Crash-safety composes: a failing device lane re-queues its unprocessed
jobs and stashes its computed results inside its sub-scheduler; this
scheduler *adopts* that state (checksum-verified) before re-raising, so
the caller sees exactly the single-scheduler contract — a failed drain
loses no work, computed or queued, whichever device failed.

With one device the behavior (and every architectural result) is
bit-identical to a plain ``FleetScheduler`` — multi-device is purely a
throughput layer.
"""
from __future__ import annotations

import concurrent.futures
import contextvars
import hashlib
import time
from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import machine as machine_mod
from ..core.blockc import program_digest, program_key
from ..core.config import EGPUConfig
from ..core.executor import name_kernel
from ..obs import counters as obs_counters
from ..obs import trace as obs_trace
from . import faults
from .devices import (balance_units, device_label, fleet_devices,
                      make_job_mesh)
from .engine import ResidencyCache
from .scheduler import (DrainCancelled, FleetJob, FleetScheduler,
                        JobResult, _result_checksum)

__all__ = ["ShardedFleetScheduler", "mega_light_fn"]

#: AOT shard_map executables kept per scheduler (LRU)
_MEGA_EXECS_MAX = 32


def mega_light_fn(cp, mesh):
    """``cp``'s light path as one ``shard_map`` over the 1-D job mesh:
    ``(shared (N, S), tdx (N,)) -> (shared, cycles, halted)`` with the
    leading (job) axis split across the mesh's devices.  Every row is an
    independent core, so this is bit-identical to the single-device
    call.  Its kernel is named ``egpu_mega_<tier>_<program digest>``."""
    fn = jax.shard_map(cp.light_fn(), mesh=mesh,
                       in_specs=(P("jobs", None), P("jobs")),
                       out_specs=(P("jobs", None), P("jobs"), P("jobs")))
    return name_kernel(fn, "mega_" + cp.mode, program_digest(cp.image))


class ShardedFleetScheduler(FleetScheduler):
    """A :class:`FleetScheduler` sharded over local jax devices.

    ``devices`` accepts everything :func:`~repro.fleet.devices.
    fleet_devices` does: ``"all"`` (default — every local device), an
    int N (the first N), or an explicit device sequence.  All other
    knobs match :class:`FleetScheduler` and apply to every per-device
    lane.
    """

    def __init__(self, cfg: EGPUConfig, batch_size: int = 32, *,
                 devices: Any = "all", **kw):
        super().__init__(cfg, batch_size, **kw)
        self.devices = fleet_devices(devices)
        self.n_devices = len(self.devices)
        self.device_labels = tuple(device_label(d) for d in self.devices)
        #: megabatch dispatches span the whole mesh, so their metrics
        #: land under this label instead of any one device
        self._dev = "mesh"
        self._mesh = make_job_mesh(self.devices)
        #: one pinned scheduler per device, all reporting into OUR
        #: registry (lifetime totals aggregate fleet-wide); jobs are
        #: injected into the lanes' queues with *our* handles, so their
        #: results/failures/salvage need no remapping
        self._scheds = tuple(
            FleetScheduler(cfg, batch_size,
                           pack_by_cost=self.pack_by_cost,
                           validate=self.validate,
                           use_compiler=self.use_compiler,
                           compile_min=self.compile_min,
                           tier_policy=kw.get("tier_policy"),
                           residency_max=kw.get("residency_max", 32),
                           fixed_bucket=self.fixed_bucket,
                           trace=self.tracer, metrics=self._m,
                           device=d)
            for d in self.devices)
        #: device-sharded megabatch inputs (separate from the base
        #: cache: same content on one device vs mesh-sharded are
        #: different placements and must never alias)
        self._mega_residency = ResidencyCache(kw.get("residency_max", 32))
        self._mega_execs: OrderedDict = OrderedDict()

    def cancel(self) -> None:
        super().cancel()
        for s in self._scheds:
            s.cancel()

    # -------------------------------------------------------- megabatch
    @property
    def _slab(self) -> int:
        """Megabatch slab: one full batch per device, dispatched as one
        ``shard_map`` call.  Exact slabs only — one XLA shape per
        program, like serving's ``fixed_bucket``."""
        return self.n_devices * self.batch_size

    def _mega_exec(self, cp, shared, tdx):
        """The AOT-compiled ``shard_map`` light executable for this
        (program, slab shape), plus compile seconds (0.0 when warm)."""
        key = (program_key(cp.image), cp.threads, cp.mode,
               np.shape(shared))
        e = self._mega_execs.get(key)
        if e is not None and e["cp"] is cp:
            self._mega_execs.move_to_end(key)
            self._m.inc("fleet_compile_cache_total", result="hit")
            return e["exe"], 0.0
        self._m.inc("fleet_compile_cache_total", result="miss")
        t0 = time.perf_counter()
        with obs_trace.span("compile", kind="xla_mega", tier=cp.mode,
                            batch=np.shape(shared)[0],
                            devices=self.n_devices):
            exe = jax.jit(mega_light_fn(cp, self._mesh)).lower(
                shared, tdx).compile()
        self._mega_execs[key] = {"cp": cp, "exe": exe}
        self._mega_execs.move_to_end(key)
        while len(self._mega_execs) > _MEGA_EXECS_MAX:
            self._mega_execs.popitem(last=False)
        return exe, time.perf_counter() - t0

    def _mega_inputs(self, cp, chunk: list[FleetJob]):
        """Mesh-sharded slab inputs, replayed from the megabatch
        residency cache when this exact content was transferred
        before (same digest discipline as the base scheduler)."""
        S = self.cfg.shared_words
        h = hashlib.blake2b(digest_size=16)
        for j in chunk:
            if j.shared_init is None:
                h.update(b"\x00")
            else:
                h.update(b"\x01")
                dt = str(j.shared_init.dtype).encode()
                h.update(len(dt).to_bytes(4, "little"))
                h.update(dt)
                payload = j.shared_init.tobytes()
                h.update(len(payload).to_bytes(8, "little"))
                h.update(payload)
            h.update(int(j.tdx_dim).to_bytes(4, "little", signed=True))
        key = (program_key(cp.image), cp.threads, self.validate,
               len(chunk), h.digest())

        def build():
            shared = np.zeros((len(chunk), S), np.uint32)
            for i, j in enumerate(chunk):
                if j.shared_init is None:
                    continue
                buf = machine_mod.pack_shared_init(j.shared_init, S)
                shared[i, :buf.size] = buf
            tdx = np.asarray([j.tdx_dim for j in chunk], np.int32)
            sh_dev = jax.device_put(
                jnp.asarray(shared),
                NamedSharding(self._mesh, P("jobs", None)))
            tdx_dev = jax.device_put(
                jnp.asarray(tdx), NamedSharding(self._mesh, P("jobs")))
            return sh_dev, tdx_dev

        if faults.fire("residency_evict") is not None:
            self._mega_residency.clear()
        arrays, hit = self._mega_residency.lookup(key, cp, build)
        self._m.inc("fleet_residency_lookups_total",
                    result="hit" if hit else "miss")
        return arrays, hit

    def _run_megabatch(self, cp, chunk: list[FleetJob],
                       results: dict[int, JobResult]) -> None:
        """One exact slab — ``n_devices * batch_size`` same-program
        jobs — as a single ``shard_map`` dispatch over the job mesh."""
        real = len(chunk)
        with obs_trace.span("batch", tier=cp.mode, jobs=real,
                            device="mesh", devices=self.n_devices):
            t0 = time.perf_counter()
            with obs_trace.span("residency") as rsp:
                (shared_dev, tdx_dev), res_hit = \
                    self._mega_inputs(cp, chunk)
            if rsp.active:
                rsp.set(hit=res_hit)
            exe, compile_s = self._mega_exec(cp, shared_dev, tdx_dev)
            self._m.inc("fleet_compile_seconds_total", compile_s)
            t_disp = time.perf_counter()
            with obs_trace.span("dispatch", cores=real, device="mesh",
                                kernel_threads=cp.kernel_threads):
                faults.maybe_raise("dispatch", tier=cp.mode, cores=real,
                                   device="mesh")
                shared_out, _, _ = exe(shared_dev, tdx_dev)
            t_sync = time.perf_counter()
            with obs_trace.span("device_sync"):
                hang = faults.hang_seconds("device_sync", tier=cp.mode,
                                           device="mesh")
                if hang:
                    time.sleep(hang)
                shared_out.block_until_ready()
            t_done = time.perf_counter()
            self._m.observe("fleet_dispatch_seconds", t_sync - t_disp,
                            tier=cp.mode, device="mesh")
            self._m.observe("fleet_device_sync_seconds", t_done - t_sync,
                            tier=cp.mode, device="mesh")
            wall = time.perf_counter() - t0 - compile_s
            with obs_trace.span("collect"):
                self._collect_light(cp, shared_out, chunk, real, wall,
                                    results)

    def _take_megabatches(self, jobs: list[FleetJob]):
        """Split out exact same-program slabs for the ``shard_map``
        path; returns ``(slabs, rest)`` where each slab is
        ``(CompiledProgram, jobs)`` and ``rest`` keeps submission
        order."""
        slab = self._slab
        groups: dict[tuple, list[FleetJob]] = {}
        order: list[FleetJob] = []
        for j in jobs:
            groups.setdefault((program_key(j.image), j.threads),
                              []).append(j)
        slabs: list[tuple[Any, list[FleetJob]]] = []
        rest_set: set[int] = set()
        for group in groups.values():
            n_slabs = len(group) // slab
            if n_slabs == 0:
                rest_set.update(id(j) for j in group)
                continue
            cp = self._compile_unit(group[0], self.batch_size,
                                    jobs=len(group))
            if cp is None:               # interpreter tier: per-device
                rest_set.update(id(j) for j in group)
                continue
            self._event("megabatch", program=program_digest(cp.image),
                        jobs=n_slabs * slab, slabs=n_slabs,
                        devices=self.n_devices, tier=cp.mode)
            for i in range(n_slabs):
                slabs.append((cp, group[i * slab:(i + 1) * slab]))
            rest_set.update(id(j) for j in group[n_slabs * slab:])
        for j in jobs:
            if id(j) in rest_set:
                order.append(j)
        return slabs, order

    # ------------------------------------------------- per-device lanes
    def _adopt_sub_state(self, sub: FleetScheduler,
                         results: dict[int, JobResult]) -> None:
        """Absorb a failed lane's crash-safety state: its computed
        (stashed) results join ours after checksum verification —
        corruption is dropped and re-executed, exactly the base
        salvage contract — and its re-queued jobs are released (our
        own requeue path re-queues every uncollected handle)."""
        for h, r in sub._salvaged.items():
            if _result_checksum(r) != sub._salvage_sums.get(h):
                self._m.inc("fleet_salvage_dropped_total")
                self._event("salvage_corrupt", cat="serve", handle=h)
                continue
            results[h] = r
        sub._salvaged, sub._salvage_sums, sub._salvage_jobs = {}, {}, {}
        sub._queue = []

    def _run_balanced(self, jobs: list[FleetJob],
                      results: dict[int, JobResult],
                      failures: dict[int, Exception],
                      isolate: bool) -> None:
        """Route a heterogeneous mix through the per-device lanes:
        same-program groups stay whole (cache locality), lanes fill
        least-loaded-first by summed job cost, and every device drains
        its lane concurrently on its own thread."""
        if not jobs:
            return
        groups: dict[tuple, list[FleetJob]] = {}
        for j in jobs:
            groups.setdefault((program_key(j.image), j.threads),
                              []).append(j)
        units = list(groups.values())
        lanes = balance_units(units, self.n_devices,
                              cost=lambda u: sum(j.cost for j in u))

        def lane_drain(d: int):
            sub = self._scheds[d]
            for unit in lanes[d]:
                sub._queue.extend(unit)
            with obs_trace.span("device_lane",
                                device=self.device_labels[d],
                                jobs=sub.pending):
                return (sub.drain_isolated() if isolate
                        else (sub.drain(), {}))

        active = [d for d in range(self.n_devices) if lanes[d]]
        outcomes: list[tuple[int, Any, BaseException | None]] = []
        if len(active) <= 1:
            for d in active:
                try:
                    outcomes.append((d, lane_drain(d), None))
                except BaseException as e:
                    outcomes.append((d, None, e))
        else:
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=len(active),
                    thread_name_prefix="fleet-dev") as ex:
                futs = [(d, ex.submit(contextvars.copy_context().run,
                                      lane_drain, d))
                        for d in active]
                for d, f in futs:
                    try:
                        outcomes.append((d, f.result(), None))
                    except BaseException as e:
                        outcomes.append((d, None, e))
        first_err: BaseException | None = None
        for d, out, err in outcomes:
            if err is None:
                res, fails = out
                results.update(res)
                failures.update(fails)
            else:
                self._adopt_sub_state(self._scheds[d], results)
                self._event("device_lane_failed", cat="serve",
                            device=self.device_labels[d],
                            error=type(err).__name__)
                if first_err is None or isinstance(err, DrainCancelled):
                    first_err = err
        if first_err is not None:
            raise first_err

    # ------------------------------------------------------------ drain
    def _drain(self, isolate: bool = False):
        results, delivered_jobs = self._take_salvaged()
        n_salvaged = len(results)
        failures: dict[int, Exception] = {}
        all_jobs = self._queue
        self._queue = []
        if not self._cancelled:          # a fresh drain clears old flags
            for s in self._scheds:
                s._cancelled = False

        with obs_trace.span("drain", jobs=len(all_jobs),
                            devices=self.n_devices) as dsp:
            try:
                pending = all_jobs
                slabs: list = []
                if self.use_compiler:
                    with obs_trace.span("partition", jobs=len(pending)):
                        slabs, pending = self._take_megabatches(pending)
                for cp, chunk in slabs:
                    if self._cancelled:
                        raise DrainCancelled("drain cancelled")
                    if isolate:
                        try:
                            self._run_megabatch(cp, chunk, results)
                        except DrainCancelled:
                            raise
                        except Exception as e:
                            # contain: the per-device isolated lanes
                            # (bisection, tier degradation) absorb it
                            self._event("megabatch_failed", cat="serve",
                                        jobs=len(chunk), tier=cp.mode,
                                        error=type(e).__name__)
                            pending = pending + chunk
                    else:
                        self._run_megabatch(cp, chunk, results)
                if self._cancelled:
                    raise DrainCancelled("drain cancelled")
                self._run_balanced(pending, results, failures, isolate)
            except BaseException:
                unprocessed = [j for j in all_jobs
                               if j.handle not in results
                               and j.handle not in failures]
                unprocessed.sort(key=lambda j: j.handle)
                self._queue = unprocessed + self._queue
                self._stash_salvage(results, delivered_jobs, all_jobs)
                raise

            tr = obs_trace.current_tracer()
            if tr is not None:
                agg = obs_counters.aggregate(
                    r.counters for r in results.values())
                if agg is not None:
                    flat = agg.flat()
                    tr.event("drain_counters", **flat)
                    tr.add_counters(flat)
                if dsp.active:
                    dsp.set(delivered=len(results),
                            failed=len(failures),
                            devices=self.n_devices)
        if n_salvaged:
            self._m.inc("fleet_salvaged_jobs_total", n_salvaged)
        return results, failures
