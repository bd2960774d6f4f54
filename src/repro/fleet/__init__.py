"""Fleet engine: batched multi-core eGPU execution.

Simulates N homogeneous eGPU cores in lock-step by ``jax.vmap``-ing the
single-core step function (:func:`repro.core.executor.make_step`) over a
batch of :class:`~repro.core.machine.MachineState`s, and schedules
heterogeneous jobs — different programs, per-job runtime thread counts
(the paper's dynamic scalability), per-job shared-memory images — into
fixed-shape batches that execute in one XLA dispatch.

This is the multi-core regime of the paper's follow-up work ("A 950 MHz
SIMT Soft Processor" scales the same microarchitecture to arrays of
cores) and what throughput studies against IP cores need.

    from repro.fleet import Fleet
    fleet = Fleet(cfg, batch_size=32)
    h = fleet.submit(image, shared_init=data, threads=256)
    results = fleet.drain()
    results[h].shared_f32()

For always-on serving (per-job futures, deadlines, priorities, retries
with backoff, bounded admission, deterministic fault injection):

    from repro.fleet import FleetService, FaultPlan
    with FleetService(cfg, batch_size=32, max_delay_s=0.002) as svc:
        fut = svc.submit(image, data, deadline_s=0.5)
        fut.result()                     # JobResult, or raises JobError
"""
from .api import Fleet, run_jobs, serve_jobs
from .devices import (balance_units, device_label, enable_compile_cache,
                      fleet_devices, make_job_mesh)
from .engine import ResidencyCache, fleet_run, stack_states, unstack_state
from .faults import FAULT_SITES, FaultPlan, FaultSpec, InjectedFault
from .scheduler import (FleetJob, FleetScheduler, FleetStats, JobResult,
                        check_job)
from .service import (AdmissionError, FleetService, JobError, ServiceStats,
                      register_serve_metrics)
from .sharded import ShardedFleetScheduler

__all__ = [
    "Fleet", "run_jobs", "serve_jobs", "fleet_run", "stack_states",
    "unstack_state", "FleetJob", "FleetScheduler", "FleetStats",
    "JobResult", "ResidencyCache", "check_job",
    "ShardedFleetScheduler", "fleet_devices", "device_label",
    "make_job_mesh", "balance_units", "enable_compile_cache",
    "FleetService", "ServiceStats", "JobError", "AdmissionError",
    "register_serve_metrics",
    "FaultPlan", "FaultSpec", "InjectedFault", "FAULT_SITES",
]
