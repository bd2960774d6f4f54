"""Fleet throughput demo: serve a queue of eGPU jobs on batched cores.

Submits a heterogeneous stream of assembled programs — different kernels,
sizes, shared-memory images, runtime thread counts — to a 32-core fleet,
drains it in vmapped batches, and compares against the one-core
``run_program`` loop.

  PYTHONPATH=src python examples/fleet_throughput.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from repro.core import machine as machine_mod
from repro.core import run_program
from repro.fleet import Fleet, enable_compile_cache
from benchmarks.fleet import build_jobs, fleet_config


def main() -> None:
    enable_compile_cache()
    cfg = fleet_config()
    jobs = build_jobs(cfg, 96, mix="suite")
    print(f"{len(jobs)} jobs over {len({b.name for b in jobs})} distinct "
          f"programs; eGPU config: {cfg.max_threads} threads, "
          f"{cfg.shared_kb}KB shared, {cfg.memory_mode.upper()} memory\n")

    def submit_all(fleet):
        return [fleet.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim,
                             tag=b.name,
                             weight=b.image.static_cycle_estimate())
                for b in jobs]

    # first drain compiles the per-batch fleet runners; time steady state
    warm = Fleet(cfg, batch_size=32)
    submit_all(warm)
    t0 = time.perf_counter()
    warm.drain()
    compile_s = time.perf_counter() - t0

    fleet = Fleet(cfg, batch_size=32)
    handles = submit_all(fleet)
    t0 = time.perf_counter()
    results = fleet.drain()
    fleet_s = time.perf_counter() - t0

    # correctness spot-check + simulated-time accounting
    sim_us = 0.0
    for b, h in zip(jobs[:8], handles[:8]):
        st = run_program(b.image, shared_init=b.shared_init,
                         tdx_dim=b.tdx_dim)
        assert np.array_equal(machine_mod.shared_as_u32(st),
                              results[h].shared_u32()), b.name
    for h in handles:
        assert results[h].hazard_violations == 0
        sim_us += results[h].time_us

    t0 = time.perf_counter()
    for b in jobs:
        run_program(b.image, shared_init=b.shared_init, tdx_dim=b.tdx_dim)
    serial_s = time.perf_counter() - t0

    s = fleet.stats
    print(f"fleet : {len(jobs)} jobs in {fleet_s * 1e3:7.1f} ms "
          f"({len(jobs) / fleet_s:7.1f} jobs/s) across {s.batches} "
          f"dispatches ({s.compiled_jobs} jobs on the block-compiled "
          f"tier, {s.pad_slots} filler slots; first-run compile "
          f"took {compile_s:.1f} s)")
    print(f"serial: {len(jobs)} jobs in {serial_s * 1e3:7.1f} ms "
          f"({len(jobs) / serial_s:7.1f} jobs/s)")
    print(f"speedup {serial_s / fleet_s:.2f}x | simulated eGPU time "
          f"{sim_us / 1e3:.2f} ms @ {cfg.fmax_mhz:.0f} MHz")

    h = handles[0]
    print(f"\nper-job result (handle {h}, {results[h].tag}): "
          f"{results[h].cycles} cycles, {results[h].steps} instructions")
    mix = {k: v for k, v in results[h].profile().items() if v[1]}
    print(f"instruction mix: {mix}")


if __name__ == "__main__":
    main()
