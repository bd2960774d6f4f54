"""On-chip smoke test of the eGPU fleet and serving path.

    python chip_smoke.py             # one TPU chip: batch, QP and serve phases
    python chip_smoke.py --chips 4   # the multi-device path only, four chips

Drives the main path through the entry points a user calls
(``Fleet.submit``/``drain``, ``FleetService.submit``) at the paper's §7
instance: 512 threads, 32 registers and 128 KB of shared memory per
simulated core, 32 cores per batch, the §7 suite at n = 32 and 64.
Every phase checks its results and fails the run on the first miss:

* each job is bit-identical to ``run_program`` of the same job on the
  same chip (shared image, cycles, steps, instruction-mix stats) and has
  no hazard violations;
* each result is within its NumPy oracle's tolerance;
* no fallback fired: no tier degradation, bisection or salvage drop in
  the fleet, no failure, retry or scheduler reset in the service;
* each distinct program also runs through ``run_program`` on the host
  CPU: cycles, steps and stats must match exactly, integer programs
  bit for bit; float programs print their largest ULP difference and
  must agree within the oracle's tolerance.

With ``--chips 4`` only the multi-device path runs (``Fleet`` and
``FleetService`` with ``devices="all"``), compared job by job with the
same jobs on one chip.  Timings printed are smoke timings of a run that
may compile cold, not benchmark numbers.  Without a TPU the script exits
1 with a reason and prints no result line; otherwise its last line is
one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import benchmark_config, isa, run_program  # noqa: E402
from repro.fleet import (Fleet, FleetService, FleetStats,  # noqa: E402
                         device_label, enable_compile_cache)
from repro.programs import (build_bitonic, build_fft, build_matmul,  # noqa: E402
                            build_reduction, build_transpose)

BATCH = 32
SIZES = (32, 64)
#: seconds any one future may take to resolve (cold compiles included)
RESULT_TIMEOUT_S = 900.0


class SmokeError(AssertionError):
    """A smoke check failed; the message says which and why."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- programs
def suite(cfg, sizes=SIZES):
    """The §7 suite on one instance: reduction (plain and dot),
    transpose, matmul (plain and dot), bitonic and FFT at each size."""
    out = []
    for n in sizes:
        out += [build_reduction(cfg, n), build_reduction(cfg, n, use_dot=True),
                build_transpose(cfg, n), build_matmul(cfg, n),
                build_matmul(cfg, n, use_dot=True), build_bitonic(cfg, n),
                build_fft(cfg, n)]
    return out


def table7(cfg, sizes=SIZES):
    """The Table 7 kernels: reduction, transpose and matmul."""
    return [build(cfg, n) for n in sizes
            for build in (build_reduction, build_transpose, build_matmul)]


def is_float(image) -> bool:
    """Whether the program does floating-point arithmetic (FP ALU or an
    extension unit); data moves alone keep every bit."""
    return any(isa.OP_CLASS[int(op)] in (isa.OpClass.FP, isa.OpClass.EXT)
               for op in np.unique(image.op))


def variant(b, k: int) -> np.ndarray:
    """Job ``k``'s input: the bench's image scaled (float data) or
    xor-ed (integer data) by ``k``, so that every row of a batch holds
    different data and a row mix-up cannot go unseen."""
    x = np.asarray(b.shared_init)
    if x.dtype.kind == "f":
        return (x * np.float32(1 + k / 64)).astype(x.dtype)
    return x ^ x.dtype.type(k)


def _ordered(u32: np.ndarray) -> np.ndarray:
    """float32 bit patterns mapped to integers in the floats' order, so
    that the difference of two is their distance in ULPs."""
    i = u32.view(np.int32).astype(np.int64)
    return np.where(i < 0, np.int64(-2**31) - i, i)


def max_ulp(a: np.ndarray, b: np.ndarray) -> int:
    a = np.ascontiguousarray(a, np.float32).view(np.uint32)
    b = np.ascontiguousarray(b, np.float32).view(np.uint32)
    return int(np.max(np.abs(_ordered(a) - _ordered(b)))) if a.size else 0


def view(b, shared_u32: np.ndarray) -> np.ndarray:
    return np.asarray(b.result_view(types.SimpleNamespace(shared=shared_u32)))


def oracle_ok(b, shared_u32: np.ndarray) -> bool:
    got, exp = view(b, shared_u32), np.asarray(b.oracle(b.shared_init))
    if got.dtype.kind == "f":
        return bool(np.allclose(got, exp, atol=b.atol, rtol=b.rtol))
    return bool(np.array_equal(got, exp))


# ------------------------------------------------------------- references
class References:
    """``run_program`` of each distinct bench, once on the default
    device (the chip) and once on the host CPU, with the time each
    side took."""

    def __init__(self, cpu):
        self.cpu = cpu
        # keyed by id() and holding the bench itself, so the id cannot
        # be reused by another bench while its entry lives
        self._chip: dict[int, tuple] = {}
        self._cross: set[int] = set()
        self.chip_s = 0.0
        self.cpu_s = 0.0

    def chip(self, b):
        """The bench run through the interpreter on the chip, checked
        against its oracle."""
        if id(b) in self._chip:
            return self._chip[id(b)][1]
        t0 = time.perf_counter()
        st = run_program(b.image, shared_init=b.shared_init,
                         tdx_dim=b.tdx_dim)
        self.chip_s += time.perf_counter() - t0
        self._chip[id(b)] = (b, st)
        check(int(st.hazard_violations) == 0,
              f"{b.name}: run_program reports "
              f"{int(st.hazard_violations)} hazard violations")
        check(oracle_ok(b, np.asarray(st.shared)),
              f"{b.name}: run_program misses the NumPy oracle")
        return st

    def cross_backend(self, b) -> str | None:
        """Run the bench on the host CPU and hold it to the chip's run;
        returns the line to print (``None`` when already done)."""
        if id(b) in self._cross:
            return None
        ref = self.chip(b)
        t0 = time.perf_counter()
        with jax.default_device(self.cpu):
            st = run_program(b.image, shared_init=b.shared_init,
                             tdx_dim=b.tdx_dim)
            st = jax.tree_util.tree_map(np.asarray, st)
        self.cpu_s += time.perf_counter() - t0
        self._cross.add(id(b))
        for leaf in ("cycles", "steps", "hazard_violations", "stat_cycles",
                     "stat_instrs"):
            check(np.array_equal(np.asarray(getattr(ref, leaf)),
                                 getattr(st, leaf)),
                  f"{b.name}: {leaf} differs between the chip and the CPU")
        chip_sh, cpu_sh = np.asarray(ref.shared), st.shared
        words = int(np.count_nonzero(chip_sh != cpu_sh))
        if not is_float(b.image):
            check(words == 0, f"{b.name}: integer program differs in "
                              f"{words} shared words between chip and CPU")
            return f"  {b.name}: integer, bit-identical to the CPU"
        a, c = view(b, chip_sh), view(b, cpu_sh)
        check(np.allclose(a, c, atol=b.atol, rtol=b.rtol),
              f"{b.name}: chip and CPU disagree beyond the oracle tolerance")
        return (f"  {b.name}: float, max ULP vs CPU {max_ulp(a, c)} over "
                f"{a.size} result words, {words} shared words differ")


def same_result(r, ref, what: str) -> None:
    """A fleet JobResult against a reference MachineState or JobResult:
    every simulated result must be bit-identical."""
    if hasattr(ref, "shared_u32"):
        ref_sh = ref.shared_u32()
        leaves = {"cycles": ref.cycles, "steps": ref.steps,
                  "stat_cycles": ref.stat_cycles,
                  "stat_instrs": ref.stat_instrs}
    else:
        ref_sh = np.asarray(ref.shared)
        leaves = {k: np.asarray(getattr(ref, k)) for k in
                  ("cycles", "steps", "stat_cycles", "stat_instrs")}
    check(r.hazard_violations == 0,
          f"{what}: {r.hazard_violations} hazard violations")
    check(np.array_equal(r.shared_u32(), ref_sh),
          f"{what}: shared memory differs (tier {r.tier})")
    for k, v in leaves.items():
        check(np.array_equal(np.asarray(getattr(r, k)), v),
              f"{what}: {k} differs (tier {r.tier})")


def no_fallbacks(stats: FleetStats, what: str) -> None:
    for k in ("degraded_units", "bisections", "salvage_dropped"):
        check(getattr(stats, k) == 0,
              f"{what}: {k} = {getattr(stats, k)}, a fallback fired")


def tier_split(stats: FleetStats) -> dict[str, int]:
    return {"superblock": stats.superblock_jobs,
            "blocks": stats.compiled_jobs - stats.superblock_jobs,
            "interp": stats.jobs - stats.compiled_jobs}


# ----------------------------------------------------------------- phases
def device_gate(chips: int):
    """The visible devices, after refusing anything but TPUs (and, for
    ``chips=4``, anything but exactly four of them)."""
    devs = jax.devices()
    d0 = devs[0]
    log(f"device: platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    check(d0.platform == "tpu",
          f"no TPU: JAX's default device is {d0.platform!r}; this smoke "
          f"test runs only on the chip")
    if chips == 4:
        check(len(devs) == 4 and all(d.platform == "tpu" for d in devs),
              f"--chips 4 needs exactly 4 TPU devices, JAX sees {len(devs)}")
    return devs


def batch_phase(name: str, cfg, full, pair=(), singles=(), *,
                refs: References, batch_size: int = BATCH) -> dict:
    """One ``Fleet`` drain: ``batch_size`` jobs of each bench in
    ``full``, two of each in ``pair`` and one of each in ``singles``,
    so that every tier the scheduler picks carries jobs.  Every job is
    held to ``run_program`` on the chip and to its oracle, and every
    distinct program to the CPU."""
    t0 = time.perf_counter()
    fleet = Fleet(cfg, batch_size=batch_size)
    jobs = ([(b, fleet.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim))
             for b in full for _ in range(batch_size)]
            + [(b, fleet.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim))
               for b in pair for _ in range(2)]
            + [(b, fleet.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim))
               for b in singles])
    t_drain = time.perf_counter()
    results = fleet.drain()
    drain_s = time.perf_counter() - t_drain
    check(len(results) == len(jobs),
          f"{name}: {len(results)} results for {len(jobs)} jobs")
    chip0, cpu0 = refs.chip_s, refs.cpu_s
    for b, h in jobs:
        same_result(results[h], refs.chip(b), f"{name} {b.name}")
    no_fallbacks(fleet.stats, name)
    tiers = tier_split(fleet.stats)
    check(tiers["superblock"] > 0, f"{name}: no job ran on the superblock "
                                   f"tier: {tiers}")
    if pair:
        check(tiers["blocks"] > 0, f"{name}: no job ran on the blocks "
                                   f"tier: {tiers}")
    if singles:
        check(tiers["interp"] > 0, f"{name}: no job ran on the "
                                   f"interpreter: {tiers}")
    lines = [refs.cross_backend(b) for b in (*full, *pair, *singles)]
    report = {"jobs": len(jobs), "tiers": tiers, "drain_s": drain_s,
              "compile_s": fleet.stats.compile_s,
              "ref_s": refs.chip_s - chip0, "cpu_s": refs.cpu_s - cpu0,
              "wall_s": time.perf_counter() - t0}
    log(f"{name}: {len(jobs)} jobs passed; jobs per tier {tiers}")
    for line in lines:
        if line is not None:
            log(line)
    return report


def serve_phase(name: str, cfg, benches, n_requests: int, *,
                refs: References, batch_size: int = BATCH) -> dict:
    """``n_requests`` mixed requests through one ``FleetService``; every
    future must resolve to the ``run_program`` result."""
    t0 = time.perf_counter()
    with FleetService(cfg, batch_size=batch_size) as svc:
        futs = [(b, svc.submit(b.image, b.shared_init, tdx_dim=b.tdx_dim))
                for b in (benches[i % len(benches)]
                          for i in range(n_requests))]
        out = [(b, f.result(timeout=RESULT_TIMEOUT_S)) for b, f in futs]
    for b, r in out:
        same_result(r, refs.chip(b), f"{name} {b.name}")
    s = svc.stats
    check(s.completed == n_requests,
          f"{name}: {s.completed} of {n_requests} requests completed")
    for k in ("failed", "retries", "scheduler_resets"):
        check(getattr(s, k) == 0, f"{name}: {k} = {getattr(s, k)}")
    fstats = FleetStats(svc.metrics)
    no_fallbacks(fstats, name)
    log(f"{name}: {n_requests} requests resolved over {s.dispatches} "
        f"dispatches; jobs per tier {tier_split(fstats)}")
    return {"jobs": n_requests, "compile_s": fstats.compile_s,
            "wall_s": time.perf_counter() - t0}


def multichip_phase(cfg, mega=None, mix=None, *,
                    batch_size: int = BATCH) -> dict:
    """The multi-device path (``devices="all"``), compared job by job
    with one chip:

    * a same-program megabatch (one ``shard_map`` over the job mesh) of
      one slab each of the ``mega`` benches (default FFT-64 and
      matmul-64);
    * a heterogeneous mix of the ``mix`` benches through the per-device
      lanes, which must give every device work;
    * a ``FleetService`` over every device, which must resolve every
      future and keep every device healthy.

    Every job holds its own data (:func:`variant`)."""
    t0 = time.perf_counter()
    devices = jax.devices()
    n_dev = len(devices)
    labels = {device_label(d) for d in devices}
    slab = n_dev * batch_size
    if mega is None:
        mega = [build_fft(cfg, 64), build_matmul(cfg, 64)]
    if mix is None:
        mix = [build_reduction(cfg, 32), build_reduction(cfg, 32, use_dot=True),
               build_transpose(cfg, 32), build_bitonic(cfg, 32),
               build_fft(cfg, 32), build_reduction(cfg, 64)]
    mega = [(b, variant(b, k)) for b in mega for k in range(slab)]
    # fewer jobs per program than one slab: the per-device lanes take them
    mix = [(b, variant(b, k)) for b in mix
           for k in range(min(batch_size, slab - 1))]
    serve = mix[::3]

    one = Fleet(cfg, batch_size=batch_size)
    handles = [one.submit(b.image, x, tdx_dim=b.tdx_dim)
               for b, x in mega + mix]
    ref_res = one.drain()
    ref = [ref_res[h] for h in handles]
    no_fallbacks(one.stats, "one-chip reference")
    t_ref = time.perf_counter()

    fleet = Fleet(cfg, batch_size=batch_size, devices="all")
    hs = [fleet.submit(b.image, x, tdx_dim=b.tdx_dim) for b, x in mega]
    res = fleet.drain()
    for i, ((b, _), h) in enumerate(zip(mega, hs)):
        same_result(res[h], ref[i], f"megabatch {b.name} job {i}")
    per_dev = fleet.stats.per_device()
    check(per_dev.get("mesh", {}).get("jobs") == len(mega),
          f"megabatch did not take the shard_map route: {per_dev}")
    no_fallbacks(fleet.stats, "megabatch")
    log(f"megabatch: {len(mega)} jobs in slabs of {slab} over {n_dev} "
        f"devices, bit-identical to one chip")

    fleet = Fleet(cfg, batch_size=batch_size, devices="all")
    hs = [fleet.submit(b.image, x, tdx_dim=b.tdx_dim) for b, x in mix]
    res = fleet.drain()
    for i, ((b, _), h) in enumerate(zip(mix, hs)):
        same_result(res[h], ref[len(mega) + i], f"lanes {b.name} job {i}")
    per_dev = fleet.stats.per_device()
    idle = sorted(lbl for lbl in labels
                  if per_dev.get(lbl, {}).get("jobs", 0) == 0)
    check(not idle, f"per-device lanes left {idle} without work: {per_dev}")
    no_fallbacks(fleet.stats, "per-device lanes")
    log(f"per-device lanes: {len(mix)} jobs, bit-identical to one chip; "
        f"jobs per device "
        f"{ {k: v['jobs'] for k, v in sorted(per_dev.items())} }")

    with FleetService(cfg, batch_size=batch_size, devices="all") as svc:
        futs = [svc.submit(b.image, x, tdx_dim=b.tdx_dim) for b, x in serve]
        out = [f.result(timeout=RESULT_TIMEOUT_S) for f in futs]
        healthy = set(svc.healthy_devices)
    for i, r in enumerate(out):
        same_result(r, ref[len(mega) + 3 * i], f"service job {i}")
    s = svc.stats
    check(s.completed == len(serve) and s.failed == 0,
          f"service: {s.completed} completed, {s.failed} failed of "
          f"{len(serve)}")
    check(healthy == labels,
          f"service: healthy devices {sorted(healthy)}, want "
          f"{sorted(labels)}")
    log(f"service over {n_dev} devices: {len(serve)} requests resolved, "
        f"all devices healthy")
    return {"jobs": len(mega) + len(mix) + len(serve),
            "ref_s": t_ref - t0, "wall_s": time.perf_counter() - t0}


# ------------------------------------------------------------------- main
def _timing(name: str, rep: dict) -> None:
    parts = ", ".join(f"{k} {rep[k]:.2f} s" for k in
                      ("compile_s", "drain_s", "ref_s", "cpu_s")
                      if k in rep)
    log(f"[smoke timing, not a benchmark] {name}: wall {rep['wall_s']:.2f} s"
        + (f" ({parts})" if parts else ""))


def run_one_chip() -> None:
    refs = References(jax.devices("cpu")[0])
    cfg = benchmark_config("dp", has_dot=True, predicate_levels=2)
    reps = {}
    full = suite(cfg)
    reps["batch-dp"] = batch_phase(
        "batch-dp", cfg, full,
        pair=[build_reduction(cfg, 128)],
        singles=[build_reduction(cfg, 32, no_dynamic=True),
                 build_bitonic(cfg, 16), build_fft(cfg, 128)],
        refs=refs)
    _timing("batch-dp", reps["batch-dp"])
    cfg_qp = benchmark_config("qp")
    reps["batch-qp"] = batch_phase("batch-qp", cfg_qp, table7(cfg_qp),
                                   refs=refs)
    _timing("batch-qp", reps["batch-qp"])
    reps["serve"] = serve_phase("serve", cfg, full, 64, refs=refs)
    _timing("serve", reps["serve"])
    log(f"[smoke timing, not a benchmark] total fleet compile "
        f"{sum(r['compile_s'] for r in reps.values()):.2f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device path, on 4 chips")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    try:
        devs = device_gate(args.chips)
        log(f"compile cache: {enable_compile_cache()}")
        if args.chips == 4:
            rep = multichip_phase(
                benchmark_config("dp", has_dot=True, predicate_levels=2))
            _timing("multichip", rep)
        else:
            run_one_chip()
    except SmokeError as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    log(f"[smoke timing, not a benchmark] whole run "
        f"{time.perf_counter() - t0:.2f} s")
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
