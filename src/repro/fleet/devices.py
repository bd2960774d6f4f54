"""Device topology + cost balancing for the multi-device fleet.

The fleet shards its job stream across every local accelerator.  This
module owns the three primitives everything above builds on:

* **resolution** — ``fleet_devices(spec)`` turns a user-facing device
  spec (``None``/``"all"``/count/explicit list) into a concrete tuple of
  jax devices, with an actionable error naming the
  ``--xla_force_host_platform_device_count`` recipe when a CPU-only box
  has fewer devices than asked for;
* **the job mesh** — ``make_job_mesh(devices)`` builds the 1-D
  ``("jobs",)`` mesh that same-program megabatches ``shard_map`` over
  (the batch axis is the *job* axis: every row is an independent core,
  so splitting it across devices is bit-identical to the single-device
  dispatch);
* **balancing** — ``balance_units(units, n, cost)`` greedily assigns
  routing units (same-program job groups) to the least-loaded device by
  the cost model's per-job estimates, keeping each group on one device
  so its ResidencyCache and AOT compile-cache entries stay warm.

Everything here is topology-only: no dispatch, no state.  The sharded
scheduler (``fleet/sharded.py``) and the serving layer
(``fleet/service.py``) compose these with per-device
``FleetScheduler`` instances.  ``enable_compile_cache()`` is the one
process-wide setting: entry points call it before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Callable, Sequence

import jax

DeviceSpec = Any  # None | int | "all" | Device | Sequence[Device]

#: ``.jax_cache/`` at the root of the checkout (``src/repro/fleet/`` is
#: three levels below it)
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` names the directory when it is set;
    otherwise it is :data:`DEFAULT_COMPILE_CACHE`, a fixed path, so a
    rerun from the same checkout finds what the last run compiled.
    Every compile is kept, however short: the fleet's AOT light-path
    and runner compiles are mostly sub-second each, but a cold drain
    pays for dozens of them.  Entry points call this before their first
    compile; importing a module never does.
    """
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(DEFAULT_COMPILE_CACHE))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_label(dev) -> str:
    """Stable metrics/trace label for a device: ``"cpu:0"``, ``"gpu:1"``.

    ``None`` (an unpinned scheduler) maps to ``"default"`` so the
    degenerate single-device fleet never touches jax device state just
    to label a metric.
    """
    if dev is None:
        return "default"
    return f"{dev.platform}:{dev.id}"


def _oversubscribed(requested: int, available: int, what: str) -> ValueError:
    return ValueError(
        f"{what} needs {requested} devices but only {available} "
        f"{'is' if available == 1 else 'are'} visible to jax. On a "
        "CPU-only host, export "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={requested} "
        "before the first jax import (see README 'Multi-device')."
    )


def fleet_devices(spec: DeviceSpec = "all"):
    """Resolve a device spec to a concrete tuple of jax devices.

    * ``"all"`` / ``None`` — every local device, in ``jax.devices()``
      order;
    * an ``int`` N — the first N local devices (raises with the
      ``xla_force_host_platform_device_count`` recipe if fewer exist);
    * a single device or a sequence of devices — used as given.
    """
    if spec is None or spec == "all":
        return tuple(jax.devices())
    if isinstance(spec, int):
        if spec < 1:
            raise ValueError(f"device count must be >= 1, got {spec}")
        devs = jax.devices()
        if spec > len(devs):
            raise _oversubscribed(spec, len(devs), f"devices={spec}")
        return tuple(devs[:spec])
    if hasattr(spec, "platform") and hasattr(spec, "id"):
        return (spec,)
    devs = tuple(spec)
    if not devs:
        raise ValueError("devices= must name at least one device")
    return devs


def make_job_mesh(devices: Sequence[Any]):
    """1-D mesh over ``devices`` with the single axis ``"jobs"``.

    Same-program megabatches shard their leading (job) axis over this
    mesh; every other array axis is replicated.
    """
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices, dtype=object), ("jobs",))


def balance_units(
    units: Sequence[Any],
    n_devices: int,
    cost: Callable[[Any], float],
) -> list[list[Any]]:
    """Greedy least-loaded assignment of routing units to devices.

    Units are sorted by descending cost (LPT scheduling) and each is
    placed on the currently least-loaded device, so a heterogeneous mix
    spreads by the cost model's estimates rather than round-robin.
    Returns ``n_devices`` lists (some possibly empty).  Ties break on
    device index so the assignment is deterministic.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    lanes: list[list[Any]] = [[] for _ in range(n_devices)]
    if n_devices == 1:
        lanes[0].extend(units)
        return lanes
    load = [0.0] * n_devices
    order = sorted(range(len(units)), key=lambda i: -float(cost(units[i])))
    for i in order:
        k = min(range(n_devices), key=lambda d: (load[d], d))
        lanes[k].append(units[i])
        load[k] += float(cost(units[i]))
    # preserve submission order within each lane (drain order stability)
    index = {id(u): i for i, u in enumerate(units)}
    for lane in lanes:
        lane.sort(key=lambda u: index[id(u)])
    return lanes
