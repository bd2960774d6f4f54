"""Four virtual CPU devices: a sound tiny drain through
``Fleet(devices="all")``, then the same with the exchange between
devices left out of every megabatch slab.  Prints one JSON line.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python3 bench/tests/bench_fault_4dev.py DIR
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.tests import bench_tiny  # noqa: E402
from repro.fleet import scheduler, sharded  # noqa: E402

# 16 jobs = one slab of 4 devices x batch 4 (shard_map); 4 jobs go to
# the per-device lanes
MIX = {"driver": "drain",
       "jobs": [["matmul_dot_16", 16], ["fft_32", 16], ["bitonic_32", 16],
                ["reduction_32", 4], ["transpose_16", 4]]}


def counted():
    seen = {"mesh_jobs": 0, "lane_jobs": 0}
    mega = sharded.ShardedFleetScheduler._run_megabatch
    lane = scheduler.FleetScheduler._run_compiled_unit

    def run_mega(self, cp, chunk, results):
        seen["mesh_jobs"] += len(chunk)
        return mega(self, cp, chunk, results)

    def run_lane(self, cp, chunk, results):
        if self.device is not None:
            seen["lane_jobs"] += len(chunk)
        return lane(self, cp, chunk, results)

    sharded.ShardedFleetScheduler._run_megabatch = run_mega
    scheduler.FleetScheduler._run_compiled_unit = run_lane
    return seen


def leave_out_exchange():
    real = sharded.ShardedFleetScheduler._mega_exec

    def mega_exec(self, cp, shared, tdx):
        exe, compile_s = real(self, cp, shared, tdx)
        b = self.batch_size

        def first_device_only(sh, td):
            out, cycles, halted = exe(sh, td)
            kept = jnp.concatenate([jax.device_get(out)[:b],
                                    jax.device_get(sh)[b:]])
            return jnp.asarray(kept), cycles, halted
        return first_device_only, compile_s

    sharded.ShardedFleetScheduler._mega_exec = mega_exec


def main() -> int:
    assert len(jax.devices()) == 4, jax.devices()
    root = bench_tiny.write_spec(pathlib.Path(sys.argv[1]))
    seen = counted()
    sound = bench_tiny.run("drain", root, chips=4, mix=MIX)
    leave_out_exchange()
    broken = bench_tiny.run("drain", root, chips=4, mix=MIX)
    print(json.dumps({"sound": {"correct": sound["correct"], **seen},
                      "exchange": {"correct": broken["correct"],
                                   "checks": broken["checks"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
