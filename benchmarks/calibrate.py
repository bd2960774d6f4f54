"""Per-backend tier-policy calibration: measure, fit, register.

The :class:`~repro.core.blockc.TierPolicy` threshold tables in
``blockc._TIER_TABLES`` ship as *priors* — the CPU table is measured,
the gpu/tpu tables are educated guesses about where the blocks ->
superblock crossover moves when dispatch cost and fixed overhead
change.  This tool replaces the prior for the backend it actually runs
on:

1. run the existing crossover sweep
   (:func:`benchmarks.superblock.bench_auto_tier` — blocks vs
   superblock over LOOP back-edge counts, light path, bit-identity
   asserted at every point) on ``jax.default_backend()``;
2. **fit** ``min_backedge_dispatches`` to the measured crossover: the
   switch-dispatch count of the first sweep point from which the
   superblock tier stays faster, and scale the companion thresholds
   (``min_trace_fusion``, ``min_fori_execd``) by the same ratio so the
   fusion/fori entry points track the dispatch economics;
3. write the fitted table (with the sweep evidence) to
   ``BENCH_tier_policy.json``, and with ``--apply`` install it via
   :func:`~repro.core.blockc.register_backend_table` so every
   device-pinned scheduler (``FleetScheduler(device=...)``,
   ``ShardedFleetScheduler``, ``FleetService(devices=...)``) picks it
   up through :func:`~repro.core.blockc.default_policy_for_device`.

    PYTHONPATH=src python -m benchmarks.calibrate --smoke
    PYTHONPATH=src python -m benchmarks.calibrate --apply
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

from repro.fleet import enable_compile_cache  # noqa: E402

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fit_table(auto: dict) -> dict:
    """Fit per-backend TierPolicy thresholds from a ``bench_auto_tier``
    result.  Returns only the thresholds that differ from the module
    defaults (an empty dict = the defaults are already right)."""
    from repro.core.blockc import _TIER_DEFAULTS

    sweep = auto.get("sweep", [])
    crossover = auto.get("crossover_backedges")
    if crossover is None or not sweep:
        return {}
    cross_rows = [r for r in sweep if r["backedges"] == crossover]
    if not cross_rows:
        return {}
    # the measured economics: a plan saving this many switch dispatches
    # is where the superblock tier starts winning on this backend
    fitted = max(2, int(cross_rows[0]["dispatches"]))
    default = int(_TIER_DEFAULTS["min_backedge_dispatches"])
    table: dict[str, int] = {}
    if fitted != default:
        table["min_backedge_dispatches"] = fitted
        # the fusion/fori entries exist to catch programs that amortize
        # the same fixed overhead through trace length or loop body
        # instead of dispatch count — scale them by the same measured
        # ratio so all three entry points describe one cost model
        ratio = fitted / default
        table["min_trace_fusion"] = max(
            32, int(round(_TIER_DEFAULTS["min_trace_fusion"] * ratio)))
        table["min_fori_execd"] = max(
            512, int(round(_TIER_DEFAULTS["min_fori_execd"] * ratio)))
    return table


def calibrate(smoke: bool = False, repeats: int = 5) -> dict:
    """Run the sweep on the current backend and fit its table."""
    import jax

    from benchmarks.superblock import bench_auto_tier, fleet_config

    backend = jax.default_backend()
    auto = bench_auto_tier(fleet_config(), smoke, repeats)
    table = fit_table(auto)
    return {
        "backend": backend,
        "devices": [str(d) for d in jax.devices()],
        "smoke": smoke,
        "fitted": table,
        "crossover_backedges": auto.get("crossover_backedges"),
        "blocks_fixed_us": auto.get("blocks_fixed_us"),
        "super_fixed_us": auto.get("super_fixed_us"),
        "sweep": [{k: r[k] for k in
                   ("backedges", "dispatches", "blocks_us", "super_us",
                    "faster_tier")}
                  for r in auto.get("sweep", [])],
    }


def apply_table(doc: dict) -> None:
    """Install the fitted table and verify the policy path sees it."""
    from repro.core.blockc import (register_backend_table,
                                   tier_policy_for_backend)

    backend, table = doc["backend"], doc["fitted"]
    register_backend_table(backend, **table)
    policy = tier_policy_for_backend(backend)
    for k, v in table.items():
        assert policy.table[k] == v, (k, v, policy.table[k])
    print(f"# registered {backend} table: "
          f"{table or 'module defaults (fit matched)'}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="two sweep points only (CI)")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--apply", action="store_true",
                    help="register the fitted table in-process and "
                         "verify default_policy_for_device pickup")
    ap.add_argument("--json", default=os.path.join(
        _REPO_ROOT, "BENCH_tier_policy.json"))
    args = ap.parse_args()
    enable_compile_cache()

    doc = calibrate(smoke=args.smoke, repeats=args.repeats)
    print(f"backend={doc['backend']} "
          f"crossover_backedges={doc['crossover_backedges']} "
          f"fitted={doc['fitted'] or '(defaults)'}")
    if args.apply:
        apply_table(doc)
    if not args.smoke:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
