"""Benchmark harness: one module per paper table/figure (deliverable d).

Usage:
  PYTHONPATH=src python -m benchmarks.run                 # all benchmarks
  PYTHONPATH=src python -m benchmarks.run --only jct      # substring filter
  PYTHONPATH=src python -m benchmarks.run --only jct,ssc  # several filters
  PYTHONPATH=src python -m benchmarks.run --quick         # reduced sizes
  PYTHONPATH=src python -m benchmarks.run --json out.json # structured output

Trajectories recorded with ``--json`` (CI uploads one as a ``BENCH_*``
artifact per PR) are compared mechanically with ``benchmarks/diff.py``,
which fails on metric regressions beyond tolerance.

Prints ``name,us_per_call,derived`` CSV rows to stdout.  With ``--json`` the
same rows (plus any extra per-row columns the modules attach, e.g.
``mean_jct`` / ``rel_comm`` / ``speedup``) are also written as a JSON list of
records -- one object per row with at least ``name``, ``us_per_call`` and
``derived`` -- so ``BENCH_*.json`` trajectories can be recorded across PRs
and diffed mechanically.

Simulation-backed benchmarks submit their *entire figure grid* through
``benchmarks.common.timed_simulate_grid``, which groups cells by their
static part and runs each group as one compiled program
(``slotted_sim.simulate_grid``: scenario knobs are traced operands, the
flattened cell x seed axis is shard_map-sharded across local devices; see
the ``grid/compile_count`` / ``grid/speedup`` rows in quick mode).  The
scenario knobs go beyond the paper's setting: bursty MMPP arrivals
(``arrival="mmpp"``, ``burst_intensity``, ``burst_stay``), heterogeneous
per-server service rates (``service_rates``, with drain-time-aware JSAQ
via ``rate_aware``), and the hybrid ``comm="et_rt"`` trigger (ET-x with an
RT staleness cap).

The mapping to paper artifacts:

  bench_comm_vs_error   -> Fig 2 / Fig 6 / Fig 7  (+ Thm 2.3/2.5 bounds)
  bench_jct_ccdf        -> Fig 3 / Figs 8-12       (JCT vs comm budget
                           + bursty / heterogeneous scenario rows)
  bench_table5          -> Fig 5                    (communication rates)
  bench_approx_quality  -> Thm 2.3 sweep            (AQ<=x-1, M<=D/x)
  bench_ssc             -> Sec 7 / Thm 7.3          (finite-n SSC trend;
                           fused via the traced service/horizon axis)
  bench_heavy_tail      -> beyond-paper: ET-x under Pareto job sizes
  bench_moe_balance     -> beyond-paper: CARE balancer in MoE training
  bench_serving         -> beyond-paper: CARE dispatch in serving
  bench_stream          -> beyond-paper: streaming segment engine
                           (pipelined chunk throughput / overlap /
                           steady-state JCT / bounded-memory soak)
  bench_faults          -> beyond-paper: degraded networks + server faults
  bench_pull            -> beyond-paper: pull policies (JIQ / hyper-
                           scalable JSQ) vs CARE push on one frontier
  bench_retrans         -> beyond-paper: reliable (ack'd) control-plane
                           transport vs fire-and-forget under loss
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

# Expose the host's cores as separate XLA CPU devices so simulate_batch can
# shard seed sweeps across them (shard_map); the slotted scan fuses into a
# compute-bound single-core loop, so device-level parallelism is the only
# CPU lever.  Set before any jax import; respects an operator-provided
# XLA_FLAGS.
if "jax" not in sys.modules and "XLA_FLAGS" not in os.environ:
    _n_dev = min(os.cpu_count() or 1, 8)
    if _n_dev > 1:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={_n_dev}"
        )

from repro.compile_cache import use_compile_cache  # noqa: E402

BENCHES = [
    "bench_comm_vs_error",
    "bench_jct_ccdf",
    "bench_table5",
    "bench_approx_quality",
    "bench_ssc",
    "bench_heavy_tail",
    "bench_moe_balance",
    "bench_serving",
    "bench_stream",
    "bench_route",
    "bench_faults",
    "bench_pull",
    "bench_retrans",
]


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return str(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default="",
        help="comma-separated substring filter(s) on module names",
    )
    ap.add_argument("--quick", action="store_true", help="reduced problem sizes")
    ap.add_argument(
        "--json",
        default="",
        metavar="OUT",
        help="also write all rows as a JSON list of records to this path",
    )
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.json:
        # Fail fast on an unwritable path rather than at the end of a run.
        open(args.json, "w").close()

    print("name,us_per_call,derived")
    failures = 0
    records: list[dict] = []
    only = [s for s in args.only.split(",") if s]
    for mod_name in BENCHES:
        if only and not any(s in mod_name for s in only):
            continue
        mod = importlib.import_module(f"benchmarks.{mod_name}")
        t0 = time.perf_counter()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # noqa: BLE001 -- keep the harness running
            failures += 1
            print(f"{mod_name}/ERROR,0,{type(e).__name__}: {e}")
            records.append(
                {
                    "name": f"{mod_name}/ERROR",
                    "us_per_call": 0,
                    "derived": f"{type(e).__name__}: {e}",
                }
            )
            continue
        wall = time.perf_counter() - t0
        for r in rows:
            print(f"{r['name']},{r['us_per_call']},{r['derived']}")
            records.append({k: _jsonable(v) for k, v in r.items()})
        print(
            f"{mod_name}/total,{round(wall * 1e6, 1)},rows={len(rows)}",
            flush=True,
        )
        records.append(
            {
                "name": f"{mod_name}/total",
                "us_per_call": round(wall * 1e6, 1),
                "derived": f"rows={len(rows)}",
            }
        )
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {len(records)} records to {args.json}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
