#!/usr/bin/env python3
"""Smoke-run the balancer's main paths on a TPU, through the entry points.

    python chip_smoke.py            # the four phases below, one chip
    python chip_smoke.py --chips 4  # only the sharded grids, over 4 chips

One process drives the chip; it starts no children.  The phases, in order:

1. ``paper_grid`` -- ``simulate_grid`` at the paper's Section 9.1 setting:
   K=30 servers, 100,000 slots, loads 0.5/0.8/0.95, JSAQ with ET-x MSR
   for x in {2,3,4,5}, 8 seeds (96 runs).  Its fit is checked with
   ``memory_analysis()`` before the run (seeds are halved until it fits,
   and the record says so).  Every run must equal ``simulate`` of that
   (key, cell) bit for bit, conserve jobs (admitted = departed + final
   queues) and keep AQ <= x-1 (Theorem 2.3).  A few runs are replayed on
   the host's CPU backend; the agreement is reported either way, with the
   first workload stage that differs.
2. ``serving_grid`` -- ``serve_grid`` at 1024 replicas x 16 decode slots,
   load 0.9, an exact / ET-4 / DT-4 / RT-16 ladder, 2 seeds, 2048 slots;
   one (cell, seed) must equal the numpy reference dispatcher bit for bit.
3. ``stream`` -- ``serve_stream`` at the same width, 4 chunks of 4096
   slots; must equal ``serve_one`` on the assembled trace.
4. ``kernels`` -- ``route_backend="pallas"`` on both tiers (slotted
   mean-field cells at K = 10^3 / 10^4 / 10^5, serving at R = 1024): the
   program must hold the compiled kernel (``tpu_custom_call``; on the CPU
   it must not, being interpreted) and match the dense backend bit for
   bit under deterministic ties.

With ``--chips 4`` only ``sharded`` runs: ``simulate_grid`` and
``serve_grid`` on a ragged 3-cell x 5-seed grid, sharded over every chip
and unsharded on one, which must agree bit for bit.

Each phase prints one JSON line: its sizes, ``wall_s`` of its entry-point
calls (host clock; they return host arrays, so the device work is done),
``compile_s`` (XLA compile or compile-cache load inside those calls) and
``trace_s`` (tracing and lowering), as JAX reports them; the same fields
prefixed ``aot_`` for the ahead-of-time compiles that check memory and
HLO, ``ref_`` for the reference runs and ``cpu_`` for the CPU replay; and
its ``checks``.
The last line is ``{"ok": ..., "device": {"platform", "kind", "count"}}``.
A failed check or phase exits 1; a platform other than TPU exits 2 before
any phase, printing nothing on stdout.  Nothing falls back to the CPU.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.care import slotted_sim  # noqa: E402
from repro.serve import engine  # noqa: E402

# JAX's own duration events: XLA compile (or persistent-cache load), and
# tracing plus lowering to StableHLO (nested jits count once per level).
_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_s",
}


@contextlib.contextmanager
def _stopwatch(rec: dict, prefix: str = ""):
    """Add the block's ``wall_s``, ``compile_s`` and ``trace_s`` to
    ``rec`` under ``prefix``."""
    spent = {"compile_s": 0.0, "trace_s": 0.0}

    def listen(event: str, secs: float, **_) -> None:
        if event in _EVENTS:
            spent[_EVENTS[event]] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    spent["wall_s"] = time.perf_counter() - t0
    for k, v in spent.items():
        rec[prefix + k] = rec.get(prefix + k, 0.0) + v


def _same(a, b, fields=None) -> bool:
    """Bitwise equality of two result dataclasses (all fields by default)."""
    names = fields or [f.name for f in dataclasses.fields(a)]
    return all(
        np.array_equal(np.asarray(getattr(a, n)), np.asarray(getattr(b, n)))
        for n in names
    )


def _device_bytes():
    """HBM the first device offers, or None where the backend won't say."""
    stats = jax.devices()[0].memory_stats()
    return stats.get("bytes_limit") if stats else None


def _program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes + m.generated_code_size_in_bytes
        - m.alias_size_in_bytes
    )


def _has_kernel(fn, args) -> bool:
    """Whether the compiled program holds a Mosaic kernel call."""
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# 1. The paper's grid (slotted tier).
# ---------------------------------------------------------------------------


def _workload_stages(seed: int, cell: slotted_sim.SimConfig) -> dict:
    """The slotted workload draws of one run, op by op (host arrays).

    The service-size uniform is redrawn as ``workload.service_sizes``
    draws it, unscaled and scaled, so a size mismatch is pinned to the
    bits-to-float step, the scaling into (1e-7, 1 - 1e-7), or the
    geometric transform.
    """
    static = cell.static_part()
    key = jax.random.key(seed)
    arrive, sizes, slot_keys = slotted_sim._prep(
        key, static, cell.scenario()
    )[:3]
    _, k_size, _ = jax.random.split(key, 3)
    n = static.slots
    return {
        "arrival draw (uniform < load)": np.asarray(arrive),
        "service-size uniform, [0, 1) from bits": np.asarray(
            jax.random.uniform(k_size, (n,), jnp.float32)
        ),
        "service-size uniform, scaled u * (max - min) + min": np.asarray(
            jax.random.uniform(k_size, (n,), jnp.float32, 1e-7, 1.0 - 1e-7)
        ),
        "service sizes, floor(log1p(-u) / log1p(-1/mean)) + 1": np.asarray(
            sizes
        ),
        "per-slot keys": np.asarray(jax.random.key_data(slot_keys)),
    }


def _first_divergence(seed: int, cell: slotted_sim.SimConfig) -> str:
    """Name the first workload stage where the chip and the CPU differ."""
    chip = _workload_stages(seed, cell)
    with jax.default_device(jax.devices("cpu")[0]):
        host = _workload_stages(seed, cell)
    for stage, a in chip.items():
        b = host[stage]
        if not np.array_equal(a, b):
            i = int(np.argwhere(a != b)[0][0])
            return f"{stage} at slot {i}: chip {a[i]!r} vs cpu {b[i]!r}"
    return "scan body (every workload draw agrees)"


def _paper_cells(slots, loads, xs):
    return [
        slotted_sim.SimConfig(
            servers=30, slots=slots, load=load, policy="jsaq", comm="et",
            x=x, approx="msr",
        )
        for load in loads
        for x in xs
    ]


def paper_grid(*, slots=100_000, seeds=8, loads=(0.5, 0.8, 0.95),
               xs=(2, 3, 4, 5), cpu_runs=3) -> dict:
    cells = _paper_cells(slots, loads, xs)
    static = cells[0].static_part()
    scns = [c.scenario() for c in cells]
    rec = {"phase": "paper_grid", "servers": 30, "slots": slots,
           "cells": len(cells)}

    seed_list, limit = list(range(seeds)), _device_bytes()
    while True:
        fn, args, _ = slotted_sim.grid_program(seed_list, static, scns)
        with _stopwatch(rec, "aot_"):
            need = _program_bytes(fn.lower(*args).compile())
        if limit is None or need <= limit or len(seed_list) == 1:
            break
        seed_list = seed_list[: len(seed_list) // 2]
    rec.update(seeds=len(seed_list), runs=len(cells) * len(seed_list),
               seeds_cut_to_fit=len(seed_list) < seeds,
               program_bytes=need, device_bytes=limit)

    with _stopwatch(rec):
        grid = slotted_sim.simulate_grid(seed_list, static, scns)

    flat = [(cell, seed, grid[i][j]) for i, cell in enumerate(cells)
            for j, seed in enumerate(seed_list)]
    with _stopwatch(rec, "ref_"):
        per_run = all(
            _same(r, slotted_sim.simulate(jax.random.key(seed), cell))
            for cell, seed, r in flat
        )
    rec["checks"] = {
        "fits_device_memory": limit is None or need <= limit,
        "grid_equals_simulate": per_run,
        "conservation": all(
            r.arrivals == r.departures + int(np.sum(r.final_q))
            for _, _, r in flat
        ),
        "aq_bound_thm_2_3": all(r.max_aq <= cell.x - 1 for cell, _, r in flat),
    }

    # Chip vs the host's CPU backend, same process: reported, not gated.
    picks = [flat[i * len(flat) // cpu_runs] for i in range(cpu_runs)]
    agree, first = True, None
    with _stopwatch(rec, "cpu_"):
        for cell, seed, r in picks:
            with jax.default_device(jax.devices("cpu")[0]):
                host = slotted_sim.simulate(jax.random.key(seed), cell)
            if not _same(r, host):
                agree = False
                first = first or (
                    f"load={cell.load} x={cell.x} seed={seed}: "
                    + _first_divergence(seed, cell)
                )
    rec["cpu_agreement"] = {"runs": len(picks), "bitwise": agree,
                            "first_divergence": first}
    return rec


# ---------------------------------------------------------------------------
# 2. The serving grid.
# ---------------------------------------------------------------------------

# The serve/replicas1024 cell of bench_serving: decode-heavy requests whose
# MSR drain emulates the nominal completion rate (16 / 64 per slot).
_WORK = dict(mean_prefill=4, mean_decode=60, msr_drain=0.25)

LADDER = {
    "exact": dict(comm="exact"),
    "et4": dict(comm="et", x=4),
    "dt4": dict(comm="dt", x=4),
    "rt16": dict(comm="rt", rt_period=16),
}


def _serve_cell(replicas, decode_slots, slots, load=0.9, **kw):
    return engine.ServeConfig(
        replicas=replicas, decode_slots=decode_slots, slots=slots,
        load=load, queue_cap=128, **_WORK, **kw,
    )


def serving_grid(*, replicas=1024, decode_slots=16, slots=2048,
                 seeds=2) -> dict:
    cells = {name: _serve_cell(replicas, decode_slots, slots, **kw)
             for name, kw in LADDER.items()}
    rec = {"phase": "serving_grid", "replicas": replicas,
           "decode_slots": decode_slots, "slots": slots, "seeds": seeds,
           "ladder": list(cells)}
    results = {}
    with _stopwatch(rec):
        for name, cell in cells.items():
            results[name] = engine.serve_grid(
                list(range(seeds)), cell.static_part(), [cell]
            )[0]
    rec["lanes"] = int(
        max(engine.workload_for(cells["et4"], s).n_arr.max()
            for s in range(seeds))
    )
    with _stopwatch(rec, "ref_"):
        ref = common.serve_reference(cells["et4"], 0)
    rec["completed"] = {n: [r.completed for r in rs]
                        for n, rs in results.items()}
    rec["checks"] = {
        "et4_seed0_equals_numpy_reference": common.serve_matches_reference(
            results["et4"][0], ref
        ),
        "no_drops": all(r.dropped == 0 for rs in results.values()
                        for r in rs),
    }
    return rec


# ---------------------------------------------------------------------------
# 3. The stream engine.
# ---------------------------------------------------------------------------


def stream(*, replicas=1024, decode_slots=16, chunk=4096, chunks=4,
           seed=0) -> dict:
    cell = _serve_cell(replicas, decode_slots, chunk * chunks, comm="et",
                       x=4)
    rec = {"phase": "stream", "replicas": replicas,
           "decode_slots": decode_slots, "chunk": chunk, "chunks": chunks}
    with _stopwatch(rec):
        res = engine.serve_stream(seed, cell, chunk=chunk)
    with _stopwatch(rec, "ref_"):
        wl = engine.StreamSampler(
            seed, engine.StreamParams.for_cell(cell)
        ).full(cell.slots)
        one = engine.serve_one(seed, cell, workload=wl)
    rec["offered"], rec["completed"] = res.offered, res.completed
    rec["checks"] = {
        "stream_equals_serve_one": (
            res.completed == one.completed
            and res.messages == one.messages
            and res.dropped == one.dropped
            and res.net_drops == one.net_drops
            and res.offered == one.offered
            and np.array_equal(res.final_occupancy, one.final_occupancy)
        ),
        "accumulators_count_every_completion": res.count == one.completed,
    }
    return rec


# ---------------------------------------------------------------------------
# 4. The Pallas route backends.
# ---------------------------------------------------------------------------

# Counters the mean-field kernel reproduces (it keeps no per-job ring, so
# no JCT).
_KERNEL_FIELDS = ("arrivals", "departures", "messages", "max_aq",
                  "max_queue", "queue_gap_sup", "dropped",
                  "per_server_arrivals", "final_q")


def _mean_field_cell(servers, slots, backend):
    # bench_route's cell: deterministic service, DT-3, lowest-index ties.
    return slotted_sim.SimConfig(
        servers=servers, slots=slots, load=0.95, mean_service=8,
        policy="jsaq", comm="dt", x=3, approx="msr",
        service="deterministic", buffer_cap=16, deterministic_ties=True,
        route_backend=backend,
    )


def kernels(*, servers=(1_000, 10_000, 100_000), slots=4_000,
            replicas=1024, decode_slots=16, serve_slots=2048) -> dict:
    on_tpu = jax.default_backend() == "tpu"
    rec = {"phase": "kernels", "servers": list(servers), "slots": slots,
           "replicas": replicas, "decode_slots": decode_slots,
           "serve_slots": serve_slots}
    checks = {}
    for k in servers:
        run = {}
        for backend in ("pallas", "dense"):
            cell = _mean_field_cell(k, slots, backend)
            args = ([7], cell.static_part(), [cell.scenario()])
            if backend == "pallas":
                with _stopwatch(rec, "aot_"):
                    kernel = _has_kernel(*slotted_sim.grid_program(*args)[:2])
                checks[f"slotted_k{k}_kernel_compiled"] = kernel == on_tpu
            with _stopwatch(rec, "" if backend == "pallas" else "ref_"):
                run[backend] = slotted_sim.simulate_grid(*args)[0][0]
        checks[f"slotted_k{k}_equals_dense"] = _same(
            run["pallas"], run["dense"], _KERNEL_FIELDS
        )

    run = {}
    for backend in ("pallas", "dense"):
        cell = _serve_cell(replicas, decode_slots, serve_slots, comm="et",
                           x=4, deterministic_ties=True,
                           route_backend=backend)
        args = ([0], cell.static_part(), [cell])
        if backend == "pallas":
            with _stopwatch(rec, "aot_"):
                kernel = _has_kernel(*engine.serve_grid_program(*args)[:2])
            checks[f"serving_r{replicas}_kernel_compiled"] = kernel == on_tpu
        with _stopwatch(rec, "" if backend == "pallas" else "ref_"):
            run[backend] = engine.serve_grid(*args)[0][0]
    checks[f"serving_r{replicas}_equals_dense"] = _same(
        run["pallas"], run["dense"],
        ("jct_by_rid", "completed", "messages", "dropped", "final_occupancy"),
    )
    rec["checks"] = checks
    return rec


# ---------------------------------------------------------------------------
# Four chips: the sharded run axis.
# ---------------------------------------------------------------------------


def sharded(*, slots=20_000, seeds=5, replicas=1024, decode_slots=16,
            serve_slots=1024) -> dict:
    rec = {"phase": "sharded", "devices": jax.local_device_count(),
           "cells": 3, "seeds": seeds, "slots": slots, "replicas": replicas,
           "serve_slots": serve_slots}
    seed_list = list(range(seeds))
    cells = [slotted_sim.SimConfig(slots=slots, load=load, x=3)
             for load in (0.5, 0.8, 0.95)]
    static, scns = cells[0].static_part(), [c.scenario() for c in cells]
    with _stopwatch(rec):
        split = slotted_sim.simulate_grid(seed_list, static, scns)
    with _stopwatch(rec, "ref_"):
        one = slotted_sim.simulate_grid(seed_list, static, scns, shard=False)
    slotted_ok = all(_same(a, b) for ra, rb in zip(split, one)
                     for a, b in zip(ra, rb))

    cells = [_serve_cell(replicas, decode_slots, serve_slots, comm="et", x=x)
             for x in (2, 3, 4)]
    with _stopwatch(rec):
        split = engine.serve_grid(seed_list, cells[0].static_part(), cells)
    with _stopwatch(rec, "ref_"):
        one = engine.serve_grid(seed_list, cells[0].static_part(), cells,
                                shard=False)
    serving_ok = all(
        _same(a, b, ("jct_by_rid", "completed", "messages", "dropped",
                     "final_occupancy"))
        for ra, rb in zip(split, one) for a, b in zip(ra, rb)
    )
    rec["checks"] = {"slotted_sharded_equals_one_device": slotted_ok,
                     "serving_sharded_equals_one_device": serving_ok}
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded grids, over four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(json.dumps({"phase": "setup", "device": device,
                      "jax": jax.__version__,
                      "compile_cache": use_compile_cache()}), flush=True)

    phases = (sharded,) if args.chips == 4 else (
        paper_grid, serving_grid, stream, kernels
    )
    ok = True
    for phase in phases:
        try:
            rec = phase()
        except Exception as e:  # noqa: BLE001 -- report and go on
            traceback.print_exc()
            rec = {"phase": phase.__name__, "error": repr(e), "checks": {}}
        ok = ok and bool(rec["checks"]) and all(rec["checks"].values())
        print(json.dumps(rec), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
