"""Shared benchmark plumbing: grid-fused simulation runs, a cell cache, rows.

Every benchmark module exposes ``run(quick: bool) -> list[dict]``; each row
must carry ``name``, ``us_per_call`` and ``derived`` (the CSV contract of
``benchmarks/run.py``) plus any extra columns for the extended report.

Simulation sweeps go through :func:`timed_simulate_grid`: the caller hands
over its *entire* figure grid as a list of ``SimConfig`` cells; the helper
groups cells by their static part (shapes + kinds) and runs each group as
**one compiled program** via ``slotted_sim.simulate_grid`` -- one jit,
vmapped over the flattened (cell x seed) axis, shard_map-sharded across
local devices.  Compile count per figure is therefore O(#static groups),
not O(#cells).

Results are cached per ``(seed, SimConfig)`` cell because several paper
tables slice the same runs (e.g. the Fig 6 communication sweep and the
Thm 2.3 verification reuse identical (comm, approx, x) cells);
:func:`timed_simulate` and :func:`timed_simulate_batch` serve from the
same cache.
"""
from __future__ import annotations

import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.care import slotted_sim
from repro.serve import engine as serve_engine

# (seed, SimConfig) -> (SimResult, attributed wall seconds)
_CELL_CACHE: dict = {}

# (seed, ServeConfig) -> (ServeResult, attributed wall seconds)
_SERVE_CACHE: dict = {}

DEFAULT_SLOTS = 100_000
QUICK_SLOTS = 20_000

# The paper's simulation setting (Section 9.1).
SERVERS = 30
LOADS = (0.5, 0.8, 0.95)


def sim_slots(quick: bool) -> int:
    return QUICK_SLOTS if quick else DEFAULT_SLOTS


def timed_simulate_grid(
    cfgs: Sequence[slotted_sim.SimConfig], seeds: Sequence[int]
):
    """Run a figure grid fused: one ``simulate_grid`` call per static group.

    Returns ``(results, walls)`` aligned with ``cfgs``: ``results[i]`` is
    the list of per-seed :class:`SimResult` for cell ``i`` and ``walls[i]``
    its attributed wall time (a group's wall is split evenly across its
    cells).  Cells already in the cache are served from it and charged
    their original attributed wall time.
    """
    seeds = tuple(int(s) for s in seeds)
    pending: dict = {}  # StaticConfig -> {cfg: None} (ordered, deduped)
    for cfg in cfgs:
        if any((s, cfg) not in _CELL_CACHE for s in seeds):
            pending.setdefault(cfg.static_part(), {})[cfg] = None
    for static, group in pending.items():
        group_cfgs = list(group)
        t0 = time.perf_counter()
        grid = slotted_sim.simulate_grid(
            list(seeds), static, [c.scenario() for c in group_cfgs]
        )
        wall = time.perf_counter() - t0
        per_seed = wall / (len(group_cfgs) * len(seeds))
        for cfg, cell in zip(group_cfgs, grid):
            for s, r in zip(seeds, cell):
                _CELL_CACHE[(s, cfg)] = (r, per_seed)
    results, walls = [], []
    for cfg in cfgs:
        cached = [_CELL_CACHE[(s, cfg)] for s in seeds]
        results.append([r for r, _ in cached])
        walls.append(sum(w for _, w in cached))
    return results, walls


def percell_reference(
    cfgs: Sequence[slotted_sim.SimConfig], seeds: Sequence[int]
):
    """The pre-grid behaviour: one fresh compiled program per cell.

    Mirrors the old ``simulate_batch`` -- a vmapped scan per ``SimConfig``,
    sharded over local devices only when the seed count divides them (the
    old ``pmap`` condition) -- built fresh per cell so every cell pays its
    own compile.  The cell's scenario enters as a traced argument, as in
    the grid: baked in as a compile-time constant it lets XLA rewrite the
    arithmetic on it (a division by the constant geometric ``log1p`` turns
    into a multiplication), which moves the odd service size across a
    floor boundary.  Cells sharing a ``static_part()`` replay the same
    workload stream as the fused grid, so results are comparable bit for
    bit; benchmarks use this as the golden reference the fused path must
    reproduce (``grid_matches_percell`` rows).
    """
    keys = slotted_sim._as_keys(list(seeds))
    n_dev = jax.local_device_count()
    if len(seeds) % n_dev != 0:
        n_dev = 1
    results = []
    for cfg in cfgs:
        static = cfg.static_part()
        scn = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (len(seeds),) + jnp.shape(a)),
            cfg.scenario(),
        )
        batched = jax.vmap(
            lambda key, s: slotted_sim._run_one(key, s, static)
        )
        out = jax.jit(slotted_sim.shard_runs(batched, n_dev, 2))(keys, scn)
        out_np = [np.asarray(o) for o in out]
        results.append(
            [
                slotted_sim._finalize(
                    out_np[0][i], tuple(o[i] for o in out_np[1:]),
                    slotted_sim._extra_outputs(static),
                )
                for i in range(len(seeds))
            ]
        )
    return results


def grids_match(grid_results, percell_results) -> bool:
    """Bitwise per-cell equality of two result grids (messages, AQ, JCT)."""
    return all(
        g.messages == p.messages
        and g.max_aq == p.max_aq
        and np.array_equal(g.jct, p.jct)
        for grow, prow in zip(grid_results, percell_results)
        for g, p in zip(grow, prow)
    )


def timed_serve_grid(
    cells: Sequence[serve_engine.ServeConfig], seeds: Sequence[int]
):
    """Run a serving grid fused: one ``serve_grid`` call per static group.

    The serving analogue of :func:`timed_simulate_grid`: cells are grouped
    by their :meth:`~repro.serve.engine.ServeConfig.static_part` (shapes +
    comm kind; trigger thresholds are traced operands) and each group runs
    as one compiled program -- vmap over (cell x seed), shard_map across
    local devices.  Returns ``(results, walls)`` aligned with ``cells``
    (``results[i]`` is the per-seed list of ``ServeResult``); cached cells
    are served from ``_SERVE_CACHE`` at their original attributed wall.
    """
    seeds = tuple(int(s) for s in seeds)
    pending: dict = {}  # EngineStatic -> {cell: None} (ordered, deduped)
    for cell in cells:
        if any((s, cell) not in _SERVE_CACHE for s in seeds):
            pending.setdefault(cell.static_part(), {})[cell] = None
    for static, group in pending.items():
        group_cells = list(group)
        t0 = time.perf_counter()
        grid = serve_engine.serve_grid(list(seeds), static, group_cells)
        wall = time.perf_counter() - t0
        per_run = wall / (len(group_cells) * len(seeds))
        for cell, row in zip(group_cells, grid):
            for s, r in zip(seeds, row):
                _SERVE_CACHE[(s, cell)] = (r, per_run)
    results, walls = [], []
    for cell in cells:
        cached = [_SERVE_CACHE[(s, cell)] for s in seeds]
        results.append([r for r, _ in cached])
        walls.append(sum(w for _, w in cached))
    return results, walls


def serve_reference(cell: serve_engine.ServeConfig, seed: int) -> dict:
    """One numpy-reference serving run on the cell's shared workload.

    The pre-refactor execution model (a Python per-slot loop) and the
    golden the fused grid must reproduce bit for bit; benchmarks time it
    to build the sequential cost model behind ``serve/grid_speedup``.
    """
    return serve_engine.run_serving_sim(
        cell.engine_config(), slots=cell.slots, load=cell.load,
        mean_prefill=cell.mean_prefill, mean_decode=cell.mean_decode,
        seed=seed, workload=serve_engine.workload_for(cell, seed),
    )


def serve_matches_reference(
    result: serve_engine.ServeResult, ref: dict
) -> bool:
    """Bitwise equality of a fused-grid run and the numpy reference."""
    return (
        result.messages == ref["messages"]
        and result.completed == ref["completed"]
        and np.array_equal(result.jct_by_rid, ref["jct_by_rid"])
        and np.array_equal(result.final_occupancy, ref["final_occupancy"])
    )


def timed_simulate(seed: int, cfg: slotted_sim.SimConfig):
    """simulate() with wall-time capture and (seed, cfg) memoisation.

    Returns (SimResult, wall_seconds).  Cached calls return the original
    (attributed) wall time so ``us_per_call`` stays meaningful.
    """
    key = (int(seed), cfg)
    if key not in _CELL_CACHE:
        t0 = time.perf_counter()
        res = slotted_sim.simulate(jax.random.key(seed), cfg)
        _CELL_CACHE[key] = (res, time.perf_counter() - t0)
    return _CELL_CACHE[key]


def timed_simulate_batch(seeds: Sequence[int], cfg: slotted_sim.SimConfig):
    """simulate_batch() with wall-time capture and per-cell memoisation.

    Returns (list[SimResult], wall_seconds) -- one result per seed; the
    one-cell special case of :func:`timed_simulate_grid`.
    """
    results, walls = timed_simulate_grid([cfg], seeds)
    return results[0], walls[0]


def timed(fn, *args, **kw):
    """``(fn(*args), wall_s)`` with the clock stopped only after every
    array in the returned pytree is materialised.

    The single honest-wall primitive: timing a bare jitted call measures
    dispatch, not execution (JAX is async -- on CPU too), so every
    benchmark that hands back device values must stop the clock behind
    ``jax.block_until_ready`` over the *returned pytree*.  Host-side
    returns (lists, floats, numpy) pass through unchanged.
    """
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def row(name: str, wall_s: float, slots: int, derived: str, **extra) -> dict:
    """One CSV row; us_per_call is wall microseconds per simulated slot."""
    return {
        "name": name,
        "us_per_call": round(1e6 * wall_s / max(slots, 1), 3),
        "derived": derived,
        **extra,
    }


def fmt_derived(**kv) -> str:
    parts = []
    for k, v in kv.items():
        if isinstance(v, float):
            parts.append(f"{k}={v:.4g}")
        else:
            parts.append(f"{k}={v}")
    return ";".join(parts)
