"""Driver ``slotted_grid``: whole figure grids through ``simulate_grid``.

One call is one grid as a user sweeps it: every (load, x) point of the
traffic file times ``seeds_per_point`` fresh seeds, each run ``slots``
slots long, as one ``slotted_sim.simulate_grid`` call that returns host
results.  Its work is runs x slots (run-slots).  Seeds come from the run's
``--seed`` and differ in every call; the work never depends on them.

Set-up compiles the grid program ahead of time with the window's shapes
(``grid_program(...).lower().compile()``), which fills the program's jit
cache: the window's first call runs it without compiling.

The check, after the window: a sample of runs, drawn from the seed, is
replayed by the numpy loop of ``chipbench/reference/slotted.py``.  Its
random inputs are drawn on the device by ``device_draws`` below -- this
benchmark's own copy of the simulator's documented draw (one threefry key
per run, split into arrival, size and per-slot tie-break streams) -- so
that the chip's float rounding (which differs from the CPU's in
``jax.random.uniform``'s scaling) is the same on both sides.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import slotted as reference
from repro.core.care import slotted_sim

RATE = "run_slots_per_s"

# Counters of a run that must equal the reference's exactly.
_COUNTERS = ("arrivals", "departures", "messages", "dropped", "max_aq",
             "max_queue", "queue_gap_sup", "per_server_arrivals", "final_q")


@functools.partial(jax.jit, static_argnums=(3, 4))
def device_draws(keys, loads, geo_log1p, slots: int, servers: int):
    """Arrival flags, job sizes and per-slot Gumbel tie scores of runs.

    Per run key: split into (arrival, size, scan) keys; arrivals are
    Bernoulli(load) per slot; sizes are geometric with support {1, 2, ...},
    by inverse CDF of a uniform on (1e-7, 1 - 1e-7); the scan key splits
    into one key per slot, whose ``(servers,)`` Gumbel draw breaks JSAQ
    ties (the highest score among the tied servers wins).
    """

    def one(key, load):
        k_arr, k_size, k_scan = jax.random.split(key, 3)
        arrive = jax.random.uniform(k_arr, (slots,), jnp.float32) < load
        u = jax.random.uniform(k_size, (slots,), jnp.float32, 1e-7,
                               1.0 - 1e-7)
        sizes = jnp.maximum(jnp.floor(jnp.log1p(-u) / geo_log1p) + 1.0, 1.0)
        gumbel = jax.vmap(lambda k: jax.random.gumbel(k, (servers,)))(
            jax.random.split(k_scan, slots)
        )
        return arrive, sizes.astype(jnp.int32), gumbel

    return jax.vmap(one)(keys, loads)


class Driver:
    def __init__(self, config: dict, traffic: dict, *, seed: int, devices,
                 variant=None):
        self.servers = int(config["servers"])
        self.slots = int(config["slots"])
        self.mean_service = float(config["mean_service"])
        self.buffer_cap = int(config["buffer_cap"])
        self.points = [(float(load), int(x)) for load in traffic["loads"]
                       for x in config["xs"]]
        self.seeds_per_point = int(traffic["seeds_per_point"])
        self.check_runs = int(traffic["check_runs"])
        self.shard = len(devices) > 1
        cells = [
            slotted_sim.SimConfig(
                servers=self.servers, slots=self.slots, load=load, x=x,
                policy=config["policy"], comm=config["comm"],
                approx=config["approx"], arrival=config["arrival"],
                service=config["service"], mean_service=self.mean_service,
                buffer_cap=self.buffer_cap,
                # The control (PERF.md): lowest-index ties, the Pallas
                # kernel's convention, in place of the paper's random ties.
                deterministic_ties=variant == "control",
            )
            for load, x in self.points
        ]
        self.static = cells[0].static_part()
        self.scenarios = [c.scenario() for c in cells]
        self.rng = np.random.default_rng(np.random.SeedSequence(seed))
        self.calls = []  # (seeds, results) of every window call

    def _seeds(self) -> list[int]:
        return [int(s) for s in
                self.rng.integers(0, 2**31 - 1, self.seeds_per_point)]

    def setup(self) -> None:
        fn, args, _ = slotted_sim.grid_program(
            self._seeds(), self.static, self.scenarios, shard=self.shard
        )
        self.compiled = fn.lower(*args).compile()
        # Sharded over chips, a call first splits its operands into one
        # block per chip, by small programs of their own: warm those too.
        jax.block_until_ready(
            jax.device_put(args, self.compiled.input_shardings[0])
        )

    def program_bytes(self) -> int:
        """Device memory of the window's program, per chip, as compiled:
        operands, results, temporaries and code."""
        m = self.compiled.memory_analysis()
        return int(m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes + m.generated_code_size_in_bytes
                   - m.alias_size_in_bytes)

    def call(self) -> int:
        seeds = self._seeds()
        results = slotted_sim.simulate_grid(
            seeds, self.static, self.scenarios, shard=self.shard
        )
        self.calls.append((seeds, results))
        return len(self.points) * len(seeds) * self.slots

    def layer_counts(self, calls: int) -> dict:
        """Scan steps each chip ran in ``calls`` calls."""
        return {"steps": calls * self.slots}

    def check(self):
        """Replay a sample of the window's runs on the reference.

        Returns ``({name: (value, limit)}, attempted, failed)``: the jobs
        whose completion time differs from the reference's and the
        counters that differ (both exact, limit 0), the runs of the window
        and the sampled runs that differ.
        """
        runs = [(seeds[j], i, res[i][j]) for seeds, res in self.calls
                for i in range(len(self.points)) for j in range(len(seeds))]
        pick = self.rng.choice(len(runs), min(self.check_runs, len(runs)),
                               replace=False)
        sample = [runs[k] for k in sorted(pick)]
        keys = jnp.stack([jax.random.key(s) for s, _, _ in sample])
        loads = jnp.asarray([self.points[i][0] for _, i, _ in sample],
                            jnp.float32)
        geo = jnp.float32(np.log1p(-1.0 / np.float64(self.mean_service)))
        arrive, sizes, gumbel = (np.asarray(a) for a in device_draws(
            keys, loads, geo, self.slots, self.servers
        ))
        ref = reference.simulate(
            arrive, sizes, gumbel,
            x=[self.points[i][1] for _, i, _ in sample],
            msr_slots=max(int(round(self.mean_service)), 1),
            buffer_cap=self.buffer_cap,
        )
        jct_diff = counter_diff = bad = 0
        for (_, _, res), want in zip(sample, ref):
            n = min(res.jct.size, want["jct"].size)
            d_jct = int(np.sum(res.jct[:n] != want["jct"][:n])
                        + abs(res.jct.size - want["jct"].size))
            d_cnt = sum(
                int(np.sum(np.asarray(getattr(res, f)) != want[f]))
                for f in _COUNTERS
            )
            jct_diff += d_jct
            counter_diff += d_cnt
            bad += (d_jct + d_cnt) > 0
        checks = {"jct_diff": (jct_diff, 0), "counter_diff": (counter_diff, 0)}
        return checks, len(runs), bad
