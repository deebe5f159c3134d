"""Tests for the static/traced config split and the fused grid simulator.

Three layers of protection for the "one compiled program per figure" path:

1. **Goldens** -- metric fingerprints captured from the pre-split
   compile-per-cell simulator (every scenario knob was a static jit
   argument).  The traced-operand path must reproduce them *bit for bit*:
   the ``Scenario`` derivations (``rt_period``, MMPP ``lam_hi/lam_lo``)
   intentionally run in host float64 so no f32-vs-f64 rounding can leak
   into the arrival streams.
2. **Grid equivalence** -- ``simulate_grid`` must equal per-cell
   ``simulate`` on fixed seeds (messages, max_aq, full JCT arrays):
   vmap / shard_map / padding are all semantics-preserving.
3. **Topology** -- padding indices are exercised directly, and
   subprocesses forced to several host devices re-run a ragged grid of
   each tier (3 runs over 8 or 4 shards) that must match one device.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import spans
from repro.core import SimConfig, simulate, simulate_batch, simulate_grid
from repro.core.care import slotted_sim
from repro.core.dispatch_sim import DispatchSimConfig, dispatch_batch
from repro.core.dispatch_sim import simulate as dispatch_simulate

# ---------------------------------------------------------------------------
# 1. Goldens: the traced path reproduces the compile-per-cell seed simulator.
# ---------------------------------------------------------------------------

HETERO_RATES = tuple(1.5 if i < 15 else 0.5 for i in range(30))

GOLDEN_CELLS = {
    "et_msr": dict(slots=4000, load=0.95, policy="jsaq", comm="et", x=3, approx="msr"),
    "et_msr_x5": dict(slots=4000, load=0.8, policy="jsaq", comm="et", x=5, approx="msr"),
    "dt_msrx": dict(slots=4000, load=0.9, policy="jsaq", comm="dt", x=3, approx="msr_x"),
    "rt": dict(slots=4000, load=0.9, policy="jsaq", comm="rt", rt_rate=0.02, approx="msr"),
    "et_rt": dict(slots=4000, load=0.5, policy="jsaq", comm="et_rt", x=3, rt_rate=0.01, approx="msr"),
    "jsq": dict(slots=4000, load=0.95, policy="jsq", comm="none"),
    "sq2": dict(slots=4000, load=0.95, policy="sq2", comm="none"),
    "rr": dict(slots=4000, load=0.95, policy="rr", comm="none"),
    "mmpp": dict(slots=4000, load=0.95, policy="jsaq", comm="et", x=3, approx="msr",
                 arrival="mmpp", burst_intensity=1.7, burst_stay=0.97),
    "hetero": dict(slots=4000, load=0.95, policy="jsaq", comm="et", x=3, approx="msr",
                   service_rates=HETERO_RATES),
    "basic": dict(slots=4000, load=0.9, policy="jsaq", comm="dt", x=4, approx="basic"),
}

# Captured from the seed implementation (SimConfig fully static) at the
# commit introducing the split, then recaptured unchanged in code when JAX
# made ``jax_threefry_partitionable`` its default (it re-draws every
# ``jax.random`` stream).  Under ``JAX_THREEFRY_PARTITIONABLE=0`` the
# seed-era fingerprints still reproduce exactly, so the traced path did
# not drift -- only the library's key derivation did.  Keys are
# (cell, seed) -> fingerprint.
GOLDENS = json.loads("""
{"et_msr/s0":{"messages":444,"max_aq":2,"departures":3715,"arrivals":3785,"dropped":0,"max_queue":7,"gap_sup":7,"jct_sum":341466,"jct_n":3715,"per_srv_sum":55740},
"et_msr/s7":{"messages":387,"max_aq":2,"departures":3719,"arrivals":3802,"dropped":0,"max_queue":6,"gap_sup":6,"jct_sum":283899,"jct_n":3719,"per_srv_sum":54948},
"et_msr_x5/s0":{"messages":67,"max_aq":4,"departures":3138,"arrivals":3182,"dropped":0,"max_queue":6,"gap_sup":6,"jct_sum":211483,"jct_n":3138,"per_srv_sum":46289},
"et_msr_x5/s7":{"messages":59,"max_aq":4,"departures":3176,"arrivals":3235,"dropped":0,"max_queue":5,"gap_sup":5,"jct_sum":194362,"jct_n":3176,"per_srv_sum":46738},
"dt_msrx/s0":{"messages":1171,"max_aq":2,"departures":3539,"arrivals":3582,"dropped":0,"max_queue":5,"gap_sup":5,"jct_sum":213771,"jct_n":3539,"per_srv_sum":51892},
"dt_msrx/s7":{"messages":1174,"max_aq":2,"departures":3551,"arrivals":3611,"dropped":0,"max_queue":4,"gap_sup":4,"jct_sum":189439,"jct_n":3551,"per_srv_sum":52067},
"rt/s0":{"messages":2400,"max_aq":4,"departures":3537,"arrivals":3582,"dropped":0,"max_queue":5,"gap_sup":5,"jct_sum":231527,"jct_n":3537,"per_srv_sum":52340},
"rt/s7":{"messages":2400,"max_aq":4,"departures":3552,"arrivals":3611,"dropped":0,"max_queue":4,"gap_sup":4,"jct_sum":202628,"jct_n":3552,"per_srv_sum":52406},
"et_rt/s0":{"messages":1200,"max_aq":2,"departures":1981,"arrivals":1998,"dropped":0,"max_queue":3,"gap_sup":3,"jct_sum":72852,"jct_n":1981,"per_srv_sum":28885},
"et_rt/s7":{"messages":1200,"max_aq":2,"departures":1986,"arrivals":2005,"dropped":0,"max_queue":3,"gap_sup":3,"jct_sum":72984,"jct_n":1986,"per_srv_sum":29082},
"jsq/s0":{"messages":0,"max_aq":25,"departures":3758,"arrivals":3785,"dropped":0,"max_queue":3,"gap_sup":3,"jct_sum":176458,"jct_n":3758,"per_srv_sum":55296},
"jsq/s7":{"messages":0,"max_aq":28,"departures":3761,"arrivals":3802,"dropped":0,"max_queue":3,"gap_sup":3,"jct_sum":137055,"jct_n":3761,"per_srv_sum":55095},
"sq2/s0":{"messages":0,"max_aq":16,"departures":3696,"arrivals":3785,"dropped":0,"max_queue":8,"gap_sup":8,"jct_sum":405079,"jct_n":3696,"per_srv_sum":54668},
"sq2/s7":{"messages":0,"max_aq":15,"departures":3699,"arrivals":3802,"dropped":0,"max_queue":7,"gap_sup":7,"jct_sum":315801,"jct_n":3699,"per_srv_sum":55138},
"rr/s0":{"messages":0,"max_aq":27,"departures":3596,"arrivals":3785,"dropped":0,"max_queue":28,"gap_sup":28,"jct_sum":618487,"jct_n":3596,"per_srv_sum":54733},
"rr/s7":{"messages":0,"max_aq":23,"departures":3603,"arrivals":3802,"dropped":0,"max_queue":24,"gap_sup":24,"jct_sum":489903,"jct_n":3603,"per_srv_sum":54964},
"mmpp/s0":{"messages":442,"max_aq":2,"departures":3736,"arrivals":3812,"dropped":0,"max_queue":6,"gap_sup":6,"jct_sum":327116,"jct_n":3736,"per_srv_sum":56267},
"mmpp/s7":{"messages":360,"max_aq":2,"departures":3699,"arrivals":3784,"dropped":0,"max_queue":6,"gap_sup":6,"jct_sum":273547,"jct_n":3699,"per_srv_sum":54631},
"hetero/s0":{"messages":438,"max_aq":2,"departures":3689,"arrivals":3785,"dropped":0,"max_queue":9,"gap_sup":9,"jct_sum":392042,"jct_n":3689,"per_srv_sum":40096},
"hetero/s7":{"messages":413,"max_aq":2,"departures":3700,"arrivals":3802,"dropped":0,"max_queue":7,"gap_sup":7,"jct_sum":267313,"jct_n":3700,"per_srv_sum":39501},
"basic/s0":{"messages":871,"max_aq":3,"departures":3535,"arrivals":3582,"dropped":0,"max_queue":5,"gap_sup":5,"jct_sum":227351,"jct_n":3535,"per_srv_sum":52029},
"basic/s7":{"messages":875,"max_aq":3,"departures":3551,"arrivals":3611,"dropped":0,"max_queue":5,"gap_sup":5,"jct_sum":211762,"jct_n":3551,"per_srv_sum":52361}}
""")


def _fingerprint(r) -> dict:
    return dict(
        messages=r.messages,
        max_aq=r.max_aq,
        departures=r.departures,
        arrivals=r.arrivals,
        dropped=r.dropped,
        max_queue=r.max_queue,
        gap_sup=r.queue_gap_sup,
        jct_sum=int(np.sum(r.jct)),
        jct_n=int(r.jct.shape[0]),
        per_srv_sum=int(np.sum(r.per_server_arrivals * np.arange(30))),
    )


class TestGoldens:
    @pytest.mark.parametrize("cell", sorted(GOLDEN_CELLS))
    @pytest.mark.parametrize("seed", [0, 7])
    def test_traced_path_matches_seed_simulator(self, cell, seed):
        r = simulate(jax.random.key(seed), SimConfig(**GOLDEN_CELLS[cell]))
        assert _fingerprint(r) == GOLDENS[f"{cell}/s{seed}"]


# ---------------------------------------------------------------------------
# 2. simulate_grid == per-cell simulate, exactly.
# ---------------------------------------------------------------------------

GRID_CFGS = [
    SimConfig(slots=3000, load=0.95, x=3, comm="et", approx="msr"),
    SimConfig(slots=3000, load=0.8, x=5, comm="et", approx="msr"),
    SimConfig(slots=3000, load=0.5, x=2, comm="et", approx="msr",
              rt_rate=0.05),
]
GRID_SEEDS = (0, 3)


def _assert_same(a, b):
    assert a.messages == b.messages
    assert a.max_aq == b.max_aq
    assert a.departures == b.departures
    assert a.arrivals == b.arrivals
    assert np.array_equal(a.jct, b.jct)
    assert np.array_equal(a.per_server_arrivals, b.per_server_arrivals)
    assert np.array_equal(a.final_q, b.final_q)


class TestSimulateGrid:
    def test_per_cell_equivalence(self):
        static = GRID_CFGS[0].static_part()
        assert all(c.static_part() == static for c in GRID_CFGS)
        grid = simulate_grid(
            list(GRID_SEEDS), static, [c.scenario() for c in GRID_CFGS]
        )
        assert len(grid) == len(GRID_CFGS)
        for cell, cfg in zip(grid, GRID_CFGS):
            assert len(cell) == len(GRID_SEEDS)
            for res, seed in zip(cell, GRID_SEEDS):
                _assert_same(res, simulate(jax.random.key(seed), cfg))

    def test_batch_is_one_cell_grid(self):
        cfg = GRID_CFGS[0]
        batch = simulate_batch(list(GRID_SEEDS), cfg)
        for res, seed in zip(batch, GRID_SEEDS):
            _assert_same(res, simulate(jax.random.key(seed), cfg))

    def test_shard_flag_is_semantics_free(self):
        static = GRID_CFGS[0].static_part()
        scns = [c.scenario() for c in GRID_CFGS]
        a = simulate_grid([5], static, scns, shard=True)
        b = simulate_grid([5], static, scns, shard=False)
        for ca, cb in zip(a, b):
            _assert_same(ca[0], cb[0])

    def test_mixed_x_and_rates_grid(self):
        # x and service_rates vary per cell within one compiled program.
        rates_a = tuple(1.5 if i < 15 else 0.5 for i in range(30))
        rates_b = tuple(0.5 if i < 15 else 1.5 for i in range(30))
        cfgs = [
            SimConfig(slots=2000, load=0.9, x=2, service_rates=rates_a),
            SimConfig(slots=2000, load=0.95, x=4, service_rates=rates_b),
        ]
        static = cfgs[0].static_part()
        assert cfgs[1].static_part() == static
        grid = simulate_grid([1], static, [c.scenario() for c in cfgs])
        for cell, cfg in zip(grid, cfgs):
            _assert_same(cell[0], simulate(jax.random.key(1), cfg))


ALL_K30 = tuple([True] * 30)
# A job size that departs past the departure window even on an idle server.
FAR_SIZE = slotted_sim.NEAR_WINDOW + 200
CALENDAR_CFGS = {
    "geometric": SimConfig(slots=1500, load=0.95, x=3),
    "deterministic": SimConfig(slots=1500, load=0.9, service="deterministic",
                               mean_service=20),
    "pareto": SimConfig(slots=1500, load=0.9, service="pareto",
                        service_tail=1.6, mean_service=20),
    "weibull": SimConfig(slots=1500, load=0.9, service="weibull",
                         service_tail=0.8, mean_service=20),
    "mmpp": SimConfig(slots=1500, load=0.95, arrival="mmpp",
                      burst_intensity=1.7, burst_stay=0.97),
    "padded": SimConfig(slots=1000, max_slots=1500, load=0.95),
    "drops": SimConfig(slots=1500, load=0.99, servers=4, mean_service=8,
                       buffer_cap=4),
    "net": SimConfig(slots=1500, load=0.9, network="net", net_delay=2,
                     net_jitter=1, net_drop=0.1),
    "classes": SimConfig(slots=1500, load=0.9, class_mix=(0.7, 0.3),
                         class_affinity=(ALL_K30, ALL_K30[:10] + (False,) * 20)),
    "jiq": SimConfig(slots=1500, load=0.9, policy="jiq", comm="jiq"),
    "k40": SimConfig(slots=1500, load=0.95, servers=40, mean_service=40),
    "k64": SimConfig(slots=1500, load=0.95, servers=64, mean_service=64),
    # Departures past the window go to the full calendar: every job of a
    # size longer than the window, and the backlog of two servers near
    # saturation.
    "far_deterministic": SimConfig(slots=3000, load=0.9,
                                   service="deterministic",
                                   mean_service=FAR_SIZE),
    "far_backlog": SimConfig(slots=4000, load=0.99, servers=2,
                             mean_service=200),
    # A scan no longer than the window: the window is the whole calendar.
    "short": SimConfig(slots=slotted_sim.NEAR_WINDOW, load=0.95),
}
FAR_CELLS = ("far_deterministic", "far_backlog")


@pytest.mark.parametrize("cell", sorted(CALENDAR_CFGS))
def test_calendar_matches_ring(cell):
    """At unit rate with no fault model the slot step takes departures
    from a calendar written at admission; given unit ``service_rates``
    (one unit per slot by the credit schedule) the same cell runs the job
    ring's countdown.  Both must give the same results."""
    cfg = CALENDAR_CFGS[cell]
    ring = dataclasses.replace(
        cfg, service_rates=(1.0,) * cfg.servers, rate_aware=False
    )
    assert slotted_sim._uses_calendar(cfg.static_part())
    assert not slotted_sim._uses_calendar(ring.static_part())
    a = simulate(jax.random.key(5), cfg)
    b = simulate(jax.random.key(5), ring)
    assert a.departures > 0
    if cell == "drops":
        assert a.dropped > 0
    # How the calendar wrote its departures is not a result of the run.
    for f in dataclasses.fields(a):
        if f.name != "far_departures":
            assert np.array_equal(getattr(a, f.name),
                                  getattr(b, f.name)), f.name
    assert b.far_departures == 0
    if cell in FAR_CELLS:
        assert a.far_departures > 0
    if cfg.static_part().slots <= slotted_sim.NEAR_WINDOW:
        assert a.far_departures == 0


def test_grid_with_some_far_runs_matches_per_run():
    """In a batch where some runs write past the window and others do
    not, the far write runs for the whole batch: a run with nothing to
    write must come out as it does alone."""
    cfgs = [
        SimConfig(slots=3000, load=0.9, service="deterministic",
                  mean_service=m)
        for m in (FAR_SIZE, 20)
    ]
    static = cfgs[0].static_part()
    assert cfgs[1].static_part() == static
    grid = simulate_grid([1, 2], static, [c.scenario() for c in cfgs])
    far = [[r.far_departures for r in row] for row in grid]
    assert all(n > 0 for n in far[0]) and all(n == 0 for n in far[1])
    assert spans.last("simulate_grid").counts["far_departures"] == sum(far[0])
    for row, cfg in zip(grid, cfgs):
        for res, seed in zip(row, (1, 2)):
            want = simulate(jax.random.key(seed), cfg)
            for f in dataclasses.fields(res):
                assert np.array_equal(getattr(res, f.name),
                                      getattr(want, f.name)), f.name


class TestGridSpans:
    """One call's host spans (``repro.spans``): its four phases and the
    counts that are their bases."""

    CHILDREN = ("prepare", "run", "fetch", "finalize")

    @pytest.fixture(scope="class")
    def call(self):
        static = GRID_CFGS[0].static_part()
        scns = [c.scenario() for c in GRID_CFGS]
        grid = simulate_grid(list(GRID_SEEDS), static, scns)
        fn, args, _ = slotted_sim.grid_program(list(GRID_SEEDS), static, scns)
        out_bytes = sum(
            int(np.prod(o.shape)) * o.dtype.itemsize
            for o in jax.eval_shape(fn, *args)
        )
        return grid, spans.last("simulate_grid"), out_bytes

    def test_root_holds_the_four_children(self, call):
        _, rec, _ = call
        names = {f"simulate_grid.{c}" for c in self.CHILDREN}
        assert set(rec.children) == names
        assert all(rec.children[n] > 0 for n in names)
        assert sum(rec.children.values()) <= rec.seconds

    @pytest.mark.parametrize(
        "count", ["runs", "bytes", "jobs", "calendar_runs", "far_departures"]
    )
    def test_count_is_its_base(self, call, count):
        grid, rec, out_bytes = call
        want = {
            "runs": len(GRID_CFGS) * len(GRID_SEEDS),
            "bytes": out_bytes,
            "jobs": sum(len(r.jct) for cell in grid for r in cell),
            # Unit rates, no fault model: every run takes the calendar.
            "calendar_runs": len(GRID_CFGS) * len(GRID_SEEDS),
            "far_departures": sum(r.far_departures
                                  for cell in grid for r in cell),
        }[count]
        assert rec.counts[count] == want

    def test_ring_runs_count_no_calendar(self):
        cfg = SimConfig(slots=500, load=0.9, service_rates=(1.0,) * 30)
        simulate_grid([1, 2], cfg.static_part(), [cfg.scenario()])
        rec = spans.last("simulate_grid")
        assert (rec.counts["runs"], rec.counts["calendar_runs"],
                rec.counts["far_departures"]) == (2, 0, 0)


# ---------------------------------------------------------------------------
# 2b. Traced service axis + padded fixed horizon.
# ---------------------------------------------------------------------------


class TestTracedServiceAxis:
    def test_mixed_mean_and_horizon_grid_matches_percell(self):
        """The bench_ssc shape: (mean_service, horizon) vary per cell inside
        one compiled program; each cell must equal its own per-cell run
        bit for bit (the per-cell path shares the padded StaticConfig, so
        the workload streams coincide)."""
        cfgs = [
            SimConfig(slots=1000, max_slots=4000, load=0.95,
                      mean_service=10, servers=10, x=2),
            SimConfig(slots=2000, max_slots=4000, load=0.95,
                      mean_service=20, servers=10, x=2),
            SimConfig(slots=4000, max_slots=4000, load=0.95,
                      mean_service=40, servers=10, x=2),
        ]
        static = cfgs[0].static_part()
        assert all(c.static_part() == static for c in cfgs)
        grid = simulate_grid([0, 3], static, [c.scenario() for c in cfgs])
        for cell, cfg in zip(grid, cfgs):
            for res, seed in zip(cell, (0, 3)):
                _assert_same(res, simulate(jax.random.key(seed), cfg))

    def test_horizon_mask_freezes_the_tail(self):
        """Slots past the traced horizon are no-ops: arrivals stop, nothing
        serves, no messages fire (RT would otherwise keep messaging
        through the padding)."""
        cfg = SimConfig(slots=1500, max_slots=4000, load=0.9, comm="rt",
                        rt_rate=0.05, approx="msr")
        r = simulate(jax.random.key(0), cfg)
        # ~0.9 * 1500 arrivals, not 0.9 * 4000.
        assert 1150 <= r.arrivals <= 1500
        # RT-0.05 on 30 servers: ~0.05 * 30 * 1500 messages, not * 4000.
        assert r.messages <= 0.05 * 30 * 1500 + 30
        assert r.arrivals == r.departures + int(r.final_q.sum())

    def test_unpadded_equals_padding_free_default(self):
        cfg = SimConfig(slots=2000, load=0.9, x=3)
        _assert_same(
            simulate(jax.random.key(1), cfg),
            simulate(
                jax.random.key(1), dataclasses.replace(cfg, max_slots=2000)
            ),
        )

    @pytest.mark.parametrize(
        "kind,tail", [("pareto", 1.6), ("weibull", 0.8), ("deterministic", 2.0)]
    )
    def test_service_kind_grid_matches_percell(self, kind, tail):
        """Heavy-tailed / deterministic sizes: tail and mean are traced per
        cell; the fused grid equals per-cell simulate bit for bit, and the
        distribution-free ET bound AQ <= x-1 (Prop 6.8) holds."""
        cfgs = [
            SimConfig(slots=2000, load=0.9, x=3, service=kind,
                      service_tail=tail, mean_service=20),
            SimConfig(slots=2000, load=0.8, x=2, service=kind,
                      service_tail=tail + 0.5, mean_service=35),
        ]
        static = cfgs[0].static_part()
        assert cfgs[1].static_part() == static
        grid = simulate_grid([2], static, [c.scenario() for c in cfgs])
        for cell, cfg in zip(grid, cfgs):
            _assert_same(cell[0], simulate(jax.random.key(2), cfg))
            assert cell[0].max_aq <= cfg.x - 1

    def test_mixed_service_kinds_fail_loudly(self):
        cfgs = [
            SimConfig(slots=1000, service="pareto", service_tail=2.0),
            SimConfig(slots=1000, service="weibull", service_tail=1.0),
        ]
        with pytest.raises(ValueError):
            slotted_sim.stack_scenarios([c.scenario() for c in cfgs])

    def test_diurnal_amp_zero_is_flat(self):
        """amp=0 is bit-identical to the unmodulated arrival stream, so
        flat cells share the diurnal cells' compiled program for free."""
        cfg = SimConfig(slots=2000, load=0.9, x=3)
        _assert_same(
            simulate(jax.random.key(4), cfg),
            simulate(
                jax.random.key(4),
                dataclasses.replace(cfg, diurnal_amp=0.0,
                                    diurnal_period=500.0),
            ),
        )

    def test_diurnal_grid_matches_percell(self):
        cfgs = [
            SimConfig(slots=2000, load=0.6, diurnal_amp=0.5,
                      diurnal_period=400.0),
            SimConfig(slots=2000, load=0.6, diurnal_amp=0.0),
        ]
        static = cfgs[0].static_part()
        assert cfgs[1].static_part() == static
        grid = simulate_grid([5], static, [c.scenario() for c in cfgs])
        for cell, cfg in zip(grid, cfgs):
            _assert_same(cell[0], simulate(jax.random.key(5), cfg))

    def test_max_slots_below_slots_rejected(self):
        with pytest.raises(ValueError):
            SimConfig(slots=2000, max_slots=1000).static_part()

    def test_diurnal_amp_validated(self):
        # Peaks above probability 1 would be silently clipped by the
        # u < rate draw, breaking the long-run-rate invariant.
        with pytest.raises(ValueError, match="peak"):
            SimConfig(load=0.95, diurnal_amp=0.5).scenario()
        with pytest.raises(ValueError, match="amp"):
            SimConfig(load=0.3, diurnal_amp=1.5).scenario()
        SimConfig(load=0.5, diurnal_amp=0.8).scenario()  # 0.9 <= 1: fine
        # mmpp clips at the modulated *burst-state* rate, not load:
        # lam_hi = 0.96, so amp=0.5 peaks at 1.44 even though load 0.6 fits.
        with pytest.raises(ValueError, match="mmpp"):
            SimConfig(arrival="mmpp", load=0.6, burst_intensity=1.6,
                      diurnal_amp=0.5).scenario()
        SimConfig(arrival="mmpp", load=0.3, burst_intensity=1.6,
                  diurnal_amp=0.5).scenario()  # 0.48 * 1.5 = 0.72: fine

    def test_diurnal_amp_validated_at_grid_boundary(self):
        # A hand-built Scenario (created without knowing the arrival kind)
        # must still be rejected where it meets an mmpp StaticConfig.
        scn = slotted_sim.Scenario.create(
            servers=30, load=0.6, burst_intensity=1.6, diurnal_amp=0.5
        )
        static = SimConfig(arrival="mmpp", load=0.6).static_part()
        with pytest.raises(ValueError, match="peak"):
            simulate_grid([0], static, [scn])


# ---------------------------------------------------------------------------
# 3. Padding + device topology.
# ---------------------------------------------------------------------------


class TestPadding:
    def test_pad_indices_multiple(self):
        idx = slotted_sim._pad_indices(8, 4)
        assert list(idx) == list(range(8))

    def test_pad_indices_ragged(self):
        idx = slotted_sim._pad_indices(9, 4)
        assert len(idx) == 12
        assert list(idx[:9]) == list(range(9))
        assert list(idx[9:]) == [0, 1, 2]  # wrap-around duplicates

    def test_pad_indices_fewer_runs_than_devices(self):
        idx = slotted_sim._pad_indices(3, 8)
        assert len(idx) == 8
        assert list(idx) == [0, 1, 2, 0, 1, 2, 0, 1]


_SUBPROCESS_PRELUDE = """
import json, sys
import numpy as np
import jax

assert jax.local_device_count() == {n_dev}, jax.local_device_count()
"""

# 3 cells x 1 seed = 3 runs: ragged over n_dev devices, exercising padding
# (the slotted tier adds a fourth, whose jobs of mean size 300 depart past
# the departure window: one shard writes the far calendar, others not).
_SUBPROCESS_SCRIPTS = {
    "slotted": """
from repro.core import SimConfig, simulate_grid

cfgs = [
    SimConfig(slots=2000, load=0.95, x=3),
    SimConfig(slots=2000, load=0.8, x=2),
    SimConfig(slots=2000, load=0.5, x=4),
    SimConfig(slots=2000, load=0.95, x=3, mean_service=300),
]
grid = simulate_grid([11], cfgs[0].static_part(), [c.scenario() for c in cfgs])
assert grid[3][0].far_departures > 0
print(json.dumps([
    dict(messages=r[0].messages, max_aq=r[0].max_aq,
         far_departures=r[0].far_departures,
         jct=np.asarray(r[0].jct).tolist())
    for r in grid
]))
""",
    "serving": """
from repro.serve import engine

cells = [
    engine.ServeConfig(replicas=16, decode_slots=4, slots=600, load=0.9,
                       x=x, comm="et")
    for x in (2, 3, 4)
]
grid = engine.serve_grid([11], cells[0].static_part(), cells)
print(json.dumps([
    dict(messages=r[0].messages, dropped=r[0].dropped,
         jct=np.asarray(r[0].jct_by_rid).tolist())
    for r in grid
]))
""",
}


class TestDeviceTopology:
    @pytest.mark.slow
    @pytest.mark.parametrize("tier,n_dev", [("slotted", 8), ("serving", 4)])
    def test_1_vs_8_device_consistency(self, tier, n_dev):
        """A ragged grid sharded over forced host devices matches 1 device
        (the slotted tier over 8, the serving tier over 4)."""
        outs = {}
        for n in (1, n_dev):
            env = dict(os.environ)
            env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
            env["JAX_PLATFORMS"] = "cpu"
            env["PYTHONPATH"] = "src" + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
            )
            script = _SUBPROCESS_PRELUDE.format(n_dev=n)
            proc = subprocess.run(
                [sys.executable, "-c", script + _SUBPROCESS_SCRIPTS[tier]],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
                cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            )
            assert proc.returncode == 0, proc.stderr[-2000:]
            outs[n] = json.loads(proc.stdout)
        assert outs[1] == outs[n_dev]


# ---------------------------------------------------------------------------
# dispatch tier: the vmapped seed batch equals the per-seed loop.
# ---------------------------------------------------------------------------


class TestDispatchBatch:
    def test_matches_sequential(self):
        cfg = DispatchSimConfig(steps=120, comm="et", x=4)
        batch = dispatch_batch([0, 1], cfg)
        for seed, b in zip([0, 1], batch):
            s = dispatch_simulate(seed, cfg)
            assert b.messages == s.messages
            assert np.allclose(b.gap, s.gap)
            assert np.allclose(b.backlog, s.backlog)
            assert abs(b.max_err - s.max_err) < 1e-5
