"""Mean-field scale: the fused Pallas route kernel vs the dense backend.

The paper's mean-field / diffusion claims (Sections 5-7) are statements
about n -> infinity; the dense slotted backend tops out around 10^4-10^5
servers because every slot materialises the full per-server carry through
the scan *and* a (K, B) FIFO ring.  The fused kernel
(``kernels/jsaq_route.care_route_pallas``) keeps the per-server state
resident across its in-kernel slot loop, drops the per-job ring (no JCT at
mean-field scale), and evaluates the trigger predicate in the same kernel
-- one ``pallas_call`` per simulation instead of one scan step per slot.

Rows:

* ``route/parity`` -- the kernel is *decision identical* to the dense
  backend (trajectory-diff gated bool): same messages, same AQ sup, same
  per-server arrival vector at every swept K where both backends run.
* ``route/crossover`` -- dense-vs-kernel wall clock over the server sweep
  (times as machine-dependent ``*_s`` fields; the crossover point itself
  as a string note) plus the ``speedup`` at the largest dense-feasible K.
* ``route/servers1e3..1e5`` -- per-K simulation metrics from the kernel
  path: messages, AQ sup vs the Theorem 2.3 bound, sup queue gap.  These
  are exact integers from a fixed stream (deterministic ties +
  deterministic service), so the 2% trajectory gate pins them tight.
* ``route/ssc/*`` -- the diffusion-limit prediction at mean-field scale:
  sup_t max_ij |Q_i - Q_j| stays O(1) as n grows through {1e3..1e5}, so
  the sqrt(n)-scaled gap collapses (Theorem 7.3 read through the SSC
  lens); ``route/ssc/summary`` gates the monotone-collapse claim.

Both modes sweep n in {1e3, 1e4, 1e5}; full mode lengthens the horizon.
n = 1e6 is out of reach until the kernel blocks its server axis: its
resident state exceeds the scoped VMEM, and ``care_route_pallas`` refuses
it (``VmemLimitError``).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks import common
from repro.core.care import slotted_sim

SWEEP = (1_000, 10_000, 100_000)
QUICK_SLOTS = 1_000
FULL_SLOTS = 4_000
X = 3
SEED = 7


def _label(k: int) -> str:
    return f"{k:.0e}".replace("e+0", "e").replace("e+", "e")


def _cfg(servers: int, slots: int, backend: str) -> slotted_sim.SimConfig:
    return slotted_sim.SimConfig(
        servers=servers,
        slots=slots,
        load=0.95,
        mean_service=8,
        policy="jsaq",
        comm="dt",
        x=X,
        approx="msr",
        service="deterministic",
        buffer_cap=16,
        deterministic_ties=True,
        route_backend=backend,
    )


def _timed(cfg: slotted_sim.SimConfig):
    """(result, cold_s, warm_s): first call pays the compile, second runs
    the cached program -- the crossover compares steady-state walls."""
    key = jax.random.key(SEED)
    t0 = time.perf_counter()
    res = slotted_sim.simulate(key, cfg)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = slotted_sim.simulate(key, cfg)
    warm = time.perf_counter() - t0
    return res, cold, warm


def run(quick: bool = False) -> list[dict]:
    slots = QUICK_SLOTS if quick else FULL_SLOTS
    rows: list[dict] = []

    parity = True
    walls: dict[int, dict[str, float]] = {}
    kernel_res: dict[int, slotted_sim.SimResult] = {}
    for k in SWEEP:
        rp, cold_p, warm_p = _timed(_cfg(k, slots, "pallas"))
        kernel_res[k] = rp
        walls[k] = {"pallas": warm_p, "pallas_cold": cold_p}
        rd, cold_d, warm_d = _timed(_cfg(k, slots, "dense"))
        walls[k]["dense"] = warm_d
        parity = parity and (
            rd.messages == rp.messages
            and rd.departures == rp.departures
            and rd.max_aq == rp.max_aq
            and rd.queue_gap_sup == rp.queue_gap_sup
            and np.array_equal(
                rd.per_server_arrivals, rp.per_server_arrivals
            )
            and np.array_equal(rd.final_q, rp.final_q)
        )

        label = _label(k)
        aq_bound = rp.max_aq <= X - 1
        rows.append(
            common.row(
                f"route/servers{label}",
                walls[k]["pallas"],
                slots,
                common.fmt_derived(
                    msgs=rp.messages,
                    deps=rp.departures,
                    max_aq=rp.max_aq,
                    gap_sup=rp.queue_gap_sup,
                    aq_bound=aq_bound,
                ),
                msgs=rp.messages,
                deps=rp.departures,
                max_aq=rp.max_aq,
                gap_sup=rp.queue_gap_sup,
                # Theorem 2.3 at mean-field scale, gate-pinned.
                aq_bound=bool(aq_bound),
            )
        )

    rows.append(
        common.row(
            "route/parity",
            0.0,
            slots,
            common.fmt_derived(
                parity=parity, dense_cells=len(SWEEP), comm="dt"
            ),
            # The acceptance claim: kernel == dense, decision for decision.
            parity=bool(parity),
        )
    )

    # Crossover: smallest swept K where the kernel's steady-state wall
    # beats the dense backend's.  Wall clocks are machine-dependent (all
    # ``*_s`` / ``speedup`` fields, skipped by the trajectory gate); the
    # crossover point rides along as a string note.
    cross = next(
        (k for k in SWEEP if walls[k]["pallas"] < walls[k]["dense"]), None
    )
    dense_big = SWEEP[-1]
    extra = {f"dense_{_label(k)}_s": walls[k]["dense"] for k in SWEEP}
    extra.update(
        {f"pallas_{_label(k)}_s": walls[k]["pallas"] for k in SWEEP}
    )
    rows.append(
        common.row(
            "route/crossover",
            sum(w["pallas"] for w in walls.values()),
            slots * len(SWEEP),
            common.fmt_derived(
                crossover="none" if cross is None else _label(cross),
                speedup_at_1e5=walls[dense_big]["dense"]
                / max(walls[dense_big]["pallas"], 1e-9),
            ),
            crossover="none" if cross is None else _label(cross),
            speedup=walls[dense_big]["dense"]
            / max(walls[dense_big]["pallas"], 1e-9),
            **extra,
        )
    )

    # SSC at mean-field scale: the sup queue gap is O(1) in n, so the
    # sqrt(n)-scaled gap collapses monotonically through the sweep.
    scaled = {
        k: kernel_res[k].queue_gap_sup / np.sqrt(k) for k in SWEEP
    }
    for k in SWEEP:
        rows.append(
            common.row(
                f"route/ssc/n{_label(k)}",
                0.0,
                slots,
                common.fmt_derived(
                    gap_sup=kernel_res[k].queue_gap_sup,
                    gap_over_sqrt_n=float(scaled[k]),
                ),
                gap_over_sqrt_n=float(scaled[k]),
            )
        )
    collapses = all(
        scaled[b] <= scaled[a] for a, b in zip(SWEEP, SWEEP[1:])
    )
    rows.append(
        common.row(
            "route/ssc/summary",
            0.0,
            slots,
            common.fmt_derived(
                scaled_gap_first=float(scaled[SWEEP[0]]),
                scaled_gap_last=float(scaled[SWEEP[-1]]),
                collapses=collapses,
            ),
            collapses=bool(collapses),
        )
    )
    return rows
