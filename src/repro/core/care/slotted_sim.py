"""Discrete-time slotted simulator for the CARE model (paper Section 9).

Dynamics (matching the paper's simulation setting exactly):

* K parallel FIFO servers, a single load balancer.
* In every slot, one job arrives with probability ``load`` (Bernoulli), or
  according to a bursty MMPP-modulated process (``cfg.arrival = "mmpp"``,
  see :mod:`repro.core.care.workload`).
* Job service requirements are i.i.d. Geometric(1/K) (mean K slots), drawn
  per job at arrival time so that *the same input* (arrival times and sizes)
  can be replayed under every policy -- the paper's comparison method.
* A busy server completes one unit of work per slot -- or ``r_i`` units under
  heterogeneous service rates (``cfg.service_rates``), realised by the
  deterministic credit schedule of :func:`workload.service_units` which the
  balancer mirrors exactly.

Within a slot the order of operations is:

  1. arrival (if any) is routed using the *pre-slot* state; a full FIFO
     (``q >= buffer_cap``) *drops* the arrival (counted in ``dropped``)
     instead of admitting it;
  2. every busy server works one unit; the head job departs when its
     remaining requirement reaches zero;
  3. the balancer's emulation advances one slot (approximation component);
  4. the communication pattern (:mod:`repro.core.care.comm` -- the single
     trigger implementation shared with the MoE dispatch simulator and the
     serving engine) evaluates its trigger and any triggered server sends a
     message carrying its exact queue length, which snaps the approximation
     to the truth.

Because a message fires in the same slot in which the trigger condition is
met, the end-of-slot approximation error satisfies ``AQ <= x - 1`` for DT-x
and ET-x (Theorem 2.3) -- asserted by the tests.

Static/traced split
-------------------

The paper's headline artifacts are *grids* over ``(load, x, rt_rate,
scenario)``.  To run a whole grid as one compiled program, the
configuration is split in two:

* :class:`StaticConfig` -- the *structure* of the program: array shapes
  (``servers``, ``slots``, ``buffer_cap``) and the policy / communication /
  approximation / arrival / service **kinds**, which select code paths via
  Python ``if``.  XLA must specialise on these; they are hashable static
  jit arguments and changing any of them costs a recompile.
* :class:`Scenario` -- a registered pytree of *traced array operands*:
  ``load``, ``x``, ``rt_rate`` (carried as the derived ``rt_period``
  operand), ``burst_intensity``/``burst_stay`` (carried as the derived
  ``lam_hi``/``lam_lo`` operands), ``service_rates``, the
  :class:`~repro.core.care.workload.ServiceProcess` operand bundle
  (traced mean / tail-shape), the diurnal-curve operands
  (``diurnal_amp``/``diurnal_period``) and the traced ``horizon``.
  Trigger thresholds, arrival/rate schedules, the size sampler and the
  MSR emulation constant consume these as arrays, so any number of
  scenario cells share one compiled program.

Padded fixed horizon
--------------------

``StaticConfig.slots`` is the *padded* scan length: the scan always runs
``slots`` steps, and each cell's effective length is the traced
``Scenario.horizon`` operand.  Slots at ``t >= horizon`` are masked into
no-ops (no arrivals, no service, no emulation drain, no trigger
evaluation -- every carry field is frozen), so cells with different
effective horizons -- e.g. the diffusion-scaling sweep of ``bench_ssc``,
which grows ``mean_service`` and the horizon together -- share one
compiled program instead of compiling once per horizon.  When
``horizon >= slots`` the mask is all-True and the program is
bit-identical to the historical unpadded one.  Note the *workload stream*
is keyed to the padded shape: two runs agree bit-for-bit exactly when
they share a ``StaticConfig`` (asserted against a per-cell reference
path in ``tests/test_grid.py``); changing the padding re-draws the
stream, just as changing ``slots`` always did.

:class:`SimConfig` remains the user-facing cell description; it is exactly
``static_part() + scenario()``.  Derived operands (``rt_period``,
``lam_hi``, ``lam_lo``, the ServiceProcess constants) are computed
host-side in float64 at :class:`Scenario` construction so the traced
program is bit-identical to the historical compile-per-cell program
(golden-tested in ``tests/test_grid.py``).

The whole simulation is a single ``jax.lax.scan``; all per-server state is
vectorised, so the simulator jit-compiles **once per StaticConfig** and
runs at native speed on CPU/TPU.  How a server's FIFO is carried (a
departure calendar, or a ring of job ids) depends on the static kinds
and lives in :mod:`repro.core.care.fifo`.  ``buffer_cap`` bounds
admission on both carriages (a full FIFO drops the arrival); only the
ring also uses it as an array shape.  Batching entry points:

* :func:`simulate` -- one key, one cell.
* :func:`simulate_batch` -- vmap over a batch of PRNG keys for one cell.
* :func:`simulate_grid` -- the sweep entry point: one jit, ``vmap`` over
  the flattened ``(scenario x seed)`` axis, sharded across local devices
  with ``shard_map``.  Ragged batches are padded up to the device count
  (and the padding dropped on the way out), so they no longer fall back to
  a single device the way the old ``pmap`` path did.

The compiled program names its phases with ``jax.named_scope``, so that a
device trace's operations map back to them through the executable's
``op_name`` metadata: ``draw`` (the workload draw), ``faults``, ``route``,
``service``, ``drain``, ``trigger`` and ``metrics`` (the slot step, in
order) and, on the ring path, ``complete`` (the completion-slot scatter
after the scan).  A run's outputs are one :class:`_RunOut`.
:func:`simulate_grid` also records host spans (:mod:`repro.spans`); its
root span counts ``calendar_runs``, the runs that took the calendar, and
``far_departures``, the departures written past its window.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import spans
from repro.core.care import approx as approx_lib
from repro.core.care import comm as comm_lib
from repro.core.care import fifo as fifo_lib
from repro.core.care import routing as routing_lib
from repro.core.care import workload as workload_lib

CommKind = comm_lib.CommKind

@dataclasses.dataclass(frozen=True)
class StaticConfig:
    """The compile-time structure of the simulator program (hashable).

    Only knobs that change the *traced program itself* live here: array
    shapes (``servers``, ``slots`` -- the *padded* scan length; each
    cell's effective length is the traced ``Scenario.horizon`` --
    ``buffer_cap``) and the policy / comm / approx / arrival / service
    kinds plus the two rate flags, which pick code paths via Python
    ``if`` at trace time.  Everything numeric a figure sweeps --
    including ``mean_service`` and the horizon, which used to be baked in
    here -- lives in :class:`Scenario` instead.
    """

    servers: int = 30
    slots: int = 100_000  # padded scan length (max horizon of the grid)
    policy: routing_lib.PolicyKind = "jsaq"
    comm: CommKind = "et"
    approx: approx_lib.ApproxKind = "msr"
    # Per-server FIFO capacity: an arrival at a full FIFO is dropped.  An
    # array shape only on the job-ring path (module docstring).
    buffer_cap: int = 2048
    sqd: int = 2
    arrival: str = "bernoulli"  # "bernoulli" | "mmpp"
    service: workload_lib.ServiceKind = "geometric"
    use_rates: bool = False  # heterogeneous service_rates in play
    rate_aware: bool = True
    # Which routing engine executes the slot loop: "dense" (the golden
    # reference -- per-slot one-hot array ops) or "pallas" (the fused
    # kernels/jsaq_route.care_route_pallas mean-field kernel; requires
    # policy jsq/jsaq, msr approximation, deterministic service, unit
    # rates and deterministic_ties -- see _check_pallas_static).
    route_backend: str = "dense"
    # Shortest-queue tie-break: False = uniformly random (the paper's
    # JSAQ definition), True = lowest index (the kernel convention; the
    # mode in which dense and pallas backends are decision-identical).
    deterministic_ties: bool = False
    # Control-plane modelling (fault-injection layer).  ``network="net"``
    # routes every server->balancer message through ``comm.net_step``
    # (traced delay / jitter / drop operands; SQ(d) query round-trips are
    # then counted as real traffic too); ``fault`` runs the crash/recovery
    # or transient-slowdown server process of ``workload.fault_transitions``.
    # "none"/"none" is bit-identical to the historical instant, fault-free
    # program.
    network: str = "none"  # "none" | "net"
    # Wire semantics under network="net": "fire_forget" is the historical
    # one-shot path (structurally unchanged), "ack" runs the reliable
    # transport of comm.net_step_ack (timeout/retransmit/backoff windows,
    # acks and keepalives billed on the same wire).  Static because it
    # selects the carry structure (NetState vs AckNetState).
    transport: str = "fire_forget"  # "fire_forget" | "ack"
    fault: str = "none"  # "none" | "crash" | "slow"
    # Ring capacity for the stale true-state views the query policies
    # (jsq / sq2 / sqd) route on under network="net"; must exceed every
    # ``net_delay`` in the grid (validated at the host entry points).
    # Static because it is an array shape.
    net_delay_cap: int = 32
    # Number of arrival classes for constrained routing (an array shape:
    # ``Scenario.class_mix`` is (C,), ``class_affinity`` (C, K)).  With
    # ``classes == 1`` no class stream is drawn and the program is
    # byte-identical to the historical single-class one.
    classes: int = 1
    # True when the config supplied an explicit affinity mask.  A SINGLE
    # class with a restricted server set is a legitimate constraint (e.g.
    # a partial placement), so the mask must be applied even when no class
    # stream is drawn -- without this bit a (1, K) affinity would silently
    # no-op.  Unconstrained single-class programs keep constrained=False
    # and stay byte-identical to the historical trace.
    constrained: bool = False


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Scenario:
    """Traced scenario operands -- one grid cell (a registered pytree).

    The user-facing knobs ``rt_rate`` / ``burst_intensity`` are carried for
    reporting, but the scan consumes the *derived* operands ``rt_period``
    and ``lam_hi``/``lam_lo``: those derivations involve host float64
    arithmetic (``round``, the MMPP rate balance), so they are computed
    once at construction -- bit-identical to the historical
    compile-per-cell program -- and traced as ready-made arrays.

    Build cells with :meth:`create` (or ``SimConfig.scenario()``); stack
    cells along a leading axis with :func:`stack_scenarios` to form the
    batched operand :func:`simulate_grid` takes.
    """

    load: jnp.ndarray  # () f32 arrival rate
    x: jnp.ndarray  # () i32 DT-x / ET-x parameter
    rt_rate: jnp.ndarray  # () f32 RT-r rate (reporting; rt_period is used)
    rt_period: jnp.ndarray  # () i32 derived RT period in slots
    burst_intensity: jnp.ndarray  # () f32 MMPP knob (reporting)
    burst_stay: jnp.ndarray  # () f32 MMPP per-slot stay probability
    lam_hi: jnp.ndarray  # () f32 derived MMPP burst-state arrival rate
    lam_lo: jnp.ndarray  # () f32 derived MMPP lull-state arrival rate
    service_rates: jnp.ndarray  # (K,) f32 per-server speeds (ones if unused)
    service: workload_lib.ServiceProcess  # size-distribution operand bundle
    horizon: jnp.ndarray  # () i32 effective slots (>= StaticConfig.slots = unpadded)
    diurnal_amp: jnp.ndarray  # () f32 diurnal curve amplitude (0 = flat)
    diurnal_period: jnp.ndarray  # () f32 diurnal curve period in slots
    # Control-plane operands (all neutral when the static kinds are "none").
    net_delay: jnp.ndarray  # () i32 deterministic delivery delay (slots)
    net_jitter: jnp.ndarray  # () i32 max extra uniform delay (slots)
    net_drop: jnp.ndarray  # () f32 i.i.d. message-drop probability
    suspect_age: jnp.ndarray  # () i32 staleness bound (0 = no suspect masking)
    # Reliable-transport operands (neutral under transport="fire_forget").
    ack_timeout: jnp.ndarray  # () i32 base ack-wait window in slots
    backoff_base: jnp.ndarray  # () f32 timeout multiplier per retransmit
    max_retries: jnp.ndarray  # () i32 retransmits before abandoning
    ka_period: jnp.ndarray  # () i32 server keepalive period (0 = none)
    crash_rate: jnp.ndarray  # () f32 per-slot fault-entry probability
    recover_rate: jnp.ndarray  # () f32 per-slot fault-exit probability
    slow_factor: jnp.ndarray  # () f32 rate multiplier while slowed (fault="slow")
    # Constrained-routing operands (neutral single-class defaults).
    class_mix: jnp.ndarray  # (C,) f32 arrival-class weights
    class_affinity: jnp.ndarray  # (C, K) bool per-class eligible servers

    @staticmethod
    def create(
        servers: int,
        load: float,
        x: int = 3,
        rt_rate: float = 0.01,
        burst_intensity: float = 1.6,
        burst_stay: float = 0.98,
        service_rates: Optional[Sequence[float]] = None,
        mean_service: float = 30,
        service: workload_lib.ServiceKind = "geometric",
        service_tail: float = 2.0,
        horizon: Optional[int] = None,
        diurnal_amp: float = 0.0,
        diurnal_period: float = 1.0,
        arrival: str = "bernoulli",  # diurnal peak-rate validation only
        network: str = "none",  # control-plane operand validation only
        net_delay: int = 0,
        net_jitter: int = 0,
        net_drop: float = 0.0,
        suspect_age: int = 0,
        transport: str = "fire_forget",  # operand validation only
        ack_timeout: int = 0,
        backoff_base: float = 1.0,
        max_retries: int = 0,
        ka_period: int = 0,
        fault: str = "none",  # control-plane operand validation only
        crash_rate: float = 0.0,
        recover_rate: float = 0.0,
        slow_factor: float = 1.0,
        class_mix: Optional[Sequence[float]] = None,
        class_affinity: Optional[Sequence[Sequence[bool]]] = None,
        policy: Optional[str] = None,  # pull-pairing validation only
        comm: Optional[str] = None,  # pull-pairing validation only
    ) -> "Scenario":
        comm_lib.validate_control_plane(
            network=network,
            net_delay=net_delay,
            net_jitter=net_jitter,
            net_drop=net_drop,
            suspect_age=suspect_age,
            transport=transport,
            ack_timeout=ack_timeout,
            backoff_base=backoff_base,
            max_retries=max_retries,
            ka_period=ka_period,
            fault=fault,
            crash_rate=crash_rate,
            recover_rate=recover_rate,
            slow_factor=slow_factor,
            policy=policy,
            comm=comm,
            token_refresh=rt_rate if policy == "hsq" else None,
        )
        if class_affinity is not None and class_mix is None:
            raise ValueError(
                "class_affinity requires class_mix (one weight per class)"
            )
        if class_mix is None:
            mix = jnp.ones((1,), jnp.float32)
            aff = jnp.ones((1, servers), bool)
        else:
            mix_np = np.asarray(class_mix, np.float64)
            if mix_np.ndim != 1 or mix_np.size < 1:
                raise ValueError(
                    f"class_mix must be a 1-D weight vector, got shape "
                    f"{mix_np.shape}"
                )
            if np.any(mix_np < 0) or mix_np.sum() <= 0:
                raise ValueError(
                    "class_mix weights must be >= 0 with a positive sum, "
                    f"got {class_mix}"
                )
            aff_np = (
                np.ones((mix_np.size, servers), bool)
                if class_affinity is None
                else np.asarray(class_affinity, bool)
            )
            if aff_np.shape != (mix_np.size, servers):
                raise ValueError(
                    f"class_affinity must have shape (classes, servers) = "
                    f"({mix_np.size}, {servers}), got {aff_np.shape}"
                )
            if not aff_np.any(axis=1).all():
                empty = int(np.argmin(aff_np.any(axis=1)))
                raise ValueError(
                    f"class_affinity row {empty} has no eligible server; "
                    "every class needs at least one"
                )
            mix = jnp.asarray(mix_np, jnp.float32)
            aff = jnp.asarray(aff_np)
        lam_hi = min(burst_intensity * load, 1.0)
        lam_lo = max(2.0 * load - lam_hi, 0.0)
        period = max(int(round(1.0 / max(rt_rate, 1e-9))), 1)
        rates = (
            jnp.ones((servers,), jnp.float32)
            if service_rates is None
            else jnp.asarray(service_rates, jnp.float32)
        )
        diurnal_amp = float(diurnal_amp)
        if not 0.0 <= diurnal_amp <= 1.0:
            raise ValueError(
                f"diurnal_amp must be in [0, 1] (rate stays non-negative), "
                f"got {diurnal_amp}"
            )
        # The highest *modulated* rate must stay a probability, or the
        # u < rate comparison silently clips the sine peaks and the
        # long-run rate drops below the nominal load.  For mmpp that peak
        # is the burst-state rate, not load.
        base_peak = lam_hi if arrival == "mmpp" else load
        if diurnal_amp and base_peak * (1.0 + diurnal_amp) > 1.0 + 1e-9:
            raise ValueError(
                f"diurnal peak rate {base_peak:.4f}*(1+amp) = "
                f"{base_peak * (1.0 + diurnal_amp):.4f} exceeds 1 "
                f"(arrival={arrival!r}); lower amp to at most "
                f"{1.0 / base_peak - 1.0:.4f}"
            )
        if horizon is None:
            horizon = np.iinfo(np.int32).max  # unbounded: never mask
        return Scenario(
            load=jnp.float32(load),
            x=jnp.int32(x),
            rt_rate=jnp.float32(rt_rate),
            rt_period=jnp.int32(period),
            burst_intensity=jnp.float32(burst_intensity),
            burst_stay=jnp.float32(burst_stay),
            lam_hi=jnp.float32(lam_hi),
            lam_lo=jnp.float32(lam_lo),
            service_rates=rates,
            service=workload_lib.ServiceProcess.create(
                kind=service, mean=mean_service, tail=service_tail
            ),
            horizon=jnp.int32(horizon),
            diurnal_amp=jnp.float32(diurnal_amp),
            diurnal_period=jnp.float32(max(float(diurnal_period), 1e-6)),
            net_delay=jnp.int32(net_delay),
            net_jitter=jnp.int32(net_jitter),
            net_drop=jnp.float32(net_drop),
            suspect_age=jnp.int32(suspect_age),
            ack_timeout=jnp.int32(ack_timeout),
            backoff_base=jnp.float32(backoff_base),
            max_retries=jnp.int32(max_retries),
            ka_period=jnp.int32(ka_period),
            crash_rate=jnp.float32(crash_rate),
            recover_rate=jnp.float32(recover_rate),
            slow_factor=jnp.float32(slow_factor),
            class_mix=mix,
            class_affinity=aff,
        )


def stack_scenarios(scenarios: Sequence[Scenario]) -> Scenario:
    """Stack unbatched cells into one batched Scenario (leading axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *scenarios)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """One grid cell as the user sees it: static structure + scenario knobs.

    ``SimConfig`` is hashable (benchmark caches key on it) and splits into
    the two halves the compiled program takes: :meth:`static_part` (jit
    specialises on it) and :meth:`scenario` (traced operands).

    Scenario knobs beyond the paper's Section 9.1 setting:

    * ``arrival="mmpp"`` with ``burst_intensity`` / ``burst_stay`` switches
      to bursty Markov-modulated arrivals (long-run rate still ``load``).
    * ``service`` selects the job-size distribution kind (``geometric`` --
      the paper's default -- ``deterministic``, ``pareto``, ``weibull``;
      see :class:`~repro.core.care.workload.ServiceProcess`) with traced
      ``mean_service`` / ``service_tail`` operands.
    * ``diurnal_amp`` / ``diurnal_period`` modulate the arrival rate with
      a sinusoidal load curve; the long-run rate stays ``load``, which
      requires ``load * (1 + amp) <= 1`` (validated at construction --
      otherwise the Bernoulli clip would shave the peaks).  amp 0 = flat.
    * ``service_rates`` (length-``servers`` tuple) gives each server a speed
      in work units/slot; ``rate_aware=True`` makes the shortest-queue
      family minimise the expected drain time ``q_i * E[S] / r_i`` instead
      of the raw queue length.
    * ``comm="et_rt"`` enables the hybrid ET-x trigger with an RT fallback
      every ``1/rt_rate`` slots (staleness cap in light traffic).
    * ``max_slots`` pads the scan to a longer fixed horizon than ``slots``
      so cells with different effective horizons share one compiled
      program (see the module docstring); ``None`` means unpadded.
    """

    servers: int = 30
    slots: int = 100_000
    load: float = 0.95
    # Mean job size in slots; the paper uses Geometric(1/K) i.e. mean == K.
    mean_service: int = 30
    policy: routing_lib.PolicyKind = "jsaq"
    comm: CommKind = "et"
    x: int = 3  # DT-x / ET-x parameter (max tolerated error is x-1).
    rt_rate: float = 0.01  # RT-r per-server message rate (messages/slot).
    approx: approx_lib.ApproxKind = "msr"
    buffer_cap: int = 2048  # per-server FIFO capacity (power of two).
    sqd: int = 2
    # Scenario layer (see module docstring / workload.py).
    arrival: str = "bernoulli"  # "bernoulli" | "mmpp"
    burst_intensity: float = 1.6
    burst_stay: float = 0.98
    service_rates: Optional[Tuple[float, ...]] = None
    rate_aware: bool = True
    service: workload_lib.ServiceKind = "geometric"
    service_tail: float = 2.0  # pareto alpha / weibull shape
    diurnal_amp: float = 0.0
    diurnal_period: float = 1.0
    max_slots: Optional[int] = None  # padded scan length (>= slots)
    route_backend: str = "dense"  # "dense" | "pallas" (see StaticConfig)
    deterministic_ties: bool = False
    # Control plane (fault-injection layer; see StaticConfig / comm.py).
    network: str = "none"  # "none" | "net"
    net_delay: int = 0
    net_jitter: int = 0
    net_drop: float = 0.0
    suspect_age: int = 0  # staleness bound in slots (0 = no suspect masking)
    # Reliable transport (see comm.NetworkConfig): transport="ack" turns
    # every data send into an ack'd transmission with a timeout/retransmit
    # window; the four operands below are traced (one compiled program per
    # delay x drop x timeout ladder).
    transport: str = "fire_forget"  # "fire_forget" | "ack"
    ack_timeout: int = 0  # base ack-wait window in slots (>= 1 under ack)
    backoff_base: float = 1.0  # timeout multiplier per retransmit (>= 1)
    max_retries: int = 0  # retransmits before abandoning the update
    ka_period: int = 0  # server keepalive period in slots (0 = none)
    fault: str = "none"  # "none" | "crash" | "slow"
    crash_rate: float = 0.0
    recover_rate: float = 0.0
    slow_factor: float = 1.0
    net_delay_cap: int = 32  # stale-view ring capacity (static shape)
    # Constrained routing: per-class arrival weights and per-class server
    # affinity masks (rows must each keep >= 1 eligible server).  The mix
    # is a traced operand; only the class count C is structural.
    class_mix: Optional[Tuple[float, ...]] = None
    class_affinity: Optional[Tuple[Tuple[bool, ...], ...]] = None

    def static_part(self) -> StaticConfig:
        if self.max_slots is not None and self.max_slots < self.slots:
            raise ValueError(
                f"max_slots ({self.max_slots}) must be >= slots ({self.slots})"
            )
        if self.comm == "exact" and self.network != "none":
            raise ValueError(
                "comm='exact' cannot run through the network model: its "
                "per-departure message accounting (Prop 6.1) assumes "
                "instant delivery -- use comm='dt' with x=1 for a "
                "near-exact pattern under network='net'"
            )
        return StaticConfig(
            servers=self.servers,
            slots=self.max_slots if self.max_slots is not None else self.slots,
            policy=self.policy,
            comm=self.comm,
            approx=self.approx,
            buffer_cap=self.buffer_cap,
            sqd=self.sqd,
            arrival=self.arrival,
            service=self.service,
            use_rates=self.service_rates is not None,
            rate_aware=self.rate_aware,
            route_backend=self.route_backend,
            deterministic_ties=self.deterministic_ties,
            network=self.network,
            transport=self.transport,
            fault=self.fault,
            net_delay_cap=self.net_delay_cap,
            classes=(
                len(self.class_mix) if self.class_mix is not None else 1
            ),
            constrained=self.class_affinity is not None,
        )

    def scenario(self) -> Scenario:
        return Scenario.create(
            servers=self.servers,
            load=self.load,
            x=self.x,
            rt_rate=self.rt_rate,
            burst_intensity=self.burst_intensity,
            burst_stay=self.burst_stay,
            service_rates=self.service_rates,
            mean_service=self.mean_service,
            service=self.service,
            service_tail=self.service_tail,
            horizon=self.slots,
            diurnal_amp=self.diurnal_amp,
            diurnal_period=self.diurnal_period,
            arrival=self.arrival,
            network=self.network,
            net_delay=self.net_delay,
            net_jitter=self.net_jitter,
            net_drop=self.net_drop,
            suspect_age=self.suspect_age,
            transport=self.transport,
            ack_timeout=self.ack_timeout,
            backoff_base=self.backoff_base,
            max_retries=self.max_retries,
            ka_period=self.ka_period,
            fault=self.fault,
            crash_rate=self.crash_rate,
            recover_rate=self.recover_rate,
            slow_factor=self.slow_factor,
            class_mix=self.class_mix,
            class_affinity=self.class_affinity,
            policy=self.policy,
            comm=self.comm,
        )


@dataclasses.dataclass
class SimResult:
    """Simulation outputs (host-side numpy)."""

    jct: np.ndarray  # (num_jobs,) job completion times in slots (>=1)
    arrivals: int  # admitted arrivals (offered minus dropped)
    departures: int
    messages: int
    max_aq: int  # sup_t AQ(t) observed at slot ends
    max_queue: int
    overflow: bool  # any arrival dropped on a full FIFO
    per_server_arrivals: np.ndarray  # (K,)
    final_q: np.ndarray  # (K,)
    # messages per departure; the exact-state baseline is 1 (Prop 6.1).
    msgs_per_departure: float = 0.0
    queue_gap_sup: int = 0  # sup_t max_ij |Q_i - Q_j| (for SSC experiments)
    dropped: int = 0  # arrivals rejected because the FIFO was full
    net_drops: int = 0  # messages lost in flight (network="net")
    retrans: int = 0  # data retransmits (transport="ack"; zero otherwise)
    # Pull-policy counters (jiq / hsq; zero otherwise).
    token_misses: int = 0  # arrivals routed with an empty token pool
    token_sum: int = 0  # sum over active slots of end-of-slot pool size
    # Departures written to the full calendar because they fell past the
    # departure window (calendar path; zero otherwise).
    far_departures: int = 0


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _RunOut:
    """One run's outputs, on the device or fetched (with a leading run
    axis when batched); :func:`_finalize` makes its :class:`SimResult`."""

    arrive: jnp.ndarray  # (T,) bool arrival flags (the job ids)
    comp_slot: jnp.ndarray  # (T,) i32 completion slot per job id, or -1
    msgs: jnp.ndarray
    deps: jnp.ndarray
    arrs: jnp.ndarray
    max_aq: jnp.ndarray
    max_q: jnp.ndarray
    per_srv: jnp.ndarray  # (K,)
    final_q: jnp.ndarray  # (K,)
    dropped: jnp.ndarray
    gap_sup: jnp.ndarray
    net_drops: jnp.ndarray
    token_misses: jnp.ndarray
    token_sum: jnp.ndarray
    # None unless the run carried a calendar (and the dense backend ran).
    far_departures: Optional[jnp.ndarray] = None
    retrans: Optional[jnp.ndarray] = None  # None unless transport="ack"

    def run(self, i: int) -> "_RunOut":
        """Run ``i`` of a batch of fetched outputs."""
        return _RunOut(**{name: None if v is None else v[i]
                          for name, v in vars(self).items()})


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class _Carry:
    q_true: jnp.ndarray  # (K,) true queue lengths
    emu: approx_lib.EmuState
    comm: comm_lib.CommState  # shared trigger bookkeeping + message total
    rr_ptr: jnp.ndarray  # () round-robin pointer
    deps: jnp.ndarray  # () total departures
    arrs: jnp.ndarray  # () total admitted arrivals
    dropped: jnp.ndarray  # () arrivals rejected on a full FIFO
    per_srv: jnp.ndarray  # (K,) arrivals per server
    max_aq: jnp.ndarray  # () running sup of end-of-slot AQ
    max_q: jnp.ndarray  # () running sup of max queue length
    gap_sup: jnp.ndarray  # () running sup of max_ij |Q_i - Q_j|
    # Control-plane state; None (an empty pytree subtree) whenever the
    # corresponding static kind is off, so the "none" carry structure --
    # and therefore the compiled program -- is unchanged.
    fault_state: Optional[jnp.ndarray] = None  # (K,) bool servers faulted
    # In-flight message buffer: NetState under transport="fire_forget",
    # AckNetState under "ack" (the static transport kind picks the subtree).
    net: Optional[object] = None
    q_hist: Optional[jnp.ndarray] = None  # (cap, K) stale true-state ring
    # Pull-policy state (None unless policy is jiq/hsq): the balancer-side
    # token pool plus its counters.
    tokens: Optional[jnp.ndarray] = None  # (K,) i32 balancer token pool
    token_miss: Optional[jnp.ndarray] = None  # () i32 empty-pool routings
    token_sum: Optional[jnp.ndarray] = None  # () i32 summed pool occupancy
    # The servers' FIFOs (fifo.carriage); last, so that the calendar's
    # leaves close the loop carry.
    fifo: "fifo_lib.Calendar | fifo_lib.JobRing" = dataclasses.field(
        kw_only=True)


def _prep(key: jax.Array, static: StaticConfig, scn: Scenario):
    """Draw the replayable workload: (arrive, sizes, slot_keys, active,
    classes, net_keys, fault_keys), the last three None where their kind
    is off.

    Fully traceable in the scenario operands (the arrival and service
    *kinds* alone are static), so a grid of cells shares one compiled
    workload generator.  The arrival rate is modulated by the diurnal
    curve (``1 + amp * sin``; exactly 1.0 when ``amp == 0``) and masked by
    the traced ``horizon``: slots at ``t >= horizon`` never see an arrival
    and are frozen by the scan body (``active`` mask).
    """
    k_arr, k_size, k_scan = jax.random.split(key, 3)
    t = static.slots
    t_idx = jnp.arange(t, dtype=jnp.int32)
    mod = workload_lib.diurnal_modulation(
        t_idx, scn.diurnal_amp, scn.diurnal_period
    )
    if static.arrival == "mmpp":
        arrive = workload_lib.mmpp_arrivals_from_rates(
            k_arr, t, scn.lam_hi, scn.lam_lo, scn.burst_stay, mod=mod
        )
    else:
        arrive = workload_lib.bernoulli_arrivals(k_arr, t, scn.load, mod=mod)
    active = t_idx < scn.horizon
    arrive = arrive & active
    sizes = workload_lib.service_sizes(k_size, t, scn.service)
    slot_keys = jax.random.split(k_scan, t)
    # Class / control-plane randomness comes from fold_in-derived side
    # streams so the three historical children of `key` -- and therefore
    # the whole single-class "none"-kind sample path -- stay byte-stable.
    classes = net_keys = fault_keys = None
    if static.classes > 1:
        classes = workload_lib.arrival_classes(
            jax.random.fold_in(key, 13), t, scn.class_mix
        )
    if static.network != "none":
        net_keys = jax.random.split(jax.random.fold_in(key, 7), t)
    if static.fault != "none":
        fault_keys = jax.random.split(jax.random.fold_in(key, 11), t)
    return arrive, sizes, slot_keys, active, classes, net_keys, fault_keys


def _sim_core(draws, static: StaticConfig, scn: Scenario, axis_name=None):
    """One full slotted run of :func:`_prep`'s ``draws`` as a lax.scan;
    traceable (also under vmap).

    ``static`` selects code paths (Python ``if`` on kinds); every numeric
    scenario knob enters as a traced operand of ``scn``.  ``active`` is
    the per-slot horizon mask: on inactive slots every carry field is
    frozen (no service, no emulation drain, no trigger evaluation), so a
    padded scan produces exactly the state a shorter scan would leave
    behind.  ``axis_name`` names the ``vmap`` axis the run is batched
    over, if any (see ``fifo.Calendar.admit``).  Returns the run's
    :class:`_RunOut`.
    """
    arrive, sizes, slot_keys, active, classes, net_keys, fault_keys = draws
    k = static.servers
    b = static.buffer_cap
    if scn.service.kind != static.service:
        raise ValueError(
            f"Scenario service kind {scn.service.kind!r} does not match "
            f"StaticConfig.service {static.service!r}"
        )
    acfg = approx_lib.ApproxConfig(
        kind=static.approx, msr_slots=scn.service.msr_slots, x=scn.x
    )
    ccfg = comm_lib.CommConfig(
        kind=static.comm, x=scn.x, rt_period=scn.rt_period
    )
    has_net = static.network != "none"
    has_ack = has_net and static.transport == "ack"
    has_fault = static.fault != "none"
    has_cls = static.classes > 1
    has_pull = static.policy in routing_lib.PULL_POLICIES
    if has_pull and static.comm != static.policy:
        raise ValueError(
            f"policy={static.policy!r} requires comm={static.policy!r} "
            f"(its token channel), got comm={static.comm!r}"
        )
    if static.comm in comm_lib.PULL_KINDS and not has_pull:
        raise ValueError(
            f"comm={static.comm!r} is the token channel of "
            f"policy={static.comm!r}, got policy={static.policy!r}"
        )
    if has_net and static.comm == "exact":
        raise ValueError(
            "comm='exact' cannot run through the network model: its "
            "per-departure message accounting (Prop 6.1) assumes instant "
            "delivery -- use comm='dt' with x=1 under network='net'"
        )
    ncfg = comm_lib.network_config(static.network, static.transport, scn)
    # Under a modeled network the query policies route on *stale* true
    # state: the 2d SQ(d) probes (and JSQ's state feed) suffer the same
    # delivery delay as push messages, read from a ring of end-of-slot
    # queue snapshots.  Delay 0 reads the previous slot's end state ==
    # this slot's pre-route state, bit-identical to the instant path.
    stale_ring = has_net and static.policy in ("jsq", "sq2", "sqd")
    cap = static.net_delay_cap
    if static.use_rates:
        rates = scn.service_rates
        # Expected per-job drain time E[S]/r_i in slots, precomputed once
        # outside the scan: both the mean and the rates are traced.  The
        # formula lives in routing.py so the serving tier's drain-time
        # policy cannot drift from this one.
        drain_slots = (
            routing_lib.expected_drain_slots(scn.service.mean, rates)
            if static.rate_aware
            else None
        )
    else:
        rates = None
        drain_slots = None
    t = arrive.shape[0]

    def slot(c: _Carry, xs):
        arr, size, jid, skey, act, cls_t, nkey, fkey = xs

        # --- 0. fault transitions -------------------------------------
        with jax.named_scope("faults"):
            # The server fault chain advances first: this slot's service (and
            # trigger suppression) sees this slot's fault state, matching the
            # numpy serving reference.  Frozen past the horizon.
            if has_fault:
                fault_u = jax.random.uniform(fkey, (k,), jnp.float32)
                faulted, recovered = workload_lib.fault_transitions(
                    c.fault_state, fault_u, scn.crash_rate, scn.recover_rate
                )
                faulted = jnp.where(act, faulted, c.fault_state)
                recovered = recovered & act
            else:
                faulted = recovered = None

        # --- 1. arrival & routing -------------------------------------
        with jax.named_scope("route"):
            if stale_ring:
                hist_idx = jid - 1 - scn.net_delay
                q_route = jnp.where(hist_idx >= 0, c.q_hist[hist_idx % cap], 0)
            else:
                q_route = c.q_true
            if has_ack:
                # Under the ack transport suspect masking is keepalive-driven:
                # the balancer reads its last-heard clock (reset by any data
                # *or* keepalive delivery), and a server that abandoned an
                # update after max_retries is a self-suspect regardless of
                # age.  An all-suspect fleet falls back to all-healthy -- the
                # balancer must route somewhere.
                h = (
                    (scn.suspect_age <= 0) | (c.net.ka_age <= scn.suspect_age)
                ) & ((scn.suspect_age <= 0) | ~c.net.gave_up)
                healthy = jnp.where(jnp.any(h), h, True)
            elif has_net or has_fault:
                # Staleness timeout: a server whose last delivered update is
                # older than suspect_age is suspect and excluded from the
                # shortest-queue candidate set (suspect_age 0 disables -- the
                # all-True mask is decision-identical to no mask).  Without a
                # network model delivery is instant, so the trigger counter
                # slots_since_msg *is* the update age.
                age = c.net.age if has_net else c.comm.slots_since_msg
                healthy = (scn.suspect_age <= 0) | (age <= scn.suspect_age)
            else:
                healthy = None
            if has_cls or static.constrained:
                # Per-class affinity constrains the candidate set; composed
                # with the suspect mask, an empty intersection falls back to
                # the affinity set alone (the SLA constraint is hard, the
                # staleness heuristic is soft) -- mirroring the SQ(d)-subset
                # fallback of the serving tier.  With a single constrained
                # class there is no class stream: every arrival reads row 0.
                aff = scn.class_affinity[cls_t if has_cls else 0]
                if healthy is not None:
                    both = aff & healthy
                    mask = jnp.where(jnp.any(both), both, aff)
                else:
                    mask = aff
            else:
                mask = healthy
            server, rr_ptr = routing_lib.route(
                static.policy, q_route, c.emu.q_app, c.rr_ptr, skey,
                d=static.sqd, drain_slots=drain_slots,
                deterministic=static.deterministic_ties,
                mask=mask, tokens=c.tokens,
            )
            # Dense one-hot arithmetic instead of scalar gathers / scatters /
            # conds: under vmap those lower to serial per-batch-element loops
            # (or both-branch selects), which destroys the batched-scan
            # throughput; elementwise (K,) ops stay fully vectorised.
            onehot = jnp.arange(k, dtype=jnp.int32) == server
            if has_pull:
                # The balancer spends one token on every routed arrival (it
                # cannot see FIFO drops); an empty selected pool is a token
                # miss -- the uniform-random fallback path.
                tok_sel = jnp.sum(jnp.where(onehot, c.tokens, 0))
                token_miss = c.token_miss + (arr & (tok_sel == 0)).astype(
                    jnp.int32
                )
                tokens = jnp.maximum(
                    c.tokens - (onehot & arr).astype(jnp.int32), 0
                )
            else:
                token_miss = c.token_miss
                tokens = c.tokens
            q_sel = jnp.sum(jnp.where(onehot, c.q_true, 0))
            # A full FIFO drops the arrival (counted) rather than letting the
            # tail wrap onto the live head entry.
            admit = arr & (q_sel < b)
            dropped = c.dropped + (arr & ~admit).astype(jnp.int32)
            sel = onehot & admit
            fifo, adm_out = c.fifo.admit(
                jid, server, onehot, admit, sel, c.q_true, q_sel, size,
                scn.horizon, axis_name,
            )
            q_true = c.q_true + sel.astype(jnp.int32)
            emu = approx_lib.emu_arrival_masked(c.emu, sel, acfg)
            arrs = c.arrs + admit.astype(jnp.int32)
            per_srv = c.per_srv + sel.astype(jnp.int32)

        # --- 2. service ------------------------------------------------
        with jax.named_scope("service"):
            # The units each server works this slot: the drain emulates
            # the nominal ones, a fault model changes what the FIFO gets.
            units = (workload_lib.service_units(jid, rates)
                     if rates is not None else None)
            if has_fault:
                eff_units = workload_lib.faulted_service_units(
                    jid, faulted,
                    jnp.ones((k,), jnp.int32) if units is None else units,
                    static.fault, scn.slow_factor, rates=rates,
                )
            else:
                eff_units = units
            fifo, dep, dep_out = fifo.depart(jid, q_true, act, eff_units,
                                             sizes)
            q_true = jnp.where(dep, q_true - 1, q_true)
            deps = c.deps + jnp.sum(dep, dtype=jnp.int32)

        # --- 3. emulation drain -----------------------------------------
        with jax.named_scope("drain"):
            emu = approx_lib.emu_drain_slot(emu, acfg, units=units, active=act)

        # --- 4/5. communication trigger (shared core, comm.py) ----------
        with jax.named_scope("trigger"):
            # The trigger counters (slots_since_msg in particular) must freeze
            # past the horizon, or RT/ET+RT cells would keep messaging through
            # the padding; evaluate unconditionally, then select the advanced
            # state only on active slots (the identity when act is True).
            err = approx_lib.approximation_error(emu, q_true)
            # Crashed servers cannot send (their counters keep advancing, so
            # the first healthy slot re-fires); a recovery force-sends a
            # resync.  The emulation keeps draining with *nominal* units --
            # the balancer is fault-unaware, so a crash or slowdown grows the
            # error until the trigger or the staleness timeout reacts.
            if has_fault and static.fault == "crash":
                can_send, force = ~faulted, recovered
            else:
                can_send = force = None
            triggered, comm_adv = comm_lib.evaluate(
                c.comm, ccfg, err, dep.astype(jnp.int32),
                can_send=can_send, force=force, q=q_true,
                count_msgs=not has_net,
            )
            triggered = triggered & act
            if has_ack:
                # The ack/keepalive channels draw from a third child of the
                # per-slot net key, so the fire_forget two-way split -- and
                # with it every pre-existing sample path -- stays byte-stable.
                kd, kj, ka = jax.random.split(nkey, 3)
                delivered, payload, sent, net_adv = comm_lib.net_step_ack(
                    c.net, ncfg, triggered, q_true,
                    jax.random.uniform(kd, (k,), jnp.float32),
                    jax.random.uniform(kj, (k,), jnp.float32),
                    jax.random.uniform(ka, (4, k), jnp.float32),
                    can_send=can_send,
                )
            elif has_net:
                # can_send wipes a crashed server's queued piggyback so it
                # cannot send its pre-crash snapshot at the next free slot --
                # the recovery resync (force) is the re-announcement path.
                kd, kj = jax.random.split(nkey)
                delivered, payload, sent, net_adv = comm_lib.net_step(
                    c.net, ncfg, triggered, q_true,
                    jax.random.uniform(kd, (k,), jnp.float32),
                    jax.random.uniform(kj, (k,), jnp.float32),
                    can_send=can_send,
                )
            if has_net:
                delivered = delivered & act
                net_state = jax.tree.map(
                    lambda adv, old: jnp.where(act, adv, old), net_adv, c.net
                )
                # net_step owns wire accounting (piggybacking batches queued
                # triggers into one send).
                comm_adv = comm_lib.CommState(
                    deps_since_msg=comm_adv.deps_since_msg,
                    slots_since_msg=comm_adv.slots_since_msg,
                    msgs=comm_adv.msgs + jnp.where(act, sent, 0),
                )
                snap_mask, snap_payload = delivered, payload
            else:
                net_state = c.net
                snap_mask, snap_payload = triggered, q_true
            if has_net and static.policy in ("sq2", "sqd"):
                # SQ(d)'s query implementation costs 2d messages per offered
                # arrival (d probes + d replies), now counted as real traffic
                # on the same axis as the push-based schemes.  The probes ride
                # the same network: their staleness is the q_hist ring above
                # (they are not subject to loss -- a query that must be
                # re-issued would stall the arrival, so d is effectively the
                # retry budget).
                d_q = 2 if static.policy == "sq2" else static.sqd
                comm_adv = comm_lib.CommState(
                    deps_since_msg=comm_adv.deps_since_msg,
                    slots_since_msg=comm_adv.slots_since_msg,
                    msgs=comm_adv.msgs + 2 * d_q * arr.astype(jnp.int32),
                )
            comm_state = jax.tree.map(
                lambda adv, old: jnp.where(act, adv, old), comm_adv, c.comm
            )
            emu = approx_lib.emu_message_reset(
                emu, snap_payload, snap_mask, acfg
            )
            if has_pull:
                # A delivered token message overwrites that server's pool
                # entry from the queue snapshot it carried: 1 iff idle for
                # JIQ, the headroom below the threshold for hsq.  Stale
                # tokens of a crashed server are spent and never refreshed,
                # which is what bounds its misroutes.
                if static.comm == "jiq":
                    fresh = (snap_payload == 0).astype(jnp.int32)
                else:  # hsq
                    fresh = jnp.maximum(scn.x - snap_payload, 0).astype(
                        jnp.int32
                    )
                tokens = jnp.where(snap_mask, fresh, tokens)
                token_sum = c.token_sum + jnp.where(
                    act, jnp.sum(tokens), 0
                ).astype(jnp.int32)
            else:
                token_sum = c.token_sum

        # --- 6. metrics ---------------------------------------------------
        with jax.named_scope("metrics"):
            if stale_ring:
                q_hist = c.q_hist.at[jid % cap].set(
                    jnp.where(act, q_true, c.q_hist[jid % cap])
                )
            else:
                q_hist = c.q_hist
            aq = jnp.max(jnp.abs(q_true - emu.q_app))
            gap = jnp.max(q_true) - jnp.min(q_true)
            carry = _Carry(
                q_true=q_true,
                emu=emu,
                comm=comm_state,
                rr_ptr=rr_ptr,
                deps=deps,
                arrs=arrs,
                dropped=dropped,
                per_srv=per_srv,
                max_aq=jnp.maximum(c.max_aq, aq),
                max_q=jnp.maximum(c.max_q, jnp.max(q_true)),
                gap_sup=jnp.maximum(c.gap_sup, gap),
                fault_state=faulted,
                net=net_state,
                q_hist=q_hist,
                tokens=tokens,
                token_miss=token_miss,
                token_sum=token_sum,
                fifo=fifo,
            )
        return carry, (adm_out, dep_out)

    carriage = fifo_lib.carriage(static)
    init = _Carry(
        q_true=jnp.zeros((k,), jnp.int32),
        emu=approx_lib.EmuState.init(jnp.zeros((k,), jnp.int32), acfg),
        comm=comm_lib.CommState.init(k),
        rr_ptr=jnp.zeros((), jnp.int32),
        deps=jnp.zeros((), jnp.int32),
        arrs=jnp.zeros((), jnp.int32),
        dropped=jnp.zeros((), jnp.int32),
        per_srv=jnp.zeros((k,), jnp.int32),
        max_aq=jnp.zeros((), jnp.int32),
        max_q=jnp.zeros((), jnp.int32),
        gap_sup=jnp.zeros((), jnp.int32),
        fault_state=jnp.zeros((k,), bool) if has_fault else None,
        net=(
            (comm_lib.AckNetState.init(k) if has_ack else comm_lib.NetState.init(k))
            if has_net
            else None
        ),
        q_hist=jnp.zeros((cap, k), jnp.int32) if stale_ring else None,
        tokens=jnp.zeros((k,), jnp.int32) if has_pull else None,
        token_miss=jnp.zeros((), jnp.int32) if has_pull else None,
        token_sum=jnp.zeros((), jnp.int32) if has_pull else None,
        fifo=carriage.init(k, t, b),
    )
    xs = (arrive, sizes, jnp.arange(t, dtype=jnp.int32), slot_keys, active,
          classes, net_keys, fault_keys)
    final, ys = jax.lax.scan(slot, init, xs)

    # The fields that are zeros here are outputs of every program; the
    # None ones exist only where their kind is on.
    return _RunOut(
        arrive=arrive,
        comp_slot=carriage.completions(ys),
        msgs=final.comm.msgs,
        deps=final.deps,
        arrs=final.arrs,
        max_aq=final.max_aq,
        max_q=final.max_q,
        per_srv=final.per_srv,
        final_q=final.q_true,
        dropped=final.dropped,
        gap_sup=final.gap_sup,
        net_drops=final.net.drops if has_net else jnp.zeros((), jnp.int32),
        token_misses=(final.token_miss if has_pull
                      else jnp.zeros((), jnp.int32)),
        token_sum=final.token_sum if has_pull else jnp.zeros((), jnp.int32),
        far_departures=final.fifo.far_departures(),
        retrans=final.net.retrans if has_ack else None,
    )


def _run_one(key, scn: Scenario, static: StaticConfig, axis_name=None):
    """Workload draw + scan for one (key, scenario) pair; vmap-able
    (``axis_name``: see :func:`_sim_core`)."""
    with jax.named_scope("draw"):
        draws = _prep(key, static, scn)
    return _sim_core(draws, static, scn, axis_name)


_simulate_jit = jax.jit(_run_one, static_argnums=(2,))


_GRID_PROGRAMS: list = []  # jitted grid wrappers, one per (static, n_dev)


@functools.lru_cache(maxsize=None)
def _grid_fn(static: StaticConfig, n_dev: int):
    """The one compiled program for a whole grid: vmap inside shard_map.

    Cached per (StaticConfig, device count) -- the device count is part of
    the key so an in-process topology change can never reuse a mesh built
    for a different shard count.  ``n_dev == 1`` skips the mesh entirely
    (plain jitted vmap), which is also the path `shard=False` forces.
    """
    batched = jax.vmap(
        lambda key, scn: _run_one(key, scn, static, fifo_lib.BATCH_AXIS),
        axis_name=fifo_lib.BATCH_AXIS,
    )
    fn = jax.jit(shard_runs(batched, n_dev, 2))
    _GRID_PROGRAMS.append(fn)
    return fn


def shard_runs(batched, n_dev: int, n_args: int):
    """Shard a vmapped per-run function's leading run axis over devices.

    ``n_dev <= 1`` returns ``batched`` unchanged.  Otherwise each of the
    first ``n_dev`` local devices runs ``1/n_dev`` of the runs (the
    caller pads the run axis to a multiple).  The scan carries start
    from constants that the per-run inputs then make device-varying, so
    the varying-axes check is off (``check_vma=False``); it is a type
    check only, and runs never communicate.
    """
    if n_dev <= 1:
        return batched
    mesh = Mesh(np.asarray(jax.local_devices()[:n_dev]), ("runs",))
    return jax.shard_map(
        batched, mesh=mesh, in_specs=(P("runs"),) * n_args,
        out_specs=P("runs"), check_vma=False,
    )


def grid_compile_count() -> int:
    """Total XLA programs compiled by the grid path so far.

    Sums the compiled-shape cache sizes of every (StaticConfig,
    device-count) jitted wrapper: re-invoking a cached wrapper with a new
    flattened batch length retraces and compiles a fresh executable, and
    that counts too -- this is real compile work, not wrapper
    instantiations.
    """
    return sum(f._cache_size() for f in _GRID_PROGRAMS)


def _check_pallas_static(static: StaticConfig) -> None:
    """Validate a StaticConfig against the fused kernel's restrictions.

    The mean-field kernel (``kernels/jsaq_route.care_route_pallas``)
    carries all per-server state as in-kernel loop carries and no per-job
    FIFO ring, which pins the modelling corner it reproduces exactly:
    shortest-queue routing with lowest-index ties, MSR emulation, and
    deterministic (mean-sized) jobs at unit rates -- the regime of the
    paper's mean-field / diffusion limits.  Anything else must use the
    dense reference backend.
    """
    if static.policy not in ("jsq", "jsaq"):
        raise ValueError(
            f"route_backend='pallas' supports policies 'jsq'/'jsaq', got "
            f"{static.policy!r}"
        )
    if static.approx != "msr":
        raise ValueError(
            f"route_backend='pallas' requires approx='msr', got "
            f"{static.approx!r}"
        )
    if static.service != "deterministic":
        raise ValueError(
            f"route_backend='pallas' requires service='deterministic' "
            f"(per-job sizes live in a FIFO ring the kernel does not "
            f"carry), got {static.service!r}"
        )
    if static.use_rates:
        raise ValueError(
            "route_backend='pallas' requires homogeneous unit service rates"
        )
    if not static.deterministic_ties:
        raise ValueError(
            "route_backend='pallas' requires deterministic_ties=True (the "
            "kernel breaks ties to the lowest index)"
        )
    if static.network != "none" or static.fault != "none":
        raise NotImplementedError(
            f"route_backend='pallas' does not implement the fault-injection "
            f"control plane (network={static.network!r}, "
            f"fault={static.fault!r}): care_route_pallas carries no "
            f"in-flight message buffer or fault state and would silently "
            f"compute instant-delivery, fault-free results -- use "
            f"route_backend='dense'"
        )
    if static.classes > 1 or static.constrained:
        raise NotImplementedError(
            f"route_backend='pallas' does not implement constrained "
            f"routing (classes={static.classes}, "
            f"constrained={static.constrained}): the kernel carries no "
            f"per-class affinity masks -- use route_backend='dense'"
        )


@functools.lru_cache(maxsize=None)
def _pallas_grid_fn(static: StaticConfig):
    """The one compiled program for a pallas-backend grid.

    The batched ``_prep`` (plain jnp -- identical workload stream to the
    dense backend, since only ``k_arr`` of the per-run key split feeds the
    arrival draw) builds the (N, T) arrival matrix, and a single
    ``care_route_pallas`` call advances every run as one kernel domain --
    the flattened run axis *is* the kernel's native domain axis, so no
    vmap-of-pallas is involved.  Its outputs are ``_run_one``'s, so
    ``_finalize``/:class:`SimResult` are shared; ``comp_slot`` is all -1
    (per-job completion tracking needs the FIFO ring the mean-field
    kernel deliberately drops, so JCT metrics are empty at this scale).
    """
    from repro.kernels import ops as kernel_ops

    def run(keys, scn):
        arrive = jax.vmap(lambda k, s: _prep(k, static, s)[0])(keys, scn)
        params = jnp.stack(
            [
                scn.x.astype(jnp.int32),
                scn.rt_period.astype(jnp.int32),
                scn.service.msr_slots.astype(jnp.int32),
                scn.horizon.astype(jnp.int32),
            ],
            axis=1,
        )
        _routed, q_final, per_srv, stats = kernel_ops.care_route(
            arrive.astype(jnp.int32),
            params,
            servers=static.servers,
            cap=static.buffer_cap,
            policy=static.policy,
            comm=static.comm,
        )
        n, t = arrive.shape
        comp_slot = jnp.full((n, t), -1, jnp.int32)
        return _RunOut(
            arrive=arrive,
            comp_slot=comp_slot,
            msgs=stats[:, 0],
            deps=stats[:, 1],
            arrs=stats[:, 2],
            max_aq=stats[:, 4],
            max_q=stats[:, 5],
            per_srv=per_srv,
            final_q=q_final,
            dropped=stats[:, 3],
            gap_sup=stats[:, 6],
            # No network model, no pull policies.
            net_drops=jnp.zeros((n,), jnp.int32),
            token_misses=jnp.zeros((n,), jnp.int32),
            token_sum=jnp.zeros((n,), jnp.int32),
        )

    fn = jax.jit(run)
    _GRID_PROGRAMS.append(fn)
    return fn


def _pad_indices(n: int, n_dev: int) -> np.ndarray:
    """Gather indices padding ``n`` runs up to a multiple of ``n_dev``.

    The pad entries re-run existing cells (wrap-around), so a ragged batch
    shards across *all* devices instead of falling back to one; the caller
    drops outputs beyond ``n``.  Handles ``n < n_dev`` too.
    """
    n_pad = ((n + n_dev - 1) // n_dev) * n_dev
    return np.arange(n_pad) % n


def _as_keys(keys: jax.Array | Sequence[int]) -> jax.Array:
    if isinstance(keys, jax.Array):
        return keys
    return jnp.stack([jax.random.key(int(s)) for s in keys])


def _check_diurnal_peak(static: StaticConfig, scn: Scenario) -> None:
    """Reject diurnal amplitudes whose *modulated* peak rate exceeds 1.

    ``Scenario.create`` already validates when told the arrival kind, but
    a hand-built Scenario meets its StaticConfig for the first time here
    (the host-level entry points; inside the traced core the operands are
    tracers and cannot be checked).  For mmpp the binding peak is the
    burst-state rate ``lam_hi``, not ``load``; a clipped peak would
    silently drop the long-run rate below nominal.
    """
    amp = np.asarray(scn.diurnal_amp)
    peak = np.asarray(scn.lam_hi if static.arrival == "mmpp" else scn.load)
    bad = (amp > 0) & (peak * (1.0 + amp) > 1.0 + 1e-6)
    if np.any(bad):
        raise ValueError(
            f"diurnal peak rate exceeds 1 for {int(np.sum(bad))} cell(s) "
            f"(arrival={static.arrival!r}: peak rate "
            f"{'lam_hi' if static.arrival == 'mmpp' else 'load'} * (1+amp) "
            f"must stay a probability)"
        )


def _check_control_plane(static: StaticConfig, scn: Scenario) -> None:
    """Validate network/fault operands against their static kinds.

    ``Scenario.create`` already validates when told the kinds, but a
    hand-built Scenario meets its StaticConfig for the first time here
    (host-level entry points; inside the traced core the operands are
    tracers).  Mirrors :func:`_check_diurnal_peak`; every error names the
    offending field.
    """
    delay = np.asarray(scn.net_delay)
    jitter = np.asarray(scn.net_jitter)
    drop = np.asarray(scn.net_drop)
    crash = np.asarray(scn.crash_rate)
    recover = np.asarray(scn.recover_rate)
    slow = np.asarray(scn.slow_factor)
    if (
        static.policy in routing_lib.PULL_POLICIES
        or static.comm in comm_lib.PULL_KINDS
    ):
        if static.comm != static.policy:
            raise ValueError(
                f"pull policies pair 1:1 with their token channel: "
                f"policy={static.policy!r} with comm={static.comm!r}"
            )
        if static.policy == "hsq" and np.any(np.asarray(scn.rt_rate) < 0):
            raise ValueError(
                "rt_rate (the hsq token-refresh rate) must be >= 0"
            )
    mix = np.asarray(scn.class_mix)
    if mix.shape[-1] != static.classes:
        raise ValueError(
            f"Scenario.class_mix has {mix.shape[-1]} classes but "
            f"StaticConfig.classes is {static.classes}"
        )
    aff = np.asarray(scn.class_affinity)
    if aff.shape[-2:] != (static.classes, static.servers):
        raise ValueError(
            f"Scenario.class_affinity must end in shape (classes, servers)"
            f" = ({static.classes}, {static.servers}), got {aff.shape}"
        )
    if static.network == "none":
        for name, arr, neutral in (
            ("net_delay", delay, 0),
            ("net_jitter", jitter, 0),
            ("net_drop", drop, 0),
        ):
            if np.any(arr != neutral):
                raise ValueError(
                    f"{name} is nonzero for {int(np.sum(arr != neutral))} "
                    f"cell(s) but network='none'; set network='net'"
                )
        if static.fault == "none" and np.any(np.asarray(scn.suspect_age) > 0):
            raise ValueError(
                "suspect_age > 0 needs a modeled control plane "
                "(network='net' and/or a fault kind)"
            )
    else:
        if np.any(delay < 0) or np.any(jitter < 0):
            raise ValueError("net_delay / net_jitter must be >= 0 slots")
        if np.any(drop < 0) or np.any(drop >= 1):
            raise ValueError(
                "net_drop is a probability and must be in [0, 1)"
            )
        if static.policy in ("jsq", "sq2", "sqd") and np.any(
            delay >= static.net_delay_cap
        ):
            raise ValueError(
                f"net_delay must be < net_delay_cap "
                f"({static.net_delay_cap}) for the query policies' stale "
                f"state ring, got max {int(np.max(delay))}; raise "
                f"StaticConfig.net_delay_cap"
            )
    timeout = np.asarray(scn.ack_timeout)
    base = np.asarray(scn.backoff_base)
    retries = np.asarray(scn.max_retries)
    ka = np.asarray(scn.ka_period)
    if static.transport == "ack":
        if static.network == "none":
            raise ValueError(
                "transport='ack' needs network='net' (instant lossless "
                "delivery has nothing to acknowledge)"
            )
        if np.any(timeout < 1):
            raise ValueError(
                f"ack_timeout must be >= 1 slot under transport='ack' "
                f"for {int(np.sum(timeout < 1))} cell(s)"
            )
        if np.any(base < 1):
            raise ValueError(
                "backoff_base must be >= 1 (the timeout window may only "
                "grow across retries)"
            )
        if np.any(retries < 0) or np.any(ka < 0):
            raise ValueError("max_retries / ka_period must be >= 0")
    else:
        for name, arr, neutral in (
            ("ack_timeout", timeout, 0),
            ("backoff_base", base, 1.0),
            ("max_retries", retries, 0),
            ("ka_period", ka, 0),
        ):
            if np.any(arr != neutral):
                raise ValueError(
                    f"{name} is non-neutral for "
                    f"{int(np.sum(arr != neutral))} cell(s) but "
                    f"transport='fire_forget'; set transport='ack'"
                )
    if static.fault == "none":
        for name, arr, neutral in (
            ("crash_rate", crash, 0.0),
            ("recover_rate", recover, 0.0),
            ("slow_factor", slow, 1.0),
        ):
            if np.any(arr != neutral):
                raise ValueError(
                    f"{name} is non-neutral for "
                    f"{int(np.sum(arr != neutral))} cell(s) but "
                    f"fault='none'; set fault='crash' or fault='slow'"
                )
    else:
        if np.any((crash < 0) | (crash > 1)) or np.any(
            (recover < 0) | (recover > 1)
        ):
            raise ValueError(
                "crash_rate / recover_rate are per-slot probabilities in "
                "[0, 1]"
            )
        if np.any((crash > 0) & (recover == 0)):
            raise ValueError(
                "recover_rate must be > 0 when crash_rate > 0 (faulted "
                "servers would never recover)"
            )
        if np.any((slow <= 0) | (slow > 1)):
            raise ValueError("slow_factor must be in (0, 1]")


def _finalize(out: _RunOut) -> SimResult:
    """Convert one run's fetched outputs into a host-side SimResult;
    counters the run's program lacks keep their defaults."""
    arrival_slots = np.nonzero(out.arrive)[0]
    comp = out.comp_slot[arrival_slots]
    done = comp >= 0
    jct = comp[done] - arrival_slots[done] + 1

    deps_i = int(out.deps)
    msgs_i = int(out.msgs)
    extra = {name: int(getattr(out, name))
             for name in ("far_departures", "retrans")
             if getattr(out, name) is not None}
    return SimResult(
        jct=jct.astype(np.int64),
        arrivals=int(out.arrs),
        departures=deps_i,
        messages=msgs_i,
        max_aq=int(out.max_aq),
        max_queue=int(out.max_q),
        overflow=bool(out.dropped > 0),
        per_server_arrivals=out.per_srv,
        final_q=out.final_q,
        msgs_per_departure=(msgs_i / deps_i) if deps_i else 0.0,
        queue_gap_sup=int(out.gap_sup),
        dropped=int(out.dropped),
        net_drops=int(out.net_drops),
        token_misses=int(out.token_misses),
        token_sum=int(out.token_sum),
        **extra,
    )


def simulate(key: jax.Array, cfg: SimConfig) -> SimResult:
    """Run one slotted simulation; returns host-side metrics.

    Routes through the same traced core as :func:`simulate_grid`, so all
    cells sharing a :class:`StaticConfig` share one compiled program.
    """
    static, scn = cfg.static_part(), cfg.scenario()
    _check_diurnal_peak(static, scn)
    _check_control_plane(static, scn)
    if static.route_backend == "pallas":
        _check_pallas_static(static)
        out = _pallas_grid_fn(static)(
            key[None], jax.tree.map(lambda a: a[None], scn)
        )
        return _finalize(jax.device_get(out).run(0))
    return _finalize(jax.device_get(_simulate_jit(key, scn, static)))


def grid_program(
    keys: jax.Array | Sequence[int],
    static_cfg: StaticConfig,
    scenarios: Scenario | Sequence[Scenario],
    *,
    shard: bool = True,
):
    """The compiled program and operands of one :func:`simulate_grid` call.

    Returns ``(fn, args, (c, s))``: ``fn(*args)`` is the grid run, whose
    first ``c * s`` outputs along the run axis are the flattened
    cell-major ``(cell, seed)`` runs (the rest is device padding).
    ``fn.lower(*args).compile()`` gives the program's compile time,
    memory and HLO without running it.
    """
    keys = _as_keys(keys)
    if isinstance(scenarios, Scenario):
        scn_stacked = scenarios
        c = int(jax.tree.leaves(scenarios)[0].shape[0])
    else:
        scenarios = list(scenarios)
        c = len(scenarios)
        scn_stacked = stack_scenarios(scenarios)
    _check_diurnal_peak(static_cfg, scn_stacked)
    _check_control_plane(static_cfg, scn_stacked)
    s = keys.shape[0]
    n = c * s

    # Flatten cell-major: run r = cell * S + seed.
    keys_flat = jnp.broadcast_to(keys[None], (c, s)).reshape((n,))
    scn_flat = jax.tree.map(
        lambda a: jnp.repeat(a, s, axis=0), scn_stacked
    )

    if static_cfg.route_backend == "pallas":
        # The kernel's grid axis is the flattened run axis itself; no
        # shard_map (the mean-field path targets one big accelerator).
        _check_pallas_static(static_cfg)
        return _pallas_grid_fn(static_cfg), (keys_flat, scn_flat), (c, s)

    n_dev = jax.local_device_count() if shard else 1
    idx = _pad_indices(n, n_dev)
    if len(idx) != n:
        keys_flat = keys_flat[idx]
        scn_flat = jax.tree.map(lambda a: a[idx], scn_flat)
    return _grid_fn(static_cfg, n_dev), (keys_flat, scn_flat), (c, s)


def simulate_grid(
    keys: jax.Array | Sequence[int],
    static_cfg: StaticConfig,
    scenarios: Scenario | Sequence[Scenario],
    *,
    shard: bool = True,
) -> list[list[SimResult]]:
    """Run a whole scenario grid as **one compiled program**.

    Args:
      keys: batched PRNG key array or sequence of integer seeds, shape
        ``(S,)`` -- every cell replays the same seed set.
      static_cfg: the shared program structure; every cell of the grid must
        agree on it (kinds and shapes are compile-time, by design -- see the
        module docstring).
      scenarios: ``C`` traced cells -- a sequence of unbatched
        :class:`Scenario` or an already-stacked batched Scenario.
      shard: shard the flattened ``(C*S,)`` run axis across local devices
        with ``shard_map``.  Ragged batches are padded up to the device
        count with wrap-around duplicate runs (dropped on output), so
        sharding never silently degrades to one device.

    Returns:
      ``results[c][s]`` -- one :class:`SimResult` per (cell, seed),
      bit-identical to ``simulate(key_s, cell_c)`` (asserted by
      ``tests/test_grid.py``): vmap, shard_map and padding are all
      semantics-preserving.

    The call is the host span ``simulate_grid`` (count ``runs``), whose
    children are ``.prepare`` (:func:`grid_program`), ``.run`` (the
    device program, waited for), ``.fetch`` (the outputs to the host,
    count ``bytes``) and ``.finalize`` (:class:`SimResult` per run, count
    ``jobs``, the completion times produced).
    """
    with spans.span("simulate_grid") as root:
        with spans.span("simulate_grid.prepare"):
            fn, args, (c, s) = grid_program(
                keys, static_cfg, scenarios, shard=shard
            )
        root.count(runs=c * s)
        with spans.span("simulate_grid.run"):
            out = jax.block_until_ready(fn(*args))
        with spans.span("simulate_grid.fetch", bytes=sum(
                o.nbytes for o in jax.tree.leaves(out))):
            out_np = jax.tree.map(lambda o: np.asarray(o)[: c * s], out)
        with spans.span("simulate_grid.finalize") as fin:
            results = [
                [_finalize(out_np.run(i * s + j)) for j in range(s)]
                for i in range(c)
            ]
            fin.count(jobs=sum(r.jct.size for row in results for r in row))
        root.count(
            calendar_runs=c * s if out.far_departures is not None else 0,
            far_departures=sum(
                r.far_departures for row in results for r in row
            ),
        )
    return results


def simulate_batch(
    keys: jax.Array | Sequence[int], cfg: SimConfig, *, shard: bool = True
) -> list[SimResult]:
    """Run a batch of simulations in one batched scan (one per PRNG key).

    ``keys`` is either a batched PRNG key array or a sequence of integer
    seeds.  Numerically identical to calling :func:`simulate` per key (vmap
    is semantics-preserving -- asserted by the tests), but executes every
    run in a single program: the one-cell special case of
    :func:`simulate_grid`, inheriting its ``shard_map`` sharding across
    local devices (TPU/GPU, or CPU with
    ``--xla_force_host_platform_device_count``, which ``benchmarks/run.py``
    sets) -- that is where the wall-clock win comes from on CPU, since the
    slotted scan body fuses into a compute-bound loop that a single core
    can't amortise further.  Ragged batches are padded, not unsharded.
    """
    return simulate_grid(
        keys, cfg.static_part(), [cfg.scenario()], shard=shard
    )[0]


def exact_state_messages(
    result: SimResult, policy: str, sqd: int = 2, network: str = "none"
) -> int:
    """Messages the *policy itself* fundamentally needs (paper Fig. 5).

    JSQ needs one message per departure [LXK+11]; SQ(d) needs 2d messages per
    arrival under the query implementation; RR / Random need none.  CARE
    policies report their trigger-counted messages directly.  Under a
    modeled network (``network="net"``) the SQ(d) query round-trips are
    already counted as real traffic in ``result.messages`` (and suffer the
    delivery delay), so the analytic formula would double-count them.
    """
    if policy == "jsq":
        return result.departures
    if policy in ("sq2", "sqd") and network != "none":
        return result.messages
    if policy == "sq2":
        return 4 * result.arrivals
    if policy == "sqd":
        return 2 * sqd * result.arrivals
    if policy in ("rr", "random"):
        return 0
    return result.messages
