"""Serving tier: continuous batching with a CARE request dispatcher.

This is the paper's own setting at the systems level: requests are jobs,
replica groups are servers, and the front-end dispatcher routes by
JSAQ over *approximated* per-replica queue occupancy.  Replicas mirror the
dispatcher's emulation (they know both their true state and, because
updates are deterministic, exactly what the dispatcher believes -- the
paper's information asymmetry) and send a correction message only when the
trigger of the shared protocol core (:mod:`repro.core.care.comm`, the same
RT/DT/ET/hybrid implementation the slotted and MoE-dispatch simulators use)
fires -- so dispatcher<->replica control traffic is sparse even at high
request rates.

The engine is discrete-time (slot = one decode iteration across replicas),
matching the paper's simulation setting; each replica runs continuous
batching with a fixed decode-slot budget, admitting queued requests as
slots free up.  Completion requires ``decode_len`` iterations after a
prefill cost proportional to the prompt.

Two interchangeable execution paths share one workload and one semantics:

* **numpy reference** (:class:`CareDispatcher` + :func:`run_serving_sim`)
  -- a host-side per-slot loop.  Replica state is vectorised (decode slots
  are a ``(replicas, decode_slots)`` remaining-work matrix, pending
  requests live in per-replica circular rings) but slots advance in
  Python.  This is the *pluggable* path: ``model_fn`` hooks a real
  ``decode_step`` closure into every slot (examples/serve_care.py), and it
  is the golden reference the jax path is tested against bit for bit.
* **jax engine** (:func:`serve_one` / :func:`serve_grid`) -- the same
  dynamics as a jitted fixed-horizon ``lax.scan`` with the static/traced
  split of the slotted tier: :class:`EngineStatic` fixes shapes and code
  paths (replicas, decode_slots, queue_cap, the padded scan length and
  per-slot arrival-lane width, the comm *kind*), :class:`EngineScenario`
  is a registered pytree of traced operands (trigger thresholds,
  ``msr_drain``, the effective ``horizon``).  ``serve_grid`` runs a whole
  regime ladder x seed sweep as **one compiled program** -- vmap over the
  flattened (cell x seed) axis, shard_map across local devices with
  wrap-around padding -- which is what scales the replica step past 1k
  replicas (``bench_serving``'s ``serve/replicas1024`` row).

The routing-policy axis (PR 5) lifts the hard-coded JSAQ into a static
``policy`` kind -- ``jsaq`` / ``sqd`` (SQ(d)) / ``rr`` (round robin) /
``drain`` (drain-time-aware JSAQ under heterogeneous per-replica
``decode_rates``) -- selected at trace time like the comm kind, so the
full (policy x comm) matrix of the paper's composition claim runs on both
backends.  The rates themselves are traced :class:`EngineScenario`
operands (a heterogeneous-speed ladder shares one compiled program);
replicas decode by the deterministic credit schedule of
:func:`repro.core.care.workload.service_units` and the drain-time score
reuses :func:`repro.core.care.routing.expected_drain_slots`, both shared
with the slotted tier.

Bit-identical equivalence is by construction: the workload (per-slot
arrival counts, per-request prefill/decode sizes, routing tie-break and
SQ(d) subset uniforms) is pre-sampled host-side by :func:`sample_workload`
into a :class:`ServeWorkload` both paths consume.  Arrival lanes are
padded to ``EngineStatic.max_arrivals`` with an active mask (exactly like
the padded horizon), tie-break/subset uniforms are float32 so both
backends truncate to the same ranks, and the emulated occupancy is
carried in float32 on *both* backends (the reference dispatcher switched
from float64 in PR 5 -- exact for the historical dyadic drains), so every
drain and score product is the same IEEE single-precision op and the
guarantee covers non-dyadic ``decode_rates`` too.

RNG streams (re-keyed in PR 4): the workload stream and the dispatcher's
tie-break stream are split with ``np.random.SeedSequence(seed).spawn(2)``
so arrival randomness and routing randomness are independent -- the old
engine seeded both from ``default_rng(seed)``, correlating them.  PR 5
appends a third child stream for the SQ(d) subset uniforms;
``SeedSequence`` spawning is prefix-stable, so the first two streams (and
every pre-PR 5 golden) are unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Literal, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.care import comm as comm_lib
from repro.core.care import metrics as metrics_lib
from repro.core.care import routing as routing_lib
from repro.core.care import workload as workload_lib
from repro.core.care.slotted_sim import _pad_indices, shard_runs
from repro.kernels import ops as kernel_ops

# The serving tier's routing-policy suite (paper Sec 2.1.4 restated for
# continuous batching).  All policies consume the same state vector JSAQ
# does -- the dispatcher's approximated occupancy, or the true occupancy
# under comm="exact" -- so the policy axis composes with every comm kind:
#
# * ``jsaq``  -- join the shortest (approximated) queue (the default).
# * ``sqd``   -- SQ(d): sample ``sqd`` distinct replicas from pre-drawn
#   uniforms, join the shortest among them.
# * ``rr``    -- round robin, deterministic cyclic assignment.
# * ``drain`` -- drain-time-aware JSAQ: minimise the expected drain time
#   ``occ_i * E[S] / r_i`` (``routing.expected_drain_slots``) under
#   heterogeneous per-replica ``decode_rates``; reduces to JSAQ when the
#   rates are uniform (scaling by one positive constant is
#   argmin-invariant, with an identical f32 tie set).
# * ``jiq`` / ``hsq`` -- the *pull* (server-initiated) family: replicas
#   push tokens through the matching comm kind (``comm`` must equal the
#   policy -- the token channel is the policy's other half) and the
#   dispatcher routes to the replica holding the most tokens, degrading
#   to a uniform tie-broken fallback when the pool is empty.  JIQ tokens
#   mark idle replicas; hyper-scalable-JSQ tokens carry the headroom
#   below the threshold ``x``, refreshed at least every ``rt_period``
#   slots.  Token traffic is billed on the same wire as push updates
#   (evaluate -> net_step), so the message-rate axis stays honest.
ServePolicy = Literal["jsaq", "sqd", "rr", "drain", "jiq", "hsq"]

# Pull policies: route on the dispatcher-side token pool, not a queue
# vector (mirrors routing.PULL_POLICIES for the slotted tier).
PULL_POLICIES = routing_lib.PULL_POLICIES

# Pre-drawn subset-uniform lane width of ServeWorkload.sub_u: SQ(d) cells
# need d <= SQD_MAX.  Fixed so cells differing only in policy / d share
# one workload stream (the paper's comparison method).
SQD_MAX = 8


def mean_decode_rate(decode_rates: Optional[Sequence[float]]) -> float:
    """Mean per-replica decode rate: the capacity multiplier of a profile.

    The single implementation behind every workload-stream key
    (:meth:`ServeConfig.workload_key`, :func:`run_serving_sim`, tests):
    the cached stream is keyed on this value, so all consumers must derive
    it identically or the two backends would sample different workloads.
    """
    if decode_rates is None:
        return 1.0
    return float(np.mean(np.asarray(decode_rates, np.float64)))


@dataclasses.dataclass
class Request:
    rid: int
    arrival: int
    prefill_cost: int  # slots of prefill work
    decode_len: int  # decode iterations to complete
    started: int = -1
    finished: int = -1


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_replicas: int = 8
    decode_slots: int = 16  # concurrent sequences per replica
    et_x: int = 4  # ET threshold on queue-occupancy error
    comm: str = "et"  # "et" | "dt" | "rt" | "et_rt" | "exact" | "jiq" | "hsq"
    dt_x: int = 4
    rt_period: int = 16
    msr_drain: float = 1.0  # emulated completions per slot per busy replica
    policy: ServePolicy = "jsaq"
    sqd: int = 2  # subset size of the "sqd" policy
    # Per-replica decode speeds in work units per decode iteration; None =
    # homogeneous unit rates.  Realised by the deterministic credit
    # schedule of workload.service_units, mirrored by the MSR drain.
    decode_rates: Optional[Tuple[float, ...]] = None
    # Mean request work components; the "drain" policy's E[S] term.
    mean_prefill: float = 4.0
    mean_decode: float = 64.0
    # Tie-break mode: False = pre-drawn f32-uniform rank (the historical
    # convention), True = lowest index (the Pallas kernel convention --
    # see kernels/jsaq_route.py).
    deterministic_ties: bool = False
    # Control plane (fault-injection layer; see comm.py).  network="net"
    # routes every replica->dispatcher update through comm.net_step;
    # fault runs the crash/recovery or transient-slowdown replica process.
    network: str = "none"  # "none" | "net"
    net_delay: int = 0
    net_jitter: int = 0
    net_drop: float = 0.0
    suspect_age: int = 0  # staleness bound in slots (0 = no suspect masking)
    # Wire transport (network="net" only): "fire_forget" is the historical
    # one-shot path; "ack" runs the reliable transport of
    # comm.net_step_ack (timeout/retransmit/backoff + keepalives).
    transport: str = "fire_forget"  # "fire_forget" | "ack"
    ack_timeout: int = 0  # slots a sender waits for an ack (>= 1 under ack)
    backoff_base: float = 1.0  # timeout multiplier per retransmit (>= 1)
    max_retries: int = 0  # retransmits before an update is abandoned
    ka_period: int = 0  # server keepalive period in slots (0 = none)
    fault: str = "none"  # "none" | "crash" | "slow"
    crash_rate: float = 0.0
    recover_rate: float = 0.0
    slow_factor: float = 1.0

    def comm_config(self) -> comm_lib.CommConfig:
        """This tier's trigger parameters in shared-core terms."""
        if self.comm == "et":
            return comm_lib.CommConfig(kind="et", x=self.et_x)
        if self.comm == "dt":
            return comm_lib.CommConfig(kind="dt", x=self.dt_x)
        if self.comm == "rt":
            return comm_lib.CommConfig(kind="rt", rt_period=self.rt_period)
        if self.comm == "et_rt":
            return comm_lib.CommConfig(
                kind="et_rt", x=self.et_x, rt_period=self.rt_period
            )
        if self.comm == "exact":
            return comm_lib.CommConfig(kind="exact")
        if self.comm == "jiq":
            return comm_lib.CommConfig(kind="jiq")
        if self.comm == "hsq":
            # hsq reuses the ET threshold as the queue threshold and the
            # RT period as the token-refresh period (both traced knobs).
            return comm_lib.CommConfig(
                kind="hsq", x=self.et_x, rt_period=self.rt_period
            )
        raise ValueError(f"unknown comm mode: {self.comm}")


# ---------------------------------------------------------------------------
# Grid-facing configuration: one serving cell = static structure + scenario.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """One serving grid cell as the user sees it (hashable).

    Splits into the two halves the compiled program takes:
    :meth:`static_part` (shapes + comm kind -- jit specialises on it) and
    :meth:`scenario` (traced operands).  ``load`` / ``mean_prefill`` /
    ``mean_decode`` parameterise the *host-side* workload sampler (they
    never enter the traced program; the sampled arrays do), while ``x`` /
    ``rt_period`` / ``msr_drain`` are genuinely traced -- an ET-x ladder
    shares one compiled program.
    """

    replicas: int = 8
    decode_slots: int = 16
    slots: int = 20_000
    load: float = 0.9
    comm: str = "et"  # "et" | "dt" | "rt" | "et_rt" | "exact" | "jiq" | "hsq"
    x: float = 4.0  # ET/DT threshold (traced)
    rt_period: int = 16
    msr_drain: float = 1.0
    mean_prefill: int = 4
    mean_decode: int = 64
    queue_cap: int = 512  # per-replica pending ring capacity (jax path)
    policy: ServePolicy = "jsaq"
    sqd: int = 2  # subset size of the "sqd" policy (static; <= SQD_MAX)
    # Per-replica decode speeds (hashable tuple; length == replicas).  None
    # = homogeneous unit rates.  The rates are *traced* EngineScenario
    # operands (a heterogeneous-speed ladder shares one compiled program);
    # only their presence is structural (EngineStatic.use_rates).
    decode_rates: Optional[Tuple[float, ...]] = None
    max_slots: Optional[int] = None  # padded scan length (>= slots)
    # Padded arrival-lane width; 0 = derive from the sampled batch.  Pin it
    # (e.g. to the maximum over every seed set a benchmark will submit) so
    # repeat invocations reuse one compiled shape.
    max_arrivals: int = 0
    # Routing engine for the within-slot arrival-lane loop: "dense" (the
    # golden lax.scan lane body) or "pallas" (the fused
    # kernels/jsaq_route.serve_route_pallas kernel; requires policy
    # "jsaq" and deterministic_ties).  Tie-break mode as in EngineConfig.
    route_backend: str = "dense"
    deterministic_ties: bool = False
    # Control plane (fault-injection layer; see comm.py).  The *kinds*
    # are static (trace-time code paths); every numeric knob is a traced
    # EngineScenario operand, so a delay x drop ladder shares one
    # compiled program.
    network: str = "none"  # "none" | "net"
    net_delay: int = 0
    net_jitter: int = 0
    net_drop: float = 0.0
    suspect_age: int = 0
    # Wire transport: the *kind* is static ("fire_forget" keeps the
    # historical one-shot wire, structurally absent ack state; "ack" runs
    # comm.net_step_ack) while ack_timeout / backoff_base / max_retries /
    # ka_period are traced EngineScenario operands -- a timeout ladder
    # shares one compiled program with its siblings.
    transport: str = "fire_forget"  # "fire_forget" | "ack"
    ack_timeout: int = 0
    backoff_base: float = 1.0
    max_retries: int = 0
    ka_period: int = 0
    fault: str = "none"  # "none" | "crash" | "slow"
    crash_rate: float = 0.0
    recover_rate: float = 0.0
    slow_factor: float = 1.0

    def rate_scale(self) -> float:
        """Mean decode rate: the capacity multiplier of heterogeneity."""
        return mean_decode_rate(self.decode_rates)

    def arrival_rate(self) -> float:
        """Offered per-slot arrival rate: load x service capacity."""
        mean_work = self.mean_prefill + self.mean_decode
        return (
            self.load * self.replicas * self.decode_slots
            * self.rate_scale() / mean_work
        )

    def static_part(self) -> "EngineStatic":
        if self.max_slots is not None and self.max_slots < self.slots:
            raise ValueError(
                f"max_slots ({self.max_slots}) must be >= slots ({self.slots})"
            )
        if self.policy == "sqd" and not 1 <= self.sqd <= min(
            self.replicas, SQD_MAX
        ):
            raise ValueError(
                f"sqd ({self.sqd}) must be in [1, min(replicas, {SQD_MAX})]"
            )
        if (
            self.decode_rates is not None
            and len(self.decode_rates) != self.replicas
        ):
            raise ValueError(
                f"decode_rates has {len(self.decode_rates)} entries for "
                f"{self.replicas} replicas"
            )
        if self.route_backend == "pallas":
            if self.policy != "jsaq":
                raise ValueError(
                    f"route_backend='pallas' supports policy 'jsaq' only, "
                    f"got {self.policy!r}"
                )
            if not self.deterministic_ties:
                raise ValueError(
                    "route_backend='pallas' requires deterministic_ties="
                    "True (the kernel breaks ties to the lowest index)"
                )
            if self.network != "none" or self.fault != "none":
                raise NotImplementedError(
                    f"route_backend='pallas' does not support the degraded "
                    f"control plane (network={self.network!r}, "
                    f"fault={self.fault!r}); use route_backend='dense'"
                )
        comm_lib.validate_control_plane(
            network=self.network,
            net_delay=self.net_delay,
            net_jitter=self.net_jitter,
            net_drop=self.net_drop,
            suspect_age=self.suspect_age,
            fault=self.fault,
            crash_rate=self.crash_rate,
            recover_rate=self.recover_rate,
            slow_factor=self.slow_factor,
            policy=self.policy,
            comm=self.comm,
            token_refresh=(
                float(self.rt_period) if self.policy == "hsq" else None
            ),
            transport=self.transport,
            ack_timeout=self.ack_timeout,
            backoff_base=self.backoff_base,
            max_retries=self.max_retries,
            ka_period=self.ka_period,
        )
        if self.network != "none" and self.comm == "exact":
            raise ValueError(
                "comm='exact' assumes instant delivery (per-departure "
                "accounting); it cannot compose with network="
                f"{self.network!r}"
            )
        return EngineStatic(
            replicas=self.replicas,
            decode_slots=self.decode_slots,
            queue_cap=self.queue_cap,
            slots=self.max_slots if self.max_slots is not None else self.slots,
            comm=self.comm,
            policy=self.policy,
            # Only the "sqd" policy reads the subset size; normalise it to
            # 0 otherwise so cells differing in the unused knob share one
            # compiled program instead of fragmenting the grid.
            sqd=self.sqd if self.policy == "sqd" else 0,
            use_rates=self.decode_rates is not None,
            max_arrivals=self.max_arrivals,
            route_backend=self.route_backend,
            deterministic_ties=self.deterministic_ties,
            network=self.network,
            transport=self.transport,
            fault=self.fault,
        )

    def scenario(self) -> "EngineScenario":
        return EngineScenario.create(
            load=self.load,
            x=self.x,
            rt_period=self.rt_period,
            msr_drain=self.msr_drain,
            mean_prefill=self.mean_prefill,
            mean_decode=self.mean_decode,
            horizon=self.slots,
            replicas=self.replicas,
            decode_rates=self.decode_rates,
            net_delay=self.net_delay,
            net_jitter=self.net_jitter,
            net_drop=self.net_drop,
            suspect_age=self.suspect_age,
            ack_timeout=self.ack_timeout,
            backoff_base=self.backoff_base,
            max_retries=self.max_retries,
            ka_period=self.ka_period,
            crash_rate=self.crash_rate,
            recover_rate=self.recover_rate,
            slow_factor=self.slow_factor,
        )

    def engine_config(self) -> EngineConfig:
        """The numpy-reference view of this cell's dispatcher parameters."""
        return EngineConfig(
            num_replicas=self.replicas,
            decode_slots=self.decode_slots,
            et_x=int(self.x) if float(self.x).is_integer() else self.x,
            comm=self.comm,
            dt_x=int(self.x) if float(self.x).is_integer() else self.x,
            rt_period=self.rt_period,
            msr_drain=self.msr_drain,
            policy=self.policy,
            sqd=self.sqd,
            decode_rates=self.decode_rates,
            mean_prefill=float(self.mean_prefill),
            mean_decode=float(self.mean_decode),
            deterministic_ties=self.deterministic_ties,
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
        )

    def workload_key(self) -> tuple:
        """The sampler's parameter tuple: cells sharing it share a stream.

        Keyed on the *mean* decode rate (the capacity multiplier), not the
        rate profile: a 2:1 ladder and its uniform control with the same
        mean replay one stream, and all-ones rates share the
        ``decode_rates=None`` stream -- routing/policy parameters never
        enter (the paper's comparison method).
        """
        return (
            self.replicas, self.decode_slots, self.slots, self.load,
            self.mean_prefill, self.mean_decode, self.rate_scale(),
            # Extra uniform streams of the degraded control plane --
            # drawn from prefix-stable SeedSequence children, so cells
            # with both kinds off replay the historical stream byte for
            # byte (only the *presence* of each stream keys the cache).
            self.network != "none", self.fault != "none",
            # The ack/keepalive uniform stream rides a sixth prefix-stable
            # child: its presence keys the cache, fire_forget cells keep
            # the historical 9-tuple stream bytes untouched.
            self.transport == "ack",
        )


@dataclasses.dataclass(frozen=True)
class EngineStatic:
    """Compile-time structure of the jax serving program (hashable).

    ``slots`` is the *padded* scan length (each cell's effective length is
    the traced ``EngineScenario.horizon``) and ``max_arrivals`` the padded
    per-slot arrival-lane width (lanes beyond a slot's sampled arrival
    count are masked no-ops).  ``max_arrivals=0`` means "derive from the
    sampled workload" -- :func:`serve_grid` replaces it with the batch
    maximum, rounded up so near-miss batches reuse a compiled program.
    ``policy`` / ``sqd`` select the routing code path at trace time (like
    the comm kind); ``use_rates`` switches the decode step and MSR drain
    to the heterogeneous credit schedule (the rates themselves are traced
    :class:`EngineScenario` operands).  ``trace_occupancy`` additionally
    emits the end-of-slot per-replica occupancy trace (tests / checkpoint
    fingerprints only -- it makes the program output O(slots x replicas)).
    """

    replicas: int = 8
    decode_slots: int = 16
    queue_cap: int = 512
    slots: int = 20_000
    comm: str = "et"
    policy: ServePolicy = "jsaq"
    sqd: int = 2
    use_rates: bool = False
    max_arrivals: int = 0
    trace_occupancy: bool = False
    route_backend: str = "dense"  # "dense" | "pallas" (see ServeConfig)
    deterministic_ties: bool = False
    network: str = "none"  # "none" | "net" (control-plane kind, static)
    # Wire transport kind (static, like network): "ack" swaps the carry's
    # NetState for an AckNetState and the delivery step for net_step_ack;
    # "fire_forget" keeps the historical program structure untouched.
    transport: str = "fire_forget"  # "fire_forget" | "ack"
    fault: str = "none"  # "none" | "crash" | "slow" (replica fault kind)
    # Segment-engine mode (serve_stream): ``slots`` becomes the *chunk*
    # length, the carry is threaded across jit calls (donated in place),
    # the rid ring carries arrival slots instead of request ids, and
    # completions fold into the on-device StreamMetrics accumulators
    # instead of the O(offered) comp_slot scatter.  The slot body is
    # otherwise op-identical to the fixed-horizon scan, which is what
    # makes any chunking bit-identical to the monolithic trace.
    stream: bool = False


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EngineScenario:
    """Traced scenario operands of one serving cell (a registered pytree).

    ``x`` / ``rt_period`` / ``msr_drain`` / ``decode_rates`` / ``horizon``
    are consumed by the scan as array operands, so cells sweeping them
    share one compiled program -- in particular a heterogeneous-speed
    ladder compiles once.  ``load`` rides along for reporting only;
    ``mean_prefill`` / ``mean_decode`` parameterise the host-side workload
    sampler *and* feed the ``drain`` policy's E[S] term.
    """

    load: jnp.ndarray  # () f32 (reporting)
    x: jnp.ndarray  # () f32 ET/DT threshold
    rt_period: jnp.ndarray  # () i32 RT period in slots
    msr_drain: jnp.ndarray  # () f32 emulated completions/slot/busy replica
    mean_prefill: jnp.ndarray  # () f32 (drain policy E[S] term)
    mean_decode: jnp.ndarray  # () f32 (drain policy E[S] term)
    decode_rates: jnp.ndarray  # (R,) f32 per-replica speeds (ones if unused)
    horizon: jnp.ndarray  # () i32 effective slots (<= EngineStatic.slots)
    # Degraded-control-plane operands (neutral when the kinds are "none"):
    net_delay: jnp.ndarray  # () i32 base delivery delay in slots
    net_jitter: jnp.ndarray  # () i32 extra uniform delay in [0, jitter]
    net_drop: jnp.ndarray  # () f32 i.i.d. message-drop probability
    suspect_age: jnp.ndarray  # () i32 staleness bound (0 = no masking)
    # Reliable-transport operands (neutral under transport="fire_forget";
    # a timeout x backoff ladder shares one compiled program):
    ack_timeout: jnp.ndarray  # () i32 timeout window of a new send (slots)
    backoff_base: jnp.ndarray  # () f32 window multiplier per retransmit
    max_retries: jnp.ndarray  # () i32 retransmits before abandoning
    ka_period: jnp.ndarray  # () i32 server keepalive period (0 = none)
    crash_rate: jnp.ndarray  # () f32 per-slot fault-entry probability
    recover_rate: jnp.ndarray  # () f32 per-slot fault-exit probability
    slow_factor: jnp.ndarray  # () f32 service-rate scale of fault="slow"
    # Streaming-mode warmup: completions landing before this absolute slot
    # are discarded from the StreamMetrics accumulators (transient
    # discard); inert in fixed-horizon mode.
    warmup: jnp.ndarray  # () i32

    @staticmethod
    def create(
        load: float,
        x: float = 4.0,
        rt_period: int = 16,
        msr_drain: float = 1.0,
        mean_prefill: float = 4,
        mean_decode: float = 64,
        horizon: Optional[int] = None,
        replicas: int = 8,
        decode_rates: Optional[Sequence[float]] = None,
        net_delay: int = 0,
        net_jitter: int = 0,
        net_drop: float = 0.0,
        suspect_age: int = 0,
        ack_timeout: int = 0,
        backoff_base: float = 1.0,
        max_retries: int = 0,
        ka_period: int = 0,
        crash_rate: float = 0.0,
        recover_rate: float = 0.0,
        slow_factor: float = 1.0,
        warmup: int = 0,
    ) -> "EngineScenario":
        if horizon is None:
            horizon = np.iinfo(np.int32).max
        rates = (
            jnp.ones((replicas,), jnp.float32)
            if decode_rates is None
            else jnp.asarray(decode_rates, jnp.float32)
        )
        return EngineScenario(
            load=jnp.float32(load),
            x=jnp.float32(x),
            rt_period=jnp.int32(rt_period),
            msr_drain=jnp.float32(msr_drain),
            mean_prefill=jnp.float32(mean_prefill),
            mean_decode=jnp.float32(mean_decode),
            decode_rates=rates,
            horizon=jnp.int32(horizon),
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
            warmup=jnp.int32(warmup),
        )


def stack_scenarios(scenarios: Sequence[EngineScenario]) -> EngineScenario:
    """Stack unbatched cells into one batched scenario (leading axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *scenarios)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class StreamMetrics:
    """On-device streaming JCT/message accumulators (segment-engine carry).

    At soak scale (1e7+ slots) completion records cannot be concatenated
    host-side, so the chunk carry folds every completion into O(1) state
    the moment it happens:

    * ``count`` / ``mean`` / ``m2`` -- Welford running mean and sum of
      squared deviations over post-warmup JCTs, combined per slot with
      Chan's parallel-batch rule.  The combine happens *inside* the scan
      for each slot's completion batch, so the accumulator trajectory is
      independent of how the stream is chunked -- any chunking is
      bit-identical.  f32: good to ~1e7 completions before the n/(n+b)
      ratios lose single-precision mass; tail quantiles never rely on it.
    * ``hist`` -- the fixed-bucket log-spaced JCT histogram of
      :func:`repro.core.care.metrics.jct_bucket` (exact integer
      bucketing), the robust source of tail quantiles at any scale.
    * ``max_jct`` -- exact running maximum.

    Message/drop totals live where they always did (``CommState.msgs``,
    ``NetState.drops``) -- the carry threads them across chunks unchanged.
    """

    count: jnp.ndarray  # () i32 post-warmup completions
    mean: jnp.ndarray  # () f32 running mean JCT
    m2: jnp.ndarray  # () f32 running sum of squared deviations
    max_jct: jnp.ndarray  # () i32 exact max JCT
    hist: jnp.ndarray  # (metrics.HIST_BUCKETS,) i32 log-bucket counts

    @staticmethod
    def init() -> "StreamMetrics":
        return StreamMetrics(
            count=jnp.zeros((), jnp.int32),
            mean=jnp.zeros((), jnp.float32),
            m2=jnp.zeros((), jnp.float32),
            max_jct=jnp.zeros((), jnp.int32),
            hist=jnp.zeros((metrics_lib.HIST_BUCKETS,), jnp.int32),
        )

    def update(self, jct: jnp.ndarray, meas: jnp.ndarray) -> "StreamMetrics":
        """Fold one slot's completion batch in (``meas`` masks ``jct``).

        Chan's batch combine in f32 -- per slot, never per chunk, so the
        result cannot depend on chunk boundaries.  A slot with no measured
        completions is an exact no-op on every field.
        """
        n_b = jnp.sum(meas, dtype=jnp.int32)
        has = n_b > 0
        jf = jct.astype(jnp.float32)
        n_bf = n_b.astype(jnp.float32)
        mean_b = jnp.sum(jnp.where(meas, jf, 0.0)) / jnp.maximum(n_bf, 1.0)
        m2_b = jnp.sum(jnp.where(meas, (jf - mean_b) ** 2, 0.0))
        n_af = self.count.astype(jnp.float32)
        tot = jnp.maximum(n_af + n_bf, 1.0)
        delta = mean_b - self.mean
        mean = jnp.where(has, self.mean + delta * n_bf / tot, self.mean)
        m2 = jnp.where(
            has, self.m2 + m2_b + delta * delta * n_af * n_bf / tot, self.m2
        )
        bucket = jnp.where(
            meas, metrics_lib.jct_bucket(jct, xp=jnp), metrics_lib.HIST_BUCKETS
        ).reshape(-1)
        hist = self.hist.at[bucket].add(1, mode="drop")
        max_jct = jnp.maximum(self.max_jct, jnp.max(jnp.where(meas, jct, 0)))
        return StreamMetrics(
            count=self.count + n_b, mean=mean, m2=m2, max_jct=max_jct,
            hist=hist,
        )


# ---------------------------------------------------------------------------
# Host-side workload sampling: one replayable stream both backends consume.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ServeWorkload:
    """Pre-sampled request stream (host-side numpy; rid = arrival order).

    Drawn once per (cell workload parameters, seed) and consumed by both
    the numpy reference and the jax scan, so the two are bit-identical by
    construction.  ``tie_u`` is float32 *at the source*: both backends
    compute the tie-break rank as ``int(f32(u) * f32(n_ties))``, so the
    f32 traced path cannot round differently from the host path.
    ``sub_u`` carries SQ(d)'s per-request subset uniforms (a third
    independent ``SeedSequence`` child) -- also float32 at the source, fed
    to the shared :func:`subset_mask` derivation on both backends.
    """

    n_arr: np.ndarray  # (T,) int64 arrivals per slot
    base: np.ndarray  # (T,) int64 rid of the first arrival in each slot
    prefill: np.ndarray  # (N,) int64 per-request prefill cost (>= 1)
    decode: np.ndarray  # (N,) int64 per-request decode length (>= 1)
    work: np.ndarray  # (N,) int64 total slot occupancy, max(p + d, 1)
    tie_u: np.ndarray  # (N,) float32 routing tie-break uniforms
    sub_u: np.ndarray  # (N, SQD_MAX) float32 SQ(d) subset uniforms
    arrival_slot: np.ndarray  # (N,) int64
    # Degraded-control-plane uniform streams (independent SeedSequence
    # children; None unless the corresponding kind is on, so the base
    # stream bytes never move): message-drop and jitter draws per
    # (slot, replica), and the fault-chain transition draws.
    net_drop_u: Optional[np.ndarray] = None  # (T, R) float32
    net_jit_u: Optional[np.ndarray] = None  # (T, R) float32
    fault_u: Optional[np.ndarray] = None  # (T, R) float32
    # Ack/keepalive-channel uniforms (transport="ack" only): rows are
    # (ack drop, ack jitter, ka drop, ka jitter) per net_step_ack.
    ack_u: Optional[np.ndarray] = None  # (T, 4, R) float32

    @property
    def total(self) -> int:
        return int(self.work.shape[0])


def sample_workload(
    seed: int,
    *,
    replicas: int,
    decode_slots: int,
    slots: int,
    load: float,
    mean_prefill: float = 4,
    mean_decode: float = 64,
    rate_scale: float = 1.0,
    with_net: bool = False,
    with_fault: bool = False,
    with_ack: bool = False,
) -> ServeWorkload:
    """Draw the replayable serving workload for one (parameters, seed).

    Streams are split with ``SeedSequence.spawn``: arrivals/sizes, routing
    tie-breaks and SQ(d) subset draws come from independent child streams,
    so changing one consumption (e.g. comparing policies, which route
    differently) can never perturb the offered workload and vice versa.
    ``rate_scale`` is the mean per-replica decode rate -- heterogeneous
    ``decode_rates`` scale the offered capacity without re-keying the
    tie-break or subset streams.  ``with_net`` / ``with_fault`` draw the
    degraded-control-plane uniforms from two further children (3 and 4);
    ``SeedSequence`` spawning is prefix-stable, so turning them on cannot
    move the first three streams -- a fault ladder replays the exact
    arrival/tie-break bytes of its fault-free control.  ``with_ack``
    (``transport="ack"``) draws the ack/keepalive-channel uniforms from a
    sixth child -- again prefix-stable, so an ack cell replays its
    fire-and-forget control's bytes on every other stream.
    """
    w_ss, r_ss, s_ss, n_ss, f_ss, a_ss = (
        np.random.SeedSequence(int(seed)).spawn(6)
    )
    wrng = np.random.default_rng(w_ss)
    rrng = np.random.default_rng(r_ss)
    srng = np.random.default_rng(s_ss)
    mean_work = mean_prefill + mean_decode
    rate = load * replicas * decode_slots * rate_scale / mean_work
    n_arr = wrng.poisson(rate, size=slots).astype(np.int64)
    total = int(n_arr.sum())
    prefill = 1 + wrng.poisson(mean_prefill, size=total).astype(np.int64)
    decode = 1 + wrng.poisson(mean_decode, size=total).astype(np.int64)
    work = np.maximum(prefill + decode, 1)
    tie_u = rrng.random(size=total, dtype=np.float32)
    sub_u = srng.random(size=(total, SQD_MAX), dtype=np.float32)
    base = np.concatenate([[0], np.cumsum(n_arr)[:-1]]).astype(np.int64)
    arrival_slot = np.repeat(np.arange(slots, dtype=np.int64), n_arr)
    net_drop_u = net_jit_u = fault_u = ack_u = None
    if with_net:
        nrng = np.random.default_rng(n_ss)
        net_drop_u = nrng.random(size=(slots, replicas), dtype=np.float32)
        net_jit_u = nrng.random(size=(slots, replicas), dtype=np.float32)
    if with_fault:
        frng = np.random.default_rng(f_ss)
        fault_u = frng.random(size=(slots, replicas), dtype=np.float32)
    if with_ack:
        arng = np.random.default_rng(a_ss)
        ack_u = arng.random(size=(slots, 4, replicas), dtype=np.float32)
    return ServeWorkload(
        n_arr=n_arr, base=base, prefill=prefill, decode=decode,
        work=work, tie_u=tie_u, sub_u=sub_u, arrival_slot=arrival_slot,
        net_drop_u=net_drop_u, net_jit_u=net_jit_u, fault_u=fault_u,
        ack_u=ack_u,
    )


@functools.lru_cache(maxsize=512)
def _cached_workload(key: tuple, seed: int) -> ServeWorkload:
    (replicas, decode_slots, slots, load, mean_prefill, mean_decode,
     rate_scale, with_net, with_fault, with_ack) = key
    return sample_workload(
        seed, replicas=replicas, decode_slots=decode_slots, slots=slots,
        load=load, mean_prefill=mean_prefill, mean_decode=mean_decode,
        rate_scale=rate_scale, with_net=with_net, with_fault=with_fault,
        with_ack=with_ack,
    )


def workload_for(cell: ServeConfig, seed: int) -> ServeWorkload:
    """The (memoised) workload of one cell x seed.  Cells differing only
    in comm kind / thresholds share the stream -- the paper's comparison
    method (identical input replayed under every policy)."""
    return _cached_workload(cell.workload_key(), int(seed))


def pick_min_tied(
    occ: np.ndarray,
    u: float,
    mask: Optional[np.ndarray] = None,
    deterministic: bool = False,
) -> int:
    """Index of the minimum of ``occ``; ties broken by the uniform ``u``.

    The rank is computed in float32 (``int(f32(u) * f32(n_ties))``) so the
    traced f32 engine reproduces the choice bit for bit; ``u`` must come
    from a float32 draw (``ServeWorkload.tie_u``) for that guarantee.

    ``deterministic=True`` ignores ``u`` and resolves ties to the lowest
    index -- the Pallas routing-kernel convention (rank 0 in the shared
    rank arithmetic), so every backend of the serving tier (this numpy
    reference, the traced lane, the fused kernel) picks the same replica
    on the same state vector.

    ``mask`` (optional, bool ``(R,)``) restricts the minimum to a candidate
    subset -- the SQ(d) path: non-candidates are lifted to ``+inf`` before
    the argmin, exactly as the traced lane does, so the tie set (and hence
    the rank arithmetic) is identical on both backends.  A single candidate
    is returned regardless of ``u``; an all-False mask returns ``-1`` (the
    engine never routes with an empty subset -- ``sqd >= 1``).
    """
    if mask is not None:
        if not mask.any():
            return -1
        occ = np.where(mask, occ, np.inf)
    ties = np.flatnonzero(occ == occ.min())
    if deterministic:
        return int(ties[0])
    rank = min(int(np.float32(u) * np.float32(len(ties))), len(ties) - 1)
    return int(ties[rank])


def subset_mask(u_row, n: int, d: int, xp=np):
    """SQ(d) candidate mask: ``d`` distinct of ``n`` replicas from uniforms.

    A partial Fisher-Yates draw consuming ``u_row[:d]`` (float32, from
    ``ServeWorkload.sub_u``): step ``i`` picks the ``k``-th of the ``n-i``
    still-available replicas with ``k = min(int(f32(u_i) * f32(n-i)),
    n-i-1)`` -- uniform over d-subsets, and pure float32/int32 arithmetic
    on either array namespace (``xp=np`` in the reference dispatcher,
    ``xp=jnp`` inside the traced lane), so both backends derive the *same*
    subset from the same pre-drawn row, bit for bit.
    """
    avail = xp.ones((n,), bool)
    mask = xp.zeros((n,), bool)
    for i in range(d):
        m = n - i  # Python int: the loop is unrolled at trace time
        u = xp.float32(u_row[i]) if xp is np else u_row[i]
        k = xp.minimum(
            (u * xp.float32(m)).astype(xp.int32), xp.int32(m - 1)
        )
        cum = xp.cumsum(avail.astype(xp.int32))
        pick = avail & (cum == k + 1)  # one-hot: k-th available replica
        mask = mask | pick
        avail = avail & ~pick
    return mask


# ---------------------------------------------------------------------------
# numpy reference: the pluggable-model_fn dispatcher (golden path).
# ---------------------------------------------------------------------------


class CareDispatcher:
    """Policy routing over approximated occupancy + shared-core triggers.

    All per-replica state is vectorised numpy: ``active_rem``/``active_rid``
    hold the decode slots (<= 0 remaining == free), ``_q_rid``/``_q_head``/
    ``_q_len`` are per-replica FIFO rings of pending request ids, and the
    trigger bookkeeping is a :class:`repro.core.care.comm.CommState`.

    ``cfg.policy`` selects the routing rule (see :data:`ServePolicy`); every
    policy consumes the same state vector JSAQ does -- the emulated
    occupancy, or the true occupancy under ``comm="exact"``.  The emulated
    occupancy is carried in **float32** (like the traced engine), so the
    bit-identity guarantee extends to non-dyadic drains and decode rates:
    both backends execute the same IEEE single-precision operations.

    ``rng`` (optional) injects the tie-break/subset streams;
    :func:`run_serving_sim` passes pre-drawn uniforms per request instead
    (``route(..., u=..., sub_u=...)``), in which case the internal stream
    is never consumed.
    """

    def __init__(
        self,
        cfg: EngineConfig,
        seed: int = 0,
        queue_cap: int = 4096,
        rng: Optional[np.random.Generator] = None,
    ):
        r, s = cfg.num_replicas, cfg.decode_slots
        if cfg.policy == "sqd" and not 1 <= cfg.sqd <= min(r, SQD_MAX):
            # Mirrors ServeConfig.static_part(): the pre-drawn sub_u rows
            # (and the rng fallback) carry SQD_MAX lanes, and a subset
            # larger than the replica set cannot be distinct.
            raise ValueError(
                f"sqd ({cfg.sqd}) must be in [1, min(num_replicas, "
                f"{SQD_MAX})]"
            )
        if (
            cfg.decode_rates is not None
            and len(cfg.decode_rates) != r
        ):
            raise ValueError(
                f"decode_rates has {len(cfg.decode_rates)} entries for "
                f"{r} replicas"
            )
        comm_lib.validate_control_plane(
            network=cfg.network,
            net_delay=cfg.net_delay,
            net_jitter=cfg.net_jitter,
            net_drop=cfg.net_drop,
            suspect_age=cfg.suspect_age,
            fault=cfg.fault,
            crash_rate=cfg.crash_rate,
            recover_rate=cfg.recover_rate,
            slow_factor=cfg.slow_factor,
            policy=cfg.policy,
            comm=cfg.comm,
            token_refresh=(
                float(cfg.rt_period) if cfg.policy == "hsq" else None
            ),
        )
        if cfg.network != "none" and cfg.comm == "exact":
            raise ValueError(
                "comm='exact' assumes instant delivery (per-departure "
                "accounting); it cannot compose with network="
                f"{cfg.network!r}"
            )
        self.cfg = cfg
        self._ccfg = cfg.comm_config()
        # Degraded control plane: per-replica in-flight message buffer
        # (network="net") and the fault mask of the crash/slow process.
        # transport="ack" swaps the wire state for an AckNetState and the
        # delivery step for net_step_ack (timeout/retransmit/backoff).
        if cfg.network != "none":
            if cfg.transport == "ack":
                self.net = comm_lib.AckNetState.init(
                    r, xp=np, payload_dtype=np.float32
                )
            else:
                self.net = comm_lib.NetState.init(
                    r, xp=np, payload_dtype=np.float32
                )
            self._ncfg = comm_lib.NetworkConfig(
                kind=cfg.network,
                delay=np.int32(cfg.net_delay),
                jitter=np.int32(cfg.net_jitter),
                drop=np.float32(cfg.net_drop),
                transport=cfg.transport,
                ack_timeout=np.int32(cfg.ack_timeout),
                backoff_base=np.float32(cfg.backoff_base),
                max_retries=np.int32(cfg.max_retries),
                ka_period=np.int32(cfg.ka_period),
            )
        else:
            self.net = None
            self._ncfg = None
        self.faulted = (
            np.zeros(r, bool) if cfg.fault != "none" else None
        )
        self.active_rem = np.zeros((r, s), np.int64)
        self.active_rid = np.full((r, s), -1, np.int64)
        self._qcap = queue_cap
        self._q_rid = np.full((r, queue_cap), -1, np.int64)
        self._q_head = np.zeros(r, np.int64)
        self._q_len = np.zeros(r, np.int64)
        self.approx = np.zeros(r, np.float32)  # emulated occupancy (f32)
        self.comm = comm_lib.CommState.init(r, xp=np)
        self.total_completions = 0
        self.rng = rng if rng is not None else np.random.default_rng(seed)
        self._rr_ptr = 0  # round-robin pointer ("rr" policy)
        self.last_subset: Optional[np.ndarray] = None  # "sqd" diagnostics
        # Pull-policy token pool: one slot per replica, refreshed on
        # token-message *delivery* (so stale pools under a degraded
        # network mirror the traced engine exactly).  token_misses counts
        # routed arrivals that found an empty pool (the uniform fallback);
        # token_sum integrates end-of-slot pool occupancy over slots.
        if cfg.policy in PULL_POLICIES:
            self._tokens: Optional[np.ndarray] = np.zeros(r, np.int32)
        else:
            self._tokens = None
        self.token_misses = 0
        self.token_sum = 0
        # Heterogeneous decode rates: None = unit rates (the historical
        # integer fast path).  The f32 vectors mirror the traced operands
        # exactly -- same IEEE products in the MSR drain and drain score.
        if cfg.decode_rates is None:
            self._rates = None
            self._drainv = np.float32(cfg.msr_drain) * np.ones(r, np.float32)
        else:
            self._rates = np.asarray(cfg.decode_rates, np.float32)
            self._drainv = np.float32(cfg.msr_drain) * self._rates
        rates_f32 = (
            np.ones(r, np.float32) if self._rates is None else self._rates
        )
        self._drain_slots = routing_lib.expected_drain_slots(
            np.float32(cfg.mean_prefill) + np.float32(cfg.mean_decode),
            rates_f32,
        )
        # rid-indexed request metadata (grown on demand).
        self._work = np.zeros(1024, np.int64)
        self._started = np.full(1024, -1, np.int64)
        self._store: dict[int, Request] = {}

    @property
    def messages(self) -> int:
        return int(self.comm.msgs)

    def true_occupancy(self) -> np.ndarray:
        """Exact per-replica occupancy (queued + active), shape (R,)."""
        return self._q_len + (self.active_rem > 0).sum(axis=1)

    def _ensure_rid(self, rid: int):
        while rid >= self._work.shape[0]:
            self._work = np.concatenate([self._work, np.zeros_like(self._work)])
            self._started = np.concatenate(
                [self._started, np.full_like(self._started, -1)]
            )

    def _grow_queues(self):
        r = self.cfg.num_replicas
        new = np.full((r, 2 * self._qcap), -1, np.int64)
        for i in range(r):  # linearise each ring into the new buffer
            idx = (self._q_head[i] + np.arange(self._q_len[i])) % self._qcap
            new[i, : self._q_len[i]] = self._q_rid[i, idx]
        self._q_rid, self._q_head, self._qcap = new, np.zeros(r, np.int64), 2 * self._qcap

    def route(
        self,
        req: Request,
        now: int,
        u: Optional[float] = None,
        sub_u: Optional[np.ndarray] = None,
    ) -> int:
        cfg = self.cfg
        if cfg.comm == "exact":
            occ = self.true_occupancy().astype(np.float32)
        else:
            occ = self.approx
        self.last_subset = None
        # Suspect-server exclusion: a replica whose last update is older
        # than the staleness bound is excluded from the shortest-queue
        # family's candidate set (all-suspect degrades to unmasked).  The
        # staleness clock is the network age when messages are delayed,
        # else the trigger's slots-since-message counter -- RT keepalives
        # reset either one, doubling as failure detection.
        healthy = None
        if cfg.suspect_age > 0:
            if self.net is not None and cfg.transport == "ack":
                # Keepalive-driven masking: the last-heard clock counts
                # any delivery (data or keepalive), and a server that
                # abandoned an update after max_retries is a self-suspect
                # until some later transmission is acked.
                healthy = (
                    self.net.ka_age <= cfg.suspect_age
                ) & ~self.net.gave_up
            else:
                age = (
                    self.net.age if self.net is not None
                    else self.comm.slots_since_msg
                )
                healthy = age <= cfg.suspect_age
            if not healthy.any():
                healthy = np.ones_like(healthy)
        if cfg.policy == "rr":
            if healthy is None:
                j = self._rr_ptr % cfg.num_replicas
                self._rr_ptr += 1
            else:
                # Masked round robin: skip suspect replicas to the
                # cyclically-next healthy one (same derivation as the
                # traced lane and routing.route_rr -- with an all-True
                # mask the choice equals the unmasked path).
                off = (
                    np.arange(cfg.num_replicas, dtype=np.int64)
                    - self._rr_ptr
                ) % cfg.num_replicas
                off = np.where(healthy, off, cfg.num_replicas)
                j = int(np.argmin(off))
                self._rr_ptr = j + 1
        else:
            if u is None:
                u = self.rng.random(dtype=np.float32)
            det = cfg.deterministic_ties
            if cfg.policy == "sqd":
                if sub_u is None:
                    sub_u = self.rng.random(size=SQD_MAX, dtype=np.float32)
                mask = subset_mask(sub_u, cfg.num_replicas, cfg.sqd, xp=np)
                self.last_subset = mask
                if healthy is not None:
                    m = mask & healthy
                    mask = m if m.any() else mask
                j = pick_min_tied(occ, u, mask=mask, deterministic=det)
            elif cfg.policy == "drain":
                j = pick_min_tied(
                    occ * self._drain_slots, u, mask=healthy,
                    deterministic=det,
                )
            elif cfg.policy in PULL_POLICIES:
                # Spend a token: join the replica holding the most (scored
                # as -tokens through the shared tie machinery, so an empty
                # pool is an all-tie -- the uniform fallback -- and the
                # suspect mask composes like every other policy).
                j = pick_min_tied(
                    (0 - self._tokens).astype(np.float32), u,
                    mask=healthy, deterministic=det,
                )
                if self._tokens[j] == 0:
                    self.token_misses += 1
                self._tokens[j] = max(int(self._tokens[j]) - 1, 0)
            else:  # jsaq
                j = pick_min_tied(occ, u, mask=healthy, deterministic=det)
        if cfg.policy == "sqd" and self.net is not None:
            # SQ(d) is a pull scheme: each routed arrival costs d query +
            # d response messages on the wire (2d round-trips), putting
            # query-based sampling on the same honest message-rate axis
            # as CARE's push updates.
            self.comm = dataclasses.replace(
                self.comm,
                msgs=self.comm.msgs + np.int32(2 * cfg.sqd),
            )
        if self._q_len[j] >= self._qcap:
            self._grow_queues()
        self._ensure_rid(req.rid)
        # A zero-work request still occupies a decode slot for one
        # iteration (matches the pre-vectorisation engine, where the first
        # decrement completed it); without the clamp it would sit at
        # rem == 0 forever and never be marked done.
        self._work[req.rid] = max(req.prefill_cost + req.decode_len, 1)
        self._store[req.rid] = req
        tail = (self._q_head[j] + self._q_len[j]) % self._qcap
        self._q_rid[j, tail] = req.rid
        self._q_len[j] += 1
        self.approx[j] += 1  # arrival known to the dispatcher (Eq. 10)
        return j

    def step(
        self,
        now: int,
        drop_u: Optional[np.ndarray] = None,
        jit_u: Optional[np.ndarray] = None,
        fault_u: Optional[np.ndarray] = None,
        ack_u: Optional[np.ndarray] = None,
    ) -> list[Request]:
        cfg = self.cfg
        rows = np.arange(cfg.num_replicas)[:, None]

        # 0. fault transitions (before admission, like the traced slot
        # body: arrivals were routed against the previous slot's state).
        recovered = None
        if self.faulted is not None:
            if fault_u is None:
                raise ValueError(
                    "step() needs this slot's fault_u row when "
                    f"fault={cfg.fault!r} (sample_workload with_fault=True)"
                )
            self.faulted, recovered = workload_lib.fault_transitions(
                self.faulted, np.asarray(fault_u, np.float32),
                np.float32(cfg.crash_rate), np.float32(cfg.recover_rate),
                xp=np,
            )

        # 1. admit: fill free decode slots from the pending rings, FIFO.
        free = self.active_rem <= 0
        free_rank = np.cumsum(free, axis=1) - 1
        n_admit = np.minimum(self._q_len, free.sum(axis=1))
        if cfg.fault == "crash" and self.faulted is not None:
            # A crashed replica is frozen: queued requests wait (conserved)
            # and resume admission on recovery.
            n_admit = np.where(self.faulted, 0, n_admit)
        take = free & (free_rank < n_admit[:, None])
        if take.any():
            qidx = (self._q_head[:, None] + free_rank) % self._qcap
            rid = self._q_rid[rows, qidx]
            self.active_rid = np.where(take, rid, self.active_rid)
            self.active_rem = np.where(take, self._work[rid], self.active_rem)
            self._started[rid[take]] = now
            self._q_head = (self._q_head + n_admit) % self._qcap
            self._q_len = self._q_len - n_admit

        # 2. service: one decode iteration on every active slot -- one work
        # unit at unit rates, or the slot's credit-schedule units under
        # heterogeneous decode_rates (shared with the slotted tier's
        # workload.service_units; a finishing unit beyond the remaining
        # work is forfeit, so rem may go negative == free).
        active = self.active_rem > 0
        if self.faulted is not None:
            if self._rates is None:
                nominal = np.ones(cfg.num_replicas, np.int64)
            else:
                nominal = workload_lib.service_units(now, self._rates, xp=np)
            units = workload_lib.faulted_service_units(
                now, self.faulted, nominal, cfg.fault,
                np.float32(cfg.slow_factor), rates=self._rates, xp=np,
            )
            self.active_rem = self.active_rem - units[:, None] * active
        elif self._rates is None:
            self.active_rem = self.active_rem - active
        else:
            units = workload_lib.service_units(now, self._rates, xp=np)
            self.active_rem = self.active_rem - units[:, None] * active
        done = active & (self.active_rem <= 0)
        completions = done.sum(axis=1)
        finished: list[Request] = []
        if done.any():
            for rid in self.active_rid[done]:
                req = self._store.pop(int(rid))
                req.started = int(self._started[rid])
                req.finished = now
                finished.append(req)
            self.active_rid[done] = -1
        self.total_completions += int(completions.sum())

        # 3. MSR drain: emulate service at the nominal completion rate,
        # scaled per replica by its decode rate (f32, like the traced path).
        busy = self.approx > 0
        self.approx = np.maximum(
            self.approx - self._drainv * busy, np.float32(0.0)
        )

        # 4. trigger (replicas mirror the emulation exactly) -- shared core.
        # Crashed replicas cannot send (counters keep advancing, so the
        # first healthy slot re-fires a due trigger) and a recovery forces
        # a resync message regardless of the trigger predicate.
        true_occ = self.true_occupancy().astype(np.float32)
        err = np.abs(true_occ - self.approx)
        can_send = force = None
        if cfg.fault == "crash" and self.faulted is not None:
            can_send = ~self.faulted
            force = recovered
        trig, self.comm = comm_lib.evaluate(
            self.comm, self._ccfg, err, completions, xp=np,
            can_send=can_send, force=force, q=true_occ,
            count_msgs=self.net is None,
        )
        # 5. network: triggered sends traverse the in-flight buffer (delay
        # + jitter + drop, piggyback batching); the dispatcher's view only
        # advances on *delivery* of the send-time snapshot.
        if self.net is not None:
            if drop_u is None or jit_u is None:
                raise ValueError(
                    "step() needs this slot's drop_u/jit_u rows when "
                    f"network={cfg.network!r} (sample_workload "
                    "with_net=True)"
                )
            if cfg.transport == "ack":
                if ack_u is None:
                    raise ValueError(
                        "step() needs this slot's ack_u rows when "
                        "transport='ack' (sample_workload with_ack=True)"
                    )
                delivered, payload, sent, self.net = comm_lib.net_step_ack(
                    self.net, self._ncfg, trig, true_occ,
                    np.asarray(drop_u, np.float32),
                    np.asarray(jit_u, np.float32),
                    np.asarray(ack_u, np.float32), xp=np,
                    can_send=can_send,
                )
            else:
                delivered, payload, sent, self.net = comm_lib.net_step(
                    self.net, self._ncfg, trig, true_occ,
                    np.asarray(drop_u, np.float32),
                    np.asarray(jit_u, np.float32), xp=np,
                    can_send=can_send,
                )
            self.comm = dataclasses.replace(
                self.comm, msgs=self.comm.msgs + sent
            )
            self.approx = np.where(delivered, payload, self.approx)
        else:
            self.approx = np.where(trig, true_occ, self.approx)
        # 6. pull-token refresh: a delivered token message *overwrites* the
        # sender's pool slot from the send-time queue snapshot (1 if idle
        # for JIQ, the headroom below the threshold for hsq -- f32
        # arithmetic truncated to int32, matching the traced engine).  A
        # crashed replica stops sending, so its stale tokens drain to zero
        # and are never replenished -- the safe-staleness property the
        # pull frontier measures.
        if self._tokens is not None:
            if cfg.comm == "jiq":
                def _fresh(p):
                    return (p == np.float32(0.0)).astype(np.int32)
            else:  # hsq
                def _fresh(p):
                    return np.maximum(
                        np.float32(self._ccfg.x) - p, np.float32(0.0)
                    ).astype(np.int32)
            if self.net is not None:
                self._tokens = np.where(
                    delivered, _fresh(payload), self._tokens
                )
            else:
                self._tokens = np.where(
                    trig, _fresh(true_occ), self._tokens
                )
            self.token_sum += int(self._tokens.sum())
        return finished


def run_serving_sim(
    cfg: EngineConfig,
    *,
    slots: int = 20_000,
    load: float = 0.9,
    mean_decode: int = 64,
    mean_prefill: int = 4,
    seed: int = 0,
    model_fn: Optional[Callable] = None,
    workload: Optional[ServeWorkload] = None,
    checkpoints: Sequence[int] = (),
) -> dict:
    """Drive the numpy engine with a pre-sampled workload; return metrics.

    The workload (arrival counts, request sizes, tie-break uniforms) comes
    from :func:`sample_workload` -- independent ``SeedSequence`` child
    streams -- unless an explicit ``workload`` is given (the equivalence
    tests feed the same object to both backends).  ``checkpoints`` lists
    slot indices at which the exact per-replica occupancy is snapshotted
    (``out["occupancy"][slot]``, captured at end of slot, matching the jax
    engine's ``trace_occupancy`` rows).
    """
    with_net = cfg.network != "none"
    with_fault = cfg.fault != "none"
    with_ack = with_net and cfg.transport == "ack"
    if workload is None:
        rate_scale = mean_decode_rate(cfg.decode_rates)
        workload = sample_workload(
            seed, replicas=cfg.num_replicas, decode_slots=cfg.decode_slots,
            slots=slots, load=load, mean_prefill=mean_prefill,
            mean_decode=mean_decode, rate_scale=rate_scale,
            with_net=with_net, with_fault=with_fault, with_ack=with_ack,
        )
    if with_net and workload.net_drop_u is None:
        raise ValueError(
            "workload lacks the network uniform streams; sample it with "
            "with_net=True"
        )
    if with_fault and workload.fault_u is None:
        raise ValueError(
            "workload lacks the fault uniform stream; sample it with "
            "with_fault=True"
        )
    if with_ack and workload.ack_u is None:
        raise ValueError(
            "workload lacks the ack/keepalive uniform stream; sample it "
            "with with_ack=True"
        )
    # One source of truth for E[S]: the drain policy's score must use the
    # same mean work the workload was sampled with, or the two backends
    # would scale occupancies by different f32 drain_slots vectors.
    # (ServeConfig.engine_config() already passes equal values, making
    # this a no-op on the grid path.)
    cfg = dataclasses.replace(
        cfg, mean_prefill=float(mean_prefill), mean_decode=float(mean_decode)
    )
    disp = CareDispatcher(cfg, seed)

    finished: list[Request] = []
    occupancy: dict[int, np.ndarray] = {}
    want_ckpt = set(int(c) for c in checkpoints)
    for now in range(slots):
        b = int(workload.base[now])
        for i in range(int(workload.n_arr[now])):
            rid = b + i
            req = Request(
                rid=rid,
                arrival=now,
                prefill_cost=int(workload.prefill[rid]),
                decode_len=int(workload.decode[rid]),
            )
            disp.route(
                req, now, u=float(workload.tie_u[rid]),
                sub_u=workload.sub_u[rid],
            )
        finished.extend(disp.step(
            now,
            drop_u=workload.net_drop_u[now] if with_net else None,
            jit_u=workload.net_jit_u[now] if with_net else None,
            fault_u=workload.fault_u[now] if with_fault else None,
            ack_u=workload.ack_u[now] if with_ack else None,
        ))
        if now in want_ckpt:
            occupancy[now] = disp.true_occupancy().copy()
        if model_fn is not None:
            model_fn(now)

    # JCT vector in rid (arrival) order so both backends emit the same
    # vector -- the old engine returned completion order, which is a
    # per-replica interleaving the batched scan has no business replaying.
    jct_by_rid = np.full(workload.total, -1, np.int64)
    for r in finished:
        jct_by_rid[r.rid] = r.finished - r.arrival + 1
    jct = jct_by_rid[jct_by_rid >= 0]
    base_msgs = max(disp.total_completions, 1)
    return {
        "jct": jct,
        "jct_by_rid": jct_by_rid,
        "mean_jct": float(jct.mean()) if jct.size else 0.0,
        "p99_jct": float(np.percentile(jct, 99)) if jct.size else 0.0,
        "completed": len(finished),
        "offered": workload.total,
        "messages": disp.messages,
        "msgs_per_completion": disp.messages / base_msgs,
        "final_occupancy": disp.true_occupancy().copy(),
        "occupancy": occupancy,
        "requests": finished,
        "net_drops": int(disp.net.drops) if disp.net is not None else 0,
        "retrans": (
            int(disp.net.retrans)
            if disp.net is not None and cfg.transport == "ack"
            else 0
        ),
        "token_misses": int(disp.token_misses),
        "token_sum": int(disp.token_sum),
    }


# ---------------------------------------------------------------------------
# jax engine: the same dynamics as one jitted fixed-horizon lax.scan.
# ---------------------------------------------------------------------------


def _serve_core(n_arr, work, tie_u, rid, sub_u, net_du, net_ju, fault_u,
                ack_u, n_cap, scn: EngineScenario, static: EngineStatic,
                carry=None, t0=None):
    """One serving run as a ``lax.scan`` over slots; traceable under vmap.

    Inputs are the padded per-slot workload: ``n_arr (T,)`` arrival counts,
    ``work``/``tie_u``/``rid`` ``(T, A)`` arrival-lane batches (lanes
    ``>= n_arr[t]`` are masked no-ops, like slots ``>= horizon``), and
    ``sub_u (T, A, D)`` the SQ(d) subset uniforms (``D = sqd`` under the
    "sqd" policy, else 0 -- the lanes exist but carry nothing).  ``n_cap``
    (static) sizes the rid-indexed completion-slot carry.

    The slot body mirrors :class:`CareDispatcher` operation for operation:
    sequential within-slot routing (an inner scan over arrival lanes --
    each routed arrival immediately bumps the occupancy the next one
    sees), then fault transitions -> admit -> decode -> MSR drain ->
    shared-core trigger -> network delivery.  ``net_du`` / ``net_ju`` /
    ``fault_u`` are the pre-drawn ``(T, R)`` control-plane uniforms
    (zero-width ``(T, 0)`` when the corresponding kind is off, so the
    grid sharding specs are shape-stable); ``ack_u`` is the ``(T, 4, R)``
    ack/keepalive-channel stream of ``transport="ack"`` (``(T, 0, 0)``
    otherwise).
    ``static.policy`` picks the route step at trace time; the drain-time
    score and heterogeneous decode/drain rates consume the traced
    ``scn.decode_rates`` operand, so a rate ladder shares one program.

    Segment mode (``static.stream``): ``carry`` resumes a previous chunk's
    final state and ``t0`` offsets the slot clock so ``t`` is absolute
    across chunks (``act = t < horizon`` then doubles as the tail-padding
    mask of a partial last chunk, exactly like the fixed engine's padded
    horizon).  The rid lanes are ignored -- a request's identity reduces
    to its arrival slot, synthesised on device -- and completions fold
    into the :class:`StreamMetrics` carry slot-by-slot instead of the
    rid-indexed ``comp_slot`` scatter.  Every op the dynamics see (routing,
    admission, decode, drain, trigger, delivery) is identical to the fixed
    path, which is what makes any chunking bit-identical to it.
    Exactness notes: the reference dispatcher carries its approximation in
    float32 too, so every drain/score product is the same IEEE single op
    on both backends (dyadic or not); decode credits are integers from the
    shared ``workload.service_units`` schedule; tie-break and subset ranks
    are computed in f32 on both sides (:func:`pick_min_tied` /
    :func:`subset_mask`).
    """
    r_n, s_n, c_n = static.replicas, static.decode_slots, static.queue_cap
    a_n, t_n = work.shape[1], work.shape[0]
    ccfg = comm_lib.CommConfig(kind=static.comm, x=scn.x,
                               rt_period=scn.rt_period)
    has_net = static.network != "none"
    has_fault = static.fault != "none"
    has_ack = has_net and static.transport == "ack"
    if has_ack:
        ncfg = comm_lib.NetworkConfig(
            kind=static.network, delay=scn.net_delay,
            jitter=scn.net_jitter, drop=scn.net_drop,
            transport="ack", ack_timeout=scn.ack_timeout,
            backoff_base=scn.backoff_base, max_retries=scn.max_retries,
            ka_period=scn.ka_period,
        )
    elif has_net:
        ncfg = comm_lib.NetworkConfig(
            kind=static.network, delay=scn.net_delay,
            jitter=scn.net_jitter, drop=scn.net_drop,
        )
    rep_idx = jnp.arange(r_n, dtype=jnp.int32)
    # Per-replica emulated drain; msr_drain * 1.0 is exact, so the unused
    # operand cannot perturb the homogeneous path.
    drainv = scn.msr_drain * scn.decode_rates
    if static.policy == "drain":
        drain_slots = routing_lib.expected_drain_slots(
            scn.mean_prefill + scn.mean_decode, scn.decode_rates
        )
    # Pull family: the carry grows a (tokens, token_miss, token_sum)
    # triple (None otherwise -- the default program structure is
    # unchanged).  ServeConfig.static_part / CareDispatcher validated the
    # 1:1 policy<->comm pairing already.
    has_pull = static.policy in PULL_POLICIES

    def slot(carry, xs):
        # Position 9 (``comp_slot``) is the rid-indexed completion-slot
        # scatter in fixed mode and the StreamMetrics accumulators in
        # stream mode; position 5 (``arid``) holds request ids in fixed
        # mode and arrival slots in stream mode.
        (q_len, q_head, q_work, q_rid, rem, arid, approx, comm_state,
         rr_ptr, comp_slot, total_comp, dropped, net_state, faulted,
         pull_state) = carry
        (t, n_arr_t, work_t, tie_t, rid_t, sub_t, ndu_t, nju_t, fu_t,
         aku_t) = xs
        if static.stream:
            # A streamed request's identity is its arrival slot: the ring
            # stores it, completion turns it into a JCT on device.
            rid_t = jnp.full((a_n,), t, jnp.int32)
        act = t < scn.horizon
        # Decode-slot busy count is frozen during the arrival phase -- the
        # dispatcher routes against the previous slot's replica state.
        busy_cnt = (rem > 0).sum(axis=1).astype(jnp.int32)

        # Suspect-server mask (graceful degradation): computed once per
        # slot from the carried staleness clock -- the network age under
        # delayed delivery, else the trigger's slots-since-message counter
        # (RT keepalives reset either, doubling as failure detection).
        # suspect_age is a traced operand; 0 yields an all-True mask,
        # which is decision-identical to no masking on both backends.
        healthy = None
        if has_ack:
            # Keepalive-driven masking (transport="ack"): the last-heard
            # clock counts data *and* keepalive deliveries, and a server
            # that abandoned an update after max_retries (gave_up) is a
            # self-suspect until a later transmission is acked.
            h = (
                (scn.suspect_age <= 0)
                | (net_state.ka_age <= scn.suspect_age)
            ) & ((scn.suspect_age <= 0) | ~net_state.gave_up)
            healthy = jnp.where(jnp.any(h), h, True)
        elif has_net or has_fault:
            age = net_state.age if has_net else comm_state.slots_since_msg
            h = (scn.suspect_age <= 0) | (age <= scn.suspect_age)
            healthy = jnp.where(jnp.any(h), h, True)

        # --- 1. route this slot's arrivals, sequentially (inner scan) ---
        # The scan carries only the small (R,) routing state (each routed
        # arrival immediately bumps the occupancy the next one sees); the
        # ring writes are deferred and applied as one vectorised scatter
        # below -- admitted lanes never collide (successive admits to the
        # same replica take successive tails) and masked lanes are routed
        # out of bounds and dropped.
        def lane(lc, lx):
            q_len, approx, rr_ptr, dropped, lpull = lc
            u, sub_l, lane_i = lx
            live = act & (lane_i < n_arr_t)
            if static.comm == "exact":
                occ = (q_len + busy_cnt).astype(jnp.float32)
            else:
                occ = approx
            if static.policy == "rr":
                if healthy is None:
                    # Deterministic cyclic assignment; the pointer
                    # advances only on live lanes (the reference routes
                    # only actual arrivals).
                    j = (rr_ptr % r_n).astype(jnp.int32)
                    rr_ptr = rr_ptr + live.astype(jnp.int32)
                else:
                    # Masked round robin: skip suspect replicas to the
                    # cyclically-next healthy one (routing.route_rr's
                    # derivation; all-True mask == unmasked decisions,
                    # with the pointer held in its bounded form).
                    off = (
                        jnp.arange(r_n, dtype=jnp.int32) - rr_ptr
                    ) % r_n
                    off = jnp.where(healthy, off, r_n)
                    j = jnp.argmin(off).astype(jnp.int32)
                    rr_ptr = jnp.where(live, j + 1, rr_ptr)
            elif static.policy in PULL_POLICIES:
                tokens, token_miss = lpull
                score = (0 - tokens).astype(jnp.float32)
                if healthy is not None:
                    score = jnp.where(healthy, score, jnp.inf)
                is_min = score == jnp.min(score)
                if static.deterministic_ties:
                    rank = jnp.zeros((), jnp.int32)
                else:
                    n_ties = jnp.sum(is_min, dtype=jnp.int32)
                    rank = jnp.minimum(
                        (u * n_ties.astype(jnp.float32)).astype(jnp.int32),
                        n_ties - 1,
                    )
                cum = jnp.cumsum(is_min.astype(jnp.int32))
                j = jnp.argmax(cum == rank + 1).astype(jnp.int32)
                # Spend the routed replica's token (empty pool counts a
                # miss -- the uniform fallback the frontier reports).
                sel_t = (rep_idx == j) & live
                tok_j = jnp.sum(jnp.where(rep_idx == j, tokens, 0))
                token_miss = token_miss + (
                    live & (tok_j == 0)
                ).astype(jnp.int32)
                tokens = jnp.maximum(tokens - sel_t.astype(jnp.int32), 0)
                lpull = (tokens, token_miss)
            else:
                if static.policy == "drain":
                    score = occ * drain_slots
                else:
                    score = occ
                if static.policy == "sqd":
                    cand = subset_mask(sub_l, r_n, static.sqd, xp=jnp)
                    if healthy is not None:
                        # Suspect exclusion within the sampled subset; an
                        # all-suspect subset falls back to the raw sample
                        # (mirrors the reference dispatcher exactly).
                        m = cand & healthy
                        cand = jnp.where(jnp.any(m), m, cand)
                    score = jnp.where(cand, score, jnp.inf)
                elif healthy is not None:
                    score = jnp.where(healthy, score, jnp.inf)
                is_min = score == jnp.min(score)
                if static.deterministic_ties:
                    # Lowest-index ties: rank 0 in the shared rank
                    # arithmetic (the Pallas kernel convention).
                    rank = jnp.zeros((), jnp.int32)
                else:
                    n_ties = jnp.sum(is_min, dtype=jnp.int32)
                    rank = jnp.minimum(
                        (u * n_ties.astype(jnp.float32)).astype(jnp.int32),
                        n_ties - 1,
                    )
                cum = jnp.cumsum(is_min.astype(jnp.int32))
                j = jnp.argmax(cum == rank + 1).astype(jnp.int32)
            onehot = rep_idx == j
            len_j = jnp.sum(jnp.where(onehot, q_len, 0))
            # The numpy ring grows on demand; the traced ring is fixed, so
            # a full ring drops the arrival (counted -- equivalence tests
            # size queue_cap to keep this path cold).
            admit = live & (len_j < c_n)
            sel = onehot & admit
            tail = (jnp.sum(jnp.where(onehot, q_head, 0)) + len_j) % c_n
            q_len = q_len + sel.astype(jnp.int32)
            approx = approx + sel.astype(jnp.float32)
            dropped = dropped + (live & ~admit).astype(jnp.int32)
            return (q_len, approx, rr_ptr, dropped, lpull), (j, tail, admit)

        if static.route_backend == "pallas":
            # Fused arrival-lane routing: the kernel's fori_loop over lanes
            # replaces the inner scan, carrying the same (q_len, approx)
            # state and emitting the same deferred scatter operands.  The
            # rr pointer is untouched (the pallas path is jsaq-only).
            jv, tailv, admitv, q_len, approx, d_drop = kernel_ops.serve_route(
                tie_t, q_len, q_head, busy_cnt, approx, n_arr_t, act,
                cap=c_n, comm=static.comm,
            )
            dropped = dropped + d_drop
        else:
            lpull = (
                (pull_state[0], pull_state[1]) if has_pull else None
            )
            lane_xs = (tie_t, sub_t, jnp.arange(a_n, dtype=jnp.int32))
            (q_len, approx, rr_ptr, dropped, lpull), (jv, tailv, admitv) = (
                jax.lax.scan(
                    lane, (q_len, approx, rr_ptr, dropped, lpull), lane_xs
                )
            )
        jv = jnp.where(admitv, jv, r_n)  # out of bounds -> dropped scatter
        q_work = q_work.at[jv, tailv].set(work_t, mode="drop")
        q_rid = q_rid.at[jv, tailv].set(rid_t, mode="drop")

        # --- 1b. fault transitions (after routing, before admission) ----
        recovered = None
        if has_fault:
            adv_f, recovered = workload_lib.fault_transitions(
                faulted, fu_t, scn.crash_rate, scn.recover_rate
            )
            faulted = jnp.where(act, adv_f, faulted)
            recovered = recovered & act

        # --- 2. admit: fill free decode slots from the rings, FIFO ------
        free = rem <= 0
        free_rank = jnp.cumsum(free, axis=1) - 1
        n_admit = jnp.minimum(q_len, free.sum(axis=1, dtype=jnp.int32))
        n_admit = jnp.where(act, n_admit, 0)
        if has_fault and static.fault == "crash":
            # A crashed replica is frozen: queued requests wait (conserved)
            # and resume admission on recovery.
            n_admit = jnp.where(faulted, 0, n_admit)
        take = free & (free_rank < n_admit[:, None])
        qidx = (q_head[:, None] + free_rank) % c_n
        w_gather = jnp.take_along_axis(q_work, qidx, axis=1)
        r_gather = jnp.take_along_axis(q_rid, qidx, axis=1)
        rem = jnp.where(take, w_gather, rem)
        arid = jnp.where(take, r_gather, arid)
        q_head = (q_head + n_admit) % c_n
        q_len = q_len - n_admit

        # --- 3. decode: one iteration on every active slot --------------
        # Unit rates decrement by one; heterogeneous rates by the slot's
        # credit-schedule units (rem may go negative == free, matching the
        # reference).
        active = (rem > 0) & act
        if has_fault:
            if static.use_rates:
                nominal = workload_lib.service_units(t, scn.decode_rates)
                rates = scn.decode_rates
            else:
                nominal = jnp.ones((r_n,), jnp.int32)
                rates = None
            units = workload_lib.faulted_service_units(
                t, faulted, nominal, static.fault, scn.slow_factor,
                rates=rates,
            )
            rem = rem - units[:, None] * active.astype(rem.dtype)
        elif static.use_rates:
            units = workload_lib.service_units(t, scn.decode_rates)
            rem = rem - units[:, None] * active.astype(rem.dtype)
        else:
            rem = rem - active.astype(rem.dtype)
        done = active & (rem <= 0)
        completions = done.sum(axis=1, dtype=jnp.int32)
        if static.stream:
            # arid carries arrival slots: the JCT is available on device
            # the slot a request completes, and folds straight into the
            # O(1) accumulators (post-warmup completions only).
            jct_t = t - arid + 1
            comp_slot = comp_slot.update(jct_t, done & (t >= scn.warmup))
        else:
            comp_idx = jnp.where(done, arid, n_cap).reshape(-1)
            comp_slot = comp_slot.at[comp_idx].max(
                jnp.where(done, t, -1).reshape(-1).astype(jnp.int32),
                mode="drop",
            )
        arid = jnp.where(done, -1, arid)
        total_comp = total_comp + jnp.sum(completions, dtype=jnp.int32)

        # --- 4. MSR drain (per-replica, decode-rate scaled) --------------
        busy = (approx > 0) & act
        approx = jnp.maximum(
            approx - drainv * busy.astype(jnp.float32), 0.0
        )

        # --- 5. trigger (shared core) -- freeze counters past horizon ----
        true_occ = (q_len + (rem > 0).sum(axis=1, dtype=jnp.int32)).astype(
            jnp.float32
        )
        err = jnp.abs(true_occ - approx)
        # Crashed replicas cannot send (counters keep advancing, so the
        # first healthy slot re-fires a due trigger); a recovery forces a
        # resync message regardless of the trigger predicate.  Under the
        # network model the trigger only expresses *intent*: message
        # accounting and the dispatcher-view update belong to net_step.
        can_send = force = None
        if has_fault and static.fault == "crash":
            can_send = ~faulted
            force = recovered
        trig, comm_adv = comm_lib.evaluate(
            comm_state, ccfg, err, completions,
            can_send=can_send, force=force, q=true_occ,
            count_msgs=not has_net,
        )
        trig = trig & act
        if has_net:
            # --- 6. network delivery (delay/jitter/drop + piggyback) ----
            if has_ack:
                delivered, payload, sent, net_adv = comm_lib.net_step_ack(
                    net_state, ncfg, trig, true_occ, ndu_t, nju_t, aku_t,
                    can_send=can_send,
                )
            else:
                delivered, payload, sent, net_adv = comm_lib.net_step(
                    net_state, ncfg, trig, true_occ, ndu_t, nju_t,
                    can_send=can_send,
                )
            delivered = delivered & act
            extra = jnp.where(act, sent, 0)
            if static.policy == "sqd":
                # SQ(d)'s 2d query round-trips per routed arrival, on the
                # same wire (mirrors CareDispatcher.route).
                n_live = jnp.minimum(n_arr_t, a_n).astype(jnp.int32)
                extra = extra + jnp.where(act, 2 * static.sqd * n_live, 0)
            comm_adv = dataclasses.replace(
                comm_adv, msgs=comm_adv.msgs + extra
            )
            net_state = jax.tree.map(
                lambda adv, old: jnp.where(act, adv, old), net_adv, net_state
            )
            approx = jnp.where(delivered, payload, approx)
        else:
            approx = jnp.where(trig, true_occ, approx)
        comm_state = jax.tree.map(
            lambda adv, old: jnp.where(act, adv, old), comm_adv, comm_state
        )
        if has_pull:
            # --- 7. pull-token refresh: a delivered token message
            # *overwrites* the sender's pool slot from the send-time queue
            # snapshot (1 if idle for JIQ, the threshold headroom for hsq
            # -- f32 truncated to int32, exactly like the reference).  A
            # crashed replica stops sending, so its stale tokens drain to
            # zero and are never replenished.
            tokens, token_miss = lpull
            if static.comm == "jiq":
                def _fresh(p):
                    return (p == 0.0).astype(jnp.int32)
            else:  # hsq
                def _fresh(p):
                    return jnp.maximum(scn.x - p, 0.0).astype(jnp.int32)
            if has_net:
                tokens = jnp.where(delivered, _fresh(payload), tokens)
            else:
                tokens = jnp.where(trig, _fresh(true_occ), tokens)
            token_sum = pull_state[2] + jnp.where(
                act, jnp.sum(tokens, dtype=jnp.int32), 0
            )
            pull_state = (tokens, token_miss, token_sum)

        carry = (q_len, q_head, q_work, q_rid, rem, arid, approx, comm_state,
                 rr_ptr, comp_slot, total_comp, dropped, net_state, faulted,
                 pull_state)
        out = true_occ.astype(jnp.int32) if static.trace_occupancy else None
        return carry, out

    init = _engine_init(static, n_cap) if carry is None else carry
    tv = jnp.arange(t_n, dtype=jnp.int32)
    if t0 is not None:
        tv = tv + t0  # absolute slot clock of the segment engine
    xs = (tv, n_arr, work, tie_u, rid, sub_u, net_du, net_ju, fault_u,
          ack_u)
    final, occ_trace = jax.lax.scan(slot, init, xs)
    if static.stream:
        # Segment mode: the caller threads the whole carry to the next
        # chunk; metrics/counters are read off it after the last one.
        return final
    (q_len, _, _, _, rem, _, _, comm_state, _, comp_slot, total_comp,
     dropped, net_state, _, pull_state) = final
    final_occ = q_len + (rem > 0).sum(axis=1, dtype=jnp.int32)
    net_drops = net_state.drops if has_net else jnp.zeros((), jnp.int32)
    token_miss = (
        pull_state[1] if has_pull else jnp.zeros((), jnp.int32)
    )
    token_sum = (
        pull_state[2] if has_pull else jnp.zeros((), jnp.int32)
    )
    outs = (comp_slot, comm_state.msgs, total_comp, dropped, final_occ,
            net_drops, token_miss, token_sum)
    if has_net and static.transport == "ack":
        # Retransmit total (ack cells only -- the fire_forget output
        # tuple, and hence its compiled program, is untouched).
        outs = outs + (net_state.retrans,)
    if static.trace_occupancy:
        outs = outs + (occ_trace,)
    return outs


def _engine_init(static: EngineStatic, n_cap: int):
    """The scan/stream carry at slot 0 (shared by both engine modes).

    Position 9 is the rid-indexed completion-slot scatter in fixed mode
    and the :class:`StreamMetrics` accumulators in stream mode; the
    control-plane subtrees are ``None`` when their kinds are off, so the
    default program structure is unchanged.
    """
    r_n, s_n, c_n = static.replicas, static.decode_slots, static.queue_cap
    comm0, net0, fault0 = comm_lib.control_plane_init(
        r_n, network=static.network, fault=static.fault,
        transport=static.transport, payload_dtype=jnp.float32,
    )
    return (
        jnp.zeros((r_n,), jnp.int32),  # q_len
        jnp.zeros((r_n,), jnp.int32),  # q_head
        jnp.zeros((r_n, c_n), jnp.int32),  # q_work ring
        jnp.full((r_n, c_n), -1, jnp.int32),  # q_rid / q_arr ring
        jnp.zeros((r_n, s_n), jnp.int32),  # rem (decode slots)
        jnp.full((r_n, s_n), -1, jnp.int32),  # arid / arrival slots
        jnp.zeros((r_n,), jnp.float32),  # approx
        comm0,
        jnp.zeros((), jnp.int32),  # rr_ptr ("rr" policy)
        StreamMetrics.init() if static.stream
        else jnp.full((n_cap,), -1, jnp.int32),  # comp_slot (rid-indexed)
        jnp.zeros((), jnp.int32),  # total completions
        jnp.zeros((), jnp.int32),  # dropped
        net0,
        fault0,
        # Pull-token pool + counters (None keeps the default structure).
        (
            jnp.zeros((r_n,), jnp.int32),  # tokens
            jnp.zeros((), jnp.int32),  # token_miss (empty-pool routes)
            jnp.zeros((), jnp.int32),  # token_sum (pool-occupancy integral)
        )
        if static.policy in PULL_POLICIES
        else None,
    )


@functools.partial(jax.jit, static_argnums=(10, 11))
def _serve_one_jit(n_arr, work, tie_u, rid, sub_u, net_du, net_ju, fault_u,
                   ack_u, scn, n_cap, static):
    return _serve_core(n_arr, work, tie_u, rid, sub_u, net_du, net_ju,
                       fault_u, ack_u, n_cap, scn, static)


_SERVE_GRID_PROGRAMS: list = []  # jitted grid wrappers, one per (static, n_dev)


@functools.lru_cache(maxsize=None)
def _serve_grid_fn(static: EngineStatic, n_cap: int, n_dev: int):
    """The one compiled program for a serving grid: vmap inside shard_map.

    Mirrors ``slotted_sim._grid_fn``: cached per (EngineStatic, rid
    capacity, device count); ``n_dev == 1`` skips the mesh (plain jitted
    vmap).  Re-invocations with a new batch length retrace -- counted by
    :func:`serve_compile_count`.
    """
    batched = jax.vmap(
        lambda n_arr, work, tie_u, rid, sub_u, net_du, net_ju, fault_u,
        ack_u, scn:
        _serve_core(
            n_arr, work, tie_u, rid, sub_u, net_du, net_ju, fault_u,
            ack_u, n_cap, scn, static
        )
    )
    fn = jax.jit(shard_runs(batched, n_dev, 10))
    _SERVE_GRID_PROGRAMS.append(fn)
    return fn


def serve_compile_count() -> int:
    """Total XLA programs compiled by the serving grid path so far.

    Same accounting as ``slotted_sim.grid_compile_count``: sums the
    compiled-shape cache sizes of every jitted grid wrapper, so batch-shape
    retraces count as the real compile work they are.
    """
    return sum(f._cache_size() for f in _SERVE_GRID_PROGRAMS)


@dataclasses.dataclass
class ServeResult:
    """One serving run's outputs (host-side numpy; jct in rid order)."""

    jct: np.ndarray  # (completed,) completion times, rid (arrival) order
    jct_by_rid: np.ndarray  # (offered,) -1 where never completed
    completed: int
    offered: int
    messages: int
    dropped: int  # arrivals rejected on a full pending ring (jax path only)
    final_occupancy: np.ndarray  # (R,)
    mean_jct: float
    p99_jct: float
    msgs_per_completion: float
    net_drops: int = 0  # messages lost in flight (network="net" only)
    token_misses: int = 0  # pull routes that found an empty token pool
    token_sum: int = 0  # end-of-slot token-pool occupancy, summed over slots
    retrans: int = 0  # data retransmits (transport="ack" only)
    occupancy: Optional[np.ndarray] = None  # (T, R) when trace_occupancy

    @staticmethod
    def from_run(wl: ServeWorkload, comp_slot, msgs, total_comp, dropped,
                 final_occ, net_drops=0, token_misses=0, token_sum=0,
                 retrans=0, occ_trace=None) -> "ServeResult":
        comp_slot = np.asarray(comp_slot)[: wl.total].astype(np.int64)
        done = comp_slot >= 0
        jct_by_rid = np.where(done, comp_slot - wl.arrival_slot + 1, -1)
        jct = jct_by_rid[done]
        completed = int(done.sum())
        msgs = int(msgs)
        return ServeResult(
            jct=jct,
            jct_by_rid=jct_by_rid,
            completed=completed,
            offered=wl.total,
            messages=msgs,
            dropped=int(dropped),
            final_occupancy=np.asarray(final_occ),
            mean_jct=float(jct.mean()) if jct.size else 0.0,
            p99_jct=float(np.percentile(jct, 99)) if jct.size else 0.0,
            msgs_per_completion=msgs / max(int(total_comp), 1),
            net_drops=int(net_drops),
            token_misses=int(token_misses),
            token_sum=int(token_sum),
            retrans=int(retrans),
            occupancy=None if occ_trace is None else np.asarray(occ_trace),
        )


def _round_up(n: int, mult: int) -> int:
    return ((max(n, 1) + mult - 1) // mult) * mult


def _split_extra_outs(out_np, static: EngineStatic):
    """Split ``_serve_core``'s variable output tail by the static flags.

    The first 8 outputs are fixed; ``retrans`` rides along only under
    ``transport="ack"`` and the occupancy trace only under
    ``trace_occupancy`` (keeping the default output tuple -- and hence
    the compiled fire-and-forget program -- byte-identical).
    """
    base, rest = list(out_np[:8]), list(out_np[8:])
    retrans = 0
    if static.network != "none" and static.transport == "ack":
        retrans, rest = rest[0], rest[1:]
    occ = rest[0] if rest else None
    return base, retrans, occ


def _pad_workload(wl: ServeWorkload, t_pad: int, a_pad: int, d: int = 0,
                  with_rid: bool = True):
    """Pad one workload to the (T, A) lane grid the static program takes.

    ``d`` is the subset-uniform lane depth: ``sqd`` under the "sqd" policy
    (the first ``d`` ``sub_u`` columns ride along as a ``(T, A, d)``
    operand), 0 otherwise (a zero-width array -- no memory, no transfer).
    ``with_rid=False`` (stream mode) makes the rid lanes zero-width too:
    the segment engine synthesises a request's identity from its arrival
    slot on device, so the rid gather/transfer would be pure overhead in
    the per-chunk host loop.
    Fully vectorised (one fancy-indexed gather per array): this runs per
    (cell, seed) on every ``serve_grid`` invocation, including the warm
    replays benchmarks time, so a Python per-slot loop would bill host
    padding to the measured steady-state throughput.
    """
    t = wl.n_arr.shape[0]
    n_arr = np.zeros(t_pad, np.int32)
    n_arr[:t] = wl.n_arr
    work = np.zeros((t_pad, a_pad), np.int32)
    tie_u = np.zeros((t_pad, a_pad), np.float32)
    rid = np.zeros((t_pad, a_pad if with_rid else 0), np.int32)
    sub_u = np.zeros((t_pad, a_pad, d), np.float32)
    if wl.total:
        lane = np.arange(a_pad, dtype=np.int64)[None, :]
        mask = lane < wl.n_arr[:, None]  # (t, a_pad) live lanes
        idx = np.minimum(wl.base[:, None] + lane, wl.total - 1)
        work[:t] = np.where(mask, wl.work[idx], 0)
        tie_u[:t] = np.where(mask, wl.tie_u[idx], 0.0)
        if with_rid:
            rid[:t] = np.where(mask, idx, 0)
        if d:
            sub_u[:t] = np.where(
                mask[..., None], wl.sub_u[idx, :d], 0.0
            )

    def pad_cp(arr):
        # Control-plane uniforms: (T, R) per-slot rows, zero-width when
        # the corresponding kind is off (no memory, no transfer).
        if arr is None:
            return np.zeros((t_pad, 0), np.float32)
        out = np.zeros((t_pad, arr.shape[1]), np.float32)
        out[: arr.shape[0]] = arr
        return out

    def pad_ack(arr):
        # Ack/keepalive uniforms: (T, 4, R) slabs, zero-width when the
        # transport is fire_forget (no memory, no transfer).
        if arr is None:
            return np.zeros((t_pad, 0, 0), np.float32)
        out = np.zeros((t_pad,) + arr.shape[1:], np.float32)
        out[: arr.shape[0]] = arr
        return out

    return (n_arr, work, tie_u, rid, sub_u, pad_cp(wl.net_drop_u),
            pad_cp(wl.net_jit_u), pad_cp(wl.fault_u), pad_ack(wl.ack_u))


def serve_grid_program(
    seeds: Sequence[int],
    static: EngineStatic,
    cells: Sequence[ServeConfig],
    *,
    shard: bool = True,
):
    """The compiled program and operands of one :func:`serve_grid` call.

    Samples and pads every (cell, seed) workload.  Returns ``(fn, args,
    wls, static)``: ``fn(*args)`` is the grid run over the flattened
    cell-major runs (``wls[c][s]`` their workloads, ``static`` the program
    structure with its lane width resolved); ``fn.lower(*args).compile()``
    gives the program's compile time, memory and HLO without running it.
    """
    cells = list(cells)
    seeds = [int(s) for s in seeds]
    for cell in cells:
        cs = cell.static_part()
        if (
            cs.replicas, cs.decode_slots, cs.queue_cap, cs.comm,
            cs.policy, cs.sqd, cs.use_rates, cs.route_backend,
            cs.deterministic_ties, cs.network, cs.transport, cs.fault,
        ) != (
            static.replicas, static.decode_slots, static.queue_cap,
            static.comm, static.policy, static.sqd, static.use_rates,
            static.route_backend, static.deterministic_ties,
            static.network, static.transport, static.fault,
        ):
            raise ValueError(
                f"cell static part {cs} does not match grid static {static}"
            )
        if cell.slots > static.slots:
            raise ValueError(
                f"cell slots {cell.slots} exceeds padded length {static.slots}"
            )

    wls = [[workload_for(cell, s) for s in seeds] for cell in cells]
    flat_wls = [w for row in wls for w in row]
    a_need = max(int(w.n_arr.max()) for w in flat_wls)
    a_pad = _round_up(a_need, 8)
    if static.max_arrivals:
        if static.max_arrivals < a_need:
            raise ValueError(
                f"static.max_arrivals={static.max_arrivals} below the "
                f"sampled batch maximum {a_need}"
            )
        a_pad = static.max_arrivals
    static = dataclasses.replace(static, max_arrivals=a_pad)
    n_cap = _round_up(max(w.total for w in flat_wls), 1024)
    d = static.sqd if static.policy == "sqd" else 0

    padded = [_pad_workload(w, static.slots, a_pad, d) for w in flat_wls]
    arrs = [jnp.asarray(np.stack([p[i] for p in padded])) for i in range(9)]
    scn_flat = stack_scenarios(
        [cell.scenario() for cell in cells for _ in seeds]
    )

    n = len(flat_wls)
    n_dev = jax.local_device_count() if shard else 1
    idx = _pad_indices(n, n_dev)
    if len(idx) != n:
        arrs = [a[idx] for a in arrs]
        scn_flat = jax.tree.map(lambda a: a[idx], scn_flat)

    fn = _serve_grid_fn(static, n_cap, n_dev)
    return fn, (*arrs, scn_flat), wls, static


def serve_grid(
    seeds: Sequence[int],
    static: EngineStatic,
    cells: Sequence[ServeConfig],
    *,
    shard: bool = True,
) -> list[list[ServeResult]]:
    """Run a whole serving grid as **one compiled program**.

    Args:
      seeds: integer seeds; every cell replays the same seed set (the
        workload sampler is host-side numpy, keyed per (cell workload
        parameters, seed) -- cells differing only in comm thresholds share
        streams, the paper's comparison method).
      static: the shared program structure.  Every cell's
        ``static_part()`` must agree with it on shapes and comm kind;
        ``static.slots`` is the padded scan length (>= every cell's
        ``slots``) and ``static.max_arrivals`` the arrival-lane width
        (``0`` = derive from the sampled batch, rounded up to a multiple
        of 8 so near-miss batches reuse the program).
      cells: the grid cells (scenario operands + workload parameters).
      shard: shard the flattened ``(C*S,)`` run axis across local devices
        with ``shard_map`` (ragged batches padded with wrap-around
        duplicates, dropped on output).

    Returns:
      ``results[c][s]`` -- one :class:`ServeResult` per (cell, seed),
      bit-identical to the numpy reference ``run_serving_sim`` (asserted
      by ``tests/test_serve_engine.py``).
    """
    fn, args, wls, static = serve_grid_program(
        seeds, static, cells, shard=shard
    )
    n = sum(len(row) for row in wls)
    out_np = [np.asarray(o)[:n] for o in fn(*args)]
    base, retrans, occ = _split_extra_outs(out_np, static)
    s = len(seeds)
    return [
        [
            ServeResult.from_run(
                wls[c][j], *(o[c * s + j] for o in base),
                retrans=0 if isinstance(retrans, int)
                else retrans[c * s + j],
                occ_trace=None if occ is None else occ[c * s + j],
            )
            for j in range(s)
        ]
        for c in range(len(wls))
    ]


def serve_one(seed: int, cell: ServeConfig, *,
              trace_occupancy: bool = False,
              workload: Optional[ServeWorkload] = None) -> ServeResult:
    """Run one serving cell on the jax engine (its own compiled program).

    The single-run analogue of :func:`serve_grid` -- used by the
    equivalence tests as the per-cell reference the fused grid must
    reproduce (padding the arrival lanes or the rid capacity differently
    must not change results).  ``workload`` overrides the cached sampler
    stream (the chunk-invariance tests feed the assembled stream-sampler
    trace to both this fixed-horizon path and :func:`serve_stream`); it
    must cover at most ``cell.slots`` slots.
    """
    wl = workload if workload is not None else workload_for(cell, seed)
    if wl.n_arr.shape[0] > cell.slots:
        raise ValueError(
            f"workload covers {wl.n_arr.shape[0]} slots, cell.slots is "
            f"{cell.slots}"
        )
    a_need = max(int(wl.n_arr.max()), 1)
    if cell.max_arrivals:
        if cell.max_arrivals < a_need:
            raise ValueError(
                f"max_arrivals={cell.max_arrivals} below the sampled "
                f"per-slot maximum {a_need}"
            )
        a_pad = cell.max_arrivals  # pinned by the caller: reuse its shape
    else:
        a_pad = _round_up(a_need, 8)
    static = dataclasses.replace(
        cell.static_part(),
        max_arrivals=a_pad,
        trace_occupancy=trace_occupancy,
    )
    n_cap = _round_up(wl.total, 1024)
    d = static.sqd if static.policy == "sqd" else 0
    padded = _pad_workload(wl, static.slots, static.max_arrivals, d)
    out = _serve_one_jit(
        *(jnp.asarray(p) for p in padded), cell.scenario(), n_cap, static,
    )
    base, retrans, occ = _split_extra_outs(
        [np.asarray(o) for o in out], static
    )
    return ServeResult.from_run(wl, *base, retrans=retrans, occ_trace=occ)


# ---------------------------------------------------------------------------
# Segment engine (serve_stream): chunked unbounded-horizon serving.
#
# The fixed-horizon scan materialises the whole trace up front, which caps
# runs at host memory and leaves the host idle while the device computes.
# The segment engine runs the same slot body chunk by chunk: a jitted step
# carries the full engine state pytree across chunks with donated buffers
# (state updated in place), while the host samples chunk k+1's workload
# slab during chunk k's device execution -- JAX async dispatch gives the
# overlap for free because the driver never blocks mid-stream.  Workload
# blocks are keyed by prefix-stable SeedSequence children, so any chunking
# replays the identical trace bit for bit -- and so does the monolithic
# fixed-horizon scan fed the assembled trace (the golden tests' contract).
# This is also the seam a live arrival feed plugs into later: swap the
# sampler for a queue drain, resume from a snapshotted carry
# (comm.snapshot_state / comm.restore_state).
# ---------------------------------------------------------------------------

# Granularity of the prefix-stable stream sampler: every quantity of block
# j (slots [j*B, (j+1)*B)) is drawn from its own SeedSequence child keyed
# (stream, j), so block j's bytes never depend on how -- or whether --
# other blocks were sampled.  Chunk boundaries need not align with blocks.
STREAM_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class StreamParams:
    """Workload parameters of one request stream (hashable).

    The stream analogue of :meth:`ServeConfig.workload_key`: everything
    the sampler needs, nothing the router consumes.  ``diurnal_amp`` /
    ``diurnal_period`` modulate the arrival rate sinusoidally
    (``rate * (1 + amp * sin(2 pi t / period))``) -- the simulated-days
    soak cycles of the steady-state claims; 0/0 keeps a flat rate.
    """

    replicas: int
    decode_slots: int
    load: float
    mean_prefill: float = 4.0
    mean_decode: float = 64.0
    rate_scale: float = 1.0
    with_net: bool = False
    with_fault: bool = False
    with_ack: bool = False
    diurnal_amp: float = 0.0
    diurnal_period: int = 0

    @staticmethod
    def for_cell(cell: ServeConfig, *, diurnal_amp: float = 0.0,
                 diurnal_period: int = 0) -> "StreamParams":
        return StreamParams(
            replicas=cell.replicas,
            decode_slots=cell.decode_slots,
            load=cell.load,
            mean_prefill=float(cell.mean_prefill),
            mean_decode=float(cell.mean_decode),
            rate_scale=cell.rate_scale(),
            with_net=cell.network != "none",
            with_fault=cell.fault != "none",
            with_ack=cell.network != "none" and cell.transport == "ack",
            diurnal_amp=diurnal_amp,
            diurnal_period=diurnal_period,
        )


@dataclasses.dataclass
class _StreamBlock:
    """One sampled block: per-slot arrivals plus per-arrival draws."""

    n_arr: np.ndarray  # (B,) int64
    cum: np.ndarray  # (B + 1,) int64 arrivals before each in-block slot
    prefill: np.ndarray  # (total,) int64
    decode: np.ndarray  # (total,) int64
    work: np.ndarray  # (total,) int64
    tie_u: np.ndarray  # (total,) float32
    sub_u: np.ndarray  # (total, SQD_MAX) float32
    net_drop_u: Optional[np.ndarray]  # (B, R) float32
    net_jit_u: Optional[np.ndarray]  # (B, R) float32
    fault_u: Optional[np.ndarray]  # (B, R) float32
    ack_u: Optional[np.ndarray]  # (B, 4, R) float32


class StreamSampler:
    """Prefix-stable chunked workload sampling (host side of the stream).

    Five root ``SeedSequence`` children split the independent streams
    exactly like :func:`sample_workload` (arrivals/sizes, tie-breaks,
    SQ(d) subsets, network uniforms, fault uniforms); block ``j`` of each
    stream then draws from the *j-th child of that child*, constructed
    statelessly as ``SeedSequence(entropy, spawn_key + (j,))``.  Spawning
    is prefix-stable, so block j's bytes are a pure function of
    (seed, params, j): slabs of any size, sampled in any order, assemble
    into one well-defined infinite trace.  A small LRU of decoded blocks
    keeps sequential slab iteration O(chunk) in time and O(1) in memory.
    """

    _CACHE_BLOCKS = 8

    def __init__(self, seed: int, params: StreamParams):
        self.seed = int(seed)
        self.params = params
        root = np.random.SeedSequence(self.seed)
        # workload, tie, subset, net, fault, ack -- spawning is
        # prefix-stable, so the sixth (ack) child cannot move the first
        # five streams' bytes.
        self._roots = root.spawn(6)
        self._cache: dict[int, _StreamBlock] = {}

    def _rng(self, stream: int, j: int) -> np.random.Generator:
        child = self._roots[stream]
        ss = np.random.SeedSequence(
            entropy=child.entropy, spawn_key=child.spawn_key + (j,)
        )
        return np.random.default_rng(ss)

    def rate_at(self, t: np.ndarray) -> np.ndarray:
        """Offered per-slot arrival rate at absolute slots ``t``."""
        p = self.params
        mean_work = p.mean_prefill + p.mean_decode
        base = p.load * p.replicas * p.decode_slots * p.rate_scale / mean_work
        if not p.diurnal_period:
            return np.full(np.shape(t), base)
        phase = 2.0 * np.pi * np.asarray(t, np.float64) / p.diurnal_period
        return base * (1.0 + p.diurnal_amp * np.sin(phase))

    def _block(self, j: int) -> _StreamBlock:
        blk = self._cache.get(j)
        if blk is not None:
            return blk
        p, b = self.params, STREAM_BLOCK
        t = j * b + np.arange(b, dtype=np.int64)
        wrng = self._rng(0, j)
        n_arr = wrng.poisson(self.rate_at(t)).astype(np.int64)
        total = int(n_arr.sum())
        prefill = 1 + wrng.poisson(p.mean_prefill, size=total).astype(np.int64)
        decode = 1 + wrng.poisson(p.mean_decode, size=total).astype(np.int64)
        work = np.maximum(prefill + decode, 1)
        tie_u = self._rng(1, j).random(size=total, dtype=np.float32)
        sub_u = self._rng(2, j).random(size=(total, SQD_MAX), dtype=np.float32)
        net_drop_u = net_jit_u = fault_u = ack_u = None
        if p.with_net:
            nrng = self._rng(3, j)
            net_drop_u = nrng.random(size=(b, p.replicas), dtype=np.float32)
            net_jit_u = nrng.random(size=(b, p.replicas), dtype=np.float32)
        if p.with_fault:
            fault_u = self._rng(4, j).random(
                size=(b, p.replicas), dtype=np.float32
            )
        if p.with_ack:
            ack_u = self._rng(5, j).random(
                size=(b, 4, p.replicas), dtype=np.float32
            )
        blk = _StreamBlock(
            n_arr=n_arr,
            cum=np.concatenate([[0], np.cumsum(n_arr)]).astype(np.int64),
            prefill=prefill, decode=decode, work=work,
            tie_u=tie_u, sub_u=sub_u,
            net_drop_u=net_drop_u, net_jit_u=net_jit_u, fault_u=fault_u,
            ack_u=ack_u,
        )
        if len(self._cache) >= self._CACHE_BLOCKS:
            self._cache.pop(next(iter(self._cache)))
        self._cache[j] = blk
        return blk

    def slab(self, t0: int, t1: int) -> ServeWorkload:
        """The trace restricted to slots ``[t0, t1)`` as a ServeWorkload.

        ``base`` is slab-local (rid of a slot's first arrival *within the
        slab's arrays*); ``arrival_slot`` is absolute.  Bit-identical to
        the same span of any other slabbing -- the chunking contract.
        """
        if not 0 <= t0 < t1:
            raise ValueError(f"bad slab bounds [{t0}, {t1})")
        b = STREAM_BLOCK
        parts: list[tuple] = []
        for j in range(t0 // b, (t1 - 1) // b + 1):
            blk = self._block(j)
            lo = max(t0 - j * b, 0)
            hi = min(t1 - j * b, b)
            a0, a1 = int(blk.cum[lo]), int(blk.cum[hi])
            parts.append((blk, lo, hi, a0, a1))
        n_arr = np.concatenate([blk.n_arr[lo:hi] for blk, lo, hi, _, _ in parts])
        cat = lambda f: np.concatenate(  # noqa: E731 -- local glue
            [getattr(blk, f)[a0:a1] for blk, _, _, a0, a1 in parts]
        )
        cat_cp = lambda f: (  # noqa: E731
            None
            if getattr(parts[0][0], f) is None
            else np.concatenate(
                [getattr(blk, f)[lo:hi] for blk, lo, hi, _, _ in parts]
            )
        )
        return ServeWorkload(
            n_arr=n_arr,
            base=np.concatenate([[0], np.cumsum(n_arr)[:-1]]).astype(np.int64),
            prefill=cat("prefill"), decode=cat("decode"), work=cat("work"),
            tie_u=cat("tie_u"), sub_u=cat("sub_u"),
            arrival_slot=np.repeat(np.arange(t0, t1, dtype=np.int64), n_arr),
            net_drop_u=cat_cp("net_drop_u"), net_jit_u=cat_cp("net_jit_u"),
            fault_u=cat_cp("fault_u"), ack_u=cat_cp("ack_u"),
        )

    def full(self, slots: int) -> ServeWorkload:
        """The assembled monolithic trace of the first ``slots`` slots.

        Feeds the fixed-horizon reference (``serve_one(workload=...)`` /
        ``run_serving_sim(workload=...)``) in the chunk-invariance golden
        tests; O(slots) memory, so tests/examples only.
        """
        return self.slab(0, slots)


_STREAM_PROGRAMS: list = []  # jitted chunk steps, for compile accounting


@functools.lru_cache(maxsize=None)
def _stream_step_fn(static: EngineStatic):
    """The jitted chunk step: one compiled program per static structure.

    ``static.slots`` is the chunk length.  ``donate_argnums=(0,)`` donates
    the carry -- queues, CommState, NetState, fault mask, StreamMetrics --
    so XLA updates the state buffers in place across chunks instead of
    allocating a fresh copy per call.  ``static.max_arrivals`` is the
    chunk's padded lane width: a grown slab retraces once per new width
    (widths are rounded up, so growth stabilises fast) and lane padding
    is masked no-ops, so results never depend on it.
    """

    def step(carry, t0, n_arr, work, tie_u, rid, sub_u, net_du, net_ju,
             fault_u, ack_u, scn):
        return _serve_core(
            n_arr, work, tie_u, rid, sub_u, net_du, net_ju, fault_u,
            ack_u, 0, scn, static, carry=carry, t0=t0,
        )

    fn = jax.jit(step, donate_argnums=(0,))
    _STREAM_PROGRAMS.append(fn)
    return fn


def stream_compile_count() -> int:
    """Compiled chunk-step programs so far (same accounting as the grid)."""
    return sum(f._cache_size() for f in _STREAM_PROGRAMS)


@dataclasses.dataclass
class StreamState:
    """Resumable segment-engine state between :func:`serve_stream` calls.

    ``carry`` is the device pytree the next chunk step consumes (it is
    *donated* on resume -- a state can be resumed once; snapshot it with
    :func:`repro.core.care.comm.snapshot_state` first to keep a copy).
    """

    carry: tuple
    t_next: int
    offered: int
    a_pad: int
    sampler: StreamSampler


@dataclasses.dataclass
class StreamResult:
    """One stream segment's outputs (host-side scalars + histogram)."""

    slots: int  # slots run in this segment (cumulative if resumed)
    offered: int
    completed: int  # all completions, warmup included
    dropped: int
    messages: int
    net_drops: int
    count: int  # post-warmup completions measured by the accumulators
    mean_jct: float
    std_jct: float
    max_jct: int
    hist: np.ndarray  # (metrics.HIST_BUCKETS,) int64
    final_occupancy: np.ndarray  # (R,)
    state: StreamState
    token_misses: int = 0  # pull routes that found an empty token pool
    token_sum: int = 0  # end-of-slot token-pool occupancy over slots
    retrans: int = 0  # data retransmits (transport="ack" only)

    @property
    def msgs_per_slot(self) -> float:
        return self.messages / max(self.slots, 1)

    @property
    def msgs_per_completion(self) -> float:
        return self.messages / max(self.completed, 1)

    def jct_summary(self) -> dict:
        """NaN-safe summary (tail quantiles from the log histogram)."""
        return metrics_lib.stream_summary(
            self.count, self.mean_jct,
            self.std_jct * self.std_jct * max(self.count, 1),
            self.max_jct, self.hist,
        )


def serve_stream(
    seed: int,
    cell: ServeConfig,
    *,
    chunk: int = 4096,
    warmup: int = 0,
    slots: Optional[int] = None,
    sampler: Optional[StreamSampler] = None,
    state: Optional[StreamState] = None,
    prefetch: bool = True,
    diurnal_amp: float = 0.0,
    diurnal_period: int = 0,
) -> StreamResult:
    """Run one serving cell as a chunked stream in bounded memory.

    The segment engine: ``slots`` (default ``cell.slots``) total slots run
    as ``ceil(slots / chunk)`` jitted chunk steps threading one donated
    carry.  The host samples chunk k+1's slab while the device executes
    chunk k (``prefetch=True``; JAX async dispatch -- the driver never
    blocks mid-stream), so workload generation rides inside device time.
    ``prefetch=False`` is the synchronous no-prefetch reference the
    overlap benchmark compares against: identical results, but each slab
    is sampled only after the previous chunk's state is materialised.

    Bit-identity contract: for any chunk size -- and for the monolithic
    fixed-horizon engine fed ``StreamSampler.full(slots)`` -- every
    counter and every carried state array is identical bit for bit
    (golden-tested).  ``warmup`` discards completions landing before that
    absolute slot from the JCT accumulators (steady-state measurement);
    counters (messages, completions, drops) are never warmup-gated.

    ``state`` resumes a previous segment (its carry is donated -- resume a
    state at most once).  Totals (slots/offered/messages/...) are
    cumulative across resumed segments.  ``t + slots`` must stay below
    2^31 (the i32 slot clock).
    """
    if cell.route_backend == "pallas" and cell.policy != "jsaq":
        raise ValueError("stream mode inherits the pallas jsaq-only limits")
    slots = cell.slots if slots is None else int(slots)
    if slots <= 0:
        raise ValueError(f"slots must be positive, got {slots}")
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    base_static = cell.static_part()  # validates the cell
    d = base_static.sqd if base_static.policy == "sqd" else 0

    if state is not None:
        sampler = state.sampler
        t_start, offered = state.t_next, state.offered
        carry, a_pad = state.carry, state.a_pad
    else:
        if sampler is None:
            sampler = StreamSampler(
                seed,
                StreamParams.for_cell(
                    cell, diurnal_amp=diurnal_amp,
                    diurnal_period=diurnal_period,
                ),
            )
        t_start, offered = 0, 0
        carry, a_pad = None, 8
    t_end = t_start + slots
    if t_end >= np.iinfo(np.int32).max:
        raise ValueError(
            f"stream end {t_end} overflows the int32 slot clock"
        )
    scn = dataclasses.replace(
        cell.scenario(),
        horizon=jnp.int32(t_end),
        warmup=jnp.int32(warmup),
    )
    if carry is None:
        carry = _engine_init(
            dataclasses.replace(base_static, stream=True), 0
        )

    n_chunks = -(-slots // chunk)

    def prep(k: int):
        """Sample + pad + stage chunk k's slab (the host half of overlap)."""
        nonlocal a_pad, offered
        c0 = t_start + k * chunk
        wl = sampler.slab(c0, min(c0 + chunk, t_end))
        offered += wl.total
        need = int(wl.n_arr.max()) if wl.n_arr.size else 0
        if need > a_pad:
            a_pad = _round_up(need, 8)
        static_k = dataclasses.replace(
            base_static, slots=chunk, stream=True, max_arrivals=a_pad,
            trace_occupancy=False,
        )
        padded = _pad_workload(wl, chunk, a_pad, d, with_rid=False)
        return static_k, np.int32(c0), tuple(jnp.asarray(p) for p in padded)

    cur = prep(0)
    for k in range(n_chunks):
        static_k, t0_k, arrs = cur
        carry = _stream_step_fn(static_k)(carry, t0_k, *arrs, scn)
        if not prefetch:
            # Synchronous reference: drain the device before touching the
            # next slab, so host sampling serialises behind device time.
            carry = jax.block_until_ready(carry)
        if k + 1 < n_chunks:
            cur = prep(k + 1)
    carry = jax.block_until_ready(carry)

    (q_len, _, _, _, rem, _, _, comm_state, _, sm, total_comp, dropped,
     net_state, _, pull_state) = carry
    q_len_np = np.asarray(q_len)
    final_occ = q_len_np + (np.asarray(rem) > 0).sum(axis=1).astype(
        q_len_np.dtype
    )
    return StreamResult(
        slots=t_end,
        offered=offered,
        completed=int(total_comp),
        dropped=int(dropped),
        messages=int(comm_state.msgs),
        net_drops=int(net_state.drops) if net_state is not None else 0,
        count=int(sm.count),
        mean_jct=float(sm.mean),
        std_jct=float(
            np.sqrt(max(float(sm.m2), 0.0) / max(int(sm.count), 1))
        ),
        max_jct=int(sm.max_jct),
        hist=np.asarray(sm.hist, np.int64),
        final_occupancy=final_occ,
        state=StreamState(
            carry=carry, t_next=t_end, offered=offered, a_pad=a_pad,
            sampler=sampler,
        ),
        token_misses=int(pull_state[1]) if pull_state is not None else 0,
        token_sum=int(pull_state[2]) if pull_state is not None else 0,
        retrans=(
            int(net_state.retrans)
            if net_state is not None and hasattr(net_state, "retrans")
            else 0
        ),
    )
