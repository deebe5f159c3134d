"""Unified communication component of the CARE model (paper Section 2.1.2).

Single source of truth for *when a server reports its exact state to the
balancer*.  Every tier of the repo -- the slotted simulator
(``care/slotted_sim.py``), the multi-dispatcher MoE simulation
(``core/dispatch_sim.py``) and the serving engine (``serve/engine.py``) --
imports its trigger evaluation and message accounting from here, so the
paper's protocol exists exactly once and cannot drift between tiers.

Patterns (paper Section 2.1.2 / Section 6):

* ``rt``     -- Rate-Triggered RT-r: a message every ``rt_period`` slots
  (``r = 1/rt_period`` messages/slot).  No deterministic error bound
  (Section 6.2), purely time-driven.
* ``dt``     -- Departure-Triggered DT-x: a message after every ``x``
  departures.  With basic/MSR-x emulation this gives ``AQ <= x-1``
  (Theorem 2.3) at relative communication ``1/x``.
* ``et``     -- Error-Triggered ET-x: a message as soon as the (mirrored)
  approximation error reaches ``x``.  Bounds ``AQ <= x-1`` for *any*
  emulation algorithm (Prop 6.8); with MSR the relative communication
  decays as ``O(1/x^2)`` under heavy load (Theorem 2.5).
* ``et_rt``  -- hybrid ET-x with an RT fallback: triggers on error >= x
  *or* after ``rt_period`` silent slots, whichever comes first.  Keeps the
  deterministic ET bound while capping staleness in light-traffic /
  idle regimes where ET alone can stay silent arbitrarily long.
* ``exact``  -- full-state baseline: one message per departure
  (Prop 6.1), the denominator of "relative communication".
* ``none``   -- never trigger (exact-state policies whose communication is
  accounted analytically, or pure open-loop emulation).

Pull patterns (server-initiated tokens; van der Boor et al. 2019):

* ``jiq``    -- Join-the-Idle-Queue: a server sends exactly when it
  *becomes* idle (a departure leaves its queue empty), pushing an idle
  token to the balancer.  At most one message per job, by construction.
* ``hsq``    -- hyper-scalable JSQ: a server reports when its queue
  *drops below* the threshold ``x`` (a downward crossing), plus a
  periodic refresh every ``rt_period`` slots so the balancer's token
  pool is replenished at a traced rate even in steady traffic.

Both pull kinds carry the same payload as push kinds -- the sender's
exact queue length -- so the token traffic rides :func:`net_step`
unchanged and experiences the same delay/jitter/drop as push updates.
The balancer-side token pool lives with the policies (the routing layer),
not here: this module only decides *when a server speaks*.

The module is pure and vectorised over the server axis.  It is written
against the shared ``numpy``/``jax.numpy`` array API: pass ``xp=jnp``
(default) inside jitted ``lax.scan`` bodies (the slotted simulator and
the jax serving engine, whose trigger thresholds arrive as traced
``EngineScenario`` operands), or ``xp=np`` from host-side hot loops (the
numpy ``CareDispatcher`` reference) -- both produce identical trigger
decisions and message counts, which is what lets the serving tier's two
backends be bit-identical.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Literal, Optional, Tuple

import jax
import jax.numpy as jnp

CommKind = Literal["none", "rt", "dt", "et", "et_rt", "exact", "jiq", "hsq"]

# Server-initiated (pull) comm kinds.  Each pairs 1:1 with the routing
# policy of the same name: the comm kind decides when a server pushes a
# token, the policy decides how the balancer spends its token pool.
PULL_KINDS = ("jiq", "hsq")

# Control-plane network model kinds: "none" keeps today's instant lossless
# delivery (bit-identical, zero overhead); "net" routes every message
# through the traced delay/jitter/drop model of :func:`net_step`.
NetworkKind = Literal["none", "net"]

# Control-plane transport kinds under ``network="net"``: "fire_forget" is
# the historical one-shot wire (a dropped message is gone; recovery relies
# on the trigger re-firing), "ack" is the reliable transport of
# :func:`net_step_ack` (per-send timeout window, exponential backoff,
# fresh-snapshot retransmit, abandonment after ``max_retries``).  A static
# kind: it selects the step function and the carry dataclass at trace
# time, so "fire_forget" programs carry no ack state at all.
TransportKind = Literal["fire_forget", "ack"]


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Communication-pattern configuration: static kind, numeric thresholds.

    Attributes:
      kind: which trigger pattern runs (see module docstring).  Always a
        Python string -- it selects code paths via ``if`` at trace time, so
        it is compile-time by construction.
      x: DT-x departure count / ET-x error threshold.  Stored as a float so
        tiers measuring error in fractional units (e.g. tokens / mu) can use
        the same comparison; integer thresholds behave identically.
      rt_period: RT-r message period in slots; also the staleness cap of the
        ``et_rt`` hybrid.

    ``x`` and ``rt_period`` may be Python numbers *or traced scalars*: the
    trigger comparisons consume them as array operands, which is what lets
    the slotted simulator run a whole ``(load, x, rt_rate)`` grid as one
    compiled program (``slotted_sim.simulate_grid``).  A config holding
    tracers must not be hashed (i.e. never passed as a static jit
    argument); callers build it *inside* the traced function from the
    static kind plus scenario operands.
    """

    kind: CommKind = "et"
    x: float = 3
    rt_period: int = 100

    @staticmethod
    def from_rate(kind: CommKind, x: float = 3, rt_rate: float = 0.01) -> "CommConfig":
        """Build a config from a per-slot message *rate* (RT-r convention)."""
        period = max(int(round(1.0 / max(rt_rate, 1e-9))), 1)
        return CommConfig(kind=kind, x=x, rt_period=period)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CommState:
    """Per-server trigger bookkeeping, shape ``(K,)`` (+ scalar totals).

    ``deps_since_msg`` / ``slots_since_msg`` count departures / slots since
    each server's last message; ``msgs`` is the running message total.
    Fields may be ``jax.numpy`` or ``numpy`` arrays -- the two backends are
    interchangeable (see module docstring).
    """

    deps_since_msg: Any  # (K,) int32
    slots_since_msg: Any  # (K,) int32
    msgs: Any  # () int32 total messages sent

    @staticmethod
    def init(k: int, xp=jnp) -> "CommState":
        return CommState(
            deps_since_msg=xp.zeros((k,), xp.int32),
            slots_since_msg=xp.zeros((k,), xp.int32),
            msgs=xp.zeros((), xp.int32),
        )


def trigger(
    cfg: CommConfig,
    *,
    err=None,
    deps_since=None,
    slots_since=None,
    new_deps=None,
    q=None,
    xp=jnp,
):
    """Pure trigger predicate on already-advanced counters.

    The single place the RT/DT/ET (and pull-token) comparisons live.
    :func:`evaluate` calls this after advancing its per-server counters;
    stateless callers (e.g. the training-tier balancer's host-level
    ``needs_sync``) call it directly with whatever scalar/vector counters
    they track.  Only the operands the ``cfg.kind`` needs may be
    ``None``-free.  ``q`` is the end-of-slot queue length the pull kinds
    key on: ``jiq`` fires on the idle transition (this slot's departures
    emptied the queue), ``hsq`` on a downward crossing of the threshold
    ``x`` or after ``rt_period`` silent slots (the traced token-refresh
    period).
    """
    if cfg.kind == "rt":
        return slots_since >= cfg.rt_period
    if cfg.kind == "dt":
        return deps_since >= cfg.x
    if cfg.kind == "et":
        return err >= cfg.x
    if cfg.kind == "et_rt":
        return (err >= cfg.x) | (slots_since >= cfg.rt_period)
    if cfg.kind == "exact":
        return new_deps > 0
    if cfg.kind == "jiq":
        return (new_deps > 0) & (q == 0)
    if cfg.kind == "hsq":
        return ((q < cfg.x) & (q + new_deps >= cfg.x)) | (
            slots_since >= cfg.rt_period
        )
    if cfg.kind == "none":
        return xp.zeros(xp.shape(deps_since), bool)
    raise ValueError(f"unknown communication kind: {cfg.kind}")


def evaluate(
    state: CommState,
    cfg: CommConfig,
    err,
    new_deps,
    xp=jnp,
    *,
    can_send=None,
    force=None,
    q=None,
    count_msgs: bool = True,
) -> Tuple[Any, CommState]:
    """Advance the pattern by one slot and evaluate the trigger.

    Order matches the paper's slot semantics (and the seed simulator
    bit-for-bit): this slot's departures and the elapsed slot are counted
    *before* the trigger comparison, so a message fires in the same slot the
    condition is met and the end-of-slot error obeys ``AQ <= x-1`` for DT-x
    and ET-x (Theorem 2.3).

    Args:
      state: current :class:`CommState`.
      cfg: :class:`CommConfig` -- ``kind`` is Python-level (callers
        specialise on it); ``x`` / ``rt_period`` may be traced operands.
      err: ``(K,)`` current approximation error per server (any real dtype).
      new_deps: ``(K,)`` departures that completed this slot (int).
      xp: array namespace -- ``jax.numpy`` (default) or ``numpy``.
      can_send: optional ``(K,)`` bool -- servers able to send this slot.
        Crashed servers (fault process) pass ``False`` here: their trigger is
        suppressed but the underlying counters keep advancing, so the very
        first healthy slot re-fires any due trigger (resync retry path).
      force: optional ``(K,)`` bool -- servers that must send regardless of
        the trigger predicate (resync-on-recovery).  Applied before
        ``can_send``.
      q: optional ``(K,)`` end-of-slot queue length, required by the pull
        kinds (``jiq`` / ``hsq``) and ignored by everything else.
      count_msgs: when ``False`` the trigger *intent* is returned but
        ``msgs`` is left untouched -- the network model (:func:`net_step`)
        owns message accounting because piggyback batching makes
        sends-on-the-wire differ from trigger events.

    Returns:
      ``(triggered, state')`` where ``triggered`` is a ``(K,)`` bool mask of
      servers that send a message this slot (the caller snaps its
      approximation to the truth for exactly these servers) and ``state'``
      has counters reset for triggered servers and ``msgs`` accumulated.
    """
    deps_since = state.deps_since_msg + new_deps
    slots_since = state.slots_since_msg + 1

    triggered = trigger(
        cfg,
        err=err,
        deps_since=deps_since,
        slots_since=slots_since,
        new_deps=new_deps,
        q=q,
        xp=xp,
    )
    if force is not None:
        triggered = triggered | force
    if can_send is not None:
        triggered = triggered & can_send

    if not count_msgs:
        sent = xp.zeros((), xp.int32)
    elif cfg.kind == "exact":
        # Full state information costs one message per departure (Prop 6.1),
        # even when several departures share a slot.
        sent = xp.sum(new_deps, dtype=xp.int32)
    else:
        sent = xp.sum(triggered, dtype=xp.int32)

    return triggered, CommState(
        deps_since_msg=xp.where(triggered, 0, deps_since),
        slots_since_msg=xp.where(triggered, 0, slots_since),
        msgs=state.msgs + sent,
    )

@dataclasses.dataclass(frozen=True)
class NetworkConfig:
    """Control-plane network model: static kind, traced numeric operands.

    Mirrors :class:`CommConfig`'s static-kind/traced-operand split.  With
    ``kind="none"`` no :class:`NetState` exists and delivery is today's
    instant lossless path, bit-identical.  With ``kind="net"`` every
    server->balancer message traverses :func:`net_step`:

    * ``delay`` -- deterministic delivery delay in slots (RTT/2; a message
      sent in slot t is applied at the balancer in slot ``t + delay``).
    * ``jitter`` -- additional uniform integer delay in ``[0, jitter]``,
      sampled i.i.d. per message.
    * ``drop`` -- i.i.d. probability a sent message is lost in flight.  A
      lost message still costs one message on the wire; under
      ``transport="fire_forget"`` no ack exists, so recovery relies on the
      trigger re-firing (ET re-arms as error keeps growing; RT/et_rt
      re-fires after ``rt_period`` slots).

    ``transport`` selects the wire semantics (a *static* kind, like
    ``kind``): ``"fire_forget"`` is the historical one-shot path above;
    ``"ack"`` runs :func:`net_step_ack`, where every data send opens a
    timeout window of ``ack_timeout`` slots (growing by ``backoff_base``
    per retry), an unacked message retransmits a *fresh* snapshot at
    expiry, and after ``max_retries`` retransmits the update is abandoned
    and the server marks itself suspect (``AckNetState.gave_up``).  Acks
    and the optional server keepalives (every ``ka_period`` slots) ride
    the same delay/jitter/drop wire and are billed as real messages.

    All numeric operands may be Python numbers or traced scalars, so a
    delay x drop x timeout ladder shares one compiled program.
    """

    kind: NetworkKind = "none"
    delay: Any = 0
    jitter: Any = 0
    drop: Any = 0.0
    transport: TransportKind = "fire_forget"
    # Reliable-transport operands (traced; neutral under "fire_forget").
    ack_timeout: Any = 0  # slots a sender waits for an ack (>= 1 under "ack")
    backoff_base: Any = 1.0  # timeout multiplier per retransmit (>= 1)
    max_retries: Any = 0  # retransmits before the update is abandoned
    ka_period: Any = 0  # server keepalive period in slots (0 = none)


def network_config(
    network: str, transport: str, scn
) -> Optional[NetworkConfig]:
    """The :class:`NetworkConfig` of a traced program: its static kinds,
    and its numeric operands from ``scn``, a scenario with the fields
    ``net_delay``, ``net_jitter``, ``net_drop`` and, under
    ``transport="ack"``, ``ack_timeout``, ``backoff_base``, ``max_retries``
    and ``ka_period``.  ``None`` without a network model."""
    if network == "none":
        return None
    if transport == "ack":
        return NetworkConfig(
            kind=network, delay=scn.net_delay, jitter=scn.net_jitter,
            drop=scn.net_drop, transport="ack", ack_timeout=scn.ack_timeout,
            backoff_base=scn.backoff_base, max_retries=scn.max_retries,
            ka_period=scn.ka_period,
        )
    return NetworkConfig(
        kind=network, delay=scn.net_delay, jitter=scn.net_jitter,
        drop=scn.net_drop,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class NetState:
    """Per-server in-flight message buffer, shape ``(K,)`` (+ scalar totals).

    Each server has one in-flight slot (messages are tiny and serialised per
    sender): ``timer`` counts down the slots until the in-flight message is
    applied at the balancer (``-1`` = nothing in flight), ``payload`` carries
    the state snapshot taken at send time, and ``pending`` marks a trigger
    that fired while a message was already in flight -- it is *piggybacked*:
    batched behind the in-flight message and sent (with a fresh snapshot)
    the slot the channel frees up, costing one message no matter how many
    triggers queued.  ``age`` counts slots since the balancer last received
    an update from each server -- the staleness clock the suspect-server
    timeout reads.  ``drops`` totals messages lost in flight.
    """

    timer: Any  # (K,) int32, -1 = idle
    payload: Any  # (K,) snapshot in flight (payload dtype is tier-specific)
    pending: Any  # (K,) bool, queued trigger to piggyback
    age: Any  # (K,) int32 slots since last delivered update
    drops: Any  # () int32 total messages lost

    @staticmethod
    def init(k: int, xp=jnp, payload_dtype=None) -> "NetState":
        dtype = payload_dtype if payload_dtype is not None else xp.int32
        return NetState(
            timer=xp.full((k,), -1, xp.int32),
            payload=xp.zeros((k,), dtype),
            pending=xp.zeros((k,), bool),
            age=xp.zeros((k,), xp.int32),
            drops=xp.zeros((), xp.int32),
        )


def net_step(
    state: NetState,
    cfg: NetworkConfig,
    triggered,
    payload_now,
    drop_u,
    jit_u,
    xp=jnp,
    can_send=None,
) -> Tuple[Any, Any, Any, NetState]:
    """Advance the network by one slot: send, fly, drop, deliver, piggyback.

    Written against the shared numpy/jax array namespace like
    :func:`evaluate`, so the jax scans and the numpy ``CareDispatcher``
    reference share one delivery semantics bit-for-bit.

    Per-slot order (all vectorised over the server axis):

    1. in-flight messages with ``timer == 0`` are *due* this slot;
    2. a server sends iff its channel is free (idle or due) and it either
       triggered now or has a ``pending`` piggybacked trigger -- the send
       snapshots ``payload_now`` (fresh state, not the stale queued one);
    3. each send costs one message; with probability ``drop`` it is lost
       (counted in ``drops``, never delivered, channel stays idle so the
       next trigger can retry);
    4. surviving sends draw ``delay + U{0..jitter}`` total delay: zero-delay
       sends deliver *this slot* (the ``none``-kind instant path, which is
       what makes a zero-operand ``net`` cell bit-identical to ``none``),
       positive-delay sends enter the in-flight buffer;
    5. due messages deliver; ``age`` resets for delivered servers and
       advances otherwise.

    Args:
      state: current :class:`NetState`.
      cfg: :class:`NetworkConfig` with ``kind == "net"``.
      triggered: ``(K,)`` bool trigger intents from :func:`evaluate`.
      payload_now: ``(K,)`` current true state to snapshot on send.
      drop_u: ``(K,)`` f32 i.i.d. uniforms for the drop draw.
      jit_u: ``(K,)`` f32 i.i.d. uniforms for the jitter draw.
      xp: array namespace -- ``jax.numpy`` (default) or ``numpy``.
      can_send: optional ``(K,)`` bool -- servers able to put a message on
        the wire this slot (crash-fault callers pass ``~faulted``).  A
        ``False`` server neither sends nor *keeps* a queued piggyback: its
        pre-crash ``pending`` snapshot intent is wiped, because the state
        it described died with the crash -- the forced recovery resync
        (a fresh snapshot) is the only correct re-entry message.

    Returns:
      ``(delivered, out_payload, sent, state')``: ``delivered`` is the
      ``(K,)`` bool mask of servers whose update reaches the balancer this
      slot, ``out_payload`` the snapshot to apply for those servers, and
      ``sent`` the () int32 count of messages put on the wire this slot
      (the caller adds it to ``CommState.msgs``).
    """
    in_flight = state.timer >= 0
    due = in_flight & (state.timer == 0)
    free = ~in_flight | due

    send = (triggered | state.pending) & free
    if can_send is not None:
        send = send & can_send
    # Triggers arriving while the channel is busy queue up for piggybacking;
    # a send clears the queue (the fresh snapshot covers everything queued).
    pending = (state.pending | triggered) & ~send
    if can_send is not None:
        # A crashed server's queued piggyback describes pre-crash state;
        # it must not fire at the next free slot ahead of the recovery
        # resync, so the crash wipes it.
        pending = pending & can_send

    lost = send & (drop_u < cfg.drop)
    # f32 jitter draw: u in [0,1) so floor(u * (jitter+1)) <= jitter.
    extra = (jit_u * xp.asarray(cfg.jitter + 1, xp.float32)).astype(xp.int32)
    total_delay = xp.asarray(cfg.delay, xp.int32) + extra

    enq = send & ~lost
    instant = enq & (total_delay == 0)
    flying = enq & (total_delay > 0)

    delivered = due | instant
    # Two distinct payloads on a handoff slot (a due delivery coinciding
    # with a new send): the *delivered* snapshot is the due message's
    # send-time payload (or the fresh one for an instant send, which
    # lands later within the slot and wins), while the *stored* snapshot
    # is the new send's -- the due payload must not be overwritten
    # before it is read.
    out_payload = xp.where(instant, payload_now, state.payload)
    stored = xp.where(flying | instant, payload_now, state.payload)

    timer = xp.where(
        flying,
        total_delay - 1,
        xp.where(in_flight & ~due, state.timer - 1, -1),
    ).astype(xp.int32)

    sent = xp.sum(send, dtype=xp.int32)
    return delivered, out_payload, sent, NetState(
        timer=timer,
        payload=stored,
        pending=pending,
        age=xp.where(delivered, 0, state.age + 1).astype(xp.int32),
        drops=state.drops + xp.sum(lost, dtype=xp.int32),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AckNetState:
    """Reliable-transport wire state, shape ``(K,)`` (+ scalar totals).

    The ``transport="ack"`` counterpart of :class:`NetState` -- a separate
    dataclass so fire-and-forget programs carry none of this structure.
    Three single-slot channels exist per server (supersede semantics: a
    newer message on a channel replaces an older one still in flight --
    under the retransmit protocol the newer snapshot strictly dominates):

    * data (server -> balancer): ``timer`` / ``payload`` / ``pending``
      exactly as in :class:`NetState`;
    * ack (balancer -> server): ``ack_timer``, one ack per data delivery;
    * keepalive (server -> balancer): ``ka_timer``, fired every
      ``ka_period`` slots (``ka_since`` is the sender-side clock).

    ``awaiting`` counts down the open timeout window of the latest data
    transmission (``-1`` = nothing awaited), ``backoff`` is the current
    window length on the exponential ladder (f32 so the traced base
    multiplies exactly the same way under numpy and jax -- no ``pow``,
    whose libm/XLA implementations could disagree bit-wise), ``retries``
    the retransmits spent on the awaited update, and ``gave_up`` marks a
    server that abandoned after ``max_retries`` -- a *self-suspect* that
    stays masked until some later transmission is acked.  ``ka_age`` is
    the balancer's last-heard clock (reset by any data *or* keepalive
    delivery) that keepalive-driven suspect masking reads; ``age`` remains
    the data-staleness clock.  ``drops`` totals losses across all three
    channels; ``retrans`` totals retransmitted data messages.
    """

    timer: Any  # (K,) int32 data in flight, -1 = idle
    payload: Any  # (K,) snapshot in flight (payload dtype is tier-specific)
    pending: Any  # (K,) bool, queued trigger to piggyback
    awaiting: Any  # (K,) int32 slots left in the timeout window, -1 = none
    backoff: Any  # (K,) f32 current timeout-window length (slots)
    retries: Any  # (K,) int32 retransmits spent on the awaited update
    ack_timer: Any  # (K,) int32 ack in flight, -1 = idle
    gave_up: Any  # (K,) bool abandoned after max_retries (self-suspect)
    ka_timer: Any  # (K,) int32 keepalive in flight, -1 = idle
    ka_since: Any  # (K,) int32 slots since last keepalive send
    ka_age: Any  # (K,) int32 balancer slots since last heard (data or ka)
    age: Any  # (K,) int32 slots since last delivered data update
    drops: Any  # () int32 total messages lost (data + ack + keepalive)
    retrans: Any  # () int32 total data retransmits

    @staticmethod
    def init(k: int, xp=jnp, payload_dtype=None) -> "AckNetState":
        dtype = payload_dtype if payload_dtype is not None else xp.int32
        return AckNetState(
            timer=xp.full((k,), -1, xp.int32),
            payload=xp.zeros((k,), dtype),
            pending=xp.zeros((k,), bool),
            awaiting=xp.full((k,), -1, xp.int32),
            backoff=xp.zeros((k,), xp.float32),
            retries=xp.zeros((k,), xp.int32),
            ack_timer=xp.full((k,), -1, xp.int32),
            gave_up=xp.zeros((k,), bool),
            ka_timer=xp.full((k,), -1, xp.int32),
            ka_since=xp.zeros((k,), xp.int32),
            ka_age=xp.zeros((k,), xp.int32),
            age=xp.zeros((k,), xp.int32),
            drops=xp.zeros((), xp.int32),
            retrans=xp.zeros((), xp.int32),
        )


def net_step_ack(
    state: AckNetState,
    cfg: NetworkConfig,
    triggered,
    payload_now,
    drop_u,
    jit_u,
    ack_u,
    xp=jnp,
    can_send=None,
) -> Tuple[Any, Any, Any, AckNetState]:
    """Advance the reliable (ack'd) transport by one slot.

    The ``transport="ack"`` counterpart of :func:`net_step`, written
    against the same shared numpy/jax namespace so both engine backends
    share one delivery semantics bit-for-bit.  Per-slot order:

    1. due traffic arrives: data at the balancer, acks and keepalives at
       their receivers;
    2. an arriving ack closes the sender's timeout window; a window that
       expires *un*-acked either retransmits -- a **fresh**
       ``payload_now`` snapshot, never the stale in-flight payload; by
       then the state it described is history -- or, once ``retries``
       reaches ``max_retries``, abandons the update and marks the server
       ``gave_up`` (self-suspect, cleared by the next successful ack);
    3. a free server (no open window, or one just closed) sends on a
       trigger or queued piggyback; every send (re- or new) opens a
       timeout window of ``backoff`` slots -- ``ack_timeout`` on a new
       send, multiplied by ``backoff_base`` (clamped at ``2^30``) per
       retransmit;
    4. data rides the wire exactly as in :func:`net_step` (drop, then
       ``delay + U{0..jitter}``; zero total delay delivers this slot);
    5. the balancer acks every delivery; acks ride the *same* wire with
       their own drop/jitter draws and are billed as messages -- the
       protocol's overhead must show on the message axis it is meant to
       protect;
    6. every ``ka_period`` slots a server fires a keepalive (same wire,
       also billed); any data or keepalive delivery resets the balancer's
       ``ka_age`` clock for that server.

    Args:
      state: current :class:`AckNetState`.
      cfg: :class:`NetworkConfig` with ``kind="net"``,
        ``transport="ack"``.
      triggered: ``(K,)`` bool trigger intents from :func:`evaluate`.
      payload_now: ``(K,)`` current true state to snapshot on send.
      drop_u / jit_u: ``(K,)`` f32 uniforms for the data-channel draws.
      ack_u: ``(4, K)`` f32 uniforms for the ack and keepalive channels,
        rows ``(ack drop, ack jitter, ka drop, ka jitter)``.
      xp: array namespace -- ``jax.numpy`` (default) or ``numpy``.
      can_send: optional ``(K,)`` bool; as in :func:`net_step`, a
        ``False`` server sends nothing (no new send, no retransmit, no
        keepalive), its queued ``pending`` is wiped, and an expired
        timeout window holds at zero until the server can act again.

    Returns:
      ``(delivered, out_payload, sent, state')`` exactly as
      :func:`net_step`; ``sent`` bills data sends, acks and keepalives.
    """
    # 1. due arrivals on the three channels.
    in_flight = state.timer >= 0
    due = in_flight & (state.timer == 0)
    ack_arr = (state.ack_timer >= 0) & (state.ack_timer == 0)
    ka_due = (state.ka_timer >= 0) & (state.ka_timer == 0)

    # 2. timeout bookkeeping: expiry -> retransmit or abandon.
    awaiting = state.awaiting >= 0
    expired = awaiting & ~ack_arr & (state.awaiting == 0)
    if can_send is not None:
        expired = expired & can_send
    abandon = expired & (state.retries >= xp.asarray(cfg.max_retries, xp.int32))
    retrans_now = expired & ~abandon

    # 3. sends: new triggers need a free window; retransmits reuse theirs.
    free = ~awaiting | ack_arr | abandon
    trig_all = triggered | state.pending
    if can_send is not None:
        trig_all = trig_all & can_send
    send_new = trig_all & free
    send = send_new | retrans_now
    pending = (state.pending | triggered) & ~send
    if can_send is not None:
        pending = pending & can_send

    # 4. data wire (identical draws and instant-delivery rule to net_step;
    # a send while an older message is still flying supersedes it).
    lost = send & (drop_u < cfg.drop)
    extra = (jit_u * xp.asarray(cfg.jitter + 1, xp.float32)).astype(xp.int32)
    total_delay = xp.asarray(cfg.delay, xp.int32) + extra
    enq = send & ~lost
    instant = enq & (total_delay == 0)
    flying = enq & (total_delay > 0)
    delivered = due | instant
    out_payload = xp.where(instant, payload_now, state.payload)
    stored = xp.where(flying | instant, payload_now, state.payload)
    timer = xp.where(
        flying,
        total_delay - 1,
        xp.where(
            send, -1, xp.where(in_flight & ~due, state.timer - 1, -1)
        ),
    ).astype(xp.int32)

    # 5. ack wire: one ack per delivery, own drop/jitter draws.
    ack_lost = delivered & (ack_u[0] < cfg.drop)
    ack_extra = (
        ack_u[1] * xp.asarray(cfg.jitter + 1, xp.float32)
    ).astype(xp.int32)
    ack_delay = xp.asarray(cfg.delay, xp.int32) + ack_extra
    ack_enq = delivered & ~ack_lost
    ack_instant = ack_enq & (ack_delay == 0)
    ack_flying = ack_enq & (ack_delay > 0)
    ack_timer = xp.where(
        ack_flying,
        ack_delay - 1,
        xp.where(
            delivered,
            -1,
            xp.where(
                (state.ack_timer >= 0) & ~ack_arr, state.ack_timer - 1, -1
            ),
        ),
    ).astype(xp.int32)
    acked = ack_arr | ack_instant

    # Timeout window for this slot's sends: the backoff ladder multiplies
    # (f32-exact under both namespaces); the i32 window is >= 1 slot.
    grown = xp.minimum(
        state.backoff * xp.asarray(cfg.backoff_base, xp.float32),
        xp.asarray(2.0**30, xp.float32),
    )
    backoff = xp.where(
        send_new,
        xp.asarray(cfg.ack_timeout, xp.float32),
        xp.where(retrans_now, grown, state.backoff),
    ).astype(xp.float32)
    window = xp.maximum(backoff.astype(xp.int32), 1)
    # A send whose data *and* ack both arrive this slot (the zero-delay
    # wire) completes its round trip immediately: no window stays open.
    rt_done = send & instant & ack_instant
    await_t = xp.where(
        send,
        xp.where(rt_done, -1, window - 1),
        xp.where(
            awaiting & ~acked & ~abandon,
            # maximum() holds an expired-but-unactionable window (crashed
            # sender) at zero so it fires on the first healthy slot.
            xp.maximum(state.awaiting - 1, 0),
            -1,
        ),
    ).astype(xp.int32)
    retries = xp.where(
        send_new,
        0,
        xp.where(
            retrans_now,
            state.retries + 1,
            xp.where(acked, 0, state.retries),
        ),
    ).astype(xp.int32)
    gave_up = (state.gave_up | abandon) & ~acked

    # 6. keepalives: fired by the server clock, same wire, billed.
    ka_p = xp.asarray(cfg.ka_period, xp.int32)
    ka_since = state.ka_since + 1
    ka_fire = (ka_p > 0) & (ka_since >= ka_p)
    if can_send is not None:
        ka_fire = ka_fire & can_send
    ka_lost = ka_fire & (ack_u[2] < cfg.drop)
    ka_extra = (
        ack_u[3] * xp.asarray(cfg.jitter + 1, xp.float32)
    ).astype(xp.int32)
    ka_delay = xp.asarray(cfg.delay, xp.int32) + ka_extra
    ka_enq = ka_fire & ~ka_lost
    ka_instant = ka_enq & (ka_delay == 0)
    ka_flying = ka_enq & (ka_delay > 0)
    ka_deliv = ka_due | ka_instant
    ka_timer = xp.where(
        ka_flying,
        ka_delay - 1,
        xp.where(
            ka_fire,
            -1,
            xp.where((state.ka_timer >= 0) & ~ka_due, state.ka_timer - 1, -1),
        ),
    ).astype(xp.int32)

    sent = (
        xp.sum(send, dtype=xp.int32)
        + xp.sum(delivered, dtype=xp.int32)  # acks: one per delivery
        + xp.sum(ka_fire, dtype=xp.int32)
    )
    return delivered, out_payload, sent, AckNetState(
        timer=timer,
        payload=stored,
        pending=pending,
        awaiting=await_t,
        backoff=backoff,
        retries=retries,
        ack_timer=ack_timer,
        gave_up=gave_up,
        ka_timer=ka_timer,
        ka_since=xp.where(ka_fire, 0, ka_since).astype(xp.int32),
        ka_age=xp.where(delivered | ka_deliv, 0, state.ka_age + 1).astype(
            xp.int32
        ),
        age=xp.where(delivered, 0, state.age + 1).astype(xp.int32),
        drops=state.drops
        + xp.sum(lost, dtype=xp.int32)
        + xp.sum(ack_lost, dtype=xp.int32)
        + xp.sum(ka_lost, dtype=xp.int32),
        retrans=state.retrans + xp.sum(retrans_now, dtype=xp.int32),
    )


def control_plane_init(
    k: int,
    *,
    network: str = "none",
    fault: str = "none",
    transport: str = "fire_forget",
    xp=jnp,
    payload_dtype=None,
):
    """Initial control-plane carries for one engine instance.

    The single constructor every tier's scan/stream carry goes through:
    returns ``(comm, net, faulted)`` where ``net`` / ``faulted`` are
    ``None`` (an empty pytree subtree) when the corresponding kind is off,
    so the default program structure is unchanged.  Under
    ``transport="ack"`` the wire state is an :class:`AckNetState`; the
    default "fire_forget" keeps the historical :class:`NetState`
    structure.  The streaming serving engine initialises its chunk carry
    here and a future live arrival feed resumes from the same triple via
    :func:`snapshot_state` / :func:`restore_state`.
    """
    comm = CommState.init(k, xp=xp)
    if network == "none":
        net = None
    elif transport == "ack":
        net = AckNetState.init(k, xp=xp, payload_dtype=payload_dtype)
    else:
        net = NetState.init(k, xp=xp, payload_dtype=payload_dtype)
    faulted = xp.zeros((k,), bool) if fault != "none" else None
    return comm, net, faulted


def snapshot_state(tree):
    """Host-side numpy copy of a control-plane (or whole-engine) carry.

    The persistence half of the resume seam: a carry pytree -- any nesting
    of :class:`CommState` / :class:`NetState` / :class:`AckNetState` /
    plain arrays -- becomes concrete ``numpy`` arrays safe to hold across
    jit calls, pickle to disk, or hand to a host-side dispatcher between
    stream segments.

    Scalar int32 counters (``CommState.msgs``, ``NetState.drops``,
    ``AckNetState.retrans``, the engine's completion totals, ...) are
    promoted to **int64** on the way out: a multi-segment soak aggregates
    host-side from these snapshots, and at 1e7-slot horizons with
    several messages per slot an int32 total wraps.  The promotion is
    reversed by :func:`restore_state`, so the on-device carry structure
    is untouched.
    """
    import numpy as np

    def cvt(a):
        a = np.asarray(a)
        if a.ndim == 0 and a.dtype == np.int32:
            return a.astype(np.int64)
        return a

    return jax.tree.map(cvt, tree)


def restore_state(tree, xp=jnp):
    """Reconstitute a :func:`snapshot_state` carry on the target namespace.

    ``xp=jnp`` places the arrays back on device for the jitted scans;
    ``xp=np`` yields the numpy view the host-side ``CareDispatcher``
    mirrors consume.  Structure (including ``None`` subtrees for disabled
    kinds) is preserved -- scalar int64 counters are narrowed back to the
    int32 the compiled carries declare (values above int32 range saturate
    rather than wrap, keeping the on-device counter monotone) -- so the
    restored carry drops straight back into the compiled chunk step that
    produced it.
    """
    import numpy as np

    def cvt(a):
        a = np.asarray(a)
        if a.ndim == 0 and a.dtype == np.int64:
            a = np.int32(min(int(a), np.iinfo(np.int32).max))
        return xp.asarray(a)

    return jax.tree.map(cvt, tree)


def validate_control_plane(
    *,
    network: str = "none",
    net_delay: float = 0,
    net_jitter: float = 0,
    net_drop: float = 0.0,
    suspect_age: float = 0,
    fault: str = "none",
    crash_rate: float = 0.0,
    recover_rate: float = 0.0,
    slow_factor: float = 1.0,
    transport: str = "fire_forget",
    ack_timeout: float = 0,
    backoff_base: float = 1.0,
    max_retries: float = 0,
    ka_period: float = 0,
    policy: str = None,
    comm: str = None,
    token_refresh: float = None,
) -> None:
    """Reject invalid network/fault/pull operands at config-validation time.

    Called from the host-side config entry points of both tiers
    (``SimConfig``/``Scenario.create`` and ``ServeConfig``/
    ``EngineConfig``) before anything is traced, mirroring the
    ``route_backend="pallas"`` corner-pinning style: every error names the
    offending field and the fix.

    ``policy`` / ``comm`` / ``token_refresh`` are the pull-family operands:
    when a tier passes its policy and comm kinds, the 1:1 pairing of the
    pull policies (``jiq`` / ``hsq``) with their token channels is enforced
    here, along with the sign of the hsq token-refresh operand (the traced
    rate in the slotted tier, the refresh period in the serving tier).
    Callers that do not model policies simply omit them.
    """
    if policy is not None and comm is not None:
        if policy in PULL_KINDS:
            if comm == "exact":
                raise ValueError(
                    f"policy={policy!r} cannot run under comm='exact' --"
                    " the exact full-state channel is push-per-departure"
                    " and would double-bill the token traffic; set"
                    f" comm={policy!r} (the matching pull token channel)"
                )
            if comm != policy:
                raise ValueError(
                    f"policy={policy!r} requires comm={policy!r} (its"
                    f" server-initiated token channel), got comm={comm!r}"
                )
        elif comm in PULL_KINDS:
            raise ValueError(
                f"comm={comm!r} is the token channel of policy={comm!r};"
                f" it cannot drive the push policy {policy!r}"
            )
    if token_refresh is not None and token_refresh < 0:
        raise ValueError(
            f"token_refresh must be >= 0 (the hsq token-refresh rate;"
            f" 0 disables the periodic refresh), got {token_refresh}"
        )
    if network not in ("none", "net"):
        raise ValueError(
            f"unknown network kind: {network!r} (expected 'none' or 'net')"
        )
    if fault not in ("none", "crash", "slow"):
        raise ValueError(
            f"unknown fault kind: {fault!r} "
            "(expected 'none', 'crash' or 'slow')"
        )
    if net_delay < 0:
        raise ValueError(f"net_delay must be >= 0 slots, got {net_delay}")
    if net_jitter < 0:
        raise ValueError(f"net_jitter must be >= 0 slots, got {net_jitter}")
    if net_drop < 0:
        raise ValueError(
            f"net_drop is a probability and must be >= 0, got {net_drop}"
        )
    if net_drop >= 1:
        raise ValueError(
            f"net_drop must be < 1, got {net_drop} -- a drop probability of"
            " 1 loses every message and no trigger retry can ever land"
        )
    if suspect_age < 0:
        raise ValueError(
            f"suspect_age must be >= 0 slots (0 disables suspect masking),"
            f" got {suspect_age}"
        )
    if network == "none":
        for field, val in (
            ("net_delay", net_delay),
            ("net_jitter", net_jitter),
            ("net_drop", net_drop),
        ):
            if val != 0:
                raise ValueError(
                    f"{field}={val} has no effect with network='none';"
                    " set network='net' to model the control plane"
                )
    if transport not in ("fire_forget", "ack"):
        raise ValueError(
            f"unknown transport kind: {transport!r} (expected"
            " 'fire_forget' or 'ack')"
        )
    if transport == "ack":
        if network == "none":
            raise ValueError(
                "transport='ack' needs network='net' -- with"
                " network='none' delivery is instant and lossless, so"
                " there is nothing to acknowledge"
            )
        if ack_timeout < 1:
            raise ValueError(
                f"ack_timeout must be >= 1 slot under transport='ack'"
                f" (a sender must wait at least one slot for its ack;"
                f" 0 would retransmit every slot forever), got"
                f" {ack_timeout}"
            )
        if backoff_base < 1:
            raise ValueError(
                f"backoff_base must be >= 1 (the timeout window may only"
                f" grow across retries), got {backoff_base}"
            )
        if max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0 (0 abandons after the first"
                f" unacked window), got {max_retries}"
            )
        if ka_period < 0:
            raise ValueError(
                f"ka_period must be >= 0 slots (0 disables keepalives),"
                f" got {ka_period}"
            )
    else:
        for field, val, neutral in (
            ("ack_timeout", ack_timeout, 0),
            ("backoff_base", backoff_base, 1.0),
            ("max_retries", max_retries, 0),
            ("ka_period", ka_period, 0),
        ):
            if val != neutral:
                raise ValueError(
                    f"{field}={val} has no effect with"
                    " transport='fire_forget'; set transport='ack' for"
                    " the reliable transport"
                )
    if not 0.0 <= crash_rate <= 1.0:
        raise ValueError(
            f"crash_rate is a per-slot probability in [0, 1], got {crash_rate}"
        )
    if not 0.0 <= recover_rate <= 1.0:
        raise ValueError(
            f"recover_rate is a per-slot probability in [0, 1],"
            f" got {recover_rate}"
        )
    if crash_rate > 0 and recover_rate == 0:
        raise ValueError(
            "recover_rate must be > 0 when crash_rate > 0 -- with"
            f" recover_rate=0 every crashed server (crash_rate={crash_rate})"
            " stays down forever and the system drains to zero capacity"
        )
    if slow_factor <= 0 or slow_factor > 1:
        raise ValueError(
            f"slow_factor scales service_rates and must be in (0, 1],"
            f" got {slow_factor}"
        )
    if fault == "none":
        for field, val, neutral in (
            ("crash_rate", crash_rate, 0.0),
            ("recover_rate", recover_rate, 0.0),
            ("slow_factor", slow_factor, 1.0),
        ):
            if val != neutral:
                raise ValueError(
                    f"{field}={val} has no effect with fault='none';"
                    " set fault='crash' or fault='slow'"
                )
    if fault == "crash" and slow_factor != 1.0:
        raise ValueError(
            f"slow_factor={slow_factor} has no effect with fault='crash';"
            " use fault='slow' for transient slowdowns"
        )
    if suspect_age > 0 and network == "none" and fault == "none":
        raise ValueError(
            "suspect_age > 0 needs a modeled control plane -- with"
            " network='none' and fault='none' updates are instant and"
            " servers never fail, so the staleness timeout would only"
            " mis-mask idle servers; enable network='net' and/or a fault"
            " kind"
        )
