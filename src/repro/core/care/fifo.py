"""How the slotted simulator carries each server's FIFO queue of jobs.

The scan of :func:`repro.core.care.slotted_sim._sim_core` keeps, per run,
one *carriage* of the K servers' FIFOs.  Which one it takes depends on the
static kinds (:func:`carriage`):

* At unit rate with no fault model (no ``service_rates``, ``fault="none"``)
  every server works one unit per slot and so serves its FIFO by
  Lindley's recursion: a job of size ``S`` admitted in slot ``t`` departs
  in slot ``max(t, D + 1) + S - 1``, where ``D`` is the departure slot of
  the job admitted before it.  Admission then knows the departure: the
  :class:`Calendar` keeps per run one bit per server and slot of the
  departures to come, which admission writes and the service phase reads
  at the slot index, and emits each admitted job's completion slot as its
  per-slot output.  A departure due within the next ``NEAR_WINDOW``
  slots is ORed into a circular *window* by a dense one-hot select; only
  a later one goes into the full departure *calendar*, by a scatter that
  runs when some run of the batch needs it.  No per-job state is
  gathered inside the loop and nothing is scattered after it.
* Otherwise (the credit schedule of heterogeneous rates,
  ``workload.service_units``, crash or slow faults) the work per slot
  varies: the :class:`JobRing`.  Job FIFOs are circular buffers of job
  ids, the head job counts down its remaining size, and the completion
  slot of each departed id is scattered after the scan (the ``complete``
  scope).

Both are registered pytrees with one interface: ``init``, ``admit`` (in
the ``route`` phase), ``depart`` (in ``service``) and ``completions`` (the
scan's per-slot outputs to each job id's completion slot, -1 if none).
The slot step keeps the queue lengths.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

# Slots ahead that the departure window covers (capped by the scan length).
# At the paper's loads a departure lies a few hundred slots past admission
# at most (a geometric size of mean 30 passes 1024 with probability
# ~1e-15), so almost every departure is written to the window.  On a TPU
# v5e the paper grid's step is slightly faster with 1024 slots than with
# 512 (whose far writes run in a few hundred steps per call) and twice as
# slow with 256.
NEAR_WINDOW = 1024
# The vmapped run axis of a grid program; the far departure write reduces
# its predicate over it.
BATCH_AXIS = "batch"


def carriage(static) -> type:
    """The FIFO carriage of a program's static kinds (a ``StaticConfig``;
    see the module docstring)."""
    if not static.use_rates and static.fault == "none":
        return Calendar
    return JobRing


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Calendar:
    """The departure slot of each server's last admitted job, and the
    departure bits, W = ceil(K / 32) words per slot, flat so that each is
    one long lane axis: the window of the next N = min(T, NEAR_WINDOW)
    slots and, when N < T, the calendar of all T slots with its count."""

    last_dep: jnp.ndarray  # (K,) i32
    win: jnp.ndarray  # (N * W,) u32
    cal: Optional[jnp.ndarray]  # (T * W,) u32; None when T <= N
    far_deps: Optional[jnp.ndarray]  # () i32; None when T <= N

    @classmethod
    def init(cls, k: int, t: int, buffer_cap: int) -> "Calendar":
        words, near = -(-k // 32), min(t, NEAR_WINDOW)
        wraps = near < t
        return cls(
            last_dep=jnp.full((k,), -1, jnp.int32),
            win=jnp.zeros((near * words,), jnp.uint32),
            cal=jnp.zeros((t * words,), jnp.uint32) if wraps else None,
            far_deps=jnp.zeros((), jnp.int32) if wraps else None,
        )

    def _shape(self):
        """(W, N, T) of this carriage."""
        words = -(-self.last_dep.shape[0] // 32)
        near = self.win.shape[0] // words
        slots = near if self.cal is None else self.cal.shape[0] // words
        return words, near, slots

    def admit(self, slot, server, onehot, admit, sel, q, q_sel, size,
              horizon, axis_name=None):
        """Write the departure of the job admitted (if ``admit``) in
        ``slot``; returns the carriage and its completion slot.
        ``axis_name`` names the ``vmap`` axis the run is batched over, if
        any: the far write then runs when any run of the batch needs it."""
        words, near, t = self._shape()
        wraps = near < t
        # Lindley's recursion: the job starts when it arrives or after the
        # job before it departs.  A size past the scan cannot depart in it;
        # capping it there keeps the sum in int32.
        last_sel = jnp.sum(jnp.where(onehot, self.last_dep, 0))
        d = jnp.maximum(slot, last_sel + 1) + jnp.minimum(size, t + 1) - 1
        last_dep = jnp.where(sel, jnp.minimum(d, t), self.last_dep)
        # One bit per departure, ORed in: two servers of one word may
        # depart in the same slot.  A departure past the scan is written
        # nowhere.
        due = admit & (d < t)
        is_near = due & (d - slot < near)
        bit = jnp.left_shift(jnp.uint32(1), server.astype(jnp.uint32) % 32)
        # The window, a dense one-hot select (under vmap a
        # dynamic_update_slice would become a scatter).  Slot slot - 1 was
        # read in the step before: clear it first, as a departure now may
        # fall in its place.
        lane = jnp.arange(near * words, dtype=jnp.int32)
        win = self.win
        if wraps:
            win = jnp.where(lane // words == (slot - 1) % near, 0, win)
        near_at = jnp.where(is_near, (d % near) * words + server // 32, -1)
        win = jnp.where(lane == near_at, win | bit, win)
        cal = far_deps = None
        if wraps:
            # The rare departure past the window goes to the full calendar,
            # by a scatter that runs only in the steps in which some run of
            # the batch has one.
            is_far = due & ~is_near
            far_deps = self.far_deps + is_far.astype(jnp.int32)
            word_at = jnp.where(is_far, d * words + server // 32, t * words)
            # Under vmap a batched predicate would make the cond a select
            # that scatters in every step: reduce it over the batch first.
            any_far = is_far
            if axis_name is not None:
                any_far = jax.lax.pmax(is_far.astype(jnp.int32),
                                       axis_name) > 0
            cal = jax.lax.cond(
                any_far,
                lambda cal: cal.at[word_at].add(bit, mode="drop"),
                lambda cal: cal,
                self.cal,
            )
        comp = jnp.where(admit & (d < horizon) & (d < t), d, -1)
        return Calendar(last_dep, win, cal, far_deps), comp

    def depart(self, slot, q, act, units, sizes):
        """The carriage and this slot's departures ``(K,)``: the slot's
        word, read at the loop counter (one index for every run of a
        batch), spread to one bit per server; none past the horizon."""
        words, near, _ = self._shape()
        k = self.last_dep.shape[0]
        word = jax.lax.dynamic_slice(self.win, ((slot % near) * words,),
                                     (words,))
        if self.cal is not None:
            word = word | jax.lax.dynamic_slice(self.cal, (slot * words,),
                                                (words,))
        bits = jnp.broadcast_to(word[:, None], (words, 32)).reshape(-1)
        shift = jnp.arange(k, dtype=jnp.uint32) % 32
        dep = act & ((bits[:k] >> shift) & 1 == 1)
        return self, dep, None

    @staticmethod
    def completions(ys):
        return ys[0]  # known at admission

    def far_departures(self):
        if self.far_deps is None:
            return jnp.zeros((), jnp.int32)
        return self.far_deps


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class JobRing:
    """Each server's FIFO as a ring of job ids (their arrival slots), its
    head index, and the head job's remaining work."""

    head_rem: jnp.ndarray  # (K,) remaining work of the job in service
    buf_jid: jnp.ndarray  # (K, B) FIFO ring of job ids
    head_ptr: jnp.ndarray  # (K,) FIFO head index

    @classmethod
    def init(cls, k: int, t: int, buffer_cap: int) -> "JobRing":
        return cls(
            head_rem=jnp.zeros((k,), jnp.int32),
            buf_jid=jnp.full((k, buffer_cap), -1, jnp.int32),
            head_ptr=jnp.zeros((k,), jnp.int32),
        )

    def admit(self, slot, server, onehot, admit, sel, q, q_sel, size,
              horizon, axis_name=None):
        """Append job ``slot`` (if ``admit``) to ``server``'s ring; on an
        empty server it starts its countdown."""
        b = self.buf_jid.shape[1]
        head_sel = jnp.sum(jnp.where(onehot, self.head_ptr, 0))
        tail = (head_sel + q_sel) % b
        # Masked one-element scatter (the ring itself still needs indexing).
        buf_jid = self.buf_jid.at[server, tail].set(
            jnp.where(admit, slot, self.buf_jid[server, tail])
        )
        head_rem = jnp.where(sel & (q == 0), size, self.head_rem)
        return JobRing(head_rem, buf_jid, self.head_ptr), None

    def depart(self, slot, q, act, units, sizes):
        """Every busy server works its ``units`` on its head job; returns
        the carriage, the departures and the departed job ids (-1 where
        none).  Past the horizon (``act`` False) nothing serves."""
        k, b = self.buf_jid.shape
        servers = jnp.arange(k)
        busy = (q > 0) & act
        head_rem = jnp.where(busy, self.head_rem - units, self.head_rem)
        dep = busy & (head_rem <= 0)
        departed = jnp.where(dep, self.buf_jid[servers, self.head_ptr % b], -1)
        head_ptr = jnp.where(dep, self.head_ptr + 1, self.head_ptr)
        # Promote the next job (if any) into service with its true size.
        next_jid = self.buf_jid[servers, head_ptr % b]
        next_size = sizes[jnp.clip(next_jid, 0, sizes.shape[0] - 1)]
        left = jnp.where(dep, q - 1, q)
        head_rem = jnp.where(dep & (left > 0), next_size, head_rem)
        return JobRing(head_rem, self.buf_jid, head_ptr), dep, departed

    @staticmethod
    def completions(ys):
        departed = ys[1]
        t = departed.shape[0]
        with jax.named_scope("complete"):
            comp_slot = jnp.full((t,), -1, jnp.int32)
            slot_idx = jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32)[:, None], departed.shape
            )
            valid = departed >= 0
            return comp_slot.at[jnp.where(valid, departed, 0)].max(
                jnp.where(valid, slot_idx, -1)
            )

    def far_departures(self):
        return None  # no window
