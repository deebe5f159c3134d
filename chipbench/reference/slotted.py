"""Plain per-slot reference of the paper's slotted balancer (arXiv:2206.02410).

The setting of the paper's Section 9: K FIFO servers that each complete one
unit of work per slot, at most one arrival per slot, routed by JSAQ (join
the shortest *approximated* queue, ties broken uniformly at random) on
MSR-emulated queue lengths, corrected by ET-x messages.  Within a slot:

1. the arrival (if any) is routed on the pre-slot approximation; a full
   FIFO (``buffer_cap`` jobs) drops it;
2. every busy server works one unit; a finished head job departs and the
   next queued job enters service with its drawn size;
3. the balancer's emulation works one slot per busy emulated queue, each
   emulated job taking ``msr_slots`` slots (Definition 4.8);
4. a server whose error ``|Q - Q_app|`` reaches ``x`` sends its true
   length, which the approximation snaps to (ET-x).

This is a numpy loop over slots, written from the paper and independent of
the code under test.  It is batched over runs only to amortise the
per-slot interpreter cost; runs never interact.  The random inputs -- the
arrival flags, the job sizes and the per-slot tie-break scores -- are
given, not drawn: they come from the same device that ran the program, so
that the comparison is exact (see ``chipbench/drivers/slotted_grid.py``).
"""
from __future__ import annotations

import numpy as np


def simulate(arrive, sizes, tie_score, *, x, msr_slots: int,
             buffer_cap: int) -> list[dict]:
    """Run ``R`` independent slotted runs; return one result dict per run.

    Args:
      arrive: ``(R, T)`` bool, an arrival in slot t.
      sizes: ``(R, T)`` int, the size of the job arriving in slot t.
      tie_score: ``(R, T, K)`` float, per-slot scores; among the servers
        tied at the shortest approximated queue, the one with the highest
        score (the lowest index on an exact tie) is chosen.
      x: ``(R,)`` int, the ET threshold of each run.
      msr_slots: the emulated service time of every job, in slots.
      buffer_cap: FIFO capacity per server.

    Returns:
      Per run: ``jct`` (completion minus arrival slot plus one, for every
      completed job in arrival order), ``arrivals`` (admitted),
      ``departures``, ``messages``, ``dropped``, ``max_aq``,
      ``max_queue``, ``queue_gap_sup``, ``per_server_arrivals`` and
      ``final_q``.
    """
    arrive = np.asarray(arrive, bool)
    sizes = np.asarray(sizes, np.int64)
    r_n, t_n = arrive.shape
    k = tie_score.shape[2]
    b = buffer_cap
    x = np.asarray(x, np.int64).reshape(r_n, 1)
    rows = np.arange(r_n)

    q = np.zeros((r_n, k), np.int64)  # true queue lengths
    rem = np.zeros((r_n, k), np.int64)  # remaining work of the head job
    ring = np.full((r_n, k, b), -1, np.int64)  # FIFO of arrival slots
    head = np.zeros((r_n, k), np.int64)
    q_app = np.zeros((r_n, k), np.int64)  # the balancer's approximation
    e_rem = np.full((r_n, k), msr_slots, np.int64)  # emulated head work
    msgs = np.zeros(r_n, np.int64)
    arrs = np.zeros(r_n, np.int64)
    deps = np.zeros(r_n, np.int64)
    dropped = np.zeros(r_n, np.int64)
    per_srv = np.zeros((r_n, k), np.int64)
    max_aq = np.zeros(r_n, np.int64)
    max_q = np.zeros(r_n, np.int64)
    gap_sup = np.zeros(r_n, np.int64)
    done_at = np.full((r_n, t_n), -1, np.int64)  # completion slot per job

    for t in range(t_n):
        # 1. route on the pre-slot approximation, random ties.
        tied = q_app == q_app.min(axis=1, keepdims=True)
        j = np.argmax(np.where(tied, tie_score[:, t], -np.inf), axis=1)
        arr = arrive[:, t]
        q_j = q[rows, j]
        admit = arr & (q_j < b)
        dropped += arr & ~admit
        a = rows[admit]
        ja = j[admit]
        ring[a, ja, (head[a, ja] + q_j[admit]) % b] = t
        starts = q_j[admit] == 0
        rem[a[starts], ja[starts]] = sizes[a[starts], t]
        q[a, ja] += 1
        e_empty = q_app[a, ja] == 0
        e_rem[a[e_empty], ja[e_empty]] = msr_slots
        q_app[a, ja] += 1
        arrs += admit
        per_srv[a, ja] += 1

        # 2. service: one unit per busy server.
        busy = q > 0
        rem -= busy
        dep = busy & (rem <= 0)
        dr, dk = np.nonzero(dep)
        done_at[dr, ring[dr, dk, head[dr, dk] % b]] = t
        q[dr, dk] -= 1
        head[dr, dk] += 1
        nxt = q[dr, dk] > 0
        rem[dr[nxt], dk[nxt]] = sizes[
            dr[nxt], ring[dr[nxt], dk[nxt], head[dr[nxt], dk[nxt]] % b]
        ]
        deps += dep.sum(axis=1)

        # 3. MSR emulation: one slot of emulated work per busy queue.
        e_busy = q_app > 0
        e_rem -= e_busy
        e_dep = e_busy & (e_rem <= 0)
        q_app -= e_dep
        e_rem[e_dep] = msr_slots

        # 4. ET-x: a server whose error reaches x reports its length.
        fire = np.abs(q - q_app) >= x
        msgs += fire.sum(axis=1)
        q_app = np.where(fire, q, q_app)
        e_rem[fire] = msr_slots

        max_aq = np.maximum(max_aq, np.abs(q - q_app).max(axis=1))
        max_q = np.maximum(max_q, q.max(axis=1))
        gap_sup = np.maximum(gap_sup, q.max(axis=1) - q.min(axis=1))

    out = []
    for r in range(r_n):
        slots = np.flatnonzero(arrive[r])
        comp = done_at[r, slots]
        ok = comp >= 0
        out.append({
            "jct": comp[ok] - slots[ok] + 1,
            "arrivals": int(arrs[r]),
            "departures": int(deps[r]),
            "messages": int(msgs[r]),
            "dropped": int(dropped[r]),
            "max_aq": int(max_aq[r]),
            "max_queue": int(max_q[r]),
            "queue_gap_sup": int(gap_sup[r]),
            "per_server_arrivals": per_srv[r].copy(),
            "final_q": q[r].copy(),
        })
    return out
