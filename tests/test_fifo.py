"""The slot step's two FIFO carriages (``repro.core.care.fifo``), driven
directly through the same scripted admissions.

Each case lists jobs as (arrival slot, server, size) and the slot in which
each departs, worked by hand from Lindley's recursion: a job of size ``S``
admitted in slot ``t`` to a server whose previous job departs in slot
``D`` departs in slot ``max(t, D + 1) + S - 1``, or never (-1) when that
is at or past the horizon or the scan, or when the arrival was dropped.
The departure calendar and the job ring must both give those slots.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.care import fifo

K = 40  # two 32-bit words of departure bits per slot
N = fifo.NEAR_WINDOW

# name: (slots, horizon, buffer_cap, jobs, departure slot of each job)
CASES = {
    "idle": (16, 16, 8, [(2, 0, 3)], [4]),
    # The second job waits for the first: max(1, 4 + 1) + 2 - 1.
    "back_to_back": (16, 16, 8, [(0, 1, 5), (1, 1, 2), (9, 1, 1)],
                     [4, 6, 9]),
    # Servers 0 and 5 share a word of departure bits, 35 is in the next.
    "same_word": (16, 16, 8, [(0, 0, 4), (1, 5, 3), (2, 35, 2)], [3, 3, 3]),
    # Both departures fall in window slot 19, one round apart.
    "window_wrap": (N + 64, N + 64, 8, [(10, 0, 10), (N - 10, 1, 30)],
                    [19, N + 19]),
    "past_window": (N + 64, N + 64, 8, [(3, 2, N + 5), (4, 3, 2)],
                    [N + 7, 5]),
    # Server 0 is full (one job) when the second job arrives.
    "dropped": (16, 16, 1, [(0, 0, 10), (2, 0, 3), (3, 1, 2)], [9, -1, 4]),
    # Horizon 8: a job due in slot 9, the one queued behind it, and one
    # due past the scan itself complete nowhere.
    "horizon": (20, 8, 8, [(1, 0, 3), (5, 1, 5), (6, 1, 1), (7, 2, 30)],
                [3, -1, -1, -1]),
}


def _drive(carriage, slots, horizon, cap, jobs):
    """Run ``carriage`` through the scripted arrivals as the slot step
    does (drop on a full FIFO, one unit of work per busy server per slot,
    nothing past the horizon); returns the completion slot of every job
    id, the departures in each slot and the final carriage."""
    arr = np.zeros(slots, bool)
    server = np.zeros(slots, np.int32)
    size = np.ones(slots, np.int32)
    for t, s, n in jobs:
        arr[t], server[t], size[t] = True, s, n
    sizes = jnp.asarray(size)

    def step(c, xs):
        ring, q = c
        slot, a, srv, n = xs
        onehot = jnp.arange(K, dtype=jnp.int32) == srv
        q_sel = jnp.sum(jnp.where(onehot, q, 0))
        admit = a & (q_sel < cap)
        sel = onehot & admit
        ring, adm_out = ring.admit(slot, srv, onehot, admit, sel, q, q_sel,
                                   n, jnp.int32(horizon))
        q = q + sel.astype(jnp.int32)
        ring, dep, dep_out = ring.depart(slot, q, slot < horizon,
                                         jnp.ones((K,), jnp.int32), sizes)
        q = jnp.where(dep, q - 1, q)
        return (ring, q), ((adm_out, dep_out), dep)

    @jax.jit
    def run(arr, server, sizes):
        init = (carriage.init(K, slots, cap), jnp.zeros((K,), jnp.int32))
        xs = (jnp.arange(slots, dtype=jnp.int32), arr, server, sizes)
        (final, _), (ys, deps) = jax.lax.scan(step, init, xs)
        return carriage.completions(ys), deps, final

    return run(jnp.asarray(arr), jnp.asarray(server), sizes)


@pytest.mark.parametrize("carriage", [fifo.Calendar, fifo.JobRing],
                         ids=["calendar", "ring"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_departure_slots(carriage, case):
    slots, horizon, cap, jobs, want = CASES[case]
    comp, deps, final = _drive(carriage, slots, horizon, cap, jobs)
    comp, deps = np.asarray(comp), np.asarray(deps)
    assert [int(comp[t]) for t, _, _ in jobs] == want
    # Every departure is a job's, in its server and slot: nothing is
    # written for a dropped arrival or a job cut by the horizon.
    got = {(int(t), int(s)) for t, s in zip(*np.nonzero(deps))}
    assert got == {(d, s) for (_, s, _), d in zip(jobs, want) if d >= 0}
    far = final.far_departures()
    if carriage is fifo.JobRing:
        assert far is None
    else:
        assert int(far) == (1 if case == "past_window" else 0)
