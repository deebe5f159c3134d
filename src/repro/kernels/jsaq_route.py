"""Pallas TPU kernel family: fused CARE routing at mean-field scale.

Join-the-Shortest-Approximated-Queue routes each arriving job to the argmin
of the balancer's approximated queue vector and immediately increments that
entry (the balancer knows its own routing decisions -- Eq. 10 in the paper).
The per-job decision is inherently sequential, which is hostile to a SIMD
machine; the TPU adaptation is:

* one *independent balancer domain* per grid program -- parallel
  simulation replicas, per-device dispatchers, grid runs;
* keep the domain's whole per-server state resident in VMEM across the
  sequential inner loop, so the route/trigger/update chain never touches
  HBM between slots.

Three kernels share the layout and the segmented reduction:

* :func:`jsaq_route_pallas` -- the seed kernel: route ``num_jobs`` jobs by
  sequential JSAQ from a given state (consumed by ``kernels/ops.py`` and
  the kernel unit tests).
* :func:`care_route_pallas` -- the mean-field simulator kernel: the whole
  ``T``-slot CARE loop (route + admit + deterministic service + MSR
  emulation drain + RT/DT/ET/ET+RT/exact trigger + snap) fused into one
  kernel invocation, so a large-K cell never materialises per-slot
  (K,)-sized intermediates in HBM.  Decision-identical to the dense
  ``slotted_sim`` path under ``deterministic_ties`` (asserted by
  ``tests/test_route_backend.py``).
* :func:`serve_route_pallas` -- the serving engine's within-slot arrival
  lane loop (sequential routing over the slot's arrival batch with the
  occupancy/approximation state resident), replacing the dense
  ``lax.scan`` lane body of ``serve/engine.py``.

Slab layout
-----------

Every per-server vector (and every per-slot or per-lane output) of one
domain is folded row-major into a dense ``(rows, 128)`` slab
(:func:`to_slab`): up to one vreg of 8 sublanes, then whole 8-row groups,
so a vreg is fully used whatever the server count.  The kernels walk a
slab one aligned row group at a time (``ref[pl.ds(pl.multiple_of(b * 8,
8), 8), :]``), so the live working set is a few vregs independent of K.
A single entry -- the slot's arrival flag, the routed server of slot
``t`` -- is read or written through the aligned row group that holds it
with an iota mask; the compiler never sees a dynamic lane offset.

Segmented argmin (:func:`_seg_argmin`): a loop over row groups carries the
running per-position minimum and the flat index that achieved it (strict
``<`` keeps the *earliest* group on ties), then one combine takes the
minimum flat index among positions achieving the global minimum.  Ties
therefore resolve to the lowest server index, matching ``jnp.argmin`` and
the simulators' ``deterministic_ties`` mode exactly.

Pad safety: slab pad entries are either filled with ``int32`` max / never
read as live servers (``jsaq_route_pallas``) or masked by an in-kernel
``index < servers`` mask (the stateful kernels) -- a pad can never win the
argmin, never triggers a message, and never enters the max/min metrics.

VMEM: :func:`care_route_pallas` keeps 7 int32 slabs per domain resident
(~28 bytes/server) plus the double-buffered arrival and routed slabs.  A
size beyond :data:`VMEM_LIMIT_BYTES`, the default scoped VMEM of a TPU
TensorCore (16 MiB on v5e), raises :class:`VmemLimitError` -- K = 10^6
does -- rather than failing inside the compiler.  Under the interpreter
(CPU) the arrays live in host memory and the same check applies, so a
size is refused alike on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8

# Default scoped VMEM of one TensorCore (v5e); the care kernel's resident
# state must fit under it.
VMEM_LIMIT_BYTES = 16 * 2**20

_I32_MAX = jnp.iinfo(jnp.int32).max


class VmemLimitError(ValueError):
    """A kernel size whose resident state exceeds :data:`VMEM_LIMIT_BYTES`."""


def slab_rows(n: int) -> int:
    """Rows of the ``(rows, 128)`` slab holding ``n`` entries.

    One vreg of up to 8 sublanes, then whole 8-row groups so every row
    group a kernel walks is aligned.
    """
    rows = max(1, -(-n // LANES))
    if rows <= SUBLANES:
        return rows
    return -(-rows // SUBLANES) * SUBLANES


def to_slab(v: jax.Array, fill) -> jax.Array:
    """Fold the last axis ``(..., n)`` into ``(..., rows, 128)``, padded."""
    n = v.shape[-1]
    rows = slab_rows(n)
    pad = rows * LANES - n
    if pad:
        v = jnp.concatenate(
            [v, jnp.full(v.shape[:-1] + (pad,), fill, v.dtype)], axis=-1
        )
    return v.reshape(v.shape[:-1] + (rows, LANES))


def from_slab(s: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`to_slab`: ``(..., rows, 128)`` -> ``(..., n)``."""
    return s.reshape(s.shape[:-2] + (-1,))[..., :n]


def _groups(rows: int) -> tuple[int, int]:
    """(rows per group, number of groups) of a slab."""
    g = min(rows, SUBLANES)
    return g, rows // g


def _rows(b, g: int, nb: int):
    """Row index of group ``b``: static where it can be, else aligned."""
    if nb == 1:
        return slice(None)
    if isinstance(b, int):
        return slice(b * g, (b + 1) * g)
    return pl.ds(pl.multiple_of(b * g, g), g)


def _for_groups(nb: int, body, carry):
    """``fori_loop`` over row groups (straight-line for one group)."""
    if nb == 1:
        return body(0, carry)
    return jax.lax.fori_loop(0, nb, body, carry)


def _iota(g: int) -> jax.Array:
    """Flat slab index of each entry of one ``(g, 128)`` row group."""
    row = jax.lax.broadcasted_iota(jnp.int32, (g, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (g, LANES), 1)
    return row * LANES + lane


def _min11(x):
    return jnp.min(jnp.min(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _max11(x):
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _sum11(x):
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _cell(ref, i):
    """``(rows, mask)``: the aligned row group of ``ref`` holding flat
    index ``i``, and the mask selecting ``i`` within it."""
    g, nb = _groups(ref.shape[0])
    if nb == 1:
        return slice(None), _iota(g) == i
    span = g * LANES
    b = i // span
    return _rows(b, g, nb), _iota(g) == i - b * span


def _read(ref, i):
    """Entry ``i`` of a slab ref as a (1, 1) value."""
    rows, mask = _cell(ref, i)
    return _sum11(jnp.where(mask, ref[rows, :], 0))


def _write(ref, i, value):
    """Store the (1, 1) ``value`` at entry ``i`` of a slab ref."""
    rows, mask = _cell(ref, i)
    ref[rows, :] = jnp.where(mask, value, ref[rows, :])


def _param(vec, k: int):
    """Lane ``k`` of a (1, 128) parameter row as a (1, 1) value."""
    lane = jax.lax.broadcasted_iota(jnp.int32, vec.shape, 1)
    return _sum11(jnp.where(lane == k, vec, 0))


def _pack(values) -> jax.Array:
    """(1, 1) values into lanes 0.. of one (1, 128) row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out = jnp.zeros((1, LANES), jnp.int32)
    for k, v in enumerate(values):
        out = jnp.where(lane == k, v, out)
    return out


def _seg_argmin(nb: int, g: int, load):
    """Lowest-index argmin over a slab walked by row groups.

    ``load(b)`` returns ``(score, *vals)`` for row group ``b`` (pads
    already lifted to the score's max).  Returns ``(j, vals_at_j)``: the
    (1, 1) lowest flat index achieving the minimum score, and each of
    ``vals`` at that index as a (1, 1) value.
    """
    iota = _iota(g)

    def step(b, carry):
        vmin, imin, vals = carry
        score, *cur = load(b)
        better = score < vmin  # strict: ties keep the earliest group
        return (
            jnp.where(better, score, vmin),
            jnp.where(better, iota + b * (g * LANES), imin),
            tuple(jnp.where(better, c, v) for c, v in zip(cur, vals)),
        )

    score0, *vals0 = load(0)
    carry = (score0, iota, tuple(vals0))
    if nb > 1:
        carry = jax.lax.fori_loop(1, nb, step, carry)
    vmin, imin, vals = carry
    j = _min11(jnp.where(vmin == _min11(vmin), imin, _I32_MAX))
    at = imin == j
    return j, tuple(_sum11(jnp.where(at, v, jnp.zeros_like(v))) for v in vals)


def _squeezed(shape):
    """BlockSpec of one domain's slab (leading domain axis squeezed)."""
    return pl.BlockSpec((None,) + shape, lambda i: (i,) + (0,) * len(shape))


_PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


# ---------------------------------------------------------------------------
# Seed kernel: batched JSAQ dispatch from a given state.
# ---------------------------------------------------------------------------


def _jsaq_kernel(q_ref, idx_ref, qout_ref, *, num_jobs: int):
    """One domain: route ``num_jobs`` jobs sequentially.

    Pad entries carry ``int32`` max from the wrapper, so the segmented
    argmin can never route to them.
    """
    g, nb = _groups(q_ref.shape[0])
    iota = _iota(g)

    def copy(b, c):
        rows = _rows(b, g, nb)
        qout_ref[rows, :] = q_ref[rows, :]
        return c

    _for_groups(nb, copy, 0)

    def job(n, c):
        j, _ = _seg_argmin(nb, g, lambda b: (qout_ref[_rows(b, g, nb), :],))
        _write(idx_ref, n, j)

        def bump(b, c):
            rows = _rows(b, g, nb)
            hit = iota + b * (g * LANES) == j
            qout_ref[rows, :] = qout_ref[rows, :] + hit.astype(jnp.int32)
            return c

        return _for_groups(nb, bump, c)

    jax.lax.fori_loop(0, num_jobs, job, 0)


def jsaq_route_pallas(
    q_app: jax.Array, num_jobs: int, *, interpret: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Route ``num_jobs`` jobs per domain by sequential JSAQ.

    Args:
      q_app: (D, K) integer approximated queue lengths, one row per domain.
      num_jobs: number of jobs to dispatch per domain (static).
      interpret: run the Pallas interpreter (CPU validation).

    Returns:
      (idx, q_out): (D, num_jobs) int32 chosen servers (ties -> lowest
      index), and the post-dispatch state (D, K) in ``q_app``'s dtype.
    """
    d, k = q_app.shape
    q = to_slab(q_app.astype(jnp.int32), _I32_MAX)
    rows, jrows = q.shape[1], slab_rows(num_jobs)
    idx, q_out = pl.pallas_call(
        functools.partial(_jsaq_kernel, num_jobs=num_jobs),
        grid=(d,),
        in_specs=[_squeezed((rows, LANES))],
        out_specs=[_squeezed((jrows, LANES)), _squeezed((rows, LANES))],
        out_shape=[
            jax.ShapeDtypeStruct((d, jrows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((d, rows, LANES), jnp.int32),
        ],
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(q)
    return from_slab(idx, num_jobs), from_slab(q_out, k).astype(q_app.dtype)


# ---------------------------------------------------------------------------
# Mean-field simulator kernel: the whole CARE slot loop, fused.
# ---------------------------------------------------------------------------


def _care_kernel(
    par_ref,
    arrive_ref,
    routed_ref,
    q_ref,
    ps_ref,
    stats_ref,
    qa_ref,
    hr_ref,
    eh_ref,
    ds_ref,
    ss_ref,
    *,
    servers: int,
    slots: int,
    cap: int,
    policy: str,
    comm: str,
):
    """One domain: fused CARE trigger+route loop over all slots.

    Mirrors ``slotted_sim._sim_core`` operation for operation under its
    mean-field restrictions (deterministic service of ``msr_slots`` per
    job, MSR emulation, unit rates, deterministic lowest-index ties), so
    the two paths are bit-identical -- but with all per-server state in
    VMEM slabs (true queue and per-server arrivals in their output
    blocks, the rest in scratch) and no per-job FIFO ring, per-slot PRNG
    keys or one-hot HBM traffic.

    ``par_ref`` carries the domain's scenario scalars ``[x, rt_period,
    msr_slots, horizon]`` in lanes 0-3; ``servers`` masks the pad
    entries; ``slots``/``cap``/``policy``/``comm`` are trace-time.
    Each slot makes two passes over the row groups: the argmin on the
    pre-slot state, then the route update, service, MSR drain, trigger
    and metric reductions fused.
    """
    g, nb = _groups(q_ref.shape[0])
    iota = _iota(g)
    par = par_ref[...]
    x, rt_period, msr, horizon = (_param(par, k) for k in range(4))
    state = (q_ref, qa_ref, hr_ref, eh_ref, ds_ref, ss_ref, ps_ref)

    def init(b, c):
        rows = _rows(b, g, nb)
        zeros = jnp.zeros((g, LANES), jnp.int32)
        for ref in state:
            ref[rows, :] = zeros
        eh_ref[rows, :] = zeros + msr  # EmuState.init
        return c

    _for_groups(nb, init, 0)

    def slot(t, st):
        msgs, deps, arrs, drops, max_aq, max_q, gap = st
        act = t < horizon  # (1, 1); pad domains carry horizon 0
        arr = (_read(arrive_ref, t) > 0) & act

        # --- 1. arrival & routing (lowest-index ties) ----------------
        def load(b):
            rows = _rows(b, g, nb)
            q = q_ref[rows, :]
            score = qa_ref[rows, :] if policy == "jsaq" else q
            valid = iota + b * (g * LANES) < servers
            return jnp.where(valid, score, _I32_MAX), q

        j, (q_sel,) = _seg_argmin(nb, g, load)
        admit = arr & (q_sel < cap)
        drops = drops + (arr & ~admit).astype(jnp.int32)
        arrs = arrs + admit.astype(jnp.int32)
        _write(routed_ref, t, jnp.where(admit, j, -1))

        def update(b, acc):
            n_dep, n_sent, aq, qmax, qmin = acc
            rows = _rows(b, g, nb)
            gidx = iota + b * (g * LANES)
            valid = gidx < servers
            q, qa, hr, eh, ds, ss, ps = (ref[rows, :] for ref in state)

            sel = (gidx == j) & admit
            hr = jnp.where(sel & (q == 0), msr, hr)
            q = q + sel.astype(jnp.int32)
            was_empty = qa == 0
            qa = qa + sel.astype(jnp.int32)
            eh = jnp.where(sel & was_empty, msr, eh)
            ps = ps + sel.astype(jnp.int32)

            # --- 2. service (deterministic msr_slots-sized jobs) ------
            busy = (q > 0) & act
            hr = jnp.where(busy, hr - 1, hr)
            dep = busy & (hr <= 0)
            q = jnp.where(dep, q - 1, q)
            hr = jnp.where(dep & (q > 0), msr, hr)

            # --- 3. MSR emulation drain -------------------------------
            ticking = (qa > 0) & act
            eh = jnp.where(ticking, eh - 1, eh)
            dep_e = ticking & (eh <= 0)
            qa = jnp.where(dep_e, qa - 1, qa)
            eh = jnp.where(dep_e, msr, eh)

            # --- 4/5. trigger (comm.evaluate semantics, fused) --------
            diff = q - qa
            err = jnp.maximum(diff, -diff)
            dsa = ds + dep.astype(jnp.int32)
            ssa = ss + 1
            if comm == "rt":
                trig = ssa >= rt_period
            elif comm == "dt":
                trig = dsa >= x
            elif comm == "et":
                trig = err >= x
            elif comm == "et_rt":
                trig = (err >= x) | (ssa >= rt_period)
            elif comm == "exact":
                trig = dep
            elif comm == "none":
                trig = jnp.zeros_like(dep)
            else:
                raise ValueError(f"unknown communication kind: {comm}")
            trig = trig & act & valid
            ds = jnp.where(act, jnp.where(trig, 0, dsa), ds)
            ss = jnp.where(act, jnp.where(trig, 0, ssa), ss)
            qa = jnp.where(trig, q, qa)
            eh = jnp.where(trig, msr, eh)
            for ref, v in zip(state, (q, qa, hr, eh, ds, ss, ps)):
                ref[rows, :] = v

            # --- 6. metric partials (pads masked out of the extrema) --
            diff = q - qa
            return (
                n_dep + dep.astype(jnp.int32),
                n_sent + (dep if comm == "exact" else trig).astype(jnp.int32),
                jnp.maximum(aq, jnp.maximum(diff, -diff)),
                jnp.maximum(qmax, jnp.where(valid, q, 0)),
                jnp.minimum(qmin, jnp.where(valid, q, _I32_MAX)),
            )

        zeros = jnp.zeros((g, LANES), jnp.int32)
        n_dep, n_sent, aq, qmax, qmin = _for_groups(
            nb, update, (zeros, zeros, zeros, zeros, zeros + _I32_MAX)
        )
        qmax, qmin = _max11(qmax), _min11(qmin)
        return (
            msgs + jnp.where(act, _sum11(n_sent), 0),
            deps + _sum11(n_dep),
            arrs,
            drops,
            jnp.maximum(max_aq, _max11(aq)),
            jnp.maximum(max_q, qmax),
            jnp.maximum(gap, qmax - qmin),
        )

    zero = jnp.zeros((1, 1), jnp.int32)
    stats = jax.lax.fori_loop(0, slots, slot, (zero,) * 7)
    stats_ref[...] = _pack(stats)


def care_vmem_bytes(servers: int, slots: int) -> int:
    """VMEM one program of :func:`care_route_pallas` holds resident.

    Seven int32 server slabs (two output blocks double-buffered by the
    pipeline, five scratch), the double-buffered arrival and routed slot
    slabs, and the parameter/stat rows.
    """
    server_slab = slab_rows(servers) * LANES * 4
    slot_slab = slab_rows(slots) * LANES * 4
    row = SUBLANES * LANES * 4  # a (1, 128) block pads to one vreg
    return (2 * 2 + 5) * server_slab + 2 * 2 * slot_slab + 2 * 2 * row


def care_route_pallas(
    arrive: jax.Array,
    params: jax.Array,
    *,
    servers: int,
    cap: int,
    policy: str,
    comm: str,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused CARE trigger+route simulation, one domain per row.

    Args:
      arrive: (D, T) int32 per-slot arrival indicators, pre-masked by each
        domain's horizon (``slotted_sim._prep`` output).
      params: (D, 4) int32 per-domain scalars ``[x, rt_period, msr_slots,
        horizon]``.
      servers: K, the live server count (static); the server slab pads
        internally and pad entries are masked everywhere.
      cap: per-server FIFO capacity (arrivals beyond it drop), static.
      policy: "jsq" | "jsaq" (which state vector the argmin consumes).
      comm: trigger kind ("rt" | "dt" | "et" | "et_rt" | "exact" | "none").
      interpret: run the Pallas interpreter (CPU).

    Returns:
      ``(routed, q_true, per_srv, stats)``: (D, T) int32 routed server per
      slot (-1 when no admitted arrival), final (D, K) queue lengths,
      (D, K) per-server admitted arrivals, and (D, 8) int32 stats
      ``[msgs, deps, arrs, dropped, max_aq, max_q, gap_sup, 0]``.

    Raises:
      VmemLimitError: the domain's resident state exceeds
        :data:`VMEM_LIMIT_BYTES`.
    """
    if policy not in ("jsq", "jsaq"):
        raise ValueError(
            f"care_route_pallas supports policies 'jsq'/'jsaq', got {policy!r}"
        )
    d, t = arrive.shape
    need = care_vmem_bytes(servers, t)
    if need > VMEM_LIMIT_BYTES:
        raise VmemLimitError(
            f"care_route_pallas at K={servers}, T={t} needs {need / 2**20:.1f}"
            f" MiB of VMEM per domain, above the {VMEM_LIMIT_BYTES // 2**20}"
            f" MiB scoped VMEM limit (VMEM_LIMIT_BYTES)"
        )
    rows, trows = slab_rows(servers), slab_rows(t)
    kernel = functools.partial(
        _care_kernel, servers=servers, slots=t, cap=cap, policy=policy,
        comm=comm,
    )
    srv = _squeezed((rows, LANES))
    routed, q_true, per_srv, stats = pl.pallas_call(
        kernel,
        grid=(d,),
        in_specs=[_squeezed((1, LANES)), _squeezed((trows, LANES))],
        out_specs=[_squeezed((trows, LANES)), srv, srv, _squeezed((1, LANES))],
        out_shape=[
            jax.ShapeDtypeStruct((d, trows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((d, rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((d, rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((d, 1, LANES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, LANES), jnp.int32)] * 5,
        compiler_params=_PARALLEL,
        interpret=interpret,
    )(
        to_slab(params.astype(jnp.int32), 0),
        to_slab(arrive.astype(jnp.int32), 0),
    )
    return (
        from_slab(routed, t),
        from_slab(q_true, servers),
        from_slab(per_srv, servers),
        stats[:, 0, :8],
    )


# ---------------------------------------------------------------------------
# Serving engine kernel: within-slot sequential arrival-lane routing.
# ---------------------------------------------------------------------------


def _serve_kernel(
    par_ref,
    qlen_ref,
    qhead_ref,
    busy_ref,
    approx_ref,
    jv_ref,
    tail_ref,
    admit_ref,
    qlen_out_ref,
    approx_out_ref,
    drops_ref,
    *,
    replicas: int,
    lanes: int,
    cap: int,
    comm: str,
):
    """One slot's arrival lanes routed sequentially, state resident.

    Mirrors the dense lane scan of ``serve/engine._serve_core`` under
    deterministic (lowest-index) ties: each admitted arrival immediately
    bumps the occupancy/approximation the next lane sees.  The f32
    approximation update is the identical IEEE ``+1.0f``, so the two
    backends stay bit-identical.
    """
    g, nb = _groups(qlen_ref.shape[0])
    iota = _iota(g)
    par = par_ref[...]
    n_arr = _param(par, 0)
    act = _param(par, 1) > 0

    def copy(b, c):
        rows = _rows(b, g, nb)
        qlen_out_ref[rows, :] = qlen_ref[rows, :]
        approx_out_ref[rows, :] = approx_ref[rows, :]
        return c

    _for_groups(nb, copy, 0)

    def load(b):
        rows = _rows(b, g, nb)
        qlen = qlen_out_ref[rows, :]
        if comm == "exact":
            score = (qlen + busy_ref[rows, :]).astype(jnp.float32)
        else:
            score = approx_out_ref[rows, :]
        valid = iota + b * (g * LANES) < replicas
        return jnp.where(valid, score, jnp.inf), qlen, qhead_ref[rows, :]

    def lane(a, drops):
        live = act & (a < n_arr)
        j, (len_j, head_j) = _seg_argmin(nb, g, load)
        admit = live & (len_j < cap)
        # (head + len) % cap, with head < cap and len <= cap.
        tail = head_j + len_j
        tail = jnp.where(tail >= cap, tail - cap, tail)

        def bump(b, c):
            rows = _rows(b, g, nb)
            sel = (iota + b * (g * LANES) == j) & admit
            qlen_out_ref[rows, :] = (
                qlen_out_ref[rows, :] + sel.astype(jnp.int32)
            )
            approx_out_ref[rows, :] = (
                approx_out_ref[rows, :] + sel.astype(jnp.float32)
            )
            return c

        _for_groups(nb, bump, 0)
        _write(jv_ref, a, j)
        _write(tail_ref, a, tail)
        _write(admit_ref, a, admit.astype(jnp.int32))
        return drops + (live & ~admit).astype(jnp.int32)

    drops = jax.lax.fori_loop(0, lanes, lane, jnp.zeros((1, 1), jnp.int32))
    drops_ref[...] = _pack((drops,))


def serve_route_pallas(
    tie_u: jax.Array,
    q_len: jax.Array,
    q_head: jax.Array,
    busy_cnt: jax.Array,
    approx: jax.Array,
    n_arr: jax.Array,
    act: jax.Array,
    *,
    cap: int,
    comm: str,
    interpret: bool = False,
):
    """Route one slot's arrival lanes sequentially (JSAQ, lowest-index ties).

    Args:
      tie_u: (A,) f32 lane uniforms (unused under deterministic ties; pins
        the lane count).
      q_len / q_head: (R,) int32 pending-ring lengths and head indices.
      busy_cnt: (R,) int32 busy decode-slot counts (the "exact" score term).
      approx: (R,) f32 emulated occupancy.
      n_arr: () int32 live arrival count this slot.
      act: () bool horizon mask.
      cap: pending-ring capacity (static).
      comm: the comm kind; "exact" scores on true occupancy.
      interpret: run the Pallas interpreter (CPU).

    Returns:
      ``(jv, tailv, admitv, q_len', approx', dropped)``: per-lane routed
      replica / ring tail / admit flag (shapes (A,)), the post-slot ring
      lengths and approximation (shapes (R,)), and the () int32 count of
      dropped lanes.
    """
    a_n = tie_u.shape[0]
    r = q_len.shape[0]
    rows, arows = slab_rows(r), slab_rows(a_n)
    par = jnp.stack([n_arr.astype(jnp.int32), act.astype(jnp.int32)])
    par = to_slab(par, 0)
    kernel = functools.partial(
        _serve_kernel, replicas=r, lanes=a_n, cap=cap, comm=comm
    )
    lane_out = jax.ShapeDtypeStruct((arows, LANES), jnp.int32)
    jv, tailv, admitv, qlen_o, approx_o, drops = pl.pallas_call(
        kernel,
        out_shape=[
            lane_out,
            lane_out,
            lane_out,
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(
        par,
        to_slab(q_len, 0),
        to_slab(q_head, 0),
        to_slab(busy_cnt, 0),
        to_slab(approx, 0.0),
    )
    return (
        from_slab(jv, a_n),
        from_slab(tailv, a_n),
        from_slab(admitv, a_n).astype(bool),
        from_slab(qlen_o, r),
        from_slab(approx_o, r),
        drops[0, 0],
    )
