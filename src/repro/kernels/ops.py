"""Jit'd public wrappers around the Pallas kernels.

On TPU the kernels run compiled; everywhere else (this CPU container, unit
tests) they run under the Pallas interpreter, which executes the kernel body
in Python with the same block semantics.  Callers can force either mode.

Wrappers also handle padding to tile multiples so call sites stay clean.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attn as _flash
from repro.kernels import jsaq_route as _jsaq
from repro.kernels import moe_route as _moe
from repro.kernels import ref as _ref


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("num_jobs", "interpret", "use_pallas"))
def jsaq_route(
    q_app: jax.Array,
    num_jobs: int,
    *,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Batched JSAQ dispatch (see kernels/jsaq_route.py).

    (D, K) -> ((D, N) idx, (D, K) q').  The kernel runs one domain per
    program and pads each domain's server slab with ``int32`` max, so the
    argmin can never route to a pad.
    """
    if not use_pallas:
        return _ref.jsaq_route_ref(q_app, num_jobs)
    interpret = _default_interpret() if interpret is None else interpret
    return _jsaq.jsaq_route_pallas(q_app, num_jobs, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("servers", "cap", "policy", "comm", "interpret"),
)
def care_route(
    arrive: jax.Array,
    params: jax.Array,
    *,
    servers: int,
    cap: int,
    policy: str,
    comm: str,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused mean-field CARE simulation (see kernels/jsaq_route.py).

    (D, T) arrivals + (D, 4) per-domain scalars -> (routed, q_true,
    per_srv, stats); the pallas ``route_backend`` of
    ``slotted_sim.simulate_grid`` and the direct entry point for the
    large-K invariants tests and ``benchmarks/bench_route.py``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return _jsaq.care_route_pallas(
        arrive,
        params,
        servers=servers,
        cap=cap,
        policy=policy,
        comm=comm,
        interpret=interpret,
    )


def serve_route(
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
    interpret: bool | None = None,
):
    """One serving slot's fused arrival-lane routing (jsaq_route.py).

    Not jitted here: it is called from inside the serving engine's traced
    scan body (``serve/engine._serve_core``), which owns the jit.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return _jsaq.serve_route_pallas(
        tie_u,
        q_len,
        q_head,
        busy_cnt,
        approx,
        n_arr,
        act,
        cap=cap,
        comm=comm,
        interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("top_k", "gate_fn", "interpret", "use_pallas")
)
def moe_route(
    logits: jax.Array,
    bias: jax.Array,
    top_k: int,
    *,
    gate_fn: str = "softmax",
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused CARE-biased top-k routing (see kernels/moe_route.py)."""
    if not use_pallas:
        return _ref.moe_route_ref(logits, bias, top_k, gate_fn)
    interpret = _default_interpret() if interpret is None else interpret
    t, e = logits.shape
    tile = _moe.TOKEN_TILE
    pad = (-t) % tile
    if pad:
        logits = jnp.concatenate(
            [logits, jnp.full((pad, e), -1e30, logits.dtype)], axis=0
        )
    idx, w, counts = _moe.moe_route_pallas(
        logits, bias, top_k, gate_fn=gate_fn, interpret=interpret
    )
    if pad:
        # Remove phantom-token contributions from the counts.
        pad_idx = idx[t:]
        phantom = jnp.zeros_like(counts).at[pad_idx.reshape(-1)].add(1)
        counts = counts - phantom
    return idx[:t], w[:t], counts


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "softcap", "interpret",
                     "use_pallas"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float,
    causal: bool = True,
    window: int | None = None,
    softcap: float = 0.0,
    interpret: bool | None = None,
    use_pallas: bool = True,
) -> jax.Array:
    """Flash SDPA (see kernels/flash_attn.py).

    q: (B, S, H, dh); k, v: (B, T, KVH, dh/dv).  GQA is handled by
    broadcasting the KV heads here (the VMEM tiles inside the kernel are
    per-head either way).  Returns (B, S, H, dv).
    """
    if not use_pallas:
        return _ref.flash_attention_ref(
            q, k, v, scale=scale, causal=causal, window=window,
            softcap=softcap,
        )
    interpret = _default_interpret() if interpret is None else interpret
    b, s, h, dh = q.shape
    t, kvh = k.shape[1], k.shape[2]
    dv = v.shape[3]
    g = h // kvh
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, t, dh)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, t, dv)
    out = _flash.flash_attention_pallas(
        qf, kf, vf, scale=scale, causal=causal, window=window,
        softcap=softcap, interpret=interpret,
    )
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
