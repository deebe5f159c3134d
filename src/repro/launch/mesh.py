"""Production mesh construction.

Pure functions only -- importing this module never touches jax device
state; ``make_production_mesh`` is called by the dry-run (under 512 fake
host devices) and by the real launcher (under actual TPU topology).
"""
from __future__ import annotations

import jax

from repro.models.parallel import ParallelContext, choose_ep_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_context(mesh, num_experts: int = 0) -> ParallelContext:
    """ParallelContext for a production mesh (handles the pod axis)."""
    axes = mesh.axis_names
    dp_axes = tuple(a for a in axes if a in ("pod", "data"))
    tp_axis = "model"
    if num_experts:
        ep_axes, fsdp = choose_ep_axes(mesh, num_experts, dp_axes, tp_axis)
    else:
        ep_axes, fsdp = (tp_axis,), None
    return ParallelContext(
        mesh=mesh, dp_axes=dp_axes, tp_axis=tp_axis, ep_axes=ep_axes,
        fsdp_axis=fsdp,
    )


def make_debug_mesh(devices=None, shape=(2, 4)):
    """Small mesh for multi-device CPU tests (subprocess with fake devices)."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    n = int(np.prod(shape))
    arr = np.array(devices[:n]).reshape(shape)
    return jax.sharding.Mesh(arr, ("data", "model"))
