"""The trace reduction, on a trace this test records on the CPU."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileOptions, TraceAnnotation

from chipbench import trace


def test_union_merges_overlapping_and_nested_intervals():
    s = np.array([5.0, 0.0, 1.0, 10.0, 12.0])
    e = np.array([6.0, 4.0, 2.0, 15.0, 13.0])
    us, ue = trace.union(s, e)
    assert us.tolist() == [0.0, 5.0, 10.0]
    assert ue.tolist() == [4.0, 6.0, 15.0]


def test_self_times_subtract_nested_events():
    # A loop [0, 10) holding two body ops, and a separate op [12, 13).
    t = trace.self_times([0.0, 1.0, 4.0, 12.0], [10.0, 3.0, 8.0, 13.0],
                         ["loop", "body", "body", "other"])
    assert t == {"loop": 4.0, "body": 6.0, "other": 1.0}


@pytest.mark.parametrize("name,loop", [
    ("%while.108 = (s32[], f32[96,30]{1,0}) while((s32[], f32[96,30]{1,0}) "
     "%tuple.3), condition=%cond, body=%body", True),
    ("%fusion.171 = s32[2880]{0} fusion(s32[96,30]{0,1} %fusion.165), "
     "kind=kCustom, calls=%while_body_fusion", False),
    ("while.5", True),
    ("end: while.5", False),
    ("fusion.2", False),
])
def test_is_loop_reads_the_operation_kind(name, loop):
    assert trace.is_loop(name) is loop


def test_op_label_keeps_the_instruction_name_and_kind():
    text = ("%fusion.171 = s32[2880]{0:T(1024)S(1)} fusion(s32[96,30]"
            "{0,1:T(8,128)} %fusion.165), kind=kCustom")
    assert trace.op_label(text) == "%fusion.171 fusion"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jax.lax.fori_loop(
        0, 200, lambda i, c: jnp.sin(c) * 1.0001 + 1.0, x))
    x = jnp.ones((4096,))
    f(x).block_until_ready()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    t0 = time.perf_counter()
    with TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
        with TraceAnnotation("test.host_sleep"):
            time.sleep(0.05)
        f(x).block_until_ready()
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    return trace.find_xplane(str(log_dir)), wall


def test_reduce_reads_busy_ops_and_labelled_gaps(recorded):
    path, wall = recorded
    # On the CPU the "device" is XLA's CPU client thread on the host plane.
    red = trace.reduce(path, device_plane=trace.HOST_PLANE,
                       busy_line=r"XLAPjRtCpuClient",
                       ops_line=r"XLAPjRtCpuClient")
    assert 0.05 < red["window_s"] <= wall
    assert len(red["devices"]) == 1
    assert 0 < red["busy_s"] < red["window_s"] - 0.04
    assert 0 < red["ops_covered_s"] <= red["window_s"]
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert all(sec > 0 for _, sec in red["device_ops"])
    assert red["ops_complete"]
    # The four calls are each one fori_loop; the sleep is outside them.
    assert 0 < red["loop_s"] <= red["busy_s"]
    labels = dict(red["idle_gaps"])
    assert labels.get("test.host_sleep", 0) > 0.04
    idle = sum(labels.values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)


def test_reduce_needs_the_window_span(recorded, tmp_path):
    path, _ = recorded
    with pytest.raises(ValueError, match="no host span"):
        trace.reduce(path, window="no.such.span")


def _step_us(red, steps=1000):
    from chipbench.run import BENCH, load_module

    reader = load_module(BENCH / "metrics" / "step_us.grid.py")
    return reader.read({"trace": red, "layers": {"steps": steps}})


def test_step_us_is_loop_time_per_step_and_silent_on_a_cut_trace():
    red = {"devices": ["/device:TPU:0"], "busy_s": 2.0, "loop_s": 1.5,
           "ops_complete": True}
    assert _step_us(red) == pytest.approx(1500.0)
    assert _step_us(dict(red, ops_complete=False)) is None
    assert _step_us(dict(red, loop_s=0.0)) is None
