"""Ahead-of-time compiles for a described TPU v5e: what the chip would refuse.

Interpret mode runs the Pallas kernels on the CPU with none of Mosaic's
rules (aligned dynamic offsets, the (8, 128) block tiling, scoped VMEM),
so a kernel can pass every parity test and still be refused by the TPU
compiler.  Here the TPU compiler, which is installed, compiles for a chip
that is described and not attached: the route kernels at real widths,
the entry programs of ``chip_smoke.py`` at its sizes, and the Pallas
backends inside those entry programs.  Nothing runs.

The topology is described inside a module fixture (never at import, in a
``skipif`` or in ``parametrize``): only one process may load the TPU
library, so only the worker that runs this file does.
"""
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.care import slotted_sim
from repro.kernels import jsaq_route, ops
from repro.serve import engine

KERNEL = "tpu_custom_call"
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Trace the entry programs with the kernels compiled, as on a TPU."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile(fn, args, sharding):
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args,
    )
    return fn.lower(*shapes).compile()


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def _smoke():
    import importlib.util

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --- the route kernels at real widths ---------------------------------------


@pytest.mark.parametrize(
    "servers,domains,slots", [(30, 3, 2_000), (100_000, 2, 4_000)]
)
def test_care_route_compiles(one_chip, servers, domains, slots):
    fn = jax.jit(lambda a, p: jsaq_route.care_route_pallas(
        a, p, servers=servers, cap=16, policy="jsaq", comm="dt",
    ))
    args = (jnp.zeros((domains, slots), jnp.int32),
            jnp.zeros((domains, 4), jnp.int32))
    assert KERNEL in _compile(fn, args, one_chip).as_text()


@pytest.mark.parametrize("replicas,lanes", [(100, 24), (1024, 288)])
@pytest.mark.parametrize("comm", ["et", "exact"])
def test_serve_route_compiles(one_chip, replicas, lanes, comm):
    fn = jax.jit(lambda *a: jsaq_route.serve_route_pallas(
        *a, cap=128, comm=comm,
    ))
    args = (
        jnp.zeros((lanes,), jnp.float32),
        jnp.zeros((replicas,), jnp.int32),
        jnp.zeros((replicas,), jnp.int32),
        jnp.zeros((replicas,), jnp.int32),
        jnp.zeros((replicas,), jnp.float32),
        jnp.zeros((), jnp.int32),
        jnp.zeros((), jnp.bool_),
    )
    assert KERNEL in _compile(fn, args, one_chip).as_text()


def test_jsaq_route_compiles(one_chip):
    fn = jax.jit(lambda q: jsaq_route.jsaq_route_pallas(q, 9))
    args = (jnp.zeros((13, 300), jnp.int32),)
    assert KERNEL in _compile(fn, args, one_chip).as_text()


def test_care_route_refuses_more_than_scoped_vmem():
    arrive = jnp.zeros((1, 100), jnp.int32)
    params = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(jsaq_route.VmemLimitError, match="VMEM_LIMIT_BYTES"):
        jsaq_route.care_route_pallas(
            arrive, params, servers=1_000_000, cap=16, policy="jsaq",
            comm="dt",
        )


# --- chip_smoke.py's entry programs at its sizes -----------------------------


def test_paper_grid_program_fits_one_chip(one_chip):
    cs = _smoke()
    size = cs.paper_grid.__kwdefaults__
    cells = cs._paper_cells(size["slots"], size["loads"], size["xs"])
    fn, args, (c, s) = slotted_sim.grid_program(
        list(range(size["seeds"])), cells[0].static_part(),
        [x.scenario() for x in cells],
    )
    assert (c * s, size["slots"]) == (96, 100_000)
    assert _bytes(_compile(fn, args, one_chip)) < HBM_BYTES


def test_serving_grid_program_fits_one_chip(one_chip):
    cs = _smoke()
    size = cs.serving_grid.__kwdefaults__
    cell = cs._serve_cell(size["replicas"], size["decode_slots"],
                          size["slots"], **cs.LADDER["et4"])
    fn, args, _, static = engine.serve_grid_program(
        list(range(size["seeds"])), cell.static_part(), [cell]
    )
    assert (static.replicas, static.decode_slots) == (1024, 16)
    assert static.max_arrivals >= 288
    compiled = _compile(fn, args, one_chip)
    assert KERNEL not in compiled.as_text()
    assert _bytes(compiled) < HBM_BYTES


# --- the Pallas backends inside the entry programs ---------------------------


def test_slotted_pallas_program_holds_the_kernel(one_chip, compiled_kernels):
    cs = _smoke()
    cell = cs._mean_field_cell(100_000, 4_000, "pallas")
    fn, args, _ = slotted_sim.grid_program(
        [7], cell.static_part(), [cell.scenario()]
    )
    assert KERNEL in _compile(fn, args, one_chip).as_text()


def test_serving_pallas_program_holds_the_kernel(one_chip, compiled_kernels):
    cs = _smoke()
    cell = cs._serve_cell(1024, 16, 2048, comm="et", x=4,
                          deterministic_ties=True, route_backend="pallas")
    fn, args, _, _ = engine.serve_grid_program(
        [0], cell.static_part(), [cell]
    )
    assert KERNEL in _compile(fn, args, one_chip).as_text()
