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
import re

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


@pytest.fixture(scope="module")
def paper_grid(one_chip):
    """The paper grid's program as compiled for one chip, and its runs
    and slots."""
    cs = _smoke()
    size = cs.paper_grid.__kwdefaults__
    cells = cs._paper_cells(size["slots"], size["loads"], size["xs"])
    fn, args, (c, s) = slotted_sim.grid_program(
        list(range(size["seeds"])), cells[0].static_part(),
        [x.scenario() for x in cells],
    )
    return _compile(fn, args, one_chip), c * s, size["slots"]


def test_paper_grid_program_fits_one_chip(paper_grid):
    compiled, runs, slots = paper_grid
    assert (runs, slots) == (96, 100_000)
    assert _bytes(compiled) < HBM_BYTES


# The slot step's phase scopes (``slotted_sim._sim_core``), in order, and
# what the scan itself puts in its body: the slicing of ``xs``, the writing
# of ``ys``, the loop counter, and broadcasts hoisted to the body's call.
PHASES = ("faults", "route", "service", "drain", "trigger", "metrics")
SCAN_PLUMBING = {"dynamic_slice", "squeeze", "broadcast_in_dim",
                 "dynamic_update_slice", "add", "closed_call"}
_INSTR = re.compile(r"(?:ROOT )?(%[\w.\-]+) = .*? ([a-z][\w\-]*)\(")


def _computations(text: str):
    """``{computation: [(opcode, op_name or None, line)]}`` of an HLO
    module's text, in schedule order, and the entry's name."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        head = re.match(r"(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps[head.group(2)] = []
            entry = head.group(2) if head.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INSTR.match(line.strip())):
            op = re.search(r'op_name="([^"]*)"', line)
            cur.append((m.group(2), op and op.group(1), line))
    return comps, entry


def _with_fusions(comps, instrs):
    """``instrs`` and those of the fused computations they call."""
    for inst in instrs:
        yield inst
        for callee in re.findall(r"calls=%([\w.\-]+)", inst[2]):
            yield from _with_fusions(comps, comps[callee])


def _scope(op_name, names):
    """The first of ``names`` that is a component of ``op_name`` (under
    ``vmap`` a scope reads ``vmap(<name>)``)."""
    for part in (op_name or "").split("/"):
        for name in names:
            if part in (name, f"vmap({name})"):
                return name
    return None


def test_paper_grid_phases_own_the_scan_body(paper_grid):
    comps, entry = _computations(paper_grid[0].as_text())
    at = next(i for i, inst in enumerate(comps[entry]) if inst[0] == "while")
    body = re.search(r"body=%([\w.\-]+)", comps[entry][at][2]).group(1)
    owned = {p: 0 for p in PHASES}
    for opcode, op_name, line in _with_fusions(comps, comps[body]):
        if op_name is None or opcode == "constant":
            # Made by the compiler, or a constant, which keeps the name of
            # whichever of its equal copies the compiler kept.
            continue
        phase = _scope(op_name, PHASES)
        if phase is None:
            assert op_name.rsplit("/while/body/", 1)[-1] in SCAN_PLUMBING, line
        else:
            owned[phase] += 1
    # The paper's cells run no fault model (fault "none").
    assert owned.pop("faults") == 0
    assert all(n > 0 for n in owned.values()), owned
    before = list(_with_fusions(comps, comps[entry][:at]))
    after = list(_with_fusions(comps, comps[entry][at + 1:]))
    assert any(_scope(op, ["draw"]) for _, op, _ in before)
    # Unit rates and no fault model: departures come from the calendar,
    # read at the loop counter, so the loop gathers nothing from the job
    # ring or the sizes (a gather of the calendar's word would hold one
    # index per run), and completion slots are the loop's own output, so
    # no scatter or sort follows it.
    runs = paper_grid[1]
    for opcode, _, line in _with_fusions(comps, comps[body]):
        if opcode == "gather":
            assert re.search(rf"= u32\[{runs}(,1)?\]", line), line
    assert not any(opcode in ("scatter", "sort") for opcode, _, _ in after)


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
