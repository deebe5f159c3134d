"""Faults planted under the timed path make ``correct`` come out false.

Each test breaks the program (never the benchmark) for one run of a cell
cut to CPU size: a call that hands back stale state, half of the batch
left out, and an answer altered where it is produced.  The exchange
between chips left out is planted in ``test_chipbench_four.py``.
"""
import pytest

from chipbench.tests import tiny
from repro.core.care import slotted_sim


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("checkout"))


def _grid_stale(monkeypatch):
    """Every call after the first returns the first call's results."""
    real, first = slotted_sim.simulate_grid, []

    def stale(*args, **kw):
        if not first:
            first.append(real(*args, **kw))
        return first[0]

    monkeypatch.setattr(slotted_sim, "simulate_grid", stale)


def _grid_half(monkeypatch):
    """Only the first half of the seeds run; their results fill the rest."""
    real = slotted_sim.simulate_grid

    def half(seeds, *args, **kw):
        h = len(seeds) // 2
        res = real(seeds[:h], *args, **kw)
        return [row + row[: len(seeds) - h] for row in res]

    monkeypatch.setattr(slotted_sim, "simulate_grid", half)


def _grid_altered(monkeypatch):
    """One job's completion time is off by one slot in every run."""
    real = slotted_sim._finalize

    def altered(*args):
        res = real(*args)
        res.jct[:1] += 1
        return res

    monkeypatch.setattr(slotted_sim, "_finalize", altered)


@pytest.mark.parametrize("cell,plant", [
    ("paper_k30.grid", _grid_stale),
    ("paper_k30.grid", _grid_half),
    ("paper_k30.grid", _grid_altered),
])
def test_planted_fault_is_not_correct(root, monkeypatch, cell, plant):
    plant(monkeypatch)
    out = tiny.run_cell(root, cell, seconds=0.5)
    assert not out["correct"], out["checks"]

