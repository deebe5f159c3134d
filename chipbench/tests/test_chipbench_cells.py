"""Every one-chip cell, cut to CPU size, runs through the harness past its
look for a chip: the program agrees with the plain reference bit for bit,
and the control (lowest-index ties in place of random ones) comes out
incorrect."""
import json

import pytest

from chipbench.tests import tiny

ONE_CHIP = ["paper_k30.grid"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.checkout(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_program_matches_reference(root, cell, capsys):
    out = tiny.run_cell(root, cell)
    info = json.loads(next(x for x in capsys.readouterr().out.splitlines()
                           if x.startswith('{"chipbench"')))
    assert info["window_compiles"] == 0
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    exact = {k: c for k, c in out["checks"].items() if c["limit"] == 0}
    assert exact and all(c["value"] == 0 for c in exact.values())
    assert set(out["metrics"]) == {"setup_s", "run_slots_per_s"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_control_is_not_correct(root, cell):
    out = tiny.run_cell(root, cell, variant="control")
    assert not out["correct"], out["checks"]


def test_traced_run_reports_breakdown(root):
    out = tiny.run_cell(root, "paper_k30.grid", trace=1)
    assert out["correct"]
    # No TPU plane on the CPU: the device readers find nothing to read.
    assert out["metrics"] == {}
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
