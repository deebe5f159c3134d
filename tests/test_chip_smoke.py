"""``chip_smoke.py`` on the CPU: every phase at tiny sizes, and the refusal.

The script's phases are plain functions of their sizes, so the checks a
chip run makes (grid vs per-run reference, numpy parity, stream vs fixed
horizon, kernel vs dense, sharded vs one device) run here too -- with the
kernels interpreted, which the kernel phase itself asserts.  ``main()``
must refuse a platform other than TPU before running anything.
"""
import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

TINY = {
    "paper_grid": dict(slots=400, seeds=2, loads=(0.5, 0.95), xs=(2, 3),
                       cpu_runs=2),
    "serving_grid": dict(replicas=8, decode_slots=4, slots=300, seeds=2),
    "stream": dict(replicas=8, decode_slots=4, chunk=64, chunks=3),
    "kernels": dict(servers=(200,), slots=300, replicas=8, decode_slots=4,
                    serve_slots=200),
    "sharded": dict(slots=400, seeds=3, replicas=8, decode_slots=4,
                    serve_slots=200),
}


@pytest.mark.parametrize("phase", sorted(TINY))
def test_phase_checks_pass_at_tiny_sizes(phase):
    rec = getattr(chip_smoke, phase)(**TINY[phase])
    assert rec["phase"] == phase
    assert rec["checks"] and all(rec["checks"].values()), rec["checks"]
    assert rec["wall_s"] > 0 and rec["compile_s"] >= 0


def test_paper_grid_reports_cpu_agreement():
    rec = chip_smoke.paper_grid(**TINY["paper_grid"])
    # On the CPU the "chip" is the CPU: the replay must agree exactly.
    assert rec["cpu_agreement"] == {
        "runs": 2, "bitwise": True, "first_divergence": None
    }
    assert not rec["seeds_cut_to_fit"]


def test_main_refuses_a_non_tpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""  # no result line
    assert "no TPU" in out.err
