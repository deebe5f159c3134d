"""The four-chip cell, cut to CPU size, on four CPU devices: the sharded
grid agrees with the plain reference, and the control and a grid whose
chips' results are never gathered (every chip's block read from the first
chip) come out incorrect.

Each case runs in a process of its own, whose JAX is started with four
host devices.
"""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests.tiny import ROOT

_SCRIPT = """
import json, pathlib, sys, tempfile
import jax, jax.numpy as jnp
from chipbench.tests import tiny
from repro.core.care import slotted_sim

case = sys.argv[1]
if case == "ungathered":
    real = slotted_sim.shard_runs

    def ungathered(batched, n_dev, n_args):
        fn = real(batched, n_dev, n_args)

        def first_chip_only(*args):
            return jax.tree.map(
                lambda o: jnp.concatenate([o[: o.shape[0] // n_dev]] * n_dev),
                fn(*args))

        return first_chip_only if n_dev > 1 else fn

    slotted_sim.shard_runs = ungathered
root = tiny.checkout(pathlib.Path(tempfile.mkdtemp()))
out = tiny.run_cell(root, "paper_k30.grid4", devices=jax.devices()[:4],
                    variant="control" if case == "control" else None)
print(json.dumps(out))
"""


def _run(case: str) -> tuple[dict, dict]:
    """The result line of one run, and the harness's line on set-up and
    window."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT, case], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    info = next(x for x in lines if "chipbench" in x)
    return lines[-1], info


def test_sharded_grid_matches_reference_and_compiles_nothing_in_window():
    out, info = _run("sound")
    assert info["window_compiles"] == 0
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["checks"].values())


@pytest.mark.parametrize("case", ["control", "ungathered"])
def test_sharded_grid_fault_is_not_correct(case):
    out, _ = _run(case)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0
