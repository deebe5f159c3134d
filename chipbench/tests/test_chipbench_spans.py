"""The readers of the grid call's host spans, in a traced run of each cell
cut to CPU size.

The CPU has no TPU plane, so the trace is reduced with the CPU client's
threads standing for the device, as ``test_chipbench_trace.py`` does.
Each cell runs in a process of its own, whose JAX is started with as many
host devices as the cell has chips.
"""
import json
import os
import subprocess
import sys

import pytest

from chipbench.tests.tiny import ROOT

SPAN_METRICS = ("prepare_s.grid", "fetch_s.grid", "finalize_s.grid")

_SCRIPT = """
import functools, json, pathlib, sys
import jax
from chipbench import trace
from chipbench.tests import tiny
from repro import spans

trace.reduce = functools.partial(
    trace.reduce, device_plane=trace.HOST_PLANE,
    busy_line=r"XLAPjRtCpuClient", ops_line=r"XLAPjRtCpuClient")
cell, chips, tmp = sys.argv[1], int(sys.argv[2]), pathlib.Path(sys.argv[3])
root = tiny.checkout(tmp)
out = tiny.run_cell(root, cell, trace=1, devices=jax.devices()[:chips])
print(json.dumps({"call_s": spans.last("simulate_grid").seconds}))
print(json.dumps(out))
"""


@pytest.mark.parametrize("cell,chips", [("paper_k30.grid", 1),
                                        ("paper_k30.grid4", 4)])
def test_traced_run_reports_the_span_metrics(cell, chips, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT, cell, str(chips), str(tmp_path)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    call_s = next(x["call_s"] for x in lines if "call_s" in x)
    out = lines[-1]
    assert out["correct"], out["checks"]
    values = [out["metrics"][m]["value"] for m in SPAN_METRICS]
    assert all(v > 0 for v in values), values
    assert all(out["metrics"][m]["unit"] == "s" for m in SPAN_METRICS)
    assert sum(values) <= call_s <= out["device"]["window_s"]
