"""A copy of the benchmark with every cell cut to a size the CPU runs in
seconds, and a way to run its cells there without the look for a chip."""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil

import jax

from chipbench import run as harness

ROOT = pathlib.Path(__file__).resolve().parents[2]

# Per file under chipbench/: the keys to shrink (a workload's keys live
# under "traffic").
SHRINK = {
    "configs/paper_k30.json": {"slots": 300},
    "workloads/paper_k30.grid.json": {"loads": [0.5, 0.95],
                                      "seeds_per_point": 2,
                                      "check_runs": 64},
    "workloads/paper_k30.grid4.json": {"loads": [0.5, 0.95],
                                       "seeds_per_point": 2,
                                       "check_runs": 64},
}


def checkout(dest: pathlib.Path) -> pathlib.Path:
    """Copy ``BENCHMARK.json`` and ``chipbench/`` to ``dest``, shrunk."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, keys in SHRINK.items():
        path = dest / "chipbench" / rel
        data = json.loads(path.read_text())
        (data["traffic"] if "traffic" in data else data).update(keys)
        path.write_text(json.dumps(data))
    return dest


def run_cell(root: pathlib.Path, cell: str, *, seed: int = 2**33 + 7,
             seconds: float = 0.2, trace: int = 0, variant=None,
             devices=None) -> dict:
    """One run of ``cell`` on the CPU, past the look for a chip and with
    the persistent compilation cache left off."""
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=trace, variant=variant)
    cache = harness.enable_compile_cache
    harness.enable_compile_cache = lambda: "off"
    try:
        return harness.run(args, devices or jax.devices()[:1], root=root)
    finally:
        harness.enable_compile_cache = cache
