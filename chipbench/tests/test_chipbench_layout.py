"""The benchmark's files agree with each other and with the contract's
rules on names, and the command refuses a machine without a TPU."""
import json
import os
import re
import shutil
import subprocess

import pytest

from chipbench.run import load_module
from chipbench.tests.tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
FILES = ROOT / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ONE_LINE = re.compile(r"^[^\t\n]{1,200}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32


def test_names_units_and_one_line_fields():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]
    ]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert ONE_LINE.match(entry["why"]), entry["why"]
    for m in BENCH["per_layer"]:
        assert ONE_LINE.match(m["layer"])
    for c in BENCH["configs"]:
        assert ONE_LINE.match(c["source"])
        assert all(NAME.match(k) for k in c["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_name_existing_config_driver_and_metrics(cell):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    wl = json.loads((FILES / "workloads" / f"{cell}.json").read_text())
    assert wl["config"] == entry["config"] and wl["chips"] == entry["chips"]
    assert entry["chips"] in (1, 4)
    assert any(c["name"] == wl["config"] for c in BENCH["configs"])
    assert (FILES / "configs" / f"{wl['config']}.json").is_file()
    driver = load_module(FILES / "drivers" / f"{wl['driver']}.py")
    e2e = [m for m in BENCH["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    assert {m["name"] for m in e2e} == {"setup_s", driver.RATE}
    layers = [m for m in BENCH["per_layer"] if cell in m["workloads"]]
    assert layers
    for m in layers:
        assert m["moves"] == driver.RATE
        reader = load_module(FILES / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)


def test_every_config_is_used_and_its_file_exists():
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def _command(cwd, env):
    return subprocess.run(
        BENCH["command"] + ["--workload", CELLS[0], "--seed", "2147483659",
                            "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_command_refuses_a_platform_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _command(ROOT, env)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_command_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(FILES, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = _command(tmp_path, env)
    assert out.returncode != 0
    assert out.stdout == ""
