"""``BENCHMARK.json`` and the files it names: each found by name, each
name and unit in the allowed characters, each per-layer metric reported
where the end-to-end metric it moves is; and ``run.py`` refuses to run
without a TPU or outside a checkout."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tiny_cells

BENCH = tiny_cells.BENCH
ROOT = BENCH.parent
harness = tiny_cells.harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command(manifest):
    assert set(manifest) == KEYS
    assert manifest["paths"] == ["bench"]
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    cells = len(manifest["workloads"])
    assert 1 <= manifest["run_seconds"] <= 51
    # a full check of 24 cells has to fit its 43,200 s
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) \
        + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, cells // 2)


def test_names_and_units(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
                assert entry["source"] in SOURCES
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in manifest[group]]
        assert len(set(group_names)) == len(group_names)
    for w in manifest["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert LINE.match(w["why"]) and w["chips"] in (1, 4)
    for m in manifest["per_layer"]:
        assert LINE.match(m["layer"])
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_each_cell_finds_its_files(manifest):
    pairs = set()
    for w in manifest["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cell = harness.find_cell(w["name"], manifest)
        assert (BENCH / "drivers" / f"{cell.kind}.py").is_file()
        assert hasattr(harness.load_driver(cell.kind), "Driver")
        assert (BENCH / "limits" / f"{w['name']}.json").is_file()
        assert cell.limits and all(isinstance(v, (int, float))
                                   for v in cell.limits.values())
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_configs_state_their_cuts(manifest):
    for c in manifest["configs"]:
        assert c["file"].startswith("bench/configs/")
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert LINE.match(c["source"]) and data["source"] == c["source"]
        used = [w for w in manifest["workloads"] if w["config"] == c["name"]]
        assert used, f"config {c['name']} has no cell"


def test_every_traffic_and_metric_file_loads(manifest):
    for path in sorted((BENCH / "traffic").glob("*.json")):
        assert "kind" in json.loads(path.read_text()), path
    for path in sorted((BENCH / "configs").glob("*.json")):
        json.loads(path.read_text())
    for path in sorted((BENCH / "limits").glob("*.json")):
        json.loads(path.read_text())
    for m in manifest["per_layer"]:
        reader = harness.load_module(BENCH / "metrics" / f"{m['name']}.py",
                                     "test_reader")
        assert callable(reader.read)


def test_per_layer_cells_report_what_they_move(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        assert m["workloads"], m["name"]
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in moved.get("workloads", cells), (m["name"], cell)


def test_bounds(manifest):
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m["name"]
        assert m["source"] in ("host_clock", "device_trace")


def _run_py(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "sift1m-ivf1024.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    res = _run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert res.stdout == ""
    assert "no TPU" in res.stderr


def test_run_exits_nonzero_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert res.stdout == ""
