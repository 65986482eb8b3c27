"""``BENCHMARK.json`` against the benchmark's contract, the files every cell
names, and ``bench/run.py`` refusing to run without an accelerator."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_sources():
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [c["name"] for c in SPEC["configs"]] + CELLS
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in {"lower", "higher"}
    setup = next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.25


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_what_it_must(cell):
    e2e = [m for m in SPEC["end_to_end"] if _reports(m, cell)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    per = [m for m in SPEC["per_layer"] if _reports(m, cell)]
    assert per
    for m in per:
        moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        assert _reports(moved, cell), (m["name"], cell)


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_names_files_that_exist(cell):
    w = next(x for x in SPEC["workloads"] if x["name"] == cell)
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cfg = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    f = ROOT / cfg["file"]
    sizes = json.loads(f.read_text())
    assert f.with_name(f"{sizes['model']}.py").is_file()
    assert set(cfg["reduced"]) == set(sizes["reduced"])
    traffic = json.loads((ROOT / "bench/traffic" / f"{w['traffic']}.json")
                         .read_text())
    assert (ROOT / "bench/drivers" / f"{traffic['driver']}.py").is_file()
    limits = json.loads((ROOT / "bench/limits" / f"{cell}.json").read_text())
    assert limits["limits"] and all(v > 0 for v in limits["limits"].values())
    for m in SPEC["per_layer"]:
        if _reports(m, cell):
            assert (ROOT / "bench/metrics" / f"{m['name']}.py").is_file()


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_layers_are_named_in_perf_md():
    perf = (ROOT / "PERF.md").read_text()
    for m in SPEC["per_layer"]:
        assert f"| {m['layer']} |" in perf, m["layer"]


def _run(cwd: Path, script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, str(script), "--workload", CELLS[0], "--seed",
         str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_an_accelerator():
    r = _run(ROOT, ROOT / "bench/run.py")
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout and '"correct"' not in r.stdout


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in SPEC["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, tmp_path / "bench/run.py")
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout
