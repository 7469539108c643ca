"""The entry point refuses to run off a TPU, and every cell resolves to its files."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run_py(cwd: Path, tmp_path: Path, *args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    p = _run_py(ROOT, tmp_path, "--workload", CELLS[0], "--seed", str(2**31 + 5),
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert "{" not in p.stdout   # no result line


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, tmp_path, "--workload", CELLS[0], "--seed", "1", "--seconds", "1")
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        run.resolve("no.such.cell")


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_its_files(name):
    cell = run.resolve(name)
    assert cell.chips in (1, 4)
    assert (ROOT / "chipbench" / "apps" / f"{cell.app_name}.py").is_file()
    # the harness builds the cell's policy, and knows every end-to-end metric
    assert run._policy(cell.traffic) is not None
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "iter_s"}
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(run.load_reader(m["name"]))
    assert set(cell.config["limits"]), "every configuration states its limits"


def test_benchmark_file_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names) and all(NAME.match(n) for n in names)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
