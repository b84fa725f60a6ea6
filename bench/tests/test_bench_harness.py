"""The harness on the CPU: cells found by name, tiny windows, refusals."""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import bench.families
from bench import run
from bench.families import family, known, olmo
from bench.tests.tiny import cell_from_files, run_tiny, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
TRAFFIC_FILES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def _configs_of(traffic: str) -> list:
    """The configurations that BENCHMARK.json runs this traffic mix with."""
    return sorted({w["config"] for w in BENCH["workloads"] if w["traffic"] == traffic})


@pytest.fixture(autouse=True)
def cpu_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    monkeypatch.setattr(run, "enable_cache", lambda: None)


def _copy_benchmark(dst: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))


def _digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in root.rglob("*") if p.is_file()}


@pytest.fixture
def families_path(monkeypatch):
    """Lets a test put one more directory on ``bench.families``' path."""
    def add(directory: Path) -> None:
        monkeypatch.setattr(bench.families, "__path__",
                            [*bench.families.__path__, str(directory)])
    yield add
    sys.modules.pop("bench.families.probe", None)


def test_family_is_found_by_model_type(tmp_path, families_path):
    assert family({"model_type": "olmo"}) is olmo
    # the families present are named, whichever a later configuration adds
    with pytest.raises(KeyError, match=r"'probe'.*known: \[.*'olmo'.*\]"):
        family({"model_type": "probe"})
    (tmp_path / "probe.py").write_text("WIDTH = 7\n")
    families_path(tmp_path)
    assert "probe" in known()
    assert family({"model_type": "probe"}).WIDTH == 7


def test_new_cell_is_files_and_entries_only(tmp_path, families_path):
    _copy_benchmark(tmp_path)
    before = _digests(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())

    # a configuration of another model_type: its family is a new module
    (tmp_path / "bench/families/probe.py").write_text(
        '"""OLMo\'s layers under another model_type."""\n'
        "from bench.families.olmo import (loss_fn, model_config, n_params, param_shapes,\n"
        "                                 train_flops_per_token)\n")
    families_path(tmp_path / "bench" / "families")
    cfg = json.loads((tmp_path / "bench/configs/olmo1b-l4.train-state.json").read_text())
    cfg["model_type"] = "probe"
    (tmp_path / "bench/configs/probe.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/steps-then-save.json").write_text(json.dumps({
        "corpus_tokens": 4096,
        "setup": [{"op": "first_steps", "n": 3}, {"op": "save"}, {"op": "save"}],
        "cycle": [{"op": "train_steps", "n": 2}, {"op": "save"}],
        "trace_cycles": 1}))
    (tmp_path / "bench/metrics/steps_done.py").write_text(
        "def read(run):\n    return float(run.job.steps_done)\n")
    bench["configs"].append({"name": "probe", "source": "https://example.org/probe",
                             "file": "bench/configs/probe.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "probe.steps", "config": "probe",
                               "traffic": "steps-then-save", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "steps_done", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "train step",
                               "moves": "train_tokens_per_s", "workloads": ["probe.steps"]})
    # setup_s carries no list: every cell reports it
    next(m for m in bench["end_to_end"]
         if m["name"] == "train_tokens_per_s")["workloads"].append("probe.steps")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digests(tmp_path)
    changed = [p for p in before if after[p] != before[p]]
    assert changed == ["BENCHMARK.json"]

    cell = tiny_cell(run.load_cell("probe.steps", root=tmp_path), steps_per_cycle=2)
    assert cell.root == tmp_path
    assert family(cell.cfg).__file__ == str(tmp_path / "bench/families/probe.py")
    out = run_tiny(cell, trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["steps_done"]["value"] == 2.0
    out = run_tiny(cell, trace=False)
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_window_runs_tiny(name, trace):
    cell = tiny_cell(run.load_cell(name))
    out = run_tiny(cell, trace=bool(trace))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    assert set(out["metrics"]) <= want
    if not trace:
        assert set(out["metrics"]) == want
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("traffic", TRAFFIC_FILES)
def test_traffic_window_runs_tiny(traffic, trace):
    configs = {c["name"]: c["file"] for c in BENCH["configs"]}
    for config in _configs_of(traffic):
        stem = Path(configs[config]).stem
        out = run_tiny(tiny_cell(cell_from_files(stem, traffic)), trace=bool(trace))
        assert out["correct"], out["checks"]
        assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("traffic", TRAFFIC_FILES)
def test_every_traffic_file_is_tested(traffic):
    """Each traffic file is the mix of some cell, so the test above runs it."""
    assert _configs_of(traffic), f"no cell of BENCHMARK.json runs {traffic}"


def _bench_cmd(cwd: Path, name: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_tpu():
    res = _bench_cmd(ROOT, CELLS[0])
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    _copy_benchmark(tmp_path)
    res = _bench_cmd(tmp_path, CELLS[0])
    assert res.returncode != 0
    assert res.stdout.strip() == ""
