"""A whole run of each driver on the CPU at tiny size, through the code
a chip run takes, minus the look for a chip; and the harness finding
its pieces by name from files alone."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import ROOT

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _contract_keys(result: dict, traced: bool) -> None:
    want = KEYS + (["breakdown"] if traced else [])
    assert sorted(k for k in result if k in want + ["breakdown"]) == \
        sorted(want)
    assert list(result)[-1] == "checks"         # numbers compared, last
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    if traced:
        assert set(result["device"]) >= {"busy_s", "window_s"}
        assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell,trace", [
    ("qwen3-1.7b.decode", False), ("qwen3-1.7b.decode", True),
    ("qwen3-1.7b.prefill", False), ("qwen3-1.7b.prefill", True)])
def test_tiny_run_prints_the_contract_line(bench_run, tiny, tmp_path,
                                           cell, trace):
    found = tiny(cell)
    result = bench_run.execute(found, 2**31 + 11, 1.0, trace,
                               trace_dir=tmp_path / "trace")
    json.dumps(result)
    _contract_keys(result, trace)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    wanted = found["per_layer"] if trace else found["end_to_end"]
    names = {m["name"] for m in wanted}
    assert set(result["metrics"]) <= names
    if not trace:
        # every end-to-end metric of the cell is there, setup_s included
        assert set(result["metrics"]) == names
        assert "setup_s" in result["metrics"]
    # set-up is split into its phases, each timed on the host
    phases = result["diagnostics"]["setup_phases"]
    assert {"program_import", "setup.weights", "setup.warmup",
            "jax.cache_read", "jax.compile"} <= set(phases)
    assert all(v >= 0 for v in phases.values())


def test_run_refuses_to_run_off_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen3-1.7b.decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "refusing to run" in proc.stderr


def test_run_fails_where_only_the_benchmark_is(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_cells_are_found_by_name_from_files_alone(bench_run, tmp_path):
    """A new cell is files and entries only: a configuration, a mix, a
    limits file and a reader, and no edit of an existing file."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "x",
                            "file": "bench/configs/toy.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "toy.mix", "config": "toy",
                              "traffic": "mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "toy_ms", "unit": "ms",
                              "better": "lower", "source": "host_clock",
                              "layer": "toy", "moves": "setup_s",
                              "workloads": ["toy.mix"]})
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    files = {"configs/toy.json": {"driver": "toydriver", "shapes": {}},
             "traffic/mix.json": {"kind": "toy", "n": 3},
             "limits/toy.mix.json": {"numbers": {}}}
    for rel, body in files.items():
        (tmp_path / "bench" / rel).write_text(json.dumps(body))
    (tmp_path / "bench" / "metrics" / "toy_ms.py").write_text(
        "def read(run):\n    return 1.5\n")
    found = bench_run.find_cell("toy.mix", root=tmp_path)
    assert found["config"]["driver"] == "toydriver"
    assert found["traffic"] == {"kind": "toy", "n": 3}
    assert [m["name"] for m in found["per_layer"]] == ["toy_ms"]
    assert [m["name"] for m in found["end_to_end"]] == ["setup_s"]
    reader = bench_run.load_file(found["bench"] / "metrics" / "toy_ms.py")
    assert reader.read({}) == 1.5
    with pytest.raises(KeyError, match="no workload"):
        bench_run.find_cell("toy.other", root=tmp_path)
