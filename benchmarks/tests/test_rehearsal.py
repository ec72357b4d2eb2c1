"""Each cell's control flow end to end on the CPU, at TINY widths, in a
temporary copy whose DATA files alone were shrunk (``rehearsal.py``). What
is checked is the contract of the result line and the books — never a
speed: a number from these runs is not a device number."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import rehearsal

ROOT = rehearsal.ROOT
ADMITTED = [w["name"] for w in
            json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
# admitted cells and the parked ones (``benchmarks/parked.json``)
CELLS = [w["name"] for w in rehearsal.with_parked()["workloads"]]


def run_cell(copy: Path, cell: str, trace: int, seconds: float = 3.0,
             seed: int = 1, chips: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/tests/rehearsal.py"),
         str(copy), "--workload", cell, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600, cwd=copy)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_tiny_copy(tmp_path_factory.mktemp("bench_copy"))


def _check_line(copy: Path, cell: str, trace: int, out: dict):
    bm = json.loads((copy / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in bm["workloads"]}[cell]
    keys = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(out) == keys | ({"breakdown"} if trace else set())
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["count"] == chips
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bm[kind]
               if "workloads" not in m or cell in m["workloads"]}
    assert out["metrics"], "a run reports at least one metric"
    for name, m in out["metrics"].items():
        assert allowed[name] == m["unit"] and isinstance(m["value"], float)
    if trace:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        for key in ("device_ops", "idle_gaps"):
            assert 0 < len(out["breakdown"][key]) <= 10
    else:
        assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_to_a_correct_result_line(copy, cell, trace):
    chips = 4 if cell.startswith("pool4") else 1
    out, log = run_cell(copy, cell, trace, chips=chips)
    _check_line(copy, cell, trace, out)
    assert "check zero compilations inside the window: ok" in log


def test_chip_refusal_without_the_bypass():
    """The real entry point on a machine without a TPU: non-zero exit and
    no result line."""
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", ADMITTED[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_no_result_in_a_directory_without_the_program(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the system under
    test is missing, so there is nothing to measure."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", ADMITTED[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=300, env=env)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_a_fifth_cell_and_a_new_metric_need_only_new_files(tmp_path):
    """A later PR adds a traffic mix, a per-layer metric and its reader as
    files plus entries in BENCHMARK.json; no file that exists under
    ``benchmarks/`` is edited."""
    copy = rehearsal.make_tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in (copy / "benchmarks").rglob("*")
              if p.is_file()}
    bench = copy / "benchmarks"
    traffic = json.loads((bench / "traffic" / "s64-steady.json").read_text())
    traffic["rate_txn_per_s"] = 150
    (bench / "traffic" / "s64-trough.json").write_text(json.dumps(traffic))
    (bench / "readers" / "deadline_close_share.py").write_text(
        '"""Percent of batches the assembler closed on its deadline."""\n\n'
        "def read(run):\n"
        "    total = sum(run.close_reasons.values())\n"
        "    return 100.0 * run.close_reasons.get('deadline', 0) / total "
        "if total else None\n")
    (bench / "layer_metrics" / "deadline_close_pct.json").write_text(
        json.dumps({"reader": "deadline_close_share"}))
    bm = json.loads((copy / "BENCHMARK.json").read_text())
    bm["workloads"].append({"name": "s64-trough", "config": "distilbert-s64",
                            "traffic": "s64-trough", "chips": 1,
                            "why": "lone events"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "s64-steady" in m.get("workloads", []):
            m["workloads"].append("s64-trough")
    bm["per_layer"].append({
        "name": "deadline_close_pct", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "batching", "moves": "p50_ms",
        "workloads": ["s64-trough"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bm))

    out, _ = run_cell(copy, "s64-trough", trace=1)
    _check_line(copy, "s64-trough", 1, out)
    assert out["metrics"]["deadline_close_pct"]["value"] > 50.0
    out, _ = run_cell(copy, "s64-trough", trace=0)
    assert "p50_ms" in out["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing benchmark file was edited"
