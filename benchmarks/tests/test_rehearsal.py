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
             seed: int = 1, chips: int = 1, device_ops: Path = None,
             broken: str = None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    stand_in = ["--device-ops", str(device_ops)] if device_ops else []
    stand_in += ["--break", broken] if broken else []
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/tests/rehearsal.py"),
         str(copy), *stand_in, "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
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


@pytest.mark.parametrize("broken,check", [
    ("text-answer", "parity with the plain float32 reference"),
    ("fan-out", "every attempted transaction accounted for")])
def test_a_broken_timed_path_comes_out_not_correct(copy, broken, check):
    """The rest of a run as it is, the program broken underneath
    (``rehearsal.BREAKS``): the result line says ``correct`` false and the
    log names the check that saw it."""
    out, log = run_cell(copy, ADMITTED[0], trace=0, broken=broken)
    assert out["correct"] is False
    assert f"check {check}: FAILED" in log


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


# ---- a configuration of another architecture, as the files a later PR adds

HF_CONFIG = {
    "name": "hfbert-s128",
    "source": "https://huggingface.co/google-bert/bert-base-uncased/blob/"
              "main/config.json",
    "builder": "hfbert_builder", "reference": "hfbert_reference",
    # Hugging Face BERT's key names, at a rehearsal's sizes
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 2,
    "intermediate_size": 256, "vocab_size": 30522,
    "max_position_embeddings": 512, "reduced": [],
    "text_len": 128, "chips": 1, "parity_rows": 8,
    "population": {"users": 2000, "merchants": 200},
}

HF_BUILDER = '''
"""The five-branch ensemble with its text branch sized from Hugging Face
BERT's key names, and a program that nests experts under each layer."""
import functools

from benchmarks.harness import flops, system

VOCABULARY = {
    "trees": {}, "lstm": {}, "gnn": {}, "iforest": {},
    "text": {"embed": {}, "layer*": {
        "attn_core": {}, "experts": {"expert*": {"up": {}, "down": {}}}}},
}
TINY = {"hidden_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 2, "intermediate_size": 256}


def _bert(cfg):
    from realtime_fraud_detection_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"])


def make_models(cfg, seed, sample_features):
    import jax

    from realtime_fraud_detection_tpu.scoring import ScorerConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    sc, a = ScorerConfig(), cfg["assumed"]
    init = jax.jit(functools.partial(
        init_scoring_models, bert_config=_bert(cfg),
        feature_dim=sc.feature_dim, node_dim=sc.node_dim,
        n_trees=a["n_trees"], tree_depth=a["tree_depth"]))
    return system.seeded_forests(init(jax.random.PRNGKey(seed)), cfg, seed,
                                 sample_features)


def make_scorer(cfg, seed, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0
    scorer = FraudScorer(
        config, models=models, bert_config=_bert(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def matmul_flops_per_batch(cfg):
    return flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=cfg["text_len"],
        batch=cfg["job"]["max_batch"])["total"]
'''

HF_REFERENCE = '''
"""The ensemble's plain reference, told the head count in this file's own
key (a model with other mathematics brings them here)."""
from benchmarks.harness import spec

_ensemble = spec.reference("ensemble_reference")
BRANCHES = _ensemble.BRANCHES


def score(models, batch, params, model_valid, cfg):
    return _ensemble.score(models, batch, params, model_valid,
                           {"n_heads": cfg["num_attention_heads"]})
'''

HF_KERNEL = '''
"""The embedding gather (scope ``text/embed``): no arithmetic to speak of,
one row of ``hidden_size`` float32 read and written per launched slot —
bound by HBM bandwidth."""


def work(counters, cfg):
    slots = counters.get("token_slots", 0)
    return {"flops": 0.0, "hbm_bytes": 2.0 * 4 * slots * cfg["hidden_size"]}
'''

# what the chip's trace would name: the program of this architecture nests
# ``expert<j>/up`` under ``layer<i>/experts``
JIT = "jit(_score_fused_packed_impl)/"
HF_DEVICE_OPS = [
    [JIT + "text/embed/gather:", 0, 10],
    [JIT + "text/layer0/experts/expert3/up/dot_general:", 10, 40],
    [JIT + "text/layer0/experts/expert5/down/dot_general:", 50, 20],
    [JIT + "text/layer1/experts/jit(grouped)/expert0/up/dot_general", 70, 30],
    [JIT + "text/layer1/attn_core/reduce_max", 100, 5],
    [JIT + "trees/jit(take)/gather", 105, 5],
    ["copy-done.1", 110, 1],
]


def test_a_configuration_of_another_architecture_needs_only_new_files(
        tmp_path):
    """A later PR adds a model of another family: a configuration file in
    that family's key names, its builder (with a scope vocabulary two levels
    deeper than the ensemble's), its reference, a byte-bound kernel, a metric
    on each, and a cell — as files and entries; no file that exists under
    ``benchmarks/`` is edited, and nothing of the harness reads a width."""
    copy = rehearsal.make_tiny_copy(tmp_path)
    before = {p: p.read_bytes() for p in (copy / "benchmarks").rglob("*")
              if p.is_file()}
    bench = copy / "benchmarks"
    shared = json.loads((bench / "configs" / "distilbert-s512.json")
                        .read_text())
    cfg = dict(HF_CONFIG, **{k: shared[k] for k in (
        "job", "parity_atol", "assumed", "compute_dtype", "guarantee")})
    assert not {"dim", "hidden_dim", "n_layers", "n_heads"} & set(cfg)
    (bench / "configs" / "hfbert-s128.json").write_text(json.dumps(cfg))
    (bench / "configs" / "hfbert_builder.py").write_text(HF_BUILDER)
    (bench / "configs" / "hfbert_reference.py").write_text(HF_REFERENCE)
    (bench / "kernels" / "embed_gather.py").write_text(HF_KERNEL)
    (bench / "layer_metrics" / "expert_up_ms_per_batch.json").write_text(
        json.dumps({"reader": "scope_time_per_batch", "args": {
            "scopes": ["text/layer*/experts/expert*/up"]}}))
    (bench / "layer_metrics" / "embed_roofline_pct.json").write_text(
        json.dumps({"reader": "scope_roofline", "args": {
            "scope": "text/embed", "kernel": "embed_gather",
            "peak": "hbm_bytes_per_s"}}))
    ops = tmp_path / "device_ops.json"
    ops.write_text(json.dumps(HF_DEVICE_OPS))

    cell = "hfbert-fulltext-saturated"
    bm = json.loads((copy / "BENCHMARK.json").read_text())
    bm["configs"].append({
        "name": "hfbert-s128", "source": cfg["source"],
        "file": "benchmarks/configs/hfbert-s128.json", "reduced": [],
        "why": "another family's key names, builder and scopes"})
    bm["workloads"].append({
        "name": cell, "config": "hfbert-s128",
        "traffic": "s512-fulltext-saturated", "chips": 1,
        "why": "the fulltext backlog on a configuration added as files"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("txn_per_s", "token_padding_pct",
                         "matmul_util_pct", "text_ms_per_batch"):
            m["workloads"].append(cell)
    for name, unit in (("expert_up_ms_per_batch", "ms"),
                       ("embed_roofline_pct", "%")):
        bm["per_layer"].append({
            "name": name, "unit": unit, "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "txn_per_s", "workloads": [cell]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bm))

    out, log = run_cell(copy, cell, trace=1, device_ops=ops)
    _check_line(copy, cell, 1, out)
    assert "configs/hfbert_reference.py" in log
    m = {k: v["value"] for k, v in out["metrics"].items()}
    batches = 1e3 * 0.070 / m["expert_up_ms_per_batch"]
    assert batches == pytest.approx(round(batches)) and batches >= 1
    # text = embed + three expert operations + attn_core: 105 ms
    assert m["text_ms_per_batch"] * batches == pytest.approx(105.0)
    assert m["embed_roofline_pct"] > 0.0
    assert m["matmul_util_pct"] > 0.0 and 0.0 <= m["token_padding_pct"] < 100
    out, _ = run_cell(copy, cell, trace=0)
    _check_line(copy, cell, 0, out)
    assert out["metrics"]["txn_per_s"]["value"] > 0.0
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing benchmark file was edited"
