"""The seam between the program and ``benchmarks/``, in tier-1: the fast
tests of ``benchmarks/tests`` (run by hand, not by the driver) copied here,
so that a program PR that moves something a builder, a kernel file or a
reader depends on fails the suite, not the next chip run.

What is held: every configuration file runs its source's published sizes
but for what it lists as ``reduced``; each builder turns its file into the
program's config class; the kernels' operation and byte counts; the readers
on a hand-made run; a builder that needs a module the program lacks stops at
once; and a TINY CPU rehearsal of the OLMoE cell end to end
(``benchmarks/tests/rehearsal.py``: the chip's code on the CPU, DATA files
alone shrunk). No test reports a device number.
"""

import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "benchmarks" / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import rehearsal  # noqa: E402  (benchmarks/tests/rehearsal.py)
from benchmarks.harness import spec  # noqa: E402

BM = spec.benchmark()
ALL = rehearsal.with_parked()
OLMOE_CELL = "olmoe-s128-memo-saturated"
OLMOE_CFG = json.loads(
    (ROOT / "benchmarks/configs/olmoe-1b-7b-s128.json").read_text())


# ------------------------------------------------------ configuration files
@pytest.mark.parametrize("config", [c["name"] for c in ALL["configs"]])
def test_a_config_file_runs_the_published_sizes_but_for_reduced(config):
    from realtime_fraud_detection_tpu.stream import JobConfig

    entry = {c["name"]: c for c in ALL["configs"]}[config]
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["source"] == entry["source"] and cfg["published"]
    for key, value in cfg["published"].items():
        assert (cfg[key] != value) == (key in cfg["reduced"]), key
    assert set(cfg["reduced"]) <= set(cfg["published"])
    builder = spec.builder(cfg)
    for name in ("make_models", "make_scorer", "matmul_flops_per_batch"):
        assert callable(getattr(builder, name)), name
    assert set(builder.TINY) <= set(cfg)
    assert callable(spec.reference(cfg["reference"]).score)
    default = JobConfig()
    for key, value in cfg["job"].items():
        if key not in ("device_pool", "inflight_depth"):
            assert getattr(default, key) == value, key


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_name_of_an_admitted_cell_resolves_to_a_file(cell):
    w = spec.cell(cell)
    assert w["config_data"]["chips"] == w["chips"] == 1
    assert spec.arrival(w["traffic_data"]["arrival"]).MODE == "backlog"
    e2e = spec.metrics_for(cell, "end_to_end")
    layer = spec.metrics_for(cell, "per_layer")
    assert {m["name"] for m in e2e} == {"txn_per_s", "setup_s"}
    for kind, defs in (("end_to_end", e2e), ("per_layer", layer)):
        for m in defs:
            assert callable(spec.reader_for(m["name"], kind))


def test_the_olmoe_file_is_the_sources_config_cut_in_depth_only():
    from realtime_fraud_detection_tpu.models.olmoe import OlmoeConfig

    builder = spec.builder(OLMOE_CFG)
    built = builder.olmoe_config(OLMOE_CFG)
    assert OLMOE_CFG["reduced"] == ["num_hidden_layers"]
    assert built == OlmoeConfig(num_hidden_layers=8)    # defaults: published
    assert OLMOE_CFG["published"]["num_hidden_layers"] == 16
    assert (built.num_experts, built.num_experts_per_tok, built.head_dim,
            built.norm_topk_prob) == (64, 8, 128, False)
    assert OLMOE_CFG["text_len"] == 128 and OLMOE_CFG["chips"] == 1
    for key in ("cut", "deployment", "assumed", "compute_dtype", "guarantee"):
        assert OLMOE_CFG[key], key
    tiny = builder.olmoe_config({**OLMOE_CFG, **builder.TINY})
    assert tiny.hidden_size < 512 and tiny.num_experts_per_tok > 1


def test_the_ensemble_file_is_distilberts_config():
    from realtime_fraud_detection_tpu.models.bert import BertConfig

    cfg = json.loads(
        (ROOT / "benchmarks/configs/distilbert-s512.json").read_text())
    assert spec.builder(cfg).bert_config(cfg) == BertConfig()


def test_the_new_cell_and_its_metrics_are_listed_once_and_last():
    cells = [w["name"] for w in BM["workloads"]]
    assert cells[-1] == OLMOE_CELL and cells.count(OLMOE_CELL) == 1
    w = BM["workloads"][-1]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "olmoe-1b-7b-s128", "s128-memo-saturated", 1)
    reports = {m["name"] for m in spec.metrics_for(OLMOE_CELL, "per_layer")}
    assert {"expert_ffn_ms_per_batch", "expert_matmul_ms_per_batch",
            "expert_ffn_roofline_pct", "router_ms_per_batch",
            "router_roofline_pct", "expert_imbalance_x",
            "attn_core_ms_per_batch", "text_ms_per_batch",
            "unscoped_device_pct", "hbm_peak_gb"} <= reports
    # DistilBERT's kernel files read DistilBERT's keys
    assert not {"ffn_ms_per_batch", "ffn_roofline_pct",
                "attn_core_roofline_pct"} & reports
    for m in BM["per_layer"][-6:]:
        assert m["workloads"] == [OLMOE_CELL] and m["moves"] == "txn_per_s"


# ------------------------------------------------- operation and byte counts
def test_olmoe_matmul_flops_three_quarters_are_the_experts():
    builder = spec.builder(OLMOE_CFG)
    per_token = builder.text_matmul_flops_per_token(OLMOE_CFG)
    assert per_token == {"projections": 2 * 4 * 2048 * 2048,
                         "router": 2 * 2048 * 64,
                         "experts": 2 * 3 * 2048 * 1024 * 8}
    total = builder.matmul_flops_per_batch(OLMOE_CFG)
    experts = 8 * 256 * 128 * per_token["experts"]
    assert 0.73 < experts / total < 0.76
    assert 35e12 < total < 36e12


def test_expert_ffn_and_router_kernels_charge_what_the_program_counted():
    rows = 256 * 128 * 8 * 8                       # one launch, 8 layers
    counters = {"expert_rows": rows, "token_slots": 256 * 128, "batches": 1}
    ffn = spec.kernel("expert_ffn").work(counters, OLMOE_CFG)
    assert ffn["flops"] == 3 * 2 * rows * 2048 * 1024
    weights = 8 * 64 * 3 * 2048 * 1024 * 2
    assert ffn["hbm_bytes"] == weights + rows * (
        2 * 2048 * 2 + 2 * 2 * 1024 * 4 + 2 * 1024 * 2 + 2048 * 4)
    # compute-bound: far above the v5e's ridge of 240 FLOP a byte
    assert ffn["flops"] / ffn["hbm_bytes"] > 240
    router = spec.kernel("router").work(counters, OLMOE_CFG)
    assert router["flops"] == 2 * 8 * 256 * 128 * 2048 * 64
    assert router["hbm_bytes"] == 8 * 256 * 128 * (
        2048 * 4 + 64 * 4 + 8 * 8 + 8 * 8)
    assert router["flops"] / router["hbm_bytes"] < 240   # memory-bound
    # a program that counted nothing is charged nothing
    none = spec.kernel("expert_ffn").work({"batches": 3}, OLMOE_CFG)
    assert none == {"flops": 0.0, "hbm_bytes": 0.0}
    assert spec.kernel("router").work({}, OLMOE_CFG)["hbm_bytes"] == 0.0


def _fake_run(scope_s, counters):
    return types.SimpleNamespace(
        trace={"window_s": 1.0}, counters_slice=dict(counters),
        counters=dict(counters),
        extra={"cfg": OLMOE_CFG, "device": {"kind": "TPU v5 lite"},
               "scope_trace": {"busy_s": 1.0, "scoped": True,
                               "scope_s": scope_s}})


def test_the_new_metrics_on_a_hand_made_run():
    rows = 2 * 256 * 128 * 8 * 8
    counters = {"batches": 2, "scored": 512, "token_slots": 2 * 256 * 128,
                "expert_rows": rows, "expert_peak_rows": int(1.25 * rows)}
    scope_s = {"text": 0.9}
    for i in range(8):
        scope_s.update({
            f"text/layer{i}/experts": 0.08,
            f"text/layer{i}/experts/matmul": 0.05,
            f"text/layer{i}/experts/dispatch": 0.01,
            f"text/layer{i}/experts/combine": 0.02,
            f"text/layer{i}/router": 0.004,
            f"text/layer{i}/attn_core": 0.003})
    run = _fake_run(scope_s, counters)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("expert_ffn_ms_per_batch") == pytest.approx(320.0)
    assert metric("expert_matmul_ms_per_batch") == pytest.approx(200.0)
    assert metric("router_ms_per_batch") == pytest.approx(16.0)
    assert metric("attn_core_ms_per_batch") == pytest.approx(12.0)
    assert metric("expert_imbalance_x") == pytest.approx(1.25)
    flops = 3 * 2 * rows * 2048 * 1024
    assert metric("expert_ffn_roofline_pct") == pytest.approx(
        100 * flops / 197e12 / 0.4)
    needs = spec.kernel("router").work(counters, OLMOE_CFG)["hbm_bytes"]
    assert metric("router_roofline_pct") == pytest.approx(
        100 * needs / 819e9 / 0.032)
    # against a program without the scopes and counters (the parent of the
    # PR that added them) every new metric is left out, none raises
    old = _fake_run({"text": 0.9, "text/layer0/ffn": 0.1},
                    {"batches": 2, "scored": 512, "token_slots": 65536})
    for name in ("expert_ffn_ms_per_batch", "expert_matmul_ms_per_batch",
                 "expert_ffn_roofline_pct", "router_ms_per_batch",
                 "router_roofline_pct", "expert_imbalance_x"):
        assert spec.reader_for(name, "per_layer")(old) is None, name


# ------------------------------------------------ a program without the module
def test_the_builder_stops_at_once_on_a_program_without_the_encoder(
        monkeypatch):
    import importlib.util

    find = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith("models.olmoe")
        else find(name, *a))
    with pytest.raises(SystemExit, match="models/olmoe.py"):
        spec.builder(OLMOE_CFG)


def test_the_parent_exits_non_zero_within_seconds(tmp_path):
    """A checkout of the benchmark without the program's new module — what
    the driver's parent run of the new cell is — prints no result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    pkg = tmp_path / "realtime_fraud_detection_tpu"
    (pkg / "models").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "models" / "__init__.py").write_text("")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", OLMOE_CELL,
         "--seed", "2600000001", "--seconds", "20", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "models/olmoe.py" in proc.stderr and not proc.stdout.strip()


# --------------------------------------------------------- the cell, at TINY
@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(tmp_path_factory.mktemp("bench_seam"))
    # the rehearsal sizes a mix it does not know for 300 txn/s; a backlog
    # has to outlast the window whatever this CPU completes
    traffic = copy / "benchmarks" / "traffic" / "s128-memo-saturated.json"
    tr = json.loads(traffic.read_text())
    tr["rate_txn_per_s"] = 2000
    traffic.write_text(json.dumps(tr))
    return copy


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_rehearsal_of_the_olmoe_cell(tiny_copy, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/tests/rehearsal.py"),
         str(tiny_copy), "--workload", OLMOE_CELL, "--seed", "2600000019",
         "--seconds", "3", "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600, cwd=tiny_copy)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["count"] == 1
    assert "check zero compilations inside the window: ok" in proc.stdout
    assert "check the backlog outlasted the window: ok" in proc.stdout
    if trace:
        # counters are read on any backend; device scopes need the chip
        assert out["metrics"]["expert_imbalance_x"]["value"] >= 1.0
        assert 0 < out["metrics"]["token_padding_pct"]["value"] < 100
        assert "expert_ffn_ms_per_batch" not in out["metrics"]
    else:
        assert set(out["metrics"]) == {"txn_per_s", "setup_s"}
