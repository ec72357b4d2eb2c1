"""The seam between the program and ``benchmarks/``, in tier-1: the fast
tests of ``benchmarks/tests`` (run by hand, not by the driver) copied here,
so that a program PR that moves something a builder, a kernel file or a
reader depends on fails the suite, not the next chip run.

What is held: every configuration file runs its source's published sizes
but for what it lists as ``reduced``; each builder turns its file into the
program's config class; the kernels' operation and byte counts; the readers
on a hand-made run; a builder that needs a module the program lacks stops at
once; and a TINY CPU rehearsal of the routed encoders' cells end to end
(``benchmarks/tests/rehearsal.py``: the chip's code on the CPU, DATA files
alone shrunk). No test reports a device number.
"""

import json
import math
import os
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "benchmarks" / "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

import rehearsal  # noqa: E402  (benchmarks/tests/rehearsal.py)
from benchmarks.harness import spec  # noqa: E402
# PR 36's readers on hand-made runs: collected here as they stand there
# PR 46's reference against the program, its lowering seam and its three
# compilations: collected here as they stand there
from test_falconh1_reference import (  # noqa: E402,F401
    test_text_branch_is_the_programs_at_float32,
    test_the_lowering_seam_reaches_every_matmul_and_the_scan,
    test_the_reference_imports_nothing_from_the_package,
    test_the_reference_refuses_what_its_equations_do_not_hold,
    test_the_text_column_costs_three_compilations,
)
# PR 50's reference against the program, its lowering seam by site and its
# four compilations, and its control at TINY: collected here as they stand
from test_nemotron3_control import (  # noqa: E402,F401
    nemotron3_cell,
    test_nemotron3_float8_operands_read_further_than_the_program,
    test_nemotron3_sound_only_and_reference_only_leave_their_halves_out,
)
from test_nemotron3_reference import (  # noqa: E402,F401
    test_nemotron3_lowering_seam_reaches_the_site_it_is_told,
    test_nemotron3_reference_imports_nothing_from_the_package,
    test_nemotron3_reference_refuses_what_its_equations_do_not_hold,
    test_nemotron3_score_composes_the_branches_and_reads_the_file,
    test_nemotron3_text_branch_is_the_programs_at_float32,
    test_nemotron3_text_column_costs_four_compilations,
)
# PR 54's reference against the program, its lowering seam by site and its
# four compilations, and its control at TINY: collected here as they stand
from test_qwen3next_control import (  # noqa: E402,F401
    qwen3next_cell,
    test_qwen3next_float8_operands_read_further_than_the_program,
    test_qwen3next_sound_only_and_reference_only_leave_their_halves_out,
    test_qwen3next_tail_reads_each_row_and_its_last_tokens_held_mass,
)
from test_qwen3next_reference import (  # noqa: E402,F401
    test_qwen3next_lowering_seam_reaches_the_site_it_is_told,
    test_qwen3next_reference_imports_nothing_from_the_package,
    test_qwen3next_reference_refuses_what_its_equations_do_not_hold,
    test_qwen3next_score_composes_the_branches_and_reads_the_file,
    test_qwen3next_text_branch_is_the_programs_at_float32,
    test_qwen3next_text_column_costs_four_compilations,
)
# PR 56's reader of a scope's parts on hand-made events, and its ten metric
# files against the program's names: collected here as they stand there
from test_scope_parts import (  # noqa: E402,F401
    PART_METRICS,
    test_a_parts_seconds_and_the_parent_unchanged,
    test_a_trace_without_parts_reads_none_never_zero,
    test_every_part_metric_names_a_part_the_program_writes,
)
from test_setup_metrics import (  # noqa: E402,F401
    test_a_ledger_that_let_records_go_reads_none,
    test_a_program_without_the_ledger_reads_none,
    test_setup_records_are_counted_and_the_windows_are_not,
    test_the_scope_and_counter_metrics_on_a_hand_made_run,
    test_two_threads_compiling_at_once_are_counted_once,
)

BM = spec.benchmark()
ALL = rehearsal.with_parked()
OLMOE_CELL = "olmoe-s128-memo-saturated"
OLMOE_CFG = json.loads(
    (ROOT / "benchmarks/configs/olmoe-1b-7b-s128.json").read_text())
# PR 30: ZAYA1's cell on the same traffic, and OLMoE's every-slot control
ZAYA_CELL = "zaya1-s128-memo-saturated"
FULL_CELL = "olmoe-s128-fullwindow-saturated"
ZAYA_CFG = json.loads(
    (ROOT / "benchmarks/configs/zaya1-8b-s128.json").read_text())
# PR 33: Laguna's cell, and ZAYA1's every-slot control on OLMoE's mix
LAGUNA_CELL = "laguna-s2048-remit-saturated"
ZAYA_FULL_CELL = "zaya1-s128-fullwindow-saturated"
LAGUNA_CFG = json.loads(
    (ROOT / "benchmarks/configs/laguna-s-2.1-s2048.json").read_text())
# PR 43: JoyAI-LLM-Flash's cell, on Laguna's traffic file
JOYAI_CELL = "joyai-s2048-remit-saturated"
JOYAI_CFG = json.loads(
    (ROOT / "benchmarks/configs/joyai-llm-flash-s2048.json").read_text())
ROUTED_CELLS = [OLMOE_CELL, ZAYA_CELL, FULL_CELL, LAGUNA_CELL,
                ZAYA_FULL_CELL, JOYAI_CELL]
# PR 46: Falcon-H1's cell, a causal DENSE encoder with a state-space mixer,
# on Laguna's and JoyAI's traffic file
FALCON_CELL = "falconh1-s2048-remit-saturated"
FALCON_CFG = json.loads(
    (ROOT / "benchmarks/configs/falcon-h1-34b-s2048.json").read_text())
# PR 50: Nemotron-3-Nano's cell, layers of ONE mixer each (routed AND
# state-space), on the same traffic file
NEMOTRON_CELL = "nemotron3-s2048-remit-saturated"
NEMOTRON_CFG = json.loads(
    (ROOT / "benchmarks/configs/nemotron-3-nano-30b-s2048.json").read_text())
NEMOTRON_ONLY = ["nemotron3_ssd_scan_roofline_pct",
                 "nemotron3_expert_ffn_roofline_pct",
                 "nemotron3_attn_core_roofline_pct"]
# PR 54: Qwen3-Next's cell, delta-rule layers to one gated attention layer,
# every layer routed over a SHARE of its experts, on the same traffic file
QWEN_CELL = "qwen3next-s2048-remit-saturated"
QWEN_CFG = json.loads(
    (ROOT / "benchmarks/configs/qwen3-next-80b-a3b-s2048.json").read_text())
QWEN_ONLY = ["delta_proj_ms_per_batch", "delta_conv_ms_per_batch",
             "delta_scan_ms_per_batch", "qwen3next_delta_scan_roofline_pct",
             "qwen3next_attn_core_roofline_pct",
             "qwen3next_expert_ffn_roofline_pct"]
# PR 53: the routed experts' way out, in the seven routed cells — a scope's
# time and a counter's share, each a data file over a reader the benchmark
# had
DISPATCH_METRICS = {
    "expert_dispatch_ms_per_batch": (
        "ms", "lower", "device_trace",
        {"reader": "scope_time_per_batch",
         "args": {"scopes": ["text/layer*/experts/dispatch"]}}),
    "dispatch_kernel_pct": (
        "%", "higher", "program_counter",
        {"reader": "counter_share",
         "args": {"num": "dispatch_kernel_rows", "den": "dispatch_rows"}})}


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
        if key == "max_batch" and "max_batch" in cfg.get("job_from", ""):
            # a departure the file states with its reason (2,048-token rows)
            assert value in (1, 8, 32, 128, 256)
        elif key not in ("device_pool", "inflight_depth"):
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


def test_the_zaya1_file_is_the_sources_config_cut_in_depth_only():
    from realtime_fraud_detection_tpu.models.zaya import ZayaConfig

    builder = spec.builder(ZAYA_CFG)
    built = builder.zaya_config(ZAYA_CFG)
    assert ZAYA_CFG["reduced"] == ["num_hidden_layers"]
    assert built == ZayaConfig(num_hidden_layers=24)    # defaults: published
    assert ZAYA_CFG["published"]["num_hidden_layers"] == 40
    # the source's nested groups are copied whole; every layer is hybrid
    assert ZAYA_CFG["layer_types"] == ["hybrid"] * 40
    assert ZAYA_CFG["rope_parameters"]["hybrid"]["rope_theta"] == 5000000
    assert built.rope_theta == 5000000 and built.rotary_dim == 64
    assert ZAYA_CFG["text_len"] == 128 and ZAYA_CFG["chips"] == 1
    for key in ("cut", "deployment", "assumed", "compute_dtype", "guarantee",
                "parity_atol_from"):
        assert ZAYA_CFG[key] and "TO BE WRITTEN" not in json.dumps(
            ZAYA_CFG[key]), key
    tiny = builder.zaya_config({**ZAYA_CFG, **builder.TINY})
    assert tiny.hidden_size < 512
    # the head layout and the routing stay the published ones at TINY
    assert (tiny.num_attention_heads, tiny.num_key_value_heads,
            tiny.num_experts, tiny.num_experts_per_tok) == (8, 2, 16, 1)
    with pytest.raises(ValueError, match="hybrid"):
        builder.zaya_config({**ZAYA_CFG, "layer_types": ["sliding"] * 40})


def test_the_laguna_file_is_the_sources_config_cut_in_depth_and_share():
    from realtime_fraud_detection_tpu.models.laguna import (
        FULL,
        SLIDING,
        LagunaConfig,
    )

    builder = spec.builder(LAGUNA_CFG)
    built = builder.laguna_config(LAGUNA_CFG)
    assert LAGUNA_CFG["reduced"] == ["num_hidden_layers", "num_experts"]
    published = LagunaConfig()                 # defaults: the source's
    assert built == LagunaConfig(
        num_hidden_layers=5, layer_types=published.layer_types[:5],
        mlp_layer_types=published.mlp_layer_types[:5],
        num_attention_heads_per_layer=(48, 72, 72, 72, 48), num_experts=64)
    assert built.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert LAGUNA_CFG["published"]["num_hidden_layers"] == 48
    assert LAGUNA_CFG["published"]["num_experts"] == 256 \
        == built.router_experts
    assert LAGUNA_CFG["expert_share"] == {"chips": 4, "index": 0}
    assert (built.num_experts, built.expert_offset) == (64, 0)
    # the source's two names the routed-encoder seam reads its own way
    assert LAGUNA_CFG["intermediate_size"] == 12288 \
        == built.dense_intermediate_size
    assert built.intermediate_size == LAGUNA_CFG["moe_intermediate_size"]
    # the source's lists and nested groups are copied whole
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert len(LAGUNA_CFG[key]) == 48, key
    assert LAGUNA_CFG["rope_parameters"]["full_attention"]["factor"] == 128
    assert LAGUNA_CFG["text_len"] == 2048 and LAGUNA_CFG["chips"] == 1
    assert LAGUNA_CFG["job"]["max_batch"] == 8
    for key in ("cut", "deployment", "assumed", "compute_dtype", "guarantee",
                "parity_atol_from", "job_from"):
        assert LAGUNA_CFG[key] and "TO BE WRITTEN" not in json.dumps(
            LAGUNA_CFG[key]), key
    for item in ("equations", "head", "weights", "tokenizer", "traffic"):
        assert LAGUNA_CFG["assumed"][item], item
    tiny = builder.laguna_config({**LAGUNA_CFG, **builder.TINY})
    assert tiny.hidden_size < 512 and tiny.sliding_window == 32
    assert (tiny.num_experts, tiny.router_experts) == (8, 32)
    with pytest.raises(ValueError, match="per-head"):
        builder.laguna_config({**LAGUNA_CFG, "gating": "per-channel"})
    with pytest.raises(ValueError, match="mlp_only_layers"):
        builder.laguna_config({**LAGUNA_CFG, "mlp_only_layers": [0, 1]})


def test_the_joyai_file_is_the_sources_config_cut_in_depth_only():
    from realtime_fraud_detection_tpu.models.joyai import JoyaiConfig

    builder = spec.builder(JOYAI_CFG)
    built = builder.joyai_config(JOYAI_CFG)
    assert JOYAI_CFG["reduced"] == ["num_hidden_layers"]
    assert built == JoyaiConfig(num_hidden_layers=5)    # defaults: published
    assert JOYAI_CFG["published"]["num_hidden_layers"] == 40
    # every key of the catalog row's config, under its own name, at its
    # published value but for the depth
    row = json.loads([
        line for line in Path(
            "/opt/skills/guides/model-configs/architectures.jsonl"
        ).read_text().splitlines() if '"JoyAI-LLM-Flash"' in line][0]) \
        if Path("/opt/skills/guides/model-configs/architectures.jsonl"
                ).is_file() else {"config": JOYAI_CFG["published"],
                                  "source_url": JOYAI_CFG["source"]}
    assert JOYAI_CFG["source"] == row["source_url"]
    assert JOYAI_CFG["published"] == row["config"]
    for key, value in row["config"].items():
        assert JOYAI_CFG[key] == (5 if key == "num_hidden_layers" else value)
    # all 256 experts held, top-8, every width as published
    assert (built.num_experts, built.num_experts_per_tok,
            built.num_sparse_layers, built.intermediate_size) == (
        256, 8, 4, 768)
    assert (built.hidden_size, built.q_lora_rank, built.kv_lora_rank,
            built.qk_head_dim, built.v_head_dim, built.qk_rope_head_dim,
            built.dense_intermediate_size, built.vocab_size) == (
        2048, 1536, 512, 192, 128, 64, 7168, 129280)
    assert "expert_share" not in JOYAI_CFG and JOYAI_CFG["ep_size"] == 1
    assert JOYAI_CFG["text_len"] == 2048 and JOYAI_CFG["chips"] == 1
    assert JOYAI_CFG["job"]["max_batch"] == 8 and JOYAI_CFG["parity_rows"] == 4
    for key in ("cut", "deployment", "not_run", "assumed", "compute_dtype",
                "guarantee", "parity_atol_from", "job_from"):
        assert JOYAI_CFG[key] and "TO BE WRITTEN" not in json.dumps(
            JOYAI_CFG[key]), key
    assert "eight pipeline stages of five layers" in JOYAI_CFG[
        "deployment"].lower()
    assert set(JOYAI_CFG["not_run"]) == {"num_nextn_predict_layers"}
    for item in ("equations", "head", "weights", "tokenizer", "traffic",
                 "routing_at_random_weights"):
        assert JOYAI_CFG["assumed"][item], item
    # the traffic file is Laguna's, byte for byte: one file, two cells
    assert spec.cell(JOYAI_CELL)["traffic"] \
        == spec.cell(LAGUNA_CELL)["traffic"] == "s2048-remit-saturated"
    tiny = builder.joyai_config({**JOYAI_CFG, **builder.TINY})
    assert tiny.hidden_size < 512 and tiny.num_experts == 16
    # the head keeps three unlike widths at TINY
    assert (tiny.qk_nope_head_dim, tiny.qk_rope_head_dim, tiny.v_head_dim,
            tiny.qk_head_dim) == (32, 16, 32, 48)
    with pytest.raises(ValueError, match="ep_size"):
        builder.joyai_config({**JOYAI_CFG, "ep_size": 8})
    with pytest.raises(ValueError, match="qk_head_dim"):
        builder.joyai_config({**JOYAI_CFG, "qk_head_dim": 128})
    with pytest.raises(ValueError, match="group-limited"):
        builder.joyai_config({**JOYAI_CFG, "n_group": 8, "topk_group": 4})


def test_the_falconh1_file_is_the_sources_config_cut_in_depth_only():
    from realtime_fraud_detection_tpu.models.falcon_h1 import FalconH1Config

    builder = spec.builder(FALCON_CFG)
    built = builder.falconh1_config(FALCON_CFG)
    assert FALCON_CFG["reduced"] == ["num_hidden_layers"]
    assert built == FalconH1Config(num_hidden_layers=6)  # defaults: published
    assert FALCON_CFG["published"]["num_hidden_layers"] == 72
    # every key of the catalog row's config, under its own name, at its
    # published value but for the depth — in the file AND in the class
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    row = json.loads([
        line for line in catalog.read_text().splitlines()
        if '"Falcon-H1-34B-Instruct"' in line][0]) if catalog.is_file() \
        else {"config": FALCON_CFG["published"],
              "source_url": FALCON_CFG["source"]}
    assert FALCON_CFG["source"] == row["source_url"]
    assert FALCON_CFG["published"] == row["config"]
    for key, value in row["config"].items():
        want = 6 if key == "num_hidden_layers" else value
        assert FALCON_CFG[key] == want, key
        held = getattr(built, key)
        assert (list(held) if isinstance(value, list) else held) == want, key
    # every width as published
    assert (built.hidden_size, built.intermediate_size, built.head_dim,
            built.num_attention_heads, built.num_key_value_heads,
            built.vocab_size) == (5120, 21504, 128, 20, 4, 261120)
    assert (built.mamba_d_ssm, built.mamba_n_heads, built.mamba_d_head,
            built.mamba_d_state, built.mamba_n_groups, built.mamba_d_conv,
            built.mamba_chunk_size, built.conv_dim, built.in_proj_dim) == (
        4096, 32, 128, 256, 2, 4, 128, 5120, 9248)
    assert built.core_refusal(2048) is None is built.scan_refusal(2048)
    assert FALCON_CFG["text_len"] == 2048 and FALCON_CFG["chips"] == 1
    assert FALCON_CFG["job"]["max_batch"] == 8
    assert FALCON_CFG["parity_rows"] == 4
    for key in ("cut", "deployment", "not_run", "assumed", "compute_dtype",
                "guarantee", "parity_atol_from", "job_from"):
        assert FALCON_CFG[key] and "TO BE WRITTEN" not in json.dumps(
            FALCON_CFG[key]), key
    assert "twelve pipeline stages of six layers" in FALCON_CFG[
        "deployment"].lower()
    assert set(FALCON_CFG["not_run"]) == {
        "lm_head", "lm_head_multiplier", "num_logits_to_keep"}
    for item in ("equations", "head", "weights", "tokenizer", "traffic"):
        assert FALCON_CFG["assumed"][item], item
    assert "none under a tenth" in FALCON_CFG["assumed"]["weights"]
    # the traffic file is Laguna's and JoyAI's, byte for byte: one file
    assert spec.cell(FALCON_CELL)["traffic"] \
        == spec.cell(LAGUNA_CELL)["traffic"] \
        == spec.cell(JOYAI_CELL)["traffic"] == "s2048-remit-saturated"
    tiny = builder.falconh1_config({**FALCON_CFG, **builder.TINY})
    # two groups, more heads than groups, five query heads a key-value
    # head, and four chunks in a rehearsal's 128 positions
    assert tiny.hidden_size < 512 and tiny.mamba_n_groups == 2
    assert tiny.mamba_n_heads > tiny.mamba_n_groups
    assert tiny.num_attention_heads // tiny.num_key_value_heads == 5
    assert 128 // tiny.mamba_chunk_size == 4
    with pytest.raises(ValueError, match="attention_bias"):
        builder.falconh1_config({**FALCON_CFG, "attention_bias": True})
    with pytest.raises(ValueError, match="tied embeddings"):
        builder.falconh1_config({**FALCON_CFG, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        builder.falconh1_config(
            {**FALCON_CFG, "mamba_norm_before_gate": True})


def test_the_nemotron3_file_is_the_sources_config_cut_in_depth_only():
    from realtime_fraud_detection_tpu.models.nemotron_h import (
        PUBLISHED_PATTERN,
        NemotronHConfig,
    )

    builder = spec.builder(NEMOTRON_CFG)
    built = builder.nemotron3_config(NEMOTRON_CFG)
    assert NEMOTRON_CFG["reduced"] == ["num_hidden_layers",
                                       "hybrid_override_pattern"]
    # the class's defaults are the published values: the cell runs the
    # first nine characters of the published pattern and nothing else cut
    assert built == NemotronHConfig(num_hidden_layers=9,
                                    hybrid_override_pattern="MEMEM*EME")
    assert NEMOTRON_CFG["published"]["num_hidden_layers"] == 52
    assert NEMOTRON_CFG["published"]["hybrid_override_pattern"] \
        == PUBLISHED_PATTERN
    assert PUBLISHED_PATTERN.startswith(built.hybrid_override_pattern)
    assert (built.num_ssm_layers, built.num_sparse_layers,
            built.layer_kinds.count("*")) == (4, 4, 1)
    # every key of the catalog row's config, under its own name, at its
    # published value but for the two of the cut — in the file AND the class
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    row = json.loads([
        line for line in catalog.read_text().splitlines()
        if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line][0]) \
        if catalog.is_file() else {"config": NEMOTRON_CFG["published"],
                                   "source_url": NEMOTRON_CFG["source"]}
    assert NEMOTRON_CFG["source"] == row["source_url"]
    assert NEMOTRON_CFG["published"] == row["config"]
    cut = {"num_hidden_layers": 9, "hybrid_override_pattern": "MEMEM*EME"}
    for key, value in row["config"].items():
        want = cut.get(key, value)
        assert NEMOTRON_CFG[key] == want, key
        assert getattr(built, key) == want, key
    # every width as published
    assert (built.hidden_size, built.head_dim, built.num_attention_heads,
            built.num_key_value_heads, built.vocab_size) == (
        2688, 128, 32, 2, 131072)
    assert (built.mamba_num_heads, built.mamba_head_dim, built.d_inner,
            built.ssm_state_size, built.n_groups, built.conv_kernel,
            built.chunk_size, built.conv_dim, built.in_proj_dim) == (
        64, 64, 4096, 128, 8, 4, 128, 6144, 10304)
    assert (built.n_routed_experts, built.num_experts_per_tok,
            built.moe_intermediate_size, built.routed_scaling_factor,
            built.moe_shared_expert_intermediate_size) == (
        128, 6, 1856, 2.5, 3712)
    assert built.core_refusal(2048) is None is built.scan_refusal(2048)
    assert NEMOTRON_CFG["text_len"] == 2048 and NEMOTRON_CFG["chips"] == 1
    assert NEMOTRON_CFG["job"]["max_batch"] == 8
    assert NEMOTRON_CFG["parity_rows"] == 4
    for key in ("cut", "deployment", "not_run", "assumed", "compute_dtype",
                "guarantee", "parity_atol_from", "job_from"):
        assert NEMOTRON_CFG[key] and "TO BE WRITTEN" not in json.dumps(
            NEMOTRON_CFG[key]), key
    assert "six pipeline stages" in NEMOTRON_CFG["deployment"].lower()
    assert set(NEMOTRON_CFG["not_run"]) == {
        "lm_head", "num_logits_to_keep", "rope_theta",
        "partial_rotary_factor", "use_mamba_kernels",
        "rescale_prenorm_residual", "time_step_min", "time_step_max",
        "time_step_floor"}
    for item in ("equations", "head", "weights", "tokenizer", "traffic"):
        assert NEMOTRON_CFG["assumed"][item], item
    assert "ASSUMED" in NEMOTRON_CFG["assumed"]["equations"]
    assert "residual" in NEMOTRON_CFG["compute_dtype"]["text_branch"]
    # the traffic file is Laguna's, JoyAI's and Falcon-H1's, byte for byte
    assert spec.cell(NEMOTRON_CELL)["traffic"] \
        == spec.cell(FALCON_CELL)["traffic"] == "s2048-remit-saturated"
    tiny = builder.nemotron3_config({**NEMOTRON_CFG, **builder.TINY})
    # the odd shapes stay odd: heads of 64 in groups of 8, an expert width
    # and a hidden size of no whole lane or sublane tiles, sixteen query
    # heads a key-value head, four chunks in a rehearsal's 128 positions
    assert tiny.hidden_size < 512 and tiny.hidden_size // 128 % 8
    assert tiny.mamba_head_dim == 64
    assert tiny.mamba_num_heads // tiny.n_groups == 8
    assert tiny.moe_intermediate_size % 128 and tiny.moe_intermediate_size \
        % 16 == 0
    assert tiny.num_attention_heads // tiny.num_key_value_heads == 16
    assert 128 // tiny.chunk_size == 4
    with pytest.raises(ValueError, match="attention_bias"):
        builder.nemotron3_config({**NEMOTRON_CFG, "attention_bias": True})
    with pytest.raises(ValueError, match="tied embeddings"):
        builder.nemotron3_config(
            {**NEMOTRON_CFG, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="dense MLP layer"):
        builder.nemotron3_config(
            {**NEMOTRON_CFG, "hybrid_override_pattern": "MEMEM-EME"})


def _reported(cell, kind="per_layer"):
    """The names ``cell`` reports, less the ten of PR 56 (``_before_pr56``
    holds those): what the asserts on the older PRs' additions count."""
    return {m["name"] for m in spec.metrics_for(cell, kind)} - set(
        PART_METRICS)


def _before_pr56():
    """``BENCHMARK.json`` with what PR 56 appended checked BY NAME and taken
    off: ten metrics at the end of ``per_layer``, each a part of a scope
    whose own metric the benchmark had, listing the cells that metric lists
    in which the program writes the part — no configuration, no cell, no
    name added to any list that was there. What is left is the benchmark
    PR 54 left."""
    bm = json.loads(json.dumps(BM))
    for name in reversed(list(PART_METRICS)):
        _scope, _part, cells = PART_METRICS[name]
        assert bm["per_layer"].pop() == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "txn_per_s", "workloads": cells}
    assert not set(PART_METRICS) & {
        m["name"] for m in bm["per_layer"] + bm["end_to_end"]}
    return bm


def test_pr56_appended_ten_part_metrics_and_nothing_else():
    bm = _before_pr56()
    assert len(BM["per_layer"]) - len(bm["per_layer"]) == 10
    assert [w["name"] for w in bm["workloads"]] == [
        w["name"] for w in BM["workloads"]]
    assert bm["configs"] == BM["configs"]
    assert bm["end_to_end"] == BM["end_to_end"]
    # each cell reports the parts of the scopes its program cuts, no other
    parts_of = {
        FALCON_CELL: {"ssm_in_proj", "ssm_gate_norm", "ssm_out_proj",
                      "falconh1_ffn_gate", "falconh1_ffn_up",
                      "falconh1_ffn_down"},
        NEMOTRON_CELL: {"ssm_in_proj", "ssm_gate_norm", "ssm_out_proj",
                        "router_choose", "router_order"},
        QWEN_CELL: {"delta_qk_norm", "delta_gates", "router_choose",
                    "router_order"},
        **{cell: {"router_choose", "router_order"}
           for cell in ROUTED_CELLS},
        "s512-fulltext-saturated": set()}
    assert set(parts_of) == {w["name"] for w in BM["workloads"]}
    for cell, parts in parts_of.items():
        assert {m["name"] for m in spec.metrics_for(cell, "per_layer")} & set(
            PART_METRICS) == {f"{p}_ms_per_batch" for p in parts}, cell


def _before_pr54():
    """``BENCHMARK.json`` with what PR 54 appended checked BY NAME and taken
    off: one configuration, one cell, the cell's name at the end of the
    ``workloads`` list of every metric it reports, and six metrics of its
    own at the end of ``per_layer``. What is left is the benchmark PR 53
    left."""
    bm = _before_pr56()
    own = [bm["per_layer"].pop() for _ in QWEN_ONLY][::-1]
    assert [m["name"] for m in own] == QWEN_ONLY
    for m in own:
        assert m == {"name": m["name"],
                     "unit": "%" if m["name"].endswith("_pct") else "ms",
                     "better": "higher" if m["name"].endswith("_pct")
                     else "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "txn_per_s", "workloads": [QWEN_CELL]}
    assert bm["configs"].pop() == {
        "name": "qwen3-next-80b-a3b-s2048", "source": QWEN_CFG["source"],
        "file": "benchmarks/configs/qwen3-next-80b-a3b-s2048.json",
        "reduced": ["num_hidden_layers", "num_experts"],
        "why": BM["configs"][-1]["why"]}
    assert bm["workloads"].pop() == {
        "name": QWEN_CELL, "config": "qwen3-next-80b-a3b-s2048",
        "traffic": "s2048-remit-saturated", "chips": 1,
        "why": BM["workloads"][-1]["why"]}
    assert len(BM["workloads"][-1]["why"]) <= 200 \
        and len(BM["configs"][-1]["why"]) <= 200
    listed = set()
    for m in bm["per_layer"] + bm["end_to_end"]:
        if QWEN_CELL in m.get("workloads", ()):
            assert m["workloads"].pop() == QWEN_CELL, m["name"]
            assert QWEN_CELL not in m["workloads"]
            listed.add(m["name"])
    assert listed | set(QWEN_ONLY) | {"setup_s"} == _reported(
        QWEN_CELL) | _reported(QWEN_CELL, "end_to_end")
    return bm, listed


def test_pr54_appended_one_cell_six_metrics_and_its_name_to_lists():
    bm, qwen_lists = _before_pr54()
    # routed over a share of the experts beside a shared one: what every
    # cell reports, the routed cells' shared names, the shared expert's
    # time and the share's; none of the state-space mixer's, no dense MLP's
    every = {m["name"] for m in BM["per_layer"]
             if len(m["workloads"]) == len(BM["workloads"])}
    assert qwen_lists == every | {"txn_per_s"} | {
        "expert_ffn_ms_per_batch", "expert_matmul_ms_per_batch",
        "expert_combine_ms_per_batch", "expert_dispatch_ms_per_batch",
        "router_ms_per_batch", "shared_expert_ms_per_batch",
        "expert_imbalance_x", "expert_tile_fill_pct", "compact_batches_pct",
        "dispatch_kernel_pct", "expert_local_share_pct"}
    assert len(bm["workloads"]) == len(BM["workloads"]) - 1 == 9
    assert [w["name"] for w in bm["workloads"]][-1] == NEMOTRON_CELL
    assert not [w for w in BM["workloads"] if w["chips"] != 1]
    # the traffic file is the four other 2,048-token cells', unedited
    assert spec.cell(QWEN_CELL)["traffic"] == spec.cell(LAGUNA_CELL)[
        "traffic"] == "s2048-remit-saturated"
    metrics = ROOT / "benchmarks/layer_metrics"
    for name, scope in (("delta_proj_ms_per_batch", "delta_proj"),
                        ("delta_conv_ms_per_batch", "delta_conv"),
                        ("delta_scan_ms_per_batch", "delta_scan")):
        assert json.loads((metrics / f"{name}.json").read_text()) == {
            "reader": "scope_time_per_batch",
            "args": {"scopes": [f"text/layer*/{scope}"]}}
    for name, scope, peak in (
            ("qwen3next_delta_scan_roofline_pct", "delta_scan",
             {"peak": "hbm_bytes_per_s"}),
            ("qwen3next_attn_core_roofline_pct", "attn_core", {}),
            ("qwen3next_expert_ffn_roofline_pct", "experts/matmul",
             {"peak": "hbm_bytes_per_s"})):
        assert json.loads((metrics / f"{name}.json").read_text()) == {
            "reader": "scope_roofline",
            "args": {"scope": f"text/layer*/{scope}",
                     "kernel": name[:-len("_roofline_pct")], **peak}}


def test_the_qwen3next_file_is_the_sources_config_cut_in_depth_and_share():
    from realtime_fraud_detection_tpu.models.qwen3_next import (
        Qwen3NextConfig,
    )

    builder = spec.builder(QWEN_CFG)
    built = builder.qwen3next_config(QWEN_CFG)
    assert QWEN_CFG["reduced"] == ["num_hidden_layers", "num_experts"]
    # the class's defaults are the published values: the cell runs six
    # layers and holds half of each layer's experts, and nothing else cut
    assert built == Qwen3NextConfig(num_hidden_layers=6, num_experts=256)
    assert (built.router_experts, built.expert_offset) == (512, 0)
    assert QWEN_CFG["published"]["num_hidden_layers"] == 48
    assert QWEN_CFG["published"]["num_experts"] == 512
    assert QWEN_CFG["expert_share"] == {"chips": 2, "index": 0}
    assert "".join(built.layer_kinds) == builder.layer_kinds(QWEN_CFG) \
        == "LLLFLL"
    # every key of the catalog row's config, under its own name, at its
    # published value but for the two of the cut — in the file AND the class
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    row = json.loads([
        line for line in catalog.read_text().splitlines()
        if '"Qwen3-Next-80B-A3B-Instruct"' in line][0]) \
        if catalog.is_file() else {"config": QWEN_CFG["published"],
                                   "source_url": QWEN_CFG["source"]}
    assert QWEN_CFG["source"] == row["source_url"]
    assert QWEN_CFG["published"] == row["config"]
    cut = {"num_hidden_layers": 6, "num_experts": 256}
    for key, value in row["config"].items():
        want = cut.get(key, value)
        assert QWEN_CFG[key] == want, key
        got = getattr(built, key)
        assert got == (tuple(want) if isinstance(want, list) else want), key
    # every width as published
    assert (built.hidden_size, built.head_dim, built.num_attention_heads,
            built.num_key_value_heads, built.vocab_size) == (
        2048, 256, 16, 2, 151936)
    assert (built.linear_num_key_heads, built.linear_num_value_heads,
            built.linear_key_head_dim, built.linear_value_head_dim,
            built.linear_conv_kernel_dim, built.conv_dim, built.rotary_dim,
            built.delta_chunk) == (16, 32, 128, 128, 4, 8192, 64, 64)
    assert (built.num_experts_per_tok, built.moe_intermediate_size,
            built.shared_expert_intermediate_size) == (10, 512, 512)
    assert built.core_refusal(2048) is None is built.scan_refusal(2048)
    assert QWEN_CFG["text_len"] == 2048 and QWEN_CFG["chips"] == 1
    assert QWEN_CFG["job"]["max_batch"] == 8
    assert QWEN_CFG["parity_rows"] == 4
    for key in ("cut", "deployment", "not_run", "assumed", "compute_dtype",
                "guarantee", "parity_atol_from", "job_from"):
        assert QWEN_CFG[key] and "PLACEHOLDER" not in json.dumps(
            QWEN_CFG[key]), key
    for words in ("sixteen chips", "eight pipeline stages", "two chips"):
        assert words in QWEN_CFG["deployment"].lower(), words
    assert "512 x 3 x 2048 x 512" in QWEN_CFG["cut"]
    assert set(QWEN_CFG["not_run"]) == {
        "lm_head", "mtp", "intermediate_size", "max_position_embeddings",
        "other_experts"}
    for item in ("equations", "head", "weights", "tokenizer", "traffic",
                 "delta_chunk"):
        assert QWEN_CFG["assumed"][item], item
    assert "ASSUMED" in QWEN_CFG["assumed"]["equations"]
    assert "residual" in QWEN_CFG["compute_dtype"]["text_branch"]
    # the byte count the file states is the init's own (AOT: the argument
    # size of the bucket's program agrees to 0.01%, tests/test_aot_tpu.py)
    import jax

    from realtime_fraud_detection_tpu.models.qwen3_next import (
        init_qwen3_next_params,
    )

    shapes = jax.eval_shape(lambda k: init_qwen3_next_params(k, built),
                            jax.random.PRNGKey(0))
    leaves = jax.tree.leaves(shapes)
    assert sum(math.prod(x.shape) for x in leaves) == 5_364_067_776
    stated = sum(math.prod(x.shape) * x.dtype.itemsize for x in leaves)
    assert stated == 10_728_527_616
    assert "5,364,067,776 parameters = 10,728,527,616 B" in QWEN_CFG["cut"]
    tiny = builder.qwen3next_config({**QWEN_CFG, **builder.TINY})
    # the ratios stay: two value heads a key head, eight query heads a
    # key-value head, a quarter of a head rotated, half the router's experts
    # held, eight chunks in a rehearsal's 128 positions
    assert tiny.hidden_size < 512
    assert tiny.linear_num_value_heads // tiny.linear_num_key_heads == 2
    assert tiny.num_attention_heads // tiny.num_key_value_heads == 8
    assert tiny.rotary_dim * 4 == tiny.head_dim
    assert tiny.router_experts == 2 * tiny.num_experts == 32
    assert 128 // tiny.delta_chunk == 8
    with pytest.raises(ValueError, match="decoder_sparse_step"):
        builder.qwen3next_config({**QWEN_CFG, "decoder_sparse_step": 2})
    with pytest.raises(ValueError, match="tied embeddings"):
        builder.qwen3next_config({**QWEN_CFG, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="are not the published 512"):
        builder.qwen3next_config({**QWEN_CFG, "num_experts": 128})


@pytest.fixture(scope="module")
def qwen3next_draw():
    """The cell's own draw — the builder's config, every width as published
    — at two layers (``LF``), eight held experts and a short vocabulary."""
    import dataclasses

    import jax

    from realtime_fraud_detection_tpu.models.qwen3_next import (
        init_qwen3_next_params,
    )

    built = spec.builder(QWEN_CFG).qwen3next_config(QWEN_CFG)
    small = dataclasses.replace(built, num_hidden_layers=2,
                                full_attention_interval=2, num_experts=8,
                                vocab_size=1024)
    params = init_qwen3_next_params(jax.random.PRNGKey(54), small)
    return built, {**params["layers"][1], **params["layers"][0]}


def test_the_qwen3next_file_names_the_draw_the_cell_is_fed(qwen3next_draw):
    """``assumed.weights`` is a description of ``init_qwen3_next_params`` as
    the builder calls it: each knob of the draw under its name and value in
    ``weights_draw`` AND in the prose, the routed experts correlated."""
    built, layer = qwen3next_draw
    assumed = QWEN_CFG["assumed"]
    assert assumed["weights_draw"] == {
        key: getattr(built, key) for key in (
            "embedding_range", "norm_range", "router_logit_rms",
            "expert_spread", "update_rms", "context_rms")}
    for quoted in ("embedding normal(1.0)", "norm_range 0.1",
                   "router_logit_rms 2 /", "logits of RMS 2",
                   "expert_spread 1/64 = 0.015625", "update_rms 0.5",
                   "context_rms 0.13", "CORRELATED"):
        assert quoted in assumed["weights"], quoted
    for stale in ("router_logit_rms 4", "PEAKED", "experts independent"):
        assert stale not in assumed["weights"], stale
    first, second = (np.asarray(layer["down_proj"][i], np.float32).ravel()
                     for i in (0, 1))
    assert np.corrcoef(first, second)[0, 1] > 0.99
    assert not np.array_equal(first, second)


@pytest.mark.parametrize("tensor,quoted", [
    ("in_proj_qkvz", "1 / sqrt(2048) = 0.0221"),
    ("out_proj", "sqrt(4096)) = 0.0130"),
    ("o_proj", "sqrt(4096)) = 0.0601"),
    ("router", "/ sqrt(2048) = 0.0442"),
    ("gate_proj", "gate and up normal(0.0221)"),
    ("down_proj", "sqrt(512)) = 0.0449"),
    ("shared_down", "sqrt(512)) = 0.0482"),
])
def test_the_qwen3next_file_quotes_the_scale_each_matrix_is_drawn_at(
        qwen3next_draw, tensor, quoted):
    text = QWEN_CFG["assumed"]["weights"]
    assert quoted in text, quoted
    stated = float(quoted.rstrip(")")[-6:])
    drawn = float(np.asarray(qwen3next_draw[1][tensor], np.float32).std())
    assert drawn == pytest.approx(stated, rel=0.02), (tensor, drawn)


def _before_pr50():
    """``BENCHMARK.json`` with what PR 50 appended checked BY NAME and taken
    off: one configuration, one cell, the cell's name at the end of the
    ``workloads`` list of every metric it reports, and three metrics of its
    own at the end of ``per_layer``. What is left is the benchmark PR 48
    left, which the asserts below count from the end of."""
    bm, _ = _before_pr54()
    # (and behind PR 50's, what PR 53 appended: two metrics, nothing else)
    for name in reversed(DISPATCH_METRICS):
        unit, better, source, data = DISPATCH_METRICS[name]
        assert bm["per_layer"].pop() == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "kernels", "moves": "txn_per_s",
            "workloads": ROUTED_CELLS + [NEMOTRON_CELL]}
        assert json.loads((ROOT / "benchmarks/layer_metrics"
                           / f"{name}.json").read_text()) == data
    assert bm["configs"].pop()["name"] == "nemotron-3-nano-30b-s2048"
    assert bm["workloads"].pop() == {
        "name": NEMOTRON_CELL, "config": "nemotron-3-nano-30b-s2048",
        "traffic": "s2048-remit-saturated", "chips": 1,
        "why": {w["name"]: w for w in BM["workloads"]}[NEMOTRON_CELL]["why"]}
    own = [bm["per_layer"].pop() for _ in NEMOTRON_ONLY][::-1]
    assert [m["name"] for m in own] == NEMOTRON_ONLY
    for m in own:
        assert m == {"name": m["name"], "unit": "%", "better": "higher",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "txn_per_s", "workloads": [NEMOTRON_CELL]}
    listed = set()
    for m in bm["per_layer"] + bm["end_to_end"]:
        if NEMOTRON_CELL in m.get("workloads", ()):
            assert m["workloads"].pop() == NEMOTRON_CELL, m["name"]
            assert NEMOTRON_CELL not in m["workloads"]
            listed.add(m["name"])
    assert listed | set(NEMOTRON_ONLY) | set(DISPATCH_METRICS) | {
            "setup_s"} == _reported(NEMOTRON_CELL) | _reported(
        NEMOTRON_CELL, "end_to_end")
    return bm, listed


def test_the_new_cells_and_their_metrics_are_appended_not_inserted():
    bm, nemotron_lists = _before_pr50()
    # routed AND state-space: what every cell reports, the routed cells'
    # shared names (with the shared expert's), and Falcon-H1's three times
    # of the mixer; no dense MLP's, no other configuration's own
    every = {m["name"] for m in BM["per_layer"]
             if len(m["workloads"]) == len(BM["workloads"])}
    assert nemotron_lists == every | {"txn_per_s"} | {
        "expert_ffn_ms_per_batch", "expert_matmul_ms_per_batch",
        "expert_combine_ms_per_batch", "router_ms_per_batch",
        "shared_expert_ms_per_batch", "expert_imbalance_x",
        "expert_tile_fill_pct", "compact_batches_pct",
        "ssm_proj_ms_per_batch", "ssm_conv_ms_per_batch",
        "ssm_scan_ms_per_batch"}
    assert {"attn_core_ms_per_batch", "attn_proj_ms_per_batch",
            "ln_ms_per_batch", "setup_programs", "hbm_peak_gb"} <= every
    cells = [w["name"] for w in bm["workloads"]]
    # the routed cells from the end: a DistilBERT cell parked or moved back
    # ahead of them (PR 42 parked longtail) shifts no index here
    assert cells[-7:] == ROUTED_CELLS + [FALCON_CELL]
    n_cells, n_routed = len(cells), len(ROUTED_CELLS)
    n_distilbert = n_cells - n_routed - 1
    assert n_distilbert == len(
        [w for w in bm["workloads"] if w["config"].startswith("distilbert")])
    by_name = {w["name"]: w for w in bm["workloads"]}
    assert (by_name[OLMOE_CELL]["config"], by_name[OLMOE_CELL]["traffic"]
            ) == ("olmoe-1b-7b-s128", "s128-memo-saturated")
    assert (by_name[ZAYA_CELL]["config"], by_name[ZAYA_CELL]["traffic"],
            by_name[ZAYA_CELL]["chips"]) == (
        "zaya1-8b-s128", "s128-memo-saturated", 1)
    assert (by_name[FULL_CELL]["config"], by_name[FULL_CELL]["traffic"],
            by_name[FULL_CELL]["chips"]) == (
        "olmoe-1b-7b-s128", "s128-fullwindow-saturated", 1)
    common = {"attn_core_ms_per_batch", "text_ms_per_batch",
              "unscoped_device_pct", "hbm_peak_gb", "expert_ffn_ms_per_batch",
              "expert_matmul_ms_per_batch", "router_ms_per_batch",
              "expert_imbalance_x", "expert_tile_fill_pct",
              "expert_combine_ms_per_batch"}
    olmoe_only = {"expert_ffn_roofline_pct", "router_roofline_pct"}
    zaya_only = {"cca_mix_ms_per_batch", "cca_mix_roofline_pct",
                 "zaya1_expert_ffn_roofline_pct", "zaya1_router_roofline_pct"}
    assert (by_name[LAGUNA_CELL]["config"], by_name[LAGUNA_CELL]["traffic"],
            by_name[LAGUNA_CELL]["chips"]) == (
        "laguna-s-2.1-s2048", "s2048-remit-saturated", 1)
    assert (by_name[ZAYA_FULL_CELL]["config"],
            by_name[ZAYA_FULL_CELL]["traffic"],
            by_name[ZAYA_FULL_CELL]["chips"]) == (
        "zaya1-8b-s128", "s128-fullwindow-saturated", 1)
    laguna_only = {"attn_core_sliding_ms_per_batch",
                   "attn_core_full_ms_per_batch",
                   "laguna_attn_core_roofline_pct",
                   "laguna_expert_ffn_roofline_pct",
                   "shared_expert_ms_per_batch", "expert_local_share_pct"}
    assert (by_name[JOYAI_CELL]["config"], by_name[JOYAI_CELL]["traffic"],
            by_name[JOYAI_CELL]["chips"]) == (
        "joyai-llm-flash-s2048", "s2048-remit-saturated", 1)
    joyai_only = {"attn_latent_ms_per_batch", "joyai_attn_core_roofline_pct",
                  "joyai_expert_ffn_roofline_pct",
                  "joyai_attn_latent_roofline_pct"}
    reports = {cell: _reported(cell) for cell in ROUTED_CELLS}
    # JoyAI's: the shared routed names, its dense layer 0's, the shared
    # expert's and its own four; neither OLMoE's, ZAYA1's nor Laguna's own
    joyai_reports = reports.pop(JOYAI_CELL)
    assert joyai_reports == (
        (reports[OLMOE_CELL] - {"expert_ffn_roofline_pct",
                                "router_roofline_pct"})
        | {"ffn_ms_per_batch", "shared_expert_ms_per_batch"} | joyai_only)
    assert common | {"compact_batches_pct"} <= joyai_reports
    assert not (olmoe_only | zaya_only | (
        laguna_only - {"shared_expert_ms_per_batch"})) & joyai_reports
    # PR 46 appended its six behind everything, one cell and one
    # configuration
    falcon_only = ["ssm_proj_ms_per_batch", "ssm_conv_ms_per_batch",
                   "ssm_scan_ms_per_batch", "falconh1_ssd_scan_roofline_pct",
                   "falconh1_attn_core_roofline_pct",
                   "falconh1_ffn_roofline_pct"]
    assert [m["name"] for m in bm["per_layer"][-8:-2]] == falcon_only
    for m in bm["per_layer"][-8:-2]:
        assert m["workloads"] == [FALCON_CELL] and m["layer"] == "kernels"
        assert m["moves"] == "txn_per_s" and m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if m["name"].endswith("roofline_pct")
            else ("ms", "lower"))
    # PR 47 appended one behind those: a counter's share of the six routed
    # cells, a data file over a reader the benchmark had
    fill = bm["per_layer"][-2]
    assert fill == {
        "name": "expert_tile_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "txn_per_s", "workloads": ROUTED_CELLS}
    assert json.loads((ROOT / "benchmarks/layer_metrics"
                       / "expert_tile_fill_pct.json").read_text()) == {
        "reader": "counter_share",
        "args": {"num": "expert_rows", "den": "expert_tile_rows"}}
    # PR 48 one more: the way home's device time in the six routed cells,
    # so that "rows moved" is a scope's time and no subtraction; a data
    # file over a reader the benchmark had, a scope the parent has too
    assert bm["per_layer"][-1] == {
        "name": "expert_combine_ms_per_batch", "unit": "ms",
        "better": "lower", "source": "device_trace", "layer": "kernels",
        "moves": "txn_per_s", "workloads": ROUTED_CELLS}
    assert json.loads((ROOT / "benchmarks/layer_metrics"
                       / "expert_combine_ms_per_batch.json").read_text()) == {
        "reader": "scope_time_per_batch",
        "args": {"scopes": ["text/layer*/experts/combine"]}}
    assert (by_name[FALCON_CELL]["config"], by_name[FALCON_CELL]["traffic"],
            by_name[FALCON_CELL]["chips"]) == (
        "falcon-h1-34b-s2048", "s2048-remit-saturated", 1)
    falcon_reports = _reported(FALCON_CELL)
    # a dense encoder: what every cell reports, the dense MLP's time, and
    # its own six; no expert's, no router's, neither launch rule's share
    every_cell = {m["name"] for m in bm["per_layer"]
                  if len(m["workloads"]) == n_cells}
    assert falcon_reports == (every_cell | {"ffn_ms_per_batch"}
                              | set(falcon_only))
    assert len(every_cell) == 20 and not {
        n for n in falcon_reports if n.startswith(("expert_", "router_"))}
    assert not {"compact_batches_pct", "split_batches_pct",
                "ffn_roofline_pct", "attn_core_roofline_pct"} & falcon_reports
    for m in bm["per_layer"] + bm["end_to_end"]:
        if FALCON_CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == FALCON_CELL, m["name"]
    assert [c["name"] for c in bm["configs"]][-2:] == [
        "joyai-llm-flash-s2048", "falcon-h1-34b-s2048"]
    for m in bm["per_layer"][-12:-8]:
        # PR 43 appended its four behind what was there
        assert m["name"] in joyai_only and m["moves"] == "txn_per_s"
        assert m["workloads"] == [JOYAI_CELL] and m["layer"] == "kernels"
    assert len(cells) == 8 and not [w for w in bm["workloads"]
                                    if w["chips"] != 1]
    per_layer = bm["per_layer"][:-12]    # the checks below: what PR 42 left
    # ZAYA1's second cell reports exactly what its first does
    assert reports[ZAYA_FULL_CELL] == reports[ZAYA_CELL]
    # Laguna's: the shared names, its dense layer 0's, and its own six
    assert reports[LAGUNA_CELL] == (
        (reports[OLMOE_CELL] - {"expert_ffn_roofline_pct",
                                "router_roofline_pct"})
        | {"ffn_ms_per_batch"} | laguna_only)
    laguna_reports = reports.pop(LAGUNA_CELL)
    assert not {"ffn_roofline_pct", "attn_core_roofline_pct"} & laguna_reports
    # PR 36 appended seven behind them: the first per-layer metrics under
    # setup_s, two scopes no metric read, and the two launch-rule counters
    assert [(m["name"], m["moves"], len(m["workloads"]))
            for m in per_layer[-7:]] == [
        ("compile_setup_s", "setup_s", n_cells),
        ("trace_lower_setup_s", "setup_s", n_cells),
        ("setup_programs", "setup_s", n_cells),
        ("attn_proj_ms_per_batch", "txn_per_s", n_cells),
        ("ln_ms_per_batch", "txn_per_s", n_cells),
        ("split_batches_pct", "txn_per_s", n_distilbert),
        ("compact_batches_pct", "txn_per_s", n_routed)]
    assert [m["name"] for m in bm["per_layer"] if m["moves"] == "setup_s"
            ] == ["compile_setup_s", "trace_lower_setup_s", "setup_programs"]
    assert "compact_batches_pct" in reports[OLMOE_CELL] \
        and "split_batches_pct" not in reports[OLMOE_CELL]
    for m in per_layer[-13:-7]:
        assert m["name"] in laguna_only
        assert [w for w in m["workloads"] if w != JOYAI_CELL] \
            == [LAGUNA_CELL] and m["moves"] == "txn_per_s"
        assert FALCON_CELL not in m["workloads"]
    earlier = per_layer[:-13]        # the checks below: what PR 30 left
    # OLMoE's second cell reports exactly what its first does
    assert reports[FULL_CELL] == reports[OLMOE_CELL] >= common | olmoe_only
    # OLMoE's kernel files read OLMoE's keys and byte model
    assert reports[ZAYA_CELL] == (reports[OLMOE_CELL] - olmoe_only) | zaya_only
    # DistilBERT's kernel files read DistilBERT's keys
    for cell in reports:
        assert not {"ffn_ms_per_batch", "ffn_roofline_pct",
                    "attn_core_roofline_pct"} & reports[cell]
    for m in earlier[-10:-4]:
        # PR 33 appended its two cells' names behind what was there
        assert m["workloads"][0] == OLMOE_CELL and m["moves"] == "txn_per_s"
        assert [w for w in m["workloads"]
                if w not in (LAGUNA_CELL, ZAYA_FULL_CELL, JOYAI_CELL,
                             FALCON_CELL)
                ][-1] == FULL_CELL
    for m in earlier[-4:]:
        assert m["name"] in zaya_only
        assert m["workloads"] == [ZAYA_CELL, ZAYA_FULL_CELL] \
            and m["moves"] == "txn_per_s"
    # the two mixes differ in how full the window is, and in nothing else
    a = spec.cell(OLMOE_CELL)["traffic_data"]
    b = spec.cell(FULL_CELL)["traffic_data"]
    same = ("arrival", "rate_txn_per_s", "warmup_s", "grace_s", "pool_events",
            "merchant_zipf_s", "memo_share", "latency_budget_ms")
    assert {k: a[k] for k in same} == {k: b[k] for k in same}
    assert b["text_tokens"] == {"dist": "lognormal", "median": 112,
                                "sigma": 0.2, "min": 64, "max": 128}


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


def test_zaya1_matmul_flops_are_the_routed_experts_and_the_latent():
    builder = spec.builder(ZAYA_CFG)
    per_token = builder.text_matmul_flops_per_token(ZAYA_CFG)
    assert per_token == {
        "projections": 2 * 2048 * (1024 + 256 + 256 + 1024),
        "convolution": 2 * 2 * 128 * 1280,
        "router": 2 * (2048 * 256 + 2 * 256 * 256 + 256 * 16),
        "experts": 2 * 3 * 2048 * 2048}
    # attention runs in the latent: its projections are 10.5 MFLOP a slot
    # against 33.6 for OLMoE's four full-width ones
    assert per_token["projections"] == 10485760
    total = builder.matmul_flops_per_batch(ZAYA_CFG)
    experts = 24 * 256 * 128 * per_token["experts"]
    assert 0.64 < experts / total < 0.68
    assert 30e12 < total < 31e12


def test_zaya1_kernels_charge_what_the_program_counted():
    layers, slots, routed, real = 24, 256 * 128, 24576, 21300
    counters = {"expert_rows": real * layers, "token_slots": slots,
                "expert_token_slots": routed, "batches": 1}
    ffn = spec.kernel("zaya1_expert_ffn").work(counters, ZAYA_CFG)
    assert ffn["flops"] == 3 * 2 * real * layers * 2048 * 2048
    weights = layers * 16 * 3 * 2048 * 2048 * 2
    assert ffn["hbm_bytes"] == weights + real * layers * (
        2 * 2048 * 2 + 2 * 2 * 2048 * 4 + 2 * 2048 * 2 + 2048 * 4)
    assert ffn["flops"] / ffn["hbm_bytes"] > 240          # compute-bound
    # the router is charged the slots it ran on, not every launched slot
    router = spec.kernel("zaya1_router").work(counters, ZAYA_CFG)
    assert router["hbm_bytes"] == layers * routed * (
        2048 * 4 + 2 * 256 * 4 + 16 * 4 + 8 + 8)
    assert router["flops"] == 2 * layers * routed * (
        2048 * 256 + 2 * 256 * 256 + 256 * 16)
    assert router["flops"] / router["hbm_bytes"] < 240    # memory-bound
    assert spec.kernel("zaya1_router").work(
        {"token_slots": slots}, ZAYA_CFG)["hbm_bytes"] == 0.0
    # the mixing runs on every launched slot: latents and values in, q, k
    # and v out, float32
    mix = spec.kernel("cca_mix").work(counters, ZAYA_CFG)
    assert mix["hbm_bytes"] == layers * slots * 2 * (1280 + 256) * 4
    assert mix["flops"] == 2 * layers * slots * 2 * 128 * 1280
    assert mix["flops"] / mix["hbm_bytes"] < 240          # memory-bound
    none = spec.kernel("zaya1_expert_ffn").work({"batches": 3}, ZAYA_CFG)
    assert none == {"flops": 0.0, "hbm_bytes": 0.0}
    assert spec.kernel("cca_mix").work({}, ZAYA_CFG)["hbm_bytes"] == 0.0


def _fake_run(scope_s, counters, cfg=OLMOE_CFG):
    return types.SimpleNamespace(
        trace={"window_s": 1.0}, counters_slice=dict(counters),
        counters=dict(counters),
        extra={"cfg": cfg, "device": {"kind": "TPU v5 lite"},
               "scope_trace": {"busy_s": 1.0, "scoped": True,
                               "scope_s": scope_s}})


@pytest.mark.parametrize("cell", ROUTED_CELLS)
def test_the_tile_fill_on_a_hand_made_run(cell):
    """``expert_tile_fill_pct``: the real pairs over the rows the fused
    kernel's grid visited, in every routed cell and in no other; left out,
    not raised, against a program that counts no visited rows (the parent,
    and any program in the XLA form, whose counter stays 0)."""
    cfg = {OLMOE_CELL: OLMOE_CFG, FULL_CELL: OLMOE_CFG, ZAYA_CELL: ZAYA_CFG,
           ZAYA_FULL_CELL: ZAYA_CFG, LAGUNA_CELL: LAGUNA_CFG,
           JOYAI_CELL: JOYAI_CFG}[cell]
    assert "expert_tile_fill_pct" in {
        m["name"] for m in spec.metrics_for(cell, "per_layer")}
    for other in (FALCON_CELL, "s512-fulltext-saturated"):
        assert "expert_tile_fill_pct" not in {
            m["name"] for m in spec.metrics_for(other, "per_layer")}
    read = spec.reader_for("expert_tile_fill_pct", "per_layer")
    counters = {"batches": 2, "scored": 16, "expert_rows": 79_000,
                "expert_tile_rows": 112_000}
    assert read(_fake_run({"text": 0.4}, counters, cfg)) == pytest.approx(
        100 * 79 / 112)
    for none in ({"expert_rows": 79_000},
                 {"expert_rows": 79_000, "expert_tile_rows": 0}):
        assert read(_fake_run({"text": 0.4}, {"batches": 2, **none},
                              cfg)) is None


def test_the_zaya1_metrics_on_a_hand_made_run():
    layers, real = 24, 21300
    counters = {"batches": 2, "scored": 512, "token_slots": 2 * 256 * 128,
                "expert_token_slots": 2 * 24576,
                "expert_rows": 2 * real * layers,
                "expert_peak_rows": 3 * 2 * real * layers}
    scope_s = {"text": 0.9}
    for i in range(layers):
        scope_s.update({
            f"text/layer{i}/experts": 0.012,
            f"text/layer{i}/experts/matmul": 0.008,
            f"text/layer{i}/router": 0.002,
            f"text/layer{i}/attn_mix": 0.005,
            f"text/layer{i}/attn_core": 0.001})
    run = _fake_run(scope_s, counters, ZAYA_CFG)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("cca_mix_ms_per_batch") == pytest.approx(60.0)
    assert metric("expert_ffn_ms_per_batch") == pytest.approx(144.0)
    assert metric("expert_matmul_ms_per_batch") == pytest.approx(96.0)
    assert metric("router_ms_per_batch") == pytest.approx(24.0)
    assert metric("attn_core_ms_per_batch") == pytest.approx(12.0)
    assert metric("expert_imbalance_x") == pytest.approx(3.0)
    for name, kernel, quantity, peak, seconds in (
            ("cca_mix_roofline_pct", "cca_mix", "hbm_bytes", 819e9, 0.12),
            ("zaya1_router_roofline_pct", "zaya1_router", "hbm_bytes", 819e9,
             0.048),
            ("zaya1_expert_ffn_roofline_pct", "zaya1_expert_ffn", "flops",
             197e12, 0.192)):
        needs = spec.kernel(kernel).work(counters, ZAYA_CFG)[quantity]
        assert 0 < metric(name) == pytest.approx(
            100 * needs / peak / seconds), name
    # against the parent (OLMoE's scopes, no attn_mix; the counters are
    # there since PR 29) and against a program from before the counters,
    # every new metric is left out and none raises
    parent = _fake_run({"text": 0.9, "text/layer0/router": 0.1,
                        "text/layer0/experts/matmul": 0.1},
                       {"batches": 2, "scored": 512}, ZAYA_CFG)
    for name in ("cca_mix_ms_per_batch", "cca_mix_roofline_pct",
                 "zaya1_expert_ffn_roofline_pct", "zaya1_router_roofline_pct"):
        assert spec.reader_for(name, "per_layer")(parent) is None, name


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
    assert metric("expert_combine_ms_per_batch") == pytest.approx(80.0)
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
                 "expert_combine_ms_per_batch", "expert_ffn_roofline_pct",
                 "router_ms_per_batch", "router_roofline_pct",
                 "expert_imbalance_x"):
        assert spec.reader_for(name, "per_layer")(old) is None, name


def test_laguna_matmul_flops_charge_the_even_share_and_visible_pairs():
    builder = spec.builder(LAGUNA_CFG)
    parts = builder.text_matmul_flops_per_row(LAGUNA_CFG)
    t, h = 2048, 3072
    # q and o of 6,144 (two full layers) and 9,216 (three sliding), k, v, gate
    assert parts["projections"] == 2.0 * t * h * (
        2 * (2 * 48 + 3 * 72) * 128 + 5 * 2 * 1024 + 2 * 48 + 3 * 72)
    assert parts["dense_mlp"] == 6.0 * t * h * 12288         # layer 0 alone
    # four sparse layers; 2.5 of a token's ten experts live here
    assert parts["experts"] == 4 * 6.0 * t * h * 1024 * 2.5
    assert parts["shared_expert"] == 4 * 6.0 * t * h * 1024
    assert parts["router"] == 4 * 2.0 * t * h * 256
    full, sliding = builder.visible_pairs(t, None), builder.visible_pairs(
        t, 512)
    assert full == t * (t + 1) // 2
    assert sliding == sum(min(i + 1, 512) for i in range(t))
    assert parts["cores"] == 4.0 * 128 * (2 * 48 * full + 3 * 72 * sliding)
    total = builder.matmul_flops_per_batch(LAGUNA_CFG)
    assert 0.99 < 8 * sum(parts.values()) / total <= 1.0
    assert "stale kind" in builder.matmul_flops_per_batch.__doc__


def test_laguna_kernels_charge_what_the_program_counted():
    counters = {"batches": 3, "token_slots": 3 * 8 * 2048,
                "attn_visible_pairs_full": 7_000_000,
                "attn_visible_pairs_sliding": 4_000_000,
                "expert_rows": 300_000, "routed_pairs": 1_200_000}
    core = spec.kernel("laguna_attn_core").work(counters, LAGUNA_CFG)
    # visible pairs of real tokens, not padded squares: 4 x 128 FLOP a pair
    # and query head, 96 heads over the two full layers, 216 over the three
    # sliding ones
    assert core["flops"] == 4 * 128 * (96 * 7_000_000 + 216 * 4_000_000)
    assert core["hbm_bytes"] == (2 * 312 + 2 * 40) * 3 * 8 * 2048 * 128 * 2
    ffn = spec.kernel("laguna_expert_ffn").work(counters, LAGUNA_CFG)
    assert ffn["flops"] == 6 * 300_000 * 3072 * 1024
    assert ffn["hbm_bytes"] > 3 * 4 * 64 * 3 * 3072 * 1024 * 2
    for kernel in ("laguna_attn_core", "laguna_expert_ffn"):
        none = spec.kernel(kernel).work({"batches": 3}, LAGUNA_CFG)
        assert none == {"flops": 0.0, "hbm_bytes": 0.0}, kernel


def test_the_laguna_metrics_on_a_hand_made_run():
    real = 10_100
    counters = {"batches": 2, "scored": 16, "token_slots": 2 * 8 * 2048,
                "real_tokens": 2 * real, "expert_token_slots": 2 * 12288,
                "routed_pairs": 2 * real * 10 * 4,
                "expert_rows": 2 * real * 10, "expert_peak_rows": 3 * real * 10,
                "attn_visible_pairs_full": 14_000_000,
                "attn_visible_pairs_sliding": 8_000_000}
    scope_s = {"text": 0.4}
    for i in range(5):
        scope_s[f"text/layer{i}/attn_core"] = 0.004 if i in (0, 4) else 0.003
    scope_s["text/layer0/ffn"] = 0.04
    for i in range(1, 5):
        scope_s.update({
            f"text/layer{i}/experts": 0.03,
            f"text/layer{i}/experts/matmul": 0.01,
            f"text/layer{i}/router": 0.002,
            f"text/layer{i}/shared_expert": 0.0015})
    run = _fake_run(scope_s, counters, LAGUNA_CFG)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("attn_core_sliding_ms_per_batch") == pytest.approx(4.5)
    assert metric("attn_core_full_ms_per_batch") == pytest.approx(4.0)
    assert metric("attn_core_ms_per_batch") == pytest.approx(8.5)
    assert metric("ffn_ms_per_batch") == pytest.approx(20.0)
    assert metric("shared_expert_ms_per_batch") == pytest.approx(3.0)
    assert metric("expert_ffn_ms_per_batch") == pytest.approx(60.0)
    assert metric("expert_matmul_ms_per_batch") == pytest.approx(20.0)
    assert metric("router_ms_per_batch") == pytest.approx(4.0)
    assert metric("expert_local_share_pct") == pytest.approx(25.0)
    assert metric("expert_imbalance_x") == pytest.approx(1.5)
    for name, kernel, seconds in (
            ("laguna_attn_core_roofline_pct", "laguna_attn_core", 0.017),
            ("laguna_expert_ffn_roofline_pct", "laguna_expert_ffn", 0.04)):
        needs = spec.kernel(kernel).work(counters, LAGUNA_CFG)["flops"]
        assert 0 < metric(name) < 100, name
        assert metric(name) == pytest.approx(
            100 * needs / 197e12 / seconds), name
    # against a program without the scopes and counters (the parent has no
    # shared_expert scope, no routed_pairs, no visible pairs) every new
    # metric is left out and none raises
    parent = _fake_run({"text": 0.9, "text/layer1/router": 0.1,
                        "text/layer1/experts/matmul": 0.1},
                       {"batches": 2, "scored": 16, "expert_rows": 5},
                       LAGUNA_CFG)
    for name in ("attn_core_sliding_ms_per_batch",
                 "attn_core_full_ms_per_batch",
                 "laguna_attn_core_roofline_pct", "shared_expert_ms_per_batch",
                 "expert_local_share_pct"):
        assert spec.reader_for(name, "per_layer")(parent) is None, name
    older = _fake_run({"text": 0.9}, {"batches": 2, "scored": 16}, LAGUNA_CFG)
    assert spec.reader_for("laguna_expert_ffn_roofline_pct",
                           "per_layer")(older) is None


def test_joyai_matmul_flops_follow_the_latent_block():
    builder = spec.builder(JOYAI_CFG)
    parts = builder.text_matmul_flops_per_row(JOYAI_CFG)
    t, h = 2048, 2048
    assert parts["latent"] == 5 * 2.0 * t * h * (1536 + 512 + 64)
    assert parts["projections"] == 5 * 2.0 * t * 32 * (
        1536 * 192 + 512 * 256 + 128 * 2048)
    # a visible pair: a score over 192 and a value of 128, a head
    assert parts["cores"] == 5 * 2.0 * 32 * 320 * (t * (t + 1) // 2)
    assert parts["dense_mlp"] == 6.0 * t * h * 7168          # layer 0 alone
    assert parts["experts"] == 4 * 6.0 * t * h * 768 * 8
    assert parts["shared_expert"] == 4 * 6.0 * t * h * 768
    assert parts["router"] == 4 * 2.0 * t * h * 256
    # ISSUE 43's per-token arithmetic: 52.7 MFLOP in the six attention
    # matmuls, 75.5 in eight experts, 9.4 in the shared one, a sparse layer
    assert (parts["latent"] + parts["projections"]) / (5 * t) \
        == pytest.approx(52.7e6, rel=2e-3)
    assert parts["experts"] / (4 * t) == pytest.approx(75.5e6, rel=2e-3)
    assert parts["shared_expert"] / (4 * t) == pytest.approx(9.4e6, rel=5e-3)
    total = builder.matmul_flops_per_batch(JOYAI_CFG)
    assert 0.99 < 8 * sum(parts.values()) / total <= 1.0
    assert "stale kind" in builder.matmul_flops_per_batch.__doc__


def test_joyai_kernels_charge_what_the_program_counted():
    slots, real = 3 * 8 * 2048, 30_000
    counters = {"batches": 3, "token_slots": slots,
                "attn_visible_pairs_full": 24_000_000,
                "attn_visible_pairs_sliding": 0,
                "expert_rows": real * 8 * 4, "routed_pairs": real * 8 * 4}
    core = spec.kernel("joyai_attn_core").work(counters, JOYAI_CFG)
    # visible pairs of real tokens: 2 x (192 + 128) FLOP a pair and head,
    # 32 heads, five layers
    assert core["flops"] == 2 * 320 * 32 * 5 * 24_000_000
    assert core["hbm_bytes"] == 5 * slots * (
        32 * 4 * 128 * 2 + 33 * 64 * 4)
    ffn = spec.kernel("joyai_expert_ffn").work(counters, JOYAI_CFG)
    assert ffn["flops"] == 6 * real * 8 * 4 * 2048 * 768
    weights = 3 * 4 * 256 * 3 * 2048 * 768 * 2
    # what any implementation moves: the weights once a launch and layer, a
    # row's bfloat16 input read once, its float32 down result written; the
    # three-call form's round trips between its calls are not charged
    assert ffn["hbm_bytes"] == weights + real * 8 * 4 * (2048 * 2 + 2048 * 4)
    # ~310 rows an expert: under the v5e's ridge of 240 FLOP a byte, so the
    # metric file names the HBM's rate
    assert 100 < ffn["flops"] / ffn["hbm_bytes"] < 240
    assert json.loads((ROOT / "benchmarks/layer_metrics/"
                       "joyai_expert_ffn_roofline_pct.json").read_text()
                      )["args"]["peak"] == "hbm_bytes_per_s"
    latent = spec.kernel("joyai_attn_latent").work(counters, JOYAI_CFG)
    assert latent["flops"] == 2 * 2048 * (1536 + 576) * 5 * slots
    assert latent["flops"] / latent["hbm_bytes"] > 240    # compute-bound
    for kernel in ("joyai_attn_core", "joyai_expert_ffn",
                   "joyai_attn_latent"):
        none = spec.kernel(kernel).work({"batches": 3}, JOYAI_CFG)
        assert none == {"flops": 0.0, "hbm_bytes": 0.0}, kernel


def test_the_joyai_metrics_on_a_hand_made_run():
    real = 10_000
    counters = {"batches": 2, "scored": 16, "token_slots": 2 * 8 * 2048,
                "real_tokens": 2 * real, "expert_token_slots": 2 * 12288,
                "routed_pairs": 2 * real * 8 * 4,
                "expert_rows": 2 * real * 8 * 4,
                "expert_peak_rows": 4 * real * 8 * 4,
                "compact_batches": 2,
                "attn_visible_pairs_full": 16_000_000,
                "attn_visible_pairs_sliding": 0}
    scope_s = {"text": 0.25}
    for i in range(5):
        scope_s.update({f"text/layer{i}/attn_core": 0.005,
                        f"text/layer{i}/attn_latent": 0.003,
                        f"text/layer{i}/attn_proj": 0.009})
    scope_s["text/layer0/ffn"] = 0.018
    for i in range(1, 5):
        scope_s.update({
            f"text/layer{i}/experts": 0.024,
            f"text/layer{i}/experts/matmul": 0.016,
            f"text/layer{i}/router": 0.004,
            f"text/layer{i}/shared_expert": 0.0015})
    run = _fake_run(scope_s, counters, JOYAI_CFG)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("attn_latent_ms_per_batch") == pytest.approx(7.5)
    assert metric("attn_core_ms_per_batch") == pytest.approx(12.5)
    assert metric("attn_proj_ms_per_batch") == pytest.approx(22.5)
    assert metric("ffn_ms_per_batch") == pytest.approx(9.0)
    assert metric("shared_expert_ms_per_batch") == pytest.approx(3.0)
    assert metric("expert_ffn_ms_per_batch") == pytest.approx(48.0)
    assert metric("expert_matmul_ms_per_batch") == pytest.approx(32.0)
    assert metric("router_ms_per_batch") == pytest.approx(8.0)
    assert metric("expert_imbalance_x") == pytest.approx(2.0)
    assert metric("compact_batches_pct") == pytest.approx(100.0)
    for name, kernel, quantity, peak, seconds in (
            ("joyai_attn_core_roofline_pct", "joyai_attn_core", "flops",
             197e12, 0.025),
            ("joyai_expert_ffn_roofline_pct", "joyai_expert_ffn",
             "hbm_bytes", 819e9, 0.064),
            ("joyai_attn_latent_roofline_pct", "joyai_attn_latent", "flops",
             197e12, 0.015)):
        needs = spec.kernel(kernel).work(counters, JOYAI_CFG)[quantity]
        assert 0 < metric(name) < 100, name
        assert metric(name) == pytest.approx(
            100 * needs / peak / seconds), name
    # against a program without the scope and the counters every new metric
    # is left out and none raises
    parent = _fake_run({"text": 0.9, "text/layer1/router": 0.1},
                       {"batches": 2, "scored": 16}, JOYAI_CFG)
    for name in ("attn_latent_ms_per_batch", "joyai_attn_core_roofline_pct",
                 "joyai_expert_ffn_roofline_pct",
                 "joyai_attn_latent_roofline_pct"):
        assert spec.reader_for(name, "per_layer")(parent) is None, name
    counted = _fake_run({"text": 0.9, "text/layer1/attn_core": 0.1,
                         "text/layer1/experts/matmul": 0.1},
                        {"batches": 2, "scored": 16}, JOYAI_CFG)
    for name in ("joyai_attn_core_roofline_pct",
                 "joyai_expert_ffn_roofline_pct"):
        assert spec.reader_for(name, "per_layer")(counted) is None, name


def test_falconh1_matmul_flops_follow_the_parallel_block():
    builder = spec.builder(FALCON_CFG)
    parts = builder.text_matmul_flops_per_row(FALCON_CFG)
    t, h = 2048, 5120
    assert parts["ssm_proj"] == 6 * 2.0 * t * h * (9248 + 4096)
    assert parts["attn_proj"] == 6 * 2.0 * t * h * 128 * (2 * 20 + 2 * 4)
    assert parts["cores"] == 6 * 4.0 * 20 * 128 * (t * (t + 1) // 2)
    assert parts["mlp"] == 6 * 6.0 * t * h * 21504
    # ISSUE 46's arithmetic: 5.37 MFLOP a slot and layer in the scan
    per_slot = spec.kernel("falconh1_ssd_scan").flops_per_slot(FALCON_CFG)
    assert per_slot == pytest.approx(5.37e6, rel=2e-3)
    assert parts["ssm_scan"] == 6 * t * per_slot
    # the mixer's projections are 16% of a layer's matmuls, the MLP 77%
    per_layer = {k: v for k, v in parts.items() if k != "cores"}
    whole = sum(per_layer.values())
    assert parts["mlp"] / whole == pytest.approx(0.765, abs=0.01)
    assert (parts["ssm_proj"] + parts["ssm_scan"]) / whole \
        == pytest.approx(0.16, abs=0.01)
    # ~5.2 GFLOP a token, 84-85 TFLOP a batch of 8 x 2,048 slots
    total = builder.matmul_flops_per_batch(FALCON_CFG)
    assert whole / t == pytest.approx(5.2e9, rel=0.02)
    assert total == pytest.approx(86e12, rel=0.03)
    assert 0.99 < 8 * sum(parts.values()) / total <= 1.0


def test_falconh1_kernels_charge_what_the_program_counted():
    slots = 3 * 8 * 2048
    counters = {"batches": 3, "token_slots": slots,
                "attn_visible_pairs_full": 20_000_000,
                "attn_visible_pairs_sliding": 0,
                "ssm_chunks": slots // 128 * 6}
    scan = spec.kernel("falconh1_ssd_scan").work(counters, FALCON_CFG)
    # every launched slot of every layer: C B^T a group, the masked product
    # and the two state products a head
    assert scan["flops"] == slots * 6 * (
        2 * 128 * 256 * 2 + 2 * 128 * 128 * 32 + 2 * 2 * 256 * 128 * 32)
    # x, B, C read in bfloat16, dt in float32, y written in float32
    assert scan["hbm_bytes"] == slots * 6 * (
        (4096 + 1024) * 2 + (32 + 4096) * 4)
    # ~200 FLOP a byte: under the v5e's ridge of 240, so the metric file
    # names the HBM's rate
    assert 190 < scan["flops"] / scan["hbm_bytes"] < 240
    assert json.loads((ROOT / "benchmarks/layer_metrics/"
                       "falconh1_ssd_scan_roofline_pct.json").read_text()
                      )["args"]["peak"] == "hbm_bytes_per_s"
    core = spec.kernel("falconh1_attn_core").work(counters, FALCON_CFG)
    assert core["flops"] == 4 * 128 * 20 * 6 * 20_000_000
    assert core["hbm_bytes"] == 6 * slots * (2 * 20 + 2 * 4) * 128 * 2
    ffn = spec.kernel("falconh1_ffn").work(counters, FALCON_CFG)
    assert ffn["flops"] == 6 * slots * 5120 * 21504 * 6
    assert ffn["flops"] / ffn["hbm_bytes"] > 1000        # compute-bound
    for kernel in ("falconh1_ssd_scan", "falconh1_attn_core",
                   "falconh1_ffn"):
        none = spec.kernel(kernel).work({"batches": 3}, FALCON_CFG)
        assert none == {"flops": 0.0, "hbm_bytes": 0.0}, kernel
    # a program that counts its slots and no chunk has no mixer
    assert spec.kernel("falconh1_ssd_scan").work(
        {"batches": 3, "token_slots": slots}, FALCON_CFG)["flops"] == 0.0


def test_the_falconh1_metrics_on_a_hand_made_run():
    slots = 2 * 8 * 2048
    counters = {"batches": 2, "scored": 16, "token_slots": slots,
                "real_tokens": 20_000, "ssm_chunks": slots // 128 * 6,
                "attn_visible_pairs_full": 14_000_000,
                "attn_visible_pairs_sliding": 0}
    scope_s = {"text": 1.14}
    for i in range(6):
        scope_s.update({f"text/layer{i}/attn_core": 0.003,
                        f"text/layer{i}/attn_proj": 0.012,
                        f"text/layer{i}/ffn": 0.130,
                        f"text/layer{i}/ln": 0.0002,
                        f"text/layer{i}/ssm_proj": 0.032,
                        f"text/layer{i}/ssm_conv": 0.008,
                        f"text/layer{i}/ssm_scan": 0.0033})
    run = _fake_run(scope_s, counters, FALCON_CFG)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("ssm_proj_ms_per_batch") == pytest.approx(96.0)
    assert metric("ssm_conv_ms_per_batch") == pytest.approx(24.0)
    assert metric("ssm_scan_ms_per_batch") == pytest.approx(9.9)
    assert metric("attn_core_ms_per_batch") == pytest.approx(9.0)
    assert metric("attn_proj_ms_per_batch") == pytest.approx(36.0)
    assert metric("ffn_ms_per_batch") == pytest.approx(390.0)
    assert metric("token_padding_pct") == pytest.approx(
        100 * (1 - 20_000 / slots))
    for name, kernel, quantity, peak, seconds in (
            ("falconh1_ssd_scan_roofline_pct", "falconh1_ssd_scan",
             "hbm_bytes", 819e9, 0.0198),
            ("falconh1_attn_core_roofline_pct", "falconh1_attn_core",
             "flops", 197e12, 0.018),
            ("falconh1_ffn_roofline_pct", "falconh1_ffn", "flops", 197e12,
             0.78)):
        needs = spec.kernel(kernel).work(counters, FALCON_CFG)[quantity]
        assert 0 < metric(name) < 100, name
        assert metric(name) == pytest.approx(
            100 * needs / peak / seconds), name
    # against a program without the scopes and the counter every new metric
    # is left out and none raises
    parent = _fake_run({"text": 0.9, "text/layer1/ffn": 0.1},
                       {"batches": 2, "scored": 16}, FALCON_CFG)
    for name in ("ssm_proj_ms_per_batch", "ssm_conv_ms_per_batch",
                 "ssm_scan_ms_per_batch", "falconh1_ssd_scan_roofline_pct",
                 "falconh1_attn_core_roofline_pct",
                 "falconh1_ffn_roofline_pct"):
        assert spec.reader_for(name, "per_layer")(parent) is None, name
    # the scope there and the counter not: the share is left out
    counted = _fake_run({"text": 0.9, "text/layer1/ssm_scan": 0.1},
                        {"batches": 2, "scored": 16, "token_slots": slots},
                        FALCON_CFG)
    assert spec.reader_for("falconh1_ssd_scan_roofline_pct",
                           "per_layer")(counted) is None


def test_nemotron3_matmul_flops_follow_the_layers_by_kind():
    builder = spec.builder(NEMOTRON_CFG)
    parts = builder.text_matmul_flops_per_row(NEMOTRON_CFG)
    t, h = 2048, 2688
    # each part times the layers of ITS kind: 4 M, 4 E, 1 *
    assert parts["ssm_proj"] == 4 * 2.0 * t * h * (10304 + 4096)
    assert parts["attn_proj"] == 1 * 2.0 * t * h * 128 * (2 * 32 + 2 * 2)
    assert parts["cores"] == 1 * 4.0 * 32 * 128 * (t * (t + 1) // 2)
    assert parts["router"] == 4 * 2.0 * t * h * 128
    # two matrices an expert: no gate
    assert parts["experts"] == 4 * 4.0 * t * h * 1856 * 6
    assert parts["shared_expert"] == 4 * 4.0 * t * h * 3712
    # ISSUE 50's arithmetic: 3.41 MFLOP a slot and layer in the scan
    per_slot = spec.kernel("nemotron3_ssd_scan").flops_per_slot(NEMOTRON_CFG)
    assert per_slot == pytest.approx(3.41e6, rel=2e-3)
    assert parts["ssm_scan"] == 4 * t * per_slot
    # ~1.0 GFLOP a token outside the core: the experts (routed and shared)
    # 63%, the mixers' projections and scans 32%
    rest = {k: v for k, v in parts.items() if k != "cores"}
    whole = sum(rest.values())
    assert whole / t == pytest.approx(1.01e9, rel=0.02)
    assert (parts["experts"] + parts["shared_expert"]) / whole \
        == pytest.approx(0.63, abs=0.02)
    assert (parts["ssm_proj"] + parts["ssm_scan"]) / whole \
        == pytest.approx(0.32, abs=0.02)
    total = builder.matmul_flops_per_batch(NEMOTRON_CFG)
    assert 0.99 < 8 * sum(parts.values()) / total <= 1.0


def test_nemotron3_kernels_charge_what_the_program_counted():
    slots = 3 * 8 * 2048
    counters = {"batches": 3, "token_slots": slots,
                "attn_visible_pairs_full": 20_000_000,
                "attn_visible_pairs_sliding": 0,
                "ssm_chunks": slots // 128 * 4,
                "expert_rows": 3 * 4 * 60_000, "routed_pairs": 3 * 4 * 60_000}
    scan = spec.kernel("nemotron3_ssd_scan").work(counters, NEMOTRON_CFG)
    # every launched slot of the four M layers: C B^T a group, the masked
    # product and the two state products a head of 64
    assert scan["flops"] == slots * 4 * (
        2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 2 * 2 * 128 * 64 * 64)
    # x, B, C read in bfloat16, dt in float32, y written in float32
    assert scan["hbm_bytes"] == slots * 4 * (
        (4096 + 2048) * 2 + (64 + 4096) * 4)
    assert scan["hbm_bytes"] / (slots * 4) == 28_928
    # ~118 FLOP a byte: half the v5e's ridge of 240, so the metric file
    # names the HBM's rate
    assert 110 < scan["flops"] / scan["hbm_bytes"] < 125
    metrics = ROOT / "benchmarks/layer_metrics"
    assert json.loads((metrics / "nemotron3_ssd_scan_roofline_pct.json"
                       ).read_text())["args"]["peak"] == "hbm_bytes_per_s"
    experts = spec.kernel("nemotron3_expert_ffn").work(counters, NEMOTRON_CFG)
    # up and down of every routed row at the PUBLISHED 1,856, whatever the
    # kernels pad it to
    assert experts["flops"] == 4 * 3 * 4 * 60_000 * 2688 * 1856
    assert experts["hbm_bytes"] == (
        3 * 4 * 128 * 2 * 2688 * 1856 * 2 + 3 * 4 * 60_000 * 2688 * 6)
    # ~340 FLOP a byte at ~470 rows an expert: over the ridge, so the metric
    # file names no peak (the reader's default is the bf16 peak)
    assert 300 < experts["flops"] / experts["hbm_bytes"] < 380
    assert "peak" not in json.loads(
        (metrics / "nemotron3_expert_ffn_roofline_pct.json").read_text()
    )["args"]
    core = spec.kernel("nemotron3_attn_core").work(counters, NEMOTRON_CFG)
    # one causal layer's pairs x the ONE * layer of the nine
    assert core["flops"] == 4 * 128 * 32 * 1 * 20_000_000
    assert core["hbm_bytes"] == 1 * slots * (2 * 32 + 2 * 2) * 128 * 2
    for kernel in ("nemotron3_ssd_scan", "nemotron3_expert_ffn",
                   "nemotron3_attn_core"):
        none = spec.kernel(kernel).work({"batches": 3}, NEMOTRON_CFG)
        assert none == {"flops": 0.0, "hbm_bytes": 0.0}, kernel


def test_the_nemotron3_metrics_on_a_hand_made_run():
    slots = 2 * 8 * 2048
    counters = {"batches": 2, "scored": 16, "token_slots": slots,
                "real_tokens": 20_000, "ssm_chunks": slots // 128 * 4,
                "attn_visible_pairs_full": 14_000_000,
                "attn_visible_pairs_sliding": 0,
                "routed_pairs": 480_000, "expert_rows": 480_000,
                "expert_peak_rows": 600_000, "expert_tile_rows": 560_000,
                "expert_token_slots": 2 * 12288, "compact_batches": 2}
    scope_s = {"text": 0.32}
    for i, kind in enumerate("MEMEM*EME"):
        scope_s[f"text/layer{i}/ln"] = 0.0004
        if kind == "M":
            scope_s.update({f"text/layer{i}/ssm_proj": 0.020,
                            f"text/layer{i}/ssm_conv": 0.008,
                            f"text/layer{i}/ssm_scan": 0.004})
        elif kind == "*":
            scope_s.update({f"text/layer{i}/attn_proj": 0.010,
                            f"text/layer{i}/attn_core": 0.004})
        else:
            scope_s.update({f"text/layer{i}/router": 0.004,
                            f"text/layer{i}/experts": 0.027,
                            f"text/layer{i}/experts/dispatch": 0.003,
                            f"text/layer{i}/experts/matmul": 0.020,
                            f"text/layer{i}/experts/combine": 0.004,
                            f"text/layer{i}/shared_expert": 0.007})
    run = _fake_run(scope_s, counters, NEMOTRON_CFG)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("ssm_proj_ms_per_batch") == pytest.approx(40.0)
    assert metric("ssm_conv_ms_per_batch") == pytest.approx(16.0)
    assert metric("ssm_scan_ms_per_batch") == pytest.approx(8.0)
    assert metric("attn_core_ms_per_batch") == pytest.approx(2.0)
    assert metric("attn_proj_ms_per_batch") == pytest.approx(5.0)
    assert metric("ln_ms_per_batch") == pytest.approx(1.8)
    assert metric("router_ms_per_batch") == pytest.approx(8.0)
    assert metric("expert_matmul_ms_per_batch") == pytest.approx(40.0)
    assert metric("expert_combine_ms_per_batch") == pytest.approx(8.0)
    assert metric("shared_expert_ms_per_batch") == pytest.approx(14.0)
    assert metric("expert_ffn_ms_per_batch") == pytest.approx(54.0)
    assert metric("expert_imbalance_x") == pytest.approx(1.25)
    assert metric("expert_tile_fill_pct") == pytest.approx(100 * 48 / 56)
    assert metric("compact_batches_pct") == pytest.approx(100.0)
    for name, kernel, quantity, peak, seconds in (
            ("nemotron3_ssd_scan_roofline_pct", "nemotron3_ssd_scan",
             "hbm_bytes", 819e9, 0.016),
            ("nemotron3_expert_ffn_roofline_pct", "nemotron3_expert_ffn",
             "flops", 197e12, 0.080),
            ("nemotron3_attn_core_roofline_pct", "nemotron3_attn_core",
             "flops", 197e12, 0.004)):
        needs = spec.kernel(kernel).work(counters, NEMOTRON_CFG)[quantity]
        assert 0 < metric(name) < 100, name
        assert metric(name) == pytest.approx(
            100 * needs / peak / seconds), name
    assert NEMOTRON_ONLY == [
        m["name"] for m in spec.metrics_for(NEMOTRON_CELL, "per_layer")
        if m["name"].startswith("nemotron3_")]
    # against a program without the scopes and the counters every new
    # metric is left out and none raises
    parent = _fake_run({"text": 0.9, "text/layer1/ffn": 0.1},
                       {"batches": 2, "scored": 16}, NEMOTRON_CFG)
    for name in NEMOTRON_ONLY:
        assert spec.reader_for(name, "per_layer")(parent) is None, name
    # the scope there and the counter not: the share is left out
    counted = _fake_run({"text": 0.9, "text/layer1/experts/matmul": 0.1},
                        {"batches": 2, "scored": 16, "token_slots": slots},
                        NEMOTRON_CFG)
    assert spec.reader_for("nemotron3_expert_ffn_roofline_pct",
                           "per_layer")(counted) is None


def test_qwen3next_matmul_flops_follow_the_layers_by_kind():
    builder = spec.builder(QWEN_CFG)
    parts = builder.text_matmul_flops_per_row(QWEN_CFG)
    t, h = 2048, 2048
    # each part times the layers of ITS kind: 5 L, 1 F, 6 sparse halves
    assert parts["delta_proj"] == 5 * 2.0 * t * h * (12288 + 64 + 4096)
    assert parts["attn_proj"] == 1 * 2.0 * t * h * 256 * (3 * 16 + 2 * 2)
    assert parts["cores"] == 1 * 4.0 * 16 * 256 * (t * (t + 1) // 2)
    # the router runs whole; five of a token's ten experts live here
    assert parts["router"] == 6 * 2.0 * t * h * 512
    assert parts["experts"] == 6 * 6.0 * t * h * 512 * 10 / 2
    assert parts["shared_expert"] == 6 * 2.0 * t * h * (3 * 512 + 1)
    # the chunked algorithm's count: 7.86 MFLOP a slot and layer
    per_slot = spec.kernel("qwen3next_delta_scan").flops_per_slot(QWEN_CFG)
    assert per_slot == pytest.approx(7.86e6, rel=2e-3)
    assert parts["delta_scan"] == 5 * t * per_slot
    total = builder.matmul_flops_per_batch(QWEN_CFG)
    assert 0.99 < 8 * sum(parts.values()) / total <= 1.0


def test_qwen3next_kernels_charge_what_the_program_counted():
    slots = 3 * 8 * 2048
    counters = {"batches": 3, "token_slots": slots,
                "attn_visible_pairs_full": 20_000_000,
                "attn_visible_pairs_sliding": 0,
                "delta_chunks": slots // 64 * 5,
                "expert_rows": 3 * 6 * 50_000, "routed_pairs": 3 * 6 * 100_000}
    scan = spec.kernel("qwen3next_delta_scan").work(counters, QWEN_CFG)
    # every launched slot of the five L layers, a chunk of 64 at a time: K
    # K^T and Q K^T a key head; the solve (ten 64^3 products), U and W, the
    # three products against the state and the masked one a value head
    a_chunk = 16 * 2 * 2 * 64 * 64 * 128 + 32 * (
        10 * 2 * 64 ** 3 + 2 * 2 * 64 * 64 * 128 + 3 * 2 * 64 * 128 * 128
        + 2 * 64 * 64 * 128)
    assert scan["flops"] == slots * 5 * a_chunk / 64
    # q, k, v read in bfloat16, g and beta in float32, o written in float32
    assert scan["hbm_bytes"] == slots * 5 * (
        (2048 + 2048 + 4096) * 2 + (64 + 4096) * 4)
    assert scan["hbm_bytes"] / (slots * 5) == 33_024
    # 238 FLOP a byte: at the v5e's ridge of 240, the bytes' bound the
    # larger, so the metric file names the HBM's rate
    assert 230 < scan["flops"] / scan["hbm_bytes"] < 240
    experts = spec.kernel("qwen3next_expert_ffn").work(counters, QWEN_CFG)
    # gate, up and down of every HELD row; the held experts' matrices once
    # a launch and layer
    assert experts["flops"] == 6 * 3 * 6 * 50_000 * 2048 * 512
    assert experts["hbm_bytes"] == (
        3 * 6 * 256 * 3 * 2048 * 512 * 2 + 3 * 6 * 50_000 * 2048 * 6)
    # ~140 FLOP a byte at ~195 rows an expert: under the ridge
    assert 130 < experts["flops"] / experts["hbm_bytes"] < 150
    core = spec.kernel("qwen3next_attn_core").work(counters, QWEN_CFG)
    # one causal layer's pairs x the ONE F layer of the six
    assert core["flops"] == 4 * 256 * 16 * 1 * 20_000_000
    assert core["hbm_bytes"] == 1 * slots * 256 * (16 * 10 + 2 * 6)
    for kernel in ("qwen3next_delta_scan", "qwen3next_expert_ffn",
                   "qwen3next_attn_core"):
        none = spec.kernel(kernel).work({"batches": 3}, QWEN_CFG)
        assert none == {"flops": 0.0, "hbm_bytes": 0.0}, kernel


def test_the_qwen3next_metrics_on_a_hand_made_run():
    slots = 2 * 8 * 2048
    counters = {"batches": 2, "scored": 16, "token_slots": slots,
                "real_tokens": 20_000, "delta_chunks": slots // 64 * 5,
                "attn_visible_pairs_full": 14_000_000,
                "attn_visible_pairs_sliding": 0,
                "routed_pairs": 1_200_000, "expert_rows": 600_000,
                "expert_peak_rows": 900_000, "expert_tile_rows": 800_000,
                "expert_token_slots": 2 * 12288, "compact_batches": 2,
                "dispatch_rows": 2 * 12288 * 60, "dispatch_kernel_rows": 0}
    scope_s = {"text": 0.40}
    for i, kind in enumerate("LLLFLL"):
        scope_s.update({f"text/layer{i}/ln": 0.001,
                        f"text/layer{i}/router": 0.004,
                        f"text/layer{i}/experts": 0.018,
                        f"text/layer{i}/experts/dispatch": 0.002,
                        f"text/layer{i}/experts/matmul": 0.010,
                        f"text/layer{i}/experts/combine": 0.006,
                        f"text/layer{i}/shared_expert": 0.001})
        if kind == "L":
            scope_s.update({f"text/layer{i}/delta_proj": 0.012,
                            f"text/layer{i}/delta_conv": 0.014,
                            f"text/layer{i}/delta_scan": 0.016})
        else:
            scope_s.update({f"text/layer{i}/attn_proj": 0.010,
                            f"text/layer{i}/attn_core": 0.004})
    run = _fake_run(scope_s, counters, QWEN_CFG)

    def metric(name):
        return spec.reader_for(name, "per_layer")(run)

    assert metric("delta_proj_ms_per_batch") == pytest.approx(30.0)
    assert metric("delta_conv_ms_per_batch") == pytest.approx(35.0)
    assert metric("delta_scan_ms_per_batch") == pytest.approx(40.0)
    assert metric("attn_core_ms_per_batch") == pytest.approx(2.0)
    assert metric("attn_proj_ms_per_batch") == pytest.approx(5.0)
    assert metric("ln_ms_per_batch") == pytest.approx(3.0)
    assert metric("router_ms_per_batch") == pytest.approx(12.0)
    assert metric("expert_matmul_ms_per_batch") == pytest.approx(30.0)
    assert metric("expert_combine_ms_per_batch") == pytest.approx(18.0)
    assert metric("expert_dispatch_ms_per_batch") == pytest.approx(6.0)
    assert metric("shared_expert_ms_per_batch") == pytest.approx(3.0)
    assert metric("expert_local_share_pct") == pytest.approx(50.0)
    assert metric("expert_tile_fill_pct") == pytest.approx(75.0)
    assert metric("dispatch_kernel_pct") == pytest.approx(0.0)
    for name, kernel, quantity, peak, seconds in (
            ("qwen3next_delta_scan_roofline_pct", "qwen3next_delta_scan",
             "hbm_bytes", 819e9, 0.080),
            ("qwen3next_expert_ffn_roofline_pct", "qwen3next_expert_ffn",
             "hbm_bytes", 819e9, 0.060),
            ("qwen3next_attn_core_roofline_pct", "qwen3next_attn_core",
             "flops", 197e12, 0.004)):
        needs = spec.kernel(kernel).work(counters, QWEN_CFG)[quantity]
        assert 0 < metric(name) < 100, name
        assert metric(name) == pytest.approx(
            100 * needs / peak / seconds), name
    assert QWEN_ONLY == [
        m["name"] for m in spec.metrics_for(QWEN_CELL, "per_layer")
        if m["name"].startswith(("delta_", "qwen3next_"))
        and m["name"] not in PART_METRICS]
    # against a program without the scopes and the counters every new
    # metric is left out and none raises
    parent = _fake_run({"text": 0.9, "text/layer1/ffn": 0.1},
                       {"batches": 2, "scored": 16}, QWEN_CFG)
    for name in QWEN_ONLY:
        assert spec.reader_for(name, "per_layer")(parent) is None, name
    # the scope there and the counter not: the share is left out
    counted = _fake_run({"text": 0.9, "text/layer1/delta_scan": 0.1},
                        {"batches": 2, "scored": 16, "token_slots": slots},
                        QWEN_CFG)
    assert spec.reader_for("qwen3next_delta_scan_roofline_pct",
                           "per_layer")(counted) is None


# ------------------------------------------------ a program without the module
@pytest.mark.parametrize("cfg,module", [(OLMOE_CFG, "olmoe"),
                                        (ZAYA_CFG, "zaya"),
                                        (LAGUNA_CFG, "laguna"),
                                        (JOYAI_CFG, "joyai"),
                                        (FALCON_CFG, "falcon_h1"),
                                        (NEMOTRON_CFG, "nemotron_h"),
                                        (QWEN_CFG, "qwen3_next")])
def test_the_builder_stops_at_once_on_a_program_without_the_encoder(
        monkeypatch, cfg, module):
    import importlib.util

    find = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name.endswith(f"models.{module}")
        else find(name, *a))
    with pytest.raises(SystemExit, match=f"models/{module}.py"):
        spec.builder(cfg)


@pytest.mark.parametrize("cell,module", [(OLMOE_CELL, "olmoe"),
                                         (ZAYA_CELL, "zaya"),
                                         (LAGUNA_CELL, "laguna"),
                                         (JOYAI_CELL, "joyai"),
                                         (FALCON_CELL, "falcon_h1"),
                                         (NEMOTRON_CELL, "nemotron_h"),
                                         (QWEN_CELL, "qwen3_next")])
def test_the_parent_exits_non_zero_within_seconds(tmp_path, cell, module):
    """A checkout of the benchmark without the program's new module — what
    the driver's parent run of a new configuration's cell is — prints no
    result."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmarks", tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    pkg = tmp_path / "realtime_fraud_detection_tpu"
    (pkg / "models").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "models" / "__init__.py").write_text("")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell,
         "--seed", "2600000001", "--seconds", "20", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert f"models/{module}.py" in proc.stderr and not proc.stdout.strip()


# --------------------------------------------------------- the cell, at TINY
@pytest.fixture(scope="module")
def tiny_copy(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(tmp_path_factory.mktemp("bench_seam"))
    # the rehearsal sizes a mix it does not know for 300 txn/s; a backlog
    # has to outlast the window whatever this CPU completes
    for mix in ("s128-memo-saturated", "s128-fullwindow-saturated",
                "s2048-remit-saturated"):
        traffic = copy / "benchmarks" / "traffic" / f"{mix}.json"
        tr = json.loads(traffic.read_text())
        tr["rate_txn_per_s"] = 2000
        traffic.write_text(json.dumps(tr))
    return copy


@pytest.mark.parametrize("cell,trace", [
    (OLMOE_CELL, 0), (OLMOE_CELL, 1), (ZAYA_CELL, 0), (ZAYA_CELL, 1),
    (FULL_CELL, 1), (LAGUNA_CELL, 0), (LAGUNA_CELL, 1), (ZAYA_FULL_CELL, 1),
    (JOYAI_CELL, 0), (JOYAI_CELL, 1), (FALCON_CELL, 0), (FALCON_CELL, 1),
    (NEMOTRON_CELL, 0), (NEMOTRON_CELL, 1), (QWEN_CELL, 0), (QWEN_CELL, 1)])
def test_tiny_rehearsal_of_a_routed_cell(tiny_copy, cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/tests/rehearsal.py"),
         str(tiny_copy), "--workload", cell, "--seed", "2600000019",
         "--seconds", "3", "--trace", str(trace)],
        capture_output=True, text=True, env=env, timeout=600, cwd=tiny_copy)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["count"] == 1
    assert "check zero compilations inside the window: ok" in proc.stdout
    assert "check the backlog outlasted the window: ok" in proc.stdout
    if trace and cell == FALCON_CELL:
        # a dense encoder: no expert metric, every slot real; the programs
        # compiled before the window opened are the admitted cells' and the
        # reference's three (ISSUE 46: at most 14, where PR 45's cell read
        # 98 and was refused on setup_s)
        assert out["metrics"]["token_padding_pct"]["value"] == 0.0
        assert 0 < out["metrics"]["setup_programs"]["value"] <= 14
        assert not [name for name in out["metrics"]
                    if name.startswith(("expert_", "router_", "ssm_",
                                        "falconh1_", "compact_"))]
        assert "'ssm_chunks': " in proc.stdout \
            and "'ssm_chunks': 0" not in proc.stdout
        assert "'expert_rows': 0, 'expert_peak_rows': 0" in proc.stdout
    elif trace:
        # counters are read on any backend; device scopes need the chip
        assert out["metrics"]["expert_imbalance_x"]["value"] >= 1.0
        padding = out["metrics"]["token_padding_pct"]["value"]
        if cell == NEMOTRON_CELL:
            # every slot real, every expert held; routed AND state-space:
            # the chunks of the four M layers are counted, and the programs
            # compiled before the window opened are the admitted cells' and
            # the reference's four (14 on the chip; the CPU rehearsal has
            # the parity sample's bucket as a program more)
            assert padding == 0.0
            assert "expert_local_share_pct" not in out["metrics"]
            assert 0 < out["metrics"]["setup_programs"]["value"] <= 15
            assert "'ssm_chunks': " in proc.stdout \
                and "'ssm_chunks': 0" not in proc.stdout
            assert not [name for name in out["metrics"]
                        if name.startswith(("ssm_", "nemotron3_", "ffn_"))]
        elif cell == QWEN_CELL:
            # every slot real; half the router's experts held here: about
            # half the pairs; routed AND recurrent: the chunks of the five
            # L layers are counted, and no device scope is read on a CPU
            assert padding == 0.0
            share = out["metrics"]["expert_local_share_pct"]["value"]
            assert 35 < share < 65
            assert 0 < out["metrics"]["setup_programs"]["value"] <= 16
            assert "'delta_chunks': " in proc.stdout \
                and "'delta_chunks': 0" not in proc.stdout
            assert "'ssm_chunks': 0" in proc.stdout
            assert not [name for name in out["metrics"]
                        if name.startswith(("delta_", "qwen3next_", "ssm_",
                                            "ffn_"))]
        elif cell == JOYAI_CELL:
            # every slot real, as Laguna's; every expert held: no share
            assert padding == 0.0
            assert "expert_local_share_pct" not in out["metrics"]
        elif cell == LAGUNA_CELL:
            # the rehearsal's 128 positions are under the mix's shortest
            # text: every slot real; a quarter of the pairs held here
            assert padding == 0.0
            share = out["metrics"]["expert_local_share_pct"]["value"]
            assert 15 < share < 35
        else:
            # the full-window mix leaves an eighth of the slots empty, the
            # memo mix a third
            assert (5 < padding < 25) if cell in (FULL_CELL, ZAYA_FULL_CELL) \
                else (25 < padding < 60)
        # the CPU runs the XLA form, whose grid visits nothing: the counter
        # stays 0 and the fill is left out
        assert "'expert_tile_rows': 0" in proc.stdout
        for name in ("expert_ffn_ms_per_batch", "cca_mix_ms_per_batch",
                     "cca_mix_roofline_pct", "laguna_attn_core_roofline_pct",
                     "shared_expert_ms_per_batch", "attn_latent_ms_per_batch",
                     "joyai_attn_core_roofline_pct", "expert_tile_fill_pct",
                     "expert_combine_ms_per_batch"):
            assert name not in out["metrics"]
    else:
        assert set(out["metrics"]) == {"txn_per_s", "setup_s"}


def test_the_nemotron3_run_with_the_timed_path_broken_is_not_correct(
        tiny_copy):
    """The cell's run with a part of each batch left out underneath the
    harness (``rehearsal.BREAKS['fan-out']``: the last prediction of every
    microbatch scored and counted, never produced) prints a result whose
    ``correct`` is false: the comparison is of what the timed path itself
    produced."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks/tests/rehearsal.py"),
         str(tiny_copy), "--break", "fan-out", "--workload", NEMOTRON_CELL,
         "--seed", "5000000023", "--seconds", "3", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=600, cwd=tiny_copy)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is False and out["attempted"] > 0
    assert "check parity with the plain float32 reference: ok" in proc.stdout
    assert "every attempted transaction accounted for: FAILED" in proc.stdout \
        or "FAILED" in proc.stdout
