"""The five-branch ensemble with Qwen3-Next-80B-A3B-Instruct's stack as its
text branch: the architecture of a configuration file that names
``"builder": "qwen3next_builder"``.

The file's keys are ``Qwen/Qwen3-Next-80B-A3B-Instruct``'s own, every one of
them, and ``models/qwen3_next.Qwen3NextConfig`` holds each under the same
name: ``qwen3next_config`` hands the file's values over key for key, beside
three that are not the source's — ``num_experts`` is what a layer HOLDS here
(``expert_share`` says of how many chips that share a layer, and which of
them this is: the router's width is the held count times the chips, and at
the published widths the builder refuses a file in which that is not
``published.num_experts``), and ``delta_chunk`` is the scan's chunk
(``assumed.delta_chunk`` says where it comes from). The scorer is built
through the seam ``rtfd serve`` uses; the only things made here are the
weights, on the device in one jitted call from the seed (bfloat16, tensor by
tensor: no float32 copy of the 5.4 B parameters exists).

The construction seam is ``olmoe_builder.py``'s: the CLASS of the text
configuration picks the encoder (``scoring/pipeline.TEXT_ENCODERS``); there
is no flag. This encoder's layers mix by a Gated-DeltaNet recurrence three
times in four and by gated softmax attention the fourth, and every layer's
second half is routed — so it is routed (capacity rungs, the program's
second small output ``i32[3, layers]``, ``StreamJob.counters['expert_rows']``
beside ``['routed_pairs']``: a share of the experts, as Laguna's) AND
recurrent (``['delta_chunks']``, counted over the ``L`` layers) at once.
What the file holds and the program does not run is refused by value, not
ignored (``Qwen3NextConfig`` raises on a dense layer, a sparse step, a
scaled rotation, a window ...); the keys read by nothing are listed under
``not_run`` in the file, each with its reason.

A program without that module (the parent of the PR that added it) cannot
run this configuration: loading this builder then stops the run at once,
before JAX is imported, with a non-zero exit.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict

import numpy as np

from benchmarks.harness import spec, system

if importlib.util.find_spec(
        "realtime_fraud_detection_tpu.models.qwen3_next") is None:
    raise SystemExit(
        "benchmark spec error: builder 'qwen3next_builder' needs "
        "realtime_fraud_detection_tpu/models/qwen3_next.py, which this "
        "program does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` a layer's two
# norms (``ln``), its routed half (``router``, ``experts``,
# ``shared_expert``) and the scopes of its mixer's KIND: a Gated-DeltaNet
# layer ``delta_proj``, ``delta_conv``, ``delta_scan``; an attention layer
# ``attn_proj``, ``attn_core``
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "ln": {},
            "delta_proj": {}, "delta_conv": {}, "delta_scan": {},
            "attn_proj": {}, "attn_core": {},
            "router": {}, "shared_expert": {},
            "experts": {"dispatch": {}, "matmul": {}, "combine": {}},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# Two value heads a key head, eight query heads a key-value head, a quarter
# of a head rotated and half the router's experts held, as published; a
# chunk of 16 so that a rehearsal's 128 positions are eight chunks
TINY = {"hidden_size": 256, "intermediate_size": 512,
        "moe_intermediate_size": 128, "shared_expert_intermediate_size": 128,
        "head_dim": 32, "num_attention_heads": 16, "num_key_value_heads": 2,
        "linear_key_head_dim": 32, "linear_value_head_dim": 32,
        "linear_num_key_heads": 4, "linear_num_value_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 4, "delta_chunk": 16}


def layer_kinds(cfg: Dict[str, Any]) -> str:
    """``L`` or ``F`` a layer: ``F`` every ``full_attention_interval``-th."""
    interval = cfg["full_attention_interval"]
    return "".join("F" if (i + 1) % interval == 0 else "L"
                   for i in range(cfg["num_hidden_layers"]))


def qwen3next_config(cfg: Dict[str, Any]):
    """``Qwen3NextConfig`` from the published ``config.json`` keys of the
    file: every key of ``published``, under its own name."""
    from realtime_fraud_detection_tpu.models.qwen3_next import (
        Qwen3NextConfig,
    )

    if cfg["tie_word_embeddings"]:
        raise ValueError("qwen3next_builder: tied embeddings are not what "
                         "the file's not_run says of the language-model head")
    share, held = cfg["expert_share"], cfg["num_experts"]
    if cfg["hidden_size"] == cfg["published"]["hidden_size"] \
            and held * share["chips"] != cfg["published"]["num_experts"]:
        raise ValueError(
            f"qwen3next_builder: {held} experts held on each of "
            f"{share['chips']} chips are not the published "
            f"{cfg['published']['num_experts']}")
    keys = {key: cfg[key] for key in cfg["published"]}
    keys["mlp_only_layers"] = tuple(keys["mlp_only_layers"])
    return Qwen3NextConfig(
        **keys, router_experts=held * share["chips"],
        expert_offset=held * share["index"], delta_chunk=cfg["delta_chunk"])


def make_models(cfg: Dict[str, Any], seed: int, sample_features: np.ndarray):
    """All five branches, made on the device in one jitted call from the
    seed; trees and isolation forest then replaced by seeded ensembles of
    the same sizes split at quantiles of ``sample_features``."""
    import jax

    from realtime_fraud_detection_tpu.scoring import ScorerConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    sc = ScorerConfig()
    a = cfg["assumed"]
    config = qwen3next_config(cfg)

    def init_qwen3next_scoring_models(key):
        # a named program: the compile ledger reads jit(<this name>)
        return init_scoring_models(
            key, bert_config=config, feature_dim=sc.feature_dim,
            node_dim=sc.node_dim, n_trees=a["n_trees"],
            tree_depth=a["tree_depth"])

    return system.seeded_forests(
        jax.jit(init_qwen3next_scoring_models)(jax.random.PRNGKey(seed)), cfg,
        seed, sample_features)


def make_scorer(cfg: Dict[str, Any], seed: int, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0   # no fixed-port listener
    scorer = FraudScorer(
        config, models=models, bert_config=qwen3next_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def text_matmul_flops_per_row(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one row of ``text_len`` real tokens needs in the layers
    run, by part: 2 x M x N x K per matmul, each part times the layers of
    its kind, the held experts at the even share of a token's experts."""
    t, h = cfg["text_len"], cfg["hidden_size"]
    kinds = layer_kinds(cfg)
    linear, full, layers = kinds.count("L"), kinds.count("F"), len(kinds)
    heads, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"])
    key_dim = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    value_dim = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    chips = cfg["expert_share"]["chips"]
    return {
        # in_proj_qkvz, in_proj_ba and out_proj of the mixer
        "delta_proj": linear * 2.0 * t * h * (
            2 * key_dim + 3 * value_dim + 2 * cfg["linear_num_value_heads"]),
        # the chunked algorithm's count, kept with the scan's roofline share
        "delta_scan": linear * t * spec.kernel(
            "qwen3next_delta_scan").flops_per_slot(cfg),
        # q with its gate, k, v and o
        "attn_proj": full * 2.0 * t * h * d * (3 * heads + 2 * kv),
        # a visible pair: a score and a weighted value over d dims, a head
        "cores": full * 2.0 * 2.0 * heads * d * (t * (t + 1) // 2),
        "router": layers * 2.0 * t * h * cfg["num_experts"] * chips,
        # gate, up and down of a token's experts that live here
        "experts": layers * 6.0 * t * h * cfg["moe_intermediate_size"]
        * cfg["num_experts_per_tok"] / chips,
        "shared_expert": layers * 2.0 * t * h * (
            3 * cfg["shared_expert_intermediate_size"] + 1),
    }


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs with
    every slot real (``matmul_util_pct``): the mixers' projections and
    scans, attention's projections and its core's visible (query, key)
    pairs, the routers, the shared expert and the routed experts at the
    EVEN share — five of a token's ten experts live on this chip of two —
    plus the LSTM and GNN as ``harness/flops.py`` counts them. **The stale
    kind** (PERF.md section 7, PR 29 (i)): the interface hands a builder the
    configuration alone, not what a batch launched, so this charges padding
    slots as real ones, which is right of the ``L`` mixers (they compute
    every slot) and not of the rest; the roofline shares of this
    configuration's kernels follow the program's counters instead."""
    from benchmarks.harness import flops

    b = cfg["job"]["max_batch"]
    text = sum(text_matmul_flops_per_row(cfg).values())
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["moe_intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=cfg["text_len"], batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
