"""The five-branch ensemble with Laguna-S-2.1's block as its text branch: the
architecture of a configuration file that names ``"builder":
"laguna_builder"``.

The file's keys are ``poolside/Laguna-S-2.1``'s own, nested groups and
per-layer lists copied whole (``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer``, ``gating_types``, ``rope_parameters``:
the first ``num_hidden_layers`` entries are run). The scorer is built
through the seam ``rtfd serve`` uses; the only things made here are the
weights, on the device in one jitted call from the seed (bfloat16, tensor by
tensor: no float32 copy of the 3.2 B parameters exists).

The construction seam is ``olmoe_builder.py``'s with the model module's
config class ``models/laguna.LagunaConfig``: the CLASS of the text
configuration picks the encoder (``scoring/pipeline.text_predict``,
``routed_text``); there is no flag. Two things this architecture asks of a
builder that its siblings' did not:

- **a per-layer shape.** No layer count times one block describes the
  encoder: the builder hands the config's lists to ``LagunaConfig`` cut to
  the layers run, refuses a list it cannot hold (a gating other than
  ``per_head``, a sparse layer 0, ``mlp_only_layers`` against
  ``mlp_layer_types``), and ``matmul_flops_per_batch`` sums over the layers
  by kind. Two of the source's names mean something else to the program's
  routed-encoder seam (``scoring/pipeline.RoutedText`` reads
  ``intermediate_size`` as ONE expert's width and ``num_experts`` as the
  groups of the grouped matmul): the file keeps the source's meaning
  (``intermediate_size`` 12,288 is layer 0's dense MLP), and
  ``laguna_config`` hands them over under ``LagunaConfig``'s own names
  (``dense_intermediate_size``; ``router_experts`` beside the held
  ``num_experts``).
- **a chip's share of the experts** (the ``model-configs`` guide, section
  4). The file's ``num_experts`` is how many routed experts of a layer this
  chip HOLDS (listed in ``reduced``; ``published.num_experts`` stays the
  router's width) and ``expert_share`` says of how many chips that share a
  layer this is which: ``{"chips": 4, "index": 0}`` = experts 0-63 of 256.
  The router runs whole and a token's weights are normalised over all ten
  of its experts; the program computes the held experts' part, the
  reference (``laguna_reference.py``) is given the same share, and nothing
  stands in for the absent chips. ``StreamJob.counters['expert_rows']``
  then comes from the device (the pairs that entered a held group) beside
  ``['routed_pairs']`` (all the routers chose).

The program returns the routed encoders' second small output (here ``i32[2,
sparse layers]``: the largest held group, the held pairs) that
``FraudScorer`` turns into ``StreamJob.counters['expert_peak_rows']`` and
``['expert_rows']``, beside ``['attn_visible_pairs_full']`` /
``['attn_visible_pairs_sliding']`` from the rows' lengths.

A program without that module (the parent of the PR that added it) cannot
run this configuration: loading this builder then stops the run at once,
before JAX is imported, with a non-zero exit.
"""

from __future__ import annotations

import functools
import importlib.util
from typing import Any, Dict

import numpy as np

from benchmarks.harness import system

if importlib.util.find_spec(
        "realtime_fraud_detection_tpu.models.laguna") is None:
    raise SystemExit(
        "benchmark spec error: builder 'laguna_builder' needs "
        "realtime_fraud_detection_tpu/models/laguna.py, which this program "
        "does not have")

# the device scopes this architecture's program writes (obs/scopes.py),
# written again on this side: the four small branches and the packed
# entry's own work as every builder's, and under ``text`` the Laguna block
# (``ffn`` is layer 0's dense MLP; the sparse layers have ``router``,
# ``experts`` and ``shared_expert``)
_BRANCHES = ("trees", "lstm", "text", "gnn", "iforest", "rules", "blend",
             "unpack", "repack")
VOCABULARY = {
    **{branch: {} for branch in _BRANCHES},
    "text": {
        "embed": {}, "head": {},
        "layer*": {
            "attn_proj": {}, "attn_core": {}, "ln": {}, "ffn": {},
            "router": {}, "shared_expert": {},
            "experts": {"dispatch": {}, "matmul": {}, "combine": {}},
        },
    },
}

# a CPU rehearsal's widths (``tests/rehearsal.py``): data, not code paths.
# The lists are as long as the source's; a window of 32 binds at the
# rehearsal's 128 positions; 8 of 32 experts are held here
TINY = {"hidden_size": 256, "intermediate_size": 512,
        "moe_intermediate_size": 256, "shared_expert_intermediate_size": 256,
        "head_dim": 32, "num_key_value_heads": 2,
        "num_attention_heads_per_layer": [4, 6, 6, 6] * 12,
        "sliding_window": 32, "num_experts": 8}

_SAME = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
         "num_experts_per_tok", "moe_intermediate_size",
         "shared_expert_intermediate_size", "norm_topk_prob",
         "moe_routed_scaling_factor", "rms_norm_eps",
         "max_position_embeddings")
_ROPE_KEYS = ("rope_type", "rope_theta", "partial_rotary_factor", "factor",
              "original_max_position_embeddings", "beta_fast", "beta_slow",
              "attention_factor")


def laguna_config(cfg: Dict[str, Any]):
    """``LagunaConfig`` from the published ``config.json`` keys of the file,
    its lists cut to the layers run."""
    from realtime_fraud_detection_tpu.models.laguna import (
        LagunaConfig,
        LagunaRope,
    )

    n = cfg["num_hidden_layers"]
    mlp = cfg["mlp_layer_types"][:n]
    if set(cfg["gating_types"][:n]) != {"per_head"} \
            or cfg["gating"] != "per-head":
        raise ValueError("laguna_builder holds a per-head output gate only: "
                         f"{cfg['gating']!r}, {cfg['gating_types'][:n]}")
    dense = [i for i, kind in enumerate(mlp) if kind == "dense"]
    if dense != [i for i in cfg["mlp_only_layers"] if i < n]:
        raise ValueError(f"laguna_builder: mlp_layer_types {mlp} against "
                         f"mlp_only_layers {cfg['mlp_only_layers']}")
    if cfg["moe_router_logit_softcapping"] \
            or cfg["moe_apply_router_weight_on_input"] \
            or cfg["attention_bias"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("laguna_builder: a router softcap, router weights "
                         "on the input, attention biases or a sparse step "
                         "are not what the equations hold")

    def rope(kind: str) -> LagunaRope:
        entry = cfg["rope_parameters"][kind]
        return LagunaRope(**{k: entry[k] for k in _ROPE_KEYS if k in entry})

    share = cfg["expert_share"]
    held = cfg["num_experts"]
    return LagunaConfig(
        dense_intermediate_size=cfg["intermediate_size"],
        layer_types=tuple(cfg["layer_types"][:n]),
        mlp_layer_types=tuple(mlp),
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"][:n]),
        rope_full=rope("full_attention"),
        rope_sliding=rope("sliding_attention"),
        router_experts=held * share["chips"],
        expert_offset=held * share["index"],
        **{k: cfg[k] for k in _SAME})


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
    init = jax.jit(functools.partial(
        init_scoring_models, bert_config=laguna_config(cfg),
        feature_dim=sc.feature_dim, node_dim=sc.node_dim,
        n_trees=a["n_trees"], tree_depth=a["tree_depth"]))
    return system.seeded_forests(init(jax.random.PRNGKey(seed)), cfg, seed,
                                 sample_features)


def make_scorer(cfg: Dict[str, Any], seed: int, models, users, merchants):
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    config.monitoring.prometheus_port = 0   # no fixed-port listener
    scorer = FraudScorer(
        config, models=models, bert_config=laguna_config(cfg),
        scorer_config=ScorerConfig(text_len=cfg["text_len"]), seed=seed,
        mesh=build_mesh(devices=jax.devices()[:1]))
    scorer.seed_profiles(users, merchants)
    return scorer


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs a causal layer computes for a row of ``seq_len``
    real tokens: ``L(L+1)/2``, under a window ``sum_i min(i+1, window)``."""
    full = seq_len * (seq_len + 1) // 2
    if window is None or seq_len <= window:
        return full
    beyond = seq_len - window
    return full - beyond * (beyond + 1) // 2


def text_matmul_flops_per_row(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Matmul FLOPs one row of ``text_len`` real tokens needs in the layers
    run, by part: 2 x M x N x K per matmul, the held experts at the even
    share of a token's experts."""
    t, h, d = cfg["text_len"], cfg["hidden_size"], cfg["head_dim"]
    n = cfg["num_hidden_layers"]
    kv_w = cfg["num_key_value_heads"] * d
    share = 1.0 / cfg["expert_share"]["chips"]
    out = dict.fromkeys(("projections", "cores", "dense_mlp", "router",
                         "experts", "shared_expert"), 0.0)
    for heads, kind, mlp in zip(cfg["num_attention_heads_per_layer"][:n],
                                cfg["layer_types"][:n],
                                cfg["mlp_layer_types"][:n]):
        # W_q, W_k, W_v, the head gate, W_o
        out["projections"] += 2.0 * t * h * (2 * heads * d + 2 * kv_w + heads)
        window = cfg["sliding_window"] if kind == "sliding_attention" \
            else None
        out["cores"] += 4.0 * heads * d * visible_pairs(t, window)
        if mlp == "dense":
            out["dense_mlp"] += 6.0 * t * h * cfg["intermediate_size"]
            continue
        out["router"] += 2.0 * t * h * cfg["num_experts"] \
            * cfg["expert_share"]["chips"]
        out["experts"] += (6.0 * t * h * cfg["moe_intermediate_size"]
                           * cfg["num_experts_per_tok"] * share)
        out["shared_expert"] += (6.0 * t * h
                                 * cfg["shared_expert_intermediate_size"])
    return out


def matmul_flops_per_batch(cfg: Dict[str, Any]) -> float:
    """Matmul FLOPs one full-bucket call of the fused program needs with
    every slot real (``matmul_util_pct``): the projections, the cores'
    visible (query, key) pairs (the kernel skips what a causal or windowed
    query cannot see), layer 0's dense MLP, the routers, the shared expert,
    and the routed experts at the EVEN share — 2.5 of a token's ten experts
    live on this chip of four — plus the LSTM and GNN as
    ``harness/flops.py`` counts them. **The stale kind** (PERF.md section 7,
    PR 29 (i)): the interface hands a builder the configuration alone, not
    what a batch launched, so this charges padding slots as real ones and
    the even share whatever the routers chose; the roofline shares of this
    configuration's kernels follow the program's counters instead."""
    from benchmarks.harness import flops

    b = cfg["job"]["max_batch"]
    text = sum(text_matmul_flops_per_row(cfg).values())
    small = flops.ensemble_matmul_flops(
        hidden=cfg["hidden_size"], intermediate=cfg["moe_intermediate_size"],
        layers=cfg["num_hidden_layers"], text_len=cfg["text_len"], batch=b)
    return float(b * text + small["lstm_sequential"] + small["graph_neural"])
