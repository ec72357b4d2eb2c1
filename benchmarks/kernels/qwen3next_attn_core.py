"""Qwen3-Next's attention core (scope ``text/layer*/attn_core``: per-head
norms, rotation, scores, causal mask, online softmax, weighted sum and the
elementwise gate, fused in one kernel in each ``F`` layer — Laguna's blocked
causal core at heads of 256, two lane tiles, eight query heads a key-value
head): what the algorithm needs for the launches the program counted.

The pair count is the program's own (``StreamJob.counters``):
``attn_visible_pairs_full`` = sum over the launched rows of ``L(L+1)/2``,
``L`` a row's real tokens — the (query, key) pairs a REAL query SEES in ONE
causal layer. Padding is not charged, nor the masked half of a block on the
diagonal, which the kernel computes and throws away: the share says how much
of the peak goes into scores that count. Each pair costs 2 x 2 x head_dim
FLOP a query head (q.k and p.v), over ``num_attention_heads`` heads and the
``F`` layers run (one of six here), not every layer. The norms, the
rotation and the gate are elementwise and not charged.

Compute-bound, so its metric divides by the bf16 peak: a block of 128
queries of a group of 8 heads reads its keys and values once (2 x 128 x 256
x 2 B a block pair against 4 x 128 x 128 x 256 x 8 FLOP), a thousand FLOP a
byte, far above the v5e's ridge of 240.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = 2        # bfloat16 v and context (``compute_dtype``)
FLOAT_BYTES = 4             # float32 q, k and gates as projected


def attention_layers(cfg: Dict[str, Any]) -> int:
    return cfg["num_hidden_layers"] // cfg["full_attention_interval"]


def flops(pairs: int, cfg: Dict[str, Any]) -> float:
    return (2.0 * 2.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * attention_layers(cfg) * pairs)


def hbm_bytes(token_slots: int, cfg: Dict[str, Any]) -> float:
    """Per ``F`` layer: q and the gates read in float32 and the context
    written, k (float32) and v read once; the scores never leave the
    chip."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a_slot = (heads * (2 * FLOAT_BYTES + ACTIVATION_BYTES)
              + kv * (FLOAT_BYTES + ACTIVATION_BYTES)) * cfg["head_dim"]
    return float(attention_layers(cfg)) * token_slots * a_slot


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its visible pairs."""
    pairs = counters.get("attn_visible_pairs_full", 0)
    return {"flops": flops(pairs, cfg),
            "hbm_bytes": hbm_bytes(counters.get("token_slots", 0), cfg)
            if pairs else 0.0}
