"""Nemotron-3-Nano's attention core (scope ``text/layer*/attn_core``: scores,
causal mask, online softmax, weighted sum, fused in one kernel in each ``*``
layer — Laguna's blocked causal core with no window, no gate and NO
rotation, sixteen query heads a key-value head): what the algorithm needs
for the launches the program counted.

The pair count is the program's own (``StreamJob.counters``):
``attn_visible_pairs_full`` = sum over the launched rows of ``L(L+1)/2``,
``L`` a row's real tokens — the (query, key) pairs a REAL query SEES in ONE
causal layer. Padding is not charged, nor the masked half of a block on the
diagonal, which the kernel computes and throws away: the share says how much
of the peak goes into scores that count. Each pair costs 2 x 2 x head_dim
FLOP a query head (q.k and p.v), over ``num_attention_heads`` heads and the
``*`` layers of the pattern run (one of nine here), not every layer.

Compute-bound, so its metric divides by the bf16 peak: a block of 128
queries of a group of 16 heads reads its keys and values once (2 x 128 x 128
x 2 B a block pair against 4 x 128 x 128 x 128 x 16 FLOP), two thousand FLOP
a byte, far above the v5e's ridge of 240.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = 2        # bfloat16 q, k, v and context (``compute_dtype``)


def attention_layers(cfg: Dict[str, Any]) -> int:
    return cfg["hybrid_override_pattern"].count("*")


def flops(pairs: int, cfg: Dict[str, Any]) -> float:
    return (2.0 * 2.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * attention_layers(cfg) * pairs)


def hbm_bytes(token_slots: int, cfg: Dict[str, Any]) -> float:
    """Per ``*`` layer: q read and the context written, k and v read once;
    the scores never leave the chip."""
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return ((2.0 * heads + 2.0 * kv) * attention_layers(cfg)
            * token_slots * cfg["head_dim"] * ACTIVATION_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its visible pairs."""
    pairs = counters.get("attn_visible_pairs_full", 0)
    return {"flops": flops(pairs, cfg),
            "hbm_bytes": hbm_bytes(counters.get("token_slots", 0), cfg)
            if pairs else 0.0}
