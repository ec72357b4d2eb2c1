"""Laguna's attention core (scope ``text/layer*/attn_core``: scores, causal
and window mask, online softmax, weighted sum, fused in one kernel a layer):
what the algorithm needs for the launches the program counted.

The pair counts are the program's own (``StreamJob.counters``):
``attn_visible_pairs_full`` = sum over the launched rows of ``L(L+1)/2`` and
``attn_visible_pairs_sliding`` = sum of ``sum_i min(i+1, sliding_window)``,
``L`` a row's real tokens — the (query, key) pairs a REAL query SEES in one
layer of each kind. Padding is not charged, nor the masked half of a block on
the diagonal or at the window's edge, which the kernel computes and throws
away: the share says how much of the peak goes into scores that count.
Each pair costs 2 x 2 x head_dim FLOP a query head (q.k and p.v); the layers'
kinds and head counts are the configuration's own lists.

Compute-bound, so its metric divides by the bf16 peak: a block of 128
queries of a group of 6 or 9 heads reads its keys and values once (2 x 128 x
128 x 2 B a block pair against 4 x 128 x 128 x 128 x G FLOP), hundreds of
FLOP a byte, above the v5e's ridge of 240.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = 2        # bfloat16 q, k, v and context (``compute_dtype``)


def layer_heads(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Query heads summed over the layers run, by the layer's kind."""
    n = cfg["num_hidden_layers"]
    out = {"full_attention": 0, "sliding_attention": 0}
    for kind, heads in zip(cfg["layer_types"][:n],
                           cfg["num_attention_heads_per_layer"][:n]):
        out[kind] += heads
    return out


def flops(pairs_full: int, pairs_sliding: int, cfg: Dict[str, Any]) -> float:
    heads = layer_heads(cfg)
    return 2.0 * 2.0 * cfg["head_dim"] * (
        heads["full_attention"] * pairs_full
        + heads["sliding_attention"] * pairs_sliding)


def hbm_bytes(token_slots: int, cfg: Dict[str, Any]) -> float:
    """Per layer: q read and the context written at the layer's own width,
    k and v read once; the scores never leave the chip."""
    heads = sum(layer_heads(cfg).values())
    kv = cfg["num_key_value_heads"] * cfg["num_hidden_layers"]
    return (2.0 * heads + 2.0 * kv) * token_slots * cfg["head_dim"] \
        * ACTIVATION_BYTES


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its visible pairs (a program
    from before the counters)."""
    full = counters.get("attn_visible_pairs_full", 0)
    sliding = counters.get("attn_visible_pairs_sliding", 0)
    return {"flops": flops(full, sliding, cfg),
            "hbm_bytes": hbm_bytes(counters.get("token_slots", 0), cfg)
            if full else 0.0}
