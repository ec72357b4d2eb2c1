"""JoyAI-LLM-Flash's latent attention core (scope ``text/layer*/attn_core``:
both score contractions, causal mask, online softmax, weighted sum, fused in
one kernel a layer): what the algorithm needs for the launches the program
counted.

The pair count is the program's own (``StreamJob.counters``):
``attn_visible_pairs_full`` = sum over the launched rows of ``L(L+1)/2``,
``L`` a row's real tokens — the (query, key) pairs a REAL query SEES in one
causal layer (every layer is full attention; ``attn_visible_pairs_sliding``
is 0 for this encoder). Each pair costs, a head, a score over
``qk_nope_head_dim + qk_rope_head_dim`` = 192 dims and a value of
``v_head_dim`` = 128: 2 x (192 + 128) FLOP, times 32 heads, times the layers
run.

What the kernel computes beyond that, and is not charged: padding inside a
row's last real block; the masked half of every block on the diagonal; the
shared 64-dim term as a contraction over a whole 128-lane tile (a head's
lanes of the tile of two heads' shared parts, the other head's zeroed: on a
128-deep MXU that costs what 64 deep costs, so a pair costs the array 2 x
(256 + 128) where the algorithm needs 2 x 320); the interleaved rotation of
a block's shared query parts and, once a (row, pair of heads), of the shared
key. The share says how much of the peak goes into scores that count.

Compute-bound, so its metric divides by the bf16 peak: a step of two heads
reads its keys and values once a row (2 x 2 x 2,048 x 128 x 2 B) for up to
16 query blocks, hundreds of FLOP a byte, above the v5e's ridge of 240.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = 2        # bfloat16 q, k, v and context (``compute_dtype``)
SHARED_BYTES = 4            # float32 shared parts, rotated in the kernel


def flops(pairs_full: int, cfg: Dict[str, Any]) -> float:
    return (2.0 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                   + cfg["v_head_dim"])
            * cfg["num_attention_heads"] * cfg["num_hidden_layers"]
            * pairs_full)


def hbm_bytes(token_slots: int, cfg: Dict[str, Any]) -> float:
    """Per layer and launched slot: q's and k's own parts and v read and the
    context written, bfloat16, at ``heads x 128``; the queries' shared parts
    (``heads x 64``) and the one shared key, float32; the scores never leave
    the chip."""
    heads = cfg["num_attention_heads"]
    own = heads * (2 * cfg["qk_nope_head_dim"] + 2 * cfg["v_head_dim"])
    shared = (heads + 1) * cfg["qk_rope_head_dim"]
    return float(cfg["num_hidden_layers"]) * token_slots * (
        own * ACTIVATION_BYTES + shared * SHARED_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its visible pairs (a program
    from before the counters)."""
    full = counters.get("attn_visible_pairs_full", 0)
    return {"flops": flops(full, cfg),
            "hbm_bytes": hbm_bytes(counters.get("token_slots", 0), cfg)
            if full else 0.0}
