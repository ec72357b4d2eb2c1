"""What latent attention puts in front of JoyAI-LLM-Flash's projections
(scope ``text/layer*/attn_latent``): the down-projection of the normed
hidden row into the query latent (``hidden -> q_lora_rank``) and into the
key-value latent with the shared key beside it (``hidden -> kv_lora_rank +
qk_rope_head_dim``), and the two latents' RMSNorms — what the algorithm
needs for the launches the program counted.

Runs on every LAUNCHED slot (``StreamJob.counters['token_slots']``, padding
included: attention is not compacted), in every layer run: 2 x hidden_size x
(q_lora_rank + kv_lora_rank + qk_rope_head_dim) = 2 x 2048 x 2112 = 8.65
MFLOP a slot and layer. The norms' arithmetic is not charged.

Compute-bound, so its metric divides by the bf16 peak: a slot reads its
2,048-wide bfloat16 row once (4 KB) and writes 2,112 float32 latents (8.4
KB), ~700 FLOP a byte against the v5e's ridge of 240; both weight matrices
(8.7 MB) are read once a launch. The two RMSNorms under the same scope are
memory-bound passes over the latents (read float32, write the bfloat16
operand of the up-projection): the share reads under what the two matmuls
alone would, and that difference is the norms.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 rows and weights (``compute_dtype``)
RESULT_BYTES = 4            # float32 latents out of the projections


def _latent_width(cfg: Dict[str, Any]) -> int:
    return (cfg["q_lora_rank"] + cfg["kv_lora_rank"]
            + cfg["qk_rope_head_dim"])


def flops(token_slots: int, cfg: Dict[str, Any]) -> float:
    return (2.0 * cfg["hidden_size"] * _latent_width(cfg)
            * cfg["num_hidden_layers"] * token_slots)


def hbm_bytes(token_slots: int, batches: int, cfg: Dict[str, Any]) -> float:
    """Per layer: a slot's row read, its latents written; both matrices
    once a launch."""
    h, wide = cfg["hidden_size"], _latent_width(cfg)
    return float(cfg["num_hidden_layers"]) * (
        token_slots * (h * OPERAND_BYTES + wide * RESULT_BYTES)
        + batches * h * wide * OPERAND_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program counted no slots."""
    slots = counters.get("token_slots", 0)
    return {"flops": flops(slots, cfg),
            "hbm_bytes": hbm_bytes(slots, counters.get("batches", 0), cfg)
            if slots else 0.0}
