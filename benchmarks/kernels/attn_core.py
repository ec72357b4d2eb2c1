"""The text branch's attention core (scope ``text/layer*/attn_core``:
scores, mask, softmax, weighted sum): what the algorithm needs for the
launches the program counted.

The token counts are the program's own (``StreamJob.counters``):
``token_slots`` = sum of bucket rows x padded ``text_len`` and
``token_slots_sq`` = sum of bucket rows x ``text_len``^2. Every launched
score is charged, padding included.

Compute-bound ONCE THE SCORES STAY ON THE CHIP, so its metric divides by the
bf16 peak: per (row, head) the kernel reads q, k, v and writes the context,
an intensity of ~T/2 FLOP per byte in bf16 — 256 at T = 512, above the
v5e's ridge of 197e12 / 819e9 = 240. A core that sends its f32 scores
through HBM (``attention_reference``) is memory-bound and reads a low share
of this roofline: the share says how far the kernel is from what a fused
core can reach, not how well it uses HBM.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = 2        # bfloat16 q, k, v and context (``compute_dtype``)


def flops(token_slots_sq: int, *, heads: int, head_dim: int, layers: int
          ) -> float:
    """Scores and weighted sum of every layer: 2 matmuls x 2 FLOP x heads x
    T^2 x head_dim per row, with rows x T^2 summed as launched."""
    return 2.0 * 2.0 * heads * token_slots_sq * head_dim * layers


def hbm_bytes(token_slots: int, *, heads: int, head_dim: int, layers: int
              ) -> float:
    """Per layer: q, k and v read and the context written once; the scores
    never leave the chip."""
    return 4.0 * token_slots * heads * head_dim * ACTIVATION_BYTES * layers


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its tokens (a program from
    before the counters)."""
    sizes = dict(heads=cfg["n_heads"], head_dim=cfg["dim"] // cfg["n_heads"],
                 layers=cfg["n_layers"])
    return {"flops": flops(counters.get("token_slots_sq", 0), **sizes),
            "hbm_bytes": hbm_bytes(counters.get("token_slots", 0), **sizes)}
