"""Falcon-H1's SwiGLU MLP (scope ``text/layer*/ffn``: gate, up, SiLU and the
product, down, with their two muP multipliers): what the algorithm needs
for the launches the program counted.

The token count is the program's own (``StreamJob.counters``):
``token_slots`` = sum of bucket rows x ``text_len``. A dense encoder's MLP
runs on every launched slot, padding included, so every slot is charged. It
is not taken from the configuration.

Compute-bound, so its metric divides by the bf16 peak: 3 x 5120 x 21504
bfloat16 weights (0.66 GB) are read once a launch and layer against 6 x
16,384 x 5120 x 21504 = 10.8 TFLOP — thousands of FLOP a byte against the
v5e's ridge of 197e12 / 819e9 = 240. The float32 gate and up results the
XLA form writes and reads back between its matmuls (1.41 GB each a layer)
are the implementation's: not charged, and what a fused gate / up kernel
would win shows as a higher share.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 rows and weights (``compute_dtype``)
RESULT_BYTES = 4            # float32 result of down


def flops(token_slots: int, cfg: Dict[str, Any]) -> float:
    """gate, up and down of every layer: 3 matmuls x 2 FLOP x slots x
    hidden_size x intermediate_size."""
    return (3.0 * 2.0 * token_slots * cfg["hidden_size"]
            * cfg["intermediate_size"] * cfg["num_hidden_layers"])


def hbm_bytes(token_slots: int, batches: int, cfg: Dict[str, Any]) -> float:
    """Per layer: the normed rows read once in bfloat16, the float32 result
    of down written, and the three matrices read once a launch."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return float(cfg["num_hidden_layers"]) * (
        token_slots * h * (OPERAND_BYTES + RESULT_BYTES)
        + batches * 3.0 * h * f * OPERAND_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its tokens."""
    slots = counters.get("token_slots", 0)
    return {"flops": flops(slots, cfg),
            "hbm_bytes": hbm_bytes(slots, counters.get("batches", 0), cfg)
            if slots else 0.0}
