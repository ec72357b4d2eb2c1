"""ZAYA1's routed expert FFN (scope ``text/layer*/experts/matmul``): what
the algorithm needs for the launches the program counted.

The row count is the program's own (``StreamJob.counters['expert_rows']``):
the launches' real tokens x experts per token (one) x layers — every (token,
expert) pair that entered the grouped gate, up and down matmuls; padding is
not routed. It is not taken from the configuration.

Compute-bound at the deployed shapes, so its metric divides by the bf16
peak: one expert's three matrices, 3 x 2048 x 2048 bfloat16 = 25.2 MB, serve
the ~1,300 rows of its group (21,300 real tokens over 16 experts, uneven) —
6 x 2048 x 2048 FLOP a row against ~19 KB of weights and ~36 KB of
activations a row, an arithmetic intensity of ~450 FLOP a byte against the
v5e's ridge of 197e12 / 819e9 = 240. An expert whose group falls under ~500
rows is paced by reading its weights; the share then reads low, and that is
the finding.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 rows and weights (``compute_dtype``)
RESULT_BYTES = 4            # float32 results of the grouped matmuls


def flops(expert_rows: int, *, hidden_size: int, expert_width: int) -> float:
    """gate, up and down of every routed row: 3 matmuls x 2 FLOP x rows x
    hidden_size x moe_intermediate_size."""
    return 3.0 * 2.0 * expert_rows * hidden_size * expert_width


def hbm_bytes(expert_rows: int, batches: int, *, hidden_size: int,
              expert_width: int, layers: int, num_experts: int) -> float:
    """Per launch and layer every expert's three matrices read once; per
    row: the gathered input read by gate and by up, both float32 results
    written and read back, the bfloat16 SiLU-product written and read, the
    float32 result of down written."""
    weights = (float(batches) * layers * num_experts * 3.0
               * hidden_size * expert_width * OPERAND_BYTES)
    per_row = (2.0 * hidden_size * OPERAND_BYTES
               + 2.0 * 2.0 * expert_width * RESULT_BYTES
               + 2.0 * expert_width * OPERAND_BYTES
               + hidden_size * RESULT_BYTES)
    return weights + expert_rows * per_row


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its expert rows."""
    rows = counters.get("expert_rows", 0)
    sizes = dict(hidden_size=cfg["hidden_size"],
                 expert_width=cfg["moe_intermediate_size"])
    return {"flops": flops(rows, **sizes),
            "hbm_bytes": hbm_bytes(
                rows, counters.get("batches", 0), **sizes,
                layers=cfg["num_hidden_layers"],
                num_experts=cfg["num_experts"]) if rows else 0.0}
