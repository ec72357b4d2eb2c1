"""Qwen3-Next's routed expert FFN (scope ``text/layer*/experts/matmul``:
``down(silu(gate x) * up x)`` over the 256 experts of 512 THIS CHIP HOLDS of
a layer's 512, two grouped kernels a layer on the chip): what the algorithm
needs for the launches the program counted.

The row count is the program's own (``StreamJob.counters['expert_rows']``),
and for this configuration it comes from the device: the sum of the held
experts' group sizes over the six layers — the (token, expert) pairs that
entered the grouped matmuls. The pairs the routers sent to experts that live
on the other chip of the layer's pair (``routed_pairs`` - ``expert_rows``)
are not computed and not charged, nor is padding. It is not taken from the
configuration.

Charged is what ANY implementation has to do: a held row costs ``3 x 2 x
2048 x 512`` = 6.29 MFLOP (gate, up and down). Moved at the least: every
held expert's three matrices once a launch and layer, 3 x 2048 x 512
bfloat16 = 6.29 MB an expert and 1.61 GB a layer, whatever the rows; a held
row's bfloat16 input read once (4,096 B) and the float32 result of its down
matmul written (8,192 B): 12,288 B a row. The bfloat16 SiLU-product rows
between the two calls are the implementation's and are not in the
denominator.

**Memory-bound at the deployed shape, so its metric divides the bytes by the
HBM's rate**: at ~195 rows an expert (~10,000 real tokens x 10 over 512
experts: the batch is the two-chip pair's) a layer is ~50,000 rows x 6.29
MFLOP = 0.31 TFLOP, 1.6 ms at the peak, against 1.61 GB + 50,000 x 12,288 B
= 2.22 GB, 2.7 ms at 819 GB/s: 142 FLOP a byte, under the v5e's ridge of
197e12 / 819e9 = 240 — the smallest experts and the fewest rows a group of
any configuration here: a layer is paced by reading its experts. The bound
changes sides at ~500 rows an expert. The FLOPs are returned too
(``flops``), for a reader that wants the other bound.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 rows and weights (``compute_dtype``)
RESULT_BYTES = 4            # float32 result of the down matmul


def flops(expert_rows: int, *, hidden_size: int, expert_width: int) -> float:
    """gate, up and down of every held row: 3 matmuls x 2 FLOP x rows x
    hidden_size x moe_intermediate_size."""
    return 3.0 * 2.0 * expert_rows * hidden_size * expert_width


def hbm_bytes(expert_rows: int, batches: int, *, hidden_size: int,
              expert_width: int, sparse_layers: int, held_experts: int
              ) -> float:
    """Per launch and layer every held expert's three matrices read once;
    per row the bfloat16 input read once and the float32 result of down
    written."""
    weights = (float(batches) * sparse_layers * held_experts * 3.0
               * hidden_size * expert_width * OPERAND_BYTES)
    per_row = hidden_size * (OPERAND_BYTES + RESULT_BYTES)
    return weights + expert_rows * per_row


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its expert rows."""
    rows = counters.get("expert_rows", 0)
    sizes = dict(hidden_size=cfg["hidden_size"],
                 expert_width=cfg["moe_intermediate_size"])
    return {"flops": flops(rows, **sizes),
            "hbm_bytes": hbm_bytes(
                rows, counters.get("batches", 0), **sizes,
                sparse_layers=cfg["num_hidden_layers"],
                held_experts=cfg["num_experts"]) if rows else 0.0}
