"""JoyAI-LLM-Flash's routed expert FFN (scope ``text/layer*/experts/matmul``)
over ALL 256 experts of a layer: what the algorithm needs for the launches
the program counted.

The row count is the program's own (``StreamJob.counters['expert_rows']``):
real tokens x 8 experts a token x the sparse layers — every expert is held,
so every pair the routers chose enters the grouped gate, up and down
matmuls; padding is not charged. It is not taken from the configuration.

**Memory-bound at the deployed shape, so its metric divides the bytes by the
HBM's rate.** Charged is what ANY implementation has to move, not what this
one moves: every expert's three matrices once a launch and layer, 3 x 2048 x
768 bfloat16 = 9.44 MB an expert and 2.42 GB a layer, whatever the rows; a
routed row's bfloat16 input read once (4,096 B) and the float32 result of its
down matmul written (8,192 B): 12,288 B a row. The program's three grouped
calls also write the float32 gate and up results and read them back, write
and read the SiLU product and read the input twice (19.5 KB a row more): a
fused gate / up kernel would not, so that traffic is the implementation's and
is NOT in the denominator, and the share RISES when it goes. At ~310 rows a
group (~10,000 real tokens x 8 over 256 experts) a layer is 6 x 2048 x 768 =
9.44 MFLOP a row x 78,000 rows = 0.74 TFLOP, 3.7 ms at the peak, against
2.42 GB + 78,000 x 12,288 B = 3.37 GB, 4.1 ms at 819 GB/s: ~218 FLOP a byte,
under the v5e's ridge of 197e12 / 819e9 = 240, so the bytes' bound is the
larger, by a little. The FLOPs are returned too (``flops``), for a reader
that wants the other bound.
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
              expert_width: int, sparse_layers: int, experts: int) -> float:
    """Per launch and sparse layer every expert's three matrices read once;
    per row the bfloat16 input read once and the float32 result of down
    written. What the three-call form moves between its calls is not
    charged (the module docstring)."""
    weights = (float(batches) * sparse_layers * experts * 3.0
               * hidden_size * expert_width * OPERAND_BYTES)
    per_row = hidden_size * (OPERAND_BYTES + RESULT_BYTES)
    return weights + expert_rows * per_row


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its expert rows."""
    rows = counters.get("expert_rows", 0)
    sizes = dict(hidden_size=cfg["hidden_size"],
                 expert_width=cfg["moe_intermediate_size"])
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return {"flops": flops(rows, **sizes),
            "hbm_bytes": hbm_bytes(
                rows, counters.get("batches", 0), **sizes,
                sparse_layers=sparse,
                experts=cfg["n_routed_experts"]) if rows else 0.0}
