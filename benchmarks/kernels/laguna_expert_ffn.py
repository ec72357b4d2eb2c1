"""Laguna's routed expert FFN (scope ``text/layer*/experts/matmul``) on the
experts THIS CHIP HOLDS: what the algorithm needs for the launches the
program counted.

The row count is the program's own (``StreamJob.counters['expert_rows']``),
and for this configuration it comes from the device: the sum of the held
experts' group sizes over the sparse layers — the (token, expert) pairs that
entered the grouped gate, up and down matmuls. The pairs the routers sent to
experts that live on the other chips of the layer's group
(``routed_pairs`` - ``expert_rows``) are not computed and not charged, nor is
padding. It is not taken from the configuration.

Compute-bound at the deployed shapes, so its metric divides by the bf16
peak: one expert's three matrices, 3 x 3072 x 1024 bfloat16 = 18.9 MB, serve
the ~400 rows of its group (10,100 real tokens x 10 over 256 experts: the
batch is the four-chip group's) — 6 x 3072 x 1024 FLOP a row against ~47 KB
of weights and ~38 KB of activations a row, ~220 FLOP a byte, at the v5e's
ridge of 197e12 / 819e9 = 240: where 256-expert models are served. An
expert whose group falls well under that is paced by reading its weights;
the share then reads low, and that is the finding.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 rows and weights (``compute_dtype``)
RESULT_BYTES = 4            # float32 results of the grouped matmuls


def flops(expert_rows: int, *, hidden_size: int, expert_width: int) -> float:
    """gate, up and down of every held row: 3 matmuls x 2 FLOP x rows x
    hidden_size x moe_intermediate_size."""
    return 3.0 * 2.0 * expert_rows * hidden_size * expert_width


def hbm_bytes(expert_rows: int, batches: int, *, hidden_size: int,
              expert_width: int, sparse_layers: int, held_experts: int
              ) -> float:
    """Per launch and sparse layer every held expert's three matrices read
    once; per row: the gathered input read by gate and by up, both float32
    results written and read back, the bfloat16 SiLU-product written and
    read, the float32 result of down written."""
    weights = (float(batches) * sparse_layers * held_experts * 3.0
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
    sparse = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count("sparse")
    return {"flops": flops(rows, **sizes),
            "hbm_bytes": hbm_bytes(
                rows, counters.get("batches", 0), **sizes,
                sparse_layers=sparse,
                held_experts=cfg["num_experts"]) if rows else 0.0}
