"""Nemotron-3-Nano's routed expert FFN (scope ``text/layer*/experts/matmul``:
``down(relu(up(x))^2)`` over ALL 128 experts of an ``E`` layer, two grouped
kernels a layer on the chip): what the algorithm needs for the launches the
program counted.

The row count is the program's own (``StreamJob.counters['expert_rows']``):
real tokens x 6 experts a token x the ``E`` layers — every expert is held,
so every pair the routers chose enters both grouped matmuls; padding is not
charged. It is not taken from the configuration.

Charged is what ANY implementation has to do, at the PUBLISHED width: a
routed row costs ``2 x 2 x 2688 x 1856`` = 19.96 MFLOP (up and down: there
is no gate matrix), whatever the kernels pad 1,856 = 14 1/2 lane tiles to.
Moved at the least: every expert's two matrices once a launch and layer, 2
x 2688 x 1856 bfloat16 = 19.96 MB an expert and 2.554 GB a layer, whatever
the rows; a routed row's bfloat16 input read once (5,376 B) and the float32
result of its down matmul written (10,752 B): 16,128 B a row. The bfloat16
``relu^2`` rows between the two calls (3,712 B a row written and read) are
the implementation's and are not in the denominator.

**Compute-bound at the deployed shape, so its metric divides the operations
by the bf16 peak**: at ~470 rows an expert (~10,000 real tokens x 6 over 128
experts) a layer is 60,000 rows x 19.96 MFLOP = 1.20 TFLOP, 6.1 ms at the
peak, against 2.554 GB + 60,000 x 16,128 B = 3.52 GB, 4.3 ms at 819 GB/s:
340 FLOP a byte, over the v5e's ridge of 197e12 / 819e9 = 240 (JoyAI's 256
narrower experts at ~310 rows read 218, under it). The bound changes sides
at ~270 rows an expert (a batch of ~5,800 real tokens), under every batch
this traffic makes. The bytes are returned too (``hbm_bytes``), for a reader
that wants the other bound.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 rows and weights (``compute_dtype``)
RESULT_BYTES = 4            # float32 result of the down matmul


def flops(expert_rows: int, *, hidden_size: int, expert_width: int) -> float:
    """up and down of every routed row: 2 matmuls x 2 FLOP x rows x
    hidden_size x moe_intermediate_size."""
    return 2.0 * 2.0 * expert_rows * hidden_size * expert_width


def hbm_bytes(expert_rows: int, batches: int, *, hidden_size: int,
              expert_width: int, sparse_layers: int, experts: int) -> float:
    """Per launch and ``E`` layer every expert's two matrices read once;
    per row the bfloat16 input read once and the float32 result of down
    written."""
    weights = (float(batches) * sparse_layers * experts * 2.0
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
                sparse_layers=cfg["hybrid_override_pattern"].count("E"),
                experts=cfg["n_routed_experts"]) if rows else 0.0}
