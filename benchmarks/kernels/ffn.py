"""The text branch's feed-forward kernel (scope ``text/layer*/ffn``): what
the algorithm needs for the launches the program counted.

The token count is the program's own (``StreamJob.counters``, summed per
launched microbatch from ``PendingScore``): ``token_slots`` = sum of bucket
rows x padded ``text_len``. It is not taken from the configuration, so a
program that launches shorter text is charged for the work it really issued
and cannot read over 100% of a roofline.

Compute-bound at the deployed shapes, so its metric divides by the bf16
peak: 2 x dim x hidden_dim weights are read once per launch of up to 131,072
(row, position) slots — an arithmetic intensity in the thousands against the
v5e's ridge of 197e12 / 819e9 = 240 FLOP per byte.
"""

from __future__ import annotations

from typing import Any, Dict

ACTIVATION_BYTES = 2        # bfloat16 matmul operands (``compute_dtype``)
WEIGHT_BYTES = 4            # float32 as stored


def flops(token_slots: int, *, dim: int, hidden_dim: int, layers: int
          ) -> float:
    """ffn1 and ffn2 of every layer: 2 matmuls x 2 FLOP x rows x dim x
    hidden_dim, over all launched (row, position) slots."""
    return 2.0 * 2.0 * token_slots * dim * hidden_dim * layers


def hbm_bytes(token_slots: int, batches: int, *, dim: int, hidden_dim: int,
              layers: int) -> float:
    """Per layer: the rows read and written once (the hidden activation
    stays on the chip) and both weight matrices read once per launch."""
    return float(layers) * (
        2.0 * token_slots * dim * ACTIVATION_BYTES
        + 2.0 * batches * dim * hidden_dim * WEIGHT_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its tokens (a program from
    before the counters)."""
    sizes = dict(dim=cfg["dim"], hidden_dim=cfg["hidden_dim"],
                 layers=cfg["n_layers"])
    slots = counters.get("token_slots", 0)
    return {"flops": flops(slots, **sizes),
            "hbm_bytes": hbm_bytes(slots, counters.get("batches", 0),
                                   **sizes) if slots else 0.0}
