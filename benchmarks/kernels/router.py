"""The text branch's router (scope ``text/layer*/router``): gate matmul,
softmax over all experts, top-k, and the sort of the (token, expert) pairs
into expert order with the group offsets.

Memory-bound, so its metric divides the bytes by ``hbm_bytes_per_s``: per
(row, position) slot and layer the float32 hidden row is read once (2048 x 4
bytes) for 2 x 2048 x 64 FLOP — 32 FLOP a byte against the v5e's ridge of
240 — and what is written is small: the experts' probabilities, the chosen
experts and their weights, and the pair's place in the sorted order and back.
The sort's own passes over its 4-byte keys are not charged: they are what an
implementation spends, not what the algorithm needs, and they are why this
share will read low.

The slot count is the program's own (``StreamJob.counters['token_slots']``).
"""

from __future__ import annotations

from typing import Any, Dict


def hbm_bytes(token_slots: int, *, hidden_size: int, num_experts: int,
              top_k: int, layers: int) -> float:
    """Per slot and layer: the hidden row read (float32), the router's
    weights are negligible (read once a launch); written: the probabilities
    over all experts (float32), the chosen experts (int32) and weights
    (float32), and per chosen pair its position in expert order and the
    inverse (int32 each)."""
    per_slot = (hidden_size * 4 + num_experts * 4 + top_k * (4 + 4)
                + top_k * (4 + 4))
    return float(layers) * token_slots * per_slot


def flops(token_slots: int, *, hidden_size: int, num_experts: int,
          layers: int) -> float:
    return 2.0 * layers * token_slots * hidden_size * num_experts


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its tokens."""
    slots = counters.get("token_slots", 0)
    sizes = dict(hidden_size=cfg["hidden_size"],
                 num_experts=cfg["num_experts"],
                 layers=cfg["num_hidden_layers"])
    return {"flops": flops(slots, **sizes),
            "hbm_bytes": hbm_bytes(slots, top_k=cfg["num_experts_per_tok"],
                                   **sizes)}
