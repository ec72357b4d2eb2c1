"""ZAYA1's router (scope ``text/layer*/router``): the down-projection to
the router's latent, the state carried from the previous layer, RMSNorm,
the three-matrix MLP, softmax over all experts, the top-1 choice, and the
sort of the tokens into expert order with the group sizes and the inverse
permutation.

Charged as memory-bound, so its metric divides the bytes by
``hbm_bytes_per_s``: per routed slot and layer the float32 hidden row is
read once (2048 x 4 bytes), the previous layer's state read and this
layer's written (256 x 4 each), and what routing writes is small: the
probabilities over all experts, the chosen expert and its weight, the
token's place in the sorted order and back. Against that stand 2 x (2048 x
256 + 2 x 256 x 256 + 256 x 16) = 1.32 MFLOP a slot: 128 FLOP a byte, under
the bf16 ridge of 240. The configuration states the router in float32 at
the highest precision, which the MXU serves in several bfloat16 passes; those
passes, like the sort's over its 4-byte keys, are what an implementation
spends, not what the algorithm needs, and they are why this share will read
low.

The slot count is ``StreamJob.counters['expert_token_slots']``: the capacity
the routed blocks were launched at — the slots the router really ran on,
not ``token_slots`` (every launched slot: attention's count).
"""

from __future__ import annotations

from typing import Any, Dict


def hbm_bytes(routed_slots: int, *, hidden_size: int, router_hidden: int,
              num_experts: int, top_k: int, layers: int) -> float:
    """Per routed slot and layer: the hidden row and the previous state read
    (float32), the new state written; the probabilities over all experts
    (float32), the chosen experts (int32) and weights (float32), and per
    chosen pair its position in expert order and the inverse (int32
    each). The router's 0.66 M weights are read once a launch: not
    charged."""
    per_slot = (hidden_size * 4 + 2 * router_hidden * 4 + num_experts * 4
                + top_k * (4 + 4) + top_k * (4 + 4))
    return float(layers) * routed_slots * per_slot


def flops(routed_slots: int, *, hidden_size: int, router_hidden: int,
          num_experts: int, layers: int) -> float:
    return 2.0 * layers * routed_slots * (
        hidden_size * router_hidden + 2 * router_hidden * router_hidden
        + router_hidden * num_experts)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its routed slots."""
    slots = counters.get("expert_token_slots", 0)
    sizes = dict(hidden_size=cfg["hidden_size"],
                 router_hidden=cfg["router_hidden_size"],
                 num_experts=cfg["num_experts"],
                 layers=cfg["num_hidden_layers"])
    return {"flops": flops(slots, **sizes),
            "hbm_bytes": hbm_bytes(slots, top_k=cfg["num_experts_per_tok"],
                                   **sizes)}
