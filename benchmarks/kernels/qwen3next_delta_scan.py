"""Qwen3-Next's gated delta-rule scan (scope ``text/layer*/delta_scan``: the
chunked scan of an ``L`` layer's Gated-DeltaNet mixer alone, ONE kernel a
layer on the chip): what the algorithm needs for the launches the program
counted.

The chunk count is the program's own (``StreamJob.counters['delta_chunks']``):
launched rows x ``text_len`` / ``delta_chunk`` x the ``L`` layers — the scan
walks every launched slot, padding included, so every slot is charged (a
program from before the counter reads nothing). It is not taken from the
configuration.

Charged is the work of the CHUNKED algorithm (``ops/delta_scan.py``'s
docstring) at the chunk ``C`` the configuration assumes (64), whatever
implements it. A chunk of one value head over a 128 x 128 state costs ``K
K^T`` and ``Q K^T`` (2 x 2 C^2 128, formed once a key head: half of it a
value head at two value heads a key head) + the solve ``T = (I - A)^-1`` as
the product of ``I + A^(2^k)`` (5 squarings and 5 products of 2 C^3) + ``U``
and ``W`` (2 x 2 C^2 128) + the three products against the carried state
(3 x 2 C 128^2) + the masked product on ``V'`` (2 C^2 128) = 1.05 + 5.24 +
2.10 + 6.29 + 1.05 = 15.73 MFLOP, 0.246 MFLOP a head and slot, 7.86 MFLOP a
slot and layer over 32 value heads. That the solve's products run in
float32 (several bfloat16 passes each on the MXU) is the implementation's:
a product is charged once. The masks' exponentials, the running sums and
the carried state's decay are elementwise and not charged.

**At the ridge, so its metric divides the bytes by the HBM's rate.** A
slot's ``q`` and ``k`` (2,048 each) and ``v`` (4,096) are read once in
bfloat16 and its 32 steps' ``g`` and ``beta`` in float32, and its ``o``
(4,096) is written once in float32 (the gated norm behind it reads
float32): 16,384 + 256 + 16,384 = 33,024 B — 238 FLOP a byte against the
v5e's ridge of 197e12 / 819e9 = 240: 0.661 ms a layer of 16,384 slots for
the bytes, 0.654 ms for the operations at the peak. The larger of the two
bounds is the bytes'. The running sums' second layout and the final state
the kernel writes (4 MB a launch and layer, which the scorer drops) are the
implementation's and not charged. The FLOPs are returned too (``flops``),
for a reader that wants the other bound.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 q, k, v (``compute_dtype``)
FLOAT_BYTES = 4             # float32 g and beta in, o out


def delta_layers(cfg: Dict[str, Any]) -> int:
    interval = cfg["full_attention_interval"]
    return sum((i + 1) % interval != 0
               for i in range(cfg["num_hidden_layers"]))


def flops_per_slot(cfg: Dict[str, Any]) -> float:
    """One layer's scan over one slot, all value heads."""
    c = cfg["delta_chunk"]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    steps = max((c - 1).bit_length() - 1, 0)
    a_key_head = 2 * 2.0 * c * c * dk                   # K K^T, Q K^T
    a_value_head = (2 * steps * 2.0 * c ** 3            # the solve
                    + 2.0 * c * c * (dv + dk)           # U and W
                    + 2.0 * c * dk * dv * 3             # W S, Q S, K^T V'
                    + 2.0 * c * c * dv)                 # the masked product
    return (hk * a_key_head + hv * a_value_head) / c


def bytes_per_slot(cfg: Dict[str, Any]) -> float:
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return ((2 * hk * dk + hv * dv) * OPERAND_BYTES
            + (2 * hv + hv * dv) * FLOAT_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its chunks (a program without
    the mixer)."""
    slot_layers = float(counters.get("delta_chunks", 0)
                        * cfg["delta_chunk"])
    return {"flops": slot_layers * flops_per_slot(cfg),
            "hbm_bytes": slot_layers * bytes_per_slot(cfg)}
