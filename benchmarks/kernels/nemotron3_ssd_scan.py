"""Nemotron-3-Nano's state-space scan (scope ``text/layer*/ssm_scan``: the
chunked scan of an ``M`` layer's Mamba-2 mixer alone, ONE kernel a layer on
the chip): what the algorithm needs for the launches the program counted.

The chunk count is the program's own (``StreamJob.counters['ssm_chunks']``):
launched rows x ``text_len`` / ``chunk_size`` x the ``M`` layers — the scan
walks every launched slot, padding included, so every slot is charged (a
program from before the counter reads nothing). It is not taken from the
configuration.

Charged is the work of the CHUNKED algorithm at the published chunk of 128,
whatever implements it. A launched slot and layer costs ``2 x 128 x 128 x
8`` (``C B^T``, once a group) ``+ 2 x 128 x 64 x 64`` (the decay-masked
product on ``x``, a head at a time) ``+ 2 x 2 x 128 x 64 x 64`` (a chunk's
closing state and ``C . S``) = 3.41 MFLOP. That heads of 64 go two a lane
tile, and that each head's products are formed against the pair's whole
tile, is the implementation's: a product 64 wide is charged 64 wide. The
masks' exponentials, the running sums and the carried state's decay are
elementwise and not charged.

**Memory-bound at the dtypes the program moves, so its metric divides the
bytes by the HBM's rate.** A slot's ``x`` (4,096), ``B`` and ``C`` (1,024
each) are read once in bfloat16 and its 64 steps ``dt`` in float32, and its
``y`` (4,096) is written once in float32 (the gated norm behind it reads
float32): 12,288 + 256 + 16,384 = 28,928 B — 118 FLOP a byte, half the
v5e's ridge of 197e12 / 819e9 = 240 (Falcon-H1's heads of 128 over a state
of 256 read 201: this model's scan does half the arithmetic on the same
bytes). The running sums the kernel also reads (256 B a slot) and the final
state it writes (17 MB a launch and layer, which the scorer drops) are the
implementation's and not charged. The FLOPs are returned too (``flops``),
for a reader that wants the other bound: 0.28 ms a layer of 16,384 slots at
the peak against 0.58 ms for the bytes.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 x, B, C (``compute_dtype``)
FLOAT_BYTES = 4             # float32 dt in, y out


def flops_per_slot(cfg: Dict[str, Any]) -> float:
    chunk, n = cfg["chunk_size"], cfg["ssm_state_size"]
    heads, p, g = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                   cfg["n_groups"])
    return (2.0 * chunk * n * g + 2.0 * chunk * p * heads
            + 2.0 * 2.0 * n * p * heads)


def bytes_per_slot(cfg: Dict[str, Any]) -> float:
    d_inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    bc = 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return ((d_inner + bc) * OPERAND_BYTES
            + (cfg["mamba_num_heads"] + d_inner) * FLOAT_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its chunks (a program without
    the mixer)."""
    slot_layers = float(counters.get("ssm_chunks", 0) * cfg["chunk_size"])
    return {"flops": slot_layers * flops_per_slot(cfg),
            "hbm_bytes": slot_layers * bytes_per_slot(cfg)}
