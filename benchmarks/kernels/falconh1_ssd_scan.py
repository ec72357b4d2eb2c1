"""Falcon-H1's state-space scan (scope ``text/layer*/ssm_scan``: the chunked
scan of the Mamba-2 mixer alone, ONE kernel a layer on the chip): what the
algorithm needs for the launches the program counted.

The chunk count is the program's own (``StreamJob.counters['ssm_chunks']``):
launched rows x ``text_len`` / ``mamba_chunk_size`` x layers — a dense
encoder's scan walks every launched slot, padding included, so every slot is
charged (a program from before the counter reads nothing). It is not taken
from the configuration.

Charged is the work of the CHUNKED algorithm at the published chunk of 128,
whatever implements it. A launched slot and layer costs ``2 x 128 x 256 x
2`` (``C B^T``, once a group) ``+ 2 x 128 x 128 x 32`` (the decay-masked
product on ``x``, a head at a time) ``+ 2 x 2 x 256 x 128 x 32`` (a chunk's
closing state and ``C . S``) = 5.37 MFLOP; the sequential recurrence would
need 2 x 3 x 128 x 256 x 32 = 6.3 MFLOP of multiply-adds a slot that no MXU
can take. The masks' exponentials, the running sums and the carried state's
decay are elementwise and not charged.

**Memory-bound at the dtypes the program moves, by a little, so its metric
divides the bytes by the HBM's rate.** A slot's ``x`` (4,096), ``B`` and
``C`` (512 each) are read once in bfloat16 and its 32 steps ``dt`` in
float32, and its ``y`` (4,096) is written once in float32 (the gated norm
behind it reads float32): 8,192 + 2,048 + 128 + 16,384 = 26,752 B — 201 FLOP
a byte, under the v5e's ridge of 197e12 / 819e9 = 240. (Were ``y`` written
bfloat16 it would be 293 and the compute peak would bind; with float32 in
and out 145.) The running sums the kernel also reads (128 B a slot) and the
final state it writes (33 MB a launch and layer, which the scorer drops) are
the implementation's and not charged. The FLOPs are returned too
(``flops``), for a reader that wants the other bound: 0.45 ms a layer of
16,384 slots at the peak against 0.54 ms for the bytes.
"""

from __future__ import annotations

from typing import Any, Dict

OPERAND_BYTES = 2           # bfloat16 x, B, C (``compute_dtype``)
FLOAT_BYTES = 4             # float32 dt in, y out


def flops_per_slot(cfg: Dict[str, Any]) -> float:
    chunk, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    heads, p, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_n_groups"])
    return (2.0 * chunk * n * g + 2.0 * chunk * p * heads
            + 2.0 * 2.0 * n * p * heads)


def bytes_per_slot(cfg: Dict[str, Any]) -> float:
    d_ssm = cfg["mamba_d_ssm"]
    bc = 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return ((d_ssm + bc) * OPERAND_BYTES
            + (cfg["mamba_n_heads"] + d_ssm) * FLOAT_BYTES)


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its chunks (a program without
    the mixer)."""
    slot_layers = float(counters.get("ssm_chunks", 0)
                        * cfg["mamba_chunk_size"])
    return {"flops": slot_layers * flops_per_slot(cfg),
            "hbm_bytes": slot_layers * bytes_per_slot(cfg)}
