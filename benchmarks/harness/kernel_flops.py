"""FLOPs the text branch's kernels ISSUED, from what the program launched.

The token counts are the program's own (``StreamJob.counters``, summed per
launched microbatch from ``PendingScore``): ``token_slots`` = sum of bucket
rows x padded ``text_len`` and ``token_slots_sq`` = sum of bucket rows x
``text_len``^2. They are not taken from the configuration, so a program
that launches shorter text is charged for the FLOPs it really issued and
cannot read over 100% of a roofline.

Both kernels are compute-bound at these shapes, so the roofline is the
chip's bf16 peak: ``ffn`` reads 2 x dim x hidden_dim weights once per
131,072 rows (arithmetic intensity in the thousands); ``attn_core`` is
compute-bound ONCE THE SCORES STAY ON THE CHIP (per (row, head) it reads q,
k, v and writes the context: intensity ~T/2 FLOP per byte in bf16, 256 at
T = 512, above the v5e's ridge of 197e12 / 819e9 = 240). As deployed
(``attention_reference``) the f32 scores go through HBM and the kernel is
memory-bound, which is why its share of this roofline is low: the share
says how far the kernel is from what flash attention could reach, not
how well it uses HBM.
"""

from __future__ import annotations


def ffn(token_slots: int, *, dim: int, hidden_dim: int, layers: int) -> float:
    """ffn1 and ffn2 of every layer: 2 matmuls x 2 FLOP x rows x dim x
    hidden_dim, over all launched (row, position) slots."""
    return 2.0 * 2.0 * token_slots * dim * hidden_dim * layers


def attn_core(token_slots_sq: int, *, heads: int, head_dim: int,
              layers: int) -> float:
    """Scores and weighted sum of every layer: 2 matmuls x 2 FLOP x heads x
    T^2 x head_dim per row, with rows x T^2 summed as launched."""
    return 2.0 * 2.0 * heads * token_slots_sq * head_dim * layers


KERNELS = {"ffn": ffn, "attn_core": attn_core}


def issued(kernel: str, counters: dict, cfg: dict) -> float:
    """FLOPs of ``kernel`` over the batches ``counters`` covers; 0.0 where
    the program did not count its tokens (a program from before the
    counters)."""
    layers = cfg["n_layers"]
    if kernel == "ffn":
        return ffn(counters.get("token_slots", 0), dim=cfg["dim"],
                   hidden_dim=cfg["hidden_dim"], layers=layers)
    if kernel == "attn_core":
        return attn_core(counters.get("token_slots_sq", 0),
                         heads=cfg["n_heads"],
                         head_dim=cfg["dim"] // cfg["n_heads"], layers=layers)
    raise ValueError(f"no FLOP count on record for kernel {kernel!r} "
                     f"(known: {sorted(KERNELS)})")
