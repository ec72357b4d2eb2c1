"""ZAYA1's convolutional mixing (scope ``text/layer*/attn_mix``): everything
between the latent projections and the attention core — the depthwise and
the per-head causal convolutions of ``[q~ ; k~]``, the q-k mean, the L2
norms with the key temperature, partial RoPE, and the value shift.

Memory-bound, so its metric divides the bytes by ``hbm_bytes_per_s``: per
launched (row, position) slot and layer the algorithm has to read the
float32 latents once (q~ and k~: ``(heads + kv_heads) x head_dim`` values,
and the ``kv_heads x head_dim`` projected values) and write q, k and the
shifted v once, the same count — 2 x 1,536 x 4 = 12,288 bytes at the
published sizes — for the per-head convolution's 2 x cca_time1 x head_dim x
1,280 = 655,360 FLOP and some tens of elementwise operations a value: 53
FLOP a byte against the v5e's ridge of 197e12 / 819e9 = 240. The
convolutions' taps (0.33 M parameters a layer) are read once a launch and
not charged. Passes an implementation adds (the shifted copies, the
concatenation of the taps, the transposes between ``[B, T, heads, D]`` and
``[B, heads, T, D]``) are what it spends, not what the algorithm needs, and
they are why this share reads low.

The slot count is the program's own (``StreamJob.counters['token_slots']``:
attention runs on every launched slot, padding included).
"""

from __future__ import annotations

from typing import Any, Dict

VALUE_BYTES = 4             # float32 latents, q, k, v (``compute_dtype``)


def hbm_bytes(token_slots: int, *, latent: int, values: int, layers: int
              ) -> float:
    """Per slot and layer: q~, k~ and the projected values read, q, k and
    the shifted values written."""
    return float(layers) * token_slots * 2.0 * (latent + values) * VALUE_BYTES


def flops(token_slots: int, *, latent: int, head_dim: int, taps: int,
          layers: int) -> float:
    """The per-head convolution: every tap a ``head_dim x head_dim`` matrix
    on each head of the latent."""
    return 2.0 * layers * token_slots * taps * head_dim * latent


def work(counters: Dict[str, Any], cfg: Dict[str, Any]) -> Dict[str, float]:
    """Zeros where the program did not count its tokens."""
    slots = counters.get("token_slots", 0)
    d = cfg["head_dim"]
    latent = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * d
    layers = cfg["num_hidden_layers"]
    return {"flops": flops(slots, latent=latent, head_dim=d,
                           taps=cfg["cca_time1"], layers=layers),
            "hbm_bytes": hbm_bytes(slots, latent=latent,
                                   values=cfg["num_key_value_heads"] * d,
                                   layers=layers)}
