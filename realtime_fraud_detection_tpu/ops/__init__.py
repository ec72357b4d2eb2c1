"""The hand-written TPU kernels (Pallas), each beside its XLA form and the
ONE predicate on shapes that the traced guard, the scorer's selector and the
tests all ask. Nothing here is a setting: a one-device TPU program at a shape
a predicate admits holds the kernel, everything else the XLA form.

- ``attention``: ``flash_attention`` (the dense encoder's fused core, scores
  in VMEM) against ``attention_reference``; ``flash_supported``.
  ``windowed_attention`` (the causal core at ``head_dim`` 128 with grouped
  keys, an optional sliding window and the rows' lengths: online softmax
  over the key blocks a query block sees; handed the weights of a norm a
  HEAD, the form that takes heads of 128 or 256 — two lane tiles — with the
  rotation inside a head's first tile and a gate a lane,
  ``models/qwen3_next.py``; handed the weights of a QK-norm
  over all heads, its one-block form, every head of a row a program;
  handed a shared key, its latent form: a second score term from ONE
  rotated key every head shares, ``models/joyai.py``) against
  ``attention_reference(causal=True, window=...)``; ``windowed_refusal``,
  asked through
  ``LagunaConfig.core_refusal``, ``OlmoeConfig.core_refusal`` and
  ``JoyaiConfig.core_refusal``.
- ``cca_mix``: ``cca_mix_fused`` (ZAYA1's convolutional mixing between the
  latent projections and the core as one pass: latents and values in, q, k
  and the shifted v out) against ``models.zaya.cca_mix``;
  ``cca_mix_refusal`` (None where the shape is taken, else why not), asked
  through ``ZayaConfig.mix_refusal``.
- ``grouped_matmul``: the routed encoders' expert matmuls, two kernels a
  sparse layer. ``grouped_gated_matmul`` (gate, up and the SiLU ⊙ product
  as ONE grouped kernel, ``gated_gmm``: two right-hand blocks a step, the
  product rounded once in the epilogue) against two ``jax.lax.ragged_dot``
  calls and the product; ``grouped_matmul`` (down: ``down_gmm``, on the
  same scaffold, whose float32 result rows are ``[M, N / 128, 128]`` — each
  one contiguous piece in HBM) against ``ragged_dot`` reshaped;
  ``grouped_relu2_matmul`` (the first half of an expert with NO gate,
  ``relu(rows @ up)^2``: ``relu2_gmm``, the fused kernel with one
  right-hand block a step, its matrices taken ``[G, N, K]`` — where a
  ragged-N parameter lies on the TPU); ``grouped_matmul_supported``, the
  one predicate of all three (a side that is no whole number of lane tiles
  goes whole in one block), and ``gmm_tiling``, their one tile rule (from
  the call's shapes, group count and right-hand blocks a step alone).
- ``combine``: the routed experts' way home. ``weighted_combine`` (each
  token's weighted sum of its experts' result rows as ONE kernel,
  ``combine_rows``: every row of a pair that entered a group fetched once
  from HBM as one piece, the sum in VMEM, no ``[pairs, hidden]`` temporary)
  against a gather, a select and the sum; ``combine_supported``.
- ``dispatch``: the routed experts' way out. ``rows_to_experts`` (the
  tokens' rows cast and gathered into expert order as ONE kernel,
  ``dispatch_rows``: the cast source laid a row a contiguous piece, every
  row of a pair that entered a group fetched once by DMA, the block written
  dense) against the cast and XLA's gather; ``dispatch_supported`` — a
  shape the kernel can run at whose source XLA's gather no longer reads
  about once (the every-slot programs of 32,768 slots of 2,048).
- ``ssd_scan``: the state-space scan of a Mamba-2 mixer
  (``models/falcon_h1.py``, ``models/nemotron_h.py``), ``ssd_scan(...,
  use_pallas=True)`` — the chunked algorithm as ONE kernel a layer, a grid
  over (row, group, chunk) with the carried states in VMEM, heads of 128 one
  a lane tile or heads of 64 two a tile — against the same algorithm in
  ``jax.numpy`` (``use_pallas=False``); ``ssd_refusal``, asked through the
  configuration's ``scan_refusal``.
- ``delta_scan``: the gated delta-rule scan of a Gated-DeltaNet mixer
  (``models/qwen3_next.py``), ``gated_delta_scan(..., use_pallas=True)`` —
  the chunked WY form (a unit-lower-triangular solve a chunk, as a product
  of ``I + A^(2^k)``, ahead of the products against the carried state) as
  ONE kernel a layer, a grid over (row, group of key heads, chunk) with the
  value heads' 128 x 128 states in VMEM — against the same algorithm in
  ``jax.numpy`` (``use_pallas=False``); ``delta_refusal``, asked through
  ``Qwen3NextConfig.scan_refusal``.
- ``causal_conv``: the depthwise causal convolution of a mixer with its
  SiLU (``models/falcon_h1.py``'s Mamba-2 mixer, which
  ``models/nemotron_h.py`` shares, and ``models/qwen3_next.py``'s
  Gated-DeltaNet one), ``causal_conv_silu`` — ONE kernel a layer that reads
  the convolved channels out of the array where the TPU holds it (positions
  down the sublanes or, ``positions_last``, along the lanes), shifts by
  ``pltpu.roll`` and writes the parts its caller cuts, each in its
  reader's dtype — against ``jax.nn.silu`` of ``falcon_h1.causal_conv``;
  ``conv_refusal``, asked through the configuration's ``conv_refusal``.
- ``dequant_matmul``, ``epilogue``: the int8 text branch's fused
  dequant-matmul and the score-and-blend epilogue, behind ``KernelSettings``.
"""

from realtime_fraud_detection_tpu.ops.attention import (  # noqa: F401
    attention_reference,
    flash_attention,
    flash_supported,
    merge_heads,
    narrowest_supported_len,
    rope_lane_tables,
    rope_pair_tables,
    split_heads,
    windowed_attention,
    windowed_refusal,
)
from realtime_fraud_detection_tpu.ops.causal_conv import (  # noqa: F401
    causal_conv_silu,
    conv_refusal,
)
from realtime_fraud_detection_tpu.ops.cca_mix import (  # noqa: F401
    cca_mix_fused,
    cca_mix_refusal,
)
from realtime_fraud_detection_tpu.ops.delta_scan import (  # noqa: F401
    delta_refusal,
    gated_delta_scan,
)
from realtime_fraud_detection_tpu.ops.dequant_matmul import (  # noqa: F401
    dequant_matmul,
    dequant_matmul_reference,
    dequant_rows,
    dequant_rows_reference,
    matmul_supported,
    rows_supported,
)
from realtime_fraud_detection_tpu.ops.epilogue import (  # noqa: F401
    combine_matrix,
    epilogue_reference,
    epilogue_supported,
    fused_epilogue,
)
from realtime_fraud_detection_tpu.ops.combine import (  # noqa: F401
    combine_supported,
    weighted_combine,
    weighted_combine_reference,
)
from realtime_fraud_detection_tpu.ops.dispatch import (  # noqa: F401
    dispatch_reference,
    dispatch_supported,
    rows_to_experts,
)
from realtime_fraud_detection_tpu.ops.grouped_matmul import (  # noqa: F401
    grouped_gated_matmul,
    grouped_matmul,
    grouped_matmul_reference,
    grouped_matmul_supported,
    grouped_relu2_matmul,
)
from realtime_fraud_detection_tpu.ops.ssd_scan import (  # noqa: F401
    ssd_refusal,
    ssd_scan,
)
