"""OLMoE-1B-7B's transformer block as a text encoder, in pure JAX.

The layer equations are those of ``allenai/OLMoE-1B-7B-0125-Instruct``'s
``config.json`` and of the Hugging Face ``modeling_olmoe.py`` it names
(``OlmoeConfig`` keeps the source's key names):

- pre-norm residual blocks with RMSNorm (float32, ``rms_norm_eps``);
- attention: bias-free q/k/v/o projections, RMSNorm on q and on k over the
  whole hidden width before the head split (QK-norm), rotary positions
  (rotate-half, ``inv_freq = rope_theta ** (-2i / head_dim)``, positions
  0..T-1), causal mask AND key mask, ``softmax(q k^T / sqrt(head_dim)) v``;
- a sparse mixture of experts in place of the dense FFN: ``p = softmax(x
  W_router)`` over all ``num_experts`` in float32, the ``num_experts_per_tok``
  largest taken WITHOUT renormalising (``norm_topk_prob`` false), each a
  SwiGLU expert ``down(silu(gate(x)) * up(x))`` of width
  ``intermediate_size``. Every token reaches all of its experts: no token
  is dropped (a *capacity*, below, is a shape, not a limit);
- after the last layer the final RMSNorm; the text is right-padded
  (``models/tokenizer.py``), so the pooled position is the last real token;
  a bias-free ``Linear(hidden -> 2)`` head, ``p_text = softmax(logits)[1]``
  (the family's sequence-classification convention; the LM head is not held:
  the branch emits a class probability, not tokens).

The expert layer is computed the dropless way: the (token, expert) pairs are
sorted by expert, the tokens' rows gathered into that order, the experts run
over the ragged groups as two grouped calls (``ops/grouped_matmul.py``: gate,
up and the SiLU ⊙ product in one, then down), and the rows are gathered home
and summed with their router weights. ``route`` and
``apply_experts`` are the two halves, held separately by the tests.

Only the batch's REAL tokens are routed. Nothing a padding position computes
reaches an answer (right-padded text, causal and key-masked attention, the
head reads the last real token), so its (token, expert) pairs belong to no
expert group: they sort last, ``group_sizes`` sums to real tokens x
``num_experts_per_tok``, the grouped matmuls never visit their rows, and
the block adds zero to the residual stream there. A **capacity** C is how
many token slots the routed block is compiled for: with C under the
launch's ``B x T`` slots the real slots are compacted, in slot order, into
``[C, hidden]`` ahead of the router (``token_slots``), the router, sort,
gatherings and matmuls run on C rows, and the result is scattered home.
The caller picks C, a static argument, and answers for C holding every
real token of the launch (``scoring/text_split.capacity`` picks it on the
host from the mask it counts; ``FraudScorer`` refuses a launch that would
not fit): tokens past C would be left out without a sign. None is every
slot: nothing is gathered or scattered. There is no branch in the program.

Precision: weights stored bfloat16 (the checkpoint's dtype), bfloat16 matmul
operands with float32 accumulation, float32 norms, softmaxes, RoPE and
residual stream; the router matmul in float32 at ``Precision.HIGHEST`` (a
fifth of a percent of the arithmetic; top-k is a discrete choice that
rounding flips).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from realtime_fraud_detection_tpu.models.text_encoder import routed_encoder
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    merge_heads,
    rope_lane_tables,
    split_heads,
    windowed_attention,
    windowed_refusal,
)
from realtime_fraud_detection_tpu.ops.combine import weighted_combine
from realtime_fraud_detection_tpu.ops.dispatch import rows_to_experts
from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    gated_tile_rows,
    grouped_gated_matmul,
    grouped_matmul,
    grouped_relu2_matmul,
)


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
    """``config.json`` of OLMoE-1B-7B-0125-Instruct, under its own keys."""

    vocab_size: int = 50304
    hidden_size: int = 2048
    intermediate_size: int = 1024       # width of ONE expert
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = False
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    num_labels: int = 2

    def __post_init__(self) -> None:
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "OlmoeConfig: grouped-query attention is not implemented "
                f"(num_key_value_heads {self.num_key_value_heads} != "
                f"num_attention_heads {self.num_attention_heads}); grouped "
                "keys live in ops/attention.py (attention_reference, "
                "windowed_attention, which this encoder's fused core calls "
                "with one key head a query head) for models/zaya.py and "
                "models/laguna.py")
        if self.norm_topk_prob:
            raise ValueError("OlmoeConfig: norm_topk_prob true is not the "
                             "published model and is not implemented; "
                             "choose_experts(renormalise=True) is what "
                             "models/laguna.py routes with")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_sparse_layers(self) -> int:
        """Layers with a routed block (``models/text_encoder.py``)."""
        return self.num_hidden_layers

    def core_refusal(self, seq_len: int) -> Optional[str]:
        """Why a program of ``seq_len`` positions keeps the XLA core where
        the fused one is asked for, or None where it holds the kernel
        (``ops.attention.windowed_refusal`` with the QK-norm riding it:
        shapes alone)."""
        return windowed_refusal(seq_len, self.head_dim,
                                self.num_attention_heads,
                                self.num_key_value_heads, None, qk_norm=True)


TINY_OLMOE = OlmoeConfig(
    vocab_size=30522, hidden_size=128, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2)


def init_olmoe_params(key: jax.Array, config: OlmoeConfig) -> Dict:
    """Normal(``initializer_range``) weights drawn directly in bfloat16, one
    tensor at a time: traced into one jitted init, no float32 copy of the
    expert weights ever exists. Norm weights are ones (float32); the head is
    float32 (two columns)."""
    h, i_, e = (config.hidden_size, config.intermediate_size,
                config.num_experts)
    std = config.initializer_range

    def w(k, shape, dtype=jnp.bfloat16):
        return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

    def ones():
        return jnp.ones((h,), jnp.float32)

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    layers = []
    for lk in jax.random.split(k_layers, config.num_hidden_layers):
        k = jax.random.split(lk, 8)
        layers.append({
            "input_layernorm": ones(),
            "q_proj": w(k[0], (h, h)), "k_proj": w(k[1], (h, h)),
            "v_proj": w(k[2], (h, h)), "o_proj": w(k[3], (h, h)),
            "q_norm": ones(), "k_norm": ones(),
            "post_attention_layernorm": ones(),
            "router": w(k[4], (h, e)),
            "gate_proj": w(k[5], (e, h, i_)),
            "up_proj": w(k[6], (e, h, i_)),
            "down_proj": w(k[7], (e, i_, h)),
        })
    return {
        "embed_tokens": w(k_emb, (config.vocab_size, h)),
        "layers": layers,
        "norm": ones(),
        "score": w(k_head, (h, config.num_labels), jnp.float32),
    }


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps) * weight


def _proj(x: jax.Array, w: jax.Array) -> jax.Array:
    """Bias-free projection: bf16 operands, f32 accumulation and result."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rope_tables(seq_len: int, head_dim: int, theta: float
                ) -> Tuple[np.ndarray, np.ndarray]:
    """cos and sin ``f32[T, head_dim]`` of positions 0..T-1: the half-width
    frequencies repeated over both halves (rotate-half layout). Constants of
    the program, computed on the host in float64."""
    inv_freq = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64)
                         / head_dim)
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    angles = np.concatenate([angles, angles], axis=-1)
    return (np.cos(angles).astype(np.float32),
            np.sin(angles).astype(np.float32))


def apply_rope(x: jax.Array, cos, sin) -> jax.Array:
    """``x * cos + rotate_half(x) * sin`` on ``[B, heads, T, head_dim]``."""
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def router_probs(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """``softmax(x W_router)`` over ALL experts, float32 at the highest
    matmul precision: ``f32[N, num_experts]``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return jax.nn.softmax(logits, axis=-1)


def choose_experts(probs: jax.Array, top_k: int,
                   bias: Optional[jax.Array] = None, *,
                   renormalise: bool = False, renormalise_eps: float = 0.0,
                   scale: float = 1.0) -> Tuple[jax.Array, jax.Array]:
    """``(experts i32[N, top_k], weights f32[N, top_k])`` of router
    probabilities ``f32[N, num_experts]``, however the encoder's router made
    them: each token's ``top_k`` largest, their probabilities NOT
    renormalised unless ``renormalise`` (``norm_topk_prob``: divided by
    their sum over the chosen, all of them, wherever their experts live —
    plus ``renormalise_eps`` where the source guards the division, as
    ``models/joyai.py``'s does), then times ``scale``
    (``moe_routed_scaling_factor``). ``bias`` (``f32[num_experts]``) moves
    the choice alone: the largest of ``probs + bias`` are taken and weighted
    by ``probs`` (a sigmoid router's ``e_score_correction_bias``)."""
    if bias is None:
        weights, experts = jax.lax.top_k(probs, top_k)
    else:
        _, experts = jax.lax.top_k(probs + bias, top_k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalise:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        if renormalise_eps:
            total = total + renormalise_eps
        weights = weights / total
    if scale != 1.0:
        weights = weights * scale
    return experts.astype(jnp.int32), weights


def route(x: jax.Array, w_router: jax.Array, top_k: int
          ) -> Tuple[jax.Array, jax.Array]:
    """OLMoE's router, one matmul: ``choose_experts`` of ``router_probs``."""
    return choose_experts(router_probs(x, w_router), top_k)


def token_slots(attention_mask: jax.Array, capacity: Optional[int]
                ) -> Tuple[Optional[jax.Array], jax.Array]:
    """What the routed block of a launch runs on: ``(idx, real)``. With
    ``capacity`` C under the ``B x T`` slots, ``idx i32[C]`` holds the real
    slots in slot order, then fillers past the last slot (each its own, so
    the indices stay sorted and unique and a scatter drops them), and
    ``real bool[C]`` tells them apart. With None (or every slot) nothing is
    compacted: ``idx`` is None and ``real`` is the mask itself. Once a
    launch: the layers share it."""
    flat = attention_mask.reshape(-1).astype(bool)
    n = flat.shape[0]
    if capacity is None or capacity == n:
        return None, flat
    if not 0 < capacity < n:
        raise ValueError(f"capacity {capacity} of a launch of {n} slots")
    idx = jnp.nonzero(flat, size=capacity, fill_value=n)[0].astype(jnp.int32)
    real = idx < n
    return jnp.where(real, idx, n + jnp.arange(capacity, dtype=jnp.int32)
                     ), real


class ExpertLoad(NamedTuple):
    """What one routed layer's grouped calls were handed: ``group_sizes``
    ``i32[held experts]``, the (token, expert) pairs in each held expert's
    group, and ``tile_rows`` ``i32[]``, the rows the fused gate / up
    kernel's grid visited for them (``ops.grouped_matmul.gated_tile_rows``:
    0 where the layer ran the XLA form)."""

    group_sizes: jax.Array
    tile_rows: jax.Array


def launch_stats(loads) -> jax.Array:
    """A routed launch's second output (``models/text_encoder.py``),
    ``i32[3, routed layers]`` from each routed layer's ``ExpertLoad``: the
    largest group, the pairs held, the rows visited."""
    return jnp.stack([
        jnp.stack([jnp.max(load.group_sizes), jnp.sum(load.group_sizes),
                   load.tile_rows]) for load in loads], axis=1)


def apply_experts(layer: Dict, x: jax.Array, experts: jax.Array,
                  weights: jax.Array, *, real: Optional[jax.Array] = None,
                  router_width: Optional[int] = None, expert_offset: int = 0,
                  use_pallas: bool = False, kernel_interpret: bool = False
                  ) -> Tuple[jax.Array, ExpertLoad]:
    """``sum_e weights[n, e] * expert_e(x[n])`` for the routed ``experts``:
    ``(f32[N, hidden], ExpertLoad)``. ``x`` is ``[N,
    hidden]``. Every (token, expert) pair of a row that ``real`` (``bool[N]``;
    None: every row) admits is computed; the other rows' pairs enter no
    group and their result is zero.

    **Two grouped calls.** ``grouped_gated_matmul`` takes the sorted rows to
    ``silu(gate) * up`` in ``down_proj``'s dtype — with ``use_pallas``, at a
    shape ``grouped_matmul_supported`` admits, ONE kernel that reads the rows
    once and keeps both float32 results in VMEM; else two ``ragged_dot``
    calls and the product — and ``grouped_matmul`` takes that through
    ``down_proj``, each float32 result row left as one contiguous piece
    (``[M, hidden / 128, 128]``). Neither writes a row past the last group.
    A layer that holds NO ``gate_proj`` has experts without a gate,
    ``down(relu(up(x))^2)`` (``models/nemotron_h.py``): its first call is
    ``grouped_relu2_matmul``, the same kernel with one matrix and that
    epilogue. What the layer holds chooses; there is no flag.

    **The way out** is ``ops.dispatch.rows_to_experts``: the tokens' rows
    cast and gathered into expert order — with ``use_pallas``, at a shape
    ``dispatch_supported`` admits (a source XLA's gather no longer reads
    about once), one pass that casts the rows and lays each as a contiguous
    piece and ONE kernel that fetches the row of every pair that entered a
    group; else the cast and XLA's gather.

    **The way home** is ``ops.combine.weighted_combine``: each token's
    weighted sum of the rows of its pairs that entered a group — with
    ``use_pallas``, at a shape ``combine_supported`` admits, ONE kernel that
    fetches each such row once and sums in VMEM; else a gather, a select
    and the sum.

    **A share of the experts.** The layer holds the experts its stacked
    weights hold: ``down_proj.shape[0]`` of the ``router_width`` the router
    chose among (None: all of them), those numbered ``expert_offset`` on.
    ``experts`` counts in the router's numbers. Where the layer holds fewer
    than the router's width, a pair whose expert lives elsewhere enters no
    group, exactly as a filler's (keyed past the last held expert, sorted
    last, never visited by the grouped matmul) and adds nothing: the result
    is this chip's part of the sum, with ``weights`` as the router made them
    over all of a token's experts. No pair of a held expert is ever left
    out."""
    n, top_k = experts.shape
    num_experts = layer["down_proj"].shape[0]
    gated = "gate_proj" in layer
    share = router_width is not None and (
        router_width != num_experts or expert_offset != 0)
    with jax.named_scope(scopes.ROUTER), \
            jax.named_scope(scopes.ROUTER_ORDER):
        # the (token, expert) pairs in expert order; a stable sort keeps a
        # group's rows in token order
        flat = experts.reshape(-1)
        if share:
            # in the layer's own numbers; a pair of an expert that lives
            # elsewhere is keyed past the last held one
            flat = flat - expert_offset
            flat = jnp.where((flat >= 0) & (flat < num_experts), flat,
                             num_experts)
        if real is not None:
            # keyed past the last expert, the other rows' pairs sort last
            # and are counted in no group
            flat = jnp.where(jnp.repeat(real, top_k), flat, num_experts)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32)[None],
            axis=0, dtype=jnp.int32)
        # where each pair's row went: the inverse permutation
        home = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32))
        tile_rows = gated_tile_rows(
            group_sizes, order.shape[0], *layer["up_proj"].shape[1:],
            use_pallas=use_pallas, matrices=1 + gated)
    gm = dict(use_pallas=use_pallas, interpret=kernel_interpret)
    with jax.named_scope(scopes.EXPERTS_DISPATCH):
        # matmul operands take the stored dtype of the weights (bfloat16
        # as deployed; float32 weights make a float32 program, for tests);
        # the rows past the last group belong to no expert and may hold
        # anything: the kernel fetches none of them
        rows = rows_to_experts(x, order // top_k, jnp.sum(group_sizes),
                               layer["up_proj"].dtype, **gm)       # [N*k, H]
    with jax.named_scope(scopes.EXPERTS_MATMUL):
        # the expert's first half in one call (gate, up and SiLU ⊙, or up
        # and relu^2), rounded once to what down reads
        first = dict(out_dtype=layer["down_proj"].dtype, **gm)
        act = (grouped_gated_matmul(rows, layer["gate_proj"],
                                    layer["up_proj"], group_sizes, **first)
               if gated else
               grouped_relu2_matmul(rows, layer["up_proj"], group_sizes,
                                    **first))
        out = grouped_matmul(act, layer["down_proj"], group_sizes, **gm)
    with jax.named_scope(scopes.EXPERTS_COMBINE):
        # a pair's row comes home only where it entered a group (its token
        # real, its expert held here): the kernels never wrote the other
        # rows (ops/grouped_matmul.py), and whatever they hold stops here
        y = weighted_combine(out, home.reshape(n, top_k), weights,
                             (flat < num_experts).reshape(n, top_k), **gm)
    return y, ExpertLoad(group_sizes, tile_rows)


def olmoe_attention(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                    config: OlmoeConfig, cos, sin, *,
                    lengths: Optional[jax.Array] = None,
                    use_pallas: bool = False,
                    kernel_interpret: bool = False) -> jax.Array:
    """``h + o_proj(attn(...))`` on ``h`` ``f32[B, T, hidden]``: the first
    half of a block. ``use_pallas`` asks for the fused core
    (``ops.attention.windowed_attention``, which norms q and k over all
    heads, rotates them and splits the heads in VMEM: q and k go to it as
    their projections wrote them); a shape it does not take
    (``OlmoeConfig.core_refusal``) runs the XLA form. ``lengths``
    ``i32[B]``: the real tokens of each row (None: the mask's row sum)."""
    eps, heads = config.rms_norm_eps, config.num_attention_heads
    with jax.named_scope(scopes.LN):
        x = rms_norm(h, layer["input_layernorm"], eps)
    if use_pallas and config.core_refusal(h.shape[1]) is None:
        operand = layer["q_proj"].dtype
        if lengths is None:
            lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
        with jax.named_scope(scopes.ATTN_PROJ):
            q = _proj(x, layer["q_proj"])
            k = _proj(x, layer["k_proj"])
            v = _proj(x, layer["v_proj"]).astype(operand)
        # q and k are normed over all heads, rotated and split inside the
        # kernel: no pass stands between the projections and it
        *tables, shift = rope_lane_tables(cos, sin, config.head_dim)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = windowed_attention(
                q, k, v, lengths, num_heads=heads,
                num_kv_heads=config.num_key_value_heads,
                rope=tuple(tables), rope_shift=shift,
                norm=(layer["q_norm"], layer["k_norm"]), norm_eps=eps,
                out_dtype=operand, interpret=kernel_interpret)
    else:
        with jax.named_scope(scopes.ATTN_PROJ):
            q = rms_norm(_proj(x, layer["q_proj"]), layer["q_norm"], eps)
            k = rms_norm(_proj(x, layer["k_proj"]), layer["k_norm"], eps)
            v = _proj(x, layer["v_proj"])
            # the barrier changes no value. Without it the TPU compiler
            # folds the norm's last multiply into a layout fusion of its own
            # ahead of the head split, which carries no op_name: 0.8 ms a
            # layer that a device trace can give to no scope (2.5% of the
            # busy time; PERF.md, PR 26). With it the same pass is a fusion
            # rooted at this scope's multiply. (The fused core norms in
            # VMEM: the barrier guards this form alone.)
            q, k = jax.lax.optimization_barrier((q, k))
            qh = apply_rope(split_heads(q, heads), cos, sin)
            kh = apply_rope(split_heads(k, heads), cos, sin)
            vh = split_heads(v, heads)
        with jax.named_scope(scopes.ATTN_CORE):
            ctx = attention_reference(qh, kh, vh, attention_mask, causal=True)
        with jax.named_scope(scopes.ATTN_PROJ):
            ctx = merge_heads(ctx)
    with jax.named_scope(scopes.ATTN_PROJ):
        attn_out = _proj(ctx, layer["o_proj"])
    with jax.named_scope(scopes.LN):
        return h + attn_out


def routed_block(layer: Dict, x: jax.Array,
                 slots: Tuple[Optional[jax.Array], jax.Array], router, *,
                 shared=None, router_width: Optional[int] = None,
                 expert_offset: int = 0,
                 use_pallas: bool = False, kernel_interpret: bool = False):
    """The routed half of a sparse block on the normed rows ``x`` ``f32[N,
    hidden]`` of every slot of a launch: ``(y f32[N, hidden], ExpertLoad,
    carry)``. ``slots`` is the launch's ``token_slots``; under a capacity
    the real slots' rows are gathered into ``[C, hidden]`` first and the
    result scattered home. ``router`` maps those rows to ``(experts,
    weights, carry)`` under the ``router`` scope: the encoder's own (one
    matmul for OLMoE, an MLP with state for ZAYA1), and ``carry`` is
    whatever it hands its next layer, on the same C rows. ``shared``
    (None: the encoder has none) maps the same rows to what an expert
    every token passes through adds, under a scope of its own — under
    whatever gate the encoder's own callback applies
    (``models/qwen3_next.gated_shared_expert``: a scalar sigmoid a token);
    it is added on the real rows before the result goes home. ``router_width`` and
    ``expert_offset`` are ``apply_experts``': which of the router's experts
    this layer holds."""
    n, width = x.shape
    idx, real = slots
    if idx is not None:
        with jax.named_scope(scopes.EXPERTS_DISPATCH):
            x = x.at[idx].get(mode="fill", fill_value=0.0)     # [C, width]
    with jax.named_scope(scopes.ROUTER), \
            jax.named_scope(scopes.ROUTER_CHOOSE):
        experts, weights, carry = router(x)
    y, load = apply_experts(
        layer, x, experts, weights, real=real, router_width=router_width,
        expert_offset=expert_offset, use_pallas=use_pallas,
        kernel_interpret=kernel_interpret)
    if shared is not None:
        with jax.named_scope(scopes.SHARED_EXPERT):
            y = y + jnp.where(real[:, None], shared(x), 0.0)
    if idx is not None:
        with jax.named_scope(scopes.EXPERTS_COMBINE):
            # home: the fillers' indices lie past the last slot and drop
            y = jnp.zeros((n, width), y.dtype).at[idx].set(
                y, mode="drop", indices_are_sorted=True, unique_indices=True)
    return y, load, carry


def olmoe_layer(layer: Dict, h: jax.Array, attention_mask: jax.Array,
                config: OlmoeConfig, cos, sin, *,
                slots: Optional[Tuple[Optional[jax.Array], jax.Array]] = None,
                lengths: Optional[jax.Array] = None,
                use_pallas: bool = False, kernel_interpret: bool = False
                ) -> Tuple[jax.Array, ExpertLoad]:
    """One pre-norm block on ``h`` ``f32[B, T, hidden]``; also the layer's
    ``ExpertLoad``. ``slots`` is the launch's
    ``token_slots`` (None: every real slot, uncompacted), ``lengths`` its
    rows' real tokens (None: the mask's row sum)."""
    b, t, width = h.shape
    if slots is None:
        slots = token_slots(attention_mask, None)
    h = olmoe_attention(layer, h, attention_mask, config, cos, sin,
                        lengths=lengths, use_pallas=use_pallas,
                        kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        x = rms_norm(h, layer["post_attention_layernorm"],
                     config.rms_norm_eps).reshape(b * t, width)
    y, load, _ = routed_block(
        layer, x, slots,
        lambda rows: (*route(rows, layer["router"],
                             config.num_experts_per_tok), None),
        use_pallas=use_pallas, kernel_interpret=kernel_interpret)
    with jax.named_scope(scopes.LN):
        h = h + y.reshape(b, t, width)
    return h, load


def olmoe_encode(params: Dict, input_ids: jax.Array,
                 attention_mask: jax.Array, config: OlmoeConfig, *,
                 capacity: Optional[int] = None,
                 use_pallas: bool = False, kernel_interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """Hidden states before the final norm ``f32[B, T, hidden]`` and the
    launch's statistics ``i32[3, layers]`` (``launch_stats``: each layer's
    largest expert group, held pairs and visited rows). ``capacity``: the
    token slots the routed blocks are compiled for (the module's
    docstring)."""
    t = input_ids.shape[1]
    cos, sin = rope_tables(t, config.head_dim, config.rope_theta)
    slots = token_slots(attention_mask, capacity)
    lengths = jnp.sum(attention_mask.astype(jnp.int32), axis=-1)
    with jax.named_scope(scopes.EMBED):
        h = params["embed_tokens"][input_ids].astype(jnp.float32)
    loads = []
    for i, layer in enumerate(params["layers"]):
        with jax.named_scope(scopes.layer_scope(i)):
            h, load = olmoe_layer(
                layer, h, attention_mask, config, cos, sin, slots=slots,
                lengths=lengths, use_pallas=use_pallas,
                kernel_interpret=kernel_interpret)
        loads.append(load)
    return h, launch_stats(loads)


def last_token_logits(params: Dict, hidden: jax.Array,
                      attention_mask: jax.Array, eps: float) -> jax.Array:
    """The sequence-classification head on ``hidden`` ``f32[B, T, hidden]``:
    final RMSNorm of the last real token (right-padded text), bias-free
    ``Linear(hidden -> num_labels)``."""
    with jax.named_scope(scopes.HEAD):
        last = jnp.maximum(
            jnp.sum(attention_mask.astype(jnp.int32), axis=-1) - 1, 0)
        pooled = jnp.take_along_axis(hidden, last[:, None, None], axis=1)[:, 0]
        pooled = rms_norm(pooled, params["norm"], eps)
        return jnp.dot(pooled, params["score"],
                       precision=jax.lax.Precision.HIGHEST)


def olmoe_logits(params: Dict, input_ids: jax.Array,
                 attention_mask: jax.Array, config: OlmoeConfig, *,
                 capacity: Optional[int] = None,
                 use_pallas: bool = False, kernel_interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    """Sequence-classification logits ``f32[B, num_labels]`` from the last
    real token, and the launch's statistics ``i32[3, layers]``."""
    hidden, stats = olmoe_encode(params, input_ids, attention_mask, config,
                                 capacity=capacity, use_pallas=use_pallas,
                                 kernel_interpret=kernel_interpret)
    return last_token_logits(params, hidden, attention_mask,
                             config.rms_norm_eps), stats


def olmoe_predict(params: Dict, input_ids: jax.Array,
                  attention_mask: jax.Array, config: OlmoeConfig, *,
                  capacity: Optional[int] = None,
                  use_pallas: bool = False, kernel_interpret: bool = False,
                  with_stats: bool = False):
    """Fraud probability ``f32[B]`` = ``softmax(logits)[:, 1]``; with
    ``with_stats`` also the launch's statistics ``i32[3, layers]``: each
    layer's largest expert group, held pairs and rows the fused kernel's
    grid visited (what ``StreamJob.counters['expert_peak_rows']``,
    ``['expert_rows']`` and ``['expert_tile_rows']`` sum)."""
    logits, stats = olmoe_logits(params, input_ids, attention_mask, config,
                                 capacity=capacity, use_pallas=use_pallas,
                                 kernel_interpret=kernel_interpret)
    p = jax.nn.softmax(logits, axis=-1)[:, 1]
    return (p, stats) if with_stats else p


TEXT_ENCODER = routed_encoder(OlmoeConfig, init_olmoe_params, olmoe_predict,
                              OlmoeConfig.core_refusal)
