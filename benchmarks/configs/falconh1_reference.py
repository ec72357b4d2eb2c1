"""Plain float32 reference of the five-branch ensemble whose text branch is
Falcon-H1's parallel hybrid block.

What ``falcon-h1-34b-s2048`` is held to. From the same weights and the same
assembled inputs it computes what the served program computes, the text
branch in the textbook form of the equations below — **the state-space
recurrence a position at a time, never in chunks, and a materialised causal
softmax** — in float32 throughout, sharing no line with ``models/``,
``ops/`` or ``scoring/`` and importing nothing from the package. It reads
the weights by the parameter names ``models/falcon_h1.py`` stores them under
and every size and multiplier from the configuration file's keys: those are
the data format, not the arithmetic.

The text column is ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")`` on whatever device the run has (at 5.2 GFLOP a token NumPy
would take minutes of set-up on the chip's host): THREE jitted functions —
``embed`` (a row's gathered vectors widened and scaled), ``layer`` (one
block on one row ``[T, hidden]``, called for every layer and every row at
the one shape) and ``head`` — and no eager ``jax.numpy`` call outside them,
so a run compiles three programs for it. The weights arrive as host arrays;
the embedding rows are gathered on the host (indexing, not arithmetic); a
layer's weights go up as stored (bfloat16) and are widened inside ``layer``.

Per layer, on one row's residual ``h`` ``[T, hidden]`` (text right-padded;
causal, so no real position reads a padded one and nothing is masked):

- ``h_0 = Emb[ids] * embedding_multiplier``;
- ``u = rms(h, input_layernorm)``;
- MIXER on ``ssm_in_multiplier * u``: ``p = (. W_in) * m``, ``m`` made of
  ``ssm_multipliers`` [z, x, B, C, dt] by segment (``mamba_d_ssm`` |
  ``mamba_d_ssm`` | ``G N`` | ``G N`` | ``mamba_n_heads``); ``z``, ``xBC``,
  ``dt`` = split(p); ``xBC <- silu(conv(xBC) + bias)``, ``conv`` depthwise
  over positions, tap ``K - 1`` on position t itself, tap 0 on ``t - K +
  1``, zeros before the row; ``x``, ``B``, ``C`` = split(xBC); ``dt <-
  softplus(dt + dt_bias)``; ``a = -exp(A_log)``; for t = 0, 1, ...: ``S <-
  exp(dt_t a) S + dt_t x_t B_t^T`` (a head at a time, ``S`` ``[head_dim,
  d_state]`` from zero, head j reading group ``j // (heads / G)``), ``y_t =
  S C_t + D x_t``; ``y <- group_rms_G(y * silu(z)) * mixer_norm``; ``mix =
  y W_out``;
- ATTENTION on ``attention_in_multiplier * u``: ``q = . W_q``, ``k = (.
  W_k) * key_multiplier``, ``v = . W_v``; rotate-half RoPE at
  ``rope_theta`` on q and k; query head g reads key head ``g // (H / Hkv)``;
  ``softmax(q k^T / sqrt(head_dim) + causal mask)`` over the whole row at
  once, times v; ``att = ctx W_o``;
- ``h += ssm_out_multiplier * mix + attention_out_multiplier * att``;
- ``n = rms(h, pre_ff_layernorm)``; ``h += (silu(gate_multiplier * n
  W_gate) * n W_up) W_down * down_multiplier`` (``mlp_multipliers`` =
  [gate, down]);
- after the last layer ``rms(h, norm)`` at the last real token,
  ``Linear(hidden -> 2)``, ``softmax[:, 1]``.

The language-model head, ``lm_head_multiplier`` and ``num_logits_to_keep``
are not part of this forward pass (``not_run`` in the configuration file).

The four other branches, the rules and the blend are
``olmoe_reference.py``'s (the same five-branch ensemble around another text
branch): loaded from that file, not copied again.

``text_branch(..., operand=f)`` is the seam ``tests/falconh1_control.py``
lowers: ``f`` rounds BOTH operands of every projection, of both
contractions of the attention core, of the MLP's three matmuls, and of the
scan's two products (``x``, ``B``, ``C``, and the state where ``S C_t``
reads it); ``parts=True`` also returns each layer's three updates' norms at
the last real token (the shares the configuration file quotes).
"""

from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path
from typing import Any, Callable, Dict, Optional

import numpy as np


def _sibling(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"_bench_configs_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ensemble = _sibling("olmoe_reference")
BRANCHES = _ensemble.BRANCHES
DECISIONS = _ensemble.DECISIONS

_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "rope_theta", "rms_norm_eps",
         "mamba_d_ssm", "mamba_d_state", "mamba_n_groups", "mamba_n_heads",
         "mamba_d_head", "mamba_d_conv", "embedding_multiplier",
         "ssm_in_multiplier", "ssm_out_multiplier",
         "attention_in_multiplier", "attention_out_multiplier",
         "key_multiplier")


def _held(cfg: Dict[str, Any]) -> None:
    if (cfg["attention_bias"] or cfg["mamba_proj_bias"] or cfg["mlp_bias"]
            or cfg["projectors_bias"] or not cfg["mamba_conv_bias"]
            or not cfg["mamba_rms_norm"] or cfg["mamba_norm_before_gate"]
            or cfg["hidden_act"] != "silu" or cfg["rope_scaling"] is not None
            or cfg["attn_layer_indices"] is not None):
        raise ValueError(
            "falconh1_reference holds bias-free projections, a convolution "
            "with bias, a gated group norm after the gate, silu, plain RoPE "
            "and attention in every layer")


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, ssm_multipliers: tuple, mlp_multipliers: tuple,
              operand: Optional[Callable]):
    """``(embed, layer, head)``: the three jitted functions for one set of
    sizes and one operand rounding (None: float32 as it is)."""
    import jax
    import jax.numpy as jnp

    c = dict(zip(_KEYS, sizes))
    f32 = np.float32
    eps = c["rms_norm_eps"]
    heads, kv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    d_ssm, n, g = c["mamba_d_ssm"], c["mamba_d_state"], c["mamba_n_groups"]
    m_heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    taps = c["mamba_d_conv"]
    gate_mult, down_mult = mlp_multipliers
    mup = np.concatenate([
        np.full((width,), mult, np.float32) for width, mult in zip(
            (d_ssm, d_ssm, g * n, g * n, m_heads), ssm_multipliers)])

    def lowered(x):
        return x if operand is None else operand(x)

    def matmul(x, w):
        return lowered(x) @ lowered(w.astype(jnp.float32))

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + f32(eps)) * w

    def silu(x):
        return x / (1.0 + jnp.exp(-x))

    def mixer(w, u):
        t = u.shape[0]
        proj = matmul(u * f32(c["ssm_in_multiplier"]), w["in_proj"]) * mup
        z, xbc, dt = (proj[:, :d_ssm], proj[:, d_ssm:2 * d_ssm + 2 * g * n],
                      proj[:, 2 * d_ssm + 2 * g * n:])
        before = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
        conv = w["conv_bias"]
        for k in range(taps):
            conv = conv + before[k:k + t] * w["conv_weight"][k]
        xbc = silu(conv)
        x = lowered(xbc[:, :d_ssm]).reshape(t, m_heads, p)
        b_in = lowered(xbc[:, d_ssm:d_ssm + g * n]).reshape(t, g, n)
        c_in = lowered(xbc[:, d_ssm + g * n:]).reshape(t, g, n)
        dt = jnp.log1p(jnp.exp(dt + w["dt_bias"]))              # softplus
        a = -jnp.exp(w["A_log"])
        per_group = m_heads // g

        def position(state, now):           # state [heads, head_dim, N]
            x_t, b_t, c_t, dt_t = now
            b_h = jnp.repeat(b_t, per_group, axis=0)            # [heads, N]
            c_h = jnp.repeat(c_t, per_group, axis=0)
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + dt_t[:, None, None] * x_t[:, :, None]
                     * b_h[:, None, :])
            y_t = jnp.sum(lowered(state) * c_h[:, None, :], axis=-1) \
                + w["D"][:, None] * x_t
            return state, y_t

        _, y = jax.lax.scan(position, jnp.zeros((m_heads, p, n), jnp.float32),
                            (x, b_in, c_in, dt))
        y = y.reshape(t, d_ssm) * silu(z)
        y = y.reshape(t, g, d_ssm // g)
        y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + f32(eps))
        return matmul(y.reshape(t, d_ssm) * w["mixer_norm"], w["out_proj"])

    def rope(x):                            # [T, heads, D], rotate-half
        t = x.shape[0]
        inv = c["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
        angle = np.arange(t, dtype=np.float64)[:, None] * inv[None]
        cos = np.concatenate([np.cos(angle)] * 2, axis=-1).astype(np.float32)
        sin = np.concatenate([np.sin(angle)] * 2, axis=-1).astype(np.float32)
        turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
        return x * cos[:, None, :] + turned * sin[:, None, :]

    def attention(w, u):
        t = u.shape[0]
        u = u * f32(c["attention_in_multiplier"])
        q = rope(matmul(u, w["q_proj"]).reshape(t, heads, d))
        k = rope((matmul(u, w["k_proj"]) * f32(c["key_multiplier"])
                  ).reshape(t, kv, d))
        v = matmul(u, w["v_proj"]).reshape(t, kv, d)
        k = jnp.repeat(k, heads // kv, axis=1)   # head g reads g // (H/Hkv)
        v = jnp.repeat(v, heads // kv, axis=1)
        scores = jnp.einsum("ihd,jhd->hij", lowered(q), lowered(k)) \
            / f32(math.sqrt(d))
        seen = np.tril(np.ones((t, t), bool))
        scores = jnp.where(seen[None], scores, f32(-1e30))
        scores = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        weights = scores / scores.sum(axis=-1, keepdims=True)
        ctx = jnp.einsum("hij,jhd->ihd", lowered(weights), lowered(v))
        return matmul(ctx.reshape(t, heads * d), w["o_proj"])

    def mlp(w, x):
        gate = matmul(x, w["mlp_gate"]) * f32(gate_mult)
        return matmul(silu(gate) * matmul(x, w["mlp_up"]), w["mlp_down"]) \
            * f32(down_mult)

    def embed(vectors):
        return vectors.astype(jnp.float32) * f32(c["embedding_multiplier"])

    def layer(w, h, last):
        u = rms(h, w["input_layernorm"])
        mix = mixer(w, u) * f32(c["ssm_out_multiplier"])
        att = attention(w, u) * f32(c["attention_out_multiplier"])
        h = h + mix + att
        ffn = mlp(w, rms(h, w["pre_ff_layernorm"]))
        return h + ffn, jnp.stack([mix[last], att[last], ffn[last]])

    def head(norm, score, h, last):
        logits = rms(h[last], norm) @ score
        e = jnp.exp(logits - logits.max())
        return (e / e.sum())[1]

    return jax.jit(embed), jax.jit(layer), jax.jit(head)


def text_branch(falcon: Dict[str, Any], token_ids, token_mask,
                cfg: Dict[str, Any], operand: Optional[Callable] = None,
                parts: bool = False):
    """The text column ``f32[B]`` of host arrays ``falcon`` (the program's
    parameter tree), a row at a time, a layer's weights on the device at a
    time. With ``parts`` also ``f64[layers, 3, B]``: the norms of the
    mixer's, attention's and the MLP's update at each row's last real
    token."""
    import jax

    _held(cfg)
    embed, layer, head = _programs(
        tuple(cfg[k] for k in _KEYS), tuple(cfg["ssm_multipliers"]),
        tuple(cfg["mlp_multipliers"]), operand)
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    table = np.asarray(falcon["embed_tokens"])
    norms = np.zeros((len(falcon["layers"]), 3, len(ids)))
    with jax.default_matmul_precision("highest"):
        hidden = [embed(table[row]) for row in ids]
        for index, weights in enumerate(falcon["layers"]):
            on_device = jax.device_put(weights)
            for row in range(len(ids)):
                hidden[row], updates = layer(on_device, hidden[row],
                                             np.int32(last[row]))
                if parts:
                    norms[index, :, row] = np.linalg.norm(
                        np.asarray(updates, np.float64), axis=-1)
            # calls are queued, not run: without the wait the host puts
            # every layer's weights up before the first layer has finished
            jax.block_until_ready(hidden)
            del on_device
        norm, score = jax.device_put((falcon["norm"], falcon["score"]))
        out = np.asarray([head(norm, score, hidden[row], np.int32(last[row]))
                          for row in range(len(ids))], np.float32)
    return (out, norms) if parts else out


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: this
    architecture reads its sizes and every multiplier from it."""
    e = _ensemble
    preds = np.stack([
        e.trees_branch(models.trees, batch.features),
        e.sequence_branch(models.lstm, batch.history, batch.history_len),
        text_branch(models.bert, batch.token_ids, batch.token_mask, cfg),
        e.graph_branch(models.gnn, batch),
        e.isolation_branch(models.iforest, batch.features),
    ], axis=1)
    valid = (np.asarray(model_valid, bool)[None, :]
             & np.asarray(batch.valid, bool)[:, None])
    out = e.blend(preds, valid, params)
    out["branches"] = preds
    out["rule_score"] = e.rule_score(batch.txn)
    return out
