"""Plain float32 reference of the five-branch ensemble whose text branch is
NVIDIA-Nemotron-3-Nano-30B-A3B's stack.

What ``nemotron-3-nano-30b-s2048`` is held to. From the same weights and the
same assembled inputs it computes what the served program computes, the text
branch in the textbook form of the equations below — **the state-space
recurrence a position at a time, never in chunks; a materialised causal
softmax; every expert over every token, weighed by zero where the router
did not choose it** (no sort, no groups, no capacity) — in float32
throughout, sharing no line with ``models/``, ``ops/`` or ``scoring/`` and
importing nothing from the package. It reads the weights by the parameter
names ``models/nemotron_h.py`` stores them under and every size from the
configuration file's keys: those are the data format, not the arithmetic.

The text column is ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")`` on whatever device the run has (at 1.0 GFLOP a token in the
program's form, and the plain form of the experts 5.2 TFLOP a row and layer,
NumPy would take an hour of set-up on the chip's host): FOUR jitted
functions — ``mamba`` and ``attention`` (a layer of that kind on one row
``[T, hidden]``), and an ``E`` layer in two, ``route`` (its norm, router,
weights and shared expert on one row) and ``experts`` (one block of
``EXPERT_BLOCK`` experts over every token of one row, added to the row's
running sum), each called at the one shape — and no eager ``jax.numpy``
call outside them, so a run compiles four programs for it; the embedding
rows are gathered and widened and the head (one vector a row) computed in
NumPy on the host. The weights arrive as host arrays; an ``M`` or ``*``
layer's go up as stored (bfloat16) and are widened inside its function; an
``E`` layer's 128 experts go up a block of ``EXPERT_BLOCK`` at a time, each
block used by every row before the next goes up, so that neither 2.6 GB of
bfloat16 nor its 10 GB of float32 ever stands beside the program's weights
(the first form put the whole layer up and set-up peaked 0.11 GB under the
chip's memory: my chip run, PR 50); the host waits after each layer and
each block.

On one row's residual ``h`` ``[T, hidden]`` (text right-padded; every mixer
causal or pointwise, so no real position reads a padded one and nothing is
masked), layer ``i`` of kind ``hybrid_override_pattern[i]``:

- ``h_0 = Emb[ids]``; ``u = rms(h, norm_i)`` (eps ``layer_norm_epsilon``);
  ``h += Mixer_i(u)``;
- ``M``: ``p = u W_in``; ``z`` (``mamba_num_heads x mamba_head_dim``),
  ``xBC`` (that + ``2 n_groups ssm_state_size``), ``dt`` (heads) =
  split(p); ``xBC <- silu(conv(xBC) + bias)``, ``conv`` depthwise over
  positions, tap ``K - 1`` on position t itself, tap 0 on ``t - K + 1``,
  zeros before the row; ``x``, ``B``, ``C`` = split(xBC); ``dt <-
  softplus(dt + dt_bias)``; ``a = -exp(A_log)``; for t = 0, 1, ...: ``S <-
  exp(dt_t a) S + dt_t x_t B_t^T`` (a head at a time, ``S`` ``[head_dim,
  state]`` from zero, head j reading group ``j // (heads / n_groups)``),
  ``y_t = S C_t + D x_t``; ``y <- group_rms_{n_groups}(y * silu(z)) *
  mixer_norm``; the mixer gives ``y W_out``;
- ``*``: ``q = u W_q``, ``k = u W_k``, ``v = u W_v``, NO rotation; query
  head g reads key head ``g // (H / Hkv)``; ``softmax(q k^T /
  sqrt(head_dim) + causal mask)`` over the whole row at once, times v;
  ``ctx W_o``;
- ``E``: ``s = sigmoid(u W_g)`` over all ``n_routed_experts``; the chosen
  are the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias``; ``w = routed_scaling_factor x s_e / (sum over
  the chosen of s + 1e-20)`` (the bias is in NO weight), zero elsewhere;
  ``sum_e w_e down_e(relu(up_e(u))^2) + shared_down(relu(shared_up(u))^2)``;
- after the last layer ``rms(h, norm)`` at the last real token,
  ``Linear(hidden -> 2)``, ``softmax[:, 1]``.

The language-model head, ``rope_theta`` / ``partial_rotary_factor`` and the
other keys under ``not_run`` in the configuration file are no part of this
forward pass.

The four other branches, the rules and the blend are
``olmoe_reference.py``'s (the same five-branch ensemble around another text
branch): loaded from that file, not copied again.

``text_branch(..., operand=f, sites=...)`` is the seam
``tests/nemotron3_control.py`` lowers: ``f`` rounds BOTH operands of every
matmul of the named ``SITES`` — ``projections`` (``W_in``, ``W_out``, q, k,
v, o), ``core`` (both contractions of attention), ``scan`` (``x``, ``B``,
``C``, and the state where ``S C_t`` reads it), ``routed`` and ``shared``
(an expert's two matmuls); never the router, which the configuration states
in float32. ``parts=True`` also returns, layer by layer and row by row at
the last real token, the norm of the layer's update, of the residual it is
added to and of the routed experts' part of it, and the share of the row's
real tokens whose chosen experts the bias changed.
"""

from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path
from typing import Any, Callable, Dict, FrozenSet, Optional

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

SITES = frozenset(("projections", "core", "scan", "routed", "shared"))
# the experts widened to float32 at a time: 8 x 2 x 2688 x 1856 x 4 B =
# 0.32 GB, and their [8, T, 1856] activations 0.12 GB at 2,048 positions
EXPERT_BLOCK = 8
PARTS = ("update", "residual", "routed", "moved")

_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "layer_norm_epsilon", "mamba_num_heads",
         "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
         "n_routed_experts", "num_experts_per_tok", "routed_scaling_factor")


def _held(cfg: Dict[str, Any]) -> None:
    pattern = cfg["hybrid_override_pattern"]
    if set(pattern) - set("ME*") or len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(
            f"nemotron3_reference holds layers of kinds M, E and *, one for "
            f"each of num_hidden_layers: {pattern!r} for "
            f"{cfg['num_hidden_layers']} ('-', a dense MLP layer, is not "
            "written down here)")
    if (cfg["attention_bias"] or cfg["mamba_proj_bias"] or cfg["mlp_bias"]
            or cfg["use_bias"] or not cfg["use_conv_bias"]
            or cfg["mamba_hidden_act"] != "silu"
            or cfg["mlp_hidden_act"] != "relu2"
            or cfg["sliding_window"] is not None
            or (cfg["n_group"], cfg["topk_group"]) != (1, 1)
            or not cfg["norm_topk_prob"] or cfg["n_shared_experts"] != 1
            or cfg["n_routed_experts"] % EXPERT_BLOCK):
        raise ValueError(
            "nemotron3_reference holds bias-free projections, a convolution "
            "with bias, silu in the mixer, relu2 experts without a gate "
            "beside one shared expert, attention over the whole row (no "
            "window), one group of experts (n_group 1, topk_group 1) "
            f"renormalised over the chosen, in blocks of {EXPERT_BLOCK}")
    if cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
            == cfg["expand"] * cfg["hidden_size"]:
        raise ValueError(
            "nemotron3_reference: mamba_num_heads x mamba_head_dim equals "
            "expand x hidden_size: the two readings of the mixer's width "
            "could not be told apart")


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, operand: Optional[Callable],
              sites: FrozenSet[str]):
    """The four jitted functions for one set of sizes and one operand
    rounding (None: float32 as it is) at ``sites``: ``M`` and ``*`` a whole
    layer on one row, ``route`` and ``experts`` the two halves of an ``E``
    layer."""
    import jax
    import jax.numpy as jnp

    c = dict(zip(_KEYS, sizes))
    f32 = np.float32
    eps = c["layer_norm_epsilon"]
    heads, kv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    m_heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n, taps = c["n_groups"], c["ssm_state_size"], c["conv_kernel"]
    d_inner = m_heads * p
    experts, top_k = c["n_routed_experts"], c["num_experts_per_tok"]

    def lowered(x, site):
        return x if operand is None or site not in sites else operand(x)

    def matmul(x, w, site):
        return lowered(x, site) @ lowered(w.astype(jnp.float32), site)

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + f32(eps)) * w

    def silu(x):
        return x / (1.0 + jnp.exp(-x))

    def mamba(w, u):
        t = u.shape[0]
        proj = matmul(u, w["in_proj"], "projections")
        z, xbc, dt = (proj[:, :d_inner],
                      proj[:, d_inner:2 * d_inner + 2 * g * n],
                      proj[:, 2 * d_inner + 2 * g * n:])
        before = jnp.concatenate(
            [jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc], axis=0)
        conv = w["conv_bias"]
        for k in range(taps):
            conv = conv + before[k:k + t] * w["conv_weight"][k]
        xbc = silu(conv)
        x = lowered(xbc[:, :d_inner], "scan").reshape(t, m_heads, p)
        b_in = lowered(xbc[:, d_inner:d_inner + g * n], "scan"
                       ).reshape(t, g, n)
        c_in = lowered(xbc[:, d_inner + g * n:], "scan").reshape(t, g, n)
        dt = jnp.log1p(jnp.exp(dt + w["dt_bias"]))             # softplus
        a = -jnp.exp(w["A_log"])
        per_group = m_heads // g

        def position(state, now):           # state [heads, head_dim, N]
            x_t, b_t, c_t, dt_t = now
            b_h = jnp.repeat(b_t, per_group, axis=0)            # [heads, N]
            c_h = jnp.repeat(c_t, per_group, axis=0)
            state = (jnp.exp(dt_t * a)[:, None, None] * state
                     + dt_t[:, None, None] * x_t[:, :, None]
                     * b_h[:, None, :])
            y_t = jnp.sum(lowered(state, "scan") * c_h[:, None, :], axis=-1) \
                + w["D"][:, None] * x_t
            return state, y_t

        _, y = jax.lax.scan(position, jnp.zeros((m_heads, p, n), jnp.float32),
                            (x, b_in, c_in, dt))
        y = y.reshape(t, d_inner) * silu(z)
        y = y.reshape(t, g, d_inner // g)
        y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + f32(eps))
        return matmul(y.reshape(t, d_inner) * w["mixer_norm"], w["out_proj"],
                      "projections"), None

    def attention(w, u):
        t = u.shape[0]
        q = matmul(u, w["q_proj"], "projections").reshape(t, heads, d)
        k = matmul(u, w["k_proj"], "projections").reshape(t, kv, d)
        v = matmul(u, w["v_proj"], "projections").reshape(t, kv, d)
        k = jnp.repeat(k, heads // kv, axis=1)   # head g reads g // (H/Hkv)
        v = jnp.repeat(v, heads // kv, axis=1)
        scores = jnp.einsum("ihd,jhd->hij", lowered(q, "core"),
                            lowered(k, "core")) / f32(math.sqrt(d))
        seen = np.tril(np.ones((t, t), bool))
        scores = jnp.where(seen[None], scores, f32(-1e30))
        scores = jnp.exp(scores - scores.max(axis=-1, keepdims=True))
        weights = scores / scores.sum(axis=-1, keepdims=True)
        ctx = jnp.einsum("hij,jhd->ihd", lowered(weights, "core"),
                         lowered(v, "core"))
        return matmul(ctx.reshape(t, heads * d), w["o_proj"],
                      "projections"), None

    def relu2(x):
        return jnp.square(jnp.maximum(x, 0.0))

    def route(w, h, last):
        """An ``E`` layer on one row but for its routed experts: ``(u, the
        router's weights by block [blocks, EXPERT_BLOCK, T], h + shared(u),
        the share of the row's real tokens whose choice the bias moved)``."""
        t = h.shape[0]
        u = rms(h, w["norm"])
        s = 1.0 / (1.0 + jnp.exp(-(u @ w["router"].astype(jnp.float32))))
        chosen = jnp.argsort(-(s + w["e_score_correction_bias"]),
                             axis=-1)[:, :top_k]
        unbiased = jnp.argsort(-s, axis=-1)[:, :top_k]
        is_chosen = jnp.zeros((t, experts), bool).at[
            jnp.arange(t)[:, None], chosen].set(True)
        moved = jnp.any(~jnp.take_along_axis(is_chosen, unbiased, axis=-1),
                        axis=-1)
        weight = jnp.where(is_chosen, s, 0.0)
        weight = weight * f32(c["routed_scaling_factor"]) / (
            weight.sum(axis=-1, keepdims=True) + f32(1e-20))
        shared = matmul(relu2(matmul(u, w["shared_up"], "shared")),
                        w["shared_down"], "shared")
        real = jnp.arange(t) <= last
        return (u, weight.T.reshape(experts // EXPERT_BLOCK, EXPERT_BLOCK, t),
                h + shared, jnp.sum(moved & real) / jnp.sum(real))

    def expert_block(total, u, up, down, weights, block):
        """``total`` plus what one block of experts adds: every expert of
        the block over every token, weighed by the router's weight (zero
        where it was not chosen). ``up`` ``[EB, H, I]``, ``down`` ``[EB, I,
        H]`` as stored."""
        act = relu2(jnp.einsum(
            "th,ehi->eti", lowered(u, "routed"),
            lowered(up.astype(jnp.float32), "routed")))
        out = jnp.einsum("eti,eih->eth", lowered(act, "routed"),
                         lowered(down.astype(jnp.float32), "routed"))
        w_block = jax.lax.dynamic_index_in_dim(weights, block, 0,
                                               keepdims=False)
        return total + jnp.sum(out * w_block[:, :, None], axis=0)

    def layer_of(mixer):
        def layer(w, h, last):
            update, _ = mixer(w, rms(h, w["norm"]))
            return h + update, jnp.stack([jnp.linalg.norm(update[last]),
                                          jnp.linalg.norm(h[last])])
        layer.__name__ = mixer.__name__
        return jax.jit(layer)

    expert_block.__name__ = "experts"
    return {"M": layer_of(mamba), "*": layer_of(attention),
            "route": jax.jit(route), "experts": jax.jit(expert_block)}


def text_branch(nemotron: Dict[str, Any], token_ids, token_mask,
                cfg: Dict[str, Any], operand: Optional[Callable] = None,
                sites: FrozenSet[str] = SITES, parts: bool = False):
    """The text column ``f32[B]`` of host arrays ``nemotron`` (the
    program's parameter tree), a row at a time, a layer's weights on the
    device at a time. With ``parts`` also ``f64[layers, 4, B]``: ``PARTS``
    at each row's last real token."""
    import jax

    _held(cfg)
    if not frozenset(sites) <= SITES:
        raise ValueError(f"nemotron3_reference: sites {sorted(sites)} of "
                         f"{sorted(SITES)}")
    layers = _programs(tuple(cfg[k] for k in _KEYS), operand,
                       frozenset(sites))
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    table = np.asarray(nemotron["embed_tokens"])
    kept = np.zeros((len(nemotron["layers"]), len(PARTS), len(ids)))
    rows = range(len(ids))
    at = [np.int32(n) for n in last]

    with jax.default_matmul_precision("highest"):
        hidden = [table[row].astype(np.float32) for row in ids]
        for index, (kind, weights) in enumerate(zip(
                cfg["hybrid_override_pattern"], nemotron["layers"])):
            if kind != "E":
                on_device = jax.device_put(weights)
                for row in rows:
                    hidden[row], norms = layers[kind](on_device, hidden[row],
                                                      at[row])
                    if parts:
                        kept[index, :2, row] = np.asarray(norms, np.float64)
                # calls are queued, not run: without the wait the host puts
                # every layer's weights up before the first has finished
                jax.block_until_ready(hidden)
                del on_device
                continue
            small = jax.device_put({k: v for k, v in weights.items()
                                    if k not in ("up_proj", "down_proj")})
            routed = [layers["route"](small, hidden[row], at[row])
                      for row in rows]
            before = hidden
            based = [r[2] for r in routed]      # h + shared(u)
            hidden = list(based)
            for block in range(cfg["n_routed_experts"] // EXPERT_BLOCK):
                lo, hi = block * EXPERT_BLOCK, (block + 1) * EXPERT_BLOCK
                up, down = jax.device_put((weights["up_proj"][lo:hi],
                                           weights["down_proj"][lo:hi]))
                for row in rows:
                    hidden[row] = layers["experts"](
                        hidden[row], routed[row][0], up, down,
                        routed[row][1], np.int32(block))
                jax.block_until_ready(hidden)
                del up, down
            if parts:
                for row in rows:
                    after, base, h = (np.asarray(x, np.float64)[last[row]]
                                      for x in (hidden[row], based[row],
                                                before[row]))
                    kept[index, :, row] = (
                        np.linalg.norm(after - h), np.linalg.norm(h),
                        np.linalg.norm(after - base), float(routed[row][3]))
            del small, routed, before, based
    # the head on the host: one vector a row
    pooled = np.stack([np.asarray(hidden[row], np.float32)[last[row]]
                       for row in range(len(ids))])
    pooled = pooled / np.sqrt(
        np.mean(pooled * pooled, axis=-1, keepdims=True)
        + np.float32(cfg["layer_norm_epsilon"])) \
        * np.asarray(nemotron["norm"], np.float32)
    logits = pooled @ np.asarray(nemotron["score"], np.float32)
    e = np.exp(logits - logits.max(axis=-1, keepdims=True))
    out = (e / e.sum(axis=-1, keepdims=True))[:, 1].astype(np.float32)
    return (out, kept) if parts else out


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: this
    architecture reads its sizes and its pattern from it."""
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
