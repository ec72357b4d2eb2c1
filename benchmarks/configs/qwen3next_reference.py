"""Plain float32 reference of the five-branch ensemble whose text branch is
Qwen3-Next-80B-A3B-Instruct's stack.

What ``qwen3-next-80b-a3b-s2048`` is held to. From the same weights and the
same assembled inputs it computes what the served program computes, the text
branch in the textbook form of the equations below — **the delta rule a
position at a time, never in chunks, with no WY form and no triangular
solve; a materialised causal softmax; every held expert over every token,
weighed by zero where the router did not choose it** (no sort, no groups, no
capacity) — in float32 throughout, sharing no line with ``models/``,
``ops/`` or ``scoring/`` and importing nothing from the package. It reads
the weights by the parameter names ``models/qwen3_next.py`` stores them
under and every size from the configuration file's keys: those are the data
format, not the arithmetic.

The text column is ``jax.numpy`` under ``jax.default_matmul_precision(
"highest")`` on whatever device the run has: FOUR jitted functions — ``L``
and ``F`` (a layer's mixer half on one row ``[T, hidden]``), and its sparse
half in two, ``route`` (its norm, router, weights and gated shared expert on
one row) and ``experts`` (one block of ``EXPERT_BLOCK`` held experts over
every token of one row, added to the row's running sum), each called at the
one shape — and no eager ``jax.numpy`` call outside them; the embedding rows
are gathered and widened and the head computed in NumPy on the host. A
layer's small weights go up as stored (bfloat16) and are widened inside its
function; its 256 held experts go up a block of ``EXPERT_BLOCK`` at a time,
each block used by every row before the next goes up
(``nemotron3_reference.py`` says why); the host waits after each layer and
each block.

On one row's residual ``h`` ``[T, hidden]`` (text right-padded; every mixer
causal or pointwise, so no real position reads a padded one and nothing is
masked), with ``znorm(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) (1 + w)``:

- ``h_0 = Emb[ids]``; layer ``i`` is ``F`` where ``(i + 1) %
  full_attention_interval == 0``, else ``L``; ``h += Mixer_i(znorm(h,
  input_layernorm))``; ``h += MoE_i(znorm(h, post_attention_layernorm))``;
- ``L``: ``in_proj_qkvz``'s columns are grouped by KEY head, ``[q (dk) | k
  (dk) | v (ratio dv) | z (ratio dv)]`` a head, ``in_proj_ba``'s ``[b
  (ratio) | a (ratio)]`` — the RESULT is split here (the program slices the
  weight); ``q | k | v <- silu(conv(q | k | v))``, depthwise over positions,
  tap ``K - 1`` on position t itself, zeros before the row, NO bias; per
  head ``q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk)``, ``k <- k / sqrt(sum k^2
  + 1e-6)``; ``beta = sigmoid(b)``, ``alpha = exp(-exp(A_log) softplus(a +
  dt_bias))``; for t = 0, 1, ...: ``S <- alpha_t S``, ``u = beta_t (v_t - S^T
  k_t)``, ``S <- S + k_t u^T``, ``o_t = S^T q_t`` (a value head at a time,
  ``S`` ``[dk, dv]`` from zero, value head j reading key head ``j //
  ratio``); ``o <- rms(o; delta_norm) silu(z)`` per head (the norm first, a
  plain weight); ``o out_proj``;
- ``F``: ``q_proj``'s columns are ``[query (d) | gate (d)]`` a head;
  ``query <- znorm(query; q_norm)``, ``key <- znorm(key; k_norm)`` per head;
  rotate-half RoPE on the first ``partial_rotary_factor d`` dims (pairs
  ``(i, i + rot / 2)``, theta ``rope_theta``); query head g reads key head
  ``g // (H / Hkv)``; ``softmax(q k^T / sqrt(d) + causal mask)`` over the
  whole row at once, times v; ``ctx sigmoid(gate)`` elementwise; ``o_proj``;
- MoE: ``p = softmax(m W_g)`` over all ``router_experts``; the
  ``num_experts_per_tok`` largest, ``w = p_e / sum over the chosen of p``,
  zero elsewhere; ``sum over the HELD experts of w_e down_e(silu(gate_e m)
  up_e m) + sigmoid(m w_sg) shared_down(silu(shared_gate m) shared_up m)``
  — the held experts are ``expert_share``'s (``expert_offset`` on,
  ``num_experts`` of them): what the absent ones would add is left out, as
  in the program;
- after the last layer ``znorm(h, norm)`` at the last real token,
  ``Linear(hidden -> 2)``, ``softmax[:, 1]``.

Departures from the published description: the expert share, the
classification head in place of the language-model head, and no
multi-token-prediction module (the configuration file's ``not_run``).

The four other branches, the rules and the blend are
``olmoe_reference.py``'s: loaded from that file, not copied again.

``text_branch(..., operand=f, sites=...)`` is the seam
``tests/qwen3next_control.py`` lowers: ``f`` rounds BOTH operands of every
matmul of the named ``SITES`` — ``projections`` (the in and out projections
of both mixers), ``core`` (both contractions of attention), ``scan`` (``q``,
``k``, ``v``, and the state where ``S^T k`` and ``S^T q`` read it),
``routed`` and ``shared`` (an expert's three matmuls); never the router,
which the configuration states in float32. ``parts=True`` also returns,
layer by layer and row by row at the last real token, the norms of the
mixer's update, of the residual it is added to, of the sparse half's update
and of its routed part, the HELD MASS of that token's ten weights (what of
them went to experts this chip holds) and its MARGIN: the router's logit of
the tenth expert less the eleventh's where one of the two is held and the
other absent, else infinity — a swap at the tenth rank between a held and an
absent expert moves the held mass by that expert's whole weight, and a
margin under the rounding of the logits is where one happens.
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
# the experts widened to float32 at a time: 8 x 3 x 2048 x 512 x 4 B =
# 0.10 GB, and their [8, T, 512] activations 0.03 GB at 2,048 positions
EXPERT_BLOCK = 8
PARTS = ("mixer", "residual", "sparse", "routed", "held", "margin")
EXPERT_KEYS = ("gate_proj", "up_proj", "down_proj")
# what ``route`` reads of a layer: the same of both kinds, so one program
ROUTE_KEYS = ("post_attention_layernorm", "router", "shared_gate",
              "shared_up", "shared_down", "shared_expert_gate")

_KEYS = ("hidden_size", "head_dim", "num_attention_heads",
         "num_key_value_heads", "rms_norm_eps", "linear_num_key_heads",
         "linear_num_value_heads", "linear_key_head_dim",
         "linear_value_head_dim", "linear_conv_kernel_dim",
         "partial_rotary_factor", "rope_theta", "router_experts",
         "num_experts", "expert_offset", "num_experts_per_tok")


def _share(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The experts held, under the reference's names: the file's
    ``num_experts`` (held) of ``expert_share``'s chips' times as many."""
    share, held = cfg["expert_share"], cfg["num_experts"]
    return {"router_experts": share["chips"] * held, "num_experts": held,
            "expert_offset": share["index"] * held}


def _held(cfg: Dict[str, Any]) -> None:
    if (cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]
            or cfg["hidden_act"] != "silu" or not cfg["norm_topk_prob"]
            or cfg["rope_scaling"] is not None or cfg["use_sliding_window"]
            or cfg["num_experts"] % EXPERT_BLOCK):
        raise ValueError(
            "qwen3next_reference holds every layer sparse "
            "(decoder_sparse_step 1, no mlp_only_layers), SiLU, weights "
            "renormalised over the chosen, an unscaled rotation, no window, "
            f"and held experts in blocks of {EXPERT_BLOCK}")


def layer_kinds(cfg: Dict[str, Any]) -> str:
    interval = cfg["full_attention_interval"]
    return "".join("F" if (i + 1) % interval == 0 else "L"
                   for i in range(cfg["num_hidden_layers"]))


@functools.lru_cache(maxsize=None)
def _programs(sizes: tuple, operand: Optional[Callable],
              sites: FrozenSet[str]):
    """The four jitted functions for one set of sizes and one operand
    rounding (None: float32 as it is) at ``sites``."""
    import jax
    import jax.numpy as jnp

    c = dict(zip(_KEYS, sizes))
    f32 = np.float32
    eps = c["rms_norm_eps"]
    heads, kv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"])
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv, taps = (c["linear_key_head_dim"], c["linear_value_head_dim"],
                    c["linear_conv_kernel_dim"])
    ratio = hv // hk
    rot = int(d * c["partial_rotary_factor"])
    top_k, routed_to = c["num_experts_per_tok"], c["router_experts"]
    first, held = c["expert_offset"], c["num_experts"]

    def lowered(x, site):
        return x if operand is None or site not in sites else operand(x)

    def matmul(x, w, site):
        return lowered(x, site) @ lowered(w.astype(jnp.float32), site)

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + f32(eps)) * w

    def znorm(x, w):
        return rms(x, 1.0 + w)

    def silu(x):
        return x / (1.0 + jnp.exp(-x))

    def sigmoid(x):
        return 1.0 / (1.0 + jnp.exp(-x))

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                            + f32(1e-6))

    def linear(w, u):
        t = u.shape[0]
        proj = matmul(u, w["in_proj_qkvz"], "projections").reshape(
            t, hk, 2 * dk + 2 * ratio * dv)
        q, k = proj[..., :dk], proj[..., dk:2 * dk]
        v = proj[..., 2 * dk:2 * dk + ratio * dv]
        z = proj[..., 2 * dk + ratio * dv:].reshape(t, hv, dv)
        ba = matmul(u, w["in_proj_ba"], "projections").reshape(
            t, hk, 2 * ratio)
        b, a = (ba[..., :ratio].reshape(t, hv),
                ba[..., ratio:].reshape(t, hv))
        qkv = jnp.concatenate([q.reshape(t, -1), k.reshape(t, -1),
                               v.reshape(t, -1)], axis=-1)
        before = jnp.concatenate(
            [jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32), qkv], axis=0)
        conv = 0.0
        for tap in range(taps):
            conv = conv + before[tap:tap + t] * w["conv_weight"][tap]
        qkv = silu(conv)
        q = l2(qkv[:, :hk * dk].reshape(t, hk, dk)) / f32(math.sqrt(dk))
        k = l2(qkv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk))
        v = qkv[:, 2 * hk * dk:].reshape(t, hv, dv)
        q, k, v = (lowered(x, "scan") for x in (q, k, v))
        beta = sigmoid(b)
        alpha = jnp.exp(-jnp.exp(w["A_log"])
                        * jnp.log1p(jnp.exp(a + w["dt_bias"])))

        def position(state, now):           # state [value heads, dk, dv]
            q_t, k_t, v_t, beta_t, alpha_t = now
            k_h = jnp.repeat(k_t, ratio, axis=0)                # [hv, dk]
            q_h = jnp.repeat(q_t, ratio, axis=0)
            state = alpha_t[:, None, None] * state
            seen = jnp.sum(lowered(state, "scan") * k_h[:, :, None], axis=1)
            u_t = beta_t[:, None] * (v_t - seen)
            state = state + k_h[:, :, None] * u_t[:, None, :]
            o_t = jnp.sum(lowered(state, "scan") * q_h[:, :, None], axis=1)
            return state, o_t

        _, o = jax.lax.scan(position, jnp.zeros((hv, dk, dv), jnp.float32),
                            (q, k, v, beta, alpha))
        y = rms(o, w["delta_norm"]) * silu(z)
        return matmul(y.reshape(t, hv * dv), w["out_proj"], "projections")

    def rotate(x, positions):
        half = rot // 2
        inv = f32(c["rope_theta"]) ** (
            -jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        angle = positions[:, None, None] * inv[None, None, :]
        x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
        return jnp.concatenate([
            x1 * jnp.cos(angle) - x2 * jnp.sin(angle),
            x2 * jnp.cos(angle) + x1 * jnp.sin(angle), rest], axis=-1)

    def full(w, u):
        t = u.shape[0]
        both = matmul(u, w["q_proj"], "projections").reshape(t, heads, 2 * d)
        q, gate = both[..., :d], both[..., d:]
        k = matmul(u, w["k_proj"], "projections").reshape(t, kv, d)
        v = matmul(u, w["v_proj"], "projections").reshape(t, kv, d)
        positions = jnp.arange(t, dtype=jnp.float32)
        q = rotate(znorm(q, w["q_norm"]), positions)
        k = rotate(znorm(k, w["k_norm"]), positions)
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
        return matmul((ctx * sigmoid(gate)).reshape(t, heads * d),
                      w["o_proj"], "projections")

    def route(w, h):
        """The sparse half on one row but for its routed experts: ``(m, the
        held experts' weights by block [blocks, EXPERT_BLOCK, T], h +
        gated shared(m), the margin [T])``."""
        t = h.shape[0]
        m = znorm(h, w["post_attention_layernorm"])
        logits = m @ w["router"].astype(jnp.float32)
        p = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
        p = p / p.sum(axis=-1, keepdims=True)
        ranked = jnp.argsort(-p, axis=-1)[:, :top_k + 1]
        chosen = ranked[:, :top_k]
        tenth, next_ = ranked[:, top_k - 1], ranked[:, top_k]
        here = (ranked >= first) & (ranked < first + held)
        margin = jnp.where(
            here[:, top_k - 1] != here[:, top_k],
            jnp.take_along_axis(logits, tenth[:, None], axis=-1)[:, 0]
            - jnp.take_along_axis(logits, next_[:, None], axis=-1)[:, 0],
            jnp.inf)
        is_chosen = jnp.zeros((t, routed_to), bool).at[
            jnp.arange(t)[:, None], chosen].set(True)
        weight = jnp.where(is_chosen, p, 0.0)
        weight = weight / weight.sum(axis=-1, keepdims=True)
        mine = weight[:, first:first + held]
        shared = matmul(
            silu(matmul(m, w["shared_gate"], "shared"))
            * matmul(m, w["shared_up"], "shared"), w["shared_down"], "shared")
        gate = sigmoid(m @ w["shared_expert_gate"].astype(jnp.float32))
        return (m, mine.T.reshape(held // EXPERT_BLOCK, EXPERT_BLOCK, t),
                h + gate * shared, margin)

    def expert_block(total, m, gate, up, down, weights, block):
        """``total`` plus what one block of held experts adds: every expert
        of the block over every token, weighed by the router's weight (zero
        where it was not chosen). ``gate`` / ``up`` ``[EB, H, I]``, ``down``
        ``[EB, I, H]`` as stored."""
        x = lowered(m, "routed")
        act = silu(jnp.einsum(
            "th,ehi->eti", x, lowered(gate.astype(jnp.float32), "routed"))
        ) * jnp.einsum("th,ehi->eti", x,
                       lowered(up.astype(jnp.float32), "routed"))
        out = jnp.einsum("eti,eih->eth", lowered(act, "routed"),
                         lowered(down.astype(jnp.float32), "routed"))
        w_block = jax.lax.dynamic_index_in_dim(weights, block, 0,
                                               keepdims=False)
        return total + jnp.sum(out * w_block[:, :, None], axis=0)

    def layer_of(mixer):
        def layer(w, h, last):
            update = mixer(w, znorm(h, w["input_layernorm"]))
            return h + update, jnp.stack([jnp.linalg.norm(update[last]),
                                          jnp.linalg.norm(h[last])])
        layer.__name__ = mixer.__name__
        return jax.jit(layer)

    expert_block.__name__ = "experts"
    return {"L": layer_of(linear), "F": layer_of(full),
            "route": jax.jit(route), "experts": jax.jit(expert_block)}


def text_branch(qwen: Dict[str, Any], token_ids, token_mask,
                cfg: Dict[str, Any], operand: Optional[Callable] = None,
                sites: FrozenSet[str] = SITES, parts: bool = False):
    """The text column ``f32[B]`` of host arrays ``qwen`` (the program's
    parameter tree), a row at a time, a layer's weights on the device at a
    time. With ``parts`` also ``f64[layers, 6, B]``: ``PARTS`` at each
    row's last real token."""
    import jax

    _held(cfg)
    if not frozenset(sites) <= SITES:
        raise ValueError(f"qwen3next_reference: sites {sorted(sites)} of "
                         f"{sorted(SITES)}")
    sizes = {**cfg, **_share(cfg)}
    layers = _programs(tuple(sizes[k] for k in _KEYS), operand,
                       frozenset(sites))
    ids, mask = np.asarray(token_ids), np.asarray(token_mask, bool)
    last = np.maximum(mask.sum(axis=-1) - 1, 0)
    table = np.asarray(qwen["embed_tokens"])
    kept = np.zeros((len(qwen["layers"]), len(PARTS), len(ids)))
    rows = range(len(ids))
    at = [np.int32(n) for n in last]

    with jax.default_matmul_precision("highest"):
        hidden = [table[row].astype(np.float32) for row in ids]
        for index, (kind, weights) in enumerate(zip(layer_kinds(cfg),
                                                    qwen["layers"])):
            small = jax.device_put({k: v for k, v in weights.items()
                                    if k not in EXPERT_KEYS})
            for row in rows:
                hidden[row], norms = layers[kind](small, hidden[row],
                                                  at[row])
                if parts:
                    kept[index, :2, row] = np.asarray(norms, np.float64)
            sparse = {k: small[k] for k in ROUTE_KEYS}
            routed = [layers["route"](sparse, hidden[row]) for row in rows]
            before = hidden
            based = [r[2] for r in routed]      # h + gated shared(m)
            hidden = list(based)
            # calls are queued, not run: without the wait the host puts
            # every block's weights up before the first has finished
            jax.block_until_ready(hidden)
            for block in range(cfg["num_experts"] // EXPERT_BLOCK):
                lo, hi = block * EXPERT_BLOCK, (block + 1) * EXPERT_BLOCK
                gate, up, down = jax.device_put(tuple(
                    weights[k][lo:hi] for k in EXPERT_KEYS))
                for row in rows:
                    hidden[row] = layers["experts"](
                        hidden[row], routed[row][0], gate, up, down,
                        routed[row][1], np.int32(block))
                jax.block_until_ready(hidden)
                del gate, up, down
            if parts:
                for row in rows:
                    after, base, h = (np.asarray(x, np.float64)[last[row]]
                                      for x in (hidden[row], based[row],
                                                before[row]))
                    kept[index, 2:, row] = (
                        np.linalg.norm(after - h),
                        np.linalg.norm(after - base),
                        float(np.asarray(routed[row][1], np.float64)[
                            :, :, last[row]].sum()),
                        float(np.asarray(routed[row][3])[last[row]]))
            del small, sparse, routed, before, based
    # the head on the host: one vector a row
    pooled = np.stack([np.asarray(hidden[row], np.float32)[last[row]]
                       for row in range(len(ids))])
    pooled = pooled / np.sqrt(
        np.mean(pooled * pooled, axis=-1, keepdims=True)
        + np.float32(cfg["rms_norm_eps"])) \
        * (1.0 + np.asarray(qwen["norm"], np.float32))
    logits = pooled @ np.asarray(qwen["score"], np.float32)
    e = np.exp(logits - logits.max(axis=-1, keepdims=True))
    out = (e / e.sum(axis=-1, keepdims=True))[:, 1].astype(np.float32)
    return (out, kept) if parts else out


def score(models, batch, params, model_valid, cfg: Dict[str, Any]
          ) -> Dict[str, Any]:
    """Everything the served program returns for ``batch`` (host NumPy
    copies of the program's containers). ``branches`` is [B, 5] in
    ``BRANCHES`` order. ``cfg`` is the configuration file: this
    architecture reads its sizes from it."""
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
