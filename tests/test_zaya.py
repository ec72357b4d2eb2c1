"""The ZAYA1 text encoder (models/zaya.py) against an independent plain
``jax.numpy`` float32 reference kept in this file, each piece of its
attention against a hand-written loop, the compacted program against every
slot routed, and the seam it enters the scorer through.

The reference shares no line with the program: convolutions by loops over
positions and taps, grouped-query attention with the keys REPEATED, every
expert computed for every token densely and masked (no sort, no groups),
the router's state kept in slot order, all under
``jax.default_matmul_precision("highest")``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import olmoe, zaya
from realtime_fraud_detection_tpu.models.zaya import (
    TINY_ZAYA,
    ZayaConfig,
    init_zaya_params,
    zaya_encode,
    zaya_logits,
    zaya_predict,
)
from realtime_fraud_detection_tpu.ops import attention_reference

F32 = jnp.float32
# hidden 128, 3 layers (the router's state is carried twice), 8 query / 2
# key-value heads of 16, 4 experts of width 128, one a token
CFG = TINY_ZAYA
B, T = 4, 16
LENGTHS = (16, 5, 1, 9)
RAGGED = (12, 5, 1, 9, 0)                  # 27 real tokens of 60 slots
CAPACITIES = {"every_slot": None, "all_60": 60, "48": 48, "32": 32,
              "exactly_27": 27}


# ----------------------------------------------------------- the reference
def _ref_rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _ref_conv_depthwise(c, taps):
    """[B, T, C] by [n, C]: y[t] = sum_j taps[j] * c[t - (n - 1) + j]."""
    n, t = taps.shape[0], c.shape[1]
    rows = []
    for pos in range(t):
        acc = jnp.zeros_like(c[:, 0])
        for j in range(n):
            src = pos - (n - 1) + j
            if src >= 0:
                acc = acc + taps[j] * c[:, src]
        rows.append(acc)
    return jnp.stack(rows, axis=1)


def _ref_conv_grouped(c, w, heads, d):
    """[B, T, heads * D] by [heads, n * D, D]: tap j of head g is the
    matrix w[g, j*D:(j+1)*D], weighing position t - (n - 1) + j."""
    n, t = w.shape[1] // d, c.shape[1]
    c = c.reshape(c.shape[0], t, heads, d)
    rows = []
    for pos in range(t):
        acc = jnp.zeros_like(c[:, 0])
        for j in range(n):
            src = pos - (n - 1) + j
            if src >= 0:
                acc = acc + jnp.einsum("bgi,gio->bgo", c[:, src],
                                       w[:, j * d:(j + 1) * d])
        rows.append(acc)
    return jnp.stack(rows, axis=1).reshape(c.shape[0], t, heads * d)


def _ref_rope_partial(x, theta, rot):
    """[B, heads, T, D]: pairs (i, i + rot/2) of the first ``rot`` dims
    rotated by pos * theta^(-2i/rot); the dims past ``rot`` untouched."""
    t = x.shape[-2]
    freq = theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    angle = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _ref_attention(layer, h, mask, cfg):
    b, t, _ = h.shape
    heads, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
    g = heads // kv
    x = _ref_rms(h, layer["input_layernorm"], cfg.rms_norm_eps)
    q_lat = x @ layer["q_proj"].astype(F32)
    k_lat = x @ layer["k_proj"].astype(F32)
    c = jnp.concatenate([q_lat, k_lat], axis=-1)
    c = _ref_conv_depthwise(c, layer["conv_depthwise"].astype(F32))
    c = _ref_conv_grouped(c, layer["conv_grouped"].astype(F32), heads + kv, d)
    q_pre = q_lat.reshape(b, t, heads, d)
    k_pre = k_lat.reshape(b, t, kv, d)
    q = c[..., :heads * d].reshape(b, t, heads, d) + 0.5 * (
        q_pre + jnp.repeat(k_pre, g, axis=2))
    k = c[..., heads * d:].reshape(b, t, kv, d) + 0.5 * (
        q_pre.reshape(b, t, kv, g, d).mean(axis=3) + k_pre)
    eps = cfg.rms_norm_eps
    q = np.sqrt(d) * q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + d * eps)
    k = np.sqrt(d) * k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + d * eps)
    k = k * layer["temperature"][None, None, :, None]
    q = _ref_rope_partial(q.transpose(0, 2, 1, 3), cfg.rope_theta,
                          cfg.rotary_dim)
    k = _ref_rope_partial(k.transpose(0, 2, 1, 3), cfg.rope_theta,
                          cfg.rotary_dim)
    x_before = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    w_v = layer["v_proj"].astype(F32)
    half = kv * d // 2
    v = jnp.concatenate([x @ w_v[:, :half], x_before @ w_v[:, half:]],
                        axis=-1).reshape(b, t, kv, d).transpose(0, 2, 1, 3)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    visible = jnp.tril(jnp.ones((t, t), bool))[None, None] \
        & mask[:, None, None, :]
    ctx = jnp.einsum("bhqk,bhkd->bhqd",
                     jax.nn.softmax(jnp.where(visible, scores, -1e30), -1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, heads * d)
    return h + ctx @ layer["o_proj"].astype(F32)


def _ref_router(layer, x, previous, cfg):
    r = x @ layer["router_down"].astype(F32)
    if previous is not None:
        r = r + layer["router_gamma"] * previous
    z = _ref_rms(r, layer["router_norm"], cfg.rms_norm_eps)
    z = jax.nn.gelu(z @ layer["router_w1"].astype(F32), approximate=False)
    z = jax.nn.gelu(z @ layer["router_w2"].astype(F32), approximate=False)
    return jax.nn.softmax(z @ layer["router_w3"].astype(F32), axis=-1), r


def _ref_moe(layer, x, s, routing=None):
    """Dense over ALL experts, then masked to the chosen one."""
    if routing is None:
        routing = jnp.argmax(s + layer["router_bias"], axis=-1)
    chosen = jax.nn.one_hot(routing, s.shape[-1], dtype=F32)
    gate = jnp.einsum("nh,ehi->nei", x, layer["gate_proj"].astype(F32))
    up = jnp.einsum("nh,ehi->nei", x, layer["up_proj"].astype(F32))
    out = jnp.einsum("nei,eih->neh", gate * jax.nn.sigmoid(gate) * up,
                     layer["down_proj"].astype(F32))
    return jnp.sum((s * chosen)[:, :, None] * out, axis=1), routing


def ref_hidden(params, ids, mask, cfg, routing=None):
    """Hidden states before the final norm, the last router state (slot
    order) and each layer's chosen experts; ``routing`` (one ``[tokens]``
    per layer) overrides the argmax."""
    with jax.default_matmul_precision("highest"):
        ids, mask = jnp.asarray(ids), jnp.asarray(mask)
        b, t = ids.shape
        h = params["embed_tokens"].astype(F32)[ids]
        r, chosen = None, []
        for i, layer in enumerate(params["layers"]):
            h = _ref_attention(layer, h, mask, cfg)
            x = _ref_rms(h, layer["post_attention_layernorm"],
                         cfg.rms_norm_eps).reshape(b * t, -1)
            s, r = _ref_router(layer, x, r, cfg)
            y, picked = _ref_moe(layer, x, s,
                                 None if routing is None else routing[i])
            chosen.append(picked)
            h = h + y.reshape(b, t, -1)
        return h, r, chosen


def ref_logits(params, ids, mask, cfg, routing=None):
    with jax.default_matmul_precision("highest"):
        h, _, _ = ref_hidden(params, ids, mask, cfg, routing)
        last = jnp.maximum(jnp.asarray(mask).sum(axis=-1) - 1, 0)
        pooled = _ref_rms(h[jnp.arange(h.shape[0]), last], params["norm"],
                          cfg.rms_norm_eps)
        return pooled @ params["score"]


# ---------------------------------------------------------------- fixtures
def _stirred(params, seed=5):
    """The vectors a random initialisation sets to one or zero (norm
    weights, temperatures, gamma, the balancing bias) moved off them, so
    that an index on the wrong axis shows; and the router's three matrices
    scaled up: at these widths (a 32-wide router) initializer_range 0.02
    lets the GELUs' common offset drown the token's own signal and every
    token of a layer picks the same expert, which would leave the sort, the
    groups and the way home untested (``test_routing_spreads...``)."""
    rng = np.random.default_rng(seed)

    def stir(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "router_bias":
            return x + jnp.asarray(rng.normal(0, 0.05, x.shape), x.dtype)
        if name in ("router_w1", "router_w2", "router_w3"):
            return (x.astype(F32) * 20.0).astype(x.dtype)
        if x.ndim == 1:
            return x * jnp.asarray(rng.uniform(0.5, 1.5, x.shape), x.dtype)
        return x

    return jax.tree_util.tree_map_with_path(stir, params)


@pytest.fixture(scope="module")
def params():
    return _stirred(init_zaya_params(jax.random.PRNGKey(1), CFG))


@pytest.fixture(scope="module")
def params32(params):
    return jax.tree_util.tree_map(lambda x: x.astype(F32), params)


@pytest.fixture(scope="module")
def text():
    rng = np.random.default_rng(17)
    ids = rng.integers(0, CFG.vocab_size, (B, T)).astype(np.int32)
    return ids, np.arange(T)[None, :] < np.asarray(LENGTHS)[:, None]


@pytest.fixture(scope="module")
def ragged():
    rng = np.random.default_rng(29)
    ids = rng.integers(0, CFG.vocab_size, (len(RAGGED), 12)).astype(np.int32)
    return ids, np.arange(12)[None, :] < np.asarray(RAGGED)[:, None]


def _program_routing(params, ids, mask, cfg):
    """Each layer's chosen expert per slot, as the PROGRAM chooses on its
    own hidden stream (the public pieces, layer by layer)."""
    cos, sin = olmoe.rope_tables(ids.shape[1], cfg.rotary_dim,
                                 cfg.rope_theta)
    h = params["embed_tokens"][jnp.asarray(ids)].astype(F32)
    r, chosen = None, []
    for layer in params["layers"]:
        attn = zaya.zaya_attention(layer, h, jnp.asarray(mask), cfg, cos, sin)
        x = olmoe.rms_norm(attn, layer["post_attention_layernorm"],
                           cfg.rms_norm_eps).reshape(-1, h.shape[-1])
        experts, _, _ = zaya.zaya_route(layer, x, r, cfg)
        chosen.append(experts[:, 0])
        h, r, _ = zaya.zaya_layer(layer, h, r, jnp.asarray(mask), cfg, cos,
                                  sin)
    return chosen


# ------------------------------------------------- against the reference
def test_routing_spreads_over_the_experts_in_these_tests(params32, text,
                                                        ragged):
    """What the comparisons below stand on: every layer sends its tokens to
    more than one expert, so groups are ragged and the permutations are not
    the identity."""
    for ids, mask in (text, ragged):
        for chosen in _program_routing(params32, ids, mask, CFG):
            counts = np.bincount(np.asarray(chosen)[mask.reshape(-1)],
                                 minlength=CFG.num_experts)
            assert (counts > 0).sum() >= 2, counts


def test_stored_dtypes_and_shapes_are_the_checkpoints():
    p = init_zaya_params(jax.random.PRNGKey(0), CFG)
    layer = p["layers"][0]
    h, d, r, e = (CFG.hidden_size, CFG.head_dim, CFG.router_hidden_size,
                  CFG.num_experts)
    shapes = {"q_proj": (h, 8 * d), "k_proj": (h, 2 * d),
              "v_proj": (h, 2 * d), "o_proj": (8 * d, h),
              "conv_depthwise": (2, 10 * d), "conv_grouped": (10, 2 * d, d),
              "temperature": (2,), "router_down": (h, r),
              "router_gamma": (r,), "router_norm": (r,),
              "router_w1": (r, r), "router_w2": (r, r), "router_w3": (r, e),
              "router_bias": (e,), "gate_proj": (e, h, 128),
              "up_proj": (e, h, 128), "down_proj": (e, 128, h),
              "input_layernorm": (h,), "post_attention_layernorm": (h,)}
    assert {k: v.shape for k, v in layer.items()} == shapes
    f32 = {"conv_depthwise", "temperature", "router_gamma", "router_norm",
           "router_bias", "input_layernorm", "post_attention_layernorm"}
    for name, value in layer.items():
        assert value.dtype == (F32 if name in f32 else jnp.bfloat16), name
    assert p["embed_tokens"].dtype == jnp.bfloat16
    assert p["score"].dtype == p["norm"].dtype == F32
    # what a random initialisation leaves at one and at zero
    for name in ("temperature", "router_gamma", "router_norm"):
        assert (np.asarray(layer[name]) == 1.0).all()
    assert (np.asarray(layer["router_bias"]) == 0.0).all()
    # the taps are drawn at 1/sqrt(fan_in), not at initializer_range
    assert 0.4 < float(jnp.std(layer["conv_depthwise"])) < 1.0
    assert len(p["layers"]) == CFG.num_hidden_layers == 3


@pytest.mark.parametrize("seed", [3, 11, 3000000007])
def test_float32_program_matches_the_plain_reference(seed, text):
    """Hidden states at every real position, the carried router state, the
    routing itself and the logits, on seeded weights."""
    ids, mask = text
    p = _stirred(jax.tree_util.tree_map(
        lambda x: x.astype(F32),
        init_zaya_params(jax.random.PRNGKey(seed % (2 ** 31)), CFG)), seed)
    want_h, want_r, want_routing = ref_hidden(p, ids, mask, CFG)
    with jax.default_matmul_precision("highest"):
        got_h, got_r, _ = zaya_encode(p, ids, mask, CFG)
        got_logits, _ = zaya_logits(p, ids, mask, CFG)
        got_routing = _program_routing(p, ids, mask, CFG)
    flat = mask.reshape(-1)
    for got, want in zip(got_routing, want_routing):
        np.testing.assert_array_equal(np.asarray(got)[flat],
                                      np.asarray(want)[flat])
    np.testing.assert_allclose(np.asarray(got_h)[mask],
                               np.asarray(want_h)[mask], atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(got_r)[flat],
                               np.asarray(want_r)[flat], atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_logits, ref_logits(p, ids, mask, CFG),
                               atol=2e-5, rtol=0)


def test_bfloat16_program_is_near_the_reference_given_its_routing(params,
                                                                  text):
    """As deployed (bfloat16 weights and operands): with the reference
    handed the program's own routing only rounding remains."""
    ids, mask = text
    routing = _program_routing(params, ids, mask, CFG)
    got, _ = zaya_logits(params, ids, mask, CFG)
    want = ref_logits(params, ids, mask, CFG, routing)
    assert 0.0 < float(jnp.abs(got - want).max()) < 2e-2


def test_predict_is_the_softmax_of_the_logits(params, text):
    ids, mask = text
    logits, peaks = zaya_logits(params, ids, mask, CFG)
    p, stats = zaya_predict(params, ids, mask, CFG, with_stats=True)
    np.testing.assert_allclose(p, jax.nn.softmax(logits, -1)[:, 1], atol=1e-7)
    np.testing.assert_array_equal(stats, peaks)
    assert peaks.shape == (3, CFG.num_hidden_layers)
    assert peaks.dtype == jnp.int32
    # one expert a token: the held pairs are the real tokens of each layer
    assert (np.asarray(peaks)[1] == np.asarray(mask).sum()).all()
    np.testing.assert_array_equal(zaya_predict(params, ids, mask, CFG), p)


# ------------------------------------------------ the pieces, one by one
def _latents(seed=0, b=3, t=9):
    rng = np.random.default_rng(seed)
    width = CFG.latent_heads * CFG.head_dim
    return jnp.asarray(rng.normal(0, 1, (b, t, width)), F32)


def _heads_first(x, heads):
    """``[B, T, heads * D]`` -> ``[heads, B, T, D]``, the mixing's layout."""
    b, t, width = x.shape
    return x.reshape(b, t, heads, width // heads).transpose(2, 0, 1, 3)


def _convolve(layer, c, cfg):
    """``cca_convolve`` on ``[B, T, heads * D]``, in and out."""
    b, t, width = c.shape
    out = zaya.cca_convolve(layer, _heads_first(c, cfg.latent_heads), cfg)
    return out.transpose(1, 2, 0, 3).reshape(b, t, width)


def test_both_convolutions_against_loops(params32):
    layer, c = params32["layers"][0], _latents()
    with jax.default_matmul_precision("highest"):
        want = _ref_conv_grouped(
            _ref_conv_depthwise(c, layer["conv_depthwise"]),
            layer["conv_grouped"], CFG.latent_heads, CFG.head_dim)
        got = _convolve(layer, c, CFG)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("times", [(1, 1), (3, 1), (2, 3)])
def test_convolutions_of_other_kernel_sizes(times):
    cfg = dataclasses.replace(CFG, cca_time0=times[0], cca_time1=times[1])
    layer = jax.tree_util.tree_map(
        lambda x: x.astype(F32),
        init_zaya_params(jax.random.PRNGKey(2), cfg)["layers"][0])
    c = _latents(4)
    with jax.default_matmul_precision("highest"):
        want = _ref_conv_grouped(
            _ref_conv_depthwise(c, layer["conv_depthwise"]),
            layer["conv_grouped"], cfg.latent_heads, cfg.head_dim)
        got = _convolve(layer, c, cfg)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def test_convolutions_are_causal_and_rows_do_not_leak(params32):
    """Moving position 5 of row 1 moves nothing before it, reaches exactly
    cca_time0 + cca_time1 - 1 positions of that row, and no other row."""
    layer, c = params32["layers"][0], _latents(1)
    moved = c.at[1, 5].add(1.0)
    delta = np.abs(np.asarray(_convolve(layer, moved, CFG)
                              - _convolve(layer, c, CFG))).max(-1)
    assert (delta[[0, 2]] == 0).all()
    assert (delta[1, :5] == 0).all() and (delta[1, 8:] == 0).all()
    assert (delta[1, 5:8] > 0).all()


def test_shift_tokens_is_zero_before_position_zero():
    x = jnp.arange(2 * 4 * 3, dtype=F32).reshape(2, 4, 3) + 1.0
    y = np.asarray(zaya.shift_tokens(x))
    assert (y[:, 0] == 0).all()
    np.testing.assert_array_equal(y[:, 1:], np.asarray(x)[:, :-1])
    np.testing.assert_array_equal(zaya.shift_tokens(x, 0), x)
    assert (np.asarray(zaya.shift_tokens(x, 5)) == 0).all()
    # the sequence on another axis: [heads, B, T, D] as the mixing keeps it
    z = np.asarray(zaya.shift_tokens(x[None], 1, axis=2))[0]
    np.testing.assert_array_equal(z, y)


def test_value_shift_position_zero_sees_no_previous_token(params32, text):
    """The second key-value head is read from the PREVIOUS token: at
    position 0 it is zero, so with only one visible key (position 0 of a
    causal row) the context of the query heads it serves is zero."""
    ids, mask = text
    layer = params32["layers"][0]
    h = params32["embed_tokens"][jnp.asarray(ids)]
    cos, sin = olmoe.rope_tables(T, CFG.rotary_dim, CFG.rope_theta)
    # o_proj replaced by the identity-like read-out of the context
    eye = dict(layer, o_proj=jnp.eye(CFG.num_attention_heads * CFG.head_dim,
                                     CFG.hidden_size, dtype=F32))
    ctx = np.asarray(zaya.zaya_attention(eye, h, jnp.asarray(mask), CFG, cos,
                                         sin) - h)
    served_by_shifted = ctx[:, 0, 4 * CFG.head_dim:8 * CFG.head_dim]
    served_by_current = ctx[:, 0, :4 * CFG.head_dim]
    assert (served_by_shifted == 0).all()
    assert (np.abs(served_by_current).max(axis=-1) > 0).all()
    assert (np.abs(ctx[0, 1, 4 * CFG.head_dim:8 * CFG.head_dim]) > 0).any()


def test_partial_rotary_leaves_the_upper_half_untouched(params32):
    """With the convolutions, the temperature and the norm out of the way,
    the dims past ``rotary_dim`` of q and k are position-independent and
    the rotated ones are the explicit pair formula's."""
    layer = dict(params32["layers"][0])
    rng = np.random.default_rng(3)
    b, t, d, rot = 2, 7, CFG.head_dim, CFG.rotary_dim
    q_lat = jnp.asarray(rng.normal(0, 1, (b, t, 8 * d)), F32)
    k_lat = jnp.asarray(rng.normal(0, 1, (b, t, 2 * d)), F32)
    cos, sin = olmoe.rope_tables(t, rot, CFG.rope_theta)
    lat = _heads_first(jnp.concatenate([q_lat, k_lat], axis=-1), 10)
    q, k = zaya.cca_mix(layer, lat, cos, sin, CFG)
    flat_cos, flat_sin = np.ones_like(cos), np.zeros_like(sin)
    q0, k0 = zaya.cca_mix(layer, lat, flat_cos, flat_sin, CFG)
    assert rot == d // 2
    np.testing.assert_array_equal(np.asarray(q)[..., rot:],
                                  np.asarray(q0)[..., rot:])
    np.testing.assert_array_equal(np.asarray(k)[..., rot:],
                                  np.asarray(k0)[..., rot:])
    np.testing.assert_allclose(
        q, _ref_rope_partial(q0, CFG.rope_theta, rot), atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        k, _ref_rope_partial(k0, CFG.rope_theta, rot), atol=1e-5, rtol=0)
    assert float(jnp.abs(q - q0)[..., 1:, :rot].max()) > 1e-3
    # per head the norm is sqrt(D), times the key head's temperature
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), np.sqrt(d),
                               rtol=1e-4)
    np.testing.assert_allclose(
        jnp.linalg.norm(k, axis=-1) / np.sqrt(d),
        np.broadcast_to(np.asarray(layer["temperature"])[None, :, None],
                        (b, 2, t)), rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_grouped_query_attention_equals_repeated_heads(causal):
    rng = np.random.default_rng(7)
    b, t, d = 2, 6, 8
    q = jnp.asarray(rng.normal(0, 1, (b, 8, t, d)), F32)
    k = jnp.asarray(rng.normal(0, 1, (b, 2, t, d)), F32)
    v = jnp.asarray(rng.normal(0, 1, (b, 2, t, d)), F32)
    mask = jnp.asarray(np.arange(t)[None] < np.asarray([6, 4])[:, None])
    with jax.default_matmul_precision("highest"):
        got = attention_reference(q, k, v, mask, causal=causal)
        want = attention_reference(q, jnp.repeat(k, 4, axis=1),
                                   jnp.repeat(v, 4, axis=1), mask,
                                   causal=causal)
    assert got.shape == (b, 8, t, d)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    # query head 3 is served by key head 0, query head 4 by key head 1
    with jax.default_matmul_precision("highest"):
        alone = attention_reference(q[:, 4:5], k[:, 1:2], v[:, 1:2], mask,
                                    causal=causal)
    np.testing.assert_allclose(got[:, 4:5], alone, atol=1e-6, rtol=0)


def test_router_state_is_carried_and_the_bias_moves_the_choice_alone(
        params32):
    layer = params32["layers"][1]
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(0, 1, (11, CFG.hidden_size)), F32)
    previous = jnp.asarray(rng.normal(0, 1, (11, CFG.router_hidden_size)),
                           F32)
    with jax.default_matmul_precision("highest"):
        want_s, want_r = _ref_router(layer, x, previous, CFG)
        experts, weights, r = zaya.zaya_route(layer, x, previous, CFG)
        first_s, first_r = _ref_router(layer, x, None, CFG)
        _, _, r_first = zaya.zaya_route(layer, x, None, CFG)
    np.testing.assert_allclose(r, want_r, atol=1e-5, rtol=0)
    np.testing.assert_allclose(r_first, first_r, atol=1e-5, rtol=0)
    assert float(jnp.abs(want_r - first_r).max()) > 0.1
    assert experts.shape == weights.shape == (11, 1)
    np.testing.assert_array_equal(
        experts[:, 0], jnp.argmax(want_s + layer["router_bias"], axis=-1))
    # weighted by the probability, not by probability + bias, and not
    # renormalised to one
    np.testing.assert_allclose(
        weights[:, 0], want_s[jnp.arange(11), experts[:, 0]], atol=1e-6)
    assert float(weights.max()) < 1.0
    # a bias large enough sends every token to expert 2, at s[2]
    tilted = dict(layer, router_bias=jnp.zeros(CFG.num_experts).at[2].set(9.))
    experts, weights, _ = zaya.zaya_route(tilted, x, previous, CFG)
    assert (np.asarray(experts) == 2).all()
    np.testing.assert_allclose(weights[:, 0], want_s[:, 2], atol=1e-6)


# ------------------------------------------- only the real tokens are routed
@pytest.mark.parametrize("case", sorted(CAPACITIES))
def test_compacted_program_equals_every_slot_routed(params32, ragged, case):
    """At any capacity that holds them, the real positions' hidden states,
    the router state the layers carry and every row's answer are what the
    reference (every slot routed, state in slot order) gives."""
    ids, mask = ragged
    capacity = CAPACITIES[case]
    assert mask.sum() == 27 and mask.size == 60
    want_h, want_r, _ = ref_hidden(params32, ids, mask, CFG)
    with jax.default_matmul_precision("highest"):
        hidden, r, peaks = zaya_encode(params32, ids, mask, CFG,
                                       capacity=capacity)
        got = zaya_predict(params32, ids, mask, CFG, capacity=capacity)
        want = jax.nn.softmax(ref_logits(params32, ids, mask, CFG), -1)[:, 1]
    np.testing.assert_allclose(np.asarray(hidden)[mask],
                               np.asarray(want_h)[mask], atol=2e-5, rtol=0)
    # the state lives on the routed slots: the real ones first, in slot
    # order, under a capacity; slot order itself without one
    compact = capacity is not None and capacity < mask.size
    state = np.asarray(r)[:27] if compact else np.asarray(r)[mask.reshape(-1)]
    assert r.shape == (capacity if compact else mask.size,
                       CFG.router_hidden_size)
    np.testing.assert_allclose(state, np.asarray(want_r)[mask.reshape(-1)],
                               atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(hidden)).all()
    assert np.isfinite(np.asarray(r)).all()
    largest, pairs, _ = np.asarray(peaks)
    assert (largest <= 27).all() and (largest > 0).all()
    assert (pairs == 27).all()
    held = mask.any(axis=1)
    np.testing.assert_allclose(got[held], want[held], atol=2e-5, rtol=0)
    assert np.isfinite(np.asarray(got)).all()


def test_a_padding_slot_changes_no_real_tokens_answer(params, params32,
                                                      ragged):
    """Other ids in the padding, and more padding: the same hidden states
    at the real positions and the same answers, bit for bit where the
    shapes agree."""
    ids, mask = ragged
    hidden, r, _ = zaya_encode(params, ids, mask, CFG, capacity=32)
    p = zaya_predict(params, ids, mask, CFG, capacity=32)
    other = np.where(mask, ids, (ids + 7) % CFG.vocab_size)
    hidden2, r2, _ = zaya_encode(params, other, mask, CFG, capacity=32)
    np.testing.assert_array_equal(np.asarray(hidden)[mask],
                                  np.asarray(hidden2)[mask])
    np.testing.assert_array_equal(np.asarray(r)[:27], np.asarray(r2)[:27])
    np.testing.assert_array_equal(
        p[:4], zaya_predict(params, other, mask, CFG, capacity=32)[:4])
    # another shape is another program: float32 weights, so that no last
    # bit of a bfloat16 operand turns into a routing flip
    wider_ids = np.pad(ids, ((0, 0), (0, 4)), constant_values=3)
    wider_mask = np.pad(mask, ((0, 0), (0, 4)))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            zaya_predict(params32, wider_ids, wider_mask, CFG)[:4],
            zaya_predict(params32, ids, mask, CFG, capacity=32)[:4],
            atol=1e-5, rtol=0)


def test_causality_a_later_token_moves_no_earlier_position(params, text):
    ids, mask = text
    hidden, _, _ = zaya_encode(params, ids, mask, CFG)
    moved = ids.copy()
    moved[0, 9] = (moved[0, 9] + 1) % CFG.vocab_size
    hidden2, _, _ = zaya_encode(params, moved, mask, CFG)
    np.testing.assert_array_equal(np.asarray(hidden)[0, :9],
                                  np.asarray(hidden2)[0, :9])
    assert float(jnp.abs(hidden[0, 9:] - hidden2[0, 9:]).max()) > 1e-3
    np.testing.assert_array_equal(np.asarray(hidden)[1:],
                                  np.asarray(hidden2)[1:])


def test_the_encoder_is_the_same_through_the_kernel():
    """Whole tiles through the grouped kernels in interpret mode: top-1 rows
    (128 slots = one row tile) against the XLA form."""
    cfg = dataclasses.replace(CFG, num_hidden_layers=2)
    p = init_zaya_params(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    mask = np.arange(32)[None] < rng.integers(1, 33, 8)[:, None]
    want = zaya_predict(p, ids, mask, cfg, capacity=128)
    got = zaya_predict(p, ids, mask, cfg, capacity=128, use_pallas=True,
                       kernel_interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=0)


@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
def test_apply_experts_is_the_same_through_the_kernels(
        experts_through_both_forms, rung):
    """One expert a token, four groups of 128-wide experts, at both
    capacities of a launch of 4,096 slots: the fused gate + up + SiLU kernel,
    down's and the combine (interpreted) against the XLA form."""
    layer = init_zaya_params(jax.random.PRNGKey(4), CFG)["layers"][1]
    assert layer["gate_proj"].shape == (4, 128, 128)
    sizes = experts_through_both_forms(
        layer, top_k=CFG.num_experts_per_tok, rung=rung, atol=1e-2)
    assert sizes.sum() == 2800 and sizes.min() > 0


# ------------------------------------------------------ the configuration
def test_published_config_is_the_default():
    c = ZayaConfig()
    assert (c.hidden_size, c.num_hidden_layers, c.num_attention_heads,
            c.num_key_value_heads, c.head_dim) == (2048, 40, 8, 2, 128)
    assert (c.cca_time0, c.cca_time1, c.partial_rotary_factor,
            c.rope_theta) == (2, 2, 0.5, 5000000.0)
    assert (c.num_experts, c.num_experts_per_tok, c.moe_intermediate_size,
            c.router_hidden_size) == (16, 1, 2048, 256)
    assert (c.vocab_size, c.rms_norm_eps) == (262272, 1e-5)
    assert c.rotary_dim == 64 and c.latent_heads == 10
    assert c.intermediate_size == c.moe_intermediate_size


@pytest.mark.parametrize("change,message", [
    ({"num_attention_heads": 7}, "divide"),
    ({"num_key_value_heads": 1, "num_attention_heads": 4}, "value shift"),
    ({"partial_rotary_factor": 0.0}, "partial_rotary_factor"),
])
def test_config_refuses_what_the_equations_cannot_hold(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_olmoe_still_refuses_grouped_query_attention():
    """The core takes grouped keys now; OLMoE's block still has none."""
    with pytest.raises(ValueError, match="grouped-query"):
        olmoe.OlmoeConfig(num_key_value_heads=4)


# ------------------------------------------------- the seam into the scorer
def test_one_description_of_a_routed_encoder_serves_both():
    from realtime_fraud_detection_tpu.models import zaya
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.scoring import pipeline

    assert pipeline.text_encoder(TINY_CONFIG).capacities(4096) is None
    assert pipeline.text_layers(TINY_CONFIG) == TINY_CONFIG.num_layers
    for cfg, module in ((olmoe.TINY_OLMOE, olmoe), (CFG, zaya)):
        routed = pipeline.text_encoder(cfg)
        assert routed is module.TEXT_ENCODER
        assert routed.capacities(4096) == (3072, 4096)
        assert pipeline.text_layers(cfg) == cfg.num_hidden_layers
        # what the routed rows' contract says the seam may read
        # (models/text_encoder.py)
        for name in ("num_experts", "num_experts_per_tok",
                     "num_hidden_layers", "hidden_size", "intermediate_size"):
            assert isinstance(getattr(cfg, name), int), name


def _one_device_mesh():
    return build_mesh(devices=jax.devices()[:1])


# top-1 of 16, the published routing, at TINY widths
CFG16 = dataclasses.replace(CFG, num_experts=16)


def _scorer(cfg=CFG16, text_len=32, **kw):
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig

    kw.setdefault("mesh", _one_device_mesh())
    return FraudScorer(bert_config=cfg,
                       scorer_config=ScorerConfig(text_len=text_len), **kw)


@pytest.mark.parametrize("text_len,compacts", [(32, False), (128, True)])
def test_through_scorer_and_job_counters_are_right_for_top_1_of_16(
        text_len, compacts):
    """One prediction a transaction, and the routed encoder's counters in
    the terms of ITS configuration: nothing assumes 8 of 64."""
    from realtime_fraud_detection_tpu.scoring import text_split
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )

    scorer = _scorer(text_len=text_len)
    broker = InMemoryBroker()
    cfg = JobConfig(max_batch=32)
    job = StreamJob(broker, scorer, cfg)
    # two full buckets of 32: a smaller last batch would take a bucket
    # under the smallest launch that gets a narrow rung
    recs = TransactionGenerator(num_users=64,
                                num_merchants=16).generate_batch(64)
    broker.produce_batch_keyed(
        cfg.transactions_topic, [(r["user_id"], r) for r in recs])
    job.run_until_drained()
    job.close()
    out = [r.value for r in broker.consumer(
        [cfg.predictions_topic], "check").poll(100_000)]
    assert sorted(o["transaction_id"] for o in out) == sorted(
        r["transaction_id"] for r in recs)
    for o in out:
        assert np.isfinite(o["fraud_probability"])
        assert 0.0 < o["model_predictions"]["bert_text"] < 1.0
        assert o["risk_level"] != "ERROR"
    c = job.counters
    layers = CFG16.num_hidden_layers
    assert c["errors"] == 0 and c["scored"] == 64
    # one expert a token: the pairs are the real tokens, a layer
    assert c["expert_rows"] == c["real_tokens"] * layers > 0
    # the largest group x 16 experts, summed over layers and batches
    assert c["expert_peak_rows"] % 16 == 0
    assert c["expert_rows"] <= c["expert_peak_rows"] \
        <= 16 * c["expert_rows"]
    assert c["expert_rows"] <= c["expert_token_slots"] * layers \
        <= c["token_slots"] * layers
    if compacts:
        # 32 x 128 = 4,096 slots: the narrow rung exists and these short
        # texts fit it
        rungs = text_split.capacities(32 * 128)
        assert len(rungs) == 2
        assert c["compact_batches"] == c["batches"] > 0
        assert c["expert_token_slots"] < c["token_slots"]
        assert scorer.host_stats()["text_split"]["compact_batches"] \
            == c["compact_batches"]
    else:
        assert c["compact_batches"] == 0
        assert c["expert_token_slots"] == c["token_slots"]
    assert scorer.kernel_snapshot()["fallback"]["attention"] >= c["batches"]


def test_the_scorers_answer_is_the_encoders(params):
    """The text column of the served packed path is ``zaya_predict`` on the
    batch the scorer assembled."""
    from realtime_fraud_detection_tpu.scoring.pipeline import MODEL_NAMES
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    scorer = _scorer(cfg=CFG, text_len=32)
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    batch = scorer.assemble(recs)
    results = scorer.finalize(scorer.dispatch(recs))
    want = zaya_predict(scorer.models.bert, jnp.asarray(batch.token_ids),
                        jnp.asarray(batch.token_mask), CFG)
    got = [r["model_predictions"]["bert_text"] for r in results]
    assert MODEL_NAMES[2] == "bert_text"
    np.testing.assert_allclose(got, np.asarray(want)[:5], atol=1e-4, rtol=0)


def _config(**planes):
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    for name, value in planes.items():
        setattr(config, name, value)
    return config


def _refusals():
    from realtime_fraud_detection_tpu.utils.config import (
        KernelSettings,
        QuantSettings,
    )

    def quant():
        _scorer(config=_config(quant=QuantSettings(enabled=True,
                                                   bert_weights="int8")))

    def dequant():
        _scorer(config=_config(kernels=KernelSettings(
            enabled=True, dequant_matmul="pallas")))

    def sharded_mesh():
        _scorer(mesh=build_mesh())             # the suite's 8 virtual devices

    def device_pool():
        from realtime_fraud_detection_tpu.scoring.device_pool import (
            DevicePool,
        )

        DevicePool(_scorer(), devices=jax.devices()[:2])

    def pipeline_parallel():
        from realtime_fraud_detection_tpu.parallel.pipeline import (
            bert_pipeline_encode,
        )

        bert_pipeline_encode(None, {}, None, None, CFG)

    return [(quant, "QuantSettings"), (dequant, "dequant_matmul"),
            (sharded_mesh, "sharded mesh"), (device_pool, "DevicePool"),
            (pipeline_parallel, "parallel/pipeline")]


@pytest.mark.parametrize("attempt,named", _refusals(),
                         ids=[n for _, n in _refusals()])
def test_a_distilbert_only_plane_refuses_a_zaya_config_by_name(attempt,
                                                               named):
    with pytest.raises(ValueError, match=named) as err:
        attempt()
    assert "ZayaConfig" in str(err.value)


def test_the_traced_guards_refuse_too(params, text):
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.scoring.pipeline import text_predict

    ids, mask = text
    with pytest.raises(ValueError, match="ZayaConfig"):
        text_predict(params, ids, mask, CFG, dequant_kernel="pallas")
    with pytest.raises(ValueError, match="text_capacity"):
        text_predict({}, ids, mask, TINY_CONFIG, capacity=32)
