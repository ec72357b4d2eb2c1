"""The Laguna text encoder (models/laguna.py): the program against the
benchmark's plain NumPy reference (``benchmarks/configs/laguna_reference.py``,
which shares no line with it) at every capacity; a chip's share of the
experts against the uncut layer; the fused causal core in interpret mode
against ``attention_reference(window=...)``; the YaRN tables against
hand-computed entries; and the seam it enters the scorer through."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import laguna, olmoe
from realtime_fraud_detection_tpu.models.text_encoder import visible_pairs
from realtime_fraud_detection_tpu.models.laguna import (
    LAGUNA_ROPE_FULL,
    LAGUNA_ROPE_SLIDING,
    TINY_LAGUNA,
    LagunaConfig,
    init_laguna_params,
    laguna_encode,
    laguna_logits,
    laguna_predict,
    laguna_rope_tables,
    yarn_inv_freq,
)
from realtime_fraud_detection_tpu.ops import (
    attention_reference,
    merge_heads,
    rope_lane_tables,
    split_heads,
    windowed_attention,
    windowed_refusal,
)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from benchmarks.harness import spec  # noqa: E402

F32 = jnp.float32
# hidden 128; five unlike layers: full + dense, then sliding x 3, full;
# 4 / 6 query heads of 16 over 2 key-value heads; a window of 8; top-4 of a
# router 16 wide, experts 4-7 held here
CFG = TINY_LAGUNA
REFERENCE = spec.reference("laguna_reference")
T = 24
LENGTHS = (24, 11, 1, 17, 0)               # 53 real tokens of 120 slots
CAPACITIES = {"every_slot": None, "all_120": 120, "96": 96, "64": 64,
              "exactly_53": 53}


def reference_cfg(config: LagunaConfig) -> dict:
    """The keys ``laguna_reference.py`` reads, for a ``LagunaConfig``: what
    ``benchmarks/configs/laguna_builder.laguna_config`` does, backwards."""
    def rope(r):
        return dataclasses.asdict(r)

    return {
        "num_attention_heads_per_layer": list(
            config.num_attention_heads_per_layer),
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.head_dim, "layer_types": list(config.layer_types),
        "mlp_layer_types": list(config.mlp_layer_types),
        "rope_parameters": {laguna.FULL: rope(config.rope_full),
                            laguna.SLIDING: rope(config.rope_sliding)},
        "sliding_window": config.sliding_window,
        "rms_norm_eps": config.rms_norm_eps,
        "num_experts_per_tok": config.num_experts_per_tok,
        "norm_topk_prob": config.norm_topk_prob,
        "moe_routed_scaling_factor": config.moe_routed_scaling_factor,
        "num_experts": config.num_experts,
        "expert_share": {
            "chips": config.router_experts // config.num_experts,
            "index": config.expert_offset // config.num_experts},
    }


@pytest.fixture(scope="module")
def params():
    return init_laguna_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def params32(params):
    return jax.tree.map(lambda x: x.astype(F32), params)


@pytest.fixture(scope="module")
def text():
    ids = jax.random.randint(jax.random.PRNGKey(1), (len(LENGTHS), T), 0,
                             CFG.vocab_size)
    mask = jnp.arange(T)[None, :] < jnp.array(LENGTHS)[:, None]
    return ids, mask


def _f32(fn, *args, **kw):
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


# ------------------------------------------- program against the reference
@pytest.mark.parametrize("case", sorted(CAPACITIES))
def test_float32_program_matches_the_plain_reference_at_every_capacity(
        params, params32, text, case):
    ids, mask = text
    got = _f32(laguna_predict, params32, ids, mask, CFG,
               capacity=CAPACITIES[case])
    want = REFERENCE.text_branch(jax.device_get(params), np.asarray(ids),
                                 np.asarray(mask), reference_cfg(CFG))
    # the empty row reads a padding position: nothing it holds is an answer
    np.testing.assert_allclose(np.asarray(got)[:4], want[:4], atol=2e-6,
                               rtol=0)


@pytest.mark.parametrize("seed", [3, 3300000007])
@pytest.mark.parametrize("index", [0, 3])
def test_any_share_matches_the_reference_given_the_same_share(seed, index,
                                                              text):
    cfg = dataclasses.replace(CFG, expert_offset=4 * index)
    p = init_laguna_params(jax.random.PRNGKey(seed), cfg)
    p32 = jax.tree.map(lambda x: x.astype(F32), p)
    ids, mask = text
    got = _f32(laguna_predict, p32, ids, mask, cfg, capacity=64)
    want = REFERENCE.text_branch(jax.device_get(p), np.asarray(ids),
                                 np.asarray(mask), reference_cfg(cfg))
    np.testing.assert_allclose(np.asarray(got)[:4], want[:4], atol=2e-6,
                               rtol=0)


def test_bfloat16_program_is_near_the_reference(params, text):
    ids, mask = text
    got = laguna_predict(params, ids, mask, CFG, capacity=64)
    want = REFERENCE.text_branch(jax.device_get(params), np.asarray(ids),
                                 np.asarray(mask), reference_cfg(CFG))
    assert np.abs(np.asarray(got) - want)[:4].max() < 2e-3


def test_predict_is_the_softmax_of_the_logits_and_stats_count_held_pairs(
        params32, text):
    ids, mask = text
    logits, stats = laguna_logits(params32, ids, mask, CFG)
    p, stats2 = laguna_predict(params32, ids, mask, CFG, with_stats=True)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(jax.nn.softmax(logits, -1)[:, 1]),
        atol=1e-7)
    peaks, held, tile_rows = np.asarray(stats)
    assert stats.shape == (3, CFG.num_sparse_layers) == (3, 4)
    assert not tile_rows.any()             # the XLA form visits no tile
    np.testing.assert_array_equal(np.asarray(stats2), np.asarray(stats))
    pairs = sum(LENGTHS) * CFG.num_experts_per_tok
    assert (held > 0).all() and (held < pairs).all()
    assert (peaks <= held).all() and (peaks * CFG.num_experts >= held).all()


# ------------------------------------------------------ the share test
def _layer_parts(params32, layer_index, x, slots, config):
    layer = params32["layers"][layer_index]
    y, sizes, _ = olmoe.routed_block(
        layer, x, slots,
        lambda rows: laguna.laguna_route(layer, rows, config),
        router_width=config.router_experts,
        expert_offset=config.expert_offset)
    return y, sizes.group_sizes


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Guide section 4: what all the shares give, with what every chip
    computes alike (the shared expert) counted once, adds up to what the
    uncut reference gives for the whole layer."""
    whole = dataclasses.replace(CFG, num_experts=16, expert_offset=0)
    p = init_laguna_params(jax.random.PRNGKey(11), whole)
    p32 = jax.tree.map(lambda a: a.astype(F32), p)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, CFG.hidden_size), F32)
    real = jnp.arange(40) % 5 != 0
    slots = (None, real)
    layer = p32["layers"][2]
    shared = laguna.swiglu(x, layer["shared_gate"], layer["shared_up"],
                           layer["shared_down"])
    with jax.default_matmul_precision("highest"):
        uncut, sizes_whole = _layer_parts(p32, 2, x, slots, whole)
        parts, held = [], []
        for index in range(4):
            cut = dataclasses.replace(CFG, expert_offset=4 * index)
            mine = dict(layer)
            for name in ("gate_proj", "up_proj", "down_proj"):
                mine[name] = layer[name][4 * index:4 * index + 4]
            y, sizes = _layer_parts({"layers": [None, None, mine]}, 2, x,
                                    slots, cut)
            parts.append(y)
            held.append(sizes)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut),
                               atol=2e-6, rtol=0)
    # every pair lives on exactly one chip
    np.testing.assert_array_equal(np.concatenate(held), sizes_whole)
    assert int(sizes_whole.sum()) == int(real.sum()) * CFG.num_experts_per_tok
    # and against the uncut plain reference, the shared expert counted once
    cfg = reference_cfg(whole)
    want = REFERENCE._sparse(jax.device_get(p)["layers"][2],
                             np.asarray(x)[np.asarray(real)], cfg, None)
    got = (uncut + jnp.where(real[:, None], shared, 0.0))[np.asarray(real)]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)


def test_no_pair_of_a_held_expert_is_dropped_at_any_capacity(params32, text):
    """A routing that sends EVERY pair to held experts: every one enters a
    group, at every capacity (a capacity is a shape, never a limit)."""
    ids, mask = text
    pairs = sum(LENGTHS) * CFG.num_experts_per_tok
    for capacity in (None, 64, 53):
        held = _held_pairs_with_lifted_logits(params32, ids, mask, capacity)
        assert held == [pairs] * CFG.num_sparse_layers, capacity


def _held_pairs_with_lifted_logits(params, ids, mask, capacity):
    """``laguna_encode``'s held pairs with the held experts' router logits
    lifted far above the others (the router has no bias: lift its
    probabilities)."""
    real_route = laguna.router_probs

    def lifted(x, w):
        probs = real_route(x, w)
        lift = jnp.zeros((probs.shape[-1],)).at[
            CFG.expert_offset:CFG.expert_offset + CFG.num_experts].set(1.0)
        return probs + lift

    laguna.router_probs = lifted
    try:
        _, stats = laguna_encode(params, ids, mask, CFG, capacity=capacity)
    finally:
        laguna.router_probs = real_route
    return [int(n) for n in np.asarray(stats)[1]]


def test_an_encoder_that_holds_every_expert_traces_what_it_always_did():
    """``apply_experts`` told the router's width of a layer that holds all
    of it is the code OLMoE and ZAYA1 run: the same jaxpr."""
    cfg = olmoe.TINY_OLMOE
    layer = olmoe.init_olmoe_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    x = jnp.ones((16, cfg.hidden_size), F32)
    experts = jnp.zeros((16, 2), jnp.int32)
    weights = jnp.ones((16, 2), F32)
    real = jnp.arange(16) < 9

    def run(**kw):
        return str(jax.make_jaxpr(lambda *a: olmoe.apply_experts(
            *a, real=real, **kw))(layer, x, experts, weights))

    assert run() == run(router_width=cfg.num_experts, expert_offset=0)
    assert run() != run(router_width=2 * cfg.num_experts)


# --------------------------------------------------------- the routing
def test_norm_topk_prob_renormalises_over_the_chosen_and_scales():
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (9, 16)))
    experts, plain = olmoe.choose_experts(probs, 4)
    again, weights = olmoe.choose_experts(probs, 4, renormalise=True,
                                          scale=2.5)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(again))
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 2.5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(weights),
        2.5 * np.asarray(plain) / np.asarray(plain).sum(-1, keepdims=True),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(plain), np.sort(np.asarray(probs), -1)[:, :-5:-1])
    _, scaled = olmoe.choose_experts(probs, 4, scale=2.5)
    np.testing.assert_allclose(np.asarray(scaled), 2.5 * np.asarray(plain),
                               atol=1e-7)


def test_routing_spreads_over_the_router_at_unit_scale_embeddings(params32,
                                                                  text):
    """What ``assumed.weights`` says of the unit-scale embedding: a token's
    own vector decides its route, so the held share sits near the even
    quarter and no expert takes most of a layer."""
    ids = jax.random.randint(jax.random.PRNGKey(5), (4, 64), 0, CFG.vocab_size)
    mask = jnp.ones((4, 64), bool)
    _, stats = laguna_encode(params32, ids, mask, CFG)
    peaks, held, _ = np.asarray(stats)
    pairs = 4 * 64 * CFG.num_experts_per_tok
    assert (0.12 < held / pairs).all() and (held / pairs < 0.40).all()
    assert (peaks * CFG.num_experts < 2.5 * held).all()


# ------------------------------------------------------------ the tables
def test_yarn_inverse_frequencies_at_hand_computed_entries():
    """ISSUE 33's step 2 on 64 rotated dims: c(32) = 9.04 -> low 9, c(1) =
    17.49 -> high 18: dims 0-9 as they are, 18-31 stretched by 128, a ramp
    between."""
    inv = yarn_inv_freq(LAGUNA_ROPE_FULL, 64)
    f = 500000.0 ** (np.arange(32) * 2 / 64)
    assert inv.shape == (32,) and inv[0] == 1.0
    np.testing.assert_allclose(inv[:10], 1.0 / f[:10], rtol=1e-12)
    np.testing.assert_allclose(inv[18:], 1.0 / (128.0 * f[18:]), rtol=1e-12)
    np.testing.assert_allclose(
        inv[13], (5 / 9) / f[13] + (4 / 9) / (128 * f[13]), rtol=1e-12)
    np.testing.assert_allclose(inv[31], 1 / (128 * 500000.0 ** (62 / 64)),
                               rtol=1e-12)
    cos, sin = laguna_rope_tables(16, 128, LAGUNA_ROPE_FULL)
    assert cos.shape == sin.shape == (16, 64)        # half a head rotates
    np.testing.assert_allclose(cos[0], 1.4852030263919618, rtol=1e-6)
    np.testing.assert_allclose(sin[0], 0.0, atol=1e-7)
    np.testing.assert_allclose(cos[5, 3], 1.4852030263919618
                               * np.cos(5 * inv[3]), rtol=1e-6)
    np.testing.assert_allclose(cos[5, 35], cos[5, 3])   # rotate-half layout
    # the sliding kind: the default form on the whole head
    cos_s, _ = laguna_rope_tables(16, 128, LAGUNA_ROPE_SLIDING)
    want, _ = olmoe.rope_tables(16, 128, 10000.0)
    np.testing.assert_array_equal(cos_s, want)


def test_lane_tables_rotate_as_rotate_half_does():
    for rope in (LAGUNA_ROPE_FULL, LAGUNA_ROPE_SLIDING):
        cos, sin = laguna_rope_tables(8, 128, rope)
        c, up, down, shift = rope_lane_tables(cos, sin, 128)
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (8, 128)))
        got = (x * c + np.roll(x, shift, 1) * up
               + np.roll(x, -shift, 1) * down)
        want = laguna._rotate(jnp.asarray(x), cos, sin)
        np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)
        assert shift == cos.shape[-1] // 2


# ---------------------------------------------------------- the fused core
def _core_inputs(b, t, heads, kv, seed=0):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, t, heads * 128))
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, t, kv * 128))
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, t, kv * 128))
    gate = jax.nn.sigmoid(
        jax.random.normal(jax.random.fold_in(key, 4), (b, t, heads)))
    return q, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), gate


def _core_oracle(q, k, v, mask, heads, kv, window):
    return merge_heads(attention_reference(
        split_heads(q, heads).astype(F32), split_heads(k, kv).astype(F32),
        split_heads(v, kv).astype(F32), mask, causal=True, window=window))


@pytest.mark.parametrize("heads,kv", [(12, 2), (18, 2)], ids=["G6", "G9"])
@pytest.mark.parametrize("window", [128, 256, None])
def test_fused_core_matches_the_windowed_reference_on_ragged_rows(
        heads, kv, window):
    """T past the window, groups of 6 and of 9 query heads a key head, rows
    that end inside a block, on a block's edge, and an empty one."""
    t, lengths = 512, jnp.array([512, 300, 128, 0], jnp.int32)
    q, k, v, _ = _core_inputs(4, t, heads, kv)
    q = q.astype(jnp.bfloat16)
    got = windowed_attention(q, k, v, lengths, num_heads=heads,
                             num_kv_heads=kv, window=window, interpret=True)
    mask = jnp.arange(t)[None, :] < lengths[:, None]
    want = _core_oracle(q, k, v, mask, heads, kv, window)
    err = np.abs(np.asarray(got.astype(F32)) - np.asarray(want))
    # bfloat16 weights and a bfloat16 result, values of a few units
    assert err[np.asarray(mask)].max() < 0.03
    # from a row's first wholly padded block on: zeros
    got = np.asarray(got.astype(F32))
    assert (got[1, 384:] == 0).all() and (got[2, 128:] == 0).all()
    assert (got[3] == 0).all()


@pytest.mark.parametrize("rope", [LAGUNA_ROPE_FULL, LAGUNA_ROPE_SLIDING],
                         ids=["yarn_half", "default_whole"])
def test_fused_core_rotates_q_and_k_and_gates_the_context_in_the_kernel(
        rope):
    t, heads, kv, window = 384, 6, 1, 128
    lengths = jnp.array([384, 200, 0], jnp.int32)
    q, _, v, gate = _core_inputs(3, t, heads, kv, seed=3)
    k = jax.random.normal(jax.random.PRNGKey(9), (3, t, kv * 128))
    cos, sin = laguna_rope_tables(t, 128, rope)
    *tables, shift = rope_lane_tables(cos, sin, 128)
    got = windowed_attention(
        q, k, v, lengths, num_heads=heads, num_kv_heads=kv, window=window,
        rope=tuple(tables), rope_shift=shift, gate=gate, out_dtype=F32,
        interpret=True)

    def rotated(x, n):
        return laguna._rotate(x.reshape(3, t, n, 128), cos[:, None],
                              sin[:, None]).astype(jnp.bfloat16).reshape(
                                  3, t, -1)

    mask = jnp.arange(t)[None, :] < lengths[:, None]
    want = _core_oracle(rotated(q, heads), rotated(k, kv), v, mask, heads,
                        kv, window)
    want = (want.reshape(3, t, heads, 128) * gate[..., None]).reshape(
        3, t, -1)
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert err[np.asarray(mask)].max() < 0.02
    assert (np.asarray(got)[2] == 0).all()
    with pytest.raises(ValueError, match="come together"):
        windowed_attention(q, k, v, lengths, num_heads=heads,
                           num_kv_heads=kv, rope=tuple(tables))


@pytest.mark.parametrize("heads,kv", [(4, 4), (4, 2), (8, 1)],
                         ids=["G1", "G2", "G8"])
def test_fused_core_norms_q_and_k_over_all_heads_in_the_kernel(heads, kv):
    """The one-block form (OLMoE's QK-norm riding the kernel): q and k
    float32 as projected, RMS-normed over their WHOLE width with a weight,
    rotated and rounded in VMEM, against ``attention_reference`` on inputs
    normed by ``rms_norm`` and rotated in XLA. Rows that end inside the
    block, on its edge, at one token, and an empty one."""
    t, eps = 128, 1e-5
    lengths = jnp.array([128, 77, 1, 0, 128], jnp.int32)
    b = lengths.shape[0]
    q, _, v, _ = _core_inputs(b, t, heads, kv, seed=5)
    k = 3.0 * jax.random.normal(jax.random.PRNGKey(11), (b, t, kv * 128))
    qw = 1 + 0.2 * jax.random.normal(jax.random.PRNGKey(12), (heads * 128,))
    kw = 1 + 0.2 * jax.random.normal(jax.random.PRNGKey(13), (kv * 128,))
    cos, sin = olmoe.rope_tables(t, 128, 10000.0)
    *tables, shift = rope_lane_tables(cos, sin, 128)
    got = windowed_attention(
        q, k, v, lengths, num_heads=heads, num_kv_heads=kv,
        rope=tuple(tables), rope_shift=shift, norm=(qw, kw), norm_eps=eps,
        out_dtype=F32, interpret=True)

    def prepared(x, w, n):
        x = olmoe.apply_rope(split_heads(olmoe.rms_norm(x, w, eps), n),
                             cos, sin)
        return merge_heads(x).astype(jnp.bfloat16)

    mask = jnp.arange(t)[None, :] < lengths[:, None]
    want = _core_oracle(prepared(q, qw, heads), prepared(k, kw, kv), v, mask,
                        heads, kv, None)
    err = np.abs(np.asarray(got) - np.asarray(want))
    assert err[np.asarray(mask)].max() < 0.02
    assert (np.asarray(got)[3] == 0).all()
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("wrong,named", [
    (dict(norm_eps=None), "come together"),
    (dict(rope=None, rope_shift=None), "norms AND rotates"),
    (dict(gate=jnp.ones((2, 128, 2))), "no gate"),
    (dict(window=128), "with no window"),
])
def test_the_one_block_form_refuses_what_it_does_not_do(wrong, named):
    q = jnp.ones((2, 128, 256))
    *tables, shift = rope_lane_tables(*olmoe.rope_tables(128, 128, 1e4), 128)
    call = dict(num_heads=2, num_kv_heads=2, rope=tuple(tables),
                rope_shift=shift, norm=(jnp.ones(256), jnp.ones(256)),
                norm_eps=1e-5, interpret=True)
    with pytest.raises(ValueError, match=named):
        windowed_attention(q, q, q, jnp.array([128, 3]), **{**call, **wrong})
    with pytest.raises(ValueError, match="seq_len 256"):
        windowed_attention(jnp.ones((2, 256, 256)), jnp.ones((2, 256, 256)),
                           jnp.ones((2, 256, 256)), jnp.array([128, 3]),
                           **call)


def test_a_call_without_a_norm_traces_the_blocked_kernel_it_always_did():
    """Laguna's call (no ``norm``) lowers to the same jaxpr whether or not
    the new keywords are spelled, over the blocked grid ``(rows, key heads,
    query blocks)``; handed a norm, the same entry is the one-block form
    over ``(rows,)``."""
    q = jnp.ones((2, 128, 256))
    v = q.astype(jnp.bfloat16)
    lengths = jnp.array([128, 3])
    *tables, shift = rope_lane_tables(*olmoe.rope_tables(128, 128, 1e4), 128)

    def run(**kw):
        return str(jax.make_jaxpr(lambda *a: windowed_attention(
            *a, num_heads=2, num_kv_heads=2, rope=tuple(tables),
            rope_shift=shift, interpret=True, **kw))(q, q, v, lengths))

    assert run() == run(norm=None, norm_eps=None)
    assert "grid=(2, 2, 1)" in run()
    normed = run(norm=(jnp.ones(256), jnp.ones(256)), norm_eps=1e-5)
    assert "grid=(2,)" in normed and "grid=(2, 2, 1)" not in normed


def test_window_of_the_reference_is_the_hugging_face_convention():
    """Query i sees keys i - window < j <= i: ``window`` with its own."""
    t, window = 12, 4
    q = jnp.zeros((1, 1, t, 8))
    v = jnp.eye(t)[None, None]                     # weights read off
    w = attention_reference(q, jnp.zeros((1, 1, t, 8)), v, causal=True,
                            window=window)[0, 0]
    for i in range(t):
        seen = np.nonzero(np.asarray(w[i]) > 0)[0]
        assert list(seen) == list(range(max(0, i - window + 1), i + 1))
    with pytest.raises(ValueError, match="causal"):
        attention_reference(q, q, v, window=4)


@pytest.mark.parametrize("shape,named", [
    ((2048, 64, 72, 8, 512), "head_dim 64"),
    ((2048, 128, 70, 8, 512), "do not divide"),
    ((100, 128, 72, 8, None), "seq_len 100"),
    ((2048, 128, 72, 8, 500), "window 500"),
    ((256, 128, 16, 16, None, True), "seq_len 256"),
    ((128, 128, 16, 16, 128, True), "with no window"),
])
def test_the_core_refuses_a_shape_by_name(shape, named):
    assert named in windowed_refusal(*shape)
    assert windowed_refusal(2048, 128, 72, 8, 512) is None
    assert windowed_refusal(2048, 128, 48, 8, None) is None
    assert windowed_refusal(128, 128, 16, 16, None, True) is None


KERNEL_CFG = LagunaConfig(
    vocab_size=512, hidden_size=128, dense_intermediate_size=256,
    num_hidden_layers=3, layer_types=(laguna.FULL, laguna.SLIDING,
                                      laguna.SLIDING),
    mlp_layer_types=(laguna.DENSE, laguna.SPARSE, laguna.SPARSE),
    num_attention_heads_per_layer=(2, 3, 3), num_key_value_heads=1,
    head_dim=128, sliding_window=128, router_experts=8, num_experts=4,
    expert_offset=4, num_experts_per_tok=2, moe_intermediate_size=128,
    shared_expert_intermediate_size=128)


def test_the_encoder_is_the_same_through_the_kernels():
    """Heads of 128 and whole blocks: the fused core (and the grouped
    matmul) interpreted against the XLA forms, ragged rows, T past the
    window."""
    assert KERNEL_CFG.core_refusal(256) is None
    assert "head_dim 16" in CFG.core_refusal(256)
    p = init_laguna_params(jax.random.PRNGKey(2), KERNEL_CFG)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 256), 0, 512)
    mask = jnp.arange(256)[None, :] < jnp.array([256, 150])[:, None]
    xla, stats = laguna_logits(p, ids, mask, KERNEL_CFG)
    fused, stats_k = laguna_logits(p, ids, mask, KERNEL_CFG, use_pallas=True,
                                   kernel_interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(xla), atol=5e-3)
    np.testing.assert_array_equal(np.asarray(stats)[1], np.asarray(stats_k)[1])


@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
def test_apply_experts_is_the_same_through_the_kernels(
        experts_through_both_forms, rung):
    """A SHARE of the experts (four of the router's eight, numbered 4 on),
    at both capacities of a launch of 4,096 slots: the pairs of absent
    experts are keyed past the last group, so about half the launched rows
    enter none, the fused gate + up + SiLU kernel and down's (interpreted)
    never write them and the combine never fetches them; against the XLA
    form."""
    layer = init_laguna_params(jax.random.PRNGKey(2), KERNEL_CFG)["layers"][1]
    assert layer["gate_proj"].shape == (4, 128, 128)
    top_k = KERNEL_CFG.num_experts_per_tok
    sizes = experts_through_both_forms(
        layer, top_k=top_k, rung=rung, atol=1e-2,
        router_width=KERNEL_CFG.router_experts,
        expert_offset=KERNEL_CFG.expert_offset)
    # the held half of the real tokens' pairs, give or take
    assert 0.4 < sizes.sum() / (2800 * top_k) < 0.6 and sizes.min() > 0


# ------------------------------------------------------ what must not move
def test_a_padding_slot_changes_no_real_tokens_answer(params32, text):
    ids, mask = text
    other = jnp.where(mask, ids, (ids + 17) % CFG.vocab_size)
    a = _f32(laguna_encode, params32, ids, mask, CFG, capacity=64)[0]
    b = _f32(laguna_encode, params32, other, mask, CFG, capacity=64)[0]
    m = np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(a)[m], np.asarray(b)[m])


def test_causality_a_later_token_moves_no_earlier_position(params32, text):
    ids, mask = text
    later = ids.at[0, 15:].set((ids[0, 15:] + 3) % CFG.vocab_size)
    a = _f32(laguna_encode, params32, ids, mask, CFG)[0]
    b = _f32(laguna_encode, params32, later, mask, CFG)[0]
    np.testing.assert_array_equal(np.asarray(a)[0, :15], np.asarray(b)[0, :15])
    assert np.abs(np.asarray(a)[0, 15:] - np.asarray(b)[0, 15:]).max() > 0


def test_the_window_binds_a_sliding_layer_sees_512_keys_not_all():
    """A token further back than every layer's reach moves nothing: with
    one full layer at the bottom only, position 0 reaches the last token
    through layer 0 alone; make layer 0 sliding too and it cannot."""
    cfg = dataclasses.replace(
        CFG, num_hidden_layers=2, layer_types=(laguna.SLIDING,) * 2,
        mlp_layer_types=(laguna.DENSE, laguna.SPARSE),
        num_attention_heads_per_layer=(4, 6), sliding_window=4)
    p = jax.tree.map(lambda a: a.astype(F32),
                     init_laguna_params(jax.random.PRNGKey(4), cfg))
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 16), 0, 1000)
    mask = jnp.ones((1, 16), bool)
    moved = ids.at[0, 0].set((ids[0, 0] + 1) % 1000)
    a = _f32(laguna_encode, p, ids, mask, cfg)[0]
    b = _f32(laguna_encode, p, moved, mask, cfg)[0]
    # two layers of reach 3 each: positions 0..6 can move, 7 on cannot
    np.testing.assert_array_equal(np.asarray(a)[0, 7:], np.asarray(b)[0, 7:])
    assert np.abs(np.asarray(a)[0, :7] - np.asarray(b)[0, :7]).max() > 0


# ------------------------------------------------------------- the config
def test_published_config_is_the_default():
    c = LagunaConfig()
    assert (c.hidden_size, c.num_hidden_layers, c.num_key_value_heads,
            c.head_dim, c.vocab_size) == (3072, 48, 8, 128, 100352)
    assert c.num_attention_heads_per_layer[:5] == (48, 72, 72, 72, 48)
    assert c.layer_types[:5] == (laguna.FULL,) + (laguna.SLIDING,) * 3 + (
        laguna.FULL,)
    assert c.mlp_layer_types[0] == laguna.DENSE
    assert set(c.mlp_layer_types[1:]) == {laguna.SPARSE}
    assert (c.router_experts, c.num_experts, c.num_experts_per_tok,
            c.moe_intermediate_size, c.shared_expert_intermediate_size,
            c.dense_intermediate_size) == (256, 256, 10, 1024, 1024, 12288)
    assert (c.norm_topk_prob, c.moe_routed_scaling_factor, c.sliding_window,
            c.rms_norm_eps) == (True, 2.5, 512, 1e-6)
    assert c.rope_full.rope_type == "yarn" and c.rope_full.factor == 128
    assert c.rope_of(0) is c.rope_full and c.rope_of(1) is c.rope_sliding
    assert c.window_of(0) is None and c.window_of(2) == 512
    # the routed-encoder seam's names (models/text_encoder.py)
    assert c.intermediate_size == 1024 and c.num_sparse_layers == 47
    assert c.core_refusal(2048) is None


@pytest.mark.parametrize("change,message", [
    ({"layer_types": (laguna.FULL,) * 4}, "layer_types holds 4"),
    ({"num_attention_heads_per_layer": (4, 6, 6, 6, 5)}, "divide"),
    ({"expert_offset": 14}, "experts 14..18"),
    ({"mlp_layer_types": ("moe",) * 5}, "mlp_layer_types"),
    ({"rope_full": dataclasses.replace(LAGUNA_ROPE_FULL,
                                       partial_rotary_factor=0.0)},
     "partial_rotary_factor"),
])
def test_config_refuses_what_the_equations_cannot_hold(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_olmoes_refusals_keep_their_wording_and_say_where_to_look():
    with pytest.raises(ValueError, match="grouped-query attention is not "
                                         "implemented.*ops/attention.py"):
        olmoe.OlmoeConfig(num_key_value_heads=4)
    with pytest.raises(ValueError, match="norm_topk_prob true is not the "
                                         "published model.*choose_experts"):
        olmoe.OlmoeConfig(norm_topk_prob=True)


def test_stored_dtypes_and_per_layer_shapes():
    shapes = jax.eval_shape(
        lambda k: init_laguna_params(k, LagunaConfig(
            num_hidden_layers=2, layer_types=(laguna.FULL, laguna.SLIDING),
            mlp_layer_types=(laguna.DENSE, laguna.SPARSE),
            num_attention_heads_per_layer=(48, 72), num_experts=64)),
        jax.random.PRNGKey(0))
    first, second = shapes["layers"]
    assert first["q_proj"].shape == (3072, 6144)
    assert second["q_proj"].shape == (3072, 9216)
    assert second["o_proj"].shape == (9216, 3072)
    assert first["g_proj"].shape == (3072, 48)
    assert second["k_proj"].shape == (3072, 1024)
    assert first["mlp_gate"].shape == (3072, 12288) and "router" not in first
    assert second["router"].shape == (3072, 256)          # published width
    assert second["gate_proj"].shape == (64, 3072, 1024)  # held here
    assert second["shared_down"].shape == (1024, 3072)
    assert second["gate_proj"].dtype == jnp.bfloat16
    assert shapes["embed_tokens"].shape == (100352, 3072)
    assert shapes["score"].dtype == F32


# ------------------------------------------------- the seam into the scorer
def test_one_description_of_a_routed_encoder_serves_all_three():
    from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA
    from realtime_fraud_detection_tpu.scoring import pipeline

    routed = pipeline.text_encoder(CFG)
    assert routed is laguna.TEXT_ENCODER
    assert routed.init is init_laguna_params
    assert pipeline.text_layers(CFG) == 5
    for cfg in (olmoe.TINY_OLMOE, TINY_ZAYA, CFG):
        for name in ("num_experts", "num_experts_per_tok",
                     "num_hidden_layers", "hidden_size", "intermediate_size",
                     "num_sparse_layers"):
            assert isinstance(getattr(cfg, name), int), name
    assert CFG.num_sparse_layers == 4 and CFG.intermediate_size == 64
    assert olmoe.TINY_OLMOE.num_sparse_layers == 2
    # the attention site's refusal comes from the row, for every encoder
    for cfg, refusal in ((olmoe.TINY_OLMOE, olmoe.TINY_OLMOE.core_refusal),
                         (TINY_ZAYA, TINY_ZAYA.mix_refusal),
                         (CFG, CFG.core_refusal)):
        site = pipeline.text_encoder(cfg).sites[0]
        assert site.name == "attention" and site.by_width
        for width in (32, 128, 256):
            assert site.refusal(cfg, width, width) == refusal(width)


def _scorer(cfg=CFG, text_len=32, **kw):
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig

    kw.setdefault("mesh", build_mesh(devices=jax.devices()[:1]))
    return FraudScorer(bert_config=cfg,
                       scorer_config=ScorerConfig(text_len=text_len), **kw)


def test_through_scorer_and_job_the_counters_follow_the_share():
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )

    scorer = _scorer(text_len=128)
    broker = InMemoryBroker()
    cfg = JobConfig(max_batch=32)
    job = StreamJob(broker, scorer, cfg)
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
        assert 0.0 < o["model_predictions"]["bert_text"] < 1.0
        assert o["risk_level"] != "ERROR"
    c = job.counters
    assert c["errors"] == 0 and c["scored"] == 64
    # the routers chose top-4 in four sparse layers for every real token
    assert c["routed_pairs"] == c["real_tokens"] * 4 * 4 > 0
    # the held share came from the device, and is a share
    assert 0 < c["expert_rows"] < c["routed_pairs"]
    assert c["expert_peak_rows"] % CFG.num_experts == 0
    assert c["expert_rows"] <= c["expert_peak_rows"]
    assert c["compact_batches"] == c["batches"] > 0
    # (query, key) pairs of the real queries: every token sees itself, none
    # more than its row's window
    assert c["real_tokens"] <= c["attn_visible_pairs_sliding"] \
        <= c["attn_visible_pairs_full"]
    assert c["attn_visible_pairs_sliding"] \
        <= c["real_tokens"] * CFG.sliding_window
    # the rule itself, against a loop over positions
    lengths = np.array([0, 1, 7, 8, 9, 31, 128])
    full = sum(n * (n + 1) // 2 for n in lengths)
    sliding = sum(sum(min(i + 1, CFG.sliding_window) for i in range(n))
                  for n in lengths)
    assert visible_pairs(CFG, lengths) == (full, sliding)
    assert "head_dim 16" in scorer.kernel_snapshot()["refused"]["attention"]


def test_the_other_routed_encoders_count_every_routed_pair_as_entered():
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    scorer = _scorer(cfg=olmoe.TINY_OLMOE)
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    pending = scorer.dispatch(recs)
    scorer.finalize(pending)
    c = pending.counters
    assert c["routed_pairs"] == c["expert_rows"] == c["real_tokens"] * 2 * 2
    # causal, no window: the full count, nothing under a window
    assert c["attn_visible_pairs_full"] > 0
    assert c["attn_visible_pairs_sliding"] == 0


def test_the_scorers_answer_is_the_encoders(params):
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    scorer = _scorer(text_len=32)
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    batch = scorer.assemble(recs)
    results = scorer.finalize(scorer.dispatch(recs))
    want = laguna_predict(scorer.models.bert, jnp.asarray(batch.token_ids),
                          jnp.asarray(batch.token_mask), CFG)
    got = [r["model_predictions"]["bert_text"] for r in results]
    np.testing.assert_allclose(got, np.asarray(want)[:5], atol=1e-4, rtol=0)


def test_a_distilbert_only_plane_refuses_a_laguna_config_by_name():
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        QuantSettings,
    )

    config = Config()
    config.quant = QuantSettings(enabled=True, bert_weights="int8")
    with pytest.raises(ValueError, match="LagunaConfig"):
        _scorer(config=config)
    with pytest.raises(ValueError, match="LagunaConfig"):
        _scorer(mesh=build_mesh(devices=jax.devices()[:2]))
