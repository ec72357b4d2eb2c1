"""The OLMoE text encoder (models/olmoe.py) against an independent plain
``jax.numpy`` float32 reference kept in this file, its two halves and its
kernel held separately, and the seam it enters the scorer through.

The reference shares no line with the program: it computes EVERY expert for
every token densely and masks (no sort, no groups), RoPE by the explicit
pair formula, and runs under ``jax.default_matmul_precision("highest")``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import olmoe
from realtime_fraud_detection_tpu.models.olmoe import (
    TINY_OLMOE,
    OlmoeConfig,
    apply_experts,
    init_olmoe_params,
    olmoe_encode,
    olmoe_logits,
    olmoe_predict,
    route,
    router_probs,
)
from realtime_fraud_detection_tpu.ops import (
    attention_reference,
    combine_supported,
    grouped_gated_matmul,
    grouped_matmul,
    grouped_matmul_supported,
)
from realtime_fraud_detection_tpu.scoring.text_split import capacities
from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    LANES,
    ROW_TILES,
    VMEM_CEILING,
    down_gmm,
    down_vmem_bytes,
    down_widths,
    gated_gmm,
    gated_tile_rows,
    gated_vmem_bytes,
    gmm_tiling,
    grouped_matmul_reference,
)

F32 = jnp.float32
# hidden 128, 2 layers, 2 heads of 64, 8 experts of width 64, 2 per token
CFG = TINY_OLMOE
B, T = 4, 16
LENGTHS = (16, 5, 1, 9)


# ----------------------------------------------------------- the reference
def _ref_rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _ref_rope(x, theta):
    """[B, heads, T, D]: pairs (i, i + D/2) rotated by pos * theta^(-2i/D)."""
    t, d = x.shape[-2:]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(t, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _ref_moe(layer, x, top_k, routing=None):
    """Dense over ALL experts, then masked: ``(y, probs, chosen mask)``."""
    p = jax.nn.softmax(x @ layer["router"].astype(F32), axis=-1)
    if routing is None:
        routing = jnp.argsort(-p, axis=-1)[:, :top_k]
    chosen = jnp.zeros_like(p).at[
        jnp.arange(p.shape[0])[:, None], routing].set(1.0)
    gate = jnp.einsum("nh,ehi->nei", x, layer["gate_proj"].astype(F32))
    up = jnp.einsum("nh,ehi->nei", x, layer["up_proj"].astype(F32))
    out = jnp.einsum("nei,eih->neh", gate * jax.nn.sigmoid(gate) * up,
                     layer["down_proj"].astype(F32))
    return jnp.sum((p * chosen)[:, :, None] * out, axis=1), p, chosen


def ref_hidden(params, ids, mask, cfg, routing=None):
    """Hidden states before the final norm and each layer's chosen-expert
    mask; ``routing`` (one ``[tokens, top_k]`` per layer) overrides top-k."""
    with jax.default_matmul_precision("highest"):
        b, t = ids.shape
        heads, eps = cfg.num_attention_heads, cfg.rms_norm_eps
        h = params["embed_tokens"].astype(F32)[ids]
        d = h.shape[-1] // heads
        see = jnp.tril(jnp.ones((t, t), bool))[None, None] \
            & jnp.asarray(mask)[:, None, None, :]
        masks = []
        for i, layer in enumerate(params["layers"]):
            def split(x):
                return x.reshape(b, t, heads, d).transpose(0, 2, 1, 3)

            x = _ref_rms(h, layer["input_layernorm"], eps)
            q = _ref_rms(x @ layer["q_proj"].astype(F32), layer["q_norm"], eps)
            k = _ref_rms(x @ layer["k_proj"].astype(F32), layer["k_norm"], eps)
            v = split(x @ layer["v_proj"].astype(F32))
            q = _ref_rope(split(q), cfg.rope_theta)
            k = _ref_rope(split(k), cfg.rope_theta)
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
            w = jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1)
            ctx = jnp.einsum("bhqk,bhkd->bhqd", w, v)
            ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, -1)
            h = h + ctx @ layer["o_proj"].astype(F32)
            x = _ref_rms(h, layer["post_attention_layernorm"], eps)
            y, _, chosen = _ref_moe(
                layer, x.reshape(b * t, -1), cfg.num_experts_per_tok,
                None if routing is None else routing[i])
            masks.append(chosen)
            h = h + y.reshape(b, t, -1)
        return h, masks


def ref_logits(params, ids, mask, cfg, routing=None):
    with jax.default_matmul_precision("highest"):
        h, _ = ref_hidden(params, ids, mask, cfg, routing)
        last = jnp.maximum(jnp.asarray(mask).sum(-1) - 1, 0)
        pooled = _ref_rms(h[jnp.arange(h.shape[0]), last], params["norm"],
                          cfg.rms_norm_eps)
        return pooled @ params["score"]


# ---------------------------------------------------------------- fixtures
@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: init_olmoe_params(k, CFG))(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def params32(params):
    """The same values, stored float32: the program's matmul operands take
    the stored dtype, so this is the program in float32."""
    return jax.tree.map(lambda a: a.astype(F32), params)


@pytest.fixture(scope="module")
def text():
    rng = np.random.default_rng(5)
    ids = rng.integers(0, CFG.vocab_size, (B, T)).astype(np.int32)
    mask = np.arange(T)[None, :] < np.asarray(LENGTHS)[:, None]
    return ids, mask


def _every_slot_routed(params, ids, mask, cfg, **site):
    """The encoder as it was before padding left the routed block: every
    (row, position) slot through the router and its experts, from the public
    pieces. ``(hidden before the final norm, each layer's top-k)``.
    ``site``: ``olmoe_attention``'s keywords (the fused core)."""
    cos, sin = olmoe.rope_tables(ids.shape[1], cfg.head_dim, cfg.rope_theta)
    h = params["embed_tokens"][ids].astype(F32)
    chosen = []
    for layer in params["layers"]:
        h = olmoe.olmoe_attention(layer, h, mask, cfg, cos, sin, **site)
        x = olmoe.rms_norm(h, layer["post_attention_layernorm"],
                           cfg.rms_norm_eps).reshape(-1, h.shape[-1])
        experts, weights = route(x, layer["router"], cfg.num_experts_per_tok)
        chosen.append(experts)
        y, _ = apply_experts(layer, x, experts, weights)
        h = h + y.reshape(h.shape)
    return h, chosen


def _program_routing(params, ids, mask, cfg):
    """Each layer's top-k as the PROGRAM chooses it on its own hidden
    stream (at a real position the stream is the same with and without the
    padding routed: test_only_the_real_tokens_are_routed)."""
    return _every_slot_routed(params, ids, mask, cfg)[1]


# ------------------------------------------------- the whole encoder, logits
def test_stored_dtypes_are_the_checkpoints(params):
    big = [a for a in jax.tree.leaves(params) if a.ndim >= 2]
    assert all(a.dtype == jnp.bfloat16 for a in big
               if a.shape != params["score"].shape)
    assert params["score"].dtype == F32
    assert params["layers"][0]["gate_proj"].shape == (
        CFG.num_experts, CFG.hidden_size, CFG.intermediate_size)
    assert params["layers"][0]["q_norm"].shape == (CFG.hidden_size,)


@pytest.mark.parametrize("stored,atol", [
    # float32 program against the float32 reference: only the order of the
    # sums differs (sorted groups against dense-and-mask), a few ulps of
    # logits of order 0.3 (measured 1.5e-7 at most)
    ("float32", 2e-5),
    # as deployed, bfloat16 operands with float32 accumulation: operands
    # rounded to 8 bits of mantissa (2^-9 relative each) through 2 layers of
    # 128- and 64-term sums move logits of order 0.3 by ~1e-3 (measured
    # here 1.3e-3 at most over the seeds below, the routing given). The same
    # reference with float8 operands is 2.3e-2 to 4.4e-2 away
    # (test_a_lower_precision_would_fail): the limit sits between
    ("bfloat16", 6e-3),
])
@pytest.mark.parametrize("seed", [3, 11, 2600000007])
def test_logits_match_the_plain_reference(stored, atol, seed, text):
    ids, mask = text
    p = jax.jit(lambda k: init_olmoe_params(k, CFG))(jax.random.PRNGKey(seed))
    if stored == "float32":
        p = jax.tree.map(lambda a: a.astype(F32), p)
    # top-k is a discrete choice: where rounding moves a logit across rank
    # k the two sides compute different (equally valid) sums. The reference
    # is given the program's routing, and the choice itself is held by
    # test_route_*; in float32 the two agree on every token anyway
    routing = _program_routing(p, ids, mask, CFG)
    got, peaks = olmoe_logits(p, ids, mask, CFG)
    want = ref_logits(p, ids, mask, CFG, routing)
    assert got.shape == (B, CFG.num_labels) and peaks.shape == (
        3, CFG.num_hidden_layers)
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    if stored == "float32":
        free = ref_logits(p, ids, mask, CFG)          # its own top-k
        np.testing.assert_allclose(got, free, atol=atol, rtol=0)


def test_a_lower_precision_would_fail(params, text):
    """The tolerance above is tight enough: the reference with float8
    (e4m3) weights and activations-at-the-embedding is several limits away."""
    import ml_dtypes

    ids, mask = text
    fp8 = jax.tree.map(
        lambda a: np.asarray(a, np.float32).astype(
            ml_dtypes.float8_e4m3fn).astype(np.float32) if a.ndim >= 2 else a,
        params)
    routing = _program_routing(params, ids, mask, CFG)
    gap = np.abs(np.asarray(ref_logits(fp8, ids, mask, CFG, routing))
                 - np.asarray(ref_logits(params, ids, mask, CFG, routing)))
    assert gap.max() > 2 * 6e-3, gap.max()


def test_predict_is_the_softmax_of_the_logits(params, text):
    ids, mask = text
    logits, peaks = olmoe_logits(params, ids, mask, CFG)
    p, stats = olmoe_predict(params, ids, mask, CFG, with_stats=True)
    np.testing.assert_allclose(p, jax.nn.softmax(logits, -1)[:, 1], atol=1e-7)
    np.testing.assert_array_equal(stats, peaks)
    # each layer's largest group, held pairs, visited rows (XLA form: none)
    largest, held, tile_rows = np.asarray(stats)
    assert (held == sum(LENGTHS) * CFG.num_experts_per_tok).all()
    assert (largest * CFG.num_experts >= held).all() and not tile_rows.any()
    assert olmoe_predict(params, ids, mask, CFG).shape == (B,)


# ------------------------------------------------------- the two halves
def test_route_probabilities_and_choice(params32):
    layer = params32["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (64, CFG.hidden_size), F32)
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(x @ layer["router"], axis=-1)
    np.testing.assert_allclose(router_probs(x, layer["router"]), want,
                               atol=1e-6, rtol=0)
    experts, weights = route(x, layer["router"], CFG.num_experts_per_tok)
    assert experts.dtype == jnp.int32 and experts.shape == (64, 2)
    top = np.argsort(-np.asarray(want), axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(top, -1))
    np.testing.assert_allclose(
        weights, np.take_along_axis(np.asarray(want), np.asarray(experts), -1),
        atol=1e-6)


def test_weights_are_not_renormalised(params32):
    """``norm_topk_prob`` false: the top-k weights are the softmax over ALL
    experts, so they sum to less than one."""
    layer = params32["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (32, CFG.hidden_size), F32)
    _, weights = route(x, layer["router"], CFG.num_experts_per_tok)
    total = np.asarray(weights).sum(-1)
    assert (total < 0.999).all() and (total > 2.0 / CFG.num_experts).all()


@pytest.mark.parametrize("stored,atol", [("float32", 1e-5),
                                         ("bfloat16", 2e-3)])
def test_apply_experts_given_the_references_routing(params, params32, stored,
                                                    atol):
    p = params32 if stored == "float32" else params
    layer = p["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(4), (96, CFG.hidden_size), F32)
    with jax.default_matmul_precision("highest"):
        want, probs, _ = _ref_moe(layer, x, CFG.num_experts_per_tok)
    experts = jnp.argsort(-probs, axis=-1)[:, :2].astype(jnp.int32)
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    got, (sizes, tile_rows) = apply_experts(layer, x, experts, weights)
    np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    assert int(tile_rows) == 0                 # the XLA form visits no tile
    assert int(sizes.sum()) == 96 * 2          # every pair computed: no drop
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(experts).ravel(),
                           minlength=CFG.num_experts))


# ---------------------------------------------------- the grouped matmul
def _loop_over_experts(lhs, rhs, sizes):
    out, start = [], 0
    for g, n in enumerate(sizes):
        out.append(np.asarray(lhs[start:start + n], np.float32)
                   @ np.asarray(rhs[g], np.float32))
        start += n
    return np.concatenate(out, axis=0)


GROUPS = {
    "even": [64, 64, 64, 64],
    "empty_groups": [0, 200, 0, 56],
    "one_holds_every_row": [0, 0, 256, 0],
    "off_the_tile": [1, 127, 3, 125],
    "first_and_last_empty": [0, 129, 127, 0],
}


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_grouped_matmul_against_a_loop_over_experts(case, form):
    sizes = GROUPS[case]
    m, k, n = sum(sizes), 128, 256
    key = jax.random.PRNGKey(len(case))
    lhs = jax.random.normal(key, (m, k), F32).astype(jnp.bfloat16)
    rhs = (jax.random.normal(jax.random.fold_in(key, 1), (len(sizes), k, n),
                             F32) * 0.1).astype(jnp.bfloat16)
    assert grouped_matmul_supported(m, k, n)
    got = grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                         use_pallas=form != "xla", interpret=True)
    # each result row as its own lane tiles: one contiguous piece in HBM
    assert got.dtype == F32 and got.shape == (m, n // LANES, LANES)
    # bf16 operands are exact in f32; only the order of 128 f32 adds differs
    np.testing.assert_allclose(got.reshape(m, n),
                               _loop_over_experts(lhs, rhs, sizes),
                               atol=1e-4, rtol=0)


# the SAME groups; the last case launches 64 rows more than the groups hold
GATED_GROUPS = dict(GROUPS, fewer_rows_than_launched=[100, 0, 37, 55])
LAUNCHED = 256


@pytest.mark.parametrize("stored,atol", [("float32", 1e-4),
                                         ("bfloat16", 2e-2)])
@pytest.mark.parametrize("form,k", [
    ("xla", 128), ("pallas_interpret", 128), ("pallas_three_k_steps", 384)],
    ids=["xla", "pallas_one_k_step", "pallas_three_k_steps"])
@pytest.mark.parametrize("case", sorted(GATED_GROUPS))
def test_grouped_gated_matmul_against_a_loop_over_experts(case, form, k,
                                                          stored, atol):
    """gate, up and SiLU ⊙ as one call: both forms, whole K in one step and
    accumulated over three, against ``silu(x @ gate_e) * (x @ up_e)`` expert
    by expert in NumPy; rounded once to the weights' dtype."""
    sizes = GATED_GROUPS[case]
    held, n = sum(sizes), 256
    dtype = jnp.dtype(stored)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    lhs = jax.random.normal(keys[0], (LAUNCHED, k), F32).astype(dtype)
    gate_w, up_w = (
        (jax.random.normal(key, (len(sizes), k, n), F32) * 0.1).astype(dtype)
        for key in keys[1:])
    assert grouped_matmul_supported(LAUNCHED, k, n)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    # the rule keeps K in one block; the kernel's accumulator passes are
    # what a K too long for the budget would run: held at a tile of its own
    assert gmm_tiling(LAUNCHED, k, n, len(sizes), gated=True)[1:] == (k, n)
    if form == "pallas_three_k_steps":
        got = gated_gmm(lhs, gate_w, up_w, group_sizes, out_dtype=dtype,
                        tiling=(128, 128, 256), interpret=True)
    else:
        got = grouped_gated_matmul(
            lhs, gate_w, up_w, group_sizes, out_dtype=dtype,
            use_pallas=form != "xla", interpret=True)
    assert got.dtype == dtype and got.shape == (LAUNCHED, n)
    gate = _loop_over_experts(lhs, gate_w, sizes)
    want = gate / (1.0 + np.exp(-gate)) * _loop_over_experts(lhs, up_w, sizes)
    # the rows past the last group are whatever the buffer held
    np.testing.assert_allclose(np.asarray(got, np.float32)[:held], want,
                               atol=atol, rtol=atol)


def test_the_kernel_declines_what_it_cannot_tile():
    assert not grouped_matmul_supported(100, 128, 128)     # rows off a tile
    assert not grouped_matmul_supported(256, 128, 64)      # N under a lane
    assert grouped_matmul_supported(262144, 2048, 1024)
    # an unsupported shape asked for the kernel runs the XLA form
    lhs = jnp.ones((100, 128), jnp.bfloat16)
    rhs = jnp.ones((2, 128, 64), jnp.bfloat16)
    sizes = jnp.asarray([40, 60], jnp.int32)
    out = grouped_matmul(lhs, rhs, sizes, use_pallas=True, interpret=True)
    assert out.shape == (100, 1, 64)   # under a lane tile: one piece a row
    np.testing.assert_allclose(out, 128.0)
    # ... and the gated call two of them and the product, by the same
    # predicate: silu(128) * 128
    act = grouped_gated_matmul(lhs, rhs, rhs, sizes, out_dtype=jnp.bfloat16,
                               use_pallas=True, interpret=True)
    assert act.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(act, np.float32), 128.0 * 128.0)


# ------------------------------------------------------ the tile rule
# (rows at the 3/4 rung, at every slot; held groups; hidden; one expert's
# width) of each routed cell's bucket, as tests/test_aot_tpu.py GATED_SITES
CELL_SITES = {"olmoe": (196608, 262144, 64, 2048, 1024),
              "zaya1": (24576, 32768, 16, 2048, 2048),
              "laguna": (122880, 163840, 64, 3072, 1024),
              "joyai": (98304, 131072, 256, 2048, 768)}
# what the rule gives them, (gated, down) at each rung: 256 rows where
# rows // groups reaches 2,048, else 128; down's N whole at all four since
# its call names its own budget (PR 48: ZAYA1's was 1,024 and Laguna's
# 1,536 inside megablox's 16 MB)
SHIPPED = {
    "olmoe": 2 * [((256, 2048, 1024), (256, 1024, 2048))],
    "zaya1": [((128, 2048, 2048), (128, 2048, 2048)),
              ((256, 2048, 2048), (256, 2048, 2048))],
    "laguna": [((128, 3072, 1024), (128, 1024, 3072)),
               ((256, 3072, 1024), (256, 1024, 3072))],
    "joyai": 2 * [((128, 2048, 768), (128, 768, 2048))],
}


def _site(encoder, rung, kernel):
    *rungs, groups, hidden, width = CELL_SITES[encoder]
    k, n = (hidden, width) if kernel == "gated" else (width, hidden)
    return rungs[rung], k, n, groups


def _legal(tiling, m, k, n, gated):
    """Every tile divides its side in whole lane tiles, the row tile is one
    of the rule's three, and the call fits what it may name."""
    tm, tk, tn = tiling
    assert tm in ROW_TILES and m % tm == 0
    assert k % tk == 0 and tk % LANES == 0 and n % tn == 0 and tn % LANES == 0
    room = gated_vmem_bytes if gated else down_vmem_bytes
    assert room(tm, tk, tn) <= VMEM_CEILING < 128 << 20
    # down's result block is (tm, tn / 128, 128): all of N or whole
    # sublane tiles of lane tiles
    assert gated or tn in down_widths(n)


@pytest.mark.parametrize("kernel", ["gated", "down"])
@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
@pytest.mark.parametrize("encoder", sorted(CELL_SITES))
def test_the_tile_rule_at_the_cells_shapes(encoder, rung, kernel):
    """What every routed cell's grouped calls run at: legal tiles inside
    the call's budget, K in ONE block at all sixteen (no accumulator pass:
    the property that makes any two tile choices bit-equal on the real
    rows), and the tiles the sweep timed fastest
    (``tools/grouped_alone_pr47.json``)."""
    m, k, n, groups = _site(encoder, rung, kernel)
    gated = kernel == "gated"
    tiling = gmm_tiling(m, k, n, groups, gated=gated)
    _legal(tiling, m, k, n, gated)
    assert tiling[1] == k
    assert tiling == SHIPPED[encoder][rung][not gated]
    # from the shapes alone: asked again, the same
    assert gmm_tiling(m, k, n, groups, gated=gated) == tiling


@pytest.mark.parametrize("kernel", ["gated", "down", "relu2"])
@pytest.mark.parametrize("encoder", sorted(CELL_SITES))
def test_which_way_round_each_call_takes_its_matrices(encoder, kernel):
    """The scaffold serves three kernels and one of them, the ungated
    ``relu2_gmm`` (PR 51), takes its matrices ``[G, N, K]``. The gated
    call's and down's traced calls are what they were: each right-hand
    block the group's ``[tk, tn]`` of a ``[G, K, N]`` operand as the
    parameters hold them (their N are whole lane tiles, which the TPU keeps
    row-major), the product contracting the rows' last side with the
    block's FIRST; the ungated call's block is ``[tn, tk]`` and its product
    contracts both last sides. (Their jaxprs hashed equal to the parent's
    at these shapes: PERF.md section 6, PR 51.)"""
    from realtime_fraud_detection_tpu.ops.grouped_matmul import relu2_gmm

    m, k, n, groups = _site(encoder, 0, "down" if kernel == "down"
                            else "gated")
    gated = kernel != "down"
    matrices = {"gated": 2, "down": 1, "relu2": 1}[kernel]
    tm, tk, tn = tiling = gmm_tiling(m, k, n, groups, gated=gated,
                                     matrices=matrices)
    sds = jax.ShapeDtypeStruct
    rows, sizes = sds((m, k), jnp.bfloat16), sds((groups,), jnp.int32)
    first = dict(out_dtype=jnp.dtype(jnp.bfloat16), tiling=tiling)
    if kernel == "relu2":
        text = str(jax.make_jaxpr(
            lambda x, w, s: relu2_gmm(x, w, s, **first))(
            rows, sds((groups, n, k), jnp.bfloat16), sizes))
        block, contracts = (tn, tk), "(([1], [1]), ([], []))"
    else:
        w = sds((groups, k, n), jnp.bfloat16)
        text = str(jax.make_jaxpr(
            (lambda x, w, s: gated_gmm(x, w, w, s, **first)) if gated else
            (lambda x, w, s: down_gmm(x, w, s, tiling=tiling)))(
            rows, w, sizes))
        block, contracts = (tk, tn), "(([1], [0]), ([], []))"
    right = ("BlockMapping(block_shape=(Squeezed(), Blocked(block_size=%d), "
             "Blocked(block_size=%d)))" % block)
    assert text.count(right) == matrices, text[:2000]
    assert text.count("Squeezed()") == matrices
    assert text.count("dimension_numbers=" + contracts) == matrices
    assert text.count("dot_general[") == matrices


# the programs a deployment launches below its cell's bucket: (positions,
# experts a token, row buckets under the cell's own) — core/batching.
# BATCH_BUCKETS x text_split.capacities
BELOW_THE_CELL = {"olmoe": (128, 8, (1, 8, 32, 128)),
                  "zaya1": (128, 1, (1, 8, 32, 128)),
                  "laguna": (2048, 10, (1,)), "joyai": (2048, 8, (1,))}
SMALL_LAUNCHES = [
    (encoder, rung * top_k)
    for encoder, (positions, top_k, buckets) in sorted(BELOW_THE_CELL.items())
    for bucket in buckets for rung in capacities(bucket * positions)]


@pytest.mark.parametrize("kernel", ["gated", "down"])
@pytest.mark.parametrize("encoder,m", SMALL_LAUNCHES)
def test_the_tile_rule_at_the_small_buckets(encoder, m, kernel):
    """Buckets 1, 8, 32 and 128 of the 128-position encoders (the last two
    at both rungs) and bucket 1 of the 2,048-position ones: tens to
    hundreds of rows a group, so the row tile narrows; still legal, still K
    in one block, and never a wider row tile than the cell's own."""
    *_, groups, hidden, width = CELL_SITES[encoder]
    k, n = (hidden, width) if kernel == "gated" else (width, hidden)
    assert grouped_matmul_supported(m, k, n)
    tiling = gmm_tiling(m, k, n, groups, gated=kernel == "gated")
    _legal(tiling, m, k, n, kernel == "gated")
    assert tiling[1] == k
    assert tiling[0] <= SHIPPED[encoder][1][kernel == "down"][0]
    assert tiling[0] == (256 if m // groups >= 2048 else 128)


@pytest.mark.parametrize("m,k,n,groups", [
    (128, 128, 128, 1), (128, 128, 128, 64), (384, 128, 384, 3),
    (640, 256, 128, 2), (1024, 256, 384, 130), (4096, 8192, 8192, 4),
    (4096, 65536, 1024, 4)],
    ids=["one_tile", "more_groups_than_rows_in_a_tile", "three_lane_tiles",
         "five_row_tiles", "seven_rows_a_group", "wide_both_ways",
         "a_k_no_budget_holds"])
def test_the_tile_rule_is_legal_wherever_the_kernel_is_asked(m, k, n, groups):
    """Shapes no cell launches: rows a group under a lane tile, a row count
    only 128 divides, sides no budget holds whole (K is split only where
    the narrowest row and result tiles do not fit beside it — the kernels'
    accumulator passes then run — and N takes what is left)."""
    for gated in (True, False):
        tiling = gmm_tiling(m, k, n, groups, gated=gated)
        _legal(tiling, m, k, n, gated)
        assert tiling[1] == k or k == 65536
    assert gmm_tiling(4096, 65536, 1024, 4, gated=True) == (128, 32768, 128)
    assert gmm_tiling(4096, 65536, 1024, 4) == (128, 8192, 1024)
    assert gmm_tiling(4096, 8192, 8192, 4, gated=True) == (128, 8192, 512)
    assert gmm_tiling(384, 128, 384, 3) == (128, 128, 384)


TILE_LAYOUTS = {"ragged": [300, 0, 41, 260, 199],
                "empty_groups": [0, 500, 0, 0, 300],
                "one_holds_every_row": [0, 0, 800, 0, 0]}


@pytest.mark.parametrize("kernel", ["gated", "down"])
@pytest.mark.parametrize("layout", sorted(TILE_LAYOUTS))
def test_any_two_tiles_are_bit_equal_on_the_real_rows(layout, kernel):
    """With K in one block a tile choice moves no bit of a real row: three
    row tiles against two widths of N, interpreted, equal each other
    exactly and the XLA form to the order of a float32 sum (800 real rows
    of 1,024 launched; the rows past the last group are nobody's). Down's
    widths are all of N or whole sublane tiles of lane tiles
    (``down_widths``: 2,048 and 1,024 of 2,048), and its XLA form is
    ``ragged_dot`` reshaped."""
    sizes = jnp.asarray(TILE_LAYOUTS[layout], jnp.int32)
    m, k, real = 1024, 256, 800
    n = 384 if kernel == "gated" else 2048
    keys = jax.random.split(jax.random.PRNGKey(47), 3)
    lhs = jax.random.normal(keys[0], (m, k), F32).astype(jnp.bfloat16)
    a, b = ((jax.random.normal(key, (5, k, n), F32) * 0.1
             ).astype(jnp.bfloat16) for key in keys[1:])
    if kernel == "gated":
        def at(tiling):
            return gated_gmm(lhs, a, b, sizes, out_dtype=jnp.bfloat16,
                             tiling=tiling, interpret=True)
        want = (jax.nn.silu(grouped_matmul_reference(lhs, a, sizes))
                * grouped_matmul_reference(lhs, b, sizes)
                ).astype(jnp.bfloat16)
        atol = 2e-2
    else:
        def at(tiling):
            return down_gmm(lhs, a, sizes, tiling=tiling, interpret=True)
        assert down_widths(n) == [2048, 1024] and down_widths(384) == [384]
        want, atol = grouped_matmul(lhs, a, sizes), 1e-4
        assert want.shape == (m, n // LANES, LANES)
    first = np.asarray(at((128, k, n)), np.float32)[:real]
    for tiling in ((256, k, 128 if kernel == "gated" else 1024), (512, k, n)):
        np.testing.assert_array_equal(
            np.asarray(at(tiling), np.float32)[:real], first)
    np.testing.assert_allclose(first, np.asarray(want, np.float32)[:real],
                               atol=atol, rtol=atol)


@pytest.mark.parametrize("case", sorted(TILE_LAYOUTS) + ["nothing_routed"])
def test_the_visited_rows_are_the_visits_times_the_row_tile(case):
    """``gated_tile_rows`` against a walk over the groups by hand: each
    non-empty group visits every row tile its span of rows touches, a tile
    two groups share once for each."""
    sizes = dict(TILE_LAYOUTS, nothing_routed=[0] * 5)[case]
    m, k, n = 1024, 256, 384
    tm = gmm_tiling(m, k, n, len(sizes), gated=True)[0]
    visits, start = 0, 0
    for size in sizes:
        if size:
            visits += -(-(start + size) // tm) - start // tm
        start += size
    got = gated_tile_rows(jnp.asarray(sizes, jnp.int32), m, k, n,
                          use_pallas=True)
    assert got.dtype == jnp.int32 and int(got) == visits * tm
    assert sum(sizes) <= int(got) <= sum(sizes) + 2 * tm * len(sizes)
    # megablox's own schedule visits as many
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    _, scheduled = make_group_metadata(
        group_sizes=jnp.asarray(sizes, jnp.int32), m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=len(sizes),
        visit_empty_groups=False)
    assert int(scheduled) == visits
    # the XLA form, asked or declined, visits nothing
    assert int(gated_tile_rows(jnp.asarray(sizes, jnp.int32), m, k, n,
                               use_pallas=False)) == 0
    assert int(gated_tile_rows(jnp.asarray(sizes, jnp.int32), 1000, k, n,
                               use_pallas=True)) == 0


def test_the_encoder_is_the_same_through_the_kernel(text):
    """Widths the kernel tiles (hidden 128, experts of 128): the Pallas form
    (interpreted) and the XLA form give the same encoder."""
    cfg = dataclasses.replace(CFG, intermediate_size=128, num_hidden_layers=1)
    p = jax.jit(lambda k: init_olmoe_params(k, cfg))(jax.random.PRNGKey(9))
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (2, 64)).astype(np.int32)
    mask = np.arange(64)[None, :] < np.asarray([64, 17])[:, None]
    a, _ = olmoe_logits(p, ids, mask, cfg)
    b, _ = olmoe_logits(p, ids, mask, cfg, use_pallas=True,
                        kernel_interpret=True)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=0)


# ------------------------------------- only the real tokens are routed
# rows of 12 positions: full, ragged, one token, and a bucket's filler row
# that holds none: 48 slots, 27 real tokens
RAGGED = (12, 5, 1, 9, 0)
CAPACITIES = {"every_slot_by_default": None, "every_slot": 60,
              "three_quarters": 45, "the_smallest_that_fits": 27}


@pytest.fixture(scope="module")
def ragged():
    rng = np.random.default_rng(29)
    ids = rng.integers(0, CFG.vocab_size, (len(RAGGED), 12)).astype(np.int32)
    return ids, np.arange(12)[None, :] < np.asarray(RAGGED)[:, None]


@pytest.mark.parametrize("case", sorted(CAPACITIES))
def test_only_the_real_tokens_are_routed(params32, ragged, case):
    """At any capacity that holds them, the real positions' hidden states
    and every row's answer are what routing every slot gave."""
    ids, mask = ragged
    capacity = CAPACITIES[case]
    assert mask.sum() == 27 and mask.size == 60
    want_hidden, _ = _every_slot_routed(params32, ids, mask, CFG)
    hidden, peaks = olmoe_encode(params32, ids, mask, CFG, capacity=capacity)
    np.testing.assert_allclose(np.asarray(hidden)[mask],
                               np.asarray(want_hidden)[mask],
                               atol=1e-5, rtol=0)
    # a padding position gets the attention half of the block and nothing
    # from the experts; the groups hold the real tokens' pairs alone
    assert np.isfinite(np.asarray(hidden)).all()
    assert (np.asarray(peaks)[1] == 27 * CFG.num_experts_per_tok).all()
    got = olmoe_predict(params32, ids, mask, CFG, capacity=capacity)
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(ref_logits(params32, ids, mask, CFG), -1)[:, 1]
    # every row that holds a token (a transaction's text has [CLS] and
    # [SEP] at least); the empty row's answer is pooled from a padding
    # position, belongs to no transaction, and is only finite
    held = mask.any(axis=1)
    np.testing.assert_allclose(got[held], want[held], atol=1e-5, rtol=0)
    assert np.isfinite(np.asarray(got)).all()


def test_token_slots_lists_the_real_slots_then_fillers_of_its_own(ragged):
    _, mask = ragged
    idx, real = olmoe.token_slots(mask, 32)
    assert idx.shape == real.shape == (32,) and int(real.sum()) == 27
    np.testing.assert_array_equal(idx[:27], np.flatnonzero(mask))
    # sorted, unique, and every filler past the last slot: a scatter with
    # mode="drop" leaves them out
    assert (np.diff(np.asarray(idx)) > 0).all() and int(idx[27]) >= mask.size
    for every_slot in (None, mask.size):
        idx, real = olmoe.token_slots(mask, every_slot)
        assert idx is None
        np.testing.assert_array_equal(real, mask.reshape(-1))
    for impossible in (0, mask.size + 16):
        with pytest.raises(ValueError, match="capacity"):
            olmoe.token_slots(mask, impossible)


def test_unrouted_rows_enter_no_group_and_get_zero(params32):
    layer = params32["layers"][0]
    k = CFG.num_experts_per_tok
    x = jax.random.normal(jax.random.PRNGKey(6), (48, CFG.hidden_size), F32)
    experts, weights = route(x, layer["router"], k)
    real = np.arange(48) % 3 != 1                          # 32 of 48
    want, (all_sizes, _) = apply_experts(layer, x, experts, weights)
    got, (sizes, _) = apply_experts(layer, x, experts, weights,
                                    real=jnp.asarray(real))
    assert int(all_sizes.sum()) == 48 * k
    assert int(sizes.sum()) == 32 * k                  # real tokens x top-k
    np.testing.assert_array_equal(
        sizes, np.bincount(np.asarray(experts)[real].ravel(),
                           minlength=CFG.num_experts))
    np.testing.assert_allclose(np.asarray(got)[real], np.asarray(want)[real],
                               atol=1e-6, rtol=0)
    assert not np.asarray(got)[~real].any()


@pytest.mark.parametrize("capacity", [None, 45, 32])
def test_rows_the_kernel_never_wrote_reach_nothing(params32, ragged,
                                                   monkeypatch, capacity):
    """The grouped kernels visit only the tiles of a group: with the groups
    summing to fewer rows than were launched, the rest of their result is
    uninitialised. Poisoned here, it must change nothing."""
    ids, mask = ragged
    want_h, _ = olmoe_encode(params32, ids, mask, CFG, capacity=capacity)
    want_p = olmoe_predict(params32, ids, mask, CFG, capacity=capacity)
    real_pairs = int(mask.sum()) * CFG.num_experts_per_tok
    poisoned = []

    def tail_poisoned(call):
        def poisoning(*operands, **kw):
            out, group_sizes = call(*operands, **kw), operands[-1]
            written = (jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
                       ).reshape((-1,) + (1,) * (out.ndim - 1))
            poisoned.append(out.shape[0] - real_pairs)
            return jnp.where(written, out, jnp.nan)

        return poisoning

    # the fused gate + up kernel leaves the same rows unwritten
    monkeypatch.setattr(olmoe, "grouped_gated_matmul",
                        tail_poisoned(grouped_gated_matmul))
    monkeypatch.setattr(olmoe, "grouped_matmul", tail_poisoned(grouped_matmul))
    got_h, _ = olmoe_encode(params32, ids, mask, CFG, capacity=capacity)
    got_p = olmoe_predict(params32, ids, mask, CFG, capacity=capacity)
    assert poisoned and min(poisoned) >= 0 and max(poisoned) > 0
    np.testing.assert_array_equal(got_h, want_h)
    np.testing.assert_array_equal(got_p, want_p)


@pytest.mark.parametrize("capacity", [None, 192, 128])
def test_compacted_through_the_kernel(capacity):
    """The Pallas form (interpreted), whose tail past the last group is
    whatever the buffer held, against the XLA form with every slot routed."""
    cfg = dataclasses.replace(CFG, intermediate_size=128, num_hidden_layers=1)
    p = jax.tree.map(
        lambda a: a.astype(F32),
        jax.jit(lambda k: init_olmoe_params(k, cfg))(jax.random.PRNGKey(9)))
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (4, 64)).astype(np.int32)
    mask = np.arange(64)[None, :] < np.asarray([64, 17, 30, 5])[:, None]
    rows = (capacity or mask.size) * cfg.num_experts_per_tok
    assert grouped_matmul_supported(rows, cfg.hidden_size,
                                    cfg.intermediate_size)
    want_h, _ = _every_slot_routed(p, ids, mask, cfg)
    got_h, _ = olmoe_encode(p, ids, mask, cfg, capacity=capacity,
                            use_pallas=True, kernel_interpret=True)
    np.testing.assert_allclose(np.asarray(got_h)[mask],
                               np.asarray(want_h)[mask], atol=1e-5, rtol=0)
    assert np.isfinite(np.asarray(got_h)).all()
    got = olmoe_predict(p, ids, mask, cfg, capacity=capacity,
                        use_pallas=True, kernel_interpret=True)
    with jax.default_matmul_precision("highest"):
        want = jax.nn.softmax(ref_logits(p, ids, mask, cfg), -1)[:, 1]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("stored,atol", [("float32", 1e-5),
                                         ("bfloat16", 1e-2)])
@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
def test_apply_experts_is_the_same_through_the_kernels(
        experts_through_both_forms, rung, stored, atol):
    """Experts as wide as a lane tile, at both capacities of a launch of
    4,096 slots: the fused gate + up + SiLU kernel, down's and the
    combine (interpreted) against the XLA form."""
    cfg = dataclasses.replace(CFG, intermediate_size=128, num_hidden_layers=1)
    layer = jax.tree.map(
        lambda a: a.astype(stored),
        jax.jit(lambda k: init_olmoe_params(k, cfg))(jax.random.PRNGKey(9))
    )["layers"][0]
    sizes = experts_through_both_forms(
        layer, top_k=cfg.num_experts_per_tok, rung=rung, atol=atol)
    assert sizes.sum() == 2800 * cfg.num_experts_per_tok and sizes.min() > 0


def test_empty_filler_rows_beside_real_ones_change_nothing(params32, ragged):
    """A bucket's filler rows hold no token (``FraudScorer._pack_launch``):
    the real rows' answers are those of the batch without them."""
    ids, mask = ragged
    alone = olmoe_predict(params32, ids[:4], mask[:4], CFG)
    filled = np.concatenate([ids[:4], np.repeat(ids[:1], 4, axis=0)])
    empty = np.concatenate([mask[:4], np.zeros((4, 12), bool)])
    for capacity in (None, 48):
        got = olmoe_predict(params32, filled, empty, CFG, capacity=capacity)
        np.testing.assert_allclose(got[:4], alone, atol=1e-6, rtol=0)
        assert np.isfinite(np.asarray(got)).all()


# ----------------------------- the fused core at the attention site
# Heads of 128 (one lane tile) and one block of 128 positions: the shape
# ops.attention.windowed_attention takes with the QK-norm riding it. TINY's
# head_dim 64 it declines by name. Rows: full, ragged, one token, and a
# bucket's filler row that holds none.
LANE_CFG = OlmoeConfig(
    vocab_size=512, hidden_size=256, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=2, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2)
LANE_LENGTHS = (128, 37, 1, 0)
KERNELS = dict(use_pallas=True, kernel_interpret=True)


@pytest.fixture(scope="module")
def lane():
    """``(params as stored, the same in float32, ids, mask)``."""
    p = jax.jit(lambda k: init_olmoe_params(k, LANE_CFG))(
        jax.random.PRNGKey(34))
    rng = np.random.default_rng(34)
    ids = rng.integers(0, LANE_CFG.vocab_size, (4, 128)).astype(np.int32)
    mask = np.arange(128)[None, :] < np.asarray(LANE_LENGTHS)[:, None]
    return p, jax.tree.map(lambda a: a.astype(F32), p), ids, mask


def test_the_core_is_asked_of_the_one_predicate():
    assert LANE_CFG.core_refusal(128) is None
    assert OlmoeConfig().core_refusal(128) is None
    assert "head_dim 64" in CFG.core_refusal(128)
    assert "seq_len 64" in LANE_CFG.core_refusal(64)
    # the norm over all heads rides the one-block form alone
    assert "seq_len 256" in LANE_CFG.core_refusal(256)


@pytest.mark.parametrize("stored,atol", [("float32", 1e-5),
                                         ("bfloat16", 1e-2)])
def test_the_encoder_is_the_same_through_the_fused_core(lane, stored, atol):
    """Every REAL position of ragged rows: the encoder whose attention site
    is ``windowed_attention`` (interpreted: QK-norm over all heads, RoPE and
    the head split inside it) against the XLA form; in float32 the routing
    and the groups are the same too. With bfloat16 weights the kernel rounds
    q, k, v and the softmax weights to bfloat16, as the chip's default
    precision does and the CPU's float32 einsum does not: the tolerance is
    that rounding's."""
    p16, p32, ids, mask = lane
    p = p32 if stored == "float32" else p16
    want, want_peaks = olmoe_encode(p, ids, mask, LANE_CFG)
    got, peaks = olmoe_encode(p, ids, mask, LANE_CFG, **KERNELS)
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask],
                               atol=atol, rtol=0)
    assert np.isfinite(np.asarray(got)).all()
    # the kernels' programs alone visit tiles, whole ones
    assert not np.asarray(want_peaks)[2].any()
    assert (np.asarray(peaks)[2] >= np.asarray(peaks)[1]).all()
    assert not (np.asarray(peaks)[2] % 128).any()
    if stored == "float32":
        np.testing.assert_array_equal(peaks[:2], want_peaks[:2])
        for a, b in zip(_every_slot_routed(p, ids, mask, LANE_CFG)[1],
                        _every_slot_routed(p, ids, mask, LANE_CFG,
                                           **KERNELS)[1]):
            np.testing.assert_array_equal(
                np.asarray(a).reshape(4, 128, -1)[mask],
                np.asarray(b).reshape(4, 128, -1)[mask])
        a, _ = olmoe_logits(p, ids, mask, LANE_CFG)
        b, _ = olmoe_logits(p, ids, mask, LANE_CFG, **KERNELS)
        held = mask.any(axis=1)
        np.testing.assert_allclose(np.asarray(b)[held], np.asarray(a)[held],
                                   atol=1e-5, rtol=0)


def test_the_fused_core_under_a_narrow_capacity(lane):
    """The compacted program (166 real tokens of 512 slots at a capacity of
    192) with the fused core, against the XLA form with every slot routed."""
    _, p32, ids, mask = lane
    assert mask.sum() == 166
    want, _ = _every_slot_routed(p32, ids, mask, LANE_CFG)
    got, peaks = olmoe_encode(p32, ids, mask, LANE_CFG, capacity=192,
                              **KERNELS)
    np.testing.assert_allclose(np.asarray(got)[mask], np.asarray(want)[mask],
                               atol=1e-5, rtol=0)
    assert np.isfinite(np.asarray(got)).all()
    assert (np.asarray(peaks)[1] == 166 * LANE_CFG.num_experts_per_tok).all()


def test_the_qk_norm_statistic_is_taken_over_all_heads():
    """Two heads a hundred times apart in scale: the RMS over the whole
    projection leaves the small head small (its scores nearly flat), a norm
    a head would bring it to unit scale. The kernel is the former."""
    from realtime_fraud_detection_tpu.ops import (
        merge_heads,
        rope_lane_tables,
        split_heads,
        windowed_attention,
    )

    b, t, heads, eps = 2, 128, 2, LANE_CFG.rms_norm_eps
    key = jax.random.PRNGKey(7)
    scale = jnp.repeat(jnp.asarray([100.0, 1.0]), 128)
    q = jax.random.normal(jax.random.fold_in(key, 1), (b, t, 256)) * scale
    k = jax.random.normal(jax.random.fold_in(key, 2), (b, t, 256)) * scale
    v = jax.random.normal(jax.random.fold_in(key, 3), (b, t, 256))
    qw = 1 + 0.1 * jax.random.normal(jax.random.fold_in(key, 4), (256,))
    kw = 1 + 0.1 * jax.random.normal(jax.random.fold_in(key, 5), (256,))
    lengths = jnp.asarray([128, 50], jnp.int32)
    mask = np.arange(t)[None, :] < np.asarray(lengths)[:, None]
    cos, sin = olmoe.rope_tables(t, 128, LANE_CFG.rope_theta)
    *tables, shift = rope_lane_tables(cos, sin, 128)
    got = windowed_attention(
        q, k, v, lengths, num_heads=heads, num_kv_heads=heads,
        rope=tuple(tables), rope_shift=shift, norm=(qw, kw), norm_eps=eps,
        interpret=True)

    def oracle(norm):
        qh = olmoe.apply_rope(split_heads(norm(q, qw), heads), cos, sin)
        kh = olmoe.apply_rope(split_heads(norm(k, kw), heads), cos, sin)
        return np.asarray(merge_heads(attention_reference(
            qh, kh, split_heads(v, heads), mask, causal=True)))

    def a_head_at_a_time(x, w):
        return olmoe.rms_norm(x.reshape(b, t, heads, 128),
                              w.reshape(heads, 128), eps).reshape(b, t, -1)

    whole = oracle(lambda x, w: olmoe.rms_norm(x, w, eps))
    np.testing.assert_allclose(np.asarray(got)[mask], whole[mask], atol=2e-5)
    assert np.abs(oracle(a_head_at_a_time) - whole)[mask].max() > 0.1


# ------------------------------------------------------ masks and pooling
@pytest.fixture(params=["xla", "fused_core"])
def site(request, params, text, lane):
    """``(config, params, ids, mask, keywords)`` of each form of the
    attention site: TINY through XLA, the lane-tile configuration through
    the interpreted kernels."""
    if request.param == "xla":
        return (CFG, params, *text, {})
    p16, _, ids, mask = lane
    return LANE_CFG, p16, ids, mask, KERNELS


def test_causality_a_later_token_moves_no_earlier_position(site):
    cfg, params, ids, mask, kw = site
    full = np.ones_like(mask)
    base, _ = olmoe_encode(params, ids, full, cfg, **kw)
    changed = ids.copy()
    changed[:, 10] = (changed[:, 10] + 1) % cfg.vocab_size
    moved, _ = olmoe_encode(params, changed, full, cfg, **kw)
    np.testing.assert_allclose(moved[:, :10], base[:, :10], atol=1e-6, rtol=0)
    assert np.abs(np.asarray(moved[:, 10:] - base[:, 10:])).max() > 1e-3


def test_padding_moves_nothing(site):
    cfg, params, ids, mask, kw = site
    held = mask.any(axis=1)     # a row of no token answers for no one
    base = olmoe_predict(params, ids, mask, cfg, **kw)
    noisy = np.where(mask, ids, (ids + 7) % cfg.vocab_size)
    np.testing.assert_allclose(
        olmoe_predict(params, noisy, mask, cfg, **kw)[held], base[held],
        atol=1e-6, rtol=0)
    # and the pooled position is the last REAL token: changing it moves the row
    at = int(mask[1].sum()) - 1
    last = ids.copy()
    last[1, at] = (last[1, at] + 1) % cfg.vocab_size
    assert abs(float(olmoe_predict(params, last, mask, cfg, **kw)[1]
                     - base[1])) > 1e-4


@pytest.mark.parametrize("causal", [False, True])
def test_attention_reference_causal_argument(causal):
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, 2, 6, 8))
               for i in range(3))
    out = attention_reference(q, k, v, None, causal=causal)
    # position 0 sees only key 0 when causal, all keys otherwise
    if causal:
        np.testing.assert_allclose(out[:, :, 0], v[:, :, 0], atol=1e-6)
    else:
        assert np.abs(np.asarray(out[:, :, 0] - v[:, :, 0])).max() > 1e-3


@pytest.mark.parametrize("change,message", [
    (dict(num_key_value_heads=1), "grouped-query"),
    (dict(norm_topk_prob=True), "norm_topk_prob"),
    (dict(num_attention_heads=3, num_key_value_heads=3), "divide"),
])
def test_config_refuses_what_is_not_implemented(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_published_config_is_the_default():
    c = OlmoeConfig()
    assert (c.hidden_size, c.intermediate_size, c.num_experts,
            c.num_experts_per_tok, c.num_attention_heads, c.head_dim,
            c.num_hidden_layers, c.vocab_size) == (
        2048, 1024, 64, 8, 16, 128, 16, 50304)
    assert c.norm_topk_prob is False and c.rms_norm_eps == 1e-5


# ------------------------------------------------- the seam into the scorer
def _one_device_mesh():
    return build_mesh(devices=jax.devices()[:1])


def _scorer(**kw):
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig

    kw.setdefault("mesh", _one_device_mesh())
    return FraudScorer(bert_config=CFG,
                       scorer_config=ScorerConfig(text_len=32), **kw)


def test_fused_program_through_scorer_and_job_one_prediction_each():
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )

    scorer = _scorer()
    broker = InMemoryBroker()
    cfg = JobConfig(max_batch=32)
    job = StreamJob(broker, scorer, cfg)
    gen = TransactionGenerator(num_users=64, num_merchants=16)
    recs = gen.generate_batch(40)
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
    assert c["errors"] == 0 and c["scored"] == 40
    k, layers = CFG.num_experts_per_tok, CFG.num_hidden_layers
    # the pairs that entered the grouped matmuls are the real tokens': the
    # padding of a row and the filler rows of a bucket are not routed
    assert c["expert_rows"] == c["real_tokens"] * k * layers
    assert c["expert_rows"] <= c["expert_token_slots"] * k * layers \
        <= c["token_slots"] * k * layers
    # 32 x 32 slots: under the smallest launch that gets a narrow rung
    assert c["expert_token_slots"] == c["token_slots"]
    assert c["compact_batches"] == 0
    # largest group x experts >= all rows; equal only under even routing
    assert c["expert_peak_rows"] >= c["expert_rows"] > 0
    assert scorer.kernel_snapshot()["fallback"]["attention"] == c["batches"]


@pytest.mark.parametrize("side", ["held", "declined_by_shape", "not_asked"])
def test_the_gate_up_site_is_counted_and_entered_once_a_sparse_layer(side):
    """``kernel_snapshot()`` counts every routed launch at ``expert_gate_up``
    — dispatched where its program holds the fused gate + up + SiLU kernel,
    a fallback where the predicate (experts 64 wide: under a lane tile) or
    the selector (a CPU mesh, nothing asked) left it the three-call form —
    and the compile ledger shows the program's trace entering ``gated_gmm``
    once a sparse layer and ``down_gmm`` once. ``expert_combine`` beside it
    by ITS predicate (``combine_supported``: hidden 128 is a lane tile, so
    the launch whose experts are too narrow for the grouped kernels still
    brings its rows home through the one kernel, ``combine_rows`` once a
    sparse layer)."""
    import time

    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        KernelSettings,
    )

    config = Config()
    if side != "not_asked":
        config.kernels = KernelSettings(enabled=True, attention="flash")
    cfg = CFG if side == "declined_by_shape" else LANE_CFG
    scorer = FraudScorer(bert_config=cfg, config=config,
                         scorer_config=ScorerConfig(text_len=128),
                         mesh=_one_device_mesh())
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    # another test on this worker may have traced the same program
    score_fused_packed.clear_cache()
    t0 = time.time()
    pending = scorer.dispatch(recs)
    assert len(scorer.finalize(pending)) == 5
    snap = scorer.kernel_snapshot()
    held = int(side == "held")
    assert snap["dispatch"]["expert_gate_up"] == held
    assert snap["fallback"]["expert_gate_up"] == 1 - held
    traces = [r for r in scorer.host_stats()["compile"]["records"]
              if r["phase"] == "trace" and r["start"] >= t0
              and "score_fused_packed" in r["program"]]
    assert len(traces) == 1                 # 8 x 128 slots: one rung
    entered = {name: times for name, (times, _) in
               traces[0].get("nested", {}).items()}
    layers = cfg.num_hidden_layers
    assert entered.get("gated_gmm", 0) == held * layers
    assert entered.get("down_gmm", 0) == held * layers
    home = int(side != "not_asked")
    assert combine_supported(8 * 128, cfg.num_experts_per_tok,
                             cfg.hidden_size)
    assert snap["dispatch"]["expert_combine"] == home
    assert snap["fallback"]["expert_combine"] == 1 - home
    assert entered.get("combine_rows", 0) == home * layers
    # the span the bucket's programs were built under names the tiles of
    # both grouped calls, and the launch counts the rows their grid
    # visited: whole row tiles, over the real pairs; nothing of either
    # where the launch holds the XLA form
    rows = 8 * 128 * cfg.num_experts_per_tok
    tiles = "x".join(map(str, gmm_tiling(
        rows, cfg.hidden_size, cfg.intermediate_size, cfg.num_experts,
        gated=True)))
    assert (f" tiles={8 * 128}:{tiles}+" in traces[0]["caused_by"]) == held
    assert ("tiles=" in traces[0]["caused_by"]) == held
    c = pending.counters
    assert c["expert_rows"] == c["routed_pairs"] > 0
    if held:
        assert c["expert_tile_rows"] % 128 == 0
        assert c["expert_rows"] < c["expert_tile_rows"] <= layers * (
            rows + 128 * cfg.num_experts)
    else:
        assert c["expert_tile_rows"] == 0


def test_the_dense_program_has_no_second_output_and_no_expert_rows():
    from realtime_fraud_detection_tpu.scoring import FraudScorer
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    scorer = FraudScorer(mesh=_one_device_mesh())
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(3)
    pending = scorer.dispatch(recs)
    c = pending.counters
    assert pending.text_stats is None and c["expert_rows"] == 0
    assert c["expert_token_slots"] == 0 and c["compact_batches"] == 0
    assert not isinstance(pending.out, tuple)
    assert len(scorer.finalize(pending)) == 3
    assert pending.counters["expert_peak_rows"] == 0
    # nor has its snapshot the routed experts' site
    snap = scorer.kernel_snapshot()
    assert not {"expert_gate_up", "expert_combine"} & {
        *snap["dispatch"], *snap["fallback"]}


def test_the_seam_leaves_the_dense_program_as_it_was(monkeypatch):
    """The DistilBERT program through ``text_predict`` is instruction for
    instruction the program with the parent's direct ``bert_predict`` call
    in its place (and ``attention_reference`` without its new argument)."""
    import re

    from jax.experimental.compilation_cache import compilation_cache as cc

    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models import bert
    from realtime_fraud_detection_tpu.scoring import pipeline
    from realtime_fraud_detection_tpu.utils.config import Config

    def compile_packed():
        models = pipeline.init_scoring_models(jax.random.PRNGKey(0))
        blobs, spec = pack_tree(pipeline.make_example_batch(
            8, pipeline.ScorerConfig(), rng=np.random.default_rng(7)))
        fn = jax.jit(lambda *a, **k: pipeline._score_fused_packed_impl(
            *a, **k), static_argnames=pipeline._PACKED_STATIC)
        text = fn.lower(
            models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
            params=EnsembleParams.from_config(
                Config(), list(pipeline.MODEL_NAMES)),
            model_valid=jnp.ones((len(pipeline.MODEL_NAMES),), bool),
            bert_config=bert.TINY_CONFIG).compile().as_text()
        start = re.search(r"^(?:ENTRY )?%\S+ \(", text, re.M).start()
        return re.sub(r", metadata=\{[^}]*\}", "", text[start:])

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        through_the_seam = compile_packed()

        def parents_call(params, ids, mask, config, capacity=None, **static):
            assert capacity is None       # a dense launch passes none
            return bert.bert_predict(params, ids, mask, config, **static), None

        def parents_attention(q, k, v, key_mask=None):
            d = q.shape[-1]
            s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(F32) / np.sqrt(d)
            if key_mask is not None:
                s = jnp.where(key_mask[:, None, None, :], s, -1e30)
            return jnp.einsum("bhqk,bhkd->bhqd",
                              jax.nn.softmax(s, axis=-1).astype(v.dtype), v)

        monkeypatch.setattr(pipeline, "text_predict", parents_call)
        monkeypatch.setattr(bert, "attention_reference", parents_attention)
        as_the_parent = compile_packed()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    assert through_the_seam.count(" fusion(") > 10
    assert through_the_seam == as_the_parent


# -------------------------------------- the DistilBERT-only planes refuse it
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

    def mesh_executor():
        from realtime_fraud_detection_tpu.scoring.mesh_executor import (
            MeshExecutor,
        )

        MeshExecutor(_scorer(), devices=jax.devices()[:2])

    def pipeline_parallel():
        from realtime_fraud_detection_tpu.parallel.pipeline import (
            bert_pipeline_encode,
        )

        bert_pipeline_encode(None, {}, None, None, CFG)

    def context_parallel():
        from realtime_fraud_detection_tpu.parallel.context import (
            bert_context_parallel_predict,
        )

        bert_context_parallel_predict(None, {}, None, None, CFG)

    return [(quant, "QuantSettings"), (dequant, "dequant_matmul"),
            (sharded_mesh, "sharded mesh"),
            (device_pool, "DevicePool"), (mesh_executor, "MeshExecutor"),
            (pipeline_parallel, "parallel/pipeline"),
            (context_parallel, "parallel/context")]


@pytest.mark.parametrize("attempt,named", _refusals(),
                         ids=[n for _, n in _refusals()])
def test_a_distilbert_only_plane_refuses_an_olmoe_config(attempt, named):
    with pytest.raises(ValueError, match=named) as err:
        attempt()
    assert "Olmoe" in str(err.value)


def test_the_traced_guards_refuse_too(params, text):
    from realtime_fraud_detection_tpu.scoring.pipeline import text_predict

    ids, mask = text
    with pytest.raises(ValueError, match="dequant_matmul"):
        text_predict(params, ids, mask, CFG, dequant_kernel="pallas")
