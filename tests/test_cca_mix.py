"""The fused mixing (ops/cca_mix.py) in interpret mode against the XLA form
it stands in for (``models.zaya.cca_mix`` with the value shift beside it):
q, k and v to float32 rounding, position 0 and the first position of every
block on their own, rows and the past kept apart, the predicate's refusals
by name, the guard in ``zaya_attention``, and the programs that hold no
mixing left as they were.

A head of the kernel is one lane tile, so nothing here runs at TINY's
``head_dim`` 16 (there the predicate declines, and a test says so): the
small cases keep ``head_dim`` 128 and cut the heads, the rows and the model
around them.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.models import olmoe, zaya
from realtime_fraud_detection_tpu.models.zaya import (
    TINY_ZAYA,
    ZayaConfig,
    init_zaya_params,
    zaya_predict,
)
from realtime_fraud_detection_tpu.ops import (
    cca_mix_fused,
    cca_mix_refusal,
    split_heads,
)
from realtime_fraud_detection_tpu.ops import cca_mix as mix_module

PUBLISHED = ZayaConfig(num_hidden_layers=1)        # 8 + 2 heads of 128
BLOCK = mix_module.BLOCK_T
# float32 rounding of values of a few units (a head's norm is sqrt(128)):
# the sums of the norm and of the mean are taken in another order
ATOL = 5e-6


def _heads(heads, kv):
    return dataclasses.replace(PUBLISHED, num_attention_heads=heads,
                               num_key_value_heads=kv)


def _inputs(cfg, b, t, seed=0):
    d, n = cfg.head_dim, cfg.latent_heads
    k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 4)
    layer = {
        "conv_depthwise": jax.random.normal(k1, (2, n * d)) / np.sqrt(2),
        "conv_grouped": (jax.random.normal(k2, (n, 2 * d, d))
                         / np.sqrt(2 * d)).astype(jnp.bfloat16),
        "temperature": jnp.linspace(0.8, 1.25, cfg.num_key_value_heads),
    }
    latents = 0.9 * jax.random.normal(k0, (n, b, t, d), jnp.float32)
    values = jax.random.normal(k3, (b, t, cfg.num_key_value_heads * d),
                               jnp.float32)
    return layer, latents, values


def _tables(cfg, t):
    return olmoe.rope_tables(t, cfg.rotary_dim, cfg.rope_theta)


def xla_form(cfg, layer, latents, values):
    """What ``zaya_attention`` runs where the kernel is not asked for."""
    cos, sin = _tables(cfg, latents.shape[2])
    q, k = jax.jit(lambda la, c: zaya.cca_mix(la, c, cos, sin, cfg))(
        layer, latents)
    now, before = jnp.split(values, 2, axis=-1)
    v = split_heads(jnp.concatenate([now, zaya.shift_tokens(before)], -1),
                    cfg.num_key_value_heads)
    return np.asarray(q), np.asarray(k), np.asarray(v)


def fused(cfg, layer, latents, values):
    cos, sin = _tables(cfg, latents.shape[2])
    out = jax.jit(lambda la, c, v: cca_mix_fused(
        c, v, la["conv_depthwise"], la["conv_grouped"], la["temperature"],
        cos, sin, num_heads=cfg.num_attention_heads,
        num_kv_heads=cfg.num_key_value_heads, eps=cfg.rms_norm_eps,
        interpret=True))(layer, latents, values)
    return tuple(np.asarray(x) for x in out)


# ------------------------------------------------- against the XLA form
@pytest.mark.parametrize("b,t", [
    (1, 128),       # one published-width block: a row of 8 + 2 heads
    (8, 128),       # the parity sample's bucket: two grid steps of four
    (6, 128),       # three steps of two rows
    (3, 256),       # one row a step, a halo between its two blocks
    (2, 384),       # two rows a step, three blocks a row
])
def test_published_heads_equal_the_xla_form(b, t):
    args = _inputs(PUBLISHED, b, t, seed=b + t)
    want, got = xla_form(PUBLISHED, *args), fused(PUBLISHED, *args)
    for name, w, g in zip("qkv", want, got):
        assert g.shape == w.shape and g.dtype == np.float32, name
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0, err_msg=name)
    # the values are copied or shifted, never computed
    np.testing.assert_array_equal(got[2], want[2])


@pytest.mark.parametrize("heads,kv", [(2, 2), (4, 2), (8, 4), (12, 2)])
@pytest.mark.parametrize("t", [128, 256])
def test_other_head_counts_equal_the_xla_form(heads, kv, t):
    """TINY's counts and others at the kernel's ``head_dim``: a group of one
    query head, of two, four key-value heads (two read from the previous
    token), a group of six (its mean is not a power of two's)."""
    cfg = _heads(heads, kv)
    args = _inputs(cfg, 2, t, seed=heads * kv)
    for name, w, g in zip("qkv", xla_form(cfg, *args), fused(cfg, *args)):
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0, err_msg=name)


@pytest.mark.parametrize("seed", [1, 32, 3200000007])
def test_rotary_dims_other_than_half_a_head(seed):
    """The rotation is two lane rolls against tables of whole lane tiles:
    a quarter of a head and a whole head rotate as the XLA form's."""
    for factor in (0.25, 1.0):
        cfg = dataclasses.replace(_heads(2, 2), partial_rotary_factor=factor)
        args = _inputs(cfg, 1, 128, seed=seed)
        for w, g in zip(xla_form(cfg, *args), fused(cfg, *args)):
            np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)


# ------------------------------------- position 0, block starts, the halo
T3 = 3 * BLOCK


@pytest.fixture(scope="module")
def three_blocks():
    args = _inputs(PUBLISHED, 2, T3, seed=7)
    return args, xla_form(PUBLISHED, *args), fused(PUBLISHED, *args)


@pytest.mark.parametrize("position", [0, BLOCK, 2 * BLOCK])
def test_first_position_of_every_block_is_the_xla_forms(three_blocks,
                                                        position):
    _, want, got = three_blocks
    for name, w, g in zip("qkv", want, got):
        np.testing.assert_allclose(g[:, :, position], w[:, :, position],
                                   atol=ATOL, rtol=0, err_msg=name)
        assert np.abs(w[:, :, position]).max() > 0 or name == "v"


def test_position_zero_has_no_past(three_blocks):
    """Position 0 of a row is what a row of that one block gives (zeros
    before it), and the value head read from the previous token is zero
    there."""
    (layer, latents, values), _, got = three_blocks
    alone = fused(PUBLISHED, layer, latents[:, :, :BLOCK], values[:, :BLOCK])
    for g, a in zip(got, alone):
        np.testing.assert_array_equal(g[:, :, 0], a[:, :, 0])
    assert (got[2][:, 1, 0] == 0).all()
    assert (got[2][:, 0, 0] == np.asarray(values)[:, 0, :128]).all()
    assert (got[2][:, 1, 1] == np.asarray(values)[:, 0, 128:]).all()


@pytest.mark.parametrize("start", [BLOCK, 2 * BLOCK])
def test_a_later_block_starts_from_its_rows_past_not_from_zeros(
        three_blocks, start):
    """The first position of a later block differs from what the block
    computes on its own (zeros before it): the halo is what carries q, k
    and the shifted v across; from the third position on a block alone is
    already right (the two convolutions look two positions back)."""
    (layer, latents, values), _, got = three_blocks
    alone = fused(PUBLISHED, layer, latents[:, :, start:start + BLOCK],
                  values[:, start:start + BLOCK])
    for name, g, a in zip("qkv", got, alone):
        head = 1 if name == "v" else slice(None)
        assert np.abs(g[:, head, start] - a[:, head, 0]).max() > 1e-3, name
    for g, a in zip(got[:2], alone[:2]):
        assert np.abs(g[:, :, start + 1] - a[:, :, 1]).max() > 1e-3
    # rotary tables are by position, so only the unrotated dims compare
    rot = PUBLISHED.rotary_dim
    for g, a in zip(got[:2], alone[:2]):
        np.testing.assert_allclose(
            g[:, :, start + 2:start + BLOCK, rot:], a[:, :, 2:, rot:],
            atol=ATOL, rtol=0)


@pytest.mark.parametrize("back,reaches", [(1, True), (2, True), (3, False)])
def test_the_halo_is_two_positions_deep(back, reaches):
    """A block's first position sees the two positions before it (the
    depthwise tap one back, the grouped tap that one's own predecessor) and
    nothing further; the kernel and the XLA form agree on which."""
    layer, latents, values = _inputs(_heads(2, 2), 1, 2 * BLOCK, seed=3)
    moved = latents.at[:, :, BLOCK - back].add(1.0)
    cfg = _heads(2, 2)
    for form in (fused, xla_form):
        q0, k0, _ = form(cfg, layer, latents, values)
        q1, k1, _ = form(cfg, layer, moved, values)
        changed = (np.abs(q1 - q0)[:, :, BLOCK].max() > 1e-4
                   and np.abs(k1 - k0)[:, :, BLOCK].max() > 1e-4)
        assert changed == reaches, form.__name__
        # causal: nothing before the moved position changes
        np.testing.assert_array_equal(q1[:, :, :BLOCK - back],
                                      q0[:, :, :BLOCK - back])


# --------------------------------------------------- rows, padded slots
def test_rows_of_one_grid_step_do_not_leak():
    """Four rows share a grid step; changing one changes no other: a row's
    last position does not reach the next row's first."""
    cfg = _heads(4, 2)
    layer, latents, values = _inputs(cfg, 8, 128, seed=5)
    base = fused(cfg, layer, latents, values)
    other = fused(cfg, layer, latents.at[:, 3].add(1.0),
                  values.at[3].add(1.0))
    rest = [r for r in range(8) if r != 3]
    for b, o in zip(base, other):
        np.testing.assert_array_equal(b[rest], o[rest])
        assert np.abs(b[3] - o[3]).max() > 1e-3


@pytest.mark.parametrize("fill", ["zeros", "one_token"])
def test_a_padded_rows_slots_are_the_xla_forms(fill):
    """The mixing runs on every launched slot and sees no mask. A row of
    padding is one token's latents at every position (or, ahead of the
    projections' rounding, zeros: ``0 * rsqrt(eps)`` is 0, not NaN); its
    slots come out finite and equal to the XLA form's, and the real rows
    beside it in the step are untouched by it."""
    cfg = _heads(4, 2)
    layer, latents, values = _inputs(cfg, 4, 256, seed=9)
    if fill == "zeros":
        latents = latents.at[:, 2].set(0.0)
        values = values.at[2].set(0.0)
    else:
        latents = latents.at[:, 2].set(latents[:, 2, :1])
        values = values.at[2].set(values[2, :1])
    want, got = xla_form(cfg, layer, latents, values), fused(
        cfg, layer, latents, values)
    for w, g in zip(want, got):
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, atol=ATOL, rtol=0)
    if fill == "zeros":
        assert all((g[2] == 0).all() for g in got)
    else:       # past the halo of position 0, every position is the same
        steady = got[0][2, :, 2:, 64:]
        np.testing.assert_allclose(
            steady, np.broadcast_to(steady[:, :1], steady.shape), atol=ATOL)


# ------------------------------------------------------- the predicate
@pytest.mark.parametrize("shape,named", [
    ((64, 128, 2), "seq_len 64 is not a multiple of the block"),
    ((200, 128, 2), "seq_len 200 is not a multiple of the block"),
    ((128, 16, 2), "head_dim 16 is not one lane tile"),
    ((128, 64, 2), "head_dim 64 is not one lane tile"),
    ((128, 128, 3), "3 key-value heads do not halve"),
    ((128, 128, 2, (3, 2)), r"cca_time0, cca_time1 \(3, 2\)"),
    ((128, 128, 2, (2, 1)), r"cca_time0, cca_time1 \(2, 1\)"),
])
def test_the_predicate_refuses_by_name(shape, named):
    import re

    assert re.search(named, cca_mix_refusal(*shape))


@pytest.mark.parametrize("t", [128, 256, 512, 1024])
def test_the_predicate_takes_whole_blocks_of_lane_tile_heads(t):
    assert cca_mix_refusal(t, 128, 2) is None
    assert ZayaConfig().mix_refusal(t) is None


def test_the_configuration_asks_the_kernels_own_predicate():
    assert "head_dim 16" in TINY_ZAYA.mix_refusal(128)
    assert "seq_len 64" in ZayaConfig().mix_refusal(64)
    odd = dataclasses.replace(ZayaConfig(), cca_time1=3)
    assert "cca_time0, cca_time1 (2, 3)" in odd.mix_refusal(128)


@pytest.mark.parametrize("t,d,named", [(64, 128, "seq_len 64"),
                                       (128, 16, "head_dim 16")])
def test_the_kernel_raises_what_the_predicate_names(t, d, named):
    cfg = dataclasses.replace(_heads(2, 2), head_dim=d)
    layer, latents, values = _inputs(cfg, 1, t)
    with pytest.raises(ValueError, match=named):
        fused(cfg, layer, latents, values)


def test_the_kernel_refuses_latents_that_are_not_the_heads():
    layer, latents, values = _inputs(_heads(4, 2), 1, 128)
    with pytest.raises(ValueError, match="6 latent heads are not 8 query"):
        fused(PUBLISHED, layer, latents, values)


# ------------------------------------------- the guard in zaya_attention
# hidden 128, one layer, 2 + 2 heads of 128, 4 experts of width 128
LANE_HEADS = ZayaConfig(
    vocab_size=30522, hidden_size=128, num_hidden_layers=1,
    num_attention_heads=2, num_key_value_heads=2, head_dim=128,
    num_experts=4, moe_intermediate_size=128, router_hidden_size=32)


# OLMoE at heads of 128: what ``OlmoeConfig.core_refusal`` takes
LANE_OLMOE = olmoe.OlmoeConfig(
    vocab_size=30522, hidden_size=256, intermediate_size=128,
    num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
    num_experts=4, num_experts_per_tok=2)


def _attention(cfg, t, **asked):
    params = init_zaya_params(jax.random.PRNGKey(2), cfg)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, t, cfg.hidden_size))
    mask = jnp.arange(t)[None] < jnp.asarray([[t], [t // 3]])
    cos, sin = _tables(cfg, t)

    def run(h):
        return zaya.zaya_attention(params["layers"][0], h, mask, cfg, cos,
                                   sin, **asked)

    return np.asarray(jax.jit(run)(h)), str(jax.make_jaxpr(run)(h))


def test_asked_at_a_shape_it_takes_the_layer_holds_the_kernel():
    want, plain = _attention(LANE_HEADS, 128)
    got, traced = _attention(LANE_HEADS, 128, use_pallas=True,
                             kernel_interpret=True)
    assert "pallas_call" in traced and "pallas_call" not in plain
    assert "cca_mix" in traced
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("cfg,t", [(TINY_ZAYA, 128), (LANE_HEADS, 64)],
                         ids=["head_dim_16", "seq_len_64"])
def test_asked_at_a_shape_it_declines_the_layer_keeps_the_xla_form(cfg, t):
    """Declined by shape alone, with no error: the same program as not
    asking, bit for bit."""
    assert cfg.mix_refusal(t)
    want, plain = _attention(cfg, t)
    got, traced = _attention(cfg, t, use_pallas=True, kernel_interpret=True)
    assert "pallas_call" not in traced
    np.testing.assert_array_equal(got, want)


def test_not_asked_the_layer_never_reaches_the_kernel(monkeypatch):
    """The CPU path: ``use_pallas`` false is the XLA form whatever the
    shape."""
    def poisoned(*a, **k):
        raise AssertionError("the fused mixing was traced")

    monkeypatch.setattr(zaya, "cca_mix_fused", poisoned)
    _attention(LANE_HEADS, 128)
    with pytest.raises(AssertionError, match="was traced"):
        _attention(LANE_HEADS, 128, use_pallas=True)


def test_the_encoder_is_the_same_through_both_kernels():
    """``zaya_predict`` with the fused mixing and the grouped expert matmul,
    both interpreted, against the XLA forms: eight rows of 128 positions,
    ragged."""
    p = init_zaya_params(jax.random.PRNGKey(4), LANE_HEADS)
    rng = np.random.default_rng(2)
    ids = rng.integers(0, LANE_HEADS.vocab_size, (8, 128)).astype(np.int32)
    mask = np.arange(128)[None] < rng.integers(1, 129, 8)[:, None]
    want = zaya_predict(p, ids, mask, LANE_HEADS)
    got = zaya_predict(p, ids, mask, LANE_HEADS, use_pallas=True,
                       kernel_interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=0)


# ---------------------------------------------- the scorer says which ran
def _scorer(cfg, text_len, **planes):
    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config()
    for name, value in planes.items():
        setattr(config, name, value)
    return FraudScorer(bert_config=cfg, config=config,
                       scorer_config=ScorerConfig(text_len=text_len),
                       mesh=build_mesh(devices=jax.devices()[:1]))


def _flash_plane():
    from realtime_fraud_detection_tpu.utils.config import KernelSettings

    return KernelSettings(enabled=True, attention="flash")


def _bert():
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG

    return TINY_CONFIG


@pytest.mark.parametrize("cfg,text_len,planes,named", [
    (lambda: LANE_HEADS, 128, {}, "a cpu mesh"),
    (lambda: LANE_HEADS, 64, {}, "seq_len 64 is not a multiple"),
    (lambda: TINY_ZAYA, 128, {}, "head_dim 16 is not one lane tile"),
    (lambda: olmoe.TINY_OLMOE, 128, {}, "one lane tile a head): head_dim 64"),
    (_bert, 64, {}, "flash_attention takes seq_len a multiple of 128"),
    (_bert, 128, {}, "a cpu mesh"),
    (lambda: LANE_HEADS, 128, {"kernels": _flash_plane}, None),
    (lambda: LANE_HEADS, 64, {"kernels": _flash_plane}, "seq_len 64"),
    (lambda: LANE_OLMOE, 128, {}, "a cpu mesh"),
    (lambda: LANE_OLMOE, 128, {"kernels": _flash_plane}, None),
], ids=["cpu", "short", "tiny_heads", "olmoe", "bert_short", "bert_cpu",
        "asked", "asked_short", "olmoe_lane_cpu", "olmoe_lane_asked"])
def test_the_snapshot_names_why_the_xla_form_runs(cfg, text_len, planes,
                                                  named):
    """Where ``flash_attention``'s fallbacks are counted
    (``kernel_snapshot``), the attention site's refusal by name: the shape
    first (the kernel's own predicate), then what kept the selector from
    asking; None where the program holds the kernel."""
    scorer = _scorer(cfg(), text_len,
                     **{k: v() for k, v in planes.items()})
    refused = scorer.kernel_snapshot()["refused"]["attention"]
    if named is None:
        assert refused is None
    else:
        assert named in refused


def test_a_scorer_asked_holds_the_kernel_and_counts_it():
    """The kernel plane forces the side on a CPU mesh (interpreted): the
    launch counts as dispatched at the attention site, and the answers are
    the XLA program's."""
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    plain = _scorer(LANE_HEADS, 128)
    asked = _scorer(LANE_HEADS, 128, kernels=_flash_plane())
    want = plain.finalize(plain.dispatch(recs))
    got = asked.finalize(asked.dispatch(recs))
    snap = asked.kernel_snapshot()
    assert snap["dispatch"]["attention"] == 1
    assert snap["fallback"]["attention"] == 0 and snap["interpret"]
    assert plain.kernel_snapshot()["fallback"]["attention"] == 1
    np.testing.assert_allclose(
        [r["model_predictions"]["bert_text"] for r in got],
        [r["model_predictions"]["bert_text"] for r in want], atol=2e-3)


def test_an_olmoe_launch_asked_counts_under_dispatch():
    """OLMoE's attention site is ``windowed_attention`` where the plane asks
    (interpreted on a CPU mesh): its launch counts as dispatched, not as a
    fallback, nothing is refused, and the answers are the XLA program's."""
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    plain = _scorer(LANE_OLMOE, 128)
    asked = _scorer(LANE_OLMOE, 128, kernels=_flash_plane())
    want = plain.finalize(plain.dispatch(recs))
    got = asked.finalize(asked.dispatch(recs))
    snap = asked.kernel_snapshot()
    assert snap["dispatch"]["attention"] == 1
    assert snap["fallback"]["attention"] == 0
    assert snap["refused"]["attention"] is None and snap["interpret"]
    assert plain.kernel_snapshot()["fallback"]["attention"] == 1
    assert plain.kernel_snapshot()["dispatch"]["attention"] == 0
    np.testing.assert_allclose(
        [r["model_predictions"]["bert_text"] for r in got],
        [r["model_predictions"]["bert_text"] for r in want], atol=2e-3)


# -------------------------- the programs with no mixing are left as they were
def _lowered(config, **static):
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        _PACKED_STATIC,
        _score_fused_packed_impl,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        8, ScorerConfig(text_len=128)))
    # a jit of its own: the served one would answer the second lowering of a
    # case from its cache, and nothing would be traced again
    return jax.jit(_score_fused_packed_impl,
                   static_argnames=_PACKED_STATIC).lower(
        models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=jnp.ones((len(MODEL_NAMES),), bool),
        blob_bf16=blobs["bf16"], bert_config=config, **static).as_text()


@pytest.mark.parametrize("encoder,static", [
    ("distilbert", {}),
    ("distilbert", {"use_pallas": True, "kernel_interpret": True}),
    ("olmoe", {}), ("olmoe", {"use_pallas": True, "kernel_interpret": True}),
    ("zaya_tiny", {}),
    ("zaya_tiny", {"use_pallas": True, "kernel_interpret": True}),
], ids=lambda v: v if isinstance(v, str) else "+".join(v) or "plain")
def test_programs_without_the_kernel_trace_no_line_of_it(monkeypatch,
                                                         encoder, static):
    """DistilBERT's and OLMoE's packed programs, asked for their kernels or
    not, and ZAYA1's at TINY (declined by shape), lower to the same text
    with the fused mixing poisoned as with it whole: no line of
    ``ops/cca_mix.py`` is traced into them. (Against the parent commit their
    optimised HLO is digest-equal with the source metadata dropped:
    PERF.md, PR 32.)"""
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG

    config = {"distilbert": TINY_CONFIG, "olmoe": olmoe.TINY_OLMOE,
              "zaya_tiny": TINY_ZAYA}[encoder]
    whole = _lowered(config, **static)
    assert "cca_mix" not in whole

    def poisoned(*a, **k):
        raise AssertionError("the fused mixing was traced")

    monkeypatch.setattr(zaya, "cca_mix_fused", poisoned)
    assert _lowered(config, **static) == whole
