"""The JoyAI-LLM-Flash text encoder (models/joyai.py): the program against
the benchmark's plain NumPy reference (``benchmarks/configs/
joyai_reference.py``, which shares no line with it) at every capacity and
through the scorer's packed path at both rungs; the fused latent core in
interpret mode against ``attention_reference``; the sigmoid router whose
bias moves the choice alone; interleaved RoPE; the cut in depth against the
deeper model; and the seam it enters the scorer through."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import joyai, olmoe
from realtime_fraud_detection_tpu.models.joyai import (
    TINY_JOYAI,
    JoyaiConfig,
    init_joyai_params,
    joyai_attention,
    joyai_encode,
    joyai_predict,
    joyai_rope_tables,
    joyai_route,
    rotate_pairs,
)
from realtime_fraud_detection_tpu.models.text_encoder import visible_pairs
from realtime_fraud_detection_tpu.ops import (
    attention_reference,
    rope_lane_tables,
    rope_pair_tables,
    windowed_attention,
    windowed_refusal,
)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from benchmarks.harness import spec  # noqa: E402

F32 = jnp.float32
# hidden 128; layer 0 dense, then two sparse layers; 4 heads whose scores run
# over 16 + 8 dims and whose values are 16 wide; latents of 96 and 32; top-4
# of 16 experts chosen by score + bias
CFG = TINY_JOYAI
REFERENCE = spec.reference("joyai_reference")
T = 24
LENGTHS = (24, 11, 1, 17, 0)               # 53 real tokens of 120 slots
CAPACITIES = {"every_slot": None, "all_120": 120, "96": 96, "64": 64,
              "exactly_53": 53}
# heads of 128 / 64 / 128 and whole blocks of positions: what the fused core
# takes (TINY's 16 / 8 / 16 is declined by name)
LANE_CFG = JoyaiConfig(
    vocab_size=512, hidden_size=128, dense_intermediate_size=256,
    moe_intermediate_size=128, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=96, kv_lora_rank=64,
    n_routed_experts=8, num_experts_per_tok=2, expert_spread=1.0)


def reference_cfg(config: JoyaiConfig) -> dict:
    """The keys ``joyai_reference.py`` reads, for a ``JoyaiConfig``: what
    ``benchmarks/configs/joyai_builder.joyai_config`` does, backwards."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rope_theta", "rms_norm_eps",
            "first_k_dense_replace", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "n_group", "topk_group", "scoring_func",
            "topk_method", "rope_scaling", "rope_interleave")
    return {k: getattr(config, k) for k in keys}


@pytest.fixture(scope="module")
def params():
    return init_joyai_params(jax.random.PRNGKey(7), CFG)


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


def _reference(params, ids, mask, config=CFG, trace=None):
    return REFERENCE.text_branch(jax.device_get(params), np.asarray(ids),
                                 np.asarray(mask), reference_cfg(config),
                                 trace=trace)


# ------------------------------------------- program against the reference
@pytest.mark.parametrize("case", sorted(CAPACITIES))
def test_float32_program_matches_the_plain_reference_at_every_capacity(
        params, params32, text, case):
    ids, mask = text
    got = _f32(joyai_predict, params32, ids, mask, CFG,
               capacity=CAPACITIES[case])
    want = _reference(params, ids, mask)
    # the empty row reads a padding position: nothing it holds is an answer
    np.testing.assert_allclose(np.asarray(got)[:4], want[:4], atol=2e-6,
                               rtol=0)
    assert want[:4].std() > 0.0


def test_bfloat16_program_is_near_the_reference(params, text):
    ids, mask = text
    got = joyai_predict(params, ids, mask, CFG, capacity=64)
    want = _reference(params, ids, mask)
    assert np.abs(np.asarray(got) - want)[:4].max() < 3e-3


def _scorer(cfg=CFG, text_len=32, **kw):
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig

    kw.setdefault("mesh", build_mesh(devices=jax.devices()[:1]))
    return FraudScorer(bert_config=cfg,
                       scorer_config=ScorerConfig(text_len=text_len), **kw)


@pytest.fixture(scope="module")
def rung_scorer():
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=29)
    scorer = _scorer(text_len=32)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return scorer, gen


@pytest.mark.parametrize("words,rung", [(3, 3072), (40, 4096)],
                         ids=["three_quarters", "every_slot"])
def test_the_scorers_packed_path_matches_the_reference_at_each_rung(
        rung_scorer, words, rung):
    """128 rows x 32 positions is the smallest launch with two rungs: short
    texts take the narrow one, full rows every slot; at both, the text
    column the served packed program returns is the reference's on the
    batch the scorer assembled."""
    from realtime_fraud_detection_tpu.scoring import text_split

    scorer, gen = rung_scorer
    assert text_split.capacities(128 * 32) == (3072, 4096)
    recs = gen.generate_batch(128)
    for r in recs:
        r["description"] = " ".join(["x"] * words)
    batch = scorer.assemble(recs)
    pending = scorer.dispatch(recs)
    results = scorer.finalize(pending)
    assert pending.counters["expert_token_slots"] == rung
    assert pending.counters["compact_batches"] == int(rung == 3072)
    want = _reference(scorer.models.bert, batch.token_ids, batch.token_mask)
    got = np.array([r["model_predictions"]["bert_text"] for r in results])
    assert np.abs(got - want[:128]).max() < 3e-3
    # every expert is held: what entered the groups is what was routed
    c = pending.counters
    assert c["expert_rows"] == c["routed_pairs"] == c["real_tokens"] * 4 * 2
    assert c["expert_peak_rows"] % 16 == 0
    assert c["expert_rows"] <= c["expert_peak_rows"]


def test_predict_is_the_softmax_of_the_head_and_the_stats_are_the_peaks(
        params32, text):
    ids, mask = text
    hidden, peaks = joyai_encode(params32, ids, mask, CFG)
    p, peaks2 = joyai_predict(params32, ids, mask, CFG, with_stats=True)
    logits = olmoe.last_token_logits(params32, hidden, mask,
                                     CFG.rms_norm_eps)
    np.testing.assert_allclose(
        np.asarray(p), np.asarray(jax.nn.softmax(logits, -1)[:, 1]),
        atol=1e-7)
    assert peaks.shape == (3, CFG.num_sparse_layers) == (3, 2)
    np.testing.assert_array_equal(np.asarray(peaks2), np.asarray(peaks))
    pairs = sum(LENGTHS) * CFG.num_experts_per_tok
    peaks, held, tile_rows = np.asarray(peaks)
    # every expert is held: the held pairs are the routers' pairs; the XLA
    # form visits no tile
    assert (held == pairs).all() and not tile_rows.any()
    assert (peaks * CFG.num_experts >= pairs).all()
    assert (peaks <= sum(LENGTHS)).all()


# ------------------------------------------------------------- the router
def _router_case(seed=5, tokens=256, bias_scale=0.02):
    layer = {
        "router": jax.random.normal(jax.random.PRNGKey(seed), (128, 16)) * 0.1,
        "e_score_correction_bias": jax.random.normal(
            jax.random.PRNGKey(seed + 1), (16,)) * bias_scale}
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (tokens, 128))
    return layer, x


def _reference_route(layer, x, drop_bias=False, bias_in_weights=False):
    """The equations, in NumPy, with the two faults a program could have."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64)
                              @ np.asarray(layer["router"], np.float64))))
    b = np.asarray(layer["e_score_correction_bias"], np.float64)
    chosen = np.argsort(-(s if drop_bias else s + b), axis=-1,
                        kind="stable")[:, :4]
    w = np.take_along_axis(s + b if bias_in_weights else s, chosen, axis=-1)
    return chosen, 2.5 * w / (w.sum(axis=-1, keepdims=True) + 1e-20)


def _dense_weights(experts, weights, n=16):
    out = np.zeros((experts.shape[0], n))
    np.put_along_axis(out, np.asarray(experts), np.asarray(weights), axis=-1)
    return out


def test_the_router_is_the_references_with_a_bias_that_changes_the_choice():
    layer, x = _router_case()
    experts, weights, carry = _f32(joyai_route, layer, x, CFG)
    assert carry is None and experts.dtype == jnp.int32
    chosen, w = _reference_route(layer, x)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(chosen, -1))
    np.testing.assert_allclose(_dense_weights(experts, weights),
                               _dense_weights(chosen, w), atol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-6)
    # the bias did move the chosen set, on a minority of the tokens
    unbiased, _ = _reference_route(layer, x, drop_bias=True)
    moved = (np.sort(unbiased, -1) != np.sort(chosen, -1)).any(-1).mean()
    assert 0.05 < moved < 0.6, moved


@pytest.mark.parametrize("fault", ["drop_bias", "bias_in_weights"])
def test_a_router_that_drops_the_bias_or_weighs_by_it_is_caught(fault):
    """The comparison above has teeth: either fault of a program reads far
    outside its tolerance against the same reference."""
    layer, x = _router_case()
    experts, weights, _ = _f32(joyai_route, layer, x, CFG)
    chosen, w = _reference_route(layer, x, **{fault: True})
    gap = np.abs(_dense_weights(experts, weights)
                 - _dense_weights(chosen, w)).max()
    assert gap > 1e-3, gap


def test_the_encoders_answer_moves_when_the_bias_is_dropped(params32, text):
    """End to end, not only at the router: the reference with the bias
    zeroed is another function of the same weights."""
    ids, mask = text
    zeroed = jax.tree.map(lambda x: x, params32)
    for layer in zeroed["layers"][CFG.first_k_dense_replace:]:
        layer["e_score_correction_bias"] = jnp.zeros_like(
            layer["e_score_correction_bias"])
    trace = []
    want = _reference(params32, ids, mask, trace=trace)
    other = _reference(zeroed, ids, mask)
    assert np.abs(want - other)[:4].max() > 1e-5
    real = np.asarray(mask).reshape(-1)
    differs = np.mean([(t["chosen"][real] != t["unbiased"][real]).any(-1)
                       .mean() for t in trace])
    assert 0.02 < differs < 0.9, differs


def test_renormalising_without_an_eps_traces_what_it_always_did():
    """Laguna's call of ``choose_experts`` (no ``renormalise_eps``) is the
    jaxpr it was; the eps is one add more."""
    probs = jnp.ones((4, 8)) / 8

    def run(**kw):
        return str(jax.make_jaxpr(lambda p: olmoe.choose_experts(
            p, 2, renormalise=True, scale=2.5, **kw))(probs))

    assert run() == run(renormalise_eps=0.0)
    assert run(renormalise_eps=1e-20).count(" add ") \
        == run().count(" add ") + 1


# ---------------------------------------------------------------- the rope
def test_interleaved_and_de_interleaved_rope_give_the_same_scores():
    """Hugging Face's code de-interleaves (dims 0, 2, 4... then 1, 3, 5...)
    and rotates halves; that is one permutation of the 64 dims on q and k
    alike, so every score is what the interleaved form gives."""
    t, r = 40, 64
    cos, sin = joyai_rope_tables(t, r, CFG.rope_theta)
    q = jax.random.normal(jax.random.PRNGKey(0), (t, r))
    k = jax.random.normal(jax.random.PRNGKey(1), (t, r))
    pairs = rotate_pairs(q, cos, sin) @ rotate_pairs(k, cos, sin).T
    order = np.concatenate([np.arange(0, r, 2), np.arange(1, r, 2)])
    half_cos, half_sin = (np.concatenate([cos, cos], -1),
                          np.concatenate([sin, sin], -1))
    halves = (olmoe.apply_rope(q[:, order], half_cos, half_sin)
              @ olmoe.apply_rope(k[:, order], half_cos, half_sin).T)
    np.testing.assert_allclose(np.asarray(pairs), np.asarray(halves),
                               atol=2e-5)
    # and it is a rotation: position 0 is the identity, norms are kept
    np.testing.assert_allclose(np.asarray(rotate_pairs(q, cos, sin))[0],
                               np.asarray(q)[0], atol=1e-7)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(rotate_pairs(q, cos, sin)), axis=-1),
        np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-5)


def test_pair_tables_rotate_as_rotate_pairs_does():
    """The kernel's form — three per-lane tables and a shift of one lane,
    two heads' shared parts side by side in one tile — against the plain
    rotation of each head."""
    t, r = 16, 64
    cos, sin = joyai_rope_tables(t, r, 32e6)
    c, up, down, shift = rope_pair_tables(cos, sin)
    assert shift == 1 and c.shape == up.shape == down.shape == (t, 128)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (t, 128)))
    got = (x * c + np.roll(x, shift, 1) * up + np.roll(x, -shift, 1) * down)
    want = np.concatenate([np.asarray(rotate_pairs(x[:, :r], cos, sin)),
                           np.asarray(rotate_pairs(x[:, r:], cos, sin))], -1)
    np.testing.assert_allclose(got, want, atol=1e-6)
    # rope_lane_tables (rotate-half) is another layout of the same idea
    assert rope_lane_tables(*olmoe.rope_tables(t, 64, 1e4), 128)[3] == 32


# ---------------------------------------------------------- the fused core
def _core_operands(b, t, heads, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    wide = (b, t, heads * 128)
    q = jax.random.normal(ks[0], wide).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], wide).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], wide).astype(jnp.bfloat16)
    q_pe = jax.random.normal(ks[3], (b, t, heads * 64))
    k_pe = jax.random.normal(ks[4], (b, t, 64))
    return q, k, v, q_pe, k_pe


def _xla_core(q, k, v, q_pe, k_pe, mask, heads, cos, sin):
    b, t, _ = q.shape
    q_pe = rotate_pairs(q_pe.reshape(b, t, heads, 64), cos[:, None],
                        sin[:, None]).astype(jnp.bfloat16)
    k_pe = rotate_pairs(k_pe, cos, sin).astype(jnp.bfloat16)
    full_q = jnp.concatenate([q.reshape(b, t, heads, 128), q_pe], -1)
    full_k = jnp.concatenate(
        [k.reshape(b, t, heads, 128),
         jnp.broadcast_to(k_pe[:, :, None], (b, t, heads, 64))], -1)
    ctx = attention_reference(
        full_q.transpose(0, 2, 1, 3).astype(F32),
        full_k.transpose(0, 2, 1, 3).astype(F32),
        v.reshape(b, t, heads, 128).transpose(0, 2, 1, 3).astype(F32), mask,
        causal=True)
    assert ctx.shape == (b, heads, t, 128)         # v's width, not q's 192
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, heads * 128)


@pytest.mark.parametrize("t,lengths", [
    (128, (1, 128, 77)), (256, (256, 129, 5, 0)), (384, (300, 384, 128))],
    ids=["T128", "T256", "T384"])
def test_fused_latent_core_matches_the_reference_on_ragged_rows(t, lengths):
    """A row of one token, full rows, rows that end inside a block and a row
    of none: every real position of the fused core (interpreted) is the XLA
    form's, scores over 128 + 64 with the shared key rotated in the kernel,
    values of 128."""
    heads = 4
    q, k, v, q_pe, k_pe = _core_operands(len(lengths), t, heads, seed=t)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    cos, sin = joyai_rope_tables(t, 64, 32e6)
    *tables, shift = rope_pair_tables(cos, sin)
    got = windowed_attention(
        q, k, v, jnp.array(lengths), num_heads=heads, num_kv_heads=heads,
        rope=tuple(tables), rope_shift=shift, shared_key=(q_pe, k_pe),
        out_dtype=F32, interpret=True)
    want = _xla_core(q, k, v, q_pe, k_pe, mask, heads, cos, sin)
    assert got.shape == want.shape == (len(lengths), t, heads * 128)
    real = np.asarray(mask)
    gap = np.abs(np.asarray(got) - np.asarray(want))[real]
    assert gap.max() < 2e-2 and gap.mean() < 2e-3
    # from a row's first wholly padded query step on the kernel writes zeros
    from realtime_fraud_detection_tpu.ops.attention import (
        latent_query_blocks,
    )

    step = 128 * latent_query_blocks(t)
    for row, n in enumerate(lengths):
        assert not np.asarray(got)[row, -(-n // step) * step:].any()


@pytest.mark.parametrize("t,span", [(256, 2), (512, 4)])
def test_a_wide_query_step_is_each_blocks_own_answer(t, span):
    """The latent form takes ``latent_query_blocks(T)`` blocks of 128
    queries a step (a head's keys serve no other head: a wider step is how
    its matmuls see more rows). Rows that end inside a wide step's first,
    middle and last key block, a row of one token and an empty one read what
    the same queries read one block a step — the row's first 128 positions
    alone, as a sequence of 128 — and the XLA form."""
    from realtime_fraud_detection_tpu.ops.attention import (
        latent_query_blocks,
    )

    assert latent_query_blocks(t) == span and latent_query_blocks(128) == 1
    heads = 2
    lengths = tuple(min(n, t) for n in (512, 300, 129, 1, 0, 257))
    q, k, v, q_pe, k_pe = _core_operands(len(lengths), t, heads, seed=span)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    cos, sin = joyai_rope_tables(t, 64, 32e6)

    def core(width):
        *tables, shift = rope_pair_tables(cos[:width], sin[:width])
        return np.asarray(windowed_attention(
            q[:, :width], k[:, :width], v[:, :width],
            jnp.minimum(jnp.array(lengths), width), num_heads=heads,
            num_kv_heads=heads, rope=tuple(tables), rope_shift=shift,
            shared_key=(q_pe[:, :width], k_pe[:, :width]), out_dtype=F32,
            interpret=True))

    wide, narrow = core(t), core(128)
    want = np.asarray(_xla_core(q, k, v, q_pe, k_pe, mask, heads, cos, sin))
    real = np.asarray(mask)
    assert np.abs(wide[:, :128] - narrow)[real[:, :128]].max() < 2e-3
    assert np.abs(wide - want)[real].max() < 2e-2
    assert np.abs(wide - want)[real].mean() < 2e-3
    assert not wide[4].any()                       # the empty row: zeros


def test_the_query_step_tiles_the_sequence():
    from realtime_fraud_detection_tpu.ops.attention import (
        LATENT_QUERY_BLOCKS,
        latent_query_blocks,
    )

    assert LATENT_QUERY_BLOCKS == 4
    assert [latent_query_blocks(t) for t in (128, 256, 384, 512, 2048)] \
        == [1, 2, 1, 4, 4]


def test_the_grouped_matmul_takes_256_narrow_groups():
    """256 groups of ~300 rows and a width of 768, six lane tiles: the rule
    gives both calls K and N whole — 768 in ONE block, where a power-of-two
    tile was a third of it and the rows crossed HBM three times — and a row
    tile of 128, under half a group (PERF.md section 6, PR 47: 7.2 -> 5.1
    and 4.8 -> 3.2 ms a layer alone on the chip). The kernel at the rule's
    tiling, interpreted, against ``ragged_dot``: 130 ragged groups of seven
    rows on average, some empty, rows past the last group never read."""
    from realtime_fraud_detection_tpu.ops.grouped_matmul import (
        gmm_tiling,
        grouped_matmul,
        grouped_matmul_reference,
    )

    for rows in (98304, 131072):
        assert gmm_tiling(rows, 2048, 768, 256, gated=True) == (
            128, 2048, 768)
        assert gmm_tiling(rows, 768, 2048, 256) == (128, 768, 2048)
    m, k, n, groups = 1024, 256, 384, 130
    assert gmm_tiling(m, k, n, groups) == (128, 256, 384)
    rng = np.random.default_rng(3)
    sizes = rng.multinomial(900, rng.dirichlet(np.full(groups, 0.5)))
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((groups, k, n)), jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul(lhs, rhs, sizes, use_pallas=True, interpret=True)
    want = grouped_matmul_reference(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(got).reshape(m, n)[:900],
                               np.asarray(want)[:900], atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
def test_apply_experts_is_the_same_through_the_kernels(
        experts_through_both_forms, rung):
    """256 groups of ~44 rows, each ending inside a row tile (a tile is
    visited by a dozen groups), at both capacities of a launch of 4,096
    slots: the fused gate + up + SiLU kernel, down's and the combine
    (interpreted) against the XLA form."""
    cfg = dataclasses.replace(LANE_CFG, n_routed_experts=256,
                              num_experts_per_tok=4)
    layer = init_joyai_params(jax.random.PRNGKey(2), cfg)["layers"][1]
    assert layer["gate_proj"].shape == (256, 128, 128)
    sizes = experts_through_both_forms(layer, top_k=4, rung=rung, atol=1e-2)
    assert sizes.sum() == 2800 * 4 and (sizes % 128 != 0).all()


def test_the_shared_term_is_in_the_scores_and_the_scale_is_192s():
    """With values that read the weights off: dropping the shared term, or
    scaling by 128^-1/2, is another softmax."""
    heads, t = 2, 128
    q, k, _, q_pe, k_pe = _core_operands(1, t, heads, seed=9)
    v = jnp.tile(jnp.eye(t, 128, dtype=jnp.bfloat16), (1, 1, heads))
    cos, sin = joyai_rope_tables(t, 64, 32e6)
    *tables, shift = rope_pair_tables(cos, sin)
    lengths = jnp.array([t])

    def core(q_pe):
        return windowed_attention(
            q * 0.1, k, v, lengths, num_heads=heads, num_kv_heads=heads,
            rope=tuple(tables), rope_shift=shift,
            shared_key=(q_pe * 0.1, k_pe), out_dtype=F32, interpret=True)

    mask = jnp.ones((1, t), bool)
    want = _xla_core(q * 0.1, k, v, q_pe * 0.1, k_pe, mask, heads, cos, sin)
    got = core(q_pe)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-2
    assert np.abs(np.asarray(core(q_pe * 0.0))
                  - np.asarray(want)).max() > 2e-2
    # each head reads ITS lanes of the shared tile: swapping the two heads'
    # shared parts is another answer
    swapped = q_pe.reshape(1, t, heads, 64)[:, :, ::-1].reshape(1, t, -1)
    assert np.abs(np.asarray(core(swapped)) - np.asarray(want)).max() > 2e-2


def test_the_encoder_is_the_same_through_the_kernels():
    """Heads of 128 / 64 / 128 and whole blocks: the fused core (and the
    grouped matmul) interpreted against the XLA forms, ragged rows."""
    assert LANE_CFG.core_refusal(256) is None
    assert "head_dim 16" in CFG.core_refusal(256)
    assert "seq_len 100" in LANE_CFG.core_refusal(100)
    p = init_joyai_params(jax.random.PRNGKey(2), LANE_CFG)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 256), 0, 512)
    mask = jnp.arange(256)[None, :] < jnp.array([256, 150])[:, None]
    xla, peaks = joyai_encode(p, ids, mask, LANE_CFG)
    fused, peaks_k = joyai_encode(p, ids, mask, LANE_CFG, use_pallas=True,
                                  kernel_interpret=True)
    real = np.asarray(mask)
    np.testing.assert_allclose(np.asarray(fused)[real], np.asarray(xla)[real],
                               atol=3e-2)
    assert np.abs(np.asarray(fused) - np.asarray(xla))[real].mean() < 2e-3
    assert peaks.shape == peaks_k.shape == (3, 1)
    np.testing.assert_array_equal(peaks[1], peaks_k[1])


def test_the_attention_site_holds_one_custom_call_under_its_scope():
    """``attn_core`` is the ONE ``windowed_attention`` call where the kernel
    is held, and no kernel where it is refused."""
    p = init_joyai_params(jax.random.PRNGKey(2), LANE_CFG)
    layer = p["layers"][0]
    h = jnp.ones((2, 128, 128))
    mask = jnp.ones((2, 128), bool)
    cos, sin = joyai_rope_tables(128, 64, LANE_CFG.rope_theta)

    def jaxpr(**kw):
        return str(jax.make_jaxpr(lambda h: joyai_attention(
            layer, h, mask, jnp.array([128, 128]), LANE_CFG, cos, sin,
            **kw))(h))

    assert jaxpr(use_pallas=True, kernel_interpret=True).count(
        "windowed_attention") >= 1
    assert "windowed_attention" not in jaxpr()


def test_lagunas_call_traces_the_blocked_kernel_it_always_did():
    """A call without a shared key lowers to the same jaxpr whether or not
    the new keyword is spelled, over ``(rows, key heads, query blocks)``;
    with one, two heads go a step together."""
    q = jnp.ones((2, 128, 256))
    v = q.astype(jnp.bfloat16)
    lengths = jnp.array([128, 3])
    *tables, shift = rope_lane_tables(*olmoe.rope_tables(128, 128, 1e4), 128)

    def run(tables=tuple(tables), shift=shift, q=q, **kw):
        return str(jax.make_jaxpr(lambda *a: windowed_attention(
            *a, num_heads=2, num_kv_heads=2, rope=tables, rope_shift=shift,
            interpret=True, **kw))(q, q, v, lengths))

    assert run() == run(shared_key=None)
    assert "grid=(2, 2, 1)" in run()
    *pair, one = rope_pair_tables(*joyai_rope_tables(128, 64, 32e6))
    latent = run(tuple(pair), one, q=v,
                 shared_key=(jnp.ones((2, 128, 128)), jnp.ones((2, 128, 64))))
    assert "grid=(2, 1, 1)" in latent


@pytest.mark.parametrize("shape,kw,named", [
    ((2048, 128, 32, 32, None), dict(shared_key_dim=48), "shared_key_dim 48"),
    ((2048, 128, 32, 8, None), dict(shared_key_dim=64),
     "one key head a query head"),
    ((2048, 128, 32, 32, 512), dict(shared_key_dim=64), "window 512"),
    ((2048, 128, 31, 31, None), dict(shared_key_dim=64), "2 heads a step"),
    ((2048, 128, 32, 32, None), dict(shared_key_dim=64, value_dim=64),
     "value_dim 64"),
    ((2000, 128, 32, 32, None), dict(shared_key_dim=64), "seq_len 2000"),
    ((2048, 192, 32, 32, None), dict(shared_key_dim=64), "head_dim 192"),
])
def test_the_core_refuses_a_latent_shape_by_name(shape, kw, named):
    assert named in windowed_refusal(*shape, **kw)
    assert windowed_refusal(2048, 128, 32, 32, None, shared_key_dim=64,
                            value_dim=128) is None
    assert JoyaiConfig().core_refusal(2048) is None
    assert "seq_len 64" in JoyaiConfig().core_refusal(64)


def test_a_latent_call_without_its_rotation_is_refused():
    q, k, v, q_pe, k_pe = _core_operands(1, 128, 2)
    with pytest.raises(ValueError, match="rotates its shared term"):
        windowed_attention(q, k, v, jnp.array([128]), num_heads=2,
                           num_kv_heads=2, shared_key=(q_pe, k_pe),
                           interpret=True)


# ------------------------------------------------------ what must not move
def test_a_padding_slot_changes_no_real_tokens_answer(params32, text):
    ids, mask = text
    base = _f32(joyai_predict, params32, ids, mask, CFG)
    other = jnp.where(mask, ids, (ids + 7) % CFG.vocab_size)
    moved = _f32(joyai_predict, params32, other, mask, CFG)
    np.testing.assert_allclose(np.asarray(moved)[:4], np.asarray(base)[:4],
                               atol=1e-7)


def test_causality_a_later_token_moves_no_earlier_position(params32, text):
    ids, mask = text
    h, _ = _f32(joyai_encode, params32, ids, mask, CFG)
    changed = ids.at[0, 20].set((ids[0, 20] + 1) % CFG.vocab_size)
    h2, _ = _f32(joyai_encode, params32, changed, mask, CFG)
    np.testing.assert_allclose(np.asarray(h2)[0, :20], np.asarray(h)[0, :20],
                               atol=1e-6)
    assert np.abs(np.asarray(h2)[0, 20:] - np.asarray(h)[0, 20:]).max() > 1e-4


# ------------------------------------------------------------------ the cut
def test_the_cut_is_the_deeper_models_first_five_layers():
    """The configuration runs 5 of 40 layers: layer 0 dense, 1-4 sparse. The
    cut's layers ARE the published model's first five (shape for shape at
    the published widths), and a deeper configuration handed those five
    layers computes bit for bit what the cut computes, program and reference
    alike: depth enters no layer's equations."""
    published, cut = JoyaiConfig(), JoyaiConfig(num_hidden_layers=5)
    assert (published.num_hidden_layers, published.num_sparse_layers,
            cut.num_sparse_layers) == (40, 39, 4)
    shapes40, shapes5 = (jax.eval_shape(
        lambda k, c=c: init_joyai_params(k, c), jax.random.PRNGKey(0))
        for c in (published, cut))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), shapes40["layers"][:5]) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), shapes5["layers"])
    assert "mlp_gate" in shapes5["layers"][0] \
        and all("router" in layer for layer in shapes5["layers"][1:])
    deep = dataclasses.replace(CFG, num_hidden_layers=8)
    five = dataclasses.replace(CFG, num_hidden_layers=5)
    p = init_joyai_params(jax.random.PRNGKey(4), deep)
    first = {**p, "layers": p["layers"][:5]}
    ids = jax.random.randint(jax.random.PRNGKey(5), (3, 16), 0, 30522)
    mask = jnp.arange(16)[None, :] < jnp.array([16, 9, 2])[:, None]
    np.testing.assert_array_equal(
        np.asarray(joyai_predict(first, ids, mask, deep)),
        np.asarray(joyai_predict(first, ids, mask, five)))
    np.testing.assert_array_equal(
        _reference(first, ids, mask, deep), _reference(first, ids, mask, five))
    # and the layers beyond do change the answer: the cut is a cut
    assert np.abs(np.asarray(joyai_predict(p, ids, mask, deep))
                  - np.asarray(joyai_predict(first, ids, mask, five))
                  ).max() > 1e-6


# ----------------------------------------------------------- configuration
def test_published_config_is_the_default():
    c = JoyaiConfig()
    assert (c.vocab_size, c.hidden_size, c.num_hidden_layers,
            c.num_attention_heads) == (129280, 2048, 40, 32)
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.qk_head_dim, c.v_head_dim) == (
        1536, 512, 128, 64, 192, 128)
    assert (c.n_routed_experts, c.num_experts_per_tok, c.n_shared_experts,
            c.moe_intermediate_size, c.dense_intermediate_size) == (
        256, 8, 1, 768, 7168)
    assert (c.routed_scaling_factor, c.norm_topk_prob, c.rope_theta,
            c.rms_norm_eps) == (2.5, True, 32e6, 1e-6)
    # what the routed-encoder seam reads, under its names
    assert (c.num_experts, c.intermediate_size, c.num_sparse_layers) == (
        256, 768, 39)


@pytest.mark.parametrize("change,message", [
    (dict(n_group=8, topk_group=4), "group-limited routing is not built"),
    (dict(scoring_func="softmax"), "'softmax' router"),
    (dict(topk_method="greedy"), "'greedy'"),
    (dict(rope_scaling={"type": "yarn", "factor": 40}), "rope_scaling null"),
    (dict(rope_interleave=False), "interleaved RoPE"),
    (dict(num_key_value_heads=8), "one key-value head a query head"),
    (dict(moe_layer_freq=2), "every layer sparse"),
    (dict(qk_rope_head_dim=63), "pairs of dims"),
])
def test_config_refuses_what_the_equations_cannot_hold(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_stored_dtypes_and_shapes_are_the_checkpoints(params):
    assert params["embed_tokens"].dtype == jnp.bfloat16
    assert params["score"].dtype == F32 and params["norm"].dtype == F32
    dense, sparse = params["layers"][0], params["layers"][1]
    h, heads = CFG.hidden_size, CFG.num_attention_heads
    assert dense["q_a_proj"].shape == (h, 96)
    assert dense["q_a_layernorm"].shape == (96,)
    assert dense["q_b_proj"].shape == (96, heads * (16 + 8))
    assert dense["kv_a_proj_with_mqa"].shape == (h, 32 + 8)
    assert dense["kv_a_layernorm"].shape == (32,)
    assert dense["kv_b_proj"].shape == (32, heads * (16 + 16))
    assert dense["o_proj"].shape == (heads * 16, h)
    assert dense["mlp_gate"].shape == (h, 256) and "router" not in dense
    assert sparse["router"].shape == (h, 16) and "mlp_gate" not in sparse
    assert sparse["e_score_correction_bias"].shape == (16,)
    assert sparse["e_score_correction_bias"].dtype == F32
    assert 0.2 * CFG.bias_range < float(
        jnp.std(sparse["e_score_correction_bias"])) < 3 * CFG.bias_range
    assert sparse["gate_proj"].shape == (16, h, 64)
    assert sparse["down_proj"].shape == (16, 64, h)
    assert sparse["shared_gate"].shape == (h, 64)
    for name in ("q_a_proj", "q_b_proj", "kv_b_proj", "o_proj", "router",
                 "gate_proj", "shared_down"):
        assert sparse[name].dtype == jnp.bfloat16, name
    # unit-scale embeddings, the rest at 0.02
    assert 0.9 < float(jnp.std(params["embed_tokens"].astype(F32))) < 1.1
    assert 0.015 < float(jnp.std(sparse["gate_proj"].astype(F32))) < 0.025


def test_routing_spreads_over_the_experts(params32, text):
    ids, mask = text
    trace = []
    _reference(params32, ids, mask, trace=trace)
    real = np.asarray(mask).reshape(-1)
    for t in trace:
        assert len(np.unique(t["chosen"][real])) >= 12      # of 16


# ------------------------------------------------- the seam into the scorer
def test_one_description_of_a_routed_encoder_serves_all_four():
    from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA
    from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA
    from realtime_fraud_detection_tpu.scoring import pipeline

    routed = pipeline.text_encoder(CFG)
    assert routed is joyai.TEXT_ENCODER
    assert routed.init is init_joyai_params
    assert routed.sites[0].refusal(CFG, 256, 256) == CFG.core_refusal(256)
    assert pipeline.text_layers(CFG) == 3
    for cfg in (olmoe.TINY_OLMOE, TINY_ZAYA, TINY_LAGUNA, CFG):
        for name in ("num_experts", "num_experts_per_tok",
                     "num_hidden_layers", "hidden_size", "intermediate_size",
                     "num_sparse_layers"):
            assert isinstance(getattr(cfg, name), int), name
    assert (CFG.num_sparse_layers, CFG.intermediate_size,
            CFG.num_experts) == (2, 64, 16)
    assert JoyaiConfig in pipeline.TextConfig.__args__


def test_through_scorer_and_job_the_counters_count_every_pair():
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
    # top-4 in two sparse layers for every real token, all of them entered
    assert c["routed_pairs"] == c["expert_rows"] \
        == c["real_tokens"] * 4 * 2 > 0
    assert c["expert_peak_rows"] % CFG.num_experts == 0
    assert c["expert_rows"] <= c["expert_peak_rows"]
    assert c["compact_batches"] == c["batches"] > 0
    # a causal encoder with no window: the full count, nothing under one
    assert c["attn_visible_pairs_full"] >= c["real_tokens"]
    assert c["attn_visible_pairs_sliding"] == 0
    lengths = np.array([0, 1, 7, 128])
    assert visible_pairs(CFG, lengths) == (
        sum(n * (n + 1) // 2 for n in lengths), 0)
    refused = scorer.kernel_snapshot()["refused"]["attention"]
    assert "head_dim 16" in refused and "value_dim 16" in refused


def test_the_scorers_answer_is_the_encoders(params):
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    scorer = _scorer(text_len=32)
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    batch = scorer.assemble(recs)
    results = scorer.finalize(scorer.dispatch(recs))
    want = joyai_predict(scorer.models.bert, jnp.asarray(batch.token_ids),
                         jnp.asarray(batch.token_mask), CFG)
    got = [r["model_predictions"]["bert_text"] for r in results]
    np.testing.assert_allclose(got, np.asarray(want)[:5], atol=1e-4, rtol=0)


def test_a_distilbert_only_plane_refuses_a_joyai_config_by_name():
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        QuantSettings,
    )

    config = Config()
    config.quant = QuantSettings(enabled=True, bert_weights="int8")
    with pytest.raises(ValueError, match="JoyaiConfig"):
        _scorer(config=config)
    with pytest.raises(ValueError, match="JoyaiConfig"):
        _scorer(mesh=build_mesh(devices=jax.devices()[:2]))
