"""The Qwen3-Next text encoder (models/qwen3_next.py): three Gated-DeltaNet
layers to one gated softmax-attention layer, every layer's second half
routed beside a gated shared expert. The program against the benchmark's
plain reference (``benchmarks/configs/qwen3next_reference.py``: the delta
rule a position at a time, a materialised softmax, every held expert over
every token, no line shared with the program) alone at every capacity and
through the scorer's packed path at both rungs; the chunked scan
(``ops/delta_scan.py``) against the recurrence across chunk boundaries and
from a state handed in, and its kernel in interpret mode against the XLA
form; the attention core at heads of 256 in interpret mode; planted faults
that each fail the configuration's parity limit; the two shares of a layer
against the uncut layer; the refusals by name; and the seam it enters the
scorer through, which leaves the seven other encoders' programs as they
were."""

import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import falcon_h1, olmoe, qwen3_next
from realtime_fraud_detection_tpu.models.qwen3_next import (
    TINY_QWEN3_NEXT,
    Qwen3NextConfig,
    init_qwen3_next_params,
    qwen3_next_encode,
    qwen3_next_predict,
)
from realtime_fraud_detection_tpu.ops.attention import windowed_refusal
from realtime_fraud_detection_tpu.ops.causal_conv import conv_refusal
from realtime_fraud_detection_tpu.ops.delta_scan import (
    delta_refusal,
    gated_delta_scan,
)

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from benchmarks.harness import spec  # noqa: E402
# a TINY packed program's lowering: Falcon-H1's tests' own
from test_falcon_h1 import _lowered  # noqa: E402

F32 = jnp.float32
CFG = TINY_QWEN3_NEXT                       # LLLFL at hidden 128
REFERENCE = spec.reference("qwen3next_reference")
FILE = json.loads(
    (ROOT / "benchmarks/configs/qwen3-next-80b-a3b-s2048.json").read_text())
# what a run on the chip is held to: a planted fault has to read over it
LIMIT = FILE["parity_atol"]["branch:bert_text"]
T = 32                                      # four chunks of 8
LENGTHS = (32, 17, 1, 25, 9)                # 84 real tokens of 160 slots
CAPACITIES = {"every_slot": None, "all_160": 160, "128": 128, "96": 96}
# what the two kernels take: a state of 128 x 128, chunks of 64, heads of
# 256 over whole blocks of 128 positions (TINY's 16 and 32 are declined by
# name)
LANE_CFG = dataclasses.replace(
    CFG, vocab_size=512, hidden_size=256, num_hidden_layers=4,
    linear_key_head_dim=128, linear_value_head_dim=128,
    linear_num_key_heads=4, linear_num_value_heads=8, delta_chunk=64,
    head_dim=256, num_attention_heads=4, num_key_value_heads=2)


def reference_cfg(config: Qwen3NextConfig) -> dict:
    """The keys ``qwen3next_reference.py`` reads, for a ``Qwen3NextConfig``:
    what ``benchmarks/configs/qwen3next_builder.qwen3next_config`` does,
    backwards."""
    return dict(dataclasses.asdict(config), expert_share={
        "chips": config.router_experts // config.num_experts,
        "index": config.expert_offset // config.num_experts})


@pytest.fixture(scope="module")
def params():
    return init_qwen3_next_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def params32(params):
    return jax.tree.map(lambda x: x.astype(F32), params)


@pytest.fixture(scope="module")
def text():
    ids = jax.random.randint(jax.random.PRNGKey(1), (len(LENGTHS), T), 0,
                             CFG.vocab_size)
    mask = jnp.arange(T)[None, :] < jnp.array(LENGTHS)[:, None]
    return ids, mask


def _predict32(params32, ids, mask, config=CFG, **kw):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, i, m: qwen3_next_predict(p, i, m, config, **kw))(
            params32, ids, mask))


def _reference(params, ids, mask, config=CFG, **kw):
    return REFERENCE.text_branch(jax.device_get(params), np.asarray(ids),
                                 np.asarray(mask), reference_cfg(config),
                                 **kw)


@pytest.fixture(scope="module")
def want(params, text):
    return _reference(params, *text)


# ------------------------------------------- program against the reference
@pytest.mark.parametrize("case", sorted(CAPACITIES))
def test_float32_program_matches_the_plain_reference_at_every_capacity(
        params32, text, want, case):
    got = _predict32(params32, *text, capacity=CAPACITIES[case])
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert want.std() > 0.01


def test_bfloat16_program_is_near_the_reference(params, text, want):
    got = qwen3_next_predict(params, *text, CFG, capacity=96)
    assert np.abs(np.asarray(got) - want).max() < 4e-3 < LIMIT


def test_both_halves_of_every_layer_weigh_in_the_residual(params, text):
    """A half whose update is small beside the residual is a half nobody
    checks (``init_qwen3_next_params``): at a long row's last real token
    every mixer's and every sparse half's update is over a twentieth of the
    residual it is added to, and over the rows the routed experts' part of
    a sparse half runs from nearly none (a token whose heaviest experts
    live elsewhere) to nearly all."""
    _, parts = _reference(params, *text, parts=True)
    assert parts.shape == (CFG.num_hidden_layers, 6, len(LENGTHS))
    mixer, residual, sparse, routed, held, _ = (parts[:, i] for i in range(6))
    # (the held mass of a token's weights: a quarter of TINY's experts held)
    assert (held >= 0.0).all() and (held <= 1.0 + 1e-6).all()
    assert (routed[held == 0.0] < 1e-6).all()
    long_rows = [i for i, n in enumerate(LENGTHS) if n >= 17]
    assert (mixer / residual)[:, long_rows].min() > 0.05
    assert (sparse / residual)[:, long_rows].min() > 0.05
    share = routed / sparse
    assert share.min() < 0.2 and share.max() > 0.8


def test_padding_leaves_a_rows_answer_bit_equal(params, text):
    ids, mask = text
    alone = qwen3_next_predict(params, ids[:, :], mask, CFG)
    other = ids.at[:, 1:].set(jnp.where(mask[:, 1:], ids[:, 1:], 7))
    np.testing.assert_array_equal(
        np.asarray(alone), np.asarray(qwen3_next_predict(params, other, mask,
                                                         CFG)))


def test_a_later_token_moves_no_earlier_position(params32, text):
    """Every mixer is causal: position t of the hidden states is the same
    whatever stands behind it (the recurrence and the convolution look
    back, the attention core is masked)."""
    ids, mask = text
    full = jnp.ones_like(mask)
    with jax.default_matmul_precision("highest"):
        a, _ = qwen3_next_encode(params32, ids, full, CFG)
        b, _ = qwen3_next_encode(params32, ids.at[:, 20:].set(3), full, CFG)
    np.testing.assert_allclose(np.asarray(a)[:, :20], np.asarray(b)[:, :20],
                               atol=1e-5)
    assert np.abs(np.asarray(a)[:, 20:] - np.asarray(b)[:, 20:]).max() > 0.1


def test_the_stats_count_every_layers_held_pairs(params32, text):
    ids, mask = text
    with jax.default_matmul_precision("highest"):
        _, stats = qwen3_next_predict(params32, ids, mask, CFG,
                                      with_stats=True)
    stats = np.asarray(stats)
    assert stats.shape == (3, CFG.num_hidden_layers)
    pairs = sum(LENGTHS) * CFG.num_experts_per_tok
    # a quarter of the router's experts are held: some pairs, never all
    assert (stats[1] > 0).all() and (stats[1] < pairs).all()
    assert (stats[0] <= stats[1]).all() and (stats[2] == 0).all()


# ------------------------------------------------------------ the delta scan
def _scan_inputs(b, t, hk, hv, dk, dv, seed=0, dtype=np.float32):
    """Unit keys and queries, decays of 0.9 to 0.999 a step (a state
    outlives a chunk) and ``beta`` across (0, 1)."""
    r = np.random.default_rng(seed)

    def unit(shape):
        x = r.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    return (jnp.asarray(unit((b, t, hk, dk)) * dk ** -0.5, dtype),
            jnp.asarray(unit((b, t, hk, dk)), dtype),
            jnp.asarray(r.standard_normal((b, t, hv, dv)), dtype),
            jnp.asarray(-np.exp(r.uniform(np.log(1e-3), np.log(0.1),
                                          (b, t, hv))), F32),
            jnp.asarray(1 / (1 + np.exp(-r.standard_normal((b, t, hv)))),
                        F32))


def _sequential(q, k, v, g, beta, state=None, delta=True, gate=True):
    """The recurrence a position and a head at a time, float64; ``delta``
    False leaves ``S^T k`` out of the update (plain gated linear
    attention), ``gate`` False reads ``beta`` as one."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    s = (np.zeros((b, hv, dk, dv)) if state is None
         else np.array(state, np.float64))
    out = np.zeros((b, t, hv, dv))
    for i in range(t):
        for h in range(hv):
            k_t, q_t = k[:, i, h // (hv // hk)], q[:, i, h // (hv // hk)]
            decayed = np.exp(g[:, i, h])[:, None, None] * s[:, h]
            seen = np.einsum("bkv,bk->bv", decayed, k_t) if delta else 0.0
            u = (beta[:, i, h, None] if gate else 1.0) * (v[:, i, h] - seen)
            s[:, h] = decayed + np.einsum("bk,bv->bkv", k_t, u)
            out[:, i, h] = np.einsum("bkv,bk->bv", s[:, h], q_t)
    return out, s


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunked_scan_is_the_recurrence_across_chunk_boundaries(chunk):
    """50 positions: six whole chunks of 8 and a part, three of 16 and a
    part, one padded chunk of 64 — the same answers and the same final
    state, and the state did cross a boundary."""
    args = _scan_inputs(2, 50, 2, 4, 16, 16)
    want, final = _sequential(*args)
    got, state = gated_delta_scan(*args, chunk=chunk)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6 * scale)
    np.testing.assert_allclose(np.asarray(state), final, atol=2e-6)
    alone, _ = gated_delta_scan(*(x[:, 16:] for x in args), chunk=chunk)
    assert np.abs(np.asarray(alone) - want[:, 16:]).max() > 0.05 * scale


def test_a_sequence_cut_in_two_hands_its_state_on():
    args = _scan_inputs(2, 48, 2, 4, 16, 16, seed=1)
    want, final = _sequential(*args)
    first, state = gated_delta_scan(*(x[:, :24] for x in args), chunk=8)
    second, last = gated_delta_scan(*(x[:, 24:] for x in args), chunk=8,
                                    initial_state=state)
    np.testing.assert_allclose(
        np.concatenate([first, second], axis=1), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(last), final, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(state), _sequential(*(x[:, :24] for x in args))[1],
        atol=1e-6)


@pytest.mark.parametrize("positions,key_heads", [
    (256, 4), (64, 4), (192, 8), (128, 16), (128, 2)])
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero",
                                                        "state_in"])
def test_the_kernel_in_interpret_mode_is_the_recurrence(positions, key_heads,
                                                        carried):
    """Key heads of 128 under twice as many value heads, chunks of 64: the
    Pallas form through the interpreter against the XLA form AND the
    recurrence a position at a time — one chunk and several, a group of
    key heads that is all of them (2, 4), one sublane tile of them (8) and
    two grid steps of a tile each (16)."""
    chunk, value_heads = 64, 2 * key_heads
    args = _scan_inputs(1, positions, key_heads, value_heads, 128, 128,
                        seed=2)
    assert delta_refusal(positions, 128, 128, chunk, key_heads,
                         value_heads) is None
    state = jnp.asarray(0.1 * np.random.default_rng(5).standard_normal(
        (1, value_heads, 128, 128)), F32) if carried else None
    xla, xla_final = gated_delta_scan(*args, chunk=chunk,
                                      initial_state=state)
    got, final = gated_delta_scan(*args, chunk=chunk, initial_state=state,
                                  use_pallas=True, interpret=True)
    want, want_final = _sequential(*args, state=state)
    assert got.shape == (1, positions, value_heads, 128)
    assert final.shape == (1, value_heads, 128, 128)
    # (the solve's products at three passes: float32-class, not float32)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                               atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4 * scale)
    np.testing.assert_allclose(np.asarray(final), want_final, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(xla_final),
                               atol=2e-4)


def test_the_kernel_gives_each_value_head_its_own_key_head_and_steps():
    """Value heads that share their values: two heads of one key head
    differ by their ``g`` and ``beta`` alone, heads of two key heads by
    their keys too; each is the one-head recurrence's answer."""
    q, k, v, g, beta = _scan_inputs(1, 128, 4, 8, 128, 128, seed=3)
    v = jnp.tile(v[:, :, :1], (1, 1, 8, 1))
    got, _ = gated_delta_scan(q, k, v, g, beta, chunk=64, use_pallas=True,
                              interpret=True)
    want, _ = _sequential(q, k, v, g, beta)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4 * scale)
    assert np.abs(want[:, :, 0] - want[:, :, 1]).max() > 1e-2 * scale
    assert np.abs(want[:, :, 1] - want[:, :, 2]).max() > 1e-2 * scale


def test_the_kernel_at_bfloat16_is_near_the_xla_form():
    args = _scan_inputs(1, 256, 4, 8, 128, 128, seed=4, dtype=jnp.bfloat16)
    want, _ = gated_delta_scan(*args, chunk=64)
    got, _ = gated_delta_scan(*args, chunk=64, use_pallas=True,
                              interpret=True)
    scale = float(jnp.abs(want).max())
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-2 * scale


@pytest.mark.parametrize("shape,says", [
    ((2048, 128, 128, 64, 16, 32), None),
    ((2048, 128, 128, 64, 4, 8), None),
    ((64, 128, 128, 64, 3, 6), None),
    ((2048, 16, 16, 8, 2, 4), "key_dim 16"),
    ((2048, 128, 256, 64, 16, 32), "value_dim 256"),
    ((2048, 128, 128, 32, 16, 32), "chunk 32"),
    ((2048, 128, 128, 128, 16, 32), "chunk 128"),
    ((100, 128, 128, 64, 16, 32), "seq_len 100"),
    ((32, 128, 128, 64, 16, 32), "seq_len 32"),
    ((2048, 128, 128, 64, 16, 16), "16 value heads over 16 key heads"),
    ((2048, 128, 128, 64, 8, 32), "32 value heads over 8 key heads"),
    ((2048, 128, 128, 64, 16, 24), "24 value heads over 16 key heads"),
])
def test_the_scan_names_what_its_kernel_refuses(shape, says):
    refusal = delta_refusal(*shape)
    assert (refusal is None) if says is None else (says in refusal), refusal


# ------------------------------------------------------------ planted faults
def _plain_linear_attention(q, k, v, g, beta, **kw):
    """``- alpha S^T k`` dropped from the update."""
    out, state = _sequential(q, k, v, g, beta, delta=False)
    return jnp.asarray(out, F32), jnp.asarray(state, F32)


def _beta_dropped(q, k, v, g, beta, **kw):
    return gated_delta_scan(q, k, v, g, jnp.ones_like(beta), **kw)


def _chunk_reset_scan(q, k, v, g, beta, *, chunk, **kw):
    """The state not carried across a chunk boundary."""
    parts = [gated_delta_scan(*(x[:, s:s + chunk] for x in (q, k, v, g,
                                                            beta)),
                              chunk=chunk)[0]
             for s in range(0, q.shape[1], chunk)]
    return jnp.concatenate(parts, axis=1), None


def _key_heads_swapped(q, k, v, g, beta, **kw):
    """Value head j reading key head ``(heads - 1) - j // ratio``."""
    return gated_delta_scan(q[:, :, ::-1], k[:, :, ::-1], v, g, beta, **kw)


def _gate_then_norm(o, z, weight, eps):
    """Mamba-2's order where the norm comes first."""
    return olmoe.rms_norm(o * jax.nn.silu(z), weight, eps)


def _weight_not_centred(x, weight, eps):
    """``1 + w`` read as ``w``."""
    return olmoe.rms_norm(x, weight, eps)


def _per_head_gate(params32):
    """Attention's gate one value a HEAD (Laguna's form): every column of a
    head's gate the mean of its columns."""
    h, heads, d = (CFG.hidden_size, CFG.num_attention_heads, CFG.head_dim)
    layers = list(params32["layers"])
    for i, kind in enumerate(CFG.layer_kinds):
        if kind == qwen3_next.FULL:
            w = layers[i]["q_proj"].reshape(h, heads, 2, d)
            gate = jnp.broadcast_to(
                w[:, :, 1].mean(axis=-1, keepdims=True) * np.sqrt(d),
                (h, heads, d))
            layers[i] = dict(layers[i], q_proj=jnp.stack(
                [w[:, :, 0], gate], axis=2).reshape(h, -1))
    return dict(params32, layers=layers)


def _shared_ungated(layer, rows):
    return qwen3_next.swiglu(rows, layer["shared_gate"], layer["shared_up"],
                             layer["shared_down"])


def _not_renormalised(probs, top_k, bias=None, **kw):
    return olmoe.choose_experts(probs, top_k)


FAULTS = {
    "delta_term_dropped": {"patch": (qwen3_next, "gated_delta_scan",
                                     _plain_linear_attention)},
    "beta_dropped": {"patch": (qwen3_next, "gated_delta_scan",
                               _beta_dropped)},
    "state_not_carried": {"patch": (qwen3_next, "gated_delta_scan",
                                    _chunk_reset_scan)},
    "key_heads_swapped": {"patch": (qwen3_next, "gated_delta_scan",
                                    _key_heads_swapped)},
    "gated_norm_order_reversed": {"patch": (qwen3_next, "gated_head_norm",
                                            _gate_then_norm)},
    "one_plus_w_read_as_w": {"patch": (qwen3_next, "znorm",
                                       _weight_not_centred)},
    "rotation_on_every_dim": {"config": {"partial_rotary_factor": 1.0}},
    "attention_gate_per_head": {"params": _per_head_gate},
    "shared_expert_ungated": {"patch": (qwen3_next, "gated_shared_expert",
                                        _shared_ungated)},
    "weights_not_renormalised": {"patch": (qwen3_next, "choose_experts",
                                           _not_renormalised)},
}


@pytest.fixture(scope="module")
def fault_case():
    """Twenty-four rows of 18 to 64 tokens (eight chunks of 8), their weights in
    float32, and the sound program's answers: a fault is read, as a cell's
    ``correct`` reads it, as the largest gap over a sample."""
    t, lengths = 64, tuple(range(64, 16, -2))
    params32 = jax.tree.map(lambda x: x.astype(F32), init_qwen3_next_params(
        jax.random.PRNGKey(8), CFG))
    ids = jax.random.randint(jax.random.PRNGKey(1), (len(lengths), t), 0,
                             CFG.vocab_size)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    return params32, ids, mask, _predict32(params32, ids, mask)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_fails_the_parity_limit(monkeypatch, fault_case,
                                                fault):
    """The comparison has teeth: each fault of a program reads over the
    limit the configuration's cell is held to, against the sound answers,
    where the sound program reads 1e-6 against the reference."""
    params32, ids, mask, sound = fault_case
    plan = FAULTS[fault]
    if "patch" in plan:
        monkeypatch.setattr(*plan["patch"])
    config = dataclasses.replace(CFG, **plan.get("config", {}))
    faulty = plan.get("params", lambda p: p)(params32)
    if fault == "delta_term_dropped":
        # the planted form is NumPy: run it untraced
        with jax.disable_jit(), jax.default_matmul_precision("highest"):
            got = np.asarray(qwen3_next_predict(faulty, ids, mask, config))
    else:
        got = _predict32(faulty, ids, mask, config=config)
    gap = np.abs(got - sound)
    assert gap.max() > LIMIT, (fault, gap)
    # not by one lucky row
    assert (gap > LIMIT / 2).sum() >= 3, (fault, gap)


def test_the_reference_lowered_to_float8_reads_far_over_bfloat16(params, text,
                                                                 want):
    """The control's seam (``benchmarks/tests/qwen3next_control.py`` runs it
    at the published widths, against the cell's limit): every matmul
    operand rounded to float8, everywhere and at one site alone."""
    import ml_dtypes

    def float8(x):
        return x.astype(ml_dtypes.float8_e4m3fn).astype(F32)

    lowered = np.abs(_reference(params, *text, operand=float8) - want).max()
    sound = np.abs(np.asarray(qwen3_next_predict(params, *text, CFG))
                   - want).max()
    assert lowered > 3e-3 and lowered > 5 * sound, (lowered, sound)
    for site in ("routed", "scan"):
        alone = np.abs(_reference(params, *text, operand=float8,
                                  sites=frozenset((site,))) - want).max()
        assert alone > 0.0, site
    with pytest.raises(ValueError, match="sites"):
        _reference(params, *text, operand=float8, sites=frozenset(("ffn",)))


# ------------------------------------------------------- a share of a layer
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """The four chips that would share a TINY layer: each one's routed part
    (its eight experts of the router's 32, the weights normalised over all
    of a token's four) and the shared expert ONCE add up to what one chip
    holding all 32 gives; and a share alone is not the whole."""
    whole = dataclasses.replace(CFG, num_experts=32, expert_offset=0)
    layer = jax.tree.map(
        lambda x: x.astype(F32),
        init_qwen3_next_params(jax.random.PRNGKey(3), whole)["layers"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (96, CFG.hidden_size))
    slots = olmoe.token_slots(jnp.ones((1, 96), bool), None)

    def route(rows):
        return qwen3_next.qwen3_next_route(layer, rows, whole)

    def block(held, offset, shared):
        mine = dict(layer, **{name: layer[name][offset:offset + held]
                              for name in ("gate_proj", "up_proj",
                                           "down_proj")})
        with jax.default_matmul_precision("highest"):
            return np.asarray(olmoe.routed_block(
                mine, x, slots, route, shared=shared, router_width=32,
                expert_offset=offset)[0])

    def shared(rows):
        return qwen3_next.gated_shared_expert(layer, rows)

    uncut = block(32, 0, shared)
    parts = [block(8, 8 * chip, None) for chip in range(4)]
    with jax.default_matmul_precision("highest"):
        once = np.asarray(shared(x))
    np.testing.assert_allclose(sum(parts) + once, uncut, atol=1e-5)
    scale = np.abs(uncut).max()
    assert all(np.abs(part).max() > 0.05 * scale for part in parts)
    assert np.abs(parts[1] + once - uncut).max() > 0.1 * scale


def test_the_router_renormalises_over_all_of_a_tokens_experts():
    layer = init_qwen3_next_params(jax.random.PRNGKey(5), CFG)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(6), (64, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        experts, weights, _ = qwen3_next.qwen3_next_route(layer, x, CFG)
    logits = np.asarray(x, np.float64) @ np.asarray(layer["router"],
                                                    np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chosen = np.argsort(-p, axis=-1)[:, :CFG.num_experts_per_tok]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(chosen, -1))
    picked = np.take_along_axis(p, np.asarray(experts), -1)
    np.testing.assert_allclose(
        np.asarray(weights), picked / picked.sum(-1, keepdims=True),
        atol=1e-5)
    # the router's numbers: most pairs name an expert that lives elsewhere
    held = (np.asarray(experts) >= CFG.expert_offset) & (
        np.asarray(experts) < CFG.expert_offset + CFG.num_experts)
    assert 0.05 < held.mean() < 0.6


# -------------------------------------------- the kernels, through the model
def test_the_core_at_heads_of_256_in_interpret_mode_is_the_xla_form():
    """An ``F`` layer's mixer at two lane tiles a head — per-head norms
    with nonzero weights, 64 of 256 dims rotated, a gate a lane — through
    ``windowed_attention`` interpreted against the XLA form."""
    config = dataclasses.replace(LANE_CFG, full_attention_interval=1,
                                 num_hidden_layers=1)
    assert config.core_refusal(256) is None
    layer = jax.tree.map(
        lambda x: x.astype(F32),
        init_qwen3_next_params(jax.random.PRNGKey(2), config)["layers"][0])
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 256))
    lengths = jnp.array([256, 130])
    mask = jnp.arange(256)[None, :] < lengths[:, None]
    cos, sin = olmoe.rope_tables(256, config.rotary_dim, config.rope_theta)
    with jax.default_matmul_precision("highest"):
        want = qwen3_next.gated_attention(layer, u, mask, lengths, config,
                                          cos, sin)
        got = qwen3_next.gated_attention(layer, u, mask, lengths, config,
                                         cos, sin, use_pallas=True,
                                         kernel_interpret=True)
    real = np.asarray(mask)[..., None]
    scale = float(np.abs(np.asarray(want) * real).max())
    assert scale > 0.1
    np.testing.assert_allclose(np.asarray(got) * real,
                               np.asarray(want) * real, atol=2e-5 * scale)


def test_the_encoder_with_its_kernels_interpreted_is_the_xla_form(
        monkeypatch):
    """LLLF at lane shapes: the delta scan's kernel and, since PR 55, the
    convolution's (q | k | v of 512 | 512 | 1,024, positions first, ``v``
    in the operands' dtype) in the ``L`` layers and the fused core in the
    ``F`` layer, through the interpreter (as are the experts' kernels where
    they take the shape), against the XLA forms."""
    seen = []
    scan, core = (qwen3_next.gated_delta_scan,
                  qwen3_next.windowed_attention)
    conv = falcon_h1.causal_conv_silu
    assert LANE_CFG.conv_refusal(128) is None

    def counted_conv(*a, **kw):
        seen.append(("conv", not kw["positions_last"] and kw["interpret"]))
        return conv(*a, **kw)

    def counted_scan(*a, use_pallas, **kw):
        seen.append(("scan", use_pallas))
        return scan(*a, use_pallas=use_pallas, **kw)

    def counted_core(*a, **kw):
        seen.append(("core", True))
        return core(*a, **kw)

    monkeypatch.setattr(qwen3_next, "gated_delta_scan", counted_scan)
    monkeypatch.setattr(qwen3_next, "windowed_attention", counted_core)
    monkeypatch.setattr(falcon_h1, "causal_conv_silu", counted_conv)
    params32 = jax.tree.map(
        lambda x: x.astype(F32),
        init_qwen3_next_params(jax.random.PRNGKey(9), LANE_CFG))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 512)
    mask = jnp.arange(128)[None, :] < jnp.array([128, 70])[:, None]
    with jax.default_matmul_precision("highest"):
        want = qwen3_next_predict(params32, ids, mask, LANE_CFG)
        assert seen == [("scan", False)] * 3
        got = qwen3_next_predict(params32, ids, mask, LANE_CFG,
                                 use_pallas=True, kernel_interpret=True)
    assert seen[3:] == [("conv", True), ("scan", True)] * 3 + [
        ("core", True)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ------------------------------------------------- the configuration's class
def test_published_config_is_the_default_and_the_kinds_follow_the_interval():
    config = Qwen3NextConfig()
    published = json.loads(
        (ROOT / "benchmarks/configs/qwen3-next-80b-a3b-s2048.json"
         ).read_text())["published"]
    for key, value in published.items():
        got = getattr(config, key)
        assert got == (tuple(value) if isinstance(value, list) else value), key
    assert config.layer_kinds == ("L", "L", "L", "F") * 12
    assert (config.key_dim, config.value_dim, config.conv_dim,
            config.rotary_dim) == (2048, 4096, 8192, 64)
    assert (config.num_sparse_layers, config.num_delta_layers) == (48, 36)
    assert config.core_refusal(2048) is None
    assert config.scan_refusal(2048) is None
    assert config.conv_refusal(2048) is None
    assert config.conv_refusal(2048) == conv_refusal(
        2048, (2048, 2048, 4096), 4)
    cut = dataclasses.replace(config, num_hidden_layers=6, num_experts=256)
    assert "".join(cut.layer_kinds) == "LLLFLL"
    assert "".join(CFG.layer_kinds) == "LLLFL"


@pytest.mark.parametrize("change,message", [
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"mlp_only_layers": (0,)}, "mlp_only_layers"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"use_sliding_window": True}, "use_sliding_window"),
    ({"num_experts": 600}, "experts 0..600"),
    ({"expert_offset": 300, "num_experts": 256}, "experts 300..556"),
    ({"linear_num_value_heads": 24}, "heads must divide"),
    ({"partial_rotary_factor": 0.3}, "partial_rotary_factor"),
])
def test_config_refuses_what_the_equations_cannot_hold(change, message):
    with pytest.raises(ValueError, match=re.escape(message)):
        dataclasses.replace(Qwen3NextConfig(), **change)


def test_the_refusals_name_their_shapes():
    assert "head_dim 32" in CFG.core_refusal(128)
    assert "key_dim 16" in CFG.scan_refusal(128)
    assert "parts (32, 32, 64) from channel 0" in CFG.conv_refusal(128)
    assert "seq_len 96" in LANE_CFG.conv_refusal(96)
    assert windowed_refusal(2048, 256, 16, 2, None, head_norm=True) is None
    assert windowed_refusal(2048, 128, 16, 2, None, head_norm=True) is None
    # two lane tiles a head come with the per-head norm alone
    assert "head_dim 256" in windowed_refusal(2048, 256, 16, 2, None)
    assert "window 512" in windowed_refusal(2048, 256, 16, 2, 512,
                                            head_norm=True)
    assert "seq_len 100" in windowed_refusal(100, 256, 16, 2, None,
                                             head_norm=True)
    wide = dataclasses.replace(Qwen3NextConfig(), partial_rotary_factor=0.75)
    assert "192 rotated dims" in wide.core_refusal(2048)


def test_a_layer_holds_what_its_kind_needs_and_nothing_else(params):
    linear, full = params["layers"][0], params["layers"][3]
    sparse = {"router", "gate_proj", "up_proj", "down_proj", "shared_gate",
              "shared_up", "shared_down", "shared_expert_gate",
              "input_layernorm", "post_attention_layernorm"}
    assert set(linear) == sparse | {
        "in_proj_qkvz", "in_proj_ba", "conv_weight", "dt_bias", "A_log",
        "delta_norm", "out_proj"}
    assert set(full) == sparse | {"q_proj", "k_proj", "v_proj", "q_norm",
                                  "k_norm", "o_proj"}
    assert "conv_bias" not in linear
    assert linear["in_proj_qkvz"].shape == (128, 2 * 32 + 2 * 64)
    assert linear["in_proj_ba"].shape == (128, 8)
    assert linear["conv_weight"].shape == (4, CFG.conv_dim)
    assert linear["conv_weight"].dtype == linear["A_log"].dtype == F32
    assert full["q_proj"].shape == (128, 2 * 8 * 32)
    assert full["q_norm"].shape == full["k_norm"].shape == (32,)
    assert linear["gate_proj"].shape == (8, 128, 64)
    assert linear["router"].shape == (128, 32)
    assert linear["shared_expert_gate"].shape == (128, 1)
    # zero-centred weights are drawn off zero, the gated norm's plain ones
    assert float(jnp.abs(linear["input_layernorm"]).max()) > 0.0
    assert float(jnp.abs(params["norm"]).max()) > 0.0
    np.testing.assert_array_equal(np.asarray(linear["delta_norm"]), 1.0)
    # a head's decay lies between ~0.9 and ~0.999 a token at a = 0
    decay = jnp.exp(-jnp.exp(linear["A_log"])
                    * jax.nn.softplus(linear["dt_bias"]))
    assert 0.88 < float(decay.min()) and float(decay.max()) < 0.9995


# --------------------------------------------------------- through the scorer
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
    scorer.models = scorer.models.replace(bert=jax.tree.map(
        lambda x: x.astype(F32), scorer.models.bert))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return scorer, gen


@pytest.mark.parametrize("words,rung", [(3, 3072), (40, 4096)],
                         ids=["three_quarters", "every_slot"])
def test_the_scorers_packed_path_matches_the_reference_at_each_rung(
        rung_scorer, words, rung):
    """128 rows x 32 positions is the smallest launch with two rungs: short
    texts take the narrow one, full rows every slot; at both, on float32
    weights, the text column the served packed program returns is the
    reference's on the batch the scorer assembled, and the launch is
    counted: pairs over the five layers, the held ones from the device,
    chunks over the four ``L`` layers, one causal layer's visible pairs."""
    from realtime_fraud_detection_tpu.scoring import text_split

    scorer, gen = rung_scorer
    assert text_split.capacities(128 * 32) == (3072, 4096)
    recs = gen.generate_batch(128)
    for r in recs:
        r["description"] = " ".join(["x"] * words)
    batch = scorer.assemble(recs)
    pending = scorer.dispatch(recs)
    results = scorer.finalize(pending)
    c = pending.counters
    assert c["expert_token_slots"] == rung
    assert c["compact_batches"] == int(rung == 3072)
    want = _reference(scorer.models.bert, batch.token_ids, batch.token_mask)
    got = np.array([r["model_predictions"]["bert_text"] for r in results])
    np.testing.assert_allclose(got, want[:128], atol=2e-5)
    assert c["routed_pairs"] == c["real_tokens"] * 4 * 5
    assert 0 < c["expert_rows"] < c["routed_pairs"]
    assert c["expert_rows"] <= c["expert_peak_rows"]
    assert c["dispatch_rows"] == rung * 4 * 5
    # a chunk of 8 over rows of 32 positions: the launch's slots / 8
    assert c["delta_chunks"] == 128 * 32 // 8 * 4
    assert c["ssm_chunks"] == 0
    lengths = np.count_nonzero(np.asarray(batch.token_mask), axis=1)
    assert c["attn_visible_pairs_full"] == int(
        (lengths * (lengths + 1) // 2).sum())
    assert c["attn_visible_pairs_sliding"] == 0


def test_one_row_of_the_seam_is_routed_and_recurrent_at_once(rung_scorer):
    from realtime_fraud_detection_tpu.models import text_encoder
    from realtime_fraud_detection_tpu.scoring import pipeline

    row = pipeline.text_encoder(CFG)
    assert row is qwen3_next.TEXT_ENCODER
    assert [site.name for site in row.sites] == [
        "attention", "expert_gate_up", "expert_dispatch", "expert_combine",
        "delta_scan", "causal_conv"]
    assert row.sites[-1].refusal(CFG, 128, 128) == CFG.conv_refusal(128)
    assert pipeline.text_layers(CFG) == 5
    assert row.capacities(4096) == (3072, 4096)
    assert Qwen3NextConfig in pipeline.TextConfig.__args__
    # the seam reads ONE expert's width, not the dense layer's
    assert (CFG.intermediate_size, text_encoder.expert_width(CFG)) == (256,
                                                                       64)
    assert text_encoder.expert_width(Qwen3NextConfig()) == 512
    assert text_encoder.expert_width(olmoe.TINY_OLMOE) \
        == olmoe.TINY_OLMOE.intermediate_size
    assert "delta_chunks" in text_encoder.LAUNCH_COUNTERS
    scorer, gen = rung_scorer
    before = scorer.kernel_snapshot()
    scorer.finalize(scorer.dispatch(gen.generate_batch(3)))
    snap = scorer.kernel_snapshot()
    # a CPU mesh is never asked for its kernels: a fallback at every site
    for site in ("attention", "delta_scan", "causal_conv", "expert_gate_up",
                 "expert_dispatch", "expert_combine"):
        assert snap["fallback"][site] == before["fallback"][site] + 1, site
        assert snap["dispatch"][site] == 0
    assert "head_dim 32" in snap["refused"]["attention"]
    assert "key_dim 16" in snap["refused"]["delta_scan"]
    assert "parts (32, 32, 64)" in snap["refused"]["causal_conv"]
    # at the published shapes every site of a launch of 8 x 2,048 holds its
    # kernel but the way out, which keeps XLA's gather at this size
    full = dataclasses.replace(Qwen3NextConfig(), num_hidden_layers=6,
                               num_experts=256)
    refusals = {site.name: site.refusal(full, 2048, 12288)
                for site in row.sites}
    assert refusals.pop("expert_dispatch") is not None
    assert set(refusals.values()) == {None}
    tiles = row.build_ids(full, [(8, 2048, 12288), (8, 2048, 16384)])
    assert tiles["tiles"].startswith("12288:") and ",16384:" in tiles["tiles"]


def test_the_stream_job_sums_pairs_and_chunks_by_kind():
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
    c = job.counters
    assert c["errors"] == 0 and c["scored"] == 64
    assert c["routed_pairs"] == c["real_tokens"] * 4 * 5 > 0
    assert 0 < c["expert_rows"] < c["routed_pairs"]
    assert c["delta_chunks"] == c["token_slots"] // 8 * 4 > 0
    assert c["ssm_chunks"] == 0
    assert c["compact_batches"] == c["batches"] > 0
    assert c["attn_visible_pairs_full"] >= c["real_tokens"]


def test_the_planes_written_for_distilbert_refuse_it_by_name():
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        QuantSettings,
    )

    config = Config()
    config.quant = QuantSettings(enabled=True, bert_weights="int8")
    with pytest.raises(ValueError, match="Qwen3NextConfig"):
        _scorer(config=config)
    with pytest.raises(ValueError, match="Qwen3NextConfig"):
        _scorer(mesh=build_mesh(devices=jax.devices()[:2]))


# ------------------------------ the other encoders' programs are left alone
@pytest.mark.parametrize("encoder", ["distilbert", "olmoe", "zaya1",
                                     "laguna", "joyai", "falconh1",
                                     "nemotron3"])
def test_the_seven_other_encoders_trace_none_of_what_this_one_added(
        monkeypatch, encoder):
    """Their packed programs at TINY lower to the same text with this
    encoder, the delta scan and the shared expert's gate poisoned as with
    them whole. (Against the parent commit their optimised HLO is
    digest-equal with the source metadata dropped: PERF.md, PR 54.)"""
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.models.falcon_h1 import TINY_FALCON_H1
    from realtime_fraud_detection_tpu.models.joyai import TINY_JOYAI
    from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA
    from realtime_fraud_detection_tpu.models.nemotron_h import TINY_NEMOTRON_H
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA

    scan_module = sys.modules["realtime_fraud_detection_tpu.ops.delta_scan"]
    config = {"distilbert": TINY_CONFIG, "olmoe": TINY_OLMOE,
              "zaya1": TINY_ZAYA, "laguna": TINY_LAGUNA,
              "joyai": TINY_JOYAI, "falconh1": TINY_FALCON_H1,
              "nemotron3": TINY_NEMOTRON_H}[encoder]
    whole = _lowered(config).as_text()

    def poisoned(*a, **kw):
        raise AssertionError("traced into another encoder's program")

    for name in ("qwen3_next_predict", "qwen3_next_encode",
                 "qwen3_next_layer", "delta_mixer", "gated_attention",
                 "qwen3_next_route", "gated_shared_expert", "gated_head_norm",
                 "znorm", "gated_delta_scan"):
        monkeypatch.setattr(qwen3_next, name, poisoned)
    for name in ("gated_delta_scan", "_delta_xla", "_delta_pallas"):
        monkeypatch.setattr(scan_module, name, poisoned)
    assert _lowered(config).as_text() == whole


@pytest.mark.parametrize("encoder", ["falconh1", "nemotron3"])
def test_the_shared_convolution_leaves_the_mamba_programs_as_they_were(
        monkeypatch, encoder):
    """``falcon_h1.causal_conv`` now takes a mixer with no bias too: the
    two encoders that hand it one compile, instruction for instruction, to
    the program with the parent's function in its place."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from realtime_fraud_detection_tpu.models.falcon_h1 import TINY_FALCON_H1
    from realtime_fraud_detection_tpu.models.nemotron_h import TINY_NEMOTRON_H

    config = {"falconh1": TINY_FALCON_H1,
              "nemotron3": TINY_NEMOTRON_H}[encoder]

    def compiled():
        text = _lowered(config).compile().as_text()
        start = re.search(r"^(?:ENTRY )?%\S+ \(", text, re.M).start()
        return re.sub(r", metadata=\{[^}]*\}", "", text[start:])

    def parents_conv(x, taps, bias):
        k, t = taps.shape[0], x.shape[1]
        padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
        return bias + sum(padded[:, i:i + t] * taps[i] for i in range(k))

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        now = compiled()
        monkeypatch.setattr(falcon_h1, "causal_conv", parents_conv)
        assert compiled() == now
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def test_a_tap_shifted_in_the_kernels_program_is_caught(monkeypatch):
    """Falcon-H1's planted fault ``conv_shifted_a_tap`` where the LANE
    program computes its ``L`` layers' convolutions — in the kernel's
    input, positions first: the program asked for its kernels reads over
    the cell's limit against the sound one."""
    params = jax.tree.map(lambda x: x.astype(F32), init_qwen3_next_params(
        jax.random.PRNGKey(9), LANE_CFG))
    t, lengths = 128, (128, 97, 60, 110)
    ids = jax.random.randint(jax.random.PRNGKey(4), (len(lengths), t), 0, 512)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    kernels = dict(use_pallas=True, kernel_interpret=True)
    sound = _predict32(params, ids, mask, config=LANE_CFG, **kernels)
    whole = falcon_h1.causal_conv_silu

    def shifted(x, *a, positions_last, **kw):
        assert not positions_last
        return whole(jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1], *a,
                     positions_last=positions_last, **kw)

    monkeypatch.setattr(falcon_h1, "causal_conv_silu", shifted)
    gap = np.abs(_predict32(params, ids, mask, config=LANE_CFG, **kernels)
                 - sound)
    assert gap.max() > LIMIT, gap
    assert (gap > LIMIT / 2).sum() >= 2, gap
