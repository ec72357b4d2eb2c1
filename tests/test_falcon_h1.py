"""The Falcon-H1 text encoder (models/falcon_h1.py) and its scan
(ops/ssd_scan.py): the program against the benchmark's plain reference
(``benchmarks/configs/falconh1_reference.py``: the recurrence a position at a
time, a materialised softmax, no line shared with the program) alone and
through the scorer's packed path; the scan's XLA form against the sequential
recurrence, its Pallas form in interpret mode against the XLA form, a
sequence cut in two; planted faults that each fail the configuration's
parity limit; the refusals by name; and the seam it enters the scorer
through, which leaves the five other encoders' programs as they were."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import falcon_h1
from realtime_fraud_detection_tpu.models.falcon_h1 import (
    TINY_FALCON_H1,
    FalconH1Config,
    causal_conv,
    falcon_h1_encode,
    falcon_h1_predict,
    init_falcon_h1_params,
    mup_vector,
)
from realtime_fraud_detection_tpu.ops.attention import windowed_refusal
from realtime_fraud_detection_tpu.ops.causal_conv import conv_refusal
from realtime_fraud_detection_tpu.ops.ssd_scan import ssd_refusal, ssd_scan

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from benchmarks.harness import spec  # noqa: E402

F32 = jnp.float32
# hidden 128; two layers; a mixer of 4 heads of 16 over 2 groups of a
# 32-wide state, chunks of 16; 10 query heads over 2 key-value heads
CFG = TINY_FALCON_H1
REFERENCE = spec.reference("falconh1_reference")
FILE = json.loads(
    (ROOT / "benchmarks/configs/falcon-h1-34b-s2048.json").read_text())
# what a run on the chip is held to: a planted fault has to read over it
LIMIT = FILE["parity_atol"]["branch:bert_text"]
T = 56                                      # three chunks and a half
LENGTHS = (56, 11, 1, 37, 49)
# heads and chunks of 128 and a state of whole lane tiles: what the two
# kernels take (TINY's 16 is declined by name)
LANE_CFG = FalconH1Config(
    vocab_size=512, hidden_size=256, intermediate_size=256,
    num_hidden_layers=2, num_attention_heads=10, num_key_value_heads=2,
    mamba_n_heads=16, mamba_d_ssm=2048, mamba_d_state=128)


def reference_cfg(config: FalconH1Config) -> dict:
    """The keys ``falconh1_reference.py`` reads, for a ``FalconH1Config``:
    what ``benchmarks/configs/falconh1_builder.falconh1_config`` does,
    backwards."""
    return dataclasses.asdict(config)


@pytest.fixture(scope="module")
def params():
    return init_falcon_h1_params(jax.random.PRNGKey(7), CFG)


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
            lambda p, i, m: falcon_h1_predict(p, i, m, config, **kw))(
            params32, ids, mask))


def _reference(params, ids, mask, config=CFG, **kw):
    return REFERENCE.text_branch(jax.device_get(params), np.asarray(ids),
                                 np.asarray(mask), reference_cfg(config),
                                 **kw)


@pytest.fixture(scope="module")
def want(params, text):
    return _reference(params, *text)


# ------------------------------------------- program against the reference
def test_float32_program_matches_the_plain_reference(params32, text, want):
    got = _predict32(params32, *text)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    assert want.std() > 0.01


def test_bfloat16_program_is_near_the_reference(params, text, want):
    got = falcon_h1_predict(params, *text, CFG)
    assert np.abs(np.asarray(got) - want).max() < 3e-3 < LIMIT


def test_no_path_leaves_a_layers_update(params, text):
    """The weights are drawn so that no path leaves ``correct``'s sight
    (``init_falcon_h1_params``): at a row's last real token the mixer's,
    attention's and the MLP's update each weigh in the sum of the three.
    (That each is over a TENTH is a reading at the cell's own lengths —
    ``o_proj_gain`` is set for contexts averaged over ~1,200 keys, and over
    the few dozen keys of these rows attention is the largest of the three:
    ``benchmarks/tests/falconh1_control.py --shares``.)"""
    _, norms = _reference(params, *text, parts=True)
    assert norms.shape == (CFG.num_hidden_layers, 3, len(LENGTHS))
    long_rows = [i for i, n in enumerate(LENGTHS) if n > 30]
    shares = (norms / norms.sum(axis=1, keepdims=True))[:, :, long_rows]
    assert shares.min() > 0.05, shares.min(axis=-1)


def test_padding_leaves_a_rows_answer_bit_equal(params, text):
    """The text is right-padded and the encoder causal: whatever stands in
    a row's padded positions, and however many there are, its answer is
    the same to the bit (nothing masks the mixer: nothing needs to)."""
    ids, mask = text
    other = jnp.where(mask, ids, (ids * 7 + 3) % CFG.vocab_size)
    assert bool(jnp.any(other != ids))
    fn = jax.jit(lambda i: falcon_h1_predict(params, i, mask, CFG))
    np.testing.assert_array_equal(np.asarray(fn(ids)), np.asarray(fn(other)))


def test_predict_is_the_softmax_of_the_head(params32, text):
    from realtime_fraud_detection_tpu.models.olmoe import last_token_logits

    ids, mask = text
    hidden = falcon_h1_encode(params32, ids, mask, CFG)
    assert hidden.shape == (len(LENGTHS), T, CFG.hidden_size)
    logits = last_token_logits(params32, hidden, mask, CFG.rms_norm_eps)
    np.testing.assert_allclose(
        np.asarray(falcon_h1_predict(params32, ids, mask, CFG)),
        np.asarray(jax.nn.softmax(logits, -1)[:, 1]), atol=1e-6)


def test_the_weights_bring_every_multiplied_activation_to_unit_size(params):
    """``init_falcon_h1_params``: each matrix at 1 / (its multiplier x
    sqrt(fan-in)), ``W_in`` by segment."""
    layer = params["layers"][0]
    h = CFG.hidden_size

    def std(x):
        return float(jnp.std(x.astype(F32)))

    assert abs(std(params["embed_tokens"]) * CFG.embedding_multiplier
               - 1.0) < 0.02
    start = 0
    for width, mult in CFG.mup_segments():
        got = std(layer["in_proj"][:, start:start + width])
        want = 1.0 / (CFG.ssm_in_multiplier * mult * h ** 0.5)
        assert abs(got / want - 1.0) < 0.15, (start, got, want)
        start += width
    assert start == CFG.in_proj_dim == layer["in_proj"].shape[1]
    assert abs(std(layer["k_proj"]) * CFG.key_multiplier * h ** 0.5
               - 1.0) < 0.05
    assert abs(std(layer["mlp_gate"]) * CFG.mlp_multipliers[0] * h ** 0.5
               - 1.0) < 0.05
    assert float(jnp.min(-jnp.exp(layer["A_log"]))) >= -16.0
    dt = jax.nn.softplus(layer["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.01
    np.testing.assert_array_equal(np.asarray(layer["D"]), 1.0)


def test_the_mup_vector_is_the_five_multipliers_by_segment():
    full = FalconH1Config()
    m = mup_vector(full)
    assert m.shape == (9248,) == (full.in_proj_dim,)
    assert full.conv_dim == 5120
    bounds = np.cumsum([0, 4096, 4096, 512, 512, 32])
    for lo, hi, mult in zip(bounds[:-1], bounds[1:], full.ssm_multipliers):
        assert (m[lo:hi] == np.float32(mult)).all()


# ------------------------------------------------------------ planted faults
def _chunk_reset_scan(x, dt, a, b_in, c_in, d, *, chunk, **kw):
    """The state not carried across a chunk boundary."""
    parts = [ssd_scan(x[:, s:s + chunk], dt[:, s:s + chunk], a,
                      b_in[:, s:s + chunk], c_in[:, s:s + chunk], d,
                      chunk=chunk)[0] for s in range(0, x.shape[1], chunk)]
    return jnp.concatenate(parts, axis=1), None


def _no_skip_scan(x, dt, a, b_in, c_in, d, **kw):
    """``D`` dropped."""
    return ssd_scan(x, dt, a, b_in, c_in, jnp.zeros_like(d), **kw)


def _shifted_conv(x, taps, bias):
    """The convolution shifted by one tap: position t sees t-4..t-1."""
    return causal_conv(jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1], taps,
                       bias)


def _swapped_mup(config):
    """The µP vector's segments swapped: x's multiplier on C and C's on x.
    (Not every swap can be seen: z's and dt's are the same number, and B
    and C meet only in the product ``C . B``, so their swap moves nothing
    but where each one's SiLU sits: 3e-3 here.)"""
    z, x, b, c, dt = config.ssm_multipliers
    return mup_vector(dataclasses.replace(
        config, ssm_multipliers=(z, c, b, x, dt)))


FAULTS = {
    "state_not_carried": {"patch": ("ssd_scan", _chunk_reset_scan)},
    "conv_shifted_a_tap": {"patch": ("causal_conv", _shifted_conv)},
    "D_dropped": {"patch": ("ssd_scan", _no_skip_scan)},
    "mup_segments_swapped": {"patch": ("mup_vector", _swapped_mup)},
    "key_multiplier_left_out": {"config": {"key_multiplier": 1.0}},
    "attention_not_summed": {"config": {"attention_out_multiplier": 0.0}},
    "mixer_not_summed": {"config": {"ssm_out_multiplier": 0.0}},
}


@pytest.fixture(scope="module")
def fault_case():
    """Twelve rows of 46 to 112 tokens (three to seven chunks of 16), their
    weights in float32, and the sound program's answers: a fault is read,
    as a cell's ``correct`` reads it, as the largest gap over a sample."""
    t, lengths = 112, tuple(range(112, 40, -6))
    params32 = jax.tree.map(lambda x: x.astype(F32), init_falcon_h1_params(
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
    where the sound program reads 1e-6 against the reference. The two
    faintest are the state not carried (2e-2: what a chunk boundary loses
    is what the state held from before it, a slowly varying part of ``y``
    that the grouped norm partly takes back) and the swapped µP segments
    (4e-2); every other reads 0.05-0.2."""
    params32, ids, mask, sound = fault_case
    plan = FAULTS[fault]
    if "patch" in plan:
        monkeypatch.setattr(falcon_h1, *plan["patch"])
    config = dataclasses.replace(CFG, **plan.get("config", {}))
    gap = np.abs(_predict32(params32, ids, mask, config=config) - sound)
    assert gap.max() > LIMIT, (fault, gap)
    # not by one lucky row
    assert (gap > LIMIT / 2).sum() >= 3, (fault, gap)


def test_the_reference_lowered_to_float8_reads_far_over_bfloat16(params, text,
                                                                 want):
    """The control's seam (``benchmarks/tests/falconh1_control.py`` runs it
    at the published widths, against the cell's limit): every matmul
    operand rounded to float8. At TINY it reads over what the bfloat16
    program is held under above, and many times what that program reads."""
    import ml_dtypes

    def float8(x):
        return x.astype(ml_dtypes.float8_e4m3fn).astype(F32)

    lowered = np.abs(_reference(params, *text, operand=float8) - want).max()
    sound = np.abs(np.asarray(falcon_h1_predict(params, *text, CFG))
                   - want).max()
    assert lowered > 3e-3 and lowered > 8 * sound, (lowered, sound)


# ------------------------------------------------------------------- the scan
def _sequential(x, dt, a, b_in, c_in, d, state=None):
    """The recurrence, a position and a head at a time, in float64."""
    x, dt, a, b_in, c_in, d = (np.asarray(v, np.float64)
                               for v in (x, dt, a, b_in, c_in, d))
    b, t, h, p = x.shape
    g, n = b_in.shape[2:]
    s = np.zeros((b, h, n, p)) if state is None else np.array(state,
                                                              np.float64)
    y = np.zeros((b, t, h, p))
    for i in range(t):
        for j in range(h):
            k = j // (h // g)
            s[:, j] = (np.exp(dt[:, i, j] * a[j])[:, None, None] * s[:, j]
                       + dt[:, i, j][:, None, None] * b_in[:, i, k][:, :, None]
                       * x[:, i, j][:, None, :])
            y[:, i, j] = (np.einsum("bnp,bn->bp", s[:, j], c_in[:, i, k])
                          + d[j] * x[:, i, j])
    return y, s


def _scan_inputs(b, t, h, p, g, n, seed=0, dtype=np.float32):
    r = np.random.default_rng(seed)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (b, t, h))
                + r.standard_normal((b, t, h)))
    return (jnp.asarray(r.standard_normal((b, t, h, p)), dtype),
            jnp.asarray(dt, F32),
            jnp.asarray(-r.uniform(1, 16, (h,)), F32),
            jnp.asarray(r.standard_normal((b, t, g, n)), dtype),
            jnp.asarray(r.standard_normal((b, t, g, n)), dtype),
            jnp.asarray(r.standard_normal((h,)), F32))


@pytest.mark.parametrize("t,chunk", [(48, 16), (40, 16), (16, 16), (7, 4),
                                     (33, 32)])
def test_the_chunked_form_is_the_sequential_recurrence(t, chunk):
    """Whole chunks, a ragged last chunk (padded with steps of dt 0), one
    chunk alone: the XLA form against the recurrence a position at a
    time."""
    args = _scan_inputs(2, t, 4, 8, 2, 16, seed=t)
    y, final = ssd_scan(*args, chunk=chunk)
    want_y, want_final = _sequential(*args)
    assert y.shape == (2, t, 4, 8) and final.shape == (2, 4, 16, 8)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), want_final, atol=2e-6)


def test_the_chunked_form_takes_a_state_in(params):
    args = _scan_inputs(2, 32, 4, 8, 2, 16, seed=3)
    state = jnp.asarray(np.random.default_rng(9).standard_normal(
        (2, 4, 16, 8)), F32)
    y, final = ssd_scan(*args, chunk=16, initial_state=state)
    want_y, want_final = _sequential(*args, state=state)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(final), want_final, atol=2e-6)


@pytest.mark.parametrize("cut", [16, 24, 5])
def test_a_sequence_cut_in_two_is_the_whole(cut):
    """The first part's ``final_state`` handed on as the second's
    ``initial_state``: on a chunk boundary, and off one."""
    x, dt, a, b_in, c_in, d = _scan_inputs(2, 48, 4, 8, 2, 16, seed=cut)
    whole_y, whole_final = ssd_scan(x, dt, a, b_in, c_in, d, chunk=16)
    y0, s0 = ssd_scan(x[:, :cut], dt[:, :cut], a, b_in[:, :cut],
                      c_in[:, :cut], d, chunk=16)
    y1, s1 = ssd_scan(x[:, cut:], dt[:, cut:], a, b_in[:, cut:],
                      c_in[:, cut:], d, chunk=16, initial_state=s0)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y0, y1], 1)),
                               np.asarray(whole_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(whole_final),
                               atol=2e-6)


@pytest.mark.parametrize("carried", [False, True], ids=["from_zero",
                                                        "state_in"])
@pytest.mark.parametrize("dtype,atol", [(np.float32, 2e-4),
                                        (jnp.bfloat16, 0.5)],
                         ids=["f32", "bf16"])
def test_the_kernel_in_interpret_mode_is_the_xla_form(dtype, atol, carried):
    """Three chunks of 128, two groups of eight heads of 128 over a state
    of 128: the Pallas form through the interpreter against the XLA form
    (bfloat16 operands: both round the masked scores and the state once,
    in another order, on outputs of size ~100)."""
    args = _scan_inputs(2, 384, 16, 128, 2, 128, seed=1, dtype=dtype)
    assert ssd_refusal(384, 128, 128, 128, 16, 2) is None
    state = jnp.asarray(np.random.default_rng(5).standard_normal(
        (2, 16, 128, 128)), F32) if carried else None
    want_y, want_final = jax.jit(
        lambda *a: ssd_scan(*a, chunk=128, initial_state=state))(*args)
    y, final = ssd_scan(*args, chunk=128, initial_state=state,
                        use_pallas=True, interpret=True)
    assert float(jnp.abs(want_y).max()) > 20.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=atol)
    np.testing.assert_allclose(np.asarray(final), np.asarray(want_final),
                               atol=atol / 4)


def test_the_kernel_cut_in_two_is_the_whole():
    x, dt, a, b_in, c_in, d = _scan_inputs(1, 256, 8, 128, 1, 128, seed=2)
    kw = dict(chunk=128, use_pallas=True, interpret=True)
    whole_y, whole_final = ssd_scan(x, dt, a, b_in, c_in, d, **kw)
    y0, s0 = ssd_scan(x[:, :128], dt[:, :128], a, b_in[:, :128],
                      c_in[:, :128], d, **kw)
    y1, s1 = ssd_scan(x[:, 128:], dt[:, 128:], a, b_in[:, 128:],
                      c_in[:, 128:], d, initial_state=s0, **kw)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y0, y1], 1)),
                               np.asarray(whole_y), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(whole_final),
                               atol=2e-5)


@pytest.mark.parametrize("shape,says", [
    ((2048, 128, 256, 128, 32, 2), None),
    ((2048, 64, 256, 128, 32, 2), None),     # two heads a lane tile
    ((2048, 32, 256, 128, 32, 2), "head_dim 32"),
    ((2048, 128, 256, 256, 32, 2), "chunk 256"),
    ((2048, 128, 64, 128, 32, 2), "d_state 64"),
    ((2000, 128, 256, 128, 32, 2), "seq_len 2000"),
    ((64, 128, 256, 128, 32, 2), "seq_len 64"),
    ((2048, 128, 256, 128, 12, 2), "12 heads in 2 groups"),
    ((2048, 128, 256, 128, 32, 3), "32 heads in 3 groups"),
])
def test_the_scans_refusal_names_its_reason(shape, says):
    refusal = ssd_refusal(*shape)
    assert (refusal is None) if says is None else (says in refusal), refusal


def test_a_declined_shape_asked_for_the_kernel_runs_the_xla_form():
    args = _scan_inputs(1, 32, 4, 8, 2, 16)
    assert ssd_refusal(32, 8, 16, 16, 4, 2)
    y, _ = ssd_scan(*args, chunk=16, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(ssd_scan(*args, chunk=16)[0]))


def test_the_published_shapes_hold_both_kernels_and_tiny_names_why_not():
    full = FalconH1Config()
    assert full.core_refusal(2048) is None is full.scan_refusal(2048)
    # the convolution's kernel over x | B | C out of W_in's 9,248, from
    # channel 4,096 on (TINY's parts of 64 are no lane tile)
    assert full.conv_refusal(2048) is None
    assert full.conv_refusal(2048) == conv_refusal(
        2048, (4096, 512, 512), 4, offset=4096)
    assert "seq_len 100" in full.conv_refusal(100)
    assert "parts (64, 64, 64) from channel 64" in CFG.conv_refusal(2048)
    # five query heads a key-value head: a count no other encoder has
    assert full.num_attention_heads // full.num_key_value_heads == 5
    assert windowed_refusal(2048, 128, 20, 4, None) is None
    assert "seq_len 100" in full.core_refusal(100)
    assert "seq_len 100" in full.scan_refusal(100)
    assert "head_dim 16" in CFG.core_refusal(2048)
    assert "head_dim 16, chunk 16" in CFG.scan_refusal(2048)
    assert "20 query heads do not divide into 3" in windowed_refusal(
        2048, 128, 20, 3, None)


# ----------------------------------------- the whole encoder at lane shapes
def test_the_encoder_with_both_kernels_interpreted_is_the_xla_form(
        monkeypatch):
    """At ``head_dim`` 128 and chunks of 128 the program asked for its
    kernels holds the fused causal core, the scan's kernel AND the
    convolution's (since PR 55: x | B | C of 2,048 | 256 | 256 read out of
    ``W_in``'s 4,624-wide result positions last) in every layer; through
    the interpreter it answers what the XLA forms answer."""
    params = init_falcon_h1_params(jax.random.PRNGKey(3), LANE_CFG)
    t, lengths = 256, (256, 130, 97)
    assert LANE_CFG.core_refusal(t) is None is LANE_CFG.scan_refusal(t)
    assert LANE_CFG.conv_refusal(t) is None
    ids = jax.random.randint(jax.random.PRNGKey(4), (3, t), 0, 512)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    plain = falcon_h1_predict(params, ids, mask, LANE_CFG)
    asked = {"scan": 0, "core": 0, "conv": 0}

    def scan(*a, **kw):
        asked["scan"] += bool(kw["use_pallas"] and kw["interpret"])
        return ssd_scan(*a, **kw)

    whole_conv = falcon_h1.causal_conv_silu

    def conv(*a, **kw):
        asked["conv"] += bool(kw["interpret"] and kw["positions_last"])
        return whole_conv(*a, **kw)

    def no_xla_conv(*a, **kw):
        raise AssertionError("the XLA convolution in a program that holds "
                             "the kernel")

    whole_core = falcon_h1.windowed_attention

    def core(*a, **kw):
        asked["core"] += bool(kw["interpret"])
        return whole_core(*a, **kw)

    monkeypatch.setattr(falcon_h1, "ssd_scan", scan)
    monkeypatch.setattr(falcon_h1, "windowed_attention", core)
    monkeypatch.setattr(falcon_h1, "causal_conv_silu", conv)
    monkeypatch.setattr(falcon_h1, "causal_conv", no_xla_conv)
    fused = np.asarray(jax.jit(lambda i, m: falcon_h1_predict(
        params, i, m, LANE_CFG, use_pallas=True, kernel_interpret=True))(
        ids, mask))
    assert asked == dict.fromkeys(("scan", "core", "conv"),
                                  LANE_CFG.num_hidden_layers)
    assert np.abs(fused - np.asarray(plain)).max() < 3e-3
    want = _reference(params, ids, mask, LANE_CFG)
    assert np.abs(fused - want).max() < LIMIT / 2


# ----------------------------------------------------------------- the seam
def test_the_config_refuses_by_value_what_the_equations_do_not_hold():
    for key, value in [("attention_bias", True), ("mamba_proj_bias", True),
                       ("mlp_bias", True), ("projectors_bias", True),
                       ("mamba_conv_bias", False), ("mamba_rms_norm", False),
                       ("mamba_norm_before_gate", True),
                       ("hidden_act", "gelu"), ("rope_scaling", {"f": 2}),
                       ("attn_layer_indices", (0, 2))]:
        with pytest.raises(ValueError, match=key):
            FalconH1Config(**{key: value})
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        FalconH1Config(mamba_d_ssm=4000)
    with pytest.raises(ValueError, match="divide"):
        FalconH1Config(num_key_value_heads=3)


def test_the_class_picks_the_encoder_and_answers_the_seams_questions():
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.scoring import pipeline

    row = pipeline.text_encoder(CFG)
    assert row is pipeline.TEXT_ENCODERS[FalconH1Config] \
        is falcon_h1.TEXT_ENCODER
    assert row.capacities(4096) is None and row.narrow_width(CFG) is None
    assert row.init is init_falcon_h1_params and not row.planes
    attention, scan, conv = row.sites
    assert (attention.name, scan.name, conv.name) == (
        "attention", "ssm_scan", "causal_conv")
    assert attention.refusal(CFG, 256, 256) == CFG.core_refusal(256)
    assert scan.refusal(CFG, 256, 256) == CFG.scan_refusal(256)
    assert conv.refusal(CFG, 256, 256) == CFG.conv_refusal(256)
    assert conv.refusal(LANE_CFG, 256, 256) is None
    assert pipeline.text_layers(CFG) == 2
    # the routed rows (six since Qwen3-Next's; Nemotron-3-Nano's is routed
    # AND has this scan site) are what they were; the dense encoder's takes
    # every plane and has the narrow width
    dense = pipeline.text_encoder(TINY_CONFIG)
    assert dense.narrow_width(TINY_CONFIG) == 128 and len(dense.planes) == 5
    routed = pipeline.text_encoder(TINY_OLMOE)
    assert [site.name for site in routed.sites] == [
        "attention", "expert_gate_up", "expert_dispatch", "expert_combine"]
    assert sum(row.capacities(4096) is not None
               for row in pipeline.TEXT_ENCODERS.values()) == 6


def test_text_predict_refuses_a_capacity_and_the_dequant_plane(params, text):
    from realtime_fraud_detection_tpu.scoring import pipeline

    p, stats = pipeline.text_predict(params, *text, CFG)
    assert stats is None and p.shape == (len(LENGTHS),)
    with pytest.raises(ValueError, match="nothing to compact"):
        pipeline.text_predict(params, *text, CFG, capacity=128)
    with pytest.raises(ValueError, match="FalconH1Config encoder has no "
                                         "quantized form"):
        pipeline.text_predict(params, *text, CFG, dequant_kernel="pallas")


def _scorer(cfg=CFG, text_len=64, **kw):
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig

    kw.setdefault("mesh", build_mesh(devices=jax.devices()[:1]))
    return FraudScorer(bert_config=cfg,
                       scorer_config=ScorerConfig(text_len=text_len), **kw)


@pytest.fixture(scope="module")
def scorer32():
    """A scorer whose text branch holds float32 weights: what it returns
    is the reference's to float32 rounding."""
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=31)
    scorer = _scorer()
    scorer.models = scorer.models.replace(bert=jax.tree.map(
        lambda x: x.astype(F32), scorer.models.bert))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return scorer, gen


@pytest.mark.parametrize("rows", [1, 8])
def test_the_scorers_packed_path_matches_the_reference(scorer32, rows):
    """Bucket 1 and bucket 8: the text column the served packed program
    returns is the reference's on the batch the scorer assembled, and the
    launch is counted as a causal dense encoder's."""
    scorer, gen = scorer32
    recs = gen.generate_batch(rows)
    for i, r in enumerate(recs):
        r["description"] = " ".join(f"w{i}x{j}" for j in range(5 + 7 * i))
    batch = scorer.assemble(recs)
    pending = scorer.dispatch(recs)
    results = scorer.finalize(pending)
    want = _reference(scorer.models.bert, batch.token_ids, batch.token_mask)
    got = np.array([r["model_predictions"]["bert_text"] for r in results])
    np.testing.assert_allclose(got, want[:rows], atol=5e-6)
    lengths = np.count_nonzero(np.asarray(batch.token_mask), axis=1)
    assert lengths.max() > 3 * CFG.mamba_chunk_size or rows == 1
    c = pending.counters
    assert c["token_slots"] == rows * 64
    assert c["ssm_chunks"] == rows * 64 // 16 * 2
    assert c["attn_visible_pairs_full"] == int(
        (lengths * (lengths + 1) // 2).sum())
    assert c["attn_visible_pairs_sliding"] == 0
    assert pending.text_stats is None
    assert (c["routed_pairs"], c["expert_rows"], c["expert_peak_rows"],
            c["expert_token_slots"], c["compact_batches"],
            c["split_batches"]) == (0,) * 6
    assert c["long_text_rows"] == rows and c["short_text_rows"] == 0


def test_the_scorer_counts_the_scan_site_and_names_its_refusals(scorer32):
    scorer, gen = scorer32
    before = scorer.kernel_snapshot()
    scorer.finalize(scorer.dispatch(gen.generate_batch(3)))
    snap = scorer.kernel_snapshot()
    # a CPU mesh is never asked for its kernels: a fallback at every site
    for site in ("attention", "ssm_scan", "causal_conv"):
        assert snap["fallback"][site] == before["fallback"][site] + 1
        assert snap["dispatch"][site] == 0
    assert "expert_gate_up" not in snap["dispatch"]
    assert "head_dim 16" in snap["refused"]["attention"]
    assert "head_dim 16, chunk 16" in snap["refused"]["ssm_scan"]
    assert "parts (64, 64, 64)" in snap["refused"]["causal_conv"]
    split = scorer.host_stats()["text_split"]
    assert split["width"] is None and "FalconH1Config" in split["refused"]
    assert split["families"] == {} and split["compact_batches"] == 0


def test_a_lane_shaped_scorer_on_a_cpu_mesh_names_the_platform():
    scorer = _scorer(LANE_CFG, text_len=256)
    assert scorer.effective_use_pallas() is False
    refused = scorer.kernel_snapshot()["refused"]
    assert "cpu mesh" in refused["attention"]
    assert "cpu mesh" in refused["ssm_scan"]
    assert "cpu mesh" in refused["causal_conv"]
    assert scorer._text_kernel_shape_ok(256)
    assert not _scorer(CFG)._text_kernel_shape_ok(64)


def test_the_stream_job_sums_the_chunks(scorer32):
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )

    scorer, gen = scorer32
    broker = InMemoryBroker()
    job = StreamJob(broker, scorer, JobConfig(max_batch=8))
    assert job.counters["ssm_chunks"] == 0
    broker.produce_batch_keyed(JobConfig.transactions_topic, [
        (str(r["user_id"]), r) for r in gen.generate_batch(16)])
    job.run_until_drained()
    job.close()
    assert job.counters["scored"] == 16 and job.counters["errors"] == 0
    assert job.counters["ssm_chunks"] == 2 * 8 * 64 // 16 * 2
    assert job.counters["token_slots"] == 2 * 8 * 64
    assert job.counters["attn_visible_pairs_full"] > 0
    assert job.counters["expert_rows"] == 0 == job.counters["routed_pairs"]


def test_the_planes_written_for_distilbert_refuse_it_by_name():
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        KernelSettings,
    )

    config = Config()
    config.quant.enabled = True
    config.quant.bert_weights = "int8"
    with pytest.raises(ValueError, match="FalconH1Config text branch"):
        _scorer(config=config)
    config = Config()
    config.kernels = KernelSettings(enabled=True, dequant_matmul="pallas")
    with pytest.raises(ValueError, match="dequant_matmul.*FalconH1Config"):
        _scorer(config=config)
    with pytest.raises(ValueError, match="causal encoder runs on one "
                                         "device"):
        _scorer(mesh=build_mesh())
    with pytest.raises(ValueError, match="DevicePool.*FalconH1Config"):
        _scorer().require_plane("pool", "DevicePool")


# ------------------------------ the other encoders' programs are left alone
def _lowered(config):
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
    blobs, spec_ = pack_tree(make_example_batch(
        8, ScorerConfig(text_len=128)))
    # a jit of its own: the served one would answer the second lowering of a
    # case from its cache, and nothing would be traced again
    return jax.jit(_score_fused_packed_impl,
                   static_argnames=_PACKED_STATIC).lower(
        models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec_,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=jnp.ones((len(MODEL_NAMES),), bool),
        blob_bf16=blobs["bf16"], bert_config=config)


@pytest.mark.parametrize("encoder", ["distilbert", "olmoe", "zaya1",
                                     "laguna", "joyai"])
def test_the_five_other_encoders_trace_no_line_of_it(monkeypatch, encoder):
    """Their packed programs at TINY lower to the same text with the new
    encoder and its scan poisoned as with them whole: no line of
    ``models/falcon_h1.py`` or ``ops/ssd_scan.py`` is traced into them.
    (Against the parent commit their optimised HLO is digest-equal with
    the source metadata dropped: PERF.md, PR 46.)"""
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.models.joyai import TINY_JOYAI
    from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA
    from realtime_fraud_detection_tpu.scoring import pipeline

    # (the package exports the function under the module's name)
    scan_module = sys.modules["realtime_fraud_detection_tpu.ops.ssd_scan"]

    config = {"distilbert": TINY_CONFIG, "olmoe": TINY_OLMOE,
              "zaya1": TINY_ZAYA, "laguna": TINY_LAGUNA,
              "joyai": TINY_JOYAI}[encoder]
    whole = _lowered(config).as_text()
    assert "ssm_" not in whole and "ssd_scan" not in whole

    def poisoned(*a, **k):
        raise AssertionError("the new encoder was traced")

    for module, name in ((falcon_h1, "falcon_h1_predict"),
                         (falcon_h1, "ssd_scan"), (scan_module, "ssd_scan"),
                         (scan_module, "_ssd_xla"), (falcon_h1, "mup_vector")):
        monkeypatch.setattr(module, name, poisoned)
    monkeypatch.setitem(
        pipeline.TEXT_ENCODERS, FalconH1Config, dataclasses.replace(
            pipeline.TEXT_ENCODERS[FalconH1Config], predict=poisoned))
    assert _lowered(config).as_text() == whole


def test_the_new_encoders_program_has_the_one_result():
    """No second output of expert statistics: the packed program returns
    the one matrix, as DistilBERT's does (a routed encoder's returns two)."""
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE

    assert _lowered(CFG).out_info.shape == (8, 13)
    matrix, stats = _lowered(TINY_OLMOE).out_info
    assert matrix.shape == (8, 13) and stats.shape == (3, 2)


def test_a_tap_shifted_in_the_kernels_program_is_caught_too(monkeypatch):
    """``conv_shifted_a_tap`` planted where the LANE program computes its
    convolution — in the kernel's input, positions last: the program asked
    for its kernels reads over the cell's limit against the sound one, as
    the XLA form's does above."""
    params = jax.tree.map(lambda x: x.astype(F32), init_falcon_h1_params(
        jax.random.PRNGKey(3), LANE_CFG))
    t, lengths = 128, (128, 97, 60, 110)
    ids = jax.random.randint(jax.random.PRNGKey(4), (len(lengths), t), 0, 512)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    kernels = dict(use_pallas=True, kernel_interpret=True)
    sound = _predict32(params, ids, mask, config=LANE_CFG, **kernels)
    whole = falcon_h1.causal_conv_silu

    def shifted(x, *a, positions_last, **kw):
        assert positions_last
        return whole(jnp.pad(x, ((0, 0), (0, 0), (1, 0)))[..., :-1], *a,
                     positions_last=positions_last, **kw)

    monkeypatch.setattr(falcon_h1, "causal_conv_silu", shifted)
    gap = np.abs(_predict32(params, ids, mask, config=LANE_CFG, **kernels)
                 - sound)
    assert gap.max() > LIMIT, gap
    assert (gap > LIMIT / 2).sum() >= 2, gap
