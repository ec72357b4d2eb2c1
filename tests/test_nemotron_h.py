"""The Nemotron-3-Nano text encoder (models/nemotron_h.py): layers that are
ONE mixer each by a pattern string. The program against the benchmark's
plain reference (``benchmarks/configs/nemotron3_reference.py``: the
recurrence a position at a time, a materialised softmax, every expert over
every token, no line shared with the program) alone at every capacity and
through the scorer's packed path at both rungs; the scan at heads of 64
(two a lane tile) in interpret mode against the XLA form and the sequential
recurrence; the ungated grouped call against ``ragged_dot`` at an expert
width that is no whole number of lane tiles; planted faults that each fail
the configuration's parity limit; the refusals by name; and the seam it
enters the scorer through, which leaves the six other encoders' programs as
they were. TINY keeps the odd shapes: a head of 64, 8 heads a group, an
expert width of 144 (a lane tile and an eighth), ``hidden / 128`` = 3."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.models import falcon_h1, nemotron_h, olmoe
from realtime_fraud_detection_tpu.models.nemotron_h import (
    PUBLISHED_PATTERN,
    TINY_NEMOTRON_H,
    NemotronHConfig,
    init_nemotron_h_params,
    nemotron_h_encode,
    nemotron_h_predict,
)
from realtime_fraud_detection_tpu.ops.attention import attention_reference
from realtime_fraud_detection_tpu.ops.causal_conv import conv_refusal
from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    gmm_tiling,
    grouped_matmul,
    grouped_matmul_reference,
    grouped_matmul_supported,
    grouped_relu2_matmul,
)
from realtime_fraud_detection_tpu.ops.ssd_scan import ssd_refusal, ssd_scan

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from benchmarks.harness import spec  # noqa: E402
# the recurrence a position and a head at a time in float64, and a TINY
# packed program's lowering: Falcon-H1's tests' own
from test_falcon_h1 import _lowered, _sequential  # noqa: E402

F32 = jnp.float32
CFG = TINY_NEMOTRON_H                       # MEM*E at hidden 384
REFERENCE = spec.reference("nemotron3_reference")
FILE = json.loads(
    (ROOT / "benchmarks/configs/nemotron-3-nano-30b-s2048.json").read_text())
# what a run on the chip is held to: a planted fault has to read over it
LIMIT = FILE["parity_atol"]["branch:bert_text"]
T = 256                                     # two chunks of 128
LENGTHS = (256, 140, 1, 200, 129)           # 726 real tokens of 1,280 slots
CAPACITIES = {"every_slot": None, "all_1280": 1280, "1024": 1024, "768": 768}
# what the three kernels take: attention heads of 128 (TINY's 16 is declined
# by name); the mixer's shapes are TINY's own
LANE_CFG = dataclasses.replace(CFG, vocab_size=512, head_dim=128,
                               num_attention_heads=16,
                               num_key_value_heads=1,
                               hybrid_override_pattern="ME*",
                               num_hidden_layers=3)


def reference_cfg(config: NemotronHConfig) -> dict:
    """The keys ``nemotron3_reference.py`` reads, for a ``NemotronHConfig``:
    what ``benchmarks/configs/nemotron3_builder.nemotron3_config`` does,
    backwards."""
    return dataclasses.asdict(config)


@pytest.fixture(scope="module")
def params():
    return init_nemotron_h_params(jax.random.PRNGKey(7), CFG)


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
            lambda p, i, m: nemotron_h_predict(p, i, m, config, **kw))(
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
    got = nemotron_h_predict(params, *text, CFG, capacity=768)
    assert np.abs(np.asarray(got) - want).max() < 3e-3 < LIMIT


def test_every_layers_one_path_weighs_in_the_residual(params, text):
    """A layer is one path, so a layer whose update is small beside the
    residual is a layer nobody checks (``init_nemotron_h_params``): at a
    long row's last real token every layer's update is over a twentieth of
    the residual it is added to — that each is between a tenth and the
    whole is a reading at the cell's own lengths and widths
    (``benchmarks/tests/nemotron3_control.py --shares``: ``context_rms`` is
    set for contexts averaged over ~1,200 keys) — both halves of an ``E``
    layer's update weigh in it, and the bias moves some token's choice."""
    _, parts = _reference(params, *text, parts=True)
    assert parts.shape == (CFG.num_hidden_layers, 4, len(LENGTHS))
    update, residual, routed, moved = (parts[:, i] for i in range(4))
    long_rows = [i for i, n in enumerate(LENGTHS) if n > 100]
    assert (update / residual)[:, long_rows].min() > 0.05
    sparse = [i for i, kind in enumerate(CFG.layer_kinds) if kind == "E"]
    share = (routed / update)[sparse][:, long_rows]
    assert 0.15 < share.min() and share.max() < 0.98, share
    assert moved[sparse].max() > 0.0 == moved[0].max()


def test_padding_leaves_a_rows_answer_bit_equal(params, text):
    """The text is right-padded and every mixer causal or pointwise:
    whatever stands in a row's padded positions, its answer is the same to
    the bit (nothing masks the state-space mixer: nothing needs to)."""
    ids, mask = text
    other = jnp.where(mask, ids, (ids * 7 + 3) % CFG.vocab_size)
    assert bool(jnp.any(other != ids))
    fn = jax.jit(lambda i: nemotron_h_predict(params, i, mask, CFG,
                                              capacity=768))
    np.testing.assert_array_equal(np.asarray(fn(ids)), np.asarray(fn(other)))


def test_a_later_token_moves_no_earlier_position(params32, text):
    ids, mask = text
    other = ids.at[:, 100:].set((ids[:, 100:] + 1) % CFG.vocab_size)
    a, _ = nemotron_h_encode(params32, ids, mask, CFG)
    b, _ = nemotron_h_encode(params32, other, mask, CFG)
    np.testing.assert_array_equal(np.asarray(a)[:, :100],
                                  np.asarray(b)[:, :100])
    assert np.abs(np.asarray(a) - np.asarray(b))[0, 100:].max() > 1e-3


def test_the_stats_count_the_routed_layers_alone(params32, text):
    ids, mask = text
    p, stats = nemotron_h_predict(params32, ids, mask, CFG, capacity=768,
                                  with_stats=True)
    assert p.shape == (len(LENGTHS),) and stats.shape == (3, 2)
    # every expert is held: each E layer's pairs are the router's
    np.testing.assert_array_equal(
        np.asarray(stats)[1], sum(LENGTHS) * CFG.num_experts_per_tok)
    assert (np.asarray(stats)[2] == 0).all()        # the XLA form: no tiles


# ------------------------------------------------------------ planted faults
def _chunk_reset_scan(x, dt, a, b_in, c_in, d, *, chunk, **kw):
    """The state not carried across a chunk boundary."""
    parts = [ssd_scan(x[:, s:s + chunk], dt[:, s:s + chunk], a,
                      b_in[:, s:s + chunk], c_in[:, s:s + chunk], d,
                      chunk=chunk)[0] for s in range(0, x.shape[1], chunk)]
    return jnp.concatenate(parts, axis=1), None


def _pair_swapped_scan(x, dt, a, b_in, c_in, d, **kw):
    """The two heads of a lane tile swapped: head 2 j run with head 2 j +
    1's steps, decay and skip (what a pair's halves exchanged would do)."""
    heads = x.shape[2]
    other = np.arange(heads).reshape(-1, 2)[:, ::-1].reshape(-1)
    return ssd_scan(x, dt[..., other], a[other], b_in, c_in, d[other], **kw)


def _relu_alone(rows, up_w, group_sizes, *, out_dtype, **kw):
    """``relu`` where ``relu^2`` belongs."""
    up = grouped_matmul_reference(rows, up_w, group_sizes)
    return jnp.maximum(up, 0.0).astype(out_dtype)


def _silu_gate(rows, up_w, group_sizes, *, out_dtype, **kw):
    """A SiLU gate in ``relu^2``'s place: ``silu(up) * up``."""
    up = grouped_matmul_reference(rows, up_w, group_sizes)
    return (jax.nn.silu(up) * up).astype(out_dtype)


def _choose(probs, top_k, bias=None, **kw):
    """The bias dropped from the choice."""
    return olmoe.choose_experts(probs, top_k, None, **kw)


def _rotated_core(q, k, v, mask, **kw):
    """A rotation applied in attention (rotate-half at ``rope_theta``)."""
    cos, sin = olmoe.rope_tables(q.shape[2], q.shape[3], CFG.rope_theta)
    return attention_reference(
        olmoe.apply_rope(q, cos, sin), olmoe.apply_rope(k, cos, sin), v,
        mask, **kw)


def _ragged_rows_left_out(lhs, rhs, group_sizes, **kw):
    """A group's rows past its last whole row tile of 128 left out of
    down."""
    out = grouped_matmul(lhs, rhs, group_sizes, **kw)
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])
    group = jnp.searchsorted(ends, row, side="right")
    start = jnp.where(group > 0, ends[jnp.maximum(group - 1, 0)], 0)
    size = group_sizes[jnp.minimum(group, group_sizes.shape[0] - 1)]
    kept = (row - start) < size // 128 * 128
    return jnp.where(kept[:, None, None], out, 0.0)


def _layers_exchanged(params32):
    """A layer kind misplaced by one: layers 1 (``E``) and 2 (``M``) in one
    another's place."""
    layers = list(params32["layers"])
    layers[1], layers[2] = layers[2], layers[1]
    return dict(params32, layers=layers)


FAULTS = {
    "state_not_carried": {"patch": (falcon_h1, "ssd_scan",
                                    _chunk_reset_scan)},
    "lane_tile_heads_swapped": {"patch": (falcon_h1, "ssd_scan",
                                          _pair_swapped_scan)},
    "relu_for_relu2": {"patch": (olmoe, "grouped_relu2_matmul",
                                 _relu_alone)},
    "silu_gate_for_relu2": {"patch": (olmoe, "grouped_relu2_matmul",
                                      _silu_gate)},
    "bias_dropped_from_the_choice": {"patch": (
        nemotron_h, "choose_experts", _choose)},
    "rotation_in_attention": {"patch": (nemotron_h, "attention_reference",
                                        _rotated_core)},
    "ragged_rows_left_out": {"patch": (olmoe, "grouped_matmul",
                                       _ragged_rows_left_out)},
    "layer_kind_misplaced_by_one": {
        "params": _layers_exchanged,
        "config": {"hybrid_override_pattern": "MME*E"}},
}


@pytest.fixture(scope="module")
def fault_case():
    """Twelve rows of 146 to 256 tokens (two chunks of 128), their weights
    in float32 with a bias large enough to move most choices, and
    the sound program's answers: a fault is read, as a cell's ``correct``
    reads it, as the largest gap over a sample."""
    t, lengths = 256, tuple(range(256, 136, -10))
    config = dataclasses.replace(CFG, bias_range=0.5)
    params32 = jax.tree.map(lambda x: x.astype(F32), init_nemotron_h_params(
        jax.random.PRNGKey(8), config))
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
    gap = np.abs(_predict32(faulty, ids, mask, config=config) - sound)
    assert gap.max() > LIMIT, (fault, gap)
    # not by one lucky row
    assert (gap > LIMIT / 2).sum() >= 3, (fault, gap)


def test_the_router_chooses_by_the_bias_and_weighs_without_it():
    """``nemotron_route`` against the rule written out in NumPy: sigmoid
    scores, the top-k of ``s + b``, weights ``s`` over their sum, x 2.5.
    The bias moves some token's choice, and a router that weighs by ``s +
    b`` gives other weights for the SAME choice (at the encoder's answer
    that fault is faint — the experts' outputs are summed either way —
    so it is held here, where it is not)."""
    config = dataclasses.replace(CFG, bias_range=0.05)
    layer = init_nemotron_h_params(jax.random.PRNGKey(5), config)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(6), (256, CFG.hidden_size))
    with jax.default_matmul_precision("highest"):
        experts, weights, _ = nemotron_h.nemotron_route(layer, x, config)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(
        layer["router"], np.float64))))
    b = np.asarray(layer["e_score_correction_bias"], np.float64)
    chosen = np.argsort(-(s + b), axis=-1)[:, :CFG.num_experts_per_tok]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(chosen, -1))
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    want = 2.5 * picked / picked.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(weights), want, atol=1e-5)
    unbiased = np.argsort(-s, axis=-1)[:, :CFG.num_experts_per_tok]
    moved = (np.sort(unbiased, -1) != np.sort(chosen, -1)).any(-1).mean()
    assert 0.05 < moved < 0.95, moved
    biased = np.take_along_axis(s + b, np.asarray(experts), -1)
    wrong = 2.5 * biased / biased.sum(-1, keepdims=True)
    assert np.abs(wrong - want).max() > 0.05


def test_the_reference_lowered_to_float8_reads_far_over_bfloat16(params, text,
                                                                 want):
    """The control's seam (``benchmarks/tests/nemotron3_control.py`` runs it
    at the published widths, against the cell's limit): every matmul
    operand rounded to float8, everywhere and at one site alone."""
    import ml_dtypes

    def float8(x):
        return x.astype(ml_dtypes.float8_e4m3fn).astype(F32)

    lowered = np.abs(_reference(params, *text, operand=float8) - want).max()
    sound = np.abs(np.asarray(nemotron_h_predict(params, *text, CFG))
                   - want).max()
    assert lowered > 3e-3 and lowered > 5 * sound, (lowered, sound)
    for site in ("routed", "scan"):
        alone = np.abs(_reference(params, *text, operand=float8,
                                  sites=frozenset((site,))) - want).max()
        assert alone > 0.0, site
    with pytest.raises(ValueError, match="sites"):
        _reference(params, *text, operand=float8, sites=frozenset(("ffn",)))


# ------------------------------------------------- the scan at heads of 64
def _scan_inputs(b, t, h, p, g, n, seed=0, dtype=np.float32):
    """Steps small enough that a state outlives a chunk (``dt a`` sums to
    about -3 over 128 positions)."""
    r = np.random.default_rng(seed)
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-2), (b, t, h)))
    return (jnp.asarray(r.standard_normal((b, t, h, p)), dtype),
            jnp.asarray(dt, F32),
            jnp.asarray(-r.uniform(1, 8, (h,)), F32),
            jnp.asarray(r.standard_normal((b, t, g, n)), dtype),
            jnp.asarray(r.standard_normal((b, t, g, n)), dtype),
            jnp.asarray(r.standard_normal((h,)), F32))


@pytest.mark.parametrize("carried", [False, True], ids=["from_zero",
                                                        "state_in"])
def test_the_pair_kernel_in_interpret_mode_is_the_recurrence(carried):
    """Two chunks of 128, two groups of eight heads of 64 (four pairs a
    group) over a state of 128: the Pallas form through the interpreter
    against the XLA form AND the recurrence a position at a time; the state
    handed in and the state handed back are paired and parted on the way."""
    args = _scan_inputs(1, 256, 16, 64, 2, 128, seed=1)
    assert ssd_refusal(256, 64, 128, 128, 16, 2) is None
    state = jnp.asarray(np.random.default_rng(5).standard_normal(
        (1, 16, 128, 64)), F32) if carried else None
    xla_y, xla_final = ssd_scan(*args, chunk=128, initial_state=state)
    y, final = ssd_scan(*args, chunk=128, initial_state=state,
                        use_pallas=True, interpret=True)
    want_y, want_final = _sequential(*args, state=state)
    assert y.shape == (1, 256, 16, 64) and final.shape == (1, 16, 128, 64)
    scale = float(np.abs(want_y).max())
    assert scale > 10.0
    np.testing.assert_allclose(np.asarray(y), np.asarray(xla_y),
                               atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(final), want_final, atol=2e-4)
    np.testing.assert_allclose(np.asarray(final), np.asarray(xla_final),
                               atol=2e-4)
    # the state crossed the boundary: the second chunk alone is another y
    alone, _ = ssd_scan(*(v[:, 128:] if v.ndim > 1 else v for v in args),
                        chunk=128, use_pallas=True, interpret=True)
    assert np.abs(np.asarray(alone) - np.asarray(y)[:, 128:]).max() \
        > 1e-2 * scale


def test_the_pair_kernel_keeps_each_head_in_its_half_of_the_tile():
    """Heads that differ in nothing but their step: a pair's two heads give
    two answers, each the one-head recurrence's (a kernel that read the
    other half's step, decay or skip would give its neighbour's)."""
    x, dt, a, b_in, c_in, d = _scan_inputs(1, 128, 8, 64, 1, 128, seed=3)
    x = jnp.tile(x[:, :, :1], (1, 1, 8, 1))      # one x for every head
    y, _ = ssd_scan(x, dt, a, b_in, c_in, d, chunk=128, use_pallas=True,
                    interpret=True)
    want, _ = _sequential(x, dt, a, b_in, c_in, d)
    scale = float(np.abs(want).max())
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5 * scale)
    assert np.abs(want[:, :, 0] - want[:, :, 1]).max() > 1e-2 * scale


def test_the_pair_kernel_at_bfloat16_is_near_the_xla_form():
    args = _scan_inputs(1, 256, 8, 64, 1, 128, seed=2, dtype=jnp.bfloat16)
    want_y, _ = ssd_scan(*args, chunk=128)
    y, _ = ssd_scan(*args, chunk=128, use_pallas=True, interpret=True)
    scale = float(jnp.abs(want_y).max())
    assert np.abs(np.asarray(y) - np.asarray(want_y)).max() < 2e-2 * scale


@pytest.mark.parametrize("shape,says", [
    ((2048, 64, 128, 128, 64, 8), None),
    ((2048, 128, 256, 128, 32, 2), None),
    ((2048, 32, 128, 128, 64, 8), "head_dim 32"),
    ((2048, 64, 128, 128, 64, 16), "64 heads in 16 groups"),
    ((2048, 64, 64, 128, 64, 8), "d_state 64"),
    ((100, 64, 128, 128, 64, 8), "seq_len 100"),
])
def test_the_scan_takes_heads_of_64_and_names_what_it_refuses(shape, says):
    refusal = ssd_refusal(*shape)
    assert (refusal is None) if says is None else (says in refusal), refusal


# ------------------------------------- the ungated grouped call, ragged width
def test_the_ungated_call_in_interpret_mode_is_ragged_dot_and_relu2():
    """An expert width that is no whole number of lane tiles (176) goes
    whole in one block, as N of the first call and as K of down's; ragged
    groups, some empty; rows past the last group never read. ``hidden /
    128`` = 3: down's result rows are three lane tiles, no sublane tile."""
    m, hidden, width, groups = 512, 384, 176, 12
    assert grouped_matmul_supported(m, hidden, width)
    assert grouped_matmul_supported(m, width, hidden)
    assert not grouped_matmul_supported(m, hidden, 168)   # no packed sublane
    assert not grouped_matmul_supported(m, hidden, 80)    # under a lane tile
    assert gmm_tiling(m, hidden, width, groups, gated=True,
                      matrices=1) == (128, hidden, width)
    assert gmm_tiling(m, width, hidden, groups) == (128, width, hidden)
    rng = np.random.default_rng(3)
    sizes = rng.multinomial(450, rng.dirichlet(np.full(groups, 0.5)))
    sizes[4] = 0
    sizes = jnp.asarray(sizes, jnp.int32)
    real = int(sizes.sum())
    rows = jnp.asarray(rng.standard_normal((m, hidden)), jnp.bfloat16)
    up = jnp.asarray(rng.standard_normal((groups, hidden, width)) * 0.05,
                     jnp.bfloat16)
    down = jnp.asarray(rng.standard_normal((groups, width, hidden)) * 0.1,
                       jnp.bfloat16)
    want = jnp.square(jnp.maximum(
        grouped_matmul_reference(rows, up, sizes), 0.0))
    act = grouped_relu2_matmul(rows, up, sizes, out_dtype=jnp.bfloat16,
                               use_pallas=True, interpret=True)
    assert act.shape == (m, width) and act.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(act, np.float32)[:real],
                               np.asarray(want)[:real], atol=3e-2, rtol=2e-2)
    assert float(jnp.abs(want[:real]).max()) > 1.0
    # the XLA form of the same call
    xla = grouped_relu2_matmul(rows, up, sizes, out_dtype=jnp.bfloat16)
    np.testing.assert_allclose(np.asarray(xla, np.float32)[:real],
                               np.asarray(want)[:real], atol=3e-2, rtol=2e-2)
    out = grouped_matmul(act, down, sizes, use_pallas=True, interpret=True)
    assert out.shape == (m, 3, 128)
    np.testing.assert_allclose(
        np.asarray(out).reshape(m, hidden)[:real],
        np.asarray(grouped_matmul_reference(act, down, sizes))[:real],
        atol=2e-2, rtol=2e-2)


def _plain_relu2_gmm(rows, up, sizes, tiling):
    """PR 50's form of the ungated call, on the same scaffold at the same
    tile: the matrices ``[G, K, N]``, the group's ``[tk, tn]`` block,
    ``jnp.dot``. What ``relu2_gmm`` must equal to the bit."""
    from jax.experimental import pallas as pl

    gmm = sys.modules["realtime_fraud_detection_tpu.ops.grouped_matmul"]
    tm, tk, tn = tiling

    def body(offsets, group_ids, row_tiles, lhs, up_w, out, *accs, tm, tn,
             tiles_k):
        visit = pl.program_id(1)

        def store(product):
            mine = gmm._own_rows(visit, offsets, group_ids, row_tiles, tm,
                                 (tm, tn))
            out[...] = jnp.where(
                mine, jnp.square(jnp.maximum(product, 0.0)),
                out[...].astype(jnp.float32)).astype(out.dtype)

        gmm._over_k((jnp.dot(lhs[...], up_w[...],
                             preferred_element_type=jnp.float32),),
                    accs, tiles_k, store)

    return gmm._grouped_call(
        body, "relu2_gmm", rows, (up,), sizes, tiling,
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], up.shape[-1]),
                                       jnp.bfloat16),
        out_block=(tm, tn), out_index=lambda row_tile, n_i: (row_tile, n_i),
        vmem=gmm.gated_vmem_bytes(tm, tk, tn, matrices=1), flops_per_mkn=2,
        transcendentals=0, interpret=True)


# 512 rows at a row tile of 128, four groups: whole tiles a group; one group
# with most of the rows; an empty group between two others; tiles that
# straddle two groups and three (rows 128 and 256 fall inside a group, tile
# 1 holds the end of group 0, all of group 1 and the start of group 2)
GROUP_LAYOUTS = {"even": (128, 128, 128, 128), "skewed": (400, 10, 30, 50),
                 "empty_group": (200, 0, 150, 100),
                 "straddling": (100, 60, 200, 90)}


@pytest.mark.parametrize("tk", [384, 128], ids=["k_whole", "k_in_three"])
@pytest.mark.parametrize("layout", sorted(GROUP_LAYOUTS))
@pytest.mark.parametrize("width", [176, 256], ids=["ragged_n", "lane_n"])
def test_relu2_gmm_takes_its_matrices_k_innermost(width, layout, tk):
    """``relu2_gmm`` is handed the up matrices ``[G, N, K]`` — how the TPU
    holds a ``[G, K, N]`` parameter whose N is no lane multiple — and
    contracts both last axes: ``ragged_dot`` and ``relu^2`` on the real
    rows, and PR 50's ``[G, K, N]`` form at the same tile TO THE BIT (same
    operands, same float32 accumulation, one rounding), at an N of 1 3/8
    lane tiles and of two, K in one block and in three."""
    from realtime_fraud_detection_tpu.ops.grouped_matmul import relu2_gmm

    m, hidden, groups = 512, 384, 4
    tiling = (128, tk, width)
    rng = np.random.default_rng(51)
    sizes = jnp.asarray(GROUP_LAYOUTS[layout], jnp.int32)
    real = int(sizes.sum())
    rows = jnp.asarray(rng.standard_normal((m, hidden)), jnp.bfloat16)
    up = jnp.asarray(rng.standard_normal((groups, hidden, width)) * 0.05,
                     jnp.bfloat16)
    act = relu2_gmm(rows, jnp.swapaxes(up, 1, 2), sizes,
                    out_dtype=jnp.bfloat16, tiling=tiling, interpret=True)
    assert act.shape == (m, width) and act.dtype == jnp.bfloat16
    want = np.asarray(jnp.square(jnp.maximum(
        grouped_matmul_reference(rows, up, sizes), 0.0)))
    assert np.abs(want[:real]).max() > 1.0
    np.testing.assert_allclose(np.asarray(act, np.float32)[:real],
                               want[:real], atol=3e-2, rtol=2e-2)
    plain = _plain_relu2_gmm(rows, up, sizes, tiling)
    np.testing.assert_array_equal(np.asarray(act, np.float32)[:real],
                                  np.asarray(plain, np.float32)[:real])


def test_the_ungated_call_hands_the_kernel_the_swapped_matrices(monkeypatch):
    """``grouped_relu2_matmul`` takes what the tree stores, ``[experts,
    hidden, width]``, and swaps inside the program: the kernel sees ``[G,
    N, K]`` at the rule's tile, and the stored tree does not change."""
    gmm = sys.modules["realtime_fraud_detection_tpu.ops.grouped_matmul"]
    seen = {}

    def kernel(rows, up_w, sizes, **kw):
        seen.update(up=up_w.shape, tiling=kw["tiling"])
        return jnp.zeros((rows.shape[0], up_w.shape[1]), kw["out_dtype"])

    monkeypatch.setattr(gmm, "relu2_gmm", kernel)
    layer = init_nemotron_h_params(jax.random.PRNGKey(2), CFG)["layers"][1]
    assert layer["up_proj"].shape == (16, 384, 144)
    grouped_relu2_matmul(jnp.zeros((1024, 384), jnp.bfloat16),
                         layer["up_proj"], jnp.full((16,), 64, jnp.int32),
                         out_dtype=jnp.bfloat16, use_pallas=True)
    assert seen == {"up": (16, 144, 384), "tiling": (128, 384, 144)}


@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
def test_apply_experts_without_a_gate_is_the_same_through_the_kernels(
        experts_through_both_forms, rung):
    """16 groups at both capacities of a launch of 4,096 slots, a width of
    144 and result rows of three lane tiles: ``relu2_gmm``, down's kernel
    and the combine (interpreted) against the XLA form. The layer holds no
    ``gate_proj``: that alone chooses the ungated call."""
    layer = init_nemotron_h_params(jax.random.PRNGKey(2), CFG)["layers"][1]
    assert "gate_proj" not in layer
    assert layer["up_proj"].shape == (16, 384, 144)
    sizes = experts_through_both_forms(layer, top_k=4, rung=rung, atol=1e-2)
    assert sizes.sum() == 2800 * 4 and (sizes % 128 != 0).all()


def test_the_published_widths_run_as_published():
    """1,856 = 14 1/2 lane tiles whole in one block in both calls, a row
    tile of 128 at ~470-770 rows an expert; ``hidden / 128`` = 21."""
    from realtime_fraud_detection_tpu.ops.combine import (
        combine_supported,
        combine_tokens,
    )

    full = NemotronHConfig()
    for slots in (12288, 16384):
        rows = slots * full.num_experts_per_tok
        assert grouped_matmul_supported(rows, 2688, 1856)
        assert gmm_tiling(rows, 2688, 1856, 128, gated=True,
                          matrices=1) == (128, 2688, 1856)
        assert gmm_tiling(rows, 1856, 2688, 128) == (128, 1856, 2688)
        assert combine_supported(slots, 6, 2688)
        assert combine_tokens(slots, 6, 2688) == 128
    ids = nemotron_h.TEXT_ENCODER.build_ids(
        full, [(8, 2048, 12288), (8, 2048, 16384)])
    assert ids == {"tiles": "12288:128x2688x1856+128x1856x2688,"
                            "16384:128x2688x1856+128x1856x2688"}


# ----------------------------------------- the whole encoder at lane shapes
def test_the_encoder_with_its_kernels_interpreted_is_the_xla_form(
        monkeypatch):
    """At attention heads of 128 the program asked for its kernels holds
    the scan's pair kernel, the fused causal core handed NO rotation, the
    ungated grouped call, down's and the combine; through the interpreter
    it answers what the XLA forms answer. Since PR 55 the ``M`` layer's
    convolution is the kernel too, reading x | B | C out of ``W_in``'s
    2,576-wide result positions last."""
    params = init_nemotron_h_params(jax.random.PRNGKey(3), LANE_CFG)
    t, lengths = 256, (256, 130)
    assert LANE_CFG.core_refusal(t) is None is LANE_CFG.scan_refusal(t)
    assert LANE_CFG.conv_refusal(t) is None
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, t), 0, 512)
    mask = jnp.arange(t)[None, :] < jnp.array(lengths)[:, None]
    plain = nemotron_h_predict(params, ids, mask, LANE_CFG)
    asked = {"scan": 0, "core": 0, "relu2": 0, "conv": 0}

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

    whole_core = nemotron_h.windowed_attention

    def core(*a, **kw):
        asked["core"] += bool(kw["interpret"]) and "rope" not in kw
        return whole_core(*a, **kw)

    def relu2(*a, **kw):
        asked["relu2"] += bool(kw["use_pallas"] and kw["interpret"])
        return grouped_relu2_matmul(*a, **kw)

    monkeypatch.setattr(falcon_h1, "ssd_scan", scan)
    monkeypatch.setattr(falcon_h1, "causal_conv_silu", conv)
    monkeypatch.setattr(falcon_h1, "causal_conv", no_xla_conv)
    monkeypatch.setattr(nemotron_h, "windowed_attention", core)
    monkeypatch.setattr(olmoe, "grouped_relu2_matmul", relu2)
    fused, stats = jax.jit(lambda i, m: nemotron_h_predict(
        params, i, m, LANE_CFG, capacity=None, use_pallas=True,
        kernel_interpret=True, with_stats=True))(ids, mask)
    assert asked == {"scan": 1, "core": 1, "relu2": 1, "conv": 1}
    assert np.abs(np.asarray(fused) - np.asarray(plain)).max() < 3e-3
    want = _reference(params, ids, mask, LANE_CFG)
    assert np.abs(np.asarray(fused) - want).max() < LIMIT / 2
    # the fused kernel's grid visited whole row tiles of the 386 x 4 pairs
    held, tiles = int(stats[1, 0]), int(stats[2, 0])
    assert held == 386 * 4 and tiles % 128 == 0 and tiles >= held


# ----------------------------------------------------------------- the seam
def test_published_config_is_the_default_and_the_pattern_is_parsed_once():
    full = NemotronHConfig()
    for key, value in FILE["published"].items():
        assert getattr(full, key) == value, key
    assert full.hybrid_override_pattern == PUBLISHED_PATTERN
    kinds = full.layer_kinds
    assert len(kinds) == 52 and (kinds.count("M"), kinds.count("E"),
                                 kinds.count("*")) == (23, 23, 6)
    assert (full.num_ssm_layers, full.num_sparse_layers) == (23, 23)
    assert full.d_inner == 4096 != full.expand * full.hidden_size
    assert (full.conv_dim, full.in_proj_dim) == (6144, 10304)
    assert full.core_refusal(2048) is None is full.scan_refusal(2048)
    assert "head_dim 16" in CFG.core_refusal(2048)
    assert CFG.scan_refusal(2048) is None           # TINY keeps the mixer
    # the convolution's kernel over x | B | C out of W_in's 10,304 from
    # channel 4,096 on; TINY's parts are whole lane tiles too
    assert full.conv_refusal(2048) is None is CFG.conv_refusal(2048)
    assert full.conv_refusal(2048) == conv_refusal(
        2048, (4096, 1024, 1024), 4, offset=4096)
    assert "seq_len 32" in CFG.conv_refusal(32)
    # the cell's cut: the first nine layers of the published fifty-two
    assert FILE["hybrid_override_pattern"] == PUBLISHED_PATTERN[:9]


@pytest.mark.parametrize("change,message", [
    ({"hybrid_override_pattern": "MEM-E"}, "dense MLP layer"),
    ({"hybrid_override_pattern": "MEMXE"}, r"kinds \['X'\]"),
    ({"hybrid_override_pattern": "MEM*"}, "names 4 layers"),
    ({"n_group": 2}, "group-limited routing"),
    ({"topk_group": 2}, "group-limited routing"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"sliding_window": 512}, "sliding_window"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"expand": 2, "hidden_size": 512}, "two readings"),
    ({"moe_intermediate_size": 64}, "ONE expert's width"),
    ({"n_groups": 3}, "divide into their groups"),
])
def test_config_refuses_what_the_equations_cannot_hold(change, message):
    with pytest.raises(ValueError, match=message):
        dataclasses.replace(CFG, **change)


def test_a_layer_holds_what_its_kind_needs_and_nothing_else(params):
    by_kind = {"M": {"norm", "in_proj", "conv_weight", "conv_bias",
                     "dt_bias", "A_log", "D", "mixer_norm", "out_proj"},
               "*": {"norm", "q_proj", "k_proj", "v_proj", "o_proj"},
               "E": {"norm", "router", "e_score_correction_bias", "up_proj",
                     "down_proj", "shared_up", "shared_down"}}
    for kind, layer in zip(CFG.layer_kinds, params["layers"]):
        assert set(layer) == by_kind[kind], kind
    mixer, routed = params["layers"][0], params["layers"][1]
    assert mixer["in_proj"].shape == (384, CFG.in_proj_dim) == (384, 2576)
    assert mixer["in_proj"].dtype == jnp.bfloat16
    assert mixer["conv_weight"].shape == (4, CFG.conv_dim)
    assert mixer["conv_weight"].dtype == mixer["A_log"].dtype == F32
    assert routed["up_proj"].shape == (16, 384, 144)
    assert routed["down_proj"].shape == (16, 144, 384)
    assert routed["e_score_correction_bias"].dtype == F32
    assert float(jnp.abs(routed["e_score_correction_bias"]).max()) > 0.0
    dt = jax.nn.softplus(mixer["dt_bias"])
    assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.01


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
    reference's on the batch the scorer assembled, and the launch is counted
    by KIND of layer: pairs over the two ``E`` layers, chunks over the two
    ``M`` layers, one causal layer's visible pairs."""
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
    assert c["expert_rows"] == c["routed_pairs"] == c["real_tokens"] * 4 * 2
    assert c["expert_rows"] <= c["expert_peak_rows"]
    # a chunk of 128 over rows of 32 positions: the launch's slots / 128
    assert c["ssm_chunks"] == 128 * 32 // 128 * 2
    lengths = np.count_nonzero(np.asarray(batch.token_mask), axis=1)
    assert c["attn_visible_pairs_full"] == int(
        (lengths * (lengths + 1) // 2).sum())
    assert c["attn_visible_pairs_sliding"] == 0


def test_one_row_of_the_seam_is_routed_and_state_space_at_once(rung_scorer):
    from realtime_fraud_detection_tpu.scoring import pipeline

    row = pipeline.text_encoder(CFG)
    assert row is nemotron_h.TEXT_ENCODER
    assert [site.name for site in row.sites] == [
        "attention", "expert_gate_up", "expert_dispatch", "expert_combine",
        "ssm_scan", "causal_conv"]
    assert row.sites[-1].refusal(CFG, 32, 32) == CFG.conv_refusal(32)
    assert pipeline.text_layers(CFG) == 5
    assert row.capacities(4096) == (3072, 4096)
    assert NemotronHConfig in pipeline.TextConfig.__args__
    assert (CFG.num_sparse_layers, CFG.num_ssm_layers, CFG.intermediate_size,
            CFG.num_experts) == (2, 2, 144, 16)
    scorer, gen = rung_scorer
    before = scorer.kernel_snapshot()
    scorer.finalize(scorer.dispatch(gen.generate_batch(3)))
    snap = scorer.kernel_snapshot()
    # a CPU mesh is never asked for its kernels: a fallback at every site
    for site in ("attention", "ssm_scan", "causal_conv", "expert_gate_up",
                 "expert_dispatch", "expert_combine"):
        assert snap["fallback"][site] == before["fallback"][site] + 1, site
        assert snap["dispatch"][site] == 0
    assert "head_dim 16" in snap["refused"]["attention"]
    assert "seq_len 32" in snap["refused"]["ssm_scan"]
    assert "seq_len 32" in snap["refused"]["causal_conv"]


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
    assert c["routed_pairs"] == c["expert_rows"] \
        == c["real_tokens"] * 4 * 2 > 0
    assert c["ssm_chunks"] == c["token_slots"] // 128 * 2 > 0
    assert c["compact_batches"] == c["batches"] > 0
    assert c["attn_visible_pairs_full"] >= c["real_tokens"]


def test_the_planes_written_for_distilbert_refuse_it_by_name():
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        QuantSettings,
    )

    config = Config()
    config.quant = QuantSettings(enabled=True, bert_weights="int8")
    with pytest.raises(ValueError, match="NemotronHConfig"):
        _scorer(config=config)
    with pytest.raises(ValueError, match="NemotronHConfig"):
        _scorer(mesh=build_mesh(devices=jax.devices()[:2]))


# ------------------------------ the other encoders' programs are left alone
@pytest.mark.parametrize("encoder", ["distilbert", "olmoe", "zaya1",
                                     "laguna", "joyai", "falconh1"])
def test_the_six_other_encoders_trace_none_of_what_this_one_added(
        monkeypatch, encoder):
    """Their packed programs at TINY lower to the same text with this
    encoder, the scan's pair kernel and the ungated grouped call poisoned as
    with them whole. (Against the parent commit their optimised HLO is
    digest-equal with the source metadata dropped, Falcon-H1's included
    through the mixer's shared half ``mamba2_mix``: PERF.md, PR 50.)"""
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.models.falcon_h1 import TINY_FALCON_H1
    from realtime_fraud_detection_tpu.models.joyai import TINY_JOYAI
    from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA

    scan_module = sys.modules["realtime_fraud_detection_tpu.ops.ssd_scan"]
    gmm_module = sys.modules[
        "realtime_fraud_detection_tpu.ops.grouped_matmul"]
    config = {"distilbert": TINY_CONFIG, "olmoe": TINY_OLMOE,
              "zaya1": TINY_ZAYA, "laguna": TINY_LAGUNA,
              "joyai": TINY_JOYAI, "falconh1": TINY_FALCON_H1}[encoder]
    whole = _lowered(config).as_text()

    def poisoned(*a, **kw):
        raise AssertionError("traced into another encoder's program")

    for name in ("nemotron_h_predict", "nemotron_h_encode", "nemotron_layer",
                 "nemotron_mixer", "nemotron_attention", "nemotron_route",
                 "relu2_mlp"):
        monkeypatch.setattr(nemotron_h, name, poisoned)
    monkeypatch.setattr(scan_module, "_ssd_pair_kernel", poisoned)
    monkeypatch.setattr(gmm_module, "relu2_gmm", poisoned)
    monkeypatch.setattr(olmoe, "grouped_relu2_matmul", poisoned)
    assert _lowered(config).as_text() == whole


def test_a_tap_shifted_in_the_kernels_program_is_caught(monkeypatch):
    """Falcon-H1's planted fault ``conv_shifted_a_tap`` where the LANE
    program computes its ``M`` layer's convolution — in the kernel's input,
    positions last: the program asked for its kernels reads over the
    cell's limit against the sound one."""
    params = jax.tree.map(lambda x: x.astype(F32), init_nemotron_h_params(
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
