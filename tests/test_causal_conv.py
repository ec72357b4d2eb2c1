"""The mixers' causal convolution as a Pallas kernel (ops/causal_conv.py), in
interpret mode on the CPU against its XLA form, ``jax.nn.silu`` of
``models/falcon_h1.causal_conv``: the three cells' part splits and output
dtypes at a sixteenth of their widths, with and without a bias, positions
first and positions last out of a wider array; what causality asks of the
first positions, of a neighbour's tail and of later inputs; the tile rule;
and the refusals by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.models.falcon_h1 import (
    causal_conv,
    conv_silu_parts,
)
from realtime_fraud_detection_tpu.ops.causal_conv import (
    BLOCK_BYTES,
    CHANNEL_TILE,
    STEP,
    STEP_POSITIONS_LAST,
    causal_conv_silu,
    conv_refusal,
    conv_tiling,
)

F32, BF16 = jnp.float32, jnp.bfloat16
# the three cells' parts at a sixteenth (Falcon-H1's B and C at a quarter: a
# part is whole lane tiles) and the dtypes their readers round to
SPLITS = {
    "falconh1": ((256, 128, 128), (BF16, BF16, BF16)),
    "nemotron3": ((512, 128, 128), (BF16, BF16, BF16)),
    "qwen3next": ((128, 128, 256), (F32, F32, BF16)),
    "float32_tests": ((256, 128, 128), (F32, F32, F32)),
}
# channels ahead of the convolved ones and a ragged tail behind them, as
# ``W_in``'s result has (z | xBC | dt): no whole number of lane tiles wide
AHEAD, BEHIND = 256, 40


def _inputs(b, t, c, biased, seed=0, wide=False):
    r = np.random.default_rng(seed)
    width = AHEAD + c + BEHIND if wide else c
    x = jnp.asarray(r.standard_normal((b, t, width)), F32)
    taps = jnp.asarray(r.standard_normal((4, c)) * 0.5, F32)
    bias = jnp.asarray(r.uniform(-0.5, 0.5, (c,)), F32) if biased else None
    return x, taps, bias


def _want(x, taps, bias, parts, dtypes, offset=0):
    return conv_silu_parts(x, taps, bias, parts, dtypes, offset=offset)


def _gap(got, want):
    return max(float(jnp.abs(g.astype(F32) - w.astype(F32)).max())
               for g, w in zip(got, want))


# ------------------------------------------------ kernel against the XLA form
@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_the_kernel_is_the_xla_form_positions_first(split, biased):
    """A row-major array of the convolved channels alone (Qwen3-Next's
    ``qkv``): four strips of ``STEP`` positions, so three of them read the
    rows before them."""
    parts, dtypes = SPLITS[split]
    x, taps, bias = _inputs(2, 4 * STEP[0], sum(parts), biased, seed=1)
    got = causal_conv_silu(x, taps, bias, parts=parts, dtypes=dtypes,
                           interpret=True)
    want = _want(x, taps, bias, parts, dtypes)
    assert [g.shape for g in got] == [(2, 4 * STEP[0], w) for w in parts]
    assert [g.dtype for g in got] == [jnp.dtype(d) for d in dtypes]
    # the same float32 sum in the same order; a bfloat16 part may round a
    # last-digit difference of the SiLU the other way
    for g, w, d in zip(got, want, dtypes):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            atol=2e-6, rtol=0 if d == F32 else 2 ** -7)


@pytest.mark.parametrize("biased", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("split", sorted(SPLITS))
def test_the_kernel_is_the_xla_form_positions_last(split, biased):
    """The channels read out of a wider array as the TPU holds ``W_in``'s
    result — ``[B, W, T]``, from channel ``AHEAD`` on — two pieces of
    ``STEP_POSITIONS_LAST`` positions, so the second reads the lane tile
    before it; the parts come ``[B, C_i, T]``."""
    parts, dtypes = SPLITS[split]
    t = 2 * STEP_POSITIONS_LAST[0]
    x, taps, bias = _inputs(2, t, sum(parts), biased, seed=2, wide=True)
    got = causal_conv_silu(jnp.swapaxes(x, 1, 2), taps, bias, parts=parts,
                           dtypes=dtypes, offset=AHEAD, positions_last=True,
                           interpret=True)
    want = _want(x, taps, bias, parts, dtypes, offset=AHEAD)
    assert [g.shape for g in got] == [(2, w, t) for w in parts]
    for g, w, d in zip(got, want, dtypes):
        assert g.dtype == jnp.dtype(d)
        np.testing.assert_allclose(
            np.asarray(jnp.swapaxes(g, 1, 2), np.float32),
            np.asarray(w, np.float32),
            atol=2e-6, rtol=0 if d == F32 else 2 ** -7)


@pytest.mark.parametrize("wide", [False, True],
                         ids=["positions_first", "positions_last"])
def test_the_callers_helper_hands_back_the_same_parts_either_way(wide):
    """``conv_silu_parts`` with ``kernel`` picks the orientation from the
    array's width and hands back ``[B, T, C_i]`` parts whichever it took:
    what ``mamba2_mix`` and ``delta_mixer`` read."""
    parts, dtypes = SPLITS["float32_tests"]
    offset = AHEAD if wide else 0
    x, taps, bias = _inputs(1, 256, sum(parts), True, seed=3, wide=wide)
    got = conv_silu_parts(x, taps, bias, parts, dtypes, offset=offset,
                          kernel=True, interpret=True)
    want = _want(x, taps, bias, parts, dtypes, offset=offset)
    assert [g.shape for g in got] == [w.shape for w in want]
    assert _gap(got, want) < 2e-6


# --------------------------------------------------------- what causality asks
@pytest.mark.parametrize("last", [False, True],
                         ids=["positions_first", "positions_last"])
def test_the_first_positions_see_zeros_and_no_neighbours_tail(last):
    """Row 0 ends in values of 1e4 and row 1 opens on ones under taps of
    one: a roll that wrapped, or a window that reached back into the row
    before, would put 1e4 into row 1's first three sums; they are 1, 2, 3
    (then 4), as after a pad of zeros."""
    t, c = (2 * STEP_POSITIONS_LAST[0] if last else 4 * STEP[0]), 128
    x = np.ones((2, t, c), np.float32)
    x[0, -8:] = 1e4
    x = jnp.asarray(x)
    feed = jnp.swapaxes(x, 1, 2) if last else x
    (got,) = causal_conv_silu(feed, jnp.ones((4, c), F32), parts=(c,),
                              dtypes=(F32,), positions_last=last,
                              interpret=True)
    got = np.asarray(jnp.swapaxes(got, 1, 2) if last else got)
    sums = np.array([1.0, 2.0, 3.0, 4.0, 4.0], np.float32)
    np.testing.assert_allclose(got[1, :5, 0], np.asarray(jax.nn.silu(sums)),
                               rtol=1e-6)
    np.testing.assert_allclose(got[0, :5, 7], np.asarray(jax.nn.silu(sums)),
                               rtol=1e-6)
    assert got[1].max() < 5.0 and got[0, -1, 0] > 3e4


@pytest.mark.parametrize("last", [False, True],
                         ids=["positions_first", "positions_last"])
@pytest.mark.parametrize("at", ["inside_a_piece", "a_pieces_last_position"])
def test_an_output_ignores_every_later_input(at, last):
    """Inputs after position ``t`` changed: outputs up to ``t`` are the
    same to the bit, the next ``K`` positions move — at a piece's last
    position too, where the next piece reads across the boundary."""
    step = (STEP_POSITIONS_LAST if last else STEP)[0]
    t = step - 1 if at == "a_pieces_last_position" else step + step // 2
    x, taps, bias = _inputs(1, (2 if last else 4) * step, 256, True, seed=4)
    moved = x.at[:, t + 1:].add(1.0)

    def run(v):
        (out,) = causal_conv_silu(
            jnp.swapaxes(v, 1, 2) if last else v, taps, bias, parts=(256,),
            dtypes=(F32,), positions_last=last, interpret=True)
        return np.asarray(jnp.swapaxes(out, 1, 2) if last else out)

    before, after = run(x), run(moved)
    np.testing.assert_array_equal(before[:, :t + 1], after[:, :t + 1])
    assert (before[:, t + 1] != after[:, t + 1]).mean() > 0.99


def test_a_tap_weighs_the_position_the_xla_form_gives_it():
    """One-hot taps: tap ``K - 1`` is the position itself, tap 0 the one
    three before it — ``causal_conv``'s own numbering."""
    x, _, _ = _inputs(1, 128, 128, False, seed=5)
    for tap in range(4):
        taps = jnp.zeros((4, 128), F32).at[tap].set(1.0)
        (got,) = causal_conv_silu(x, taps, parts=(128,), dtypes=(F32,),
                                  interpret=True)
        back = 3 - tap
        want = jax.nn.silu(jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :128])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(jax.nn.silu(causal_conv(x, taps))),
            atol=1e-6)


# ------------------------------------------------------ the rule, the refusals
@pytest.mark.parametrize("parts,offset,tile", [
    ((4096, 512, 512), 4096, 512),          # Falcon-H1
    ((4096, 1024, 1024), 4096, 512),        # Nemotron-3-Nano
    ((2048, 2048, 4096), 0, 512),           # Qwen3-Next
    ((1024, 256, 256), 1024, 256),          # TINY_NEMOTRON_H
    ((256, 128, 128), 256, 128),
])
def test_the_channel_tile_divides_every_part(parts, offset, tile):
    assert conv_tiling(2048, parts, offset) == tile <= CHANNEL_TILE
    assert conv_refusal(2048, parts, 4, offset) is None


def test_a_long_row_takes_a_narrower_tile_until_none_fits():
    parts = (2048, 2048, 4096)
    assert conv_tiling(4096, parts) == 256
    assert conv_tiling(8192, parts) == 128
    assert 8192 * 128 * 16 == BLOCK_BYTES
    assert conv_tiling(8192 + 128, parts) == 0
    assert "every position in one block" in conv_refusal(8192 + 128, parts, 4)


@pytest.mark.parametrize("shape,names", [
    ((2048, (64, 64, 64), 4, 64), "whole lane tiles of 128 channels"),
    ((2048, (4096, 512, 500), 4, 4096), "whole lane tiles of 128 channels"),
    ((2048, (4096, 512, 512), 4, 4000), "from channel 4000"),
    ((2048, (4096, 512, 512), 9, 4096), "9 taps"),
    ((2048, (4096, 512, 512), 0, 4096), "0 taps"),
    ((56, (256, 128, 128), 4, 256), "seq_len 56"),
    ((2048 + 32, (256, 128, 128), 4, 256), "whole lane tiles of 128 pos"),
])
def test_the_refusal_names_what_it_declines(shape, names):
    refusal = conv_refusal(*shape)
    assert refusal is not None and names in refusal
    assert refusal.startswith("causal_conv's kernel")


def test_the_kernel_alone_raises_the_refusal():
    x, taps, bias = _inputs(1, 56, 256, True)
    with pytest.raises(ValueError, match="seq_len 56"):
        causal_conv_silu(x, taps, bias, parts=(256,), dtypes=(F32,),
                         interpret=True)
    with pytest.raises(ValueError, match="under taps of 256 channels"):
        causal_conv_silu(jnp.zeros((1, 128, 384), F32), taps, bias,
                         parts=(384,), dtypes=(F32,), interpret=True)


# ------------------------------------ who traces it: the three mixers alone
@pytest.mark.parametrize("encoder", ["distilbert", "olmoe", "zaya1",
                                     "laguna", "joyai"])
def test_the_encoders_without_a_convolution_trace_no_line_of_it(
        monkeypatch, encoder):
    """The five encoders of the seven cells that run no causal mixer lower
    to the same text with the kernel, its predicate and the callers' helper
    poisoned as with them whole: nothing of ``ops/causal_conv.py`` is in
    their programs, and no start of theirs pays for it. (Against the parent
    commit their optimised HLO is digest-equal with the source metadata
    dropped: CHANGES.md, PR 55.)"""
    from test_falcon_h1 import _lowered

    from realtime_fraud_detection_tpu.models import falcon_h1
    from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
    from realtime_fraud_detection_tpu.models.joyai import TINY_JOYAI
    from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA
    from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE
    from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA
    from realtime_fraud_detection_tpu.ops import causal_conv as kernel_module

    config = {"distilbert": TINY_CONFIG, "olmoe": TINY_OLMOE,
              "zaya1": TINY_ZAYA, "laguna": TINY_LAGUNA,
              "joyai": TINY_JOYAI}[encoder]
    whole = _lowered(config).as_text()
    assert "causal_conv" not in whole and "_conv" not in whole

    def poisoned(*a, **k):
        raise AssertionError("the convolution was traced")

    for module, name in ((kernel_module, "causal_conv_silu"),
                         (kernel_module, "_conv_pallas"),
                         (kernel_module, "conv_refusal"),
                         (kernel_module, "conv_tiling"),
                         (falcon_h1, "causal_conv_silu"),
                         (falcon_h1, "conv_silu_parts"),
                         (falcon_h1, "causal_conv")):
        monkeypatch.setattr(module, name, poisoned)
    assert _lowered(config).as_text() == whole


def test_the_kernel_alone_timer_rehearses_on_the_cpu(tmp_path, capsys):
    """``tools/conv_alone.py --rehearse``: the three cells' shapes at a
    quarter of their widths, every form bit-equal to the XLA form in
    interpret mode; the rows it would write on the chip."""
    import importlib.util
    import json
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "tools" / "conv_alone.py"
    module_spec = importlib.util.spec_from_file_location("conv_alone", path)
    tool = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(tool)
    out = tmp_path / "conv.json"
    assert tool.main(["--rehearse", "--repeats", "1", "--tiles", "128",
                      "--strips", "32", "--steps", "128x16",
                      "--out", str(out)]) == 0
    capsys.readouterr()
    rows = json.loads(out.read_text())["rows"]
    forms = {(row["cell"], row["form"].split(" tile")[0]) for row in rows}
    assert forms == {
        (cell, form) for cell in tool.SHAPES
        for form in ("xla", "kernel parts", "kernel one output")} | {
        (cell, "kernel positions last") for cell in ("falconh1", "nemotron3")}
    assert all(row["max_abs_gap_to_xla"] < 1e-6 for row in rows)
