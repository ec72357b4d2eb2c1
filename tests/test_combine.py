"""The routed experts' way home (``ops/combine.py``): the Pallas form,
interpreted, against the XLA form, at the four routed encoders' ``(top_k,
hidden)`` and a small ``N``; what the kernels never wrote reaches nothing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.ops import (
    combine_supported,
    weighted_combine,
    weighted_combine_reference,
)
from realtime_fraud_detection_tpu.ops.combine import (
    ROWS_BUDGET,
    TOKEN_TILES,
    combine_rows,
    combine_tokens,
    combine_vmem_bytes,
)
from realtime_fraud_detection_tpu.ops.grouped_matmul import VMEM_CEILING

# a token's experts, hidden, the router's width, the experts held here
ENCODERS = {"olmoe": (8, 2048, 64, 64), "zaya1": (1, 2048, 16, 16),
            "laguna": (10, 3072, 256, 64), "joyai": (8, 2048, 256, 256)}
TOKENS, REAL = 64, 50
# the popularity of the held experts: even, skewed, one holds every pair
LAYOUTS = ("even", "skewed", "one_group_holds_all")


def routed(encoder, layout, seed=0):
    """What ``apply_experts`` hands the combine for ``TOKENS`` slots of which
    the first ``REAL`` are real (the rest fillers): ``out3`` with every row
    of no group poisoned, ``home`` from a stable sort, weights, ``valid``."""
    top_k, hidden, width, held = ENCODERS[encoder]
    rng = np.random.default_rng(seed)
    if layout == "one_group_holds_all":
        # every token's first expert is expert 0; its others live elsewhere
        # (or, where the layer holds them all, are spread)
        experts = np.stack([rng.permutation(width - 1)[:top_k] + 1
                            for _ in range(TOKENS)])
        experts[:, 0] = 0
        if held == width and top_k > 1:
            held_here = experts == 0
        else:
            held_here = experts < held
    else:
        p = rng.dirichlet(np.full(width, 1e6 if layout == "even" else 0.5))
        experts = np.stack([rng.choice(width, top_k, replace=False, p=p)
                            for _ in range(TOKENS)])
        held_here = experts < held
    valid = held_here & (np.arange(TOKENS) < REAL)[:, None]
    flat = np.where(valid, experts, held).reshape(-1)
    order = np.argsort(flat, kind="stable")
    home = np.empty_like(order)
    home[order] = np.arange(order.size)
    pairs = int(valid.sum())
    out3 = rng.standard_normal(
        (TOKENS * top_k, hidden // 128, 128)).astype(np.float32)
    out3[pairs:] = np.nan                 # the rows no kernel ever wrote
    weights = rng.random((TOKENS, top_k)).astype(np.float32)
    return (jnp.asarray(out3), jnp.asarray(home.reshape(TOKENS, top_k),
                                           jnp.int32),
            jnp.asarray(weights), jnp.asarray(valid))


@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("encoder", sorted(ENCODERS))
def test_the_kernel_equals_the_xla_form(encoder, layout):
    """Fillers (the last 14 tokens), absent pairs (Laguna holds a quarter
    of its router's experts) and NaN in every row no group owns."""
    out3, home, weights, valid = routed(encoder, layout)
    top_k, hidden = ENCODERS[encoder][:2]
    assert combine_supported(TOKENS, top_k, hidden)
    want = weighted_combine_reference(out3, home, weights, valid)
    got = weighted_combine(out3, home, weights, valid, use_pallas=True,
                           interpret=True)
    assert got.shape == (TOKENS, hidden) and got.dtype == jnp.float32
    assert np.isfinite(np.asarray(want)).all()
    assert np.isfinite(np.asarray(got)).all()
    assert not np.asarray(got)[REAL:].any()
    if encoder == "laguna":
        assert 0 < int(valid.sum()) < REAL * top_k          # absent pairs
    # the order of a token's top_k float32 adds is all that differs
    np.testing.assert_allclose(got, want, atol=5e-6, rtol=0)
    # against the sum written out by hand
    by_hand = np.zeros((TOKENS, hidden), np.float32)
    for n, j in zip(*np.nonzero(np.asarray(valid))):
        by_hand[n] += np.asarray(weights)[n, j] * np.asarray(
            out3)[int(home[n, j])].reshape(-1)
    np.testing.assert_allclose(got, by_hand, atol=5e-6, rtol=0)


@pytest.mark.parametrize("tokens", [32, 64])
def test_any_block_of_tokens_gives_the_same_bits(tokens):
    """The block is how the work is cut, not what is summed: 128 tokens in
    blocks of 32 and 64 (two and four steps: the copies of a step are
    started during the one before) equal one block of 128 exactly."""
    out3, home, weights, valid = (
        jnp.concatenate([a, b]) for a, b in zip(routed("olmoe", "skewed"),
                                                routed("olmoe", "even", 1)))
    # the second half's rows lie after the first half's
    home = home.at[TOKENS:].add(TOKENS * 8)
    home, weights = jnp.where(valid, home, -1), jnp.where(valid, weights, 0.0)
    whole = combine_rows(out3, home, weights, tokens=128, interpret=True)
    cut = combine_rows(out3, home, weights, tokens=tokens, interpret=True)
    np.testing.assert_array_equal(cut, whole)
    assert np.isfinite(np.asarray(whole)).all()


def test_nothing_valid_is_zero():
    out3, home, weights, valid = routed("zaya1", "even")
    got = weighted_combine(out3 * jnp.nan, home, weights,
                           jnp.zeros_like(valid), use_pallas=True,
                           interpret=True)
    assert not np.asarray(got).any()


def test_the_one_predicate():
    """``combine_supported`` is what the traced guard asks: a shape it
    declines runs the XLA form, asked for the kernel or not."""
    assert combine_supported(24576, 8, 2048)            # OLMoE, 3/4 rung
    assert combine_supported(32768, 1, 2048)            # ZAYA1, every slot
    assert combine_supported(12288, 10, 3072)           # Laguna
    assert combine_supported(1024, 8, 2048)             # the bucket-8 program
    assert not combine_supported(48, 2, 128)            # no block divides 48
    assert not combine_supported(64, 2, 64)             # under a lane tile
    # the block follows (top_k, hidden) inside the budget the rows may take
    for n, top_k, hidden in ((24576, 8, 2048), (32768, 1, 2048),
                             (12288, 10, 3072), (64, 2, 128)):
        tm = combine_tokens(n, top_k, hidden)
        assert tm in TOKEN_TILES and n % tm == 0
        assert 2 * top_k * tm * hidden * 4 <= ROWS_BUDGET
        assert combine_vmem_bytes(tm, top_k, hidden) <= VMEM_CEILING
    rng = np.random.default_rng(3)
    out3 = jnp.asarray(rng.standard_normal((96, 1, 64)), jnp.float32)
    home = jnp.asarray(rng.permutation(96).reshape(48, 2), jnp.int32)
    weights = jnp.asarray(rng.random((48, 2)), jnp.float32)
    valid = jnp.asarray(rng.random((48, 2)) > 0.2)
    np.testing.assert_array_equal(
        weighted_combine(out3, home, weights, valid, use_pallas=True,
                         interpret=True),
        weighted_combine_reference(out3, home, weights, valid))


def test_under_jit_the_layers_share_one_trace():
    out3, home, weights, valid = routed("joyai", "even")

    @jax.jit
    def two_layers(out3, home, weights, valid):
        return (weighted_combine(out3, home, weights, valid, use_pallas=True,
                                 interpret=True)
                + weighted_combine(out3, home, weights, valid,
                                   use_pallas=True, interpret=True))

    before = combine_rows._cache_size()
    got = two_layers(out3, home, weights, valid)
    assert combine_rows._cache_size() - before <= 1
    np.testing.assert_allclose(
        got, 2 * weighted_combine_reference(out3, home, weights, valid),
        atol=1e-5, rtol=0)
