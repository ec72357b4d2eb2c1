"""The routed experts' way out (``ops/dispatch.py``): the Pallas row fetch,
interpreted, against XLA's cast and gather — a copy, so every row of a group
is held to the same BITS — at the routed encoders' ``top_k``, with padded
tokens and a share of the experts, through ``apply_experts`` (gated and
``relu2`` layers, bfloat16 and float32 weights) and ``olmoe_predict``; and
the one predicate, at the seven routed cells' shapes."""

import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.models import olmoe
from realtime_fraud_detection_tpu.models.olmoe import (
    OlmoeConfig,
    apply_experts,
    init_olmoe_params,
    olmoe_predict,
)
from realtime_fraud_detection_tpu.ops import (
    dispatch_reference,
    dispatch_supported,
    rows_to_experts,
)
from realtime_fraud_detection_tpu.ops.dispatch import (
    ROW_TILES,
    XLA_KEEPS_BYTES,
    dispatch_rows,
    dispatch_takes,
    dispatch_tile,
    dispatch_vmem_bytes,
    lay_rows,
    row_pieces,
)
from realtime_fraud_detection_tpu.ops.grouped_matmul import VMEM_CEILING

# the module, where the line between the two forms is kept
dispatch_module = sys.modules["realtime_fraud_detection_tpu.ops.dispatch"]

TOKENS, REAL, HIDDEN = 64, 50, 256
# a token's experts, the router's width, the experts held here, the first
# of them: OLMoE's, ZAYA1's, and a chip's share of a wider router (Laguna)
ROUTINGS = {"top8_of_64": (8, 64, 64, 0), "top1_of_16": (1, 16, 16, 0),
            "top4_share_8_of_32": (4, 32, 8, 16)}
KERNELS = dict(use_pallas=True, kernel_interpret=True)


@pytest.fixture
def engaged(monkeypatch):
    """The kernel pays past a source of 112 MiB; the tests' shapes are
    let through the same guard by moving the line, not by a second path."""
    monkeypatch.setattr(dispatch_module, "XLA_KEEPS_BYTES", 0)


@pytest.fixture
def fetches(monkeypatch):
    """The calls of ``dispatch_rows`` a test's traces made (the jitted
    function's own cache may already hold the shape)."""
    calls = []

    def counted(*args, **kw):
        calls.append(kw["rows"])
        return dispatch_rows(*args, **kw)

    monkeypatch.setattr(dispatch_module, "dispatch_rows", counted)
    return calls


def routed(routing, seed=0):
    """What ``apply_experts`` hands the way out for ``TOKENS`` slots of
    which the first ``REAL`` are real: ``x`` float32, ``src = order //
    top_k`` from a stable sort with the fillers' and the absent experts'
    pairs keyed last, and the pairs that entered a group."""
    top_k, width, held, offset = ROUTINGS[routing]
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((TOKENS, width)), axis=-1)[:, :top_k]
    local = experts - offset
    valid = ((local >= 0) & (local < held)
             & (np.arange(TOKENS) < REAL)[:, None])
    flat = np.where(valid, local, held).reshape(-1)
    order = np.argsort(flat, kind="stable")
    x = rng.standard_normal((TOKENS, HIDDEN)).astype(np.float32)
    x[REAL:] = np.nan                     # what a filler's row may hold
    return (jnp.asarray(x), jnp.asarray(order // top_k, jnp.int32),
            int(valid.sum()))


def same_bits(got, want, rows):
    got, want = (np.asarray(a[:rows]).view(np.uint8) for a in (got, want))
    return np.array_equal(got, want)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_kernel_copies_every_row_of_a_group(engaged, routing, dtype):
    """Fillers (the last 14 tokens, NaN), absent pairs (a quarter of the
    router's experts held) and one expert a token: the rows under ``held``
    are ``x.astype(dtype)[src]`` bit for bit, whatever lies past them."""
    x, src, held = routed(routing)
    pairs = TOKENS * ROUTINGS[routing][0]
    assert 0 < held < pairs
    assert dispatch_supported(TOKENS, pairs, HIDDEN, jnp.dtype(dtype).itemsize)
    want = dispatch_reference(x, src, dtype)
    got = rows_to_experts(x, src, jnp.int32(held), dtype, use_pallas=True,
                          interpret=True)
    assert got.shape == (pairs, HIDDEN) and got.dtype == jnp.dtype(dtype)
    assert np.isfinite(np.asarray(want[:held], np.float32)).all()
    assert same_bits(got, want, held)
    # and it IS the kernel: the XLA form would have copied the tail too
    if routing == "top4_share_8_of_32":
        assert not same_bits(got, want, pairs)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_source_is_laid_in_one_pass(dtype):
    """``lay_rows`` (the cast and the re-laying as one kernel) writes the
    words the XLA form does: a row's lane tiles one under the other, a
    16-bit row's in pairs, tile ``2s`` in the low halves."""
    x, _, _ = routed("top1_of_16")
    got = lay_rows(x, dtype=jnp.dtype(dtype), interpret=True)
    want = row_pieces(x, dtype)
    assert got.shape == want.shape == (
        TOKENS, HIDDEN * jnp.dtype(dtype).itemsize // 512, 128)
    assert got.dtype == want.dtype
    assert same_bits(got, want, TOKENS)
    if dtype == "bfloat16":
        halves = np.asarray(got).view(np.uint16).reshape(TOKENS, -1, 128, 2)
        cast = np.asarray(x.astype(jnp.bfloat16)).view(np.uint16).reshape(
            TOKENS, -1, 2, 128)
        np.testing.assert_array_equal(halves[..., 0], cast[:, :, 0])
        np.testing.assert_array_equal(halves[..., 1], cast[:, :, 1])


@pytest.mark.parametrize("rows", [16, 32, 64])
def test_any_block_of_rows_gives_the_same_bits(rows):
    """The block is how the work is cut, not what is copied: 512 pairs in
    blocks of 16 to 64 rows (the copies of a step are started during the
    one before; the turns past the last held block move nothing) equal one
    block of 512, and all of them the gather."""
    x, src, held = routed("top8_of_64")
    x3, want = lay_rows(x, dtype=jnp.bfloat16, interpret=True), (
        dispatch_reference(x, src, jnp.bfloat16))
    call = dict(dtype=jnp.bfloat16, interpret=True)
    whole = dispatch_rows(x3, src, jnp.int32(held), rows=512, **call)
    cut = dispatch_rows(x3, src, jnp.int32(held), rows=rows, **call)
    assert same_bits(cut, whole, held) and same_bits(cut, want, held)
    # every row asked for: nothing is left out at the end of a block
    everything = dispatch_rows(x3, src, jnp.int32(src.shape[0]), rows=rows,
                               **call)
    assert same_bits(everything, want, src.shape[0])


def test_nothing_held_fetches_nothing():
    x, src, _ = routed("top1_of_16")
    got = dispatch_rows(row_pieces(x * jnp.nan, jnp.bfloat16), src,
                        jnp.int32(0), dtype=jnp.bfloat16, rows=16,
                        interpret=True)
    assert got.shape == (TOKENS, HIDDEN)


def test_the_one_predicate():
    """``dispatch_supported`` at the (slots, pairs, hidden) of the seven
    routed cells' programs: the every-slot programs of 32,768 slots of
    2,048 — a bfloat16 source of 128 MiB, where XLA's gather no longer
    reads its source about once — take the kernel; every compact program
    and every 2,048-position program (sources of 48 to 96 MiB) keep XLA's
    gather, whether the kernel could run there (``dispatch_takes``) or
    not."""
    assert XLA_KEEPS_BYTES == 112 << 20
    cells = {
        # cell's program: slots, a token's experts, hidden, kernel?
        "olmoe-s128-fullwindow": (32768, 8, 2048, True),
        "olmoe-s128-memo": (24576, 8, 2048, False),
        "zaya1-s128-fullwindow": (32768, 1, 2048, True),
        "zaya1-s128-memo": (24576, 1, 2048, False),
        "laguna-s2048 3/4": (12288, 10, 3072, False),
        "laguna-s2048 every": (16384, 10, 3072, False),
        "joyai-s2048 3/4": (12288, 8, 2048, False),
        "joyai-s2048 every": (16384, 8, 2048, False),
        "nemotron3-s2048 3/4": (12288, 6, 2688, False),
        "nemotron3-s2048 every": (16384, 6, 2688, False),
        # the parity sample's bucket of 8 and one row
        "olmoe bucket 8": (1024, 8, 2048, False),
        "olmoe bucket 1": (128, 8, 2048, False),
    }
    for cell, (slots, top_k, hidden, kernel) in cells.items():
        assert dispatch_supported(slots, slots * top_k, hidden, 2) == kernel, (
            cell)
    # the line itself: 28,672 rows of 2,048 (112 MiB) were gathered at
    # XLA's fast pace, 29,184 (114 MiB) at its slow one
    assert not dispatch_supported(28672, 28672 * 8, 2048, 2)
    assert dispatch_supported(29184, 29184 * 8, 2048, 2)
    # what the kernel could run at is wider than where it pays: rows of
    # whole 32-bit lane tiles (2,688 bfloat16 are 10 1/2), whole blocks
    assert dispatch_takes(12288 * 10, 3072, 2)
    assert not dispatch_takes(16384 * 6, 2688, 2)
    assert dispatch_takes(16384 * 6, 2688, 4)
    assert not dispatch_takes(1000, 2048, 2)
    for pairs in (262144, 32768, 512, 48):
        tm = dispatch_tile(pairs)
        assert tm in ROW_TILES and pairs % tm == 0
        assert dispatch_vmem_bytes(tm, 2048, 2) <= VMEM_CEILING
    # a shape the predicate declines runs the XLA form, asked or not
    x, src, held = routed("top8_of_64")
    got = rows_to_experts(x, src, jnp.int32(held), jnp.bfloat16,
                          use_pallas=True, interpret=True)
    assert same_bits(got, dispatch_reference(x, src, jnp.bfloat16),
                     src.shape[0])


def test_under_jit_the_layers_share_one_trace(engaged):
    x, src, held = routed("top8_of_64")

    @jax.jit
    def two_layers(x, src, held):
        return tuple(rows_to_experts(x + i, src, held, jnp.bfloat16,
                                     use_pallas=True, interpret=True)
                     for i in range(2))

    before = dispatch_rows._cache_size(), lay_rows._cache_size()
    first, second = two_layers(x, src, jnp.int32(held))
    assert dispatch_rows._cache_size() - before[0] <= 1
    assert lay_rows._cache_size() - before[1] <= 1
    assert same_bits(first, dispatch_reference(x, src, jnp.bfloat16), held)
    assert same_bits(second, dispatch_reference(x + 1, src, jnp.bfloat16),
                     held)


# ------------------------------------------------- through apply_experts
LAYER_CFG = OlmoeConfig(
    vocab_size=512, hidden_size=256, intermediate_size=128,
    num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
    num_experts=8, num_experts_per_tok=2)
SLOTS, REAL_SLOTS = 128, 100


def a_layer(stored, gated):
    layer = jax.tree.map(
        lambda a: a.astype(stored),
        jax.jit(lambda k: init_olmoe_params(k, LAYER_CFG))(
            jax.random.PRNGKey(53)))["layers"][0]
    if not gated:
        layer = {k: v for k, v in layer.items() if k != "gate_proj"}
    return layer


def a_routing(top_k, width, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((SLOTS, 256)), jnp.float32),
            jnp.asarray(np.argsort(rng.random((SLOTS, width)), -1)[:, :top_k],
                        jnp.int32),
            jnp.asarray(rng.random((SLOTS, top_k)), jnp.float32),
            jnp.arange(SLOTS) < REAL_SLOTS)


@pytest.mark.parametrize("share", [False, True], ids=["all_held", "a_share"])
@pytest.mark.parametrize("stored", ["bfloat16", "float32"])
@pytest.mark.parametrize("gated", [True, False], ids=["gated", "relu2"])
def test_apply_experts_is_the_same_bits_with_the_row_fetch(
        monkeypatch, fetches, gated, stored, share):
    """One sparse layer with its kernels asked for (interpreted), padded
    tokens, the experts all held or eight of a router's sixteen from the
    fourth on: with the way out through ``dispatch_rows`` the layer's result
    is BIT-equal to the same kernels behind XLA's gather (the rows of every
    group are the same bits, and no other row is read), and near the XLA
    form of the whole layer."""
    layer = a_layer(stored, gated)
    x, experts, weights, real = a_routing(2, 16 if share else 8)
    kw = dict(real=real, **(dict(router_width=16, expert_offset=4)
                            if share else {}))
    behind_xla, (sizes, _) = apply_experts(layer, x, experts, weights,
                                           **kw, **KERNELS)
    assert not fetches                               # nothing of it traced
    monkeypatch.setattr(dispatch_module, "XLA_KEEPS_BYTES", 0)
    fetched, (sizes_k, _) = apply_experts(layer, x, experts, weights,
                                          **kw, **KERNELS)
    assert fetches == [dispatch_tile(SLOTS * 2)]
    np.testing.assert_array_equal(sizes, sizes_k)
    assert 0 < int(sizes.sum()) < (REAL_SLOTS * 2 if share else SLOTS * 2)
    np.testing.assert_array_equal(fetched, behind_xla)
    assert np.isfinite(np.asarray(fetched)).all()
    assert not np.asarray(fetched)[REAL_SLOTS:].any()
    plain, _ = apply_experts(layer, x, experts, weights, **kw)
    scale = float(np.abs(np.asarray(plain)).max())
    np.testing.assert_allclose(
        fetched, plain, rtol=0,
        atol=scale * (1e-5 if stored == "float32" else 1e-2))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_rows_past_the_last_group_reach_nothing(monkeypatch, kernels):
    """The row fetch writes no row from the block after the last held one
    on, and the rest of that block holds whatever the slot held: poisoned
    here from ``sum(group_sizes)`` on, in both forms of the grouped calls,
    the layer's result does not move."""
    layer = a_layer("float32", True)
    x, experts, weights, real = a_routing(2, 8, seed=1)
    kw = dict(real=real, **(KERNELS if kernels else {}))
    want, _ = apply_experts(layer, x, experts, weights, **kw)
    poisoned = []

    def poisoning(x, src, held, dtype, **kw):
        rows = rows_to_experts(x, src, held, dtype, **kw)
        poisoned.append(int(rows.shape[0] - held))
        return jnp.where((jnp.arange(rows.shape[0]) < held)[:, None], rows,
                         jnp.nan)

    monkeypatch.setattr(olmoe, "rows_to_experts", poisoning)
    got, _ = apply_experts(layer, x, experts, weights, **kw)
    assert poisoned == [(SLOTS - REAL_SLOTS) * 2]
    np.testing.assert_array_equal(got, want)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("capacity", [None, 384],
                         ids=["every_slot", "three_quarters"])
def test_olmoe_predict_is_the_same_bits_with_the_row_fetch(
        monkeypatch, fetches, capacity):
    """The whole encoder with its kernels asked for, four rows of 128
    positions (full, ragged, one token, none): at a shape that engages the
    row fetch the answer is BIT-equal to the one at a shape that does not,
    and both are the XLA form's to rounding."""
    cfg = dataclasses.replace(LAYER_CFG, num_hidden_layers=2)
    params = jax.tree.map(
        lambda a: a.astype(jnp.float32),
        jax.jit(lambda k: init_olmoe_params(k, cfg))(jax.random.PRNGKey(5)))
    rng = np.random.default_rng(5)
    ids = rng.integers(0, cfg.vocab_size, (4, 128)).astype(np.int32)
    mask = np.arange(128)[None, :] < np.asarray([128, 37, 1, 0])[:, None]
    assert cfg.core_refusal(128) is None
    kw = dict(capacity=capacity, **KERNELS)
    behind_xla = olmoe_predict(params, ids, mask, cfg, **kw)
    assert not fetches
    monkeypatch.setattr(dispatch_module, "XLA_KEEPS_BYTES", 0)
    fetched = olmoe_predict(params, ids, mask, cfg, **kw)
    assert len(fetches) == cfg.num_hidden_layers
    np.testing.assert_array_equal(fetched, behind_xla)
    # (the row that holds no token reads its answer at a filler's place)
    np.testing.assert_allclose(
        fetched[:3],
        olmoe_predict(params, ids, mask, cfg, capacity=capacity)[:3],
        atol=1e-5, rtol=0)


# ------------------------------------------------ what the scorer says of it
@pytest.mark.parametrize("side", ["held", "declined_by_shape", "not_asked"])
def test_the_dispatch_site_is_counted_and_named(monkeypatch, side):
    """``kernel_snapshot()`` counts every routed launch at
    ``expert_dispatch`` — dispatched where the program's way out is the row
    fetch, a fallback where the predicate (a source XLA's gather reads about
    once) or the selector (a CPU mesh, nothing asked) left it XLA's gather —
    the compile ledger shows the program's trace entering ``dispatch_rows``
    once a sparse layer or not at all, and the launch's counters say how
    many of the rows its routed layers gathered went through it."""
    import time

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.models.text_encoder import (
        LAUNCH_COUNTERS,
    )
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
    if side != "declined_by_shape":
        monkeypatch.setattr(dispatch_module, "XLA_KEEPS_BYTES", 0)
    cfg = dataclasses.replace(LAYER_CFG, num_hidden_layers=2)
    scorer = FraudScorer(bert_config=cfg, config=config,
                         scorer_config=ScorerConfig(text_len=128),
                         mesh=build_mesh(devices=jax.devices()[:1]))
    recs = TransactionGenerator(num_users=8, num_merchants=4).generate_batch(5)
    # another test on this worker may have traced the same program
    score_fused_packed.clear_cache()
    t0 = time.time()
    pending = scorer.dispatch(recs)
    assert len(scorer.finalize(pending)) == 5
    snap = scorer.kernel_snapshot()
    held = int(side == "held")
    assert snap["dispatch"]["expert_dispatch"] == held
    assert snap["fallback"]["expert_dispatch"] == 1 - held
    traces = [r for r in scorer.host_stats()["compile"]["records"]
              if r["phase"] == "trace" and r["start"] >= t0
              and "score_fused_packed" in r["program"]]
    assert len(traces) == 1
    entered = {name: times for name, (times, _) in
               traces[0].get("nested", {}).items()}
    for kernel in ("lay_rows", "dispatch_rows"):
        assert entered.get(kernel, 0) == held * cfg.num_hidden_layers
    assert {"dispatch_rows", "dispatch_kernel_rows"} <= set(LAUNCH_COUNTERS)
    c = pending.counters
    gathered = 8 * 128 * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert c["dispatch_rows"] == gathered
    assert c["dispatch_kernel_rows"] == held * gathered
    # the site names its refusal by the launch's shape
    site = {s.name: s for s in scorer._text.sites}["expert_dispatch"]
    refusal = site.refusal(cfg, 128, 8 * 128)
    if side == "declined_by_shape":
        assert "0 MiB" in refusal and "1024 slots" in refusal
    else:
        assert refusal is None
    assert "whole 32-bit lane tiles" in site.refusal(
        dataclasses.replace(cfg, hidden_size=128), 128, 8 * 128)
