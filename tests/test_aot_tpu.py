"""Ask the installed TPU compiler, without a chip, whether the Pallas kernels
of the main path still compile at the widths the repo deploys.

The compile targets a DESCRIBED ``v5e:2x2`` topology (nothing runs, nothing
is timed): what Mosaic refuses here it refuses on the chip, and interpret
mode on the CPU cannot show that. Skipped where the topology cannot be
described (no libtpu). The persistent compile cache is off around these:
an AOT executable for an absent chip is written but can never be read
back, and every later read would warn.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.models.bert import BertConfig

FULL = BertConfig()           # DistilBERT-base widths: 6 x 768, FFN 3072
TEXT_LEN = 64
DEPLOYED_TEXT_LEN = 512       # benchmarks/configs/distilbert-s512.json
BUCKET = 256
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _shapes_of(tree, sharding):
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), tree)


@pytest.mark.parametrize("k,n", [(768, 768), (768, 3072), (3072, 768)])
def test_dequant_matmul_compiles_at_distilbert_widths(one_chip, k, n):
    from realtime_fraud_detection_tpu.ops import (
        dequant_matmul,
        matmul_supported,
    )

    m = BUCKET * TEXT_LEN
    assert matmul_supported(m, k, n)
    text = dequant_matmul.lower(
        _sds((m, k), jnp.float32, one_chip), _sds((k, n), jnp.int8, one_chip),
        _sds((n,), jnp.float32, one_chip), _sds((n,), jnp.float32, one_chip),
    ).compile().as_text()
    assert CUSTOM_CALL in text


def test_dequant_rows_compiles_at_position_table_shape(one_chip):
    from realtime_fraud_detection_tpu.ops import dequant_rows, rows_supported

    assert rows_supported(TEXT_LEN, FULL.hidden_size)
    text = dequant_rows.lower(
        _sds((TEXT_LEN, FULL.hidden_size), jnp.int8, one_chip),
        _sds((TEXT_LEN,), jnp.float32, one_chip)).compile().as_text()
    assert CUSTOM_CALL in text


def test_dequant_rows_declines_the_word_gather_above_bucket_32():
    """At full width the word-embedding widen is the XLA expression from
    bucket 128 up: the predicate says so (and the scorer counts it as a
    dequant_matmul-site fallback) rather than a kernel failing late."""
    from realtime_fraud_detection_tpu.ops import rows_supported

    h = FULL.hidden_size
    assert rows_supported(32 * TEXT_LEN, h)
    assert not rows_supported(128 * TEXT_LEN, h)
    assert not rows_supported(BUCKET * TEXT_LEN, h)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("bucket,text_len", [
    (1, DEPLOYED_TEXT_LEN), (8, DEPLOYED_TEXT_LEN),
    (BUCKET, DEPLOYED_TEXT_LEN),    # the bucket programs the cells warm
    (BUCKET, 128),                  # the shortest window the core takes
])
def test_flash_attention_compiles(one_chip, bucket, text_len, dtype):
    from realtime_fraud_detection_tpu.ops import (
        flash_attention,
        flash_supported,
    )

    assert flash_supported(text_len, FULL.head_dim, FULL.num_heads)
    qkv = _sds((bucket, text_len, FULL.hidden_size), dtype, one_chip)
    text = flash_attention.lower(
        qkv, qkv, qkv, _sds((bucket, text_len), jnp.bool_, one_chip),
        num_heads=FULL.num_heads).compile().as_text()
    assert CUSTOM_CALL in text


@pytest.mark.parametrize("b", [1, BUCKET])
def test_epilogue_compiles(one_chip, b):
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.ops import (
        epilogue_supported,
        fused_epilogue,
    )
    from realtime_fraud_detection_tpu.scoring.pipeline import MODEL_NAMES
    from realtime_fraud_detection_tpu.utils.config import Config

    m = len(MODEL_NAMES)
    assert epilogue_supported(b, m)
    params = EnsembleParams.from_config(Config(), list(MODEL_NAMES))
    text = jax.jit(
        lambda p, v, r: fused_epilogue(p, v, r, params)).lower(
        _sds((b, m), jnp.float32, one_chip), _sds((b, m), jnp.bool_, one_chip),
        _sds((b,), jnp.float32, one_chip)).compile().as_text()
    assert CUSTOM_CALL in text


def _quantized(models):
    from realtime_fraud_detection_tpu.models.quant import (
        quantize_bert_params,
    )

    return models.replace(
        bert=quantize_bert_params(jax.device_get(models.bert)))


def test_whole_program_with_kernels_compiles_at_bucket_256(one_chip):
    """The served program — packed blobs in, one matrix out — with the
    int8 text branch and every per-site kernel on, at full depth."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    sc = ScorerConfig(text_len=TEXT_LEN)
    models = _quantized(init_scoring_models(jax.random.PRNGKey(0),
                                            bert_config=FULL))
    blobs, spec = pack_tree(make_example_batch(BUCKET, sc))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=FULL,
        use_pallas=True, tree_kernel="gemm", iforest_kernel="gemm",
        dequant_kernel="pallas", epilogue_kernel="pallas").compile()
    # six dense sites per layer (at 64 tokens the attention site is the
    # reference: flash_supported), the position-row widen, the epilogue;
    # the word-row widen is declined at this bucket (see above)
    assert compiled.as_text().count(CUSTOM_CALL) == FULL.num_layers * 6 + 2
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


# text_split.family(256, 128, 512, bucket_for): the programs bucket 256 of
# the 512-token deployment launches once its batches split; a test below
# holds the list to the rule
BUCKET_256_FAMILY = [(256, 512), (256, 128), (8, 512), (32, 512)]


def test_the_aot_family_is_the_rules_own():
    from realtime_fraud_detection_tpu.core.batching import bucket_for
    from realtime_fraud_detection_tpu.ops import narrowest_supported_len
    from realtime_fraud_detection_tpu.scoring import text_split

    narrow = narrowest_supported_len(FULL.head_dim, FULL.num_heads)
    assert list(text_split.family(BUCKET, narrow, DEPLOYED_TEXT_LEN,
                                  bucket_for)) == BUCKET_256_FAMILY


@pytest.mark.parametrize("rows,text_len", BUCKET_256_FAMILY)
def test_deployed_program_holds_the_fused_core_at_bucket_256(
        one_chip, rows, text_len):
    """What the benchmark's cells launch — f32 weights, kernel plane off,
    the selector's choice on a TPU — for every member of bucket 256's
    family at 512 tokens (the unsplit program, the 128-wide one the short
    rows run in, the long rows' 8 and 32): one Mosaic call a layer, and
    the f32 score tensor (4.23 GB of temporaries with the reference core
    at 256 x 512) not reserved."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    sc = ScorerConfig(text_len=text_len)
    models = init_scoring_models(jax.random.PRNGKey(0), bert_config=FULL)
    blobs, spec = pack_tree(make_example_batch(rows, sc))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=FULL,
        use_pallas=True).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == FULL.num_layers
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


# ---- the routed text encoders (models/olmoe.py, models/zaya.py) at
# published widths
# (rows at the 3/4 rung, at every slot; groups; hidden; one expert's width):
# what each routed cell launches — OLMoE bucket 256 x 128 tokens x 8 experts,
# ZAYA1 the same slots x 1, Laguna 8 x 2,048 x 10 with 64 of 256 experts
# held, JoyAI 8 x 2,048 x 8
GATED_SITES = {"olmoe": (196608, 262144, 64, 2048, 1024),
               "zaya1": (24576, 32768, 16, 2048, 2048),
               "laguna": (122880, 163840, 64, 3072, 1024),
               "joyai": (98304, 131072, 256, 2048, 768)}
# the grouped calls' rows in every OTHER program a deployment launches, and
# the cells compile beside their bucket's (the parity sample's bucket 8):
# core/batching.BATCH_BUCKETS 1, 8, 32, 128 x text_split.capacities x
# experts a token for the 128-position encoders, bucket 1 of the
# 2,048-position ones (tests/test_olmoe.py SMALL_LAUNCHES derives them)
SMALL_ROWS = {"olmoe": (1024, 8192, 24576, 32768, 98304, 131072),
              "zaya1": (128, 1024, 3072, 4096, 12288, 16384),
              "laguna": (20480,), "joyai": (16384,)}


def _grouped_call(one_chip, rows, groups, k, n, gated):
    """The lowered text of one grouped call asked for its kernel at
    ``[rows, k] x [groups, k, n]``, and the tile the rule gave it."""
    from realtime_fraud_detection_tpu.ops.grouped_matmul import (
        VMEM_CEILING,
        down_vmem_bytes,
        gated_vmem_bytes,
        gmm_tiling,
        grouped_gated_matmul,
        grouped_matmul,
        grouped_matmul_supported,
    )

    assert grouped_matmul_supported(rows, k, n)
    tiling = gmm_tiling(rows, k, n, groups, gated=gated)
    # K in one block, and the budget the call names holds it
    assert tiling[1] == k
    if gated:
        assert gated_vmem_bytes(*tiling) <= VMEM_CEILING
        fn = jax.jit(lambda x, a, b, g: grouped_gated_matmul(
            x, a, b, g, out_dtype=jnp.bfloat16, use_pallas=True))
    else:
        assert down_vmem_bytes(*tiling) <= VMEM_CEILING
        fn = jax.jit(lambda x, a, b, g: grouped_matmul(
            x, a, g, use_pallas=True))
    weights = _sds((groups, k, n), jnp.bfloat16, one_chip)
    text = fn.lower(_sds((rows, k), jnp.bfloat16, one_chip), weights, weights,
                    _sds((groups,), jnp.int32, one_chip)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1
    return text, tiling


@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
@pytest.mark.parametrize("encoder", sorted(GATED_SITES))
def test_grouped_matmul_compiles_at_published_widths(one_chip, encoder, rung):
    """down's ``down_gmm`` as ONE Mosaic call at the tile ``gmm_tiling``
    picks, for all four routed encoders at both capacities: its result block
    is ``(tm, tn / 128, 128)`` of ``f32[rows, hidden / 128, 128]`` — each
    row one contiguous piece — and the call names its own budget."""
    *rungs, groups, hidden, width = GATED_SITES[encoder]
    rows = rungs[rung]
    text, _ = _grouped_call(one_chip, rows, groups, width, hidden, False)
    assert "jit(down_gmm)/down_gmm/pallas_call" in text
    assert f"f32[{rows},{hidden // 128},128]" in text
    assert f"f32[{rows},{hidden}]" not in text
    if (encoder, rung) != ("olmoe", 1):
        return
    # the XLA form lowers to the compiler's own grouped kernel, whose custom
    # calls carry no scope in their op_name: why the chip runs the Pallas one
    from realtime_fraud_detection_tpu.ops.grouped_matmul import (
        grouped_matmul,
    )

    xla = jax.jit(lambda a, b, g: grouped_matmul(a, b, g)).lower(
        _sds((rows, width), jnp.bfloat16, one_chip),
        _sds((groups, width, hidden), jnp.bfloat16, one_chip),
        _sds((groups,), jnp.int32, one_chip)).compile().as_text()
    assert 'op_name="ragged-dot' in xla


@pytest.mark.parametrize("rung", [0, 1], ids=["three_quarters", "every_slot"])
@pytest.mark.parametrize("encoder", sorted(GATED_SITES))
def test_gated_matmul_compiles_at_published_widths(one_chip, encoder, rung):
    """gate, up and SiLU ⊙ as ONE Mosaic call at the tile ``gmm_tiling``
    picks, for all four routed encoders at both capacities: K and N whole
    (768 in one block, 3072 in one block; ZAYA1's pair of 2048 x 2048
    blocks), so two right-hand blocks a step take 12 to 32 MB double
    buffered — past the 16 MB a call gets unasked — and the call names its
    own budget, under the rule's ceiling; what Mosaic refuses for VMEM it
    refuses here."""
    *rungs, groups, k, n = GATED_SITES[encoder]
    rows = rungs[rung]
    text, tiling = _grouped_call(one_chip, rows, groups, k, n, True)
    assert tiling[1:] == (k, n)
    assert "jit(gated_gmm)/gated_gmm/pallas_call" in text
    # neither float32 product exists outside the kernel
    assert f"f32[{rows},{n}]" not in text
    assert f"bf16[{rows},{n}]" in text


@pytest.mark.parametrize("kernel", ["gated", "down"])
@pytest.mark.parametrize("encoder,rows", [
    (encoder, rows) for encoder in sorted(SMALL_ROWS)
    for rows in SMALL_ROWS[encoder]])
def test_the_small_buckets_grouped_calls_compile(one_chip, encoder, rows,
                                                 kernel):
    """No cell times the programs under its bucket, and a rule from ``rows
    // groups`` narrows their row tile: each of their grouped calls is held
    here, ONE Mosaic call inside its budget at the rule's tile (8 to 2,048
    rows a group: a 128-row tile but for OLMoE's bucket 128 at every
    slot)."""
    *_, groups, hidden, width = GATED_SITES[encoder]
    k, n = (hidden, width) if kernel == "gated" else (width, hidden)
    _, tiling = _grouped_call(one_chip, rows, groups, k, n, kernel == "gated")
    assert tiling[0] == (256 if (encoder, rows) == ("olmoe", 131072)
                         else 128)


# a token's experts, by encoder: the combine's tokens are the grouped calls'
# rows over them
TOP_K = {"olmoe": 8, "zaya1": 1, "laguna": 10, "joyai": 8}


@pytest.mark.parametrize("encoder,rows", [
    (encoder, rows) for encoder in sorted(GATED_SITES)
    for rows in SMALL_ROWS[encoder] + GATED_SITES[encoder][:2]])
def test_the_combine_compiles_at_every_bucket_and_rung(one_chip, encoder,
                                                       rows):
    """The experts' way home (``ops/combine.py``) as ONE Mosaic call at the
    block of tokens ``combine_tokens`` picks, for all four routed encoders
    at every (bucket, rung) a deployment launches — 128 tokens of ZAYA1's
    bucket 1 to 32,768 — single rows fetched out of ``f32[rows, hidden /
    128, 128]`` in HBM (out of ``f32[rows, hidden]`` Mosaic refuses a
    one-row copy: "Slice shape along dimension 0 must be aligned to tiling
    (8)"), inside the budget the call names; no ``[pairs, hidden]`` array
    leaves it."""
    from realtime_fraud_detection_tpu.ops.combine import (
        combine_supported,
        combine_tokens,
        combine_vmem_bytes,
        weighted_combine,
    )
    from realtime_fraud_detection_tpu.ops.grouped_matmul import VMEM_CEILING

    hidden, top_k = GATED_SITES[encoder][3], TOP_K[encoder]
    tokens = rows // top_k
    assert combine_supported(tokens, top_k, hidden)
    assert combine_vmem_bytes(combine_tokens(tokens, top_k, hidden), top_k,
                              hidden) <= VMEM_CEILING
    fn = jax.jit(lambda out3, home, weights, valid: weighted_combine(
        out3, home, weights, valid, use_pallas=True))
    text = fn.lower(
        _sds((rows, hidden // 128, 128), jnp.float32, one_chip),
        _sds((tokens, top_k), jnp.int32, one_chip),
        _sds((tokens, top_k), jnp.float32, one_chip),
        _sds((tokens, top_k), jnp.bool_, one_chip)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1
    assert "jit(combine_rows)/weighted_combine/pallas_call" in text
    assert f"f32[{tokens},{hidden}]" in text
    # (one expert a token: the pairs' shape is the tokens' own)
    assert top_k == 1 or f"f32[{rows},{hidden}]" not in text


# the programs whose way out is the row fetch: a cell's every-slot program
# of 32,768 slots of 2,048 (slots, a token's experts, hidden)
DISPATCH_SITES = {"olmoe_every_slot": (32768, 8, 2048),
                  "zaya1_every_slot": (32768, 1, 2048)}


@pytest.mark.parametrize("site", sorted(DISPATCH_SITES))
def test_the_row_fetch_compiles_at_the_every_slot_programs(one_chip, site):
    """The experts' way out (``ops/dispatch.py``) as TWO Mosaic calls at
    OLMoE's and ZAYA1's every-slot shapes: ``lay_rows`` (the cast and the
    re-laying, one pass: the slots' bfloat16 rows as ``u32[slots, hidden /
    256, 128]``, a row one contiguous piece) and ``dispatch_rows`` at the
    block of rows ``dispatch_tile`` picks (single rows fetched out of that
    array in HBM, written as the dense ``bf16[pairs, hidden]`` the grouped
    kernels read), inside the budget the call names; XLA adds no pass of
    its own between them: the source exists once."""
    import re

    from realtime_fraud_detection_tpu.ops.dispatch import (
        dispatch_supported,
        dispatch_tile,
        dispatch_vmem_bytes,
        rows_to_experts,
    )
    from realtime_fraud_detection_tpu.ops.grouped_matmul import VMEM_CEILING

    slots, top_k, hidden = DISPATCH_SITES[site]
    pairs = slots * top_k
    assert dispatch_supported(slots, pairs, hidden, 2)
    assert dispatch_vmem_bytes(dispatch_tile(pairs), hidden,
                               2) <= VMEM_CEILING
    fn = jax.jit(lambda x, src, held: rows_to_experts(
        x, src, held, jnp.bfloat16, use_pallas=True))
    compiled = fn.lower(_sds((slots, hidden), jnp.float32, one_chip),
                        _sds((pairs,), jnp.int32, one_chip),
                        _sds((), jnp.int32, one_chip)).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 2
    assert "jit(lay_rows)/lay_rows/pallas_call" in text
    assert "jit(dispatch_rows)/dispatch_rows/pallas_call" in text
    assert f"u32[{slots},{hidden // 256},128]" in text
    entry = text[text.index("ENTRY "):]
    assert not re.search(rf" = \w+\[{slots},[\d,]+\]\S* (copy|fusion)\(",
                         entry)
    # the call's result is the array itself, row-major: nothing re-lays it
    assert re.search(rf"ROOT %\S+ = bf16\[{pairs},{hidden}\]\S* "
                     r"custom-call\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * (
        slots * hidden * 2)


def test_a_program_that_keeps_xlas_gather_traces_no_row_fetch(one_chip):
    """OLMoE's compact program (24,576 slots: a source of 96 MiB) asked for
    its kernels: the predicate declines, the way out is XLA's gather and no
    Mosaic call."""
    from realtime_fraud_detection_tpu.ops.dispatch import (
        dispatch_rows,
        rows_to_experts,
    )

    before = dispatch_rows._cache_size()
    text = jax.jit(lambda x, src, held: rows_to_experts(
        x, src, held, jnp.bfloat16, use_pallas=True)).lower(
            _sds((24576, 2048), jnp.float32, one_chip),
            _sds((196608,), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip)).compile().as_text()
    assert CUSTOM_CALL not in text and " gather(" in text
    assert dispatch_rows._cache_size() == before


@pytest.mark.parametrize("bucket,text_len", [
    (BUCKET, 128), (8, 128), (1, 128), (32, DEPLOYED_TEXT_LEN)],
    ids=["bucket256", "parity_bucket8", "bucket1", "halo_at_512"])
def test_cca_mix_compiles_at_published_widths(one_chip, bucket, text_len):
    """ZAYA1's fused mixing at 8 + 2 heads of 128: the cell's bucket (four
    rows a grid step: 12.6 MB of double-buffered blocks beside the body's
    temporaries, over Mosaic's default budget, so the call names its own),
    the parity sample's bucket of 8 (the same lowering), one row, and a
    window of four blocks a row (the halo inputs)."""
    from realtime_fraud_detection_tpu.models.olmoe import rope_tables
    from realtime_fraud_detection_tpu.models.zaya import ZayaConfig
    from realtime_fraud_detection_tpu.ops import cca_mix_fused

    cfg = ZayaConfig()
    assert cfg.mix_refusal(text_len) is None
    heads, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
    cos, sin = rope_tables(text_len, cfg.rotary_dim, cfg.rope_theta)
    fn = jax.jit(lambda c, v, dw, gw, temp: cca_mix_fused(
        c, v, dw, gw, temp, cos, sin, num_heads=heads, num_kv_heads=kv,
        eps=cfg.rms_norm_eps))
    compiled = fn.lower(
        _sds((heads + kv, bucket, text_len, d), jnp.float32, one_chip),
        _sds((bucket, text_len, kv * d), jnp.float32, one_chip),
        _sds((2, (heads + kv) * d), jnp.float32, one_chip),
        _sds((heads + kv, 2 * d, d), jnp.bfloat16, one_chip),
        _sds((kv,), jnp.float32, one_chip)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


def _two_layers(encoder):
    if encoder == "olmoe":
        from realtime_fraud_detection_tpu.models.olmoe import OlmoeConfig

        return OlmoeConfig(num_hidden_layers=2)
    from realtime_fraud_detection_tpu.models.zaya import ZayaConfig

    return ZayaConfig(num_hidden_layers=2)


@pytest.mark.parametrize("capacity", [None, 24576],
                         ids=["every_slot", "three_quarters"])
@pytest.mark.parametrize("encoder", ["olmoe", "zaya1"])
def test_routed_program_compiles_with_every_large_pass_under_a_scope(
        one_chip, encoder, capacity):
    """The served packed program with an ``OlmoeConfig`` or a ``ZayaConfig``
    (two of the published layers, every width as published, bucket 256 x 128
    tokens), at both capacities of that bucket's routed blocks
    (``scoring/text_split.capacities``):
    four Mosaic calls a layer (the experts' three — gate, up and SiLU ⊙ as
    ``gated_gmm``, down as ``down_gmm``, with no float32 ``[rows, I]``
    between them, and the way home as ``weighted_combine``, with no
    ``[pairs, hidden]`` float32 array anywhere: no gather of one, no
    relayout ``copy`` of one — two more in the every-slot program, whose way
    out is ``lay_rows`` and the row fetch ``dispatch_rows`` under
    ``experts/dispatch`` (a source of 128 MiB:
    ``ops.dispatch.dispatch_supported``), where the
    three-quarters program keeps XLA's gather and holds nothing of that
    kernel — and, at the
    attention site, OLMoE's fused causal core ``windowed_attention`` or
    ZAYA1's fused mixing ``ops/cca_mix.py``), a second small output,
    temporaries that
    leave room for the cell's layers of weights in 16 GB — and no
    instruction that
    writes 64 MB or more without a named scope in its ``op_name`` (what a
    device trace would count as ``unscoped``), and no conditional: a
    ``cond`` ahead of ``experts/`` in an ``op_name`` would hide the block
    from the trace's attribution. With OLMoE, nothing under ``attn_proj``
    but the four projections (and the one rounding of the normed input that
    three of them read) writes an array of a launch's ``[256, 128, 2048]``
    elements: QK-norm, RoPE, the head split and the merge left with the XLA
    core (PERF.md, PR 34)."""
    import re

    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    config = _two_layers(encoder)
    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        BUCKET, ScorerConfig(text_len=128)))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=config,
        use_pallas=True, text_capacity=capacity).compile()
    text = compiled.as_text()
    # the experts' two grouped kernels and their combine, and the attention
    # site's kernel
    fetch = capacity is None
    assert text.count(CUSTOM_CALL) == (
        4 + 2 * fetch) * config.num_hidden_layers
    site = "cca_mix" if encoder == "zaya1" else "windowed_attention"
    for kernel in (site, "gated_gmm", "down_gmm", "weighted_combine"):
        assert len(re.findall(rf"%{kernel}\S* = .*custom-call\(", text)) == (
            config.num_hidden_layers)
    for kernel in ("lay_rows", "dispatch_rows"):
        assert len(re.findall(rf"%{kernel}\S* = .*custom-call\(",
                              text)) == fetch * config.num_hidden_layers
        assert (f"jit({kernel})" in text) == fetch
    # each under the scope the trace's attribution reads it by
    for part, call in (
            ("matmul", "jit(gated_gmm)/gated_gmm/pallas_call"),
            ("matmul", "jit(down_gmm)/down_gmm/pallas_call"),
            ("combine", "jit(combine_rows)/weighted_combine/pallas_call"),
            *([("dispatch", "jit(lay_rows)/lay_rows/pallas_call"),
               ("dispatch", "jit(dispatch_rows)/dispatch_rows/pallas_call")]
              if fetch else [])):
        assert len(re.findall(
            rf' custom-call\(.*op_name="[^"]*/experts/{part}/'
            rf'{re.escape(call)}"', text)) == config.num_hidden_layers, call
    rows = (capacity or BUCKET * 128) * config.num_experts_per_tok
    if encoder == "olmoe":
        # (ZAYA1's experts are as wide as its hidden state: down's result
        # has that shape)
        assert f"f32[{rows},{config.intermediate_size}]" not in text
        # the pairs' float32 rows exist in ONE form, the one down wrote
        # them in: nothing gathers them into, or copies them as, a [pairs,
        # hidden] array (at ZAYA1's one expert a token that shape is the
        # tokens' own)
        assert f"f32[{rows},{config.hidden_size}]" not in text
    assert f"f32[{rows},{config.hidden_size // 128},128]" in text
    for line in text.splitlines():
        # (under the combine: at one expert a token the compaction gather
        # of experts/dispatch has the pairs' shape)
        if "/experts/combine/" in line and re.search(
                rf" = f32\[{rows},[\d,]+\]\S* (copy|gather)\(", line):
            raise AssertionError(line)
    assert " conditional(" not in text and "cond/branch_" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 5 << 30
    entry = text[text.index("ENTRY "):]
    sizes = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}
    unnamed = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(%\S+) = (\w+)\[([\d,]+)\]\S* "
                     r"(fusion|copy|custom-call|transpose)\(", line)
        if not m or m.group(2) not in sizes:
            continue
        if 'custom_call_target="ConcatBitcast"' in line:
            # the compiler's own view of sliced prefetches into the fast
            # memory space as one array: a bitcast, it moves nothing (the
            # ZAYA1 program's q on its way to the core; the slices are 16 MB
            # each and read 0.08 ms a batch on the chip, where
            # unscoped_device_pct is 0.27: PERF.md, PR 30)
            continue
        nbytes = sizes[m.group(2)] * int(np.prod(
            [int(d) for d in m.group(3).split(",")]))
        op = re.search(r'op_name="jit\([^"]*?\)/([^"]*)"', line)
        if nbytes >= 64e6 and not (op and op.group(1).startswith("text/")):
            unnamed.append((m.group(1), nbytes))
    assert not unnamed, unnamed
    if encoder == "olmoe":
        slots = BUCKET * 128 * config.hidden_size
        passes = []
        for line in entry.splitlines():
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) "
                         r"(?:fusion|copy|custom-call|transpose|convolution)"
                         r"\(", line)
            op = re.search(r'op_name="[^"]*/attn_proj/([^"/]*)"', line)
            if m and op and any(
                    int(np.prod([int(d) for d in dims.split(",")])) >= slots
                    for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))):
                passes.append(op.group(1))
        assert sorted(passes) == sorted(
            (["dot_general"] * 4 + ["convert_element_type"])
            * config.num_hidden_layers), passes


# ----------------------------------------------------------------- Laguna
@pytest.mark.parametrize("heads,window", [(72, 512), (48, None)],
                         ids=["sliding_72_heads", "full_48_heads"])
def test_windowed_attention_compiles_at_published_widths(one_chip, heads,
                                                         window):
    """Laguna's fused causal core as the cell launches it: 8 rows x 2,048
    positions, groups of 9 or 6 query heads of 128 over 8 key-value heads,
    q and k float32 and rotated in VMEM (the YaRN half-head tables on a
    full layer, the default whole-head ones on a sliding one), the context
    gated in the epilogue, bfloat16 out."""
    from realtime_fraud_detection_tpu.models.laguna import (
        LagunaConfig,
        laguna_rope_tables,
    )
    from realtime_fraud_detection_tpu.ops import (
        rope_lane_tables,
        windowed_attention,
    )

    cfg, b, t, kv = LagunaConfig(), 8, 2048, 8
    assert cfg.core_refusal(t) is None
    rope = cfg.rope_sliding if window else cfg.rope_full
    *tables, shift = rope_lane_tables(
        *laguna_rope_tables(t, cfg.head_dim, rope), cfg.head_dim)
    fn = jax.jit(lambda q, k, v, lens, gate: windowed_attention(
        q, k, v, lens, num_heads=heads, num_kv_heads=kv, window=window,
        rope=tuple(tables), rope_shift=shift, gate=gate,
        out_dtype=jnp.bfloat16))
    compiled = fn.lower(
        _sds((b, t, heads * 128), jnp.float32, one_chip),
        _sds((b, t, kv * 128), jnp.float32, one_chip),
        _sds((b, t, kv * 128), jnp.bfloat16, one_chip),
        _sds((b,), jnp.int32, one_chip),
        _sds((b, t, heads), jnp.float32, one_chip)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("bucket", [256, 8, 1])
def test_windowed_attention_compiles_with_olmoes_operands(one_chip, bucket):
    """OLMoE's fused core as its cells launch it (bucket 256; 8 is the parity
    sample's, 1 the smallest): rows of one block of 128 positions, 16 heads
    of 128, q and k float32 as their projections wrote them, normed over all
    16 heads, rotated and split in VMEM (the one-block form), v and the
    context bfloat16."""
    from realtime_fraud_detection_tpu.models.olmoe import (
        OlmoeConfig,
        rope_tables,
    )
    from realtime_fraud_detection_tpu.ops import (
        rope_lane_tables,
        windowed_attention,
    )

    cfg, t = OlmoeConfig(), 128
    assert cfg.core_refusal(t) is None
    heads, width = cfg.num_attention_heads, cfg.hidden_size
    *tables, shift = rope_lane_tables(
        *rope_tables(t, cfg.head_dim, cfg.rope_theta), cfg.head_dim)
    fn = jax.jit(lambda q, k, v, lens, qw, kw: windowed_attention(
        q, k, v, lens, num_heads=heads, num_kv_heads=heads,
        rope=tuple(tables), rope_shift=shift, norm=(qw, kw),
        norm_eps=cfg.rms_norm_eps, out_dtype=jnp.bfloat16))
    compiled = fn.lower(
        _sds((bucket, t, width), jnp.float32, one_chip),
        _sds((bucket, t, width), jnp.float32, one_chip),
        _sds((bucket, t, width), jnp.bfloat16, one_chip),
        _sds((bucket,), jnp.int32, one_chip),
        _sds((width,), jnp.float32, one_chip),
        _sds((width,), jnp.float32, one_chip)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


def test_laguna_program_compiles_with_its_unlike_layers(one_chip):
    """The served packed program with a ``LagunaConfig``: layer 0 (full
    attention, the dense MLP) and one sliding sparse layer holding 64 of 256
    experts, every width as published, bucket 8 x 2,048 tokens at the
    three-quarters capacity: two fused cores, the experts' two grouped
    kernels and their combine, a second small output, no conditional, temporaries that leave
    room for the
    cell's five layers of weights in 16 GB."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models.laguna import (
        DENSE,
        FULL,
        SLIDING,
        SPARSE,
        LagunaConfig,
    )
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    config = LagunaConfig(
        num_hidden_layers=2, layer_types=(FULL, SLIDING),
        mlp_layer_types=(DENSE, SPARSE),
        num_attention_heads_per_layer=(48, 72), num_experts=64)
    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        8, ScorerConfig(text_len=2048)))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=config,
        use_pallas=True, text_capacity=12288).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 2 + 3
    for call in ("jit(gated_gmm)/gated_gmm", "jit(down_gmm)/down_gmm",
                 "jit(combine_rows)/weighted_combine"):
        assert text.count(f"{call}/pallas_call") == 1
    # ten experts a token: no [pairs, hidden] float32 array
    assert "f32[122880,3072]" not in text and "f32[122880,24,128]" in text
    assert text.count("windowed_attention") >= 2
    assert " conditional(" not in text and "cond/branch_" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 6 << 30


# ------------------------------------------------------------------ JoyAI
def test_latent_attention_compiles_at_published_widths(one_chip):
    """JoyAI-LLM-Flash's fused latent core as the cell launches it: 8 rows x
    2,048 positions, 32 heads whose scores run over 128 dims of their own
    plus 64 of ONE shared rotated key (two heads a step, their shared parts
    one lane tile, rotated interleaved in VMEM), values of 128, bfloat16
    operands as their projections wrote them, bfloat16 out."""
    from realtime_fraud_detection_tpu.models.joyai import (
        JoyaiConfig,
        joyai_rope_tables,
    )
    from realtime_fraud_detection_tpu.ops import (
        rope_pair_tables,
        windowed_attention,
    )

    cfg, b, t = JoyaiConfig(), 8, 2048
    assert cfg.core_refusal(t) is None
    heads, pe = cfg.num_attention_heads, cfg.qk_rope_head_dim
    *tables, shift = rope_pair_tables(
        *joyai_rope_tables(t, pe, cfg.rope_theta))
    fn = jax.jit(lambda q, k, v, lens, q_pe, k_pe: windowed_attention(
        q, k, v, lens, num_heads=heads, num_kv_heads=heads,
        rope=tuple(tables), rope_shift=shift, shared_key=(q_pe, k_pe),
        out_dtype=jnp.bfloat16))
    wide = _sds((b, t, heads * 128), jnp.bfloat16, one_chip)
    compiled = fn.lower(
        wide, wide, wide, _sds((b,), jnp.int32, one_chip),
        _sds((b, t, heads * pe), jnp.float32, one_chip),
        _sds((b, t, pe), jnp.float32, one_chip)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("capacity", [12288, None],
                         ids=["three_quarters", "every_slot"])
def test_joyai_program_compiles_with_all_256_experts(one_chip, capacity):
    """The served packed program with a ``JoyaiConfig``: layer 0 (the dense
    MLP) and one sparse layer holding all 256 experts, every width as
    published, bucket 8 x 2,048 tokens at both capacities: two fused latent
    cores, the experts' two grouped kernels and their combine, a second
    small output, no conditional, no ``[pairs, 2048]`` float32 array,
    no ``[8, 32, 2048, 2048]`` scores, temporaries that leave room for the
    cell's 10.6 GB of weights in 16 GB."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models.joyai import JoyaiConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    config = JoyaiConfig(num_hidden_layers=2)
    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        8, ScorerConfig(text_len=2048)))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=config,
        use_pallas=True, text_capacity=capacity).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 2 + 3
    for call in ("jit(gated_gmm)/gated_gmm", "jit(down_gmm)/down_gmm",
                 "jit(combine_rows)/weighted_combine"):
        assert text.count(f"{call}/pallas_call") == 1
    pairs = (capacity or 8 * 2048) * 8
    assert f"f32[{pairs},2048]" not in text
    assert f"f32[{pairs},16,128]" in text
    assert text.count("windowed_attention") >= 2
    assert " conditional(" not in text and "cond/branch_" not in text
    assert "f32[8,32,2048,2048]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


# --------------------------------------------------------------- Falcon-H1
@pytest.mark.parametrize("bucket", [8, 1])
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero",
                                                        "state_in"])
def test_ssd_scan_compiles_at_published_widths(one_chip, bucket, carried):
    """Falcon-H1-34B's state-space scan as the cell launches it: rows of
    2,048 positions in chunks of 128, 32 heads of 128 in two groups over a
    256-wide state, bfloat16 ``x``, ``B``, ``C`` and float32 steps; ONE
    kernel, with and without a state handed in."""
    from realtime_fraud_detection_tpu.models.falcon_h1 import FalconH1Config
    from realtime_fraud_detection_tpu.ops.ssd_scan import ssd_scan

    cfg, t = FalconH1Config(), 2048
    assert cfg.scan_refusal(t) is None
    h, p, g, n = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                  cfg.mamba_d_state)
    args = [_sds((bucket, t, h, p), jnp.bfloat16, one_chip),
            _sds((bucket, t, h), jnp.float32, one_chip),
            _sds((h,), jnp.float32, one_chip),
            _sds((bucket, t, g, n), jnp.bfloat16, one_chip),
            _sds((bucket, t, g, n), jnp.bfloat16, one_chip),
            _sds((h,), jnp.float32, one_chip)]
    if carried:
        args.append(_sds((bucket, h, n, p), jnp.float32, one_chip))
    compiled = jax.jit(lambda x, dt, a, b_in, c_in, d, state=None: ssd_scan(
        x, dt, a, b_in, c_in, d, chunk=cfg.mamba_chunk_size,
        initial_state=state, use_pallas=True)).lower(*args).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1
    # neither the [chunks, heads, 128, 128] masks nor the per-chunk states
    # are temporaries of the program: the running sums and B's transpose are
    assert compiled.memory_analysis().temp_size_in_bytes < bucket * (48 << 20)


def test_falconh1_program_compiles_with_both_kernels_in_every_layer(one_chip):
    """The served packed program with a ``FalconH1Config``: two of the
    parallel hybrid layers, every width as published, bucket 8 x 2,048
    tokens: the fused causal core (five query heads a key-value head), the
    scan's kernel and (PR 55) the convolution's in each layer — which reads
    x | B | C out of ``W_in``'s result where it lies, positions last: no
    float32 copy of a slice of it — ONE result, and temporaries that leave
    room for the cell's six layers of weights in 16 GB."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models.falcon_h1 import FalconH1Config
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    config = FalconH1Config(num_hidden_layers=2)
    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        8, ScorerConfig(text_len=2048)))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=config,
        use_pallas=True).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 2 + 2 + 2
    assert text.count("ssm_scan/jit(_ssd_pallas)/ssd_scan/pallas_call") >= 2
    assert text.count("ssm_conv/jit(_conv_pallas)/causal_conv/"
                      "pallas_call") >= 2
    assert "f32[8,9248,2048]{2,1,0}" in text       # the operand as it lies
    assert _float32_copies(text, "f32[8,2048,5120]",
                           "f32[8,2048,9248]") == []
    assert text.count("attn_core/jit(windowed_attention)/"
                      "windowed_attention/pallas_call") >= 2
    assert " conditional(" not in text and "cond/branch_" not in text
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes <= 1 << 12    # the one small matrix
    assert memory.temp_size_in_bytes < 4 << 30


def test_falconh1_weights_are_made_without_a_float32_copy(one_chip):
    """The builder's one jitted init at the published sizes: the 2.7 GB
    embedding is drawn a block of rows at a time (drawn whole, its float32
    normals stood beside 7.8 GB of weights and set-up's peak was the chip's
    whole memory: my chip run, PR 46)."""
    from realtime_fraud_detection_tpu.models.falcon_h1 import FalconH1Config
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    config = FalconH1Config(num_hidden_layers=6)
    memory = jax.jit(
        lambda key: init_scoring_models(key, bert_config=config)).lower(
        _sds((2,), jnp.uint32, one_chip)).compile().memory_analysis()
    assert 7.8e9 < memory.output_size_in_bytes < 7.9e9
    assert memory.temp_size_in_bytes < 1 << 30


# --------------------------------------------------------- Nemotron-3-Nano
# an E layer's grouped calls: 8 x 2,048 slots at the 3/4 rung and at every
# slot, and bucket 1's one launch, x 6 experts a token over 128 groups
NEMOTRON_ROWS = (73728, 98304, 12288)


def _float32_copies(text: str, *shapes: str):
    """The program's ``copy`` instructions that write one of ``shapes``:
    a Mosaic call's operand re-laid for it."""
    return [line.strip()[:160] for line in text.splitlines()
            if " copy(" in line and any(
                f" = {shape}{{" in line for shape in shapes)]


def _copies_of_up_matrices(text: str, experts: int = 128):
    """The compiled program's ``copy`` instructions that produce an ``E``
    layer's up matrices in either order of their sides: the TPU holds
    ``bf16[128, 2688, 1856]`` with 2,688 innermost, and a Mosaic call that
    asks for it ``[G, K, N]`` row-major is handed a copy, 1.28 GB read and
    written a layer a launch (PERF.md section 6, PR 51)."""
    made = tuple(f" = bf16[{experts},{a},{b}]" for a, b in
                 ((2688, 1856), (1856, 2688)))
    return [line.strip()[:160] for line in text.splitlines()
            if " copy(" in line and any(m in line for m in made)]


@pytest.mark.parametrize("bucket", [8, 1])
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero",
                                                        "state_in"])
def test_ssd_scan_compiles_at_heads_of_64(one_chip, bucket, carried):
    """Nemotron-3-Nano's state-space scan as the cell launches it: rows of
    2,048 positions in chunks of 128, 64 heads of 64 — TWO a lane tile — in
    eight groups of eight over a 128-wide state; ONE kernel, with and
    without a state handed in (paired and parted outside the call)."""
    from realtime_fraud_detection_tpu.models.nemotron_h import NemotronHConfig
    from realtime_fraud_detection_tpu.ops.ssd_scan import ssd_scan

    cfg, t = NemotronHConfig(), 2048
    assert cfg.scan_refusal(t) is None
    h, p, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.ssm_state_size)
    assert (h, p, g, n) == (64, 64, 8, 128)
    args = [_sds((bucket, t, h, p), jnp.bfloat16, one_chip),
            _sds((bucket, t, h), jnp.float32, one_chip),
            _sds((h,), jnp.float32, one_chip),
            _sds((bucket, t, g, n), jnp.bfloat16, one_chip),
            _sds((bucket, t, g, n), jnp.bfloat16, one_chip),
            _sds((h,), jnp.float32, one_chip)]
    if carried:
        args.append(_sds((bucket, h, n, p), jnp.float32, one_chip))
    compiled = jax.jit(lambda x, dt, a, b_in, c_in, d, state=None: ssd_scan(
        x, dt, a, b_in, c_in, d, chunk=cfg.chunk_size,
        initial_state=state, use_pallas=True)).lower(*args).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < bucket * (48 << 20)


@pytest.mark.parametrize("rows", NEMOTRON_ROWS,
                         ids=["three_quarters", "every_slot", "bucket1"])
@pytest.mark.parametrize("kernel", ["relu2", "down"])
def test_the_ungated_experts_compile_at_the_published_1856(one_chip, kernel,
                                                           rows):
    """An expert 1,856 = 14 1/2 lane tiles wide, run AS PUBLISHED: the
    width whole in one block as N of ``relu2_gmm`` (one right-hand block a
    step, ``relu^2`` in the epilogue) and as K of ``down_gmm``, whose
    result rows are ``hidden / 128`` = 21 lane tiles; ONE Mosaic call each
    inside the budget it names, at a row tile of 128."""
    from realtime_fraud_detection_tpu.ops.grouped_matmul import (
        VMEM_CEILING,
        down_vmem_bytes,
        gated_vmem_bytes,
        gmm_tiling,
        grouped_matmul,
        grouped_matmul_supported,
        grouped_relu2_matmul,
    )

    hidden, width, groups = 2688, 1856, 128
    weights = _sds((groups, hidden, width) if kernel == "relu2"
                   else (groups, width, hidden), jnp.bfloat16, one_chip)
    sizes = _sds((groups,), jnp.int32, one_chip)
    if kernel == "relu2":
        assert grouped_matmul_supported(rows, hidden, width)
        tiling = gmm_tiling(rows, hidden, width, groups, gated=True,
                            matrices=1)
        assert tiling == (128, hidden, width)
        assert gated_vmem_bytes(*tiling, matrices=1) <= VMEM_CEILING
        text = jax.jit(lambda x, w, g: grouped_relu2_matmul(
            x, w, g, out_dtype=jnp.bfloat16, use_pallas=True)).lower(
            _sds((rows, hidden), jnp.bfloat16, one_chip), weights,
            sizes).compile().as_text()
        assert "jit(relu2_gmm)/relu2_gmm/pallas_call" in text
        assert f"f32[{rows},{width}]" not in text
        assert f"bf16[{rows},{width}]" in text
        # the matrices go to the call where they lie: K innermost
        assert _copies_of_up_matrices(text) == []
        assert f"bf16[{groups},{width},{hidden}]" in text
    else:
        assert grouped_matmul_supported(rows, width, hidden)
        tiling = gmm_tiling(rows, width, hidden, groups)
        assert tiling == (128, width, hidden)
        assert down_vmem_bytes(*tiling) <= VMEM_CEILING
        text = jax.jit(lambda x, w, g: grouped_matmul(
            x, w, g, use_pallas=True)).lower(
            _sds((rows, width), jnp.bfloat16, one_chip), weights,
            sizes).compile().as_text()
        assert "jit(down_gmm)/down_gmm/pallas_call" in text
        assert f"f32[{rows},21,128]" in text
        assert f"f32[{rows},{hidden}]" not in text
    assert text.count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("rows", NEMOTRON_ROWS,
                         ids=["three_quarters", "every_slot", "bucket1"])
def test_the_combine_compiles_at_rows_of_21_lane_tiles(one_chip, rows):
    """``hidden`` 2,688 = 21 lane tiles, not a whole sublane tile of them:
    a fetched row lands ``21 n`` sublanes into its plane. ONE Mosaic call
    at 128 tokens a step, inside the budget the call names."""
    from realtime_fraud_detection_tpu.ops.combine import (
        combine_supported,
        combine_tokens,
        combine_vmem_bytes,
        weighted_combine,
    )
    from realtime_fraud_detection_tpu.ops.grouped_matmul import VMEM_CEILING

    hidden, top_k = 2688, 6
    tokens = rows // top_k
    assert combine_supported(tokens, top_k, hidden)
    assert combine_tokens(tokens, top_k, hidden) == 128
    assert combine_vmem_bytes(128, top_k, hidden) <= VMEM_CEILING
    text = jax.jit(lambda out3, home, weights, valid: weighted_combine(
        out3, home, weights, valid, use_pallas=True)).lower(
        _sds((rows, 21, 128), jnp.float32, one_chip),
        _sds((tokens, top_k), jnp.int32, one_chip),
        _sds((tokens, top_k), jnp.float32, one_chip),
        _sds((tokens, top_k), jnp.bool_, one_chip)).compile().as_text()
    assert text.count(CUSTOM_CALL) == 1
    assert "jit(combine_rows)/weighted_combine/pallas_call" in text
    assert f"f32[{rows},{hidden}]" not in text


@pytest.mark.parametrize("bucket", [8, 1])
def test_windowed_attention_compiles_with_no_rotation(one_chip, bucket):
    """Nemotron-3-Nano's ``*`` layer: 32 query heads over 2 key-value heads
    of 128 (sixteen heads a step), no window, no gate and NO tables — q and
    k go to the contraction as their projections wrote them, bfloat16."""
    from realtime_fraud_detection_tpu.models.nemotron_h import NemotronHConfig
    from realtime_fraud_detection_tpu.ops import windowed_attention

    cfg, t = NemotronHConfig(), 2048
    assert cfg.core_refusal(t) is None
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    fn = jax.jit(lambda q, k, v, lens: windowed_attention(
        q, k, v, lens, num_heads=heads, num_kv_heads=kv,
        out_dtype=jnp.bfloat16))
    narrow = _sds((bucket, t, kv * 128), jnp.bfloat16, one_chip)
    compiled = fn.lower(
        _sds((bucket, t, heads * 128), jnp.bfloat16, one_chip), narrow,
        narrow, _sds((bucket,), jnp.int32, one_chip)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("bucket,capacity,temporaries",
                         [(8, 12288, 1.70e9), (8, None, 2.18e9),
                          (1, None, 0.27e9)],
                         ids=["three_quarters", "every_slot", "bucket1"])
def test_nemotron3_program_compiles_with_a_kernel_for_every_kind(
        one_chip, bucket, capacity, temporaries):
    """The served packed program with a ``NemotronHConfig``: one layer of
    each KIND (``ME*``), every width as published, bucket 8 x 2,048 tokens at
    both capacities and bucket 1: the scan's pair kernel, the convolution's
    kernel (PR 55: x | B | C read out of ``W_in``'s result positions last,
    and the row-major float32 copy of them that XLA made for its own
    fusion is gone), the ungated grouped call, down's, the combine and the
    fused causal core — six Mosaic calls — a second small output, no
    conditional, no float32
    ``[pairs, 2688]`` array, no copy of a parameter, and temporaries that
    leave room for the cell's 11.44 GB of weights in 16 GB: 1.57 / 2.01 /
    0.24 GB read here (2.26 / 2.51 with the copy's destination, PR 50), held
    under 8% over."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models.nemotron_h import NemotronHConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    config = NemotronHConfig(num_hidden_layers=3,
                             hybrid_override_pattern="ME*")
    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        bucket, ScorerConfig(text_len=2048)))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=config,
        use_pallas=True, text_capacity=capacity).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 6
    assert _float32_copies(text, f"f32[{bucket},2048,6144]",
                           f"f32[{bucket},2048,10304]") == []
    for call in ("ssm_scan/jit(_ssd_pallas)/ssd_scan",
                 "ssm_conv/jit(_conv_pallas)/causal_conv",
                 "attn_core/jit(windowed_attention)/windowed_attention",
                 "jit(relu2_gmm)/relu2_gmm", "jit(down_gmm)/down_gmm",
                 "jit(combine_rows)/weighted_combine"):
        assert f"{call}/pallas_call" in text, call
    assert "gated_gmm/pallas_call" not in text
    pairs = (capacity or bucket * 2048) * 6
    assert f"f32[{pairs},2688]" not in text
    assert f"f32[{pairs},21,128]" in text
    assert " conditional(" not in text and "cond/branch_" not in text
    assert f"f32[{bucket},32,2048,2048]" not in text
    # no launch re-lays a parameter out: the up matrices reach relu2_gmm
    # as a bitcast of the parameter
    assert _copies_of_up_matrices(text) == []
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


def test_nemotron3_weights_are_made_without_a_float32_copy(one_chip):
    """The builder's one jitted init at the cell's nine layers: 11.44 GB of
    arguments (5.72 B parameters, bfloat16 but for the norms, the
    convolutions, the mixers' vectors, the routers' biases and the head),
    drawn tensor by tensor with no float32 copy standing beside them."""
    from realtime_fraud_detection_tpu.models.nemotron_h import NemotronHConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    config = NemotronHConfig(num_hidden_layers=9,
                             hybrid_override_pattern="MEMEM*EME")
    memory = jax.jit(
        lambda key: init_scoring_models(key, bert_config=config)).lower(
        _sds((2,), jnp.uint32, one_chip)).compile().memory_analysis()
    assert 11.40e9 < memory.output_size_in_bytes < 11.48e9
    assert memory.temp_size_in_bytes < 1 << 30


# ---------------------------------------------------------------------------
# Qwen3-Next (PR 54): the delta-rule scan, the core at heads of 256, the
# grouped calls at 256 groups of experts of 512, and the bucket's program

QWEN_ROWS = (122880, 163840, 20480)     # 12,288 / 16,384 / 2,048 slots x 10


@pytest.mark.parametrize("bucket", [8, 4, 2, 1])
@pytest.mark.parametrize("carried", [False, True], ids=["from_zero",
                                                        "state_in"])
def test_delta_scan_compiles_at_published_widths(one_chip, bucket, carried):
    """Qwen3-Next's Gated-DeltaNet scan (``ops/delta_scan.py``): 2,048
    positions, 16 key heads under 32 value heads of 128 over a 128 x 128
    state, bfloat16 operands, at the chunk the configuration assumes, at
    every bucket of rows the job launches, from zero and from a state
    handed in — ONE Mosaic call inside its VMEM budget."""
    from realtime_fraud_detection_tpu.ops.delta_scan import (
        delta_refusal,
        gated_delta_scan,
    )

    t, chunk = 2048, 64
    assert delta_refusal(t, 128, 128, chunk, 16, 32) is None
    keys = _sds((bucket, t, 16, 128), jnp.bfloat16, one_chip)
    steps = _sds((bucket, t, 32), jnp.float32, one_chip)
    args = [keys, keys, _sds((bucket, t, 32, 128), jnp.bfloat16, one_chip),
            steps, steps]
    if carried:
        args.append(_sds((bucket, 32, 128, 128), jnp.float32, one_chip))
    compiled = jax.jit(lambda q, k, v, g, beta, state=None: gated_delta_scan(
        q, k, v, g, beta, chunk=chunk, initial_state=state,
        use_pallas=True)).lower(*args).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


CONV_SITES = {
    # parts, their dtypes, bias, the array's width, the first channel
    "falconh1": ((4096, 512, 512), ("bfloat16",) * 3, True, 9248, 4096),
    "nemotron3": ((4096, 1024, 1024), ("bfloat16",) * 3, True, 10304, 4096),
    "qwen3next": ((2048, 2048, 4096), ("float32", "float32", "bfloat16"),
                  False, 8192, 0),
}


@pytest.mark.parametrize("bucket", [8, 1])
@pytest.mark.parametrize("encoder", sorted(CONV_SITES))
def test_causal_conv_compiles_where_each_mixer_holds_its_input(
        one_chip, encoder, bucket):
    """The convolution's kernel at the three cells' shapes, 2,048 positions:
    positions last out of ``W_in``'s 9,248 / 10,304 channels (no whole
    number of lane tiles: the TPU holds such an array positions-minor),
    positions first on Qwen3-Next's 8,192; ONE Mosaic call, the parts its
    only outputs."""
    from realtime_fraud_detection_tpu.ops.causal_conv import (
        causal_conv_silu,
        conv_refusal,
    )

    parts, dtypes, biased, wide, offset = CONV_SITES[encoder]
    last = wide % 128 != 0
    assert conv_refusal(2048, parts, 4, offset) is None
    c = sum(parts)
    args = [_sds((bucket, wide, 2048) if last else (bucket, 2048, wide),
                 jnp.float32, one_chip),
            _sds((4, c), jnp.float32, one_chip)]
    if biased:
        args.append(_sds((c,), jnp.float32, one_chip))
    compiled = jax.jit(lambda x, taps, bias=None: causal_conv_silu(
        x, taps, bias, parts=parts, dtypes=dtypes, offset=offset,
        positions_last=last)).lower(*args).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 1
    assert [line for line in text.splitlines()
            if " copy(" in line and f"{bucket},2048" in line] == []
    out = sum(bucket * 2048 * width * jnp.dtype(d).itemsize
              for width, d in zip(parts, dtypes))
    memory = compiled.memory_analysis()
    assert out <= memory.output_size_in_bytes <= out + 4096
    assert memory.temp_size_in_bytes <= 1 << 20   # taps turned, no more


@pytest.mark.parametrize("bucket", [8, 1])
def test_windowed_attention_compiles_at_heads_of_256(one_chip, bucket):
    """Qwen3-Next's ``F`` layer's core: 16 query heads of 256 — two lane
    tiles — over 2 key-value heads, per-head norms, 64 of 256 dims rotated
    inside a head's first tile, a gate a lane; float32 q, k and gates as
    projected, bfloat16 v and context. ONE Mosaic call."""
    from realtime_fraud_detection_tpu.models.olmoe import rope_tables
    from realtime_fraud_detection_tpu.ops.attention import (
        rope_lane_tables,
        windowed_attention,
        windowed_refusal,
    )

    t, heads, kv, d = 2048, 16, 2, 256
    assert windowed_refusal(t, d, heads, kv, None, head_norm=True) is None
    *tables, shift = rope_lane_tables(*rope_tables(t, 64, 1e7), 128)
    weights = (np.ones(d, np.float32), np.ones(d, np.float32))
    fn = jax.jit(lambda q, k, v, lengths, gate: windowed_attention(
        q, k, v, lengths, num_heads=heads, num_kv_heads=kv,
        rope=tuple(tables), rope_shift=shift, gate=gate, head_norm=weights,
        norm_eps=1e-6, out_dtype=jnp.bfloat16))
    wide = _sds((bucket, t, heads * d), jnp.float32, one_chip)
    compiled = fn.lower(
        wide, _sds((bucket, t, kv * d), jnp.float32, one_chip),
        _sds((bucket, t, kv * d), jnp.bfloat16, one_chip),
        _sds((bucket,), jnp.int32, one_chip), wide).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("rows", QWEN_ROWS,
                         ids=["three_quarters", "every_slot", "bucket1"])
@pytest.mark.parametrize("kernel", ["gated", "down", "combine"])
def test_the_experts_compile_at_256_groups_of_512(one_chip, kernel, rows):
    """The most groups and the narrowest experts of any configuration: 256
    held experts of 512 at ~195 rows a group, ten pairs a token — the fused
    gate / up call, down's and the combine, ONE Mosaic call each at both
    rungs of bucket 8 and at bucket 1."""
    from realtime_fraud_detection_tpu.ops.combine import (
        combine_supported,
        weighted_combine,
    )
    from realtime_fraud_detection_tpu.ops.grouped_matmul import (
        grouped_gated_matmul,
        grouped_matmul,
        grouped_matmul_supported,
    )

    groups, h, width, top_k = 256, 2048, 512, 10
    sizes = _sds((groups,), jnp.int32, one_chip)
    if kernel == "gated":
        assert grouped_matmul_supported(rows, h, width)
        matrix = _sds((groups, h, width), jnp.bfloat16, one_chip)
        compiled = jax.jit(lambda x, g, u, s: grouped_gated_matmul(
            x, g, u, s, out_dtype=jnp.bfloat16, use_pallas=True)).lower(
            _sds((rows, h), jnp.bfloat16, one_chip), matrix, matrix,
            sizes).compile()
    elif kernel == "down":
        compiled = jax.jit(lambda x, w, s: grouped_matmul(
            x, w, s, use_pallas=True)).lower(
            _sds((rows, width), jnp.bfloat16, one_chip),
            _sds((groups, width, h), jnp.bfloat16, one_chip),
            sizes).compile()
    else:
        slots = rows // top_k
        assert combine_supported(slots, top_k, h)
        pairs = _sds((slots, top_k), jnp.int32, one_chip)
        compiled = jax.jit(lambda out, home, w, ok: weighted_combine(
            out, home, w, ok, use_pallas=True)).lower(
            _sds((rows, h // 128, 128), jnp.float32, one_chip), pairs,
            _sds((slots, top_k), jnp.float32, one_chip),
            _sds((slots, top_k), jnp.bool_, one_chip)).compile()
    assert compiled.as_text().count(CUSTOM_CALL) == 1


@pytest.mark.parametrize("bucket,capacity,temporaries",
                         [(8, 12288, 1.50e9), (8, None, 1.95e9),
                          (1, None, 0.47e9)],
                         ids=["three_quarters", "every_slot", "bucket1"])
def test_qwen3next_program_compiles_with_a_kernel_at_every_site(
        one_chip, bucket, capacity, temporaries):
    """The served packed program with a ``Qwen3NextConfig``: one whole
    period (``LLLF``), every width as published, half of each layer's
    experts held, bucket 8 x 2,048 tokens at both capacities and bucket 1:
    three delta scans, their three convolutions (PR 55: ``qkv`` read as
    the projection wrote it, q | k | v written apart), the fused core at
    heads of 256 and three Mosaic calls a sparse half — nineteen — a second
    small output, no conditional,
    no float32 ``[pairs, 2048]`` array, no ``[8, 16, 2048, 2048]`` of
    scores, no copy of a parameter, and temporaries that leave room for the
    cell's 10.73 GB of weights in 16 GB: 1.38 / 1.79 / 0.43 GB read here at
    six layers (PR 54), held under 10% over."""
    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models.qwen3_next import Qwen3NextConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused_packed,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Qwen3NextConfig(num_hidden_layers=4, num_experts=256)
    models = jax.eval_shape(
        lambda key: init_scoring_models(key, bert_config=config),
        jax.random.PRNGKey(0))
    blobs, spec = pack_tree(make_example_batch(
        bucket, ScorerConfig(text_len=2048)))
    compiled = score_fused_packed.lower(
        _shapes_of(models, one_chip),
        *(_shapes_of(blobs[k], one_chip) for k in ("f32", "i32", "u8")),
        spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=_sds((len(MODEL_NAMES),), jnp.bool_, one_chip),
        blob_bf16=_shapes_of(blobs["bf16"], one_chip), bert_config=config,
        use_pallas=True, text_capacity=capacity).compile()
    text = compiled.as_text()
    assert text.count(CUSTOM_CALL) == 3 + 3 + 1 + 4 * 3
    assert _float32_copies(text, f"f32[{bucket},2048,8192]") == []
    for call in ("delta_scan/jit(_delta_pallas)/gated_delta_scan",
                 "delta_conv/jit(_conv_pallas)/causal_conv",
                 "attn_core/jit(windowed_attention)/windowed_attention",
                 "jit(gated_gmm)/gated_gmm", "jit(down_gmm)/down_gmm",
                 "jit(combine_rows)/weighted_combine"):
        assert f"{call}/pallas_call" in text, call
    pairs = (capacity or bucket * 2048) * 10
    assert f"f32[{pairs},2048]" not in text
    assert f"f32[{pairs},16,128]" in text
    assert " conditional(" not in text and "cond/branch_" not in text
    assert f"f32[{bucket},16,2048,2048]" not in text
    # no launch re-lays a parameter out: the weights' parts are sliced under
    # their scopes, the experts reach their calls as they are held
    copies = [line.strip()[:160] for line in text.splitlines()
              if " copy(" in line and "parameter" in line]
    assert copies == []
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


def test_qwen3next_weights_are_made_without_a_float32_copy(one_chip):
    """The builder's one jitted init at the cell's six layers: 10.73 GB of
    arguments (5,364,067,776 parameters, bfloat16 but for the norms, the
    convolutions' taps, the mixers' vectors and the head: the byte count
    the configuration file states, within 1%), drawn tensor by tensor with
    no float32 copy standing beside them."""
    from realtime_fraud_detection_tpu.models.qwen3_next import Qwen3NextConfig
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    config = Qwen3NextConfig(num_hidden_layers=6, num_experts=256)
    memory = jax.jit(
        lambda key: init_scoring_models(key, bert_config=config)).lower(
        _sds((2,), jnp.uint32, one_chip)).compile().memory_analysis()
    assert abs(memory.output_size_in_bytes / 10_728_527_616 - 1.0) < 0.01
    assert memory.temp_size_in_bytes < 1 << 30
