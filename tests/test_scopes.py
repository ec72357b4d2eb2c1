"""The device program's named scopes (obs/scopes.py) are metadata only:
every scope name reaches some instruction's ``op_name``, and the optimised
program is the same with and without them."""

import contextlib
import re

import jax
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.packing import pack_tree
from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
from realtime_fraud_detection_tpu.obs import scopes
from realtime_fraud_detection_tpu.scoring.pipeline import (
    MODEL_NAMES,
    ScorerConfig,
    _score_fused_packed_impl,
    _PACKED_STATIC,
    init_scoring_models,
    make_example_batch,
)
from realtime_fraud_detection_tpu.utils.config import Config


def _compile_packed(bert_config=TINY_CONFIG):
    """A fresh jit each time (the module's own would answer the second
    lowering from its trace cache, scopes and all)."""
    models = init_scoring_models(jax.random.PRNGKey(0),
                                 bert_config=bert_config)
    blobs, spec = pack_tree(
        make_example_batch(8, ScorerConfig(), rng=np.random.default_rng(7)))
    fn = jax.jit(lambda *a, **k: _score_fused_packed_impl(*a, **k),
                 static_argnames=_PACKED_STATIC)
    lowered = fn.lower(
        models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=jax.numpy.ones((len(MODEL_NAMES),), bool),
        bert_config=bert_config)
    return lowered, lowered.compile().as_text()


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_off():
    """The persistent cache keys a program without its metadata, so the
    second compile would be answered with the first one's text."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture(scope="module")
def scoped():
    return _compile_packed()


def _expected_paths():
    text = [f"{scopes.TEXT}/{scopes.EMBED}", f"{scopes.TEXT}/{scopes.HEAD}"]
    for i in range(TINY_CONFIG.num_layers):
        text += [f"{scopes.TEXT}/{scopes.layer_scope(i)}/{k}"
                 for k in scopes.LAYER_SCOPES]
    return [s for s in scopes.BRANCH_SCOPES if s != scopes.TEXT] + text


@pytest.mark.parametrize("path", _expected_paths())
def test_scope_reaches_the_lowered_module(scoped, path):
    """Every scope constant names at least one operation of the lowered
    module (its location metadata is what becomes ``op_name``)."""
    lowered, _ = scoped
    asm = lowered.compiler_ir().operation.get_asm(enable_debug_info=True)
    assert re.search(rf'"jit\([^"]*\)/{re.escape(path)}/', asm), path


@pytest.mark.parametrize("path", [
    scopes.TREES, scopes.LSTM, scopes.GNN, scopes.IFOREST,
    f"{scopes.TEXT}/{scopes.layer_scope(0)}/{scopes.FFN}",
    f"{scopes.TEXT}/{scopes.layer_scope(1)}/{scopes.ATTN_CORE}",
    f"{scopes.TEXT}/{scopes.layer_scope(1)}/{scopes.ATTN_PROJ}",
])
def test_scope_survives_optimisation(scoped, path):
    """The scopes that own real work still name an instruction of the
    OPTIMISED program (``op_name`` in its text): what a trace reads."""
    _, text = scoped
    assert re.search(rf'op_name="jit\([^"]*\)/{re.escape(path)}/', text), path


def _program_body(text):
    """The optimised module's computations without what only describes
    where a line came from: the stack-frame tables at the top and each
    instruction's ``metadata={...}``."""
    start = re.search(r"^(?:ENTRY )?%\S+ \(", text, re.M).start()
    return re.sub(r", metadata=\{[^}]*\}", "", text[start:])


def test_scopes_do_not_change_the_compiled_program(scoped, monkeypatch):
    _, with_scopes = scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    _, without = _compile_packed()
    assert f"/{scopes.TEXT}/" not in without        # the patch took
    assert f"/{scopes.TEXT}/" in with_scopes
    a, b = _program_body(with_scopes), _program_body(without)
    for counted in (" = ", " fusion(", " dot(", "\n}\n"):
        assert a.count(counted) == b.count(counted), counted
    assert a.count(" fusion(") > 10
    assert a == b


def _vocabulary_paths(level, prefix=""):
    """Every path of a builder's nested ``VOCABULARY`` as a pattern, ``*``
    as some layer's digits (an encoder's layers may be unlike: Laguna's
    layer 0 has the ``ffn``, its later ones the experts)."""
    for name, below in level.items():
        path = prefix + re.escape(name).replace(r"\*", r"\d+")
        yield path
        yield from _vocabulary_paths(below, path + "/")


def _lowered_asm(bert_config):
    models = init_scoring_models(jax.random.PRNGKey(0),
                                 bert_config=bert_config)
    blobs, spec = pack_tree(
        make_example_batch(8, ScorerConfig(), rng=np.random.default_rng(7)))
    fn = jax.jit(lambda *a, **k: _score_fused_packed_impl(*a, **k),
                 static_argnames=_PACKED_STATIC)
    return fn.lower(
        models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
        params=EnsembleParams.from_config(Config(), list(MODEL_NAMES)),
        model_valid=jax.numpy.ones((len(MODEL_NAMES),), bool),
        bert_config=bert_config,
    ).compiler_ir().operation.get_asm(enable_debug_info=True)


@pytest.mark.parametrize("builder", ["ensemble_builder", "olmoe_builder",
                                     "zaya1_builder", "laguna_builder",
                                     "joyai_builder", "falconh1_builder",
                                     "nemotron3_builder",
                                     "qwen3next_builder"])
def test_every_name_in_a_builders_vocabulary_is_one_the_program_writes(
        builder):
    """A builder (``benchmarks/configs/<builder>.py``) writes the device
    scopes again (it has to load against a program without them); every path
    of its ``VOCABULARY`` names an operation of the program it builds, and
    the program's own layer scopes are all in it."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.harness import scopes as bench
    from benchmarks.harness import spec

    vocabulary = spec.builder({"builder": builder}).VOCABULARY
    if builder == "ensemble_builder":
        config, layer_parts = TINY_CONFIG, scopes.LAYER_SCOPES
        assert vocabulary is bench.ENSEMBLE_VOCABULARY
        assert bench.BRANCHES == scopes.BRANCH_SCOPES
        assert bench.TEXT == scopes.TEXT
        assert bench.TEXT_PARTS == (scopes.EMBED, scopes.HEAD)
        assert bench.LAYER_PARTS == scopes.LAYER_SCOPES
        assert bench.LAYER_RE.match(scopes.layer_scope(11))
        assert bench.PREFIX == scopes.ANNOTATION_PREFIX
        assert bench.GC_SPAN == scopes.HOST_GC
        assert bench.scope_path(
            f"jit(f)/{scopes.TEXT}/{scopes.layer_scope(2)}/{scopes.FFN}/dot"
        ) == f"{scopes.TEXT}/{scopes.layer_scope(2)}/{scopes.FFN}"
    elif builder == "falconh1_builder":
        from realtime_fraud_detection_tpu.models.falcon_h1 import (
            TINY_FALCON_H1,
        )

        # a causal DENSE encoder: DistilBERT's four names and the three of
        # the state-space mixer; no router, no experts
        config, layer_parts = TINY_FALCON_H1, scopes.FALCON_H1_LAYER_SCOPES
        assert set(layer_parts) == set(scopes.LAYER_SCOPES) | {
            scopes.SSM_PROJ, scopes.SSM_CONV, scopes.SSM_SCAN}
        assert scopes.EXPERTS not in vocabulary[scopes.TEXT]["layer*"]
        # the scan's kernel as a device trace names it
        assert bench.scope_path(
            f"jit(f)/{scopes.TEXT}/{scopes.layer_scope(5)}/{scopes.SSM_SCAN}"
            "/jit(_ssd_pallas)/ssd_scan/pallas_call", vocabulary
        ) == f"{scopes.TEXT}/{scopes.layer_scope(5)}/{scopes.SSM_SCAN}"
    else:
        gate_up = "jit(gated_gmm)/gated_gmm/pallas_call"
        if builder == "nemotron3_builder":
            from realtime_fraud_detection_tpu.models.nemotron_h import (
                TINY_NEMOTRON_H,
            )

            # a layer is ONE mixer: its norm and the scopes of its kind —
            # Falcon-H1's three of the mixer, attention's two, a routed
            # block's three; no ``ffn`` (no layer has a dense MLP); the
            # experts' first call has no gate
            config, layer_parts = (TINY_NEMOTRON_H,
                                   scopes.NEMOTRON_H_LAYER_SCOPES)
            assert set(layer_parts) == {
                scopes.LN, scopes.SSM_PROJ, scopes.SSM_CONV, scopes.SSM_SCAN,
                scopes.ATTN_PROJ, scopes.ATTN_CORE, scopes.ROUTER,
                scopes.EXPERTS, scopes.SHARED_EXPERT}
            gate_up = "jit(relu2_gmm)/relu2_gmm/pallas_call"
        elif builder == "qwen3next_builder":
            from realtime_fraud_detection_tpu.models.qwen3_next import (
                TINY_QWEN3_NEXT,
            )

            # every layer has both norms and a routed block beside a shared
            # expert; its mixer is a Gated-DeltaNet's three scopes or
            # attention's two; no ``ffn`` (no layer has a dense MLP)
            config, layer_parts = (TINY_QWEN3_NEXT,
                                   scopes.QWEN3_NEXT_LAYER_SCOPES)
            assert set(layer_parts) == {
                scopes.LN, scopes.DELTA_PROJ, scopes.DELTA_CONV,
                scopes.DELTA_SCAN, scopes.ATTN_PROJ, scopes.ATTN_CORE,
                scopes.ROUTER, scopes.EXPERTS, scopes.SHARED_EXPERT}
            # the scan's kernel as a device trace names it
            assert bench.scope_path(
                f"jit(f)/{scopes.TEXT}/{scopes.layer_scope(4)}/"
                f"{scopes.DELTA_SCAN}/jit(_delta_pallas)/gated_delta_scan"
                "/pallas_call", vocabulary
            ) == f"{scopes.TEXT}/{scopes.layer_scope(4)}/{scopes.DELTA_SCAN}"
        elif builder == "olmoe_builder":
            from realtime_fraud_detection_tpu.models.olmoe import TINY_OLMOE

            config, layer_parts = TINY_OLMOE, scopes.MOE_LAYER_SCOPES
        elif builder == "laguna_builder":
            from realtime_fraud_detection_tpu.models.laguna import (
                TINY_LAGUNA,
            )

            # unlike layers: layer 0's dense MLP is ``ffn``, the sparse
            # layers have the shared expert beside the routed ones
            config, layer_parts = TINY_LAGUNA, scopes.LAGUNA_LAYER_SCOPES
            assert set(layer_parts) == set(scopes.MOE_LAYER_SCOPES) | {
                scopes.FFN, scopes.SHARED_EXPERT}
        elif builder == "joyai_builder":
            from realtime_fraud_detection_tpu.models.joyai import TINY_JOYAI

            # Laguna's names and what latent attention puts in front of
            # the projections
            config, layer_parts = TINY_JOYAI, scopes.JOYAI_LAYER_SCOPES
            assert set(layer_parts) == set(scopes.LAGUNA_LAYER_SCOPES) | {
                scopes.ATTN_LATENT}
        else:
            from realtime_fraud_detection_tpu.models.zaya import TINY_ZAYA

            config, layer_parts = TINY_ZAYA, scopes.ZAYA_LAYER_SCOPES
            assert scopes.ATTN_MIX in vocabulary[scopes.TEXT]["layer*"]
            assert set(layer_parts) == set(scopes.MOE_LAYER_SCOPES) | {
                scopes.ATTN_MIX}
        assert set(vocabulary[scopes.TEXT]["layer*"][scopes.EXPERTS]) == set(
            scopes.EXPERTS_PARTS)
        for part in scopes.EXPERTS_PARTS:
            assert getattr(scopes, f"EXPERTS_{part.upper()}") == (
                f"{scopes.EXPERTS}/{part}")
        # the experts' two grouped kernels, down's and the fused gate + up
        # + SiLU one, and the combine's, as a device trace names them
        for part, kernel in (
                (scopes.EXPERTS_MATMUL, "jit(down_gmm)/down_gmm/pallas_call"),
                (scopes.EXPERTS_MATMUL, gate_up),
                (scopes.EXPERTS_COMBINE,
                 "jit(combine_rows)/weighted_combine/pallas_call")):
            assert bench.scope_path(
                f"jit(f)/{scopes.TEXT}/{scopes.layer_scope(3)}/"
                f"{part}/{kernel}", vocabulary
            ) == f"{scopes.TEXT}/{scopes.layer_scope(3)}/{part}"
    assert set(vocabulary) == set(scopes.BRANCH_SCOPES)
    assert set(vocabulary[scopes.TEXT]["layer*"]) == set(layer_parts)
    asm = _lowered_asm(config)
    for path in _vocabulary_paths(vocabulary):
        assert re.search(rf'"jit\([^"]*\)/{path}/', asm), path


# ---- the parts written inside four of the layer scopes (ISSUE 56)

def _part_config(encoder):
    """``models/<encoder>.py``'s TINY configuration."""
    import importlib

    module = importlib.import_module(
        f"realtime_fraud_detection_tpu.models.{encoder}")
    return getattr(module, f"TINY_{encoder.upper()}")


# which of the parted scopes each touched encoder's program writes
PARTED = {
    "falcon_h1": (scopes.SSM_PROJ, scopes.FFN),
    "nemotron_h": (scopes.SSM_PROJ, scopes.ROUTER),
    "qwen3_next": (scopes.DELTA_CONV, scopes.ROUTER),
    "olmoe": (scopes.ROUTER,),
}
PART_CASES = [(encoder, parent, part) for encoder, parents in PARTED.items()
              for parent in parents for part in scopes.SCOPE_PARTS[parent]]
PART_NAMES = {part for parts in scopes.SCOPE_PARTS.values() for part in parts}
# test-only: the name the coverage test puts round the convolution, which
# the program leaves directly under ``delta_conv`` (a kernel's ``op_name``
# is never moved by a part)
CONVOLUTION = "convolution"


@pytest.fixture(scope="module")
def parted():
    """``encoder -> (lowered, optimised text)`` at TINY, compiled once."""
    made = {}

    def get(encoder):
        if encoder not in made:
            made[encoder] = _compile_packed(_part_config(encoder))
        return made[encoder]
    return get


def _asm(lowered):
    return lowered.compiler_ir().operation.get_asm(enable_debug_info=True)


def _below(asm, parent):
    """The names written directly under ``parent`` in any layer."""
    return set(re.findall(
        rf'"jit\([^"]*\)/{scopes.TEXT}/{scopes.LAYER}\d+/{parent}/'
        rf'(?:jit\([^/"]*\)/)*([^/"]+)', asm))


@pytest.mark.parametrize("encoder,parent,part", PART_CASES)
def test_part_reaches_the_lowered_module(parted, encoder, parent, part):
    lowered, _ = parted(encoder)
    assert re.search(
        rf'"jit\([^"]*\)/{scopes.TEXT}/{scopes.LAYER}\d+/{parent}/{part}/',
        _asm(lowered)), (encoder, parent, part)


@pytest.mark.parametrize("encoder,parent,part", PART_CASES)
def test_part_survives_optimisation(parted, encoder, parent, part):
    """Each part still names an instruction of the OPTIMISED program: what
    a trace reads, and ``scope_part_time_per_batch`` with it."""
    _, text = parted(encoder)
    assert re.search(
        rf'op_name="jit\([^"]*\)/{scopes.TEXT}/{scopes.LAYER}\d+/{parent}/'
        rf'{part}/', text), (encoder, parent, part)


@pytest.mark.parametrize("encoder", list(PARTED))
def test_no_operation_under_a_parent_lies_outside_its_parts(
        parted, monkeypatch, encoder):
    """Under ``ssm_proj``, ``router`` and Falcon-H1's ``ffn`` every
    operation the program writes lies in exactly one part, so the parts'
    metrics add up to the parent's. Under ``delta_conv`` what lies in none
    is the convolution (named here, for the test alone) and the reshape of
    its third result, v, to heads."""
    if scopes.DELTA_CONV in PARTED[encoder]:
        from realtime_fraud_detection_tpu.models import qwen3_next

        real = qwen3_next.conv_silu_parts

        def named(*args, **kwargs):
            with jax.named_scope(CONVOLUTION):
                return real(*args, **kwargs)
        monkeypatch.setattr(qwen3_next, "conv_silu_parts", named)
        lowered, _ = _compile_packed(_part_config(encoder))
    else:
        lowered, _ = parted(encoder)
    asm = _asm(lowered)
    for parent in PARTED[encoder]:
        below = _below(asm, parent)
        if parent == scopes.DELTA_CONV:
            assert CONVOLUTION in below
            below -= {CONVOLUTION, "reshape"}
        assert below == set(scopes.SCOPE_PARTS[parent]), (parent, below)


def test_scope_parts_holds_exactly_the_parted_scopes():
    """One mapping: every parted scope is one some touched encoder writes,
    no part is named twice, and the other encoders' ``ffn`` (DistilBERT's,
    Laguna's, JoyAI's) are left whole."""
    assert set(scopes.SCOPE_PARTS) == {
        parent for parents in PARTED.values() for parent in parents}
    assert len(PART_NAMES) == sum(map(len, scopes.SCOPE_PARTS.values()))
    from realtime_fraud_detection_tpu.models.laguna import TINY_LAGUNA

    for config in (TINY_CONFIG, TINY_LAGUNA):
        below = _below(_lowered_asm(config), scopes.FFN)
        assert below and not below & PART_NAMES, below


@pytest.mark.parametrize("encoder", list(PARTED))
def test_parts_do_not_change_the_compiled_program(parted, monkeypatch,
                                                  encoder):
    """The optimised program with the parts replaced by ``nullcontext`` is
    the program with them, metadata apart (the parents stay)."""
    _, with_parts = parted(encoder)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in PART_NAMES else real(name))
    _, without = _compile_packed(_part_config(encoder))
    for parent in PARTED[encoder]:
        for part in scopes.SCOPE_PARTS[parent]:
            assert f"/{parent}/{part}/" in with_parts
            assert f"/{parent}/{part}/" not in without      # the patch took
        assert f"/{parent}/" in without
    a, b = _program_body(with_parts), _program_body(without)
    assert a.count(" fusion(") > 10
    assert a == b


def test_the_family_build_is_a_span_of_the_microbatch_under_pack():
    """``build_programs`` (ISSUE 36) is one of the host spans, a child of
    ``pack``; every span is named once and after its parent."""
    names = [name for name, _ in scopes.BATCH_SPANS]
    assert len(names) == len(set(names))
    assert dict(scopes.BATCH_SPANS)[scopes.BUILD_PROGRAMS] == scopes.PACK
    for i, (name, parent) in enumerate(scopes.BATCH_SPANS):
        assert parent == "" or parent in names[:i], name


@pytest.mark.parametrize("metadata_in_key", [False, True])
def test_a_cached_program_carries_the_scopes_of_its_key(
        tmp_path, monkeypatch, metadata_in_key):
    """Why ``configure_compile_cache`` puts the metadata in the cache key:
    with JAX's default an unscoped program cached first answers the scoped
    one, and a trace of it names no scope (seen on the v5e, PR 23)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = (jax.config.jax_compilation_cache_dir, getattr(jax.config, flag))
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update(flag, metadata_in_key)
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
        real = jax.named_scope
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        _, unscoped = _compile_packed()
        monkeypatch.setattr(jax, "named_scope", real)
        _, scoped = _compile_packed()
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update(flag, before[1])
        cc.reset_cache()
    assert f"/{scopes.TEXT}/" not in unscoped
    assert (f"/{scopes.TEXT}/" in scoped) is metadata_in_key
