"""Pallas kernel plane (ISSUE 17): interpret-mode parity for the fused
int8 dequant-matmul, the on-chip score-and-blend epilogue and flash
attention against their XLA references, the KernelSettings config
surface, scorer threading + honest dispatch/fallback accounting, the
kernel_* Prometheus mirror, checkpoint hygiene (kernel selection is
runtime config, never serialized), device-pool/mesh composition, and the
`rtfd kernel-drill --fast` tier-1 smoke."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.mesh import build_mesh
from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
from realtime_fraud_detection_tpu.models.bert import (
    TINY_CONFIG,
    init_bert_params,
)
from realtime_fraud_detection_tpu.models.quant import (
    is_quantized_bert,
    quantize_bert_params,
    quantize_dense,
    quantize_embedding,
)
from realtime_fraud_detection_tpu.ops import (
    attention_reference,
    dequant_matmul,
    dequant_matmul_reference,
    dequant_rows,
    dequant_rows_reference,
    epilogue_reference,
    epilogue_supported,
    flash_attention,
    flash_supported,
    fused_epilogue,
    matmul_supported,
    merge_heads,
    rows_supported,
    split_heads,
)
from realtime_fraud_detection_tpu.qos.ladder import LADDER_LEVELS
from realtime_fraud_detection_tpu.scoring import (
    MODEL_NAMES,
    DevicePool,
    FraudScorer,
    MeshExecutor,
    ScorerConfig,
)
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.utils.config import (
    VALID_KERNEL_SITES,
    Config,
    KernelSettings,
    QuantSettings,
)

BATCH = 16


def _kernel_config(kernels=True, quant=True) -> Config:
    return Config(
        quant=QuantSettings.full() if quant else QuantSettings(),
        kernels=KernelSettings.full() if kernels else KernelSettings())


# the shortest text window the fused attention core takes: the plane-on
# scorers below run it (interpreted) instead of counting a fallback
TEXT_LEN = 128


def _scorer(kernels=True, quant=True, seed=0, gen_seed=7, one_device=False):
    gen = TransactionGenerator(num_users=150, num_merchants=40,
                               seed=gen_seed)
    mesh = build_mesh(devices=jax.devices()[:1]) if one_device else None
    s = FraudScorer(_kernel_config(kernels, quant),
                    scorer_config=ScorerConfig(text_len=TEXT_LEN), mesh=mesh,
                    seed=seed)
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return gen, s


def _rows(results):
    return [(r["transaction_id"], r["fraud_probability"], r["confidence"],
             r["decision"], r["risk_level"]) for r in results]


def _random_int8_dense(rng, k, n):
    w = rng.standard_normal((k, n)).astype(np.float32) * 0.2
    return quantize_dense({"w": w, "b": rng.standard_normal(n)
                           .astype(np.float32)})


# ------------------------------------------------------ fused dequant-matmul
class TestDequantMatmul:
    def test_f32_compute_parity_random(self, rng):
        q = _random_int8_dense(rng, 256, 128)
        x = jnp.asarray(rng.standard_normal((64, 256)), jnp.float32)
        ref = dequant_matmul_reference(x, q["qw"], q["scale"], q["b"],
                                       jnp.float32)
        got = dequant_matmul(x, jnp.asarray(q["qw"]), jnp.asarray(q["scale"]),
                             jnp.asarray(q["b"]), compute_dtype=jnp.float32,
                             interpret=True)
        assert got.dtype == jnp.float32
        scale = max(1.0, float(jnp.abs(ref).max()))
        assert float(jnp.abs(got - ref).max()) / scale <= 1e-5

    def test_bf16_compute_parity_random(self, rng):
        q = _random_int8_dense(rng, 128, 256)
        x = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
        ref = dequant_matmul_reference(x, q["qw"], q["scale"], q["b"],
                                       jnp.bfloat16).astype(jnp.float32)
        got = dequant_matmul(x, jnp.asarray(q["qw"]), jnp.asarray(q["scale"]),
                             jnp.asarray(q["b"]), interpret=True)
        scale = max(1.0, float(jnp.abs(ref).max()))
        # bf16 reassociation slack only — rounding scale, not bit-exact
        assert float(jnp.abs(got - ref).max()) / scale <= 0.02

    def test_trained_params_parity_both_dtypes(self, rng):
        params = quantize_bert_params(jax.device_get(
            init_bert_params(jax.random.PRNGKey(2), TINY_CONFIG)))
        x = jnp.asarray(rng.standard_normal(
            (16, TINY_CONFIG.hidden_size)), jnp.float32)
        for name in ("q", "ffn1"):
            p = params["layers"][0][name]
            for cd, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 0.02)):
                ref = dequant_matmul_reference(
                    x, p["qw"], p["scale"], p["b"], cd).astype(jnp.float32)
                got = dequant_matmul(x, jnp.asarray(p["qw"]),
                                     jnp.asarray(p["scale"]),
                                     jnp.asarray(p["b"]), compute_dtype=cd,
                                     interpret=True)
                scale = max(1.0, float(jnp.abs(ref).max()))
                assert float(jnp.abs(got - ref).max()) / scale <= tol

    def test_unsupported_shapes_raise(self, rng):
        q = _random_int8_dense(rng, 256, 128)
        x = jnp.asarray(rng.standard_normal((7, 256)), jnp.float32)
        with pytest.raises(ValueError, match="unsupported"):  # odd M
            dequant_matmul(x, jnp.asarray(q["qw"]), jnp.asarray(q["scale"]),
                           jnp.asarray(q["b"]), interpret=True)

    def test_supported_predicate_is_the_guard(self):
        assert matmul_supported(64, 256, 128)
        assert not matmul_supported(7, 256, 128)     # no row block divides 7
        assert not matmul_supported(64, 200, 128)    # K not lane-aligned
        assert not matmul_supported(64, 256, 100)    # N not lane-aligned
        assert not matmul_supported(64, 4224, 128)   # K over the VMEM cap


# --------------------------------------------------------- per-row dequant
class TestDequantRows:
    def test_parity_exact_random(self, rng):
        q = jnp.asarray(rng.integers(-127, 128, (64, 128)), jnp.int8)
        s = jnp.asarray(rng.uniform(1e-4, 0.1, (64,)), jnp.float32)
        got = dequant_rows(q, s, interpret=True)
        ref = dequant_rows_reference(q, s)
        # one widen + one multiply: bit-exact, zero tolerance
        assert bool(jnp.all(got == ref))

    def test_trained_embedding_rows_exact(self, rng):
        emb = quantize_embedding(np.asarray(jax.device_get(
            init_bert_params(jax.random.PRNGKey(3),
                             TINY_CONFIG))["word_emb"]))
        idx = rng.integers(0, emb["qe"].shape[0], (32,))
        q = jnp.asarray(emb["qe"][idx])
        s = jnp.asarray(emb["scale"][idx])
        assert bool(jnp.all(dequant_rows(q, s, interpret=True)
                            == dequant_rows_reference(q, s)))

    def test_unsupported_shapes_raise(self, rng):
        q = jnp.asarray(rng.integers(-127, 128, (30, 128)), jnp.int8)
        s = jnp.ones((30,), jnp.float32)
        with pytest.raises(ValueError, match="unsupported"):  # rows % 32
            dequant_rows(q, s, interpret=True)
        assert not rows_supported(64, 100)            # H not lane-aligned
        assert not rows_supported(1 << 16, 128)       # over the VMEM cap
        assert rows_supported(64, 128)


# ----------------------------------------------------------- fused epilogue
class TestFusedEpilogue:
    def _params(self):
        return EnsembleParams.from_config(Config(), list(MODEL_NAMES))

    def test_parity_all_strategies(self, rng):
        base = self._params()
        preds = jnp.asarray(rng.uniform(0, 1, (32, 5)), jnp.float32)
        valid = jnp.asarray(rng.uniform(0, 1, (32, 5)) > 0.25)
        rule = jnp.asarray(rng.uniform(0, 1, (32,)), jnp.float32)
        for strat in range(3):
            params = base.replace(strategy=strat)
            ref = epilogue_reference(preds, valid, rule, params)
            got = fused_epilogue(preds, valid, rule, params, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got["fraud_probability"]),
                np.asarray(ref["fraud_probability"]), atol=1e-6)
            np.testing.assert_allclose(
                np.asarray(got["model_contributions"]),
                np.asarray(ref["model_contributions"]), atol=1e-6)
            for key in ("decision", "risk_level", "rule_decision",
                        "rule_risk"):
                assert bool(jnp.all(got[key] == ref[key])), (strat, key)

    def test_masked_rung_equality_all_ladder_levels(self, rng):
        """Satellite pin: the on-chip blend under every QoS ladder rung's
        validity mask matches the host reference exactly on the ladders —
        including the rules_only rung's all-invalid blend."""
        params = self._params()
        preds = jnp.asarray(rng.uniform(0, 1, (24, 5)), jnp.float32)
        rule = jnp.asarray(rng.uniform(0, 1, (24,)), jnp.float32)
        assert len(LADDER_LEVELS) == 4
        for level in LADDER_LEVELS:
            mask = jnp.asarray([n not in level.dropped_branches
                                for n in MODEL_NAMES])
            ref = epilogue_reference(preds, mask, rule, params)
            got = fused_epilogue(preds, mask, rule, params, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got["fraud_probability"]),
                np.asarray(ref["fraud_probability"]), atol=1e-6)
            for key in ("decision", "risk_level", "rule_decision",
                        "rule_risk"):
                assert bool(jnp.all(got[key] == ref[key])), (level.name, key)

    def test_unsupported_shape_raises(self, rng):
        params = self._params()
        preds = jnp.zeros((0, 5), jnp.float32)
        with pytest.raises(ValueError, match="unsupported"):
            fused_epilogue(preds, jnp.ones((5,), bool),
                           jnp.zeros((0,), jnp.float32), params,
                           interpret=True)
        assert not epilogue_supported(0, 5)
        assert not epilogue_supported((1 << 16) + 1, 5)
        assert epilogue_supported(512, 5)


# ---------------------------------------------------------- flash attention
def _longtail_case(rng, t, dtype, h=4, d=64):
    """Four rows of [T, H*D] with the benchmark's long-tail lengths: only
    [CLS] real (every key beyond it masked), 20, 207 and the full window."""
    q, k, v = (jnp.asarray(rng.standard_normal((4, t, h * d)),
                           jnp.float32).astype(dtype) for _ in range(3))
    lens = np.minimum([1, 20, 207, t], t)
    mask = jnp.asarray(np.arange(t)[None, :] < lens[:, None])
    return q, k, v, mask


def _stated_precision_oracle(q, k, v, mask, h):
    """attention_reference's mathematics with the roundings written out
    where the configuration states them and a TPU's default-precision
    einsum makes them: bf16 MXU operands, f32 everything else."""
    def bf(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    qh, kh, vh = (split_heads(bf(x.astype(jnp.float32)), h) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   precision="highest") / np.sqrt(qh.shape[-1])
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    out = jnp.einsum("bhqk,bhkd->bhqd", bf(p), vh, precision="highest")
    return merge_heads(out / p.sum(axis=-1, keepdims=True))


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("t", [128, 512])
    def test_parity_longtail_mask(self, rng, t, dtype):
        h = 4
        q, k, v, mask = _longtail_case(rng, t, dtype, h=h)
        got = flash_attention(q, k, v, mask, num_heads=h, interpret=True)
        assert got.shape == q.shape and got.dtype == dtype
        got = got.astype(jnp.float32)
        assert bool(jnp.isfinite(got).all())
        # (a) the kernel's own mathematics, rounding for rounding: today's
        # f32 slack (bf16 outputs carry their own last-place rounding)
        oracle = _stated_precision_oracle(q, k, v, mask, h)
        tight = 1e-3 if dtype == jnp.float32 else 2e-2
        assert float(jnp.abs(got - oracle).max()) <= tight
        # (b) the XLA reference, which on this CPU multiplies in f32: the
        # gap is bf16 operand rounding on N(0, 1) data, nothing more
        ref = merge_heads(attention_reference(
            split_heads(q, h), split_heads(k, h), split_heads(v, h), mask))
        assert float(jnp.abs(got - ref.astype(jnp.float32)).max()) <= 3e-2

    def test_fully_masked_row_is_uniform_like_the_reference(self, rng):
        q, k, v, _ = _longtail_case(rng, 128, jnp.float32, h=2)
        mask = jnp.zeros((4, 128), bool)
        got = flash_attention(q, k, v, mask, num_heads=2, interpret=True)
        mean_v = jnp.broadcast_to(
            v.astype(jnp.bfloat16).astype(jnp.float32).mean(
                axis=1, keepdims=True), v.shape)
        assert float(jnp.abs(got - mean_v).max()) <= 2e-3

    @pytest.mark.parametrize("t,d,h,ok", [
        (64, 64, 12, False),    # parked s64 configurations: XLA keeps up
        (96, 64, 12, False),    # not whole lane tiles of keys
        (128, 64, 12, True),
        (512, 64, 12, True),    # the deployed shape
        (512, 64, 2, True),     # TINY_CONFIG's heads
        (512, 32, 12, False),   # the lane pairing is for 64-wide heads
        (512, 64, 3, False),    # an odd head has no partner
    ])
    def test_flash_supported_truth_table(self, t, d, h, ok):
        assert flash_supported(t, d, h) is ok

    def test_unsupported_shapes_raise(self, rng):
        q = jnp.zeros((1, 96, 128), jnp.float32)
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, q, q, num_heads=2, interpret=True)
        q = jnp.zeros((1, 128, 96), jnp.float32)
        with pytest.raises(ValueError, match="64-wide heads"):
            flash_attention(q, q, q, num_heads=3, interpret=True)


# ----------------------------------------------------------- config surface
class TestKernelSettings:
    def test_validation(self):
        with pytest.raises(ValueError):
            KernelSettings(dequant_matmul="cuda").validate()
        with pytest.raises(ValueError):
            KernelSettings(attention="paged").validate()
        KernelSettings.full().validate()

    def test_disabled_plane_reports_off_modes(self):
        s = KernelSettings(dequant_matmul="pallas", epilogue="pallas",
                           attention="flash")       # enabled=False gates all
        assert s.site_modes() == {"dequant_matmul": "off",
                                  "epilogue": "off",
                                  "attention": "reference"}
        assert KernelSettings.full().site_modes() == {
            "dequant_matmul": "pallas", "epilogue": "pallas",
            "attention": "flash"}

    def test_config_overlay_round_trip(self, tmp_path):
        p = tmp_path / "k.json"
        p.write_text(json.dumps({"kernels": {"enabled": True,
                                             "attention": "flash"}}))
        loaded = Config.from_file(str(p)).kernels
        assert loaded.enabled and loaded.attention == "flash"
        assert loaded.dequant_matmul == "off"       # per-site independence

    def test_the_deleted_megakernel_site_is_not_a_field(self):
        with pytest.raises(TypeError, match="megakernel"):
            KernelSettings(enabled=True, megakernel="pallas")
        assert [f.name for f in dataclasses.fields(KernelSettings)] == [
            "enabled", "dequant_matmul", "epilogue", "attention"]
        assert VALID_KERNEL_SITES == ("dequant_matmul", "epilogue",
                                      "attention")

    def test_an_overlay_that_still_names_it_is_warned_by_name(
            self, tmp_path, caplog):
        p = tmp_path / "k.json"
        p.write_text(json.dumps({"kernels": {"enabled": True,
                                             "megakernel": "pallas"}}))
        with caplog.at_level("WARNING"):
            loaded = Config.from_file(str(p)).kernels
        assert loaded.enabled and not hasattr(loaded, "megakernel")
        assert any("unknown key 'megakernel' on KernelSettings"
                   in r.getMessage() for r in caplog.records)


# --------------------------------------------------------- scorer threading
class TestScorerKernelPlane:
    def test_off_by_default_statics_are_legacy(self):
        _, s = _scorer(kernels=False, quant=False)
        assert s.kernel_static() == {"dequant_kernel": "off",
                                     "epilogue_kernel": "off",
                                     "kernel_interpret": False}
        assert not hasattr(s.sc, "use_pallas")      # nothing a user sets
        assert s.kernel_snapshot()["dispatch"] == {
            "dequant_matmul": 0, "epilogue": 0, "attention": 0}

    def test_selector_picks_the_reference_on_cpu(self):
        """Plane off, CPU devices: the selector never asks for the kernel,
        whatever the shape — tier-1 programs stay the reference's."""
        _, s = _scorer(kernels=False, quant=False)
        assert flash_supported(s.sc.text_len, s.bert_config.head_dim,
                               s.bert_config.num_heads)
        assert s.effective_use_pallas() is False

    @pytest.mark.parametrize("text_len,want", [(64, False), (128, True),
                                               (512, True)])
    def test_selector_follows_platform_and_shape(self, text_len, want):
        """Plane off: on TPU devices the predicate alone decides. The test
        stands in for the platform the scorer read from its mesh."""
        s = FraudScorer(_kernel_config(kernels=False, quant=False),
                        scorer_config=ScorerConfig(text_len=text_len))
        s._platform = "tpu"
        assert s.effective_use_pallas(devices=1) is want
        # XLA cannot partition a Mosaic call: a program whose batch is
        # sharded over a mesh keeps the reference
        assert s.effective_use_pallas(devices=4) is False
        assert s.effective_use_pallas() is (want and s.mesh.devices.size == 1)

    def test_plane_forces_either_side(self):
        cfg = _kernel_config(kernels=True, quant=False)
        cfg.kernels.attention = "reference"
        s = FraudScorer(cfg, scorer_config=ScorerConfig(text_len=TEXT_LEN))
        s._platform = "tpu"
        assert s.effective_use_pallas() is False    # forced off on a TPU
        _, on = _scorer()
        assert on.effective_use_pallas() is True    # forced on on a CPU

    def test_attention_engagement_counts_with_plane_off(self):
        """Every launch lands on the attention site's counters, plane or no
        plane: here the selector sent both launches to the reference."""
        gen, s = _scorer(kernels=False, quant=False)
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        snap = s.kernel_snapshot()
        assert snap["dispatch"]["attention"] == 0
        assert snap["fallback"]["attention"] == 2
        assert snap["modes"]["attention"] == "reference"
        assert snap["interpret"] is False

    def test_forced_flash_at_an_unsupported_length_counts_fallbacks(self):
        gen = TransactionGenerator(num_users=150, num_merchants=40, seed=7)
        s = FraudScorer(_kernel_config(kernels=True, quant=False),
                        scorer_config=ScorerConfig(text_len=64))
        s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        assert s.effective_use_pallas()             # the plane asks ...
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        snap = s.kernel_snapshot()                  # ... the guard declines
        assert (snap["dispatch"]["attention"],
                snap["fallback"]["attention"]) == (0, 1)

    def test_kernel_statics_on(self):
        _, s = _scorer()
        static = s.kernel_static()
        assert static["dequant_kernel"] == "pallas"
        assert static["epilogue_kernel"] == "pallas"
        assert static["kernel_interpret"] is True   # no TPU in CI
        assert s.effective_use_pallas()             # flash selected

    def test_score_parity_and_zero_flips(self):
        (gen_a, off), (gen_b, on) = (_scorer(kernels=False),
                                     _scorer(kernels=True))
        ra = off.score_batch(gen_a.generate_batch(2 * BATCH), now=1000.0)
        rb = on.score_batch(gen_b.generate_batch(2 * BATCH), now=1000.0)
        pa = np.asarray([r["fraud_probability"] for r in ra])
        pb = np.asarray([r["fraud_probability"] for r in rb])
        assert np.max(np.abs(pa - pb)) < 1e-3
        assert [r["decision"] for r in ra] == [r["decision"] for r in rb]
        assert [r["risk_level"] for r in ra] == \
            [r["risk_level"] for r in rb]

    def test_dispatch_counters_with_zero_fallbacks(self):
        gen, s = _scorer()
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        snap = s.kernel_snapshot()
        assert snap["interpret"] is True
        assert all(v == 2 for v in snap["dispatch"].values())
        assert all(v == 0 for v in snap["fallback"].values())

    @pytest.mark.parametrize("rung", range(len(LADDER_LEVELS)),
                             ids=[r.name for r in LADDER_LEVELS])
    @pytest.mark.parametrize("kernels", [False, True],
                             ids=["plane-off", "plane-full"])
    def test_more_batches_and_a_qos_rung_compile_nothing(self, kernels,
                                                         rung):
        """The ladder's mask is a runtime tensor: after the first batch,
        further batches and a step to any rung reach the program the first
        one compiled, through the same memoized static dicts."""
        from realtime_fraud_detection_tpu.qos.ladder import DegradationLadder
        from realtime_fraud_detection_tpu.scoring.pipeline import (
            score_fused_packed,
        )

        gen, s = _scorer(kernels=kernels)
        kernel_static, quant_static = s.kernel_static(), s.quant_static()
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        compiled = score_fused_packed._cache_size()
        for _ in range(3):
            s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        level = LADDER_LEVELS[rung]
        s.set_degradation(
            DegradationLadder().level_mask(MODEL_NAMES, level=rung),
            rules_only=level.rules_only, level=rung)
        results = s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        assert len(results) == BATCH
        assert score_fused_packed._cache_size() == compiled
        assert s.kernel_static() is kernel_static
        assert s.quant_static() is quant_static

    def test_f32_params_count_dequant_fallback(self):
        """Honesty pin: kernels on over an f32 (unquantized) scorer — the
        dequant site has no int8 layout to fuse, so every launch counts a
        dispatch AND a fallback; the other sites stay clean."""
        gen, s = _scorer(quant=False)
        assert not is_quantized_bert(s.models.bert)
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        snap = s.kernel_snapshot()
        assert snap["dispatch"]["dequant_matmul"] == 1
        assert snap["fallback"]["dequant_matmul"] == 1
        assert snap["fallback"]["epilogue"] == 0
        assert snap["fallback"]["attention"] == 0


# -------------------------------------------------------- kernel_* metrics
class TestSyncKernels:
    def test_counter_delta_mirror_and_exhaustive_modes(self):
        from realtime_fraud_detection_tpu.obs.metrics import MetricsCollector

        gen, s = _scorer()
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        m = MetricsCollector()
        m.sync_kernels(s.kernel_snapshot())
        m.sync_kernels(s.kernel_snapshot())     # re-sync: NOT double-counted
        assert m.kernel_dispatches.value(site="epilogue") == 1.0
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        m.sync_kernels(s.kernel_snapshot())
        assert m.kernel_dispatches.value(site="epilogue") == 2.0
        assert m.kernel_fallbacks.value(site="dequant_matmul") == 0.0
        # site-mode gauges are exhaustive: the inactive mode reads 0
        assert m.kernel_site_mode.value(site="epilogue", mode="pallas") == 1.0
        assert m.kernel_site_mode.value(site="epilogue", mode="off") == 0.0
        assert m.kernel_site_mode.value(site="attention",
                                        mode="flash") == 1.0
        assert m.kernel_site_mode.value(site="attention",
                                        mode="reference") == 0.0
        assert m.kernel_interpret.value() == 1.0

    def test_stream_and_serving_render_identical(self):
        from realtime_fraud_detection_tpu.obs.metrics import MetricsCollector

        gen, s = _scorer()
        s.score_batch(gen.generate_batch(BATCH), now=1000.0)
        snap = s.kernel_snapshot()
        a, b = MetricsCollector(), MetricsCollector()
        a.sync_kernels(snap)
        b.sync_kernels(snap)

        def kernel_lines(mc):
            return [ln for ln in mc.render_prometheus().splitlines()
                    if ln.startswith("kernel_")]

        assert kernel_lines(a) and kernel_lines(a) == kernel_lines(b)
        text = a.render_prometheus()
        assert 'kernel_site_mode{mode="pallas",site="epilogue"} 1' in text \
            or 'kernel_site_mode{site="epilogue",mode="pallas"} 1' in text
        assert "kernel_dispatch_total" in text


# ------------------------------------------------------- checkpoint hygiene
class TestCheckpointKernelHygiene:
    def test_manifest_carries_no_kernel_stamp(self, tmp_path):
        from realtime_fraud_detection_tpu.checkpoint import CheckpointManager

        _, s = _scorer()
        mgr = CheckpointManager(tmp_path / "ck")
        mgr.save(1, params=s.models)
        manifest = mgr.manifest(1)
        assert not any("kernel" in key for key in manifest)
        assert manifest["quant_mode"] == {"bert_weights": "int8"}

    def test_restore_round_trips_identically_kernels_on_off(self, tmp_path):
        """Kernel selection is runtime config: one checkpoint restores
        into kernels-on and kernels-off scorers alike, each keeps its own
        (unserialized) kernel selection, and both serve the same
        decisions."""
        from realtime_fraud_detection_tpu.checkpoint import CheckpointManager

        _, src = _scorer(kernels=False, seed=0)
        mgr = CheckpointManager(tmp_path / "ck")
        mgr.save(2, params=src.models)

        gen_off, off = _scorer(kernels=False, seed=9)
        gen_on, on = _scorer(kernels=True, seed=9)
        assert mgr.restore_into_scorer(off).step == 2
        assert mgr.restore_into_scorer(on).step == 2
        # the restore moved params only — each side's kernel plane stands
        assert off.kernel_static()["epilogue_kernel"] == "off"
        assert on.kernel_static()["epilogue_kernel"] == "pallas"
        ra = off.score_batch(gen_off.generate_batch(BATCH), now=1000.0)
        rb = on.score_batch(gen_on.generate_batch(BATCH), now=1000.0)
        assert [r["decision"] for r in ra] == [r["decision"] for r in rb]
        pa = np.asarray([r["fraud_probability"] for r in ra])
        pb = np.asarray([r["fraud_probability"] for r in rb])
        assert np.max(np.abs(pa - pb)) < 1e-3


# ------------------------------------------------- pool / mesh composition
class TestPoolMeshComposition:
    def test_pooled_kernels_bit_identical_to_serial(self):
        gen_a, serial = _scorer()
        gen_b, pooled = _scorer()
        DevicePool(pooled, inflight_depth=2)
        batches_a = [gen_a.generate_batch(BATCH) for _ in range(4)]
        batches_b = [gen_b.generate_batch(BATCH) for _ in range(4)]
        pend_a = [serial.dispatch(b, now=1000.0) for b in batches_a]
        want = [_rows(serial.finalize(p, now=1000.0)) for p in pend_a]
        pend_b = [pooled.dispatch(b, now=1000.0) for b in batches_b]
        got = [_rows(pooled.finalize(p, now=1000.0)) for p in pend_b]
        assert got == want
        snap = pooled.kernel_snapshot()
        assert all(v == 0 for v in snap["fallback"].values())

    def test_pool_hot_swap_no_mixed_kernel_batch(self):
        """Replica-by-replica hot swap under the score lock with the
        kernel plane on: the swapped-in f32 params are re-quantized so
        the fused dequant kernel keeps engaging (zero fallbacks), and the
        pooled sequence stays bit-identical to a serial scorer running
        the SAME dispatch/swap/dispatch interleaving."""
        from realtime_fraud_detection_tpu.scoring.pipeline import (
            init_scoring_models,
        )

        sides = []
        for use_pool in (False, True):
            gen, s = _scorer()
            if use_pool:
                DevicePool(s, inflight_depth=2)
            fresh = init_scoring_models(jax.random.PRNGKey(42),
                                        bert_config=s.bert_config,
                                        feature_dim=s.sc.feature_dim,
                                        node_dim=s.sc.node_dim)
            batches = [gen.generate_batch(BATCH) for _ in range(3)]
            out = _rows(s.finalize(s.dispatch(batches[0], now=1000.0),
                                   now=1000.0))
            s.set_models(fresh)         # fans out under the score lock
            assert is_quantized_bert(s.models.bert)
            pend = [s.dispatch(b, now=1000.0) for b in batches[1:]]
            for p in pend:
                out.extend(_rows(s.finalize(p, now=1000.0)))
            assert all(v == 0 for v in
                       s.kernel_snapshot()["fallback"].values())
            sides.append(out)
        assert sides[0] == sides[1]

    @staticmethod
    def _pipelined(scorer, batches):
        """Depth-2 pipelined drive: two launches in flight before the
        first finalize, never out-dispatching an attached executor's
        slots (a single-threaded dispatcher past depth would deadlock by
        design) — the SAME interleaving on reference and meshed sides so
        state evolution matches step for step."""
        from collections import deque

        pend, got = deque(), []
        for b in batches:
            pend.append(scorer.dispatch(b, now=1000.0))
            if len(pend) >= 2:
                got.append(_rows(scorer.finalize(pend.popleft(),
                                                 now=1000.0)))
        while pend:
            got.append(_rows(scorer.finalize(pend.popleft(), now=1000.0)))
        return got

    def test_mesh_executor_kernels_pipelined_depth2(self):
        gen_a, ref = _scorer(one_device=True)
        want = self._pipelined(
            ref, [gen_a.generate_batch(BATCH) for _ in range(3)])

        gen_b, meshed = _scorer(one_device=True)
        MeshExecutor(meshed, model_axis=2, inflight_depth=2,
                     shard_branches=("bert_text",))
        got = self._pipelined(
            meshed, [gen_b.generate_batch(BATCH) for _ in range(3)])
        assert got == want
        snap = meshed.kernel_snapshot()
        assert snap["dispatch"]["dequant_matmul"] == 3
        assert all(v == 0 for v in snap["fallback"].values())


# ----------------------------------------------------------------- CLI
class TestCliFlags:
    def test_parse_kernel_flags(self):
        from realtime_fraud_detection_tpu.cli import build_parser

        p = build_parser()
        assert p.parse_args(["run-job", "--kernels"]).kernels is True
        assert p.parse_args(["serve", "--kernels"]).kernels is True
        args = p.parse_args(["kernel-drill", "--fast", "--no-replay",
                             "--seed", "5"])
        assert args.fast and args.no_replay and args.seed == 5


def test_kernel_drill_fast_smoke():
    """Tier-1 acceptance: `rtfd kernel-drill --fast` runs un-slow-marked
    on every pass — divergence below the measured bf16 calibration-noise
    bound, zero decision flips, exact masked rungs, per-kernel parity,
    every site dispatched with zero fallbacks (replay runs in the full
    drill; the fast smoke pins the gates themselves). Runs as a real CLI
    subprocess in the single-device serving env (the netfault/elastic
    drill-CLI convention): the harness's 8-virtual-device mesh exists for
    sharding tests and makes interpret-mode Pallas pay ~2.6x for nothing
    this drill measures."""
    import os
    import pathlib
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    proc = subprocess.run(
        [sys.executable, "-m", "realtime_fraud_detection_tpu",
         "kernel-drill", "--fast", "--no-replay"],
        capture_output=True, text=True, timeout=600,
        cwd=str(pathlib.Path(__file__).resolve().parents[1]), env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout.strip().splitlines()
    compact = json.loads(out[-1])               # final line: compact verdict
    assert len(out[-1].encode()) < 2048
    assert compact["passed"] is True
    checks = compact["checks"]
    assert checks["divergence_below_noise"]
    assert checks["zero_decision_flips"]
    assert checks["masked_rungs_equal"]
    assert checks["rules_only_exact"]
    assert checks["dequant_matmul_parity"]
    assert checks["dequant_rows_parity"]
    assert checks["epilogue_parity"]
    assert checks["attention_parity"]
    assert checks["all_sites_dispatched"]
    assert checks["zero_fallbacks"]
    full = json.loads(out[-2])                  # preceding line: full result
    assert full["divergence"]["decision_flips"] == 0
    assert full["divergence"]["max"] <= \
        full["divergence"]["noise_scale"] * \
        full["divergence"]["noise_floor"]["bound"]
    assert full["modes"]["off"]["epilogue"] == "off"
    assert full["modes"]["on"]["epilogue"] == "pallas"


# ---------------------------------------------------------------------------
# which platform the kernels lower for is decided by the scorer's mesh, and
# nothing is interpreted or swapped silently
# ---------------------------------------------------------------------------

def _mesh_on(platform: str):
    """A stand-in mesh whose devices report ``platform`` (the scorer reads
    only that before the kernel-plane checks)."""
    import types

    devices = np.empty((1,), object)
    devices[0] = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(devices=devices)


def test_cpu_mesh_serves_kernels_interpreted_and_says_so():
    scorer = FraudScorer(Config(kernels=KernelSettings.full()),
                         scorer_config=ScorerConfig(text_len=32))
    assert scorer.kernel_snapshot()["interpret"] is True
    assert scorer.kernel_static()["kernel_interpret"] is True


def test_kernels_refuse_a_platform_they_neither_compile_nor_interpret_for():
    with pytest.raises(ValueError, match="'gpu'"):
        FraudScorer(Config(kernels=KernelSettings.full()),
                    mesh=_mesh_on("gpu"))
