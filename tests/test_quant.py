"""Quantized scoring plane (ISSUE 9): weight-only int8 BERT calibration,
the QuantSettings config surface, scorer threading, checkpoint quant-mode
arch stamps, the quant_* Prometheus mirror, and the `rtfd quant-drill`
tier-1 smoke."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realtime_fraud_detection_tpu.models.bert import (
    TINY_CONFIG,
    bert_predict,
    init_bert_params,
)
from realtime_fraud_detection_tpu.models.quant import (
    bert_param_bytes,
    is_quantized_bert,
    quant_error_bound,
    quantize_bert_params,
    quantize_dense,
    quantize_embedding,
)
from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
from realtime_fraud_detection_tpu.utils.config import Config, QuantSettings


def _quant_config() -> Config:
    return Config(quant=QuantSettings.full())


def _scorer_pair(seed=0, n_users=120, n_merch=40):
    """Identically seeded (f32, quantized) scorers with seeded profiles."""
    out = []
    for cfg in (Config(), _quant_config()):
        gen = TransactionGenerator(num_users=n_users, num_merchants=n_merch,
                                   seed=7)
        s = FraudScorer(cfg, scorer_config=ScorerConfig(), seed=seed)
        s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        out.append((gen, s))
    return out


class TestCalibration:
    def test_dense_reconstruction_within_half_lsb(self):
        rng = np.random.default_rng(0)
        w = rng.standard_normal((32, 16)).astype(np.float32) * 0.2
        q = quantize_dense({"w": w, "b": np.zeros(16, np.float32)})
        assert q["qw"].dtype == np.int8 and q["scale"].shape == (16,)
        recon = q["qw"].astype(np.float32) * q["scale"][None, :]
        # symmetric rounding: error bounded by half a step per channel
        assert np.all(np.abs(recon - w) <= q["scale"][None, :] * 0.5 + 1e-7)

    def test_zero_channel_stays_exact_zero(self):
        w = np.zeros((8, 4), np.float32)
        w[:, 0] = 1.0
        q = quantize_dense({"w": w, "b": np.zeros(4, np.float32)})
        recon = q["qw"].astype(np.float32) * q["scale"][None, :]
        assert np.array_equal(recon[:, 1:], np.zeros((8, 3), np.float32))
        np.testing.assert_allclose(recon[:, 0], w[:, 0], atol=1e-6)

    def test_embedding_per_row_scales(self):
        rng = np.random.default_rng(1)
        w = rng.standard_normal((10, 6)).astype(np.float32)
        w[3] *= 50.0                      # an outlier row must not crush
        q = quantize_embedding(w)         # the resolution of the others
        recon = q["qe"].astype(np.float32) * q["scale"][:, None]
        assert np.all(np.abs(recon - w) <= q["scale"][:, None] * 0.5 + 1e-6)

    def test_bert_pytree_layout_and_idempotence(self):
        params = init_bert_params(jax.random.PRNGKey(0), TINY_CONFIG)
        q = quantize_bert_params(jax.device_get(params))
        assert is_quantized_bert(q) and not is_quantized_bert(params)
        # head + norms stay f32; every per-layer dense went int8
        assert "w" in q["classifier"] and "qw" in q["layers"][0]["ffn1"]
        # idempotent: a hot-swap path can apply it unconditionally
        q2 = quantize_bert_params(q)
        assert q2 is q
        assert quant_error_bound(q) > 0.0

    def test_deterministic_calibration(self):
        params = jax.device_get(init_bert_params(jax.random.PRNGKey(3),
                                                 TINY_CONFIG))
        a, b = quantize_bert_params(params), quantize_bert_params(params)
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            assert np.array_equal(np.asarray(x), np.asarray(y))

    def test_bytes_ratio_exceeds_floor(self):
        """Acceptance: quantized BERT branch >= 3.5x smaller than f32."""
        params = init_bert_params(jax.random.PRNGKey(0), TINY_CONFIG)
        q = quantize_bert_params(jax.device_get(params))
        assert bert_param_bytes(params) / bert_param_bytes(q) >= 3.5

    def test_forward_parity_close(self):
        params = init_bert_params(jax.random.PRNGKey(5), TINY_CONFIG)
        q = jax.device_put(quantize_bert_params(jax.device_get(params)))
        rng = np.random.default_rng(5)
        ids = jnp.asarray(rng.integers(0, TINY_CONFIG.vocab_size, (8, 16)),
                          jnp.int32)
        mask = jnp.ones((8, 16), bool)
        a = np.asarray(bert_predict(params, ids, mask, TINY_CONFIG))
        b = np.asarray(bert_predict(q, ids, mask, TINY_CONFIG))
        np.testing.assert_allclose(a, b, atol=2e-3)


class TestQuantSettings:
    def test_validation(self):
        with pytest.raises(ValueError):
            QuantSettings(bert_weights="int4").validate()
        with pytest.raises(ValueError):
            QuantSettings(tree_kernel="einsum").validate()
        QuantSettings.full().validate()

    def test_disabled_plane_serves_f32_gather(self):
        s = QuantSettings(bert_weights="int8", tree_kernel="gemm")
        assert not s.enabled
        assert s.bert_mode() == "f32"
        assert s.stamp() == {"bert_weights": "f32"}
        assert QuantSettings.full().stamp() == {"bert_weights": "int8"}

    def test_config_overlay_round_trip(self, tmp_path):
        p = tmp_path / "q.json"
        p.write_text(json.dumps({"quant": {"enabled": True,
                                           "bert_weights": "int8"}}))
        loaded = Config.from_file(str(p)).quant
        assert loaded.enabled and loaded.bert_mode() == "int8"
        assert loaded.tree_kernel == "gather"


class TestScorerThreading:
    def test_quant_scorer_serves_int8_and_gemm(self):
        (_, f32), (_, q) = _scorer_pair()
        assert not is_quantized_bert(f32.models.bert)
        assert is_quantized_bert(q.models.bert)
        assert q.quant_static() == {"tree_kernel": "gemm",
                                    "iforest_kernel": "gemm"}
        assert f32.quant_static() == {"tree_kernel": "gather",
                                      "iforest_kernel": "gather"}
        snap = q.quant_snapshot()
        assert snap["modes"] == {"bert_text": "int8",
                                 "xgboost_primary": "gemm",
                                 "isolation_forest": "gemm"}
        assert snap["param_bytes"]["bert_text"] < \
            f32.quant_snapshot()["param_bytes"]["bert_text"]

    def test_score_parity_and_zero_flips(self):
        (gen_f, f32), (gen_q, q) = _scorer_pair()
        ra = f32.score_batch(gen_f.generate_batch(48), now=1000.0)
        rb = q.score_batch(gen_q.generate_batch(48), now=1000.0)
        pa = np.asarray([r["fraud_probability"] for r in ra])
        pb = np.asarray([r["fraud_probability"] for r in rb])
        assert np.max(np.abs(pa - pb)) < 1e-3
        assert [r["decision"] for r in ra] == [r["decision"] for r in rb]

    def test_set_models_quantizes_incoming_f32(self):
        from realtime_fraud_detection_tpu.scoring.pipeline import (
            init_scoring_models,
        )

        (_, _), (_, q) = _scorer_pair()
        fresh = init_scoring_models(jax.random.PRNGKey(42),
                                    bert_config=q.bert_config,
                                    feature_dim=q.sc.feature_dim,
                                    node_dim=q.sc.node_dim)
        assert not is_quantized_bert(fresh.bert)
        q.set_models(fresh)     # hot swap: promotion / reload / drill
        assert is_quantized_bert(q.models.bert)

    def test_init_quantized_params_are_device_committed(self):
        """Regression pin: __init__ calibration must commit the int8
        pytree back onto the mesh (host numpy leaves in self.models would
        re-upload the whole BERT branch H2D on every non-pool dispatch —
        the exact payload this plane shrinks)."""
        (_, _), (_, q) = _scorer_pair()
        for leaf in jax.tree_util.tree_leaves(q.models.bert):
            assert isinstance(leaf, jax.Array), type(leaf)

    def test_gate_ledger_counts(self):
        (_, _), (_, q) = _scorer_pair()
        q.record_quant_gate(True)
        q.record_quant_gate(True)
        q.record_quant_gate(False)
        assert q.quant_snapshot()["gate"] == {"pass": 2, "fail": 1}


class TestCheckpointQuantStamp:
    def _mk(self, tmp_path, quantized: bool, seed=0):
        from realtime_fraud_detection_tpu.checkpoint import CheckpointManager

        cfg = _quant_config() if quantized else Config()
        s = FraudScorer(cfg, scorer_config=ScorerConfig(), seed=seed)
        mgr = CheckpointManager(tmp_path / "ck")
        return s, mgr

    def test_manifest_records_quant_mode(self, tmp_path):
        s, mgr = self._mk(tmp_path, quantized=True)
        mgr.save(1, params=s.models)
        assert mgr.manifest(1)["quant_mode"] == {"bert_weights": "int8"}
        s2, mgr2 = self._mk(tmp_path / "b", quantized=False)
        mgr2.save(1, params=s2.models)
        assert mgr2.manifest(1)["quant_mode"] == {"bert_weights": "f32"}

    def test_same_mode_round_trip_serves_identically(self, tmp_path):
        gen = TransactionGenerator(num_users=80, num_merchants=30, seed=3)
        s, mgr = self._mk(tmp_path, quantized=True)
        s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        mgr.save(2, params=s.models)
        ref = s.score_batch(gen.generate_batch(16), now=1000.0)

        gen2 = TransactionGenerator(num_users=80, num_merchants=30, seed=3)
        s2 = FraudScorer(_quant_config(), scorer_config=ScorerConfig(),
                         seed=9)        # different init: restore overwrites
        s2.seed_profiles(gen2.users.profiles(), gen2.merchants.profiles())
        ck = mgr.restore_into_scorer(s2)
        assert ck.step == 2 and is_quantized_bert(s2.models.bert)
        got = s2.score_batch(gen2.generate_batch(16), now=1000.0)
        assert [r["fraud_probability"] for r in ref] == \
            [r["fraud_probability"] for r in got]

    def test_cross_mode_restore_refused_both_ways(self, tmp_path):
        s_q, mgr_q = self._mk(tmp_path / "q", quantized=True)
        mgr_q.save(1, params=s_q.models)
        s_f, mgr_f = self._mk(tmp_path / "f", quantized=False)
        mgr_f.save(1, params=s_f.models)

        # int8 checkpoint into an f32 scorer: refused
        with pytest.raises(ValueError, match="quantization-mode mismatch"):
            mgr_q.restore_into_scorer(
                FraudScorer(Config(), scorer_config=ScorerConfig()))
        # f32 checkpoint into a quantized scorer: refused
        with pytest.raises(ValueError, match="quantization-mode mismatch"):
            mgr_f.restore_into_scorer(
                FraudScorer(_quant_config(), scorer_config=ScorerConfig()))

    def test_allow_arch_mismatch_serves_checkpoint_form(self, tmp_path):
        s_q, mgr_q = self._mk(tmp_path / "q", quantized=True)
        mgr_q.save(1, params=s_q.models)
        f32 = FraudScorer(Config(), scorer_config=ScorerConfig())
        mgr_q.restore_into_scorer(f32, allow_arch_mismatch=True)
        # the scorer serves the checkpoint's actual (int8) form, and the
        # observability snapshot reads the live-params truth
        assert is_quantized_bert(f32.models.bert)
        assert f32.quant_snapshot()["modes"]["bert_text"] == "int8"

    def test_stampless_manifest_restores_leniently(self, tmp_path):
        s, mgr = self._mk(tmp_path, quantized=False)
        mgr.save(1, params=s.models)
        mpath = mgr.directory / "step_0000000001" / "manifest.json"
        m = json.loads(mpath.read_text())
        del m["quant_mode"]               # an old, pre-ISSUE-9 checkpoint
        mpath.write_text(json.dumps(m))
        target = FraudScorer(_quant_config(), scorer_config=ScorerConfig())
        mgr.restore_into_scorer(target)   # no refusal
        # set_models quantized the incoming f32 params to the scorer's form
        assert is_quantized_bert(target.models.bert)


class TestSyncQuant:
    def test_counter_delta_mirror_and_modes(self):
        from realtime_fraud_detection_tpu.obs.metrics import MetricsCollector

        (_, _), (_, q) = _scorer_pair()
        q.record_quant_gate(True)
        m = MetricsCollector()
        m.sync_quant(q.quant_snapshot())
        m.sync_quant(q.quant_snapshot())        # re-sync: NOT double-counted
        assert m.quant_gate_verdicts.value(verdict="pass") == 1.0
        q.record_quant_gate(False)
        m.sync_quant(q.quant_snapshot())
        assert m.quant_gate_verdicts.value(verdict="pass") == 1.0
        assert m.quant_gate_verdicts.value(verdict="fail") == 1.0
        # branch-mode gauges are exhaustive: the inactive mode reads 0
        assert m.quant_branch_mode.value(branch="bert_text",
                                         mode="int8") == 1.0
        assert m.quant_branch_mode.value(branch="bert_text",
                                         mode="f32") == 0.0
        assert m.quant_branch_mode.value(branch="xgboost_primary",
                                         mode="gemm") == 1.0
        assert m.quant_param_bytes.value(branch="bert_text") > 0

    def test_stream_and_serving_render_identical(self):
        """Satellite pin: the stream job and the serving app mirror the
        SAME scorer snapshot into independent collectors — the rendered
        quant_* series must match line for line."""
        from realtime_fraud_detection_tpu.obs.metrics import MetricsCollector

        (_, _), (_, q) = _scorer_pair()
        q.record_quant_gate(True)
        snap = q.quant_snapshot()
        a, b = MetricsCollector(), MetricsCollector()
        a.sync_quant(snap)
        b.sync_quant(snap)

        def quant_lines(mc):
            return [ln for ln in mc.render_prometheus().splitlines()
                    if ln.startswith("quant_")]

        assert quant_lines(a) and quant_lines(a) == quant_lines(b)

    def test_serving_metrics_endpoint_exposes_quant(self):
        from realtime_fraud_detection_tpu.obs.metrics import MetricsCollector

        m = MetricsCollector()
        (_, _), (_, q) = _scorer_pair()
        m.sync_quant(q.quant_snapshot())
        text = m.render_prometheus()
        assert 'quant_branch_mode{branch="bert_text",mode="int8"} 1' in text
        assert "quant_gate_verdicts_total" in text


class TestCliFlags:
    def test_parse_quant_flags(self):
        from realtime_fraud_detection_tpu.cli import build_parser

        p = build_parser()
        assert p.parse_args(["run-job", "--quant"]).quant is True
        assert p.parse_args(["serve", "--quant"]).quant is True
        args = p.parse_args(["quant-drill", "--fast", "--no-replay",
                             "--seed", "5"])
        assert args.fast and args.no_replay and args.seed == 5


def test_quant_drill_fast_smoke(capsys):
    """Tier-1 acceptance: `rtfd quant-drill --fast` runs un-slow-marked on
    every pass — divergence below the calibration-noise bound, zero
    decision flips at the operating point, AUC unchanged on the quality
    protocol, exact GEMM-vs-gather leaves, >= 3.5x smaller BERT bytes,
    and a bit-identical replay."""
    from realtime_fraud_detection_tpu import cli

    rc = cli.main(["quant-drill", "--fast"])
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    compact = json.loads(out[-1])               # final line: compact verdict
    assert len(out[-1].encode()) < 2048
    assert compact["passed"] is True
    checks = compact["checks"]
    assert checks["divergence_below_noise"]
    assert checks["zero_decision_flips"]
    assert checks["auc_unchanged"]
    assert checks["gemm_leaves_identical"]
    assert checks["gemm_logits_within_tol"]
    assert checks["bytes_ratio_ge_min"]
    assert checks["replay_bit_identical"]
    full = json.loads(out[-2])                  # preceding line: full result
    assert full["divergence"]["decision_flips"] == 0
    assert full["param_bytes"]["ratio"] >= 3.5
    assert full["divergence"]["max"] <= \
        full["divergence"]["noise_floor"]["bound"]
