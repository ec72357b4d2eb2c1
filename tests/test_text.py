"""Text branch tests: tokenizer, attention kernel, BERT, analyzer."""

import jax
import numpy as np
import pytest

from realtime_fraud_detection_tpu.models.bert import (
    TINY_CONFIG,
    BertConfig,
    bert_predict,
    init_bert_params,
)
from realtime_fraud_detection_tpu.models.text import (
    TextAnalyzer,
    combined_text,
    detect_fraud_patterns,
    get_text_features,
)
from realtime_fraud_detection_tpu.models.tokenizer import (
    CLS_ID,
    PAD_ID,
    SEP_ID,
    FraudTokenizer,
)
from realtime_fraud_detection_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    flash_supported,
    merge_heads,
    split_heads,
)


class TestTokenizer:
    def test_preprocess_matches_reference(self):
        # bert_text_analyzer.py:228-251: lower, strip specials, collapse ws
        assert FraudTokenizer.preprocess("  QuickPay!! #1  Wire-Transfer ") == \
            "quickpay 1 wire transfer"

    def test_deterministic_and_special_tokens(self):
        tok = FraudTokenizer(max_length=16)
        a = tok.encode("Bitcoin Exchange LLC")
        b = tok.encode("Bitcoin Exchange LLC")
        assert a == b
        assert a[0] == CLS_ID and a[-1] == SEP_ID

    def test_domain_words_stable_oov_hashed(self):
        tok = FraudTokenizer()
        bitcoin = tok.encode("bitcoin")[1]
        assert bitcoin < 2000  # in-vocab id
        weird = tok.encode("zxqvwk")[1]
        assert 2000 <= weird < tok.vocab_size

    def test_batch_padding_and_mask(self):
        tok = FraudTokenizer(max_length=8)
        ids, mask = tok.encode_batch(["one two", ""])
        assert ids.shape == (2, 8)
        assert mask[0].sum() == 4  # CLS one two SEP
        assert mask[1].sum() == 2  # CLS SEP
        assert (ids[0][~mask[0]] == PAD_ID).all()


class TestFlashAttention:
    """The fused core inside the text branch: ``bert_predict`` with the
    kernel asked for (interpreted here) against the reference path."""

    @staticmethod
    def _both_ways(t, lens, seed=0):
        cfg = TINY_CONFIG
        params = init_bert_params(jax.random.PRNGKey(seed), cfg)
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, cfg.vocab_size, (len(lens), t)).astype(np.int32)
        mask = np.arange(t)[None, :] < np.asarray(lens)[:, None]
        ref = bert_predict(params, ids, mask, cfg, use_pallas=False)
        got = bert_predict(params, ids, mask, cfg, use_pallas=True,
                           kernel_interpret=True)
        return np.asarray(got), np.asarray(ref)

    @pytest.mark.parametrize("t", [128, 256, 512])
    def test_branch_matches_reference(self, t):
        # one real token (all keys beyond [CLS] masked), the long tail's
        # median and p99, the full window
        got, ref = self._both_ways(t, [1, 20, min(207, t), t])
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_fully_masked_rows_no_nan(self):
        got, ref = self._both_ways(128, [0, 0])
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)

    def test_unsupported_length_runs_the_reference(self):
        """The traced guard: at a length ``flash_supported`` declines the
        program asked for the kernel IS the reference program."""
        assert not flash_supported(64, TINY_CONFIG.head_dim,
                                   TINY_CONFIG.num_heads)
        got, ref = self._both_ways(64, [1, 20, 64])
        np.testing.assert_array_equal(got, ref)

    def test_indivisible_seq_rejected(self):
        q = np.zeros((1, 100, 128), np.float32)
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, q, q, num_heads=2, interpret=True)

    def test_fused_core_matches_split_reference(self):
        rng = np.random.default_rng(3)
        b, h, t, d = 2, 2, 128, 64
        # bf16-representable inputs: both sides multiply the same numbers
        q, k, v = (np.asarray(jax.numpy.asarray(
            rng.normal(size=(b, t, h * d)), jax.numpy.bfloat16), np.float32)
            for _ in range(3))
        mask = rng.random((b, t)) > 0.3
        mask[:, 0] = True

        ours = np.asarray(flash_attention(q, k, v, mask, num_heads=h,
                                          interpret=True))
        ref = np.asarray(merge_heads(attention_reference(
            *(split_heads(jax.numpy.asarray(x), h) for x in (q, k, v)),
            mask)))
        # what is left is the bf16 rounding of the softmax weights
        np.testing.assert_allclose(ours, ref, rtol=1e-2, atol=1e-2)


class TestBert:
    def test_logits_shape_and_probs(self):
        cfg = TINY_CONFIG
        params = init_bert_params(jax.random.PRNGKey(0), cfg)
        tok = FraudTokenizer(max_length=32)
        ids, mask = tok.encode_batch(["gift card outlet", "corner grocery store"])
        p = np.asarray(bert_predict(params, ids, mask, cfg))
        assert p.shape == (2,)
        assert ((p > 0) & (p < 1)).all()

    def test_padding_invariance(self):
        # same text at max_length 16 vs 32 must give the same probability
        cfg = TINY_CONFIG
        params = init_bert_params(jax.random.PRNGKey(1), cfg)
        short_tok = FraudTokenizer(max_length=16)
        long_tok = FraudTokenizer(max_length=32)
        text = ["wire transfer co"]
        a = np.asarray(bert_predict(params, *short_tok.encode_batch(text), cfg))
        b = np.asarray(bert_predict(params, *long_tok.encode_batch(text), cfg))
        np.testing.assert_allclose(a, b, atol=2e-3)


class TestTextRules:
    def test_keyword_groups(self):
        # bert_text_analyzer.py:309-342
        p = detect_fraud_patterns({"merchant_name": "QuickBitcoin Wallet",
                                   "description": "urgent gift card reload"})
        assert p["crypto_keywords"] and p["urgent_language"] and p["gift_card_keywords"]
        assert not p["known_scam_patterns"]
        p2 = detect_fraud_patterns({"description": "nigerian prince inheritance"})
        assert p2["known_scam_patterns"]

    def test_combined_text_format(self):
        # bert_text_analyzer.py:253-281
        t = combined_text({"merchant_name": "Acme", "category": "retail"})
        assert t == "Merchant: Acme | Category: retail"

    def test_text_features(self):
        # bert_text_analyzer.py:346-399
        f = get_text_features({"merchant_name": "Shop-24x7!", "description": "pay 99"})
        assert f["merchant_name_length"] == 10
        assert f["numbers_in_merchant"] == 3  # 2, 4, 7
        assert f["special_chars_merchant"] == 2  # '-' and '!'
        assert f["merchant_word_count"] == 1
        assert f["total_word_count"] == 3


class TestTextAnalyzer:
    def test_batched_field_risks_and_overall(self):
        analyzer = TextAnalyzer(config=TINY_CONFIG, max_length=32)
        results = analyzer.analyze_transaction_text([
            {"merchant_name": "Casino Royale", "category": "gambling"},
            {"description": "grocery run"},
            {},
        ])
        r0, r1, r2 = results
        assert {"merchant_name_risk", "combined_text_risk", "overall_text_risk"} <= set(r0)
        # weighted overall (weights .4/.3 renormalized)
        expected = (r0["merchant_name_risk"] * 0.4 + r0["combined_text_risk"] * 0.3) / 0.7
        assert r0["overall_text_risk"] == pytest.approx(expected, rel=1e-5)
        assert "description_risk" in r1 and "merchant_name_risk" not in r1
        assert r2 == {"overall_text_risk": 0.0}

    def test_performance_stats(self):
        analyzer = TextAnalyzer(config=TINY_CONFIG, max_length=16)
        analyzer.analyze_transaction_text([{"merchant_name": "x"}])
        stats = analyzer.get_performance_stats()
        assert stats["total_predictions"] == 1
        assert stats["avg_processing_time_ms"] > 0


class TestTextTraining:
    def test_bert_learns_suspicious_names(self):
        from realtime_fraud_detection_tpu.sim import TransactionGenerator
        from realtime_fraud_detection_tpu.training.text import (
            build_text_dataset,
            train_bert,
        )

        gen = TransactionGenerator(num_users=200, num_merchants=100, seed=4)
        params = train_bert(gen, config=TINY_CONFIG, n_transactions=4000,
                            max_length=32, epochs=3, seed=0)
        ids, mask, labels = build_text_dataset(gen, 2000, max_length=32)
        p = np.asarray(bert_predict(params, ids, mask, TINY_CONFIG))
        order = np.argsort(p)
        ranks = np.empty_like(order, dtype=np.float64)
        ranks[order] = np.arange(1, len(p) + 1)
        pos = labels > 0.5
        n1, n0 = pos.sum(), (~pos).sum()
        auc = (ranks[pos].sum() - n1 * (n1 + 1) / 2) / (n1 * n0)
        # text alone is a weak signal (only merchant identity correlates);
        # must still be clearly better than chance
        assert auc > 0.6, f"AUC {auc:.3f}"


class TestKeywordVocabCoupling:
    def test_rule_keywords_are_in_vocab(self):
        from realtime_fraud_detection_tpu.models.keywords import (
            ALL_KEYWORD_GROUPS,
        )

        tok = FraudTokenizer()
        for group in ALL_KEYWORD_GROUPS:
            for phrase in group:
                for word in phrase.split():
                    assert word in tok.vocab, f"{word!r} fell out of the vocab"


class TestWordPiece:
    """models/wordpiece.py: the trained-subword analog of the reference's
    distilbert-base-uncased tokenizer (bert_text_analyzer.py:47-66)."""

    def test_trainer_learns_frequent_words_as_whole_pieces(self):
        from realtime_fraud_detection_tpu.models.wordpiece import (
            train_wordpiece_vocab,
        )

        vocab = train_wordpiece_vocab(
            ["crypto exchange wire transfer"] * 50 + ["casino cash out"] * 30,
            vocab_size=200)
        for w in ("crypto", "exchange", "wire", "transfer", "casino"):
            assert w in vocab, f"frequent word {w!r} not a whole piece"

    def test_greedy_longest_match_and_continuations(self):
        from realtime_fraud_detection_tpu.models.wordpiece import (
            WordPieceTokenizer,
        )

        t = WordPieceTokenizer(vocab=["crypto", "pay", "##pay", "c", "##r"],
                               max_length=16)
        pieces = t.decode_pieces(t.encode("cryptopay"))
        assert pieces == ["[CLS]", "crypto", "##pay", "[SEP]"]

    def test_uncoverable_word_becomes_unk_not_crash(self):
        from realtime_fraud_detection_tpu.models.wordpiece import (
            WordPieceTokenizer,
        )

        t = WordPieceTokenizer(vocab=["abc"], max_length=16)
        pieces = t.decode_pieces(t.encode("abc zzz"))
        assert pieces == ["[CLS]", "abc", "[UNK]", "[SEP]"]

    def test_committed_domain_vocab_loads_and_covers_fraud_terms(self):
        from realtime_fraud_detection_tpu.models.wordpiece import (
            WordPieceTokenizer,
        )

        t = WordPieceTokenizer(max_length=32)   # committed vocab file
        assert t.vocab_size > 1500
        # the planted suspicious-merchant tokens (sim/simulator.py) must
        # tokenize to whole pieces — this is the signal the text branch
        # learns from
        for term in ("crypto", "exchange", "gift", "card", "wire",
                     "transfer", "casino"):
            ids = t.encode(term)
            assert len(ids) == 3, f"{term!r} -> {t.decode_pieces(ids)}"

    def test_encode_batch_shapes_and_special_ids(self):
        import numpy as np

        from realtime_fraud_detection_tpu.models.tokenizer import (
            CLS_ID,
            PAD_ID,
            SEP_ID,
        )
        from realtime_fraud_detection_tpu.models.wordpiece import (
            WordPieceTokenizer,
        )

        t = WordPieceTokenizer(max_length=12)
        ids, mask = t.encode_batch(["crypto exchange", ""])
        assert ids.shape == (2, 12) and mask.shape == (2, 12)
        assert ids.dtype == np.int32
        assert ids[0, 0] == CLS_ID
        assert SEP_ID in ids[0]
        assert ids[1, 2] == PAD_ID and not mask[1, 2]

    def test_scorer_uses_wordpiece_by_config(self):
        from realtime_fraud_detection_tpu.models.wordpiece import (
            WordPieceTokenizer,
        )
        from realtime_fraud_detection_tpu.scoring import (
            FraudScorer,
            ScorerConfig,
        )
        from realtime_fraud_detection_tpu.sim.simulator import (
            TransactionGenerator,
        )

        gen = TransactionGenerator(num_users=16, num_merchants=8, seed=1)
        scorer = FraudScorer(
            scorer_config=ScorerConfig(text_len=32, tokenizer="wordpiece"))
        assert isinstance(scorer.tokenizer, WordPieceTokenizer)
        results = scorer.score_batch(gen.generate_batch(4))
        assert len(results) == 4
