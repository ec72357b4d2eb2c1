"""Native (C++) microbatcher tests: correctness + concurrency."""

import json
import shutil
import threading

import pytest

from realtime_fraud_detection_tpu.native import (
    NativeMicrobatchQueue,
    native_available,
    native_build_error,
)

pytestmark = pytest.mark.skipif(
    shutil.which("g++") is None, reason="no C++ toolchain"
)


@pytest.fixture(scope="module")
def _native():
    if not native_available():
        pytest.fail(f"native build failed: {native_build_error()}")


def test_push_pop_roundtrip(_native):
    q = NativeMicrobatchQueue(capacity=64, max_batch=8, max_delay_ms=1e9)
    payloads = [json.dumps({"n": i}).encode() for i in range(8)]
    for p in payloads:
        assert q.push(p)
    batch = q.next_batch()
    assert batch == payloads
    assert q.pending() == 0
    q.close()


def test_size_trigger_before_deadline(_native):
    q = NativeMicrobatchQueue(capacity=256, max_batch=4, max_delay_ms=1e9)
    for i in range(10):
        q.push(f"r{i}".encode())
    assert len(q.next_batch()) == 4
    assert len(q.next_batch()) == 4
    assert q.next_batch() == []      # 2 pending, no deadline, not full
    assert q.pending() == 2
    q.close()


def test_deadline_trigger(_native):
    q = NativeMicrobatchQueue(capacity=64, max_batch=256, max_delay_ms=5.0)
    q.push(b"only-one")
    # blocking poll longer than the deadline must flush the partial batch
    batch = q.next_batch(block_ms=100)
    assert batch == [b"only-one"]
    q.close()


def test_backpressure_when_full(_native):
    q = NativeMicrobatchQueue(capacity=4, max_batch=4, max_delay_ms=1e9)
    assert all(q.push(b"x") for _ in range(4))
    assert not q.push(b"overflow")
    assert q.stats()["dropped"] == 1
    q.close()


def test_oversized_payload_raises(_native):
    q = NativeMicrobatchQueue(capacity=4, slot_bytes=16)
    with pytest.raises(ValueError):
        q.push(b"y" * 17)
    q.close()


def test_concurrent_producers_no_loss(_native):
    """8 producer threads, one consumer; every record arrives exactly once."""
    q = NativeMicrobatchQueue(capacity=8192, max_batch=128, max_delay_ms=1.0)
    n_threads, per_thread = 8, 500
    errors = []

    def produce(tid):
        for i in range(per_thread):
            payload = f"{tid}:{i}".encode()
            while not q.push(payload):
                pass  # spin on backpressure

    threads = [threading.Thread(target=produce, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()

    seen = set()
    expected = n_threads * per_thread
    import time
    t_end = time.monotonic() + 30.0
    while len(seen) < expected and time.monotonic() < t_end:
        for p in q.next_batch(block_ms=10):
            key = p.decode()
            if key in seen:
                errors.append(f"duplicate {key}")
            seen.add(key)
    for t in threads:
        t.join()
    assert not errors
    assert len(seen) == expected
    q.close()


def test_tsan_stress(tmp_path):
    """Race-freedom under ThreadSanitizer (SURVEY.md §5.2 requirement)."""
    import subprocess
    from pathlib import Path

    src_dir = Path(__file__).resolve().parent.parent / (
        "realtime_fraud_detection_tpu/native"
    )
    binary = tmp_path / "stress_tsan"
    build = subprocess.run(
        ["g++", "-O1", "-g", "-std=c++17", "-fsanitize=thread", "-pthread",
         str(src_dir / "stress_main.cpp"), "-o", str(binary)],
        capture_output=True, text=True, timeout=120,
    )
    if build.returncode != 0:
        pytest.skip(f"TSAN unavailable: {build.stderr[:200]}")
    run = subprocess.run([str(binary)], capture_output=True, text=True,
                         timeout=120)
    assert run.returncode == 0, run.stdout + run.stderr
    assert run.stdout.startswith("OK")


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
class TestNativeTreeScorer:
    """C++ tree kernel vs the JAX tensorized traversal (same layout)."""

    @pytest.fixture(scope="class")
    def trained(self):
        import numpy as np

        from realtime_fraud_detection_tpu.training import GBDTTrainer

        rng = np.random.default_rng(0)
        x = rng.standard_normal((2000, 64)).astype(np.float32)
        y = (x[:, 3] + 0.5 * x[:, 17] > 0.7).astype(np.float32)
        ens = GBDTTrainer(n_estimators=20, max_depth=4, seed=1).fit(x, y)
        return ens, x

    def test_matches_jax_kernel(self, trained):
        import numpy as np

        from realtime_fraud_detection_tpu.models.trees import (
            tree_ensemble_logits,
        )
        from realtime_fraud_detection_tpu.native import (
            NativeTreeScorer,
            native_trees_available,
        )

        if not native_trees_available():
            pytest.skip("native build failed")
        ens, x = trained
        scorer = NativeTreeScorer(ens)
        got = scorer.logits(x)
        expect = np.asarray(tree_ensemble_logits(ens, x))
        np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-5)

    def test_predict_is_sigmoid_and_threaded_matches(self, trained):
        import numpy as np

        from realtime_fraud_detection_tpu.native import (
            NativeTreeScorer,
            native_trees_available,
        )

        if not native_trees_available():
            pytest.skip("native build failed")
        ens, x = trained
        st = NativeTreeScorer(ens, n_threads=1)
        mt = NativeTreeScorer(ens, n_threads=4)
        np.testing.assert_allclose(st.logits(x), mt.logits(x))
        p = st.predict(x[:8])
        np.testing.assert_allclose(
            p, 1.0 / (1.0 + np.exp(-st.logits(x[:8]))), rtol=1e-6)
        assert ((p >= 0) & (p <= 1)).all()

    def test_rejects_too_narrow_input(self, trained):
        import numpy as np

        from realtime_fraud_detection_tpu.native import (
            NativeTreeScorer,
            native_trees_available,
        )

        if not native_trees_available():
            pytest.skip("native build failed")
        ens, _ = trained
        scorer = NativeTreeScorer(ens)
        narrow = np.zeros((4, scorer.min_features - 1), np.float32)
        with pytest.raises(ValueError, match="features"):
            scorer.logits(narrow)


class TestIngressGateway:
    """The native queue's production call site: threaded ingress gateway."""

    def test_concurrent_submitters_exact_delivery(self):
        import threading

        from realtime_fraud_detection_tpu.stream import (
            IngressGateway,
            InMemoryBroker,
        )
        from realtime_fraud_detection_tpu.stream import topics as T

        broker = InMemoryBroker()
        gw = IngressGateway(broker, T.TRANSACTIONS)
        n_threads, per = 6, 300

        def producer(tid):
            for i in range(per):
                txn = {"transaction_id": f"{tid}:{i}", "user_id": f"u{tid}",
                       "merchant_id": "m", "amount": 1.0}
                while not gw.submit(txn):
                    pass

        threads = [threading.Thread(target=producer, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert gw.flush(timeout_s=30)
        gw.close()
        recs = broker.consumer([T.TRANSACTIONS], "check").poll(10_000)
        ids = [r.value["transaction_id"] for r in recs]
        assert len(ids) == n_threads * per
        assert len(set(ids)) == n_threads * per      # exactly once, no dup
        assert gw.dropped == 0
        # per-key (per-submitter-user) FIFO survives the lock-free handoff
        per_user = {}
        for r in recs:
            per_user.setdefault(r.value["user_id"], []).append(
                int(r.value["transaction_id"].split(":")[1]))
        for uid, seq in per_user.items():
            assert seq == sorted(seq), f"{uid} reordered"

    def test_oversized_payload_bypasses_ring(self):
        from realtime_fraud_detection_tpu.stream import (
            IngressGateway,
            InMemoryBroker,
        )
        from realtime_fraud_detection_tpu.stream import topics as T

        broker = InMemoryBroker()
        gw = IngressGateway(broker, T.TRANSACTIONS)
        txn = {"transaction_id": "big", "user_id": "u", "merchant_id": "m",
               "amount": 1.0, "description": "x" * 20_000}
        assert gw.submit(txn)
        assert gw.flush(timeout_s=10)
        gw.close()
        recs = broker.consumer([T.TRANSACTIONS], "check").poll(10)
        assert recs and recs[0].value["transaction_id"] == "big"

    def test_native_backend_engaged_when_available(self):
        from realtime_fraud_detection_tpu.native import native_available
        from realtime_fraud_detection_tpu.stream import (
            IngressGateway,
            InMemoryBroker,
        )
        from realtime_fraud_detection_tpu.stream import topics as T

        gw = IngressGateway(InMemoryBroker(), T.TRANSACTIONS)
        assert gw.native == native_available()
        gw.close()


def test_build_is_keyed_on_source_content(_native, tmp_path):
    """A binary can only serve the source it was built from: the library
    name carries the source's digest, an edit builds a new one and drops
    the stale one — no mtime involved (a copied tree keeps binaries but
    not necessarily their timestamps)."""
    import hashlib

    from realtime_fraud_detection_tpu import native

    src = tmp_path / "trees.cpp"
    shutil.copy(native._TREES_SRC, src)

    def built():
        return sorted(p.name for p in tmp_path.glob("_trees.*.so"))

    lib, err = native._compile_native(src)
    assert lib is not None and err is None
    first = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert built() == [f"_trees.{first}.so"]
    src.write_text(src.read_text() + "\n// edited\n")
    lib, err = native._compile_native(src)
    assert lib is not None and err is None
    second = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert second != first and built() == [f"_trees.{second}.so"]
