"""The family of programs a row bucket can launch is closed, and compiled
before it is needed (scoring/text_split.py, ISSUE 27)."""

import jax
import numpy as np
import pytest

from realtime_fraud_detection_tpu.core.batching import BATCH_BUCKETS, bucket_for
from realtime_fraud_detection_tpu.scoring import text_split

# (narrow, full): the deployed pair, the tests' pair, and the pairs with
# nothing to split (no narrower width; text_len at or under it)
WIDTHS = [(128, 512), (128, 256), (None, 512), (128, 128), (128, 64)]


@pytest.mark.parametrize("multiple", [1, 8], ids=["one-device", "data-axis-8"])
@pytest.mark.parametrize("bucket", BATCH_BUCKETS)
def test_every_launch_lies_in_the_family_of_its_bucket(bucket, multiple):
    def bucket_of(n):
        return bucket_for(n, BATCH_BUCKETS, multiple_of=multiple)

    size = bucket_of(bucket)
    sizes = [n for n in range(1, size + 1) if bucket_of(n) == size]
    for narrow, full in WIDTHS:
        fam = text_split.family(size, narrow, full, bucket_of)
        assert fam[0] == (size, full)
        unsplit_slots = size * full
        for n in sizes:
            for n_long in range(n + 1):
                launches = text_split.plan(n - n_long, n_long, size, narrow,
                                           full, bucket_of)
                assert {(r, w) for _, r, w in launches} <= set(fam)
                slots = sum(r * w for _, r, w in launches)
                if launches != ((text_split.ALL, size, full),):
                    # a batch leaves the one launch only for fewer slots
                    assert slots < unsplit_slots
                    assert narrow is not None and narrow < full
                held = {which: r for which, r, _ in launches}
                if text_split.LONG in held:
                    assert held[text_split.LONG] >= n_long
                    assert held[text_split.SHORT] == size
                # a short row never runs wider than it must, a long row
                # never narrower than its text
                if n_long and launches[0][0] == text_split.ALL:
                    assert launches[0][2] == full


def test_the_deployed_family_is_the_one_the_issue_names():
    fam = text_split.family(256, 128, 512, bucket_for)
    assert set(fam) == {(256, 512), (256, 128), (8, 512), (32, 512)}
    # at bucket 8 one long row already costs more in two launches
    assert set(text_split.family(8, 128, 512, bucket_for)) == {
        (8, 512), (8, 128)}
    assert set(text_split.family(1, 128, 512, bucket_for)) == {
        (1, 512), (1, 128)}
    # OLMoE's 128 tokens, the parked 64-token configurations: the parent's
    for full in (128, 64):
        assert text_split.family(256, 128, full, bucket_for) == ((256, full),)
    assert text_split.plan(248, 8, 256, 128, 512, bucket_for) == (
        (text_split.SHORT, 256, 128), (text_split.LONG, 8, 512))
    assert text_split.plan(247, 9, 256, 128, 512, bucket_for) == (
        (text_split.SHORT, 256, 128), (text_split.LONG, 32, 512))
    assert text_split.plan(223, 33, 256, 128, 512, bucket_for) == (
        (text_split.ALL, 256, 512),)
    assert text_split.plan(256, 0, 256, 128, 512, bucket_for) == (
        (text_split.ALL, 256, 128),)


class _Compiles:
    """XLA compilations, cache loads included: what
    ``benchmarks/harness/correct.CompileCounter`` counts."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


def test_one_warming_batch_compiles_everything_longtail_traffic_launches():
    """One batch that leaves the unsplit launch compiles its bucket's whole
    family; 200 seeded batches of long-tailed text (median 20 tokens, 3%
    over the narrow width, as ``s512-longtail-saturated``) then compile
    nothing, whichever member each one needs."""
    from realtime_fraud_detection_tpu.core.mesh import local_mesh_size
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    rows, text_len, batches = 32, 256, 200
    gen = TransactionGenerator(num_users=200, num_merchants=40, seed=27)
    s = FraudScorer(scorer_config=ScorerConfig(text_len=text_len))
    s.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    rng = np.random.default_rng(27)
    want = np.clip(rng.lognormal(np.log(20.0), 1.0, (batches + 1, rows)),
                   4, text_len).astype(int)
    want[0, 5] = 200          # the warming batch splits
    want[50, :6] = 200        # too many long rows: the unsplit member
    want[100] = 20            # all short: one launch at the narrow width

    def batch(i):
        recs = gen.generate_batch(rows)
        for r, t in zip(recs, want[i]):
            r["description"] = " ".join(["x"] * int(t))
        return recs

    multiple = local_mesh_size(s.mesh)
    size = bucket_for(rows, multiple_of=multiple)
    # another test file on this worker may have launched the same programs:
    # the count below is of what THIS scorer's first split makes the
    # process compile or load
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )

    score_fused_packed.clear_cache()
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    launched = set()
    try:
        s.finalize(s.dispatch(batch(0), now=1000.0), now=1000.0)
        fam = s.host_stats()["text_split"]["families"]
        assert fam == {size: list(text_split.family(
            size, 128, text_len,
            lambda n: bucket_for(n, multiple_of=multiple)))}
        # the listener hears this process's programs, loaded or compiled
        assert compiles.count >= len(fam[size])
        compiles.count = 0
        for i in range(1, batches + 1):
            p = s.dispatch(batch(i), now=1000.0 + i)
            launched.add((p.token_slots, p.split_batches))
            s.finalize(p, now=1000.0 + i)
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert compiles.count == 0
    # all three answers of the rule were launched
    assert (size * text_len, 0) in launched
    assert (size * 128, 0) in launched
    assert any(split for _, split in launched)
    counts = s.host_stats()["text_split"]
    assert counts["short_text_rows"] + counts["long_text_rows"] == \
        (batches + 1) * rows == s.stats["scored"]
    assert 0.9 < counts["short_text_rows"] / s.stats["scored"] < 1.0
    assert s.host_stats()["text_split"]["families"] == fam
