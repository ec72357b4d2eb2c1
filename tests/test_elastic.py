"""Elastic process-cluster plane (ISSUE 12): the network handoff store's
failure modes (torn blob -> previous checkpoint, zombie fencing, server
restart retried), the autoscale controller's deterministic ledger +
ahead-of-ramp property, the sync_autoscale Prometheus mirror pins, the
421-following ingress client over live HTTP, the SIGTERM-vs-SIGKILL
replay-depth regression on a REAL worker subprocess, the tuner's
in-flight-depth freeze under cluster feedback, and the `rtfd
elastic-drill --fast` tier-1 smoke."""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from realtime_fraud_detection_tpu.cluster.autoscale import (
    AutoscaleController,
)
from realtime_fraud_detection_tpu.cluster.handoff import (
    HandoffClient,
    HandoffServer,
)
from realtime_fraud_detection_tpu.stream import topics as T
from realtime_fraud_detection_tpu.tuning.forecast import ArrivalForecaster


# ---------------------------------------------------------------------------
# handoff server: durability + failure modes
# ---------------------------------------------------------------------------


class TestHandoffStore:
    def test_roundtrip_and_server_restart_scan(self, tmp_path):
        """Blobs survive a handoff-server restart: the committed files
        are rescanned and served, sha-verified."""
        blob_dir = str(tmp_path / "blobs")
        srv = HandoffServer(blob_dir=blob_dir).start()
        port = srv.port
        cli = HandoffClient(port=port)
        cli.epoch = 1
        cli.put(3, 120, b"state-blob-a")
        cli.put(3, 150, b"state-blob-b")
        assert cli.get(3) == (150, b"state-blob-b")
        assert cli.offsets() == {3: 150}
        cli.close()
        srv.stop()

        srv2 = HandoffServer(port=port, blob_dir=blob_dir).start()
        try:
            cli2 = HandoffClient(port=port)
            assert cli2.get(3) == (150, b"state-blob-b")
            assert cli2.stats()["restores_total"] == 1
            cli2.close()
        finally:
            srv2.stop()

    def test_torn_blob_detected_and_previous_served(self, tmp_path):
        """A torn/truncated newest checkpoint fails its sha256 and the
        PREVIOUS checkpoint is served instead — counted, never silently
        used."""
        blob_dir = str(tmp_path / "blobs")
        srv = HandoffServer(blob_dir=blob_dir).start()
        try:
            cli = HandoffClient(port=srv.port)
            cli.put(0, 100, b"good-old-checkpoint")
            cli.put(0, 200, b"torn-new-checkpoint")
            newest = sorted(
                glob.glob(os.path.join(blob_dir, "p0-*.blob")),
                key=lambda p: int(os.path.basename(p).split("-")[1]))[-1]
            assert "200" in os.path.basename(newest)
            with open(newest, "r+b") as f:
                f.truncate(70)            # sha header + a few bytes
            # force the disk path (drop the in-memory copy, like a
            # restarted server would)
            with srv._lock:
                srv._ledger[0] = [(off, ep, sha, None, path)
                                  for off, ep, sha, _, path
                                  in srv._ledger[0]]
            assert cli.get(0) == (100, b"good-old-checkpoint")
            stats = cli.stats()
            assert stats["torn_blobs_total"] == 1
            assert stats["restores_total"] == 1
            cli.close()
        finally:
            srv.stop()

    def test_zombie_writer_fenced_by_epoch(self, tmp_path):
        """A checkpoint put carrying a stale offset-epoch — a zombie
        worker that lost the partition in a rebalance — is refused
        loudly; the current-epoch owner still writes."""
        srv = HandoffServer(blob_dir=str(tmp_path / "b")).start()
        try:
            cli = HandoffClient(port=srv.port)
            cli.epoch = 3
            cli.put(5, 10, b"gen3")
            cli.fence(5, 4)
            with pytest.raises(RuntimeError, match="FencedEpochError"):
                cli.put(5, 12, b"zombie-gen3")
            assert cli.stats()["fenced_rejects_total"] == 1
            cli.epoch = 4
            cli.put(5, 15, b"gen4")
            assert cli.get(5) == (15, b"gen4")
            cli.close()
        finally:
            srv.stop()

    def test_server_restart_mid_restore_retried_with_backoff(self,
                                                             tmp_path):
        """A restore against a restarting handoff server retries the
        SAME address with DeterministicBackoff instead of surfacing a
        worker crash."""
        blob_dir = str(tmp_path / "blobs")
        srv = HandoffServer(blob_dir=blob_dir).start()
        port = srv.port
        slept = []

        def _sleep(d):
            slept.append(d)
            time.sleep(min(d, 0.05))

        cli = HandoffClient(port=port, retry_sleep=_sleep)
        cli.put(7, 42, b"before-restart")
        srv.stop()

        def _restart():
            time.sleep(0.15)
            HandoffServer(port=port, blob_dir=blob_dir).start()

        t = threading.Thread(target=_restart, daemon=True)
        t.start()
        assert cli.get(7) == (42, b"before-restart")
        assert slept, "reconnect must go through the backoff seam"
        t.join()
        cli.close()


# ---------------------------------------------------------------------------
# autoscale controller
# ---------------------------------------------------------------------------


def _ramp_arrivals(seed: int = 7):
    from realtime_fraud_detection_tpu.sim.arrivals import (
        DiurnalBurstConfig,
        DiurnalBurstProcess,
    )

    proc = DiurnalBurstProcess(DiurnalBurstConfig(
        trough_tps=100.0, peak_tps=700.0, period_s=12.0,
        burst_duration_s=0.0), seed=seed)
    return proc, proc.generate(12.0)


class TestAutoscaleController:
    def _controller(self):
        return AutoscaleController(
            per_worker_tps=110.0, min_workers=4, max_workers=8,
            headroom=1.25, lead_s=1.5, decide_interval_s=0.5,
            down_patience=3,
            forecaster=ArrivalForecaster(bucket_s=0.25))

    def test_validation(self):
        with pytest.raises(ValueError):
            AutoscaleController(per_worker_tps=0.0)
        with pytest.raises(ValueError):
            AutoscaleController(per_worker_tps=10, min_workers=5,
                                max_workers=4)
        with pytest.raises(ValueError):
            AutoscaleController(per_worker_tps=10, headroom=0.9)

    def test_ledger_deterministic_and_chunking_independent(self):
        """The decision ledger is a pure function of the arrival
        schedule: idle polls at arbitrary instants between arrivals must
        not change it (boundaries are decided before arrivals beyond
        them are fed)."""
        _, times = _ramp_arrivals()
        a, b = self._controller(), self._controller()
        for t in times:
            a.observe(float(t), 1)
        a.observe(14.0, 0)
        poll = 0.137
        nxt = poll
        for t in times:
            while nxt < t:            # irregular idle polls interleaved
                b.observe(nxt, 0)
                nxt += poll
            b.observe(float(t), 1)
        while nxt < 14.0:
            b.observe(nxt, 0)
            nxt += poll
        b.observe(14.0, 0)
        assert a.snapshot()["decisions"] == b.snapshot()["decisions"]
        assert a.events == b.events and a.events["up"] >= 1

    def test_ahead_of_ramp_and_drain(self):
        """Provisioned capacity (ledger target x per-worker tps) covers
        the true diurnal envelope at every decision boundary — the
        forecast lead + headroom keep the controller ahead of a steep
        ramp — and after the ramp the target drains back to the floor."""
        proc, times = _ramp_arrivals()
        c = self._controller()
        for t in times:
            c.observe(float(t), 1)
        decisions = list(c.decisions)
        target_at = [(0.0, 4)] + [(d["t"], d["target"]) for d in decisions]

        def target(t):
            cur = 4
            for td, tg in target_at:
                if td <= t:
                    cur = tg
            return cur

        for i in range(25):
            t = i * 0.5
            assert target(t) * 110.0 >= proc.rate_at(t) - 1e-6, \
                f"under-provisioned at t={t}"
        ups = [d for d in decisions if d["direction"] == "up"]
        assert ups and ups[-1]["t"] < 6.0        # peak is at period/2
        assert max(d["target"] for d in ups) == 8
        # trailing silence: the rate forecast decays, the fleet drains
        for i in range(1, 30):
            c.observe(12.0 + i * 0.25, 0)
        assert c.target == 4
        assert c.events["down"] >= 1

    def test_down_patience_hysteresis(self):
        c = AutoscaleController(
            per_worker_tps=100.0, min_workers=1, max_workers=8,
            headroom=1.0, lead_s=0.0, decide_interval_s=1.0,
            down_patience=3,
            forecaster=ArrivalForecaster(bucket_s=0.5))
        t = 0.0
        for _ in range(4000):             # ~400 tps for 10s
            c.observe(t, 1)
            t += 0.0025
        assert c.target >= 4
        high = c.target
        # one quiet decision must NOT drain (patience 3)
        c.observe(t + 1.0, 0)
        assert c.target == high
        for i in range(2, 6):
            c.observe(t + i * 1.0, 0)
        assert c.target == 1


# ---------------------------------------------------------------------------
# sync_autoscale Prometheus mirror
# ---------------------------------------------------------------------------


def _autoscale_snapshot(up=2, down=1, ckpts=10, restores=3, torn=1):
    return {
        "target_workers": 6, "forecast_rate": 512.3,
        "events": {"up": up, "down": down},
        "handoff_server": {"checkpoints_total": ckpts,
                           "restores_total": restores,
                           "torn_blobs_total": torn},
    }


class TestSyncAutoscale:
    def _lines(self, m):
        return "\n".join(
            ln for ln in m.render_prometheus().splitlines()
            if ln.startswith(("autoscale_", "handoff_server_")))

    def test_stream_vs_serving_render_identical(self):
        from realtime_fraud_detection_tpu.obs.metrics import (
            MetricsCollector,
        )

        a, b = MetricsCollector(), MetricsCollector()
        snap = _autoscale_snapshot()
        a.sync_autoscale(snap)
        b.sync_autoscale(snap)
        assert self._lines(a) == self._lines(b)
        assert "autoscale_target_workers 6" in self._lines(a)
        assert 'autoscale_events_total{direction="up"} 2' in self._lines(a)
        assert "handoff_server_torn_blobs_total 1" in self._lines(a)

    def test_honest_counter_deltas(self):
        from realtime_fraud_detection_tpu.obs.metrics import (
            MetricsCollector,
        )

        m = MetricsCollector()
        m.sync_autoscale(_autoscale_snapshot())
        m.sync_autoscale(_autoscale_snapshot())       # re-sync: no growth
        assert m.autoscale_events.total() == 3
        assert m.handoff_server_checkpoints.total() == 10
        m.sync_autoscale(_autoscale_snapshot(up=4, ckpts=15))
        assert m.autoscale_events.total() == 5
        assert m.handoff_server_checkpoints.total() == 15

    def test_snapshot_without_handoff_block(self):
        from realtime_fraud_detection_tpu.obs.metrics import (
            MetricsCollector,
        )

        m = MetricsCollector()
        m.sync_autoscale({"target_workers": 3, "forecast_rate": 9.0,
                          "events": {"up": 0, "down": 0}})
        assert m.autoscale_target_workers.value() == 3
        assert m.handoff_server_checkpoints.total() == 0


# ---------------------------------------------------------------------------
# partition-scoped consumers over the TCP netbroker
# ---------------------------------------------------------------------------


class TestNetbrokerScopedConsumer:
    def test_partition_scoped_consumption_over_tcp(self):
        from realtime_fraud_detection_tpu.stream.netbroker import (
            BrokerServer,
            NetBrokerClient,
        )

        srv = BrokerServer(port=0).start()
        try:
            cli = NetBrokerClient(port=srv.port)
            n_parts = cli.partitions(T.TRANSACTIONS)
            for i in range(200):
                cli.produce(T.TRANSACTIONS, {"i": i}, key=f"user_{i}")
            scoped = cli.consumer([T.TRANSACTIONS], "g-scoped",
                                  partitions={T.TRANSACTIONS: [0, 1]})
            got = []
            while True:
                recs = scoped.poll(64)
                if not recs:
                    break
                got.extend(recs)
            assert got and all(r.partition in (0, 1) for r in got)
            ends = cli.end_offsets(T.TRANSACTIONS)
            assert len(got) == ends[0] + ends[1] < 200
            assert n_parts == len(ends)
            cli.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# ingress client: follows 421s over live HTTP
# ---------------------------------------------------------------------------


class TestShardIngressClient:
    def test_unreachable_fleet_retries_then_raises(self):
        from realtime_fraud_detection_tpu.serving.ingress_client import (
            NoShardAvailableError,
            ShardIngressClient,
        )

        slept = []
        cli = ShardIngressClient(["http://127.0.0.1:1"], retries=3,
                                 timeout_s=0.5,
                                 retry_sleep=slept.append)
        with pytest.raises(NoShardAvailableError):
            cli.predict({"transaction_id": "t1", "user_id": "u1",
                         "merchant_id": "m1", "amount": 1.0})
        assert len(slept) == 3          # the deterministic backoff seam
        assert cli.snapshot()["retried"] == 3

    def test_stale_ring_pingpong_terminates_explicitly(self):
        """ISSUE 13 satellite: when BOTH the learned affinity and the
        serving ring are stale mid-rebalance, two workers can bounce a
        key back and forth forever — the bounded-redirect guard must
        terminate with an explicit error, never loop."""
        from realtime_fraud_detection_tpu.serving.ingress_client import (
            NoShardAvailableError,
            ShardIngressClient,
        )

        urls = ["http://a", "http://b"]
        cli = ShardIngressClient(urls, max_redirects=3,
                                 retry_sleep=lambda s: None)
        posts = []

        def _pingpong(url, payload):
            posts.append(url)
            other = urls[1] if url == urls[0] else urls[0]
            return 421, {"owner": "elsewhere", "location": other}

        cli._post = _pingpong
        with pytest.raises(NoShardAvailableError):
            cli.predict({"transaction_id": "t1", "user_id": "u9",
                         "merchant_id": "m1", "amount": 1.0})
        # initial attempt + exactly max_redirects follows — bounded
        assert len(posts) == 1 + 3
        assert cli.snapshot()["redirects_followed"] == 3
        # the ping-pong left NO poisoned affinity behind: the last 421
        # invalidated the entry the previous redirect had learned
        assert cli.snapshot()["affinity_size"] == 0

    def test_affinity_invalidated_on_421_for_confirmed_user(self):
        """A previously-CONFIRMED user→worker mapping that starts
        answering 421 (its partition moved) is dropped from the learned
        affinity even when the redirect cannot be followed — the next
        request must not re-route into the same refusal."""
        from realtime_fraud_detection_tpu.serving.ingress_client import (
            NoShardAvailableError,
            ShardIngressClient,
        )

        cli = ShardIngressClient(["http://a", "http://b"],
                                 retry_sleep=lambda s: None)
        script = {"phase": "confirm"}

        def _post(url, payload):
            if script["phase"] == "confirm":
                return 200, {"transaction_id": "t", "fraud_score": 0.1}
            # moved: the old owner refuses and (mid-rebalance) cannot
            # even name a successor yet
            if url == script["stale_url"]:
                return 421, {"owner": None, "location": ""}
            return 200, {"transaction_id": "t", "fraud_score": 0.2}

        cli._post = _post
        txn = {"transaction_id": "t", "user_id": "u1",
               "merchant_id": "m", "amount": 1.0}
        cli.predict(txn)                        # learns the affinity
        stale_url = cli._affinity["u1"]
        script.update(phase="moved", stale_url=stale_url)
        with pytest.raises(NoShardAvailableError):
            cli.predict(txn)                    # 421, no location
        assert "u1" not in cli._affinity        # poisoned entry dropped
        body = cli.predict(txn)                 # rotates to a live worker
        assert body["fraud_score"] == 0.2
        assert cli._affinity["u1"] != stale_url

    def test_follows_421_to_owner_and_learns_affinity(self):
        """Two live cluster-mode serving apps: a request for a user the
        second worker owns, sent to the first, follows the 421 to the
        owner and succeeds; the learned affinity sends the next request
        for that user direct (no second redirect)."""
        import asyncio

        from realtime_fraud_detection_tpu.cluster.hashring import (
            ShardRouter,
        )
        from realtime_fraud_detection_tpu.serving import ServingApp
        from realtime_fraud_detection_tpu.serving.ingress_client import (
            ShardIngressClient,
        )
        from realtime_fraud_detection_tpu.utils.config import Config

        def make_app(wid):
            config = Config()
            config.monitoring.prometheus_port = 0
            config.cluster.enabled = True
            config.cluster.worker_id = wid
            config.cluster.workers = {"w0": "", "w1": ""}
            return ServingApp(config, host="127.0.0.1", port=0)

        apps = {wid: make_app(wid) for wid in ("w0", "w1")}
        loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(loop)

            async def _start():
                for app in apps.values():
                    await app.start()
                started.set()

            loop.run_until_complete(_start())
            loop.run_forever()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        assert started.wait(timeout=60)
        try:
            urls = {wid: f"http://127.0.0.1:{app.port}"
                    for wid, app in apps.items()}
            for app in apps.values():
                app.cluster_router.addresses.update(urls)
            ref = ShardRouter(apps["w0"].config.cluster.n_partitions,
                              ["w0", "w1"])
            uid = next(f"user_{i:06d}" for i in range(10_000)
                       if ref.route(f"user_{i:06d}") == "w1")
            txn = {"transaction_id": "t_ingress_1", "user_id": uid,
                   "merchant_id": "m1", "amount": 12.5,
                   "timestamp": 1.0}
            # urls in w0-first order: the round-robin client hits the
            # WRONG shard first, by construction
            cli = ShardIngressClient([urls["w0"], urls["w1"]])
            res = cli.predict(txn)
            assert res.get("fraud_probability") is not None
            assert res["_ingress"]["redirects"] == 1
            assert res["_ingress"]["worker_url"] == urls["w1"]
            res2 = cli.predict({**txn, "transaction_id": "t_ingress_2"})
            assert res2["_ingress"]["redirects"] == 0      # affinity hit
            snap = cli.snapshot()
            assert snap["redirects_followed"] == 1
            assert snap["affinity_hits"] == 1
        finally:
            async def _stop():
                for app in apps.values():
                    await app.stop()

            asyncio.run_coroutine_threadsafe(_stop(),
                                             loop).result(timeout=10)
            loop.call_soon_threadsafe(loop.stop)
            t.join(timeout=5)


# ---------------------------------------------------------------------------
# SIGTERM vs SIGKILL on a REAL worker subprocess (graceful-drain satellite)
# ---------------------------------------------------------------------------


def _one_worker_fleet(tmp_path, tag):
    from realtime_fraud_detection_tpu.cluster.handoff import HandoffServer
    from realtime_fraud_detection_tpu.cluster.procfleet import ProcessFleet
    from realtime_fraud_detection_tpu.stream.netbroker import BrokerServer

    broker = BrokerServer(port=0).start()
    handoff = HandoffServer(blob_dir=str(tmp_path / f"b-{tag}")).start()
    fleet = ProcessFleet(
        f"127.0.0.1:{broker.port}", f"127.0.0.1:{handoff.port}",
        n_partitions=12,
        worker_spec={"batch": 32, "max_delay_ms": 5.0,
                     "checkpoint_every": 6, "base_ms": 5.0,
                     "per_txn_ms": 1.5})
    fleet.start(1)
    items = []
    for i in range(1800):
        uid = f"user_{i % 300}"
        items.append((uid, {"transaction_id": f"stx_{i}", "user_id": uid,
                            "merchant_id": f"m_{i % 40}",
                            "amount": 5.0 + i % 23,
                            "event_ts": i * 0.001}, time.time()))
    fleet.client.produce_batch_stamped(T.TRANSACTIONS, items)
    deadline = time.time() + 60
    while time.time() < deadline:
        committed = sum(
            fleet.client.committed(fleet.group_id, T.TRANSACTIONS, p)
            for p in range(12))
        if committed > 400 \
                and fleet.handoff.stats()["checkpoints_total"] >= 2:
            break
        time.sleep(0.02)
    else:
        raise AssertionError("worker made no progress")
    return broker, handoff, fleet


def _replay_depth(fleet):
    """Records a resuming inheritor would state-replay: committed offset
    minus last checkpointed offset, summed over partitions."""
    offsets = fleet.handoff.offsets()
    return sum(
        max(0, fleet.client.committed(fleet.group_id, T.TRANSACTIONS, p)
            - offsets.get(p, 0))
        for p in range(12))


class TestWorkerSignals:
    def test_sigterm_drains_to_zero_replay_sigkill_does_not(self,
                                                            tmp_path):
        """THE graceful-shutdown regression: SIGTERM mid-stream drains
        in-flight batches, commits, and writes a final handoff
        checkpoint — a resumer replays NOTHING. SIGKILL (by definition
        unhandled) leaves the committed-vs-checkpoint gap the handoff
        plane exists to replay."""
        broker, handoff, fleet = _one_worker_fleet(tmp_path, "term")
        try:
            st = fleet.workers["w0"]
            os.kill(st["pid"], signal.SIGTERM)
            assert st["proc"].wait(timeout=60) == 0
            deadline = time.time() + 10
            while "w0" not in fleet.all_byes() and time.time() < deadline:
                fleet.poll_events()
                time.sleep(0.02)
            bye = fleet.all_byes()["w0"]
            assert bye["graceful"] and bye["reason"] == "SIGTERM"
            assert bye["final_checkpoints"] == 12
            assert _replay_depth(fleet) == 0
        finally:
            fleet.terminate()
            handoff.stop()
            broker.stop()

        broker, handoff, fleet = _one_worker_fleet(tmp_path, "kill")
        try:
            st = fleet.workers["w0"]
            os.kill(st["pid"], signal.SIGKILL)
            assert st["proc"].wait(timeout=60) == -signal.SIGKILL
            fleet.poll_events()
            assert "w0" not in fleet.all_byes()
            assert _replay_depth(fleet) > 0
        finally:
            fleet.terminate()
            handoff.stop()
            broker.stop()


# ---------------------------------------------------------------------------
# tuner in-flight-depth dimension under cluster feedback (PR 6 follow-on)
# ---------------------------------------------------------------------------


class TestTunerDepthClusterFeedback:
    def test_depth_trial_reverts_and_freezes_on_ladder(self):
        """The tuner may trial the in-flight depth against live cluster
        latencies, but the moment the (cross-process) QoS ladder signal
        reports degradation the trial reverts and the tuner freezes —
        the freeze interaction holds when the feedback comes from a
        worker process, not just in-process."""
        from realtime_fraud_detection_tpu.tuning import TuningPlane
        from realtime_fraud_detection_tpu.utils.config import (
            TuningSettings,
        )

        plane = TuningPlane(TuningSettings(
            enabled=True, tune_interval_batches=4,
            tuner_cooldown_epochs=0))
        tuner = plane.tuner
        tuner._dim_i = 2                     # next proposal: "inflight"
        saved = tuner.inflight_depth

        def epoch(now0, p99_ms):
            for b in range(4):
                plane.on_batch_complete(
                    32, 0.05, now0 + b * 0.1,
                    latencies_ms=[p99_ms] * 8,
                    burn_rate=0.0, ladder_level=0)

        epoch(0.0, 40.0)                     # baseline epoch
        epoch(1.0, 40.0)                     # rolling baseline -> trial
        assert tuner.snapshot()["in_trial"]
        assert tuner.snapshot()["trial_dim"] == "inflight"
        assert tuner.inflight_depth != saved
        # cluster feedback: a worker's ladder went degraded mid-trial
        plane.on_batch_complete(32, 0.05, 2.0, latencies_ms=[500.0],
                                burn_rate=0.0, ladder_level=2)
        snap = tuner.snapshot()
        assert snap["frozen"] and not snap["in_trial"]
        assert tuner.inflight_depth == saved   # reverted, not kept
        assert plane.recommended_inflight_depth() == saved


# ---------------------------------------------------------------------------
# settings + lint scope + compact summary
# ---------------------------------------------------------------------------


class TestElasticSettingsAndScopes:
    def test_cluster_autoscale_validation(self):
        from realtime_fraud_detection_tpu.utils.config import (
            ClusterSettings,
        )

        ClusterSettings().validate()
        with pytest.raises(ValueError):
            ClusterSettings(min_workers=4, max_workers=2).validate()
        with pytest.raises(ValueError):
            ClusterSettings(per_worker_tps=0).validate()
        with pytest.raises(ValueError):
            ClusterSettings(autoscale_headroom=0.5).validate()
        with pytest.raises(ValueError):
            ClusterSettings(autoscale_down_patience=0).validate()

    def test_autoscale_in_lint_scopes(self):
        """cluster/autoscale.py (and the whole process plane) sit inside
        the wall-clock AND determinism lint scopes via the cluster
        subsystem — wall reads need justified pragmas, RNG must be
        seeded instances."""
        from realtime_fraud_detection_tpu.analysis.lint import (
            CLOCK_SUBSYSTEMS,
            DETERMINISM_SUBSYSTEMS,
        )

        assert "cluster" in CLOCK_SUBSYSTEMS
        assert "cluster" in DETERMINISM_SUBSYSTEMS

    def test_lockwatch_ninth_drill_registered(self):
        from realtime_fraud_detection_tpu.analysis.lockwatch import (
            LOCKWATCH_DRILLS,
        )

        assert "elastic-drill" in LOCKWATCH_DRILLS
        # thirteen since ISSUE 20 added obs-drill
        assert len(LOCKWATCH_DRILLS) == 13

    def test_compact_summary_under_2kb_even_when_bloated(self):
        from realtime_fraud_detection_tpu.cluster.elastic_drill import (
            compact_elastic_summary,
        )

        summary = {"metric": "elastic_drill", "passed": False,
                   "autoscale_events": {"up": 99, "down": 99},
                   "checks": {f"very_long_check_name_{i}" * 4: False
                              for i in range(64)}}
        compact = compact_elastic_summary(summary)
        assert len(json.dumps(compact,
                              separators=(",", ":")).encode()) < 2048
        assert compact["metric"] == "elastic_drill"


# ---------------------------------------------------------------------------
# tier-1 smoke: the full drill through the CLI
# ---------------------------------------------------------------------------


class TestElasticDrillSmoke:
    def test_elastic_drill_fast_cli(self):
        """Tier-1 acceptance: `rtfd elastic-drill --fast` — >= 8 real OS
        worker processes over the TCP netbroker, network handoff, a real
        SIGKILL mid-peak, autoscale up-then-drain, oracle equality, and
        the fresh-run determinism digest — passes end to end, final
        stdout line a parseable <2KB verdict."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "realtime_fraud_detection_tpu",
             "elastic-drill", "--fast"],
            capture_output=True, text=True, timeout=540, env=env)
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        compact = json.loads(lines[-1])
        assert len(lines[-1].encode()) < 2048
        assert compact["metric"] == "elastic_drill"
        assert compact["passed"] is True
        assert compact["kill_returncode"] == -9
        assert compact["workers_joined"] >= 8
        assert compact["lost"] == 0 and compact["conflicting_scored"] == 0
        full = json.loads(lines[-2])
        assert full["checks"]["replay_deterministic"] is True
        assert full["checks"]["autoscale_ahead_of_ramp"] is True
        assert full["checks"]["state_equals_oracle"] is True
