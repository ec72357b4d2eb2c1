"""Command-line entry points: simulate / run-job / serve / train /
health-check / topics.

The reference's operational surface is a pile of shell scripts and service
mains (simulator.py:478-503 argparse, FraudDetectionJob.main + JobConfig
CLI flags JobConfig.java:69-146, uvicorn in main.py:343,
scripts/setup/{start-all,health-check,start-simulation}.sh). Here it is one
typed CLI over the framework:

    python -m realtime_fraud_detection_tpu simulate --count 1000
    python -m realtime_fraud_detection_tpu run-job --count 10000 --analytics
    python -m realtime_fraud_detection_tpu serve --port 8000
    python -m realtime_fraud_detection_tpu train --rows 20000 --out ./ckpt
    python -m realtime_fraud_detection_tpu health-check --url http://...
    python -m realtime_fraud_detection_tpu topics
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Optional


def _add_sim_args(p: argparse.ArgumentParser) -> None:
    p.add_argument("--users", type=int, default=10_000,
                   help="user pool size (simulator.py:481)")
    p.add_argument("--merchants", type=int, default=5_000,
                   help="merchant pool size (:482)")
    p.add_argument("--tps", type=float, default=1000.0,
                   help="simulated event-time rate (:481)")
    p.add_argument("--seed", type=int, default=42)


def cmd_simulate(args: argparse.Namespace) -> int:
    """Generate transactions as JSON lines (simulator.py main() analog —
    minus the sleep(1/tps) pacing loop; event time is synthesized)."""
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator

    gen = TransactionGenerator(num_users=args.users,
                               num_merchants=args.merchants,
                               seed=args.seed, tps=args.tps)
    if getattr(args, "broker", ""):
        # produce into an external broker at ~tps (start-simulation.sh
        # role) through the ingress gateway: generation paces here, the
        # gateway's C++ lock-free queue + sender thread overlaps the
        # network produce with generation
        from realtime_fraud_detection_tpu.stream import IngressGateway
        from realtime_fraud_detection_tpu.stream import topics as T

        client = _broker_client(args.broker)
        gateway = IngressGateway(client, T.TRANSACTIONS)
        n_fraud = produced = 0
        try:
            while produced < args.count:
                chunk = min(1000, args.count - produced,
                            max(1, int(args.tps)))
                t0 = time.perf_counter()
                for txn in gen.generate_batch(chunk):
                    n_fraud += bool(txn.get("is_fraud"))
                    while not gateway.submit(txn):  # backpressure: spin
                        time.sleep(0.001)
                produced += chunk
                budget = chunk / args.tps - (time.perf_counter() - t0)
                if budget > 0:
                    time.sleep(budget)
        finally:
            gateway.close()
            client.close()
        print(f"produced {produced} txns ({n_fraud} fraud, "
              f"native_queue={gateway.native}, dropped={gateway.dropped}) "
              f"to {args.broker}", file=sys.stderr)
        return 0
    out = sys.stdout if args.output == "-" else open(args.output, "w")
    try:
        n_fraud = 0
        remaining = args.count
        while remaining > 0:
            for txn in gen.generate_batch(min(1000, remaining)):
                n_fraud += bool(txn.get("is_fraud"))
                out.write(json.dumps(txn) + "\n")
            remaining -= min(1000, remaining)
    finally:
        if out is not sys.stdout:
            out.close()
    print(f"generated {args.count} txns ({n_fraud} fraud)", file=sys.stderr)
    return 0


def _addr(spec: str, default_port: int) -> tuple[str, int]:
    host, _, port = spec.partition(":")
    return host or "127.0.0.1", int(port or default_port)


def _broker_client(spec: str, default_port: int = 9092):
    """Broker client from an address spec. A comma-separated list (the
    replicated-cluster deployment, primary first) returns an
    HaBrokerClient that rotates on connection loss or a not-yet-promoted
    replica's READONLY; a single address returns the plain client."""
    from realtime_fraud_detection_tpu.stream import (
        HaBrokerClient,
        NetBrokerClient,
    )

    addrs = [_addr(a, default_port) for a in spec.split(",") if a.strip()]
    if not addrs:
        raise ValueError(f"no broker address in {spec!r}")
    if len(addrs) > 1:
        return HaBrokerClient(addrs)
    return NetBrokerClient(host=addrs[0][0], port=addrs[0][1])


def cmd_run_job(args: argparse.Namespace) -> int:
    """End-to-end streaming job: simulator -> broker -> microbatched TPU
    scorer -> output topics, with checkpointing + durable job metadata."""
    from realtime_fraud_detection_tpu.checkpoint import (
        CheckpointManager,
        snapshot_scorer_host_state,
    )
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
    from realtime_fraud_detection_tpu.state import MetadataStore
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )
    from realtime_fraud_detection_tpu.stream import topics as T

    gen = TransactionGenerator(num_users=args.users,
                               num_merchants=args.merchants,
                               seed=args.seed, tps=args.tps)
    if args.broker:
        broker = _broker_client(args.broker)
    else:
        broker = InMemoryBroker()
    state_client = None
    if args.state:
        from realtime_fraud_detection_tpu.state import RespClient

        shost, sport = _addr(args.state, 6379)
        state_client = RespClient(host=shost, port=sport)
    job_config_obj = None
    if getattr(args, "quant", False) or getattr(args, "kernels", False):
        from realtime_fraud_detection_tpu.utils.config import (
            Config,
            KernelSettings,
            QuantSettings,
        )

        job_config_obj = Config()
        if getattr(args, "quant", False):
            # quantized scoring plane (models/quant.py): int8 BERT weights
            # + GEMM-form tree kernels, the configuration rtfd quant-drill
            # gates
            job_config_obj.quant = QuantSettings.full()
        if getattr(args, "kernels", False):
            # Pallas kernel plane (ops/): fused dequant-matmul + fused
            # score-and-blend epilogue + flash attention, the
            # configuration rtfd kernel-drill gates
            job_config_obj.kernels = KernelSettings.full()
    scorer = FraudScorer(job_config_obj, scorer_config=ScorerConfig(),
                         state_client=state_client)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    feedback_plane = None
    if getattr(args, "feedback", False):
        # continuous-learning plane: the job feeds emitted predictions into
        # the label join; this entry point also plays the label-producer
        # role (delayed ground truth from the simulator onto the labels
        # topic), so a self-generating run closes the loop end to end
        from realtime_fraud_detection_tpu.feedback import FeedbackPlane
        from realtime_fraud_detection_tpu.obs import (
            DriftConfig,
            FeatureDriftMonitor,
        )
        from realtime_fraud_detection_tpu.utils.config import (
            FeedbackSettings,
        )

        settings = FeedbackSettings(
            enabled=True,
            label_delay_scale=args.feedback_delay_scale)
        feedback_plane = FeedbackPlane(
            settings, scorer=scorer, config=scorer.config,
            drift_monitor=FeatureDriftMonitor(
                DriftConfig(num_features=scorer.sc.feature_dim)))
    qos_settings = None
    if getattr(args, "qos", False):
        from realtime_fraud_detection_tpu.utils.config import QosSettings

        qos_settings = QosSettings(
            enabled=True, budget_ms=args.qos_budget_ms,
            admission_rate=args.qos_rate)
    tracing_settings = None
    if getattr(args, "trace", False):
        from realtime_fraud_detection_tpu.utils.config import TracingSettings

        tracing_settings = TracingSettings(enabled=True)
    tuning_settings = None
    if getattr(args, "autotune", False):
        from realtime_fraud_detection_tpu.utils.config import TuningSettings

        tuning_settings = TuningSettings(enabled=True)
        # the hard QoS floor holds at the CLI seam too: with --qos, the
        # tuner's deadline search space is clamped to the budget's
        # assembly slice, then checked by the same validation
        # Config.validate applies
        tuning_settings.clamp_to_qos(qos_settings)
    job = StreamJob(broker, scorer, JobConfig(
        max_batch=args.batch, enable_analytics=args.analytics,
        enable_enrichment=args.enrichment,
        pipeline_depth=args.pipeline_depth, qos=qos_settings,
        feedback=feedback_plane,
        overlap_assembly=getattr(args, "overlap_assembly", False),
        device_pool=getattr(args, "device_pool", False),
        inflight_depth=getattr(args, "inflight_depth", 2),
        tracing=tracing_settings, autotune=tuning_settings))

    metadata: Optional[MetadataStore] = None
    ckpt: Optional[CheckpointManager] = None
    job_id = f"job-{args.seed}"
    if args.metadata_db:
        metadata = MetadataStore(args.metadata_db)
        metadata.register_job(job_id, "fraud-detection-job", parallelism=1)
        metadata.put_profiles(gen.users.profiles(), gen.merchants.profiles())
    if args.checkpoint_dir:
        ckpt = CheckpointManager(args.checkpoint_dir)

    def _checkpoint_step(step: int) -> None:
        if ckpt is None:
            return
        t_ck = time.perf_counter()
        path = ckpt.save(
            step, params=scorer.models,
            host_state=snapshot_scorer_host_state(scorer),
            offsets=job.consumer.positions())
        if metadata is not None:
            metadata.record_checkpoint(
                job_id, step, str(path),
                duration_ms=(time.perf_counter() - t_ck) * 1e3)

    # graceful shutdown (robustness satellite, ISSUE 12): SIGTERM/SIGINT
    # drain the in-flight microbatches, commit their offsets, and write a
    # final checkpoint before exit — a terminated job loses NOTHING to
    # replay-on-restart; only SIGKILL (no handler possible) replays the
    # uncommitted tail
    import signal as _signal

    stop_sig: Dict[str, Any] = {"name": None}

    def _graceful(signum, frame):  # noqa: ANN001 - signal contract
        stop_sig["name"] = _signal.Signals(signum).name
        job.request_stop()

    try:
        _signal.signal(_signal.SIGTERM, _graceful)
        _signal.signal(_signal.SIGINT, _graceful)
    except ValueError:
        pass                      # not the main thread (embedded/test use)

    t0 = time.perf_counter()
    produced = scored = step = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        # resume: models + host state + transport offsets from the latest
        # checkpoint (the Flink restore-from-checkpoint behavior); step
        # numbering continues so retention never collides
        # rtfd-lint: allow[lock-order] CLI startup: restore runs before any scoring thread exists
        ck = ckpt.restore_into_scorer(scorer)
        if ck.offsets:
            job.consumer.seek_to_positions(ck.offsets)
        step = ck.step
        print(f"resumed from checkpoint step {ck.step} "
              f"({args.checkpoint_dir})", file=sys.stderr)
    try:
        if args.count == 0:
            # consume-only: an external simulator feeds the broker; run in
            # checkpointed slices until --duration elapses (0 = forever)
            while (args.duration <= 0
                   or time.perf_counter() - t0 < args.duration) \
                    and not job.stop_requested:
                scored += job.run_for(
                    min(10.0, args.duration - (time.perf_counter() - t0))
                    if args.duration > 0 else 10.0)
                step += 1
                _checkpoint_step(step)
        while produced < args.count and not job.stop_requested:
            chunk = min(args.count - produced, 10_000)
            records = gen.generate_batch(chunk)
            broker.produce_batch(T.TRANSACTIONS, records,
                                 key_fn=lambda r: str(r["user_id"]))
            if feedback_plane is not None:
                # label-producer role: delayed ground truth for the chunk
                broker.produce_batch(
                    T.LABELS,
                    gen.label_events(records,
                                     delay_scale=args.feedback_delay_scale),
                    key_fn=lambda e: str(e["transaction_id"]))
            produced += chunk
            scored += job.run_until_drained()
            step += 1
            _checkpoint_step(step)
    except BaseException:
        if metadata is not None:
            metadata.set_job_status(job_id, "FAILED")
            metadata.close()
        raise
    if job.analytics is not None:
        job.analytics.flush()
    if stop_sig["name"] is not None:
        # the run loops drained + committed before returning; the final
        # checkpoint pins (state, offsets) at the drained point so resume
        # replays NOTHING (regression-pinned in tests/test_elastic.py)
        step += 1
        _checkpoint_step(step)
        print(f"graceful shutdown on {stop_sig['name']}: in-flight "
              f"drained, offsets committed"
              + (f", final checkpoint step {step}"
                 if ckpt is not None else ""), file=sys.stderr)
    dt = time.perf_counter() - t0
    if metadata is not None:
        metadata.set_job_status(job_id, "FINISHED")
        metadata.close()

    summary: Dict[str, Any] = {
        "scored": scored,
        "wall_s": round(dt, 3),
        "txn_per_s": round(scored / dt, 1),
        "counters": job.counters,
        **({"stopped_by": stop_sig["name"]}
           if stop_sig["name"] is not None else {}),
    }
    if feedback_plane is not None:
        snap = feedback_plane.snapshot()
        summary["feedback"] = {
            "prequential_sliding": snap["prequential"]["sliding"],
            "labels_matched": snap["label_join"]["matched"],
            "buffer": snap["buffer"]["size"],
            "policy": snap["policy"],
        }
    if job.tracer is not None:
        bd = job.tracer.breakdown()
        slo = job.tracer.slo.snapshot()
        summary["tracing"] = {
            "traces": bd["n"],
            "p99": bd["quantiles"].get("p99"),
            "slo_fast": slo["windows"]["fast"],
            "counters": dict(job.tracer.counters),
        }
    if job.tuning is not None:
        snap = job.tuning.snapshot()
        summary["autotune"] = {
            "decisions": snap["controller"]["decisions"],
            "max_wait_ms": snap["controller"]["max_wait_ms"],
            "tuner": snap["tuner"]["counters"],
            "close_reasons": dict(job.assembler.close_reasons),
        }
    if job.analytics is not None:
        summary["analytics"] = {
            k: v["fired"] for k, v in job.analytics.stats().items()}
    print(json.dumps(summary))
    return 0


def cmd_serve(args: argparse.Namespace) -> int:
    """Run the scoring service (reference main.py:343 uvicorn analog)."""
    from realtime_fraud_detection_tpu.serving.app import ServingApp
    from realtime_fraud_detection_tpu.utils.config import Config

    config = Config.from_file(args.config) if args.config else Config()
    if args.host:
        config.serving.host = args.host
    if args.port is not None:
        config.serving.port = args.port
    if getattr(args, "qos", False):
        config.qos.enabled = True
    if getattr(args, "qos_budget_ms", None):
        config.qos.budget_ms = args.qos_budget_ms
    if getattr(args, "qos_rate", None):
        config.qos.admission_rate = args.qos_rate
    if getattr(args, "trace", False):
        config.tracing.enabled = True
    if getattr(args, "quant", False):
        from realtime_fraud_detection_tpu.utils.config import QuantSettings

        config.quant = QuantSettings.full()
    if getattr(args, "kernels", False):
        from realtime_fraud_detection_tpu.utils.config import KernelSettings

        config.kernels = KernelSettings.full()
    if getattr(args, "autotune", False):
        config.tuning.enabled = True
        # clamp the tuner's deadline search space to the budget's
        # assembly slice (the validation floor), then re-check
        config.tuning.clamp_to_qos(config.qos)
    if getattr(args, "overlap_assembly", False):
        config.serving.overlap_assembly = True
    if getattr(args, "device_pool", False):
        config.serving.device_pool = True
    if getattr(args, "inflight_depth", None):
        config.serving.inflight_depth = args.inflight_depth
    scorer_kwargs: Dict[str, Any] = {}
    if getattr(args, "quality_artifact", ""):
        applied = config.apply_quality_artifact(args.quality_artifact)
        print(f"serving the measured blend from {args.quality_artifact}: "
              f"{applied}", file=sys.stderr)
        # the artifact records the text-branch architecture + tokenizer the
        # blend was measured (and its checkpoint trained) with — the scorer
        # must be built to match or a checkpoint restore would mismatch
        with open(args.quality_artifact) as f:
            proto = json.load(f).get("protocol", {})
        if proto.get("text_model"):
            from realtime_fraud_detection_tpu.models.bert import BertConfig

            scorer_kwargs["bert_config"] = BertConfig(**proto["text_model"])
    scorer = None
    state_addr = args.state or os.environ.get("RTFD_STATE_ADDR", "")
    if state_addr or scorer_kwargs:
        from realtime_fraud_detection_tpu.scoring import (
            FraudScorer,
            ScorerConfig,
        )

        sc = ScorerConfig()
        if getattr(args, "quality_artifact", "") and proto.get("text_model"):
            import dataclasses as _dc

            sc = _dc.replace(
                sc, text_len=int(proto.get("text_len", 32)),
                tokenizer=proto.get("tokenizer", "word"))
        if state_addr:
            from realtime_fraud_detection_tpu.state import RespClient

            shost, sport = _addr(state_addr, 6379)
            scorer_kwargs["state_client"] = RespClient(host=shost,
                                                       port=sport)
            print(f"using shared state tier at {state_addr}",
                  file=sys.stderr)
        scorer = FraudScorer(config, scorer_config=sc, **scorer_kwargs)
    app = ServingApp(config=config, scorer=scorer)
    if args.checkpoint_dir:
        from realtime_fraud_detection_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(args.checkpoint_dir)
        if getattr(args, "quality_artifact", ""):
            # refuse to combine a checkpoint and an artifact recording
            # DIFFERENT text-encoder architectures: the
            # blend was measured against one model, the restored params
            # are another. --allow-arch-mismatch overrides explicitly.
            art_tm = Config.load_artifact_text_model(args.quality_artifact)
            ck_tm = (mgr.manifest().get("metadata") or {}).get("text_model")
            if (art_tm is not None and ck_tm is not None
                    and dict(art_tm) != dict(ck_tm)
                    and not getattr(args, "allow_arch_mismatch", False)):
                print(f"text-encoder architecture mismatch: artifact "
                      f"{args.quality_artifact} records {art_tm}, "
                      f"checkpoint {args.checkpoint_dir} records {ck_tm}; "
                      f"pass --allow-arch-mismatch to combine anyway",
                      file=sys.stderr)
                return 2
        try:
            # rtfd-lint: allow[lock-order] CLI startup: restore runs before the serving loop starts
            ck = mgr.restore_into_scorer(
                app.scorer,
                allow_arch_mismatch=getattr(args, "allow_arch_mismatch",
                                            False))
        except ValueError as e:
            # quantization-mode / shape stamp refusal: exit loudly instead
            # of serving a silently cross-mode model
            print(str(e), file=sys.stderr)
            return 2
        print(f"restored checkpoint step {ck.step} from "
              f"{args.checkpoint_dir}", file=sys.stderr)
    print(f"serving on {config.serving.host}:{config.serving.port}",
          file=sys.stderr)
    app.run_forever()
    return 0


def cmd_train(args: argparse.Namespace) -> int:
    """Train the tree models on synthetic data and save a checkpoint
    (model_trainer.py analog: XGBoost + IsolationForest, AUC eval,
    artifact save — :41-295). The checkpoint holds a FULL ScoringModels
    set (trained trees + isolation forest, fresh neural branches) so
    ``serve --checkpoint-dir`` and ``POST /reload-models`` can load it
    directly."""
    import numpy as np

    from realtime_fraud_detection_tpu.checkpoint import CheckpointManager
    from realtime_fraud_detection_tpu.features.extract import extract_features
    from realtime_fraud_detection_tpu.models.isolation_forest import (
        IsolationForestTrainer,
    )
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
    from realtime_fraud_detection_tpu.training import GBDTTrainer

    gen = TransactionGenerator(num_users=args.users,
                               num_merchants=args.merchants, seed=args.seed)
    batch, labels = gen.generate_encoded(args.rows)
    x = np.asarray(extract_features(batch))
    y = labels["is_fraud"].astype(np.float32)
    split = int(0.8 * len(y))

    gbdt_trainer = GBDTTrainer(n_estimators=args.trees, seed=args.seed)
    trees = gbdt_trainer.fit(x[:split], y[:split])
    from realtime_fraud_detection_tpu.models.trees import tree_ensemble_logits

    logits = np.asarray(tree_ensemble_logits(trees, x[split:]))
    auc = _auc(y[split:], logits)

    iforest = IsolationForestTrainer(seed=args.seed).fit(
        x[:split][y[:split] == 0])          # fit on normals only (:235-276)

    import jax

    from realtime_fraud_detection_tpu.scoring import init_scoring_models

    models = init_scoring_models(jax.random.PRNGKey(args.seed))
    models = models.replace(trees=trees, iforest=iforest)

    if args.neural:
        # train every neural branch too (the reference's ModelTrainer
        # docstring claims LSTM/BERT/GNN trainers that don't exist —
        # model_trainer.py:2-4, SURVEY.md §3.5)
        from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG
        from realtime_fraud_detection_tpu.training.neural import (
            train_gnn,
            train_lstm,
        )
        from realtime_fraud_detection_tpu.training.text import train_bert

        n = args.rows
        lstm = train_lstm(gen, n_transactions=n, hidden=128,
                          epochs=2, seed=args.seed)
        gnn, _, _, _ = train_gnn(gen, n_transactions=n, node_dim=16,
                                 hidden=64, epochs=2, seed=args.seed)
        bert = train_bert(gen, config=TINY_CONFIG,
                          n_transactions=min(n, 8000), epochs=1,
                          seed=args.seed)
        models = models.replace(lstm=lstm, gnn=gnn, bert=bert)

    mgr = CheckpointManager(args.out)
    # a FRESH step per run (never overwrite in place): a reader — the
    # serving hot-reload or the 3 AM validate CronJob — resolving "latest"
    # mid-save sees the previous complete step, not a torn rmtree'd dir.
    # The recorded sim_seed lets validate refuse a contaminated eval stream.
    latest = mgr.latest_step()
    step = 0 if latest is None else latest + 1
    path = mgr.save(step, params=models,
                    metadata={"rows": args.rows, "auc": auc,
                              "fraud_rate": float(y.mean()),
                              "sim_seed": args.seed,
                              "sim_users": args.users,
                              "sim_merchants": args.merchants,
                              # restored by restore_into_scorer so served
                              # explanations keep their importances
                              "feature_importances":
                                  [round(float(v), 6) for v in
                                   gbdt_trainer.feature_importances_]})
    from realtime_fraud_detection_tpu.features.extract import (
        top_feature_importances,
    )

    print(json.dumps({"auc": round(auc, 4),
                      "fraud_rate": round(float(y.mean()), 4),
                      "neural_trained": bool(args.neural),
                      "top_feature_importances": top_feature_importances(
                          gbdt_trainer.feature_importances_),
                      "checkpoint": str(path)}))
    return 0


def _auc(y: "Any", score: "Any") -> float:
    """Mann-Whitney AUC with average ranks for ties (tied logits are common
    with few trees; ordinal ranks would bias the estimate)."""
    import numpy as np

    score = np.asarray(score, float)
    order = np.argsort(score)
    rank = np.empty(len(score), float)
    sorted_scores = score[order]
    i = 0
    while i < len(score):
        j = i
        while j + 1 < len(score) and sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        rank[order[i:j + 1]] = (i + j) / 2.0 + 1.0   # average 1-based rank
        i = j + 1
    pos = np.asarray(y) > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    if not n_pos or not n_neg:
        return 0.5
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def cmd_validate(args: argparse.Namespace) -> int:
    """Validate a trained checkpoint against a fresh labeled stream.

    The reference schedules this as its model-validation CronJob
    (ci-cd-pipeline.yaml:351-390: daily run, metrics pushed to a Prometheus
    gateway) but ships no implementation. Here: restore the checkpoint into
    a scorer, score a freshly simulated stream with known injected fraud,
    report AUC/accuracy/precision/recall, optionally write a Prometheus
    textfile, and FAIL (exit 1) below --min-auc so the CronJob's status is
    the quality gate.
    """
    import numpy as np

    from realtime_fraud_detection_tpu.checkpoint import CheckpointManager
    from realtime_fraud_detection_tpu.scoring import FraudScorer
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator

    scorer = FraudScorer()
    # rtfd-lint: allow[lock-order] CLI startup: restore runs before any scoring begins
    ckpt = CheckpointManager(args.checkpoint_dir).restore_into_scorer(
        scorer, step=args.step)
    # Held-out eval stream: never the checkpoint's recorded training seed.
    # The +1 convention alone is not a guarantee (validate --seed 41 would
    # land exactly on a 42-trained stream), so cross-check the manifest.
    train_seed = (ckpt.metadata or {}).get("sim_seed")
    val_seed = args.seed + 1
    if train_seed is not None and val_seed == int(train_seed):
        val_seed += 1
    gen = TransactionGenerator(num_users=args.users,
                               num_merchants=args.merchants, seed=val_seed)
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())

    ys, ss = [], []
    remaining = args.rows
    while remaining > 0:
        recs = gen.generate_batch(min(256, remaining))
        remaining -= len(recs)
        res = scorer.score_batch(recs)
        ys += [bool(r.get("is_fraud")) for r in recs]
        ss += [r["fraud_probability"] for r in res]
    y = np.asarray(ys, float)
    s = np.asarray(ss, float)
    pos = y > 0.5
    flag = s >= 0.5
    auc = _auc(y, s)
    tp = float((flag & pos).sum())
    report = {
        "n": int(len(y)),
        "fraud_rate": round(float(pos.mean()), 4),
        "auc": round(auc, 4),
        "accuracy": round(float((flag == pos).mean()), 4),
        "precision": round(tp / max(float(flag.sum()), 1.0), 4),
        "recall": round(tp / max(float(pos.sum()), 1.0), 4),
        "min_auc": args.min_auc,
        "passed": bool(auc >= args.min_auc),
        "eval_seed": val_seed,
        "checkpoint_step": int(ckpt.step),
    }
    if args.metrics_out:
        # Prometheus textfile (node-exporter textfile-collector format) —
        # the no-egress analog of the reference's pushgateway POST; rendered
        # by the project's own exposition code so formatting/escaping has
        # one implementation (obs/metrics.py)
        from realtime_fraud_detection_tpu.obs.metrics import Registry

        reg = Registry()
        for k, v in report.items():
            if isinstance(v, bool):
                v = int(v)
            elif not isinstance(v, (int, float)):
                continue
            reg.gauge(f"rtfd_validation_{k}",
                      f"model validation gate: {k}").set(float(v))
        with open(args.metrics_out, "w") as f:
            f.write(reg.render())
    print(json.dumps(report))
    return 0 if report["passed"] else 1


def cmd_broker(args: argparse.Namespace) -> int:
    """Run the standalone durable log broker (the Kafka-role process of a
    multi-service deployment; stream/netbroker.py). Blocks until SIGINT."""
    from realtime_fraud_detection_tpu.stream.netbroker import BrokerServer

    import time as _time

    server = BrokerServer(host=args.host, port=args.port,
                          log_dir=args.log_dir or None,
                          role=getattr(args, "role", "primary"),
                          min_isr=getattr(args, "min_isr", 1)).start()
    for addr in getattr(args, "replica", []) or []:
        rhost, _, rport = addr.rpartition(":")
        # a cluster starting in parallel may bring the primary up first:
        # retry attachment until the replica answers (k8s data-plane.yaml)
        for attempt in range(60):
            try:
                server.add_replica(rhost or "127.0.0.1", int(rport))
                break
            except OSError as e:
                if attempt == 59:
                    raise
                print(f"replica {addr} not reachable yet ({e}); retrying",
                      file=sys.stderr)
                _time.sleep(2.0)
        print(f"replica {addr} caught up and in sync", file=sys.stderr)
    print(f"broker listening on {args.host}:{server.port}"
          + (f" (log_dir={args.log_dir})" if args.log_dir else "")
          + (f" role={server.role} min_isr={server.min_isr}"),
          file=sys.stderr)
    try:
        threading_event_wait()
    finally:
        server.stop()
    return 0


def cmd_cluster_worker(args: argparse.Namespace) -> int:
    """One partition-scoped fleet worker PROCESS (cluster/procfleet.py):
    spawned by the elastic coordinator (``ProcessFleet`` — the elastic
    drill) with a JSON spec naming the
    broker, the handoff server, and this worker's identity. Consumes its
    assigned partitions over the TCP netbroker, checkpoints into the
    network handoff store, drains gracefully on SIGTERM/shutdown, and
    reports state digests in its bye event. Not normally invoked by
    hand."""
    from realtime_fraud_detection_tpu.cluster.procfleet import worker_main

    return worker_main(json.loads(args.spec))


def cmd_state_server(args: argparse.Namespace) -> int:
    """Run the shared state node (Redis-protocol; state/resp.py) — the
    RedisService-role process N scorer replicas share. Blocks until SIGINT."""
    from realtime_fraud_detection_tpu.state.resp import MiniRedisServer

    replica_of = None
    if args.replica_of:
        host, _, port = args.replica_of.rpartition(":")
        replica_of = (host, int(port))
    server = MiniRedisServer(
        host=args.host, port=args.port,
        maxmemory=args.maxmemory, policy=args.policy,
        aof_path=args.aof or None, replica_of=replica_of,
    ).start()
    role = "replica" if server.is_replica else "master"
    print(f"state server (RESP, {role}) listening on "
          f"{args.host}:{server.port}", file=sys.stderr)
    try:
        threading_event_wait()
    finally:
        server.stop()
    return 0


def threading_event_wait() -> None:  # pragma: no cover - blocks forever
    import threading

    threading.Event().wait()


def cmd_quality_eval(args: argparse.Namespace) -> int:
    """Run the production blend-selection protocol (training/blend_eval.py):
    train all 5 branches on a stream-matched segment, admit branches into
    the blend by validation A/B, report held-out quality + ablations. The
    committed QUALITY_r*.json artifacts are produced by exactly this
    command."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.training.blend_eval import (
        BlendEvalConfig,
        run_blend_eval,
    )

    cfg = _dc.replace(
        BlendEvalConfig(), seed=args.seed,
        train_batches=args.train_batches, val_batches=args.val_batches,
        test_batches=args.test_batches)
    result = run_blend_eval(
        cfg, log=lambda m: print(f"[quality-eval] {m}", file=sys.stderr,
                                 flush=True),
        checkpoint_dir=args.checkpoint_dir or None)
    payload = json.dumps(result, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(payload + "\n")
        print(f"wrote {args.output}", file=sys.stderr)
    else:
        print(payload)
    return 0


def cmd_alert_router(args: argparse.Namespace) -> int:
    """Fan fraud alerts out to notification receivers.

    The reference routes high-risk events EventBridge -> Lambda -> SNS
    (fraud-detection-additional-resources.yaml:364-458: the Lambda just
    reshapes the event and publishes it). Here the same seam is a consumer
    on the ``fraud-alerts`` topic that POSTs each alert to an
    Alertmanager-compatible webhook (deploy/monitoring/alertmanager.yml
    owns the receiver fan-out: email/page/chat — the SNS-subscription
    analog), or prints JSON lines when no webhook is configured (log
    sink). ``--once`` drains and exits (the CronJob/test mode); default
    follows the topic forever.
    """
    import time as _time
    import urllib.request

    from realtime_fraud_detection_tpu.stream import topics as T

    broker = _broker_client(args.broker)
    consumer = broker.consumer([T.ALERTS], args.group)
    routed = 0
    backoff = 1.0
    try:
        while True:
            recs = consumer.poll(500)
            if not recs:
                if args.once:
                    break
                _time.sleep(args.poll_interval)
                continue
            payload = []
            for r in recs:
                a = r.value if isinstance(r.value, dict) else {}
                payload.append({
                    "labels": {
                        "alertname": str(a.get("alert_type",
                                               "FRAUD_DETECTED")),
                        "severity": ("critical"
                                     if str(a.get("decision")) == "DECLINE"
                                     else "warning"),
                        "risk_level": str(a.get("risk_level", "UNKNOWN")),
                        "merchant_id": str(a.get("merchant_id", "")),
                        "service": "rtfd",
                    },
                    "annotations": {
                        "transaction_id": str(a.get("transaction_id", "")),
                        "user_id": str(a.get("user_id", "")),
                        "amount": str(a.get("amount", "")),
                        "fraud_score": str(a.get("fraud_score", "")),
                    },
                })
            if args.webhook:
                req = urllib.request.Request(
                    args.webhook, data=json.dumps(payload).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                try:
                    with urllib.request.urlopen(req, timeout=10) as resp:
                        resp.read()
                except OSError as e:  # URLError subclasses OSError
                    # a receiver blip must not crash-loop the daemon:
                    # leave offsets uncommitted (the batch redelivers),
                    # back off, retry. --once propagates the failure so
                    # CronJob/test mode stays loud.
                    if args.once:
                        raise
                    print(f"webhook unreachable ({e}); retrying in "
                          f"{backoff:.0f}s", file=sys.stderr)
                    _time.sleep(backoff)
                    backoff = min(backoff * 2, 60.0)
                    # rewind to the committed offsets (the crash-recovery
                    # path) so the uncommitted batch redelivers
                    consumer.seek_to_committed()
                    continue
            else:
                for item in payload:
                    print(json.dumps(item), flush=True)
            backoff = 1.0
            # commit only after the receiver accepted the batch:
            # at-least-once alert delivery (receivers dedupe on
            # transaction_id, same contract as the predictions topic)
            consumer.commit()
            routed += len(payload)
    except KeyboardInterrupt:  # pragma: no cover - operator stop
        pass
    finally:
        broker.close()
    print(f"routed {routed} alerts", file=sys.stderr)
    return 0


def cmd_qos_drill(args: argparse.Namespace) -> int:
    """Deterministic overload demo for the QoS plane (qos/drill.py): drive
    offered load at N× the sustainable rate through the real stream path on
    a virtual clock; print the admission/ladder/budget outcome as JSON.
    Exit 1 if the admitted p99 missed the configured budget."""
    from realtime_fraud_detection_tpu.qos import run_overload_drill

    summary = run_overload_drill(
        offered_multiplier=args.multiplier,
        overload_s=args.overload_s,
        recovery_s=args.recovery_s,
        max_batch=args.batch,
        budget_ms=args.budget_ms,
        high_frac=args.high_frac,
        low_frac=args.low_frac,
        seed=args.seed,
    )
    print(json.dumps(summary, indent=2))
    return 0 if summary["p99_within_budget"] else 1


def cmd_feedback_drill(args: argparse.Namespace) -> int:
    """Deterministic closed-loop continuous-learning demo (feedback/
    drill.py): virtual clock, real scorer + retraining. Prints the full
    summary, then a compact (<2 KB) parseable verdict as the FINAL stdout
    line. Exit 1 unless the whole loop passed:
    drift injected -> prequential AUC dip -> retrain trigger -> gate
    rejects the negative control bit-identically -> genuine candidate
    promoted only on gate-pass -> AUC recovers."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.feedback.drill import (
        FeedbackDrillConfig,
        compact_drill_summary,
        run_feedback_drill,
    )

    cfg = (FeedbackDrillConfig.fast() if args.fast
           else FeedbackDrillConfig())
    cfg = _dc.replace(cfg, seed=args.seed, drift_rate=args.drift_rate)
    summary = run_feedback_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_drill_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_quant_drill(args: argparse.Namespace) -> int:
    """Deterministic quantization drill (scoring/quant_drill.py): the
    score-delta oracle gating the quantized scoring plane. One seeded
    stream through the f32 and the fully quantized fused programs (int8
    BERT + GEMM-form tree kernels): max score divergence pinned below the
    measured calibration-noise floor (what the committed bf16 compute
    policy already moves scores by), zero decision flips at the pinned
    operating point, quality-protocol AUC unchanged, exact GEMM-vs-gather
    leaf equality, >= 3.5x smaller BERT param bytes, and a bit-identical
    second run. Prints the full summary, then a compact (<2 KB) verdict
    as the FINAL stdout line. Exit 1 unless every
    check passed."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.scoring.quant_drill import (
        QuantDrillConfig,
        compact_quant_summary,
        run_quant_drill,
    )

    cfg = QuantDrillConfig.fast() if args.fast else QuantDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay=not getattr(args, "no_replay", False))
    summary = run_quant_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_quant_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_kernel_drill(args: argparse.Namespace) -> int:
    """Deterministic kernel drill (scoring/kernel_drill.py): the parity
    oracle gating the Pallas kernel plane. One seeded stream through two
    quantized fused programs — stock XLA lowering vs every kernel on
    (fused dequant-matmul + fused score-and-blend epilogue + flash
    attention): max score divergence pinned below the measured
    calibration-noise floor, zero decision flips, exact masked-blend
    equality at every QoS ladder rung, per-kernel interpret-vs-reference
    parity on the served params, zero guard fallbacks, and a bit-identical
    second run. Prints the full summary, then a compact (<2 KB)
    verdict as the FINAL stdout line. Exit 1 unless every check passed."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.scoring.kernel_drill import (
        KernelDrillConfig,
        compact_kernel_summary,
        run_kernel_drill,
    )

    cfg = KernelDrillConfig.fast() if args.fast else KernelDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay=not getattr(args, "no_replay", False))
    summary = run_kernel_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_kernel_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_trace_drill(args: argparse.Namespace) -> int:
    """Deterministic tracing drill (obs/trace_drill.py): the real stream
    path on a virtual clock with an injected slow stage. Pins that the
    critical-path analyzer names the right culprit (slow assembly ->
    `assemble`, slow device -> `device_wait`), that the SLO burn rate
    reacts to the injected violation and recovers (engaging/releasing the
    QoS gate), that FIFO/shed behavior is identical with tracing on, and
    that per-txn tracing overhead stays under the pinned bound. Prints
    the full summary, then a compact (<2 KB) verdict as the FINAL stdout
    line. Exit 1 unless every check passed."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.obs.trace_drill import (
        TraceDrillConfig,
        compact_trace_summary,
        run_trace_drill,
    )

    cfg = TraceDrillConfig.fast() if args.fast else TraceDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed)
    summary = run_trace_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_trace_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_autotune_drill(args: argparse.Namespace) -> int:
    """Deterministic self-tuning drill (tuning/drill.py): replay one
    nonstationary offered-load timeline (diurnal ramp + bursts, virtual
    clock) through a pinned grid of static fixed-deadline configs AND
    through the arrival-aware just-in-time controller. Pins that the
    controller beats every static config on admitted p99 at
    equal-or-better throughput, never sheds high-value traffic, respects
    the QoS budget floor, and that its decisions replay bit-identically.
    Prints the full summary, then a compact (<2 KB) verdict as the FINAL
    stdout line. Exit 1 unless every check passed."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.tuning.drill import (
        AutotuneDrillConfig,
        compact_autotune_summary,
        run_autotune_drill,
    )

    cfg = AutotuneDrillConfig.fast() if args.fast else AutotuneDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed)
    summary = run_autotune_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_autotune_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_trace_export(args: argparse.Namespace) -> int:
    """Run a traced fake-Kafka job and export the captured window as
    Chrome-trace/Perfetto JSON (load in ui.perfetto.dev or
    chrome://tracing). The flight recorder's ring plus the slowest-N
    exemplars land in the file; a one-line capture summary goes to
    stdout.

    ``--merge ring_w0.json ring_w1.json ...`` skips the local capture
    and instead folds multi-process flight-recorder ring dumps (the
    ``{worker, pid, traces}`` shape ``rtfd obs-drill --rings-out`` and
    the workers' bye frames emit) into ONE fleet trace: a named track
    per OS process and the broker hop drawn as a flow arrow from the
    producer's transit slice to the consuming worker's first slice."""
    if getattr(args, "merge", None):
        from realtime_fraud_detection_tpu.obs.fleetmetrics import (
            merge_chrome_traces,
        )

        dumps = []
        for path in args.merge:
            with open(path) as f:
                dumps.append(json.load(f))
        payload = merge_chrome_traces(dumps)
        with open(args.out, "w") as f:
            json.dump(payload, f)
        print(json.dumps({
            "merged_rings": len(dumps),
            "traces": payload["metadata"]["n_traces"],
            "tracks": payload["metadata"]["tracks"],
            "events": len(payload["traceEvents"]),
            "out": args.out,
        }))
        return 0
    from realtime_fraud_detection_tpu.obs.tracing import Tracer
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.sim.simulator import TransactionGenerator
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )
    from realtime_fraud_detection_tpu.stream import topics as T
    from realtime_fraud_detection_tpu.utils.config import TracingSettings

    gen = TransactionGenerator(num_users=args.users,
                               num_merchants=args.merchants,
                               seed=args.seed, tps=args.tps)
    scorer = FraudScorer(scorer_config=ScorerConfig())
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    tracer = Tracer(TracingSettings(enabled=True,
                                    ring_size=max(64, args.count)))
    broker = InMemoryBroker()
    job = StreamJob(broker, scorer, JobConfig(
        max_batch=args.batch, tracing=tracer, emit_features=False))
    produced = 0
    while produced < args.count:
        chunk = min(args.count - produced, 10_000)
        broker.produce_batch(T.TRANSACTIONS, gen.generate_batch(chunk),
                             key_fn=lambda r: str(r["user_id"]))
        produced += chunk
        job.run_until_drained()
    payload = tracer.export_chrome_trace()
    with open(args.out, "w") as f:
        json.dump(payload, f)
    bd = tracer.breakdown()
    print(json.dumps({
        "traces": bd["n"],
        "events": len(payload["traceEvents"]),
        "p99": bd["quantiles"].get("p99"),
        "out": args.out,
    }))
    return 0


def virtual_cpu_env(n_devices: int) -> Dict[str, str]:
    """Child env for a drill re-exec'd onto ``n_devices`` virtual CPU
    devices. The pool/mesh/chaos drills pin multi-device contracts
    (bit-equality, rotation, hot swap) that must read the same on every
    box, so they never run on whatever accelerator the parent would find;
    the parent stays JAX-free and the child alone owns a backend."""
    env = dict(os.environ)
    flags = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count="
        f"{n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    return env


def cmd_pool_drill(args: argparse.Namespace) -> int:
    """Deterministic device-pool drill (scoring/pool_drill.py): the real
    pooled scoring path on N host-platform virtual devices, pinning
    bit-equality with single-device scoring, FIFO completion, full
    utilization, hot-swap purity, and the scheduler's >= 3x virtual-time
    scaling. Prints the full summary, then a compact (<2 KB) verdict as
    the FINAL stdout line. Exit 1 unless every
    check passed.

    Always re-execs onto a virtual N-device CPU host platform
    (``virtual_cpu_env``: the parent never initializes a backend, and
    the verdict is identical on every box). Scaling on four chips is not
    measured: no admitted cell of the benchmark runs the pool.
    """
    import subprocess

    if os.environ.get("_RTFD_POOL_DRILL_CHILD") == "1":
        return _pool_drill_inprocess(args)
    env = virtual_cpu_env(args.devices)
    env["_RTFD_POOL_DRILL_CHILD"] = "1"
    argv = [sys.executable, "-m", "realtime_fraud_detection_tpu",
            "pool-drill", "--devices", str(args.devices),
            "--inflight-depth", str(args.inflight_depth),
            "--seed", str(args.seed)]
    if args.fast:
        argv.append("--fast")
    proc = subprocess.run(argv, env=env, timeout=540)
    return proc.returncode


def _pool_drill_inprocess(args: argparse.Namespace) -> int:
    import dataclasses as _dc

    import jax

    jax.config.update("jax_platforms", "cpu")

    from realtime_fraud_detection_tpu.scoring.pool_drill import (
        PoolDrillConfig,
        compact_pool_summary,
        run_pool_drill,
    )

    cfg = PoolDrillConfig.fast() if args.fast else PoolDrillConfig()
    cfg = _dc.replace(cfg, n_devices=args.devices,
                      inflight_depth=args.inflight_depth, seed=args.seed)
    summary = run_pool_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_pool_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_mesh_drill(args: argparse.Namespace) -> int:
    """Deterministic mesh-sharding drill (scoring/mesh_drill.py): the real
    GSPMD data x model serving path on N host-platform virtual devices,
    pinning bit-equality with single-device scoring for every branch-
    placement combo (quantized forms and every QoS ladder rung included),
    no-mixed-params hot swap under the same placement, donated staging
    actually consumed, per-chip BERT bytes <= 60% of replicated at
    model_axis=2, and a bit-identical second pass. Prints the full
    summary, then a compact (<2 KB) verdict as the FINAL stdout line.
    Exit 1 unless every check passed.

    Always re-execs onto a virtual N-device CPU host platform
    (``virtual_cpu_env``: the parent never initializes a backend, and
    the verdict is identical on every box). Throughput is not gated
    here — model-sharding is an HBM bet that may LOSE on CPU, and the
    drill refuses to pretend otherwise.
    """
    import subprocess

    if os.environ.get("_RTFD_MESH_DRILL_CHILD") == "1":
        return _mesh_drill_inprocess(args)
    env = virtual_cpu_env(args.devices)
    env["_RTFD_MESH_DRILL_CHILD"] = "1"
    argv = [sys.executable, "-m", "realtime_fraud_detection_tpu",
            "mesh-drill", "--devices", str(args.devices),
            "--model-axis", str(args.model_axis),
            "--inflight-depth", str(args.inflight_depth),
            "--seed", str(args.seed)]
    if args.fast:
        argv.append("--fast")
    if args.no_replay:
        argv.append("--no-replay")
    proc = subprocess.run(argv, env=env, timeout=540)
    return proc.returncode


def _mesh_drill_inprocess(args: argparse.Namespace) -> int:
    import dataclasses as _dc

    import jax

    jax.config.update("jax_platforms", "cpu")

    from realtime_fraud_detection_tpu.scoring.mesh_drill import (
        MeshDrillConfig,
        compact_mesh_summary,
        run_mesh_drill,
    )

    cfg = MeshDrillConfig.fast() if args.fast else MeshDrillConfig()
    cfg = _dc.replace(cfg, n_devices=args.devices,
                      model_axis=args.model_axis,
                      inflight_depth=args.inflight_depth, seed=args.seed,
                      replay_check=not args.no_replay)
    summary = run_mesh_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_mesh_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_chaos_drill(args: argparse.Namespace) -> int:
    """Deterministic combined recovery drill (chaos/drill.py): one seeded
    virtual-clock timeline layering a flash crowd, a broker replica outage
    (real NotEnoughReplicas window + add_replica backfill), device-pool
    replica death + slow device, a label-stream stall, and a coordinated
    fraud ring — proving the QoS/tracing/pool/feedback planes hold
    TOGETHER: zero high-value sheds, effectively-once across the outage,
    ladder + SLO burn recovery, pool retries with FIFO intact, ring AUC
    retrained back past baseline via a gate-passed promotion, and a second
    run replaying bit-identically. Prints the full summary, then a compact
    (<2 KB) verdict as the FINAL stdout line. Exit 1
    unless every check passed.

    Always re-execs onto a virtual N-device CPU host platform
    (``virtual_cpu_env``: the parent never initializes a backend, and
    the verdict is identical on every box).
    """
    import subprocess

    if os.environ.get("_RTFD_CHAOS_DRILL_CHILD") == "1":
        return _chaos_drill_inprocess(args)
    from realtime_fraud_detection_tpu.chaos.drill import ChaosDrillConfig

    devices = args.devices or (ChaosDrillConfig.fast().n_devices
                               if args.fast else ChaosDrillConfig().n_devices)
    env = virtual_cpu_env(devices)
    env["_RTFD_CHAOS_DRILL_CHILD"] = "1"
    argv = [sys.executable, "-m", "realtime_fraud_detection_tpu",
            "chaos-drill", "--devices", str(devices)]
    if args.seed is not None:       # explicit flag wins over chaos.seed
        argv += ["--seed", str(args.seed)]
    if args.config:
        argv += ["--config", args.config]
    if args.fast:
        argv.append("--fast")
    if args.no_replay:
        argv.append("--no-replay")
    proc = subprocess.run(argv, env=env, timeout=540)
    return proc.returncode


def _chaos_drill_inprocess(args: argparse.Namespace) -> int:
    import dataclasses as _dc

    import jax

    jax.config.update("jax_platforms", "cpu")

    from realtime_fraud_detection_tpu.chaos.drill import (
        ChaosDrillConfig,
        apply_chaos_settings,
        compact_chaos_summary,
        run_chaos_drill,
    )

    cfg = ChaosDrillConfig.fast() if args.fast else ChaosDrillConfig()
    if args.config:
        from realtime_fraud_detection_tpu.utils.config import Config

        cfg = apply_chaos_settings(cfg, Config.from_file(args.config).chaos)
    cfg = _dc.replace(cfg, replay_check=not args.no_replay,
                      **({"seed": args.seed}
                         if args.seed is not None else {}),
                      **({"n_devices": args.devices} if args.devices else {}))
    summary = run_chaos_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_chaos_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_shard_drill(args: argparse.Namespace) -> int:
    """Deterministic partition-parallel worker drill (cluster/drill.py):
    a simulated population (1M users at the full config) scored across
    >= 4 partition-scoped StreamJob workers sharing one broker log, with
    a mid-stream worker kill (chaos WorkerKill injector) recovered by
    checkpointed state handoff + committed-gap state replay. Pins zero
    lost / double-scored transactions, gap-free committed offsets,
    per-key ordering, sharded state digest-equal to a single-worker
    oracle run, consistent-hash router agreement with fleet ownership
    (only the dead worker's partitions move), and a bit-identical second
    run. Prints the full summary, then a compact (<2 KB) verdict as the
    FINAL stdout line. Exit 1 unless every check
    passed. Pure host arithmetic on a virtual clock — no device needed."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.cluster.drill import (
        ShardDrillConfig,
        compact_shard_summary,
        run_shard_drill,
    )

    cfg = ShardDrillConfig.fast() if args.fast else ShardDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay_check=not args.no_replay,
                      **({"n_workers": args.workers} if args.workers
                         else {}))
    summary = run_shard_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_shard_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_elastic_drill(args: argparse.Namespace) -> int:
    """Deterministic elastic-cluster drill (cluster/elastic_drill.py): a
    seeded diurnal-ramp timeline over a 10M-user id space scored by a
    fleet of REAL OS worker processes over the TCP netbroker, with the
    network-served handoff store, a real SIGKILL at the busiest worker
    mid-peak, and the autoscale controller growing the fleet ahead of the
    forecast peak and draining it after. Pins effectively-once scoring
    (zero lost / conflicting-scored, gap-free offsets, state + scores
    equal to a single-process oracle), returncode -9 from the kill,
    bounded consistent-hash movement, and a digest-identical second
    fresh run (host-timing fields excluded). Prints the full summary,
    then a compact (<2 KB) verdict as the FINAL stdout line. Exit 1
    unless every check passed. Pure host arithmetic in the workers — no device needed, but REAL processes, REAL TCP,
    REAL signals."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.cluster.elastic_drill import (
        ElasticDrillConfig,
        compact_elastic_summary,
        run_elastic_drill,
    )

    cfg = ElasticDrillConfig.fast() if args.fast else ElasticDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay_check=not args.no_replay)
    summary = run_elastic_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_elastic_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_partition_drill(args: argparse.Namespace) -> int:
    """Deterministic split-brain partition drill (chaos/partition_drill
    .py): >= 4 real OS worker processes over the TCP netbroker while the
    link-fault layer (chaos/netfaults.py) degrades the network itself —
    an asymmetric partition at the busiest worker (deaf to the
    coordinator, data path alive: evicted by session expiry, fenced at
    the broker's producer-generation seam, its post-fence produces
    REFUSED and counted), a slow link under load (healthy-vs-window p99
    reported as degraded_network), and a full partition that heals
    (bounded backoff, fenced discovery, fresh rejoin). Pins zero lost /
    conflicting-scored vs a single-process oracle, gap-free offsets,
    state equality, detection inside the session-timeout bound, both
    rejoins with no double-ownership interval, bounded byte-identical
    duplicates, and a digest-identical second fresh run. Prints the full
    summary, then a compact (<2 KB) verdict as the FINAL stdout line.
    Exit 1 unless every check passed. Pure host
    arithmetic in the workers — no device needed, but REAL processes,
    REAL TCP, REAL link faults."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.chaos.partition_drill import (
        PartitionDrillConfig,
        compact_partition_summary,
        run_partition_drill,
    )

    cfg = (PartitionDrillConfig.fast() if args.fast
           else PartitionDrillConfig())
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay_check=not args.no_replay,
                      **({"n_workers": args.workers} if args.workers
                         else {}))
    summary = run_partition_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_partition_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_obs_drill(args: argparse.Namespace) -> int:
    """Deterministic distributed observability drill (obs/obs_drill.py):
    one seeded timeline over >= 2 real OS worker processes with the
    fleet tracing plane live — every produced record carries a wire
    trace carrier the consuming worker re-hydrates, so stitched traces
    span ingest -> broker transit (producer stamp vs consume stamp) ->
    the worker's stages -> remote graph-fetch child spans to the OTHER
    worker's fetch server. Pins: carrier losses inside the netfault
    window counted EXACTLY (fresh local roots, never a gap or wedge),
    fleet metric sums exactly equal the per-worker bye counters, the
    slow-worker injection attributed to that worker's device_wait, one
    named Chrome-trace track per process with a broker-transit flow
    arrow per stitched trace, traced-vs-untraced makespan ratio under
    the pinned bound, and a digest-identical second fresh run. Prints
    the full summary, then a compact (<2 KB) verdict as the FINAL
    stdout line. Exit 1 unless every check
    passed."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.obs.obs_drill import (
        ObsDrillConfig,
        compact_obs_summary,
        run_obs_drill,
    )

    cfg = ObsDrillConfig.fast() if args.fast else ObsDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay_check=not args.no_replay,
                      rings_out=getattr(args, "rings_out", "") or "",
                      **({"n_workers": args.workers} if args.workers
                         else {}))
    summary = run_obs_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_obs_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_graph_drill(args: argparse.Namespace) -> int:
    """Deterministic entity-graph drill (graph/drill.py): the typed
    user/device/merchant/IP graph maintained from the transaction flow,
    serve-time two-hop neighborhood sampling through the columnar
    assemble path feeding the GNN branch, and cross-partition neighbor
    fetch over TCP — driven end-to-end across >= 2 REAL partition-scoped
    workers with a coordinated FraudRing straddling the shards. Pins
    ring-phase AUC lift of the graph-on blend over the trees-only
    incumbent on the drill's truth ledger, remote fetches demonstrably
    exercised, graceful degrade (zero lost/errored scores) under an
    injected netfault partition window, columnar == serial bit-exact
    with graph sampling on, and a digest-identical fresh second run.
    Prints the full summary, then a compact (<2 KB) verdict as the FINAL
    stdout line. Exit 1 unless every check passed.
    Real fused-program scoring on whatever backend is live (CPU-sized by
    default), REAL TCP between the workers' graph-fetch planes."""
    import dataclasses as _dc

    from realtime_fraud_detection_tpu.graph.drill import (
        GraphDrillConfig,
        compact_graph_summary,
        run_graph_drill,
    )

    cfg = GraphDrillConfig.fast() if args.fast else GraphDrillConfig()
    cfg = _dc.replace(cfg, seed=args.seed,
                      replay_check=not args.no_replay,
                      **({"n_workers": args.workers} if args.workers
                         else {}))
    summary = run_graph_drill(cfg)
    print(json.dumps(summary), flush=True)
    print(json.dumps(compact_graph_summary(summary),
                     separators=(",", ":")), flush=True)
    return 0 if summary["passed"] else 1


def cmd_lint(args: argparse.Namespace) -> int:
    """Run the repo-native invariant checker (analysis/lint.py) — or, with
    --lockwatch, the dynamic lock-order watcher under all thirteen
    deterministic drills (analysis/lockwatch.py). Exit 0 only when clean.

    The static rules (wall-clock, d2h, metrics, lock-order, determinism,
    pragma-hygiene) encode THIS repo's invariants — virtual-clock
    determinism, the pre-pull-safe device-timing discipline, honest
    counter-delta Prometheus mirrors, score-lock discipline — and are
    enforced in tier-1 (tests/test_analysis.py), so `rtfd lint` on a
    committed tree must print `clean`.
    """
    if getattr(args, "lockwatch_run", ""):
        # child mode (one drill, one process): emits a single JSON line.
        # pool-drill / chaos-drill / mesh-drill children are launched with
        # the virtual 8-device host platform env by the parent below.
        from realtime_fraud_detection_tpu.analysis.lockwatch import (
            run_drill_watched,
        )

        rep = run_drill_watched(args.lockwatch_run, fast=args.fast,
                                seed=args.seed)
        print(json.dumps(rep), flush=True)
        return 0 if (rep["lockwatch"]["ok"] and rep["drill_passed"]) else 1
    if args.lockwatch:
        return _lockwatch_all_drills(args)
    from realtime_fraud_detection_tpu.analysis.lint import run_lint

    code, out = run_lint(args.paths or None, fmt=args.format)
    print(out)
    return code


def _lockwatch_all_drills(args: argparse.Namespace) -> int:
    """Parent mode: one child process per drill (pool-drill needs the
    virtual multi-device platform set before jax initializes; the others
    inherit the session platform). Prints a per-drill table plus a final
    compact JSON verdict line."""
    import subprocess

    from realtime_fraud_detection_tpu.analysis.lockwatch import (
        LOCKWATCH_DRILLS,
    )

    results: Dict[str, Any] = {}
    ok = True
    for drill in LOCKWATCH_DRILLS:
        env = (virtual_cpu_env(8)
               if drill in ("pool-drill", "chaos-drill", "mesh-drill")
               else dict(os.environ))
        argv = [sys.executable, "-m", "realtime_fraud_detection_tpu",
                "lint", "--lockwatch-run", drill, "--seed", str(args.seed)]
        if args.fast:
            argv.append("--fast")
        print(f"[lockwatch] {drill} ...", file=sys.stderr, flush=True)
        rep: Dict[str, Any] = {}
        try:
            proc = subprocess.run(argv, env=env, timeout=540,
                                  capture_output=True, text=True)
        except subprocess.TimeoutExpired as e:
            # a hung drill is a failed drill, not a crashed parent: the
            # remaining drills still run and the final verdict line still
            # prints (callers parse it)
            rep = {"drill": drill, "drill_passed": False,
                   "lockwatch": {"ok": False,
                                 "error": f"timeout after {e.timeout}s"}}
        else:
            for line in reversed(proc.stdout.splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        rep = json.loads(line)
                        break
                    except json.JSONDecodeError:
                        continue
            if not rep:
                rep = {"drill": drill, "drill_passed": False,
                       "lockwatch": {"ok": False,
                                     "error": (proc.stderr or "")[-500:]}}
        lw = rep.get("lockwatch") or {}
        results[drill] = {
            "drill_passed": rep.get("drill_passed"),
            "ok": lw.get("ok"),
            "locks": len(lw.get("locks") or ()),
            "acquisitions": lw.get("acquisitions"),
            "edges": len(lw.get("edges") or ()),
            "cycles": lw.get("cycles") or [],
            "violations": lw.get("violations") or [],
            "warnings": len(lw.get("warnings") or ()),
            "max_hold_ms": (max(lw.get("max_hold_ms", {}).values())
                            if lw.get("max_hold_ms") else 0.0),
        }
        ok = ok and bool(lw.get("ok")) and bool(rep.get("drill_passed"))
        if results[drill]["ok"] and rep.get("drill_passed"):
            status = "clean"
        elif results[drill]["ok"]:
            status = "DRILL FAILED (locks clean)"
        else:
            status = "VIOLATIONS"
        print(f"[lockwatch] {drill}: {status} "
              f"(locks={results[drill]['locks']} "
              f"acq={results[drill]['acquisitions']} "
              f"edges={results[drill]['edges']} "
              f"max_hold={results[drill]['max_hold_ms']}ms)",
              file=sys.stderr, flush=True)
    print(json.dumps({"lockwatch": results, "passed": ok},
                     separators=(",", ":")), flush=True)
    return 0 if ok else 1


def cmd_health_check(args: argparse.Namespace) -> int:
    """Probe a running scoring service (health-check.sh analog)."""
    import urllib.error
    import urllib.request

    url = args.url.rstrip("/") + "/health"
    try:
        with urllib.request.urlopen(url, timeout=args.timeout) as resp:
            body = json.loads(resp.read())
    except (urllib.error.URLError, OSError, json.JSONDecodeError) as e:
        print(json.dumps({"healthy": False, "error": str(e)}))
        return 1
    healthy = body.get("status") == "healthy"
    print(json.dumps({"healthy": healthy, **body}))
    return 0 if healthy else 1


def cmd_topics(args: argparse.Namespace) -> int:
    """Print the topic contract; with --broker --create, materialize it on
    a running broker (create-topics.sh:101-160 analog)."""
    from realtime_fraud_detection_tpu.stream.topics import TOPIC_SPECS

    broker = None
    if getattr(args, "create", False):
        if not args.broker:
            print("--create requires --broker host:port", file=sys.stderr)
            return 2
        from realtime_fraud_detection_tpu.stream.netbroker import (
            NetBrokerClient,
        )

        host, _, port = args.broker.rpartition(":")
        broker = NetBrokerClient(host=host or "127.0.0.1", port=int(port))
    for t in TOPIC_SPECS:
        flag = " compacted" if t.compacted else ""
        if broker is not None:
            broker.create_topic(t.name, t.partitions)
            print(f"created {t.name:28s} partitions={t.partitions}{flag}")
        else:
            print(f"{t.name:28s} partitions={t.partitions}{flag}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="realtime_fraud_detection_tpu",
        description="TPU-native realtime fraud detection framework")
    sub = p.add_subparsers(dest="command", required=True)

    sp = sub.add_parser("simulate", help="generate transaction JSON lines")
    _add_sim_args(sp)
    sp.add_argument("--count", type=int, default=1000)
    sp.add_argument("--output", default="-")
    sp.add_argument("--broker", default="",
                    help="produce to a broker (host:port, or comma list) at ~tps instead "
                         "of writing JSON lines")
    sp.set_defaults(fn=cmd_simulate)

    sp = sub.add_parser("run-job", help="run the streaming scoring job")
    _add_sim_args(sp)
    sp.add_argument("--count", type=int, default=10_000,
                    help="self-generate this many txns; 0 = consume-only "
                         "from --broker")
    sp.add_argument("--duration", type=float, default=0.0,
                    help="consume-only runtime seconds (0 = forever)")
    sp.add_argument("--broker", default="",
                    help="external broker host:port, or a comma list for the replicated cluster (default: in-memory)")
    sp.add_argument("--state", default="",
                    help="shared state server host:port (RESP)")
    sp.add_argument("--batch", type=int, default=256)
    sp.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight microbatches (3 overlaps the result "
                         "transfer with a full batch period; see "
                         "JobConfig.pipeline_depth for the state-staleness "
                         "tradeoff)")
    sp.add_argument("--analytics", action="store_true",
                    help="attach the windowed-analytics stage")
    sp.add_argument("--enrichment", action="store_true",
                    help="blend the 6-category feature score into the "
                         "enriched output (FeatureEnrichmentProcessor)")
    sp.add_argument("--checkpoint-dir", default="",
                    help="save params+state checkpoints per chunk")
    sp.add_argument("--metadata-db", default="",
                    help="SQLite path for durable job/checkpoint metadata")
    sp.add_argument("--qos", action="store_true",
                    help="enable the deadline-aware QoS plane (admission + "
                         "degradation ladder + latency budgets)")
    sp.add_argument("--qos-budget-ms", type=float, default=20.0,
                    help="per-transaction latency budget")
    sp.add_argument("--qos-rate", type=float, default=0.0,
                    help="admission token rate in txn/s (0 = unlimited)")
    sp.add_argument("--overlap-assembly", action="store_true",
                    help="background host-assembly stage: assemble batch "
                         "N+1 while batch N runs on device (scoring/"
                         "host_pipeline.py; see JobConfig.overlap_assembly "
                         "for the staleness tradeoff)")
    sp.add_argument("--device-pool", action="store_true",
                    help="replicate the model onto every addressable "
                         "device and dispatch microbatches round-robin "
                         "across per-device in-flight queues "
                         "(scoring/device_pool.py)")
    sp.add_argument("--inflight-depth", type=int, default=2,
                    help="per-replica in-flight batches for --device-pool "
                         "(>=2 keeps each device's compute back-to-back)")
    sp.add_argument("--feedback", action="store_true",
                    help="enable the continuous-learning plane: delayed "
                         "labels -> prequential metrics -> drift-gated "
                         "retrain-and-promote (feedback/)")
    sp.add_argument("--feedback-delay-scale", type=float, default=1e-4,
                    help="compresses the chargeback label-delay "
                         "distribution (1.0 = realistic days)")
    sp.add_argument("--trace", action="store_true",
                    help="enable the per-transaction tracing plane "
                         "(obs/tracing.py): flight recorder, latency "
                         "breakdown, SLO burn rate in the summary")
    sp.add_argument("--autotune", action="store_true",
                    help="self-tuning host pipeline (tuning/): arrival-"
                         "aware just-in-time batch closing + online "
                         "config tuner replace the fixed assembly "
                         "deadline")
    sp.add_argument("--quant", action="store_true",
                    help="quantized scoring plane (models/quant.py): "
                         "weight-only int8 BERT + GEMM-form tree kernels "
                         "(the rtfd quant-drill gated configuration)")
    sp.add_argument("--kernels", action="store_true",
                    help="Pallas kernel plane (ops/): fused dequant-"
                         "matmul + fused score-and-blend epilogue + flash "
                         "attention (the rtfd kernel-drill gated "
                         "configuration)")
    sp.set_defaults(fn=cmd_run_job)

    sp = sub.add_parser("serve", help="run the scoring HTTP service")
    sp.add_argument("--host", default="")
    sp.add_argument("--port", type=int, default=None)
    sp.add_argument("--state", default="",
                    help="shared state server host:port (RESP); also "
                         "honors RTFD_STATE_ADDR")
    sp.add_argument("--config", default="", help="JSON config file")
    sp.add_argument("--checkpoint-dir", default="",
                    help="restore model params (e.g. from `train`) at startup")
    sp.add_argument("--quality-artifact", default="",
                    help="deploy the measured blend from a quality-eval "
                         "JSON (e.g. QUALITY_r05.json): enabled branches "
                         "+ weights become the artifact's selected_blend")
    sp.add_argument("--qos", action="store_true",
                    help="enable the deadline-aware QoS plane (also "
                         "toggleable at runtime via POST /qos)")
    sp.add_argument("--qos-budget-ms", type=float, default=0.0,
                    help="per-transaction latency budget (0 = default)")
    sp.add_argument("--qos-rate", type=float, default=0.0,
                    help="admission token rate in txn/s (0 = unlimited)")
    sp.add_argument("--overlap-assembly", action="store_true",
                    help="two-phase pipelined microbatcher: dispatch batch "
                         "N+1 while batch N waits on the device "
                         "(serving.overlap_assembly)")
    sp.add_argument("--device-pool", action="store_true",
                    help="replicated multi-device scoring pool "
                         "(serving.device_pool; implies the two-phase "
                         "pipelined microbatcher)")
    sp.add_argument("--inflight-depth", type=int, default=None,
                    help="per-replica in-flight batches for --device-pool "
                         "(default: serving.inflight_depth, 2)")
    sp.add_argument("--allow-arch-mismatch", action="store_true",
                    help="combine a checkpoint and quality artifact even "
                         "when their recorded text-encoder architectures "
                         "differ, and restore a checkpoint whose recorded "
                         "quantization mode crosses this server's quant "
                         "config (both refused by default)")
    sp.add_argument("--quant", action="store_true",
                    help="quantized scoring plane (models/quant.py): "
                         "weight-only int8 BERT + GEMM-form tree kernels "
                         "(the rtfd quant-drill gated configuration)")
    sp.add_argument("--kernels", action="store_true",
                    help="Pallas kernel plane (ops/): fused dequant-"
                         "matmul + fused score-and-blend epilogue + flash "
                         "attention (the rtfd kernel-drill gated "
                         "configuration)")
    sp.add_argument("--trace", action="store_true",
                    help="enable the per-transaction tracing plane: "
                         "GET /latency/breakdown, GET /slo, trace_* "
                         "Prometheus series")
    sp.add_argument("--autotune", action="store_true",
                    help="self-tuning host pipeline (tuning/): the "
                         "request microbatcher closes just-in-time "
                         "against the arrival forecast; GET /autotune, "
                         "autotune_* Prometheus series")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("train", help="train tree models on synthetic data")
    _add_sim_args(sp)
    sp.add_argument("--rows", type=int, default=10_000,
                    help="synthetic rows (model_trainer.py:123)")
    sp.add_argument("--trees", type=int, default=100)
    sp.add_argument("--neural", action="store_true",
                    help="also train the LSTM/GNN/BERT branches")
    sp.add_argument("--out", default="./checkpoints")
    sp.set_defaults(fn=cmd_train)

    sp = sub.add_parser("validate",
                        help="quality-gate a checkpoint on a fresh stream")
    _add_sim_args(sp)
    sp.add_argument("--checkpoint-dir", required=True)
    sp.add_argument("--step", type=int, default=None)
    sp.add_argument("--rows", type=int, default=4096)
    sp.add_argument("--min-auc", type=float, default=0.80)
    sp.add_argument("--metrics-out", default=None,
                    help="write a Prometheus textfile here")
    sp.set_defaults(fn=cmd_validate)

    sp = sub.add_parser("broker", help="run the durable log broker (TCP)")
    sp.add_argument("--host", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=9092)
    sp.add_argument("--log-dir", default="",
                    help="write-ahead segment dir (empty = in-memory only)")
    sp.add_argument("--role", choices=("primary", "replica"),
                    default="primary",
                    help="replica = read-only standby until promoted")
    sp.add_argument("--min-isr", type=int, default=1,
                    help="in-sync copies (self included) a produce must "
                         "reach before the ack (create-topics.sh minISR=2 "
                         "analog)")
    sp.add_argument("--replica", action="append", default=[],
                    metavar="HOST:PORT",
                    help="attach a running replica server (repeatable); "
                         "each is caught up then joins the ISR")
    sp.set_defaults(fn=cmd_broker)

    import dataclasses as _dcs
    from types import SimpleNamespace as _NS

    from realtime_fraud_detection_tpu.training.blend_eval import (
        BlendEvalConfig as _BLEND_DEFAULTS_CLS,
    )

    # read field defaults WITHOUT instantiating (the bert default factory
    # would pull jax into every CLI invocation's parser build)
    _BLEND_DEFAULTS = _NS(**{
        f.name: f.default for f in _dcs.fields(_BLEND_DEFAULTS_CLS)
        if f.default is not _dcs.MISSING
    })
    sp = sub.add_parser("quality-eval",
                        help="run the blend-selection quality protocol")
    sp.add_argument("--output", default="",
                    help="write the evidence JSON here (default stdout)")
    sp.add_argument("--seed", type=int, default=3)
    # defaults mirror BlendEvalConfig exactly — the CLI and the Python
    # entry must make identical admission decisions
    sp.add_argument("--train-batches", type=int,
                    default=_BLEND_DEFAULTS.train_batches)
    sp.add_argument("--val-batches", type=int,
                    default=_BLEND_DEFAULTS.val_batches)
    sp.add_argument("--test-batches", type=int,
                    default=_BLEND_DEFAULTS.test_batches)
    sp.add_argument("--checkpoint-dir", default="",
                    help="also save the trained+calibrated branches as a "
                         "serving checkpoint (deploy with serve "
                         "--checkpoint-dir + --quality-artifact)")
    sp.set_defaults(fn=cmd_quality_eval)

    sp = sub.add_parser("alert-router",
                        help="fan fraud alerts out to notification receivers")
    sp.add_argument("--broker", default="127.0.0.1:9092",
                    help="broker host:port to consume fraud-alerts from")
    sp.add_argument("--webhook", default="",
                    help="Alertmanager /api/v2/alerts URL "
                         "(empty = JSON lines on stdout)")
    sp.add_argument("--group", default="alert-router",
                    help="consumer group (offset checkpointing)")
    sp.add_argument("--once", action="store_true",
                    help="drain the topic and exit (CronJob/test mode)")
    sp.add_argument("--poll-interval", type=float, default=1.0)
    sp.set_defaults(fn=cmd_alert_router)

    sp = sub.add_parser("cluster-worker",
                        help="run one partition-scoped fleet worker "
                             "process (spawned by the elastic cluster "
                             "coordinator)")
    sp.add_argument("--spec", required=True,
                    help="JSON worker spec from the coordinator "
                         "(broker/handoff addresses, worker id, group, "
                         "partition count, batch/cost knobs)")
    sp.set_defaults(fn=cmd_cluster_worker)

    sp = sub.add_parser("state-server",
                        help="run the shared state server (Redis protocol)")
    sp.add_argument("--host", default="0.0.0.0")
    sp.add_argument("--port", type=int, default=6379)
    sp.add_argument("--maxmemory", type=int, default=1 << 30,
                    help="eviction threshold in bytes (0 = unlimited; "
                         "default 1 GiB like the reference redis-master.conf)")
    sp.add_argument("--policy", default="allkeys-lru",
                    choices=["allkeys-lru", "noeviction"])
    sp.add_argument("--aof", default="",
                    help="append-only persistence file (empty = volatile)")
    sp.add_argument("--replica-of", default="",
                    help="host:port of the primary to replicate from "
                         "(read-only replica; promote by restarting without)")
    sp.set_defaults(fn=cmd_state_server)

    sp = sub.add_parser("qos-drill",
                        help="deterministic QoS overload demo "
                             "(virtual clock, real stream path)")
    sp.add_argument("--multiplier", type=float, default=2.0,
                    help="offered load as a multiple of the sustainable "
                         "rate")
    sp.add_argument("--overload-s", type=float, default=1.5,
                    help="virtual seconds of overload")
    sp.add_argument("--recovery-s", type=float, default=1.5,
                    help="virtual seconds of post-overload trickle")
    sp.add_argument("--batch", type=int, default=64)
    sp.add_argument("--budget-ms", type=float, default=20.0)
    sp.add_argument("--high-frac", type=float, default=0.2,
                    help="fraction of traffic in the high (never-shed) "
                         "class")
    sp.add_argument("--low-frac", type=float, default=0.5,
                    help="fraction of traffic in the low (sheds-first) "
                         "class")
    sp.add_argument("--seed", type=int, default=7)
    sp.set_defaults(fn=cmd_qos_drill)

    sp = sub.add_parser("feedback-drill",
                        help="deterministic closed-loop continuous-"
                             "learning demo (virtual clock, real "
                             "retraining)")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--seed", type=int, default=5)
    sp.add_argument("--drift-rate", type=float, default=0.08,
                    help="fraction of the stream turned into the drifted "
                         "fraud pattern")
    sp.set_defaults(fn=cmd_feedback_drill)

    sp = sub.add_parser("trace-drill",
                        help="deterministic tracing drill (virtual "
                             "clock, injected slow stage, SLO burn + "
                             "overhead pins)")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--seed", type=int, default=7)
    sp.set_defaults(fn=cmd_trace_drill)

    sp = sub.add_parser("autotune-drill",
                        help="deterministic self-tuning drill (virtual "
                             "clock, diurnal+burst load, JIT controller "
                             "vs a pinned static-config grid)")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--seed", type=int, default=7)
    sp.set_defaults(fn=cmd_autotune_drill)

    sp = sub.add_parser("quant-drill",
                        help="deterministic quantization drill (score-"
                             "delta oracle): int8 BERT + GEMM-form tree "
                             "kernels vs the f32 fused program — "
                             "divergence below calibration noise, zero "
                             "decision flips, AUC unchanged, bit-"
                             "identical replay")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--seed", type=int, default=11)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the bit-identical second run (the "
                         "replay gate is waived)")
    sp.set_defaults(fn=cmd_quant_drill)

    sp = sub.add_parser("kernel-drill",
                        help="deterministic kernel drill (parity oracle): "
                             "the Pallas kernel plane vs the stock XLA "
                             "lowering — divergence below calibration "
                             "noise, zero decision flips, exact masked-"
                             "blend equality at every QoS rung, per-"
                             "kernel interpret-vs-reference parity, bit-"
                             "identical replay")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--seed", type=int, default=13)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the bit-identical second run (the "
                         "replay gate is waived)")
    sp.set_defaults(fn=cmd_kernel_drill)

    sp = sub.add_parser("trace-export",
                        help="run a traced fake-Kafka job and export "
                             "Chrome-trace/Perfetto JSON")
    _add_sim_args(sp)
    sp.add_argument("--count", type=int, default=2048,
                    help="transactions to score through the traced job")
    sp.add_argument("--batch", type=int, default=128)
    sp.add_argument("--out", default="trace.json",
                    help="Chrome-trace JSON output path (open in "
                         "ui.perfetto.dev)")
    sp.add_argument("--merge", nargs="+", default=None, metavar="RING",
                    help="merge per-worker ring dumps ({worker, pid, "
                         "traces} JSON, e.g. from `obs-drill "
                         "--rings-out`) into one fleet trace instead of "
                         "capturing locally")
    sp.set_defaults(fn=cmd_trace_export)

    sp = sub.add_parser("pool-drill",
                        help="deterministic device-pool drill (virtual "
                             "8-device host platform, real pooled "
                             "scoring path)")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--devices", type=int, default=8,
                    help="virtual host-platform device count")
    sp.add_argument("--inflight-depth", type=int, default=2,
                    help="per-replica in-flight batches")
    sp.add_argument("--seed", type=int, default=7)
    sp.set_defaults(fn=cmd_pool_drill)

    sp = sub.add_parser("mesh-drill",
                        help="deterministic mesh-sharding drill (virtual "
                             "8-device host platform, real GSPMD "
                             "data x model serving path): bit-equality "
                             "per branch placement, hot swap, donation, "
                             "per-chip param bytes")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--devices", type=int, default=8,
                    help="virtual host-platform device count")
    sp.add_argument("--model-axis", type=int, default=2,
                    help="model-parallel axis size per mesh replica")
    sp.add_argument("--inflight-depth", type=int, default=2,
                    help="in-flight programs per mesh replica")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second bit-identical pass")
    sp.set_defaults(fn=cmd_mesh_drill)

    sp = sub.add_parser("chaos-drill",
                        help="deterministic combined recovery drill: "
                             "flash crowd + broker outage + device faults "
                             "+ fraud ring on one virtual-clock timeline")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--devices", type=int, default=0,
                    help="virtual host-platform device count for the pool "
                         "(0 = the config's default: 4 full, 2 fast)")
    sp.add_argument("--seed", type=int, default=None,
                    help="timeline seed (default: chaos.seed from --config "
                         "if given, else 11)")
    sp.add_argument("--config", default="",
                    help="JSON config file; the chaos.* block reshapes the "
                         "fault timeline (outage/stall windows, flash "
                         "multipliers, ring shape)")
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second bit-identical replay run")
    sp.set_defaults(fn=cmd_chaos_drill)

    sp = sub.add_parser("shard-drill",
                        help="deterministic partition-parallel worker "
                             "drill: key-sharded state across >= 4 "
                             "workers, mid-stream worker kill, "
                             "checkpointed handoff, oracle state "
                             "equality")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--workers", type=int, default=0,
                    help="fleet size (0 = the config default, 4)")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second bit-identical replay run")
    sp.set_defaults(fn=cmd_shard_drill)

    sp = sub.add_parser("elastic-drill",
                        help="deterministic elastic-cluster drill: >= 8 "
                             "real OS worker processes over the TCP "
                             "netbroker, network handoff, autoscale "
                             "ahead of a diurnal peak, real SIGKILL "
                             "mid-peak, oracle state equality")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second fresh determinism run")
    sp.set_defaults(fn=cmd_elastic_drill)

    sp = sub.add_parser("partition-drill",
                        help="deterministic split-brain partition drill: "
                             ">= 4 real OS worker processes under link "
                             "chaos (asymmetric/slow/full partitions), "
                             "broker producer-generation fencing, "
                             "session-expiry eviction + fresh rejoin, "
                             "oracle state equality")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--workers", type=int, default=0,
                    help="fleet size (0 = the config default)")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second fresh determinism run")
    sp.set_defaults(fn=cmd_partition_drill)

    sp = sub.add_parser("obs-drill",
                        help="deterministic distributed observability "
                             "drill: >= 2 real OS worker processes with "
                             "cross-process trace carriers, fleet metric "
                             "aggregation pinned exact, slow-worker p99 "
                             "attribution, carrier loss counted under a "
                             "netfault window, merged Chrome-trace "
                             "export with broker-transit flow arrows")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--workers", type=int, default=0,
                    help="fleet size (0 = the config default)")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--rings-out", default="",
                    help="directory for per-worker flight-recorder ring "
                         "dumps (the `trace-export --merge` input)")
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second fresh determinism run")
    sp.set_defaults(fn=cmd_obs_drill)

    sp = sub.add_parser("graph-drill",
                        help="deterministic entity-graph drill: typed "
                             "user/device/merchant/IP graph + two-hop "
                             "sampling feeding the GNN branch across >= 2 "
                             "partition workers, cross-partition neighbor "
                             "fetch over TCP, netfault degrade window, "
                             "ring-phase AUC lift vs the trees-only "
                             "incumbent")
    sp.add_argument("--fast", action="store_true",
                    help="tier-1 sizes (the CI smoke configuration)")
    sp.add_argument("--workers", type=int, default=0,
                    help="fleet size (0 = the config default)")
    sp.add_argument("--seed", type=int, default=7)
    sp.add_argument("--no-replay", action="store_true",
                    help="skip the second fresh determinism run")
    sp.set_defaults(fn=cmd_graph_drill)

    sp = sub.add_parser("lint",
                        help="repo-native invariant checker (static rules "
                             "+ --lockwatch dynamic lock-order watcher)")
    sp.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the package tree)")
    sp.add_argument("--format", choices=("text", "json"), default="text")
    sp.add_argument("--lockwatch", action="store_true",
                    help="run the thirteen deterministic drills under the "
                         "instrumented lock watcher instead of the static "
                         "rules")
    sp.add_argument("--lockwatch-run", default="",
                    metavar="DRILL", help=argparse.SUPPRESS)  # child mode
    sp.add_argument("--fast", action="store_true",
                    help="drill fast configs (the CI smoke sizes)")
    sp.add_argument("--seed", type=int, default=7)
    sp.set_defaults(fn=cmd_lint)

    sp = sub.add_parser("health-check", help="probe a running service")
    sp.add_argument("--url", default="http://127.0.0.1:8000")
    sp.add_argument("--timeout", type=float, default=5.0)
    sp.set_defaults(fn=cmd_health_check)

    sp = sub.add_parser("topics", help="print the topic contract")
    sp.add_argument("--broker", default="",
                    help="broker host:port to create the topics on")
    sp.add_argument("--create", action="store_true",
                    help="materialize the contract on --broker")
    sp.set_defaults(fn=cmd_topics)
    return p


def configure_process_logging() -> None:
    """Structured logging for a CLI-launched process (reference
    logging_config.py is imported by each service entry point): LOG_LEVEL /
    LOG_FILE via the config env layer; with a log file, every JSON line is
    stamped with service_name. Called from the real process entry points
    only — library callers (and tests) keep their own logging config.
    Never fatal: a bad LOG_LEVEL must not take down --help."""
    import logging

    try:
        from realtime_fraud_detection_tpu.obs.logs import setup_logging
        from realtime_fraud_detection_tpu.utils.config import Config

        cfg = Config()
        setup_logging(level=cfg.monitoring.log_level,
                      json_file=cfg.monitoring.log_file or None,
                      service_name=cfg.service_name)
    except Exception as e:  # noqa: BLE001 — fall back, don't crash the CLI
        logging.basicConfig(level=logging.INFO)
        logging.getLogger(__name__).warning(
            "logging setup failed (%s); using basicConfig", e)


def entrypoint() -> int:
    """Console-script entry (pyproject [project.scripts]): identical
    behavior to ``python -m realtime_fraud_detection_tpu``."""
    configure_process_logging()
    return main()


def main(argv: Optional[list[str]] = None) -> int:
    from realtime_fraud_detection_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    args = build_parser().parse_args(argv)
    # before any command (or a child it spawns) compiles anything
    configure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    configure_process_logging()
    raise SystemExit(main())
