"""StreamJob e2e soak on the chip: the 6,250 txn/s/chip measurement.

Clear the per-chip share of the 50k-TPS north star (BASELINE.json;
50,000 / 8 chips = 6,250) with a MEASUREMENT through the production
``stream/job.py`` path, not arithmetic. This runner sweeps the levers the
round-4 analysis named — microbatch 512 vs 256, pipeline depth
2 vs 3, bf16 wire format, explanation assembly on/off — each as a
sustained ``run_for`` soak over a pre-filled backlog (the job never
starves; compile warmed outside the window), plus the decomposition
(scorer-direct device rate, host assemble-only rate) that shows WHERE the
e2e number comes from.

Varied-input methodology: every scored microbatch is freshly generated
simulator traffic — no repeated tensors for any cache layer to serve
(utils/timing.py rule 1); state (velocity/history/graph) evolves live.

Usage: python soak_tpu.py            # exits non-zero if JAX finds no TPU
Writes chiprun_out/soak[_quant][_mesh][_kern].json (the directory the chip
tool brings back) and prints one JSON line per config on stdout.
"""

from __future__ import annotations

import json
import os
import sys
import time


def run() -> None:
    import jax

    from realtime_fraud_detection_tpu.models.bert import BertConfig
    from realtime_fraud_detection_tpu.scoring import (
        FraudScorer,
        ScorerConfig,
    )
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )
    from realtime_fraud_detection_tpu.stream import (
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )
    from realtime_fraud_detection_tpu.stream import topics as T
    from realtime_fraud_detection_tpu.utils.chip import require_tpu
    from realtime_fraud_detection_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    configure_compile_cache()
    # RTFD_SOAK_SMOKE=1 is the tiny-size rehearsal of this script's control
    # flow and may run anywhere; the measurement itself demands the chip
    smoke = os.environ.get("RTFD_SOAK_SMOKE") == "1"
    if not smoke:
        require_tpu("soak_tpu.py")
    t0 = time.monotonic()

    def log(m):
        print(f"[soak +{time.monotonic() - t0:6.1f}s] {m}",
              file=sys.stderr, flush=True)

    dev0 = jax.devices()[0]
    out = {
        "device": {"platform": dev0.platform, "kind": dev0.device_kind,
                   "count": len(jax.devices())},
        "pass_line_txn_per_s_per_chip": 6250.0,
        "methodology": (
            "sustained StreamJob.run_for over a pre-filled backlog of "
            "freshly generated simulator traffic (varied inputs by "
            "construction, live state evolution); per-config compile "
            "warmed outside the timed window; in-memory broker so the "
            "measurement isolates assemble+device+fan-out+commit"),
        "configs": [],
    }
    log(f"device: {out['device']}")

    gen = TransactionGenerator(num_users=2000, num_merchants=500, seed=3)
    # --quant: every config serves the quantized scoring plane (weight-
    # only int8 BERT + GEMM-form tree kernels — the rtfd quant-drill
    # gated configuration), so two invocations give f32 and quantized
    # e2e rates. Calibration pulls the f32 weights host-side once per
    # scorer build, before any timed window.
    quant = "--quant" in sys.argv
    out["quantized"] = quant
    # --kernels: every config serves the Pallas kernel plane (fused
    # dequant-matmul + fused score-and-blend epilogue + flash attention —
    # the rtfd kernel-drill gated configuration), for kernel-on e2e
    # rates next to the f32/--quant ones. Composes with --quant: the
    # dequant kernel engages on the int8 form.
    # --mega: asks for the persistent megakernel, which FraudScorer
    # refuses on a TPU mesh (ops/megakernel.MEGA_TPU_REFUSAL) — the run
    # stops there rather than measure another program under its name.
    mega_on = "--mega" in sys.argv
    kernels_on = "--kernels" in sys.argv or mega_on
    out["kernels"] = kernels_on
    out["mega"] = mega_on
    # --mesh: every config scores through a MeshExecutor (GSPMD
    # data x model over all addressable chips, BERT branch stored sharded
    # over ``model`` — the rtfd mesh-drill gated path) instead of the
    # single-device program, for the mesh e2e rate next to the
    # f32/--quant ones. Composes with --quant: the sharded storage
    # carries the int8 form for free.
    mesh_on = "--mesh" in sys.argv
    mesh_model_axis = 0
    if mesh_on:
        n_dev = len(jax.devices())
        mesh_model_axis = 2 if n_dev > 1 and n_dev % 2 == 0 else 1
    out["mesh"] = ({"model_axis": mesh_model_axis} if mesh_on else None)

    def attach_mesh(scorer, depth):
        if not mesh_on:
            return
        from realtime_fraud_detection_tpu.scoring import MeshExecutor

        # the executor's slot count BECOMES the job's in-flight window
        # (StreamJob._inflight_depth follows an attached pool's
        # total_slots), so each sweep config's depth knob must flow into
        # the executor or the d2-vs-d3 comparison would silently measure
        # one window twice. A single-threaded dispatcher must also never
        # out-dispatch the slots — it would deadlock waiting for a
        # completion only it can perform — hence depth is passed, never
        # hardcoded below a caller's hand-rolled loop depth.
        MeshExecutor(scorer, model_axis=mesh_model_axis,
                     inflight_depth=depth,
                     shard_branches=(("bert_text",)
                                     if mesh_model_axis > 1 else ()))
    if smoke:
        # smoke: tiny arch + two configs — rehearses the measurement path
        # end-to-end so a bug never costs chip time
        from realtime_fraud_detection_tpu.models.bert import TINY_CONFIG

        bert_config = TINY_CONFIG
        sweep = [(64, 3, False, False), (64, 2, True, True)]
        soak_s = 5.0
    else:
        bert_config = BertConfig()        # full DistilBERT-base dims
        sweep = [
            # (max_batch, depth, bf16_wire, explanation)
            (512, 3, False, False),
            (512, 3, True, False),
            (512, 2, False, False),
            (256, 3, False, False),
            (512, 3, False, True),        # explanation cost on the record
        ]
        soak_s = 20.0
    for max_batch, depth, bf16, explain in sweep:
        label = (f"b{max_batch}-d{depth}"
                 f"{'-bf16' if bf16 else ''}{'-explain' if explain else ''}"
                 f"{'-quant' if quant else ''}{'-mesh' if mesh_on else ''}"
                 f"{'-kern' if kernels_on else ''}"
                 f"{'-mega' if mega_on else ''}")
        log(f"config {label}: building scorer")
        cfg = Config()
        cfg.ensemble.enable_explanation = explain
        if quant:
            from realtime_fraud_detection_tpu.utils.config import (
                QuantSettings,
            )

            cfg.quant = QuantSettings.full()
        if kernels_on:
            from realtime_fraud_detection_tpu.utils.config import (
                KernelSettings,
            )

            cfg.kernels = (KernelSettings.mega() if mega_on
                           else KernelSettings.full())
        scorer = FraudScorer(
            config=cfg,
            scorer_config=ScorerConfig(text_len=64, transfer_bf16=bf16),
            bert_config=bert_config)
        attach_mesh(scorer, depth)
        scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
        broker = InMemoryBroker()
        job = StreamJob(broker, scorer,
                        JobConfig(max_batch=max_batch, emit_features=False,
                                  pipeline_depth=depth))
        # backlog must exceed (max plausible rate x window) or the job
        # starves mid-window and the clamp — not the chip — sets the
        # number: 600k over 20 s caps measurement at 30k txn/s, ~3x the
        # best rate any per-chip config has shown
        log(f"config {label}: backlog + warm")
        backlog = 0
        for _ in range(1 if smoke else 24):
            backlog += broker.produce_batch(
                T.TRANSACTIONS, gen.generate_batch(500 if smoke else 25_000),
                key_fn=lambda r: str(r["user_id"]))
        scorer.score_batch(gen.generate_batch(max_batch))  # compile, unwarmed
        t1 = time.perf_counter()
        scored = job.run_for(soak_s)
        dt = time.perf_counter() - t1
        entry = {
            "label": label,
            "max_batch": max_batch,
            "pipeline_depth": depth,
            "transfer_bf16": bf16,
            "explanation": explain,
            "txn_per_s": round(scored / dt, 1),
            "scored": scored,
            "window_s": round(dt, 2),
            "batches": job.counters["batches"],
            "meets_6250": scored / dt >= 6250.0,
            # a drained backlog means the number is a floor set by supply,
            # not the chip — flagged so it can never be read as sustained
            "starved": scored >= int(0.95 * backlog),
        }
        out["configs"].append(entry)
        print(json.dumps(entry), flush=True)

    # ------------------------------------------------- decomposition
    # scorer-direct (no job loop) pipelined rate + host assemble-only rate
    log("decomposition: scorer-direct depth-3")
    cfg = Config()
    cfg.ensemble.enable_explanation = False
    if quant:
        from realtime_fraud_detection_tpu.utils.config import QuantSettings

        cfg.quant = QuantSettings.full()
    if kernels_on:
        from realtime_fraud_detection_tpu.utils.config import KernelSettings

        cfg.kernels = (KernelSettings.mega() if mega_on
                       else KernelSettings.full())
    scorer = FraudScorer(config=cfg, scorer_config=ScorerConfig(text_len=64),
                         bert_config=bert_config)
    attach_mesh(scorer, 4)   # >= the hand-rolled depth-3 loop below
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    batch_recs = [gen.generate_batch(64 if smoke else 512)
                  for _ in range(6 if smoke else 40)]
    scorer.score_batch(batch_recs[0])     # warm
    from collections import deque
    t1 = time.perf_counter()
    inflight: deque = deque()
    n = 0
    for recs in batch_recs:
        inflight.append(scorer.dispatch(recs))
        if len(inflight) >= 3:
            n += len(scorer.finalize(inflight.popleft()))
    while inflight:
        n += len(scorer.finalize(inflight.popleft()))
    dt = time.perf_counter() - t1
    direct = round(n / dt, 1)
    log("decomposition: assemble-only")
    t1 = time.perf_counter()
    m = 0
    for recs in batch_recs[:20]:
        scorer.assemble(recs)
        m += len(recs)
    assemble_rate = round(m / (time.perf_counter() - t1), 1)
    out["decomposition"] = {
        "scorer_direct_depth3_txn_per_s": direct,
        "host_assemble_only_txn_per_s": assemble_rate,
        "note": "e2e = job loop over (assemble || device || fan-out); "
                "scorer-direct bounds the device+assemble pipeline, "
                "assemble-only bounds the host stage alone",
    }
    print(json.dumps(out["decomposition"]), flush=True)

    best = max(out["configs"], key=lambda e: e["txn_per_s"])
    out["best"] = best
    here = os.path.dirname(os.path.abspath(__file__))
    suffix = (f"{'_quant' if quant else ''}{'_mesh' if mesh_on else ''}"
              f"{'_kern' if kernels_on else ''}")
    out_dir = os.path.join(here, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(
        out_dir, "soak_smoke.json" if smoke else f"soak{suffix}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    log(f"wrote {path}; best {best['label']} = {best['txn_per_s']} txn/s "
        f"({'PASS' if best['meets_6250'] else 'below'} 6,250/chip)")


if __name__ == "__main__":
    run()
