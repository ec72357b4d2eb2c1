"""On-chip tuning sweep.

Complements bench.py (the fixed-format benchmark) with the sweeps needed
to CHOOSE the production constants (drive p99 under the 20 ms budget with
measured numbers):

1. The fused attention core vs plain XLA attention at seq 128/256/512,
   buckets 8 and 256 — the crossover ops.attention.flash_supported states.
2. score_fused bucket-size sweep (64..1024): per-bucket device latency and
   txn/s so BATCH_BUCKETS reflects the chip's actual knee.
3. Per-branch device timings at the chosen bucket — where the p99 goes.

Usage:  python tune_tpu.py            # exits non-zero if JAX finds no TPU
Output: one JSON line per sweep point on stdout (greppable), summary last.

Timing discipline: utils/timing.py (varied inputs, block_until_ready
inside the timed region, result pulls after it).
"""

from __future__ import annotations

import json
import sys

import numpy as np


def _emit(**kv) -> None:
    print(json.dumps(kv), flush=True)


def _time_blocked(fn, iters: int) -> dict:
    """Shared discipline (utils/timing.py): varied inputs, no d2h pulls."""
    from realtime_fraud_detection_tpu.utils.timing import time_blocked

    ms = np.asarray(time_blocked(fn, iters)) * 1e3
    return {"p50_ms": round(float(np.percentile(ms, 50)), 3),
            "p99_ms": round(float(np.percentile(ms, 99)), 3)}


def _throughput(fn, batch: int, iters: int) -> float:
    """Shared discipline (utils/timing.py): varied inputs, no d2h pulls."""
    from realtime_fraud_detection_tpu.utils.timing import (
        throughput_pipelined,
    )

    return throughput_pipelined(fn, batch, iters)


def main() -> int:
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.ensemble.combine import EnsembleParams
    from realtime_fraud_detection_tpu.models.bert import (
        BertConfig,
        bert_predict,
    )
    from realtime_fraud_detection_tpu.ops.attention import (
        attention_reference,
        flash_attention,
        flash_supported,
        merge_heads,
        split_heads,
    )
    from realtime_fraud_detection_tpu.scoring import (
        MODEL_NAMES,
        ScorerConfig,
        init_scoring_models,
        make_example_batch,
        score_fused,
    )
    from realtime_fraud_detection_tpu.utils.chip import require_tpu
    from realtime_fraud_detection_tpu.utils.compile_cache import (
        configure_compile_cache,
    )
    from realtime_fraud_detection_tpu.utils.config import Config

    configure_compile_cache()
    dev = require_tpu("tune_tpu.py")
    # --quant: sweep the QUANTIZED fused program (weight-only int8 BERT +
    # GEMM-form tree kernels — the rtfd quant-drill gated configuration)
    # instead of f32, so two invocations give both sweeps. Calibration pulls the f32 weights host-side ONCE, here
    # at startup, before any timed section.
    quant = "--quant" in sys.argv
    # --kernels: sweep the fused program with the Pallas kernel plane on
    # (fused dequant-matmul + fused score-and-blend epilogue + flash
    # attention — the rtfd kernel-drill gated configuration), for
    # kernel-on numbers next to the f32 / --quant sweeps.
    # --mega is refused: the persistent megakernel does not compile for
    # the TPU (ops/megakernel.MEGA_TPU_REFUSAL), so there is nothing to
    # sweep and no other program is measured under its name.
    if "--mega" in sys.argv:
        from realtime_fraud_detection_tpu.ops.megakernel import (
            MEGA_TPU_REFUSAL,
        )

        raise SystemExit(f"tune_tpu.py --mega: {MEGA_TPU_REFUSAL}")
    kernels = "--kernels" in sys.argv
    _emit(stage="start",
          device={"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())},
          quantized=quant, kernels=kernels)
    rng = np.random.default_rng(0)

    # 1 ------------------------------------- fused attention core vs XLA
    # The crossover behind ops.attention.flash_supported: the fused core
    # (one program a batch row, scores in VMEM) against the reference as
    # bert_layer runs each (bf16 [B, T, H*D] in and out; f32 with the head
    # split and merge). attn_verdict says whether the predicate still
    # agrees with this chip at each length the kernel lowers for.
    h, d = 12, 64
    ref = jax.jit(lambda q, k, v, m: merge_heads(attention_reference(
        split_heads(q, h), split_heads(k, h), split_heads(v, h), m)))
    for seq in (128, 256, 512):
        for b in (8, 256):
            k, v = (jnp.asarray(rng.standard_normal((b, seq, h * d)),
                                jnp.float32) for _ in range(2))
            qs = [jnp.asarray(rng.standard_normal((b, seq, h * d)),
                              jnp.float32) for _ in range(8)]
            kb, vb = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
            qb = [q.astype(jnp.bfloat16) for q in qs]
            mask = jnp.ones((b, seq), bool)
            base = _time_blocked(lambda i: ref(qs[i % 8], k, v, mask), 30)
            fused = _time_blocked(lambda i: flash_attention(
                qb[i % 8], kb, vb, mask, num_heads=h), 30)
            _emit(stage="attn_verdict", seq=seq, bucket=b,
                  xla_p50_ms=base["p50_ms"], flash_p50_ms=fused["p50_ms"],
                  flash_wins=bool(fused["p50_ms"] < base["p50_ms"]),
                  flash_supported=flash_supported(seq, d, h))

    # 2 ---------------------------------------------------- bucket sweep
    bert_config = BertConfig()
    sc = ScorerConfig(text_len=64)
    # stamp the exact text-encoder architecture this sweep measures, so a
    # sweep line is never combined with quality numbers from a different
    # model by assumption (bench.py records the same)
    _emit(stage="text_encoder", num_layers=bert_config.num_layers,
          hidden_size=bert_config.hidden_size,
          intermediate_size=bert_config.intermediate_size,
          num_heads=bert_config.num_heads,
          vocab_size=bert_config.vocab_size, text_len=sc.text_len)
    models = init_scoring_models(
        jax.random.PRNGKey(0), bert_config=bert_config,
        feature_dim=sc.feature_dim, node_dim=sc.node_dim)
    kernel = "gather"
    if quant:
        from realtime_fraud_detection_tpu.models.quant import (
            quantize_bert_params,
        )

        models = models.replace(
            bert=quantize_bert_params(jax.device_get(models.bert)))
        kernel = "gemm"
    # --mesh: sweep the GSPMD-SHARDED fused program — batch over ``data``,
    # BERT params STORED over ``model`` and re-gathered at the use seam
    # (scoring/mesh_executor.py semantics, the rtfd mesh-drill gated
    # path) — for mesh numbers next to the f32 and --quant sweeps.
    mesh = None
    if "--mesh" in sys.argv and len(jax.devices()) > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from realtime_fraud_detection_tpu.core.mesh import (
            MeshConfig,
            build_mesh,
        )
        from realtime_fraud_detection_tpu.parallel.layouts import (
            batch_shardings,
            branch_serving_specs,
            tree_specs_to_shardings,
        )

        model_axis = 2 if len(jax.devices()) % 2 == 0 else 1
        mesh = build_mesh(MeshConfig(model=model_axis))
        _emit(stage="mesh", data_axis=int(mesh.shape["data"]),
              model_axis=model_axis, shard_branches=["bert_text"])
        models = jax.device_put(models, tree_specs_to_shardings(
            mesh, branch_serving_specs(models, model_axis,
                                       ("bert_text",))))
        _rep = NamedSharding(mesh, P())

    def _put(x):
        """Stage a host array: sharded over the mesh data axis under
        --mesh, plain default-device put otherwise."""
        if mesh is None:
            return jax.device_put(x)
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.device_put(x, NamedSharding(
            mesh, P("data", *([None] * (np.ndim(x) - 1)))))

    # kernel-plane statics (rtfd kernel-drill gated): flash attention +
    # fused dequant-matmul (engages on the int8 params under --quant) +
    # fused epilogue, compiled for real on the chip (interpret=False)
    # Without the plane the attention core is what a FraudScorer on this
    # chip would pick (FraudScorer.effective_use_pallas): the predicate.
    kern = (dict(use_pallas=True, dequant_kernel="pallas",
                 epilogue_kernel="pallas") if kernels else
            dict(use_pallas=flash_supported(
                sc.text_len, bert_config.head_dim, bert_config.num_heads)))
    if mesh is None:
        models = jax.device_put(models)
        fused = jax.jit(lambda m, b, p, v: score_fused(
            m, b, p, v, bert_config=bert_config, with_model_preds=False,
            tree_kernel=kernel, iforest_kernel=kernel, **kern))
    else:
        fused = jax.jit(lambda m, b, p, v: score_fused(
            m.replace(bert=jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, _rep),
                m.bert)),
            b, p, v, bert_config=bert_config, with_model_preds=False,
            tree_kernel=kernel, iforest_kernel=kernel, **kern))
    params = EnsembleParams.from_config(Config(), list(MODEL_NAMES))
    valid = jnp.ones((len(MODEL_NAMES),), bool)
    for bucket in (64, 128, 256, 512, 1024):
        host_batch = make_example_batch(
            bucket, sc, rng=np.random.default_rng(bucket))
        # variants built from the HOST copy (utils/timing.py rule 2)
        feats = [_put(host_batch.features + np.float32(j))
                 for j in range(8)]
        batch = (jax.device_put(host_batch) if mesh is None
                 else jax.device_put(host_batch,
                                     batch_shardings(mesh, host_batch)))
        t = _time_blocked(
            lambda i: fused(models, batch.replace(features=feats[i % 8]),
                            params, valid), 40)
        tput = _throughput(
            lambda i: fused(models, batch.replace(features=feats[i % 8]),
                            params, valid), bucket, 40)
        _emit(stage="bucket", bucket=bucket, txn_per_s=round(tput, 1),
              ms_per_batch_pipelined=round(1e3 * bucket / tput, 3), **t)

    # 3 ------------------------------------------------ per-branch split
    from realtime_fraud_detection_tpu.models.isolation_forest import (
        iforest_predict,
    )
    from realtime_fraud_detection_tpu.models.lstm import lstm_logits
    from realtime_fraud_detection_tpu.models.trees import tree_ensemble_predict

    host_batch = make_example_batch(256, sc, rng=np.random.default_rng(1))
    feats = [_put(host_batch.features + np.float32(j))
             for j in range(8)]
    hists = [_put(host_batch.history + np.float32(j))
             for j in range(8)]
    toks = [_put(((host_batch.token_ids + j)
                  % bert_config.vocab_size).astype(np.int32))
            for j in range(8)]
    if mesh is None:
        batch = jax.device_put(host_batch)
    else:
        from realtime_fraud_detection_tpu.parallel.layouts import (
            batch_shardings,
        )

        batch = jax.device_put(host_batch,
                               batch_shardings(mesh, host_batch))
    jtree = jax.jit(lambda f: tree_ensemble_predict(models.trees, f,
                                                    kernel=kernel))
    jifo = jax.jit(lambda f: iforest_predict(models.iforest, f,
                                             kernel=kernel))
    jlstm = jax.jit(lambda h: jax.nn.sigmoid(lstm_logits(
        models.lstm, h, batch.history_len)))
    jbert = jax.jit(lambda t: bert_predict(
        models.bert, t, batch.token_mask, bert_config))
    branches = {
        "trees": (lambda i: jtree(feats[i % 8])),
        "iforest": (lambda i: jifo(feats[i % 8])),
        "lstm": (lambda i: jlstm(hists[i % 8])),
        "bert": (lambda i: jbert(toks[i % 8])),
    }
    for name, fn in branches.items():
        t = _time_blocked(fn, 30)
        tput = _throughput(fn, 256, 30)
        _emit(stage="branch", branch=name, batch=256,
              ms_per_batch_pipelined=round(256e3 / tput, 3), **t)
    return 0


if __name__ == "__main__":
    sys.exit(main())
