"""The control of ``correct`` (a) for ``olmoe-1b-7b-s128``, and how often the
program's routing differs from the reference's.

``control.py`` cannot take this configuration as it is (its lowered side is
the program's int8 DistilBERT path, which refuses an ``OlmoeConfig``, and it
patches ``ensemble_reference._linear``), so this is its pattern as a file of
its own. For each seed, on the cell's own weights and parity sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference (``configs/olmoe_reference.py``);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection
  and expert matmul rounded to float8 (e4m3), the router left in float32 —
  against the reference as it stands, column by column against the same
  ``parity_atol``. It has to come out NOT correct;
- ``reference_bf16``: the same with bfloat16 operands: what the stated
  precision alone costs, routing flips included, with no program in it;
- ``routing``: the share of (token, layer) pairs whose top-k SET differs
  from the float32 reference's — for the program (its own hidden stream, its
  own router, from the public pieces of ``models/olmoe.py``, jitted layer by
  layer on the scorer's device) and for the two lowered references.

    python3 benchmarks/tests/olmoe_control.py --workload \
        olmoe-s128-memo-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_olmoe_control.py``).
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


def _rounded(dtype):
    def cast(x):
        return np.asarray(x, np.float32).astype(dtype).astype(np.float32)
    return cast


def _reference_columns(reference, args, operand=None):
    """``score`` and the per-layer routing, with every projection and expert
    matmul's operands passed through ``operand`` first."""
    plain = reference._matmul
    if operand is not None:
        reference._matmul = lambda x, w: plain(operand(x), operand(w))
    try:
        models, batch, params, valid, cfg = args
        trace = []
        reference.text_branch(
            models.bert, batch.token_ids, batch.token_mask,
            n_heads=cfg["num_attention_heads"],
            top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
            theta=cfg["rope_theta"], trace=trace)
        return reference.score(*args), trace
    finally:
        reference._matmul = plain


def _deltas(lowered, plain, reference, cfg):
    deltas = {name: float(np.abs(lowered[name] - plain[name]).max())
              for name in ("fraud_probability", "confidence", "rule_score")}
    for j, name in enumerate(reference.BRANCHES):
        deltas[f"branch:{name}"] = float(np.abs(
            lowered["branches"][:, j] - plain["branches"][:, j]).max())
    over = [k for k, d in deltas.items() if not d <= cfg["parity_atol"][k]]
    return {"ok": not over, "max_delta": deltas, "over": over}


def program_routing(scorer, batch, cfg):
    """Each layer's chosen experts (sorted, ``[tokens, top_k]``) as the
    PROGRAM chooses them on its own hidden stream."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models import olmoe

    config = scorer.bert_config
    use_pallas = scorer.effective_use_pallas()
    interpret = scorer.kernel_static()["kernel_interpret"]
    ids, mask = jnp.asarray(batch.token_ids), jnp.asarray(batch.token_mask)
    cos, sin = olmoe.rope_tables(ids.shape[1], config.head_dim,
                                 config.rope_theta)

    @jax.jit
    def one_layer(layer, h):
        h = olmoe.olmoe_attention(layer, h, mask, config, cos, sin)
        x = olmoe.rms_norm(h, layer["post_attention_layernorm"],
                           config.rms_norm_eps).reshape(-1, h.shape[-1])
        experts, weights = olmoe.route(x, layer["router"],
                                       config.num_experts_per_tok)
        y, _ = olmoe.apply_experts(layer, x, experts, weights,
                                   use_pallas=use_pallas,
                                   kernel_interpret=interpret)
        return h + y.reshape(h.shape), jnp.sort(experts, axis=-1)

    params = scorer.models.bert
    h = params["embed_tokens"][ids].astype(jnp.float32)
    chosen = []
    for layer in params["layers"]:
        h, experts = one_layer(layer, h)
        chosen.append(np.asarray(experts))
    return chosen


def _flip_share(chosen, reference_trace, real=None):
    """Share of (token, layer) pairs whose top-k set is not the float32
    reference's; ``real`` restricts to real (unpadded) tokens."""
    differs = np.stack([(a != b).any(axis=-1)
                        for a, b in zip(chosen, reference_trace)])
    if real is not None:
        differs = differs[:, real.reshape(-1)]
    return float(differs.mean())


def readings(cell, seed):
    import jax
    import ml_dtypes

    from benchmarks.harness import correct, events, spec, system

    cfg = cell["config_data"]
    builder = spec.builder(cfg)
    reference = spec.reference(cfg["reference"])
    made = events.make_stream(cell, seed, 1.0)
    users = made.population.user_profiles()
    merchants = made.population.merchant_profiles()
    sample = made.pool.materialize(range(512), np.zeros(512), "q")
    models = builder.make_models(
        cfg, seed, system.event_features(sample, users, merchants))
    recs = made.pool.materialize(
        range(cfg["parity_rows"]), np.zeros(cfg["parity_rows"]), "p")
    scorer = builder.make_scorer(cfg, seed, models, users, merchants)
    out = {"sound": correct.parity(scorer, recs, cfg)}
    batch = scorer.assemble(recs)
    chosen = program_routing(scorer, batch, cfg)
    host_models, host_batch = jax.device_get((models, batch))
    args = (host_models, host_batch, scorer.ensemble_params,
            scorer.effective_model_valid(), cfg)
    plain, trace = _reference_columns(reference, args)
    real = np.asarray(host_batch.token_mask, bool)
    out["routing"] = {
        "pairs": int(real.size * len(trace)),
        "program_differs": _flip_share(chosen, trace),
        "program_differs_real_tokens": _flip_share(chosen, trace, real)}
    for name, dtype in (("reference_fp8", ml_dtypes.float8_e4m3fn),
                        ("reference_bf16", ml_dtypes.bfloat16)):
        lowered, low_trace = _reference_columns(reference, args,
                                                _rounded(dtype))
        out[name] = dict(_deltas(lowered, plain, reference, cfg),
                         rows=len(recs),
                         routing_differs=_flip_share(low_trace, trace))
    return out


def main(argv=None) -> int:
    from benchmarks.harness import runner, spec

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if not args.cpu:
        runner.require_devices(int(cell["chips"]))
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
