"""The control of ``correct`` (a) for ``laguna-s-2.1-s2048``, and how often
the program's routing differs from the reference's: ``olmoe_control.py``'s
pattern (its column comparison, rounding and flip share are used as they
stand) on Laguna's reference and Laguna's program pieces. For each seed, on
the cell's own weights and parity sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference (``configs/laguna_reference.py``);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection,
  core contraction, dense-MLP, routed- and shared-expert matmul rounded to
  float8 (e4m3), the router left in float32 — against the reference as it
  stands, column by column against the same ``parity_atol``. It has to come
  out NOT correct;
- ``reference_bf16``: the same with bfloat16 operands: what the stated
  precision alone costs, routing flips included, with no program in it;
- ``routing``: the share of (token, sparse layer) pairs whose top-10 set
  differs from the float32 reference's — for the program (its own hidden
  stream, from the public pieces of ``models/laguna.py``, jitted layer by
  layer on the scorer's device) and for the two lowered references — and
  ``held_share``: the share of the reference's chosen pairs whose expert
  this chip holds (0.25 under even routing).

    python3 benchmarks/tests/laguna_control.py --workload \
        laguna-s2048-remit-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_laguna_control.py``);
``--sound-only`` leaves the two lowered references out, ``--reference-only``
the program (NumPy against NumPy: the same on any machine, and the only form
the published widths at 2,048 positions take on a CPU, where the XLA core's
scores would be 9.7 GB a layer).
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(1, str(Path(__file__).resolve().parent))

from olmoe_control import _deltas, _flip_share, _rounded  # noqa: E402


def _reference_columns(reference, args, operand=None):
    """``score`` and the per-layer routing, with every matmul's operands but
    the router's passed through ``operand`` first."""
    plain = reference._matmul
    if operand is not None:
        reference._matmul = lambda x, w: plain(operand(x), operand(w))
    try:
        models, batch, params, valid, cfg = args
        trace = []
        reference.text_branch(models.bert, batch.token_ids, batch.token_mask,
                              cfg, trace=trace)
        return reference.score(*args), trace
    finally:
        reference._matmul = plain


def program_routing(scorer, batch):
    """Each sparse layer's chosen experts (sorted, ``[tokens, top_k]``, in
    the router's numbers) as the PROGRAM chooses them on its own hidden
    stream."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models import laguna, olmoe

    config = scorer.bert_config
    use_pallas = scorer.effective_use_pallas()
    interpret = scorer.kernel_static()["kernel_interpret"]
    ids, mask = jnp.asarray(batch.token_ids), jnp.asarray(batch.token_mask)
    lengths = jnp.sum(mask.astype(jnp.int32), axis=-1)
    kw = dict(use_pallas=use_pallas, kernel_interpret=interpret)

    def one_layer(layer, h, index):
        cos, sin = laguna.laguna_rope_tables(
            ids.shape[1], config.head_dim, config.rope_of(index))
        experts = None
        if config.mlp_layer_types[index] == laguna.SPARSE:
            after = laguna.laguna_attention(layer, h, mask, lengths, config,
                                            index, cos, sin, **kw)
            m = olmoe.rms_norm(after, layer["post_attention_layernorm"],
                               config.rms_norm_eps)
            experts = jnp.sort(laguna.laguna_route(
                layer, m.reshape(-1, m.shape[-1]), config)[0], axis=-1)
        h, _ = laguna.laguna_layer(layer, h, mask, lengths, config, index,
                                   cos, sin, **kw)
        return h, experts

    step = jax.jit(one_layer, static_argnums=2)
    params = scorer.models.bert
    h = params["embed_tokens"][ids].astype(jnp.float32)
    chosen = []
    for index, layer in enumerate(params["layers"]):
        h, experts = step(layer, h, index)
        if experts is not None:
            chosen.append(np.asarray(experts))
    return chosen


def held_share(trace, cfg):
    """Share of the chosen (token, expert) pairs whose expert this chip
    holds, over the sparse layers."""
    lo = cfg["expert_share"]["index"] * cfg["num_experts"]
    pairs = np.concatenate([t.reshape(-1) for t in trace])
    return float(((pairs >= lo) & (pairs < lo + cfg["num_experts"])).mean())


def readings(cell, seed, lowered=True, program=True):
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
    out = {}
    if program:
        out["sound"] = correct.parity(scorer, recs, cfg)
    batch = scorer.assemble(recs)
    host_models, host_batch = jax.device_get((models, batch))
    args = (host_models, host_batch, scorer.ensemble_params,
            scorer.effective_model_valid(), cfg)
    plain, trace = _reference_columns(reference, args)
    real = np.asarray(host_batch.token_mask, bool)
    out["routing"] = {"pairs": int(real.size * len(trace)),
                      "held_share": held_share(
                          [t[real.reshape(-1)] for t in trace], cfg)}
    if program:
        chosen = program_routing(scorer, batch)
        out["routing"].update(
            program_differs=_flip_share(chosen, trace),
            program_differs_real_tokens=_flip_share(chosen, trace, real))
    for name, dtype in (("reference_fp8", ml_dtypes.float8_e4m3fn),
                        ("reference_bf16", ml_dtypes.bfloat16)):
        if not lowered:
            break
        low, low_trace = _reference_columns(reference, args, _rounded(dtype))
        out[name] = dict(_deltas(low, plain, reference, cfg), rows=len(recs),
                         routing_differs=_flip_share(low_trace, trace, real))
    return out


def main(argv=None) -> int:
    from benchmarks.harness import runner, spec

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--sound-only", action="store_true")
    ap.add_argument("--reference-only", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if not args.cpu:
        runner.require_devices(int(cell["chips"]))
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **readings(
            cell, seed, lowered=not args.sound_only,
            program=not args.reference_only)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
