"""The control of ``correct`` (a) for ``zaya1-8b-s128``, and how often the
program's routing differs from the reference's: ``olmoe_control.py``'s
pattern (its column comparison, rounding and flip share are used as they
stand) on ZAYA1's reference and ZAYA1's program pieces. For each seed, on the
cell's own weights and parity sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference (``configs/zaya1_reference.py``);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection,
  per-head convolution tap and expert matmul rounded to float8 (e4m3), the
  router left in float32 — against the reference as it stands, column by
  column against the same ``parity_atol``. It has to come out NOT correct;
- ``reference_bf16``: the same with bfloat16 operands: what the stated
  precision alone costs, routing flips included, with no program in it;
- ``routing``: the share of (token, layer) pairs whose chosen expert differs
  from the float32 reference's — for the program (its own hidden stream and
  its own carried router state, from the public pieces of
  ``models/zaya.py``, jitted layer by layer on the scorer's device) and for
  the two lowered references. Top-1: a flip costs that token the whole
  expert's output in that layer.

    python3 benchmarks/tests/zaya1_control.py --workload \
        zaya1-s128-memo-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_zaya1_control.py``);
``--sound-only`` leaves the two lowered references out (a third of the
time: for many seeds on the chip).
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
    """``score`` and the per-layer routing, with every projection,
    convolution tap and expert matmul's operands passed through ``operand``
    first."""
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
    """Each layer's chosen expert (``[tokens, 1]``) as the PROGRAM chooses
    it on its own hidden stream and its own carried router state."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models import olmoe, zaya

    config = scorer.bert_config
    use_pallas = scorer.effective_use_pallas()
    interpret = scorer.kernel_static()["kernel_interpret"]
    ids, mask = jnp.asarray(batch.token_ids), jnp.asarray(batch.token_mask)
    cos, sin = olmoe.rope_tables(ids.shape[1], config.rotary_dim,
                                 config.rope_theta)
    slots = olmoe.token_slots(mask, None)

    def one_layer(layer, h, r):
        chosen = []

        def router(rows):
            experts, weights, state = zaya.zaya_route(layer, rows, r, config)
            chosen.append(experts)
            return experts, weights, state

        h = zaya.zaya_attention(layer, h, mask, config, cos, sin)
        x = olmoe.rms_norm(h, layer["post_attention_layernorm"],
                           config.rms_norm_eps).reshape(-1, h.shape[-1])
        y, _, state = olmoe.routed_block(
            layer, x, slots, router, use_pallas=use_pallas,
            kernel_interpret=interpret)
        return h + y.reshape(h.shape), state, chosen[0]

    first, later = jax.jit(lambda layer, h: one_layer(layer, h, None)), \
        jax.jit(one_layer)
    params = scorer.models.bert
    h = params["embed_tokens"][ids].astype(jnp.float32)
    r, chosen = None, []
    for layer in params["layers"]:
        h, r, experts = first(layer, h) if r is None else later(layer, h, r)
        chosen.append(np.asarray(experts))
    return chosen


def readings(cell, seed, lowered=True):
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
    chosen = program_routing(scorer, batch)
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
        if not lowered:
            break
        low, low_trace = _reference_columns(reference, args, _rounded(dtype))
        out[name] = dict(_deltas(low, plain, reference, cfg), rows=len(recs),
                         routing_differs=_flip_share(low_trace, trace))
    return out


def main(argv=None) -> int:
    from benchmarks.harness import runner, spec

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--sound-only", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if not args.cpu:
        runner.require_devices(int(cell["chips"]))
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **readings(
            cell, seed, lowered=not args.sound_only)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
