"""The control of ``correct`` (a) for ``joyai-llm-flash-s2048``, how often the
program's routing differs from the reference's, and how often the bias moves
the choice: ``laguna_control.py``'s pattern (``olmoe_control.py``'s column
comparison, rounding and flip share are used as they stand) on JoyAI's
reference and JoyAI's program pieces. For each seed, on the cell's own
weights and parity sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference (``configs/joyai_reference.py``);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection
  (the four latent ones and ``W_o``), both contractions of a score, the
  weighted sum, the dense MLP, every routed- and shared-expert matmul rounded
  to float8 (e4m3), the router left in float32 — against the reference as it
  stands, column by column against the same ``parity_atol``. It has to come
  out NOT correct;
- ``reference_fp8_experts``: float8 operands in the ROUTED experts' gate, up
  and down matmuls ALONE, everything else float32: the layer the cell is
  named for, by itself, also has to come out NOT correct (with the experts
  of a layer drawn independently a routing swap at a row's last token read
  like this, and with their output scaled down to hide the swap this read
  correct: ``models/joyai.init_joyai_params``);
- ``reference_tail_dropped``: a planted fault of the grouped matmuls in
  float32 — every expert's rows past its group's last whole 128 come out
  zero (~a sixth of the pairs at ~310 rows a group): NOT correct;
- ``reference_bf16``: the same as ``reference_fp8`` with bfloat16 operands:
  what the stated precision alone costs, routing flips included, with no
  program in it;
- ``routing``: ``bias_moves_choice``, the share of real (token, sparse
  layer) pairs whose chosen set (the top-8 of ``score + bias``) is NOT the
  top-8 of the scores alone, in the float32 reference — what makes a program
  that drops ``e_score_correction_bias`` another function; ``weights``, the
  smallest, mean and largest normalised weight of a chosen expert before the
  2.5; and the share of pairs whose chosen set differs from the float32
  reference's — for the program (its own hidden stream, from the public
  pieces of ``models/joyai.py``, jitted layer by layer on the scorer's
  device) and for the two lowered references.

    python3 benchmarks/tests/joyai_control.py --workload \
        joyai-s2048-remit-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_joyai_control.py``);
``--sound-only`` leaves the two lowered references out, ``--reference-only``
the program (NumPy against NumPy: the same on any machine, and the only form
the published widths at 2,048 positions take on a CPU, where the XLA core's
scores would be 4.3 GB a layer).
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(1, str(Path(__file__).resolve().parent))

from olmoe_control import _deltas, _flip_share, _rounded  # noqa: E402


TILE = 128


def _reference_columns(reference, args, operand=None, experts_only=False,
                       ragged_tail_dropped=False):
    """``score`` and the per-layer routing of the one pass it makes, with
    every matmul's operands but the router's passed through ``operand``
    first; ``experts_only``: those of the ROUTED experts' three matmuls
    alone (``reference._expert``), everything else float32;
    ``ragged_tail_dropped``: a planted fault in float32 — the rows of an
    expert's group past its last whole ``TILE`` come out zero, what a
    grouped kernel that forgot its ragged last tile would write."""
    plain, branch, expert = (reference._matmul, reference.text_branch,
                             reference._expert)
    trace = []

    def tail_dropped(x, *weights):
        y = expert(x, *weights)
        y[len(x) // TILE * TILE:] = 0.0
        return y

    def lowered(x, w):
        return plain(operand(x), operand(w))

    def lowered_expert(*a):
        reference._matmul = lowered
        try:
            return expert(*a)
        finally:
            reference._matmul = plain

    if ragged_tail_dropped:
        reference._expert = tail_dropped
    elif operand is not None and experts_only:
        reference._expert = lowered_expert
    elif operand is not None:
        reference._matmul = lowered
    reference.text_branch = lambda *a: branch(*a, trace=trace)
    try:
        return reference.score(*args), trace
    finally:
        reference._matmul, reference.text_branch, reference._expert = (
            plain, branch, expert)


def program_routing(scorer, batch):
    """Each sparse layer's chosen experts (sorted, ``[tokens, top_k]``) as
    the PROGRAM chooses them on its own hidden stream."""
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models import joyai, olmoe

    config = scorer.bert_config
    kw = dict(use_pallas=scorer.effective_use_pallas(),
              kernel_interpret=scorer.kernel_static()["kernel_interpret"])
    ids, mask = jnp.asarray(batch.token_ids), jnp.asarray(batch.token_mask)
    lengths = jnp.sum(mask.astype(jnp.int32), axis=-1)
    cos, sin = joyai.joyai_rope_tables(
        ids.shape[1], config.qk_rope_head_dim, config.rope_theta)

    def one_layer(layer, h, index):
        experts = None
        if index >= config.first_k_dense_replace:
            after = joyai.joyai_attention(layer, h, mask, lengths, config,
                                          cos, sin, **kw)
            m = olmoe.rms_norm(after, layer["post_attention_layernorm"],
                               config.rms_norm_eps)
            experts = jnp.sort(joyai.joyai_route(
                layer, m.reshape(-1, m.shape[-1]), config)[0], axis=-1)
        h, _ = joyai.joyai_layer(layer, h, mask, lengths, config, index,
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
    chosen_f32 = [t["chosen"] for t in trace]
    out["routing"] = {
        "pairs": int(real.size * len(trace)),
        "bias_moves_choice": _flip_share(
            chosen_f32, [t["unbiased"] for t in trace], real)}
    if program:
        chosen = program_routing(scorer, batch)
        out["routing"].update(
            program_differs=_flip_share(chosen, chosen_f32),
            program_differs_real_tokens=_flip_share(chosen, chosen_f32, real))
    fp8 = _rounded(ml_dtypes.float8_e4m3fn)
    for name, how in (
            ("reference_fp8", dict(operand=fp8)),
            ("reference_fp8_experts", dict(operand=fp8, experts_only=True)),
            ("reference_tail_dropped", dict(ragged_tail_dropped=True)),
            ("reference_bf16", dict(operand=_rounded(ml_dtypes.bfloat16)))):
        if not lowered:
            break
        low, low_trace = _reference_columns(reference, args, **how)
        out[name] = dict(
            _deltas(low, plain, reference, cfg), rows=len(recs),
            routing_differs=_flip_share(
                [t["chosen"] for t in low_trace], chosen_f32, real))
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
