"""The control of ``correct`` (a) for ``qwen3-next-80b-a3b-s2048``, and how
much of the residual each layer's two halves add: ``nemotron3_control.py``'s
pattern (``olmoe_control.py``'s column comparison is used as it stands) on
Qwen3-Next's reference. For each seed, on the cell's own weights and parity
sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference
  (``configs/qwen3next_reference.py``), and under ``kernels`` what
  ``kernel_snapshot()`` counted for that launch (sites ``attention``,
  ``delta_scan``, ``expert_gate_up``, ``expert_combine``: ``dispatch`` on
  the chip, ``fallback`` elsewhere);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection
  of both mixers, both contractions of the attention core, the delta rule's
  products (``q``, ``k``, ``v`` and the state where ``S^T k`` and ``S^T q``
  read it) and all three matmuls of every routed and shared expert rounded
  to float8 (e4m3); the router stays float32, as the configuration states
  it — against the reference as it stands, column by column against the
  same ``parity_atol``. It has to come out NOT correct;
- ``reference_fp8_routed`` / ``reference_fp8_scan``: float8 in the ROUTED
  experts' matmuls alone, and in the SCAN's products alone: whether either
  mechanism by itself is in ``correct``'s sight;
- ``reference_bf16``: bfloat16 operands everywhere: what the stated
  precision alone costs, with no program in it;
- ``shares`` (``--shares``): in the float32 reference, at each row's last
  real token and a layer at a time (``[layers][rows]``): ``mixer`` the norm
  of the mixer's update over the norm of the residual it is added to,
  ``sparse`` the same of the layer's sparse half, ``routed`` the routed
  experts' part of the sparse half's update over the whole of it;
- ``tail`` (``--tail``): what makes a large reading, row by row: the text
  column of the program, of the reference and of the reference with
  bfloat16 operands (``program`` / ``plain`` / ``bf16``: ``[rows]``), and
  the HELD MASS of each row's last token's ten weights in the two
  references (``held_plain`` / ``held_bf16``: ``[layers][rows]``) — where
  they differ by more than rounding, that token swapped a held expert for
  an absent one at the tenth rank between the two precisions; and how near
  such a swap the float32 reference's own routing stands (``margin_plain``:
  the logit of the tenth rank less the eleventh's where one is held and the
  other absent, -1 where they do not straddle the share).

    python3 benchmarks/tests/qwen3next_control.py --workload \
        qwen3next-s2048-remit-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_qwen3next_control.py``; the
reference is ``jax.numpy`` and runs on whatever device the process has);
``--sound-only`` leaves the lowered references out, ``--reference-only``
the program.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(1, str(Path(__file__).resolve().parent))

from olmoe_control import _deltas  # noqa: E402

# the lowered references (float8 everywhere, in the routed experts alone,
# in the scan alone, bfloat16) and the seam that hands them to ``score``:
# Nemotron-3-Nano's control's, whose reference has the same ``SITES`` names
from nemotron3_control import LOWERED, _reference_columns  # noqa: E402


def _operand(name):
    """Round a ``jax.numpy`` array to float8 or bfloat16 and back, with a
    barrier between the two converts: where the pair feeds an elementwise
    product and not a ``dot`` (the reference's ``scan`` site: ``S^T k`` is a
    multiply and a sum) the TPU's compiler otherwise keeps the excess
    precision, and float8 in the scan alone read exactly 0.0 on the chip
    where it moves one ``L`` layer's output by 0.18 of 2.4 (PERF.md, PR
    54). A new function a call: the reference keeps its programs by the
    operand function's identity."""
    import jax
    import jax.numpy as jnp

    dtype = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[name]
    return lambda x: jax.lax.optimization_barrier(
        x.astype(dtype)).astype(jnp.float32)


def readings(cell, seed, lowered=True, program=True, shares=False,
             tail=False):
    import jax

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
        # which form each kernel site of that launch ran
        snap = scorer.kernel_snapshot()
        out["kernels"] = {k: snap[k] for k in ("dispatch", "fallback",
                                               "refused")}
    batch = scorer.assemble(recs)
    host_models, host_batch = jax.device_get((models, batch))
    args = (host_models, host_batch, scorer.ensemble_params,
            scorer.effective_model_valid(), cfg)
    plain, parts = _reference_columns(reference, args, parts=shares or tail)
    if shares:
        mixer, residual, sparse, routed = (parts[:, i] for i in range(4))
        out["shares"] = {
            "mixer": (mixer / residual).round(4).tolist(),
            "sparse": (sparse / residual).round(4).tolist(),
            "routed": (routed / sparse).round(4).tolist()}
    if shares or tail:
        out["tokens"] = np.count_nonzero(
            np.asarray(host_batch.token_mask), axis=1).tolist()
    if tail:
        from realtime_fraud_detection_tpu.scoring.pipeline import OUT_COLUMNS

        column = reference.BRANCHES.index("bert_text")
        pending = scorer.dispatch_assembled(batch, recs)
        served = np.asarray(pending.out)[:len(recs),
                                         len(OUT_COLUMNS) + column]
        scorer.finalize(pending)
        low, parts_low = _reference_columns(
            reference, args, operand=_operand("bf16"), parts=True)
        out["tail"] = {
            "program": served.astype(float).tolist(),
            "plain": plain["branches"][:len(recs), column].tolist(),
            "bf16": low["branches"][:len(recs), column].tolist(),
            "held_plain": parts[:, 4].round(5).tolist(),
            "held_bf16": parts_low[:, 4].round(5).tolist(),
            # (infinity where the tenth and eleventh ranks do not straddle
            # the share: JSON has none)
            "margin_plain": np.where(np.isfinite(parts[:, 5]),
                                     parts[:, 5], -1.0).round(5).tolist()}
    for name, precision, sites in LOWERED if lowered else ():
        low, _ = _reference_columns(
            reference, args, operand=_operand(precision),
            **({} if sites is None else {"sites": frozenset(sites)}))
        out[f"reference_{name}"] = dict(
            _deltas(low, plain, reference, cfg), rows=len(recs))
    return out


def main(argv=None) -> int:
    from benchmarks.harness import runner, spec

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--sound-only", action="store_true")
    ap.add_argument("--reference-only", action="store_true")
    ap.add_argument("--shares", action="store_true")
    ap.add_argument("--tail", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if not args.cpu:
        runner.require_devices(int(cell["chips"]))
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **readings(
            cell, seed, lowered=not args.sound_only,
            program=not args.reference_only, shares=args.shares,
            tail=args.tail)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
