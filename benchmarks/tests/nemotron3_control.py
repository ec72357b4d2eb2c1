"""The control of ``correct`` (a) for ``nemotron-3-nano-30b-s2048``, and how
much of the residual each layer's one path adds: ``falconh1_control.py``'s
pattern (``olmoe_control.py``'s column comparison is used as it stands) on
Nemotron-3-Nano's reference. For each seed, on the cell's own weights and
parity sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference
  (``configs/nemotron3_reference.py``), and under ``kernels`` what
  ``kernel_snapshot()`` counted for that launch (sites ``attention``,
  ``ssm_scan``, ``expert_gate_up``, ``expert_combine``: ``dispatch`` on the
  chip, ``fallback`` elsewhere);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection
  (``W_in``, ``W_out``, q, k, v, o), both contractions of the attention
  core, the scan's two products (``x``, ``B``, ``C`` and the state where ``S
  C_t`` reads it) and both matmuls of every routed and shared expert
  rounded to float8 (e4m3); the router stays float32, as the configuration
  states it — against the reference as it stands, column by column against
  the same ``parity_atol``. It has to come out NOT correct;
- ``reference_fp8_routed`` / ``reference_fp8_scan``: float8 in the ROUTED
  experts' matmuls alone, and in the SCAN's products alone: whether either
  mechanism by itself is in ``correct``'s sight;
- ``reference_bf16``: bfloat16 operands everywhere: what the stated
  precision alone costs, with no program in it;
- ``shares`` (``--shares``): in the float32 reference, at each row's last
  real token and a layer at a time (``[layers][rows]``): ``update`` the norm
  of the layer's update over the norm of the residual it is added to (a
  layer is ONE path: under a tenth and nobody checks the layer), ``routed``
  the routed experts' part of an ``E`` layer's update over the whole of it,
  ``moved`` the share of the row's real tokens whose chosen experts the
  router's bias changed.

    python3 benchmarks/tests/nemotron3_control.py --workload \
        nemotron3-s2048-remit-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_nemotron3_control.py``; the
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

# (one rounding function a precision: the reference keeps its programs by
# the operand function's identity)
from falconh1_control import _operand  # noqa: E402
from olmoe_control import _deltas  # noqa: E402

# (name, precision, the reference's sites it reaches: None = all)
LOWERED = (("fp8", "fp8", None), ("fp8_routed", "fp8", ("routed",)),
           ("fp8_scan", "fp8", ("scan",)), ("bf16", "bf16", None))


def _reference_columns(reference, args, **lowering):
    """``score`` with the text branch handed ``lowering`` (``operand``,
    ``sites``, ``parts``); with ``parts`` also what it kept."""
    branch = reference.text_branch
    kept = []

    def lowered(*a):
        out = branch(*a, **lowering)
        if lowering.get("parts"):
            out, parts = out
            kept.append(parts)
        return out

    reference.text_branch = lowered
    try:
        return reference.score(*args), (kept[0] if kept else None)
    finally:
        reference.text_branch = branch


def readings(cell, seed, lowered=True, program=True, shares=False):
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
    plain, parts = _reference_columns(reference, args, parts=shares)
    if shares:
        update, residual, routed, moved = (parts[:, i] for i in range(4))
        out["shares"] = {
            "update": (update / residual).round(4).tolist(),
            "routed": (routed / update).round(4).tolist(),
            "moved": moved.round(4).tolist()}
        out["tokens"] = np.count_nonzero(
            np.asarray(host_batch.token_mask), axis=1).tolist()
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
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    if not args.cpu:
        runner.require_devices(int(cell["chips"]))
    for seed in args.seeds:
        print(json.dumps({"seed": seed, **readings(
            cell, seed, lowered=not args.sound_only,
            program=not args.reference_only, shares=args.shares)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
