"""The control of ``correct`` (a) for ``falcon-h1-34b-s2048``, and the share
of a layer's update that each of its three paths contributes:
``joyai_control.py``'s pattern (``olmoe_control.py``'s column comparison is
used as it stands) on Falcon-H1's reference. For each seed, on the cell's own
weights and parity sample:

- ``sound``: ``correct.parity`` itself — the served program on the chip
  against the plain float32 reference (``configs/falconh1_reference.py``),
  and under ``kernels`` what ``kernel_snapshot()`` counted for that launch
  (site ``ssm_scan``: ``dispatch`` on the chip, ``fallback`` elsewhere);
- ``reference_fp8``: the reference in the program's place one precision
  below what the configuration states — BOTH operands of every projection
  (the mixer's ``W_in`` and ``W_out``, attention's four), both contractions
  of the attention core, the MLP's three matmuls, and the scan's two
  products (``x``, ``B``, ``C`` and the state where ``S C_t`` reads it)
  rounded to float8 (e4m3) — against the reference as it stands, column by
  column against the same ``parity_atol``. It has to come out NOT correct;
- ``reference_bf16``: the same with bfloat16 operands: what the stated
  precision alone costs, with no program in it;
- ``shares`` (``--shares``): in the float32 reference, the norm of the
  mixer's, attention's and the MLP's update at each row's last real token
  over the sum of the three, a layer at a time (``[layers][3][rows]``):
  none may be under a tenth (``init_falcon_h1_params`` says how the weights
  are drawn so).

    python3 benchmarks/tests/falconh1_control.py --workload \
        falconh1-s2048-remit-saturated --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here at whatever
size the configuration file has (TINY in ``test_falconh1_control.py``; the
reference is ``jax.numpy`` and runs on whatever device the process has);
``--sound-only`` leaves the lowered references out, ``--reference-only``
the program.
"""

import argparse
import functools
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(1, str(Path(__file__).resolve().parent))

from olmoe_control import _deltas  # noqa: E402


@functools.lru_cache(maxsize=None)
def _operand(name):
    """Round a ``jax.numpy`` array to float8 or bfloat16 and back (the
    reference's ``operand`` seam is traced). ONE function a precision:
    ``falconh1_reference._programs`` keeps its three programs by the
    operand function's identity."""
    import jax.numpy as jnp

    dtype = {"fp8": jnp.float8_e4m3fn, "bf16": jnp.bfloat16}[name]
    return lambda x: x.astype(dtype).astype(jnp.float32)


def _reference_columns(reference, args, operand=None, parts=False):
    """``score`` with every matmul operand of the text branch passed
    through ``operand`` first; with ``parts`` also the three paths'
    norms."""
    branch = reference.text_branch
    norms = []

    def lowered(*a):
        out = branch(*a, operand=operand, parts=parts)
        if parts:
            out, kept = out
            norms.append(kept)
        return out

    reference.text_branch = lowered
    try:
        return reference.score(*args), (norms[0] if norms else None)
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
        # which form of the scan (and of the core) that launch ran
        snap = scorer.kernel_snapshot()
        out["kernels"] = {k: snap[k] for k in ("dispatch", "fallback",
                                               "refused")}
    batch = scorer.assemble(recs)
    host_models, host_batch = jax.device_get((models, batch))
    args = (host_models, host_batch, scorer.ensemble_params,
            scorer.effective_model_valid(), cfg)
    plain, norms = _reference_columns(reference, args, parts=shares)
    if shares:
        out["shares"] = (norms / norms.sum(axis=1, keepdims=True)
                         ).round(4).tolist()
        out["tokens"] = np.count_nonzero(
            np.asarray(host_batch.token_mask), axis=1).tolist()
    for name in ("fp8", "bf16") if lowered else ():
        low, _ = _reference_columns(reference, args, operand=_operand(name))
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
