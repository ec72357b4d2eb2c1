"""The control of ``correct`` (a): the program computed one precision below
what the configuration states, held to the same comparison — it has to come
out NOT correct, or the tolerances in the configuration file let a later PR
serve a cheaper model under the same name.

The configuration states bfloat16 matmul operands on float32 weights for
the text branch; the step below is int8, and the program has that path of
its own: ``QuantSettings(enabled=True, bert_weights="int8")``, weight-only
int8 dequantised at the matmul seam. ``readings`` builds one set of float32
weights from the seed, serves the cell's parity sample through the program
as deployed and through the program with that path switched on, and
compares both with the plain float32 reference ON THE FLOAT32 WEIGHTS
(``correct.parity`` itself; the int8 scorer is handed the float32 weights to
show the reference, which refuses quantised ones).

That path keeps bfloat16 activations, and read on the chip it is no further
from float32 than bfloat16 itself (PERF.md section 2). So ``readings`` also
puts the reference in the program's place one step below in BOTH operands:
``reference_fp8`` is the plain reference with the operands of every dense
layer rounded to float8 (e4m3), compared with the plain reference as it
stands, column by column against the same ``parity_atol``. That comparison is
NumPy against NumPy on seeded weights: it reads the same on any machine.

On the chip, at the cell's own size (no window: the comparison needs none):

    python3 benchmarks/tests/control.py --workload <cell> --seeds 1 2 3

prints one JSON line per seed; ``--cpu`` reads the same here (the int8
program then runs on the CPU backend: its gap is not the chip's).
``test_control.py`` runs the same at TINY.
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


class _ShownTheFloat32Weights:
    """A scorer whose ``models`` are the float32 ones it was made from."""

    def __init__(self, scorer, models):
        self._scorer, self.models = scorer, models

    def __getattr__(self, name):
        return getattr(self._scorer, name)


def reference_fp8(reference, scorer, models, recs, cfg):
    """The float32 reference against itself with every dense layer's
    operands rounded to float8: ``parity``'s columns, limits and verdict
    (but for the decisions, which a run compares too)."""
    import jax
    import ml_dtypes

    def fp8(x):
        return np.asarray(x, np.float32).astype(
            ml_dtypes.float8_e4m3fn).astype(np.float32)

    models, batch = jax.device_get((models, scorer.assemble(recs)))
    args = (models, batch, scorer.ensemble_params,
            scorer.effective_model_valid(), cfg)
    plain = reference.score(*args)
    dense = reference._linear
    reference._linear = lambda x, p: dense(fp8(x), dict(p, w=fp8(p["w"])))
    try:
        lowered = reference.score(*args)
    finally:
        reference._linear = dense
    deltas = {name: float(np.abs(lowered[name] - plain[name]).max())
              for name in ("fraud_probability", "confidence", "rule_score")}
    for j, name in enumerate(reference.BRANCHES):
        deltas[f"branch:{name}"] = float(np.abs(
            lowered["branches"][:, j] - plain["branches"][:, j]).max())
    over = [k for k, d in deltas.items() if not d <= cfg["parity_atol"][k]]
    return {"ok": not over, "rows": len(recs), "max_delta": deltas,
            "over": over}


def readings(cell, seed):
    """``{"sound": parity(...), "control": parity(...), "reference_fp8":
    ...}`` for one seed."""
    from benchmarks.harness import correct, events, spec, system
    from realtime_fraud_detection_tpu.utils.config import QuantSettings

    cfg = cell["config_data"]
    builder = spec.builder(cfg)
    made = events.make_stream(cell, seed, 1.0)
    users = made.population.user_profiles()
    merchants = made.population.merchant_profiles()
    sample = made.pool.materialize(range(512), np.zeros(512), "q")
    models = builder.make_models(
        cfg, seed, system.event_features(sample, users, merchants))
    recs = made.pool.materialize(
        range(cfg["parity_rows"]), np.zeros(cfg["parity_rows"]), "p")
    out = {}
    for name, quant in (("sound", None), ("control", QuantSettings(
            enabled=True, bert_weights="int8"))):
        scorer = builder.make_scorer(cfg, seed, models, users, merchants)
        if quant is not None:
            scorer.quant = quant
            scorer.set_models(models)
            assert scorer.quant_snapshot()["modes"]["bert_text"] == "int8"
        out[name] = correct.parity(
            _ShownTheFloat32Weights(scorer, models), recs, cfg)
    out["reference_fp8"] = reference_fp8(
        spec.reference(cfg["reference"]), scorer, models, recs, cfg)
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
