"""The comparison that decides ``correct``.

(a) ``parity``: a seeded sample scored through the served packed path on
    the scorer's device, against the configuration's plain float32
    reference (``configs/<reference>.py``: NumPy, independent of the
    program) on the same weights and the same assembled inputs: score,
    confidence, rule score and every branch within the configuration's
    ``parity_atol`` for that column, and the same decision wherever the
    reference is not within that tolerance of a rung of the ladder.
(b) ``prediction_ok`` / ``failed_marker``: every emitted prediction finite,
    on the decision ladder, without an error marker.
(c) compilations inside the window are counted by ``CompileCounter``.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import numpy as np


def parity(scorer, events: Sequence[Dict[str, Any]], cfg: Dict[str, Any]
           ) -> Dict[str, Any]:
    """Returns ``{"ok", "rows", "max_delta": {column: value}, "over":
    [columns beyond their tolerance], "flips"}``. The tolerances are the
    configuration's, one per column: each is set from the gap measured
    between the program at its stated compute dtype and the float32
    reference, so that a lower precision fails."""
    import jax

    from benchmarks.harness import spec
    from realtime_fraud_detection_tpu.scoring.pipeline import OUT_COLUMNS

    reference = spec.reference(cfg["reference"])
    atol = {k: float(v) for k, v in cfg["parity_atol"].items()}
    recs = list(events)
    n = len(recs)
    batch = scorer.assemble(recs)
    pending = scorer.dispatch_assembled(batch, recs)
    on_device = np.asarray(pending.out)[:n]
    scorer.finalize(pending)
    models, host_batch = jax.device_get((scorer.models, batch))
    ref = reference.score(models, host_batch, scorer.ensemble_params,
                          scorer.effective_model_valid(), cfg)
    cols = {name: j for j, name in enumerate(OUT_COLUMNS)}
    ok = (on_device.shape[1] == len(OUT_COLUMNS) + len(reference.BRANCHES)
          and bool(np.isfinite(on_device).all()))
    deltas: Dict[str, float] = {}
    over = ["shape or non-finite output"]
    flips = -1
    if ok:
        for name in ("fraud_probability", "confidence", "rule_score"):
            deltas[name] = float(np.max(np.abs(
                on_device[:, cols[name]] - ref[name][:n])))
        for j, name in enumerate(reference.BRANCHES):
            deltas[f"branch:{name}"] = float(np.max(np.abs(
                on_device[:, len(OUT_COLUMNS) + j] - ref["branches"][:n, j])))
        # a decision may differ only where the reference itself sits within
        # the tolerance of a rung
        rungs = ref["rungs"]
        near = (np.abs(ref["confidence"][:n] - rungs["confidence"])
                <= atol["confidence"])
        for name in ("decline", "review", "monitor"):
            near |= (np.abs(ref["fraud_probability"][:n] - rungs[name])
                     <= atol["fraud_probability"])
        differs = on_device[:, cols["decision"]] != ref["decision"][:n]
        flips = int(np.sum(differs & ~near))
        over = [name for name, d in deltas.items() if not d <= atol[name]]
        ok = not over and flips == 0
    return {"ok": ok, "rows": n, "max_delta": deltas, "over": over,
            "flips": flips}


def prediction_ok(res: Any) -> bool:
    """Finite probability in [0, 1], a ladder decision, no error marker
    (``chip_smoke._result_ok``)."""
    from realtime_fraud_detection_tpu.features.rules import DECISIONS

    if not isinstance(res, dict):
        return False
    p = res.get("fraud_probability")
    return (isinstance(p, float) and np.isfinite(p) and 0.0 <= p <= 1.0
            and res.get("decision") in DECISIONS
            and res.get("risk_level") != "ERROR")


def failed_marker(res: Any) -> bool:
    """An emitted prediction that stands for a failure: the job's ERROR
    marker, or a QoS shed (an explicit REVIEW with a shed reason)."""
    if not isinstance(res, dict) or res.get("risk_level") == "ERROR":
        return True
    expl = res.get("explanation")
    return isinstance(expl, dict) and bool(
        expl.get("error") or expl.get("shed") or expl.get("shed_reason"))


class CompileCounter:
    """Counts XLA compilations (cache hits included: a program that is
    loaded inside the window was not warmed) through ``jax.monitoring``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration
