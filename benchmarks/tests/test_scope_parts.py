"""``readers/scope_part_time_per_batch.py`` on a small hand-made event list
(``fixtures/scope_part_events.json``: operations with their whole HLO line
and ``op_name``, as a trace holds them): a part's seconds, the parent
unchanged under the builder's own vocabulary, ``None`` and the log line
against a trace without parts; and every metric file over the reader names a
scope and a part that the program writes. Arithmetic only: no test reports a
device number."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import scopes as S, spec
from benchmarks.harness import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "scope_part_events.json"
READER = "scope_part_time_per_batch"
VOCABULARY = spec.builder({"builder": "nemotron3_builder"}).VOCABULARY
# metric -> (the parent's pattern, the part, the cells that report it)
SSM_CELLS = ["falconh1-s2048-remit-saturated",
             "nemotron3-s2048-remit-saturated"]
QWEN_CELLS = ["qwen3next-s2048-remit-saturated"]
ROUTED_CELLS = [m for m in spec.benchmark()["per_layer"]
                if m["name"] == "router_ms_per_batch"][0]["workloads"]
PART_METRICS = {
    "ssm_in_proj_ms_per_batch": ("ssm_proj", "in_proj", SSM_CELLS),
    "ssm_gate_norm_ms_per_batch": ("ssm_proj", "gate_norm", SSM_CELLS),
    "ssm_out_proj_ms_per_batch": ("ssm_proj", "out_proj", SSM_CELLS),
    "delta_qk_norm_ms_per_batch": ("delta_conv", "qk_norm", QWEN_CELLS),
    "delta_gates_ms_per_batch": ("delta_conv", "gates", QWEN_CELLS),
    "router_choose_ms_per_batch": ("router", "choose", ROUTED_CELLS),
    "router_order_ms_per_batch": ("router", "order", ROUTED_CELLS),
    "falconh1_ffn_gate_ms_per_batch": ("ffn", "gate", SSM_CELLS[:1]),
    "falconh1_ffn_up_ms_per_batch": ("ffn", "up", SSM_CELLS[:1]),
    "falconh1_ffn_down_ms_per_batch": ("ffn", "down", SSM_CELLS[:1]),
}


def reader():
    return spec._load_module(spec.BENCH / "readers" / f"{READER}.py", READER)


def traced(monkeypatch, strip_parts=False):
    """A run whose trace is the fixture: ``read_xplane`` answers with the
    fixture's events under whatever vocabulary it is handed, as the real
    one does; ``strip_parts`` takes the parts' names out of every
    ``op_name`` first (the program before the parts)."""
    from realtime_fraud_detection_tpu.obs import scopes as program

    rec = json.loads(FIXTURE.read_text())
    parts = {p for ps in program.SCOPE_PARTS.values() for p in ps}
    asked = []

    def read_xplane(path, vocabulary=S.ENSEMBLE_VOCABULARY):
        asked.append(vocabulary)
        out = []
        for plane, line, name, a, d, op in rec["events"]:
            if strip_parts:
                op = "/".join(c for c in op.split("/") if c not in parts)
            device = plane.startswith(T.DEVICE_PLANE_PREFIX)
            out.append((plane, line, name.split(" ", 1)[0] if device
                        else name, a, d,
                        S.scope_path(op, vocabulary) if device else ""))
        return out

    monkeypatch.setattr(S, "read_xplane", read_xplane)
    monkeypatch.setattr(T, "newest_xplane", lambda log_dir: "fixture")
    run = types.SimpleNamespace(
        trace={"window_s": 1.0}, counters_slice={"batches": rec["batches"]},
        extra={"vocabulary": VOCABULARY})
    return run, asked


def test_a_parts_seconds_and_the_parent_unchanged(monkeypatch, capsys):
    run, asked = traced(monkeypatch)
    read = reader().read
    ssm = "text/layer*/ssm_proj"
    got = {part: read(run, ssm, part)
           for part in ("in_proj", "gate_norm", "out_proj")}
    # ms a batch, from the fixture's made-up durations: W_in's fusion and
    # the cut of z; the gate's fusion, the norm's reduce and rsqrt; W_out's
    assert got == {"in_proj": pytest.approx(4.6 + 0.9),
                   "gate_norm": pytest.approx(1.5 + 0.6 + 0.02),
                   "out_proj": pytest.approx(1.4)}
    assert read(run, "text/layer*/router", "choose") == pytest.approx(1.8)
    assert read(run, "text/layer*/router", "order") == pytest.approx(2.5)
    # the parent under the builder's OWN vocabulary reads what it read and
    # is the sum of its parts: every operation under it lies in one
    parent = spec.reader_for("ssm_proj_ms_per_batch", "per_layer")(run)
    assert parent == pytest.approx(sum(got.values()))
    own = run.extra["scope_trace"]["scope_s"]
    assert not [p for p in own if p.count("/") > 2 and "experts" not in p]
    assert spec.reader_for("router_ms_per_batch", "per_layer")(run) == \
        pytest.approx(1.8 + 2.5)
    # the trace was read once for the builder's vocabulary and once more for
    # ALL the parts, whichever metric asked first
    assert len(asked) == 2 and asked[0] is VOCABULARY
    deep = asked[1]["text"]["layer*"]
    assert set(deep["ssm_proj"]) == {"in_proj", "gate_norm", "out_proj"}
    assert set(deep["router"]) == {"choose", "order"}
    assert set(deep["experts"]) == {"dispatch", "matmul", "combine"}
    assert "ffn" not in deep and "delta_conv" not in deep    # not this cell's
    assert VOCABULARY["text"]["layer*"]["ssm_proj"] == {}    # left as it was
    # a part of a scope this program does not write: left out, said once
    assert read(run, "text/layer*/delta_conv", "qk_norm") is None
    assert "no device operation under part 'qk_norm'" in capsys.readouterr().out


def test_a_trace_without_parts_reads_none_never_zero(monkeypatch, capsys):
    """The parent commit: the same operations, no part in any ``op_name``."""
    run, _ = traced(monkeypatch, strip_parts=True)
    read = reader().read
    assert read(run, "text/layer*/ssm_proj", "in_proj") is None
    assert ("no device operation under part 'in_proj' of scope "
            "'text/layer*/ssm_proj'") in capsys.readouterr().out
    assert spec.reader_for("ssm_proj_ms_per_batch", "per_layer")(run) == \
        pytest.approx(9.02)
    for name in PART_METRICS:
        assert spec.reader_for(name, "per_layer")(run) is None, name
    # an untraced run, and a slice in which no batch completed
    assert read(types.SimpleNamespace(
        trace=None, counters_slice={"batches": 2}, extra={}),
        "text/layer*/router", "order") is None
    run.counters_slice["batches"] = 0
    assert read(run, "text/layer*/router", "order") is None


def test_every_part_metric_names_a_part_the_program_writes():
    from realtime_fraud_detection_tpu.obs import scopes as program

    by_name = {m["name"]: m for m in spec.benchmark()["per_layer"]}
    assert sorted(reader().declared_parts()) == sorted(
        (f"text/layer*/{scope}", part)
        for scope, part, _ in PART_METRICS.values())
    assert {(scope, part) for scope, part, _ in PART_METRICS.values()} == {
        (scope, part) for scope, parts in program.SCOPE_PARTS.items()
        for part in parts}
    for name, (scope, part, cells) in PART_METRICS.items():
        assert json.loads((spec.BENCH / "layer_metrics" / f"{name}.json"
                           ).read_text()) == {
            "reader": READER,
            "args": {"scope": f"{program.TEXT}/{program.LAYER}*/{scope}",
                     "part": part}}
        assert part in program.SCOPE_PARTS[scope]
        assert by_name[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "txn_per_s", "workloads": cells}
        # where the parent is reported, and nowhere the program has no part
        parent = by_name[{"ssm_proj": "ssm_proj_ms_per_batch",
                          "delta_conv": "delta_conv_ms_per_batch",
                          "router": "router_ms_per_batch",
                          "ffn": "ffn_ms_per_batch"}[scope]]
        assert set(cells) <= set(parent["workloads"])
        assert callable(spec.reader_for(name, "per_layer"))
