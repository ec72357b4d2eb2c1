"""``tools/scope_ops.py`` on the hand-made events of
``benchmarks/tests/fixtures/scope_part_events.json``: what a scope holds by
instruction, and where each unscoped operation's operands came from.
Arithmetic on made-up times: no test reports a device number."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)

import scope_ops  # noqa: E402
from benchmarks.harness import spec  # noqa: E402
from realtime_fraud_detection_tpu.obs import scopes  # noqa: E402

FIXTURE = ROOT / "benchmarks/tests/fixtures/scope_part_events.json"


@pytest.fixture(scope="module")
def trace():
    return scope_ops.read_events(str(FIXTURE))


@pytest.fixture(scope="module")
def vocabulary():
    return scope_ops.deepened(
        spec.builder({"builder": "nemotron3_builder"}).VOCABULARY)


def test_the_vocabulary_is_deepened_by_the_programs_own_parts(vocabulary):
    layer = vocabulary[scopes.TEXT]["layer*"]
    for scope in (scopes.SSM_PROJ, scopes.ROUTER):
        assert tuple(layer[scope]) == scopes.SCOPE_PARTS[scope]
    assert set(layer[scopes.EXPERTS]) == set(scopes.EXPERTS_PARTS)
    assert layer[scopes.SSM_SCAN] == {} and vocabulary[scopes.TREES] == {}
    falcon = scope_ops.deepened(
        spec.builder({"builder": "falconh1_builder"}).VOCABULARY)
    assert tuple(falcon[scopes.TEXT]["layer*"][scopes.FFN]) == \
        scopes.SCOPE_PARTS[scopes.FFN]


def test_a_scope_is_listed_by_kind_shape_and_part(trace, vocabulary):
    found = scope_ops.listing(trace, vocabulary, "text/layer*/ssm_proj")
    assert found["batches"] == 2
    assert found["ms_per_batch"] == pytest.approx(9.02)
    rows = found["rows"]
    assert [(r["kind"], r["scope"].rsplit("/", 1)[1], r["count"])
            for r in rows] == [
        ("fusion", "in_proj", 2), ("fusion", "gate_norm", 2),
        ("multiply_reduce_fusion", "out_proj", 2), ("slice", "in_proj", 2),
        ("bitcast_reduce_fusion", "gate_norm", 2),
        ("add_rsqrt_fusion", "gate_norm", 2)]
    assert rows[0]["shape"] == "f32[8,2048,10304]{1,2,0:T(8,128)}"
    assert rows[0]["ms_per_batch"] == pytest.approx(4.6)
    assert rows[0]["op_name"].endswith("/ssm_proj/in_proj/dot_general")
    assert sum(r["ms_per_batch"] for r in rows) == pytest.approx(
        found["ms_per_batch"])
    # a part alone; a kernel stays directly under its scope
    part = scope_ops.listing(trace, vocabulary,
                             "text/layer*/ssm_proj/gate_norm")
    assert part["ms_per_batch"] == pytest.approx(1.5 + 0.6 + 0.02)
    conv = scope_ops.listing(trace, vocabulary, "text/layer*/ssm_conv")
    assert [(r["kind"], r["scope"]) for r in conv["rows"]] == [
        ("causal_conv", "text/layer*/ssm_conv")]
    # a tuple's shapes are kept whole; ``--top`` cuts the rows, not the sum
    order = scope_ops.listing(trace, vocabulary, "text/layer*/router/order",
                              top=1)
    assert order["kinds"] == 2 and len(order["rows"]) == 1
    assert order["rows"][0]["shape"] == "(s32[98304]{0}, s32[98304]{0})"
    assert order["ms_per_batch"] == pytest.approx(2.5)
    # ``--batches`` overrides the count the trace gives
    assert scope_ops.listing(trace, vocabulary, "text/layer*/ssm_conv",
                             batches=4)["ms_per_batch"] == pytest.approx(0.6)
    assert scope_ops.listing(trace, vocabulary, "gnn")["rows"] == []


def test_unscoped_operations_name_their_operands_scopes(trace, vocabulary):
    found = scope_ops.listing(trace, vocabulary, "unscoped")
    assert found["ms_per_batch"] == pytest.approx(0.76)
    big, small = found["rows"]
    assert (big["kind"], big["shape"], big["count"]) == (
        "copy", "f32[8,2048,4096]{2,1,0:T(8,128)}", 2)
    assert big["operands"] == [
        "f32[8,2048,4096]{1,2,0:T(8,128)} %slice.256 <- "
        "text/layer*/ssm_proj/in_proj"]
    # an operand that no operation of the trace produced
    assert small["operands"] == [
        "f32[16384,128]{1,0} %get-tuple-element.317 <- -"]
    # under the builder's own vocabulary the producer is the parent
    plain = spec.builder({"builder": "nemotron3_builder"}).VOCABULARY
    assert scope_ops.listing(trace, plain, "unscoped")["rows"][0][
        "operands"][0].endswith("<- text/layer*/ssm_proj")


def test_an_hlo_line_is_cut_into_name_shape_and_operands():
    line = ("%fusion.7 = (f32[8]{0}, s32[8,2]{1,0:T(8,128)}) fusion(f32[8]{0} "
            "%a.1, %b), kind=kLoop, calls=%fused_computation.7")
    name, shape, rest = scope_ops.instruction(line)
    assert (name, shape) == ("%fusion.7", "(f32[8]{0}, s32[8,2]{1,0:T(8,128)})")
    assert scope_ops.operands(rest) == [("f32[8]{0}", "%a.1"), ("", "%b")]
    assert scope_ops.instruction("%copy-start.2")[0] == "%copy-start.2"
    assert scope_ops.kind("%convolution_multiply_fusion.12") == \
        "convolution_multiply_fusion"
    assert scope_ops.folded("text/layer11/experts/matmul") == \
        "text/layer*/experts/matmul"


def test_the_command_line_writes_its_listings(tmp_path, capsys):
    out = tmp_path / "ops.json"
    assert scope_ops.main([
        str(FIXTURE), "--builder", "nemotron3_builder", "--scope",
        "text/layer*/router", "--scope", "unscoped", "--out", str(out)]) == 0
    said = capsys.readouterr().out
    assert "text/layer*/router: 4.300 ms a batch over 2 batches" in said
    assert "from f32[8,2048,4096]{1,2,0:T(8,128)} %slice.256 <- " in said
    wrote = json.loads(out.read_text())
    assert wrote["builder"] == "nemotron3_builder"
    assert [f["scope"] for f in wrote["listings"]] == [
        "text/layer*/router", "unscoped"]
