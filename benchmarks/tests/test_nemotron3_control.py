"""``nemotron3_control.py`` at TINY on the CPU: the program as deployed is
within the configuration's tolerance of the float32 reference, the reference
with float8 operands is far further from itself than the program is, float8
at one site alone reads something and no more than float8 everywhere about,
and no layer's one path leaves the residual. Whether float8 is past
``parity_atol`` is a reading at the cell's own widths (PERF.md, PR 50)."""

import json

import numpy as np
import pytest

import nemotron3_control
import rehearsal


@pytest.fixture(scope="module")
def nemotron3_cell(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(
        tmp_path_factory.mktemp("nemotron3_control"))
    bench = copy / "benchmarks"
    return {"name": "tiny", "config_data": json.loads(
        (bench / "configs" / "nemotron-3-nano-30b-s2048.json").read_text()),
        "traffic_data": json.loads(
            (bench / "traffic" / "s2048-remit-saturated.json").read_text())}


def test_nemotron3_float8_operands_read_further_than_the_program(
        nemotron3_cell):
    r = nemotron3_control.readings(nemotron3_cell, 5000000999, shares=True)
    assert r["sound"]["ok"], r["sound"]
    # a CPU run keeps the XLA form at every site, and says so
    for site in ("attention", "ssm_scan", "expert_gate_up",
                 "expert_combine"):
        assert r["kernels"]["fallback"][site] >= 1, site
        assert not r["kernels"]["dispatch"].get(site)
    sound = r["sound"]["max_delta"]["branch:bert_text"]
    text = {name: r[f"reference_{name}"]["max_delta"]["branch:bert_text"]
            for name, *_ in nemotron3_control.LOWERED}
    assert text["fp8"] > 4.0 * max(sound, text["bf16"]) > 0.0
    assert text["fp8_routed"] > 0.0 and text["fp8_scan"] > 0.0
    for column, d in r["reference_fp8"]["max_delta"].items():
        if column not in ("branch:bert_text", "fraud_probability",
                          "confidence"):
            assert d == 0.0, column
    rows = nemotron3_cell["config_data"]["parity_rows"]
    update, routed, moved = (np.asarray(r["shares"][key])
                             for key in ("update", "routed", "moved"))
    assert update.shape == routed.shape == moved.shape == (9, rows)
    # (between a tenth and the whole at the cell's own lengths: PERF.md, PR
    # 50; over the rehearsal's 128 keys a context is no average of 1,200
    # values and attention's update is the largest)
    assert update.min() > 0.05
    sparse = [i for i, kind in enumerate("MEMEM*EME") if kind == "E"]
    assert (routed[sparse] > 0.1).all() and (routed[sparse] < 1.0).all()
    assert moved[sparse].max() > 0.0


def test_nemotron3_sound_only_and_reference_only_leave_their_halves_out(
        nemotron3_cell):
    r = nemotron3_control.readings(nemotron3_cell, 2, lowered=False)
    assert set(r) == {"sound", "kernels"} and r["sound"]["ok"]
    assert r["kernels"]["refused"]["ssm_scan"]
