"""``falconh1_control.py`` at TINY on the CPU: the program as deployed is
within the configuration's tolerance of the float32 reference, the reference
with float8 operands is far further from itself than the program is, and
no path leaves a layer's update. Whether float8 is past
``parity_atol`` is a reading at the cell's own widths (PERF.md, PR 46)."""

import json

import numpy as np
import pytest

import falconh1_control
import rehearsal


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(
        tmp_path_factory.mktemp("falconh1_control"))
    bench = copy / "benchmarks"
    return {"name": "tiny", "config_data": json.loads(
        (bench / "configs" / "falcon-h1-34b-s2048.json").read_text()),
        "traffic_data": json.loads(
            (bench / "traffic" / "s2048-remit-saturated.json").read_text())}


@pytest.mark.parametrize("seed", [1, 4600000999])
def test_float8_operands_read_further_than_the_program(cell, seed):
    r = falconh1_control.readings(cell, seed, shares=True)
    assert r["sound"]["ok"], r["sound"]
    sound = r["sound"]["max_delta"]["branch:bert_text"]
    fp8 = r["reference_fp8"]["max_delta"]["branch:bert_text"]
    bf16 = r["reference_bf16"]["max_delta"]["branch:bert_text"]
    assert fp8 > 5.0 * max(sound, bf16) > 0.0
    for column, d in r["reference_fp8"]["max_delta"].items():
        if column not in ("branch:bert_text", "fraud_probability",
                          "confidence"):
            assert d == 0.0, column
    shares = np.asarray(r["shares"])
    assert shares.shape[1:] == (3, cell["config_data"]["parity_rows"])
    # (over a tenth each at the cell's own lengths: PERF.md, PR 46; over the
    # rehearsal's 128 keys attention is the largest and the MLP the least)
    assert shares.min() > 0.03 and abs(shares.sum(axis=1) - 1.0).max() < 1e-3


def test_sound_only_and_reference_only_leave_their_halves_out(cell):
    r = falconh1_control.readings(cell, 2, lowered=False)
    assert set(r) == {"sound", "kernels"} and r["sound"]["ok"]
    # a CPU run keeps the XLA form of the scan, and says so
    assert r["kernels"]["fallback"]["ssm_scan"] >= 1
    assert not r["kernels"]["dispatch"].get("ssm_scan")
    assert r["kernels"]["refused"]["ssm_scan"]
    r = falconh1_control.readings(cell, 2, program=False)
    assert set(r) == {"reference_fp8", "reference_bf16"}
