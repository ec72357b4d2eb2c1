"""``laguna_control.py`` at TINY on the CPU: the program as deployed is
within the configuration's tolerance of the float32 reference, the reference
with float8 operands is far further from itself than the program is, and
the routing shares are shares. Whether float8 is past ``parity_atol`` is a
reading at the cell's own widths (PERF.md, PR 33)."""

import json

import pytest

import laguna_control
import rehearsal


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(tmp_path_factory.mktemp("laguna_control"))
    bench = copy / "benchmarks"
    return {"name": "tiny", "config_data": json.loads(
        (bench / "configs" / "laguna-s-2.1-s2048.json").read_text()),
        "traffic_data": json.loads(
            (bench / "traffic" / "s2048-remit-saturated.json").read_text())}


@pytest.mark.parametrize("seed", [1, 3300000999])
def test_float8_operands_read_further_than_the_program(cell, seed):
    r = laguna_control.readings(cell, seed)
    assert r["sound"]["ok"], r["sound"]
    sound = r["sound"]["max_delta"]["branch:bert_text"]
    fp8 = r["reference_fp8"]["max_delta"]["branch:bert_text"]
    bf16 = r["reference_bf16"]["max_delta"]["branch:bert_text"]
    assert fp8 > 5.0 * max(sound, bf16) > 0.0
    for name in ("program_differs", "program_differs_real_tokens"):
        assert 0.0 <= r["routing"][name] < 0.3
    assert 0.1 < r["routing"]["held_share"] < 0.4
    assert r["reference_fp8"]["routing_differs"] \
        > r["reference_bf16"]["routing_differs"]
    for column, d in r["reference_fp8"]["max_delta"].items():
        if column not in ("branch:bert_text", "fraud_probability",
                          "confidence"):
            assert d == 0.0, column


def test_sound_only_and_reference_only_leave_their_halves_out(cell):
    r = laguna_control.readings(cell, 2, lowered=False)
    assert set(r) == {"sound", "routing"} and r["sound"]["ok"]
    r = laguna_control.readings(cell, 2, program=False)
    assert set(r) == {"routing", "reference_fp8", "reference_bf16"}
    assert "program_differs" not in r["routing"]
