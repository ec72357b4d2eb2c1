"""``joyai_control.py`` at TINY on the CPU: the program as deployed is within
the configuration's tolerance of the float32 reference, the reference with
float8 operands is far further from itself than the program is, the bias
moves the choice on a share of the pairs, and the routing shares are shares.
Whether float8 is past ``parity_atol`` is a reading at the cell's own widths
(PERF.md, PR 43)."""

import json

import pytest

import joyai_control
import rehearsal


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(tmp_path_factory.mktemp("joyai_control"))
    bench = copy / "benchmarks"
    return {"name": "tiny", "config_data": json.loads(
        (bench / "configs" / "joyai-llm-flash-s2048.json").read_text()),
        "traffic_data": json.loads(
            (bench / "traffic" / "s2048-remit-saturated.json").read_text())}


@pytest.mark.parametrize("seed", [1, 4300000999])
def test_float8_operands_read_further_than_the_program(cell, seed):
    r = joyai_control.readings(cell, seed)
    assert r["sound"]["ok"], r["sound"]
    sound = r["sound"]["max_delta"]["branch:bert_text"]
    fp8 = r["reference_fp8"]["max_delta"]["branch:bert_text"]
    bf16 = r["reference_bf16"]["max_delta"]["branch:bert_text"]
    assert fp8 > 5.0 * max(sound, bf16) > 0.0
    # the routed experts' matmuls alone in float8: a part of the whole
    alone = r["reference_fp8_experts"]["max_delta"]["branch:bert_text"]
    assert 0.0 < alone and alone != fp8
    # a planted fault of the grouped matmuls (a group's ragged last tile
    # left out; at TINY every group is under a tile) is another function
    assert r["reference_tail_dropped"]["max_delta"]["branch:bert_text"] \
        > 5.0 * max(sound, bf16)
    for name in ("program_differs", "program_differs_real_tokens"):
        assert 0.0 <= r["routing"][name] < 0.3
    assert 0.0 < r["routing"]["bias_moves_choice"] < 1.0
    assert r["reference_fp8"]["routing_differs"] \
        > r["reference_bf16"]["routing_differs"]
    for column, d in r["reference_fp8"]["max_delta"].items():
        if column not in ("branch:bert_text", "fraud_probability",
                          "confidence"):
            assert d == 0.0, column


def test_sound_only_and_reference_only_leave_their_halves_out(cell):
    r = joyai_control.readings(cell, 2, lowered=False)
    assert set(r) == {"sound", "routing"} and r["sound"]["ok"]
    r = joyai_control.readings(cell, 2, program=False)
    assert set(r) == {"routing", "reference_fp8", "reference_fp8_experts",
                      "reference_tail_dropped", "reference_bf16"}
    assert "program_differs" not in r["routing"]
