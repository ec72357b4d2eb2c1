"""``qwen3next_control.py`` at TINY on the CPU: the program as deployed is
within the configuration's tolerance of the float32 reference, the reference
with float8 operands is far further from itself than the program is, float8
at one site alone reads something, and neither half of any layer leaves the
residual. Whether float8 is past ``parity_atol`` is a reading at the cell's
own widths (PERF.md, PR 54)."""

import json

import numpy as np
import pytest

import qwen3next_control
import rehearsal


@pytest.fixture(scope="module")
def qwen3next_cell(tmp_path_factory):
    copy = rehearsal.make_tiny_copy(
        tmp_path_factory.mktemp("qwen3next_control"))
    bench = copy / "benchmarks"
    return {"name": "tiny", "config_data": json.loads(
        (bench / "configs" / "qwen3-next-80b-a3b-s2048.json").read_text()),
        "traffic_data": json.loads(
            (bench / "traffic" / "s2048-remit-saturated.json").read_text())}


def test_qwen3next_float8_operands_read_further_than_the_program(
        qwen3next_cell):
    r = qwen3next_control.readings(qwen3next_cell, 5000000999, shares=True)
    assert r["sound"]["ok"], r["sound"]
    # a CPU run keeps the XLA form at every site, and says so
    for site in ("attention", "delta_scan", "expert_gate_up",
                 "expert_combine"):
        assert r["kernels"]["fallback"][site] >= 1, site
        assert not r["kernels"]["dispatch"].get(site)
    sound = r["sound"]["max_delta"]["branch:bert_text"]
    text = {name: r[f"reference_{name}"]["max_delta"]["branch:bert_text"]
            for name, *_ in qwen3next_control.LOWERED}
    assert text["fp8"] > 4.0 * max(sound, text["bf16"]) > 0.0
    assert text["fp8_routed"] > 0.0 and text["fp8_scan"] > 0.0
    for column, d in r["reference_fp8"]["max_delta"].items():
        if column not in ("branch:bert_text", "fraud_probability",
                          "confidence"):
            assert d == 0.0, column
    rows = qwen3next_cell["config_data"]["parity_rows"]
    mixer, sparse, routed = (np.asarray(r["shares"][key])
                             for key in ("mixer", "sparse", "routed"))
    assert mixer.shape == sparse.shape == routed.shape == (6, rows)
    assert mixer.min() > 0.05 and sparse.min() > 0.05
    assert (routed >= 0.0).all() and (routed < 1.05).all()
    assert routed.max() > 0.3


def test_qwen3next_tail_reads_each_row_and_its_last_tokens_held_mass(
        qwen3next_cell):
    r = qwen3next_control.readings(qwen3next_cell, 3, lowered=False,
                                   tail=True)
    rows = qwen3next_cell["config_data"]["parity_rows"]
    tail = r["tail"]
    program, plain, low = (np.asarray(tail[k]) for k in ("program", "plain",
                                                         "bf16"))
    assert program.shape == plain.shape == low.shape == (rows,)
    # the largest of the rows is what ``correct.parity`` reads
    assert np.abs(program - plain).max() == pytest.approx(
        r["sound"]["max_delta"]["branch:bert_text"], abs=1e-7)
    assert 0.0 < np.abs(low - plain).max() < 0.05
    held, held_low = (np.asarray(tail[k]) for k in ("held_plain",
                                                    "held_bf16"))
    assert held.shape == held_low.shape == (6, rows)
    assert (held >= 0.0).all() and (held <= 1.00001).all() \
        and held.std() > 0.0
    margin = np.asarray(tail["margin_plain"])
    assert margin.shape == (6, rows)
    assert ((margin > 0.0) | (margin == -1.0)).all() and (margin > 0.0).any()
    assert len(r["tokens"]) == rows


def test_qwen3next_sound_only_and_reference_only_leave_their_halves_out(
        qwen3next_cell):
    r = qwen3next_control.readings(qwen3next_cell, 2, lowered=False)
    assert set(r) == {"sound", "kernels"} and r["sound"]["ok"]
    assert r["kernels"]["refused"]["delta_scan"]
