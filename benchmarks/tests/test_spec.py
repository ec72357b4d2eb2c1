import json
import re

import pytest

import rehearsal
from benchmarks.harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BM = spec.benchmark()
# with the entries of ``benchmarks/parked.json`` (cells the driver's memory
# floor refused): held to the same rules, so that one can be moved back
ALL = rehearsal.with_parked()


def test_keys_and_limits_of_the_contract():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["benchmarks"]
    assert 1 <= BM["run_seconds"] <= 51
    assert 2 <= len(BM["workloads"]) <= 24
    four = [w for w in BM["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BM["workloads"]) // 4)
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("BM", [BM, ALL], ids=["admitted", "with_parked"])
def test_names_units_and_one_line_texts(BM):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BM[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind in ("end_to_end", "per_layer"), e["name"]))
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
    assert len(names) == len(set(names))
    for e in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for e in BM["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    e2e = {e["name"] for e in BM["end_to_end"]}
    for e in BM["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert e["moves"] in e2e
    for w in BM["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("cell", [w["name"] for w in ALL["workloads"]])
def test_every_name_of_a_cell_resolves_to_a_file(cell, monkeypatch):
    if cell not in {w["name"] for w in BM["workloads"]}:
        monkeypatch.setattr(spec, "benchmark", lambda: ALL)
    w = spec.cell(cell)
    assert w["config_data"]["chips"] == w["chips"]
    arrival = spec.arrival(w["traffic_data"]["arrival"])
    assert arrival.MODE in ("open_loop", "backlog")
    e2e = spec.metrics_for(cell, "end_to_end")
    layer = spec.metrics_for(cell, "per_layer")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer, "every cell reports at least one per-layer metric"
    for kind, defs in (("end_to_end", e2e), ("per_layer", layer)):
        for m in defs:
            assert callable(spec.reader_for(m["name"], kind))


def test_a_name_without_a_file_stops_the_run():
    with pytest.raises(SystemExit, match="no workload"):
        spec.cell("no-such-cell")
    with pytest.raises(SystemExit, match="no file"):
        spec.reader_for("no_such_metric", "per_layer")
    with pytest.raises(SystemExit, match="no file"):
        spec.arrival("no-such-kind")
    for resolve, path in (
            (lambda: spec.builder({"builder": "no_such_builder"}),
             "benchmarks/configs/no_such_builder.py"),
            (lambda: spec.reference("no_such_reference"),
             "benchmarks/configs/no_such_reference.py"),
            (lambda: spec.kernel("no_such_kernel"),
             "benchmarks/kernels/no_such_kernel.py")):
        with pytest.raises(SystemExit, match=f"no file {path}"):
            resolve()


def test_config_files_are_used_once_hold_the_published_sizes_and_defaults():
    from realtime_fraud_detection_tpu.models.bert import BertConfig
    from realtime_fraud_detection_tpu.stream import JobConfig

    for bm in (BM, ALL):
        files = [c["file"] for c in bm["configs"]]
        assert len(files) == len(set(files))
        used = {w["config"] for w in bm["workloads"]}
        assert used == {c["name"] for c in bm["configs"]}
    for c in ALL["configs"]:
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert c["reduced"] == cfg["reduced"]
        assert cfg["source"] == c["source"]
        # the file runs the source's sizes (it lists them under
        # ``published``), but for the keys it says it reduced
        assert cfg["published"], c["name"]
        for key, value in cfg["published"].items():
            if key in cfg["reduced"]:
                assert cfg[key] != value, key
            else:
                assert cfg[key] == value, key
        assert set(cfg["reduced"]) <= set(cfg["published"])
        builder = spec.builder(cfg)
        for name in ("make_models", "make_scorer", "matmul_flops_per_batch"):
            assert callable(getattr(builder, name)), name
        assert set(builder.TINY) <= set(cfg)
        if cfg.get("builder", spec.DEFAULT_BUILDER) == spec.DEFAULT_BUILDER:
            # distilbert-base-uncased's config.json is what BertConfig() holds
            assert builder.bert_config(cfg) == BertConfig()
        # what `rtfd run-job` builds with no flags, plus the pool switches
        default = JobConfig()
        for key, value in cfg["job"].items():
            if key in ("device_pool", "inflight_depth"):
                continue
            assert getattr(default, key) == value, key


def test_every_metric_file_is_listed_and_every_file_name_is_plain():
    listed = {m["name"] for m in ALL["end_to_end"] + ALL["per_layer"]}
    on_disk = {p.stem for sub in ("end_to_end", "layer_metrics")
               for p in (spec.BENCH / sub).glob("*.json")}
    assert on_disk == listed
    for p in spec.BENCH.rglob("*"):
        if "__pycache__" in p.parts:
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$",
                        str(p.relative_to(spec.ROOT))), p
