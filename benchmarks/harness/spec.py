"""Names in ``BENCHMARK.json`` -> the files that define them.

Nothing about one cell, configuration, architecture, kernel, traffic mix,
arrival kind or metric is written in the harness's code: each is a file
found by its name, and a name with no file stops the run with the path that
was looked for.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

ROOT = Path(__file__).resolve().parents[2]
BENCH = Path(__file__).resolve().parents[1]


class SpecError(SystemExit):
    """A name that resolves to nothing. Exits non-zero, prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark spec error: {msg}")


def _load_json(path: Path, what: str) -> Dict[str, Any]:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path.relative_to(ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _load_module(path: Path, what: str):
    if not path.is_file():
        raise SpecError(f"{what}: no file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> Dict[str, Any]:
    return _load_json(ROOT / "BENCHMARK.json", "BENCHMARK.json")


def cell(name: str) -> Dict[str, Any]:
    """One ``workloads`` entry with its configuration and traffic loaded."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(known: {sorted(cells)})")
    w = dict(cells[name])
    configs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config {w['config']!r}, "
                        f"which BENCHMARK.json does not list")
    w["config_data"] = _load_json(ROOT / configs[w["config"]]["file"],
                                  f"config {w['config']!r}")
    w["traffic_data"] = _load_json(BENCH / "traffic" / f"{w['traffic']}.json",
                                   f"traffic {w['traffic']!r}")
    return w


def metrics_for(cell_name: str, kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    without a ``workloads`` list, and those whose list names the cell. A
    per-layer metric is reported only where the metric it moves is."""
    bm = benchmark()

    def applies(m: Dict[str, Any]) -> bool:
        return "workloads" not in m or cell_name in m["workloads"]

    out = [m for m in bm[kind] if applies(m)]
    if kind == "per_layer":
        e2e = {m["name"] for m in bm["end_to_end"] if applies(m)}
        out = [m for m in out if m["moves"] in e2e]
    return out


def reader_for(metric: str, kind: str) -> Callable[[Any], Any]:
    """``<kind dir>/<metric>.json`` names a reader under ``readers/`` and
    its arguments; returns ``run -> value or None``."""
    sub = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[kind]
    d = _load_json(BENCH / sub / f"{metric}.json", f"metric {metric!r}")
    mod = _load_module(BENCH / "readers" / f"{d['reader']}.py",
                       f"reader {d['reader']!r} of metric {metric!r}")
    args = d.get("args", {})
    if "kernel" in args:        # a name like any other: no file, no run
        kernel(args["kernel"])
    return lambda run: mod.read(run, **args)


def reference(name: str):
    """``configs/<name>.py``: a configuration's plain reference, with
    ``score(models, batch, params, model_valid, cfg)`` and ``BRANCHES``."""
    return _load_module(BENCH / "configs" / f"{name}.py",
                        f"reference {name!r}")


# the five-branch ensemble with a DistilBERT-keyed text branch: what every
# configuration file written before builders were named resolves to
DEFAULT_BUILDER = "ensemble_builder"


def builder(cfg: Dict[str, Any]):
    """``configs/<cfg["builder"]>.py``: the architecture a configuration's
    keys are written for. ``make_models(cfg, seed, sample_features)``,
    ``make_scorer(cfg, seed, models, users, merchants)``,
    ``matmul_flops_per_batch(cfg)``, ``VOCABULARY`` (the device scopes its
    program writes, nested: ``harness/scopes.py``) and ``TINY`` (the data
    overrides of a CPU rehearsal)."""
    name = cfg.get("builder", DEFAULT_BUILDER)
    return _load_module(BENCH / "configs" / f"{name}.py",
                        f"builder {name!r}")


def kernel(name: str):
    """``kernels/<name>.py``: ``work(counters, cfg) -> {"flops": ...,
    "hbm_bytes": ...}``, what the algorithm needs for the launches the
    program counted."""
    return _load_module(BENCH / "kernels" / f"{name}.py",
                        f"kernel {name!r}")


def arrival(kind: str):
    """``arrivals/<kind>.py``: ``MODE`` and ``schedule()``."""
    return _load_module(BENCH / "arrivals" / f"{kind}.py",
                        f"arrival kind {kind!r}")
