"""CPU rehearsal of a cell's control flow at TINY widths.

``make_tiny_copy(dst)`` copies ``BENCHMARK.json`` and ``benchmarks/`` into
``dst`` and shrinks only DATA files there (widths — each configuration's
from its own builder's ``TINY`` — population, rates), so the rehearsal runs
the very code the chip runs. The copy's ``BENCHMARK.json``
also lists the entries of ``benchmarks/parked.json`` (cells the driver's
memory floor refused: their files and code paths stay, and stay rehearsed).
Run as a script it drives one cell from such a copy with the chip refusal
bypassed — the bypass lives here, in the tests, and nowhere in the harness:

    python benchmarks/tests/rehearsal.py <copy> --workload s64-steady \
        --seed 1 --seconds 3 --trace 0

The CPU's trace names no device operation, so the readers of the program's
device scopes find nothing here. ``--device-ops <file>`` (before the run's
own arguments) stands in for the chip's trace on their side alone: the file
lists ``[op_name, start ms, duration ms]`` of operations in a 1 s slice, and
the scope reduction gets those, each through ``scope_path`` with the
configuration's own vocabulary, in place of the file the profiler wrote.
``--break <name>`` breaks the timed path underneath the harness (``BREAKS``),
for the test that sees ``correct`` come out false.
"""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TINY_RATE = {"s64-steady": 300, "s64-saturated": 5000,
             "s512-longtail-saturated": 4000,
             "s512-fulltext-saturated": 4000, "pool4-saturated": 5000}


def with_parked() -> dict:
    """``BENCHMARK.json`` plus the parked entries; a metric listed in both
    reports in the cells of both."""
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    parked = json.loads((ROOT / "benchmarks" / "parked.json").read_text())
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"]: e for e in bm[kind]}
        for e in parked[kind]:
            if e["name"] in have:
                have[e["name"]]["workloads"] = (
                    have[e["name"]]["workloads"] + e["workloads"])
            else:
                bm[kind].append(e)
    return bm


def make_tiny_copy(dst: Path) -> Path:
    from benchmarks.harness import spec

    dst = Path(dst)
    (dst / "BENCHMARK.json").write_text(json.dumps(with_parked()))
    shutil.copytree(ROOT / "benchmarks", dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path in (dst / "benchmarks" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(spec.builder(cfg).TINY)
        cfg["text_len"] = min(cfg["text_len"], 128)
        cfg["population"] = {"users": 2000, "merchants": 200}
        cfg["parity_rows"] = 8
        cfg["job"]["max_batch"] = 32
        path.write_text(json.dumps(cfg))
    for path in (dst / "benchmarks" / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        tr["rate_txn_per_s"] = TINY_RATE.get(path.stem, 300)
        tr["pool_events"] = 1024
        tr["warmup_s"] = tr["grace_s"] = 1.0
        tr["text_tokens"]["max"] = min(tr["text_tokens"]["max"], 128)
        path.write_text(json.dumps(tr))
    return dst


def stand_in_device_ops(ops):
    """``scopes.read_xplane`` replaced by one that returns ``ops`` as the
    one device's operations inside a 1 s ``bench:slice``."""
    from benchmarks.harness import scopes, trace

    ms = 1e6

    def read_xplane(path, vocabulary):
        return [("/threads", "main", scopes.WINDOW, 0.0, 1000 * ms, "")] + [
            (trace.DEVICE_PLANE_PREFIX, trace.OPS_LINE, "op", start * ms,
             dur * ms, scopes.scope_path(op_name, vocabulary))
            for op_name, start, dur in ops]

    scopes.read_xplane = read_xplane


def _break_text_answer():
    """An answer altered where it is produced: the text branch of the fused
    program returns its probability plus 0.01."""
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.scoring import pipeline

    sound = pipeline.bert_predict
    pipeline.bert_predict = lambda *a, **k: jnp.clip(
        sound(*a, **k) + 0.01, 0.0, 1.0)


def _break_fan_out():
    """A part of each batch left out: the last prediction of every
    microbatch is scored and counted, never produced."""
    from realtime_fraud_detection_tpu.stream import JobConfig, StreamJob

    sound = StreamJob._produce

    def lossy(self, *args):
        produce = self.broker.produce_batch_keyed
        self.broker.produce_batch_keyed = lambda topic, items: produce(
            topic, items[:-1] if topic == JobConfig.predictions_topic
            else items)
        try:
            return sound(self, *args)
        finally:
            del self.broker.produce_batch_keyed

    StreamJob._produce = lossy


BREAKS = {"text-answer": _break_text_answer, "fan-out": _break_fan_out}


def main(argv) -> int:
    copy, rest = Path(argv[0]).resolve(), argv[1:]
    sys.path.insert(0, str(copy))       # `benchmarks` = the copy
    sys.path.insert(1, str(ROOT))       # the program itself
    import jax

    from benchmarks.harness import peaks, runner, trace

    assert Path(runner.__file__).is_relative_to(copy), runner.__file__
    runner.require_devices = lambda chips: jax.devices()[:chips]
    # the CPU backend's operations run on host threads: let the host plane
    # stand in for the device plane so the traced path runs to its end
    trace.DEVICE_PLANE_PREFIX = "/host:CPU"
    peaks.PEAKS["cpu"] = {"bf16_flops_per_s": 1e12,    # no device numbers
                          "hbm_bytes_per_s": 1e11}
    while rest[0] in ("--device-ops", "--break"):
        if rest[0] == "--break":
            BREAKS[rest[1]]()
        else:
            stand_in_device_ops(json.loads(Path(rest[1]).read_text()))
        rest = rest[2:]
    return runner.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
