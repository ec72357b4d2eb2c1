"""CPU rehearsal of a cell's control flow at TINY widths.

``make_tiny_copy(dst)`` copies ``BENCHMARK.json`` and ``benchmarks/`` into
``dst`` and shrinks only DATA files there (widths, population, rates), so
the rehearsal runs the very code the chip runs. The copy's ``BENCHMARK.json``
also lists the entries of ``benchmarks/parked.json`` (cells the driver's
memory floor refused: their files and code paths stay, and stay rehearsed).
Run as a script it drives one cell from such a copy with the chip refusal
bypassed — the bypass lives here, in the tests, and nowhere in the harness:

    python benchmarks/tests/rehearsal.py <copy> --workload s64-steady \
        --seed 1 --seconds 3 --trace 0
"""

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
TINY_WIDTHS = {"dim": 128, "n_layers": 2, "n_heads": 2, "hidden_dim": 256}
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
    dst = Path(dst)
    (dst / "BENCHMARK.json").write_text(json.dumps(with_parked()))
    shutil.copytree(ROOT / "benchmarks", dst / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for path in (dst / "benchmarks" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(TINY_WIDTHS)
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
    peaks.PEAKS["cpu"] = {"bf16_flops_per_s": 1e12}    # no device number
    return runner.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
