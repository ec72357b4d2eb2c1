"""What a named scope of a traced benchmark run holds, instruction by
instruction: the split that ``PERF.md`` §5 cites for a scope, and — for
``unscoped`` — where each compiler-made operation's operands came from.

    python3 benchmarks/run.py --workload nemotron3-s2048-remit-saturated \\
        --seed 7 --seconds 20 --trace 1
    python3 tools/scope_ops.py . --builder nemotron3_builder \\
        --scope 'text/layer*/ssm_proj/gate_norm' --scope unscoped \\
        --out chiprun_out/nemotron3_ops.json

``TRACE`` is a checkout (its ``.bench_trace``), a profile directory, an
``.xplane.pb``, or a ``.json`` of events as ``tests`` keep them (``{"window":
[a, b], "batches": n, "events": [[plane, line, HLO line, start_ns, dur_ns,
op_name], ...]}``). A scope pattern is a path under the builder's
``VOCABULARY`` deepened by the program's own parts (``obs/scopes.SCOPE_PARTS``),
``*`` for one component's digits; it selects the operations AT or BELOW the
path. ``unscoped`` selects those whose ``op_name`` starts with no branch
(``unscoped_device_pct``: operations the compiler added, which no
``named_scope`` reaches).

A row is the operations of one kind (the instruction's name without its
number), one result shape and one scope path with the layers' digits
folded: how many ran in the traced slice, their device time a batch (summed
durations, clipped to the slice; the scope metrics take the union, which
differs only where operations overlap) and, under ``unscoped``, each
operand as the HLO line names it with the scope of the instruction that
produced it (``-`` for a parameter or an instruction that never ran as an
operation of its own). Batches are the ``rtfd:job.complete_batch`` spans
that end inside the slice unless ``--batches`` says.

Reads through ``benchmarks/harness/scopes.py`` and ``trace.py`` and edits
nothing there. No number it prints is a benchmark metric.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import scopes as S  # noqa: E402
from benchmarks.harness import trace as T  # noqa: E402
from realtime_fraud_detection_tpu.obs import scopes as program  # noqa: E402

# (plane, HLO line, start_ns, duration_ns, op_name)
Op = Tuple[str, str, float, float, str]
COMPLETE = S.PREFIX + program.JOB_COMPLETE


def deepened(vocabulary: S.Vocabulary) -> Dict[str, Any]:
    """``vocabulary`` with the program's parts below every layer scope that
    ``SCOPE_PARTS`` cuts: the part metrics' own deepening
    (``benchmarks/readers/scope_part_time_per_batch.py``), handed every part
    the program names."""
    from benchmarks.readers.scope_part_time_per_batch import (
        deepened as by_parts,
    )

    return by_parts(vocabulary, [
        (f"{program.TEXT}/{program.LAYER}*/{scope}", part)
        for scope, parts in program.SCOPE_PARTS.items() for part in parts])


def read_trace(path: str) -> Dict[str, Any]:
    """``{"ops": [Op], "window": (a, b), "batches": n or None}`` of the
    newest profile under ``path``."""
    import jax

    if os.path.isdir(path):
        bench = os.path.join(path, ".bench_trace")
        path = T.newest_xplane(bench if os.path.isdir(bench) else path)
    names = S.op_names(path)
    ops: List[Op] = []
    window, ends = None, []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        device = plane.name.startswith(T.DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        op_name = names.get(plane.name, {})
        for line in plane.lines:
            if device and line.name != T.OPS_LINE:
                continue
            for ev in line.events:
                if device:
                    ops.append((plane.name, ev.name, float(ev.start_ns),
                                float(ev.duration_ns),
                                op_name.get(ev.name, "")))
                elif ev.name == S.WINDOW:
                    window = (float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns))
                elif ev.name == COMPLETE:
                    ends.append(float(ev.start_ns + ev.duration_ns))
    if window is None:
        raise SystemExit(f"no {S.WINDOW} annotation in {path}")
    return {"ops": ops, "window": window,
            "batches": sum(window[0] <= e <= window[1] for e in ends) or None}


def read_events(path: str) -> Dict[str, Any]:
    rec = json.loads(Path(path).read_text())
    return {"ops": [(p, name, float(a), float(d), op)
                    for p, _line, name, a, d, op in rec["events"]
                    if p.startswith(T.DEVICE_PLANE_PREFIX)],
            "window": tuple(rec["window"]), "batches": rec.get("batches")}


def instruction(hlo_line: str) -> Tuple[str, str, str]:
    """``%fusion.99 = f32[8,2048]{1,0} fusion(...)`` -> (``%fusion.99``,
    ``f32[8,2048]{1,0}``, what follows the shape)."""
    lhs, sep, rhs = hlo_line.partition(" = ")
    if not sep:
        return lhs.split(" ", 1)[0], "", ""
    if rhs.startswith("("):             # a tuple's shapes, to its close
        end = rhs.find(") ")
        return lhs, rhs[:end + 1], rhs[end + 2:]
    shape, _, rest = rhs.partition(" ")
    return lhs, shape, rest


def operands(rest: str) -> List[Tuple[str, str]]:
    """``copy(f32[8,4]{1,0} %slice.2), backend_config=...`` -> ``[("f32[8,4]
    {1,0}", "%slice.2")]``: what stands between the operation's own
    brackets, each with its shape where the line gives one."""
    start = rest.find("(")
    depth, end = 0, len(rest)
    for i in range(start, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if depth == 0:
            end = i
            break
    return re.findall(
        r"(?:([a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?) )?(%[\w.\-]+)",
        rest[start + 1:end]) if start >= 0 else []


def kind(name: str) -> str:
    """``%convolution_multiply_fusion.2`` -> ``convolution_multiply_fusion``."""
    return re.sub(r"\.\d+$", "", name.lstrip("%"))


def folded(path: str) -> str:
    """``text/layer3/ssm_proj`` -> ``text/layer*/ssm_proj``."""
    return "/".join(re.sub(r"(?<=[a-z_])\d+$", "*", part)
                    for part in path.split("/"))


def selects(pattern: str):
    """Whether a scope path lies at or below ``pattern`` (the harness's own
    reading of ``*``)."""
    if pattern == S.UNSCOPED:
        return lambda path: path == ""
    depth, at = pattern.count("/") + 1, S.digits_re(pattern)
    return lambda path: bool(at.match("/".join(path.split("/")[:depth])))


def listing(trace: Dict[str, Any], vocabulary: S.Vocabulary, pattern: str,
            batches: Optional[int] = None, top: int = 40) -> Dict[str, Any]:
    """The rows of ``pattern`` by device time, longest first; see the
    module's docstring."""
    w0, w1 = trace["window"]
    batches = batches or trace["batches"]
    if not batches:
        raise SystemExit("no completed batch in the slice: give --batches")
    wanted = selects(pattern)
    # a launch repeats every line and every op_name: cut each once
    cut = functools.lru_cache(maxsize=None)(instruction)
    path_of = functools.lru_cache(maxsize=None)(
        lambda op: S.scope_path(op, vocabulary))
    produced: Dict[Tuple[str, str], set] = {}
    for plane, line, _a, _d, op in trace["ops"]:
        produced.setdefault((plane, cut(line)[0]), set()).add(
            folded(path_of(op)) or S.UNSCOPED)
    rows: Dict[Tuple[str, str, str], Dict[str, Any]] = {}
    total = 0.0
    for plane, line, a, d, op in trace["ops"]:
        path = path_of(op)
        clipped = min(a + d, w1) - max(a, w0)
        if clipped <= 0 or not wanted(path):
            continue
        name, shape, rest = cut(line)
        row = rows.setdefault((kind(name), shape, folded(path)), {
            "count": 0, "ns": 0.0, "op_name": op, "operands": []})
        row["count"] += 1
        row["ns"] += clipped
        total += clipped
        if pattern == S.UNSCOPED and not row["operands"]:
            for shape_of, operand in operands(rest):
                scopes = sorted(produced.get((plane, operand), ())) or ["-"]
                row["operands"].append(
                    f"{shape_of} {operand} <- {' | '.join(scopes)}".strip())
    out = [{"kind": k, "shape": shape, "scope": path or S.UNSCOPED,
            "count": r["count"],
            "ms_per_batch": r["ns"] / 1e6 / batches,
            "op_name": r["op_name"],
            **({"operands": r["operands"]} if r["operands"] else {})}
           for (k, shape, path), r in rows.items()]
    out.sort(key=lambda r: -r["ms_per_batch"])
    return {"scope": pattern, "batches": batches,
            "ms_per_batch": total / 1e6 / batches, "kinds": len(out),
            "rows": out[:top]}


def show(found: Dict[str, Any]) -> None:
    print(f"{found['scope']}: {found['ms_per_batch']:.3f} ms a batch over "
          f"{found['batches']} batches, {found['kinds']} kinds of operation")
    for r in found["rows"]:
        print(f"  {r['ms_per_batch']:9.3f} ms  x{r['count']:<5d} "
              f"{r['kind']}  {r['shape'][:70]}  [{r['scope']}]")
        for operand in r.get("operands", ()):
            print(f"      from {operand[:150]}")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("trace")
    ap.add_argument("--builder", required=True,
                    help="benchmarks/configs/<builder>.py, for its VOCABULARY")
    ap.add_argument("--scope", action="append", required=True)
    ap.add_argument("--batches", type=int, default=None)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from benchmarks.harness import spec

    vocabulary = deepened(spec.builder({"builder": args.builder}).VOCABULARY)
    trace = (read_events if args.trace.endswith(".json")
             else read_trace)(args.trace)
    found = [listing(trace, vocabulary, pattern, args.batches, args.top)
             for pattern in args.scope]
    for f in found:
        show(f)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"builder": args.builder, "listings": found}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
