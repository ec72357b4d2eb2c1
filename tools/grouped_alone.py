"""The routed experts' two grouped kernels ALONE on the chip, at candidate
tiles: the sweep ``ops/grouped_matmul.gmm_tiling``'s constants come from.

    chiprun -- python3 tools/grouped_alone.py --out chiprun_out/grouped_alone.json
    JAX_PLATFORMS=cpu python3 tools/grouped_alone.py --aot     # no chip

For each routed encoder (OLMoE, ZAYA1, Laguna, JoyAI) at both capacity
rungs of its cell's bucket (three quarters of the slots, every slot), for
the fused gate + up + SiLU kernel (``gated_gmm``) and for down's
``megablox.gmm``, under even and skewed groups of the cell's real rows: the
time of one call at each candidate ``(tm, tk, tn)`` (host clock round
``REPEATS`` x ``CALLS`` calls that end in ``block_until_ready``, the
fastest repeat; the kernels take 1-10 ms, a dispatch ~0.03), whether its
result is bit-equal on the real rows to the result at the tile the parent's
rule gave (and, with K in one block, to the first such candidate's: where
the parent split K, no whole-K tile can equal it), and its largest distance
from the XLA form (``ragged_dot``).
The candidates: the parent's tile (PR 46's ``gmm_tiling``, kept here as
``parent_tiling``), what the shipped rule picks, and every row tile of
128 / 256 / 512 with K whole against N whole, N's widest proper divisor in
lane tiles, and the parent's N.

``--small`` adds the same calls at the shapes of the programs under a
cell's bucket, which no cell times. ``--aot`` compiles each candidate for a
DESCRIBED v5e instead (nothing runs): which tiles Mosaic takes inside the
budget their call names. ``--rehearse`` runs the script end to end on a
CPU at a tiny shape, the kernels interpreted.

Not part of the package's import graph and not under ``benchmarks/``: a
builder's instrument (ROADMAP D18). The next user is S12's row movement,
whose candidates want the same shapes, group layouts and clock.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox import gmm

from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    LANES,
    gated_gmm,
    gmm_tiling,
    grouped_matmul_reference,
)

# encoder: (rows at the 3/4 rung, at every slot), held groups, hidden,
# one expert's width, (real rows at each rung: what the cell's traffic
# leaves of the rung once padding and absent pairs have sorted last)
SITES = {
    "olmoe": ((196608, 262144), 64, 2048, 1024, (170000, 229000)),
    "zaya1": ((24576, 32768), 16, 2048, 2048, (21300, 28700)),
    "laguna": ((122880, 163840), 64, 3072, 1024, (28000, 36000)),
    "joyai": ((98304, 131072), 256, 2048, 768, (79000, 108000)),
}
# --small: the same calls in the programs UNDER a cell's bucket, which no
# cell times (core/batching.BATCH_BUCKETS x text_split.capacities): OLMoE's
# and ZAYA1's bucket 32 at both rungs, OLMoE's bucket 8 and ZAYA1's bucket
# 128, JoyAI's and Laguna's bucket 1 (one rung: under 4,096 slots) — 64 to
# 1,024 rows a group by rows // groups
SMALL = {
    "olmoe_b32": ((24576, 32768), 64, 2048, 1024, (21000, 28000)),
    "olmoe_b8": ((8192,), 64, 2048, 1024, (5500,)),
    "zaya1_b128": ((12288, 16384), 16, 2048, 2048, (10600, 14300)),
    "zaya1_b32": ((3072, 4096), 16, 2048, 2048, (2700, 3600)),
    "laguna_b1": ((20480,), 64, 3072, 1024, (3500,)),
    "joyai_b1": ((16384,), 256, 2048, 768, (10000,)),
}
# --rehearse: the script end to end on a CPU, the kernels interpreted
TINY = {"tiny": ((1024, 2048), 4, 256, 384, (800, 1500))}
SHAPES = {**SITES, **SMALL, **TINY}
RUNGS = ("three_quarters", "every_slot")
ROW_TILES = (128, 256, 512)
REPEATS, CALLS = 3, 5


def parent_tiling(m: int, k: int, n: int):
    """PR 46's rule: power-of-two tiles, rows to 512, K to 2048, N under a
    million-element block."""
    def largest(size, limit):
        tile = LANES
        while tile * 2 <= limit and size % (tile * 2) == 0:
            tile *= 2
        return tile

    tk = largest(k, 2048)
    return largest(m, 512), tk, largest(n, 1024 * 1024 // tk)


def candidates(m: int, k: int, n: int, groups: int, gated: bool):
    """The parent's tile first, then the rule's, then the grid."""
    parent = parent_tiling(m, k, n)
    lanes = n // LANES
    proper = max(d for d in range(1, lanes) if lanes % d == 0) * LANES \
        if lanes > 1 else n
    out = [parent, gmm_tiling(m, k, n, groups, gated=gated)]
    for tn in (n, proper, parent[2]):
        for tm in ROW_TILES:
            if m % tm == 0:
                out.append((tm, k, tn))
    return list(dict.fromkeys(out))


def group_sizes(rng, groups: int, real: int, concentration: float):
    return rng.multinomial(
        real, rng.dirichlet(np.full(groups, concentration))).astype(np.int32)


def make_call(gated: bool, tiling, interpret: bool = False):
    if gated:
        return jax.jit(lambda x, a, b, s: gated_gmm(
            x, a, b, s, out_dtype=jnp.dtype(jnp.bfloat16), tiling=tiling,
            interpret=interpret))
    return jax.jit(lambda x, a, b, s: gmm(x, a, s, jnp.float32, tiling,
                                          interpret=interpret))


def xla_form(gated: bool):
    if gated:
        return jax.jit(lambda x, a, b, s: (
            jax.nn.silu(grouped_matmul_reference(x, a, s))
            * grouped_matmul_reference(x, b, s)).astype(jnp.bfloat16))
    return jax.jit(lambda x, a, b, s: grouped_matmul_reference(x, a, s))


def timed_ms(fn, *args) -> float:
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / CALLS * 1e3)
    return best


def compare(real: int):
    @jax.jit
    def fn(got, want):
        a = got[:real].astype(jnp.float32)
        b = want[:real].astype(jnp.float32)
        return jnp.all(a == b), jnp.max(jnp.abs(a - b))
    return fn


def operands(gated: bool, m: int, groups: int, k: int, n: int):
    keys = jax.random.split(jax.random.PRNGKey(47), 3)

    def draw(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale
                ).astype(jnp.bfloat16)

    x = draw(keys[0], (m, k), 1.0 if gated else 0.05)
    a = draw(keys[1], (groups, k, n), 0.02)
    # down has one matrix: the second operand is unused
    b = draw(keys[2], (groups, k, n), 0.02) if gated else a
    return x, a, b


def sweep(encoders, out_path, interpret=False):
    rng = np.random.default_rng(47)
    device = jax.devices()[0]
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "clock": f"host, fastest of {REPEATS} x {CALLS} calls",
              "sites": {}}
    for encoder in encoders:
        rungs, groups, hidden, width, reals = SHAPES[encoder]
        for rung, m, real in zip(RUNGS[-len(rungs):], rungs, reals):
            layouts = {"even": group_sizes(rng, groups, real, 1e6),
                       "skewed": group_sizes(rng, groups, real, 8.0)}
            for gated, (k, n) in ((True, (hidden, width)),
                                  (False, (width, hidden))):
                kernel = "gated" if gated else "down"
                site = result["sites"][f"{encoder}.{rung}.{kernel}"] = {
                    "m": m, "k": k, "n": n, "groups": groups, "real": real,
                    "parent": list(parent_tiling(m, k, n)),
                    "rule": list(gmm_tiling(m, k, n, groups, gated=gated)),
                    "layouts": {}}
                x, a, b = operands(gated, m, groups, k, n)
                calls = {t: make_call(gated, t, interpret)
                         for t in candidates(m, k, n, groups, gated)}
                same = compare(real)
                for layout, sizes in layouts.items():
                    s = jnp.asarray(sizes)
                    rows = site["layouts"][layout] = {
                        "largest_over_mean": float(sizes.max() / sizes.mean()),
                        "tiles": {}}
                    want = xla = whole = None
                    for tiling, fn in calls.items():
                        name = "x".join(map(str, tiling))
                        try:
                            ms = timed_ms(fn, x, a, b, s)
                            got = fn(x, a, b, s)
                            if want is None:       # the parent's tile
                                want = got
                                xla = xla_form(gated)(x, a, b, s)
                            equal, _ = same(got, want)
                            _, far = same(got, xla)
                            rows["tiles"][name] = {
                                "ms": ms,
                                "bit_equal_to_parent_tile": bool(equal),
                                "max_abs_from_xla": float(far)}
                            if tiling[1] == k:
                                # K in one block: every such tile alike
                                if whole is None:
                                    whole = got
                                rows["tiles"][name][
                                    "bit_equal_to_first_whole_k"] = bool(
                                        same(got, whole)[0])
                            del got
                        except Exception as e:  # noqa: BLE001 — refused
                            rows["tiles"][name] = {"error": str(e)[-300:]}
                    del want, xla, whole
                    print(encoder, rung, kernel, layout,
                          json.dumps(rows["tiles"]), flush=True)
                del x, a, b, calls
                gc.collect()
                os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
                with open(out_path, "w") as f:
                    json.dump(result, f, indent=1)
    return result


def aot(encoders):
    """Each candidate compiled for a described v5e: ``ok`` or the
    compiler's refusal."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for encoder in encoders:
        rungs, groups, hidden, width, _ = SHAPES[encoder]
        for rung, m in zip(RUNGS[-len(rungs):], rungs):
            for gated, (k, n) in ((True, (hidden, width)),
                                  (False, (width, hidden))):
                for tiling in candidates(m, k, n, groups, gated):
                    w = sds((groups, k, n), jnp.bfloat16)
                    try:
                        make_call(gated, tiling).lower(
                            sds((m, k), jnp.bfloat16), w, w,
                            sds((groups,), jnp.int32)).compile()
                        verdict = "ok"
                    except Exception as e:  # noqa: BLE001
                        verdict = "REFUSED " + str(e).strip()[-160:].replace(
                            "\n", " ")
                    print(encoder, rung, "gated" if gated else "down",
                          tiling, verdict, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--encoders", nargs="*", default=None)
    ap.add_argument("--small", action="store_true",
                    help="the small buckets' shapes after the cells'")
    ap.add_argument("--out", default="chiprun_out/grouped_alone.json")
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.encoders is None:
        args.encoders = sorted(SITES) + (sorted(SMALL) if args.small else [])
    if args.rehearse:
        sweep(sorted(TINY), args.out, interpret=True)
    elif args.aot:
        aot(args.encoders)
    else:
        sweep(args.encoders, args.out)


if __name__ == "__main__":
    main()
