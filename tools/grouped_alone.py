"""The routed experts' two grouped kernels ALONE on the chip, at candidate
tiles: the sweep ``ops/grouped_matmul.gmm_tiling``'s constants come from.

    chiprun -- python3 tools/grouped_alone.py --out chiprun_out/grouped_alone.json
    chiprun -- python3 tools/grouped_alone.py --combine --out chiprun_out/x.json
    chiprun -- python3 tools/grouped_alone.py --relu2 --out chiprun_out/y.json
    chiprun -- python3 tools/grouped_alone.py --dispatch --out chiprun_out/z.json
    JAX_PLATFORMS=cpu python3 tools/grouped_alone.py --aot     # no chip

For each routed encoder (OLMoE, ZAYA1, Laguna, JoyAI) at both capacity
rungs of its cell's bucket (three quarters of the slots, every slot), for
the fused gate + up + SiLU kernel (``gated_gmm``) and for down's
``down_gmm``, under even and skewed groups of the cell's real rows: the
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

``--combine`` (PR 48) times one layer's **down + way home** as a pair
instead, alone on the chip, at the same sites with each encoder's ``top_k``
(8 / 1 / 10 / 8) and Laguna's held share, ``home`` from a real stable sort:
(a) the parent's form — ``megablox.gmm`` into ``f32[M, H]``, ``out[home]``,
the weighted sum; (x) ``down_gmm`` into ``f32[M, H / 128, 128]`` and XLA's
gather and sum on the 3-D array; (y) ``down_gmm`` and
``ops.combine.combine_rows`` at 32 to 256 tokens a step; beside them down
alone by spelling of its store (against ``megablox.gmm`` at the parent's
tile and at the rule's) and the way home alone on a result that is already
there (ns a fetched row, ns an issued pair). ``tools/grouped_alone_pr48.json``
is its output; ``--aot`` and ``--rehearse`` work with it.

``--relu2`` (PR 51) times the UNGATED first half (``relu2_gmm``,
Nemotron-3-Nano's experts: 128 groups, 2,688 -> 1,856 = 14 1/2 lane tiles)
alone at the cell's two rungs in three forms: the parent's ``[G, K, N]``
kernel fed a ROW-MAJOR array (``plain_row_major``: the kernel with nothing
before it), the same fed the array as the device holds a ``[G, K, N]``
parameter of that shape — its lane-multiple side, K, innermost — so that
the program re-lays it out first (``plain_as_held``: the copy is timed), and
the shipped ``[G, N, K]`` form fed ``swapaxes`` of that array
(``transposed``). ``tools/grouped_alone_pr51.json`` is its output; ``--aot``
(with each form's temporaries and whether the compiled text holds a ``copy``
of the matrices) and ``--rehearse`` work with it.

``--dispatch`` (PR 53) times the way OUT alone — the tokens' rows gathered
into expert order — at the (source rows, pairs, hidden, ``top_k``) of the
seven routed cells' programs, both rungs: XLA's cast and gather
(``x.astype(bf16)[src]``, what the parent traces), the gather alone from a
source that is already bfloat16, the cast and the re-laying alone in XLA
(``ops.dispatch.row_pieces``) and as one kernel (``lay_rows``), and
``ops.dispatch.dispatch_rows`` behind ``lay_rows`` at 256 to
1,024 output rows a step, fetching every row and fetching only the rows
under ``sum(group_sizes)``; ``src`` from a real stable sort, bit-equality on
the rows of a group beside each time. For the record and the next issue,
XLA's gather of 262,144 rows out of ``bf16[32768, 1024]`` beside
``bf16[32768, 2048]`` and ``bf16[24576, 2048]`` (and sources of 112 and
114 MiB): whether its pace follows the source's size.
``tools/grouped_alone_pr53.json`` is its output, and
``ops.dispatch.XLA_KEEPS_BYTES`` is read off it; ``--aot`` and
``--rehearse`` work with it.

Not part of the package's import graph and not under ``benchmarks/``: a
builder's instrument (ROADMAP D18). The next users are what is left of S12:
ZAYA1's compaction gather and ``routed_block``'s scatter home, whose
candidates want the same shapes, group layouts and clock.
"""

from __future__ import annotations

import argparse
import functools
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

from realtime_fraud_detection_tpu.ops.combine import (
    TOKEN_TILES,
    combine_rows,
    combine_tokens,
    weighted_combine_reference,
)
from realtime_fraud_detection_tpu.ops.dispatch import (
    dispatch_reference,
    dispatch_rows,
    dispatch_supported,
    dispatch_takes,
    lay_rows,
    row_pieces,
)
from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    LANES,
    down_gmm,
    gated_gmm,
    gmm_tiling,
    grouped_matmul_reference,
    relu2_gmm,
)

# the package exports the FUNCTION ``ops.grouped_matmul`` under its module's
# name: the module itself, for the scaffold the spellings are built on
gm = sys.modules["realtime_fraud_detection_tpu.ops.grouped_matmul"]

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
    # down's result block is 3-D: all of N, or whole sublane tiles of it
    return [t for t in dict.fromkeys(out)
            if gated or t[2] in gm.down_widths(n)]


def group_sizes(rng, groups: int, real: int, concentration: float):
    return rng.multinomial(
        real, rng.dirichlet(np.full(groups, concentration))).astype(np.int32)


def make_call(gated: bool, tiling, interpret: bool = False):
    if gated:
        return jax.jit(lambda x, a, b, s: gated_gmm(
            x, a, b, s, out_dtype=jnp.dtype(jnp.bfloat16), tiling=tiling,
            interpret=interpret))
    return jax.jit(lambda x, a, b, s: down_gmm(x, a, s, tiling=tiling,
                                               interpret=interpret))


def xla_form(gated: bool):
    if gated:
        return jax.jit(lambda x, a, b, s: (
            jax.nn.silu(grouped_matmul_reference(x, a, s))
            * grouped_matmul_reference(x, b, s)).astype(jnp.bfloat16))
    return jax.jit(lambda x, a, b, s: grouped_matmul_reference(
        x, a, s).reshape(x.shape[0], -1, LANES))


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


# ------------------------------------------------------------------ --combine
# encoder: a token's experts, the router's width (the held groups of SITES
# are numbered from 0), and the real tokens at each rung
ROUTING = {
    "olmoe": (8, 64, (21300, 28700)),
    "zaya1": (1, 16, (21300, 28700)),
    "laguna": (10, 256, (11500, 15400)),
    "joyai": (8, 256, (9900, 13500)),
    "tiny": (2, 8, (400, 750)),
}


def pr47_down_tiling(m: int, k: int, n: int, groups: int):
    """The parent's rule for ``megablox.gmm`` (PR 47): K whole, N as wide
    as 13 MB hold, 256 rows where ``m // groups`` reaches 2,048."""
    def room(tm, tk, tn):
        return 2 * (tm * tk + tk * tn) * 2 + 2 * tm * tn * 4

    def widest(sides, tile):
        return next((t for t in sides if room(*tile(t)) <= 13 << 20), LANES)

    tk = widest(gm._lane_divisors(k), lambda t: (LANES, t, LANES))
    tn = widest(gm._lane_divisors(n), lambda t: (LANES, tk, t))
    rows = [t for t in (256, 128) if m % t == 0 and t * 8 <= m // groups]
    return widest(rows, lambda t: (t, tk, tn)), tk, tn


def routed(key, tokens: int, real: int, top_k: int, width: int, held: int,
           concentration: float):
    """What ``models.olmoe.apply_experts`` computes under its ``router``
    scope for ``tokens`` slots of which the first ``real`` are real, each
    with ``top_k`` distinct experts of ``width`` drawn by a popularity from
    ``dirichlet(concentration)``, the experts numbered under ``held`` held
    here: ``(group_sizes i32[held], home i32[tokens, top_k], weights,
    valid, real)`` from a real stable sort."""
    k_pop, k_draw, k_w = jax.random.split(key, 3)
    popularity = jnp.log(jax.random.dirichlet(
        k_pop, jnp.full((width,), concentration)))
    # Gumbel top-k: top_k distinct experts a token by the popularity
    noise = jax.random.gumbel(k_draw, (tokens, width))
    experts = jax.lax.top_k(popularity[None] + noise, top_k)[1].astype(
        jnp.int32)
    weights = jax.random.uniform(k_w, (tokens, top_k), jnp.float32)
    valid = (experts < held) & (jnp.arange(tokens) < real)[:, None]
    flat = jnp.where(valid, experts, held).reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    sizes = jnp.sum(flat[:, None] == jnp.arange(held, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
    home = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    return (sizes, home.reshape(tokens, top_k), weights, valid,
            jnp.arange(tokens) < real)


def parent_way_home(out, home, weights, valid, real, share: bool):
    """``models.olmoe.apply_experts``' combine as the parent traces it, on
    ``megablox.gmm``'s ``f32[M, H]``: ``out[home]``, the weights, the sum
    (a share of the experts: expert-major, absent pairs selected out), the
    other rows selected to zero."""
    n, top_k = home.shape
    if share:
        back = out[home.T.reshape(-1)].reshape(top_k, n, -1)
        back = jnp.where(valid.T[:, :, None], back, 0.0)
        y = jnp.sum(back * weights.T[:, :, None], axis=0)
    else:
        back = out[home.reshape(-1)].reshape(n, top_k, -1)
        y = jnp.sum(back * weights[:, :, None], axis=1)
    return jnp.where(real[:, None], y, 0.0)


def _down_body(spelling: str):
    """down's kernel body in the spellings ISSUE 48 names, on the package's
    scaffold: ``strided`` (what ships: a lane tile at a time, a store with
    a sublane stride), ``reshape`` (one 3-D store of the reshaped result),
    ``dots`` (a 128-wide dot a lane tile), ``whole`` (strided, and a tile
    that one group owns whole is stored without reading what it held)."""
    from jax.experimental import pallas as pl

    if spelling == "strided":
        return gm._down_kernel

    def body(offsets, group_ids, row_tiles, lhs, w, out, *accs, tm, tn,
             tiles_k):
        chunks = tn // LANES
        visit = pl.program_id(1)
        if spelling == "dots":
            assert tiles_k == 1
            mine = gm._own_rows(visit, offsets, group_ids, row_tiles, tm,
                                (tm, LANES))
            x = lhs[...]
            for c in range(chunks):
                out[:, c, :] = jnp.where(mine, jnp.dot(
                    x, w[:, c * LANES:(c + 1) * LANES],
                    preferred_element_type=jnp.float32), out[:, c, :])
            return

        def store(result):
            if spelling == "reshape":
                mine = gm._own_rows(visit, offsets, group_ids, row_tiles,
                                    tm, (tm, chunks, LANES))
                out[...] = jnp.where(
                    mine, result.reshape(tm, chunks, LANES), out[...])
                return
            group = group_ids[visit]
            first = row_tiles[visit] * tm
            whole = (offsets[group] <= first) & (
                first + tm <= offsets[group + 1])

            @pl.when(whole)
            def _():
                for c in range(chunks):
                    out[:, c, :] = result[:, c * LANES:(c + 1) * LANES]

            @pl.when(jnp.logical_not(whole))
            def _():
                mine = gm._own_rows(visit, offsets, group_ids, row_tiles,
                                    tm, (tm, LANES))
                for c in range(chunks):
                    out[:, c, :] = jnp.where(
                        mine, result[:, c * LANES:(c + 1) * LANES],
                        out[:, c, :])

        gm._over_k((jnp.dot(lhs[...], w[...],
                            preferred_element_type=jnp.float32),),
                   accs, tiles_k, store)
    return body


SPELLINGS = ("strided", "reshape", "dots", "whole")


def down_spelled(spelling: str, tiling, interpret: bool = False):
    def call(lhs, rhs, sizes):
        m, k = lhs.shape
        n = rhs.shape[-1]
        tm, tk, tn = tiling
        return gm._grouped_call(
            _down_body(spelling), "down_gmm", lhs, (rhs,), sizes, tiling,
            out_shape=jax.ShapeDtypeStruct((m, n // LANES, LANES),
                                           jnp.float32),
            out_block=(tm, tn // LANES, LANES),
            out_index=lambda row_tile, n_i: (row_tile, n_i, 0),
            vmem=gm.down_vmem_bytes(tm, tk, tn), flops_per_mkn=2,
            transcendentals=0, interpret=interpret)
    return jax.jit(call)


def combine_programs(encoder, rung_index, interpret, spellings):
    """One (encoder, rung)'s programs: ``(site, pair, alone, home_alone)``
    — the pair by form ``(act, w, sizes, home, weights, valid, real) -> y``,
    down alone ``(act, w, sizes) -> out`` by spelling beside
    ``megablox.gmm``, and the way home alone ``(out, home, weights, valid,
    real) -> y`` by form."""
    rungs, groups, hidden, width, _ = SHAPES[encoder]
    top_k, router_width, reals = ROUTING[encoder]
    m, real = rungs[rung_index], reals[rung_index]
    share = router_width != groups
    tokens = m // top_k
    token_tiles = [t for t in sorted(TOKEN_TILES) if tokens % t == 0
                   and 2 * top_k * t * hidden * 4 <= 48 << 20]
    parent_tile = pr47_down_tiling(m, width, hidden, groups)
    tile = gmm_tiling(m, width, hidden, groups)
    site = {"rows": m, "tokens": tokens, "top_k": top_k, "hidden": hidden,
            "width": width, "groups": groups, "router_width": router_width,
            "real_tokens": real, "parent_tile": list(parent_tile),
            "tile": list(tile),
            "rule_tokens": combine_tokens(tokens, top_k, hidden)}

    def megablox(tiling):
        return lambda act, w, sizes: gmm(act, w, sizes, jnp.float32, tiling,
                                         interpret=interpret)

    def down(act, w, sizes):
        return down_gmm(act, w, sizes, tiling=tile, interpret=interpret)

    home_alone = {
        "a_parent": functools.partial(parent_way_home, share=share),
        "x_gather3d": lambda out, home, weights, valid, real:
            weighted_combine_reference(out, home, weights, valid)}
    for tm in token_tiles:
        home_alone[f"y_tm{tm}"] = (
            lambda out, home, weights, valid, real, tm=tm: combine_rows(
                out, jnp.where(valid, home, -1),
                jnp.where(valid, weights, 0.0), tokens=tm,
                interpret=interpret))
    pair = {name: (lambda act, w, sizes, *rest, name=name, fn=fn: fn(
        (megablox(parent_tile) if name == "a_parent" else down)(
            act, w, sizes), *rest)) for name, fn in home_alone.items()}
    alone = {"megablox": megablox(parent_tile)}
    if tile != parent_tile:
        alone["megablox_at_tile"] = megablox(tile)
    for spelling in spellings:
        alone[f"down_{spelling}"] = down_spelled(spelling, tile, interpret)
    jit = lambda fns: {name: jax.jit(fn) for name, fn in fns.items()}
    return site, jit(pair), jit(alone), jit(home_alone)


def combine_sweep(encoders, out_path, interpret=False, spellings=SPELLINGS):
    """One layer's down + way home, alone on the chip: the pair by form,
    down alone by spelling beside ``megablox.gmm``, the way home alone (on
    a result that is already there) by form; under even and skewed groups,
    ``home`` from a real stable sort."""
    device = jax.devices()[0]
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "clock": f"host, fastest of {REPEATS} x {CALLS} calls",
              "sites": {}}
    draw = jax.jit(routed, static_argnums=(1, 2, 3, 4, 5, 6))

    def timed(rows, fns, args, note):
        first = None
        for name, fn in fns.items():
            try:
                ms = timed_ms(fn, *args(name))
                got = fn(*args(name))
                first = got if first is None else first
                rows[name] = {"ms": ms, **note(got, first)}
                del got
            except Exception as e:  # noqa: BLE001 — refused
                rows[name] = {"error": str(e)[-300:]}

    for encoder in encoders:
        for index, rung in enumerate(RUNGS[-len(SHAPES[encoder][0]):]):
            site, pair, alone, home_alone = combine_programs(
                encoder, index, interpret, spellings)
            m, tokens = site["rows"], site["tokens"]
            act, w, _ = operands(False, m, site["groups"], site["width"],
                                 site["hidden"])
            site["layouts"] = {}
            result["sites"][f"{encoder}.{rung}"] = site
            for layout, concentration in (("even", 1e6), ("skewed", 8.0)):
                sizes, home, weights, valid, real = draw(
                    jax.random.PRNGKey(48), tokens, site["real_tokens"],
                    site["top_k"], site["router_width"], site["groups"],
                    concentration)
                fetched, held = int(jnp.sum(valid)), int(jnp.sum(sizes))
                assert fetched == held
                row = site["layouts"][layout] = {
                    "fetched_rows": fetched,
                    "largest_over_mean": float(
                        jnp.max(sizes) / jnp.maximum(jnp.mean(sizes), 1)),
                    "pair": {}, "down_alone": {}, "home_alone": {}}
                way = (home, weights, valid, real)
                timed(row["pair"], pair, lambda name: (act, w, sizes, *way),
                      lambda got, first: {"max_abs_from_parent": float(
                          jnp.max(jnp.abs(got - first)))})
                timed(row["down_alone"], alone,
                      lambda name: (act, w, sizes),
                      lambda got, first: {"bit_equal_to_megablox": bool(
                          jnp.all(got.reshape(m, -1)[:held]
                                  == first.reshape(m, -1)[:held]))})
                outs = {"a_parent": alone["megablox"](act, w, sizes)}
                out3 = down_gmm(act, w, sizes, tiling=tuple(site["tile"]),
                                interpret=interpret)
                timed(row["home_alone"], home_alone,
                      lambda name: (outs.get(name, out3), *way),
                      lambda got, first: {})
                for name, cell in row["home_alone"].items():
                    if "ms" in cell:
                        cell["ns_per_fetched_row"] = (
                            cell["ms"] * 1e6 / max(fetched, 1))
                        cell["ns_per_issued_row"] = cell["ms"] * 1e6 / m
                del outs, out3
                print(encoder, rung, layout, json.dumps(row), flush=True)
                os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
                with open(out_path, "w") as f:
                    json.dump(result, f, indent=1)
            del act, w, pair, alone, home_alone
            gc.collect()
    return result


def combine_aot(encoders, spellings=SPELLINGS):
    """Every program of ``combine_sweep`` compiled for a described v5e."""
    chip = described_chip()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for encoder in encoders:
        for index, rung in enumerate(RUNGS[-len(SHAPES[encoder][0]):]):
            site, pair, alone, home_alone = combine_programs(
                encoder, index, False, spellings)
            m, tokens, top_k = site["rows"], site["tokens"], site["top_k"]
            chunks = site["hidden"] // LANES
            down = (sds((m, site["width"]), jnp.bfloat16),
                    sds((site["groups"], site["width"], site["hidden"]),
                        jnp.bfloat16), sds((site["groups"],), jnp.int32))
            way = (sds((tokens, top_k), jnp.int32),
                   sds((tokens, top_k), jnp.float32),
                   sds((tokens, top_k), jnp.bool_), sds((tokens,), jnp.bool_))
            for kind, fns, args in (
                    ("pair", pair, lambda name: down + way),
                    ("down_alone", alone, lambda name: down),
                    ("home_alone", home_alone, lambda name: (
                        sds((m, site["hidden"]) if name == "a_parent"
                            else (m, chunks, LANES), jnp.float32),) + way)):
                for name, fn in fns.items():
                    try:
                        fn.lower(*args(name)).compile()
                        verdict = "ok"
                    except Exception as e:  # noqa: BLE001
                        verdict = "REFUSED " + str(e).strip()[-160:].replace(
                            "\n", " ")
                    print(encoder, rung, kind, name, verdict, flush=True)


# ------------------------------------------------------------------- --relu2
# encoder: (pairs at the 3/4 rung, at every slot), groups, hidden, one
# expert's width, (real pairs at each rung: ~10,100 / ~13,500 real tokens of
# 12,288 / 16,384 slots, six experts each)
RELU2_SITES = {"nemotron3": ((73728, 98304), 128, 2688, 1856,
                             (60600, 81000))}
RELU2_TINY = {"tiny": ((1024, 2048), 4, 256, 176, (800, 1500))}
# the cell's ``expert_imbalance_x`` (largest group over the mean: ledger,
# PR 50); 128 groups drawn from dirichlet(1) until they read it within 4%
CELL_IMBALANCE = 5.0


def skewed_to(rng, groups: int, real: int, imbalance: float):
    for _ in range(1000):
        sizes = group_sizes(rng, groups, real, 1.0)
        if abs(sizes.max() / sizes.mean() / imbalance - 1) < 0.04:
            break
    return sizes


def _plain_relu2_kernel(offsets, group_ids, row_tiles, lhs, up_w, out, *accs,
                        tm, tn, tiles_k):
    """The parent's body (PR 50): the group's ``[tk, tn]`` block of a
    ``[G, K, N]`` matrix, ``jnp.dot``."""
    from jax.experimental import pallas as pl

    visit = pl.program_id(1)

    def store(up):
        mine = gm._own_rows(visit, offsets, group_ids, row_tiles, tm,
                            (tm, tn))
        out[...] = jnp.where(mine, jnp.square(jnp.maximum(up, 0.0)),
                             out[...].astype(jnp.float32)).astype(out.dtype)

    gm._over_k((jnp.dot(lhs[...], up_w[...],
                        preferred_element_type=jnp.float32),),
               accs, tiles_k, store)


def relu2_forms(tiling, interpret: bool = False):
    """``{form: (x, w, sizes) -> bf16[M, N]``, ``w`` ``[G, K, N]`` in each:
    what the stored tree holds."""
    tm, tk, tn = tiling

    def plain(x, w, sizes):
        return gm._grouped_call(
            _plain_relu2_kernel, "relu2_gmm", x, (w,), sizes, tiling,
            out_shape=jax.ShapeDtypeStruct((x.shape[0], w.shape[-1]),
                                           jnp.bfloat16),
            out_block=(tm, tn),
            out_index=lambda row_tile, n_i: (row_tile, n_i),
            vmem=gm.gated_vmem_bytes(tm, tk, tn, matrices=1),
            flops_per_mkn=2, transcendentals=0, interpret=interpret)

    def transposed(x, w, sizes):
        return relu2_gmm(x, jnp.swapaxes(w, 1, 2), sizes,
                         out_dtype=jnp.dtype(jnp.bfloat16), tiling=tiling,
                         interpret=interpret)

    return {"plain_row_major": plain, "plain_as_held": plain,
            "transposed": transposed}


def row_major(sharding):
    """``[G, K, N]`` with N innermost whatever its size: what a Mosaic call
    asks of its operand."""
    from jax.experimental.layout import Format, Layout

    return Format(Layout(major_to_minor=(0, 1, 2)), sharding)


def relu2_programs(tiling, sharding, interpret: bool = False):
    """``{form: (timed, whole)}``: ``timed`` returns one sublane tile of the
    result's rows (a ``bf16[M, 1856]`` program RESULT is re-laid out with M
    innermost, 0.36 GB each way, which no caller of the kernel pays: its
    result goes to down's call row-major), ``whole`` all of it for the
    comparison."""
    out = {}
    for form, fn in relu2_forms(tiling, interpret).items():
        kw = ({"in_shardings": (sharding, row_major(sharding), sharding)}
              if form == "plain_row_major" else {})
        out[form] = (jax.jit(lambda x, w, s, fn=fn: fn(x, w, s)[:gm.SUBLANES],
                             **kw), jax.jit(fn, **kw))
    return out


def relu2_tiles(m: int, k: int, n: int, groups: int):
    rule = gmm_tiling(m, k, n, groups, gated=True, matrices=1)
    return list(dict.fromkeys([rule] + [
        (tm, k, n) for tm in ROW_TILES[:2] if m % tm == 0]))


def relu2_sweep(sites, out_path, interpret=False):
    rng = np.random.default_rng(51)
    device = jax.devices()[0]
    sharding = jax.sharding.SingleDeviceSharding(device)
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "clock": f"host, fastest of {REPEATS} x {CALLS} calls",
              "sites": {}}
    for encoder, (rungs, groups, k, n, reals) in sites.items():
        for rung, m, real in zip(RUNGS[-len(rungs):], rungs, reals):
            site = result["sites"][f"{encoder}.{rung}.relu2"] = {
                "m": m, "k": k, "n": n, "groups": groups, "real": real,
                "rule": list(gmm_tiling(m, k, n, groups, gated=True,
                                        matrices=1)), "layouts": {}}
            x, w = operands(True, m, groups, k, n)[:2]
            try:
                w_rows = jax.device_put(w, row_major(sharding))
                site["row_major_layout"] = str(w_rows.format.layout)
            except Exception as e:  # noqa: BLE001 — no such layout here
                w_rows, site["row_major_layout"] = None, str(e)[-300:]
            site["held_layout"] = str(w.format.layout)
            want_fn = jax.jit(lambda x, w, s: jnp.square(jnp.maximum(
                grouped_matmul_reference(x, w, s), 0.0)).astype(jnp.bfloat16))
            same = compare(real)
            programs = {t: relu2_programs(t, sharding, interpret)
                        for t in relu2_tiles(m, k, n, groups)}
            for layout, sizes in (
                    ("even", group_sizes(rng, groups, real, 1e6)),
                    ("skewed", skewed_to(rng, groups, real, CELL_IMBALANCE))):
                s = jnp.asarray(sizes)
                rows = site["layouts"][layout] = {
                    "largest_over_mean": float(sizes.max() / sizes.mean()),
                    "tiles": {}}
                xla = want_fn(x, w, s)
                for tiling, forms in programs.items():
                    cell = rows["tiles"]["x".join(map(str, tiling))] = {}
                    parent = None
                    for form, (timed, whole) in forms.items():
                        arg = w_rows if form == "plain_row_major" else w
                        try:
                            if arg is None:
                                raise ValueError(site["row_major_layout"])
                            ms = timed_ms(timed, x, arg, s)
                            got = whole(x, arg, s)
                            parent = got if parent is None else parent
                            cell[form] = {
                                "ms": ms,
                                "bit_equal_to_plain": bool(
                                    same(got, parent)[0]),
                                "max_abs_from_xla": float(same(got, xla)[1])}
                            del got
                        except Exception as e:  # noqa: BLE001 — refused
                            cell[form] = {"error": str(e)[-300:]}
                    del parent
                print(encoder, rung, layout, json.dumps(rows["tiles"]),
                      flush=True)
                os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
                with open(out_path, "w") as f:
                    json.dump(result, f, indent=1)
            del x, w, w_rows, programs
            gc.collect()
    return result


def relu2_aot(sites):
    """Each form compiled for a described v5e: its temporaries, and whether
    the compiled text holds a ``copy`` that produces the matrices."""
    chip = described_chip()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for encoder, (rungs, groups, k, n, _) in sites.items():
        copies = [f" = bf16[{groups},{a},{b}]" for a, b in ((k, n), (n, k))]
        for rung, m in zip(RUNGS[-len(rungs):], rungs):
            for tiling in relu2_tiles(m, k, n, groups):
                for form, (_, whole) in relu2_programs(tiling, chip).items():
                    try:
                        compiled = whole.lower(
                            sds((m, k), jnp.bfloat16),
                            sds((groups, k, n), jnp.bfloat16),
                            sds((groups,), jnp.int32)).compile()
                        held = [line.strip()[:120]
                                for line in compiled.as_text().splitlines()
                                if " copy(" in line
                                and any(c in line for c in copies)]
                        verdict = "ok, temporaries %.2f GB, %s" % (
                            compiled.memory_analysis().temp_size_in_bytes
                            / 1e9, f"COPIES {held}" if held
                            else "no copy of the matrices")
                    except Exception as e:  # noqa: BLE001
                        verdict = "REFUSED " + str(e).strip()[-160:].replace(
                            "\n", " ")
                    print(encoder, rung, tiling, form, verdict, flush=True)


# ----------------------------------------------------------------- --dispatch
# cell's program: (source rows, a token's experts, hidden, the router's
# width, the experts held, real tokens) — a cell's two rungs; the real tokens
# are what the cell's traffic leaves of a rung (token_padding_pct: ledger,
# PR 51)
DISPATCH_SITES = {
    "olmoe.three_quarters": (24576, 8, 2048, 64, 64, 21300),
    "olmoe.every_slot": (32768, 8, 2048, 64, 64, 28150),
    "zaya1.three_quarters": (24576, 1, 2048, 16, 16, 21300),
    "zaya1.every_slot": (32768, 1, 2048, 16, 16, 28150),
    "laguna.three_quarters": (12288, 10, 3072, 256, 64, 11500),
    "laguna.every_slot": (16384, 10, 3072, 256, 64, 15400),
    "joyai.three_quarters": (12288, 8, 2048, 256, 256, 9900),
    "joyai.every_slot": (16384, 8, 2048, 256, 256, 13500),
    "nemotron3.three_quarters": (12288, 6, 2688, 128, 128, 10100),
    "nemotron3.every_slot": (16384, 6, 2688, 128, 128, 13500),
}
DISPATCH_TINY = {"tiny.every_slot": (256, 2, 256, 8, 8, 200)}
DISPATCH_ROWS = (256, 512, 1024)
TURNS = (4, 16)
# XLA's gather of OLMoE's every-slot pairs by the source's size: (source
# rows, hidden), 262,144 rows out of each
GATHER_SOURCES = ((32768, 1024), (32768, 2048), (24576, 2048),
                  # where the line falls (112 and 114 MiB)
                  (28672, 2048), (29184, 2048))


def dispatch_programs(site, interpret: bool = False):
    """``{form: (x, src, held) -> bf16[pairs, H]}`` for one program's
    shapes; ``x`` float32, as ``apply_experts`` holds it."""
    n, top_k, hidden, _, _, _ = site
    dtype = jnp.dtype(jnp.bfloat16)
    forms = {
        "xla_cast_and_gather": lambda x, src, held: dispatch_reference(
            x, src, dtype),
        "xla_gather_alone": lambda xb, src, held: xb[src]}
    if dispatch_takes(n * top_k, hidden, dtype.itemsize):
        forms["row_pieces_alone"] = lambda x, src, held: row_pieces(x, dtype)
        forms["lay_rows_alone"] = lambda x, src, held: lay_rows(
            x, dtype=dtype, interpret=interpret)
    if dispatch_takes(n * top_k, hidden, dtype.itemsize):
        for rows in DISPATCH_ROWS:
            if (n * top_k) % rows:
                continue
            for skip in (False, True):
                forms[f"kernel_tm{rows}" + ("_held" if skip else "_all")] = (
                    lambda x, src, held, rows=rows, skip=skip: dispatch_rows(
                        lay_rows(x, dtype=dtype, interpret=interpret), src,
                        held if skip else jnp.int32(src.shape[0]),
                        dtype=dtype, rows=rows, interpret=interpret))
        # the copies started a turn of the scalar loop (the module's
        # constant is read when the body is traced)
        for turn in TURNS:
            forms[f"kernel_tm1024_held_turn{turn}"] = functools.partial(
                _at_turn, turn, dtype, interpret)
    return {name: jax.jit(fn) for name, fn in forms.items()}


def _at_turn(turn, dtype, interpret, x, src, held):
    dm = sys.modules["realtime_fraud_detection_tpu.ops.dispatch"]
    shipped, dm.ROWS_A_TURN = dm.ROWS_A_TURN, turn
    try:
        return dispatch_rows.__wrapped__(
            lay_rows(x, dtype=dtype, interpret=interpret), src, held,
            dtype=dtype, rows=1024 if src.shape[0] % 1024 == 0 else 256,
            interpret=interpret)
    finally:
        dm.ROWS_A_TURN = shipped


def dispatch_sweep(sites, out_path, interpret=False, sources=GATHER_SOURCES):
    device = jax.devices()[0]
    result = {"device": {"platform": device.platform,
                         "kind": device.device_kind},
              "clock": f"host, fastest of {REPEATS} x {CALLS} calls",
              "sites": {}, "xla_gather_by_source": {}}
    draw = jax.jit(routed, static_argnums=(1, 2, 3, 4, 5, 6))

    def save():
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(result, f, indent=1)

    for name, site in sites.items():
        n, top_k, hidden, width, groups, real = site
        pairs = n * top_k
        sizes, home, _, _, _ = draw(jax.random.PRNGKey(53), n, real, top_k,
                                    width, groups, 8.0)
        # the token each sorted pair reads: the inverse of ``home``
        src = jnp.zeros((pairs,), jnp.int32).at[home.reshape(-1)].set(
            jnp.arange(pairs, dtype=jnp.int32) // top_k)
        held = jnp.sum(sizes)
        x = jax.random.normal(jax.random.PRNGKey(54), (n, hidden),
                              jnp.float32)
        row = result["sites"][name] = {
            "source_rows": n, "pairs": pairs, "hidden": hidden,
            "top_k": top_k, "held_pairs": int(held),
            "source_mib": n * hidden * 2 / 2 ** 20,
            "dispatch_supported": dispatch_supported(n, pairs, hidden, 2),
            "forms": {}}
        want = None
        for form, fn in dispatch_programs(site, interpret).items():
            arg = x.astype(jnp.bfloat16) if form == "xla_gather_alone" else x
            try:
                ms = timed_ms(fn, arg, src, held)
                cell = row["forms"][form] = {"ms": ms}
                if not form.endswith("_alone") or form.startswith("xla"):
                    cell["ns_per_row"] = ms * 1e6 / pairs
                    got = fn(arg, src, held)
                    want = got if want is None else want
                    cell["bit_equal_on_held_rows"] = bool(
                        compare(int(held))(got, want)[0])
                    del got
            except Exception as e:  # noqa: BLE001 — refused
                row["forms"][form] = {"error": str(e)[-300:]}
        del want, x
        print(name, json.dumps(row), flush=True)
        save()
        gc.collect()
    # for the record: does XLA's pace a row follow the source's size?
    rows = 262144 if not interpret else 512
    for n, hidden in sources:
        src = jax.random.randint(jax.random.PRNGKey(55), (rows,), 0, n)
        src = jnp.sort(src.reshape(64, -1), axis=1).reshape(-1)
        xb = jax.random.normal(jax.random.PRNGKey(56), (n, hidden),
                               jnp.float32).astype(jnp.bfloat16)
        ms = timed_ms(jax.jit(lambda xb, src: xb[src]), xb, src)
        result["xla_gather_by_source"][f"bf16[{n},{hidden}]"] = {
            "rows": rows, "source_mib": n * hidden * 2 / 2 ** 20, "ms": ms,
            "ns_per_row": ms * 1e6 / rows,
            "gb_per_s_written": rows * hidden * 2 / ms / 1e6}
        print(n, hidden, result["xla_gather_by_source"], flush=True)
        save()
        del xb
        gc.collect()
    return result


def dispatch_aot(sites):
    """Every program of ``dispatch_sweep`` compiled for a described v5e."""
    chip = described_chip()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for name, site in sites.items():
        n, top_k, hidden = site[:3]
        for form, fn in dispatch_programs(site).items():
            x = sds((n, hidden), jnp.bfloat16 if form == "xla_gather_alone"
                    else jnp.float32)
            try:
                compiled = fn.lower(x, sds((n * top_k,), jnp.int32),
                                    sds((), jnp.int32)).compile()
                verdict = "ok, temporaries %.2f GB" % (
                    compiled.memory_analysis().temp_size_in_bytes / 1e9)
            except Exception as e:  # noqa: BLE001
                verdict = "REFUSED " + str(e).strip()[-160:].replace(
                    "\n", " ")
            print(name, form, verdict, flush=True)


def described_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def aot(encoders):
    """Each candidate compiled for a described v5e: ``ok`` or the
    compiler's refusal."""
    chip = described_chip()

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
    ap.add_argument("--combine", action="store_true",
                    help="down + the way home as a pair, by form (PR 48)")
    ap.add_argument("--spellings", nargs="*", default=list(SPELLINGS))
    ap.add_argument("--relu2", action="store_true",
                    help="the ungated first half in three forms (PR 51)")
    ap.add_argument("--dispatch", action="store_true",
                    help="the way out: XLA's gather beside the row fetch "
                         "(PR 53)")
    args = ap.parse_args()
    if args.dispatch:
        if args.rehearse:
            dispatch_sweep(DISPATCH_TINY, args.out, interpret=True,
                           sources=((64, 128), (64, 256)))
        elif args.aot:
            dispatch_aot(DISPATCH_SITES)
        else:
            dispatch_sweep({k: v for k, v in DISPATCH_SITES.items()
                            if not args.encoders
                            or k.split(".")[0] in args.encoders}, args.out)
        return
    if args.relu2:
        if args.rehearse:
            relu2_sweep(RELU2_TINY, args.out, interpret=True)
        elif args.aot:
            relu2_aot(RELU2_SITES)
        else:
            relu2_sweep(RELU2_SITES, args.out)
        return
    if args.encoders is None:
        args.encoders = sorted(SITES) + (
            sorted(SMALL) if args.small and not args.combine else [])
    if args.combine:
        if args.rehearse:
            combine_sweep(sorted(TINY), args.out, interpret=True,
                          spellings=args.spellings)
        elif args.aot:
            combine_aot(args.encoders, args.spellings)
        else:
            combine_sweep(args.encoders, args.out, spellings=args.spellings)
    elif args.rehearse:
        sweep(sorted(TINY), args.out, interpret=True)
    elif args.aot:
        aot(args.encoders)
    else:
        sweep(args.encoders, args.out)


if __name__ == "__main__":
    main()
