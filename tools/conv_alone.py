"""Time the mixers' causal convolution + SiLU alone on the chip
(``ops/causal_conv.py``) at the three cells' shapes — 8 rows x 2,048
positions, four taps; Falcon-H1 4,096 | 512 | 512 and Nemotron-3-Nano 4,096 |
1,024 | 1,024 channels with a bias out of ``W_in``'s 9,248 / 10,304 from
channel 4,096 on, every part bfloat16; Qwen3-Next 2,048 | 2,048 | 4,096
without one, ``q`` and ``k`` float32 and ``v`` bfloat16 — the kernel beside
the XLA form (``models/falcon_h1.conv_silu_parts``) on a row-major array of
the convolved channels alone, at every channel tile and loop step asked
for, writing the parts itself or ONE float32 array that XLA then cuts; and,
for the two Mamba-2 shapes, the kernel reading the wide array where the TPU
holds it, positions last. Each form's distance from the XLA form stands
beside its time and the share of the HBM's rate its bytes come to.

    chiprun -- python3 tools/conv_alone.py --out chiprun_out/conv.json

``--rehearse`` runs it on the CPU at a tiny shape, in interpret mode.
``tools/conv_alone_pr55.json`` keeps PR 55's readings.
"""

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

HBM_BYTES_PER_S = 819e9
# (parts, bytes an element of each part is written in, bias, the width of
# the array the channels are read out of and their first channel in it)
SHAPES = {
    "falconh1": ((4096, 512, 512), (2, 2, 2), True, 9248, 4096),
    "nemotron3": ((4096, 1024, 1024), (2, 2, 2), True, 10304, 4096),
    "qwen3next": ((2048, 2048, 4096), (4, 4, 2), False, 8192, 0),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--repeats", type=int, default=40)
    ap.add_argument("--tiles", default="256,512")
    ap.add_argument("--strips", default="16,32,64",
                    help="positions a loop step takes, positions first")
    ap.add_argument("--steps",
                    default="512x16,1024x16,1024x32,2048x16,2048x32,2048x64",
                    help="positions x channels a loop step takes, "
                         "positions last")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.falcon_h1 import conv_silu_parts
    from realtime_fraud_detection_tpu.ops.causal_conv import (
        STEP,
        STEP_POSITIONS_LAST,
        _conv_pallas,
        conv_tiling,
    )

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("conv_alone: no TPU (use --rehearse on a CPU)")
    b, t, k = (1, 256, 4) if args.rehearse else (8, 2048, 4)
    tiles = [int(x) for x in args.tiles.split(",")]
    strips = [int(x) for x in args.strips.split(",")]
    steps = [tuple(int(n) for n in x.split("x"))
             for x in args.steps.split(",")]
    rows = []

    def timed(cell, form, fn, want, nbytes):
        got = jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for i in range(args.repeats):
            out = fn()
            if i % 4 == 3:          # a few in flight: the device stays busy
                jax.block_until_ready(out)
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) / args.repeats * 1e3
        err = max(float(jnp.abs(g.astype(jnp.float32)
                                - w.astype(jnp.float32)).max())
                  for g, w in zip(got, want))
        rows.append({"cell": cell, "form": form, "ms": round(ms, 4),
                     "hbm_rate_pct": round(
                         100 * nbytes / HBM_BYTES_PER_S / (ms * 1e-3), 1),
                     "max_abs_gap_to_xla": err})
        print(json.dumps(rows[-1]), flush=True)

    for cell, (parts, widths, biased, wide, offset) in SHAPES.items():
        if args.rehearse:
            parts = tuple(width // 4 for width in parts)
            wide, offset = offset // 4 + sum(parts) + wide % 128, offset // 4
        c = sum(parts)
        r = np.random.default_rng(55)
        p = jnp.asarray(r.standard_normal((b, t, wide)), jnp.float32)
        x = p[..., offset:offset + c]
        taps = jnp.asarray(r.standard_normal((k, c)) * 0.5, jnp.float32)
        bias = (jnp.asarray(r.uniform(-0.5, 0.5, (c,)), jnp.float32)
                if biased else None)
        dtypes = tuple(jnp.dtype({2: jnp.bfloat16, 4: jnp.float32}[w])
                       for w in widths)
        nbytes = b * t * (4 * c + sum(
            width * size for width, size in zip(parts, widths)))
        xla = jax.jit(lambda x, taps, bias, parts=parts, dtypes=dtypes:
                      conv_silu_parts(x, taps, bias, parts, dtypes))
        want = jax.block_until_ready(xla(x, taps, bias))
        timed(cell, "xla", lambda: xla(x, taps, bias), want, nbytes)

        def cut(whole, parts=parts, dtypes=dtypes):
            edges = np.cumsum((0,) + parts)
            return tuple(whole[..., lo:hi].astype(d)
                         for lo, hi, d in zip(edges, edges[1:], dtypes))

        def named(tile, step, rule):
            return f"tile {tile} step {step[0]}x{step[1]}" + (
                " (the rule's)" if (tile, step) == rule else "")

        rule = conv_tiling(t, parts)
        rule = (rule, (STEP[0], rule))
        for tile, strip in itertools.product(tiles, strips):
            if any(width % tile for width in parts) or t % strip:
                continue
            step = (strip, tile)
            kw = dict(offset=0, tile=tile, step=step, last=False,
                      interpret=args.rehearse)
            timed(cell, f"kernel parts {named(tile, step, rule)}",
                  lambda: _conv_pallas(x, taps, bias, parts=parts,
                                       dtypes=dtypes, **kw), want, nbytes)
            one = jax.jit(lambda x, taps, bias, kw=kw, c=c: cut(_conv_pallas(
                x, taps, bias, parts=(c,), dtypes=(jnp.dtype(jnp.float32),),
                **kw)[0]))
            timed(cell, f"kernel one output {named(tile, step, rule)}",
                  lambda: one(x, taps, bias), want, nbytes)
        if wide % 128 == 0:
            continue
        # the wide array as the TPU holds it, positions last; the parts
        # come [B, C_i, T]
        held = jnp.swapaxes(p, 1, 2)
        turned = tuple(jnp.swapaxes(part, 1, 2) for part in want)
        rule = (conv_tiling(t, parts, offset), STEP_POSITIONS_LAST)
        for tile, step in itertools.product(tiles, steps):
            if any(width % tile for width in parts + (offset,)) \
                    or t % step[0] or tile % step[1]:
                continue
            timed(cell, "kernel positions last "
                  + named(tile, step, rule), lambda: _conv_pallas(
                      held, taps, bias, offset=offset, parts=parts,
                      dtypes=dtypes, tile=tile, step=step, last=True,
                      interpret=args.rehearse), turned, nbytes)
    result = {"shape": [b, t, k], "device": str(jax.devices()[0].device_kind),
              "repeats": args.repeats, "rows": rows}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
