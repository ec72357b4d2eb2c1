"""Time the gated delta-rule scan alone on the chip (``ops/delta_scan.py``)
at Qwen3-Next's shape — 8 rows x 2,048 positions, 16 key heads under 32
value heads of 128, chunks of 64 — the kernel beside the XLA chunked form,
with each form's distance from the float64 recurrence on one row beside its
time.

    chiprun -- python3 tools/delta_alone.py --out chiprun_out/delta.json

``--rehearse`` runs it on the CPU at a tiny shape, in interpret mode.
``tools/delta_alone_pr54.json`` keeps PR 54's readings: ``rows`` of the
tree as it stands, ``forms_tried`` of the forms that PR timed and took out
(a head at a time, chunks of 128, the solve's products at six passes and at
one).
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def inputs(b, t, hk, hv, d, seed, dtype):
    import jax.numpy as jnp

    r = np.random.default_rng(seed)

    def unit(shape):
        x = r.standard_normal(shape)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    return (jnp.asarray(unit((b, t, hk, d)) * d ** -0.5, dtype),
            jnp.asarray(unit((b, t, hk, d)), dtype),
            jnp.asarray(r.standard_normal((b, t, hv, d)), dtype),
            jnp.asarray(-np.exp(r.uniform(np.log(1e-3), np.log(0.1),
                                          (b, t, hv))), jnp.float32),
            jnp.asarray(1 / (1 + np.exp(-r.standard_normal((b, t, hv)))),
                        jnp.float32))


def recurrence(q, k, v, g, beta):
    """One row a position at a time, float64, on the inputs as rounded."""
    q, k, v, g, beta = (np.asarray(x.astype("float32"), np.float64)[0]
                        for x in (q, k, v, g, beta))
    t, hk, _ = q.shape
    hv = v.shape[1]
    s = np.zeros((hv, q.shape[2], v.shape[2]))
    out = np.zeros_like(v)
    for i in range(t):
        k_t = np.repeat(k[i], hv // hk, axis=0)
        q_t = np.repeat(q[i], hv // hk, axis=0)
        s *= np.exp(g[i])[:, None, None]
        u = beta[i][:, None] * (v[i] - np.einsum("hkv,hk->hv", s, k_t))
        s += k_t[:, :, None] * u[:, None, :]
        out[i] = np.einsum("hkv,hk->hv", s, q_t)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--repeats", type=int, default=10)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.ops.delta_scan import gated_delta_scan

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        raise SystemExit("delta_alone: no TPU (use --rehearse on a CPU)")
    shape = (1, 256, 4, 8, 128) if args.rehearse else (8, 2048, 16, 32, 128)
    data = inputs(*shape, seed=54, dtype=jnp.bfloat16)
    want = recurrence(*(x[:1] for x in data))
    scale = float(np.abs(want).max())
    rows = []

    def timed(name, fn):
        out = jax.block_until_ready(fn())[0]
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            jax.block_until_ready(fn())
        ms = (time.perf_counter() - t0) / args.repeats * 1e3
        err = float(np.abs(np.asarray(out[:1], np.float64)[0] - want).max())
        rows.append({"form": name, "ms": round(ms, 3),
                     "max_err_over_scale": err / scale})
        print(json.dumps(rows[-1]), flush=True)

    timed("kernel", lambda: gated_delta_scan(
        *data, chunk=64, use_pallas=True, interpret=args.rehearse))
    xla = jax.jit(lambda *a: gated_delta_scan(*a, chunk=64))
    timed("xla", lambda: xla(*data))
    result = {"shape": shape, "device": str(jax.devices()[0].device_kind),
              "scale": scale, "rows": rows}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
