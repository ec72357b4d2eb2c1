"""Shared device-timing discipline for bench.py and tune_tpu.py.

Two rules:

1. **Vary the input every timed call.** A repeated identical computation
   can be served by a cache somewhere below the caller. Timed callables
   take the iteration index so callers cycle pre-staged input variants.

2. **No device->host pull inside a timed section.** ``device_get`` /
   ``np.asarray`` on a device array waits for the device and copies, so it
   times the transfer along with the compute; ``block_until_ready`` is
   what ends a timed region. Build input variants from HOST arrays and
   ``device_put`` them; pull results after the timed section.
"""

from __future__ import annotations

import time
from typing import Callable, List


def time_blocked(fn: Callable[[int], object], iters: int) -> List[float]:
    """Per-call latency in seconds: block on each call before the next.

    ``fn(i)`` must produce a fresh computation per index (rule 1).
    """
    import jax

    jax.block_until_ready(fn(0))         # warm (compile already done)
    times = []
    for i in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(i + 1))
        times.append(time.perf_counter() - t0)
    return times


def throughput_pipelined(fn: Callable[[int], object], batch_size: int,
                         iters: int) -> float:
    """Items/second with async dispatch: the device stays fed, one block at
    the end — the basis for a throughput-derived MFU. ``fn(i)`` varies per
    call (rule 1)."""
    import jax

    jax.block_until_ready(fn(0))
    t0 = time.perf_counter()
    outs = [fn(i + 1) for i in range(iters)]
    jax.block_until_ready(outs)
    return batch_size * iters / (time.perf_counter() - t0)
