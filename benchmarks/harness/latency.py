"""Latency arithmetic: one place, so every PR computes the same number.

A transaction that failed (not emitted within the grace, or emitted with an
error or shed marker) misses every limit: it enters a percentile as
``miss_ms``, the longest latency the run could have measured. A percentile
is reported only with at least ``MIN_BEYOND`` samples beyond it.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

MIN_BEYOND = 10


def latencies_ms(due: np.ndarray, emitted: np.ndarray, failed: np.ndarray,
                 miss_ms: float) -> np.ndarray:
    """Per attempted transaction: emitted - due in ms; ``miss_ms`` where it
    failed or was never emitted (``emitted`` NaN)."""
    lat = (np.asarray(emitted, np.float64) - np.asarray(due, np.float64)) * 1e3
    bad = np.asarray(failed, bool) | ~np.isfinite(lat)
    return np.where(bad, float(miss_ms), lat)


def percentile(samples: np.ndarray, q: float) -> Optional[float]:
    """Linear-interpolated ``q`` (0..1), or None when fewer than
    ``MIN_BEYOND`` samples lie beyond it (the median needs only one)."""
    x = np.asarray(samples, np.float64)
    n = len(x)
    if n == 0:
        return None
    beyond = n * (1.0 - q)
    if q > 0.5 and beyond < MIN_BEYOND:
        return None
    return float(np.percentile(x, q * 100.0))


def share_over(samples: np.ndarray, limit_ms: float) -> Optional[float]:
    """Percent of attempted transactions over ``limit_ms`` (failed ones are
    over every limit by construction of ``latencies_ms``)."""
    x = np.asarray(samples, np.float64)
    if len(x) == 0:
        return None
    return float(100.0 * np.mean(x > limit_ms))
