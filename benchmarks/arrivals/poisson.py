"""Open loop, constant rate: exponential gaps at ``rate_txn_per_s``.

Independent card holders make a Poisson stream; the producer submits each
event when it is due whether or not the job keeps up.
"""

import numpy as np

MODE = "open_loop"


def schedule(traffic: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times in seconds from the stream's start, sorted, covering
    ``seconds`` (warm-up included by the caller)."""
    rate = float(traffic["rate_txn_per_s"])
    n = int(rate * seconds * 1.1) + 64
    due = np.cumsum(rng.exponential(1.0 / rate, n))
    return due[due < seconds]
