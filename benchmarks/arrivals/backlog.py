"""Above the knee: everything is already waiting when the job starts.

``rate_txn_per_s`` is the rate the backlog could feed for the whole run,
set well above anything the cell's system can complete (the traffic file
says from what), so the job never runs dry, every batch is full, and a
later PR's gain cannot empty it.
"""

import numpy as np

MODE = "backlog"


def schedule(traffic: dict, seconds: float, rng: np.random.Generator
             ) -> np.ndarray:
    """All due at 0: one entry per event of the backlog."""
    return np.zeros(int(np.ceil(float(traffic["rate_txn_per_s"]) * seconds)))
