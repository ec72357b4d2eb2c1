"""``pytest benchmarks/tests`` — run by hand, not part of tier-1.

Everything here runs on the CPU: arithmetic, the generator, the name
resolution, and TINY-width rehearsals of each cell's control flow in a
temporary copy (``rehearsal.py``). No test reports a device number.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(Path(__file__).resolve().parent)):
    if p not in sys.path:
        sys.path.insert(0, p)
