"""``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json`` on the chip.

Prints what is worth keeping on earlier lines and the contract's one JSON
object last. Exits non-zero, with no result, without the cell's chips.
See ``benchmarks/README.md``.
"""

import time

_T_PROCESS = time.time()        # set-up is counted from here

import sys                      # noqa: E402
from pathlib import Path        # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmarks.harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=_T_PROCESS))
