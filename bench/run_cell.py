"""Run one cell of the benchmark on the chip this process is started on.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, alone on the chip.  The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, optionally ``breakdown``, then
``checks``); the last lines of standard error give each compared number
beside its limit.  Exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
