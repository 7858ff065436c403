"""The control of the comparison that decides ``correct``.

The configurations state float64 for the event loop.  The control is
the plain reference put in the program's place and computed one
precision lower, in float32, at the cell's own size and load; the
comparison must refuse it.  It is not part of a benchmark run:

    python3 bench/control.py --workload <cell> --seconds <s> --seed <n> [<n> ...]

prints, for each seed, every compared number with its limit and whether
the run came out correct (it must not).  It needs no accelerator: the
reference runs on the host.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Sequence

if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import reference  # noqa: E402
from bench.gen import Query  # noqa: E402


class ReferenceProgram:
    """The reference in the program's place, in ``precision``."""

    def __init__(self, warehouse: Dict, pool: Sequence[Sequence[Query]],
                 strategies: Sequence[Sequence[Dict]],
                 precision: str = "float32"):
        self.warehouse = warehouse
        self.pool = pool
        self.strategies = strategies
        self.precision = precision

    def run(self, entry: int):
        out = reference.run(self.warehouse, self.pool[entry],
                            self.strategies[entry], precision=self.precision)
        return out["results"], {}, out["links"]

    def answers(self, entry: int, results, links):
        return results, links


def main(argv: List[str] = None) -> int:
    import argparse

    import jax

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in args.seed:
        out = harness.run(cell, seed, args.seconds, False, jax.devices(),
                          time.perf_counter(), log=lambda m: None,
                          program_cls=ReferenceProgram)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
