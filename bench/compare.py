"""The comparison that decides ``correct``.

Three numbers, each the worst over every compared query of what the
timed path produced against what `bench/reference.py` computes from the
same generated inputs:

* ``result_rel_gap``: the event loop's results.  The widest relative
  gap over latency, utilization, bytes moved, rows redistributed,
  decision overhead, tick count and per-worker busy seconds (the widest
  worker's gap over the query's total busy seconds).
* ``link_state_mismatch``: the decision core's.  The number of elements
  of the final link state (states, strikes, float32 metrics, transition
  and tick counters) that differ.
* ``conservation_rel_gap``: the guarantee that every generated row runs
  exactly once.  The gap between a query's busy seconds and the cost of
  its generated rows, over that cost.

Each has its own limit, read from the configuration file
(``correct_limits``); `PERF.md` gives the readings each was set from.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

#: Compared numbers, in the order they are printed.
NUMBERS = ("result_rel_gap", "link_state_mismatch", "conservation_rel_gap")

#: Scalar result fields and the floor of each relative gap's denominator.
FIELDS = {
    "latency": 1e-300,
    "utilization": 1e-300,
    "bytes_moved_remote": 1.0,
    "rows_redistributed": 1.0,
    "decision_overhead": 1e-300,
    "num_ticks": 1.0,
}


def _rel(a: float, b: float, floor: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), floor)


def _mismatch(prog_link: Optional[Dict], ref_link: Optional[Dict]) -> float:
    if (prog_link is None) != (ref_link is None):
        return float("inf")
    if ref_link is None:
        return 0.0
    out = 0.0
    for key, want in ref_link.items():
        have = prog_link.get(key)
        if have is None or np.shape(have) != np.shape(want):
            out += np.size(want)
        else:
            out += float(np.count_nonzero(np.asarray(have) != np.asarray(want)))
    return out


def query_gaps(prog: Dict, ref: Dict, prog_link: Optional[Dict],
               ref_link: Optional[Dict], cost: float) -> Dict[str, float]:
    """Every compared number for one query."""
    rb = np.asarray(ref["per_worker_busy"], np.float64)
    pb = np.asarray(prog["per_worker_busy"], np.float64)
    if pb.shape == rb.shape:
        busy = float(np.max(np.abs(pb - rb))) / max(float(rb.sum()), 1e-300)
    else:
        busy = float("inf")
    result = max([busy] + [_rel(prog[k], ref[k], f) for k, f in FIELDS.items()])
    return {
        "result_rel_gap": result,
        "link_state_mismatch": _mismatch(prog_link, ref_link),
        "conservation_rel_gap": _rel(float(pb.sum()), cost, 1e-300),
    }


def worst(gaps: List[Dict[str, float]]) -> Dict[str, float]:
    return {k: max((g[k] for g in gaps), default=0.0) for k in NUMBERS}


def failing(gaps: Dict[str, float], limits: Dict[str, float]) -> List[str]:
    """Names of the numbers above their limit (NaN fails)."""
    return [k for k in NUMBERS if not gaps[k] <= limits[k]]
