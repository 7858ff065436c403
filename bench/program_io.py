"""The one place the benchmark touches the program under test.

It builds the program's input objects from the benchmark's generated
queries and strategy dicts (in set-up), runs one job through the served
entry `MultiQuerySimulator.run`, and reads back what the program
reports: its `QueryResult`s, its per-kind event counters and its final
link state.  Nothing else of the program is used.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import numpy as np

from repro.core.types import DySkewConfig, Policy, SkewModelKind
from repro.sim.engine import (
    Batch,
    ClusterConfig,
    MultiQuerySimulator,
    StrategyConfig,
    TenantQuery,
)

from bench.gen import Query


def strategy(st: Dict) -> StrategyConfig:
    cfg = dict(st["dyskew"])
    cfg["policy"] = Policy[cfg["policy"]]
    cfg["skew_model"] = SkewModelKind[cfg["skew_model"]]
    fields = {k: v for k, v in st.items() if k != "dyskew"}
    return StrategyConfig(dyskew=DySkewConfig(**cfg), **fields)


def tenants(queries: Sequence[Query], strategies: Sequence[Dict]) -> List[TenantQuery]:
    """One `TenantQuery` per generated query."""
    out = []
    for i, (qy, st) in enumerate(zip(queries, strategies)):
        out.append(TenantQuery(
            name=f"{qy.profile.name}#{i:03d}",
            streams=[[Batch(costs=c, sizes=s) for c, s in stream]
                     for stream in qy.streams],
            strategy=strategy(st),
            arrival=qy.arrival,
            arrival_gap=qy.gap,
        ))
    return out


class Program:
    """The system under test, handed one cell's generated pool of jobs.

    The harness drives any object with these two methods; the control
    (`bench/control.py`) and the fault tests put others in its place."""

    def __init__(self, warehouse: Dict, pool: Sequence[Sequence[Query]],
                 strategies: Sequence[Sequence[Dict]]):
        self.cluster = ClusterConfig(**warehouse)
        self.strategies = strategies
        self.jobs = [tenants(job, st) for job, st in zip(pool, strategies)]

    def run(self, entry: int):
        """One call of the served entry on pool entry ``entry``: returns
        (results, event counts, link states), the link states left where
        the program put them."""
        sim = MultiQuerySimulator(self.cluster)
        results = sim.run(self.jobs[entry])
        return results, sim.last_event_counts, sim.last_link_states

    def answers(self, entry: int, results, links) -> Tuple[List[Dict], List]:
        """Host copies of one job's answers: per query a result dict and
        its link state (see `link_rows`)."""
        states = jax.device_get(links)
        return ([result_dict(r) for r in results],
                link_rows(states, self.strategies[entry]))


def result_dict(r) -> Dict:
    return {
        "latency": r.latency,
        "utilization": r.utilization,
        "bytes_moved_remote": r.bytes_moved_remote,
        "rows_redistributed": r.rows_redistributed,
        "per_worker_busy": np.asarray(r.per_worker_busy, np.float64),
        "decision_overhead": r.decision_overhead,
        "num_ticks": r.num_ticks,
    }


def _flatten(tree: Dict, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_flatten(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = np.asarray(v)
    return out


def link_rows(link_states: List[Dict], strategies: Sequence[Dict]) -> List:
    """Per query, its link state as host arrays keyed like
    `reference.Links.snapshot`.

    The program returns one stacked state per batched tick group, groups
    in order of their first member and members in query order (a lone
    query is a group of one).  Raises ValueError when the states cannot
    be mapped that way."""
    keys = []
    members: Dict[tuple, List[int]] = {}
    for q, st in enumerate(strategies):
        key = (tuple(sorted(st["dyskew"].items())), st["tick_interval"])
        if key not in members:
            keys.append(key)
            members[key] = []
        members[key].append(q)
    flat = [_flatten(s) for s in link_states]
    if len(flat) != len(keys) or any(f["state"].ndim != 2 for f in flat):
        raise ValueError(
            f"cannot map {len(flat)} link states onto {len(keys)} stacked "
            "link groups")
    out: List = [None] * len(strategies)
    for key, f in zip(keys, flat):
        for i, q in enumerate(members[key]):
            out[q] = {k: v[i] for k, v in f.items()}
    return out
