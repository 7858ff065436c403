"""Fig. 3 — customer-query replay across cluster sizes (2/4/8 nodes).

Paper claims reproduced:
  * slight regression at 2 nodes,
  * significant latency reductions at 4 and 8 nodes,
  * ~10 % improvement in P99 tail latency,
  * utilization gains growing with cluster size.
"""

from __future__ import annotations

import os
import time
from typing import List, Tuple

from repro.sim.engine import ClusterConfig
from repro.sim.replay import improvement, pool_workers, run_ab, warm_pool
from repro.sim.workload import customer_replay_suite

Row = Tuple[str, float, str]


def _workers() -> int:
    """Simulations are per-query independent; fan out across cores unless
    REPRO_BENCH_WORKERS pins it (0/1 = serial).  Capped: each worker is a
    full Python+jax process, and past ~8 the spawn cost outweighs the
    parallelism for quick-mode suites.  Off the CPU backend the suite
    stays in this process, which holds the chip (`pool_workers`)."""
    env = os.environ.get("REPRO_BENCH_WORKERS")
    if env is not None:
        return pool_workers(int(env))
    return pool_workers(min(os.cpu_count() or 1, 8))


def run(quick: bool = False) -> List[Row]:
    num_queries = 40 if quick else 150
    workers = _workers()
    warm_pool(workers)  # overlap worker spawn with profile generation
    profiles = customer_replay_suite(num_queries=num_queries)
    rows: List[Row] = []
    for nodes in (2, 4, 8):
        cluster = ClusterConfig(num_nodes=nodes)
        t0 = time.time()
        suites = run_ab(profiles, cluster, seed=nodes, workers=workers)
        rr, dk = suites["legacy"], suites["dyskew"]
        mean_impr = improvement(rr.mean_latency(), dk.mean_latency())
        p99_impr = improvement(rr.p(99), dk.p(99))
        p50_impr = improvement(rr.p(50), dk.p(50))
        util_delta = dk.mean_utilization() - rr.mean_utilization()
        rows.append((
            f"fig3_nodes{nodes}_mean_latency_dyskew",
            dk.mean_latency() * 1e6,
            f"mean_improvement={mean_impr:+.3f}",
        ))
        rows.append((
            f"fig3_nodes{nodes}_p99_latency_dyskew",
            dk.p(99) * 1e6,
            f"p99_improvement={p99_impr:+.3f}",
        ))
        rows.append((
            f"fig3_nodes{nodes}_p50",
            dk.p(50) * 1e6,
            f"p50_improvement={p50_impr:+.3f};util_delta={util_delta:+.3f};"
            f"wall_s={time.time()-t0:.1f}",
        ))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))
