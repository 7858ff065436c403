"""Smoke test: the warehouse simulator's jitted link tick on one TPU chip.

Run from the repository root, in a process that is alone on the chip:

    python chip_smoke.py

Phases, each through the library's public entry points:

  0. device — exits non-zero unless JAX's first device is a TPU;
  1. the Fig. 3 replay (`replay.run_ab`) of 40 customer queries on a
     Large warehouse (8 nodes x 8 interpreters: 64 link instances per
     query);
  2. 512 open-loop tenants, the top rung of the multi-tenant bench's
     ``--many`` study, on the same warehouse: `MultiQuerySimulator`
     batches the whole fleet into one (512, 64) jitted tick group;
  3. the four Pallas kernels, compiled, at the kernel bench's full shapes
     against their jnp oracles.

Phases 1 and 2 must show (a) the final link state resident on the TPU,
(b) every query's busy seconds equal to the cost of its generated rows
(rel 1e-9), and (c) results bit-identical to the same phase rerun in this
process on the CPU backend, the reference.  The last line of standard
output is one JSON object naming the device; it is printed only when every
check held.
"""

from __future__ import annotations

import importlib.metadata
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

#: Kernels run compiled; the Pallas interpreter is for CPU test runs.
INTERPRET = False

_failures: list = []
_compiles = {"n": 0, "secs": 0.0, "cache_hits": 0}


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}")
    if not ok:
        _failures.append(what)


def _on_duration(event: str, secs: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compiles["n"] += 1
        _compiles["secs"] += secs


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _compiles["cache_hits"] += 1


def require_tpu():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"chip_smoke: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}), not a TPU; nothing was run"
        )
    return dev


def on_device(tree, dev) -> bool:
    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and all(
        isinstance(x, jax.Array) and x.devices() == {dev} for x in leaves
    )


def same_results(a, b) -> bool:
    return len(a) == len(b) and all(
        x.latency == y.latency
        and np.array_equal(x.per_worker_busy, y.per_worker_busy)
        and x.rows_redistributed == y.rows_redistributed
        for x, y in zip(a, b)
    )


def conserved(results, totals) -> bool:
    return all(
        abs(float(np.sum(r.per_worker_busy)) - t) <= 1e-9 * t
        for r, t in zip(results, totals)
    )


def stream_cost(streams) -> float:
    return sum(float(b.costs.sum()) for s in streams for b in s)


def phase_replay(dev, cpu) -> None:
    from repro.sim.engine import ClusterConfig, MultiQuerySimulator, TenantQuery
    from repro.sim.replay import (
        dyskew_strategy,
        run_ab,
        scan_arrival_gap,
    )
    from repro.sim.workload import customer_replay_suite, generate_query

    print("phase 1: Fig. 3 replay, 40 queries, 8 nodes x 8 interpreters")
    seed = 8
    cluster = ClusterConfig(num_nodes=8)
    profiles = customer_replay_suite(num_queries=40)
    t0 = time.perf_counter()
    suites = run_ab(profiles, cluster, seed=seed)
    wall = time.perf_counter() - t0
    for arm, s in suites.items():
        print(f"  {arm}: mean latency {s.mean_latency()!r} s, "
              f"p99 {s.p(99)!r} s (simulated)")
    print(f"  wall {wall!r} s on {dev.platform}")

    # (a) Query 0's dyskew arm again, as `run_ab` runs it, to read the
    # link state its tick driver ends with.
    prof = profiles[0]
    tenant = TenantQuery(
        name="query",
        streams=generate_query(prof, cluster.num_workers, seed=seed * 1000),
        strategy=dyskew_strategy(prof),
        arrival_gap=scan_arrival_gap(prof, cluster),
    )
    sim = MultiQuerySimulator(cluster, seed=seed)
    direct = sim.run([tenant])
    check(same_results(direct, suites["dyskew"].results[:1]),
          "query 0 run directly matches its run_ab result")
    check(all(on_device(s, dev) for s in sim.last_link_states)
          and bool(sim.last_link_states),
          f"link state of query 0 is on {dev}")

    totals = [
        stream_cost(generate_query(p, cluster.num_workers,
                                   seed=seed * 1000 + i))
        for i, p in enumerate(profiles)
    ]
    for arm, s in suites.items():
        check(conserved(s.results, totals),
              f"{arm}: busy seconds equal generated row cost (rel 1e-9)")

    t0 = time.perf_counter()
    with jax.default_device(cpu):
        ref = run_ab(profiles, cluster, seed=seed)
    print(f"  CPU-backend rerun wall {time.perf_counter() - t0!r} s")
    for arm in suites:
        check(same_results(suites[arm].results, ref[arm].results),
              f"{arm}: latency, per-worker busy and rows redistributed "
              "bit-identical to the CPU backend")


def phase_many_tenants(dev, cpu) -> None:
    from benchmarks.bench_multi_tenant import _many_strategy
    from repro.sim.engine import ClusterConfig, MultiQuerySimulator
    from repro.sim.replay import open_loop_rate, open_loop_tenants
    from repro.sim.workload import ArrivalProcess, many_tenants_suite

    print("phase 2: 512 open-loop tenants, one batched tick group, "
          "8 nodes x 8 interpreters")
    cluster = ClusterConfig(num_nodes=8)
    specs = many_tenants_suite(512, seed=71)
    st = _many_strategy()
    proc = ArrivalProcess(
        kind="poisson",
        rate=open_loop_rate([p for p, _ in specs], cluster, load=3.0),
    )
    tenants = open_loop_tenants(
        specs, cluster, lambda prof: st, proc, 512, seed=1,
        grid_align=st.tick_interval,
    )
    sim = MultiQuerySimulator(cluster)
    t0 = time.perf_counter()
    results = sim.run(tenants)
    wall = time.perf_counter() - t0
    ev = sim.last_event_counts
    lat = np.array([r.latency for r in results])
    print(f"  wall {wall!r} s on {dev.platform}; gticks {ev['gtick']}, "
          f"heap events {ev['heap_events']}")
    print(f"  mean latency {float(lat.mean())!r} s, "
          f"p99 {float(np.percentile(lat, 99))!r} s (simulated)")
    shapes = [np.shape(s["state"]) for s in sim.last_link_states]
    check(shapes == [(512, 64)], f"one (512, 64) tick group (got {shapes})")
    check(all(on_device(s, dev) for s in sim.last_link_states),
          f"batched link state is on {dev}")
    check(conserved(results, [stream_cost(t.streams) for t in tenants]),
          "busy seconds equal generated row cost (rel 1e-9)")

    t0 = time.perf_counter()
    with jax.default_device(cpu):
        ref = MultiQuerySimulator(cluster).run(tenants)
    print(f"  CPU-backend rerun wall {time.perf_counter() - t0!r} s")
    check(same_results(results, ref),
          "latency, per-worker busy and rows redistributed bit-identical "
          "to the CPU backend")


def phase_kernels() -> None:
    from repro.kernels.dispatch.kernel import dispatch_gather
    from repro.kernels.dispatch.ref import dispatch_gather_ref
    from repro.kernels.histogram.kernel import load_histogram
    from repro.kernels.histogram.ref import load_histogram_ref
    from repro.kernels.ssd_scan.kernel import ssd_state_scan
    from repro.kernels.ssd_scan.ref import ssd_state_scan_ref
    from repro.kernels.topk_gating.kernel import topk_gating
    from repro.kernels.topk_gating.ref import topk_gating_ref

    print("phase 3: Pallas kernels at the kernel bench's full shapes")
    key = jax.random.PRNGKey(0)
    t0 = time.perf_counter()

    T, S, D = 1024, 2048, 512
    x = jax.random.normal(key, (T, D), jnp.bfloat16)
    src = jax.random.randint(key, (S,), 0, T)
    valid = jax.random.bernoulli(jax.random.PRNGKey(1), 0.8, (S,))
    out = dispatch_gather(x, src, valid, interpret=INTERPRET)
    ref = dispatch_gather_ref(x, src, valid)
    check(np.array_equal(np.asarray(out, np.float32),
                         np.asarray(ref, np.float32)),
          f"dispatch_gather ({T}, {S}, {D}) bf16 equals the reference")

    N, E = 8192, 384
    ids = jax.random.randint(key, (N,), 0, E)
    h = np.asarray(load_histogram(ids, num_dest=E, interpret=INTERPRET))
    check(np.array_equal(h, np.asarray(load_histogram_ref(ids, E)))
          and float(h.sum()) == N,
          f"load_histogram N={N} E={E} equals the reference")

    Tt, Et, k = 1024, 384, 8
    logits = jax.random.normal(key, (Tt, Et))
    w, idx = topk_gating(logits, k=k, interpret=INTERPRET)
    wr, idxr = topk_gating_ref(logits, k)
    check(np.array_equal(np.asarray(idx), np.asarray(idxr))
          and np.allclose(np.asarray(w), np.asarray(wr),
                          rtol=1e-5, atol=1e-6),
          f"topk_gating T={Tt} E={Et} k={k} matches the reference")

    C, H, P, Nn = 32, 16, 64, 128
    states = jax.random.normal(key, (C, H, P, Nn))
    decay = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (C, H)))
    out = ssd_state_scan(states, decay, interpret=INTERPRET)
    check(np.allclose(np.asarray(out),
                      np.asarray(ssd_state_scan_ref(states, decay)),
                      rtol=1e-5, atol=1e-5),
          f"ssd_state_scan C={C} H={H} P={P} N={Nn} matches the reference")
    print(f"  wall {time.perf_counter() - t0!r} s")


def main() -> int:
    dev = require_tpu()
    print(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"jaxlib={importlib.metadata.version('jaxlib')} "
        f"libtpu={importlib.metadata.version('libtpu')}"
    )
    from repro.runtime.compile_cache import configure_compile_cache
    from repro.sim.batched_link import _JittedBatchedMachine
    from repro.sim.engine import _JittedMachine

    print(f"compile cache: {configure_compile_cache(ROOT)}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    cpu = jax.devices("cpu")[0]

    phase_replay(dev, cpu)
    phase_many_tenants(dev, cpu)
    print(f"tick jit caches: per-tenant {len(_JittedMachine._cache)}, "
          f"batched {len(_JittedBatchedMachine._cache)} (config, shape) keys")
    phase_kernels()
    print(f"compilations: {_compiles['n']} in {_compiles['secs']!r} s "
          f"(persistent cache hits {_compiles['cache_hits']})")

    if _failures:
        print(f"chip_smoke: {len(_failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
