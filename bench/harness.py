"""One run of one cell: set-up, the measured window, the traced jobs and
the check that decides ``correct``.

Everything that belongs to a configuration, a traffic mix or a
per-layer metric is data found by name: the configuration file named in
``BENCHMARK.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``.  A configuration's ``queries`` names its
query population either as a file, ``{"population": "<name>"}`` for
``bench/populations/<name>.json``, or as a suite generator of
`bench/gen.py` with its arguments (`gen.suite`).

A *job* is one call of the program's served entry,
`MultiQuerySimulator.run`.  The traffic file's ``job`` says what it
holds: one query arriving at 0 (``"query"``), or ``concurrency``
queries sharing the warehouse, the first arriving at 0 and the others
at times drawn from ``arrivals`` and snapped onto the strategies' tick
grid (``"concurrent"``, `check_traffic`).  Set-up draws the traffic
file's number of ``passes`` over the configuration's queries, each pass
in its own order and with its own rows, cuts them in that order into
jobs, and builds the program's input objects for every job; the window
runs these jobs back to back, so no job hands the program an input it
has seen before.  The window ends when the job running at ``--seconds``
completes, and every rate is taken over all the rows of all the
window's jobs and the whole time from the window's start to the end of
its last job.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import compare, gen, reference, strategy, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_SUBDIR = ".jax_cache"


def _positive(where: str, val, kind=(int, float)) -> None:
    if isinstance(val, bool) or not isinstance(val, kind) or not val > 0:
        raise ValueError(f"field {where!r} must be a positive "
                         f"{'int' if kind is int else 'number'}, not {val!r}")


def check_traffic(traffic: Dict, profiles) -> None:
    """Raise ValueError, naming the field, unless ``traffic`` is a
    ``"query"`` traffic without the fields of a concurrent one, or a
    ``"concurrent"`` one with a positive int ``concurrency`` and an
    ``arrivals`` of exactly its process's fields (`gen.ARRIVAL_FIELDS`),
    each a positive number (``burst_fraction`` below 1), under whose
    strategies every profile ticks at one positive ``tick_interval``."""
    kind = traffic.get("job")
    if kind == "query":
        extra = sorted({"concurrency", "arrivals"} & traffic.keys())
        if extra:
            raise ValueError(f"field {extra[0]!r} is for a concurrent job, "
                             "not for 'query'")
        return
    if kind != "concurrent":
        raise ValueError(f"field 'job': unknown job kind {kind!r}, not "
                         "'query' or 'concurrent'")
    for key in ("concurrency", "arrivals"):
        if key not in traffic:
            raise ValueError(f"field {key!r} is missing")
    _positive("concurrency", traffic["concurrency"], int)
    arr = traffic["arrivals"]
    if not isinstance(arr, dict) or "process" not in arr:
        raise ValueError("field 'arrivals.process' is missing")
    fields = gen.ARRIVAL_FIELDS.get(arr["process"])
    if fields is None:
        raise ValueError(f"field 'arrivals.process': unknown process "
                         f"{arr['process']!r}, not one of "
                         f"{sorted(gen.ARRIVAL_FIELDS)}")
    unknown = sorted(arr.keys() - set(fields))
    if unknown:
        raise ValueError(f"field 'arrivals.{unknown[0]}' is unknown to "
                         f"process {arr['process']!r}")
    for key in fields[1:]:
        if key not in arr:
            raise ValueError(f"field 'arrivals.{key}' is missing")
        _positive(f"arrivals.{key}", arr[key])
    if arr.get("burst_fraction", 0.0) >= 1.0:
        raise ValueError("field 'arrivals.burst_fraction' must lie below 1, "
                         f"not {arr['burst_fraction']!r}")
    ticks = sorted({strategy.resolve(traffic, p)["tick_interval"]
                    for p in profiles}, key=repr)
    if len(ticks) != 1:
        raise ValueError("field 'tick_interval': the strategies of a "
                         f"concurrent job must share one, not {ticks}")
    _positive("tick_interval", ticks[0])


def load_cell(name: str, root: str = ROOT) -> Dict:
    """The cell's entry, configuration, traffic and metric lists, and
    the directory its population files are found in.  Exits non-zero
    when the configuration's ``queries`` is not exactly one of the two
    forms, its population file is malformed, or the traffic file fails
    `check_traffic`."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    traffic_file = os.path.join("bench", "traffic", cell["traffic"] + ".json")
    with open(os.path.join(root, traffic_file)) as f:
        traffic = json.load(f)
    populations = os.path.join(root, "bench", "populations")
    try:
        profiles = gen.suite(config["queries"], populations)
    except ValueError as e:
        raise SystemExit(f"{entry['file']}: {e}") from e
    try:
        check_traffic(traffic, profiles)
    except ValueError as e:
        raise SystemExit(f"{traffic_file}: {e}") from e

    def mine(metrics: List[Dict]) -> List[Dict]:
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {
        "chips": cell["chips"],
        "config": config,
        "traffic": traffic,
        "populations": populations,
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
    }


def require_chips(chips: int):
    """JAX's devices, or exit non-zero when they are not enough TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"needs {chips} TPU chip(s); JAX's first device is "
            f"{devs[0].platform!r} ({devs[0].device_kind}), {len(devs)} in "
            "all: nothing was run")
    return devs


def place_compile_cache(root: str = ROOT) -> str:
    """Keep ``JAX_COMPILATION_CACHE_DIR`` where set, else a fixed
    directory in the checkout; cache every compiled program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, CACHE_SUBDIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Counts JAX's backend compilations (each a compile or a load from
    the persistent cache) and, of them, the persistent-cache hits."""

    def __init__(self):
        self.count = 0
        self.hits = 0

    def __call__(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1


def derived_seed(seed: int, *path: int) -> int:
    return int(np.random.SeedSequence([seed, *path]).generate_state(1)[0])


def p95(samples: List[float]) -> Dict:
    """95th percentile (linear interpolation) and how many samples lie
    beyond it."""
    x = np.asarray(samples, np.float64)
    v = float(np.percentile(x, 95))
    return {"value": v, "n": int(len(x)), "beyond": int(np.sum(x > v))}


def run_window(job: Callable[[int], object], seconds: float,
               clock: Callable[[], float] = time.perf_counter) -> Dict:
    """Run ``job(0), job(1), ...`` back to back until one ends at or past
    ``seconds`` after the start.  Returns the start, the end of the last
    job, each job's wall seconds and outputs."""
    start = clock()
    walls, outs = [], []
    i = 0
    while True:
        t0 = clock()
        outs.append(job(i))
        t1 = clock()
        walls.append(t1 - t0)
        i += 1
        if t1 - start >= seconds:
            return {"start": start, "end": t1, "walls": walls, "outs": outs}


def rate(units: float, window: Dict) -> float:
    return units / (window["end"] - window["start"])


def generate(cell: Dict, seed: int) -> Dict:
    """The cell's jobs, each a list of generated queries with their
    strategies: ``passes`` pools of every configured query, each pass
    ordered and drawn from its own seed, cut in that order into jobs;
    and the warm-up's own jobs, from a seed of their own.  A ``"query"``
    traffic warms up one query per distinct strategy; a ``"concurrent"``
    one, per distinct strategy, a job of each size 1 to ``concurrency``
    of the strategy's query with the fewest rows, all arriving at 0, so
    that every tick group a window job can form is compiled.  Pure
    set-up; the program sees none of it yet."""
    config, traffic = cell["config"], cell["traffic"]
    wh = config["warehouse"]
    n = wh["num_nodes"] * wh["interpreters_per_node"]
    ff = traffic["feed_factor"]
    profiles = gen.suite(config["queries"], cell["populations"])
    queries = [q for p in range(int(traffic["passes"]))
               for q in gen.query_pool(profiles, n, ff, derived_seed(seed, 1, p))]
    by_strategy: Dict[str, List[int]] = {}
    for i, prof in enumerate(profiles):
        key = json.dumps(strategy.resolve(traffic, prof), sort_keys=True)
        by_strategy.setdefault(key, []).append(i)
    if traffic["job"] == "query":
        pool = [[q] for q in queries]
        warm = [[gen.draw(profiles, idx[0], n, ff, derived_seed(seed, 5))]
                for idx in by_strategy.values()]
    else:
        k = traffic["concurrency"]
        step = strategy.resolve(traffic, profiles[0])["tick_interval"]
        pool = []
        for j, first in enumerate(range(0, len(queries), k)):
            job = queries[first:first + k]
            times = gen.job_arrivals(traffic["arrivals"], len(job), step,
                                     derived_seed(seed, 6, j))
            pool.append([dataclasses.replace(q, arrival=t)
                         for q, t in zip(job, times)])
        warm = []
        for idx in by_strategy.values():
            i = min(idx, key=lambda i: profiles[i].n_rows)
            warm += [[gen.draw(profiles, i, n, ff, derived_seed(seed, 5, t, m))
                      for m in range(t)] for t in range(1, k + 1)]

    def strategies(jobs):
        return [[strategy.resolve(traffic, q.profile) for q in job]
                for job in jobs]

    return {"pool": pool, "strategies": strategies(pool),
            "warm": warm, "warm_strategies": strategies(warm)}


def _load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sum_counts(counts: List[Dict[str, int]]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for c in counts:
        for k, v in c.items():
            out[k] = out.get(k, 0) + int(v)
    return out


def sample_jobs(pool: List[List], ran: List[int], queries: int,
                seed: int) -> set:
    """The pool entries of the window's jobs ``ran`` that the check
    compares: the longest job (the most rows in all its queries), then
    whole jobs drawn from ``seed`` until at least ``queries`` queries are
    in.  With one query a job that is the longest query and
    ``queries - 1`` others."""
    longest = max(ran, key=lambda e: sum(q.rows for q in pool[e]))
    rest = [e for e in ran if e != longest]
    k = min(len(rest), max(queries - 1, 0))
    pick = np.random.default_rng([seed, 4]).choice(len(rest), k, replace=False)
    out, held = {longest}, len(pool[longest])
    for i in pick:
        if held >= queries:
            break
        out.add(rest[int(i)])
        held += len(pool[rest[int(i)]])
    return out


def check(cell: Dict, gen_out: Dict, jobs: List[Dict], seed: int) -> Dict:
    """Compare the window's answers with the reference.

    A sample of the jobs the window ran is drawn from ``seed``
    (`sample_jobs`), always with the longest job in it, and compared
    whole.  Busy-time conservation is checked on every window job.  A
    job that answers for fewer or more queries than it was given fails
    every number."""
    config, traffic = cell["config"], cell["traffic"]
    pool, strats = gen_out["pool"], gen_out["strategies"]
    ran = sorted({j["entry"] for j in jobs})
    sample = sample_jobs(pool, ran, int(traffic["compare_queries"]), seed)
    gaps, cons = [], 0.0
    t0 = time.perf_counter()
    refs = {e: reference.run(config["warehouse"], pool[e], strats[e])
            for e in sorted(sample)}
    ref_s = time.perf_counter() - t0
    compared = failed = 0
    for j in jobs:
        e = j["entry"]
        costs = [q.cost for q in pool[e]]
        if len(j["results"]) != len(costs) or len(j["links"]) != len(costs):
            gaps.append({k: float("inf") for k in compare.NUMBERS})
            failed += len(costs)
            continue
        for r, c in zip(j["results"], costs):
            cons = max(cons, abs(float(np.sum(r["per_worker_busy"])) - c) / c)
        if e not in sample:
            continue
        links = j["links"]
        for q, (r, rr) in enumerate(zip(j["results"], refs[e]["results"])):
            g = compare.query_gaps(r, rr, links[q], refs[e]["links"][q],
                                   costs[q])
            gaps.append(g)
            compared += 1
            failed += bool(compare.failing(g, config["correct_limits"]))
    worst = compare.worst(gaps)
    worst["conservation_rel_gap"] = max(worst["conservation_rel_gap"], cons)
    return {"gaps": worst, "compared": compared, "failed": failed,
            "reference_s": ref_s}


def run(cell: Dict, seed: int, seconds: float, trace: bool, devices,
        t_start: float, log: Callable[[str], None] = print,
        program_cls=None) -> Dict:
    """One run; returns the result object the last line prints.

    ``program_cls(warehouse, pool, strategies)`` builds what the window
    drives (`bench.program_io.Program`, the system under test, unless a
    control or a test puts another in its place)."""
    import jax

    if program_cls is None:
        from bench.program_io import Program as program_cls

    seed = int(seed) % (1 << 62)
    config, traffic = cell["config"], cell["traffic"]
    g = generate(cell, seed)
    pool = g["pool"]
    program = program_cls(config["warehouse"], pool, g["strategies"])
    rows = [sum(q.rows for q in job) for job in pool]
    t_gen = time.perf_counter()
    log(f"traffic generated at {t_gen - t_start!r} s")

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    jax.monitoring.register_event_listener(counter.on_event)

    def job(i: int, k0: int = 0) -> Dict:
        e = (k0 + i) % len(pool)
        res, counts, links = program.run(e)
        return {"entry": e, "results": res, "counts": counts, "links": links}

    # Warm-up: one job of its own per distinct strategy, so every shape
    # the window uses is compiled.
    warm = program_cls(config["warehouse"], g["warm"], g["warm_strategies"])
    for e in range(len(g["warm"])):
        warm.run(e)
    del warm
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s!r} s, warm-up {setup_s - (t_gen - t_start)!r} s "
        f"of it, {counter.count} compilations, {counter.hits} of them "
        "from the persistent cache")

    before = counter.count
    win = run_window(job, seconds)
    compiles = counter.count - before
    jax.monitoring.unregister_event_duration_listener(counter)
    jax.monitoring.unregister_event_listener(counter.on_event)
    jobs = win["outs"]
    window_rows = sum(rows[j["entry"]] for j in jobs)
    counts = _sum_counts([j["counts"] for j in jobs])
    log(f"window: {len(jobs)} jobs, {window_rows} rows in "
        f"{win['end'] - win['start']!r} s, {compiles} compilations, "
        f"{counts.get('tick', 0)} per-query and {counts.get('gtick', 0)} "
        "group tick calls")
    if len(jobs) + int(traffic["traced_jobs"]) * trace > len(pool):
        log(f"the window ran past the {len(pool)} generated jobs and "
            "replayed some of them")

    obs: Dict = {"compiles_in_window": compiles}
    dev_info: Dict = {}
    breakdown = None
    if trace:
        traced = []
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            with jax.profiler.trace(log_dir, profiler_options=opts):
                for i in range(int(traffic["traced_jobs"])):
                    with jax.profiler.TraceAnnotation(
                            trace_reduce.JOB_SPAN, name=traffic["job"]):
                        traced.append(job(i, len(jobs)))
            raw = trace_reduce.read(trace_reduce.find_xplane(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        red = trace_reduce.reduce(raw)
        obs["traced"] = {
            "rows": sum(rows[t["entry"]] for t in traced),
            "counts": _sum_counts([t["counts"] for t in traced]),
        }
        obs["trace"] = red
        if red is not None:
            dev_info = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        del traced

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    # The program's answers to host memory; its device state is freed
    # before the reference runs.
    for j in jobs:
        j["results"], j["links"] = program.answers(
            j["entry"], j["results"], j["links"])
    del program
    chk = check(cell, g, jobs, seed)
    log(f"reference: {chk['reference_s']!r} s for "
        f"{chk['compared']} compared queries")

    limits = config["correct_limits"]
    checks = {k: {"value": chk["gaps"][k], "limit": limits[k]}
              for k in compare.NUMBERS}
    bad = compare.failing(chk["gaps"], limits)

    metrics: Dict = {}
    if trace:
        for m in cell["per_layer"]:
            v = _load_reader(m["name"])(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        tail = p95(win["walls"])
        values = {
            "rows_per_s": rate(window_rows, win),
            "query_wall_p95_ms": 1000.0 * tail["value"],
            "setup_s": setup_s,
        }
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
        log(f"job wall p95 over {tail['n']} jobs, {tail['beyond']} beyond it; "
            f"walls {[round(w, 4) for w in win['walls']]}")

    dev = devices[0]
    out = {
        "correct": not bad and chk["compared"] > 0,
        "attempted": sum(len(pool[j["entry"]]) for j in jobs),
        "failed": chk["failed"],
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak,
                   **dev_info},
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter() if t_start is None else t_start

    cell = load_cell(args.workload)
    devices = require_chips(cell["chips"])
    place_compile_cache()

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    out = run(cell, args.seed, args.seconds, bool(args.trace), devices,
              t_start, log)
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0
