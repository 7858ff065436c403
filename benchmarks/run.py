"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and records the run as
machine-readable JSON so the perf trajectory survives across PRs: per
bench the wall time and every row with its derived key=value pairs
(speedups vs legacy, tenant counts, ...) parsed into a dict.  The
default output derives the NEXT free ``BENCH_<n>.json`` index in the
repo root from the records already present (so each PR's run lands in a
fresh, diffable file instead of clobbering the previous PR's baseline);
``--out``/``--json`` pin an explicit path.  ``--quick`` (or env
REPRO_BENCH_QUICK=1) shrinks workloads for CI-speed runs.  Individual
benches can be selected with ``--only <substring>``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import re
import subprocess
import sys
import time
import traceback

# Make `python benchmarks/run.py` work from anywhere: the repo root (for
# the `benchmarks` package) and src/ (for `repro`) must be importable.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BENCHES = [
    "benchmarks.bench_cluster_scaling",   # Fig. 3
    "benchmarks.bench_multi_tenant",      # concurrent queries, shared cluster
    "benchmarks.bench_tpcxbb",            # Fig. 4
    "benchmarks.bench_rollout",           # Fig. 5
    "benchmarks.bench_heavy_rows",        # §III.B row-size case study
    "benchmarks.bench_self_skip",         # §III.B forced-remote case study
    "benchmarks.bench_moe_dispatch",      # technique → TPU (MoE adaptive dispatch)
    "benchmarks.bench_kernels",           # Pallas kernel latencies (interpret)
]


def _jsonable(obj):
    """Deep-copy with NaN/±inf floats replaced by None (strict JSON)."""
    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, float) and (obj != obj or obj in (float("inf"),
                                                         float("-inf"))):
        return None
    return obj


def _next_bench_json() -> str:
    """Default record path: the next free ``BENCH_<n>.json`` index.

    Previous PRs' records stay untouched, so the trajectory
    (BENCH_3.json vs BENCH_4.json vs ...) is diffable from the repo
    alone.  Explicit ``--out``/``--json`` always wins — use it when
    iterating locally (repeated default runs each mint a fresh index;
    only commit the record that represents the PR).  Records carry a
    ``quick`` flag so a shrunken-workload run can never masquerade as a
    full-run baseline when diffing.
    """
    indices = [0]
    for name in os.listdir(_ROOT):
        m = re.fullmatch(r"BENCH_(\d+)\.json", name)
        if m:
            indices.append(int(m.group(1)))
    return os.path.join(_ROOT, f"BENCH_{max(indices) + 1}.json")


def _git_sha() -> str:
    """HEAD commit of the repo the record was produced from, or
    "unknown" outside a git checkout — provenance for diffing BENCH
    records across PRs (which code produced which numbers)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=_ROOT,
            capture_output=True, text=True, timeout=10,
        )
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def _parse_derived(derived: str) -> dict:
    """Best-effort split of a row's derived string into key=value pairs
    (values parsed as float where they look numeric, trailing 'x'/'%'
    units stripped); non-conforming fragments land under 'notes'."""
    out: dict = {}
    notes = []
    for frag in str(derived).split(";"):
        frag = frag.strip()
        if not frag:
            continue
        if "=" not in frag:
            notes.append(frag)
            continue
        k, v = frag.split("=", 1)
        try:
            out[k] = float(v.rstrip("x%"))
        except ValueError:
            out[k] = v
    if notes:
        out["notes"] = "; ".join(notes)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    default=bool(os.environ.get("REPRO_BENCH_QUICK")))
    ap.add_argument("--only", type=str, default="")
    ap.add_argument("--json", "--out", dest="json", type=str, default=None,
                    help="where to write the machine-readable record of "
                         "this run ('' disables; default: the next free "
                         "BENCH_<n>.json in the repo root)")
    ap.add_argument("--check-docs", action="store_true",
                    help="run the README/ARCHITECTURE doc-link check "
                         "instead of the benches (see tools/check_docs.py)")
    ap.add_argument("--seed", type=int,
                    default=int(os.environ.get("REPRO_BENCH_SEED", "0")),
                    help="base seed recorded in the BENCH_<n>.json header "
                         "and exported as REPRO_BENCH_SEED for bench "
                         "modules that consult it (default 0)")
    args = ap.parse_args()
    os.environ["REPRO_BENCH_SEED"] = str(args.seed)

    if args.check_docs:
        sys.path.insert(0, os.path.join(_ROOT, "tools"))
        import check_docs

        sys.exit(check_docs.main())

    selected = [m for m in BENCHES if not args.only or args.only in m]
    if args.only and not selected:
        # A typo'd --only used to print the CSV header and exit 0,
        # which reads as "ran fine, zero rows" in CI logs.
        print(f"error: --only {args.only!r} matches no bench; "
              f"choose a substring of: "
              f"{', '.join(m.split('.')[-1] for m in BENCHES)}",
              file=sys.stderr)
        sys.exit(2)

    from repro.runtime.compile_cache import configure_compile_cache

    configure_compile_cache(_ROOT)
    print("name,us_per_call,derived")
    failures = 0
    record = {
        "schema": 1,
        "created_unix": time.time(),
        "quick": bool(args.quick),
        "only": args.only,
        "seed": int(args.seed),
        "git_sha": _git_sha(),
        "benches": [],
    }
    for mod_name in selected:
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
        except ModuleNotFoundError:
            print(f"{mod_name},0,SKIP (module not present)")
            record["benches"].append(
                {"suite": mod_name, "status": "skipped"}
            )
            continue
        entry = {"suite": mod_name, "status": "ok", "rows": []}
        try:
            for name, us, derived in mod.run(quick=args.quick):
                print(f"{name},{us:.1f},{derived}")
                entry["rows"].append({
                    "name": name,
                    "us_per_call": float(us),
                    "derived": _parse_derived(derived),
                })
            entry["wall_s"] = time.time() - t0
            print(f"{mod_name.split('.')[-1]}_wall,"
                  f"{entry['wall_s']*1e6:.0f},total bench wall time")
        except Exception:
            failures += 1
            entry["status"] = "failed"
            entry["wall_s"] = time.time() - t0
            print(f"{mod_name},0,FAILED")
            traceback.print_exc()
        record["benches"].append(entry)
    out_json = args.json
    if out_json is None:
        # A partial (--only) run would pollute the trajectory with an
        # incomplete numbered record; require an explicit path for it.
        out_json = "" if args.only else _next_bench_json()
        if args.only:
            print("# partial run (--only): no BENCH_<n>.json written "
                  "(pass --out to force)", file=sys.stderr)
    if out_json:
        record["total_wall_s"] = sum(
            b.get("wall_s", 0.0) for b in record["benches"]
        )
        with open(out_json, "w", encoding="utf-8") as f:
            # NaN is a legal bench value (e.g. Jain's index of a class
            # with zero completions) but not legal JSON — null it.
            json.dump(_jsonable(record), f, indent=2, sort_keys=True,
                      allow_nan=False)
            f.write("\n")
        print(f"# wrote {out_json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
