"""The benchmark's own copy of the simulator's traffic generators.

Copied from `src/repro/sim/workload.py` and `src/repro/sim/replay.py` so
that no change to the program can move the yardstick.  Every function
here returns, for the same arguments, exactly what its original returns
(``bench/tests/test_gen.py`` pins that), but in plain numpy: a
query is a list of per-producer streams, each a list of
``(costs, sizes)`` array pairs.  `bench/program_io.py` turns these into
the program's own input objects; `bench/reference.py` reads them as
they are.

A configuration names its fixed query population in one of two forms
(:func:`suite`): ``{"suite", "num_queries", "seed"}``, a generator of
:data:`SUITES` with its arguments, or ``{"population": "<name>"}``, the
profiles of ``bench/populations/<name>.json`` in file order
(:func:`load_population`).  A population file states every field of
every profile, the policy by name, so a deployment is added as data.

On top of that sits what a cell draws from its ``--seed``
(:func:`query_pool`): the seed orders the population's profiles and
draws each row's cost, size and producer; the row count of every query
comes from the configuration.  A job of several concurrent queries also
draws their arrival times in virtual seconds (:func:`job_arrivals`):
`workload.arrival_times`'s Poisson or on/off process, snapped down onto
the tick grid that `replay.open_loop_tenants(grid_align=...)` builds.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: Policy ids, as `repro.core.types.Policy` numbers them.
POLICY_IDS = {"NEVER": 0, "LATE": 1, "EARLY": 2, "EAGER_SNOWPARK": 3}

POPULATIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "populations")

Stream = List[Tuple[np.ndarray, np.ndarray]]


@dataclasses.dataclass(frozen=True)
class QueryProfile:
    name: str
    n_rows: int = 20_000
    mean_row_cost: float = 2e-3
    cost_sigma: float = 0.5
    partition_alpha: float = 0.0
    hot_fraction: float = 0.0
    row_bytes: float = 512.0
    row_bytes_sigma: float = 0.3
    batch_rows: int = 128
    batch_bytes_target: float = 16e6
    udf: bool = True
    locality_constrained: bool = False
    policy: int = POLICY_IDS["EAGER_SNOWPARK"]


def _partition_rows(
    rng: np.random.Generator, n_rows: int, n_producers: int,
    alpha: float, hot_fraction: float,
) -> np.ndarray:
    if alpha <= 0.0 and hot_fraction <= 0.0:
        return rng.integers(0, n_producers, n_rows)
    probs = np.ones(n_producers)
    if alpha > 0.0:
        probs = 1.0 / np.arange(1, n_producers + 1) ** alpha
    probs = probs / probs.sum()
    if hot_fraction > 0.0:
        probs = (1.0 - hot_fraction) * probs
        probs[0] += hot_fraction
    perm = rng.permutation(n_producers)
    return perm[rng.choice(n_producers, size=n_rows, p=probs)]


def generate_query(
    profile: QueryProfile, n_producers: int, seed: int
) -> List[Stream]:
    """One query's per-producer batch streams (`workload.generate_query`)."""
    rng = np.random.default_rng(seed)
    owner = _partition_rows(
        rng, profile.n_rows, n_producers, profile.partition_alpha,
        profile.hot_fraction,
    )
    mu = np.log(profile.mean_row_cost) - 0.5 * profile.cost_sigma**2
    costs = rng.lognormal(mu, profile.cost_sigma, profile.n_rows)
    smu = np.log(profile.row_bytes) - 0.5 * profile.row_bytes_sigma**2
    sizes = rng.lognormal(smu, profile.row_bytes_sigma, profile.n_rows)

    streams: List[Stream] = []
    target = profile.batch_bytes_target
    batch_rows = profile.batch_rows
    for p in range(n_producers):
        idx = np.nonzero(owner == p)[0]
        cs, sz = costs[idx], sizes[idx]
        m = len(idx)
        csum = np.concatenate(([0.0], np.cumsum(sz)))
        stream: Stream = []
        i = 0
        while i < m:
            limit = min(batch_rows, m - i)
            fit = int(np.searchsorted(csum, csum[i] + target, side="right")) - 1 - i
            if fit >= limit:
                take = limit
            else:
                take, acc = 0, 0.0
                while (
                    take < limit
                    and (take == 0 or acc + sz[i + take] <= target)
                ):
                    acc += sz[i + take]
                    take += 1
            stream.append((cs[i:i + take].copy(), sz[i:i + take].copy()))
            i += take
        streams.append(stream)
    return streams


def customer_replay_suite(num_queries: int = 150, seed: int = 7) -> List[QueryProfile]:
    """Fig. 3's customer replay mix (`workload.customer_replay_suite`)."""
    rng = np.random.default_rng(seed)
    out = []
    for q in range(num_queries):
        r = rng.random()
        alpha = 0.0
        hot = 0.0
        sigma = 0.4
        constrained = False
        n_rows = int(rng.integers(6_000, 24_000))
        if r < 0.55:
            n_rows = int(rng.integers(12_000, 30_000))
            sigma = float(rng.uniform(0.3, 0.8))
        elif r < 0.80:
            alpha = float(rng.uniform(0.1, 0.3))
            hot = float(rng.uniform(0.005, 0.02))
            constrained = bool(rng.random() < 0.35)
        else:
            sigma = float(rng.uniform(1.0, 1.8))
            if rng.random() < 0.4:
                alpha = float(rng.uniform(0.1, 0.4))
        out.append(
            QueryProfile(
                name=f"cust_{q:03d}",
                n_rows=n_rows,
                mean_row_cost=float(10 ** rng.uniform(-3.3, -2.4)),
                cost_sigma=sigma,
                partition_alpha=alpha,
                hot_fraction=hot,
                row_bytes=float(10 ** rng.uniform(2.0, 3.5)),
                locality_constrained=constrained,
            )
        )
    return out


SUITES = {"customer_replay": customer_replay_suite}

_SUITE_KEYS = {"suite", "num_queries", "seed"}
_POPULATION_KEYS = {"population"}


def _profile(i: int, row: Dict) -> QueryProfile:
    """One population file profile, every field stated and typed."""
    where = f"profile {i} ({row.get('name', '?')!r})"
    fields = {f.name: f.type for f in dataclasses.fields(QueryProfile)}
    missing = sorted(fields.keys() - row.keys())
    if missing:
        raise ValueError(f"{where}: field {missing[0]!r} is missing")
    unknown = sorted(row.keys() - fields.keys())
    if unknown:
        raise ValueError(f"{where}: unknown field {unknown[0]!r}")
    out = {}
    for key, kind in fields.items():
        val = row[key]
        if key == "policy":
            if not isinstance(val, str) or val not in POLICY_IDS:
                raise ValueError(f"{where}: field 'policy': unknown policy "
                                 f"{val!r}, not one of {sorted(POLICY_IDS)}")
            val = POLICY_IDS[val]
        elif kind == "float" and type(val) is int:
            val = float(val)
        if type(val).__name__ != kind:
            raise ValueError(f"{where}: field {key!r} must be of type "
                             f"{kind}, not {val!r}")
        out[key] = val
    for key in ("n_rows", "batch_rows"):
        if out[key] <= 0:
            raise ValueError(f"{where}: field {key!r} must be positive, "
                             f"not {out[key]!r}")
    return QueryProfile(**out)


def load_population(name: str, directory: str = POPULATIONS) -> List[QueryProfile]:
    """The profiles of ``<directory>/<name>.json``, in file order.

    Raises ValueError, naming the profile and the field, for a missing
    or unknown field, a value of the wrong type, an unknown policy name
    and a non-positive ``n_rows`` or ``batch_rows``."""
    with open(os.path.join(directory, name + ".json")) as f:
        doc = json.load(f)
    if not doc["profiles"]:
        raise ValueError(f"population {name!r} holds no profiles")
    return [_profile(i, row) for i, row in enumerate(doc["profiles"])]


def scan_arrival_gap(
    prof: QueryProfile, num_workers: int, feed_factor: float = 2.0
) -> float:
    """Backpressured scan gap between a producer's batches
    (`replay.scan_arrival_gap`)."""
    ideal = prof.n_rows * prof.mean_row_cost / num_workers
    nbatches = max(prof.n_rows // min(prof.batch_rows, prof.n_rows), 1)
    return ideal / (feed_factor * nbatches)


# --------------------------------------------------------------------- #
# Arrivals of a job of concurrent queries
# --------------------------------------------------------------------- #

#: The fields of a traffic file's ``arrivals``, by process.
ARRIVAL_FIELDS = {
    "poisson": ("process", "rate"),
    "burst": ("process", "rate", "burst_factor", "burst_fraction",
              "mean_burst_s"),
}

#: The most tick intervals a job's arrivals may span, as the program's
#: `engine._arrivals_on_grid` walks them.
MAX_GRID_STEPS = 1 << 20


def arrival_times(arrivals: Dict, num_arrivals: int, seed: int) -> np.ndarray:
    """``num_arrivals`` open-loop arrival times in virtual seconds
    (`workload.arrival_times`): a Poisson stream at ``rate``, or a
    2-state on/off MMPP whose bursts, exponential with mean
    ``mean_burst_s``, cover ``burst_fraction`` of the time at ``rate *
    burst_factor``."""
    rng = np.random.default_rng(seed)
    rate = arrivals["rate"]
    if arrivals["process"] == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, num_arrivals))
    if arrivals["process"] != "burst":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    f = min(max(arrivals["burst_fraction"], 1e-6), 1 - 1e-6)
    mean_off_s = arrivals["mean_burst_s"] * (1.0 - f) / f
    times: List[float] = []
    t, on = 0.0, False
    while len(times) < num_arrivals:
        dur = rng.exponential(arrivals["mean_burst_s"] if on else mean_off_s)
        r = rate * (arrivals["burst_factor"] if on else 1.0)
        a = t + rng.exponential(1.0 / r)
        while a < t + dur and len(times) < num_arrivals:
            times.append(a)
            a += rng.exponential(1.0 / r)
        t += dur
        on = not on
    return np.asarray(times)


def on_grid(times: np.ndarray, step: float) -> np.ndarray:
    """Each time (>= 0) snapped down onto the chained float grid ``0,
    0+step, (0+step)+step, ...``, built by repeated addition as
    `replay.open_loop_tenants(grid_align=...)` builds it, so that every
    value is one the program's tick chain reaches exactly from any
    earlier one."""
    kmax = int(np.floor(float(times.max()) / step)) + 1
    if kmax > MAX_GRID_STEPS:
        raise ValueError(f"arrivals span {kmax} ticks of {step!r} s, more "
                         f"than {MAX_GRID_STEPS}")
    chain = np.empty(kmax + 1)
    t = 0.0
    for k in range(kmax + 1):
        chain[k] = t
        t += step
    idx = np.searchsorted(chain, times, side="right") - 1
    return chain[np.clip(idx, 0, kmax)]


def job_arrivals(arrivals: Dict, num_queries: int, step: float,
                 seed: int) -> List[float]:
    """One job's arrival times: the first at 0, the later gaps those of
    the process drawn from ``seed``, each time on the grid of ``step``."""
    times = arrival_times(arrivals, num_queries, seed)
    return [float(t) for t in on_grid(times - times[0], step)]


# --------------------------------------------------------------------- #
# What a cell draws from its seed
# --------------------------------------------------------------------- #


@dataclasses.dataclass
class Query:
    """One generated query: the profile it was drawn from, its streams,
    its arrival (virtual seconds) and its scan gap."""

    profile: QueryProfile
    streams: List[Stream]
    arrival: float
    gap: float

    @property
    def rows(self) -> int:
        return self.profile.n_rows

    @property
    def cost(self) -> float:
        """Total hidden UDF seconds of the generated rows."""
        return float(sum(float(c.sum()) for s in self.streams for c, _ in s))


def suite(queries: Dict, populations: str = POPULATIONS) -> List[QueryProfile]:
    """The configuration's fixed query population: a population file
    found by name in ``populations``, or a suite generator's output.

    Raises ValueError unless ``queries`` holds exactly one of the two
    forms."""
    keys = set(queries)
    if keys == _POPULATION_KEYS:
        return load_population(queries["population"], populations)
    if keys == _SUITE_KEYS:
        return SUITES[queries["suite"]](queries["num_queries"], queries["seed"])
    raise ValueError(
        f"queries must be {sorted(_POPULATION_KEYS)} or "
        f"{sorted(_SUITE_KEYS)}, not {sorted(keys)}")


def draw(
    profiles: Sequence[QueryProfile], i: int, num_workers: int,
    feed_factor: float, seed: int,
) -> Query:
    """Profile ``i`` with its rows drawn from ``seed`` (the seed*1000+i
    convention of `run_ab`)."""
    return Query(
        profile=profiles[i],
        streams=generate_query(profiles[i], num_workers, seed * 1000 + i),
        arrival=0.0,
        gap=scan_arrival_gap(profiles[i], num_workers, feed_factor),
    )


def query_pool(
    profiles: Sequence[QueryProfile], num_workers: int, feed_factor: float,
    seed: int,
) -> List[Query]:
    """Every profile once, in an order drawn from ``seed``, each with its
    rows drawn from ``seed``."""
    order = np.random.default_rng([seed, 1]).permutation(len(profiles))
    return [draw(profiles, int(i), num_workers, feed_factor, seed)
            for i in order]
