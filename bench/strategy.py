"""A traffic file's per-query strategy rules, resolved as data.

A traffic file gives one complete ``strategy`` (every field of the
program's `StrategyConfig` and `DySkewConfig`, stated, not defaulted)
and a list of ``rules``.  Each rule whose ``if`` matches the query's
profile fields merges its ``set`` into the strategy, in order.  An
``if`` may name the profile's declared ``policy`` by name
(``{"policy": "LATE"}``) or by its id in `bench.gen.POLICY_IDS`.  The
result is a plain dict that `bench/program_io.py` turns into the
program's config objects and `bench/reference.py` reads as it is.
"""

from __future__ import annotations

import copy
from typing import Dict

from bench.gen import POLICY_IDS, QueryProfile


def _want(key: str, want):
    if key == "policy" and isinstance(want, str):
        if want not in POLICY_IDS:
            raise ValueError(f"rule names unknown policy {want!r}, not one "
                             f"of {sorted(POLICY_IDS)}")
        return POLICY_IDS[want]
    return want


def _matches(cond: Dict, profile: QueryProfile) -> bool:
    wants = {key: _want(key, want) for key, want in cond.items()}
    return all(getattr(profile, key) == want for key, want in wants.items())


def _merge(base: Dict, patch: Dict) -> None:
    for key, val in patch.items():
        if isinstance(val, dict):
            _merge(base[key], val)
        else:
            base[key] = val


def resolve(traffic: Dict, profile: QueryProfile) -> Dict:
    """The strategy one query runs under."""
    out = copy.deepcopy(traffic["strategy"])
    for rule in traffic.get("rules", []):
        if _matches(rule.get("if", {}), profile):
            _merge(out, rule["set"])
    return out
