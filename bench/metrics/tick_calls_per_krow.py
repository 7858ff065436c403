"""Tick-driver calls (per-query ticks plus batched group ticks) per 1000
rows, over the traced jobs.  Nothing to read where no link ticked.

Source: ``last_event_counts["tick"] + ["gtick"]``, summed over the traced
jobs."""


def read(obs):
    jobs = obs.get("traced")
    if not jobs or not jobs["rows"]:
        return None
    calls = jobs["counts"].get("tick", 0) + jobs["counts"].get("gtick", 0)
    if not calls:
        return None
    return 1000.0 * calls / jobs["rows"]
