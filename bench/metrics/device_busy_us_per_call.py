"""Device busy microseconds per tick-driver call, over the traced jobs.

Source: the union of device-op intervals in the profiler trace of the
traced jobs, divided by those jobs' tick calls
(``last_event_counts["tick"] + ["gtick"]``)."""


def read(obs):
    trace, jobs = obs.get("trace"), obs.get("traced")
    if not trace or not jobs:
        return None
    calls = jobs["counts"].get("tick", 0) + jobs["counts"].get("gtick", 0)
    if not calls:
        return None
    return 1e6 * trace["busy_s"] / calls
