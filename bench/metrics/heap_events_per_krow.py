"""Heap events the event loop popped per 1000 rows, over the traced jobs.

Source: the program's ``MultiQuerySimulator.last_event_counts
["heap_events"]``, summed over the traced jobs."""


def read(obs):
    jobs = obs.get("traced")
    if not jobs or not jobs["rows"]:
        return None
    return 1000.0 * jobs["counts"].get("heap_events", 0) / jobs["rows"]
