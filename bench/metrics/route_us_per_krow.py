"""Wall microseconds of routing per 1000 rows, over the traced jobs:
each arrival's (or same-instant run of arrivals') policy, admission and
waterfill.

Source: ``last_event_counts["span_ns:dyskew.route"]``, summed over the
traced jobs.  Nothing to read where the program recorded no span."""


def read(obs):
    jobs = obs.get("traced")
    if not jobs or not jobs["rows"] or not jobs["counts"].get("span_n:dyskew.run"):
        return None
    return jobs["counts"].get("span_ns:dyskew.route", 0) / jobs["rows"]
