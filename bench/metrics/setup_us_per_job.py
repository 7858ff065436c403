"""Wall microseconds of one job's set-up in the program, over the traced
jobs: rings, policies, tick groups and link drivers, up to the first
heap pop.

Source: the program's ``dyskew.setup`` span over its ``dyskew.run``
spans (one per job): ``last_event_counts["span_ns:dyskew.setup"]`` over
``["span_n:dyskew.run"]``, summed over the traced jobs.  Nothing to read
where the program recorded no span."""


def read(obs):
    jobs = obs.get("traced")
    if not jobs or not jobs["counts"].get("span_n:dyskew.run"):
        return None
    c = jobs["counts"]
    return 1e-3 * c.get("span_ns:dyskew.setup", 0) / c["span_n:dyskew.run"]
