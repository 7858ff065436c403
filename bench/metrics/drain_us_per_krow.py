"""Wall microseconds of the closed-form drain per 1000 rows, over the
traced jobs (0 where no job drained).

Source: ``last_event_counts["span_ns:dyskew.drain"]``, summed over the
traced jobs.  Nothing to read where the program recorded no span."""


def read(obs):
    jobs = obs.get("traced")
    if not jobs or not jobs["rows"] or not jobs["counts"].get("span_n:dyskew.run"):
        return None
    return jobs["counts"].get("span_ns:dyskew.drain", 0) / jobs["rows"]
