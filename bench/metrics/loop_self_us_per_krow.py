"""Self time of the event loop and heap per 1000 rows, over the traced
jobs: the ``dyskew.loop`` span less its ``dyskew.route`` and
``dyskew.tick`` children, that is the pops and the enqueue, completion
and other event handlers.

Source: ``last_event_counts["span_ns:dyskew.loop"] - ["span_ns:dyskew.route"]
- ["span_ns:dyskew.tick"]``, summed over the traced jobs.  Nothing to
read where the program recorded no span."""


def read(obs):
    jobs = obs.get("traced")
    if not jobs or not jobs["rows"] or not jobs["counts"].get("span_n:dyskew.run"):
        return None
    c = jobs["counts"]
    ns = (c.get("span_ns:dyskew.loop", 0) - c.get("span_ns:dyskew.route", 0)
          - c.get("span_ns:dyskew.tick", 0))
    return ns / jobs["rows"]
