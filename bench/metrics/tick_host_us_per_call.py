"""Host microseconds of one tick-driver call, over the traced jobs: the
tick handler's input formulas, padding and launch, less its wait for
the device's mask.

Source: ``(last_event_counts["span_ns:dyskew.tick"] - ["span_ns:dyskew.tick.wait"])
/ ["span_n:dyskew.tick"]``, summed over the traced jobs.  Nothing to
read where no tick span was recorded."""


def read(obs):
    jobs = obs.get("traced")
    calls = jobs and jobs["counts"].get("span_n:dyskew.tick")
    if not calls:
        return None
    c = jobs["counts"]
    return 1e-3 * (c["span_ns:dyskew.tick"]
                   - c.get("span_ns:dyskew.tick.wait", 0)) / calls
