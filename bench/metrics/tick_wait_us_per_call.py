"""Microseconds the host waits for the device's distribute mask, per
tick-driver call, over the traced jobs (the ``np.asarray`` of the
jitted tick's output: the device's work plus the round trip).

Source: ``last_event_counts["span_ns:dyskew.tick.wait"] /
["span_n:dyskew.tick"]``, summed over the traced jobs.  Nothing to read
where no tick span was recorded."""


def read(obs):
    jobs = obs.get("traced")
    calls = jobs and jobs["counts"].get("span_n:dyskew.tick")
    if not calls:
        return None
    return 1e-3 * jobs["counts"].get("span_ns:dyskew.tick.wait", 0) / calls
