"""Share of the traced window outside the program's ``dyskew.run``
spans: the harness, the program's input glue and the gaps between jobs.

Source: 100 * (1 - the traced jobs' summed
``last_event_counts["span_ns:dyskew.run"]`` / the trace's window).
Nothing to read without a trace or a span."""


def read(obs):
    trace, jobs = obs.get("trace"), obs.get("traced")
    if not trace or not trace["window_s"] or not jobs:
        return None
    ns = jobs["counts"].get("span_ns:dyskew.run")
    if ns is None:
        return None
    return 100.0 * (1.0 - 1e-9 * ns / trace["window_s"])
