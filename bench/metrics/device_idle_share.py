"""Share of the traced window in which no operation ran on the device.

Source: 1 - (union of device-op intervals / the traced window), from the
profiler trace of the traced jobs."""


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
