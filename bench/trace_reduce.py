"""Reduce a profiler trace (``.xplane.pb``) to the device metrics.

The harness wraps every traced job in a host annotation named
``bench_job`` (`jax.profiler.TraceAnnotation`).  The traced window runs
from the first such span's start to the last one's end.  Within it:

* busy: the union of the intervals in which an operation ran on a
  device (the device plane's ``XLA Ops`` line), averaged over the
  devices that ran any;
* idle gaps: the rest of the window.  Each of the longest is named by
  what the host thread that ran the jobs was doing at its midpoint: the
  job's ``name`` argument (or ``between jobs``), then the innermost
  host event there (JAX's own, such as a jitted call's dispatch or a
  transfer), or ``python`` where JAX recorded none;
* top device ops: total device time per operation, by its short name.

It needs nothing but JAX's own reader of the trace, and no name from
the program.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

JOB_SPAN = "bench_job"
OPS_LINE = "XLA Ops"

Interval = Tuple[float, float]
Event = Tuple[float, float, str]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def short_name(name: str) -> str:
    """``%fusion.9 = (f32[512,64]...) fusion(...)`` -> ``fusion.9``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _stat(event, key: str) -> Optional[object]:
    for k, v in event.stats:
        if k == key:
            return v
    return None


def read(path: str) -> Dict:
    """The job spans, the other events of the host thread that ran them,
    and each device's op events, in ns."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    jobs: List[Event] = []
    host: List[Event] = []
    devices: Dict[str, List[Event]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                evs = devices.setdefault(plane.name, [])
                for ev in line.events:
                    evs.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                short_name(ev.name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev)
                       for ev in line.events]
                mine = [(s, e, str(_stat(ev, "name") or "job"))
                        for s, e, ev in evs if ev.name == JOB_SPAN]
                if mine:
                    jobs.extend(mine)
                    host.extend((s, e, ev.name) for s, e, ev in evs
                                if ev.name != JOB_SPAN)
    return {"jobs": sorted(jobs), "host": host, "devices": devices}


def _innermost(events: List[Event], t: float) -> Optional[str]:
    best = None
    for s, e, name in events:
        if s <= t < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return None if best is None else best[2]


def name_gap(raw: Dict, s: float, e: float) -> str:
    mid = 0.5 * (s + e)
    job = _innermost(raw["jobs"], mid) or "between jobs"
    return f"{job}: {_innermost(raw.get('host', []), mid) or 'python'}"


def reduce(raw: Dict, top: int = 10) -> Optional[Dict]:
    """Busy and window seconds, the top device ops and the longest idle
    gaps, or None when the trace holds no job span or no device op."""
    jobs = raw["jobs"]
    devices = {k: v for k, v in raw["devices"].items() if v}
    if not jobs or not devices:
        return None
    lo = min(s for s, _, _ in jobs)
    hi = max(e for _, e, _ in jobs)
    busy_ns = 0.0
    per_op: Dict[str, float] = {}
    idle: List[Interval] = []
    for evs in devices.values():
        busy = union(clip([(s, e) for s, e, _ in evs], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for s, e, name in evs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                per_op[name] = per_op.get(name, 0.0) + (e - s)
        idle.extend(gaps(busy, lo, hi))
    n = len(devices)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns * 1e-9 / n,
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        "device_ops": [[name, t * 1e-9 / n] for name, t in ops],
        "idle_gaps": [[name_gap(raw, s, e), (e - s) * 1e-9] for s, e in longest],
    }
