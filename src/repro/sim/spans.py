"""Wall-time spans of one `MultiQuerySimulator.run`.

A `Spans` recorder is on only while a JAX profiler session records
(`jax.profiler.TraceAnnotation.is_enabled()`, read once per run).  Off,
`span` hands back one shared null context and nothing is recorded.  On,
each span is a `jax.profiler.TraceAnnotation`, so it lands on the
profiler's host plane beside the device ops, and its wall nanoseconds
and its count are added to per-name totals.  A span's clock starts
before its annotation is made, so a parent's self time leaves out its
children's instrumentation; consecutive phases share one clock read, so
they tile their parent.  `counts` turns the totals into the integer
``span_ns:<name>`` / ``span_n:<name>`` and ``event_ns:<kind>`` keys the
run adds to ``last_event_counts``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Optional

import jax

_NULL = contextlib.nullcontext()


def _now() -> int:
    # dyslint: disable=DY104 -- telemetry only: wall ns feed the span totals, never virtual time
    return time.perf_counter_ns()


class _Span:
    __slots__ = ("_rec", "_name", "_ann", "_t0")

    def __init__(self, rec: "Spans", name: str):
        self._rec = rec
        self._name = name

    def begin(self, t0: int) -> "_Span":
        self._t0 = t0
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        return self

    def end(self, t1: int) -> None:
        self._ann.__exit__(None, None, None)
        rec, name = self._rec, self._name
        rec.ns[name] = rec.ns.get(name, 0) + t1 - self._t0
        rec.n[name] = rec.n.get(name, 0) + 1

    def __enter__(self) -> "_Span":
        return self.begin(_now())

    def __exit__(self, *exc) -> None:
        self.end(_now())


class Spans:
    """Span recorder of one run; off unless constructed with ``on``."""

    def __init__(self, on: bool = False):
        self.on = on
        #: Inclusive wall nanoseconds and number of spans, per name.
        self.ns: Dict[str, int] = {}
        self.n: Dict[str, int] = {}
        #: Event-loop wall nanoseconds per heap event kind (set by the run).
        self.event_ns: Dict[str, int] = {}
        self._phase: Optional[_Span] = None

    @classmethod
    def for_run(cls) -> "Spans":
        """On exactly when a JAX profiler session is recording."""
        return cls(jax.profiler.TraceAnnotation.is_enabled())

    def span(self, name: str):
        return _Span(self, name) if self.on else _NULL

    def phase(self, name: Optional[str]) -> None:
        """End the open phase span, if any, and open ``name`` (None opens
        nothing).  Phases are consecutive spans of one level."""
        if not self.on:
            return
        t = _now()
        if self._phase is not None:
            self._phase.end(t)
        self._phase = None if name is None else _Span(self, name).begin(t)

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for name, ns in self.ns.items():
            out[f"span_ns:{name}"] = ns
            out[f"span_n:{name}"] = self.n[name]
        for kind, ns in self.event_ns.items():
            out[f"event_ns:{kind}"] = ns
        return out


#: The recorder of a tick driver used on its own.
OFF = Spans()
