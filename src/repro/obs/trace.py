"""Structured tracing: nestable spans → Chrome trace-event JSON and the
JAX profiler's own trace.

The :class:`Tracer` records *complete* events (``ph: "X"``) and
*instant* events (``ph: "i"``) in the Chrome Trace Event format —
``{"traceEvents": [...]}`` — which chrome://tracing and Perfetto load
directly, giving the serving engines and the plan executor a zoomable
timeline for free (DESIGN.md §11).

Timestamps are ``time.perf_counter()`` microseconds relative to the
tracer's epoch, so spans from every thread share one monotonic clock.
A span is a context manager that times its body.  Code that keeps its
own perf_counter stamps for its stats (the engines' RoundStats /
StepStats / Request accounting) takes them inside the span and hands
them over with :meth:`Span.stamp`, so the timeline and the stats views
can never disagree about a duration.

A span may also enter a ``jax.profiler.TraceAnnotation`` of the same
name (``annotate=True``): the span then lies on the profiler's host
plane, on the clock of the device trace, whether or not the Chrome
tracer records it (``tracer=None``).

``tid`` defaults to the recording thread's ident; slot-scoped serving
spans override it with the slot index so Perfetto renders one lane per
slot.  ``list.append`` is atomic under the GIL, so concurrent recording
needs no lock on the hot path.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "Span", "NULL_SPAN"]


class _NullSpan:
    """Shared, stateless no-op context manager — the disabled path
    allocates nothing (obs.span returns this singleton)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def stamp(self, t0_s=None, t1_s=None) -> None: ...

    def set(self, **args) -> None: ...


NULL_SPAN = _NullSpan()


def _scalar(args: Dict[str, Any]) -> Dict[str, Any]:
    """Profiler metadata takes numbers and strings; the rest as text."""
    return {k: v if isinstance(v, (int, float, str)) else str(v)
            for k, v in args.items()}


class Span:
    """One span: a Chrome event when ``tracer`` is given, a profiler
    annotation when ``annotate`` is set."""

    __slots__ = ("_tracer", "_name", "_alias", "_args", "_tid", "_ann",
                 "t0", "t1")

    def __init__(self, tracer: Optional["Tracer"], name: str,
                 tid: Optional[int], args: Dict[str, Any], *,
                 annotate: bool = False, alias: Optional[str] = None):
        self._tracer = tracer
        self._name = name
        self._alias = alias
        self._args = args
        self._tid = tid
        self._ann = TraceAnnotation(name, **_scalar(args)) if annotate \
            else None
        self.t0 = self.t1 = None

    def stamp(self, t0_s: float, t1_s: float) -> None:
        """Adopt the caller's own perf_counter stamps for the Chrome event
        (taken inside the span; the profiler keeps its own clock)."""
        self.t0, self.t1 = t0_s, t1_s

    def set(self, **args) -> None:
        """Arguments known only inside the body."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**_scalar(args))

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter() if self.t1 is None else self.t1
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._tracer is not None:
            for name in (self._name, self._alias):
                if name is not None:
                    self._tracer.complete(name, self.t0, t1, tid=self._tid,
                                          **self._args)
        return False


class Tracer:
    def __init__(self):
        self.events: List[Dict[str, Any]] = []
        self.epoch = time.perf_counter()
        self.pid = os.getpid()

    def _us(self, t_s: float) -> float:
        return (t_s - self.epoch) * 1e6

    def span(self, name: str, *, tid: Optional[int] = None,
             annotate: bool = False, alias: Optional[str] = None, **args):
        """Context manager timing its body into one complete event (two,
        under ``name`` and ``alias``, when an alias is given)."""
        return Span(self, name, tid, args, annotate=annotate, alias=alias)

    def complete(self, name: str, t0_s: float, t1_s: float, *,
                 tid: Optional[int] = None, **args) -> None:
        """Record a complete ("X") event from existing perf_counter stamps."""
        self.events.append({
            "name": name, "ph": "X", "cat": name.split(".", 1)[0],
            "ts": self._us(t0_s), "dur": max(0.0, (t1_s - t0_s) * 1e6),
            "pid": self.pid,
            "tid": threading.get_ident() if tid is None else int(tid),
            "args": args})

    def instant(self, name: str, *, tid: Optional[int] = None,
                **args) -> None:
        """Record an instant ("i", thread-scoped) event at now."""
        self.events.append({
            "name": name, "ph": "i", "s": "t",
            "cat": name.split(".", 1)[0],
            "ts": self._us(time.perf_counter()), "pid": self.pid,
            "tid": threading.get_ident() if tid is None else int(tid),
            "args": args})

    def to_chrome(self) -> Dict[str, Any]:
        """The loadable trace object (stable event order: by ts)."""
        return {"traceEvents": sorted(self.events,
                                      key=lambda e: (e["ts"], e["ph"])),
                "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
