"""repro.obs — unified tracing, metrics, and timeline export (DESIGN.md §11).

One process-wide tracer + metrics registry behind a module facade, OFF
by default: every instrumentation site in the serving engines and the
plan executor goes through these helpers, and when
disabled each helper is a boolean check returning a shared no-op
singleton — the engines' token streams, dispatch counts, and RoundStats
are byte-identical with the subsystem off (asserted in tests/test_obs_
integration.py) and the per-call overhead is a bare function call
(microbenched in tests/test_obs.py).

Enable with ``REPRO_OBS=1`` in the environment or :func:`enable` in
code (the ``--trace-out``/``--metrics-out`` flags of launch/serve.py,
launch/plan.py and benchmarks/serve_bench.py do the latter).

Spans also land on the JAX profiler's clock: while a profiler session
collects (``jax.profiler.trace`` / ``start_trace``), :func:`span` enters
a ``jax.profiler.TraceAnnotation`` of the same name, enabled or not, so
the device trace and the program's spans line up.  With neither on it
returns ``NULL_SPAN``.  Three export surfaces:

* :func:`write_trace` — Chrome trace-event JSON (Perfetto-loadable
  timeline: per-slot serving lanes, per-task executor spans);
* :func:`write_prometheus` — Prometheus text exposition of every
  counter/gauge/histogram (the scrape surface);
* :func:`write_jsonl` — one JSON object per time series, the offline
  event log ``launch/summarize.py --metrics`` renders and diffs.

Metric families follow the §11 naming scheme: ``repro_serve_*`` (engine
lifecycle: TTFT/TPOT histograms, slot/queue gauges, admission/eviction
counters) and ``repro_plan_*`` (executor tasks/retries/stragglers).
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict

from .metrics import Counter, Gauge, Histogram, Registry
from .trace import NULL_SPAN, Span, TraceAnnotation, Tracer

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "Tracer",
           "enabled", "enable", "disable", "reset", "registry", "tracer",
           "scoped",
           "span", "instant", "counter", "gauge", "histogram",
           "counters_snapshot", "prometheus_text", "jsonl_lines",
           "write_trace", "write_prometheus", "write_jsonl"]

_enabled: bool = os.environ.get("REPRO_OBS", "0").lower() \
    not in ("0", "", "false", "off")
_registry = Registry()
_tracer = Tracer()


class _NullMetric:
    """Accepts every instrument method as a no-op (the disabled path)."""

    __slots__ = ()

    def inc(self, amount: float = 1.0) -> None: ...

    def add(self, amount: float = 1.0) -> None: ...

    def set(self, value: float) -> None: ...

    def observe(self, value: float) -> None: ...


_NULL_METRIC = _NullMetric()


def enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Fresh registry + tracer (test isolation / per-run scoping)."""
    global _registry, _tracer
    _registry = Registry()
    _tracer = Tracer()


def registry() -> Registry:
    return _registry


def tracer() -> Tracer:
    return _tracer


@contextlib.contextmanager
def scoped(*, enable_obs: bool = False):
    """Swap in a fresh registry + tracer for the body, restore on exit.

    An isolated measurement scope: a benchmark section that must not
    pollute the surrounding run's counters (e.g. serve_bench's quality
    cells run obs-enabled even when the ladder runs obs-off, and the
    surrounding run's counters would otherwise see their traffic).  The
    enabled flag is saved/restored too; ``enable_obs`` turns recording
    on inside the scope.  Yields ``(registry, tracer)``.
    """
    global _registry, _tracer, _enabled
    saved = (_registry, _tracer, _enabled)
    _registry, _tracer = Registry(), Tracer()
    if enable_obs:
        _enabled = True
    try:
        yield _registry, _tracer
    finally:
        _registry, _tracer, _enabled = saved


# -- recording facade (each helper no-ops when disabled) --------------------


#: True while a profiler session collects (jaxlib's TraceMe check)
_profiling = TraceAnnotation.is_enabled


def span(name: str, **args):
    """``with obs.span("serve.prefill", slot=3) as sp: …`` — times the body
    into the Chrome trace when enabled, and into the profiler's trace
    while a session collects.  ``tid=`` picks the Chrome lane, ``alias=``
    records the Chrome event under a second name too; ``sp.stamp(t0, t1)``
    hands the Chrome event the caller's own stamps, ``sp.set(**args)``
    adds arguments known only inside the body."""
    if _enabled:
        return _tracer.span(name, annotate=_profiling(), **args)
    if _profiling():
        args.pop("alias", None)
        return Span(None, name, args.pop("tid", None), args, annotate=True)
    return NULL_SPAN


def instant(name: str, **args) -> None:
    if _enabled:
        _tracer.instant(name, **args)


def counter(name: str, **labels):
    return _registry.counter(name, **labels) if _enabled else _NULL_METRIC


def gauge(name: str, **labels):
    return _registry.gauge(name, **labels) if _enabled else _NULL_METRIC


def histogram(name: str, **labels):
    return _registry.histogram(name, **labels) if _enabled else _NULL_METRIC


# -- export surfaces --------------------------------------------------------


def counters_snapshot(prefix: str = "") -> Dict[str, float]:
    return _registry.counters_snapshot(prefix)


def prometheus_text() -> str:
    return _registry.to_prometheus()


def jsonl_lines():
    return _registry.jsonl_lines()


def write_trace(path: str) -> None:
    _tracer.write(path)


def write_prometheus(path: str) -> None:
    with open(path, "w") as f:
        f.write(_registry.to_prometheus())


def write_jsonl(path: str) -> None:
    _registry.dump_jsonl(path)
