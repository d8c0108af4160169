"""Dependency-free telemetry core: spans, counters, gauges, histograms.

The observability spine of the engine.  Everything here is plain
stdlib — no jax, no numpy — so the instrumented hot paths
(``core/engine/session.py``, ``core/engine/aggregate.py``,
``core/federated_methods.py``) pay dict-update + ``perf_counter`` cost
and nothing else, and the module is importable from anywhere without
cycles.

  * ``Registry`` — counters (monotonic sums), gauges (last-write
    scalars), histograms (raw-value series with numpy-convention
    percentiles), plus a thread-local span stack for nested timing.
  * ``Registry.span(name)`` — context manager: on exit the duration
    lands in the ``"<name>.ms"`` histogram AND a ``"span"`` event
    (with ``parent``/``depth`` from the nesting stack) goes to every
    attached sink.  The yielded dict carries the measured ``ms`` after
    the block, so callers can reuse the number without re-timing.
    Once JAX is imported, the span is also a
    ``jax.profiler.TraceAnnotation`` of the same name: under a profiler
    session it lands on the host planes of the trace, on the clock of
    the device's events, so an idle gap on the device can be put down
    to the span open on the host (about a microsecond when no profiler
    runs).
  * sinks (``obs/sinks.py``) — anything with ``emit(event: dict)``;
    ``JsonlSink`` appends events as JSON lines, ``ConsoleSink`` prints
    a summary table on close, and ``Registry.snapshot()`` is the dict
    sink the benchmarks embed into their schema-versioned JSON.

A process-global registry backs the module-level convenience functions
(``span`` / ``count`` / ``gauge`` / ``observe`` / ``event`` /
``snapshot`` / ``reset`` / ``add_sink``), which is what the engine
modules call; tests construct private ``Registry`` instances.
"""
from __future__ import annotations

import contextlib
import math
import sys
import threading
import time
from typing import Any, Callable, Iterable, Optional


_TraceAnnotation = None


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, imported on first use; a
    null context while JAX is not imported (no profiler can run then),
    so this module needs nothing beyond the standard library."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        if "jax" not in sys.modules:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation as _TraceAnnotation
    return _TraceAnnotation(name)


class Histogram:
    """A value series with numpy-default (linear interpolation)
    percentiles — ``percentile(p)`` matches ``numpy.percentile`` on the
    same values, which ``tests/test_obs.py`` pins."""

    __slots__ = ("values",)

    def __init__(self, values: Optional[Iterable[float]] = None):
        self.values: list[float] = list(values or ())

    def observe(self, value: float) -> None:
        self.values.append(float(value))

    def merge(self, other: "Histogram") -> None:
        self.values.extend(other.values)

    @property
    def count(self) -> int:
        return len(self.values)

    def percentile(self, p: float) -> float:
        if not self.values:
            return float("nan")
        vals = sorted(self.values)
        rank = (p / 100.0) * (len(vals) - 1)
        lo = math.floor(rank)
        hi = math.ceil(rank)
        if lo == hi:
            return vals[lo]
        frac = rank - lo
        return vals[lo] * (1.0 - frac) + vals[hi] * frac

    def summary(self) -> dict:
        if not self.values:
            return {"count": 0}
        total = sum(self.values)
        return {
            "count": len(self.values),
            "sum": total,
            "mean": total / len(self.values),
            "min": min(self.values),
            "max": max(self.values),
            "p50": self.percentile(50.0),
            "p95": self.percentile(95.0),
            "p99": self.percentile(99.0),
        }


class Registry:
    """Counters + gauges + histograms + sinks + a span stack.

    Mutations are guarded by a lock (the engine is single-threaded
    today, but sinks/serving loops need not be); the span *stack* is
    thread-local so nesting is per-thread.  ``reset()`` clears the
    aggregates but keeps attached sinks — a driver that attached a
    JSONL trace keeps receiving events across ``simulate()`` runs.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        self.histograms: dict[str, Histogram] = {}
        self._sinks: list[Any] = []

    # ----------------------------------------------------------- metrics

    def count(self, name: str, value: float = 1.0) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + float(value)

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = float(value)

    def observe(self, name: str, value: float) -> None:
        with self._lock:
            hist = self.histograms.get(name)
            if hist is None:
                hist = self.histograms[name] = Histogram()
            hist.observe(value)

    # ------------------------------------------------------------- spans

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, **fields: Any):
        """Time a block: duration -> ``"<name>.ms"`` histogram + a
        ``"span"`` event carrying nesting (``parent``/``depth``), and a
        profiler trace annotation of the same name around it.  The
        yielded dict gains ``"ms"`` on exit."""
        stack = self._stack()
        info = {"name": name, **fields}
        if stack:
            info["parent"] = stack[-1]
        info["depth"] = len(stack)
        stack.append(name)
        t0 = time.perf_counter()
        try:
            with _annotation(name):
                yield info
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            stack.pop()
            info["ms"] = ms
            self.observe(f"{name}.ms", ms)
            self.event("span", **info)

    # ------------------------------------------------------------- sinks

    def add_sink(self, sink: Any) -> Any:
        """Attach anything with ``emit(event: dict)`` (and optionally
        ``close()``).  Returns the sink."""
        with self._lock:
            self._sinks.append(sink)
        return sink

    def remove_sink(self, sink: Any) -> None:
        with self._lock:
            if sink in self._sinks:
                self._sinks.remove(sink)

    def event(self, kind: str, **fields: Any) -> Optional[dict]:
        """Emit one structured event to every sink. Returns the event,
        or ``None`` when no sink is attached: then nothing is built."""
        with self._lock:
            sinks = list(self._sinks)
        if not sinks:
            return None
        evt = {"event": kind, "ts": time.time(), **fields}
        for sink in sinks:
            sink.emit(evt)
        return evt

    def close_sinks(self) -> None:
        with self._lock:
            sinks, self._sinks = list(self._sinks), []
        for sink in sinks:
            close = getattr(sink, "close", None)
            if callable(close):
                close()

    # ---------------------------------------------------------- snapshot

    def snapshot(self) -> dict:
        """The dict sink: aggregates only (no raw event stream) — what
        the benchmarks embed per row into their schema-versioned JSON."""
        with self._lock:
            return {
                "counters": dict(self.counters),
                "gauges": dict(self.gauges),
                "histograms": {n: h.summary()
                               for n, h in self.histograms.items()},
            }

    def merge(self, other: "Registry") -> None:
        """Fold another registry's aggregates in.  Counter sums and
        histogram value multisets are order-independent under merge
        (the hypothesis property in ``tests/test_obs.py``); gauges are
        last-write-wins by definition."""
        with self._lock:
            for name, v in other.counters.items():
                self.counters[name] = self.counters.get(name, 0.0) + v
            self.gauges.update(other.gauges)
            for name, h in other.histograms.items():
                mine = self.histograms.get(name)
                if mine is None:
                    mine = self.histograms[name] = Histogram()
                mine.merge(h)

    def reset(self) -> None:
        """Drop all aggregates; attached sinks stay attached."""
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            self.histograms.clear()


# ------------------------------------------------- process-global registry

GLOBAL = Registry()


def span(name: str, **fields: Any):
    return GLOBAL.span(name, **fields)


def count(name: str, value: float = 1.0) -> None:
    GLOBAL.count(name, value)


def gauge(name: str, value: float) -> None:
    GLOBAL.gauge(name, value)


def observe(name: str, value: float) -> None:
    GLOBAL.observe(name, value)


def event(kind: str, **fields: Any) -> Optional[dict]:
    return GLOBAL.event(kind, **fields)


def add_sink(sink: Any) -> Any:
    return GLOBAL.add_sink(sink)


def remove_sink(sink: Any) -> None:
    GLOBAL.remove_sink(sink)


def snapshot() -> dict:
    return GLOBAL.snapshot()


def reset() -> None:
    GLOBAL.reset()
