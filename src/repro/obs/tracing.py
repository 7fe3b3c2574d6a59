"""Spans with two sinks: the profiler's trace and a bounded ring buffer.

Every span enters a ``jax.profiler.TraceAnnotation`` of its name, with its
args as the event's stats, so a ``jax.profiler`` session (``start_trace``,
TensorBoard, the benchmark's traced run) sees the program's spans on the
profiler's own clock, beside the device's operations.  The annotation is
written whether or not a ring tracer is installed; with no profile being
captured it costs about a microsecond.  ``jax`` is imported on the first
span, so importing ``repro.obs`` does not import it.

The ring (``Tracer``) records spans as complete ("ph": "X") trace events
into a fixed-size buffer; ``export()`` writes the Chrome trace-event format
that Perfetto / chrome://tracing load directly.  It is off unless installed
(``install()``).  ``fenced_span`` is the JAX-aware timer: the caller
registers jitted outputs on the fence and the span closes only after
``jax.block_until_ready`` — otherwise async dispatch makes a jitted step
look ~free.
"""
from __future__ import annotations

import json
import time
from collections import deque
from typing import Deque, List, Optional

from repro.obs import metrics as _metrics

_ANNOTATION = None  # jax.profiler.TraceAnnotation, imported on first use


def _annotation(name: str, args: dict):
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name, **args)


class Span:
    """Context manager for one complete trace event.

    Also usable as the fence for jitted work: call the span with the jax
    outputs to block on (``fence(x)`` returns ``x``), and the duration is
    measured after ``block_until_ready``.  ``dur_s`` is valid after exit
    even when the owning tracer is a no-op, so callers can feed metrics.
    The profiler annotation is open from enter to exit (after the fence).
    """

    __slots__ = ("tracer", "name", "cat", "args", "fenced", "_pending",
                 "_t0_ns", "dur_s", "_note")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 fenced: bool = False, **args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self.fenced = fenced
        self._pending: List[object] = []
        self._t0_ns = 0
        self.dur_s = 0.0

    def __call__(self, x):
        if self.fenced:
            self._pending.append(x)
        return x

    def set_args(self, **args) -> None:
        """Add args known only once the span's work is done (a count of
        what it built); call before exit."""
        self.args.update(args)
        self._note.set_metadata(**args)

    def __enter__(self) -> "Span":
        self._note = _annotation(self.name, self.args)
        self._note.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._pending:
            import jax

            jax.block_until_ready(self._pending)
            self._pending.clear()
        dt_ns = time.perf_counter_ns() - self._t0_ns
        self._note.__exit__(*exc)
        self.dur_s = dt_ns * 1e-9
        if self.tracer is not None:
            self.tracer.emit(
                self.name, self.cat,
                (self._t0_ns - self.tracer._t0_ns) / 1e3, dt_ns / 1e3,
                self.args,
            )


class Tracer:
    """Fixed-capacity ring buffer of Chrome trace events (oldest dropped)."""

    def __init__(self, capacity: int = 65536, pid: int = 0):
        self.capacity = capacity
        self.pid = pid
        self._events: Deque[dict] = deque(maxlen=capacity)
        self._t0_ns = time.perf_counter_ns()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._events)

    def emit(self, name: str, cat: str, ts_us: float, dur_us: float,
             args: Optional[dict] = None, ph: str = "X", tid: int = 0) -> None:
        if len(self._events) == self.capacity:
            self.dropped += 1
        ev = {
            "name": name, "cat": cat, "ph": ph,
            "ts": ts_us, "pid": self.pid, "tid": tid,
        }
        if ph == "X":
            ev["dur"] = dur_us
        if args:
            ev["args"] = dict(args)
        self._events.append(ev)

    def span(self, name: str, cat: str = "obs", **args) -> Span:
        return Span(self, name, cat, **args)

    def fenced_span(self, name: str, cat: str = "jax", **args) -> Span:
        return Span(self, name, cat, fenced=True, **args)

    def events(self) -> List[dict]:
        return sorted(self._events, key=lambda e: e["ts"])

    def export(self, path: Optional[str] = None) -> dict:
        obj = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }
        if path is not None:
            with open(path, "w") as f:
                json.dump(obj, f)
        return obj


_TRACER: Optional[Tracer] = None


def install(capacity: int = 65536) -> Tracer:
    """Install (or replace) the process tracer and enable telemetry."""
    global _TRACER
    _TRACER = Tracer(capacity)
    _metrics.enable()
    return _TRACER


def uninstall() -> None:
    global _TRACER
    _TRACER = None


def tracer() -> Optional[Tracer]:
    return _TRACER


def active() -> bool:
    return _TRACER is not None and _metrics.enabled()


def span(name: str, cat: str = "obs", **args) -> Span:
    t = _TRACER if active() else None
    return Span(t, name, cat, **args)


def fenced_span(name: str, cat: str = "jax", **args) -> Span:
    # Fence only when telemetry is on: an unconditional block_until_ready
    # would serialize async dispatch even with observability disabled.
    t = _TRACER if active() else None
    return Span(t, name, cat, fenced=_metrics.enabled(), **args)
