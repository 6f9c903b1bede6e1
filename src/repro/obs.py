"""The program's tracer: host spans, per-step counters and compile counts.

A span is ``(name, t0, t1, depth)`` on ``time.perf_counter()``, recorded
when it closes, with the name of the span it was opened in and the
trainer step it belongs to.  ``step(n)`` opens the span of one trainer
step; the spans opened inside it belong to step ``n``.  Counters are kept
per step.  While a recording tracer is in a step, every backend compile
(``jax.monitoring``'s compile duration event, a cold compile or a load
from the persistent cache) counts as ``compiles`` in that step.  Each step
also records the process's CPU seconds and involuntary context switches
over it (``resource.getrusage``), so a long step shows whether the host
was descheduled.  Code with no tracer at hand counts through
``count_event(name)``, a ``jax.monitoring`` event that the recording
tracer in a step counts as ``name``: the model's ``attention`` counts the
path each traced call takes (``attention.fused``, ``attention.chunked``).

With ``annotate=True`` each span is also a
``jax.profiler.TraceAnnotation("repro.<name>")`` and each step a
``jax.profiler.StepTraceAnnotation("train", step_num=n)``: the program's
spans land on the device trace's own clock.

``NULL`` is the tracer of a program that nobody traces: its ``span`` and
``step`` return one shared context that does nothing, and ``count`` does
nothing.  No tracer ever waits for the device.
"""

from __future__ import annotations

import resource
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

PREFIX = "repro."
STEP = "train"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COUNT_EVENT = "/repro/count/"

_active: Optional["Tracer"] = None  # the recording tracer inside a step
_listening = False


def _on_duration(event: str, secs: float, **_: Any) -> None:
    if event == COMPILE_EVENT and _active is not None:
        _active.count("compiles")


def _on_event(event: str, **_: Any) -> None:
    if event.startswith(COUNT_EVENT) and _active is not None:
        _active.count(event[len(COUNT_EVENT) :])


def _listen() -> None:
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


def count_event(name: str) -> None:
    """Count ``name`` in the current step of the recording tracer, if one
    is in a step; nothing else listens."""
    jax.monitoring.record_event(COUNT_EVENT + name)


def _rusage() -> Tuple[float, int]:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return r.ru_utime + r.ru_stime, r.ru_nivcsw


class _Noop:
    """The shared context of the NULL tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


class _Span:
    """One span of a recording tracer; ``step`` is set on a step's span."""

    __slots__ = ("tracer", "name", "step", "t0", "usage", "ann")

    def __init__(self, tracer: "Tracer", name: str, step: Optional[int] = None):
        self.tracer, self.name, self.step = tracer, name, step
        self.ann = None

    def __enter__(self) -> "_Span":
        self.tracer._open(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.tracer._close(self, time.perf_counter())
        return False


class Tracer:
    """Records spans, counters and step attributes in memory."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.records: List[Tuple[str, float, float, int]] = []
        self.parents: List[Optional[str]] = []  # per record: the enclosing span's name
        self.steps_of: List[Optional[int]] = []  # per record: its trainer step
        self.counters: Dict[Optional[int], Dict[str, int]] = {}
        self.step_attrs: Dict[int, Dict[str, float]] = {}
        self._open_names: List[str] = []
        self._step: Optional[int] = None
        _listen()

    def span(self, name: str) -> _Span:
        return _Span(self, name)

    def step(self, step: int) -> _Span:
        return _Span(self, "step", step)

    def count(self, name: str, n: int = 1) -> None:
        c = self.counters.setdefault(self._step, {})
        c[name] = c.get(name, 0) + n

    def _open(self, span: _Span) -> None:
        global _active
        if span.step is not None:
            self._step = span.step
            _active = self
            span.usage = _rusage()
            if self.annotate:
                span.ann = jax.profiler.StepTraceAnnotation(STEP, step_num=span.step)
        elif self.annotate:
            span.ann = jax.profiler.TraceAnnotation(PREFIX + span.name)
        if span.ann is not None:
            span.ann.__enter__()
        self._open_names.append(span.name)

    def _close(self, span: _Span, t1: float) -> None:
        global _active
        if span.ann is not None:
            span.ann.__exit__(None, None, None)
        self._open_names.pop()
        depth = len(self._open_names)
        self.records.append((span.name, span.t0, t1, depth))
        self.parents.append(self._open_names[-1] if self._open_names else None)
        self.steps_of.append(self._step)
        if span.step is not None:
            cpu, switches = _rusage()
            self.step_attrs[span.step] = {
                "cpu_s": cpu - span.usage[0],
                "invol_switches": switches - span.usage[1],
            }
            self._step = None
            if _active is self:
                _active = None

    def durations(self, name: str) -> List[float]:
        return [t1 - t0 for n, t0, t1, _ in self.records if n == name]


class _NullTracer:
    """Records nothing: every span is one shared no-op context."""

    def span(self, name: str) -> _Noop:
        return _NOOP

    def step(self, step: int) -> _Noop:
        return _NOOP

    def count(self, name: str, n: int = 1) -> None:
        pass


NULL = _NullTracer()
