"""Host spans on the profiler's clock: ``trace_span("estimator.solve")``.

Every span is a ``jax.profiler.TraceAnnotation``, entered and left with
the span, whether or not obs is enabled.  With no profiler session that is
one cheap enter/exit in C++; inside ``jax.profiler.start_trace`` /
``stop_trace`` the span lands on the host plane of the trace, on the same
timeline as the device ops, with its keyword metadata (``solve_id=...``)
as the event's stats.

While obs is enabled a span is also recorded: its name, start and end in
nanoseconds of ``time.time_ns()`` (the wall clock, which the profiler's
host events are timed on too), its parent span and its metadata.  Spans
nest per thread; a completed ROOT span (no open parent on this thread) is
appended to a bounded ring, readable via :func:`span_trees`, and every
span records its duration into the histogram ``span.<name>``.

``jax`` is imported once, on the first span, so that importing this
module never imports it.

Instrument OUTSIDE ``jit``: a span measures host time, so wrapping traced
code times tracing, not execution.  Inside traced code, name the stage
with ``jax.named_scope``, which lands on the device ops' ``op_name``.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import List, Optional

from . import metrics

_MAX_ROOTS = 64
_roots: "collections.deque" = collections.deque(maxlen=_MAX_ROOTS)
_roots_lock = threading.Lock()
_local = threading.local()
_annotation = None


def _annotation_cls():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class Span:
    """One recorded region: name, start and end (``time.time_ns()``),
    parent, metadata and child spans."""

    __slots__ = ("name", "metadata", "parent", "t0_ns", "t1_ns", "children")

    def __init__(self, name: str, metadata: dict,
                 parent: Optional["Span"]) -> None:
        self.name = name
        self.metadata = metadata
        self.parent = parent
        self.t0_ns = time.time_ns()
        self.t1_ns = self.t0_ns
        self.children: List["Span"] = []

    @property
    def dur_s(self) -> float:
        return (self.t1_ns - self.t0_ns) * 1e-9

    def as_dict(self) -> dict:
        d = {"name": self.name, "t0_ns": self.t0_ns, "t1_ns": self.t1_ns,
             "dur_s": self.dur_s}
        if self.metadata:
            d["metadata"] = dict(self.metadata)
        if self.children:
            d["children"] = [c.as_dict() for c in self.children]
        return d


def trace_span(name: str, **metadata):
    """``with trace_span(name, **metadata):`` -- a profiler annotation
    around the body (``metadata`` values, ints, floats or strings, become
    its stats), recorded as a span under the current thread's open span
    while obs is enabled.  Disabled, the bare annotation is returned: one
    C++ enter/exit, no Python object of obs's own."""
    ann = _annotation_cls()(name, **metadata)
    if metrics.enabled():
        return _Recorded(ann, name, metadata)
    return ann


class _Recorded:
    __slots__ = ("_ann", "_name", "_metadata", "_span")

    def __init__(self, ann, name: str, metadata: dict) -> None:
        self._ann, self._name, self._metadata = ann, name, metadata

    def __enter__(self) -> Span:
        self._ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self._span = span = Span(self._name, self._metadata,
                                 stack[-1] if stack else None)
        stack.append(span)
        return span

    def __exit__(self, *exc) -> None:
        span = self._span
        span.t1_ns = time.time_ns()
        stack = _local.stack
        if stack and stack[-1] is span:
            stack.pop()
        if span.parent is not None:
            span.parent.children.append(span)
        else:
            with _roots_lock:
                _roots.append(span)
        metrics.record(f"span.{span.name}", span.dur_s)
        self._ann.__exit__(*exc)


def span_trees() -> List[dict]:
    """The most recent completed root spans (oldest first) as nested
    ``{"name", "t0_ns", "t1_ns", "dur_s", "metadata", "children"}``
    dicts (``metadata`` and ``children`` only where present)."""
    with _roots_lock:
        return [s.as_dict() for s in _roots]


def reset() -> None:
    with _roots_lock:
        _roots.clear()
