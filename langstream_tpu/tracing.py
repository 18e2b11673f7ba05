"""Lightweight distributed tracing.

The reference has NO tracing (SURVEY §5: "no OpenTelemetry; observability =
prometheus + logs"); this is one of the rebuild's additions. Spans are
in-process (contextvars parent propagation, ring-buffered), exported over
the runtime HTTP server (``/traces``) in a jaeger-ish JSON shape, and
propagated ACROSS agents through a record header (``ls-trace-id``) so a
record's path through a pipeline stitches into one trace.

The device's side is `jax.profiler`'s own: the serving engine wraps its
phases and dispatches in `jax.profiler.TraceAnnotation`s, so a profile of
a replica holds them beside the device's lines (docs/SERVING.md §12).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Iterator, Optional

TRACE_HEADER = "ls-trace-id"

# The process's ONE monotonic → wall offset, taken once: every span's `start`
# is a monotonic stamp plus this, so two spans' starts differ by exactly
# their monotonic stamps, and a profile that holds a launch annotation's
# `t_mono_ns` (docs/SERVING.md §12) puts spans and device lines on one clock.
MONO_TO_WALL_S = time.time() - time.monotonic()

_current_span: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "ls_current_span", default=None
)


@dataclass
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: Optional[str]
    start_s: float
    duration_s: float = 0.0
    attributes: dict[str, Any] = field(default_factory=dict)
    status: str = "ok"

    def to_dict(self) -> dict[str, Any]:
        return {
            "name": self.name,
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "start": self.start_s,
            "durationMs": round(self.duration_s * 1000.0, 3),
            "attributes": self.attributes,
            "status": self.status,
        }


class Tracer:
    """Per-process tracer; finished spans land in a bounded ring buffer."""

    def __init__(self, capacity: int = 2048) -> None:
        self._finished: deque[Span] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.enabled = True

    @contextlib.contextmanager
    def span(
        self,
        name: str,
        trace_id: Optional[str] = None,
        **attributes: Any,
    ) -> Iterator[Span]:
        if not self.enabled:
            yield Span(name, "", "", None, 0.0)
            return
        parent = _current_span.get()
        started = time.monotonic()
        span = Span(
            name=name,
            trace_id=trace_id
            or (parent.trace_id if parent is not None else uuid.uuid4().hex[:16]),
            span_id=uuid.uuid4().hex[:16],
            parent_id=parent.span_id if parent is not None else None,
            start_s=started + MONO_TO_WALL_S,
            attributes=dict(attributes),
        )
        token = _current_span.set(span)
        try:
            yield span
        except BaseException as e:
            span.status = f"error: {type(e).__name__}"
            raise
        finally:
            span.duration_s = time.monotonic() - started
            _current_span.reset(token)
            with self._lock:
                self._finished.append(span)

    def emit(self, span: Span) -> None:
        """Append an already-finished span built by hand — the serving
        engine's request-lifecycle spans are assembled from phase
        timestamps at request completion (one emission point, nothing on
        the token hot loop) rather than held open across engine-thread
        iterations, so the context-manager form cannot carry them."""
        if not self.enabled:
            return
        with self._lock:
            self._finished.append(span)

    def current_trace_id(self) -> Optional[str]:
        span = _current_span.get()
        return span.trace_id if span is not None else None

    def find(self, name: str, trace_id: Optional[str] = None) -> list[Span]:
        """Finished spans by name (and optionally trace) — tests/debugging."""
        with self._lock:
            items = list(self._finished)
        return [
            s
            for s in items
            if s.name == name and (trace_id is None or s.trace_id == trace_id)
        ]

    def spans(self, limit: int = 500) -> list[dict[str, Any]]:
        with self._lock:
            items = list(self._finished)[-limit:]
        return [s.to_dict() for s in items]

    def clear(self) -> None:
        with self._lock:
            self._finished.clear()


TRACER = Tracer()


def record_trace_id(record: Any) -> Optional[str]:
    """Extract the propagated trace id from a record's headers."""
    headers = getattr(record, "headers", ())
    for h in headers:
        if h.key == TRACE_HEADER:
            return h.value_as_string()
    return None
