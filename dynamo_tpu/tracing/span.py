"""In-process span recorder: monotonic clocks, ring buffer, near-zero
cost when disabled.

Every instrumentation point in the stack calls the module-level
:func:`span` / :func:`event` helpers. When tracing is off (the default)
those return a shared no-op context manager after ONE attribute check —
no allocation, no clock read — so the decode loop pays nothing for the
instrumentation being present.

When enabled, finished spans land in a bounded ring buffer and are
optionally handed to a *sink* (the bus exporter in worker processes, the
collector directly in single-process setups). Durations come from
``time.perf_counter`` (monotonic, high-resolution); the wall-clock
``ts`` anchors spans from different processes onto one timeline.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Optional

logger = logging.getLogger(__name__)

from .context import TraceContext, current_trace

# span dict keys (the wire/shape contract, see docs/tracing.md):
#   name, trace_id, span_id, parent_id, service, ts (wall s), dur_ms, attrs


class _SpanHandle:
    """One open span; ``__exit__`` / ``end()`` records it."""

    __slots__ = ("recorder", "name", "trace", "attrs", "ts", "_t0", "_done")

    def __init__(self, recorder: "SpanRecorder", name: str, trace: TraceContext, attrs: dict):
        self.recorder = recorder
        self.name = name
        self.trace = trace
        self.attrs = attrs
        self.ts = time.time()
        self._t0 = time.perf_counter()
        self._done = False

    def set(self, **attrs: Any) -> "_SpanHandle":
        self.attrs.update(attrs)
        return self

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        self.recorder._record(
            self.name, self.trace, self.ts,
            (time.perf_counter() - self._t0) * 1e3, self.attrs,
        )

    def __enter__(self) -> "_SpanHandle":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.end()
        return False


class _NullSpan:
    """Shared do-nothing span for the disabled path."""

    __slots__ = ()

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def end(self) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *args) -> bool:
        return False


NULL_SPAN = _NullSpan()


class SpanRecorder:
    """Process-wide recorder. ``enabled`` gates everything."""

    def __init__(self, maxlen: int = 4096):
        self.enabled = False
        self.service = "proc"
        self._ring: deque = deque(maxlen=maxlen)
        self._sink: Optional[Callable[[dict], None]] = None
        self._lock = threading.Lock()  # spans land from executor threads too

    def configure(
        self,
        enabled: bool = True,
        service: Optional[str] = None,
        sink: Optional[Callable[[dict], None]] = None,
        maxlen: Optional[int] = None,
    ) -> "SpanRecorder":
        self.enabled = enabled
        if service is not None:
            self.service = service
        if sink is not None or not enabled:
            self._sink = sink
        if maxlen is not None:
            with self._lock:
                self._ring = deque(self._ring, maxlen=maxlen)
        return self

    # ---- recording ----
    def span(self, name: str, trace: Optional[TraceContext] = None, **attrs: Any):
        """Open a span (context manager or ``.end()`` by hand). Records
        only when enabled AND a trace is in scope — spans are always
        request-scoped."""
        if not self.enabled:
            return NULL_SPAN
        tc = trace or current_trace()
        if tc is None:
            return NULL_SPAN
        return _SpanHandle(self, name, tc.child(), attrs)

    def event(self, name: str, trace: Optional[TraceContext] = None, **attrs: Any) -> None:
        """Instant (zero-duration) span."""
        if not self.enabled:
            return
        tc = trace or current_trace()
        if tc is None:
            return
        self._record(name, tc.child(), time.time(), 0.0, attrs)

    def record_span(
        self,
        name: str,
        trace: TraceContext,
        ts: float,
        dur_ms: float,
        to_sink: bool = True,
        **attrs: Any,
    ) -> None:
        """Record a span whose start/duration were measured elsewhere
        (e.g. queue wait reconstructed at admission time).
        ``to_sink=False`` keeps it in the ring only: a span of a trace
        that never ends (the engine loop's) must not reach a collector,
        which keeps every span of a trace."""
        if not self.enabled:
            return
        self._record(name, trace.child(), ts, dur_ms, attrs, to_sink)

    def _record(self, name, trace: TraceContext, ts, dur_ms, attrs,
                to_sink: bool = True) -> None:
        rec = {
            "name": name,
            "trace_id": trace.trace_id,
            "span_id": trace.span_id,
            "parent_id": trace.parent_id,
            "service": self.service,
            "ts": ts,
            "dur_ms": round(dur_ms, 3),
            "attrs": attrs,
        }
        with self._lock:
            self._ring.append(rec)
        sink = self._sink if to_sink else None
        if sink is not None:
            try:
                sink(rec)
            except Exception:  # noqa: BLE001 — tracing must never fail a request
                logger.debug("span sink failed", exc_info=True)

    # ---- inspection ----
    def spans(self, trace_id: Optional[str] = None,
              name: Optional[str] = None) -> list[dict]:
        with self._lock:
            out = list(self._ring)
        if trace_id is not None:
            out = [s for s in out if s["trace_id"] == trace_id]
        if name is not None:
            out = [s for s in out if s["name"] == name]
        return out

    def drain(self) -> list[dict]:
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


#: the process-wide recorder every instrumentation point uses
RECORDER = SpanRecorder()


def configure(**kwargs: Any) -> SpanRecorder:
    return RECORDER.configure(**kwargs)


def enabled() -> bool:
    return RECORDER.enabled


def span(name: str, trace: Optional[TraceContext] = None, **attrs: Any):
    return RECORDER.span(name, trace, **attrs)


def event(name: str, trace: Optional[TraceContext] = None, **attrs: Any) -> None:
    RECORDER.event(name, trace, **attrs)
