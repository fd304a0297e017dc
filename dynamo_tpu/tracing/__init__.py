"""Distributed request tracing: end-to-end span propagation with
per-request TTFT decomposition.

A request crosses frontend -> router -> prefill queue -> decode worker
-> offload tier; this package gives it one trace id at ingress
(W3C-traceparent compatible, client-supplied ``traceparent`` honored),
carries it across every hop (contextvars in-process, the bus
RequestEnvelope / disagg handoff / TCP prologue across processes),
records spans in a near-zero-cost ring buffer, and assembles them into
per-request timelines with a canonical TTFT decomposition
(tokenize / route / queue wait / KV-transfer exposed-vs-hidden /
prefill / first decode). The engine's scheduler loop has its own
clock (``loop_clock``): per-step phase accounting, always on. See
docs/tracing.md.
"""

from .context import (
    TRACEPARENT_HEADER,
    TraceContext,
    current_trace,
    current_traceparent,
    extract,
    inject,
    reset_trace,
    set_trace,
    use_trace,
)
from .collector import (
    TRACE_EVENTS_SUBJECT,
    BusExporter,
    TraceCollector,
    chrome_trace,
)
from .loop_clock import STEP_SPAN
from .span import (
    NULL_SPAN,
    RECORDER,
    configure,
    enabled,
    event,
    span,
)
from .ttft import COMPONENTS

__all__ = [
    "BusExporter",
    "COMPONENTS",
    "NULL_SPAN",
    "RECORDER",
    "STEP_SPAN",
    "TRACEPARENT_HEADER",
    "TRACE_EVENTS_SUBJECT",
    "TraceCollector",
    "TraceContext",
    "chrome_trace",
    "configure",
    "current_trace",
    "current_traceparent",
    "enabled",
    "event",
    "extract",
    "inject",
    "reset_trace",
    "set_trace",
    "span",
    "use_trace",
]
