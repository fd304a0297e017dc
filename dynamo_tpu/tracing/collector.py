"""Trace collector: subscribe ``trace-events``, assemble per-request
timelines, export Chrome-trace JSON + TTFT decompositions.

Workers and frontends export finished spans onto the bus (one
``trace-events`` subject per component, :class:`BusExporter`); the
collector subscribes — with a wildcard when it isn't pinned to one
component — and keeps a bounded LRU of assembled traces. Lookups accept
either a trace id or a request id (spans carry ``request_id`` as an
attribute wherever the ingress knew it).

Exports:
  * ``timeline(id)``        — spans sorted by wall-clock start,
  * ``ttft(id)``            — the canonical decomposition (tracing.ttft),
  * ``chrome_trace(id)``    — Chrome trace-event JSON (load it in
    ``chrome://tracing`` / Perfetto),
  * ``percentiles()``       — aggregate p50/p95/p99 per TTFT component,
    the feed for the metrics plane and bench artifacts.
"""

from __future__ import annotations

import asyncio
import json
import logging
import threading
from collections import OrderedDict, deque
from typing import Optional

from . import ttft as ttft_mod

logger = logging.getLogger(__name__)

TRACE_EVENTS_SUBJECT = "trace-events"
#: subscribe-all pattern for collectors not pinned to one component
TRACE_EVENTS_WILDCARD = "*.*." + TRACE_EVENTS_SUBJECT


def percentile(values: list[float], p: float) -> float:
    """Nearest-rank percentile (p in [0,100]) on a small sample."""
    if not values:
        return 0.0
    xs = sorted(values)
    k = min(len(xs) - 1, max(0, int(round(p / 100.0 * (len(xs) - 1)))))
    return xs[k]


def chrome_trace(spans: list[dict]) -> dict:
    """Chrome trace-event JSON: complete ("X") events per span,
    instant ("i") events for zero-duration spans, one pid per
    service so frontend/router/worker/prefill rows separate."""
    events = []
    for s in spans:
        ev = {
            "name": s["name"],
            "cat": s.get("service", "proc"),
            "ts": s["ts"] * 1e6,  # wall seconds -> microseconds
            "pid": s.get("service", "proc"),
            "tid": s["trace_id"][:8],
            "args": dict(s.get("attrs") or {}),
        }
        if s["dur_ms"] > 0:
            ev["ph"] = "X"
            ev["dur"] = s["dur_ms"] * 1e3
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        events.append(ev)
    return {"traceEvents": events, "displayTimeUnit": "ms"}


class TraceCollector:
    """Assembles spans into per-request timelines. Works standalone
    (feed :meth:`ingest` directly, e.g. as a recorder sink) or
    subscribed to a distributed runtime's bus via :meth:`start`."""

    def __init__(self, drt=None, component=None, max_traces: int = 1024,
                 max_samples: int = 2048):
        self.drt = drt
        self.component = component
        self.max_traces = max_traces
        self._traces: OrderedDict[str, list[dict]] = OrderedDict()
        self._aliases: OrderedDict[str, str] = OrderedDict()  # request_id -> trace_id
        # aggregate TTFT component samples (ms), bounded
        self._samples: dict[str, deque] = {}
        self._max_samples = max_samples
        self._decomposed: set[str] = set()
        self._lock = threading.Lock()
        self._sub = None
        self._task = None
        self.spans_total = 0

    # ---- bus plumbing ----
    @property
    def subject(self) -> str:
        if self.component is not None:
            return self.component.event_subject(TRACE_EVENTS_SUBJECT)
        return TRACE_EVENTS_WILDCARD

    async def start(self) -> "TraceCollector":
        assert self.drt is not None, "start() needs a DistributedRuntime"
        sub = self.drt.bus.subscribe(self.subject)
        ready = getattr(sub, "ready", None)
        if ready is not None:
            await ready
        self._sub = sub
        self._task = self.drt.runtime.spawn(self._consume(sub))
        return self

    async def close(self) -> None:
        if self._sub is not None:
            self._sub.unsubscribe()
            self._sub = None
        if self._task is not None:
            self._task.cancel()
            self._task = None

    async def _consume(self, sub) -> None:
        async for msg in sub:
            try:
                payload = json.loads(msg.payload)
                self.ingest(payload)
            except Exception:  # noqa: BLE001 — a bad batch must not kill the loop
                logger.exception("bad trace-events payload")

    # ---- ingestion ----
    def ingest(self, spans) -> None:
        """Accept one span dict or a batch list of them."""
        if isinstance(spans, dict):
            spans = [spans]
        with self._lock:
            for s in spans:
                tid = s.get("trace_id")
                if not tid:
                    continue
                bucket = self._traces.get(tid)
                if bucket is None:
                    bucket = self._traces[tid] = []
                    while len(self._traces) > self.max_traces:
                        old, _ = self._traces.popitem(last=False)
                        self._decomposed.discard(old)
                else:
                    self._traces.move_to_end(tid)
                    # dedupe by span id: a frontend collector subscribed
                    # to the wildcard also hears the frontend's OWN
                    # bus-exported batches — the same span must not
                    # enter the timeline (and the decomposition) twice
                    sid = s.get("span_id")
                    if sid is not None and any(
                        b.get("span_id") == sid for b in bucket
                    ):
                        continue
                bucket.append(s)
                self.spans_total += 1
                rid = (s.get("attrs") or {}).get("request_id")
                if rid:
                    self._aliases[rid] = tid
                    while len(self._aliases) > self.max_traces:
                        self._aliases.popitem(last=False)
            # fold finished timelines into the aggregate percentiles: a
            # trace is decomposable once BOTH anchors (request receipt +
            # first token) arrived — try on either anchor landing, since
            # the request span closes after the stream ends and batches
            # can deliver the two in any order
            for s in spans:
                tid = s.get("trace_id")
                if (
                    tid
                    and tid not in self._decomposed
                    and s.get("name") in (
                        ttft_mod.EVENT_FIRST_TOKEN,
                        ttft_mod.EVENT_ENGINE_FIRST_TOKEN,
                        ttft_mod.SPAN_REQUEST,
                    )
                ):
                    d = ttft_mod.decompose(self._traces.get(tid, []))
                    if d is not None:
                        self._decomposed.add(tid)
                        for k, v in d.items():
                            q = self._samples.get(k)
                            if q is None:
                                q = self._samples[k] = deque(
                                    maxlen=self._max_samples
                                )
                            q.append(v)

    # ---- lookup ----
    def resolve(self, id_: str) -> Optional[str]:
        with self._lock:
            if id_ in self._traces:
                return id_
            tid = self._aliases.get(id_)
            # an alias can outlive its LRU-evicted trace: answering with
            # the stale tid would fabricate an empty timeline downstream
            return tid if tid in self._traces else None

    def timeline(self, id_: str) -> Optional[list[dict]]:
        tid = self.resolve(id_)
        if tid is None:
            return None
        with self._lock:
            spans = list(self._traces.get(tid, []))
        return sorted(spans, key=lambda s: (s["ts"], -s["dur_ms"]))

    def ttft(self, id_: str) -> Optional[dict]:
        spans = self.timeline(id_)
        if spans is None:
            return None
        return ttft_mod.decompose(spans)

    def trace_ids(self) -> list[str]:
        with self._lock:
            return list(self._traces)

    # ---- exports ----
    def chrome_trace(self, id_: str) -> Optional[dict]:
        spans = self.timeline(id_)
        return None if spans is None else chrome_trace(spans)

    def render_trace(self, id_: str, fmt: str = "timeline") -> Optional[dict]:
        """The ``/trace/{id}`` response body."""
        tid = self.resolve(id_)
        if tid is None:
            return None
        if fmt == "chrome":
            return self.chrome_trace(tid)
        return {
            "trace_id": tid,
            "spans": self.timeline(tid),
            "ttft": self.ttft(tid),
        }

    # ---- aggregates ----
    def percentiles(self, ps=(50, 95, 99)) -> dict:
        """{component: {"p50": ms, ...}} across collected traces."""
        with self._lock:
            samples = {k: list(q) for k, q in self._samples.items()}
        return {
            k: {f"p{int(p)}": round(percentile(v, p), 3) for p in ps}
            for k, v in samples.items()
            if v
        }


class BusExporter:
    """Recorder sink publishing span batches onto the bus.

    Spans land from the event loop AND from executor threads (engine
    device work), so the sink buffers under a lock and flushes at most
    once per loop tick — one small publish per tick, never one per span.
    Best-effort: export failures are dropped, never surfaced to the
    request path."""

    def __init__(self, bus, subject: str, max_batch: int = 512):
        self.bus = bus
        self.subject = subject
        self.max_batch = max_batch
        self._buf: list[dict] = []
        self._lock = threading.Lock()
        self._flush_scheduled = False
        self._loop = asyncio.get_event_loop()

    def __call__(self, span: dict) -> None:
        with self._lock:
            self._buf.append(span)
            if len(self._buf) > self.max_batch:
                del self._buf[: -self.max_batch]
            if self._flush_scheduled:
                return
            self._flush_scheduled = True
        try:
            self._loop.call_soon_threadsafe(self._flush)
        except RuntimeError:  # loop closed: drop silently
            with self._lock:
                self._flush_scheduled = False

    def _flush(self) -> None:
        with self._lock:
            batch, self._buf = self._buf, []
            self._flush_scheduled = False
        if not batch:
            return
        try:
            # dynflow: publishes=TRACE_EVENTS_SUBJECT (constructor-injected
            # subject — dynamo_run wires component.event_subject of it)
            res = self.bus.publish(self.subject, json.dumps(batch).encode())
            if hasattr(res, "__await__"):  # remote hub bus
                task = self._loop.create_task(res)
                task.add_done_callback(lambda t: t.exception())
        except Exception:  # noqa: BLE001
            logger.debug("trace export failed", exc_info=True)
