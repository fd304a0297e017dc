"""OpenAI-compatible HTTP frontend.

Re-design of the reference's axum HTTP service (lib/llm/src/http/service/
{service_v2,openai}.rs): routes /v1/chat/completions, /v1/completions,
/v1/models, /metrics, /health. The service always streams from the engine
and folds for non-streaming clients (ref http/service.rs:24-26); client
disconnects kill the request context so TPU work is cancelled end-to-end
(ref openai.rs client-disconnect handling).

The server is a dependency-free asyncio HTTP/1.1 implementation — the
Python-idiomatic equivalent of the reference's axum layer, with SSE
streaming via chunked transfer encoding.
"""

from __future__ import annotations

import asyncio
import json
import logging
from typing import Optional

from .. import tracing
from ..protocols.aggregator import aggregate_chat_chunks, aggregate_completion_chunks
from ..protocols.openai import ChatCompletionRequest, CompletionRequest, RequestError
from ..protocols.sse import encode_comment, encode_data, encode_done, encode_event
from ..runtime.annotated import Annotated
from ..runtime.engine import AsyncEngine, AsyncEngineContext, Context
from .base import HttpError, HttpServerBase, _STATUS_TEXT  # noqa: F401 — HttpError re-exported
from .metrics import DEFAULT_SLO_CLASS, Metrics

logger = logging.getLogger(__name__)


def _chunk_has_tokens(data) -> bool:
    """True when an SSE chunk carries generated content — finish-only and
    usage-only chunks must not pollute the TTFT/ITL histograms."""
    if not isinstance(data, dict):
        return True  # raw engine items (tests/custom engines) count
    choices = data.get("choices") or []
    for c in choices:
        delta = c.get("delta") or {}
        if delta.get("content") or c.get("text"):
            return True
        msg = c.get("message") or {}
        if msg.get("content"):
            return True
    return False


class ModelManager:
    """Live model registry (ref http/service.rs:58 ModelManager): model name
    -> engine, hot add/remove as workers come and go."""

    def __init__(self):
        self._chat: dict[str, AsyncEngine] = {}
        self._completion: dict[str, AsyncEngine] = {}

    def add_chat_model(self, name: str, engine: AsyncEngine) -> None:
        self._chat[name] = engine

    def add_completion_model(self, name: str, engine: AsyncEngine) -> None:
        self._completion[name] = engine

    def remove_chat_model(self, name: str) -> None:
        self._chat.pop(name, None)

    def remove_completion_model(self, name: str) -> None:
        self._completion.pop(name, None)

    def chat_engine(self, name: str) -> Optional[AsyncEngine]:
        return self._chat.get(name)

    def completion_engine(self, name: str) -> Optional[AsyncEngine]:
        return self._completion.get(name)

    def model_names(self) -> list[str]:
        return sorted(set(self._chat) | set(self._completion))


class HttpService(HttpServerBase):
    """ref service_v2.rs:24 HttpService + builder."""

    def __init__(
        self,
        model_manager: Optional[ModelManager] = None,
        host: str = "0.0.0.0",
        port: int = 8080,
        metrics: Optional[Metrics] = None,
        trace_collector=None,
        admission=None,
        flight=None,
        profiler=None,
    ):
        super().__init__(host=host, port=port)
        self.models = model_manager or ModelManager()
        self.metrics = metrics or Metrics()
        # tracing.TraceCollector serving /trace/{request_id} (None = off)
        self.tracing = trace_collector
        # observability.FlightRecorder (None = off): every finished
        # request is recorded; SLO breaches / error finishes persist an
        # autopsy served at /autopsy/{request_id}
        self.flight = None
        if flight is not None:
            self.attach_flight(flight)
        # async callable (seconds[, out dir] -> trace dir) running jax.profiler on
        # the serving engine; wired by dynamo_run when the engine is
        # in-process (None = POST /profile answers 501)
        self.profiler = profiler
        # planner.AdmissionGate overload control (None = admit all):
        # shed requests get 429 + Retry-After BEFORE touching the
        # engine, so admitted requests keep their SLO under overload
        self.admission = admission
        if admission is not None:
            self.metrics.register_source(admission.render_stats)
        # client-supplied request ids currently in flight: a duplicate
        # would key cross-request shared state (worker inflight map,
        # disagg transfer futures) onto one id — the second request
        # falls back to a minted uuid instead
        self._inflight_ids: set[str] = set()

    def attach_flight(self, flight) -> None:
        """Wire a FlightRecorder to this service: its counters join the
        /metrics exposition, and breach counting drives
        ``slo_breaches_total`` so the counter and the autopsy inventory
        can never drift apart."""
        self.flight = flight
        self.metrics.register_source(flight.counters)
        if flight.on_breach is None:
            flight.on_breach = self.metrics.observe_breach

    # ---------------- routing ----------------

    async def _route(self, method, path, headers, body, writer) -> None:
        path, _, query = path.partition("?")
        if method == "GET":
            if path in ("/health", "/live", "/ready"):
                await self._send_json(writer, 200, {"status": "ok"})
            elif path == "/metrics":
                await self._send_response(
                    writer, 200, self.metrics.render().encode(),
                    content_type="text/plain; version=0.0.4",
                )
            elif path == "/v1/models":
                data = [
                    {"id": name, "object": "model", "owned_by": "dynamo_tpu"}
                    for name in self.models.model_names()
                ]
                await self._send_json(writer, 200, {"object": "list", "data": data})
            elif path.startswith("/trace/") or path == "/trace":
                await self._trace_endpoint(writer, path, query)
            elif path.startswith("/autopsy/") or path == "/autopsy":
                await self._autopsy_endpoint(writer, path)
            else:
                raise HttpError(404, f"no route for GET {path}", "not_found")
        elif method == "POST":
            if path == "/v1/chat/completions":
                await self._openai_endpoint(writer, headers, body, chat=True)
            elif path == "/v1/completions":
                await self._openai_endpoint(writer, headers, body, chat=False)
            elif path == "/profile":
                await self._profile_endpoint(writer, query)
            else:
                raise HttpError(404, f"no route for POST {path}", "not_found")
        else:
            raise HttpError(405, f"method {method} not allowed")

    # ---------------- tracing endpoint ----------------

    async def _trace_endpoint(self, writer, path: str, query: str) -> None:
        """``GET /trace/{request_id}[?format=chrome]`` — the assembled
        per-request timeline + TTFT decomposition (or Chrome trace-event
        JSON); ``GET /trace/engine`` the in-process engine's
        ``engine.step`` spans; ``GET /trace`` lists collected trace ids
        + aggregate percentiles."""
        if self.tracing is None:
            raise HttpError(404, "tracing is not enabled", "tracing_disabled")
        if path in ("/trace", "/trace/"):
            await self._send_json(writer, 200, {
                "traces": self.tracing.trace_ids(),
                "ttft_percentiles_ms": self.tracing.percentiles(),
            })
            return
        trace_id = path[len("/trace/"):]
        fmt = "chrome" if "format=chrome" in query else "timeline"
        if trace_id == "engine":
            # the scheduler loop's steps live in the recorder's ring,
            # not in the collector: the loop is one endless trace
            spans = tracing.RECORDER.spans(name=tracing.STEP_SPAN)
            await self._send_json(
                writer, 200, tracing.chrome_trace(spans)
                if fmt == "chrome" else {"spans": spans})
            return
        body = self.tracing.render_trace(trace_id, fmt=fmt)
        if body is None:
            raise HttpError(404, f"no trace for {trace_id!r}", "trace_not_found")
        if fmt == "timeline":
            body = {"request_id": trace_id, **body}
        await self._send_json(writer, 200, body)

    # ---------------- flight recorder + profiler ----------------

    async def _autopsy_endpoint(self, writer, path: str) -> None:
        """``GET /autopsy/{request_id}`` — the persisted slow-request
        autopsy (timeline + decomposition + engine/sanitizer/compile
        snapshots); ``GET /autopsy`` lists autopsied request ids."""
        if self.flight is None:
            raise HttpError(
                404, "flight recorder is not enabled", "flight_disabled"
            )
        if path in ("/autopsy", "/autopsy/"):
            await self._send_json(writer, 200, {
                "autopsies": self.flight.autopsy_ids(),
                "records_total": self.flight.recorded_total,
                "autopsies_total": self.flight.autopsies_total,
            })
            return
        rid = path[len("/autopsy/"):]
        body = self.flight.autopsy(rid)
        if body is None:
            raise HttpError(404, f"no autopsy for {rid!r}", "autopsy_not_found")
        await self._send_json(writer, 200, body)

    async def _profile_endpoint(self, writer, query: str) -> None:
        """``POST /profile?seconds=N[&dir=<path>]`` — run ``jax.profiler``
        on the in-process engine for N seconds, into ``dir`` when given
        (created if missing; else a fresh temporary directory), and
        return the trace path."""
        if self.profiler is None:
            raise HttpError(
                501, "profiler is not wired on this frontend "
                "(in-process engine required)", "profiler_unavailable",
            )
        import math
        from urllib.parse import unquote

        seconds, out_dir = 2.0, None
        for part in query.split("&"):
            k, _, v = part.partition("=")
            if k == "dir" and v:
                out_dir = unquote(v)
            if k == "seconds" and v:
                try:
                    seconds = float(v)
                except ValueError:
                    raise HttpError(400, f"bad seconds={v!r}") from None
                if not math.isfinite(seconds):
                    # nan slides through min/max clamps (every NaN
                    # comparison is False) straight into time.sleep
                    raise HttpError(400, f"bad seconds={v!r}")
        seconds = min(max(seconds, 0.1), 120.0)
        try:
            trace_dir = await self.profiler(
                *((seconds,) if out_dir is None else (seconds, out_dir)))
        except Exception as e:  # noqa: BLE001 — surface, don't 500-loop
            raise HttpError(
                500, f"profiler failed: {type(e).__name__}: {e}",
                "profiler_error",
            ) from None
        await self._send_json(
            writer, 200, {"trace_dir": trace_dir, "seconds": seconds}
        )

    # ---------------- openai endpoints (ref openai.rs:132,214) ----------------

    @staticmethod
    def _client_request_id(headers: dict) -> Optional[str]:
        """Honor a client-supplied ``X-Request-Id`` (so client logs
        correlate with traces) — sanitized: printable, bounded, no
        whitespace. Anything unusable falls back to a minted uuid."""
        rid = (headers.get("x-request-id") or "").strip()
        if 0 < len(rid) <= 128 and all(33 <= ord(c) <= 126 for c in rid):
            return rid
        return None

    async def _openai_endpoint(self, writer, headers: dict, body: bytes, chat: bool) -> None:
        endpoint = "chat_completions" if chat else "completions"
        try:
            payload = json.loads(body or b"{}")
        except json.JSONDecodeError as e:
            raise HttpError(400, f"invalid JSON body: {e}") from None
        try:
            req = (
                ChatCompletionRequest.from_dict(payload)
                if chat
                else CompletionRequest.from_dict(payload)
            )
        except RequestError as e:
            raise HttpError(400, str(e)) from None

        engine = (
            self.models.chat_engine(req.model)
            if chat
            else self.models.completion_engine(req.model)
        )
        if engine is None:
            raise HttpError(
                404, f"model {req.model!r} not found", "model_not_found"
            )

        slo_class: Optional[str] = None
        if self.admission is not None:
            # overload gate: classify by nvext annotation (["slo:batch"])
            # — falling back to the model's configured SLO pool
            # (AdmissionGate.model_classes) — and admit/shed before any
            # engine work is queued
            slo_class = self.admission.classify(
                getattr(getattr(req, "nvext", None), "annotations", None),
                model=req.model,
            )
            decision = self.admission.admit(slo_class)
            if not decision.admitted:
                self.metrics.requests_total[
                    (req.model, endpoint, "shed")
                ] += 1
                tracing.event(
                    "frontend.shed", slo_class=slo_class,
                    reason=decision.reason,
                )
                raise HttpError(
                    429,
                    f"overloaded ({decision.reason}); retry after "
                    f"{decision.retry_after_s:.0f}s",
                    "overloaded",
                    retry_after_s=decision.retry_after_s,
                )

        guard = self.metrics.inflight_guard(
            req.model, endpoint, slo_class or DEFAULT_SLO_CLASS
        )
        client_rid = self._client_request_id(headers)
        if client_rid is not None:
            if client_rid in self._inflight_ids:
                logger.warning(
                    "duplicate in-flight X-Request-Id %r; minting fresh id",
                    client_rid,
                )
                client_rid = None
            else:
                self._inflight_ids.add(client_rid)
        context = Context(req, AsyncEngineContext(client_rid))
        if slo_class is not None:
            # downstream planes (router, engine queues, traces) see the
            # request's SLO class
            context.annotations["slo_class"] = slo_class
        req_span = tracing.NULL_SPAN
        trace_token = None
        if tracing.enabled():
            # root the request's trace here (honoring an incoming
            # traceparent); the contextvar scopes this handler task, so
            # the preprocessor/router/client-egress spans all join it
            tc = tracing.TraceContext.for_request(
                context.id, headers.get(tracing.TRACEPARENT_HEADER)
            )
            trace_token = tracing.set_trace(tc)
            req_span = tracing.span(
                "frontend.request", request_id=context.id,
                model=req.model, endpoint=endpoint,
            )
        try:
            stream = engine.generate(context)
            if req.stream:
                await self._stream_sse(writer, stream, context, req, guard)
            else:
                chunks: list[dict] = []
                error: Optional[str] = None
                first_token = True
                async for item in stream:
                    ann = item if isinstance(item, Annotated) else Annotated.from_data(item)
                    if ann.is_error():
                        error = ann.error or "engine error"
                        break
                    if ann.data is not None:
                        # the engine streams internally even for folded
                        # responses — TTFT/ITL are still real
                        if _chunk_has_tokens(ann.data):
                            guard.observe_token()
                            if first_token:
                                first_token = False
                                tracing.event(
                                    "frontend.first_token",
                                    request_id=context.id,
                                )
                        chunks.append(ann.data)
                if error is not None:
                    guard.mark("error")
                    raise HttpError(500, error, "engine_error")
                if not chunks:
                    guard.mark("error")
                    raise HttpError(500, "engine produced no output", "engine_error")
                full = (
                    aggregate_chat_chunks(chunks)
                    if chat
                    else aggregate_completion_chunks(chunks)
                )
                self._count_tokens(req.model, full)
                guard.mark_ok()
                await self._send_json(writer, 200, full)
        finally:
            elapsed_ms = guard.elapsed_ms
            guard.done()
            # close the request span BEFORE the flight recorder judges
            # the finish: the decomposition needs the frontend.request
            # anchor in the collector, or a breach autopsy would carry
            # a timeline that can't decompose
            req_span.end()
            if self.flight is not None:
                # per-worker attribution: the KV router stamps the pinned
                # instance on the shared annotations dict (autopilot
                # quarantine evidence) — absent on round-robin fallbacks
                rw = context.annotations.get("routed_worker_id")
                self.flight.finish(
                    context.id, req.model, guard.slo_class, guard.status,
                    guard.ttft_ms, elapsed_ms,
                    worker_id=rw if isinstance(rw, int) else None,
                )
            if slo_class is not None:
                self.admission.done(slo_class)
            if client_rid is not None:
                self._inflight_ids.discard(client_rid)
            if trace_token is not None:
                tracing.reset_trace(trace_token)

    def _count_tokens(self, model: str, full: dict) -> None:
        usage = full.get("usage") or {}
        if usage.get("prompt_tokens"):
            self.metrics.observe_tokens(model, "prompt", usage["prompt_tokens"])
        if usage.get("completion_tokens"):
            self.metrics.observe_tokens(model, "completion", usage["completion_tokens"])

    async def _stream_sse(self, writer, stream, context: Context, req, guard) -> None:
        head = (
            "HTTP/1.1 200 OK\r\n"
            "Content-Type: text/event-stream\r\n"
            "Cache-Control: no-cache\r\n"
            "Transfer-Encoding: chunked\r\n"
            "\r\n"
        )
        writer.write(head.encode())
        await writer.drain()

        async def send(chunk: bytes):
            writer.write(f"{len(chunk):x}\r\n".encode() + chunk + b"\r\n")
            await writer.drain()

        include_usage = bool(getattr(req, "stream_options", {}).get("include_usage"))
        ok = True
        first_token = True
        try:
            try:
                async for item in stream:
                    ann = item if isinstance(item, Annotated) else Annotated.from_data(item)
                    if ann.is_error():
                        await send(encode_event("error", {"message": ann.error}))
                        ok = False
                        break
                    if ann.event and ann.event != "sentinel":
                        await send(encode_event(ann.event,
                            json.loads(ann.comment[0]) if ann.comment else None))
                        continue
                    if ann.data is not None:
                        data = ann.data
                        if isinstance(data, dict) and data.get("usage") is not None:
                            self._count_tokens(req.model, data)
                            if not include_usage:
                                data = {k: v for k, v in data.items() if k != "usage"}
                        if _chunk_has_tokens(data):
                            guard.observe_token()  # TTFT / ITL histograms
                            if first_token:
                                first_token = False
                                tracing.event(
                                    "frontend.first_token",
                                    request_id=context.id,
                                )
                        await send(encode_data(data))
            except (ConnectionResetError, BrokenPipeError):
                raise
            except Exception as e:  # noqa: BLE001
                # engine failure mid-stream: the 200 + SSE head is already on
                # the wire, so surface it as an SSE error event, never as a
                # second HTTP response on the same socket
                logger.exception("engine error mid-stream")
                await send(encode_event("error", {"message": str(e)}))
                ok = False
            await send(encode_done())
        except (ConnectionResetError, BrokenPipeError):
            # client went away: kill generation end-to-end (ref openai.rs)
            context.context.kill()
            guard.mark("disconnect")
            return
        # end chunked body
        try:
            writer.write(b"0\r\n\r\n")
            await writer.drain()
        except (ConnectionResetError, BrokenPipeError):
            context.context.kill()
        if ok:
            guard.mark_ok()
