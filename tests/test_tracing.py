"""Distributed request tracing: context propagation, span recording,
collector assembly, TTFT decomposition, codec forward-compat.

Covers the ISSUE-2 tentpole end to end at three scopes:
  * unit — traceparent wire form, recorder ring buffer, disabled-path
    cost model (no allocation, no spans),
  * in-process e2e — a tiny JaxEngine request traced frontend-style,
    decomposition summing to the measured TTFT within the 5% bound,
  * cross-process — the same trace id observed in frontend, router and
    worker spans through BOTH the mock transport and the real TCP
    response plane, plus the codec's unknown-header-key tolerance.
"""

import asyncio
import json

import pytest

from dynamo_tpu import tracing
from dynamo_tpu.runtime import (
    Annotated,
    AsyncEngine,
    Context,
    DistributedRuntime,
    LocalBus,
    LocalStore,
    RequestEnvelope,
    TwoPartMessage,
    collect,
)


@pytest.fixture(autouse=True)
def _reset_recorder():
    """Tracing state is process-global; every test starts dark, and
    leaves the ring as long as it found it (a ring left at 8 spans
    fails whichever file the worker runs next that counts spans)."""
    maxlen = tracing.RECORDER._ring.maxlen
    yield
    tracing.RECORDER.configure(enabled=False, sink=None, maxlen=maxlen)
    tracing.RECORDER.clear()


# ---------------- unit: context ----------------


def test_traceparent_roundtrip():
    tc = tracing.TraceContext.new()
    parsed = tracing.TraceContext.from_traceparent(tc.to_traceparent())
    assert parsed.trace_id == tc.trace_id
    assert parsed.span_id == tc.span_id
    assert parsed.sampled


def test_traceparent_rejects_malformed():
    for bad in (
        None, "", "junk", "00-short-id-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",  # reserved version
    ):
        assert tracing.TraceContext.from_traceparent(bad) is None


def test_for_request_honors_incoming_traceparent():
    theirs = tracing.TraceContext.new()
    tc = tracing.TraceContext.for_request("req-1", theirs.to_traceparent())
    assert tc.trace_id == theirs.trace_id  # caller's trace continues
    assert tc.parent_id == theirs.span_id  # as OUR parent span
    # no traceparent: a 32-hex request id IS the trace id
    rid = "ab" * 16
    assert tracing.TraceContext.for_request(rid).trace_id == rid
    # non-hex request ids mint a fresh trace id
    assert tracing.TraceContext.for_request("my-req").trace_id != "my-req"


def test_contextvar_and_annotation_carriers():
    tc = tracing.TraceContext.new()
    assert tracing.current_trace() is None
    with tracing.use_trace(tc):
        assert tracing.current_trace() is tc
        ann = tracing.inject({})
        assert tracing.extract(ann).trace_id == tc.trace_id
    assert tracing.current_trace() is None
    assert tracing.extract({}) is None
    assert tracing.inject(None) is None


# ---------------- unit: recorder ----------------


def test_disabled_recorder_records_nothing():
    assert not tracing.enabled()
    with tracing.use_trace(tracing.TraceContext.new()):
        # the disabled path returns the SHARED null span: no allocation
        assert tracing.span("x") is tracing.NULL_SPAN
        tracing.event("y")
    assert tracing.RECORDER.spans() == []


def test_spans_need_a_trace_in_scope():
    tracing.configure(enabled=True, service="t")
    assert tracing.span("x") is tracing.NULL_SPAN  # no trace -> no span
    tracing.event("y")
    assert tracing.RECORDER.spans() == []


def test_recorder_ring_and_thread_safety():
    tracing.configure(enabled=True, service="t", maxlen=8)
    tc = tracing.TraceContext.new()
    import threading

    def record_many():
        for i in range(50):
            with tracing.span(f"s{i}", trace=tc):
                pass

    threads = [threading.Thread(target=record_many) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tracing.RECORDER.spans()
    assert len(spans) == 8  # bounded
    assert all(s["trace_id"] == tc.trace_id for s in spans)


def test_span_parenting_and_error_attr():
    tracing.configure(enabled=True, service="t")
    tc = tracing.TraceContext.new()
    with tracing.use_trace(tc):
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("x")
    (s,) = tracing.RECORDER.spans()
    assert s["parent_id"] == tc.span_id
    assert s["attrs"]["error"] == "ValueError"


# ---------------- unit: collector + decomposition ----------------


def _mk_span(name, tc, ts, dur_ms, **attrs):
    return {
        "name": name, "trace_id": tc.trace_id, "span_id": "s" + name,
        "parent_id": None, "service": "t", "ts": ts, "dur_ms": dur_ms,
        "attrs": attrs,
    }


def test_collector_decomposition_sums_to_ttft():
    tc = tracing.TraceContext.new()
    col = tracing.TraceCollector()
    t0 = 1000.0
    col.ingest([
        _mk_span("frontend.request", tc, t0, 300.0, request_id="r1"),
        _mk_span("tokenize", tc, t0 + 0.001, 10.0),
        _mk_span("router.schedule", tc, t0 + 0.012, 5.0),
        _mk_span("engine.queue_wait", tc, t0 + 0.020, 40.0),
        # the restore wait nests INSIDE the prefill span (offload
        # preamble of the first chunk) — prefill's 120ms contains it
        _mk_span("engine.kv_restore", tc, t0 + 0.060, 20.0,
                 exposed_ms=20.0, hidden_ms=35.0),
        _mk_span("engine.prefill", tc, t0 + 0.060, 120.0),
        _mk_span("engine.first_token", tc, t0 + 0.200, 0.0),
        _mk_span("frontend.first_token", tc, t0 + 0.210, 0.0, request_id="r1"),
    ])
    d = col.ttft("r1")  # request-id alias resolves
    assert d["ttft_ms"] == pytest.approx(210.0, rel=1e-6)
    assert d["tokenize"] == 10.0
    assert d["route"] == 5.0
    assert d["queue_wait"] == 40.0
    assert d["kv_transfer_exposed"] == 20.0
    assert d["kv_transfer_hidden"] == 35.0
    # prefill is carved disjoint from the nested restore wait
    assert d["prefill"] == 100.0
    total = (d["tokenize"] + d["route"] + d["queue_wait"]
             + d["kv_transfer_exposed"] + d["prefill"] + d["first_decode"])
    assert total == pytest.approx(d["ttft_ms"], rel=0.05)
    # aggregate percentiles got fed
    assert col.percentiles()["ttft_ms"]["p50"] == pytest.approx(210.0)


def test_collector_dedupes_replayed_spans():
    """A frontend collector on the wildcard also hears its own
    bus-exported batches — the same span must ingest once."""
    col = tracing.TraceCollector()
    tc = tracing.TraceContext.new()
    s = _mk_span("tokenize", tc, 1.0, 2.0, request_id="d1")
    col.ingest(s)
    col.ingest([dict(s)])  # bus replay of the identical span
    assert len(col.timeline(tc.trace_id)) == 1
    assert col.spans_total == 1


def test_collector_stale_alias_resolves_to_none():
    """A request-id alias whose trace was LRU-evicted must read as
    not-found, never as a fabricated empty timeline."""
    col = tracing.TraceCollector(max_traces=1)
    tc = tracing.TraceContext.new()
    col.ingest(_mk_span("frontend.request", tc, 1.0, 5.0, request_id="old"))
    col.ingest(_mk_span("x", tracing.TraceContext.new(), 2.0, 1.0))  # evicts
    assert col.resolve("old") is None
    assert col.timeline("old") is None
    assert col.render_trace("old") is None


def test_collector_chrome_trace_and_lru():
    tc = tracing.TraceContext.new()
    col = tracing.TraceCollector(max_traces=2)
    col.ingest(_mk_span("frontend.request", tc, 1.0, 5.0, request_id="rq"))
    chrome = col.chrome_trace(tc.trace_id)
    (ev,) = chrome["traceEvents"]
    assert ev["ph"] == "X" and ev["dur"] == 5000.0 and ev["ts"] == 1e6
    # instant events render as ph=i
    col.ingest(_mk_span("frontend.first_token", tc, 1.005, 0.0))
    assert [e["ph"] for e in col.chrome_trace("rq")["traceEvents"]] == ["X", "i"]
    # LRU bound: two newer traces evict the first
    for _ in range(2):
        col.ingest(_mk_span("x", tracing.TraceContext.new(), 2.0, 1.0))
    assert col.timeline(tc.trace_id) is None


def test_disagg_remote_prefill_transfer_attribution():
    tc = tracing.TraceContext.new()
    col = tracing.TraceCollector()
    t0 = 50.0
    col.ingest([
        _mk_span("frontend.request", tc, t0, 500.0, request_id="rr"),
        _mk_span("disagg.remote_prefill", tc, t0 + 0.01, 300.0),
        _mk_span("prefill.queue_wait", tc, t0 + 0.01, 50.0),
        _mk_span("prefill.compute", tc, t0 + 0.06, 200.0),
        _mk_span("engine.first_token", tc, t0 + 0.4, 0.0),
    ])
    d = col.ttft(tc.trace_id)
    # decode-side wait minus worker-side spans = the transfer cost
    assert d["kv_transfer_exposed"] == pytest.approx(50.0)
    assert d["queue_wait"] == pytest.approx(50.0)
    assert d["prefill"] == pytest.approx(200.0)


# ---------------- codec forward-compat (satellite) ----------------


def test_codec_header_field_ignores_unknown_keys():
    msg = TwoPartMessage.from_json(
        {"type": "data", "traceparent": "00-aa-bb-01", "future_field": [1, 2]}
    )
    assert msg.header_field("type") == "data"
    assert msg.header_field("missing", "dflt") == "dflt"
    # malformed / non-object headers read as empty, not as an exception
    assert TwoPartMessage(header=b"not json").header_field("type") is None
    assert TwoPartMessage(header=b"[1,2]").header_field("type") is None
    assert TwoPartMessage().header_field("type", "x") == "x"


def test_tcp_response_plane_tolerates_unknown_header_keys(run):
    """Version-skew safety: a newer worker adds header keys (prologue
    traceparent, data-frame trace fields) — the caller-side stream
    server must decode the frames it knows and ignore the rest."""
    from dynamo_tpu.runtime.codec import write_frame
    from dynamo_tpu.runtime.engine import AsyncEngineContext
    from dynamo_tpu.runtime.tcp import TcpStreamServer

    async def main():
        server = TcpStreamServer(host="127.0.0.1")
        await server.start()
        info = server.register(AsyncEngineContext("req-x"))
        host, port = server.address.rsplit(":", 1)
        reader, writer = await asyncio.open_connection(host, int(port))
        # prologue with extra keys a current build doesn't know
        await write_frame(writer, TwoPartMessage.from_json({
            "type": "prologue", "stream_id": info.stream_id,
            "traceparent": "00-" + "a" * 32 + "-" + "b" * 16 + "-01",
            "compression": "zstd-someday",
        }))
        ack = None
        fut = server.stream(info)
        # data + sentinel frames also carrying unknown keys
        await write_frame(writer, TwoPartMessage(
            header=json.dumps({
                "type": "data", "trace": "t", "shard": 0, "v2_field": True,
            }).encode(),
            data=json.dumps({"data": {"token": "hi"}}).encode(),
        ))
        await write_frame(writer, TwoPartMessage.from_json(
            {"type": "sentinel", "spans_flushed": 3}
        ))
        out = [item async for item in fut]
        writer.close()
        await server.close()
        assert ack is None
        return out

    out = run(main())
    assert len(out) == 1
    assert out[0].data == {"token": "hi"}


def test_request_envelope_trace_field_roundtrip_and_skew():
    env = RequestEnvelope("rid", None, {"x": 1}, {}, trace="00-tp")
    d = json.loads(env.to_bytes())
    assert d["trace"] == "00-tp"
    # older payload without the field still decodes
    old = json.dumps({"request_id": "r", "payload": 1}).encode()
    assert RequestEnvelope.from_bytes(old).trace is None


def test_remote_prefill_request_skew_tolerance():
    from dynamo_tpu.disagg.protocols import RemotePrefillRequest

    rpr = RemotePrefillRequest(
        request_id="r", request={}, skip_blocks=0, connection={},
        trace="00-x", enqueue_ts=1.5,
    )
    raw = json.loads(rpr.to_bytes())
    raw["hypothetical_v3_field"] = {"a": 1}
    back = RemotePrefillRequest.from_bytes(json.dumps(raw).encode())
    assert back.trace == "00-x" and back.enqueue_ts == 1.5


# ---------------- in-process e2e: engine TTFT decomposition ----------------


def _tiny_engine(**kw):
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    kw.setdefault("model", ModelConfig.tiny())
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("prefill_chunk", 32)
    return JaxEngine(EngineConfig(**kw), seed=0)


def _req(toks, max_tokens=4):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )

    return PreprocessedRequest(
        token_ids=list(toks),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, seed=0),
        eos_token_ids=[],
    )


def test_engine_trace_decomposition_sums_within_5pct(run):
    """ISSUE-2 acceptance (in-process shape): spans cover receipt ->
    first token and the decomposition sums to the measured TTFT."""
    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="frontend", sink=col.ingest)
    engine = _tiny_engine(host_cache_blocks=16)

    async def main():
        tc = tracing.TraceContext.for_request("cd" * 16)
        with tracing.use_trace(tc):
            with tracing.span("frontend.request", request_id="cd" * 16):
                first = True
                async for _out in engine.generate(Context(_req(range(40, 58)))):
                    if first:
                        first = False
                        tracing.event("frontend.first_token")
        await engine.close()
        return tc

    tc = run(main())
    spans = col.timeline(tc.trace_id)
    names = {s["name"] for s in spans}
    assert {"frontend.request", "frontend.first_token", "engine.queue_wait",
            "engine.prefill", "engine.first_token"} <= names
    d = col.ttft(tc.trace_id)
    assert d is not None and d["ttft_ms"] > 0
    assert d["prefill"] > 0  # prefill compute attributed
    total = sum(d[k] for k in tracing.COMPONENTS)
    assert total == pytest.approx(d["ttft_ms"], rel=0.05)


def test_engine_untraced_requests_record_nothing(run):
    """Tracing enabled globally but no trace in scope: the engine path
    must not record request spans (and pays only None-checks)."""
    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="t", sink=col.ingest)
    engine = _tiny_engine()

    async def main():
        outs = await collect(engine.generate(Context(_req(range(16)))))
        await engine.close()
        return outs

    outs = run(main())
    assert sum(len(o.token_ids) for o in outs) == 4
    assert col.trace_ids() == []


def test_disagg_trace_covers_remote_prefill(run):
    """The acceptance shape in-process: a disagg-served request's trace
    covers the remote-prefill leg (queue wait, prefill compute, KV send)
    under the SAME trace id, and the decomposition still sums."""
    from dynamo_tpu.disagg import (
        ConditionalDisaggRouter, DisaggConfig, DisaggEngine,
        LocalKvPipe, PrefillQueue, PrefillWorker,
    )

    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="disagg", sink=col.ingest)
    # engine construction is blocking host work — keep it off the loop
    # (the stall-guard fixture enforces exactly this discipline)
    decode = _tiny_engine(max_context=128)
    prefill = _tiny_engine(max_context=128)

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        transfer = LocalKvPipe()
        worker = PrefillWorker(prefill, queue, local_pipe=transfer)
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer)

        tc = tracing.TraceContext.for_request("ad" * 16)
        with tracing.use_trace(tc):
            with tracing.span("frontend.request", request_id="ad" * 16):
                first = True
                async for _ in eng.generate(
                    Context(_req(range(10, 34), max_tokens=4))
                ):
                    if first:
                        first = False
                        tracing.event("frontend.first_token")
        assert eng.stats["remote_prefills"] == 1
        await worker.close()
        await decode.close()
        await prefill.close()
        await router.stop()
        await drt.shutdown()
        return tc.trace_id

    tid = run(main())
    spans = col.timeline(tid) or []
    names = {s["name"] for s in spans}
    assert {"disagg.remote_prefill", "prefill.queue_wait", "prefill.compute",
            "prefill.kv_send", "engine.first_token"} <= names
    assert all(s["trace_id"] == tid for s in spans)
    d = col.ttft(tid)
    total = sum(d[k] for k in tracing.COMPONENTS)
    assert total == pytest.approx(d["ttft_ms"], rel=0.05)


# ---------------- cross-process propagation (satellite) ----------------


class _WorkerEngine(AsyncEngine):
    """Records a worker-side span from the request's propagated trace."""

    async def generate(self, request: Context):
        with tracing.span("worker.engine", request_id=request.id):
            yield Annotated.from_data({"tok": 1})


async def _traced_frontend_call(front, client, router=None):
    """One request with a frontend-rooted trace; returns its trace_id."""
    from dynamo_tpu.kv_router.router import KvRoutedEngine

    tc = tracing.TraceContext.for_request("ef" * 16)
    with tracing.use_trace(tc):
        with tracing.span("frontend.request", request_id="ef" * 16):
            if router is not None:
                eng = KvRoutedEngine(router, client)
                out = [
                    a async for a in eng.generate(
                        Context({"token_ids": [1, 2, 3]})
                    )
                ]
            else:
                stream = await client.round_robin(
                    Context({"token_ids": [1, 2, 3]})
                )
                out = await collect(stream)
    assert any(getattr(a, "data", None) for a in out)
    return tc.trace_id


def test_trace_propagates_through_mock_transport(run):
    """Same trace_id in frontend, router and worker spans — latency-model
    bus/store (the mock multi-node transport)."""
    from dynamo_tpu.kv_router import KvRouter
    from dynamo_tpu.runtime.mock import LatencyBus, LatencyModel, LatencyStore

    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="test", sink=col.ingest)

    async def main():
        lat = LatencyModel.constant(0.001)
        store = LatencyStore(LocalStore(), lat)
        bus = LatencyBus(LocalBus(), lat)
        worker = await DistributedRuntime.from_settings(store=store, bus=bus)
        front = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = worker.namespace("ns").component("gen")
        await comp.endpoint("g").serve(_WorkerEngine())
        fcomp = front.namespace("ns").component("gen")
        client = await fcomp.endpoint("g").client().start()
        await client.wait_for_instances(timeout=5)
        router = await KvRouter(front, fcomp, block_size=4).start()
        tid = await _traced_frontend_call(front, client, router)
        await worker.shutdown()
        await front.shutdown()
        return tid

    tid = run(main())
    spans = col.timeline(tid) or []
    by_name = {s["name"] for s in spans}
    assert "frontend.request" in by_name
    assert "router.schedule" in by_name
    assert "worker.handle" in by_name  # ingress span, worker process side
    assert "worker.engine" in by_name  # engine saw the same trace
    assert all(s["trace_id"] == tid for s in spans)


def test_trace_propagates_through_real_tcp_plane(run):
    """Same trace_id end to end over the real TCP response plane
    (LocalBus envelope + connect-back stream on loopback)."""
    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="test", sink=col.ingest)

    async def main():
        store, bus = LocalStore(), LocalBus()
        worker = await DistributedRuntime.from_settings(store=store, bus=bus)
        front = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = worker.namespace("ns").component("gen")
        await comp.endpoint("g").serve(_WorkerEngine())
        client = (
            await front.namespace("ns").component("gen").endpoint("g")
            .client().start()
        )
        await client.wait_for_instances(timeout=5)
        tid = await _traced_frontend_call(front, client)
        await worker.shutdown()
        await front.shutdown()
        return tid

    tid = run(main())
    spans = col.timeline(tid) or []
    by_name = {s["name"] for s in spans}
    assert {"frontend.request", "worker.handle", "worker.engine"} <= by_name
    # the worker's prologue traceparent attributed the connect-back
    assert "response.stream_connect" in by_name
    assert all(s["trace_id"] == tid for s in spans)


# ---------------- http frontend (satellites: X-Request-Id, /trace) ----------


async def _http_roundtrip(svc, raw: bytes) -> bytes:
    reader, writer = await asyncio.open_connection("127.0.0.1", svc.port)
    writer.write(raw)
    await writer.drain()
    writer.write_eof()
    data = await reader.read()
    writer.close()
    return data


def _post(path, body: dict, headers: dict = None) -> bytes:
    payload = json.dumps(body).encode()
    head = (
        f"POST {path} HTTP/1.1\r\nHost: t\r\nConnection: close\r\n"
        f"Content-Length: {len(payload)}\r\n"
    )
    for k, v in (headers or {}).items():
        head += f"{k}: {v}\r\n"
    return head.encode() + b"\r\n" + payload


class _HttpEcho(AsyncEngine):
    """Engine yielding one OpenAI-ish chunk; captures the request id."""

    def __init__(self):
        self.seen_ids = []

    async def generate(self, request: Context):
        self.seen_ids.append(request.id)
        with tracing.span("tokenize", request_id=request.id):
            pass
        yield {
            "choices": [{"index": 0, "delta": {"content": "hi"},
                         "finish_reason": "stop"}],
        }


def test_http_request_id_trace_endpoint(run):
    """Client-supplied X-Request-Id threads into Context(request_id=...)
    and /trace/{that-id} serves the assembled timeline."""
    from dynamo_tpu.http.service import HttpService, ModelManager

    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="frontend", sink=col.ingest)
    engine = _HttpEcho()

    async def main():
        manager = ModelManager()
        manager.add_chat_model("m", engine)
        svc = HttpService(manager, host="127.0.0.1", port=0,
                          trace_collector=col)
        await svc.start()
        body = {"model": "m",
                "messages": [{"role": "user", "content": "q"}]}
        resp = await _http_roundtrip(svc, _post(
            "/v1/chat/completions", body,
            {"X-Request-Id": "client-abc-123"},
        ))
        assert b"200 OK" in resp.split(b"\r\n", 1)[0]
        trace_resp = await _http_roundtrip(
            svc, b"GET /trace/client-abc-123 HTTP/1.1\r\nHost: t\r\n"
                 b"Connection: close\r\n\r\n"
        )
        chrome_resp = await _http_roundtrip(
            svc, b"GET /trace/client-abc-123?format=chrome HTTP/1.1\r\n"
                 b"Host: t\r\nConnection: close\r\n\r\n"
        )
        missing = await _http_roundtrip(
            svc, b"GET /trace/nope HTTP/1.1\r\nHost: t\r\n"
                 b"Connection: close\r\n\r\n"
        )
        await svc.close()
        return resp, trace_resp, chrome_resp, missing

    resp, trace_resp, chrome_resp, missing = run(main())
    # the satellite: the minted uuid is GONE — the engine saw the client id
    assert engine.seen_ids == ["client-abc-123"]
    body = json.loads(trace_resp.split(b"\r\n\r\n", 1)[1])
    assert body["request_id"] == "client-abc-123"
    names = {s["name"] for s in body["spans"]}
    assert {"frontend.request", "frontend.first_token", "tokenize"} <= names
    assert body["ttft"]["ttft_ms"] >= 0
    chrome = json.loads(chrome_resp.split(b"\r\n\r\n", 1)[1])
    assert chrome["traceEvents"]
    assert b"404" in missing.split(b"\r\n", 1)[0]


def test_http_duplicate_inflight_request_id_minted_fresh(run):
    """Two CONCURRENT requests with the same X-Request-Id must not share
    an id — the second falls back to a minted uuid (cross-request state
    like worker inflight maps and disagg transfer futures key on it)."""
    from dynamo_tpu.http.service import HttpService, ModelManager

    class _Slow(AsyncEngine):
        def __init__(self):
            self.seen_ids = []

        async def generate(self, request: Context):
            self.seen_ids.append(request.id)
            await asyncio.sleep(0.3)
            yield {
                "choices": [{"index": 0, "delta": {"content": "x"},
                             "finish_reason": "stop"}],
            }

    engine = _Slow()

    async def main():
        manager = ModelManager()
        manager.add_chat_model("m", engine)
        svc = HttpService(manager, host="127.0.0.1", port=0)
        await svc.start()
        body = {"model": "m",
                "messages": [{"role": "user", "content": "q"}]}
        raw = _post("/v1/chat/completions", body, {"X-Request-Id": "dup-1"})
        r1, r2 = await asyncio.gather(
            _http_roundtrip(svc, raw), _http_roundtrip(svc, raw)
        )
        # sequential reuse after completion is fine (client retries)
        r3 = await _http_roundtrip(svc, raw)
        await svc.close()
        return r1, r2, r3

    r1, r2, r3 = run(main())
    for r in (r1, r2, r3):
        assert b"200 OK" in r.split(b"\r\n", 1)[0]
    assert len(engine.seen_ids) == 3
    assert engine.seen_ids.count("dup-1") == 2  # one concurrent dup minted
    assert len(set(engine.seen_ids)) == 2


def test_http_trace_endpoint_404_when_disabled(run):
    from dynamo_tpu.http.service import HttpService, ModelManager

    async def main():
        svc = HttpService(ModelManager(), host="127.0.0.1", port=0)
        await svc.start()
        resp = await _http_roundtrip(
            svc, b"GET /trace/x HTTP/1.1\r\nHost: t\r\nConnection: close\r\n\r\n"
        )
        await svc.close()
        return resp

    assert b"404" in run(main()).split(b"\r\n", 1)[0]


def test_http_honors_incoming_traceparent(run):
    """A request arriving with a W3C traceparent keeps its trace id."""
    from dynamo_tpu.http.service import HttpService, ModelManager

    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="frontend", sink=col.ingest)

    async def main():
        manager = ModelManager()
        manager.add_chat_model("m", _HttpEcho())
        svc = HttpService(manager, host="127.0.0.1", port=0,
                          trace_collector=col)
        await svc.start()
        theirs = "00-" + "5" * 32 + "-" + "6" * 16 + "-01"
        resp = await _http_roundtrip(svc, _post(
            "/v1/chat/completions",
            {"model": "m", "messages": [{"role": "user", "content": "q"}]},
            {"traceparent": theirs},
        ))
        await svc.close()
        return resp

    assert b"200 OK" in run(main()).split(b"\r\n", 1)[0]
    assert "5" * 32 in col.trace_ids()


# ---------------- metrics surface ----------------


def test_metrics_component_renders_ttft_percentiles(run):
    from dynamo_tpu.observability.component import MetricsComponent

    col = tracing.TraceCollector()
    tc = tracing.TraceContext.new()
    col.ingest([
        _mk_span("frontend.request", tc, 10.0, 100.0, request_id="r"),
        _mk_span("engine.prefill", tc, 10.02, 60.0),
        _mk_span("frontend.first_token", tc, 10.09, 0.0),
    ])

    async def main():
        drt = await DistributedRuntime.from_settings(
            store=LocalStore(), bus=LocalBus()
        )
        comp = drt.namespace("ns").component("gen")
        mc = MetricsComponent(drt, comp, host="127.0.0.1", port=0,
                              tracing_collector=col)
        text = mc.render()
        await drt.shutdown()
        return text

    text = run(main())
    assert 'ttft_component_ms{component="prefill",quantile="p50"} 60.0' in text
    assert 'ttft_component_ms{component="ttft_ms"' in text
    assert "traces_spans_total 3" in text
