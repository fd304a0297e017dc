"""Disaggregated serving workers.

``DisaggEngine`` is the decode-side AsyncEngine: per request it consults
the ConditionalDisaggRouter; local prompts flow straight into the wrapped
JaxEngine, long prompts are pre-allocated (begin_remote), enqueued on the
PrefillQueue, and completed when the prefill worker's KV lands on the
transfer plane (ref examples/llm/components/worker.py:45-189).

``PrefillWorker`` is the queue consumer: prefill + first-token sample on
its own engine/mesh, then push the KV to the requesting decode host
(ref examples/llm/components/prefill_worker.py:84-141). Failures nack the
item so it redelivers to another worker — elastic xPyD
(docs/disagg_serving.md:93-101)."""

from __future__ import annotations

import asyncio
import logging
import time
from typing import AsyncIterator, Optional, Union

import numpy as np

from .. import tracing
from ..engine.engine import JaxEngine, OutOfBlocks
from ..models.llama import KV_HEAD_LAYOUT
from ..protocols.common import LLMEngineOutput, PreprocessedRequest
from ..resilience import faultpoints
from ..resilience.faultpoints import FaultInjected
from ..runtime.engine import AsyncEngine, AsyncEngineContext, Context
from .protocols import RemotePrefillRequest
from .queue import PrefillQueue
from .transfer import (
    KV_QUANT_WIRE_VERSION,
    KV_STREAM_BASE_VERSION,
    KV_STREAM_VERSION,
    KvStreamSender,
    KvTransferServer,
    LocalKvPipe,
    SinkClosed,
    TransferError,
    send_kv_blocks,
)
from .router import ConditionalDisaggRouter

logger = logging.getLogger(__name__)

#: per-segment wall bound for the streamed handoff's socket sends — the
#: sender's backpressure reaches into prefill compute (device lock held),
#: so a peer that stops reading must fail fast into nack/redelivery
SEGMENT_SEND_TIMEOUT_S = 60.0


class PrefillWorker:
    def __init__(
        self,
        engine: JaxEngine,
        queue: PrefillQueue,
        local_pipe: Optional[LocalKvPipe] = None,
        layer_chunk: int = 4,
        head_layout: Optional[str] = None,
        kv_stream: bool = True,
        segment_blocks: int = 0,
        concurrency: int = 1,
        kv_ici: bool = True,
    ):
        self.engine = engine
        self.queue = queue
        self.local_pipe = local_pipe
        self.layer_chunk = layer_chunk
        # wire-declared kv-head ordering; override only when wrapping an
        # engine whose extraction really produces a non-natural order
        self.head_layout = head_layout or KV_HEAD_LAYOUT
        # streamed layer-wise handoff (FlowKV): open the transfer at
        # prefill start, ship each chunk's blocks as its compute lands.
        # Engages only when the decode peer advertised the capability in
        # its connection info — old peers keep getting the bulk protocol
        self.kv_stream = kv_stream
        self.segment_blocks = segment_blocks
        # ICI same-slice fast path (disagg/ici.py): stamp streamed
        # headers ``ici`` when the decode peer advertised a covering
        # kv_ici version AND the same slice fingerprint — the decode
        # sink then re-lays segments device→device instead of letting
        # the scatter resolve a foreign placement implicitly. Any
        # mismatch silently keeps the plain streamed/TCP path.
        self.kv_ici = kv_ici
        # per-block wire quantization (engine/kvquant.py, the engine's
        # --kv-quant mode): TCP handoffs ship int8/fp8 payloads + scale
        # frames to decode peers that advertised the kv_quant
        # capability — half the DCN bytes per handoff. Local-pipe and
        # ICI handoffs stay full width (they never serialize), and
        # legacy peers get dequantized full-width bytes. getattr: test
        # harnesses wrap engines whose cfg predates the knob.
        self.kv_quant = getattr(engine.cfg, "kv_quant", "none")
        # consume-loop fan-out: with the engine's streamed extract taking
        # the device lock per CHUNK, N concurrent prompts interleave
        # chunk-wise and each streams its segments as its own chunks
        # land — M queued prompts advance together instead of
        # head-of-line blocking on whole-prompt prefills (the disagg
        # twin of the mixed-batch packer). Each loop owns its item's
        # full dequeue->process->ack lifecycle, so the PR 4 no-ack/
        # redeliver semantics are untouched.
        self.concurrency = max(int(concurrency), 1)
        self._tasks: list[asyncio.Task] = []
        self._stop = asyncio.Event()
        # prefill-role send-side counters: asserted by the disagg tests
        # and bench directly from this dict; the router only routes
        # DECODE workers, so none of these belong in WorkerLoad
        self.stats = {
            "prefills_total": 0, "prefill_errors": 0, "nacks": 0,  # dynlint: disable=unscraped-stat -- prefill-role diagnostics; the scrape plane describes decode workers
            "kv_stream_sends": 0, "kv_stream_segments": 0, "kv_bulk_sends": 0,  # dynlint: disable=unscraped-stat -- prefill-role diagnostics; the scrape plane describes decode workers
            "kv_ici_sends": 0,  # dynlint: disable=unscraped-stat -- prefill-role diagnostics; the scrape plane describes decode workers
            "kv_quant_sends": 0,  # dynlint: disable=unscraped-stat -- prefill-role diagnostic; the decode-side tier counters are the gauges
        }

    def _wire_quant(self, connection: dict, local: bool) -> str:
        """Negotiated wire codec for one handoff: this worker's
        --kv-quant mode, IF the channel serializes (never the local
        pipe) and the decode peer advertised the kv_quant capability.
        Everything else — legacy peers above all — gets full width."""
        if (
            self.kv_quant != "none"
            and not local
            and int(connection.get("kv_quant") or 0) >= KV_QUANT_WIRE_VERSION
        ):
            return self.kv_quant
        return "none"

    def start(self) -> None:
        if not self._tasks:
            loop = asyncio.get_running_loop()
            self._tasks = [
                loop.create_task(self.run()) for _ in range(self.concurrency)
            ]

    async def close(self) -> None:
        self._stop.set()
        for t in self._tasks:
            t.cancel()
        self._tasks = []

    MAX_DELIVERIES = 5  # poison-pill cutoff: after this, fail the request

    async def run(self) -> None:
        while not self._stop.is_set():
            try:
                await self._run_once()
            except asyncio.CancelledError:
                return
            except FaultInjected:
                # harness kill: the consume loop DIES (no retry) — the
                # un-acked item redelivers to a surviving consumer, not
                # back to this one
                logger.warning("prefill worker killed by fault point")
                self._stop.set()
                return
            except Exception:  # noqa: BLE001 — transient bus/hub error:
                # the fleet must not silently lose a prefill consumer
                logger.exception("prefill consume loop error; retrying")
                await asyncio.sleep(0.5)

    async def _run_once(self) -> None:
        got = await self.queue.dequeue(timeout=0.5)
        if got is None:
            return
        item_id, rpr = got
        try:
            await self._process(rpr)
        except FaultInjected:
            # harness kill mid-processing: die like a real crash — no
            # ack, no nack, no error notification; the queue's
            # visibility timeout redelivers the item to a survivor
            raise
        except OutOfBlocks:
            # pool full: hand the item back for another worker (or
            # ourselves, once running prefills free their blocks)
            self.stats["nacks"] += 1
            await self.queue.nack(item_id)
            await asyncio.sleep(0.05)
            return
        except TransferError as e:
            # the KV never landed: retriable — unless this item has
            # already bounced enough to look like a dead decode host
            if self.queue.deliveries(item_id) < self.MAX_DELIVERIES:
                logger.warning("kv transfer failed (%s); redelivering", e)
                self.stats["nacks"] += 1
                await self.queue.nack(item_id)
                await asyncio.sleep(0.1)
                return
            logger.error("kv transfer failed %d times: %s", self.MAX_DELIVERIES, e)
            self.stats["prefill_errors"] += 1
            await self._notify_error(rpr, str(e))
        except Exception as e:  # noqa: BLE001 — a COMPUTE failure is
            # deterministic (bad request, model error): another worker
            # would fail identically, so notify the decode side and ack
            logger.exception("remote prefill failed: %s (decode engine %x)",
                             rpr.request_id, rpr.engine_id)
            self.stats["prefill_errors"] += 1
            await self._notify_error(rpr, str(e))
        # the WAL item is acked only here — AFTER the KV handoff
        # committed (or after a deterministic failure was delivered): a
        # worker killed anywhere above leaves the item in flight and the
        # prefill redelivers instead of silently dropping
        await self.queue.ack(item_id)

    async def _process(self, rpr: RemotePrefillRequest) -> None:
        req = PreprocessedRequest.from_dict(rpr.request)
        ctx = AsyncEngineContext(rpr.request_id)
        trace_token = None
        if tracing.enabled() and rpr.trace:
            # continue the decode side's trace across the queue handoff
            tc = tracing.TraceContext.for_request(rpr.request_id, rpr.trace)
            trace_token = tracing.set_trace(tc)
            if rpr.enqueue_ts:
                # queue wait reconstructed from the decode side's enqueue
                # stamp (cross-host wall clocks; see protocols.py)
                waited_s = max(time.time() - rpr.enqueue_ts, 0.0)
                tracing.RECORDER.record_span(
                    "prefill.queue_wait", tc, ts=rpr.enqueue_ts,
                    dur_ms=waited_s * 1e3, request_id=rpr.request_id,
                )
        try:
            # in-process pipe => same device slice: keep KV on device end to
            # end (gather -> pipe -> decode scatter, no host hop); the TCP
            # path needs host bytes anyway. A local-advertising decode may
            # ALSO carry a TCP connect-back address (DisaggEngine
            # tcp_fallback) — a pipe-less worker then delivers over TCP,
            # which is what lets one queue mix same-slice and remote
            # prefill workers (and redeliveries cross between them).
            local = bool(rpr.connection.get("local")) and self.local_pipe is not None
            has_addr = bool(rpr.connection.get("address"))
            if rpr.connection.get("local") and not local and not has_addr:
                # no channel at all: nack/redeliver to a worker that has
                # one instead of failing the request deterministically
                raise TransferError("local connection without pipe")
            # graceful downgrade: stream only when the decode peer
            # advertised a protocol version covering the BASE streamed
            # layout — an old peer (no kv_stream key, or below the
            # base) silently gets the bulk protocol it already speaks.
            # v1 peers still take v2 streams (the v2 scale frames only
            # engage behind the separate kv_quant capability below)
            streamed = (
                self.kv_stream
                and int(rpr.connection.get("kv_stream") or 0)
                >= KV_STREAM_BASE_VERSION
                and hasattr(self.engine, "prefill_extract_stream")
                and (local or has_addr or not rpr.connection.get("local"))
            )
            if streamed:
                await self._process_streamed(rpr, req, ctx, local)
                return
            timings: dict = {}
            compute_span = tracing.span(
                "prefill.compute", request_id=rpr.request_id,
                prompt_tokens=len(req.token_ids), skip_blocks=rpr.skip_blocks,
            )
            with compute_span:
                first, first_lp, k, v = await self.engine.prefill_extract(
                    req, ctx, skip_blocks=rpr.skip_blocks, keep_on_device=local,
                    timings=timings,
                )
                # the d2h gather inside the extract is handoff time, not
                # prompt compute — ttft.py carves it out of this span
                # into the kv_transfer decomposition
                compute_span.set(
                    kv_gather_ms=round(timings.get("gather_ms", 0.0), 3)
                )
            self.stats["prefills_total"] += 1
            layout = self.head_layout
            tp = self.engine.cfg.mesh.tp if self.engine.cfg.mesh else 1
            wire_q = self._wire_quant(rpr.connection, local)
            k_scales = v_scales = None
            if wire_q != "none" and k is not None and k.shape[2]:
                from ..engine import kvquant

                # multi-MB per-block quantize: executor thread, like the
                # d2h it follows — half the DCN bytes for the send below
                k, v, k_scales, v_scales = (
                    await asyncio.get_running_loop().run_in_executor(
                        None, kvquant.quantize_stack, k, v, wire_q
                    )
                )
                self.stats["kv_quant_sends"] += 1
            await faultpoints.hit("mid_kv_transfer", request_id=rpr.request_id)
            send_span = tracing.span(
                "prefill.kv_send", request_id=rpr.request_id,
                local=bool(rpr.connection.get("local")),
            )
            with send_span:
                t0 = time.perf_counter()
                try:
                    if local:
                        await self.local_pipe.deliver(
                            rpr.request_id, first, k, v, head_layout=layout, src_tp=tp,
                            first_lp=first_lp,
                        )
                    else:
                        await send_kv_blocks(
                            rpr.connection, rpr.request_id, first, k, v,
                            layer_chunk=self.layer_chunk, head_layout=layout, src_tp=tp,
                            first_lp=first_lp, kv_quant=wire_q,
                            k_scales=k_scales, v_scales=v_scales,
                        )
                except (TransferError, FaultInjected):
                    raise
                except Exception as e:  # noqa: BLE001 — ANY handoff-stage
                    # failure (connection reset writing the stream,
                    # serialization trouble) means the KV never committed
                    # on the decode side: it must redeliver like a
                    # TransferError, never ack-with-error (which would
                    # strand the decode side waiting out its full
                    # transfer timeout on a prefill nobody will redo)
                    raise TransferError(f"kv handoff failed: {e}") from e
                # bulk handoff: the ENTIRE send sits after prefill, so it
                # is all exposed transfer time (ttft.py reads these attrs)
                send_span.set(
                    exposed_ms=round((time.perf_counter() - t0) * 1e3, 3),
                    hidden_ms=0.0,
                )
            self.stats["kv_bulk_sends"] += 1
        finally:
            if trace_token is not None:
                tracing.reset_trace(trace_token)

    async def _process_streamed(
        self, rpr: RemotePrefillRequest, req: PreprocessedRequest, ctx, local: bool
    ) -> None:
        """Streamed handoff: open the transfer BEFORE prefill compute,
        pump each chunk's blocks through a bounded send queue while the
        next chunk computes, finish with the sampled first token and the
        stream's single end-to-end ack. Failure semantics match the bulk
        path exactly: transfer trouble -> TransferError (nack/redeliver),
        fault kill -> crash-like no-ack, compute error -> propagates for
        the deterministic error notification."""
        engine = self.engine
        layout = self.head_layout
        tp = engine.cfg.mesh.tp if engine.cfg.mesh else 1
        n_prompt = engine.n_prompt_blocks(len(req.token_ids))
        n = max(n_prompt - rpr.skip_blocks, 0)
        kc, vc = engine.k_cache, engine.v_cache
        # ICI fast path: negotiated on SLICE IDENTITY, not channel —
        # the decode peer advertised a covering kv_ici version and the
        # same slice fingerprint as this engine's devices. In-process
        # (LocalKvPipe) handoffs stay device-resident end to end;
        # launched same-slice roles ship wire segments but the decode
        # sink still lands them through the compiled per-bucket mover
        # programs onto its cache layout (mesh-agnostic placement)
        # instead of letting the scatter resolve a foreign placement
        # implicitly. A kv-head-layout mismatch drops it (the decode
        # sink's regroup owns that case), keeping the fallback matrix
        # clean
        from .ici import ici_negotiated

        ici = (
            ici_negotiated(rpr.connection, engine, enabled=self.kv_ici)
            and layout == rpr.connection.get("ici_layout", layout)
        )
        # streamed wire quantization: negotiated like the bulk path,
        # plus the receiver must speak the v2 frame layout; ICI
        # handoffs stay full width (their segments land device→device
        # through the mover — quantizing would add a host round-trip)
        wire_q = self._wire_quant(rpr.connection, local)
        if ici or int(rpr.connection.get("kv_stream") or 0) < KV_STREAM_VERSION:
            wire_q = "none"
        if wire_q != "none":
            from ..engine.kvquant import quant_dtype

            wire_dtype = str(quant_dtype(wire_q))
        else:
            wire_dtype = str(kc.dtype)
        head = {
            "request_id": rpr.request_id,
            "stream": KV_STREAM_VERSION,
            "n_blocks": n,
            "shape": [kc.shape[0], kc.shape[1], n, kc.shape[3], kc.shape[4]],
            "v_shape": [vc.shape[0], vc.shape[1], n, vc.shape[3], vc.shape[4]],
            "dtype": wire_dtype,
            "layer_chunk": self.layer_chunk,
            "head_layout": layout,
            "src_tp": tp,
        }
        if wire_q != "none":
            head["kv_quant"] = wire_q
        if ici:
            from ..parallel.mesh import slice_fingerprint

            head["ici"] = 1
            head["ici_fp"] = slice_fingerprint()
        await faultpoints.hit("mid_kv_transfer", request_id=rpr.request_id)
        send_span = tracing.span(
            "prefill.kv_send", request_id=rpr.request_id, local=local,
            streamed=True,
        )
        # the connection opens at prefill START — segment i's wire time
        # hides behind chunk i+1's compute (FlowKV, ROADMAP item 1)
        if local:
            assert self.local_pipe is not None
            stream = await self.local_pipe.open_stream(rpr.request_id, head)
        else:
            try:
                stream = await KvStreamSender.open(
                    rpr.connection, rpr.request_id, head
                )
            except TransferError as e:
                send_span.set(error=type(e).__name__)
                send_span.end()
                raise
        sendq: asyncio.Queue = asyncio.Queue(maxsize=2)

        send_ms = 0.0

        async def pump() -> None:
            nonlocal send_ms
            while True:
                item = await sendq.get()
                if item is None:
                    return
                t_s = time.perf_counter()
                try:
                    # the pump's backpressure reaches into prefill compute
                    # (emit_upto blocks on the queue under the DEVICE
                    # lock), so a half-open peer that stops reading must
                    # become a bounded TransferError -> nack, not a
                    # forever-wedged prefill engine
                    await asyncio.wait_for(
                        stream.send_segment(*item), SEGMENT_SEND_TIMEOUT_S
                    )
                except (TransferError, FaultInjected):
                    raise
                except asyncio.TimeoutError as e:
                    raise TransferError(
                        f"kv segment send stalled > {SEGMENT_SEND_TIMEOUT_S}s"
                    ) from e
                except Exception as e:  # noqa: BLE001 — same contract as
                    # the bulk handoff stage: an uncommitted segment must
                    # redeliver, never ack-with-error
                    raise TransferError(f"kv segment handoff failed: {e}") from e
                send_ms += (time.perf_counter() - t_s) * 1e3
                self.stats["kv_stream_segments"] += 1

        pump_task = asyncio.get_running_loop().create_task(pump())

        async def put_or_fail(item) -> None:
            # never block on a queue whose consumer died: race the put
            # against the pump so a send failure surfaces immediately
            put = asyncio.ensure_future(sendq.put(item))
            done, _ = await asyncio.wait(
                {put, pump_task}, return_when=asyncio.FIRST_COMPLETED
            )
            if put in done:
                return
            put.cancel()
            exc = pump_task.exception()
            raise exc if exc else TransferError("kv stream sender stopped")

        async def on_segment(b0: int, k_seg, v_seg) -> None:
            await faultpoints.hit("mid_kv_transfer", request_id=rpr.request_id)
            if not local:
                # segment-sized (multi-MB) device->host materialization
                # (+ the per-block wire quantize when negotiated): off
                # the loop, or the whole engine freezes for the copy
                # while prefill compute should be hiding it
                def _materialize():
                    k_np, v_np = np.asarray(k_seg), np.asarray(v_seg)
                    if wire_q != "none":
                        from ..engine import kvquant

                        return kvquant.quantize_stack(k_np, v_np, wire_q)
                    return k_np, v_np, None, None
                k_np, v_np, ks, vs = (
                    await asyncio.get_running_loop().run_in_executor(
                        None, _materialize
                    )
                )
                if ks is not None:
                    await put_or_fail((b0, k_np, v_np, ks, vs))
                    return
                await put_or_fail((b0, k_np, v_np))
                return
            await put_or_fail((b0, k_seg, v_seg))

        ok = False
        timings: dict = {}
        try:
            compute_span = tracing.span(
                "prefill.compute", request_id=rpr.request_id,
                prompt_tokens=len(req.token_ids), skip_blocks=rpr.skip_blocks,
            )
            with compute_span:
                first, first_lp, _sent = await engine.prefill_extract_stream(
                    req, ctx, skip_blocks=rpr.skip_blocks, keep_on_device=local,
                    segment_blocks=self.segment_blocks, on_segment=on_segment,
                    timings=timings,
                )
                # per-segment gathers OVERLAP the wire transfer of the
                # segments already shipped — unlike the bulk path's
                # whole-stack gather (which nothing overlaps, so it's
                # carved into kv_transfer_exposed via kv_gather_ms),
                # they are pipeline stages, recorded for observability
                # but left inside the prefill region
                compute_span.set(
                    seg_gather_ms=round(timings.get("gather_ms", 0.0), 3)
                )
            self.stats["prefills_total"] += 1
            t_done = time.perf_counter()
            await put_or_fail(None)
            await pump_task  # drains the tail; raises on send failure
            await stream.finish(first, first_lp)
            ok = True
            self.stats["kv_stream_sends"] += 1
            if ici:
                self.stats["kv_ici_sends"] += 1
            if wire_q != "none":
                self.stats["kv_quant_sends"] += 1
            # exposed = the post-compute tail (final drain + fin + ack);
            # hidden = ACTUAL send activity that overlapped compute (the
            # pump's measured per-segment send time minus the part that
            # ran in the tail) — not the open-to-finish window, which
            # would misreport the whole prefill duration as transfer.
            # ttft.py folds these into the PR 2 decomposition
            now = time.perf_counter()
            exposed_ms = (now - t_done) * 1e3
            nbytes = n * (
                getattr(engine, "kv_wire_block_bytes", 0)
                if wire_q != "none"
                else getattr(engine, "kv_block_bytes", 0)
            )
            send_span.set(
                exposed_ms=round(exposed_ms, 3),
                hidden_ms=round(max(send_ms - exposed_ms, 0.0), 3),
                segments=stream.segments,
                n_blocks=n,
                # link class + volume: the span doubles as a transfer-
                # cost observation (tracing/ttft.cost_observations)
                link="ici" if ici else ("local" if local else "dcn"),
                nbytes=nbytes,
            )
            # calibrate the sender's cost model from its own measured
            # send activity: cross-host streamed sends are the "dcn"
            # class (the ici class is observed decode-side, where the
            # mover+scatter wall is the honest number)
            cost = getattr(engine, "cost", None)
            if cost is not None and not local and send_ms > 0 and nbytes:
                cost.observe("dcn", nbytes, send_ms / 1e3)
        finally:
            if not pump_task.done():
                pump_task.cancel()
                # cancel alone is NOT enough: if it lands while the pump
                # awaits a segment scatter riding run_in_executor, the
                # executor future is uncancellable once its fn is running
                # — asyncio swallows the cancellation waiting it out, and
                # the pump then parks on sendq.get() forever, deadlocking
                # this drain against a producer that is already unwinding
                # (found as a ~40% hang of the mid-stream kill tests).
                # Feed the shutdown sentinel so a cancel-surviving pump
                # exits through its normal path (pending segments are
                # discarded — this attempt is abandoned, and no-ack means
                # the queue redelivers it whole), and bound the drain so
                # teardown can never wedge the consume loop regardless.
                while not sendq.empty():
                    sendq.get_nowait()
                try:
                    sendq.put_nowait(None)
                except asyncio.QueueFull:
                    pass  # pump is mid-get of the last item; the
                    # sentinel slot frees by the time it looks again
                try:
                    await asyncio.wait_for(pump_task, SEGMENT_SEND_TIMEOUT_S)
                except (asyncio.CancelledError, Exception):  # noqa: BLE001
                    pass
            if not ok:
                await stream.aclose()
            send_span.end()

    async def _notify_error(self, rpr: RemotePrefillRequest, message: str) -> None:
        try:
            if rpr.connection.get("local") and self.local_pipe is not None:
                await self.local_pipe.deliver(
                    rpr.request_id, -1, None, None, error=message
                )
            elif rpr.connection.get("address"):
                await send_kv_blocks(
                    rpr.connection, rpr.request_id, -1, None, None, error=message
                )
        except Exception:  # noqa: BLE001 — decode side also has a timeout
            logger.exception("error notification failed: %s", rpr.request_id)


class _RemoteScatterSink:
    """Decode-side landing policy for ONE streamed remote prefill: each
    segment scatters into the request's pre-allocated pages the moment
    it arrives (engine.scatter_remote_segment), so the full-stack buffer
    never materializes and only the final segment's tail can sit on
    TTFT. A kv-head-layout / tp mismatch no longer declines the stream:
    the head-axis permutation (ops/kv_rearrange) is block-independent,
    so each segment regroups ON ARRIVAL — mismatched peers stream too
    (ROADMAP item 1's last leftover). ``begin`` validates the
    permutation against the declared geometry and only falls back to
    the buffered bulk path when no valid regroup exists (bad peer
    metadata — the bulk delivery's regroup then surfaces the error
    through the existing abort path). ``aclose`` waits out any
    in-flight scatter before the caller frees the reservation, so an
    abandoned stream can never write into recycled pages."""

    def __init__(self, engine: JaxEngine, handle, stats: dict):
        self._engine = engine
        self._handle = handle
        self._stats = stats
        self._closed = False
        self._lock = asyncio.Lock()
        self._regroup = None  # (src_tp, dst_tp, src_layout, dst_layout)
        self._ici = None  # IciSegmentMover when the ICI path negotiated
        self.segments = 0

    async def begin(self, head: dict) -> bool:
        if self._closed:
            return False
        my_tp = self._engine.cfg.mesh.tp if self._engine.cfg.mesh else 1
        layout = head.get("head_layout", "blocked")
        src_tp = head.get("src_tp", 1)
        self._regroup = None
        self._ici = None
        from ..ops.kv_rearrange import layout_mismatched

        if layout_mismatched(layout, src_tp, KV_HEAD_LAYOUT, my_tp):
            from ..ops.kv_rearrange import rearrange_for_decode

            # validate the permutation NOW against both declared head
            # geometries (k and v differ for MLA latents): a geometry
            # the regroup can't cover must take the bulk fallback at
            # begin-time, not poison the stream mid-flight
            shape = tuple(head.get("shape") or ())
            v_shape = tuple(head.get("v_shape") or shape)
            try:
                for hkv in {shape[1], v_shape[1]}:
                    rearrange_for_decode(
                        np.empty((1, hkv, 0, 1, 1), np.int8),
                        src_tp, my_tp, layout, KV_HEAD_LAYOUT,
                    )
            except Exception:  # noqa: BLE001 — bad peer metadata
                return False
            self._regroup = (src_tp, my_tp, layout, KV_HEAD_LAYOUT)
        if head.get("ici") and self._regroup is None:
            # ICI fast path: the sender negotiated the same-slice
            # device→device handoff (fingerprint re-checked here —
            # defense against a stale connection dict) and the layouts
            # agree. Mover construction failure just leaves the plain
            # streamed landing in charge; the stream stays valid.
            from ..disagg.ici import IciSegmentMover
            from ..parallel.mesh import cache_sharding, slice_fingerprint

            try:
                if head.get("ici_fp") in (None, slice_fingerprint()):
                    eng = self._engine
                    sh = (
                        cache_sharding(eng.mesh, eng.cfg.model)
                        if eng.mesh is not None else None
                    )
                    self._ici = IciSegmentMover(sh, sh)
                    self._stats["ici_handoffs"] = (
                        self._stats.get("ici_handoffs", 0) + 1
                    )
            except Exception:  # noqa: BLE001 — fast path is optional
                logger.debug("ici mover setup failed; plain streamed "
                             "landing", exc_info=True)
                self._ici = None
        # a redelivered stream restarts from block 0 — re-scatters over
        # the same uncommitted pages are idempotent
        self.segments = 0
        return True

    async def segment(self, b0: int, k_seg, v_seg,
                      k_scales=None, v_scales=None) -> None:
        async with self._lock:
            if self._closed:
                raise SinkClosed(self._handle.seq.context.id)
            if self._regroup is not None:
                from ..ops.kv_rearrange import rearrange_for_decode

                src_tp, dst_tp, sl, dl = self._regroup
                # pure head-axis gather; on device-resident segments
                # (local pipe) XLA fuses it into the scatter. Valid on
                # quantized payloads unchanged: the codec's scales are
                # per (layer, block) — deliberately kv-head-free
                k_seg = rearrange_for_decode(k_seg, src_tp, dst_tp, sl, dl)
                v_seg = rearrange_for_decode(v_seg, src_tp, dst_tp, sl, dl)
                self._stats["kv_stream_regroups"] = (
                    self._stats.get("kv_stream_regroups", 0) + 1
                )
            t0 = time.perf_counter()
            if self._ici is not None:
                # ICI fast path: explicit device→device re-layout onto
                # the decode cache's sharding (compiled per geometry
                # bucket) — the scatter below then lands same-placed
                # arrays instead of resolving a foreign one implicitly
                k_seg, v_seg = self._ici.move(k_seg, v_seg)
                self._stats["ici_segments"] = (
                    self._stats.get("ici_segments", 0) + 1
                )
            if k_scales is not None:
                await self._engine.scatter_remote_segment(
                    self._handle, b0, k_seg, v_seg, k_scales, v_scales
                )
            else:
                # positional-compat with the pre-quant signature:
                # full-width segments keep the 4-arg call shape
                await self._engine.scatter_remote_segment(
                    self._handle, b0, k_seg, v_seg
                )
            if self._ici is not None:
                # the moved+scattered wall is the decode side's honest
                # per-segment ICI cost — folding it into the engine's
                # cost model is what lets routing learn this link class
                cost = getattr(self._engine, "cost", None)
                nbytes = getattr(k_seg, "nbytes", 0) + getattr(
                    v_seg, "nbytes", 0
                )
                if cost is not None and nbytes:
                    cost.observe(
                        "ici", nbytes,
                        max(time.perf_counter() - t0, 1e-9),
                    )
            self.segments += 1
            self._stats["kv_stream_segments"] += 1

    async def aclose(self) -> None:
        self._closed = True
        async with self._lock:
            pass


class DisaggEngine(AsyncEngine):
    """Decode-side conditional-disaggregation front (AsyncEngine over
    PreprocessedRequest -> LLMEngineOutput stream)."""

    def __init__(
        self,
        engine: JaxEngine,
        router: ConditionalDisaggRouter,
        queue: PrefillQueue,
        transfer: Union[KvTransferServer, LocalKvPipe],
        engine_id: int = 0,
        transfer_timeout: float = 120.0,
        kv_stream: bool = True,
        kv_ici: bool = True,
        tcp_fallback: Optional[KvTransferServer] = None,
    ):
        self.engine = engine
        self.router = router
        self.queue = queue
        self.transfer = transfer
        self.engine_id = engine_id
        self.transfer_timeout = transfer_timeout
        # optional second delivery channel for LocalKvPipe engines: the
        # connection then carries BOTH the in-process flag and a real
        # TCP address, so one prefill queue can serve same-slice workers
        # (pipe, ICI fast path) and remote workers (TCP) — and a
        # redelivery after a same-slice worker dies mid-stream lands
        # over TCP from a survivor. Ignored unless transfer is a pipe.
        self._tcp = (
            tcp_fallback if isinstance(transfer, LocalKvPipe) else None
        )
        # advertise the streamed-handoff capability to prefill workers;
        # off = force the legacy bulk protocol end to end
        self.kv_stream = kv_stream
        # advertise the ICI same-slice fast path (disagg/ici.py):
        # version + slice fingerprint + kv-head layout ride connection
        # info; a prefill worker on the same slice then marks its
        # streamed headers ``ici`` and the scatter sink re-lays segments
        # device→device. Off = plain streamed/bulk everywhere.
        self.kv_ici = kv_ici
        # delivery-flavor counters ride to gauges (streamed_deliveries/
        # bulk_deliveries/kv_stream_segments/ici_handoffs in WorkerLoad);
        # the rest are handoff diagnostics the disagg tests assert on
        # directly
        self.stats = {
            "remote_prefills": 0, "local_prefills": 0, "remote_errors": 0,  # dynlint: disable=unscraped-stat -- disagg-path diagnostics asserted by tests/bench; not router inputs
            "streamed_deliveries": 0, "bulk_deliveries": 0,
            "kv_stream_segments": 0, "kv_stream_regroups": 0,  # dynlint: disable=unscraped-stat -- regroup count is a handoff diagnostic, not a router input
            "ici_handoffs": 0, "ici_segments": 0,  # dynlint: disable=unscraped-stat -- per-segment volume is a diagnostic; ici_handoffs is the gauge
        }

    def _connection(self) -> dict:
        if isinstance(self.transfer, LocalKvPipe):
            conn = {"local": True}
            if self._tcp is not None:
                conn.update(self._tcp.address.to_dict())
        else:
            conn = self.transfer.address.to_dict()
        if self.kv_stream:
            conn["kv_stream"] = KV_STREAM_VERSION
        if self.engine.mirror is None:
            # wire-codec capability: this decode side dequantizes
            # int8/fp8 deliveries on landing (scales through the
            # device-side scatter), independent of its OWN --kv-quant
            # mode. Mirror-backed engines scatter via lockstep
            # broadcasts that are full-width only — they must not
            # advertise it.
            conn["kv_quant"] = KV_QUANT_WIRE_VERSION
        if self.kv_ici and self.kv_stream and self.engine.mirror is None:
            from ..parallel.mesh import slice_fingerprint
            from .ici import KV_ICI_VERSION

            conn["kv_ici"] = KV_ICI_VERSION
            conn["ici_fp"] = slice_fingerprint()
            conn["ici_layout"] = KV_HEAD_LAYOUT
        return conn

    def _expect(self, req_id: str, sink) -> asyncio.Future:
        """Register the pending delivery on every advertised channel
        (pipe + optional TCP fallback) and return one future resolving
        with whichever lands first. The shared sink is attempt-safe:
        ``begin`` re-inits per stream, and the post-delivery sink close
        turns a racing loser's late segments into discards."""
        fut = self.transfer.expect(req_id, sink=sink)
        if self._tcp is None:
            return fut
        fut2 = self._tcp.expect(req_id, sink=sink)

        async def race():
            done, _pending = await asyncio.wait(
                {fut, fut2}, return_when=asyncio.FIRST_COMPLETED
            )
            # both channels can resolve in one loop tick (a late error
            # notification racing the redelivered push): prefer a real
            # KV delivery over an error — failing a request whose KV
            # landed on the other channel would recompute for nothing
            best = None
            for f in done:
                if f.cancelled():
                    continue
                d = f.result()
                if best is None or (
                    getattr(best, "error", None)
                    and not getattr(d, "error", None)
                ):
                    best = d
            if best is None:
                raise asyncio.CancelledError()
            return best

        return asyncio.ensure_future(race())

    def _abandon(self, req_id: str) -> None:
        self.transfer.abandon(req_id)
        if self._tcp is not None:
            self._tcp.abandon(req_id)

    async def generate(self, request: Context) -> AsyncIterator[LLMEngineOutput]:
        req = request.data
        if isinstance(req, dict):
            req = PreprocessedRequest.from_dict(req)
            request = request.transfer(req)
        prompt_len = len(req.token_ids or [])
        handle = None
        remote = False
        # fast path: a prompt under the threshold can never go remote
        # (cached prefix only shortens it) — skip the reservation churn
        # and the queue-depth RPC entirely
        if (
            self.router.config.enabled
            and prompt_len > self.router.config.max_local_prefill_length
        ):
            handle = self.engine.begin_remote(request)
        if handle is not None:
            depth = await self.queue.get_depth()
            remote = self.router.prefill_remote(
                prompt_len, handle.seq.cached_prefix, depth
            )
        if not remote:
            if handle is not None:
                self.engine.release_remote(handle)
            self.stats["local_prefills"] += 1
            async for out in self.engine.generate(request):
                yield out
            return

        self.stats["remote_prefills"] += 1
        self.engine.start()
        req_id = request.id
        sink = (
            _RemoteScatterSink(self.engine, handle, self.stats)
            if self.kv_stream else None
        )
        fut = self._expect(req_id, sink)
        rpr = RemotePrefillRequest(
            request_id=req_id,
            request=req.to_dict(),
            skip_blocks=handle.skip_blocks,
            connection=self._connection(),
            engine_id=self.engine_id,
            trace=tracing.current_traceparent(),
            enqueue_ts=time.time() if tracing.enabled() else 0.0,
        )
        # decode-side wait for the whole remote leg (queue + prefill +
        # KV transfer); the decomposition subtracts the worker-side spans
        # to isolate the transfer cost
        remote_span = tracing.span(
            "disagg.remote_prefill", request_id=req_id,
            prompt_tokens=prompt_len, skip_blocks=handle.skip_blocks,
        )
        t_handoff = time.perf_counter()
        try:
            await self.queue.enqueue(rpr)
            delivery = await asyncio.wait_for(fut, self.transfer_timeout)
            # whole remote leg (queue + prefill + KV transfer) into the
            # worker's handoff distribution (SLO observatory plane)
            self.engine.hist["handoff_ms"].observe(
                (time.perf_counter() - t_handoff) * 1e3
            )
        except asyncio.CancelledError:
            # caller went away: clean up the reservation, propagate.
            # The sink must close BEFORE abort_remote frees the blocks —
            # an in-flight streamed scatter may still be writing them
            remote_span.set(error="cancelled")
            self._abandon(req_id)
            if sink is not None:
                await sink.aclose()
            self.engine.abort_remote(handle, "cancelled")
            raise
        except Exception as e:  # noqa: BLE001 — timeout, enqueue or
            # transfer-stream failure: blocks must return to the pool
            remote_span.set(error=type(e).__name__)
            self._abandon(req_id)
            if sink is not None:
                await sink.aclose()
            self.stats["remote_errors"] += 1
            self.engine.abort_remote(handle, f"remote prefill failed: {e}")
            yield await handle.seq.out_queue.get()
            return
        finally:
            # the remote leg ends when the delivery future resolves (or
            # fails) — everything after is local scatter/decode work
            remote_span.end()
        # one channel delivered: retire the OTHER channel's pending
        # entry (no-op single-channel) so a late duplicate push into a
        # recycled request id can never land — it discards+acks instead
        self._abandon(req_id)
        if delivery.error:
            self.stats["remote_errors"] += 1
            if sink is not None:
                await sink.aclose()
            self.engine.abort_remote(handle, delivery.error)
            yield await handle.seq.out_queue.get()
            return
        if delivery.streamed:
            self.stats["streamed_deliveries"] += 1
        else:
            self.stats["bulk_deliveries"] += 1
        if sink is not None:
            # the delivery is complete: a STALE concurrent attempt (a
            # visibility-timeout redelivery racing the winner) must not
            # scatter into these pages once they commit and go live for
            # decode — closing the sink turns its late segments into
            # SinkClosed -> discard, and waits out any in-flight scatter
            # before the commit below
            await sink.aclose()
        k_data, v_data = delivery.k_data, delivery.v_data
        my_tp = self.engine.cfg.mesh.tp if self.engine.cfg.mesh else 1
        from ..ops.kv_rearrange import layout_mismatched

        mismatched = k_data is not None and layout_mismatched(
            delivery.head_layout, delivery.src_tp, KV_HEAD_LAYOUT, my_tp
        )
        if mismatched:
            from ..ops.kv_rearrange import rearrange_for_decode

            try:
                # head-axis permutation only — valid on quantized
                # payloads as-is (the block scales are kv-head-free)
                k_data = rearrange_for_decode(
                    k_data, delivery.src_tp, my_tp, delivery.head_layout,
                    KV_HEAD_LAYOUT,
                )
                v_data = rearrange_for_decode(
                    v_data, delivery.src_tp, my_tp, delivery.head_layout,
                    KV_HEAD_LAYOUT,
                )
            except Exception as e:  # noqa: BLE001 — bad peer metadata must
                # not leak the reservation (blocks) or hang the caller
                self.stats["remote_errors"] += 1
                self.engine.abort_remote(handle, f"kv rearrange failed: {e}")
                yield await handle.seq.out_queue.get()
                return
        out_queue = await self.engine.complete_remote(
            handle, delivery.first_token, k_data, v_data,
            first_lp=delivery.first_lp,
            k_scales=delivery.k_scales, v_scales=delivery.v_scales,
        )
        while True:
            out = await out_queue.get()
            if out is None:
                return
            yield out
            if out.is_final():
                return
