"""Worker-side KV event publication + router-side metrics aggregation.

Re-design of lib/llm/src/kv_router/{publisher,metrics_aggregator,scoring}.rs:

  * :class:`KvEventPublisher` — hooks the engine's BlockAllocator
    stored/removed/demoted callbacks (and the offload tier's last-tier
    drop queue) and publishes RouterEvents on the component's
    ``kv_events`` subject,
  * :class:`KvPrefetchListener` — the other direction: consumes the
    router's ``kv-prefetch`` hints addressed to this worker, pulls
    peer-held prefix continuations over the transfer plane when the
    hint names a deeper peer (fleet prefix cache), and hands the
    block-hash chain to the engine's host-tier prefetch
    (engine.prefetch_hint), so restores start before requests arrive,
  * :class:`KvPeerServer` — the serve side of those pulls: answers
    ``kv-peer-fetch`` requests addressed to this worker by pushing the
    chain's host/disk-resident blocks to the requester's connect-back
    address (disagg/transfer.py framing + ack),
  * :class:`KvMetricsAggregator` — periodically scrapes every worker
    instance's stats endpoint (the engine's ``load_metrics``) into
    :class:`ProcessedEndpoints` for the scheduler.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import uuid
from typing import Optional

import numpy as np

from .protocols import (
    KV_EVENT_SUBJECT,
    KV_PEER_FETCH_SUBJECT,
    KV_PREFETCH_SUBJECT,
    KvCacheEvent,
    KvPeerFetchRequest,
    KvPrefetchHint,
    RouterEvent,
    StoredBlock,
)
from .scheduler import ProcessedEndpoints, WorkerLoad

logger = logging.getLogger(__name__)

#: wall bound on one peer prefix pull (bus negotiation + TCP push):
#: past this the hinted request is probably already being served, so
#: the puller abandons the delivery and lets admission recompute
PEER_PULL_TIMEOUT_S = 20.0


class KvEventPublisher:
    """ref publisher.rs:33-73."""

    def __init__(self, drt, component, worker_id: int):
        self.drt = drt
        self.subject = component.event_subject(KV_EVENT_SUBJECT)
        self.worker_id = worker_id
        self._ids = itertools.count(1)

    def publish(self, event: KvCacheEvent) -> None:
        ev = RouterEvent(self.worker_id, event, next(self._ids))
        self.drt.bus.publish(self.subject, ev.to_bytes())

    # -- allocator callback adapters --
    def on_stored(self, block, parent_hash: Optional[int]) -> None:
        self.publish(
            KvCacheEvent.stored(
                parent_hash,
                [StoredBlock(block_hash=block.seq_hash, tokens_hash=block.local_hash)],
            )
        )

    def on_removed(self, block_hashes: list[int]) -> None:
        self.publish(KvCacheEvent.removed(block_hashes))

    def on_demoted(self, block_hashes: list[int]) -> None:
        self.publish(KvCacheEvent.demoted(block_hashes))

    def attach(self, allocator, offload=None) -> None:
        """Wire the allocator's events; with an ``offload`` manager the
        residency story becomes tiered: device evictions publish
        ``demoted`` (the worker still holds the KV, one tier down —
        the router keeps the radix entry, which is what lets peers pull
        it), and the true ``removed`` fires from the offload manager's
        last-tier drop queue (OffloadManager.flush_dropped)."""
        allocator.on_stored = self.on_stored
        allocator.on_removed = self.on_removed
        if offload is not None:
            allocator.on_demoted = self.on_demoted
            offload.on_dropped = self.on_removed


class KvPrefetchListener:
    """Worker-side prefetch-hint consumer: subscribes the component's
    ``kv-prefetch`` subject, filters hints addressed to this worker, and
    drives the engine's router-hinted host-tier prefetch. Hints are
    advisory — any failure is logged and dropped (the request still
    serves correctly, it just pays the cold restore).

    Fleet prefix cache: a hint naming a ``peer_worker_id`` whose chain
    runs deeper than this worker's local coverage triggers a peer pull
    first — a ``kv-peer-fetch`` negotiation on the bus answered by the
    peer pushing the blocks to this listener's transfer server, landed
    in the HOST tier, then promoted to device by the very same
    ``engine.prefetch_hint`` restore that serves locally-offloaded
    chains. Every failure mode (peer dead, timeout, partial serve,
    miss) degrades to exactly what would have happened without the
    peer: recompute."""

    def __init__(self, drt, component, worker_id: int, engine,
                 transfer=None, peer_pull: bool = True,
                 pull_timeout: float = PEER_PULL_TIMEOUT_S):
        self.drt = drt
        self.subject = component.event_subject(KV_PREFETCH_SUBJECT)
        self.fetch_subject = component.event_subject(KV_PEER_FETCH_SUBJECT)
        self.worker_id = worker_id
        self.engine = engine
        self.hints_received = 0
        self.blocks_prefetched = 0
        self.peer_pulls = 0
        self.peer_pull_blocks = 0
        self.peer_pull_failures = 0
        # PRESERVE-style weight pre-stage (hint.model): requests
        # forwarded to the engine hook, and failures swallowed there —
        # a broken pre-stage must never cost the KV prefetch
        self.prestage_requests = 0
        self.prestage_failures = 0
        self.pull_timeout = pull_timeout
        self.peer_pull = peer_pull
        # connect-back target for peer pushes: the disagg decode role
        # shares its existing KvTransferServer; otherwise the listener
        # owns a lightweight one, started lazily with it
        self._transfer = transfer
        self._own_transfer = False
        self._task: Optional[asyncio.Task] = None
        self._sub = None
        # one task per hint: a dead peer's pull waits out its timeout
        # WITHOUT head-of-line blocking every later hint's restore (the
        # same hazard KvPeerServer spawns per serve for). Pulls beyond
        # the cap skip the peer and go straight to the local restore;
        # the restores themselves serialize (one h2d pipe, and the
        # engine's prefetch path was written for one caller at a time)
        self._hint_tasks: set[asyncio.Task] = set()
        self._restore_lock = asyncio.Lock()
        self._active_pulls = 0
        self.max_concurrent_pulls = 8

    def _pull_ready(self) -> bool:
        off = getattr(self.engine, "offload", None)
        return (
            self.peer_pull
            and self._transfer is not None
            and off is not None
            and off.mirror is None
        )

    async def start(self) -> "KvPrefetchListener":
        off = getattr(self.engine, "offload", None)
        if (
            self.peer_pull
            and self._transfer is None
            and off is not None
            and off.mirror is None  # same gate as _pull_ready: a mirror
            # engine never pulls, so don't bind a dead connect-back
            # socket + server task per mirror worker
        ):
            from ..disagg.transfer import KvTransferServer

            self._transfer = KvTransferServer()
            await self._transfer.start()
            self._own_transfer = True
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
        if self._task is not None:
            self._task.cancel()
        for t in list(self._hint_tasks):
            t.cancel()
        if self._own_transfer and self._transfer is not None:
            await self._transfer.close()

    async def _consume(self, sub) -> None:
        async for msg in sub:
            try:
                hint = KvPrefetchHint.from_bytes(msg.payload)
                if hint.worker_id != self.worker_id:
                    continue
                self.hints_received += 1
                t = asyncio.get_running_loop().create_task(
                    self._handle_hint(hint)
                )
                self._hint_tasks.add(t)
                t.add_done_callback(self._hint_tasks.discard)
            except Exception:  # noqa: BLE001 — hints are advisory
                logger.debug("prefetch hint failed", exc_info=True)

    async def _handle_hint(self, hint: KvPrefetchHint) -> None:
        try:
            if hint.model:
                # fire-and-forget, never awaited inline: a SLOW weight
                # pre-stage (the whole point once multi-model staging is
                # real) must not delay the prefix restore it rides with,
                # and a failing/fault-killed one is swallowed inside
                # _pre_stage — either way the KV work below is unaffected
                t = asyncio.get_running_loop().create_task(
                    self._pre_stage(hint.model)
                )
                self._hint_tasks.add(t)
                t.add_done_callback(self._hint_tasks.discard)
            blocks = [(l, s) for l, s in hint.blocks]
            if (
                hint.peer_worker_id is not None
                and self._pull_ready()
                # gate on PULLS in flight, not hint tasks — peer-less
                # hints and tasks merely queued on the restore lock must
                # not lock later hints out of their pulls
                and self._active_pulls < self.max_concurrent_pulls
            ):
                self._active_pulls += 1
                try:
                    await self._maybe_pull(hint, blocks)
                finally:
                    self._active_pulls -= 1
            async with self._restore_lock:
                n = await self.engine.prefetch_hint(blocks)
            self.blocks_prefetched += n
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — hints are advisory
            logger.debug("prefetch hint failed", exc_info=True)

    async def _pre_stage(self, model: str) -> None:
        """PRESERVE-style weight pre-stage: the hint named the model the
        routed request will run, so staging its weights can start before
        the request arrives — resolved through the engine's
        ``pre_stage_weights`` hook, which stages the adapter's A/B
        stacks into a device slot (engine/adapters.py) so the request
        lands on a warm adapter instead of paying the cold-load stall
        inline. Best-effort end to end, with its own faultpoint so
        tests can prove a dead pre-stage never takes the KV prefetch
        down with it."""
        from ..resilience import faultpoints

        self.prestage_requests += 1
        try:
            await faultpoints.hit("pre_stage_weights", model=model)
            fn = getattr(self.engine, "pre_stage_weights", None)
            if fn is not None:
                await fn(model)
        except asyncio.CancelledError:
            raise
        except Exception:  # noqa: BLE001 — advisory, like the hint
            self.prestage_failures += 1
            logger.debug("weight pre-stage for %r failed", model,
                         exc_info=True)

    async def _maybe_pull(self, hint: KvPrefetchHint, blocks: list) -> None:
        """One peer prefix pull: size the remote tail from local
        coverage, negotiate over the bus, await the transfer-plane
        delivery, and land it in the host tier. Best-effort throughout."""
        chain = [s for _l, s in blocks]
        cov = self.engine.chain_coverage(chain)
        if cov >= min(hint.peer_blocks, len(chain)):
            return  # local tiers already cover what the peer offers
        tail = chain[cov:]
        request_id = f"peer-pull-{uuid.uuid4().hex}"
        fut = self._transfer.expect(request_id)
        from ..disagg.transfer import KV_QUANT_WIRE_VERSION

        req = KvPeerFetchRequest(
            peer_worker_id=hint.peer_worker_id,
            src_worker_id=self.worker_id,
            request_id=request_id,
            hashes=tail,
            connection=self._transfer.address.to_dict(),
            # this puller dequantizes (or re-quantizes to its own mode)
            # on landing, so it always accepts the quantized wire shape
            accept_quant=KV_QUANT_WIRE_VERSION,
        )
        self.peer_pulls += 1
        import time as _time

        t0 = _time.monotonic()
        try:
            self.drt.bus.publish(self.fetch_subject, req.to_bytes())
            delivery = await asyncio.wait_for(fut, self.pull_timeout)
        except Exception:  # noqa: BLE001 — dead peer / timeout / bus
            # trouble: the request recomputes, exactly as if the peer
            # never existed. The pending future is abandoned so a
            # stale late push can't land into a recycled request id.
            self.peer_pull_failures += 1
            self._transfer.abandon(request_id)
            logger.debug("peer pull %s failed; falling back to recompute",
                         request_id, exc_info=True)
            return
        if delivery.error or not delivery.hashes or delivery.k_data is None:
            self.peer_pull_failures += 1
            return
        # transfer-cost calibration: the pull's measured wall + bytes
        # feed the engine's "peer" link-class estimate — this is the
        # number the router prices this worker's future pulls with
        cost = getattr(self.engine, "cost", None)
        if cost is not None and delivery.k_data is not None:
            cost.observe(
                "peer",
                delivery.k_data.nbytes + delivery.v_data.nbytes,
                max(_time.monotonic() - t0, 1e-9),
            )
        served = [int(h) for h in delivery.hashes]
        if served != tail[: len(served)]:
            # a peer whose probe drifted from the request must not park
            # mislabeled KV in the content-addressed pool
            self.peer_pull_failures += 1
            logger.warning("peer pull %s returned a mismatched chain",
                           request_id)
            return
        # regroup (a whole-stack head-axis permutation copy) AND the
        # per-block landing copies are multi-MB host work: one executor
        # hop for both — neither belongs on the serving loop
        try:
            n = await asyncio.get_running_loop().run_in_executor(
                None, self._regroup_and_land, delivery, served
            )
        except Exception:  # noqa: BLE001 — bad peer metadata
            self.peer_pull_failures += 1
            logger.warning("peer pull %s regroup/landing failed", request_id,
                           exc_info=True)
            return
        self.peer_pull_blocks += n

    def _regroup_and_land(self, delivery, served: list) -> int:
        """Executor thread: permute a foreign kv-head ordering (same
        shared rule as the disagg delivery paths — ops/kv_rearrange.
        layout_mismatched) and park the chain in the host staging
        area. A quantized delivery regroups as-is (the codec's scales
        are kv-head-free) and lands with its scale arrays — the
        landing normalizes it to THIS worker's codec mode."""
        from ..models.llama import KV_HEAD_LAYOUT
        from ..ops.kv_rearrange import layout_mismatched, rearrange_for_decode

        k, v = delivery.k_data, delivery.v_data
        my_tp = self.engine.cfg.mesh.tp if self.engine.cfg.mesh else 1
        if layout_mismatched(
            delivery.head_layout, delivery.src_tp, KV_HEAD_LAYOUT, my_tp
        ):
            k = rearrange_for_decode(
                k, delivery.src_tp, my_tp, delivery.head_layout, KV_HEAD_LAYOUT
            )
            v = rearrange_for_decode(
                v, delivery.src_tp, my_tp, delivery.head_layout, KV_HEAD_LAYOUT
            )
        return self.engine.offload.land_peer_chain(
            served, k, v,
            k_scales=delivery.k_scales, v_scales=delivery.v_scales,
        )


class KvPeerServer:
    """Serve side of the fleet prefix cache: consumes ``kv-peer-fetch``
    requests addressed to this worker and answers each by pushing the
    requested chain's host/disk-resident blocks to the requester's
    transfer server — the same bulk framing, layer-chunked frames and
    end-to-end ack as the disagg KV handoff (disagg/transfer.py). A
    total miss answers with an error delivery so the requester falls
    back immediately instead of waiting out its pull timeout. Serving
    is non-destructive (export reads, never takes), so a requester
    dying mid-pull leaves this worker's tiers untouched."""

    def __init__(self, drt, component, worker_id: int, engine,
                 layer_chunk: int = 4):
        self.drt = drt
        self.subject = component.event_subject(KV_PEER_FETCH_SUBJECT)
        self.worker_id = worker_id
        self.engine = engine
        self.layer_chunk = layer_chunk
        self.fetches_received = 0
        self.blocks_served = 0
        self.misses = 0
        self.serve_errors = 0
        self.serve_rejects = 0
        self._task: Optional[asyncio.Task] = None
        self._sub = None
        self._serves: set[asyncio.Task] = set()
        # a hint storm naming this worker for a hot shared prefix must
        # not stack unbounded concurrent exports (each one np.stacks a
        # multi-MB..GB KV run on the executor) — the puller side caps
        # its fan-out the same way (max_concurrent_pulls)
        self.max_concurrent_serves = 8
        # per-fetch bound on the DEVICE-tier d2h export: a serve must
        # never turn into an unbounded HBM drain under the device lock
        # (the concurrency cap above bounds the fan-out; this bounds
        # each serve's burst)
        self.max_d2h_blocks = 128

    async def start(self) -> "KvPeerServer":
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
        if self._task is not None:
            self._task.cancel()
        for t in list(self._serves):
            t.cancel()

    async def _consume(self, sub) -> None:
        async for msg in sub:
            try:
                req = KvPeerFetchRequest.from_bytes(msg.payload)
                if req.peer_worker_id != self.worker_id:
                    continue
                self.fetches_received += 1
                if len(self._serves) >= self.max_concurrent_serves:
                    # over the export cap: answer busy so the puller
                    # falls back to recompute NOW instead of waiting
                    # out its pull timeout
                    self.serve_rejects += 1
                    t = asyncio.get_running_loop().create_task(
                        self._reject(req)
                    )
                else:
                    # one task per serve: a slow requester link must not
                    # head-of-line block other peers' pulls
                    t = asyncio.get_running_loop().create_task(
                        self._serve(req)
                    )
                self._serves.add(t)
                t.add_done_callback(self._serves.discard)
            except Exception:  # noqa: BLE001 — fetches are advisory
                logger.debug("bad kv-peer-fetch request", exc_info=True)

    async def _reject(self, req: KvPeerFetchRequest) -> None:
        from ..disagg.transfer import send_kv_blocks

        try:
            await send_kv_blocks(
                req.connection, req.request_id, -1, None, None,
                error="peer-busy",
            )
        except Exception:  # noqa: BLE001 — the puller's timeout covers us
            logger.debug("peer-busy notify %s failed", req.request_id,
                         exc_info=True)

    async def _serve(self, req: KvPeerFetchRequest) -> None:
        from ..disagg.transfer import send_kv_blocks
        from ..models.llama import KV_HEAD_LAYOUT
        from ..resilience import faultpoints

        try:
            # deterministic worker-death injection for the mid-pull
            # crash tests: a kill here is a peer dying before (or
            # instead of) the push — no data, no ack, the puller's
            # timeout degrades it to recompute
            await faultpoints.hit("mid_peer_serve", request_id=req.request_id)
            off = getattr(self.engine, "offload", None)
            hashes, k, v = ([], None, None)
            ks = vs = None
            # serve at the stored codec's width only when the puller
            # advertised the capability (tolerant default 0 = legacy
            # puller = full-width bytes; the negotiation matrix of
            # docs/kv_offload.md). Without a host tier the DEVICE
            # cache's own codec (int8-with-scales) is the stored codec.
            dev_q = (
                "int8"
                if getattr(self.engine, "k_scales", None) is not None
                else "none"
            )
            serve_q = (
                (off.kv_quant if off is not None else dev_q)
                if req.accept_quant >= 1
                else "none"
            )
            # device tier first: chains living ONLY in HBM used to be
            # invisible to the fleet prefix cache — a bounded,
            # non-destructive d2h export (engine device lock + executor
            # hop) serves the hottest tier too; the host/disk export
            # continues the run past the device-resident prefix
            export_dev = getattr(self.engine, "export_device_chain", None)
            dks = dvs = None
            if export_dev is not None:
                hashes, k, v, dks, dvs = await export_dev(
                    req.hashes, max_blocks=self.max_d2h_blocks
                )
            if off is not None:

                def _export_and_merge(k=k, v=v, dks=dks, dvs=dvs,
                                      hashes=tuple(hashes)):
                    # executor thread: the lower-tier export, the
                    # device run's wire quantize, and the multi-MB
                    # merge all stay off the event loop
                    from ..engine import kvquant as _kvq

                    tail = req.hashes[len(hashes):]
                    ks = vs = None
                    if dks is not None and hashes:
                        # int8 DEVICE-codec export: ship verbatim when
                        # the negotiated wire codec matches; otherwise
                        # re-encode (the counted bounce — what used to
                        # happen silently on every device serve)
                        if serve_q == "int8":
                            ks, vs = dks, dvs
                        else:
                            k, v = _kvq.dequantize_stack(
                                k, v, dks, dvs, self.engine.cfg.model.dtype
                            )
                            self.engine.note_export_requant(len(hashes))
                            if serve_q != "none":
                                k, v, ks, vs = _kvq.quantize_stack(
                                    k, v, serve_q
                                )
                    elif serve_q != "none" and hashes:
                        k, v, ks, vs = _kvq.quantize_stack(k, v, serve_q)
                    h2, k2, v2, ks2, vs2 = off.export_chain_q(
                        list(tail), quant_ok=serve_q != "none"
                    )
                    if not h2:
                        return list(hashes), k, v, ks, vs
                    if hashes:
                        k = np.concatenate([k, k2], axis=2)
                        v = np.concatenate([v, v2], axis=2)
                        if ks2 is not None:
                            ks = np.concatenate([ks, ks2], axis=1)
                            vs = np.concatenate([vs, vs2], axis=1)
                        return list(hashes) + h2, k, v, ks, vs
                    return h2, k2, v2, ks2, vs2

                hashes, k, v, ks, vs = (
                    await asyncio.get_running_loop().run_in_executor(
                        None, _export_and_merge
                    )
                )
            elif dks is not None and hashes:
                # no host tier: the device-codec export ships verbatim
                # to a quant-capable puller, or dequantizes (counted)
                # for a legacy one
                if serve_q == "int8":
                    ks, vs = dks, dvs
                else:

                    def _dequant(k=k, v=v):
                        from ..engine import kvquant as _kvq

                        self.engine.note_export_requant(len(hashes))
                        return _kvq.dequantize_stack(
                            k, v, dks, dvs, self.engine.cfg.model.dtype
                        )

                    k, v = await asyncio.get_running_loop().run_in_executor(
                        None, _dequant
                    )
            if not hashes:
                self.misses += 1
                await send_kv_blocks(
                    req.connection, req.request_id, -1, None, None,
                    error="peer-miss",
                )
                return
            await send_kv_blocks(
                req.connection, req.request_id, -1, k, v,
                layer_chunk=self.layer_chunk,
                head_layout=KV_HEAD_LAYOUT,
                src_tp=self.engine.cfg.mesh.tp if self.engine.cfg.mesh else 1,
                hashes=hashes,
                kv_quant=serve_q if ks is not None else "none",
                k_scales=ks, v_scales=vs,
            )
            self.blocks_served += len(hashes)
        except Exception:  # noqa: BLE001 — serving is best-effort: the
            # puller's timeout covers us, and a FaultInjected kill must
            # look exactly like a crashed peer (no ack, no retry)
            self.serve_errors += 1
            logger.debug("peer serve %s for worker %x failed",
                         req.request_id, req.src_worker_id, exc_info=True)


class KvMetricsAggregator:
    """ref metrics_aggregator.rs:27-109 collect_endpoints_task."""

    def __init__(self, drt, component, interval: float = 1.0):
        self.drt = drt
        self.component = component
        self.interval = interval
        self.endpoints = ProcessedEndpoints([])
        # last-known load per instance: a worker that misses one scrape
        # window (1s stats timeout on a starved box) keeps its previous
        # snapshot — with its ORIGINAL ts, so the scheduler's load_ttl_s
        # ages it out if it stays silent — instead of vanishing from the
        # routing view for a tick. Departed workers (discovery key gone)
        # still drop immediately.
        self._known: dict[int, WorkerLoad] = {}
        self._task: Optional[asyncio.Task] = None
        #: completed-scrape signal: counter + waiter futures resolved at
        #: the end of every _collect_once. Lets tests (and watchers)
        #: synchronize on SCRAPES OBSERVED rather than wall time — on a
        #: starved box the scrape loop stretches, and a fixed-duration
        #: poll times out while the aggregator has simply not run yet
        #: (the test_kv_routed_serving flake, CHANGES.md PR 5).
        self.scrapes_total = 0
        self._scrape_waiters: list[asyncio.Future] = []

    async def start(self) -> "KvMetricsAggregator":
        await self._collect_once()
        self._task = self.drt.runtime.spawn(self._loop())
        return self

    async def next_scrape(self, timeout: Optional[float] = None) -> int:
        """Resolve after the NEXT completed scrape (an event, not a
        timer); returns the new ``scrapes_total``. With ``timeout``,
        falls through after that many seconds even if no scrape landed —
        callers decide whether a starved loop is an error."""
        fut = asyncio.get_running_loop().create_future()
        self._scrape_waiters.append(fut)
        try:
            await asyncio.wait_for(fut, timeout)
        except asyncio.TimeoutError:
            pass
        finally:
            if fut in self._scrape_waiters:
                self._scrape_waiters.remove(fut)
        return self.scrapes_total

    async def _loop(self) -> None:
        while True:
            await asyncio.sleep(self.interval)
            try:
                await self._collect_once()
            except Exception:  # noqa: BLE001
                logger.exception("metrics scrape failed")

    async def _collect_once(self) -> None:
        import time as _time

        stats = await self.component.scrape_stats(include_missing=True)
        now = _time.monotonic()
        merged: dict[int, WorkerLoad] = {}
        for s in stats:
            d = s.get("data")
            if d is None:
                # discovered but slow: retain the last-known load (stale
                # ts and all) rather than dropping a live worker
                prev = self._known.get(s["instance_id"])
                if prev is not None:
                    merged[s["instance_id"]] = prev
                continue
            # ts stamped at scrape time: the scheduler ages these out
            # (load_ttl_s) instead of trusting a dead worker's last
            # report forever
            merged[s["instance_id"]] = WorkerLoad.from_stats(
                s["instance_id"], d, ts=now
            )
        self._known = merged
        self.endpoints = ProcessedEndpoints(list(merged.values()))
        self.scrapes_total += 1
        waiters, self._scrape_waiters = self._scrape_waiters, []
        for fut in waiters:
            if not fut.done():
                fut.set_result(self.scrapes_total)
