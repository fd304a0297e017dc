"""KV router tests: prefix index, scheduler cost model, and the full
events -> index -> routing loop with two live JAX workers."""

import asyncio

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.kv_router import (
    KvEventPublisher,
    KvIndexer,
    KvRouter,
    OverlapScores,
    PrefixIndex,
    ProcessedEndpoints,
    RouterEvent,
    WorkerLoad,
)
from dynamo_tpu.kv_router.protocols import KvCacheEvent, StoredBlock
from dynamo_tpu.kv_router.router import KvRoutedEngine
from dynamo_tpu.kv_router.scheduler import AllWorkersBusy, KvScheduler
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, DistributedRuntime, LocalBus, LocalStore, collect


def _hashes(tokens, bs=4):
    return [s for _l, s in sequence_block_hashes(tokens, bs)]


def _stored_event(worker, tokens, bs=4):
    hashes = sequence_block_hashes(tokens, bs)
    blocks = [StoredBlock(block_hash=s, tokens_hash=l) for l, s in hashes]
    return RouterEvent(worker, KvCacheEvent.stored(None, blocks))


# ---------------- index ----------------


def test_index_find_matches_depth():
    idx = PrefixIndex()
    tokens = list(range(16))  # 4 blocks
    idx.apply_event(_stored_event(1, tokens))
    idx.apply_event(_stored_event(2, tokens[:8]))  # worker 2 has 2 blocks

    scores = idx.find_matches(_hashes(tokens))
    assert scores.scores == {1: 4, 2: 2}
    assert scores.total_blocks == 4

    # divergent suffix: only shared prefix counts
    other = tokens[:8] + [99, 98, 97, 96]
    scores = idx.find_matches(_hashes(other))
    assert scores.scores == {1: 2, 2: 2}


def test_index_removed_and_remove_worker():
    idx = PrefixIndex()
    tokens = list(range(16))
    idx.apply_event(_stored_event(1, tokens))
    idx.apply_event(_stored_event(2, tokens))
    h = _hashes(tokens)
    # worker 1 evicts the second block -> its chain depth ends at 1
    idx.apply_event(RouterEvent(1, KvCacheEvent.removed([h[1]])))
    scores = idx.find_matches(h)
    assert scores.scores == {1: 1, 2: 4}
    # worker 2 dies entirely
    idx.remove_worker(2)
    scores = idx.find_matches(h)
    assert scores.scores == {1: 1}


# ---------------- scheduler ----------------


def make_eps(*loads):
    return ProcessedEndpoints([
        WorkerLoad(worker_id=i + 1, kv_active_blocks=int(u * 100), kv_total_blocks=100,
                   active_requests=a, total_slots=8, waiting=w)
        for i, (u, a, w) in enumerate(loads)
    ])


def test_scheduler_prefers_overlap_when_balanced():
    s = KvScheduler()
    eps = make_eps((0.5, 2, 0), (0.5, 2, 0))
    overlaps = OverlapScores(scores={2: 8}, total_blocks=10)
    assert s.select_worker(eps, overlaps, 10) == 2


def test_scheduler_prefers_load_in_balance_mode():
    s = KvScheduler()
    # huge load skew: worker 1 nearly full, worker 2 empty
    eps = make_eps((0.95, 7, 0), (0.05, 0, 0))
    overlaps = OverlapScores(scores={1: 10}, total_blocks=10)
    # balance mode outweighs the perfect overlap on worker 1
    assert s.select_worker(eps, overlaps, 10) == 2


def test_scheduler_avoid_set_soft_excludes():
    s = KvScheduler()
    # worker 1 wins on perfect overlap — but a migrating request that
    # already failed on it (dead, lease not yet expired) must go elsewhere
    eps = make_eps((0.5, 2, 0), (0.5, 2, 0))
    overlaps = OverlapScores(scores={1: 10}, total_blocks=10)
    assert s.select_worker(eps, overlaps, 10, avoid=frozenset({1})) == 2
    s.request_finished(2)
    # soft: when the avoid set covers every candidate, still pick one
    # (lone-worker restart) rather than refuse
    assert s.select_worker(eps, overlaps, 10, avoid=frozenset({1, 2})) in (1, 2)


def test_scheduler_all_busy_and_optimistic_bump():
    s = KvScheduler()
    eps = make_eps((0.5, 8, 3), (0.5, 8, 1))
    with pytest.raises(AllWorkersBusy):
        s.select_worker(eps, OverlapScores(), 4)
    # optimistic bumps spread ties
    eps = make_eps((0.5, 0, 0), (0.5, 0, 0))
    first = s.select_worker(eps, OverlapScores(), 4)
    second = s.select_worker(eps, OverlapScores(), 4)
    assert {first, second} == {1, 2}
    s.request_finished(first)
    s.request_finished(second)


# ---------------- end-to-end: events + metrics + routing ----------------


def make_worker_engine():
    cfg = EngineConfig(
        model=ModelConfig.tiny(), num_blocks=64, block_size=4,
        max_batch_size=4, max_context=128, prefill_chunk=32,
    )
    return JaxEngine(cfg, seed=0)


def make_req(tokens, max_tokens=3):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[511],
    ).to_dict()


def test_kv_routed_serving(run):
    async def main():
        store, bus = LocalStore(), LocalBus()
        front = await DistributedRuntime.from_settings(store=store, bus=bus)
        workers = []
        engines = []
        for _ in range(2):
            w = await DistributedRuntime.from_settings(store=store, bus=bus)
            engine = make_worker_engine()
            comp = w.namespace("dyn").component("worker")
            pub = KvEventPublisher(w, comp, w.primary_lease_id)
            pub.attach(engine.kv.allocator)
            await comp.endpoint("gen").serve(engine, stats_handler=engine.load_metrics)
            workers.append(w)
            engines.append(engine)

        comp = front.namespace("dyn").component("worker")
        client = await comp.endpoint("gen").client().start()
        await client.wait_for_instances(5)
        router = await KvRouter(front, comp, block_size=4).start()
        routed = KvRoutedEngine(router, client)

        prompt = list(range(100, 124))  # 6 blocks of 4
        out1 = await collect(routed.generate(Context(make_req(prompt))))
        assert any((a.data or {}).get("finish_reason") for a in out1)
        # let kv events propagate into the index (generous: box load
        # stretches the bus consumer the same way it stretches scrapes)
        for _ in range(500):
            if router.indexer.events_applied >= 6:
                break
            await asyncio.sleep(0.02)
        assert router.indexer.events_applied >= 6

        # wait for a post-completion stats scrape: on a loaded box the
        # aggregator's last snapshot can still show the cached worker
        # with the finished request active, and the scheduler CORRECTLY
        # prefers the idle worker on that stale view — the property
        # under test is prefix routing between idle workers. Wait on
        # SCRAPES OBSERVED (the aggregator's completion event), not wall
        # time: under 4x-parallel box load the 1s scrape loop stretches
        # arbitrarily and a fixed-duration poll times out while the
        # aggregator simply hasn't run (the PR 5-era flake).
        def _all_idle():
            eps = router.metrics.endpoints
            return (len(eps.loads) == 2
                    and all(l.active_requests == 0 and l.waiting == 0
                            for l in eps.loads))

        for _ in range(30):  # 30 COMPLETED scrapes, not 30 ticks of a clock
            if _all_idle():
                break
            await router.metrics.next_scrape(timeout=30.0)
        assert _all_idle(), (
            f"workers never scraped idle after {router.metrics.scrapes_total}"
            " scrapes"
        )

        # same prompt again: must route to the worker holding the prefix
        scores = router.indexer.find_matches(_hashes(prompt))
        assert len(scores.scores) == 1
        cached_worker = next(iter(scores.scores))
        wid, overlap = await router.schedule(prompt)
        assert wid == cached_worker
        assert overlap >= 5
        router.request_finished(wid)

        # dead-worker cleanup drops its residency from the index
        router.remove_worker(cached_worker)
        assert router.indexer.find_matches(_hashes(prompt)).scores == {}

        for w in workers:
            await w.shutdown()
        await front.shutdown()

    run(main())


# ---------------- prefetch hints ----------------


def test_schedule_emits_prefetch_hint_for_uncovered_prompt(run):
    """Routing a request whose prompt extends past the chosen worker's
    device radix match must ship the block-hash chain on the component's
    kv-prefetch subject; a fully-covered prompt must not."""
    from dynamo_tpu.kv_router.protocols import (
        KV_PREFETCH_SUBJECT,
        KvPrefetchHint,
    )

    async def main():
        store, bus = LocalStore(), LocalBus()
        drt = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = drt.namespace("dyn").component("worker")
        router = await KvRouter(drt, comp, block_size=4).start()
        router.metrics.endpoints = make_eps((0.1, 1, 0))  # worker 1

        sub = bus.subscribe(comp.event_subject(KV_PREFETCH_SUBJECT))
        prompt = list(range(300, 324))  # 6 blocks, index cold
        wid, overlap = await router.schedule(prompt)
        assert wid == 1 and overlap == 0
        msg = await sub.next(1.0)
        assert msg is not None
        hint = KvPrefetchHint.from_bytes(msg.payload)
        assert hint.worker_id == 1
        pairs = sequence_block_hashes(prompt, 4)
        # block-multiple prompt: the final block can never be claimed by
        # admission (it hashes prompt[:-1]), so the hint excludes it
        assert hint.blocks == [[l, s] for l, s in pairs[:-1]]
        router.request_finished(wid)

        # full coverage: worker 1 now holds the whole chain -> no hint
        router.indexer.index.apply_event(_stored_event(1, prompt))
        wid, overlap = await router.schedule(prompt)
        assert wid == 1 and overlap == len(pairs)
        assert await sub.next(0.2) is None
        await drt.shutdown()

    run(main())


def test_prefetch_listener_filters_and_forwards(run):
    """The worker-side listener consumes only hints addressed to it and
    hands the chain to engine.prefetch_hint."""
    from dynamo_tpu.kv_router import KvPrefetchListener
    from dynamo_tpu.kv_router.protocols import (
        KV_PREFETCH_SUBJECT,
        KvPrefetchHint,
    )

    class FakeEngine:
        def __init__(self):
            self.calls = []

        async def prefetch_hint(self, blocks):
            self.calls.append(blocks)
            return len(blocks)

    async def main():
        store, bus = LocalStore(), LocalBus()
        drt = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = drt.namespace("dyn").component("worker")
        eng = FakeEngine()
        listener = await KvPrefetchListener(drt, comp, 42, eng).start()
        subject = comp.event_subject(KV_PREFETCH_SUBJECT)
        bus.publish(subject, KvPrefetchHint(99, [[1, 2]]).to_bytes())
        bus.publish(subject, KvPrefetchHint(42, [[3, 4], [5, 6]]).to_bytes())
        for _ in range(100):
            if eng.calls:
                break
            await asyncio.sleep(0.01)
        assert eng.calls == [[(3, 4), (5, 6)]]
        assert listener.hints_received == 1
        assert listener.blocks_prefetched == 2
        await listener.close()
        await drt.shutdown()

    run(main())
