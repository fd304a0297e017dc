"""Reshard churn soak (ISSUE 12 acceptance): a routed two-worker pool
serves concurrent greedy waves while the pool's parallelism degree
morphs underneath them — grow TP=1→2 mid-wave, shrink back, then a
`mid_reshard` kill on one worker mid-morph — asserting

  * zero client-visible errors across every wave,
  * exactly-once delivery (one finish chunk per stream, none lost),
  * every stream bit-identical to an unmorphed single-engine reference,
  * the kill's casualties (in-flight AND newly-routed requests on the
    dead worker) resume on the survivor via the PR 4 migration path.

The control path is the real one end to end: MorphDecisions publish on
the ``reshard`` bus subject and each worker's ReshardListener actuates
them (pool-wide and targeted), exactly as dynamo_run wires it.
"""

import asyncio
import itertools
import random

import jax
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.kv_router import KvEventPublisher, KvRouter
from dynamo_tpu.kv_router.router import KvRoutedEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.planner import MorphDecision, PLANNER_RESHARD_SUBJECT
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.resilience import (
    MigratingEngine, MigrationPolicy, ReshardListener, faultpoints,
)
from dynamo_tpu.runtime import Context, DistributedRuntime, LocalBus, LocalStore

pytestmark = pytest.mark.faultinject

BLOCK = 4
TINY = ModelConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.key(0))
MAX_TOKENS = 6


def make_engine():
    cfg = EngineConfig(
        model=TINY, num_blocks=48, block_size=BLOCK, max_batch_size=4,
        max_context=128, prefill_chunk=32,
    )
    return JaxEngine(cfg, params=PARAMS, seed=0)


def make_req(tokens):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=MAX_TOKENS,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[511],
    ).to_dict()


def test_reshard_soak_morphs_mid_wave(run):
    async def main():
        rng = random.Random(12)
        store, bus = LocalStore(), LocalBus()

        workers = []  # (drt, engine, listener)
        for _ in range(2):
            w = await DistributedRuntime.from_settings(store=store, bus=bus)
            engine = make_engine()
            comp = w.namespace("soak").component("worker")
            KvEventPublisher(w, comp, w.primary_lease_id).attach(
                engine.kv.allocator
            )
            listener = await ReshardListener(
                w, comp, w.primary_lease_id, engine
            ).start()
            await comp.endpoint("gen").serve(
                engine, stats_handler=engine.load_metrics
            )
            workers.append((w, engine, listener))

        front = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = front.namespace("soak").component("worker")
        client = await comp.endpoint("gen").client().start()
        await client.wait_for_instances(5)
        router = await KvRouter(front, comp, block_size=BLOCK).start()
        routed = MigratingEngine(
            KvRoutedEngine(router, client),
            # budget sized for the kill window: until the victim's lease
            # drops, saturated-fallback round robin can bounce a
            # re-dispatch off the corpse a few times before landing
            MigrationPolicy(max_migrations=8, deadline_s=60.0),
            client=client,
        )
        reshard_subject = comp.event_subject(PLANNER_RESHARD_SUBJECT)

        # prompt pool + unmorphed reference streams (greedy, so one
        # reference engine defines the expected tokens per prompt)
        prefixes = [[rng.randrange(100, 500) for _ in range(16)]
                    for _ in range(5)]
        prompts = [tuple(rng.choice(prefixes)
                         + [rng.randrange(100, 500) for _ in range(8)])
                   for _ in range(12)]
        ref_engine = make_engine()
        reference = {}
        for p in prompts:
            toks = []
            async for out in ref_engine.generate(Context(make_req(p))):
                toks.extend(out.token_ids or [])
            reference[p] = toks
        await ref_engine.close()

        stats = {"done": 0, "errors": 0, "finish_chunks": 0,
                 "mismatches": 0}

        async def one_request(i):
            prompt = prompts[i % len(prompts)]
            try:
                toks, finishes = [], 0
                async for a in routed.generate(Context(make_req(prompt))):
                    if a.error:
                        raise RuntimeError(a.error)
                    d = a.data or {}
                    toks.extend(d.get("token_ids") or [])
                    if d.get("finish_reason"):
                        finishes += 1
                assert finishes == 1, f"req {i}: {finishes} finish chunks"
                stats["finish_chunks"] += finishes
                if toks != reference[prompt]:
                    stats["mismatches"] += 1
                stats["done"] += 1
            except AssertionError:
                raise
            except Exception:
                stats["errors"] += 1

        counter = itertools.count()

        async def wave(n, concurrency=12):
            sem = asyncio.Semaphore(concurrency)

            async def bounded(i):
                async with sem:
                    await one_request(i)

            await asyncio.gather(*(bounded(next(counter)) for _ in range(n)))

        def morphed(tp):
            return all(
                (e.cfg.mesh.tp if e.cfg.mesh else 1) == tp
                for _w, e, _l in workers
            )

        # ---- calm wave on TP=1
        await wave(24)
        assert stats["errors"] == 0 and stats["mismatches"] == 0

        # ---- grow mid-wave: pool-wide MorphDecision, both workers
        # morph TP=1 -> TP=2 under live load, streams held
        grow = asyncio.ensure_future(wave(30))
        await asyncio.sleep(0.1)
        bus.publish(reshard_subject, MorphDecision(
            worker_id=0, tp=2, reason="grow_tp").to_bytes())
        await grow
        assert stats["errors"] == 0, "grow wave leaked client errors"
        assert stats["mismatches"] == 0, "grow wave broke greedy streams"
        for _ in range(200):
            if morphed(2):
                break
            await asyncio.sleep(0.05)
        assert morphed(2), "pool never reached TP=2"

        # ---- shrink mid-wave back to TP=1
        shrink = asyncio.ensure_future(wave(30))
        await asyncio.sleep(0.1)
        bus.publish(reshard_subject, MorphDecision(
            worker_id=0, tp=1, reason="shrink_tp").to_bytes())
        await shrink
        assert stats["errors"] == 0, "shrink wave leaked client errors"
        assert stats["mismatches"] == 0
        for _ in range(200):
            if morphed(1):
                break
            await asyncio.sleep(0.05)
        assert morphed(1), "pool never shrank back to TP=1"

        # ---- kill one worker MID-MORPH (quiesced phase = hit 2 of a
        # targeted morph): its loop dies like any crash — in-flight
        # streams and later dispatches migrate to the survivor
        victim_drt, victim_engine, _vl = workers[0]
        faultpoints.arm("mid_reshard", "kill", after=2, times=1)
        kill_wave = asyncio.ensure_future(wave(60, concurrency=16))
        # the kill's migration assertion needs CASUALTIES, and the
        # 6-token streams are fast enough that the victim can fully
        # drain between "it has work" and the kill at the commit
        # boundary. Deterministic version (same pattern as the
        # resilience kill matrix): hold the victim's device lock so its
        # streams CANNOT advance, wait until it demonstrably holds
        # work, morph it over the bus, and release only once the morph
        # is posted — the commit-boundary kill then provably catches
        # those streams in flight and the migration layer must carry
        # them to the survivor
        while True:
            for _ in range(3000):
                if victim_engine._n_active > 0:
                    break
                await asyncio.sleep(0.01)
            assert victim_engine._n_active > 0, "victim never got work"
            await victim_engine._device_lock.acquire()
            if victim_engine._n_active > 0:
                break  # lock held, streams frozen mid-flight
            victim_engine._device_lock.release()
        try:
            bus.publish(reshard_subject, MorphDecision(
                worker_id=victim_drt.primary_lease_id, tp=2,
                reason="grow_tp").to_bytes())
            for _ in range(6000):
                if victim_engine._reshard_req is not None:
                    break
                await asyncio.sleep(0.01)
            assert victim_engine._reshard_req is not None, \
                "morph never posted"
        finally:
            victim_engine._device_lock.release()
        # a real crash takes the worker's LEASE with it; model that by
        # dropping the victim from discovery the moment it dies —
        # otherwise the corpse squats in the routing view forever (a
        # state no real deployment sustains) and saturated-fallback
        # round robin ping-pongs re-dispatches into it until their
        # migration budgets exhaust
        # generous: publish→listener→stage→quiesce→kill competes with
        # the 60-request wave for CPU; a loaded box stretches it well
        # past the calm-run ~1s
        for _ in range(3000):
            if victim_engine._dead is not None:
                break
            await asyncio.sleep(0.02)
        assert victim_engine._dead is not None, "the kill never fired"
        await victim_drt.shutdown()
        await kill_wave
        faultpoints.reset()
        assert stats["errors"] == 0, "kill wave leaked client errors"
        assert stats["mismatches"] == 0, "migrated streams not bit-exact"
        # the dead worker stays wholly on its pre-morph layout
        assert victim_engine.mesh is None and victim_engine.cfg.mesh is None
        assert routed.stats["migrations_total"] >= 1, routed.stats
        assert routed.stats["migration_failures"] == 0, routed.stats

        # ---- final calm wave on the survivor
        await wave(24)
        assert stats["errors"] == 0

        # ---- global invariants: lossless, exactly-once
        issued = next(counter)
        assert stats["done"] == issued
        assert stats["finish_chunks"] == stats["done"]
        # the survivor really morphed during the soak
        _w1, survivor, _l1 = workers[1]
        assert survivor.stats["resharded_total"] >= 2

        for w, e, l in workers:
            await l.close()
            await e.close()
            if w is not victim_drt:  # the victim already shut down
                await w.shutdown()
        await front.shutdown()

    run(main())
