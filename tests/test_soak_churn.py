"""Serving soak under churn (VERDICT r4 next #9; ref
lib/runtime/tests/soak.rs:16 — the reference soaks raw transport; this
drives the COMPOSED serving stack).

One durable hub, real JAX engines (tiny model) behind the KV router
with preemption-sized block pools and a host offload tier, a few
thousand streamed requests — while workers leave and join mid-load and
the hub is killed and restarted mid-serving.

With the migration layer (resilience/) wrapped around the routed
engine, the invariant is now *zero client-visible errors*: a churn
wave's in-flight casualties re-dispatch to survivors as prompt +
tokens-so-far instead of erroring, and every stream still terminates
with EXACTLY one finish chunk (zero lost streams, zero duplicated
streams, no token loss or duplication across migration seams).
"""

import asyncio
import itertools
import random

import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.kv_router import KvEventPublisher, KvRouter
from dynamo_tpu.kv_router.router import KvRoutedEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.resilience import MigratingEngine, MigrationPolicy
from dynamo_tpu.runtime import Context, DistributedRuntime
from dynamo_tpu.runtime.hub import HubServer, connect_hub

pytestmark = pytest.mark.slow

BLOCK = 4


def make_engine():
    # 40 blocks of 4 = 160 tokens of pool for up to 4 concurrent
    # sequences of ~32+6 tokens: tight enough that bursts preempt, with
    # a host tier to offload into
    cfg = EngineConfig(
        model=ModelConfig.tiny(), num_blocks=40, block_size=BLOCK,
        max_batch_size=4, max_context=128, prefill_chunk=32,
        host_cache_blocks=64,
    )
    return JaxEngine(cfg, seed=0)


async def spawn_worker(hub_addr):
    store, bus, conn = await connect_hub(hub_addr)
    drt = await DistributedRuntime.from_settings(store=store, bus=bus)
    engine = make_engine()
    comp = drt.namespace("soak").component("worker")
    pub = KvEventPublisher(drt, comp, drt.primary_lease_id)
    pub.attach(engine.kv.allocator)
    await comp.endpoint("gen").serve(
        engine, stats_handler=engine.load_metrics)
    return drt, conn, engine


def make_req(tokens, max_tokens=4):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[511],
    ).to_dict()


def test_soak_serving_churn(run, tmp_path):
    async def main():
        rng = random.Random(7)
        hub = HubServer(data_dir=str(tmp_path / "hub"))
        await hub.start()
        hub_port = int(hub.address.rsplit(":", 1)[1])

        workers = {}  # tag -> (drt, conn, engine)
        for tag in ("w1", "w2"):
            workers[tag] = await spawn_worker(hub.address)

        fs, fb, fconn = await connect_hub(hub.address)
        front = await DistributedRuntime.from_settings(store=fs, bus=fb)
        comp = front.namespace("soak").component("worker")
        client = await comp.endpoint("gen").client().start()
        await client.wait_for_instances(5)
        router = await KvRouter(front, comp, block_size=BLOCK).start()
        # migration enabled: churn must be CLIENT-INVISIBLE — kills
        # re-dispatch in-flight streams to survivors (tokens spliced
        # exactly-once), hub bounces retry transparently
        routed = MigratingEngine(
            KvRoutedEngine(router, client),
            MigrationPolicy(max_migrations=4, deadline_s=60.0),
            client=client,
        )

        # shared prefix pool: exercises router overlap + prefix reuse
        prefixes = [[rng.randrange(100, 500) for _ in range(16)]
                    for _ in range(6)]
        stats = {"done": 0, "errors": 0, "finish_chunks": 0}

        async def one_request(i):
            prompt = (rng.choice(prefixes)
                      + [rng.randrange(100, 500) for _ in range(12)])
            try:
                stream = routed.generate(Context(make_req(prompt)))
                finishes = 0
                async for a in stream:
                    if a.error:
                        # a churn casualty, delivered AS an error — the
                        # legal way for a stream to not finish
                        raise RuntimeError(a.error)
                    if (a.data or {}).get("finish_reason"):
                        finishes += 1
                # exactly-once: one terminal chunk per stream, never
                # more, never silent truncation
                assert finishes == 1, f"req {i}: {finishes} finish chunks"
                stats["finish_chunks"] += finishes
                stats["done"] += 1
            except AssertionError:
                raise
            except Exception:
                stats["errors"] += 1

        counter = itertools.count()

        async def wave(n, concurrency=24):
            sem = asyncio.Semaphore(concurrency)

            async def bounded(i):
                async with sem:
                    await one_request(i)

            await asyncio.gather(*(bounded(next(counter)) for _ in range(n)))

        # ---- calm wave: everything completes, zero errors
        await wave(300)
        assert stats["errors"] == 0 and stats["done"] == 300

        # ---- churn 1: worker leaves mid-load — with migration enabled
        # its in-flight streams must resume on the survivor, error-free
        churn = asyncio.ensure_future(wave(250))
        await asyncio.sleep(0.2)
        drt, conn, _eng = workers.pop("w1")
        await drt.shutdown()
        await conn.close()
        await churn
        assert stats["errors"] == 0, "churn wave 1 leaked client errors"
        for _ in range(100):
            if len(client.instance_ids()) == 1:
                break
            await asyncio.sleep(0.05)
        assert len(client.instance_ids()) == 1

        # ---- calm wave on the survivor
        before_err = stats["errors"]
        await wave(250)
        assert stats["errors"] == before_err

        # ---- churn 2: replacement joins mid-load
        churn = asyncio.ensure_future(wave(250))
        workers["w3"] = await spawn_worker(hub.address)
        await churn
        for _ in range(100):
            if len(client.instance_ids()) == 2:
                break
            await asyncio.sleep(0.05)
        assert len(client.instance_ids()) == 2
        assert workers["w3"][2].stats["requests_total"] > 0  # newcomer took traffic

        # ---- churn 3: the HUB dies and restarts mid-serving (durable
        # store + WAL; clients redial with jittered backoff and the
        # re-established watches emit watch_resumed after reconcile) —
        # dispatches that hit the outage retry on the transient path
        churn = asyncio.ensure_future(wave(200))
        await asyncio.sleep(0.2)
        await hub.close()
        await asyncio.sleep(0.3)
        hub = HubServer(data_dir=str(tmp_path / "hub"), port=hub_port)
        await hub.start()
        await churn
        assert stats["errors"] == 0, "hub-restart wave leaked client errors"

        # ---- final calm wave: the system fully recovered
        before_err = stats["errors"]
        await wave(400)
        assert stats["errors"] == before_err, "errors after hub restart"

        # ---- global invariants: migration makes churn LOSSLESS — every
        # issued request completed, none errored, each exactly once
        issued = next(counter)
        assert stats["errors"] == 0, f"{stats['errors']} client-visible errors"
        assert stats["done"] == issued
        assert stats["finish_chunks"] == stats["done"]  # exactly-once
        # churn actually exercised the migration path (otherwise this
        # soak silently degrades into the calm-wave test)
        assert routed.stats["migrations_total"] >= 1, routed.stats
        assert routed.stats["migration_failures"] == 0, routed.stats
        # preemption pressure actually happened somewhere (the pools are
        # sized for it; a soak that never preempts tests less than it
        # claims) — and every engine drained
        for drt, conn, eng in workers.values():
            assert eng.stats["requests_active"] == 0, "sequences leaked"
            assert eng._n_active == 0
        for drt, conn, eng in workers.values():
            await drt.shutdown()
            await conn.close()
        await front.shutdown()
        await fconn.close()
        await hub.close()

    run(main())
