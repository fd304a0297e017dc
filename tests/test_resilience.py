"""Resilient serving: migration, graceful drain, fault injection.

The fault-point matrix is the subsystem's acceptance test: a worker
killed at each request-lifecycle stage (admission, mid-prefill,
mid-decode) must yield a client stream that CONTINUES on a surviving
worker to a single finish chunk, with the greedy token sequence
bit-exact against an unkilled reference run — no token lost, none
duplicated across the seam. Alongside it: resume-annotation continuity
(seeded sampling + penalties), graceful drain (finish and hand-off
flavors), the drain coordinator sequence, the hub watch_resumed marker,
and disagg prefill redelivery under a mid-transfer kill.
"""

import asyncio

import pytest

from dynamo_tpu.disagg.protocols import RemotePrefillRequest
from dynamo_tpu.disagg.queue import PrefillQueue
from dynamo_tpu.disagg.worker import PrefillWorker
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.resilience import (
    MIGRATION_SIGNAL,
    DrainCoordinator,
    FailureKind,
    FaultInjected,
    MigratingEngine,
    MigrationPolicy,
    classify_failure,
    faultpoints,
)
from dynamo_tpu.runtime import (
    Annotated,
    AsyncEngine,
    Context,
    DistributedRuntime,
    EngineClient,
    LocalBus,
    LocalStore,
)
from dynamo_tpu.runtime.hub import HubServer, connect_hub
from dynamo_tpu.runtime.store import EventKind

pytestmark = pytest.mark.faultinject

#: ONE tiny config shared by every engine in the module — ModelConfig
#: hashes by identity (jit static arg), so sharing it shares the
#: compiled program cache across all workers/tests here
TINY = ModelConfig.tiny()


def make_engine(**kw):
    cfg = EngineConfig(
        model=TINY, num_blocks=64, block_size=4, max_batch_size=4,
        max_context=128, prefill_chunk=32, **kw,
    )
    return JaxEngine(cfg, seed=0)


def make_req(tokens=None, max_tokens=10, temperature=0.0, seed=None,
             annotations=None, **so):
    return PreprocessedRequest(
        token_ids=list(tokens if tokens is not None else range(100, 116)),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=temperature, seed=seed, **so
        ),
        eos_token_ids=[511],
        annotations=annotations or {},
    )


@pytest.fixture(autouse=True)
def _fault_hygiene():
    faultpoints.reset()
    yield
    faultpoints.reset()


def _chunk(item):
    """Normalize a stream item (LLMEngineOutput or Annotated[dict]) to
    (token_ids, finish_reason, text, error)."""
    if isinstance(item, Annotated):
        if item.is_error():
            return [], None, None, item.error or "error"
        d = item.data or {}
        return (
            list(d.get("token_ids") or []), d.get("finish_reason"),
            d.get("text"), None,
        )
    fr = item.finish_reason.value if item.finish_reason else None
    return list(item.token_ids or []), fr, item.text, None


async def drive(engine, req, annotations=None):
    """-> (tokens, finishes:list, errors:list, final_chunk_fields)."""
    toks, finishes, errors, final = [], [], [], {}
    async for item in engine.generate(Context(req, annotations=annotations)):
        t, fr, text, err = _chunk(item)
        if err is not None:
            errors.append(err)
            continue
        toks.extend(t)
        if fr is not None:
            finishes.append(fr)
            if isinstance(item, Annotated):
                final = dict(item.data or {})
            else:
                final = {
                    "prompt_tokens": item.prompt_tokens,
                    "completion_tokens": item.completion_tokens,
                    "text": item.text,
                }
    return toks, finishes, errors, final


async def reference_tokens(engine, req):
    """Drive ``req`` on a dedicated engine (constructed OUTSIDE the
    stall-guarded coroutine — the ctor's device work blocks the loop)."""
    toks, finishes, errors, _ = await drive(engine, req)
    assert finishes and not errors
    await engine.close()
    return toks


# ---------------------------------------------------------------------------
# fault-point registry semantics
# ---------------------------------------------------------------------------


def test_faultpoints_deterministic_counters(run):
    async def main():
        faultpoints.arm("mid_decode", "kill", after=3, times=1)
        fired = []
        for i in range(1, 7):
            try:
                faultpoints.hit_sync("mid_decode")
            except FaultInjected as e:
                fired.append((i, e.hit))
        # fires on exactly the 3rd hit, exactly once
        assert fired == [(3, 3)]
        # async delay action actually sleeps
        faultpoints.reset()
        faultpoints.arm("mid_kv_transfer", "delay", delay_s=0.02)
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        await faultpoints.hit("mid_kv_transfer")
        assert loop.time() - t0 >= 0.015
        # spec grammar round-trips
        faultpoints.reset()
        faultpoints.FAULTS.arm_from_spec("mid_decode:kill@4x2,admission:delay=0.1")
        arms = faultpoints.FAULTS._arms
        assert arms["mid_decode"].after == 4 and arms["mid_decode"].times == 2
        assert arms["admission"].action == "delay"
        assert arms["admission"].delay_s == 0.1
        with pytest.raises(ValueError):
            faultpoints.arm("nonsense_point")

    run(main())


def test_classify_failure_taxonomy():
    assert classify_failure(
        "response stream truncated: worker connection lost"
    ) is FailureKind.WORKER_LOST
    assert classify_failure("worker shutdown: stream aborted").retryable
    assert classify_failure(MIGRATION_SIGNAL).retryable
    assert classify_failure(
        "fault injected: worker killed at mid_decode (hit 1)"
    ).retryable
    assert classify_failure(exc=ConnectionError("hub connection lost")) \
        is FailureKind.TRANSIENT
    assert classify_failure("some model error") is FailureKind.FATAL
    assert not classify_failure("some model error").retryable

    class _FakeClient:
        def __init__(self, ids):
            self._ids = ids

        def instance_ids(self):
            return self._ids

    # worker still registered -> TCP blip, not lease loss
    assert classify_failure(
        "response stream truncated: worker connection reset",
        worker_id=7, client=_FakeClient([7, 8]),
    ) is FailureKind.TRANSIENT
    assert classify_failure(
        "response stream truncated: worker connection reset",
        worker_id=7, client=_FakeClient([8]),
    ) is FailureKind.LEASE_LOST


# ---------------------------------------------------------------------------
# resume-annotation continuity (the splice contract, engine side)
# ---------------------------------------------------------------------------


def test_resume_annotation_continuity_sampled_with_penalties(run):
    """A resumed request (prompt + tokens-so-far + resume annotation) on
    a FRESH engine continues the original sampled stream exactly: the
    per-step keys fold_in(seed, generated) pick up at the seam and the
    frequency-penalty state rebuilds from the true prompt/output split."""

    req = make_req(max_tokens=10, temperature=0.9, seed=11,
                   frequency_penalty=0.6)
    cuts = (1, 4, 9)
    # all engines constructed outside the stall-guarded coroutine
    ref_engine = make_engine(decode_window=1)
    resume_engines = {cut: make_engine(decode_window=1) for cut in cuts}

    async def main():
        ref = await reference_tokens(ref_engine, req)
        assert len(ref) == 10
        for cut in cuts:
            resumed = make_req(
                tokens=req.token_ids + ref[:cut], max_tokens=10,
                temperature=0.9, seed=11, frequency_penalty=0.6,
                annotations={"resume": {"prompt_len": len(req.token_ids)}},
            )
            e = resume_engines[cut]
            toks, finishes, errors, final = await drive(e, resumed)
            assert not errors and finishes == ["length"]
            assert toks == ref[cut:], f"cut={cut}"
            # usage counts from the ORIGINAL prompt, not the splice
            assert final["prompt_tokens"] == len(req.token_ids)
            assert final["completion_tokens"] == 10
            assert e.stats["migration_resumes"] == 1
            await e.close()

    run(main())


# ---------------------------------------------------------------------------
# the kill matrix: worker death at each lifecycle stage, through the
# full distributed stack (bus ingress + TCP response plane + migration)
# ---------------------------------------------------------------------------


async def _two_worker_stack(engines):
    store, bus = LocalStore(), LocalBus()
    drts, handles = [], []
    for e in engines:
        drt = await DistributedRuntime.from_settings(store=store, bus=bus)
        h = await drt.namespace("res").component("w").endpoint("gen").serve(
            e, stats_handler=e.load_metrics
        )
        drts.append(drt)
        handles.append(h)
    front = await DistributedRuntime.from_settings(store=store, bus=bus)
    client = (
        await front.namespace("res").component("w").endpoint("gen")
        .client().start()
    )
    await client.wait_for_instances(timeout=5)
    return drts, handles, front, client


async def _teardown_stack(drts, front, engines):
    for e in engines:
        await e.close()
    for drt in drts:
        await drt.shutdown()
    await front.shutdown()


@pytest.mark.parametrize(
    "point,after,min_pre_tokens",
    [
        ("admission", 1, 0),
        ("mid_prefill", 1, 0),
        ("mid_decode", 4, 2),  # several tokens on the wire before death
    ],
)
def test_kill_matrix_stream_continues_bit_exact(run, point, after,
                                                min_pre_tokens):
    req = make_req(max_tokens=10)
    engines = [make_engine(decode_window=1) for _ in range(2)]
    ref_engine = make_engine(decode_window=1)

    async def main():
        ref = await reference_tokens(ref_engine, req)
        drts, handles, front, client = await _two_worker_stack(engines)
        mig = MigratingEngine(
            EngineClient(client), MigrationPolicy(max_migrations=3),
            client=client,
        )
        faultpoints.arm(point, "kill", after=after, times=1)
        # dict payload: the bus envelope is JSON (what real frontends send)
        toks, finishes, errors, _final = await drive(mig, req.to_dict())
        # the fault actually fired and migration picked the stream up
        assert faultpoints.FAULTS.history, "fault point never fired"
        assert mig.stats["migrations_total"] >= 1
        # the client saw: zero errors, exactly one finish chunk, and the
        # exact greedy token sequence — no loss, no duplication
        assert errors == []
        assert finishes == ["length"]
        assert toks == ref
        assert len(toks) == 10
        faultpoints.reset()
        await _teardown_stack(drts, front, engines)

    run(main())


#: DEFECT (ROADMAP C7), not a tolerance: a migrated stream is NOT
#: bit-exact on this XLA CPU build when it crosses a near-tie. The
#: survivor replays prompt + generated tokens through PREFILL (a
#: 32-row bucket where the original ran a 16-row prefill and decode
#: rows), which moves the bf16 logprobs by about 2e-4; this request's
#: greedy stream has its two best candidates 4.8e-6 apart at index 28
#: (156 at -5.831658, 380 at -5.831663), so the replay emits 380. No
#: mesh change is needed: one plain engine that replays 3 or more of
#: the generated tokens flips the same index. The token comparison is
#: the test's LAST assertion: layout atomicity, one finish chunk, zero
#: errors and the teardown all run before the expected failure.
_REPLAY_NEAR_TIE = pytest.mark.xfail(
    strict=True,
    reason="migration replay through prefill flips a greedy near-tie: "
           "index 28, got 380, reference 156 (top-2 logprob gap 4.8e-6 "
           "in the reference, replay shifts logprobs by 2e-4)",
)


@pytest.mark.parametrize(
    "after,dies,on_new_layout",
    [
        (1, False, False),  # pre_stage: staging kill, loop untouched
        # quiesced: dies wholly on the old layout
        pytest.param(2, True, False, marks=_REPLAY_NEAR_TIE),
        # kv_staged: staged, not committed -> old
        pytest.param(3, True, False, marks=_REPLAY_NEAR_TIE),
        # committed: dies wholly on the new layout
        pytest.param(4, True, True, marks=_REPLAY_NEAR_TIE),
    ],
)
def test_mid_reshard_kill_matrix_stream_migrates_bit_exact(
    run, after, dies, on_new_layout
):
    """ISSUE 12 crash-atomicity rule through the FULL distributed stack:
    a worker killed at each live-reshard phase must (a) land wholly on
    exactly one layout, and (b) when the kill takes the serving loop
    with it, its in-flight stream continues on the surviving worker to
    one finish chunk, bit-exact — a morph crash is just a worker death
    to the migration layer."""
    from dynamo_tpu.parallel.mesh import MeshConfig

    req = make_req(max_tokens=40)
    engines = [make_engine(decode_window=1) for _ in range(2)]
    ref_engine = make_engine(decode_window=1)

    async def main():
        ref = await reference_tokens(ref_engine, req)
        drts, handles, front, client = await _two_worker_stack(engines)
        mig = MigratingEngine(
            EngineClient(client), MigrationPolicy(max_migrations=3),
            client=client,
        )
        task = asyncio.ensure_future(drive(mig, req.to_dict()))
        victim = None
        for _ in range(600):
            victim = next(
                (e for e in engines if e._n_active >= 1), None)
            if victim is not None:
                break
            await asyncio.sleep(0.01)
        assert victim is not None, "stream never reached a decode batch"
        faultpoints.arm("mid_reshard", "kill", after=after, times=1)
        # stall the victim's decode at the device lock while the morph
        # stages + posts, so the kill deterministically catches the
        # stream IN FLIGHT at the commit boundary
        async with victim._device_lock:
            morph = asyncio.ensure_future(victim.reshard(MeshConfig(tp=2)))
            for _ in range(800):
                if victim._reshard_req is not None or morph.done():
                    break
                await asyncio.sleep(0.01)
        with pytest.raises(FaultInjected):
            await morph
        toks, finishes, errors, _final = await drive_task(task)
        assert errors == []
        assert finishes == ["length"]
        # all-or-nothing layout, whichever side of the commit the kill hit
        assert victim.cfg.mesh == (MeshConfig(tp=2) if on_new_layout
                                   else None)
        if dies:
            assert victim._dead is not None
            assert mig.stats["migrations_total"] >= 1
        else:
            assert victim._dead is None
            assert mig.stats["migrations_total"] == 0
        faultpoints.reset()
        await _teardown_stack(drts, front, engines)
        # last, so the expected failure (_REPLAY_NEAR_TIE) hides nothing
        assert toks == ref

    run(main())


async def drive_task(task):
    return await task


def test_kill_after_death_requests_fail_fast_not_hang(run):
    """A fault-killed engine must bounce subsequent dispatches with a
    retryable signature immediately (not park them on a dead queue)."""
    e = make_engine(decode_window=1)

    async def main():
        faultpoints.arm("mid_decode", "kill", after=1, times=1)
        toks, finishes, errors, final = await drive(e, make_req())
        assert finishes == ["error"]
        assert "fault injected" in (final.get("text") or "")
        # next request: immediate worker-lost bounce, no hang
        toks2, finishes2, _errors2, final2 = await drive(e, make_req())
        assert toks2 == [] and finishes2 == ["error"]
        assert "fault injected" in (final2.get("text") or "")
        await e.close()

    run(main())


# ---------------------------------------------------------------------------
# graceful drain
# ---------------------------------------------------------------------------


def test_drain_lets_inflight_finish_and_bounces_new_work(run):
    e = make_engine(decode_window=1)

    async def main():
        req = make_req(max_tokens=8)
        stream_task = asyncio.ensure_future(drive(e, req))
        # wait until the request is actually running
        while e.stats["requests_total"] == 0:
            await asyncio.sleep(0.005)
        res = await e.drain(deadline_s=30.0, handoff=True)
        toks, finishes, errors, _ = await stream_task
        # generous deadline: the stream finished NATURALLY, no handoff
        assert finishes == ["length"] and len(toks) == 8 and not errors
        assert res["handed_off"] == 0
        assert e.stats["drains_total"] == 1
        assert e.load_metrics()["draining"] == 1
        # new work during/after drain bounces with the migration signal
        toks2, finishes2, _e2, final2 = await drive(e, make_req())
        assert toks2 == [] and finishes2 == ["error"]
        assert final2.get("text") == MIGRATION_SIGNAL
        await e.close()

    run(main())


def test_drain_deadline_hands_off_and_migration_resumes(run):
    """DrainCoordinator on worker 1 with a tiny deadline: the in-flight
    stream is handed off mid-decode and the migration layer finishes it
    on worker 2, bit-exact, with the lease revoked only afterwards."""
    engines = [make_engine(decode_window=1) for _ in range(2)]
    ref_engine = make_engine(decode_window=1)
    req = make_req(max_tokens=16)

    async def main():
        ref = await reference_tokens(ref_engine, make_req(max_tokens=16))
        drts, handles, front, client = await _two_worker_stack(engines)
        e1 = engines[0]
        mig = MigratingEngine(
            EngineClient(client), MigrationPolicy(max_migrations=4),
            client=client,
        )
        stream_task = asyncio.ensure_future(drive(mig, req.to_dict()))
        # round robin sends the first request to the first-leased worker;
        # wait until it is streaming tokens
        while e1.stats["tokens_generated"] < 3:
            await asyncio.sleep(0.005)
        coord = DrainCoordinator(
            drts[0], engines=[e1], handles=[handles[0]], deadline_s=0.0,
        )
        res = await coord.drain()
        assert res["handed_off"] >= 1
        toks, finishes, errors, _ = await stream_task
        assert errors == []
        assert finishes == ["length"]
        assert toks == ref
        assert mig.stats["migrations_total"] >= 1
        # the drained worker left discovery (lease revoked last)
        for _ in range(100):
            if len(client.instance_ids()) == 1:
                break
            await asyncio.sleep(0.02)
        assert client.instance_ids() == [drts[1].primary_lease_id]
        await engines[1].close()
        await drts[1].shutdown()
        await front.shutdown()

    run(main())


def test_mid_drain_fault_aborts_drain(run):
    """Arming ``mid_drain`` kills the coordinator right after it leaves
    discovery: the drain aborts (counted in drain_errors), the engines
    are never drained — surviving streams take the worker-death path and
    migrate anyway — and the aborted sequence must NOT revoke the lease
    or stop the ingress (a real mid-drain crash dies before those)."""

    class _Handle:
        def __init__(self):
            self.deregistered = False
            self.stopped = False

        async def deregister(self):
            self.deregistered = True

        def inflight_count(self):
            return 0

        async def stop(self):
            self.stopped = True

    class _Drt:
        def __init__(self):
            self.shutdowns = 0

        async def shutdown(self):
            self.shutdowns += 1

    class _Engine:
        def __init__(self):
            self.drained = 0

        async def drain(self, deadline_s=0.0, handoff=True):
            self.drained += 1
            return {"handed_off": 0}

    async def main():
        h, drt, e = _Handle(), _Drt(), _Engine()
        coord = DrainCoordinator(
            drt, engines=[e], handles=[h], deadline_s=0.0
        )
        faultpoints.arm("mid_drain", "kill")
        await coord.trigger()
        assert h.deregistered  # step 1 ran: discovery keys deleted
        assert e.drained == 0  # fault fired before the engine drain
        assert not h.stopped and drt.shutdowns == 0  # sequence aborted
        assert coord.stats["drain_errors"] == 1
        # delay flavor: the drain survives (slow, not dead) and runs the
        # full sequence through lease revocation
        faultpoints.reset()
        faultpoints.arm("mid_drain", "delay", delay_s=0.01)
        h2, drt2, e2 = _Handle(), _Drt(), _Engine()
        coord2 = DrainCoordinator(
            drt2, engines=[e2], handles=[h2], deadline_s=0.0
        )
        res = await coord2.drain()
        assert res["drained"] and e2.drained == 1
        assert h2.stopped and drt2.shutdowns == 1

    run(main())


# ---------------------------------------------------------------------------
# migration policy edges
# ---------------------------------------------------------------------------


class _ScriptedEngine(AsyncEngine):
    """Inner engine driven by a list of per-attempt scripts."""

    def __init__(self, scripts):
        self.scripts = list(scripts)
        self.requests = []

    async def generate(self, request):
        self.requests.append(request)
        script = self.scripts.pop(0) if self.scripts else ["finish"]
        for step in script:
            if step == "finish":
                yield Annotated.from_data(
                    {"token_ids": [], "finish_reason": "length"}
                )
                return
            if step == "truncate":
                return  # end with neither finish nor error
            if isinstance(step, tuple) and step[0] == "error":
                yield Annotated.from_error(step[1])
                return
            yield Annotated.from_data({"token_ids": [step]})


def test_migration_truncation_resumes_with_splice(run):
    async def main():
        inner = _ScriptedEngine([[1, 2, 3, "truncate"], [4, 5, "finish"]])
        mig = MigratingEngine(inner, MigrationPolicy(max_migrations=2))
        req = make_req(tokens=[10, 11, 12])
        toks, finishes, errors, _ = await drive(mig, req)
        assert toks == [1, 2, 3, 4, 5] and finishes == ["length"]
        assert errors == []
        # the re-dispatch carried prompt + tokens-so-far + resume marker
        assert len(inner.requests) == 2
        resumed = inner.requests[1].data
        assert resumed["token_ids"] == [10, 11, 12, 1, 2, 3]
        assert resumed["annotations"]["resume"]["prompt_len"] == 3

    run(main())


def test_migration_redispatch_avoids_failed_worker(run):
    """A killed worker stays in discovery until its lease TTL lapses, and
    radix prefix affinity would re-pick the corpse every time — the
    re-dispatch must carry the failed worker id so the router steers
    around it (the e2e SIGKILL-with-live-lease scenario)."""

    class _RoutedEngine(_ScriptedEngine):
        # mimic KvRoutedEngine: stamp the pinned instance, then fail
        async def generate(self, request):
            request.annotations["routed_worker_id"] = 7
            async for item in super().generate(request):
                yield item

    async def main():
        inner = _RoutedEngine([[1, 2, "truncate"], ["finish"]])
        mig = MigratingEngine(inner, MigrationPolicy(max_migrations=2))
        _toks, finishes, errors, _ = await drive(mig, make_req())
        assert finishes == ["length"] and errors == []
        assert len(inner.requests) == 2
        resumed = inner.requests[1]
        # worker 7 ate the first attempt: the router must avoid it, and
        # the stale pin must not leak into the re-dispatch
        assert resumed.annotations["migration.avoid_workers"] == [7]

    run(main())


def test_migration_fatal_error_not_retried_and_budget_bounds(run):
    async def main():
        # deterministic engine error: surfaced unchanged, inner called once
        inner = _ScriptedEngine([[("error", "some model error")]])
        mig = MigratingEngine(inner, MigrationPolicy(max_migrations=3))
        _toks, _fin, errors, _ = await drive(mig, make_req())
        assert errors == ["some model error"]
        assert len(inner.requests) == 1
        assert mig.stats["migrations_total"] == 0

        # endless truncation: bounded by max_migrations, then surfaced
        inner = _ScriptedEngine([["truncate"]] * 10)
        mig = MigratingEngine(inner, MigrationPolicy(max_migrations=2))
        _toks, _fin, errors, _ = await drive(mig, make_req())
        assert len(errors) == 1 and "migration budget exhausted" in errors[0]
        assert len(inner.requests) == 3  # original + 2 re-dispatches

        # off-switch: the first retryable failure surfaces as-is
        inner = _ScriptedEngine([["truncate"]])
        mig = MigratingEngine(inner, MigrationPolicy(enabled=False))
        _toks, _fin, errors, _ = await drive(mig, make_req())
        assert len(errors) == 1 and "truncated" in errors[0]
        assert len(inner.requests) == 1

    run(main())


# ---------------------------------------------------------------------------
# store watch resume marker (satellite: closes the stale-watch window)
# ---------------------------------------------------------------------------


def test_hub_restart_emits_watch_resumed(run, tmp_path):
    async def main():
        hub = HubServer(data_dir=str(tmp_path / "hub"))
        await hub.start()
        port = int(hub.address.rsplit(":", 1)[1])
        store, _bus, conn = await connect_hub(hub.address)
        w = await store.watch_prefix("res/")
        await store.kv_put("res/a", b"1")
        ev = await asyncio.wait_for(w.__anext__(), 5)
        assert ev.kind == EventKind.PUT and ev.key == "res/a"

        await hub.close()
        hub = HubServer(data_dir=str(tmp_path / "hub"), port=port)
        await hub.start()

        # reconnect reconcile: the durable key re-PUTs, then the
        # watch_resumed marker closes the gap
        kinds = []
        while True:
            ev = await asyncio.wait_for(w.__anext__(), 10)
            kinds.append((ev.kind, ev.key))
            if ev.kind == EventKind.RESUMED:
                assert ev.key == "res/"
                break
        assert (EventKind.PUT, "res/a") in kinds
        # the watch is LIVE again, not silently stale
        await store.kv_put("res/b", b"2")
        ev = await asyncio.wait_for(w.__anext__(), 5)
        assert ev.kind == EventKind.PUT and ev.key == "res/b"
        await conn.close()
        await hub.close()

    run(main())


# ---------------------------------------------------------------------------
# disagg: the prefill WAL item outlives a worker killed mid-transfer
# ---------------------------------------------------------------------------


class _StubPrefillEngine:
    class _Cfg:
        mesh = None

    cfg = _Cfg()

    async def prefill_extract(self, req, ctx, skip_blocks=0,
                              keep_on_device=False, timings=None):
        return 7, None, None, None


class _FlakyPipe:
    """LocalKvPipe stand-in whose first delivery dies mid-transfer."""

    def __init__(self, fail_first=1):
        self.calls = 0
        self.fail_first = fail_first
        self.delivered = []

    async def deliver(self, request_id, first, k, v, **kw):
        self.calls += 1
        if self.calls <= self.fail_first:
            raise ConnectionResetError("decode host hung up mid-transfer")
        self.delivered.append((request_id, first))


def _rpr(request_id="r1"):
    return RemotePrefillRequest(
        request_id=request_id, request=make_req().to_dict(), skip_blocks=0,
        connection={"local": True}, engine_id=0,
    )


def test_prefill_handoff_failure_redelivers_not_drops(run):
    async def main():
        queue = PrefillQueue(LocalBus(), "res", redeliver_after=30.0)
        pipe = _FlakyPipe()
        worker = PrefillWorker(_StubPrefillEngine(), queue, local_pipe=pipe)
        await queue.enqueue(_rpr())
        # attempt 1: the handoff stage dies -> the item must NACK (the
        # pre-fix behavior acked-with-error and stranded the decode side)
        await worker._run_once()
        assert worker.stats["nacks"] == 1 and pipe.delivered == []
        assert await queue.get_depth() == 1
        # attempt 2 (redelivery): commits, then acks
        await worker._run_once()
        assert [r for r, _ in pipe.delivered] == ["r1"]
        assert await queue.get_depth() == 0

    run(main())


def test_prefill_kill_mid_transfer_leaves_item_inflight(run):
    async def main():
        queue = PrefillQueue(LocalBus(), "res", redeliver_after=0.05)
        pipe = _FlakyPipe(fail_first=0)
        worker = PrefillWorker(_StubPrefillEngine(), queue, local_pipe=pipe)
        await queue.enqueue(_rpr("r2"))
        faultpoints.arm("mid_kv_transfer", "kill", times=1)
        # the kill propagates like a crash: no ack, no nack, no error
        with pytest.raises(FaultInjected):
            await worker._run_once()
        assert pipe.delivered == []
        # visibility timeout expires -> the item redelivers and commits
        await asyncio.sleep(0.1)
        await worker._run_once()
        assert [r for r, _ in pipe.delivered] == ["r2"]
        assert await queue.get_depth() == 0

    run(main())
