"""Elastic live resharding (ISSUE 12): morph a pool's parallelism
degree — or absorb a lost host — without dropping a token.

The acceptance matrix:

  * MeshMorpher compiles one program per (geometry, src, dst) and takes
    the cheap shard_map-identity path on matched layouts;
  * a serving engine morphs TP mid-stream with streams bit-identical to
    an unmorphed reference (greedy AND seeded-sampled + penalties —
    RNG/penalty continuity across the seam);
  * requests issued during the morph window are HELD, not bounced;
  * a `mid_reshard` kill at every phase leaves the engine wholly on
    exactly one layout (the crash-atomicity rule);
  * the planner's MorphDecision policy grows/shrinks/relayouts behind
    ScaleGuard rails without flapping, the ReshardListener actuates it,
    and the KV scheduler soft-excludes morphing workers;
  * the reshard gauges flow load_metrics -> WorkerLoad -> metrics
    component.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.engine import ReshardUnsupported
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.publisher import ProcessedEndpoints
from dynamo_tpu.kv_router.scheduler import (
    KvScheduler,
    SchedulerConfig,
    WorkerLoad,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import (
    LogicalLayout,
    MeshConfig,
    cache_sharding,
    make_mesh,
)
from dynamo_tpu.parallel.morph import MeshMorpher
from dynamo_tpu.planner import (
    CapacityModel,
    MorphConfig,
    MorphDecision,
    PLANNER_RESHARD_SUBJECT,
    Planner,
    PlannerConfig,
    TelemetryAggregator,
)
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.resilience import MIGRATION_SIGNAL, ReshardListener, faultpoints
from dynamo_tpu.resilience.faultpoints import FaultInjected
from dynamo_tpu.runtime import Context, DistributedRuntime

from conftest import FakeClock

#: ONE tiny config shared module-wide: ModelConfig hashes by identity
#: (jit static arg), so all engines here share compiled programs
TINY = ModelConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.key(0))

TP2 = MeshConfig(tp=2)


def make_engine(mesh=None, **kw):
    cfg = EngineConfig(
        model=TINY, num_blocks=64, block_size=4, max_batch_size=4,
        max_context=128, prefill_chunk=32, mesh=mesh, **kw,
    )
    return JaxEngine(cfg, params=PARAMS, seed=0)


def make_req(tokens=None, max_tokens=10, temperature=0.0, seed=None, **so):
    return PreprocessedRequest(
        token_ids=list(tokens if tokens is not None else range(100, 116)),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(
            temperature=temperature, seed=seed, **so
        ),
        eos_token_ids=[511],
    )


async def drive(engine, req):
    """-> (tokens, finishes, errors, texts-of-error-chunks)."""
    toks, finishes, err_texts = [], [], []
    async for item in engine.generate(Context(req)):
        toks.extend(item.token_ids or [])
        if item.finish_reason is not None:
            finishes.append(item.finish_reason.value)
            if item.finish_reason.value == "error":
                err_texts.append(item.text or "")
    return toks, finishes, err_texts


async def reference_tokens(req, mesh=None):
    eng = make_engine(mesh)
    toks, finishes, errs = await drive(eng, req)
    assert finishes and not errs
    await eng.close()
    return toks


@pytest.fixture(autouse=True)
def _fault_hygiene():
    faultpoints.reset()
    yield
    faultpoints.reset()


# ---------------------------------------------------------------------------
# MeshMorpher + LogicalLayout units
# ---------------------------------------------------------------------------


def test_morpher_matched_geometry_takes_permute_path():
    m = MeshMorpher()
    mesh = make_mesh(TP2)
    sh = NamedSharding(mesh, P(None, "tp"))
    x = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8), sh)
    out = m.apply(x, sh)  # same split, same devices -> identity permute
    assert m.permute_programs == 1 and m.reshard_programs == 0
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    # memoized: a second call at the same geometry compiles nothing new
    m.apply(x, sh)
    assert m.programs() == 1


def test_morpher_cross_layout_and_cross_device_set():
    m = MeshMorpher()
    mesh2 = make_mesh(TP2)
    x = np.arange(4 * 8 * 8, dtype=np.float32).reshape(4, 8, 8)
    dev0 = jax.device_put(x, jax.devices()[0])
    # single device -> 2-device split: a genuine cross-device-set move
    sh2 = NamedSharding(mesh2, P(None, "tp", None))
    moved = m.apply(dev0, sh2)
    assert set(moved.sharding.device_set) == set(mesh2.devices.flat)
    np.testing.assert_array_equal(np.asarray(moved), x)
    # ...and back down to the default device (dst=None placement)
    back = m.apply(moved, None)
    assert len(back.sharding.device_set) == 1
    np.testing.assert_array_equal(np.asarray(back), x)
    # split-axis change on the SAME device set: the reshard program
    resplit = m.apply(moved, NamedSharding(mesh2, P("tp", None, None)))
    assert m.reshard_programs >= 1
    np.testing.assert_array_equal(np.asarray(resplit), x)


def test_morpher_apply_tree_moves_params_pytree():
    m = MeshMorpher()
    layout = LogicalLayout(TINY)
    mesh = make_mesh(TP2)
    shardings = layout.param_shardings(PARAMS, mesh)
    moved = m.apply_tree(PARAMS, shardings)
    devs = set(mesh.devices.flat)
    for leaf in jax.tree.leaves(moved):
        assert set(leaf.sharding.device_set) <= devs
    # bit-identical content after the move
    a = np.asarray(jax.tree.leaves(PARAMS)[0])
    b = np.asarray(jax.tree.leaves(moved)[0])
    np.testing.assert_array_equal(a, b)
    assert m.moved_arrays == len(jax.tree.leaves(PARAMS))
    assert m.counters()["morph_moved_bytes"] > 0


def test_logical_layout_resolves_per_mesh():
    layout = LogicalLayout(TINY)
    mesh = make_mesh(TP2)
    # cache rule: kv-head axis shards over tp when divisible
    sh = layout.cache_sharding(mesh)
    assert sh == cache_sharding(mesh, TINY)
    assert layout.cache_sharding(None) is None
    # weight shardings resolve against the given mesh; unsharded = None
    tree = layout.param_shardings(PARAMS, mesh)
    leaves = jax.tree.leaves(tree, is_leaf=lambda x: x is None
                             or isinstance(x, NamedSharding))
    assert all(isinstance(l, NamedSharding) for l in leaves)
    none_tree = layout.param_shardings(PARAMS, None)
    assert all(
        l is None for l in jax.tree.leaves(
            none_tree, is_leaf=lambda x: x is None)
    )


# ---------------------------------------------------------------------------
# live morphs: bit-exact streams, held requests, RNG/penalty continuity
# ---------------------------------------------------------------------------


def _n_devices(x) -> int:
    return len(x.sharding.device_set)


def test_reshard_grow_shrink_mid_stream_bit_exact(run):
    async def main():
        req = make_req(max_tokens=60)
        want = await reference_tokens(make_req(max_tokens=60))
        eng = make_engine(None)
        task = asyncio.ensure_future(drive(eng, make_req(max_tokens=60)))
        await asyncio.sleep(0.15)  # let it get into decode
        out = await eng.reshard(TP2)
        assert out["changed"] and out["hold_ms"] >= 0
        # the KV pool really re-laid live content (the stream's blocks
        # plus whatever the prefix cache holds)
        assert out["kv_moved_blocks"] > 0
        assert _n_devices(eng.k_cache) == 2  # kv heads sharded over tp
        toks, finishes, errs = await task
        assert not errs and finishes == ["length"]
        assert toks == want, "morph mid-stream changed the greedy stream"
        # a fresh request entirely on the grown layout
        toks2, _f, errs2 = await drive(eng, req)
        assert not errs2 and toks2 == want
        # shrink back to the unsharded fast path
        out = await eng.reshard(None)
        assert out["changed"] and eng.mesh is None
        assert _n_devices(eng.k_cache) == 1
        toks3, _f, errs3 = await drive(eng, req)
        assert not errs3 and toks3 == want
        assert eng.stats["resharded_total"] == 2
        lm = eng.load_metrics()
        assert lm["resharded_total"] == 2 and lm["resharding"] == 0
        assert lm["reshard_kv_moved_blocks"] > 0
        # no-op at the current shape; force re-lays anyway (the
        # lost-host survivor case: same shape, placement re-resolved)
        assert (await eng.reshard(None))["changed"] is False
        assert (await eng.reshard(None, force=True))["changed"] is True
        await eng.close()

    run(main())


def test_reshard_grow_shrink_int8_cache_bit_exact(run):
    """Grow/shrink with the int8-with-scales device cache live (ISSUE
    18): the per-page scale planes are commit-block state — they re-lay
    (replicated) with the quantized pages, so a mid-stream morph keeps
    the greedy stream bit-exact against an unmorphed int8 reference,
    and the planes keep their per-page values across both directions."""
    async def main():
        req = make_req(max_tokens=60)
        ref = make_engine(None, kv_cache_dtype="int8")
        want, finishes, errs = await drive(ref, make_req(max_tokens=60))
        assert finishes and not errs
        await ref.close()

        eng = make_engine(None, kv_cache_dtype="int8")
        task = asyncio.ensure_future(drive(eng, make_req(max_tokens=60)))
        await asyncio.sleep(0.15)  # let it get into decode
        planes_before = np.asarray(eng.k_scales).copy()
        out = await eng.reshard(TP2)
        assert out["changed"] and out["kv_moved_blocks"] > 0
        assert eng.k_cache.dtype == jnp.int8
        assert _n_devices(eng.k_cache) == 2
        # planes moved WITH the pages (replicated on the new mesh) and
        # kept every page's scale — a lost scale would silently rescale
        # resident content
        assert _n_devices(eng.k_scales) == 2
        assert np.asarray(eng.k_scales).shape == planes_before.shape
        toks, finishes, errs = await task
        assert not errs and finishes == ["length"]
        assert toks == want, (
            "morph mid-stream changed the quantized greedy stream"
        )
        # fresh request on the grown layout, then shrink back
        toks2, _f, errs2 = await drive(eng, req)
        assert not errs2 and toks2 == want
        out = await eng.reshard(None)
        assert out["changed"] and eng.mesh is None
        assert eng.k_cache.dtype == jnp.int8
        assert _n_devices(eng.k_scales) == 1
        toks3, _f, errs3 = await drive(eng, req)
        assert not errs3 and toks3 == want
        assert eng.stats["resharded_total"] == 2
        await eng.close()

    run(main())


def test_reshard_rng_and_penalty_continuity(run):
    async def main():
        # seeded sampling + penalties: the state the morph must carry
        # token-exactly (fold_in(seed, generated) + [B,V] pen planes)
        def sampled_req():
            return make_req(
                max_tokens=60, temperature=0.9, seed=123,
                frequency_penalty=0.4, presence_penalty=0.2,
                repetition_penalty=1.3,
            )

        want = await reference_tokens(sampled_req())
        eng = make_engine(None)
        task = asyncio.ensure_future(drive(eng, sampled_req()))
        await asyncio.sleep(0.1)
        assert (await eng.reshard(TP2))["changed"]
        toks, finishes, errs = await task
        assert not errs and finishes == ["length"]
        assert toks == want, "sampled stream diverged across the morph"
        await eng.close()

    run(main())


def test_reshard_holds_requests_issued_during_morph(run):
    async def main():
        want = await reference_tokens(make_req(max_tokens=6))
        eng = make_engine(None)
        # saturate with a long stream so the morph has in-flight work
        long_task = asyncio.ensure_future(
            drive(eng, make_req(list(range(200, 216)), max_tokens=20))
        )
        await asyncio.sleep(0.3)
        morph = asyncio.ensure_future(eng.reshard(TP2))
        # requests landing in the morph window queue and serve after
        # resume — never a bounce, never an error
        held = [
            asyncio.ensure_future(drive(eng, make_req(max_tokens=6)))
            for _ in range(3)
        ]
        out = await morph
        assert out["changed"]
        for t in held:
            toks, finishes, errs = await t
            assert not errs and finishes == ["length"]
            assert toks == want
        toks, _f, errs = await long_task
        assert not errs
        await eng.close()

    run(main())


async def _pause_decode_and_post_morph(eng, coro):
    """Deterministically catch streams IN FLIGHT at the morph commit:
    wait for the stream to join the decode batch, stall the decode loop
    by holding the device lock (dispatch can't proceed), start the
    reshard (weight staging needs no device lock, so it completes and
    POSTS the commit request), then release — the loop's very next
    boundary runs the commit with the stream still mid-decode."""
    for _ in range(400):
        if eng._n_active >= 1:
            break
        await asyncio.sleep(0.01)
    assert eng._n_active >= 1, "stream never reached the decode batch"
    async with eng._device_lock:
        task = asyncio.ensure_future(coro)
        for _ in range(800):
            if eng._reshard_req is not None or task.done():
                break
            await asyncio.sleep(0.01)
    return task


def test_reshard_handoff_when_not_held(run):
    async def main():
        eng = make_engine(None)
        task = asyncio.ensure_future(
            drive(eng, make_req(list(range(300, 316)), max_tokens=100))
        )
        morph = await _pause_decode_and_post_morph(
            eng, eng.reshard(TP2, hold=False)
        )
        out = await morph
        assert out["changed"]
        toks, finishes, errs = await task
        # the in-flight stream was handed off with the migration
        # signal: a migration-aware frontend would splice it elsewhere
        assert finishes == ["error"] and errs == [MIGRATION_SIGNAL]
        assert eng.stats["drain_handoffs"] >= 1
        # the engine itself is NOT draining — it serves on, morphed
        toks2, finishes2, errs2 = await drive(eng, make_req(max_tokens=4))
        assert not errs2 and finishes2 == ["length"]
        await eng.close()

    run(main())


def test_reshard_prefix_cache_survives_morph(run):
    async def main():
        eng = make_engine(None)
        prompt = list(range(150, 182))  # 8 full blocks
        await drive(eng, make_req(prompt, max_tokens=4))
        assert (await eng.reshard(TP2))["changed"]
        before = eng.stats["prefix_cache_hits_tokens"]
        await drive(eng, make_req(prompt, max_tokens=4))
        # the re-laid pool still serves the committed prefix by hash
        assert eng.stats["prefix_cache_hits_tokens"] > before
        await eng.close()

    run(main())


def test_reshard_rejects_mirror_and_overlap(run):
    async def main():
        eng = make_engine(None)
        eng.mirror = object()  # quack like a multi-host leader
        with pytest.raises(ReshardUnsupported):
            await eng.reshard(TP2)
        eng.mirror = None
        # overlapping morphs: the second call must be rejected, not
        # silently queued into a flap — the slot is claimed BEFORE the
        # staging await, so even two calls racing through the checks
        # concurrently can't both post (the loser would otherwise
        # overwrite the winner's request and hang its caller forever)
        first = asyncio.ensure_future(eng.reshard(TP2))
        await asyncio.sleep(0)  # first call reaches its staging await
        with pytest.raises(RuntimeError, match="already in flight"):
            await eng.reshard(TP2)
        out = await first
        assert out["changed"] is True
        await eng.reshard(None)  # back to unsharded for the rest
        # unsatisfiable degree: error surfaces, engine stays healthy
        with pytest.raises(ValueError):
            await eng.reshard(MeshConfig(tp=4096))
        assert eng._dead is None and not eng._resharding
        toks, _f, errs = await drive(eng, make_req(max_tokens=3))
        assert toks and not errs
        await eng.close()

    run(main())


# ---------------------------------------------------------------------------
# mid_reshard crash atomicity: the faultpoint matrix
# ---------------------------------------------------------------------------


def _assert_layout_whole(eng, expect_mesh_devices: int):
    """Every piece of device state agrees with engine.mesh — the
    morph's all-or-nothing contract."""
    if expect_mesh_devices <= 1:
        assert eng.mesh is None
        expected = None
    else:
        assert eng.mesh is not None
        expected = set(eng.mesh.devices.flat)
        assert len(expected) == expect_mesh_devices
    pieces = jax.tree.leaves(eng.params) + [eng.k_cache, eng.v_cache]
    for leaf in pieces:
        devs = set(leaf.sharding.device_set)
        if expected is None:
            assert len(devs) == 1
        else:
            assert devs <= expected
    # the cache's kv-head split is the visible tp signature
    assert _n_devices(eng.k_cache) == (expect_mesh_devices or 1)


@pytest.mark.faultinject
def test_mid_reshard_kill_matrix_leaves_one_layout(run):
    async def main():
        # phases in hit order: 1=pre_stage, 2=quiesced, 3=kv_staged,
        # 4=committed (resilience/faultpoints.py POINTS docstring)
        for hit_n, on_new_layout, loop_dies in (
            (1, False, False),  # staging kill: loop never involved
            (2, False, True),
            (3, False, True),
            (4, True, True),
        ):
            eng = make_engine(None)
            # populate the pool so the morph has real content to move
            toks, _f, errs = await drive(eng, make_req(max_tokens=4))
            assert toks and not errs
            faultpoints.arm("mid_reshard", "kill", after=hit_n, times=1)
            with pytest.raises(FaultInjected):
                await eng.reshard(TP2)
            faultpoints.reset()
            _assert_layout_whole(eng, 2 if on_new_layout else 0)
            assert eng.cfg.mesh == (TP2 if on_new_layout else None)
            assert not eng._resharding and eng._reshard_req is None
            if loop_dies:
                # a kill inside the loop's commit step IS a worker
                # death: new work must bounce with the retryable
                # worker-lost signature, exactly like any crash
                assert eng._dead is not None
                _toks, finishes, errs = await drive(
                    eng, make_req(max_tokens=3))
                assert finishes == ["error"]
            else:
                # a staging kill never touched the loop: the engine
                # keeps serving on the old layout
                assert eng._dead is None
                toks2, _f2, errs2 = await drive(eng, make_req(max_tokens=3))
                assert toks2 and not errs2
            await eng.close()

    run(main())


@pytest.mark.faultinject
def test_mid_reshard_kill_with_streams_in_flight_is_migratable(run):
    async def main():
        eng = make_engine(None)
        task = asyncio.ensure_future(
            drive(eng, make_req(list(range(400, 416)), max_tokens=100))
        )
        faultpoints.arm("mid_reshard", "kill", after=3, times=1)
        morph = await _pause_decode_and_post_morph(eng, eng.reshard(TP2))
        with pytest.raises(FaultInjected):
            await morph
        _toks, finishes, errs = await task
        # the in-flight stream got the worker-lost signature — the
        # migration layer re-dispatches it (test_reshard_soak drives
        # that end to end through the router)
        assert finishes == ["error"]
        assert errs and "fault injected" in errs[0]
        _assert_layout_whole(eng, 0)
        await eng.close()

    run(main())


# ---------------------------------------------------------------------------
# control plane: listener, planner policy, router soft-exclusion
# ---------------------------------------------------------------------------


def test_reshard_listener_applies_and_filters(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        comp = drt.namespace("morphns").component("worker")
        subject = comp.event_subject(PLANNER_RESHARD_SUBJECT)
        eng = make_engine(None)
        listener = await ReshardListener(drt, comp, worker_id=7,
                                         engine=eng).start()

        # a morph recompiles the step programs: seconds on a quiet
        # machine, more beside five other workers (the driver's run on
        # PR 31's tree waited 4 s and failed); the wait ends with pred
        async def publish_and_wait(decision, pred, n=3000):
            drt.bus.publish(subject, decision.to_bytes())
            for _ in range(n):
                if pred():
                    return True
                await asyncio.sleep(0.02)
            return pred()

        # addressed to another worker: ignored
        assert not await publish_and_wait(
            MorphDecision(worker_id=9, tp=2),
            lambda: eng.cfg.mesh is not None, n=25,
        )
        # addressed to another POOL: ignored even pool-wide (a decode
        # grow must not morph prefill workers sharing the subject)
        assert not await publish_and_wait(
            MorphDecision(worker_id=0, tp=2, pool="prefill"),
            lambda: eng.cfg.mesh is not None, n=25,
        )
        # pool-wide grow applies (the listener counts a morph AFTER the
        # engine's reshard has returned: wait for the count, the mesh is
        # set a task switch earlier)
        assert await publish_and_wait(
            MorphDecision(worker_id=0, tp=2, reason="grow_tp"),
            lambda: listener.morphs_applied == 1,
        )
        assert eng.cfg.mesh is not None and eng.cfg.mesh.tp == 2
        # shrink normalizes the all-ones mesh back to unsharded
        assert await publish_and_wait(
            MorphDecision(worker_id=7, tp=1, reason="shrink_tp"),
            lambda: listener.morphs_applied == 2,
        )
        assert eng.cfg.mesh is None
        # same degree again: noop, not an error
        assert await publish_and_wait(
            MorphDecision(worker_id=0, tp=1),
            lambda: listener.morphs_noop >= 1,
        )
        assert listener.stats()["reshard_morphs_failed"] == 0
        await listener.close()
        await eng.close()
        await drt.shutdown()

    run(main())


def test_reshard_listener_drain_fallback_for_mirrors(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        comp = drt.namespace("morphns2").component("worker")
        subject = comp.event_subject(PLANNER_RESHARD_SUBJECT)

        class _MirrorEngine:
            """Quacks like a mirrored JaxEngine: can't morph live."""

            def __init__(self):
                self.cfg = type("C", (), {"mesh": None})()
                self.drained = []

            async def reshard(self, mesh, hold=True, force=False):
                raise ReshardUnsupported("mirrored")

            async def drain(self, deadline_s=10.0, handoff=True):
                self.drained.append((deadline_s, handoff))
                return {"handed_off": 0}

        eng = _MirrorEngine()
        listener = await ReshardListener(drt, comp, worker_id=1,
                                         engine=eng).start()
        drt.bus.publish(
            subject, MorphDecision(worker_id=0, tp=2).to_bytes()
        )
        for _ in range(200):
            if eng.drained:
                break
            await asyncio.sleep(0.02)
        # the decision was honored via the PR 4 path: drain WITH
        # handoff, streams migrate to workers that can serve the layout
        assert eng.drained and eng.drained[0][1] is True
        assert listener.morphs_drained == 1
        await listener.close()
        await drt.shutdown()

    run(main())


@pytest.mark.planner
def test_planner_morph_policy_grow_shrink_guarded():
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)

    class _Sink:
        def __init__(self):
            self.morphs = []

        def publish(self, decision, watermark):
            pass

        def publish_morph(self, m):
            self.morphs.append(m)

    sink = _Sink()
    planner = Planner(
        telemetry, CapacityModel(1000.0, 1000.0),
        PlannerConfig(morph=MorphConfig(
            tp_min=1, tp_max=4, grow_prompt_tokens=512.0,
        )),
        publisher=sink, clock=clk,
    )

    def long_prompt_traffic():
        telemetry.record_arrival(prompt_tokens=6000, n=10)  # mean 600

    # long-prompt-dominated: grow 1 -> 2
    long_prompt_traffic()
    planner.tick()
    assert [m.reason for m in sink.morphs] == ["grow_tp"]
    assert sink.morphs[-1].tp == 2
    # the up-cooldown rails pace the next doubling: no flap at +1s
    clk.advance(1.0)
    long_prompt_traffic()
    planner.tick()
    assert len(sink.morphs) == 1
    # past the cooldown the sustained signal doubles again to tp_max
    clk.advance(35.0)
    long_prompt_traffic()
    planner.tick()
    assert [m.tp for m in sink.morphs] == [2, 4]
    # sustained idle: the shrink waits out down_stable + down_cooldown,
    # then lands ONCE at the floor (no intermediate steps, no flap)
    for _ in range(40):
        clk.advance(10.0)
        planner.tick()
    shrinks = [m for m in sink.morphs if m.reason == "shrink_tp"]
    assert len(shrinks) == 1 and shrinks[0].tp == 1
    assert planner.render_stats()["planner_morph_tp"] == 1


@pytest.mark.planner
def test_planner_morph_relayout_on_lost_host():
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)

    class _Sink:
        def __init__(self):
            self.morphs = []

        def publish(self, decision, watermark):
            pass

        def publish_morph(self, m):
            self.morphs.append(m)

    sink = _Sink()
    planner = Planner(
        telemetry, CapacityModel(1000.0, 1000.0),
        PlannerConfig(morph=MorphConfig()), publisher=sink, clock=clk,
    )

    def load(wid, draining=0):
        return WorkerLoad(worker_id=wid, total_slots=8, draining=draining)

    telemetry.observe_loads([load(1), load(2), load(3, draining=1)])
    clk.advance(1.0)
    # worker 2 vanishes hard; worker 3 vanishes mid-drain (planned).
    # ONE missed scrape is a slow endpoint, not a lost host — no
    # relayout until the miss CONFIRMS on a second consecutive scrape
    telemetry.observe_loads([load(1)])
    planner.tick()
    assert [m for m in sink.morphs
            if m.reason == "relayout_lost_host"] == []
    clk.advance(1.0)
    telemetry.observe_loads([load(1)])
    planner.tick()
    relayouts = [m for m in sink.morphs if m.reason == "relayout_lost_host"]
    assert len(relayouts) == 1
    assert relayouts[0].force is True and relayouts[0].worker_id == 0
    assert relayouts[0].lost_workers == [2]  # the drained exit is NOT lost
    # the same loss does not republish every tick
    clk.advance(1.0)
    planner.tick()
    assert len([m for m in sink.morphs
                if m.reason == "relayout_lost_host"]) == 1


@pytest.mark.planner
def test_planner_morph_single_miss_is_not_a_lost_host():
    """A worker that misses ONE scrape and reappears (slow metrics
    endpoint, long compile) must never trigger the pool-wide force
    relayout — the miss count resets on reappearance."""
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)

    def load(wid):
        return WorkerLoad(worker_id=wid, total_slots=8)

    telemetry.observe_loads([load(1), load(2)])
    for _ in range(6):  # flap: miss one, reappear, miss one, ...
        clk.advance(1.0)
        telemetry.observe_loads([load(1)])
        clk.advance(1.0)
        telemetry.observe_loads([load(1), load(2)])
    assert telemetry.snapshot().lost_workers == []


@pytest.mark.planner
def test_planner_morph_guard_seeds_from_deployed_tp():
    """A planner starting against a TP=4 fleet must reason from the
    DEPLOYED degree (workers advertise mesh_tp), not tp_min: its first
    lost-host relayout re-lays survivors at 4, and a grow from 4 at
    tp_max=4 clamps to a no-op instead of publishing a shrink labeled
    grow."""
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)

    class _Sink:
        def __init__(self):
            self.morphs = []

        def publish(self, decision, watermark):
            pass

        def publish_morph(self, m):
            self.morphs.append(m)

    sink = _Sink()
    planner = Planner(
        telemetry, CapacityModel(1000.0, 1000.0),
        PlannerConfig(morph=MorphConfig(tp_min=1, tp_max=4)),
        publisher=sink, clock=clk,
    )

    def load(wid):
        return WorkerLoad(worker_id=wid, total_slots=8, mesh_tp=4)

    telemetry.observe_loads([load(1), load(2)])
    # long-prompt traffic at the ceiling: no grow decision (4 is max)
    telemetry.record_arrival(prompt_tokens=6000, n=10)
    planner.tick()
    assert sink.morphs == []
    assert planner.morph_guard.current == 4  # seeded from the fleet
    # now lose worker 2 (two consecutive misses): the relayout carries
    # the DEPLOYED degree, not tp_min's fiction
    for _ in range(2):
        clk.advance(1.0)
        telemetry.observe_loads([load(1)])
    telemetry.record_arrival(prompt_tokens=6000, n=10)
    planner.tick()
    relayouts = [m for m in sink.morphs if m.reason == "relayout_lost_host"]
    assert len(relayouts) == 1 and relayouts[0].tp == 4


def test_scheduler_soft_excludes_resharding_worker():
    clk = FakeClock()
    sched = KvScheduler(config=SchedulerConfig(cost_model=False),
                        clock=clk)

    def load(wid, resharding=0):
        return WorkerLoad(worker_id=wid, total_slots=8,
                          resharding=resharding, ts=clk())

    eps = ProcessedEndpoints([load(1, resharding=1), load(2)])
    picked = sched.select_worker(eps, OverlapScores(scores={}), 4)
    assert picked == 2  # morphing worker avoided
    # ...but a pool that is ALL morphing still serves (soft, not hard)
    eps = ProcessedEndpoints([load(1, resharding=1)])
    assert sched.select_worker(eps, OverlapScores(scores={}), 4) == 1


def test_workerload_and_gauges_carry_reshard_surface():
    from dynamo_tpu.observability.component import MetricsComponent

    d = {
        "resharding": 1, "resharded_total": 3,
        "reshard_hold_ms": 12.5, "reshard_kv_moved_blocks": 40,
    }
    w = WorkerLoad.from_stats(9, d)
    assert (w.resharding, w.resharded_total, w.reshard_hold_ms,
            w.reshard_kv_moved_blocks) == (1, 3, 12.5, 40)
    mc = MetricsComponent.__new__(MetricsComponent)
    mc.prefix = "dynamo_tpu"
    mc.aggregator = type("A", (), {"endpoints": ProcessedEndpoints([w])})()
    mc.hit_events = 0
    mc.hit_isl_blocks = 0
    mc.hit_overlap_blocks = 0
    mc.planner_decision = None
    mc.planner_watermark = None
    mc.planner_decisions_total = 0
    mc.tracing = None
    mc.route_cost_events = 0
    mc.route_predicted_ttft_ms = 0.0
    text = mc.render()
    assert 'dynamo_tpu_resharding{worker="9"} 1' in text
    assert 'dynamo_tpu_resharded_total{worker="9"} 3' in text
    assert 'dynamo_tpu_reshard_hold_ms{worker="9"} 12.5' in text
    assert 'dynamo_tpu_reshard_kv_moved_blocks{worker="9"} 40' in text


def test_morph_decision_wire_roundtrip_and_tolerance():
    d = MorphDecision(ts=1.0, worker_id=5, tp=4, reason="grow_tp",
                      hold=False, force=True, lost_workers=[9])
    back = MorphDecision.from_bytes(d.to_bytes())
    assert back == d
    # forward-compat: unknown keys ignored, missing keys defaulted
    import json as _json

    raw = _json.dumps({"tp": 2, "new_field": "x"}).encode()
    back = MorphDecision.from_bytes(raw)
    assert back.tp == 2 and back.worker_id == 0 and back.hold is True


# ---------------------------------------------------------------------------
# lease-expiry lost-host evidence (ROADMAP PR 12 leftover): the
# discovery watch's lease-expiry events corroborate missed scrapes,
# cutting relayout_lost_host detection latency — without ever firing
# on a worker whose scrapes keep arriving.
# ---------------------------------------------------------------------------


def _tele_load(wid, draining=0):
    return WorkerLoad(worker_id=wid, total_slots=8, draining=draining)


@pytest.mark.planner
def test_lease_expiry_alone_does_not_relayout():
    """THE regression the satellite demands: a lease expiry while
    scrapes keep arriving (hub restart, watch flap) must NOT force a
    relayout — the host is demonstrably alive."""
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)
    telemetry.observe_loads([_tele_load(1), _tele_load(2)])
    telemetry.record_lease_expiry(2)
    for _ in range(5):
        clk.advance(1.0)
        telemetry.observe_loads([_tele_load(1), _tele_load(2)])
    assert telemetry.snapshot().lost_workers == []
    # the evidence was cleared by the arriving scrapes: even if the
    # worker NOW misses one scrape, the normal two-miss debounce holds
    clk.advance(1.0)
    telemetry.observe_loads([_tele_load(1)])
    assert telemetry.snapshot().lost_workers == []


@pytest.mark.planner
def test_lease_expiry_halves_scrape_debounce():
    """Expiry + ONE missed scrape confirms (the scrape-only path needs
    two consecutive misses)."""
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)
    telemetry.observe_loads([_tele_load(1), _tele_load(2)])
    telemetry.record_lease_expiry(2)
    clk.advance(1.0)
    telemetry.observe_loads([_tele_load(1)])  # first miss
    assert telemetry.snapshot().lost_workers == [2]


@pytest.mark.planner
def test_lease_expiry_after_miss_confirms_immediately():
    """The worker already missed a scrape when its lease expires: both
    signals agree — confirmed on the spot, no further scrape needed."""
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)
    telemetry.observe_loads([_tele_load(1), _tele_load(2)])
    clk.advance(1.0)
    telemetry.observe_loads([_tele_load(1)])  # one miss: below debounce
    assert telemetry.snapshot().lost_workers == []
    telemetry.record_lease_expiry(2)
    assert telemetry.snapshot().lost_workers == [2]


@pytest.mark.planner
def test_lease_expiry_ignores_drained_and_unknown_workers():
    clk = FakeClock()
    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)
    telemetry.observe_loads([_tele_load(1), _tele_load(3, draining=1)])
    telemetry.record_lease_expiry(3)   # draining: planned departure
    telemetry.record_lease_expiry(99)  # never scraped: not our pool
    clk.advance(1.0)
    telemetry.observe_loads([_tele_load(1)])
    clk.advance(1.0)
    telemetry.observe_loads([_tele_load(1)])
    assert telemetry.snapshot().lost_workers == []


@pytest.mark.planner
def test_lease_watch_feeds_telemetry():
    """End to end through the runtime: a worker's discovery key deleted
    (lease revoke) reaches record_lease_expiry via start_lease_watch."""
    from dynamo_tpu.planner.telemetry import start_lease_watch

    async def main():
        drt = DistributedRuntime()
        await drt.start()
        try:
            comp = drt.namespace("ns").component("workers")
            clk = FakeClock()
            telemetry = TelemetryAggregator(window_s=30.0, clock=clk)
            task = await start_lease_watch(drt, comp, telemetry)
            key = "ns/components/workers/generate:2a"
            put = drt.store.kv_put(key, b"{}")
            if asyncio.iscoroutine(put):
                await put
            telemetry.observe_loads([_tele_load(0x2A), _tele_load(1)])
            clk.advance(1.0)
            telemetry.observe_loads([_tele_load(1)])  # one miss
            delete = drt.store.kv_delete(key)
            if asyncio.iscoroutine(delete):
                await delete
            for _ in range(50):
                if telemetry.lease_expiries:
                    break
                await asyncio.sleep(0.01)
            assert telemetry.lease_expiries == 1
            # corroborated miss: confirmed without a second missed scrape
            assert telemetry.snapshot().lost_workers == [0x2A]
            task.cancel()
        finally:
            await drt.shutdown()

    asyncio.run(main())
