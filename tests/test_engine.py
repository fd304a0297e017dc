"""JAX engine tests: continuous batching, prefix cache, cancellation,
stop conditions — all on the CPU mesh with a tiny model."""

import asyncio

import jax
import pytest

from dynamo_tpu.engine import BlockAllocator, EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, collect


@pytest.fixture(scope="module")
def engine_cfg():
    return EngineConfig(
        model=ModelConfig.tiny(),
        num_blocks=64,
        block_size=4,
        max_batch_size=4,
        max_context=128,
        prefill_chunk=32,
    )


@pytest.fixture
def shared_engine(engine_cfg):
    # fresh engine per test (asyncio state binds to the test's loop);
    # jit compile caches are module-level so this stays fast
    return JaxEngine(engine_cfg, seed=0)


def make_req(tokens, max_tokens=8, temperature=0.0, seed=0, **stops):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, **stops),
        sampling_options=SamplingOptions(temperature=temperature, seed=seed),
        eos_token_ids=[511],
    )


# ---------------- allocator unit tests (ref lib/llm/tests/kv_manager.rs) --------


def test_allocator_alloc_free_reuse():
    a = BlockAllocator(num_blocks=9, block_size=4)
    assert a.free_count == 8
    blocks = a.allocate(3)
    assert a.free_count == 5 and all(b.idx != 0 for b in blocks)
    # commit first as full, then free all
    h = a.commit_full_block(blocks[0], [1, 2, 3, 4], None)
    a.free(blocks)
    assert a.free_count == 8
    # matching prefix claims the committed block back
    matched = a.match_prefix([1, 2, 3, 4, 5, 6])
    assert len(matched) == 1 and matched[0].seq_hash == h
    a.free(matched)


def test_allocator_chained_hashes_differ_by_prefix():
    h1 = sequence_block_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    h2 = sequence_block_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert h1[0][0] != h2[0][0]
    # same local hash for the second block, different chained hash
    assert h1[1][0] == h2[1][0]
    assert h1[1][1] != h2[1][1]


def test_allocator_exhaustion_and_refcounts():
    a = BlockAllocator(num_blocks=5, block_size=4)
    blocks = a.allocate(4)
    assert a.allocate(1) is None
    h = a.commit_full_block(blocks[0], [7, 7, 7, 7], None)
    m = a.match_prefix([7, 7, 7, 7])  # shared ref on same block
    assert m[0].idx == blocks[0].idx and m[0].ref_count == 2
    a.free([blocks[0]])
    assert a.free_count == 0  # still referenced by m
    a.free(m)
    assert a.free_count == 1  # now in reuse pool

    removed = []
    a.on_removed = removed.append
    got = a.allocate(1)  # must evict the reuse-pool block
    assert got is not None
    assert removed and removed[0] == [h]


# ---------------- engine behavior ----------------


def test_engine_greedy_deterministic(run, engine_cfg, shared_engine):
    async def main():
        engine = shared_engine
        req = make_req(range(10, 20), max_tokens=6)
        out1 = await collect(engine.generate(Context(req)))
        out2 = await collect(engine.generate(Context(make_req(range(10, 20), max_tokens=6))))
        toks1 = [t for o in out1 for t in o.token_ids]
        toks2 = [t for o in out2 for t in o.token_ids]
        assert len(toks1) == 6
        assert toks1 == toks2
        final = out1[-1]
        assert final.finish_reason == FinishReason.LENGTH
        assert final.prompt_tokens == 10 and final.completion_tokens == 6

    run(main())


@pytest.mark.parametrize("chained", [True, False],
                         ids=["chained", "unchained"])
def test_warmup_compiles_buckets_and_serving_still_exact(
        run, engine_cfg, chained):
    """warmup() must cover every reachable prefill bucket and every
    window the loop ever runs, and a real request after warmup must
    produce the same stream as a cold engine (dummy blocks may enter the
    prefix cache but cannot change outputs)."""
    from dataclasses import replace

    from dynamo_tpu.engine.engine import JaxEngine

    async def main():
        cold = JaxEngine(replace(engine_cfg), seed=0)
        ref = await collect(cold.generate(Context(make_req(range(30, 44),
                                                           max_tokens=5))))
        ref_toks = [t for o in ref for t in o.token_ids]
        await cold.close()

        # prefill_chunk=48 is not a bucket boundary: real 33..48-token
        # chunks round UP to bucket 64, which the warm set must include
        warm = JaxEngine(
            replace(engine_cfg, prefill_chunk=48, decode_window=4,
                    spec_gamma=3, decode_pipeline=chained),
            seed=0,
        )
        windows = []
        orig_pick = warm._pick_window
        warm._pick_window = lambda: windows.append(n := orig_pick()) or n
        sizes = await warm.warmup()
        warm._pick_window = orig_pick
        assert sizes == [16, 32, 64], sizes
        # distinct per-bucket dummy tokens: a prefix-cache hit would mean
        # a warmup prompt only prefilled its (smaller) TAIL bucket
        assert warm.stats["prefix_cache_hits_tokens"] == 0, warm.stats
        # the decode-window ladder walks ALL the way down: 1-step windows
        # are what concurrent admission dispatches, and speculation (the
        # other path that could swallow window dispatches on repetitive
        # dummy prompts) must be held off during warmup. A chained window
        # runs at most half decode_window, so 4 is never dispatched (nor
        # compiled) there, and the coverage report counts what it runs
        assert ({2, 1} if chained else {4, 2, 1}) <= set(windows), windows
        assert chained == (4 not in windows), windows
        assert (warm.stats["xla_reachable_buckets"]
                == len(sizes) + (2 if chained else 3))
        assert warm.stats["spec_proposed"] == 0, warm.stats
        assert warm.cfg.spec_gamma == 3  # restored after warmup

        # prefill-only role (disagg prefill worker): no decode windows
        pre = JaxEngine(replace(engine_cfg, decode_window=4), seed=0)
        base_steps = pre.stats["decode_steps"]
        await pre.warmup(decode=False)
        assert pre.stats["decode_steps"] == base_steps, pre.stats
        await pre.close()
        out = await collect(warm.generate(Context(make_req(range(30, 44),
                                                           max_tokens=5))))
        assert [t for o in out for t in o.token_ids] == ref_toks
        await warm.close()

    run(main())


def test_decode_window_matches_single_step(run, engine_cfg):
    """Fused n-step decode windows must produce the exact token stream of
    1-step dispatch (sampled and greedy): the scan feeds step i's token to
    step i+1 on device with identical PRNG key derivation."""

    async def main():
        from dataclasses import replace

        outs = {}
        for window in (1, 4):
            cfg = replace(engine_cfg, decode_window=window)
            engine = JaxEngine(cfg, seed=0)
            for name, req in (
                ("greedy", make_req(range(10, 20), max_tokens=7)),
                ("sampled", make_req(range(10, 20), max_tokens=7,
                                     temperature=0.9, seed=123)),
            ):
                out = await collect(engine.generate(Context(req)))
                outs[(window, name)] = [t for o in out for t in o.token_ids]
                assert out[-1].finish_reason == FinishReason.LENGTH
            await engine.close()
        assert outs[(1, "greedy")] == outs[(4, "greedy")]
        assert outs[(1, "sampled")] == outs[(4, "sampled")]

    run(main())


def test_decode_window_midwindow_eos(run, engine_cfg):
    """A stop token sampled mid-window must end the stream there — the
    window's tail tokens are discarded, not emitted."""

    async def main():
        from dataclasses import replace

        # find what greedy generates, then declare its 2nd token a stop id
        engine = JaxEngine(replace(engine_cfg, decode_window=1), seed=0)
        out = await collect(engine.generate(Context(make_req(range(20, 30),
                                                            max_tokens=6))))
        toks = [t for o in out for t in o.token_ids]
        await engine.close()

        engine = JaxEngine(replace(engine_cfg, decode_window=4), seed=0)
        req = make_req(range(20, 30), max_tokens=6,
                       stop_token_ids=[toks[2]])
        out = await collect(engine.generate(Context(req)))
        got = [t for o in out for t in o.token_ids]
        assert got == toks[:3]
        assert out[-1].finish_reason == FinishReason.STOP
        assert engine._n_active == 0
        await engine.close()

    run(main())


def test_preemption_under_pool_pressure(run):
    """Pool exhaustion mid-decode must preempt (evict + resume) instead of
    truncating: every request completes its full max_tokens with exactly
    the tokens an uncontended run produces (ref vllm patch scheduler
    swap-preemption, patch:249-742)."""

    def cfg(blocks):
        return EngineConfig(
            model=ModelConfig.tiny(), num_blocks=blocks, block_size=4,
            max_batch_size=4, max_context=128, prefill_chunk=32,
        )

    # construct OUTSIDE the stall-guarded coroutine: a cold JaxEngine
    # ctor (param init + device_put, seconds on a cold jit cache) is
    # synchronous host work, and inside the guarded loop it would trip
    # the asyncio stall detector on standalone runs
    ref_engine = JaxEngine(cfg(64), seed=0)
    engine = JaxEngine(cfg(14), seed=0)

    async def main():
        prompts = [list(range(10 + 7 * i, 22 + 7 * i)) for i in range(3)]

        # ground truth: roomy pool, sequential (no contention).
        # ignore_eos: the random tiny model's greedy rollout can emit the
        # declared eos id (511) mid-stream — this test pins preemption
        # geometry at exactly 24 tokens, not eos semantics
        want = []
        for p in prompts:
            out = await collect(ref_engine.generate(
                Context(make_req(p, max_tokens=24, ignore_eos=True))
            ))
            want.append([t for o in out for t in o.token_ids])
        await ref_engine.close()

        # starved pool: 3 requests x (12 prompt + 24 gen = 36 tokens = 9
        # blocks) vs 13 usable blocks -> must preempt to finish
        outs = await asyncio.gather(
            *[collect(engine.generate(Context(
                make_req(p, max_tokens=24, ignore_eos=True)
            ))) for p in prompts]
        )
        for i, out in enumerate(outs):
            toks = [t for o in out for t in o.token_ids]
            assert out[-1].finish_reason == FinishReason.LENGTH
            assert len(toks) == 24, f"req {i} truncated to {len(toks)}"
            assert toks == want[i], f"req {i} diverged after preemption"
        assert engine.stats["preemptions"] > 0
        assert engine._n_active == 0
        await engine.close()

    run(main())


def test_unservable_request_finishes_instead_of_hanging(run):
    """A request whose minimum block reservation exceeds the whole pool
    must finish (ERROR — a capacity misconfiguration, not an honest
    truncation) rather than head-of-line-block admission forever."""

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=4, block_size=4,
            max_batch_size=2, max_context=128, prefill_chunk=32,
        )
        engine = JaxEngine(cfg, seed=0)
        # 24-token prompt -> 8-block minimum vs 3 usable blocks
        big = make_req(range(10, 34), max_tokens=4)
        small = make_req(range(40, 46), max_tokens=2)
        out_big, out_small = await asyncio.gather(
            asyncio.wait_for(collect(engine.generate(Context(big))), 60),
            asyncio.wait_for(collect(engine.generate(Context(small))), 60),
        )
        assert out_big[-1].finish_reason == FinishReason.ERROR
        # the small request behind it still completes fully
        assert sum(len(o.token_ids) for o in out_small) == 2
        await engine.close()

    run(main())


@pytest.mark.parametrize(
    "gone", ["decode_layer_scan", "decode_merged", "kv_head_layout"]
)
def test_removed_engine_options_are_type_errors(gone):
    """Nothing selects the decode layer loop (``_decode_body`` derives it
    from ``use_pallas`` and the model) and the cache's kv-head order is
    a constant (``llama.KV_HEAD_LAYOUT``; foreign layouts are declared
    on the transfer metadata): the three options are not fields."""
    with pytest.raises(TypeError, match=gone):
        EngineConfig(model=ModelConfig.tiny(), **{gone: "interleaved"})


def test_mirror_decode_header_names_no_layer_loop():
    """The multi-host mirror's decode op: leader and followers derive
    the layer loop from the same inputs, so the wire header carries
    neither ``unroll`` nor ``merged`` and the leader's program runs."""
    import numpy as np

    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh
    from dynamo_tpu.parallel.multihost import StepMirror

    cfg = ModelConfig.tiny()
    mirror = StepMirror(make_mesh(MeshConfig(tp=2)), cfg)
    heads = []
    mirror._lead = lambda op, arrays, **extra: heads.append((op, extra))
    B, M, bs = 2, 4, 4
    params = mirror.shard_params(llama.init_params(cfg, jax.random.key(0)))
    kc, vc = mirror.init_cache(B * M + 1, bs)
    i32 = lambda *v: np.asarray(v, np.int32)  # noqa: E731
    toks, kc, vc = mirror.lead_decode(
        params, i32(3, 5), i32(0, 0),
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M), i32(1, 1),
        i32(0, 0), i32(0, 0), np.zeros(B, np.float32), i32(0, 0),
        np.ones(B, np.float32), kc, vc, n_steps=2,
    )
    assert toks.shape == (2, B)
    (op, extra), = heads
    assert op == "decode"
    assert sorted(extra) == ["chain", "lp", "n", "pallas", "penalized"]

    import inspect

    for fn in (mirror.lead_decode, mirror._decode_fn):
        assert not {"unroll", "merged"} & set(inspect.signature(fn).parameters)


def test_commit_respects_written_horizon(run, engine_cfg, shared_engine):
    """A block whose last KV row is the just-sampled (not-yet-written)
    token must NOT enter the prefix-reuse pool: a concurrent prefix hit
    would attend garbage. Decode-side commits (seq placed in a batch
    slot) must lag one token behind seq_len; they catch up on the next
    dispatch once the pending token's KV is written."""

    async def main():
        engine = shared_engine
        bs = engine.cfg.block_size  # 4
        decode_commits = []
        orig = engine.kv.commit

        def spy(hold, tokens, written_len, chunk=False):
            orig(hold, tokens, written_len, chunk)
            # a decode-window site (a prefill commits its prompt, whole)
            if written_len == len(tokens) - 1:
                decode_commits.append((hold.committed * bs, len(tokens)))

        engine.kv.commit = spy
        try:
            # prompt 11 + admission token = 12, then window=4 dispatches
            # land a commit exactly at the seq_len=16 block boundary while
            # token 15's KV is still pending. ignore_eos: an incidental
            # eos id (511) in the greedy rollout would end the stream
            # before the boundary geometry this test depends on
            req = make_req(range(30, 41), max_tokens=8, ignore_eos=True)
            await collect(engine.generate(Context(req)))
        finally:
            engine.kv.commit = orig
        boundary = [c for c, sl in decode_commits if sl % bs == 0]
        assert boundary, "no window ended on a block boundary — bad geometry"
        for committed_tokens, seq_len in decode_commits:
            assert committed_tokens <= seq_len - 1, (
                f"committed {committed_tokens} tokens but only "
                f"{seq_len - 1} have written KV"
            )

    run(main())


def test_engine_prefix_cache_hit(run, engine_cfg, shared_engine):
    async def main():
        engine = shared_engine
        base = engine.stats["prefix_cache_hits_tokens"]
        prompt = list(range(30, 46))  # 16 tokens = 4 full blocks
        await collect(engine.generate(Context(make_req(prompt, max_tokens=2))))
        await collect(engine.generate(Context(make_req(prompt, max_tokens=2))))
        # second run must reuse at least 3 full blocks (last block recomputed)
        assert engine.stats["prefix_cache_hits_tokens"] - base >= 12

    run(main())


def test_engine_concurrent_requests_batch(run, engine_cfg, shared_engine):
    async def main():
        engine = shared_engine
        reqs = [make_req(range(50 + i, 60 + i), max_tokens=5, seed=i) for i in range(3)]
        outs = await asyncio.gather(
            *[collect(engine.generate(Context(r))) for r in reqs]
        )
        for out in outs:
            toks = [t for o in out for t in o.token_ids]
            assert len(toks) == 5
            assert out[-1].finish_reason == FinishReason.LENGTH
        # all sequences finished and freed their blocks
        assert engine._n_active == 0

    run(main())


def test_engine_cancellation(run, engine_cfg, shared_engine):
    async def main():
        engine = shared_engine
        ctx = Context(make_req(range(70, 80), max_tokens=100))
        got = []
        async for out in engine.generate(ctx):
            got.append(out)
            if len(got) == 2:
                ctx.context.stop_generating()
        assert got[-1].finish_reason == FinishReason.CANCELLED
        assert engine._n_active == 0

    run(main())


def test_engine_stop_token(run, engine_cfg, shared_engine):
    async def main():
        engine = shared_engine
        # run one greedy request, find its 3rd token, then use it as a stop id
        probe = await collect(
            engine.generate(Context(make_req(range(90, 100), max_tokens=5)))
        )
        toks = [t for o in probe for t in o.token_ids]
        req = make_req(range(90, 100), max_tokens=5, stop_token_ids=[toks[2]])
        out = await collect(engine.generate(Context(req)))
        got = [t for o in out for t in o.token_ids]
        assert got == toks[:3]
        assert out[-1].finish_reason == FinishReason.STOP

    run(main())


def test_engine_metrics_shape(run, engine_cfg, shared_engine):
    async def main():
        m = shared_engine.load_metrics()
        assert set(m) >= {
            "kv_active_blocks", "kv_total_blocks", "gpu_cache_usage_perc",
            "request_active_slots", "request_total_slots", "num_requests_waiting",
        }
        assert m["kv_total_blocks"] == 63

    run(main())


def test_chunked_prefill_interleaves_decode(run, engine_cfg):
    """A long prompt prefills in chunks (one per scheduler iteration) while
    an already-running sequence keeps streaming decode tokens between
    chunks — long prompts must not stall the running batch."""

    async def main():
        engine = JaxEngine(engine_cfg, seed=0)
        decode_steps_during_chunk: list[int] = []
        orig_chunk = engine._prefill_chunk_device
        orig_mixed = engine._dispatch_mixed

        def spy_chunk(st):
            decode_steps_during_chunk.append(engine.stats["decode_steps"])
            return orig_chunk(st)

        def spy_mixed(st):
            # mixed-batch chunks: the chunk rides the decode step itself
            decode_steps_during_chunk.append(engine.stats["decode_steps"])
            return orig_mixed(st)

        engine._prefill_chunk_device = spy_chunk
        engine._dispatch_mixed = spy_mixed

        # start a short-prompt sequence that decodes for a while
        short = collect(
            engine.generate(Context(make_req(range(10, 14), max_tokens=30)))
        )
        t_short = asyncio.ensure_future(short)
        while engine.stats["decode_steps"] == 0:
            await asyncio.sleep(0.01)
        # now a 100-token prompt: 4 chunks of 32 with prefill_chunk=32
        long_out = await collect(
            engine.generate(Context(make_req(range(100, 200), max_tokens=2)))
        )
        out_short = await t_short
        assert long_out[-1].finish_reason is not None
        assert sum(len(o.token_ids) for o in out_short) == 30
        # the long prompt took several chunks...
        assert len(decode_steps_during_chunk) >= 4
        # ...and decode steps advanced while the chunks were running
        assert decode_steps_during_chunk[-1] > decode_steps_during_chunk[0]
        await engine.close()

    run(main())


# ---------------- pipelined decode (decode_pipeline=True) ----------------


def test_pipelined_decode_matches_unpipelined(run):
    """With decode_pipeline=True and no pool contention, the token streams
    (greedy AND sampled) must be bit-identical to the unpipelined engine —
    the chained device windows use the same PRNG steps and positions."""

    async def main():
        outs = {}
        for pipe in (False, True):
            cfg = EngineConfig(
                model=ModelConfig.tiny(), num_blocks=64, block_size=4,
                max_batch_size=4, decode_window=4, decode_pipeline=pipe,
            )
            engine = JaxEngine(cfg, seed=0)
            reqs = [
                make_req(range(10, 20), max_tokens=17),
                make_req(range(30, 38), max_tokens=17,
                         temperature=0.9, seed=7),
            ]
            results = await asyncio.gather(
                *[collect(engine.generate(Context(r))) for r in reqs]
            )
            outs[pipe] = [
                [t for o in out for t in o.token_ids] for out in results
            ]
            for out in results:
                assert out[-1].finish_reason == FinishReason.LENGTH
            await engine.close()
        assert outs[True] == outs[False]

    run(main())


def test_pipelined_cancellation_mid_stream(run):
    """Cancelling a request while windows are in flight must terminate its
    stream promptly and leave the engine serving others."""

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=64, block_size=4,
            max_batch_size=4, decode_window=4, decode_pipeline=True,
        )
        engine = JaxEngine(cfg, seed=0)
        ctx = Context(make_req(range(10, 20), max_tokens=64))
        stream = engine.generate(ctx)
        got = 0
        async for out in stream:
            got += len(out.token_ids)
            if got >= 4:
                ctx.context.stop_generating()
        # engine still serves new requests afterwards
        out = await collect(
            engine.generate(Context(make_req(range(40, 50), max_tokens=5)))
        )
        assert out[-1].finish_reason == FinishReason.LENGTH
        assert len([t for o in out for t in o.token_ids]) == 5
        await engine.close()

    run(main())


def test_pipelined_preemption_completes_all(run):
    """Under pool starvation with pipelining on, every request still
    completes its full max_tokens (preemption, never truncation); the
    tokens may differ from the uncontended stream only after a replay
    whose prefix blocks were evicted (recompute numerics)."""

    cfg = EngineConfig(
        model=ModelConfig.tiny(), num_blocks=14, block_size=4,
        max_batch_size=4, max_context=128, prefill_chunk=32,
        decode_window=4, decode_pipeline=True,
    )
    # ctor outside the stall-guarded coroutine (cold-cache param init is
    # synchronous seconds-long host work; see
    # test_preemption_under_pool_pressure)
    engine = JaxEngine(cfg, seed=0)

    async def main():
        prompts = [list(range(10 + 7 * i, 22 + 7 * i)) for i in range(3)]
        # ignore_eos: full-length completion is the property under test;
        # an incidental eos id (511) in the rollout is not a truncation
        outs = await asyncio.gather(
            *[collect(engine.generate(Context(
                make_req(p, max_tokens=24, ignore_eos=True)
            ))) for p in prompts]
        )
        for i, out in enumerate(outs):
            toks = [t for o in out for t in o.token_ids]
            assert out[-1].finish_reason == FinishReason.LENGTH
            assert len(toks) == 24, f"req {i} truncated to {len(toks)}"
        assert engine._n_active == 0 and engine._inflight is None
        await engine.close()

    run(main())


def test_pipelined_context_limit_not_truncated_early(run):
    """A sequence approaching max_context with a window in flight must
    still generate up to the true limit: the speculative pending-window
    block requirement must not trigger a premature LENGTH finish that
    discards in-flight tokens (regression: drain-and-repick before the
    context-limit check)."""

    async def main():
        outs = {}
        for pipe in (False, True):
            cfg = EngineConfig(
                model=ModelConfig.tiny(), num_blocks=64, block_size=4,
                max_batch_size=2, max_context=32, decode_window=4,
                decode_pipeline=pipe,
            )
            engine = JaxEngine(cfg, seed=0)
            # 12-token prompt, ask for more than fits: must emit exactly
            # max_context - prompt_len = 20 tokens, not fewer
            out = await collect(
                engine.generate(Context(make_req(range(10, 22), max_tokens=64)))
            )
            toks = [t for o in out for t in o.token_ids]
            assert out[-1].finish_reason == FinishReason.LENGTH
            outs[pipe] = toks
            await engine.close()
        assert len(outs[True]) == len(outs[False]) == 20
        assert outs[True] == outs[False]

    run(main())


def test_pipelined_repick_never_grows_window(run):
    """Regression (advisor r2 medium): when a mid-provisioning drain
    re-picks the fused window, the new n must be CLAMPED to the value the
    earlier-validated sequences were provisioned for — a drain that
    finishes a headroom-constraining sequence could otherwise return a
    larger n and write past their allocated blocks (silent corruption via
    reserved page 0). Mixed max_tokens make one sequence finish mid-flight
    (the headroom constrainer); tight pools force the drain path. Streams
    must match the unpipelined engine bit-for-bit whenever neither run
    preempted."""

    async def main():
        for num_blocks in (18, 20, 24, 64):
            outs, preempts = {}, {}
            for pipe in (False, True):
                # mixed_batch off: this pins the ALTERNATING scheduler's
                # pipelined-repick clamp (still shipped: mirrors, ring
                # chunks, and the mixed_batch=False escape hatch run it).
                # The pipe-vs-nopipe preemption-count equality relies on
                # the two schedules staying in lockstep, which the fused
                # mixed path legitimately shifts near the pool cliff —
                # its preemption behavior is pinned by
                # tests/test_mixed_batch.py instead.
                cfg = EngineConfig(
                    model=ModelConfig.tiny(), num_blocks=num_blocks,
                    block_size=4, max_batch_size=4, max_context=64,
                    prefill_chunk=32, decode_window=8, decode_pipeline=pipe,
                    mixed_batch=False,
                )
                engine = JaxEngine(cfg, seed=0)
                reqs = [
                    make_req(range(10, 18), max_tokens=5),   # constrainer
                    make_req(range(30, 42), max_tokens=30),
                    make_req(range(50, 60), max_tokens=26),
                ]
                results = await asyncio.gather(
                    *[collect(engine.generate(Context(r))) for r in reqs]
                )
                outs[pipe] = [
                    [t for o in out for t in o.token_ids] for out in results
                ]
                preempts[pipe] = engine.stats["preemptions"]
                assert engine._n_active == 0 and engine._inflight is None
                await engine.close()
            for i, (a, b) in enumerate(zip(outs[False], outs[True])):
                assert len(b) == len(a), (
                    f"blocks={num_blocks} req {i}: pipelined len {len(b)} "
                    f"!= unpipelined {len(a)}"
                )
            if preempts[False] == preempts[True] == 0:
                assert outs[True] == outs[False], f"blocks={num_blocks}"
            # pipelining must not preempt when the unpipelined engine
            # didn't (the speculative window requirement is shed by the
            # drain, never by eviction)
            if preempts[False] == 0:
                assert preempts[True] == 0, f"blocks={num_blocks}"

    run(main())


# ---------------- sampling penalties ----------------


def _pen_req(tokens, max_tokens=16, **so):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0, **so),
        eos_token_ids=[],
    )


def test_frequency_penalty_breaks_greedy_loops(run):
    """A greedy tiny model degenerates into repeating one token; a strong
    frequency penalty must break the loop (counts accumulate on device
    through the fused windows)."""

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=64, block_size=4,
            max_batch_size=2, max_context=128, decode_window=4,
        )
        engine = JaxEngine(cfg, seed=0)
        # long enough that the random tiny model's greedy rollout enters
        # a cycle (short rollouts may not loop for every init seed)
        plain = await collect(
            engine.generate(Context(_pen_req(range(10, 20), max_tokens=48)))
        )
        pen = await collect(
            engine.generate(Context(_pen_req(
                range(10, 20), max_tokens=48, frequency_penalty=5.0
            )))
        )
        toks_plain = [t for o in plain for t in o.token_ids]
        toks_pen = [t for o in pen for t in o.token_ids]
        assert len(toks_pen) == 48

        def max_mult(toks):
            return max(toks.count(t) for t in set(toks))

        # the penalty must strictly reduce the worst repetition
        assert max_mult(toks_pen) < max_mult(toks_plain), (toks_plain, toks_pen)
        await engine.close()

    run(main())


def test_penalized_window_matches_single_step(run):
    """Fused windows with penalties must produce the exact stream of
    1-step... 2-step dispatch (the counts carry updates per step on
    device; spec_gamma requires window >= 2 so compare 2 vs 4)."""

    async def main():
        outs = {}
        for window in (2, 4):
            cfg = EngineConfig(
                model=ModelConfig.tiny(), num_blocks=64, block_size=4,
                max_batch_size=2, decode_window=window,
            )
            engine = JaxEngine(cfg, seed=0)
            out = await collect(engine.generate(Context(_pen_req(
                range(30, 40), max_tokens=15, frequency_penalty=2.0,
                presence_penalty=0.5, repetition_penalty=1.2,
            ))))
            outs[window] = [t for o in out for t in o.token_ids]
            await engine.close()
        assert len(outs[2]) == 15
        assert outs[2] == outs[4]

    run(main())


def test_repetition_penalty_applies_to_first_token(run):
    """A huge repetition penalty on a prompt whose greedy continuation
    would repeat a prompt token must change the FIRST generated token too
    (the penalty covers the prompt)."""

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=64, block_size=4,
            max_batch_size=2, decode_window=4,
        )
        engine = JaxEngine(cfg, seed=0)
        prompt = list(range(10, 20))
        plain = await collect(
            engine.generate(Context(_pen_req(prompt, max_tokens=1)))
        )
        first_plain = plain[0].token_ids[0]
        # force the penalty scenario: make the greedy-first token part of
        # the prompt, then penalize hard
        prompt2 = prompt + [first_plain]
        plain2 = await collect(
            engine.generate(Context(_pen_req(prompt2, max_tokens=1)))
        )
        pen2 = await collect(
            engine.generate(Context(_pen_req(
                prompt2, max_tokens=1, repetition_penalty=50.0
            )))
        )
        # with the huge penalty the first token must avoid prompt tokens
        # whenever the unpenalized choice was a prompt token
        if plain2[0].token_ids[0] in prompt2:
            assert pen2[0].token_ids[0] not in prompt2
        await engine.close()

    run(main())


def test_pipelined_decode_survives_idle_transitions(run):
    """Lost-wakeup regression (round 5): with decode_pipeline on, the
    idle path AWAITS the inflight drain between its emptiness check and
    _wake.clear() — requests arriving in that window had their wakeup
    erased and the scheduler slept on a non-empty queue forever. Waves
    separated by idle gaps reproduce it; wait_for turns the hang into a
    failure."""
    import asyncio

    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime import Context, collect

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=256, block_size=16,
            max_batch_size=8, max_context=128, prefill_chunk=32,
            decode_pipeline=True, decode_window=8,
        )
        eng = JaxEngine(cfg, seed=0)

        def mkreq(i):
            return Context(PreprocessedRequest(
                token_ids=[100 + i] * 40,
                stop_conditions=StopConditions(max_tokens=12),
                sampling_options=SamplingOptions(temperature=0.0),
                eos_token_ids=[],
            ).to_dict())

        async def one(i):
            out = await collect(eng.generate(mkreq(i)))
            assert any(getattr(o, "finish_reason", None) for o in out)

        for wave in range(3):
            await asyncio.wait_for(
                asyncio.gather(*(one(wave * 12 + i) for i in range(12))),
                timeout=180,
            )
            await asyncio.sleep(0.05)  # let the scheduler go idle
        await eng.close()

    run(main())


def test_out_of_vocab_prompt_rejected(run):
    """Out-of-vocab token ids must be rejected loudly: their embedding
    gather is IMPLEMENTATION-DEFINED (XLA clamps on one device, a
    multi-process sharded mesh lands OOB rows differently), so the same
    request can legally produce different streams on different meshes —
    the test_multihost_compose "cancel-after-restore token mismatch"
    was exactly this, OOB prompt ids masquerading as an engine bug."""

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=32, block_size=4,
            max_batch_size=2, max_context=64,
        )
        eng = JaxEngine(cfg, seed=0)
        V = cfg.model.vocab_size
        for bad in ([1, 2, V], [1, -1, 2], [V + 100] * 8):
            out = await collect(eng.generate(Context(PreprocessedRequest(
                token_ids=bad,
                stop_conditions=StopConditions(max_tokens=2),
                sampling_options=SamplingOptions(temperature=0.0),
                eos_token_ids=[],
            ))))
            assert out[-1].finish_reason == FinishReason.ERROR
            assert "out of range" in (out[-1].text or "")
        # in-vocab boundary ids still serve
        ok = await collect(eng.generate(Context(PreprocessedRequest(
            token_ids=[0, V - 1, 1],
            stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[],
        ))))
        assert sum(len(o.token_ids) for o in ok) == 2
        await eng.close()

    run(main())


def test_spec_engages_under_pipelining(run):
    """Pipelined decode must not starve speculation forever: the stale
    probe lags the tail by one window, so a stale hit whose fresh
    re-probe misses must dispatch ONE unchained window (next probe sees
    a fresh tail) instead of re-entering the pipeline — before this, a
    spec_gamma + decode_pipeline engine never accepted a single token
    on persistently repetitive streams."""

    async def main():
        cfg = EngineConfig(
            model=ModelConfig.tiny(), num_blocks=64, block_size=4,
            max_batch_size=2, max_context=256, prefill_chunk=8,
            spec_gamma=3, decode_pipeline=True, decode_window=4,
        )
        eng = JaxEngine(cfg, seed=0)
        out = await collect(eng.generate(Context(PreprocessedRequest(
            token_ids=[11, 12, 13, 14] * 6,
            stop_conditions=StopConditions(max_tokens=96, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[],
        ))))
        assert sum(len(o.token_ids) for o in out) == 96
        assert eng.stats["spec_accepted"] > 0, eng.stats
        await eng.close()

    run(main())
