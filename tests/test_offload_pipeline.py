"""Async KV-tier pipeline (engine/offload.py + router-hinted prefetch):

  * eviction flushes run OFF the scheduler loop — decode windows keep
    streaming tokens while a d2h fetch is in flight — without corrupting
    restored prefixes,
  * the d2h pipeline is double-buffered and budgeted (pages the dispatch
    itself writes always flush),
  * a router-hinted prefetch lands the host chain on device before the
    request arrives, so TTFT beats a cold restore and the restore
    latency counts as hidden,
  * cancellation mid-upload rolls the reservation back into the pool.

Latency is injected through the module-level ``_device_fetch`` /
``_device_put`` hooks so a laptop-fast CPU transfer behaves like a busy
PCIe link.
"""

import asyncio
import time

import jax.numpy as jnp
import numpy as np

import dynamo_tpu.engine.offload as offload_mod
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.engine.engine import _Sequence
from dynamo_tpu.engine.offload import OffloadManager
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, collect


def _req(tokens, max_tokens=2):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, seed=0),
        eos_token_ids=[511],
    )


def _cfg(**kw):
    base = dict(
        model=ModelConfig.tiny(), num_blocks=17, block_size=4,
        max_batch_size=2, max_context=64, prefill_chunk=32,
        host_cache_blocks=64,
    )
    base.update(kw)
    return EngineConfig(**base)


# ---------------- manager-level: budget + double buffer ----------------


def test_flush_budget_and_double_buffer(monkeypatch):
    fetched = []
    real_fetch = offload_mod._device_fetch

    def slow_fetch(arr):
        time.sleep(0.15)
        fetched.append(time.monotonic())
        return real_fetch(arr)

    monkeypatch.setattr(offload_mod, "_device_fetch", slow_fetch)
    k = jnp.zeros((2, 2, 40, 4, 8), jnp.float32)
    v = jnp.zeros((2, 2, 40, 4, 8), jnp.float32)
    om = OffloadManager(64)
    for i in range(1, 31):
        om.on_evict(1000 + i, i)

    # budget takes 8 optional blocks; must_idxs ride along regardless
    om.flush_evictions_async(k, v, budget=8, must_idxs={29, 30})
    assert om.d2h_flush_async_total == 1
    assert len(om._pending) == 30 - 10  # 8 budget + 2 must
    assert {1029, 1030} <= set(om._inflight_flushes[0].hashes)
    # the dispatch returned while the fetch is still sleeping: off-loop
    assert not om._inflight_flushes[0].future.done()

    om.flush_evictions_async(k, v, budget=8)
    assert om.d2h_flush_async_total == 2
    # double buffer full: a third budgeted call must NOT open a gather
    om.flush_evictions_async(k, v, budget=8)
    assert om.d2h_flush_async_total == 2 and len(om._pending) == 12

    # reserve_chain joins only the flush holding the probed hash
    hashes, data = om.reserve_chain([1001, 1002])
    assert hashes == [1001, 1002] and len(data) == 2
    om.unreserve(hashes, data)

    # budget=None drains everything pending
    om.flush_evictions_async(k, v)
    for t in list(om._inflight_flushes):
        t.future.result()
    assert om.pool.stored_total == 30
    assert len(om.pool) == 30
    om.close()


# ---------------- engine-level: decode interleaves with flush ----------------


def test_decode_interleaves_with_async_flush(run, monkeypatch):
    """Forced evictions + slow d2h must not stall decode windows: tokens
    keep streaming while a flush is in flight, and the flushed prefix
    restores bit-exact afterwards (the acceptance gate: the scheduler
    loop never blocks on a d2h eviction flush)."""
    windows = []  # (start, end) of each fetch, once the phase is armed
    real_fetch = offload_mod._device_fetch

    def slow_fetch(arr):
        if not windows_armed:
            return real_fetch(arr)
        t0 = time.monotonic()
        time.sleep(0.2)
        out = real_fetch(arr)
        windows.append((t0, time.monotonic()))
        return out

    windows_armed = []
    monkeypatch.setattr(offload_mod, "_device_fetch", slow_fetch)
    engine = JaxEngine(_cfg(), seed=0)

    async def main():
        token_times = []

        async def run_b(base):
            async for o in engine.generate(
                Context(_req(range(base, base + 8), max_tokens=20))
            ):
                token_times.append(time.monotonic())

        async def churn(base):
            for i in range(4):
                filler = list(range(base + 30 * i, base + 30 * i + 24))
                await collect(engine.generate(Context(_req(filler, 2))))

        # every program of the measured wave is compiled first (the
        # prefill buckets and the decode-window ladder, then the same
        # wave on other tokens for its mixed steps): a cold compile
        # inside it stalls the stream for a second and decides by itself
        # which tokens fall into which fetch
        await engine.warmup()
        await asyncio.gather(run_b(700), churn(500))
        token_times.clear()
        windows_armed.append(True)

        prompt_a = list(range(100, 124))  # 6 blocks of 4
        out1 = await collect(engine.generate(Context(_req(prompt_a, 4))))
        toks1 = [t for o in out1 for t in o.token_ids]

        # long decode B records per-token arrival times while churn
        # prompts force evictions (and therefore async flushes) under it
        await asyncio.gather(run_b(400), churn(200))
        assert engine.offload.d2h_flush_async_total > 0

        # decode progressed while a d2h was in flight: at least one B
        # token landed strictly inside a fetch's sleep window
        overlapped = any(
            any(t0 < tt < t1 for t0, t1 in windows) for tt in token_times
        )
        assert overlapped, (windows, token_times)

        # flushed-then-restored prefix reproduces the greedy stream
        base_hits = engine.offload.pool.hit_blocks_total
        out2 = await collect(engine.generate(Context(_req(prompt_a, 4))))
        toks2 = [t for o in out2 for t in o.token_ids]
        assert engine.offload.pool.hit_blocks_total > base_hits
        assert toks1 == toks2, "async flush corrupted the restored prefix"
        stats = engine.offload.stats()
        assert stats["d2h_flush_async"] == engine.offload.d2h_flush_async_total
        await engine.close()

    run(main())


# ---------------- hinted prefetch vs cold restore ----------------


async def _park_in_host_tier(engine, prompt):
    """Serve ``prompt`` once, then churn until its blocks sit in the
    host pool; returns the greedy tokens of the first serve."""
    # warm the RESUME prefill bucket first: a restored-history prefill
    # only runs the prompt's short tail (bucket 16), a shape the full
    # prompt (bucket 32) never compiles — without this, both measured
    # paths pay the same one-time XLA compile inside the timed region
    # and the hinted-vs-cold ratio drowns in it
    await collect(engine.generate(Context(_req(range(450, 462), 2))))
    out = await collect(engine.generate(Context(_req(prompt, 2))))
    toks = [t for o in out for t in o.token_ids]
    for i in range(4):
        filler = list(range(200 + 30 * i, 200 + 30 * i + 24))
        await collect(engine.generate(Context(_req(filler, 2))))
    # wait for the background flushes to land the chain
    chain = [s for _l, s in sequence_block_hashes(prompt, 4)]
    for _ in range(100):
        if engine.offload.pool.match_chain(chain) >= 5:
            return toks
        await asyncio.sleep(0.02)
    raise AssertionError("prompt chain never landed in the host tier")


def test_hinted_prefetch_beats_cold_restore_ttft(run, monkeypatch):
    """A router hint restores the chain BEFORE the request arrives, so
    TTFT skips the (slow) h2d wait a cold restore pays, and the upload
    latency counts as hidden (restore_latency_hidden_frac > 0)."""
    real_put = offload_mod._device_put

    def slow_put(arr):
        time.sleep(0.3)
        return real_put(arr)

    monkeypatch.setattr(offload_mod, "_device_put", slow_put)
    prompt_a = list(range(100, 124))

    async def ttft(engine, prompt):
        t0 = time.monotonic()
        agen = engine.generate(Context(_req(prompt, 2)))
        async for _o in agen:
            break
        dt = time.monotonic() - t0
        async for _o in agen:
            pass
        return dt

    async def main():
        # cold: admission reserves the chain and the first prefill chunk
        # waits out the slow upload
        cold = JaxEngine(_cfg(), seed=0)
        toks_ref = await _park_in_host_tier(cold, prompt_a)
        ttft_cold = await ttft(cold, prompt_a)
        stats_cold = cold.offload.stats()
        await cold.close()
        assert ttft_cold >= 0.25, "cold restore should pay the h2d wait"
        assert stats_cold["h2d_prefetch_hits"] == 0

        # hinted: same engine history, but the router hint lands the
        # chain before the request is admitted
        hinted = JaxEngine(_cfg(), seed=0)
        toks_ref2 = await _park_in_host_tier(hinted, prompt_a)
        assert toks_ref2 == toks_ref
        pairs = sequence_block_hashes(prompt_a, 4)
        n = await hinted.prefetch_hint(pairs)
        assert n >= 5, f"prefetch restored only {n} blocks"
        ttft_hinted = await ttft(hinted, prompt_a)
        stats = hinted.offload.stats()
        await hinted.close()
        assert stats["h2d_prefetch_blocks_total"] >= 5
        assert stats["h2d_prefetch_hits"] >= 5, "claim must count hint hits"
        assert stats["restore_latency_hidden_frac"] > 0
        assert ttft_hinted < ttft_cold * 0.75, (ttft_hinted, ttft_cold)

    run(main())


# ---------------- cancellation mid-upload ----------------


def test_cancel_mid_upload_rolls_back(run, monkeypatch):
    """A request cancelled while its reserved chain is still uploading
    must hand the blocks back to the host pool (no leak, no corruption):
    a later identical request restores and reproduces the stream."""
    real_put = offload_mod._device_put

    def slow_put(arr):
        time.sleep(0.3)
        return real_put(arr)

    monkeypatch.setattr(offload_mod, "_device_put", slow_put)
    engine = JaxEngine(_cfg(), seed=0)
    prompt_a = list(range(100, 124))

    async def main():
        toks_ref = await _park_in_host_tier(engine, prompt_a)
        resident_before = len(engine.offload.pool)
        free_before = engine.kv.allocator.free_count
        ctx = Context(_req(prompt_a, 2))
        seq = _Sequence(
            request=ctx.data, context=ctx.context,
            out_queue=asyncio.Queue(), tokens=list(prompt_a),
            prompt_len=len(prompt_a),
        )
        assert engine._begin_prefill(seq)
        assert engine._prefill_states
        st = engine._prefill_states[0]
        assert st.upload is not None
        assert not st.upload.future.done(), "upload should still be in flight"
        # cancel while the h2d is mid-flight
        ctx.context.stop_generating()
        admitted = await engine._prefill_step()
        assert not admitted and not engine._prefill_states
        out = seq.out_queue.get_nowait()
        assert out.finish_reason is not None

        # reservation rolled back: pool regained the chain, device
        # blocks freed, the abandonment is counted
        assert len(engine.offload.pool) == resident_before
        assert engine.kv.allocator.free_count == free_before
        assert engine.offload.h2d_uploads_cancelled == 1

        # and the chain still restores, bit-exact
        base_hits = engine.offload.pool.hit_blocks_total
        out2 = await collect(engine.generate(Context(_req(prompt_a, 2))))
        toks2 = [t for o in out2 for t in o.token_ids]
        assert engine.offload.pool.hit_blocks_total > base_hits
        assert toks2 == toks_ref
        await engine.close()

    run(main())


# ---------------- sync escape hatch ----------------


def test_sync_escape_hatch_still_roundtrips(run):
    """offload_async=False keeps the legacy synchronous transfers."""
    engine = JaxEngine(_cfg(offload_async=True), seed=0)
    sync_engine = JaxEngine(_cfg(offload_async=False), seed=0)

    async def roundtrip(eng):
        prompt_a = list(range(100, 124))
        out1 = await collect(eng.generate(Context(_req(prompt_a, 4))))
        for i in range(4):
            filler = list(range(200 + 30 * i, 200 + 30 * i + 24))
            await collect(eng.generate(Context(_req(filler, 2))))
        out2 = await collect(eng.generate(Context(_req(prompt_a, 4))))
        await eng.close()
        return (
            [t for o in out1 for t in o.token_ids],
            [t for o in out2 for t in o.token_ids],
        )

    a1, a2 = run(roundtrip(engine))
    s1, s2 = run(roundtrip(sync_engine))
    assert a1 == a2 == s1 == s2
    assert engine.offload.d2h_flush_async_total > 0
    assert sync_engine.offload.d2h_flush_async_total == 0


def test_adopt_restored_duplicate_hash_never_leaks_blocks():
    """A prefetch racing its own request (the request commits the hash
    to the reuse pool while the upload is in flight) must not adopt a
    second block under the same hash — parking it would overwrite the
    reuse entry and orphan the original block forever."""
    from dynamo_tpu.engine.allocator import BlockAllocator

    alloc = BlockAllocator(num_blocks=9, block_size=4)
    total_free = alloc.free_count
    # the request's block: committed, then freed into the reuse pool
    (winner,) = alloc.allocate(1)
    h = alloc.commit_full_block(winner, [1, 2, 3, 4], None)
    alloc.free([winner])
    assert alloc.free_count == total_free

    # the racing prefetch: same hash, different block — must NOT adopt
    (loser,) = alloc.allocate(1)
    assert alloc.adopt_restored(loser, h, 123, None) is False
    assert loser.seq_hash is None
    alloc.free([loser])
    assert alloc.free_count == total_free, "duplicate adoption leaked a block"

    # the original entry still claims by hash
    matched = alloc.match_prefix([1, 2, 3, 4])
    assert [b.idx for b in matched] == [winner.idx]
    alloc.free(matched)
    assert alloc.free_count == total_free


def test_offload_stats_exported_via_load_metrics(run):
    engine = JaxEngine(_cfg(), seed=0)
    m = engine.load_metrics()
    for key in ("d2h_flush_async", "h2d_prefetch_hits",
                "restore_latency_hidden_frac"):
        assert key in m, key

    async def main():
        await engine.close()

    run(main())
