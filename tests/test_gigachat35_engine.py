"""GigaChat 3.5 through the ENGINE: the two-part state (convolution rows,
float32 delta-rule matrices) has to follow a sequence through decode
slots, chunked and mixed prefill, the SPARSE snapshot pool and
preemption. Logits (the server's reported logprobs) against the plain
float32 reference of
``chipbench/configs/gigachat3.5-432b-a28b/reference.py`` at the tiny size
of ``tests/test_gigachat35.py``, whose fixtures these are."""

import asyncio
import dataclasses
import functools

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import Block
from dynamo_tpu.engine.engine import STATE_COUNTERS
from dynamo_tpu.engine.kv_manager import SNAPSHOT_COUNTERS, SnapshotPool
from dynamo_tpu.models import llama
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime import Context, collect
from tests.test_gigachat35 import ATOL, BS, _logp, forward, tiny  # noqa: F401


def _request(prompt, max_tokens, logprobs=4):
    return Context(PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, logprobs=logprobs),
        eos_token_ids=[],
    ))


@pytest.fixture()
def sparse(monkeypatch):
    """The tiny state is 34 KiB a row: lower the line under which a state
    gets a row a block, so that the tiny model takes the sparse pool the
    published widths take."""
    monkeypatch.setattr(llama, "SNAPSHOT_ROW_A_BLOCK_BYTES", 1024)


def _engine(cfg, params, **kw):
    base = dict(num_blocks=64, block_size=BS, max_batch_size=4,
                max_context=128, prefill_chunk=16, state_snapshots=8)
    base.update(kw)
    return JaxEngine(EngineConfig(model=cfg, **base), params=params)


async def _serve(engine, prompt, max_tokens):
    out = await collect(engine.generate(_request(prompt, max_tokens)))
    toks = [t for o in out for t in o.token_ids]
    lps = [e for o in out for e in (o.logprobs or [])]
    assert len(toks) == len(lps) == max_tokens
    return toks, lps


def _check(forward, params, hf, prompt, toks, lps):
    want = _logp(forward(params, hf, list(prompt) + toks[:-1]))
    for i, (tok, entry) in enumerate(zip(toks, lps)):
        row = want[len(prompt) - 1 + i]
        assert tok == int(np.argmax(row)), i
        np.testing.assert_allclose(entry["logprob"], row[tok], atol=ATOL)
        for tid, lp in entry["top"]:
            np.testing.assert_allclose(lp, row[tid], atol=ATOL)


def _stats(engine):
    """The scheduler's counters and the KV manager's, as one mapping."""
    return {**engine.stats, **engine.kv.stats}


def _delta(engine, before):
    return {k: _stats(engine)[k] - before[k] for k in (
        "prefix_cache_hits_tokens", *STATE_COUNTERS, *SNAPSHOT_COUNTERS)}


def test_prefix_hits_count_up_to_the_last_snapshot(forward, tiny, sparse):
    """(e) The first asker leaves a snapshot at its prompt's last full
    block only; a prompt that shares 20 tokens matches 5 blocks but finds
    no snapshot there: the hit is cut to nothing, the tokens are counted
    as unsnapshotted, and ITS chunk that ends at the match leaves the
    snapshot; the third asker restores it. A repeat of the whole prompt
    restores the first one's. Logits as the reference's throughout."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(21)
    prompt = [int(t) for t in rng.integers(16, 512, 38)]
    fork = lambda n: prompt[:22] + [  # noqa: E731
        int(t) for t in rng.integers(16, 512, n)]

    async def main():
        engine = _engine(cfg, params)
        assert not engine.kv.snapshots.dense and engine.kv.snapshots.rows == 8
        # (prompt, tokens matched, tokens skipped, snapshots it takes)
        steps = ((prompt, 0, 0, 1),       # at 36, its last full block
                 (fork(9), 20, 0, 2),     # at 20 (pays) and at 28
                 (fork(11), 20, 20, 1),   # restores 20; leaves one at 32
                 (prompt, 36, 36, 0))     # the whole prompt again
        for p, matched, hit, snaps in steps:
            before = _stats(engine)
            toks, lps = await _serve(engine, p, 6)
            _check(forward, params, hf, p, toks, lps)
            got = _delta(engine, before)
            assert got["prefix_matched_tokens"] == matched
            assert got["prefix_cache_hits_tokens"] == hit
            assert got["prefix_unsnapshotted_tokens"] == matched - hit
            assert got["state_restores"] == (hit > 0)
            assert got["state_snapshots"] == snaps, p
            assert got["linear_state_bytes"] > 0
        m = engine.device_path_stats()
        assert m["engine_prefix_unsnapshotted_tokens_total"] == 20
        assert m["engine_state_snapshot_evictions_total"] == 0
        assert m["engine_state_restores_total"] == 2
        assert m["engine_linear_state_bytes_total"] == engine.stats[
            "linear_state_bytes"]
        await engine.close()

    asyncio.run(main())


def test_snapshot_rows_are_reused_and_a_lost_snapshot_cuts_the_hit(
        forward, tiny, sparse):
    """Two rows only: a third snapshot takes the least recently used
    row, the block that lost it answers no more, and the next asker of
    that prefix computes it again (and still gets the reference's
    logits)."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(24)
    prompts = [[int(t) for t in rng.integers(16, 512, 17)] for _ in range(3)]

    async def main():
        engine = _engine(cfg, params, state_snapshots=2)
        for p in prompts:
            await _serve(engine, p, 2)
        assert engine.kv.stats["state_snapshot_evictions"] == 1
        for p, hit in ((prompts[2], 16), (prompts[0], 0)):
            before = _stats(engine)
            toks, lps = await _serve(engine, p, 4)
            _check(forward, params, hf, p, toks, lps)
            got = _delta(engine, before)
            assert got["prefix_matched_tokens"] == 16
            assert got["prefix_cache_hits_tokens"] == hit
        await engine.close()

    asyncio.run(main())


def test_chunks_mixed_steps_and_slot_reuse(forward, tiny, sparse):
    """(b, d) Three requests at once through two decode slots: prompts
    longer than the mixed step's budget ride several mixed steps beside a
    decoding row (chunks that cross the delta rule's blocks and end where
    a snapshot is wanted), and the third request takes the slot of a
    sequence that has finished (no stale state in either part)."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(22)
    prompts = [[int(t) for t in rng.integers(16, 512, n)]
               for n in (85, 37, 11)]

    async def main():
        engine = _engine(cfg, params, max_batch_size=2, mixed_step_budget=32,
                         mixed_max_prefills=1)
        outs = await asyncio.gather(*[
            _serve(engine, p, n) for p, n in zip(prompts, (12, 5, 9))])
        for p, (toks, lps) in zip(prompts, outs):
            _check(forward, params, hf, p, toks, lps)
        assert engine.stats["mixed_steps"] >= 3
        await engine.close()

    asyncio.run(main())


def test_preempts_and_resumes_with_its_state(forward, tiny, sparse):
    """(f) A pool too small for three sequences: a preempted sequence is
    replayed from the last of its blocks that holds a snapshot (or from
    token 0), and every stream's logits stay the reference's."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(16, 512, 12)] for _ in range(3)]

    async def main():
        engine = _engine(cfg, params, num_blocks=14, prefill_chunk=32)
        outs = await asyncio.gather(*[
            _serve(engine, p, 24) for p in prompts])
        assert engine.stats["preemptions"] > 0
        for p, (toks, lps) in zip(prompts, outs):
            _check(forward, params, hf, p, toks, lps)
        await engine.close()

    asyncio.run(main())


def test_a_small_state_gets_a_row_a_block_and_the_same_rule(forward, tiny):
    """Without the lowered line the tiny state is small enough for a row
    a block (64 rows here), but WHERE a snapshot can be taken is the
    kind's rule, not the size's: a recurrent matrix still has one at a
    chunk's end only, so the pool is a map all the same, and a repeat
    hits the snapshot at the prompt's last full block."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(25)
    prompt = [int(t) for t in rng.integers(16, 512, 22)]

    async def main():
        engine = _engine(cfg, params)
        assert engine.kv.snapshots.rows == 64 and not engine.kv.snapshots.dense
        for hit in (0, 20):
            before = _stats(engine)
            toks, lps = await _serve(engine, prompt, 3)
            _check(forward, params, hf, prompt, toks, lps)
            assert _delta(engine, before)["prefix_cache_hits_tokens"] == hit
        await engine.close()

    asyncio.run(main())


def test_counts_held_assignments_and_state_bytes(tiny, sparse):
    _hf, cfg, params = tiny

    async def main():
        engine = _engine(cfg, params)
        await _serve(engine, list(range(20, 31)), 5)
        s = engine.stats
        # 4 expert layers of 5; 11 prompt rows + 4 decoded rows, 4 a token
        assert s["moe_assignments"] == 4 * 4 * (11 + 4)
        assert 0 < s["moe_held_assignments"] < s["moe_assignments"]
        assert s["moe_expert_slots"] % (4 * cfg.experts_held) == 0
        m = engine.device_path_stats()
        assert m["engine_moe_held_assignments_total"] == s[
            "moe_held_assignments"]
        row = 4 * 4 * 16 * 16 * 4  # 4 layers x 4 heads x [16, 16] float32
        assert s["linear_state_bytes"] % (2 * row) == 0
        assert m["engine_state_bytes"] == sum(
            a.size * a.dtype.itemsize for a in engine.state.values())
        await engine.close()

    asyncio.run(main())


@pytest.fixture()
def kernels_on(monkeypatch):
    """The engine as the chip runs it, where the CPU can: decode windows
    and mixed steps with the kernels on in interpret mode (``use_pallas``
    follows the backend, so a test sets it on the engine it has built);
    prefill chunks (alone or in a mixed step) stay on the XLA path."""
    prefill, walks = llama.prefill, llama.linear_step_walks_live
    monkeypatch.setattr(
        llama, "linear_step_walks_live",
        lambda cfg, use_pallas, interpret=False: walks(cfg, use_pallas, True))
    for name in ("decode_window", "mixed_step"):
        monkeypatch.setattr(llama, name, functools.partial(
            getattr(llama, name), interpret=True))

    def xla(fn):
        return lambda *a, **kw: fn(*a, **dict(kw, use_pallas=False))

    alone = xla(prefill)
    alone.__wrapped__ = xla(prefill.__wrapped__)  # a mixed step's chunks
    monkeypatch.setattr(llama, "prefill", alone)


def test_a_decode_step_counts_and_moves_its_live_rows(
        forward, tiny, sparse, kernels_on):
    """Two sequences decode in an engine of four slots while a third is
    admitted in chunks beside them: a decode step adds the TWO live rows'
    matrices to ``linear_state_bytes``, read and written, not the four
    slots' (the kernel walks the live rows; with kernels off every slot
    counts, as the plain step carries every slot). The slot the third
    sequence owns while it is still prefilling is dead to the decode
    group of every mixed step that carries its chunks, in the SAME
    program and after the chunk has written it: the kernel leaves its
    matrices where they lie, or the third stream's logits would not be
    the reference's. Every stream's are."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(27)
    short = [[int(t) for t in rng.integers(16, 512, n)] for n in (9, 13)]
    long = [int(t) for t in rng.integers(16, 512, 40)]
    row = 4 * 4 * 16 * 16 * 4  # 4 layers x 4 heads x [16, 16] float32

    async def main():
        engine = _engine(cfg, params, mixed_step_budget=16,
                         mixed_max_prefills=1)
        assert engine._rec_row_bytes == row and not engine.use_pallas
        engine._note_state(0, 1, 2)
        assert engine.stats["linear_state_bytes"] == 2 * row * 4
        engine.use_pallas = True
        engine.stats["linear_state_bytes"] = 0
        seen = []  # (segments, decode steps, live rows, bytes added)
        note = engine._note_state

        def spy(segments, decode_steps=0, live_rows=0):
            before = engine.stats["linear_state_bytes"]
            note(segments, decode_steps, live_rows)
            seen.append((segments, decode_steps, live_rows,
                         engine.stats["linear_state_bytes"] - before))

        engine._note_state = spy
        first = [asyncio.ensure_future(_serve(engine, p, 10)) for p in short]
        while engine.stats["decode_steps"] < 2:
            await asyncio.sleep(0.01)
        third = asyncio.ensure_future(_serve(engine, long, 3))
        outs = [await f for f in first] + [await third]
        for p, (toks, lps) in zip(short + [long], outs):
            _check(forward, params, hf, p, toks, lps)
        assert engine.stats["mixed_steps"] >= 3
        for segments, steps, live, added in seen:
            assert added == 2 * row * (segments + steps * live)
        # decode windows of both short streams alone: two rows a step
        assert any(seg == 0 and live == 2 for seg, _s, live, _a in seen)
        # the long prompt's chunks beside them: its own segment and two rows
        assert any(seg == 1 and live == 2 for seg, _s, live, _a in seen)
        assert all(live <= 3 for _seg, _s, live, _a in seen)
        assert engine.stats["linear_state_bytes"] % (2 * row) == 0
        await engine.close()

    asyncio.run(main())


@pytest.mark.parametrize("kw,word", [
    (dict(spec_gamma=2, decode_window=4), "spec_gamma"),
    (dict(mesh=MeshConfig(tp=2)), "mesh"),
    (dict(host_cache_blocks=8), "KV tiers"),
    (dict(kv_cache_dtype="int8"), "int8"),
    (dict(ring_prefill_threshold=64), "ring"),
])
def test_engine_refuses_what_cannot_carry_the_state(tiny, kw, word):
    _hf, cfg, params = tiny
    with pytest.raises(ValueError, match=word) as e:
        _engine(cfg, params, **kw)
    assert "linear-attention" in str(e.value)


def test_no_state_on_the_wire(tiny):
    _hf, cfg, params = tiny
    engine = _engine(cfg, params)
    with pytest.raises(ValueError, match="per-sequence state"):
        engine.kv.refuse_transfer("prefill_extract (disaggregation)")


# ---------------- the pool's map, alone ----------------


def _block(idx, h):
    return Block(idx=idx, seq_hash=h)


def test_snapshot_pool_maps_blocks_to_rows_lru():
    pool = SnapshotPool(2, 100)
    a, b, c = _block(5, 50), _block(6, 60), _block(7, 70)
    assert not pool.dense and pool.row_of(a) == -1
    ra, rb = pool.take(a), pool.take(b)
    assert {ra, rb} == {0, 1} and pool.row_of(a) == ra  # a is now newest
    rc = pool.take(c)  # b was used least recently
    assert rc == rb and pool.evictions == 1
    assert pool.row_of(b) == -1 and pool.row_of(c) == rc
    # a recycled block id does not answer for other content
    assert pool.row_of(_block(5, 51)) == -1
    # a pinned row is not taken; with every row pinned there is none
    pool.pin(ra)
    d = _block(8, 80)
    assert pool.take(d) == rc and pool.row_of(a) == ra
    pool.pin(rc)
    assert pool.take(_block(9, 90)) == -1
    pool.unpin(ra)
    assert pool.take(_block(9, 90)) == ra


def test_snapshot_pool_binds_a_pending_snapshot_at_commit():
    pool = SnapshotPool(4, 100)
    blk = _block(3, None)  # its chunk is in flight: no hash yet
    row = pool.take(blk)
    assert pool.row_of(blk) == -1
    blk.seq_hash = 33
    pool.bind(blk)
    assert pool.row_of(blk) == row


def test_a_pool_written_at_block_ends_is_the_identity():
    pool = SnapshotPool(100, 100, at_block_ends=True)
    assert pool.dense and pool.row_of(_block(42, 1)) == 42
    with pytest.raises(ValueError, match="a row a block"):
        SnapshotPool(64, 100, at_block_ends=True)
