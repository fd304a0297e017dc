"""Mellum 2 through the ENGINE: the window pool (the window layers' KV
under a second allocator and a second block table a sequence) has to
follow a sequence through chunked and mixed prefill, decode windows,
preemption and the prefix cache, holding a window and not a history.
Logits (the server's reported logprobs) against the plain float32
reference of ``chipbench/configs/mellum2-12b-a2.5b/reference.py`` at the
tiny size of ``tests/test_mellum2.py`` (window 16, blocks of 4), whose
fixtures these are; contexts run to several windows."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.engine.kv_manager import WINDOW_COUNTERS
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime import Context, collect
from tests.test_mellum2 import ATOL, BS, W, _logp, forward, tiny  # noqa: F401

CHUNK = 16


def _request(prompt, max_tokens, logprobs=4):
    return Context(PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, logprobs=logprobs),
        eos_token_ids=[],
    ))


def _engine(cfg, params, **kw):
    base = dict(num_blocks=128, block_size=BS, max_batch_size=4,
                max_context=256, prefill_chunk=CHUNK, mixed_step_budget=CHUNK)
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


def _prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(16, 512, n)]


def _stats(engine):
    """The scheduler's counters and the KV manager's, as one mapping."""
    return {**engine.stats, **engine.kv.stats}


def _used(engine):
    return (engine.kv.allocator.used_count,
            engine.kv.window.allocator.used_count)


def _poison_released(engine):
    """From now on every block the window pool takes back from behind a
    window is filled with NaN at once, in K and in V, and stays so until
    the pool hands it out again (then it is zeroed: the XLA attention of
    these CPU tests multiplies a masked key's zero weight with what the
    block holds, and a fresh block's unwritten rows are masked keys)."""
    pool = engine.kv.window
    release = pool.release_behind

    def fill(blocks, value):
        idx = jnp.asarray(blocks)
        (kf, kw), (vf, vw) = engine.k_cache, engine.v_cache
        engine.k_cache = (kf, kw.at[:, :, idx].set(value))
        engine.v_cache = (vf, vw.at[:, :, idx].set(value))

    def release_and_poison(wblocks, floor, pos, *rest):
        held = [b.idx for b in wblocks[floor:] if b is not None]
        top = release(wblocks, floor, pos, *rest)
        still = {b.idx for b in wblocks[floor:] if b is not None}
        gone = [i for i in held if i not in still]
        if gone:
            fill(gone, jnp.nan)
            engine.poisoned = getattr(engine, "poisoned", 0) + len(gone)
        return top

    pool.release_behind = release_and_poison
    pool.allocator.on_allocated = lambda idx: fill([idx], 0.0)


def test_chunked_prefill_and_decode_hold_a_window_not_a_history(forward, tiny):
    """A prompt of five windows in chunks of one, then decode windows two
    more windows on, every released block poisoned with NaN the moment it
    goes back: logits as the reference's; the sequence never holds more
    than window + chunk (+ a block of slack) in the window pool, while
    its full-pool blocks grow with the context; both pools end empty."""
    hf, cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.kv.window.window == W
    _poison_released(engine)
    peak = [0, 0]
    step_done = engine._step_done

    def watching(*info):
        full, win = _used(engine)
        peak[0], peak[1] = max(peak[0], full), max(peak[1], win)
        step_done(*info)

    engine._step_done = watching
    prompt = _prompt(10, 5 * W + 3)

    async def run():
        toks, lps = await _serve(engine, prompt, 2 * W)
        await engine.close()
        return toks, lps

    toks, lps = asyncio.run(run())
    _check(forward, params, hf, prompt, toks, lps)
    assert engine.poisoned >= (len(prompt) + len(toks) - 2 * W) // BS
    assert peak[1] <= -(-(W + CHUNK) // BS) + 1
    assert peak[0] >= (len(prompt) + len(toks)) // BS
    assert _used(engine) == (0, 0)
    st = engine.kv.stats
    assert 0 < st["kv_window_resident_tokens"] < st["kv_window_context_tokens"] / 3
    assert 0 < st["attn_window_pages"] < st["attn_window_context_pages"] / 3
    # every gauge and counter of the window pool is exported
    series = engine.device_path_stats()
    for name in WINDOW_COUNTERS:
        assert f"engine_{name}_total" in series
    for pool in ("full", "window"):
        for state in ("used", "cached", "free"):
            assert (f'engine_kv_pool_blocks{{pool="{pool}",state="{state}"}}'
                    in series)


def test_mixed_steps_and_many_rows_stay_under_the_bound(forward, tiny):
    """Three requests of several windows each, the later ones admitted in
    mixed steps beside the rows that decode: logits as the reference's,
    and the window pool's used blocks stay under rows x
    ceil((window + chunk) / block) + 1 while the contexts grow to six
    windows."""
    hf, cfg, params = tiny
    engine = _engine(cfg, params)
    _poison_released(engine)
    peak = [0]
    step_done = engine._step_done

    def watching(*info):
        peak[0] = max(peak[0], engine.kv.window.allocator.used_count)
        step_done(*info)

    engine._step_done = watching
    prompts = [_prompt(20 + i, n) for i, n in enumerate((3 * W, 4 * W + 5, 50))]

    async def run():
        async def late(i):
            while i and engine._n_active == 0:  # the first row decodes
                await asyncio.sleep(0.001)
            return await _serve(engine, prompts[i], 3 * W - 8 * i)

        out = await asyncio.gather(*(late(i) for i in range(3)))
        await engine.close()
        return out

    out = asyncio.run(run())
    assert engine.stats["mixed_steps"] > 0
    for prompt, (toks, lps) in zip(prompts, out):
        _check(forward, params, hf, prompt, toks, lps)
    assert peak[0] <= 3 * -(-(W + CHUNK) // BS) + 1
    assert _used(engine) == (0, 0)


def test_preemption_and_resume(forward, tiny):
    """A full pool too small for two contexts at once: one row is
    preempted, gives back BOTH pools' blocks, and resumes from the prefix
    cache of both; logits as the reference's for both."""
    hf, cfg, params = tiny
    engine = _engine(cfg, params, num_blocks=44, max_batch_size=2)
    prompts = [_prompt(30, 40), _prompt(31, 44)]

    async def run():
        out = await asyncio.gather(
            *(_serve(engine, p, 56) for p in prompts))
        await engine.close()
        return out

    out = asyncio.run(run())
    assert engine.stats["preemptions"] > 0
    for prompt, (toks, lps) in zip(prompts, out):
        _check(forward, params, hf, prompt, toks, lps)
    assert _used(engine) == (0, 0)


def _forget(engine, blocks):
    """The window pool reuses ``blocks`` (indices along the first
    prompt's chain): they hold other content now."""
    alloc = engine.kv.window.allocator
    for h in blocks:
        b = alloc.claim(h)
        del alloc._by_hash[h]
        b.seq_hash = b.local_hash = None
        alloc.free([b])


@pytest.mark.parametrize("lost,hit", [
    ([], 64),                 # the window pool backs the whole match
    ([13], 52),               # a hole inside the last window: cut below it
    (list(range(8, 16)), 32),  # the tail is gone: back to what is whole
    (list(range(16)), 0),     # nothing left: the full pool's match is void
], ids=["whole", "inside", "beyond", "nothing"])
def test_the_prefix_rule(forward, tiny, lost, hit):
    """A prompt hits up to the longest block boundary p such that the
    full pool holds blocks [0, p) AND the window pool holds the blocks
    covering [p - window, p): the full pool matches 64 tokens every time,
    and what the window pool has lost decides how many of them count."""
    hf, cfg, params = tiny
    engine = _engine(cfg, params)
    first = _prompt(40, 80)
    second = first[:64] + _prompt(41, 20)

    async def run():
        await _serve(engine, first, 4)
        chain = [h for _l, h in sequence_block_hashes(first, BS)]
        _forget(engine, [chain[i] for i in lost])
        before = _stats(engine)
        toks, lps = await _serve(engine, second, 6)
        await engine.close()
        return toks, lps, before

    toks, lps, before = asyncio.run(run())
    _check(forward, params, hf, second, toks, lps)
    delta = {k: _stats(engine)[k] - before[k] for k in (
        "prefix_cache_hits_tokens", "prefix_matched_tokens",
        "prefix_window_missed_tokens")}
    assert delta == {"prefix_cache_hits_tokens": hit,
                     "prefix_matched_tokens": 64,
                     "prefix_window_missed_tokens": 64 - hit}
    assert _used(engine) == (0, 0)


def test_what_cannot_carry_two_pools_refuses_by_name(tiny):
    hf, cfg, params = tiny
    for kw, word in (
        (dict(host_cache_blocks=8), "KV tiers"),
        (dict(spec_gamma=2), "spec_gamma"),
        (dict(kv_cache_dtype="int8"), "scale planes"),
        (dict(adapters=("a:4",)), "adapters"),
        (dict(ring_prefill_threshold=64), "ring prefill"),
    ):
        with pytest.raises(ValueError, match=word):
            _engine(cfg, params, **kw)
    engine = _engine(cfg, params)
    for call, word in (
        (lambda: asyncio.run(engine.reshard(None)), "reshard"),
        (lambda: engine.begin_remote(_request([1, 2, 3], 1)), "begin_remote"),
    ):
        with pytest.raises(ValueError, match=word):
            call()


def test_a_model_with_one_kind_of_layer_keeps_one_pool(tiny):
    """All full (no window) or all windowed (a cut to three layers): ONE
    cache, one allocator, plain tables, none of the window series."""
    hf, _cfg, _params = tiny
    for change in (dict(use_sliding_window=False), dict(num_hidden_layers=3)):
        cfg = ModelConfig.from_hf_config(dict(hf, **change))
        engine = JaxEngine(EngineConfig(
            model=cfg, num_blocks=16, block_size=BS, max_batch_size=2,
            max_context=64))
        assert engine.kv.window is None and not isinstance(
            engine.k_cache, tuple)
        assert not any(
            word in k for k in engine.device_path_stats()
            for word in ("kv_window", "attn_window", "window_missed",
                         "kv_pool_blocks", "prefix_matched"))
        assert engine._rows.wtables is None


def test_the_window_pools_size_is_derived_or_asked(tiny):
    """Derived from what the engine is told anyway: every slot's window
    and chunk and a lone prefill's chunk (the floor), and a window a slot
    of cached tails; ``window_blocks`` replaces it and may not lie under
    the floor. A model with one pool has none."""
    from dynamo_tpu.engine.kv_manager import window_pool_blocks

    hf, cfg, params = tiny
    served = ModelConfig.from_local_path(
        "chipbench/configs/mellum2-12b-a2.5b")
    # 32 x (ceil((1024 + 512) / 16) + 1) + 128 + 1 = 3,233; + 32 x 64
    assert window_pool_blocks(served, 32, 16, 512, 2048) == 5281
    assert window_pool_blocks(served, 32, 16, 512, 2048, 8192) == 8192
    with pytest.raises(ValueError, match="under the 3233 blocks"):
        window_pool_blocks(served, 32, 16, 512, 2048, 3000)
    assert window_pool_blocks(ModelConfig.tiny(), 32, 16, 512, 2048) == 0
    engine = _engine(cfg, params, window_blocks=80)
    assert engine.kv.window.allocator.num_blocks == 80
    assert engine.k_cache[1].shape[2] == engine.v_cache[1].shape[2] == 80
    with pytest.raises(ValueError, match="window_blocks=20"):
        _engine(cfg, params, window_blocks=20)


def test_a_long_prompts_inside_does_not_push_out_other_contexts_tails(
        forward, tiny):
    """The inside of a long prompt passes through the window pool as it
    is prefilled, released behind the window chunk by chunk: those blocks
    are parked COLD (the first the pool gives away), so that they recycle
    among themselves and the TAILS other sequences left at their ends
    stay. With the reuse pool giving away its oldest block whatever it is
    worth, the long prompt's 40 inside blocks would have pushed the two
    short contexts' tails out of a pool of 48, and their repeats would
    have missed."""
    hf, cfg, params = tiny
    engine = _engine(cfg, params, window_blocks=48, max_batch_size=2)
    short = [_prompt(50, 40), _prompt(51, 36)]
    long = _prompt(52, 200)

    async def run():
        for p in short:
            await _serve(engine, p, 2)
        await _serve(engine, long, 2)
        before = _stats(engine)
        out = [await _serve(engine, p + _prompt(53, 6), 4) for p in short]
        # and the long one's own tail is there too
        out.append(await _serve(engine, long + _prompt(54, 5), 4))
        await engine.close()
        return out, before

    out, before = asyncio.run(run())
    for prompt, extra, (toks, lps) in zip(
            short + [long], (_prompt(53, 6),) * 2 + (_prompt(54, 5),), out):
        _check(forward, params, hf, prompt + extra, toks, lps)
    assert engine.kv.stats["prefix_window_missed_tokens"] == before[
        "prefix_window_missed_tokens"] == 0
    hit = engine.stats["prefix_cache_hits_tokens"] - before[
        "prefix_cache_hits_tokens"]
    assert hit == 40 + 36 + 200
