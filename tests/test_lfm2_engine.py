"""LFM2 through the ENGINE: the conv layers' state has to follow a sequence
through decode slots, chunked and mixed prefill, the prefix cache and
preemption. Logits (the server's reported logprobs) against the plain
float32 reference of ``chipbench/configs/lfm2-8b-a1b/reference.py`` at
the tiny size of ``tests/test_lfm2.py``, whose fixtures these are."""

import asyncio

import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.engine import MOE_COUNTERS, STATE_COUNTERS
from dynamo_tpu.engine.kv_manager import SNAPSHOT_COUNTERS
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel.mesh import MeshConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime import Context, collect
from tests.test_lfm2 import ATOL, BS, _logp, forward, tiny  # noqa: F401


# ---------------- the engine: the state follows the sequence ----------------


def _request(prompt, max_tokens, logprobs=4):
    return Context(PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, logprobs=logprobs),
        eos_token_ids=[],
    ))


def _engine(cfg, params, **kw):
    base = dict(num_blocks=64, block_size=BS, max_batch_size=4,
                max_context=128, prefill_chunk=16)
    base.update(kw)
    return JaxEngine(EngineConfig(model=cfg, **base), params=params)


async def _serve(engine, prompt, max_tokens):
    out = await collect(engine.generate(_request(prompt, max_tokens)))
    toks = [t for o in out for t in o.token_ids]
    lps = [e for o in out for e in (o.logprobs or [])]
    assert len(toks) == len(lps) == max_tokens
    return toks, lps


def _check(forward, params, hf, prompt, toks, lps):
    """Every generated token's reported logprobs against the reference's
    full forward over prompt + answer."""
    want = _logp(forward(params, hf, list(prompt) + toks[:-1]))
    for i, (tok, entry) in enumerate(zip(toks, lps)):
        row = want[len(prompt) - 1 + i]
        assert tok == int(np.argmax(row)), i
        np.testing.assert_allclose(entry["logprob"], row[tok], atol=ATOL)
        for tid, lp in entry["top"]:
            np.testing.assert_allclose(lp, row[tid], atol=ATOL)


def test_engine_prefix_hit_equals_the_cold_run(forward, tiny):
    """The same prompt twice (the second a hit of whole blocks) and a
    prompt that shares only its first blocks: logits as the reference's,
    which holds no cache; the hit skips exactly the matched tokens."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(21)
    prompt = [int(t) for t in rng.integers(16, 512, 38)]
    fork = prompt[:22] + [int(t) for t in rng.integers(16, 512, 9)]

    async def main():
        engine = _engine(cfg, params)
        for p, hit in ((prompt, 0), (prompt, 36), (fork, 20)):
            before = {**engine.stats, **engine.kv.stats}
            toks, lps = await _serve(engine, p, 6)
            _check(forward, params, hf, p, toks, lps)
            now = {**engine.stats, **engine.kv.stats}
            got = {k: now[k] - before[k] for k in (
                "prefix_cache_hits_tokens", *STATE_COUNTERS,
                *SNAPSHOT_COUNTERS)}
            assert got["prefix_cache_hits_tokens"] == hit
            assert got["prefix_matched_tokens"] == hit
            assert got["state_restores"] == (hit > 0)
        m = engine.device_path_stats()
        assert m["engine_prefix_matched_tokens_total"] == 56
        assert m["engine_state_restores_total"] == 2
        assert m["engine_state_snapshots_total"] >= 38 // BS
        assert m["engine_state_bytes"] == (64 + 4) * 6 * 2 * 64 * 4
        await engine.close()

    asyncio.run(main())


def test_engine_chunks_mixed_steps_and_slot_reuse(forward, tiny):
    """Three requests at once through two decode slots: prompts longer
    than the mixed step's budget ride several mixed steps beside a
    decoding row, and the third request takes the slot of a longer
    sequence that has finished (no stale state)."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(22)
    prompts = [[int(t) for t in rng.integers(16, 512, n)]
               for n in (45, 37, 11)]

    async def main():
        engine = _engine(cfg, params, max_batch_size=2, mixed_step_budget=16,
                         mixed_max_prefills=1)
        outs = await asyncio.gather(*[
            _serve(engine, p, n) for p, n in zip(prompts, (12, 5, 9))])
        for p, (toks, lps) in zip(prompts, outs):
            _check(forward, params, hf, p, toks, lps)
        assert engine.stats["mixed_steps"] >= 3
        await engine.close()

    asyncio.run(main())


def test_engine_preempts_and_resumes_with_its_state(forward, tiny):
    """A pool too small for three sequences: the preempted ones resume
    from their committed blocks' snapshot, and every stream's logits stay
    the reference's."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(23)
    prompts = [[int(t) for t in rng.integers(16, 512, 12)] for _ in range(3)]

    async def main():
        engine = _engine(cfg, params, num_blocks=14, prefill_chunk=32)
        outs = await asyncio.gather(*[
            _serve(engine, p, 24) for p in prompts])
        assert engine.stats["preemptions"] > 0
        assert engine.stats["state_restores"] > 0
        for p, (toks, lps) in zip(prompts, outs):
            _check(forward, params, hf, p, toks, lps)
        await engine.close()

    asyncio.run(main())


def test_engine_counts_the_expert_layers_only(tiny):
    _hf, cfg, params = tiny

    async def main():
        engine = _engine(cfg, params)
        await _serve(engine, range(20, 41), 9)
        m = engine.device_path_stats()
        await engine.close()
        return m

    m = asyncio.run(main())
    got = {k: m[f"engine_{k}_total"] for k in MOE_COUNTERS}
    L, X, top = cfg.moe_layers, cfg.num_experts, cfg.num_experts_per_tok
    assert (L, cfg.num_layers) == (6, 8)
    assert got["moe_expert_slots"] % (L * X) == 0
    assert got["moe_assignments"] == L * top * (21 + 8)
    dense = ModelConfig.tiny(dtype="float32")

    async def plain():
        engine = JaxEngine(EngineConfig(
            model=dense, num_blocks=64, block_size=BS, max_batch_size=4,
            max_context=64, prefill_chunk=16))
        m = engine.device_path_stats()
        await engine.close()
        return m

    m = asyncio.run(plain())
    # (the decode batch's step state, ``engine_step_state_*``, is every
    # model's; the per-sequence state's series are what a dense one lacks)
    assert not [k for k in m if "prefix_matched" in k
                or ("state" in k and "step_state" not in k)]


@pytest.mark.parametrize("kw,word", [
    (dict(spec_gamma=2), "spec_gamma"),
    (dict(ring_prefill_threshold=64), "ring prefill"),
    (dict(mesh=MeshConfig(tp=2)), "mesh"),
    (dict(host_cache_blocks=8), "KV tiers"),
    (dict(adapters=("a:4",)), "adapters"),
    (dict(kv_cache_dtype="int8"), "kv_cache_dtype=int8"),
])
def test_engine_refuses_what_cannot_carry_the_state(tiny, kw, word):
    _hf, cfg, params = tiny
    with pytest.raises(ValueError, match=f"{word}.*conv layers"):
        _engine(cfg, params, **kw)


def test_engine_refuses_the_transfer_hooks(tiny):
    _hf, cfg, params = tiny
    engine = _engine(cfg, params)

    async def main():
        for call in (
            lambda: engine.reshard(None),
            lambda: engine.export_device_chain([1]),
            lambda: engine.prefill_extract(
                _request(range(16, 30), 1).data, None),
        ):
            with pytest.raises(ValueError, match="conv layers"):
                await call()
        with pytest.raises(ValueError, match="conv layers"):
            engine.begin_remote(_request(range(16, 30), 1))
        await engine.close()

    asyncio.run(main())
