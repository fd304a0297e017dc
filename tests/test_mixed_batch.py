"""Fused mixed prefill+decode batching (ISSUE 3): exactness + kernel.

The mixed-batch scheduler must be a pure LATENCY optimization: fusing a
prefill chunk into the decode step may change WHEN tokens arrive, never
WHICH tokens (or logprobs) arrive. Every test here runs the same
concurrent workload — a live decode stream with a multi-chunk prompt
prefilling beside it — through the fused engine (mixed_batch=True, the
default) and the alternating baseline (mixed_batch=False), asserting
bit-identical streams across the model families the engine serves:
dense GQA, sliding-window, gpt-oss (alternating per-layer windows +
sinks + MoE), and MLA. The ragged mixed-attention kernel itself is
pinned against the XLA decode/chunk attention pair in interpret mode.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jaxpr_eqns, quantize_pages

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, collect


def _req(tokens, max_tokens, *, temperature=0.0, seed=0, logprobs=None,
         eos=(), **stops):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, **stops),
        sampling_options=SamplingOptions(
            temperature=temperature, seed=seed, logprobs=logprobs,
        ),
        eos_token_ids=list(eos),
    )


def _engine_cfg(model, mixed, **over):
    base = dict(
        model=model, num_blocks=96, block_size=4, max_batch_size=2,
        max_context=128, prefill_chunk=16, mixed_batch=mixed,
    )
    base.update(over)
    return EngineConfig(**base)


def _stream(out):
    return (
        [t for o in out for t in o.token_ids],
        [lp for o in out if o.logprobs for lp in o.logprobs],
        out[-1].finish_reason,
    )


async def _mixed_workload(engine, *, dec_kw=None, long_kw=None):
    """A decode stream running WHILE a multi-chunk prompt prefills: the
    exact interleaving the mixed scheduler fuses. Returns (decode
    stream, long-prompt stream)."""
    dec = _req(range(10, 18), 16, ignore_eos=True, **(dec_kw or {}))
    t = asyncio.ensure_future(collect(engine.generate(Context(dec))))
    while engine.stats["decode_steps"] == 0:
        await asyncio.sleep(0.005)
    # 48 tokens -> 3 chunks of prefill_chunk=16 riding the decode steps
    long = _req(range(200, 248), 3, temperature=0.8, seed=7,
                ignore_eos=True, **(long_kw or {}))
    long_out = await collect(engine.generate(Context(long)))
    dec_out = await t
    return dec_out, long_out


FAMILIES = {
    "dense": lambda: ModelConfig.tiny(),
    "sliding_window": lambda: ModelConfig.tiny(sliding_window=8),
    "gptoss": lambda: ModelConfig.tiny(
        num_layers=2, layer_windows=(6, 0), attn_sinks=True, o_bias=True,
        attention_bias=True, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=32, moe_act="gptoss_clamp",
    ),
    "mla": lambda: ModelConfig.tiny_mla(),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_mixed_step_exact_vs_alternating(run, family):
    """The fused mixed step must produce bit-identical token streams AND
    logprob entries to the alternating baseline — greedy decode stream
    (with logprobs), sampled long prompt — for every model family."""

    async def one(mixed):
        engine = JaxEngine(_engine_cfg(FAMILIES[family](), mixed), seed=0)
        dec_out, long_out = await _mixed_workload(
            engine, dec_kw={"logprobs": 2}
        )
        fused_steps = engine.stats["mixed_steps"]
        await engine.close()
        return _stream(dec_out), _stream(long_out), fused_steps

    async def main():
        dec_f, long_f, fused_steps = await one(True)
        dec_a, long_a, alt_steps = await one(False)
        # the fused path actually engaged (several chunks rode decode
        # steps) and the baseline really was the alternating scheduler
        assert fused_steps >= 3, f"mixed never engaged ({fused_steps})"
        assert alt_steps == 0
        assert dec_f == dec_a, f"{family}: decode stream diverged"
        assert long_f == long_a, f"{family}: prefilled stream diverged"

    run(main())


def test_mixed_step_midstream_eos(run):
    """A decode row sampling its eos DURING the fused phase must end its
    stream there (EOS, exact prefix) while the prefill completes."""

    async def main():
        # probe the greedy continuation to learn a real mid-stream token
        probe = JaxEngine(_engine_cfg(ModelConfig.tiny(), True), seed=0)
        out = await collect(probe.generate(
            Context(_req(range(10, 18), 8, ignore_eos=True))
        ))
        toks = [t for o in out for t in o.token_ids]
        await probe.close()

        engine = JaxEngine(_engine_cfg(ModelConfig.tiny(), True), seed=0)
        dec = _req(range(10, 18), 24, eos=[toks[2]])
        t = asyncio.ensure_future(collect(engine.generate(Context(dec))))
        while engine.stats["decode_steps"] == 0:
            await asyncio.sleep(0.005)
        long_out = await collect(engine.generate(
            Context(_req(range(200, 248), 2, ignore_eos=True))
        ))
        dec_out = await t
        got = [t for o in dec_out for t in o.token_ids]
        assert got == toks[:3]
        assert dec_out[-1].finish_reason == FinishReason.EOS
        assert sum(len(o.token_ids) for o in long_out) == 2
        assert engine._n_active == 0
        await engine.close()

    run(main())


def test_mixed_step_preemption_replay_exact(run):
    """Pool starvation during mixed batching must preempt + replay, never
    truncate: every stream completes max_tokens with exactly the tokens
    the uncontended engine produces (the seed preemption contract,
    carried over to the fused scheduler)."""

    async def main():
        prompts = [list(range(10 + 7 * i, 22 + 7 * i)) for i in range(3)]
        ref = JaxEngine(
            _engine_cfg(ModelConfig.tiny(), True, num_blocks=64,
                        max_batch_size=4, prefill_chunk=32), seed=0,
        )
        want = []
        for p in prompts:
            out = await collect(ref.generate(
                Context(_req(p, 24, ignore_eos=True))
            ))
            want.append([t for o in out for t in o.token_ids])
        await ref.close()

        engine = JaxEngine(
            _engine_cfg(ModelConfig.tiny(), True, num_blocks=14,
                        max_batch_size=4, prefill_chunk=32), seed=0,
        )
        outs = await asyncio.gather(
            *[collect(engine.generate(Context(_req(p, 24, ignore_eos=True))))
              for p in prompts]
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


def test_mixed_step_with_penalties_exact(run):
    """Penalized sampling through the fused step (device counts carried
    across mixed and plain steps) must match the alternating path."""

    async def one(mixed):
        engine = JaxEngine(_engine_cfg(ModelConfig.tiny(), mixed), seed=0)
        dec_kw = {"temperature": 0.0}
        dec = PreprocessedRequest(
            token_ids=list(range(10, 18)),
            stop_conditions=StopConditions(max_tokens=16, ignore_eos=True),
            sampling_options=SamplingOptions(
                temperature=0.0, seed=0, frequency_penalty=2.0,
                presence_penalty=0.5, repetition_penalty=1.2,
            ),
            eos_token_ids=[],
        )
        t = asyncio.ensure_future(collect(engine.generate(Context(dec))))
        while engine.stats["decode_steps"] == 0:
            await asyncio.sleep(0.005)
        long_out = await collect(engine.generate(
            Context(_req(range(200, 248), 2, ignore_eos=True))
        ))
        dec_out = await t
        del dec_kw
        await engine.close()
        return (
            [t for o in dec_out for t in o.token_ids],
            [t for o in long_out for t in o.token_ids],
        )

    async def main():
        assert await one(True) == await one(False)

    run(main())


# ---------------- multi-prompt packing (ISSUE 9: M prefill segments) -------


async def _multi_prefill_workload(engine, n_prompts, *, dec_kw=None,
                                  long_mt=3):
    """A decode stream running while M multi-chunk prompts prefill
    CONCURRENTLY — the head-of-line mixture the multi-segment packer
    splits the token budget across. Returns (decode stream, [prompt
    streams] in submission order)."""
    dec = _req(range(10, 18), 20, ignore_eos=True, **(dec_kw or {}))
    t = asyncio.ensure_future(collect(engine.generate(Context(dec))))
    while engine.stats["decode_steps"] == 0:
        await asyncio.sleep(0.005)
    longs = [
        _req(range(200 + 60 * i, 248 + 60 * i), long_mt, temperature=0.8,
             seed=7 + i, ignore_eos=True)
        for i in range(n_prompts)
    ]
    long_outs = await asyncio.gather(
        *[collect(engine.generate(Context(lg))) for lg in longs]
    )
    dec_out = await t
    return dec_out, long_outs


@pytest.mark.parametrize("family", list(FAMILIES))
@pytest.mark.parametrize("n_prompts", [3])
def test_multi_prefill_pack_exact_vs_alternating(run, family, n_prompts):
    """M concurrent prompts packing into fused steps must produce
    bit-identical token streams AND logprob entries to the alternating
    baseline (which serializes the prefills entirely), for every model
    family — and the packer must actually pack (segments > steps)."""

    async def one(mixed):
        engine = JaxEngine(
            _engine_cfg(FAMILIES[family](), mixed, num_blocks=192,
                        max_batch_size=4 + n_prompts),
            seed=0,
        )
        dec_out, long_outs = await _multi_prefill_workload(
            engine, n_prompts, dec_kw={"logprobs": 2}
        )
        stats = dict(engine.stats)
        await engine.close()
        return (
            [_stream(dec_out)] + [_stream(o) for o in long_outs], stats,
        )

    async def main():
        fused, s_f = await one(True)
        alt, s_a = await one(False)
        # the packer actually engaged: multiple segments rode single
        # fused steps (admission-order budget split)
        assert s_f["mixed_prefill_segments"] > s_f["mixed_steps"] > 0, s_f
        assert s_a["mixed_steps"] == 0
        assert fused == alt, f"{family}: streams diverged under packing"

    run(main())


def test_multi_prefill_one_prompt_cancelled_mid_mixture(run):
    """Cancelling ONE of M packed prompts mid-prefill must drop only it
    (CANCELLED, its blocks/upload rolled back) while the other prompts
    and the decode stream finish with exactly the uncancelled-run
    streams of those survivors."""

    async def one(cancel):
        engine = JaxEngine(
            _engine_cfg(ModelConfig.tiny(), True, num_blocks=192,
                        max_batch_size=6),
            seed=0,
        )
        dec = _req(range(10, 18), 20, ignore_eos=True)
        t = asyncio.ensure_future(collect(engine.generate(Context(dec))))
        while engine.stats["decode_steps"] == 0:
            await asyncio.sleep(0.005)
        ctxs = [
            Context(_req(range(200 + 60 * i, 248 + 60 * i), 3,
                         temperature=0.8, seed=7 + i, ignore_eos=True))
            for i in range(3)
        ]
        victim = ctxs[1]
        if cancel:
            # cancel prompt 1 once the pack is in flight (its first
            # chunks have ridden fused steps beside the others)
            async def cancel_when_packed():
                while engine.stats["mixed_steps"] == 0:
                    await asyncio.sleep(0.002)
                victim.context.stop_generating()

            asyncio.ensure_future(cancel_when_packed())
        outs = await asyncio.gather(
            *[collect(engine.generate(c)) for c in ctxs]
        )
        dec_out = await t
        # scheduler fully unwound: no leaked states, and no leaked
        # block refcounts (the whole pool is re-claimable — reuse-pool
        # residents are LRU-claimable, a leaked refcount is not)
        assert not engine._prefill_states
        assert engine._n_active == 0
        fresh = engine.kv.allocator.allocate(engine.kv.allocator.num_blocks - 1)
        assert fresh is not None, "cancelled prompt leaked block refs"
        engine.kv.allocator.free(fresh)
        await engine.close()
        return dec_out, outs

    async def main():
        dec_c, outs_c = await one(True)
        dec_u, outs_u = await one(False)
        assert outs_c[1][-1].finish_reason == FinishReason.CANCELLED
        # survivors and the decode stream are untouched by the cancel
        assert _stream(dec_c) == _stream(dec_u)
        assert _stream(outs_c[0]) == _stream(outs_u[0])
        assert _stream(outs_c[2]) == _stream(outs_u[2])

    run(main())


def test_multi_prefill_midstream_eos_of_decode_row(run):
    """A decode row sampling its eos while M prompts are packing must
    end its stream there (EOS, exact prefix) while every packed prompt
    still completes."""

    async def main():
        probe = JaxEngine(_engine_cfg(ModelConfig.tiny(), True), seed=0)
        out = await collect(probe.generate(
            Context(_req(range(10, 18), 8, ignore_eos=True))
        ))
        toks = [t for o in out for t in o.token_ids]
        await probe.close()

        engine = JaxEngine(
            _engine_cfg(ModelConfig.tiny(), True, num_blocks=192,
                        max_batch_size=6),
            seed=0,
        )
        dec = _req(range(10, 18), 24, eos=[toks[2]])
        t = asyncio.ensure_future(collect(engine.generate(Context(dec))))
        while engine.stats["decode_steps"] == 0:
            await asyncio.sleep(0.005)
        long_outs = await asyncio.gather(*[
            collect(engine.generate(Context(
                _req(range(200 + 60 * i, 248 + 60 * i), 2, ignore_eos=True)
            )))
            for i in range(2)
        ])
        dec_out = await t
        got = [t for o in dec_out for t in o.token_ids]
        assert got == toks[:3]
        assert dec_out[-1].finish_reason == FinishReason.EOS
        for o in long_outs:
            assert sum(len(x.token_ids) for x in o) == 2
        assert engine._n_active == 0
        await engine.close()

    run(main())


def test_multi_prefill_pack_without_decode_batch(run):
    """A pure prefill burst (nothing decoding) must still pack: M
    queued prompts advance TOGETHER through prefill-only fused steps
    instead of serializing whole prompts, with streams bit-identical to
    the alternating scheduler."""

    async def one(mixed):
        engine = JaxEngine(
            _engine_cfg(ModelConfig.tiny(), mixed, num_blocks=192,
                        max_batch_size=6),
            seed=0,
        )
        longs = [
            _req(range(200 + 60 * i, 248 + 60 * i), 4, temperature=0.8,
                 seed=7 + i, ignore_eos=True)
            for i in range(3)
        ]
        outs = await asyncio.gather(
            *[collect(engine.generate(Context(lg))) for lg in longs]
        )
        stats = dict(engine.stats)
        await engine.close()
        return [_stream(o) for o in outs], stats

    async def main():
        fused, s_f = await one(True)
        alt, s_a = await one(False)
        assert s_f["mixed_prefill_segments"] > 0, s_f
        assert fused == alt

    run(main())


# ---------------- the ragged kernel itself (interpret mode) ----------------


def _random_cache_setup(rng, *, B, Hkv, G, D, bs, M, T, hist, valid):
    """A populated paged cache + packed queries for B decode rows and one
    prefill chunk, with everything written write-before-attend."""
    H = Hkv * G
    N = (B + 1) * M + 1
    kc = jnp.asarray(rng.standard_normal((Hkv, N, bs, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((Hkv, N, bs, D)), jnp.float32)
    # disjoint physical pages per sequence; page 0 reserved
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    d_tables = pages[: B * M].reshape(B, M)
    p_table = pages[B * M : (B + 1) * M]
    d_seq_lens = np.asarray(
        [1 + rng.integers(0, M * bs - 1) for _ in range(B)], np.int32
    )
    q_dec = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    q_chunk = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
    return (
        kc, vc, jnp.asarray(d_tables), jnp.asarray(d_seq_lens),
        jnp.asarray(p_table), q_dec, q_chunk,
    )


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("with_sinks", [False, True])
def test_ragged_mixed_kernel_matches_xla(window, with_sinks):
    """Interpret-mode kernel vs the XLA pair it fuses: decode rows must
    match decode_attention_xla (per-row lengths + window + sinks), chunk
    rows must match chunk_attention_with_cache_xla on the real rows."""
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    rng = np.random.default_rng(3)
    B, Hkv, G, D, bs, M = 3, 2, 2, 16, 8, 8
    T, valid = 16, 13
    hist = 9
    scale = D ** -0.5
    kc, vc, d_tables, d_seq_lens, p_table, q_dec, q_chunk = (
        _random_cache_setup(rng, B=B, Hkv=Hkv, G=G, D=D, bs=bs, M=M, T=T,
                            hist=hist, valid=valid)
    )
    H = Hkv * G
    sinks = (
        jnp.asarray(rng.standard_normal(H), jnp.float32) if with_sinks
        else None
    )
    # the chunk's own K/V: write rows [hist, hist+T) through the table
    # (padded rows too — the causal mask keeps real rows off them)
    k_chunk = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    v_chunk = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    kc = att.write_chunk_to_cache(kc, k_chunk, p_table, jnp.int32(hist))
    vc = att.write_chunk_to_cache(vc, v_chunk, p_table, jnp.int32(hist))

    o_dec, o_chunks = ragged_mixed_attention(
        q_dec, q_chunk[None], kc[None], vc[None], 0, d_tables, d_seq_lens,
        p_table[None],
        jnp.asarray([hist], jnp.int32), jnp.asarray([valid], jnp.int32),
        scale, q_tile=8, window=window, sinks=sinks, interpret=True,
    )
    ref_dec = att.decode_attention_xla(
        q_dec, kc, vc, d_tables, d_seq_lens, scale, window=window,
        sinks=sinks,
    )
    ref_chunk = att.chunk_attention_with_cache_xla(
        q_chunk, k_chunk, v_chunk, kc, vc, p_table, jnp.int32(hist),
        jnp.int32(valid), scale, window=window, sinks=sinks,
    )
    np.testing.assert_allclose(
        np.asarray(o_dec), np.asarray(ref_dec), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(o_chunks)[0, :valid], np.asarray(ref_chunk)[:valid],
        rtol=2e-5, atol=2e-5,
    )


def test_ragged_mixed_kernel_sharded_tp2_matches_xla():
    """The shard_map wrapper (tp=2 over kv heads) must match the XLA pair
    — interpret mode on a CPU mesh; same shard_map + Mosaic compile on
    TPU (the mixed kernel is kv-head-parallel like its parents)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention_sharded,
    )

    rng = np.random.default_rng(11)
    B, Hkv, G, D, bs, M = 2, 2, 2, 16, 8, 8
    T, valid, hist = 16, 16, 5
    scale = D ** -0.5
    kc, vc, d_tables, d_seq_lens, p_table, q_dec, q_chunk = (
        _random_cache_setup(rng, B=B, Hkv=Hkv, G=G, D=D, bs=bs, M=M, T=T,
                            hist=hist, valid=valid)
    )
    k_chunk = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    v_chunk = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    kc = att.write_chunk_to_cache(kc, k_chunk, p_table, jnp.int32(hist))
    vc = att.write_chunk_to_cache(vc, v_chunk, p_table, jnp.int32(hist))

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 1, 2),
                ("dp", "pp", "sp", "ep", "tp"))
    qd = jax.device_put(q_dec, NamedSharding(mesh, P(None, "tp", None)))
    qc = jax.device_put(
        q_chunk[None], NamedSharding(mesh, P(None, None, "tp", None))
    )
    spec = NamedSharding(mesh, P(None, "tp", None, None, None))
    kcs = jax.device_put(kc[None], spec)
    vcs = jax.device_put(vc[None], spec)
    o_dec, o_chunks = ragged_mixed_attention_sharded(
        qd, qc, kcs, vcs, 0, d_tables, d_seq_lens, p_table[None],
        jnp.asarray([hist], jnp.int32), jnp.asarray([valid], jnp.int32),
        scale, mesh, interpret=True,
    )
    ref_dec = att.decode_attention_xla(
        q_dec, kc, vc, d_tables, d_seq_lens, scale
    )
    ref_chunk = att.chunk_attention_with_cache_xla(
        q_chunk, k_chunk, v_chunk, kc, vc, p_table, jnp.int32(hist),
        jnp.int32(valid), scale,
    )
    np.testing.assert_allclose(
        np.asarray(o_dec), np.asarray(ref_dec), rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        np.asarray(o_chunks)[0], np.asarray(ref_chunk), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("with_sinks", [False, True])
def test_ragged_mixed_kernel_multi_segment_matches_xla(window, with_sinks):
    """The generalized kernel with M=2 segments (different histories,
    different fills) must match the XLA pair per part: decode rows vs
    decode_attention_xla, EACH segment's real rows vs
    chunk_attention_with_cache_xla."""
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    rng = np.random.default_rng(3)
    B, Hkv, G, D, bs, M = 3, 2, 2, 16, 8, 8
    MP, T = 2, 16
    valids, hists = [13, 16], [9, 3]
    scale = D ** -0.5
    H = Hkv * G
    N = (B + MP) * M + 1
    kc = jnp.asarray(rng.standard_normal((Hkv, N, bs, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((Hkv, N, bs, D)), jnp.float32)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    d_tables = jnp.asarray(pages[: B * M].reshape(B, M))
    p_tables = jnp.asarray(pages[B * M : (B + MP) * M].reshape(MP, M))
    d_seq_lens = jnp.asarray(
        [1 + rng.integers(0, M * bs - 1) for _ in range(B)], jnp.int32
    )
    q_dec = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    q_chunks = jnp.asarray(rng.standard_normal((MP, T, H, D)), jnp.float32)
    k_chunks, v_chunks = [], []
    for m in range(MP):
        k_m = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
        v_m = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
        kc = att.write_chunk_to_cache(
            kc, k_m, p_tables[m], jnp.int32(hists[m])
        )
        vc = att.write_chunk_to_cache(
            vc, v_m, p_tables[m], jnp.int32(hists[m])
        )
        k_chunks.append(k_m)
        v_chunks.append(v_m)
    sinks = (
        jnp.asarray(rng.standard_normal(H), jnp.float32) if with_sinks
        else None
    )

    o_dec, o_chunks = ragged_mixed_attention(
        q_dec, q_chunks, kc[None], vc[None], 0, d_tables, d_seq_lens,
        p_tables,
        jnp.asarray(hists, jnp.int32), jnp.asarray(valids, jnp.int32),
        scale, q_tile=8, window=window, sinks=sinks, interpret=True,
    )
    ref_dec = att.decode_attention_xla(
        q_dec, kc, vc, d_tables, d_seq_lens, scale, window=window,
        sinks=sinks,
    )
    np.testing.assert_allclose(
        np.asarray(o_dec), np.asarray(ref_dec), rtol=2e-5, atol=2e-5
    )
    for m in range(MP):
        ref_chunk = att.chunk_attention_with_cache_xla(
            q_chunks[m], k_chunks[m], v_chunks[m], kc, vc, p_tables[m],
            jnp.int32(hists[m]), jnp.int32(valids[m]), scale,
            window=window, sinks=sinks,
        )
        np.testing.assert_allclose(
            np.asarray(o_chunks[m])[: valids[m]],
            np.asarray(ref_chunk)[: valids[m]],
            rtol=2e-5, atol=2e-5,
        )


def test_ragged_mixed_kernel_dead_segment_and_inactive_slot_zero():
    """Dead pad segments (valid 0 — the segment-count bucket filler) and
    inactive decode slots must emit zeros (every superblock skipped)
    while live parts stay finite and exact."""
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    rng = np.random.default_rng(7)
    B, Hkv, G, D, bs, M = 2, 1, 4, 16, 8, 4
    MP, T = 2, 8
    N = (B + MP) * M + 1
    kc = jnp.asarray(rng.standard_normal((Hkv, N, bs, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((Hkv, N, bs, D)), jnp.float32)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    d_tables = jnp.asarray(pages[: B * M].reshape(B, M))
    p_tables_np = pages[B * M : (B + MP) * M].reshape(MP, M).copy()
    p_tables_np[1] = 0  # dead segment: zero table, like the engine pads
    H = Hkv * G
    q_dec = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    q_chunks = jnp.asarray(rng.standard_normal((MP, T, H, D)), jnp.float32)
    k0 = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    v0 = jnp.asarray(rng.standard_normal((T, Hkv, D)), jnp.float32)
    kc = att.write_chunk_to_cache(kc, k0, jnp.asarray(p_tables_np[0]),
                                  jnp.int32(0))
    vc = att.write_chunk_to_cache(vc, v0, jnp.asarray(p_tables_np[0]),
                                  jnp.int32(0))
    d_seq_lens = jnp.asarray([5, 0], jnp.int32)  # slot 1 inactive
    o_dec, o_chunks = ragged_mixed_attention(
        q_dec, q_chunks, kc[None], vc[None], 0, d_tables, d_seq_lens,
        jnp.asarray(p_tables_np), jnp.asarray([0, 0], jnp.int32),
        jnp.asarray([8, 0], jnp.int32), D ** -0.5, interpret=True,
    )
    assert np.all(np.asarray(o_dec)[1] == 0.0)
    assert np.all(np.asarray(o_chunks)[1] == 0.0)  # dead segment
    assert np.all(np.isfinite(np.asarray(o_dec)[0]))
    ref0 = att.chunk_attention_with_cache_xla(
        q_chunks[0], k0, v0, kc, vc, jnp.asarray(p_tables_np[0]),
        jnp.int32(0), jnp.int32(8), D ** -0.5,
    )
    np.testing.assert_allclose(
        np.asarray(o_chunks)[0], np.asarray(ref0), rtol=2e-5, atol=2e-5
    )


def test_ragged_mixed_kernel_inactive_slots_zero():
    """Inactive decode slots (seq_len 0) must emit zeros — their tiles
    skip every superblock — exactly like the XLA fallback."""
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    rng = np.random.default_rng(5)
    B, Hkv, G, D, bs, M = 2, 1, 4, 16, 8, 4
    kc, vc, d_tables, _sl, p_table, q_dec, q_chunk = _random_cache_setup(
        rng, B=B, Hkv=Hkv, G=G, D=D, bs=bs, M=M, T=8, hist=0, valid=8,
    )
    d_seq_lens = jnp.asarray([5, 0], jnp.int32)  # slot 1 inactive
    o_dec, _ = ragged_mixed_attention(
        q_dec, q_chunk[None], kc[None], vc[None], 0, d_tables, d_seq_lens,
        p_table[None],
        jnp.asarray([0], jnp.int32), jnp.asarray([8], jnp.int32),
        D ** -0.5, interpret=True,
    )
    assert np.all(np.asarray(o_dec)[1] == 0.0)
    assert np.all(np.isfinite(np.asarray(o_dec)[0]))


def _pallas_grids(jaxpr):
    """The grid of every pallas_call under ``jaxpr``, nested calls too."""
    return [
        tuple(eqn.params["grid_mapping"].grid) for eqn in jaxpr_eqns(jaxpr)
        if eqn.primitive.name == "pallas_call"
    ]


@pytest.mark.parametrize("B", [2, 32])
def test_ragged_mixed_decode_rows_take_the_decode_kernel(B):
    """The decode rows are not tiles of the ragged grid (as tiles each
    row, live or dead, cost kv_heads x superblocks grid steps: 20-27 ms
    a layer-call at 32 rows on the chip, PERF.md section 6): the ragged
    grid covers the segments' tiles whatever B is, and the decode rows
    come out of the decode kernel, bit for bit."""
    from dynamo_tpu.ops.paged_attention_pallas import paged_decode_attention
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    rng = np.random.default_rng(11)
    Hkv, G, D, bs, M, T = 2, 2, 16, 8, 4, 16
    kc, vc, d_tables, d_seq_lens, p_table, q_dec, q_chunk = (
        _random_cache_setup(rng, B=B, Hkv=Hkv, G=G, D=D, bs=bs, M=M, T=T,
                            hist=0, valid=T)
    )
    d_seq_lens = d_seq_lens.at[1].set(0)  # a dead slot among the rows
    args = (
        q_dec, q_chunk[None], kc[None], vc[None], 0, d_tables, d_seq_lens,
        p_table[None],
        jnp.asarray([0], jnp.int32), jnp.asarray([T], jnp.int32),
    )

    def call(*a):
        return ragged_mixed_attention(
            *a, scale=D ** -0.5, q_tile=8, pages_per_step=2, interpret=True
        )

    grids = _pallas_grids(jax.make_jaxpr(call)(*args).jaxpr)
    # the segments' tiles x heads x superblocks; the decode rows x head
    # tiles (the decode kernel walks a row's own pages inside a step)
    assert sorted(grids) == sorted([(T // 8, Hkv, M // 2), (B, 1)]), grids
    o_dec, _ = call(*args)
    ref = paged_decode_attention(
        q_dec, kc[None], vc[None], 0, d_tables, d_seq_lens, D ** -0.5,
        interpret=True,
    )
    assert np.array_equal(np.asarray(o_dec), np.asarray(ref))


# ---------------- the mixed step appends where the pool lies ----------------
# Both of its kernels take the WHOLE ``[L, Hkv, N, bs, D]`` cache and a
# layer index (one operand form), and its K and V rows land in place in
# the donated pool: one scatter a layer and cache, no slab cut out and
# none written back (PERF.md section 6, PR 41).

_WHOLE_CACHE_MIXED_CASES = {
    "bf16": dict(),
    "int8-scales": dict(int8=True),
    "sinks": dict(sinks=True),
    "window": dict(window=11),
    "sinks-window-int8": dict(sinks=True, window=11, int8=True),
    "scan-traced-layer": dict(scan=True),
    "scan-traced-layer-int8": dict(scan=True, int8=True),
}


@pytest.mark.parametrize("case", list(_WHOLE_CACHE_MIXED_CASES))
def test_mixed_whole_cache_operand_equals_slab_bitwise(case):
    """For every layer of a 3-layer cache, the call on the whole cache
    equals the call on that layer's slab as a one-layer cache BIT FOR
    BIT, decode rows and segments alike (same kernel bodies, same bytes;
    the layer is a value only the page index maps read, a traced one
    under ``lax.scan`` too)."""
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    c = _WHOLE_CACHE_MIXED_CASES[case]
    rng = np.random.default_rng(41)
    L, B, Hkv, G, D, bs, M = 3, 3, 2, 2, 16, 8, 8
    MP, T = 2, 16
    H, N = Hkv * G, (B + MP) * M + 1
    kc = jnp.asarray(rng.standard_normal((L, Hkv, N, bs, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((L, Hkv, N, bs, D)), jnp.float32)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    d_tables = jnp.asarray(pages[: B * M].reshape(B, M))
    p_tables = jnp.asarray(pages[B * M:].reshape(MP, M))
    d_seq_lens = jnp.asarray([0, 21, M * bs], jnp.int32)  # dead, ragged, full
    hists = jnp.asarray([9, 0], jnp.int32)
    valids = jnp.asarray([13, 16], jnp.int32)
    q_dec = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    q_chunks = jnp.asarray(rng.standard_normal((MP, T, H, D)), jnp.float32)
    k_planes = v_planes = None
    if c.get("int8"):
        kc, k_planes = map(jnp.stack, zip(*map(quantize_pages, kc)))
        vc, v_planes = map(jnp.stack, zip(*map(quantize_pages, vc)))
    else:
        q_dec, q_chunks = (a.astype(jnp.bfloat16) for a in (q_dec, q_chunks))
        kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    sinks = (
        jnp.asarray(rng.standard_normal(H), jnp.float32)
        if c.get("sinks") else None
    )

    def call(k, v, layer, planes):
        return ragged_mixed_attention(
            q_dec, q_chunks, k, v, layer, d_tables, d_seq_lens, p_tables,
            hists, valids, D ** -0.5, q_tile=8, window=c.get("window", 0),
            sinks=sinks, interpret=True, **planes,
        )

    def planes(l):
        if k_planes is None:
            return {}
        return dict(k_scales=k_planes[l], v_scales=v_planes[l])

    if c.get("scan"):  # one traced index for all layers, one kernel
        _, whole = jax.lax.scan(
            lambda _, l: (None, call(kc, vc, l, planes(l))), None,
            jnp.arange(L),
        )
        whole = [jax.tree.map(lambda a: a[l], whole) for l in range(L)]
    else:
        whole = [call(kc, vc, l, planes(l)) for l in range(L)]
    for l in range(L):
        slab = call(kc[l][None], vc[l][None], 0, planes(l))
        for w, s in zip(whole[l], slab):
            assert w.dtype == s.dtype and w.shape == s.shape
            np.testing.assert_array_equal(
                np.asarray(w, np.float32), np.asarray(s, np.float32)
            )
    # and the layers differ: an index that read the wrong slab would show
    assert not np.array_equal(
        np.asarray(whole[0][1], np.float32), np.asarray(whole[1][1], np.float32)
    )


def _mixed_family(family):
    """(cfg, extra mixed_step kwargs builder) of a tiny model a family."""
    import json
    import os

    from dynamo_tpu.models import llama

    if family == "lfm2-stack":
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "chipbench", "testdata", "tiny-lfm2", "config.json")
        with open(path) as f:
            hf = dict(json.load(f), torch_dtype="float32", hidden_size=128,
                      num_attention_heads=2, num_key_value_heads=1)
        cfg = ModelConfig.from_hf_config(hf)
        assert cfg.conv_layers and cfg.kv_layers == 2
    else:
        cfg = {
            "dense": lambda: ModelConfig.tiny(num_layers=3, head_dim=128),
            "experts": lambda: ModelConfig.tiny(
                num_layers=3, head_dim=128, num_experts=4,
                num_experts_per_tok=2, moe_intermediate_size=32),
            # gpt-oss's lanes: a head of 64 in 128, sinks, alternating
            # sliding / full layers
            "head64-sinks-windows": lambda: ModelConfig.tiny(
                num_layers=4, head_dim=64, attn_sinks=True,
                layer_windows=(20, 0, 20, 0)),
        }[family]()

    def extra(B, N, p_slots):
        kw = {}
        if cfg.is_moe:
            kw["moe_counters"] = True
        if cfg.conv_layers:
            kw.update(state=llama.init_state(cfg, B, N),
                      p_slots=jnp.asarray(p_slots, jnp.int32))
        return kw

    return cfg, extra


_MIXED_FAMILIES = ["dense", "experts", "lfm2-stack", "head64-sinks-windows"]


def _mixed_step_inputs(cfg, seed=0):
    """A mixed step with every kind of row: slot 0 live (12 tokens), slot
    1 dead (length 0, a zero table), segment 0 five real tokens of 16
    behind a cached block (three more pad the block's tail, eight fall
    through zero table entries into trash page 0), segment 1 dead."""
    from dynamo_tpu.models import llama

    B, M, N, bs, MP, T = 2, 8, 24, 8, 2, 16
    rng = np.random.default_rng(seed)
    shape_k, shape_v = llama.kv_cache_shapes(cfg, N, bs)
    dt = jnp.float32 if cfg.conv_layers else jnp.bfloat16
    kc = jnp.asarray(rng.standard_normal(shape_k, np.float32), dt)
    vc = jnp.asarray(rng.standard_normal(shape_v, np.float32), dt)
    d_tables = np.zeros((B, M), np.int32)
    d_tables[0, :2] = [3, 7]
    p_tables = np.zeros((MP, M), np.int32)
    p_tables[0, :2] = [11, 5]
    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    args = (
        jnp.asarray([9, 0], jnp.int32),  # d_tokens
        jnp.asarray([11, 0], jnp.int32),  # d_positions
        jnp.asarray(d_tables),
        jnp.asarray([12, 0], jnp.int32),  # d_seq_lens
        zi, zi, zf, zi, jnp.ones(B, jnp.float32),  # greedy sampling
        jnp.asarray(rng.integers(16, cfg.vocab_size, (MP, T)), jnp.int32),
        jnp.asarray(p_tables),
        jnp.asarray([bs, 0], jnp.int32),  # p_hists
        jnp.asarray([5, 0], jnp.int32),  # p_valids
    )
    # the pages a row may land in: the live slot's tail page, segment 0's
    # second page, the trash page
    return args, kc, vc, dict(B=B, N=N, bs=bs, MP=MP, T=T,
                              touched={7, 5, 0}, p_slots=[1, B])


@pytest.mark.parametrize("family", _MIXED_FAMILIES)
def test_mixed_step_hands_the_kernels_the_whole_cache(family):
    """The slab must not come back: in the fused mixed step every cache
    operand of every ``pallas_call`` is the cache itself, 5-D, and no
    equation produces an ``[Hkv, N, bs, D]`` layer of it: not a slice in
    front of a kernel (the TPU compiler materialises it: a copy of the
    slab), not a slab to scatter into and write back (PERF.md section 6,
    PRs 29 and 41). Dense, experts, an LFM2 stack (its attention layers
    by their ordinal in the cache) and a head of 64 with sinks and
    alternating windows take the one path."""
    from dynamo_tpu.models import llama

    cfg, extra = _mixed_family(family)
    params = llama.init_params(cfg, jax.random.key(0))
    args, kc, vc, g = _mixed_step_inputs(cfg)
    kw = extra(g["B"], g["N"], g["p_slots"])
    state = kw.pop("state", None)

    def step(p, k, v, st):
        return llama.mixed_step.__wrapped__(
            p, cfg, *args, k, v, use_pallas=True, interpret=True, **kw,
            **({} if st is None else {"state": st}),
        )

    jaxpr = jax.make_jaxpr(step)(params, kc, vc, state)
    cache_shape = kc.shape
    assert cache_shape == (cfg.kv_layers, cfg.num_kv_heads, g["N"], g["bs"],
                           llama.kv_lanes(cfg))
    kernels = 0
    for eqn in jaxpr_eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            caches = 0
            for var in eqn.invars:
                shape = var.aval.shape
                if len(shape) >= 4 and shape[-3:] == cache_shape[-3:]:
                    assert shape == cache_shape, (
                        f"a pallas_call takes a {shape} cut of the "
                        f"{cache_shape} cache"
                    )
                    caches += 1
            kernels += caches > 0
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert shape not in (cache_shape[1:], (1, *cache_shape[1:])), (
                f"{eqn.primitive.name} produces a layer slab {shape}"
            )
    # the decode rows' kernel and the segments', every attention layer
    assert kernels == 2 * cfg.kv_layers


@pytest.mark.parametrize("family", _MIXED_FAMILIES)
def test_mixed_step_write_leaves_the_slab_writes_pool(monkeypatch, family):
    """The in-place write against the form it replaces (a layer's slab
    cut out, the decode rows and each chunk scattered into it, the slab
    written back), on the same inputs: the same tokens and segment
    logits bit for bit, and a pool that is byte-identical outside trash
    page 0, where the dead slot's row, the dead segment's rows and the
    live segment's padding land (duplicate slots there: which row lands
    last is not defined, and nothing reads it). No page but the live
    rows' own and page 0 changes."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.ops import attention as att

    cfg, extra = _mixed_family(family)
    params = llama.init_params(cfg, jax.random.key(0))
    args, kc0, vc0, g = _mixed_step_inputs(cfg)
    B, MP, T = g["B"], g["MP"], g["T"]
    d_positions, d_tables, p_tables, p_hists = (
        args[1], args[2], args[10], args[11])

    def slab_write(cache, layer, rows, blk, off, mesh=None):
        d_blk, d_off = att.decode_slot_indices(
            d_tables, d_positions, cache.shape[3])
        slab = cache[layer].at[:, d_blk, d_off].set(
            rows[:B].swapaxes(0, 1).astype(cache.dtype))
        for m in range(MP):
            slab = att.write_chunk_to_cache(
                slab, rows[B + m * T: B + (m + 1) * T], p_tables[m],
                p_hists[m])
        return cache.at[layer].set(slab)

    def run(write):
        monkeypatch.setattr(att, "write_rows_to_cache", write)
        kw = extra(B, g["N"], g["p_slots"])
        out = jax.jit(lambda p, k, v: llama.mixed_step.__wrapped__(
            p, cfg, *args, k, v, use_pallas=True, interpret=True, **kw,
        ))(params, kc0, vc0)
        return [np.asarray(a, np.float32) for a in out[:4]]

    got = run(att.write_rows_to_cache)
    want = run(slab_write)
    np.testing.assert_array_equal(got[0], want[0])  # tokens
    np.testing.assert_array_equal(got[1][0], want[1][0])  # live segment
    for new, old, start in ((got[2], want[2], kc0), (got[3], want[3], vc0)):
        np.testing.assert_array_equal(new[:, :, 1:], old[:, :, 1:])
        changed = np.flatnonzero(
            (new != np.asarray(start, np.float32)).any(axis=(0, 1, 3, 4)))
        assert set(changed) <= g["touched"], changed
        assert {7, 5} <= set(changed)  # every layer's rows did land


def test_write_rows_to_cache_on_a_tp2_mesh_matches_one_device():
    """The write under shard_map over ``tp`` (each device scatters into
    its local kv heads) leaves the pool the single-device write leaves,
    at a traced layer index too."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.parallel import mesh as pm

    rng = np.random.default_rng(2)
    L, Hkv, N, bs, D, R = 3, 4, 12, 8, 16, 10
    cache = jnp.asarray(rng.standard_normal((L, Hkv, N, bs, D)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((R, Hkv, D)), jnp.float32)
    blk = jnp.asarray(rng.integers(1, N, R), jnp.int32)
    off = jnp.asarray(rng.permutation(bs * 2)[:R] % bs, jnp.int32)
    # distinct slots: the last write at a duplicate is not defined
    assert len({(int(b), int(o)) for b, o in zip(blk, off)}) == R
    mesh = pm.make_mesh(pm.MeshConfig(tp=2), devices=jax.devices()[:2])
    want = att.write_rows_to_cache(cache, 1, rows, blk, off)
    assert not np.array_equal(np.asarray(want[1]), np.asarray(cache[1]))
    for l in (0, 2):
        np.testing.assert_array_equal(np.asarray(want[l]), np.asarray(cache[l]))
    sharded = jax.device_put(
        cache, NamedSharding(mesh, P(None, "tp", None, None, None)))
    got = jax.jit(
        lambda c, l: att.write_rows_to_cache(c, l, rows, blk, off, mesh)
    )(sharded, jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_mixed_step_on_a_tp2_mesh_matches_one_device():
    """The fused mixed step under a ``tp`` = 2 mesh (the in-place write
    and both kernels under shard_map on each device's kv heads) against
    the same step on one device: the same token, the segment's logits to
    float tolerance (the projections' sums split over ``tp``) and the
    same rows in the same pages."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.parallel import mesh as pm

    cfg = ModelConfig.tiny(num_layers=2, head_dim=128)
    params = llama.init_params(cfg, jax.random.key(0))
    args, kc, vc, g = _mixed_step_inputs(cfg)
    kc, vc = kc.astype(jnp.float32), vc.astype(jnp.float32)
    want = llama.mixed_step(
        params, cfg, *args, kc + 0, vc + 0, use_pallas=True, interpret=True)
    mesh = pm.make_mesh(pm.MeshConfig(tp=2), devices=jax.devices()[:2])
    sharding = pm.cache_sharding(mesh, cfg)
    got = llama.mixed_step(
        pm.shard_params(params, mesh), cfg, *args,
        jax.device_put(kc, sharding), jax.device_put(vc, sharding),
        use_pallas=True, interpret=True, mesh=mesh)
    assert int(got[0][0]) == int(want[0][0])
    np.testing.assert_allclose(
        np.asarray(got[1][0]), np.asarray(want[1][0]), atol=2e-3)
    for new, old in ((got[2], want[2]), (got[3], want[3])):
        np.testing.assert_allclose(
            np.asarray(new)[:, :, 1:], np.asarray(old)[:, :, 1:], atol=2e-3)
