"""Low-precision compute lane (ISSUE 18): the int8-with-scales DEVICE
KV cache and int8 weight GEMMs through the live serving path.

Families:
  * plane lifecycle — the engine creates per-(layer, page) f32 scale
    planes for ``kv_cache_dtype="int8"``, decode appends grow them
    (requants counted on device), allocator recycling queues scale
    resets flushed as one bucketed scatter, and prefix-cache claims
    keep their scales (bit-stable re-serves);
  * writer codec — the fused quantized append
    (``kv_cache_append_quantized``, interpret-pinned) matches a
    hand-computed numpy reference of the same absmax/rint/clip math;
  * tier exchange — an int8 device cache and an int8 tier adopt blocks
    verbatim (zero export requants), full-width tiers force the
    VISIBLE dequant bounce (``kv_device_export_requant_total``), and
    the device-chain export ships the device codec with scales;
  * weights — ``quantization="int8_native"`` stores int8 leaves and
    serves greedy streams, drift recorded under its own stat key;
  * observability — the five lane gauges flow load_metrics →
    WorkerLoad.from_stats → the metrics render;
  * gates — MLA models refuse the int8 device cache loudly.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.kvquant import measure_logprob_drift
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import KV_INT8_QMAX, KV_SCALE_EPS
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, collect

MODEL_CFG = ModelConfig.tiny()
PARAMS = llama.init_params(MODEL_CFG, jax.random.key(7))


def engine_cfg(**kw):
    kw.setdefault("model", MODEL_CFG)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_context", 128)
    kw.setdefault("prefill_chunk", 32)
    return EngineConfig(**kw)


def make_req(tokens, max_tokens=8, logprobs=None):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, seed=0,
                                         logprobs=logprobs),
        eos_token_ids=[],
    )


async def serve_tokens(eng, tokens, max_tokens=8):
    out = []
    async for o in eng.generate(Context(make_req(tokens, max_tokens))):
        out.extend(o.token_ids)
    return out


async def settle_tiers(eng, need_blocks=1):
    for _ in range(300):
        if eng.offload.stats()["offload_blocks_resident"] >= need_blocks:
            return
        await asyncio.sleep(0.02)
    raise AssertionError("tier never settled")


# ---------------- plane lifecycle ----------------


def test_int8_cache_creates_scale_planes_and_counts_hbm(run):
    async def main():
        eng = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            assert eng.k_cache.dtype == jnp.int8
            L, N = MODEL_CFG.num_layers, 64
            assert eng.k_scales.shape == (L, N)
            assert eng.v_scales.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(eng.k_scales),
                                       KV_SCALE_EPS)
            # plane bytes are KV-pool bytes, not dark matter
            hbm = eng._hbm_stats()
            expect = (eng.k_cache.nbytes + eng.v_cache.nbytes
                      + eng.k_scales.nbytes + eng.v_scales.nbytes)
            assert hbm["kv_pool"] == expect
        finally:
            await eng.close()

    run(main())


def test_mla_refuses_int8_device_cache():
    mla = ModelConfig.tiny_mla()
    with pytest.raises(ValueError, match="MLA"):
        JaxEngine(
            engine_cfg(model=mla, kv_cache_dtype="int8"),
            params=llama.init_params(mla, jax.random.key(0)),
        )


def test_decode_appends_grow_scales_and_count_requants(run):
    async def main():
        eng = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            toks = await serve_tokens(eng, range(10, 42), max_tokens=12)
            assert len(toks) == 12
            lm = eng.load_metrics()
            assert lm["kv_device_quant_pages"] > 0
            assert lm["kv_device_requants_total"] > 0
            assert lm["kv_device_bytes_saved_total"] > 0
            # the written pages' scales grew past the reset floor
            plane = np.asarray(eng.k_scales)
            assert (plane > KV_SCALE_EPS * 2).any()
        finally:
            await eng.close()

    run(main())


def test_recycled_pages_reset_scales_fresh_claims_keep_them(run):
    async def main():
        eng = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            # unit core: a stale plane entry resets to EPS on recycle
            eng.k_scales = eng.k_scales.at[:, 5].set(99.0)
            eng.v_scales = eng.v_scales.at[:, 7].set(42.0)
            before = np.asarray(eng.k_scales)[:, 9].copy()
            eng._pending_scale_resets.extend([5, 7])
            eng._flush_scale_resets()
            np.testing.assert_allclose(
                np.asarray(eng.k_scales)[:, 5], KV_SCALE_EPS)
            np.testing.assert_allclose(
                np.asarray(eng.v_scales)[:, 7], KV_SCALE_EPS)
            # untouched pages keep their scales
            np.testing.assert_allclose(
                np.asarray(eng.k_scales)[:, 9], before)
            assert not eng._pending_scale_resets

            # behavioral: a prefix re-serve (match_prefix claim, no
            # on_allocated fire) reproduces the greedy stream bit-exact
            prompt = list(range(100, 124))
            first = await serve_tokens(eng, prompt)
            hits0 = eng.stats["prefix_cache_hits_tokens"]
            again = await serve_tokens(eng, prompt)
            assert eng.stats["prefix_cache_hits_tokens"] > hits0
            assert first == again
        finally:
            await eng.close()

    run(main())


def test_every_fresh_allocation_queues_a_scale_reset(run):
    async def main():
        eng = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            seen = []
            inner = eng.kv.allocator.on_allocated
            eng.kv.allocator.on_allocated = lambda i: (seen.append(i),
                                                    inner(i))
            await serve_tokens(eng, range(10, 30), max_tokens=4)
            assert seen, "fresh allocations must queue scale resets"
            # dispatch preamble drained the queue into the scatter
            assert not eng._pending_scale_resets
        finally:
            await eng.close()

    run(main())


# ---------------- writer codec (interpret-pinned) ----------------


def test_quantized_append_matches_numpy_reference():
    from dynamo_tpu.ops.kv_cache_update_pallas import (
        kv_cache_append_quantized,
    )

    rng = np.random.default_rng(11)
    L, B, Hkv, D, N, bs = 2, 3, 2, 8, 6, 4
    k_cache = rng.integers(-127, 128, (L, Hkv, N, bs, D)).astype(np.int8)
    v_cache = rng.integers(-127, 128, (L, Hkv, N, bs, D)).astype(np.int8)
    scales = np.full((L, N), 0.01, np.float32)
    k_new = rng.standard_normal((L, B, Hkv, D)).astype(np.float32) * 2.0
    v_new = rng.standard_normal((L, B, Hkv, D)).astype(np.float32) * 0.02
    blk = np.asarray([1, 3, 4], np.int32)
    off = np.asarray([0, 2, 3], np.int32)

    ko, vo, kso, vso, nreq = kv_cache_append_quantized(
        jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(k_cache.copy()), jnp.asarray(v_cache.copy()),
        jnp.asarray(scales), jnp.asarray(scales),
        jnp.asarray(blk), jnp.asarray(off), interpret=True,
    )

    def ref(cache, new, sc):
        cache, sc = cache.copy().astype(np.float32), sc.copy()
        amax = np.abs(new).max(axis=(2, 3)) / KV_INT8_QMAX  # [L, B]
        grown = 0
        for b in range(B):
            for l in range(L):
                ns = max(sc[l, blk[b]], amax[l, b], KV_SCALE_EPS)
                if ns > sc[l, blk[b]]:
                    # requantize the resident page by old/new ratio
                    r = sc[l, blk[b]] / ns
                    cache[l, :, blk[b]] = np.clip(
                        np.round(cache[l, :, blk[b]] * r), -127, 127)
                    grown += 1
                sc[l, blk[b]] = ns
                cache[l, :, blk[b], off[b]] = np.clip(
                    np.round(new[l, b] / ns), -127, 127)
        return cache.astype(np.int8), sc, grown

    kr, ksr, gk = ref(k_cache, k_new, scales)
    vr, vsr, gv = ref(v_cache, v_new, scales)
    np.testing.assert_allclose(np.asarray(kso), ksr, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vso), vsr, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ko), kr)
    np.testing.assert_array_equal(np.asarray(vo), vr)
    assert int(nreq) == gk + gv


def test_greedy_stream_matches_fullwidth_reference(run):
    """int8 device cache vs the bf16 cache on the same weights: the
    tiny-model drift stays below any greedy argmax flip on these fixed
    prompts (the logprob deltas are the honest numbers — see
    bench_lowprec)."""
    async def main():
        ref = JaxEngine(engine_cfg(), params=PARAMS)
        q = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            d = await measure_logprob_drift(
                ref, q,
                [[(13 * j + 41 * c) % 480 + 10 for j in range(48)]
                 for c in range(2)],
                max_tokens=10, park=None,
            )
            assert d["greedy_agreement"] == 1.0, d
            assert d["logprob_delta_max"] < 0.2, d
            # the stat keeps the raw max; the result rounds to 6 places
            assert q.stats["kv_quant_logprob_drift_max"] == pytest.approx(
                d["logprob_delta_max"], abs=1e-6)
        finally:
            await ref.close()
            await q.close()

    run(main())


# ---------------- tier exchange ----------------


def test_int8_tier_adopts_device_codec_zero_bounce(run):
    """int8 device cache + int8 tier codec: flushes ship the device
    payload + plane scales verbatim — no dequant bounce — and the
    restored prefix reproduces the greedy stream."""
    async def main():
        eng = JaxEngine(
            engine_cfg(num_blocks=16, kv_cache_dtype="int8",
                       kv_quant="int8", host_cache_blocks=32),
            params=PARAMS,
        )
        try:
            prompt = list(range(200, 240))
            first = await serve_tokens(eng, prompt)
            # churn the prompt's pages out of the tiny device pool
            for i in range(3):
                await serve_tokens(eng, range(300 + 50 * i, 340 + 50 * i))
            await settle_tiers(eng, need_blocks=4)
            assert eng.offload.device_requants_total == 0
            assert eng.load_metrics()["kv_device_export_requant_total"] == 0
            # quantized entries carry their scale sections
            st = eng.offload.stats()
            assert st["kv_quant_blocks_total"] > 0
            again = await serve_tokens(eng, prompt)
            assert first == again
            # the adopt path restored without any export requants
            assert eng.load_metrics()["kv_device_export_requant_total"] == 0
        finally:
            await eng.close()

    run(main())


def test_fullwidth_tier_bounce_is_counted_not_silent(run):
    """int8 device cache + full-width tier (kv_quant='none'): every
    flushed block must leave the device codec — the dequant bounce is
    visible in kv_device_export_requant_total."""
    async def main():
        eng = JaxEngine(
            engine_cfg(num_blocks=16, kv_cache_dtype="int8",
                       host_cache_blocks=32),
            params=PARAMS,
        )
        try:
            await serve_tokens(eng, range(200, 240))
            for i in range(3):
                await serve_tokens(eng, range(300 + 50 * i, 340 + 50 * i))
            await settle_tiers(eng, need_blocks=4)
            assert eng.offload.device_requants_total > 0
            assert eng.load_metrics()["kv_device_export_requant_total"] > 0
        finally:
            await eng.close()

    run(main())


def test_export_device_chain_ships_device_codec_with_scales(run):
    from dynamo_tpu.engine.allocator import sequence_block_hashes

    async def main():
        eng = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            prompt = list(range(100, 124))  # 6 blocks of 4
            await serve_tokens(eng, prompt)
            chain = [s for _l, s in sequence_block_hashes(prompt, 4)]
            served, k, v, ks, vs = await eng.export_device_chain(chain)
            assert len(served) >= 5
            assert k.dtype == np.int8 and v.dtype == np.int8
            assert ks.shape == (MODEL_CFG.num_layers, len(served))
            assert vs.dtype == np.float32
            assert (ks > 0).all()
            # verbatim device codec: zero export requants
            assert eng.load_metrics()["kv_device_export_requant_total"] == 0
        finally:
            await eng.close()

    run(main())


def test_export_device_chain_fullwidth_engine_has_no_scales(run):
    from dynamo_tpu.engine.allocator import sequence_block_hashes

    async def main():
        eng = JaxEngine(engine_cfg(), params=PARAMS)
        try:
            prompt = list(range(100, 124))
            await serve_tokens(eng, prompt)
            chain = [s for _l, s in sequence_block_hashes(prompt, 4)]
            served, k, v, ks, vs = await eng.export_device_chain(chain)
            assert len(served) >= 5 and ks is None and vs is None
            assert k.dtype != np.int8
        finally:
            await eng.close()

    run(main())


# ---------------- int8 weight GEMMs ----------------


def test_int8_native_weights_store_int8_and_serve(run):
    async def main():
        eng = JaxEngine(engine_cfg(quantization="int8_native"),
                        params=PARAMS)
        try:
            leaves = jax.tree.leaves(eng.params)
            assert any(x.dtype == jnp.int8 for x in leaves), (
                "int8_native must store int8 weight leaves"
            )
            toks = await serve_tokens(eng, range(10, 42), max_tokens=8)
            assert len(toks) == 8
            # drift harness records weight-lane drift under its own key
            ref = JaxEngine(engine_cfg(), params=PARAMS)
            try:
                d = await measure_logprob_drift(
                    ref, eng, [list(range(50, 82))], max_tokens=6,
                    park=None, stat_key="lowprec_weight_drift_max",
                )
            finally:
                await ref.close()
            assert eng.stats["lowprec_weight_drift_max"] == pytest.approx(
                d["logprob_delta_max"], abs=1e-6)
            # distinct key: the tier codec's default stat stays untouched
            assert eng.stats["kv_quant_logprob_drift_max"] == 0.0
        finally:
            await eng.close()

    run(main())


def test_both_lanes_together_serve_greedy(run):
    async def main():
        eng = JaxEngine(
            engine_cfg(quantization="int8_native", kv_cache_dtype="int8"),
            params=PARAMS,
        )
        try:
            toks = await serve_tokens(eng, range(10, 42), max_tokens=8)
            assert len(toks) == 8
            lm = eng.load_metrics()
            assert lm["kv_device_quant_pages"] > 0
        finally:
            await eng.close()

    run(main())


# ---------------- observability ----------------


def test_workerload_scrapes_lowprec_keys():
    from dynamo_tpu.kv_router.scheduler import WorkerLoad

    wl = WorkerLoad.from_stats(7, {
        "kv_device_quant_pages": 24,
        "kv_device_requants_total": 328,
        "kv_device_bytes_saved_total": 770048,
        "kv_device_export_requant_total": 3,
        "lowprec_tok_s": 262.7,
    })
    assert wl.kv_device_quant_pages == 24
    assert wl.kv_device_requants == 328
    assert wl.kv_device_bytes_saved == 770048
    assert wl.kv_device_export_requants == 3
    assert wl.lowprec_tok_s == pytest.approx(262.7)
    legacy = WorkerLoad.from_stats(8, {})
    assert legacy.kv_device_quant_pages == 0
    assert legacy.lowprec_tok_s == 0.0


def test_metrics_render_includes_lowprec_gauges():
    from dynamo_tpu.kv_router.publisher import KvMetricsAggregator
    from dynamo_tpu.kv_router.scheduler import (
        ProcessedEndpoints,
        WorkerLoad,
    )
    from dynamo_tpu.observability.component import MetricsComponent

    comp = MetricsComponent.__new__(MetricsComponent)
    comp.prefix = "dynamo_tpu"
    comp.aggregator = KvMetricsAggregator.__new__(KvMetricsAggregator)
    comp.aggregator.endpoints = ProcessedEndpoints([
        WorkerLoad.from_stats(0xAB, {
            "kv_device_quant_pages": 24,
            "kv_device_requants_total": 328,
            "kv_device_bytes_saved_total": 770048,
            "kv_device_export_requant_total": 3,
            "lowprec_tok_s": 262.7,
        })
    ])
    comp.hit_events = comp.hit_isl_blocks = comp.hit_overlap_blocks = 0
    comp.planner_decision = comp.planner_watermark = None
    comp.planner_decisions_total = 0
    comp.tracing = None
    text = comp.render()
    assert 'dynamo_tpu_kv_device_quant_pages{worker="ab"} 24' in text
    assert 'dynamo_tpu_kv_device_requants_total{worker="ab"} 328' in text
    assert ('dynamo_tpu_kv_device_bytes_saved_total{worker="ab"} 770048'
            in text)
    assert ('dynamo_tpu_kv_device_export_requant_total{worker="ab"} 3'
            in text)
    assert 'dynamo_tpu_lowprec_tok_s{worker="ab"} 262.7' in text


def test_engine_load_metrics_exports_lowprec_keys(run):
    async def main():
        eng = JaxEngine(engine_cfg(kv_cache_dtype="int8"), params=PARAMS)
        try:
            await serve_tokens(eng, range(10, 42), max_tokens=6)
            lm = eng.load_metrics()
            for key in ("kv_device_quant_pages", "kv_device_requants_total",
                        "kv_device_bytes_saved_total",
                        "kv_device_export_requant_total", "lowprec_tok_s"):
                assert key in lm, key
        finally:
            await eng.close()

    run(main())
