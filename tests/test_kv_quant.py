"""Per-block KV quantization across the tiers and the wire (ISSUE 14).

Five families:
  * codec units — roundtrip error bounds, entry forms, wire-byte math;
  * tier capacity — the host-pool/disk byte budgets really hold ~2x
    the quantized blocks at the same budget, quantized disk entries
    round-trip their scale sections, and a corrupt/truncated scale
    section is a CLEAN miss (disk_corrupt_discards), never a restore
    exception; a --kv-quant flip across a restart normalizes instead
    of misreading;
  * kernels — interpret-mode bit-identity of the quantized-KV Pallas
    paths vs the XLA quantized path, single (decode + prefill kernels)
    AND mixed (ragged kernel) dispatch, int8+scales and scale-free
    fp8; plus the engine's explicit dispatch-capability gate;
  * wire matrix — quantized streamed/bulk disagg handoffs land through
    the scale-aware scatter, every quant/no-quant version-skew combo
    (quantized puller vs unquantized peer and vice versa, legacy
    receiver) degrades to full-width bytes with zero client-visible
    errors, and a mid-quantized-stream kill redelivers exactly once;
  * observability/routing — the kv_quant gauges flow load_metrics →
    WorkerLoad.from_stats → metrics render, and predict/choose_peer
    price restore/pull legs at the advertised quantized wire bytes.
"""

import asyncio
import os
import struct

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dynamo_tpu.disagg import (
    ConditionalDisaggRouter,
    DisaggConfig,
    DisaggEngine,
    KvTransferServer,
    PrefillQueue,
    PrefillWorker,
)
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine import kvquant
from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.engine.offload import DiskKvStore, HostKvPool, OffloadManager
from dynamo_tpu.kv_router.costmodel import predict_worker_ttft_ms
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.protocols import KvPrefetchHint
from dynamo_tpu.kv_router.scheduler import (
    KvScheduler,
    ProcessedEndpoints,
    SchedulerConfig,
    WorkerLoad,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, DistributedRuntime, collect

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


# ---------------- codec units ----------------


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_codec_stack_roundtrip_error_bounds(mode):
    rng = np.random.default_rng(0)
    L, H, n, bs, D = 3, 2, 5, 4, 8
    k = rng.standard_normal((L, H, n, bs, D)).astype(np.float32) * 3.0
    v = rng.standard_normal((L, H, n, bs, D)).astype(np.float32) * 0.01
    qk, qv, ks, vs = kvquant.quantize_stack(k, v, mode)
    assert qk.dtype == kvquant.quant_dtype(mode)
    assert ks.shape == (L, n) and vs.shape == (L, n)
    k2, v2 = kvquant.dequantize_stack(qk, qv, ks, vs, np.float32)
    # absmax symmetric error bounds — the scale recenters each block's
    # own range, so the tiny-magnitude v blocks quantize as tightly as
    # the k blocks: int8 errs by at most half a step (scale/2); fp8
    # (e4m3, 3 mantissa bits) errs RELATIVE to the value (ulp/2 =
    # 2^-4), with the scaled denormal floor near zero
    for orig, rt, sc in ((k, k2, ks), (v, v2, vs)):
        step = np.broadcast_to(sc[:, None, :, None, None], orig.shape)
        if mode == "int8":
            bound = step * 0.5001
        else:
            bound = np.maximum(np.abs(orig) * (2.0 ** -4) * 1.001, step)
        assert np.all(np.abs(orig - rt) <= bound)
    # fully saturated values survive (no clip past the absmax)
    assert np.isfinite(np.asarray(qk, np.float32)).all()


def test_codec_entry_roundtrip_and_nbytes():
    rng = np.random.default_rng(1)
    k = rng.standard_normal((4, 2, 8, 16)).astype(np.float32)
    v = rng.standard_normal((4, 2, 8, 16)).astype(np.float32)
    qk, qv, ks, vs = kvquant.quantize_entry(k, v, "int8")
    assert ks.shape == (4,) and vs.shape == (4,)
    k2, v2 = kvquant.dequantize_entry(qk, qv, ks, vs, np.float32)
    np.testing.assert_allclose(k2, k, atol=float(ks.max()) * 0.51)
    np.testing.assert_allclose(v2, v, atol=float(vs.max()) * 0.51)
    full = kvquant.entry_nbytes((k, v))
    quant = kvquant.entry_nbytes((qk, qv, ks, vs))
    assert full == k.nbytes + v.nbytes
    # 4-byte f32 payload -> 1-byte int8 + per-layer scales: ~4x here
    assert quant < full / 3


def test_wire_block_bytes_math():
    # bf16 block: 2 bytes/elem -> 1 byte/elem + 2 * L * 4 scale bytes
    full = 65536  # 32768 elems at bf16
    assert kvquant.wire_block_bytes(full, 2, layers=4, mode="int8") == (
        32768 + 2 * 4 * 4
    )
    assert kvquant.wire_block_bytes(full, 2, layers=4, mode="none") == full
    # the headline claim: int8 holds >= 1.8x at the same byte budget
    assert full / kvquant.wire_block_bytes(full, 2, 4, "int8") >= 1.8


# ---------------- tier capacity (byte budgets) ----------------


def _blk(seed, L=2, H=2, bs=4, D=8, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((L, H, bs, D)).astype(dtype),
        rng.standard_normal((L, H, bs, D)).astype(dtype),
    )


def test_host_pool_byte_budget_holds_2x_quantized_blocks():
    k, v = _blk(0)
    block_bytes = k.nbytes + v.nbytes
    # full-width entries: byte budget == the legacy 4-entry count
    pool = HostKvPool(4, block_bytes=block_bytes)
    for h in range(10):
        kk, vv = _blk(h)
        pool.put(h, kk, vv)
    assert len(pool) == 4
    # quantized entries at the SAME budget: ~2x (f32 here -> ~4x, but
    # the contract we pin is the >= 1.8x the bench asserts end to end)
    poolq = HostKvPool(4, block_bytes=block_bytes)
    for h in range(40):
        kk, vv = _blk(h)
        qk, qv, ks, vs = kvquant.quantize_entry(kk, vv, "int8")
        poolq.put(h, qk, qv, scales=(ks, vs))
    assert len(poolq) >= int(4 * 1.8)
    # take() releases budget: the pool refills to the same count
    for h in list(poolq._data)[:3]:
        assert poolq.take(h) is not None
    before = len(poolq)
    for h in range(100, 104):
        kk, vv = _blk(h)
        qk, qv, ks, vs = kvquant.quantize_entry(kk, vv, "int8")
        poolq.put(h, qk, qv, scales=(ks, vs))
    assert len(poolq) >= before


def test_disk_store_quantized_entry_roundtrips_scales(tmp_path):
    s = DiskKvStore(str(tmp_path), capacity_blocks=8)
    k, v = _blk(3)
    qk, qv, ks, vs = kvquant.quantize_entry(k, v, "int8")
    assert s.put(33, qk, qv, scales=(ks, vs))
    got = s.get(33)
    assert got is not None and len(got) == 4
    np.testing.assert_array_equal(got[0], qk)
    np.testing.assert_array_equal(got[2], ks)
    np.testing.assert_array_equal(got[3], vs)
    # survives a restart rescan too
    s2 = DiskKvStore(str(tmp_path), capacity_blocks=8)
    got2 = s2.get(33)
    assert got2 is not None and len(got2) == 4


def test_disk_store_corrupt_or_truncated_scale_section_is_clean_miss(tmp_path):
    path = str(tmp_path)

    def write_entry(h):
        s = DiskKvStore(path, capacity_blocks=8)
        k, v = _blk(h)
        qk, qv, ks, vs = kvquant.quantize_entry(k, v, "int8")
        assert s.put(h, qk, qv, scales=(ks, vs))
        return os.path.join(path, f"{h:016x}.kvb")

    # flipped byte INSIDE the scale section (the trailing vs bytes):
    # CRC covers the scales, so this is a corrupt-discard, not a
    # mis-scaled restore
    f = write_entry(21)
    raw = bytearray(open(f, "rb").read())
    raw[-2] ^= 0xFF
    open(f, "wb").write(bytes(raw))
    s = DiskKvStore(path, capacity_blocks=8)
    assert s.get(21) is None and s.corrupt_discards == 1
    assert 21 in s.drain_dropped()

    # truncated scale section (torn write of the tail): length check
    f = write_entry(22)
    raw = open(f, "rb").read()
    open(f, "wb").write(raw[:-5])
    s = DiskKvStore(path, capacity_blocks=8)
    assert s.get(22) is None and s.corrupt_discards == 1

    # scale vector with the wrong layer count (header/payload drift)
    f = write_entry(23)
    raw = open(f, "rb").read()
    (hlen,) = struct.unpack("<I", raw[4:8])
    head = raw[8 : 8 + hlen].replace(b'"ks_bytes": 8', b'"ks_bytes": 4')
    open(f, "wb").write(
        raw[:4] + struct.pack("<I", len(head)) + head + raw[8 + hlen :]
    )
    s = DiskKvStore(path, capacity_blocks=8)
    assert s.get(23) is None and s.corrupt_discards == 1


def test_disk_store_byte_budget_holds_more_quantized_blocks(tmp_path):
    k, v = _blk(0)
    bb = k.nbytes + v.nbytes
    s = DiskKvStore(str(tmp_path / "full"), capacity_blocks=4, block_bytes=bb)
    for h in range(10):
        s.put(h, *_blk(h))
    full_resident = len(s)
    # the byte budget charges PAYLOAD bytes, so a full-width tier holds
    # EXACTLY its advertised block count (headers must not shave one)
    assert full_resident == 4
    sq = DiskKvStore(str(tmp_path / "q"), capacity_blocks=4, block_bytes=bb)
    for h in range(40):
        kk, vv = _blk(h)
        qk, qv, ks, vs = kvquant.quantize_entry(kk, vv, "int8")
        sq.put(h, qk, qv, scales=(ks, vs))
    assert len(sq) >= int(full_resident * 1.8)


def test_manager_normalizes_disk_entries_across_kv_quant_flip(tmp_path):
    """A worker restarted with a different --kv-quant must read the
    other format cleanly: quantized disk entries dequantize under
    mode none, full-width entries quantize under int8 — never a
    corrupt-discard, never a mixed-dtype restore stack."""
    path = str(tmp_path)
    k, v = _blk(9)
    bb = k.nbytes + v.nbytes
    om_q = OffloadManager(4, disk_blocks=8, disk_path=path,
                          kv_quant="int8", block_bytes=bb,
                          full_dtype="float32")
    e = om_q._encode_entry(k, v)
    assert om_q.disk.put(77, e[0], e[1], scales=(e[2], e[3]))
    om_q.close()
    # mode-none restart: promote dequantizes to full width
    om_n = OffloadManager(4, disk_blocks=8, disk_path=path,
                          full_dtype="float32")
    n = om_n.promote_chain([77])
    assert n == 1
    hashes, data = om_n.reserve_chain([77])
    assert hashes == [77] and len(data[0]) == 2
    np.testing.assert_allclose(data[0][0], k, atol=float(e[2].max()) * 0.51)
    assert om_n.disk.corrupt_discards == 0
    om_n.close()
    # int8 restart over a full-width v2 entry: quantize on promote
    om_n2 = OffloadManager(4, disk_blocks=8, disk_path=path,
                           full_dtype="float32")
    om_n2.disk.put(78, k, v)
    om_n2.close()
    om_q2 = OffloadManager(4, disk_blocks=8, disk_path=path,
                           kv_quant="int8", block_bytes=bb,
                           full_dtype="float32")
    assert om_q2.promote_chain([78]) == 1
    hashes, data = om_q2.reserve_chain([78])
    assert hashes == [78] and len(data[0]) == 4
    assert data[0][0].dtype == np.int8
    assert om_q2.disk.corrupt_discards == 0
    om_q2.close()


# ---------------- kernels: interpret bit-identity ----------------


def _quantize_cache_per_page(kc, vc, mode):
    """Per-page quantization of a [Hkv, N, bs, D] cache layer (the
    per-block-per-layer codec, this layer's column): scales [N]."""
    qmax = 127.0 if mode == "int8" else 448.0
    ks = np.maximum(np.abs(kc).max(axis=(0, 2, 3)) / qmax, 1e-12)
    vs = np.maximum(np.abs(vc).max(axis=(0, 2, 3)) / qmax, 1e-12)
    if mode == "int8":
        qk = np.clip(np.rint(kc / ks[None, :, None, None]), -127, 127)
        qv = np.clip(np.rint(vc / vs[None, :, None, None]), -127, 127)
    else:
        qk, qv = kc / ks[None, :, None, None], vc / vs[None, :, None, None]
    dt = kvquant.quant_dtype(mode)
    return (qk.astype(dt), qv.astype(dt),
            ks.astype(np.float32), vs.astype(np.float32))


def _mixed_setup(seed=3):
    rng = np.random.default_rng(seed)
    B, Hkv, G, D, bs, M = 3, 2, 2, 16, 8, 8
    T, valid, hist = 16, 13, 9
    H = Hkv * G
    N = (B + 1) * M + 1
    kc = rng.standard_normal((Hkv, N, bs, D)).astype(np.float32)
    vc = rng.standard_normal((Hkv, N, bs, D)).astype(np.float32)
    pages = rng.permutation(np.arange(1, N)).astype(np.int32)
    d_tables = pages[: B * M].reshape(B, M)
    p_table = pages[B * M : (B + 1) * M]
    d_seq_lens = np.asarray(
        [1 + rng.integers(0, M * bs - 1) for _ in range(B)], np.int32
    )
    q_dec = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    q_chunk = jnp.asarray(rng.standard_normal((T, H, D)), jnp.float32)
    scale = D ** -0.5
    return (kc, vc, d_tables, p_table, d_seq_lens, q_dec, q_chunk,
            dict(B=B, Hkv=Hkv, G=G, D=D, bs=bs, M=M, T=T, valid=valid,
                 hist=hist, scale=scale))


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_ragged_kernel_fused_dequant_matches_xla_quantized_path(mode):
    """MIXED dispatch: the ragged kernel consuming int8/fp8 pages with
    their scale arrays in-kernel must match the XLA quantized path
    (attention over the dequantized cache) on decode AND chunk rows."""
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    kc, vc, d_tables, p_table, d_seq_lens, q_dec, q_chunk, g = _mixed_setup()
    qk, qv, ks, vs = _quantize_cache_per_page(kc, vc, mode)
    kd = qk.astype(np.float32) * ks[None, :, None, None]
    vd = qv.astype(np.float32) * vs[None, :, None, None]
    o_dec, o_chunks = ragged_mixed_attention(
        q_dec, q_chunk[None], jnp.asarray(qk), jnp.asarray(qv),
        jnp.asarray(d_tables), jnp.asarray(d_seq_lens),
        jnp.asarray(p_table)[None],
        jnp.asarray([g["hist"]], jnp.int32),
        jnp.asarray([g["valid"]], jnp.int32),
        g["scale"], q_tile=8,
        k_scales=jnp.asarray(ks), v_scales=jnp.asarray(vs),
        interpret=True,
    )
    ref_dec = att.decode_attention_xla(
        q_dec, jnp.asarray(kd), jnp.asarray(vd),
        jnp.asarray(d_tables), jnp.asarray(d_seq_lens), g["scale"],
    )
    np.testing.assert_allclose(
        np.asarray(o_dec), np.asarray(ref_dec), rtol=2e-5, atol=2e-5
    )
    # chunk rows vs the XLA chunk path over the dequantized cache; the
    # chunk's own K/V ride full-width (write-before-attend wrote them
    # quantized INTO the quantized cache, so read them back from it)
    k_chunk = np.zeros((g["T"], g["Hkv"], g["D"]), np.float32)
    v_chunk = np.zeros_like(k_chunk)
    for t in range(g["T"]):
        pos = g["hist"] + t
        blk, off = p_table[pos // g["bs"]], pos % g["bs"]
        k_chunk[t] = kd[:, blk, off]
        v_chunk[t] = vd[:, blk, off]
    ref_chunk = att.chunk_attention_with_cache_xla(
        q_chunk, jnp.asarray(k_chunk), jnp.asarray(v_chunk),
        jnp.asarray(kd), jnp.asarray(vd), jnp.asarray(p_table),
        jnp.int32(g["hist"]), jnp.int32(g["valid"]), g["scale"],
    )
    np.testing.assert_allclose(
        np.asarray(o_chunks)[0, : g["valid"]],
        np.asarray(ref_chunk)[: g["valid"]], rtol=2e-5, atol=2e-5,
    )


def test_single_dispatch_kernels_consume_fp8_pages():
    """SINGLE dispatch: the decode and prefill Pallas kernels must take
    a scale-free fp8 (direct-cast) cache and match the XLA quantized
    path bit-for-bit at interpret level."""
    import ml_dtypes

    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.paged_attention_pallas import (
        paged_decode_attention,
        paged_prefill_attention,
    )

    kc, vc, d_tables, p_table, d_seq_lens, q_dec, q_chunk, g = _mixed_setup(5)
    kc8 = jnp.asarray(kc.astype(ml_dtypes.float8_e4m3fn))
    vc8 = jnp.asarray(vc.astype(ml_dtypes.float8_e4m3fn))
    out = paged_decode_attention(
        q_dec, kc8[None], vc8[None], 0, jnp.asarray(d_tables),
        jnp.asarray(d_seq_lens), g["scale"], interpret=True,
    )
    ref = att.decode_attention_xla(
        q_dec, kc8, vc8, jnp.asarray(d_tables), jnp.asarray(d_seq_lens),
        g["scale"],
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
    out_p = paged_prefill_attention(
        q_chunk, kc8, vc8, jnp.asarray(p_table), jnp.int32(g["hist"]),
        g["scale"], interpret=True,
    )
    # XLA twin reads the chunk rows back out of the quantized cache
    kd = np.asarray(kc8).astype(np.float32)
    vd = np.asarray(vc8).astype(np.float32)
    k_chunk = np.zeros((g["T"], g["Hkv"], g["D"]), np.float32)
    v_chunk = np.zeros_like(k_chunk)
    for t in range(g["T"]):
        pos = g["hist"] + t
        blk, off = p_table[pos // g["bs"]], pos % g["bs"]
        k_chunk[t] = kd[:, blk, off]
        v_chunk[t] = vd[:, blk, off]
    ref_p = att.chunk_attention_with_cache_xla(
        q_chunk, jnp.asarray(k_chunk), jnp.asarray(v_chunk), kc8, vc8,
        jnp.asarray(p_table), jnp.int32(g["hist"]),
        jnp.int32(g["valid"]), g["scale"],
    )
    np.testing.assert_allclose(
        np.asarray(out_p)[: g["valid"]], np.asarray(ref_p)[: g["valid"]],
        rtol=2e-5, atol=2e-5,
    )


def test_engine_gate_keeps_pallas_for_quantized_cache(monkeypatch):
    """engine.py's silent Pallas opt-out for quantized caches is now an
    explicit capability check: fp8 caches keep the kernel path on TPU
    backends, MLA fp8 takes the XLA path and says why."""
    eng = JaxEngine(
        engine_cfg(kv_cache_dtype="float8_e4m3", block_size=8,
                   model=ModelConfig.tiny(head_dim=64)),
        params=llama.init_params(ModelConfig.tiny(head_dim=64),
                                 jax.random.key(0)),
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng._use_pallas_for(None), (
        "a quantized (fp8) cache must keep the Pallas ragged path"
    )
    assert eng.attention_path["path"] == "pallas"
    mla = ModelConfig.tiny_mla()
    eng_mla = JaxEngine(
        EngineConfig(model=mla, num_blocks=16, block_size=8,
                     max_batch_size=2, max_context=128,
                     kv_cache_dtype="float8_e4m3"),
        params=llama.init_params(mla, jax.random.key(0)),
    )
    assert not eng_mla._use_pallas_for(None), (
        "MLA latent kernels are bf16/f32-only; fp8 must fall back"
    )
    assert eng_mla.attention_path["path"] == "xla"
    assert "bf16/f32-only" in eng_mla.attention_path["reason"]


# ---------------- tier round-trip + drift harness ----------------


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_tier_roundtrip_drift_gate(run, mode):
    """The quality gate end to end: serve fixed prompts on a bf16
    reference and on a quantized-tier engine whose prefix is forced
    through the quantize→restore round-trip; greedy agreement must
    clear the 0.99 gate and the drift rides the stats plane."""

    async def main():
        tiny = ModelConfig.tiny()
        params = llama.init_params(tiny, jax.random.key(0))

        def cfg(quant):
            return EngineConfig(
                model=tiny, num_blocks=24, block_size=16, max_batch_size=2,
                max_context=512, prefill_chunk=64,
                host_cache_blocks=16, kv_quant=quant,
            )

        ref = JaxEngine(cfg("none"), params=params)
        q = JaxEngine(cfg(mode), params=params)

        async def park(engine, toks):
            for i in range(3):
                filler = [(17 * j + 29 * i) % 250 + 5 for j in range(176)]
                await collect(engine.generate(Context(make_req(filler))))
            await asyncio.sleep(0.3)

        prompts = [[(11 * j + p) % 250 + 5 for j in range(160)]
                   for p in range(2)]
        d = await kvquant.measure_logprob_drift(
            ref, q, prompts, max_tokens=8, park=park
        )
        assert d["n_tokens"] > 0
        assert d["greedy_agreement"] >= 0.99, d
        assert d["logprob_delta_max"] < 0.05, d
        st = q.offload.stats()
        assert st["kv_quant_blocks_total"] > 0
        assert st["kv_quant_bytes_saved_total"] > 0
        lm = q.load_metrics()
        assert lm["kv_quant_logprob_drift_max"] == pytest.approx(
            d["logprob_delta_max"], abs=1e-6  # the report rounds to 6dp
        )
        assert 0 < lm["kv_wire_block_bytes"] < lm["kv_block_bytes"]
        await ref.close()
        await q.close()

    run(main())


# ---------------- peer-pull mismatch matrix ----------------


@pytest.mark.parametrize("peer_mode,puller_mode", [
    ("int8", "none"), ("none", "int8"), ("int8", "int8"),
])
def test_peer_pull_quant_mismatch_matrix(run, peer_mode, puller_mode):
    """Quantized puller vs unquantized peer AND vice versa: every combo
    lands the chain (normalized to the puller's codec), restores it,
    and serves bit-matching greedy tokens — zero client errors."""
    from dynamo_tpu.kv_router.protocols import KV_PREFETCH_SUBJECT
    from dynamo_tpu.kv_router.publisher import (
        KvPeerServer,
        KvPrefetchListener,
    )
    from dynamo_tpu.runtime import LocalBus, LocalStore

    async def main():
        tiny = ModelConfig.tiny()
        params = llama.init_params(tiny, jax.random.key(5))
        BS = 16
        PREFIX, TAIL = 160, 16

        def cfg(quant):
            return EngineConfig(
                model=tiny, num_blocks=20, block_size=BS, max_batch_size=2,
                max_context=512, prefill_chunk=64,
                host_cache_blocks=32, kv_quant=quant,
            )

        prefix = [(11 * j) % 250 + 5 for j in range(PREFIX)]
        measured = prefix + [(7 * j) % 250 + 5 for j in range(TAIL)]
        pairs = sequence_block_hashes(measured, BS)[: PREFIX // BS]
        chain = [s for _l, s in pairs]

        eng_peer = JaxEngine(cfg(peer_mode), params=params)
        eng_puller = JaxEngine(cfg(puller_mode), params=params)
        eng_ref = JaxEngine(cfg("none"), params=params)
        store, bus = LocalStore(), LocalBus()
        drt = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = drt.namespace("dynamo").component("kvq")
        server = await KvPeerServer(drt, comp, 1, eng_peer).start()
        listener = await KvPrefetchListener(drt, comp, 2, eng_puller).start()
        try:
            # park the shared prefix in the peer's (possibly quantized)
            # host tier
            await collect(eng_peer.generate(Context(make_req(
                prefix + [(13 * j) % 250 + 5 for j in range(TAIL)]
            ))))
            for i in range(3):
                filler = [(17 * j + 29 * i) % 250 + 5
                          for j in range(PREFIX + TAIL)]
                await collect(eng_peer.generate(Context(make_req(filler))))
            for _ in range(300):
                if all(eng_peer.offload.tier_contains(h) for h in chain):
                    break
                await asyncio.sleep(0.02)
            assert all(eng_peer.offload.tier_contains(h) for h in chain)

            hint = KvPrefetchHint(
                2, [[l, s] for l, s in pairs], peer_worker_id=1,
                peer_blocks=len(pairs),
            )
            bus.publish(comp.event_subject(KV_PREFETCH_SUBJECT),
                        hint.to_bytes())
            for _ in range(300):
                if listener.blocks_prefetched >= len(chain):
                    break
                await asyncio.sleep(0.02)
            assert listener.blocks_prefetched >= len(chain), (
                listener.blocks_prefetched, listener.peer_pull_failures
            )
            ref_toks = [
                t for o in await collect(
                    eng_ref.generate(Context(make_req(measured))))
                for t in o.token_ids
            ]
            got = [
                t for o in await collect(
                    eng_puller.generate(Context(make_req(measured))))
                for t in o.token_ids
            ]
            # the restored prefix crossed at most ONE quantize round
            # trip (peer tier or puller landing); greedy streams on
            # this geometry stay identical — and there must be no
            # client-visible error either way
            assert got == ref_toks, (peer_mode, puller_mode, got, ref_toks)
            if peer_mode == "int8" and puller_mode == "int8":
                # both sides speak the codec: the wire itself was
                # quantized (the peer's export never dequantized)
                assert eng_puller.offload.peer_pull_blocks_total == len(chain)
        finally:
            await listener.close()
            await server.close()
            for e in (eng_peer, eng_puller, eng_ref):
                await e.close()
            await drt.shutdown()

    run(main())


# ---------------- disagg wire matrix ----------------


def _quant_disagg_stack(quant="int8", decode_quant=None):
    decode = JaxEngine(engine_cfg(kv_quant=quant if decode_quant is None
                                  else decode_quant), params=PARAMS)
    prefill = JaxEngine(engine_cfg(kv_quant=quant), params=PARAMS)
    return decode, prefill


@pytest.mark.parametrize("kv_stream", [True, False])
def test_disagg_quantized_handoff_tcp(run, kv_stream):
    """Streamed AND bulk quantized handoffs over real TCP: the wire
    carries int8 + scale frames (kv_quant_sends), the decode side
    dequantizes through the scale-aware scatter, and the stream
    matches the aggregated full-width reference."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode, prefill = _quant_disagg_stack("int8")
        transfer = KvTransferServer()
        await transfer.start()
        # kv_ici off: same-process engines share a slice fingerprint,
        # and the ICI fast path (rightly) keeps its wire full-width —
        # this test exercises the quantized DCN shape
        worker = PrefillWorker(
            prefill, queue, layer_chunk=1, kv_stream=kv_stream,
            segment_blocks=2, kv_ici=False,
        )
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer,
                           kv_stream=kv_stream)
        try:
            prompt = list(range(10, 34))
            outs = await collect(
                eng.generate(Context(make_req(prompt, max_tokens=6)))
            )
            toks = [t for o in outs for t in o.token_ids]
            assert outs[-1].finish_reason == FinishReason.LENGTH
            assert worker.stats["kv_quant_sends"] == 1
            if kv_stream:
                assert eng.stats["streamed_deliveries"] == 1
            else:
                assert eng.stats["bulk_deliveries"] == 1
            ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
            ref = await collect(
                ref_engine.generate(Context(make_req(prompt, max_tokens=6)))
            )
            ref_toks = [t for o in ref for t in o.token_ids]
            # first token sampled on the prefill worker from full-width
            # logits: always exact; the decode continuation crossed one
            # int8 round-trip and stays greedy-identical here
            assert toks == ref_toks, (toks, ref_toks)
            await ref_engine.close()
        finally:
            await worker.close()
            await transfer.close()
            await decode.close()
            await prefill.close()
            await router.stop()
            await drt.shutdown()

    run(main())


def test_disagg_quantized_sender_legacy_receiver_gets_full_width(run):
    """Version-skew: a legacy decode peer (no kv_quant capability key)
    must transparently receive dequantized full-width bytes — never a
    stream it can't decode, zero client-visible errors."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode, prefill = _quant_disagg_stack("int8", decode_quant="none")
        transfer = KvTransferServer()
        await transfer.start()
        worker = PrefillWorker(prefill, queue, layer_chunk=1)
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer)
        # simulate the LEGACY receiver: strip the capability key (and
        # the v2 stream version) from the advertised connection info
        orig_conn = eng._connection

        def legacy_conn():
            conn = orig_conn()
            conn.pop("kv_quant", None)
            conn["kv_stream"] = 1
            return conn

        eng._connection = legacy_conn
        try:
            prompt = list(range(10, 34))
            outs = await collect(
                eng.generate(Context(make_req(prompt, max_tokens=6)))
            )
            toks = [t for o in outs for t in o.token_ids]
            assert outs[-1].finish_reason == FinishReason.LENGTH
            # the sender honored the skew: zero quantized sends
            assert worker.stats["kv_quant_sends"] == 0
            assert eng.stats["remote_errors"] == 0
            ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
            ref = await collect(
                ref_engine.generate(Context(make_req(prompt, max_tokens=6)))
            )
            assert toks == [t for o in ref for t in o.token_ids]
            await ref_engine.close()
        finally:
            await worker.close()
            await transfer.close()
            await decode.close()
            await prefill.close()
            await router.stop()
            await drt.shutdown()

    run(main())


@pytest.mark.faultinject
def test_mid_kv_transfer_kill_mid_quantized_stream_redelivers_once(run):
    """A prefill worker killed MID-quantized-stream (scale frames
    already landed through the dequant scatter) must redeliver to a
    survivor exactly once, with the final stream identical to a clean
    quantized run — the exactly-once contract survives the codec."""
    from dynamo_tpu.resilience import faultpoints

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus, redeliver_after=3.0)
        decode, prefill = _quant_disagg_stack("int8")
        transfer = KvTransferServer()
        await transfer.start()
        worker_a = PrefillWorker(
            prefill, queue, layer_chunk=1, segment_blocks=2, kv_ici=False
        )
        worker_a.start()
        eng = DisaggEngine(decode, router, queue, transfer)
        try:
            warm = await collect(eng.generate(
                Context(make_req(list(range(60, 84)), max_tokens=2))
            ))
            assert [t for o in warm for t in o.token_ids]
            a_sends = worker_a.stats["kv_stream_sends"]
            faultpoints.arm("mid_kv_transfer", "kill", after=3, times=1)
            prompt = list(range(10, 34))
            gen = asyncio.ensure_future(
                collect(eng.generate(Context(make_req(prompt, max_tokens=6))))
            )
            for _ in range(100):
                if worker_a._stop.is_set():
                    break
                await asyncio.sleep(0.05)
            assert worker_a._stop.is_set(), "fault point never fired"
            assert worker_a.stats["kv_stream_sends"] == a_sends
            prefill_b = JaxEngine(engine_cfg(kv_quant="int8"), params=PARAMS)
            worker_b = PrefillWorker(
                prefill_b, queue, layer_chunk=1, segment_blocks=2,
                kv_ici=False,
            )
            worker_b.start()
            outs = await asyncio.wait_for(gen, 30)
            toks = [t for o in outs for t in o.token_ids]
            assert outs[-1].finish_reason in (
                FinishReason.LENGTH, FinishReason.EOS
            )
            # reference: a CLEAN quantized disagg run (same codec, same
            # scales — deterministic) on fresh engines
            d2, p2 = _quant_disagg_stack("int8")
            t2 = KvTransferServer()
            await t2.start()
            w2 = PrefillWorker(p2, queue, layer_chunk=1, segment_blocks=2,
                               kv_ici=False)
            eng2 = DisaggEngine(d2, router, queue, t2)
            w2.start()
            ref = await collect(
                eng2.generate(Context(make_req(prompt, max_tokens=6)))
            )
            assert toks == [t for o in ref for t in o.token_ids]
            # exactly once, quantized frames actually used, queue clean
            assert eng.stats["streamed_deliveries"] == 2
            assert worker_b.stats["kv_quant_sends"] >= 1
            assert await queue.get_depth() == 0
            await w2.close()
            await t2.close()
            await d2.close()
            await p2.close()
            await worker_b.close()
            await prefill_b.close()
        finally:
            faultpoints.reset()
            await worker_a.close()
            await transfer.close()
            await decode.close()
            await prefill.close()
            await router.stop()
            await drt.shutdown()

    run(main())


# ---------------- observability + routing ----------------


def test_workerload_from_stats_scrapes_kv_quant_keys():
    wl = WorkerLoad.from_stats(7, {
        "kv_quant_blocks_total": 42,
        "kv_quant_bytes_saved_total": 12345,
        "kv_quant_logprob_drift_max": 0.0021,
        "kv_block_bytes": 4096,
        "kv_wire_block_bytes": 2064,
    })
    assert wl.kv_quant_blocks == 42
    assert wl.kv_quant_bytes_saved == 12345
    assert wl.kv_quant_logprob_drift_max == pytest.approx(0.0021)
    assert wl.wire_block_bytes == 2064
    assert wl.wire_bytes_per_block == 2064
    # pre-quant worker: wire pricing falls back to the full width
    legacy = WorkerLoad.from_stats(8, {"kv_block_bytes": 4096})
    assert legacy.wire_bytes_per_block == 4096


def test_metrics_render_includes_kv_quant_gauges():
    from dynamo_tpu.kv_router.publisher import KvMetricsAggregator
    from dynamo_tpu.observability.component import MetricsComponent

    comp = MetricsComponent.__new__(MetricsComponent)
    comp.prefix = "dynamo_tpu"
    comp.aggregator = KvMetricsAggregator.__new__(KvMetricsAggregator)
    comp.aggregator.endpoints = ProcessedEndpoints([
        WorkerLoad.from_stats(0xAB, {
            "kv_quant_blocks_total": 9,
            "kv_quant_bytes_saved_total": 777,
            "kv_quant_logprob_drift_max": 0.003,
        })
    ])
    comp.hit_events = comp.hit_isl_blocks = comp.hit_overlap_blocks = 0
    comp.planner_decision = comp.planner_watermark = None
    comp.planner_decisions_total = 0
    comp.tracing = None
    text = comp.render()
    assert 'dynamo_tpu_kv_quant_blocks_total{worker="ab"} 9' in text
    assert 'dynamo_tpu_kv_quant_bytes_saved_total{worker="ab"} 777' in text
    assert 'dynamo_tpu_kv_quant_logprob_drift_max{worker="ab"} 0.003' in text


def test_predict_and_choose_peer_price_quantized_wire_bytes():
    """Restore/pull legs must be priced at the advertised quantized
    bytes: halving wire_block_bytes halves the transfer legs, and
    choose_peer's net-benefit flips once the cheaper wire makes a
    pull worth more than recompute."""
    def load(wid, wire_bb, overlaps_extra=0):
        return WorkerLoad(
            worker_id=wid, cost_obs=50,
            link_gbps={"host": 1.0, "peer": 1.0, "ici": 1.0},
            link_lat_ms={}, prefill_tok_s=100_000.0,
            block_bytes=1 << 20, wire_block_bytes=wire_bb,
            block_size=16, total_slots=8, kv_total_blocks=100,
        )

    # 10 tiered (non-device) blocks to restore: full-width at 1 GB/s =
    # ~10.5 ms of legs; quantized advertisement halves it
    ov = OverlapScores(scores={1: 10}, device_scores={1: 0})
    full = predict_worker_ttft_ms(load(1, 0), ov, isl_blocks=10)
    quant = predict_worker_ttft_ms(load(1, 1 << 19), ov, isl_blocks=10)
    assert full is not None and quant is not None
    assert quant < full * 0.6, (full, quant)

    # choose_peer: at 16 tok/blk and 100k tok/s, recompute of 8 blocks
    # is ~1.28 ms; a full-width pull (8 MiB over pull+land ≈ 16 ms)
    # loses, the quantized pull (~1.0 ms total) wins
    sched = KvScheduler(config=SchedulerConfig())
    ov2 = OverlapScores(scores={1: 2, 2: 10}, device_scores={1: 2, 2: 0})
    eps_full = ProcessedEndpoints([load(1, 0), load(2, 0)])
    w, _depth = sched.choose_peer(eps_full, ov2, worker_id=1, n_hint=10)
    assert w is None  # full-width pull costs more than recompute
    eps_q = ProcessedEndpoints([load(1, 1 << 14), load(2, 1 << 14)])
    w, depth = sched.choose_peer(eps_q, ov2, worker_id=1, n_hint=10)
    assert w == 2 and depth == 10  # quantized wire makes the pull pay

    # mixed fleet: the WIRE leg is priced at the SERVING PEER's codec
    # width (it ships its stored form) — a quantized puller facing a
    # full-width peer must not underprice the pull with its own halved
    # advertisement
    eps_mixed = ProcessedEndpoints([load(1, 1 << 14), load(2, 0)])
    w, _ = sched.choose_peer(eps_mixed, ov2, worker_id=1, n_hint=10)
    assert w is None, "full-width peer bytes must price the pull out"
    # and predict's pull term takes the peer's width the same way
    p_cheap = predict_worker_ttft_ms(
        load(1, 1 << 14), ov2, isl_blocks=10, peer_wire_bytes=1 << 14
    )
    p_full = predict_worker_ttft_ms(
        load(1, 1 << 14), ov2, isl_blocks=10, peer_wire_bytes=1 << 20
    )
    assert p_full > p_cheap, (p_full, p_cheap)
