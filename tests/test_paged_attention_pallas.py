"""Pallas ragged paged-attention kernel vs the XLA reference path.

Runs in Pallas interpret mode on CPU — same kernel code that compiles via
Mosaic on TPU (ref for the role: vLLM's paged_attention kernel tests).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import decode_attention_xla
from dynamo_tpu.ops.paged_attention_pallas import paged_decode_attention


def _mk(B, H, Hkv, D, N, bs, M, seed=0):
    k = jax.random.key(seed)
    ks = jax.random.split(k, 5)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (Hkv, N, bs, D), jnp.float32)
    vc = jax.random.normal(ks[2], (Hkv, N, bs, D), jnp.float32)
    # distinct physical pages per sequence (1.. like the allocator; 0 = trash)
    tables = np.zeros((B, M), np.int32)
    perm = np.arange(1, N)
    rng = np.random.default_rng(seed)
    rng.shuffle(perm)
    for b in range(B):
        tables[b] = perm[b * M : (b + 1) * M]
    return q, kc, vc, jnp.asarray(tables)


@pytest.mark.parametrize("H,Hkv", [(8, 8), (8, 2), (16, 8)])
def test_kernel_matches_xla(H, Hkv):
    B, D, N, bs, M = 4, 128, 64, 16, 4
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M)
    seq_lens = jnp.asarray([1, bs, 2 * bs + 3, M * bs], jnp.int32)
    scale = D**-0.5
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale)
    got = paged_decode_attention(
        q, kc[None], vc[None], 0, tables, seq_lens, scale, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_kernel_ragged_and_empty_slots():
    """Empty slots (seq_len 0) must not poison other rows with NaNs."""
    B, H, Hkv, D, N, bs, M = 4, 8, 4, 128, 32, 8, 3
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M, seed=1)
    seq_lens = jnp.asarray([0, 5, 0, 17], jnp.int32)
    scale = D**-0.5
    got = paged_decode_attention(
        q, kc[None], vc[None], 0, tables, seq_lens, scale, interpret=True
    )
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale)
    got, ref = np.asarray(got), np.asarray(ref)
    assert not np.isnan(got).any()
    for b, sl in enumerate([0, 5, 0, 17]):
        if sl > 0:
            np.testing.assert_allclose(got[b], ref[b], rtol=2e-5, atol=2e-5)


def test_kernel_sharded_tp2_matches_xla():
    """The shard_map wrapper (tp=2 over kv heads) must match the dense XLA
    path — this is the sharded-mesh decode hot path (interpret mode on a
    CPU mesh; same shard_map + kernel compile via Mosaic on TPU)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.attention import paged_decode_attention_sharded

    B, H, Hkv, D, N, bs, M = 4, 8, 4, 128, 64, 16, 4
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M, seed=3)
    seq_lens = jnp.asarray([1, bs, 2 * bs + 3, M * bs], jnp.int32)
    scale = D**-0.5
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 1, 2),
                ("dp", "pp", "sp", "ep", "tp"))
    qs = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    cache_sh = NamedSharding(mesh, P(None, "tp", None, None, None))
    kcs = jax.device_put(kc[None], cache_sh)
    vcs = jax.device_put(vc[None], cache_sh)
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale)
    got = paged_decode_attention_sharded(
        qs, kcs, vcs, 0, tables, seq_lens, scale, mesh, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------- ragged prefill kernel ----------------


def _mk_prefill(T, H, Hkv, D, N, bs, M, hist, seed=0):
    """Random cache with history + a chunk written at [hist, hist+T) —
    returns everything both the XLA ref and the Pallas kernel need."""
    from dynamo_tpu.ops.attention import write_chunk_to_cache

    k = jax.random.key(seed)
    ks = jax.random.split(k, 5)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    k_chunk = jax.random.normal(ks[1], (T, Hkv, D), jnp.float32)
    v_chunk = jax.random.normal(ks[2], (T, Hkv, D), jnp.float32)
    kc = jax.random.normal(ks[3], (Hkv, N, bs, D), jnp.float32)
    vc = jax.random.normal(ks[4], (Hkv, N, bs, D), jnp.float32)
    rng = np.random.default_rng(seed)
    table = rng.permutation(np.arange(1, N))[:M].astype(np.int32)
    table = jnp.asarray(table)
    hist = jnp.int32(hist)
    # pallas reads the chunk from the cache: write-before-attend
    kc_w = write_chunk_to_cache(kc, k_chunk, table, hist)
    vc_w = write_chunk_to_cache(vc, v_chunk, table, hist)
    return q, k_chunk, v_chunk, kc, vc, kc_w, vc_w, table, hist


@pytest.mark.parametrize("H,Hkv,hist,T,valid", [
    (8, 8, 0, 32, 32),       # plain prefill, no history
    (8, 2, 24, 32, 32),      # GQA + chunked continuation
    (16, 8, 7, 48, 33),      # ragged: padded chunk tail
    (8, 4, 0, 8, 5),         # tiny chunk, padded
])
def test_prefill_kernel_matches_xla(H, Hkv, hist, T, valid):
    from dynamo_tpu.ops.attention import chunk_attention_with_cache_xla
    from dynamo_tpu.ops.paged_attention_pallas import paged_prefill_attention

    D, N, bs, M = 128, 64, 16, 8
    q, k_chunk, v_chunk, kc, vc, kc_w, vc_w, table, h = _mk_prefill(
        T, H, Hkv, D, N, bs, M, hist
    )
    scale = D**-0.5
    ref = chunk_attention_with_cache_xla(
        q, k_chunk, v_chunk, kc, vc, table, h, jnp.int32(valid), scale
    )
    got = paged_prefill_attention(q, kc_w, vc_w, table, h, scale, interpret=True)
    # real rows must agree exactly; padded tail rows are discarded by callers
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(ref)[:valid], rtol=2e-5, atol=2e-5
    )
    assert not np.isnan(np.asarray(got)).any()


def test_prefill_kernel_long_multitile():
    """T > 128 exercises multiple q tiles sharing the page pipeline."""
    from dynamo_tpu.ops.attention import chunk_attention_with_cache_xla
    from dynamo_tpu.ops.paged_attention_pallas import paged_prefill_attention

    T, H, Hkv, D, N, bs, M, hist = 160, 8, 4, 128, 128, 16, 16, 30
    q, k_chunk, v_chunk, kc, vc, kc_w, vc_w, table, h = _mk_prefill(
        T, H, Hkv, D, N, bs, M, hist, seed=5
    )
    scale = D**-0.5
    ref = chunk_attention_with_cache_xla(
        q, k_chunk, v_chunk, kc, vc, table, h, jnp.int32(T), scale
    )
    got = paged_prefill_attention(q, kc_w, vc_w, table, h, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_prefill_kernel_sharded_tp2_matches_xla():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.attention import (
        chunk_attention_with_cache_xla,
        paged_prefill_attention_sharded,
    )

    T, H, Hkv, D, N, bs, M, hist = 32, 8, 4, 128, 64, 16, 8, 16
    q, k_chunk, v_chunk, kc, vc, kc_w, vc_w, table, h = _mk_prefill(
        T, H, Hkv, D, N, bs, M, hist, seed=7
    )
    scale = D**-0.5
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 1, 2),
                ("dp", "pp", "sp", "ep", "tp"))
    qs = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    kcs = jax.device_put(kc_w, NamedSharding(mesh, P("tp", None, None, None)))
    vcs = jax.device_put(vc_w, NamedSharding(mesh, P("tp", None, None, None)))
    ref = chunk_attention_with_cache_xla(
        q, k_chunk, v_chunk, kc, vc, table, h, jnp.int32(T), scale
    )
    got = paged_prefill_attention_sharded(
        qs, kcs, vcs, table, h, scale, mesh, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_prefill_end_to_end_matches_dense():
    """llama.prefill with the Pallas path (interpret) must match
    dense_forward logits — the full-model equivalence the engine relies on."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import attention as att

    cfg = ModelConfig(
        num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=128, intermediate_size=128, vocab_size=128,
        dtype="float32",
    )
    params = llama.init_params(cfg, jax.random.key(0))
    T, bs, N = 24, 8, 16
    toks = jax.random.randint(jax.random.key(1), (T,), 0, cfg.vocab_size)
    ref_logits = llama.dense_forward(params, cfg, toks)[-1]

    kc, vc = llama.init_kv_cache(cfg, N, bs)
    table = jnp.arange(1, 1 + -(-T // bs), dtype=jnp.int32)
    table = jnp.pad(table, (0, 8 - table.shape[0]))
    orig = att.chunk_attention_with_cache

    def pallas_interp(*a, **kw):
        kw["interpret"] = True
        return orig(*a, **kw)

    att.chunk_attention_with_cache = pallas_interp
    try:
        logits, kc, vc = llama.prefill.__wrapped__(
            params, cfg, toks, table, jnp.int32(0), jnp.int32(T), kc, vc,
            use_pallas=True,
        )
    finally:
        att.chunk_attention_with_cache = orig
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )


def test_kernel_bf16_cache():
    B, H, Hkv, D, N, bs, M = 2, 8, 4, 128, 32, 16, 2
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M, seed=2)
    q = q.astype(jnp.bfloat16)
    kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    seq_lens = jnp.asarray([7, 2 * bs], jnp.int32)
    scale = D**-0.5
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale)
    got = paged_decode_attention(
        q, kc[None], vc[None], 0, tables, seq_lens, scale, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2
    )


def test_kernel_fp8_cache():
    """A float8_e4m3 cache flows through the kernel's existing
    cast-to-f32 tile reads (interpret mode; the compiled lowering is
    probed on-chip by validate_tpu_kernels §7 before the engine gate
    admits quantized caches to the Pallas path)."""
    B, H, Hkv, D, N, bs, M = 2, 8, 4, 128, 32, 16, 2
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M, seed=3)
    kc = kc.astype(jnp.float8_e4m3fn)
    vc = vc.astype(jnp.float8_e4m3fn)
    seq_lens = jnp.asarray([7, 2 * bs], jnp.int32)
    scale = D**-0.5
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale)
    got = paged_decode_attention(
        q, kc[None], vc[None], 0, tables, seq_lens, scale, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def test_decode_kernel_sliding_window_matches_xla():
    B, H, Hkv, D, N, bs, M = 4, 8, 4, 128, 64, 16, 4
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M, seed=7)
    seq_lens = jnp.asarray([5, bs + 2, 3 * bs, M * bs], jnp.int32)
    scale = D**-0.5
    W = 10
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale, window=W)
    got = paged_decode_attention(
        q, kc[None], vc[None], 0, tables, seq_lens, scale, window=W,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_prefill_kernel_sliding_window_matches_xla():
    from dynamo_tpu.ops.attention import (
        chunk_attention_with_cache_xla,
        write_chunk_to_cache,
    )
    from dynamo_tpu.ops.paged_attention_pallas import paged_prefill_attention

    T, H, Hkv, D, N, bs, M = 8, 8, 4, 128, 32, 16, 4
    ks = jax.random.split(jax.random.key(3), 5)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    kch = jax.random.normal(ks[1], (T, Hkv, D), jnp.float32)
    vch = jax.random.normal(ks[2], (T, Hkv, D), jnp.float32)
    kc = jax.random.normal(ks[3], (Hkv, N, bs, D), jnp.float32)
    vc = jax.random.normal(ks[4], (Hkv, N, bs, D), jnp.float32)
    table = jnp.asarray(np.arange(1, M + 1, dtype=np.int32))
    hist = jnp.int32(bs + 3)
    W = 12
    scale = D**-0.5
    # pallas reads the chunk from cache: write-before-attend
    kc1 = write_chunk_to_cache(kc, kch, table, hist)
    vc1 = write_chunk_to_cache(vc, vch, table, hist)
    ref = chunk_attention_with_cache_xla(
        q, kch, vch, kc, vc, table, hist, jnp.int32(T), scale, window=W
    )
    got = paged_prefill_attention(
        q, kc1, vc1, table, hist, scale, window=W, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_merged_decode_sliding_window_matches_xla():
    from dynamo_tpu.ops.attention import decode_attention_merged

    B, H, Hkv, D, N, bs, M = 4, 8, 4, 128, 64, 16, 4
    q, kc, vc, tables = _mk(B, H, Hkv, D, N, bs, M, seed=9)
    ks = jax.random.split(jax.random.key(4), 2)
    k_new = jax.random.normal(ks[0], (B, Hkv, D), jnp.float32)
    v_new = jax.random.normal(ks[1], (B, Hkv, D), jnp.float32)
    hist = jnp.asarray([0, 5, bs, 2 * bs + 3], jnp.int32)
    scale = D**-0.5
    W = 9
    from dynamo_tpu.ops.attention import decode_slot_indices

    blk, off = decode_slot_indices(tables, hist, bs)
    # contiguous advanced indices stay in place: update is [Hkv, B, D]
    kc1 = kc.at[:, blk, off].set(k_new.swapaxes(0, 1))
    vc1 = vc.at[:, blk, off].set(v_new.swapaxes(0, 1))
    ref = decode_attention_xla(q, kc1, vc1, tables, hist + 1, scale, window=W)
    got = decode_attention_merged(
        q, k_new, v_new, kc[None], vc[None], 0, tables, hist, scale,
        window=W, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------- the decode kernel's head tiles ----------------
# A grid step covers ``Hh`` KV heads of its page group (derived from the
# shapes, ``_pick_heads_per_step``): every case runs the kernel against
# the XLA path at a shape that puts a different ``Hh`` and head-tile
# count in the grid.

_HEAD_TILE_CASES = {
    # the chat cell's head shape (MHA 16 x 128, pages of 16) cut in batch
    # and table: empty, one token, a page's edge either side, a
    # superblock's edge either side, the full table
    "cell-mha16": dict(H=16, Hkv=16, M=32,
                       lens=[0, 1, 16, 17, 128, 129, 300, 512]),
    "gqa-24-8": dict(H=24, Hkv=8, M=16, lens=[0, 5, 128, 256]),
    "tp-shard-hkv2": dict(H=4, Hkv=2, M=16, lens=[1, 129, 0, 255]),
    # f32 pages of 32 heads overflow the step's budget: two head tiles
    "head-tiles-hkv32": dict(H=32, Hkv=32, M=16, lens=[130, 256], tiles=2),
    "stats": dict(H=16, Hkv=16, M=16, lens=[1, 17, 129, 256], stats=True),
    "stats-gqa": dict(H=24, Hkv=8, M=16, lens=[3, 128, 200, 256],
                      stats=True),
    # the verify path: T in-flight tokens x G heads in the row dim, each
    # row with its own window floor
    "window-group": dict(H=16, Hkv=8, M=16, lens=[1, 40, 130, 256], T=3,
                         window=40),
    "int8-scales": dict(H=16, Hkv=16, M=16, lens=[0, 16, 129, 256],
                        int8=True),
    "int8-scales-stats": dict(H=24, Hkv=8, M=16, lens=[7, 128, 129, 256],
                              int8=True, stats=True),
}


def _quantize_pages(x):
    """f32 pages -> (int8 pages, per-page f32 scales [N])."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=(0, 2, 3)) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / s[None, :, None, None]), -127, 127)
    return q.astype(jnp.int8), s


@pytest.mark.parametrize("case", list(_HEAD_TILE_CASES))
def test_decode_kernel_head_tiles_match_xla(case):
    from dynamo_tpu.ops.attention import _history_attention_xla
    from dynamo_tpu.ops.paged_attention_pallas import (
        _pick_heads_per_step,
        _pick_pages_per_step,
    )

    c = _HEAD_TILE_CASES[case]
    H, Hkv, M, lens = c["H"], c["Hkv"], c["M"], c["lens"]
    T, window = c.get("T", 0), c.get("window", 0)
    B, D, bs = len(lens), 128, 16
    q, kc, vc, tables = _mk(B, H, Hkv, D, B * M + 1, bs, M, seed=11)
    seq_lens = jnp.asarray(lens, jnp.int32)
    scale = D**-0.5
    scales = {}
    if c.get("int8"):
        kc, ks = _quantize_pages(kc)
        vc, vs = _quantize_pages(vc)
        scales = dict(k_scales=ks, v_scales=vs)
    Hh = _pick_heads_per_step(
        Hkv, 8, D, bs, _pick_pages_per_step(M), kc.dtype.itemsize
    )
    assert Hkv // Hh == c.get("tiles", 1)
    live = np.asarray(lens) > 0

    if T:  # packed as verify_attention packs: rows (hkv, t, g)
        G = H // Hkv
        q4 = jax.random.normal(jax.random.key(5), (B, T, H, D), jnp.float32)
        qp = q4.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4)
        o, m, l = paged_decode_attention(
            qp.reshape(B, Hkv * T * G, D), kc[None], vc[None], 0, tables,
            seq_lens, scale,
            return_stats=True, window=window, q_pos_offset=1, group=G,
            interpret=True, **scales,
        )
        ro, rm, rl = _history_attention_xla(
            q4, kc, vc, tables, seq_lens, scale, window=window, **scales
        )
        got = (o.reshape(B, Hkv, T, G, D), m.reshape(B, Hkv, T, G),
               l.reshape(B, Hkv, T, G))
        for g, r in zip(got, (ro, rm, rl)):
            np.testing.assert_allclose(
                np.asarray(g)[live], np.asarray(r)[live], rtol=2e-5,
                atol=2e-5,
            )
        return

    out = paged_decode_attention(
        q, kc[None], vc[None], 0, tables, seq_lens, scale,
        return_stats=bool(c.get("stats")), interpret=True, **scales,
    )
    o = out[0] if c.get("stats") else out
    assert not np.isnan(np.asarray(o)).any()  # empty slots included
    ref = decode_attention_xla(q, kc, vc, tables, seq_lens, scale, **scales)
    np.testing.assert_allclose(
        np.asarray(o)[live], np.asarray(ref)[live], rtol=2e-5, atol=2e-5
    )
    if c.get("stats"):  # the m / l planes the merged path folds
        _, rm, rl = _history_attention_xla(
            q[:, None], kc, vc, tables, seq_lens, scale, **scales
        )
        np.testing.assert_allclose(
            np.asarray(out[1])[live], np.asarray(rm)[live, :, 0],
            rtol=2e-5, atol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(out[2])[live], np.asarray(rl)[live, :, 0],
            rtol=2e-5, atol=2e-5,
        )


def test_heads_per_step_rule():
    """``Hh`` divides Hkv, fits the stated budget, is the largest that
    does, and is every head for the chat cell's shape."""
    from dynamo_tpu.ops.paged_attention_pallas import (
        _STEP_VMEM_BUDGET,
        _decode_step_vmem_bytes,
        _pick_heads_per_step,
    )

    # the cell: MHA 16 x 128, bf16 pages of 16, P 8 -> one step, and the
    # docstring's arithmetic: 2 MiB of streams + 2 MiB of f32 K / V
    assert _pick_heads_per_step(16, 8, 128, 16, 8, 2) == 16
    assert 4 * 2**20 < _decode_step_vmem_bytes(16, 8, 128, 16, 8, 2) \
        < 5 * 2**20
    assert _pick_heads_per_step(8, 8, 128, 16, 8, 2) == 8  # phi-4-mini
    assert _pick_heads_per_step(2, 8, 128, 16, 8, 2) == 2  # a tp=4 shard
    assert _pick_heads_per_step(32, 8, 128, 16, 8, 2) == 16  # MHA 7B
    for Hkv in (1, 2, 3, 8, 12, 16, 32, 40, 64, 128):
        for Gp, D, bs, P, item in (
            (8, 128, 16, 8, 2), (8, 128, 16, 8, 1), (8, 64, 16, 8, 2),
            (16, 128, 32, 8, 4), (40, 256, 16, 4, 2), (8, 128, 16, 1, 2),
        ):
            Hh = _pick_heads_per_step(Hkv, Gp, D, bs, P, item)
            assert Hkv % Hh == 0
            fits = [
                h for h in range(1, Hkv + 1)
                if Hkv % h == 0 and _decode_step_vmem_bytes(
                    h, Gp, D, bs, P, item) <= _STEP_VMEM_BUDGET
            ]
            assert Hh == (max(fits) if fits else 1)


# ---------------- the whole cache as the operand ----------------
# The kernel takes ``[L, Hkv, N, bs, D]`` and a layer index and reads
# the layer's pages in place. Every case: for each layer of a 3-layer
# cache, the call on the whole cache equals the call on that layer's
# slab as a one-layer cache BIT FOR BIT (same kernel body, same bytes).

_WHOLE_CACHE_CASES = {
    "bf16-hkv2": dict(H=4, Hkv=2),
    "bf16-hkv16-stats": dict(H=16, Hkv=16, stats=True),
    # bf16 pages of 32 heads: two head tiles of 16
    "bf16-hkv32-two-tiles": dict(H=32, Hkv=32, stats=True, tiles=2),
    "int8-scales-hkv16": dict(H=16, Hkv=16, int8=True),
    "int8-scales-hkv2-stats": dict(H=8, Hkv=2, int8=True, stats=True),
    # the verify path's packing: T tokens x G heads a row, per-row floor
    "window-group": dict(H=4, Hkv=2, T=3, window=40, stats=True),
    "sinks": dict(H=8, Hkv=2, sinks=True, window=24),
    "scan-traced-layer": dict(H=16, Hkv=16, stats=True, scan=True),
    "scan-traced-layer-int8": dict(H=4, Hkv=2, int8=True, scan=True),
}


@pytest.mark.parametrize("case", list(_WHOLE_CACHE_CASES))
def test_whole_cache_operand_equals_slab_bitwise(case):
    from dynamo_tpu.ops import attention as att
    from dynamo_tpu.ops.paged_attention_pallas import (
        _pick_heads_per_step,
        _pick_pages_per_step,
    )

    c = _WHOLE_CACHE_CASES[case]
    H, Hkv, T = c["H"], c["Hkv"], c.get("T", 0)
    L, B, D, bs, M = 3, 3, 128, 16, 16
    N = B * M + 1
    lens = jnp.asarray([0, 130, 256], jnp.int32)  # dead, ragged, full
    ks = jax.random.split(jax.random.key(29), 4)
    rows = Hkv * T * (H // Hkv) if T else H
    q = jax.random.normal(ks[0], (B, rows, D), jnp.float32)
    kc = jax.random.normal(ks[1], (L, Hkv, N, bs, D), jnp.float32)
    vc = jax.random.normal(ks[2], (L, Hkv, N, bs, D), jnp.float32)
    tables = jnp.asarray(
        np.random.default_rng(29).permutation(np.arange(1, N))
        .reshape(B, M).astype(np.int32)
    )
    k_planes = v_planes = None
    if c.get("int8"):
        # pages [L, Hkv, N, bs, D] int8 and their planes [L, N]
        kc, k_planes = map(jnp.stack, zip(*map(_quantize_pages, kc)))
        vc, v_planes = map(jnp.stack, zip(*map(_quantize_pages, vc)))
    else:
        q = q.astype(jnp.bfloat16)
        kc, vc = kc.astype(jnp.bfloat16), vc.astype(jnp.bfloat16)
    Hh = _pick_heads_per_step(
        Hkv, 8, D, bs, _pick_pages_per_step(M), kc.dtype.itemsize
    )
    assert Hkv // Hh == c.get("tiles", 1)
    kw = dict(interpret=True)
    if c.get("sinks"):
        sinks = jax.random.normal(ks[3], (H,), jnp.float32)

        def call(k, v, layer, scales):
            return att._decode_kernel_with_sinks(
                q, k, v, layer, tables, lens, D**-0.5, sinks,
                window=c["window"], **scales, **kw,
            )
    else:
        if T:
            kw.update(q_pos_offset=1, group=H // Hkv)

        def call(k, v, layer, scales):
            return paged_decode_attention(
                q, k, v, layer, tables, lens, D**-0.5,
                return_stats=bool(c.get("stats")),
                window=c.get("window", 0), **scales, **kw,
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
        for w, s in zip(jax.tree.leaves(whole[l]), jax.tree.leaves(slab)):
            assert w.dtype == s.dtype and w.shape == s.shape
            np.testing.assert_array_equal(
                np.asarray(w, np.float32), np.asarray(s, np.float32)
            )
    # and the layers differ: an index that read the wrong slab would show
    assert not np.array_equal(
        np.asarray(jax.tree.leaves(whole[0])[0], np.float32)[1:],
        np.asarray(jax.tree.leaves(whole[1])[0], np.float32)[1:],
    )


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones (jit, scan, while, cond,
    shard_map) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize(
    "program", ["decode_window-dense", "decode_window-experts",
                "decode_step", "verify_window"]
)
def test_step_programs_hand_the_kernel_the_whole_cache(program):
    """The slab must not come back: in the decode programs every cache
    operand of every ``pallas_call`` is the cache itself, 5-D, and no
    equation cuts an ``[Hkv, N, bs, D]`` layer out of it (the TPU
    compiler materialises a slice that feeds a custom call: a copy of
    the whole pool a step, PERF.md section 6, PR 29)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    moe = dict(num_experts=4, num_experts_per_tok=2,
               moe_intermediate_size=32)
    cfg = ModelConfig.tiny(
        num_layers=3, head_dim=128,
        **(moe if program.endswith("experts") else {}),
    )
    B, M, N, bs = 4, 8, 40, 16
    params = llama.init_params(cfg, jax.random.key(0))
    kc, vc = llama.init_kv_cache(cfg, N, bs)
    cache_shape = kc.shape
    assert cache_shape == (3, cfg.num_kv_heads, N, bs, 128)
    ints = jnp.ones((B,), jnp.int32)
    floats = jnp.ones((B,), jnp.float32)
    tables = jnp.ones((B, M), jnp.int32)
    kw = dict(use_pallas=True, interpret=True)
    if program.startswith("decode_window"):
        jaxpr = jax.make_jaxpr(
            lambda p, k, v: llama.decode_window(
                p, cfg, ints, ints, tables, ints, ints, ints, floats, ints,
                floats, k, v, n_steps=2,
                moe_counters=program.endswith("experts"), **kw,
            )
        )(params, kc, vc)
    elif program == "decode_step":
        jaxpr = jax.make_jaxpr(
            lambda p, k, v: llama.decode_step(
                p, cfg, ints, ints, tables, ints, k, v, **kw,
            )
        )(params, kc, vc)
    else:
        jaxpr = jax.make_jaxpr(
            lambda p, k, v: llama.verify_window(
                p, cfg, jnp.ones((B, 3), jnp.int32),
                jnp.ones((B, 2), jnp.int32), ints, tables, ints, ints, ints,
                floats, ints, floats, k, v, n_spec=2, **kw,
            )
        )(params, kc, vc)
    kernels = 0
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            kernels += 1
            for var in eqn.invars:
                shape = var.aval.shape
                if len(shape) >= 4 and shape[-3:] == cache_shape[-3:]:
                    assert shape == cache_shape, (
                        f"a pallas_call takes a {shape} cut of the "
                        f"{cache_shape} cache"
                    )
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert shape not in (cache_shape[1:], (1, *cache_shape[1:])), (
                f"{eqn.primitive.name} produces a layer slab {shape}"
            )
    # attention of every layer (+ the merged paths' one append)
    assert kernels >= cfg.num_layers


@pytest.mark.parametrize("use_pallas", [True, False], ids=["kernels", "xla"])
@pytest.mark.parametrize("family", ["gqa", "mla", "softcap"])
def test_decode_layer_loop_follows_what_the_program_observes(
    family, use_pallas
):
    """``_decode_body``'s one rule, as a test: kernels on and no softcap
    -> the MERGED loop of the cache kind (exactly one ``pallas_call``
    writes the caches, all layers at once, and no XLA scatter lands in
    them); otherwise WRITE-THEN-ATTEND (one scatter a layer into K and
    into V, attention by XLA: no ``pallas_call`` at all). Nothing but
    ``use_pallas`` and the model selects the loop."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = {
        "gqa": lambda: ModelConfig.tiny(num_layers=3),
        "mla": lambda: ModelConfig.tiny_mla(num_layers=3),
        "softcap": lambda: ModelConfig.tiny(num_layers=3, attn_softcap=30.0),
    }[family]()
    B, M, N, bs = 4, 8, 40, 16
    params = llama.init_params(cfg, jax.random.key(0))
    kc, vc = llama.init_kv_cache(cfg, N, bs)
    ints = jnp.ones((B,), jnp.int32)
    floats = jnp.ones((B,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, k, v: llama.decode_window(
            p, cfg, ints, ints, jnp.ones((B, M), jnp.int32), ints, ints,
            ints, floats, ints, floats, k, v, n_steps=2,
            use_pallas=use_pallas, interpret=use_pallas,
        )
    )(params, kc, vc)
    caches = {kc.shape, vc.shape}

    def writes_cache(eqn):
        return any(getattr(v.aval, "shape", None) in caches
                   for v in eqn.outvars)

    eqns = list(_eqns(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    appends = [e for e in kernels if writes_cache(e)]
    scatters = [e for e in eqns
                if e.primitive.name.startswith("scatter") and writes_cache(e)]
    if use_pallas and family != "softcap":
        assert len(appends) == 1 and not scatters
        assert len(kernels) == cfg.num_layers + 1  # + attention a layer
    else:
        assert not kernels
        assert len(scatters) == 2 * cfg.num_layers

