"""Pallas ragged paged-attention kernel vs the XLA reference path.

Runs in Pallas interpret mode on CPU — same kernel code that compiles via
Mosaic on TPU (ref for the role: vLLM's paged_attention kernel tests).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import jaxpr_eqns as _eqns, quantize_pages as _quantize_pages
from jax.experimental.pallas import tpu as pltpu

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


def _check_decode_case(c, interpret=True):
    """One call of the kernel against the XLA path: live rows to f32
    rounding (fp8 pages: to the pages' own), dead rows EXACTLY (out 0,
    m -1e30, l 0: what ``decode_attention_merged`` folds). ``layers``:
    the cache has that many and a ``lax.scan`` hands the kernel a traced
    index; every layer is held to the XLA path on its own slab."""
    from dynamo_tpu.ops.attention import _history_attention_xla
    from dynamo_tpu.ops.paged_attention_pallas import (
        _pick_heads_per_step,
        _pick_pages_per_step,
    )

    H, Hkv, M, lens = c["H"], c["Hkv"], c["M"], c["lens"]
    T, window, L = c.get("T", 0), c.get("window", 0), c.get("layers", 0)
    stats = bool(c.get("stats") or T)
    B, D, bs = len(lens), 128, 16
    q, kc, vc, tables = _mk(B, H, Hkv, D, B * M + 1, bs, M, seed=11)
    seq_lens = jnp.asarray(lens, jnp.int32)
    scale = D**-0.5
    tol = dict(rtol=2e-5, atol=2e-5)
    if c.get("fp8"):
        kc, vc = (a.astype(jnp.float8_e4m3fn) for a in (kc, vc))
    # the cache's layers: the slab alone, or L slabs that differ
    slabs = [(kc, vc)]
    if L:
        slabs = [(jnp.roll(kc, l, axis=1), jnp.roll(vc, l, axis=1))
                 for l in range(L)]
    planes = [{} for _ in slabs]
    if c.get("int8"):
        quant = [[_quantize_pages(a) for a in kv] for kv in slabs]
        slabs = [(k[0], v[0]) for k, v in quant]
        planes = [dict(k_scales=k[1], v_scales=v[1]) for k, v in quant]
    Hh = _pick_heads_per_step(
        Hkv, 8, D, bs, _pick_pages_per_step(M), slabs[0][0].dtype.itemsize
    )
    assert Hkv // Hh == c.get("tiles", 1)
    live = np.asarray(lens) > 0
    G = H // Hkv
    kw = dict(return_stats=stats, window=window, interpret=interpret)
    if T:  # packed as verify_attention packs: rows (hkv, t, g)
        q4 = jax.random.normal(jax.random.key(5), (B, T, H, D), jnp.float32)
        qk = q4.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4)
        qk = qk.reshape(B, Hkv * T * G, D)
        kw.update(q_pos_offset=1, group=G)
    else:
        q4, qk = q[:, None], q
    k_all = jnp.stack([k for k, _ in slabs])
    v_all = jnp.stack([v for _, v in slabs])

    def call(layer, scales):
        return paged_decode_attention(
            qk, k_all, v_all, layer, tables, seq_lens, scale, **scales, **kw
        )

    if L:  # one traced index for all layers, one kernel
        stacked = {k: jnp.stack([p[k] for p in planes]) for k in planes[0]}
        _, outs = jax.lax.scan(
            lambda _, l: (None, call(
                l, {k: a[l] for k, a in stacked.items()})),
            None, jnp.arange(L),
        )
        outs = [jax.tree.map(lambda a: a[l], outs) for l in range(L)]
    else:
        outs = [call(0, planes[0])]
    for out, (k_l, v_l), scales in zip(outs, slabs, planes):
        o = np.asarray(out[0] if stats else out)
        # the twin's first query sits one PAST the history (the verify
        # path's q_pos_offset 1); a plain decode row's is its last token
        ro, rm, rl = _history_attention_xla(
            q4, k_l, v_l, tables, seq_lens, scale,
            window=window and window + (not T), **scales,
        )
        if not T:  # and the plain decode reference agrees with its twin
            ref = decode_attention_xla(
                q, k_l, v_l, tables, seq_lens, scale, window=window,
                **scales,
            )
            np.testing.assert_allclose(
                o[live], np.asarray(ref)[live], **tol
            )
        shape = (B, Hkv, T or 1, G)
        np.testing.assert_allclose(
            o.reshape(*shape, D)[live], np.asarray(ro)[live], **tol
        )
        assert not o[~live].any()  # a dead row: exactly 0
        if stats:  # the m / l planes the merged path folds
            m, l = (np.asarray(a).reshape(shape) for a in out[1:])
            np.testing.assert_allclose(
                m[live], np.asarray(rm)[live], **tol
            )
            np.testing.assert_allclose(
                l[live], np.asarray(rl)[live], **tol
            )
            assert (m[~live] == np.float32(-1e30)).all()
            assert not l[~live].any()


@pytest.mark.parametrize("case", list(_HEAD_TILE_CASES))
def test_decode_kernel_head_tiles_match_xla(case):
    _check_decode_case(_HEAD_TILE_CASES[case])


# ---------------- the walk over a row's own pages ----------------
# The kernel's grid is (rows, head tiles); inside a step it walks the
# row's OWN superblocks (``cdiv(seq_len, P * bs)`` of them, from the
# window's floor on) and fetches each one's pages itself. One batch
# holds every edge of that walk: empty, one token, a page's edge either
# side, a superblock's edge either side, the full table, with dead rows
# first, last and between the live ones.


def _walk_lens(M, bs=16, P=8):
    return [0, 1, bs, 0, bs + 1, P * bs, P * bs + 1, 0, M * bs, 0]


_LIVE_WALK_CASES = {
    "plain": dict(H=16, Hkv=16, M=32, lens=_walk_lens(32)),
    "stats": dict(H=16, Hkv=16, M=32, lens=_walk_lens(32), stats=True),
    # the floor of the longer rows lies past their first superblock
    # (lengths 129 and 512 at window 40 start at superblocks 0 and 3)
    "window": dict(H=8, Hkv=4, M=32, lens=_walk_lens(32), window=40),
    "window-stats": dict(H=8, Hkv=4, M=32, lens=_walk_lens(32), window=40,
                         stats=True),
    # as verify_attention calls it: T in-flight tokens x G heads a row,
    # the query one past the history, each row with its own floor
    "verify": dict(H=8, Hkv=4, M=32, lens=_walk_lens(32), T=3),
    "verify-window": dict(H=8, Hkv=4, M=32, lens=_walk_lens(32), T=3,
                          window=40),
    "int8-scales": dict(H=16, Hkv=16, M=32, lens=_walk_lens(32), int8=True,
                        stats=True),
    "fp8": dict(H=8, Hkv=4, M=32, lens=_walk_lens(32), fp8=True,
                stats=True),
    "tp-shard-hkv2": dict(H=4, Hkv=2, M=32, lens=_walk_lens(32),
                          stats=True),
    "two-head-tiles-hkv32": dict(H=32, Hkv=32, M=16, lens=_walk_lens(16),
                                 stats=True, tiles=2),
    "gqa-24-8": dict(H=24, Hkv=8, M=32, lens=_walk_lens(32), stats=True),
    "scan-traced-layer": dict(H=8, Hkv=4, M=32, lens=_walk_lens(32),
                              stats=True, layers=3),
    "scan-traced-layer-int8": dict(H=4, Hkv=2, M=16, lens=_walk_lens(16),
                                   int8=True, layers=3),
}


@pytest.mark.parametrize("case", list(_LIVE_WALK_CASES))
def test_decode_kernel_walks_each_rows_own_pages(case):
    _check_decode_case(_LIVE_WALK_CASES[case])


@pytest.mark.parametrize("case", ["stats", "fp8", "verify-window"])
def test_decode_kernel_reads_nothing_it_did_not_fetch(case):
    """The same walk under the TPU interpreter with every scratch buffer
    and every unwritten output poisoned with NaN, and its race detector
    on: a page past a row's last is never fetched, so whatever the slot
    holds there must not reach the accumulator (0 x NaN of a stale V
    row), and no DMA may land in a slot that is still being read. Every
    live row ends on a page it does not hold, whichever row the
    interpreter runs first (it shuffles a parallel grid dimension)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc

    M, bs, P = 16, 16, 8
    c = dict(_LIVE_WALK_CASES[case], M=M,
             lens=[0, 1, bs + 1, 0, P * bs + 1, (M - 1) * bs, 0])
    ipc.reset_tpu_interpret_mode_state()
    _check_decode_case(c, interpret=pltpu.InterpretParams(
        uninitialized_memory="nan", detect_races=True,
    ))
    assert not ipc.races.races_found


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


def _step_program_jaxpr(program, B=4, M=8, N=40, layers=3):
    """(jaxpr, cfg, the caches' shapes) of a decode program on the
    kernels' path, traced at a tiny width. ``<program>-mla``: a latent
    model, whose second cache (the rotary keys) is narrower."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    moe = dict(num_experts=4, num_experts_per_tok=2,
               moe_intermediate_size=32)
    bs = 16
    if program.endswith("-mla"):
        program = program[:-len("-mla")]
        cfg = ModelConfig.tiny_mla(num_layers=layers, kv_lora_rank=128)
    else:
        cfg = ModelConfig.tiny(
            num_layers=layers, head_dim=128,
            **(moe if program.endswith("experts") else {}),
        )
    params = llama.init_params(cfg, jax.random.key(0))
    kc, vc = llama.init_kv_cache(cfg, N, bs)
    if cfg.is_mla:
        assert kc.shape == (layers, 1, N, bs, 128)
        assert vc.shape == (layers, 1, N, bs, 8)
    else:
        assert kc.shape == vc.shape == (
            layers, cfg.num_kv_heads, N, bs, 128)
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
    return jaxpr, cfg, {kc.shape, vc.shape}


@pytest.mark.parametrize(
    "program", ["decode_window-dense", "decode_window-experts",
                "decode_step", "verify_window", "decode_window-mla",
                "decode_step-mla", "verify_window-mla"]
)
def test_step_programs_hand_the_kernel_the_whole_cache(program):
    """The slab must not come back: in the decode programs every cache
    operand of every ``pallas_call`` is the cache itself, 5-D, and no
    equation cuts an ``[Hkv, N, bs, D]`` layer out of it (the TPU
    compiler materialises a slice that feeds a custom call: a copy of
    the whole pool a step, PERF.md section 6, PR 29). A latent model
    (PR 53) with more than one latent layer, so that a cut is no
    bitcast."""
    jaxpr, cfg, cache_shapes = _step_program_jaxpr(program)
    kernels = 0
    for eqn in _eqns(jaxpr.jaxpr):
        for cache_shape in cache_shapes:
            if eqn.primitive.name == "pallas_call":
                for var in eqn.invars:
                    shape = var.aval.shape
                    if len(shape) >= 4 and shape[-3:] == cache_shape[-3:]:
                        assert shape == cache_shape, (
                            f"a pallas_call takes a {shape} cut of the "
                            f"{cache_shape} cache"
                        )
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                assert shape not in (
                    cache_shape[1:], (1, *cache_shape[1:])), (
                    f"{eqn.primitive.name} produces a layer slab {shape}"
                )
        kernels += eqn.primitive.name == "pallas_call"
    # attention of every layer (+ the merged paths' one append)
    assert kernels >= cfg.num_layers


@pytest.mark.parametrize(
    "program", ["decode_window-dense", "decode_step", "verify_window",
                "decode_window-mla", "decode_step-mla", "verify_window-mla"]
)
def test_step_programs_walk_no_grid_over_the_table(program):
    """The table's width must not come back into a grid: in the decode
    programs no ``pallas_call`` has a grid dimension of ``M // P``
    superblocks (a step for a table page no sequence holds cost a
    microsecond each, 96-99 % of the kernel: PERF.md section 6, PR 31),
    and each cache is an operand ONCE (the BlockSpec streams took it
    ``P`` times). Sizes chosen so that 7 is nothing else's extent."""
    from dynamo_tpu.ops.paged_attention_pallas import _pick_pages_per_step

    B, M, layers = 3, 56, 2
    P = _pick_pages_per_step(M)
    assert (P, M // P) == (8, 7)
    jaxpr, cfg, cache_shapes = _step_program_jaxpr(
        program, B=B, M=M, N=B * M + 1, layers=layers
    )
    attention = 0
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        grid = tuple(eqn.params["grid_mapping"].grid)
        assert M // P not in grid and M not in grid, (
            f"a kernel's grid {grid} has the table's width in it"
        )
        caches = [v for v in eqn.invars if v.aval.shape in cache_shapes]
        assert len(caches) <= 2 and len(set(map(id, caches))) == len(caches), (
            f"a kernel takes {len(caches)} cache operands"
        )
        tables = [v for v in eqn.invars if v.aval.shape == (B, M)]
        if tables and not any(
            v.aval.shape in cache_shapes for v in eqn.outvars
        ):
            attention += 1
            # rows x head tiles; a latent cache has one "head": rows
            assert grid == ((B,) if cfg.is_mla else (B, 1))
    assert attention == layers


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



def test_decode_window_hands_a_dead_slot_no_length_at_any_step(monkeypatch):
    """A slot that enters a decode window dead costs the kernel zero
    trips at EVERY step: the scan counts its length up from 0 (positions
    and the append keep that), but attention is handed a length <= 0 for
    it at each of the window's steps, so the kernel's walk — the row's
    own superblocks — is empty. Recorded from inside the scan by a stub
    over the kernel."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import paged_attention_pallas as pap

    seen = []
    kernel = pap.paged_decode_attention

    def recording(q, k_cache, v_cache, layer, block_tables, seq_lens, *a,
                  **kw):
        jax.debug.callback(lambda sl: seen.append(np.asarray(sl)), seq_lens)
        return kernel(q, k_cache, v_cache, layer, block_tables, seq_lens,
                      *a, **kw)

    monkeypatch.setattr(pap, "paged_decode_attention", recording)
    layers, n_steps, bs, M = 2, 4, 16, 8
    cfg = ModelConfig.tiny(num_layers=layers, head_dim=128)
    lens = np.asarray([0, 5, 0, 33], np.int32)  # dead, live, dead, live
    B = len(lens)
    params = llama.init_params(cfg, jax.random.key(0))
    kc, vc = llama.init_kv_cache(cfg, B * M + 1, bs)
    tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    ints = jnp.ones((B,), jnp.int32)
    floats = jnp.ones((B,), jnp.float32)
    # un-jitted: a cached trace would not see the stub
    out = llama.decode_window.__wrapped__(
        params, cfg, ints, jnp.asarray(np.maximum(lens - 1, 0)), tables,
        jnp.asarray(lens), ints, ints, floats, ints, floats, kc, vc,
        n_steps=n_steps, use_pallas=True, interpret=True,
    )
    jax.block_until_ready(out)
    jax.effects_barrier()
    assert len(seen) == layers * n_steps
    dead = lens == 0
    for handed in seen:  # the history's length: the sequence's less one
        assert (handed[dead] <= 0).all(), handed
    for b in np.flatnonzero(~dead):
        assert sorted(int(h[b]) for h in seen) == sorted(
            [lens[b] - 1 + t for t in range(n_steps)] * layers
        )


def _head_64_model(family):
    from dynamo_tpu.models.config import ModelConfig

    return {
        "plain": lambda: ModelConfig.tiny(head_dim=64),
        # gpt-oss's lanes: sinks, alternating sliding / full layers
        "sinks+windows": lambda: ModelConfig.tiny(
            head_dim=64, attn_sinks=True, layer_windows=(20, 0)),
        "qk-norm": lambda: ModelConfig.tiny(head_dim=64, qk_norm=True),
    }[family]()


@pytest.mark.parametrize("family", ["plain", "sinks+windows", "qk-norm"])
def test_a_head_of_64_rides_in_128_lanes(monkeypatch, family):
    """The decode kernel cuts its pages out of the cache in HBM with its
    own DMAs, which Mosaic allows along whole 128-lane tiles only. A head
    of 64 (gpt-oss) therefore rides in rows of 128 lanes, upper half
    zero, in the cache and wherever attention sees q, k and v
    (``llama.kv_lanes``): the kernels get a head of 128 and the model's
    numbers are those of the unpadded head."""
    from dynamo_tpu.models import llama

    cfg = _head_64_model(family)
    B, M, bs, n_steps = 3, 4, 16, 3
    lens = np.asarray([37, 0, 18], np.int32)  # a dead slot between
    params = llama.init_params(cfg, jax.random.key(0))
    tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M))
    prompt = jax.random.randint(jax.random.key(2), (int(lens.max()),), 0,
                                cfg.vocab_size)
    ints, floats = jnp.ones((B,), jnp.int32), jnp.zeros((B,), jnp.float32)

    def run(use_pallas):
        """Prefill rows 0 and 2, then a greedy decode window."""
        kc, vc = llama.init_kv_cache(cfg, B * M + 1, bs)
        for b in np.flatnonzero(lens):
            n = int(lens[b]) - 1
            _, kc, vc = llama.prefill(
                params, cfg, jnp.pad(prompt[:n], (0, 48 - n)), tables[b],
                jnp.int32(0), jnp.int32(n), kc, vc,
            )
        return llama.decode_window(
            params, cfg, prompt[:B], jnp.asarray(np.maximum(lens - 1, 0)),
            tables, jnp.asarray(lens), ints, ints, floats, ints,
            floats + 1.0, kc, vc, n_steps=n_steps, use_pallas=use_pallas,
            interpret=use_pallas, with_logprobs=True,
        )

    by_kernel = run(True)
    kc = by_kernel[1]
    assert kc.shape[-1] == 128 == llama.kv_lanes(cfg)
    # what the cache holds past the head's 64 lanes is zero
    assert not np.asarray(kc[..., 64:], np.float32).any()
    by_xla = run(False)
    # unpadded: the head as the checkpoint has it, 64 lanes everywhere
    monkeypatch.setattr(llama, "kv_lanes", lambda c: c.head_dim)
    jax.clear_caches()
    unpadded = run(False)
    jax.clear_caches()
    assert unpadded[1].shape[-1] == 64
    live = lens > 0
    for got in (by_kernel, by_xla):
        np.testing.assert_array_equal(  # the sampled tokens
            np.asarray(got[0])[:, live], np.asarray(unpadded[0])[:, live])
        for a, b in zip(jax.tree.leaves(got[-1]),
                        jax.tree.leaves(unpadded[-1])):  # the logprobs
            if not jnp.issubdtype(a.dtype, jnp.floating):
                continue  # top-k ids swap at bf16 near-ties
            np.testing.assert_allclose(
                np.asarray(a, np.float32)[:, live],
                np.asarray(b, np.float32)[:, live], rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("head_dim,why_not", [
    (64, None), (128, None), (256, None),
    (96, "head_dim 96 is not a multiple of 64"),
])
def test_engine_gate_keeps_the_kernels_for_a_head_of_64(
    monkeypatch, head_dim, why_not
):
    """gpt-oss (head_dim 64) serves by the Pallas kernels on a TPU: the
    gate refuses only a head that is no multiple of 64, with its reason."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny(head_dim=head_dim)
    eng = JaxEngine(
        EngineConfig(model=cfg, num_blocks=8, block_size=16,
                     max_batch_size=2, max_context=64),
        params=llama.init_params(cfg, jax.random.key(0)),
    )
    assert eng.attention_path["path"] == "xla"  # the CPU's answer
    assert eng.k_cache.shape[-1] == llama.kv_lanes(cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert eng._pallas_gate(None) == why_not
