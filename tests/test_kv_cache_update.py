"""In-place cache append kernel + merged decode attention (the one-write-
per-step decode path: ops/kv_cache_update_pallas + decode_attention_merged).

Interpret/CPU: the merge math and the append semantics are validated
against the write-then-attend XLA reference path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.attention import (
    decode_attention_merged,
    decode_attention_xla,
    decode_slot_indices,
)
from dynamo_tpu.ops.kv_cache_update_pallas import kv_cache_append


def _setup(B, H, Hkv, D, L, N, bs, M, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    kc = jax.random.normal(ks[1], (L, Hkv, N, bs, D), jnp.float32)
    vc = jax.random.normal(ks[2], (L, Hkv, N, bs, D), jnp.float32)
    k_new = jax.random.normal(ks[3], (L, B, Hkv, D), jnp.float32)
    v_new = jax.random.normal(ks[4], (L, B, Hkv, D), jnp.float32)
    tables = np.zeros((B, M), np.int32)
    perm = np.arange(1, N)
    rng = np.random.default_rng(seed)
    rng.shuffle(perm)
    for b in range(B):
        tables[b] = perm[b * M : (b + 1) * M]
    return q, kc, vc, k_new, v_new, jnp.asarray(tables)


def _positions(B, M, bs):
    """Write positions covering row 0, mid-page, a page edge and the last
    row of the table — every row a distinct sequence (distinct pages)."""
    return jnp.asarray([0, 5, bs + 1, M * bs - 1][:B], jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_append_matches_scatter(dtype):
    """The kernel body itself (Pallas interpret mode — the same
    _append_kernel Mosaic compiles) against the XLA scatter it replaces."""
    B, H, Hkv, D, L, N, bs, M = 4, 8, 4, 128, 3, 64, 16, 4
    _, kc, vc, k_new, v_new, tables = _setup(B, H, Hkv, D, L, N, bs, M)
    kc, vc = kc.astype(dtype), vc.astype(dtype)
    blk, off = decode_slot_indices(tables, _positions(B, M, bs), bs)

    # mixed basic+advanced indexing with a separated group puts the
    # advanced axes (blk, off) in front: update layout [B, Hkv, D]
    # (same convention as llama._decode_body's per-layer writes)
    ref_k, ref_v = kc, vc
    for l in range(L):
        ref_k = ref_k.at[l, :, blk, off].set(k_new[l].astype(dtype))
        ref_v = ref_v.at[l, :, blk, off].set(v_new[l].astype(dtype))

    got_k, got_v = kv_cache_append(
        k_new, v_new, jnp.copy(kc), jnp.copy(vc), blk, off, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))


def test_append_quantized_matches_scatter():
    """int8 pages + per-page scale planes: _append_quant_kernel's body
    (interpret) against write_decode_token_to_cache_quantized, the XLA
    scatter that serves the non-merged path — same scale growth, page
    requant and quantized row, bit for bit."""
    from dynamo_tpu.ops.attention import write_decode_token_to_cache_quantized
    from dynamo_tpu.ops.kv_cache_update_pallas import (
        kv_cache_append_quantized,
    )

    B, H, Hkv, D, L, N, bs, M = 4, 8, 4, 128, 2, 64, 16, 4
    _, kc, vc, k_new, v_new, tables = _setup(B, H, Hkv, D, L, N, bs, M, 3)
    positions = _positions(B, M, bs)
    blk, off = decode_slot_indices(tables, positions, bs)
    # resident int8 pages under small scales, so the new rows GROW some
    # pages' scales (requant) and leave others alone
    ks = jnp.full((L, N), 0.01, jnp.float32).at[:, blk[1]].set(1.0)
    vs = jnp.full((L, N), 0.02, jnp.float32).at[:, blk[2]].set(1.0)
    kq = jnp.clip(jnp.round(kc * 40), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vc * 40), -127, 127).astype(jnp.int8)

    ref_k, ref_v, ref_ks, ref_vs = [], [], [], []
    for l in range(L):
        k_l, ks_l = write_decode_token_to_cache_quantized(
            kq[l], ks[l], k_new[l], tables, positions
        )
        v_l, vs_l = write_decode_token_to_cache_quantized(
            vq[l], vs[l], v_new[l], tables, positions
        )
        ref_k.append(k_l), ref_ks.append(ks_l)
        ref_v.append(v_l), ref_vs.append(vs_l)

    got_k, got_v, got_ks, got_vs, n_requants = kv_cache_append_quantized(
        k_new, v_new, jnp.copy(kq), jnp.copy(vq), ks, vs, blk, off,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_ks), np.stack(ref_ks))
    np.testing.assert_array_equal(np.asarray(got_vs), np.stack(ref_vs))
    np.testing.assert_array_equal(np.asarray(got_k), np.stack(ref_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.stack(ref_v))
    assert int(n_requants) > 0


@pytest.mark.parametrize("T", [1, 3, 16], ids=["T1", "T3", "T16"])
def test_append_tokens_matches_scatter(T):
    """T consecutive rows per sequence (speculative verify): both phases
    of _append_tokens_kernel (interpret) against the XLA scatter — rows
    that stay inside a page, cross into the next, and fill a whole one."""
    from dynamo_tpu.ops.kv_cache_update_pallas import (
        kv_cache_append_tokens,
        kv_cache_append_tokens_xla,
    )

    B, H, Hkv, D, L, N, bs, M = 4, 8, 4, 128, 2, 64, 16, 4
    _, kc, vc, _, _, tables = _setup(B, H, Hkv, D, L, N, bs, M, seed=4)
    ks = jax.random.split(jax.random.key(9), 2)
    k_new = jax.random.normal(ks[0], (L, B, T, Hkv, D), jnp.float32)
    v_new = jax.random.normal(ks[1], (L, B, T, Hkv, D), jnp.float32)
    # starts: page row 0, mid-page, 2 rows before an edge, last page
    start = jnp.asarray([0, 5, bs - 2, (M - 1) * bs], jnp.int32)
    pos = start[:, None] + jnp.arange(T)[None, :]  # [B, T]
    blk = jnp.take_along_axis(tables, pos // bs, axis=1)
    off = pos % bs

    ref_k, ref_v = kv_cache_append_tokens_xla(
        k_new, v_new, kc, vc, blk, off
    )
    got_k, got_v = kv_cache_append_tokens(
        k_new, v_new, jnp.copy(kc), jnp.copy(vc), blk, off, interpret=True
    )
    # page 0 is the sacrificial page phase 1 passes through: never read
    np.testing.assert_array_equal(
        np.asarray(got_k)[:, :, 1:], np.asarray(ref_k)[:, :, 1:]
    )
    np.testing.assert_array_equal(
        np.asarray(got_v)[:, :, 1:], np.asarray(ref_v)[:, :, 1:]
    )


@pytest.mark.parametrize("H,Hkv", [(8, 8), (8, 2), (16, 8)])
def test_merged_attention_matches_write_then_attend(H, Hkv):
    B, D, L, N, bs, M = 4, 128, 1, 64, 16, 4
    q, kc, vc, k_new, v_new, tables = _setup(B, H, Hkv, D, L, N, bs, M)
    # history lengths INCLUDING variety: 0 (empty), mid-page, page edge
    hist = jnp.asarray([0, 5, bs - 1, 3 * bs], jnp.int32)
    scale = D**-0.5

    # reference: write the token at position hist, then attend over hist+1
    blk, off = decode_slot_indices(tables, hist, bs)
    kc1 = kc.at[0, :, blk, off].set(k_new[0])
    vc1 = vc.at[0, :, blk, off].set(v_new[0])
    ref = decode_attention_xla(q, kc1[0], vc1[0], tables, hist + 1, scale)

    got = decode_attention_merged(
        q, k_new[0], v_new[0], kc, vc, 0, tables, hist, scale,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_decode_body_merged_path_matches_regular():
    """llama._decode_body's merged one-write branch (use_pallas=True,
    interpret) must produce the same logits and cache as the regular
    write-then-attend XLA branch over several chained decode steps."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny(dtype="float32")
    params = llama.init_params(cfg, jax.random.key(0))
    B, bs, M = 2, 4, 8
    kc0, vc0 = llama.init_kv_cache(cfg, num_blocks=2 * M + 1, block_size=bs)
    tables = jnp.asarray(
        np.arange(1, 2 * M + 1, dtype=np.int32).reshape(B, M)
    )
    rng = np.random.RandomState(7)

    state = {}
    for tag, use_pallas in (("reg", False), ("merged", True)):
        kc, vc = jnp.copy(kc0), jnp.copy(vc0)
        toks = jnp.asarray([3, 9], jnp.int32)
        logits_all = []
        for step in range(5):
            positions = jnp.asarray([step, step + 2], jnp.int32)
            seq_lens = positions + 1
            logits, kc, vc = llama.decode_step(
                params, cfg, toks, positions, tables, seq_lens, kc, vc,
                use_pallas=use_pallas, interpret=use_pallas,
            )
            logits_all.append(np.asarray(logits))
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        state[tag] = (np.stack(logits_all), np.asarray(kc), np.asarray(vc))

    np.testing.assert_allclose(
        state["merged"][0], state["reg"][0], rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        state["merged"][1], state["reg"][1], rtol=2e-5, atol=2e-5
    )
    np.testing.assert_allclose(
        state["merged"][2], state["reg"][2], rtol=2e-5, atol=2e-5
    )


def test_decode_body_merged_honors_sliding_window():
    """Regression (advisor r2 high): a sliding-window model on the merged
    decode path must mask history beyond the window — the merged calls in
    llama._decode_body previously dropped cfg.sliding_window, silently
    attending the full history once context exceeded the window."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig.tiny(dtype="float32", sliding_window=3)
    params = llama.init_params(cfg, jax.random.key(0))
    B, bs, M = 2, 4, 8
    kc0, vc0 = llama.init_kv_cache(cfg, num_blocks=2 * M + 1, block_size=bs)
    tables = jnp.asarray(
        np.arange(1, 2 * M + 1, dtype=np.int32).reshape(B, M)
    )

    state = {}
    for tag, use_pallas in (("reg", False), ("merged", True)):
        kc, vc = jnp.copy(kc0), jnp.copy(vc0)
        toks = jnp.asarray([3, 9], jnp.int32)
        logits_all = []
        # run well past the window so masking actually matters
        for step in range(8):
            positions = jnp.asarray([step, step + 2], jnp.int32)
            seq_lens = positions + 1
            logits, kc, vc = llama.decode_step(
                params, cfg, toks, positions, tables, seq_lens, kc, vc,
                use_pallas=use_pallas, interpret=use_pallas,
            )
            logits_all.append(np.asarray(logits))
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        state[tag] = np.stack(logits_all)

    np.testing.assert_allclose(
        state["merged"], state["reg"], rtol=2e-4, atol=2e-4
    )


def test_merged_sharded_tp2_matches_single_device():
    """decode_attention_merged_sharded + kv_cache_append_sharded over a
    tp=2 CPU mesh must match the single-device merged path (this is the
    sharded-mesh decode hot path on TPU)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dynamo_tpu.ops.attention import decode_attention_merged_sharded
    from dynamo_tpu.ops.kv_cache_update_pallas import kv_cache_append_sharded

    B, H, Hkv, D, L, N, bs, M = 4, 8, 4, 128, 2, 64, 16, 4
    q, kc, vc, k_new, v_new, tables = _setup(B, H, Hkv, D, L, N, bs, M, seed=5)
    hist = jnp.asarray([0, 5, bs, 2 * bs + 3], jnp.int32)
    scale = D**-0.5

    ref_o = decode_attention_merged(
        q, k_new[0], v_new[0], kc, vc, 0, tables, hist, scale,
        interpret=True,
    )
    blk, off = decode_slot_indices(tables, hist, bs)
    ref_k, ref_v = kv_cache_append(
        k_new, v_new, jnp.copy(kc), jnp.copy(vc), blk, off, interpret=True
    )

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(1, 1, 1, 1, 2),
                ("dp", "pp", "sp", "ep", "tp"))
    qs = jax.device_put(q, NamedSharding(mesh, P(None, "tp", None)))
    kns = jax.device_put(k_new, NamedSharding(mesh, P(None, None, "tp", None)))
    vns = jax.device_put(v_new, NamedSharding(mesh, P(None, None, "tp", None)))
    cache_sh = NamedSharding(mesh, P(None, "tp", None, None, None))
    kcs = jax.device_put(kc, cache_sh)
    vcs = jax.device_put(vc, cache_sh)

    got_o = decode_attention_merged_sharded(
        qs, kns[0], vns[0], kcs, vcs, 0, tables, hist, scale, mesh,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_o), np.asarray(ref_o), rtol=2e-5, atol=2e-5
    )

    got_k, got_v = kv_cache_append_sharded(
        kns, vns, jnp.copy(kcs), jnp.copy(vcs), blk, off, mesh,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(ref_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(ref_v))


def test_merged_attention_no_nans_on_empty_batch():
    B, H, Hkv, D, L, N, bs, M = 2, 8, 4, 128, 1, 16, 16, 2
    q, kc, vc, k_new, v_new, tables = _setup(B, H, Hkv, D, L, N, bs, M, seed=2)
    hist = jnp.zeros(B, jnp.int32)
    got = decode_attention_merged(
        q, k_new[0], v_new[0], kc, vc, 0, tables, hist, D**-0.5,
        interpret=True,
    )
    assert not np.isnan(np.asarray(got)).any()
