"""MLA latent Pallas kernel (ops/mla_attention_pallas).

The kernel must reproduce the absorbed XLA latent path exactly (ragged
lengths, ragged tables), the merged one-write variant must equal
write-then-attend, and the model-level merged MLA decode must match the
per-layer-write XLA decode stream.
"""

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama, mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.mla_attention_pallas import (
    mla_decode_attention_merged,
    mla_paged_decode_attention,
)

BS = 8


def _latent_state(B, M, C, R, H, seed=0):
    N = B * M + 1
    ks = jax.random.split(jax.random.key(seed), 4)
    q_eff = jax.random.normal(ks[0], (B, H, C), jnp.float32)
    q_pe = jax.random.normal(ks[1], (B, H, R), jnp.float32)
    c_cache = jax.random.normal(ks[2], (1, N, BS, C), jnp.float32)
    pe_cache = jax.random.normal(ks[3], (1, N, BS, R), jnp.float32)
    tables = jnp.asarray(np.arange(1, N, dtype=np.int32).reshape(B, M))
    return q_eff, q_pe, c_cache, pe_cache, tables


def test_mla_kernel_matches_xla_ragged():
    B, M, C, R, H = 3, 4, 32, 8, 4
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(B, M, C, R, H)
    seq_lens = jnp.asarray([1, BS + 3, 3 * BS], jnp.int32)  # ragged
    scale = 0.21
    got = mla_paged_decode_attention(
        q_eff, q_pe, c_cache, pe_cache, tables, seq_lens, scale,
        interpret=True,
    )
    ref = mla.mla_decode_attention_xla(
        q_eff, q_pe, c_cache, pe_cache, tables, seq_lens, scale
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_mla_merged_matches_write_then_attend():
    B, M, C, R, H = 3, 4, 32, 8, 4
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(B, M, C, R, H, 1)
    ks = jax.random.split(jax.random.key(7), 2)
    c_new = jax.random.normal(ks[0], (B, C), jnp.float32)
    pe_new = jax.random.normal(ks[1], (B, R), jnp.float32)
    # hist 0 exercises the degenerate out == c_new row
    hist = jnp.asarray([0, 5, 2 * BS + 1], jnp.int32)
    scale = 0.17
    got = mla_decode_attention_merged(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, tables, hist, scale,
        interpret=True,
    )
    # reference: write the current token, attend through the cache
    cc, pc = c_cache, pe_cache
    for b in range(B):
        pos = int(hist[b])
        blk, off = int(tables[b, pos // BS]), pos % BS
        cc = cc.at[0, blk, off].set(c_new[b])
        pc = pc.at[0, blk, off].set(pe_new[b])
    ref = mla.mla_decode_attention_xla(
        q_eff, q_pe, cc, pc, tables, hist + 1, scale
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_mla_merged_decode_stream_matches_xla_path():
    """Model-level: the merged MLA decode (latent kernel + one append,
    interpret mode) must produce the same tokens and cache as the
    per-layer-write XLA path over a multi-step window."""
    cfg = ModelConfig.tiny_mla(dtype="float32")
    B, M, T = 2, 4, 5
    params = llama.init_params(cfg, jax.random.key(3))
    N = B * M + 1
    kc0, vc0 = llama.init_kv_cache(cfg, N, BS)
    tables = jnp.asarray(np.arange(1, N, dtype=np.int32).reshape(B, M))
    rng = np.random.RandomState(5)
    hist_tokens = rng.randint(0, cfg.vocab_size, (B, 8)).astype(np.int32)
    seq_lens0 = jnp.asarray([3, 6], jnp.int32)

    streams = {}
    caches = {}
    for label, up in {"xla": False, "merged": True}.items():
        kc, vc = jnp.copy(kc0), jnp.copy(vc0)
        # teacher-forced history
        for p in range(int(seq_lens0.max())):
            toks = jnp.asarray(hist_tokens[:, p])
            positions = jnp.full((B,), p, jnp.int32)
            lens = jnp.minimum(positions + 1, seq_lens0)
            _, kc, vc = llama.decode_step(
                params, cfg, toks, positions, tables, lens, kc, vc,
                use_pallas=up, interpret=up,
            )
        # greedy continuation
        toks = jnp.asarray(hist_tokens[np.arange(B), np.asarray(seq_lens0) - 1])
        lens = seq_lens0
        out = []
        for t in range(T):
            positions = lens - 1
            logits, kc, vc = llama.decode_step(
                params, cfg, toks, positions, tables, lens + 0, kc, vc,
                use_pallas=up, interpret=up,
            )
            toks = jnp.argmax(logits, axis=-1)
            out.append(np.asarray(toks))
            lens = lens + 1
        streams[label] = np.stack(out, axis=1)
        caches[label] = (np.asarray(kc), np.asarray(vc))

    np.testing.assert_array_equal(streams["xla"], streams["merged"])
    # caches agree on every written row (compare via the written range)
    for b in range(B):
        upto = int(seq_lens0[b]) + T - 1  # rows 0..upto-1 are real
        for pos in range(upto):
            blk, off = int(tables[b, pos // BS]), pos % BS
            for which in (0, 1):
                np.testing.assert_allclose(
                    caches["xla"][which][:, 0, blk, off],
                    caches["merged"][which][:, 0, blk, off],
                    rtol=2e-5, atol=2e-5,
                    err_msg=f"b={b} pos={pos} cache={which}",
                )


def test_mla_merged_sharded_matches_single_device():
    """The tp-sharded merged latent attention (query heads sharded,
    cache replicated) must equal the single-device call."""
    from jax.sharding import Mesh

    from dynamo_tpu.ops.mla_attention_pallas import (
        mla_decode_attention_merged_sharded,
    )

    B, M, C, R, H = 2, 4, 32, 8, 4
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(B, M, C, R, H, 4)
    ks = jax.random.split(jax.random.key(11), 2)
    c_new = jax.random.normal(ks[0], (B, C), jnp.float32)
    pe_new = jax.random.normal(ks[1], (B, R), jnp.float32)
    hist = jnp.asarray([3, BS + 2], jnp.int32)
    scale = 0.25
    ref = mla_decode_attention_merged(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, tables, hist, scale,
        interpret=True,
    )
    devs = np.array(jax.devices("cpu")[:2]).reshape(2)
    mesh = Mesh(devs, ("tp",))
    got = mla_decode_attention_merged_sharded(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, tables, hist, scale,
        mesh, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_mla_pallas_decode_on_tp_mesh_matches_single_device():
    """Model-level: MLA decode with the Pallas path on a tp=2 mesh (the
    merged loop: the one kernels-on MLA loop) must match the
    single-device XLA stream."""
    from jax.sharding import Mesh

    cfg = ModelConfig.tiny_mla(dtype="float32")
    B, M, T = 2, 4, 4
    params = llama.init_params(cfg, jax.random.key(8))
    N = B * M + 1
    tables = jnp.asarray(np.arange(1, N, dtype=np.int32).reshape(B, M))
    devs = np.array(jax.devices("cpu")[:2]).reshape(1, 2, 1, 1, 1)
    mesh = Mesh(devs, ("dp", "tp", "pp", "sp", "ep"))

    streams = {}
    for label, (msh, up) in {
        "ref": (None, False),
        "mesh-merged": (mesh, True),
    }.items():
        kc, vc = llama.init_kv_cache(cfg, N, BS)
        toks = jnp.asarray([5, 9], jnp.int32)
        lens = jnp.asarray([1, 1], jnp.int32)
        out = []
        for t in range(T):
            logits, kc, vc = llama.decode_step(
                params, cfg, toks, lens - 1, tables, lens, kc, vc,
                use_pallas=up, mesh=msh, interpret=up,
            )
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(toks))
            lens = lens + 1
        streams[label] = np.stack(out, axis=1)
    np.testing.assert_array_equal(streams["ref"], streams["mesh-merged"])


def test_mla_prefill_kernel_matches_xla():
    """The chunked-prefill latent kernel (write-before-attend, absolute-
    position causal masking) must equal mla_prefill_attention_xla on all
    REAL rows, across chunk boundaries and prefix-cache history."""
    from dynamo_tpu.ops.mla_attention_pallas import (
        mla_paged_prefill_attention,
    )

    M, C, R, H = 6, 32, 8, 4
    N = M + 1
    ks = jax.random.split(jax.random.key(12), 4)
    c_cache = jax.random.normal(ks[0], (1, N, BS, C), jnp.float32)
    pe_cache = jax.random.normal(ks[1], (1, N, BS, R), jnp.float32)
    table = jnp.asarray(np.arange(1, N, dtype=np.int32))
    scale = 0.23
    for hist, T, valid in ((0, 16, 16), (5, 16, 11), (BS + 2, 8, 3)):
        q_eff = jax.random.normal(ks[2], (T, H, C), jnp.float32)
        q_pe = jax.random.normal(ks[3], (T, H, R), jnp.float32)
        got = mla_paged_prefill_attention(
            q_eff, q_pe, c_cache, pe_cache, table, jnp.int32(hist), scale,
            interpret=True,
        )
        ref = mla.mla_prefill_attention_xla(
            q_eff, q_pe, c_cache, pe_cache, table, jnp.int32(hist),
            jnp.int32(valid), scale,
        )
        # agreement on REAL rows only (padded tails are discarded by
        # every caller; the kernel and the XLA twin mask them
        # differently by design)
        np.testing.assert_allclose(
            np.asarray(got)[:valid], np.asarray(ref)[:valid],
            rtol=2e-5, atol=2e-5, err_msg=f"hist={hist} T={T}",
        )


def test_mla_prefill_sharded_matches_single_device():
    from jax.sharding import Mesh

    from dynamo_tpu.ops.mla_attention_pallas import (
        mla_paged_prefill_attention,
        mla_paged_prefill_attention_sharded,
    )

    M, C, R, H, T = 4, 32, 8, 4, 8
    N = M + 1
    ks = jax.random.split(jax.random.key(13), 4)
    c_cache = jax.random.normal(ks[0], (1, N, BS, C), jnp.float32)
    pe_cache = jax.random.normal(ks[1], (1, N, BS, R), jnp.float32)
    q_eff = jax.random.normal(ks[2], (T, H, C), jnp.float32)
    q_pe = jax.random.normal(ks[3], (T, H, R), jnp.float32)
    table = jnp.asarray(np.arange(1, N, dtype=np.int32))
    ref = mla_paged_prefill_attention(
        q_eff, q_pe, c_cache, pe_cache, table, jnp.int32(3), 0.2,
        interpret=True,
    )
    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("tp",))
    got = mla_paged_prefill_attention_sharded(
        q_eff, q_pe, c_cache, pe_cache, table, jnp.int32(3), 0.2, mesh,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
    )


def test_mla_verify_attention_matches_write_then_attend():
    """Out-of-cache multi-token latent verify (both the XLA twin and the
    kernel-backed path) must equal writing the window's latents then
    attending per position through the cache."""
    from dynamo_tpu.ops.mla_attention_pallas import mla_verify_attention

    B, T, M, C, R, H = 2, 3, 4, 32, 8, 4
    N = B * M + 1
    ks = jax.random.split(jax.random.key(6), 6)
    q_eff = jax.random.normal(ks[0], (B, T, H, C), jnp.float32)
    q_pe = jax.random.normal(ks[1], (B, T, H, R), jnp.float32)
    c_win = jax.random.normal(ks[2], (B, T, C), jnp.float32)
    pe_win = jax.random.normal(ks[3], (B, T, R), jnp.float32)
    c_cache = jax.random.normal(ks[4], (1, N, BS, C), jnp.float32)
    pe_cache = jax.random.normal(ks[5], (1, N, BS, R), jnp.float32)
    tables = jnp.asarray(np.arange(1, N, dtype=np.int32).reshape(B, M))
    hist = jnp.asarray([0, BS + 3], jnp.int32)  # hist 0: window-only row
    scale = 0.19

    cc, pc = c_cache, pe_cache
    for b in range(B):
        for t in range(T):
            pos = int(hist[b]) + t
            blk, off = int(tables[b, pos // BS]), pos % BS
            cc = cc.at[0, blk, off].set(c_win[b, t])
            pc = pc.at[0, blk, off].set(pe_win[b, t])
    for use_pallas in (False, True):
        got = mla_verify_attention(
            q_eff, q_pe, c_win, pe_win, c_cache, pe_cache, tables, hist,
            scale, use_pallas=use_pallas, interpret=True,
        )
        for t in range(T):
            ref_t = mla.mla_decode_attention_xla(
                q_eff[:, t], q_pe[:, t], cc, pc, tables, hist + t + 1, scale
            )
            np.testing.assert_allclose(
                np.asarray(got[:, t]), np.asarray(ref_t),
                rtol=2e-5, atol=2e-5,
                err_msg=f"use_pallas={use_pallas} t={t}",
            )


def test_mla_kernel_stats_power_the_merge():
    """return_stats must emit the exact (m, l) of the history softmax:
    reconstructing full attention from (o, m, l) + the current token
    must equal the direct merged call."""
    B, M, C, R, H = 2, 4, 32, 8, 4
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(B, M, C, R, H, 2)
    hist = jnp.asarray([4, 11], jnp.int32)
    scale = 0.3
    o, m, l = mla_paged_decode_attention(
        q_eff, q_pe, c_cache, pe_cache, tables, hist, scale,
        return_stats=True, interpret=True,
    )
    ks = jax.random.split(jax.random.key(9), 2)
    c_new = jax.random.normal(ks[0], (B, C), jnp.float32)
    pe_new = jax.random.normal(ks[1], (B, R), jnp.float32)
    s_new = (
        jnp.einsum("bhc,bc->bh", q_eff, c_new)
        + jnp.einsum("bhr,br->bh", q_pe, pe_new)
    ) * scale
    m_f = jnp.maximum(m, s_new)
    alpha = jnp.exp(m - m_f)
    p_new = jnp.exp(s_new - m_f)
    manual = (
        (l * alpha)[..., None] * o.astype(jnp.float32)
        + p_new[..., None] * c_new[:, None, :]
    ) / (l * alpha + p_new)[..., None]
    direct = mla_decode_attention_merged(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, tables, hist, scale,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(manual), np.asarray(direct), rtol=2e-5, atol=2e-5
    )
