"""MLA latent Pallas kernel (ops/mla_attention_pallas).

The kernel must reproduce the absorbed XLA latent path exactly (ragged
lengths, ragged tables), the merged one-write variant must equal
write-then-attend, and the model-level merged MLA decode must match the
per-layer-write XLA decode stream.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from dynamo_tpu.models import llama, mla
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.mla_attention_pallas import (
    mla_decode_attention_merged,
    mla_paged_decode_attention,
)

BS = 8


def _latent_state(B, M, C, R, H, seed=0, L=1, bs=BS):
    """Queries, the WHOLE caches ``[L, 1, N, bs, C / R]`` as the kernel
    takes them, and a table of distinct pages."""
    N = B * M + 1
    ks = jax.random.split(jax.random.key(seed), 4)
    q_eff = jax.random.normal(ks[0], (B, H, C), jnp.float32)
    q_pe = jax.random.normal(ks[1], (B, H, R), jnp.float32)
    c_cache = jax.random.normal(ks[2], (L, 1, N, bs, C), jnp.float32)
    pe_cache = jax.random.normal(ks[3], (L, 1, N, bs, R), jnp.float32)
    tables = jnp.asarray(np.arange(1, N, dtype=np.int32).reshape(B, M))
    return q_eff, q_pe, c_cache, pe_cache, tables


def _write_then_attend(q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, layer,
                       tables, hist, scale):
    """The reference of the merged path: the current token's latents
    written at position ``hist`` of each live row, then XLA attention
    through the cache over ``hist + 1`` tokens."""
    bs = c_cache.shape[3]
    cc, pc = c_cache[layer], pe_cache[layer]
    for b in range(q_eff.shape[0]):
        pos = int(hist[b])
        if pos < 0:
            continue
        blk, off = int(tables[b, pos // bs]), pos % bs
        cc = cc.at[0, blk, off].set(c_new[b])
        pc = pc.at[0, blk, off].set(pe_new[b])
    return mla.mla_decode_attention_xla(
        q_eff, q_pe, cc, pc, tables, hist + 1, scale
    )


def test_mla_kernel_matches_xla_ragged():
    B, M, C, R, H = 3, 4, 32, 8, 4
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(B, M, C, R, H)
    seq_lens = jnp.asarray([1, BS + 3, 3 * BS], jnp.int32)  # ragged
    scale = 0.21
    got = mla_paged_decode_attention(
        q_eff, q_pe, c_cache, pe_cache, 0, tables, seq_lens, scale,
        interpret=True,
    )
    ref = mla.mla_decode_attention_xla(
        q_eff, q_pe, c_cache[0], pe_cache[0], tables, seq_lens, scale
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
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, 0, tables, hist,
        scale, interpret=True,
    )
    # reference: write the current token, attend through the cache
    ref = _write_then_attend(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, 0, tables, hist, scale
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
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, 0, tables, hist,
        scale, interpret=True,
    )
    devs = np.array(jax.devices("cpu")[:2]).reshape(2)
    mesh = Mesh(devs, ("tp",))
    got = mla_decode_attention_merged_sharded(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, 0, tables, hist,
        scale, mesh, interpret=True,
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
    # (two layers that differ: the verify reads layer 1 by its index)
    c_cache = jax.random.normal(ks[4], (2, 1, N, BS, C), jnp.float32)
    pe_cache = jax.random.normal(ks[5], (2, 1, N, BS, R), jnp.float32)
    tables = jnp.asarray(np.arange(1, N, dtype=np.int32).reshape(B, M))
    hist = jnp.asarray([0, BS + 3], jnp.int32)  # hist 0: window-only row
    scale = 0.19

    cc, pc = c_cache[1], pe_cache[1]
    for b in range(B):
        for t in range(T):
            pos = int(hist[b]) + t
            blk, off = int(tables[b, pos // BS]), pos % BS
            cc = cc.at[0, blk, off].set(c_win[b, t])
            pc = pc.at[0, blk, off].set(pe_win[b, t])
    for use_pallas in (False, True):
        got = mla_verify_attention(
            q_eff, q_pe, c_win, pe_win, c_cache, pe_cache, 1, tables, hist,
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
        q_eff, q_pe, c_cache, pe_cache, 0, tables, hist, scale,
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
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, 0, tables, hist,
        scale, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(manual), np.asarray(direct), rtol=2e-5, atol=2e-5
    )


# ---- the walk over each row's own pages (PR 53) ----

_BS16, _P = 16, 8


def _walk_lens(M, bs=_BS16, P=_P):
    """Dead slots between rows of length 1, a page, a page and one, a
    superblock exactly, a superblock and one, and the whole table."""
    return [0, 1, bs, 0, bs + 1, P * bs, P * bs + 1, 0, M * bs, 0]


def _check_walk(stats, lens, M=16, C=128, R=128, H=8, L=1, layer=0,
                interpret=True, seed=3, poison=False):
    """The kernel against the XLA path over a batch of ``lens``; with
    ``poison`` every page no row holds is NaN and every table entry past
    a row's last page (a dead row's: all of them) is out of range, so
    anything read that a row does not hold shows."""
    B = len(lens)
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(
        B, M, C, R, H, seed, L=L, bs=_BS16)
    lens = jnp.asarray(lens, jnp.int32)
    ref = mla.mla_decode_attention_xla(
        q_eff, q_pe, c_cache[layer], pe_cache[layer], tables, lens, 0.2)
    if poison:
        held = np.arange(M)[None, :] * _BS16 < np.asarray(lens)[:, None]
        pages = np.asarray(tables)[held]
        unheld = np.ones(c_cache.shape[2], bool)
        unheld[pages] = False
        c_cache = c_cache.at[:, :, unheld].set(jnp.nan)
        pe_cache = pe_cache.at[:, :, unheld].set(jnp.nan)
        tables = jnp.where(held, tables, 2**30)
    got = mla_paged_decode_attention(
        q_eff, q_pe, c_cache, pe_cache, layer, tables, lens, 0.2,
        return_stats=stats, interpret=interpret,
    )
    out = got[0] if stats else got
    live = np.asarray(lens) > 0
    assert not np.isnan(np.asarray(out)).any()
    np.testing.assert_allclose(
        np.asarray(out)[live], np.asarray(ref)[live], rtol=2e-5, atol=2e-5)
    if stats:
        _, m, l = got
        # a dead row scored nothing: an empty softmax, out 0
        np.testing.assert_array_equal(np.asarray(l)[~live], 0.0)
        np.testing.assert_array_equal(np.asarray(out)[~live], 0.0)
        assert (np.asarray(l)[live] > 0).all()


@pytest.mark.parametrize("stats", [False, True], ids=["plain", "stats"])
@pytest.mark.parametrize("layers", [1, 3], ids=["L1", "L3-layer-2"])
def test_mla_kernel_walks_each_rows_own_pages(stats, layers):
    """Rows of length 0, 1, exactly ``P * bs`` and ragged tails in one
    batch, the table two superblocks wide."""
    _check_walk(stats, _walk_lens(16), L=layers, layer=layers - 1)


@pytest.mark.parametrize("stats", [False, True], ids=["plain", "stats"])
def test_mla_kernel_reads_nothing_a_row_does_not_hold(stats):
    """Unheld pages are NaN and the table entries past a row's last page
    are out of range: neither a latent (the values) nor a rope row the
    walk did not fetch reaches the output."""
    _check_walk(stats, _walk_lens(16), poison=True)


def test_mla_kernel_reads_nothing_it_did_not_fetch():
    """The same walk under the TPU interpreter with every scratch buffer
    and every unwritten output poisoned with NaN, and its race detector
    on: the latents of a page past a row's last are blanked in the slot
    (0 x NaN of a stale slot would reach the accumulator), and no DMA
    may land in a slot that is still being read."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ipc

    bs, P, M = _BS16, _P, 16
    ipc.reset_tpu_interpret_mode_state()
    _check_walk(
        True, [0, 1, bs + 1, 0, P * bs + 1, (M - 1) * bs, 0],
        interpret=pltpu.InterpretParams(
            uninitialized_memory="nan", detect_races=True),
    )
    assert not ipc.races.races_found


@pytest.mark.parametrize("case", ["L1", "L3", "L3-stats", "L3-scan-traced"])
def test_whole_cache_operand_equals_slab_bitwise(case):
    """The layer is a value: the whole cache with index ``l`` gives
    bit for bit what layer ``l``'s slab as ``slab[None]`` with index 0
    gives, for slabs that differ, also under one traced index."""
    L = 1 if case == "L1" else 3
    stats = case != "L3"
    B, M, C, R, H = 3, 16, 128, 128, 8
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(
        B, M, C, R, H, 29, L=L, bs=_BS16)
    q_eff, q_pe = q_eff.astype(jnp.bfloat16), q_pe.astype(jnp.bfloat16)
    c_cache = c_cache.astype(jnp.bfloat16)
    pe_cache = pe_cache.astype(jnp.bfloat16)
    lens = jnp.asarray([0, 130, 256], jnp.int32)  # dead, ragged, full

    def call(c, pe, layer):
        return mla_paged_decode_attention(
            q_eff, q_pe, c, pe, layer, tables, lens, 0.2,
            return_stats=stats, interpret=True,
        )

    if case.endswith("scan-traced"):  # one traced index, one kernel
        _, whole = jax.lax.scan(
            lambda _, l: (None, call(c_cache, pe_cache, l)), None,
            jnp.arange(L),
        )
        whole = [jax.tree.map(lambda a: a[l], whole) for l in range(L)]
    else:
        whole = [call(c_cache, pe_cache, l) for l in range(L)]
    for l in range(L):
        slab = call(c_cache[l][None], pe_cache[l][None], 0)
        for w, s in zip(jax.tree.leaves(whole[l]), jax.tree.leaves(slab)):
            assert w.dtype == s.dtype and w.shape == s.shape
            np.testing.assert_array_equal(
                np.asarray(w, np.float32), np.asarray(s, np.float32)
            )
    if L > 1:  # an index that read the wrong slab would show
        assert not np.array_equal(
            np.asarray(jax.tree.leaves(whole[0])[0], np.float32)[1:],
            np.asarray(jax.tree.leaves(whole[1])[0], np.float32)[1:],
        )


@pytest.mark.parametrize("layers", [1, 3], ids=["L1", "L3-layer-1"])
def test_mla_merged_walk_matches_write_then_attend(layers):
    """The stats variant through ``mla_decode_attention_merged`` over a
    ragged batch with dead slots (history -1: the caller's ``seq_lens -
    1`` of a slot of length 0) against write-then-attend."""
    bs, P, M, C, R, H = _BS16, _P, 16, 128, 128, 8
    hist = [-1, 0, bs - 1, bs, P * bs - 1, P * bs, M * bs - 1, -1]
    B, layer = len(hist), layers // 2
    q_eff, q_pe, c_cache, pe_cache, tables = _latent_state(
        B, M, C, R, H, 5, L=layers, bs=bs)
    ks = jax.random.split(jax.random.key(17), 2)
    c_new = jax.random.normal(ks[0], (B, C), jnp.float32)
    pe_new = jax.random.normal(ks[1], (B, R), jnp.float32)
    hist = jnp.asarray(hist, jnp.int32)
    got = mla_decode_attention_merged(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, layer, tables, hist,
        0.2, interpret=True,
    )
    ref = _write_then_attend(
        q_eff, q_pe, c_new, pe_new, c_cache, pe_cache, layer, tables, hist,
        0.2)
    live = np.asarray(hist) >= 0
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(ref)[live], rtol=2e-5, atol=2e-5)
    # a slot with nothing in the cache attends its own token alone
    np.testing.assert_allclose(
        np.asarray(got)[~live],
        np.broadcast_to(np.asarray(c_new)[~live, None], (2, H, C)),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("path", ["decode", "merged", "verify", "prefill"])
def test_a_rope_row_of_64_rides_in_128_lanes(path):
    """R = 64 in a rope pool of 128 lanes (upper lanes zero, q_pe padded
    where it is scored) against the XLA path on the unpadded cache: the
    zero lanes add nothing to ``q_pe . k_pe``."""
    from dynamo_tpu.ops.mla_attention_pallas import (
        mla_paged_prefill_attention,
        mla_verify_attention,
    )

    bs, M, C, R, H = _BS16, 8, 128, 64, 8
    lens = [0, 1, bs + 3, M * bs - 1]
    B = len(lens)
    q_eff, q_pe, c_cache, pe64, tables = _latent_state(
        B, M, C, R, H, 31, L=2, bs=bs)
    pe128 = jnp.pad(pe64, [(0, 0)] * 4 + [(0, 64)])
    lens = jnp.asarray(lens, jnp.int32)
    live = np.asarray(lens) > 0
    ks = jax.random.split(jax.random.key(41), 2)
    if path == "decode":
        got = mla_paged_decode_attention(
            q_eff, q_pe, c_cache, pe128, 1, tables, lens, 0.2,
            interpret=True)
        ref = mla.mla_decode_attention_xla(
            q_eff, q_pe, c_cache[1], pe64[1], tables, lens, 0.2)
    elif path == "merged":
        c_new = jax.random.normal(ks[0], (B, C), jnp.float32)
        pe_new = jax.random.normal(ks[1], (B, R), jnp.float32)
        got = mla_decode_attention_merged(
            q_eff, q_pe, c_new, pe_new, c_cache, pe128, 1, tables, lens - 1,
            0.2, interpret=True)
        ref = _write_then_attend(
            q_eff, q_pe, c_new, pe_new, c_cache, pe64, 1, tables, lens - 1,
            0.2)
    elif path == "verify":
        T = 2
        c_win = jax.random.normal(ks[0], (B, T, C), jnp.float32)
        pe_win = jax.random.normal(ks[1], (B, T, R), jnp.float32)
        q4 = lambda q: jnp.stack([q, q[::-1]], axis=1)  # noqa: E731
        hist = jnp.maximum(lens - T, 0)
        got, ref = (
            mla_verify_attention(
                q4(q_eff), q4(q_pe), c_win, pe_win, c_cache, pe, 1, tables,
                hist, 0.2, use_pallas=use_pallas, interpret=True)
            for pe, use_pallas in ((pe128, True), (pe64, False)))
        live = np.ones(B, bool)
    else:
        T, hist = 16, 21
        qe = jax.random.normal(ks[0], (T, H, C), jnp.float32)
        qp = jax.random.normal(ks[1], (T, H, R), jnp.float32)
        got = mla_paged_prefill_attention(
            qe, qp, c_cache[1], pe128[1], tables[3], jnp.int32(hist), 0.2,
            interpret=True)
        ref = mla.mla_prefill_attention_xla(
            qe, qp, c_cache[1], pe64[1], tables[3], jnp.int32(hist),
            jnp.int32(T), 0.2)
        live = np.ones(T, bool)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(ref)[live], rtol=2e-5, atol=2e-5)


def test_rope_lanes_follow_the_shape():
    """``rope_lanes`` seats 64 in 128 (every published latent model) and
    leaves the tests' tiny widths alone; ``kv_cache_shapes`` gives the
    second pool that width and ``mla_q_and_latent`` its rows, upper
    lanes zero."""
    from dynamo_tpu.models.config import ModelConfig as MC

    assert llama.rope_lanes(MC.tiny_mla()) == 8
    cfg = MC.tiny_mla(qk_rope_head_dim=64, dtype="float32")
    assert llama.rope_lanes(cfg) == 128
    ks, vs = llama.kv_cache_shapes(cfg, 9, 16)
    assert ks == (cfg.num_layers, 1, 9, 16, cfg.kv_lora_rank)
    assert vs == (cfg.num_layers, 1, 9, 16, 128)
    params = llama.init_params(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.key(1), (5, cfg.hidden_size))
    inv_freq, msc = mla.mla_rope_freqs(cfg)
    _, q_pe, _, k_pe = mla.mla_q_and_latent(
        lp, cfg, x, jnp.arange(5), inv_freq, msc)
    assert q_pe.shape == (5, cfg.num_heads, 128) and k_pe.shape == (5, 128)
    assert not np.asarray(q_pe)[..., 64:].any()
    assert not np.asarray(k_pe)[..., 64:].any()
    assert np.asarray(k_pe)[..., :64].any()
