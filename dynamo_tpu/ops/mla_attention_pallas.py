"""Pallas TPU kernels: paged LATENT attention for MLA decode and prefill.

The reference serves DeepSeek through vLLM, whose GPU MLA path pairs a
fused latent decode kernel with reshape_and_cache (README workloads;
patch:3548-3560). Here the equivalent is a Mosaic kernel over the
COMPRESSED cache (models/mla.py layout): per token the cache holds the
kv_lora_rank latent ``c_kv`` and the head-shared rotated ``k_pe`` —
attention is MQA-shaped (one shared KV stream, H query heads), scores
are the two-part absorbed dot ``q_eff . c_kv + q_pe . k_pe``, and the
VALUES are the ``c_kv`` latents themselves (the caller folds the output
latent through w_vc).

Decode (``mla_paged_decode_attention``) has the form of
``ops/paged_attention_pallas.paged_decode_attention`` (PERF.md section
6, PRs 29, 31 and 53):

  * the operands are the WHOLE caches, ``c_cache [L, 1, N, bs, C]`` and
    ``pe_cache [L, 1, N, bs, Rl]``, once each, left in HBM
    (``memory_space=pl.ANY``), and the layer as a value (scalar
    prefetch): a program's layer-calls share one Mosaic kernel, a traced
    index may be passed, and a caller that holds one layer's slab passes
    ``slab[None]`` and layer 0 (a bitcast). A ``c_cache[l]`` operand of a
    custom call is a copy of a slab a layer-call.
  * grid = ``(B,)``: the table's width is not in the grid. Inside a grid
    step the kernel walks the row's OWN superblocks, ``cdiv(seq_len,
    P * bs)`` of them, with the GQA kernel's page pipeline
    (``_page_walk``): one DMA a page and operand (``c_cache.at[layer,
    0, page]`` -> ``[bs, C]``, ``pe_cache.at[layer, 0, page]`` ->
    ``[bs, Rl]``) into a two-slot VMEM scratch, superblock ``i + 1`` in
    flight while ``i`` is scored; a page past the row's last is not
    fetched and its latents in the slot are blanked (they ARE the
    values); a row of length <= 0 runs no trip. A grid over ``M // P``
    superblocks ran 1,024 steps a call of which 120-130 scored anything
    in ``gigachat35.reason`` (``attn_table_live_share`` 11.5 %).
  * Mosaic cuts a page out of an HBM ref only along whole 128-lane
    tiles, so the rope pool holds ``k_pe`` in ``Rl`` lanes, ``R`` rounded
    up to 128 (``models/llama.py`` ``rope_lanes``; upper lanes zero, which
    add nothing to ``q_pe . k_pe``). That is also the form the chip's
    default layout keeps row-major: a pool of 64 lanes was laid pages-minor
    and every program re-laid it at entry and exit and staged it around
    each custom call.
  * the kv-head grid axis is gone (Hkv == 1 by construction) and the H
    query heads pack the row dimension: H is 16..128 for real DeepSeek
    configs, so the score matrix ``[H, P*bs]`` is MXU-shaped without the
    query-group packing the GQA kernel needs. fp32 online softmax over
    the row's superblocks in their order, output written once.

The stats-emitting variant (m, l) powers the MERGED one-write decode:
attention handles the current token out-of-cache (flash merge), so the
step batches all layers' latent writes into one in-place append
(ops/kv_cache_update_pallas) instead of 2L XLA scatters that each copy
the cache.

Prefill (``mla_paged_prefill_attention``) keeps the grid (q tiles,
superblocks of the table) and one layer's slab as ``P`` BlockSpec
streams an operand: the lone prefill's slab is every family's
(ROADMAP A5), one chunk's queries score against most of their table,
and no decode stream waits on it. It reads the same ``Rl``-lane rope
pool.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# one superblock-sizing policy and one page pipeline for every paged
# decode kernel (GQA and MLA walk the same block table the same way)
from .paged_attention_pallas import _page_walk, _pick_pages_per_step

_NEG_INF = -1e30


def rope_to_lanes(pe: jnp.ndarray, lanes: int) -> jnp.ndarray:
    """``q_pe`` / ``k_pe [..., R]`` in the rope pool's ``lanes``, zeros
    above ``R`` (they add nothing to ``q_pe . k_pe``)."""
    pad = lanes - pe.shape[-1]
    if pad == 0:
        return pe
    return jnp.pad(pe, [(0, 0)] * (pe.ndim - 1) + [(0, pad)])


def _mla_decode_kernel(
    # scalar prefetch
    block_tables_ref,  # [B, M] int32 (SMEM)
    seq_lens_ref,  # [B] int32 (SMEM)
    layer_ref,  # [1] int32 (SMEM): the layer every page fetch reads
    # inputs: q_eff, q_pe (VMEM blocks), the whole caches (HBM)
    qc_ref,  # [1, Hp, C]
    qp_ref,  # [1, Hp, Rl]
    c_hbm,  # [L, 1, N, bs, C]
    pe_hbm,  # [L, 1, N, bs, Rl]
    # outputs (o [1, Hp, C] [+ m, l [1, Hp, 128]]), then the scratch
    *refs,
    scale: float,
    block_size: int,
    pages_per_step: int,
    return_stats: bool,
):
    P = pages_per_step
    n_out = 3 if return_stats else 1
    o_ref, *stat_refs = refs[:n_out]
    # c_buf [2, P, bs, C] / pe_buf [2, P, bs, Rl]: two slots of one
    # superblock's pages; sems [2, 2] DMA semaphores (operand, slot)
    c_buf, pe_buf, sems, m_scr, l_scr, acc_scr = refs[n_out:]

    b = pl.program_id(0)
    seq_len = seq_lens_ref[b]
    layer = layer_ref[0]
    span = P * block_size
    last_page = (seq_len - 1) // block_size
    # the row's own superblocks: none for a row of length <= 0
    last = (seq_len + span - 1) // span

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    pages_of, fetch, arrive = _page_walk(
        block_tables_ref, b, last_page, P,
        [(lambda page, hbm=hbm: hbm.at[layer, 0, page], buf)
         for hbm, buf in ((c_hbm, c_buf), (pe_hbm, pe_buf))],
        sems, c_buf,  # the latents ARE the values
    )

    @pl.when(0 < last)
    def _warm_up():
        fetch(0, 0)

    def superblock(i, slot):
        # superblock i + 1 is in flight while i is computed
        @pl.when(i + 1 < last)
        def _prefetch():
            fetch(i + 1, 1 - slot)

        arrive(pages_of(i), slot)
        start = i * span
        qc = qc_ref[0].astype(jnp.float32) * scale  # [Hp, C]
        qp = qp_ref[0].astype(jnp.float32) * scale  # [Hp, Rl]
        c = jnp.concatenate(
            [c_buf[slot, p] for p in range(P)], axis=0
        ).astype(jnp.float32)  # [P*bs, C]
        pe = jnp.concatenate(
            [pe_buf[slot, p] for p in range(P)], axis=0
        ).astype(jnp.float32)  # [P*bs, Rl]
        # two-part absorbed score; separate dots keep each contracted dim
        # at its own whole-tile width (C and Rl) instead of a concat
        s = jax.lax.dot_general(
            qc, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            qp, pe, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Hp, P*bs]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < seq_len, s, _NEG_INF)

        m_prev = m_scr[:, 0:1]  # [Hp, 1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)  # [Hp, P*bs]
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # values ARE the latents
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)
        return 1 - slot

    jax.lax.fori_loop(0, last, superblock, 0)

    l = jnp.maximum(l_scr[:, 0:1], 1e-20)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    if return_stats:
        mo_ref, lo_ref = stat_refs
        mo_ref[0] = m_scr[...]
        lo_ref[0] = l_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=("scale", "pages_per_step", "return_stats", "interpret"),
)
def mla_paged_decode_attention(
    q_eff: jnp.ndarray,  # [B, H, C] absorbed queries
    q_pe: jnp.ndarray,  # [B, H, R] (R <= Rl)
    c_cache: jnp.ndarray,  # [L, 1, N, bs, C]: the whole cache
    pe_cache: jnp.ndarray,  # [L, 1, N, bs, Rl]
    layer,  # int or int32 scalar (may be traced): the layer to read
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] int32
    scale: float,
    pages_per_step: int = 0,  # 0 -> auto (largest pow2 <= 8 dividing M)
    return_stats: bool = False,
    interpret: bool = False,
):  # [B, H, C] f-out, or (out, m [B, H], l [B, H]) when return_stats
    B, H, C = q_eff.shape
    _, _, N, bs, Rl = pe_cache.shape
    M = block_tables.shape[1]
    P = pages_per_step or _pick_pages_per_step(M)
    if M % P:
        raise ValueError(
            f"pages_per_step={P} must divide table width M={M} "
            "(the last superblock would reach past the table)"
        )
    Hp = max(8, -(-H // 8) * 8)  # fp32 sublane quantum
    qc = q_eff.astype(jnp.float32)
    qp = rope_to_lanes(q_pe, Rl).astype(jnp.float32)
    if Hp != H:
        qc = jnp.pad(qc, ((0, 0), (0, Hp - H), (0, 0)))
        qp = jnp.pad(qp, ((0, 0), (0, Hp - H), (0, 0)))

    # index maps see every scalar-prefetch ref; ``*_`` absorbs them
    def row_index(b, *_):
        return (b, 0, 0)

    o_spec = pl.BlockSpec((1, Hp, C), row_index)
    stat_spec = pl.BlockSpec((1, Hp, 128), row_index)
    out_specs = [o_spec, stat_spec, stat_spec] if return_stats else o_spec
    out_shape = jax.ShapeDtypeStruct((B, Hp, C), q_eff.dtype)
    if return_stats:
        stat_shape = jax.ShapeDtypeStruct((B, Hp, 128), jnp.float32)
        out_shape = [out_shape, stat_shape, stat_shape]
    # the caches stay in HBM, whole: the kernel fetches the pages a row
    # holds itself, so no grid dimension has the table's width
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hp, C), row_index),
            pl.BlockSpec((1, Hp, Rl), row_index),
            in_hbm,
            in_hbm,
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, P, bs, C), c_cache.dtype),
            pltpu.VMEM((2, P, bs, Rl), pe_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hp, 128), jnp.float32),
            pltpu.VMEM((Hp, 128), jnp.float32),
            pltpu.VMEM((Hp, C), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_decode_kernel, scale=scale, block_size=bs, pages_per_step=P,
        return_stats=return_stats,
    )
    caches = (c_cache, pe_cache)
    if not interpret:
        # pinned: left to choose (``pl.ANY``), the compiler stages a pool
        # that fits its fast memory around the call, whole, for a kernel
        # that reads a few pages of it (the interpreter knows no spaces)
        caches = [pltpu.with_memory_space_constraint(c, pltpu.HBM)
                  for c in caches]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        # an upper bound, what a batch of full rows costs: the walk
        # follows seq_lens, which no shape tells the scheduler
        cost_estimate=pl.CostEstimate(
            flops=2 * B * H * M * bs * (C + Rl + C),
            bytes_accessed=M * bs * (C + Rl) * c_cache.dtype.itemsize * B,
            transcendentals=B * H * M * bs,
        ),
        interpret=interpret,
    )(
        block_tables, seq_lens, jnp.asarray(layer, jnp.int32).reshape(1),
        qc, qp, *caches,
    )
    if return_stats:
        o, m, l = out
        return o[:, :H, :], m[:, :H, 0], l[:, :H, 0]
    return out[:, :H, :]


def mla_decode_attention_merged(
    q_eff: jnp.ndarray,  # [B, H, C]
    q_pe: jnp.ndarray,  # [B, H, R]
    c_new: jnp.ndarray,  # [B, C] current token's latent (NOT in cache)
    pe_new: jnp.ndarray,  # [B, R] current token's rotated k_pe
    c_cache: jnp.ndarray,  # [L, 1, N, bs, C] history only: the whole cache
    pe_cache: jnp.ndarray,  # [L, 1, N, bs, Rl]
    layer,  # int or int32 scalar (may be traced): the layer to read
    block_tables: jnp.ndarray,  # [B, M]
    hist_lens: jnp.ndarray,  # [B] tokens in cache (EXCLUDES current)
    scale: float,
    interpret: bool = False,
) -> jnp.ndarray:  # [B, H, C] latent output
    """MLA decode attention with the current token handled OUT of the
    cache: history via the stats-emitting latent kernel, the current
    token's score ``q_eff.c_new + q_pe.pe_new`` (value: ``c_new``,
    shared across heads) folded in with the flash-decoding merge — the
    same one-write trick as ops/attention.decode_attention_merged, so
    all layers' latent writes batch into one in-place append.
    hist_lens == 0 rows degenerate cleanly to out = c_new."""
    o_h, m_h, l_h = mla_paged_decode_attention(
        q_eff, q_pe, c_cache, pe_cache, layer, block_tables, hist_lens,
        scale, return_stats=True, interpret=interpret,
    )
    o_h = o_h.astype(jnp.float32)
    s_new = (
        jnp.einsum(
            "bhc,bc->bh", q_eff.astype(jnp.float32), c_new.astype(jnp.float32)
        )
        + jnp.einsum(
            "bhr,br->bh", q_pe.astype(jnp.float32), pe_new.astype(jnp.float32)
        )
    ) * scale  # [B, H]
    m_f = jnp.maximum(m_h, s_new)
    alpha = jnp.exp(m_h - m_f)
    p_new = jnp.exp(s_new - m_f)
    num = (l_h * alpha)[..., None] * o_h + p_new[..., None] * c_new[
        :, None, :
    ].astype(jnp.float32)
    den = l_h * alpha + p_new  # >= p_new > 0: the current token is live
    return num / den[..., None]


def _mla_prefill_kernel(
    # scalar prefetch
    block_table_ref,  # [M] int32 (SMEM)
    hist_ref,  # [1] int32 (SMEM): tokens already cached before this chunk
    # inputs: q_eff, q_pe, then P c-page refs then P pe-page refs
    *refs,
    scale: float,
    block_size: int,
    q_tile: int,  # Tq: chunk rows per grid step
    group: int,  # Hp: padded query heads per token
    pages_per_step: int,
):
    P = pages_per_step
    qc_ref = refs[0]  # [1, Tq*Hp, C]
    qp_ref = refs[1]  # [1, Tq*Hp, R]
    c_refs = refs[2 : 2 + P]  # each [1, 1, bs, C]
    pe_refs = refs[2 + P : 2 + 2 * P]
    o_ref = refs[2 + 2 * P]  # [1, Tq*Hp, C]
    m_scr, l_scr, acc_scr = refs[3 + 2 * P :]

    j = pl.program_id(0)  # q tile
    i = pl.program_id(1)  # kv superblock (innermost: sequential accum)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    hist = hist_ref[0]
    start = i * (P * block_size)
    # last query position in this tile — superblocks past it are fully
    # masked (full attention only: MLA models have no sliding window)
    in_range = start <= hist + (j + 1) * q_tile - 1

    @pl.when(in_range)
    def _superblock():
        qc = qc_ref[0].astype(jnp.float32) * scale  # [Tq*Hp, C]
        qp = qp_ref[0].astype(jnp.float32) * scale
        c = jnp.concatenate(
            [r[0, 0] for r in c_refs], axis=0
        ).astype(jnp.float32)  # [P*bs, C]
        pe = jnp.concatenate([r[0, 0] for r in pe_refs], axis=0).astype(
            jnp.float32
        )
        s = jax.lax.dot_general(
            qc, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) + jax.lax.dot_general(
            qp, pe, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [Tq*Hp, P*bs]
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = hist + j * q_tile + rows // group
        kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kv_pos <= q_pos, s, _NEG_INF)

        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(i == pl.num_programs(1) - 1)
    def _emit():
        l = jnp.maximum(l_scr[:, 0:1], 1e-20)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "pages_per_step", "interpret")
)
def mla_paged_prefill_attention(
    q_eff: jnp.ndarray,  # [T, H, C] chunk's absorbed queries
    q_pe: jnp.ndarray,  # [T, H, R]
    c_cache_layer: jnp.ndarray,  # [1, N, bs, C] — chunk ALREADY written
    pe_cache_layer: jnp.ndarray,  # [1, N, bs, Rl]
    block_table: jnp.ndarray,  # [M] int32, covers history + padded chunk
    history_len: jnp.ndarray,  # scalar int32
    scale: float,
    pages_per_step: int = 0,  # 0 -> auto
    interpret: bool = False,
) -> jnp.ndarray:  # [T, H, C] latent outputs
    """Flash-style chunked-prefill latent attention over the paged MLA
    cache — the MLA twin of ops/paged_attention_pallas
    .paged_prefill_attention (write-before-attend: the caller scattered
    this chunk's latents first, so the kernel reads history AND chunk
    through the block table; causal masking at absolute positions does
    all the ragged bookkeeping; padded tail rows produce garbage only in
    rows every caller discards). Two-stream page DMA and values-are-
    latents exactly as the decode kernel."""
    T, H, C = q_eff.shape
    _, N, bs, R = pe_cache_layer.shape
    M = block_table.shape[0]
    Hp = max(8, -(-H // 8) * 8)
    # cap the packed row dim near 1024 so fp32 VMEM scratch stays a few
    # MB at C=512 (acc [Tq*Hp, C] is the big one)
    Tq = max(1, min(T, 1024 // Hp))
    nT = -(-T // Tq)
    Tpad = nT * Tq
    P = pages_per_step or _pick_pages_per_step(M)
    if M % P:
        raise ValueError(
            f"pages_per_step={P} must divide table width M={M} "
            "(a truncated grid would silently drop tail pages)"
        )
    # [T, H, C] -> [1, Tpad*Hp, C]: rows (t, h) lexicographic, so
    # in-kernel row r of tile j maps to t = j*Tq + r // Hp
    def pack(q, D):
        q = jnp.pad(
            q.astype(jnp.float32),
            ((0, Tpad - T), (0, Hp - H), (0, 0)),
        )
        return q.reshape(1, Tpad * Hp, D)

    qc = pack(q_eff, C)
    qp = pack(rope_to_lanes(q_pe, R), R)

    def page_index(p):
        def index(j, i, bt, hist):
            tile_last = (hist[0] + (j + 1) * Tq - 1) // bs
            written_last = (hist[0] + Tpad - 1) // bs
            pi = jnp.minimum(
                jnp.minimum(i * P + p, tile_last),
                jnp.minimum(written_last, M - 1),
            )
            return (0, bt[pi], 0, 0)

        return index

    c_specs = [pl.BlockSpec((1, 1, bs, C), page_index(p)) for p in range(P)]
    pe_specs = [pl.BlockSpec((1, 1, bs, R), page_index(p)) for p in range(P)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nT, M // P),
        in_specs=[
            pl.BlockSpec((1, Tq * Hp, C), lambda j, i, bt, hist: (0, j, 0)),
            pl.BlockSpec((1, Tq * Hp, R), lambda j, i, bt, hist: (0, j, 0)),
            *c_specs,
            *pe_specs,
        ],
        out_specs=pl.BlockSpec(
            (1, Tq * Hp, C), lambda j, i, bt, hist: (0, j, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((Tq * Hp, 128), jnp.float32),
            pltpu.VMEM((Tq * Hp, 128), jnp.float32),
            pltpu.VMEM((Tq * Hp, C), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mla_prefill_kernel, scale=scale, block_size=bs, q_tile=Tq,
        group=Hp, pages_per_step=P,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, Tpad * Hp, C), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * Tpad * H * M * bs * (C + R + C),
            bytes_accessed=M * bs * (C + R) * c_cache_layer.dtype.itemsize,
            transcendentals=Tpad * H * M * bs,
        ),
        interpret=interpret,
    )(jnp.asarray(block_table), jnp.asarray(history_len, jnp.int32).reshape(1),
      qc, qp, *([c_cache_layer] * P), *([pe_cache_layer] * P))
    out = out.reshape(Tpad, Hp, C)[:T, :H, :]
    return out


def mla_paged_prefill_attention_sharded(
    q_eff: jnp.ndarray,  # [T, H, C], H sharded over tp
    q_pe: jnp.ndarray,  # [T, H, R], H sharded over tp
    c_cache_layer: jnp.ndarray,  # replicated
    pe_cache_layer: jnp.ndarray,  # replicated
    block_table: jnp.ndarray,  # [M] replicated
    history_len: jnp.ndarray,  # scalar replicated
    scale: float,
    mesh,
    interpret: bool = False,
) -> jnp.ndarray:
    """The prefill latent kernel under shard_map over ``tp`` (query
    heads parallel, replicated latent cache — same argument as the
    decode wrappers)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        partial(mla_paged_prefill_attention, scale=scale,
                interpret=interpret),
        mesh=mesh,
        in_specs=(
            P(None, "tp", None),  # q_eff
            P(None, "tp", None),  # q_pe
            P(),  # c cache
            P(),  # pe cache
            P(),  # table
            P(),  # history_len
        ),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )(q_eff, q_pe, c_cache_layer, pe_cache_layer, block_table, history_len)


def mla_verify_attention(
    q_eff: jnp.ndarray,  # [B, T, H, C] T in-flight tokens' absorbed queries
    q_pe: jnp.ndarray,  # [B, T, H, R]
    c_win: jnp.ndarray,  # [B, T, C] their latents (NOT in cache)
    pe_win: jnp.ndarray,  # [B, T, R]
    c_cache: jnp.ndarray,  # [L, 1, N, bs, C] history only: the whole cache
    pe_cache: jnp.ndarray,  # [L, 1, N, bs, Rl]
    layer,  # int or int32 scalar: the layer to read
    block_tables: jnp.ndarray,  # [B, M]
    hist_lens: jnp.ndarray,  # [B] tokens in cache (before the window)
    scale: float,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jnp.ndarray:  # [B, T, H, C] f32 latent outputs
    """Multi-token latent attention for the speculative verify, with the
    whole in-flight window OUT of the cache: history comes from the
    stats-emitting latent kernel (every history row precedes every
    window position, so the T*H query rows simply pack the kernel's row
    dimension) or its XLA twin; the tiny [T, T'] intra-window causal
    part is dense and folds in with the flash merge. Keeping the window
    out of the cache lets the caller batch all layers' latent writes
    into ONE append (kv_cache_append_tokens) instead of 2L scatters that
    each copy the cache."""
    B, T, H, C = q_eff.shape
    R = q_pe.shape[-1]
    if use_pallas:
        o_h, m_h, l_h = mla_paged_decode_attention(
            q_eff.reshape(B, T * H, C), q_pe.reshape(B, T * H, R),
            c_cache, pe_cache, layer, block_tables, hist_lens, scale,
            return_stats=True, interpret=interpret,
        )
        o_h = o_h.reshape(B, T, H, C).astype(jnp.float32)
        m_h = m_h.reshape(B, T, H)
        l_h = l_h.reshape(B, T, H)
    else:
        M = block_tables.shape[1]
        bs = c_cache.shape[3]
        # the XLA twin cuts its own layer (a gather reads it in place)
        ck = jnp.take(c_cache[layer, 0], block_tables, axis=0).reshape(
            B, M * bs, C
        )
        kp = jnp.take(pe_cache[layer, 0], block_tables, axis=0).reshape(
            B, M * bs, -1
        )
        q_pe_l = rope_to_lanes(q_pe, kp.shape[-1])
        s = (
            jnp.einsum("bthc,bsc->bths", q_eff.astype(jnp.float32) * scale,
                       ck.astype(jnp.float32))
            + jnp.einsum("bthr,bsr->bths", q_pe_l.astype(jnp.float32) * scale,
                         kp.astype(jnp.float32))
        )
        valid = jnp.arange(M * bs)[None, :] < hist_lens[:, None]  # [B, S]
        s = jnp.where(valid[:, None, None, :], s, _NEG_INF)
        m_h = jnp.max(s, axis=-1)  # [B, T, H]
        p = jnp.exp(s - m_h[..., None])
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l_h = jnp.sum(p, axis=-1)
        o_h = jnp.einsum("bths,bsc->bthc", p, ck.astype(jnp.float32))
        o_h = o_h / jnp.maximum(l_h, 1e-20)[..., None]
    # intra-window causal scores [B, T, H, T']
    s_w = (
        jnp.einsum("bthc,buc->bthu", q_eff.astype(jnp.float32),
                   c_win.astype(jnp.float32))
        + jnp.einsum("bthr,bur->bthu", q_pe.astype(jnp.float32),
                     pe_win.astype(jnp.float32))
    ) * scale
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]  # [T, T']
    s_w = jnp.where(causal[:, None, :], s_w, _NEG_INF)
    m_w = jnp.max(s_w, axis=-1)  # [B, T, H]
    m_f = jnp.maximum(m_h, m_w)
    alpha = jnp.exp(m_h - m_f)
    p_w = jnp.exp(s_w - m_f[..., None])
    o_w = jnp.einsum("bthu,buc->bthc", p_w, c_win.astype(jnp.float32))
    l_w = jnp.sum(p_w, axis=-1)
    num = (l_h * alpha)[..., None] * o_h + o_w
    den = l_h * alpha + l_w  # >= the diagonal term (u == t) > 0
    return num / den[..., None]


def mla_decode_attention_merged_sharded(
    q_eff: jnp.ndarray,  # [B, H, C], H sharded over tp
    q_pe: jnp.ndarray,  # [B, H, R], H sharded over tp
    c_new: jnp.ndarray,  # [B, C] replicated
    pe_new: jnp.ndarray,  # [B, R] replicated
    c_cache: jnp.ndarray,  # [L, 1, N, bs, C] replicated: the whole cache
    pe_cache: jnp.ndarray,  # [L, 1, N, bs, Rl] replicated
    layer,  # int or int32 scalar: the layer to read
    block_tables: jnp.ndarray,  # [B, M] replicated
    hist_lens: jnp.ndarray,  # [B] replicated
    scale: float,
    mesh,
    interpret: bool = False,
) -> jnp.ndarray:
    """Merged latent attention under shard_map over ``tp``: MLA is
    MQA-shaped, so the QUERY-head axis is the parallel one — each device
    runs the kernel for its H/tp heads against the full (replicated)
    latent cache, no collectives. (The cache cannot shard over kv heads
    the way GQA does — there is only one latent stream — and at
    kv_lora_rank+rope bytes/token it is ~4x smaller than a GQA cache,
    which is the MLA trade: replicate small cache, shard heads.)"""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        partial(mla_decode_attention_merged, scale=scale,
                interpret=interpret),
        mesh=mesh,
        in_specs=(
            P(None, "tp", None),  # q_eff
            P(None, "tp", None),  # q_pe
            P(),  # c_new
            P(),  # pe_new
            P(),  # c cache
            P(),  # pe cache
            P(),  # layer
            P(),  # tables
            P(),  # hist_lens
        ),
        out_specs=P(None, "tp", None),
        check_vma=False,
    )(q_eff, q_pe, c_new, pe_new, c_cache, pe_cache,
      jnp.asarray(layer, jnp.int32), block_tables, hist_lens)
