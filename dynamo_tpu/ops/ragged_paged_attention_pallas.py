"""Pallas TPU kernel: ragged paged attention for MIXED prefill+decode.

The engine's mixed-batch step (engine/engine.py `_mixed_step_once` →
models/llama.mixed_step) fuses M chunked-prefill segments into the same
device dispatch as a decode step for every active sequence, so decode
streams stop stalling behind prefill chunks AND queued prompts stop
stalling behind each other's prefills (the Sarathi token-budget packing
+ the full "Ragged Paged Attention" formulation — PAPERS.md). This
module is that step's attention: one call computes

  * B decode rows — one query token per sequence, each against its own
    block table and sequence length, and
  * M prefill segments — each up to a per-segment share of the step's
    token budget, every segment's rows against its own sequence's
    history plus the causal prefix of the segment itself,

with per-row query positions, causal masking, per-row sliding-window
floors, and the gpt-oss sink fold.

Design — the decode rows take the decode kernel
(paged_attention_pallas.paged_decode_attention through
ops/attention.decode_attention, sinks and page scales included), whose
cost follows the live KV; the segments take this module's ragged grid,
a generalization of paged_attention_pallas._prefill_kernel to M
sequences with the same row/group mapping (row r of a tile is token
t = r // group, head g = r % group). Until PR 28 the decode rows were
tiles of the same grid, one ``q_tile``-token tile a row: every row,
live or dead, then cost kv_heads * superblocks grid steps of about
1 us, 20-27 ms a layer-call at 32 rows x 16 heads x 32 superblocks
against 1.1 ms in the decode kernel and 2.7 for the segments alone
(chip, PR 28; the outputs are bit-identical):

  * everything is write-before-attend: the caller has already scattered
    the decode tokens' K/V and every segment's K/V into the paged
    cache, so every query row attends PURELY through block tables and
    the mask is uniform — ``kv_pos <= q_pos`` (plus the window floor).
    One mask rule covers history, chunk-causal, and the decode
    self-row, for every segment.
  * grid = (tiles, kv_heads, superblocks). The tile axis is ragged over
    SEQUENCES: the M prefill segments in ``q_tile``-token slices,
    segment-major.
  * scalar-prefetched per-tile metadata (`tile_seq`, `tile_q0`,
    `tile_last_q`) and the segments' block tables ([M, Mb]) let each
    page stream's ``index_map`` fetch exactly the physical pages the
    tile's own sequence needs; pages past a tile's causal horizon
    re-map to its last needed page (consecutive identical indices skip
    the re-fetch, the same trick as the parent kernels). ``tile_seq``
    is what makes the tile axis truly ragged: a tile does not infer
    its table row from its position.
  * all segments share ONE padded length T (the caller buckets the
    largest take), so the compiled program is keyed by (M bucket, T
    bucket) — never by the segment-length mixture. Dead segments
    (valid 0) and all-padding tiles have ``tile_last_q == -1``, skip
    every superblock, and emit zeros the caller slices off.
  * fp32 online softmax in VMEM scratch; output written once on the
    final superblock, with the sink logit folded into the normalizer
    there (per-row head via the relayout-free one-hot dot).

Interpret mode (CPU tests) runs the same kernel body under the Pallas
interpreter — the exactness tests in tests/test_mixed_batch.py pin it
against the XLA decode/chunk attention pair.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P


_NEG_INF = -1e30


def _pick_pages_per_step(M: int, cap: int = 8) -> int:
    p = 1
    while p * 2 <= cap and M % (p * 2) == 0:
        p *= 2
    return p


def _mixed_page(sq, bt, lastq, s, i, p, pages_per_step, block_size):
    """Physical page behind stream ``p`` of superblock ``i`` for tile
    ``s`` (clamped to the tile's causal horizon and the table width).
    Shared by the page index maps and the kernel's scale lookup."""
    last_pg = jnp.maximum(lastq[s], 0) // block_size
    pi = jnp.minimum(
        jnp.minimum(i * pages_per_step + p, last_pg), bt.shape[1] - 1
    )
    return bt[sq[s], pi]


def _mixed_kernel(
    # scalar prefetch (order matches the pallas_call operands)
    seq_ref,  # [S] int32: tile -> its sequence's row in tables_ref
    tables_ref,  # [MP, Mb] int32 (SMEM): the segments' block tables
    q0_ref,  # [S] int32: tile row 0's absolute query position
    lastq_ref,  # [S] int32: tile's last REAL query position (-1 = all pad)
    # [+ k_scales, v_scales [N] f32 (SMEM) when has_scales]
    # inputs: q, P k-page refs, P v-page refs [, sinks]
    *refs,
    scale: float,
    block_size: int,
    group: int,  # Gp: padded query heads per kv head
    pages_per_step: int,
    window: int = 0,  # sliding attention; 0 = full
    has_sinks: bool = False,
    has_scales: bool = False,  # quantized pages + per-page dequant scales
):
    Pp = pages_per_step
    if has_scales:
        # per-page dequant scales: the whole planes sit in SMEM (scalar
        # prefetch) and the kernel looks each page's scale up by the SAME
        # physical-page rule as the page streams — the fused dequant of
        # the quantized-KV path: page * scale right at the load, f32
        # compute after, zero extra HBM passes
        ks_ref, vs_ref, *refs = refs  # [N] f32 each
    q_ref = refs[0]  # [1, Tq*Gp, D]
    k_refs = refs[1 : 1 + Pp]  # each [1, 1, bs, D]
    v_refs = refs[1 + Pp : 1 + 2 * Pp]
    off = 1 + 2 * Pp
    n_in = off + int(has_sinks)
    sink_ref = refs[off] if has_sinks else None  # [1, Gp, 128]
    o_ref = refs[n_in]  # [1, Tq*Gp, D]
    m_scr, l_scr, acc_scr = refs[n_in + 1 :]

    s_tile = pl.program_id(0)
    i = pl.program_id(2)  # kv superblock (innermost: sequential accumulation)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q0 = q0_ref[s_tile]
    last_q = lastq_ref[s_tile]
    start = i * (Pp * block_size)
    # causal upper bound over the tile's REAL rows; all-padding tiles
    # (last_q == -1) never enter a superblock and emit zeros
    in_range = start <= last_q
    if window > 0:
        # row 0's window floor is the tile MINIMUM (later rows only see
        # more); per-row exactness is enforced in the score mask
        in_range &= start + Pp * block_size > q0 - window + 1

    @pl.when(in_range)
    def _superblock():
        q = q_ref[0].astype(jnp.float32) * scale  # [Tq*Gp, D]
        if has_scales:
            # quantized pages: cast + per-page scale multiply fused at
            # the load (the page's scalar scale broadcasts over [bs, D])
            pages = [
                _mixed_page(seq_ref, tables_ref, lastq_ref, s_tile, i, p,
                            Pp, block_size)
                for p in range(Pp)
            ]
            k = jnp.concatenate(
                [
                    r[0, 0].astype(jnp.float32) * ks_ref[pages[p]]
                    for p, r in enumerate(k_refs)
                ],
                axis=0,
            )  # [P*bs, D]
            v = jnp.concatenate(
                [
                    r[0, 0].astype(jnp.float32) * vs_ref[pages[p]]
                    for p, r in enumerate(v_refs)
                ],
                axis=0,
            )
        else:
            k = jnp.concatenate(
                [r[0, 0] for r in k_refs], axis=0
            ).astype(jnp.float32)  # [P*bs, D]
            v = jnp.concatenate([r[0, 0] for r in v_refs], axis=0).astype(
                jnp.float32
            )
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Tq*Gp, P*bs]
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = q0 + rows // group
        kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # write-before-attend: every position up to the row's own is
        # valid (history, chunk-causal prefix, and the decode self-row
        # all reduce to this one rule)
        keep = kv_pos <= q_pos
        if window > 0:
            keep &= (q_pos - kv_pos) < window
        s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)

    @pl.when(i == pl.num_programs(2) - 1)
    def _emit():
        l = l_scr[:, 0:1]
        if has_sinks:
            # sink joins the normalizer: l' = l*exp(m-m_f) + exp(s-m_f);
            # row r's sink is head g = r % Gp, selected with a one-hot
            # dot (gather/relayout-free in Mosaic)
            n_rows = l_scr.shape[0]
            g_of_row = jax.lax.broadcasted_iota(
                jnp.int32, (n_rows, group), 0
            ) % group
            col = jax.lax.broadcasted_iota(jnp.int32, (n_rows, group), 1)
            oh = (col == g_of_row).astype(jnp.float32)
            s = jax.lax.dot_general(
                oh, sink_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )[:, 0:1]
            m = m_scr[:, 0:1]
            m_f = jnp.maximum(m, s)
            l = l * jnp.exp(m - m_f) + jnp.exp(s - m_f)
            acc = acc_scr[...] * jnp.exp(m - m_f)
        else:
            acc = acc_scr[...]
        l = jnp.maximum(l, 1e-20)
        o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "q_tile", "pages_per_step", "window", "interpret"
    ),
)
def ragged_mixed_attention(
    q_dec: jnp.ndarray,  # [B, H, D] decode queries (token ALREADY written)
    q_chunks: jnp.ndarray,  # [MP, T, H, D] segment queries (ALREADY written)
    k_cache_layer: jnp.ndarray,  # [Hkv, N, bs, D]
    v_cache_layer: jnp.ndarray,
    d_tables: jnp.ndarray,  # [B, M] int32 decode block tables
    d_seq_lens: jnp.ndarray,  # [B] int32, INCLUDING the new token
    p_tables: jnp.ndarray,  # [MP, M] int32 the prefill sequences' tables
    p_hists: jnp.ndarray,  # [MP] int32: tokens cached before each segment
    p_valids: jnp.ndarray,  # [MP] int32: real tokens in each segment
    scale: float,
    q_tile: int = 0,  # 0 -> min(128, T); must divide T
    pages_per_step: int = 0,  # 0 -> auto (largest pow2 <= 8 dividing M)
    window: int = 0,  # sliding attention width; 0 = full
    sinks: jnp.ndarray | None = None,  # [H] gpt-oss sink logits
    k_scales: jnp.ndarray | None = None,  # [N] f32 per-page dequant scales
    v_scales: jnp.ndarray | None = None,  # [N] f32 (quantized caches)
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:  # (o_dec [B,H,D], o_chunks [MP,T,H,D])
    """B decode rows (the decode kernel) + M prefill segments (the
    ragged grid) against one layer of the paged cache.

    Every part must be write-before-attend (K/V for the decode tokens
    AND every segment scattered into the cache first); every row then
    attends ``kv_pos <= q_pos`` through its sequence's block table.
    Decode row b sits at q_pos = d_seq_lens[b]-1; segment m's row t at
    p_hists[m] + t. Inactive decode slots (seq_len 0), dead segments
    (p_valids[m] == 0), and padded segment rows emit zeros/garbage the
    caller slices off — their superblocks are skipped entirely. All
    segments share the padded length T, so the compiled program is
    keyed by (MP, T) buckets, never the per-segment length mixture.

    Quantized KV (ROADMAP item 3): the cache layers may be int8/fp8 —
    the kernel casts page tiles to f32 at the load, and with
    ``k_scales``/``v_scales`` (one f32 scale per physical page — the
    per-block-per-layer codec of engine/kvquant.py, this layer's
    column) multiplies each page by its scale right there, so the
    dequant is fused into the KV load instead of costing a second HBM
    pass. Scale-free quantized caches (the fp8 direct-cast device
    cache) simply pass no scales.
    """
    _, H, D = q_dec.shape
    MP, T = q_chunks.shape[0], q_chunks.shape[1]
    Hkv, N, bs, _ = k_cache_layer.shape
    M = d_tables.shape[1]
    assert p_tables.shape == (MP, M), (
        "decode and prefill tables must share the blocks-per-seq width"
    )
    G = H // Hkv
    Gp = max(8, -(-G // 8) * 8)
    Tq = q_tile or min(128, T)
    if T % Tq:
        raise ValueError(f"q_tile={Tq} must divide segment length T={T}")
    nT = T // Tq
    S = MP * nT  # ragged tile axis: the segments' q_tile-token slices
    Pp = pages_per_step or _pick_pages_per_step(M)
    if M % Pp:
        raise ValueError(
            f"pages_per_step={Pp} must divide table width M={M} "
            "(a truncated grid would silently drop tail pages)"
        )

    # ---- decode rows: the decode kernel, whose cost follows the live
    # KV (all KV heads of a page group in one grid step). As tiles of
    # this grid each of the B rows, live or dead, cost Hkv * M / Pp
    # steps of about 1 us: 16,384 of them, 20-27 ms a layer-call at
    # B = 32, Hkv = 16, M = 256, nine tenths of a mixed step's
    # attention whatever it held (PERF.md section 6, PR 28) ----
    from .attention import decode_attention

    # both kernels take the slab as ONE one-layer cache: handed the slab
    # in two ranks, the TPU compiler keeps a second copy of it for the
    # decode kernel's (0.5 ms a layer at a 5 GiB pool, PERF.md section 6,
    # PR 29)
    k_cache, v_cache = k_cache_layer[None], v_cache_layer[None]
    o_dec = decode_attention(
        q_dec, k_cache, v_cache, 0, d_tables, d_seq_lens, scale,
        use_pallas=True, window=window, sinks=sinks, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales,
    )

    # ---- pack segment queries: [Hkv, S*Tq*Gp, D], rows (t, g)
    # lexicographic ----
    qp = q_chunks.reshape(MP * T, Hkv, G, D)
    qp = jnp.pad(qp, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))
    q_all = qp.reshape(S, Tq, Hkv, Gp, D)
    q_all = q_all.transpose(2, 0, 1, 3, 4).reshape(Hkv, S * Tq * Gp, D)

    # ---- per-tile metadata (scalar prefetch) ----
    tables = p_tables.astype(jnp.int32)  # [MP, M]
    hists = p_hists.astype(jnp.int32)  # [MP]
    valids = p_valids.astype(jnp.int32)
    # segment-major sub-tiling: tile m*nT + j is segment m, slice j
    tile_seq = jnp.repeat(jnp.arange(MP, dtype=jnp.int32), nT)  # [S]
    j_idx = jnp.tile(jnp.arange(nT, dtype=jnp.int32), MP)
    tile_q0 = hists[tile_seq] + j_idx * Tq
    # last REAL row of each segment tile (tiles fully in the padding —
    # or of a dead segment — get -1, which skips every superblock)
    real = jnp.clip(valids[tile_seq] - j_idx * Tq, 0, Tq)
    tile_last = jnp.where(real > 0, tile_q0 + real - 1, -1)

    # index maps see every scalar-prefetch ref; ``*_`` absorbs the scale
    # planes of the quantized lane
    def page_index(p):
        def index(s, h, i, sq, bt, q0, lastq, *_):
            return (0, h, _mixed_page(sq, bt, lastq, s, i, p, Pp, bs), 0, 0)

        return index

    page_spec = [
        pl.BlockSpec((None, 1, 1, bs, D), page_index(p)) for p in range(Pp)
    ]
    has_scales = k_scales is not None
    # per-page scales are scalars looked up by physical page: SMEM
    # (scalar prefetch). A (1, 128) VMEM block of an [N, 128] plane is
    # below Mosaic's (8, 128) block floor.
    scale_inputs = ()
    if has_scales:
        scale_inputs = (
            k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)
        )

    def tile_index(s, h, i, *_):
        return (h, s, 0)

    sink_inputs, sink_specs = (), ()
    if sinks is not None:
        # [H] -> [Hkv, Gp, 128] lane-broadcast; padded group lanes at a
        # large FINITE negative (exp underflows to 0; -inf would 0*inf)
        sk = sinks.astype(jnp.float32).reshape(Hkv, G)
        sk = jnp.pad(sk, ((0, 0), (0, Gp - G)), constant_values=-1e30)
        sk = jnp.broadcast_to(sk[:, :, None], (Hkv, Gp, 128))
        sink_inputs = (sk,)
        sink_specs = (
            pl.BlockSpec((1, Gp, 128), lambda s, h, i, *_: (h, 0, 0)),
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + len(scale_inputs),
        grid=(S, Hkv, M // Pp),
        in_specs=[
            pl.BlockSpec((1, Tq * Gp, D), tile_index),
            *page_spec,
            *page_spec,
            *sink_specs,
        ],
        out_specs=pl.BlockSpec((1, Tq * Gp, D), tile_index),
        scratch_shapes=[
            pltpu.VMEM((Tq * Gp, 128), jnp.float32),
            pltpu.VMEM((Tq * Gp, 128), jnp.float32),
            pltpu.VMEM((Tq * Gp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _mixed_kernel, scale=scale, block_size=bs, group=Gp,
        pages_per_step=Pp, window=window, has_sinks=sinks is not None,
        has_scales=has_scales,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, S * Tq * Gp, D), q_dec.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * S * Tq * H * M * bs * D,
            bytes_accessed=2 * Hkv * M * bs * D
            * k_cache_layer.dtype.itemsize * S,
            transcendentals=S * Tq * H * M * bs,
        ),
        interpret=interpret,
    )(
        tile_seq, tables, tile_q0, tile_last, *scale_inputs, q_all,
        *([k_cache] * Pp), *([v_cache] * Pp), *sink_inputs,
    )
    o_chunks = out.reshape(Hkv, MP, nT, Tq, Gp, D)
    o_chunks = o_chunks.transpose(1, 2, 3, 0, 4, 5)  # [MP,nT,Tq,Hkv,Gp,D]
    o_chunks = o_chunks.reshape(MP, T, Hkv, Gp, D)[:, :, :, :G, :]
    return o_dec, o_chunks.reshape(MP, T, H, D)


def ragged_mixed_attention_sharded(
    q_dec: jnp.ndarray,  # [B, H, D], H sharded over tp
    q_chunks: jnp.ndarray,  # [MP, T, H, D], H sharded over tp
    k_cache_layer: jnp.ndarray,  # [Hkv, N, bs, D], Hkv sharded over tp
    v_cache_layer: jnp.ndarray,
    d_tables: jnp.ndarray,  # [B, M] replicated
    d_seq_lens: jnp.ndarray,  # [B] replicated
    p_tables: jnp.ndarray,  # [MP, M] replicated
    p_hists: jnp.ndarray,  # [MP] replicated
    p_valids: jnp.ndarray,  # [MP] replicated
    scale: float,
    mesh,
    window: int = 0,
    sinks=None,  # [H], sharded over tp with the heads
    k_scales=None,  # [N] f32 per-page dequant scales (replicated — the
    v_scales=None,  # page axis is unsharded; scales are head-free)
    interpret: bool = False,
):
    """ragged_mixed_attention under shard_map over ``tp`` — the mixed
    kernel is kv-head-parallel exactly like its decode/prefill parents
    (ops/attention._shard_tp), so each device runs it on its local head
    shard with no collectives. Scalars (tables, lengths) replicate, and
    so do the per-page dequant scales (one scale per block per layer —
    the kv-head axis is deliberately scale-free, which is also what
    keeps kv_rearrange valid on quantized payloads)."""
    has_scales = k_scales is not None

    def _local(qd, qc, kc, vc, bt, sl, pt, ph, pv, *rest):
        ks = vs = s = None
        i = 0
        if has_scales:
            ks, vs = rest[0], rest[1]
            i = 2
        if len(rest) > i:
            s = rest[i]
        return ragged_mixed_attention(
            qd, qc, kc, vc, bt, sl, pt, ph, pv, scale,
            window=window, sinks=s, k_scales=ks, v_scales=vs,
            interpret=interpret,
        )

    in_specs = [
        P(None, "tp", None),  # q_dec
        P(None, None, "tp", None),  # q_chunks
        P("tp", None, None, None),  # k cache layer
        P("tp", None, None, None),  # v cache layer
        P(), P(), P(), P(), P(),  # tables + lengths replicate
    ]
    operands = (
        q_dec, q_chunks, k_cache_layer, v_cache_layer,
        d_tables, d_seq_lens, p_tables, p_hists, p_valids,
    )
    if has_scales:
        in_specs += [P(), P()]  # scales replicate (page axis unsharded)
        operands += (k_scales, v_scales)
    if sinks is not None:
        in_specs.append(P("tp"))
        operands += (sinks,)
    return jax.shard_map(
        _local, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(P(None, "tp", None), P(None, None, "tp", None)),
        check_vma=False,
    )(*operands)
