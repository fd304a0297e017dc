"""Pallas TPU kernels: ragged paged-attention for decode and prefill.

The reference's equivalent is vLLM's paged_attention CUDA kernel plus its
flash-attention prefill (invoked inside the engines Dynamo wraps); here
they are native Mosaic/TPU kernels.

A layer of the cache is [Hkv, N, bs, D] (head-major): a (head, page)
tile is one contiguous ``[bs, D]`` block, and one page of ``Hh`` heads is
``Hh`` such tiles a fixed stride apart — one strided DMA (see
dynamo_tpu.ops.attention module docs).

Decode (``paged_decode_attention``):

  * the operand is the WHOLE cache ``[L, Hkv, N, bs, D]`` plus a layer
    index, the form ``kv_cache_append`` writes it in, once each for K and
    V, left in HBM (``memory_space=pl.ANY``): the kernel reads its pages
    where they lie in the pool. A ``k_cache[l]`` operand is a slice that
    feeds a custom call, which the TPU compiler materialises: one copy
    of the whole K and V pool a decode step, 39 % and 33 % of a step of
    the two benchmark cells (PERF.md section 6, PR 29). The index is a
    value (scalar prefetch), not part of the kernel: a program's
    layer-calls share one Mosaic kernel and a ``lax.scan`` may pass a
    traced one. A caller that holds one layer's slab passes ``slab[None]``
    and layer 0 (a bitcast).
  * grid = (batch, head tiles) = ``(B, Hkv // Hh)``: the table's width
    is NOT in the grid. Inside a grid step the kernel walks the row's
    OWN superblocks, ``cdiv(seq_len, P * bs)`` of them (from the sliding
    window's floor on), so a row of length <= 0 runs zero trips and a
    dead slot costs its q block, the scratch's reset and its output
    blocks, nothing else. A grid over ``M // P`` superblocks paid about a
    microsecond a step for table pages no sequence held: 96-99 % of
    1,024 steps a layer-call in the benchmark's cells, 68 % and 42 % of
    a decode step (PERF.md section 6, PR 31).
  * a superblock is ``P`` consecutive logical pages of ``Hh`` KV heads of
    one row. The kernel fetches them itself: one strided DMA a page and
    operand (``cache.at[layer, heads, page]``, ``Hh`` ``[bs, D]`` tiles a
    fixed stride apart) into a two-slot VMEM scratch ``[2, P, Hh, bs,
    D]``; superblock ``i + 1`` is in flight while ``i`` is scored by one
    ``[Hh, Gp, D] x [Hh, P*bs, D]`` batched dot. The block table and the
    sequence lengths ride in SMEM (``PrefetchScalarGridSpec``) and turn
    a *logical* page number into the *physical* page index. No gather of
    the whole table, no materialized [B, M*bs, H, D] intermediate (what
    the XLA fallback does).
  * a page past a row's last is not fetched at all: its scores are
    masked like a page's tail, and its V rows in the slot are blanked
    first (whatever the slot held would reach the accumulator as 0 x
    NaN). Bandwidth and trips follow the row's true length.
  * Mosaic cuts a page out of an HBM ref only along whole 128-lane
    tiles, so ``D`` here is a multiple of 128: a head of 64 (gpt-oss)
    comes in rows of 128 lanes, q and cache alike, upper half zero
    (``models/llama.py`` ``kv_lanes``; HBM pads a ``[bs, 64]`` tile to
    128 lanes anyway).
  * ``Hh`` is derived from the shapes (``_pick_heads_per_step``): the
    largest divisor of the local ``Hkv`` whose step fits
    ``_STEP_VMEM_BUDGET``, 8 MiB, half of the 16 MiB of scoped VMEM a
    v5e kernel gets by default (the other half is left to Mosaic's own
    temporaries: the concatenated pages, scores and probabilities).
    Counted per head: the two slots of ``P`` K and ``P`` V pages
    (``2 * 2P * bs * D * itemsize``), the f32 copies of K and V both dots
    read (``2 * P * bs * D * 4``), the q, output and stat blocks
    double-buffered at f32 and the m / l / acc scratch. For bf16 pages
    of 16 x 128 at ``P`` 8 and MHA's ``Gp`` 8: 128 KiB of page slots +
    128 KiB of temporaries + 44 KiB of the rest a head, so 16 heads ride
    in one step (2 MiB + 2 MiB + 0.7 MiB), a tp shard's 2 heads in one,
    and 32 heads in two steps of 16 (int8 or fp8 pages: one of 32).
  * flash-attention-style online softmax in fp32 VMEM scratch
    (running max / normalizer / accumulator, one plane a head) across
    the row's superblocks, in their order; the output tile is written
    once at the end of the grid step. q is scaled in f32 and K / V are
    widened to f32 before both dots.

Prefill (``paged_prefill_attention``) keeps the grid (q tiles, kv heads,
superblocks): one KV head and ``P`` ``[bs, D]`` pages a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30

# what one decode grid step may hold in VMEM by the count of
# ``_pick_heads_per_step``: half of a v5e kernel's default 16 MiB of
# scoped VMEM
_STEP_VMEM_BUDGET = 8 * 2**20


def _pick_pages_per_step(M: int, cap: int = 8) -> int:
    """Largest power of two <= cap dividing the table width."""
    p = 1
    while p * 2 <= cap and M % (p * 2) == 0:
        p *= 2
    return p


def _decode_step_vmem_bytes(Hh, Gp, D, bs, P, itemsize):
    """VMEM one decode grid step of ``Hh`` KV heads holds, as the module
    docstring counts it (``itemsize``: the cache's)."""
    streams = 2 * (2 * P) * bs * D * itemsize  # two slots of K and V pages
    widened = 2 * P * bs * D * 4  # f32 K and V
    # double-buffered q, out and the two stat planes, at f32
    q_and_out = 2 * (2 * Gp * D + 2 * Gp * 128) * 4
    scratch = (2 * 128 + D) * Gp * 4  # m, l, acc
    return Hh * (streams + widened + q_and_out + scratch)


def _pick_heads_per_step(Hkv, Gp, D, bs, P, itemsize) -> int:
    """KV heads a decode grid step covers: the largest divisor of the
    (local) ``Hkv`` whose step fits ``_STEP_VMEM_BUDGET``; one head
    always may."""
    for Hh in range(Hkv, 1, -1):
        if Hkv % Hh == 0 and _decode_step_vmem_bytes(
            Hh, Gp, D, bs, P, itemsize
        ) <= _STEP_VMEM_BUDGET:
            return Hh
    return 1


def _page_walk(block_tables_ref, b, last_page, P, streams, sems, values):
    """A decode grid step's page pipeline over row ``b`` of the table:
    ``(pages_of, fetch, arrive)``, shared by the GQA kernel here and the
    latent kernel (``ops/mla_attention_pallas.py``). ``streams`` is one
    ``(source, buf)`` an operand: ``source(page)`` the HBM view of one
    physical page, ``buf`` ``[2, P, ...]`` its two slots of a
    superblock's pages; ``sems`` ``[operands, 2]`` DMA semaphores;
    ``values`` the buffer whose rows reach the accumulator."""

    def pages_of(i):
        """(held, physical page) of superblock ``i``'s ``P`` pages, the
        one rule of the fetch, its wait and the int8 lane's scale lookup:
        a page past the row's last is not ``held`` and never fetched, and
        names the last one (no table entry that no sequence holds is
        read)."""
        return [
            (i * P + p <= last_page,
             block_tables_ref[b, jnp.minimum(i * P + p, last_page)])
            for p in range(P)
        ]

    def page_copies(page, slot, p):
        """The copies of one page into ``slot``, one DMA an operand (a
        GQA page's ``Hh`` heads are one strided DMA)."""
        return [
            pltpu.make_async_copy(
                source(page), buf.at[slot, p], sems.at[o, slot])
            for o, (source, buf) in enumerate(streams)
        ]

    def fetch(i, slot):
        for p, (held, page) in enumerate(pages_of(i)):
            @pl.when(held)
            def _start():
                for copy in page_copies(page, slot, p):
                    copy.start()

    def arrive(pages, slot):
        for p, (held, page) in enumerate(pages):
            @pl.when(held)
            def _wait():
                for copy in page_copies(page, slot, p):
                    copy.wait()

            # what the slot holds of an unfetched page is whatever was
            # there: its scores are masked below, but 0 x NaN of a stale
            # value row would still reach the accumulator
            @pl.when(jnp.logical_not(held))
            def _blank():
                values[slot, p] = jnp.zeros(values.shape[2:], values.dtype)

    return pages_of, fetch, arrive


def _decode_kernel(
    # scalar prefetch [+ k_scales, v_scales [N] f32 when has_scales]
    block_tables_ref,  # [B, M] int32 (SMEM)
    seq_lens_ref,  # [B] int32 (SMEM)
    layer_ref,  # [1] int32 (SMEM): the layer every page fetch reads
    # inputs: q (VMEM block), the whole k and v caches (HBM), outputs,
    # then the scratch
    *refs,
    scale: float,
    block_size: int,
    pages_per_step: int,
    return_stats: bool,
    window: int = 0,  # sliding attention; 0 = full
    q_pos_offset: int = 0,  # query position = seq_len - 1 + offset
    group: int = 0,  # >0: row r is in-flight token t = r // group, so its
    # query position is seq_len - 1 + q_pos_offset + r // group (the
    # verify path packs T tokens x G heads into the row dim); 0 = all
    # rows share one position (plain decode)
    has_scales: bool = False,  # int8-with-scales device cache: the
    # per-page scale planes ride in SMEM as two more scalar-prefetch refs
    # and the per-page dequant fuses into the page loads (same scheme as
    # ragged_paged_attention_pallas); one scalar a page, shared by the
    # step's heads
):
    P = pages_per_step
    if has_scales:
        ks_ref, vs_ref, *refs = refs  # [N] f32 each (SMEM)
    q_ref, k_hbm, v_hbm = refs[:3]  # [1, Hh, Gp, D]; [L, Hkv, N, bs, D] x 2
    n_out = 3 if return_stats else 1
    o_ref, *stat_refs = refs[3 : 3 + n_out]  # [1, Hh, Gp, D] [+ m, l]
    # k_buf / v_buf [2, P, Hh, bs, D]: two slots of one superblock's
    # pages; sems [2, 2] DMA semaphores (operand, slot)
    k_buf, v_buf, sems, m_scr, l_scr, acc_scr = refs[3 + n_out :]
    Hh = k_buf.shape[2]

    b = pl.program_id(0)
    h = pl.program_id(1)
    seq_len = seq_lens_ref[b]
    layer = layer_ref[0]
    span = P * block_size
    last_page = (seq_len - 1) // block_size
    # sliding window: row r's query sits at seq_len-1+q_pos_offset+t(r)
    # (the merged/out-of-cache path scores against history of length
    # seq_len with queries past it); only positions in (q_pos-window,
    # q_pos] contribute. ``lo`` is row 0's floor — the MINIMUM over rows
    # (later in-flight tokens only see more) — so it bounds the walk from
    # below by whole superblocks; per-row exactness is enforced in the
    # score mask.
    lo = seq_len + q_pos_offset - window if window > 0 else 0
    # the row's own superblocks: none for a row of length <= 0
    first = jnp.maximum(lo, 0) // span if window > 0 else 0
    last = (seq_len + span - 1) // span

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    pages_of, fetch, arrive = _page_walk(
        block_tables_ref, b, last_page, P,
        [(lambda page, hbm=hbm: hbm.at[layer, pl.ds(h * Hh, Hh), page], buf)
         for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf))],
        sems, v_buf,
    )

    @pl.when(first < last)
    def _warm_up():
        fetch(first, 0)

    def superblock(i, slot):
        # superblock i + 1 is in flight while i is computed
        @pl.when(i + 1 < last)
        def _prefetch():
            fetch(i + 1, 1 - slot)

        pages = pages_of(i)
        arrive(pages, slot)
        start = i * span
        q = q_ref[0].astype(jnp.float32) * scale  # [Hh, Gp, D]
        if has_scales:
            # fused per-page dequant: quantized tile * its page scale
            k = jnp.concatenate(
                [
                    k_buf[slot, p].astype(jnp.float32) * ks_ref[page]
                    for p, (_, page) in enumerate(pages)
                ],
                axis=1,
            )  # [Hh, P*bs, D]
            v = jnp.concatenate(
                [
                    v_buf[slot, p].astype(jnp.float32) * vs_ref[page]
                    for p, (_, page) in enumerate(pages)
                ],
                axis=1,
            )
        else:
            k = jnp.concatenate(
                [k_buf[slot, p] for p in range(P)], axis=1
            ).astype(jnp.float32)  # [Hh, P*bs, D]
            v = jnp.concatenate(
                [v_buf[slot, p] for p in range(P)], axis=1
            ).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hh, Gp, P*bs]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        keep = pos < seq_len
        if window > 0:
            row_lo = lo
            if group > 0:  # per-row floor: row r is token t = r // group
                row_lo = lo + (
                    jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // group
                )
            keep &= pos >= row_lo
        s = jnp.where(keep, s, _NEG_INF)

        m_prev = m_scr[:, :, 0:1]  # [Hh, Gp, 1]
        l_prev = l_scr[:, :, 0:1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)  # [Hh, Gp, P*bs]
        l_cur = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [Hh, Gp, D]
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)
        return 1 - slot

    jax.lax.fori_loop(first, last, superblock, 0)

    l = jnp.maximum(l_scr[:, :, 0:1], 1e-20)
    o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
    if return_stats:
        mo_ref, lo_ref = stat_refs  # [1, Hh, Gp, 128] each
        mo_ref[0] = m_scr[...]
        lo_ref[0] = l_scr[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "pages_per_step", "return_stats", "window",
        "q_pos_offset", "group", "interpret"
    ),
)
def paged_decode_attention(
    q: jnp.ndarray,  # [B, H, D]
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D]: the whole cache
    v_cache: jnp.ndarray,  # [L, Hkv, N, bs, D]
    layer,  # int or int32 scalar (may be traced): the layer to read
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] int32
    scale: float,
    pages_per_step: int = 0,  # 0 -> auto (largest pow2 <= 8 dividing M)
    return_stats: bool = False,
    window: int = 0,  # sliding attention width; 0 = full
    q_pos_offset: int = 0,  # see _decode_kernel
    group: int = 0,  # see _decode_kernel (verify path: heads per token)
    interpret: bool = False,
    k_scales: jnp.ndarray | None = None,  # [N] f32 per-page (int8 cache):
    v_scales: jnp.ndarray | None = None,  # this layer's planes
):  # [B, H, D] or (out, m [B, Hkv, G], l [B, Hkv, G]) when return_stats
    B, H, D = q.shape
    _, Hkv, N, bs, _ = k_cache.shape
    M = block_tables.shape[1]
    G = H // Hkv
    P = pages_per_step or _pick_pages_per_step(M)
    if M % P:
        raise ValueError(
            f"pages_per_step={P} must divide table width M={M} "
            "(the last superblock would reach past the table)"
        )
    # pad the query-group dim to the fp32 sublane quantum
    Gp = max(8, -(-G // 8) * 8)
    Hh = _pick_heads_per_step(Hkv, Gp, D, bs, P, k_cache.dtype.itemsize)
    qg = q.reshape(B, Hkv, G, D).astype(jnp.float32)
    if Gp != G:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    # per-page scales are scalars the kernel looks up by physical page:
    # SMEM (scalar prefetch), not a VMEM stream — a (1, 128) block of an
    # [N, 128] plane is below Mosaic's (8, 128) block floor
    scale_inputs = ()
    if k_scales is not None:
        scale_inputs = (
            k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)
        )

    # index maps see every scalar-prefetch ref; ``*_`` absorbs them
    def row_index(b, h, *_):
        return (b, h, 0, 0)

    o_spec = pl.BlockSpec((1, Hh, Gp, D), row_index)
    stat_spec = pl.BlockSpec((1, Hh, Gp, 128), row_index)
    out_specs = [o_spec, stat_spec, stat_spec] if return_stats else o_spec
    out_shape = jax.ShapeDtypeStruct((B, Hkv, Gp, D), q.dtype)
    if return_stats:
        stat_shape = jax.ShapeDtypeStruct((B, Hkv, Gp, 128), jnp.float32)
        out_shape = [out_shape, stat_shape, stat_shape]
    # the caches stay in HBM, whole: the kernel fetches the pages a row
    # holds itself, so no grid dimension has the table's width
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + len(scale_inputs),
        grid=(B, Hkv // Hh),
        in_specs=[pl.BlockSpec((1, Hh, Gp, D), row_index), in_hbm, in_hbm],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((2, P, Hh, bs, D), k_cache.dtype),
            pltpu.VMEM((2, P, Hh, bs, D), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((Hh, Gp, 128), jnp.float32),
            pltpu.VMEM((Hh, Gp, 128), jnp.float32),
            pltpu.VMEM((Hh, Gp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_size=bs, pages_per_step=P,
        return_stats=return_stats, window=window, q_pos_offset=q_pos_offset,
        group=group, has_scales=k_scales is not None,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        # an upper bound, what a batch of full rows costs: the walk
        # follows seq_lens, which no shape tells the scheduler
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * B * H * M * bs * D,
            bytes_accessed=2 * Hkv * M * bs * D * k_cache.dtype.itemsize * B,
            transcendentals=B * H * M * bs,
        ),
        interpret=interpret,
    )(
        block_tables, seq_lens, jnp.asarray(layer, jnp.int32).reshape(1),
        *scale_inputs, qg, k_cache, v_cache,
    )
    if return_stats:
        o, m, l = out
        return (
            o[:, :, :G, :].reshape(B, H, D),
            m[:, :, :G, 0],  # [B, Hkv, G] (stats broadcast over lanes)
            l[:, :, :G, 0],
        )
    return out[:, :, :G, :].reshape(B, H, D)


# ---------------- ragged prefill (chunked, reads the paged cache) ----------------


def _prefill_page(bt, hist, j, i, p, pages_per_step, block_size, q_tile,
                  t_pad):
    """Physical page behind stream ``p`` of superblock ``i`` for q tile
    ``j`` (clamped to the tile's causal horizon and the last written
    page). Shared by the page index maps and the scale lookup."""
    tile_last = (hist[0] + (j + 1) * q_tile - 1) // block_size
    written_last = (hist[0] + t_pad - 1) // block_size
    pi = jnp.minimum(
        jnp.minimum(i * pages_per_step + p, tile_last),
        jnp.minimum(written_last, bt.shape[0] - 1),
    )
    return bt[pi]


def _prefill_kernel(
    # scalar prefetch [+ k_scales, v_scales [N] f32 when has_scales]
    block_table_ref,  # [M] int32 (SMEM)
    hist_ref,  # [1] int32 (SMEM): tokens already cached before this chunk
    # inputs: q then P k-page refs then P v-page refs [then sinks]
    *refs,
    scale: float,
    block_size: int,
    q_tile: int,  # Tq: chunk rows per grid step
    group: int,  # Gp: padded query heads per kv head
    pages_per_step: int,
    window: int = 0,  # sliding attention; 0 = full
    has_sinks: bool = False,  # gpt-oss per-head sink logits
    has_scales: bool = False,  # int8 device cache: the scale planes
    # ride in SMEM as two more scalar-prefetch refs
    t_pad: int = 0,  # padded chunk rows (the page clamp needs it)
):
    P = pages_per_step
    if has_scales:
        ks_ref, vs_ref, *refs = refs  # [N] f32 each (SMEM)
    q_ref = refs[0]  # [1, Tq*Gp, D]
    k_refs = refs[1 : 1 + P]  # each [1, 1, bs, D]
    v_refs = refs[1 + P : 1 + 2 * P]
    n_in = 1 + 2 * P
    sink_ref = refs[n_in] if has_sinks else None  # [1, Gp]
    n_in += int(has_sinks)
    o_ref = refs[n_in]  # [1, Tq*Gp, D]
    m_scr, l_scr, acc_scr = refs[n_in + 1 :]

    j = pl.program_id(0)  # q tile
    i = pl.program_id(2)  # kv superblock (innermost: sequential accumulation)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    hist = hist_ref[0]
    start = i * (P * block_size)
    # last query position in this tile — superblocks past it are fully masked
    tile_last_q = hist + (j + 1) * q_tile - 1
    in_range = start <= tile_last_q
    if window > 0:
        # first (lowest) query position of the tile bounds the window floor
        tile_first_q = hist + j * q_tile
        in_range &= start + P * block_size > tile_first_q - window + 1

    @pl.when(in_range)
    def _superblock():
        q = q_ref[0].astype(jnp.float32) * scale  # [Tq*Gp, D]
        if has_scales:
            pages = [
                _prefill_page(block_table_ref, hist_ref, j, i, p, P,
                              block_size, q_tile, t_pad)
                for p in range(P)
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
        q_pos = hist + j * q_tile + rows // group
        kv_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
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
            # gpt-oss: the sink logit joins the softmax normalization —
            # l' = l*exp(m - m_f) + exp(s - m_f) with m_f = max(m, s).
            # Row r's sink is its query head's (g = r % Gp; rows are
            # (t, g) lexicographic). Select it with a one-hot dot —
            # gather/relayout-free in Mosaic; sink_ref is [Gp, 128]
            # lane-broadcast so the product lands as [rows, 128].
            rows = q_tile * group
            g_of_row = jax.lax.broadcasted_iota(
                jnp.int32, (rows, group), 0
            ) % group
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, group), 1)
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
    jax.jit, static_argnames=("scale", "pages_per_step", "window", "interpret")
)
def paged_prefill_attention(
    q: jnp.ndarray,  # [T, H, D] chunk queries
    k_cache_layer: jnp.ndarray,  # [Hkv, N, bs, D] — chunk ALREADY written
    v_cache_layer: jnp.ndarray,
    block_table: jnp.ndarray,  # [M] int32, covers history + padded chunk
    history_len: jnp.ndarray,  # scalar int32
    scale: float,
    pages_per_step: int = 0,  # 0 -> auto (largest pow2 <= 8 dividing M)
    window: int = 0,  # sliding attention width; 0 = full
    sinks: jnp.ndarray | None = None,  # [H] gpt-oss sink logits
    interpret: bool = False,
    k_scales: jnp.ndarray | None = None,  # [N] f32 per-page (int8 cache)
    v_scales: jnp.ndarray | None = None,
) -> jnp.ndarray:  # [T, H, D]
    """Flash-style chunked-prefill attention over the paged cache.

    The caller must have scattered this chunk's (rope'd) K/V into the cache
    first (write-before-attend, as llama.prefill does) — the kernel then
    reads history AND chunk through the block table, so one code path
    covers chunked prefill and prefix-cache hits. Causal masking at
    absolute positions does all the ragged bookkeeping: padded tail rows
    only ever produce garbage in rows the wrapper's caller discards, and
    real rows (t < valid_len) never attend past themselves.

    Grid = (q_tiles, kv_heads, superblocks of P pages); block table +
    history length are scalar-prefetched so each page's ``index_map`` DMAs
    exactly the needed physical [bs, D] tile per stream (pages beyond a
    tile's causal horizon re-map to the last needed page — consecutive
    identical indices skip the fetch). fp32 online softmax in VMEM
    scratch, output written once on the final step.
    """
    T, H, D = q.shape
    Hkv, N, bs, _ = k_cache_layer.shape
    M = block_table.shape[0]
    G = H // Hkv
    Gp = max(8, -(-G // 8) * 8)
    Tq = min(128, T)
    nT = -(-T // Tq)
    Tpad = nT * Tq
    P = pages_per_step or _pick_pages_per_step(M)
    if M % P:
        raise ValueError(
            f"pages_per_step={P} must divide table width M={M} "
            "(a truncated grid would silently drop tail pages)"
        )
    # [T, H, D] -> [Hkv, nT*Tq*Gp, D]: rows are (tile, t, g) lexicographic,
    # so in-kernel row r of tile j maps to t = j*Tq + r//Gp, g = r%Gp
    qg = q.reshape(T, Hkv, G, D)
    qg = jnp.pad(qg, ((0, Tpad - T), (0, 0), (0, Gp - G), (0, 0)))
    qg = qg.transpose(1, 0, 2, 3).reshape(Hkv, Tpad * Gp, D)

    # index maps see every scalar-prefetch ref; ``*_`` absorbs the scale
    # planes of the int8 lane (SMEM scalars — see paged_decode_attention)
    def page_index(p):
        def index(j, h, i, bt, hist, *_):
            return (h, _prefill_page(bt, hist, j, i, p, P, bs, Tq, Tpad),
                    0, 0)

        return index

    page_spec = [
        pl.BlockSpec((1, 1, bs, D), page_index(p)) for p in range(P)
    ]
    scale_inputs = ()
    if k_scales is not None:
        scale_inputs = (
            k_scales.astype(jnp.float32), v_scales.astype(jnp.float32)
        )

    def tile_index(j, h, i, *_):
        return (h, j, 0)

    sink_inputs, sink_specs = (), ()
    if sinks is not None:
        # [H] -> [Hkv, Gp, 128] f32 lane-broadcast; padded group lanes
        # at a large FINITE negative (their exp underflows to 0 — -inf
        # would produce 0*inf NaNs in the one-hot dot)
        s = sinks.astype(jnp.float32).reshape(Hkv, G)
        s = jnp.pad(s, ((0, 0), (0, Gp - G)), constant_values=-1e30)
        s = jnp.broadcast_to(s[:, :, None], (Hkv, Gp, 128))
        sink_inputs = (s,)
        sink_specs = (
            pl.BlockSpec((1, Gp, 128), lambda j, h, i, *_: (h, 0, 0)),
        )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(scale_inputs),
        grid=(nT, Hkv, M // P),
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
        _prefill_kernel, scale=scale, block_size=bs, q_tile=Tq, group=Gp,
        pages_per_step=P, window=window, has_sinks=sinks is not None,
        has_scales=k_scales is not None, t_pad=Tpad,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Hkv, Tpad * Gp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * 2 * Tpad * H * M * bs * D,
            bytes_accessed=2 * Hkv * M * bs * D * k_cache_layer.dtype.itemsize,
            transcendentals=Tpad * H * M * bs,
        ),
        interpret=interpret,
    )(jnp.asarray(block_table), jnp.asarray(history_len, jnp.int32).reshape(1),
      *scale_inputs, qg, *([k_cache_layer] * P), *([v_cache_layer] * P),
      *sink_inputs)
    out = out.reshape(Hkv, nT, Tq, Gp, D).transpose(1, 2, 0, 3, 4)
    return out.reshape(Tpad, Hkv, Gp, D)[:T, :, :G, :].reshape(T, H, D)
