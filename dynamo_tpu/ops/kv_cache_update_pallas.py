"""Pallas TPU kernel: in-place KV-cache page writes.

XLA lowers the engine's cache writes to scatter ops, and whether it
updates the donated cache in place depends on the INDEX FORM (measured
on v5e, by AOT and on the chip; PERF.md section 6, PRs 29, 33 and 41):

  * mixed basic + advanced indexing, ``cache.at[l, :, blk, off].set(v)``
    (the XLA decode path) and ``cache_layer.at[:, blk, off].set(chunk)``
    (``write_chunk_to_cache``: ``prefill``, and the mixed step until PR
    41): NOT in place. The full slice over the heads is a window the
    compiler makes contiguous by re-laying the operand, so every such
    write copies the array it writes into, the pool or a layer's slab
    (``copy`` / ``copy_bitcast_fusion`` / ``slice_bitcast_fusion`` /
    ``copy_dynamic-update-slice_fusion`` in a trace: 48 ms of a mixed
    step of ``olmoe-1b-7b``, 82 of ``olmo2-1b``'s, whatever it wrote);
  * every index advanced, ``cache.at[l, heads[:, None], blk[None],
    off[None]].set(rows)`` (``ops/attention.write_rows_to_cache``, the
    mixed step since PR 41): IN PLACE. The compiler views the pool as
    ``[L*Hkv*N*bs, D]`` (a bitcast) and scatters rows of ``D``: about
    0.08 us a row, 0.65 ms for the 544 rows x 16 heads of a 512-token
    mixed step, no temporary.

(The reference hits the same wall on GPU and solves it with vLLM's
reshape_and_cache CUDA kernel; vLLM's TPU port ships an equivalent
kv_cache_update Pallas kernel.)

This kernel is that equivalent for decode (one row a sequence, every
layer at once, where a scatter a layer would also serialise behind the
attention that follows it), for the stacked-layer head-major layout
``[L, Hkv, N, bs, D]``: ``input_output_aliases`` pins the output buffer
to the input cache, so only the touched page tiles move. Per grid step
(l, b) the pipeline DMAs the target page tile [Hkv, bs, D] in, the
kernel overwrites row ``off[b]``, and the pipeline writes the tile back
— a read-modify-write of 64 KB per (layer, seq) instead of a copy of
the full multi-GB cache.

Decode usage (one call per fused step, all layers at once): the layer
loop STACKS each layer's new-token K/V (tiny [L, B, Hkv, D]) instead of
scattering into the big cache 2L times per step; attention handles the
current token out-of-cache (ops/attention.decode_attention_merged) so
nothing needs the write until the step ends.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _in_hbm(cache):
    """``out_shape`` of an aliased cache, pinned to HBM (and with it the
    input it aliases). The kernels touch a page tile a grid step; the
    TPU compiler, which takes a custom call to read its operands whole,
    otherwise stages a pool that fits its fast memory around the call,
    in and out again: a latent model's 50 MB rope pool three times a
    decode step (PERF.md section 6, PR 53). Pools of GiBs never were.
    The price: a program that donates the caches and whose ROOT is the
    call itself (an append jitted on its own) does not compile, the
    pinned result and the unpinned parameter fail the compiler's alias
    check. Behind anything at all it does (every step program;
    ``scripts/validate_tpu_kernels.py`` puts a barrier there)."""
    return pltpu.HBM(cache.shape, cache.dtype)


def _land_row(tile, new_row, row):
    """``tile`` [Hkv, bs, D] f32 with sublane ``row`` replaced by
    ``new_row`` [Hkv, 1, D] f32 — a full-tile select on an iota mask.
    Mosaic refuses a single-row store at a dynamic sublane offset into a
    (bs, D)-tiled page ("cannot statically prove that index in dimension
    3 is a multiple of 8"); the select touches only whole tiles, and in
    f32 (a lossless round trip for bf16/fp8/int8 pages) it needs no
    packed-dtype mask relayout. ``row`` outside [0, bs) selects nothing."""
    hit = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1) == row
    return jnp.where(hit, new_row, tile)


def _append_kernel(
    # scalar prefetch
    blk_ref,  # [B] int32 physical page per sequence (SMEM)
    off_ref,  # [B] int32 row within the page (SMEM)
    # inputs
    k_new_ref,  # [1, 1, Hkv, 1, D] layer l, sequence b
    v_new_ref,  # [1, 1, Hkv, 1, D]
    k_page_ref,  # [1, Hkv, 1, bs, D] aliased page tile of k_cache
    v_page_ref,  # [1, Hkv, 1, bs, D] aliased page tile of v_cache
    # outputs (aliased)
    k_out_ref,  # [1, Hkv, 1, bs, D]
    v_out_ref,  # [1, Hkv, 1, bs, D]
):
    off = off_ref[pl.program_id(1)]
    # pass the tile through with row `off` of every head replaced
    for new_ref, page_ref, out_ref in (
        (k_new_ref, k_page_ref, k_out_ref),
        (v_new_ref, v_page_ref, v_out_ref),
    ):
        out_ref[0, :, 0] = _land_row(
            page_ref[0, :, 0].astype(jnp.float32),
            new_ref[0, 0].astype(jnp.float32),
            off,
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(2, 3))
def kv_cache_append(
    k_new: jnp.ndarray,  # [L, B, Hkv, D] this step's keys, all layers
    v_new: jnp.ndarray,  # [L, B, Hkv, D]
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] donated
    v_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] donated
    blk: jnp.ndarray,  # [B] int32 physical page of each sequence's slot
    off: jnp.ndarray,  # [B] int32 row within that page
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write one new token per sequence into both caches, in place.

    Sequences sharing a physical page (cannot happen for live decode
    slots — the allocator gives every sequence its own tail page) would
    race, so callers must pass distinct ``blk`` entries for real rows;
    padded rows may all point at the sacrificial page 0 with distinct
    semantics handled by masking (never read).
    """
    return _append_call(
        k_new, v_new, k_cache, v_cache, blk, off, interpret=interpret
    )


def kv_cache_append_sharded(
    k_new: jnp.ndarray,  # [L, B, Hkv, D], Hkv sharded over tp
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D], Hkv sharded over tp
    v_cache: jnp.ndarray,
    blk: jnp.ndarray,  # [B] replicated
    off: jnp.ndarray,  # [B] replicated
    mesh,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The append kernel under shard_map over ``tp``: each device RMWs the
    page tiles of its local kv-head shard — head-parallel, no collectives
    (kv-head axis is the cache's sharded axis, see ops/attention docs)."""
    import functools

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        functools.partial(_append_call, interpret=interpret),
        mesh=mesh,
        in_specs=(
            P(None, None, "tp", None),  # k_new
            P(None, None, "tp", None),  # v_new
            P(None, "tp", None, None, None),  # k_cache
            P(None, "tp", None, None, None),  # v_cache
            P(),  # blk
            P(),  # off
        ),
        out_specs=(
            P(None, "tp", None, None, None),
            P(None, "tp", None, None, None),
        ),
        check_vma=False,
    )(k_new, v_new, k_cache, v_cache, blk, off)


def kv_cache_append_replicated(
    k_new: jnp.ndarray,  # [L, B, Hkv, Dk] replicated
    v_new: jnp.ndarray,  # [L, B, Hkv, Dv] replicated
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, Dk] replicated
    v_cache: jnp.ndarray,
    blk: jnp.ndarray,
    off: jnp.ndarray,
    mesh,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The append kernel on a mesh whose cache is fully REPLICATED (the
    MLA latent cache: single kv "head", so no tp axis to shard — see
    parallel/mesh.cache_sharding). shard_map with all-replicated specs
    pins the pallas_call per device; each redundantly RMWs its replica,
    which beats letting GSPMD guess a partition for the kernel."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        _ft.partial(_append_call, interpret=interpret),
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(k_new, v_new, k_cache, v_cache, blk, off)


def _append_quant_kernel(
    # scalar prefetch
    blk_ref,  # [B] int32 physical page per sequence (SMEM)
    off_ref,  # [B] int32 row within the page (SMEM)
    rk_ref,  # [L, B] f32 old/new k-scale ratio (<= 1) for the page
    rv_ref,  # [L, B] f32 old/new v-scale ratio
    # inputs
    kq_ref,  # [1, 1, Hkv, 1, D] layer l, sequence b — PRE-quantized int8
    vq_ref,  # [1, 1, Hkv, 1, D]
    k_page_ref,  # [1, Hkv, 1, bs, D] aliased page tile of k_cache
    v_page_ref,  # [1, Hkv, 1, bs, D]
    # outputs (aliased)
    k_out_ref,
    v_out_ref,
):
    l = pl.program_id(0)
    b = pl.program_id(1)
    off = off_ref[b]
    # requantize the page against its grown scale (r == 1 when the scale
    # did not grow: int8 -> f32 -> round -> int8 round-trips bit-exactly)
    for r_ref, q_ref, page_ref, out_ref in (
        (rk_ref, kq_ref, k_page_ref, k_out_ref),
        (rv_ref, vq_ref, v_page_ref, v_out_ref),
    ):
        page = page_ref[0, :, 0].astype(jnp.float32) * r_ref[l, b]
        page = jnp.clip(jnp.round(page), -127.0, 127.0)
        # then land the new row, already quantized against the new scale
        out_ref[0, :, 0] = _land_row(
            page, q_ref[0, 0].astype(jnp.float32), off
        ).astype(out_ref.dtype)


def quant_scale_update(x_new, scales, blk, qmax=127.0, eps=1e-12):
    """Scale-plane update for one appended row per sequence.

    ``x_new`` [L, B, Hkv, D] new rows; ``scales`` [L, N] per-page f32;
    ``blk`` [B] target page per sequence. Returns ``(new_scales, r, q)``:
    the grown plane (running absmax/qmax per page, scatter-max so
    duplicate pages — the trash page 0 — resolve deterministically), the
    old/new ratio per (layer, row) for requantizing resident page
    content, and the rows quantized against the NEW scale."""
    xf = x_new.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(2, 3)) / qmax  # [L, B]
    new_scales = scales.at[:, blk].max(jnp.maximum(amax, eps))
    r = (scales / new_scales)[:, blk]  # [L, B], <= 1
    q = jnp.clip(
        jnp.round(xf / new_scales[:, blk][:, :, None, None]), -qmax, qmax
    )
    return new_scales, r, q.astype(jnp.int8)


def _append_quant_call(kq, vq, k_cache, v_cache, rk, rv, blk, off,
                       interpret=False):
    """Page RMW for the quantized append: requantize the target page by
    its old/new scale ratio, then write the pre-quantized int8 row. The
    scale math happens OUTSIDE (quant_scale_update) so the sharded path
    sees a globally-consistent plane (a per-shard absmax over the local
    kv-head slice would diverge across devices)."""
    L, B, Hkv, Dk = kq.shape
    Dv = vq.shape[-1]
    bs = k_cache.shape[3]
    k_page = pl.BlockSpec(
        (1, Hkv, 1, bs, Dk), lambda l, b, blk, off, rk, rv: (l, 0, blk[b], 0, 0)
    )
    v_page = pl.BlockSpec(
        (1, Hkv, 1, bs, Dv), lambda l, b, blk, off, rk, rv: (l, 0, blk[b], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(L, B),
        in_specs=[
            pl.BlockSpec(
                (1, 1, Hkv, 1, Dk),
                lambda l, b, blk, off, rk, rv: (l, b, 0, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, Hkv, 1, Dv),
                lambda l, b, blk, off, rk, rv: (l, b, 0, 0, 0),
            ),
            k_page,
            v_page,
        ],
        out_specs=[k_page, v_page],
    )
    return pl.pallas_call(
        _append_quant_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(k_cache.shape, k_cache.dtype),
            jax.ShapeDtypeStruct(v_cache.shape, v_cache.dtype),
        ],
        # +4 scalar-prefetch args precede the tensor operands
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(blk, off, rk, rv, kq[:, :, :, None, :], vq[:, :, :, None, :],
      k_cache, v_cache)


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(2, 3))
def kv_cache_append_quantized(
    k_new: jnp.ndarray,  # [L, B, Hkv, D] this step's keys, full precision
    v_new: jnp.ndarray,  # [L, B, Hkv, D]
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] int8, donated
    v_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] int8, donated
    k_scales: jnp.ndarray,  # [L, N] f32 per-page scale plane (NOT donated)
    v_scales: jnp.ndarray,  # [L, N] f32
    blk: jnp.ndarray,  # [B] int32
    off: jnp.ndarray,  # [B] int32
    interpret: bool = False,
):
    """kv_cache_append for the int8-with-scales device cache: one fused
    dispatch that grows each written page's running absmax scale,
    requantizes the page when its scale grew, and lands the new row
    quantized against the updated scale. Returns ``(k_cache, v_cache,
    k_scales, v_scales, n_requants)`` — n_requants counts the
    (layer, page) scale entries that grew this step (the
    kv_device_requants_total gauge reads it off-device)."""
    new_ks, rk, kq = quant_scale_update(k_new, k_scales, blk)
    new_vs, rv, vq = quant_scale_update(v_new, v_scales, blk)
    k_cache, v_cache = _append_quant_call(
        kq, vq, k_cache, v_cache, rk, rv, blk, off, interpret=interpret
    )
    n_requants = (
        jnp.sum(new_ks > k_scales) + jnp.sum(new_vs > v_scales)
    ).astype(jnp.int32)
    return k_cache, v_cache, new_ks, new_vs, n_requants


def kv_cache_append_quantized_sharded(
    k_new: jnp.ndarray,  # [L, B, Hkv, D], Hkv sharded over tp
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D], Hkv sharded over tp
    v_cache: jnp.ndarray,
    k_scales: jnp.ndarray,  # [L, N] replicated
    v_scales: jnp.ndarray,
    blk: jnp.ndarray,  # [B] replicated
    off: jnp.ndarray,  # [B] replicated
    mesh,
    interpret: bool = False,
):
    """Quantized append under shard_map over ``tp``. The scale update is
    computed on the GLOBAL arrays first (absmax spans all kv heads, so
    it cannot run per-shard); only the page RMW shard_maps."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    new_ks, rk, kq = quant_scale_update(k_new, k_scales, blk)
    new_vs, rv, vq = quant_scale_update(v_new, v_scales, blk)
    k_cache, v_cache = jax.shard_map(
        _ft.partial(_append_quant_call, interpret=interpret),
        mesh=mesh,
        in_specs=(
            P(None, None, "tp", None),  # kq
            P(None, None, "tp", None),  # vq
            P(None, "tp", None, None, None),  # k_cache
            P(None, "tp", None, None, None),  # v_cache
            P(),  # rk
            P(),  # rv
            P(),  # blk
            P(),  # off
        ),
        out_specs=(
            P(None, "tp", None, None, None),
            P(None, "tp", None, None, None),
        ),
        check_vma=False,
    )(kq, vq, k_cache, v_cache, rk, rv, blk, off)
    n_requants = (
        jnp.sum(new_ks > k_scales) + jnp.sum(new_vs > v_scales)
    ).astype(jnp.int32)
    return k_cache, v_cache, new_ks, new_vs, n_requants


def _append_tokens_kernel(
    # scalar prefetch
    page_ref,  # [B] int32 this phase's target page per sequence
    off0_ref,  # [B] int32 row of the FIRST in-flight token within page 0
    # inputs
    k_new_ref,  # [1, 1, T, Hkv, 1, D]
    v_new_ref,
    k_page_ref,  # [1, Hkv, 1, bs, D] aliased page tile
    v_page_ref,
    # outputs (aliased)
    k_out_ref,
    v_out_ref,
    *,
    n_tokens: int,
    block_size: int,
    phase: int,  # 0: rows inside the first page; 1: spill into the next
):
    off0 = off0_ref[pl.program_id(1)]
    for new_ref, page_ref, out_ref in (
        (k_new_ref, k_page_ref, k_out_ref),
        (v_new_ref, v_page_ref, v_out_ref),
    ):
        tile = page_ref[0, :, 0].astype(jnp.float32)
        for t in range(n_tokens):
            # token t's row in THIS phase's page; the other phase's rows
            # fall outside [0, bs) and select nothing
            row = off0 + t - phase * block_size
            tile = _land_row(
                tile, new_ref[0, 0, t].astype(jnp.float32), row
            )
        out_ref[0, :, 0] = tile.astype(out_ref.dtype)


def kv_cache_append_tokens_xla(k_new, v_new, k_cache, v_cache, blk, off):
    """The XLA scatter kv_cache_append_tokens replaces — what serves
    where the Pallas path is off (CPU, meshes the kernels do not cover)
    and the oracle the kernel is tested against. Same operands."""
    L, B, T = k_new.shape[:3]
    lidx = jnp.arange(L)[:, None, None]
    bidx = jnp.arange(B)[None, :, None]
    tidx = jnp.arange(T)[None, None, :]
    k_cache = k_cache.at[lidx, :, blk[bidx, tidx], off[bidx, tidx]].set(
        k_new.astype(k_cache.dtype)
    )
    v_cache = v_cache.at[lidx, :, blk[bidx, tidx], off[bidx, tidx]].set(
        v_new.astype(v_cache.dtype)
    )
    return k_cache, v_cache


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(2, 3))
def kv_cache_append_tokens(
    k_new: jnp.ndarray,  # [L, B, T, Hkv, D] T in-flight tokens per seq
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] donated
    v_cache: jnp.ndarray,
    blk: jnp.ndarray,  # [B, T] int32 physical page per (seq, token)
    off: jnp.ndarray,  # [B, T] int32 row within the page
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Multi-token kv_cache_append (speculative-decoding verify): writes
    T consecutive-position rows per sequence, all layers, in place.

    T consecutive rows span at most TWO pages. Each page is RMW'd in its
    own chained pallas_call (phase 0 = the first page's rows, phase 1 =
    the spill into the next page) so one grid step owns each page — a
    same-page RMW split across pipeline steps could read a stale
    prefetched tile and lose the earlier step's rows. Sequences that
    don't cross a boundary point phase 1 at the sacrificial page 0 (a
    benign passthrough; real pages are never 0). Requires T <= block_size.
    The two caches may have different trailing dims (MLA: c_kv vs k_pe).
    """
    L, B, T, Hkv, Dk = k_new.shape
    Dv = v_new.shape[-1]
    bs = k_cache.shape[3]
    if T > bs:
        raise ValueError(f"T={T} in-flight rows must fit a page (bs={bs})")

    blk0 = blk[:, 0]
    blk_last = blk[:, T - 1]
    # no boundary cross -> phase 1 RMWs the trash page instead
    blk1 = jnp.where(blk_last == blk0, 0, blk_last)
    off0 = off[:, 0]

    for phase, page in ((0, blk0), (1, blk1)):
        k_page = pl.BlockSpec(
            (1, Hkv, 1, bs, Dk), lambda l, b, pg, o0: (l, 0, pg[b], 0, 0)
        )
        v_page = pl.BlockSpec(
            (1, Hkv, 1, bs, Dv), lambda l, b, pg, o0: (l, 0, pg[b], 0, 0)
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L, B),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, T, Hkv, 1, Dk),
                    lambda l, b, pg, o0: (l, b, 0, 0, 0, 0),
                ),
                pl.BlockSpec(
                    (1, 1, T, Hkv, 1, Dv),
                    lambda l, b, pg, o0: (l, b, 0, 0, 0, 0),
                ),
                k_page,
                v_page,
            ],
            out_specs=[k_page, v_page],
        )
        kernel = functools.partial(
            _append_tokens_kernel, n_tokens=T, block_size=bs, phase=phase
        )
        k_cache, v_cache = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[_in_hbm(k_cache), _in_hbm(v_cache)],
            input_output_aliases={4: 0, 5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
            interpret=interpret,
        )(page, off0, k_new[:, :, :, :, None, :], v_new[:, :, :, :, None, :],
          k_cache, v_cache)
    return k_cache, v_cache


def kv_cache_append_tokens_sharded(
    k_new: jnp.ndarray,  # [L, B, T, Hkv, D], Hkv sharded over tp
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D], Hkv sharded over tp
    v_cache: jnp.ndarray,
    blk: jnp.ndarray,  # [B, T] replicated
    off: jnp.ndarray,  # [B, T] replicated
    mesh,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """kv_cache_append_tokens under shard_map over ``tp`` (head-parallel,
    no collectives — same argument as kv_cache_append_sharded)."""
    import functools as _ft

    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        _ft.partial(kv_cache_append_tokens, interpret=interpret),
        mesh=mesh,
        in_specs=(
            P(None, None, None, "tp", None),  # k_new
            P(None, None, None, "tp", None),  # v_new
            P(None, "tp", None, None, None),  # k_cache
            P(None, "tp", None, None, None),  # v_cache
            P(),  # blk
            P(),  # off
        ),
        out_specs=(
            P(None, "tp", None, None, None),
            P(None, "tp", None, None, None),
        ),
        check_vma=False,
    )(k_new, v_new, k_cache, v_cache, blk, off)


def _append_call(k_new, v_new, k_cache, v_cache, blk, off, interpret=False):
    """The pallas_call body shared by the single-device and shard_map
    paths (operates on whatever shard it is handed). The two caches may
    have DIFFERENT trailing dims (MLA stores the c_kv latent in the
    k slot and the head-shared k_pe in the v slot)."""
    L, B, Hkv, Dk = k_new.shape
    Dv = v_new.shape[-1]
    bs = k_cache.shape[3]
    k_page = pl.BlockSpec(
        (1, Hkv, 1, bs, Dk), lambda l, b, blk, off: (l, 0, blk[b], 0, 0)
    )
    v_page = pl.BlockSpec(
        (1, Hkv, 1, bs, Dv), lambda l, b, blk, off: (l, 0, blk[b], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L, B),
        in_specs=[
            pl.BlockSpec(
                (1, 1, Hkv, 1, Dk), lambda l, b, blk, off: (l, b, 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, Hkv, 1, Dv), lambda l, b, blk, off: (l, b, 0, 0, 0)
            ),
            k_page,
            v_page,
        ],
        out_specs=[k_page, v_page],
    )
    return pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[_in_hbm(k_cache), _in_hbm(v_cache)],
        # +2 for the scalar-prefetch args: pallas numbers aliases over the
        # FULL operand list including prefetch scalars
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(blk, off, k_new[:, :, :, None, :], v_new[:, :, :, None, :],
      k_cache, v_cache)
