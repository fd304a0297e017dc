"""Paged attention over a block-table-indirect KV cache.

The reference's equivalent lives inside the engines it wraps (vLLM's paged
attention CUDA kernels); on TPU we own it. Two implementations with one
interface:

  * :func:`decode_attention_xla` et al — pure-XLA gather + dense attention.
    Correct everywhere (CPU tests, any TPU), and XLA fuses it acceptably
    for small batches.
  * a Pallas ragged kernel in :mod:`dynamo_tpu.ops.paged_attention_pallas`
    (used on TPU for decode via the :func:`decode_attention` dispatcher).

Cache layout (one array per K/V for all layers — a single sharded
residency):

    k_cache, v_cache: [num_layers, num_kv_heads, num_blocks, block_size, head_dim]

The kv-head axis leads the page axes so one (head, page) is a contiguous
``[block_size, head_dim]`` tile — the unit the Pallas kernel DMAs from HBM
into VMEM — and the "tp" mesh axis shards on num_kv_heads. Block tables
are [batch, max_blocks_per_seq] int32 indices into num_blocks; sequence
length masks out unused tail positions. Static shapes throughout — batch,
table width, and block count are fixed per compiled program (XLA
requirement).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _shard_tp(mesh, local_fn, *, arr_specs, arrs, k_cache, v_cache,
              scalars, sinks, out_spec):
    """One shard_map over ``tp`` shared by every paged-attention wrapper.

    The kv-head axis is the cache's sharded axis (ops module docs), and
    paged attention is embarrassingly parallel over kv-head groups: each
    device runs the kernel on its local [..., Hkv/tp, N, bs, D] cache
    shard (the decode wrappers' whole cache with its leading layer axis,
    the prefill wrapper's one layer) against its local head-sharded
    query arrays (``arrs`` with ``arr_specs``); ``scalars`` (the layer
    index, block tables, lengths) replicate, matching the engine's
    host-batch inputs; other mesh axes (dp/pp/sp/ep) replicate too — no
    collectives needed. Per-head sinks, only when present, shard with
    the heads and arrive as ``local_fn``'s LAST argument; keeping the
    sinks/no-sinks cases one invocation stops the spec blocks drifting
    apart."""
    cache_spec = P(*([None] * (k_cache.ndim - 4)), "tp", None, None, None)
    in_specs = (
        *arr_specs,
        cache_spec,  # k cache
        cache_spec,  # v cache
        *([P()] * len(scalars)),
    )
    # asarray: the layer index may be a Python int
    operands = (*arrs, k_cache, v_cache, *map(jnp.asarray, scalars))
    if sinks is not None:
        in_specs += (P("tp"),)
        operands += (sinks,)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        check_vma=False,
    )(*operands)


def repeat_kv(x: jnp.ndarray, n_rep: int, axis: int) -> jnp.ndarray:
    """GQA: repeat kv heads to match query heads."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=axis)


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D]: the whole cache
    v_cache: jnp.ndarray,
    layer,  # int or int32 scalar: the layer to read (a slab: [None], 0)
    block_tables: jnp.ndarray,
    seq_lens: jnp.ndarray,
    scale: float,
    use_pallas: bool = False,
    mesh=None,
    window: int = 0,
    sinks=None,  # [H] gpt-oss sink logits; stats-fold on the kernel path
    cap: float = 0.0,  # gemma-2 softcap: forces the XLA path
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
) -> jnp.ndarray:
    """Dispatcher: Pallas ragged kernel on TPU, XLA path elsewhere.
    ``window`` (sliding attention) is honored by both: the XLA path
    masks, the in-repo Mosaic kernel takes a window floor.

    ``use_pallas`` must be trace-static. With a ``mesh``, the kernel runs
    under shard_map: each device gets its tp shard of the kv heads (cache
    axis 1 / q axis 1) and runs the kernel on purely local tiles — paged
    attention is head-parallel, so no collectives are needed. Callers
    guarantee num_kv_heads % tp == 0 (the engine falls back to XLA
    otherwise, where GSPMD handles uneven head splits).

    ``k_scales``/``v_scales`` (per-page f32, this layer's [N] slice of
    the engine's scale planes) ride every path: fused per-page dequant
    in the kernels, gathered-scale multiply in the XLA fallback.

    The kernels take the cache whole and read ``layer``'s pages in
    place (a ``k_cache[layer]`` operand of a kernel is a copy of the
    pool: paged_attention_pallas module docs); the XLA path slices the
    layer here, which fuses into its gather.
    """
    if use_pallas and mesh is not None and not cap:
        return paged_decode_attention_sharded(
            q, k_cache, v_cache, layer, block_tables, seq_lens, scale,
            mesh, window=window, sinks=sinks, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    if use_pallas and sinks is None and not cap:
        from .paged_attention_pallas import paged_decode_attention

        return paged_decode_attention(
            q, k_cache, v_cache, layer, block_tables, seq_lens, scale,
            window=window, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    if use_pallas and not cap:
        return _decode_kernel_with_sinks(
            q, k_cache, v_cache, layer, block_tables, seq_lens, scale,
            sinks, window=window, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    return decode_attention_xla(
        q, k_cache[layer], v_cache[layer], block_tables, seq_lens, scale,
        window=window, sinks=sinks, cap=cap,
        k_scales=k_scales, v_scales=v_scales,
    )


def _decode_kernel_with_sinks(
    q, k_cache, v_cache, layer, block_tables, seq_lens, scale,
    sinks, window: int = 0, interpret: bool = False,
    k_scales=None, v_scales=None,
):
    """Pallas decode attention for gpt-oss sink models: the in-repo
    stats-emitting kernel scores the cache, then the sink logit joins
    the normalization OUTSIDE the kernel — the kernel's output o is
    already softmax-normalized by l, so the sink fold is one per-head
    rescale: o' = o * l*exp(m-m_f) / (l*exp(m-m_f) + exp(s-m_f)), the
    same algebra verify_attention uses for its merge denominator."""
    from .paged_attention_pallas import paged_decode_attention

    B, H, D = q.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    o, m, l = paged_decode_attention(
        q, k_cache, v_cache, layer, block_tables, seq_lens, scale,
        return_stats=True, window=window, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales,
    )
    s = sinks.astype(jnp.float32).reshape(1, Hkv, G)
    m_f = jnp.maximum(m, s)
    kept = l * jnp.exp(m - m_f)  # [B, Hkv, G]
    w = kept / jnp.maximum(kept + jnp.exp(s - m_f), 1e-20)
    o = o.astype(jnp.float32).reshape(B, Hkv, G, D) * w[..., None]
    return o.reshape(B, H, D).astype(q.dtype)


def paged_decode_attention_sharded(
    q: jnp.ndarray,  # [B, H, D]
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D], Hkv sharded over tp
    v_cache: jnp.ndarray,
    layer,  # int or int32 scalar, replicated
    block_tables: jnp.ndarray,  # [B, M] replicated
    seq_lens: jnp.ndarray,  # [B] replicated
    scale: float,
    mesh,
    window: int = 0,
    sinks=None,  # [H], sharded over tp with the heads
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page, replicated (page axis is unsharded)
    v_scales=None,
) -> jnp.ndarray:
    """Pallas decode kernel under shard_map over tp (see _shard_tp).
    Head-parallel — the sink fold included (it's a per-head rescale).
    Per-page scales replicate like the block tables (pages aren't the
    sharded axis; every shard reads the same plane)."""

    def _local(q, kc, vc, ly, bt, sl, *rest):
        from .paged_attention_pallas import paged_decode_attention

        rest = list(rest)
        ks = vs = s = None
        if k_scales is not None:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        if rest:
            s = rest[0]
        if s is None:
            return paged_decode_attention(
                q, kc, vc, ly, bt, sl, scale, window=window,
                interpret=interpret, k_scales=ks, v_scales=vs,
            )
        return _decode_kernel_with_sinks(
            q, kc, vc, ly, bt, sl, scale, s, window=window,
            interpret=interpret, k_scales=ks, v_scales=vs,
        )

    scalars = (layer, block_tables, seq_lens)
    if k_scales is not None:
        scalars += (k_scales, v_scales)
    return _shard_tp(
        mesh, _local,
        arr_specs=(P(None, "tp", None),),  # q: heads sharded
        arrs=(q,),
        k_cache=k_cache, v_cache=v_cache,
        scalars=scalars, sinks=sinks,
        out_spec=P(None, "tp", None),
    )


def decode_attention_merged(
    q: jnp.ndarray,  # [B, H, D] current token's queries
    k_new: jnp.ndarray,  # [B, Hkv, D] current token's key (rope'd)
    v_new: jnp.ndarray,  # [B, Hkv, D]
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] — current token NOT written
    v_cache: jnp.ndarray,
    layer,  # int or int32 scalar: the layer to read
    block_tables: jnp.ndarray,  # [B, M] int32
    hist_lens: jnp.ndarray,  # [B] int32 tokens in cache (EXCLUDES current)
    scale: float,
    window: int = 0,
    sinks=None,  # [H] gpt-oss sink logits; joins the merge denominator
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
) -> jnp.ndarray:  # [B, H, D]
    """Decode attention with the current token handled OUT of the cache.

    History attention comes from the in-repo paged kernel with softmax
    stats (m, l); the current token's contribution — scores s_new = q.k_new
    and value v_new — is folded in with the flash-decoding merge:

        m_f = max(m_h, s_new)
        out = (l_h*exp(m_h-m_f)*o_h + exp(s_new-m_f)*v_new)
              / (l_h*exp(m_h-m_f) + exp(s_new-m_f))

    Why: it removes the write-before-attend dependency, so the decode
    step batches ALL layers' cache writes into one in-place Pallas append
    (ops/kv_cache_update_pallas) instead of 2L XLA scatters that each
    copy the cache (the reference's reshape_and_cache + paged-attention
    split does the same on GPU). hist_lens == 0 rows degenerate cleanly
    to out = v_new (l_h = 0, m_h = -inf).
    """
    # exactly verify_attention with a T=1 in-flight window (the merge,
    # stats kernel, window floor — and the sink's place in the merge
    # denominator — all coincide; one implementation)
    return verify_attention(
        q[:, None], k_new[:, None], v_new[:, None], k_cache, v_cache,
        layer, block_tables, hist_lens, scale, use_pallas=True,
        window=window, sinks=sinks, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales,
    )[:, 0]


def decode_attention_merged_sharded(
    q: jnp.ndarray,  # [B, H, D], H sharded over tp
    k_new: jnp.ndarray,  # [B, Hkv, D], Hkv sharded over tp
    v_new: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D], Hkv sharded over tp
    v_cache: jnp.ndarray,
    layer,  # int or int32 scalar, replicated
    block_tables: jnp.ndarray,  # [B, M] replicated
    hist_lens: jnp.ndarray,  # [B] replicated
    scale: float,
    mesh,
    window: int = 0,
    sinks=None,  # [H], sharded over tp with the heads
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page, replicated
    v_scales=None,
) -> jnp.ndarray:
    """Merged decode attention under shard_map over ``tp``.

    The whole merged computation — paged kernel over the local kv-head
    shard, s_new = q.k_new, the flash merge, and the per-head sink fold
    — is elementwise per kv-head group, so each device runs it on local
    tiles with no collectives (same head-parallel argument as
    _shard_tp)."""

    def _local(q, k_new, v_new, kc, vc, ly, bt, hl, *rest):
        rest = list(rest)
        ks = vs = s = None
        if k_scales is not None:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        if rest:
            s = rest[0]
        return decode_attention_merged(
            q, k_new, v_new, kc, vc, ly, bt, hl, scale, window=window,
            sinks=s, interpret=interpret, k_scales=ks, v_scales=vs,
        )

    scalars = (layer, block_tables, hist_lens)
    if k_scales is not None:
        scalars += (k_scales, v_scales)
    return _shard_tp(
        mesh, _local,
        arr_specs=(
            P(None, "tp", None),  # q
            P(None, "tp", None),  # k_new
            P(None, "tp", None),  # v_new
        ),
        arrs=(q, k_new, v_new),
        k_cache=k_cache, v_cache=v_cache,
        scalars=scalars, sinks=sinks,
        out_spec=P(None, "tp", None),
    )


def verify_attention(
    q: jnp.ndarray,  # [B, T, H, D] queries for T in-flight tokens per seq
    k_win: jnp.ndarray,  # [B, T, Hkv, D] their keys (rope'd, NOT in cache)
    v_win: jnp.ndarray,  # [B, T, Hkv, D]
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D] history only
    v_cache: jnp.ndarray,
    layer,  # int or int32 scalar: the layer to read (a slab: [None], 0)
    block_tables: jnp.ndarray,  # [B, M]
    hist_lens: jnp.ndarray,  # [B] tokens in cache (before the T in-flight)
    scale: float,
    use_pallas: bool = False,
    window: int = 0,
    sinks=None,  # [H] gpt-oss sink logits; joins the merge denominator
    cap: float = 0.0,  # gemma-2 softcap (XLA path only; callers gate)
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
) -> jnp.ndarray:  # [B, T, H, D]
    """Multi-token decode attention (speculative-decoding verify): T
    in-flight tokens per sequence attend cached history plus the causal
    prefix of the in-flight window, all out-of-cache.

    The Pallas path reuses the stats-emitting DECODE kernel unchanged:
    every history row precedes every in-flight position, so no causal
    masking is needed against history — the T*G query rows simply pack
    into the kernel's query-group dimension. The tiny [T, T] intra-window
    causal part is dense XLA, folded in with the same flash merge as
    decode_attention_merged.
    """
    B, T, H, D = q.shape
    Hkv = k_cache.shape[1]
    G = H // Hkv
    # a softcap routes history scoring to the XLA twin — the kernels
    # know no cap (same guard as the decode/prefill dispatchers)
    use_pallas = use_pallas and not cap
    if use_pallas:
        from .paged_attention_pallas import paged_decode_attention

        # rows ordered (hkv, t, g) so the kernel's internal
        # reshape(B, Hkv, T*G, D) lands each row on its kv head.
        # Windowed: group=G tells the kernel row r is in-flight token
        # t = r // G, so every row gets its EXACT per-row window floor
        # (hist + t + 1 - window); q_pos_offset=1 anchors token 0 one
        # past the cached history.
        qp = q.reshape(B, T, Hkv, G, D).transpose(0, 2, 1, 3, 4)
        qp = qp.reshape(B, Hkv * T * G, D)
        o_h, m_h, l_h = paged_decode_attention(
            qp, k_cache, v_cache, layer, block_tables, hist_lens,
            scale, return_stats=True, window=window, q_pos_offset=1,
            group=G, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )  # o: [B, Hkv*T*G, D]; m, l: [B, Hkv, T*G]
        o_h = o_h.reshape(B, Hkv, T, G, D).astype(jnp.float32)
        m_h = m_h.reshape(B, Hkv, T, G)
        l_h = l_h.reshape(B, Hkv, T, G)
    else:
        o_h, m_h, l_h = _history_attention_xla(
            q, k_cache[layer], v_cache[layer], block_tables, hist_lens, scale,
            window=window, cap=cap, k_scales=k_scales, v_scales=v_scales,
        )
    # intra-window causal scores [B, Hkv, T, G, T']
    qg = q.reshape(B, T, Hkv, G, D)
    s_w = softcap(jnp.einsum(
        "btkgd,bukd->bktgu",
        qg.astype(jnp.float32) * scale,
        k_win.astype(jnp.float32),
    ), cap)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]  # [T, T']
    if window > 0:  # only binds when T > window (degenerate but exact)
        causal &= (jnp.arange(T)[:, None] - jnp.arange(T)[None, :]) < window
    s_w = jnp.where(causal[None, None, :, None, :], s_w, NEG_INF)
    m_w = jnp.max(s_w, axis=-1)  # [B, Hkv, T, G]
    m_f = jnp.maximum(m_h, m_w)
    if sinks is not None:  # gpt-oss: the sink joins the normalization
        s_k = sinks.astype(jnp.float32).reshape(1, Hkv, 1, G)
        m_f = jnp.maximum(m_f, s_k)
    alpha = jnp.exp(m_h - m_f)
    p_w = jnp.exp(s_w - m_f[..., None])  # [B, Hkv, T, G, T']
    o_w = jnp.einsum("bktgu,bukd->bktgd", p_w, v_win.astype(jnp.float32))
    l_w = jnp.sum(p_w, axis=-1)
    num = (l_h * alpha)[..., None] * o_h + o_w
    den = l_h * alpha + l_w
    if sinks is not None:
        den = den + jnp.exp(s_k - m_f)
    out = num / den[..., None]  # den >= 1 term from the diagonal (u == t)
    return (
        out.transpose(0, 2, 1, 3, 4).reshape(B, T, H, D).astype(q.dtype)
    )


def verify_attention_sharded(
    q: jnp.ndarray,  # [B, T, H, D], H sharded over tp
    k_win: jnp.ndarray,  # [B, T, Hkv, D], Hkv sharded over tp
    v_win: jnp.ndarray,
    k_cache: jnp.ndarray,  # [L, Hkv, N, bs, D], Hkv sharded over tp
    v_cache: jnp.ndarray,
    layer,  # int or int32 scalar, replicated
    block_tables: jnp.ndarray,  # replicated
    hist_lens: jnp.ndarray,  # replicated
    scale: float,
    mesh,
    use_pallas: bool = True,
    window: int = 0,
    sinks=None,  # [H], sharded over tp with the heads
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page, replicated
    v_scales=None,
) -> jnp.ndarray:
    """verify_attention under shard_map over ``tp``: the paged-kernel
    history pass, the dense intra-window part, the flash merge, and the
    sink fold are all kv-head-parallel — each device computes its head
    shard on local tiles, no collectives (same argument as
    decode_attention_merged)."""

    def _local(q, k_win, v_win, kc, vc, ly, bt, hl, *rest):
        rest = list(rest)
        ks = vs = s = None
        if k_scales is not None:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        if rest:
            s = rest[0]
        return verify_attention(
            q, k_win, v_win, kc, vc, ly, bt, hl, scale,
            use_pallas=use_pallas, window=window, sinks=s,
            interpret=interpret, k_scales=ks, v_scales=vs,
        )

    scalars = (layer, block_tables, hist_lens)
    if k_scales is not None:
        scalars += (k_scales, v_scales)
    return _shard_tp(
        mesh, _local,
        arr_specs=(
            P(None, None, "tp", None),  # q
            P(None, None, "tp", None),  # k_win
            P(None, None, "tp", None),  # v_win
        ),
        arrs=(q, k_win, v_win),
        k_cache=k_cache, v_cache=v_cache,
        scalars=scalars, sinks=sinks,
        out_spec=P(None, None, "tp", None),
    )


def _history_attention_xla(
    q: jnp.ndarray,  # [B, T, H, D]
    k_cache_layer: jnp.ndarray,
    v_cache_layer: jnp.ndarray,
    block_tables: jnp.ndarray,
    hist_lens: jnp.ndarray,
    scale: float,
    window: int = 0,
    cap: float = 0.0,  # gemma-2 softcap; 0 = off
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
):
    """XLA twin of the stats-emitting kernel path: history-only attention
    with raw softmax stats (o normalized, m row max, l normalizer) in the
    [B, Hkv, T, G(, D)] layout verify_attention merges over."""
    B, T, H, D = q.shape
    M = block_tables.shape[1]
    Hkv, _, bs, _ = k_cache_layer.shape
    G = H // Hkv
    k = jnp.take(k_cache_layer, block_tables, axis=1).reshape(Hkv, B, M * bs, D)
    v = jnp.take(v_cache_layer, block_tables, axis=1).reshape(Hkv, B, M * bs, D)
    if k_scales is not None:  # per-page dequant, gathered like the pages
        ks = jnp.repeat(k_scales[block_tables], bs, axis=1)  # [B, M*bs]
        vs = jnp.repeat(v_scales[block_tables], bs, axis=1)
        k = k.astype(jnp.float32) * ks[None, :, :, None]
        v = v.astype(jnp.float32) * vs[None, :, :, None]
    qg = q.reshape(B, T, Hkv, G, D)
    s = softcap(jnp.einsum(
        "btkgd,kbsd->bktgs", qg.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
    ), cap)
    valid = jnp.arange(M * bs)[None, :] < hist_lens[:, None]  # [B, S]
    if window > 0:
        # query t sits at absolute position hist + t
        q_pos = hist_lens[:, None] + jnp.arange(q.shape[1])[None, :]  # [B, T]
        lo = (q_pos - window + 1)[:, :, None]  # [B, T, 1]
        valid_tw = valid[:, None, :] & (
            jnp.arange(M * bs)[None, None, :] >= lo
        )  # [B, T, S]
        s = jnp.where(valid_tw[:, None, :, None, :], s, NEG_INF)
    else:
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [B, Hkv, T, G]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bktgs,kbsd->bktgd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-20)[..., None]
    return o, m, l


def softcap(scores, cap: float):
    """Gemma-2 logit soft-capping: cap * tanh(s / cap); identity at 0."""
    if not cap:
        return scores
    return cap * jnp.tanh(scores / cap)


def _sink_softmax(scores, mask, sinks, Hkv, G):
    """Masked softmax whose normalization includes an optional per-head
    SINK logit (gpt-oss): the sink joins the denominator but contributes
    no value row, so attention mass can park off the real tokens.
    scores: [B, Hkv, G, S] f32; mask: [B, S]; sinks: [H] or None.
    Returns probs [B, Hkv, G, S] (rows sum to < 1 when a sink is set)."""
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)  # [B, Hkv, G, 1]
    if sinks is not None:
        s = sinks.astype(jnp.float32).reshape(1, Hkv, G, 1)
        m = jnp.maximum(m, s)
    p = jnp.exp(scores - m)
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
    if sinks is not None:
        l = l + jnp.exp(s - m)  # noqa: E741
    return p / jnp.maximum(l, 1e-30)


def decode_attention_xla(
    q: jnp.ndarray,  # [B, H, D] one new token per sequence
    k_cache_layer: jnp.ndarray,  # [Hkv, num_blocks, block_size, D]
    v_cache_layer: jnp.ndarray,  # [Hkv, num_blocks, block_size, D]
    block_tables: jnp.ndarray,  # [B, M] int32
    seq_lens: jnp.ndarray,  # [B] int32 (includes the new token)
    scale: float,
    window: int = 0,  # sliding window width; 0 = full attention
    sinks=None,  # [H] per-head sink logits (gpt-oss); None = off
    cap: float = 0.0,  # gemma-2 attention-score softcap; 0 = off
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
) -> jnp.ndarray:  # [B, H, D]
    B, H, D = q.shape
    M = block_tables.shape[1]
    Hkv, _, bs, _ = k_cache_layer.shape
    G = H // Hkv
    # gather pages -> [Hkv, B, M*bs, D] (no repeat_kv materialization:
    # grouped-query einsum keeps kv heads shared). A quantized (fp8) cache
    # casts back to the compute dtype here — XLA fuses the convert into
    # the gather read, so HBM traffic stays at the narrow dtype's bytes.
    k = jnp.take(k_cache_layer, block_tables, axis=1).reshape(Hkv, B, M * bs, D)
    v = jnp.take(v_cache_layer, block_tables, axis=1).reshape(Hkv, B, M * bs, D)
    if k_scales is not None:  # int8-with-scales: per-page dequant on read
        ks = jnp.repeat(k_scales[block_tables], bs, axis=1)  # [B, M*bs]
        vs = jnp.repeat(v_scales[block_tables], bs, axis=1)
        k = (k.astype(jnp.float32) * ks[None, :, :, None]).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs[None, :, :, None]).astype(q.dtype)
    elif k.dtype != q.dtype:
        k, v = k.astype(q.dtype), v.astype(q.dtype)
    qg = q.reshape(B, Hkv, G, D)
    scores = softcap(
        jnp.einsum("bkgd,kbtd->bkgt", qg * scale, k).astype(jnp.float32), cap
    )
    positions = jnp.arange(M * bs)[None, :]  # [1, T]
    mask = positions < seq_lens[:, None]  # [B, T]
    if window > 0:  # q position is seq_len-1; keep kv in (q-W, q]
        mask &= positions >= (seq_lens[:, None] - window)
    probs = _sink_softmax(scores, mask, sinks, Hkv, G).astype(v.dtype)
    out = jnp.einsum("bkgt,kbtd->bkgd", probs, v)
    return out.reshape(B, H, D)


def _sink_softmax_rows(scores, mask, sinks):
    """Row-wise variant of _sink_softmax for prefill layouts: scores
    [H, T, S] f32 with mask [T, S] (or [1, T, S]); sinks [H] or None."""
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)  # [H, T, 1]
    if sinks is not None:
        s = sinks.astype(jnp.float32).reshape(-1, 1, 1)
        m = jnp.maximum(m, s)
    p = jnp.exp(scores - m)
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
    if sinks is not None:
        l = l + jnp.exp(s - m)  # noqa: E741
    return p / jnp.maximum(l, 1e-30)


def prefill_attention_xla(
    q: jnp.ndarray,  # [T, H, D]
    k: jnp.ndarray,  # [T, Hkv, D] (this chunk's keys)
    v: jnp.ndarray,  # [T, Hkv, D]
    q_positions: jnp.ndarray,  # [T] absolute positions of the queries
    valid_len: jnp.ndarray,  # scalar: number of real (unpadded) tokens
    scale: float,
    window: int = 0,  # sliding window width; 0 = full attention
    sinks=None,  # [H] per-head sink logits (gpt-oss); None = off
    cap: float = 0.0,  # gemma-2 attention-score softcap; 0 = off
) -> jnp.ndarray:  # [T, H, D]
    """Causal self-attention within one (padded) prompt chunk."""
    T, H, D = q.shape
    Hkv = k.shape[1]
    k = repeat_kv(k, H // Hkv, axis=1)
    v = repeat_kv(v, H // Hkv, axis=1)
    scores = softcap(
        jnp.einsum("thd,shd->hts", q * scale, k).astype(jnp.float32), cap
    )
    causal = q_positions[:, None] >= q_positions[None, :]  # [T, T]
    if window > 0:
        causal &= (q_positions[:, None] - q_positions[None, :]) < window
    valid = jnp.arange(T)[None, :] < valid_len  # [1, T]
    mask = causal & valid
    probs = _sink_softmax_rows(scores, mask[None], sinks).astype(v.dtype)
    return jnp.einsum("hts,shd->thd", probs, v)


def chunk_attention_with_cache(
    q: jnp.ndarray,  # [T, H, D] chunk queries
    k_chunk: jnp.ndarray,  # [T, Hkv, D]
    v_chunk: jnp.ndarray,
    k_cache_layer: jnp.ndarray,  # [Hkv, num_blocks, bs, D]
    v_cache_layer: jnp.ndarray,
    block_table: jnp.ndarray,  # [M]
    history_len: jnp.ndarray,
    valid_len: jnp.ndarray,
    scale: float,
    use_pallas: bool = False,
    mesh=None,
    window: int = 0,
    sinks=None,  # [H] gpt-oss sink logits; in-kernel fold on the pallas path
    cap: float = 0.0,  # gemma-2 softcap: forces the XLA path
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
) -> jnp.ndarray:
    """Prefill dispatcher: Pallas flash kernel on TPU, XLA gather fallback.
    ``window`` (sliding attention) is honored by both paths (the Pallas
    prefill kernel masks per query row — exact, unlike the decode
    kernel's uniform floor which is exact only at T=1).

    The Pallas path requires the chunk's K/V to be ALREADY scattered into
    the cache (write-before-attend — llama.prefill's layer body does this),
    so it ignores ``k_chunk``/``v_chunk`` and reads history + chunk through
    the block table. The XLA path reads history from the cache and the
    chunk from the args. Both agree on all real rows (t < valid_len);
    padded tail rows differ but are discarded by every caller.
    """
    if use_pallas and mesh is not None and not cap:
        return paged_prefill_attention_sharded(
            q, k_cache_layer, v_cache_layer, block_table, history_len, scale,
            mesh, window=window, sinks=sinks, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    if use_pallas and not cap:
        from .paged_attention_pallas import paged_prefill_attention

        return paged_prefill_attention(
            q, k_cache_layer, v_cache_layer, block_table, history_len, scale,
            window=window, sinks=sinks, interpret=interpret,
            k_scales=k_scales, v_scales=v_scales,
        )
    return chunk_attention_with_cache_xla(
        q, k_chunk, v_chunk, k_cache_layer, v_cache_layer, block_table,
        history_len, valid_len, scale, window=window, sinks=sinks, cap=cap,
        k_scales=k_scales, v_scales=v_scales,
    )


def paged_prefill_attention_sharded(
    q: jnp.ndarray,  # [T, H, D]
    k_cache_layer: jnp.ndarray,  # [Hkv, N, bs, D], Hkv sharded over tp
    v_cache_layer: jnp.ndarray,
    block_table: jnp.ndarray,  # [M] replicated
    history_len: jnp.ndarray,  # scalar replicated
    scale: float,
    mesh,
    window: int = 0,
    sinks=None,  # [H], sharded over tp with the heads
    interpret: bool = False,
    k_scales=None,  # [N] f32 per-page, replicated
    v_scales=None,
) -> jnp.ndarray:
    """Pallas prefill kernel under shard_map over tp (see _shard_tp;
    the in-kernel sink fold is per-head, so it shards with the heads)."""
    from .paged_attention_pallas import paged_prefill_attention

    def _local(q, kc, vc, bt, hist, *rest):
        rest = list(rest)
        ks = vs = s = None
        if k_scales is not None:
            ks, vs = rest[0], rest[1]
            rest = rest[2:]
        if rest:
            s = rest[0]
        return paged_prefill_attention(
            q, kc, vc, bt, hist, scale, window=window, sinks=s,
            interpret=interpret, k_scales=ks, v_scales=vs,
        )

    scalars = (block_table, history_len)
    if k_scales is not None:
        scalars += (k_scales, v_scales)
    return _shard_tp(
        mesh, _local,
        arr_specs=(P(None, "tp", None),),  # q: heads sharded
        arrs=(q,),
        k_cache=k_cache_layer, v_cache=v_cache_layer,
        scalars=scalars, sinks=sinks,
        out_spec=P(None, "tp", None),
    )


def chunk_attention_with_cache_xla(
    q: jnp.ndarray,  # [T, H, D] chunk queries
    k_chunk: jnp.ndarray,  # [T, Hkv, D]
    v_chunk: jnp.ndarray,  # [T, Hkv, D]
    k_cache_layer: jnp.ndarray,  # [Hkv, num_blocks, bs, D]
    v_cache_layer: jnp.ndarray,
    block_table: jnp.ndarray,  # [M] this sequence's blocks
    history_len: jnp.ndarray,  # scalar: tokens already in cache
    valid_len: jnp.ndarray,  # scalar: real tokens in this chunk
    scale: float,
    window: int = 0,  # sliding window width; 0 = full attention
    sinks=None,  # [H] per-head sink logits (gpt-oss); None = off
    cap: float = 0.0,  # gemma-2 attention-score softcap; 0 = off
    k_scales=None,  # [N] f32 per-page scales (int8-with-scales cache)
    v_scales=None,
) -> jnp.ndarray:
    """Chunked-prefill attention: queries attend to cached history plus the
    causal prefix of the current chunk (enables chunked prefill and
    prefix-cache reuse without recomputing cached blocks)."""
    T, H, D = q.shape
    M = block_table.shape[0]
    Hkv, _, bs, _ = k_cache_layer.shape
    G = H // Hkv
    k_hist = jnp.take(k_cache_layer, block_table, axis=1).reshape(Hkv, M * bs, D)
    v_hist = jnp.take(v_cache_layer, block_table, axis=1).reshape(Hkv, M * bs, D)
    if k_scales is not None:  # int8-with-scales: per-page dequant on read
        ks = jnp.repeat(k_scales[block_table], bs)  # [M*bs]
        vs = jnp.repeat(v_scales[block_table], bs)
        k_hist = (k_hist.astype(jnp.float32) * ks[None, :, None]).astype(
            k_chunk.dtype
        )
        v_hist = (v_hist.astype(jnp.float32) * vs[None, :, None]).astype(
            v_chunk.dtype
        )
    elif k_hist.dtype != k_chunk.dtype:  # quantized cache: cast on read
        k_hist = k_hist.astype(k_chunk.dtype)
        v_hist = v_hist.astype(v_chunk.dtype)
    k_all = jnp.concatenate([k_hist, k_chunk.swapaxes(0, 1)], axis=1)  # [Hkv, S, D]
    v_all = jnp.concatenate([v_hist, v_chunk.swapaxes(0, 1)], axis=1)
    qg = q.reshape(T, Hkv, G, D)
    scores = softcap(
        jnp.einsum("tkgd,ksd->tkgs", qg * scale, k_all).astype(jnp.float32),
        cap,
    )
    S = M * bs + T
    q_pos = history_len + jnp.arange(T)  # absolute positions of queries
    kv_pos = jnp.concatenate([jnp.arange(M * bs), history_len + jnp.arange(T)])
    kv_is_hist = jnp.arange(S) < M * bs
    kv_valid = jnp.where(
        kv_is_hist,
        jnp.arange(S) < history_len,  # history entries below history_len
        (jnp.arange(S) - M * bs) < valid_len,  # chunk entries below valid_len
    )
    causal = q_pos[:, None] >= kv_pos[None, :]
    if window > 0:
        causal &= (q_pos[:, None] - kv_pos[None, :]) < window
    mask = causal & kv_valid[None, :]  # [T, S]
    # _sink_softmax's leading axis is batch-like — the chunk layout's T
    # rows broadcast identically ([T, 1, 1, S] mask vs [T, Hkv, G, S])
    probs = _sink_softmax(scores, mask, sinks, Hkv, G).astype(v_all.dtype)
    out = jnp.einsum("tkgs,ksd->tkgd", probs, v_all)
    return out.reshape(T, H, D)


def write_chunk_to_cache(
    cache_layer: jnp.ndarray,  # [Hkv, num_blocks, bs, D]
    chunk: jnp.ndarray,  # [T, Hkv, D]
    block_table: jnp.ndarray,  # [M]
    start_pos: jnp.ndarray,  # scalar: first absolute position of the chunk
) -> jnp.ndarray:
    """Scatter a chunk's K or V into its paged blocks. Padded tail tokens
    are routed to a sacrificial slot (last block's last position is
    overwritten by real data later or never read thanks to masking)."""
    T = chunk.shape[0]
    bs = cache_layer.shape[2]
    pos = start_pos + jnp.arange(T)
    blk = block_table[pos // bs]
    off = pos % bs
    return cache_layer.at[:, blk, off].set(
        chunk.swapaxes(0, 1).astype(cache_layer.dtype)
    )


def write_chunk_to_cache_quantized(
    cache_layer: jnp.ndarray,  # [Hkv, num_blocks, bs, D] int8
    scales: jnp.ndarray,  # [N] f32 this layer's per-page scale plane
    chunk: jnp.ndarray,  # [T, Hkv, D] full-precision K or V rows
    block_table: jnp.ndarray,  # [M]
    start_pos: jnp.ndarray,  # scalar: first absolute position of the chunk
    valid_len: jnp.ndarray,  # scalar: real (unpadded) tokens in the chunk
    qmax: float = 127.0,
    eps: float = 1e-12,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """write_chunk_to_cache for the int8-with-scales device cache.

    Grows each written page's running absmax scale (scatter-max over the
    chunk's per-row absmax), requantizes resident page content by the
    old/new ratio, then lands the rows quantized against the NEW scales.
    Padded tail rows are zeroed first so they can neither inflate a real
    page's scale nor write garbage into its tail slots (they land as
    exact zeros — never read, and harmless if overwritten later).
    Returns ``(cache_layer, scales)``."""
    T = chunk.shape[0]
    bs = cache_layer.shape[2]
    pos = start_pos + jnp.arange(T)
    blk = block_table[pos // bs]
    off = pos % bs
    real = jnp.arange(T) < valid_len
    cf = chunk.astype(jnp.float32) * real[:, None, None]
    row_amax = jnp.max(jnp.abs(cf), axis=(1, 2)) / qmax  # [T]
    new_scales = scales.at[blk].max(jnp.maximum(row_amax, eps))
    # requantize touched pages (duplicate pages — bs consecutive rows
    # share one — carry identical ratios and content: deterministic)
    r = (scales / new_scales)[blk]  # [T], <= 1; == 1 round-trips exactly
    pages = cache_layer[:, blk].astype(jnp.float32) * r[None, :, None, None]
    cache_layer = cache_layer.at[:, blk].set(
        jnp.clip(jnp.round(pages), -qmax, qmax).astype(cache_layer.dtype)
    )
    qrows = jnp.clip(
        jnp.round(cf / new_scales[blk][:, None, None]), -qmax, qmax
    )
    cache_layer = cache_layer.at[:, blk, off].set(
        qrows.swapaxes(0, 1).astype(cache_layer.dtype)
    )
    return cache_layer, new_scales


def decode_slot_indices(
    block_tables: jnp.ndarray,  # [B, M]
    positions: jnp.ndarray,  # [B]
    block_size: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(physical block, in-block offset) of each sequence's write slot —
    the one slot-mapping convention, shared by the scan-path writer below
    and the unrolled decode loop's in-place scatters (models/llama.py)."""
    blk = jnp.take_along_axis(
        block_tables, (positions // block_size)[:, None], axis=1
    )[:, 0]
    return blk, positions % block_size


def write_decode_token_to_cache(
    cache_layer: jnp.ndarray,  # [Hkv, num_blocks, bs, D]
    token_kv: jnp.ndarray,  # [B, Hkv, D]
    block_tables: jnp.ndarray,  # [B, M]
    positions: jnp.ndarray,  # [B] absolute position of the new token
) -> jnp.ndarray:
    blk, off = decode_slot_indices(block_tables, positions, cache_layer.shape[2])
    return cache_layer.at[:, blk, off].set(
        token_kv.swapaxes(0, 1).astype(cache_layer.dtype)
    )


def write_decode_token_to_cache_quantized(
    cache_layer: jnp.ndarray,  # [Hkv, num_blocks, bs, D] int8
    scales: jnp.ndarray,  # [N] f32 this layer's per-page scale plane
    token_kv: jnp.ndarray,  # [B, Hkv, D] full-precision rows
    block_tables: jnp.ndarray,  # [B, M]
    positions: jnp.ndarray,  # [B] absolute position of the new token
    qmax: float = 127.0,
    eps: float = 1e-12,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """write_decode_token_to_cache for the int8-with-scales cache: same
    scale-growth + page-requant + quantized-row-write contract as
    write_chunk_to_cache_quantized, one row per sequence. Padded batch
    rows target the trash page 0 — its scale may grow and its content is
    garbage, both harmless (page 0 is never read). Returns
    ``(cache_layer, scales)``."""
    blk, off = decode_slot_indices(
        block_tables, positions, cache_layer.shape[2]
    )
    xf = token_kv.astype(jnp.float32)  # [B, Hkv, D]
    amax = jnp.max(jnp.abs(xf), axis=(1, 2)) / qmax  # [B]
    new_scales = scales.at[blk].max(jnp.maximum(amax, eps))
    r = (scales / new_scales)[blk]  # [B]
    pages = cache_layer[:, blk].astype(jnp.float32) * r[None, :, None, None]
    cache_layer = cache_layer.at[:, blk].set(
        jnp.clip(jnp.round(pages), -qmax, qmax).astype(cache_layer.dtype)
    )
    qrows = jnp.clip(
        jnp.round(xf / new_scales[blk][:, None, None]), -qmax, qmax
    )
    cache_layer = cache_layer.at[:, blk, off].set(
        qrows.swapaxes(0, 1).astype(cache_layer.dtype)
    )
    return cache_layer, new_scales
