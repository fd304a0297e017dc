"""Multi-Latent Attention (DeepSeek-V2/V3/R1) over the paged cache.

The reference serves DeepSeek through its wrapped engines (README
workloads; the vLLM patch carries a deepseek_v2 tweak, patch:3548-3560).
Here MLA is TPU-native and built around the COMPRESSED cache from the
start:

  * per token the cache stores the kv_lora_rank latent ``c_kv`` (k-cache
    slot) and the head-shared rotated ``k_pe`` (v-cache slot, in whole
    128-lane rows: ``llama.rope_lanes``, 64 values in 128 lanes, upper
    lanes zero, so that the decode kernel can cut its pages out of the
    pool itself) — a single-"head" paged layout ``[L, 1, N, bs, D]``
    that rides the existing block tables / allocator / offload /
    transfer machinery unchanged (the two caches just have different
    trailing dims);
  * attention runs ABSORBED: q_nope is folded through the kv_b
    up-projection once per layer (``q_eff = q_nope @ w_kc``), scores are
    ``q_eff . c_kv + q_pe . k_pe`` against raw latents, and the output
    latent folds back through ``w_vc`` — no per-token reconstruction of
    full K/V, so HBM reads per step stay at
    ``kv_lora_rank + rope_lanes`` values/token (the entire point of
    MLA; 640 vs 2*128*Hkv for V3);
  * the XLA paths here (dense einsums over gathered pages, MQA-shaped:
    one shared KV stream, H query heads) are the correctness baseline
    and serve CPU/meshes; single-host TPU decode runs the Pallas latent
    kernel + merged one-write append (ops/mla_attention_pallas) — no
    per-step page gather, one cache write for all layers.

RoPE uses DeepSeek's YaRN variant over the qk_rope dims, with the
mscale cos/sin correction and the mscale_all_dim softmax-scale
correction (ModelConfig.mla_softmax_scale).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ModelConfig, is_yarn, yarn_mscale

NEG_INF = -1e30


def mla_rope_freqs(cfg: ModelConfig) -> tuple[jnp.ndarray, float]:
    """(inv_freq over qk_rope_head_dim, cos/sin mscale ratio).

    YaRN per DeepSeek-V2: interpolate low-frequency dims by ``factor``,
    extrapolate high-frequency dims, linear-ramp between the correction
    range derived from beta_fast/beta_slow."""
    D = cfg.qk_rope_head_dim
    base = cfg.rope_theta
    inv = 1.0 / (base ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    rs = cfg.rope_scaling or {}
    if is_yarn(rs):
        factor = rs.get("factor", 1.0)
        beta_fast = rs.get("beta_fast", 32)
        beta_slow = rs.get("beta_slow", 1)
        orig = rs.get("original_max_position_embeddings", 4096)

        def corr_dim(n_rot):
            return (D * math.log(orig / (n_rot * 2 * math.pi))) / (
                2 * math.log(base)
            )

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), D - 1)
        ramp = jnp.clip(
            (jnp.arange(D // 2, dtype=jnp.float32) - low)
            / max(high - low, 0.001),
            0.0, 1.0,
        )
        extrap_mask = 1.0 - ramp
        inv = (inv / factor) * (1 - extrap_mask) + inv * extrap_mask
        msc = yarn_mscale(factor, rs.get("mscale", 1.0)) / yarn_mscale(
            factor, rs.get("mscale_all_dim", 0.0) or 0.0
        )
        return inv, msc
    return inv, 1.0


def rope_rotate(x: jnp.ndarray, positions: jnp.ndarray,
                inv_freq: jnp.ndarray, mscale: float = 1.0) -> jnp.ndarray:
    """Half-split rotation (same convention as llama.apply_rope) over the
    trailing rope dims; x: [..., T, Hx, Dr], positions: [..., T].

    DeepSeek checkpoints store rope dims INTERLEAVED (pairs); weights.py
    de-interleaves q_b/kv_a at load so runtime rotation stays the fast
    half-split form."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.cos(angles)[..., None, :] * mscale
    sin = jnp.sin(angles)[..., None, :] * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _wkv_b_parts(lp: dict, cfg: ModelConfig):
    """Split the kv_b up-projection [kv_lora, H*(nope+v)] into
    w_kc [kv_lora, H, nope] and w_vc [kv_lora, H, v]."""
    H, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    w = lp["wkv_b"]
    if isinstance(w, dict):  # quantized {"q", "s"}: dequant for the fold
        w = w["q"].astype(jnp.bfloat16) * w["s"].astype(jnp.bfloat16)
    w = w.reshape(w.shape[0], H, dn + dv)
    return w[:, :, :dn], w[:, :, dn:]


def mla_q_and_latent(lp: dict, cfg: ModelConfig, x: jnp.ndarray,
                     positions: jnp.ndarray, inv_freq: jnp.ndarray,
                     mscale: float):
    """Shared projection head for prefill, decode, and verify.

    x: [..., E] with arbitrary leading batch dims; positions broadcasts
    against them (prefill [T]/[T,E], decode [B]/[B,E], verify [B,T]/
    [B,T,E]).
    Returns (q_eff [..., H, C], q_pe [..., H, Rl], c_kv [..., C],
    k_pe [..., Rl])
    with C = kv_lora_rank and the rotated qk_rope_head_dim values in
    Rl = llama.rope_lanes lanes, the rope pool's row (upper lanes zero:
    they add nothing to q_pe . k_pe). q_eff is the ABSORBED query
    (q_nope @ w_kc) scoring directly against cache latents."""
    from ..ops.mla_attention_pallas import rope_to_lanes
    from .llama import _mm, model_norm, rope_lanes

    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        q = _mm(model_norm(_mm(x, lp["wq_a"]), lp["q_norm"], cfg),
                lp["wq_b"])
    else:
        q = _mm(x, lp["wq"])
    q = q.reshape(x.shape[:-1] + (H, dn + dr))
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = rope_rotate(q_pe, positions, inv_freq, mscale)

    kv = _mm(x, lp["wkv_a"])  # [T, C + R]
    c_kv = model_norm(kv[..., : cfg.kv_lora_rank], lp["kv_norm"], cfg)
    k_pe = kv[..., cfg.kv_lora_rank:]
    k_pe = rope_rotate(k_pe[..., None, :], positions, inv_freq,
                       mscale)[..., 0, :]
    q_pe = rope_to_lanes(q_pe, rope_lanes(cfg))
    k_pe = rope_to_lanes(k_pe, rope_lanes(cfg))

    w_kc, _ = _wkv_b_parts(lp, cfg)
    # fold q_nope through the k up-projection: [T, H, dn] x [C, H, dn]
    q_eff = jnp.einsum(
        "...hd,chd->...hc", q_nope.astype(jnp.float32),
        w_kc.astype(jnp.float32),
    ).astype(x.dtype)
    return q_eff, q_pe, c_kv, k_pe


def _o_proj(lp: dict, cfg: ModelConfig, out_lat: jnp.ndarray) -> jnp.ndarray:
    """Fold the attention's latent output back through w_vc and flatten
    heads: [.., H, C] f32 -> [.., H*v_head_dim]."""
    _, w_vc = _wkv_b_parts(lp, cfg)
    o = jnp.einsum("...hc,chd->...hd", out_lat, w_vc.astype(jnp.float32))
    return o.reshape(o.shape[:-2] + (-1,))


def o_proj_gated(lp: dict, cfg: ModelConfig, out_lat: jnp.ndarray,
                 h: jnp.ndarray) -> jnp.ndarray:
    """``_o_proj``, then GigaChat 3.5's output gate where the layer has
    one (``attn_gate``): the attention rows times sigmoid(h W_g), ``h``
    the layer's normed input, before the output projection."""
    from .llama import _mm

    o = _o_proj(lp, cfg, out_lat)
    if "attn_gate" in lp:
        o = o * jax.nn.sigmoid(_mm(h, lp["attn_gate"]).astype(jnp.float32))
    return o


def mla_prefill_attention_xla(
    q_eff: jnp.ndarray,  # [T, H, C]
    q_pe: jnp.ndarray,  # [T, H, R]
    c_cache_layer: jnp.ndarray,  # [1, N, bs, C] — chunk ALREADY written
    pe_cache_layer: jnp.ndarray,  # [1, N, bs, R]
    block_table: jnp.ndarray,  # [M]
    history_len: jnp.ndarray,  # scalar
    valid_len: jnp.ndarray,  # scalar: real tokens in this chunk
    scale: float,
) -> jnp.ndarray:  # [T, H, C] latent output (caller folds through w_vc)
    """Write-before-attend chunked prefill over latents: every query row
    attends cached history plus the causal prefix of its own chunk, all
    read back through the block table."""
    T, H, C = q_eff.shape
    M = block_table.shape[0]
    bs = c_cache_layer.shape[2]
    ck = jnp.take(c_cache_layer[0], block_table, axis=0).reshape(M * bs, C)
    kp = jnp.take(pe_cache_layer[0], block_table, axis=0).reshape(M * bs, -1)
    s = (
        jnp.einsum("thc,sc->ths", q_eff.astype(jnp.float32) * scale,
                   ck.astype(jnp.float32))
        + jnp.einsum("thr,sr->ths", q_pe.astype(jnp.float32) * scale,
                     kp.astype(jnp.float32))
    )
    q_pos = history_len + jnp.arange(T)  # absolute positions of queries
    s_pos = jnp.arange(M * bs)
    valid = s_pos[None, :] <= q_pos[:, None]  # causal incl. self
    valid &= s_pos[None, :] < history_len + valid_len  # real rows only
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("ths,sc->thc", p, ck.astype(jnp.float32))


def mla_decode_attention_xla(
    q_eff: jnp.ndarray,  # [B, H, C]
    q_pe: jnp.ndarray,  # [B, H, R]
    c_cache_layer: jnp.ndarray,  # [1, N, bs, C] — current token written
    pe_cache_layer: jnp.ndarray,  # [1, N, bs, R]
    block_tables: jnp.ndarray,  # [B, M]
    seq_lens: jnp.ndarray,  # [B] incl. the current token
    scale: float,
) -> jnp.ndarray:  # [B, H, C] latent output
    B, H, C = q_eff.shape
    M = block_tables.shape[1]
    bs = c_cache_layer.shape[2]
    ck = jnp.take(c_cache_layer[0], block_tables, axis=0).reshape(B, M * bs, C)
    kp = jnp.take(pe_cache_layer[0], block_tables, axis=0).reshape(
        B, M * bs, -1
    )
    s = (
        jnp.einsum("bhc,bsc->bhs", q_eff.astype(jnp.float32) * scale,
                   ck.astype(jnp.float32))
        + jnp.einsum("bhr,bsr->bhs", q_pe.astype(jnp.float32) * scale,
                     kp.astype(jnp.float32))
    )
    mask = jnp.arange(M * bs)[None, :] < seq_lens[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, ck.astype(jnp.float32))
