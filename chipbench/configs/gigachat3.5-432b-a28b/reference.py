"""The plain reference of ``gigachat3.5-432b-a28b``: a float32
``jax.numpy`` forward of GigaChat3.5-432B-A28B (``model_type:
gigachat3_5``), read straight from ``config.json``.

No cache, no state carried between calls, no chunked form, no kernels, no
batching, no grouped matmul, under
``jax.default_matmul_precision("highest")``. With ``n`` the model's norm
(below), each layer has one OPERATOR and one FFN, each between a norm
before and a norm after:

    x += n_post(op(n_pre(x)));   x += n_ffn_post(ffn(n_ffn_pre(x)))

    layer l in full_attention_layers -- gated latent attention (MLA):
        c_q = n(h Wq_a);  [q_nope, q_pe] = heads(c_q Wq_b)
        [c, k_pe] = h Wkv_a;  c = n(c);  [k_nope, v] = heads(c Wkv_b)
        q = [q_nope, rope(q_pe)], k = [k_nope, rope(k_pe) for every head]
        full causal softmax over the uncompressed k, v, scale
        qk_head_dim^-0.5 * (0.1 mscale_all_dim ln(factor) + 1)^2 (YaRN)
        op = (attn * sigmoid(h W_g)) Wo                  (gated_attention)
    every other layer -- the gated delta rule (Gated DeltaNet):
        [q, k, v, z] = h W_qkvz;  [b, a] = h W_ba
        [q, k, v] = silu(depthwise causal conv over linear_conv_kernel_dim
                         taps of [q, k, v]), rows before the first = 0
        q = l2norm(q) * Dk^-0.5, k = l2norm(k) per head; key head j
        serves value heads [j r, (j + 1) r), r = Hv / Hk
        beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
        per value head, token by token (lax.scan), S [Dk, Dv] from 0:
            S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t)
            S <- S + k_t d^T;  o_t = S^T q_t
        op = (rms(o_t, eps linear_attn_o_norm_eps) * (1 + w_o)
              * linear_sigmoid_gate_scale * sigmoid(z)) W_out
    l < first_k_dense_replace:  ffn = W_d glu(g W_g, g W_u)
    else:  s = sigmoid(g Wr) over ALL n_routed_experts published, float32
           S = the num_experts_per_tok largest of s + bias
           p_e = routed_scaling_factor * s_e / (sum over S of s + 1e-20)
           ffn = sum over e in S AND HELD HERE of p_e . expert_e(g)
                 + shared(g)
    glu(gate, up) = silu(min(gate, swiglu_limit))
                    * clip(up, -swiglu_limit, swiglu_limit)

then the norm and the head (untied).

One chip's share (``serve.json``): ``config.json``'s ``expert_share``
says which of the published experts the weights hold (``n_routed_experts``
of them from ``first``); the experts on other chips are NOT stood in
for, here as in the program: their part of the sum is left out. The
vocabulary is the slice ``vocab_size`` gives. The first
``num_hidden_layers`` layers are the ones that are there.

ASSUMED -- what ``config.json``'s keys alone do not settle (the modelling
file is not on this machine); each is one line of one function here:

    router scoring        no scoring_func / topk_method key: sigmoid scores
                          and a selection-only bias, the family's
                          (GigaChat3.1's catalog row; DeepSeek-V3)  [route]
    gated_attention       the gate's input is the layer's normed input h,
                          its width num_attention_heads * v_head_dim, applied
                          before Wo (arXiv:2505.06708)               [mla]
    norm_type             ZeroCenteredGatedNorm + layernorm_gating_weight 2:
                          scale = 2 * sigmoid(w), 1 at w = 0          [n]
    q_a / kv_a norms      the same norm class                        [mla]
    layernorm_type        pre_post: a norm before and after each sublayer
    swiglu_limit          gate clamped from above, up on both sides  [glu]
    linear_gating_type    gated_rmsnorm_sigmoid_zero_centered: the output
                          norm's weight is (1 + w), the gate
                          linear_sigmoid_gate_scale * sigmoid(z) [delta_net]
    l2norm eps            1e-6, added under the root           [delta_net]
    state dtype           the delta rule's S in float32
    use_mla_scaling_factor  the YaRN mscale^2 on the softmax scale [mla]
    rope_interleave       a checkpoint convention; seeded weights are drawn
                          de-interleaved, rotation is half-split

Departures from the published model: the weights are the program's own
seeded draws in its tree layout (``linear_ops`` [Ll, ...], ``attn_ops``
[La, ...], ``dense_layers``, ``layers``; ``[in, out]`` matrices; taps
``[K, C]`` with the LAST tap on the current token). ``flaws`` names
deliberate departures, for the tests of what the check catches.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

FLAWS = ("plain_norm_scale", "no_attn_gate", "no_clamp", "no_decay",
         "all_experts_here")


def n(x, w, hf, flaws=()):
    """ZeroCenteredGatedNorm: RMS norm, scale gating_weight * sigmoid(w)."""
    scale = w if "plain_norm_scale" in flaws else (
        hf["layernorm_gating_weight"] * jax.nn.sigmoid(w))
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + hf["rms_norm_eps"]) * scale


def glu(gate, up, hf, flaws=()):
    lim = hf.get("swiglu_limit")
    if lim and "no_clamp" not in flaws:
        gate, up = jnp.minimum(gate, lim), jnp.clip(up, -lim, lim)
    return jax.nn.silu(gate) * up


def rope(x, cos, sin):
    """x [T, h, D]: rotate all D dims, half-split."""
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def yarn(hf, T):
    """(cos, sin [T, R/2], softmax scale): DeepSeek's YaRN over the rope
    dims, with the mscale ratio on cos / sin and mscale_all_dim^2 on the
    softmax scale."""
    D, base, rs = hf["qk_rope_head_dim"], hf["rope_theta"], hf["rope_scaling"]
    inv = 1.0 / (base ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr(n_rot):
        return D * math.log(orig / (n_rot * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    inv = (inv / factor) * ramp + inv * (1.0 - ramp)

    def msc(m):
        return 0.1 * m * math.log(factor) + 1.0 if m and factor > 1 else 1.0

    ratio = msc(rs.get("mscale", 1.0)) / msc(rs.get("mscale_all_dim", 0.0))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    scale = (hf["qk_nope_head_dim"] + D) ** -0.5
    if hf.get("use_mla_scaling_factor", True):
        scale *= msc(rs.get("mscale_all_dim", 0.0)) ** 2
    return jnp.cos(ang) * ratio, jnp.sin(ang) * ratio, scale


def mla(h, op, hf, flaws=()):
    T = h.shape[0]
    H, dn, dr, dv = (hf["num_attention_heads"], hf["qk_nope_head_dim"],
                     hf["qk_rope_head_dim"], hf["v_head_dim"])
    C = hf["kv_lora_rank"]
    cos, sin, scale = yarn(hf, T)
    q = (n(h @ op["wq_a"], op["q_norm"], hf, flaws) @ op["wq_b"]).reshape(
        T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], cos, sin)], -1)
    kv = h @ op["wkv_a"]
    c = n(kv[:, :C], op["kv_norm"], hf, flaws)
    k_pe = rope(kv[:, None, C:], cos, sin)  # [T, 1, dr], shared by heads
    kvb = (c @ op["wkv_b"]).reshape(T, H, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_pe, (T, H, dr))], -1)
    s = jnp.einsum("thd,shd->hts", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kvb[..., dn:])
    o = o.reshape(T, H * dv)
    if hf.get("gated_attention") and "no_attn_gate" not in flaws:
        o = o * jax.nn.sigmoid(h @ op["attn_gate"])
    return o @ op["wo"]


def delta_net(h, op, hf, flaws=()):
    T = h.shape[0]
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    K, C = hf["linear_conv_kernel_dim"], 2 * Hk * Dk + Hv * Dv
    qkvz = h @ op["lin_qkvz"]
    mix, z = qkvz[:, :C], qkvz[:, C:]
    ba = h @ op["lin_ba"]
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), mix])
    mix = jax.nn.silu(sum(op["lin_conv_w"][j] * padded[j : j + T]
                          for j in range(K)))
    q = mix[:, : Hk * Dk].reshape(T, Hk, Dk)
    k = mix[:, Hk * Dk : 2 * Hk * Dk].reshape(T, Hk, Dk)
    v = mix[:, 2 * Hk * Dk :].reshape(T, Hv, Dv)

    def l2norm(a):
        return a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2norm(q) * Dk ** -0.5, Hv // Hk, axis=1)  # [T, Hv, Dk]
    k = jnp.repeat(l2norm(k), Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(op["lin_A_log"]) * jax.nn.softplus(
        ba[:, Hv:] + op["lin_dt_bias"])
    if "no_decay" in flaws:
        g = jnp.zeros_like(g)

    def step(S, xs):  # S [Hv, Dk, Dv]
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * d[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((Hv, Dk, Dv)), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + hf["linear_attn_o_norm_eps"])
    o = o * (1.0 + op["lin_o_norm"])
    o = o * hf["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(
        z.reshape(T, Hv, Dv))
    return o.reshape(T, Hv * Dv) @ op["lin_out"]


def route(g, ffn, hf):
    """(chosen experts [T, k], their weights [T, k]) over the PUBLISHED
    experts."""
    s = jax.nn.sigmoid(g @ ffn["moe_gate"])
    _, chosen = jax.lax.top_k(s + ffn["moe_gate_bias"],
                              hf["num_experts_per_tok"])
    p = jnp.take_along_axis(s, chosen, 1)
    if hf.get("norm_topk_prob", True):
        p = p / (jnp.sum(p, -1, keepdims=True) + 1e-20)
    return chosen, p * hf["routed_scaling_factor"]


def expert_ffn(g, ffn, hf, flaws=(), share=None):
    """The expert layer's output. ``share`` = (first, held) overrides the
    config's ``expert_share``: the part that THOSE experts give (a test
    adds the shares up); the shared expert is part of every share."""
    chosen, p = route(g, ffn, hf)
    pub = hf.get("expert_share")
    first, held = share or (
        (pub["first"], hf["n_routed_experts"]) if pub
        else (0, hf["n_routed_experts"]))
    if "all_experts_here" in flaws:
        first = 0
    out = jnp.zeros_like(g)
    for e in range(held):  # the stack's expert e is published expert first + e
        w = jnp.sum(jnp.where(chosen == first + e, p, 0.0), -1, keepdims=True)
        y = glu(g @ ffn["we_gate"][e], g @ ffn["we_up"][e], hf, flaws)
        out = out + w * (y @ ffn["we_down"][e])
    if "shared_gate" in ffn:
        out = out + glu(g @ ffn["shared_gate"], g @ ffn["shared_up"],
                        hf, flaws) @ ffn["shared_down"]
    return out


def forward(params, hf, tokens, flaws=()):
    """tokens [T] -> logits [T, V], float32."""
    f32 = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jnp.asarray(a, jnp.float32), t)
    L, kd = hf["num_hidden_layers"], hf.get("first_k_dense_replace", 0)
    full = [l for l in hf["full_attention_layers"] if l < L]
    pick = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[jnp.asarray(tokens)]
        for l in range(L):
            if l in full:
                op = f32(pick(params["attn_ops"], full.index(l)))
                fn = mla
            else:
                li = l - sum(1 for a in full if a < l)
                op = f32(pick(params["linear_ops"], li))
                fn = delta_net
            h = n(x, op["attn_norm"], hf, flaws)
            x = x + n(fn(h, op, hf, flaws), op["attn_post_norm"], hf, flaws)
            ffn = f32(pick(params["dense_layers"], l) if l < kd
                      else pick(params["layers"], l - kd))
            g = n(x, ffn["mlp_norm"], hf, flaws)
            if l < kd:
                y = glu(g @ ffn["w_gate"], g @ ffn["w_up"], hf,
                        flaws) @ ffn["w_down"]
            else:
                y = expert_ffn(g, ffn, hf, flaws)
            x = x + n(y, ffn["mlp_post_norm"], hf, flaws)
        x = n(x, f32(params["final_norm"]), hf, flaws)
        return x @ f32(params["lm_head"])
