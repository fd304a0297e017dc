"""The plain reference of ``lfm2-8b-a1b``: a float32 ``jax.numpy`` forward
of LFM2-8B-A1B (``model_type: lfm2_moe``), read straight from
``config.json``.

No cache, no state, no kernels, no batching, no grouped matmul, under
``jax.default_matmul_precision("highest")``. The published layer
equations (``n`` = RMS norm, eps ``norm_eps``, weight multiplied): each
layer has one OPERATOR, chosen by ``layer_types[l]``, and one FFN,

    h = n_op(x)
    conv:            [B, C, u] = split3(h W_in);  v = B * u
                     c[t] = w0 * v[t-2] + w1 * v[t-1] + w2 * v[t]
                     (depthwise, causal, conv_L_cache taps, v[t<0] = 0,
                     no bias, no activation);  x += (C * c) W_out
    full_attention:  q = rope(n_q(heads(h Wq))), k = rope(n_k(heads(h Wk)))
                     (one [head_dim] weight shared by the heads, BEFORE
                     the rotary embedding), v = heads(h Wv); causal,
                     scale head_dim ** -0.5;  x += attn Wo
    g = n_ffn(x)
    l < num_dense_layers:  x += W2 (silu(W1 g) * W3 g)
    else:  s = sigmoid(g Wr) over ALL experts, float32
           S = the num_experts_per_tok largest of s + b  (b: expert_bias;
               it picks and does not weigh)
           p_e = s_e / (sum over S of s + 1e-6), times routed_scaling_factor
           x += sum over e in S of p_e . W2_e (silu(W1_e g) * W3_e g)

then one RMS norm (``embedding_norm``) and the head, TIED to the
embedding. The first ``num_hidden_layers`` entries of ``layer_types`` are
the layers that are there (the configuration cuts the depth and keeps
the published list whole). The experts are a plain loop: each computes
the rows that chose it.

Departures from the published model: the weights are the program's own
seeded draws, read in the program's tree layout (leaves stacked by kind:
``conv_ops`` [Lc, ...], ``attn_ops`` [La, ...], ``dense_layers``
[num_dense_layers, ...], ``layers`` [the rest, ...]; ``[in, out]``
matrices; taps ``[K, E]`` with the LAST tap on the current token; expert
stacks ``[L, X, in, out]``); rotary dimensions are half-split (HF's
``rotate_half``), as the program stores them. Sigmoid scoring, the
``1e-6`` and the tied head are an offline reading of the published
modelling code (``serve.json`` ``assumed``); the conv, attention and norm
equations were read from ``transformers``' ``modeling_lfm2.py``.
``taps``, if a list, receives each expert layer's ``(g, chosen)``: the
router's input and the ``[T, k]`` experts it chose. ``flaws`` names
deliberate departures, and ``zero_state_at`` is one (the conv layers
forget what came before that row, as a decode that started from a zeroed
state would): for the tests of what the check catches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

FLAWS = ("drop_oldest_tap", "bias_in_weights", "ignore_bias", "no_qk_norm")


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, cos, sin):
    """x [T, h, D]: rotate all D dims, half-split."""
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def short_conv(h, op, flaws, zero_state_at=None):
    T, E = h.shape
    b, c, u = jnp.split(h @ op["conv_in"], 3, -1)
    v = b * u
    K = op["conv_w"].shape[0]
    first = 1 if "drop_oldest_tap" in flaws else 0

    def taps(v):
        padded = jnp.concatenate([jnp.zeros((K - 1, E)), v])  # v[t<0] = 0
        return sum(op["conv_w"][k] * padded[k : k + T]
                   for k in range(first, K))

    conv = taps(v)
    if zero_state_at is not None:
        # a flaw: the rows from there on see nothing of v before them
        cut = jnp.arange(T)[:, None] >= zero_state_at
        conv = jnp.where(cut, taps(jnp.where(cut, v, 0.0)), conv)
    return (c * conv) @ op["conv_out"]


def attention(h, op, hf, cos, sin, flaws):
    T = h.shape[0]
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D, eps = hf["hidden_size"] // H, hf["norm_eps"]
    q = (h @ op["wq"]).reshape(T, H, D)
    k = (h @ op["wk"]).reshape(T, Hkv, D)
    if "no_qk_norm" not in flaws:
        q, k = rms(q, op["q_norm"], eps), rms(k, op["k_norm"], eps)
    q, k = rope(q, cos, sin), rope(k, cos, sin)
    v = (h @ op["wv"]).reshape(T, Hkv, D)
    g = H // Hkv  # query heads per kv head, consecutive
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, Hkv, g, D), k)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s * D**-0.5, -jnp.inf)
    o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
    return o.reshape(T, H * D) @ op["wo"]


def experts(g, weights, chosen, stacks, l, f32):
    """sum over each row's chosen experts of p_e . FFN_e(g): expert by
    expert, each on the rows that chose it."""
    y = jnp.zeros_like(g)
    for e in range(stacks["we_gate"].shape[1]):
        rows, slot = jnp.nonzero(chosen == e)
        if rows.size == 0:
            continue
        ge = g[rows]
        gate, up, down = (f32(stacks[k][l, e])
                          for k in ("we_gate", "we_up", "we_down"))
        f = (jax.nn.silu(ge @ gate) * (ge @ up)) @ down
        y = y.at[rows].add(weights[rows, slot][:, None] * f)
    return y


def forward(params: dict, hf: dict, tokens, taps=None, flaws=(),
            zero_state_at=None) -> jnp.ndarray:
    """tokens [T] -> logits [T, V], float32."""
    if hf.get("model_type") != "lfm2_moe" or hf.get("conv_bias") or not (
            hf.get("norm_topk_prob") and hf.get("use_expert_bias")):
        raise ValueError("not LFM2-8B-A1B's: model_type lfm2_moe, no conv "
                         "bias, renormalised weights, an expert bias")
    if not hf.get("tie_word_embeddings", True) or hf.get("rope_scaling"):
        raise ValueError("an untied head and rope scaling are not "
                         "LFM2-8B-A1B's")
    assert set(flaws) <= set(FLAWS), flaws
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    L, kd = hf["num_hidden_layers"], hf["num_dense_layers"]
    D, eps = hf["hidden_size"] // hf["num_attention_heads"], hf["norm_eps"]
    top = hf["num_experts_per_tok"]
    inv = 1.0 / (float(hf["rope_theta"])
                 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    lay = params["layers"]
    assert lay["moe_gate"].shape == (L - kd, hf["hidden_size"],
                                     hf["num_experts"])
    small = lambda grp, i: {  # noqa: E731 — layer i's leaves but the stacks
        k: f32(v[i]) for k, v in grp.items() if not k.startswith("we_")}
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        n_conv = n_attn = 0
        for l, kind in enumerate(hf["layer_types"][:L]):
            if kind == "conv":
                op = small(params["conv_ops"], n_conv)
                n_conv += 1
                x = x + short_conv(rms(x, op["attn_norm"], eps), op, flaws,
                                   zero_state_at)
            elif kind == "full_attention":
                op = small(params["attn_ops"], n_attn)
                n_attn += 1
                x = x + attention(rms(x, op["attn_norm"], eps), op, hf,
                                  cos, sin, flaws)
            else:
                raise ValueError(f"layer_types[{l}] = {kind!r}")
            if l < kd:
                ffn = small(params["dense_layers"], l)
                g = rms(x, ffn["mlp_norm"], eps)
                x = x + (jax.nn.silu(g @ ffn["w_gate"])
                         * (g @ ffn["w_up"])) @ ffn["w_down"]
                continue
            ffn = small(lay, l - kd)
            g = rms(x, ffn["mlp_norm"], eps)
            s = jax.nn.sigmoid(g @ ffn["moe_gate"])
            biased = s + ffn["moe_gate_bias"]
            _, chosen = jax.lax.top_k(
                s if "ignore_bias" in flaws else biased, top)
            picked = jnp.take_along_axis(
                biased if "bias_in_weights" in flaws else s, chosen, -1)
            weights = (picked / (picked.sum(-1, keepdims=True) + 1e-6)
                       * hf["routed_scaling_factor"])
            if taps is not None:
                taps.append((g, chosen))
            x = x + experts(g, weights, chosen, lay, l - kd, f32)
        x = rms(x, f32(params["final_norm"]), eps)
        return x @ f32(params["embed"]).T
