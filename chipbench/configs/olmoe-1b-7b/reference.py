"""The plain reference of ``olmoe-1b-7b``: a float32 ``jax.numpy`` forward
of OLMoE (``OlmoeForCausalLM``), read straight from ``config.json``.

No cache, no kernels, no batching, no grouped matmul, under
``jax.default_matmul_precision("highest")``. The published layer
equations: pre-norm residual blocks; RMS norms over the full-width q and
k projections before the heads are split; rotary embedding on every head
dimension; causal attention; a router that takes the softmax over ALL
experts in float32, keeps the ``num_experts_per_tok`` largest and uses
their probabilities as they are (``norm_topk_prob`` false: no
renormalisation); each expert a SwiGLU of width ``intermediate_size``; no
shared expert,

    x += Wo . attn(rope(n(Wq n(x))), rope(n(Wk n(x))), Wv n(x))
    h  = n(x);  p = softmax(h Wr);  S = the k largest of p
    x += sum over e in S of p_e . Wd_e (silu(Wg_e h) * Wu_e h)

then a final RMS norm and an untied head. The experts are a plain loop:
each computes the rows that chose it.

Departures from the published model: the weights are the program's own
seeded draws, read in the program's tree layout (stacked ``[L, ...]``
leaves, ``[in, out]`` matrices, expert stacks ``[L, X, in, out]``);
rotary dimensions are half-split (HF's ``rotate_half``), as the program
stores them; ``clip_qkv`` is null in the published file and a value is
refused. ``taps``, if a list, receives each layer's ``(h, experts)``:
the router's input and the ``[T, k]`` experts it chose.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, cos, sin):
    """x [T, h, D]: rotate all D dims, half-split."""
    x1, x2 = x[..., : x.shape[-1] // 2], x[..., x.shape[-1] // 2:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def experts(h, probs, chosen, lay, l, f32):
    """sum over each row's chosen experts of p_e . FFN_e(h): expert by
    expert, each on the rows that chose it."""
    y = jnp.zeros_like(h)
    for e in range(probs.shape[-1]):
        rows = jnp.nonzero(jnp.any(chosen == e, -1))[0]
        if rows.size == 0:
            continue
        he = h[rows]
        f = (jax.nn.silu(he @ f32(lay["we_gate"][l, e]))
             * (he @ f32(lay["we_up"][l, e]))) @ f32(lay["we_down"][l, e])
        y = y.at[rows].add(probs[rows, e][:, None] * f)
    return y


def forward(params: dict, hf: dict, tokens, taps=None) -> jnp.ndarray:
    """tokens [T] -> logits [T, V], float32."""
    if (hf.get("rope_scaling") or hf.get("tie_word_embeddings")
            or hf.get("clip_qkv") is not None or hf.get("norm_topk_prob")):
        raise ValueError("rope scaling, a tied head, clip_qkv and "
                         "renormalised router weights are not OLMoE-1B-7B's")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    H = hf["num_attention_heads"]
    Hkv = hf.get("num_key_value_heads", H)
    D, eps = hf["hidden_size"] // H, hf["rms_norm_eps"]
    top = hf["num_experts_per_tok"]
    inv = 1.0 / (float(hf["rope_theta"])
                 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    causal = jnp.tril(jnp.ones((T, T), bool))
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        lay = params["layers"]
        assert lay["moe_gate"].shape[-1] == hf["num_experts"]
        for l in range(lay["wq"].shape[0]):
            lp = {k: f32(v[l]) for k, v in lay.items()
                  if not k.startswith("we_")}
            h = rms(x, lp["attn_norm"], eps)
            q = rms(h @ lp["wq"], lp["q_norm"], eps)
            k = rms(h @ lp["wk"], lp["k_norm"], eps)
            q = rope(q.reshape(T, H, D), cos, sin)
            k = rope(k.reshape(T, Hkv, D), cos, sin)
            v = (h @ lp["wv"]).reshape(T, Hkv, D)
            g = H // Hkv  # query heads per kv head, consecutive
            s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, Hkv, g, D), k)
            s = jnp.where(causal[None, None], s * D**-0.5, -jnp.inf)
            o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
            x = x + o.reshape(T, H * D) @ lp["wo"]
            h = rms(x, lp["mlp_norm"], eps)
            probs = jax.nn.softmax(h @ lp["moe_gate"], -1)
            _, chosen = jax.lax.top_k(probs, top)
            if taps is not None:
                taps.append((h, chosen))
            x = x + experts(h, probs, chosen, lay, l, f32)
        return rms(x, f32(params["final_norm"]), eps) @ f32(params["lm_head"])
