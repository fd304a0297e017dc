"""The plain reference of ``olmo2-1b``: a float32 ``jax.numpy`` forward of
OLMo-2 (``Olmo2ForCausalLM``), read straight from ``config.json``.

No cache, no kernels, no batching, under
``jax.default_matmul_precision("highest")``. The published layer
equations: no input norms; RMS norms over the full-width q and k
projections before the heads are split; rotary embedding on every head
dimension; causal attention; an RMS norm on each sublayer's OUTPUT before
the residual add,

    x += n(Wo . attn(rope(n(Wq x)), rope(n(Wk x)), Wv x))
    x += n(Wd . (silu(Wg x) * Wu x))

then a final RMS norm and an untied head.

Departure from the published model: the weights are the program's own
seeded draws, read in the program's tree layout (stacked ``[L, ...]``
leaves, ``[in, out]`` matrices); rotary dimensions are half-split (HF's
``rotate_half``), as the program stores them.
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


def forward(params: dict, hf: dict, tokens) -> jnp.ndarray:
    """tokens [T] -> logits [T, V], float32."""
    if hf.get("rope_scaling") or hf.get("tie_word_embeddings"):
        raise ValueError("rope scaling and a tied head are not OLMo-2's")
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    tokens = jnp.asarray(tokens, jnp.int32)
    T = tokens.shape[0]
    H = hf["num_attention_heads"]
    Hkv = hf.get("num_key_value_heads", H)
    D, eps = hf["hidden_size"] // H, hf["rms_norm_eps"]
    inv = 1.0 / (float(hf["rope_theta"])
                 ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    causal = jnp.tril(jnp.ones((T, T), bool))
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        lay = params["layers"]
        for l in range(lay["wq"].shape[0]):
            lp = {k: f32(v[l]) for k, v in lay.items()}
            q = rms(x @ lp["wq"], lp["q_norm"], eps)
            k = rms(x @ lp["wk"], lp["k_norm"], eps)
            q = rope(q.reshape(T, H, D), cos, sin)
            k = rope(k.reshape(T, Hkv, D), cos, sin)
            v = (x @ lp["wv"]).reshape(T, Hkv, D)
            g = H // Hkv  # query heads per kv head, consecutive
            s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, Hkv, g, D), k)
            s = jnp.where(causal[None, None], s * D**-0.5, -jnp.inf)
            o = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(s, -1), v)
            o = o.reshape(T, H * D) @ lp["wo"]
            x = x + rms(o, lp["attn_post_norm"], eps)
            f = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
            x = x + rms(f, lp["mlp_post_norm"], eps)
        return rms(x, f32(params["final_norm"]), eps) @ f32(params["lm_head"])
