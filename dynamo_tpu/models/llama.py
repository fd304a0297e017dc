"""Llama-family model: pure-JAX functional forward over a paged KV cache.

Covers Llama 2/3, DeepSeek-R1-Distill-Llama, Mistral, Qwen2 (bias), and
Gemma (GeGLU, (1+w) norms folded at load, sqrt(E)-scaled embeddings) — the
dense decoder families the reference serves through vLLM (README model
list). Design is TPU-first, not a port:

  * parameters are a pytree with layers **stacked on a leading axis** and
    the layer loop is ``lax.scan`` — one traced layer body, fast XLA
    compiles even at 80 layers;
  * the KV cache is two arrays ``[L, Hkv, num_blocks, block_size, D]``
    (head-major so each (head, page) is one contiguous DMA tile)
    threaded through scan functionally and **donated** by the engine's jit,
    so XLA updates it in place in HBM;
  * attention reads the cache through block tables (paged), masks do the
    ragged bookkeeping — all shapes static;
  * sharding is annotation-only: the engine places params/cache with
    NamedSharding over a ("dp", "tp") mesh and jit propagates (XLA SPMD
    inserts the collectives the reference gets from NCCL/Ray).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import attention as att
from .config import ModelConfig, yarn_mscale


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.bfloat16}[
        str(cfg.dtype)
    ]


# ---------------- parameter init / structure ----------------


def _init_layer_group(cfg: ModelConfig, key: jax.Array, L: int,
                      moe: bool) -> dict:
    """Stacked [L, ...] layer leaves for one homogeneous group (attention
    + one FFN kind). DeepSeek's first_k_dense_replace makes the model
    heterogeneous, so params carry up to two groups (``dense_layers``
    then ``layers``) — each scanned separately.

    Mellum 2 and afmoe (``cfg.seeded_by_layer``, families new with PRs 47
    and 51: no served draw to keep) are drawn a layer and a matrix at a
    time (``_draw_stacked``: the float32 draw of a whole stack of 64
    experts is 4.2 GB beside the weights it joins; one layer's matrix of
    afmoe's 128 is 1.07 GB), and their experts' down-projections at an
    eighth: the marginal one of 8 renormalised experts carries an eighth
    of the layer's output, and a random router's choices flip under bf16
    rounding (as LFM2's, PERF.md section 6, PR 33). Where a norm FOLLOWS
    the expert layer (afmoe) they are drawn at a sixteenth: the norm
    scales the sublayer back to the residual's size, so the eighth only
    shrinks the routed experts against the shared one, and a flipped
    choice still moved a logprob by up to 0.099 at the published widths
    (0.041 at a sixteenth, 0.029 with no routed experts at all: my chip
    run, PR 51, PERF.md section 6). Such a family's selection bias is
    drawn non-zero, so that a forward that drops it or lets it weigh
    shows."""
    dt = _dtype(cfg)
    by_layer = cfg.seeded_by_layer
    E, H, Hkv, D, F, V = (
        cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
        cfg.intermediate_size, cfg.vocab_size,
    )
    keys = jax.random.split(key, 12)

    def layer_stack(k, shape, scale=0.02):
        if by_layer:
            return _draw_stacked(k, (L,) + shape, scale, dt)
        return (
            jax.random.normal(k, (L,) + shape, jnp.float32) * scale
        ).astype(dt)

    layers = {
        **(
            {} if cfg.norm_after else {
                "attn_norm": jnp.ones((L, E), dt),
                "mlp_norm": jnp.ones((L, E), dt),
            }
        ),
        **(
            {"attn_post_norm": jnp.ones((L, E), dt),
             "mlp_post_norm": jnp.ones((L, E), dt)}
            if cfg.post_norms else {}
        ),
    }
    if cfg.is_mla:
        Cq, C = cfg.q_lora_rank, cfg.kv_lora_rank
        dqk, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if Cq:
            layers["wq_a"] = layer_stack(keys[1], (E, Cq))
            layers["q_norm"] = jnp.ones((L, Cq), dt)
            layers["wq_b"] = layer_stack(keys[2], (Cq, H * (dqk + dr)))
        else:
            layers["wq"] = layer_stack(keys[1], (E, H * (dqk + dr)))
        layers["wkv_a"] = layer_stack(keys[3], (E, C + dr))
        layers["kv_norm"] = jnp.ones((L, C), dt)
        layers["wkv_b"] = layer_stack(keys[9], (C, H * (dqk + dv)))
        layers["wo"] = layer_stack(keys[4], (H * dv, E))
    else:
        layers["wq"] = layer_stack(keys[1], (E, H * D))
        layers["wk"] = layer_stack(keys[2], (E, Hkv * D))
        layers["wv"] = layer_stack(keys[3], (E, Hkv * D))
        layers["wo"] = layer_stack(keys[4], (H * D, E))
        if cfg.gated_attention:
            layers["attn_gate"] = layer_stack(keys[0], (E, H * D))
        if cfg.attention_bias:
            layers["bq"] = jnp.zeros((L, H * D), dt)
            layers["bk"] = jnp.zeros((L, Hkv * D), dt)
            layers["bv"] = jnp.zeros((L, Hkv * D), dt)
        if cfg.qk_norm_full:  # olmo-2: full projection width
            layers["q_norm"] = jnp.ones((L, H * D), dt)
            layers["k_norm"] = jnp.ones((L, Hkv * D), dt)
        elif cfg.qk_norm:
            layers["q_norm"] = jnp.ones((L, D), dt)
            layers["k_norm"] = jnp.ones((L, D), dt)
        if cfg.attn_sinks:
            layers["sinks"] = layer_stack(keys[10], (H,), 0.5)
        if cfg.o_bias:
            layers["bo"] = jnp.zeros((L, E), dt)
    if moe:
        X = cfg.num_experts
        Fm = cfg.moe_intermediate_size or F
        mk = jax.random.split(keys[5], 8)
        layers["moe_gate"] = layer_stack(mk[0], (E, X))
        if cfg.moe_gate_bias:
            layers["moe_gate_bias"] = _draw_leaf(
                jax.random.fold_in(mk[0], 1), (L, X), 0.1, jnp.float32
            ) if by_layer else jnp.zeros((L, X), jnp.float32)
        # the router scores every published expert; the stacks hold the
        # experts that are here (cfg.experts_held: one chip's share)
        X = cfg.local_experts
        layers["we_gate"] = layer_stack(mk[1], (X, E, Fm))
        layers["we_up"] = layer_stack(mk[2], (X, E, Fm))
        layers["we_down"] = layer_stack(
            mk[3], (X, Fm, E),
            0.02 / (16 if cfg.post_norms else 8) if by_layer else 0.02)
        if cfg.moe_act == "gptoss_clamp":  # gpt-oss expert/router biases
            layers["moe_router_bias"] = jnp.zeros((L, X), jnp.float32)
            layers["be_gate"] = layer_stack(keys[8], (X, Fm), 0.05)
            layers["be_up"] = layer_stack(keys[9], (X, Fm), 0.05)
            layers["be_down"] = layer_stack(keys[11], (X, E), 0.05)
        if cfg.num_shared_experts:
            Fs = cfg.shared_expert_size or Fm * cfg.num_shared_experts
            layers["shared_gate"] = layer_stack(mk[4], (E, Fs))
            layers["shared_up"] = layer_stack(mk[5], (E, Fs))
            layers["shared_down"] = layer_stack(mk[6], (Fs, E))
            if cfg.shared_expert_gate:  # qwen2moe sigmoid gate [E, 1]
                layers["shared_egate"] = layer_stack(mk[7], (E, 1))
    else:
        layers["w_gate"] = layer_stack(keys[5], (E, F))
        layers["w_up"] = layer_stack(keys[6], (E, F))
        layers["w_down"] = layer_stack(keys[7], (F, E))
    return layers


def layer_groups(params: dict, cfg: ModelConfig):
    """[(stacked_layer_params, n_layers, layer_offset)] in forward order
    — one group for homogeneous models, (dense, moe) for DeepSeek-style
    first_k_dense_replace checkpoints."""
    k = cfg.first_dense_layers if "dense_layers" in params else 0
    out = []
    if k:
        out.append((params["dense_layers"], k, 0))
    out.append((params["layers"], cfg.num_layers - k, k))
    return out


class _LayerOf(NamedTuple):
    """Layer ``index`` of a stacked ``[L, X, in, out]`` expert leaf (or of
    a quantized one's ``{"q", "s"}`` node), left where it is: see
    ``_layer``."""

    stack: Any
    index: int


_EXPERT_STACKS = ("we_gate", "we_up", "we_down")


def _layer(lps: dict, li: int, mesh=None) -> dict:
    """Layer ``li``'s leaves out of a stacked group, for an UNROLLED
    layer loop. A slice that feeds an XLA dot fuses into the dot's
    operand read; one that feeds a custom call (the TPU's grouped-matmul
    kernel behind ``lax.ragged_dot``) is first COPIED out of the stack:
    0.75 GiB a layer at OLMoE-1B-7B's widths, more than the kernel then
    streams, and held as a program temporary (an AOT compile for the
    v5e put 6 GiB of them into an 8-layer decode window). So a
    single-device expert stack, plain or quantized, is not sliced: it
    rides as ``_LayerOf`` and ``_ragged_mm`` hands the kernel the whole
    stack and the layer's index."""
    whole = {
        k: _LayerOf(lps[k], li) for k in _EXPERT_STACKS
        if mesh is None and k in lps
    }
    lp = jax.tree.map(
        lambda a: a[li], {k: v for k, v in lps.items() if k not in whole})
    return {**lp, **whole}


#: where a hybrid stack keeps the leaves of each operator kind
_OP_LEAVES = {"conv": "conv_ops", "linear": "linear_ops", "attn": "attn_ops"}


def _is_state_layer(lp: dict) -> bool:
    """``lp`` (``_layers``) is a layer whose operator carries a
    per-sequence state and no keys and values: LFM2's short convolution
    or GigaChat 3.5's gated delta rule."""
    return "conv_in" in lp or "lin_qkvz" in lp


def _layers(params: dict, cfg: ModelConfig, mesh=None):
    """(l, lp) for every layer in forward order, for an UNROLLED layer
    loop: ``lp`` holds layer ``l``'s leaves (``_layer``). An LFM2 stack
    (``cfg.layer_ops``) keeps its leaves stacked by KIND — conv
    operators, attention operators, dense FFNs, expert FFNs — and ``lp``
    joins the layer's operator with its FFN; ``"conv_in" in lp`` says
    which operator it is."""
    if not cfg.layer_ops:
        for lps, n, goff in layer_groups(params, cfg):
            for li in range(n):
                yield goff + li, _layer(lps, li, mesh)
        return
    kd = cfg.first_dense_layers if "dense_layers" in params else 0
    for l, op in enumerate(cfg.layer_ops):
        ops = params[_OP_LEAVES[op]]
        ffn = params["dense_layers"] if l < kd else params["layers"]
        yield l, {**_layer(ops, cfg.op_index(l)),
                  **_layer(ffn, l if l < kd else l - kd, mesh)}


def _scan_groups(body, x, params, cfg: ModelConfig, k_cache, v_cache,
                 tally=None):
    """lax.scan the layer body over every layer group, threading the
    cache slices; returns (x, k_cache, v_cache) with per-group ys
    re-concatenated on the layer axis. ``prefill``'s homogeneous layer
    loop: no decode program scans its layers. A body that adds to a
    MoeTally scans through it (``tally``)."""
    kcs, vcs = [], []
    scan = lax.scan if tally is None else tally.scan
    for lps, n, off in layer_groups(params, cfg):
        x, (kc_g, vc_g) = scan(
            body, x, (lps, k_cache[off : off + n], v_cache[off : off + n])
        )
        kcs.append(kc_g)
        vcs.append(vc_g)
    k_cache = jnp.concatenate(kcs) if len(kcs) > 1 else kcs[0]
    v_cache = jnp.concatenate(vcs) if len(vcs) > 1 else vcs[0]
    return x, k_cache, v_cache


def _init_hybrid_layers(cfg: ModelConfig, key: jax.Array) -> dict:
    """The layer leaves of an LFM2 stack, stacked by KIND: ``conv_ops``
    [Lc, ...] and ``attn_ops`` [La, ...] (each operator with its norm),
    ``dense_layers`` [first_dense_layers, ...] and ``layers`` (the other
    layers' FFNs, experts or dense, with their norm). The taps and the
    experts' selection bias are drawn non-zero so that a forward that
    drops either shows. Two draws are smaller than the matrices' 0.02,
    so that a bf16 forward stays near the float32 one (PERF.md section
    6, PR 33): a random router's choices flip under bf16 rounding, and
    the marginal one of 4 renormalised sigmoid experts carries a fifth
    of the layer's output, so the routed experts' down-projections are
    drawn at an eighth; the conv operator is a product of three
    projections of its input, which triples what a rounding error
    gains in it, so its in-projection is drawn at a half."""
    dt = _dtype(cfg)
    E, H, Hkv, D, K = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                       cfg.head_dim, cfg.conv_kernel)
    Lc, La = cfg.conv_layers, cfg.kv_layers
    kd = cfg.first_dense_layers if cfg.is_moe else 0
    keys = jax.random.split(key, 16)

    def draw(k, shape, scale=0.02, dtype=dt):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    def dense_ffn(k, n):
        ks = jax.random.split(k, 3)
        F = cfg.intermediate_size
        return {
            "mlp_norm": jnp.ones((n, E), dt),
            "w_gate": draw(ks[0], (n, E, F)),
            "w_up": draw(ks[1], (n, E, F)),
            "w_down": draw(ks[2], (n, F, E)),
        }

    out = {
        "conv_ops": {
            "attn_norm": jnp.ones((Lc, E), dt),
            "conv_in": draw(keys[0], (Lc, E, 3 * E), 0.01),
            "conv_w": draw(keys[1], (Lc, K, E), 0.3),
            "conv_out": draw(keys[2], (Lc, E, E)),
        },
        "attn_ops": {
            "attn_norm": jnp.ones((La, E), dt),
            "wq": draw(keys[3], (La, E, H * D)),
            "wk": draw(keys[4], (La, E, Hkv * D)),
            "wv": draw(keys[5], (La, E, Hkv * D)),
            "wo": draw(keys[6], (La, H * D, E)),
            "q_norm": jnp.ones((La, D), dt),
            "k_norm": jnp.ones((La, D), dt),
        },
    }
    if kd:
        out["dense_layers"] = dense_ffn(keys[7], kd)
    n = cfg.num_layers - kd
    if not cfg.is_moe:
        out["layers"] = dense_ffn(keys[8], n)
        return out
    X, Fm = cfg.num_experts, cfg.moe_intermediate_size
    out["layers"] = {
        "mlp_norm": jnp.ones((n, E), dt),
        "moe_gate": draw(keys[9], (n, E, X)),
        "we_gate": draw(keys[10], (n, X, E, Fm)),
        "we_up": draw(keys[11], (n, X, E, Fm)),
        "we_down": draw(keys[12], (n, X, Fm, E), 0.0025),
    }
    if cfg.moe_gate_bias:
        out["layers"]["moe_gate_bias"] = draw(
            keys[13], (n, X), 0.1, jnp.float32)
    return out


@partial(jax.jit, static_argnames=("shape", "scale", "dtype"))
def _draw_leaf(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _draw_stacked(key, shape, scale, dtype):
    """A stacked ``[n, ...]`` leaf drawn a layer at a time: the float32
    draw of a whole stack of experts (3.76 GB at GigaChat 3.5's widths)
    does not fit beside the weights it joins (ROADMAP B1)."""
    ks = jax.random.split(key, shape[0])
    return jnp.stack([_draw_leaf(k, tuple(shape[1:]), scale, dtype)
                      for k in ks])


def _init_gigachat35_layers(cfg: ModelConfig, key: jax.Array) -> dict:
    """The layer leaves of a GigaChat 3.5 stack, by KIND as LFM2's:
    ``linear_ops`` [Ll, ...] (the gated delta rule), ``attn_ops``
    [La, ...] (latent attention and its output gate), ``dense_layers``
    and ``layers`` (the FFNs: experts HELD here, the shared expert, the
    router over all published experts). Each operator and FFN carries a
    norm before and one after. Norm leaves, taps, the selection bias,
    ``A_log`` and ``dt_bias`` are drawn non-zero so that a forward that
    drops or misreads one shows. Smaller than the matrices' 0.02, so
    that a bf16 forward stays near the float32 one (PERF.md section 6,
    PRs 33 and 43): the routed experts' down-projections (a flipped
    marginal choice of 8 carries an eighth of the routed output times
    routed_scaling_factor 2.5) and ``lin_ba``, whose ``g`` sits in an
    exponent."""
    dt = _dtype(cfg)
    E, H = cfg.hidden_size, cfg.num_heads
    Ll, La = cfg.linear_layers, cfg.kv_layers
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    Dk, Dv, K = cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv_kernel
    C = cfg.linear_conv_dim
    kd = cfg.first_dense_layers
    keys = iter(jax.random.split(key, 48))

    def draw(shape, scale=0.02, dtype=dt):
        return _draw_stacked(next(keys), tuple(shape), scale, dtype)

    def norm(n, width):
        return draw((n, width), 0.1)

    def dense_ffn(n):
        F = cfg.intermediate_size
        return {
            "mlp_norm": norm(n, E), "mlp_post_norm": norm(n, E),
            "w_gate": draw((n, E, F)), "w_up": draw((n, E, F)),
            "w_down": draw((n, F, E)),
        }

    Cq, Ckv = cfg.q_lora_rank, cfg.kv_lora_rank
    dqk, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    out = {
        "linear_ops": {
            "attn_norm": norm(Ll, E), "attn_post_norm": norm(Ll, E),
            "lin_qkvz": draw((Ll, E, C + Hv * Dv)),
            "lin_ba": draw((Ll, E, 2 * Hv), 0.005),
            "lin_conv_w": draw((Ll, K, C), 0.3),
            # decay rates exp(A_log) in about [0.5, 8], steps
            # softplus(dt_bias) about 0.05: g = -A * step near -0.1
            "lin_A_log": draw((Ll, Hv), 0.7, jnp.float32) + 0.7,
            "lin_dt_bias": draw((Ll, Hv), 0.5, jnp.float32) - 3.0,
            "lin_o_norm": draw((Ll, Dv), 0.1),
            "lin_out": draw((Ll, Hv * Dv, E)),
        },
        "attn_ops": {
            "attn_norm": norm(La, E), "attn_post_norm": norm(La, E),
            "wq_a": draw((La, E, Cq)), "q_norm": norm(La, Cq),
            "wq_b": draw((La, Cq, H * (dqk + dr))),
            "wkv_a": draw((La, E, Ckv + dr)), "kv_norm": norm(La, Ckv),
            "wkv_b": draw((La, Ckv, H * (dqk + dv))),
            "wo": draw((La, H * dv, E)),
        },
    }
    if cfg.gated_attention:
        out["attn_ops"]["attn_gate"] = draw((La, E, H * dv))
    if kd:
        out["dense_layers"] = dense_ffn(kd)
    n = cfg.num_layers - kd
    if not cfg.is_moe:
        out["layers"] = dense_ffn(n)
        return out
    X, Xl, Fm = cfg.num_experts, cfg.local_experts, cfg.moe_intermediate_size
    Fs = Fm * cfg.num_shared_experts
    out["layers"] = {
        "mlp_norm": norm(n, E), "mlp_post_norm": norm(n, E),
        "moe_gate": draw((n, E, X)),
        "moe_gate_bias": draw((n, X), 0.1, jnp.float32),
        "we_gate": draw((n, Xl, E, Fm)), "we_up": draw((n, Xl, E, Fm)),
        "we_down": draw((n, Xl, Fm, E), 0.0025),
    }
    if Fs:
        out["layers"].update(
            shared_gate=draw((n, E, Fs)), shared_up=draw((n, E, Fs)),
            shared_down=draw((n, Fs, E)))
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random-init params (tests/benches; real weights via weights.py)."""
    dt = _dtype(cfg)
    E, V, L = cfg.hidden_size, cfg.vocab_size, cfg.num_layers
    keys = jax.random.split(key, 4)

    def norm_init(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    kd = cfg.first_dense_layers if cfg.is_moe else 0
    params = {
        "embed": norm_init(keys[0], (V, E), 0.02),
        "final_norm": jnp.ones((E,), dt),
    }
    if cfg.linear_layers:
        params.update(_init_gigachat35_layers(cfg, keys[1]))
        params["final_norm"] = norm_init(keys[3], (E,), 0.1)
    elif cfg.layer_ops:
        params.update(_init_hybrid_layers(cfg, keys[1]))
    else:
        params["layers"] = _init_layer_group(
            cfg, keys[1], L - kd, cfg.is_moe)
        if kd:
            params["dense_layers"] = _init_layer_group(
                cfg, keys[3], kd, False)
    if not cfg.tie_word_embeddings:
        # GigaChat 3.5 (hidden 7168): the head drawn so that the logits
        # spread as at the hidden 2048 of the configurations the reference
        # check's tolerance was set on (0.02 sqrt(2048) = 0.9): the same
        # relative error in the last hidden state then moves a logprob as
        # far (PERF.md section 6, PR 43); 0.02 at a width up to 2048
        head = min(0.02, 0.02 * (2048 / E) ** 0.5) if cfg.linear_layers else 0.02
        params["lm_head"] = norm_init(keys[2], (E, V), head)
    return params


# kv-head ordering of the cache below: natural (blocked) order, the only
# one this engine stores. Foreign-ordered peers declare theirs on the KV
# wire (PrefillWorker head_layout / KvDelivery.head_layout) and the
# decode side regroups on delivery (ops/kv_rearrange.py)
KV_HEAD_LAYOUT = "blocked"


def kv_lanes(cfg: ModelConfig) -> int:
    """Width of a head's row in the KV cache, and of q / k / v wherever
    attention sees them: ``head_dim`` stored in whole 128-lane rows. A
    head of 64 (gpt-oss) rides in a row of 128 whose upper lanes are
    zero. That is what the TPU's HBM holds anyway (a ``[bs, 64]`` tile is
    padded to 128 lanes), so it costs no memory there, and it is the only
    form in which a kernel can cut a page out of the cache with its own
    DMA (Mosaic slices an HBM ref along whole lane tiles only:
    ``ops/paged_attention_pallas.py``). ``_qkv`` pads, ``_from_lanes``
    cuts the attention rows back before the output projection; scores
    and outputs are those of the unpadded head. Heads the kernels never
    take (not a multiple of 64: the tests' tiny models) stay as they
    are."""
    return _in_lanes(cfg.head_dim)


def _in_lanes(width: int) -> int:
    """``width`` in whole 128-lane rows if the kernels take it (a multiple
    of 64), else as it is (the tests' tiny models)."""
    return -(-width // 128) * 128 if width % 64 == 0 else width


def rope_lanes(cfg: ModelConfig) -> int:
    """Width of a token's row in a latent model's rope pool, and of
    ``q_pe`` / ``k_pe`` wherever attention sees them: ``kv_lanes``' rule
    for ``qk_rope_head_dim`` (64 rides in 128 lanes for every published
    latent model, upper lanes zero: they add nothing to ``q_pe . k_pe``).
    The latent decode kernel cuts its pages out of the pool itself, and
    a pool of 64 lanes is not even row-major on the chip: its default
    layout is pages-minor, which every program that takes the pool
    re-laid at entry and exit (``ops/mla_attention_pallas.py``; PERF.md
    section 6, PR 53). A token of latent cache is ``kv_lora_rank +
    rope_lanes`` values a layer, 1,280 B where the model's own widths
    make 1,152."""
    return _in_lanes(cfg.qk_rope_head_dim)


def _from_lanes(cfg: ModelConfig, o_flat: jnp.ndarray) -> jnp.ndarray:
    """Attention rows ``[R, H * kv_lanes]`` back to ``[R, H * head_dim]``
    (the padded lanes hold V's zeros)."""
    D, lanes = cfg.head_dim, kv_lanes(cfg)
    if cfg.is_mla or lanes == D:
        return o_flat
    R = o_flat.shape[0]
    return o_flat.reshape(R, -1, lanes)[..., :D].reshape(R, -1)


def kv_cache_shapes(
    cfg: ModelConfig, num_blocks: int, block_size: int
) -> tuple[tuple, tuple]:
    """(k_shape, v_shape). MLA stores the compressed latent instead of
    per-head K/V: c_kv rides the k slot, the head-shared rotated k_pe the
    v slot (in ``rope_lanes`` lanes) — both single-"head" paged arrays,
    so every block-table / allocator / offload / transfer path works
    unchanged (models/mla.py).
    Every other family stores a head in ``kv_lanes`` lanes. The layer
    axis counts the layers that hold keys and values (an LFM2 stack's
    attention layers: ``cfg.op_index`` is a layer's index here)."""
    L = cfg.kv_layers
    if cfg.is_mla:
        return (
            (L, 1, num_blocks, block_size, cfg.kv_lora_rank),
            (L, 1, num_blocks, block_size, rope_lanes(cfg)),
        )
    s = (L, cfg.num_kv_heads, num_blocks, block_size, kv_lanes(cfg))
    return s, s


def init_kv_cache(
    cfg: ModelConfig, num_blocks: int, block_size: int, dtype=None,
    window_blocks: int = 0,
):
    """(k_cache, v_cache). A ``window_kv_pool`` model's are PAIRS, (the
    full layers' cache of ``num_blocks``, the window layers' of
    ``window_blocks``): the step programs take either form as their
    ``k_cache`` / ``v_cache`` argument, with a pair of block tables
    beside a pair of caches (``_pools``)."""
    ks, vs = kv_cache_shapes(cfg, num_blocks, block_size)
    dt = dtype or _dtype(cfg)
    if not cfg.window_kv_pool:
        return jnp.zeros(ks, dt), jnp.zeros(vs, dt)
    Lf, Lw = cfg.kv_pool_layers
    full, win = (Lf,) + ks[1:], (Lw, ks[1], window_blocks) + ks[3:]
    return ((jnp.zeros(full, dt), jnp.zeros(win, dt)),
            (jnp.zeros(full, dt), jnp.zeros(win, dt)))


def _by_pool(cfg: ModelConfig, arg) -> list:
    """A cache or block-table argument as a list by pool: one entry for
    a model with one cache, (full, window) for a ``window_kv_pool``
    model, whose arguments are pairs."""
    return list(arg) if cfg.window_kv_pool else [arg]


def _pools(cfg: ModelConfig, k_cache, v_cache, tables):
    """(K caches, V caches, block tables), each a list by pool
    (``_by_pool``). A layer reads and writes entry ``cfg.kv_pool(l)`` at
    ``cfg.kv_index(l)`` (entry 0 at ``cfg.op_index(l)`` for a model with
    one cache); ``_unpool`` gives the step program's outputs their
    argument's form back."""
    return (_by_pool(cfg, k_cache), _by_pool(cfg, v_cache),
            _by_pool(cfg, tables))


def _unpool(cfg: ModelConfig, kcs: list, vcs: list):
    if cfg.window_kv_pool:
        return tuple(kcs), tuple(vcs)
    return kcs[0], vcs[0]


def _table_rows(tables, m):
    """Row(s) ``m`` of a block-table argument in either form."""
    return jax.tree.map(lambda t: t[m], tables)


# ---------------- the decode batch's resident rows ----------------
# Everything a step program is told of its decode slots, in ONE int32
# matrix ``[B, W]`` that lives on the device from dispatch to dispatch
# (``engine/step_state.StepState`` keeps its host mirror): a column a
# field (a float field as its bits), then a slot's block-table row (two
# for a model with a window pool, the full pool's first). A program that
# is given ``rows`` takes none of its per-slot arguments, applies
# ``rows_delta`` ([K, 3] int32 cells of (slot, column, value); a slot
# past the batch: dropped) before its first layer, and returns the
# matrix as its last output with the tokens, lengths and steps its own
# steps left, which is what the next dispatch of the same batch wants.

ROW_FIELDS = ("tokens", "seq_lens", "steps", "seeds", "top_ks",
              "adapter_ids", "temps", "top_ps", "freq_pens", "pres_pens",
              "rep_pens")
ROW_FLOATS = frozenset(ROW_FIELDS[6:])
ROW_TABLES = len(ROW_FIELDS)  # the first table column
#: what a caller that gives ``rows`` passes for the nine per-slot
#: arguments of ``decode_window`` and ``mixed_step``
ROWS_RESIDENT = (None,) * 9


def rows_enter(rows: jnp.ndarray, delta: jnp.ndarray, n_tables: int):
    """``rows`` with ``delta``'s cells written, and its fields by name
    (``tables`` in a step program's form: one table or the pair)."""
    rows = rows.at[delta[:, 0], delta[:, 1]].set(delta[:, 2], mode="drop")
    f = {}
    for i, name in enumerate(ROW_FIELDS):
        col = rows[:, i]
        f[name] = (lax.bitcast_convert_type(col, jnp.float32)
                   if name in ROW_FLOATS else col)
    M = (rows.shape[1] - ROW_TABLES) // n_tables
    tabs = tuple(rows[:, ROW_TABLES + t * M: ROW_TABLES + (t + 1) * M]
                 for t in range(n_tables))
    f["tables"] = tabs[0] if n_tables == 1 else tabs
    return rows, f


def rows_leave(rows, live, tokens, seq_lens, steps, mesh=None):
    """``rows`` after a program's own steps: the ``live`` slots' last
    token, length and step count; a slot that entered dead stays as it
    was (length 0). Under a mesh the matrix is pinned replicated, the
    sharding it came in with: a program fed its own output must not
    compile again."""
    new = jnp.stack([tokens, seq_lens, steps], axis=1)
    rows = rows.at[:, :3].set(jnp.where(live[:, None], new, rows[:, :3]))
    if mesh is not None:
        rows = lax.with_sharding_constraint(
            rows, jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
    return rows


def _rows_args(cfg: ModelConfig, rows, rows_delta):
    """A step program's per-slot arguments out of its resident rows:
    (rows, tokens, positions, tables, seq_lens, seeds, steps, temps,
    top_ks, top_ps, penalties by keyword, adapter ids)."""
    rows, f = rows_enter(rows, rows_delta, 2 if cfg.window_kv_pool else 1)
    pens = {k: f[k] for k in ("freq_pens", "pres_pens", "rep_pens")}
    return (rows, f["tokens"], jnp.maximum(f["seq_lens"] - 1, 0),
            f["tables"], f["seq_lens"], f["seeds"], f["steps"], f["temps"],
            f["top_ks"], f["top_ps"], pens, f["adapter_ids"])


# ---------------- building blocks ----------------


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * w


def attn_query_scale(cfg: ModelConfig) -> float:
    """Query scale: head_dim**-0.5, or gemma-2's fixed
    query_pre_attn_scalar**-0.5."""
    return (cfg.attn_scale_base or cfg.head_dim) ** -0.5


def model_norm(x: jnp.ndarray, w: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The family's RMS norm over the last axis with the learned leaf
    ``w``: the scale is ``w`` itself, or GigaChat 3.5's
    ``norm_gate_weight * sigmoid(w)`` (ZeroCenteredGatedNorm: 1 at
    ``w = 0``)."""
    if cfg.norm_gate_weight:
        # the scale is COMPUTED (2 sigmoid(w)), so it stays in float32
        # and the product is rounded once: rounding it to the model's
        # dtype first would add a second rounding a norm that a learned
        # scale, stored in that dtype, does not have
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        scale = cfg.norm_gate_weight * jax.nn.sigmoid(w.astype(jnp.float32))
        return (xf * lax.rsqrt(var + cfg.rms_norm_eps) * scale).astype(x.dtype)
    return rms_norm(x, w, cfg.rms_norm_eps)


def pre_norm(lp: dict, key: str, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Pre-sublayer RMS norm — identity for norm-AFTER families (OLMo-2
    carries no input/pre-FFN norms; normalization happens on the
    sublayer output via post_norm)."""
    w = lp.get(key)
    return x if w is None else model_norm(x, w, cfg)


def post_norm(lp: dict, key: str, v: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Gemma-2 sandwich norm: normalize the sublayer OUTPUT before the
    residual add (post_attention/post_feedforward_layernorm). No-op for
    every other family (no post-norm weights in lp)."""
    w = lp.get(key)
    return v if w is None else model_norm(v, w, cfg)


def window_for_layer(cfg: ModelConfig, l: int) -> int:
    """Layer l's sliding-window width (0 = full). gpt-oss alternates
    sliding/full per layer (cfg.layer_windows); every other family is
    homogeneous (cfg.sliding_window). Call sites must be UNROLLED —
    the value is trace-static per layer."""
    return cfg.layer_windows[l] if cfg.layer_windows else cfg.sliding_window


def _rope_law(cfg: ModelConfig, local: bool) -> tuple:
    """(theta, scaling dict) of the model's rotary law, or of the window
    layers' own (``rope_local_theta`` / ``rope_local_scaling``)."""
    if local:
        return cfg.rope_local_theta, cfg.rope_local_scaling or {}
    return cfg.rope_theta, cfg.rope_scaling or {}


def _rope_attention_scaling(cfg: ModelConfig, local: bool = False) -> float:
    """YaRN multiplies cos/sin by an attention factor (transformers
    _compute_yarn_parameters); 1.0 for every other rope flavor."""
    import math

    _theta, scaling = _rope_law(cfg, local)
    kind = scaling.get("rope_type") or scaling.get("type")
    if kind == "longrope":
        # Phi-3: sqrt(1 + log(ctx growth)/log(orig)) on cos/sin —
        # applied in BOTH factor regimes (HF computes it once at init).
        af = scaling.get("attention_factor")
        if af is not None:
            return float(af)
        orig = scaling.get("original_max_position_embeddings")
        if orig:
            factor = cfg.max_position_embeddings / orig
            log_base = orig
        else:
            # no original context recorded: HF falls back to the
            # explicit rope_scaling["factor"] over max_position
            factor = scaling.get("factor", 1.0)
            log_base = cfg.max_position_embeddings
        if factor <= 1.0:
            return 1.0
        return math.sqrt(1.0 + math.log(factor) / math.log(log_base))
    if kind != "yarn":
        return 1.0
    factor = scaling.get("factor", 1.0)
    af = scaling.get("attention_factor")
    if af is not None:
        return float(af)
    msc, mad = scaling.get("mscale"), scaling.get("mscale_all_dim")
    if msc and mad:
        return float(yarn_mscale(factor, msc) / yarn_mscale(factor, mad))
    if factor <= 1.0:
        return 1.0
    return 0.1 * math.log(factor) + 1.0


def _rope_freqs(cfg: ModelConfig, local: bool = False) -> jnp.ndarray:
    import math

    theta, scaling = _rope_law(cfg, local)
    D = cfg.rope_partial_dim or cfg.head_dim
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    if (scaling.get("rope_type") or scaling.get("type")) == "yarn":
        # YaRN (transformers _compute_yarn_parameters): interpolate the
        # low-frequency dims by ``factor``, extrapolate the high ones,
        # linear ramp across the correction range (gpt-oss ships
        # truncate=False, so the range bounds stay fractional)
        factor = scaling.get("factor", 1.0)
        beta_fast = scaling.get("beta_fast") or 32
        beta_slow = scaling.get("beta_slow") or 1
        orig = (scaling.get("original_max_position_embeddings")
                or cfg.max_position_embeddings)

        def corr_dim(n_rot):
            return (D * math.log(orig / (n_rot * 2 * math.pi))) / (
                2 * math.log(theta)
            )

        low, high = corr_dim(beta_fast), corr_dim(beta_slow)
        if scaling.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, D - 1)
        ramp = jnp.clip(
            (jnp.arange(D // 2, dtype=jnp.float32) - low)
            / max(high - low, 0.001),
            0.0, 1.0,
        )
        extrap = 1.0 - ramp
        return (inv / factor) * (1 - extrap) + inv * extrap
    kind = scaling.get("rope_type") or scaling.get("type")
    if kind == "linear":
        # position-interpolation scaling (gemma-3 global layers et al.)
        return inv / scaling.get("factor", 1.0)
    if kind == "longrope":
        # Phi-3 LongRoPE: two per-dim rescale-factor sets, selected PER
        # POSITION at the original-context boundary (vLLM's
        # Phi3LongRoPEScaledRotaryEmbedding semantics — the serving
        # standard; HF instead re-ropes the WHOLE sequence when its
        # length crosses the boundary, which an incremental KV cache
        # cannot replay). apply_rope consumes the (stacked-sets,
        # threshold) form.
        orig = (scaling.get("original_max_position_embeddings")
                or cfg.max_position_embeddings)
        short = inv / jnp.asarray(scaling["short_factor"], jnp.float32)
        long = inv / jnp.asarray(scaling["long_factor"], jnp.float32)
        return (jnp.stack([short, long]), orig)
    if scaling.get("rope_type") == "llama3" or scaling.get("type") == "llama3":
        # llama-3.1 NTK-by-parts frequency remap
        factor = scaling.get("factor", 8.0)
        lo = scaling.get("low_freq_factor", 1.0)
        hi = scaling.get("high_freq_factor", 4.0)
        old_ctx = scaling.get("original_max_position_embeddings", 8192)
        wavelen = 2 * jnp.pi / inv
        ratio = old_ctx / wavelen
        smooth = jnp.clip((ratio - lo) / (hi - lo), 0.0, 1.0)
        inv = jnp.where(
            ratio < lo, inv / factor,
            jnp.where(ratio > hi, inv, (1 - smooth) * inv / factor + smooth * inv),
        )
    return inv


def _rope_local(cfg: ModelConfig):
    """The window layers' own rotary law as (frequencies, cos/sin
    factor): Gemma-3's local base frequency with no scaling, Mellum 2's
    plain law beside YaRN on the full layers; None when the model has a
    single rope."""
    if not cfg.rope_local_theta:
        return None
    return _rope_freqs(cfg, local=True), _rope_attention_scaling(cfg, True)


#: ``rope_for_layer``'s answer for a layer that carries no positions
ROPE_NONE = "none"


def rope_for_layer(cfg: ModelConfig, l: int, rope_global: tuple, rope_local):
    """Layer l's (rope frequencies, cos/sin factor): the LOCAL law on
    sliding layers when the model defines one, the global law elsewhere;
    ``ROPE_NONE`` for a layer of the kind the model does not rotate
    (``cfg.rope_none_layers``). Static per layer — callers are the
    unrolled layer loops."""
    windowed = window_for_layer(cfg, l) > 0
    if cfg.rope_none_layers == ("window" if windowed else "full"):
        return ROPE_NONE
    if rope_local is None:
        return rope_global
    return rope_local if windowed else rope_global


def rotate_qk(q: jnp.ndarray, k: jnp.ndarray, positions: jnp.ndarray, rope):
    """``q`` and ``k`` rotated at ``positions`` under the layer's law
    (``rope_for_layer``); as they are under ``ROPE_NONE``."""
    if isinstance(rope, str):
        assert rope == ROPE_NONE, rope
        return q, k
    fr, fm = rope
    return apply_rope(q, positions, fr, fm), apply_rope(k, positions, fr, fm)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq,
               mscale: float = 1.0) -> jnp.ndarray:
    """x: [..., T, Hx, D] rotated at absolute positions [..., T];
    ``mscale`` is the cos/sin attention factor (YaRN / LongRoPE; 1.0
    elsewhere). ``inv_freq`` is a [D/2] array, or LongRoPE's
    ``([2, D/2] stacked short/long sets, original-context threshold)``
    — each position uses the set its side of the threshold, so an
    incrementally-written KV cache stays self-consistent."""
    if isinstance(inv_freq, tuple):
        sets, orig = inv_freq
        inv = jnp.where(positions[..., None] < orig, sets[0], sets[1])
    else:
        inv = inv_freq
    R = 2 * inv.shape[-1]  # rotary dims; < head_dim = partial rotary
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    xr, x_pass = xf[..., :R], xf[..., R:]
    angles = positions[..., None].astype(jnp.float32) * inv  # [..., T, R/2]
    cos = jnp.cos(angles)[..., None, :] * mscale  # [..., T, 1, R/2]
    sin = jnp.sin(angles)[..., None, :] * mscale
    x1, x2 = jnp.split(xr, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if x_pass.shape[-1]:
        out = jnp.concatenate([out, x_pass], axis=-1)
    return out.astype(dtype)


def _embed(params: dict, cfg: ModelConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    """Token embedding lookup; gemma scales activations by sqrt(E) (the
    table itself must stay unscaled — it is tied to the lm head)."""
    x = params["embed"][tokens]
    if cfg.scale_embed:
        x = (x.astype(jnp.float32) * (cfg.hidden_size ** 0.5)).astype(x.dtype)
    return x


def _mm(x: jnp.ndarray, w) -> jnp.ndarray:
    """Matmul against a plain or quantized weight. Quantized weights are
    ``{"q": int8|float8 [in, out], "s": f32 [out]}`` (models/quant.py);
    the convert fuses into the dot's operand read and the per-channel
    scale into its epilogue, so int8/fp8 storage halves HBM traffic with
    bf16 MXU compute.

    ``{"qn": int8, "s": f32}`` (quantization="int8_native") instead runs
    a REAL int8 dot: activations are dynamically quantized per row
    (absmax/127 over the contraction axis), the s8 x s8 dot accumulates
    in int32 on the MXU, and both scales apply in the f32 epilogue —
    the measured low-precision compute lane, not just narrow storage."""
    if isinstance(w, dict):
        if "qn" in w:
            xf = x.astype(jnp.float32)
            s_x = jnp.maximum(
                jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0, 1e-12
            )
            xq = jnp.clip(jnp.round(xf / s_x), -127, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(
                xq, w["qn"],
                (((xq.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            out = acc.astype(jnp.float32) * s_x * w["s"]
            return out.astype(x.dtype)
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _mm_b(x: jnp.ndarray, lp: dict, w_key: str, b_key: str) -> jnp.ndarray:
    """_mm plus an optional bias leaf (gpt-oss: o_proj carries one)."""
    out = _mm(x, lp[w_key])
    b = lp.get(b_key)
    return out if b is None else out + b


def _glu(gate, up, act: str = "silu", limit: float = 0.0):
    """act(gate) * up; with a ``limit`` (GigaChat 3.5's swiglu_limit)
    both streams are clamped first: gate from above, up on both sides."""
    if limit:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    gate = (
        jax.nn.gelu(gate, approximate=True) if act == "gelu_tanh"
        else jax.nn.silu(gate)
    )
    return gate * up


def swiglu(x, w_gate, w_up, w_down, act: str = "silu", limit: float = 0.0):
    return _mm(_glu(_mm(x, w_gate), _mm(x, w_up), act, limit), w_down)


def _layer_sums(n: int, e: jnp.ndarray, t: jnp.ndarray,
                group_sizes: jnp.ndarray, live: jnp.ndarray, streams=None):
    """What ONE expert layer adds to a ``MoeTally`` of ``n`` entries:
    assignment r goes to expert ``e[r]`` from row ``t[r]``
    (``_moe_route``'s sacrificial row counts as not live);
    ``group_sizes`` [X] is what the kernel is handed; ``live`` [rows]
    marks the rows that carry a real token; ``streams`` is the kernel's
    count of expert visits (None: the layer runs on ``lax.ragged_dot``,
    a visit a touched expert)."""
    live = jnp.append(live, False)[t]
    by_expert = jnp.zeros_like(group_sizes).at[e].add(
        live.astype(group_sizes.dtype))
    touched = jnp.sum(group_sizes > 0)
    return jnp.stack([
        touched, jnp.sum(by_expert > 0), jnp.max(group_sizes),
        touched if streams is None else streams, jnp.sum(group_sizes),
    ][:n]).astype(jnp.int32)


class MoeTally:
    """Routing counters of ONE step program, gathered while it is traced
    (the engine's ``engine_moe_*`` series; docs/observability.md).

    ``sums`` (int32 [4]) adds up, over the expert layers traced so far:
    the experts that received at least one row (what the grouped matmuls
    have to stream), the experts that received at least one LIVE row,
    the rows of the largest group, and the expert matrices a matmul of
    the layer DID stream: the visits the grouped-matmul kernel makes, an
    expert once a row tile its group crosses
    (``ops/moe_gmm_pallas.GroupPlan.streams``; the touched experts again
    where ``lax.ragged_dot`` runs the layer). On a chip that holds a
    share of the experts (``cfg.experts_held``; ``MoeTally.zeros``) a
    fifth entry counts the assignments that fell to an expert held here;
    where every expert is here that is every live row's, which the host
    counts. The step programs hand their ``live``
    mask to the routing (``_moe_route``), which sends a row that carries
    no token to no group: the first two then agree, and a routing that
    let a dead row into a group would show as the first above the
    second. The program that made the tally returns ``sums`` beside its
    tokens.
    """

    def __init__(self, sums=None):
        self.sums = jnp.zeros((4,), jnp.int32) if sums is None else sums

    @staticmethod
    def zeros(cfg: ModelConfig) -> jnp.ndarray:
        """The empty sums of a program over ``cfg``."""
        return jnp.zeros((5 if cfg.experts_held else 4,), jnp.int32)

    def add(self, e: jnp.ndarray, t: jnp.ndarray, group_sizes: jnp.ndarray,
            live: jnp.ndarray, streams=None):
        """One expert layer's ``_layer_sums``, added."""
        self.sums = self.sums + _layer_sums(
            self.sums.shape[0], e, t, group_sizes, live, streams)

    def scan(self, body, x, xs):
        """``lax.scan(body, x, xs)`` for a layer body that adds to this
        tally: the body is traced at the scan's own level, so inside it
        the sums ride the carry."""
        def counted(carry, layer_in):
            x, self.sums = carry
            x, ys = body(x, layer_in)
            return (x, self.sums), ys

        (x, self.sums), ys = lax.scan(counted, (x, self.sums), xs)
        return x, ys


def _moe_route(lp: dict, cfg: ModelConfig, x: jnp.ndarray, live=None):
    """Top-k routing + expert-sorted dispatch order (shared by the single-
    device and ep-sharded ragged paths). Returns (t_sorted, w_sorted,
    e_sorted, group_sizes): token row per assignment in expert order, its
    combine weight, its expert, and per-expert assignment counts.

    Covers Mixtral/Qwen softmax routing AND the DeepSeek variants: V2
    softmax, V3 sigmoid scoring with the no-aux-loss gate bias (bias
    picks the experts, the UNBIASED score is the combine weight) and
    group-limited top-k (score the n_group blocks by their top-2 sum,
    route only within the best topk_group blocks), with
    routed_scaling_factor applied to the final weights.

    ``live`` [T] bool (a step program's mask: a decode slot that holds a
    sequence, a chunk's rows below its valid length) takes the rows that
    carry no token out of every group: their assignments get the expert
    id X, one past the last group, so the stable sort puts them behind
    every group and ``group_sizes`` counts k a LIVE row. An expert only
    dead rows chose has size 0: the grouped matmul visits no tile of it
    and streams none of its weights. What the matmuls leave in the rows
    past the last group is unspecified, so those assignments combine
    with weight 0 into the sacrificial row T (``_moe_combine``), and
    ``e_sorted`` names the last expert for them (an index in bounds for
    the per-expert biases). A live row's arithmetic is the same with and
    without the mask. None routes every row. A chip that holds a share
    of the experts (``cfg.experts_held``) drops the assignments to the
    others the same way: the ids returned are LOCAL ones."""
    k = cfg.num_experts_per_tok
    X = cfg.local_experts  # the groups the matmuls are handed
    vals, idx = _route_topk(lp, cfg, x)
    keep = None if live is None else jnp.broadcast_to(live[:, None], idx.shape)
    if cfg.experts_held:
        # one chip's share: an assignment to an expert that lies on
        # another chip joins no group here, like a dead row's
        idx = idx - cfg.expert_first
        here = (idx >= 0) & (idx < X)
        keep = here if keep is None else keep & here
    if keep is not None:
        idx = jnp.where(keep, idx, X)
        vals = jnp.where(keep, vals, 0.0)
    e_flat = idx.reshape(-1)  # [T*k] row-major: assignment r -> token r//k
    order = jnp.argsort(e_flat)  # stable: deterministic within an expert
    t_sorted = order // k
    w_sorted = vals.reshape(-1)[order]
    e_sorted = e_flat[order]  # expert id per sorted row (expert biases)
    group_sizes = jnp.bincount(e_flat, length=X)  # an id of X counts nowhere
    if keep is not None:
        t_sorted = jnp.where(e_sorted < X, t_sorted, x.shape[0])
        e_sorted = jnp.minimum(e_sorted, X - 1)
    return t_sorted, w_sorted, e_sorted, group_sizes


def _route_topk(lp: dict, cfg: ModelConfig, x: jnp.ndarray):
    """(combine weights [T, k], expert indices [T, k]) — ONE scoring
    implementation shared by the ragged, sharded-ragged and dense
    dispatch paths."""
    k = cfg.num_experts_per_tok
    X = cfg.num_experts
    gate_logits = x.astype(jnp.float32) @ lp["moe_gate"].astype(jnp.float32)
    if lp.get("moe_router_bias") is not None:
        # gpt-oss: a LOGIT bias (pre-softmax, affects selection AND
        # combine) — unlike V3's moe_gate_bias, which biases selection
        # on post-score values only
        gate_logits = gate_logits + lp["moe_router_bias"].astype(jnp.float32)
    if cfg.moe_scoring == "sigmoid":
        scores = jax.nn.sigmoid(gate_logits)
    else:
        scores = jax.nn.softmax(gate_logits, axis=-1)  # [T, X]
    sel = scores
    if lp.get("moe_gate_bias") is not None:
        sel = scores + lp["moe_gate_bias"]
    if cfg.n_group > 1 and cfg.topk_group:
        T = sel.shape[0]
        g = sel.reshape(T, cfg.n_group, X // cfg.n_group)
        if cfg.moe_group_score == "top2":  # V3 noaux_tc
            g_score = jnp.sum(lax.top_k(g, 2)[0], axis=-1)  # [T, n_group]
        else:  # V2 group_limited_greedy: the group's max score
            g_score = jnp.max(g, axis=-1)
        _, g_idx = lax.top_k(g_score, cfg.topk_group)
        g_mask = jnp.zeros((T, cfg.n_group), bool).at[
            jnp.arange(T)[:, None], g_idx
        ].set(True)
        # masked groups score 0.0, not -inf — the HF routers mask to 0,
        # and a NEGATIVE biased in-group score must lose to an
        # out-of-group 0 exactly as it does there
        sel = jnp.where(
            jnp.repeat(g_mask, X // cfg.n_group, axis=1), sel, 0.0
        )
    _, idx = lax.top_k(sel, k)  # selection by (biased, group-limited) score
    vals = jnp.take_along_axis(scores, idx, axis=1)  # combine: raw score
    if cfg.norm_topk_prob:
        vals = vals / (
            jnp.sum(vals, axis=-1, keepdims=True) + cfg.topk_norm_eps)
    return vals * cfg.routed_scaling_factor, idx


def _expert_act(cfg: ModelConfig, g: jnp.ndarray, u: jnp.ndarray):
    """Expert gating nonlinearity. gpt-oss clamps both streams and uses
    an alpha-sigmoid GLU with a +1 on the linear stream:
    glu = min(g, 7) * sigmoid(1.702 * min(g, 7)); out = (clip(u) + 1) * glu."""
    if cfg.moe_act == "gptoss_clamp":
        g = jnp.clip(g, None, 7.0)
        u = jnp.clip(u, -7.0, 7.0)
        return (u + 1.0) * (g * jax.nn.sigmoid(1.702 * g))
    return _glu(g, u, limit=cfg.swiglu_limit)


def _expert_plan(group_sizes, rows: int, use_pallas: bool):
    """What the three grouped matmuls of ONE expert layer share: on the
    chip the kernel's step list over ``rows`` assignment rows
    (``ops/moe_gmm_pallas.group_plan``), computed once; None where
    ``lax.ragged_dot`` runs the layer."""
    if not use_pallas:
        return None
    from ..ops.moe_gmm_pallas import group_plan

    return group_plan(group_sizes, rows)


def _ragged_mm(xs, w, group_sizes, use_pallas: bool, interpret: bool,
               plan=None):
    """Grouped matmul against a plain or int8/fp8-quantized expert stack
    — the ragged twin of ``_mm``. ``w`` is a layer's [X, in, out] (a
    {"q", "s"} node for a quantized stack, models/quant.py) or a
    ``_LayerOf`` the whole stack. On the chip (``use_pallas``) ONE
    kernel takes them all (ops/moe_gmm_pallas.py): the whole stack and a
    layer index, weights streamed from HBM at storage width, ``plan``
    the layer's ``_expert_plan``. Without use_pallas ``lax.ragged_dot``
    runs the layer (the CPU path and the kernel's parity oracle); a
    quantized stack then dequantizes first, which materializes the bf16
    stack and exists for correctness only."""
    stack, layer = (w.stack, w.index) if isinstance(w, _LayerOf) else (w, None)
    quantized = isinstance(stack, dict)
    if use_pallas:
        from ..ops.moe_gmm_pallas import moe_grouped_matmul

        return moe_grouped_matmul(
            xs, stack["q"] if quantized else stack, group_sizes,
            layer=0 if layer is None else layer,
            scale=stack["s"] if quantized else None,
            plan=plan, interpret=interpret)
    if quantized:
        from ..ops.moe_gmm_pallas import ragged_int8_xla

        if layer is not None:
            stack = jax.tree.map(lambda a: a[layer], stack)
        return ragged_int8_xla(
            xs, stack["q"], stack["s"], group_sizes).astype(xs.dtype)
    if layer is not None:
        # the layer's X groups among the stack's L * X: XLA's kernel
        # visits no tile of an empty group, so it streams this layer's
        # touched experts and nothing is copied (_layer); the index
        # arrives traced (_moe_single)
        L, X = stack.shape[:2]
        return lax.ragged_dot(
            xs, stack.reshape((L * X,) + stack.shape[2:]),
            lax.dynamic_update_slice(
                jnp.zeros((L * X,), group_sizes.dtype), group_sizes,
                (layer * X,)),
        )
    return lax.ragged_dot(xs, stack, group_sizes)


def _dense_expert_mm(x, w, spec: str):
    """Dense-dispatch einsum against a plain or quantized expert stack:
    both dispatch einsums produce [T, X, out] with scales [X, out], so
    one broadcast covers gate/up and down."""
    if isinstance(w, dict):
        out = jnp.einsum(spec, x, w["q"].astype(x.dtype))
        return out * w["s"][None].astype(out.dtype)
    return jnp.einsum(spec, x, w)


def _moe_gather(x, t_sorted, masked):
    """The token row of each assignment, in expert order. Where the
    routing dropped assignments (``masked``: the ``live`` mask it was
    given, or True for a share of the experts) they name the sacrificial
    row T (``_moe_route``): they read the last row instead, into rows no
    group covers."""
    if masked is not None and masked is not False:
        t_sorted = jnp.minimum(t_sorted, x.shape[0] - 1)
    return x[t_sorted]


def _moe_combine(o, t_sorted, w_sorted, T: int, dtype):
    """Scatter-add expert outputs back to token rows. ``t_sorted`` entries
    of masked rows point at the sacrificial row T, sliced off (with
    whatever the matmuls left in rows that belong to no group)."""
    out = jnp.zeros((T + 1, o.shape[-1]), dtype)
    out = out.at[t_sorted].add(o * w_sorted[:, None].astype(dtype))
    return out[:T]


#: the leaves of a layer that its expert FFN reads (``_moe_single``)
_MOE_LEAVES = ("moe_gate", "moe_gate_bias", "moe_router_bias",
               *_EXPERT_STACKS, "be_gate", "be_up", "be_down",
               "shared_gate", "shared_up", "shared_down", "shared_egate")


# jitted: the decode window and the mixed step unroll their layers, and
# a jitted callee is traced and lowered ONCE a program however many
# layers call it (traced bare, the routing, the plan and the combine of
# every layer were a seventh of a step program's trace, which every warm
# start pays: PERF.md section 6, PR 45). XLA inlines the calls: the
# compiled program is the one the bare body gives. A ``_LayerOf`` crosses
# the call as a pytree, so the layer's index arrives traced.
@partial(jax.jit,
         static_argnames=("cfg", "use_pallas", "interpret", "counted"))
def _moe_single(lp: dict, x: jnp.ndarray, live, *, cfg: ModelConfig,
                use_pallas: bool, interpret: bool, counted: int):
    """``moe_ffn`` on one device: (the layer's output, what it adds to a
    ``MoeTally`` of ``counted`` entries; None for 0)."""
    t_sorted, w_sorted, e_sorted, group_sizes = _moe_route(lp, cfg, x, live)
    plan = _expert_plan(group_sizes, t_sorted.shape[0], use_pallas)
    sums = None
    if counted:
        sums = _layer_sums(counted, e_sorted, t_sorted, group_sizes, live,
                           None if plan is None else plan.streams)
    xs = _moe_gather(x, t_sorted, live is not None or bool(cfg.experts_held))
    g = _ragged_mm(xs, lp["we_gate"], group_sizes, use_pallas, interpret,
                   plan)
    u = _ragged_mm(xs, lp["we_up"], group_sizes, use_pallas, interpret, plan)
    if "be_gate" in lp:  # gpt-oss per-expert projection biases
        g = g + lp["be_gate"][e_sorted]
        u = u + lp["be_up"][e_sorted]
    o = _ragged_mm(_expert_act(cfg, g, u), lp["we_down"], group_sizes,
                   use_pallas, interpret, plan)
    if "be_down" in lp:
        o = o + lp["be_down"][e_sorted]
    out = _moe_combine(o, t_sorted, w_sorted, x.shape[0], x.dtype)
    if "shared_gate" in lp:
        out = out + _shared_expert(lp, x, cfg.swiglu_limit)
    return out, sums


def moe_ffn(
    lp: dict, cfg: ModelConfig, x: jnp.ndarray, mesh=None,
    use_pallas: bool = False, interpret: bool = False,
    tally: Optional[MoeTally] = None, live: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Mixtral/DeepSeek-style sparse MoE FFN with RAGGED dispatch (ref
    serves these via vLLM's fused_moe grouped-GEMM CUDA kernels; the TPU
    equivalent is ``lax.ragged_dot`` — XLA's grouped matmul).

    Tokens are sorted by assigned expert and each expert contracts only
    its own contiguous row group, so per-token FLOPs scale with top-k, not
    with the expert count (dense dispatch computed every expert for every
    token — X/k times the work, fatal at Mixtral-8x22B scale). Exact: no
    capacity factor, no token dropping.

    With a mesh, the dispatch runs under shard_map over (ep, tp): experts
    are ep-sharded (parallel/mesh.py we_* specs) so each device slices the
    expert-sorted rows at its own traced offset — a static [T*k]-row
    window, masked to its true count — and the token-scatter combine
    psum-reduces over ep (the expert combine) and tp (the down-projection
    contraction). Routing is computed replicated per device: T×X scalar
    work, negligible beside the expert GEMMs.

    Three paths: no mesh -> plain ragged dispatch (``_ragged_mm``: the
    Pallas grouped matmul on the chip, ``lax.ragged_dot`` elsewhere);
    mesh + divisible shapes ->
    shard_map ragged; mesh but indivisible shapes (or ep/tp axes absent)
    -> dense dispatch. The last is deliberate: ragged_dot's group axis is
    opaque to GSPMD, so running it on ep-sharded weights would all-gather
    every expert onto every device — the dense einsum's contraction over
    experts IS GSPMD's expert-parallel reduce, making it the safe (if
    FLOP-heavier) fallback for odd shapes.

    ``live`` [T] bool (a step program's mask of the rows that carry a
    token; ``_moe_route``) keeps every other row out of the groups of
    both ragged paths: such a row's routed output is exactly zero, and
    an expert only such rows chose is not streamed. The dense dispatch
    streams every expert whatever is routed and ignores it. None (a
    program without a mask) routes every row. ``tally`` (a step
    program's MoeTally) counts this layer's routing under that mask; on
    a mesh it routes once more outside the shard_map for that (and
    counts a stream a touched expert: a shard's kernel visits are its
    own).
    """
    if mesh is not None and cfg.experts_held:
        raise ValueError(
            "experts_held (one chip's share of the experts) under a mesh: "
            "the share IS the expert-parallel layout; shard the whole "
            "layer over ep instead")
    if tally is not None and mesh is not None:
        t_sorted, _, e_sorted, group_sizes = _moe_route(lp, cfg, x, live)
        tally.add(e_sorted, t_sorted, group_sizes, live)
    if mesh is None:
        out, sums = _moe_single(
            {k: lp[k] for k in _MOE_LEAVES if k in lp}, x, live, cfg=cfg,
            use_pallas=use_pallas, interpret=interpret,
            counted=0 if tally is None else tally.sums.shape[0])
        if tally is not None:
            tally.sums = tally.sums + sums
        return out
    if _moe_can_shard(mesh, cfg):
        out = _moe_ragged_sharded(lp, cfg, x, mesh, use_pallas, interpret,
                                  live)
        if "be_down" in lp:
            # the down-projection bias is added OUTSIDE the shard_map:
            # inside, the tp psum over the Fm contraction would count it
            # tp times. Per token it is sum_k w_k * be_down[e_k] — the
            # replicated routing matrix against [X, E], trivially
            # GSPMD-safe and exact.
            vals, idx = _route_topk(lp, cfg, x)
            if live is not None:
                vals = jnp.where(live[:, None], vals, 0.0)
            w = jnp.sum(
                jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
                * vals[..., None],
                axis=1,
            )  # [T, X]
            out = out + (
                w @ lp["be_down"].astype(jnp.float32)).astype(x.dtype)
    else:
        out = _moe_dense_dispatch(lp, cfg, x)
    if "shared_gate" in lp:
        out = out + _shared_expert(lp, x, cfg.swiglu_limit)
    return out


def _shared_expert(lp: dict, x: jnp.ndarray, limit: float = 0.0) -> jnp.ndarray:
    """Shared-expert contribution: DeepSeek's is always-on; Qwen2-MoE
    gates it per token with sigmoid(x @ shared_expert_gate)."""
    shared = swiglu(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"],
                    limit=limit)
    if "shared_egate" in lp:
        g = jax.nn.sigmoid(
            x.astype(jnp.float32) @ lp["shared_egate"].astype(jnp.float32)
        )
        shared = shared * g.astype(shared.dtype)
    return shared


def _moe_dense_dispatch(lp: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Dense dispatch: every expert computes every token, the routing
    matrix (zero except each token's top-k) selects at combine. X/k times
    the ragged path's expert-GEMM FLOPs, but fully GSPMD-shardable — the
    equivalence ground truth for tests and the mesh fallback for shapes
    the shard_map ragged path can't cover."""
    vals, idx = _route_topk(lp, cfg, x)  # [T, k]
    w = jnp.sum(
        jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)
        * vals[..., None],
        axis=1,
    )  # [T, X] routing weights
    if cfg.experts_held:  # the held experts' columns
        w = w[:, cfg.expert_first : cfg.expert_first + cfg.experts_held]
    g = _dense_expert_mm(x, lp["we_gate"], "te,xef->txf")
    u = _dense_expert_mm(x, lp["we_up"], "te,xef->txf")
    if "be_gate" in lp:  # gpt-oss per-expert projection biases
        g = g + lp["be_gate"][None]
        u = u + lp["be_up"][None]
    y = _dense_expert_mm(_expert_act(cfg, g, u), lp["we_down"], "txf,xfe->txe")
    if "be_down" in lp:
        y = y + lp["be_down"][None]
    return jnp.einsum("txe,tx->te", y, w.astype(x.dtype))


def moe_ffn_dense(lp: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Full dense-dispatch reference (incl. shared experts) for tests."""
    out = _moe_dense_dispatch(lp, cfg, x)
    if "shared_gate" in lp:
        out = out + _shared_expert(lp, x, cfg.swiglu_limit)
    return out


def _moe_can_shard(mesh, cfg: ModelConfig) -> bool:
    if not {"ep", "tp"} <= set(mesh.axis_names):
        return False
    fm = cfg.moe_intermediate_size or cfg.intermediate_size
    return (
        cfg.num_experts % mesh.shape["ep"] == 0 and fm % mesh.shape["tp"] == 0
    )


def _moe_ragged_sharded(lp: dict, cfg: ModelConfig, x: jnp.ndarray, mesh,
                        use_pallas: bool = False, interpret: bool = False,
                        live=None):
    """shard_map body for ragged MoE over (ep, tp); other axes stay auto.

    gpt-oss rides this path too: the router LOGIT bias is replicated into
    the routing computation, and the per-expert gate/up projection biases
    are ep×tp-sharded with their weights and indexed by each window row's
    LOCAL expert id (recovered from the cumulative local group sizes).
    The down bias is the caller's job (moe_ffn adds it outside — inside,
    the tp psum would multiply it). ``live`` (``_moe_route``): a dead
    row's assignments sort behind every shard's window, into the tail
    that ``valid`` masks already."""
    from jax.sharding import PartitionSpec as P

    T = x.shape[0]
    X = cfg.num_experts
    R = T * cfg.num_experts_per_tok
    ep = mesh.shape["ep"]
    Xl = X // ep
    out_dt = x.dtype
    has_eb = "be_gate" in lp

    def body(x, live, moe_gate, gate_bias, router_bias, we_gate, we_up,
             we_down, be_gate, be_up):
        t_sorted, w_sorted, _e_sorted, group_sizes = _moe_route(
            {"moe_gate": moe_gate, "moe_gate_bias": gate_bias,
             "moe_router_bias": router_bias}, cfg, x, live
        )
        first = lax.axis_index("ep") * Xl
        gs_local = lax.dynamic_slice_in_dim(group_sizes, first, Xl)
        start = jnp.sum(jnp.where(jnp.arange(X) < first, group_sizes, 0))
        count = jnp.sum(gs_local)
        # static [R]-row window at this device's traced offset; rows past
        # ``count`` belong to other devices' experts and are masked out
        xs = _moe_gather(x, t_sorted, live)
        xs = jnp.concatenate([xs, jnp.zeros_like(xs)], 0)
        xs = lax.dynamic_slice_in_dim(xs, start, R)
        t_l = lax.dynamic_slice_in_dim(
            jnp.concatenate([t_sorted, jnp.full((R,), T, t_sorted.dtype)]),
            start, R,
        )
        w_l = lax.dynamic_slice_in_dim(
            jnp.concatenate([w_sorted, jnp.zeros((R,), w_sorted.dtype)]),
            start, R,
        )
        valid = jnp.arange(R) < count
        t_l = jnp.where(valid, t_l, T)  # sacrificial combine row
        w_l = jnp.where(valid, w_l, 0.0)
        plan = _expert_plan(gs_local, R, use_pallas)
        g = _ragged_mm(xs, we_gate, gs_local, use_pallas, interpret, plan)
        u = _ragged_mm(xs, we_up, gs_local, use_pallas, interpret, plan)
        if has_eb:
            # window row r's LOCAL expert: first local group whose
            # cumulative size exceeds r (masked tail rows clamp to the
            # last expert; their combine weight is already zero)
            e_l = jnp.searchsorted(
                jnp.cumsum(gs_local), jnp.arange(R), side="right"
            )
            e_l = jnp.minimum(e_l, Xl - 1)
            g = g + be_gate[e_l]
            u = u + be_up[e_l]
        o = _ragged_mm(_expert_act(cfg, g, u), we_down, gs_local,
                       use_pallas, interpret, plan)
        out = _moe_combine(o, t_l, w_l, T, out_dt)
        return lax.psum(out, ("ep", "tp"))

    def _z(key, shape):  # uniform operand pytree for the shard_map
        v = lp.get(key)
        return v if v is not None else jnp.zeros(shape, jnp.float32)

    def _wspec(w, spec: P) -> object:
        # quantized stacks ({"q", "s"}) shard q like the plain weight
        # and s with the contraction axis dropped (mirrors
        # parallel/mesh._spec_for's derivation for the placed pytree)
        if isinstance(w, dict):
            ps = tuple(spec)
            return {"q": spec, "s": P(*ps[:-2], ps[-1])}
        return spec

    wg, wu, wd = lp["we_gate"], lp["we_up"], lp["we_down"]
    Fm = (wg["q"] if isinstance(wg, dict) else wg).shape[-1]
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(),  # x replicated (batch inputs are replicated engine-side)
            P(),  # the rows' mask (None: an empty operand)
            P(),  # router weights replicated
            P(),  # V3 no-aux gate bias (zeros when absent)
            P(),  # gpt-oss router logit bias (zeros when absent)
            _wspec(wg, P("ep", None, "tp")),  # we_gate [X, E, Fm]
            _wspec(wu, P("ep", None, "tp")),  # we_up
            _wspec(wd, P("ep", "tp", None)),  # we_down [X, Fm, E]
            P("ep", "tp"),  # be_gate [X, Fm] (zeros when absent)
            P("ep", "tp"),  # be_up
        ),
        out_specs=P(),
        check_vma=False,
    )(x, live, lp["moe_gate"], _z("moe_gate_bias", (X,)),
      _z("moe_router_bias", (X,)), wg, wu, wd,
      _z("be_gate", (X, Fm)), _z("be_up", (X, Fm)))


def _ffn(lp: dict, cfg: ModelConfig, h: jnp.ndarray, mesh=None,
         use_pallas: bool = False, interpret: bool = False,
         tally: Optional[MoeTally] = None, live=None) -> jnp.ndarray:
    # branch on the GROUP's own leaves, not cfg.is_moe: DeepSeek's
    # first_k_dense_replace layers are dense inside an MoE model
    if "moe_gate" in lp:
        return moe_ffn(lp, cfg, h, mesh=mesh, use_pallas=use_pallas,
                       interpret=interpret, tally=tally, live=live)
    return swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], cfg.hidden_act,
                  cfg.swiglu_limit)


def _logits(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    head = params["embed"].T if cfg.tie_word_embeddings else params["lm_head"]
    return att.softcap((x @ head).astype(jnp.float32), cfg.final_softcap)


def _qkv(lp: dict, cfg: ModelConfig, x: jnp.ndarray, lora_l=None,
         lora_ids=None, lora_grouped: bool = False):
    q = _mm(x, lp["wq"])
    k = _mm(x, lp["wk"])
    v = _mm(x, lp["wv"])
    if lora_l is not None:
        # per-row LoRA deltas land on the FLAT projections, before bias
        # and qk-norm (norms see base+delta exactly as a merged-weight
        # forward would); rows with id -1 get an exact +0.0
        from ..ops.lora import lora_delta

        q = q + lora_delta(x, lora_l["qa"], lora_l["qb"], lora_ids,
                           lora_grouped)
        k = k + lora_delta(x, lora_l["ka"], lora_l["kb"], lora_ids,
                           lora_grouped)
        v = v + lora_delta(x, lora_l["va"], lora_l["vb"], lora_ids,
                           lora_grouped)
    if "bq" in lp:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    if cfg.qk_norm_full:  # olmo-2: norm the FLAT projection pre-reshape
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    # head counts derive from the projection width, not cfg: under a
    # manual-tp shard_map (parallel/pp.py) lp holds per-device column
    # shards, so this one function serves both global and tp-local views
    D = cfg.head_dim
    q = q.reshape(x.shape[:-1] + (q.shape[-1] // D, D))
    k = k.reshape(x.shape[:-1] + (k.shape[-1] // D, D))
    v = v.reshape(x.shape[:-1] + (v.shape[-1] // D, D))
    if cfg.qk_norm and not cfg.qk_norm_full:
        # qwen3: per-head RMS norm before rope, weight [D]
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    lanes = kv_lanes(cfg)
    if lanes != D:
        # the cache's row width (rope passes the zero lanes through)
        pad = ((0, 0),) * (q.ndim - 1) + ((0, lanes - D),)
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    return q, k, v


def _wo_proj(lp: dict, o_flat: jnp.ndarray, lora_l=None, lora_ids=None,
             lora_grouped: bool = False) -> jnp.ndarray:
    """Attention output projection (+ optional per-row LoRA delta on the
    flat [R, H*D] rows, mirroring ``_qkv``'s q/k/v deltas)."""
    p = _mm_b(o_flat, lp, "wo", "bo")
    if lora_l is not None:
        from ..ops.lora import lora_delta

        p = p + lora_delta(o_flat, lora_l["oa"], lora_l["ob"], lora_ids,
                           lora_grouped)
    return p


def _layer_tail(x, lp: dict, cfg: ModelConfig, o_flat, lora_l=None,
                lora_ids=None, lora_grouped: bool = False, mesh=None,
                use_pallas: bool = False, interpret: bool = False,
                tally: Optional[MoeTally] = None, live=None) -> jnp.ndarray:
    """A layer's second half, the same in every forward: the attention
    rows ``o_flat`` [R, H*D] through the output projection (+ LoRA
    delta) onto the residual ``x`` [R, E], then the FFN sublayer, each
    inside the family's pre/post norms. ``live`` [R] is the program's
    mask of the rows that carry a token, for the expert layers' routing
    (``moe_ffn``). A GQA layer with an output gate (``attn_gate``:
    afmoe) has its rows times sigmoid(h W_g) first, ``h`` the layer's
    normed input, which is ``x``'s norm again (one expression with the
    one that fed q, k and v: the compiler keeps one); the latent path
    gates its rows where it un-absorbs them (``mla.o_proj_gated``)."""
    o_flat = _from_lanes(cfg, o_flat)
    if "attn_gate" in lp and not cfg.is_mla:
        g = jax.nn.sigmoid(_mm(pre_norm(lp, "attn_norm", x, cfg),
                               lp["attn_gate"]).astype(jnp.float32))
        o_flat = (o_flat.astype(jnp.float32) * g).astype(o_flat.dtype)
    x = x + post_norm(
        lp, "attn_post_norm",
        _wo_proj(lp, o_flat, lora_l, lora_ids, lora_grouped), cfg,
    )
    return _ffn_tail(x, lp, cfg, mesh, use_pallas, interpret, tally, live)


def _ffn_tail(x, lp: dict, cfg: ModelConfig, mesh=None,
              use_pallas: bool = False, interpret: bool = False,
              tally: Optional[MoeTally] = None, live=None) -> jnp.ndarray:
    """The FFN sublayer onto the residual, inside the family's norms."""
    h = pre_norm(lp, "mlp_norm", x, cfg)
    return x + post_norm(
        lp, "mlp_post_norm",
        _ffn(lp, cfg, h, mesh=mesh, use_pallas=use_pallas,
             interpret=interpret, tally=tally, live=live), cfg,
    )


# ---------------- LFM2's short convolution and its state ----------------


#: a state whose row is at least this large gets a snapshot pool of its
#: own size and not a row a KV block (``state_snapshot_rows``)
SNAPSHOT_ROW_A_BLOCK_BYTES = 1 << 20


def state_row_bytes(cfg: ModelConfig) -> int:
    """Bytes of ONE sequence's state over all state layers: the
    convolution's last rows in the model's dtype and, for the gated
    delta rule, one float32 [key_dim, value_dim] matrix a value head."""
    item = jnp.dtype(_dtype(cfg)).itemsize
    conv = cfg.conv_layers * (cfg.conv_kernel - 1) * cfg.hidden_size
    lin = cfg.linear_layers * (cfg.linear_conv_kernel - 1) * cfg.linear_conv_dim
    rec = (cfg.linear_layers * cfg.linear_value_heads * cfg.linear_key_dim
           * cfg.linear_value_dim)
    return (conv + lin) * item + rec * 4


def state_snapshot_rows(cfg: ModelConfig, num_blocks: int,
                        snapshots: int = 0) -> int:
    """Rows of the snapshot pool: a row a KV block for a small state
    (LFM2's 72 KiB: every block end can hold one), else ``snapshots``
    rows (GigaChat 3.5's 16.4 MiB: 64 rows are 1 GiB), which the engine
    maps to blocks and reuses."""
    if state_row_bytes(cfg) < SNAPSHOT_ROW_A_BLOCK_BYTES:
        return num_blocks
    return max(1, min(snapshots or 64, num_blocks))


def init_state(cfg: ModelConfig, max_batch: int, num_blocks: int,
               snapshots: int = 0):
    """The per-sequence state that is not keys and values, or None for a
    stack without state layers. A conv layer needs, to go on from token
    t, the last ``conv_kernel - 1`` rows of ``B * x`` (``short_conv``):
    ``conv`` [max_batch, Lc * (K-1) * E] holds them for the sequence in
    each decode slot, ``snap`` [snapshot rows, Lc * (K-1) * E] as they
    stood when a snapshot was taken (``StateTrack``): what a prefix hit
    restores. A row is kept flat: the TPU tiles an array's last two
    dimensions, and rows of K-1 = 2 would be padded to a tile's 16.

    A linear-attention layer (``gated_delta``) has the same rows for its
    convolution over q, k and v, and beside them ``rec`` [Ll, max_batch,
    Hv, Dk, Dv] float32, the delta rule's matrices (layer-major: a
    layer's rows are one contiguous slab, updated in place), with
    ``snap_rec`` [Ll, snapshot rows, Hv, Dk, Dv]."""
    if not cfg.state_layers:
        return None
    if cfg.conv_layers and cfg.linear_layers:
        raise ValueError("conv and linear-attention layers in one stack "
                         "are not supported")
    n_snap = state_snapshot_rows(cfg, num_blocks, snapshots)
    if cfg.conv_layers:
        width = cfg.conv_layers * (cfg.conv_kernel - 1) * cfg.hidden_size
    else:
        width = (cfg.linear_layers * (cfg.linear_conv_kernel - 1)
                 * cfg.linear_conv_dim)
    state = {"conv": jnp.zeros((max_batch, width), _dtype(cfg)),
             "snap": jnp.zeros((n_snap, width), _dtype(cfg))}
    if cfg.linear_layers:
        mat = (cfg.linear_value_heads, cfg.linear_key_dim,
               cfg.linear_value_dim)
        state["rec"] = jnp.zeros(
            (cfg.linear_layers, max_batch) + mat, jnp.float32)
        state["snap_rec"] = jnp.zeros(
            (cfg.linear_layers, n_snap) + mat, jnp.float32)
    return state


def short_conv(lp: dict, h: jnp.ndarray, parts: list):
    """LFM2's gated short convolution over SEGMENTS that each start from
    an incoming state — a decode row is a segment of one row, a chunk of
    a prompt a segment that starts from its sequence's state, a fresh
    prompt one that starts from zeros.

        [B, C, u] = split3(h W_in);  v = B * u
        c[t] = sum_k w[k] * v[t - (K-1) + k]       (depthwise, causal)
        y = (C * c) W_out

    ``h`` [R, E] holds the parts' rows in order; a part is
    ``(S, T, state_in [S, K-1, E])``: S segments of T rows, and the K-1
    rows of ``v`` before each. Returns (y [R, E], one ``v_ext``
    [S, K-1 + T, E] a part: the state followed by the segment's own
    ``v``, so the state after r rows is ``v_ext[:, r : r + K-1]``)."""
    E = h.shape[-1]
    b, c, u = jnp.split(_mm(h, lp["conv_in"]), 3, axis=-1)
    v = b * u
    w = lp["conv_w"].astype(jnp.float32)  # [K, E], w[K-1] on the row itself
    convs, exts, r = [], [], 0
    for S, T, state_in in parts:
        v_ext = jnp.concatenate(
            [state_in.astype(v.dtype), v[r : r + S * T].reshape(S, T, E)], 1)
        acc = sum(w[k] * v_ext[:, k : k + T].astype(jnp.float32)
                  for k in range(w.shape[0]))
        convs.append(acc.astype(v.dtype).reshape(S * T, E))
        exts.append(v_ext)
        r += S * T
    conv = convs[0] if len(convs) == 1 else jnp.concatenate(convs)
    return _mm(c * conv, lp["conv_out"]), exts


# ---------------- GigaChat 3.5's gated delta rule ----------------

#: rows of one block of the chunked form: inside a block the delta rule
#: is a triangular solve and matrix products, across blocks a scan
DELTA_BLOCK = 64
_HI = lax.Precision.HIGHEST


def delta_rule_step(q, k, v, g, beta, S):
    """ONE token of the gated delta rule for every (segment, value head):

        S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

    q, k [N, Hv, Dk], v [N, Hv, Dv], g, beta [N, Hv], S [N, Hv, Dk, Dv],
    all float32. Returns (o [N, Hv, Dv], S). A row with g = 0 and
    beta = 0 leaves S as it was."""
    S = S * jnp.exp(g)[..., None, None]
    d = beta[..., None] * (v - jnp.sum(S * k[..., None], axis=-2))
    S = S + k[..., None] * d[..., None, :]
    return jnp.sum(S * q[..., None], axis=-2), S


def delta_rule_chunked(q, k, v, g, beta, S, block: int = DELTA_BLOCK):
    """T tokens of the gated delta rule from the state ``S``, in blocks
    of ``block`` rows (the chunked form of arXiv:2412.06464): inside a
    block the T dependent steps become one unit-lower-triangular solve
    and matrix products, and the state moves once a block.

    q, k [N, Hv, T, Dk], v [N, Hv, T, Dv], g, beta [N, Hv, T], S
    [N, Hv, Dk, Dv], float32, T a multiple of ``block``. Returns (o
    [N, Hv, T, Dv], S after the last row); rows with g = 0 and beta = 0
    (padding) leave the state as it was. Products are taken at full
    float32 precision: the recurrence (``delta_rule_step``) is exact in
    float32, and the two forms have to agree to its rounding."""
    N, Hv, T, Dk = q.shape
    nb = T // block
    blk = lambda a: a.reshape((N, Hv, nb, block) + a.shape[3:])  # noqa: E731
    q, k, v, g, beta = blk(q), blk(k), blk(v), blk(g), blk(beta)
    gc = jnp.cumsum(g, axis=-1)  # decay from the block's start, log
    i = jnp.arange(block)
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(i[:, None] >= i[None, :], diff, -jnp.inf))
    kb = k * beta[..., None]
    # (I + A) u = beta v, (I + A) w = beta k exp(gc): A strictly lower
    A = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI) * decay
    A = jnp.where(i[:, None] > i[None, :], A, 0.0) + jnp.eye(block)
    rhs = jnp.concatenate(
        [v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(
        A, rhs, lower=True, unit_diagonal=True)
    u, w = sol[..., : v.shape[-1]], sol[..., v.shape[-1]:]
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(gc)[..., None]
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    last = jnp.exp(gc[..., -1])

    def one(S, xs):
        u_b, w_b, qk_b, q_b, k_b, last_b = xs
        v_new = u_b - jnp.einsum("nhtk,nhkv->nhtv", w_b, S, precision=_HI)
        o = (jnp.einsum("nhtk,nhkv->nhtv", q_b, S, precision=_HI)
             + jnp.einsum("nhts,nhsv->nhtv", qk_b, v_new, precision=_HI))
        S = S * last_b[..., None, None] + jnp.einsum(
            "nhtk,nhtv->nhkv", k_b, v_new, precision=_HI)
        return S, o

    front = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    S, o = lax.scan(one, S, tuple(map(front, (u, w, qk, q_in, k_out, last))))
    return jnp.moveaxis(o, 0, 2).reshape(N, Hv, T, -1), S


def gated_delta(lp: dict, cfg: ModelConfig, h: jnp.ndarray, parts: list,
                steps: Optional[list] = None):
    """GigaChat 3.5's linear-attention operator (Gated DeltaNet) over
    SEGMENTS that each start from an incoming state, as ``short_conv``:

        [q, k, v, z] = h W_qkvz;  [b, a] = h W_ba
        [q, k, v] = silu(causal depthwise conv_K([q, k, v]))
        q = l2norm(q) Dk^-0.5, k = l2norm(k)    (a head; a key head
                                                 serves Hv / Hk value heads)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        o_t = delta rule over t (``delta_rule_step``), state float32
        y = (rms(o) (1 + w_o) * gate_scale sigmoid(z)) W_out

    ``h`` [R, E] holds the parts' rows in order; a part is ``(S, T, n
    [S], conv_in [S, K-1, C], rec_in [S, Hv, Dk, Dv])``: S segments of T
    rows of which the first ``n`` are real, the K-1 rows of the
    convolution's input before each and the matrices they start from. A
    segment of one row runs the recurrence, a longer one the chunked
    form; rows past ``n`` leave the matrices as they were. Returns (y
    [R, E], one ``v_ext`` [S, K-1 + T, C] a part, as ``short_conv``, one
    ``rec`` [S, Hv, Dk, Dv] a part: the matrices after row n).
    ``steps`` may name, a part of one-row segments, another
    implementation of ``delta_rule_step`` (the Pallas kernel over the
    whole state, ``StateTrack.run_linear``: ``rec_in`` is then whatever
    that one takes); None: the ``jax.numpy`` one."""
    Hk, Hv = cfg.linear_key_heads, cfg.linear_value_heads
    Dk, Dv, C = cfg.linear_key_dim, cfg.linear_value_dim, cfg.linear_conv_dim
    f32 = jnp.float32
    qkvz = _mm(h, lp["lin_qkvz"])
    mix, z = qkvz[:, :C], qkvz[:, C:]
    ba = h.astype(f32) @ lp["lin_ba"].astype(f32)
    beta_all = jax.nn.sigmoid(ba[:, :Hv])
    g_all = -jnp.exp(lp["lin_A_log"].astype(f32)) * jax.nn.softplus(
        ba[:, Hv:] + lp["lin_dt_bias"].astype(f32))
    w = lp["lin_conv_w"].astype(f32)  # [K, C], w[K-1] on the row itself
    outs, exts, recs, r = [], [], [], 0
    for pi, (S, T, n, conv_in, rec_in) in enumerate(parts):
        step = (steps[pi] if steps else None) or delta_rule_step
        rows = slice(r, r + S * T)
        r += S * T
        v_ext = jnp.concatenate(
            [conv_in.astype(mix.dtype), mix[rows].reshape(S, T, C)], 1)
        acc = sum(w[j] * v_ext[:, j : j + T].astype(f32)
                  for j in range(w.shape[0]))
        acc = jax.nn.silu(acc)
        q = acc[..., : Hk * Dk].reshape(S, T, Hk, Dk)
        k = acc[..., Hk * Dk : 2 * Hk * Dk].reshape(S, T, Hk, Dk)
        v = acc[..., 2 * Hk * Dk :].reshape(S, T, Hv, Dv)
        l2 = lambda a: a * lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q = jnp.repeat(l2(q) * Dk ** -0.5, Hv // Hk, axis=2)
        k = jnp.repeat(l2(k), Hv // Hk, axis=2)
        real = (jnp.arange(T)[None, :] < n[:, None])[..., None]  # [S, T, 1]
        g = jnp.where(real, g_all[rows].reshape(S, T, Hv), 0.0)
        beta = jnp.where(real, beta_all[rows].reshape(S, T, Hv), 0.0)
        if T == 1:
            with jax.named_scope("linear_attn_recurrent"):
                o, rec = step(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], rec_in)
            o = o[:, None]
        else:
            block = min(DELTA_BLOCK, T)
            pad = -T % block
            tm = lambda a: jnp.moveaxis(  # noqa: E731
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)),
                1, 2)
            with jax.named_scope("linear_attn_chunked"):
                o, rec = delta_rule_chunked(
                    tm(q), tm(k), tm(v), tm(g), tm(beta), rec_in, block)
            o = jnp.moveaxis(o, 2, 1)[:, :T]
        outs.append(o.reshape(S * T, Hv, Dv))
        exts.append(v_ext)
        recs.append(rec)
    o = outs[0] if len(outs) == 1 else jnp.concatenate(outs)
    o = o * lax.rsqrt(
        jnp.mean(o * o, -1, keepdims=True) + cfg.linear_o_norm_eps)
    o = o * (1.0 + lp["lin_o_norm"].astype(f32))
    gate = cfg.linear_gate_scale * jax.nn.sigmoid(
        z.astype(f32)).reshape(-1, Hv, Dv)
    y = (o * gate).astype(h.dtype).reshape(-1, Hv * Dv)
    return _mm(y, lp["lin_out"]), exts, recs


def linear_step_walks_live(cfg: ModelConfig, use_pallas: bool,
                           interpret: bool = False) -> bool:
    """Whether a decode batch's recurrence runs in the Pallas kernel,
    which moves the LIVE rows' matrices only (ops/gated_delta_pallas);
    ``delta_rule_step`` carries every slot's through."""
    from ..ops.gated_delta_pallas import kernel_serves

    return bool(cfg.linear_layers) and use_pallas and (
        interpret or kernel_serves(cfg.linear_value_heads,
                                   cfg.linear_key_dim, cfg.linear_value_dim))


class Segments(NamedTuple):
    """S segments of T rows each, as a step program hands them to the
    state layers: a decode batch (T = 1) or prefill chunks."""

    rows: Optional[jnp.ndarray]  # [S] state row of each; None: s itself
    n: jnp.ndarray  # [S] real rows of each (0: a dead slot or segment)
    hist: jnp.ndarray  # [S] tokens before each segment's first row
    tables: jnp.ndarray  # [S, M] block tables
    T: int
    # [S] the snapshot row that takes each segment's state after its last
    # real row (an index past the pool: none), for a state that is
    # snapshotted at a segment's end only; None: no segment's is
    snap: Optional[jnp.ndarray] = None


class StateTrack:
    """What the state layers of ONE step program read and leave behind,
    gathered while it is traced. Each segment starts from its row of the
    state (``init_state``); the state after each segment's last REAL row
    is written back (a dead segment's is left as it was), and snapshots
    are taken WHERE THE KIND OF STATE ALLOWS:

      * a conv layer's state is a window of rows the layer has anyway
        (``v_ext``), so for every block of the KV pool whose last token
        is among a segment's real rows the state at that token goes into
        ``state["snap"]`` under the block's id (a row a block: the pool
        is as long as the KV pool). The block is committed to the prefix
        cache only after the program that filled it, so a committed
        block always has its snapshot;
      * a recurrent matrix exists at a segment's END only, so a segment
        whose ``Segments.snap`` names a row leaves its end state (both
        parts) there; the engine ends a chunk where it wants a snapshot
        and keeps the map from blocks to rows (engine.SnapshotPool).
    """

    def __init__(self, cfg: ModelConfig, state: dict, groups: list,
                 block_size: int):
        self.cfg, self.state, self.groups = cfg, state, groups
        self.linear = bool(cfg.linear_layers)
        conv = state["conv"]
        if self.linear:
            K, W = cfg.linear_conv_kernel, cfg.linear_conv_dim
            self.rec, self.snap_rec = state["rec"], state["snap_rec"]
        else:
            K, W = cfg.conv_kernel, cfg.hidden_size
        row = (cfg.state_layers, K - 1, W)
        self.start = [
            (conv if g.rows is None else conv[g.rows]).reshape((-1,) + row)
            for g in groups]
        # a group: where in a layer's v_ext the state after the last real
        # row and at each block end lies, and the blocks that end there
        self.last, self.block_end, self.blocks = [], [], []
        self.after = [[] for _ in groups]  # a state layer: [S, K-1, W]
        self.ends = [[] for _ in groups]  # a conv layer: [S, nb, K-1, W]
        tap = jnp.arange(K - 1)
        for g in groups:
            S, M = g.tables.shape
            seg = jnp.arange(S)
            self.last.append((seg[:, None], g.n[:, None] + tap))
            if self.linear:
                continue
            nb = g.T // block_size + 1  # block ends a segment can hold
            j = g.hist[:, None] // block_size + jnp.arange(nb)[None]
            e = (j + 1) * block_size - g.hist[:, None]  # rows up to the end
            ok = (e >= 1) & (e <= g.n[:, None])
            blk = jnp.take_along_axis(g.tables, jnp.clip(j, 0, M - 1), 1)
            self.block_end.append(
                (seg[:, None, None], jnp.clip(e, 0, g.T)[:, :, None] + tap))
            # an index past the pool is dropped by the scatter
            self.blocks.append(
                jnp.where(ok, blk, state["snap"].shape[0]).reshape(-1))

    def parts(self, ci: int) -> list:
        return [(st.shape[0], g.T, st[:, ci])
                for g, st in zip(self.groups, self.start)]

    def add(self, exts: list) -> None:
        for gi, v_ext in enumerate(exts):
            self.after[gi].append(v_ext[self.last[gi]])
            if not self.linear:
                self.ends[gi].append(v_ext[self.block_end[gi]])

    def run_linear(self, lp: dict, h: jnp.ndarray, ci: int,
                   use_pallas: bool = False,
                   interpret: bool = False) -> jnp.ndarray:
        """Linear layer ``ci``'s operator over the groups; its matrices
        move in place in ``rec`` (and into ``snap_rec`` where a segment
        names a snapshot row). With kernels on, a decode batch (every
        slot a segment of one row) takes its step in the Pallas kernel,
        which reads and writes the LIVE rows' matrices of the layer once,
        where they lie, and leaves a dead slot's alone
        (ops/gated_delta_pallas)."""
        from ..ops import gated_delta_pallas as gdp

        cfg = self.cfg
        kernel = linear_step_walks_live(cfg, use_pallas, interpret)

        def in_place(n):
            def step(q, k, v, g, beta, _rec_in):
                o, self.rec = gdp.linear_attn_recurrent_step(
                    q, k, v, g, beta, self.rec, jnp.int32(ci), n,
                    interpret=interpret)
                return o, None

            return step

        parts, steps = [], []
        for g, st in zip(self.groups, self.start):
            whole = kernel and g.rows is None and g.T == 1
            rec_in = (None if whole else self.rec[ci] if g.rows is None
                      else self.rec[ci, g.rows])
            parts.append((st.shape[0], g.T, g.n, st[:, ci], rec_in))
            steps.append(in_place(g.n) if whole else None)
        y, exts, recs = gated_delta(lp, cfg, h, parts, steps)
        self.add(exts)
        for g, rec in zip(self.groups, recs):
            if rec is None:  # the kernel has written self.rec
                continue
            self.rec = (self.rec.at[ci].set(rec) if g.rows is None
                        else self.rec.at[ci, g.rows].set(rec, mode="drop"))
            if g.snap is not None:
                self.snap_rec = self.snap_rec.at[ci, g.snap].set(
                    rec, mode="drop")
        return y

    def finish(self) -> dict:
        conv, snap = self.state["conv"], self.state["snap"]
        W = conv.shape[1]
        for gi, g in enumerate(self.groups):
            # [S, Ls, K-1, W] and [S, nb, Lc, K-1, W], as flat rows
            after = jnp.stack(self.after[gi], 1).reshape(-1, W)
            conv = after if g.rows is None else conv.at[g.rows].set(
                after, mode="drop")
            if not self.linear:
                ends = jnp.stack(self.ends[gi], 2).reshape(-1, W)
                snap = snap.at[self.blocks[gi]].set(ends, mode="drop")
            elif g.snap is not None:
                snap = snap.at[g.snap].set(after, mode="drop")
        out = {"conv": conv, "snap": snap}
        if self.linear:
            out.update(rec=self.rec, snap_rec=self.snap_rec)
        return out


def _conv_layer(x, lp: dict, cfg: ModelConfig, l: int,
                track: Optional[StateTrack], **ffn_kw) -> jnp.ndarray:
    """One state layer of a hybrid stack onto the residual ``x`` [R, E]:
    the operator (LFM2's short convolution or GigaChat 3.5's gated delta
    rule, by the layer's leaves) over ``track``'s segments (one segment
    from zeros without a track: ``dense_forward``), then the FFN
    sublayer."""
    h = pre_norm(lp, "attn_norm", x, cfg)
    R = x.shape[0]
    if "lin_qkvz" in lp:
        if track is None:
            zeros = jnp.zeros(
                (1, cfg.linear_conv_kernel - 1, cfg.linear_conv_dim), x.dtype)
            rec = jnp.zeros((1, cfg.linear_value_heads, cfg.linear_key_dim,
                             cfg.linear_value_dim), jnp.float32)
            y, _, _ = gated_delta(
                lp, cfg, h, [(1, R, jnp.full((1,), R), zeros, rec)])
        else:
            y = track.run_linear(
                lp, h, cfg.op_index(l), ffn_kw.get("use_pallas", False),
                ffn_kw.get("interpret", False))
    elif track is None:
        zeros = jnp.zeros((1, cfg.conv_kernel - 1, x.shape[-1]), x.dtype)
        y, _ = short_conv(lp, h, [(1, R, zeros)])
    else:
        y, exts = short_conv(lp, h, track.parts(cfg.op_index(l)))
        track.add(exts)
    return _ffn_tail(x + post_norm(lp, "attn_post_norm", y, cfg), lp, cfg,
                     **ffn_kw)



# ---------------- prefill (one sequence, chunked) ----------------


@partial(
    jax.jit,
    static_argnames=("cfg", "use_pallas", "mesh", "use_ring", "moe_counters"),
    donate_argnames=("k_cache", "v_cache", "state"),
)
def prefill(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [T] padded chunk
    block_table: jnp.ndarray,  # [M] covers history + padded chunk
    history_len: jnp.ndarray,  # scalar int32: tokens already cached
    valid_len: jnp.ndarray,  # scalar int32: real tokens in this chunk
    k_cache: jnp.ndarray,  # [L, N, bs, Hkv, D] (donated)
    v_cache: jnp.ndarray,
    use_pallas: bool = False,
    mesh=None,
    use_ring: bool = False,
    # int8-with-scales device cache: per-page f32 scale planes [L, N]
    # (NOT donated — the engine diffs them for gauges). When present the
    # chunk lands quantized and the return grows to
    # (logits, k_cache, v_cache, k_scales, v_scales).
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    # multi-LoRA lane: stacked {qa,qb,ka,kb,va,vb,oa,ob} [L, NA, ...]
    # adapter pytree + this sequence's adapter slot (scalar int32; -1 =
    # base model — the deltas are then an exact +0.0). The return shape
    # is unchanged; lora forces the unrolled layer loop.
    lora=None,
    adapter_id: Optional[jnp.ndarray] = None,
    # expert models: also return the chunk's routing counters (MoeTally
    # sums, int32 [3]) as the LAST output. Counted or not, only the rows
    # below valid_len are routed (``_moe_route``'s live mask)
    moe_counters: bool = False,
    # LFM2: the conv layers' state (``init_state``, donated) and the row
    # of it this sequence starts from and leaves its state in; the new
    # state follows v_cache in the return
    state: Optional[dict] = None,
    slot: Optional[jnp.ndarray] = None,
    # a state that is snapshotted at a segment's end only (GigaChat
    # 3.5's matrices): the snapshot row that takes this chunk's end
    # state, or an index past the pool for none
    snap_row: Optional[jnp.ndarray] = None,
):
    """Process one (chunk of a) prompt; returns (last_hidden_logits, caches).

    Supports chunked prefill and prefix-cache hits: ``history_len`` tokens
    are already in the cache and are attended to but not recomputed
    (the reference gets this from vLLM's chunked-prefill scheduler patch).

    On a pp>1 mesh (dense models, divisible shapes) the layer loop runs
    as a STAGED PIPELINE: microbatches flow through the pp stages via
    ppermute so stages compute concurrently (parallel/pp.py), instead of
    the scan all-gathering one stage's weights per step.

    ``use_ring`` (static; history-free chunks only — the ENGINE gates it
    on history == 0, an sp>1 mesh, T % sp == 0, prompt length >= its
    ring threshold, full attention) routes the chunk's self-attention
    through sequence-parallel ring attention over the sp axis
    (parallel/ring_attention.py) instead of the dense score matrix:
    each device holds T/sp query rows and the KV shards — or, for MLA,
    the far smaller compressed (c_kv, k_pe) latent shards — rotate the
    ICI ring. Cache writes are unchanged, so decode and later chunked
    prefill continue through the paged path.
    """
    quantized = k_scales is not None
    if quantized:
        # scale planes thread per layer, so: no staged pipeline (stage
        # movers don't carry planes), no ring (ring writes full-width),
        # no MLA (the engine gates MLA+int8 loudly at init)
        assert not use_ring and not cfg.is_mla
    if lora is not None:
        # adapters slice per layer (unrolled loop), don't ride the
        # staged pipeline, and MLA/ring are gated at engine init
        assert not use_ring and not cfg.is_mla
        lora_ids = jnp.full((tokens.shape[0],), adapter_id, jnp.int32)
    else:
        lora_ids = None
    if mesh is not None and not use_ring and not quantized and lora is None:
        from ..parallel.pp import can_pipeline, pick_n_micro, pipelined_prefill

        n_micro = pick_n_micro(mesh, tokens.shape[0])
        if can_pipeline(mesh, cfg, tokens.shape[0], n_micro):
            return pipelined_prefill(
                params, cfg, tokens, block_table, history_len, valid_len,
                k_cache, v_cache, mesh, n_micro, use_pallas=use_pallas,
            )
    if use_ring:
        assert mesh is not None and mesh.shape.get("sp", 1) > 1
        assert cfg.sliding_window == 0 and not cfg.layer_windows
        assert not cfg.attn_sinks
    T = tokens.shape[0]
    x = _embed(params, cfg, tokens)  # [T, E]
    positions = history_len + jnp.arange(T)
    live = jnp.arange(T) < valid_len  # the bucket's padding routes nowhere
    tally = MoeTally(MoeTally.zeros(cfg)) if moe_counters else None
    if cfg.is_mla:
        from . import mla

        inv_freq, msc = mla.mla_rope_freqs(cfg)
        scale = cfg.mla_softmax_scale()
        rope_global = None  # the latent layers rotate under their own law
    else:
        rope_global = (_rope_freqs(cfg), _rope_attention_scaling(cfg))
        scale = attn_query_scale(cfg)

    rope_local = _rope_local(cfg)
    # the scanned loop's one law: every layer is of layer 0's kind
    rope_scan = rope_for_layer(cfg, 0, rope_global, rope_local)
    track = None
    if cfg.state_layers:
        assert state is not None and not quantized and lora is None
        track = StateTrack(cfg, state, [Segments(
            slot[None], valid_len[None], history_len[None],
            block_table[None], T,
            None if snap_row is None else snap_row[None])],
            k_cache.shape[3])

    def body(carry, layer_in, window=cfg.sliding_window, freqs=None,
             scales=None, lora_l=None, table=None):
        x = carry
        lp, kc, vc = layer_in
        # (a window_kv_pool model: the table of the layer's own pool)
        bt = block_table if table is None else table
        h = pre_norm(lp, "attn_norm", x, cfg)
        if cfg.is_mla:
            from . import mla

            q_eff, q_pe, c_kv, k_pe = mla.mla_q_and_latent(
                lp, cfg, h, positions, inv_freq, msc
            )
            kc = att.write_chunk_to_cache(
                kc, c_kv[:, None, :], block_table, history_len
            )
            vc = att.write_chunk_to_cache(
                vc, k_pe[:, None, :], block_table, history_len
            )
            if use_ring:
                # sequence-parallel exact attention over the sp ring,
                # rotating the COMPRESSED latents (C+R elements/token of
                # ICI traffic instead of 2*H*D of repeated K/V)
                from ..parallel.ring_attention import (
                    mla_ring_attention_sharded,
                )

                out_lat = mla_ring_attention_sharded(
                    q_eff, q_pe, c_kv, k_pe, mesh, scale
                )
            elif use_pallas and mesh is not None:
                from ..ops import mla_attention_pallas as _mla_ops

                out_lat = _mla_ops.mla_paged_prefill_attention_sharded(
                    q_eff, q_pe, kc, vc, block_table, history_len, scale,
                    mesh,
                )
            elif use_pallas:
                from ..ops import mla_attention_pallas as _mla_ops

                out_lat = _mla_ops.mla_paged_prefill_attention(
                    q_eff, q_pe, kc, vc, block_table, history_len, scale,
                )
            else:
                out_lat = mla.mla_prefill_attention_xla(
                    q_eff, q_pe, kc, vc, block_table, history_len,
                    valid_len, scale,
                )
            o = mla.o_proj_gated(lp, cfg, out_lat, h).astype(x.dtype)
        else:
            q, k, v = _qkv(lp, cfg, h, lora_l, lora_ids)
            q, k = rotate_qk(
                q, k, positions, rope_scan if freqs is None else freqs)
            if scales is None:
                ks_l = vs_l = None
                kc = att.write_chunk_to_cache(kc, k, bt, history_len)
                vc = att.write_chunk_to_cache(vc, v, bt, history_len)
            else:
                ks_l, vs_l = scales
                kc, ks_l = att.write_chunk_to_cache_quantized(
                    kc, ks_l, k, bt, history_len, valid_len
                )
                vc, vs_l = att.write_chunk_to_cache_quantized(
                    vc, vs_l, v, bt, history_len, valid_len
                )
            if use_ring:
                from ..parallel.ring_attention import ring_attention_sharded

                H = q.shape[1]
                o = ring_attention_sharded(
                    q, att.repeat_kv(k, H // k.shape[1], axis=1),
                    att.repeat_kv(v, H // v.shape[1], axis=1),
                    mesh, scale,
                )
            else:
                o = att.chunk_attention_with_cache(
                    q, k, v, kc, vc, bt, history_len, valid_len,
                    scale, use_pallas=use_pallas, mesh=mesh,
                    window=window, sinks=lp.get("sinks"),
                    cap=cfg.attn_softcap,
                    k_scales=ks_l, v_scales=vs_l,
                )
        x = _layer_tail(
            x, lp, cfg, o.reshape(T, -1), lora_l, lora_ids, mesh=mesh,
            use_pallas=use_pallas, tally=tally, live=live,
        )
        if scales is not None:
            return x, (kc, vc, ks_l, vs_l)
        return x, (kc, vc)

    def lora_for_layer(l):
        return (
            None if lora is None
            else jax.tree.map(lambda arr: arr[l], lora)
        )

    if quantized:
        # per-layer scale-plane slices must thread through every write,
        # so the layer loop unrolls (the scan body cannot in-place
        # scatter the planes without a full re-stack copy per layer)
        for l, lp in _layers(params, cfg, mesh):
            x, (kc_l, vc_l, ks_l, vs_l) = body(
                x, (lp, k_cache[l], v_cache[l]),
                window=window_for_layer(cfg, l),
                freqs=rope_for_layer(cfg, l, rope_global, rope_local),
                scales=(k_scales[l], v_scales[l]),
                lora_l=lora_for_layer(l),
            )
            k_cache = k_cache.at[l].set(kc_l)
            v_cache = v_cache.at[l].set(vc_l)
            k_scales = k_scales.at[l].set(ks_l)
            v_scales = v_scales.at[l].set(vs_l)
    elif cfg.layer_windows or lora is not None or cfg.layer_ops:
        # heterogeneous attention (gpt-oss alternating sliding/full):
        # the window width is trace-static PER LAYER, so the layer loop
        # unrolls — a lax.scan body cannot carry a per-layer mask shape.
        # LoRA rides the same unrolled loop: adapter stacks slice per
        # layer with a static index (quantized-KV precedent). So does an
        # LFM2 stack: its operator differs by layer, and an attention
        # layer's cache index is its ordinal among them.
        kcs, vcs, tbls = _pools(cfg, k_cache, v_cache, block_table)
        for l, lp in _layers(params, cfg, mesh):
            if _is_state_layer(lp):
                x = _conv_layer(x, lp, cfg, l, track, mesh=mesh,
                                use_pallas=use_pallas, tally=tally,
                                live=live)
                continue
            p, a = cfg.kv_pool(l), cfg.kv_index(l)
            x, (kc_l, vc_l) = body(
                x, (lp, kcs[p][a], vcs[p][a]),
                window=window_for_layer(cfg, l),
                freqs=rope_for_layer(cfg, l, rope_global, rope_local),
                lora_l=lora_for_layer(l), table=tbls[p],
            )
            kcs[p] = kcs[p].at[a].set(kc_l)
            vcs[p] = vcs[p].at[a].set(vc_l)
        k_cache, v_cache = _unpool(cfg, kcs, vcs)
    else:
        x, k_cache, v_cache = _scan_groups(
            body, x, params, cfg, k_cache, v_cache, tally=tally
        )
    x = model_norm(x, params["final_norm"], cfg)
    # logits for the last *real* token of the chunk
    last = jnp.clip(valid_len - 1, 0, T - 1)
    logits = _logits(params, cfg, x[last])
    out = (logits, k_cache, v_cache)
    if track is not None:
        out += (track.finish(),)
    if quantized:
        out += (k_scales, v_scales)
    return out + (tally.sums,) if moe_counters else out


# ---------------- batched decode step ----------------


def _decode_body(
    params, cfg, tokens, positions, block_tables, seq_lens,
    k_cache, v_cache, use_pallas, mesh=None, interpret=False,
    k_scales=None, v_scales=None, lora=None, adapter_ids=None,
    moe_tally=None, state=None, live=None,
):
    """Shared un-jitted decode forward (one token per sequence).

    ONE rule picks the layer loop, from what the program can observe:
    kernels on (``use_pallas``) and no attention softcap -> the MERGED
    one-write loop of the cache kind (attention takes the current token
    out of the cache, all layers' writes batch into one in-place Pallas
    append); otherwise WRITE-THEN-ATTEND (XLA scatter, then attention
    over the cache: what CPU tier-1 runs and every parity test uses as
    its reference; gemma-2's caps live in the XLA attention). Two loops
    a cache kind (GQA, MLA latents), each reached by exactly one
    condition; nothing else selects a loop.

    Every loop is an UNROLLED python loop over layers with static layer
    indices: the caches are updated in place on the donated stacked
    arrays, and the attention kernels take them WHOLE with the layer's
    index. A ``k_cache[l]`` operand of a kernel is a copy of the pool
    (ops/paged_attention_pallas module docs), and so are the re-stacked
    outputs of a scan over layers: decode is supposed to stream WEIGHTS,
    not copy the KV pool (PERF.md section 6, PR 29).

    ``k_scales``/``v_scales`` ([L, N] f32, int8-with-scales device cache)
    thread through every write (scale growth + page requant) and attention
    read (fused dequant); when present the return grows to
    (logits, k_cache, v_cache, k_scales, v_scales, n_requants).
    ``live`` [B] bool (the caller's mask of the slots that hold a
    sequence; None: a program that has none) keeps dead slots out of the
    expert layers' routing (``_moe_route``), and ``moe_tally`` (the
    caller's MoeTally) counts that routing. ``state`` (LFM2:
    ``init_state``) is the conv layers' state, row b the sequence of
    decode slot b; the new state is the LAST output. A dead slot (length
    0) keeps its row as it was: a sequence that is still prefilling may
    own it already."""
    quantized = k_scales is not None
    track = None
    if cfg.state_layers:
        assert state is not None and not quantized and lora is None
        track = StateTrack(cfg, state, [Segments(
            None, (seq_lens > 0).astype(jnp.int32), positions,
            block_tables, 1)], k_cache.shape[3])
    if quantized:
        if cfg.is_mla:
            raise ValueError("int8 device KV scales: MLA is gated at "
                             "engine init (absorbed-matmul latents)")
        k_scales0, v_scales0 = k_scales, v_scales
    if lora is not None and cfg.is_mla:
        raise ValueError("LoRA adapters: MLA is gated at engine init "
                         "(deltas attach to the GQA projections)")
    merged = use_pallas and not cfg.attn_softcap
    B = tokens.shape[0]
    x = _embed(params, cfg, tokens)  # [B, E]
    if cfg.is_mla:
        from . import mla as _mla
        from ..ops import mla_attention_pallas as _mla_ops

        inv_freq, msc = _mla.mla_rope_freqs(cfg)
        scale = cfg.mla_softmax_scale()
        rope_global = None  # the latent layers rotate under their own law
    else:
        rope_global = (_rope_freqs(cfg), _rope_attention_scaling(cfg))
        scale = attn_query_scale(cfg)

    def layer_tail(x, lp, o, lora_l=None):
        return _layer_tail(
            x, lp, cfg, o.reshape(B, -1), lora_l, adapter_ids, mesh=mesh,
            use_pallas=use_pallas, interpret=interpret, tally=moe_tally,
            live=live,
        )

    rope_local = _rope_local(cfg)

    def layer_qkv(x, lp, l, lora_l=None):
        h = pre_norm(lp, "attn_norm", x, cfg)
        # q: [B, H, D], k/v: [B, Hkv, D]
        q, k, v = _qkv(lp, cfg, h, lora_l, adapter_ids)
        q, k = rotate_qk(
            q, k, positions, rope_for_layer(cfg, l, rope_global, rope_local))
        return q, k, v

    def mla_q_and_latent(x, lp):
        """(the normed input, for the output gate; q_eff, q_pe, c_kv, k_pe)"""
        h = pre_norm(lp, "attn_norm", x, cfg)
        return (h,) + _mla.mla_q_and_latent(
            lp, cfg, h, positions, inv_freq, msc)

    def lora_for_layer(l):
        return (
            None if lora is None
            else jax.tree.map(lambda arr: arr[l], lora)
        )

    def layers():  # (l, leaves) in forward order, for the unrolled loops
        return _layers(params, cfg, mesh)

    def conv_layer(x, lp, l):
        return _conv_layer(
            x, lp, cfg, l, track, mesh=mesh, use_pallas=use_pallas,
            interpret=interpret, tally=moe_tally, live=live)

    # by pool (``_pools``): the caches, their tables, and the token's
    # slot in each
    kcs, vcs, tbls = _pools(cfg, k_cache, v_cache, block_tables)
    slots = [att.decode_slot_indices(t, positions, kc.shape[3])
             for t, kc in zip(tbls, kcs)]
    blk, off = slots[0]
    hist_lens = seq_lens - 1  # cache contents EXCLUDE the new token
    if merged and cfg.is_mla:
        # MERGED one-write loop, MLA flavor: the latent kernel scores
        # history with stats, the current token's (c_kv, k_pe) folds in
        # via the flash merge, and ALL layers' latent writes batch into
        # one in-place Pallas append — same 2L-scatters-to-1-append trick
        # as the GQA merged loop below. On a mesh the query heads are
        # the parallel axis and the latent cache replicates (MQA shape —
        # see parallel/mesh.cache_sharding), so attention shard_maps over
        # tp and every device RMWs its cache replica.
        from ..ops.kv_cache_update_pallas import (
            kv_cache_append,
            kv_cache_append_replicated,
        )

        c_news, pe_news = [], []
        for l, lp in layers():
            if _is_state_layer(lp):
                x = conv_layer(x, lp, l)
                continue
            a = cfg.op_index(l)  # the layer's index in the cache
            h, q_eff, q_pe, c_kv, k_pe = mla_q_and_latent(x, lp)
            c_news.append(c_kv)
            pe_news.append(k_pe)
            if mesh is None:
                o_lat = _mla_ops.mla_decode_attention_merged(
                    q_eff, q_pe, c_kv, k_pe, k_cache, v_cache, a,
                    block_tables, hist_lens, scale, interpret=interpret,
                )
            else:
                o_lat = _mla_ops.mla_decode_attention_merged_sharded(
                    q_eff, q_pe, c_kv, k_pe, k_cache, v_cache, a,
                    block_tables, hist_lens, scale, mesh,
                    interpret=interpret,
                )
            x = layer_tail(
                x, lp, _mla.o_proj_gated(lp, cfg, o_lat, h).astype(x.dtype))
        c_stack = jnp.stack(c_news)[:, :, None, :]  # [L, B, 1, C]
        pe_stack = jnp.stack(pe_news)[:, :, None, :]  # [L, B, 1, R]
        if mesh is None:
            k_cache, v_cache = kv_cache_append(
                c_stack, pe_stack, k_cache, v_cache, blk, off,
                interpret=interpret,
            )
        else:
            k_cache, v_cache = kv_cache_append_replicated(
                c_stack, pe_stack, k_cache, v_cache, blk, off, mesh,
                interpret=interpret,
            )
    elif cfg.is_mla:
        # WRITE-THEN-ATTEND, MLA flavor: the token's latent lands by XLA
        # scatter, then absorbed attention gathers the layer's pages
        for l, lp in layers():
            if _is_state_layer(lp):
                x = conv_layer(x, lp, l)
                continue
            a = cfg.op_index(l)  # the layer's index in the cache
            h, q_eff, q_pe, c_kv, k_pe = mla_q_and_latent(x, lp)
            # advanced indices (blk, off) behind the scalar a and the
            # full slice come to the front: the value is [B, 1, D]
            k_cache = k_cache.at[a, :, blk, off].set(
                c_kv[:, None].astype(k_cache.dtype)
            )
            v_cache = v_cache.at[a, :, blk, off].set(
                k_pe[:, None].astype(v_cache.dtype)
            )
            o_lat = _mla.mla_decode_attention_xla(
                q_eff, q_pe, k_cache[a], v_cache[a], block_tables,
                seq_lens, scale,
            )
            x = layer_tail(
                x, lp, _mla.o_proj_gated(lp, cfg, o_lat, h).astype(x.dtype))
    elif merged:
        # MERGED one-write loop (TPU): attention handles the current token
        # out-of-cache (flash merge over the stats-emitting paged kernel),
        # so the cache sees ONE in-place Pallas append per step instead of
        # 2L XLA scatters — XLA will not update scatters of this shape in
        # place; each one copies the full cache (the reference's
        # equivalent split is vLLM's reshape_and_cache + paged attention).
        # Sinks join the flash-merge denominator and per-layer windows
        # are static per layer call, so gpt-oss runs it like every other
        # GQA family. On a mesh, every piece is kv-head-parallel and runs
        # under shard_map over tp (the engine only sets use_pallas when
        # tp divides the kv heads).
        from ..ops.kv_cache_update_pallas import (
            kv_cache_append,
            kv_cache_append_quantized,
            kv_cache_append_quantized_sharded,
            kv_cache_append_sharded,
        )

        k_news, v_news = [[] for _ in kcs], [[] for _ in kcs]
        for l, lp in layers():
            if _is_state_layer(lp):
                x = conv_layer(x, lp, l)
                continue
            # the layer's cache and its index in it
            p, a = cfg.kv_pool(l), cfg.kv_index(l)
            lora_l = lora_for_layer(l)
            q, k, v = layer_qkv(x, lp, l, lora_l)
            k_news[p].append(k)
            v_news[p].append(v)
            # history pages dequantize through the step-entry scale
            # planes — consistent: the batched append below is what
            # mutates pages/scales, and it runs after attention
            ks_l = k_scales[l] if quantized else None
            vs_l = v_scales[l] if quantized else None
            if mesh is None:
                o = att.decode_attention_merged(
                    q, k, v, kcs[p], vcs[p], a, tbls[p],
                    hist_lens, scale, window=window_for_layer(cfg, l),
                    sinks=lp.get("sinks"), interpret=interpret,
                    k_scales=ks_l, v_scales=vs_l,
                )
            else:
                o = att.decode_attention_merged_sharded(
                    q, k, v, k_cache, v_cache, a, block_tables,
                    hist_lens, scale, mesh,
                    window=window_for_layer(cfg, l),
                    sinks=lp.get("sinks"), interpret=interpret,
                    k_scales=ks_l, v_scales=vs_l,
                )
            x = layer_tail(x, lp, o, lora_l)
        assert not cfg.window_kv_pool or (mesh is None and not quantized)
        if mesh is None and not quantized:
            # one in-place append a cache, at its own table's slots
            news = [(jnp.stack(k_news[p]), jnp.stack(v_news[p]))
                    for p in range(len(kcs))]
            if cfg.window_kv_pool:
                # the window pool's last reader is not the last layer:
                # nothing orders its append behind that layer's attention,
                # and the compiler then COPIES the pool so that both can
                # run (0.48 GiB a cache, twice a step: my AOT compile,
                # PR 47). The new rows wait for the last layer's output
                news, x = lax.optimization_barrier((news, x))
            for p, (blk_p, off_p) in enumerate(slots):
                kcs[p], vcs[p] = kv_cache_append(
                    *news[p], kcs[p], vcs[p], blk_p, off_p,
                    interpret=interpret,
                )
            k_cache, v_cache = _unpool(cfg, kcs, vcs)
        else:
            k_new, v_new = jnp.stack(k_news[0]), jnp.stack(v_news[0])
            if quantized:
                if mesh is None:
                    k_cache, v_cache, k_scales, v_scales, _ = (
                        kv_cache_append_quantized(
                            k_new, v_new, k_cache, v_cache, k_scales, v_scales,
                            blk, off, interpret=interpret,
                        )
                    )
                else:
                    k_cache, v_cache, k_scales, v_scales, _ = (
                        kv_cache_append_quantized_sharded(
                            k_new, v_new, k_cache, v_cache, k_scales, v_scales,
                            blk, off, mesh, interpret=interpret,
                        )
                    )
            else:
                k_cache, v_cache = kv_cache_append_sharded(
                    k_new, v_new, k_cache, v_cache, blk, off, mesh,
                    interpret=interpret,
                )
    else:
        # WRITE-THEN-ATTEND: the XLA path (CPU, kernels refused for the
        # shape, softcap models)
        for l, lp in layers():
            if _is_state_layer(lp):
                x = conv_layer(x, lp, l)
                continue
            # the layer's cache and its index in it
            p, a = cfg.kv_pool(l), cfg.kv_index(l)
            blk, off = slots[p]
            lora_l = lora_for_layer(l)
            q, k, v = layer_qkv(x, lp, l, lora_l)
            ks_l = vs_l = None
            if quantized:
                # write-before-attend: the row quantizes against the
                # (possibly grown) page scale, then attention
                # dequantizes through the SAME updated plane slice
                kc_l, ks_l = att.write_decode_token_to_cache_quantized(
                    k_cache[l], k_scales[l], k, block_tables, positions
                )
                vc_l, vs_l = att.write_decode_token_to_cache_quantized(
                    v_cache[l], v_scales[l], v, block_tables, positions
                )
                kcs[0] = k_cache = k_cache.at[l].set(kc_l)
                vcs[0] = v_cache = v_cache.at[l].set(vc_l)
                k_scales = k_scales.at[l].set(ks_l)
                v_scales = v_scales.at[l].set(vs_l)
            else:
                # mixed basic+advanced indexing puts the advanced axes
                # (blk, off) in front: the update value is [B, Hkv, D]
                kcs[p] = kcs[p].at[a, :, blk, off].set(
                    k.astype(kcs[p].dtype)
                )
                vcs[p] = vcs[p].at[a, :, blk, off].set(
                    v.astype(vcs[p].dtype)
                )
                k_cache, v_cache = _unpool(cfg, kcs, vcs)
            o = att.decode_attention_xla(
                q, kcs[p][a], vcs[p][a], tbls[p], seq_lens, scale,
                window=window_for_layer(cfg, l), sinks=lp.get("sinks"),
                cap=cfg.attn_softcap, k_scales=ks_l, v_scales=vs_l,
            )
            x = layer_tail(x, lp, o, lora_l)
    x = model_norm(x, params["final_norm"], cfg)
    logits = _logits(params, cfg, x)  # [B, V]
    if quantized:
        # scales only grow within a step, so plane entries above their
        # step-entry value count exactly the pages requantized this step
        n_requants = (
            jnp.sum(k_scales > k_scales0) + jnp.sum(v_scales > v_scales0)
        ).astype(jnp.int32)
        return logits, k_cache, v_cache, k_scales, v_scales, n_requants
    if track is not None:
        return logits, k_cache, v_cache, track.finish()
    return logits, k_cache, v_cache


@partial(
    jax.jit,
    static_argnames=("cfg", "use_pallas", "mesh", "interpret"),
    donate_argnames=("k_cache", "v_cache", "state"),
)
def decode_step(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] last sampled token per sequence
    positions: jnp.ndarray,  # [B] absolute position of that token
    block_tables: jnp.ndarray,  # [B, M]
    seq_lens: jnp.ndarray,  # [B] length including the new token
    k_cache: jnp.ndarray,  # donated
    v_cache: jnp.ndarray,
    use_pallas: bool = False,
    mesh=None,
    interpret: bool = False,
    k_scales: Optional[jnp.ndarray] = None,  # [L, N] f32, NOT donated
    v_scales: Optional[jnp.ndarray] = None,
    lora=None,                                # stacked adapter pytree
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32; -1 = base
    state: Optional[dict] = None,  # LFM2: init_state, donated
):
    """One continuous-batching decode step for all active sequences.

    With scale planes the return grows to (logits, k_cache, v_cache,
    k_scales, v_scales, n_requants), with a conv state to (logits,
    k_cache, v_cache, state) — see ``_decode_body``, which also picks
    the layer loop."""
    return _decode_body(
        params, cfg, tokens, positions, block_tables, seq_lens,
        k_cache, v_cache, use_pallas, mesh, interpret,
        k_scales=k_scales, v_scales=v_scales, lora=lora,
        adapter_ids=adapter_ids, state=state,
    )


@partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "use_pallas", "mesh", "interpret",
                     "with_logprobs", "moe_counters"),
    donate_argnames=("k_cache", "v_cache", "counts", "state", "rows"),
)
def decode_window(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B] last sampled token per sequence
    positions: jnp.ndarray,  # [B]
    block_tables: jnp.ndarray,  # [B, M]
    seq_lens: jnp.ndarray,  # [B]
    seeds: jnp.ndarray,  # [B] int32 sampling seeds
    steps: jnp.ndarray,  # [B] int32 per-request generation counters
    temps: jnp.ndarray,  # [B] float32
    top_ks: jnp.ndarray,  # [B] int32
    top_ps: jnp.ndarray,  # [B] float32
    k_cache: jnp.ndarray,  # donated
    v_cache: jnp.ndarray,
    n_steps: int = 1,
    use_pallas: bool = False,
    mesh=None,
    interpret: bool = False,
    # sampling penalties (all-or-nothing per program: the engine compiles
    # the penalized variant only when some active request asks for one)
    freq_pens: Optional[jnp.ndarray] = None,  # [B] f32
    pres_pens: Optional[jnp.ndarray] = None,  # [B] f32
    rep_pens: Optional[jnp.ndarray] = None,  # [B] f32 (1.0 = off)
    counts: Optional[jnp.ndarray] = None,  # [B, V] i32 output-token counts, donated
    prompt_mask: Optional[jnp.ndarray] = None,  # [B, V] bool
    with_logprobs: bool = False,  # also emit per-step top-k logprobs
    # int8-with-scales device cache planes ([L, N] f32, NOT donated);
    # they ride the scan carry, and the output grows by
    # (k_scales, v_scales, n_requants) right after v_cache
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    # multi-LoRA: step-invariant (closure constants, not scan carry)
    lora=None,
    adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32; -1 = base
    # expert models: the window's routing counters (MoeTally sums over
    # its steps and expert layers, int32 [3]) as the LAST output. Counted
    # or not, only the rows with seq_lens > 0 are routed
    moe_counters: bool = False,
    # LFM2: the conv layers' state (init_state, donated; row b = slot
    # b); it rides the scan carry and follows v_cache in the output
    state: Optional[dict] = None,
    # the batch's resident rows (donated) and this dispatch's cells: they
    # stand for all nine per-slot arguments above (None then), the
    # penalties and the adapter ids, and the rows come back, advanced, as
    # the LAST output ("the decode batch's resident rows", above)
    rows: Optional[jnp.ndarray] = None,
    rows_delta: Optional[jnp.ndarray] = None,
):
    """``n_steps`` fused decode+sample steps in ONE dispatch (lax.scan):
    the sampled token of step i feeds step i+1 entirely on device, so the
    host syncs once per window instead of once per token (SURVEY §7
    "per-token latency floor"; VERDICT round-1 weak #4). Returns
    (tokens [n_steps, B], k_cache, v_cache[, counts]) — counts only when
    penalties are active. The host discards any tail tokens of sequences
    that hit a stop condition mid-window; callers must pre-allocate KV
    blocks for ``n_steps`` new tokens per sequence."""
    from ..ops.sampling import (
        apply_penalties,
        bump_counts,
        make_keys,
        sample_tokens,
        token_logprobs,
    )

    penalized = counts is not None
    quantized = k_scales is not None
    stateful = state is not None
    if rows is not None:
        (rows, tokens, positions, block_tables, seq_lens, seeds, steps,
         temps, top_ks, top_ps, pens, ids) = _rows_args(cfg, rows, rows_delta)
        if penalized:
            freq_pens, pres_pens, rep_pens = pens.values()
        if lora is not None:
            adapter_ids = ids
    # a dead slot enters with length 0 (the scan then counts it up:
    # attention is handed 0 for it at every step, and walks no page; the
    # expert layers send it to no group)
    live = seq_lens > 0

    def body(carry, _):
        tokens, positions, seq_lens, steps, k_cache, v_cache = carry[:6]
        rest = list(carry[6:])
        st = rest.pop(0) if stateful else None
        if quantized:
            ks, vs, nreq = rest[:3]
            del rest[:3]
        tally = MoeTally(rest.pop(0)) if moe_counters else None
        cnt = rest[0] if penalized else None
        attn_lens = jnp.where(live, seq_lens, 0)
        if quantized:
            logits, k_cache, v_cache, ks, vs, nr = _decode_body(
                params, cfg, tokens, positions, block_tables, attn_lens,
                k_cache, v_cache, use_pallas, mesh, interpret,
                k_scales=ks, v_scales=vs, lora=lora,
                adapter_ids=adapter_ids, moe_tally=tally, live=live,
            )
            nreq = nreq + nr
        else:
            # (a stateful stack's new state follows the caches)
            logits, k_cache, v_cache, *st = _decode_body(
                params, cfg, tokens, positions, block_tables, attn_lens,
                k_cache, v_cache, use_pallas, mesh, interpret,
                lora=lora, adapter_ids=adapter_ids, moe_tally=tally,
                state=st, live=live,
            )
        raw_logits = logits  # reported logprobs are the model's own dist
        if penalized:
            logits = apply_penalties(
                logits, cnt, prompt_mask, freq_pens, pres_pens, rep_pens
            )
        keys = make_keys(seeds, steps)
        nxt = sample_tokens.__wrapped__(logits, keys, temps, top_ks, top_ps)
        ys = (nxt, *token_logprobs(raw_logits, nxt)) if with_logprobs else nxt
        tail = tuple(st) if stateful else ()
        if quantized:
            tail = tail + (ks, vs, nreq)
        if moe_counters:
            tail = tail + (tally.sums,)
        if penalized:
            tail = tail + (bump_counts(cnt, nxt),)
        return (nxt, positions + 1, seq_lens + 1, steps + 1,
                k_cache, v_cache) + tail, ys

    carry = (tokens, positions, seq_lens, steps, k_cache, v_cache)
    if stateful:
        carry = carry + (state,)
    if quantized:
        carry = carry + (k_scales, v_scales, jnp.zeros((), jnp.int32))
    if moe_counters:
        carry = carry + (MoeTally.zeros(cfg),)
    if penalized:
        carry = carry + (counts,)
    fin, ys = lax.scan(body, carry, None, length=n_steps)
    k_cache, v_cache = fin[4], fin[5]
    rest = list(fin[6:])
    toks = ys[0] if with_logprobs else ys
    lps = ys[1:] if with_logprobs else None
    out = (toks, k_cache, v_cache)
    if stateful:
        out = out + (rest.pop(0),)
    if quantized:
        out = out + tuple(rest[:3])  # (k_scales, v_scales, n_requants)
        del rest[:3]
    moe = (rest.pop(0),) if moe_counters else ()
    if penalized:
        out = out + (rest[0],)
    if with_logprobs:
        out = out + (lps,)
    if rows is not None:
        moe = moe + (rows_leave(rows, live, fin[0], fin[2], fin[3], mesh),)
    return out + moe


# ---------------- fused mixed prefill+decode step ----------------


def _mixed_fused_forward(
    params, cfg, d_tokens, d_positions, d_tables, d_seq_lens,
    p_tokens, p_tables, p_hists, p_valids, k_cache, v_cache,
    mesh=None, interpret=False, k_scales=None, v_scales=None,
    lora=None, d_adapter_ids=None, p_adapter_ids=None, moe_tally=None,
    state=None, p_slots=None, live=None,
):
    """The FULLY-fused mixed forward (TPU/Pallas path): embeddings and
    every projection/FFN/logits GEMM run over the combined [B + MP*T]
    row axis — the weight stream amortizes across the decode rows and
    every prefill segment (the mixed-batch MFU win) — and attention is
    one call per layer covering all parts
    (ops/ragged_paged_attention_pallas: the decode rows through the
    decode kernel, the segments through the ragged grid).
    Write-before-attend throughout: a layer's K and V rows, the decode
    rows' and every segment's together, land in place in the donated
    pool with one scatter a cache (``att.write_rows_to_cache``), and
    both kernels take the cache WHOLE with the layer's index in it
    (``cfg.op_index(l)``). No slab is cut out in front of a kernel and
    none is written back: either is a copy of the slab, twice a layer
    and cache (PERF.md section 6, PR 41).

    Combined-row GEMMs reassociate reductions vs the unfused [B]- and
    [T]-row programs, so this path matches them only to kernel-grade
    tolerance (greedy streams preserved except at exact logit ties —
    the same contract as the Pallas-vs-XLA kernel pairs and spec
    decode). The bit-exact twin for the XLA path lives in mixed_step's
    other branch. GQA families only; MLA and softcap models take the
    per-part branch.

    Returns (decode_logits [B, V] f32, p_logits [MP, V] f32, k_cache,
    v_cache), then the conv layers' new ``state`` for an LFM2 stack (the
    decode rows are segments of one row from their slots' state, the
    prefill segments start from the rows ``p_slots`` names). ``live``
    [B + MP*T] bool marks the combined rows that carry a token, for the
    expert layers' routing (``_moe_route``).
    """
    from ..ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
        ragged_mixed_attention_sharded,
    )

    B = d_tokens.shape[0]
    MP, T = p_tokens.shape
    x = _embed(
        params, cfg, jnp.concatenate([d_tokens, p_tokens.reshape(-1)])
    )  # [B + MP*T, E]
    p_positions = (p_hists[:, None] + jnp.arange(T)[None, :]).reshape(-1)
    positions_all = jnp.concatenate([d_positions, p_positions])
    rope_global = (_rope_freqs(cfg), _rope_attention_scaling(cfg))
    scale = attn_query_scale(cfg)
    rope_local = _rope_local(cfg)
    if lora is not None:
        # one adapter-id per combined row: decode rows carry theirs,
        # every row of segment m carries segment m's. The deltas run
        # GROUPED here — rows stable-sorted by adapter, two ragged-dot
        # passes (the MoE grouped-GMM shape) — so a batch mixing k
        # adapters costs one low-rank dispatch, not k (ops/lora.py).
        ids_all = jnp.concatenate(
            [d_adapter_ids.astype(jnp.int32),
             jnp.repeat(p_adapter_ids.astype(jnp.int32), T)]
        )
    else:
        ids_all = None
    track = None
    if cfg.state_layers:
        assert state is not None and k_scales is None and lora is None
        track = StateTrack(cfg, state, [
            Segments(None, (d_seq_lens > 0).astype(jnp.int32), d_positions,
                     d_tables, 1),
            Segments(p_slots, p_valids, p_hists, p_tables, T),
        ], k_cache.shape[3])

    # every combined row's slot in its cache, the same at every layer
    # of a pool (``_pools``): a decode row is a chunk of one
    kcs, vcs, d_tbls = _pools(cfg, k_cache, v_cache, d_tables)
    p_tbls = _by_pool(cfg, p_tables)
    slots = []
    for kc_p, d_t, p_t in zip(kcs, d_tbls, p_tbls):
        bs = kc_p.shape[3]
        d_blk, d_off = att.decode_slot_indices(d_t, d_positions, bs)
        p_blk, p_off = att.chunk_slot_indices(p_t, p_hists, T, bs)
        slots.append((jnp.concatenate([d_blk, p_blk]),
                      jnp.concatenate([d_off, p_off])))

    # UNROLLED layer loop (per-layer windows / local rope stay
    # trace-static; program count bounded by the prefill buckets)
    for l, lp in _layers(params, cfg, mesh):
        if _is_state_layer(lp):
            x = _conv_layer(x, lp, cfg, l, track, mesh=mesh, use_pallas=True,
                            interpret=interpret, tally=moe_tally, live=live)
            continue
        # the layer's cache, its index in it, and that cache's tables
        p, a = cfg.kv_pool(l), cfg.kv_index(l)
        d_tables, p_tables = d_tbls[p], p_tbls[p]
        blk, off = slots[p]
        lora_l = (
            None if lora is None
            else jax.tree.map(lambda arr: arr[l], lora)
        )
        h = pre_norm(lp, "attn_norm", x, cfg)
        w = window_for_layer(cfg, l)
        # [B+MP*T, H/Hkv, D]
        q, k, v = _qkv(lp, cfg, h, lora_l, ids_all, lora_grouped=True)
        q, k = rotate_qk(
            q, k, positions_all,
            rope_for_layer(cfg, l, rope_global, rope_local))
        Hq, Dh = q.shape[1], q.shape[2]
        q_chunks = q[B:].reshape(MP, T, Hq, Dh)
        # write-before-attend for EVERY part (distinct pages: no
        # prefill sequence is in the decode batch and segments are
        # distinct sequences; padded/dead segment rows land in
        # reserved trash page 0 through their zero table entries)
        ks_l = vs_l = None
        if k_scales is not None:
            # the int8 lane's write requantises resident pages against
            # a grown scale: still a read-modify-write of the layer's
            # slab (ROADMAP A12); its attention reads the pool like
            # every other's
            kc_l, vc_l = k_cache[a], v_cache[a]
            ks_l, vs_l = k_scales[l], v_scales[l]
            kc_l, ks_l = att.write_decode_token_to_cache_quantized(
                kc_l, ks_l, k[:B], d_tables, d_positions
            )
            vc_l, vs_l = att.write_decode_token_to_cache_quantized(
                vc_l, vs_l, v[:B], d_tables, d_positions
            )
            for m in range(MP):
                sl = slice(B + m * T, B + (m + 1) * T)
                kc_l, ks_l = att.write_chunk_to_cache_quantized(
                    kc_l, ks_l, k[sl], p_tables[m], p_hists[m],
                    p_valids[m],
                )
                vc_l, vs_l = att.write_chunk_to_cache_quantized(
                    vc_l, vs_l, v[sl], p_tables[m], p_hists[m],
                    p_valids[m],
                )
            kcs[0] = k_cache = k_cache.at[a].set(kc_l)
            vcs[0] = v_cache = v_cache.at[a].set(vc_l)
            k_scales = k_scales.at[l].set(ks_l)
            v_scales = v_scales.at[l].set(vs_l)
        else:
            # the combined rows land where the pool lies: one in-place
            # scatter a cache, no slab cut and none written back
            kcs[p] = att.write_rows_to_cache(kcs[p], a, k, blk, off, mesh)
            vcs[p] = att.write_rows_to_cache(vcs[p], a, v, blk, off, mesh)
            k_cache, v_cache = _unpool(cfg, kcs, vcs)
        if mesh is not None:
            o_dec, o_chunks = ragged_mixed_attention_sharded(
                q[:B], q_chunks, k_cache, v_cache, a, d_tables, d_seq_lens,
                p_tables, p_hists, p_valids, scale, mesh, window=w,
                sinks=lp.get("sinks"), interpret=interpret,
                k_scales=ks_l, v_scales=vs_l,
            )
        else:
            o_dec, o_chunks = ragged_mixed_attention(
                q[:B], q_chunks, kcs[p], vcs[p], a, d_tables, d_seq_lens,
                p_tables, p_hists, p_valids, scale, window=w,
                sinks=lp.get("sinks"), interpret=interpret,
                k_scales=ks_l, v_scales=vs_l,
            )
        o = jnp.concatenate(
            [o_dec.reshape(B, -1), o_chunks.reshape(MP * T, -1)]
        )
        x = _layer_tail(
            x, lp, cfg, o, lora_l, ids_all, lora_grouped=True,
            mesh=mesh, use_pallas=True, interpret=interpret,
            tally=moe_tally, live=live,
        )
    x = model_norm(x, params["final_norm"], cfg)
    logits_d = _logits(params, cfg, x[:B])  # [B, V] f32
    # each segment's last REAL row only (the unfused prefill computes
    # the same single row — full [T, V] head matmuls would be pure waste)
    last = B + jnp.arange(MP) * T + jnp.clip(p_valids - 1, 0, T - 1)
    p_logits = _logits(params, cfg, x[last])  # [MP, V] f32
    if k_scales is not None:
        return logits_d, p_logits, k_cache, v_cache, k_scales, v_scales
    if track is not None:
        return logits_d, p_logits, k_cache, v_cache, track.finish()
    return logits_d, p_logits, k_cache, v_cache


@partial(
    jax.jit,
    static_argnames=("cfg", "use_pallas", "mesh", "interpret",
                     "with_logprobs", "moe_counters"),
    donate_argnames=("k_cache", "v_cache", "counts", "state", "rows"),
)
def mixed_step(
    params: dict,
    cfg: ModelConfig,
    # decode side (same conventions as decode_window at n_steps=1)
    d_tokens: jnp.ndarray,  # [B] last sampled token per sequence
    d_positions: jnp.ndarray,  # [B] absolute position of that token
    d_tables: jnp.ndarray,  # [B, M]
    d_seq_lens: jnp.ndarray,  # [B] length including the new token
    seeds: jnp.ndarray,  # [B] int32 sampling seeds
    steps: jnp.ndarray,  # [B] int32 per-request generation counters
    temps: jnp.ndarray,  # [B] float32
    top_ks: jnp.ndarray,  # [B] int32
    top_ps: jnp.ndarray,  # [B] float32
    # prefill side (same conventions as prefill's chunk args, stacked
    # over M in-flight prompts; dead pad segments have valid 0 + zero
    # tables and their logits row is garbage the engine never reads)
    p_tokens: jnp.ndarray,  # [MP, T] padded chunks of in-flight prompts
    p_tables: jnp.ndarray,  # [MP, M] the prefill sequences' block tables
    p_hists: jnp.ndarray,  # [MP] int32: tokens already cached per prompt
    p_valids: jnp.ndarray,  # [MP] int32: real tokens in each chunk
    k_cache: jnp.ndarray,  # donated
    v_cache: jnp.ndarray,
    use_pallas: bool = False,
    mesh=None,
    interpret: bool = False,
    # sampling penalties (compiled in only when some request asks)
    freq_pens: Optional[jnp.ndarray] = None,  # [B] f32
    pres_pens: Optional[jnp.ndarray] = None,  # [B] f32
    rep_pens: Optional[jnp.ndarray] = None,  # [B] f32 (1.0 = off)
    counts: Optional[jnp.ndarray] = None,  # [B, V] i32, donated
    prompt_mask: Optional[jnp.ndarray] = None,  # [B, V] bool
    with_logprobs: bool = False,
    # int8-with-scales device cache planes ([L, N] f32, NOT donated);
    # output grows by (k_scales, v_scales, n_requants) after v_cache
    k_scales: Optional[jnp.ndarray] = None,
    v_scales: Optional[jnp.ndarray] = None,
    # multi-LoRA lane: stacked adapter pytree + per-row slot ids
    # (-1 = base). The Pallas flavor runs GROUPED deltas over the
    # combined rows; the XLA flavor threads the same lora through the
    # unfused prefill/decode calls (per-adapter loop — bit-identical
    # to solo dispatch). Output shape unchanged.
    lora=None,
    d_adapter_ids: Optional[jnp.ndarray] = None,  # [B] int32
    p_adapter_ids: Optional[jnp.ndarray] = None,  # [MP] int32
    # expert models: the step's routing counters (MoeTally sums, int32
    # [3]) as the LAST output. Counted or not, the rows routed are the
    # decode rows with d_seq_lens > 0 and each segment's rows below its
    # valid length
    moe_counters: bool = False,
    # LFM2: the conv layers' state (init_state, donated), the decode
    # rows' by slot, and the state row each prefill segment starts from
    # and leaves its state in (a dead segment: max_batch, dropped); the
    # new state follows v_cache in the output
    state: Optional[dict] = None,
    p_slots: Optional[jnp.ndarray] = None,  # [MP] int32
    # [MP] int32: the snapshot row that takes each segment's end state
    # (``prefill``'s ``snap_row``; past the pool: none)
    p_snaps: Optional[jnp.ndarray] = None,
    # the batch's resident rows (donated) and this dispatch's cells, for
    # the nine decode-side arguments, the penalties and ``d_adapter_ids``
    # (``decode_window``); the rows come back as the LAST output
    rows: Optional[jnp.ndarray] = None,
    rows_delta: Optional[jnp.ndarray] = None,
):
    """ONE device dispatch fusing M prefill chunks into a decode step.

    Two forward flavors behind one dispatch boundary:

      * **Pallas (TPU) path** — `_mixed_fused_forward`: combined-row
        GEMMs over the decode rows + every segment, plus one ragged
        paged-attention kernel invocation per layer (the full
        mixed-batch MFU win). Matches the unfused paths to kernel-grade
        tolerance; greedy streams preserved except at exact logit ties
        — the standing contract for every Pallas-vs-XLA pairing in this
        repo. MLA and softcap families on this path fall through to the
        per-part flavor below (MLA's latent decode+prefill kernel pair
        runs inside the same dispatch, once per segment; there is no
        latent ragged kernel yet).
      * **XLA path** (CPU, quantized-KV, softcap) — per-part structural
        identity: each segment runs through EXACTLY the unfused prefill
        forward (``prefill.__wrapped__``: same scan/unrolled layer
        loop, same [T]-row GEMMs), in admission order, and the decode
        batch through EXACTLY ``_decode_body`` (which derives the same
        layer loop from the same inputs) — so tokens AND logprobs are
        BIT-IDENTICAL to the alternating scheduler (the
        tests/test_mixed_batch.py contract; restructured GEMMs would
        reassociate bf16 reductions and flip sampled tokens). All parts
        are computationally independent (no prefill sequence is in the
        decode batch; segments are distinct sequences with disjoint
        pages), so fusing them into one program cannot change any.

    The segment count MP and padded length T are static shape keys —
    the engine buckets both (segment-count buckets x prefill buckets),
    so the compiled program count is bounded by the bucket grid, never
    the per-step segment-length mixture.

    Sampling mirrors decode_window's body exactly (penalties on the
    sampled distribution, raw logits for reported logprobs).

    Returns (next_tokens [B], p_logits [MP, V] f32 — each segment's
    last-real-row logits, for host-side first-token sampling on a
    prompt's final chunk —, k_cache, v_cache[, counts]
    [, (chosen_lp [B], top_ids [B, K], top_lps [B, K])]).
    """
    from ..ops.sampling import (
        apply_penalties,
        bump_counts,
        make_keys,
        sample_tokens,
        token_logprobs,
    )

    MP, T = p_tokens.shape
    quantized = k_scales is not None
    if rows is not None:
        (rows, d_tokens, d_positions, d_tables, d_seq_lens, seeds, steps,
         temps, top_ks, top_ps, pens, ids) = _rows_args(cfg, rows, rows_delta)
        if counts is not None:
            freq_pens, pres_pens, rep_pens = pens.values()
        if lora is not None:
            d_adapter_ids = ids
    if quantized:
        # scales only grow within a step — plane entries above their
        # step-entry value count the pages requantized this dispatch
        k_scales0, v_scales0 = k_scales, v_scales
    fused = use_pallas and not cfg.is_mla and not cfg.attn_softcap
    # the fused forward routes every row at once; the per-part one
    # routes (and counts) the decode rows here and each segment in its
    # prefill
    live = d_seq_lens > 0
    if fused:
        p_live = jnp.arange(T)[None, :] < p_valids[:, None]
        live = jnp.concatenate([live, p_live.reshape(-1)])
    tally = MoeTally(MoeTally.zeros(cfg)) if moe_counters else None
    if fused:
        if quantized:
            logits_d, p_logits, k_cache, v_cache, k_scales, v_scales = (
                _mixed_fused_forward(
                    params, cfg, d_tokens, d_positions, d_tables,
                    d_seq_lens, p_tokens, p_tables, p_hists, p_valids,
                    k_cache, v_cache, mesh=mesh, interpret=interpret,
                    k_scales=k_scales, v_scales=v_scales, lora=lora,
                    d_adapter_ids=d_adapter_ids,
                    p_adapter_ids=p_adapter_ids, moe_tally=tally,
                    live=live,
                )
            )
        else:
            out = _mixed_fused_forward(
                params, cfg, d_tokens, d_positions, d_tables, d_seq_lens,
                p_tokens, p_tables, p_hists, p_valids, k_cache, v_cache,
                mesh=mesh, interpret=interpret, lora=lora,
                d_adapter_ids=d_adapter_ids, p_adapter_ids=p_adapter_ids,
                moe_tally=tally, state=state, p_slots=p_slots, live=live,
            )
            logits_d, p_logits, k_cache, v_cache = out[:4]
            state = out[4] if state is not None else None
    else:
        # chunks first (admission order), then decode — order is
        # numerically irrelevant (independent parts) and matches the
        # admission-then-decode order of the alternating scheduler
        p_logit_rows = []
        for m in range(MP):
            aid = None if lora is None else p_adapter_ids[m]
            seg = prefill.__wrapped__(
                params, cfg, p_tokens[m], _table_rows(p_tables, m), p_hists[m],
                p_valids[m], k_cache, v_cache, use_pallas=use_pallas,
                mesh=mesh, k_scales=k_scales, v_scales=v_scales,
                lora=lora, adapter_id=aid, moe_counters=moe_counters,
                state=state, slot=None if state is None else p_slots[m],
                snap_row=None if p_snaps is None else p_snaps[m],
            )
            if moe_counters:
                tally.sums = tally.sums + seg[-1]
                seg = seg[:-1]
            if state is not None:
                lg, k_cache, v_cache, state = seg
            elif quantized:
                lg, k_cache, v_cache, k_scales, v_scales = seg
            else:
                lg, k_cache, v_cache = seg
            p_logit_rows.append(lg)
        p_logits = jnp.stack(p_logit_rows)  # [MP, V]
        if quantized:
            logits_d, k_cache, v_cache, k_scales, v_scales, _ = _decode_body(
                params, cfg, d_tokens, d_positions, d_tables, d_seq_lens,
                k_cache, v_cache, use_pallas, mesh, interpret,
                k_scales=k_scales, v_scales=v_scales,
                lora=lora, adapter_ids=d_adapter_ids, moe_tally=tally,
                live=live,
            )
        else:
            out = _decode_body(
                params, cfg, d_tokens, d_positions, d_tables, d_seq_lens,
                k_cache, v_cache, use_pallas, mesh, interpret,
                lora=lora, adapter_ids=d_adapter_ids, moe_tally=tally,
                state=state, live=live,
            )
            logits_d, k_cache, v_cache = out[:3]
            state = out[3] if state is not None else None

    raw_logits = logits_d
    penalized = counts is not None
    if penalized:
        logits_d = apply_penalties(
            logits_d, counts, prompt_mask, freq_pens, pres_pens, rep_pens
        )
    keys = make_keys(seeds, steps)
    nxt = sample_tokens.__wrapped__(logits_d, keys, temps, top_ks, top_ps)
    result = [nxt, p_logits, k_cache, v_cache]
    if state is not None:
        result.append(state)
    if quantized:
        n_requants = (
            jnp.sum(k_scales > k_scales0) + jnp.sum(v_scales > v_scales0)
        ).astype(jnp.int32)
        result += [k_scales, v_scales, n_requants]
    if penalized:
        result.append(bump_counts(counts, nxt))
    if with_logprobs:
        result.append(token_logprobs(raw_logits, nxt))
    if moe_counters:
        result.append(tally.sums)
    if rows is not None:
        result.append(rows_leave(rows, d_seq_lens > 0, nxt, d_seq_lens + 1,
                                 steps + 1, mesh))
    return tuple(result)


# ---------------- speculative verify (prompt-lookup decoding) ----------------


def _verify_forward(
    params, cfg, tokens, positions, block_tables, seq_lens,
    k_cache, v_cache, n_spec, use_pallas=False, mesh=None, interpret=False,
):
    """The fused multi-token forward of the speculative verify: logits
    for T = n_spec+1 in-flight tokens per sequence in one pass (the
    weight stream amortizes over T — the whole point of speculation),
    with all T rows' K/V appended to the cache in place. Rows past the
    accepted run hold rejected proposals' K/V, which live above the
    commit horizon and are overwritten before any read (same invariant
    as a discarded decode-window tail)."""
    from ..ops.kv_cache_update_pallas import (
        kv_cache_append_tokens,
        kv_cache_append_tokens_sharded,
        kv_cache_append_tokens_xla,
    )

    T = n_spec + 1
    B, E = tokens.shape[0], cfg.hidden_size
    pos_bt = positions[:, None] + jnp.arange(T)[None, :]  # [B, T]
    hist_lens = seq_lens - 1  # cache rows before the in-flight window
    x = _embed(params, cfg, tokens.reshape(-1)).reshape(B, T, E)
    # write slots of the T in-flight rows (one slot-mapping convention)
    bs = k_cache.shape[3]
    blk = jnp.take_along_axis(block_tables, pos_bt // bs, axis=1)
    off = pos_bt % bs

    def layer_tail(x, lp, o):
        return _layer_tail(
            x.reshape(B * T, E), lp, cfg, o.reshape(B * T, -1), mesh=mesh,
            use_pallas=use_pallas, interpret=interpret,
        ).reshape(B, T, E)

    if cfg.is_mla:
        # MLA verify: absorbed multi-token attention with the in-flight
        # window OUT of the cache (ops/mla_attention_pallas
        # .mla_verify_attention), so all layers' latent writes batch
        # into ONE append instead of 2L cache-copying scatters. Rows
        # past the accepted run live above the commit horizon and are
        # overwritten before any read (same invariant as below).
        from . import mla as _mla
        from ..ops import mla_attention_pallas as _mla_ops

        inv_freq, msc = _mla.mla_rope_freqs(cfg)
        scale = cfg.mla_softmax_scale()
        c_news, pe_news = [], []
        for lps, ng, goff in layer_groups(params, cfg):
            for li in range(ng):
                l = goff + li
                lp = _layer(lps, li, mesh)
                h = pre_norm(lp, "attn_norm", x, cfg)
                q_eff, q_pe, c_kv, k_pe = _mla.mla_q_and_latent(
                    lp, cfg, h, pos_bt, inv_freq, msc
                )
                c_news.append(c_kv)
                pe_news.append(k_pe)
                o = _mla_ops.mla_verify_attention(
                    q_eff, q_pe, c_kv, k_pe, k_cache, v_cache, l,
                    block_tables, hist_lens, scale,
                    use_pallas=use_pallas and mesh is None,
                    interpret=interpret,
                )
                o = _mla._o_proj(lp, cfg, o).astype(x.dtype)
                x = layer_tail(x, lp, o)
        x = model_norm(x, params["final_norm"], cfg)
        logits = _logits(params, cfg, x.reshape(B * T, E)).reshape(B, T, -1)
        # the kernel serves the unsharded Pallas path; a mesh (replicated
        # latent cache) or a Pallas-off engine takes the XLA scatter
        append = (
            partial(kv_cache_append_tokens, interpret=interpret)
            if use_pallas and mesh is None else kv_cache_append_tokens_xla
        )
        k_cache, v_cache = append(
            jnp.stack(c_news)[:, :, :, None, :],  # [L, B, T, 1, C]
            jnp.stack(pe_news)[:, :, :, None, :],  # [L, B, T, 1, R]
            k_cache, v_cache, blk, off,
        )
        return logits, k_cache, v_cache

    rope_global = (_rope_freqs(cfg), _rope_attention_scaling(cfg))
    rope_local = _rope_local(cfg)
    scale = attn_query_scale(cfg)

    k_news, v_news = [], []
    for lps, ng, goff in layer_groups(params, cfg):
        for li in range(ng):
            l = goff + li
            lp = _layer(lps, li, mesh)
            h = pre_norm(lp, "attn_norm", x, cfg)
            q, k, v = _qkv(lp, cfg, h)  # [B, T, H/Hkv, D]
            q, k = rotate_qk(
                q, k, pos_bt, rope_for_layer(cfg, l, rope_global, rope_local))
            k_news.append(k)
            v_news.append(v)
            if use_pallas and mesh is not None:
                o = att.verify_attention_sharded(
                    q, k, v, k_cache, v_cache, l, block_tables, hist_lens,
                    scale, mesh, use_pallas=True,
                    window=window_for_layer(cfg, l), sinks=lp.get("sinks"),
                    interpret=interpret,
                )
            else:
                # the layer loop is unrolled, so per-layer windows and
                # sinks (gpt-oss) thread straight through the XLA verify
                o = att.verify_attention(
                    q, k, v, k_cache, v_cache, l, block_tables, hist_lens,
                    scale, use_pallas=use_pallas,
                    window=window_for_layer(cfg, l), sinks=lp.get("sinks"),
                    cap=cfg.attn_softcap, interpret=interpret,
                )
            x = layer_tail(x, lp, o)
    x = model_norm(x, params["final_norm"], cfg)
    logits = _logits(params, cfg, x.reshape(B * T, E)).reshape(B, T, -1)

    if use_pallas and mesh is not None:
        k_cache, v_cache = kv_cache_append_tokens_sharded(
            jnp.stack(k_news), jnp.stack(v_news), k_cache, v_cache, blk,
            off, mesh, interpret=interpret,
        )
    elif use_pallas:
        k_cache, v_cache = kv_cache_append_tokens(
            jnp.stack(k_news), jnp.stack(v_news), k_cache, v_cache, blk, off,
            interpret=interpret,
        )
    else:
        k_cache, v_cache = kv_cache_append_tokens_xla(
            jnp.stack(k_news), jnp.stack(v_news), k_cache, v_cache, blk, off,
        )
    return logits, k_cache, v_cache


@partial(
    jax.jit,
    static_argnames=("cfg", "n_spec", "use_pallas", "mesh", "interpret",
                     "with_logprobs"),
    donate_argnames=("k_cache", "v_cache", "counts"),
)
def verify_window(
    params: dict,
    cfg: ModelConfig,
    tokens: jnp.ndarray,  # [B, T]: t=0 last accepted token, t>=1 proposals
    proposals: jnp.ndarray,  # [B, n_spec] int32, -1 = no proposal
    positions: jnp.ndarray,  # [B] absolute position of tokens[:, 0]
    block_tables: jnp.ndarray,  # [B, M]
    seq_lens: jnp.ndarray,  # [B] length INCLUDING tokens[:, 0]
    seeds: jnp.ndarray,  # [B] int32 sampling seeds
    steps: jnp.ndarray,  # [B] int32 per-request generation counters
    temps: jnp.ndarray,  # [B] float32; 0 = greedy row
    top_ks: jnp.ndarray,  # [B] int32
    top_ps: jnp.ndarray,  # [B] float32
    k_cache: jnp.ndarray,  # donated; holds history only (rows < seq_len-1)
    v_cache: jnp.ndarray,
    n_spec: int,
    use_pallas: bool = False,
    mesh=None,
    interpret: bool = False,
    # sampling penalties (compiled in only when some request asks)
    freq_pens: Optional[jnp.ndarray] = None,  # [B] f32
    pres_pens: Optional[jnp.ndarray] = None,  # [B] f32
    rep_pens: Optional[jnp.ndarray] = None,  # [B] f32 (1.0 = off)
    counts: Optional[jnp.ndarray] = None,  # [B, V] i32, donated
    prompt_mask: Optional[jnp.ndarray] = None,  # [B, V] bool
    with_logprobs: bool = False,
    # the batch's resident rows and this dispatch's cells, for every
    # per-slot argument above but ``proposals`` (``tokens``: the rows'
    # last tokens before the proposals). NOT returned: a verify step's
    # rows advance by their own accepted counts, which the host applies
    rows: Optional[jnp.ndarray] = None,
    rows_delta: Optional[jnp.ndarray] = None,
):
    """Speculative verify + acceptance (greedy AND sampled rows):

      * greedy rows accept proposals matching the argmax chain;
      * sampled rows use rejection sampling against the deterministic
        draft (ops/sampling.speculative_accept) — lossless in
        distribution; accept draws come from a tweaked seed stream
        (seed ^ 0x5EC) so emitted-token keys stay identical to the
        plain decode stream (replay-exactness of resumed requests).

    Penalties (when ``counts`` is given) model the SEQUENTIAL semantics
    of plain decode inside the joint verify: position t's distribution is
    penalized by the base counts plus the window's own tokens before t
    (accepted proposals bump as they would had they been emitted one by
    one), and the returned counts include every emitted token (the
    accepted run + correction/bonus). Acceptance and greedy argmax run on
    the PENALIZED logits — exactly the distribution the plain sampler
    would have used — while reported logprobs stay the model's own raw
    distribution (same convention as decode_window).

    Returns (out_tokens [B, T], n_acc [B], k_cache, v_cache[, counts]
    [, (chosen_lp [B,T], top_ids [B,T,K], top_lps [B,T,K])]): the caller
    emits out_tokens[:, :n_acc+1] — accepted run + correction/bonus.
    """
    from ..ops.sampling import (
        apply_penalties,
        make_keys,
        speculative_accept,
        token_logprobs,
    )

    if rows is not None:
        (rows, last, positions, block_tables, seq_lens, seeds, steps,
         temps, top_ks, top_ps, pens, _ids) = _rows_args(cfg, rows, rows_delta)
        # (-1 -> 0 for a safe embed; acceptance uses the ORIGINAL -1s)
        tokens = jnp.concatenate(
            [last[:, None], jnp.maximum(proposals, 0)], axis=1)
        if counts is not None:
            freq_pens, pres_pens, rep_pens = pens.values()
    T = n_spec + 1
    B = tokens.shape[0]
    logits, k_cache, v_cache = _verify_forward(
        params, cfg, tokens, positions, block_tables, seq_lens,
        k_cache, v_cache, n_spec, use_pallas, mesh, interpret,
    )
    raw_logits = logits.astype(jnp.float32)
    penalized = counts is not None
    if penalized:
        V = raw_logits.shape[-1]
        d = jnp.maximum(proposals, 0)
        valid = proposals >= 0
        # window-token bumps BEFORE each position: one_hot of V (the
        # invalid sentinel) is all-zeros, so unproposed slots bump nothing
        oh = jax.nn.one_hot(
            jnp.where(valid, d, V), V, dtype=jnp.int32
        )  # [B, g, V]
        cum = jnp.cumsum(oh, axis=1)
        cnt_t = counts[:, None] + jnp.concatenate(
            [jnp.zeros((B, 1, V), jnp.int32), cum], axis=1
        )  # [B, T, V]
        sample_logits = apply_penalties(
            raw_logits.reshape(B * T, V),
            cnt_t.reshape(B * T, V),
            jnp.repeat(prompt_mask, T, axis=0),
            jnp.repeat(freq_pens, T),
            jnp.repeat(pres_pens, T),
            jnp.repeat(rep_pens, T),
        ).reshape(B, T, V)
    else:
        sample_logits = raw_logits
    keys_accept = jnp.stack(
        [make_keys(seeds ^ 0x5EC, steps + t) for t in range(n_spec)], axis=1
    ) if n_spec else jnp.zeros((tokens.shape[0], 0, 2), jnp.uint32)
    keys_sample = jnp.stack(
        [make_keys(seeds, steps + t) for t in range(T)], axis=1
    )
    out, n_acc = speculative_accept(
        sample_logits, proposals, keys_accept, keys_sample,
        temps, top_ks, top_ps,
    )
    result = [out, n_acc, k_cache, v_cache]
    if penalized:
        # count every emitted token (t <= n_acc); others drop via index V
        emitted = jnp.arange(T)[None, :] <= n_acc[:, None]
        ids = jnp.where(emitted, out, raw_logits.shape[-1])
        counts = counts.at[jnp.arange(B)[:, None], ids].add(1, mode="drop")
        result.append(counts)
    if with_logprobs:
        chosen_lp, top_ids, top_lps = token_logprobs(
            raw_logits.reshape(B * T, -1), out.reshape(-1)
        )
        K = top_ids.shape[-1]
        result.append((
            chosen_lp.reshape(B, T),
            top_ids.reshape(B, T, K),
            top_lps.reshape(B, T, K),
        ))
    return tuple(result)


# ---------------- reference dense forward (tests) ----------------


def dense_forward(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Straight full-attention forward [T] -> logits [T, V]; ground truth
    for paged-path equivalence tests. MLA models run the NAIVE
    (non-absorbed) formulation — reconstruct per-head K/V from latents —
    which the absorbed paged path must match. ``positions`` [T]
    (ascending; default 0..T-1) are the tokens' positions, for the tests
    of what a layer kind reads of them."""
    T = tokens.shape[0]
    x = _embed(params, cfg, tokens)
    if positions is None:
        positions = jnp.arange(T)
    if cfg.is_mla:
        from . import mla as _mla

        inv_freq, msc = _mla.mla_rope_freqs(cfg)
        scale = cfg.mla_softmax_scale()
        rope_global = None  # the latent layers rotate under their own law
    else:
        rope_global = (_rope_freqs(cfg), _rope_attention_scaling(cfg))
        scale = attn_query_scale(cfg)

    rope_local = _rope_local(cfg)
    # the scanned loop's one law: every layer is of layer 0's kind
    rope_scan = rope_for_layer(cfg, 0, rope_global, rope_local)

    def body(x, lp, window=cfg.sliding_window, freqs=None):
        h = pre_norm(lp, "attn_norm", x, cfg)
        if cfg.is_mla:
            # DELIBERATELY independent of mla.mla_q_and_latent: this is
            # the ground-truth NAIVE formulation (reconstruct full K/V,
            # no absorption) the absorbed paged path is validated
            # against — sharing the projection code would make the
            # equivalence tests circular. External anchor: the HF parity
            # tests (tests/test_hf_parity.py deepseek v2/v3).
            from . import mla as _mla

            H, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            if cfg.q_lora_rank:
                q = _mm(model_norm(_mm(h, lp["wq_a"]), lp["q_norm"], cfg),
                        lp["wq_b"])
            else:
                q = _mm(h, lp["wq"])
            q = q.reshape(T, H, dn + dr)
            q_nope, q_pe = q[..., :dn], q[..., dn:]
            q_pe = _mla.rope_rotate(q_pe, positions, inv_freq, msc)
            kv = _mm(h, lp["wkv_a"])
            c_kv = model_norm(kv[..., : cfg.kv_lora_rank], lp["kv_norm"], cfg)
            k_pe = _mla.rope_rotate(
                kv[..., cfg.kv_lora_rank:][:, None, :], positions,
                inv_freq, msc,
            )[:, 0, :]
            w_kc, w_vc = _mla._wkv_b_parts(lp, cfg)
            # naive reconstruction: per-head K/V from the latent
            k_nope = jnp.einsum("tc,chd->thd", c_kv.astype(jnp.float32),
                                w_kc.astype(jnp.float32))
            v = jnp.einsum("tc,chd->thd", c_kv.astype(jnp.float32),
                           w_vc.astype(jnp.float32))
            qf = jnp.concatenate(
                [q_nope.astype(jnp.float32),
                 q_pe.astype(jnp.float32)], axis=-1,
            )
            kf = jnp.concatenate(
                [k_nope,
                 jnp.broadcast_to(k_pe[:, None, :].astype(jnp.float32),
                                  (T, H, dr))], axis=-1,
            )
            s = jnp.einsum("thd,shd->hts", qf * scale, kf)
            causal = positions[:, None] >= positions[None, :]
            s = jnp.where(causal[None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("hts,shd->thd", p, v).reshape(T, -1)
            if "attn_gate" in lp:
                o = o * jax.nn.sigmoid(
                    _mm(h, lp["attn_gate"]).astype(jnp.float32))
            o = o.astype(x.dtype)
        else:
            q, k, v = _qkv(lp, cfg, h)
            q, k = rotate_qk(
                q, k, positions, rope_scan if freqs is None else freqs)
            o = att.prefill_attention_xla(
                q, k, v, positions, jnp.int32(T), scale,
                window=window, sinks=lp.get("sinks"), cap=cfg.attn_softcap,
            )
        return _layer_tail(x, lp, cfg, o.reshape(T, -1)), None

    if cfg.layer_windows or cfg.layer_ops:
        # per-layer static windows, per-layer operators: unrolled
        for l, lp in _layers(params, cfg):
            if _is_state_layer(lp):
                x = _conv_layer(x, lp, cfg, l, None)
                continue
            x, _ = body(
                x, lp, window=window_for_layer(cfg, l),
                freqs=rope_for_layer(cfg, l, rope_global, rope_local),
            )
    else:
        for lps, _n, _off in layer_groups(params, cfg):
            x, _ = lax.scan(body, x, lps)
    x = model_norm(x, params["final_norm"], cfg)
    return _logits(params, cfg, x)
