"""Model architecture config (llama-family superset + MoE fields).

Parsed from HF ``config.json`` (the reference reads the same artifact via
its ModelDeploymentCard, model_card/create.rs). Covers Llama 2/3,
DeepSeek-R1-Distill-Llama, Qwen2 (bias variant), Mistral, Gemma
(GeGLU/(1+w)-norm/scaled-embedding variants), and Mixtral/DeepSeek-style
MoE.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Optional


def is_yarn(rope_scaling: dict) -> bool:
    return (
        rope_scaling.get("type") == "yarn"
        or rope_scaling.get("rope_type") == "yarn"
    )


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's YaRN attention-scale correction (ONE copy — the rope
    cos/sin correction in models/mla.py uses the same formula)."""
    import math

    if factor <= 1.0 or mscale == 0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


#: expert families the parser knows, by ``model_type`` and by the prefix
#: of an ``architectures`` entry (docs/models.md lists them)
_EXPERT_MODEL_TYPES = frozenset((
    "mixtral", "qwen2_moe", "qwen3_moe", "deepseek", "deepseek_v2",
    "deepseek_v3", "gpt_oss", "olmoe", "lfm2_moe", "gigachat3_5",
))
_EXPERT_ARCH_PREFIXES = (
    "Mixtral", "Qwen2Moe", "Qwen3Moe", "Deepseek", "GptOss", "Olmoe",
    "Lfm2Moe", "GigaChat35",
)
_EXPERT_COUNT_KEYS = ("num_local_experts", "n_routed_experts", "num_experts")


def _reject_unknown_expert_family(cfg: dict, archs: list) -> None:
    """A config that counts experts under a model_type / architecture
    this parser does not know would parse as a Llama with experts —
    whatever its norms, router and expert widths really are — and serve
    wrong logits in silence (what ``olmoe`` did before it was added).
    Refuse it by name. A bare dict that names no family is let through."""
    if not any(cfg.get(k) for k in _EXPERT_COUNT_KEYS):
        return
    named = list(archs) + ([cfg["model_type"]] if cfg.get("model_type") else [])
    if not named:
        return
    known = cfg.get("model_type") in _EXPERT_MODEL_TYPES or any(
        a.startswith(_EXPERT_ARCH_PREFIXES) for a in archs
    )
    if not known:
        raise ValueError(
            f"unsupported expert model {named}: its config.json counts "
            "experts, and this parser knows the expert layers of "
            f"{sorted(_EXPERT_MODEL_TYPES)} only — refusing to serve it "
            "as a Llama with experts"
        )


#: what a ``layer_types`` entry may say, and the operator it names
_LAYER_TYPE_OPS = {
    "full_attention": "attn", "sliding_attention": "attn", "conv": "conv",
}


#: the linear-attention operators the forwards implement, by the
#: config's ``linear_attention_type``
_LINEAR_ATTENTION_TYPES = frozenset(("GigaChat35GatedDeltaNet",))


def _linear_layer_kinds(cfg: dict) -> tuple:
    """GigaChat 3.5: ``full_attention_layers`` names the (latent)
    attention layers, every other layer is the linear-attention operator
    ``linear_attention_type`` names. A depth cut keeps the published list
    whole: the entries under ``num_hidden_layers`` are the layers that
    are there, and at least one entry must be (a stack of linear layers
    alone has no KV cache to hand the engine)."""
    kind = cfg.get("linear_attention_type")
    if kind not in _LINEAR_ATTENTION_TYPES:
        raise ValueError(
            f"unsupported linear_attention_type {kind!r}: the forwards "
            f"know {sorted(_LINEAR_ATTENTION_TYPES)} only")
    L = cfg.get("num_hidden_layers", 32)
    full = cfg.get("full_attention_layers")
    if not isinstance(full, (list, tuple)) or any(
            not isinstance(i, int) or i < 0 for i in full):
        raise ValueError(
            f"full_attention_layers must list layer indices, got {full!r}")
    depth = cfg.get("num_hidden_layers_published", L)
    past = sorted(i for i in full if i >= max(depth, L))
    if past:
        raise ValueError(
            f"full_attention_layers entries {past} lie past the depth "
            f"({max(depth, L)} layers)")
    here = {i for i in full if i < L}
    if not here:
        raise ValueError(
            f"full_attention_layers {list(full)} names no layer under "
            f"num_hidden_layers {L}: a stack of linear-attention layers "
            "alone is not supported")
    return tuple("attn" if l in here else "linear" for l in range(L))


def _layer_kinds(cfg: dict, is_lfm2: bool,
                 is_gigachat35: bool = False) -> tuple[tuple, int]:
    """(operator kind of every layer, leading dense-FFN layers): the ONE
    place ``layer_types`` / ``full_attention_layers`` and
    ``num_dense_layers`` / ``first_k_dense_replace`` are read into kinds.
    The operator tuple is empty for an attention-only stack (every family
    but LFM2 and GigaChat 3.5); an entry no forward implements is refused
    by name, for every family — it would otherwise run as attention."""
    if is_gigachat35:
        return _linear_layer_kinds(cfg), cfg.get(
            "first_k_dense_replace", 0) or 0
    if cfg.get("linear_attention_type") or cfg.get("full_attention_layers"):
        raise ValueError(
            "linear_attention_type / full_attention_layers under a family "
            "other than gigachat3_5: only GigaChat 3.5's gated delta rule "
            "is implemented")
    types = cfg.get("layer_types") or ()
    unknown = sorted({t for t in types if t not in _LAYER_TYPE_OPS})
    if unknown:
        raise ValueError(
            f"unsupported layer_types entries {unknown}: the forwards "
            f"know {sorted(_LAYER_TYPE_OPS)} only")
    ops = tuple(_LAYER_TYPE_OPS[t] for t in types)
    if "conv" in ops and not is_lfm2:
        raise ValueError(
            "layer_types names 'conv' layers under a family other than "
            "lfm2 / lfm2_moe: only LFM2's gated short convolution is "
            "implemented")
    n_dense = cfg.get("first_k_dense_replace", 0) or 0
    if not is_lfm2:
        return (), n_dense
    # a depth cut keeps the published list whole: the first
    # num_hidden_layers entries are the layers that are there
    L = cfg.get("num_hidden_layers", 32)
    if len(ops) < L:
        raise ValueError(
            f"layer_types has {len(ops)} entries for {L} layers")
    return ops[:L], cfg.get("num_dense_layers", 0) or 0


@dataclass(eq=False)  # identity hash/eq: used as a jit static arg
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 0  # 0 -> hidden_size // num_heads
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # qwen3: per-head RMS norm on q and k after projection, before rope
    qk_norm: bool = False
    # olmo-2, olmoe: q/k RMS norm over the FULL projection width
    # (pre-reshape)
    qk_norm_full: bool = False
    # olmo-2: NO input/pre-FFN norms — normalization applies to the
    # sublayer OUTPUT (post_norms) only
    norm_after: bool = False
    # MoE (0 experts = dense)
    num_experts: int = 0
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0  # DeepSeek-style always-on experts
    # Qwen2-MoE: one shared expert of its OWN width whose contribution
    # is gated by sigmoid(x @ shared_expert_gate) instead of always-on
    shared_expert_size: int = 0  # 0 = moe_intermediate * num_shared
    shared_expert_gate: bool = False
    first_dense_layers: int = 0  # DeepSeek first_k_dense_replace
    norm_topk_prob: bool = True  # Mixtral renormalizes top-k gate probs
    # DeepSeek-V2/V3 routing variants (ref patch:3548-3560 deepseek_v2;
    # BASELINE config 5 names DeepSeek-R1 = the V3 architecture)
    moe_scoring: str = "softmax"  # "softmax" (V2) | "sigmoid" (V3)
    moe_gate_bias: bool = False  # V3 e_score_correction_bias (topk only)
    routed_scaling_factor: float = 1.0
    n_group: int = 0  # group-limited routing (0 = off)
    topk_group: int = 0
    # group score: V2 group_limited_greedy uses the group MAX, V3
    # noaux_tc the sum of the group's top-2
    moe_group_score: str = "max"
    # Multi-Latent Attention (DeepSeek-V2/V3; kv_lora_rank > 0 enables).
    # The KV cache stores the COMPRESSED latent per token: c_kv
    # [kv_lora_rank] in the k-cache slot and the shared rotated k_pe
    # [qk_rope_head_dim] in the v-cache slot, both single-"head" paged
    # arrays — attention runs ABSORBED (q_nope folded through the
    # kv_b up-projection), so per-token cache bytes are
    # kv_lora_rank + qk_rope_head_dim instead of 2*Hkv*head_dim.
    q_lora_rank: int = 0  # 0 = direct q projection (V2-Lite)
    kv_lora_rank: int = 0  # 0 = regular attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # DeepSeek checkpoints store rope dims interleaved (GPT-J pairs);
    # weights.py de-interleaves at load so the runtime rotation stays the
    # fast half-split form — this flag records the CHECKPOINT convention
    rope_interleave: bool = False
    # sliding-window attention (mistral v0.1-style; 0 = full attention).
    # Enforced by masking in the XLA paths and by per-row window floors
    # in the in-repo Pallas kernels (the jax library decode kernel has
    # no window support and is skipped when a window is set).
    # Speculative decoding composes (exact per-row floors via the
    # kernel's ``group`` row mapping).
    sliding_window: int = 0
    # gpt-oss: layers ALTERNATE sliding/full attention. When set, entry
    # l is layer l's window (0 = full) and the GLOBAL sliding_window is
    # forced to 0 — per-layer entries are the only source of widths, so
    # homogeneous gates never window every layer. Such models run the
    # unrolled layer paths (a lax.scan body cannot carry a per-layer
    # static mask shape).
    layer_windows: tuple = ()
    # gpt-oss attention sinks: a learnable per-head logit joins every
    # softmax's normalization (no value row) — attention mass can park
    # on the sink instead of real tokens. Folded into the denominator
    # in the XLA attention paths.
    attn_sinks: bool = False
    # gpt-oss expert FFN: fused clamped SwiGLU — gate clamped at +limit,
    # up at +-limit, glu = gate*sigmoid(alpha*gate), out = (up+1)*glu —
    # with per-expert biases on both projections
    moe_act: str = "swiglu"  # "swiglu" | "gptoss_clamp"
    # o_proj bias (gpt-oss: every attention projection carries bias)
    o_bias: bool = False
    # gemma-family variants
    hidden_act: str = "silu"  # "silu" | "gelu_tanh" (gemma GeGLU)
    rms_add_unit: bool = False  # gemma RMSNorm scales by (1 + w)
    scale_embed: bool = False  # gemma multiplies embeddings by sqrt(E)
    # gemma-2: tanh caps on attention scores / final logits, sandwich
    # (post-attention + post-FFN) norms, and a fixed query scale from
    # query_pre_attn_scalar instead of head_dim
    attn_softcap: float = 0.0  # 0 = off
    final_softcap: float = 0.0
    post_norms: bool = False
    attn_scale_base: int = 0  # 0 = use head_dim
    # gemma-3: sliding layers rope at their own LOCAL base frequency
    # (rope_local_base_freq); full layers use rope_theta (+scaling)
    rope_local_theta: float = 0.0  # 0 = single rope for all layers
    # partial rotary (Phi-4-mini, GLM, persimmon): only the first
    # head_dim * rope_partial_factor dims of each head rotate
    # (rope_partial_dim derives in __post_init__ once head_dim resolves)
    rope_partial_factor: float = 1.0
    rope_partial_dim: int = 0
    # LFM2: every layer's OPERATOR is one of two kinds, "attn" or "conv"
    # (a gated short causal convolution over the last conv_kernel
    # tokens), chosen per layer; () = attention everywhere. A conv layer
    # holds no keys and values: its per-sequence state is the last
    # conv_kernel - 1 rows of B * x (llama.short_conv), and the KV cache
    # holds the attention layers only (kv_layers, op_index). The FFN kind
    # is chosen independently: first_dense_layers leading dense ones.
    layer_ops: tuple = ()
    conv_kernel: int = 0
    # GigaChat 3.5: a third operator kind, "linear": the gated delta rule
    # (llama.gated_delta) over linear_key_heads key heads (each serving
    # linear_value_heads / linear_key_heads value heads), behind a
    # depthwise causal convolution of linear_conv_kernel taps over q, k
    # and v. Its per-sequence state has two parts: the convolution's last
    # taps - 1 rows (the model's dtype) and one [key_dim, value_dim]
    # float32 matrix a value head (llama.init_state)
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_conv_kernel: int = 0
    linear_o_norm_eps: float = 1e-6
    linear_gate_scale: float = 1.0  # the output gate: scale * sigmoid(z)
    # the attention output times sigmoid(h W_g) before the output
    # projection (arXiv:2505.06708)
    gated_attention: bool = False
    # SwiGLU with both streams clamped: silu(min(gate, limit)) *
    # clip(up, -limit, limit), in every FFN kind; 0 = off
    swiglu_limit: float = 0.0
    # ZeroCenteredGatedNorm: an RMS norm's learned scale is
    # norm_gate_weight * sigmoid(w) (1 at w = 0 for a weight of 2);
    # 0 = the plain scale w
    norm_gate_weight: float = 0.0
    # one chip's share of an expert layer (any expert family): the chip
    # holds experts [expert_first, expert_first + experts_held) of
    # num_experts. The router scores all num_experts and picks
    # num_experts_per_tok of them; the chip computes what ITS experts
    # give the rows routed to them (plus the shared expert), and the
    # other chips' parts are not stood in for. 0 = every expert is here
    experts_held: int = 0
    expert_first: int = 0
    # the renormalised top-k combine weights divide by (sum + this)
    topk_norm_eps: float = 1e-20
    # runtime
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.layer_windows:
            self.layer_windows = tuple(self.layer_windows)
            if len(self.layer_windows) != self.num_layers:
                raise ValueError(
                    f"layer_windows has {len(self.layer_windows)} entries "
                    f"for {self.num_layers} layers"
                )
        self.layer_ops = tuple(self.layer_ops)
        if self.layer_ops and len(self.layer_ops) != self.num_layers:
            raise ValueError(
                f"layer_ops has {len(self.layer_ops)} entries for "
                f"{self.num_layers} layers"
            )
        if self.head_dim == 0:
            self.head_dim = self.hidden_size // self.num_heads
        if self.rope_partial_factor != 1.0 and not self.rope_partial_dim:
            self.rope_partial_dim = int(self.head_dim * self.rope_partial_factor)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def moe_layers(self) -> int:
        """Layers whose FFN is the expert layer."""
        return self.num_layers - self.first_dense_layers if self.is_moe else 0

    @property
    def conv_layers(self) -> int:
        """Layers whose operator is the short convolution: the layers
        that carry a per-sequence state instead of keys and values."""
        return self.layer_ops.count("conv")

    @property
    def linear_layers(self) -> int:
        """Layers whose operator is the gated delta rule: a convolution's
        rows AND a recurrent matrix a sequence instead of keys and values."""
        return self.layer_ops.count("linear")

    @property
    def state_layers(self) -> int:
        """Layers that carry a per-sequence state (either kind)."""
        return self.conv_layers + self.linear_layers

    @property
    def kv_layers(self) -> int:
        """Layers that hold keys and values: the KV cache's layer axis."""
        return self.num_layers - self.state_layers

    @property
    def local_experts(self) -> int:
        """Experts whose weights this chip holds (``experts_held``)."""
        return self.experts_held or self.num_experts

    @property
    def linear_conv_dim(self) -> int:
        """Channels of a linear layer's convolution: q, k and v."""
        return (2 * self.linear_key_heads * self.linear_key_dim
                + self.linear_value_heads * self.linear_value_dim)

    def op_index(self, l: int) -> int:
        """Layer ``l``'s ordinal among the layers of its operator kind:
        an attention layer's index into the KV cache, a conv layer's into
        the conv state (``l`` itself for an attention-only stack)."""
        if not self.layer_ops:
            return l
        return self.layer_ops[:l].count(self.layer_ops[l])

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def mla_softmax_scale(self) -> float:
        """qk_head_dim^-0.5 times the YaRN mscale^2 correction DeepSeek
        applies when rope_scaling.mscale_all_dim is set."""
        scale = self.qk_head_dim**-0.5
        rs = self.rope_scaling or {}
        if is_yarn(rs):
            m = yarn_mscale(rs.get("factor", 1.0),
                            rs.get("mscale_all_dim", 0.0) or 0.0)
            scale = scale * m * m
        return scale

    @staticmethod
    def from_hf_config(cfg: dict) -> "ModelConfig":
        archs = cfg.get("architectures") or []
        if isinstance(cfg.get("text_config"), dict) and any(
            a.startswith("Gemma3") for a in archs
        ):
            # gemma-3 multimodal checkpoints nest the language model
            # under text_config; serve that (the vision tower has no
            # TPU serving path here)
            merged = {**cfg["text_config"], "architectures": archs}
            if cfg.get("torch_dtype") and "torch_dtype" not in merged:
                merged["torch_dtype"] = cfg["torch_dtype"]
            cfg = merged
        # Qwen2 has qkv bias baked into the architecture; its HF config
        # carries no attention_bias field
        qkv_bias = cfg.get("attention_bias", False) or any(
            a.startswith("Qwen2") for a in archs
        )
        # gemma: GeGLU activation, (1+w) norms, sqrt(E)-scaled embeddings
        is_gemma = any(a.startswith("Gemma") for a in archs) or (
            cfg.get("model_type", "").startswith("gemma")
        )
        is_gptoss = any(a.startswith("GptOss") for a in archs)
        is_gemma2 = any(a.startswith("Gemma2") for a in archs) or (
            cfg.get("model_type") == "gemma2"
        )
        is_gemma3 = any(a.startswith("Gemma3") for a in archs) or (
            cfg.get("model_type") in ("gemma3", "gemma3_text")
        )
        # EXACT arch matching: Glm4Moe (qk-norm MoE) and Glm4v
        # (multimodal, text under text_config) have different layer
        # anatomy — reject them rather than mis-serve (the file's
        # standing reject-over-wrong-logits rule)
        glm_archs = {a for a in archs if a.startswith("Glm")}
        if glm_archs - {"GlmForCausalLM", "Glm4ForCausalLM"}:
            raise ValueError(
                f"unsupported GLM variant {sorted(glm_archs)} — only "
                "GlmForCausalLM / Glm4ForCausalLM are implemented"
            )
        is_glm = bool(glm_archs) or cfg.get("model_type") in ("glm", "glm4")
        is_olmo2 = any(a.startswith("Olmo2") for a in archs) or (
            cfg.get("model_type") == "olmo2"
        )
        is_glm4 = "Glm4ForCausalLM" in glm_archs or (
            cfg.get("model_type") == "glm4"
        )
        # olmoe: a PRE-norm llama layer with olmo-2's full-width q/k
        # norms; intermediate_size is ONE expert's width; the router's
        # softmax weights are used as they are (norm_topk_prob false)
        is_olmoe = any(a.startswith("Olmoe") for a in archs) or (
            cfg.get("model_type") == "olmoe"
        )
        if is_olmoe and cfg.get("clip_qkv") is not None:
            raise ValueError(
                f"olmoe with clip_qkv={cfg['clip_qkv']} is not supported "
                "(the q/k/v clamp is not implemented; the published "
                "OLMoE-1B-7B configs carry clip_qkv: null)"
            )
        # lfm2 / lfm2_moe: conv and attention operators chosen per layer
        # (layer_types), per-head q/k norms before rope, a tied head;
        # lfm2_moe adds sigmoid-routed experts picked by a biased score
        # behind num_dense_layers dense layers
        is_lfm2 = any(a.startswith("Lfm2") for a in archs) or (
            cfg.get("model_type") in ("lfm2", "lfm2_moe")
        )
        is_lfm2moe = any(a.startswith("Lfm2Moe") for a in archs) or (
            cfg.get("model_type") == "lfm2_moe"
        )
        if is_lfm2 and cfg.get("conv_bias"):
            raise ValueError(
                "lfm2 with conv_bias=true is not supported (the "
                "convolution's and its projections' biases are not "
                "implemented; the published LFM2 configs carry false)"
            )
        # gigachat3_5: linear-attention (gated delta rule) and gated
        # latent-attention layers (full_attention_layers), norms before
        # and after every sublayer whose scale is 2 sigmoid(w), clamped
        # SwiGLU, DeepSeek-V3's sigmoid router with a selection bias (the
        # family's: the config carries no scoring_func / topk_method key)
        is_gigachat35 = any(a.startswith("GigaChat35") for a in archs) or (
            cfg.get("model_type") == "gigachat3_5"
        )
        if is_gigachat35:
            refused = [k for k, bad in (
                ("norm_type", cfg.get("norm_type", "ZeroCenteredGatedNorm")
                 != "ZeroCenteredGatedNorm"),
                ("layernorm_type",
                 cfg.get("layernorm_type", "pre_post") != "pre_post"),
                ("linear_gating_type", cfg.get(
                    "linear_gating_type",
                    "gated_rmsnorm_sigmoid_zero_centered")
                 != "gated_rmsnorm_sigmoid_zero_centered"),
                ("use_shared_expert_sigmoid",
                 bool(cfg.get("use_shared_expert_sigmoid"))),
                ("n_group", (cfg.get("n_group") or 1) > 1),
                ("kv_lora_rank", not cfg.get("kv_lora_rank")),
            ) if bad]
            if refused:
                raise ValueError(
                    f"gigachat3_5 with {refused} other than the published "
                    "GigaChat3.5 configs carry is not supported (only "
                    "ZeroCenteredGatedNorm / pre_post / "
                    "gated_rmsnorm_sigmoid_zero_centered, an always-on "
                    "shared expert, one routing group and latent "
                    "attention are implemented)")
            if cfg.get("num_nextn_predict_layers"):
                raise ValueError(
                    "gigachat3_5 with num_nextn_predict_layers="
                    f"{cfg['num_nextn_predict_layers']}: the multi-token "
                    "prediction modules are not served (no model-native "
                    "drafting; the verify forward cannot roll a recurrent "
                    "state back) — serve with num_nextn_predict_layers 0")
        layer_ops, n_dense = _layer_kinds(cfg, is_lfm2, is_gigachat35)
        _reject_unknown_expert_family(cfg, archs)
        # one chip's share of the experts (any family): expert_share
        # {"published": X, "first": i} beside the family's own count key,
        # which then counts the experts held here
        share = cfg.get("expert_share")
        held_key = next((k for k in _EXPERT_COUNT_KEYS if cfg.get(k)), None)
        experts_held = expert_first = 0
        if share is not None:
            held = cfg.get(held_key, 0) if held_key else 0
            pub, expert_first = share.get("published", 0), share.get("first", 0)
            if not (0 < held <= pub and 0 <= expert_first
                    and expert_first + held <= pub):
                raise ValueError(
                    f"expert_share {share} beside {held_key}={held}: the "
                    "held experts must be a range of the published ones")
            experts_held = held if held < pub else 0
            cfg = dict(cfg)
            cfg[held_key] = pub
        # qwen2moe: gated shared expert; interleaved dense layers are
        # not implemented — reject rather than serve wrong logits
        is_qwen2moe = any(a.startswith("Qwen2Moe") for a in archs)
        if is_qwen2moe and (
            cfg.get("decoder_sparse_step", 1) != 1
            or cfg.get("mlp_only_layers")
        ):
            raise ValueError(
                "qwen2moe with decoder_sparse_step != 1 or mlp_only_layers "
                "is not supported (interleaved dense/sparse layers)"
            )
        # layer_types: per-layer sliding/full alternation (gpt-oss,
        # gemma-2/3 style)
        layer_windows: tuple = ()
        if (is_gptoss or is_gemma2 or is_gemma3) and cfg.get("layer_types"):
            sw = cfg.get("sliding_window") or 0
            layer_windows = tuple(
                sw if t == "sliding_attention" else 0
                for t in cfg["layer_types"]
            )
        elif is_gemma3 and cfg.get("sliding_window") and cfg.get(
            "sliding_window_pattern"
        ):
            # original gemma-3 uploads predate layer_types: every Nth
            # layer is full attention (HF: sliding iff (i+1) % N != 0)
            sw, n = cfg["sliding_window"], cfg["sliding_window_pattern"]
            layer_windows = tuple(
                sw if (i + 1) % n else 0
                for i in range(cfg.get("num_hidden_layers", 32))
            )
        elif is_gemma3 and cfg.get("sliding_window"):
            raise ValueError(
                "gemma-3 config has sliding_window but neither "
                "layer_types nor sliding_window_pattern — cannot "
                "recover the sliding/full alternation; refusing to "
                "serve wrong attention"
            )
        elif is_gemma2 and cfg.get("sliding_window"):
            # original gemma-2 uploads predate the layer_types key: the
            # architecture alternates sliding on EVEN layers
            # (modeling_gemma2: sliding iff layer_idx % 2 == 0) — a bare
            # global window would wrongly mask the full-attention layers
            sw = cfg["sliding_window"]
            layer_windows = tuple(
                sw if i % 2 == 0 else 0
                for i in range(cfg.get("num_hidden_layers", 32))
            )

        # Phi-3 keeps original_max_position_embeddings at the TOP level
        # of config.json; the longrope math needs it inside the scaling
        # dict (where yarn/llama3 checkpoints put theirs)
        rope_scaling = cfg.get("rope_scaling")
        if (
            rope_scaling
            and (rope_scaling.get("rope_type") or rope_scaling.get("type"))
            == "longrope"
            and "original_max_position_embeddings" not in rope_scaling
            and cfg.get("original_max_position_embeddings")
        ):
            rope_scaling = dict(
                rope_scaling,
                original_max_position_embeddings=cfg[
                    "original_max_position_embeddings"
                ],
            )
        act = cfg.get("hidden_act") or cfg.get("hidden_activation") or "silu"
        if act in ("gelu", "gelu_pytorch_tanh", "gelu_tanh"):
            act = "gelu_tanh"
        ffn_width = cfg.get("intermediate_size", 11008)
        if is_lfm2:
            # the original LFM2 uploads' key names, read as
            # transformers' Lfm2Config reads them
            ffn_width = cfg.get("block_ff_dim", ffn_width)
            cfg = dict(cfg)
            cfg["rope_theta"] = cfg.get("theta", cfg.get("rope_theta", 1e6))
            if "tie_embedding" in cfg:
                cfg["tie_word_embeddings"] = cfg["tie_embedding"]
        if is_lfm2 and not is_lfm2moe and cfg.get(
            "block_auto_adjust_ff_dim", True
        ):
            # dense LFM2 (Lfm2MLP): the written width is cut to 2/3,
            # scaled, and rounded up to a multiple
            mult = cfg.get("block_multiple_of", 256)
            ffn_width = int(2 * ffn_width / 3)
            if cfg.get("block_ffn_dim_multiplier", 1.0) is not None:
                ffn_width = int(
                    cfg.get("block_ffn_dim_multiplier", 1.0) * ffn_width)
            ffn_width = mult * ((ffn_width + mult - 1) // mult)
        # the keys only GigaChat 3.5 is read for: absent for every other
        giga = cfg if is_gigachat35 else {}
        return ModelConfig(
            vocab_size=cfg.get("vocab_size", 32000),
            hidden_size=cfg.get("hidden_size", 4096),
            intermediate_size=ffn_width,
            num_layers=cfg.get("num_hidden_layers", 32),
            num_heads=cfg.get("num_attention_heads", 32),
            num_kv_heads=cfg.get("num_key_value_heads", cfg.get("num_attention_heads", 32)),
            head_dim=cfg.get("head_dim", 0) or 0,
            rope_theta=cfg.get("rope_theta", 10000.0),
            rope_partial_factor=cfg.get("partial_rotary_factor") or 1.0,
            rope_scaling=rope_scaling,
            rms_norm_eps=cfg.get("norm_eps", 1e-5) if is_lfm2
            else cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 8192),
            tie_word_embeddings=cfg.get(
                "tie_word_embeddings", is_gemma or is_lfm2),
            attention_bias=qkv_bias,
            # qwen3 (dense and MoE): per-head q/k RMS norm, no qkv bias
            qk_norm=any(a.startswith("Qwen3") for a in archs) or is_gemma3
            or is_olmo2 or is_olmoe or is_lfm2,
            layer_windows=layer_windows,
            layer_ops=layer_ops,
            conv_kernel=(cfg.get("conv_L_cache", 3) if is_lfm2 else 0),
            linear_key_heads=giga.get("linear_num_key_heads", 0),
            linear_value_heads=giga.get("linear_num_value_heads", 0),
            linear_key_dim=giga.get("linear_key_head_dim", 0),
            linear_value_dim=giga.get("linear_value_head_dim", 0),
            linear_conv_kernel=giga.get("linear_conv_kernel_dim", 4)
            if is_gigachat35 else 0,
            linear_o_norm_eps=giga.get("linear_attn_o_norm_eps", 1e-6),
            linear_gate_scale=giga.get("linear_sigmoid_gate_scale", 1.0),
            gated_attention=bool(giga.get("gated_attention")),
            swiglu_limit=float(giga.get("swiglu_limit") or 0.0),
            norm_gate_weight=float(giga.get("layernorm_gating_weight", 2))
            if is_gigachat35 else 0.0,
            experts_held=experts_held,
            expert_first=expert_first,
            attn_sinks=is_gptoss,
            moe_act="gptoss_clamp" if is_gptoss else "swiglu",
            o_bias=is_gptoss and bool(cfg.get("attention_bias")),
            # mixtral: num_local_experts; deepseek: n_routed_experts;
            # qwen2moe/qwen3moe/olmoe: the bare num_experts key
            num_experts=cfg.get(
                "num_local_experts",
                cfg.get(
                    "n_routed_experts",
                    cfg.get("num_experts", 0)
                    if is_olmoe or is_lfm2moe
                    or any(a.startswith(("Qwen3", "Qwen2Moe"))
                           for a in archs) else 0,
                ),
            ) or 0,
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            moe_intermediate_size=(
                cfg.get("intermediate_size", 0) if is_olmoe
                else cfg.get("moe_intermediate_size", 0)
            ) or 0,
            # qwen2moe: ONE gated shared expert of its own width
            num_shared_experts=cfg.get("n_shared_experts", 0) or (
                1 if is_qwen2moe else 0
            ),
            shared_expert_size=(
                cfg.get("shared_expert_intermediate_size", 0) or 0
            ) if is_qwen2moe else 0,
            shared_expert_gate=is_qwen2moe,
            first_dense_layers=n_dense,
            norm_topk_prob=cfg.get("norm_topk_prob", not is_olmoe),
            # deepseek_v2/v3 (R1 = V3): sigmoid scoring + gate bias and
            # group-limited top-k arrive with topk_method "noaux_tc"
            # lfm2_moe: sigmoid scores, a bias that picks and does not
            # weigh (use_expert_bias), weights renormalised over
            # (sum + 1e-6)
            moe_scoring="sigmoid" if is_lfm2moe or is_gigachat35
            else cfg.get("scoring_func", "softmax"),
            moe_gate_bias=bool(cfg.get("use_expert_bias")) if is_lfm2moe
            else is_gigachat35 or cfg.get("topk_method") == "noaux_tc",
            topk_norm_eps=1e-6 if is_lfm2moe else 1e-20,
            moe_group_score=(
                "top2" if cfg.get("topk_method") == "noaux_tc" else "max"
            ),
            routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
            n_group=cfg.get("n_group", 0) or 0,
            topk_group=cfg.get("topk_group", 0) or 0,
            q_lora_rank=cfg.get("q_lora_rank") or 0,
            kv_lora_rank=cfg.get("kv_lora_rank") or 0,
            qk_nope_head_dim=cfg.get("qk_nope_head_dim") or 0,
            qk_rope_head_dim=cfg.get("qk_rope_head_dim") or 0,
            v_head_dim=cfg.get("v_head_dim") or 0,
            # interleaved (GPT-J-pair) rope storage: MLA checkpoints
            # interleave the TRAILING rope dims, GLM the LEADING partial
            # dims — both de-interleave at load so the runtime rotation
            # stays the fast half-split form
            rope_interleave=cfg.get(
                "rope_interleave",
                (cfg.get("model_type", "").startswith("deepseek")
                 and bool(cfg.get("kv_lora_rank"))) or is_glm,
            ),
            # with per-layer windows the GLOBAL width stays 0 — the
            # homogeneous paths/gates must not window every layer
            sliding_window=(
                0 if layer_windows else (cfg.get("sliding_window") or 0)
            ),
            hidden_act=act if act != "silu" else "silu",
            rms_add_unit=is_gemma,
            attn_softcap=(cfg.get("attn_logit_softcapping") or 0.0)
            if is_gemma2 else 0.0,
            final_softcap=(cfg.get("final_logit_softcapping") or 0.0)
            if is_gemma2 else 0.0,
            post_norms=is_gemma2 or is_gemma3 or is_glm4 or is_olmo2
            or is_gigachat35,
            norm_after=is_olmo2,
            qk_norm_full=is_olmo2 or is_olmoe,
            attn_scale_base=(cfg.get("query_pre_attn_scalar") or 0)
            if (is_gemma2 or is_gemma3) else 0,
            rope_local_theta=(cfg.get("rope_local_base_freq") or 0.0)
            if is_gemma3 else 0.0,
            scale_embed=is_gemma,
            dtype=cfg.get("torch_dtype") or "bfloat16",
        )

    @staticmethod
    def from_local_path(path: str) -> "ModelConfig":
        with open(os.path.join(path, "config.json")) as f:
            return ModelConfig.from_hf_config(json.load(f))

    @staticmethod
    def tiny(**overrides) -> "ModelConfig":
        """A small config for tests/benches."""
        base = dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_layers=2, num_heads=4, num_kv_heads=2, head_dim=16,
            max_position_embeddings=512,
        )
        base.update(overrides)
        return ModelConfig(**base)

    @staticmethod
    def tiny_mla(**overrides) -> "ModelConfig":
        """A small DeepSeek-shaped MLA config (compressed latent cache,
        absorbed attention) for tests/benches — ONE definition so shape
        tweaks can't drift between the many tests that need it."""
        base = dict(
            num_heads=4, num_kv_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            q_lora_rank=24, num_layers=2,
        )
        base.update(overrides)
        return ModelConfig.tiny(**base)

    # llama-3-8b-ish for benches
    @staticmethod
    def llama3_8b(**overrides) -> "ModelConfig":
        base = dict(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_layers=32, num_heads=32, num_kv_heads=8, head_dim=128,
            rope_theta=500000.0, max_position_embeddings=8192,
        )
        base.update(overrides)
        return ModelConfig(**base)

    # llama-3-70b (BASELINE config 4: the disagg + router north star)
    @staticmethod
    def llama3_70b(**overrides) -> "ModelConfig":
        base = dict(
            vocab_size=128256, hidden_size=8192, intermediate_size=28672,
            num_layers=80, num_heads=64, num_kv_heads=8, head_dim=128,
            rope_theta=500000.0, max_position_embeddings=8192,
        )
        base.update(overrides)
        return ModelConfig(**base)

    # mixtral-8x22b (BASELINE config 5 alternative: classic EP decode)
    @staticmethod
    def mixtral_8x22b(**overrides) -> "ModelConfig":
        base = dict(
            vocab_size=32768, hidden_size=6144, intermediate_size=16384,
            num_layers=56, num_heads=48, num_kv_heads=8, head_dim=128,
            rope_theta=1000000.0, max_position_embeddings=65536,
            num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=16384, norm_topk_prob=True,
        )
        base.update(overrides)
        return ModelConfig(**base)

    # gpt-oss (published shapes): alternating sliding/full attention,
    # sinks, biased clamped-SwiGLU MoE, head_dim 64. 120b: 36 layers /
    # 128 experts; 20b: 24 layers / 32 experts — both top-4.
    @staticmethod
    def gptoss_120b(**overrides) -> "ModelConfig":
        base = dict(
            vocab_size=201088, hidden_size=2880, intermediate_size=2880,
            num_layers=36, num_heads=64, num_kv_heads=8, head_dim=64,
            rope_theta=150000.0, max_position_embeddings=131072,
            num_experts=128, num_experts_per_tok=4,
            moe_intermediate_size=2880, moe_act="gptoss_clamp",
            attn_sinks=True, o_bias=True, attention_bias=True,
            layer_windows=tuple(128 if i % 2 == 0 else 0
                                for i in range(36)),
            # the published YaRN extension (4k→128k): llama._rope_freqs
            # implements this ruleset (incl. the fractional correction
            # range gpt-oss's truncate=False keeps) — required for
            # correct logits past ~4k when real weights load through
            # this preset
            rope_scaling=dict(
                rope_type="yarn", factor=32.0, beta_fast=32.0,
                beta_slow=1.0, original_max_position_embeddings=4096,
                truncate=False,
            ),
        )
        base.update(overrides)
        return ModelConfig(**base)

    @staticmethod
    def gptoss_20b(**overrides) -> "ModelConfig":
        base = dict(num_layers=24, num_experts=32,
                    layer_windows=tuple(128 if i % 2 == 0 else 0
                                        for i in range(24)))
        base.update(overrides)
        return ModelConfig.gptoss_120b(**base)

    # deepseek-r1 = the DeepSeek-V3 architecture (BASELINE config 5
    # flagship: MLA latent cache + 256-expert sigmoid-scored MoE).
    # Shape fields follow the published V3 config.json.
    @staticmethod
    def deepseek_r1(**overrides) -> "ModelConfig":
        base = dict(
            vocab_size=129280, hidden_size=7168, intermediate_size=18432,
            num_layers=61, num_heads=128, num_kv_heads=128,
            rope_theta=10000.0, max_position_embeddings=163840,
            num_experts=256, num_experts_per_tok=8,
            moe_intermediate_size=2048, num_shared_experts=1,
            first_dense_layers=3, norm_topk_prob=True,
            moe_scoring="sigmoid", moe_gate_bias=True,
            routed_scaling_factor=2.5, n_group=8, topk_group=4,
            moe_group_score="top2",
            q_lora_rank=1536, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            # the published config.json's YaRN extension (4k→160k) and
            # GPT-J-interleaved rope storage — required for correct
            # logits when real R1 weights load through this preset
            rope_scaling=dict(
                type="yarn", factor=40.0, beta_fast=32.0, beta_slow=1.0,
                mscale=1.0, mscale_all_dim=1.0,
                original_max_position_embeddings=4096,
            ),
            rope_interleave=True,
        )
        base.update(overrides)
        return ModelConfig(**base)
