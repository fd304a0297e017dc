"""HF checkpoint loading: safetensors -> the engine's param pytree.

Replaces the reference's delegation of weight loading to its engines (plus
hub download, launch/dynamo-run/src/hub.rs — here models are local paths;
fetching is an operator concern). Loads sharded ``*.safetensors`` files
lazily, maps HF llama naming onto the stacked-layer pytree, and can place
each tensor directly onto its mesh sharding to avoid a full host copy of
the model per process.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

logger = logging.getLogger(__name__)


def _np_dtype(dtype: str):
    return {"bfloat16": jnp.bfloat16, "float32": np.float32, "float16": np.float16}[dtype]


def load_llama_params(
    path: str,
    cfg: ModelConfig,
    mesh=None,
    dtype: Optional[str] = None,
) -> dict:
    """Load a HF llama-family or DeepSeek-MLA checkpoint directory into
    the stacked pytree used by dynamo_tpu.models.llama. DeepSeek's
    first_k_dense_replace leading dense layers land in a second stacked
    group (``dense_layers``) that the forward scans separately."""
    from safetensors import safe_open

    dt = _np_dtype(dtype or str(cfg.dtype))
    files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {path}")

    # build tensor name -> file map (honors index.json if present)
    index_file = os.path.join(path, "model.safetensors.index.json")
    name_to_file: dict[str, str] = {}
    if os.path.exists(index_file):
        with open(index_file) as f:
            name_to_file = json.load(f)["weight_map"]
    else:
        for fname in files:
            with safe_open(os.path.join(path, fname), framework="numpy") as f:
                for name in f.keys():
                    name_to_file[name] = fname

    handles: dict[str, object] = {}

    # multimodal checkpoints (gemma-3 conditional generation et al.)
    # nest the language model: weights live under language_model.model.*
    # (or model.language_model.* in newer transformers) instead of the
    # bare model.* this loader's name table uses — resolve the prefix
    # once from wherever the embedding actually lives
    _prefix = ""
    if "model.embed_tokens.weight" not in name_to_file:
        for cand in ("language_model.", "model.language_model."):
            if (
                cand + "model.embed_tokens.weight" in name_to_file
                or cand + "embed_tokens.weight" in name_to_file
            ):
                _prefix = cand
                break

    def _resolve(name: str) -> str:
        """Bare llama-family name -> this checkpoint's actual key.
        Tries, in order: the bare name (lm_head etc. stay top-level in
        multimodal checkpoints), prefix+name, and prefix replacing the
        leading "model." segment."""
        if not _prefix or name in name_to_file:
            return name
        full = _prefix + name
        if full in name_to_file:
            return full
        if name.startswith("model."):
            alt = _prefix + name[len("model."):]
            if alt in name_to_file:
                return alt
        return name

    def get(name: str) -> np.ndarray:
        name = _resolve(name)
        fname = name_to_file[name]
        if fname not in handles:
            handles[fname] = safe_open(os.path.join(path, fname), framework="numpy")
        t = handles[fname].get_tensor(name)
        return t

    L = cfg.num_layers

    def stack(fmt: str, rng, transpose: bool = True) -> np.ndarray:
        mats = []
        for i in rng:
            t = get(fmt.format(i=i))
            mats.append(t.T if transpose else t)
        return np.stack(mats)

    def has(name: str) -> bool:
        return _resolve(name) in name_to_file

    def deinterleave_rope(w: np.ndarray, n_head: int, d_head: int,
                          d_rope: int, leading: bool = False) -> np.ndarray:
        """GPT-J-pair rope columns -> the half-split layout the runtime
        rotation uses, for a [..., n_head*d_head] projection (or stacked
        bias). DeepSeek/MLA interleaves the TRAILING d_rope dims of each
        head; GLM (``leading=True``) the LEADING ones."""
        if not cfg.rope_interleave:
            return w
        v = w.reshape(*w.shape[:-1], n_head, d_head)
        perm = np.concatenate(
            [np.arange(0, d_rope, 2), np.arange(1, d_rope, 2)]
        )
        if leading:
            v = np.concatenate([v[..., :d_rope][..., perm],
                                v[..., d_rope:]], -1)
        else:
            v = np.concatenate([v[..., : d_head - d_rope],
                                v[..., d_head - d_rope:][..., perm]], -1)
        return v.reshape(w.shape)

    def attn_leaves(rng) -> dict:
        out = {}
        if not cfg.norm_after:  # olmo-2 has no input norms at all
            out["attn_norm"] = stack(
                "model.layers.{i}.input_layernorm.weight",
                rng, transpose=False,
            )
        glm4_norms = cfg.post_norms and has(
            "model.layers.{}.post_self_attn_layernorm.weight"
            .format(next(iter(rng)))
        )
        if cfg.norm_after:
            # olmo-2: ONLY output norms exist — post_attention on the
            # attention output, post_feedforward on the MLP output
            out["attn_post_norm"] = stack(
                "model.layers.{i}.post_attention_layernorm.weight",
                rng, transpose=False,
            )
            out["mlp_post_norm"] = stack(
                "model.layers.{i}.post_feedforward_layernorm.weight",
                rng, transpose=False,
            )
        elif glm4_norms:
            # glm-4 sandwich naming: post_self_attn / post_mlp norms,
            # with post_attention_layernorm keeping its llama meaning
            # (the pre-FFN norm)
            out["attn_post_norm"] = stack(
                "model.layers.{i}.post_self_attn_layernorm.weight",
                rng, transpose=False,
            )
            out["mlp_norm"] = stack(
                "model.layers.{i}.post_attention_layernorm.weight",
                rng, transpose=False,
            )
            out["mlp_post_norm"] = stack(
                "model.layers.{i}.post_mlp_layernorm.weight",
                rng, transpose=False,
            )
        elif cfg.post_norms:
            # gemma-2 sandwich norms: post_attention_layernorm is the
            # ATTENTION OUTPUT norm here (not the pre-FFN norm it names
            # in llama-family checkpoints)
            out["attn_post_norm"] = stack(
                "model.layers.{i}.post_attention_layernorm.weight",
                rng, transpose=False,
            )
            out["mlp_norm"] = stack(
                "model.layers.{i}.pre_feedforward_layernorm.weight",
                rng, transpose=False,
            )
            out["mlp_post_norm"] = stack(
                "model.layers.{i}.post_feedforward_layernorm.weight",
                rng, transpose=False,
            )
        else:
            out["mlp_norm"] = stack(
                "model.layers.{i}.post_attention_layernorm.weight",
                rng, transpose=False,
            )
        if cfg.is_mla:
            dqk, dr = cfg.qk_head_dim, cfg.qk_rope_head_dim
            H = cfg.num_heads
            if cfg.q_lora_rank:
                out["wq_a"] = stack(
                    "model.layers.{i}.self_attn.q_a_proj.weight", rng
                )
                out["q_norm"] = stack(
                    "model.layers.{i}.self_attn.q_a_layernorm.weight",
                    rng, transpose=False,
                )
                wq_b = stack("model.layers.{i}.self_attn.q_b_proj.weight", rng)
                out["wq_b"] = np.stack(
                    [deinterleave_rope(w, H, dqk, dr) for w in wq_b]
                )
            else:
                wq = stack("model.layers.{i}.self_attn.q_proj.weight", rng)
                out["wq"] = np.stack(
                    [deinterleave_rope(w, H, dqk, dr) for w in wq]
                )
            wkv_a = stack(
                "model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight", rng
            )
            # rope dims are the trailing d_rope columns (one "head")
            out["wkv_a"] = np.stack(
                [
                    deinterleave_rope(w, 1, cfg.kv_lora_rank + dr, dr)
                    for w in wkv_a
                ]
            )
            out["kv_norm"] = stack(
                "model.layers.{i}.self_attn.kv_a_layernorm.weight",
                rng, transpose=False,
            )
            out["wkv_b"] = stack(
                "model.layers.{i}.self_attn.kv_b_proj.weight", rng
            )
            out["wo"] = stack("model.layers.{i}.self_attn.o_proj.weight", rng)
        elif has(f"model.layers.{next(iter(rng))}.self_attn.qkv_proj.weight"):
            # Phi-3 fuses q/k/v into one projection ([Hq+2Hkv]*D rows,
            # q first) and gate/up likewise — split to our leaves
            qkv = stack("model.layers.{i}.self_attn.qkv_proj.weight", rng)
            dq = cfg.num_heads * cfg.head_dim
            dkv = cfg.num_kv_heads * cfg.head_dim
            out["wq"] = qkv[..., :dq]
            out["wk"] = qkv[..., dq : dq + dkv]
            out["wv"] = qkv[..., dq + dkv :]
            out["wo"] = stack("model.layers.{i}.self_attn.o_proj.weight", rng)
        else:
            out["wq"] = stack("model.layers.{i}.self_attn.q_proj.weight", rng)
            out["wk"] = stack("model.layers.{i}.self_attn.k_proj.weight", rng)
            out["wv"] = stack("model.layers.{i}.self_attn.v_proj.weight", rng)
            out["wo"] = stack("model.layers.{i}.self_attn.o_proj.weight", rng)
            if cfg.attention_bias:
                out["bq"] = stack("model.layers.{i}.self_attn.q_proj.bias",
                                  rng, transpose=False)
                out["bk"] = stack("model.layers.{i}.self_attn.k_proj.bias",
                                  rng, transpose=False)
                out["bv"] = stack("model.layers.{i}.self_attn.v_proj.bias",
                                  rng, transpose=False)
            if cfg.rope_interleave:
                # GLM: the LEADING partial-rotary dims of every head are
                # stored as GPT-J pairs; permuting q AND k the same way
                # leaves attention scores identical while the runtime
                # keeps the fast half-split rotation
                rot = cfg.rope_partial_dim or cfg.head_dim
                for key, n_head in (("wq", cfg.num_heads),
                                    ("wk", cfg.num_kv_heads),
                                    ("bq", cfg.num_heads),
                                    ("bk", cfg.num_kv_heads)):
                    if key in out:
                        out[key] = deinterleave_rope(
                            out[key], n_head, cfg.head_dim, rot,
                            leading=True,
                        )
            if cfg.qk_norm:  # qwen3 per-head q/k norms, weight [head_dim]
                out["q_norm"] = stack(
                    "model.layers.{i}.self_attn.q_norm.weight", rng,
                    transpose=False,
                )
                out["k_norm"] = stack(
                    "model.layers.{i}.self_attn.k_norm.weight", rng,
                    transpose=False,
                )
            if cfg.o_bias:
                out["bo"] = stack("model.layers.{i}.self_attn.o_proj.bias",
                                  rng, transpose=False)
            if cfg.attn_sinks:
                out["sinks"] = stack(
                    "model.layers.{i}.self_attn.sinks", rng, transpose=False
                ).astype(np.float32)
        return out

    def dense_ffn_leaves(rng) -> dict:
        if has(f"model.layers.{next(iter(rng))}.mlp.gate_up_proj.weight"):
            # Phi-3 fused gate_up ([2F, E] rows: gate then up)
            gu = stack("model.layers.{i}.mlp.gate_up_proj.weight", rng)
            F2 = gu.shape[-1] // 2
            return {
                "w_gate": gu[..., :F2],
                "w_up": gu[..., F2:],
                "w_down": stack("model.layers.{i}.mlp.down_proj.weight", rng),
            }
        return {
            "w_gate": stack("model.layers.{i}.mlp.gate_proj.weight", rng),
            "w_up": stack("model.layers.{i}.mlp.up_proj.weight", rng),
            "w_down": stack("model.layers.{i}.mlp.down_proj.weight", rng),
        }

    def gptoss_moe_leaves(rng) -> dict:
        """gpt-oss expert tensors are FUSED per layer (not per expert):
        gate_up_proj [X, E, 2F] with gate/up INTERLEAVED on the last
        axis (gate = [..., ::2], up = [..., 1::2]) plus bias [X, 2F];
        down_proj [X, F, E] (+bias [X, E]) is already in our we_down
        orientation; the router is mlp.router with a LOGIT bias."""
        gu = np.stack(
            [get(f"model.layers.{i}.mlp.experts.gate_up_proj") for i in rng]
        )  # [L, X, E, 2F]
        gub = np.stack(
            [get(f"model.layers.{i}.mlp.experts.gate_up_proj_bias")
             for i in rng]
        )  # [L, X, 2F]
        return {
            "moe_gate": stack("model.layers.{i}.mlp.router.weight", rng),
            "moe_router_bias": stack(
                "model.layers.{i}.mlp.router.bias", rng, transpose=False
            ).astype(np.float32),
            "we_gate": gu[..., ::2],
            "we_up": gu[..., 1::2],
            "be_gate": gub[..., ::2],
            "be_up": gub[..., 1::2],
            "we_down": np.stack(
                [get(f"model.layers.{i}.mlp.experts.down_proj") for i in rng]
            ),
            "be_down": np.stack(
                [get(f"model.layers.{i}.mlp.experts.down_proj_bias")
                 for i in rng]
            ),
        }

    def moe_ffn_leaves(rng) -> dict:
        if cfg.moe_act == "gptoss_clamp":
            return gptoss_moe_leaves(rng)
        X = cfg.num_experts

        def stack_experts(mix_fmt: str, ds_fmt: str) -> np.ndarray:
            """[L, X, in, out] from per-expert tensors; supports Mixtral
            (block_sparse_moe.experts.N.w1/w3/w2) and DeepSeek/Qwen-MoE
            (mlp.experts.N.gate/up/down_proj) naming."""
            out = []
            for i in rng:
                fmt = mix_fmt if has(mix_fmt.format(i=i, x=0)) else ds_fmt
                out.append(
                    np.stack([get(fmt.format(i=i, x=x)).T for x in range(X)])
                )
            return np.stack(out)

        gate_mix = "model.layers.{i}.block_sparse_moe.gate.weight"
        gate_ds = "model.layers.{i}.mlp.gate.weight"
        out = {
            "moe_gate": np.stack(
                [
                    get((gate_mix if has(gate_mix.format(i=i))
                         else gate_ds).format(i=i)).T
                    for i in rng
                ]
            ),
            "we_gate": stack_experts(
                "model.layers.{i}.block_sparse_moe.experts.{x}.w1.weight",
                "model.layers.{i}.mlp.experts.{x}.gate_proj.weight",
            ),
            "we_up": stack_experts(
                "model.layers.{i}.block_sparse_moe.experts.{x}.w3.weight",
                "model.layers.{i}.mlp.experts.{x}.up_proj.weight",
            ),
            "we_down": stack_experts(
                "model.layers.{i}.block_sparse_moe.experts.{x}.w2.weight",
                "model.layers.{i}.mlp.experts.{x}.down_proj.weight",
            ),
        }
        if cfg.moe_gate_bias:
            out["moe_gate_bias"] = stack(
                "model.layers.{i}.mlp.gate.e_score_correction_bias",
                rng, transpose=False,
            ).astype(np.float32)
        if cfg.num_shared_experts:
            # DeepSeek writes plural "shared_experts", Qwen2-MoE writes
            # singular "shared_expert" — same tensors either way
            plural = "model.layers.{i}.mlp.shared_experts.gate_proj.weight"
            base = (
                "model.layers.{i}.mlp.shared_experts"
                if has(plural.format(i=next(iter(rng))))
                else "model.layers.{i}.mlp.shared_expert"
            )
            out["shared_gate"] = stack(base + ".gate_proj.weight", rng)
            out["shared_up"] = stack(base + ".up_proj.weight", rng)
            out["shared_down"] = stack(base + ".down_proj.weight", rng)
            if cfg.shared_expert_gate:  # qwen2moe: [1, E] -> [E, 1]
                out["shared_egate"] = stack(
                    "model.layers.{i}.mlp.shared_expert_gate.weight", rng
                )
        return out

    def lfm2_layer_groups() -> dict:
        """LFM2's names onto leaves stacked by KIND (llama._layers). The
        dense model's (``conv.{in_proj,conv,out_proj}``,
        ``self_attn.{q,k,v,out}_proj`` and ``{q,k}_layernorm``,
        ``operator_norm``, ``ffn_norm``, ``feed_forward.{w1,w2,w3}``) are
        transformers' ``Lfm2ForCausalLM``; the expert layers'
        (``feed_forward.gate``, ``.expert_bias``, ``.experts.N.{w1,w2,w3}``)
        are an offline reading of the published lfm2_moe modelling code."""
        lay = "model.layers.{i}."
        conv = [l for l, op in enumerate(cfg.layer_ops) if op == "conv"]
        attn = [l for l, op in enumerate(cfg.layer_ops) if op == "attn"]
        out = {
            "conv_ops": {
                "attn_norm": stack(lay + "operator_norm.weight", conv, False),
                "conv_in": stack(lay + "conv.in_proj.weight", conv),
                # Conv1d's [E, 1, K] -> taps [K, E], the last on the row
                "conv_w": np.stack(
                    [get(f"model.layers.{i}.conv.conv.weight")[:, 0, :].T
                     for i in conv]),
                "conv_out": stack(lay + "conv.out_proj.weight", conv),
            },
            "attn_ops": {
                "attn_norm": stack(lay + "operator_norm.weight", attn, False),
                "wq": stack(lay + "self_attn.q_proj.weight", attn),
                "wk": stack(lay + "self_attn.k_proj.weight", attn),
                "wv": stack(lay + "self_attn.v_proj.weight", attn),
                "wo": stack(lay + "self_attn.out_proj.weight", attn),
                "q_norm": stack(lay + "self_attn.q_layernorm.weight", attn,
                                False),
                "k_norm": stack(lay + "self_attn.k_layernorm.weight", attn,
                                False),
            },
        }

        def dense_ffn(rng) -> dict:
            return {
                "mlp_norm": stack(lay + "ffn_norm.weight", rng, False),
                "w_gate": stack(lay + "feed_forward.w1.weight", rng),
                "w_up": stack(lay + "feed_forward.w3.weight", rng),
                "w_down": stack(lay + "feed_forward.w2.weight", rng),
            }

        kd = cfg.first_dense_layers if cfg.is_moe else 0
        if kd:
            out["dense_layers"] = dense_ffn(range(kd))
        rest = range(kd, L)
        if not cfg.is_moe:
            out["layers"] = dense_ffn(rest)
            return out

        def experts(w: str) -> np.ndarray:
            return np.stack([np.stack([
                get(f"model.layers.{i}.feed_forward.experts.{x}.{w}.weight").T
                for x in range(cfg.num_experts)]) for i in rest])

        out["layers"] = {
            "mlp_norm": stack(lay + "ffn_norm.weight", rest, False),
            "moe_gate": stack(lay + "feed_forward.gate.weight", rest),
            "we_gate": experts("w1"),
            "we_up": experts("w3"),
            "we_down": experts("w2"),
        }
        return out  # (+ the float32 expert_bias, after the cast below)

    kd = cfg.first_dense_layers if cfg.is_moe else 0
    if cfg.layer_ops:
        params: dict = {
            "embed": get("model.embed_tokens.weight"),
            "final_norm": get("model.embedding_norm.weight"),
            **lfm2_layer_groups(),
        }
        layers = {}  # no group takes gemma's norm fold below
    else:
        layers: dict = attn_leaves(range(kd, L))
        layers.update(
            moe_ffn_leaves(range(kd, L)) if cfg.is_moe
            else dense_ffn_leaves(range(kd, L))
        )
        params = {
            "embed": get("model.embed_tokens.weight"),
            "final_norm": get("model.norm.weight"),
            "layers": layers,
        }
    if kd and not cfg.layer_ops:
        dense = attn_leaves(range(0, kd))
        dense.update(dense_ffn_leaves(range(0, kd)))
        params["dense_layers"] = dense
    if not cfg.tie_word_embeddings:
        params["lm_head"] = get("lm_head.weight").T

    if cfg.rms_add_unit:
        # gemma checkpoints store norm weights as offsets (the model
        # scales by 1 + w); folding the +1 here keeps every runtime
        # rms_norm call family-agnostic (incl. gemma-3's per-head q/k
        # norms, which share the convention)
        for key in ("attn_norm", "mlp_norm", "attn_post_norm",
                    "mlp_post_norm", "q_norm", "k_norm"):
            if key in layers:
                layers[key] = layers[key] + 1.0
        params["final_norm"] = params["final_norm"] + 1.0

    # cast + (optionally) place on mesh shard-by-shard
    if mesh is not None:
        from ..parallel.mesh import shard_params

        params = jax.tree.map(lambda x: jnp.asarray(x, dt), params)
        params = shard_params(params, mesh)
    else:
        params = jax.tree.map(lambda x: jnp.asarray(x, dt), params)
    if cfg.layer_ops and cfg.moe_gate_bias:
        # the selection bias stays float32, as published and as drawn
        params["layers"]["moe_gate_bias"] = jnp.asarray(
            stack("model.layers.{i}.feed_forward.expert_bias",
                  range(kd, L), False), jnp.float32)
    for h in handles.values():
        del h
    return params


def save_llama_params(path: str, params: dict, cfg=None) -> None:
    """Write params back out as a single safetensors file (testing and
    fixture generation)."""
    from safetensors.numpy import save_file

    if cfg is not None and getattr(cfg, "rope_interleave", False):
        raise NotImplementedError(
            "saving back to the interleaved-rope checkpoint convention "
            "is not supported (the loader de-interleaved at load)"
        )
    flat: dict[str, np.ndarray] = {}
    flat["model.embed_tokens.weight"] = np.asarray(params["embed"], np.float32)
    final_norm = params["final_norm"]
    if cfg is not None and getattr(cfg, "rms_add_unit", False):
        # inverse of the load-time (1 + w) fold: gemma checkpoints store
        # norm OFFSETS
        final_norm = final_norm - 1.0
    flat["model.norm.weight"] = np.asarray(final_norm, np.float32)
    names = {
        "attn_norm": ("model.layers.{i}.input_layernorm.weight", False),
        "wq": ("model.layers.{i}.self_attn.q_proj.weight", True),
        "wk": ("model.layers.{i}.self_attn.k_proj.weight", True),
        "wv": ("model.layers.{i}.self_attn.v_proj.weight", True),
        "wo": ("model.layers.{i}.self_attn.o_proj.weight", True),
        "mlp_norm": ("model.layers.{i}.post_attention_layernorm.weight", False),
        "w_gate": ("model.layers.{i}.mlp.gate_proj.weight", True),
        "w_up": ("model.layers.{i}.mlp.up_proj.weight", True),
        "w_down": ("model.layers.{i}.mlp.down_proj.weight", True),
        # MLA (models/mla.py)
        "wq_a": ("model.layers.{i}.self_attn.q_a_proj.weight", True),
        "q_norm": ("model.layers.{i}.self_attn.q_a_layernorm.weight", False),
        "wq_b": ("model.layers.{i}.self_attn.q_b_proj.weight", True),
        "wkv_a": ("model.layers.{i}.self_attn.kv_a_proj_with_mqa.weight", True),
        "kv_norm": ("model.layers.{i}.self_attn.kv_a_layernorm.weight", False),
        "wkv_b": ("model.layers.{i}.self_attn.kv_b_proj.weight", True),
        "moe_gate_bias": (
            "model.layers.{i}.mlp.gate.e_score_correction_bias", False
        ),
        "k_norm": ("model.layers.{i}.self_attn.k_norm.weight", False),
    }
    if cfg is not None and getattr(cfg, "qk_norm", False):
        # "q_norm" is shared between two checkpoint conventions: the MLA
        # q_a_layernorm (default above) and qwen3's per-head q_norm
        names["q_norm"] = (
            "model.layers.{i}.self_attn.q_norm.weight", False
        )
    if cfg is not None and getattr(cfg, "post_norms", False):
        # gemma-2 sandwich norms: post_attention_layernorm is the attn
        # OUTPUT norm; the pre-FFN norm gets its own name
        names["mlp_norm"] = (
            "model.layers.{i}.pre_feedforward_layernorm.weight", False
        )
        names["attn_post_norm"] = (
            "model.layers.{i}.post_attention_layernorm.weight", False
        )
        names["mlp_post_norm"] = (
            "model.layers.{i}.post_feedforward_layernorm.weight", False
        )

    def save_group(lay: dict, n: int, off: int) -> None:
        lay = dict(lay)
        if cfg is not None and getattr(cfg, "rms_add_unit", False):
            for key in ("attn_norm", "mlp_norm", "attn_post_norm",
                        "mlp_post_norm"):
                if key in lay:
                    lay[key] = lay[key] - 1.0
        for key, (fmt, transpose) in names.items():
            if key not in lay:
                continue
            for li in range(n):
                t = np.asarray(lay[key][li], np.float32)
                flat[fmt.format(i=off + li)] = t.T.copy() if transpose else t
        if "we_gate" in lay:  # MoE: Mixtral naming (shared: DeepSeek's)
            X = lay["we_gate"].shape[1]
            expert_names = {
                "we_gate": "model.layers.{i}.block_sparse_moe.experts.{x}.w1.weight",
                "we_up": "model.layers.{i}.block_sparse_moe.experts.{x}.w3.weight",
                "we_down": "model.layers.{i}.block_sparse_moe.experts.{x}.w2.weight",
            }
            # plural = DeepSeek convention; singular + gate = Qwen2-MoE
            sbase = (
                "model.layers.{i}.mlp.shared_expert"
                if "shared_egate" in lay
                else "model.layers.{i}.mlp.shared_experts"
            )
            shared_names = {
                "shared_gate": sbase + ".gate_proj.weight",
                "shared_up": sbase + ".up_proj.weight",
                "shared_down": sbase + ".down_proj.weight",
                "shared_egate":
                    "model.layers.{i}.mlp.shared_expert_gate.weight",
            }
            for li in range(n):
                i = off + li
                flat[f"model.layers.{i}.block_sparse_moe.gate.weight"] = (
                    np.asarray(lay["moe_gate"][li], np.float32).T.copy()
                )
                for key, fmt in expert_names.items():
                    for x in range(X):
                        flat[fmt.format(i=i, x=x)] = np.asarray(
                            lay[key][li, x], np.float32
                        ).T.copy()
                for key, fmt in shared_names.items():
                    if key in lay:
                        flat[fmt.format(i=i)] = np.asarray(
                            lay[key][li], np.float32
                        ).T.copy()

    def n_layers(group: dict) -> int:
        # attn_norm is absent for norm-after (olmo-2) params — count
        # from any leaf (all are layer-stacked on axis 0)
        return next(iter(group.values())).shape[0]

    kd = 0
    if "dense_layers" in params:
        kd = n_layers(params["dense_layers"])
        save_group(params["dense_layers"], kd, 0)
    save_group(params["layers"], n_layers(params["layers"]), kd)
    if "lm_head" in params:
        flat["lm_head.weight"] = np.asarray(params["lm_head"], np.float32).T.copy()
    save_file(flat, os.path.join(path, "model.safetensors"))
