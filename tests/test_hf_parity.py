"""Numerical parity vs HuggingFace transformers (torch CPU).

The strongest correctness check for the model zoo: build a tiny random HF
checkpoint per family (llama / qwen2 / mistral / mixtral), load it with our
safetensors loader, and compare full-vocab logits of the JAX forward pass
against the torch reference. Catches weight-transpose, RoPE, GQA, bias and
router bugs that internal-consistency tests cannot see.

(ref parity point: the reference delegates correctness to vLLM et al.; the
TPU build owns the models, so it owns this proof too.)
"""

import json

import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
import transformers  # noqa: E402

from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import ModelConfig  # noqa: E402
from dynamo_tpu.models.weights import load_llama_params  # noqa: E402

TINY = dict(
    vocab_size=256,
    hidden_size=64,
    intermediate_size=112,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_position_embeddings=128,
    torch_dtype="float32",
)


def _save(tmp_path, model):
    model = model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)
    # make the declared dtype explicit for our loader (older transformers
    # versions omit torch_dtype from the saved config)
    cfg_path = tmp_path / "config.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["torch_dtype"] = "float32"
    cfg_path.write_text(json.dumps(cfg))
    return str(tmp_path)


def _compare(path, tokens, hf_model, atol=2e-4):
    cfg = ModelConfig.from_local_path(path)
    assert cfg.dtype == "float32"
    params = load_llama_params(path, cfg)
    ours = np.asarray(llama.dense_forward(params, cfg, jnp.asarray(tokens)))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(tokens)[None]).logits[0].numpy()
    np.testing.assert_allclose(ours, theirs, atol=atol, rtol=2e-3)


TOKENS = [3, 17, 92, 45, 200, 7, 7, 133]


def test_llama_parity(tmp_path):
    hf_cfg = transformers.LlamaConfig(**TINY, rope_theta=10000.0)
    model = transformers.LlamaForCausalLM(hf_cfg)
    _compare(_save(tmp_path, model), TOKENS, model)


def test_qwen2_parity(tmp_path):
    # qwen2: qkv bias baked into the architecture (no config field) —
    # randomize the zero-initialized biases so the check isn't vacuous
    hf_cfg = transformers.Qwen2Config(**TINY)
    model = transformers.Qwen2ForCausalLM(hf_cfg)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("bias"):
                p.normal_(0.0, 0.1)
    path = _save(tmp_path, model)
    assert ModelConfig.from_local_path(path).attention_bias
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Qwen3Config"),
    reason="transformers too old for Qwen3",
)
def test_qwen3_parity(tmp_path):
    # qwen3: per-head RMS norm on q/k before rope, no qkv bias
    hf_cfg = transformers.Qwen3Config(**TINY, head_dim=16)
    model = transformers.Qwen3ForCausalLM(hf_cfg)
    with torch.no_grad():  # ones-init norms would make the check vacuous
        for name, p in model.named_parameters():
            if "q_norm" in name or "k_norm" in name:
                p.normal_(1.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.qk_norm and not cfg.attention_bias
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Qwen3MoeConfig"),
    reason="transformers too old for Qwen3-MoE",
)
def test_qwen3_moe_parity(tmp_path):
    hf_cfg = transformers.Qwen3MoeConfig(
        **TINY, head_dim=16, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=48, norm_topk_prob=True,
    )
    model = transformers.Qwen3MoeForCausalLM(hf_cfg)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if "q_norm" in name or "k_norm" in name:
                p.normal_(1.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.qk_norm and cfg.num_experts == 4
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Qwen2MoeConfig"),
    reason="transformers too old for Qwen2-MoE",
)
def test_qwen2_moe_parity(tmp_path):
    """Qwen2-MoE (Qwen1.5-MoE-A2.7B / Qwen2-57B-A14B architecture): one
    GATED shared expert of its own width riding beside top-k routing
    (sigmoid(x @ shared_expert_gate) scales the shared contribution),
    qkv bias, norm_topk_prob=False."""
    hf_cfg = transformers.Qwen2MoeConfig(
        **TINY, num_experts=4, num_experts_per_tok=2,
        moe_intermediate_size=48, shared_expert_intermediate_size=96,
    )
    model = transformers.Qwen2MoeForCausalLM(hf_cfg)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.num_experts == 4 and cfg.shared_expert_gate
    assert cfg.shared_expert_size == 96 and not cfg.norm_topk_prob
    assert cfg.attention_bias  # qwen2 family qkv bias
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "GptOssConfig"),
    reason="transformers too old for GPT-OSS",
)
def test_gptoss_parity(tmp_path):
    """gpt-oss: alternating sliding/full layers, per-head attention
    sinks, biased router with topk-then-softmax, fused clamped-SwiGLU
    experts with biases, biased attention projections, YaRN rope with
    truncate=False."""
    hf_cfg = transformers.GptOssConfig(
        vocab_size=256, hidden_size=64, intermediate_size=48,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_local_experts=4, num_experts_per_tok=2,
        sliding_window=8, max_position_embeddings=128,
        layer_types=["sliding_attention", "full_attention"] * 2,
        rope_scaling={
            "rope_type": "yarn", "factor": 4.0, "beta_fast": 32.0,
            "beta_slow": 1.0, "truncate": False,
            "original_max_position_embeddings": 32,
        },
    )
    model = transformers.GptOssForCausalLM(hf_cfg)
    with torch.no_grad():  # randomize empty-init sink/bias params
        for name, p in model.named_parameters():
            if "sinks" in name or "bias" in name:
                p.normal_(0.0, 0.5)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.attn_sinks and cfg.moe_act == "gptoss_clamp"
    assert cfg.layer_windows == (8, 0, 8, 0) and cfg.sliding_window == 0
    assert cfg.o_bias and cfg.attention_bias
    # prompt longer than the window so sliding layers actually mask
    toks = [(7 * i + 3) % 256 for i in range(24)]
    _compare(path, toks, model, atol=5e-4)


@pytest.mark.skipif(
    not hasattr(transformers, "Phi3Config"),
    reason="transformers too old for Phi-3",
)
def test_phi3_parity(tmp_path):
    """Phi-3: FUSED qkv_proj / gate_up_proj (the loader splits them)."""
    hf_cfg = transformers.Phi3Config(**TINY, pad_token_id=0)
    model = transformers.Phi3ForCausalLM(hf_cfg)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert not cfg.attention_bias
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Phi3Config"),
    reason="transformers too old for Phi-3",
)
def test_phi3_partial_rotary_parity(tmp_path):
    """Partial rotary (the Phi-4-mini convention): only the first
    head_dim * partial_rotary_factor dims of each head rotate; the rest
    pass through."""
    import inspect

    if "partial_rotary_factor" not in inspect.signature(
        transformers.Phi3Config.__init__
    ).parameters:
        pytest.skip("installed transformers predates Phi-3 partial rotary")
    hf_cfg = transformers.Phi3Config(
        **TINY, pad_token_id=0, partial_rotary_factor=0.5,
    )
    model = transformers.Phi3ForCausalLM(hf_cfg)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.rope_partial_dim == 8  # head_dim 16 * 0.5
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Phi3Config"),
    reason="transformers too old for Phi-3",
)
def test_phi3_longrope_parity(tmp_path):
    """Phi-3 LongRoPE. Factor sets are selected PER POSITION at the
    original-context boundary (vLLM's serving semantics — HF instead
    re-ropes the whole sequence when its length crosses the boundary,
    which an incremental KV cache cannot replay), so:

      * prompts inside the original context match HF EXACTLY (both use
        the short set + the sqrt-log attention factor);
      * past the boundary, each position's frequencies must equal the
        matching HF regime's values (short below, long above).
    """
    import math

    D2 = 16 // 2  # head_dim 16 -> 8 freq dims
    short = [1.0 + 0.05 * i for i in range(D2)]
    long = [1.5 + 0.25 * i for i in range(D2)]
    hf_cfg = transformers.Phi3Config(
        **{**TINY, "max_position_embeddings": 256},
        pad_token_id=0,
        original_max_position_embeddings=64,
        rope_scaling={
            "type": "longrope", "short_factor": short, "long_factor": long,
        },
    )
    model = transformers.Phi3ForCausalLM(hf_cfg)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert (cfg.rope_scaling or {}).get("type") == "longrope"
    # short regime end-to-end: exact HF parity (attention factor incl.)
    toks = [(t * 7) % 256 for t in range(50)]
    _compare(path, toks, model)

    # per-position frequency selection across the boundary
    from dynamo_tpu.models.llama import (
        _rope_attention_scaling, _rope_freqs, apply_rope,
    )

    inv = _rope_freqs(cfg)
    msc = _rope_attention_scaling(cfg)
    assert msc == pytest.approx(math.sqrt(1 + math.log(4) / math.log(64)))
    base = 1.0 / (10000.0 ** (np.arange(0, 16, 2) / 16))
    # x1 = ones, x2 = zeros: rotated halves are exactly cos/sin * msc
    x = jnp.zeros((2, 1, 16)).at[..., :8].set(1.0)
    pos = jnp.asarray([63, 64])  # last-short, first-long
    out = np.asarray(apply_rope(x, pos, inv, msc))
    for row, p, factors in ((0, 63, short), (1, 64, long)):
        angles = p * (base / np.asarray(factors))
        np.testing.assert_allclose(
            out[row, 0, :8], np.cos(angles) * msc, rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            out[row, 0, 8:], np.sin(angles) * msc, rtol=1e-5, atol=1e-6
        )


@pytest.mark.skipif(
    not hasattr(transformers, "Gemma2Config"),
    reason="transformers too old for Gemma-2",
)
def test_gemma2_parity(tmp_path):
    """Gemma-2: sandwich (post-attention/post-FFN) norms, attention and
    final logit soft-capping, query_pre_attn_scalar scale, alternating
    sliding/full layers, (1+w) norms, scaled embeddings, GeGLU."""
    hf_cfg = transformers.Gemma2Config(
        **{**TINY, "num_hidden_layers": 4},
        head_dim=16, pad_token_id=0,
        query_pre_attn_scalar=32,
        sliding_window=5,
        attn_logit_softcapping=50.0,
        final_logit_softcapping=30.0,
    )
    model = transformers.Gemma2ForCausalLM(hf_cfg)
    with torch.no_grad():  # non-trivial norms so the sandwich order shows
        for name, p in model.named_parameters():
            if "norm" in name:
                p.normal_(0.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.post_norms and cfg.attn_softcap == 50.0
    assert cfg.final_softcap == 30.0 and cfg.attn_scale_base == 32
    assert cfg.layer_windows and cfg.layer_windows[0] == 5
    assert cfg.rms_add_unit and cfg.scale_embed
    # 12 tokens: window 5 binds on the sliding layers
    toks = [(t * 11) % 256 for t in range(12)]
    _compare(path, toks, model, atol=5e-4)


@pytest.mark.skipif(
    not hasattr(transformers, "Gemma3TextConfig"),
    reason="transformers too old for Gemma-3",
)
def test_gemma3_parity(tmp_path):
    """Gemma-3 (text): per-layer ROPE — sliding layers rotate at the
    LOCAL base frequency, full layers at rope_theta with linear
    scaling — plus per-head (1+w) q/k norms, sandwich norms, 5:1
    sliding pattern, query_pre_attn_scalar scale, no softcaps."""
    hf_cfg = transformers.Gemma3TextConfig(
        **{**TINY, "num_hidden_layers": 6}, head_dim=16, pad_token_id=0,
        query_pre_attn_scalar=32, sliding_window=5,
        rope_theta=1000000.0, rope_local_base_freq=10000.0,
        rope_scaling={"rope_type": "linear", "factor": 8.0},
    )
    model = transformers.Gemma3ForCausalLM(hf_cfg)
    with torch.no_grad():  # non-trivial norms (zero-offset init)
        for name, p in model.named_parameters():
            if "norm" in name:
                p.normal_(0.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.post_norms and cfg.qk_norm and cfg.rms_add_unit
    assert cfg.rope_local_theta == 10000.0
    assert cfg.layer_windows == (5, 5, 5, 5, 5, 0)
    assert (cfg.rope_scaling or {}).get("factor") == 8.0
    toks = [(t * 11) % 256 for t in range(12)]
    _compare(path, toks, model, atol=5e-4)


@pytest.mark.skipif(
    not hasattr(transformers, "Gemma3TextConfig"),
    reason="transformers too old for Gemma-3",
)
def test_gemma3_paged_engine_matches_dense():
    """Paged serving (chunked prefill + decode with per-layer rope and
    windows) reproduces the dense gemma-3-shaped forward."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import Context, collect

    cfg = ModelConfig.tiny(
        num_layers=6, layer_windows=(6, 6, 6, 6, 6, 0),
        post_norms=True, qk_norm=True, attn_scale_base=32,
        rms_add_unit=True, scale_embed=True, tie_word_embeddings=True,
        hidden_act="gelu_tanh", rope_theta=1000000.0,
        rope_local_theta=10000.0, dtype="float32",
    )
    params = llama.init_params(cfg, __import__("jax").random.key(6))
    prompt = [(17 * i + 3) % cfg.vocab_size for i in range(18)]
    cur = list(prompt)
    for _ in range(6):
        lg = llama.dense_forward(params, cfg, jnp.asarray(cur))
        cur.append(int(np.argmax(np.asarray(lg[-1]))))
    want = cur[len(prompt):]

    import asyncio

    async def main():
        engine = JaxEngine(
            EngineConfig(model=cfg, num_blocks=32, block_size=4,
                         max_batch_size=2, max_context=64, prefill_chunk=8),
            params=params,
        )
        out = await collect(engine.generate(Context(PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[],
        ))))
        toks = [t for o in out for t in o.token_ids]
        assert toks == want, (toks, want)
        await engine.close()

    asyncio.run(main())


@pytest.mark.skipif(
    not hasattr(transformers, "Gemma3TextConfig"),
    reason="transformers too old for Gemma-3",
)
def test_gemma3_multimodal_checkpoint_text_serving(tmp_path):
    """Gemma-3 MULTIMODAL checkpoints: config nests under text_config
    and the text weights carry the language_model.model.* prefix — the
    loader resolves both, lm_head/top-level names included."""
    import os

    from safetensors.numpy import load_file, save_file

    hf_cfg = transformers.Gemma3TextConfig(
        **{**TINY, "num_hidden_layers": 2}, head_dim=16, pad_token_id=0,
        query_pre_attn_scalar=32, sliding_window=5,
        layer_types=["sliding_attention", "full_attention"],
        rope_local_base_freq=10000.0, rope_theta=1000000.0,
    )
    model = transformers.Gemma3ForCausalLM(hf_cfg)
    path = _save(tmp_path, model)
    # rewrite as a multimodal-shaped checkpoint: prefixed weights +
    # nested text_config
    st = os.path.join(path, "model.safetensors")
    tensors = load_file(st)
    save_file(
        {"language_model." + k: v for k, v in tensors.items()}, st
    )
    text_cfg = json.loads((tmp_path / "config.json").read_text())
    (tmp_path / "config.json").write_text(json.dumps({
        "architectures": ["Gemma3ForConditionalGeneration"],
        "model_type": "gemma3",
        "torch_dtype": "float32",
        "text_config": {k: v for k, v in text_cfg.items()
                        if k not in ("architectures", "torch_dtype")},
        "vision_config": {"model_type": "siglip_vision_model"},
    }))
    cfg = ModelConfig.from_local_path(path)
    assert cfg.post_norms and cfg.rope_local_theta == 10000.0
    assert cfg.dtype == "float32"  # top-level torch_dtype carried
    _compare(path, TOKENS, model, atol=5e-4)


@pytest.mark.skipif(
    not hasattr(transformers, "GlmConfig"),
    reason="transformers too old for GLM",
)
def test_glm_parity(tmp_path):
    """GLM (glm-4-9b legacy arch): INTERLEAVED partial rotary on the
    leading head dims (de-interleaved at load — q and k permute
    identically so scores are unchanged), qkv bias, fused gate_up."""
    hf_cfg = transformers.GlmConfig(
        **TINY, head_dim=16, pad_token_id=0,
    )
    model = transformers.GlmForCausalLM(hf_cfg)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("bias"):
                p.normal_(0.0, 0.1)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.rope_interleave and cfg.rope_partial_dim == 8
    assert cfg.attention_bias
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Glm4Config"),
    reason="transformers too old for GLM-4",
)
def test_glm4_parity(tmp_path):
    """GLM-4 (0414): GLM plus EXTRA sandwich norms (post_self_attn /
    post_mlp), with post_attention_layernorm keeping its llama meaning."""
    hf_cfg = transformers.Glm4Config(
        **TINY, head_dim=16, pad_token_id=0,
    )
    model = transformers.Glm4ForCausalLM(hf_cfg)
    with torch.no_grad():
        for name, p in model.named_parameters():
            if name.endswith("bias"):
                p.normal_(0.0, 0.1)
            if "post_self_attn" in name or "post_mlp" in name:
                p.normal_(1.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.post_norms and cfg.rope_interleave
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Olmo2Config"),
    reason="transformers too old for OLMo-2",
)
def test_olmo2_parity(tmp_path):
    """OLMo-2: norm-AFTER architecture — no input/pre-FFN norms,
    post_attention/post_feedforward norms on the sublayer OUTPUTS —
    plus q/k RMS norms over the FULL projection width (pre-reshape)."""
    hf_cfg = transformers.Olmo2Config(**TINY, pad_token_id=0)
    model = transformers.Olmo2ForCausalLM(hf_cfg)
    with torch.no_grad():  # non-trivial norms so ordering shows
        for name, p in model.named_parameters():
            if "norm" in name:
                p.normal_(1.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.norm_after and cfg.post_norms and cfg.qk_norm_full
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "OlmoeConfig"),
    reason="transformers too old for OLMoE",
)
def test_olmoe_parity(tmp_path):
    """OLMoE: PRE-norm layers with olmo-2's full-width q/k RMS norms;
    64 experts, top-8, the router's softmax weights used without
    renormalisation; intermediate_size is one expert's width."""
    hf_cfg = transformers.OlmoeConfig(
        **{**TINY, "intermediate_size": 32, "num_key_value_heads": 4},
        num_experts=64, num_experts_per_tok=8, norm_topk_prob=False,
        pad_token_id=0,
    )
    model = transformers.OlmoeForCausalLM(hf_cfg)
    with torch.no_grad():  # non-trivial norms so ordering shows
        for name, p in model.named_parameters():
            if "norm" in name:
                p.normal_(1.0, 0.3)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.qk_norm_full and not cfg.norm_after and not cfg.post_norms
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    assert cfg.moe_intermediate_size == 32 and not cfg.norm_topk_prob
    _compare(path, TOKENS, model)


def test_mistral_parity(tmp_path):
    hf_cfg = transformers.MistralConfig(**TINY, sliding_window=None)
    model = transformers.MistralForCausalLM(hf_cfg)
    _compare(_save(tmp_path, model), TOKENS, model)


def test_mixtral_parity(tmp_path):
    hf_cfg = transformers.MixtralConfig(
        **TINY, num_local_experts=4, num_experts_per_tok=2
    )
    model = transformers.MixtralForCausalLM(hf_cfg)
    _compare(_save(tmp_path, model), TOKENS, model)


def test_tied_embeddings_parity(tmp_path):
    cfg_kwargs = dict(TINY, tie_word_embeddings=True)
    hf_cfg = transformers.LlamaConfig(**cfg_kwargs)
    model = transformers.LlamaForCausalLM(hf_cfg)
    _compare(_save(tmp_path, model), TOKENS, model)


def test_llama31_rope_scaling_parity(tmp_path):
    hf_cfg = transformers.LlamaConfig(
        **TINY,
        rope_theta=500000.0,
        rope_scaling={
            "rope_type": "llama3",
            "factor": 8.0,
            "low_freq_factor": 1.0,
            "high_freq_factor": 4.0,
            "original_max_position_embeddings": 64,
        },
    )
    model = transformers.LlamaForCausalLM(hf_cfg)
    _compare(_save(tmp_path, model), TOKENS, model)


def test_deepseek_v2_mla_parity(tmp_path):
    """MLA with q_lora + kv_lora compressed cache, shared experts, and
    first_k_dense_replace=1 (heterogeneous dense->MoE stack) — the
    DeepSeek-V2 shape (BASELINE config 5 family)."""
    from transformers.models.deepseek_v2 import (
        DeepseekV2Config,
        DeepseekV2ForCausalLM,
    )

    hf_cfg = DeepseekV2Config(
        vocab_size=256, hidden_size=64, intermediate_size=112,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, torch_dtype="float32",
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=4, num_experts_per_tok=2, moe_intermediate_size=48,
        n_shared_experts=1, first_k_dense_replace=1, moe_layer_freq=1,
        routed_scaling_factor=1.0, scoring_func="softmax",
        norm_topk_prob=False, topk_method="greedy",
        n_group=1, topk_group=1, rope_theta=10000.0,
    )
    model = DeepseekV2ForCausalLM(hf_cfg)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.is_mla and cfg.first_dense_layers == 1
    _compare(path, TOKENS, model)


def test_deepseek_v3_mla_parity(tmp_path):
    """V3/R1 routing: sigmoid scoring + no-aux gate bias + group-limited
    top-k + routed_scaling_factor, on the MLA attention stack."""
    from transformers.models.deepseek_v3 import (
        DeepseekV3Config,
        DeepseekV3ForCausalLM,
    )

    hf_cfg = DeepseekV3Config(
        vocab_size=256, hidden_size=64, intermediate_size=112,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=128, torch_dtype="float32",
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=48,
        n_shared_experts=1, first_k_dense_replace=1, moe_layer_freq=1,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        norm_topk_prob=True, topk_method="noaux_tc",
        n_group=2, topk_group=1, rope_theta=10000.0,
    )
    model = DeepseekV3ForCausalLM(hf_cfg)
    with torch.no_grad():  # non-zero gate bias so the check isn't vacuous
        for name, p in model.named_parameters():
            if name.endswith("e_score_correction_bias"):
                p.normal_(0.0, 0.5)
    path = _save(tmp_path, model)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.is_mla and cfg.moe_scoring == "sigmoid" and cfg.moe_gate_bias
    _compare(path, TOKENS, model)


def test_gptoss_paged_engine_matches_dense():
    """The paged serving path (chunked prefill + decode with per-layer
    windows and sinks) must reproduce the dense gpt-oss-shaped forward
    token-for-token through the engine — with chunks crossing window
    boundaries."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import Context, collect

    cfg = ModelConfig.tiny(
        num_layers=4, layer_windows=(6, 0, 6, 0),  # global width stays 0
        attn_sinks=True, o_bias=True, attention_bias=True,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        moe_act="gptoss_clamp", dtype="float32",
    )
    params = llama.init_params(cfg, __import__("jax").random.key(2))
    prompt = [(11 * i + 5) % cfg.vocab_size for i in range(18)]
    cur = list(prompt)
    for _ in range(6):
        lg = llama.dense_forward(params, cfg, jnp.asarray(cur))
        cur.append(int(np.argmax(np.asarray(lg[-1]))))
    want = cur[len(prompt):]

    import asyncio

    async def main():
        engine = JaxEngine(
            EngineConfig(model=cfg, num_blocks=32, block_size=4,
                         max_batch_size=2, max_context=64, prefill_chunk=8),
            params=params,
        )
        out = await collect(engine.generate(Context(PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[],
        ))))
        toks = [t for o in out for t in o.token_ids]
        assert toks == want, (toks, want)
        await engine.close()

    asyncio.run(main())


def test_gemma2_paged_engine_matches_dense():
    """The paged serving path (chunked prefill + decode with sandwich
    norms, score/logit softcaps, alternating windows, fixed query scale)
    must reproduce the dense gemma-2-shaped forward token-for-token
    through the engine."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import Context, collect

    cfg = ModelConfig.tiny(
        num_layers=4, layer_windows=(6, 0, 6, 0),
        post_norms=True, attn_softcap=50.0, final_softcap=30.0,
        attn_scale_base=32, rms_add_unit=True, scale_embed=True,
        tie_word_embeddings=True, hidden_act="gelu_tanh", dtype="float32",
    )
    params = llama.init_params(cfg, __import__("jax").random.key(4))
    prompt = [(13 * i + 2) % cfg.vocab_size for i in range(18)]
    cur = list(prompt)
    for _ in range(6):
        lg = llama.dense_forward(params, cfg, jnp.asarray(cur))
        cur.append(int(np.argmax(np.asarray(lg[-1]))))
    want = cur[len(prompt):]

    import asyncio

    async def main():
        engine = JaxEngine(
            EngineConfig(model=cfg, num_blocks=32, block_size=4,
                         max_batch_size=2, max_context=64, prefill_chunk=8),
            params=params,
        )
        out = await collect(engine.generate(Context(PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[],
        ))))
        toks = [t for o in out for t in o.token_ids]
        assert toks == want, (toks, want)
        await engine.close()

    asyncio.run(main())


def test_mla_paged_engine_matches_dense(tmp_path):
    """The ABSORBED paged prefill+decode path (compressed latent cache)
    must reproduce the naive dense MLA forward token-for-token through
    the engine."""
    import asyncio

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import Context, collect

    cfg = ModelConfig.tiny(
        num_heads=4, num_kv_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=24, dtype="float32",
    )
    params = llama.init_params(cfg, __import__("jax").random.key(0))
    prompt = [3, 17, 92, 45, 200, 7, 7, 133, 9, 20]
    # greedy rollout of the dense (naive, non-absorbed) reference
    cur = list(prompt)
    for _ in range(6):
        lg = llama.dense_forward(params, cfg, jnp.asarray(cur))
        cur.append(int(np.argmax(np.asarray(lg[-1]))))
    want = cur[len(prompt):]

    async def main():
        engine = JaxEngine(
            EngineConfig(model=cfg, num_blocks=32, block_size=4,
                         max_batch_size=2, max_context=64, prefill_chunk=8),
            params=params,
        )
        out = await collect(engine.generate(Context(PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=6, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[],
        ))))
        toks = [t for o in out for t in o.token_ids]
        assert toks == want, (toks, want)
        await engine.close()

    asyncio.run(main())


# ---------------- LFM2: conv and attention operators by layer ----------------


def _lfm2_model(tmp_path):
    hf_cfg = transformers.Lfm2Config(
        vocab_size=256, hidden_size=64, intermediate_size=160,
        block_multiple_of=16, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
        layer_types=["conv", "full_attention", "conv", "conv"],
        conv_L_cache=3, torch_dtype="float32",
    )
    model = transformers.Lfm2ForCausalLM(hf_cfg)
    with torch.no_grad():  # ones-init norms would make the check vacuous
        for name, p in model.named_parameters():
            if "norm" in name:
                p.normal_(1.0, 0.3)
    return _save(tmp_path, model), model


@pytest.mark.skipif(
    not hasattr(transformers, "Lfm2Config"),
    reason="transformers too old for LFM2",
)
def test_lfm2_parity(tmp_path):
    """The dense LFM2 through the real safetensors loader: the gated
    short convolution and its taps' order, per-head q/k norms before the
    rotary embedding, the operator / ffn / embedding norms, the adjusted
    FFN width and the tied head, against ``Lfm2ForCausalLM``."""
    path, model = _lfm2_model(tmp_path)
    cfg = ModelConfig.from_local_path(path)
    assert cfg.layer_ops == ("conv", "attn", "conv", "conv")
    assert cfg.intermediate_size == 112 and cfg.tie_word_embeddings
    _compare(path, TOKENS, model)


@pytest.mark.skipif(
    not hasattr(transformers, "Lfm2Config"),
    reason="transformers too old for LFM2",
)
def test_lfm2_decode_through_the_cache_matches_hf_cache(tmp_path):
    """Prefill, then decoding token by token through the paged KV cache
    and the conv state, against ``Lfm2ForCausalLM`` decoding with its own
    ``Lfm2HybridConvCache``."""
    path, model = _lfm2_model(tmp_path)
    cfg = ModelConfig.from_local_path(path)
    params = load_llama_params(path, cfg)
    prompt, more = TOKENS[:5], TOKENS[5:] + [91, 12]
    with torch.no_grad():
        out = model(torch.tensor(prompt)[None], use_cache=True)
        theirs = [out.logits[0, -1].numpy()]
        for t in more:
            out = model(torch.tensor([[t]]), use_cache=True,
                        past_key_values=out.past_key_values)
            theirs.append(out.logits[0, -1].numpy())
    bs, N, M = 4, 8, 4
    kc, vc = llama.init_kv_cache(cfg, N, bs)
    state = llama.init_state(cfg, 1, N)
    table = jnp.asarray([1, 2, 3, 4], jnp.int32)
    toks = np.zeros(16, np.int32)
    toks[:5] = prompt
    logits, kc, vc, state = llama.prefill(
        params, cfg, jnp.asarray(toks), table, jnp.int32(0), jnp.int32(5),
        kc, vc, state=state, slot=jnp.int32(0))
    ours = [np.asarray(logits)]
    for i, t in enumerate(more):
        logits, kc, vc, state = llama.decode_step(
            params, cfg, jnp.asarray([t], jnp.int32),
            jnp.asarray([5 + i], jnp.int32), table[None],
            jnp.asarray([6 + i], jnp.int32), kc, vc, state=state)
        ours.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.stack(ours), np.stack(theirs),
                               atol=2e-4, rtol=2e-3)
