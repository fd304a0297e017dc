"""OLMoE (``OlmoeForCausalLM``): pre-norm layers with full-width q/k
norms, 64 experts of which each token takes 8, router probabilities used
without renormalisation. The program against the plain float32 reference
of ``chipbench/configs/olmoe-1b-7b/reference.py`` at tiny widths that
keep the ratios (64 experts, top-8, MHA, full-width q/k norms), on seeded
weights whose norms are not all ones; the expert layer's routing counters
(``llama.MoeTally``, ``engine_moe_*``)."""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import load_forward
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.engine import MOE_COUNTERS
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime import Context, collect

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(REPO, "chipbench", "configs", "olmoe-1b-7b")
TINY = os.path.join(REPO, "chipbench", "testdata", "tiny-olmoe", "config.json")
BS = 4  # KV block size of the paged tests

# float32 against float32: the program and the reference differ by the
# order of their sums only (grouped matmul and scatter-add against an
# expert-by-expert loop; a paged cache against one score matrix), 1e-6 to
# 1e-5 on log-softmax here. bf16 anywhere that float32 is stated moves
# them by 1e-2 (test_reference_tolerance_catches_bf16), fifty times this.
ATOL = 2e-4


def _load_json(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def forward():
    return load_forward(os.path.join(CONFIG_DIR, "reference.py"))


@pytest.fixture(scope="module")
def tiny():
    """(hf dict, ModelConfig, params) in float32, norms perturbed so that
    a misplaced or missing norm shows."""
    hf = dict(_load_json(TINY), torch_dtype="float32")
    cfg = ModelConfig.from_hf_config(hf)
    params = llama.init_params(cfg, jax.random.key(0))
    k = jax.random.key(1)
    params = jax.tree.map(
        lambda a: a if a.ndim > 2 or a.shape[-1] == cfg.vocab_size
        else a + 0.1 * jax.random.normal(k, a.shape, a.dtype), params)
    return hf, cfg, params


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits), -1))


# ---------------- (a) the published config.json ----------------


def test_published_config_parses():
    hf = _load_json(os.path.join(CONFIG_DIR, "config.json"))
    assert hf["num_hidden_layers"] == 8  # the benchmark's depth cut
    cfg = ModelConfig.from_hf_config(dict(hf, num_hidden_layers=16))
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (
        16, 2048, 50304)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok) == (64, 8)
    # intermediate_size is ONE expert's width
    assert cfg.moe_intermediate_size == 1024
    assert cfg.num_shared_experts == 0 and cfg.first_dense_layers == 0
    assert cfg.moe_scoring == "softmax" and not cfg.norm_topk_prob
    # pre-norm layers with olmo-2's full-width q/k norms
    assert cfg.qk_norm_full and not cfg.norm_after and not cfg.post_norms
    assert cfg.rms_norm_eps == 1e-5 and cfg.rope_theta == 10000
    assert not cfg.rope_scaling and not cfg.tie_word_embeddings
    assert cfg.max_position_embeddings == 4096 and cfg.dtype == "bfloat16"
    lay = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))["layers"]
    assert lay["q_norm"].shape == lay["k_norm"].shape == (16, 2048)
    assert lay["we_gate"].shape == (16, 64, 2048, 1024)
    assert "attn_norm" in lay and "attn_post_norm" not in lay
    # the router's default when the key is absent is HF's: false
    hf.pop("norm_topk_prob")
    assert not ModelConfig.from_hf_config(hf).norm_topk_prob


def test_clip_qkv_is_refused():
    hf = _load_json(os.path.join(CONFIG_DIR, "config.json"))
    with pytest.raises(ValueError, match="clip_qkv"):
        ModelConfig.from_hf_config(dict(hf, clip_qkv=8.0))


def test_unknown_expert_family_is_refused_by_name():
    """A config that counts experts under a name the parser does not
    know is not served as a Llama with experts (what ``olmoe`` was)."""
    hf = _load_json(os.path.join(CONFIG_DIR, "config.json"))
    for key in ("num_experts", "num_local_experts", "n_routed_experts"):
        bad = {k: v for k, v in hf.items() if k != "num_experts"}
        bad.update({key: 64, "model_type": "phimoe",
                    "architectures": ["PhimoeForCausalLM"]})
        with pytest.raises(ValueError, match="PhimoeForCausalLM"):
            ModelConfig.from_hf_config(bad)
    # every family the parser lists still parses, a dense config of an
    # unknown name too, and so does a bare dict that names nothing
    assert ModelConfig.from_hf_config(
        {"model_type": "mixtral", "num_local_experts": 8}).num_experts == 8
    assert ModelConfig.from_hf_config(
        {"architectures": ["DeepseekV3ForCausalLM"],
         "n_routed_experts": 16}).num_experts == 16
    assert not ModelConfig.from_hf_config(
        {"model_type": "some_dense_model"}).is_moe
    assert ModelConfig.from_hf_config({"num_local_experts": 4}).num_experts == 4


# ---------------- (b) the dense forward ----------------


def test_dense_forward_matches_reference(forward, tiny):
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(16, 512, 40)
    with jax.default_matmul_precision("highest"):
        want = llama.dense_forward(params, cfg, jnp.asarray(toks))
    taps = []
    got = forward(params, hf, toks, taps=taps)
    np.testing.assert_allclose(_logp(got), _logp(want), atol=ATOL)
    # the program's router picks the reference's experts, layer by layer
    assert len(taps) == cfg.num_layers
    for l, (h, chosen) in enumerate(taps):
        lp = {k: v[l] for k, v in params["layers"].items()}
        vals, idx = llama._route_topk(lp, cfg, h)
        assert np.array_equal(np.sort(np.asarray(idx), -1),
                              np.sort(np.asarray(chosen), -1))
        # softmax over all 64, not renormalised over the chosen 8
        assert float(jnp.max(jnp.sum(vals, -1))) < 0.999


def test_reference_tolerance_catches_bf16(forward, tiny):
    """What ATOL is tight enough for: the same weights served in bf16."""
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(16, 512, 40)
    cfg16 = ModelConfig.from_hf_config(dict(hf, torch_dtype="bfloat16"))
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    low = llama.dense_forward(p16, cfg16, jnp.asarray(toks))
    diff = np.abs(_logp(low) - _logp(forward(params, hf, toks))).max()
    assert diff > 10 * ATOL


# ---------------- (c) the served path ----------------


def _table(first, n, width):
    t = np.zeros(width, np.int32)
    t[:n] = np.arange(first, first + n)
    return t


def test_served_path_matches_reference(forward, tiny):
    """What the engine dispatches: a chunked ``prefill``, a three-step
    ``decode_window`` through the paged cache beside dead rows, then a
    ``mixed_step`` that decodes on while a second prompt prefills.
    Compared with the reference's full forward on logits and logprobs
    (greedy tokens follow from them), within ATOL: float32 on both sides
    (see ATOL for what it is made of and what it would catch)."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(16, 512, 21)]
    M, B = 16, 4  # table width; decode slots (slot 0 live, three dead)
    kc, vc = llama.init_kv_cache(cfg, num_blocks=40, block_size=BS)
    table = _table(1, 12, M)

    # prefill in two chunks: 16 tokens, then 5 more on their history
    chunk = jnp.asarray(prompt[:16], jnp.int32)
    _, kc, vc = llama.prefill(params, cfg, chunk, jnp.asarray(table),
                              jnp.int32(0), jnp.int32(16), kc, vc)
    chunk = jnp.zeros(16, jnp.int32).at[:5].set(jnp.asarray(prompt[16:]))
    logits, kc, vc = llama.prefill(params, cfg, chunk, jnp.asarray(table),
                                   jnp.int32(16), jnp.int32(5), kc, vc)
    want = _logp(forward(params, hf, prompt))
    np.testing.assert_allclose(_logp(logits), want[-1], atol=ATOL)
    seq = prompt + [int(np.argmax(want[-1]))]

    def batch(seq):
        """Slot 0 holds ``seq`` (its last token not yet in the cache);
        slots 1.. are dead as the engine leaves them: length 0, a zero
        table, some stale last token."""
        toks = np.array([seq[-1], 7, 7, 300], np.int32)
        lens = np.array([len(seq), 0, 0, 0], np.int32)
        tables = np.zeros((B, M), np.int32)
        tables[0] = table
        return (jnp.asarray(toks), jnp.asarray(np.maximum(lens - 1, 0)),
                jnp.asarray(tables), jnp.asarray(lens))

    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    sampling = (zi, zi, zf, zi, jnp.ones(B, jnp.float32))  # greedy

    # a decode window of three steps, logprobs on
    n = 3
    toks, kc, vc, lps = llama.decode_window(
        params, cfg, *batch(seq), *sampling, kc, vc, n_steps=n,
        with_logprobs=True)
    chosen_lp, top_ids, top_lps = (np.asarray(a) for a in lps)
    for s in range(n):
        seq.append(int(toks[s, 0]))
        want = _logp(forward(params, hf, seq[:-1]))[-1]
        assert seq[-1] == int(np.argmax(want))
        np.testing.assert_allclose(chosen_lp[s, 0], want[seq[-1]], atol=ATOL)
        np.testing.assert_allclose(
            top_lps[s, 0], want[top_ids[s, 0]], atol=ATOL)

    # a mixed step: slot 0 decodes on, a second prompt prefills beside it
    other = [int(t) for t in rng.integers(16, 512, 11)]
    p_tok = np.zeros((1, 16), np.int32)
    p_tok[0, :11] = other
    out = llama.mixed_step(
        params, cfg, *batch(seq), *sampling, jnp.asarray(p_tok),
        jnp.asarray(_table(20, 4, M))[None], jnp.zeros(1, jnp.int32),
        jnp.asarray([11], jnp.int32), kc, vc, with_logprobs=True)
    nxt, p_logits, kc, vc, (chosen_lp, top_ids, top_lps) = out
    want = _logp(forward(params, hf, seq))[-1]
    assert int(nxt[0]) == int(np.argmax(want))
    np.testing.assert_allclose(
        np.asarray(top_lps)[0], want[np.asarray(top_ids)[0]], atol=ATOL)
    np.testing.assert_allclose(
        _logp(p_logits[0]), _logp(forward(params, hf, other))[-1], atol=ATOL)


# ---------------- (d) the ragged dispatch ----------------


@pytest.mark.parametrize("case", ["even", "skewed", "empty"])
def test_ragged_dispatch_matches_dense_dispatch(tiny, case):
    """``moe_ffn`` (sort, ``ragged_dot`` over 64 groups, scatter-add)
    against ``moe_ffn_dense`` (every expert on every row) at 64 / 8."""
    _hf, cfg, params = tiny
    lp = {k: v[0] for k, v in params["layers"].items()}
    T = {"even": 64, "skewed": 48, "empty": 3}[case]
    x = jax.random.normal(jax.random.key(5), (T, cfg.hidden_size))
    if case == "skewed":
        # five experts' logits lifted: most rows choose them
        lp["moe_gate"] = lp["moe_gate"].at[:, :5].multiply(8.0).at[
            :, :5].add(jnp.abs(x).mean(0)[:, None] * jnp.sign(x.mean(0))[:, None])
    _t, _w, _e, sizes = llama._moe_route(lp, cfg, x)
    sizes = np.asarray(sizes)
    assert sizes.sum() == T * 8
    if case == "even":
        assert (sizes > 0).all()
    elif case == "skewed":
        assert sizes.max() >= 4 * np.median(sizes)
    else:
        assert (sizes == 0).sum() >= 64 - T * 8
    np.testing.assert_allclose(
        np.asarray(llama.moe_ffn(lp, cfg, x)),
        np.asarray(llama.moe_ffn_dense(lp, cfg, x)), atol=1e-5)


# ---------------- (e) the routing counters ----------------


def _expected_tally(lp, cfg, x, live):
    _, idx = llama._route_topk(lp, cfg, x)
    idx = np.asarray(idx)
    sizes = np.bincount(idx.reshape(-1), minlength=cfg.num_experts)
    return [int((sizes > 0).sum()), len(set(idx[live].reshape(-1))),
            int(sizes.max())]


def test_tally_counts_touched_live_and_largest_group(tiny):
    """A decode batch of three live rows and five dead ones that all
    carry one token: the dead rows choose the same 8 experts, which are
    touched but (those no live row chose) not touched-live, and make the
    largest group."""
    _hf, cfg, params = tiny
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.key(9), (8, cfg.hidden_size))
    x = x.at[3:].set(x[3])
    live = np.arange(8) < 3
    tally = llama.MoeTally(jnp.asarray(live))
    out = llama.moe_ffn(lp, cfg, x, tally=tally)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(llama.moe_ffn(lp, cfg, x)))
    touched, touched_live, largest = (int(v) for v in tally.sums)
    assert [touched, touched_live, largest] == _expected_tally(
        lp, cfg, x, live)
    assert largest >= 5 and touched_live <= 24 and touched > touched_live
    # a second layer adds to the same tally
    llama.moe_ffn(lp, cfg, x, tally=tally)
    assert [int(v) for v in tally.sums] == [2 * touched, 2 * touched_live,
                                            2 * largest]


def test_step_programs_return_the_tally(tiny):
    """``prefill`` (the layer scan), ``decode_window`` (steps x unrolled
    layers) and ``mixed_step`` append the sums as their last output when
    asked to, and change nothing else."""
    _hf, cfg, params = tiny
    L, M, B = cfg.num_layers, 8, 4
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(16, 512, 16), jnp.int32)
    table = jnp.asarray(_table(1, 6, M))

    def prefill(**kw):
        kc, vc = llama.init_kv_cache(cfg, num_blocks=16, block_size=BS)
        return llama.prefill(params, cfg, toks, table, jnp.int32(0),
                             jnp.int32(9), kc, vc, **kw)

    plain, counted = prefill(), prefill(moe_counters=True)
    assert len(counted) == len(plain) + 1
    np.testing.assert_array_equal(plain[0], counted[0])
    touched, touched_live, largest = (int(v) for v in counted[-1])
    # 9 live rows of 16: at most 72 live assignments a layer
    assert L <= touched_live <= min(touched, L * 64) and touched <= L * 64
    assert touched_live <= L * 72 and L <= largest <= L * 16

    def window(**kw):
        kc, vc = counted[1], counted[2]
        lens = jnp.asarray([10, 0, 0, 0], jnp.int32)
        z = jnp.zeros(B, jnp.int32)
        tables = jnp.zeros((B, M), jnp.int32).at[0].set(table)
        return llama.decode_window(
            params, cfg, jnp.asarray([5, 6, 7, 8], jnp.int32),
            jnp.maximum(lens - 1, 0), tables, lens, z, z,
            jnp.zeros(B, jnp.float32), z, jnp.ones(B, jnp.float32),
            jnp.array(kc), jnp.array(vc), n_steps=2, **kw)

    plain, counted_w = window(), window(moe_counters=True)
    assert len(counted_w) == len(plain) + 1
    np.testing.assert_array_equal(plain[0], counted_w[0])
    touched, touched_live, largest = (int(v) for v in counted_w[-1])
    # two steps x L layers; one live row takes exactly 8 experts a layer
    assert touched_live == 2 * L * 8
    assert 2 * L * 8 <= touched <= 2 * L * 32 and largest >= 2 * L


def _request(prompt, max_tokens):
    return Context(PreprocessedRequest(
        token_ids=list(prompt),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[],
    ))


def test_engine_exports_moe_counters_for_expert_models_only(tiny):
    _hf, cfg, params = tiny

    async def serve(model, params):
        engine = JaxEngine(
            EngineConfig(model=model, num_blocks=64, block_size=BS,
                         max_batch_size=4, max_context=64, prefill_chunk=16),
            params=params)
        out = await collect(engine.generate(_request(range(20, 41), 9)))
        assert sum(len(o.token_ids) for o in out) == 9
        m = engine.device_path_stats()
        await engine.close()
        return m

    m = asyncio.run(serve(cfg, params))
    got = {k: m[f"engine_{k}_total"] for k in MOE_COUNTERS}
    L, X, top = cfg.num_layers, cfg.num_experts, cfg.num_experts_per_tok
    steps = got["moe_expert_slots"] // (L * X)
    assert steps * L * X == got["moe_expert_slots"] and steps >= 9
    # one sequence: 21 prompt tokens, then a live row a decode step (the
    # ninth token needs no ninth forward)
    assert got["moe_assignments"] == L * top * (21 + 8)
    assert (L * top * 8 <= got["moe_experts_touched_live"]
            <= got["moe_experts_touched"] <= got["moe_expert_slots"])
    # three dead slots beside the live one: experts no live row chose
    assert got["moe_experts_touched"] > got["moe_experts_touched_live"]
    assert got["moe_max_group_rows"] >= steps * L

    dense = ModelConfig.tiny(dtype="float32")
    m = asyncio.run(serve(dense, llama.init_params(dense, jax.random.key(0))))
    assert not [k for k in m if "moe" in k]


# ---------------- the loader ----------------


def test_checkpoint_of_olmoe_naming_loads(tmp_path, tiny):
    """A checkpoint named as ``OlmoeForCausalLM`` names its tensors
    (``mlp.experts.N.*_proj``, ``mlp.gate``, full-width
    ``self_attn.q_norm`` / ``k_norm`` beside the two pre-norms) loads
    into the tree the engine serves."""
    from safetensors.numpy import save_file

    from dynamo_tpu.models.weights import load_llama_params

    hf, cfg, params = tiny
    lay = params["layers"]
    t = lambda a: np.ascontiguousarray(np.asarray(a).T)  # noqa: E731
    flat = {"model.embed_tokens.weight": np.asarray(params["embed"]),
            "model.norm.weight": np.asarray(params["final_norm"]),
            "lm_head.weight": t(params["lm_head"])}
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}."
        for leaf, name in (("attn_norm", "input_layernorm"),
                           ("mlp_norm", "post_attention_layernorm"),
                           ("q_norm", "self_attn.q_norm"),
                           ("k_norm", "self_attn.k_norm")):
            flat[pre + name + ".weight"] = np.asarray(lay[leaf][i])
        for leaf in "qkvo":
            flat[pre + f"self_attn.{leaf}_proj.weight"] = t(lay["w" + leaf][i])
        flat[pre + "mlp.gate.weight"] = t(lay["moe_gate"][i])
        for x in range(cfg.num_experts):
            for leaf in ("gate", "up", "down"):
                flat[pre + f"mlp.experts.{x}.{leaf}_proj.weight"] = t(
                    lay["we_" + leaf][i, x])
    save_file(flat, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(hf))
    loaded = load_llama_params(
        str(tmp_path), ModelConfig.from_local_path(str(tmp_path)))
    assert set(loaded["layers"]) == set(lay)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
