"""LFM2 (``lfm2_moe``, and the dense ``lfm2``): gated short convolutions
beside attention, chosen per layer; two leading dense FFNs, then experts
picked by a biased sigmoid score. The program against the plain float32
reference of ``chipbench/configs/lfm2-8b-a1b/reference.py`` at tiny
widths that keep the pattern (``c c A c`` twice, 2 dense layers then 6
expert layers of 8, 2 a token), on seeded weights whose norms are not all
ones: LOGITS through every step program (``tests/test_lfm2_engine.py``:
through the engine, wherever the conv layers' state has to follow a
sequence)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import load_forward
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_DIR = os.path.join(REPO, "chipbench", "configs", "lfm2-8b-a1b")
TINY = os.path.join(REPO, "chipbench", "testdata", "tiny-lfm2", "config.json")
BS = 4  # KV block size of the paged tests

# float32 against float32: the program and the reference differ by the
# order of their sums only (see tests/test_olmoe.py)
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

    def bump(tree):
        return {name: bump(a) if isinstance(a, dict)
                else a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
                if name.endswith("norm") else a for name, a in tree.items()}

    return hf, cfg, bump(params)


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


def _table(first, n, M):
    t = np.zeros(M, np.int32)
    t[:n] = np.arange(first, first + n)
    return t


# ---------------- the parser ----------------


def test_published_config_parses_to_the_published_shapes():
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    assert cfg.layer_ops == ("conv", "conv", "attn", "conv") * 3
    assert (cfg.conv_layers, cfg.kv_layers, cfg.moe_layers) == (9, 3, 10)
    assert [cfg.op_index(l) for l in (0, 2, 3, 6, 11)] == [0, 0, 2, 1, 8]
    assert (cfg.hidden_size, cfg.head_dim, cfg.num_heads,
            cfg.num_kv_heads) == (2048, 64, 32, 8)
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.num_experts, cfg.num_experts_per_tok) == (7168, 1792, 32, 4)
    assert (cfg.first_dense_layers, cfg.conv_kernel) == (2, 3)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_gate_bias
    assert cfg.norm_topk_prob and cfg.topk_norm_eps == 1e-6
    assert cfg.tie_word_embeddings and cfg.qk_norm and not cfg.qk_norm_full
    assert llama.kv_cache_shapes(cfg, 8, 16)[0] == (3, 8, 8, 16, 128)
    state = jax.eval_shape(lambda: llama.init_state(cfg, 32, 8))
    assert state["conv"].shape == (32, 9 * 2 * 2048)
    assert state["snap"].shape == (8, 9 * 2 * 2048)


def test_dense_lfm2_adjusts_its_ffn_width_as_transformers_does():
    hf = {"model_type": "lfm2", "hidden_size": 64, "intermediate_size": 300,
          "block_multiple_of": 32, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "layer_types": ["conv", "full_attention"]}
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.intermediate_size == 224 and not cfg.is_moe  # 200 -> 224
    assert cfg.layer_ops == ("conv", "attn") and cfg.rope_theta == 1e6
    off = ModelConfig.from_hf_config(dict(hf, block_auto_adjust_ff_dim=False))
    assert off.intermediate_size == 300


@pytest.mark.parametrize("hf,word", [
    ({"model_type": "lfm2", "layer_types": ["conv", "linear_attention"],
      "num_hidden_layers": 2}, "linear_attention"),
    ({"model_type": "qwen3", "layer_types": ["full_attention", "mamba"],
      "num_hidden_layers": 2}, "mamba"),
    ({"model_type": "llama", "layer_types": ["conv"],
      "num_hidden_layers": 1}, "other than lfm2"),
    ({"model_type": "lfm2_moe", "layer_types": ["conv"],
      "num_hidden_layers": 1, "conv_bias": True}, "conv_bias"),
    ({"model_type": "lfm2", "layer_types": ["conv"],
      "num_hidden_layers": 2}, "1 entries for 2 layers"),
])
def test_parser_refuses_by_name(hf, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(hf)


# ---------------- the step programs against the reference ----------------


def test_dense_forward_matches_the_reference(forward, tiny):
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(16, 512, 40)
    taps = []
    want = _logp(forward(params, hf, toks, taps=taps))
    with jax.default_matmul_precision("highest"):
        got = _logp(llama.dense_forward(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=ATOL)
    assert len(taps) == cfg.moe_layers == 6  # the expert layers only


def test_prefill_then_decode_window_through_the_cache(forward, tiny):
    """A prompt of 21 in a bucket of 32 (the state must be the one at
    the TRUE last token, not the bucket's), then a decode window of
    three steps beside three dead slots, then a mixed step: slot 1
    decodes on while a second prompt prefills in two chunks whose ends
    are not block multiples."""
    hf, cfg, params = tiny
    B, M, N = 4, 16, 48
    rng = np.random.default_rng(2)
    seq = [int(t) for t in rng.integers(16, 512, 21)]
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, B, N)
    # rows that are not this sequence's hold garbage: nothing may read it
    state = jax.tree.map(lambda a: a + 7.0, state)
    state["conv"] = state["conv"].at[jnp.asarray([1, 3])].set(0.0)
    table = _table(1, 12, M)
    t = np.zeros(32, np.int32)
    t[:21] = seq
    logits, kc, vc, state = llama.prefill(
        params, cfg, jnp.asarray(t), jnp.asarray(table), jnp.int32(0),
        jnp.int32(21), kc, vc, state=state, slot=jnp.int32(1))
    want = _logp(forward(params, hf, seq))[-1]
    np.testing.assert_allclose(_logp(logits), want, atol=ATOL)
    seq.append(int(np.argmax(want)))

    def batch(seq):
        lens = np.zeros(B, np.int32)
        lens[1] = len(seq)
        toks = np.zeros(B, np.int32)
        toks[1] = seq[-1]
        tables = np.zeros((B, M), np.int32)
        tables[1] = table
        return (jnp.asarray(toks), jnp.asarray(np.maximum(lens - 1, 0)),
                jnp.asarray(tables), jnp.asarray(lens))

    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    sampling = (zi, zi, zf, zi, jnp.ones(B, jnp.float32))  # greedy
    n = 3
    dead = np.asarray(state["conv"][0])
    toks, kc, vc, state, lps, sums = llama.decode_window(
        params, cfg, *batch(seq), *sampling, kc, vc, n_steps=n,
        with_logprobs=True, moe_counters=True, state=state)
    np.testing.assert_array_equal(np.asarray(state["conv"][0]), dead)
    # the tally counts the 6 expert layers: a live row takes 2 of each
    assert int(sums[1]) == n * cfg.moe_layers * cfg.num_experts_per_tok
    _chosen, top_ids, top_lps = (np.asarray(a) for a in lps)
    for s in range(n):
        seq.append(int(toks[s, 1]))
        want = _logp(forward(params, hf, seq[:-1]))[-1]
        assert seq[-1] == int(np.argmax(want))
        np.testing.assert_allclose(
            top_lps[s, 1], want[top_ids[s, 1]], atol=ATOL)

    other = [int(t) for t in rng.integers(16, 512, 23)]
    o_table = _table(20, 8, M)
    done = 0
    for take in (13, 10):  # 13 and 23: neither a multiple of the block
        p_tok = np.zeros((1, 16), np.int32)
        p_tok[0, :take] = other[done : done + take]
        nxt, p_logits, kc, vc, state, lps = llama.mixed_step(
            params, cfg, *batch(seq), *sampling, jnp.asarray(p_tok),
            jnp.asarray(o_table)[None], jnp.asarray([done], jnp.int32),
            jnp.asarray([take], jnp.int32), kc, vc, with_logprobs=True,
            state=state, p_slots=jnp.asarray([3], jnp.int32))
        done += take
        want = _logp(forward(params, hf, seq))[-1]
        assert int(nxt[1]) == int(np.argmax(want))
        np.testing.assert_allclose(
            np.asarray(lps[2])[1], want[np.asarray(lps[1])[1]], atol=ATOL)
        seq.append(int(nxt[1]))
        np.testing.assert_allclose(
            _logp(p_logits[0]),
            _logp(forward(params, hf, other[:done]))[-1], atol=ATOL)
    # every full block the two sequences wrote left its snapshot: the
    # state after its last token, as a prompt cut there would leave it
    for tokens, tab, slot in ((seq[:-1], table, 1), (other, o_table, 3)):
        for b in range(len(tokens) // BS):
            cold = llama.init_state(cfg, 1, N)
            tt = np.zeros(32, np.int32)
            tt[: (b + 1) * BS] = tokens[: (b + 1) * BS]
            k2, v2 = llama.init_kv_cache(cfg, N, BS)
            cold = llama.prefill(
                params, cfg, jnp.asarray(tt), jnp.asarray(tab), jnp.int32(0),
                jnp.int32((b + 1) * BS), k2, v2, state=cold,
                slot=jnp.int32(0))[3]
            np.testing.assert_allclose(
                np.asarray(state["snap"][tab[b]]),
                np.asarray(cold["conv"][0]), atol=1e-5)


def test_fused_mixed_forward_matches_the_per_part_one(tiny):
    """The chip's mixed step (combined-row matmuls, the ragged kernel in
    interpret mode) against the CPU's per-part one: tokens, prefill
    logits and the conv state it leaves."""
    hf = dict(_load_json(TINY), torch_dtype="float32", hidden_size=128,
              num_attention_heads=2, num_key_value_heads=1)
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.head_dim == 64 and llama.kv_lanes(cfg) == 128
    # two attention layers: the fused step reads and writes each by its
    # ordinal in the cache
    assert cfg.kv_layers == 2
    params = llama.init_params(cfg, jax.random.key(3))
    B, M, N, bs = 2, 8, 16, 8
    rng = np.random.default_rng(4)
    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    sampling = (zi, zi, zf, zi, jnp.ones(B, jnp.float32))

    def run(use_pallas):
        kc, vc = llama.init_kv_cache(cfg, N, bs)
        state = llama.init_state(cfg, B, N)
        prompt = np.zeros(16, np.int32)
        prompt[:11] = rng0.integers(16, 512, 11)
        _lg, kc, vc, state = llama.prefill(
            params, cfg, jnp.asarray(prompt), jnp.asarray(_table(1, 4, M)),
            jnp.int32(0), jnp.int32(11), kc, vc, state=state,
            slot=jnp.int32(0))
        tables = np.zeros((B, M), np.int32)
        tables[0] = _table(1, 4, M)
        p_tok = np.zeros((1, 16), np.int32)
        p_tok[0, :13] = rng0.integers(16, 512, 13)
        return llama.mixed_step(
            params, cfg, jnp.asarray([9, 0], jnp.int32),
            jnp.asarray([11, 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray([12, 0], jnp.int32), *sampling, jnp.asarray(p_tok),
            jnp.asarray(_table(6, 3, M))[None], jnp.zeros(1, jnp.int32),
            jnp.asarray([13], jnp.int32), kc, vc, use_pallas=use_pallas,
            interpret=use_pallas, state=state,
            p_slots=jnp.asarray([1], jnp.int32))

    rng0 = np.random.default_rng(5)
    want = run(False)
    rng0 = np.random.default_rng(5)
    got = run(True)
    assert int(got[0][0]) == int(want[0][0])
    np.testing.assert_allclose(_logp(got[1]), _logp(want[1]), atol=2e-3)
    for key in ("conv", "snap"):
        np.testing.assert_allclose(
            np.asarray(got[4][key]), np.asarray(want[4][key]), atol=1e-4)


# ---------------- the router ----------------


def test_the_bias_picks_and_does_not_weigh(tiny):
    _hf, cfg, params = tiny
    lp = {k: v[0] for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.key(7), (64, cfg.hidden_size))
    vals, idx = llama._route_topk(lp, cfg, x)
    scores = jax.nn.sigmoid(x @ lp["moe_gate"])
    picked = jnp.take_along_axis(scores, idx, 1)
    np.testing.assert_allclose(
        np.asarray(vals),
        np.asarray(picked / (picked.sum(-1, keepdims=True) + 1e-6)),
        atol=1e-6)
    _, biased = jax.lax.top_k(scores + lp["moe_gate_bias"], 2)
    _, plain = jax.lax.top_k(scores, 2)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(biased))
    # the seeded bias is not zeros: it changes choices
    assert (np.sort(np.asarray(biased)) != np.sort(np.asarray(plain))).any()


def test_seeded_bias_changes_choices_at_the_published_widths():
    """The draws the chip's reference check sees: at 32 experts, 4 a
    token, the seeded bias moves a measurable share of the choices."""
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    E, X = cfg.hidden_size, cfg.num_experts
    k = jax.random.split(jax.random.key(0), 3)
    gate = jax.random.normal(k[0], (E, X)) * 0.02
    bias = jax.random.normal(k[1], (X,)) * 0.1
    x = jax.random.normal(k[2], (512, E))
    lp = {"moe_gate": gate, "moe_gate_bias": bias}
    _, with_bias = llama._route_topk(lp, cfg, x)
    _, without = llama._route_topk({"moe_gate": gate}, cfg, x)
    moved = (np.sort(np.asarray(with_bias)) != np.sort(np.asarray(without)))
    assert moved.any(-1).mean() > 0.05


# ---------------- what the check catches ----------------


@pytest.mark.parametrize("flaw", ["drop_oldest_tap", "bias_in_weights",
                                  "ignore_bias", "no_qk_norm", "zero_state"])
def test_reference_moves_when_the_mathematics_is_cut(forward, tiny, flaw):
    """Each departure moves the reference's own logprobs, at the last
    position of a 9-token answer to a 48-token prompt (the chip check's
    last position), by more than ``reference.json``'s tolerance. Tiny
    matrices of scale 0.02 make a model that hardly looks at its input,
    so every matrix is brought to 0.12 (the conv in-projection and the
    experts' down-projection, drawn smaller, too), the experts' to 0.24
    and the selection bias to 0.4. PERF.md section 6 (PR 33) has the
    same departures at full size on the served draws."""
    hf, cfg, _ = tiny
    params = llama.init_params(cfg, jax.random.key(0))  # as served
    tol = _load_json(os.path.join(
        REPO, "chipbench", "reference.json"))["tolerance"]
    up = {"conv_w": 1.0, "conv_in": 12.0, "we_gate": 12.0, "we_up": 12.0,
          "we_down": 96.0}
    big = {grp: {n: a * (up.get(n, 6.0) if a.ndim >= 3 else 1.0)
                 for n, a in leaves.items()} if isinstance(leaves, dict)
           else leaves for grp, leaves in params.items()}
    big["layers"]["moe_gate_bias"] = params["layers"]["moe_gate_bias"] * 4.0
    toks = np.random.default_rng(11).integers(16, 512, 48 + 8)
    want = _logp(forward(big, hf, toks))[-1]
    if flaw == "zero_state":
        got = _logp(forward(big, hf, toks, zero_state_at=48))[-1]
    else:
        got = _logp(forward(big, hf, toks, flaws=(flaw,)))[-1]
    top = np.argsort(want)[-21:]
    assert np.abs(got[top] - want[top]).max() > tol


# ---------------- the loader ----------------


def test_checkpoint_of_lfm2_moe_naming_loads(tmp_path, tiny):
    """A checkpoint named as the published one names its tensors
    (``conv.{in_proj,conv,out_proj}``, ``self_attn.{q,k,v,out}_proj``,
    ``{q,k}_layernorm``, ``operator_norm``, ``ffn_norm``,
    ``feed_forward.{w1,w2,w3}`` or ``feed_forward.{gate,expert_bias,
    experts.N.{w1,w2,w3}}``, ``model.embedding_norm``) loads into the
    tree the engine serves. The expert layers' names are an offline
    reading (``transformers`` 4.57 has the dense ``lfm2`` only)."""
    from safetensors.numpy import save_file

    from dynamo_tpu.models.weights import load_llama_params

    hf, cfg, params = tiny
    t = lambda a: np.ascontiguousarray(np.asarray(a).T)  # noqa: E731
    flat = {"model.embed_tokens.weight": np.asarray(params["embed"]),
            "model.embedding_norm.weight": np.asarray(params["final_norm"])}
    kd = cfg.first_dense_layers
    for l, op in enumerate(cfg.layer_ops):
        pre, i = f"model.layers.{l}.", cfg.op_index(l)
        if op == "conv":
            g = params["conv_ops"]
            flat[pre + "conv.in_proj.weight"] = t(g["conv_in"][i])
            flat[pre + "conv.out_proj.weight"] = t(g["conv_out"][i])
            # torch's Conv1d: [channels, 1, taps]
            flat[pre + "conv.conv.weight"] = t(g["conv_w"][i])[:, None, :]
        else:
            g = params["attn_ops"]
            for leaf, name in (("wq", "q_proj"), ("wk", "k_proj"),
                               ("wv", "v_proj"), ("wo", "out_proj")):
                flat[pre + f"self_attn.{name}.weight"] = t(g[leaf][i])
            for leaf in "qk":
                flat[pre + f"self_attn.{leaf}_layernorm.weight"] = (
                    np.asarray(g[leaf + "_norm"][i]))
        flat[pre + "operator_norm.weight"] = np.asarray(g["attn_norm"][i])
        f = params["dense_layers"] if l < kd else params["layers"]
        fi = l if l < kd else l - kd
        flat[pre + "ffn_norm.weight"] = np.asarray(f["mlp_norm"][fi])
        if l < kd:
            for leaf, name in (("w_gate", "w1"), ("w_up", "w3"),
                               ("w_down", "w2")):
                flat[pre + f"feed_forward.{name}.weight"] = t(f[leaf][fi])
            continue
        flat[pre + "feed_forward.gate.weight"] = t(f["moe_gate"][fi])
        flat[pre + "feed_forward.expert_bias"] = np.asarray(
            f["moe_gate_bias"][fi])
        for x in range(cfg.num_experts):
            for leaf, name in (("we_gate", "w1"), ("we_up", "w3"),
                               ("we_down", "w2")):
                flat[pre + f"feed_forward.experts.{x}.{name}.weight"] = t(
                    f[leaf][fi, x])
    save_file(flat, str(tmp_path / "model.safetensors"))
    (tmp_path / "config.json").write_text(json.dumps(hf))
    loaded = load_llama_params(
        str(tmp_path), ModelConfig.from_local_path(str(tmp_path)))
    assert jax.tree.structure(loaded) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------- weight-only quantization ----------------


def test_int8_weights_cover_the_operators_and_stay_close(forward, tiny):
    """``quantize_params`` reaches the conv and attention operators'
    matrices in their own groups (not only the FFN groups), and the
    quantized forward stays near the full-precision reference."""
    from dynamo_tpu.models.quant import quantize_params

    hf, cfg, params = tiny
    q = quantize_params(params, cfg, "int8")
    for grp, keys in (("conv_ops", ("conv_in", "conv_out")),
                      ("attn_ops", ("wq", "wk", "wv", "wo")),
                      ("dense_layers", ("w_gate", "w_up", "w_down")),
                      ("layers", ("we_gate", "we_up", "we_down"))):
        for key in keys:
            assert set(q[grp][key]) == {"q", "s"}, (grp, key)
    assert not isinstance(q["conv_ops"]["conv_w"], dict)
    toks = np.random.default_rng(3).integers(16, 512, 24)
    got = _logp(llama.dense_forward(q, cfg, jnp.asarray(toks)))
    want = _logp(forward(params, hf, toks))
    assert 1e-4 < np.abs(got - want).max() < 0.05
