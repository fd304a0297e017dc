"""GigaChat 3.5 (``gigachat3_5``): gated delta-rule linear attention beside
gated latent attention (three layers to one), a dense FFN then experts of
which the chip holds a SHARE, beside a shared one. The program against the
plain float32 reference of
``chipbench/configs/gigachat3.5-432b-a28b/reference.py`` at tiny widths
that keep the pattern (L L L A L; 1 dense layer then 4 expert layers, 4 of
16 experts held from the 4th, 4 a token, a shared expert), on seeded
weights whose norms, taps, decay rates and selection bias are drawn
non-zero: LOGITS through every step program
(``tests/test_gigachat35_engine.py``: through the engine, wherever the
two-part state has to follow a sequence)."""

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
CONFIG_DIR = os.path.join(REPO, "chipbench", "configs",
                          "gigachat3.5-432b-a28b")
TINY = os.path.join(REPO, "chipbench", "testdata", "tiny-gigachat35",
                    "config.json")
BS = 4  # KV block size of the paged tests

# float32 against float32: the program and the reference differ by the
# order of their sums (tests/test_olmoe.py) and, in a linear layer, by the
# form of the delta rule: the program's chunked form solves a triangular
# system a block where the reference steps token by token, and its
# decayed products pass through exp(cumsum(g)). Both are float32
# rounding; the largest difference seen at these sizes is 4e-6
ATOL = 2e-4


def _load_json(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def forward():
    return load_forward(os.path.join(CONFIG_DIR, "reference.py"))


@pytest.fixture(scope="module")
def reference():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gigachat35_reference", os.path.join(CONFIG_DIR, "reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny():
    """(hf dict, ModelConfig, params) in float32 (the seeded draw has no
    leaf at a trivial value: norms, taps, A_log, dt_bias, bias)."""
    hf = dict(_load_json(TINY), torch_dtype="float32")
    cfg = ModelConfig.from_hf_config(hf)
    return hf, cfg, llama.init_params(cfg, jax.random.key(0))


def _logp(logits):
    return np.asarray(jax.nn.log_softmax(jnp.asarray(logits, jnp.float32)))


def _table(first, n, M):
    t = np.zeros(M, np.int32)
    t[:n] = np.arange(first, first + n)
    return t


# ---------------- the parser ----------------


def test_served_config_parses_to_the_published_widths():
    cfg = ModelConfig.from_local_path(CONFIG_DIR)
    assert cfg.layer_ops == ("linear", "linear", "linear", "attn", "linear")
    assert (cfg.linear_layers, cfg.kv_layers, cfg.moe_layers) == (4, 1, 4)
    assert [cfg.op_index(l) for l in range(5)] == [0, 1, 2, 0, 3]
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank) == (7168, 64, 1536, 512)
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.linear_key_heads, cfg.linear_value_heads, cfg.linear_key_dim,
            cfg.linear_value_dim, cfg.linear_conv_kernel) == (
                32, 64, 128, 128, 4)
    assert cfg.linear_conv_dim == 16384
    assert (cfg.intermediate_size, cfg.moe_intermediate_size) == (18432, 2048)
    # the router is as wide as published; the chip holds a sixteenth
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first,
            cfg.local_experts, cfg.num_experts_per_tok) == (256, 16, 0, 16, 8)
    assert (cfg.num_shared_experts, cfg.first_dense_layers) == (1, 1)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_gate_bias
    assert cfg.routed_scaling_factor == 2.5 and cfg.norm_topk_prob
    assert cfg.gated_attention and cfg.post_norms and cfg.is_mla
    assert cfg.swiglu_limit == 10.0 and cfg.norm_gate_weight == 2.0
    assert cfg.vocab_size == 16032 and not cfg.tie_word_embeddings
    assert llama.kv_cache_shapes(cfg, 8, 16)[0] == (1, 1, 8, 16, 512)
    # a row of state is 16.4 MiB: the snapshot pool has a size of its own
    assert llama.state_row_bytes(cfg) == 4 * (4 << 20) + 4 * 3 * 16384 * 2
    assert llama.state_snapshot_rows(cfg, 12288, 0) == 64
    assert llama.state_snapshot_rows(cfg, 12288, 48) == 48
    state = jax.eval_shape(lambda: llama.init_state(cfg, 32, 12288, 64))
    assert state["conv"].shape == (32, 4 * 3 * 16384)
    assert state["rec"].shape == (4, 32, 64, 128, 128)
    assert state["snap"].shape == (64, 4 * 3 * 16384)
    assert state["snap_rec"].shape == (4, 64, 64, 128, 128)
    assert str(state["rec"].dtype) == "float32"


def test_a_small_state_keeps_a_snapshot_row_a_block():
    lfm2 = ModelConfig.from_local_path(
        os.path.join(REPO, "chipbench", "configs", "lfm2-8b-a1b"))
    assert llama.state_row_bytes(lfm2) == 9 * 2 * 2048 * 2
    assert llama.state_snapshot_rows(lfm2, 11264, 64) == 11264


def test_expert_share_is_read_for_every_expert_family():
    """An olmoe that holds 16 of its 64 experts runs through the same
    lines: the router stays 64 wide, the stacks hold 16."""
    hf = {"model_type": "olmoe", "num_experts": 16, "num_experts_per_tok": 8,
          "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 1,
          "num_attention_heads": 4, "vocab_size": 64,
          "expert_share": {"published": 64, "first": 32}}
    cfg = ModelConfig.from_hf_config(hf)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_first) == (64, 16, 32)
    params = llama.init_params(cfg, jax.random.key(0))
    assert params["layers"]["moe_gate"].shape == (1, 64, 64)
    assert params["layers"]["we_gate"].shape == (1, 16, 64, 32)
    whole = ModelConfig.from_hf_config(
        dict(hf, num_experts=64, expert_share={"published": 64, "first": 0}))
    assert (whole.experts_held, whole.local_experts) == (0, 64)


_GIGA = {"model_type": "gigachat3_5", "num_hidden_layers": 4,
         "linear_attention_type": "GigaChat35GatedDeltaNet",
         "full_attention_layers": [3], "kv_lora_rank": 32}


@pytest.mark.parametrize("hf,word", [
    (dict(_GIGA, linear_attention_type="Mamba2"), "linear_attention_type"),
    (dict(_GIGA, linear_attention_type=None), "linear_attention_type"),
    (dict(_GIGA, full_attention_layers=[3, 7]), "past the depth"),
    (dict(_GIGA, full_attention_layers=[3, 41],
          num_hidden_layers_published=40), "past the depth"),
    (dict(_GIGA, full_attention_layers=[7],
          num_hidden_layers_published=40), "names no layer"),
    (dict(_GIGA, num_nextn_predict_layers=2), "num_nextn_predict_layers"),
    (dict(_GIGA, norm_type="RMSNorm"), "norm_type"),
    (dict(_GIGA, use_shared_expert_sigmoid=True), "use_shared_expert_sigmoid"),
    (dict(_GIGA, n_group=8), "n_group"),
    ({"model_type": "llama", "num_hidden_layers": 2,
      "full_attention_layers": [1]}, "other than gigachat3_5"),
    ({"model_type": "gigachat3_5_next", "n_routed_experts": 8,
      "num_hidden_layers": 2}, "unsupported expert model"),
    (dict(_GIGA, n_routed_experts=8,
          expert_share={"published": 16, "first": 12}), "expert_share"),
])
def test_parser_refuses_by_name(hf, word):
    with pytest.raises(ValueError, match=word):
        ModelConfig.from_hf_config(hf)


def test_a_depth_cut_keeps_the_published_list_whole():
    cfg = ModelConfig.from_hf_config(dict(
        _GIGA, full_attention_layers=[3, 7, 11],
        num_hidden_layers_published=12))
    assert cfg.layer_ops == ("linear", "linear", "linear", "attn")


# ---------------- the delta rule: recurrence against the chunked form ----


@pytest.mark.parametrize("T,block", [(64, 64), (192, 64), (48, 16)])
def test_recurrence_and_chunked_form_agree(T, block):
    """The two forms of ONE operator: T steps of the recurrence against
    the chunked form from the same incoming state, outputs and final
    state, to float32 rounding (products at full precision; the decay
    exp(cumsum g) over a block of 64 stays above 1e-9 at these rates)."""
    N, Hv, Dk, Dv = 2, 3, 16, 8
    ks = jax.random.split(jax.random.key(5), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (N, Hv, T, Dk))) * Dk ** -0.5
    k = unit(jax.random.normal(ks[1], (N, Hv, T, Dk)))
    v = jax.random.normal(ks[2], (N, Hv, T, Dv))
    g = -jax.random.uniform(ks[3], (N, Hv, T), minval=0.01, maxval=0.3)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (N, Hv, T)))
    S0 = jax.random.normal(ks[5], (N, Hv, Dk, Dv)) * 0.3
    # the last rows are padding: they must leave the state where it was
    real = jnp.arange(T) < T - 5
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)

    def step(S, xs):
        o, S = llama.delta_rule_step(*xs, S)
        return S, o

    front = lambda a: jnp.moveaxis(a, 2, 0)  # noqa: E731
    S_rec, o_rec = jax.lax.scan(step, S0, tuple(map(front, (q, k, v, g, beta))))
    o_chk, S_chk = llama.delta_rule_chunked(q, k, v, g, beta, S0, block)
    np.testing.assert_allclose(o_chk, jnp.moveaxis(o_rec, 0, 2), atol=2e-5)
    np.testing.assert_allclose(S_chk, S_rec, atol=2e-5)
    # and the state after the real rows alone is the same state
    cut = T - 5
    S_cut, _ = jax.lax.scan(step, S0, tuple(
        front(a[:, :, :cut]) for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(S_chk, S_cut, atol=2e-5)


# ---------------- the step programs against the reference ----------------


def test_dense_forward_matches_the_reference(forward, tiny):
    hf, cfg, params = tiny
    toks = np.random.default_rng(0).integers(16, 512, 150)
    want = _logp(forward(params, hf, toks))
    with jax.default_matmul_precision("highest"):
        got = _logp(llama.dense_forward(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_every_assumed_reading_shows_in_the_reference(reference, tiny):
    """The seeded draw makes each reading matter: a reference that reads
    the norm's scale, the attention gate, the decay or the share
    otherwise moves the logits far beyond any tolerance here."""
    hf, _cfg, params = tiny
    toks = np.random.default_rng(1).integers(16, 512, 40)
    want = np.asarray(reference.forward(params, hf, toks))
    for flaw in ("plain_norm_scale", "no_attn_gate", "no_decay",
                 "all_experts_here"):
        got = np.asarray(reference.forward(params, hf, toks, flaws=(flaw,)))
        assert np.abs(got - want).max() > 100 * ATOL, flaw
    # the clamp binds only where a projection reaches 10: force it
    small = dict(hf, swiglu_limit=0.01)
    a = np.asarray(reference.forward(params, small, toks))
    b = np.asarray(reference.forward(params, small, toks, flaws=("no_clamp",)))
    assert np.abs(a - b).max() > 100 * ATOL


def test_swiglu_limit_binds_in_the_program_as_in_the_reference(forward, tiny):
    hf, _cfg, params = tiny
    small = dict(hf, swiglu_limit=0.01)
    cfg = ModelConfig.from_hf_config(small)
    toks = np.random.default_rng(2).integers(16, 512, 24)
    with jax.default_matmul_precision("highest"):
        got = _logp(llama.dense_forward(params, cfg, jnp.asarray(toks)))
    np.testing.assert_allclose(got, _logp(forward(params, small, toks)),
                               atol=ATOL)


def _prefill_chunks(params, cfg, toks, cuts, table, kc, vc, state, slot=0,
                    bucket=None, snap_rows=None, **kw):
    """``toks`` through ``prefill`` in chunks that end at ``cuts``;
    returns the last chunk's logits and what the programs left."""
    pos, lg = 0, None
    for i, end in enumerate(cuts):
        n = end - pos
        T = bucket or n
        chunk = np.zeros(T, np.int32)
        chunk[:n] = toks[pos:end]
        extra = {}
        if snap_rows is not None:
            extra["snap_row"] = jnp.int32(snap_rows[i])
        lg, kc, vc, state = llama.prefill(
            params, cfg, jnp.asarray(chunk), jnp.asarray(table),
            jnp.int32(pos), jnp.int32(n), kc, vc, state=state,
            slot=jnp.int32(slot), **extra, **kw)[:4]
        pos = end
    return lg, kc, vc, state


@pytest.mark.parametrize("cuts,bucket", [
    ([150], None),            # (a) lone prefill
    ([70, 134, 150], None),   # (b) chunks that cross a 64-token block
    ([70, 150], 128),         # (g) bucket padding behind the real rows
], ids=["lone", "chunks-cross-a-block", "padded-buckets"])
def test_prefill_matches_the_reference(forward, tiny, cuts, bucket):
    hf, cfg, params = tiny
    toks = np.random.default_rng(3).integers(16, 512, 150)
    want = _logp(forward(params, hf, toks))[-1]
    M, N = 72, 80
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, 2, N, 4)
    with jax.default_matmul_precision("highest"):
        lg, *_ = _prefill_chunks(params, cfg, toks, cuts, _table(1, 70, M),
                                 kc, vc, state, bucket=bucket)
    np.testing.assert_allclose(_logp(lg), want, atol=ATOL)


def test_decode_carries_cache_and_state(forward, tiny):
    """(c) prefill, then decode windows through the latent cache and the
    two-part state: every step's logprobs against the reference's."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(4)
    toks = [int(t) for t in rng.integers(16, 512, 21)]
    B, M, N = 2, 16, 24
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, B, N, 4)
    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    with jax.default_matmul_precision("highest"):
        lg, kc, vc, state = _prefill_chunks(
            params, cfg, toks, [len(toks)], _table(1, 10, M), kc, vc, state)
        out = [int(jnp.argmax(lg))]
        tables = np.zeros((B, M), np.int32)
        tables[0] = _table(1, 10, M)
        got_lps = []
        for _ in range(3):
            n = len(toks) + len(out)
            w, kc, vc, state, lps = llama.decode_window(
                params, cfg, jnp.asarray([out[-1], 0], jnp.int32),
                jnp.asarray([n - 1, 0], jnp.int32), jnp.asarray(tables),
                jnp.asarray([n, 0], jnp.int32), zi, zi, zf, zi,
                jnp.ones(B, jnp.float32), kc, vc, n_steps=2, state=state,
                with_logprobs=True)
            out += [int(t) for t in np.asarray(w)[:, 0]]
            got_lps += [float(x) for x in np.asarray(lps[0])[:, 0]]
    want = _logp(forward(params, hf, toks + out[:-1]))
    for i, tok in enumerate(out):
        assert tok == int(np.argmax(want[len(toks) - 1 + i])), i
    np.testing.assert_allclose(
        got_lps, [want[len(toks) + i, out[i + 1]] for i in range(6)],
        atol=ATOL)


def _mixed(params, cfg, d, chunk, p_table, hist, kc, vc, state, slot, pad=0,
           bucket=None, snap=None):
    """One mixed step: decode rows ``d`` = (tokens, positions, tables,
    lens) beside ONE prefill segment."""
    B = len(d[0])
    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    T = bucket or len(chunk)
    p_tok = np.full((1, T), pad, np.int32)
    p_tok[0, : len(chunk)] = chunk
    extra = {} if snap is None else {"p_snaps": jnp.asarray([snap], jnp.int32)}
    return llama.mixed_step(
        params, cfg, *(jnp.asarray(a) for a in d), zi, zi, zf, zi,
        jnp.ones(B, jnp.float32), jnp.asarray(p_tok),
        jnp.asarray(p_table)[None], jnp.asarray([hist], jnp.int32),
        jnp.asarray([len(chunk)], jnp.int32), kc, vc, state=state,
        p_slots=jnp.asarray([slot], jnp.int32), with_logprobs=True, **extra)


def test_mixed_step_matches_the_reference(forward, tiny):
    """(d) a decode row beside a prefill segment that starts from its
    sequence's state (the second chunk of its prompt) in one mixed step."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(5)
    a = [int(t) for t in rng.integers(16, 512, 13)]
    b = [int(t) for t in rng.integers(16, 512, 90)]
    B, M, N = 3, 32, 64
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, B, N, 4)
    with jax.default_matmul_precision("highest"):
        lg, kc, vc, state = _prefill_chunks(
            params, cfg, a, [13], _table(1, 6, M), kc, vc, state, slot=0)
        tok = int(jnp.argmax(lg))
        _lg, kc, vc, state = _prefill_chunks(
            params, cfg, b, [20], _table(8, 24, M), kc, vc, state, slot=2)
        tables = np.zeros((B, M), np.int32)
        tables[0] = _table(1, 6, M)
        d = (np.asarray([tok, 0, 0], np.int32), np.asarray([13, 0, 0], np.int32),
             tables, np.asarray([14, 0, 0], np.int32))
        nxt, p_logits, kc, vc, state, lps = _mixed(
            params, cfg, d, b[20:], _table(8, 24, M), 20, kc, vc, state, 2,
            bucket=128)
    want_a = _logp(forward(params, hf, a + [tok]))
    assert int(nxt[0]) == int(np.argmax(want_a[-1]))
    np.testing.assert_allclose(float(lps[0][0]), want_a[-1].max(), atol=ATOL)
    np.testing.assert_allclose(
        _logp(p_logits[0]), _logp(forward(params, hf, b))[-1], atol=ATOL)


def test_dead_slots_and_padding_leave_the_state_untouched(tiny):
    """(g) what a dead decode slot holds (token, both parts of its state)
    and what pads a bucket move nothing of the live rows, and the dead
    slot's state is left as it was."""
    _hf, cfg, params = tiny
    rng = np.random.default_rng(6)
    prompt = rng.integers(16, 512, 11)
    chunk = rng.integers(16, 512, 13)
    B, M, N = 3, 8, 16

    def run(dead_tok, fill, pad):
        kc, vc = llama.init_kv_cache(cfg, N, BS)
        state = llama.init_state(cfg, B, N, 4)
        state["conv"] = state["conv"].at[1].set(fill)
        state["rec"] = state["rec"].at[:, 1].set(fill)
        _lg, kc, vc, state = _prefill_chunks(
            params, cfg, prompt, [11], _table(1, 4, M), kc, vc, state,
            bucket=16)
        tables = np.zeros((B, M), np.int32)
        tables[0] = _table(1, 4, M)
        d = (np.asarray([9, dead_tok, dead_tok], np.int32),
             np.asarray([11, 0, 0], np.int32), tables,
             np.asarray([12, 0, 0], np.int32))
        nxt, p_logits, kc, vc, state, lps = _mixed(
            params, cfg, d, chunk, _table(6, 4, M), 0, kc, vc, state, 2,
            pad=pad, bucket=16)
        return state, [np.asarray(nxt)[0], np.asarray(p_logits),
                       np.asarray(state["conv"])[[0, 2]],
                       np.asarray(state["rec"])[:, [0, 2]],
                       np.asarray(lps[0])[0]]

    s_a, a = run(0, 0.0, 0)
    s_b, b = run(301, 7.0, 44)
    for u, v in zip(a, b):
        np.testing.assert_array_equal(u, v)
    assert np.all(np.asarray(s_b["conv"])[1] == 7.0)
    assert np.all(np.asarray(s_b["rec"])[:, 1] == 7.0)


def test_snapshot_at_a_chunks_end_restores_both_parts(forward, tiny):
    """(e) at the level of the programs: a chunk that names a snapshot
    row leaves BOTH parts of its end state there; a second sequence that
    starts from that row (and the cached latents) ends where a cold run
    does."""
    from dynamo_tpu.engine.engine import _begin_state_row

    hf, cfg, params = tiny
    toks = np.random.default_rng(7).integers(16, 512, 44)
    M, N = 16, 24
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, 2, N, 4)
    with jax.default_matmul_precision("highest"):
        cold, kc, vc, state = _prefill_chunks(
            params, cfg, toks, [32, 44], _table(1, 12, M), kc, vc, state,
            snap_rows=[3, 4])  # 4 rows: an index of 4 is dropped
        assert float(jnp.abs(state["snap_rec"][:, 3]).max()) > 0
        assert float(jnp.abs(state["snap"][3]).max()) > 0
        assert float(jnp.abs(state["snap_rec"][:, :3]).max()) == 0
        # the second asker: the same 8 blocks, row 1 from snapshot 3
        state = _begin_state_row(state, jnp.int32(1), jnp.int32(3))
        chunk = np.zeros(16, np.int32)
        chunk[:12] = toks[32:]
        warm, kc, vc, state = llama.prefill(
            params, cfg, jnp.asarray(chunk), jnp.asarray(_table(1, 12, M)),
            jnp.int32(32), jnp.int32(12), kc, vc, state=state,
            slot=jnp.int32(1), snap_row=jnp.int32(4))
    np.testing.assert_allclose(_logp(warm), _logp(cold), atol=ATOL)
    np.testing.assert_allclose(_logp(cold), _logp(forward(params, hf, toks))[-1],
                               atol=ATOL)
    np.testing.assert_allclose(state["rec"][:, 1], state["rec"][:, 0],
                               atol=1e-5)
    # a start from zeros (snap_row < 0) clears both parts
    state = _begin_state_row(state, jnp.int32(1), jnp.int32(-1))
    assert float(jnp.abs(state["rec"][:, 1]).max()) == 0
    assert float(jnp.abs(state["conv"][1]).max()) == 0


# ---------------- one chip's share of the expert layer ----------------


def test_the_shares_add_up(reference, tiny):
    """The parts of an expert layer's output that all four shares give
    (4 of 16 experts each), with the shared expert counted once, equal
    the uncut layer: program share by share against the reference's
    share, and their sum against the reference with every expert."""
    hf, cfg, params = tiny
    lp = {k: v[1] for k, v in params["layers"].items()}
    g = jax.random.normal(jax.random.key(9), (40, cfg.hidden_size)) * 0.5
    X, held = cfg.num_experts, cfg.experts_held
    ks = jax.random.split(jax.random.key(10), 3)
    full = {n: jax.random.normal(k, (X,) + lp[n].shape[1:]) * 0.05
            for n, k in zip(("we_gate", "we_up", "we_down"), ks)}
    shared = {k: lp[k] for k in ("shared_gate", "shared_up", "shared_down")}
    with jax.default_matmul_precision("highest"):
        whole_hf = dict(hf, n_routed_experts=X,
                        expert_share={"published": X, "first": 0})
        want = reference.expert_ffn(g, {**lp, **full}, whole_hf)
        shared_out = reference.glu(
            g @ shared["shared_gate"], g @ shared["shared_up"],
            hf) @ shared["shared_down"]
        total = jnp.zeros_like(g)
        for first in range(0, X, held):
            part = {n: w[first : first + held] for n, w in full.items()}
            share_hf = dict(hf, expert_share={"published": X, "first": first})
            c = ModelConfig.from_hf_config(share_hf)
            got = llama.moe_ffn({**lp, **part}, c, g)
            ref = reference.expert_ffn(g, {**lp, **part}, share_hf)
            np.testing.assert_allclose(got, ref, atol=ATOL)
            dense = llama.moe_ffn_dense({**lp, **part}, c, g)
            np.testing.assert_allclose(dense, ref, atol=ATOL)
            total = total + got - shared_out
        np.testing.assert_allclose(total + shared_out, want, atol=ATOL)


def test_tally_counts_the_held_assignments(tiny):
    _hf, cfg, params = tiny
    lp = {k: v[0] for k, v in params["layers"].items()}
    g = jax.random.normal(jax.random.key(11), (64, cfg.hidden_size))
    live = jnp.arange(64) < 48
    tally = llama.MoeTally(llama.MoeTally.zeros(cfg))
    llama.moe_ffn(lp, cfg, g, tally=tally, live=live)
    _vals, idx = llama._route_topk(lp, cfg, g)
    here = (idx >= cfg.expert_first) & (
        idx < cfg.expert_first + cfg.experts_held) & live[:, None]
    touched, touched_live, _largest, streams, held = (
        int(v) for v in tally.sums)
    assert streams == touched
    assert held == int(here.sum()) < 48 * cfg.num_experts_per_tok
    assert touched == touched_live <= cfg.experts_held


# ---------------- the decode step's kernel ----------------


def _step_operands(B=6, Hv=8, Dk=16):
    Ll, Dv = 3, 128
    ks = jax.random.split(jax.random.key(12), 6)
    q = jax.random.normal(ks[0], (B, Hv, Dk)) * 0.3
    k = jax.random.normal(ks[1], (B, Hv, Dk)) * 0.3
    v = jax.random.normal(ks[2], (B, Hv, Dv))
    g = -jax.random.uniform(ks[3], (B, Hv), minval=0.01, maxval=0.5)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, Hv)))
    rec = jax.random.normal(ks[5], (Ll, B, Hv, Dk, Dv))
    return q, k, v, g, beta, rec


@pytest.mark.parametrize("alive,heads", [
    pytest.param([1, 1, 1, 1, 1, 1], 8, id="all-live"),
    pytest.param([0, 0, 0, 0, 0, 0], 8, id="none-live"),
    pytest.param([1, 0, 0, 0, 0, 0], 8, id="only-slot-0"),
    pytest.param([0, 0, 0, 0, 0, 1], 8, id="only-the-last-slot"),
    pytest.param([0, 1, 0, 0, 1, 1], 8, id="a-scattered-half"),
    pytest.param([1, 1, 0, 1, 1, 1], 8, id="one-dead-between-live"),
    # the published head count: two grid steps of 32 heads a live row,
    # each a loop over four unrolled groups (8 heads are one group)
    pytest.param([1, 0, 1], 64, id="64-heads"),
])
def test_recurrent_step_kernel_matches_the_plain_step(alive, heads):
    """The Pallas kernel (interpret mode) against ``delta_rule_step``:
    one layer of a layer-major state moved one token on, in place, for
    the LIVE rows (``n > 0``); the other layers and the dead rows'
    matrices bit for bit as they were, a dead row's ``o`` zero. Both are
    float32 multiply-adds of the same terms; only the order of a 16-term
    sum differs."""
    from chipbench import kernel_work
    from dynamo_tpu.ops.gated_delta_pallas import (
        HEADS_PER_STEP, HEADS_UNROLLED, linear_attn_recurrent_step,
    )

    assert (HEADS_PER_STEP, HEADS_UNROLLED) == (32, 8)
    q, k, v, g, beta, rec = _step_operands(len(alive), heads)
    a = np.asarray(alive, bool)
    n = jnp.asarray(a.astype(np.int32) * 3)  # any count of real tokens
    want_o, want_S = llama.delta_rule_step(q, k, v, g, beta, rec[1])
    o, out = linear_attn_recurrent_step(
        q, k, v, g, beta, jnp.array(rec), jnp.int32(1), n, interpret=True)
    o, out = np.asarray(o), np.asarray(out)
    np.testing.assert_allclose(o[a], np.asarray(want_o)[a], atol=1e-5)
    np.testing.assert_allclose(out[1][a], np.asarray(want_S)[a], atol=1e-5)
    np.testing.assert_array_equal(out[1][~a], np.asarray(rec[1])[~a])
    np.testing.assert_array_equal(o[~a], 0.0)
    np.testing.assert_array_equal(out[0], rec[0])
    np.testing.assert_array_equal(out[2], rec[2])
    # what a call moves at the published widths: 32 rows x 64 heads
    assert kernel_work.linear_attn_recurrent_step_bytes(
        32, 64, 128, 128) == 2 * (128 << 20) + 6 * (1 << 20)
    assert kernel_work.linear_attn_recurrent_step_flops(
        32, 64, 128, 128) == 7 * (32 << 20)


def test_recurrent_step_kernel_does_not_read_a_dead_row():
    """Everything a dead slot holds, poisoned: its ``q, k, v, g, beta``
    AND its matrices are NaN. The live rows' results are those of the
    clean call, bit for bit, and the dead rows' matrices come back as
    they went in (NaN where NaN was: moved by nothing)."""
    from dynamo_tpu.ops.gated_delta_pallas import linear_attn_recurrent_step

    q, k, v, g, beta, rec = _step_operands()
    a = np.asarray([1, 0, 1, 0, 0, 1], bool)
    n = jnp.asarray(a.astype(np.int32))
    clean_o, clean = linear_attn_recurrent_step(
        q, k, v, g, beta, jnp.array(rec), jnp.int32(1), n, interpret=True)
    dead = jnp.asarray(~a)
    nan = lambda x: jnp.where(  # noqa: E731
        dead.reshape((-1,) + (1,) * (x.ndim - 1)), jnp.nan, x)
    bad = rec.at[1].set(nan(rec[1]))
    o, out = linear_attn_recurrent_step(
        nan(q), nan(k), nan(v), nan(g), nan(beta), jnp.array(bad),
        jnp.int32(1), n, interpret=True)
    np.testing.assert_array_equal(np.asarray(o)[a], np.asarray(clean_o)[a])
    np.testing.assert_array_equal(np.asarray(o)[~a], 0.0)
    np.testing.assert_array_equal(np.asarray(out[1])[a],
                                  np.asarray(clean[1])[a])
    assert np.isnan(np.asarray(out[1])[~a]).all()
    np.testing.assert_array_equal(out[0], rec[0])
    np.testing.assert_array_equal(out[2], rec[2])


def test_decode_program_hands_the_kernel_the_whole_state(tiny):
    """A kernels-on decode program of the tiny model: ONE ``pallas_call``
    named ``linear_attn_recurrent_step`` a linear layer, with three
    scalar-prefetch operands (the layer, the live slots, their count:
    the kernel walks the live rows), and its state operand the whole
    ``[Ll, B, Hv, Dk, Dv]`` array: no equation cuts a layer's matrices
    out of it (a slice in front of a custom call is a copy, 128 MiB a
    layer at the published widths)."""
    from conftest import jaxpr_eqns

    _hf, cfg, params = tiny
    B, M, N = 4, 8, 24
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, B, N, 4)
    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda p, kc, vc, state: llama.decode_window(
            p, cfg, zi, zi, jnp.zeros((B, M), jnp.int32), zi + 1, zi, zi, zf,
            zi, zf + 1, kc, vc, n_steps=1, state=state, use_pallas=True,
            interpret=True))(params, kc, vc, state)
    rec_shape = state["rec"].shape
    assert rec_shape[:2] == (cfg.linear_layers, B)
    calls = 0
    for eqn in jaxpr_eqns(jaxpr.jaxpr):
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert shape not in (rec_shape[1:], (1, *rec_shape[1:])), (
                f"{eqn.primitive.name} produces a layer's matrices {shape}")
        if eqn.primitive.name != "pallas_call" or (
                eqn.params["name"] != "linear_attn_recurrent_step"):
            continue
        calls += 1
        assert eqn.params["grid_mapping"].num_index_operands == 3
        assert [v.aval.shape for v in eqn.invars].count(rec_shape) == 1
        assert eqn.outvars[1].aval.shape == rec_shape
    assert calls == cfg.linear_layers == 4


def test_decode_through_the_kernels_matches_the_reference(forward, tiny):
    """(c) again with the kernels on (interpret mode): the decode step's
    recurrence in the Pallas kernel over the whole state, the latent
    layer on its Pallas kernels, against the reference."""
    hf, cfg, params = tiny
    rng = np.random.default_rng(14)
    toks = [int(t) for t in rng.integers(16, 512, 19)]
    B, M, N = 2, 16, 24
    kc, vc = llama.init_kv_cache(cfg, N, BS)
    state = llama.init_state(cfg, B, N, 4)
    zi, zf = jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.float32)
    with jax.default_matmul_precision("highest"):
        lg, kc, vc, state = _prefill_chunks(
            params, cfg, toks, [len(toks)], _table(1, 10, M), kc, vc, state)
        out = [int(jnp.argmax(lg))]
        tables = np.zeros((B, M), np.int32)
        tables[0] = _table(1, 10, M)
        n = len(toks) + 1
        w, kc, vc, state, lps = llama.decode_window(
            params, cfg, jnp.asarray([out[-1], 0], jnp.int32),
            jnp.asarray([n - 1, 0], jnp.int32), jnp.asarray(tables),
            jnp.asarray([n, 0], jnp.int32), zi, zi, zf, zi,
            jnp.ones(B, jnp.float32), kc, vc, n_steps=3, state=state,
            with_logprobs=True, use_pallas=True, interpret=True)
    out += [int(t) for t in np.asarray(w)[:, 0]]
    want = _logp(forward(params, hf, toks + out[:-1]))
    for i, tok in enumerate(out):
        assert tok == int(np.argmax(want[len(toks) - 1 + i])), i
    np.testing.assert_allclose(
        np.asarray(lps[0])[:, 0],
        [want[len(toks) + i, out[i + 1]] for i in range(3)], atol=ATOL)
