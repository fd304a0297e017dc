"""The sampler's top-k / nucleus filter (dynamo_tpu/ops/sampling.py).

``_apply_topk_topp`` finds its two cuts by a threshold search over the
row's values. The full descending sort it replaced stays here as the
oracle (``sort_topk_topp``): for every row with ``top_p < 1`` the new
function's masked logits equal the oracle's exactly, so a seeded request
draws the token it drew before. Rows with ``top_p >= 1`` take no nucleus
cut (the oracle's ``cumsum`` rounded up to 1.0 before the row's end and
cut a tail nobody asked to cut). A lowering guard keeps the sort and the
``cumsum`` over ``[B, V]`` out of the step programs, and a test on the
tiny engine holds ``engine_sampler_filter_steps_total`` to the steps
that had a live row asking for a cut.
"""

import asyncio
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import sampling
from dynamo_tpu.ops.sampling import NEG_INF
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context

VOCABS = (50_304, 65_536, 100_352)  # olmoe-1b-7b, lfm2-8b-a1b, olmo2-1b


def sort_topk_topp(
    scaled: jnp.ndarray, top_k: jnp.ndarray, top_p: jnp.ndarray
) -> jnp.ndarray:
    """The oracle: the filter as it was before the threshold search, a
    full descending sort, a softmax and a cumsum over the sorted row."""
    V = scaled.shape[-1]
    kth = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)  # [B]
    sorted_desc = -jnp.sort(-scaled, axis=-1)  # [B, V] descending
    kth_val = jnp.take_along_axis(sorted_desc, (kth - 1)[:, None], axis=1)
    scaled = jnp.where(scaled < kth_val, NEG_INF, scaled)
    probs_sorted = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs_sorted, axis=-1)
    inside = cum - probs_sorted < top_p[:, None]
    thresh = jnp.min(
        jnp.where(inside, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(scaled < thresh, NEG_INF, scaled)


def _draw(kind: str, rows: int, V: int, seed: int) -> np.ndarray:
    """[rows, V] float32 temperature-scaled logits of one kind."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((rows, V)) * 3.0).astype(np.float32)
    if kind == "ties":  # a few dozen distinct values: ties at both cuts
        x = np.round(x * 2) / 2
    elif kind == "flat":  # every token ties with every other
        x = np.full((rows, V), -1.25, np.float32)
    elif kind == "cold":  # temperature 1e-6: exp underflows below the top
        x = x / np.float32(1e-6)
    elif kind == "penalised":  # penalties pushed a third far down
        x = np.where(rng.random((rows, V)) < 0.33, x * 1e6 - 1e7, x)
        x = x.astype(np.float32)
    elif kind == "zeros":  # +0.0, -0.0 and values around them
        x = np.round(x).astype(np.float32)
        x = np.where(rng.random((rows, V)) < 0.5, -x, x).astype(np.float32)
    else:
        assert kind == "normal"
    return x


def _grid(V: int) -> tuple[np.ndarray, np.ndarray]:
    """Every top_k x top_p of the grid, a row each: one batch asks for
    different things in different rows."""
    ks, ps = np.meshgrid([0, 1, 5, V - 1, V, V + 9], [0.01, 0.5, 0.95])
    return ks.reshape(-1).astype(np.int32), ps.reshape(-1).astype(np.float32)


def _support(masked: np.ndarray) -> np.ndarray:
    return masked > NEG_INF / 2


CASES = [(kind, 1003) for kind in
         ("normal", "ties", "flat", "cold", "penalised", "zeros")]
CASES += [(kind, V) for V in VOCABS for kind in ("normal", "ties")]


@pytest.mark.parametrize("kind,V", CASES, ids=lambda v: str(v))
def test_masked_logits_equal_the_sort_oracle(kind, V):
    top_k, top_p = _grid(V)
    x = _draw(kind, len(top_k), V, seed=V + len(kind))
    got = np.asarray(jax.jit(sampling._apply_topk_topp)(x, top_k, top_p))
    want = np.asarray(jax.jit(sort_topk_topp)(x, top_k, top_p))
    for r in range(len(top_k)):
        assert np.array_equal(got[r], want[r]), (
            f"row {r} top_k {top_k[r]} top_p {top_p[r]}: support "
            f"{_support(got[r]).sum()} against the oracle's "
            f"{_support(want[r]).sum()}")
    # every row keeps its largest value
    assert (_support(got).sum(-1) >= 1).all()
    assert np.array_equal(got.argmax(-1), x.argmax(-1))


@pytest.mark.parametrize("top_k", [0, 7])
@pytest.mark.parametrize("top_p", [1.0, 1.5])
def test_top_p_of_one_or_more_takes_no_nucleus_cut(top_k, top_p):
    """``1.0 => disabled``: the row keeps every token (top_k 0) or
    exactly its top-k; the oracle cut a tail of mass under 1e-6 here."""
    B, V = 4, 1003
    x = _draw("normal", B, V, seed=11) * 4
    k = np.full(B, top_k, np.int32)
    got = np.asarray(jax.jit(sampling._apply_topk_topp)(
        x, k, np.full(B, top_p, np.float32)))
    keep = _support(got).sum(-1)
    assert (keep == (top_k or V)).all()
    if top_k:
        only_k = np.asarray(jax.jit(sort_topk_topp)(
            x, k, np.full(B, 2.0, np.float32)))
        assert np.array_equal(got, only_k)
    else:
        assert np.array_equal(got, x)


def test_a_batch_that_asks_for_nothing_is_left_alone():
    x = _draw("normal", 3, 1003, seed=5)
    got = jax.jit(sampling._apply_topk_topp)(
        x, np.zeros(3, np.int32), np.ones(3, np.float32))
    assert np.array_equal(np.asarray(got), x)


def _with_oracle(monkeypatch, fn):
    """``fn()`` traced with the sort-based filter in the sampler's place
    (a fresh trace: the patched global is read while tracing)."""
    with monkeypatch.context() as m:
        m.setattr(sampling, "_apply_topk_topp", sort_topk_topp)
        jax.clear_caches()
        out = jax.tree.map(np.asarray, fn())
    jax.clear_caches()
    return out


def test_filtered_dist_and_speculative_accept_match_the_oracle(monkeypatch):
    B, T, V = 5, 4, 1003
    rng = np.random.default_rng(21)
    logits = jnp.asarray(rng.standard_normal((B, T, V)) * 3, jnp.float32)
    temperature = jnp.asarray([0.7, 1.0, 0.0, 0.3, 1.3], jnp.float32)
    top_k = jnp.asarray([0, 5, 0, 40, V + 1], jnp.int32)
    top_p = jnp.asarray([0.95, 0.5, 0.9, 0.01, 0.8], jnp.float32)
    proposals = jnp.asarray(
        np.where(rng.random((B, T - 1)) < 0.6,
                 np.asarray(logits[:, :-1].argmax(-1)),
                 rng.integers(-1, V, (B, T - 1))), jnp.int32)
    keys_a = jax.vmap(jax.vmap(
        lambda i: jax.random.key_data(jax.random.key(i))))(
        jnp.arange(B * (T - 1)).reshape(B, T - 1))
    keys_s = jax.vmap(jax.vmap(
        lambda i: jax.random.key_data(jax.random.key(1000 + i))))(
        jnp.arange(B * T).reshape(B, T))

    def run():
        dist = jax.jit(sampling.filtered_dist)(
            logits[:, 0], temperature, top_k, top_p)
        out, n_acc = jax.jit(sampling.speculative_accept)(
            logits, proposals, keys_a, keys_s, temperature, top_k, top_p)
        return dist, out, n_acc

    want = _with_oracle(monkeypatch, run)
    got = jax.tree.map(np.asarray, run())
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    assert 0 < int(got[2].sum()) < B * (T - 1)  # some accepted, some not


# ---------------- the step programs ----------------

BS = 4


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig.tiny(dtype="float32")
    return cfg, llama.init_params(cfg, jax.random.key(0))


def _sampled_batch(cfg, params):
    """Four decode slots behind a prefilled prompt each (slot 3 dead),
    asking for nucleus, top-k, both, nothing."""
    B, M = 4, 8
    kc, vc = llama.init_kv_cache(cfg, num_blocks=40, block_size=BS)
    tables = np.zeros((B, M), np.int32)
    lens = np.zeros(B, np.int32)
    last = np.zeros(B, np.int32)
    rng = np.random.default_rng(9)
    for b in range(3):
        n = 5 + 3 * b
        tables[b] = np.arange(1 + 8 * b, 9 + 8 * b)
        chunk = np.zeros(16, np.int32)
        chunk[:n] = rng.integers(1, cfg.vocab_size, n)
        logits, kc, vc = llama.prefill(
            params, cfg, jnp.asarray(chunk), jnp.asarray(tables[b]),
            jnp.int32(0), jnp.int32(n), kc, vc)
        last[b], lens[b] = int(jnp.argmax(logits)), n + 1
    args = (jnp.asarray(last), jnp.asarray(np.maximum(lens - 1, 0)),
            jnp.asarray(tables), jnp.asarray(lens),
            jnp.asarray([3, 5, 7, 9], jnp.int32),  # seeds
            jnp.zeros(B, jnp.int32),  # steps
            jnp.asarray([0.9, 1.2, 0.7, 1.0], jnp.float32),
            jnp.asarray([0, 12, 30, 0], jnp.int32),
            jnp.asarray([0.9, 1.0, 0.6, 1.0], jnp.float32))
    return args, kc, vc


def _mixed_tail(cfg):
    p_tok = np.zeros((1, 16), np.int32)
    p_tok[0, :9] = np.arange(20, 29)
    table = np.zeros((1, 8), np.int32)
    table[0, :4] = np.arange(30, 34)
    return (jnp.asarray(p_tok), jnp.asarray(table),
            jnp.zeros(1, jnp.int32), jnp.asarray([9], jnp.int32))


def test_seeded_step_programs_emit_the_oracle_builds_tokens(
        tiny, monkeypatch):
    cfg, params = tiny

    def run():
        args, kc, vc = _sampled_batch(cfg, params)
        toks, kc, vc = llama.decode_window(
            params, cfg, *args, kc, vc, n_steps=4)
        seq = (args[0], args[1] + 4, args[2], args[3] + 4,
               args[4], args[5] + 4) + args[6:]
        nxt = llama.mixed_step(
            params, cfg, *seq, *_mixed_tail(cfg), kc, vc)[0]
        return toks, nxt

    want = _with_oracle(monkeypatch, run)
    toks, nxt = (np.asarray(a) for a in run())
    assert np.array_equal(toks, want[0]) and np.array_equal(nxt, want[1])
    # the draw is a real one: sampled rows left the greedy path
    args, kc, vc = _sampled_batch(cfg, params)
    greedy = llama.decode_window(
        params, cfg, *args[:6], jnp.zeros(4, jnp.float32), *args[7:], kc, vc,
        n_steps=4)[0]
    assert not np.array_equal(toks[:, :3], np.asarray(greedy)[:, :3])


def _operand_types(text: str, op: str) -> list[str]:
    """The operand types of every ``stablehlo.<op>`` of a lowering: they
    follow the op's region, ``}) : (types) -> ...``."""
    return re.findall(
        rf'"stablehlo\.{op}"\(.*?\n\s*\}}\) : \(([^)]*)\)', text, re.S)


@pytest.mark.parametrize("program", ["decode_window", "mixed_step"])
def test_no_sort_and_no_cumsum_over_the_vocabulary_in(program, tiny):
    """The CPU lowering of a sampled batch's step program holds no
    ``stablehlo.sort`` with a ``[B, V]`` operand and no ``reduce_window``
    over one: the sort cannot come back unseen."""
    cfg, params = tiny
    args, kc, vc = _sampled_batch(cfg, params)
    if program == "decode_window":
        low = llama.decode_window.lower(
            params, cfg, *args, kc, vc, n_steps=2)
    else:
        low = llama.mixed_step.lower(
            params, cfg, *args, *_mixed_tail(cfg), kc, vc)
    text = low.as_text()
    assert "stablehlo.while" in text  # the search is there
    bv = f"tensor<{len(args[0])}x{cfg.vocab_size}x"
    assert not [t for op in ("sort", "reduce_window")
                for t in _operand_types(text, op) if bv in t]
    # and the guard can see one: the oracle's lowering trips it
    text = jax.jit(sort_topk_topp).lower(
        jnp.zeros((4, cfg.vocab_size)), args[7], args[8]).as_text()
    assert any(bv in t for t in _operand_types(text, "sort"))
    assert any(bv in t for t in _operand_types(text, "reduce_window"))


# ---------------- the counter ----------------


def _request(options: SamplingOptions, max_tokens: int = 9):
    return PreprocessedRequest(
        token_ids=[(37 + 11 * j) % 200 + 5 for j in range(20)],
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=options,
        eos_token_ids=[],
    )


NUCLEUS = SamplingOptions(temperature=0.8, top_p=0.9, seed=1)
GREEDY = SamplingOptions(temperature=0.0)


@pytest.mark.parametrize("requests,filtered", [
    ([_request(NUCLEUS)], "all"),
    ([_request(SamplingOptions(temperature=0.8, top_k=5, seed=1))], "all"),
    ([_request(SamplingOptions(temperature=0.8, seed=1))], "none"),
    ([_request(SamplingOptions(temperature=0.0, top_p=0.9))], "none"),
    ([_request(NUCLEUS), _request(GREEDY, max_tokens=30)], "some"),
], ids=["top_p", "top_k", "plain", "greedy", "beside_a_longer_greedy_one"])
def test_filter_steps_counter_counts_steps_with_a_live_cut(
        run, requests, filtered):
    """``engine_sampler_filter_steps_total`` counts the decode and mixed
    steps in which a live row samples under a cut: all of a lone such
    request's, none of a request's that asks for no cut or is greedy, and
    it rides ``engine.step``'s attributes under tracing."""
    tracing.configure(enabled=True, service="t", sink=None)
    tracing.RECORDER.clear()

    async def main():
        engine = JaxEngine(EngineConfig(
            model=ModelConfig.tiny(), num_blocks=64, block_size=4,
            max_batch_size=4, max_context=128, prefill_chunk=32), seed=0)

        async def serve(request):
            async for _ in engine.generate(Context(request)):
                pass

        try:
            await asyncio.gather(*(serve(r) for r in requests))
            return engine.device_path_stats()
        finally:
            await engine.close()

    try:
        stats = run(main())
        steps = stats["engine_decode_steps_total"]
        counted = stats["engine_sampler_filter_steps_total"]
        assert steps > 0
        assert {"all": counted == steps, "none": counted == 0,
                "some": 0 < counted < steps}[filtered], (counted, steps)
        spans = tracing.RECORDER.spans(name=tracing.STEP_SPAN)
        assert counted == sum(
            s["attrs"].get("filter_steps", 0) for s in spans)
    finally:
        tracing.configure(enabled=False, sink=None)
        tracing.RECORDER.clear()
