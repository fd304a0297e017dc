"""Multi-LoRA serving lane (docs/multi_model.md): adapter registry,
adapter-aware fused batching, and the per-model routing/planner
dimension.

The load-bearing contracts:

  * **bit-exactness** — a mixed-adapter batch produces, per request,
    EXACTLY the tokens a solo run of that request produces (greedy and
    seeded), because the low-rank delta is row-local; the grouped
    ragged-dot lane is pinned bit-identical to the unrolled loop lane;
  * **prefix isolation** — a token-identical prompt under two models
    can never share a KV block: the model name salts the chain root,
    at the router/indexer AND at the engine's admission/restore path;
  * **back-compat** — a fleet that never configured ``--adapters`` is
    byte-identical to a pre-multi-model build: same block hashes, same
    program keys, no new per-model metric families.
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.adapters import (
    LORA_KEYS,
    AdapterRegistry,
    parse_adapter_specs,
)
from dynamo_tpu.engine.allocator import model_hash_salt, sequence_block_hashes
from dynamo_tpu.kv_router.scheduler import (
    AllWorkersBusy,
    KvScheduler,
    ProcessedEndpoints,
    WorkerLoad,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops.lora import lora_delta
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context

#: ONE shared tiny config + params for every engine in this module —
#: ModelConfig hashes by identity (jit static arg), so sharing the
#: instance is what lets the engines reuse each other's programs
TINY = ModelConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.key(3))
ADAPTERS = ("alice:4", "bob:8:7")


def make_engine(adapters=ADAPTERS, **kw):
    cfg = dict(
        model=TINY, num_blocks=64, block_size=16, max_batch_size=8,
        max_context=512, adapters=adapters,
        served_model_name="base" if adapters else "",
        # pin the fused step's prefill bucket to ONE value so the
        # reachable program grid is just the segment-count ladder —
        # keeps this module's first-touch XLA compile cost off tier-1's
        # clock without changing any stream (chunking is host-side)
        prefill_chunk=16,
    )
    cfg.update(kw)
    return JaxEngine(EngineConfig(**cfg), params=PARAMS)


def make_req(tokens, model="", max_tokens=8, seed=0, temperature=0.0):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=temperature,
                                         seed=seed),
        model=model,
        eos_token_ids=[],
    )


async def serve(engine, req):
    """-> (tokens, finish_reason); raises on ERROR finishes."""
    toks, fr = [], None
    async for o in engine.generate(Context(req)):
        fr = o.finish_reason or fr
        if o.finish_reason is FinishReason.ERROR:
            return toks, fr, o.text
        toks.extend(o.token_ids)
    return toks, fr, None


# ---------------- ops: the two delta lanes ----------------


def _reassociation_bound(x, a, b, ids):
    """How far two f32 evaluations of ``(x @ A) @ B`` may lie apart when
    they differ only in the ORDER of their sums (another dot kernel, a
    gemv where the other ran a gemm). A length-n f32 dot product in any
    order is within gamma_n * sum|x_i y_i| of the exact one, gamma_n =
    n u / (1 - n u), u = 2**-24 (Higham, Accuracy and Stability of
    Numerical Algorithms, eq. 3.5); chaining the E-long and the r-long
    dots gives gamma_E + gamma_r + gamma_E gamma_r <= gamma_{E+r}
    (lemma 3.3) on sum|x||A||B|, and two such evaluations differ by at
    most twice that. Per output element, [R, O]; zero on base rows."""
    x, a, b, ids = (np.asarray(v) for v in (x, a, b, ids))
    n, u = a.shape[1] + a.shape[2], 2.0 ** -24
    gamma = n * u / (1 - n * u)
    mass = np.stack([
        np.abs(x[i]).astype(np.float64) @ np.abs(a[k]) @ np.abs(b[k])
        if k >= 0 else np.zeros(b.shape[-1])
        for i, k in enumerate(ids)
    ])
    return 2 * gamma * mass


def test_lora_delta_grouped_matches_loop():
    """The grouped ragged-dot lane and the unrolled loop lane are the
    SAME function up to the order of their f32 sums (two dot kernels:
    ``_reassociation_bound``; this XLA CPU build differs in the last ulp)
    — and EXACTLY on rows with ids=-1 (base: exactly zero), with
    zero-padded adapter/rank bucket planes."""
    rng = np.random.RandomState(0)
    R, E, r, O, NA = 13, 32, 8, 24, 4  # odd row count: ragged groups
    x = jnp.asarray(rng.randn(R, E).astype(np.float32))
    a = jnp.asarray(rng.randn(NA, E, r).astype(np.float32))
    b = jnp.asarray(rng.randn(NA, r, O).astype(np.float32))
    # two live adapters, bucket-padded planes 2..3 zeroed, base rows mixed in
    a = a.at[2:].set(0.0)
    b = b.at[2:].set(0.0)
    ids = jnp.asarray(
        np.array([0, -1, 1, 1, -1, 0, 1, -1, -1, 0, 1, 0, -1], np.int32)
    )
    d_loop = lora_delta(x, a, b, ids, grouped=False)
    d_grp = lora_delta(x, a, b, ids, grouped=True)
    gap = np.abs(np.asarray(d_loop, np.float64) - np.asarray(d_grp))
    assert (gap <= _reassociation_bound(x, a, b, ids)).all(), gap.max()
    # base rows are EXACTLY zero, not merely small
    base_rows = np.asarray(d_grp)[np.asarray(ids) < 0]
    assert not base_rows.any()
    # every-row-base batch: zero everywhere on both lanes
    all_base = jnp.full((R,), -1, jnp.int32)
    assert not np.asarray(lora_delta(x, a, b, all_base, grouped=True)).any()
    assert not np.asarray(lora_delta(x, a, b, all_base, grouped=False)).any()


def test_lora_delta_solo_row_equals_mixed_row():
    """Row-locality, the property the engine's mixed batching rests on:
    a row's delta in a mixed-id batch is its delta in a solo batch — the
    same sums over the same operands; a [1, E] batch may take another
    dot kernel than a [6, E] one, so equal up to their order
    (``_reassociation_bound``), and base rows exactly zero."""
    rng = np.random.RandomState(1)
    E, r, O, NA = 16, 4, 16, 2
    a = jnp.asarray(rng.randn(NA, E, r).astype(np.float32))
    b = jnp.asarray(rng.randn(NA, r, O).astype(np.float32))
    rows = jnp.asarray(rng.randn(6, E).astype(np.float32))
    ids = jnp.asarray(np.array([1, 0, -1, 1, 0, 1], np.int32))
    bound = _reassociation_bound(rows, a, b, ids)
    for grouped in (False, True):
        mixed = np.asarray(lora_delta(rows, a, b, ids, grouped=grouped))
        for i in range(rows.shape[0]):
            solo = np.asarray(lora_delta(rows[i:i + 1], a, b, ids[i:i + 1],
                                         grouped=grouped))[0]
            gap = np.abs(mixed[i].astype(np.float64) - solo)
            assert (gap <= bound[i]).all(), (grouped, i, gap.max())
    assert not bound[2].any()  # the base row: both sides exactly zero


# ---------------- registry ----------------


def test_adapter_registry_specs_staging_and_lru():
    specs = parse_adapter_specs(("alice:4", "bob:8:7"))
    assert [s.name for s in specs] == ["alice", "bob"]
    reg = AdapterRegistry(specs, TINY, max_live=1)
    assert reg.is_known("alice") and reg.is_known("bob")
    assert not reg.is_known("charlie")
    slot_a, nbytes = reg.stage("alice")
    assert reg.is_staged("alice") and nbytes > 0
    assert reg.stats["adapters_staged_total"] == 1
    # 1-slot LRU: staging bob evicts alice
    reg.stage("bob")
    assert reg.is_staged("bob") and not reg.is_staged("alice")
    assert reg.stats["adapters_evicted_total"] == 1
    # a pinned (in-use) adapter may not be evicted
    with pytest.raises(RuntimeError):
        reg.stage("alice", in_use={"bob"})
    # the host-side stacks carry every projection's A/B pair
    w = reg.host_weights("alice")
    assert set(w) == set(LORA_KEYS)

    with pytest.raises(ValueError):
        parse_adapter_specs(("alice:4", "alice:8"))  # duplicate name
    with pytest.raises(ValueError):
        parse_adapter_specs(("bad::",))


# ---------------- engine: mixed vs solo bit-exactness ----------------


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_mixed_adapter_batch_bit_exact_vs_solo(run, temperature):
    """Concurrent base+alice+bob traffic through ONE engine produces the
    same per-request token streams as a fresh engine serving each
    request alone — greedy and seeded sampling. This is the fused
    batching contract: one shared base-GEMM pass + grouped low-rank
    deltas must cost zero output drift."""
    def reqs():
        # one request per model: a genuinely mixed 3-row batch while
        # keeping the first-touch segment-bucket compiles (the tier-1
        # clock's dominant cost here) to the small end of the ladder
        out = []
        for i, m in enumerate(["", "alice", "bob"]):
            toks = [(29 * i + 7 * j) % 480 + 7 for j in range(20)]
            out.append(make_req(toks, model=m, max_tokens=8,
                                temperature=temperature, seed=100 + i))
        return out

    async def main():
        mixed = make_engine()
        solo = make_engine()
        try:
            got = await asyncio.gather(*(serve(mixed, r) for r in reqs()))
            want = [await serve(solo, r) for r in reqs()]
            for i, ((gt, _gf, ge), (wt, _wf, we)) in enumerate(
                    zip(got, want)):
                assert ge is None and we is None, (ge, we)
                assert gt, f"request {i} produced no tokens"
                assert gt == wt, (
                    f"request {i} (model={reqs()[i].model!r}): mixed "
                    f"{gt} != solo {wt}")
        finally:
            await mixed.close()
            await solo.close()

    run(main())


def test_adapter_output_differs_from_base(run):
    """The deltas actually flow: the same greedy prompt under base,
    alice, and bob yields three distinct streams (otherwise every
    bit-exactness assertion above is vacuous)."""
    async def main():
        engine = make_engine()
        try:
            prompt = [(11 * j) % 480 + 7 for j in range(20)]
            streams = {}
            for m in ("", "alice", "bob"):
                toks, _fr, err = await serve(
                    engine, make_req(prompt, model=m, max_tokens=8))
                assert err is None
                streams[m] = toks
            assert streams[""] != streams["alice"]
            assert streams[""] != streams["bob"]
            assert streams["alice"] != streams["bob"]
        finally:
            await engine.close()

    run(main())


def test_unknown_adapter_clean_engine_error(run):
    """A name that is neither the served base nor a registered adapter
    fails with the SAME clean signature the frontend's 404 carries —
    never silently serving base-model tokens under a wrong name."""
    async def main():
        engine = make_engine()
        try:
            toks, fr, err = await serve(
                engine, make_req(range(100, 120), model="charlie"))
            assert fr is FinishReason.ERROR
            assert err == "unknown model 'charlie'"
            assert toks == []
            # the served base NAME resolves to the base lane (no error,
            # same stream as "")
            t1, _f, e1 = await serve(
                engine, make_req(range(100, 120), model="base"))
            t2, _f, e2 = await serve(
                engine, make_req(range(100, 120), model=""))
            assert e1 is None and e2 is None and t1 == t2
        finally:
            await engine.close()

    run(main())


# ---------------- prefix isolation ----------------


def test_model_salt_namespaces_block_hashes():
    """Indexer-level isolation: the model name salts the chain root, so
    token-identical prompts under different models share ZERO hashes —
    cross-model overlap scoring is structurally impossible. The base
    model ("" / None salt) keeps the exact pre-multi-model bytes."""
    toks = list(range(100, 164))
    base = sequence_block_hashes(toks, 16)
    assert base == sequence_block_hashes(toks, 16, salt=None)
    assert model_hash_salt("") is None and model_hash_salt(None) is None
    alice = sequence_block_hashes(toks, 16, salt=model_hash_salt("alice"))
    bob = sequence_block_hashes(toks, 16, salt=model_hash_salt("bob"))
    for other in (alice, bob):
        assert len(other) == len(base)
        assert not ({s for _l, s in base} & {s for _l, s in other})
    assert not ({s for _l, s in alice} & {s for _l, s in bob})
    # deterministic across processes (the salt is content-derived)
    assert model_hash_salt("alice") == model_hash_salt("alice")


def test_engine_prefix_isolation_across_models(run):
    """Engine admission/restore path: a token-identical prompt under
    another model must NOT reuse the first model's committed blocks,
    while a same-model repeat MUST."""
    async def main():
        engine = make_engine()
        try:
            prompt = [(13 * j) % 480 + 7 for j in range(48)]  # 3 blocks
            await serve(engine, make_req(prompt, model="", max_tokens=2))
            h0 = engine.stats["prefix_cache_hits_tokens"]
            # cross-model: zero reuse of base's blocks
            await serve(engine,
                        make_req(prompt, model="alice", max_tokens=2))
            assert engine.stats["prefix_cache_hits_tokens"] == h0, (
                "alice reused base-model KV blocks")
            # same-model repeat: reuse works inside the namespace
            await serve(engine,
                        make_req(prompt, model="alice", max_tokens=2))
            assert engine.stats["prefix_cache_hits_tokens"] > h0, (
                "within-model prefix reuse broken by the salt"
            )
        finally:
            await engine.close()

    run(main())


# ---------------- prestage ----------------


def test_pre_stage_weights_hides_cold_load(run):
    """With a 1-slot device stack: an unhinted request stages inline
    (cold load on its TTFT); after ``pre_stage_weights`` the request
    finds the adapter resident — counted as a prestage hit, zero
    staging work on the request path."""
    async def main():
        engine = make_engine(max_live_adapters=1)
        try:
            reg = engine.adapters
            await serve(engine,
                        make_req(range(20, 40), model="alice",
                                 max_tokens=2))
            staged0 = reg.stats["adapters_staged_total"]
            # cold: bob's stage rides the request
            await serve(engine,
                        make_req(range(50, 70), model="bob", max_tokens=2))
            assert reg.stats["adapters_staged_total"] == staged0 + 1
            # hint: stage alice back BEFORE its request
            assert await engine.pre_stage_weights("alice") is True
            staged1 = reg.stats["adapters_staged_total"]
            hits0 = engine.stats["weight_prestage_hits"]
            await serve(engine,
                        make_req(range(80, 100), model="alice",
                                 max_tokens=2))
            assert reg.stats["adapters_staged_total"] == staged1, (
                "hinted request still staged inline")
            assert engine.stats["weight_prestage_hits"] == hits0 + 1
            # already-staged hint is a no-op (LRU touch only)
            assert await engine.pre_stage_weights("alice") is False
            # base / unknown names never stage
            assert await engine.pre_stage_weights("base") is False
            lm = engine.load_metrics()
            assert lm["weight_prestage_bytes"] > 0
            assert lm["weight_prestage_hits"] >= 1
            assert lm["served_models"] == ["base", "alice", "bob"]
        finally:
            await engine.close()

    run(main())


# ---------------- control plane ----------------


def _load(worker_id, models=(), **kw):
    d = dict(kv_active_blocks=0, kv_total_blocks=64,
             active_requests=0, total_slots=8, waiting=0,
             served_models=list(models))
    d.update(kw)
    return WorkerLoad.from_stats(worker_id, d)


def test_select_worker_filters_on_model():
    sched = KvScheduler(None, None)

    class _NoOverlap:
        scores = {}

        def device(self, wid):
            return 0

    eps = ProcessedEndpoints([
        _load(1, models=("base", "alice")),
        _load(2, models=("base", "bob")),
        _load(3, models=()),        # legacy: no advertisement at all
        _load(4, models=("",)),     # legacy: unnamed single-model engine
    ])
    ov = _NoOverlap()
    # base traffic ("" and the served base name) reaches everyone
    assert sched.select_worker(eps, ov, 4, model="") in (1, 2, 3, 4)
    # adapter traffic only reaches advertisers (+ legacy wildcards)
    for _ in range(8):
        wid = sched.select_worker(ProcessedEndpoints([
            _load(1, models=("base", "alice")),
            _load(2, models=("base", "bob")),
        ]), ov, 4, model="alice")
        assert wid == 1
    # wildcard workers stay eligible for any name (pre-multi-model
    # producers must not be stranded by the upgrade)
    assert _load(3, models=()).serves("alice")
    assert _load(4, models=("",)).serves("alice")
    # nobody serves it: a deployment gap, loudly distinct from pressure
    with pytest.raises(AllWorkersBusy, match="no worker serves model"):
        sched.select_worker(ProcessedEndpoints([
            _load(1, models=("base",)),
        ]), ov, 4, model="charlie")


def test_worker_load_scrapes_multi_model_stats():
    from dynamo_tpu.observability.hist import MS_BUCKETS, Histogram

    h = Histogram(MS_BUCKETS)
    h.observe(12.0)
    w = WorkerLoad.from_stats(9, {
        "kv_active_blocks": 1, "kv_total_blocks": 64,
        "active_requests": 0, "total_slots": 8, "waiting": 0,
        "served_models": ["base", "alice"],
        "weight_prestage_bytes": 4096, "weight_prestage_hits": 3,
        "hist_ttft_ms": {"alice": h.to_vec()},
    })
    assert w.models == ("base", "alice")
    assert w.prestage_bytes == 4096 and w.prestage_hits == 3
    got = Histogram.from_vec(w.model_hists["alice"])
    assert got is not None and got.count == 1


def test_metrics_render_multi_model_families():
    """serves_model rows, prestage counters, and per-model TTFT
    histogram families (model as a LABEL) render for multi-model
    workers — and NONE of the per-model families appear for a legacy
    single-model worker (unchanged metric surface on upgrade)."""
    from dynamo_tpu.observability import MetricsComponent
    from dynamo_tpu.observability.hist import MS_BUCKETS, Histogram

    def render(loads):
        mc = MetricsComponent.__new__(MetricsComponent)
        mc.prefix = "dynamo_tpu"
        mc.aggregator = type(
            "A", (), {"endpoints": ProcessedEndpoints(loads)})()
        mc.hit_events = mc.hit_isl_blocks = mc.hit_overlap_blocks = 0
        mc.planner_decision = mc.planner_watermark = None
        mc.planner_decisions_total = 0
        mc.tracing = None
        return mc.render()

    h = Histogram(MS_BUCKETS)
    h.observe(25.0)
    multi = _load(1, models=("base", "alice"),
                  weight_prestage_bytes=86016, weight_prestage_hits=2,
                  hist_ttft_ms={"": h.to_vec(), "alice": h.to_vec()})
    text = render([multi])
    assert 'serves_model{worker="1",model="base"} 1' in text
    assert 'serves_model{worker="1",model="alice"} 1' in text
    assert "weight_prestage_bytes_total" in text
    assert "weight_prestage_hits_total" in text
    assert 'worker_ttft_ms_bucket{worker="1",model="alice"' in text
    assert 'fleet_ttft_ms_bucket{model="alice"' in text
    # legacy worker: no model label anywhere, no per-model families
    legacy = render([_load(2, models=("",),
                           hist_ttft_ms={"": h.to_vec()})])
    assert "serves_model" not in legacy
    assert "worker_ttft_ms" not in legacy
    assert "fleet_ttft_ms" not in legacy
    assert 'model="' not in legacy


def test_admission_model_slo_classes():
    from dynamo_tpu.planner.admission import AdmissionGate

    gate = AdmissionGate(rate_req_s=100.0,
                         model_classes={"alice": "batch",
                                        "ghost": "nosuchclass"})
    # model mapping routes to the class pool
    assert gate.classify(model="alice") == "batch"
    # explicit annotation outranks the model mapping
    assert gate.classify(["slo:interactive"], model="alice") == "interactive"
    # unmapped / unknown models and bogus classes fall back to default
    assert gate.classify(model="bob") == "interactive"
    assert gate.classify(model="ghost") == "interactive"
    assert gate.classify() == "interactive"


# ---------------- HTTP surface ----------------


def test_v1_models_lists_adapters_and_unknown_404_parity(run):
    """/v1/models enumerates base AND adapters; an unknown adapter name
    gets the same clean 404 body as an unknown model."""
    from tests.test_http_service import http_request
    from dynamo_tpu.http.service import HttpService, ModelManager
    from dynamo_tpu.llm.openai_engine import OpenAIWorkerEngine
    from dynamo_tpu.llm.tokenizer import ByteTokenizer
    from tests.test_llm_protocols import TokenEchoEngine

    async def main():
        tok = ByteTokenizer()
        engine = OpenAIWorkerEngine(tok, TokenEchoEngine())
        manager = ModelManager()
        # dynamo_run registers the base and each adapter as chat +
        # completion entries against the SAME engine lane
        for name in ("base", "alice", "bob"):
            manager.add_chat_model(name, engine)
            manager.add_completion_model(name, engine)
        svc = HttpService(manager, host="127.0.0.1", port=0)
        await svc.start()
        try:
            status, _, body = await http_request(svc.port, "GET",
                                                 "/v1/models")
            assert status == 200
            ids = {m["id"] for m in json.loads(body)["data"]}
            assert {"base", "alice", "bob"} <= ids

            async def chat_404(model):
                payload = json.dumps({
                    "model": model,
                    "messages": [{"role": "user", "content": "hi"}],
                }).encode()
                st, _, b = await http_request(
                    svc.port, "POST", "/v1/chat/completions", payload,
                    {"Content-Type": "application/json"})
                return st, json.loads(b)

            st1, b1 = await chat_404("charlie")   # unknown adapter
            st2, b2 = await chat_404("no-such")   # unknown model
            assert st1 == st2 == 404
            # identical body shape and code; only the name differs
            assert b1.keys() == b2.keys()

            def scrub(d):
                return json.dumps(d).replace("charlie", "X").replace(
                    "no-such", "X")

            assert scrub(b1) == scrub(b2)
            # registered adapter names do NOT 404
            st3, b3 = await chat_404("alice")
            assert st3 == 200, b3
        finally:
            await svc.close()

    run(main())


# ---------------- single-model back-compat ----------------


def test_single_model_fleet_unchanged(run):
    """No ``--adapters``: any model name passes through untouched (the
    legacy contract — the frontend already checked registration), block
    hashes carry no salt, program compile keys carry no lora suffix,
    and load_metrics advertises the legacy wildcard."""
    async def main():
        engine = make_engine(adapters=())
        try:
            assert engine.adapters is None
            assert engine._lora_key() == ()
            # a named request on a single-model fleet serves normally
            t1, fr, err = await serve(
                engine, make_req(range(100, 120), model="whatever"))
            assert err is None and t1
            t2, _fr, _e = await serve(
                engine, make_req(range(100, 120), model=""))
            assert t1 == t2
            lm = engine.load_metrics()
            assert lm["served_models"] == [""]
            assert lm["weight_prestage_bytes"] == 0
            assert lm["weight_prestage_hits"] == 0
            # the wildcard advertisement keeps the worker eligible for
            # ANY name at the router
            w = WorkerLoad.from_stats(1, lm)
            assert w.serves("whatever") and w.serves("")
        finally:
            await engine.close()

    run(main())
