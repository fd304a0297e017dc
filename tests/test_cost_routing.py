"""Transfer-cost-aware placement + ICI same-slice fast path (ISSUE 11).

Four families:
  * cost-model units — EWMA convergence, restart clamp, cold-start,
    stale-observation TTL, roofline-seed correction;
  * scheduler — predicted-TTFT candidate matrix (device-hot vs
    deeper-cold-tier, flipping with link speed), cold-start fallback,
    deterministic tie-breaks (the float-sum routing-flap fix), and the
    nearest-adequate-peer chooser;
  * ICI path — negotiation/fallback matrix ({same-slice, cross-slice}
    × {negotiated, legacy}) with bit-exact streams and per-segment
    device-residency asserts, the mover's program-count/geometry
    contract, and a mid-transfer kill on the ICI path redelivering
    exactly once over TCP;
  * fleet-cache device tier + weight pre-stage — KvPeerServer serving
    device-only chains via the bounded d2h export, and the PRESERVE
    pre-stage call path (stat + pre_stage_weights faultpoint).
"""

import asyncio

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.allocator import sequence_block_hashes
from dynamo_tpu.kv_router.costmodel import (
    TransferCostModel,
    predict_worker_ttft_ms,
)
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.scheduler import (
    KvScheduler,
    ProcessedEndpoints,
    SchedulerConfig,
    WorkerLoad,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import (
    Context,
    DistributedRuntime,
    LocalBus,
    LocalStore,
    collect,
)

# ---------------- cost model units ----------------


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_costmodel_ewma_converges_and_prices_transfers():
    m = TransferCostModel(block_bytes=1 << 17)
    for _ in range(30):
        m.observe("host", 20_000_000, 0.01)  # 2 GB/s steady
    g = m.link_gbps("host")
    assert g is not None and abs(g - 2.0) < 0.05
    # 20 MB at ~2 GB/s ≈ 10 ms (+ learned per-op latency floor ~0)
    ms = m.transfer_ms("host", 20_000_000)
    assert 8.0 < ms < 13.0
    assert m.counters()["kv_link_gbps"]["host"] == pytest.approx(g, rel=1e-6)
    assert m.counters()["kv_cost_obs_total"] == 30


def test_costmodel_restart_clamp_bounds_one_sample():
    m = TransferCostModel()
    for _ in range(10):
        m.observe("peer", 1_000_000_000, 1.0)  # 1 GB/s established
    # one absurd timer reading (1000x) must move the estimate by at
    # most alpha * SAMPLE_CLAMP, not repoint routing wholesale
    m.observe("peer", 1_000_000_000_000, 1.0)
    g = m.link_gbps("peer")
    assert g < 1.0 * (1 + 0.25 * TransferCostModel.SAMPLE_CLAMP)
    # ...and symmetric: an absurdly slow one-off
    m2 = TransferCostModel()
    for _ in range(10):
        m2.observe("peer", 1_000_000_000, 1.0)
    m2.observe("peer", 1_000_000, 1.0)  # 1000x slower
    assert m2.link_gbps("peer") > 1.0 / 2.0


def test_costmodel_stale_observation_ttl():
    clk = FakeClock()
    m = TransferCostModel(obs_ttl_s=60.0, clock=clk)
    m.observe("disk", 10_000_000, 0.1)
    assert m.link_gbps("disk") is not None
    clk.t += 61.0
    # aged out: the link stops informing routing AND drops out of the
    # advertised counters (the router's cold-start gate sees it)
    assert m.link_gbps("disk") is None
    assert "disk" not in m.counters()["kv_link_gbps"]
    # a fresh observation after the gap RESTARTS the estimate at the
    # new sample instead of averaging across two different worlds
    m.observe("disk", 100_000_000, 0.1)  # 1 GB/s now
    assert m.link_gbps("disk") == pytest.approx(1.0, rel=0.01)


def test_costmodel_prefill_cold_start_and_seed_correction():
    m = TransferCostModel()
    assert m.prefill_tok_s() is None  # cold: nothing observed
    for _ in range(5):
        m.observe_prefill(640, 0.1)  # 6400 tok/s observed
    assert m.prefill_tok_s() == pytest.approx(6400, rel=0.05)
    # roofline-seeded: correction folds observed/modeled, clamped to
    # corr_bounds exactly like the planner's CapacityModel
    s = TransferCostModel(prefill_seed_tok_s=1000.0)
    assert s.prefill_tok_s() == 1000.0  # seed serves before any obs
    for _ in range(50):
        s.observe_prefill(10_000, 0.1)  # 100x the seed
    assert s.prefill_tok_s() == pytest.approx(4000.0)  # clamp 4x


# ---------------- scheduler: predicted-TTFT matrix ----------------


def _calibrated_load(wid, link_gbps, tok_s=10_000.0, obs=50, **kw):
    kw.setdefault("total_slots", 8)
    kw.setdefault("kv_total_blocks", 100)
    return WorkerLoad(
        worker_id=wid, cost_obs=obs, link_gbps=dict(link_gbps),
        prefill_tok_s=tok_s, block_bytes=1 << 20, block_size=16, **kw,
    )


def test_predict_matrix_device_hot_vs_deep_tier_flips_with_link():
    # candidate DEEP holds all 20 blocks but only in host/disk tiers;
    # candidate HOT holds 12 hot on device. 1 MiB blocks.
    overlaps = OverlapScores(
        scores={1: 20, 2: 12}, total_blocks=20, device_scores={1: 0}
    )
    slow = _calibrated_load(1, {"host": 0.001, "disk": 0.001})
    hot = _calibrated_load(2, {"host": 1.0})
    p_slow = predict_worker_ttft_ms(slow, overlaps, 20)
    p_hot = predict_worker_ttft_ms(hot, overlaps, 20)
    # 20 MiB over 1 MB/s ≈ 21s of restore vs 8 blocks of prefill
    assert p_slow > p_hot
    s = KvScheduler()
    eps = ProcessedEndpoints([slow, hot])
    assert s.select_worker(eps, overlaps, 20) == 2
    assert s.last_predicted_ttft_ms == pytest.approx(p_hot)
    assert s.route_cost_decisions == 1
    s.request_finished(2)
    # fast restore link: the deeper chain wins (restore ≈ free)
    fast = _calibrated_load(1, {"host": 100.0, "disk": 100.0})
    eps = ProcessedEndpoints([fast, hot])
    assert s.select_worker(eps, overlaps, 20) == 1


def test_predict_queue_wait_term():
    overlaps = OverlapScores(scores={1: 20, 2: 12}, total_blocks=20,
                             device_scores={1: 0})
    # same fast links, but DEEP is a 1-slot engine with a request in
    # flight: the queue term prices one whole prompt ahead of us
    busy = _calibrated_load(1, {"host": 100.0}, active_requests=1,
                            total_slots=1)
    idle = _calibrated_load(2, {"host": 100.0})
    assert (
        predict_worker_ttft_ms(busy, overlaps, 20)
        > predict_worker_ttft_ms(idle, overlaps, 20)
    )
    # BELOW saturation the co-location share still spreads load: a
    # half-busy worker prices higher than an idle twin even though no
    # request queues — a cold-prompt burst must not pile onto whichever
    # candidate advertises marginally higher tok/s
    ov2 = OverlapScores(scores={}, total_blocks=20)
    half = _calibrated_load(1, {"host": 100.0}, active_requests=4)
    empty = _calibrated_load(2, {"host": 100.0})
    assert (
        predict_worker_ttft_ms(half, ov2, 20)
        > predict_worker_ttft_ms(empty, ov2, 20)
    )


def test_cost_cold_start_falls_back_to_overlap():
    # one calibrated + one cold candidate: the WHOLE decision must fall
    # back (mixed score scales are incomparable), and overlap scoring
    # then prefers the deeper chain
    overlaps = OverlapScores(scores={1: 20, 2: 12}, total_blocks=20,
                             device_scores={1: 0})
    calibrated = _calibrated_load(1, {"host": 0.001})
    cold = WorkerLoad(worker_id=2, kv_total_blocks=100, total_slots=8)
    s = KvScheduler()
    wid = s.select_worker(
        ProcessedEndpoints([calibrated, cold]), overlaps, 20
    )
    assert wid == 1  # deepest overlap, NOT the cost model's pick
    assert s.last_predicted_ttft_ms is None
    assert s.route_overlap_decisions == 1 and s.route_cost_decisions == 0


def test_tie_break_deterministic_across_scrape_order():
    """The PR 9 float-sum ordering flap: identical candidates must pick
    the same worker regardless of the loads list's order — cost mode,
    overlap mode, and the legacy config all tie-break on overlap then
    worker id."""
    overlaps = OverlapScores(scores={}, total_blocks=8)
    for cfg in (SchedulerConfig(), SchedulerConfig(cost_model=False)):
        picks = set()
        for order in ((1, 2), (2, 1)):
            s = KvScheduler(config=cfg)
            loads = [_calibrated_load(w, {"host": 1.0}) for w in order]
            picks.add(s.select_worker(
                ProcessedEndpoints(loads), overlaps, 8
            ))
        assert picks == {1}, f"{cfg.cost_model=} flapped: {picks}"
    # equal predicted TTFT but different overlap: overlap breaks first
    s = KvScheduler()
    ov = OverlapScores(scores={1: 2, 2: 2, 3: 4}, total_blocks=20,
                       device_scores={})
    loads = [_calibrated_load(w, {"host": 1e9}, tok_s=1e12)
             for w in (1, 2, 3)]
    assert s.select_worker(ProcessedEndpoints(loads), ov, 20) == 3


def test_choose_peer_nearest_adequate_not_deepest():
    """Peer chooser: a same-slice peer covering the chain beats a
    deeper peer across a slow wire; cold model keeps the PR 9 deepest
    rule; a pull pricier than recompute names no peer at all."""
    overlaps = OverlapScores(
        scores={10: 2, 20: 16, 30: 20}, total_blocks=20
    )
    # routed worker 10: ici fast (same slice as peer 20), peer link
    # slow; host link present — the chooser prices the pulled chain's
    # h2d landing leg too (same rule as predict)
    routed = _calibrated_load(
        10, {"ici": 10.0, "peer": 0.0005, "host": 1.0}, tok_s=1000.0)
    routed.slice_fp = "slice-A"
    near = _calibrated_load(20, {"host": 1.0})
    near.slice_fp = "slice-A"
    deep = _calibrated_load(30, {"host": 1.0})
    deep.slice_fp = "slice-B"
    eps = ProcessedEndpoints([routed, near, deep])
    s = KvScheduler()
    peer, blocks = s.choose_peer(eps, overlaps, 10, n_hint=20)
    # 20 is adequate (14 extra blocks over ICI ≈ free); 30 is deeper
    # but its 18 extra blocks over a 0.5 MB/s wire cost far more than
    # recomputing the 4-block difference
    assert (peer, blocks) == (20, 16)
    # cold model: deepest chain, exactly the PR 9 behavior
    s2 = KvScheduler(config=SchedulerConfig(cost_model=False))
    assert s2.choose_peer(eps, overlaps, 10, n_hint=20) == (30, 20)
    # every pull worse than recompute -> no peer named
    slow_everything = _calibrated_load(
        10, {"ici": 1e-9, "peer": 1e-9, "host": 1.0}, tok_s=1e12)
    eps3 = ProcessedEndpoints([slow_everything, near, deep])
    assert s.choose_peer(eps3, overlaps, 10, n_hint=20) == (None, 0)
    # no restore link observed: the landing leg can't be priced ->
    # deepest-chain fallback, not a mispriced wire-only net
    no_restore = _calibrated_load(10, {"ici": 10.0, "peer": 1.0})
    eps4 = ProcessedEndpoints([no_restore, near, deep])
    assert s.choose_peer(eps4, overlaps, 10, n_hint=20) == (30, 20)


def test_worker_load_from_stats_roundtrips_cost_fields():
    d = {
        "kv_active_blocks": 5, "kv_total_blocks": 50,
        "kv_cost_obs_total": 9, "kv_link_gbps": {"host": 2.5, "ici": 40.0},
        "kv_link_lat_ms": {"host": 0.7}, "kv_prefill_tok_s": 1234.5,
        "kv_block_bytes": 4096,
        "kv_block_size": 16, "kv_slice_fp": "abc123",
        "ici_handoffs": 3, "peer_serve_d2h_blocks_total": 7,
        "weight_prestage_requests": 2,
    }
    w = WorkerLoad.from_stats(42, d, ts=1.0)
    assert w.cost_obs == 9 and w.link_gbps == {"host": 2.5, "ici": 40.0}
    assert w.link_lat_ms == {"host": 0.7}
    assert w.prefill_tok_s == 1234.5 and w.block_bytes == 4096
    assert w.slice_fp == "abc123" and w.ici_handoffs == 3
    assert w.peer_serve_d2h_blocks == 7 and w.weight_prestage_requests == 2


def test_metrics_component_renders_cost_gauges():
    from dynamo_tpu.observability.component import MetricsComponent

    w = WorkerLoad(
        worker_id=7, cost_obs=11, link_gbps={"host": 2.0, "ici": 30.0},
        ici_handoffs=4, peer_serve_d2h_blocks=9, weight_prestage_requests=3,
    )
    mc = MetricsComponent.__new__(MetricsComponent)
    mc.prefix = "dynamo_tpu"
    mc.aggregator = type("A", (), {"endpoints": ProcessedEndpoints([w])})()
    mc.hit_events = 0
    mc.hit_isl_blocks = 0
    mc.hit_overlap_blocks = 0
    mc.planner_decision = None
    mc.planner_watermark = None
    mc.planner_decisions_total = 0
    mc.tracing = None
    mc.route_cost_events = 5
    mc.route_predicted_ttft_ms = 123.456
    text = mc.render()
    assert 'dynamo_tpu_kv_cost_obs_total{worker="7"} 11' in text
    assert 'dynamo_tpu_kv_link_gbps{worker="7",link="host"} 2.0' in text
    assert 'dynamo_tpu_kv_link_gbps{worker="7",link="ici"} 30.0' in text
    assert 'dynamo_tpu_ici_handoffs_total{worker="7"} 4' in text
    assert 'dynamo_tpu_peer_serve_d2h_blocks_total{worker="7"} 9' in text
    assert 'dynamo_tpu_weight_prestage_requests_total{worker="7"} 3' in text
    assert "dynamo_tpu_route_predicted_ttft_ms 123.456" in text


# ---------------- engines: shared fixtures ----------------

TINY = ModelConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.key(0))


def engine_cfg(**kw):
    base = dict(
        model=TINY, num_blocks=64, block_size=4, max_batch_size=4,
        max_context=128, prefill_chunk=8,
    )
    base.update(kw)
    return EngineConfig(**base)


def make_req(tokens, max_tokens=4):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, seed=0),
        eos_token_ids=[],
    )


def _disagg_stack(kv_ici=True, **decode_kw):
    from dynamo_tpu.disagg import (
        ConditionalDisaggRouter, DisaggConfig, DisaggEngine, LocalKvPipe,
        PrefillQueue, PrefillWorker,
    )

    async def build(drt):
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode = JaxEngine(engine_cfg(), params=PARAMS)
        prefill = JaxEngine(engine_cfg(), params=PARAMS)
        pipe = LocalKvPipe()
        worker = PrefillWorker(prefill, queue, local_pipe=pipe,
                               kv_ici=kv_ici)
        worker.start()
        eng = DisaggEngine(decode, router, queue, pipe, kv_ici=kv_ici,
                           **decode_kw)
        return router, queue, decode, prefill, pipe, worker, eng

    return build


async def _serve_and_reference(eng, prompt, max_tokens=4):
    outs = await collect(eng.generate(Context(make_req(prompt, max_tokens))))
    toks = [t for o in outs for t in o.token_ids]
    ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
    ref = await collect(
        ref_engine.generate(Context(make_req(prompt, max_tokens)))
    )
    await ref_engine.close()
    return toks, [t for o in ref for t in o.token_ids]


# ---------------- ICI negotiation / fallback matrix ----------------


def test_ici_same_slice_negotiated_device_path(run):
    """Same slice + both sides negotiated: the handoff takes the ICI
    path — per-segment device-resident arrays through the mover (no
    host staging), ici stats on both sides, stream bit-exact vs an
    aggregated reference, and the decode engine's cost model learns
    the ici link class from its own timings."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router, queue, decode, prefill, pipe, worker, eng = (
            await _disagg_stack()(drt)
        )
        seen = []
        orig_scatter = decode.scatter_remote_segment

        async def spy_scatter(handle, b0, k_data, v_data):
            seen.append((k_data, v_data))
            await orig_scatter(handle, b0, k_data, v_data)

        decode.scatter_remote_segment = spy_scatter
        prompt = list(range(10, 34))  # 24 tokens >> max_local 8
        toks, ref_toks = await _serve_and_reference(eng, prompt)
        assert toks == ref_toks
        assert eng.stats["streamed_deliveries"] == 1
        assert eng.stats["ici_handoffs"] == 1
        assert eng.stats["ici_segments"] >= 1
        assert worker.stats["kv_ici_sends"] == 1
        # per-segment: every scattered array stayed a device-resident
        # jax.Array through the mover — no host staging anywhere
        assert seen
        for k, v in seen:
            assert isinstance(k, jax.Array) and not isinstance(k, np.ndarray)
            assert isinstance(v, jax.Array) and not isinstance(v, np.ndarray)
        # the decode engine observed the ici link from its own timings
        assert decode.cost is not None
        assert decode.cost.link_gbps("ici") is not None
        assert "ici" in decode.load_metrics()["kv_link_gbps"]

        await worker.close()
        await decode.close()
        await prefill.close()
        await router.stop()
        await drt.shutdown()

    run(main())


@pytest.mark.parametrize("who", ["decode_legacy", "prefill_legacy",
                                 "cross_slice"])
def test_ici_fallback_matrix(run, who):
    """Negotiation absent on either side, or a slice-fingerprint
    mismatch, must fall back to the plain streamed path — zero ici
    stats, stream still bit-exact."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        build = _disagg_stack(
            kv_ici=(who != "decode_legacy" if who != "prefill_legacy"
                    else True)
        )
        router, queue, decode, prefill, pipe, worker, eng = await build(drt)
        if who == "prefill_legacy":
            worker.kv_ici = False
            eng.kv_ici = True
        elif who == "decode_legacy":
            worker.kv_ici = True
            eng.kv_ici = False
        elif who == "cross_slice":
            # the decode side advertises a DIFFERENT slice: negotiation
            # must fail at the prefill worker's fingerprint check
            orig_conn = eng._connection

            def patched():
                c = orig_conn()
                c["ici_fp"] = "ffffffffffffffff"
                return c

            eng._connection = patched
        prompt = list(range(50, 74))
        toks, ref_toks = await _serve_and_reference(eng, prompt)
        assert toks == ref_toks
        assert eng.stats["streamed_deliveries"] == 1
        assert eng.stats["ici_handoffs"] == 0
        assert eng.stats["ici_segments"] == 0
        assert worker.stats["kv_ici_sends"] == 0

        await worker.close()
        await decode.close()
        await prefill.close()
        await router.stop()
        await drt.shutdown()

    run(main())


def test_ici_layout_mismatch_falls_back(run):
    """A kv-head-layout mismatch keeps the regroup path in charge: the
    stream regroups per segment (PR 8 behavior), the ICI path stays
    out, and the stream is bit-exact."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router, queue, decode, prefill, pipe, worker, eng = (
            await _disagg_stack()(drt)
        )
        # the worker declares a foreign wire layout (same single-tp
        # geometry, different head ordering contract)
        worker.head_layout = "interleaved"
        prompt = list(range(30, 54))
        outs = await collect(eng.generate(Context(make_req(prompt))))
        toks = [t for o in outs for t in o.token_ids]
        assert toks  # served; regroup validity is covered by PR 8 tests
        assert eng.stats["streamed_deliveries"] == 1
        assert eng.stats["ici_handoffs"] == 0
        assert worker.stats["kv_ici_sends"] == 0

        await worker.close()
        await decode.close()
        await prefill.close()
        await router.stop()
        await drt.shutdown()

    run(main())


@pytest.mark.faultinject
def test_ici_kill_mid_transfer_redelivers_over_tcp_once(run):
    """A same-slice worker killed mid-ICI-stream (after segments
    already scattered) must look like a crash: no ack, and the
    redelivery — consumed by a surviving worker WITHOUT the in-process
    pipe — lands over real TCP into the same reservation, exactly
    once, bit-identical to an unkilled aggregated run."""
    from dynamo_tpu.disagg import (
        ConditionalDisaggRouter, DisaggConfig, DisaggEngine,
        KvTransferServer, LocalKvPipe, PrefillQueue, PrefillWorker,
    )
    from dynamo_tpu.resilience import faultpoints

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus, redeliver_after=3.0)
        decode = JaxEngine(engine_cfg(), params=PARAMS)
        prefill_a = JaxEngine(engine_cfg(), params=PARAMS)
        pipe = LocalKvPipe()
        tcp = KvTransferServer()
        await tcp.start()
        worker_a = PrefillWorker(
            prefill_a, queue, local_pipe=pipe, segment_blocks=2,
            kv_ici=True,
        )
        worker_a.start()
        # decode advertises BOTH channels: in-process pipe (+ici) for
        # same-slice workers, TCP connect-back for everyone else
        eng = DisaggEngine(decode, router, queue, pipe, kv_ici=True,
                           tcp_fallback=tcp)
        try:
            # warm-up round (compiles every jit in both paths' shared
            # module caches)
            warm = await collect(eng.generate(
                Context(make_req(list(range(60, 84)), max_tokens=2))
            ))
            assert [t for o in warm for t in o.token_ids]
            assert eng.stats["ici_handoffs"] == 1
            a_sends = worker_a.stats["kv_stream_sends"]

            # hit 1 = stream open, hits 2+ = per segment: the 3rd hit
            # kills worker A after an ICI segment already scattered
            faultpoints.arm("mid_kv_transfer", "kill", after=3, times=1)
            prompt = list(range(10, 34))
            gen = asyncio.ensure_future(
                collect(eng.generate(Context(make_req(prompt, max_tokens=6))))
            )
            # generous: under parallel box load the dequeue/compile path
            # to the 3rd hit stretches well past the quiet-box ~1s
            for _ in range(600):
                if worker_a._stop.is_set():
                    break
                await asyncio.sleep(0.05)
            assert worker_a._stop.is_set(), "fault point never fired"
            assert worker_a.stats["kv_stream_sends"] == a_sends
            # survivor WITHOUT the pipe: its only channel is TCP
            prefill_b = JaxEngine(engine_cfg(), params=PARAMS)
            worker_b = PrefillWorker(prefill_b, queue, layer_chunk=1,
                                     segment_blocks=2)
            worker_b.start()
            outs = await asyncio.wait_for(gen, 30)
            toks = [t for o in outs for t in o.token_ids]

            ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
            ref = await collect(ref_engine.generate(
                Context(make_req(prompt, max_tokens=6))
            ))
            assert toks == [t for o in ref for t in o.token_ids]
            # exactly once: warm-up + the measured request's TCP
            # redelivery. Worker B has no pipe, so its channel is real
            # TCP — but it shares this process's slice fingerprint, so
            # the channel-agnostic negotiation (ISSUE 12 satellite)
            # still stamps ici and the decode sink lands B's wire
            # segments through the compiled mover programs
            assert eng.stats["streamed_deliveries"] == 2
            assert worker_b.stats["kv_stream_sends"] >= 1
            assert worker_b.stats["kv_ici_sends"] == 1
            assert await queue.get_depth() == 0

            await worker_b.close()
            await prefill_b.close()
            await ref_engine.close()
        finally:
            faultpoints.reset()
            await worker_a.close()
            await tcp.close()
            await decode.close()
            await prefill_a.close()
            await router.stop()
            await drt.shutdown()

    run(main())


# ---------------- device-tier peer serving ----------------


def test_export_device_chain_bounded_and_nondestructive(run):
    async def main():
        eng = JaxEngine(engine_cfg(), params=PARAMS)
        prompt = list(range(100, 124))  # 6 blocks of 4
        await collect(eng.generate(Context(make_req(prompt))))
        pairs = sequence_block_hashes(prompt, 4)
        chain = [s for _l, s in pairs]
        served, k, v, _ks, _vs = await eng.export_device_chain(chain)
        assert len(served) >= 5 and served == chain[: len(served)]
        assert k.shape[2] == len(served)
        assert isinstance(k, np.ndarray)
        # bounded
        short, k2, _v2, _ks2, _vs2 = await eng.export_device_chain(chain, max_blocks=2)
        assert len(short) == 2 and k2.shape[2] == 2
        # non-destructive: the chain is still device-resident and a
        # prefix-hit serve afterwards still claims it (stats bump)
        assert all(eng.kv.allocator.has_hash(h) for h in served)
        hits0 = eng.stats["prefix_cache_hits_tokens"]
        await collect(eng.generate(Context(make_req(prompt))))
        assert eng.stats["prefix_cache_hits_tokens"] > hits0
        assert eng.stats["peer_serve_d2h_blocks"] == len(served) + 2
        # a miss at the head serves nothing
        none, nk, _nv, _nks, _nvs = await eng.export_device_chain([123456789])
        assert none == [] and nk is None
        await eng.close()

    run(main())


def test_peer_server_serves_device_only_chain(run):
    """Fleet prefix cache, device tier: a peer whose chain lives ONLY
    in HBM (host pool cold) answers a kv-peer-fetch via the bounded
    d2h export; the puller lands + promotes it and serves the prompt
    with prefix hits."""
    from dynamo_tpu.kv_router import KvPeerServer, KvPrefetchListener
    from dynamo_tpu.kv_router.protocols import (
        KV_PREFETCH_SUBJECT,
        KvPrefetchHint,
    )

    async def main():
        store, bus = LocalStore(), LocalBus()
        drt = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = drt.namespace("dynamo").component("w")
        peer_eng = JaxEngine(engine_cfg(host_cache_blocks=16), params=PARAMS)
        pull_eng = JaxEngine(engine_cfg(host_cache_blocks=16), params=PARAMS)
        server = await KvPeerServer(drt, comp, 1, peer_eng).start()
        listener = await KvPrefetchListener(drt, comp, 2, pull_eng).start()
        try:
            prompt = list(range(100, 124))
            await collect(peer_eng.generate(Context(make_req(prompt))))
            pairs = sequence_block_hashes(prompt, 4)
            chain = [s for _l, s in pairs]
            # the chain is device-resident on the peer, host pool EMPTY
            assert all(peer_eng.kv.allocator.has_hash(h) for h in chain[:5])
            assert len(peer_eng.offload.pool) == 0
            hint = KvPrefetchHint(
                2, [[l, s] for l, s in pairs[:5]],
                peer_worker_id=1, peer_blocks=5,
            )
            bus.publish(comp.event_subject(KV_PREFETCH_SUBJECT),
                        hint.to_bytes())
            for _ in range(300):
                if listener.blocks_prefetched >= 5:
                    break
                await asyncio.sleep(0.02)
            assert listener.blocks_prefetched >= 5
            assert peer_eng.stats["peer_serve_d2h_blocks"] >= 5
            assert pull_eng.offload.peer_pull_blocks_total >= 5
            # the pulled chain serves as ordinary prefix hits,
            # bit-exact vs the peer's own stream
            outs = await collect(pull_eng.generate(Context(make_req(prompt))))
            toks = [t for o in outs for t in o.token_ids]
            ref = await collect(peer_eng.generate(Context(make_req(prompt))))
            assert toks == [t for o in ref for t in o.token_ids]
        finally:
            await listener.close()
            await server.close()
            await peer_eng.close()
            await pull_eng.close()
            await drt.shutdown()

    run(main())


# ---------------- weight pre-stage (PRESERVE) ----------------


@pytest.mark.faultinject
def test_prefetch_hint_prestages_weights_and_survives_kill(run):
    """A hint naming a model drives the pre_stage_weights call path
    (stat end to end); a fault KILL inside the pre-stage must not cost
    the hint its KV restore (guarded separately)."""
    from dynamo_tpu.kv_router import KvPrefetchListener
    from dynamo_tpu.kv_router.protocols import (
        KV_PREFETCH_SUBJECT,
        KvPrefetchHint,
    )
    from dynamo_tpu.resilience import faultpoints

    class FakeEngine:
        def __init__(self):
            self.calls = []
            self.prestaged = []

        async def prefetch_hint(self, blocks):
            self.calls.append(blocks)
            return len(blocks)

        async def pre_stage_weights(self, model):
            self.prestaged.append(model)
            return False

    async def main():
        store, bus = LocalStore(), LocalBus()
        drt = await DistributedRuntime.from_settings(store=store, bus=bus)
        comp = drt.namespace("dyn").component("worker")
        eng = FakeEngine()
        listener = await KvPrefetchListener(drt, comp, 42, eng).start()
        subject = comp.event_subject(KV_PREFETCH_SUBJECT)
        try:
            bus.publish(subject, KvPrefetchHint(
                42, [[1, 2]], model="llama-tiny").to_bytes())
            # pre-stage is fire-and-forget (a slow stage must not delay
            # the restore): poll both the restore AND the stage counter
            for _ in range(100):
                if eng.calls and eng.prestaged:
                    break
                await asyncio.sleep(0.01)
            assert eng.prestaged == ["llama-tiny"]
            assert listener.prestage_requests == 1
            assert listener.prestage_failures == 0

            # kill inside the pre-stage: the KV restore still runs
            faultpoints.arm("pre_stage_weights", "kill", after=1, times=1)
            bus.publish(subject, KvPrefetchHint(
                42, [[3, 4], [5, 6]], model="llama-tiny").to_bytes())
            for _ in range(100):
                if len(eng.calls) >= 2 and listener.prestage_failures:
                    break
                await asyncio.sleep(0.01)
            assert eng.calls[-1] == [(3, 4), (5, 6)]
            assert listener.prestage_failures == 1
            assert eng.prestaged == ["llama-tiny"]  # kill pre-empted #2
            # hint without a model: no pre-stage at all
            bus.publish(subject, KvPrefetchHint(42, [[7, 8]]).to_bytes())
            for _ in range(100):
                if len(eng.calls) >= 3:
                    break
                await asyncio.sleep(0.01)
            assert listener.prestage_requests == 2
        finally:
            faultpoints.reset()
            await listener.close()
            await drt.shutdown()

    run(main())


def test_jax_engine_prestage_counts_into_load_metrics(run):
    async def main():
        eng = JaxEngine(engine_cfg(), params=PARAMS)
        assert await eng.pre_stage_weights("some-model") is False
        assert eng.load_metrics()["weight_prestage_requests"] == 1
        # the cost/geometry advertisement is present too
        lm = eng.load_metrics()
        assert lm["kv_block_bytes"] > 0
        assert lm["kv_block_size"] == 4
        assert lm["kv_slice_fp"]
        assert "kv_cost_obs_total" in lm
        await eng.close()

    run(main())


def test_ttft_cost_observations_bridge():
    """The PR 2 decomposition's transfer spans double as cost-model
    observations: cost_observations extracts (link, bytes, wall) from
    kv_send/kv_restore spans, skipping spans without a volume."""
    from dynamo_tpu.tracing import ttft

    spans = [
        {"name": "prefill.kv_send", "dur_ms": 5.0,
         "attrs": {"link": "dcn", "nbytes": 1000,
                   "hidden_ms": 3.0, "exposed_ms": 1.0}},
        {"name": "engine.kv_restore", "dur_ms": 2.0,
         "attrs": {"nbytes": 500, "hidden_ms": 2.0, "exposed_ms": 0.0}},
        {"name": "prefill.kv_send", "dur_ms": 5.0, "attrs": {}},
    ]
    obs = ttft.cost_observations(spans)
    assert ("dcn", 1000, 4.0) in obs
    assert ("host", 500, 2.0) in obs
    assert len(obs) == 2
    m = TransferCostModel()
    for link, nbytes, wall_ms in obs:
        m.observe(link, nbytes, wall_ms / 1e3)
    assert m.link_gbps("dcn") is not None
