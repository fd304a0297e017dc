"""Disaggregated prefill/decode tests (ref docs/disagg_serving.md).

End-to-end on the CPU mesh with tiny models: conditional routing,
prefill queue semantics, the KV transfer plane (local pipe + TCP), and
token-level equivalence between disaggregated and aggregated serving.
"""

import asyncio

import numpy as np
import pytest

import jax

from dynamo_tpu.disagg import (
    ConditionalDisaggRouter,
    DisaggConfig,
    DisaggEngine,
    KvTransferServer,
    LocalKvPipe,
    PrefillQueue,
    PrefillWorker,
    RemotePrefillRequest,
)
from dynamo_tpu.disagg.transfer import KvStreamSender, send_kv_blocks
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    FinishReason,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.runtime import Context, DistributedRuntime, collect

MODEL_CFG = ModelConfig.tiny()
PARAMS = llama.init_params(MODEL_CFG, jax.random.key(7))


def engine_cfg(**kw):
    kw.setdefault("model", MODEL_CFG)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_context", 128)
    kw.setdefault("prefill_chunk", 32)
    return EngineConfig(**kw)


def make_req(tokens, max_tokens=8):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens),
        sampling_options=SamplingOptions(temperature=0.0, seed=0),
        eos_token_ids=[511],
    )


# ---------------- policy ----------------


def test_disagg_config_roundtrip():
    cfg = DisaggConfig(max_local_prefill_length=100, max_prefill_queue_size=4)
    again = DisaggConfig.from_json(cfg.to_json())
    assert again == cfg


def test_disagg_decision_logic(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        r = ConditionalDisaggRouter(
            drt, "dynamo", "m", DisaggConfig(max_local_prefill_length=512)
        )
        await r.start()
        # short prompt local; long remote; cached prefix subtracts
        assert not r.prefill_remote(100, 0, 0)
        assert r.prefill_remote(1000, 0, 0)
        assert not r.prefill_remote(1000, 600, 0)
        # queue-depth cutoff
        await r.update(DisaggConfig(max_local_prefill_length=512, max_prefill_queue_size=2))
        assert not r.prefill_remote(1000, 0, 5)
        assert r.prefill_remote(1000, 0, 1)
        await r.stop()
        await drt.shutdown()

    run(main())


def test_disagg_config_hot_reload(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        r = ConditionalDisaggRouter(drt, "dynamo", "m")
        await r.start()
        # a second router (ops CLI) updates the store; first sees it
        r2 = ConditionalDisaggRouter(drt, "dynamo", "m")
        await r2.start()
        await r2.update(DisaggConfig(max_local_prefill_length=7777))
        for _ in range(50):
            if r.config.max_local_prefill_length == 7777:
                break
            await asyncio.sleep(0.01)
        assert r.config.max_local_prefill_length == 7777
        await r.stop()
        await r2.stop()
        await drt.shutdown()

    run(main())


# ---------------- queue ----------------


def test_prefill_queue_ack_nack(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        q = PrefillQueue(drt.bus, redeliver_after=0.2)
        rpr = RemotePrefillRequest(
            request_id="r1", request=make_req([1, 2, 3]).to_dict(),
            skip_blocks=0, connection={"local": True},
        )
        await q.enqueue(rpr)
        assert q.depth == 1
        item_id, got = await q.dequeue(timeout=1.0)
        assert got.request_id == "r1" and got.skip_blocks == 0
        # nack -> redelivered
        await q.nack(item_id)
        item_id2, got2 = await q.dequeue(timeout=1.0)
        assert got2.request_id == "r1"
        assert await q.ack(item_id2)
        assert q.depth == 0
        # visibility timeout redelivery without ack
        await q.enqueue(rpr)
        iid, _ = await q.dequeue(timeout=1.0)
        await asyncio.sleep(0.3)
        redelivered = await q.dequeue(timeout=1.0)
        assert redelivered is not None
        await q.ack(redelivered[0])
        await drt.shutdown()

    run(main())


# ---------------- transfer plane ----------------


def test_kv_transfer_tcp_roundtrip(run):
    async def main():
        srv = KvTransferServer()
        await srv.start()
        fut = srv.expect("req-9")
        k = np.random.default_rng(0).standard_normal((4, 2, 3, 4, 8)).astype(np.float32)
        v = np.random.default_rng(1).standard_normal((4, 2, 3, 4, 8)).astype(np.float32)
        await send_kv_blocks(srv.address, "req-9", 42, k, v, layer_chunk=3)
        d = await asyncio.wait_for(fut, 5)
        assert d.first_token == 42 and d.n_blocks == 3
        np.testing.assert_array_equal(d.k_data, k)
        np.testing.assert_array_equal(d.v_data, v)
        # error notification path
        fut2 = srv.expect("req-10")
        await send_kv_blocks(srv.address, "req-10", -1, None, None, error="boom")
        d2 = await asyncio.wait_for(fut2, 5)
        assert d2.error == "boom" and d2.n_blocks == 0
        await srv.close()

    run(main())


def test_kv_stream_tcp_roundtrip(run):
    """Streamed protocol over real TCP with NO registered sink: segments
    buffer on the receiver and the delivery is bit-identical to the bulk
    path's full stack. Headers carry extra unknown keys (forward-compat
    contract: a newer peer's fields must be ignored, not fatal)."""

    async def main():
        srv = KvTransferServer()
        await srv.start()
        fut = srv.expect("req-s1")
        rng = np.random.default_rng(2)
        k = rng.standard_normal((4, 2, 5, 4, 8)).astype(np.float32)
        v = rng.standard_normal((4, 2, 5, 4, 8)).astype(np.float32)
        head = {
            "request_id": "req-s1", "stream": 1, "n_blocks": 5,
            "shape": [4, 2, 5, 4, 8], "v_shape": [4, 2, 5, 4, 8],
            "dtype": "float32", "layer_chunk": 3,
            "head_layout": "blocked", "src_tp": 1,
            "future_knob": {"x": 1},  # unknown key: must be ignored
        }
        sender = await KvStreamSender.open(srv.address, "req-s1", head)
        # two uneven segments, shipped out of completion order of sizes
        await sender.send_segment(0, k[:, :, :2], v[:, :, :2])
        await sender.send_segment(2, k[:, :, 2:], v[:, :, 2:])
        await sender.finish(77, {"logprob": -0.5})
        d = await asyncio.wait_for(fut, 5)
        assert d.first_token == 77 and d.n_blocks == 5 and not d.streamed
        assert d.first_lp == {"logprob": -0.5}
        np.testing.assert_array_equal(d.k_data, k)
        np.testing.assert_array_equal(d.v_data, v)

        # zero-block stream (decode's prefix cache covered every shipped
        # block): header + fin only, no data frames
        fut0 = srv.expect("req-s0")
        head0 = dict(head, request_id="req-s0", n_blocks=0,
                     shape=[4, 2, 0, 4, 8], v_shape=[4, 2, 0, 4, 8])
        sender0 = await KvStreamSender.open(srv.address, "req-s0", head0)
        await sender0.finish(12)
        d0 = await asyncio.wait_for(fut0, 5)
        assert d0.first_token == 12 and d0.n_blocks == 0
        assert d0.k_data is None and d0.error is None
        await srv.close()

    run(main())


def test_kv_stream_truncation_leaves_future_pending(run):
    """A sender dying mid-stream must NOT resolve the delivery future —
    the pending future is what the queue's redelivery retries against
    (resilience contract: no ack, no delivery, try again)."""

    async def main():
        srv = KvTransferServer()
        await srv.start()
        fut = srv.expect("req-t1")
        k = np.zeros((2, 2, 4, 4, 8), np.float32)
        head = {
            "request_id": "req-t1", "stream": 1, "n_blocks": 4,
            "shape": [2, 2, 4, 4, 8], "v_shape": [2, 2, 4, 4, 8],
            "dtype": "float32", "layer_chunk": 1,
            "head_layout": "blocked", "src_tp": 1,
        }
        sender = await KvStreamSender.open(srv.address, "req-t1", head)
        await sender.send_segment(0, k[:, :, :2], k[:, :, :2])
        await sender.aclose()  # dies before fin
        await asyncio.sleep(0.1)
        assert not fut.done()
        # a second (redelivered) attempt completes the SAME future
        sender2 = await KvStreamSender.open(srv.address, "req-t1", head)
        await sender2.send_segment(0, k[:, :, :2], k[:, :, :2])
        await sender2.send_segment(2, k[:, :, 2:], k[:, :, 2:])
        await sender2.finish(5)
        d = await asyncio.wait_for(fut, 5)
        assert d.first_token == 5 and d.n_blocks == 4
        await srv.close()

    run(main())


# ---------------- end-to-end ----------------


def _disagg_stack():
    """decode engine + prefill engine with shared weights."""
    decode = JaxEngine(engine_cfg(), params=PARAMS)
    prefill = JaxEngine(engine_cfg(), params=PARAMS)
    return decode, prefill


@pytest.mark.parametrize("kv_stream", [True, False])
@pytest.mark.parametrize("mode", ["local_pipe", "tcp"])
def test_disagg_end_to_end_matches_aggregated(run, mode, kv_stream):
    """The full handoff matrix: {local pipe, TCP} x {streamed, bulk} all
    land a first token + decode continuation bit-identical to aggregated
    serving, and each flavor is asserted to have actually engaged."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode, prefill = _disagg_stack()
        if mode == "local_pipe":
            transfer = LocalKvPipe()
            worker = PrefillWorker(
                prefill, queue, local_pipe=transfer, kv_stream=kv_stream
            )
        else:
            transfer = KvTransferServer()
            await transfer.start()
            worker = PrefillWorker(
                prefill, queue, layer_chunk=1, kv_stream=kv_stream
            )
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer, kv_stream=kv_stream)

        prompt = list(range(10, 34))  # 24 tokens >> max_local 8 -> remote
        outs = await collect(eng.generate(Context(make_req(prompt, max_tokens=6))))
        toks = [t for o in outs for t in o.token_ids]
        assert outs[-1].finish_reason in (FinishReason.LENGTH, FinishReason.EOS)
        assert eng.stats["remote_prefills"] == 1
        assert worker.stats["prefills_total"] == 1
        if kv_stream:
            assert eng.stats["streamed_deliveries"] == 1
            assert worker.stats["kv_stream_sends"] == 1
            assert worker.stats["kv_stream_segments"] >= 1
        else:
            assert eng.stats["bulk_deliveries"] == 1
            assert worker.stats["kv_bulk_sends"] == 1

        # aggregated reference run with the same weights must match exactly
        ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
        ref = await collect(ref_engine.generate(Context(make_req(prompt, max_tokens=6))))
        ref_toks = [t for o in ref for t in o.token_ids]
        assert toks == ref_toks

        # short prompt stays local
        outs2 = await collect(eng.generate(Context(make_req([1, 2, 3], max_tokens=3))))
        assert eng.stats["local_prefills"] == 1
        assert [t for o in outs2 for t in o.token_ids]

        # decode-side prefix cache: same long prompt again -> skip_blocks > 0,
        # decision sees the cached prefix and stays local now
        outs3 = await collect(eng.generate(Context(make_req(prompt, max_tokens=6))))
        toks3 = [t for o in outs3 for t in o.token_ids]
        assert toks3 == ref_toks
        assert eng.stats["local_prefills"] == 2  # cached prefix -> local

        await worker.close()
        if mode == "tcp":
            await transfer.close()
        await decode.close()
        await prefill.close()
        await router.stop()
        await drt.shutdown()

    run(main())


def test_disagg_mla_kv_transfer_matches_aggregated(run):
    """Disagg on the MLA family: the KV transfer plane must carry the
    latent cache's ASYMMETRIC k/v shapes (c_kv vs k_pe) over the TCP
    path and land a decode stream equal to aggregated serving."""

    async def main():
        mla_cfg = ModelConfig.tiny_mla()
        mla_params = llama.init_params(mla_cfg, jax.random.key(9))
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny-mla", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode = JaxEngine(engine_cfg(model=mla_cfg), params=mla_params)
        prefill = JaxEngine(engine_cfg(model=mla_cfg), params=mla_params)
        assert decode.k_cache.shape[-1] != decode.v_cache.shape[-1]
        transfer = KvTransferServer()
        await transfer.start()
        worker = PrefillWorker(prefill, queue, layer_chunk=1)
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer)

        prompt = list(range(10, 34))  # 24 tokens >> max_local 8 -> remote
        outs = await collect(eng.generate(Context(make_req(prompt, max_tokens=6))))
        toks = [t for o in outs for t in o.token_ids]
        assert eng.stats["remote_prefills"] == 1
        # the default handoff is STREAMED: the asymmetric v_shape rode
        # the per-segment frames, not the bulk stack
        assert eng.stats["streamed_deliveries"] == 1

        ref_engine = JaxEngine(engine_cfg(model=mla_cfg), params=mla_params)
        ref = await collect(ref_engine.generate(Context(make_req(prompt, max_tokens=6))))
        assert toks == [t for o in ref for t in o.token_ids]

        await worker.close()
        await transfer.close()
        await decode.close()
        await prefill.close()
        await ref_engine.close()
        await router.stop()
        await drt.shutdown()

    run(main())


def test_disagg_first_token_carries_logprobs(run):
    """Regression (advisor r2 low): a logprobs request served via remote
    prefill must emit a logprob entry for the FIRST generated token too —
    the entry is computed on the prefill worker (where the logits are)
    and rides the KV transfer. Entries must match the aggregated run."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode, prefill = _disagg_stack()
        transfer = KvTransferServer()
        await transfer.start()
        worker = PrefillWorker(prefill, queue, layer_chunk=1)
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer)

        def lp_req(max_tokens=5):
            return PreprocessedRequest(
                token_ids=list(range(10, 34)),  # 24 >> max_local 8 -> remote
                stop_conditions=StopConditions(max_tokens=max_tokens),
                sampling_options=SamplingOptions(
                    temperature=0.0, seed=0, logprobs=3
                ),
                eos_token_ids=[511],
            )

        outs = await collect(eng.generate(Context(lp_req())))
        assert eng.stats["remote_prefills"] == 1
        toks = [t for o in outs for t in o.token_ids]
        entries = [e for o in outs for e in (o.logprobs or [])]
        # one entry per emitted token, INCLUDING the prefill-sampled first
        assert len(entries) == len(toks), (len(entries), len(toks))
        assert all(len(e["top"]) == 3 for e in entries)

        ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
        ref = await collect(ref_engine.generate(Context(lp_req())))
        ref_entries = [e for o in ref for e in (o.logprobs or [])]
        assert len(ref_entries) == len(entries)
        np.testing.assert_allclose(
            [e["logprob"] for e in entries],
            [e["logprob"] for e in ref_entries],
            rtol=1e-4, atol=1e-4,
        )
        assert [[t[0] for t in e["top"]] for e in entries] == [
            [t[0] for t in e["top"]] for e in ref_entries
        ]

        await worker.close()
        await transfer.close()
        await decode.close()
        await prefill.close()
        await ref_engine.close()
        await router.stop()
        await drt.shutdown()

    run(main())


@pytest.mark.parametrize("kv_stream", [True, False])
def test_disagg_local_pipe_stays_on_device(run, kv_stream):
    """VERDICT round-1 missing #3: the in-process pipe must hand over
    device-resident jax.Arrays — no numpy hop, so same-slice disagg never
    pays d2h + h2d. (The TCP path still serializes, by design.) Both
    handoff flavors: the bulk delivery's full stack, and every SEGMENT
    of the streamed handoff landing through the decode scatter sink."""

    async def main():
        import jax as _jax

        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode, prefill = _disagg_stack()
        transfer = LocalKvPipe()
        seen = []
        orig_deliver = transfer.deliver
        orig_scatter = decode.scatter_remote_segment

        async def spy_deliver(request_id, first_token, k_data, v_data, **kw):
            seen.append((k_data, v_data))
            await orig_deliver(request_id, first_token, k_data, v_data, **kw)

        async def spy_scatter(handle, b0, k_data, v_data):
            seen.append((k_data, v_data))
            await orig_scatter(handle, b0, k_data, v_data)

        transfer.deliver = spy_deliver
        decode.scatter_remote_segment = spy_scatter
        worker = PrefillWorker(
            prefill, queue, local_pipe=transfer, kv_stream=kv_stream
        )
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer, kv_stream=kv_stream)
        prompt = list(range(50, 74))
        outs = await collect(eng.generate(Context(make_req(prompt, max_tokens=4))))
        assert [t for o in outs for t in o.token_ids]
        if kv_stream:
            assert eng.stats["streamed_deliveries"] == 1
            assert len(seen) >= 1  # one scatter per streamed segment
        else:
            assert eng.stats["bulk_deliveries"] == 1
            assert len(seen) == 1
        for k, v in seen:
            assert isinstance(k, _jax.Array), type(k)
            assert isinstance(v, _jax.Array)
            assert not isinstance(k, np.ndarray)

        await worker.close()
        await decode.close()
        await prefill.close()
        await router.stop()
        await drt.shutdown()

    run(main())


@pytest.mark.faultinject
def test_disagg_streamed_kill_mid_stream_redelivers_once(run):
    """A prefill worker killed MID-STREAM (after segments already landed
    in the decode cache) must look like a crash: no ack, the half-landed
    stream resolves nothing, and a surviving worker's redelivery re-runs
    the prefill and re-streams from scratch over the SAME pre-allocated
    blocks — the decode side sees exactly one delivery and a token
    stream bit-identical to an unkilled aggregated run."""
    from dynamo_tpu.resilience import faultpoints

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus, redeliver_after=3.0)
        decode, prefill = _disagg_stack()
        transfer = KvTransferServer()
        await transfer.start()
        # segment_blocks=2 splits the 6-block prompt into 3 segments so
        # the kill can land strictly MID-stream
        worker_a = PrefillWorker(prefill, queue, layer_chunk=1, segment_blocks=2)
        worker_a.start()
        eng = DisaggEngine(decode, router, queue, transfer)

        try:
            # warm-up round trip (faultpoint not armed): compiles every
            # jit in the streamed path (module-level caches, shared by
            # worker B's engine) so neither attempt of the measured
            # request outlives the redelivery visibility window
            warm = await collect(
                eng.generate(Context(make_req(list(range(60, 84)), max_tokens=2)))
            )
            assert [t for o in warm for t in o.token_ids]
            assert eng.stats["streamed_deliveries"] == 1
            # the cold-compile warm-up may have outlived the visibility
            # window and been processed twice (second copy DISCARDED by
            # the assembler — delivery above still counted once); only
            # deltas from here on are meaningful
            a_sends = worker_a.stats["kv_stream_sends"]

            # hit 1 = stream open, hits 2+ = one per emitted segment:
            # the 3rd hit kills worker A after a segment already
            # scattered into the decode cache
            faultpoints.arm("mid_kv_transfer", "kill", after=3, times=1)
            prompt = list(range(10, 34))
            gen = asyncio.ensure_future(
                collect(eng.generate(Context(make_req(prompt, max_tokens=6))))
            )
            # wait for worker A to die mid-stream, then bring up the
            # survivor that consumes the redelivered item
            for _ in range(100):
                if worker_a._stop.is_set():
                    break
                await asyncio.sleep(0.05)
            assert worker_a._stop.is_set(), "fault point never fired"
            # A's measured-request attempt never completed a stream
            assert worker_a.stats["kv_stream_sends"] == a_sends
            prefill_b = JaxEngine(engine_cfg(), params=PARAMS)
            worker_b = PrefillWorker(
                prefill_b, queue, layer_chunk=1, segment_blocks=2
            )
            worker_b.start()
            outs = await asyncio.wait_for(gen, 30)
            toks = [t for o in outs for t in o.token_ids]
            assert outs[-1].finish_reason in (FinishReason.LENGTH, FinishReason.EOS)

            ref_engine = JaxEngine(engine_cfg(), params=PARAMS)
            ref = await collect(
                ref_engine.generate(Context(make_req(prompt, max_tokens=6)))
            )
            assert toks == [t for o in ref for t in o.token_ids]
            # exactly once: one delivery of the measured request (plus
            # the warm-up's), by the survivor, and the item is off the
            # queue (acked only after the handoff committed)
            assert eng.stats["streamed_deliveries"] == 2
            assert worker_b.stats["kv_stream_sends"] >= 1
            assert await queue.get_depth() == 0

            await worker_b.close()
            await prefill_b.close()
            await ref_engine.close()
        finally:
            faultpoints.reset()
            await worker_a.close()
            await transfer.close()
            await decode.close()
            await prefill.close()
            await router.stop()
            await drt.shutdown()

    run(main())


def test_disagg_timeout_fails_request(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=4)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode = JaxEngine(engine_cfg(), params=PARAMS)
        transfer = LocalKvPipe()
        # no prefill worker running -> delivery never arrives
        eng = DisaggEngine(decode, router, queue, transfer, transfer_timeout=0.3)
        outs = await collect(eng.generate(Context(make_req(list(range(20))))))
        assert outs[-1].finish_reason == FinishReason.ERROR
        # blocks were returned to the pool
        assert decode.kv.allocator.used_count == 0
        await decode.close()
        await router.stop()
        await drt.shutdown()

    run(main())


def test_concurrent_streamed_prefills_interleave_chunkwise(run):
    """PrefillWorker ``concurrency`` + the per-chunk device lock in
    prefill_extract_stream (ISSUE 9): two queued prompts must advance
    chunk-wise TOGETHER — each streaming its own segments as its own
    chunks land — instead of serializing whole prompts, and both decode
    streams must stay bit-identical to aggregated serving."""

    async def main():
        drt = await DistributedRuntime.from_settings()
        router = ConditionalDisaggRouter(
            drt, "dynamo", "tiny", DisaggConfig(max_local_prefill_length=8)
        )
        await router.start()
        queue = PrefillQueue(drt.bus)
        decode = JaxEngine(engine_cfg(max_batch_size=4), params=PARAMS)
        # small chunks so each prompt takes several chunks — the
        # interleaving window the per-chunk lock release opens
        prefill = JaxEngine(engine_cfg(prefill_chunk=8), params=PARAMS)
        transfer = LocalKvPipe()
        worker = PrefillWorker(
            prefill, queue, local_pipe=transfer, segment_blocks=2,
            concurrency=2,
        )
        # observe the chunk schedule: request id per _run_one_chunk call
        schedule = []
        orig_chunk = prefill._run_one_chunk

        def spy(seq, pos):
            schedule.append(seq.tokens[0])
            return orig_chunk(seq, pos)

        prefill._run_one_chunk = spy
        worker.start()
        eng = DisaggEngine(decode, router, queue, transfer)

        prompts = [list(range(40, 80)), list(range(140, 180))]  # 5 chunks each
        outs = await asyncio.gather(*[
            collect(eng.generate(Context(make_req(p, max_tokens=4))))
            for p in prompts
        ])
        assert eng.stats["remote_prefills"] == 2
        assert eng.stats["streamed_deliveries"] == 2
        assert worker.stats["kv_stream_segments"] >= 4
        # the two prompts' chunks INTERLEAVED on the device (neither
        # prompt ran start-to-finish while the other waited)
        firsts = [schedule.index(p[0]) for p in prompts]
        lasts = [
            len(schedule) - 1 - schedule[::-1].index(p[0]) for p in prompts
        ]
        assert max(firsts) < min(lasts), (
            f"prompts serialized instead of interleaving: {schedule}"
        )

        ref_engine = JaxEngine(engine_cfg(max_batch_size=4), params=PARAMS)
        for p, out in zip(prompts, outs):
            ref = await collect(ref_engine.generate(
                Context(make_req(p, max_tokens=4))
            ))
            assert [t for o in out for t in o.token_ids] == [
                t for o in ref for t in o.token_ids
            ]

        await worker.close()
        await decode.close()
        await prefill.close()
        await ref_engine.close()
        await router.stop()
        await drt.shutdown()

    run(main())


def test_kv_bulk_zero_block_delivery(run):
    """Bulk (non-streamed) zero-block delivery — the decode side's
    prefix cache covered every shipped block, kv_stream off. The
    receiver used to resolve the header's empty dtype eagerly and
    crash into a redelivery loop (dynflow header-plane finding); it
    must ack and resolve the future cleanly."""
    from dynamo_tpu.disagg.transfer import send_kv_blocks

    async def main():
        srv = KvTransferServer()
        await srv.start()
        fut = srv.expect("req-b0")
        await send_kv_blocks(srv.address, "req-b0", 42, None, None)
        d = await asyncio.wait_for(fut, 5)
        assert d.first_token == 42 and d.n_blocks == 0
        assert d.k_data is None and d.error is None
        await srv.close()

    run(main())


def test_kv_bulk_drifted_header_forces_redelivery(run):
    """A peer whose header schema drifted (n_blocks renamed/absent) but
    whose shape still declares real blocks must NOT be acked as a
    legitimate zero-block delivery — that would hand the decode side a
    phantom prefix hit. The geometry cross-check (shape's block dim vs
    n_blocks) raises, no ack is sent, and the pending future survives
    for the redelivery."""
    import json as _json

    from dynamo_tpu.runtime.codec import TwoPartMessage, write_frame

    async def main():
        srv = KvTransferServer()
        await srv.start()
        fut = srv.expect("req-drift")
        host, port = srv.address.address.rsplit(":", 1)
        reader, writer = await asyncio.open_connection(host, int(port))
        head = {  # no n_blocks key — the drift — but a real-block shape
            "request_id": "req-drift",
            "shape": [2, 2, 3, 4, 8], "v_shape": [2, 2, 3, 4, 8],
            "dtype": "float32", "layer_chunk": 1,
        }
        await write_frame(
            writer, TwoPartMessage(_json.dumps(head).encode(), b"")
        )
        # receiver must close WITHOUT acking (protocol error path)
        ack = await asyncio.wait_for(reader.read(2), 5)
        assert ack == b""  # EOF, not b"ok"
        assert not fut.done()  # pending: the redelivery retries it
        writer.close()
        await writer.wait_closed()
        srv.abandon("req-drift")
        await srv.close()

    run(main())
