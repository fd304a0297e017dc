"""KV head-layout rearrangement (ref vllm patch kv_rearrange, :743-810).

The TPU design ships KV as global arrays, so TP mismatch per se needs no
kernel — what's covered here is head-order regrouping (blocked vs
interleaved shard layouts), GQA replication, and the disagg delivery path
applying the regroup when prefill and decode engines disagree.
"""

import numpy as np

from dynamo_tpu.ops.kv_rearrange import (
    expand_kv_heads,
    rearrange_for_decode,
    regroup_heads,
)


def _stack(heads=8, L=2, n=3, bs=4, D=5):
    # value at [l,h,...] encodes the head id so permutations are visible
    x = np.zeros((L, heads, n, bs, D), np.float32)
    for h in range(heads):
        x[:, h] = h
    return x


def test_regroup_blocked_to_interleaved_roundtrip():
    x = _stack(heads=8)
    y = regroup_heads(x, tp=4, src_layout="blocked", dst_layout="interleaved")
    # blocked shard-major list: 0..7; interleaved shard 0 must own heads
    # {0, 4} of the *blocked* world placed at its positions
    back = regroup_heads(y, tp=4, src_layout="interleaved", dst_layout="blocked")
    np.testing.assert_array_equal(back, x)
    assert not np.array_equal(y, x)


def test_regroup_shard_contents_match():
    """After blocked->interleaved regroup with tp shards, shard i's slice
    of the output holds exactly the heads the interleaved layout assigns
    it (i, i+tp, ...), in order."""
    heads, tp = 8, 4
    x = _stack(heads=heads)
    y = regroup_heads(x, tp=tp, src_layout="blocked", dst_layout="interleaved")
    per = heads // tp
    for shard in range(tp):
        ids = y[:, shard * per : (shard + 1) * per, 0, 0, 0][0]
        assert list(ids) == [shard + j * tp for j in range(per)]


def test_identity_when_layouts_match():
    x = _stack()
    assert regroup_heads(x, tp=2) is x
    assert expand_kv_heads(x, 1) is x


def test_expand_kv_heads_replicates():
    x = _stack(heads=4)
    y = expand_kv_heads(x, 2)
    assert y.shape[1] == 8
    assert list(y[0, :, 0, 0, 0]) == [0, 0, 1, 1, 2, 2, 3, 3]


def test_rearrange_for_decode_never_expands():
    """The decode cache is a global [L, Hkv, ...] array — GQA replication
    is a mesh-sharding concern; rearrange must preserve the head count."""
    x = _stack(heads=4)
    y = rearrange_for_decode(x, src_tp=2, dst_tp=8)
    assert y.shape[1] == 4


import pytest


@pytest.mark.parametrize("streamed", [True, False])
def test_disagg_delivery_applies_regroup(run, streamed):
    """A tp=2 prefill engine whose gathered KV arrives in *interleaved*
    head order (simulated by permuting the gather output, since the native
    engine stores heads naturally) feeding a blocked decode engine: the
    delivery-side regroup must undo the permutation, giving greedy tokens
    identical to an all-local run — on BOTH wire flavors. The streamed
    path regroups each segment on arrival in the scatter sink (ISSUE 9:
    mismatched peers stream too, no more buffered-bulk downgrade); the
    bulk path keeps the delivery-time full-stack regroup."""

    from dynamo_tpu.disagg import (
        ConditionalDisaggRouter, DisaggConfig, DisaggEngine, LocalKvPipe,
        PrefillQueue, PrefillWorker,
    )
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel.mesh import MeshConfig
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime import Context, DistributedRuntime, collect

    def make_req(prompt):
        return PreprocessedRequest(
            token_ids=list(prompt),
            stop_conditions=StopConditions(max_tokens=4),
            sampling_options=SamplingOptions(temperature=0.0),
            eos_token_ids=[511],
        )

    async def main():
        mcfg = ModelConfig.tiny(num_kv_heads=4)
        drt = await DistributedRuntime.from_settings()
        prefill_engine = JaxEngine(
            EngineConfig(
                model=mcfg, num_blocks=64, block_size=4, max_batch_size=2,
                max_context=128, mesh=MeshConfig(tp=2),
            ),
            seed=0,
        )
        # simulate an engine that physically stores heads interleaved:
        # permute what the natural-order gather returns — patched at the
        # GATHER so both the bulk extract and the streamed per-segment
        # extract ship permuted data
        orig_gather = prefill_engine._gather_device

        def interleaved_gather(idxs, keep_on_device=False):
            k, v = orig_gather(idxs, keep_on_device)
            k = regroup_heads(k, tp=2, src_layout="blocked",
                              dst_layout="interleaved")
            v = regroup_heads(v, tp=2, src_layout="blocked",
                              dst_layout="interleaved")
            return k, v

        prefill_engine._gather_device = interleaved_gather

        decode_engine = JaxEngine(
            EngineConfig(
                model=mcfg, num_blocks=64, block_size=4, max_batch_size=2,
                max_context=128,
            ),
            seed=0,
        )
        router = ConditionalDisaggRouter(
            drt, "t", "m", DisaggConfig(max_local_prefill_length=8)
        )
        pipe = LocalKvPipe()
        queue = PrefillQueue(drt.bus, "t")
        worker = PrefillWorker(
            prefill_engine, queue, local_pipe=pipe,
            head_layout="interleaved", kv_stream=streamed,
        )
        worker.start()
        disagg = DisaggEngine(
            decode_engine, router, queue, pipe, kv_stream=streamed
        )

        prompt = list(range(40, 72))  # 32 tokens > threshold -> remote
        out = await collect(disagg.generate(Context(make_req(prompt))))
        toks = [t for o in out for t in o.token_ids]
        assert disagg.stats["remote_prefills"] == 1
        if streamed:
            # the mismatch must no longer downgrade to buffered bulk:
            # segments landed incrementally, each regrouped on arrival
            assert disagg.stats["streamed_deliveries"] == 1
            assert disagg.stats["kv_stream_regroups"] >= 1
            assert disagg.stats["kv_stream_segments"] >= 1
        else:
            assert disagg.stats["bulk_deliveries"] == 1
            assert disagg.stats["kv_stream_regroups"] == 0

        # reference: same request served fully locally on a fresh engine
        local_engine = JaxEngine(
            EngineConfig(
                model=mcfg, num_blocks=64, block_size=4, max_batch_size=2,
                max_context=128,
            ),
            seed=0,
        )
        ref = await collect(local_engine.generate(Context(make_req(prompt))))
        ref_toks = [t for o in ref for t in o.token_ids]
        assert toks == ref_toks
        await worker.close()
        await disagg.engine.close()
        await local_engine.close()
        await prefill_engine.close()
        await drt.shutdown()

    run(main())


def test_interleaved_same_layout_different_tp_not_identity():
    """interleaved(tp=2) -> interleaved(tp=4) is a real permutation —
    the delivery guard must not treat same-layout as same-order."""
    x = _stack(heads=8)
    y = rearrange_for_decode(x, src_tp=2, dst_tp=4,
                             src_layout="interleaved", dst_layout="interleaved")
    assert not np.array_equal(y, x)
    back = rearrange_for_decode(y, src_tp=4, dst_tp=2,
                                src_layout="interleaved", dst_layout="interleaved")
    np.testing.assert_array_equal(back, x)
