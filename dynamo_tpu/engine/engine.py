"""The native JAX engine: continuous batching over a paged KV cache.

This is the TPU replacement for the reference's wrapped GPU engines (vLLM
et al.): a single background scheduler task owns the device state (params,
KV cache, block tables) and interleaves

  * **admission**: claim prefix-cache hits, allocate blocks, run (chunked,
    bucketed) prefill for new requests,
  * **decode**: one batched ``decode_step`` per iteration for all active
    sequences (continuous batching — sequences join/leave the batch at any
    step),
  * **emission**: stream sampled tokens into per-request asyncio queues
    (the AsyncEngine facade yields from them).

Static-shape discipline (XLA): prefill lengths are bucketed, the decode
batch is padded to ``max_batch_size``, block tables are a fixed
``[B, max_blocks_per_seq]`` — so there are O(#buckets + 1) compiled
programs total, reused forever. The KV cache arrays are donated through
every jit call and never leave the device.
"""

from __future__ import annotations

import asyncio
import contextlib
import logging
import os
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import AsyncIterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import llama
from ..models.config import ModelConfig
from ..ops.sampling import make_keys, sample_first_token, sample_tokens
from ..parallel.mesh import LogicalLayout, MeshConfig, make_mesh, replicated
from ..protocols.common import (
    FinishReason,
    LLMEngineOutput,
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from ..analysis import sanitizer
from ..observability.hist import MS_BUCKETS, Histogram
from ..resilience import faultpoints
from ..resilience.faultpoints import FaultInjected
from ..resilience.policy import MIGRATION_SIGNAL
from ..runtime.engine import AsyncEngine, Context
from .. import tracing
from ..tracing.loop_clock import KINDS, LoopClock
from .allocator import model_hash_salt, sequence_block_hashes
from .kv_manager import Hold, KvManager
from .offload import OffloadManager
from .step_state import StepState

logger = logging.getLogger(__name__)

PREFILL_BUCKETS = [16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192]
#: engine.stats keys of the work counters, exported as engine_<key>_total
WORK_COUNTERS = ("rows_dispatched", "rows_live", "prefill_tokens_dispatched",
                 "prefill_tokens_padding", "attn_table_pages",
                 "attn_live_pages", "sampler_filter_steps")
#: the same for a model with expert layers (absent for a dense one):
#: expert slots handed over and assignments of live rows, counted on the
#: host, and what the step programs' MoeTally brings back
MOE_COUNTERS = ("moe_expert_slots", "moe_assignments", "moe_experts_touched",
                "moe_experts_touched_live", "moe_max_group_rows",
                "moe_expert_streams", "moe_held_assignments")

#: the same for a model whose layers carry a per-sequence state (LFM2's
#: conv layers, GigaChat 3.5's linear-attention layers; absent
#: otherwise), beside the KV manager's (kv_manager.SNAPSHOT_COUNTERS):
#: admissions that started from a snapshot, and the bytes of recurrent
#: matrices the step programs read and wrote (0 for conv rows only)
STATE_COUNTERS = ("state_restores", "linear_state_bytes")

# the prefill-admission first-token sampler, jitted ONCE at module scope:
# a per-call ``jax.jit(sample_first_token)`` built a fresh wrapper (and a
# fresh trace cache) on every admission, so every prefill paid a retrace
_sample_first_jit = jax.jit(sample_first_token)


def _bucket(n: int) -> int:
    for b in PREFILL_BUCKETS:
        if n <= b:
            return b
    return ((n + 8191) // 8192) * 8192


def _seg_bucket(n: int) -> int:
    """Segment-count bucket for the fused mixed step: the smallest power
    of two covering ``n`` in-flight prefill segments. The fused program
    is keyed by (segment-count bucket x prefill-length bucket), so the
    mixture of live prompts never multiplies compiles — dead pad
    segments (valid 0, zero tables) fill the bucket."""
    b = 1
    while b < n:
        b *= 2
    return b


@jax.jit
def _reset_scale_entries(k_scales, v_scales, idxs):
    """Reset recycled pages' scale-plane entries to the codec epsilon
    (one scatter for the whole batch of allocator recycles). ``idxs`` is
    padded with the trash page 0 — resetting its scale is harmless."""
    from ..models.quant import KV_SCALE_EPS

    return (
        k_scales.at[:, idxs].set(KV_SCALE_EPS),
        v_scales.at[:, idxs].set(KV_SCALE_EPS),
    )


from functools import partial as _partial


@_partial(jax.jit, static_argnames=("dtype",))
def _dequant_gathered(pages, scales, dtype):
    """Dequantize a gathered int8 page stack ([L, Hkv, n, bs, D] codes +
    [L, n] scales) back to the model's full-width ``dtype`` — the
    device-side half of an export that must leave the device codec
    (legacy peer, disagg full-width wire)."""
    return (
        pages.astype(jnp.float32) * scales[:, None, :, None, None]
    ).astype(dtype)


@_partial(jax.jit, donate_argnames=("state",))
def _begin_state_row(state, slot, snap_row):
    """A sequence takes row ``slot`` of the state (llama.init_state) for
    its prefill: snapshot ``snap_row`` (of the last block of its cached
    prefix: ``KvManager.restore_from``), every part of it, or zeros for a
    prompt that starts from token 0 (``snap_row`` < 0)."""
    at = jnp.maximum(snap_row, 0)
    out = dict(state)
    out["conv"] = state["conv"].at[slot].set(
        jnp.where(snap_row >= 0, state["snap"][at], 0))
    if "rec" in state:
        out["rec"] = state["rec"].at[:, slot].set(
            jnp.where(snap_row >= 0, state["snap_rec"][:, at], 0))
    return out


@jax.jit
def _reset_pen_slot(counts, mask, slot, prompt_ids, gen_ids):
    """Rebuild one slot's penalty state: prompt-token mask from
    ``prompt_ids`` and output counts from ``gen_ids`` (non-empty after a
    prefill's first sampled token or a preemption replay). Both padded
    with vocab_size — out-of-bounds scatters drop."""
    V = mask.shape[1]
    crow = jnp.zeros((V,), jnp.int32).at[gen_ids].add(1, mode="drop")
    counts = counts.at[slot].set(crow)
    row = jnp.zeros((V,), jnp.bool_).at[prompt_ids].set(True, mode="drop")
    return counts, mask.at[slot].set(row)


@dataclass
class EngineConfig:
    model: ModelConfig
    num_blocks: int = 256
    block_size: int = 16
    max_batch_size: int = 8
    max_context: int = 0  # 0 -> model.max_position_embeddings
    prefill_chunk: int = 2048
    mesh: Optional[MeshConfig] = None
    max_queue: int = 1024
    # fused mixed prefill+decode batching (Sarathi-style chunked-prefill
    # piggybacking / ragged paged attention, PAPERS.md): while a chunked
    # prefill is in flight AND sequences are decoding, each scheduler
    # iteration dispatches ONE fused step — a budget-bounded prefill
    # chunk plus a decode token for every active sequence — instead of
    # alternating a dedicated prefill dispatch with 1-step decode
    # windows. Decode inter-token latency stops absorbing the chunk's
    # device time behind a separate dispatch, and the chunk's GEMMs
    # amortize the weight stream over the decode rows (bench.py
    # ``decode_itl_under_prefill_ms``). False = the legacy alternating
    # scheduler (escape hatch + the bench baseline). Multi-host mirrors
    # and ring-prefill chunks always take the alternating path.
    mixed_batch: bool = True
    # prefill tokens per fused mixed step (the Sarathi token budget);
    # 0 = prefill_chunk. Smaller budgets bound the fused step's device
    # time (tighter decode ITL) at more steps per prompt.
    mixed_step_budget: int = 0
    # max concurrent prompts whose prefills PACK into one fused step
    # (the full ragged formulation / Sarathi stall-free multi-prompt
    # packing): the mixed_step_budget splits across up to this many
    # in-flight prompts per iteration — admission order, per-prompt
    # minimum chunk so no prompt starves — killing head-of-line
    # blocking among queued prompts (short prompts behind a long
    # prefill get their first token without waiting it out). 1 = one
    # prefill at a time (the PR 3 behavior). Compiled program count is
    # bounded by segment-count buckets x prefill buckets, not by the
    # live mixture (test_compiled_perf).
    mixed_max_prefills: int = 4
    # host-DRAM offload tier capacity in blocks (0 = disabled); evicted
    # device blocks park here and restore on prefix hits (engine/offload.py)
    host_cache_blocks: int = 0
    # third KV tier: local disk/SSD capacity in blocks (0 = disabled;
    # requires a host tier — promotion back to device goes THROUGH host
    # DRAM so the unchanged upload/scatter restore path serves it).
    # Host-pool LRU overflow demotes here instead of dropping; disk
    # LRU/TTL overflow is the real drop (offload.DiskKvStore)
    disk_cache_blocks: int = 0
    # disk-tier directory (None = a fresh tempdir per engine); a
    # restarted worker pointed at the same path keeps its disk tier
    disk_cache_path: Optional[str] = None
    # disk-tier entry TTL in seconds (0 = LRU only): at fleet scale the
    # long tail of stale prefixes ages out instead of squatting capacity
    kv_tier_ttl_s: float = 0.0
    # async offload tier: d2h eviction flushes land via background
    # executor threads (double-buffered, budgeted) and h2d restores
    # upload from the moment admission reserves the chain — the
    # scheduler loop never blocks on a transfer (offload.py module
    # docstring). False = legacy synchronous transfers (escape hatch;
    # the multi-host mirror is always synchronous regardless).
    offload_async: bool = True
    # max OPTIONAL evicted blocks one decode dispatch gathers d2h;
    # evictions whose pages the dispatch itself overwrites always flush
    offload_flush_budget: int = 64
    # self-calibrating transfer-cost model (kv_router/costmodel.py):
    # fold observed restore/pull/handoff/prefill timings into per-link
    # bandwidth estimates and advertise them via load_metrics, so the
    # KV router can score this worker by predicted TTFT instead of raw
    # overlap. False = no observations, no advertisement — the router
    # keeps this worker on the overlap-scoring cold-start path forever.
    kv_cost_model: bool = True
    # max fused decode steps per device dispatch (lax.scan window): the
    # sampled token of step i feeds step i+1 on device, so the host syncs
    # once per window, not once per token. The scheduler drops to 1-step
    # windows whenever admission work is pending (fairness) and clamps to
    # each sequence's stop/context headroom. Power of two.
    decode_window: int = 4
    # chained decode (the engine's decode path since PR 49): the loop
    # enqueues program i (a decode window or a mixed step) BEFORE it
    # fetches program i-1's result, so the chip has its next program
    # queued while the host emits, provisions and admits. What is in
    # flight is a queue of programs over the resident step state
    # (engine/step_state.py), not a frozen batch: a stream's end goes up
    # as cells with the next dispatch and its queued steps are discarded,
    # a mixed step is enqueued behind a window in flight, and only pool
    # pressure (the queued program's blocks go back first: chaining is
    # never the CAUSE of a preemption), a verify step, a reshard, a
    # whole-state resynchronisation and the loop going idle drain the
    # chain. A chained window runs decode_window // 2 steps: two of them
    # cost an arrival what one unchained window did. The multi-host
    # mirror keeps the frozen form (its rows are host arrays: drained at
    # every membership change and before a mixed phase).
    # False = the UNCHAINED loop: enqueue, wait, emit. It is the tests'
    # bit-exact reference for the chained schedule (streams are token
    # for token the same; under pool starvation the overlapped schedule
    # can shift WHICH sequence a genuine preemption picks, and a replay
    # whose prefix blocks were evicted recomputes with other reduction
    # orders). On the chip the chained loop wins in every cell (ledger,
    # PR 49; PERF.md section 6). ROADMAP C3: the reference stays; the
    # user's switch and the mirror's frozen form go with C8's verdict
    # on the mirror.
    decode_pipeline: bool = True
    # speculative decoding via prompt-lookup (n-gram) drafts: propose up
    # to spec_gamma continuation tokens from the sequence's own history
    # (last spec_ngram tokens matched against earlier occurrences) and
    # verify them in ONE fused forward (llama.verify_window) — the weight
    # stream amortizes over gamma+1 tokens, so accepted runs multiply
    # decode throughput on repetitive/structured text. Greedy rows accept
    # argmax-matching proposals; sampled rows use rejection sampling
    # against the deterministic draft (lossless in distribution). Slots
    # without a match fall back to a plain single-token step inside the
    # same dispatch. Greedy streams are preserved except at exact logit
    # ties; sampled streams match plain decode in distribution, not
    # token-for-token (the standard spec-decode contract). 0 = off.
    spec_gamma: int = 0
    # rows of the state's snapshot pool for a model whose per-sequence
    # state is too large for a row a KV block (llama.
    # state_snapshot_rows; GigaChat 3.5: 16.4 MiB a row); 0 = 64. A
    # small state (LFM2) keeps a row a block whatever this says
    state_snapshots: int = 0
    # blocks of the window pool of a model whose window layers keep their
    # KV in a pool of their own (ModelConfig.window_kv_pool); 0 = derived
    # (kv_manager.window_pool_blocks: what the decode slots and one lone
    # prefill hold at most, and a window a slot of cached tails). How many
    # contexts' tails stay hittable beyond that is the deployment's to
    # say, as num_blocks is for the histories
    window_blocks: int = 0
    spec_ngram: int = 3
    # weight quantization: "none" | "int8" | "fp8_e4m3" (models/quant.py —
    # per-output-channel scales; halves decode's HBM weight streaming, the
    # ref's FP8 serving equivalent, docs/architecture.md:57-61)
    quantization: str = "none"
    # quantization covers MoE expert stacks by default (the grouped-
    # dequant Pallas kernel streams them at storage width,
    # ops/moe_gmm_pallas.py); False pins experts at the model dtype
    quant_experts: bool = True
    # KV cache storage dtype: "model" | "float8_e4m3" | "bfloat16"
    # (float8 = scale-free direct cast, vLLM fp8-KV approach; halves KV
    # HBM traffic + doubles cache capacity at some quality cost). A
    # quantized cache still runs the Pallas ragged kernels — the
    # dequant cast fuses into the kernels' KV page loads
    # (_use_pallas_for; ops/ragged_paged_attention_pallas.py)
    kv_cache_dtype: str = "model"
    # per-block KV quantization for the OFFLOAD tiers and the wire
    # (engine/kvquant.py): "none" | "int8" | "fp8". Blocks entering the
    # host pool / disk tier / peer-pull + disagg wire are stored and
    # shipped int8/fp8 with per-(layer, block) scales and dequantized
    # in the device-side scatter on restore — ~2x effective capacity
    # of every tier and the wire at once, at a measured (NOT zero)
    # logprob drift (kvquant.measure_logprob_drift gates it). Opt-in
    # per model; "none" keeps every plane bit-exact full width.
    kv_quant: str = "none"
    # sequence-parallel long-prompt prefill: prompts at least this many
    # tokens go through ring attention over the mesh's sp axis as ONE
    # history-free chunk (parallel/ring_attention.py) instead of chunked
    # dense prefill — each sp device computes T/sp query rows while KV
    # shards rotate the ICI ring. 0 = off. Requires an sp>1 mesh; full
    # attention, non-MLA models (engine falls back otherwise).
    # Measured (scripts/ablate_ring.py, benchmarks/ablate_ring.json):
    # ring wins grow with T (3.6x @ 1k -> 11.4x @ 4k on the virtual
    # mesh) and dense prefill's O(T^2) score memory becomes the binding
    # constraint near 16k — set the threshold where score memory rivals
    # a layer's weights (~8k for 8B-class) on sp>1 slices.
    ring_prefill_threshold: int = 0
    # multi-LoRA serving lane (engine/adapters.py): adapter specs, each
    # "name:rank[:seed]" (synthetic seeded weights — tests/bench) or
    # "name=/path/to/adapter.npz" (real weights). Non-empty turns on the
    # adapter registry: requests may carry a model name that resolves to
    # one of these adapters and the batch runs ONE shared base-GEMM pass
    # plus grouped per-adapter low-rank deltas (ops/lora.py). Empty ()
    # keeps every compiled program, block hash, and wire payload
    # byte-identical to a pre-multi-model fleet.
    adapters: tuple = ()
    # public name of the BASE model (what /v1/models advertises and what
    # requests resolve to adapter_id -1); "" = serve under any name the
    # frontend registered (legacy single-model behavior)
    served_model_name: str = ""
    # max adapters resident in the device stack at once (0 = all
    # configured adapters stay resident — the test/bench default).
    # Smaller than the configured count turns on LRU staging: a request
    # for an unstaged adapter pays a host->device copy unless
    # pre_stage_weights hid it beforehand.
    max_live_adapters: int = 0

    def __post_init__(self):
        if self.spec_gamma > 0 and self.decode_window < 2:
            raise ValueError(
                "spec_gamma requires decode_window >= 2: the speculative "
                "path only engages when the scheduler picks multi-step "
                "windows (decode_window=1 would silently disable it)"
            )
        if self.max_context == 0:
            self.max_context = self.model.max_position_embeddings
        if self.mixed_step_budget < 0:
            # a negative budget would slice empty chunks: the fused
            # prefill would never advance and admission behind it would
            # hang forever — fail loudly at construction instead
            raise ValueError(
                f"mixed_step_budget={self.mixed_step_budget} must be >= 0 "
                "(0 = prefill_chunk)"
            )
        if self.mixed_step_budget == 0:
            self.mixed_step_budget = self.prefill_chunk
        if self.disk_cache_blocks > 0 and self.host_cache_blocks <= 0:
            # the disk tier restores THROUGH host DRAM (promotion), so a
            # disk-only configuration would silently never restore —
            # fail loudly at construction
            raise ValueError(
                "disk_cache_blocks > 0 requires host_cache_blocks > 0 "
                "(disk restores promote through the host tier)"
            )
        if self.mixed_max_prefills < 1:
            raise ValueError(
                f"mixed_max_prefills={self.mixed_max_prefills} must be "
                ">= 1 (1 = single-prefill fused steps)"
            )
        from .kvquant import KV_QUANT_MODES

        if self.kv_quant not in KV_QUANT_MODES:
            raise ValueError(
                f"kv_quant must be one of {KV_QUANT_MODES}, "
                f"got {self.kv_quant!r}"
            )
        if self.adapters:
            # loud construction-time gates, matching the int8/MLA
            # precedent: every incompatible lane fails HERE, not as a
            # shape error mid-serve
            if self.spec_gamma > 0:
                raise ValueError(
                    "adapters are incompatible with speculative decoding "
                    "(spec_gamma > 0): verify_window has no LoRA lane yet"
                )
            if getattr(self.model, "is_mla", False):
                raise ValueError(
                    "adapters target the separate-QKV projection path; "
                    "MLA models have no LoRA lane yet"
                )
            if self.ring_prefill_threshold > 0:
                raise ValueError(
                    "adapters are incompatible with ring prefill: the "
                    "ring chunk path has no LoRA lane yet"
                )
        self.max_blocks_per_seq = (
            self.max_context + self.block_size - 1
        ) // self.block_size


class OutOfBlocks(Exception):
    """KV pool exhausted — caller should backpressure/retry (the prefill
    queue nacks the item so another worker, or this one later, retries)."""


class ReshardUnsupported(RuntimeError):
    """This engine cannot morph its mesh live (multi-host mirrors: every
    dispatch is a lockstep broadcast and the followers' device state
    can't be re-laid from the leader's loop). Callers fall back to the
    PR 4 migration path — drain with handoff so the streams continue on
    workers that CAN serve the new layout."""


@dataclass
class _Sequence:
    request: PreprocessedRequest
    context: object  # AsyncEngineContext
    out_queue: asyncio.Queue
    tokens: list[int] = field(default_factory=list)  # prompt + generated
    prompt_len: int = 0
    # what it holds in the pools (kv_manager.Hold): KvManager.reserve's
    # answer, empty before it and after KvManager.release
    hold: Hold = field(default_factory=Hold)
    generated: int = 0
    # tokens the scheduler need not prefill (reserve's answer too)
    cached_prefix: int = 0
    slot: int = -1  # decode batch slot
    # multi-LoRA lane: resolved adapter slot in the device stack (-1 =
    # base model, no delta) and the public model name the request
    # arrived under ("" = base). The name — not the slot — salts the
    # block hash chain, so staging/eviction can reshuffle slots without
    # moving any block out of its model's prefix namespace.
    adapter_id: int = -1
    model: str = ""
    # the row of the conv state (a decode slot's) this sequence holds
    # from its first prefill chunk on; -1: none (no conv layers)
    state_slot: int = -1
    finished: bool = False
    arrival_t: float = field(default_factory=time.monotonic)
    # request trace (tracing.TraceContext), captured at generate() entry
    # while the caller's contextvar is still in scope; None = untraced,
    # and every hot-path instrumentation site gates on that None first
    trace: Optional[object] = None

    @property
    def seq_len(self) -> int:
        return len(self.tokens)


class JaxEngine(AsyncEngine):
    """AsyncEngine over PreprocessedRequest -> LLMEngineOutput stream."""

    def __init__(
        self,
        cfg: EngineConfig,
        params: Optional[dict] = None,
        seed: int = 0,
        mirror=None,
    ):
        self.cfg = cfg
        mcfg = cfg.model
        # what a sequence holds in the pools, who may hit it, and what
        # cannot serve a model whose sequences hold more than one paged
        # cache (refused in there, by name): engine/kv_manager.py
        self.kv = KvManager(
            cfg, mirror=mirror, snapshot_rows=llama.state_snapshot_rows(
                mcfg, cfg.num_blocks, cfg.state_snapshots))
        # multi-host: a StepMirror (parallel/multihost.py) makes this engine
        # the leader of a process-spanning mesh — every device dispatch is
        # broadcast to follower ranks which replay the identical jit call
        self.mirror = mirror
        # the LOGICAL sharding contract (parallel/mesh.LogicalLayout):
        # placement rules carried mesh-free, resolved against whatever
        # mesh currently backs the engine — the refactor that makes
        # reshard() a first-class operation instead of a rebuild
        self.layout = LogicalLayout(mcfg)
        if mirror is not None:
            self.mesh = mirror.mesh
        else:
            self.mesh = make_mesh(cfg.mesh) if cfg.mesh else None
        if params is None:
            params = llama.init_params(mcfg, jax.random.key(seed))
        from ..models.quant import kv_cache_dtype, quantize_params

        # quantize BEFORE placement so the derived q/s leaves get their
        # own shardings (parallel/mesh.py derives them from the parent's)
        params = quantize_params(params, mcfg, cfg.quantization,
                                 experts=cfg.quant_experts)
        if mirror is not None:
            params = mirror.shard_params(params)
        else:
            params = self.layout.place_params(params, self.mesh)
        self.params = params
        cache_dt = kv_cache_dtype(mcfg, cfg.kv_cache_dtype)
        if mirror is not None:
            k, v = mirror.init_cache(cfg.num_blocks, cfg.block_size, dtype=cache_dt)
        else:
            k, v = llama.init_kv_cache(
                mcfg, cfg.num_blocks, cfg.block_size, dtype=cache_dt,
                window_blocks=self.kv.window_blocks,
            )
            sh = self.layout.cache_sharding(self.mesh)
            if sh is not None:
                k, v = jax.device_put(k, sh), jax.device_put(v, sh)
        self.k_cache, self.v_cache = k, v
        # the per-sequence state that is not keys and values (LFM2's
        # conv layers; None otherwise): a row a decode slot, and the
        # snapshot pool's rows (llama.init_state; which block's snapshot
        # lies in which row is the KV manager's to say). A sequence holds
        # its row from its first prefill chunk on (_Sequence.state_slot)
        self.state = llama.init_state(
            mcfg, cfg.max_batch_size, cfg.num_blocks, cfg.state_snapshots)
        # bytes of recurrent matrices ONE sequence holds (0: conv rows only)
        rec = (self.state or {}).get("rec")  # [Ll, max_batch, Hv, Dk, Dv]
        self._rec_row_bytes = 0 if rec is None else (
            rec.size // rec.shape[1] * rec.dtype.itemsize)
        # int8-with-scales DEVICE cache (kv_cache_dtype="int8"): per-page
        # f32 scale planes [L, N] — one symmetric absmax scale per
        # (layer, physical page) per K/V, the tier codec's exact
        # granularity (engine/kvquant.py), so wire landings adopt their
        # carried scales directly and d2h exports re-encode from the
        # planes with zero full-width bounce. None for every other mode.
        self.k_scales = self.v_scales = None
        if cache_dt == jnp.int8:
            if mcfg.is_mla:
                # LOUD gate, not a silent fallback: the absorbed-matmul
                # MLA path folds W_kv^B into the query/output projections
                # and dots queries against the latent cache DIRECTLY —
                # a per-page scale would have to multiply inside the
                # absorbed einsums (and the merged latent append + the
                # bf16-gated MLA Pallas kernels have no scale stream).
                # MLA keeps the scale-free fp8 cast (kv_cache_dtype=
                # "float8_e4m3") as its low-precision option.
                raise ValueError(
                    "kv_cache_dtype='int8' is not supported for MLA "
                    "models: the absorbed-matmul latent path has no "
                    "per-page scale stream — use kv_cache_dtype="
                    "'float8_e4m3' (scale-free cast) for MLA"
                )
            if mirror is not None:
                raise ValueError(
                    "kv_cache_dtype='int8' is not supported under the "
                    "multi-host mirror (lockstep broadcasts carry no "
                    "scale planes)"
                )
            from ..models.quant import KV_SCALE_EPS

            plane = jnp.full(
                (mcfg.num_layers, cfg.num_blocks), KV_SCALE_EPS,
                jnp.float32,
            )
            if self.mesh is not None:
                # planes replicate: the page axis is unsharded and the
                # scales are kv-head-free (ops/attention._shard_tp
                # passes them as replicated scalars)
                from jax.sharding import NamedSharding, PartitionSpec

                plane = jax.device_put(
                    plane, NamedSharding(self.mesh, PartitionSpec())
                )
            self.k_scales, self.v_scales = plane, plane
        # recycled pages must not inherit a previous tenant's absmax
        # scale: every fresh-mutable allocation queues a scale reset,
        # flushed as ONE scatter on the next dispatch preamble
        # (_flush_scale_resets). match_prefix claims keep their scales.
        self._pending_scale_resets: list[int] = []
        # device-side accumulator of page requantizations (folded into
        # stats at scrape time — see _note_quant_step), and the last
        # folded value of the offload manager's export-bounce counter
        self._requants_dev = None
        self._offload_requants_seen = 0
        # decode-throughput EMA for the low-precision lane (lowprec_tok_s)
        self._lowprec_rate_t = 0.0
        if self.k_scales is not None:
            self.kv.allocator.on_allocated = self._pending_scale_resets.append
            # bytes one token's K+V rows save landing int8 instead of
            # full width (per-page scale overhead is L*8 bytes per block
            # against Hkv*D*bs*itemsize — sub-1% — and is counted in
            # _hbm_stats, not here)
            full_itemsize = jnp.dtype(mcfg.dtype).itemsize
            self._kv_saved_per_token = int(
                2 * mcfg.num_layers * mcfg.num_kv_heads
                * llama.kv_lanes(mcfg) * (full_itemsize - 1)
            )
            if cfg.spec_gamma > 0:
                logger.warning(
                    "kv_cache_dtype='int8': speculative (prompt-lookup) "
                    "decoding is disabled — the fused verify forward "
                    "has no scale-plane stream; decode runs plain "
                    "windows"
                )
        # transfer-cost calibration (kv_router/costmodel.py): one model
        # per engine, fed by the restore/pull/handoff/prefill paths and
        # advertised through load_metrics. Block bytes from the real
        # cache geometry (k and v differ for MLA latents; a model with a
        # window pool has PAIRS of caches, the full pool's first).
        k_full = jax.tree.leaves(self.k_cache)[0]
        v_full = jax.tree.leaves(self.v_cache)[0]
        self.kv_block_bytes = int(
            (k_full.nbytes + v_full.nbytes) // max(cfg.num_blocks, 1)
        )
        # bytes one block costs on the TIER/WIRE planes: the full-width
        # size, or the quantized payload + per-layer scales under
        # --kv-quant (engine/kvquant.py). Advertised alongside
        # kv_block_bytes so routing prices restore/pull legs at the
        # bytes that actually move
        from .kvquant import wire_block_bytes as _wire_bb

        self.kv_wire_block_bytes = _wire_bb(
            self.kv_block_bytes, k_full.dtype.itemsize,
            mcfg.num_layers,
            # mirror-backed engines force the tier codec off (lockstep
            # broadcasts are full-width only) — advertise accordingly
            cfg.kv_quant if mirror is None else "none",
        )
        self.offload: Optional[OffloadManager] = None
        if cfg.host_cache_blocks > 0:
            # under the multi-host mirror, flush/restore become mirrored
            # ops and every process parks its own cache shards in host DRAM
            self.offload = OffloadManager(
                cfg.host_cache_blocks, mirror=mirror,
                flush_budget=cfg.offload_flush_budget,
                async_tier=cfg.offload_async,
                disk_blocks=cfg.disk_cache_blocks,
                disk_path=cfg.disk_cache_path,
                tier_ttl_s=cfg.kv_tier_ttl_s,
                kv_quant=cfg.kv_quant,
                block_bytes=self.kv_block_bytes,
                # the tier's FULL-WIDTH dtype: with an int8 device cache
                # the cache dtype is the quantized code, not the width
                # dequants should target — use the model's compute dtype
                full_dtype=(
                    mcfg.dtype if self.k_scales is not None
                    else str(self.k_cache.dtype)
                ),
            )
            self.kv.attach_offload(self.offload)
            if self.k_scales is not None:
                # publish the scale planes so tier traffic speaks the
                # device codec: flushes gather int8 pages + scales (an
                # int8 tier adopts them with zero re-encode), restores
                # land payload + scales back into cache + planes
                self.offload.device_planes = (
                    lambda: (self.k_scales, self.v_scales)
                )

                def _set_planes(planes):
                    self.k_scales, self.v_scales = planes

                self.offload.device_planes_set = _set_planes
        self.cost = None
        if cfg.kv_cost_model:
            from ..kv_router.costmodel import TransferCostModel

            self.cost = TransferCostModel(block_bytes=self.kv_block_bytes)
            if self.offload is not None:
                self.offload.cost_model = self.cost
        self.use_pallas = self._use_pallas_for(self.mesh)
        # multi-LoRA lane (engine/adapters.py): registry of adapter A/B
        # stacks. None when cfg.adapters is empty — every dispatch site
        # below gates on that None, so base-only fleets run programs
        # byte-identical to pre-multi-model builds.
        self.adapters = None
        if cfg.adapters:
            if mirror is not None:
                raise ValueError(
                    "adapters are not supported under the multi-host "
                    "mirror yet (lockstep dispatches carry no adapter "
                    "stacks) — serve adapters on single-host workers"
                )
            from .adapters import AdapterRegistry

            self.adapters = AdapterRegistry(
                cfg.adapters, mcfg, max_live=cfg.max_live_adapters,
            )
        self._waiting: asyncio.Queue[_Sequence] = asyncio.Queue(cfg.max_queue)
        # re-admissions (preemption replay, backpressure put-back) jump
        # the line through this explicit front buffer — consumers drain
        # it before the queue, so no reaching into asyncio.Queue._queue
        # internals (advisor r2 weak #4)
        self._waiting_front: deque[_Sequence] = deque()
        # in-flight chunked prefills, admission order. The mixed-batch
        # scheduler packs the Sarathi token budget across ALL of them
        # per fused step (up to cfg.mixed_max_prefills); the alternating
        # scheduler (mixed off / mirror / ring) only ever holds one
        self._prefill_states: list[_PrefillState] = []
        # remotely-prefilled sequences with KV landed, awaiting a batch slot
        self._remote_ready: list[_Sequence] = []
        self._active: list[Optional[_Sequence]] = [None] * cfg.max_batch_size
        self._n_active = 0
        self._loop_task: Optional[asyncio.Task] = None
        # serializes device-state mutation (k/v cache is donated through
        # every jit call — concurrent dispatch would use freed buffers);
        # contended only when disagg hooks run beside the decode loop.
        # Named for the runtime sanitizer: when active, its hold times
        # histogram under "device_lock" instead of an acquire site.
        self._device_lock = sanitizer.name_lock(asyncio.Lock(), "device_lock")
        # chained decode: the record of the ONE program (a decode window
        # or a mixed step) enqueued and not yet emitted: its device
        # tokens, its steps, and which sequence held each slot when it
        # was enqueued (_pending: a row's steps on the device that the
        # host has not seen)
        self._inflight: Optional[dict] = None
        self._wake = asyncio.Event()
        self._closed = False
        self._backpressured = False
        # graceful drain (resilience/drain.py): _draining stops admission
        # (generate() bounces new work with the migration signal); past
        # _drain_deadline the scheduler hands off in-flight streams too.
        # _dead marks a crashed/fault-killed scheduler loop — generate()
        # then fails FAST with a worker-lost signature instead of parking
        # requests on a queue nothing will ever drain.
        self._draining = False
        self._drain_handoff = True
        self._drain_deadline = 0.0
        self._dead: Optional[str] = None
        # elastic live resharding (docs/elastic_resharding.md): a posted
        # morph request the scheduler loop commits at a step boundary;
        # _resharding is advertised through load_metrics so the router
        # soft-excludes this worker for the morph window. The morpher
        # (parallel/morph.MeshMorpher) memoizes the compiled cross-mesh
        # permutation programs across morphs, lazily built on first use.
        self._reshard_req: Optional[dict] = None
        # claimed synchronously at reshard() entry (before the staging
        # await) so concurrent calls can't both pass the overlap check
        self._reshard_busy = False
        self._resharding = False
        self.morpher = None
        # the decode batch's per-slot step state: the host mirror, the
        # device's resident copy and what differs (engine/step_state.py)
        self._rows = StepState(
            cfg.max_batch_size, cfg.max_blocks_per_seq,
            window=mcfg.window_kv_pool, sharding=self._rows_sharding(),
            head=-(-max(cfg.decode_window, 1) // cfg.block_size))
        # sampling penalties (vLLM semantics — see ops/sampling):
        # device [B, V] output-token counts + prompt-membership mask,
        # allocated lazily on the first request that asks for a penalty
        self._pen_counts = None
        self._pen_mask = None
        # requested top-logprob count per slot (-1 = logprobs off;
        # 0 = chosen-token logprob only, no alternates)
        self._logprob_ks = np.full(cfg.max_batch_size, -1, np.int32)
        self._window_logprobs = None
        # live-request refcount per adapter NAME: an adapter a running
        # sequence depends on must never be LRU-evicted mid-stream
        self._adapter_refs: dict[str, int] = {}
        # metrics
        self.stats = {
            "requests_total": 0,
            "requests_active": 0,
            "tokens_generated": 0,
            "prompt_tokens_total": 0,
            "prefix_cache_hits_tokens": 0,
            "decode_steps": 0,
            "mixed_steps": 0,
            "mixed_prefill_segments": 0,
            "preemptions": 0,
            "spec_proposed": 0,
            "spec_accepted": 0,
            "drains_total": 0,
            "drain_handoffs": 0,
            "migration_resumes": 0,
            # fleet prefix cache: blocks served to peers straight out of
            # the DEVICE tier (bounded d2h export on fetch)
            "peer_serve_d2h_blocks": 0,
            # PRESERVE weight pre-stage lane (pre_stage_weights +
            # on-demand staging in generate): requests, bytes actually
            # copied host->device, and hits — a request that arrived to
            # find its adapter already staged (the prestage did its job)
            "weight_prestage_requests": 0,
            "weight_prestage_bytes": 0,
            "weight_prestage_hits": 0,
            # elastic resharding: completed morphs, KV blocks re-laid by
            # the last morph's commit, and the last morph's client-
            # visible hold window (quiesce -> resume, weight staging
            # excluded — it overlaps serving)
            "resharded_total": 0,
            "reshard_kv_moved_blocks": 0,
            "reshard_hold_ms": 0.0,
            # worst chosen-token logprob drift the kv-quant harness
            # (engine/kvquant.measure_logprob_drift) recorded against
            # this engine's quantized tiers; 0 until a harness ran
            "kv_quant_logprob_drift_max": 0.0,
            # int8-with-scales DEVICE cache lane (docs/kv_offload.md):
            # live quantized resident pages, cumulative page
            # requantizations (scale-growth rewrites), cumulative bytes
            # the int8 landings saved vs full width, d2h exports that
            # had to requantize (tier codec mismatch — the int8->int8
            # fast path keeps this at 0), and the measured decode
            # throughput of the low-precision lane
            "kv_device_quant_pages": 0,
            "kv_device_requants_total": 0,
            "kv_device_bytes_saved_total": 0,
            "kv_device_export_requant_total": 0,
            "lowprec_tok_s": 0.0,
            # XLA compile ledger (docs/observability.md): first-dispatch
            # count + wall-ms per distinct program bucket, and the
            # warmup coverage report (_warm coverage in warmup()) —
            # cold-bucket compile stalls in production become
            # attributable instead of anonymous 20-40s TTFTs
            "xla_compiles_total": 0,
            "xla_compile_ms_total": 0.0,
            "xla_warm_buckets": 0,
            "xla_reachable_buckets": 0,
            # autopilot actuation surface (docs/autopilot.md): control-
            # plane warmups the WarmupListener ran on this engine (and
            # their wall-ms — the compile tax paid off the hot path),
            # plus the QuarantineListener's mirror of this worker's
            # quarantine state so one scrape shows a worker was pulled
            # from rotation and how often
            "autopilot_warmups_applied": 0,
            "autopilot_warmup_ms_total": 0.0,
            "autopilot_quarantined": 0,
            "autopilot_quarantines_total": 0,
            # work counters, counted where the work is handed to the
            # device (_note_decode_work / _note_prefill_work): decode
            # rows of the batch against rows holding a live sequence,
            # prefill bucket lengths against their real tokens, and the
            # block-table pages the attention kernels were asked to
            # walk against the pages that hold live tokens
            **dict.fromkeys(WORK_COUNTERS, 0),
            # the dispatch thunks' hand-overs: host-to-device transfers
            # by the step's kind, and the decode-row dispatches that
            # sent the whole step state against those that sent a few
            # cells or nothing (engine/step_state.py)
            **{"step_handovers_" + k: 0 for k in KINDS.values()},
            "step_state_resyncs": 0,
            "step_state_resident": 0,
        }
        # expert layers whose routing the step programs count (models/
        # llama.MoeTally; the multi-host mirror runs programs of its own
        # and counts nothing). The counters come back as device arrays:
        # they wait here, with what the host counted at the dispatch,
        # until the step that finds them ready folds them into stats
        self._moe_layers = mcfg.moe_layers if mirror is None else 0
        self._moe_pending: deque = deque()
        self._state_rows = 0
        self._filter_steps = 0  # of the open step, for its span
        self._device_awaits = 0  # waits for the device so far (_on_device)
        if self._moe_layers:
            self.stats.update(dict.fromkeys(MOE_COUNTERS, 0))
        if self.state is not None:
            self.stats.update(dict.fromkeys(STATE_COUNTERS, 0))
        # the loop's clock (tracing/loop_clock.py): seconds by phase,
        # dispatches by kind and slow steps, kept in self.stats
        self._clock = LoopClock(self.stats, tracing.RECORDER)
        # SLO observatory worker-side latency distributions
        # (docs/observability.md): fixed log-bucket histograms riding
        # load_metrics as serialized vectors -> WorkerLoad.hists -> the
        # metrics component's per-worker histogram families. Observed
        # from the loop AND device-executor threads; a lost count under
        # a rare unlocked race is acceptable for this plane (same
        # tradeoff as the sanitizer's own histograms).
        self.hist = {
            "queue_wait_ms": Histogram(MS_BUCKETS),
            "prefill_ms": Histogram(MS_BUCKETS),
            "restore_ms": Histogram(MS_BUCKETS),
            "handoff_ms": Histogram(MS_BUCKETS),
        }
        # per-model TTFT distributions, lazily keyed by public model
        # name ("" = base): the multi-model SLO plane trace-replay
        # asserts against — measured arrival -> first emitted token,
        # the engine-side component of the frontend's TTFT
        self.hist_ttft: dict[str, Histogram] = {}
        # (kind, *bucket-shape) keys whose program has dispatched at
        # least once — the complement of "about to pay a compile stall"
        self._compiled_keys: set[tuple] = set()
        #: newest-last {kind, key, ms} entries (bounded); the flight
        #: recorder's autopsies carry the tail so a compile-stalled TTFT
        #: names the program that compiled inside its window
        self.compile_ledger: list[dict] = []
        self._weight_bytes: Optional[int] = None
        #: device_path_stats' view of how the leaves are laid out over
        #: the mesh, read once per layout (reshard clears it)
        self._leaf_layout: Optional[dict] = None

    def _use_pallas_for(self, mesh) -> bool:
        """Pallas attention path for ``mesh``: TPU backend + shapes the
        kernels cover. Sharded meshes run the kernels under shard_map
        over tp (head-parallel, no collectives) when tp divides the kv
        heads; otherwise the XLA path lets GSPMD handle the uneven
        split. A SHAPE gate decided once per mesh — a kernel the chip's
        compiler then refuses raises at first dispatch (_timed_dispatch),
        nothing switches paths behind a running engine. A method (not an
        __init__ constant) because reshard() must re-derive it for the
        new mesh — tp=4 may gate the kernels off where tp=1 allowed
        them. Records the choice and its reason in ``attention_path``
        (logged here, exported by device_path_stats)."""
        why_not = self._pallas_gate(mesh)
        path = {
            "path": "xla" if why_not else "pallas",
            "reason": why_not or "TPU backend, shapes covered by the "
            "in-repo Mosaic kernels",
        }
        if path != getattr(self, "attention_path", None):
            logger.info("attention path: %s (%s)", path["path"],
                        path["reason"])
        self.attention_path = path
        return not why_not

    def _pallas_gate(self, mesh) -> Optional[str]:
        """None when the Pallas kernels serve ``mesh``; otherwise the
        reason the XLA attention path serves instead."""
        cfg = self.cfg
        m = cfg.model
        backend = jax.default_backend()
        if backend != "tpu":
            if "cpu" not in (jax.config.jax_platforms or "").lower():
                # JAX found no TPU and nobody asked for the CPU: serving
                # on would hide the missing device behind a slow answer
                raise RuntimeError(
                    f"JAX backend is {backend!r}, not 'tpu', and "
                    "JAX_PLATFORMS does not name 'cpu': refusing to "
                    "serve on a fallback backend (set JAX_PLATFORMS=cpu "
                    "for a CPU run)"
                )
            return f"backend is {backend}, the kernels are TPU-only"
        if cfg.block_size % 8:
            return f"block_size {cfg.block_size} is not a multiple of 8"
        tp = mesh.shape["tp"] if mesh is not None else 1
        # quantized KV: the non-MLA ragged/decode/prefill kernels consume
        # int8/fp8 pages directly — the dequant cast fuses into their KV
        # page loads. The MLA latent kernels are bf16/f32-only (the
        # absorbed latent matmuls were never validated at sub-bf16).
        kv_dt = jax.tree.leaves(self.k_cache)[0].dtype
        if kv_dt not in (jnp.bfloat16, jnp.float32):
            if m.is_mla:
                return (f"quantized KV cache ({kv_dt}) on an MLA model: "
                        "the latent kernels are bf16/f32-only")
            if kv_dt not in (jnp.float8_e4m3fn, jnp.int8):
                return f"no kernel reads a {kv_dt} KV cache"
        if m.is_mla:
            # MLA: the latent decode kernel + merged one-write append
            # (ops/mla_attention_pallas). Query heads are the tp axis;
            # the latent cache replicates — but pp shards the cache's
            # LAYER axis, which the per-layer shard_map would have to
            # all-gather back, so pp meshes keep the XLA absorbed path.
            if m.kv_lora_rank % 128:
                return f"kv_lora_rank {m.kv_lora_rank} is not 128-aligned"
            # the decode kernel cuts its pages out of the pools itself:
            # whole 128-lane rows (``llama.rope_lanes`` seats 64 in 128)
            if llama.rope_lanes(m) % 128:
                return (f"qk_rope_head_dim {m.qk_rope_head_dim} does not "
                        "fill 128-lane rows")
            if mesh is not None and mesh.shape.get("pp", 1) != 1:
                return "pp shards the latent cache's layer axis"
            # the sharded latent kernels shard_map the QUERY-head axis
            if mesh is not None and m.num_heads % tp:
                return f"tp={tp} does not divide {m.num_heads} query heads"
            return None
        # 64 covers gpt-oss (head_dim=64): its cache rows, q, k and v are
        # 128 lanes wide with the upper half zero (``llama.kv_lanes``), so
        # every kernel sees a head of 128 (tests/test_tpu_compile.py
        # compiles decode_window for it). Sinks fold into the kernels'
        # merge denominators and per-layer windows are static per
        # unrolled layer call, so gpt-oss is NOT gated off.
        if m.head_dim % 64:
            return f"head_dim {m.head_dim} is not a multiple of 64"
        if m.attn_softcap:
            return "gemma-2 score softcapping lives in the XLA paths"
        if mesh is not None and m.num_kv_heads % tp:
            return f"tp={tp} does not divide {m.num_kv_heads} kv heads"
        return None

    def device_path_stats(self) -> dict:
        """Which device path this engine serves, as flat numeric gauges
        (the single-process frontend registers this on ``/metrics``):
        the attention path chosen at construction, every program bucket
        in the compile ledger with its first-dispatch ms, and each mesh
        device's allocator view — what chip_smoke.py (and a benchmark)
        asserts instead of trusting that a kernel served."""
        from .. import native
        from ..utils.compile_cache import CACHE_EVENTS

        ap = self.attention_path
        dev = jax.devices()[0]
        out = {
            f'engine_device{{platform="{dev.platform}",'
            f'kind="{dev.device_kind}"}}': len(jax.devices()),
            # block hashing served by native/*.cc (1) or its Python twin
            "engine_native_hasher": int(native.available()),
            "engine_compile_cache_hits": CACHE_EVENTS["hits"],
            "engine_compile_cache_misses": CACHE_EVENTS["misses"],
            "engine_use_pallas": int(self.use_pallas),
            f'engine_attention_path{{path="{ap["path"]}",'
            f'reason="{ap["reason"]}"}}': 1,
            "engine_xla_compiles_total": self.stats["xla_compiles_total"],
            "engine_xla_compile_ms_total": round(
                self.stats["xla_compile_ms_total"], 3),
            "engine_prefix_cache_hits_tokens": self.stats[
                "prefix_cache_hits_tokens"],
            "engine_mixed_steps": self.stats["mixed_steps"],
            "engine_decode_steps_total": self.stats["decode_steps"],
            "engine_slow_steps_total": self.stats["slow_steps"],
            "engine_preemptions_total": self.stats["preemptions"],
            "engine_kv_pages_used": self.kv.allocator.used_count,
            "engine_kv_pages_total": self.kv.allocator.num_blocks - 1,
        }
        for phase, s in self._clock.totals().items():
            out[f'engine_loop_seconds_total{{phase="{phase}"}}'] = round(s, 6)
        for kind in KINDS.values():
            out[f'engine_steps_total{{kind="{kind}"}}'] = self.stats[
                f"steps_{kind}"]
            # a step's seconds (idle apart), device steps and seconds
            # with nothing outstanding on the device, by its kind
            out[f'engine_step_seconds_total{{kind="{kind}"}}'] = round(
                self.stats[f"step_seconds_{kind}"], 6)
            out[f'engine_device_steps_total{{kind="{kind}"}}'] = self.stats[
                f"device_steps_{kind}"]
            out[f'engine_step_exposed_seconds_total{{kind="{kind}"}}'] = round(
                self.stats[f"step_exposed_seconds_{kind}"], 6)
            out[f'engine_step_handovers_total{{kind="{kind}"}}'] = self.stats[
                f"step_handovers_{kind}"]
            # programs of the kind enqueued while another of the loop's
            # was still outstanding (LoopClock.enqueued)
            out[f'engine_dispatch_chained_total{{kind="{kind}"}}'] = (
                self.stats[f"dispatch_chained_{kind}"])
        for name in ("step_state_resyncs", "step_state_resident"):
            out[f"engine_{name}_total"] = self.stats[name]
        for name in WORK_COUNTERS + (MOE_COUNTERS if self._moe_layers else ()):
            out[f"engine_{name}_total"] = self.stats[name]
        # the KV manager's own (a model with a window pool or a state)
        for name, v in self.kv.stats.items():
            out[f"engine_{name}_total"] = v
        out.update(self.kv.gauges())
        if self.state is not None:
            for name in STATE_COUNTERS:
                out[f"engine_{name}_total"] = self.stats[name]
            out["engine_state_bytes"] = sum(
                a.size * a.dtype.itemsize for a in self.state.values())
        for e in self.compile_ledger:
            key = ",".join(str(k) for k in e["key"]).replace('"', "'")
            out[f'engine_compiled_program_ms{{kind="{e["kind"]}",'
                f'key="{key}"}}'] = e["ms"]
        devices = (
            list(self.mesh.devices.flat) if self.mesh is not None
            else jax.local_devices()[:1]
        )
        out["engine_mesh_devices"] = len(devices)
        if self._leaf_layout is None:
            try:
                self._leaf_layout = self._read_leaf_layout()
            except RuntimeError:
                # a scrape can fall between a dispatch donating the KV
                # cache and the engine taking the new one back ("Array
                # has been deleted"): the next scrape reads the layout,
                # this one must not lose every other series with it
                logger.debug("leaf layout unreadable mid-dispatch",
                             exc_info=True)
        out.update(self._leaf_layout or {})
        for d in devices:
            ms = d.memory_stats() or {}  # CPU reports nothing
            for name in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                out[f'engine_device_{name}{{device="{d.id}"}}'] = int(
                    ms.get(name, 0))
        return out

    def _read_leaf_layout(self) -> dict:
        """How many devices hold a shard of each param/cache leaf (min
        over leaves), and the bytes that are partitioned, not copied."""
        leaves = jax.tree.leaves(
            (self.params, self.k_cache, self.v_cache))
        return {
            "engine_leaf_devices_min": min(
                len({sh.device for sh in x.addressable_shards})
                for x in leaves
            ),
            "engine_partitioned_bytes": sum(
                x.nbytes for x in leaves
                if x.addressable_shards[0].data.nbytes < x.nbytes
            ),
            "engine_leaf_bytes_total": sum(x.nbytes for x in leaves),
        }

    # ---------------- public api ----------------

    def start(self) -> None:
        if self._loop_task is None:
            self._loop_task = asyncio.get_running_loop().create_task(self._loop())

    async def close(self) -> None:
        self._closed = True
        self._wake.set()
        if self._loop_task:
            self._loop_task.cancel()
            self._loop_task = None
        if self.offload is not None:
            self.offload.close()
        if self.mirror is not None:
            # release follower ranks blocked on the next broadcast; take the
            # device lock so the halt can't interleave with a decode/prefill
            # broadcast still running in an executor thread
            async with self._device_lock:
                await asyncio.get_running_loop().run_in_executor(
                    None, self.mirror.lead_halt
                )

    async def warmup(self, decode: bool = True) -> list[int]:
        """Compile the serving paths BEFORE real traffic: one dummy
        request per reachable prefill bucket (chunked prefill buckets
        every chunk, so larger prompts only ever see these shapes) plus
        the full decode-window ladder. Without this, the first real
        request at each new shape pays a 20-40s XLA compile on its TTFT
        — the TPU analog of the reference engines' startup
        profile/warmup pass.

        Details that make the coverage real:
          * each bucket gets its own pseudo-random prompt — a repeated
            prompt would prefix-hit the previous request's committed
            blocks and prefill only the (smaller-bucket) tail;
          * a prompt of min(prefill_chunk, max_context-1) tokens warms
            the TOP bucket real chunks round up to, which the
            power-of-two list alone misses when that limit isn't a
            bucket boundary;
          * the first request's max_tokens is 2*decode_window: one token
            comes from the prefill sample, so decode has a 2W-1 budget
            and _pick_window walks the whole power-of-two ladder
            W, W/2, ..., 1 (the chained loop: W/2, ..., 1, the windows
            it ever runs) — the smaller windows (especially 1) are
            exactly what concurrent admission traffic dispatches, so
            leaving them cold would inject the compile stall mid-stream
            under real load;
          * speculation is held off for the duration: repeated-token
            dummy prompts are the canonical prompt-lookup trigger, and
            an engaged verify would swallow the very window dispatches
            being warmed (the verify itself still compiles on its first
            organic proposal).

        ``decode=False`` skips the window ladder entirely (every request
        stops at its prefill-sampled token) — for prefill-only disagg
        workers, which never dispatch decode windows.

        Dummy blocks enter the prefix cache content-addressed and age
        out LRU like any other. Returns the warmed bucket sizes.
        """
        lim = min(self.cfg.prefill_chunk, self.cfg.max_context - 1)
        lengths = [b for b in PREFILL_BUCKETS if b <= lim]
        sizes = list(lengths)
        top = _bucket(lim)
        if top not in sizes:
            lengths.append(lim)
            sizes.append(top)
        W = self.cfg.decode_window
        V = self.cfg.model.vocab_size
        gamma, self.cfg.spec_gamma = self.cfg.spec_gamma, 0
        try:
            for i, n_toks in enumerate(lengths):
                # per-bucket pseudo-random prompts: distinct across
                # buckets (no prefix-cache hit shrinking the prefilled
                # shape) and non-repeating within one (no n-gram bait)
                toks = np.random.RandomState(1000 + i).randint(
                    0, V, n_toks
                ).tolist()
                req = PreprocessedRequest(
                    token_ids=toks,
                    stop_conditions=StopConditions(
                        # the first (shortest) prompt has the context
                        # headroom to walk the decode-window ladder; the
                        # rest stop at their prefill-sampled token
                        max_tokens=2 * W if (i == 0 and decode) else 1,
                        ignore_eos=True,
                    ),
                    sampling_options=SamplingOptions(temperature=0.0),
                    eos_token_ids=[],
                )
                async for _ in self.generate(Context(req)):
                    pass
        finally:
            self.cfg.spec_gamma = gamma
        # compile-warmup coverage report (docs/observability.md): how
        # many serving-path program buckets this warmup actually
        # compiled vs what production traffic can reach through it —
        # the gap is the cold-bucket compile-stall exposure the ledger
        # will attribute later (xla_warm_buckets/xla_reachable_buckets
        # gauges through load_metrics)
        warm = sum(
            1 for k in self._compiled_keys
            if k[0] in ("prefill", "decode", "mixed")
        )
        reachable = len(sizes)
        if decode:
            # the _pick_window power-of-two ladder (a chained window runs
            # at most half decode_window: _chained_window_steps)
            w = max(W // 2, 1) if self._chained() else W
            while w >= 1:
                reachable += 1
                w //= 2
        self.stats["xla_warm_buckets"] = warm
        self.stats["xla_reachable_buckets"] = reachable
        logger.info(
            "warmup coverage: %d/%d reachable program buckets compiled "
            "(%d total compiles, %.0f ms compile wall)",
            warm, reachable, self.stats["xla_compiles_total"],
            self.stats["xla_compile_ms_total"],
        )
        return sizes

    async def generate(self, request: Context) -> AsyncIterator[LLMEngineOutput]:
        if self._draining or self._dead is not None:
            # draining/dead worker: bounce immediately with a worker-lost
            # signature so a migration-aware frontend re-dispatches —
            # never park work on a queue this scheduler won't drain
            yield LLMEngineOutput(
                finish_reason=FinishReason.ERROR,
                text=self._dead or MIGRATION_SIGNAL,
            )
            return
        self.start()
        faultpoints.hit_sync("admission", request_id=request.id)
        req: PreprocessedRequest = request.data
        if isinstance(req, dict):
            req = PreprocessedRequest.from_dict(req)
        if not req.token_ids:
            yield LLMEngineOutput(finish_reason=FinishReason.ERROR, text="empty prompt")
            return
        if len(req.token_ids) >= self.cfg.max_context:
            yield LLMEngineOutput(finish_reason=FinishReason.ERROR)
            return
        if not self._tokens_in_vocab(req.token_ids):
            # out-of-vocab ids make the embedding gather IMPLEMENTATION-
            # DEFINED (XLA clamps on one device; a multi-process sharded
            # mesh lands OOB rows differently), so the same request can
            # legally produce different streams on different meshes —
            # found as the test_multihost_compose cancel-after-restore
            # "token mismatch", which was OOB prompt ids all along.
            # Reject loudly instead of serving garbage.
            yield LLMEngineOutput(
                finish_reason=FinishReason.ERROR,
                text=f"prompt token id out of range [0, "
                     f"{self.cfg.model.vocab_size})",
            )
            return
        # multi-LoRA lane: resolve the request's model name to base
        # (adapter_id -1) or a registered adapter. Fleets without
        # --adapters skip all of this — any model name passes through
        # untouched (legacy single-model behavior, the frontend already
        # checked registration).
        adapter_id, model_name = -1, ""
        if self.adapters is not None and req.model:
            base = self.cfg.served_model_name
            if self.adapters.is_known(req.model):
                try:
                    adapter_id = self._claim_adapter(req.model)
                except RuntimeError as e:
                    yield LLMEngineOutput(
                        finish_reason=FinishReason.ERROR, text=str(e)
                    )
                    return
                model_name = req.model
            elif base and req.model != base:
                # same clean signature the frontend's 404 carries —
                # worker-side requests (bench, direct dispatch) get the
                # identical body instead of serving base-model tokens
                # under an unknown name
                yield LLMEngineOutput(
                    finish_reason=FinishReason.ERROR,
                    text=f"unknown model {req.model!r}",
                )
                return
        seq = _Sequence(
            request=req,
            context=request.context,
            out_queue=asyncio.Queue(),
            tokens=list(req.token_ids),
            prompt_len=len(req.token_ids),
            adapter_id=adapter_id,
            model=model_name,
            trace=tracing.current_trace() if tracing.enabled() else None,
        )
        resume = (
            req.annotations.get("resume")
            if isinstance(req.annotations, dict) else None
        )
        if isinstance(resume, dict):
            # migration resume (resilience/migration.py): token_ids =
            # original prompt + tokens already delivered. Restoring the
            # prompt/generated split makes the continuation exact: the
            # per-step sampling keys fold_in(seed, generated) pick up at
            # the seam, penalty state rebuilds from the TRUE output list,
            # and max/min_tokens + usage count from the original prompt.
            try:
                plen = int(resume.get("prompt_len", 0))
            except (TypeError, ValueError):
                plen = 0
            if 0 < plen <= len(req.token_ids):
                seq.prompt_len = plen
                seq.generated = len(req.token_ids) - plen
                self.stats["migration_resumes"] += 1
        self.stats["requests_total"] += 1
        self.stats["prompt_tokens_total"] += seq.prompt_len
        await self._waiting.put(seq)
        self._wake.set()
        while True:
            out = await seq.out_queue.get()
            if out is None:
                return
            yield out
            if out.is_final():
                return

    def _claim_adapter(self, name: str) -> int:
        """Resolve an adapter request to its device-stack slot, staging
        on demand (the cold-load stall ``pre_stage_weights`` exists to
        hide) and pinning the adapter against LRU eviction for the
        request's lifetime (released in ``_finish``)."""
        reg = self.adapters
        if reg.is_staged(name):
            # the prestage (or a previous request) already paid the
            # host->device copy — this is the hit the PRESERVE lane
            # measures
            self.stats["weight_prestage_hits"] += 1
            slot = reg.slot_of(name)
        else:
            in_use = {n for n, c in self._adapter_refs.items() if c > 0}
            slot, nbytes = reg.stage(name, in_use=in_use)
            self.stats["weight_prestage_bytes"] += nbytes
        self._adapter_refs[name] = self._adapter_refs.get(name, 0) + 1
        return slot

    def served_models(self) -> list[str]:
        """Every public name this worker answers to: the base model
        first ("" = any name, the legacy wildcard), then each configured
        adapter. Advertised through load_metrics so ``select_worker``
        filters on model identity before scoring."""
        out = [self.cfg.served_model_name or ""]
        if self.adapters is not None:
            out.extend(self.adapters.names())
        return out

    def _hbm_stats(self) -> dict:
        """TPU device-memory telemetry (docs/observability.md): real
        allocator numbers from ``device.memory_stats()`` where the
        backend exposes them (TPU does; CPU returns nothing), with the
        engine's own attribution — KV pool and weight bytes are computed
        from the arrays themselves, so they are exact on every backend.
        When the allocator view is unavailable, ``in_use`` falls back to
        the attributed sum (flagged by ``limit == 0``) so the gauge
        exists fleet-wide instead of silently disappearing on CPU."""
        kv = sum(int(getattr(c, "nbytes", 0) or 0)
                 for c in jax.tree.leaves((self.k_cache, self.v_cache)))
        if self.k_scales is not None:
            # the int8 cache's per-page scale planes are KV-pool bytes
            kv += int(self.k_scales.nbytes) + int(self.v_scales.nbytes)
        if self._weight_bytes is None:
            try:
                self._weight_bytes = sum(
                    int(getattr(x, "nbytes", 0) or 0)
                    for x in jax.tree.leaves(self.params)
                )
            except Exception:  # noqa: BLE001 — attribution is best-effort
                self._weight_bytes = 0
        in_use = limit = 0
        try:
            dev = (
                self.mesh.devices.flat[0] if self.mesh is not None
                else jax.local_devices()[0]
            )
            ms = dev.memory_stats() or {}
            in_use = int(ms.get("bytes_in_use", 0) or 0)
            limit = int(ms.get("bytes_limit", 0) or 0)
        except Exception:  # noqa: BLE001 — backend without memory_stats
            logger.debug("device memory_stats unavailable", exc_info=True)
        if not in_use:
            in_use = kv + self._weight_bytes
        return {"in_use": in_use, "limit": limit, "kv_pool": kv,
                "weights": self._weight_bytes}

    async def profile(self, seconds: float, out_dir: Optional[str] = None) -> str:
        """On-demand ``jax.profiler`` capture (the frontend's
        ``POST /profile?seconds=N&dir=<path>``): trace every device for N
        seconds into ``out_dir`` (a fresh temporary directory without
        one) and return its path (TensorBoard / Perfetto-loadable). Runs
        in an executor thread so serving, lease keepalives and scrapes
        continue underneath the capture. The Python tracer is OFF: it
        slows exactly the host code whose gaps a profile is meant to
        size, and under ``--trace`` the loop's own annotations
        (tracing/loop_clock.py) say what the host did, on the clock of
        the device ops. The capture starts when asked and lasts
        ``seconds``; if the loop enqueued nothing in that time (no
        request was being served) it goes on until the first step that
        follows is done, ``3 * seconds`` more at most, so that a reader
        is not handed a profile without one device op."""
        import tempfile

        if out_dir is None:
            out_dir = tempfile.mkdtemp(prefix="dynamo-profile-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2

        def _capture() -> str:
            os.makedirs(out_dir, exist_ok=True)
            jax.profiler.start_trace(out_dir, profiler_options=options)
            try:
                # the trace's first event: readers place the capture on
                # their own clock by it
                with jax.profiler.TraceAnnotation("engine.profile"):
                    clk, seen = self._clock, self._clock.programs
                    time.sleep(seconds)
                    if clk.programs == seen:  # nothing was dispatched
                        late = time.monotonic() + 3 * seconds
                        while (clk.programs == seen
                               and time.monotonic() < late):
                            time.sleep(0.005)
                        step = clk.seq  # the step of the first dispatch
                        while clk.seq == step and time.monotonic() < late:
                            time.sleep(0.005)
            finally:
                jax.profiler.stop_trace()
            return out_dir

        return await asyncio.get_running_loop().run_in_executor(
            None, _capture
        )

    def load_metrics(self) -> dict:
        """Worker stats for the KV router plane (ref ForwardPassMetrics)."""
        self._register_device_executor()
        self._fold_quant_counters()
        out = {}
        # SLO observatory: worker latency distributions as serialized
        # bucket vectors (merged loss-free downstream), the XLA compile
        # ledger counters + warmup coverage, and HBM telemetry
        out["hist_queue_wait_ms"] = self.hist["queue_wait_ms"].to_vec()
        out["hist_prefill_ms"] = self.hist["prefill_ms"].to_vec()
        out["hist_restore_ms"] = self.hist["restore_ms"].to_vec()
        out["hist_handoff_ms"] = self.hist["handoff_ms"].to_vec()
        # per-model TTFT families keyed by public model name ("" = base)
        out["hist_ttft_ms"] = {
            m: h.to_vec() for m, h in self.hist_ttft.items()
        }
        out["xla_compiles_total"] = self.stats["xla_compiles_total"]
        out["xla_compile_ms_total"] = round(
            self.stats["xla_compile_ms_total"], 3
        )
        out["xla_warm_buckets"] = self.stats["xla_warm_buckets"]
        out["xla_reachable_buckets"] = self.stats["xla_reachable_buckets"]
        # autopilot actuation mirrors (warmup/quarantine listeners)
        out["autopilot_warmups_applied"] = self.stats[
            "autopilot_warmups_applied"]
        out["autopilot_warmup_ms_total"] = self.stats[
            "autopilot_warmup_ms_total"]
        out["autopilot_quarantined"] = self.stats["autopilot_quarantined"]
        out["autopilot_quarantines_total"] = self.stats[
            "autopilot_quarantines_total"]
        hbm = self._hbm_stats()
        out["hbm_bytes_in_use"] = hbm["in_use"]
        out["hbm_bytes_limit"] = hbm["limit"]
        out["hbm_kv_pool_bytes"] = hbm["kv_pool"]
        out["hbm_weights_bytes"] = hbm["weights"]
        if self.offload is not None:
            # piggyback the (loop-side) stats scrape to publish queued
            # tier-drop removals: blocks that left the LAST local tier
            # must stop counting as this worker's radix residency
            self.offload.flush_dropped()
            out.update(self.offload.stats())
        # runtime-sanitizer counters (analysis/sanitizer.py): zeros when
        # no sanitizer has ever been active in this process; under
        # --sanitize (or the test suite) they surface loop stalls and
        # worst lock holds through the scrape -> metrics-gauge plane
        out.update(sanitizer.counters())
        kv_share, kv_used, kv_total = self.kv.usage()
        return out | self.kv.stats | {
            # mixed-batch fusion activity (prefill chunks riding decode
            # steps, and how many prompt segments packed into them) —
            # lets the router/metrics plane see whether decode ITL is
            # being shielded from concurrent prefill and whether queued
            # prompts are advancing together or head-of-line blocking
            "mixed_steps": self.stats["mixed_steps"],
            "mixed_prefill_segments": self.stats["mixed_prefill_segments"],
            # (over the pools, where the window layers have one of their own)
            "kv_active_blocks": kv_used,
            "kv_total_blocks": kv_total,
            "gpu_cache_usage_perc": kv_share,  # dynlint: disable=unscraped-stat -- reference-schema compat key (vLLM ForwardPassMetrics); consumers derive usage from kv_active/kv_total
            "request_active_slots": self._n_active,
            "request_total_slots": self.cfg.max_batch_size,
            "num_requests_waiting": self._waiting_size(),
            # cumulative serving counters: the planner's telemetry
            # aggregator derives fleet arrival/throughput rates from
            # scrape-to-scrape deltas of these
            "requests_total": self.stats["requests_total"],
            "tokens_generated": self.stats["tokens_generated"],
            "prompt_tokens_total": self.stats["prompt_tokens_total"],
            # resilience surface: the router deprioritizes draining
            # workers; the metrics component tracks drain/migration volume
            "draining": int(self._draining),
            "drains_total": self.stats["drains_total"],
            "drain_handoffs": self.stats["drain_handoffs"],
            "migration_resumes": self.stats["migration_resumes"],
            # transfer-cost-aware placement surface (costmodel.py): the
            # worker's observed link bandwidths + corrected prefill
            # throughput + block geometry + slice identity — everything
            # the router needs to convert this worker's overlap depths
            # into predicted TTFT milliseconds
            "kv_block_bytes": self.kv_block_bytes,
            # tier/wire bytes per block under --kv-quant (== the full
            # width when the codec is off): what restore/pull legs
            # actually move, so the router prices them at these bytes
            "kv_wire_block_bytes": self.kv_wire_block_bytes,
            # the kv-quant quality gate's worst observed drift (set by
            # kvquant.measure_logprob_drift runs against this engine)
            "kv_quant_logprob_drift_max": self.stats[
                "kv_quant_logprob_drift_max"],
            "kv_block_size": self.cfg.block_size,
            "kv_slice_fp": self._slice_fp(),
            # the ACTUALLY-deployed TP degree: seeds the planner's
            # morph guard so a restarted planner reasons from the
            # pool's real layout instead of tp_min
            "mesh_tp": self.cfg.mesh.tp if self.cfg.mesh is not None else 1,
            # elastic-reshard surface: ``resharding`` marks the morph
            # window (the router soft-excludes this worker for it, like
            # ``draining`` but transient); the counters/gauges feed the
            # metrics component (resharded_total, reshard_hold_ms,
            # reshard_kv_moved_blocks)
            "resharding": int(self._resharding),
            "resharded_total": self.stats["resharded_total"],
            "reshard_hold_ms": self.stats["reshard_hold_ms"],
            "reshard_kv_moved_blocks": self.stats[
                "reshard_kv_moved_blocks"],
            "peer_serve_d2h_blocks_total": self.stats[
                "peer_serve_d2h_blocks"],
            "weight_prestage_requests": self.stats[
                "weight_prestage_requests"],
            "weight_prestage_bytes": self.stats["weight_prestage_bytes"],
            "weight_prestage_hits": self.stats["weight_prestage_hits"],
            # multi-model surface: every name this worker answers to
            # (base first, "" = legacy wildcard) — select_worker filters
            # on membership before scoring
            "served_models": self.served_models(),
            # int8-with-scales device-cache lane (zeros unless
            # kv_cache_dtype="int8"): resident quantized pages,
            # cumulative scale-growth requantizations, bytes the int8
            # landings saved vs full width, exports that paid a
            # requantize, and the lane's measured decode throughput
            "kv_device_quant_pages": self.stats["kv_device_quant_pages"],
            "kv_device_requants_total": self.stats[
                "kv_device_requants_total"],
            "kv_device_bytes_saved_total": self.stats[
                "kv_device_bytes_saved_total"],
            "kv_device_export_requant_total": self.stats[
                "kv_device_export_requant_total"],
            "lowprec_tok_s": self.stats["lowprec_tok_s"],
        } | (self.cost.counters() if self.cost is not None else {})

    def _register_device_executor(self) -> None:
        """Register the loop's default executor (every device dispatch
        rides ``run_in_executor(None, ...)``) for the sanitizer's
        executor-pressure surface. Lazy + idempotent: asyncio creates
        the default executor on first use, so the first scrape after
        real work picks it up; ``register_executor`` no-ops on repeats."""
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # scraped off-loop (tests constructing engines raw)
        # asyncio offers no public getter for the lazily-built default
        # executor; reading the private slot is the only non-invasive way
        # to observe it without forcing our own pool onto the loop
        ex = getattr(loop, "_default_executor", None)
        if ex is not None:
            sanitizer.register_executor(ex, "device")

    # ---------------- graceful drain (resilience/drain.py) ----------------

    async def drain(self, deadline_s: float = 10.0, handoff: bool = True) -> dict:
        """Stop admitting and retire in-flight work: requests get
        ``deadline_s`` to finish naturally; with ``handoff=True`` the
        stragglers (and everything still queued) are terminated with the
        migration signal so a migration-aware frontend resumes them on a
        surviving worker as prompt + tokens-so-far. ``handoff=False``
        waits for natural completion regardless of the deadline."""
        if not self._draining:
            self._draining = True
            self.stats["drains_total"] += 1
        self._drain_handoff = handoff
        self._drain_deadline = asyncio.get_running_loop().time() + deadline_s
        self._wake.set()
        handoffs_before = self.stats["drain_handoffs"]
        while (
            self._has_pending_work()
            and self._loop_task is not None
            and not self._closed
            and self._dead is None
        ):
            await asyncio.sleep(0.01)
        return {"handed_off": self.stats["drain_handoffs"] - handoffs_before}

    def _drain_tick(self) -> None:
        """One scheduler-loop pass of drain progress (runs at an
        iteration boundary, so it never races a device dispatch)."""
        if not self._drain_handoff:
            return
        # queued-but-unstarted work first: nothing is computed yet, so
        # the re-dispatch loses nothing — hand it back immediately
        while not self._waiting_is_empty():
            self._handoff_seq(self._pop_waiting())
        if asyncio.get_running_loop().time() < self._drain_deadline:
            return
        # deadline passed: hand off the stragglers still on the device.
        # _remote_ready waits until here too — its prefill + KV transfer
        # are already paid for, and admission keeps pulling it into the
        # batch while the drain window is open, so it can finish locally
        while self._remote_ready:
            self._handoff_seq(self._remote_ready.pop())
        for st in list(self._prefill_states):
            self.stats["drain_handoffs"] += 1
            self._abort_prefill(st, FinishReason.ERROR, text=MIGRATION_SIGNAL)
        for seq in list(self._active):
            if seq is not None and not seq.finished:
                self._handoff_seq(seq)

    def _handoff_seq(self, seq: "_Sequence") -> None:
        """Terminate one stream with the migration signal (tokens already
        emitted stay valid — the frontend splices the continuation)."""
        if seq.finished:
            return
        self.stats["drain_handoffs"] += 1
        seq.out_queue.put_nowait(
            LLMEngineOutput(
                finish_reason=FinishReason.ERROR, text=MIGRATION_SIGNAL
            )
        )
        self._finish(seq, FinishReason.ERROR, emit=False)

    # ---------------- elastic live resharding ----------------
    # (docs/elastic_resharding.md — quiesce / morph / resume)

    @staticmethod
    def _mesh_shape(mc: Optional[MeshConfig]) -> tuple:
        return (mc.dp, mc.pp, mc.sp, mc.ep, mc.tp) if mc is not None else ()

    async def reshard(
        self,
        mesh: Optional[MeshConfig],
        hold: bool = True,
        force: bool = False,
    ) -> dict:
        """Morph this engine's parallelism degree LIVE: re-lay weights
        and the paged KV pool onto ``mesh`` without dropping a token.

        Protocol: (1) the new layout's weights are PRE-STAGED off the
        hold window (PRESERVE-style — the move overlaps continued
        serving, since params are read-only to dispatch); (2) the
        scheduler loop quiesces at a step boundary (the pipelined
        window drains; the device lock serializes against disagg
        hooks), in-flight and queued requests are *held*, not handed
        off; (3) KV + penalty planes re-lay through the same compiled
        cross-mesh permutation programs (parallel/morph.MeshMorpher);
        (4) one assignment-only commit swaps every piece of device
        state plus ``self.mesh`` — a crash lands wholly before or
        wholly after it (the ``mid_reshard`` faultpoint phases walk
        exactly this matrix); (5) the loop resumes: RNG streams
        continue token-exactly because sampling keys fold_in(seed,
        generated) from host-side state the morph never touches, and
        penalty counts/masks moved bit-identically.

        ``hold=False`` hands off in-flight streams via the PR 4
        migration path instead of holding them (deadline-pressured
        requests; queued work is always held — it costs nothing).
        ``force=True`` re-lays even when the mesh shape is unchanged
        (absorbing a lost host: same logical shape, new device set).
        Multi-host mirrors raise :class:`ReshardUnsupported` — their
        callers drain-with-handoff instead.  Returns the morph stats
        dict ({"changed", "kv_moved_blocks", "hold_ms", ...})."""
        self.kv.refuse_transfer("reshard")
        if self.mirror is not None:
            raise ReshardUnsupported(
                "multi-host mirrored engines cannot morph live; drain "
                "with handoff and restart on the new mesh instead"
            )
        if self._dead is not None:
            raise RuntimeError(self._dead)
        if self._closed:
            raise RuntimeError("engine closed")
        if self._reshard_busy:
            raise RuntimeError("a reshard is already in flight")
        same = self._mesh_shape(mesh) == self._mesh_shape(self.cfg.mesh)
        if same and not force:
            return {"changed": False, "kv_moved_blocks": 0, "hold_ms": 0.0}
        self.start()
        # claim the morph slot BEFORE the staging await: a second
        # reshard() racing through the checks above would otherwise
        # overwrite this one's posted request and park its caller on a
        # future nothing ever resolves
        self._reshard_busy = True
        t0 = time.perf_counter()
        self._resharding = True  # advertised: router soft-excludes now
        loop = asyncio.get_running_loop()
        try:
            # PRESERVE-style pre-stage: build the new mesh and move the
            # weights onto its layout while the engine keeps serving —
            # only the KV re-lay and the commit need the hold window
            new_mesh, staged = await loop.run_in_executor(
                None, self._stage_reshard, mesh
            )
            # the staging await dropped the loop: an engine closed (or
            # loop-crashed) meanwhile would never run _reshard_step, so
            # posting now would hang this caller forever
            if self._closed or self._dead is not None:
                raise RuntimeError(self._dead or "engine closed")
        except BaseException:
            self._resharding = False
            self._reshard_busy = False
            raise
        fut = loop.create_future()
        self._reshard_req = {
            "mesh_cfg": mesh,
            "new_mesh": new_mesh,
            "staged": staged,
            "hold": hold,
            "fut": fut,
            "t0": t0,
        }
        self._wake.set()
        return await fut

    def _stage_reshard(self, mesh_cfg: Optional[MeshConfig]):
        """Executor thread, NO device lock: resolve the logical weight
        layout against the target mesh and move the params there.
        Dispatch only ever reads params (the KV caches are the donated
        arrays), so staging overlaps live decode — the new layout's
        weight load never sits on the hold window."""
        from ..parallel.morph import MeshMorpher

        faultpoints.hit_sync("mid_reshard", phase="pre_stage")
        new_mesh = make_mesh(mesh_cfg) if mesh_cfg is not None else None
        if self.morpher is None:
            self.morpher = MeshMorpher()
        staged = self.morpher.apply_tree(
            self.params, self.layout.param_shardings(self.params, new_mesh)
        )
        jax.block_until_ready(staged)
        return new_mesh, staged

    async def _reshard_step(self) -> None:
        """One posted morph, run by the scheduler loop at an iteration
        boundary (so no dispatch is in flight) — quiesce, commit,
        resume. A failed morph leaves the engine wholly on the old
        layout and surfaces the error to the caller without killing the
        serving loop; a FaultInjected kill propagates (that IS the
        crash-mid-morph experiment)."""
        req = self._reshard_req
        fut = req["fut"]
        try:
            if not req["hold"]:
                # requests that cannot be held through the morph take
                # the PR 4 migration path NOW: tokens already delivered
                # stay valid, the frontend splices the continuation on
                # a worker that isn't morphing (the router is already
                # soft-excluding this one via the resharding flag)
                while self._remote_ready:
                    self._handoff_seq(self._remote_ready.pop())
                for st in list(self._prefill_states):
                    self.stats["drain_handoffs"] += 1
                    self._abort_prefill(
                        st, FinishReason.ERROR, text=MIGRATION_SIGNAL
                    )
                for seq in list(self._active):
                    if seq is not None and not seq.finished:
                        self._handoff_seq(seq)
            # a pipelined decode window still in flight would chain
            # tokens across the morph's program swap — drain it first
            await self._drain_inflight()
            t_hold = time.perf_counter()
            async with self._device_lock:
                out = await asyncio.get_running_loop().run_in_executor(
                    None, self._commit_reshard_device, req
                )
            out["hold_ms"] = round((time.perf_counter() - t_hold) * 1e3, 3)
            out["total_ms"] = round((time.perf_counter() - req["t0"]) * 1e3, 3)
            self.stats["reshard_hold_ms"] = out["hold_ms"]
            logger.info(
                "resharded to %s: %d KV blocks re-laid, hold %.1fms "
                "(total %.1fms)", out["mesh"], out["kv_moved_blocks"],
                out["hold_ms"], out["total_ms"],
            )
            if not fut.done():
                fut.set_result(out)
        except asyncio.CancelledError:
            fut.cancel()
            raise
        except FaultInjected as e:
            if not fut.done():
                fut.set_exception(e)
            raise
        except Exception as e:  # noqa: BLE001 — a failed morph must not
            # kill the serving loop; the engine stays on the old layout
            logger.exception("reshard failed; engine stays on old layout")
            if not fut.done():
                fut.set_exception(e)
        finally:
            self._reshard_req = None
            self._resharding = False
            self._reshard_busy = False

    def _commit_reshard_device(self, req: dict) -> dict:
        """Executor thread, device lock held, loop quiesced: re-lay the
        paged KV pool (+ penalty planes) onto the target layout, then
        commit everything in one assignment-only block. The two staging
        faultpoint phases sit BEFORE the block and the committed phase
        AFTER it — there is deliberately nothing fallible in between,
        which is what makes a mid-morph kill leave the engine on
        exactly one layout."""
        new_mesh = req["new_mesh"]
        m = self.morpher
        # the device-side requant accumulator (_note_quant_step) lives
        # on the OLD device set; fold it to the host stat now — the
        # loop is quiesced, so the one-scalar sync is free — or the
        # first post-morph dispatch would add an old-mesh scalar to a
        # new-mesh one and trip an incompatible-devices error
        self._fold_quant_counters()
        faultpoints.hit_sync("mid_reshard", phase="quiesced")
        cache_sh = self.layout.cache_sharding(new_mesh)
        new_k = m.apply(self.k_cache, cache_sh)
        new_v = m.apply(self.v_cache, cache_sh)
        rep = self.layout.replicated_sharding(new_mesh)
        new_pc = new_pm = None
        if self._pen_counts is not None:
            new_pc = m.apply(self._pen_counts, rep)
            new_pm = m.apply(self._pen_mask, rep)
        new_ks = new_vs = None
        if self.k_scales is not None:
            # int8 device cache: the scale planes ride the same morph
            # (replicated layout, page axis unsharded) so every re-laid
            # page keeps its bit-identical dequant scale
            new_ks = m.apply(self.k_scales, rep)
            new_vs = m.apply(self.v_scales, rep)
        # the staged state must be REAL (transfers landed) before the
        # commit claims the engine is on the new layout
        jax.block_until_ready(
            (new_k, new_v) if new_ks is None
            else (new_k, new_v, new_ks, new_vs)
        )
        # every fallible computation happens BEFORE the commit: the
        # dynflow commit-block-purity rule found _use_pallas_for being
        # called inside it — had that call raised, params/caches/mesh
        # would already have swapped while use_pallas (and the caller's
        # "engine stays on old layout" recovery) stayed stale: a torn
        # engine on neither layout
        new_use_pallas = self._use_pallas_for(new_mesh)
        new_params = req["staged"]
        new_mesh_cfg = req["mesh_cfg"]
        faultpoints.hit_sync("mid_reshard", phase="kv_staged")
        # dynflow: commit-block -- reshard layout swap (crash-atomicity)
        self.params = new_params
        self.k_cache, self.v_cache = new_k, new_v
        if new_pc is not None:
            self._pen_counts, self._pen_mask = new_pc, new_pm
        if new_ks is not None:
            self.k_scales, self.v_scales = new_ks, new_vs
        self.mesh = new_mesh
        self.cfg.mesh = new_mesh_cfg
        self.use_pallas = new_use_pallas
        # dynflow: end-commit-block
        self._rows.move(self._rows_sharding())
        moved = self.kv.allocator.resident_count
        self.stats["resharded_total"] += 1
        self.stats["reshard_kv_moved_blocks"] += moved
        # SLO observatory invalidation: every jit program recompiles
        # under the new shardings on its next dispatch — clearing the
        # compiled-key set keeps the compile ledger seeing (and tracing)
        # those post-morph stalls instead of treating them as warm; the
        # weight-bytes attribution re-derives from the new params
        self._compiled_keys.clear()
        self._weight_bytes = None
        self._leaf_layout = None
        # ---- committed ----
        faultpoints.hit_sync("mid_reshard", phase="committed")
        return {
            "changed": True,
            "kv_moved_blocks": moved,
            "mesh": "x".join(map(str, self._mesh_shape(req["mesh_cfg"])))
                    or "unsharded",
            "morph_programs": m.programs(),
        }

    # ---------------- scheduler loop ----------------

    async def _on_device(self, fn, *args, lock: bool = True,
                         first: str = "dispatch"):
        """The loop task's way to the device executor, on the loop's
        clock: the await is ``lag`` but for what the thunk itself spent
        in ``dispatch`` and ``device`` (tracing/loop_clock.py); waiting
        for the device lock is lag too. Comes back to the open phase."""
        clk = self._clock
        prev = clk.await_thunk()
        self._device_awaits += 1
        try:
            async with self._device_lock if lock else contextlib.nullcontext():
                return await asyncio.get_running_loop().run_in_executor(
                    None, clk.thunk, first, fn, *args)
        finally:
            clk.settle(prev)

    async def _loop(self) -> None:
        clk = self._clock
        clk.start("admit")
        try:
            while not self._closed:
                clk.mark("admit")
                if self._draining:
                    self._drain_tick()
                if self._reshard_req is not None:
                    # a morph owns the device for its hold window
                    clk.mark("lag")
                    await self._reshard_step()
                    continue
                admitted = await self._admit()
                if (
                    self._n_active == 0
                    and not admitted
                    and not self._prefill_states
                ):
                    # drop a stale pipelined window before going idle (its
                    # participants all finished; tokens are discards)
                    await self._drain_inflight()
                    # the drain AWAITED (device sync): requests that
                    # arrived during it already called _wake.set() — a
                    # blind clear() here erases their wakeup and the
                    # loop sleeps on a non-empty queue forever (the
                    # pipelined-decode deadlock tests/test_engine.py
                    # pins). Re-check before AND after the clear; the
                    # after-clear check has no awaits in between, so a
                    # concurrent set() is always observed by wait().
                    if self._has_pending_work():
                        continue
                    self._wake.clear()
                    if self._has_pending_work():
                        continue
                    clk.mark("idle")
                    await self._wake.wait()
                    continue
                # a multi-prompt prefill pack with no decode batch is
                # still a fused dispatch (_mixed_fusable covers it) —
                # the queued prompts advance TOGETHER instead of
                # head-of-line blocking behind states[0]
                awaited = self._device_awaits
                if self._n_active or self._mixed_fusable():
                    await self._decode_once()
                if self._device_awaits != awaited and (
                    self._n_active or self._prefill_states
                ):
                    # the tokens this step emitted flush while the NEXT
                    # dispatch runs: every wait for the device hands the
                    # event loop to the streams, so yielding to them here
                    # as well only keeps the device idle for as long as
                    # they take (a millisecond a live stream and
                    # dispatch: PERF.md section 6, PR 33)
                    continue
                # nothing waited for the device: yield to the event
                # loop so emissions flush and requests are heard
                clk.mark("yield")
                await asyncio.sleep(0)
        except asyncio.CancelledError:
            # engine close() with sequences in flight: fail them — their
            # generate() coroutines block on out_queue forever otherwise,
            # and an ingress that gets cancelled around that block would
            # hand callers silently-truncated streams
            self._fail_all_owned()
        except FaultInjected as e:
            # the harness killed this worker mid-step: mark the engine
            # dead and abort every owned stream with the worker-lost
            # signature, exactly what a real death looks like through the
            # transport — the migration layer re-dispatches them all
            logger.warning("engine killed by fault point: %s", e)
            self._dead = str(e)
            self._fail_all_owned(text=str(e))
        except Exception:  # noqa: BLE001
            logger.exception("engine loop crashed")
            # a dead scheduler must not accept (and silently park) new
            # requests: fail fast with a retryable signature
            self._dead = "engine stopped: scheduler loop crashed"
            self._fail_all_owned(text=self._dead)
        finally:
            clk.stop()

    def _has_pending_work(self) -> bool:
        """Anything the idle scheduler must NOT sleep on."""
        return bool(
            self._waiting_front
            or not self._waiting.empty()
            or self._remote_ready
            or self._n_active
            or self._prefill_states
        )

    def _fail_all_owned(self, text: Optional[str] = None) -> None:
        """ERROR-terminate every request this engine owns — active,
        mid-prefill, and still-waiting. ``text`` rides the terminal chunk
        (a worker-lost signature there lets the migration layer pick the
        streams up instead of surfacing errors)."""
        if self._reshard_req is not None:
            # a morph awaiting the loop must fail WITH the loop, not
            # park its caller forever
            fut = self._reshard_req["fut"]
            if not fut.done():
                fut.set_exception(RuntimeError(text or "engine stopped"))
            self._reshard_req = None
            self._resharding = False
            self._reshard_busy = False
        in_prefill = [st.seq for st in self._prefill_states]
        for seq in self._active + self._remote_ready + in_prefill:
            if seq is not None:
                seq.out_queue.put_nowait(
                    LLMEngineOutput(finish_reason=FinishReason.ERROR, text=text)
                )
        self._remote_ready.clear()
        self._prefill_states.clear()
        while self._waiting_front or not self._waiting.empty():
            seq = self._pop_waiting()
            seq.out_queue.put_nowait(
                LLMEngineOutput(finish_reason=FinishReason.ERROR, text=text)
            )

    # ---- admission ----

    def _waiting_is_empty(self) -> bool:
        return not self._waiting_front and self._waiting.empty()

    def _waiting_size(self) -> int:
        return len(self._waiting_front) + self._waiting.qsize()

    def _pop_waiting(self) -> "_Sequence":
        if self._waiting_front:
            return self._waiting_front.popleft()
        return self._waiting.get_nowait()

    async def _admit(self) -> bool:
        admitted = False
        # re-derived every scheduler iteration; True means the head of the
        # waiting queue can't get blocks right now, so waiting requests are
        # NOT actionable admission work and decode-window fusion stays on
        self._backpressured = False
        while self._remote_ready and self._n_active < self.cfg.max_batch_size:
            seq = self._remote_ready.pop(0)
            if seq.finished:
                continue
            if seq.context.is_stopped():
                self._finish(seq, FinishReason.CANCELLED)
                continue
            self._place_in_batch(seq)
            admitted = True
        # advance an in-flight chunked prefill by exactly one chunk per
        # iteration. With mixed batching OFF (or nothing to fuse with)
        # that's a dedicated prefill dispatch here; when chunks can FUSE
        # into the running batch's decode step (or into each other —
        # multi-prompt packs dispatch even with no decode batch),
        # _decode_once dispatches one mixed step instead — decode
        # streams never stall a full chunk's device time behind a
        # separate dispatch, and queued prompts advance together
        if self._prefill_states and not self._mixed_fusable():
            admitted |= await self._prefill_step()
        while (
            len(self._prefill_states) < self._prefill_limit()
            and self._n_active + len(self._prefill_states)
            < self.cfg.max_batch_size
            and (self._waiting_front or not self._waiting.empty())
        ):
            seq = self._pop_waiting()
            # a ring-routed prompt owns its whole dispatch (sequence-
            # parallel one-shot prefill) — never pack IT behind other
            # in-flight prefills; it waits for the states list to clear
            # and runs alternating (where _mixed_fusable defers to it)
            if self._prefill_states and self._could_ring(seq):
                self._waiting_front.appendleft(seq)
                break
            if seq.context.is_stopped():
                seq.out_queue.put_nowait(LLMEngineOutput(finish_reason=FinishReason.CANCELLED))
                continue
            prompt_hashes = None
            if self.offload is not None and self.offload.async_tier and (
                self.offload.has_pending()
                or self.offload.has_inflight_flushes()
            ):
                # land any in-flight d2h holding this prompt's chain
                # off-loop, so _begin_prefill's host probe never blocks
                # the scheduler on a transfer; the chain is computed once
                # and handed down so admission doesn't re-hash the prompt
                prompt_hashes = sequence_block_hashes(
                    seq.tokens[: seq.seq_len - 1], self.cfg.block_size,
                    salt=model_hash_salt(seq.model),
                )
                self._clock.mark("lag")
                await self._offload_prejoin(
                    [s for _l, s in prompt_hashes]
                )
                self._clock.mark("admit")
            try:
                ok = self._begin_prefill(seq, hashes=prompt_hashes)
            except Exception:  # noqa: BLE001
                # device failure on THIS request (oom, compile error): fail
                # it alone — the loop and other requests keep going
                logger.exception("prefill failed for request %s", seq.context.id)
                self.kv.release(seq.hold)
                seq.out_queue.put_nowait(
                    LLMEngineOutput(finish_reason=FinishReason.ERROR)
                )
                continue
            if ok and self._mixed_fusable():
                # first chunk rides the next fused step; keep admitting
                # more queued prompts into the pack (up to the limit —
                # the while condition) so the budget splits across them
                continue
            if not ok:
                # A sequence whose minimum reservation exceeds the whole
                # pool can never admit (e.g. preempted late with a grown
                # token list, or an oversized prompt) — finish it rather
                # than head-of-line-block the queue forever.
                min_needed = self.kv.blocks_for(seq.seq_len)
                if min_needed > self.kv.allocator.num_blocks - 1:
                    # a fresh prompt that can never fit is a capacity ERROR
                    # (like prompts >= max_context); a preempted sequence
                    # that outgrew the pool already streamed real tokens,
                    # so it ends as an honest LENGTH truncation
                    reason = (
                        FinishReason.LENGTH if seq.generated
                        else FinishReason.ERROR
                    )
                    logger.warning(
                        "request %s needs %d blocks but the pool holds %d — "
                        "finishing as %s",
                        getattr(seq.context, "id", "?"), min_needed,
                        self.kv.allocator.num_blocks - 1, reason,
                    )
                    self._finish(seq, reason)
                    continue
                # out of KV blocks: put back and stop admitting (backpressure)
                self._waiting_front.appendleft(seq)
                self._backpressured = True
                break
            admitted |= await self._prefill_step()
        self.stats["requests_active"] = self._n_active
        return admitted

    def _tokens_in_vocab(self, ids) -> bool:
        V = self.cfg.model.vocab_size
        return all(0 <= t < V for t in ids)

    def _prefill_limit(self) -> int:
        """How many chunked prefills may be in flight at once: mixed
        batching packs up to ``mixed_max_prefills`` prompts per fused
        step; the alternating scheduler (mixed off, multi-host mirror)
        keeps the single-prefill discipline."""
        if not self.cfg.mixed_batch or self.mirror is not None:
            return 1
        return self.cfg.mixed_max_prefills

    def _could_ring(self, seq: _Sequence) -> bool:
        """Pre-reservation screen for ring routing: would this prompt
        plausibly take the sp ring-attention path at pos 0? Used only to
        keep ring prompts OUT of multi-prefill packs (the ring dispatch
        is whole-prompt, not chunk-wise). Mirrors ``_ring_chunk``'s
        model-family exclusions — a family that can never ring
        (sliding-window, gpt-oss windows/sinks, gemma-2 softcap) must
        not have its long prompts barred from packing; the authoritative
        per-chunk check stays ``_ring_chunk`` (only the cached-prefix
        pos!=0 and bucket-divisibility terms are unknowable here, and
        over-matching on those merely under-packs)."""
        cfg = self.cfg
        return (
            cfg.ring_prefill_threshold > 0
            and self.mesh is not None
            and self.mesh.shape.get("sp", 1) > 1
            and len(seq.tokens) >= cfg.ring_prefill_threshold
            and cfg.model.sliding_window == 0
            and not cfg.model.layer_windows
            and not cfg.model.attn_sinks
            and not cfg.model.attn_softcap
        )

    def _reserve(self, seq: _Sequence, probe_host: bool = False,
                 hashes=None):
        """``seq``'s hold in the pools (``KvManager.reserve``: prefix
        hits, the host tier's probe, fresh blocks). Returns (history,
        upload or None), or None with nothing held."""
        reserved = self.kv.reserve(
            seq.tokens, model_hash_salt(seq.model), hashes=hashes,
            probe_host=probe_host)
        if reserved is None:
            return None
        seq.hold, seq.cached_prefix, upload = reserved
        return seq.cached_prefix, upload

    def _begin_prefill(self, seq: _Sequence, hashes=None) -> bool:
        """Reserve blocks + prefix/host-tier claims and queue the sequence
        as the in-flight chunked prefill. Returns False on pool pressure."""
        reserved = self._reserve(seq, probe_host=True, hashes=hashes)
        if reserved is None:
            return False
        history, upload = reserved
        self.stats["prefix_cache_hits_tokens"] += history
        if self.state is not None:
            # the matched tokens up to the last snapshot are skipped
            # (the prefix rule); the row is one no sequence decodes in
            # and no other prefill holds
            self.stats["state_restores"] += history > 0
            held = {st.seq.state_slot for st in self._prefill_states}
            seq.state_slot = next(
                i for i, s in enumerate(self._active)
                if s is None and i not in held)
        if seq.generated == 0:
            # admission latency: arrival -> blocks reserved, reconstructed
            # backwards so the span's start anchors at arrival time. A
            # preemption REPLAY (generated > 0) is post-first-token work:
            # re-recording would overlap the original span and break the
            # decomposition's sum-to-TTFT contract
            waited_s = time.monotonic() - seq.arrival_t
            self.hist["queue_wait_ms"].observe(waited_s * 1e3)
            if seq.trace is not None:
                tracing.RECORDER.record_span(
                    "engine.queue_wait", seq.trace,
                    ts=time.time() - waited_s, dur_ms=waited_s * 1e3,
                    request_id=seq.context.id,
                    waiting=self._waiting_size(), step=self._clock.seq,
                )
        self._prefill_states.append(
            _PrefillState(seq=seq, pos=history, upload=upload)
        )
        return True

    async def _prefill_step(self) -> bool:
        """Run ONE prefill chunk of the OLDEST in-flight sequence (the
        alternating path only ever holds one); on the final chunk,
        sample the first token and join the decode batch. Returns True
        when the sequence was admitted (prefill completed)."""
        assert self._prefill_states
        st = self._prefill_states[0]
        faultpoints.hit_sync("mid_prefill", request_id=st.seq.context.id)
        seq = st.seq
        if seq.context.is_stopped():
            # hand reserved host blocks back even mid-upload (the upload
            # only READS the host arrays, so re-pooling is safe) — same
            # as the error path below; dropping them would leak the
            # cached prefix
            self._abort_prefill(st, FinishReason.CANCELLED)
            return False
        if not self.kv.grow(seq.hold, st.pos, min(
                len(seq.tokens), st.pos + self.cfg.prefill_chunk)):
            self._pool_full(st)
            return False
        # device work (jit dispatch + compile + host sync) runs in a worker
        # thread so lease keepalives / bus traffic stay live on the loop
        try:
            first_token = await self._on_device(self._prefill_chunk_device, st)
        except Exception:
            # device failure: hand reserved host blocks back so the prefix
            # isn't silently lost from the offload tier (host arrays are
            # never mutated, so re-pooling is safe even mid-upload)
            logger.exception("prefill failed for request %s", seq.context.id)
            self._abort_prefill(st, FinishReason.ERROR)
            return False
        if first_token is None:  # more chunks to go
            self.kv.commit(seq.hold, seq.tokens, st.pos, chunk=True)
            self._step_done()
            return False
        phase = self._clock.mark("emit")
        self._prefill_done(st, first_token)
        self._step_done()
        self._clock.mark(phase)
        return True

    def _prefill_done(self, st: "_PrefillState", first: tuple) -> None:
        """A prompt's final chunk ran, alone or in a mixed step: its
        ``engine.prefill`` span, its blocks committed, its first token
        (``first``: token, logprob entry) emitted, its row placed."""
        seq = st.seq
        if seq.generated == 0:
            # first prefill only — a preemption replay's prefill is
            # post-first-token and must not re-enter the decomposition
            self.hist["prefill_ms"].observe(st.dev_ms)
            if seq.trace is not None:
                tracing.RECORDER.record_span(
                    "engine.prefill", seq.trace, ts=st.t0_wall,
                    dur_ms=st.dev_ms,
                    request_id=seq.context.id,
                    prompt_tokens=seq.prompt_len,
                    cached_prefix=seq.cached_prefix,
                    step=self._clock.seq,
                    **self.kv.prefill_attrs(seq.hold, seq.cached_prefix),
                )
        if st in self._prefill_states:
            self._prefill_states.remove(st)
        self.kv.commit(seq.hold, seq.tokens, seq.seq_len)
        self._emit_token(seq, *first)
        if not seq.finished:
            if self._n_active < self.cfg.max_batch_size:
                self._place_in_batch(seq)
            else:
                # slots filled mid-prefill (a remote-ready admission took
                # the last one): the KV is landed, so queue for the next
                # free slot exactly like a remotely-prefilled sequence —
                # an unconditional placement would index(None) on a full
                # batch and crash the scheduler loop
                self._remote_ready.append(seq)

    def _pool_full(self, st: "_PrefillState") -> None:
        """A pool cannot give a prefill its next chunk's blocks (its first
        chunk's were reserved at admission and a later chunk gives back
        what it takes: a window pool sized under one sequence's window
        and chunk). The request fails alone, by name."""
        logger.error(
            "%s under request %s at %d of %d tokens: failing it",
            self.kv.why(), getattr(st.seq.context, "id", "?"), st.pos,
            len(st.seq.tokens))
        self._abort_prefill(st, FinishReason.ERROR)

    def _abort_prefill(
        self, st: "_PrefillState", reason: FinishReason,
        text: Optional[str] = None,
    ) -> None:
        """The one teardown for an in-flight prefill — cancellation AND
        device failure, alternating AND mixed paths: drop the state,
        free the sequence's blocks, hand the reserved host chain back
        (_rollback_upload), and terminate the stream. The call sites
        share it so the rollback protocol cannot drift between them;
        ``text`` lets the drain handoff stamp the migration signal."""
        seq = st.seq
        if st in self._prefill_states:
            self._prefill_states.remove(st)
        self.kv.release(seq.hold)
        seq.state_slot = -1
        self._rollback_upload(st)
        seq.out_queue.put_nowait(
            LLMEngineOutput(finish_reason=reason, text=text)
        )

    def _rollback_upload(self, st: _PrefillState) -> None:
        """Shared cancel/error rollback for a prefill's reserved host
        chain: record the abandoned upload (if it never landed) and
        return the entries to the pool — the one protocol both paths
        must not drift apart on."""
        if self.offload is None or st.upload is None:
            return
        if not st.restored:
            self.offload.cancel_upload(st.upload)
        self.offload.unreserve(
            st.upload.hashes, st.upload.data, restored=st.restored
        )

    def _prefill_chunk_device(self, st: _PrefillState) -> Optional[int]:
        """Runs in an executor thread: one bucketed prefill chunk. Returns
        the sampled first token on the final chunk, else None."""
        t0 = time.perf_counter()
        try:
            self._offload_preamble(
                st.upload if not st.restored else None, seq=st.seq
            )
            st.restored = True
            self._state_preamble(st)
            p0, t_c = st.pos, time.perf_counter()
            logits, st.pos = self._run_one_chunk(st.seq, st.pos)
            if self.cost is not None and st.pos > p0:
                # measured chunk timing = the observation that corrects
                # the cost model's modeled prefill throughput
                self.cost.observe_prefill(
                    st.pos - p0, max(time.perf_counter() - t_c, 1e-9)
                )
            if st.pos < len(st.seq.tokens):
                return None
            first = self._sample_prefill(st.seq, logits)  # (token, lp_entry)
            self._clock.landed()
            return first
        finally:
            # accumulate DEVICE time only: chunks of a long prompt
            # interleave with other requests' decode steps, so the
            # traced prefill component must not absorb that wall time
            st.dev_ms += (time.perf_counter() - t0) * 1e3

    def _state_preamble(self, st: _PrefillState) -> None:
        """Before a sequence's first prefill chunk: its row of the conv
        state starts from the snapshot of its cached prefix's last
        block, or from zeros."""
        if self.state is None or st.state_ready:
            return
        st.state_ready = True
        self.state = _begin_state_row(
            self.state, jnp.int32(st.seq.state_slot),
            jnp.int32(self.kv.restore_from(st.seq.hold)))

    def _note_state(self, segments: int, decode_steps: int = 0,
                    live_rows: int = 0) -> None:
        """Bytes of recurrent matrices a dispatch reads and writes: a
        prefill segment its sequence's, a decode step its ``live_rows``'
        (the kernel walks the live rows and leaves a dead slot's where
        they lie: ops/gated_delta_pallas). With kernels off the plain
        step carries every slot's through, and every slot counts: the
        counter says what the program moves."""
        if not llama.linear_step_walks_live(self.cfg.model, self.use_pallas):
            live_rows = self.cfg.max_batch_size
        self.stats["linear_state_bytes"] += 2 * self._rec_row_bytes * (
            segments + decode_steps * live_rows)

    def _state_kw(self, seq: Optional[_Sequence] = None,
                  end: int = 0) -> dict:
        """The step programs' keywords for a model with state layers: the
        state and, for ``seq``'s prefill chunk that ends after ``end``
        tokens, its row and (a sparse pool) its snapshot row."""
        if self.state is None:
            return {}
        kw = {"state": self.state}
        if seq is not None:
            kw["slot"] = jnp.int32(seq.state_slot)
            snaps = self.kv.snap_rows([(seq.hold, end)])
            if snaps is not None:
                kw["snap_row"] = jnp.int32(snaps[0])
        return kw

    def _offload_preamble(self, upload=None, seq: Optional[_Sequence] = None) -> None:
        """Dispatch d2h gathers for every pending eviction before this
        prefill overwrites their pages (the fetch lands in background —
        budget=None takes all pending because a prefill may write any
        freshly allocated page), then land the reserved chain's h2d
        upload: a cheap on-device scatter that waits only if the upload
        begun at reservation hasn't arrived yet. With a traced ``seq``,
        the restore's hidden-vs-exposed split (PR 1's accounting) is
        recorded as this request's ``engine.kv_restore`` span."""
        if self.offload is None:
            return
        self._before_landing()
        if upload is not None:
            t0 = time.perf_counter()
            self.k_cache, self.v_cache = self.offload.finish_upload(
                self.k_cache, self.v_cache, upload
            )
            self.hist["restore_ms"].observe((time.perf_counter() - t0) * 1e3)
            if seq is not None and seq.trace is not None and seq.generated == 0:
                waited_ms = (time.perf_counter() - t0) * 1e3
                t_landed = getattr(upload, "t_landed", None)
                total_ms = (
                    max((t_landed - upload.t_start) * 1e3, 0.0)
                    if t_landed is not None else waited_ms
                )
                exposed_ms = min(waited_ms, total_ms)
                tracing.RECORDER.record_span(
                    "engine.kv_restore", seq.trace,
                    ts=time.time() - waited_ms / 1e3, dur_ms=waited_ms,
                    request_id=seq.context.id,
                    blocks=len(upload.hashes),
                    # restore volume: lets ttft.cost_observations replay
                    # this span into a TransferCostModel ("host" class);
                    # wire bytes — what the h2d actually moved under
                    # --kv-quant
                    nbytes=len(upload.hashes) * self.kv_wire_block_bytes,
                    exposed_ms=round(exposed_ms, 3),
                    hidden_ms=round(max(total_ms - exposed_ms, 0.0), 3),
                )

    def _before_landing(self) -> None:
        """What KV that lands on freshly allocated pages (a tier restore,
        a prefetch, a peer or disagg delivery, a prefill) needs first,
        in this order: the d2h gathers of every pending eviction, which
        may still reference those pages (budget=None: a landing may
        target any of them), then the pages' queued scale resets (int8
        cache): a reset that ran AFTER the landing would leave the
        landed int8 payload under scale EPS."""
        if self.offload is not None:
            self.offload.flush_evictions_async(self.k_cache, self.v_cache)
        self._flush_scale_resets()

    def _flush_scale_resets(self) -> None:
        """int8 device cache: reset the scale-plane entries of every
        page the allocator recycled since the last dispatch (queued by
        its ``on_allocated`` hook), as ONE scatter riding the next
        write dispatch's preamble (or a landing's: ``_before_landing``).
        Idx count pads to the power-of-two bucket with the trash page 0
        so the scatter's program count stays bucket-bounded."""
        if self.k_scales is None or not self._pending_scale_resets:
            return
        idxs = np.unique(
            np.asarray(self._pending_scale_resets, np.int32)
        )
        self._pending_scale_resets.clear()
        padded = np.zeros(_bucket(len(idxs)), np.int32)
        padded[: len(idxs)] = idxs
        self.k_scales, self.v_scales = _reset_scale_entries(
            self.k_scales, self.v_scales, jnp.asarray(padded)
        )

    def _note_quant_step(
        self, n_requants, tokens_written: int, gen_tokens: int = 0
    ) -> None:
        """Fold one quantized dispatch's outcome into the lane gauges.
        ``n_requants`` (the device-computed count of (layer, page) scale
        entries that grew) stays a DEVICE scalar — it accumulates
        asynchronously and only converts at scrape time
        (_fold_quant_counters), so pipelined decode never syncs on it.
        ``gen_tokens`` > 0 (decode dispatches) feeds the measured
        lane-throughput EMA behind ``lowprec_tok_s``."""
        self._requants_dev = (
            n_requants if self._requants_dev is None
            else self._requants_dev + n_requants
        )
        self.stats["kv_device_bytes_saved_total"] += (
            tokens_written * self._kv_saved_per_token
        )
        self.stats["kv_device_quant_pages"] = self.kv.allocator.resident_count
        if gen_tokens > 0:
            now = time.perf_counter()
            dt = now - self._lowprec_rate_t
            if self._lowprec_rate_t and 0 < dt < 10.0:
                inst = gen_tokens / dt
                prev = self.stats["lowprec_tok_s"]
                self.stats["lowprec_tok_s"] = round(
                    inst if prev == 0.0 else 0.8 * prev + 0.2 * inst, 3
                )
            self._lowprec_rate_t = now

    def _fold_quant_counters(self) -> None:
        """Convert the accumulated device-side requant counter into the
        host stat (one scalar d2h; called from load_metrics scrapes),
        and fold the offload manager's export-bounce count (blocks that
        had to leave the device codec for a full-width/fp8 tier) into
        the export-requant gauge."""
        if self._requants_dev is not None:
            self.stats["kv_device_requants_total"] += int(self._requants_dev)
            self._requants_dev = None
        if self.offload is not None and self.k_scales is not None:
            cur = self.offload.device_requants_total
            self.stats["kv_device_export_requant_total"] += (
                cur - self._offload_requants_seen
            )
            self._offload_requants_seen = cur

    def _ring_chunk(self, seq: _Sequence, pos: int) -> bool:
        """Route THIS chunk through sp ring attention? History-free
        first chunk of a long-enough prompt on an sp>1 mesh, full
        attention (the whole prompt becomes one ring chunk). MLA models
        ride a latent ring — the rotated chunk is the compressed
        (c_kv, k_pe) stream."""
        cfg = self.cfg
        if (
            cfg.ring_prefill_threshold <= 0
            or pos != 0
            or self.mesh is None
            or self.mesh.shape.get("sp", 1) <= 1
            # int8 device cache: ring writes land full-width (no scale
            # stream through the rotated chunks) — paged path only
            or self.k_scales is not None
            or len(seq.tokens) < cfg.ring_prefill_threshold
            or cfg.model.sliding_window != 0
            or cfg.model.layer_windows  # per-layer windows (gpt-oss)
            or cfg.model.attn_sinks  # sinks live in the paged XLA paths
            or cfg.model.attn_softcap  # gemma-2 caps: paged XLA paths
        ):
            return False
        # bucket sizes are powers of two >= sp, so T % sp == 0 holds
        return _bucket(len(seq.tokens)) % self.mesh.shape["sp"] == 0

    # ---- multi-LoRA dispatch plumbing ----
    # With a registry configured, EVERY dispatch carries the full device
    # stack + per-row adapter ids — base rows get exact +0.0 deltas
    # (ops/lora.py) — so mixed-adapter and solo-adapter traffic run the
    # SAME compiled programs and program counts key on the registry's
    # (count, rank) buckets, never the live request mixture. Fleets
    # without --adapters return {} and the programs are byte-identical
    # to pre-multi-model builds.

    def _lora_prefill_kw(self, adapter_id: int) -> dict:
        if self.adapters is None:
            return {}
        return {
            "lora": self.adapters.device_stack(),
            "adapter_id": jnp.int32(adapter_id),
        }

    def _lora_decode_kw(self) -> dict:
        if self.adapters is None:
            return {}
        # (the rows' adapter ids ride the resident step state)
        return {"lora": self.adapters.device_stack()}

    def _lora_key(self) -> tuple:
        """Compile-key suffix: the registry's static bucket pair (or
        empty — base fleets keep their exact historical key tuples)."""
        if self.adapters is None:
            return ()
        return (("lora", self.adapters.count_bucket,
                 self.adapters.rank_bucket),)

    def _run_one_chunk(self, seq: _Sequence, pos: int):
        """One bucketed prefill chunk at ``pos``; returns (logits, new_pos)."""
        cfg = self.cfg
        ring = self._ring_chunk(seq, pos)
        # ring: the WHOLE prompt is one sequence-parallel chunk
        chunk = seq.tokens[pos:] if ring else (
            seq.tokens[pos : pos + self.kv.clip_take(
                seq.hold, pos, cfg.prefill_chunk)]
        )
        T = _bucket(len(chunk))
        toks = np.zeros(T, np.int32)
        toks[: len(chunk)] = chunk
        self._note_prefill_work(T, len(chunk))
        # the table must cover the padded chunk: page 0 pads it
        tables = self.kv.tables(seq.hold)
        # tokens, table(s), history, valid: each its own transfer
        self._handed("prefill", 3 + len(jax.tree.leaves(tables)))
        if self.mirror is not None:
            logits, self.k_cache, self.v_cache = self._timed_dispatch(
                lambda: self.mirror.lead_prefill(
                    self.params, toks, tables, pos,
                    len(chunk), self.k_cache, self.v_cache,
                    use_pallas=self.use_pallas, use_ring=ring,
                ),
                key=("prefill", T, ring), trace=seq.trace,
            )
            return logits, pos + len(chunk)
        if self.k_scales is not None:
            self._flush_scale_resets()
            out = self._timed_dispatch(
                lambda: llama.prefill(
                    self.params,
                    cfg.model,
                    jnp.asarray(toks),
                    jnp.asarray(tables),
                    jnp.int32(pos),
                    jnp.int32(len(chunk)),
                    self.k_cache,
                    self.v_cache,
                    use_pallas=self.use_pallas,
                    mesh=self.mesh,
                    use_ring=ring,
                    k_scales=self.k_scales,
                    v_scales=self.v_scales,
                    **self._lora_prefill_kw(seq.adapter_id),
                    **self._moe_kw(),
                ),
                key=("prefill", T, ring) + self._lora_key(),
                trace=seq.trace,
            )
            (logits, self.k_cache, self.v_cache,
             self.k_scales, self.v_scales) = out[:5]
            self._note_quant_step(0, len(chunk))
            if self._moe_layers:
                self._note_moe(out[5], 1, len(chunk))
            return logits, pos + len(chunk)
        state_kw = self._state_kw(seq, pos + len(chunk))
        out = self._timed_dispatch(
            lambda: llama.prefill(
                self.params,
                cfg.model,
                jnp.asarray(toks),
                jax.tree.map(jnp.asarray, tables),
                jnp.int32(pos),
                jnp.int32(len(chunk)),
                self.k_cache,
                self.v_cache,
                use_pallas=self.use_pallas,
                mesh=self.mesh,
                use_ring=ring,
                **self._lora_prefill_kw(seq.adapter_id),
                **self._moe_kw(),
                **state_kw,
            ),
            key=("prefill", T, ring) + self._lora_key(),
            trace=seq.trace,
        )
        logits, self.k_cache, self.v_cache = out[:3]
        rest = list(out[3:])
        if self.state is not None:
            self.state = rest.pop(0)
            self._state_rows += 1
            self._note_state(1)
        if self._moe_layers:
            self._note_moe(rest.pop(0), 1, len(chunk))
        return logits, pos + len(chunk)

    def _prefill_device(
        self,
        seq: _Sequence,
        history: int,
        upload=None,
    ) -> tuple[int, Optional[dict]]:
        """Runs in an executor thread: whole-prompt chunked prefill +
        first-token sample (the disagg prefill-worker path, which owns the
        device for the whole prompt — the serving loop uses the chunk-at-a-
        time _prefill_chunk_device instead). Returns (token, logprob
        entry or None) — the entry rides the KV transfer so a logprobs
        request served via remote prefill doesn't lose its first token's
        logprobs (advisor r2)."""
        self._offload_preamble(upload, seq=seq)
        logits = None
        pos = history
        while pos < len(seq.tokens):
            p0, t_c = pos, time.perf_counter()
            logits, pos = self._run_one_chunk(seq, pos)
            if self.cost is not None and pos > p0:
                self.cost.observe_prefill(
                    pos - p0, max(time.perf_counter() - t_c, 1e-9)
                )
        return self._sample_prefill(seq, logits)

    def _check_provisioned(self, n: int, what: str) -> None:
        """Provisioning invariant (loud, not silent): every active
        sequence must have blocks covering this dispatch's ``n`` steps'
        writes. A violation would scatter through zero block-table
        entries into reserved page 0: garbage K/V that later reads
        silently consume."""
        for seq in self._active:
            if (seq is None or seq.finished or seq.slot < 0
                    or self._leaving(seq)):
                continue
            if self.kv.short(seq.hold, self._reach(seq, n)):
                raise RuntimeError(
                    f"{what} pending={self._pending(seq)} exceeds "
                    f"provisioned blocks for request "
                    f"{getattr(seq.context, 'id', '?')} "
                    f"(seq_len={seq.seq_len}, blocks={len(seq.hold.blocks)})"
                )

    def _grow(self, seq: _Sequence, upto: int) -> bool:
        """A decode row provisioned up to token ``upto`` (exclusive), in
        every pool (``KvManager.grow``; what lies behind the HOST's
        position's window goes back), and its table row(s) anew. False
        when a pool has no block to give."""
        if not self.kv.grow(seq.hold, seq.seq_len - 1, upto):
            return False
        self._rows.set_tables(seq.slot, *self.kv.table_rows(seq.hold))
        return True

    def _decode_rows(self, kind: str, pending=0) -> dict:
        """A step program's decode rows, for all three thunks: the
        resident step state and what this dispatch must add to it
        (``StepState.hand_over``: the mirror whole, a few cells, or
        nothing), as the program's keywords."""
        rows, delta, sent = self._rows.hand_over(pending)
        self._handed(kind, 1 if sent else 0)
        self.stats["step_state_resyncs" if sent == "mirror"
                   else "step_state_resident"] += 1
        return {"rows": rows, "rows_delta": delta}

    def _handed(self, kind: str, n: int) -> None:
        """``n`` host-to-device transfers of a dispatch thunk of
        ``kind`` (a program key's first element)."""
        self.stats["step_handovers_" + KINDS[kind]] += n

    def _rows_sharding(self):
        """Where the resident step state lives: replicated over the
        mesh, or the default device's own placement."""
        return None if self.mesh is None else replicated(self.mesh)

    def _sample_prefill(self, seq: _Sequence, logits):
        """Sample the first token from the prefill logits; returns
        (token, logprob_entry_or_None). Full penalty semantics: the
        prompt mask AND output counts rebuild from the sequence's token
        lists, so the replay-after-preemption first token draws from the
        same distribution a decode window would use."""
        so = seq.request.sampling_options
        temp = so.temperature if so.temperature is not None else 1.0
        if getattr(seq.request, "greedy", False):
            temp = 0.0
        V = self.cfg.model.vocab_size

        def pad(ids):
            out = np.full(_bucket(max(len(ids), 1)), V, np.int32)
            out[: len(ids)] = ids
            return out

        # the token lists only feed the penalties; without one they are
        # a no-op, and passing them anyway would key the sampler's
        # program (a full-vocabulary sort: seconds to compile on the
        # chip) on the PROMPT's bucket — one recompile per bucket
        penalized = bool(
            so.frequency_penalty or so.presence_penalty
            or (so.repetition_penalty or 1.0) != 1.0
        )
        prompt_p = pad(seq.tokens[: seq.prompt_len] if penalized else ())
        gen_p = pad(seq.tokens[seq.prompt_len :] if penalized else ())
        if self.mirror is not None:
            token = self.mirror.lead_sample1(
                logits, (so.seed or 0) & 0x7FFFFFFF, seq.generated, temp,
                so.top_k or 0, so.top_p if so.top_p is not None else 1.0,
                freq=so.frequency_penalty or 0.0,
                pres=so.presence_penalty or 0.0,
                rep=so.repetition_penalty or 1.0,
                prompt_ids=prompt_p, gen_ids=gen_p,
            )
            entry = None
            k = min(so.logprobs, 20) if so.logprobs is not None else -1
            if k >= 0:
                # read the leader's LOCAL shard (replicated => complete);
                # jax.device_get on a multiprocess array would wait on a
                # collective the followers never join
                row = np.asarray(logits.addressable_data(0), np.float64)
                row = row - row.max()
                row = row - np.log(np.exp(row).sum())
                top = np.argsort(row)[::-1][:k]
                entry = {
                    "logprob": float(row[token]),
                    "top": [[int(i), float(row[i])] for i in top],
                }
            return token, entry
        keys = make_keys(
            jnp.asarray([(so.seed or 0) & 0x7FFFFFFF]),
            jnp.asarray([seq.generated]),
        )
        tok = _sample_first_jit(
            logits[None, :],
            keys,
            jnp.asarray([temp], jnp.float32),
            jnp.asarray([so.top_k or 0], jnp.int32),
            jnp.asarray([so.top_p if so.top_p is not None else 1.0], jnp.float32),
            jnp.asarray([so.frequency_penalty or 0.0], jnp.float32),
            jnp.asarray([so.presence_penalty or 0.0], jnp.float32),
            jnp.asarray([so.repetition_penalty or 1.0], jnp.float32),
            jnp.asarray(prompt_p),
            jnp.asarray(gen_p),
        )
        token = int(jax.device_get(tok)[0])
        entry = None
        k = min(so.logprobs, 20) if so.logprobs is not None else -1
        if k >= 0:
            from ..ops.sampling import token_logprobs

            chosen, top_ids, top_lps = token_logprobs(
                jnp.asarray(logits)[None].astype(jnp.float32),
                jnp.asarray([token], jnp.int32),
            )
            ids = np.asarray(jax.device_get(top_ids))[0]
            lps = np.asarray(jax.device_get(top_lps))[0]
            entry = {
                "logprob": float(jax.device_get(chosen)[0]),
                "top": [[int(ids[j]), float(lps[j])] for j in range(k)],
            }
        return token, entry

    def _place_in_batch(self, seq: _Sequence) -> None:
        # a sequence with conv state decodes in the row its prefill left
        # the state in
        slot = (seq.state_slot if seq.state_slot >= 0
                else self._active.index(None))
        assert self._active[slot] is None
        seq.slot = slot
        self._active[slot] = seq
        self._n_active += 1
        so = seq.request.sampling_options
        self._rows.set_tables(seq.slot, *self.kv.table_rows(seq.hold))
        self._rows.place(
            slot, seq_len=seq.seq_len, token=seq.tokens[-1],
            steps=seq.generated,
            # mask into int32 range: PRNG seeds only need entropy, not magnitude
            seed=(so.seed or 0) & 0x7FFFFFFF,
            temperature=so.temperature if so.temperature is not None else 1.0,
            top_k=so.top_k or 0,
            top_p=so.top_p if so.top_p is not None else 1.0,
            freq_pen=so.frequency_penalty or 0.0,
            pres_pen=so.presence_penalty or 0.0,
            rep_pen=so.repetition_penalty or 1.0,
            adapter_id=seq.adapter_id,
        )
        self._logprob_ks[slot] = (
            min(so.logprobs, 20) if so.logprobs is not None else -1
        )
        if self._slot_has_penalty(slot):
            self._reset_penalty_slot(slot, seq)

    def _slot_has_penalty(self, i: int) -> bool:
        r = self._rows
        return (
            r.freq_pens[i] != 0.0
            or r.pres_pens[i] != 0.0
            or r.rep_pens[i] != 1.0
        )

    def _penalties_active(self) -> bool:
        return self._pen_counts is not None and any(
            self._slot_has_penalty(i)
            for i, s in enumerate(self._active) if s is not None
        )

    def _logprobs_active(self) -> bool:
        return any(
            self._logprob_ks[i] >= 0
            for i, s in enumerate(self._active) if s is not None
        )

    def _reset_penalty_slot(self, slot: int, seq: _Sequence) -> None:
        """Zero the slot's output counts and rebuild its prompt mask
        (repetition penalty covers prompt + output tokens)."""
        V = self.cfg.model.vocab_size
        B = self.cfg.max_batch_size

        def pad(ids):
            out = np.full(_bucket(max(len(ids), 1)), V, np.int32)  # V = drop
            out[: len(ids)] = ids
            return out

        prompt_p = pad(seq.tokens[: seq.prompt_len])
        gen_p = pad(seq.tokens[seq.prompt_len :])
        if self.mirror is not None:
            # broadcast FIRST: multi-process array creation below expects
            # every rank to participate, and the followers only start on
            # receiving the pen_reset op (leader-only device_put of a
            # process-spanning array blocks awaiting peers)
            self.mirror.lead_pen_reset(slot, prompt_p, gen_p)
        if self._pen_counts is None:
            if self.mirror is not None:
                self._pen_counts = self.mirror.to_global(
                    np.zeros((B, V), np.int32)
                )
                self._pen_mask = self.mirror.to_global(np.zeros((B, V), bool))
            else:
                self._pen_counts = jnp.zeros((B, V), jnp.int32)
                self._pen_mask = jnp.zeros((B, V), jnp.bool_)
        if self.mirror is not None:
            prompt_j = self.mirror.to_global(prompt_p)
            gen_j = self.mirror.to_global(gen_p)
        else:
            prompt_j, gen_j = jnp.asarray(prompt_p), jnp.asarray(gen_p)
        self._pen_counts, self._pen_mask = _reset_pen_slot(
            self._pen_counts, self._pen_mask, slot, prompt_j, gen_j
        )

    # ---- offload tier helpers ----

    def _flush_evictions_budgeted(self) -> None:
        """Budgeted background d2h for decode-path dispatches: at most
        ``offload_flush_budget`` optional blocks per window so offload
        traffic can't starve decode, but every pending eviction whose
        page appears in the live block tables (a page this dispatch may
        write) flushes unconditionally — deferring those would snapshot
        overwritten KV."""
        if self.offload is None or not self.offload.has_pending():
            return
        must = set(np.unique(self._rows.tables).tolist())
        must.discard(0)
        self.offload.flush_evictions_async(
            self.k_cache, self.v_cache,
            budget=self.offload.flush_budget, must_idxs=must,
        )

    async def _offload_prejoin(self, hashes: list[int]) -> None:
        """Before an event-loop host-tier probe: dispatch any pending
        eviction gathers (budget-deferred entries are otherwise invisible
        to admission — neither in the pool nor in flight), wait
        OFF-LOOP for in-flight flushes holding ``hashes``, and promote
        any disk-tier continuation into the host pool — so the probe
        sees every landed block without the event loop ever blocking on
        a d2h fetch or a file read."""
        off = self.offload
        if off is None or not off.async_tier or not hashes:
            return
        off.flush_dropped()
        loop = asyncio.get_running_loop()
        if off.has_pending():
            # under the device lock: dispatch order across threads stays
            # serialized, so the gathers remain stream-ordered before any
            # later compute that overwrites the evicted pages
            async with self._device_lock:
                await loop.run_in_executor(
                    None, off.flush_evictions_async,
                    self.k_cache, self.v_cache,
                )
        if off.has_inflight_flushes():
            await loop.run_in_executor(None, off._join_flushes_for, hashes)
        if off.disk is not None:
            # disk -> host promotion off-loop; cheap when the disk index
            # has no continuation for this chain (index-only probe first)
            await loop.run_in_executor(None, off.promote_chain, hashes)

    def _slice_fp(self) -> str:
        """Accelerator-slice fingerprint (parallel/mesh.py, memoized
        there per process) — advertised in load_metrics so the router
        can tell which workers can hand KV device→device over ICI."""
        from ..parallel.mesh import slice_fingerprint

        return slice_fingerprint()

    async def export_device_chain(
        self, seq_hashes: list[int], max_blocks: int = 128
    ) -> tuple:
        """Serve side of the fleet prefix cache, DEVICE tier: the
        longest consecutive run of ``seq_hashes`` resident in the device
        prefix cache, gathered d2h as one bounded export — so chains
        living only in HBM (the hottest tier) stop being invisible to
        peers. Non-destructive: the blocks are ref-claimed for the
        gather's duration (a concurrent eviction can't recycle the
        pages mid-copy) and released untouched. The d2h runs on the
        device executor under the device lock, bounded by
        ``max_blocks`` so a serve can never become an unbounded HBM
        drain. Mirrored engines return empty (their gather is a
        lockstep broadcast no peer fetch should trigger).

        Returns (hashes, k, v, k_scales, v_scales). With an int8 device
        cache the export is the DEVICE CODEC verbatim — int8 payloads +
        [L, n] per-block scales, no full-width bounce through HBM or
        PCIe (the scales are non-None exactly then); the serving side
        adopts them when the wire codec matches and re-encodes (counted
        in ``kv_device_export_requant_total``) when it doesn't."""
        self.kv.refuse_transfer("export_device_chain (fleet prefix cache)")
        if self.mirror is not None or not seq_hashes or self._closed:
            return [], None, None, None, None
        # claim refs via the allocator's own chain matcher (hashes are
        # chained, so the local-hash slot is unused by the lookup) —
        # claiming pins the pages against eviction during the gather
        claimed = self.kv.allocator.match_prefix(
            (), hashes=[(0, h) for h in seq_hashes[:max_blocks]]
        )
        if not claimed:
            return [], None, None, None, None
        ks = vs = None
        try:
            idxs = [b.idx for b in claimed]
            async with self._device_lock:
                if self.k_scales is not None:
                    k, v, ks, vs = await (
                        asyncio.get_running_loop().run_in_executor(
                            None, self._gather_device, idxs, False, True
                        )
                    )
                else:
                    k, v = await asyncio.get_running_loop().run_in_executor(
                        None, self._gather_device, idxs, False
                    )
        finally:
            self.kv.allocator.free(claimed)
        served = list(seq_hashes[: len(claimed)])
        self.stats["peer_serve_d2h_blocks"] += len(served)
        return served, k, v, ks, vs

    def note_export_requant(self, n: int) -> None:
        """A peer serve re-encoded ``n`` device-codec blocks away from
        int8 (the puller's wire codec didn't match) — the visible form
        of what used to be a silent full-width bounce."""
        self.stats["kv_device_export_requant_total"] += n

    async def pre_stage_weights(self, model: str) -> bool:
        """PRESERVE-style weight pre-stage hook, driven by the router's
        prefetch hint naming the model/adapter the routed request will
        run. With an adapter registry configured this stages the named
        adapter's A/B stacks host->device BEFORE the request lands, so
        its admission finds the weights resident (a prestage *hit*,
        ``weight_prestage_hits``) instead of paying the cold-load copy
        on its TTFT. Base-model names (and fleets without --adapters)
        only count the request — the base weights are always resident.
        Returns True when staging work actually ran."""
        self.stats["weight_prestage_requests"] += 1
        reg = self.adapters
        if reg is None or not model or not reg.is_known(model):
            return False
        if reg.is_staged(model):
            # LRU-touch so the hinted adapter survives until its request
            reg.slot_of(model)
            return False
        faultpoints.hit_sync("weight_prestage", model=model)
        in_use = {n for n, c in self._adapter_refs.items() if c > 0}
        try:
            _slot, nbytes = await asyncio.get_running_loop().run_in_executor(
                None, lambda: reg.stage(model, in_use=in_use)
            )
        except RuntimeError:
            # every slot pinned by live requests — the request itself
            # will retry (and likely hit the same wall, loudly)
            return False
        self.stats["weight_prestage_bytes"] += nbytes
        return True

    def chain_coverage(self, chain: list[int]) -> int:
        """Longest prefix of chained hashes resident in ANY local tier
        (device radix, host pool, or disk index) — index-only probes, no
        data reads. The peer-pull path sizes its remote fetch from this:
        only the continuation PAST local coverage is worth wire time."""
        n = 0
        for h in chain:
            if self.kv.allocator.has_hash(h):
                n += 1
                continue
            if self.offload is not None and self.offload.tier_contains(h):
                n += 1
                continue
            break
        return n

    async def prefetch_hint(self, blocks: list) -> int:
        """Router-hinted host-tier prefetch (PRESERVE-style): ``blocks``
        is the request's prompt chain as (local_hash, chained_hash)
        pairs, shipped by the KV router the moment it picked this worker
        (kv_router/scheduler.py emit_prefetch). Probes the device tiers
        for the longest resident prefix, restores the host-tier
        continuation into freshly allocated pages, and commits them to
        the content-addressed reuse pool — so when the request itself
        arrives, admission claims them as ordinary device prefix hits
        and TTFT never sees the h2d latency.

        Best-effort by design: bails without side effects under pool
        pressure, on mirrored engines (restores there are lockstep
        broadcasts), or when the tier is cold. The host chain is read
        NON-destructively (peek, not take): a request racing its own
        hint still finds the chain in the pool and restores normally —
        a hint can never make the hinted request slower. The host copies
        are only discarded after the device commit. Returns blocks
        restored."""
        if (
            self.offload is None
            or self.mirror is not None
            or not self.cfg.offload_async
            or not blocks
            or self._closed
        ):
            return 0
        chain = [s for _l, s in blocks]
        await self._offload_prejoin(chain)
        n_dev = 0
        for h in chain:
            if not self.kv.allocator.has_hash(h):
                break
            n_dev += 1
        tail = blocks[n_dev:]
        if not tail:
            return 0
        hashes, data = self.offload.peek_chain([s for _l, s in tail])
        if not hashes:
            return 0
        fresh = self.kv.allocator.allocate(len(hashes))
        if fresh is None:
            return 0
        upload = self.offload.begin_upload(
            hashes, data, [b.idx for b in fresh]
        )
        try:
            # wait out the h2d BEFORE taking the device lock — holding it
            # across the transfer would stall every decode window for the
            # upload duration, re-exposing the very latency this hides.
            # Bounded: a wedged executor must degrade this hint to a cold
            # restore, not wedge the (serial) prefetch listener with it
            if upload.future is not None:
                await asyncio.get_running_loop().run_in_executor(
                    None, upload.future.result, 30.0
                )
            async with self._device_lock:
                await asyncio.get_running_loop().run_in_executor(
                    None, self._prefetch_land_device, upload
                )
        except Exception:  # noqa: BLE001 — prefetch is advisory
            logger.exception("hinted prefetch restore failed")
            self.kv.allocator.free(fresh)
            self.offload.cancel_upload(upload)
            return 0
        # commit the restored pages into the reuse pool under their
        # chained hashes (parent linkage from the hint), then drop our
        # ref — they become LRU-claimable device prefix blocks, exactly
        # like blocks a finished sequence left behind. A hash that went
        # device-resident DURING the upload (the request raced its own
        # hint) is not adopted — that block returns to the free list.
        # Only now do the host copies go (entries a racing admission
        # already took are fine — content is hash-addressed, identical).
        parent = chain[n_dev - 1] if n_dev else None
        adopted = 0
        for b, (local, seq_hash) in zip(fresh, tail):
            if self.kv.allocator.adopt_restored(b, seq_hash, local, parent):
                b.prefetched = True
                adopted += 1
            parent = seq_hash
        self.kv.allocator.free(fresh)
        self.offload.discard_chain(hashes)
        self.offload.note_prefetch_landed(upload)
        return adopted

    def _prefetch_land_device(self, upload) -> None:
        """Executor thread: flush pending evictions that may reference
        the prefetch's pages, then scatter the landed upload."""
        self._before_landing()
        self.k_cache, self.v_cache = self.offload.finish_upload(
            self.k_cache, self.v_cache, upload, account=False
        )

    # ---- decode ----

    def _mixed_fusable(self) -> bool:
        """Can the in-flight prefills' next chunks fuse into one mixed
        step? Needs the mixed-batch path on, no multi-host mirror (the
        fused step is not a broadcast op — mirrored engines keep the
        alternating scheduler), a head-of-line chunk that isn't routed
        through sp ring attention (admission never packs a ring prompt
        behind others, so only states[0] can ring), and something to
        fuse WITH: a live decode batch, or at least two prompts packing
        into each other (a lone prefill with nothing decoding gains
        nothing from the fused dispatch — the dedicated prefill program
        is cheaper)."""
        sts = self._prefill_states
        return (
            self.cfg.mixed_batch
            and bool(sts)
            and self.mirror is None
            and not self._ring_chunk(sts[0].seq, sts[0].pos)
            and (self._n_active > 0 or len(sts) > 1)
        )

    def _pick_window(self) -> int:
        """Fused steps for the next dispatch: 1 while *actionable* admission
        work is pending (a long window would delay waiting requests), else
        the largest power of two within every active sequence's remaining
        stop/context headroom. Waiting requests that CANNOT admit right now
        (pool backpressure, batch full) don't disable fusion — that would
        reintroduce the per-token host sync exactly under load. An
        in-flight prefill whose chunks fuse into mixed steps is not
        actionable admission work either (it advances WITH the decode
        steps), so it no longer collapses the window — though mixed
        dispatch itself never consults this (a fused step is inherently
        one decode step per chunk)."""
        phase = self._clock.mark("admit")
        n = self._window_steps()
        self._clock.mark(phase)
        return n

    def _window_steps(self) -> int:
        batch_full = self._n_active >= self.cfg.max_batch_size
        actionable = (
            (bool(self._prefill_states) and not self._mixed_fusable())
            or (not self._waiting_is_empty() and not batch_full
                and not self._backpressured)
            or (bool(self._remote_ready) and not batch_full)
        )
        if actionable or self.cfg.decode_window <= 1:
            return 1
        if self._chained():
            return self._chained_window_steps()
        headroom = self.cfg.decode_window
        for seq in self._active:
            if seq is None:
                continue
            headroom = min(headroom, self.cfg.max_context - seq.seq_len)
            sc = seq.request.stop_conditions
            if sc.max_tokens is not None:
                headroom = min(headroom, sc.max_tokens - seq.generated)
        n = 1
        while n * 2 <= headroom and n * 2 <= self.cfg.decode_window:
            n *= 2
        return n

    # ---- the chained loop's view of what is in flight ----

    def _chained(self) -> bool:
        """Does the loop enqueue a program before it has fetched the one
        before, whatever the two programs' kinds? Not under the
        multi-host mirror: its rows are host arrays that describe a
        frozen batch (it keeps the frozen form: ``_decode_once``)."""
        return self.cfg.decode_pipeline and self.mirror is None

    def _pending(self, seq: _Sequence) -> int:
        """Device steps enqueued for ``seq`` that the host has not
        emitted: those of the program in flight, if it held the slot
        when that was enqueued."""
        w = self._inflight
        if w is None or w["slots"].get(seq.slot) is not seq:
            return 0
        return w["n"]

    def _pending_rows(self) -> np.ndarray:
        """``_pending`` by decode slot (a vacated slot's: 0)."""
        out = np.zeros(self.cfg.max_batch_size, np.int32)
        w = self._inflight
        if w is not None:
            for i, seq in w["slots"].items():
                if self._active[i] is seq:
                    out[i] = w["n"]
        return out

    def _tokens_left(self, seq: _Sequence) -> tuple[int, int]:
        """(tokens ``seq`` can still be given, positions its context
        still has), both behind what is already enqueued for it. The
        first never exceeds the second; EOS or a stop token may end the
        stream sooner, never later."""
        pending = self._pending(seq)
        room = self.cfg.max_context - seq.seq_len - pending
        sc = seq.request.stop_conditions
        if sc.max_tokens is None:
            return room, room
        return min(room, sc.max_tokens - seq.generated - pending), room

    def _reach(self, seq: _Sequence, n: int) -> int:
        """One past the last position a dispatch of ``n`` steps must
        have a block of ``seq``'s for: behind its steps already
        enqueued, and no further than the tokens it can still be given.
        A step past that (a chained window outlives its shortest row)
        writes through a zero table entry into reserved page 0, or into
        the spare room of the row's last block, and its token is a
        discard: it costs no block, so chaining asks the pool for
        nothing the unchained loop would not."""
        return seq.seq_len + self._pending(seq) + max(
            min(n, self._tokens_left(seq)[0]), 0)

    def _leaving(self, seq: _Sequence) -> bool:
        """Has ``seq``'s leave gone up already (``_retire_ended``)? The
        step state then holds length 0 for a slot that still has its
        sequence: every token it will get is in the program in flight."""
        return seq.slot >= 0 and self._rows.seq_lens[seq.slot] == 0

    def _retire_ended(self) -> None:
        """The host knows ``max_tokens`` and ``max_context``: a row whose
        every remaining token is already enqueued leaves the device's
        batch with the NEXT dispatch (``StepState.release``: cells), so
        no program runs a row that has ended and none is queued behind
        the last row's end. The host's side of the leave (``_finish``)
        follows when the program in flight is emitted. A row that ends
        by EOS or a stop token is only found there: its queued steps
        are discarded (``_emit_window``)."""
        if self._inflight is None:
            return
        for seq in self._active:
            if (seq is not None and not seq.finished
                    and not self._leaving(seq)
                    and self._tokens_left(seq)[0] <= 0):
                self._rows.release(seq.slot)

    def _chained_window_steps(self) -> int:
        """A chained window's steps: as many as the longest-lived row
        still needs, rounded up to a power of two (a row that ends
        sooner has its tail discarded), at most HALF ``decode_window``
        (with the host's gap hidden a
        window's length has no host cost to amortise, and what is queued
        behind the running program is what an arrival waits out: two
        chained windows of 2 cost it what one unchained window of 4
        did), and never past a row's ``max_context``: a write past the
        table is not a discard. 0: no row has a step to take."""
        cap = max(self.cfg.decode_window // 2, 1)
        want, room = 0, cap
        for seq in self._active:
            if seq is None or seq.finished or self._leaving(seq):
                continue
            left, ctx = self._tokens_left(seq)
            if left <= 0:
                continue  # (leaves with this dispatch: _retire_ended)
            want, room = max(want, left), min(room, ctx)
        if want == 0:
            return 0
        n = 1
        while n < min(want, cap):
            n *= 2
        while n > room:
            n //= 2
        return n

    def _preempt(self, seq: _Sequence) -> None:
        """Evict a running sequence under pool pressure (ref vllm patch
        scheduler edits, patch:249-742: swap/recompute preemption). The
        recompute flavor composes with the content-addressed reuse pool:
        freed full blocks stay claimable by hash (and park in the host
        offload tier on eviction), so re-admission re-claims the prefix
        and only recomputes the uncommitted tail — never silent
        truncation."""
        self._release_slot(seq)
        self.kv.release(seq.hold)
        seq.cached_prefix = 0
        # resume at the FRONT of the waiting queue: the whole token list
        # (prompt + generated so far) re-admits as a prefill whose final
        # sampled token simply continues the stream (PRNG steps continue
        # from seq.generated, so sampling is replay-exact)
        self._waiting_front.appendleft(seq)
        self.stats["preemptions"] += 1
        logger.info(
            "preempted request %s at %d tokens (pool pressure)",
            getattr(seq.context, "id", "?"), seq.seq_len,
        )

    def _youngest_active(self) -> Optional[_Sequence]:
        cand = [s for s in self._active if s is not None and not s.finished]
        return max(cand, key=lambda s: s.arrival_t) if cand else None

    def _evict_for_headroom(self, seq: _Sequence) -> bool:
        """Pool exhausted while growing ``seq``'s blocks: preempt the
        youngest active sequence — possibly ``seq`` itself — or, when
        nothing else is left to evict, LENGTH-finish ``seq`` (the pool
        cannot hold even one sequence at this length). ONE policy shared
        by the window and mixed dispatch paths so victim selection can't
        drift between them. Returns True when ``seq`` itself was removed
        (caller stops growing it)."""
        victim = self._youngest_active()
        if victim is seq or victim is None:
            if self._n_active <= 1:
                logger.warning(
                    "KV pool too small for request %s at %d tokens",
                    getattr(seq.context, "id", "?"), seq.seq_len,
                )
                self._finish(seq, FinishReason.LENGTH)
            else:
                self._preempt(seq)
            return True
        self._preempt(victim)
        return False

    async def _decode_once(self) -> None:
        """One turn of the decode path: enqueue the next program (a
        decode window, or a mixed step while prompts are prefilling),
        THEN fetch and emit the one before it (``_chained``; the
        unchained reference fetches its own at once). See
        ``EngineConfig.decode_pipeline`` for what drains the chain."""
        cfg = self.cfg
        self._clock.mark("provision")
        faultpoints.hit_sync("mid_decode")
        chained = self._chained()
        if self._mixed_fusable():
            if not chained:
                # the frozen form (the mirror's) cannot chain across the
                # membership change a completing prefill brings, and the
                # unchained one has nothing in flight
                await self._drain_inflight()
            if self._mixed_fusable():
                await self._mixed_step_once()
                return
            if self._n_active == 0:
                return
        if chained:
            self._retire_ended()
            if all(s is None or s.finished or self._leaving(s)
                   for s in self._active):
                # every row's last token is in the program in flight:
                # nothing to enqueue behind it
                await self._drain_inflight()
                return
        n = self._pick_window()
        # ensure every active sequence has blocks for the window's tokens,
        # behind those of its steps that are already enqueued (_pending)
        for seq in list(self._active):
            if seq is None or seq.finished or seq.slot < 0:
                continue  # may have been preempted earlier this pass
            if self._leaving(seq):
                continue
            if seq.context.is_stopped():
                self._finish(seq, FinishReason.CANCELLED)
                continue
            while (
                seq.slot >= 0
                and not seq.finished
                and self.kv.short(seq.hold, self._reach(seq, n))
            ):
                if self.kv.at_limit(seq.hold):
                    if self._inflight is not None:
                        # the requirement is inflated by the steps in
                        # flight — drain (emits their tokens, advances
                        # seq_len, nothing pending), re-pick the window
                        # from fresh lengths, and re-evaluate before
                        # declaring a context-limit finish, or the
                        # in-flight tokens would be discarded and the
                        # stream truncated up to a window early.
                        # min(): CLAMP to the previously validated n.
                        # Sequences already provisioned earlier in this
                        # pass hold seq_len_old + pending + n <= allocated;
                        # the drain turns that into seq_len_new + n' <=
                        # allocated only for n' <= n — a larger re-pick
                        # (the drain can finish a headroom-constraining
                        # sequence) would write past their blocks through
                        # zero table entries into reserved page 0
                        await self._drain_inflight()
                        n = min(n, self._pick_window())
                        continue
                    self._finish(seq, FinishReason.LENGTH)  # true ctx limit
                    break
                if self._grow(seq, self._reach(seq, n)):
                    continue
                if self._inflight is not None:
                    # chaining must never CAUSE a preemption: the blocks
                    # of the queued program's steps are the first thing to
                    # give back under pressure. Draining emits it
                    # (advancing seq_len by what was pending) and frees
                    # the headroom requirement. min(): same
                    # already-validated-sequences clamp as above.
                    await self._drain_inflight()
                    n = min(n, self._pick_window())
                    continue
                if chained and n > 1:
                    # nor must the window's length: a shorter one asks
                    # for fewer blocks ahead (the unchained ladder walks
                    # down with its shortest row; this window is as long
                    # as its LONGEST-lived row needs)
                    n //= 2
                    continue
                # pool exhausted: preempt the youngest running sequence
                # (possibly this one) instead of truncating output
                if self._evict_for_headroom(seq):
                    break
        if self._n_active == 0 or n == 0:
            await self._drain_inflight()
            return

        # The frozen form (the multi-host mirror's rows are host arrays
        # that describe ONE batch): if the membership changed under the
        # window in flight (finish, cancellation, preemption, admission),
        # the chained device tokens and the pending offset no longer
        # describe the current batch — drain first (survivors' tokens
        # still emit; a vacated slot's are discarded) and start an
        # unchained window. The chained loop needs none of this: a leave
        # is a few cells of the next dispatch (StepState.release) and a
        # row's pending steps are its own (_pending).
        if self._inflight is not None and not chained:
            infl = self._inflight["slots"]
            cur = {i: s for i, s in enumerate(self._active) if s is not None}
            if cur.keys() != infl.keys() or any(
                cur[i] is not infl[i] for i in cur
            ):
                await self._drain_inflight()
                if self._n_active == 0:  # drain may finish survivors
                    return

        # Speculative decoding: batches with an n-gram match verify gamma
        # proposals in one fused forward instead of a decode window.
        # Unchained (drains any pipeline first); bails to the normal path
        # when blocks are short or nothing matched. Composes with
        # penalties (sequential semantics modeled in the joint verify),
        # logprobs (emitted from the verify forward's own logits),
        # sliding-window models (the verify kernel computes exact
        # per-row window floors via its ``group`` row mapping), MLA
        # models (multi-token absorbed attention, write-before-attend),
        # gpt-oss models (per-layer windows and sinks thread through
        # the unrolled XLA verify), and the multi-host mirror (the
        # verify is a broadcast op). NO model family is gated off.
        spec_hot = False
        if (
            cfg.spec_gamma > 0
            and n > 1
            and not self._prefill_states
            # int8-with-scales cache: the verify forward has no scale
            # stream (gated loudly at init) — plain windows only
            and self.k_scales is None
        ):
            # Proposals must come from the FRESH tail (an undrained
            # window's tokens are part of it), but draining kills the
            # pipeline overlap — so with a window in flight, first probe
            # the stale tail cheaply: only a hit pays the drain, then
            # re-proposes on the advanced tail. No stale hit -> stay
            # pipelined (a fresh-only match is possible but rare, and the
            # next iteration's stale probe would see it anyway).
            proposals = self._propose_ngram()
            if proposals is not None:
                if self._inflight is not None:
                    await self._drain_inflight()
                    if self._n_active == 0:
                        return
                    proposals = self._propose_ngram()
                if proposals is not None and await self._spec_verify_once(
                    proposals
                ):
                    return
                # a stale hit whose fresh re-probe (or verify) missed:
                # the tail is HOT — a match existed a window's tokens
                # ago. Re-entering pipelined mode here would keep every
                # future probe one window behind the repetition, so
                # speculation could NEVER engage on a pipelined engine
                # (found via test_multihost_compose phase 4, which this
                # starved to 0 accepted tokens). Dispatch this one
                # window unchained so the next iteration probes fresh.
                spec_hot = True

        # Enqueue this window BEFORE the one in flight is fetched. Its
        # token inputs are that program's last sampled tokens, its
        # lengths and steps what that program left: all in the resident
        # step state (under the mirror: the in-flight token array and
        # the host arrays advanced by the pending count). Safe without
        # draining on a finish or a preemption because (a) the in-flight
        # program's writes land only ABOVE the commit horizon (a row
        # writes at its device length - 1 and up, the host commits blocks
        # below its own, smaller, length - 1: never into a
        # hash-claimable block), (b) a block given back and re-used is
        # written again by a program that is device-ordered AFTER the one
        # in flight (the next tenant's prefill, a restore's scatter; an
        # eviction's gather reads committed blocks only), (c) the window
        # pool gives pages back behind the HOST's position
        # (_grow), which lags the device's: conservative, and
        # (d) a state row's next tenant begins it (_begin_state_row) in
        # a program ordered after too, and a dead row keeps its state
        # row as it was. The alternating scheduler (a prefill that cannot
        # fuse: mixed batching off, a ring chunk, the mirror) places its
        # row between two windows and keeps the unchained window.
        pipe = (
            cfg.decode_pipeline
            and (chained or n > 1)
            and not self._prefill_states
            and not spec_hot
        )
        if pipe and chained and self._inflight is not None and (
                self._rows.stale(self._pending_rows())):
            # the step state has to go up whole (a slot was taken by a
            # remote admission, more cells than a delta holds, a dispatch
            # that raised): that describes a batch with nothing in flight
            await self._drain_inflight()
        if not pipe:
            await self._drain_inflight()
        if not pipe or self._inflight is None:
            if self._n_active == 0:
                return
            # min(): the provisioning pass above validated blocks for at
            # most n tokens per sequence; a fresh pick may shrink (e.g.
            # admission became actionable after a preemption, or a drain
            # finished the longest-lived row) but must never grow past
            # what was provisioned
            n = min(n, self._pick_window())
            if n == 0:
                return
        prev = self._inflight
        # a window in flight chains its tokens on the device: in the
        # resident step state, or under the mirror, whose previous output
        # is a multi-process array (eager indexing is illegal), as the
        # whole [n, B] array that lead_decode slices on device (followers
        # slice their own copy).
        tokens_in = (prev["toks"] if prev is not None
                     and self.mirror is not None else None)
        toks = await self._on_device(self._dispatch_window, n, tokens_in)
        self._inflight = {
            "toks": toks, "n": n,
            "lps": self._window_logprobs,
            # the clock's number of the window's program (its fetch
            # settles the programs up to it) and its description, for
            # the step that closes at its emission (tracing/loop_clock.py)
            "program": self._clock.programs,
            "info": self._clock.described(),
            "slots": {i: s for i, s in enumerate(self._active)
                      if s is not None and not self._leaving(s)},
        }
        if prev is not None:
            await self._emit_window(prev)
        if not pipe:
            await self._drain_inflight()

    def _propose_ngram(self) -> Optional[np.ndarray]:
        """Prompt-lookup drafts: match each sequence's trailing n-gram
        against its own earlier tokens and propose the continuation that
        followed last time (the draft-model-free speculation vLLM ships
        as prompt lookup / assisted generation). Returns [B, gamma] with
        -1 padding (never matches a real token id), or None when no slot
        produced a proposal."""
        g, ng = self.cfg.spec_gamma, self.cfg.spec_ngram
        out = np.full((self.cfg.max_batch_size, g), -1, np.int64)
        found = False
        for i, seq in enumerate(self._active):
            if seq is None or seq.finished:
                continue
            toks = seq.tokens
            if len(toks) < ng + 2:
                continue
            # vectorized sliding match over a bounded tail window (one
            # array conversion + ng compares, not a python tuple scan)
            arr = np.asarray(toks[-4097:], np.int64)
            key = arr[-ng:]
            hay = arr[:-1]  # a match ending at the tail itself is useless
            hits = hay[: len(hay) - ng + 1] == key[0]
            for k in range(1, ng):
                hits &= hay[k : len(hay) - ng + 1 + k] == key[k]
            idx = np.flatnonzero(hits)
            # the most recent occurrence BEFORE the trailing one
            idx = idx[idx < len(arr) - ng]
            if idx.size == 0:
                continue
            j = int(idx[-1])
            cont = arr[j + ng : j + ng + g]
            if cont.size:
                out[i, : cont.size] = cont
                found = True
        return out if found else None

    async def _spec_verify_once(self, proposals: np.ndarray) -> bool:
        """One fused verify of gamma proposals + bonus token per slot.
        Returns False (caller falls back to a plain window) when block
        headroom for the in-flight rows isn't available without
        preempting — speculation must never cause a preemption."""
        cfg = self.cfg
        g = cfg.spec_gamma
        T = g + 1
        if T > cfg.block_size:
            return False  # in-flight rows must fit a page (append kernel)
        for seq in list(self._active):
            if seq is None or seq.finished or seq.slot < 0:
                continue
            if self.kv.short(seq.hold, seq.seq_len + g):
                # (near the context limit plain windows clamp)
                if self.kv.at_limit(seq.hold) or not self._grow(
                        seq, seq.seq_len + g):
                    return False

        out_toks, n_accs, lps = await self._on_device(
            self._dispatch_verify, proposals.astype(np.int32),
        )
        self._clock.mark("emit")
        self.stats["decode_steps"] += 1
        for i, seq in list(enumerate(self._active)):
            if seq is None or seq.finished:
                continue
            n_acc = int(n_accs[i])
            self.stats["spec_proposed"] += int((proposals[i] >= 0).sum())
            self.stats["spec_accepted"] += n_acc
            k = int(self._logprob_ks[i])
            for t in range(n_acc + 1):
                if seq.finished:
                    break
                entry = None
                if lps is not None and k >= 0:
                    chosen, top_ids, top_lps = lps
                    entry = {
                        "logprob": float(chosen[i, t]),
                        "top": [
                            [int(top_ids[i, t, j]), float(top_lps[i, t, j])]
                            for j in range(k)
                        ],
                    }
                self._emit_token(seq, int(out_toks[i, t]), entry)
            if seq.finished or self._active[i] is not seq:
                continue
            self._rows.advance(i, seq.seq_len, seq.tokens[-1], seq.generated)
            self.kv.commit(seq.hold, seq.tokens, seq.seq_len - 1)
        self._step_done()
        return True

    async def _mixed_step_once(self) -> None:
        """ONE fused mixed step: the ``mixed_step_budget`` token budget
        packed across EVERY in-flight prefill (admission order, each
        prompt guaranteed a minimum chunk so none starves) AND one
        decode token for every active sequence, in a single device
        dispatch (llama.mixed_step). The decode side behaves exactly
        like a 1-step window (same commit horizon / emission /
        preemption rules); each prefill side advances like a
        `_prefill_step` chunk (same per-prompt cancel/error rollback,
        same per-segment ``engine.prefill`` span accounting — the fused
        dispatch's device time splits across the advancing prompts in
        proportion to their token take, so decode ITL stops absorbing
        chunk time and each prompt's traced prefill stays honest)."""
        cfg = self.cfg
        # per-prompt cancel: drop ONE cancelled prompt from the pack,
        # the others keep advancing in the same step
        for st in list(self._prefill_states):
            if st.seq.context.is_stopped():
                self._abort_prefill(st, FinishReason.CANCELLED)
        if not self._prefill_states:
            return
        # provision one decode token per active sequence, behind those of
        # its steps that are already enqueued (the chained loop enqueues
        # this step behind a window in flight; the other forms drained)
        if self._chained():
            self._retire_ended()
        for seq in list(self._active):
            if seq is None or seq.finished or seq.slot < 0:
                continue
            if self._leaving(seq):
                continue
            if seq.context.is_stopped():
                self._finish(seq, FinishReason.CANCELLED)
                continue
            while (
                seq.slot >= 0
                and not seq.finished
                and self.kv.short(seq.hold, self._reach(seq, 1))
            ):
                if (not self.kv.at_limit(seq.hold)
                        and self._grow(seq, self._reach(seq, 1))):
                    continue
                if self._inflight is not None:
                    # the queued program's blocks go back first: chaining
                    # never causes a preemption or an early LENGTH finish
                    await self._drain_inflight()
                    continue
                if self.kv.at_limit(seq.hold):
                    self._finish(seq, FinishReason.LENGTH)
                    break
                if self._evict_for_headroom(seq):
                    break
        if self._n_active == 0 and len(self._prefill_states) < 2:
            # a lone prefill alone: the alternating step is cheaper (what
            # is in flight holds finished rows' tokens: discards)
            await self._drain_inflight()
            return
        self._clock.mark("admit")
        packed = self._split_mixed_budget()
        self._clock.mark("provision")
        for st, take in list(packed):
            if not self.kv.grow(st.seq.hold, st.pos, st.pos + take):
                self._pool_full(st)
                packed.remove((st, take))
        if not packed:
            return
        if self._inflight is not None and self._rows.stale(
                self._pending_rows()):
            # the step state has to go up whole: nothing may be in flight
            await self._drain_inflight()
        try:
            step = await self._on_device(self._dispatch_mixed, packed)
        except Exception:  # noqa: BLE001
            # a fused-dispatch failure (lowering/compile) is not
            # attributable to one prompt: fail every in-flight prefill,
            # each with its OWN upload rollback (the donated caches are
            # intact; the decode rows simply didn't advance and retry
            # next iteration on the plain path)
            logger.exception(
                "mixed prefill step failed for requests %s",
                [st.seq.context.id for st, _ in packed],
            )
            for st in list(self._prefill_states):
                self._abort_prefill(st, FinishReason.ERROR)
            await self._drain_inflight()
            return
        # a prompt with chunks to go: its blocks commit chunk by chunk
        # (a window pool's go back from behind the window only once
        # committed). The step is enqueued: whatever reads those blocks
        # is enqueued after it
        for st, _take in packed:
            if st.pos < len(st.seq.tokens):
                self.kv.commit(st.seq.hold, st.seq.tokens, st.pos, chunk=True)
        prev, self._inflight = self._inflight, step
        if prev is not None:
            # the window ahead of it is fetched and emitted while the
            # mixed step runs
            await self._emit_window(prev)
        if step["completed"] or not self._chained():
            # the step's OWN result, before the next enqueue: a prompt it
            # completed gets its first token sampled on the host and its
            # row placed (the one exposed gap an admission leaves)
            await self._drain_inflight()

    def _split_mixed_budget(self) -> list[tuple["_PrefillState", int]]:
        """Pack the Sarathi token budget across the in-flight prefills:
        every prompt gets a minimum chunk of budget // n_prompts (at
        least 1 token — no prompt starves, the stall-free guarantee),
        and the leftover goes to the EARLIEST-admitted prompts first
        (admission order keeps TTFT ordering fair). Returns
        [(state, take)] with every take >= 1."""
        sts = self._prefill_states
        budget = self.cfg.mixed_step_budget
        rem = [len(st.seq.tokens) - st.pos for st in sts]
        floor = max(budget // len(sts), 1)
        takes = [min(r, floor) for r in rem]
        left = budget - sum(takes)
        for i in range(len(sts)):
            if left <= 0:
                break
            extra = min(left, rem[i] - takes[i])
            takes[i] += extra
            left -= extra
        takes = [self.kv.clip_take(st.seq.hold, st.pos, t)
                 for st, t in zip(sts, takes)]
        return list(zip(sts, takes))

    def _dispatch_mixed(self, packed: list[tuple["_PrefillState", int]]):
        """Executor thread: enqueue the fused mixed step over M prefill
        segments + the decode batch WITHOUT fetching its result. Returns
        its in-flight record (``_emit_window`` fetches and emits it):
        the decode tokens [B] and logprob arrays as device handles, and
        ``completed``: (segment, state) of every prompt whose final
        chunk it runs, with the segments' last logits ``p_logits``.

        Shape discipline: the segment count pads to a power-of-two
        bucket (dead segments: valid 0, zero tables — their rows land
        in reserved trash page 0 and their logits are never read) and
        every segment shares ONE bucketed length T = bucket(max take),
        so the compiled program count is bounded by segment-count
        buckets x prefill buckets, never by the live mixture."""
        cfg = self.cfg
        pending = self._pending_rows()
        self._check_provisioned(1, "mixed step")
        t0 = time.perf_counter()
        try:
            # land each prompt's reserved host chain (first step only);
            # eviction flushes are shared across the pack
            for st, _take in packed:
                self._offload_preamble(
                    st.upload if not st.restored else None, seq=st.seq
                )
                st.restored = True
            MP = _seg_bucket(len(packed))
            T = _bucket(max(take for _st, take in packed))
            toks_p = np.zeros((MP, T), np.int32)
            hists_p = np.zeros(MP, np.int32)
            valids_p = np.zeros(MP, np.int32)
            for i, (st, take) in enumerate(packed):
                chunk = st.seq.tokens[st.pos : st.pos + take]
                toks_p[i, : len(chunk)] = chunk
                hists_p[i] = st.pos
                valids_p[i] = len(chunk)
            penalized = self._penalties_active()
            want_lp = self._logprobs_active()
            kwargs = self._decode_rows("mixed", pending)
            # what the segments need, in ONE transfer
            segs = {"p_tokens": toks_p, "p_hists": hists_p,
                    "p_valids": valids_p,
                    "p_tables": self.kv.stack_tables(
                        (st.seq.hold for st, _take in packed), MP)}
            if penalized:
                kwargs.update(
                    counts=self._pen_counts,
                    prompt_mask=self._pen_mask,
                )
            quantized = self.k_scales is not None
            if quantized:
                self._flush_scale_resets()
                kwargs.update(
                    k_scales=self.k_scales, v_scales=self.v_scales
                )
            if self.adapters is not None:
                # decode rows use the slot-mirrored ids; prefill
                # segments carry their sequence's id (padded rows -1 =
                # base = exact zero delta)
                p_ids = np.full(MP, -1, np.int32)
                for i, (st, _take) in enumerate(packed):
                    p_ids[i] = st.seq.adapter_id
                kwargs.update(lora=self.adapters.device_stack())
                segs["p_adapter_ids"] = p_ids
            live_rows = int((self._rows.seq_lens > 0).sum())
            if self.state is not None:
                # a dead segment names a row past the state: dropped
                slots_p = np.full(MP, cfg.max_batch_size, np.int32)
                for i, (st, _take) in enumerate(packed):
                    self._state_preamble(st)
                    slots_p[i] = st.seq.state_slot
                kwargs.update(self._state_kw())
                segs["p_slots"] = slots_p
                snaps_p = self.kv.snap_rows(
                    [(st.seq.hold, st.pos + take) for st, take in packed], MP)
                if snaps_p is not None:
                    segs["p_snaps"] = snaps_p
                self._state_rows += len(packed) + live_rows
                self._note_state(len(packed), 1, live_rows)
            kwargs.update(jax.device_put(segs, self._rows.sharding))
            self._handed("mixed", 1)
            self._note_prefill_work(MP * T, int(valids_p.sum()))
            seq_lens = self._rows.seq_lens + pending
            self._note_decode_work(1, seq_lens, seg_pages=(
                MP * cfg.max_blocks_per_seq,
                int(((hists_p + valids_p + cfg.block_size - 1)
                     // cfg.block_size).sum()),
            ))
            self.kv.note_work(1, self._live_holds(seq_lens), [
                (st.pos, take) for st, take in packed])
            out = self._timed_dispatch(lambda: llama.mixed_step(
                self.params,
                cfg.model,
                *llama.ROWS_RESIDENT,
                k_cache=self.k_cache,
                v_cache=self.v_cache,
                use_pallas=self.use_pallas,
                mesh=self.mesh,
                with_logprobs=want_lp,
                **kwargs,
                **self._moe_kw(),
            ), key=("mixed", MP, T, penalized, want_lp)
                + self._lora_key())
            toks, p_logits, self.k_cache, self.v_cache = out[:4]
            rest = list(out[4:])
            self._rows.took(rest.pop(), 1)
            if self.state is not None:
                self.state = rest.pop(0)
            if quantized:
                self.k_scales = rest.pop(0)
                self.v_scales = rest.pop(0)
                self._note_quant_step(
                    rest.pop(0), self._n_active + int(valids_p.sum()),
                    gen_tokens=self._n_active,
                )
            if penalized:
                self._pen_counts = rest.pop(0)
            lps_dev = rest.pop(0) if want_lp else None
            if self._moe_layers:
                self._note_moe(rest.pop(0), 1,
                               live_rows + int(valids_p.sum()))
            completed = []
            for i, (st, take) in enumerate(packed):
                st.pos += take
                if st.pos >= len(st.seq.tokens):
                    completed.append((i, st))
            # device handles: fetched where the step is emitted, so that
            # the window ahead of it is emitted while it runs
            return {
                "toks": toks, "n": 1, "lps": lps_dev,
                "program": self._clock.programs,
                "info": self._clock.described(),
                "slots": {i: s for i, s in enumerate(self._active)
                          if s is not None and not self._leaving(s)},
                "packed": packed, "completed": completed,
                "p_logits": p_logits, "t0": t0,
            }
        except BaseException:
            self._mixed_device_ms(packed, t0)
            raise

    def _mixed_device_ms(self, packed, t0: float) -> None:
        """The fused dispatch's time, enqueue to result, lands on the
        traced prefill components, split across the advancing prompts in
        proportion to their token take (the chunks dominate the step;
        attributing the decode row share too slightly overcounts prefill
        but keeps decode ITL honest — the span decode streams no longer
        wait on)."""
        dt_ms = (time.perf_counter() - t0) * 1e3
        total_take = sum(take for _st, take in packed) or 1
        for st, take in packed:
            st.dev_ms += dt_ms * (take / total_take)

    def _note_compile(self, key: tuple, wall_ms: float, trace=None) -> None:
        """First dispatch of a program bucket: ledger it. The wall time
        of a first dispatch is dominated by trace+compile (steady-state
        dispatch of a compiled program returns in microseconds), so the
        entry's ``ms`` is the compile stall a cold request would have
        paid. With a request trace in scope the compile is also stamped
        into that request's timeline — the autopsy names it."""
        self._compiled_keys.add(key)
        entry = {"kind": key[0], "key": list(key[1:]),
                 "ms": round(wall_ms, 3)}
        self.compile_ledger.append(entry)
        if len(self.compile_ledger) > 512:
            del self.compile_ledger[:-256]
        self.stats["xla_compiles_total"] += 1
        self.stats["xla_compile_ms_total"] += wall_ms
        if trace is not None:
            tracing.RECORDER.event(
                "engine.xla_compile", trace=trace,
                kind=key[0], key=str(key[1:]), ms=round(wall_ms, 3),
            )
        if wall_ms > 1000.0:
            logger.info("XLA compile: %s %s took %.0f ms",
                        key[0], key[1:], wall_ms)

    def _timed_dispatch(self, thunk, key: Optional[tuple] = None,
                        trace=None, n: int = 1):
        """Run a device dispatch. ``key`` names its program bucket (kind +
        the shape coordinates the jit cache keys on): the first dispatch
        of each bucket is timed into the XLA compile ledger
        (_note_compile). Whatever the compiler raises — a Mosaic
        rejection of a Pallas kernel included — propagates: the engine
        serves the attention path it chose at construction
        (attention_path) or fails, never a quiet substitute. On the
        loop's clock the thunk's ``dispatch`` phase ends when the call
        returns (the program is enqueued); ``n`` is its device steps."""
        cold = key is not None and key not in self._compiled_keys
        if key is not None:
            self._clock.describe(key, cold, n, self._n_active,
                                 self.cfg.max_batch_size)
        faultpoints.hit_thread("mid_dispatch")
        t0 = time.perf_counter() if cold else 0.0
        out = thunk()
        self._clock.enqueued()
        if cold:
            self._note_compile(key, (time.perf_counter() - t0) * 1e3, trace)
        return out

    def _moe_kw(self) -> dict:
        """The step programs' keyword that makes an expert model return
        its routing counters (nothing for a dense model: its programs
        stay as they were)."""
        return {"moe_counters": True} if self._moe_layers else {}

    def _note_moe(self, sums, steps: int, live_rows: int) -> None:
        """One dispatch of an expert model: ``sums`` is the program's
        MoeTally output (a device array, not waited for here), ``steps``
        its device steps and ``live_rows`` the rows with a real token,
        summed over the steps."""
        m = self.cfg.model
        self._moe_pending.append((
            sums, self._moe_layers * m.local_experts * steps,
            self._moe_layers * m.num_experts_per_tok * live_rows,
        ))

    def _step_done(self, info: Optional[dict] = None) -> None:
        """Close the loop clock's step (``info``: the description of the
        program it emitted, where the loop has enqueued another since).
        An expert model's step carries, as the span's ``moe``, the
        routing counters that had come back from the device by now (a
        chained program's arrive with the step that emits its tokens)."""
        attrs = {}
        if self._filter_steps:
            attrs["filter_steps"], self._filter_steps = self._filter_steps, 0
        if self.state is not None:
            # sequences whose conv state the step's dispatches advanced
            attrs["state"], self._state_rows = self._state_rows, 0
        attrs.update(self.kv.step_attrs())
        moe = dict.fromkeys(MOE_COUNTERS, 0)
        while self._moe_pending and self._moe_pending[0][0].is_ready():
            sums, slots, assignments = self._moe_pending.popleft()
            sums = [int(v) for v in np.asarray(sums)]
            if len(sums) == 4:  # every expert is here: all of them held
                sums.append(assignments)
            for name, v in zip(MOE_COUNTERS, (slots, assignments, *sums)):
                moe[name] += v
        if not moe["moe_expert_slots"]:  # a dense model; nothing back yet
            self._clock.step_done(info, **attrs)
            return
        for name, v in moe.items():
            self.stats[name] += v
        self._clock.step_done(info, moe={k[4:]: v for k, v in moe.items()},
                              **attrs)

    def _note_decode_work(self, n: int, seq_lens: np.ndarray,
                          seg_pages: tuple = (0, 0)) -> None:
        """Work counters of one decode, mixed or verify dispatch, from
        what the engine hands the device (no device read): the batch's
        rows x ``n`` steps against the rows holding a live sequence, and
        the block-table pages the attention kernel is asked to walk
        (rows x the table's width x steps, plus a mixed step's prefill
        segments: ``seg_pages`` = (handed, live)) against the pages
        that hold live tokens."""
        st, bs = self.stats, self.cfg.block_size
        r = self._rows
        rows, width = r.tables.shape
        live = seq_lens[r.seq_lens > 0]
        st["rows_dispatched"] += rows * n
        st["rows_live"] += len(live) * n
        st["attn_table_pages"] += rows * width * n + seg_pages[0]
        st["attn_live_pages"] += int(((live + bs - 1) // bs).sum()) * n \
            + seg_pages[1]
        # steps whose sampler searches for a cut: a live row samples
        # under a top-k or a nucleus (ops/sampling._apply_topk_topp)
        if ((r.seq_lens > 0) & (r.temps > 0)
                & ((r.top_ks > 0) | (r.top_ps < 1.0))).any():
            st["sampler_filter_steps"] += n
            self._filter_steps += n

    def _live_holds(self, seq_lens: np.ndarray):
        """(hold, context length) of each live decode row: what the KV
        manager's work counters read, where it counts any."""
        return ((seq.hold, int(seq_lens[i]))
                for i, seq in enumerate(self._active)
                if seq is not None and self._rows.seq_lens[i] > 0)

    def _note_prefill_work(self, dispatched: int, real: int) -> None:
        """Prefill tokens handed to the device (bucket length x
        segments) against the real tokens among them."""
        self.stats["prefill_tokens_dispatched"] += dispatched
        self.stats["prefill_tokens_padding"] += dispatched - real

    def _dispatch_verify(self, proposals: np.ndarray):
        """Executor thread: fused verify forward + on-device acceptance.
        Returns (out_tokens [B, T], n_acc [B], lp arrays or None)."""
        cfg = self.cfg
        r = self._rows
        self._flush_evictions_budgeted()
        penalized = self._penalties_active()
        want_lp = self._logprobs_active()
        self._note_decode_work(1, r.seq_lens)
        if self.mirror is not None:
            # window tokens: last accepted token + proposals (-1 -> 0 for
            # a safe embed; acceptance on device uses the ORIGINAL -1s,
            # which never accept)
            window = np.concatenate(
                [r.tokens[:, None], np.maximum(proposals, 0)], axis=1)
            out = self._timed_dispatch(lambda: self.mirror.lead_verify(
                self.params, window, proposals,
                np.maximum(r.seq_lens - 1, 0).astype(np.int32),
                r.tables, r.seq_lens, r.seeds, r.steps,
                r.temps, r.top_ks, r.top_ps,
                self.k_cache, self.v_cache,
                n_spec=cfg.spec_gamma, use_pallas=self.use_pallas,
                penalties=(r.freq_pens, r.pres_pens, r.rep_pens)
                if penalized else None,
                pen_state=(self._pen_counts, self._pen_mask)
                if penalized else None,
                with_logprobs=want_lp,
            ), key=("verify", cfg.spec_gamma, penalized, want_lp))
            toks, n_acc, self.k_cache, self.v_cache = out[:4]
            rest = list(out[4:])
            if penalized:
                self._pen_counts = rest.pop(0)
            lps = rest.pop(0) if want_lp else None
            self._clock.landed()
            return toks, n_acc, lps
        # (the rows it is handed are not what it leaves: the accepted
        # counts are the host's to apply, and the next dispatch sends
        # the mirror whole)
        kwargs = self._decode_rows("verify")
        if penalized:
            kwargs.update(
                counts=self._pen_counts,
                prompt_mask=self._pen_mask,
            )
        self._handed("verify", 1)
        out = self._timed_dispatch(lambda: llama.verify_window(
            self.params,
            cfg.model,
            None,
            jax.device_put(proposals, r.sharding),
            *llama.ROWS_RESIDENT[1:],
            k_cache=self.k_cache,
            v_cache=self.v_cache,
            n_spec=cfg.spec_gamma,
            use_pallas=self.use_pallas,
            mesh=self.mesh,
            with_logprobs=want_lp,
            **kwargs,
        ), key=("verify", cfg.spec_gamma, penalized, want_lp))
        toks, n_acc, self.k_cache, self.v_cache = out[:4]
        rest = list(out[4:])
        if penalized:
            self._pen_counts = rest.pop(0)
        lps_dev = rest.pop(0) if want_lp else None
        lps = (
            tuple(np.asarray(jax.device_get(a)) for a in lps_dev)
            if lps_dev is not None else None
        )
        toks, n_acc = (np.asarray(jax.device_get(a)) for a in (toks, n_acc))
        self._clock.landed()
        return toks, n_acc, lps

    async def _drain_inflight(self) -> None:
        """Fetch + emit the program in flight, if any."""
        inflight, self._inflight = self._inflight, None
        if inflight is not None:
            await self._emit_window(inflight)

    async def _emit_window(self, window: dict) -> None:
        """Fetch a program's result (a decode window's [n, B] tokens, or
        a mixed step's [B] and its completed prompts' first tokens),
        emit it, and close the loop clock's step under its description."""
        packed = window.get("packed")  # a mixed step's segments

        def fetch(a):
            if hasattr(a, "addressable_data") and not getattr(
                a, "is_fully_addressable", True
            ):
                # multi-process replicated array: read the local shard,
                # complete for a replicated output (device_get would
                # wait on a collective followers never join)
                return np.asarray(a.addressable_data(0))
            return np.asarray(jax.device_get(a))

        def materialize():
            # a completed prompt's first token is sampled on the host's
            # side of the step (llama.mixed_step returns its logits)
            firsts = [
                (st, self._sample_prefill(st.seq, window["p_logits"][i]))
                for i, st in window.get("completed", ())
            ]
            toks = fetch(window["toks"])
            lp = window.get("lps")
            if lp is not None:
                lp = tuple(fetch(a) for a in lp)
            if packed is not None:  # one step: [B] -> [1, B]
                toks = toks[None]
                lp = lp and tuple(a[None] for a in lp)
                self._mixed_device_ms(packed, window["t0"])
            self._clock.landed(window["program"])
            return toks, lp, firsts

        toks_host, lps, firsts = await self._on_device(
            materialize, lock=False, first="device"
        )
        phase = self._clock.mark("emit")
        n = window["n"]
        self.stats["decode_steps"] += n
        # emit window tokens in step order; a sequence that hits a stop
        # condition mid-window has its tail tokens discarded (and so has
        # one that ended in the program before this one, which was
        # enqueued already: its slot's tokens here are all discards), and
        # a slot that changed hands since dispatch (finish ->
        # re-admission) must not receive the old occupant's tokens
        live = [
            (i, seq) for i, seq in window["slots"].items()
            if self._active[i] is seq and not seq.finished
        ]
        for step_i in range(n):
            for i, seq in live:
                if seq.finished:
                    continue
                entry = None
                k = int(self._logprob_ks[i])
                if lps is not None and k >= 0:
                    chosen, top_ids, top_lps = lps
                    entry = {
                        "logprob": float(chosen[step_i, i]),
                        "top": [
                            [int(top_ids[step_i, i, j]),
                             float(top_lps[step_i, i, j])]
                            for j in range(k)
                        ],
                    }
                self._emit_token(seq, int(toks_host[step_i, i]), entry)
        for i, seq in live:
            if seq.finished:
                continue
            self._rows.advance(i, seq.seq_len, seq.tokens[-1], seq.generated)
            self.kv.commit(seq.hold, seq.tokens, seq.seq_len - 1)
        if packed is not None:
            # a mixed step's prefill side: the prompts whose FINAL chunk
            # it ran emit and join the batch, in admission order
            self.stats["mixed_steps"] += 1
            self.stats["mixed_prefill_segments"] += len(packed)
            for st, first in firsts:
                self._prefill_done(st, first)
        self._clock.mark(phase)
        self._step_done(window["info"])

    def _dispatch_window(self, n: int, tokens_in=None):
        """Runs in an executor thread: dispatch one fused n-step
        decode+sample window WITHOUT syncing its result. Returns the
        sampled-token device array [n, B] (host np array on the mirror
        path, which syncs internally).

        With a program in flight (``self._inflight``: the loop records
        this window only after the thunk returns) this window's token
        inputs are that program's last sampled tokens and a row's length
        and steps are its ``_pending`` count ahead of the host's mirror.
        The resident step state already holds all three (the chain stays
        on device); on the mirror path ``tokens_in`` is the in-flight
        window's token array and the host arrays advance."""
        cfg = self.cfg
        pending = self._pending_rows()
        if pending.any() and tokens_in is None and self.mirror is not None:
            raise RuntimeError(
                "pending window without a chained token source"
            )
        self._check_provisioned(n, f"window n={n}")
        self._flush_evictions_budgeted()
        r = self._rows
        seq_lens = (r.seq_lens + pending).astype(np.int32)
        self._note_decode_work(n, seq_lens)
        self.kv.note_work(n, self._live_holds(seq_lens))
        if self.mirror is not None:
            penalized = self._penalties_active()
            want_lp = self._logprobs_active()
            positions = (
                np.maximum(r.seq_lens - 1, 0) + pending
            ).astype(np.int32)
            out = self._timed_dispatch(lambda: self.mirror.lead_decode(
                self.params, r.tokens, positions,
                r.tables, seq_lens, r.seeds, r.steps + pending,
                r.temps, r.top_ks, r.top_ps,
                self.k_cache, self.v_cache,
                n_steps=n, use_pallas=self.use_pallas,
                penalties=(r.freq_pens, r.pres_pens, r.rep_pens)
                if penalized else None,
                pen_state=(self._pen_counts, self._pen_mask)
                if penalized else None,
                with_logprobs=want_lp,
                tokens_dev=tokens_in,
                sync=False,  # device handle; materialized at emission so
                # a pipelined next window dispatches without waiting
            ), key=("decode", n, penalized, want_lp), n=n)
            toks, self.k_cache, self.v_cache = out[0], out[1], out[2]
            rest = list(out[3:])
            if penalized:
                self._pen_counts = rest.pop(0)
            # device handles; materialized at emission
            self._window_logprobs = rest.pop(0) if want_lp else None
            return toks
        # (a program in flight has left the resident rows their
        # ``pending`` steps ahead of the mirror, its last sampled tokens
        # in them: the chain stays on the device)
        want_lp = self._logprobs_active()
        kw = dict(
            self._decode_rows("decode", pending),
            k_cache=self.k_cache,
            v_cache=self.v_cache,
            n_steps=n,
            use_pallas=self.use_pallas,
            mesh=self.mesh,
            with_logprobs=want_lp,
        )
        kw.update(self._lora_decode_kw())
        kw.update(self._moe_kw())
        kw.update(self._state_kw())
        quantized = self.k_scales is not None
        if quantized:
            self._flush_scale_resets()
            kw.update(k_scales=self.k_scales, v_scales=self.v_scales)
        penalized = self._penalties_active()
        if penalized:
            kw.update(counts=self._pen_counts, prompt_mask=self._pen_mask)
        out = self._timed_dispatch(
            lambda: llama.decode_window(
                self.params, cfg.model, *llama.ROWS_RESIDENT, **kw),
            key=("decode", n, penalized, want_lp) + self._lora_key(), n=n,
        )
        toks, self.k_cache, self.v_cache = out[:3]
        rest = list(out[3:])
        r.took(rest.pop(), n)
        live_rows = int((r.seq_lens > 0).sum())
        if self.state is not None:
            self.state = rest.pop(0)
            self._state_rows += live_rows
            self._note_state(0, n, live_rows)
        if quantized:
            self.k_scales = rest.pop(0)
            self.v_scales = rest.pop(0)
            self._note_quant_step(
                rest.pop(0), self._n_active * n,
                gen_tokens=self._n_active * n,
            )
        if penalized:
            self._pen_counts = rest.pop(0)
        lps = rest.pop(0) if want_lp else None
        if self._moe_layers:
            self._note_moe(rest.pop(0), n, live_rows * n)
        # device handles; materialized at emission (fetching here would
        # block the pipelined dispatch on the window's full execution)
        self._window_logprobs = lps
        return toks

    # ---- token emission + finish logic ----

    def _emit_token(self, seq: _Sequence, token: int, lp_entry=None) -> None:
        req = seq.request
        sc = req.stop_conditions
        seq.tokens.append(token)
        seq.generated += 1
        self.stats["tokens_generated"] += 1
        if seq.generated == 1:
            # per-model TTFT family (the trace-replay assertion plane)
            h = self.hist_ttft.get(seq.model)
            if h is None:
                h = self.hist_ttft[seq.model] = Histogram(MS_BUCKETS)
            h.observe((time.monotonic() - seq.arrival_t) * 1000.0)
        if seq.trace is not None and seq.generated == 1:
            # first-token anchor for the TTFT decomposition; later tokens
            # pay only the seq.trace None-check above
            tracing.RECORDER.event(
                "engine.first_token", trace=seq.trace,
                request_id=seq.context.id, step=self._clock.seq,
            )

        finish: Optional[FinishReason] = None
        eos_ids = set(req.eos_token_ids or [])
        min_ok = seq.generated >= (sc.min_tokens or 0)
        if token in (sc.stop_token_ids or []):
            finish = FinishReason.STOP
        elif not sc.ignore_eos and token in eos_ids and min_ok:
            finish = FinishReason.EOS
        elif sc.max_tokens is not None and seq.generated >= sc.max_tokens:
            finish = FinishReason.LENGTH
        elif seq.seq_len >= self.cfg.max_context:
            finish = FinishReason.LENGTH
        elif seq.context.is_stopped():
            finish = FinishReason.CANCELLED

        out = LLMEngineOutput(token_ids=[token])
        if lp_entry is not None:
            out.logprobs = [lp_entry]
        if finish is not None:
            out.finish_reason = finish
            out.prompt_tokens = seq.prompt_len
            out.completion_tokens = seq.generated
            out.kv_overlap_blocks = seq.cached_prefix // self.cfg.block_size
        seq.out_queue.put_nowait(out)
        if finish is not None:
            self._finish(seq, finish, emit=False)

    def _finish(self, seq: _Sequence, reason: FinishReason, emit: bool = True) -> None:
        if seq.finished:
            return
        seq.finished = True
        if seq.model:
            # release the adapter's eviction pin (idempotent via the
            # finished flag above)
            held = self._adapter_refs.get(seq.model, 0)
            if held > 0:
                self._adapter_refs[seq.model] = held - 1
        if emit:
            seq.out_queue.put_nowait(
                LLMEngineOutput(
                    finish_reason=reason,
                    prompt_tokens=seq.prompt_len,
                    completion_tokens=seq.generated,
                )
            )
        self._release_slot(seq)
        self.kv.release(seq.hold)
        self._wake.set()

    def _release_slot(self, seq: _Sequence) -> None:
        """Vacate a sequence's continuous-batching slot (shared by finish
        and preemption so the teardown can't drift between them)."""
        if seq.slot >= 0:
            self._active[seq.slot] = None
            self._rows.release(seq.slot)
            self._n_active -= 1
            seq.slot = seq.state_slot = -1

    # ---------------- disaggregation hooks ----------------
    # (ref docs/disagg_serving.md:58-91; vllm patch remote-prefill states)

    def n_prompt_blocks(self, prompt_len: int) -> int:
        bs = self.cfg.block_size
        return (prompt_len + bs - 1) // bs

    def _guard_remote_adapter(self, req: PreprocessedRequest) -> None:
        """The disagg remote-prefill/decode paths have no adapter lane
        yet (the KV wire carries no adapter identity, and a prefill
        worker would silently compute BASE KV for an adapter prompt —
        wrong tokens with no error). Reject loudly; the monolithic path
        serves adapter traffic. Declared as a leftover in
        docs/multi_model.md."""
        if (
            self.adapters is not None
            and req.model
            and self.adapters.is_known(req.model)
        ):
            raise RuntimeError(
                f"adapter model {req.model!r} is not supported on the "
                "remote prefill/decode paths yet — route adapter "
                "traffic to monolithic workers"
            )

    def _extract_begin(self, what: str, req: PreprocessedRequest,
                       context) -> tuple[_Sequence, int]:
        """The prefill worker's side of a remote prefill, before its
        first chunk: what cannot be extracted is refused by name, and
        the prompt's hold is reserved (with this worker's own prefix
        cache). Returns the sequence and its cached history."""
        self.kv.refuse_transfer(f"{what} (disaggregation)")
        self._guard_remote_adapter(req)
        prompt = list(req.token_ids)
        seq = _Sequence(
            request=req,
            context=context,
            out_queue=asyncio.Queue(),
            tokens=prompt,
            prompt_len=len(prompt),
            trace=tracing.current_trace() if tracing.enabled() else None,
        )
        reserved = self._reserve(seq)
        if reserved is None:
            raise OutOfBlocks(f"cannot cover {len(prompt)}-token prompt")
        self.stats["prefix_cache_hits_tokens"] += reserved[0]
        return seq, reserved[0]

    async def prefill_extract(
        self, req: PreprocessedRequest, context, skip_blocks: int = 0,
        keep_on_device: bool = False, timings: Optional[dict] = None,
    ) -> tuple[int, Optional[dict], Optional[np.ndarray], Optional[np.ndarray]]:
        """Prefill-worker side: compute the prompt's KV (with this worker's
        own prefix cache), sample the first token (max_tokens=1 semantics,
        ref prefill_worker.py:109-137), and return the prompt's KV blocks
        after ``skip_blocks`` (the decode side's prefix hit). Blocks are
        committed to the reuse pool before being freed, so repeated
        prefixes stay warm on the prefill worker.

        ``keep_on_device`` returns jax.Arrays (the gather's own device
        buffers — safe after the source blocks are freed) instead of host
        copies: the in-process LocalKvPipe path hands them straight to the
        decode engine's scatter, so same-slice disagg never pays the
        d2h + h2d round-trip (VERDICT round-1 missing #3; the reference's
        same-node NIXL path is GPU-direct for the same reason).

        Under the multi-host mirror the gather is a mirrored op with
        replicated output (compiled all-gather over ICI/DCN) and the
        LEADER ships full host blocks over the transfer plane;
        ``keep_on_device`` is ignored there (a multi-process array cannot
        hand over in-process to a differently-meshed engine)."""
        if self.mirror is not None:
            keep_on_device = False
        seq, history = self._extract_begin("prefill_extract", req, context)
        prompt = seq.tokens
        try:
            async with self._device_lock:
                first_token, first_lp = await (
                    asyncio.get_running_loop().run_in_executor(
                        None, self._prefill_device, seq, history
                    )
                )
                n_prompt = self.n_prompt_blocks(len(prompt))
                idxs = [b.idx for b in seq.hold.blocks[skip_blocks:n_prompt]]
                if idxs:
                    t_g = time.perf_counter()
                    k_np, v_np = await asyncio.get_running_loop().run_in_executor(
                        None, self._gather_device, idxs, keep_on_device
                    )
                    if timings is not None:
                        # the d2h extraction is HANDOFF work, not prompt
                        # compute — the caller folds it into the
                        # kv_transfer decomposition (ttft.py)
                        timings["gather_ms"] = (
                            timings.get("gather_ms", 0.0)
                            + (time.perf_counter() - t_g) * 1e3
                        )
                else:
                    k_np = v_np = None
            self.kv.commit(seq.hold, seq.tokens, seq.seq_len)
        finally:
            self.kv.release(seq.hold)
        return first_token, first_lp, k_np, v_np

    async def prefill_extract_stream(
        self, req: PreprocessedRequest, context, skip_blocks: int = 0,
        keep_on_device: bool = False, segment_blocks: int = 0,
        on_segment=None, timings: Optional[dict] = None,
    ) -> tuple[int, Optional[dict], int]:
        """Streamed twin of :meth:`prefill_extract` (ROADMAP item 1 /
        FlowKV): the prompt prefills chunk by chunk and every chunk's
        freshly completed blocks are gathered and handed to
        ``on_segment(b0, k_seg, v_seg)`` the moment the chunk's compute
        finishes — the caller ships them while the NEXT chunk computes,
        hiding the transfer behind prefill. ``b0`` is the block offset
        relative to ``skip_blocks``; segments arrive in order and cover
        [skip_blocks, n_prompt_blocks) exactly once. ``segment_blocks``
        caps a segment's block count (0 = one segment per prefill chunk).

        The FINAL segment (including the prompt's partial last block) is
        emitted BEFORE first-token sampling, so even the tail transfer
        overlaps the sampling dispatch instead of sitting on TTFT.

        Gathers go through the same bucketed ``_gather_device`` as the
        bulk path, so the compiled-program count is bounded by segment
        GEOMETRY buckets, not per-request shapes (test_compiled_perf).
        Returns (first_token, first_lp, blocks_emitted)."""
        if self.mirror is not None:
            keep_on_device = False
        seq, history = self._extract_begin(
            "prefill_extract_stream", req, context)
        prompt = seq.tokens
        bs = self.cfg.block_size
        n_prompt = self.n_prompt_blocks(len(prompt))
        sent = skip_blocks
        loop = asyncio.get_running_loop()

        async def emit_upto(full: int) -> None:
            nonlocal sent
            while sent < full:
                hi = (
                    min(full, sent + segment_blocks)
                    if segment_blocks > 0 else full
                )
                idxs = [b.idx for b in seq.hold.blocks[sent:hi]]
                t_g = time.perf_counter()
                async with self._device_lock:
                    k_seg, v_seg = await loop.run_in_executor(
                        None, self._gather_device, idxs, keep_on_device
                    )
                if timings is not None:
                    # per-segment d2h time is handoff work too (same
                    # accounting as the bulk twin's single gather)
                    timings["gather_ms"] = (
                        timings.get("gather_ms", 0.0)
                        + (time.perf_counter() - t_g) * 1e3
                    )
                await on_segment(sent - skip_blocks, k_seg, v_seg)
                sent = hi

        try:
            # the device lock is taken PER CHUNK (and per gather), not
            # across the whole prompt: M concurrent streamed extracts —
            # and a co-resident serving loop's decode steps — interleave
            # chunk-wise instead of serializing whole prompts, so every
            # advancing prompt streams its segments as its own chunks
            # land (the multi-prompt twin of the mixed-batch packer;
            # PrefillWorker ``concurrency`` drives it). Safe because the
            # sequence's blocks are reserved (no interleaved dispatch
            # can touch them) and every cache-donating dispatch still
            # serializes under the lock. on_segment backpressure is paid
            # OUTSIDE the lock, so a slow peer throttles only its own
            # prompt, never the whole engine.
            async with self._device_lock:
                await loop.run_in_executor(None, self._offload_preamble)
            pos = history
            logits = None
            while pos < len(prompt):
                p0, t_c = pos, time.perf_counter()
                async with self._device_lock:
                    logits, pos = await loop.run_in_executor(
                        None, self._run_one_chunk, seq, pos
                    )
                if self.cost is not None and pos > p0:
                    self.cost.observe_prefill(
                        pos - p0, max(time.perf_counter() - t_c, 1e-9)
                    )
                # blocks whose every position is now written; the
                # final chunk also releases the partial last block
                full = n_prompt if pos >= len(prompt) else min(
                    pos // bs, n_prompt
                )
                if on_segment is not None and full > sent:
                    await emit_upto(full)
            async with self._device_lock:
                first_token, first_lp = await loop.run_in_executor(
                    None, self._sample_prefill, seq, logits
                )
            self.kv.commit(seq.hold, seq.tokens, seq.seq_len)
        finally:
            self.kv.release(seq.hold)
        return first_token, first_lp, max(n_prompt - skip_blocks, 0)

    def _gather_device(self, idxs: list[int], keep_on_device: bool = False,
                       with_scales: bool = False):
        """Bucketed d2h page gather. With an int8 device cache the pages
        are quantized codes: ``with_scales=True`` returns the device
        codec verbatim — (k, v, k_scales, v_scales) with [L, n] scale
        stacks matching the tier/wire entry form, zero re-encode — while
        ``with_scales=False`` (callers that need full width: disagg
        extract, legacy peers) dequantizes on device before the d2h and
        counts the bounce in ``kv_device_export_requant_total``."""
        from .offload import _gather_blocks, _gather_blocks_s, _pad_idxs

        padded = _pad_idxs(idxs)
        if self.mirror is not None:
            k, v = self.mirror.lead_kv_gather_full(
                self.k_cache, self.v_cache, padded
            )
            return k[:, :, : len(idxs)], v[:, :, : len(idxs)]
        if self.k_scales is not None:
            k, v, ks, vs = _gather_blocks_s(
                self.k_cache, self.v_cache, self.k_scales, self.v_scales,
                jnp.asarray(padded),
            )
            n = len(idxs)
            if with_scales:
                k, v = k[:, :, :n], v[:, :, :n]
                ks, vs = ks[:, :n], vs[:, :n]
                if keep_on_device:
                    return k, v, ks, vs
                return tuple(
                    np.asarray(jax.device_get(a)) for a in (k, v, ks, vs)
                )
            # full-width bounce (visible, not silent): dequantize with
            # the plane scales before the d2h
            self.stats["kv_device_export_requant_total"] += n
            k = _dequant_gathered(k, ks, self.cfg.model.dtype)
            v = _dequant_gathered(v, vs, self.cfg.model.dtype)
            k, v = k[:, :, :n], v[:, :, :n]
            if keep_on_device:
                return k, v
            return np.asarray(jax.device_get(k)), np.asarray(jax.device_get(v))
        k, v = _gather_blocks(self.k_cache, self.v_cache, jnp.asarray(padded))
        k, v = k[:, :, : len(idxs)], v[:, :, : len(idxs)]
        if keep_on_device:
            return k, v
        return np.asarray(jax.device_get(k)), np.asarray(jax.device_get(v))

    def begin_remote(self, request: Context) -> Optional["RemoteHandle"]:
        """Decode side, before enqueueing a remote prefill: match the local
        prefix cache and pre-allocate the sequence's blocks (the reference
        allocates decode blocks up front and ships their ids in
        RemotePrefillRequest). Returns None when the pool can't cover the
        request — caller falls back to local serving's backpressure.

        Composes with the multi-host mirror: the reservation is pure
        host-side allocator work, and the eventual remote-KV landing
        (complete_remote -> _scatter_device) broadcasts the blocks so
        every process scatters its shards in lockstep."""
        self.kv.refuse_transfer("begin_remote (disaggregation)")
        req: PreprocessedRequest = request.data
        if isinstance(req, dict):
            req = PreprocessedRequest.from_dict(req)
        prompt = list(req.token_ids)
        if (
            not prompt
            or len(prompt) >= self.cfg.max_context
            # OOB ids: fall back to local serving, whose generate()
            # rejects them with the clean vocab-range error
            or not self._tokens_in_vocab(prompt)
            # adapter traffic: fall back to local serving (the remote
            # paths have no adapter lane — _guard_remote_adapter)
            or (
                self.adapters is not None
                and req.model
                and self.adapters.is_known(req.model)
            )
        ):
            return None
        seq = _Sequence(
            request=req,
            context=request.context,
            out_queue=asyncio.Queue(),
            tokens=prompt,
            prompt_len=len(prompt),
            trace=tracing.current_trace() if tracing.enabled() else None,
        )
        if self._reserve(seq) is None:
            return None
        self.stats["requests_total"] += 1
        self.stats["prompt_tokens_total"] += seq.prompt_len
        return RemoteHandle(
            seq=seq,
            skip_blocks=seq.hold.committed,
            n_prompt_blocks=self.n_prompt_blocks(len(prompt)),
        )

    def release_remote(self, handle: "RemoteHandle") -> None:
        """Local-prefill fallback chosen after begin_remote: return the
        blocks untouched (no output emitted; caller re-submits locally)."""
        self.stats["requests_total"] -= 1
        self.stats["prompt_tokens_total"] -= handle.seq.prompt_len
        self.kv.release(handle.seq.hold)

    async def complete_remote(
        self,
        handle: "RemoteHandle",
        first_token: int,
        k_data: Optional[np.ndarray],
        v_data: Optional[np.ndarray],
        first_lp: Optional[dict] = None,
        k_scales: Optional[np.ndarray] = None,
        v_scales: Optional[np.ndarray] = None,
    ) -> asyncio.Queue:
        """KV landed from the prefill worker: scatter it into the
        pre-allocated pages, register the sequence for continuous-batching
        decode, emit the (already sampled) first token with the logprob
        entry the prefill worker computed for it (if requested).
        ``k_scales``/``v_scales`` ([L, n] f32) mark a quantized wire
        delivery — the dequant fuses into the device-side scatter."""
        seq = handle.seq
        if k_data is not None and k_data.shape[2]:
            n = int(k_data.shape[2])
            lo = handle.skip_blocks
            idxs = [b.idx for b in seq.hold.blocks[lo : lo + n]]
            async with self._device_lock:
                await asyncio.get_running_loop().run_in_executor(
                    None, self._scatter_device, idxs, k_data, v_data,
                    k_scales, v_scales,
                )
        self.stats["prefix_cache_hits_tokens"] += seq.cached_prefix
        self._emit_token(seq, first_token, first_lp)
        if not seq.finished:
            self.kv.commit(seq.hold, seq.tokens, seq.seq_len - 1)
            self._remote_ready.append(seq)
            self._wake.set()
        return seq.out_queue

    async def scatter_remote_segment(
        self, handle: "RemoteHandle", b0: int, k_data, v_data,
        k_scales=None, v_scales=None,
    ) -> None:
        """Streamed disagg landing (decode side): scatter ONE segment's
        blocks into the pre-allocated reservation the moment it arrives,
        instead of buffering the full [L, Hkv, n, bs, D] stack until
        prefill completes. ``b0`` is the block offset relative to the
        handle's skip_blocks. Replay-safe: a redelivered stream
        re-scatters the same still-uncommitted pages, so exactly-once
        queue semantics need no extra bookkeeping here.

        The data stack is padded HOST-side to the bucketed index count
        (pad rows target trash block 0), so the donated scatter compiles
        one program per segment-size bucket — not one per distinct
        segment geometry (test_compiled_perf guard)."""
        seq = handle.seq
        n = int(k_data.shape[2])
        if n == 0:
            return
        lo = handle.skip_blocks + b0
        blocks = seq.hold.blocks[lo : lo + n]
        if seq.finished or len(blocks) != n:
            raise RuntimeError(
                f"remote segment [{b0}, {b0 + n}) outside the live "
                f"reservation of {getattr(seq.context, 'id', '?')}"
            )
        idxs = [b.idx for b in blocks]
        async with self._device_lock:
            await asyncio.get_running_loop().run_in_executor(
                None, self._scatter_segment_device, idxs, k_data, v_data,
                k_scales, v_scales,
            )

    def _scatter_segment_device(self, idxs: list[int], k_data, v_data,
                                k_scales=None, v_scales=None) -> None:
        from .offload import _pad_idxs

        bucket = len(_pad_idxs(idxs))
        if int(k_data.shape[2]) < bucket:
            pad = [(0, 0)] * k_data.ndim
            pad[2] = (0, bucket - int(k_data.shape[2]))
            spad = ((0, 0), (0, bucket - int(k_data.shape[2])))
            if isinstance(k_data, np.ndarray):
                k_data = np.pad(k_data, pad)
                v_data = np.pad(v_data, pad)
                if k_scales is not None:
                    k_scales = np.pad(np.asarray(k_scales, np.float32), spad)
                    v_scales = np.pad(np.asarray(v_scales, np.float32), spad)
            else:  # device-resident segment (LocalKvPipe)
                k_data = jnp.pad(k_data, pad)
                v_data = jnp.pad(v_data, pad)
        self._scatter_device(idxs, k_data, v_data, k_scales, v_scales)

    def abort_remote(self, handle: "RemoteHandle", message: str = "") -> None:
        seq = handle.seq
        self.kv.release(seq.hold)
        seq.finished = True
        seq.out_queue.put_nowait(
            LLMEngineOutput(finish_reason=FinishReason.ERROR, text=message or None)
        )

    def _scatter_device(
        self, idxs: list[int], k_data: np.ndarray, v_data: np.ndarray,
        k_scales: Optional[np.ndarray] = None,
        v_scales: Optional[np.ndarray] = None,
    ) -> None:
        from .offload import (
            _pad_idxs,
            _scatter_blocks,
            _scatter_blocks_adopt,
            _scatter_blocks_q,
            _scatter_blocks_requant,
        )

        self._before_landing()
        padded = _pad_idxs(idxs)
        if self.mirror is not None:
            # mirrored landing: broadcast the UNPADDED host blocks (the
            # scatter core pads on device), every process scatters its
            # cache shards in lockstep. Quantized wire deliveries never
            # reach mirrors (the negotiation requires the capability,
            # which mirror-backed engines do not advertise).
            assert k_scales is None, "mirror landings are full-width"
            self.k_cache, self.v_cache = self.mirror.lead_kv_scatter(
                self.k_cache, self.v_cache, padded,
                np.asarray(k_data), np.asarray(v_data),
            )
            return
        # only real blocks ship over PCIe — the scatter cores pad the
        # stack to the bucketed index count on device
        if self.k_scales is not None:
            # int8 device cache: the plain cores' astype would truncate
            # real values into int8 codes. A matching int8 wire payload
            # adopts verbatim (payload + scales, same codec); anything
            # else (full-width, fp8 wire) re-quantizes on landing.
            k_j, v_j = jnp.asarray(k_data), jnp.asarray(v_data)
            if k_scales is not None and k_j.dtype == self.k_cache.dtype:
                core = _scatter_blocks_adopt
            else:
                core = _scatter_blocks_requant
            if k_scales is None:
                shape = (self.k_scales.shape[0], int(k_j.shape[2]))
                ks_j = vs_j = jnp.ones(shape, jnp.float32)
            else:
                ks_j = jnp.asarray(np.asarray(k_scales, np.float32))
                vs_j = jnp.asarray(np.asarray(v_scales, np.float32))
            (
                self.k_cache, self.v_cache, self.k_scales, self.v_scales,
            ) = core(
                self.k_cache, self.v_cache, self.k_scales, self.v_scales,
                jnp.asarray(padded), k_j, v_j, ks_j, vs_j,
            )
            return
        if k_scales is not None:
            # quantized delivery: dequant fuses into the donated scatter
            self.k_cache, self.v_cache = _scatter_blocks_q(
                self.k_cache, self.v_cache, jnp.asarray(padded),
                jnp.asarray(k_data), jnp.asarray(v_data),
                jnp.asarray(np.asarray(k_scales, np.float32)),
                jnp.asarray(np.asarray(v_scales, np.float32)),
            )
            return
        self.k_cache, self.v_cache = _scatter_blocks(
            self.k_cache,
            self.v_cache,
            jnp.asarray(padded),
            jnp.asarray(k_data),
            jnp.asarray(v_data),
        )


@dataclass
class RemoteHandle:
    """A decode-side reservation for a remotely-prefilled sequence."""

    seq: _Sequence
    skip_blocks: int
    n_prompt_blocks: int


@dataclass
class _PrefillState:
    """An in-flight chunked prefill: one chunk runs per scheduler
    iteration so decode steps interleave with long prompts (the
    reference gets this from its patched engine scheduler's chunked
    prefill; here it's native to the loop)."""

    seq: _Sequence
    pos: int  # next prompt index to prefill
    # reserved host chain's in-flight h2d stage (offload.RestoreUpload,
    # begun at reservation), or None when the host tier missed
    upload: Optional[object] = None
    restored: bool = False  # host-tier restore landed (first chunk)
    state_ready: bool = False  # the conv state's row is set (first chunk)
    # span anchors for the traced "engine.prefill" component: wall start
    # + accumulated per-chunk DEVICE milliseconds (the span duration —
    # wall time would absorb decode steps interleaved between chunks)
    t0_wall: float = field(default_factory=time.time)
    dev_ms: float = 0.0
