"""Compiled-program perf-property tests (no chip required).

The merged one-write decode's whole value claim is structural: the KV
caches are DONATED through the jit boundary and appended IN PLACE by one
Mosaic kernel per step, instead of 2L full-cache XLA scatter copies
(docs/performance.md "decode killer #2": ~0.55 GB copied per scatter on
the 1B config). These tests assert it on the artifacts a chip-free box
CAN produce:

  * ``jax.export`` with ``platforms=["tpu"]`` — Mosaic lowering is
    hardware-independent, so the TPU StableHLO module is inspectable on
    CPU: the Pallas kernels must appear as ``tpu_custom_call``s whose
    cache operands carry ``output_operand_alias`` (the in-place RMW),
    with ZERO full-cache-shaped ``stablehlo.scatter`` ops left;
  * a real CPU ``.lower().compile()`` — the executable's
    ``input_output_alias`` header must map both cache parameters to
    outputs (donation survived to the buffer assignment).

A negative control locks the regexes themselves: the XLA fallback path
(``use_pallas=False``) MUST trip the scatter detector — if it stops
doing so, the detector has rotted, not the product.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export as jexport
from jax.sharding import Mesh

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig

B, BLOCK, CTX, NSTEPS = 2, 16, 128, 4


def _decode_inputs(cfg):
    M = CTX // BLOCK
    num_blocks = B * M + 1
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(cfg, num_blocks, BLOCK)
    tables = jnp.asarray(np.arange(1, num_blocks, dtype=np.int32).reshape(B, M))
    return dict(
        params=params, k_cache=k_cache, v_cache=v_cache, tables=tables,
        tokens=jnp.zeros(B, jnp.int32),
        positions=jnp.full((B,), 10, jnp.int32),
        seq_lens=jnp.full((B,), 11, jnp.int32),
        seeds=jnp.zeros(B, jnp.int32), steps=jnp.zeros(B, jnp.int32),
        temps=jnp.zeros(B, jnp.float32), top_ks=jnp.zeros(B, jnp.int32),
        top_ps=jnp.ones(B, jnp.float32),
    )


def _export_tpu_text(cfg, inp, *, use_pallas, mesh=None):
    """TPU-platform StableHLO of the real ``llama.decode_window`` jit
    (donate_argnames and all), as text."""
    exp = jexport.export(llama.decode_window, platforms=["tpu"])(
        inp["params"], cfg, inp["tokens"], inp["positions"], inp["tables"],
        inp["seq_lens"], inp["seeds"], inp["steps"], inp["temps"],
        inp["top_ks"], inp["top_ps"], inp["k_cache"], inp["v_cache"],
        n_steps=NSTEPS, use_pallas=use_pallas, mesh=mesh,
    )
    return exp.mlir_module()


def _cache_shape_res(*caches):
    # stablehlo type syntax: tensor<2x2x17x16x128xbf16>
    return [
        "x".join(str(d) for d in c.shape) + "x" + ("bf16" if c.dtype == jnp.bfloat16 else str(c.dtype))
        for c in caches
    ]


def _full_cache_scatters(text, shape_res):
    """Scatter ops whose type signature touches a full-cache shape. The
    stablehlo.scatter op prints MULTI-LINE (its update-computation region
    sits between the op name and the trailing type signature), so the
    detector scans a bounded window after each occurrence rather than a
    single line."""
    hits = []
    idx = 0
    while True:
        i = text.find("stablehlo.scatter", idx)
        if i < 0:
            break
        window = text[i : i + 4000]
        if any(s in window for s in shape_res):
            hits.append(window.split("\n", 1)[0][:160])
        idx = i + 1
    return hits


def test_merged_decode_is_scatter_free_on_tpu():
    """The headline path (use_pallas, merged): every per-step cache write
    is one aliased Mosaic custom call; no full-cache scatter survives
    lowering. head_dim=128 matches the engine's kernel gate."""
    cfg = ModelConfig.tiny(dtype="bfloat16", head_dim=128)
    inp = _decode_inputs(cfg)
    text = _export_tpu_text(cfg, inp, use_pallas=True)
    shape_res = _cache_shape_res(inp["k_cache"], inp["v_cache"])

    assert text.count("tpu_custom_call") >= 2, (
        "expected Mosaic kernels (paged attention + cache append) in the "
        "TPU lowering; the Pallas path silently fell back to XLA"
    )
    # the append kernel RMWs both caches in place
    assert text.count("output_operand_alias") >= 2
    scatters = _full_cache_scatters(text, shape_res)
    assert not scatters, (
        "full-cache scatter(s) back in the merged decode path — the "
        f"~0.55GB/step copy regression: {scatters}"
    )
    # donation intent on both caches survives to the exported module
    donors = text.count("jax.buffer_donor") + text.count("tf.aliasing_output")
    assert donors >= 2


def test_merged_decode_sharded_tp_is_scatter_free_on_tpu():
    """Same property under the tp shard_map (kv-head-parallel kernels)."""
    cfg = ModelConfig.tiny(dtype="bfloat16", head_dim=128)
    inp = _decode_inputs(cfg)
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    text = _export_tpu_text(cfg, inp, use_pallas=True, mesh=mesh)
    shape_res = _cache_shape_res(inp["k_cache"], inp["v_cache"])
    assert text.count("tpu_custom_call") >= 2
    assert text.count("output_operand_alias") >= 2
    assert not _full_cache_scatters(text, shape_res)


def test_mla_merged_decode_is_scatter_free_on_tpu():
    """The MLA latent merged path: all layers' latent writes batch into
    one aliased append (kv_lora_rank=128 engages the engine gate)."""
    cfg = ModelConfig.tiny_mla(dtype="bfloat16", kv_lora_rank=128)
    inp = _decode_inputs(cfg)
    text = _export_tpu_text(cfg, inp, use_pallas=True)
    shape_res = _cache_shape_res(inp["k_cache"], inp["v_cache"])
    assert text.count("tpu_custom_call") >= 2
    assert text.count("output_operand_alias") >= 2
    assert not _full_cache_scatters(text, shape_res)


def test_xla_fallback_trips_the_scatter_detector():
    """Negative control: the XLA path DOES contain full-cache scatters
    (that's why the Pallas append exists). If this stops failing the
    detector, the regexes rotted and the positive tests prove nothing."""
    cfg = ModelConfig.tiny(dtype="bfloat16", head_dim=128)
    inp = _decode_inputs(cfg)
    text = _export_tpu_text(cfg, inp, use_pallas=False)
    shape_res = _cache_shape_res(inp["k_cache"], inp["v_cache"])
    assert _full_cache_scatters(text, shape_res), (
        "scatter detector no longer matches the known-scatter XLA path"
    )


def test_cpu_compiled_executable_aliases_both_caches():
    """Donation must survive all the way into the compiled executable's
    buffer assignment: the HloModule header's input_output_alias has to
    map two parameters with exactly the cache shapes. (A donation that
    XLA could not honor is silently dropped — caches would be COPIED
    every window.)"""
    cfg = ModelConfig.tiny(dtype="bfloat16")
    inp = _decode_inputs(cfg)
    compiled = llama.decode_window.lower(
        inp["params"], cfg, inp["tokens"], inp["positions"], inp["tables"],
        inp["seq_lens"], inp["seeds"], inp["steps"], inp["temps"],
        inp["top_ks"], inp["top_ps"], inp["k_cache"], inp["v_cache"],
        n_steps=NSTEPS, use_pallas=False,
    ).compile()
    text = compiled.as_text()
    header = text.splitlines()[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation", header)
    assert m, f"no input_output_alias in compiled module header: {header[:200]}"
    param_idxs = [int(p) for p in re.findall(r"\((\d+), \{\}", m.group(1))]
    assert len(param_idxs) >= 2, f"expected both caches aliased: {m.group(1)}"
    # map the aliased parameter indices back to shapes via the entry params
    shape_of = dict(
        (int(idx), shape)
        for shape, idx in re.findall(
            r"(\S+\[[0-9,]*\])\{[0-9,]*\} parameter\((\d+)\)", text
        )
    )
    cache_shape = "bf16[" + ",".join(str(d) for d in inp["k_cache"].shape) + "]"
    aliased_shapes = [shape_of.get(i) for i in param_idxs]
    assert aliased_shapes.count(cache_shape) >= 2, (
        f"aliased params {param_idxs} have shapes {aliased_shapes}, "
        f"expected two of {cache_shape}"
    )


def test_mixed_step_program_count_bounded():
    """Shape-bucketing guard for the fused mixed prefill+decode step
    (ISSUEs 3 + 9): across every reachable (decode-batch x
    segment-count-bucket x prefill-bucket) dispatch shape, the number
    of distinct XLA programs must equal segment-count buckets x prefill
    buckets — the decode batch is ALWAYS padded to max_batch_size and
    lengths/positions/histories/valids are traced values, so nothing
    else (in particular NOT the live segment-length mixture) may key a
    recompile. A regression here (e.g. an accidentally-static chunk
    length, or per-mixture shapes) multiplies warmup/compile time by
    the request mix and injects 20-40s XLA stalls mid-serving."""
    cfg = ModelConfig.tiny(dtype="float32")
    M = CTX // BLOCK
    MP_MAX = 2
    num_blocks = (B + MP_MAX) * M + 1
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(cfg, num_blocks, BLOCK)
    d_tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    p_tables = jnp.asarray(
        np.arange(B * M + 1, (B + MP_MAX) * M + 1, dtype=np.int32)
        .reshape(MP_MAX, M)
    )
    seg_buckets = (1, 2)
    buckets = (16, 32)
    base = llama.mixed_step._cache_size()
    for MP in seg_buckets:
        for T in buckets:
            # two dispatches per bucket pair with DIFFERENT traced
            # values (active rows, lengths, per-segment fill/history,
            # dead pad segments) — only the bucket pair may recompile
            variants = (
                (11, (0,) * MP, (T - 3,) + (2,) * (MP - 1)),
                (7, (T // 2,) * MP, (2,) + (0,) * (MP - 1)),
            )
            for sl, hists, valids in variants:
                out = llama.mixed_step(
                    params, cfg,
                    jnp.zeros(B, jnp.int32),
                    jnp.full((B,), sl - 1, jnp.int32),
                    d_tables,
                    jnp.full((B,), sl, jnp.int32),
                    jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                    jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
                    jnp.ones(B, jnp.float32),
                    jnp.zeros((MP, T), jnp.int32), p_tables[:MP],
                    jnp.asarray(hists, jnp.int32),
                    jnp.asarray(valids, jnp.int32),
                    k_cache, v_cache,
                    use_pallas=False,
                )
                _, _, k_cache, v_cache = out[:4]
    grown = llama.mixed_step._cache_size() - base
    limit = len(seg_buckets) * len(buckets)
    assert grown == limit, (
        f"mixed_step compiled {grown} programs for {len(seg_buckets)} "
        f"segment-count buckets x {len(buckets)} prefill buckets "
        f"(expected {limit}) — a traced value leaked into the static "
        "shape key"
    )


def test_mixed_step_program_count_bounded_quantized_kv():
    """Quantized-KV twin of the bucketing guard (ISSUE 14): a
    float8_e4m3 cache (the quantized device-KV mode the Pallas gate now
    keeps on the kernel path) must compile exactly the same
    (segment-count x prefill-bucket) program grid as bf16 — per-DTYPE
    programs are expected (different cache types ARE different
    programs), but traced-value variation under a quantized cache must
    never add more."""
    cfg = ModelConfig.tiny(dtype="float32")
    M = CTX // BLOCK
    MP_MAX = 2
    num_blocks = (B + MP_MAX) * M + 1
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(
        cfg, num_blocks, BLOCK, dtype=jnp.float8_e4m3fn
    )
    d_tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    p_tables = jnp.asarray(
        np.arange(B * M + 1, (B + MP_MAX) * M + 1, dtype=np.int32)
        .reshape(MP_MAX, M)
    )
    seg_buckets = (1, 2)
    buckets = (16, 32)
    base = llama.mixed_step._cache_size()
    for MP in seg_buckets:
        for T in buckets:
            variants = (
                (11, (0,) * MP, (T - 3,) + (2,) * (MP - 1)),
                (7, (T // 2,) * MP, (2,) + (0,) * (MP - 1)),
            )
            for sl, hists, valids in variants:
                out = llama.mixed_step(
                    params, cfg,
                    jnp.zeros(B, jnp.int32),
                    jnp.full((B,), sl - 1, jnp.int32),
                    d_tables,
                    jnp.full((B,), sl, jnp.int32),
                    jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                    jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
                    jnp.ones(B, jnp.float32),
                    jnp.zeros((MP, T), jnp.int32), p_tables[:MP],
                    jnp.asarray(hists, jnp.int32),
                    jnp.asarray(valids, jnp.int32),
                    k_cache, v_cache,
                    use_pallas=False,
                )
                _, _, k_cache, v_cache = out[:4]
                assert k_cache.dtype == jnp.float8_e4m3fn
    grown = llama.mixed_step._cache_size() - base
    limit = len(seg_buckets) * len(buckets)
    assert grown == limit, (
        f"quantized-KV mixed_step compiled {grown} programs for "
        f"{len(seg_buckets)} segment-count buckets x {len(buckets)} "
        f"prefill buckets (expected {limit}) — the quantized cache "
        "leaked a traced value into the static shape key"
    )


def test_mixed_step_program_count_bounded_int8_scales_kv():
    """int8-with-scales twin of the bucketing guard (ISSUE 18): the
    int8 device cache threads two [L, N] f32 scale planes through every
    mixed dispatch and returns them grown — the planes are TRACED
    operands, so across the same (segment-count x prefill-bucket) grid
    the program count must stay exactly the bucket grid. A regression
    here (a plane shape or a scale value leaking into the static key)
    multiplies compiles by the page-recycling pattern."""
    cfg = ModelConfig.tiny(dtype="float32")
    M = CTX // BLOCK
    MP_MAX = 2
    num_blocks = (B + MP_MAX) * M + 1
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(
        cfg, num_blocks, BLOCK, dtype=jnp.int8
    )
    k_scales = jnp.full((cfg.num_layers, num_blocks), 1e-12, jnp.float32)
    v_scales = k_scales
    d_tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    p_tables = jnp.asarray(
        np.arange(B * M + 1, (B + MP_MAX) * M + 1, dtype=np.int32)
        .reshape(MP_MAX, M)
    )
    seg_buckets = (1, 2)
    buckets = (16, 32)
    base = llama.mixed_step._cache_size()
    for MP in seg_buckets:
        for T in buckets:
            variants = (
                (11, (0,) * MP, (T - 3,) + (2,) * (MP - 1)),
                (7, (T // 2,) * MP, (2,) + (0,) * (MP - 1)),
            )
            for sl, hists, valids in variants:
                out = llama.mixed_step(
                    params, cfg,
                    jnp.zeros(B, jnp.int32),
                    jnp.full((B,), sl - 1, jnp.int32),
                    d_tables,
                    jnp.full((B,), sl, jnp.int32),
                    jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
                    jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
                    jnp.ones(B, jnp.float32),
                    jnp.zeros((MP, T), jnp.int32), p_tables[:MP],
                    jnp.asarray(hists, jnp.int32),
                    jnp.asarray(valids, jnp.int32),
                    k_cache, v_cache,
                    use_pallas=False,
                    k_scales=k_scales, v_scales=v_scales,
                )
                _, _, k_cache, v_cache, k_scales, v_scales, _ = out[:7]
                assert k_cache.dtype == jnp.int8
                assert k_scales.dtype == jnp.float32
    grown = llama.mixed_step._cache_size() - base
    limit = len(seg_buckets) * len(buckets)
    assert grown == limit, (
        f"int8+scales mixed_step compiled {grown} programs for "
        f"{len(seg_buckets)} segment-count buckets x {len(buckets)} "
        f"prefill buckets (expected {limit}) — the scale planes leaked "
        "a traced value into the static shape key"
    )


def test_mixed_step_tpu_lowering_uses_ragged_kernel_quantized_kv():
    """The quantized-cache TPU path must still lower the ragged Mosaic
    kernel — engine/engine.py's capability gate now keeps fp8 caches on
    the Pallas path, and this pins that the lowering actually holds
    (the in-kernel `.astype(f32)` page cast is the fused dequant)."""
    cfg = ModelConfig.tiny(dtype="bfloat16", head_dim=128)
    M = CTX // BLOCK
    MP = 2
    num_blocks = (B + MP) * M + 1
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(
        cfg, num_blocks, BLOCK, dtype=jnp.float8_e4m3fn
    )
    d_tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    p_tables = jnp.asarray(
        np.arange(B * M + 1, (B + MP) * M + 1, dtype=np.int32)
        .reshape(MP, M)
    )
    T = 32
    exp = jexport.export(llama.mixed_step, platforms=["tpu"])(
        params, cfg,
        jnp.zeros(B, jnp.int32), jnp.full((B,), 10, jnp.int32), d_tables,
        jnp.full((B,), 11, jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
        jnp.ones(B, jnp.float32),
        jnp.zeros((MP, T), jnp.int32), p_tables,
        jnp.zeros(MP, jnp.int32), jnp.full((MP,), T, jnp.int32),
        k_cache, v_cache, use_pallas=True,
    )
    text = exp.mlir_module()
    assert text.count("tpu_custom_call") >= 1, (
        "no Mosaic kernel in the quantized-KV mixed step's TPU "
        "lowering — the fp8 cache silently fell back to XLA"
    )


def test_mixed_step_tpu_lowering_uses_ragged_kernel():
    """The fused step's TPU path must actually lower the ragged
    mixed-attention Mosaic kernel (head_dim=128 matches the engine's
    kernel gate) — a silent fall-through to the XLA pair would ship the
    fusion's scheduling without its single-kernel attention."""
    cfg = ModelConfig.tiny(dtype="bfloat16", head_dim=128)
    M = CTX // BLOCK
    MP = 2  # a multi-segment pack must still lower the ONE ragged kernel
    num_blocks = (B + MP) * M + 1
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(cfg, num_blocks, BLOCK)
    d_tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    p_tables = jnp.asarray(
        np.arange(B * M + 1, (B + MP) * M + 1, dtype=np.int32)
        .reshape(MP, M)
    )
    T = 32
    exp = jexport.export(llama.mixed_step, platforms=["tpu"])(
        params, cfg,
        jnp.zeros(B, jnp.int32), jnp.full((B,), 10, jnp.int32), d_tables,
        jnp.full((B,), 11, jnp.int32),
        jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
        jnp.zeros(B, jnp.float32), jnp.zeros(B, jnp.int32),
        jnp.ones(B, jnp.float32),
        jnp.zeros((MP, T), jnp.int32), p_tables,
        jnp.zeros(MP, jnp.int32), jnp.full((MP,), T, jnp.int32),
        k_cache, v_cache, use_pallas=True,
    )
    text = exp.mlir_module()
    assert text.count("tpu_custom_call") >= 1, (
        "no Mosaic kernel in the mixed step's TPU lowering — the ragged "
        "paged-attention path silently fell back to XLA"
    )
    # donation intent on both caches survives to the exported module
    donors = text.count("jax.buffer_donor") + text.count("tf.aliasing_output")
    assert donors >= 2


def test_pp_decode_moves_activations_not_weights():
    """Locks the measured pp-decode structure (docs/performance.md,
    VERDICT r3 #8): on a pp mesh the compiled decode window must move
    ACTIVATIONS through collective-permutes and all-gather ZERO bytes of
    stage weights — a regression to weight gathering would put the whole
    stage's parameter volume on every decode step's critical path."""
    cfg = ModelConfig.tiny(dtype="float32", num_layers=4)
    inp = _decode_inputs(cfg)
    from dynamo_tpu.parallel.mesh import (
        MeshConfig, cache_sharding, make_mesh, shard_params,
    )

    mesh = make_mesh(MeshConfig(pp=2))
    params = shard_params(inp["params"], mesh)
    cs = cache_sharding(mesh, cfg)
    k_cache = jax.device_put(inp["k_cache"], cs)
    v_cache = jax.device_put(inp["v_cache"], cs)
    compiled = llama.decode_window.lower(
        params, cfg, inp["tokens"], inp["positions"], inp["tables"],
        inp["seq_lens"], inp["seeds"], inp["steps"], inp["temps"],
        inp["top_ks"], inp["top_ps"], k_cache, v_cache,
        n_steps=NSTEPS, use_pallas=False, mesh=mesh,
    ).compile()
    text = compiled.as_text()
    assert "collective-permute" in text, (
        "pp decode no longer pipelines activations through "
        "collective-permute — partitioning regressed"
    )
    # weight all-gathers: any all-gather whose result is a 2D+ f32
    # tensor with >= 64*64 elements would be a stage-weight gather (the
    # activation permutes are [B, E] = tiny)
    big_ag = []
    for m in re.finditer(r"= f32\[([0-9,]+)\][^\n]*? all-gather", text):
        dims = [int(d) for d in m.group(1).split(",") if d]
        if np.prod(dims) >= 64 * 64:
            big_ag.append(m.group(0)[:120])
    assert not big_ag, f"stage-weight all-gathers appeared: {big_ag}"


def test_streamed_handoff_program_count_bounded(run):
    """Shape-bucketing guard for the streamed disagg handoff (ISSUE 6):
    the incremental extract's per-segment gathers and the decode side's
    per-segment scatters must compile one program per SEGMENT-GEOMETRY
    BUCKET (``_pad_idxs`` power-of-two bucketing), never per request
    shape — an accidental per-request key would inject an XLA compile
    into every streamed segment of every new prompt length."""
    import asyncio

    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.engine.offload import _gather_blocks, _pad_idxs, _scatter_blocks
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest,
        SamplingOptions,
        StopConditions,
    )
    from dynamo_tpu.runtime import Context

    cfg = ModelConfig.tiny(dtype="float32")

    def eng():
        return JaxEngine(
            EngineConfig(
                model=cfg, num_blocks=64, block_size=4, max_batch_size=4,
                max_context=128, prefill_chunk=8,
            ),
            seed=0,
        )

    def req(toks):
        return PreprocessedRequest(
            token_ids=list(toks),
            stop_conditions=StopConditions(max_tokens=2, ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0, seed=0),
            eos_token_ids=[],
        )

    prefill, decode = eng(), eng()

    async def main():
        # prompts of DIFFERENT lengths whose chunking lands on the same
        # segment bucket (prefill_chunk 8 / block 4 -> 2-block segments)
        cases = [
            (list(range(10, 34)), 0),   # 24 tokens, per-chunk segments
            (list(range(50, 90)), 0),   # 40 tokens, same 2-block bucket
            (list(range(200, 224)), 1), # segment_blocks=1 -> new bucket
        ]
        g0, s0 = _gather_blocks._cache_size(), _scatter_blocks._cache_size()
        seen_buckets = set()
        for i, (toks, seg_blocks) in enumerate(cases):
            segs = []

            async def on_segment(b0, k, v, _segs=segs):
                _segs.append((b0, np.asarray(k), np.asarray(v)))

            await prefill.prefill_extract_stream(
                req(toks), None, segment_blocks=seg_blocks,
                on_segment=on_segment,
            )
            handle = decode.begin_remote(Context(req(toks)))
            assert handle is not None
            for b0, k, v in segs:
                seen_buckets.add(len(_pad_idxs(list(range(k.shape[2])))))
                await decode.scatter_remote_segment(handle, b0, k, v)
            decode.abort_remote(handle, "test teardown")
        g_grown = _gather_blocks._cache_size() - g0
        s_grown = _scatter_blocks._cache_size() - s0
        assert g_grown <= len(seen_buckets), (
            f"extract gathers compiled {g_grown} programs for "
            f"{len(seen_buckets)} segment buckets {sorted(seen_buckets)}"
        )
        assert s_grown <= len(seen_buckets), (
            f"segment scatters compiled {s_grown} programs for "
            f"{len(seen_buckets)} segment buckets {sorted(seen_buckets)}"
        )
        await prefill.close()
        await decode.close()

    run(main())


def test_adapter_program_count_keys_on_buckets_not_census(run):
    """Multi-LoRA bucketing guard (ISSUE 19): the adapter device stack's
    ``[L, NA, ..., rb]`` shapes are the registry's (count, rank)
    BUCKETS — zero-padded, bitwise exact — so staging, evicting and
    re-staging adapters, and dispatching ANY per-row adapter-id mixture,
    must compile exactly ONE prefill program for a fixed chunk bucket.
    A program count that scales with the live adapter census would
    inject an XLA compile into every LRU slot churn. The engine's
    dispatch key mirrors this: adapter fleets append one static
    ``("lora", count_bucket, rank_bucket)`` suffix; no-adapter engines
    append NOTHING (their key tuples — and therefore their compiled
    programs — stay byte-identical to pre-multi-model builds)."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.engine.adapters import AdapterRegistry

    cfg = ModelConfig.tiny(dtype="float32")
    reg = AdapterRegistry(("alice:4", "bob:8:7", "carol:2:3"), cfg)
    # 3 live slots -> count bucket 4; ranks {4, 8, 2} -> rank bucket 8
    assert (reg.count_bucket, reg.rank_bucket) == (4, 8)

    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(cfg, 8, BLOCK)
    tables = jnp.asarray(np.arange(1, 5, dtype=np.int32))
    T = 16
    base = llama.prefill._cache_size()
    shapes0 = jax.tree.map(lambda a: a.shape, reg.device_stack())
    # every registry state x adapter-id mixture the LRU can produce:
    # cold stack, each staging, an eviction, a re-stage into the freed
    # slot — with base (-1) and adapter rows dispatched against each
    states = (
        lambda: None,
        lambda: reg.stage("alice"),
        lambda: reg.stage("bob"),
        lambda: reg.evict("alice"),
        lambda: reg.stage("carol"),
        lambda: reg.stage("alice"),
    )
    for mutate in states:
        mutate()
        assert jax.tree.map(lambda a: a.shape, reg.device_stack()) == shapes0
        for aid in (-1, 0, 2):
            _, k_cache, v_cache = llama.prefill(
                params, cfg, jnp.zeros(T, jnp.int32), tables,
                jnp.int32(0), jnp.int32(T - 3), k_cache, v_cache,
                lora=reg.device_stack(), adapter_id=jnp.int32(aid),
            )
    grown = llama.prefill._cache_size() - base
    assert grown == 1, (
        f"adapter prefill compiled {grown} programs across "
        f"{len(states)} registry states x 3 id mixtures (expected 1) — "
        "the live adapter census leaked into the static shape key"
    )

    async def engines():
        lora_eng = JaxEngine(
            EngineConfig(
                model=cfg, num_blocks=32, block_size=BLOCK,
                max_batch_size=2, max_context=128,
                adapters=("alice:4", "bob:8:7", "carol:2:3"),
                served_model_name="base",
            ),
            seed=0,
        )
        plain_eng = JaxEngine(
            EngineConfig(
                model=cfg, num_blocks=32, block_size=BLOCK,
                max_batch_size=2, max_context=128,
            ),
            seed=0,
        )
        assert lora_eng._lora_key() == (("lora", 4, 8),)
        assert plain_eng._lora_key() == ()
        await lora_eng.close()
        await plain_eng.close()

    run(engines())


def test_ici_mover_program_count_bounded(run):
    """Shape-bucketing guard for the ICI same-slice handoff (ISSUE 11):
    the decode sink's per-segment device→device mover must compile one
    program per SEGMENT-GEOMETRY BUCKET (the same ``_pad_idxs``
    power-of-two bucketing as the streamed scatter), never per segment
    size — an accidental per-shape key would inject an XLA compile into
    every segment of every new prompt length."""
    from dynamo_tpu.disagg.ici import IciSegmentMover
    from dynamo_tpu.engine.offload import _pad_idxs

    def main():
        import jax.numpy as jnp

        mover = IciSegmentMover(None, None)
        seen_buckets = set()
        # segment sizes across two buckets (1,2 -> 2; 3,4 -> 4) in a
        # fixed [L=2, H=2, n, bs=4, D=8] geometry — also odd/partial
        # tails, which the mover pads to the bucket before the compiled
        # move and slices back after
        for n in (1, 2, 3, 4, 2, 3, 1, 4):
            k = jnp.arange(2 * 2 * n * 4 * 8, dtype=jnp.float32).reshape(
                2, 2, n, 4, 8
            )
            v = k + 1
            seen_buckets.add(len(_pad_idxs(list(range(n)))))
            mk, mv = mover.move(k, v)
            assert mk.shape == k.shape and mv.shape == v.shape
            assert jnp.array_equal(mk, k) and jnp.array_equal(mv, v)
        assert mover.segments_moved == 8
        # k and v compile separately (MLA-asymmetric shapes), so the
        # bound is 2 programs per bucket
        assert mover.programs() <= 2 * len(seen_buckets), (
            f"ici mover compiled {mover.programs()} programs for "
            f"{len(seen_buckets)} segment buckets {sorted(seen_buckets)}"
        )
        # the matched-geometry (single-device) case took the explicit
        # shard_map path, not the generic reshard
        assert mover.permute_programs == mover.programs()
        assert mover.reshard_programs == 0

    async def amain():
        main()

    run(amain())
