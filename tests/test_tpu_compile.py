"""AOT-compile the serving path's Pallas kernels for a described v5e.

No chip is attached: ``jax.experimental.topologies`` describes a
``v5e:2x2`` slice and the TPU compiler (Mosaic included) compiles for
it, so whatever the chip's compiler would refuse — a slice not aligned
to the tiling, a block shape off the (8, 128) floor, too much VMEM —
fails HERE instead of on the first chip dispatch. Interpret mode cannot
see any of that. A compile that passes is not a chip run: numerics on
the chip are ``scripts/validate_tpu_kernels.py`` (chip_smoke phase a).

The topology is described inside a module-scoped fixture (never at
import/collection time: only one process may hold libtpu, and every
xdist worker imports every test file), the compiles run in the test's
own process, and the persistent compile cache is off around them (an
AOT entry cannot be read back without a chip and would only warn).
Keep every such test in THIS file — a second file could land on another
worker, where the fixture would skip it in silence.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# serving widths: B decode slots, H query / Hkv kv heads of D, N pages of
# bs rows, M-wide block tables, L layers (ISSUE 23's compile rehearsal)
B, H, HKV, D, N, BS, M, L = 16, 32, 8, 128, 2048, 16, 128, 16
T_CHUNK, MP = 512, 2  # prefill chunk rows, mixed-step prefill segments
C_MLA, R_MLA = 512, 64  # DeepSeek latent / rope widths
RL_MLA = 128  # a rope row in the pool: 64 in 128 lanes (llama.rope_lanes)
SCALE = D**-0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """shape -> ShapeDtypeStruct placed on the described chip 0."""
    one = SingleDeviceSharding(topo.devices[0])

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return spec


def _compile(fn, *args):
    """Lower + compile for the described chip; Mosaic errors propagate."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        "no Mosaic kernel in the compiled program"
    )


def _scale_planes(chip, scales):
    """The int8 lane's per-page scale planes as extra positional args."""
    return (chip((N,), jnp.float32),) * 2 if scales else ()


def _cache(chip, dtype, d=D, hkv=HKV, layers=L):
    return chip((layers, hkv, N, BS, d), dtype)


# ---------------- cache appends ----------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float8_e4m3fn],
                         ids=["bf16", "fp8"])
def test_kv_cache_append(chip, dtype):
    from dynamo_tpu.ops.kv_cache_update_pallas import kv_cache_append

    new = chip((L, B, HKV, D), jnp.bfloat16)
    idx = chip((B,), jnp.int32)
    _compile(kv_cache_append, new, new, _cache(chip, dtype),
             _cache(chip, dtype), idx, idx)


def test_kv_cache_append_mla_latent(chip):
    """MLA stores c_kv [C] in the k slot and k_pe in the v slot: one kv
    "head", two different trailing dims (the rope row in whole lanes)."""
    from dynamo_tpu.ops.kv_cache_update_pallas import kv_cache_append

    idx = chip((B,), jnp.int32)
    _compile(
        kv_cache_append,
        chip((L, B, 1, C_MLA), jnp.bfloat16),
        chip((L, B, 1, RL_MLA), jnp.bfloat16),
        _cache(chip, jnp.bfloat16, d=C_MLA, hkv=1),
        _cache(chip, jnp.bfloat16, d=RL_MLA, hkv=1),
        idx, idx,
    )


def test_kv_cache_append_quantized(chip):
    from dynamo_tpu.ops.kv_cache_update_pallas import (
        kv_cache_append_quantized,
    )

    new = chip((L, B, HKV, D), jnp.bfloat16)
    plane = chip((L, N), jnp.float32)
    idx = chip((B,), jnp.int32)
    _compile(kv_cache_append_quantized, new, new, _cache(chip, jnp.int8),
             _cache(chip, jnp.int8), plane, plane, idx, idx)


@pytest.mark.parametrize("t", [2, 5], ids=["T2", "T5"])
def test_kv_cache_append_tokens(chip, t):
    from dynamo_tpu.ops.kv_cache_update_pallas import kv_cache_append_tokens

    new = chip((L, B, t, HKV, D), jnp.bfloat16)
    idx = chip((B, t), jnp.int32)
    _compile(kv_cache_append_tokens, new, new, _cache(chip, jnp.bfloat16),
             _cache(chip, jnp.bfloat16), idx, idx)


# ---------------- decode / prefill / mixed attention ----------------


def _layer(chip, dtype, d=D):
    return chip((HKV, N, BS, d), dtype)


# decode slots, query / kv heads, table width of the benchmark's models
# (each with bf16 pages, stats on: the merged path a decode step runs)
CELL_OLMO2_1B = (32, 16, 16, 256)  # olmo2-1b.chat: MHA, every head a step
CELL_PHI4_MINI = (32, 24, 8, 256)  # GQA 24 / 8
TP4_SHARD = (B, 4, 2, M)  # Qwen3-1.7B's 16 / 8 heads over --tp 4


@pytest.mark.parametrize(
    "d,dtype,scales,stats,shape",
    [
        (128, jnp.bfloat16, False, False, None),
        (128, jnp.bfloat16, False, True, None),  # the merged decode path
        # gpt-oss head_dim, in the 128 lanes its q and cache ride in
        (64, jnp.bfloat16, False, True, None),
        (128, jnp.int8, True, True, None),  # --kv-cache-dtype int8
        # --kv-cache-dtype float8_e4m3
        (128, jnp.float8_e4m3fn, False, True, None),
        (128, jnp.bfloat16, False, True, CELL_OLMO2_1B),
        (128, jnp.bfloat16, False, True, CELL_PHI4_MINI),
        # 32 KV heads: bf16 pages take two head tiles of 16, int8 pages
        # one of 32 (the largest step the budget admits)
        (128, jnp.bfloat16, False, True, (B, 32, 32, M)),
        (128, jnp.int8, True, True, (B, 32, 32, M)),
        (128, jnp.bfloat16, False, True, TP4_SHARD),
    ],
    ids=["D128", "D128-stats", "D64-stats", "int8-scales", "fp8",
         "olmo2-1b-cell", "phi-4-mini", "mha32-two-tiles", "mha32-int8",
         "tp4-shard"],
)
def test_paged_decode_attention(chip, d, dtype, scales, stats, shape):
    """The kernel's operand is the whole ``[L, Hkv, N, bs, D]`` cache and
    a layer index that is a VALUE of the program (scalar prefetch), the
    form every decode step hands it. The kernel fetches its pages with
    its own DMAs, and Mosaic cuts a page out of an HBM ref only along
    whole 128-lane tiles ("Slice shape along dimension 4 must be aligned
    to tiling (128), but is 64"): a head of 64 comes in rows of
    ``llama.kv_lanes`` = 128 lanes, q and cache alike, and keeps its own
    scale."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops.paged_attention_pallas import paged_decode_attention

    b, h, hkv, m = shape or (B, H, HKV, M)
    lanes = llama.kv_lanes(ModelConfig.tiny(head_dim=d))

    def fn(q, kc, vc, layer, bt, sl, ks=None, vs=None):
        return paged_decode_attention(
            q, kc, vc, layer, bt, sl, d**-0.5, return_stats=stats,
            k_scales=ks, v_scales=vs,
        )

    cache = _cache(chip, dtype, d=lanes, hkv=hkv)
    _compile(fn, chip((b, h, lanes), jnp.bfloat16), cache, cache,
             chip((), jnp.int32), chip((b, m), jnp.int32),
             chip((b,), jnp.int32), *_scale_planes(chip, scales))


def _pool_sized(text, *pools):
    """``scripts/aot_step_programs.py``'s reader of an optimized HLO
    text: opcode -> instructions whose result is as large as a pool or a
    layer's slab of it."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "aot_step_programs", os.path.join(
            os.path.dirname(__file__), "..", "scripts",
            "aot_step_programs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod._pool_sized(text, *pools)


@pytest.mark.parametrize(
    "experts,head", [(0, 128), (8, 128), (0, 64), (0, 0)],
    ids=["dense", "experts", "head64-sinks-windows", "mla"])
def test_decode_window_keeps_no_copy_of_the_pool(chip, experts, head):
    """An unrolled ``decode_window`` on the Pallas path reads its KV where
    it lies: the program's temporaries stay far under its pool. With a
    ``k_cache[l]`` operand of the attention kernel the TPU compiler
    copies every layer's slab out first: at the benchmark's sizes the
    temporaries were the pool and more (PERF.md section 6, PR 29). A
    head of 64 with gpt-oss's sinks and alternating windows compiles the
    same kernels: its cache and its q, k and v come 128 lanes wide
    (``llama.kv_lanes``), the form the kernel's own DMAs can slice.
    ``mla``: two latent layers (a cut is no bitcast) at DeepSeek's
    widths. Both pools are parameters in the custom calls' row-major
    form (a rope pool of 64 lanes came pages-minor and was re-laid at
    entry and exit), and nothing copies or stages either: the pools are
    small enough here for the compiler's fast memory, which is where it
    staged the rope pool three times a step (PERF.md section 6, PR 53)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    if head:
        cfg = ModelConfig(
            vocab_size=2048, hidden_size=512, intermediate_size=1024,
            num_layers=4, num_heads=4, num_kv_heads=4, head_dim=head,
            num_experts=experts, num_experts_per_tok=2,
            moe_intermediate_size=256 if experts else 0,
            attn_sinks=head == 64,
            layer_windows=(128, 0, 128, 0) if head == 64 else (),
        )
    else:
        cfg = ModelConfig(
            vocab_size=2048, hidden_size=512, intermediate_size=1024,
            num_layers=2, num_heads=16, num_kv_heads=16, q_lora_rank=256,
            kv_lora_rank=C_MLA, qk_nope_head_dim=128,
            qk_rope_head_dim=R_MLA, v_head_dim=128,
        )
    b, m, n = 8, 32, 1024
    params = jax.tree.map(
        lambda a: chip(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0))),
    )
    pools = llama.kv_cache_shapes(cfg, n, BS)
    caches = [chip(pool, jnp.bfloat16) for pool in pools]
    ints, floats = chip((b,), jnp.int32), chip((b,), jnp.float32)
    compiled = llama.decode_window.lower(
        params, cfg, ints, ints, chip((b, m), jnp.int32), ints, ints, ints,
        floats, ints, floats, *caches, n_steps=2, use_pallas=True,
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # under a quarter of the pool, and sharper: under half of ONE layer's
    # K slab, since a compiler that reuses one buffer for the slabs of
    # this small program would still pass the quarter (the sliced form
    # measured 30.0 MB here, this form 2.5-3.0 MB, a slab is 16.8 MB)
    slab = pools[0][1] * n * BS * pools[0][-1] * 2
    assert compiled.memory_analysis().temp_size_in_bytes < slab // 2
    if not cfg.is_mla:
        return
    assert pools[1][-1] == RL_MLA
    copied = _pool_sized(text, *pools).keys() & {
        "copy", "copy-start", "transpose", "slice", "dynamic-slice",
        "dynamic-update-slice"}
    assert not copied, f"pool- or slab-sized {sorted(copied)}"
    for name, pool in zip(("k_cache", "v_cache"), pools):
        dims = ",".join(map(str, pool))
        laid = re.findall(
            rf"%{name}\S* = bf16\[{dims}\]\{{([\d,]+)[:}}][^\n]* parameter\(",
            text)
        assert laid == ["4,3,2,1,0"], f"{name} is laid out as {laid}"


@pytest.mark.parametrize("tp", [0, 4], ids=["one-chip", "tp4-shard"])
@pytest.mark.parametrize("program", ["decode_window", "mixed_step"])
def test_a_step_program_returns_its_rows_as_it_took_them(
        topo, chip, program, tp):
    """The resident step state (PR 48: ``engine/step_state.py``) is a
    program's donated input and its last output, and the next dispatch's
    input again: the compiled program gives the matrix back with the
    shape, dtype and sharding it took (replicated under a ``tp`` mesh,
    where the output is pinned), or the second dispatch of every program
    would compile anew; and the donation is taken (an alias, no copy)."""
    from dynamo_tpu.engine.step_state import DELTA_CELLS
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel import mesh as pm

    cfg = ModelConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024,
        num_layers=2, num_heads=4, num_kv_heads=4, head_dim=128)
    b, m, n, t = 8, 32, 1024 * max(tp, 1), 64
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    pool = llama.kv_cache_shapes(cfg, n, BS)[0]
    mesh = None
    if tp:
        from jax.sharding import NamedSharding

        mesh = pm.make_mesh(pm.MeshConfig(tp=tp), devices=topo.devices)
        params = jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
            shapes, pm.spec_tree(shapes, mesh=mesh))
        cache = jax.ShapeDtypeStruct(
            pool, jnp.bfloat16, sharding=pm.cache_sharding(mesh, cfg))
        rep = pm.replicated(mesh)

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
    else:
        params = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes)
        cache, arr = chip(pool, jnp.bfloat16), chip
    rows = arr((b, llama.ROW_TABLES + m), jnp.int32)
    one = arr((1,), jnp.int32)
    segs = (arr((1, t), jnp.int32), arr((1, m), jnp.int32), one, one)
    lowered = getattr(llama, program).lower(
        params, cfg, *llama.ROWS_RESIDENT,
        *(segs if program == "mixed_step" else ()), cache, cache,
        use_pallas=True, mesh=mesh, rows=rows,
        rows_delta=arr((DELTA_CELLS, 3), jnp.int32))
    compiled = lowered.compile()
    back = jax.tree.leaves(lowered.out_info)[-1]
    assert (back.shape, back.dtype) == (rows.shape, rows.dtype)
    out_sharding = jax.tree.leaves(compiled.output_shardings)[-1]
    assert out_sharding.is_equivalent_to(rows.sharding, 2)
    # the rows' buffer is one of the aliased (donated and reused) inputs
    aliases = compiled.as_text().split("input_output_alias=")[1].split(
        "entry_computation_layout")[0]
    assert aliases.count("-alias") == 3, "the caches' and the rows'"


@pytest.mark.parametrize(
    "experts,head,tp", [(0, 128, 0), (8, 128, 0), (0, 64, 0), (0, 128, 4)],
    ids=["dense", "experts", "head64-sinks-windows", "tp4-shard"])
def test_mixed_step_keeps_no_copy_of_the_pool(topo, chip, experts, head, tp):
    """The fused ``mixed_step`` on the Pallas path lands its K and V rows
    in place in the donated pool and both of its kernels read the pool
    where it lies (the model and the bound of the decode window's guard
    above: temporaries under half of ONE layer's K slab). With a slab
    cut out in front of the kernels, scattered into and written back,
    the temporaries were K's and V's slabs and their re-laid copies: the
    sliced form measured 474-477 MB here, this form 3.7-4.5 MB, a slab
    is 67.1 MB (my AOT compiles, PR 41; at the benchmark's sizes
    2.25-7.69 GiB against 0.06-0.08 GiB: PERF.md section 4). The pool is
    four times the decode guard's: at 1,024 pages the compiler ran the
    sliced form in 3.8-6.8 MB too and the guard guarded nothing.
    ``tp4-shard``: the write and the kernels under shard_map over four
    chips, a device's temporaries against its own quarter of a slab
    (sliced 205 MB, this form 1.4 MB, a quarter 67.1 MB)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.parallel import mesh as pm

    cfg = ModelConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024,
        num_layers=4, num_heads=4, num_kv_heads=4, head_dim=head,
        num_experts=experts, num_experts_per_tok=2,
        moe_intermediate_size=256 if experts else 0,
        attn_sinks=head == 64,
        layer_windows=(128, 0, 128, 0) if head == 64 else (),
    )
    b, m, n, t = 8, 32, 4096 * max(tp, 1), 64
    shapes = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    pool = llama.kv_cache_shapes(cfg, n, BS)[0]
    mesh = None
    if tp:
        from jax.sharding import NamedSharding

        mesh = pm.make_mesh(pm.MeshConfig(tp=tp), devices=topo.devices)
        params = jax.tree.map(
            lambda a, spec: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, spec)),
            shapes, pm.spec_tree(shapes, mesh=mesh))
        cache = jax.ShapeDtypeStruct(
            pool, jnp.bfloat16, sharding=pm.cache_sharding(mesh, cfg))
        rep = pm.replicated(mesh)

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)
    else:
        params = jax.tree.map(lambda a: chip(a.shape, a.dtype), shapes)
        cache, arr = chip(pool, jnp.bfloat16), chip
    ints, floats = arr((b,), jnp.int32), arr((b,), jnp.float32)
    one = arr((1,), jnp.int32)
    compiled = llama.mixed_step.lower(
        params, cfg, ints, ints, arr((b, m), jnp.int32), ints, ints, ints,
        floats, ints, floats, arr((1, t), jnp.int32),
        arr((1, m), jnp.int32), one, one, cache, cache, use_pallas=True,
        mesh=mesh,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    slab = cfg.num_kv_heads * n * BS * llama.kv_lanes(cfg) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < (
        slab // max(tp, 1) // 2)


def test_lfm2_step_programs_keep_no_copy_of_the_state(chip):
    """An LFM2 stack (conv and attention operators by layer, a head of
    64, biased sigmoid experts behind a dense layer) compiles its three
    step programs with the kernels on, the KV cache indexed by attention
    ordinal, and writes the conv state and its per-block snapshots where
    they lie: a decode window's temporaries stay far under the snapshot
    pool (the scatter rides the scan's carry in place)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cfg = ModelConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024,
        num_layers=4, num_heads=8, num_kv_heads=2, head_dim=64,
        layer_ops=("conv", "conv", "attn", "conv"), conv_kernel=3,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=256,
        first_dense_layers=1, moe_scoring="sigmoid", moe_gate_bias=True,
        qk_norm=True, tie_word_embeddings=True, topk_norm_eps=1e-6,
    )
    b, m, n, t = 8, 32, 4096, 64
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: chip(a.shape, a.dtype), tree)
    params = shaped(jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    state = shaped(jax.eval_shape(lambda: llama.init_state(cfg, b, n)))
    cache = chip(llama.kv_cache_shapes(cfg, n, BS)[0], jnp.bfloat16)
    assert cache.shape[0] == 1  # the attention layer only
    ints, floats = chip((b,), jnp.int32), chip((b,), jnp.float32)
    batch = (ints, ints, chip((b, m), jnp.int32), ints, ints, ints,
             floats, ints, floats)
    window = llama.decode_window.lower(
        params, cfg, *batch, cache, cache, n_steps=2, use_pallas=True,
        moe_counters=True, state=state).compile()
    assert "tpu_custom_call" in window.as_text()
    snap = state["snap"].size * 2
    assert window.memory_analysis().temp_size_in_bytes < snap // 4
    one = chip((1,), jnp.int32)
    mixed = llama.mixed_step.lower(
        params, cfg, *batch, chip((1, t), jnp.int32),
        chip((1, m), jnp.int32), one, one, cache, cache, use_pallas=True,
        moe_counters=True, state=state, p_slots=one).compile()
    assert "tpu_custom_call" in mixed.as_text()
    scalar = chip((), jnp.int32)
    llama.prefill.lower(
        params, cfg, chip((t,), jnp.int32), chip((m,), jnp.int32), scalar,
        scalar, cache, cache, use_pallas=True, moe_counters=True,
        state=state, slot=scalar).compile()


@pytest.mark.parametrize("dtype,scales", [(jnp.bfloat16, False),
                                          (jnp.int8, True)],
                         ids=["bf16", "int8-scales"])
def test_paged_prefill_attention(chip, dtype, scales):
    from dynamo_tpu.ops.paged_attention_pallas import paged_prefill_attention

    def fn(q, kc, vc, bt, hist, ks=None, vs=None):
        return paged_prefill_attention(
            q, kc, vc, bt, hist, SCALE, k_scales=ks, v_scales=vs
        )

    _compile(fn, chip((T_CHUNK, H, D), jnp.bfloat16), _layer(chip, dtype),
             _layer(chip, dtype), chip((M,), jnp.int32),
             chip((), jnp.int32), *_scale_planes(chip, scales))


@pytest.mark.parametrize(
    "dtype,scales,shape",
    [(jnp.bfloat16, False, None), (jnp.int8, True, None),
     (jnp.bfloat16, False, TP4_SHARD)],
    ids=["bf16", "int8-scales", "tp4-shard"])
def test_ragged_mixed_attention(chip, dtype, scales, shape):
    """One operand form: the whole ``[L, Hkv, N, bs, D]`` cache and a
    layer index that is a VALUE of the program, for the segments' grid
    and the decode rows' kernel alike (``tp4-shard``: what one device of
    ``ragged_mixed_attention_sharded`` compiles)."""
    from dynamo_tpu.ops.ragged_paged_attention_pallas import (
        ragged_mixed_attention,
    )

    b, h, hkv, m = shape or (B, H, HKV, M)

    def fn(qd, qc, kc, vc, layer, dt, dl, pt, ph, pv, ks=None, vs=None):
        return ragged_mixed_attention(
            qd, qc, kc, vc, layer, dt, dl, pt, ph, pv, SCALE,
            k_scales=ks, v_scales=vs,
        )

    cache = _cache(chip, dtype, hkv=hkv)
    _compile(
        fn, chip((b, h, D), jnp.bfloat16),
        chip((MP, T_CHUNK, h, D), jnp.bfloat16), cache, cache,
        chip((), jnp.int32), chip((b, m), jnp.int32), chip((b,), jnp.int32),
        chip((MP, m), jnp.int32), chip((MP,), jnp.int32),
        chip((MP,), jnp.int32), *_scale_planes(chip, scales),
    )


# ---------------- MLA latent kernels ----------------


@pytest.mark.parametrize(
    "b,h,m,layers,stats",
    [(B, H, M, L, True), (32, 64, 256, 1, True), (32, 64, 256, 1, False)],
    ids=["deepseek-16-layers", "gigachat35-cell", "gigachat35-cell-plain"])
def test_mla_paged_decode_attention(chip, b, h, m, layers, stats):
    """The whole caches and a traced layer, the kernel's own page DMAs
    out of HBM (a rope row of 64 in 128 lanes: Mosaic cuts whole lane
    tiles only); ``gigachat35-cell``: the benchmark cell's widths, 32
    slots of 64 heads over a table of 256."""
    from dynamo_tpu.ops.mla_attention_pallas import mla_paged_decode_attention

    def fn(qe, qp, cc, pc, layer, bt, sl):
        return mla_paged_decode_attention(
            qe, qp, cc, pc, layer, bt, sl, (C_MLA + R_MLA) ** -0.5,
            return_stats=stats,
        )

    _compile(fn, chip((b, h, C_MLA), jnp.bfloat16),
             chip((b, h, R_MLA), jnp.bfloat16),
             _cache(chip, jnp.bfloat16, d=C_MLA, hkv=1, layers=layers),
             _cache(chip, jnp.bfloat16, d=RL_MLA, hkv=1, layers=layers),
             chip((), jnp.int32), chip((b, m), jnp.int32),
             chip((b,), jnp.int32))


def test_mla_paged_prefill_attention(chip):
    from dynamo_tpu.ops.mla_attention_pallas import (
        mla_paged_prefill_attention,
    )

    def fn(qe, qp, cc, pc, bt, hist):
        return mla_paged_prefill_attention(
            qe, qp, cc, pc, bt, hist, (C_MLA + R_MLA) ** -0.5
        )

    _compile(fn, chip((T_CHUNK, H, C_MLA), jnp.bfloat16),
             chip((T_CHUNK, H, R_MLA), jnp.bfloat16),
             chip((1, N, BS, C_MLA), jnp.bfloat16),
             chip((1, N, BS, RL_MLA), jnp.bfloat16),
             chip((M,), jnp.int32), chip((), jnp.int32))


# ---------------- the grouped matmul (MoE experts) ----------------


@pytest.mark.parametrize(
    "r,k,n,x",
    [(96, 512, 256, 8), (256, 7168, 2048, 4)],
    ids=["ragged-small", "deepseek-ish"],
)
def test_moe_grouped_matmul_int8(chip, r, k, n, x):
    """An int8 stack with its scales, whole and by layer index: the
    kernel's own DMAs pick the layer, the scale block's index map too."""
    from dynamo_tpu.ops.moe_gmm_pallas import moe_grouped_matmul

    def fn(lhs, w, s, sizes, layer):
        return moe_grouped_matmul(lhs, w, sizes, layer=layer, scale=s)

    _compile(fn, chip((r, k), jnp.bfloat16), chip((3, x, k, n), jnp.int8),
             chip((3, x, n), jnp.float32), chip((x,), jnp.int32),
             chip((), jnp.int32))


#: the benchmark's three expert configurations: experts the layer is
#: handed, the router's width, experts a token, hidden and expert widths
CELL_WIDTHS = {
    "olmoe": dict(x=64, routed=64, top=8, e=2048, f=1024),
    "lfm2": dict(x=32, routed=32, top=4, e=2048, f=1792),
    "gigachat35": dict(x=16, routed=256, top=8, e=7168, f=2048),
}


@pytest.mark.parametrize("rows", [32, 544], ids=["decode-32", "mixed-544"])
@pytest.mark.parametrize("widths", sorted(CELL_WIDTHS))
def test_moe_ffn_bf16_at_the_cells_widths(chip, widths, rows):
    """The single-chip expert layer of a bf16 stack at the widths of the
    benchmark's three expert configurations (``olmoe-1b-7b``: 64 experts
    of [2048, 1024], top-8; ``lfm2-8b-a1b``: 32 of [2048, 1792], top-4;
    ``gigachat3.5-432b-a28b``: 16 of 256 held, [7168, 2048], top-8) as a
    step program runs it on the chip: the routing tally, the rows' live
    mask, the layer read out of a whole ``[L, X, ...]`` stack by its
    index (``llama._layer``), the three grouped matmuls on the Pallas
    kernel with ONE plan, the scatter-add; for a decode batch and for a
    512-token mixed step. No ``ragged-dot`` is left in the program and
    no copy of a layer's stack in front of the kernel."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    w = CELL_WIDTHS[widths]
    x, e, f = w["x"], w["e"], w["f"]
    held = x if x < w["routed"] else 0
    cfg = ModelConfig(hidden_size=e, num_heads=16, num_kv_heads=16,
                      num_experts=w["routed"], num_experts_per_tok=w["top"],
                      moe_intermediate_size=f, norm_topk_prob=False,
                      experts_held=held)
    layers = 3
    lps = {"moe_gate": chip((layers, e, w["routed"]), jnp.bfloat16),
           "we_gate": chip((layers, x, e, f), jnp.bfloat16),
           "we_up": chip((layers, x, e, f), jnp.bfloat16),
           "we_down": chip((layers, x, f, e), jnp.bfloat16)}

    def layer(lps, h, live):
        tally = llama.MoeTally(llama.MoeTally.zeros(cfg))
        out = llama.moe_ffn(llama._layer(lps, 1), cfg, h, use_pallas=True,
                            tally=tally, live=live)
        return out, tally.sums

    compiled = jax.jit(layer).lower(
        lps, chip((rows, e), jnp.bfloat16), chip((rows,), jnp.bool_)
    ).compile()
    text = compiled.as_text()
    assert text.count("moe_grouped_matmul") >= 3 and "ragged-dot" not in text
    # the kernel's operand is the stack where it lies: a layer of one
    # matrix is x * e * f * 2 bytes (0.23-0.47 GB), the program's
    # temporaries a few [rows * top, e] blocks (62.8 MB at most, the
    # 4,352 gathered rows of 7,168)
    assert compiled.memory_analysis().temp_size_in_bytes < x * e * f


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_decode_window_runs_its_experts_on_the_grouped_matmul(chip, quantized):
    """After PR 41's guards: an expert model's ``decode_window`` on the
    Pallas path holds no ``ragged-dot`` op (the TPU compiler's own
    grouped matmul streamed a step's touched experts at half the chip's
    bandwidth: PERF.md section 6, PR 45), three ``moe_grouped_matmul``
    calls an expert layer, and no temporary the size of one layer of an
    expert stack: plain and int8 stacks alike ride whole, with the
    layer's index, into the one kernel (a ``w["q"][l]`` operand was a
    copy of the layer: ROADMAP A12)."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.models.quant import quantize_params

    cfg = ModelConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024,
        num_layers=4, num_heads=4, num_kv_heads=4, head_dim=128,
        num_experts=16, num_experts_per_tok=2, moe_intermediate_size=2048,
    )

    def init():
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
        return quantize_params(params, cfg, "int8") if quantized else params

    b, m, n = 8, 32, 256
    params = jax.tree.map(lambda a: chip(a.shape, a.dtype),
                          jax.eval_shape(init))
    cache = chip(llama.kv_cache_shapes(cfg, n, BS)[0], jnp.bfloat16)
    ints, floats = chip((b,), jnp.int32), chip((b,), jnp.float32)
    compiled = llama.decode_window.lower(
        params, cfg, ints, ints, chip((b, m), jnp.int32), ints, ints, ints,
        floats, ints, floats, cache, cache, n_steps=2, use_pallas=True,
        moe_counters=True,
    ).compile()
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert text.count("moe_grouped_matmul") >= 3 * cfg.num_layers
    # one layer of one expert matrix: 16 x 512 x 2048 (16.8 MB in int8,
    # 33.6 MB in bf16); the pool here is 2 x 4 x 4.2 MB
    layer_bytes = 16 * 512 * 2048 * (1 if quantized else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 2


def test_expert_layer_is_traced_once_a_program(chip, monkeypatch):
    """The warm-start guard (PERF.md section 6, PR 45). A server that
    compiles nothing still traces and lowers every program it warms up,
    and the decode window and the fused mixed step unroll their layers:
    the expert layer is a jitted callee (``llama._moe_single``, as the
    kernel itself is), so a program of four expert layers traces the
    routing and the plan once and the kernel's body once a distinct
    (rows, K, N), and its module holds one function for the layer and
    one Mosaic payload a kernel shape. A change that unrolls them again
    fails here and not at a benchmark's ``setup_s`` bound."""
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig
    from dynamo_tpu.ops import moe_gmm_pallas

    cfg = ModelConfig(
        vocab_size=2048, hidden_size=512, intermediate_size=1024,
        num_layers=4, num_heads=4, num_kv_heads=4, head_dim=128,
        num_experts=16, num_experts_per_tok=2, moe_intermediate_size=1024,
    )
    calls = {}

    def counted(module, name):
        fn = getattr(module, name)

        def wrapper(*a, **k):
            calls[name] = calls.get(name, 0) + 1
            return fn(*a, **k)

        monkeypatch.setattr(module, name, wrapper)

    counted(llama, "_moe_route")
    counted(llama, "_moe_combine")
    counted(moe_gmm_pallas, "group_plan")
    counted(moe_gmm_pallas, "_kernel")

    # sizes no other test of this file lowers: the jitted callees' own
    # caches must not hide a trace
    b, m, n, t = 24, 32, 256, 48
    params = jax.tree.map(
        lambda a: chip(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    cache = chip(llama.kv_cache_shapes(cfg, n, BS)[0], jnp.bfloat16)
    ints, floats = chip((b,), jnp.int32), chip((b,), jnp.float32)
    batch = (ints, ints, chip((b, m), jnp.int32), ints, ints, ints, floats,
             ints, floats)
    one = chip((1,), jnp.int32)
    programs = {
        "decode_window": lambda: llama.decode_window.lower(
            params, cfg, *batch, cache, cache, n_steps=2, use_pallas=True,
            moe_counters=True),
        "mixed_step": lambda: llama.mixed_step.lower(
            params, cfg, *batch, chip((1, t), jnp.int32),
            chip((1, m), jnp.int32), one, one, cache, cache,
            use_pallas=True, moe_counters=True),
    }
    for name, lower in programs.items():
        calls.clear()
        text = lower().as_text()
        # gate and up share a shape, down has the other
        assert calls == {"_moe_route": 1, "_moe_combine": 1, "group_plan": 1,
                         "_kernel": 2}, (name, calls)
        # ... and the lowered module: the layer once, called four times;
        # three kernel calls inside it, two payloads
        assert len(re.findall(r"func\.func private @_moe_single", text)) == 1
        assert len(re.findall(r"call @_moe_single", text)) == cfg.num_layers
        assert len(re.findall(
            r"func\.func private @moe_grouped_matmul", text)) == 2, name


# ---------------- the gated delta rule's decode step ----------------


def test_linear_attn_recurrent_step_at_gigachat35_widths(chip):
    """One token of the delta rule for 32 decode slots x 64 value heads
    of [128, 128] float32, in place in layer 2 of a 4-layer state of 512
    MiB: the whole state is aliased to the output, nothing of it is a
    temporary (a ``rec[li]`` operand would be a copy of a layer's 128
    MiB). Which slots are live is an operand (``n``: 13 of the 32 in the
    benchmark's cell), so one program serves every load and the index
    maps that walk the live rows are in it."""
    from dynamo_tpu.ops.gated_delta_pallas import (
        kernel_serves, linear_attn_recurrent_step,
    )

    b, hv, dk, dv, ll = 32, 64, 128, 128, 4
    assert kernel_serves(hv, dk, dv) and not kernel_serves(4, 16, 16)
    f = jnp.float32

    def step(q, k, v, g, beta, rec, n):
        return linear_attn_recurrent_step(
            q, k, v, g, beta, rec, jnp.int32(2), n)

    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        chip((b, hv, dk), f), chip((b, hv, dk), f), chip((b, hv, dv), f),
        chip((b, hv), f), chip((b, hv), f), chip((ll, b, hv, dk, dv), f),
        chip((b,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") and "linear_attn_recurrent_step" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == ll * b * hv * dk * dv * 4
    assert mem.temp_size_in_bytes < 16 << 20
