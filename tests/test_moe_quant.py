"""Quantized MoE experts (VERDICT r4 next #3).

``quantization="int8"`` must cover expert stacks — the flagship EP-decode
configs (DeepSeek-R1, Mixtral) are exactly where halving the expert
weight stream matters most.  Coverage:

* the grouped-dequant Pallas kernel (ops/moe_gmm_pallas.py) matches the
  dequantize->ragged_dot XLA reference across the ragged edge cases
  (empty groups, one-expert-takes-all, groups crossing row tiles,
  window padding, all-empty windows);
* quantized MoE logits stay within quant tolerance of bf16 on the
  dense-dispatch, unsharded-ragged AND ep×tp-sharded paths;
* the TPU lowering of the real decode window streams expert weights as
  int8 into the kernel, with NO materialized full-stack dequant — the
  failure mode that would make expert quantization cost MORE bandwidth
  than bf16 (the XLA fallback is the negative control: it must contain
  exactly that materialization).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import quantize_params
from dynamo_tpu.ops.moe_gmm_pallas import ragged_int8_gmm, ragged_int8_xla
from dynamo_tpu.parallel.mesh import MeshConfig, make_mesh

QCFG = ModelConfig.tiny(
    dtype="float32", num_experts=4, num_experts_per_tok=2,
    moe_intermediate_size=32,
)


@pytest.fixture(scope="module")
def qsetup():
    params = llama.init_params(QCFG, jax.random.key(3))
    qparams = quantize_params(params, QCFG, "int8")
    lp = {k: v[0] for k, v in params["layers"].items()}
    qlp = jax.tree.map(lambda a: a[0], qparams["layers"])
    return QCFG, lp, qlp


# ---------------------------------------------------------------------------
# kernel parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("r,k,n,x,sizes", [
    (24, 64, 128, 4, [7, 0, 9, 8]),
    (64, 32, 256, 8, [64, 0, 0, 0, 0, 0, 0, 0]),  # one expert takes all
    (40, 16, 128, 4, [1, 1, 1, 37]),  # tiny groups + one spanning tiles
    (16, 8, 128, 16, [1] * 16),  # more experts than fit one tile
    (8, 128, 128, 4, [0, 0, 0, 0]),  # empty window (ep shard with 0 rows)
    (100, 48, 384, 6, [20, 0, 30, 10, 25, 15]),  # R % tm != 0 (padding)
])
def test_gmm_kernel_matches_xla_reference(r, k, n, x, sizes):
    rng = np.random.default_rng(0)
    gs = jnp.asarray(np.asarray(sizes, np.int32))
    total = int(np.sum(sizes))
    lhs = jnp.asarray(rng.normal(size=(r, k)), jnp.bfloat16)
    q = jnp.asarray(rng.integers(-127, 128, size=(x, k, n)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.5, 2.0, size=(x, n)), jnp.float32)
    ref = np.asarray(ragged_int8_xla(lhs, q, s, gs))
    ref = np.where(np.arange(r)[:, None] < total, ref, 0.0)
    got = np.asarray(ragged_int8_gmm(lhs, q, s, gs, tm=8, interpret=True))
    scale = np.abs(ref).max() + 1e-6
    np.testing.assert_allclose(got / scale, ref / scale, atol=2e-2)


def test_gmm_kernel_zeroes_unowned_rows():
    """Rows beyond sum(group_sizes) (window padding) must come back as
    exact zeros — a NaN there would poison the zero-weight combine."""
    lhs = jnp.ones((16, 8), jnp.bfloat16)
    q = jnp.ones((2, 8, 128), jnp.int8)
    s = jnp.ones((2, 128), jnp.float32)
    gs = jnp.asarray([3, 2], jnp.int32)
    out = np.asarray(ragged_int8_gmm(lhs, q, s, gs, tm=8, interpret=True))
    assert (out[5:] == 0).all()
    assert np.isfinite(out).all()


# ---------------------------------------------------------------------------
# quantize_params coverage
# ---------------------------------------------------------------------------


def test_quantize_params_covers_expert_stacks():
    params = llama.init_params(QCFG, jax.random.key(0))
    qp = quantize_params(params, QCFG, "int8")
    for key in ("we_gate", "we_up", "we_down"):
        node = qp["layers"][key]
        assert isinstance(node, dict) and node["q"].dtype == jnp.int8
        # scales: per (layer, expert, out-channel)
        assert node["s"].shape == node["q"].shape[:-2] + node["q"].shape[-1:]
    # escape hatch
    qp2 = quantize_params(params, QCFG, "int8", experts=False)
    assert not isinstance(qp2["layers"]["we_gate"], dict)
    assert isinstance(qp2["layers"]["wq"], dict)  # dense still covered


# ---------------------------------------------------------------------------
# model-path parity (quant tolerance vs full precision)
# ---------------------------------------------------------------------------


def _rel_err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.abs(a - b).max() / (np.abs(b).max() + 1e-6)


def test_moe_ffn_int8_close_to_full_precision(qsetup):
    cfg, lp, qlp = qsetup
    x = jax.random.normal(jax.random.key(1), (12, cfg.hidden_size),
                          jnp.float32)
    ref = llama.moe_ffn(lp, cfg, x)
    got = llama.moe_ffn(qlp, cfg, x)  # XLA fallback path
    assert _rel_err(got, ref) < 0.05


def test_moe_ffn_kernel_path_matches_xla_path(qsetup):
    """use_pallas (interpret) and the XLA fallback compute the same
    quantized math — tight tolerance, it's the same numbers reordered."""
    cfg, lp, qlp = qsetup
    x = jax.random.normal(jax.random.key(2), (12, cfg.hidden_size),
                          jnp.float32)
    ref = llama.moe_ffn(qlp, cfg, x)
    got = llama.moe_ffn(qlp, cfg, x, use_pallas=True, interpret=True)
    assert _rel_err(got, ref) < 2e-3


def test_moe_dense_dispatch_consumes_quantized_experts(qsetup):
    """The GSPMD fallback (indivisible shapes) must also accept quant
    nodes: einsum dequant matches the ragged quant path exactly."""
    cfg, lp, qlp = qsetup
    x = jax.random.normal(jax.random.key(4), (10, cfg.hidden_size),
                          jnp.float32)
    ragged = llama.moe_ffn(qlp, cfg, x)
    dense = llama.moe_ffn_dense(qlp, cfg, x)
    assert _rel_err(dense, ragged) < 2e-3


def test_moe_sharded_quant_matches_unsharded(qsetup):
    """ep×tp shard_map with quantized expert shards (q sliced like the
    plain stack, s with the contraction axis dropped)."""
    cfg, lp, qlp = qsetup
    x = jax.random.normal(jax.random.key(5), (8, cfg.hidden_size),
                          jnp.float32)
    ref = llama.moe_ffn(qlp, cfg, x)
    mesh = make_mesh(MeshConfig(ep=2, tp=2))
    got = llama.moe_ffn(qlp, cfg, x, mesh=mesh)
    assert _rel_err(got, ref) < 2e-3
    got_k = llama.moe_ffn(qlp, cfg, x, mesh=mesh, use_pallas=True,
                          interpret=True)
    assert _rel_err(got_k, ref) < 2e-3


# ---------------------------------------------------------------------------
# compiled-program property: int8 streams, no materialized dequant
# ---------------------------------------------------------------------------


def _export_decode_text(cfg, qparams, use_pallas):
    from jax import export as jexport

    B, BLOCK, CTX = 2, 16, 64
    M = CTX // BLOCK
    nb = B * M + 1
    ks, vs = llama.kv_cache_shapes(cfg, nb, BLOCK)
    dt = jnp.bfloat16
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), qparams)
    exp = jexport.export(llama.decode_window, platforms=["tpu"])(
        shapes, cfg, i32(B), i32(B),
        jax.ShapeDtypeStruct((B, M), jnp.int32), i32(B),
        i32(B), i32(B), f32(B), i32(B), f32(B),
        jax.ShapeDtypeStruct(ks, dt), jax.ShapeDtypeStruct(vs, dt),
        n_steps=2, use_pallas=use_pallas,
    )
    return exp.mlir_module()


@pytest.fixture(scope="module")
def qcfg_bf16_params():
    cfg = ModelConfig.tiny(
        dtype="bfloat16", head_dim=128, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=128,
    )
    params = llama.init_params(cfg, jax.random.key(0))
    return cfg, quantize_params(params, cfg, "int8")


def test_decode_tpu_export_streams_experts_as_int8(qcfg_bf16_params):
    cfg, qparams = qcfg_bf16_params
    text = _export_decode_text(cfg, qparams, use_pallas=True)
    x, k, n = (cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size)
    stack = f"{x}x{k}x{n}xi8"
    assert stack in text, "expert stack lost its int8 storage"
    # the materialized-dequant failure mode: a bf16/f32 copy of the
    # full per-layer expert stack
    for bad in (f"{x}x{k}x{n}xbf16", f"{x}x{k}x{n}xf32"):
        assert f"-> tensor<{bad}>" not in text, (
            f"full expert stack materialized at {bad} — expert "
            "quantization is costing bandwidth instead of saving it"
        )
    assert text.count("tpu_custom_call") >= 3  # attention+append+gmm


def test_decode_xla_fallback_trips_the_dequant_detector(qcfg_bf16_params):
    """Negative control: the XLA path DOES materialize the dequantized
    stack (that's why the kernel exists)."""
    cfg, qparams = qcfg_bf16_params
    text = _export_decode_text(cfg, qparams, use_pallas=False)
    x, k, n = (cfg.num_experts, cfg.hidden_size, cfg.moe_intermediate_size)
    hits = [bad for bad in (f"{x}x{k}x{n}xbf16", f"{x}x{k}x{n}xf32")
            if f"-> tensor<{bad}>" in text]
    assert hits, "dequant detector no longer matches the XLA path"
