"""Pallas TPU kernel: ONE token of the gated delta rule for a decode batch,
in place in the layer-major state.

A decode step of GigaChat 3.5's linear-attention layers
(``llama.gated_delta``) moves, a layer, one float32 ``[Dk, Dv]`` matrix a
(row, value head): 128 MiB at 32 rows x 64 heads of 128 x 128. In plain
``jax.numpy`` (``llama.delta_rule_step``) the update needs ``S^T k``
before it can write ``S``, so XLA reads the matrices twice and writes them
once, and ``rec[li]`` / ``rec.at[li].set`` around it may copy the layer's
slab. This kernel holds a block of heads' matrices in VMEM, reads and
writes each once, and takes the WHOLE state ``rec [Ll, B, Hv, Dk, Dv]``
with the layer's index (scalar prefetch into the index maps;
``input_output_aliases`` pins the output to the input, so only the
layer's tiles move):

    S <- exp(g) S;  d = beta (v - S^T k);  S <- S + k d^T;  o = S^T q

all float32 on the VPU (no matmul: ``S^T k`` of one token is a
matrix-vector product, and the MXU's passes would round it).

Layout. ``S`` lies ``[Dk (sublanes), Dv (lanes)]``, so ``v``, ``d``, the
decay and ``beta`` are ROWS (handed over as ``[B, Hv, Dv]``, the two
scalars a head broadcast along the lanes outside: 1 MiB) and ``k``, ``q``
have to be COLUMNS. A ``[Dk, 1]`` block would be padded to 128 lanes in
HBM and in VMEM, so the caller hands ``qk [B, Dk, 2 Hv]``: a row's key
heads in lanes 0..Hv-1 and its query heads behind them (exactly 128
lanes at 64 heads), and the kernel takes head h's column out with a
select on a lane iota and a lane reduction, which Mosaic has for every
layout (a dynamic lane slice it has not).

A row with ``g = 0`` and ``beta = 0`` (a dead decode slot) gets its
matrices written back as they were.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: value heads a grid step: 8 matrices of 64 KiB in and out, double
#: buffered: 2 MiB of VMEM
HEADS_PER_STEP = 8


def _step_kernel(li_ref, qk_ref, v_ref, decay_ref, beta_ref, s_ref,
                 o_ref, s_out_ref, *, heads: int, n_heads: int):
    del li_ref  # the layer's index: used by the index maps
    j = pl.program_id(1)
    qk = qk_ref[0]  # [Dk, 2 Hv]
    lane = lax.broadcasted_iota(jnp.int32, qk.shape, 1)
    for h in range(heads):
        hg = j * heads + h
        k_col = jnp.sum(jnp.where(lane == hg, qk, 0.0), axis=1, keepdims=True)
        q_col = jnp.sum(jnp.where(lane == n_heads + hg, qk, 0.0), axis=1,
                        keepdims=True)
        S = s_ref[0, 0, h] * decay_ref[0, h : h + 1, :]
        kS = jnp.sum(S * k_col, axis=0, keepdims=True)  # [1, Dv]
        d = beta_ref[0, h : h + 1, :] * (v_ref[0, h : h + 1, :] - kS)
        S = S + k_col * d
        o_ref[0, h : h + 1, :] = jnp.sum(S * q_col, axis=0, keepdims=True)
        s_out_ref[0, 0, h] = S


def kernel_serves(n_heads: int, key_dim: int, value_dim: int) -> bool:
    """The shapes Mosaic takes: whole 128-lane rows everywhere. Anything
    else (the tests' tiny heads) runs in interpret mode or in
    ``llama.delta_rule_step``."""
    return (2 * n_heads) % 128 == 0 and key_dim % 8 == 0 and (
        value_dim % 128 == 0 and n_heads % HEADS_PER_STEP == 0)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def linear_attn_recurrent_step(q, k, v, g, beta, rec, layer,
                               interpret: bool = False):
    """q, k [B, Hv, Dk], v [B, Hv, Dv], g, beta [B, Hv] float32; ``rec``
    [Ll, B, Hv, Dk, Dv] float32 (donated), ``layer`` a scalar int32.
    Returns (o [B, Hv, Dv], rec with layer ``layer``'s matrices moved one
    token on)."""
    B, Hv, Dk = q.shape
    Dv = v.shape[-1]
    heads = HEADS_PER_STEP if Hv % HEADS_PER_STEP == 0 else Hv
    f32 = jnp.float32
    qk = jnp.concatenate([jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2)],
                         axis=-1).astype(f32)  # [B, Dk, 2 Hv]
    rows = lambda a: jnp.broadcast_to(  # noqa: E731
        a.astype(f32)[..., None], (B, Hv, Dv))
    row_spec = pl.BlockSpec((1, heads, Dv), lambda b, j, li: (b, j, 0))
    s_spec = pl.BlockSpec((1, 1, heads, Dk, Dv),
                          lambda b, j, li: (li[0], b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hv // heads),
        in_specs=[
            pl.BlockSpec((1, Dk, 2 * Hv), lambda b, j, li: (b, 0, 0)),
            row_spec, row_spec, row_spec, s_spec,
        ],
        out_specs=[row_spec, s_spec],
    )
    o, rec = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, n_heads=Hv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hv, Dv), f32),
                   jax.ShapeDtypeStruct(rec.shape, rec.dtype)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="linear_attn_recurrent_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), qk, v.astype(f32),
      rows(jnp.exp(g)), rows(beta), rec)
    return o, rec
