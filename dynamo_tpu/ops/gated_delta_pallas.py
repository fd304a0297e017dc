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

The kernel walks the LIVE rows. ``n [B]`` (``llama.Segments.n``: 0 = a
dead decode slot) gives, by scalar prefetch, the live slots' ids compacted
to the front and their count; grid step ``b`` works on slot ``live[b]``,
and the steps past the last live row map to the block that row left, so
they move nothing and run nothing. A dead slot's matrices are neither
read nor written (a sequence that is still prefilling may own them), its
``q, k, v, g, beta`` are not read either, and its ``o`` comes back zero.
What a call moves is the live rows' matrices: with every slot live it is
the whole layer, with none one block of heads (copied onto itself: the
one output block the grid maps to has to hold what it is flushed with).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: value heads a grid step: 32 matrices of 64 KiB in and out, double
#: buffered: 8 MiB of VMEM. A grid step costs 0.2-0.35 us whether it
#: moves anything or not, so at 13 live rows of 32 a call at 32 heads a
#: step takes 0.179 ms where 8 heads a step took 0.196 (scripts/
#: bench_linear_attn_step.py, PERF.md section 6, PR 52)
HEADS_PER_STEP = 32
#: heads unrolled in the kernel's body; the rest of a grid step's heads
#: are a loop over such groups (what a program traces and lowers stays
#: eight heads' worth)
HEADS_UNROLLED = 8


def _step_kernel(li_ref, live_ref, n_live_ref, qk_ref, v_ref, decay_ref,
                 beta_ref, s_ref, o_ref, s_out_ref, *, heads: int,
                 n_heads: int):
    del li_ref, live_ref  # the layer and the slots: used by the index maps
    b, j = pl.program_id(0), pl.program_id(1)
    n_live = n_live_ref[0]

    @pl.when(b < n_live)
    def _():
        qk = qk_ref[0]  # [Dk, 2 Hv]
        lane = lax.broadcasted_iota(jnp.int32, qk.shape, 1)
        unrolled = HEADS_UNROLLED if heads % HEADS_UNROLLED == 0 else heads

        def group(i, carry):
            for u in range(unrolled):
                h = i * unrolled + u
                hg = j * heads + h
                k_col = jnp.sum(jnp.where(lane == hg, qk, 0.0), axis=1,
                                keepdims=True)
                q_col = jnp.sum(jnp.where(lane == n_heads + hg, qk, 0.0),
                                axis=1, keepdims=True)
                row = pl.ds(h, 1)
                S = s_ref[0, 0, h] * decay_ref[0, row, :]
                kS = jnp.sum(S * k_col, axis=0, keepdims=True)  # [1, Dv]
                d = beta_ref[0, row, :] * (v_ref[0, row, :] - kS)
                S = S + k_col * d
                o_ref[0, row, :] = jnp.sum(S * q_col, axis=0, keepdims=True)
                s_out_ref[0, 0, h] = S
            return carry

        lax.fori_loop(0, heads // unrolled, group, 0)

    # no live row: every step maps to ONE block, which no step writes and
    # which is flushed at the end all the same
    @pl.when((n_live == 0) & (b == 0) & (j == 0))
    def _():
        s_out_ref[...] = s_ref[...]


def kernel_serves(n_heads: int, key_dim: int, value_dim: int) -> bool:
    """The shapes Mosaic takes: whole 128-lane rows everywhere. Anything
    else (the tests' tiny heads) runs in interpret mode or in
    ``llama.delta_rule_step``."""
    return (2 * n_heads) % 128 == 0 and key_dim % 8 == 0 and (
        value_dim % 128 == 0 and n_heads % HEADS_PER_STEP == 0)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def linear_attn_recurrent_step(q, k, v, g, beta, rec, layer, n,
                               interpret: bool = False):
    """q, k [B, Hv, Dk], v [B, Hv, Dv], g, beta [B, Hv] float32; ``rec``
    [Ll, B, Hv, Dk, Dv] float32 (donated), ``layer`` a scalar int32, ``n``
    [B] int32: a row's real tokens (``Segments.n``; 0: a dead slot, whose
    other operands are not read). Returns (o [B, Hv, Dv], zero in a dead
    row; rec with layer ``layer``'s matrices of the live rows moved one
    token on, every other matrix where and as it was)."""
    B, Hv, Dk = q.shape
    Dv = v.shape[-1]
    heads = HEADS_PER_STEP if Hv % HEADS_PER_STEP == 0 else Hv
    J = Hv // heads
    f32 = jnp.float32
    qk = jnp.concatenate([jnp.swapaxes(k, 1, 2), jnp.swapaxes(q, 1, 2)],
                         axis=-1).astype(f32)  # [B, Dk, 2 Hv]
    rows = lambda a: jnp.broadcast_to(  # noqa: E731
        a.astype(f32)[..., None], (B, Hv, Dv))
    # the live slots' ids in slot order, the dead ones' behind them
    live = jnp.argsort(n == 0, stable=True).astype(jnp.int32)
    n_live = jnp.sum(n > 0, dtype=jnp.int32)

    def at(b, j, live, n_live):
        """Grid step (b, j)'s (slot, block of heads): past the last live
        row, the block that row's last step left (no DMA; the output
        block is revisited and flushed once, at the end)."""
        on = b < n_live[0]
        last = live[jnp.maximum(n_live[0] - 1, 0)]
        return jnp.where(on, live[b], last), jnp.where(on, j, J - 1)

    row_spec = pl.BlockSpec(
        (1, heads, Dv), lambda b, j, li, *lv: (*at(b, j, *lv), 0))
    s_spec = pl.BlockSpec(
        (1, 1, heads, Dk, Dv),
        lambda b, j, li, *lv: (li[0], *at(b, j, *lv), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, J),
        in_specs=[
            pl.BlockSpec((1, Dk, 2 * Hv),
                         lambda b, j, li, *lv: (at(b, j, *lv)[0], 0, 0)),
            row_spec, row_spec, row_spec, s_spec,
        ],
        out_specs=[row_spec, s_spec],
    )
    o, rec = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads, n_heads=Hv),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, Hv, Dv), f32),
                   jax.ShapeDtypeStruct(rec.shape, rec.dtype)],
        input_output_aliases={7: 1},
        # consecutive steps revisit one output block: a chip with two
        # cores must not split either axis
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="linear_attn_recurrent_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), live,
      jnp.reshape(n_live, (1,)), qk, v.astype(f32), rows(jnp.exp(g)),
      rows(beta), rec)
    # a dead row's o is never written
    return jnp.where(n[:, None, None] > 0, o, 0.0), rec
