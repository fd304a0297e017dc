"""Grouped low-rank (LoRA) delta GEMMs for adapter-aware batching.

A batch row belongs to at most one adapter (``ids[r]``; ``-1`` = base
model, no delta). The fused mixed step keeps ONE shared base-GEMM pass
over the packed ``[B + MP*T]`` row axis and adds the per-adapter
low-rank correction here:

    delta[r] = (x[r] @ A[ids[r]]) @ B[ids[r]]        (0 when ids[r] < 0)

Two implementations behind one call:

  * **grouped** — rows stable-sorted by adapter id (base rows keyed past
    the last adapter so they sort to the tail), then two
    ``lax.ragged_dot`` passes over the per-adapter group sizes — the
    same grouped-GMM machinery as the MoE expert dispatch
    (ops/moe_gmm_pallas.py / models/llama._moe_route). A batch mixing
    k adapters costs one ragged pass, not k dispatches.
  * **loop** — an unrolled per-adapter ``where`` loop. This is the
    XLA fallback: each row's delta is two plain row GEMMs against its
    own adapter, the sums a solo-adapter batch of that row computes.

Both paths are row-local — a row's delta depends only on its own
activations and its own adapter — so the two lanes, and a row in a
mixed-adapter batch and in a solo one, compute the same sums over the
same operands. They agree to the order of those f32 sums, not to the
bit: another lane or another batch shape may run another dot kernel
(tests/test_multi_model.py states the bound, 2 gamma_{E+r} sum|x||A||B|;
this XLA CPU build differs in the last ulp). Base rows (ids < 0) are
exactly zero on both lanes. Greedy streams of a mixed batch match
their solo references except at near-ties of that size.

Shape/bucketing contract: ``a`` is ``[NA, E, r]``, ``b`` is
``[NA, r, O]``. NA is the engine's adapter-count bucket and r the rank
bucket — both padded with ZERO weight planes, which is bitwise exact
(``x @ 0 == 0`` and ``y + 0.0 == y``), so program counts key on the
bucket pair, never the live adapter census (test_compiled_perf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def lora_delta(
    x: jnp.ndarray,      # [R, E] activations (rows)
    a: jnp.ndarray,      # [NA, E, r] down-projections
    b: jnp.ndarray,      # [NA, r, O] up-projections
    ids: jnp.ndarray,    # [R] int32 adapter id per row; -1 = base
    grouped: bool = False,
) -> jnp.ndarray:
    """Per-row low-rank delta ``[R, O]``; exactly zero where ids < 0."""
    if x.ndim != 2:
        # prefill bodies pass [T, E]; decode merged passes [B, E] — any
        # leading structure is the caller's to keep
        raise ValueError(f"lora_delta wants [R, E] rows, got {x.shape}")
    if grouped:
        return _delta_grouped(x, a, b, ids)
    return _delta_loop(x, a, b, ids)


def _delta_loop(x, a, b, ids):
    """Unrolled per-adapter loop (XLA fallback): adapter n's delta is
    computed for every row and selected where ids == n. NA is small (the adapter bucket) and r
    tiny, so the redundant row work is noise next to the base GEMMs."""
    NA = a.shape[0]
    wdt = a.dtype
    delta = jnp.zeros((x.shape[0], b.shape[-1]), x.dtype)
    xw = x.astype(wdt)
    for n in range(NA):
        d = ((xw @ a[n]) @ b[n]).astype(x.dtype)
        delta = jnp.where((ids == n)[:, None], d, delta)
    return delta


def _delta_grouped(x, a, b, ids):
    """Grouped-GMM path: stable-sort rows by adapter id and run both
    low-rank passes as ragged dots over the per-adapter group sizes —
    one dispatch regardless of how many adapters the batch mixes."""
    NA = a.shape[0]
    base = ids < 0
    # base rows sort past every adapter group (key NA) and fall outside
    # sum(group_sizes); their output rows are masked to exact zero below
    key = jnp.where(base, NA, ids).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    x_s = x[order].astype(a.dtype)
    group_sizes = jnp.bincount(key, length=NA + 1)[:NA].astype(jnp.int32)
    h = lax.ragged_dot(x_s, a, group_sizes)          # [R, r]
    d_s = lax.ragged_dot(h, b, group_sizes)          # [R, O]
    inv = jnp.argsort(order, stable=True)
    d = d_s[inv].astype(x.dtype)
    return jnp.where(base[:, None], jnp.zeros((), x.dtype), d)


def slice_layer(lora, l: int):
    """One layer's adapter stacks out of the stacked-[L] pytree (the
    lora layer loops are always unrolled, like the quantized-KV branch,
    so ``l`` is a static python int)."""
    return jax.tree.map(lambda arr: arr[l], lora)
