"""Decompose the fused decode window's 21.6 ms/step on the real chip.

Self-contained window variants (not llama.decode_window) so each cost can
be ablated independently inside the SAME scan structure:

  full       = matmuls + cache writes + attention (== production path)
  no-write   = matmuls + attention on stale cache
  no-attend  = matmuls + cache writes
  matmul-only= matmuls
  no-scan    = full, but W unrolled as Python loop (no lax.scan carry)

If (full - no-write) is ~10ms/step, the scan carry is double-buffering
the caches; if (full - no-attend) dominates, it's the attention kernel.
"""

import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

if os.environ.get("DECOMPOSE_SMOKE"):
    jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np
from jax import lax

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import attention as att

if os.environ.get("DECOMPOSE_SMOKE"):  # CPU correctness smoke
    cfg = ModelConfig.tiny(num_heads=4, num_kv_heads=2, head_dim=128)
    B, BLOCK, CTX = 4, 16, 128
    W = 4
else:
    cfg = ModelConfig(
        vocab_size=32768, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=16, num_kv_heads=8, head_dim=128,
        max_position_embeddings=2048, dtype="bfloat16",
    )
    B, BLOCK, CTX = 16, 16, 2048
    W = 32
M = CTX // BLOCK
NUM_BLOCKS = B * M + 1

params = llama.init_params(cfg, jax.random.key(0))
tables = jnp.asarray(np.arange(1, NUM_BLOCKS, dtype=np.int32).reshape(B, M))
inv_freq = llama._rope_freqs(cfg)
scale = cfg.head_dim ** -0.5


def layer_body(x, lp, positions, k_cache, v_cache, l, *, write, attend):
    h = llama.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q, k, v = llama._qkv(lp, cfg, h)
    q = llama.apply_rope(q, positions, inv_freq)
    k = llama.apply_rope(k, positions, inv_freq)
    if write:
        blk, off = att.decode_slot_indices(tables, positions, BLOCK)
        k_cache = k_cache.at[l, :, blk, off].set(k.astype(k_cache.dtype))
        v_cache = v_cache.at[l, :, blk, off].set(v.astype(v_cache.dtype))
    if attend:
        seq_lens = positions + 1
        o = att.decode_attention(
            q, k_cache, v_cache, l, tables, seq_lens, scale,
            use_pallas=not os.environ.get("DECOMPOSE_SMOKE"),
        )
    else:
        o = q
    x = x + llama._mm(o.reshape(B, -1), lp["wo"])
    h = llama.rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
    x = x + llama._ffn(lp, cfg, h)
    return x, k_cache, v_cache


def step(tokens, positions, k_cache, v_cache, *, write, attend):
    x = params["embed"][tokens]
    for l in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[l], params["layers"])
        x, k_cache, v_cache = layer_body(
            x, lp, positions, k_cache, v_cache, l, write=write, attend=attend
        )
    x = llama.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = llama._logits(params, cfg, x)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return nxt, k_cache, v_cache


@partial(jax.jit, static_argnames=("write", "attend", "scan"),
         donate_argnames=("k_cache", "v_cache"))
def window(tokens, positions, k_cache, v_cache, *, write, attend, scan=True):
    if scan:
        def body(carry, _):
            tokens, positions, k_cache, v_cache = carry
            nxt, k_cache, v_cache = step(
                tokens, positions, k_cache, v_cache, write=write, attend=attend
            )
            return (nxt, positions + 1, k_cache, v_cache), None

        (tokens, positions, k_cache, v_cache), _ = lax.scan(
            body, (tokens, positions, k_cache, v_cache), None, length=W
        )
    else:
        for _ in range(W):
            tokens, k_cache, v_cache = step(
                tokens, positions, k_cache, v_cache, write=write, attend=attend
            )
            positions = positions + 1
    return tokens, positions, k_cache, v_cache


def run(tag, total=128, **kw):
    k_cache, v_cache = llama.init_kv_cache(cfg, NUM_BLOCKS, BLOCK)
    tokens = jnp.zeros(B, jnp.int32)
    positions = jnp.full((B,), CTX // 2, jnp.int32)
    iters = total // W
    state = (tokens, positions, k_cache, v_cache)
    t0 = time.perf_counter()
    state = window(*state, **kw)
    np.asarray(jax.device_get(state[0]))
    print(f"  [{tag}: compile+first {time.perf_counter()-t0:.1f}s]", flush=True)
    t0 = time.perf_counter()
    for _ in range(iters):
        state = window(*state, **kw)
    np.asarray(jax.device_get(state[0]))
    dt = time.perf_counter() - t0
    per_step = dt / (iters * W)
    print(f"{tag:28s} {per_step*1e3:7.3f} ms/step  {B/per_step:7.0f} tok/s",
          flush=True)


def run_merged(tag, total=128):
    """Production merged path: llama.decode_window use_pallas=True (one
    in-place Pallas append per step, flash-merged attention)."""
    k_cache, v_cache = llama.init_kv_cache(cfg, NUM_BLOCKS, BLOCK)
    tokens = jnp.zeros(B, jnp.int32)
    positions = jnp.full((B,), CTX // 2, jnp.int32)
    seq_lens = positions + 1
    Z = jnp.zeros(B, jnp.int32)
    iters = total // W

    def window(tokens, positions, seq_lens, k_cache, v_cache):
        toks, k_cache, v_cache = llama.decode_window(
            params, cfg, tokens, positions, tables, seq_lens,
            Z, Z, jnp.zeros(B, jnp.float32), Z, jnp.ones(B, jnp.float32),
            k_cache, v_cache, n_steps=W, use_pallas=True,
        )
        return toks[-1], positions + W, seq_lens + W, k_cache, v_cache

    state = (tokens, positions, seq_lens, k_cache, v_cache)
    t0 = time.perf_counter()
    state = window(*state)
    np.asarray(jax.device_get(state[0]))
    print(f"  [{tag}: compile+first {time.perf_counter()-t0:.1f}s]", flush=True)
    t0 = time.perf_counter()
    for _ in range(iters):
        state = window(*state)
    np.asarray(jax.device_get(state[0]))
    dt = time.perf_counter() - t0
    per_step = dt / (iters * W)
    print(f"{tag:28s} {per_step*1e3:7.3f} ms/step  {B/per_step:7.0f} tok/s",
          flush=True)


run("full (scan)", write=True, attend=True)
run("no-write", write=False, attend=True)
run("no-attend", write=True, attend=False)
run("matmul-only", write=False, attend=False)
run_merged("MERGED production path")
run("full UNROLLED steps", write=True, attend=True, scan=False, total=64)
