"""Decode profiling, take 2: chained windows exactly like bench.py.

Per-step time vs window size separates per-dispatch host overhead
from device compute; isolated timings of the library attention
kernel, the cache scatter, and the lm head find the on-device split.
All jitted fns take params/caches as ARGUMENTS (no captured constants).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.ops import attention as att
from dynamo_tpu.ops.paged_attention_pallas import paged_decode_attention

cfg = ModelConfig(
    vocab_size=32768, hidden_size=2048, intermediate_size=8192,
    num_layers=16, num_heads=16, num_kv_heads=8, head_dim=128,
    max_position_embeddings=2048, dtype="bfloat16",
)
B, BLOCK, CTX = 16, 16, 2048
M = CTX // BLOCK
NUM_BLOCKS = B * M + 1

params = llama.init_params(cfg, jax.random.key(0))
k_cache0, v_cache0 = llama.init_kv_cache(cfg, NUM_BLOCKS, BLOCK)

tables = jnp.asarray(np.arange(1, NUM_BLOCKS, dtype=np.int32).reshape(B, M))
seq_len0 = CTX // 2
tokens0 = jnp.zeros(B, jnp.int32)
seeds = jnp.zeros(B, jnp.int32)
temps = jnp.zeros(B, jnp.float32)
top_ks = jnp.zeros(B, jnp.int32)
top_ps = jnp.ones(B, jnp.float32)


def bench_windows(W: int, total: int = 384):
    """Chained decode windows (donated caches, like bench.py)."""
    k_cache, v_cache = jnp.copy(k_cache0), jnp.copy(v_cache0)
    tokens = tokens0
    positions = jnp.full((B,), seq_len0, jnp.int32)
    seq_lens = jnp.full((B,), seq_len0 + 1, jnp.int32)
    steps = jnp.zeros(B, jnp.int32)
    iters = total // W

    def window(tokens, positions, seq_lens, steps, k_cache, v_cache):
        toks, k_cache, v_cache = llama.decode_window(
            params, cfg, tokens, positions, tables, seq_lens,
            seeds, steps, temps, top_ks, top_ps, k_cache, v_cache,
            n_steps=W, use_pallas=True,
        )
        return (toks[-1], positions + W, seq_lens + W, steps + W,
                k_cache, v_cache)

    state = (tokens, positions, seq_lens, steps, k_cache, v_cache)
    state = window(*state)  # compile
    np.asarray(jax.device_get(state[0]))
    t0 = time.perf_counter()
    for _ in range(iters):
        state = window(*state)
    np.asarray(jax.device_get(state[0]))
    dt = time.perf_counter() - t0
    per_step = dt / (iters * W)
    print(f"decode_window W={W:3d}: {per_step*1e3:7.3f} ms/step, "
          f"{B/per_step:7.0f} tok/s, {iters} dispatches in {dt:.2f}s",
          flush=True)
    return per_step


def timeit(name, fn, *args, iters=20):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r)
    dt = (time.perf_counter() - t0) / iters
    print(f"{name:48s} {dt*1e3:9.3f} ms", flush=True)
    return dt


# ---- chained windows: per-step vs W reveals dispatch overhead ----
for W in (4, 16, 64):
    bench_windows(W)

# ---- isolated pieces ----
seq_lens_h = jnp.full((B,), seq_len0 + 1, jnp.int32)
q = jnp.zeros((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
scale = cfg.head_dim ** -0.5

lib_att = jax.jit(
    lambda q, kc, vc: paged_decode_attention(
        q, kc, vc, 0, tables, seq_lens_h, scale
    )
)
timeit("paged decode attention kernel (1 layer)", lib_att,
       q, k_cache0, v_cache0)

# full-cache scatter: what _decode_body does per layer per step
kv_new = jnp.zeros((B, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)


@jax.jit
def scatter(k_cache, kv_new, positions):
    blk, off = att.decode_slot_indices(tables, positions, BLOCK)
    return k_cache.at[0, :, blk, off].set(kv_new)


pos_h = jnp.full((B,), seq_len0, jnp.int32)
kc = jnp.copy(k_cache0)
timeit("cache scatter .at[l,:,blk,off].set (1 layer)", scatter,
       kc, kv_new, pos_h, iters=10)


@jax.jit
def scatter_donated(k_cache, kv_new, positions):
    blk, off = att.decode_slot_indices(tables, positions, BLOCK)
    return k_cache.at[0, :, blk, off].set(kv_new)


scatter_d = jax.jit(
    lambda k_cache, kv_new, positions: scatter_donated(k_cache, kv_new, positions),
    donate_argnums=(0,),
)
# donated variant: chain it so each call consumes the previous output
kc = jnp.copy(k_cache0)
jax.block_until_ready(kc)
out = scatter_d(kc, kv_new, pos_h)
jax.block_until_ready(out)
t0 = time.perf_counter()
for _ in range(10):
    out = scatter_d(out, kv_new, pos_h)
jax.block_until_ready(out)
print(f"{'cache scatter DONATED (1 layer)':48s} {(time.perf_counter()-t0)/10*1e3:9.3f} ms",
      flush=True)

# lm head + embed: [B,E]x[E,V]
lm = jax.jit(lambda x, params: llama._logits(params, cfg, x))
x0 = jnp.zeros((B, cfg.hidden_size), jnp.bfloat16)
timeit("lm head logits [16,2048]x[2048,32768]", lm, x0, params)

# sampling
from dynamo_tpu.ops.sampling import make_keys, sample_tokens
logits = jnp.zeros((B, cfg.vocab_size), jnp.bfloat16)
keys = make_keys(seeds, jnp.zeros(B, jnp.int32))
samp = jax.jit(lambda l, k: sample_tokens(l, k, temps, top_ks, top_ps))
timeit("sample_tokens (greedy)", samp, logits, keys)

# single dispatch round-trip latency: trivial op
triv = jax.jit(lambda x: x + 1)
timeit("trivial dispatch x+1 [16]", triv, tokens0)
