"""Measure the pp-decode tradeoff on the virtual mesh (VERDICT r3 #8).

Decode keeps PER-LAYER STAGE SHARDING on pp meshes (weights live on
their stage; GSPMD all-gathers each layer's weights to every device as
the unrolled loop reaches it) instead of pipelining microbatches — at
decode's one-token-per-seq compute the pipeline bubble dominates, but
the weight collectives sit on the critical path and that cost was
asserted, never measured (VERDICT r3 weak #6).

Two chip-free measurements per mesh config:

  * STRUCTURE — collective ops in the compiled decode-window program
    (all-gather / all-reduce / collective-permute / reduce-scatter
    counts from the optimized HLO). Backend-independent: the same
    GSPMD partitioning decides the TPU program, so "pp=2 adds N
    all-gathers of total weight volume ~= the whole stage's weights
    per step" transfers to silicon even though CPU wall time doesn't.
  * WALL — median per-token ms on the virtual CPU mesh (collectives
    via shared memory; a lower bound on structure cost, an upper bound
    on nothing — labeled as such).

Run: JAX_PLATFORMS=cpu python scripts/pp_decode_overhead.py
"""

import json
import os
import re
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import ModelConfig  # noqa: E402
from dynamo_tpu.parallel.mesh import (  # noqa: E402
    MeshConfig, cache_sharding, make_mesh, shard_params,
)

B, BLOCK, CTX, WINDOW = 4, 8, 128, 4
N_WARM, N_TIMED = 2, 16

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")


def build(cfg, mesh):
    params = llama.init_params(cfg, jax.random.key(0))
    k_cache, v_cache = llama.init_kv_cache(
        cfg, B * (CTX // BLOCK) + 1, BLOCK
    )
    if mesh is not None:
        params = shard_params(params, mesh)
        cs = cache_sharding(mesh, cfg)
        k_cache = jax.device_put(k_cache, cs)
        v_cache = jax.device_put(v_cache, cs)
    M = CTX // BLOCK
    tables = jnp.asarray(
        np.arange(1, B * M + 1, dtype=np.int32).reshape(B, M)
    )
    return params, k_cache, v_cache, tables


def measure(name, cfg, mesh):
    params, k_cache, v_cache, tables = build(cfg, mesh)
    zeros = jnp.zeros(B, jnp.int32)
    args = lambda kc, vc: (  # noqa: E731
        params, cfg, zeros, jnp.full((B,), 40, jnp.int32), tables,
        jnp.full((B,), 41, jnp.int32), zeros, zeros,
        jnp.zeros(B, jnp.float32), zeros, jnp.ones(B, jnp.float32),
        kc, vc,
    )
    kw = dict(n_steps=WINDOW, use_pallas=False, mesh=mesh)

    # STRUCTURE: collective census of the compiled program
    compiled = llama.decode_window.lower(*args(k_cache, v_cache), **kw).compile()
    text = compiled.as_text()
    census = {}
    for op in COLLECTIVES:
        n = len(re.findall(rf"\b{op}(?:-start|-done)?\(", text))
        if op in ("all-gather", "all-reduce"):
            n += len(re.findall(rf"\b{op}-(?:start|done)\(", text))
            n = len(re.findall(rf"\b{op}\w*\(", text))
        if n:
            census[op] = n
    # bytes all-gathered per step ~ the weight volume crossing stages
    # (HLO line shape: `%x = f32[4,64]{...} all-gather(...)`; tuple
    # results of -start variants are summed element-wise too)
    ag_bytes = 0
    for m in re.finditer(
        r"= \(?((?:\w+\[[0-9,]*\][^ )]*(?:, )?)+)\)? all-gather", text
    ):
        for dt, dims in re.findall(r"(\w+)\[([0-9,]*)\]", m.group(1)):
            size = int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
            itemsize = {"bf16": 2, "f32": 4, "f16": 2, "s32": 4,
                        "s8": 1, "pred": 1}.get(dt, 4)
            ag_bytes += size * itemsize

    # WALL: median per-token ms over chained windows
    tok, pos, sl, st = zeros, jnp.full((B,), 40, jnp.int32), jnp.full((B,), 41, jnp.int32), zeros
    kc, vc = k_cache, v_cache
    times = []
    for i in range(N_WARM + N_TIMED):
        t0 = time.perf_counter()
        out = llama.decode_window(
            params, cfg, tok, pos, tables, sl, st, st,
            jnp.zeros(B, jnp.float32), zeros, jnp.ones(B, jnp.float32),
            kc, vc, **kw,
        )
        toks, kc, vc = out[:3]
        tok = toks[-1]
        jax.block_until_ready(tok)
        if i >= N_WARM:
            times.append(time.perf_counter() - t0)
        # stay inside the table: rewind positions (cache rows reused)
        if (i + 1) % 4 == 0:
            pos = jnp.full((B,), 40, jnp.int32)
            sl = jnp.full((B,), 41, jnp.int32)
        else:
            pos, sl = pos + WINDOW, sl + WINDOW
    per_tok_ms = sorted(times)[len(times) // 2] / (WINDOW * B) * 1e3
    rec = {
        "config": name,
        "collectives": census,
        "all_gather_bytes_per_window": ag_bytes,
        "wall_per_token_ms_cpu": round(per_tok_ms, 3),
    }
    print(json.dumps(rec), flush=True)
    return rec


def main():
    cfg = ModelConfig.tiny(dtype="float32", num_layers=4)
    rows = [
        measure("single", cfg, None),
        measure("tp2", cfg, make_mesh(MeshConfig(tp=2))),
        measure("pp2", cfg, make_mesh(MeshConfig(pp=2))),
        measure("pp2_tp2", cfg, make_mesh(MeshConfig(pp=2, tp=2))),
        measure("dp2_tp2", cfg, make_mesh(MeshConfig(dp=2, tp=2))),
    ]
    base = rows[0]["wall_per_token_ms_cpu"]
    for r in rows:
        r["wall_vs_single"] = round(r["wall_per_token_ms_cpu"] / base, 2)
    print(json.dumps({"summary": rows}, indent=1))


if __name__ == "__main__":
    main()
