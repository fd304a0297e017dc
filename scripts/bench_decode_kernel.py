#!/usr/bin/env python3
"""The decode attention kernel alone, on the chip: what a layer-call of
``paged_decode_attention`` costs against the bytes its live pages hold.

    chiprun -- python3 scripts/bench_decode_kernel.py
    chiprun -- python3 scripts/bench_decode_kernel.py --parent .archive_check/parent

The kernel walks each row's own pages, so its price should follow the
live pages and not the table's width. At the chat cells' shape (32 slots,
MHA 16 x 128, bf16 pages of 16, a 256-page table, layer 11 of a 16-layer
5 GiB pool) it times loads from an empty batch to a full one, then one
load at other head shapes and page types (GQA 24 / 8, a tp shard's 2 KV
heads, 32 KV heads in two head tiles, int8 pages with scales), each as

* ms a call: ``--calls`` layer-calls chained inside ONE program (a decode
  step's pattern: the host's dispatch is not in the number), stats on, as
  the merged decode path calls it;
* the share of the bytes-touched floor: live pages x one page's K and V
  bytes / the chip's HBM bandwidth (``chipbench/peaks.json``).

``--parent DIR`` (a ``git archive`` of another commit unpacked in DIR)
runs that checkout's kernel on the same inputs: its ms a call and
max |difference| over out, m and l (0.0 = bit-identical). Results also
go to ``chiprun_out/bench_decode_kernel.json``. Needs a TPU: on another
backend it stops.

``--latent`` times ``mla_paged_decode_attention`` instead, at
``gigachat35.reason``'s shape (32 slots, 64 heads, latents of 512 and a
rope row of 64 in 128 lanes, a 256-page table over the cell's one-layer
pool of 12,288 pages), from an empty batch over the cell's 12 live rows
of 0.3-4 k tokens to 32 full rows; a ``--parent`` from before PR 53
takes one layer's slab and a rope pool of 64 lanes (its program re-lays
and stages that pool: what its callers paid is in its number). Results:
``chiprun_out/bench_decode_kernel_latent.json``.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from dynamo_tpu.ops import paged_attention_pallas as this_kernel  # noqa: E402
from scripts.bench_moe_layer import timed  # noqa: E402

B, D, BS, M, N = 32, 128, 16, 256, 2560
# the latent cell: query heads, latent and rope widths, the rope row's
# lanes in the pool, the pool's pages
H_LAT, C_LAT, R_LAT, RL_LAT, N_LAT = 64, 512, 64, 128, 12288

# (name, rows, tokens a row) at the cells' shape
CELL_LOADS = [
    ("every slot empty", 0, 0),
    ("2 rows of 400", 2, 400),
    ("11 rows of 430", 11, 430),
    ("13 rows of 350", 13, 350),
    ("32 rows of 1200", 32, 1200),
    ("1 row of 4096", 1, 4096),
]
# (name, query heads, kv heads, int8 pages, layers of the pool)
SHAPES = [
    ("cell: MHA 16 x 128, bf16", 16, 16, False, 16),
    ("GQA 24 / 8, bf16", 24, 8, False, 8),
    ("tp shard: 4 / 2 heads, bf16", 4, 2, False, 8),
    ("MHA 32 x 128, bf16, two head tiles", 32, 32, False, 4),
    ("MHA 16 x 128, int8 pages + scales", 16, 16, True, 8),
]


def load_kernel(checkout: str, module: str, name: str):
    """``name`` of another checkout's ``dynamo_tpu/ops/<module>.py``,
    imported under a package of its own (the kernels' files import each
    other relatively)."""
    pkg = types.ModuleType("parent_ops")
    pkg.__path__ = [os.path.join(checkout, "dynamo_tpu", "ops")]
    sys.modules.setdefault("parent_ops", pkg)
    return getattr(importlib.import_module(f"parent_ops.{module}"), name)


def chained(kernel, calls: int, layers: int):
    """One program of ``calls`` layer-calls, as a decode step makes them:
    each reads another layer of the pool and feeds the next one's query
    (nothing can be folded away). Returns the last call's (out, m, l)."""

    def run(q, kc, vc, tables, lens, ks, vs):
        def call(layer, q):
            scales = {} if ks is None else dict(
                k_scales=ks[layer], v_scales=vs[layer])
            return kernel(q, kc, vc, layer, tables, lens, D**-0.5,
                          return_stats=True, **scales)

        def body(i, q):
            o, _m, _l = call(i % layers, q)
            return q + (o * 1e-3).astype(q.dtype)

        q = lax.fori_loop(0, calls - 1, body, q)
        return call((calls - 1) % layers, q)

    return jax.jit(run)


def chained_latent(kernel, calls: int):
    """``chained`` for the latent kernel: ``calls`` layer-calls of the
    cell's one latent layer, each feeding the next one's ``q_eff``. A
    kernel from before PR 53 takes the layer's slab and no index."""
    scale = (C_LAT + R_LAT) ** -0.5
    whole = "layer" in inspect.signature(kernel).parameters

    def run(q_eff, q_pe, cc, pc, tables, lens):
        def call(q_eff):
            caches = (cc, pc, 0) if whole else (cc[0], pc[0])
            return kernel(q_eff, q_pe, *caches, tables, lens, scale,
                          return_stats=True)

        def body(_, q_eff):
            o, _m, _l = call(q_eff)
            return q_eff + (o * 1e-3).astype(q_eff.dtype)

        return call(lax.fori_loop(0, calls - 1, body, q_eff))

    return jax.jit(run), whole


def latent_main(args, dev, bw: float) -> int:
    from dynamo_tpu.ops import mla_attention_pallas as this_latent

    kernels = {"this": this_latent.mla_paged_decode_attention}
    if args.parent:
        kernels["parent"] = load_kernel(
            args.parent, "mla_attention_pallas", "mla_paged_decode_attention")
    rng = np.random.default_rng(53)
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, N_LAT))[:M] for _ in range(B)
    ]).astype(np.int32))
    keys = jax.random.split(jax.random.key(53), 4)
    q_eff = jax.random.normal(keys[0], (B, H_LAT, C_LAT), jnp.bfloat16)
    q_pe = jax.random.normal(keys[1], (B, H_LAT, R_LAT), jnp.bfloat16)
    cc = jax.random.normal(keys[2], (1, 1, N_LAT, BS, C_LAT), jnp.bfloat16)
    pe64 = jax.random.normal(keys[3], (1, 1, N_LAT, BS, R_LAT), jnp.bfloat16)
    pe128 = jnp.pad(pe64, [(0, 0)] * 4 + [(0, RL_LAT - R_LAT)])
    page_bytes = BS * (C_LAT + RL_LAT) * 2
    cell = np.zeros(B, np.int32)
    cell[np.linspace(0, B - 1, 12).round().astype(int)] = np.linspace(
        300, 4000, 12).astype(np.int32)
    loads = [("every slot empty", np.zeros(B, np.int32)),
             ("12 rows of 0.3-4 k", cell)]
    for rows, tokens in ((12, 1300), (32, 1200), (32, 4096)):
        lens = np.zeros(B, np.int32)
        lens[np.linspace(0, B - 1, rows).round().astype(int)] = tokens
        loads.append((f"{rows} rows of {tokens}", lens))
    fns = {k: chained_latent(fn, args.calls) for k, fn in kernels.items()}
    print(f"device {dev.device_kind}; latent kernel, {B} slots x {H_LAT} "
          f"heads, pages of {BS} x ({C_LAT} + {RL_LAT}) = "
          f"{page_bytes // 1024} KiB, a {M}-page table, {N_LAT} pages; HBM "
          f"{bw / 1e9:.0f} GB/s; {args.calls} layer-calls a program; floor "
          f"= live pages x a page / bandwidth", flush=True)
    rows_out = []
    for load, lens in loads:
        live_pages = int(((lens + BS - 1) // BS).sum())
        floor_ms = live_pages * page_bytes / bw * 1e3
        row = {"load": load, "live_pages": live_pages, "floor_ms": floor_ms}
        outs = {}
        for k, (fn, whole) in fns.items():
            inputs = (q_eff, q_pe, cc, pe128 if whole else pe64, tables,
                      jnp.asarray(lens))
            outs[k] = jax.tree.map(
                lambda a: np.asarray(a, np.float32), fn(*inputs))
            row[f"{k}_us"] = 1e3 * timed(
                fn, *inputs, reps=args.reps) / args.calls
        line = (f"  {load:19s} live pages {live_pages:5d} floor "
                f"{1e3 * floor_ms:7.1f} us | this {row['this_us']:7.1f} us "
                f"({100 * 1e3 * floor_ms / row['this_us']:5.1f} % of the "
                f"floor)")
        if args.parent:
            row["max_abs_diff"] = max(
                float(np.abs(a - b).max())
                for a, b in zip(outs["this"], outs["parent"]))
            line += (f" | parent {row['parent_us']:7.1f} us, x "
                     f"{row['parent_us'] / row['this_us']:5.1f}, max |diff| "
                     f"out, m, l {row['max_abs_diff']}")
        rows_out.append(row)
        print(line, flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_decode_kernel_latent.json"), "w") as f:
        json.dump({"device": dev.device_kind, "calls": args.calls,
                   "rows": rows_out}, f, indent=1)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--latent", action="store_true",
                    help="time the latent (MLA) decode kernel at "
                         "gigachat35.reason's shape instead")
    ap.add_argument("--parent", default="",
                    help="a checkout whose kernel runs on the same inputs")
    ap.add_argument("--calls", type=int, default=16,
                    help="layer-calls chained in one program")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_decode_kernel: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        bw = float(json.load(f)[dev.device_kind]["hbm_bytes_per_s"])
    if args.latent:
        return latent_main(args, dev, bw)
    kernels = {"this": this_kernel.paged_decode_attention}
    if args.parent:
        kernels["parent"] = load_kernel(
            args.parent, "paged_attention_pallas", "paged_decode_attention")
    rng = np.random.default_rng(31)
    # a row's pages are distinct; rows share the pool, as a prefix does
    tables = jnp.asarray(np.stack([
        rng.permutation(np.arange(1, N))[:M] for _ in range(B)
    ]).astype(np.int32))
    rows_out = []
    print(f"device {dev.device_kind}; {B} slots, pages of {BS} x {D}, a "
          f"{M}-page table, {N} pages a layer; HBM {bw / 1e9:.0f} GB/s; "
          f"{args.calls} layer-calls a program", flush=True)
    for name, H, Hkv, int8, layers in SHAPES:
        keys = jax.random.split(jax.random.key(H * 64 + Hkv), 5)
        shape = (layers, Hkv, N, BS, D)
        # made on the chip: the cell's pool is too much to draw on the host
        if int8:
            kc, vc = (jax.random.randint(k, shape, -127, 128, jnp.int8)
                      for k in keys[:2])
            ks, vs = (jax.random.uniform(k, (layers, N), jnp.float32, 0.005,
                                         0.02) for k in keys[2:4])
        else:
            kc, vc = (jax.random.normal(k, shape, jnp.bfloat16)
                      for k in keys[:2])
            ks = vs = None
        q = jax.random.normal(keys[4], (B, H, D), jnp.bfloat16)
        page_bytes = 2 * Hkv * BS * D * kc.dtype.itemsize  # K and V
        fns = {k: chained(fn, args.calls, layers) for k, fn in kernels.items()}
        loads = CELL_LOADS if name.startswith("cell") else CELL_LOADS[2:5:2]
        print(f"{name}; a page {page_bytes // 1024} KiB; floor = live pages "
              f"x that / bandwidth", flush=True)
        for load, rows, tokens in loads:
            lens = np.zeros(B, np.int32)
            # live rows spread over the slots, dead ones between them
            lens[np.linspace(0, B - 1, rows).round().astype(int)] = tokens
            live_pages = int(((lens + BS - 1) // BS).sum())
            floor_ms = live_pages * page_bytes / bw * 1e3
            inputs = (q, kc, vc, tables, jnp.asarray(lens), ks, vs)
            row = {"shape": name, "load": load, "live_pages": live_pages,
                   "floor_ms": floor_ms}
            outs = {}
            for k, fn in fns.items():
                outs[k] = jax.tree.map(
                    lambda a: np.asarray(a, np.float32), fn(*inputs))
                row[f"{k}_ms"] = timed(fn, *inputs, reps=args.reps) / args.calls
            line = (f"  {load:17s} live pages {live_pages:5d} floor "
                    f"{floor_ms:6.4f} ms | this {row['this_ms']:7.4f} ms "
                    f"({100 * floor_ms / row['this_ms']:5.1f} % of the floor)")
            if args.parent:
                row["max_abs_diff"] = max(
                    float(np.abs(a - b).max())
                    for a, b in zip(outs["this"], outs["parent"])
                )
                line += (f" | parent {row['parent_ms']:7.4f} ms, x "
                         f"{row['parent_ms'] / row['this_ms']:5.1f}, max "
                         f"|diff| out, m, l {row['max_abs_diff']}")
            rows_out.append(row)
            print(line, flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_decode_kernel.json"), "w") as f:
        json.dump({"device": dev.device_kind, "calls": args.calls,
                   "rows": rows_out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
