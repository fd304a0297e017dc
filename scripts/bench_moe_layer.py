#!/usr/bin/env python3
"""The single-chip expert layer alone, on the chip: what ``lax.ragged_dot``
costs at a configuration's widths against the bytes it has to touch.

    chiprun -- python3 scripts/bench_moe_layer.py
    chiprun -- python3 scripts/bench_moe_layer.py --rows 32,256 --layers 4

For each row count (assignments: tokens x experts per token) and each
routing (``even``: every token draws its experts uniformly; ``skewed``:
expert popularity falls as 1/rank; ``dead``: an eighth of the tokens draw
uniformly and the rest are one token repeated, a decode batch of mostly
identical dead slots) it times, in ms a call:

* ``gate`` / ``down``: one ``lax.ragged_dot`` over ``[X, E, F]`` /
  ``[X, F, E]`` held as an array of its own;
* ``ffn``: gate, up, activation, down (the three grouped matmuls);
* ``stack``: the same ``ffn`` on layer 1 of an ``[L, X, ...]`` stack read
  in place (``llama._layer``: the layer's groups among ``L * X``);
* ``sliced``: the same with the layer sliced out of the stack first, as
  an unrolled program did before (XLA copies the slice for the kernel);
* ``layer``: ``llama.moe_ffn`` whole (router, top-k, sort, gather, ffn,
  scatter-add) with its routing tally;

and prints each as a share of the bytes-touched floor: touched experts x
one expert's bytes x 3 matrices / the chip's HBM bandwidth
(``chipbench/peaks.json``). A last line streams the same bytes through
one dense matmul, for what XLA reaches on this chip. Results also go to
``chiprun_out/bench_moe_layer.json``. Needs a TPU: on another backend it
stops.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.models.config import ModelConfig  # noqa: E402


def draw_experts(kind: str, tokens: int, X: int, k: int, rng) -> np.ndarray:
    """[tokens, k] distinct experts a token."""
    p = np.ones(X) if kind != "skewed" else 1.0 / np.arange(1, X + 1)
    p = p / p.sum()
    rows = [rng.choice(X, k, replace=False, p=p) for _ in range(tokens)]
    if kind == "dead":
        live = max(tokens // 8, 1)
        rows = rows[:live] + [rows[live]] * (tokens - live)
    return np.asarray(rows)


def timed(fn, *args, reps: int) -> float:
    """ms a call: ``reps`` calls enqueued back to back, one wait."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) * 1e3 / reps


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=os.path.join(
        REPO, "chipbench", "configs", "olmoe-1b-7b"))
    ap.add_argument("--rows", default="32,104,256,4352")
    ap.add_argument("--layers", type=int, default=3,
                    help="depth of the stack the 'stack'/'sliced' lines read")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_moe_layer: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        peaks = json.load(f)[dev.device_kind]
    bw = float(peaks["hbm_bytes_per_s"])
    cfg = ModelConfig.from_local_path(args.config)
    X, k, E = cfg.num_experts, cfg.num_experts_per_tok, cfg.hidden_size
    F, L = cfg.moe_intermediate_size, args.layers
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 5)
    draw = lambda key, shape: (  # noqa: E731
        jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dt)
    stack = {"we_gate": draw(keys[0], (L, X, E, F)),
             "we_up": draw(keys[1], (L, X, E, F)),
             "we_down": draw(keys[2], (L, X, F, E))}
    own = {n: jnp.array(w[1]) for n, w in stack.items()}  # arrays of their own
    gate_w = draw(keys[3], (E, X))
    expert_bytes = 3 * E * F * 2

    def ffn(lp, xs, sizes):
        g = llama._ragged_mm(xs, lp["we_gate"], sizes, False, False)
        u = llama._ragged_mm(xs, lp["we_up"], sizes, False, False)
        return llama._ragged_mm(jax.nn.silu(g) * u, lp["we_down"], sizes,
                                False, False)

    # every weight is an ARGUMENT: an array a jitted function closes over
    # is baked into its program as a constant
    fns = {
        "dot": jax.jit(lax.ragged_dot),
        "ffn": jax.jit(ffn),
        "stack": jax.jit(lambda st, xs, s: ffn(llama._layer(st, 1), xs, s)),
        "sliced": jax.jit(lambda st, xs, s: ffn(
            jax.tree.map(lambda a: a[1], st), xs, s)),
    }

    @jax.jit
    def layer(lp, x, live):
        tally = llama.MoeTally(live)
        return llama.moe_ffn(lp, cfg, x, tally=tally), tally.sums

    print(f"device {dev.device_kind}; X {X} top-{k} E {E} F {F} bf16; one "
          f"expert {expert_bytes / 1e6:.2f} MB in 3 matrices; HBM "
          f"{bw / 1e9:.0f} GB/s; floor = touched x {expert_bytes / 1e6:.2f} "
          f"MB / bandwidth", flush=True)
    rng = np.random.default_rng(0)
    rows_out = []
    for R in (int(r) for r in args.rows.split(",")):
        for kind in ("even", "skewed", "dead"):
            experts = draw_experts(kind, R // k, X, k, rng)
            sizes = jnp.asarray(
                np.bincount(experts.reshape(-1), minlength=X), jnp.int32)
            touched = int((np.asarray(sizes) > 0).sum())
            floor_ms = touched * expert_bytes / bw * 1e3
            xs = draw(keys[4], (R, E))
            hs = draw(keys[4], (R, F))
            ms = {
                "gate": timed(fns["dot"], xs, own["we_gate"], sizes,
                              reps=args.reps),
                "down": timed(fns["dot"], hs, own["we_down"], sizes,
                              reps=args.reps),
                "ffn": timed(fns["ffn"], own, xs, sizes, reps=args.reps),
                "stack": timed(fns["stack"], stack, xs, sizes,
                               reps=args.reps),
                "sliced": timed(fns["sliced"], stack, xs, sizes,
                                reps=args.reps),
            }
            row = {"rows": R, "routing": kind, "touched": touched,
                   "largest_group": int(np.asarray(sizes).max()),
                   "floor_ms": floor_ms, "ms": ms,
                   "ffn_share_of_floor": floor_ms / ms["ffn"]}
            rows_out.append(row)
            print(f"rows {R:5d} {kind:6s} touched {touched:2d} largest "
                  f"{row['largest_group']:4d} floor {floor_ms:6.3f} ms | "
                  + " ".join(f"{n} {v:7.3f}" for n, v in ms.items())
                  + f" | ffn roofline share {100 * floor_ms / ms['ffn']:.1f} %",
                  flush=True)
    # moe_ffn whole, routing from a real router on random activations
    lp = dict(own, moe_gate=gate_w)
    for T in (32, 544):
        x = draw(keys[4], (T, E)) * 50
        live = jnp.arange(T) < max(T // 2, 1)
        _out, sums = layer(lp, x, live)
        ms = timed(layer, lp, x, live, reps=args.reps)
        touched = int(sums[0])
        floor_ms = touched * expert_bytes / bw * 1e3
        rows_out.append({"tokens": T, "layer_ms": ms, "touched": touched,
                         "touched_live": int(sums[1]),
                         "largest_group": int(sums[2]), "floor_ms": floor_ms})
        print(f"layer (moe_ffn + tally) {T} tokens: {ms:.3f} ms, touched "
              f"{touched} (live {int(sums[1])}), largest group "
              f"{int(sums[2])}, floor {floor_ms:.3f} ms", flush=True)
    # the same bytes through one dense matmul: what XLA streams here
    w = own["we_gate"].reshape(X * E, F)
    x = draw(keys[4], (32, X * E))
    ms = timed(jax.jit(lambda x, w: x @ w), x, w, reps=args.reps)
    gbs = w.size * 2 / ms / 1e6
    rows_out.append({"dense_stream_ms": ms, "gb_per_s": gbs})
    print(f"dense [32, {X * E}] @ [{X * E}, {F}] ({w.size * 2 / 1e6:.0f} MB): "
          f"{ms:.3f} ms = {gbs:.0f} GB/s", flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_moe_layer.json"), "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows_out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
