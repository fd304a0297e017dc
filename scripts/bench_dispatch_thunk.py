#!/usr/bin/env python3
"""A decode dispatch's hand-over alone, on the chip: what it costs the
host to give a step program its per-slot arguments, part by part.

    chiprun -- python3 scripts/bench_dispatch_thunk.py
    chiprun -- python3 scripts/bench_dispatch_thunk.py --reps 400

For each cell's batch and block-table width (B = 32; 256 columns at a
context of 4,096, two tables of 1,024 for ``mellum2.repo``) it times, in
ms of HOST time until the call returns (nothing waits for the device
inside a timing; the device is drained between two):

* ``asarray x9``: the nine ``jnp.asarray`` of the parent's thunk (last
  tokens, positions, table(s), lengths, seeds, steps, temperatures,
  top-k, top-p);
* ``device_put(9)``: ONE ``jax.device_put`` of the same nine as a tuple;
* ``device_put(1)``: one ``jax.device_put`` of the packed ``[B, W]``
  int32 matrix the engine keeps since PR 48 (``engine/step_state.py``);
* ``call``: a jitted step with ``--leaves`` parameter leaves (192: a
  16-layer model's), two donated caches and its per-slot arguments
  already on the device: the call's own overhead;
* ``parent``: ``asarray x9`` and the call, what the parent's thunk does;
* ``delta``: what a steady dispatch does now: one ``device_put`` of a
  ``[K, 3]`` int32 array of (slot, column, page) and the call of a step
  that scatters it into the resident matrix and returns the matrix;
* ``steady``: the same call with the cached empty delta (no page
  crossed: nothing handed over);
* ``resync``: one ``device_put`` of the whole matrix and that call.

The step here is a stand-in (a few adds over its arguments), not a
model: the device's part of a step is not this script's business, and
the host's part of the call depends on the count of leaves, not on their
sizes. Results also go to ``chiprun_out/bench_dispatch_thunk.json``.
Needs a TPU: on another backend it stops (``--allow-cpu`` rehearses the
control flow and says so in the output).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dynamo_tpu.engine.step_state import DELTA_CELLS, StepState  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402

# (cell, batch, table columns, tables)
CELLS = [
    ("olmo2-1b.chat", 32, 256, 1),
    ("olmoe-1b-7b.chat", 32, 256, 1),
    ("lfm2-8b-a1b.chat", 32, 256, 1),
    ("gigachat35.reason", 32, 256, 1),
    ("mellum2.repo", 32, 1024, 2),
]


def median_ms(fn, reps: int, settle) -> float:
    """Median host ms of ``fn()`` over ``reps`` calls; ``settle(out)``
    waits for the device outside the timing."""
    out = []
    for _ in range(reps):
        t = time.perf_counter()
        res = fn()
        out.append((time.perf_counter() - t) * 1e3)
        settle(res)
    return statistics.median(out)


@partial(jax.jit, donate_argnames=("kc", "vc"))
def parent_step(params, tokens, positions, tables, seq_lens, seeds, steps,
                temps, top_ks, top_ps, kc, vc):
    bump = sum(jnp.sum(p) for p in jax.tree_util.tree_leaves(params))
    tabs = jax.tree_util.tree_leaves(tables)
    toks = (tokens + positions + seq_lens + seeds + steps + top_ks
            + sum(t[:, 0] for t in tabs)
            + (temps + top_ps + bump).astype(jnp.int32))
    return toks, kc + 1, vc + 1


@partial(jax.jit, static_argnames=("n_tables",),
         donate_argnames=("rows", "kc", "vc"))
def resident_step(params, rows, delta, kc, vc, n_tables):
    bump = sum(jnp.sum(p) for p in jax.tree_util.tree_leaves(params))
    rows, r = llama.rows_enter(rows, delta, n_tables)
    tabs = jax.tree_util.tree_leaves(r["tables"])
    toks = (r["tokens"] + r["seq_lens"] + r["seeds"] + r["steps"]
            + r["top_ks"] + sum(t[:, 0] for t in tabs)
            + (r["temps"] + r["top_ps"] + bump).astype(jnp.int32))
    live = r["seq_lens"] > 0
    rows = llama.rows_leave(rows, live, toks, r["seq_lens"] + 1,
                            r["steps"] + 1)
    return toks, kc + 1, vc + 1, rows


def bench_cell(B: int, M: int, n_tables: int, leaves: int, reps: int) -> dict:
    rng = np.random.default_rng(0)
    params = {f"w{i}": jnp.ones((8, 8), jnp.float32) for i in range(leaves)}
    state = StepState(B, M, window=n_tables == 2)
    for b in range(B):
        state.place(b, seq_len=int(rng.integers(40, 900)), token=7,
                    steps=3, seed=b, temperature=0.8, top_k=0, top_p=0.95)
        for t in state.table_views():
            t[b, :60] = rng.integers(1, 4000, 60)
    h = {name: getattr(state, name) for name in llama.ROW_FIELDS}
    positions = np.maximum(h["seq_lens"] - 1, 0).astype(np.int32)
    tables = [np.ascontiguousarray(t) for t in state.table_views()]

    def nine():
        tabs = tuple(jnp.asarray(t) for t in tables)
        return (jnp.asarray(h["tokens"]), jnp.asarray(positions),
                tabs if n_tables == 2 else tabs[0],
                jnp.asarray(h["seq_lens"]), jnp.asarray(h["seeds"]),
                jnp.asarray(h["steps"]), jnp.asarray(h["temps"]),
                jnp.asarray(h["top_ks"]), jnp.asarray(h["top_ps"]))

    host9 = (h["tokens"], positions, tuple(tables), h["seq_lens"],
             h["seeds"], h["steps"], h["temps"], h["top_ks"], h["top_ps"])
    block = jax.block_until_ready
    out = {"B": B, "M": M, "tables": n_tables,
           "matrix_bytes": int(state.host.nbytes)}
    out["asarray_x9"] = median_ms(nine, reps, block)
    out["device_put_9"] = median_ms(
        lambda: jax.device_put(host9), reps, block)
    out["device_put_1"] = median_ms(
        lambda: jax.device_put(state.host.copy()), reps, block)

    caches = [jnp.zeros((4, 1024), jnp.bfloat16) for _ in range(2)]
    dev9 = block(nine())

    def call_parent(args):
        toks, caches[0], caches[1] = parent_step(params, *args, *caches)
        return toks

    block(call_parent(dev9))
    out["call"] = median_ms(lambda: call_parent(dev9), reps, block)
    out["parent"] = median_ms(lambda: call_parent(nine()), reps, block)

    dev = {"rows": block(jax.device_put(state.host.copy()))}
    empty = block(jax.device_put(state.pack_delta([])))
    cells = [(b, state.table_column(0, 61), 4001 + b) for b in range(4)]

    def call_resident(rows, delta):
        toks, caches[0], caches[1], dev["rows"] = resident_step(
            params, rows, delta, *caches, n_tables=n_tables)
        return toks

    block(call_resident(dev["rows"], empty))
    out["steady"] = median_ms(
        lambda: call_resident(dev["rows"], empty), reps, block)
    out["delta"] = median_ms(
        lambda: call_resident(
            dev["rows"], jax.device_put(state.pack_delta(cells))),
        reps, block)
    out["resync"] = median_ms(
        lambda: call_resident(jax.device_put(state.host.copy()), empty),
        reps, block)
    out["handovers_share_of_parent"] = out["asarray_x9"] / out["parent"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=300)
    ap.add_argument("--leaves", type=int, default=192)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    result = {"device": f"{dev.platform} {dev.device_kind}",
              "delta_cells": DELTA_CELLS, "leaves": args.leaves,
              "reps": args.reps, "cells": {}}
    cols = ["asarray_x9", "device_put_9", "device_put_1", "call", "parent",
            "steady", "delta", "resync"]
    print(f"device: {result['device']}; host ms until the call returns, "
          f"median of {args.reps}")
    print(f"{'cell':20s} {'B':>3s} {'M':>5s} " + " ".join(
        f"{c:>12s}" for c in cols))
    done = {}
    for name, B, M, n_tables in CELLS:
        if (B, M, n_tables) not in done:
            done[B, M, n_tables] = bench_cell(
                B, M, n_tables, args.leaves, args.reps)
        r = result["cells"][name] = done[B, M, n_tables]
        print(f"{name:20s} {B:3d} {M * n_tables:5d} " + " ".join(
            f"{r[c]:12.4f}" for c in cols))
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           "bench_dispatch_thunk.json"), "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
