#!/usr/bin/env python3
"""The sampler's top-k / nucleus filter alone, on the chip: what finding
the two cuts of a decode batch costs at the served vocabularies.

    chiprun -- python3 scripts/bench_sampler.py
    chiprun -- python3 scripts/bench_sampler.py --rows 32 --vocabs 100352

For each vocabulary (``olmoe-1b-7b`` 50,304, ``lfm2-8b-a1b`` 65,536,
``olmo2-1b`` 100,352) and each request mix (``nucleus``: every row asks
for ``top_p`` 0.95, the ``chat`` traffic; ``nucleus+top_k``: also
``top_k`` 50) it times, in ms a call over ``[rows, V]`` float32 logits:

* ``sort``: the division by the temperature and the filter as it was, a
  full descending sort, a softmax and a ``cumsum`` over the sorted rows
  (``tests/test_sampling.py``'s oracle);
* ``search``: ``ops/sampling.filtered_dist``, the same division and the
  threshold search;
* ``sample``: ``sample_tokens`` whole (division, search, the Gumbel draw
  and the argmax), what a step program's tail pays;

against the floor of ONE read of ``[rows, V]`` float32 at the chip's HBM
bandwidth (``chipbench/peaks.json``). Both forms sum float32 masses in
their own order, so at a nucleus's edge (a token of mass 1e-5 or less at
these sizes) they may round apart: the script counts the rows whose
support differs between the two and holds each to a float64 reference,
a row passing if it keeps what the reference keeps for some ``top_p``
within ``--p-slack`` of the one asked. It exits 1 if a ``search`` row
does not. Results also go to ``chiprun_out/bench_sampler.json``. Needs a
TPU: on another backend it stops.
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

from dynamo_tpu.ops import sampling  # noqa: E402
from tests.test_sampling import sort_topk_topp  # noqa: E402


def timed(fn, temps, *args, reps: int) -> float:
    """ms a call of ``fn(temps, *args) -> [B]``: ``reps`` calls in ONE
    dispatch (a dispatch alone is 0.2 ms here, more than a search), each
    call's temperatures waiting on the call before."""

    @jax.jit
    def many(temps, *args):
        def one(wait, _):
            out = fn(temps + wait, *args).astype(jnp.float32)
            return out * 0.0, None  # a float product XLA may not fold

        return jax.lax.scan(one, jnp.zeros_like(temps), None, length=reps)[0]

    jax.block_until_ready(many(temps, *args))
    t = time.perf_counter()
    jax.block_until_ready(many(temps, *args))
    return (time.perf_counter() - t) * 1e3 / reps


def rows_outside(x: np.ndarray, kept: np.ndarray, top_k: int, top_p: float,
                 slack: float) -> int:
    """Rows whose kept count is not the float64 filter's for any nucleus
    between ``top_p - slack`` and ``top_p + slack`` (supports nest, so the
    count names the support)."""
    bad = 0
    for row, n in zip(x.astype(np.float64), kept):
        desc = np.sort(row)[::-1]
        mass = np.exp(desc - desc[0])
        before = (np.cumsum(mass) - mass) / mass.sum()
        lo, hi = (desc[before < p].min() for p in (top_p + slack,
                                                   top_p - slack))
        if top_k:
            lo, hi = (max(v, desc[min(top_k, len(desc)) - 1])
                      for v in (lo, hi))
        bad += not (row >= hi).sum() <= n <= (row >= lo).sum()
    return bad


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--vocabs", default="50304,65536,100352")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--p-slack", type=float, default=1e-5)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_sampler: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        bw = float(json.load(f)[dev.device_kind]["hbm_bytes_per_s"])
    B = args.rows
    kept = lambda masked: (masked > sampling.NEG_INF / 2).sum(-1)  # noqa: E731
    fns = {
        "sort": lambda t, x, k, p, _keys: kept(sort_topk_topp(
            x / jnp.maximum(t, 1e-6)[:, None], k, p)),
        "search": lambda t, x, k, p, _keys: kept(
            sampling.filtered_dist(x, t, k, p)),
        "sample": lambda t, x, k, p, keys: sampling.sample_tokens(
            x, keys, t, k, p),
    }
    print(f"device {dev.device_kind}; {B} rows; HBM {bw / 1e9:.0f} GB/s; "
          f"floor = one read of [rows, V] float32", flush=True)
    rows_out = []
    for V in (int(v) for v in args.vocabs.split(",")):
        floor_ms = B * V * 4 / bw * 1e3
        # logits of a served model's spread; the chat mix's temperature
        x = jax.random.normal(jax.random.key(V), (B, V), jnp.float32) * 2.5
        keys = sampling.make_keys(jnp.arange(B), jnp.zeros(B, jnp.int32))
        temps = jnp.full(B, 0.7, jnp.float32)
        for mix, k in (("nucleus", 0), ("nucleus+top_k", 50)):
            top_k = jnp.full(B, k, jnp.int32)
            top_p = jnp.full(B, 0.95, jnp.float32)
            ms = {n: timed(f, temps, x, top_k, top_p, keys, reps=args.reps)
                  for n, f in fns.items()}
            scaled = x / temps[:, None]
            kept_by = {
                "sort": np.asarray(jax.jit(sort_topk_topp)(
                    scaled, top_k, top_p)) > sampling.NEG_INF / 2,
                "search": np.asarray(jax.jit(sampling.filtered_dist)(
                    x, temps, top_k, top_p)) > sampling.NEG_INF / 2,
            }
            differ = int(
                (kept_by["sort"] != kept_by["search"]).any(-1).sum())
            outside = {n: rows_outside(np.asarray(scaled), m.sum(-1), k,
                                       0.95, args.p_slack)
                       for n, m in kept_by.items()}
            rows_out.append({"rows": B, "vocab": V, "mix": mix, "ms": ms,
                             "floor_ms": floor_ms, "rows_differing": differ,
                             "rows_outside_float64": outside,
                             "kept_mean": float(
                                 kept_by["search"].sum(-1).mean())})
            print(f"V {V:6d} {mix:13s} floor {floor_ms:.4f} ms | "
                  + " ".join(f"{n} {v:7.4f}" for n, v in ms.items())
                  + f" | search = {ms['search'] / floor_ms:.1f} reads, "
                  f"sort / search {ms['sort'] / ms['search']:.1f} x; kept "
                  f"{rows_out[-1]['kept_mean']:.0f} a row; rows differing "
                  f"{differ}, outside float64's: sort {outside['sort']} "
                  f"search {outside['search']}", flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_sampler.json"), "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows_out}, f, indent=1)
    return int(any(r["rows_outside_float64"]["search"] for r in rows_out))


if __name__ == "__main__":
    sys.exit(main())
