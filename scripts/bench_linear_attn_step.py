#!/usr/bin/env python3
"""The recurrent-state kernel alone, on the chip: what a layer-call of
``linear_attn_recurrent_step`` costs against the bytes its LIVE rows hold.

    chiprun -- python3 scripts/bench_linear_attn_step.py
    chiprun -- python3 scripts/bench_linear_attn_step.py --parent .archive_check/parent

The kernel walks the live decode slots (PR 52), so its price should
follow the live rows and not the slots. At ``gigachat35.reason``'s widths
(32 slots, 64 value heads of 128 x 128 float32, a 4-layer state of 512
MiB) it times loads from a full batch to an empty one, the live rows
spread over the slots with dead ones between them, each as

* ms a call: ``--calls`` layer-calls chained inside ONE program (a decode
  step's pattern: each call moves another layer of the state, donated
  from call to call, and feeds the next one's query; the host's dispatch
  is not in the number);
* the share of the bytes-touched floor: what the live rows' matrices and
  row operands weigh (``chipbench/kernel_work.py`` handed the LIVE rows)
  / the chip's HBM bandwidth (``chipbench/peaks.json``). An empty batch
  has no floor.

``--parent DIR`` (a ``git archive`` of another commit unpacked in DIR;
may be given more than once) runs that checkout's kernel on the same
inputs (dead rows carry ``g = 0, beta = 0``, which is what a kernel
without ``n`` needs): its ms a call and max |difference| over the live
rows' ``o`` and a sample of the state that holds live and dead slots
(every 7th slot's every 9th head; 0.0 = bit-identical). Results also go to
``chiprun_out/bench_linear_attn_step.json``. Needs a TPU: on another
backend it stops.
"""

from __future__ import annotations

import argparse
import importlib.util
import inspect
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

from chipbench import kernel_work  # noqa: E402
from dynamo_tpu.ops import gated_delta_pallas as this_kernel  # noqa: E402

B, HV, DK, DV, LAYERS = 32, 64, 128, 128, 4
KERNEL_FILE = os.path.join("dynamo_tpu", "ops", "gated_delta_pallas.py")
LOADS = (32, 20, 13, 4, 0)


def load_kernel(checkout: str):
    """``linear_attn_recurrent_step`` of another checkout, from its file."""
    spec = importlib.util.spec_from_file_location(
        "parent_gated_delta_pallas", os.path.join(checkout, KERNEL_FILE))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.linear_attn_recurrent_step


def chained(kernel, calls: int):
    """One program of ``calls`` layer-calls, as a decode step makes them.
    Returns (the last call's o, the state)."""
    takes_n = "n" in inspect.signature(kernel).parameters

    def run(q, k, v, g, beta, rec, n):
        def call(i, q, rec):
            extra = (n,) if takes_n else ()
            return kernel(q, k, v, g, beta, rec, i % LAYERS, *extra)

        def body(i, carry):
            q, rec = carry
            o, rec = call(i, q, rec)
            return q + o[..., :DK] * 1e-3, rec

        q, rec = lax.fori_loop(0, calls - 1, body, (q, rec))
        return call(calls - 1, q, rec)

    return jax.jit(run, donate_argnums=(5,))


def timed(fn, rec, *args, reps: int):
    """(ms a program, the state): ``reps`` programs enqueued back to back,
    the state handed from one to the next, one wait."""
    q, k, v, g, beta, n = args
    for _ in range(2):
        o, rec = fn(q, k, v, g, beta, rec, n)
    jax.block_until_ready(o)
    t = time.perf_counter()
    for _ in range(reps):
        o, rec = fn(q, k, v, g, beta, rec, n)
    jax.block_until_ready(o)
    return (time.perf_counter() - t) * 1e3 / reps, rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", action="append", default=[],
                    help="a checkout whose kernel runs on the same inputs")
    ap.add_argument("--calls", type=int, default=16,
                    help="layer-calls chained in one program")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_linear_attn_step: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 2
    with open(os.path.join(REPO, "chipbench", "peaks.json")) as f:
        bw = float(json.load(f)[dev.device_kind]["hbm_bytes_per_s"])
    kernels = {"this": this_kernel.linear_attn_recurrent_step}
    for d in args.parent:
        kernels[os.path.basename(os.path.normpath(d))] = load_kernel(d)
    fns = {name: chained(fn, args.calls) for name, fn in kernels.items()}
    ks = jax.random.split(jax.random.key(52), 6)
    q = jax.random.normal(ks[0], (B, HV, DK)) * 0.1
    k = jax.random.normal(ks[1], (B, HV, DK)) * 0.1
    v = jax.random.normal(ks[2], (B, HV, DV))
    g_all = -jax.random.uniform(ks[3], (B, HV), minval=0.01, maxval=0.5)
    beta_all = jax.nn.sigmoid(jax.random.normal(ks[4], (B, HV)))
    shape = (LAYERS, B, HV, DK, DV)
    print(f"device {dev.device_kind}; {B} slots x {HV} heads of [{DK}, {DV}] "
          f"float32, {LAYERS} layers ({4 * np.prod(shape) >> 20} MiB); "
          f"{this_kernel.HEADS_PER_STEP} heads a grid step; HBM "
          f"{bw / 1e9:.0f} GB/s; {args.calls} layer-calls a program",
          flush=True)
    rows_out = []
    for live in LOADS:
        alive = np.zeros(B, bool)
        # live rows spread over the slots, dead ones between them
        alive[np.linspace(0, B - 1, live).round().astype(int)] = True
        n = jnp.asarray(alive.astype(np.int32))
        g = jnp.where(n[:, None] > 0, g_all, 0.0)
        beta = jnp.where(n[:, None] > 0, beta_all, 0.0)
        floor_ms = kernel_work.linear_attn_recurrent_step_bytes(
            live, HV, DK, DV) / bw * 1e3
        row = {"live": live, "floor_ms": floor_ms}
        outs = {}
        for name, fn in fns.items():
            # the same start for every kernel, made on the chip
            rec = jax.random.normal(ks[5], shape)
            o, rec = fn(q, k, v, g, beta, rec, n)
            outs[name] = (np.asarray(o)[alive], np.asarray(rec[:, ::7, ::9]))
            ms, rec = timed(fn, rec, q, k, v, g, beta, n, reps=args.reps)
            row[f"{name}_ms"] = ms / args.calls
            del rec  # 512 MiB a kernel: free it before the next one's
        share = (f"{100 * floor_ms / row['this_ms']:5.1f} % of the floor"
                 if live else "no floor")
        line = (f"  {live:2d} live rows: floor {floor_ms:6.4f} ms | this "
                f"{row['this_ms']:7.4f} ms ({share})")
        for name in list(fns)[1:]:
            row[f"{name}_max_abs_diff"] = max(
                float(np.abs(a - b).max()) if a.size else 0.0
                for a, b in zip(outs["this"], outs[name]))
            line += (f" | {name} {row[f'{name}_ms']:7.4f} ms, x "
                     f"{row[f'{name}_ms'] / row['this_ms']:5.2f}, max |diff| "
                     f"{row[f'{name}_max_abs_diff']}")
        rows_out.append(row)
        print(line, flush=True)
    out = os.path.join(REPO, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "bench_linear_attn_step.json"), "w") as f:
        json.dump({"device": dev.device_kind, "calls": args.calls,
                   "heads_per_step": this_kernel.HEADS_PER_STEP,
                   "rows": rows_out}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
