#!/usr/bin/env python3
"""A builder's tool, not run by the driver: sweep fixed arrival rates for
one open-loop cell with ONE server, to find the knee once.

    python3 chipbench/find_knee.py --workload olmo2-1b.chat --rates 4,6,8,10,12 --seconds 20

For each rate it runs the cell's own traffic (lead-in, then a window) and
prints output tokens/s received in the window, the requests in flight at
the window's middle and end, and the TTFT / TPOT percentiles. The knee is
the highest rate at which tokens/s still rose by more than 5 % over the
previous rate and no more requests were in flight at the window's end
than at its middle; the cell's ``rate_rps`` is 0.8 of it, written by hand
into ``chipbench/cells/<cell>.json`` with the table in ``PERF.md``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import generators, run  # noqa: E402
from chipbench.client import N_RESERVED, Server, say  # noqa: E402


def in_flight(records, t):
    return sum(1 for r in records if r.res.sent_at is not None
               and r.res.sent_at <= t and (r.res.done_at or 1e18) > t)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = run.Cell(args.workload, args.rehearse)
    os.makedirs(run.WORK, exist_ok=True)
    model_dir = run.prepare_model_dir(cell)
    vocab_words = cell.model_config["vocab_size"] - N_RESERVED
    gen = generators.load(cell.mix["generator"])
    rows = []
    with Server(run.REPO, model_dir, cell.flags,
                os.path.join(run.WORK, "server_find_knee.log"),
                run.child_env(args.rehearse)) as srv:
        say(f"server ready after {srv.start_s:.1f} s")
        asyncio.run(run.warm_mixed(srv, cell, vocab_words))
        for rate in (float(x) for x in args.rates.split(",")):
            cell.params["rate_rps"] = rate
            reqs = gen.generate(cell.mix, cell.params, args.seconds,
                                vocab_words, args.seed, srv.model_name)
            records, t0, box, m1, _ = asyncio.run(
                run.run_window(srv, cell, reqs, args.seconds, False))
            window = [r for r in records if r.req.due_s >= 0]
            seen = run.client_latencies(window)
            toks = sum(1 for r in records for t in r.res.token_times
                       if t0 <= t < t0 + args.seconds)
            compiled = (m1["engine_xla_compiles_total"]
                        - box["m0"]["engine_xla_compiles_total"])
            row = {
                "rate_rps": rate, "attempted": len(window),
                "failed": sum(not r.ok for r in window),
                "tokens_per_s": toks / args.seconds,
                "in_flight_mid": in_flight(records, t0 + args.seconds / 2),
                "in_flight_end": in_flight(records, t0 + args.seconds),
                **seen,
                "compiled_in_window": compiled,
            }
            rows.append(row)
            say("SWEEP " + json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
