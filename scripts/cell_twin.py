#!/usr/bin/env python3
"""A builder's tool: how far does the ORDER of a cell's requests alone move
its ``tpot_mean_ms``? A twin of the engine's loop, run on the generator's
own requests over many seeds, on the CPU, before chip time is spent on
rates (PR 28 and PR 43 were each refused once for a new cell's spread).

    python3 scripts/cell_twin.py --cell gigachat35.reason \\
        --fit chiprun_out/pr43/call3/requests_77777.json \\
        --rates 1.05,1.2,1.5 --leads 25,40 --seeds 200

The loop it plays: while a prompt waits and a slot is free, one mixed step
a chunk (each live stream gains one token; the prompt's first token comes
with its last chunk); otherwise a decode window of ``--window`` steps. A
decode step costs ``a + b L`` ms at L live rows, a mixed step
``c + d n + b L`` for a chunk of n tokens (``--tail-ms`` for a tail under a
block that follows a snapshot, which only a recurrent state has). The
metric is ``chipbench.stats.tpot_mean_ms`` over the streams due in the
window, cut at the drain as ``run.py`` cuts them.

``--fit`` reads a run's ``chipbench/work/requests_<cell>.json`` (``run.py``
writes it: each request's chunk times) and prints ``a`` and ``b`` fitted on
its decode windows and the mixed steps' times by kind; the defaults are
PR 43's reading of ``gigachat35.reason`` (``PERF.md`` section 6). What the
twin has not: run-to-run noise, a host that stands still, the experts a
step touches beyond one slope. On PR 43's nine seeds it read an sd of
1.0 % where the chip read 1.25 %, and put two seeds 0.46 ms apart that
read 0.55 apart. It needs no JAX.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import stats  # noqa: E402
from chipbench.generators import load  # noqa: E402


def dispatches(requests: list) -> list:
    """[(time_ms, ms since the last, streams, most tokens a stream
    got, first tokens among them)]: chunk times within 4 ms are one
    dispatch's."""
    reqs = [r for r in requests if r["chunks"]]
    first = {i: r["chunks"][0][0] for i, r in enumerate(reqs)}
    events = sorted((c[0], i, c[1]) for i, r in enumerate(reqs)
                    for c in r["chunks"])
    groups, cur = [], [events[0]]
    for e in events[1:]:
        if e[0] - cur[-1][0] < 4.0:
            cur.append(e)
        else:
            groups.append(cur)
            cur = [e]
    groups.append(cur)
    out, prev = [], None
    for g in groups:
        per = collections.Counter()
        for _t, i, k in g:
            per[i] += k
        t = float(np.mean([e[0] for e in g]))
        if prev is not None:
            out.append((t, t - prev, len(per), max(per.values()),
                        sum(first[i] >= g[0][0] for i in per)))
        prev = t
    return out


def fit(path: str, window: int) -> None:
    """Print the decode step's ``a + b L`` and the mixed steps' times."""
    rows = np.array(dispatches(json.load(open(path))["requests"]))
    seconds = json.load(open(path))["seconds"] * 1e3
    inside = (rows[:, 0] > 0) & (rows[:, 0] < seconds)
    dec = inside & (rows[:, 3] == window) & (rows[:, 4] == 0) \
        & (rows[:, 1] < 20 * window)
    a, b = np.linalg.lstsq(
        np.c_[np.ones(dec.sum()), rows[dec, 2]], rows[dec, 1] / window,
        rcond=None)[0]
    print(f"{path}: {dec.sum()} decode windows of {window}: "
          f"a {a:.2f} ms, b {b:.3f} ms a live row")
    one = inside & (rows[:, 3] == 1)
    for name, sel in (("with a first token (a prompt's last chunk)",
                       one & (rows[:, 4] > 0)),
                      ("without (an earlier chunk, or a cut window)",
                       one & (rows[:, 4] == 0))):
        if sel.sum():
            q = np.percentile(rows[sel, 1], [10, 50, 90])
            print(f"  one-token dispatches {name}: {sel.sum()}, "
                  f"ms p10 / p50 / p90 {q[0]:.1f} / {q[1]:.1f} / {q[2]:.1f}")
    gaps = rows[inside, 1]
    print(f"  longest gap between dispatches in the window "
          f"{gaps.max():.0f} ms (a host that stood still shows here)")


def chunks_of(prompt: int, o) -> list:
    """The prefill chunks of a prompt behind the restored shared prefix."""
    end = prompt // o.block * o.block if o.tail_ms else prompt
    out, at = [], o.restored
    while at < end:
        out.append(min(o.budget, end - at))
        at += out[-1]
    if prompt > end:
        out.append(prompt - end)
    return out or [1]


def mixed_ms(n: int, live: int, o) -> float:
    if o.tail_ms and n < o.block:
        return o.tail_ms + o.b * live
    return o.c + o.d * n + o.b * live


def run(seed: int, mix: dict, rate: float, seconds: float, o) -> float:
    """One seed's ``tpot_mean_ms`` in the twin."""
    gen = load(mix["generator"])
    reqs = sorted(gen.generate(mix, {"rate_rps": rate}, seconds, 1000,
                               seed, "m"), key=lambda r: r.due_s)
    t = -float(mix["lead_s"]) * 1e3
    end = (reqs[-1].due_s + float(mix.get("drain_s", 30))) * 1e3
    nxt, queue, live, pre, ended = 0, [], [], None, []
    while t < end:
        while nxt < len(reqs) and reqs[nxt].due_s * 1e3 <= t:
            queue.append(reqs[nxt])
            nxt += 1
        if pre is None and queue and len(live) < o.slots:
            r = queue.pop(0)
            pre = [r, chunks_of(r.prompt_tokens, o)]
        if pre is not None:
            t += mixed_ms(pre[1].pop(0), len(live), o)
            for s in live:
                s[1].append(t)
            if not pre[1]:  # the prompt's last chunk gives its first token
                live.append([pre[0], [t]])
                pre = None
        elif live:
            t += o.window * (o.a + o.b * len(live))
            for s in live:
                s[1].extend([t] * o.window)
        else:
            t = reqs[nxt].due_s * 1e3 if nxt < len(reqs) else end
            continue
        for s in [s for s in live if len(s[1]) >= s[0].max_tokens]:
            del s[1][s[0].max_tokens:]
            live.remove(s)
            ended.append(s)
    streams = [[x / 1e3 for x in times] for r, times in ended + live
               if r.due_s >= 0]
    return stats.tpot_mean_ms(streams)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="gigachat35.reason")
    ap.add_argument("--fit", action="append", default=[],
                    help="a requests_<cell>.json to fit a, b on (repeatable)")
    ap.add_argument("--rates", default="",
                    help="comma-separated req/s (default: the cell's own)")
    ap.add_argument("--leads", default="",
                    help="comma-separated lead_s (default: the mix's own)")
    ap.add_argument("--seeds", type=int, default=100)
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--a", type=float, default=11.58, help="decode step, ms")
    ap.add_argument("--b", type=float, default=0.089, help="ms a live row")
    ap.add_argument("--c", type=float, default=29.0, help="mixed step, ms")
    ap.add_argument("--d", type=float, default=0.075, help="ms a chunk token")
    ap.add_argument("--tail-ms", type=float, default=30.0,
                    help="a tail chunk under a block behind a snapshot; 0: "
                         "prompts are not cut at their last full block")
    ap.add_argument("--window", type=int, default=4)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--budget", type=int, default=512)
    ap.add_argument("--block", type=int, default=16)
    ap.add_argument("--restored", type=int, default=64,
                    help="prompt tokens a prefix hit restores")
    o = ap.parse_args()
    for path in o.fit:
        fit(path, o.window)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == o.cell)
    mix = json.load(open(os.path.join(
        ROOT, "chipbench", "traffic", cell["traffic"] + ".json")))
    own = json.load(open(os.path.join(
        ROOT, "chipbench", "cells", o.cell + ".json")))
    seconds = o.seconds or float(bench["run_seconds"])
    rates = [float(x) for x in o.rates.split(",") if x] or [own["rate_rps"]]
    leads = [float(x) for x in o.leads.split(",") if x] or [mix["lead_s"]]
    for lead in leads:
        for rate in rates:
            v = [run(1000 + 7919 * k, dict(mix, lead_s=lead), rate, seconds,
                     o) for k in range(o.seeds)]
            print(f"{o.cell} at {rate} req/s, lead {lead:g} s, {o.seeds} "
                  f"seeds: tpot_mean_ms mean {statistics.mean(v):.3f}, sd "
                  f"{100 * statistics.stdev(v) / statistics.mean(v):.2f} %, "
                  f"quartile spread {100 * stats.spread(v):.2f} %")


if __name__ == "__main__":
    main()
