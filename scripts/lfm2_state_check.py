#!/usr/bin/env python3
"""A builder's tool: the conv layers' state through the SERVER, against
the plain reference, beyond what the benchmark's own check asks.

    chiprun -- python3 scripts/lfm2_state_check.py [--rehearse]

One ``dynamo_run in=http out=jax --trace`` child serves
``chipbench/configs/lfm2-8b-a1b`` (its ``serve.json`` flags; seeded
weights), and the server's logprobs are scored by the configuration's
reference exactly as ``chipbench/run.py`` scores them (``ReferenceCheck``
with prompts of its own), for

  * one prompt asked twice: the second answer starts from a prefix hit
    of whole blocks, so its conv state comes from a snapshot (of the
    pair's ``engine.prefill`` spans one must say ``restored`` 0 and the
    other more);
  * one prompt of more than 1,024 tokens sent while other streams
    decode: it is admitted through fused mixed steps of <= 512 tokens,
    so its state crosses chunk ends beside decoding rows.

Prints the largest |logprob difference| of each and exits non-zero if
one is over ``reference.json``'s tolerance. This parent imports no JAX.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import generators, run  # noqa: E402
from chipbench.client import (  # noqa: E402
    N_RESERVED, Server, http_json, say, stream_request,
)

CELL = "lfm2-8b-a1b.chat"


class Probe(run.ReferenceCheck):
    """``ReferenceCheck`` over this tool's prompts (scored uncached)."""

    def __init__(self, cell, vocab_words, prompts):
        super().__init__(cell, vocab_words)
        self.prompts, self.cache = prompts, {}
        self.cache_path = os.path.join(run.WORK, "lfm2_state_check.json")
        if self.child is None:
            self._start_child()


def prefill_spans(srv: Server) -> list:
    """(prompt tokens, restored) of every ``engine.prefill`` span the
    collector holds."""
    out = []
    for tid in http_json(f"{srv.base}/trace", timeout=30)["traces"]:
        body = http_json(f"{srv.base}/trace/{tid}", timeout=30)
        for s in body.get("spans") or []:
            if s["name"] == "engine.prefill":
                a = s.get("attrs") or s
                out.append((a.get("prompt_tokens"), a.get("restored")))
    return out


async def busy_then(srv: Server, vocab_words: int, n: int, thunk):
    """``n`` long greedy streams decode while ``thunk`` runs."""
    rng = np.random.default_rng(99)
    mix = {"endpoint": "completions", "sampling": {"temperature": 0}}
    started, tasks = [], []
    for i in range(n):
        path, body = generators.request_body(
            mix, srv.model_name, generators.words(rng, 40, vocab_words),
            600, 0)
        ev = asyncio.Event()
        started.append(ev)
        tasks.append(asyncio.create_task(stream_request(
            srv.port, path, body, f"busy-{i}", 600, first=ev)))
    try:
        for ev in started:
            await asyncio.wait_for(ev.wait(), 600)
        return await asyncio.get_running_loop().run_in_executor(None, thunk)
    finally:
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = run.Cell(CELL, args.rehearse)
    os.makedirs(run.WORK, exist_ok=True)
    model_dir = run.prepare_model_dir(cell)
    run.build_native_hasher()
    vocab_words = cell.model_config["vocab_size"] - N_RESERVED
    rng = np.random.default_rng(33)
    twice = generators.words(rng, 200, vocab_words)
    long_one = generators.words(rng, 1300, vocab_words)
    probe = Probe(cell, vocab_words, [twice, twice, long_one])
    ref = probe.ref
    try:
        with Server(REPO, model_dir, cell.flags + ["--trace"],
                    os.path.join(run.WORK, "server_lfm2_state_check.log"),
                    run.child_env(args.rehearse)) as srv:
            say(f"server ready after {srv.start_s:.1f} s")
            asyncio.run(run.warm_mixed(srv, cell, vocab_words))
            m0 = srv.metrics()
            answers = [run.ask_logprobs(srv, twice, ref),
                       run.ask_logprobs(srv, twice, ref)]
            m1 = srv.metrics()
            answers.append(asyncio.run(busy_then(
                srv, vocab_words, 6,
                lambda: run.ask_logprobs(srv, long_one, ref))))
            m2 = srv.metrics()
            spans = prefill_spans(srv)
            # score each answer alone: ReferenceCheck.check asks again,
            # so hand it the answers we hold
            names = ("cold", "prefix hit", "long prompt in mixed steps")
            todo = {}
            for p, a in zip(probe.prompts, answers):
                todo[probe._key(p, a["tokens"]) + str(len(todo))] = {
                    "prompt": [run.token_id(w) for w in p.split()],
                    "prompt_tokens": a["prompt_tokens"],
                    "tokens": a["tokens"],
                    "candidates": {k: sorted(v)
                                   for k, v in a["candidates"].items()},
                }
            out, _ = probe.child.communicate(json.dumps(todo) + "\n",
                                             timeout=3000)
            want = json.loads(out.strip().splitlines()[-1])
    finally:
        probe.close()
    worst_all = 0.0
    for name, (key, _item), a in zip(names, todo.items(), answers):
        worst = max(abs(got - want[key][pos][str(tid)])
                    for pos, cands in a["candidates"].items()
                    for tid, got in cands.items())
        worst_all = max(worst_all, worst)
        say(f"{name}: prompt of {a['prompt_tokens']} tokens, max |logprob "
            f"diff| {worst:.4f} (tolerance {ref['tolerance']})")
    pair = [r for n, r in spans if n == answers[0]["prompt_tokens"]]
    say(f"engine.prefill (prompt tokens, restored): {spans[-12:]}")
    hits = "engine_prefix_cache_hits_tokens"
    mixed = m2["engine_mixed_steps"] - m1["engine_mixed_steps"]
    say(f"prefix-cache hit tokens: the pair +{m1[hits] - m0[hits]:.0f}, "
        f"the long prompt +{m2[hits] - m1[hits]:.0f}; mixed steps in the "
        f"long prompt's phase +{mixed:.0f}; "
        f"state restores {m2.get('engine_state_restores_total')}, "
        f"snapshots {m2.get('engine_state_snapshots_total')}")
    ok = (worst_all <= ref["tolerance"]
          and sorted(bool(r) for r in pair) == [False, True]
          and mixed >= 3)
    say("OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
