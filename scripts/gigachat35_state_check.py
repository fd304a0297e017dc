#!/usr/bin/env python3
"""A builder's tool: GigaChat 3.5's two-part state through the SERVER at
the TIMED sizes, against the plain reference, beyond what the benchmark's
own check asks (four prompts of 48 tokens and 9 answer tokens never cross
a 64-token block of the chunked form, a 512-token chunk or a snapshot).

    chiprun --timeout 3000 -- python3 scripts/gigachat35_state_check.py [--rehearse]

One ``dynamo_run in=http out=jax --trace`` child serves
``chipbench/configs/gigachat3.5-432b-a28b`` (its ``serve.json`` flags;
seeded weights). Behind one 64-word shared prefix, while six other streams
decode (so that every chunk rides a mixed step of <= 512 tokens beside
decode rows), it asks, each for 64 greedy tokens with logprobs:

  * ``cold``: a prompt of about 1,000 words whose prefix blocks are
    committed but hold no snapshot yet: the hit is cut to nothing, the
    prompt is computed from token 0 in chunks, and its chunk that ends at
    the prefix leaves the snapshot (the second asker pays);
  * ``restored at the prefix``: another such prompt: it starts from the
    snapshot at 64 tokens;
  * ``restored at the prompt's end``: the first prompt again: it starts
    from the snapshot at its last full block.

The server's logprobs at the first and the last of the 64 positions are
scored by the configuration's reference over the whole sequence (the
child below: ``chipbench/reference.py``'s scoring, on the weights as
served) and, to show what the tolerance catches, by the same reference on
weights put through an int8 round trip (every matrix of every layer and
the head, symmetric per output channel), which has to come out OVER it.
Prints the readings and exits non-zero if a served answer is over the
tolerance or the int8 one is not. This parent imports no JAX.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

CELL = "gigachat35.reason"
ANSWER_TOKENS = 64
#: |logprob difference| allowed at the two positions. reference.json's
#: 0.08 is twice what bf16 activations read against the float32 reference
#: over 57 tokens of a 1.5 B model; here 1,130 tokens pass 4 recurrent
#: layers whose state is carried in float32 but FED bf16 projections, so
#: the served answer may stand further out. Set between the two readings
#: of PERF.md section 6 (PR 43): the largest served one and the int8 one.
TOLERANCE = 0.08


# ---------------- the scoring child (JAX on the CPU) ----------------


def child(config_dir: str, reference_path: str, int8: bool) -> int:
    import jax

    from chipbench import reference
    from chipbench.check_tolerance import int8_round_trip
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = reference.load_forward(reference_path)
    with open(os.path.join(config_dir, "config.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig.from_local_path(config_dir)
    params = llama.init_params(cfg, jax.random.key(0))
    if int8:
        matrices = ("w", "lin_qkvz", "lin_ba", "lin_out", "attn_gate",
                    "shared_", "moe_gate")
        params = {
            group: ({k: int8_round_trip(v) if v.ndim >= 3 and k.startswith(
                matrices) and k != "moe_gate_bias" else v
                for k, v in tree.items()} if isinstance(tree, dict) else tree)
            for group, tree in params.items()}
        params["lm_head"] = int8_round_trip(params["lm_head"])
    jax.block_until_ready(params)
    print("scoring child: weights built", file=sys.stderr, flush=True)
    todo = json.loads(sys.stdin.readline())
    out = {k: reference.score(forward, params, hf, item)
           for k, item in todo.items()}
    print(json.dumps(out), flush=True)
    return 0


# ---------------- the parent ----------------


def start_child(cell, env, int8: bool, log: str):
    env = dict(env, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--config-dir", cell.config_dir, "--reference", cell.reference_path]
        + (["--int8"] if int8 else []),
        cwd=REPO, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=open(log, "wb"), text=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--config-dir")
    ap.add_argument("--reference")
    ap.add_argument("--words", type=int, default=1000)
    args = ap.parse_args()
    if args.child:
        return child(args.config_dir, args.reference, args.int8)

    from chipbench import generators, run
    from chipbench.client import (
        N_RESERVED, Server, http_json, say, stream_request,
    )
    from scripts.lfm2_state_check import busy_then, prefill_spans

    cell = run.Cell(CELL, args.rehearse)
    os.makedirs(run.WORK, exist_ok=True)
    model_dir = run.prepare_model_dir(cell)
    run.build_native_hasher()
    vocab_words = cell.model_config["vocab_size"] - N_RESERVED
    rng = np.random.default_rng(43)
    prefix = generators.words(rng, 64, vocab_words)
    first = prefix + " " + generators.words(rng, 30, vocab_words)
    a = prefix + " " + generators.words(rng, args.words, vocab_words)
    b = prefix + " " + generators.words(rng, args.words - 37, vocab_words)
    ref = dict(run.load_json(run.HERE, "reference.json"),
               answer_tokens=ANSWER_TOKENS)
    env = run.child_env(args.rehearse)
    scorer = start_child(cell, env, False,
                         os.path.join(run.WORK, "state_check_ref.log"))
    names = ("cold (pays for the prefix's snapshot)",
             "restored at the prefix", "restored at the prompt's end")
    try:
        with Server(REPO, model_dir, cell.flags + ["--trace"],
                    os.path.join(run.WORK, "server_gigachat35_state_check.log"),
                    env) as srv:
            say(f"server ready after {srv.start_s:.1f} s")
            asyncio.run(run.warm_mixed(srv, cell, vocab_words))
            run.ask_logprobs(srv, first, dict(ref, answer_tokens=2))
            m0 = srv.metrics()
            answers = asyncio.run(busy_then(
                srv, vocab_words, 6, lambda: [
                    run.ask_logprobs(srv, p, ref) for p in (a, b, a)]))
            m1 = srv.metrics()
            spans = prefill_spans(srv)
    except BaseException:
        scorer.kill()
        raise
    todo = {}
    for name, p, ans in zip(names, (a, b, a), answers):
        todo[name] = {
            "prompt": [run.token_id(w) for w in p.split()],
            "prompt_tokens": ans["prompt_tokens"], "tokens": ans["tokens"],
            "candidates": {k: sorted(v) for k, v in ans["candidates"].items()},
        }
    line = json.dumps(todo) + "\n"
    out, _ = scorer.communicate(line, timeout=3000)
    want = json.loads(out.strip().splitlines()[-1])
    out, _ = start_child(
        cell, env, True, os.path.join(run.WORK, "state_check_int8.log")
    ).communicate(line, timeout=3000)
    want8 = json.loads(out.strip().splitlines()[-1])

    def worst(scores, name, ans):
        return {pos: max(abs(got - scores[name][pos][str(tid)])
                         for tid, got in cands.items())
                for pos, cands in ans["candidates"].items()}

    served, quant = 0.0, 0.0
    for name, ans in zip(names, answers):
        w, w8 = worst(want, name, ans), worst(want8, name, ans)
        served = max(served, *w.values())
        quant = max(quant, *w8.values())
        say(f"{name}: prompt of {ans['prompt_tokens']} tokens, max |logprob "
            f"diff| at answer positions {sorted(w)}: served vs reference "
            f"{[round(w[k], 4) for k in sorted(w)]}, served vs the int8 "
            f"reference {[round(w8[k], 4) for k in sorted(w8)]}")
    delta = {k: m1.get(k, 0) - m0.get(k, 0) for k in (
        "engine_prefix_matched_tokens_total", "engine_prefix_cache_hits_tokens",
        "engine_prefix_unsnapshotted_tokens_total",
        "engine_state_restores_total", "engine_state_snapshots_total",
        "engine_mixed_steps")}
    say(f"engine.prefill (prompt tokens, restored): {spans[-8:]}")
    say(f"counters over the three asks (and the busy streams): {delta}")
    say(f"largest served reading {served:.4f}, int8 reading {quant:.4f}, "
        f"tolerance {TOLERANCE}")
    restored = [r for n, r in spans
                if n in {x["prompt_tokens"] for x in answers}]
    ok = (served <= TOLERANCE < quant
          and delta["engine_state_restores_total"] >= 2
          and delta["engine_prefix_unsnapshotted_tokens_total"] >= 64
          and delta["engine_mixed_steps"] >= 3
          and sum(bool(r) for r in restored) >= 2)
    say("OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
