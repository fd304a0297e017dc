#!/usr/bin/env python3
"""A builder's tool: Mellum 2's two KV pools through the ENGINE at the
TIMED sizes, against the plain reference, beyond what the benchmark's own
check asks (four prompts of 48 tokens and 9 answer tokens never cross the
window of 1,024 tokens and never reach YaRN's far range).

    chiprun --timeout 3000 -- python3 scripts/mellum2_window_check.py

One in-process ``JaxEngine`` serves ``chipbench/configs/mellum2-12b-a2.5b``
(its ``serve.json`` flags; seeded weights) through ``generate``, the
path every request takes behind the HTTP frontend. While another stream
decodes (so that every chunk rides a mixed step of <= 512 tokens beside a
decode row), one prompt P of about 3,000 tokens is asked, greedy, with
logprobs:

  * ``past 1,024`` / ``past 2,048``: P's first 1,100 / 2,100 tokens, one
    token of answer: the logprobs at a position one / two windows in, the
    first computed in chunks from nothing, the second on a prefix hit;
  * ``in chunks``: P itself, 64 tokens of answer (a prefix hit under the
    rule up to 2,096, the rest in chunks, then decode);
  * ``hit``: P again: a hit over all of it but the last tokens, across
    both pools;
  * ``poisoned``: P a third time, after EVERY window-pool block that no
    sequence holds and that the hit does not need (everything behind the
    window of P's cached tail) was overwritten with NaN, and with every
    block released behind a window from then on overwritten at once: the
    tokens and logprobs have to be those of ``hit``, to the bit.

The reference (a ``JAX_PLATFORMS=cpu`` child: ``reference.py`` on the
weights as served, in float32) scores P and the 64 answer tokens in one
forward; every reported logprob (the chosen token's and the top 20, at
each position) has to lie within ``reference.json``'s tolerance of it.
Prints the readings and exits non-zero otherwise.
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

CONFIG = "mellum2-12b-a2.5b"
CONFIG_DIR = os.path.join(REPO, "chipbench", "configs", CONFIG)
ANSWER_TOKENS = 64
TOP = 20


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------- the scoring child (JAX on the CPU) ----------------


def child(config_dir: str) -> int:
    import jax

    from chipbench import reference
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = reference.load_forward(os.path.join(CONFIG_DIR, "reference.py"))
    hf = load_json(config_dir, "config.json")
    cfg = ModelConfig.from_local_path(config_dir)
    params = llama.init_params(cfg, jax.random.key(0))
    jax.block_until_ready(params)
    print("scoring child: weights built", file=sys.stderr, flush=True)
    tokens = json.loads(sys.stdin.readline())
    logp = np.asarray(jax.nn.log_softmax(forward(params, hf, tokens)))
    want = json.loads(sys.stdin.readline())  # {position: [token ids]}
    print(json.dumps({pos: {str(t): float(logp[int(pos), t]) for t in ids}
                      for pos, ids in want.items()}), flush=True)
    return 0


# ---------------- the parent (JAX on the chip) ----------------


def _flag(flags, name, default=None):
    return flags[flags.index(name) + 1] if name in flags else default


def build_engine(config_dir: str, rehearse: bool):
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    serve = load_json(CONFIG_DIR, "serve.json")
    flags = serve["rehearse"]["flags"] if rehearse else serve["flags"]
    cfg = ModelConfig.from_local_path(config_dir)
    return JaxEngine(EngineConfig(
        model=cfg, num_blocks=int(_flag(flags, "--num-blocks")),
        max_batch_size=int(_flag(flags, "--max-batch")),
        max_context=int(_flag(flags, "--max-context")),
        mixed_step_budget=int(_flag(flags, "--mixed-step-budget")),
        mixed_max_prefills=int(_flag(flags, "--mixed-max-prefills")),
        window_blocks=int(_flag(flags, "--window-blocks", 0))))


def request(tokens, max_tokens):
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime import Context

    return Context(PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0, logprobs=TOP),
        eos_token_ids=[]))


async def ask(engine, tokens, max_tokens):
    from dynamo_tpu.runtime import collect

    out = await collect(engine.generate(request(tokens, max_tokens)))
    toks = [t for o in out for t in o.token_ids]
    lps = [e for o in out for e in (o.logprobs or [])]
    assert len(toks) == len(lps) == max_tokens, (len(toks), len(lps))
    return toks, lps


def poison(engine, keep_hashes: set) -> int:
    """NaN into every window-pool block that no sequence holds and whose
    hash is not in ``keep_hashes``; from now on also into every block
    released behind a window, at once (a block handed out again is
    zeroed: its unwritten rows are masked keys). Returns how many blocks
    the sweep overwrote."""
    import jax
    import jax.numpy as jnp

    fill = jax.jit(lambda c, idx, v: c.at[:, :, idx].set(v), donate_argnums=0)
    pool = engine.kv.window

    def write(blocks, value):
        idx = jnp.asarray(blocks, jnp.int32)
        (kf, kw), (vf, vw) = engine.k_cache, engine.v_cache
        engine.k_cache = (kf, fill(kw, idx, value))
        engine.v_cache = (vf, fill(vw, idx, value))

    dead = [b.idx for b in pool.allocator._blocks[1:]
            if b.ref_count == 0 and b.seq_hash not in keep_hashes]
    write(dead, jnp.nan)
    release = pool.release_behind

    def release_and_poison(wblocks, floor, pos, *rest):
        held = [b.idx for b in wblocks[floor:] if b is not None]
        top = release(wblocks, floor, pos, *rest)
        still = {b.idx for b in wblocks[floor:] if b is not None}
        gone = [i for i in held if i not in still]
        if gone:
            write(gone, jnp.nan)
        return top

    pool.release_behind = release_and_poison
    pool.allocator.on_allocated = lambda idx: write([idx], 0.0)
    return len(dead)


def cuts(words: int) -> dict:
    """Where the two probes end: 1,100 and 2,100 of 3,000 tokens."""
    return {"past 1,024": words * 11 // 30, "past 2,048": words * 21 // 30}


async def drive(engine, prompt, busy_prompt, say):
    from dynamo_tpu.engine.allocator import sequence_block_hashes

    bs = engine.cfg.block_size
    busy = asyncio.create_task(ask(engine, busy_prompt, 1500))
    while engine._n_active == 0:  # the busy stream decodes
        await asyncio.sleep(0.01)
    got = {}
    both = lambda: engine.stats | engine.kv.stats  # noqa: E731
    stats = lambda: {k: both()[k] for k in (  # noqa: E731
        "prefix_cache_hits_tokens", "prefix_matched_tokens",
        "prefix_window_missed_tokens", "mixed_steps")}
    for name, cut, n in (*((k, v, 1) for k, v in cuts(len(prompt)).items()),
                         ("in chunks", len(prompt), ANSWER_TOKENS),
                         ("hit", len(prompt), ANSWER_TOKENS)):
        before = stats()
        got[name] = await ask(engine, prompt[:cut], n)
        after = stats()
        say(f"{name}: {cut} prompt tokens, counters "
            f"{ {k: after[k] - before[k] for k in after} }; pools "
            f"{engine.kv.window.allocator.state_counts()}")
    # what the third ask's hit needs of the window pool: the window's
    # worth of blocks in front of the hit's boundary
    hashes = [h for _l, h in sequence_block_hashes(
        prompt[: len(prompt) - 1], bs)]
    p = len(hashes)
    keep = set(hashes[engine.kv.window.first_seen(p * bs): p])
    n = poison(engine, keep)
    say(f"poisoned {n} window-pool blocks of "
        f"{engine.kv.window.allocator.num_blocks - 1} (kept: the busy "
        f"stream's and the {len(keep)} behind P's hit)")
    before = stats()
    got["poisoned"] = await ask(engine, prompt, ANSWER_TOKENS)
    after = stats()
    say(f"poisoned: counters { {k: after[k] - before[k] for k in after} }; "
        f"released behind windows so far {engine.kv.window.released}")
    busy.cancel()
    try:
        await busy
    except (asyncio.CancelledError, Exception):  # noqa: BLE001
        pass
    await engine.close()
    return got


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="the tiny stand-in on the CPU (control flow only)")
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--config-dir", default=CONFIG_DIR)
    ap.add_argument("--words", type=int, default=3000)
    args = ap.parse_args()
    if args.child:
        return child(args.config_dir)
    config_dir = args.config_dir
    if args.rehearse:
        config_dir = os.path.join(REPO, "chipbench", "testdata", "tiny-mellum2")
        os.environ["JAX_PLATFORMS"] = "cpu"
    say = lambda m: print(m, flush=True)  # noqa: E731
    os.makedirs(os.path.join(REPO, "chipbench", "work"), exist_ok=True)
    scorer = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child",
         "--config-dir", config_dir],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        stderr=open(os.path.join(REPO, "chipbench", "work",
                                 "window_check_ref.log"), "wb"))
    try:
        vocab = load_json(config_dir, "config.json")["vocab_size"]
        rng = np.random.default_rng(47)
        prompt = [int(t) for t in rng.integers(16, vocab, args.words)]
        busy_prompt = [int(t) for t in rng.integers(16, vocab, 32)]
        engine = build_engine(config_dir, args.rehearse)
        say("engine: window pool "
            f"{engine.kv.window.allocator.num_blocks} blocks, "
            f"full pool {engine.kv.allocator.num_blocks}, attention path "
            f"{engine.attention_path}")
        got = asyncio.run(drive(engine, prompt, busy_prompt, say))
    except BaseException:
        scorer.kill()
        raise
    toks, _ = got["in chunks"]
    sequence = prompt + toks[:-1]
    # the positions each ask reported logprobs at, and the candidates there
    want: dict = {}
    for name, (answer, lps) in got.items():
        start = cuts(len(prompt)).get(name, len(prompt))
        if name not in cuts(len(prompt)) and answer != toks:
            say(f"{name}: ANSWER DIFFERS from the first ask's")
        for i, entry in enumerate(lps):
            ids = want.setdefault(str(start - 1 + i), set())
            ids.update([answer[i]] + [t for t, _ in entry["top"]])
    scorer.stdin.write(json.dumps(sequence) + "\n")
    scorer.stdin.write(json.dumps({k: sorted(v) for k, v in want.items()}) + "\n")
    scorer.stdin.flush()
    out, _ = scorer.communicate(timeout=3000)
    ref = json.loads(out.strip().splitlines()[-1])
    tolerance = load_json(REPO, "chipbench", "reference.json")["tolerance"]
    ok, worst_all = True, 0.0
    for name, (answer, lps) in got.items():
        start = cuts(len(prompt)).get(name, len(prompt))
        worst, at = 0.0, None
        for i, entry in enumerate(lps):
            row = ref[str(start - 1 + i)]
            for tid, lp in [(answer[i], entry["logprob"])] + entry["top"]:
                d = abs(lp - row[str(tid)])
                if not d <= worst:  # (a NaN reading is the worst of all)
                    worst, at = d, start - 1 + i
        worst_all = max(worst_all, worst)
        ok &= worst <= tolerance
        say(f"{name}: {len(lps)} positions from {start - 1}, largest "
            f"|logprob diff| {worst:.4f} at position {at}")
    same = got["poisoned"] == got["hit"]
    say(f"poisoned answer and logprobs equal the hit's to the bit: {same}")
    say(f"largest reading {worst_all:.4f}, tolerance {tolerance}")
    ok &= same and got["hit"][0] == toks
    say("OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
