#!/usr/bin/env python3
"""The reference check's child: scores the server's own tokens with a
configuration's plain reference.

Each configuration carries its reference beside its sizes
(``chipbench/configs/<name>/reference.py``: ``forward(params, hf, tokens)
-> logits``, plain float32 ``jax.numpy``, read from ``config.json`` and
not through the program's ``ModelConfig``). From the program this child
takes only the weights: the tree its engine draws,
``init_params(cfg, jax.random.key(seed))`` (threefry bits are the same on
the CPU and the chip).

As a script (``JAX_PLATFORMS=cpu``) it builds the weights, then reads one
JSON line of sequences from stdin and prints their reference logprobs.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

import jax


def load_forward(path: str):
    """``forward`` of the reference file at ``path``."""
    spec = importlib.util.spec_from_file_location("chipbench_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.forward


def score(forward, params: dict, hf: dict, item: dict) -> dict:
    """Reference logprobs of the server's candidates at the first and the
    last generated position, given the prompt and the server's tokens."""
    prompt = list(item["prompt"])
    extra = item["prompt_tokens"] - len(prompt)
    if extra == 1:
        prompt = [1] + prompt  # the tokenizer's <s>
    elif extra != 0:
        raise ValueError(f"prompt of {len(prompt)} words is "
                         f"{item['prompt_tokens']} tokens")
    toks = item["tokens"]
    logits = forward(params, hf, prompt + toks[:-1])
    out = {}
    for pos, cands in item["candidates"].items():
        lp = jax.nn.log_softmax(logits[len(prompt) - 1 + int(pos)])
        out[pos] = {str(c): float(lp[c]) for c in cands}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config-dir", required=True,
                    help="holds the config.json that is served")
    ap.add_argument("--reference", required=True,
                    help="the configuration's reference.py")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    forward = load_forward(args.reference)
    with open(os.path.join(args.config_dir, "config.json")) as f:
        hf = json.load(f)
    # the weights are the system's own: the tree its engine draws
    cfg = ModelConfig.from_local_path(args.config_dir)
    params = llama.init_params(cfg, jax.random.key(args.seed))
    jax.block_until_ready(params)
    print("reference: weights built", file=sys.stderr, flush=True)
    todo = json.loads(sys.stdin.readline())
    out = {key: score(forward, params, hf, item) for key, item in todo.items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
