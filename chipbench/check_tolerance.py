#!/usr/bin/env python3
"""A builder's tool, not run by the driver: what the reference check's
tolerance would catch.

    JAX_PLATFORMS=cpu python3 chipbench/check_tolerance.py --config olmo2-1b

On the CPU, at the configuration's full size, it scores the reference
check's own prompts twice with the configuration's plain reference: on
the weights as served, and on the same weights after a round trip through
int8 (symmetric, one scale per output channel: the usual weight-only
quantization of every matmul and the head). It prints the largest
|logprob difference| at the reference's top candidates, the quantity the
check compares with ``reference.json``'s ``tolerance``. A serving path
that quietly quantized its weights would differ from the reference by
this much on top of its bf16 arithmetic. The figure is a property of the
yardstick, not a device metric; it is recorded in ``tolerance_why``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import generators, reference  # noqa: E402
from chipbench.client import N_RESERVED, token_id  # noqa: E402


def int8_round_trip(w):
    """[..., in, out] -> the same after symmetric per-output-channel int8."""
    scale = jnp.max(jnp.abs(w.astype(jnp.float32)), -2, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127)
    return (q * scale).astype(w.dtype)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    args = ap.parse_args()
    from dynamo_tpu.models import llama
    from dynamo_tpu.models.config import ModelConfig

    cdir = os.path.join(HERE, "configs", args.config)
    with open(os.path.join(cdir, "config.json")) as f:
        hf = json.load(f)
    with open(os.path.join(HERE, "reference.json")) as f:
        ref = json.load(f)
    forward = reference.load_forward(os.path.join(cdir, "reference.py"))
    cfg = ModelConfig.from_local_path(cdir)
    params = llama.init_params(cfg, jax.random.key(ref["weights_seed"]))
    quant = dict(params)
    quant["layers"] = {
        k: int8_round_trip(v) if v.ndim == 3 else v
        for k, v in params["layers"].items()}
    quant["lm_head"] = int8_round_trip(params["lm_head"])
    rng = np.random.default_rng(ref["prompt_seed"])
    worst = 0.0
    for i in range(ref["prompts"]):
        words = generators.words(rng, ref["prompt_tokens"],
                                 hf["vocab_size"] - N_RESERVED)
        toks = [1] + [token_id(w) for w in words.split()]
        a = jax.nn.log_softmax(forward(params, hf, toks)[-1])
        b = jax.nn.log_softmax(forward(quant, hf, toks)[-1])
        top = jnp.argsort(-a)[: ref["top_logprobs"] + 1]
        d = float(jnp.max(jnp.abs(a[top] - b[top])))
        worst = max(worst, d)
        print(f"prompt {i}: max |logprob diff| at the top "
              f"{ref['top_logprobs'] + 1} candidates {d:.4f}", flush=True)
    verdict = "fails" if worst > ref["tolerance"] else "PASSES"
    print(f"int8 weights: max |logprob diff| {worst:.4f} against a tolerance "
          f"of {ref['tolerance']}: the check {verdict} them")
    return 0


if __name__ == "__main__":
    sys.exit(main())
