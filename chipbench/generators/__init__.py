"""Traffic generators, one file each, found by the ``generator`` name in
a mix's file under ``chipbench/traffic/``. Each exposes

    generate(mix, cell, seconds, vocab_words, seed) -> list[Request]

and gives EVERY seed the same multiset of sizes and arrival gaps
(stratified quantiles of the mix's distributions): the run's seed draws
their ORDER, the words and the per-request sampling seeds, so runs with
different seeds do the same amount of work in another order."""

from __future__ import annotations

import dataclasses
import importlib
import math
import statistics

import numpy as np

from chipbench.client import filler_word


@dataclasses.dataclass
class Request:
    """One request of a run. ``due_s`` is relative to the start of the
    measured window (negative: lead-in traffic)."""

    index: int
    due_s: float
    path: str
    body: dict
    prompt_tokens: int
    max_tokens: int


def load(name: str):
    return importlib.import_module(f"chipbench.generators.{name}")


# ---------------- stratified draws: one multiset for every seed ----------------


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified(spec: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at evenly spaced quantiles of ``spec`` (a
    ``lognormal`` by median/sigma), clipped to [min, max]. No
    randomness: the seed permutes."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf(x) for x in _quantiles(n)])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(int)


def exponential_gaps(n: int, total_s: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of an exponential law,
    scaled to sum to ``total_s``: a Poisson process given its count."""
    g = -np.log1p(-_quantiles(n))
    return g * (total_s / g.sum())


def words(rng: np.random.Generator, n: int, vocab_words: int) -> str:
    """``n`` filler words drawn uniformly: one token each, and two
    prompts share a prefix only where the generator makes them."""
    return " ".join(filler_word(i) for i in rng.integers(0, vocab_words, n))


def request_body(mix: dict, model: str, prompt: str, max_tokens: int,
                 seed: int, system: str | None = None) -> tuple[str, dict]:
    body = {
        "model": model, "stream": True, "max_tokens": int(max_tokens),
        "nvext": {"ignore_eos": True},
        "stream_options": {"include_usage": True},
        **mix.get("sampling", {"temperature": 0}),
    }
    if mix.get("seed_per_request"):
        body["seed"] = int(seed)
    if mix["endpoint"] == "chat":
        msgs = [{"role": "user", "content": prompt}]
        if system:
            msgs.insert(0, {"role": "system", "content": system})
        body["messages"] = msgs
        return "/v1/chat/completions", body
    body["prompt"] = prompt
    return "/v1/completions", body


def prompt_tokens(mix: dict, own: int, system: int = 0) -> int:
    """Tokens the server counts for a prompt of ``own`` words (after
    ``system`` words of system prompt). A completion is its words. The
    generated chat template (``client.CHAT_TEMPLATE``) glues each role
    tag to the next word (one unknown token for the two) and ends each
    message in ``</s>``, which the tokenizer splits off: a user message
    is own + 2 tokens (``</s>``, ``<|assistant|>``) and a system
    message before it adds system + 1."""
    if mix["endpoint"] != "chat":
        return own
    return own + 2 + (system + 1 if system else 0)
