"""Independent requests in an open loop: each its own prompt (after an
optional shared system prompt) and its own output length.

The cell's ``rate_rps`` fixes how many requests fall due in the lead-in
and in the window; their gaps are the quantiles of an exponential law.
Every seed gets the same sizes and the same gaps; the run's seed draws
their order, the words and the per-request sampling seeds. With few
requests in a window the order alone moves a time to first token (PR 25:
``ttft_p50_ms`` 917 to 1,372 ms over six seeds at 23 requests, two runs of
one seed within 1 %): a cell reports a tail only where its window holds
enough requests for it (``PERF.md`` section 2)."""

from __future__ import annotations

import numpy as np

from . import (
    Request, exponential_gaps, prompt_tokens, request_body, stratified, words,
)


def _phase(mix, model, n, span, start, rng, vocab_words, first_index, system,
           seed):
    """``n`` requests due in [start, start + span): the mix's stratified
    sizes and gaps in the seed's order, on the seed's words."""
    shared = mix.get("shared_prefix_tokens", 0)
    p_len = rng.permutation(stratified(mix["prompt_tokens"], n))
    o_len = rng.permutation(stratified(mix["output_tokens"], n))
    gaps = rng.permutation(exponential_gaps(n, span))
    due = start + np.cumsum(gaps) - gaps[0] * 0.5
    out = []
    for k in range(n):
        own = max(int(p_len[k]) - shared, 1)
        path, body = request_body(
            mix, model, words(rng, own, vocab_words), o_len[k],
            seed + first_index + k, system)
        n_prompt = prompt_tokens(mix, own, shared if system else 0)
        out.append(Request(first_index + k, float(due[k]), path, body,
                           n_prompt, int(o_len[k])))
    return out


def generate(mix: dict, cell: dict, seconds: float, vocab_words: int,
             seed: int, model: str) -> list[Request]:
    rng = np.random.default_rng(seed)
    shared = mix.get("shared_prefix_tokens", 0)
    # the shared system prompt is the deployment's, the same for every seed
    system = (
        words(np.random.default_rng(mix.get("system_prompt_seed", 7)),
              shared, vocab_words)
        if shared and mix["endpoint"] == "chat" else None
    )
    rate, lead = float(cell["rate_rps"]), float(mix["lead_s"])
    out = []
    for span, start in ((lead, -lead), (seconds, 0.0)):
        n = max(int(round(rate * span)), 1)
        out.extend(_phase(mix, model, n, span, start, rng, vocab_words,
                          len(out), system, seed))
    return out
