"""Generators: deterministic in the seed, one multiset of sizes and gaps
for every seed in the seed's own order, clips honoured, due times inside
the span."""

import collections
import json
import os

from chipbench import generators

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB_WORDS = 100352 - 16
CELLS = {"chat": {"rate_rps": 6.0}}


def mix_of(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def gen(name, seed, seconds=20):
    mix = mix_of(name)
    return mix, generators.load(mix["generator"]).generate(
        mix, CELLS[name], seconds, VOCAB_WORDS, seed, "m")


def gaps(reqs):
    due = [r.due_s for r in reqs]
    return sorted(round(b - a, 9) for a, b in zip(due, due[1:]))


def test_same_seed_same_requests():
    _, a = gen("chat", 2**31 + 5)
    _, b = gen("chat", 2**31 + 5)
    assert [(r.due_s, r.body) for r in a] == [(r.due_s, r.body) for r in b]
    _, c = gen("chat", 7)
    assert [r.body for r in a] != [r.body for r in c]


def test_every_seed_gets_the_same_sizes_and_gaps_in_its_own_order():
    _, a = gen("chat", 1)
    _, b = gen("chat", 2)
    for lo, hi in ((-1e9, 0.0), (0.0, 1e9)):  # lead-in, window
        pa = [r for r in a if lo <= r.due_s < hi]
        pb = [r for r in b if lo <= r.due_s < hi]
        assert len(pa) == len(pb)
        for key in ("prompt_tokens", "max_tokens"):
            assert sorted(getattr(r, key) for r in pa) == sorted(
                getattr(r, key) for r in pb)
    assert [r.prompt_tokens for r in a] != [r.prompt_tokens for r in b]
    assert [r.due_s for r in a] != [r.due_s for r in b]
    # each window's gaps are the one stratified set, less the gap that
    # came first in the seed's order (the first request is due half of it in)
    law = collections.Counter(
        round(float(g), 9) for g in generators.exponential_gaps(120, 20))
    for reqs in (a, b):
        seen = collections.Counter(gaps([r for r in reqs if r.due_s >= 0]))
        assert sum(seen.values()) == 119 and not seen - law
    assert len({r.prompt_tokens for r in a}) > len(a) // 4  # and they vary


def test_clips():
    mix, reqs = gen("chat", 3)
    shared = mix["shared_prefix_tokens"]
    for r in reqs:
        p = mix["prompt_tokens"]
        assert p["min"] <= r.prompt_tokens - 3 <= p["max"]
        o = mix["output_tokens"]
        assert o["min"] <= r.max_tokens <= o["max"]
        assert r.body["max_tokens"] == r.max_tokens
        words = r.body["messages"][-1]["content"]
        assert len(words.split()) == r.prompt_tokens - 3 - shared


def test_open_loop_counts_and_due_times():
    mix, reqs = gen("chat", 4, seconds=20)
    window = [r for r in reqs if r.due_s >= 0]
    assert len(window) == 120  # rate 6 x 20 s, whatever the seed
    assert len(reqs) - len(window) == 90  # lead-in of 15 s
    assert all(-mix["lead_s"] <= r.due_s < 20 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)


def test_chat_shares_its_system_prompt_and_nothing_else():
    _, reqs = gen("chat", 5)
    systems = {r.body["messages"][0]["content"] for r in reqs}
    assert len(systems) == 1 and len(next(iter(systems)).split()) == 64
    users = [r.body["messages"][1]["content"].split()[0] for r in reqs]
    assert len(set(users)) > len(users) * 0.9
