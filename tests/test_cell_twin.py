"""``scripts/cell_twin.py``: the loop's twin that says how far a cell's
``tpot_mean_ms`` moves with the order of its requests alone (PR 43)."""

import argparse
import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "cell_twin", os.path.join(ROOT, "scripts", "cell_twin.py"))
twin = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(twin)

OPTS = argparse.Namespace(a=10.0, b=0.1, c=30.0, d=0.05, tail_ms=30.0,
                          window=4, slots=8, budget=512, block=16,
                          restored=64)
MIX = json.load(open(os.path.join(ROOT, "chipbench", "traffic",
                                  "reason.json")))


@pytest.mark.parametrize("prompt, tail_ms, want", [
    (640, 30.0, [512, 64]),           # ends on a block: no tail
    (1100, 30.0, [512, 512, 12]),     # 1088 is the last full block
    (1100, 0.0, [512, 512, 12]),      # no snapshot: cut by the budget alone
    (70, 30.0, [6]),                  # under a block behind the prefix
    (64, 30.0, [1]),                  # all of it restored
])
def test_chunks_follow_the_budget_and_the_last_full_block(prompt, tail_ms,
                                                          want):
    o = argparse.Namespace(**{**vars(OPTS), "tail_ms": tail_ms})
    assert twin.chunks_of(prompt, o) == want
    assert sum(want) == max(prompt - 64, 1)


def test_a_tail_costs_its_own_time_and_a_chunk_by_its_tokens():
    assert twin.mixed_ms(12, 10, OPTS) == pytest.approx(30.0 + 1.0)
    assert twin.mixed_ms(512, 10, OPTS) == pytest.approx(30 + 25.6 + 1.0)


def test_a_seed_repeats_and_lies_between_a_decode_and_a_mixed_step():
    a = twin.run(5, MIX, 0.5, 20.0, OPTS)
    assert a == twin.run(5, MIX, 0.5, 20.0, OPTS)
    assert OPTS.a < a < OPTS.c + OPTS.d * 512
    assert a != twin.run(6, MIX, 0.5, 20.0, OPTS)


def test_full_slots_hold_the_live_rows_and_queue_the_rest():
    """Far over what 8 slots complete, every decode step has 8 rows: the
    token time is a step's at 8 rows plus the stalls' share."""
    t = twin.run(7, MIX, 3.0, 20.0, OPTS)
    assert t > OPTS.a + OPTS.b * OPTS.slots


def test_dispatches_group_chunk_times_and_count_first_tokens():
    reqs = [
        {"chunks": [[100.0, 1], [160.0, 2], [160.5, 2], [220.0, 4]]},
        {"chunks": [[160.2, 1], [220.3, 4]]},
        {"chunks": []},
    ]
    rows = twin.dispatches(reqs)
    assert [(n, k, f) for _t, _dt, n, k, f in rows] == [(2, 4, 1), (2, 4, 0)]
    assert rows[0][1] == pytest.approx(60.23, abs=0.1)
