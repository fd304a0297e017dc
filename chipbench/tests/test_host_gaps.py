"""Idle gaps by name: ``host_gaps.attribute`` on a hand-made case and on
``testdata/host_trace_cut.json``, the fifty longest gaps and every
``engine.*`` host annotation of a 3 s profile recorded on the v5e beside
`olmo2-1b.chat`'s traffic with the server's ``--trace`` on (my chip run,
PR 26; ``host_gaps.py <trace_dir> --keep``). ``testdata/device_trace_cut.json``
(PR 25) comes from a server that wrote no annotation: all its gaps stay
``unattributed``."""

import json
import os

import pytest

from chipbench import host_gaps

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name):
    with open(os.path.join(HERE, "testdata", name)) as f:
        return json.load(f)


def test_a_gap_takes_the_name_that_covers_most_of_it():
    host = [
        ["engine.profile", 0, 10_000, {}],       # the capture: never a name
        ["engine.emit", 100, 700, {}],           # covers 70 % of gap 1
        ["engine.admit", 800, 150, {}],
        ["engine.dispatch", 2_000, 300, {"kind": "decode_window"}],
        ["engine.emit", 2_300, 100, {}],
    ]
    gaps = [[0, 1_000], [2_000, 3_000], [5_000, 5_500]]
    got = host_gaps.attribute(gaps, host)
    assert [g[0] for g in got] == ["engine.emit", "engine.dispatch",
                                   "unattributed"]
    assert got[0][1] == pytest.approx(1e-6)
    assert got[0][2] == {"engine.emit": 0.7, "engine.admit": 0.15,
                         "await": 0.15}
    # named by what covers most of it; the shares say how little that is
    assert got[1][2] == {"engine.dispatch": 0.3, "engine.emit": 0.1,
                         "await": 0.6}
    assert got[2][2] == {}


def test_device_gaps_are_what_lies_between_the_ops():
    trace = {"span_ns": [0, 1500], "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["while.5", 100, 150], ["fusion.1", 100, 100],
                ["fusion.7", 500, 100], ["copy", 1000, 50]]},
            {"name": "XLA Modules", "events": [["jit_decode", 100, 500]]}]}]}
    assert host_gaps.device_gaps(trace) == [[600, 1000], [250, 500]]
    cut = host_gaps.cut(trace, [["engine.emit", 600, 300, {}]])
    assert host_gaps.attribute(cut["gaps"], cut["host"])[0][0] == "engine.emit"


def test_without_annotations_every_gap_is_unattributed():
    gaps = host_gaps.device_gaps(load("device_trace_cut.json"))
    assert gaps and all(
        name == "unattributed" for name, _s, _c in host_gaps.attribute(gaps, []))


def test_the_recorded_cut_names_its_longest_gaps():
    """The three gaps of milliseconds in a 3 s profile of `olmo2-1b.chat`
    (19.0, 17.2, 11.8 ms; the next is 16 us): `engine.dispatch` covers
    most of each among the annotations, and most of each is the loop's
    un-annotated awaits."""
    cut = load("host_trace_cut.json")
    names = {e[0] for e in cut["host"]}
    assert {"engine.profile", "engine.admit", "engine.provision",
            "engine.dispatch", "engine.device", "engine.emit"} <= names
    dispatch = next(e for e in cut["host"] if e[0] == "engine.dispatch")
    assert {"kind", "key", "n", "live", "seq"} <= set(dispatch[3])
    lo, hi = cut["span_ns"]
    assert all(lo <= a < b <= hi for a, b in cut["gaps"])
    got = host_gaps.attribute(cut["gaps"], cut["host"])
    assert [g[1] for g in got] == sorted((g[1] for g in got), reverse=True)
    assert [round(g[1] * 1e3, 1) for g in got[:4]] == [19.0, 17.2, 11.8, 0.0]
    for name, _seconds, shares in got[:3]:
        assert name == "engine.dispatch"
        assert 0.2 < shares["engine.dispatch"] < 0.35
        assert 0.4 < shares["await"] < 0.8
        assert sum(shares.values()) == pytest.approx(1.0, abs=0.01)
