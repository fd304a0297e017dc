"""The trace reducer: on a hand-made trace, and on two cuts recorded from
real v5e traces: ``testdata/device_trace_cut.json`` (the attention
kernels by name; its span was shrunk to its ops by the first ``cut``) and
``testdata/device_trace_cut_edge.json`` (the head of a traced run's
profile as the present ``cut`` keeps it, the profiler's leading edge
included; my chip run, PR 25)."""

import json
import os

import pytest

from chipbench import trace_reduce
from chipbench.reducers import (
    device_idle_share, device_ms_per_token, device_op_share,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def hand_made():
    """Ops from 100 to 1050 ns inside a trace that runs from 0 to 1500:
    the profiler's start takes the first 100 ns and its stop the last
    450, and the device plane records nothing meanwhile."""
    ops = [["while.5", 100, 150], ["fusion.1", 100, 100],  # nested: 100-250
           ["attn_kernel.2", 200, 50], ["fusion.7", 500, 100],
           ["copy", 1000, 50]]
    return {"span_ns": [0, 1500], "seen": [], "planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [["jit_decode", 100, 500]]}]}]}


def test_busy_is_the_union_and_gaps_are_what_is_left():
    r = trace_reduce.reduce(hand_made())
    assert r["trace_s"] == pytest.approx(1500e-9)
    assert r["window_s"] == pytest.approx(950e-9)  # first op to last op
    assert r["busy_s"] == pytest.approx(300e-9)  # 150 + 100 + 50
    assert r["n_ops"] == 5
    assert r["device_ops"][0] == ["fusion", pytest.approx(200e-9)]
    assert dict(r["op_seconds"])["while"] == pytest.approx(0.0)  # self time
    assert r["programs"] == [["jit_decode", pytest.approx(500e-9)]]
    assert r["idle_gaps"] == [["unattributed", pytest.approx(400e-9)],
                              ["unattributed", pytest.approx(250e-9)]]
    ctx = {"device": dict(r, tokens_in_window=3.0)}
    assert device_idle_share.reduce(ctx, {}) == pytest.approx(
        100.0 * 650 / 950)
    assert device_op_share.reduce(ctx, {"pattern": "attn"}) == pytest.approx(
        100.0 * 50 / 300)
    assert device_ms_per_token.reduce(ctx, {}) == pytest.approx(1e-4)
    assert device_idle_share.reduce({"device": None}, {}) is None


def test_the_profilers_edges_are_no_idle_time():
    r = trace_reduce.reduce(hand_made())
    assert r["edge_gaps"] == [["before_first_op", pytest.approx(100e-9)],
                              ["after_last_op", pytest.approx(450e-9)]]
    # the window, busy time and the idle gaps are the same without them
    t = hand_made()
    t["span_ns"] = [100, 1050]
    tight = trace_reduce.reduce(t)
    for key in ("window_s", "busy_s", "idle_gaps"):
        assert tight[key] == r[key]
    assert [g for _n, g in tight["edge_gaps"]] == [0.0, 0.0]
    assert sum(g for _n, g in r["idle_gaps"]) + r["busy_s"] == pytest.approx(
        r["window_s"])


def test_two_chips_average():
    t = hand_made()
    t["planes"].append({"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.1", 200, 500]]}]})
    r = trace_reduce.reduce(t)
    assert r["busy_s"] == pytest.approx(400e-9)  # (300 + 500) / 2
    assert r["window_s"] == pytest.approx(725e-9)  # (950 + 500) / 2
    assert r["edge_gaps"][0][1] == pytest.approx(150e-9)  # (100 + 200) / 2


def test_no_ops_line_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"span_ns": [0, 1], "planes": [], "seen": ["x"]})


def test_recorded_cut():
    path = os.path.join(HERE, "testdata", "device_trace_cut.json")
    with open(path) as f:
        trace = json.load(f)
    r = trace_reduce.reduce(trace)
    assert r["planes"] == ["/device:TPU:0"]
    assert 0 < r["busy_s"] <= r["window_s"] <= r["trace_s"]
    ops = next(l["events"] for l in trace["planes"][0]["lines"]
               if l["name"] == "XLA Ops")
    first = min(s for _n, s, _d in ops)
    assert r["edge_gaps"][0][1] == pytest.approx(
        (first - trace["span_ns"][0]) / 1e9)
    assert r["window_s"] == pytest.approx(
        (max(s + d for _n, s, d in ops) - first) / 1e9)
    assert r["n_ops"] >= 200
    with open(os.path.join(HERE, "layer_metrics",
                           "attn_kernel_share.serve.json")) as f:
        sel = json.load(f)["selector"]
    share = device_op_share.reduce({"device": r}, sel)
    assert 0 < share < 100  # the Pallas attention calls are found by name


def test_recorded_leading_edge():
    """The profile of the traced proving run: the trace starts 54.5 ms
    before the device plane's first op, and that is no idle time."""
    path = os.path.join(HERE, "testdata", "device_trace_cut_edge.json")
    with open(path) as f:
        trace = json.load(f)
    r = trace_reduce.reduce(trace)
    assert r["edge_gaps"] == [["before_first_op", pytest.approx(0.05451286)],
                              ["after_last_op", 0.0]]
    assert r["trace_s"] == pytest.approx(r["window_s"] + 0.05451286)
    idle = sum(g for _n, g in trace_reduce.reduce(trace, top=10**6)["idle_gaps"])
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])
    assert device_idle_share.reduce({"device": r}, {}) < 1.0  # %, not 44
