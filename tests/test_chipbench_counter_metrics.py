"""The benchmark's counter metrics of the loop's series by kind (PR 40),
through the reducer the benchmark already has, and EVERY metric file of
``chipbench/layer_metrics/`` against a server that lacks what later PRs
added to the program.

The driver runs a PR's benchmark files over the PARENT's program too: a
reader then meets a ``/metrics`` without the series its PR added, and
has to answer None (``run.py``'s ``layer_metrics`` leaves the metric out
of the line), never raise. These tests live here and not under
``chipbench/tests/`` because a PR that is not a ``benchmark`` one edits
no file the benchmark has.
"""

import importlib
import json
import os

import pytest

from chipbench.reducers import counter_ratio
from dynamo_tpu.tracing import loop_clock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
KINDS = tuple(loop_clock.KINDS.values())
PHASE = 'engine_loop_seconds_total{phase="%s"}'
STEPS = 'engine_steps_total{kind="%s"}'
SECONDS = 'engine_step_seconds_total{kind="%s"}'
EXPOSED = 'engine_step_exposed_seconds_total{kind="%s"}'
DEVICE_STEPS = 'engine_device_steps_total{kind="%s"}'
# a made-up window: 50 s of work in 100 dispatches, 200 device steps;
# the kinds' seconds sum to the phases' but idle
DELTA = {
    PHASE % "idle": 4.0, PHASE % "admit": 0.10, PHASE % "provision": 0.05,
    PHASE % "dispatch": 1.0, PHASE % "device": 48.0, PHASE % "lag": 0.25,
    PHASE % "emit": 0.35, PHASE % "yield": 0.25,
    STEPS % "decode_window": 60, STEPS % "mixed_step": 25,
    STEPS % "prefill": 15, STEPS % "verify": 0,
    "engine_decode_steps_total": 185,
    SECONDS % "decode_window": 36.0, SECONDS % "mixed_step": 12.5,
    SECONDS % "prefill": 1.5, SECONDS % "verify": 0.0,
    EXPOSED % "decode_window": 4.5, EXPOSED % "mixed_step": 0.25,
    EXPOSED % "prefill": 0.25, EXPOSED % "verify": 0.0,
    DEVICE_STEPS % "decode_window": 160, DEVICE_STEPS % "mixed_step": 25,
    DEVICE_STEPS % "prefill": 15, DEVICE_STEPS % "verify": 0,
}
EXPECTED = {
    "decode_step_ms": 225.0,           # 1e3 x 36 s / 160 device steps
    "mixed_step_ms": 500.0,            # 1e3 x 12.5 s / 25 mixed steps
    "stall_step_time_share": 28.0,     # 100 x (12.5 + 1.5) / 50
    "decode_host_gap_ms": 75.0,        # 1e3 x 4.5 s / 60 windows
    "device_exposed_share": 10.0,      # 100 x 5 / 50
}
with open(os.path.join(BENCH, "testdata", "series_at_39e17ad.json")) as f:
    RECORDED = json.load(f)
METRIC_FILES = sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
    if f.endswith(".json"))


def spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_five_metrics_by_kind_on_a_made_up_delta(name):
    s = spec(name)
    assert s["reducer"] == "counter_ratio" and s["source"] == "metrics_delta"
    assert s["moves"] == "tpot_mean_ms"
    got = counter_ratio.reduce({"delta": DELTA}, s["selector"])
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_their_series_are_what_the_engine_exports(name):
    """Every series a selector names is one ``device_path_stats`` builds
    for one of the clock's kinds, spelled as the engine spells it."""
    with open(os.path.join(REPO, "dynamo_tpu", "engine", "engine.py")) as f:
        engine = f.read()
    sel = spec(name)["selector"]
    for series in sel["num"] + sel["den"]:
        base, _, label = series.partition("{")
        assert label.split('"')[1] in KINDS, series
        assert f"'{base}{{{{kind=" in engine, series


#: the per-layer metrics PR 43 added for ``gigachat35.reason``: what a
#: server before it lacks (series or ops), so each answers None there
NEW_IN_43 = ("linear_attn_op_share.serve", "latent_attn_kernel_share.serve",
             "moe_held_assignment_share", "linear_state_mib_per_step",
             "prefix_unsnapshotted_share")


def test_benchmark_json_lists_the_five_for_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    five = ["decode_step_ms", "mixed_step_ms", "stall_step_time_share",
            "decode_host_gap_ms", "device_exposed_share"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    # entries are only ever appended: the five stand where PR 40 put them
    assert names[names.index(five[0]):][:5] == five
    for m in (by_name[n] for n in five):
        s = spec(m["name"])
        assert m["source"] == "program_counter" and m["workloads"] == cells
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            s["unit"], s["better"], s["layer"], s["moves"])


@pytest.mark.parametrize("model", ["dense", "expert", "conv_state"])
@pytest.mark.parametrize("name", METRIC_FILES)
def test_every_metric_file_passes_a_server_that_lacks_its_series(name, model):
    """``delta`` holds exactly the series a server at 39e17ad exports
    (recorded from rehearsal servers of that commit, a dense, an expert
    and a conv-state stand-in); nothing was traced (``spans`` and
    ``window`` empty, no ``device``). No reducer raises; the five of
    PR 40 answer None; what 39e17ad exports, its own metrics read."""
    delta = dict.fromkeys(RECORDED["common"] + RECORDED[model], 1.0)
    s = spec(name)
    reducer = importlib.import_module(f"chipbench.reducers.{s['reducer']}")
    ctx = {"window": [], "delta": delta, "spans": {}, "device": None,
           "peaks": {}}
    got = reducer.reduce(ctx, s.get("selector", {}))
    assert got is None or isinstance(got, float)
    if name in EXPECTED or name in NEW_IN_43:
        assert got is None
    elif s["reducer"] == "counter_ratio" and not name.startswith(
            ("moe_", "state_")):
        assert got is not None


def test_benchmark_json_lists_pr43s_metrics_for_its_cell_only():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-len(NEW_IN_43):]] == list(
        NEW_IN_43)
    for m in bench["per_layer"][-len(NEW_IN_43):]:
        s = spec(m["name"])
        assert m["workloads"] == ["gigachat35.reason"]
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            s["unit"], s["better"], s["layer"], s["moves"])
        assert m["source"] == {"metrics_delta": "program_counter",
                               "device_trace": "device_trace"}[s["source"]]
    # the cell joins every metric that was there, and nothing else moved
    for m in bench["per_layer"][: -len(NEW_IN_43)]:
        assert m["workloads"][-1] == "gigachat35.reason", m["name"]


@pytest.mark.parametrize("name", NEW_IN_43)
def test_pr43s_metrics_read_a_server_that_has_their_series(name):
    """A window of the new cell, made up: 1,000 decode steps of 32 slots
    (a layer's matrices 4 MiB a slot, 4 layers, read and written), 64 of
    1,024 assignments held, 64 of 640 matched tokens without a snapshot;
    a trace in which the two kernels took 3 and 1 of 10 busy seconds."""
    delta = {
        "engine_decode_steps_total": 1000.0,
        "engine_linear_state_bytes_total": 1000.0 * 32 * 2 * (16 << 20),
        "engine_moe_held_assignments_total": 64.0,
        "engine_moe_assignments_total": 1024.0,
        "engine_prefix_unsnapshotted_tokens_total": 64.0,
        "engine_prefix_matched_tokens_total": 640.0,
    }
    device = {"busy_s": 10.0, "op_seconds": [
        ["linear_attn_recurrent_step", 3.0], ["fusion", 5.0],
        ["mla_paged_decode_attention", 0.75],
        ["mla_paged_prefill_attention", 0.25], ["ragged-dot", 1.0]]}
    s = spec(name)
    reducer = importlib.import_module(f"chipbench.reducers.{s['reducer']}")
    got = reducer.reduce({"delta": delta, "device": device}, s["selector"])
    assert got == pytest.approx({
        "linear_attn_op_share.serve": 30.0,
        "latent_attn_kernel_share.serve": 10.0,
        "moe_held_assignment_share": 6.25,
        "linear_state_mib_per_step": 1024.0,
        "prefix_unsnapshotted_share": 10.0,
    }[name])
