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


#: the two PR 45 added for the three expert cells: the streams counter
#: and the kernel's name are what a server before it lacks
NEW_IN_44 = ("moe_expert_restream_factor", "moe_matmul_share.serve")
EXPERT_CELLS = ["olmoe-1b-7b.chat", "lfm2-8b-a1b.chat", "gigachat35.reason"]
#: the three PR 47 added for its cell: the window pool's counters are
#: what a server before it (and every model with one KV pool) lacks
NEW_IN_47 = ("window_kv_resident_share", "window_attn_walked_share",
             "window_prefix_missed_share")
CELL_47 = "mellum2.repo"
#: the two PR 48 added for all five cells: the hand-overs' counters are
#: what a server before the resident step state lacks
NEW_IN_48 = ("step_handovers_per_dispatch", "step_state_resident_share")
#: the one PR 49 added for all five cells: the chained dispatches'
#: counter is what a server before the chained loop lacks
NEW_IN_49 = ("decode_chained_share",)


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
    if name in EXPECTED or name in (
            NEW_IN_43 + NEW_IN_44 + NEW_IN_47 + NEW_IN_48 + NEW_IN_49):
        assert got is None
    elif s["reducer"] == "counter_ratio" and not name.startswith(
            ("moe_", "state_")):
        assert got is not None


def test_benchmark_json_lists_pr43s_metrics_for_its_cell_only():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_IN_43[0])  # entries are only ever appended
    assert names[at:at + len(NEW_IN_43)] == list(NEW_IN_43)
    for m in bench["per_layer"][at:at + len(NEW_IN_43)]:
        s = spec(m["name"])
        assert m["workloads"] == ["gigachat35.reason"]
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            s["unit"], s["better"], s["layer"], s["moves"])
        assert m["source"] == {"metrics_delta": "program_counter",
                               "device_trace": "device_trace"}[s["source"]]
    # the cell joins every metric that was there, and nothing else moved
    # (cells are only ever appended: PR 47's stands behind it)
    for m in bench["per_layer"][:at]:
        then = [w for w in m["workloads"] if w != CELL_47]
        assert then[-1] == "gigachat35.reason", m["name"]


def test_benchmark_json_lists_pr44s_metrics_for_the_expert_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_IN_44[0])  # entries are only ever appended
    assert names[at:at + 2] == list(NEW_IN_44)
    for m in bench["per_layer"][at:at + 2]:
        s = spec(m["name"])
        assert m["workloads"][:3] == EXPERT_CELLS and m["layer"] == "kernels"
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            s["unit"], s["better"], s["layer"], s["moves"])
        assert m["source"] == {"metrics_delta": "program_counter",
                               "device_trace": "device_trace"}[s["source"]]


def test_benchmark_json_lists_pr47s_metrics_for_its_cell_only():
    """The three stand where PR 47 appended them (before PR 48's two),
    for ``mellum2.repo`` alone; the cell is
    appended to ``tpot_mean_ms`` and to the per-layer metrics whose
    instrument the model has (every one that lists all four older cells,
    and the expert layer's counters), and to none of the state's."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(NEW_IN_47[0])  # entries are only ever appended
    assert names[at:at + 5] == list(NEW_IN_47 + NEW_IN_48)
    for m in bench["per_layer"][at:at + 3]:
        s = spec(m["name"])
        assert m["workloads"] == [CELL_47] and m["source"] == "program_counter"
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            s["unit"], s["better"], s["layer"], s["moves"])
        assert s["reducer"] == "counter_ratio" and m["better"] == "lower"
    older = [w["name"] for w in bench["workloads"] if w["name"] != CELL_47]
    joined = {m["name"] for m in bench["per_layer"][:at]
              if CELL_47 in m["workloads"]}
    for m in bench["per_layer"][:-5]:
        if CELL_47 in m["workloads"]:
            assert m["workloads"][-1] == CELL_47, m["name"]
        if m["workloads"][:4] == older:
            assert m["name"] in joined, m["name"]
    assert {"moe_experts_touched_share", "moe_touched_live_share",
            "moe_matmul_share.serve", "moe_expert_restream_factor"} <= joined
    assert not joined & {"moe_op_share.serve", "state_restore_share",
                         "linear_state_mib_per_step", *NEW_IN_43}
    tpot = next(m for m in bench["end_to_end"] if m["name"] == "tpot_mean_ms")
    assert tpot["workloads"] == older + [CELL_47]


@pytest.mark.parametrize("name", NEW_IN_47)
def test_pr47s_metrics_read_a_server_that_has_their_series(name):
    """A window made up: rows that hold 1,200 of their contexts' 7,500
    tokens in the window pool and walk 70 of 470 pages, and hits cut
    short by 64 of 6,400 matched tokens; the engine spells the series as
    the selectors do."""
    delta = {"engine_kv_window_resident_tokens_total": 1200.0,
             "engine_kv_window_context_tokens_total": 7500.0,
             "engine_attn_window_pages_total": 70.0,
             "engine_attn_window_context_pages_total": 470.0,
             "engine_prefix_window_missed_tokens_total": 64.0,
             "engine_prefix_matched_tokens_total": 6400.0}
    s = spec(name)
    got = counter_ratio.reduce({"delta": delta}, s["selector"])
    assert got == pytest.approx({
        "window_kv_resident_share": 16.0,
        "window_attn_walked_share": 100 * 70 / 470,
        "window_prefix_missed_share": 1.0}[name])
    from dynamo_tpu.engine.kv_manager import WINDOW_COUNTERS

    for series in s["selector"]["num"] + s["selector"]["den"]:
        assert series[len("engine_"):-len("_total")] in WINDOW_COUNTERS


@pytest.mark.parametrize("name", NEW_IN_44)
def test_pr44s_metrics_read_a_server_that_has_their_series(name):
    """A window made up: 1,100 expert matrices streamed for 1,000
    touched experts (a tenth of them crossed a row tile), and a trace in
    which the kernel took 5 and what stayed on ``lax.ragged_dot`` 1 of
    10 busy seconds; the engine spells the series as the selector does,
    and the kernel's name is the ``pallas_call``'s."""
    delta = {"engine_moe_expert_streams_total": 1100.0,
             "engine_moe_experts_touched_total": 1000.0}
    device = {"busy_s": 10.0, "op_seconds": [
        ["moe_grouped_matmul", 5.0], ["ragged-dot-none", 0.75],
        ["ragged-dot-metadata", 0.25], ["fusion", 4.0]]}
    s = spec(name)
    reducer = importlib.import_module(f"chipbench.reducers.{s['reducer']}")
    got = reducer.reduce({"delta": delta, "device": device}, s["selector"])
    assert got == pytest.approx({"moe_expert_restream_factor": 1.1,
                                 "moe_matmul_share.serve": 60.0}[name])
    from dynamo_tpu.engine.engine import MOE_COUNTERS

    assert "moe_expert_streams" in MOE_COUNTERS
    with open(os.path.join(REPO, "dynamo_tpu", "ops",
                           "moe_gmm_pallas.py")) as f:
        assert 'name="moe_grouped_matmul"' in f.read()


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


def test_benchmark_json_lists_pr48s_metrics_for_every_cell():
    """The two stand where PR 48 appended them and PR 49's one last, for
    all five cells, under the scheduler."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(
        NEW_IN_48 + NEW_IN_49)
    for m in bench["per_layer"][-3:]:
        s = spec(m["name"])
        assert m["workloads"] == cells and m["source"] == "program_counter"
        assert s["reducer"] == "counter_ratio" and m["layer"] == "scheduler"
        assert (m["unit"], m["better"], m["layer"], m["moves"]) == (
            s["unit"], s["better"], s["layer"], s["moves"])


@pytest.mark.parametrize("name,want", [
    ("step_handovers_per_dispatch", 1.5),   # (90 + 50 + 10) / 100 steps
    ("step_state_resident_share", 80.0),    # 100 x 68 / (68 + 17)
])
def test_pr48s_metrics_read_a_server_that_has_their_series(name, want):
    """The made-up window above with its hand-overs: 90 by the 60 decode
    windows, 50 by the 25 mixed steps, 10 by the 15 prefills; and of the
    85 dispatches with decode rows 17 sent the whole mirror. The engine
    spells the series as the selectors do."""
    handed = 'engine_step_handovers_total{kind="%s"}'
    delta = dict(DELTA, **{
        handed % "decode_window": 90, handed % "mixed_step": 50,
        handed % "prefill": 10, handed % "verify": 0,
        "engine_step_state_resident_total": 68,
        "engine_step_state_resyncs_total": 17})
    sel = spec(name)["selector"]
    assert counter_ratio.reduce({"delta": delta}, sel) == pytest.approx(want)
    with open(os.path.join(REPO, "dynamo_tpu", "engine", "engine.py")) as f:
        engine = f.read()
    for series in sel["num"] + sel["den"]:
        base, _, label = series.partition("{")
        if label:
            assert label.split('"')[1] in KINDS, series
            assert f"'{base}{{{{kind=" in engine, series
        else:
            assert base.removeprefix("engine_").removesuffix("_total") in (
                "step_state_resident", "step_state_resyncs")
            assert 'out[f"engine_{name}_total"]' in engine


@pytest.mark.parametrize("chained,want", [
    ((48, 20), 80.0),   # 100 x (48 + 20) / (60 + 25)
    ((0, 0), 0.0),      # the unchained loop: the series stand at 0
])
def test_pr49s_metric_reads_a_server_that_has_its_series(chained, want):
    """The made-up window above: of its 60 decode windows 48 were
    enqueued while another program was outstanding, of its 25 mixed
    steps 20 (a lone prefill or a verify step is neither counted nor
    divided by). The engine spells the series as the selector does, and
    a server without it (every one before PR 49) reads nothing."""
    series = 'engine_dispatch_chained_total{kind="%s"}'
    delta = dict(DELTA, **{
        series % "decode_window": chained[0],
        series % "mixed_step": chained[1],
        series % "prefill": 0, series % "verify": 0})
    sel = spec(NEW_IN_49[0])["selector"]
    assert counter_ratio.reduce({"delta": delta}, sel) == pytest.approx(want)
    assert counter_ratio.reduce({"delta": DELTA}, sel) is None
    with open(os.path.join(REPO, "dynamo_tpu", "engine", "engine.py")) as f:
        engine = f.read()
    for s in sel["num"] + sel["den"]:
        base, _, label = s.partition("{")
        assert label.split('"')[1] in ("decode_window", "mixed_step"), s
        assert f"'{base}{{{{kind=" in engine, s
