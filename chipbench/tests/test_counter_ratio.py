"""The ``counter_ratio`` reducer on a made-up ``delta``, and the six
loop-clock metrics' files against the series names the engine exports."""

import json
import os

import pytest

from chipbench.reducers import counter_ratio

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEC = 'engine_loop_seconds_total{phase="%s"}'
STEPS = 'engine_steps_total{kind="%s"}'
DELTA = {
    SEC % "idle": 4.0, SEC % "admit": 0.10, SEC % "provision": 0.05,
    SEC % "dispatch": 1.0, SEC % "device": 48.0, SEC % "lag": 0.25,
    SEC % "emit": 0.35, SEC % "yield": 0.25,
    STEPS % "decode_window": 60, STEPS % "mixed_step": 25,
    STEPS % "prefill": 15, STEPS % "verify": 0,
    "engine_decode_steps_total": 200, "engine_rows_live_total": 3000,
    "engine_rows_dispatched_total": 6400,
    "engine_prefill_tokens_dispatched_total": 8000,
    "engine_prefill_tokens_padding_total": 2000,
    "engine_attn_table_pages_total": 1638400,
    "engine_attn_live_pages_total": 81920,
}
EXPECTED = {
    "loop_host_ms_per_step": 5.0,      # 1e3 x 0.5 s / 100 dispatches
    "loop_wait_ms_per_step": 5.0,      # 1e3 x 0.5 s / 100
    "device_wait_share": 98.0,         # 100 x 49 / 50 (idle apart)
    "live_rows_per_step": 15.0,
    "prefill_padding_share": 25.0,
    "attn_table_live_share": 5.0,
}


def spec(name):
    with open(os.path.join(HERE, "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_the_six_metrics_on_a_made_up_delta(name):
    s = spec(name)
    assert s["reducer"] == "counter_ratio" and s["source"] == "metrics_delta"
    got = counter_ratio.reduce({"delta": DELTA}, s["selector"])
    assert got == pytest.approx(EXPECTED[name])


def test_missing_series_and_zero_denominator_give_none():
    sel = {"num": ["a"], "den": ["b", "c"], "scale": 100}
    assert counter_ratio.reduce({"delta": {"a": 1, "b": 1, "c": 3}},
                                sel) == pytest.approx(25.0)
    # an older program exports none of it: the metric is left out
    assert counter_ratio.reduce({"delta": {"b": 1, "c": 3}}, sel) is None
    assert counter_ratio.reduce({"delta": {"a": 1, "b": 1}}, sel) is None
    assert counter_ratio.reduce({"delta": {"a": 1, "b": 0, "c": 0}},
                                sel) is None
    assert counter_ratio.reduce({"delta": {"a": 6, "b": 2, "c": 1}},
                                {"num": ["a"], "den": ["b", "c"]}) == 2.0


def test_the_series_are_what_the_engine_exports():
    """Every series a selector names is one ``device_path_stats`` builds
    (the names are spelled in the engine's source)."""
    repo = os.path.dirname(HERE)
    with open(os.path.join(repo, "dynamo_tpu", "engine", "engine.py")) as f:
        engine = f.read()
    with open(os.path.join(repo, "dynamo_tpu", "tracing",
                           "loop_clock.py")) as f:
        clock = f.read()
    for name in EXPECTED:
        sel = spec(name)["selector"]
        for series in sel["num"] + sel["den"]:
            base, _, label = series.partition("{")
            if label:  # engine_loop_seconds_total{phase="x"} and the kinds
                value = label.split('"')[1]
                assert base + "{{" in engine, series
                assert f'"{value}"' in clock, series
            else:
                stat = base.removeprefix("engine_").removesuffix("_total")
                assert f'"{stat}"' in engine, series
