"""The scheduler loop's clock (dynamo_tpu/tracing/loop_clock.py): per-step
phase accounting and work counters on the tiny engine.

Always on: the eight phases sum to the loop task's wall time, the work
counters go out through ``device_path_stats`` and a step that takes far
longer than its kind leads one to expect logs ONE warning naming the
phase. Under tracing: one ``engine.step`` span per dispatch in the
recorder's ring (never the sink), the same boundaries as profiler
annotations, and a profile written where the caller asks, without the
Python tracer.
"""

import asyncio
import glob
import json
import logging
import os
import re
import time

import pytest

from dynamo_tpu import tracing
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.resilience import faultpoints
from dynamo_tpu.runtime import Context
from dynamo_tpu.tracing import loop_clock
from dynamo_tpu.tracing.span import SpanRecorder

PHASES = loop_clock.PHASES
COUNTER_PAIRS = (  # (part, whole): part <= whole at every scrape
    ("engine_rows_live_total", "engine_rows_dispatched_total"),
    ("engine_prefill_tokens_padding_total",
     "engine_prefill_tokens_dispatched_total"),
    ("engine_attn_live_pages_total", "engine_attn_table_pages_total"),
)
NEW_SERIES = (
    [f'engine_loop_seconds_total{{phase="{p}"}}' for p in PHASES]
    + [f'engine_steps_total{{kind="{k}"}}'
       for k in loop_clock.KINDS.values()]
    + [name for pair in COUNTER_PAIRS for name in pair]
    + ["engine_decode_steps_total", "engine_slow_steps_total",
       "engine_preemptions_total"]
)


@pytest.fixture(autouse=True)
def _tracing_and_faults_off():
    tracing.configure(enabled=False, sink=None)
    tracing.RECORDER.clear()
    faultpoints.reset()
    yield
    tracing.configure(enabled=False, sink=None)
    tracing.RECORDER.clear()
    faultpoints.reset()


def _engine(**kw):
    kw.setdefault("model", ModelConfig.tiny())
    kw.setdefault("num_blocks", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_context", 128)
    kw.setdefault("prefill_chunk", 32)
    return JaxEngine(EngineConfig(**kw), seed=0)


def _req(salt: int, prompt_tokens: int = 24, max_tokens: int = 12):
    toks = [(salt * 37 + 11 * j) % 200 + 5 for j in range(prompt_tokens)]
    return PreprocessedRequest(
        token_ids=toks,
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[],
    )


async def _serve(engine, salt, prompt_tokens=24, max_tokens=12, traced=False):
    ctx = Context(_req(salt, prompt_tokens, max_tokens))
    tc = tracing.TraceContext.for_request(ctx.id) if traced else None
    with tracing.use_trace(tc):
        async for _ in engine.generate(ctx):
            pass
    return ctx.id


async def _wave(engine, base: int, traced: bool = False):
    """Six requests of mixed lengths at once: alternating prefills,
    fused mixed steps, decode windows of every size."""
    return await asyncio.gather(*(
        _serve(engine, base + i, 18 + 7 * i, 8 + 3 * i, traced)
        for i in range(6)))


def test_phases_sum_to_the_loop_tasks_wall_time(run):
    async def main():
        engine = _engine()
        try:
            await _serve(engine, 1)  # the loop task is running from here
            t0, a = time.perf_counter(), engine._clock.totals()
            await _wave(engine, 100)
            await asyncio.sleep(0.05)  # idle counts too
            t1, b = time.perf_counter(), engine._clock.totals()
        finally:
            await engine.close()
        return t1 - t0, {p: b[p] - a[p] for p in PHASES}

    wall, delta = run(main())
    assert all(d >= 0 for d in delta.values()), delta
    assert sum(delta.values()) == pytest.approx(wall, rel=0.01)
    # the wave dispatched, waited for the device, emitted and idled
    for phase in ("dispatch", "device", "emit", "admit", "idle"):
        assert delta[phase] > 0, (phase, delta)


def test_loop_yields_only_when_no_dispatch_waited_for_the_device(run):
    """Between two dispatches the loop does not hand the event loop a
    pass of its own (``yield``): every dispatch waits for the device in
    an executor, and the streams flush the last step's tokens during
    that wait, so a pass of their own only keeps the device idle. The
    streams still hear every token while the engine runs."""
    async def main():
        engine = _engine()
        yields = 0
        mark = engine._clock.mark

        def counting(phase):
            nonlocal yields
            yields += phase == "yield"
            return mark(phase)

        try:
            await _serve(engine, 1)  # programs compiled, loop running
            engine._clock.mark = counting
            steps0 = sum(engine.stats[f"steps_{k}"]
                         for k in loop_clock.KINDS.values())
            seen_while_running = 0
            ctx = Context(_req(7, 24, 40))
            async for _ in engine.generate(ctx):
                seen_while_running += engine._n_active > 0
            steps = sum(engine.stats[f"steps_{k}"]
                        for k in loop_clock.KINDS.values()) - steps0
        finally:
            engine._clock.mark = mark
            await engine.close()
        return yields, steps, seen_while_running

    yields, steps, seen = run(main())
    assert steps >= 10  # a prefill and ten or more decode windows
    assert yields <= 2, (yields, steps)  # at the edges, never a step
    assert seen >= steps // 2  # tokens arrived as the steps went


def test_counters_are_monotone_exported_and_bounded(run):
    async def main():
        engine = _engine()
        scrapes = [engine.device_path_stats()]

        async def scrape():
            while True:
                await asyncio.sleep(0.002)
                scrapes.append(engine.device_path_stats())

        scraper = asyncio.create_task(scrape())
        try:
            await _wave(engine, 200)
            await _wave(engine, 300)
        finally:
            scraper.cancel()
            scrapes.append(engine.device_path_stats())
            await engine.close()
        return scrapes

    scrapes = run(main())
    first, last = scrapes[0], scrapes[-1]
    for name in NEW_SERIES + ["engine_kv_pages_used", "engine_kv_pages_total"]:
        assert name in last, name
    for name in NEW_SERIES:
        values = [s[name] for s in scrapes]
        assert all(b >= a for a, b in zip(values, values[1:])), name
    for part, whole in COUNTER_PAIRS:
        assert last[whole] > first[whole], whole
        assert all(0 <= s[part] <= s[whole] for s in scrapes), part
    rows = last["engine_rows_dispatched_total"]
    assert rows == 4 * last["engine_decode_steps_total"]  # max_batch_size
    assert last["engine_rows_live_total"] > 0
    assert last["engine_prefill_tokens_padding_total"] > 0  # 18 in 32
    dispatches = sum(last[f'engine_steps_total{{kind="{k}"}}']
                     for k in loop_clock.KINDS.values())
    assert dispatches > 0
    assert last["engine_kv_pages_total"] == 63


def test_a_scrape_between_donation_and_reassignment_keeps_its_series(run):
    """On the chip a dispatch donates the KV cache: a scrape that falls
    before the engine takes the new one back finds a deleted array
    (one chip run of PR 26 lost `engine_device`, and with it the run,
    that way). The layout is read once; a scrape that cannot read it
    still carries every other series."""
    async def main():
        fresh, warm = _engine(), _engine()
        try:
            layout = ("engine_leaf_devices_min", "engine_partitioned_bytes",
                      "engine_leaf_bytes_total")
            first = warm.device_path_stats()
            assert all(k in first for k in layout)
            warm.k_cache.delete()
            fresh.k_cache.delete()
            later, never = warm.device_path_stats(), fresh.device_path_stats()
            assert all(later[k] == first[k] for k in layout)
            assert not any(k in never for k in layout)
            for stats in (later, never):
                assert any(k.startswith("engine_device{") for k in stats)
                assert "engine_rows_live_total" in stats
        finally:
            await fresh.close()
            await warm.close()

    run(main())


def test_tracing_off_constructs_no_span_and_no_annotation(run, monkeypatch):
    import jax.profiler

    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)

    async def main():
        engine = _engine()
        try:
            await _wave(engine, 400)
        finally:
            await engine.close()
        return engine.stats["decode_steps"]

    assert run(main()) > 0
    assert tracing.RECORDER.spans() == []
    assert made == []


def test_one_step_span_per_dispatch_and_requests_name_their_step(
        run, monkeypatch):
    import jax.profiler

    names = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            names.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="t", sink=col.ingest)

    async def main():
        engine = _engine()
        try:
            rids = await _wave(engine, 500, traced=True)
        finally:
            await engine.close()
        return engine, rids

    engine, rids = run(main())
    steps = tracing.RECORDER.spans(name=tracing.STEP_SPAN)
    dispatches = sum(engine.stats[f"steps_{k}"]
                     for k in loop_clock.KINDS.values())
    assert len(steps) == dispatches > 0
    assert [s["attrs"]["seq"] for s in steps] == list(range(1, len(steps) + 1))
    for s in steps:
        a = s["attrs"]
        assert set(a["phases"]) == set(PHASES)
        assert sum(a["phases"].values()) == pytest.approx(
            s["dur_ms"], abs=0.02)
        assert a["kind"] in loop_clock.KINDS.values()
        assert a["n"] >= 1 and 0 <= a["live"] <= a["rows"] == 4
    # consecutive steps tile the loop's time: each starts where the
    # last one ended
    for a, b in zip(steps, steps[1:]):
        assert b["ts"] == pytest.approx(a["ts"] + a["dur_ms"] / 1e3, abs=0.05)
    # the loop's trace never reaches the sink's collector
    assert steps[0]["trace_id"] not in col.trace_ids()
    by_seq = {s["attrs"]["seq"]: s for s in steps}
    for rid in rids:
        spans = {s["name"]: s for s in col.timeline(rid)}
        for name in ("engine.queue_wait", "engine.prefill",
                     "engine.first_token"):
            assert spans[name]["attrs"]["step"] in by_seq, name
        served = by_seq[spans["engine.prefill"]["attrs"]["step"]]
        assert served["attrs"]["kind"] in ("prefill", "mixed_step")
        assert (spans["engine.queue_wait"]["attrs"]["step"]
                <= spans["engine.prefill"]["attrs"]["step"]
                == spans["engine.first_token"]["attrs"]["step"])
    assert {"engine.admit", "engine.provision", "engine.dispatch",
            "engine.device", "engine.emit"} <= set(names)


def test_a_delayed_dispatch_logs_one_slow_step_line_naming_dispatch(
        run, caplog):
    async def main():
        engine = _engine()
        try:
            # compile every shape the delayed wave will use, and give
            # each kind its history
            await _serve(engine, 600, max_tokens=24)
            await _serve(engine, 601, max_tokens=24)
            caplog.clear()
            before = engine.stats["slow_steps"]
            faultpoints.arm("mid_dispatch", "delay", after=3, delay_s=1.3)
            await _serve(engine, 602, max_tokens=24)
            return engine.stats["slow_steps"] - before
        finally:
            await engine.close()

    with caplog.at_level(logging.WARNING, logger=loop_clock.__name__):
        counted = run(main())
    lines = [r.getMessage() for r in caplog.records
             if r.name == loop_clock.__name__ and "slow step" in r.getMessage()]
    assert len(lines) == 1 and counted == 1, lines
    line = lines[0]
    assert "decode_window" in line and "COLD" not in line, line
    ms = {p: float(v) for p, v in re.findall(r"(\w+)=(\d+)", line.split(
        "ms by phase:")[1])}
    assert set(ms) == set(PHASES)
    assert ms["dispatch"] >= 1300 and max(ms, key=ms.get) == "dispatch", line


def test_slow_step_rule_on_the_stall_on_record():
    """A 2-step window that took 4.7 s where its twins took 0.6 s is
    named; a plain 4-step window of 1.2 s is not; nor is idle time."""
    stats = {}
    clk = loop_clock.LoopClock(stats, SpanRecorder())
    clk.start("admit")

    def step(n, seconds, idle=0.0):
        clk._info = {"kind": "decode_window", "key": (n,), "n": n,
                     "live": 20, "rows": 32, "cold": False}
        clk._step["device"] += seconds  # as the phases would have booked
        clk._step["idle"] += idle
        clk.step_done()
        return stats["slow_steps"]

    # a kind's first warm step has nothing to be judged by; a compile
    # over a second is named as one, and enters no history
    assert step(4, 1.2) == 0
    clk._history["decode_window"].clear()
    clk._info = {"kind": "decode_window", "key": (4,), "n": 4, "live": 1,
                 "rows": 32, "cold": True}
    clk._step["dispatch"] += 3.9
    clk.step_done()
    assert stats["slow_steps"] == 1 and not clk._history["decode_window"]
    for _ in range(10):
        assert step(2, 0.6) == 1
    assert step(4, 1.2) == 1
    assert step(2, 0.6, idle=30.0) == 1
    assert step(2, 4.7) == 2
    assert step(2, 0.6) == 2  # the stall did not enter the history
    assert step(2, 1.9) == 3
    assert stats["steps_decode_window"] == 17


def test_profile_writes_where_asked_without_the_python_tracer(run, tmp_path):
    from jax.profiler import ProfileData

    tracing.configure(enabled=True, service="t")
    out = str(tmp_path / "kept" / "profile")

    async def main():
        engine = _engine()
        try:
            await _serve(engine, 700)
            capture = asyncio.create_task(engine.profile(0.2, out_dir=out))
            await asyncio.sleep(0.05)
            await _wave(engine, 710)
            return await capture
        finally:
            await engine.close()

    assert run(main()) == out
    files = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1
    host = [p for p in ProfileData.from_file(files[0]).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    events = [e for line in host[0].lines for e in line.events]
    names = {e.name for e in events}
    assert {"engine.profile", "engine.dispatch", "engine.device"} <= names
    assert not [n for n in names if n.startswith("$")], "Python-tracer frames"
    dispatch = next(e for e in events if e.name == "engine.dispatch")
    assert {"kind", "key", "n", "live", "seq"} <= set(dict(dispatch.stats))
    first = min(events, key=lambda e: e.start_ns)
    assert first.name == "engine.profile"


def test_trace_engine_endpoint_serves_the_ring(run):
    from dynamo_tpu.http.service import HttpService, ModelManager

    col = tracing.TraceCollector()
    tracing.configure(enabled=True, service="t", sink=col.ingest)

    async def get(svc, path):
        reader, writer = await asyncio.open_connection("127.0.0.1", svc.port)
        writer.write(f"GET {path} HTTP/1.1\r\nHost: t\r\n"
                     "Connection: close\r\n\r\n".encode())
        await writer.drain()
        data = await reader.read()
        writer.close()
        return json.loads(data.split(b"\r\n\r\n", 1)[1])

    async def main():
        engine = _engine()
        svc = HttpService(ModelManager(), host="127.0.0.1", port=0,
                          trace_collector=col)
        await svc.start()
        try:
            await _wave(engine, 800, traced=True)
            return (await get(svc, "/trace/engine"),
                    await get(svc, "/trace/engine?format=chrome"))
        finally:
            await svc.close()
            await engine.close()

    body, chrome = run(main())
    assert body["spans"] and all(
        s["name"] == tracing.STEP_SPAN for s in body["spans"])
    assert len(chrome["traceEvents"]) == len(body["spans"])
    assert chrome["traceEvents"][0]["args"]["phases"]
