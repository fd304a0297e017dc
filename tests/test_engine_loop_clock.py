"""The scheduler loop's clock (dynamo_tpu/tracing/loop_clock.py): per-step
phase accounting and work counters on the tiny engine.

Always on: the eight phases sum to the loop task's wall time, the work
counters go out through ``device_path_stats``, a step's seconds are
booked by its kind and by whether a program was outstanding on the
device, and a step that takes far longer than its kind leads one to
expect logs ONE warning naming the phase. Under tracing: one ``engine.step`` span per dispatch in the
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
KINDS = tuple(loop_clock.KINDS.values())
STEP_SECONDS = 'engine_step_seconds_total{kind="%s"}'
STEP_EXPOSED = 'engine_step_exposed_seconds_total{kind="%s"}'
DEVICE_STEPS = 'engine_device_steps_total{kind="%s"}'
KIND_SERIES = [f % k for f in (STEP_SECONDS, STEP_EXPOSED, DEVICE_STEPS)
               for k in KINDS]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: a dense, an expert and a conv-state model (the benchmark's stand-ins)
MODELS = {"dense": None, "expert": "tiny-olmoe", "conv_state": "tiny-lfm2"}
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


def _model(name: str) -> ModelConfig:
    if MODELS[name] is None:
        return ModelConfig.tiny()
    with open(os.path.join(REPO, "chipbench", "testdata", MODELS[name],
                           "config.json")) as f:
        return ModelConfig.from_hf_config(
            dict(json.load(f), torch_dtype="float32"))


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


# ---------------- a step's seconds by its kind, covered and exposed ----------------


def _by_kind(stats, series=STEP_SECONDS):
    return sum(stats[series % k] for k in KINDS)


_SERVED = {}


def _served(run, name):
    """Two waves on ``name``'s engine, scraped every 2 ms and once more
    after the drain: (scrapes, the clock's phase totals at that last
    scrape, the open step's seconds but ``idle``)."""
    if name in _SERVED:
        return _SERVED[name]

    async def main():
        engine = _engine(model=_model(name))
        scrapes = [engine.device_path_stats()]

        async def scrape():
            while True:
                await asyncio.sleep(0.002)
                scrapes.append(engine.device_path_stats())

        scraper = asyncio.create_task(scrape())
        try:
            await _wave(engine, 900)
            await _wave(engine, 950)
            await asyncio.sleep(0.05)  # drained: the loop idles
        finally:
            scraper.cancel()
            scrapes.append(engine.device_path_stats())
            clk = engine._clock
            totals = clk.totals()
            open_step = sum(v for p, v in clk._step.items() if p != "idle")
            if clk.phase not in (None, "idle"):
                open_step += time.perf_counter() - clk._t
            await engine.close()
        return scrapes, totals, open_step

    _SERVED[name] = run(main())
    return _SERVED[name]


@pytest.mark.parametrize("name", sorted(MODELS))
def test_kinds_seconds_sum_to_the_phases_but_idle(run, name):
    scrapes, totals, open_step = _served(run, name)
    last = scrapes[-1]
    busy = sum(v for p, v in totals.items() if p != "idle")
    assert _by_kind(last) > 0
    # every second but idle's is booked under the kind of its step, but
    # for the open step's (what followed the last dispatch)
    assert _by_kind(last) + open_step == pytest.approx(busy, rel=1e-3)
    assert _by_kind(last) == pytest.approx(busy, rel=0.01)
    # and a kind's steps are counted where its seconds are
    for k in KINDS:
        assert (last[STEP_SECONDS % k] > 0) == (
            last[f'engine_steps_total{{kind="{k}"}}'] > 0), k


@pytest.mark.parametrize("name", sorted(MODELS))
def test_exposed_is_a_part_of_a_kinds_seconds_monotone_and_exported(
        run, name):
    scrapes, _totals, _open = _served(run, name)
    for series in KIND_SERIES:
        values = [s[series] for s in scrapes]  # KeyError: not exported
        assert all(b >= a for a, b in zip(values, values[1:])), series
    for s in scrapes:
        for k in KINDS:
            assert 0 <= s[STEP_EXPOSED % k] <= s[STEP_SECONDS % k] + 1e-6, k
    last = scrapes[-1]
    # the device had work for a part of the time, and not for all of it
    assert 0 < _by_kind(last, STEP_EXPOSED) < _by_kind(last)
    assert last[STEP_SECONDS % "verify"] == 0  # no speculation here


@pytest.mark.parametrize("name", sorted(MODELS))
def test_device_steps_but_prefills_are_the_decode_steps_after_a_drain(
        run, name):
    scrapes, _totals, _open = _served(run, name)
    last = scrapes[-1]
    assert sum(last[DEVICE_STEPS % k] for k in KINDS if k != "prefill") \
        == last["engine_decode_steps_total"] > 0
    # a window of n counts n, every other dispatch 1
    assert last[DEVICE_STEPS % "decode_window"] > last[
        'engine_steps_total{kind="decode_window"}']
    for k in ("mixed_step", "prefill"):
        assert last[DEVICE_STEPS % k] == last[
            f'engine_steps_total{{kind="{k}"}}'] > 0, k


def _serve_measured(run, setup=None, max_tokens=24, **engine_kw):
    """One request on a warm engine: the rise of (seconds, exposed) over
    all kinds and of the loop's ``emit`` seconds. ``setup(engine)`` arms
    what the measured request is to meet; without one the reading is
    the file's baseline, taken once."""
    base = ("base", max_tokens, tuple(sorted(engine_kw.items())))
    if setup is None and base in _SERVED:
        return _SERVED[base]

    async def main():
        engine = _engine(**engine_kw)
        try:
            await _serve(engine, 40, max_tokens=max_tokens)  # compiled
            await _serve(engine, 41, max_tokens=max_tokens)
            if setup is not None:
                setup(engine)
            a, ta = engine.device_path_stats(), engine._clock.totals()
            await _serve(engine, 42, max_tokens=max_tokens)
            await asyncio.sleep(0.02)
            b, tb = engine.device_path_stats(), engine._clock.totals()
        finally:
            await engine.close()
        return (_by_kind(b) - _by_kind(a),
                _by_kind(b, STEP_EXPOSED) - _by_kind(a, STEP_EXPOSED),
                tb["emit"] - ta["emit"])

    out = run(main())
    if setup is None:
        _SERVED[base] = out
    return out


def test_a_delay_before_the_enqueue_is_exposed(run):
    """``mid_dispatch`` sits before the jit call: nothing is outstanding
    while a dispatch of the UNCHAINED loop stalls there. (The chained
    loop has the program before it enqueued and not yet fetched: by the
    clock's definition, enqueue to result on the host, such a stall is
    covered, although the device may have run dry behind it; the
    trace's idle share is what sees that. docs/tracing.md.)"""
    delay = 0.5
    base_s, base_x, _ = _serve_measured(run, decode_pipeline=False)
    seconds, exposed, _ = _serve_measured(
        run, lambda _e: faultpoints.arm(
            "mid_dispatch", "delay", after=3, delay_s=delay),
        decode_pipeline=False)
    assert exposed >= delay
    assert exposed - base_x >= 0.9 * delay
    covered, base_c = seconds - exposed, base_s - base_x
    assert covered - base_c < 0.3 * delay


def test_a_delay_inside_the_result_fetch_is_covered(run, monkeypatch):
    """The program stays outstanding until its result is on the host:
    a slow fetch is the device's time (or the link's), not the host's."""
    import jax

    delay, fetches = 0.05, []
    real = jax.device_get

    def slow(x):
        fetches.append(1)
        time.sleep(delay)
        return real(x)

    base_s, base_x, _ = _serve_measured(run)
    seconds, exposed, _ = _serve_measured(
        run, lambda _e: monkeypatch.setattr(jax, "device_get", slow))
    monkeypatch.undo()
    injected = delay * len(fetches)
    assert len(fetches) >= 6  # the first token and the windows
    covered, base_c = seconds - exposed, base_s - base_x
    assert covered - base_c >= 0.9 * injected
    assert exposed - base_x < 0.2 * injected


@pytest.mark.parametrize("pipeline", [False, True])
def test_a_pipelined_windows_emit_is_not_exposed(run, pipeline):
    """With ``decode_pipeline`` window k+1 is enqueued before window k
    is fetched, so the emission of k has a program outstanding beside
    it; without, the device has nothing while the loop emits."""
    def slow_emit(engine):
        emit = engine._emit_token

        def slow(*a, **kw):
            time.sleep(0.005)
            return emit(*a, **kw)

        engine._emit_token = slow

    _seconds, exposed, emit = _serve_measured(
        run, slow_emit, max_tokens=48, decode_pipeline=pipeline)
    assert emit >= 48 * 0.005
    if pipeline:
        # the first token's and the last window's emission are exposed
        assert exposed < 0.5 * emit
    else:
        assert exposed >= 0.95 * emit


def test_step_span_carries_exposed_ms(run):
    tracing.configure(enabled=True, service="t")

    async def main():
        engine = _engine()
        try:
            await _wave(engine, 1000)
            return engine.device_path_stats()
        finally:
            await engine.close()

    stats = run(main())
    steps = tracing.RECORDER.spans(name=tracing.STEP_SPAN)
    assert steps
    for s in steps:
        a = s["attrs"]
        busy = s["dur_ms"] - a["phases"]["idle"]
        assert 0 <= a["exposed_ms"] <= busy + 0.01, a
    # the spans and the counters are one booking
    for k in KINDS:
        assert sum(s["attrs"]["exposed_ms"] for s in steps
                   if s["attrs"]["kind"] == k) == pytest.approx(
            1e3 * stats[STEP_EXPOSED % k], abs=0.01 * len(steps) + 0.01)


def test_outstanding_on_a_bare_clock():
    """The definition on made-up times: covered runs from the enqueue
    that raised the count from 0 to the fetch that brought it back; a
    fetch settles every program enqueued before its own; ``step_done``
    splits an open interval so that steps tile it."""
    import threading

    stats, now = {}, [100.0]
    clk = loop_clock.LoopClock(stats, SpanRecorder())
    real = time.perf_counter
    time.perf_counter = lambda: now[0]
    try:
        clk.start("admit")
        clk._th_thread = threading.get_ident()  # as inside a thunk
        clk._th = [now[0], None, None]

        def step(kind="decode_window", n=4):
            clk._info = {"kind": kind, "key": (n,), "n": n, "live": 1,
                         "rows": 4, "cold": True}
            clk.step_done()
            return (stats["step_seconds_" + kind],
                    stats["step_exposed_seconds_" + kind],
                    stats["device_steps_" + kind])

        now[0] += 1.0            # 1 s of host work, nothing outstanding
        clk.enqueued()           # window 1
        first = clk.programs
        now[0] += 2.0
        clk.landed(first)        # fetched: 2 s covered
        now[0] += 0.5            # emission, exposed
        assert step() == (3.5, 1.5, 4)
        clk.enqueued()           # a prefill chunk nobody fetches ...
        now[0] += 1.0
        clk.enqueued()           # ... then window 2, and window 3
        second = clk.programs
        clk.enqueued()
        now[0] += 1.0
        clk.landed(second)       # window 2's tokens: 3 is outstanding
        now[0] += 1.0
        assert step() == (6.5, 1.5, 8)   # covered all through
        now[0] += 0.25
        clk.landed()             # the newest: nothing is outstanding
        now[0] += 0.75
        assert step("mixed_step", 1) == (1.0, 0.75, 1)
        clk.enqueued()
        now[0] += 1.0
        clk.mark("idle")         # nobody will fetch it: dropped
        now[0] += 10.0
        clk.mark("admit")
        now[0] += 0.5
        assert step("prefill", 1) == (1.5, 0.5, 1)
    finally:
        time.perf_counter = real


# ---------------- POST /profile and an engine with nothing to do ----------------


def _profile_event_s(out):
    """How long the capture's ``engine.profile`` annotation lasted."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = [e for p in ProfileData.from_file(path).planes
              if p.name == "/host:CPU" for line in p.lines
              for e in line.events if e.name == "engine.profile"]
    assert len(events) == 1
    return events[0].duration_ns / 1e9


@pytest.mark.parametrize("case", ["traffic", "a_request_later", "idle"])
def test_profile_does_not_end_before_a_dispatch_if_one_comes(
        run, tmp_path, case):
    """A capture lasts ``seconds`` when the loop dispatched in that
    time; on an engine with nothing to do it goes on until the first
    step after it is done, ``3 * seconds`` more at most."""
    seconds, out = 0.4, str(tmp_path / "profile")

    async def main():
        engine = _engine()
        try:
            await _serve(engine, 1100)
            await asyncio.sleep(0.05)
            before = engine._clock.seq
            capture = asyncio.create_task(engine.profile(seconds, out))
            if case == "traffic":
                while not capture.done():
                    await _serve(engine, 1101)
            elif case == "a_request_later":
                await asyncio.sleep(2 * seconds)
                await _serve(engine, 1102, max_tokens=4)
            await capture
            return engine._clock.seq - before
        finally:
            await engine.close()

    steps, lasted = run(main()), _profile_event_s(out)
    if case == "traffic":
        assert steps > 0 and seconds <= lasted < seconds + 0.2
    elif case == "a_request_later":
        # it ended with the request's first step, not at the cap (the
        # capture's own start-up shifts the request towards its start)
        assert steps > 0 and seconds < lasted < 3 * seconds
    else:
        assert steps == 0 and 4 * seconds <= lasted < 4 * seconds + 0.2
