#!/usr/bin/env python
"""Seeded multi-model trace generator + live-stack replay harness.

Two halves, both deterministic from ``--seed``:

* **generator** (``gen_trace``): a synthetic production trace with the
  four load shapes that make multi-model serving hard —

    - *heavy-tail lengths*: prompt bodies and output budgets drawn from
      a capped Pareto (most requests short, a fat tail of long ones);
    - *prefix-sharing populations*: each model owns a handful of shared
      prompt prefixes (system prompts / few-shot preambles) that a
      fraction of its requests extend — the router's overlap scoring
      and the block-hash namespacing both get real traffic shapes;
    - *multi-model mix*: weighted arrivals across the base model and
      the configured LoRA adapters;
    - *diurnal ramp*: a compressed "day" — Poisson arrivals whose rate
      follows one sinusoidal period across the trace, so the replay
      sweeps through quiet and peak load instead of a flat rate.

* **replay** (``replay_trace``): drives the trace through a live
  scaled-down stack — two real JAX engines (tiny model, adapters
  ``alice``/``bob``) behind the KV router on an in-process runtime —
  then reads the **measured** per-model TTFT histograms the workers
  exported through ``load_metrics`` (``hist_ttft_ms``, the same
  vectors the metrics component renders as ``worker_ttft_ms`` /
  ``fleet_ttft_ms``), merges them fleet-wide, and asserts per-model
  p99s from those histograms — not from client-side stopwatches.

``--check-repro`` replays the same seed twice on fresh stacks and
asserts the runs agree: identical trace bytes, identical per-model
request counts in the measured histograms, zero errors in both.

Usage::

    JAX_PLATFORMS=cpu python scripts/trace_replay.py --seed 7 \
        --requests 80 --check-repro
"""

import argparse
import asyncio
import json
import math
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BLOCK = 16
ADAPTERS = ("alice:4", "bob:8:7")
#: arrival mix: base model carries half the traffic, adapters split the
#: rest unevenly (a popular and a niche fine-tune)
MODEL_MIX = (("", 0.5), ("alice", 0.3), ("bob", 0.2))
#: generous per-model TTFT p99 ceiling for the assertion — a CPU tiny
#: model decode step is ~ms; 60s means "the lane is not wedged", which
#: is the strongest claim a shared CI box supports
P99_CEILING_MS = 60_000.0


# ---------------------------------------------------------------- trace

def gen_trace(seed: int, n: int, day_s: float = 8.0) -> list[dict]:
    """Deterministic trace: ``n`` arrivals over one compressed diurnal
    period of ``day_s`` seconds. Same seed -> byte-identical trace."""
    rng = random.Random(seed)
    models = [m for m, _w in MODEL_MIX]
    weights = [w for _m, w in MODEL_MIX]

    # prefix-sharing populations: per model, a few shared preambles of
    # 2-4 blocks; ~60% of a model's requests extend one of them
    pools = {
        m: [[rng.randrange(7, 487) for _ in range(BLOCK * rng.randint(2, 4))]
            for _ in range(3)]
        for m in models
    }

    base_rate = n / day_s  # mean arrivals/s across the whole "day"
    t = 0.0
    out = []
    for i in range(n):
        # diurnal ramp: sinusoidal rate, one period over the trace, never
        # below 20% of the mean (nights are quiet, not silent)
        rate = base_rate * (1.0 + 0.8 * math.sin(2 * math.pi * t / day_s))
        t += rng.expovariate(max(rate, 0.2 * base_rate))
        m = rng.choices(models, weights=weights)[0]
        body = min(96, int(rng.paretovariate(1.6) * 6))  # heavy tail
        toks = list(rng.choice(pools[m])) if rng.random() < 0.6 else []
        toks = toks + [rng.randrange(7, 487) for _ in range(max(body, 4))]
        out.append({
            "t": round(t, 6),
            "model": m,
            "tokens": toks[:192],
            "max_tokens": min(24, 2 + int(rng.paretovariate(2.0) * 3)),
        })
    return out


# --------------------------------------------------------------- replay

def _mk_engine():
    from dynamo_tpu.engine import EngineConfig, JaxEngine
    from dynamo_tpu.models.config import ModelConfig

    cfg = EngineConfig(
        model=ModelConfig.tiny(), num_blocks=128, block_size=BLOCK,
        max_batch_size=8, max_context=512, adapters=ADAPTERS,
        served_model_name="base",
        # 16-token chunks pin the fused step's prefill-length bucket to
        # ONE value, so the program grid the replay can reach is just
        # the segment-count ladder {1,2,4,8} — small enough to warm
        # completely before the timed trace (a cold bucket compiling
        # mid-replay would charge seconds of XLA time to every
        # in-flight TTFT)
        prefill_chunk=16,
    )
    return JaxEngine(cfg, seed=0)


async def _replay(trace: list[dict], speedup: float) -> dict:
    from dynamo_tpu.kv_router import KvEventPublisher, KvRouter
    from dynamo_tpu.kv_router.router import KvRoutedEngine
    from dynamo_tpu.observability.hist import Histogram
    from dynamo_tpu.protocols.common import (
        PreprocessedRequest, SamplingOptions, StopConditions,
    )
    from dynamo_tpu.runtime import (
        Context, DistributedRuntime, LocalBus, LocalStore,
    )

    store, bus = LocalStore(), LocalBus()
    front = await DistributedRuntime.from_settings(store=store, bus=bus)
    workers, engines = [], []
    for _ in range(2):
        w = await DistributedRuntime.from_settings(store=store, bus=bus)
        engine = _mk_engine()
        comp = w.namespace("replay").component("worker")
        pub = KvEventPublisher(w, comp, w.primary_lease_id)
        pub.attach(engine.kv.allocator)
        await comp.endpoint("gen").serve(
            engine, stats_handler=engine.load_metrics)
        workers.append(w)
        engines.append(engine)

    comp = front.namespace("replay").component("worker")
    client = await comp.endpoint("gen").client().start()
    await client.wait_for_instances(5)
    router = await KvRouter(front, comp, block_size=BLOCK).start()
    routed = KvRoutedEngine(router, client)

    # compile the full program-bucket ladder on both engines (with
    # adapters configured every dispatch carries the lora operand, so
    # the engine's own warmup covers the multi-LoRA programs too), pin
    # the adapter stacks, then RESET the TTFT histograms: the replayed
    # trace must measure serving latency, not first-request XLA
    # compiles — on CPU a cold bucket compile stalls the whole queue
    # for seconds and every in-flight TTFT inherits it
    async def _warm(engine):
        await engine.warmup()  # prefill/decode ladders, seg bucket 1
        for m, _w in MODEL_MIX:
            if m:
                await engine.pre_stage_weights(m)

        # the engine's warmup runs its dummies sequentially, so the
        # fused step's SEGMENT-COUNT buckets > 1 are still cold —
        # concurrent waves walk the {2,4,8} ladder
        async def _one(i, m):
            toks = [(37 * i + 11 * j) % 480 + 7 for j in range(40)]
            req = PreprocessedRequest(
                token_ids=toks,
                stop_conditions=StopConditions(max_tokens=4,
                                               ignore_eos=True),
                sampling_options=SamplingOptions(temperature=0.0, seed=0),
                model=m,
                eos_token_ids=[],
            )
            async for _ in engine.generate(Context(req)):
                pass

        models = [m for m, _w in MODEL_MIX]
        for wave in (8, 4, 2):
            await asyncio.gather(*(
                _one(100 * wave + i, models[i % len(models)])
                for i in range(wave)))
        engine.hist_ttft.clear()

    await asyncio.gather(*(_warm(e) for e in engines))

    errors: list[str] = []

    async def one(entry: dict):
        req = PreprocessedRequest(
            token_ids=list(entry["tokens"]),
            stop_conditions=StopConditions(
                max_tokens=entry["max_tokens"], ignore_eos=True),
            sampling_options=SamplingOptions(temperature=0.0, seed=0),
            model=entry["model"],
            eos_token_ids=[],
        ).to_dict()
        got = 0
        async for a in routed.generate(Context(req)):
            if a.error:
                errors.append(str(a.error))
                return
            got += len((a.data or {}).get("token_ids", []))
        if got == 0:
            errors.append(f"empty stream for model {entry['model']!r}")

    t0 = asyncio.get_running_loop().time()
    tasks = []
    for entry in trace:
        # replay the diurnal arrival process, compressed by `speedup`
        delay = entry["t"] / speedup - (
            asyncio.get_running_loop().time() - t0)
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(entry)))
    await asyncio.gather(*tasks)

    # fleet rollup of the MEASURED per-model TTFT histograms — the same
    # merge observability/component.py performs for fleet_ttft_ms
    fleet: dict[str, Histogram] = {}
    for engine in engines:
        for m, vec in engine.load_metrics()["hist_ttft_ms"].items():
            h = Histogram.from_vec(vec)
            if h is None:
                continue
            if m in fleet:
                fleet[m].merge(h)
            else:
                fleet[m] = h

    out = {"requests": len(trace), "errors": len(errors),
           "error_sample": errors[:3], "models": {}}
    for m, h in sorted(fleet.items()):
        out["models"][m or "<base>"] = {
            "count": h.count,
            "ttft_p50_ms": round(h.quantile(0.5) or 0.0, 3),
            "ttft_p99_ms": round(h.quantile(0.99) or 0.0, 3),
        }

    for w in workers:
        await w.shutdown()
    await front.shutdown()
    for engine in engines:
        await engine.close()
    return out


def replay_trace(trace: list[dict], speedup: float = 4.0) -> dict:
    return asyncio.run(_replay(trace, speedup))


# ---------------------------------------------------------- planner sim

class _SimRecorder:
    """The slice of the flight recorder the autopilot consumes: the
    cumulative per-worker (unhealthy, finished) counters."""

    def __init__(self):
        self.counters: dict[int, list[int]] = {}

    def record(self, worker_id: int, breached: bool) -> None:
        c = self.counters.setdefault(worker_id, [0, 0])
        c[1] += 1
        if breached:
            c[0] += 1

    def worker_counters(self) -> dict:
        return {w: (c[0], c[1]) for w, c in self.counters.items()}


def planner_sim(seed: int, ticks: int = 90, tick_s: float = 2.0) -> dict:
    """Fake-clock planner + autopilot decision loop — no live workers.

    Drives the REAL control stack (TelemetryAggregator -> Planner and
    Autopilot -> AdmissionGate) against a scripted three-worker fleet
    on an injected clock: worker 3 starts with a cold XLA bucket grid
    (pre-warm loop), worker 2 breaches hard for a mid-sim window
    (quarantine -> probe -> reinstate), and a diurnal load peak pushes
    utilization over the headroom threshold (measured per-class caps).
    Pure decision loop — deterministic from ``seed``; same seed, same
    JSON."""
    from dynamo_tpu.autopilot import Autopilot, AutopilotConfig
    from dynamo_tpu.autopilot.quarantine import QuarantineConfig
    from dynamo_tpu.kv_router.scheduler import WorkerLoad
    from dynamo_tpu.planner import (
        CapacityModel, Planner, PlannerConfig, SloTargets,
        TelemetryAggregator,
    )
    from dynamo_tpu.planner.admission import AdmissionGate

    rng = random.Random(seed)
    now = [1000.0]
    clk = lambda: now[0]  # noqa: E731

    telemetry = TelemetryAggregator(window_s=30.0, clock=clk)
    planner = Planner(
        telemetry, CapacityModel(400.0, 400.0),
        PlannerConfig(tick_s=tick_s, slo=SloTargets()), clock=clk,
    )
    gate = AdmissionGate(12.0, burst=12.0, clock=clk)
    recorder = _SimRecorder()
    ap = Autopilot(
        telemetry=telemetry, recorder=recorder, gate=gate,
        config=AutopilotConfig(
            interval_s=tick_s, headroom=True, headroom_window_s=20.0,
            prewarm_cooldown_s=6.0,
            quarantine_cfg=QuarantineConfig(
                trip_ticks=2, hold_s=6 * tick_s, probe_ticks=2,
            ),
        ),
        clock=clk,
    )

    WORKERS = (1, 2, 3)
    served = {w: 0 for w in WORKERS}  # cumulative requests_total
    tokens = {w: 0 for w in WORKERS}
    warm = {1: True, 2: True, 3: False}  # worker 3: cold bucket grid
    warm_eta: dict[int, int] = {}  # simulated actuator: ticks to warm
    warm_tick = None
    quarantine_log: list[tuple] = []
    headroom_log: list[tuple] = []
    shed_headroom_prev = 0

    for i in range(ticks):
        now[0] += tick_s
        peak = ticks // 3 <= i < 2 * ticks // 3  # diurnal peak window
        pathology = ticks // 3 + 5 <= i < ticks // 2  # worker 2 breaches

        # offered load through the REAL gate: interactive steady, batch
        # surging at peak (the headroom loop's shedding target)
        for _ in range(rng.randrange(2, 5)):
            d = gate.admit("interactive")
            if d.admitted:
                gate.done("interactive")
        for _ in range(rng.randrange(12, 18) if peak else rng.randrange(0, 3)):
            d = gate.admit("batch")
            if d.admitted:
                gate.done("batch")

        # the fleet's measured plane for this tick
        loads = []
        quarantined_now = set(ap.quarantine.quarantined)
        for w in WORKERS:
            routed = w not in quarantined_now and (warm[w] or w == 3)
            n = rng.randrange(6, 10) if (routed and peak) else \
                rng.randrange(1, 4) if routed else 0
            served[w] += n
            tokens[w] += 8 * n
            for _ in range(n):
                recorder.record(
                    w, pathology and w == 2 and rng.random() < 0.8
                )
            loads.append(WorkerLoad(
                worker_id=w,
                active_requests=7 if peak else 2, total_slots=8,
                waiting=3 if peak else 0,
                kv_active_blocks=96 if peak else 16, kv_total_blocks=128,
                requests_total=served[w], tokens_generated=tokens[w],
                prompt_tokens_total=16 * served[w],
                xla_warm_buckets=4 if warm[w] else 0,
                xla_reachable_buckets=4 if warm[w] else 0,
                ts=now[0],
            ))
        telemetry.observe_loads(loads)

        before = len(ap.quarantine.events)
        directives_before = ap.warmup_directives
        ap.tick()
        planner.tick()
        for ev in ap.quarantine.events[before:]:
            quarantine_log.append((i, ev.action, ev.worker_id))
        if ap.headroom_caps and (not headroom_log
                                 or headroom_log[-1][1] != sorted(
                                     ap.headroom_caps)):
            headroom_log.append((i, sorted(ap.headroom_caps)))
        # simulated warmup actuator: a directive at a cold worker warms
        # its grid two ticks later (the real WarmupListener's role)
        if ap.warmup_directives > directives_before:
            warm_eta.setdefault(3, 2)
        for w in list(warm_eta):
            warm_eta[w] -= 1
            if warm_eta[w] <= 0:
                del warm_eta[w]
                if not warm[w]:
                    warm[w] = True
                    warm_tick = i + 1

    shed_headroom_prev = gate.stats["shed_headroom_total"]
    return {
        "ticks": ticks,
        "warmup_directives": ap.warmup_directives,
        "worker3_warm_tick": warm_tick,
        "prewarm_holds_now": sorted(ap.prewarm_hold),
        "quarantine_events": quarantine_log,
        "quarantined_now": ap.quarantine.quarantined,
        "headroom_caps_applied": len(headroom_log),
        "admission": {
            "admitted_total": gate.stats["admitted_total"],
            "shed_total": gate.stats["shed_total"],
            "shed_headroom_total": shed_headroom_prev,
        },
        "planner_decode_replicas":
            planner.decode_guard.current
            if hasattr(planner.decode_guard, "current")
            else None,
        "planner_ticks": planner.stats["ticks"],
    }


def check_sim(result: dict) -> None:
    """The four loops must all have closed inside the sim."""
    actions = [(a, w) for _i, a, w in result["quarantine_events"]]
    assert result["warmup_directives"] >= 1, "pre-warm loop never fired"
    assert result["worker3_warm_tick"] is not None, "worker 3 never warmed"
    assert result["prewarm_holds_now"] == [], "stale pre-warm hold"
    assert ("quarantine", 2) in actions, "worker 2 never quarantined"
    assert ("reinstate", 2) in actions, "worker 2 never reinstated"
    assert result["quarantined_now"] == [], "quarantine never cleared"
    assert result["admission"]["shed_headroom_total"] > 0, \
        "headroom loop never shed"
    assert result["planner_ticks"] == result["ticks"]


def check(result: dict, trace: list[dict]) -> None:
    """Per-model TTFT p99 assertions from the measured histograms."""
    assert result["errors"] == 0, f"replay errors: {result['error_sample']}"
    want = {m or "<base>": sum(1 for e in trace if e["model"] == m)
            for m, _w in MODEL_MIX}
    for name, n in want.items():
        got = result["models"].get(name)
        assert got is not None, f"no measured TTFT histogram for {name}"
        assert got["count"] == n, (
            f"{name}: histogram count {got['count']} != {n} arrivals")
        assert 0.0 < got["ttft_p99_ms"] <= P99_CEILING_MS, (
            f"{name}: p99 {got['ttft_p99_ms']}ms outside (0, "
            f"{P99_CEILING_MS}]")
        assert got["ttft_p50_ms"] <= got["ttft_p99_ms"], name


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=80)
    ap.add_argument("--day-s", type=float, default=8.0,
                    help="trace duration = one diurnal period, seconds")
    ap.add_argument("--speedup", type=float, default=4.0,
                    help="replay wall-clock compression factor")
    ap.add_argument("--dump-trace", metavar="PATH",
                    help="write the generated trace as JSONL and exit")
    ap.add_argument("--check-repro", action="store_true",
                    help="replay the seed twice on fresh stacks and "
                         "assert the runs agree")
    ap.add_argument("--planner-sim", action="store_true",
                    help="fake-clock planner + autopilot decision loop "
                         "over a scripted fleet — no live workers, no "
                         "JAX; asserts all four autopilot loops close "
                         "and (with --check-repro) bit-identical "
                         "decisions across runs")
    ap.add_argument("--sim-ticks", type=int, default=90)
    args = ap.parse_args()

    if args.planner_sim:
        result = planner_sim(args.seed, ticks=args.sim_ticks)
        check_sim(result)
        print(json.dumps({"sim1": result}))
        if args.check_repro:
            result2 = planner_sim(args.seed, ticks=args.sim_ticks)
            check_sim(result2)
            assert json.dumps(result) == json.dumps(result2), \
                "planner sim not deterministic"
            print(json.dumps({"sim2": result2, "reproducible": True}))
        return 0

    trace = gen_trace(args.seed, args.requests, day_s=args.day_s)
    if args.dump_trace:
        with open(args.dump_trace, "w") as f:
            for e in trace:
                f.write(json.dumps(e) + "\n")
        print(f"wrote {len(trace)} entries to {args.dump_trace}")
        return 0

    # determinism of the generator itself: same seed, same bytes
    again = gen_trace(args.seed, args.requests, day_s=args.day_s)
    assert json.dumps(trace) == json.dumps(again), "generator not seeded"

    result = replay_trace(trace, speedup=args.speedup)
    check(result, trace)
    print(json.dumps({"run1": result}))

    if args.check_repro:
        result2 = replay_trace(trace, speedup=args.speedup)
        check(result2, trace)
        for name, got in result["models"].items():
            got2 = result2["models"][name]
            assert got["count"] == got2["count"], (
                f"{name}: run1 served {got['count']}, run2 {got2['count']}")
        print(json.dumps({"run2": result2, "reproducible": True}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
