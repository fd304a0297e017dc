"""Fleet autopilot: quarantine hysteresis, windowed tails, control loops.

The flap-resistance matrix is the heart of this module (ISSUE 20
satellite): a single slow scrape, one autopsy burst, or a sub-floor
breach blip must NOT quarantine a worker, while a genuine breach-rate
spike must — and a quarantined worker's held streams must drain
cleanly through the routed stack. Everything control-plane runs on the
injected FakeClock; the bus-driven listeners run on the in-process
LocalBus exactly like the reshard actuator tests they mirror.
"""

import asyncio

import jax
import pytest

from dynamo_tpu.autopilot import (
    AUTOPILOT_HEALTH_SUBJECT,
    AUTOPILOT_WARMUP_SUBJECT,
    Autopilot,
    AutopilotConfig,
    HealthDirective,
    QuarantineConfig,
    QuarantineManager,
    TailTracker,
    WarmupDirective,
    WarmupListener,
)
from dynamo_tpu.autopilot.tails import delta_hist
from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.kv_router import KvRouter
from dynamo_tpu.kv_router.costmodel import tail_adjusted_ttft_ms
from dynamo_tpu.kv_router.indexer import OverlapScores
from dynamo_tpu.kv_router.publisher import KvEventPublisher
from dynamo_tpu.kv_router.router import KvRoutedEngine
from dynamo_tpu.kv_router.scheduler import (
    KvScheduler,
    ProcessedEndpoints,
    SchedulerConfig,
    WorkerLoad,
)
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.observability.flight import FlightRecorder, SloPolicy
from dynamo_tpu.observability.hist import MS_BUCKETS, Histogram
from dynamo_tpu.planner.admission import AdmissionGate, SloClass
from dynamo_tpu.planner.telemetry import ClusterSnapshot
from dynamo_tpu.protocols.common import (
    PreprocessedRequest,
    SamplingOptions,
    StopConditions,
)
from dynamo_tpu.resilience.quarantine import QuarantineListener
from dynamo_tpu.runtime import Context, DistributedRuntime, LocalBus, LocalStore

from conftest import FakeClock

#: ONE tiny config shared module-wide (ModelConfig hashes by identity,
#: so both routed-stack engines share compiled programs)
TINY = ModelConfig.tiny()
PARAMS = llama.init_params(TINY, jax.random.key(0))


# ---------------------------------------------------------------------------
# quarantine hysteresis: the flap-resistance matrix
# ---------------------------------------------------------------------------


def _mgr(clk, **kw):
    kw.setdefault("trip_ticks", 2)
    kw.setdefault("min_breaches", 3)
    kw.setdefault("breach_frac", 0.5)
    kw.setdefault("hold_s", 20.0)
    kw.setdefault("probe_ticks", 2)
    return QuarantineManager(QuarantineConfig(**kw), clock=clk)


def test_single_slow_scrape_is_not_evidence():
    """A tick with no counter movement (slow scrape / idle worker)
    advances nothing in either direction — the unhealthy streak neither
    grows nor resets."""
    clk = FakeClock()
    m = _mgr(clk)
    # two workers so the cap allows one quarantine
    m.step({1: (0, 0), 2: (0, 0)})
    clk.advance(2.0)
    m.step({1: (5, 6), 2: (0, 5)})  # tick 1: unhealthy, streak 1
    assert m.quarantined == []
    clk.advance(2.0)
    m.step({1: (5, 6), 2: (0, 5)})  # slow scrape: zero deltas
    clk.advance(2.0)
    m.step({2: (0, 5)})  # no scrape for worker 1 at all
    assert m.quarantined == []
    clk.advance(2.0)
    # the streak survived the evidence-free ticks: one more unhealthy
    # observed tick trips (streak 2 >= trip_ticks)
    m.step({1: (10, 12), 2: (0, 5)})
    assert m.quarantined == [1]


def test_one_autopsy_burst_does_not_quarantine():
    """One unhealthy tick (trip_ticks=2) followed by a clean observed
    tick resets the streak — a burst never trips on its own."""
    clk = FakeClock()
    m = _mgr(clk)
    m.step({1: (0, 0), 2: (0, 0)})
    clk.advance(2.0)
    m.step({1: (6, 6), 2: (0, 5)})  # the burst: 6/6 breached
    assert m.quarantined == [] and m.state(1) == "healthy"
    clk.advance(2.0)
    m.step({1: (6, 16), 2: (0, 9)})  # 0/10 clean — streak resets
    clk.advance(2.0)
    m.step({1: (12, 22), 2: (0, 12)})  # unhealthy again: streak back to 1
    assert m.quarantined == []
    assert m.quarantines_total == 0


def test_breach_floor_gates_ratio():
    """2 breaches out of 2 finishes is a blip, not a pathology: below
    min_breaches the ratio never counts as unhealthy."""
    clk = FakeClock()
    m = _mgr(clk, min_breaches=3)
    m.step({1: (0, 0), 2: (0, 0)})
    for i in range(1, 6):
        clk.advance(2.0)
        m.step({1: (2 * i, 2 * i), 2: (0, 5 * i)})  # 2/2 per tick, 100%
    assert m.quarantined == []
    assert m.quarantines_total == 0


def test_lone_worker_is_never_quarantined():
    clk = FakeClock()
    m = _mgr(clk)
    m.step({1: (0, 0)})
    for i in range(1, 8):
        clk.advance(2.0)
        m.step({1: (10 * i, 10 * i)})  # 10/10 breached every tick
    assert m.quarantined == []  # cap = int(0.5 * 1) = 0


def test_quarantined_share_is_capped():
    """With both workers spiking, at most max_quarantined_frac of the
    pool goes out — the loop degrades to serve-with-breaches."""
    clk = FakeClock()
    m = _mgr(clk)
    m.step({1: (0, 0), 2: (0, 0)})
    for i in range(1, 5):
        clk.advance(2.0)
        m.step({1: (10 * i, 10 * i), 2: (10 * i, 10 * i)})
    assert len(m.quarantined) == 1  # cap = int(0.5 * 2) = 1


def test_full_lifecycle_trip_probe_reinstate():
    clk = FakeClock()
    m = _mgr(clk, hold_s=10.0)
    m.step({1: (0, 0), 2: (0, 0)})
    clk.advance(2.0)
    m.step({1: (5, 6), 2: (0, 5)})
    clk.advance(2.0)
    ev = m.step({1: (10, 12), 2: (0, 9)})
    assert [e.action for e in ev] == ["quarantine"]
    assert m.state(1) == "quarantined"
    # held streams still breach while they drain — pre-quarantine
    # traffic must not extend the hold or re-trip on probe entry
    clk.advance(2.0)
    m.step({1: (30, 33), 2: (0, 12)})
    assert m.state(1) == "quarantined"  # hold is purely time-based
    clk.advance(9.0)  # past held_until (10s from the trip)
    ev = m.step({1: (30, 33), 2: (0, 14)})
    assert [e.action for e in ev] == ["probe"]
    # two clean observed ticks reinstate (an evidence-free tick in the
    # middle is neutral)
    clk.advance(2.0)
    m.step({1: (30, 40), 2: (0, 16)})
    clk.advance(2.0)
    m.step({1: (30, 40), 2: (0, 16)})  # no movement: neutral
    assert m.state(1) == "probe"
    clk.advance(2.0)
    ev = m.step({1: (30, 48), 2: (0, 18)})
    assert [e.action for e in ev] == ["reinstate"]
    assert m.state(1) == "healthy" and m.reinstates_total == 1


def test_dirty_probe_requarantines_with_backoff():
    clk = FakeClock()
    m = _mgr(clk, hold_s=10.0, backoff=2.0, max_hold_s=25.0)
    m.step({1: (0, 0), 2: (0, 0)})
    clk.advance(2.0)
    m.step({1: (5, 6), 2: (0, 5)})
    clk.advance(2.0)
    m.step({1: (10, 12), 2: (0, 9)})
    assert m.state(1) == "quarantined"
    clk.advance(10.0)
    m.step({1: (10, 12), 2: (0, 11)})
    assert m.state(1) == "probe"
    clk.advance(2.0)
    ev = m.step({1: (20, 22), 2: (0, 13)})  # still sick: dirty probe
    assert [e.action for e in ev] == ["requarantine"]
    assert m.requarantines_total == 1
    h = m._workers[1]
    assert h.hold_s == pytest.approx(20.0)  # 10 * backoff
    # a second dirty probe caps at max_hold_s
    clk.advance(20.0)
    m.step({1: (20, 22), 2: (0, 15)})
    clk.advance(2.0)
    m.step({1: (30, 32), 2: (0, 17)})
    assert h.hold_s == pytest.approx(25.0)


def test_counter_reset_rebases_evidence():
    """A recorder restart makes deltas negative: evidence starts over
    instead of tripping on garbage."""
    clk = FakeClock()
    m = _mgr(clk)
    m.step({1: (0, 0), 2: (0, 0)})
    clk.advance(2.0)
    m.step({1: (5, 6), 2: (0, 5)})  # streak 1
    clk.advance(2.0)
    m.step({1: (3, 4), 2: (0, 7)})  # reset: negative delta
    clk.advance(2.0)
    m.step({1: (8, 10), 2: (0, 9)})  # 5/6 unhealthy — but streak was 0
    assert m.quarantined == []


def test_forget_clears_departed_worker():
    clk = FakeClock()
    m = _mgr(clk)
    m.step({1: (0, 0), 2: (0, 0)})
    clk.advance(2.0)
    m.step({1: (5, 6), 2: (0, 5)})
    clk.advance(2.0)
    m.step({1: (10, 12), 2: (0, 9)})
    assert m.quarantined == [1]
    m.forget(1)
    assert m.quarantined == [] and m.state(1) == "healthy"


# ---------------------------------------------------------------------------
# windowed tails
# ---------------------------------------------------------------------------


def _vec(values):
    h = Histogram(MS_BUCKETS)
    for v in values:
        h.observe(v)
    return h.to_vec()


def test_tail_tracker_windows_out_old_history():
    """A worker that WAS slow but recovered must not be priced at its
    cumulative past: the windowed tail reflects only recent samples."""
    clk = FakeClock(1000.0)
    tt = TailTracker(window_s=10.0, q=0.99, min_count=8, clock=clk)
    slow = [5000.0] * 50  # the bad era
    tt.observe(1, {"queue_wait_ms": _vec(slow)}, ts=clk())
    clk.advance(12.0)  # bad era ages out of the window
    tt.observe(1, {"queue_wait_ms": _vec(slow)}, ts=clk())
    clk.advance(5.0)
    fast = slow + [2.0] * 20  # cumulative: old stalls + new fast era
    tt.observe(1, {"queue_wait_ms": _vec(fast)}, ts=clk())
    tail = tt.tail_ms(1)
    assert tail is not None and tail < 50.0  # windows out the 5s stalls


def test_tail_tracker_sees_fresh_pathology():
    """The inverse: a worker that BECAME slow shows its new tail even
    though the cumulative mean still looks good."""
    clk = FakeClock(1000.0)
    tt = TailTracker(window_s=10.0, q=0.99, min_count=8, clock=clk)
    fast = [2.0] * 500
    tt.observe(1, {"queue_wait_ms": _vec(fast)}, ts=clk())
    clk.advance(11.0)
    tt.observe(1, {"queue_wait_ms": _vec(fast)}, ts=clk())
    clk.advance(5.0)
    sick = fast + [4000.0] * 10  # last 5s: stalls
    tt.observe(1, {"queue_wait_ms": _vec(sick)}, ts=clk())
    tail = tt.tail_ms(1)
    assert tail is not None and tail > 1000.0


def test_tail_min_count_gates_thin_evidence():
    clk = FakeClock(1000.0)
    tt = TailTracker(window_s=10.0, min_count=8, clock=clk)
    tt.observe(1, {"queue_wait_ms": _vec([1.0])}, ts=clk())
    assert tt.tail_ms(1) is None  # single snapshot: no window at all
    clk.advance(11.0)
    tt.observe(1, {"queue_wait_ms": _vec([1.0] * 4)}, ts=clk())
    assert tt.tail_ms(1) is None  # 3 window samples < min_count
    clk.advance(2.0)
    tt.observe(1, {"queue_wait_ms": _vec([1.0] * 20)}, ts=clk())
    assert tt.tail_ms(1) is not None


def test_tail_counter_reset_rebases_window():
    clk = FakeClock(1000.0)
    tt = TailTracker(window_s=10.0, min_count=1, clock=clk)
    tt.observe(1, {"queue_wait_ms": _vec([1.0] * 20)}, ts=clk())
    clk.advance(11.0)
    # engine restarted: cumulative counts went DOWN
    tt.observe(1, {"queue_wait_ms": _vec([1.0] * 5)}, ts=clk())
    assert tt.tail_ms(1) is None
    assert tt.rebases == 1
    # next scrape pairs against the rebased snapshot cleanly
    clk.advance(2.0)
    tt.observe(1, {"queue_wait_ms": _vec([1.0] * 9)}, ts=clk())
    assert tt.tail_ms(1) is not None


def test_delta_hist_rejects_bounds_skew():
    a = Histogram(MS_BUCKETS)
    a.observe(5.0)
    b = Histogram(MS_BUCKETS[:-4])
    b.observe(5.0)
    assert delta_hist(a.to_vec(), b.to_vec()) is None
    assert delta_hist(a.to_vec(), {"garbage": 1}) is None
    assert delta_hist(a.to_vec(), None) is not None


def test_tail_adjusted_ttft_floors_prediction():
    assert tail_adjusted_ttft_ms(10.0, None) == 10.0
    assert tail_adjusted_ttft_ms(10.0, 3.0) == 10.0  # healthy tail: model wins
    assert tail_adjusted_ttft_ms(10.0, 250.0) == 250.0  # bimodal: tail floors


# ---------------------------------------------------------------------------
# scheduler: soft exclusion + tail folding
# ---------------------------------------------------------------------------


def _load(wid, **kw):
    kw.setdefault("total_slots", 8)
    kw.setdefault("kv_total_blocks", 100)
    return WorkerLoad(worker_id=wid, **kw)


def test_scheduler_soft_excludes_quarantined_and_held():
    s = KvScheduler(config=SchedulerConfig(cost_model=False, tail_aware=False))
    eps = ProcessedEndpoints([_load(1), _load(2), _load(3)])
    s.set_autopilot_health(quarantined=[1], prewarm_hold=[3])
    picked = s.select_worker(eps, OverlapScores(), 4)
    assert picked == 2
    s.request_finished(picked)
    # last-resort semantics: an entirely-excluded pool still serves
    s.set_autopilot_health(quarantined=[1, 2], prewarm_hold=[3])
    picked = s.select_worker(eps, OverlapScores(), 4)
    assert picked in (1, 2, 3)
    s.request_finished(picked)
    # full replacement: a reinstated worker clears automatically
    s.set_autopilot_health(quarantined=[], prewarm_hold=[])
    assert s.quarantined == set() and s.prewarm_hold == set()


def test_scheduler_autopilot_ttl_expires_stale_directives():
    clk = FakeClock()
    s = KvScheduler(
        config=SchedulerConfig(cost_model=False, tail_aware=False,
                               autopilot_ttl_s=30.0),
        clock=clk,
    )
    eps = ProcessedEndpoints([_load(1), _load(2)])
    s.set_autopilot_health(quarantined=[1])
    assert s.select_worker(eps, OverlapScores(), 4) == 2
    s.request_finished(2)
    # the autopilot dies; its last directive must not pin routing
    clk.advance(31.0)
    s.select_worker(eps, OverlapScores(), 4)
    assert s.quarantined == set()


def test_scheduler_tail_fold_reroutes_bimodal_worker():
    """Two cost-identical candidates; worker 1's windowed queue-wait
    tail spikes — the fold reprices it and routing flips to worker 2."""
    clk = FakeClock(1000.0)
    s = KvScheduler(
        config=SchedulerConfig(tail_window_s=10.0, tail_min_count=8),
        clock=clk,
    )

    def eps_with(hists1):
        mk = lambda wid, h: WorkerLoad(  # noqa: E731
            worker_id=wid, total_slots=8, kv_total_blocks=100,
            cost_obs=50, link_gbps={"host": 1.0}, prefill_tok_s=10_000.0,
            block_bytes=1 << 20, block_size=16, hists=h, ts=clk(),
        )
        return ProcessedEndpoints([mk(1, hists1), mk(2, {})])

    # identical calibration: worker 1 wins the id tie-break while its
    # tail window is empty
    assert s.select_worker(eps_with({}), OverlapScores(), 4) == 1
    s.request_finished(1)
    # build worker 1 a bimodal window: baseline snapshot, then stalls
    base = [2.0] * 100
    s.tails.observe(1, {"queue_wait_ms": _vec(base)}, ts=clk())
    clk.advance(11.0)
    s.tails.observe(1, {"queue_wait_ms": _vec(base)}, ts=clk())
    clk.advance(5.0)
    sick = _vec(base + [8000.0] * 10)
    picked = s.select_worker(
        eps_with({"queue_wait_ms": sick}), OverlapScores(), 4
    )
    assert picked == 2
    assert s.route_tail_overrides >= 1
    s.request_finished(picked)


def test_worker_load_from_stats_roundtrips_autopilot_fields():
    w = WorkerLoad.from_stats(7, {
        "autopilot_warmups_applied": 3,
        "autopilot_warmup_ms_total": 1234.5,
        "autopilot_quarantined": 1,
        "autopilot_quarantines_total": 2,
    })
    assert w.autopilot_warmups == 3
    assert w.autopilot_warmup_ms == pytest.approx(1234.5)
    assert w.autopilot_quarantined == 1 and w.autopilot_quarantines == 2


# ---------------------------------------------------------------------------
# wire schema
# ---------------------------------------------------------------------------


def test_directive_round_trips_and_tolerates_skew():
    w = WarmupDirective(ts=1.0, worker_id=9, pool="decode",
                        reason="cold_buckets", decode=True)
    assert WarmupDirective.from_bytes(w.to_bytes()) == w
    h = HealthDirective(ts=2.0, quarantined=[3], probing=[4],
                        prewarm_hold=[5], reason="cold:5")
    assert HealthDirective.from_bytes(h.to_bytes()) == h
    # unknown keys from a newer peer are dropped, missing keys default
    fut = b'{"quarantined": [1], "novel_field": true}'
    got = HealthDirective.from_bytes(fut)
    assert got.quarantined == [1] and got.prewarm_hold == []


# ---------------------------------------------------------------------------
# flight recorder: per-worker attribution
# ---------------------------------------------------------------------------


def test_flight_recorder_worker_counters():
    fr = FlightRecorder(policy=SloPolicy(default_ttft_ms=100.0))
    fr.finish("a", "m", "interactive", "success", 50.0, 200.0, worker_id=1)
    fr.finish("b", "m", "interactive", "success", 500.0, 900.0, worker_id=1)
    fr.finish("c", "m", "interactive", "error", None, 10.0, worker_id=2)
    fr.finish("d", "m", "interactive", "success", 50.0, 80.0)  # unattributed
    assert fr.worker_counters() == {1: (1, 2), 2: (1, 1)}


# ---------------------------------------------------------------------------
# controller: the synchronous tick
# ---------------------------------------------------------------------------


class _FakeTelemetry:
    def __init__(self):
        self.snap = ClusterSnapshot()

    def snapshot(self):
        return self.snap


class _FakeRecorder:
    def __init__(self):
        self.counters = {}

    def worker_counters(self):
        return dict(self.counters)


def test_autopilot_prewarm_holds_until_warm():
    clk = FakeClock(100.0)
    tel = _FakeTelemetry()
    cold = _load(1)  # 0/0: never warmed
    warm = _load(2, xla_warm_buckets=4, xla_reachable_buckets=4)
    tel.snap.workers = [cold, warm]
    ap = Autopilot(telemetry=tel,
                   config=AutopilotConfig(prewarm_cooldown_s=5.0), clock=clk)
    d = ap.tick()
    assert ap.warmup_directives == 1
    assert d.prewarm_hold == [1]
    # cooldown bounds republishes
    clk.advance(2.0)
    ap.tick()
    assert ap.warmup_directives == 1
    clk.advance(4.0)
    ap.tick()
    assert ap.warmup_directives == 2
    # the worker warms: the hold releases on the next tick
    cold.xla_warm_buckets = cold.xla_reachable_buckets = 6
    clk.advance(2.0)
    d = ap.tick()
    assert d.prewarm_hold == []
    assert "warm:1" in d.reason


def test_autopilot_prewarm_attempts_cap_releases_to_serve_cold():
    clk = FakeClock(100.0)
    tel = _FakeTelemetry()
    tel.snap.workers = [_load(1), _load(2, xla_warm_buckets=1,
                                        xla_reachable_buckets=1)]
    ap = Autopilot(
        telemetry=tel,
        config=AutopilotConfig(prewarm_cooldown_s=1.0, prewarm_max_attempts=3),
        clock=clk,
    )
    for _ in range(3):
        ap.tick()
        clk.advance(2.0)
    assert ap.warmup_directives == 3
    d = ap.tick()  # attempts exhausted: serve cold, don't hold forever
    assert d.prewarm_hold == []
    assert ap.warmup_directives == 3


def test_autopilot_prewarm_releases_departed_worker():
    clk = FakeClock(100.0)
    tel = _FakeTelemetry()
    tel.snap.workers = [_load(1), _load(2, xla_warm_buckets=1,
                                        xla_reachable_buckets=1)]
    ap = Autopilot(telemetry=tel, config=AutopilotConfig(), clock=clk)
    assert ap.tick().prewarm_hold == [1]
    tel.snap.workers = [tel.snap.workers[1]]  # worker 1 departs mid-warm
    clk.advance(2.0)
    assert ap.tick().prewarm_hold == []


def test_autopilot_quarantine_rides_health_directive():
    clk = FakeClock(100.0)
    rec = _FakeRecorder()
    ap = Autopilot(
        recorder=rec,
        config=AutopilotConfig(
            prewarm=False,
            quarantine_cfg=QuarantineConfig(trip_ticks=2, hold_s=10.0),
        ),
        clock=clk,
    )
    rec.counters = {1: (0, 0), 2: (0, 0)}
    ap.tick()
    clk.advance(2.0)
    rec.counters = {1: (5, 6), 2: (0, 5)}
    ap.tick()
    clk.advance(2.0)
    rec.counters = {1: (10, 12), 2: (0, 9)}
    d = ap.tick()
    assert d.quarantined == [1]
    assert "quarantine:1" in d.reason
    stats = ap.render_stats()
    assert stats["autopilot_quarantined_now"] == 1
    assert stats["autopilot_quarantines_total"] == 1


def test_autopilot_headroom_caps_and_lifts(run):
    clk = FakeClock(100.0)
    tel = _FakeTelemetry()
    tel.snap.active_requests = 9
    tel.snap.total_slots = 10  # util 0.9 > headroom_util
    gate = AdmissionGate(
        100.0, burst=100.0,
        classes=(SloClass("interactive", reserve_frac=0.0),
                 SloClass("batch", reserve_frac=0.5)),
        clock=clk,
    )
    ap = Autopilot(
        telemetry=tel, gate=gate,
        config=AutopilotConfig(prewarm=False, quarantine=False,
                               headroom=True, headroom_window_s=10.0),
        clock=clk,
    )
    ap.tick()  # establishes counter baselines
    # 10s of traffic: 40 interactive + 40 batch admitted
    for _ in range(40):
        gate.done(gate.admit("interactive").slo_class)
        gate.done(gate.admit("batch").slo_class)
    clk.advance(10.0)
    ap.tick()
    assert "batch" in ap.headroom_caps
    assert "interactive" not in ap.headroom_caps  # critical: never capped
    assert "admission_headroom_rate_batch" in gate.render_stats()
    # capacity - critical demand, with the safety margin: 8 req/s served
    # at util 0.9 -> ~8 capacity, minus ~4 req/s interactive demand
    assert 0.25 <= ap.headroom_caps["batch"] < 8.0
    # utilization drops: every cap lifts
    tel.snap.active_requests = 1
    clk.advance(2.0)
    ap.tick()
    assert ap.headroom_caps == {}
    assert gate.class_buckets == {}

    # close() lifts caps too (controller death must not freeze them in)
    async def main():
        tel.snap.active_requests = 9
        for _ in range(40):
            gate.done(gate.admit("interactive").slo_class)
            gate.done(gate.admit("batch").slo_class)
        clk.advance(10.0)
        ap.tick()
        assert ap.headroom_caps
        await ap.close()
        assert ap.headroom_caps == {} and gate.class_buckets == {}

    run(main())


# ---------------------------------------------------------------------------
# worker-side actuators on the live bus
# ---------------------------------------------------------------------------


class _FakeEngine:
    """Quacks like a JaxEngine for the warmup actuator: a stats dict
    and an awaitable warmup() that covers the reachable grid."""

    def __init__(self, reachable=0, warm=0, fail=False):
        self.stats = {"xla_reachable_buckets": reachable,
                      "xla_warm_buckets": warm}
        self.fail = fail
        self.warmup_calls = 0

    async def warmup(self, decode=True):
        self.warmup_calls += 1
        if self.fail:
            raise RuntimeError("compile exploded")
        self.stats["xla_reachable_buckets"] = 4
        self.stats["xla_warm_buckets"] = 4


def test_warmup_listener_applies_filters_and_noops(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        comp = drt.namespace("apns").component("worker")
        subject = comp.event_subject(AUTOPILOT_WARMUP_SUBJECT)
        eng = _FakeEngine()
        listener = await WarmupListener(drt, comp, worker_id=7,
                                        engine=eng).start()

        async def publish_and_wait(directive, pred, n=200):
            drt.bus.publish(subject, directive.to_bytes())
            for _ in range(n):
                if pred():
                    return True
                await asyncio.sleep(0.02)
            return pred()

        # addressed to another worker: ignored
        assert not await publish_and_wait(
            WarmupDirective(worker_id=9), lambda: eng.warmup_calls > 0, n=25)
        # another pool: ignored even pool-wide
        assert not await publish_and_wait(
            WarmupDirective(worker_id=0, pool="prefill"),
            lambda: eng.warmup_calls > 0, n=25)
        # pool-wide directive applies and mirrors into engine.stats
        assert await publish_and_wait(
            WarmupDirective(worker_id=0),
            lambda: listener.warmups_applied == 1)
        assert eng.stats["autopilot_warmups_applied"] == 1
        assert eng.stats["autopilot_warmup_ms_total"] >= 0.0
        # already warm: republished directive is a counted no-op
        assert await publish_and_wait(
            WarmupDirective(worker_id=7),
            lambda: listener.warmups_noop == 1)
        assert eng.warmup_calls == 1
        await listener.close()
        await drt.shutdown()

    run(main())


def test_warmup_listener_counts_failure_and_keeps_serving(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        comp = drt.namespace("apns2").component("worker")
        subject = comp.event_subject(AUTOPILOT_WARMUP_SUBJECT)
        eng = _FakeEngine(fail=True)
        listener = await WarmupListener(drt, comp, worker_id=3,
                                        engine=eng).start()
        drt.bus.publish(subject, WarmupDirective(worker_id=3).to_bytes())
        for _ in range(200):
            if listener.warmups_failed:
                break
            await asyncio.sleep(0.02)
        assert listener.warmups_failed == 1
        assert listener.stats()["autopilot_warmups_applied"] == 0
        # the loop survived the failure: the next directive still lands
        eng.fail = False
        drt.bus.publish(subject, WarmupDirective(worker_id=3).to_bytes())
        for _ in range(200):
            if listener.warmups_applied:
                break
            await asyncio.sleep(0.02)
        assert listener.warmups_applied == 1
        await listener.close()
        await drt.shutdown()

    run(main())


def test_quarantine_listener_mirrors_membership(run):
    async def main():
        drt = await DistributedRuntime.from_settings()
        comp = drt.namespace("apns3").component("worker")
        subject = comp.event_subject(AUTOPILOT_HEALTH_SUBJECT)
        eng = _FakeEngine()
        listener = await QuarantineListener(drt, comp, worker_id=5,
                                            engine=eng).start()
        drt.bus.publish(
            subject, HealthDirective(quarantined=[5, 9]).to_bytes())
        for _ in range(200):
            if listener.quarantined:
                break
            await asyncio.sleep(0.02)
        assert listener.quarantined and listener.quarantines_seen == 1
        assert eng.stats["autopilot_quarantined"] == 1
        # full replacement: the next view reinstates via probe
        drt.bus.publish(
            subject, HealthDirective(quarantined=[9], probing=[5]).to_bytes())
        for _ in range(200):
            if not listener.quarantined:
                break
            await asyncio.sleep(0.02)
        assert not listener.quarantined and listener.probing
        assert eng.stats["autopilot_quarantined"] == 0
        assert eng.stats["autopilot_quarantines_total"] == 1
        await listener.close()
        await drt.shutdown()

    run(main())


# ---------------------------------------------------------------------------
# end to end: quarantined worker's held streams drain cleanly
# ---------------------------------------------------------------------------


def _mk_engine():
    cfg = EngineConfig(
        model=TINY, num_blocks=64, block_size=4,
        max_batch_size=4, max_context=128, prefill_chunk=32,
    )
    return JaxEngine(cfg, params=PARAMS, seed=0)


def _req(tokens, max_tokens=3):
    return PreprocessedRequest(
        token_ids=list(tokens),
        stop_conditions=StopConditions(max_tokens=max_tokens,
                                       ignore_eos=True),
        sampling_options=SamplingOptions(temperature=0.0),
        eos_token_ids=[511],
    ).to_dict()


def test_quarantined_worker_streams_drain_cleanly(run):
    """Quarantine is a soft exclusion: a long stream already pinned to
    the quarantined worker completes without a client-visible error,
    while NEW requests route to the healthy worker."""

    async def main():
        store, bus = LocalStore(), LocalBus()
        front = await DistributedRuntime.from_settings(store=store, bus=bus)
        workers, engines = [], []
        for _ in range(2):
            w = await DistributedRuntime.from_settings(store=store, bus=bus)
            engine = _mk_engine()
            comp = w.namespace("dyn").component("worker")
            pub = KvEventPublisher(w, comp, w.primary_lease_id)
            pub.attach(engine.kv.allocator)
            await comp.endpoint("gen").serve(
                engine, stats_handler=engine.load_metrics)
            workers.append(w)
            engines.append(engine)

        comp = front.namespace("dyn").component("worker")
        client = await comp.endpoint("gen").client().start()
        await client.wait_for_instances(5)
        router = await KvRouter(front, comp, block_size=4).start()
        routed = KvRoutedEngine(router, client)

        async def collect(ctx):
            out = []
            async for a in routed.generate(ctx):
                out.append(a)
            return out

        # a LONG stream: quarantine lands while it decodes
        ctx_long = Context(_req(range(100, 124), max_tokens=40))
        task = asyncio.ensure_future(collect(ctx_long))
        for _ in range(500):
            if "routed_worker_id" in ctx_long.annotations:
                break
            await asyncio.sleep(0.02)
        pinned = ctx_long.annotations.get("routed_worker_id")
        assert pinned is not None
        other = next(w.primary_lease_id for w in workers
                     if w.primary_lease_id != pinned)

        # the autopilot pulls the pinned worker from rotation mid-stream
        router.scheduler.set_autopilot_health(quarantined=[pinned])
        out = await task
        finishes = [(a.data or {}).get("finish_reason") for a in out]
        assert any(f == "length" for f in finishes)  # drained, no error
        assert not any(f == "error" for f in finishes)

        # NEW work routes around the quarantined worker — even for a
        # prompt whose KV prefix lives there (soft exclusion outranks
        # prefix affinity)
        for i in range(3):
            ctx = Context(_req(range(100 + i, 124 + i), max_tokens=2))
            out = await collect(ctx)
            assert any((a.data or {}).get("finish_reason") for a in out)
            assert ctx.annotations.get("routed_worker_id") == other

        # reinstatement (full replacement) makes it routable again
        router.scheduler.set_autopilot_health(quarantined=[])
        assert router.scheduler.quarantined == set()

        for w in workers:
            await w.shutdown()
        await front.shutdown()

    run(main())


# ---------------------------------------------------------------------------
# the fake-clock planner-sim leg (scripts/trace_replay.py --planner-sim)
# ---------------------------------------------------------------------------


def test_planner_sim_deterministic_and_all_loops_close():
    """The pure decision-loop replay (no live workers) must be
    byte-deterministic per seed AND close all four loops — the same
    check the CLI's ``--planner-sim --check-repro`` run enforces,
    pinned here so the sim leg can't rot between releases."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
    sys.path.insert(0, scripts)
    try:
        from trace_replay import check_sim, planner_sim
    finally:
        sys.path.remove(scripts)

    r1 = planner_sim(7, ticks=60)
    r2 = planner_sim(7, ticks=60)
    assert r1 == r2
    check_sim(r1)
    # a different seed still closes every loop (the pathology script
    # is structural, not a lucky RNG draw)
    check_sim(planner_sim(123, ticks=60))
