"""One clock for an engine's scheduler loop: per-step phase accounting.

Every second of the loop task's life goes to exactly one phase, so the
phases sum to the wall (see docs/tracing.md for what each one means):

    idle  admit  provision  dispatch  device  lag  emit  yield

The loop thread calls :meth:`LoopClock.mark` at each phase boundary (it
closes the current phase at ``perf_counter()`` and opens the next).
Device work runs in an executor thread: the loop awaits it in ``lag``,
the thunk is timed on its own thread (:meth:`thunk`, split at
:meth:`enqueued` into ``dispatch`` and ``device``) and :meth:`settle`
books the await's wall time as dispatch + device + lag.

A *step* is everything between two programs' results
(:meth:`step_done`, called where a program's tokens have been emitted;
the loop has as a rule enqueued the next program by then, so the step is
booked under the description it is given, the emitted program's, and
not under the last one described): its phase times tile the loop's wall
time, and three readers share them:

* always on — cumulative seconds per phase and dispatches per kind in
  the engine's ``stats`` (``/metrics``); by the step's kind, its seconds
  (``idle`` apart), its device steps and its *exposed* seconds, those
  with no program of the loop's outstanding on the device (below); by
  a program's kind, how often it was enqueued *chained*, while another
  was still outstanding; and ONE warning for a step that took far
  longer than its kind leads one to expect;
* under ``--trace`` — one ``engine.step`` span per step in the
  recorder's ring (never handed to the sink: the loop is one endless
  trace), and the same boundaries as ``jax.profiler.TraceAnnotation``
  so a profile's ``/host:CPU`` plane carries ``engine.admit`` /
  ``provision`` / ``dispatch`` / ``device`` / ``emit`` on the clock of
  the device ops. Annotations open only around synchronous sections and
  inside executor thunks, never across an ``await``.

A program is *outstanding* from :meth:`enqueued` (the jit call
returned) until its result is on the host (:meth:`landed`, called where
the engine fetches it). The device runs programs in order, so a result
on the host settles every program enqueued before it too. Seconds with
one or more outstanding are *covered*; a step's exposed seconds are its
wall time, ``idle`` apart, less its covered ones.

Tracing off costs one attribute check per mark.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Any, Optional

from .context import TraceContext
from .span import SpanRecorder

logger = logging.getLogger(__name__)

PHASES = ("idle", "admit", "provision", "dispatch", "device", "lag",
          "emit", "yield")
#: dispatch kinds, by the first element of a program key
KINDS = {"decode": "decode_window", "mixed": "mixed_step",
         "prefill": "prefill", "verify": "verify"}
STEP_SPAN = "engine.step"
#: phases the loop thread spends computing: annotated under --trace
_SYNC = frozenset(("admit", "provision", "emit"))
_SECONDS = {p: f"loop_seconds_{p}" for p in PHASES}
_STEPS = {k: f"steps_{k}" for k in KINDS.values()}
#: booked once a step under its kind: (seconds, device steps, exposed)
_BY_KIND = {k: (f"step_seconds_{k}", f"device_steps_{k}",
                f"step_exposed_seconds_{k}") for k in KINDS.values()}
#: a program of the kind enqueued while another was outstanding
_CHAINED = {k: f"dispatch_chained_{k}" for k in KINDS.values()}

# The slow-step rule: a step is slow when its wall time (idle apart)
# exceeds what its kind leads one to expect — the mean wall time per
# device step over the last SLOW_STEP_HISTORY steps of that kind, times
# its n — by more than SLOW_STEP_EXCESS_S AND by more than
# SLOW_STEP_FACTOR times.
SLOW_STEP_EXCESS_S = 1.0
SLOW_STEP_FACTOR = 3.0
SLOW_STEP_HISTORY = 64


def _annotate(name: str, **meta: Any):
    """An entered ``jax.profiler.TraceAnnotation`` (imported here: only
    a process that traces an engine needs JAX for its spans)."""
    from jax.profiler import TraceAnnotation

    ann = TraceAnnotation(name, **meta)
    ann.__enter__()
    return ann


class LoopClock:
    def __init__(self, stats: dict, recorder: SpanRecorder):
        self.stats = stats  # the engine's: totals go out through /metrics
        stats.update(dict.fromkeys(_SECONDS.values(), 0.0))
        stats.update(dict.fromkeys(_STEPS.values(), 0), slow_steps=0)
        for seconds, steps, exposed in _BY_KIND.values():
            stats.update({seconds: 0.0, steps: 0, exposed: 0.0})
        stats.update(dict.fromkeys(_CHAINED.values(), 0))
        self.recorder = recorder
        self.trace = TraceContext.new()  # the loop's own: one per engine
        #: number of the open step; request spans name it (``step=``)
        self.seq = 1
        self.phase: Optional[str] = None  # None until the loop starts
        self._t = 0.0
        self._step = dict.fromkeys(PHASES, 0.0)
        self._step_ts = 0.0
        self._ann = None
        # the awaited thunk, written by its executor thread: [entered,
        # enqueued, left] at perf_counter() (None until then), the
        # thread's ident and its open annotation
        self._th: Optional[list] = None
        self._th_thread: Optional[int] = None
        self._th_ann = None
        self._info: dict = {}
        # did a thunk of the open step dispatch a program for the first
        # time? (a chained step holds the NEXT program's enqueue)
        self._step_cold = False
        # programs enqueued by the loop's thunks, how many of them are
        # known done, when the count outstanding rose from 0 (None at
        # 0) and the open step's covered seconds
        self.programs = 0
        self._landed = 0
        self._out_since: Optional[float] = None
        self._covered = 0.0
        self._history = {k: deque(maxlen=SLOW_STEP_HISTORY)
                         for k in KINDS.values()}

    # ---- the loop thread ----

    def start(self, phase: str = "admit") -> None:
        self.phase, self._t = phase, time.perf_counter()
        self._step_ts = time.time()

    def stop(self) -> None:
        """The loop task ended: book the open phase, open nothing."""
        if self.phase is not None:
            self.mark(self.phase)
            self.phase = None
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def mark(self, phase: str) -> Optional[str]:
        """Close the current phase now and open ``phase``; returns the
        phase that was open (to come back to it). Nothing is open, and
        nothing opens, while the loop is not running."""
        prev = self.phase
        if prev is None:
            return None
        now = time.perf_counter()
        d = now - self._t
        self.stats[_SECONDS[prev]] += d
        self._step[prev] += d
        self.phase, self._t = phase, now
        if phase == "idle":
            # nobody is left to fetch what is still outstanding
            self._settle_programs(self.programs, now)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self.recorder.enabled and phase in _SYNC:
            self._ann = _annotate("engine." + phase)
        return prev

    def await_thunk(self) -> Optional[str]:
        """The loop is about to await an executor thunk: opens ``lag``."""
        self._th = None
        return self.mark("lag")

    def _thunk_split(self, now: float) -> tuple[float, float, float]:
        """(lag, dispatch, device) seconds of the await open since
        ``self._t``, as far as the thunk has come by ``now``."""
        th = self._th
        if th is None:
            return now - self._t, 0.0, 0.0
        t0, t1, t2 = th
        end = now if t2 is None else t2
        enq = end if t1 is None else t1
        return (t0 - self._t) + (now - end), enq - t0, end - enq

    def settle(self, phase: Optional[str]) -> None:
        """The await returned: its wall time is the thunk's own dispatch
        and device seconds plus what is left, ``lag`` — queueing for the
        executor and for the event loop to resume this task. Opens
        ``phase``."""
        if self.phase is None:
            return
        now = time.perf_counter()
        for name, d in zip(("lag", "dispatch", "device"),
                           self._thunk_split(now)):
            self.stats[_SECONDS[name]] += d
            self._step[name] += d
        self._th, self._t = None, now
        self.mark(phase)

    def totals(self) -> dict:
        """Seconds by phase up to NOW, the open phase included (an open
        await split as far as its thunk has come), so that two readings
        differ by the wall time between them and no phase ever falls."""
        out = {p: self.stats[_SECONDS[p]] for p in PHASES}
        if self.phase == "lag":
            for name, d in zip(("lag", "dispatch", "device"),
                               self._thunk_split(time.perf_counter())):
                out[name] += d
        elif self.phase is not None:
            out[self.phase] += time.perf_counter() - self._t
        return out

    def described(self) -> dict:
        """What the last dispatch thunk described. A loop that enqueues
        the next program before it fetches this one keeps it with the
        program's record and hands it back to :meth:`step_done`."""
        return self._info

    def step_done(self, info: Optional[dict] = None, **attrs: Any) -> None:
        """A program's result is emitted: close the step. ``info``: that
        program's description (:meth:`described`), where another has
        been described since; ``attrs`` go onto the step's span as they
        are (an expert model's ``moe``: the routing counters that came
        back during the step)."""
        if self.phase is None:
            return
        self.mark(self.phase)
        step, self._step = self._step, dict.fromkeys(PHASES, 0.0)
        ts, self._step_ts = self._step_ts, time.time()
        info, self._info = (self._info if info is None else info), {}
        step_cold, self._step_cold = self._step_cold, False
        kind, n = info.get("kind", "?"), max(info.get("n", 1), 1)
        dur = sum(step.values())
        busy = dur - step["idle"]
        if self._out_since is not None:
            # split the open interval here, so that steps tile it
            self._covered += self._t - self._out_since
            self._out_since = self._t
        exposed = max(busy - self._covered, 0.0)
        self._covered = 0.0
        if kind in _STEPS:
            self.stats[_STEPS[kind]] += 1
            for name, d in zip(_BY_KIND[kind], (busy, n, exposed)):
                self.stats[name] += d
            hist = self._history[kind]
            cold = bool(info.get("cold")) or step_cold
            expected = n * sum(hist) / len(hist) if hist else 0.0
            # a kind's first warm steps have nothing to be judged by
            if (hist or cold) and (
                    busy > expected + SLOW_STEP_EXCESS_S
                    and busy > SLOW_STEP_FACTOR * expected):
                self.stats["slow_steps"] += 1
                logger.warning(
                    "slow step %d: %s %s n=%d live=%s took %.0f ms "
                    "(expected %.0f)%s; ms by phase: %s",
                    self.seq, kind, info.get("key"), n, info.get("live"),
                    busy * 1e3, expected * 1e3,
                    " COLD: first dispatch of this program" if cold else "",
                    " ".join(f"{p}={step[p] * 1e3:.0f}" for p in PHASES),
                )
            elif not cold:
                # neither a slow step nor a compile enters the history:
                # the next steps are judged against what the kind
                # usually takes
                hist.append(busy / n)
        if self.recorder.enabled:
            self.recorder.record_span(
                STEP_SPAN, self.trace, ts=ts, dur_ms=dur * 1e3,
                to_sink=False, seq=self.seq, kind=kind,
                key=str(info.get("key")), n=n, live=info.get("live"),
                rows=info.get("rows"),
                phases={p: round(step[p] * 1e3, 3) for p in PHASES},
                exposed_ms=round(exposed * 1e3, 3),
                **attrs,
            )
        self.seq += 1

    # ---- the executor thread ----

    def thunk(self, first: str, fn, *args):
        """Run ``fn(*args)`` (the loop awaits it) and time it on this
        thread: ``first`` is the phase it starts in (``dispatch``, or
        ``device`` for a pure wait); :meth:`enqueued` switches it to
        ``device``."""
        self._th_thread = threading.get_ident()
        if self.recorder.enabled:
            self._th_ann = _annotate("engine." + first, seq=self.seq)
        t0 = time.perf_counter()
        self._th = th = [t0, t0 if first == "device" else None, None]
        try:
            return fn(*args)
        finally:
            th[2] = time.perf_counter()
            self._th_thread = None
            if self._th_ann is not None:
                self._th_ann.__exit__(None, None, None)
                self._th_ann = None

    def describe(self, key: tuple, cold: bool, n: int, live: int,
                 rows: int) -> None:
        """What the open thunk is about to hand the device (the step's
        span, its slow-step line and the ``engine.dispatch`` annotation
        carry it). A dispatch outside a loop thunk describes nothing."""
        if threading.get_ident() != self._th_thread:
            return
        kind = KINDS.get(key[0], key[0])
        self._info = {"kind": kind, "key": key[1:], "cold": cold, "n": n,
                      "live": live, "rows": rows}
        self._step_cold |= cold
        if self._th_ann is not None:
            self._th_ann.set_metadata(
                kind=kind, key=str(key[1:]), n=n, live=live)

    def enqueued(self) -> None:
        """The program is enqueued: it is outstanding, and from here the
        thunk waits for the device (only a thunk's first call moves it
        from ``dispatch`` to ``device``)."""
        if threading.get_ident() != self._th_thread:
            return
        now = time.perf_counter()
        if self.programs > self._landed and self._info.get("kind") in _CHAINED:
            # the device has its next program before it is done with the
            # one before: nothing of this dispatch is exposed
            self.stats[_CHAINED[self._info["kind"]]] += 1
        self.programs += 1
        if self._out_since is None:
            self._out_since = now
        if self._th[1] is not None:
            return
        self._th[1] = now
        if self._th_ann is not None:
            self._th_ann.__exit__(None, None, None)
            self._th_ann = _annotate("engine.device", seq=self.seq)

    def landed(self, program: Optional[int] = None) -> None:
        """A result is on the host: that of the ``program``-th enqueued
        (:attr:`programs` as its dispatch left it), or of the newest.
        It and every program before it are outstanding no longer."""
        if threading.get_ident() == self._th_thread:
            self._settle_programs(
                self.programs if program is None else program,
                time.perf_counter())

    def _settle_programs(self, upto: int, now: float) -> None:
        self._landed = max(self._landed, upto)
        if self._landed >= self.programs and self._out_since is not None:
            self._covered += now - self._out_since
            self._out_since = None
