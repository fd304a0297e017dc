"""The decode batch's resident step state (``engine/step_state.py``):
the step programs are told of their decode slots through one matrix that
lives on the device, and a dispatch hands over only what changed; and
the chained loop over it (``EngineConfig.decode_pipeline``): a program is
enqueued before the one before it is fetched, whatever the two are.

The plain reference of (a) is the SAME engine, unchained, made to send
its numpy mirror whole before every dispatch, which is what the engine
did before the state was resident (one ``jnp.asarray`` a field, every
dispatch) and before the loop was chained (enqueue, wait, emit)."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, JaxEngine
from dynamo_tpu.engine.step_state import DELTA_CELLS, StepState
from dynamo_tpu.models import llama
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.parallel import mesh as pm
from dynamo_tpu.protocols.common import (
    FinishReason, PreprocessedRequest, SamplingOptions, StopConditions,
)
from dynamo_tpu.runtime import Context
from dynamo_tpu.tracing.loop_clock import KINDS

BS = 4


# ---------------- the schedule and its two engines ----------------


def _schedule(seed: int, vocab: int, n: int, penalties: bool, max_new=44):
    """``n`` requests: when each arrives (in decode steps of the engine),
    what it asks, and whether its client walks away. ``penalties``:
    some ask for penalties and some for logprobs (each a program variant
    of its own: the stateful families, slow to compile here, go without)."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        so = {}
        kind = rng.integers(0, 3)
        if kind == 1:
            so = dict(temperature=0.8, top_p=0.9, seed=int(rng.integers(1, 99)))
        elif kind == 2:
            so = dict(temperature=1.1, top_k=12, seed=int(rng.integers(1, 99)))
        else:
            so = dict(temperature=0.0)
        if penalties and rng.random() < 0.3:
            so.update(frequency_penalty=0.7, repetition_penalty=1.2)
        if penalties and rng.random() < 0.3:
            so.update(logprobs=2)
        out.append(dict(
            prompt=[int(t) for t in rng.integers(8, vocab, rng.integers(5, 38))],
            max_tokens=int(rng.integers(3, max_new)),
            so=so,
            after=0 if i == 0 else int(rng.integers(0, 60)),
            cancel_at=int(rng.integers(2, 9)) if rng.random() < 0.2 else 0,
        ))
    return out


async def _drive(engine, schedule):
    """Each request's (tokens, logprob entries, finish reason)."""
    running = [0]

    async def one(spec):
        while (engine.stats["decode_steps"] < spec["after"]
               and running[0] > 0):
            await asyncio.sleep(0.001)
        running[0] += 1
        ctx = Context(PreprocessedRequest(
            token_ids=spec["prompt"],
            stop_conditions=StopConditions(
                max_tokens=spec["max_tokens"], ignore_eos=True),
            sampling_options=SamplingOptions(**spec["so"]),
            eos_token_ids=[],
        ))
        toks, lps, reason = [], [], None
        try:
            async for o in engine.generate(ctx):
                toks += o.token_ids
                lps += o.logprobs or []
                reason = o.finish_reason or reason
                if spec["cancel_at"] and len(toks) >= spec["cancel_at"]:
                    ctx.context.stop_generating()
        finally:
            running[0] -= 1
        return toks, lps, reason

    return await asyncio.gather(*[one(s) for s in schedule])


def _host_fed(engine):
    """The reference: every dispatch sends the whole mirror (so nothing
    is ever in flight when it is enqueued: the unchained loop)."""
    assert not engine.cfg.decode_pipeline
    engine._rows.stale = lambda pending=0: True
    return engine


def _watch(engine, seen):
    """After every step: the resident matrix (with the cells still to be
    sent) IS the mirror, on every row, but for what the program in
    flight has advanced (a live row's token, and its length and step
    count by its pending steps; a row that left keeps the token and step
    count its last program left it, which nothing reads); a dead row
    holds length 0 and page 0 where a dead row writes. A state that is to
    go up whole says nothing."""
    done = engine._step_done

    def step_done(*info):
        rows = engine._rows
        pend = engine._pending_rows()
        if not rows.stale(pend):
            dev = np.array(rows._dev)
            for (slot, col), v in rows._cells.items():
                dev[slot, col] = v
            live = rows.seq_lens > 0
            np.testing.assert_array_equal(dev[:, 3:], rows.host[:, 3:])
            still = live & (pend == 0)
            np.testing.assert_array_equal(dev[still, :3], rows.host[still, :3])
            np.testing.assert_array_equal(
                dev[live, 1:3], rows.host[live, 1:3] + pend[live, None])
            assert not dev[~live, 1].any(), "a dead row has a length"
            for pool in range(len(rows.table_views())):
                head = [rows.table_column(pool, c) for c in range(rows.head)]
                assert not dev[~live][:, head].any(), (
                    "a dead row writes through a page")
            seen.append(int(live.sum()))
        done(*info)

    engine._step_done = step_done


def _assert_same_streams(got, want, schedule):
    for spec, (toks, lps, reason), (rtoks, rlps, rreason) in zip(
            schedule, got, want):
        if spec["cancel_at"] and FinishReason.CANCELLED in (reason, rreason):
            # the client walks away between two windows: how many tokens
            # of the last one it still saw is the event loop's timing
            n = min(len(toks), len(rtoks))
            assert n >= spec["cancel_at"] - 1
            toks, rtoks, lps, rlps = toks[:n], rtoks[:n], lps[:n], rlps[:n]
        else:
            assert reason == rreason == FinishReason.LENGTH
            assert len(toks) == spec["max_tokens"]
        assert toks == rtoks
        assert len(lps) == len(rlps)
        for a, b in zip(lps, rlps):
            assert a["top"] == b["top"] and a["logprob"] == b["logprob"]


def _tiny_dense():
    cfg = ModelConfig.tiny()
    return cfg, llama.init_params(cfg, jax.random.key(0))


def _tiny_of(module):
    """(cfg, params) of a stateful family's tiny fixture (its module's
    ``tiny``, which is a pytest fixture: call what it wraps)."""
    import importlib

    mod = importlib.import_module(f"tests.{module}")
    _hf, cfg, params = mod.tiny.__wrapped__()
    return cfg, params


MODELS = {
    # name: (cfg and params, engine options, requests, penalties)
    "dense": (_tiny_dense, dict(num_blocks=96), 14, True),
    # a pool that cannot hold the batch: preemption and replay
    "dense-starved": (_tiny_dense, dict(num_blocks=22), 8, True),
    "dense-window1": (_tiny_dense, dict(num_blocks=96, decode_window=1), 8,
                      False),
    "lfm2-conv-state": (lambda: _tiny_of("test_lfm2"),
                        dict(num_blocks=96, prefill_chunk=16), 6, False),
    "gigachat35-linear-state": (
        lambda: _tiny_of("test_gigachat35"),
        dict(num_blocks=96, prefill_chunk=16, state_snapshots=8), 6, False),
    "mellum2-window-pool": (
        lambda: _tiny_of("test_mellum2"),
        dict(num_blocks=160, max_context=256, prefill_chunk=16,
             mixed_step_budget=16), 6, False),
    "olmoe-experts": (lambda: _tiny_of("test_olmoe"),
                      dict(num_blocks=96), 6, False),
}


@pytest.mark.parametrize("name", list(MODELS))
def test_streams_equal_the_host_fed_engines(name, monkeypatch):
    """(a) and (b): a seeded random schedule (admissions that land while
    a window is in flight, streams that end inside a window, clients that
    walk away, page crossings every fourth token, a page that leaves a
    window while a program is queued, sampled and greedy rows, penalties
    and logprobs on some) gives, on the chained engine, the streams of
    the unchained host-fed one, token for token and logprob for logprob;
    and after every step the device's matrix is the mirror."""
    make, opts, n, penalties = MODELS[name]
    cfg, params = make()
    if "gigachat35" in name:
        monkeypatch.setattr(llama, "SNAPSHOT_ROW_A_BLOCK_BYTES", 1024)
    base = dict(block_size=BS, max_batch_size=4, max_context=128,
                prefill_chunk=32)
    base.update(opts)
    long_answers = 90 if "mellum2" in name else 44
    schedule = _schedule(7, cfg.vocab_size, n, penalties, long_answers)
    engines = [JaxEngine(EngineConfig(model=cfg, decode_pipeline=pipe, **base),
                         params=params) for pipe in (True, False)]
    seen = []

    evicted_under = []
    evict = engines[0]._evict_for_headroom

    def evict_spy(seq):
        evicted_under.append(engines[0]._inflight)
        return evict(seq)

    engines[0]._evict_for_headroom = evict_spy

    async def main():
        _watch(engines[0], seen)
        got = await _drive(engines[0], schedule)
        want = await _drive(_host_fed(engines[1]), schedule)
        for e in engines:
            assert e._n_active == 0
            await e.close()
        return got, want

    got, want = asyncio.run(main())
    _assert_same_streams(got, want, schedule)
    st, ref = engines[0].stats, engines[1].stats
    assert len(seen) >= 10 and max(seen) > 1, "the watch saw no running batch"
    # the reference resynchronised at every dispatch, the engine did not
    assert ref["step_state_resident"] == 0 < ref["step_state_resyncs"]
    assert st["step_state_resident"] > st["step_state_resyncs"] // 4
    assert st["dispatch_chained_" + KINDS["decode"]] > 0
    assert ref["dispatch_chained_" + KINDS["decode"]] == 0
    if name == "dense-starved":
        # every request at full length all the same (_assert_same_
        # streams), and the queued program's blocks went back before
        # any sequence was evicted: chaining never causes a preemption
        assert st["preemptions"] > 0
        assert evicted_under and all(w is None for w in evicted_under)
    if name == "mellum2-window-pool":
        assert engines[0].kv.window.released > 0, "no page left a window"


def test_pipelined_windows_chain_in_the_resident_state():
    """``decode_pipeline``: the next window is dispatched before the last
    is read, its tokens, lengths and steps already in the rows the last
    one returned; the streams are the unpipelined engine's."""
    cfg, params = _tiny_dense()
    schedule = _schedule(11, cfg.vocab_size, 6, False)
    for s in schedule:
        s["cancel_at"] = 0

    async def main(**kw):
        e = JaxEngine(EngineConfig(
            model=cfg, num_blocks=96, block_size=BS, max_batch_size=4,
            max_context=128, prefill_chunk=32, **kw), params=params)
        out = await _drive(e, schedule)
        await e.close()
        return out, e.stats

    piped, st = asyncio.run(main(decode_pipeline=True))
    plain, _ = asyncio.run(main(decode_pipeline=False))
    _assert_same_streams(piped, plain, schedule)
    assert st["step_state_resident"] > 0


# ---------------- the chain: what does not drain it ----------------


def _spec(prompt, max_tokens, after=0):
    return dict(prompt=list(prompt), max_tokens=max_tokens, after=after,
                so=dict(temperature=0.0), cancel_at=0)


def _outstanding(engine) -> int:
    clk = engine._clock
    return clk.programs - clk._landed


@pytest.mark.parametrize("event", ["a-streams-end", "a-mixed-step"])
def test_the_chain_is_not_drained_by(event):
    """Two streams decode together, one ends long before the other, and
    a third request arrives while the survivor decodes. Neither the end
    nor the admission's mixed step finds the device without a program:
    where the host finishes the short stream the next program is already
    enqueued, the leave goes up as cells (no resynchronisation between it
    and the next admission), and the mixed step is enqueued behind a
    window in flight."""
    cfg, params = _tiny_dense()
    engine = JaxEngine(EngineConfig(
        model=cfg, num_blocks=96, block_size=BS, max_batch_size=4,
        max_context=128, prefill_chunk=32), params=params)
    schedule = [_spec(range(10, 19), 60), _spec(range(30, 41), 13),
                _spec(range(50, 70), 10, after=24)]
    seen = {"ends": [], "mixed": [], "admits": []}
    finish, mixed, begin = (
        engine._finish, engine._dispatch_mixed, engine._begin_prefill)

    def finish_spy(seq, reason, emit=True):
        if seq.slot >= 0 and engine._n_active > 1:
            seen["ends"].append((_outstanding(engine), seq.generated,
                                 engine.stats["step_state_resyncs"]))
        return finish(seq, reason, emit)

    def mixed_spy(packed):
        if engine._n_active:
            seen["mixed"].append((_outstanding(engine),
                                  engine._inflight is not None))
        return mixed(packed)

    def begin_spy(seq, **kw):
        seen["admits"].append(engine.stats["step_state_resyncs"])
        return begin(seq, **kw)

    engine._finish, engine._dispatch_mixed = finish_spy, mixed_spy
    engine._begin_prefill = begin_spy

    async def main():
        out = await _drive(engine, schedule)
        assert engine._n_active == 0
        await engine.close()
        return out

    out = asyncio.run(main())
    assert [len(t) for t, _l, _r in out] == [60, 13, 10]
    assert engine.stats["preemptions"] == 0
    if event == "a-streams-end":
        # the 13-token stream ended beside the long one
        (outstanding, generated, resyncs), = [
            e for e in seen["ends"] if e[1] == 13]
        assert outstanding >= 1, "the end found nothing enqueued behind it"
        # its leave was cells: the state next went up whole for the third
        # request's row, not for the slot given up
        assert seen["admits"][-1] == resyncs
    else:
        # the third request's chunk rode a mixed step behind a window
        # (the second request's rode one too, right behind the first's
        # own admission: the state had to go up whole there)
        assert seen["mixed"], "no mixed step beside a running stream"
        outstanding, inflight = seen["mixed"][-1]
        assert outstanding >= 1 and inflight
        assert engine.stats["dispatch_chained_" + KINDS["mixed"]] == sum(
            out >= 1 for out, _inflight in seen["mixed"]) >= 1


@pytest.mark.parametrize("window", [1, 4, 8])
def test_chained_dispatches_are_counted(window):
    """A lone stream of 41 tokens: the prefill samples one, the windows
    (half ``decode_window`` steps each, chained) the other 40. Every
    window but the first is enqueued while the one before it is
    outstanding; the last is followed by none (the host knows
    ``max_tokens``: nothing is queued behind a stream's end)."""
    cfg, params = _tiny_dense()
    engine = JaxEngine(EngineConfig(
        model=cfg, num_blocks=96, block_size=BS, max_batch_size=4,
        max_context=128, prefill_chunk=32, decode_window=window),
        params=params)

    async def main():
        out = await _drive(engine, [_spec(range(10, 20), 41)])
        await engine.close()
        return out

    (toks, _lps, reason), = asyncio.run(main())
    assert len(toks) == 41 and reason == FinishReason.LENGTH
    steps = max(window // 2, 1)
    st = engine.stats
    assert st["steps_" + KINDS["decode"]] == 40 // steps
    assert st["device_steps_" + KINDS["decode"]] == 40  # no overrun
    assert st["dispatch_chained_" + KINDS["decode"]] == 40 // steps - 1
    series = engine.device_path_stats()
    for kind in KINDS.values():
        assert series[f'engine_dispatch_chained_total{{kind="{kind}"}}'] == (
            st["dispatch_chained_" + kind])
    assert st["dispatch_chained_" + KINDS["prefill"]] == 0


@pytest.mark.parametrize("window", [4, 8])
def test_max_context_is_never_written_past(window):
    """Rows that run into ``max_context`` with programs chained behind
    one another: no window is enqueued that would write a position the
    table does not have (a step past ``max_tokens`` is a discard into
    page 0, a step past the table is not), and the streams are the
    unchained engine's, up to the context's last token."""
    cfg, params = _tiny_dense()
    schedule = [_spec(range(10, 22), 64), _spec(range(40, 49), 64, after=3),
                _spec(range(60, 75), 5, after=6)]

    async def main(**kw):
        engine = JaxEngine(EngineConfig(
            model=cfg, num_blocks=64, block_size=BS, max_batch_size=4,
            max_context=32, prefill_chunk=32, decode_window=window, **kw),
            params=params)
        dispatch, reached = engine._dispatch_window, []

        def spy(n, tokens_in=None):
            for seq in engine._active:
                if seq is not None and not engine._leaving(seq):
                    reached.append(seq.seq_len + engine._pending(seq) + n)
            return dispatch(n, tokens_in)

        engine._dispatch_window = spy
        out = await _drive(engine, schedule)
        await engine.close()
        return out, reached

    got, reached = asyncio.run(main())
    want, _ = asyncio.run(main(decode_pipeline=False))
    assert max(reached) == 32, "no row ran into the context's end"
    assert [len(t) for t, _l, _r in got] == [20, 23, 5]
    assert [t for t, _l, _r in got] == [t for t, _l, _r in want]


# ---------------- (c) what a dispatch hands over ----------------


def test_a_dispatch_hands_over_at_most_two_host_arrays():
    """A decode dispatch moves host memory to the device in ONE place
    (``StepState.hand_over``: the mirror whole, or a few cells, or
    nothing), a mixed step in two (its segments besides); everything
    else of ``_dispatch_window`` runs under a guard that refuses any
    transfer, explicit ones too. The counters say the same."""
    cfg, params = _tiny_dense()
    engine = JaxEngine(EngineConfig(
        model=cfg, num_blocks=96, block_size=BS, max_batch_size=4,
        max_context=128, prefill_chunk=32), params=params)
    per_dispatch = []
    window, hand_over, handed = (
        engine._dispatch_window, engine._rows.hand_over, engine._handed)

    def guarded(*a):
        per_dispatch.append(0)
        with jax.transfer_guard_host_to_device("disallow_explicit"):
            return window(*a)

    def sanctioned(*a):
        with jax.transfer_guard_host_to_device("allow"):
            return hand_over(*a)

    def count(kind, n):
        if kind != "prefill":
            if kind != "decode":
                per_dispatch.append(0)
            per_dispatch[-1] += n
        handed(kind, n)

    engine._dispatch_window = guarded
    engine._rows.hand_over = sanctioned
    engine._handed = count

    async def main():
        # one long stream alone: a steady batch
        alone = _schedule(3, cfg.vocab_size, 1, False)
        alone[0].update(max_tokens=41, cancel_at=0)
        await _drive(engine, alone)
        steady = dict(engine.stats)
        await _drive(engine, _schedule(5, cfg.vocab_size, 8, False))
        await engine.close()
        return alone[0], steady

    spec, steady = asyncio.run(main())
    assert per_dispatch and max(per_dispatch) <= 2
    # the lone stream: ONE resynchronisation (it took the slot), then a
    # hand-over only where the window crossed a page
    decodes = steady["steps_" + KINDS["decode"]]
    crossings = (len(spec["prompt"]) + 41) // BS - len(spec["prompt"]) // BS
    assert steady["step_state_resyncs"] == 1
    assert steady["step_state_resident"] == decodes - 1 >= 9
    assert steady["step_handovers_" + KINDS["decode"]] <= 1 + crossings
    series = engine.device_path_stats()
    for kind in KINDS.values():
        assert f'engine_step_handovers_total{{kind="{kind}"}}' in series
    assert series["engine_step_state_resyncs_total"] == (
        engine.stats["step_state_resyncs"])
    total = sum(engine.stats["steps_" + KINDS[k]]
                for k in ("decode", "mixed", "verify"))
    assert (series["engine_step_state_resyncs_total"]
            + series["engine_step_state_resident_total"]) == total


def test_more_cells_than_a_delta_holds_resynchronise():
    state = StepState(4, 64)
    state.place(0, seq_len=9, token=1, steps=0)
    state.hand_over()
    state.took(None, 0)
    state._dev = jnp.zeros((4, 4), jnp.int32)  # (something resident)
    table = np.arange(1, 65, dtype=np.int32)
    for slot in range(3):
        state.set_tables(slot, table + slot)
    assert len(state._cells) == 192 > DELTA_CELLS
    _rows, delta, sent = state.hand_over()
    assert sent == "mirror"
    assert (np.asarray(delta)[:, 0] == 4).all(), "a resync carries no cell"
    np.testing.assert_array_equal(state.tables[2], table + 2)


def test_a_cell_written_twice_goes_up_once_with_its_last_value():
    state = StepState(2, 8, window=True)
    state.place(1, seq_len=5, token=1, steps=0)
    state.hand_over()
    state.took(jnp.asarray(state.host), 0)
    full = np.zeros(8, np.int32)
    win = np.zeros(8, np.int32)
    win[2] = 7
    state.set_tables(1, full, win)
    win[2], win[3] = 0, 9  # released behind the window; the next taken
    state.set_tables(1, full, win)
    _rows, delta, sent = state.hand_over()
    cells = {(s, c): v for s, c, v in np.asarray(delta) if s < 2}
    assert sent == "cells"
    assert cells == {(1, state.table_column(1, 2)): 0,
                     (1, state.table_column(1, 3)): 9}


# ---------------- (d) no second compile ----------------


def _rows_for(cfg, b, m, sharding=None):
    state = StepState(b, m, window=cfg.window_kv_pool, sharding=sharding)
    for slot in range(b - 1):  # (the last slot dead)
        state.place(slot, seq_len=5 + slot, token=3 + slot, steps=0,
                    seed=slot, temperature=0.7, top_p=0.9)
        for t in state.table_views():
            t[slot, :4] = 1 + 4 * slot + np.arange(4)
    return state


@pytest.mark.parametrize("tp", [0, 4], ids=["one-device", "tp4-mesh"])
@pytest.mark.parametrize("program", ["decode_window", "mixed_step"])
def test_a_program_fed_its_own_rows_compiles_nothing_new(program, tp):
    """The rows a program returns carry the dtype, shape and sharding of
    the rows it takes: the second dispatch of the same batch, with the
    first's output, is a cache hit (also under a ``tp`` mesh, where the
    output is pinned replicated)."""
    cfg = ModelConfig.tiny(num_kv_heads=4)
    b, m, n = 4, 8, 64
    params = llama.init_params(cfg, jax.random.key(0))
    kc, vc = llama.init_kv_cache(cfg, n, BS)
    mesh = sharding = None
    if tp:
        mesh = pm.make_mesh(pm.MeshConfig(tp=tp), devices=jax.devices()[:tp])
        params = pm.LogicalLayout(cfg).place_params(params, mesh)
        cache_sh = pm.LogicalLayout(cfg).cache_sharding(mesh)
        kc, vc = jax.device_put(kc, cache_sh), jax.device_put(vc, cache_sh)
        sharding = pm.replicated(mesh)
    state = _rows_for(cfg, b, m, sharding)
    segs = ()
    if program == "mixed_step":
        p_tok = np.zeros((1, 16), np.int32)
        p_tok[0, :9] = np.arange(20, 29)
        table = np.zeros((1, m), np.int32)
        table[0, :4] = np.arange(40, 44)
        segs = jax.device_put(
            (p_tok, table, np.zeros(1, np.int32), np.asarray([9], np.int32)),
            sharding)
    fn = getattr(llama, program)
    sizes, lens = [], []
    for i in range(4):
        if i == 3:  # a slot changes hands: the mirror goes up whole again
            state.place(3, seq_len=0, token=0, steps=0)
        rows, delta, sent = state.hand_over()
        assert sent == ("mirror" if i in (0, 3) else None)
        out = fn(params, cfg, *llama.ROWS_RESIDENT, *segs, kc, vc,
                 mesh=mesh, rows=rows, rows_delta=delta)
        kc, vc = out[2:4] if program == "mixed_step" else out[1:3]
        state.took(out[-1], 1)
        for slot in range(b - 1):  # the host follows, as after an emit
            state.advance(slot, int(state.seq_lens[slot]) + 1, 0, 0)
        sizes.append(fn._cache_size())
        lens.append(np.asarray(out[-1])[:, 1].tolist())
    # (under a mesh the FIRST dispatch's caches come from device_put and
    # the later ones from the program, whose equivalent sharding spells
    # its spec shorter: one more entry, then and before this change)
    assert sizes[1] == sizes[2] == sizes[3], "a second compile"
    assert tp or sizes[0] == sizes[1]
    assert out[-1].dtype == jnp.int32 and out[-1].shape == state.host.shape
    if tp:
        assert out[-1].sharding == sharding
    # a live row counts up on the device, the dead one stays at 0
    assert lens[3] == [9, 10, 11, 0]


def test_a_warm_start_lowers_no_more_programs_than_before():
    """An engine's warm-up and first traffic on the tiny model: the
    programs JAX lowers, counted by its own monitoring event. 26 before
    the state was resident (the parent, the same script): the state adds
    no program (its delta is an argument, a resynchronisation a
    transfer), and the nine eager ``jnp.asarray`` it replaces were none."""
    from jax import monitoring

    lowered = []

    def listener(event, _secs, **_kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(event)

    cfg, params = _tiny_dense()
    params = jax.block_until_ready(params)
    monitoring.register_event_duration_secs_listener(listener)
    try:
        engine = JaxEngine(EngineConfig(
            model=cfg, num_blocks=96, block_size=BS, max_batch_size=4,
            max_context=128, prefill_chunk=32), params=params)

        async def main():
            await engine.warmup()
            await _drive(engine, _schedule(9, cfg.vocab_size, 4, False))
            await engine.close()

        jax.clear_caches()
        asyncio.run(main())
    finally:
        monitoring.unregister_event_duration_listener(listener)
    assert 0 < len(lowered) <= PARENT_LOWERED


#: what the parent (6b1adba) lowers in the test above
PARENT_LOWERED = 26
