#!/usr/bin/env python3
"""chipbench/run.py — one run of one cell of the on-chip serving benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything
that belongs to it is found BY NAME: its configuration's directory
(``chipbench/configs/<config>/``: ``config.json``, ``serve.json`` and
the plain ``reference.py``), its
traffic mix (``chipbench/traffic/<traffic>.json``, read by the generator
the mix names in ``chipbench/generators/``), its arrival rate
(``chipbench/cells/<cell>.json``) and, with ``--trace 1``, each per-layer
metric's ``chipbench/layer_metrics/<metric>.json`` with the reducer it
names in ``chipbench/reducers/``. See ``chipbench/README.md``.

This parent never imports JAX (a chip belongs to one process): it starts
one ``dynamo_run in=http out=jax`` child, warms up the cell's shapes,
checks the server against the plain reference, drives the traffic over
HTTP for ``--seconds`` and prints one JSON object as its last line.
Without a TPU it exits non-zero and prints no result (``--rehearse``
serves a tiny model on the CPU for the builder's own rehearsals).
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import importlib
import json
import math
import os
import shutil
import subprocess
import sys
import time

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from chipbench import generators, stats  # noqa: E402
from chipbench.client import (  # noqa: E402
    N_RESERVED, BenchFailure, Server, StreamResult, http_json,
    make_sim_wordlevel, say, stream_request, token_id,
)

WORK = os.path.join(HERE, "work")  # git-ignored: tokenizers, caches, logs
SOURCE_KINDS = {  # a layer metric's source kind -> BENCHMARK.json's word
    "client": "host_clock", "span": "program_span",
    "metrics_delta": "program_counter", "device_trace": "device_trace",
}


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


# ---------------- the cell's files, by name ----------------


class Cell:
    def __init__(self, name: str, rehearse: bool):
        self.bench = load_json(REPO, "BENCHMARK.json")
        entry = next(
            (w for w in self.bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise BenchFailure(f"no workload {name!r} in BENCHMARK.json")
        self.name, self.chips = name, entry["chips"]
        self.config_name, self.traffic_name = entry["config"], entry["traffic"]
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == self.config_name)
        self.config_dir = os.path.dirname(os.path.join(REPO, cfg["file"]))
        self.serve = load_json(self.config_dir, "serve.json")
        self.reference_path = os.path.join(self.config_dir, "reference.py")
        if rehearse:
            self.config_dir = os.path.join(
                HERE, "testdata", self.serve["rehearse"]["config"])
        self.model_config = load_json(self.config_dir, "config.json")
        self.mix = load_json(HERE, "traffic", self.traffic_name + ".json")
        self.params = load_json(HERE, "cells", name + ".json")
        if rehearse:
            self.params.update(self.params.get("rehearse", {}))
        self.flags = list(
            self.serve["rehearse"]["flags"] if rehearse else self.serve["flags"])
        self.rehearse = rehearse

    def metrics(self, group: str) -> list:
        return [m for m in self.bench[group]
                if self.name in m.get("workloads", [self.name])]

    def flag(self, name: str, default=None):
        return (self.flags[self.flags.index(name) + 1]
                if name in self.flags else default)


def prepare_model_dir(cell: Cell) -> str:
    """``config.json`` + a generated full-vocabulary tokenizer in a
    git-ignored directory named after the configuration (the server
    serves the model under its directory's name)."""
    sub = "rehearse" if cell.rehearse else "models"
    out = os.path.join(WORK, sub, cell.config_name)
    os.makedirs(out, exist_ok=True)
    shutil.copyfile(os.path.join(cell.config_dir, "config.json"),
                    os.path.join(out, "config.json"))
    stamp = os.path.join(out, "tokenizer.vocab_size")
    vocab = cell.model_config["vocab_size"]
    if not (os.path.exists(stamp) and open(stamp).read() == str(vocab)):
        make_sim_wordlevel(vocab, out)
        with open(stamp, "w") as f:
            f.write(str(vocab))
    return out


def build_native_hasher() -> None:
    """``native/build/libdynamo_native.so`` from ``native/*.cc``, as
    ``chip_smoke.prepare`` does (the module imports no JAX): a fresh
    checkout has no build, and the engine would hash KV blocks with the
    slower Python twin on the measured host path. A build that fails
    ends the run; /metrics says which hasher served, and the twin makes
    the run incorrect."""
    from dynamo_tpu import native

    try:
        native.ensure_fresh()
    except Exception as e:  # noqa: BLE001 — whatever the build raises
        raise BenchFailure(
            f"native hasher not built ({type(e).__name__}: {e})") from e


def child_env(rehearse: bool) -> dict:
    env = os.environ.copy()
    env["PYTHONPATH"] = REPO
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


# ---------------- warm-up and the reference check ----------------


def mixed_buckets(cell: Cell) -> list:
    """Prefill-length buckets a fused mixed (decode + prefill) step can
    take under the configuration's ``--mixed-step-budget``: programs the
    server's own ``--warmup`` does not reach and the window can."""
    budget = int(cell.flag("--mixed-step-budget", 2048))
    return [b for b in (16, 32, 64, 128, 256, 512, 1024, 2048) if b <= budget]


async def warm_mixed(srv: Server, cell: Cell, vocab_words: int) -> None:
    """One long greedy stream decodes while prompts of every mixed
    bucket arrive one at a time, so each (1 segment x bucket) program
    compiles, or is read from the compile cache, before the window."""
    rng = np.random.default_rng(12345)
    buckets = mixed_buckets(cell)
    mix = {"endpoint": "completions", "sampling": {"temperature": 0}}
    path, body = generators.request_body(
        mix, srv.model_name, generators.words(rng, 24, vocab_words),
        3000, 0)
    decoding = asyncio.Event()
    long_stream = asyncio.create_task(stream_request(
        srv.port, path, body, "warm-long", 1100, first=decoding))
    try:
        try:
            await asyncio.wait_for(decoding.wait(), 1100)
        except TimeoutError:
            raise BenchFailure(
                "warm-up: the long stream never produced a token:\n"
                + srv.log_tail()) from None
        for b in buckets:
            _p, body = generators.request_body(
                mix, srv.model_name,
                generators.words(rng, b - 2, vocab_words), 2, 0)
            r = await stream_request(srv.port, path, body, f"warm-{b}", 1100)
            if r.error:
                raise BenchFailure(f"warm-up prompt of {b} tokens: {r.error}")
    finally:
        long_stream.cancel()
        try:
            await long_stream
        except asyncio.CancelledError:
            pass


def ask_logprobs(srv: Server, prompt: str, ref: dict) -> dict:
    """The greedy non-streamed request with logprobs (as
    ``chip_smoke.greedy_logprobs``): the server's own tokens and, at the
    first and last generated position, its candidates' logprobs."""
    resp = http_json(srv.base + "/v1/completions", {
        "model": srv.model_name, "prompt": prompt,
        "max_tokens": ref["answer_tokens"], "temperature": 0,
        "logprobs": ref["top_logprobs"], "nvext": {"ignore_eos": True},
    })
    lp = resp["choices"][0]["logprobs"]
    if len(lp["tokens"]) != ref["answer_tokens"]:
        raise BenchFailure(f"logprobs: {len(lp['tokens'])} tokens for "
                           f"{ref['answer_tokens']}")
    out = {"prompt_tokens": resp["usage"]["prompt_tokens"], "tokens": [],
           "candidates": {}}
    try:
        out["tokens"] = [token_id(t) for t in lp["tokens"]]
    except KeyError:
        out["tokens"] = None  # a control word was generated: not scored
        return out
    for pos in (0, ref["answer_tokens"] - 1):
        cands = dict(lp["top_logprobs"][pos])
        cands[lp["tokens"][pos]] = lp["token_logprobs"][pos]
        ids = {}
        for word, val in cands.items():
            try:
                ids[token_id(word)] = val
            except KeyError:
                pass  # control words: not scored
        out["candidates"][str(pos)] = ids
    return out


class ReferenceCheck:
    """The configuration's plain float32 reference (``reference.py``
    beside its ``config.json``), run by ``chipbench/reference.py`` in a
    ``JAX_PLATFORMS=cpu`` child that builds the weights while the server
    starts. Its scores are cached in ``chipbench/work/`` by configuration,
    seed, prompt and the server's own tokens, so only a cell's first run
    in a checkout pays for it."""

    def __init__(self, cell: Cell, vocab_words: int):
        self.cell = cell
        self.ref = load_json(HERE, "reference.json")
        rng = np.random.default_rng(self.ref["prompt_seed"])
        self.prompts = [
            generators.words(rng, self.ref["prompt_tokens"], vocab_words)
            for _ in range(self.ref["prompts"])]
        sub = "rehearse" if cell.rehearse else "models"
        self.cache_path = os.path.join(
            WORK, sub, cell.config_name, "reference_cache.json")
        self.cache = (load_json(self.cache_path)
                      if os.path.exists(self.cache_path) else {})
        self.child = None
        if not self.cache:
            self._start_child()

    def _start_child(self) -> None:
        env = child_env(self.cell.rehearse)
        env["JAX_PLATFORMS"] = "cpu"
        self.child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "reference.py"),
             "--config-dir", self.cell.config_dir,
             "--reference", self.cell.reference_path,
             "--seed", str(self.ref["weights_seed"])],
            cwd=REPO, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=open(os.path.join(WORK, "reference.log"), "wb"), text=True,
        )

    def _key(self, prompt: str, tokens: list) -> str:
        h = hashlib.sha256(
            json.dumps([self.ref["weights_seed"], prompt, tokens]).encode())
        return h.hexdigest()[:24]

    def check(self, srv: Server) -> tuple[bool, float, int]:
        """(within tolerance, max |logprob diff|, positions scored)."""
        answers = [ask_logprobs(srv, p, self.ref) for p in self.prompts]
        todo = {}
        for p, a in zip(self.prompts, answers):
            if a["tokens"] is not None and self._key(p, a["tokens"]) not in self.cache:
                todo[self._key(p, a["tokens"])] = {
                    "prompt": [token_id(w) for w in p.split()],
                    "prompt_tokens": a["prompt_tokens"],
                    "tokens": a["tokens"],
                    "candidates": {k: sorted(v) for k, v in
                                   a["candidates"].items()},
                }
        if todo:
            if self.child is None:
                self._start_child()
            out, _ = self.child.communicate(json.dumps(todo) + "\n",
                                            timeout=1500)
            if self.child.returncode != 0:
                raise BenchFailure(
                    "the reference child failed: see chipbench/work/reference.log")
            self.cache.update(json.loads(out.strip().splitlines()[-1]))
            with open(self.cache_path, "w") as f:
                json.dump(self.cache, f)
        self.close()
        worst, scored = 0.0, 0
        for p, a in zip(self.prompts, answers):
            if a["tokens"] is None:
                continue
            want = self.cache[self._key(p, a["tokens"])]
            for pos, cands in a["candidates"].items():
                for tid, got in cands.items():
                    worst = max(worst, abs(got - want[pos][str(tid)]))
                scored += 1
        return (scored >= self.ref["min_positions"]
                and worst <= self.ref["tolerance"]), worst, scored

    def close(self) -> None:
        if self.child is not None and self.child.poll() is None:
            self.child.kill()
        if self.child is not None:
            self.child.wait()
            self.child = None


# ---------------- the measured traffic ----------------


class Record:
    """One request's outcome, as the metrics read it."""

    def __init__(self, req, due_at, rid):
        self.req, self.due_at, self.rid = req, due_at, rid
        self.res = StreamResult()
        self.cut = False  # still streaming when the drain ended

    @property
    def ok(self) -> bool:
        """Answered in full: no error, and the server counts as many
        completion tokens as were asked for (``ignore_eos``)."""
        u = self.res.usage or {}
        return (self.res.error is None
                and u.get("completion_tokens") == self.req.max_tokens)

    def usage_adds_up(self) -> bool:
        """The prompt is as long as the generator made it, the total is
        the sum, and the streamed words are the completion tokens (less
        the rare control word, which the detokenizer drops)."""
        u, got = self.res.usage or {}, len(self.res.token_times)
        return (u.get("prompt_tokens") == self.req.prompt_tokens
                and u.get("total_tokens")
                == self.req.prompt_tokens + self.req.max_tokens
                and 0.95 * self.req.max_tokens - 1 <= got
                <= self.req.max_tokens)


async def one(srv, rec: Record):
    await stream_request(srv.port, rec.req.path, rec.req.body, rec.rid,
                         res=rec.res)


async def drive_open(srv, reqs, t0: float, drain_s: float) -> list:
    """Open loop: every request is sent when it falls due, whatever the
    earlier ones are doing. Returns all records, lead-in included."""
    records, tasks = [], []
    for r in sorted(reqs, key=lambda r: r.due_s):
        await sleep_until(t0 + r.due_s)
        rec = Record(r, t0 + r.due_s, f"r{r.index}")
        records.append(rec)
        tasks.append(asyncio.create_task(one(srv, rec)))
    # an answer still streaming drain_s after the last request fell due
    # is cut there and measured on the tokens it had received (a mean
    # answer outlasts any drain a run can afford); only one with no
    # token at all by then counts as failed
    _done, late = await asyncio.wait(tasks, timeout=drain_s)
    for t in late:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    for rec in records:
        if rec.res.error is None and rec.res.usage is None:
            rec.cut = True
            if not rec.res.token_times:
                rec.res.error = f"no token {drain_s} s after the window"
    return records


async def sleep_until(t: float) -> None:
    delay = t - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)


async def run_window(srv, cell: Cell, reqs, seconds: float, trace: bool):
    """Lead-in, then the window. Returns (records, t0, the set-up time
    and /metrics at the window's start, /metrics at its end, the
    profile's answer or None)."""
    lead = float(cell.mix["lead_s"])
    t0 = time.monotonic() + lead
    loop = asyncio.get_running_loop()
    box = {"setup_s": t0 - T_PROCESS_START}

    async def metrics_at_start():
        await sleep_until(t0 - 0.1)
        box["m0"] = await loop.run_in_executor(None, srv.metrics)

    async def profile():
        n = float(cell.params.get("profile_s", 3))
        await sleep_until(t0 + max((seconds - n) / 2, 0))
        t_a = time.monotonic()
        ans = await loop.run_in_executor(
            None, lambda: http_json(
                f"{srv.base}/profile?seconds={n}", body={}, timeout=300,
                method="POST"))
        ans["asked_at"], ans["answered_at"] = t_a, time.monotonic()
        return ans

    prof = asyncio.create_task(profile()) if trace else None
    at_start = asyncio.create_task(metrics_at_start())
    records = await drive_open(
        srv, reqs, t0, float(cell.mix.get("drain_s", 30)))
    await at_start
    m1 = await loop.run_in_executor(None, srv.metrics)
    return records, t0, box, m1, (await prof if prof else None)


# ---------------- metrics ----------------


def client_latencies(window: list) -> dict:
    """Percentiles on the client's clock over the requests due in the
    window; a failed request ranks above every measured value."""
    good = lambda r: r.res.error is None and (r.ok or r.cut)  # noqa: E731
    ttft = [stats.ttft_ms(r.due_at, r.res.token_times) if good(r)
            else stats.FAILED for r in window]
    tpot = [stats.tpot_ms(r.res.token_times) if good(r) else stats.FAILED
            for r in window]
    return {
        "ttft_p50_ms": stats.percentile(ttft, 50),
        "ttft_p90_ms": stats.percentile(ttft, 90),
        "tpot_p50_ms": stats.percentile(tpot, 50),
        "tpot_p90_ms": stats.percentile(tpot, 90),
        # one failed request and the time per token has no value either
        "tpot_mean_ms": stats.tpot_mean_ms(
            [r.res.token_times for r in window])
        if all(good(r) for r in window) else stats.FAILED,
    }


def longest_silence_s(records: list, a: float, b: float) -> float:
    """The longest stretch of [a, b) in which no stream received a
    chunk: a decode window of several steps is silent for its length,
    a stalled host or server for longer."""
    times = sorted({t for r in records for t in r.res.token_times
                    if a <= t < b})
    edges = [a] + times + [b]
    return max(y - x for x, y in zip(edges, edges[1:]))


def write_requests(cell: Cell, args, records: list, t0: float) -> str:
    """Every request of the run (lead-in included) with its chunks'
    arrival times, into ``chipbench/work/``: what any client-side
    statistic can be recomputed from, overwritten by the next run."""
    rows = []
    for r in records:
        chunks = []
        for t in r.res.token_times:
            ms = round((t - t0) * 1e3, 1)
            if chunks and chunks[-1][0] == ms:
                chunks[-1][1] += 1
            else:
                chunks.append([ms, 1])
        rows.append({
            "rid": r.rid, "due_s": round(r.req.due_s, 4),
            "sent_late_ms": None if r.res.sent_at is None
            else round((r.res.sent_at - r.due_at) * 1e3, 2),
            "prompt_tokens": r.req.prompt_tokens,
            "max_tokens": r.req.max_tokens, "cut": r.cut,
            "error": r.res.error, "chunks": chunks,
        })
    path = os.path.join(WORK, f"requests_{cell.name}.json")
    with open(path, "w") as f:
        json.dump({"workload": cell.name, "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace,
                   "requests": rows}, f)
    return path


def layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Each per-layer metric of the cell through its own data file and
    the reducer that file names; one that finds nothing is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        if SOURCE_KINDS[spec["source"]] != m["source"]:
            raise BenchFailure(f"{m['name']}: source kinds disagree")
        reducer = importlib.import_module(
            f"chipbench.reducers.{spec['reducer']}")
        value = reducer.reduce(ctx, spec.get("selector", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def fetch_spans(srv: Server, window: list) -> dict:
    """{span name: [dur_ms]} over the window's requests, from /trace."""
    spans = {}
    for rec in window:
        try:
            body = http_json(f"{srv.base}/trace/{rec.rid}", timeout=30)
        except BenchFailure:
            continue  # the collector keeps the newest 1024 traces
        for s in body.get("spans") or []:
            spans.setdefault(s["name"], []).append(s["dur_ms"])
    return spans


def reduce_trace(trace_dir: str, rehearse: bool) -> dict:
    """The device trace through ``chipbench/trace_reduce.py`` in a CPU
    child (reading an xplane needs JAX; the parent stays off it)."""
    env = child_env(rehearse)
    env["JAX_PLATFORMS"] = "cpu"
    out = os.path.join(WORK, "trace_reduced.json")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "trace_reduce.py"), trace_dir,
         "--out", out, "--keep", os.path.join(WORK, "last_trace")],
        cwd=REPO, env=env, text=True, capture_output=True, timeout=600)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if r.returncode != 0:
        raise BenchFailure(f"trace_reduce failed:\n{r.stderr[-3000:]}")
    return load_json(out)


# ---------------- one run ----------------


def device_of(m: dict) -> dict:
    key = next((k for k in m if k.startswith("engine_device{")), None)
    if key is None:
        raise BenchFailure("/metrics names no engine_device")
    plat = key.split('platform="')[1].split('"')[0]
    kind = key.split('kind="')[1].split('"')[0]
    peak = max((v for k, v in m.items()
                if k.startswith("engine_device_peak_bytes_in_use")), default=0)
    return {"platform": plat, "kind": kind, "count": int(m[key]),
            "memory_peak_bytes": int(peak)}


def run(args) -> int:
    cell = Cell(args.workload, args.rehearse)
    os.makedirs(WORK, exist_ok=True)
    model_dir = prepare_model_dir(cell)
    build_native_hasher()
    vocab_words = cell.model_config["vocab_size"] - N_RESERVED
    peaks = load_json(HERE, "peaks.json")
    flags = cell.flags + (["--trace"] if args.trace else [])
    gen = generators.load(cell.mix["generator"])
    reqs = gen.generate(cell.mix, cell.params, args.seconds, vocab_words,
                        args.seed, os.path.basename(model_dir))
    ref = ReferenceCheck(cell, vocab_words)
    log_path = os.path.join(WORK, f"server_{cell.name}.log")
    try:
        with Server(REPO, model_dir, flags, log_path,
                    child_env(args.rehearse)) as srv:
            m = srv.metrics()
            device = device_of(m)
            say(f"server ready after {srv.start_s:.1f} s on {device}; "
                f"compile cache {m['engine_compile_cache_hits']:.0f} hits "
                f"{m['engine_compile_cache_misses']:.0f} misses")
            if not args.rehearse:
                if device["platform"] != "tpu":
                    raise BenchFailure(
                        f"JAX found no accelerator: {device['platform']!r}")
                if device["kind"] not in peaks:
                    raise BenchFailure(
                        f"no peaks for device kind {device['kind']!r}")
            if device["count"] < cell.chips:
                raise BenchFailure(
                    f"need {cell.chips} chips, JAX sees {device['count']}")
            t = time.monotonic()
            asyncio.run(warm_mixed(srv, cell, vocab_words))
            ref_ok, ref_worst, ref_n = ref.check(srv)
            say(f"warm-up {time.monotonic() - t:.1f} s; reference check: "
                f"max |logprob diff| {ref_worst:.4f} over {ref_n} positions "
                f"(tolerance {ref.ref['tolerance']}) -> "
                f"{'ok' if ref_ok else 'FAILED'}")
            records, t0, box, m1, prof = asyncio.run(
                run_window(srv, cell, reqs, args.seconds, bool(args.trace)))
            result = report(cell, args, srv, records, t0, box, m1, prof,
                            ref_ok, device_of(m1), peaks)
    finally:
        ref.close()
    print(json.dumps(result), flush=True)
    return 0


def report(cell, args, srv, records, t0, box, m1, prof, ref_ok, device,
           peaks):
    seconds, m0 = args.seconds, box["m0"]
    window = [r for r in records if r.req.due_s >= 0]
    done = [r for r in window if r.ok]
    failed = [r for r in window if not (r.ok or r.cut)
              or r.res.error is not None]
    delta = {k: m1.get(k, 0) - m0.get(k, 0) for k in m1}
    path = next((k for k in m1 if k.startswith("engine_attention_path")), "?")
    checks = {
        "reference": ref_ok,
        "pallas_path": args.rehearse or (
            'path="pallas"' in path and m1.get("engine_use_pallas") == 1),
        "native_hasher": m1.get("engine_native_hasher") == 1,
        "no_compile_in_window": delta["engine_xla_compiles_total"] == 0
        and delta["engine_compile_cache_misses"] == 0,
        "usage_adds_up": all(r.usage_adds_up() for r in done),
        "some_completed": len(done) > 0,
    }
    for r in failed[:5]:
        say(f"failed {r.rid}: {r.res.error or 'short answer'} "
            f"({len(r.res.token_times)}/{r.req.max_tokens} tokens, usage "
            f"{r.res.usage})")
    bad_usage = [r for r in done if not r.usage_adds_up()][:3]
    for r in bad_usage:
        say(f"usage {r.rid}: {r.res.usage} expected prompt "
            f"{r.req.prompt_tokens} + {r.req.max_tokens}")
    seen = dict(client_latencies(window), setup_s=box["setup_s"])
    lateness = sorted((r.res.sent_at - r.due_at) * 1e3 for r in window
                      if r.res.sent_at is not None)
    say(f"window {seconds} s: attempted {len(window)}, completed "
        f"{len(done)}, cut by the drain {sum(r.cut for r in window)}, "
        f"failed {len(failed)}; generator lateness p90 "
        f"{stats.percentile(lateness, 90):.2f} ms max {lateness[-1]:.2f} ms; "
        f"prompt tokens {sum(r.req.prompt_tokens for r in window)}, "
        f"output tokens received "
        f"{sum(len(r.res.token_times) for r in window)}")
    say(f"longest silence on every stream at once in the window "
        f"{longest_silence_s(records, t0, t0 + seconds):.3f} s; requests "
        f"and chunk times in {write_requests(cell, args, records, t0)}")
    say(f"engine in window: mixed steps +{delta['engine_mixed_steps']:.0f}, "
        f"prefix-cache hit tokens "
        f"+{delta['engine_prefix_cache_hits_tokens']:.0f}, programs compiled "
        f"+{delta['engine_xla_compiles_total']:.0f}, compile cache "
        f"+{delta['engine_compile_cache_hits']:.0f} hits "
        f"+{delta['engine_compile_cache_misses']:.0f} misses; peak device "
        f"memory {device['memory_peak_bytes'] / 2**30:.2f} GiB; {path}")
    e2e = {m["name"]: seen[m["name"]] for m in cell.metrics("end_to_end")}
    say(f"end to end: {json.dumps(e2e)}")
    say("on the client's clock, not metrics of this cell: "
        + json.dumps({k: v for k, v in seen.items() if k not in e2e}))
    say(f"checks: {json.dumps(checks)}")
    result = {
        "correct": all(checks.values()), "attempted": len(window),
        "failed": len(failed), "device": device,
    }
    if not all(math.isfinite(v) for v in e2e.values()):
        # a percentile that reaches into the failed requests has no value
        raise BenchFailure(f"{len(failed)} of {len(window)} requests failed: "
                           f"no finite value for {e2e}")
    if not args.trace:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")
        }
        return result
    ctx = {
        "window": window, "delta": delta,
        "spans": fetch_spans(srv, window), "device": None, "peaks": peaks,
    }
    if prof is not None and not args.rehearse:
        dev = reduce_trace(prof["trace_dir"], args.rehearse)
        # the trace starts when the profile was asked for: the first op
        # came lead seconds later on the client's clock, the last op
        # window_s after that (tokens_between takes an error of some
        # tens of milliseconds in that: a stream's pace is steady)
        lead, trail = (g for _n, g in dev["edge_gaps"])
        a = prof["asked_at"] + lead
        dev["tokens_in_window"] = sum(
            stats.tokens_between(r.res.token_times, a, a + dev["window_s"])
            for r in records)
        ctx["device"] = dev
        result["device"]["busy_s"] = dev["busy_s"]
        result["device"]["window_s"] = dev["window_s"]
        result["breakdown"] = {
            "device_ops": dev["device_ops"][:10],
            "idle_gaps": dev["idle_gaps"][:8] + [
                ["profiler_edge." + n, g] for n, g in dev["edge_gaps"]],
        }
        say(f"device trace: {dev['trace_s']:.3f} s traced, first op to last "
            f"{dev['window_s']:.3f} s (the profiler's edges: {lead:.3f} s "
            f"before, {trail:.3f} s after), busy {dev['busy_s']:.3f} s, "
            f"{dev['n_ops']} ops on {dev['planes']}; "
            f"{dev['tokens_in_window']:.1f} client tokens fell to it; by "
            f"program {dev['programs'][:4]}")
    elif prof is not None:
        shutil.rmtree(prof["trace_dir"], ignore_errors=True)
    result["metrics"] = layer_metrics(cell, ctx)
    return result


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="serve the configuration's tiny stand-in on the "
                    "CPU (builder's rehearsal: no device metric is printed)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "dynamo_tpu")):
        print("chipbench: no dynamo_tpu package beside chipbench/ — there "
              "is no system to measure", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(load_json(REPO, "BENCHMARK.json")["run_seconds"])
    try:
        return run(args)
    except (BenchFailure, subprocess.TimeoutExpired) as e:
        print(f"chipbench FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
