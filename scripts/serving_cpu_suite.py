#!/usr/bin/env python
"""CPU-relative serving-layer artifacts (VERDICT r4 next #5/#6).

The composed serving path — HTTP frontend, SSE streaming, scheduler,
continuous batching, detokenization — has overheads no kernel bench
sees.  On a chip-less box the MODEL is tiny (so compute is cheap and
the serving layer dominates), which is exactly what makes the numbers
useful as serving-LAYER regression tracking: they are labeled
cpu-relative and never compared against chip rooflines.

Runs serve_bench presets through real OS-process servers:

  * tiny / byte tokenizer          (config-1-shaped workload)
  * tiny-mla / byte tokenizer      (config-5's model family)
  * tiny / real WordLevel tokenizer (tokenize + detokenize on the path)
  * tiny / byte with the chained decode loop on (the default) AND off
    (--no-decode-pipeline) — the ablation of EngineConfig.
    decode_pipeline (VERDICT r4 weak #2): the pair lands in the
    artifact so the overlap win/loss is a recorded number, not a claim.

Writes benchmarks/serving_cpu.json (full records) and appends one
summary line per run to benchmarks/serving_cpu_history.jsonl with a
median-of-recent regression band like the decode smoke's
(bench.check_smoke_regression — reused, one banding implementation).

Run:  python scripts/serving_cpu_suite.py          (~4 min on CPU)
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HISTORY = os.path.join(REPO, "benchmarks", "serving_cpu_history.jsonl")
ARTIFACT = os.path.join(REPO, "benchmarks", "serving_cpu.json")

PRESETS = [
    dict(name="tiny-byte", args=["--model-path", "tiny"]),
    dict(name="tiny-mla-byte", args=["--model-path", "tiny-mla"]),
    dict(name="tiny-hf-wordlevel",
         args=["--model-path", "tiny", "--sim-tokenizer"]),
    # the pipeline ablation's ON arm IS tiny-byte (identical args: the
    # chained loop is the default) — running it twice would double-pay
    # a full server spawn for a duplicate record
    dict(name="tiny-pipeline-off",
         args=["--model-path", "tiny", "--no-decode-pipeline"]),
]
COMMON = ["--cpu", "--n", "12", "--isl", "64", "--osl", "24",
          "--concurrency", "4", "--num-blocks", "256", "--max-batch", "8",
          "--startup-timeout", "300"]


def run_preset(p):
    cmd = [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py"),
           *p["args"], *COMMON]
    # own process group: a timeout must take the spawned SERVER down
    # with serve_bench, not leak it to eat the box (observed: one
    # leaked tiny-model server starved every later preset)
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, cwd=REPO,
                            start_new_session=True)
    try:
        out, err = proc.communicate(timeout=900)
    except subprocess.TimeoutExpired:
        import signal

        # TERM first: serve_bench's handler tears down the SERVER group
        # (it runs in its own session, so killpg here cannot reach it)
        os.killpg(proc.pid, signal.SIGTERM)
        try:
            proc.wait(timeout=30)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
        return {"preset": p["name"], "error": "timeout after 900s"}
    if proc.returncode != 0:
        return {"preset": p["name"], "error": err[-800:]}
    line = [ln for ln in out.splitlines() if ln.startswith("{")][-1]
    rec = json.loads(line)
    rec["preset"] = p["name"]
    return rec


def main():
    from bench import check_smoke_regression

    records = []
    for p in PRESETS:
        t0 = time.time()
        rec = run_preset(p)
        ok = "error" not in rec
        print(f"{p['name']:>20}: "
              + (f"{rec.get('tokens_per_sec', 0):8.1f} tok/s  "
                 f"ttft p50 {(rec.get('ttft_ms') or {}).get('p50', 0):7.1f} ms  "
                 f"itl p50 {(rec.get('itl_ms') or {}).get('p50', 0):6.2f} ms  "
                 f"({time.time()-t0:.0f}s)" if ok
                 else "FAILED " + rec["error"][-200:]),
              flush=True)
        records.append(rec)

    # history band on the byte preset's throughput (the stable one)
    base = next((r for r in records
                 if r["preset"] == "tiny-byte" and "error" not in r), None)
    summary = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    if base:
        history = []
        try:
            with open(HISTORY) as f:
                for ln in f:
                    try:
                        history.append(float(json.loads(ln)["tokens_per_sec"]))
                    except (ValueError, KeyError):
                        continue
        except OSError:
            pass
        ratio, regressed = check_smoke_regression(
            base["tokens_per_sec"], history)
        summary.update(
            tokens_per_sec=base["tokens_per_sec"],
            ttft_p50_ms=(base.get("ttft_ms") or {}).get("p50"),
            itl_p50_ms=(base.get("itl_ms") or {}).get("p50"),
            vs_prev=ratio, regressed=regressed,
        )
        if regressed:
            print(f"SERVING REGRESSION: {ratio:.2f}x recent median",
                  flush=True)

    # pipeline ablation delta as a first-class field (ON arm =
    # tiny-byte, the identical configuration but for the knob)
    off = next((r for r in records if r["preset"] == "tiny-pipeline-off"
                and "error" not in r), None)
    if base and off and off.get("tokens_per_sec"):
        summary["pipeline_speedup"] = round(
            base["tokens_per_sec"] / off["tokens_per_sec"], 4)

    with open(ARTIFACT, "w") as f:
        json.dump({"summary": summary, "records": records,
                   "note": "cpu-relative: tiny models on a CPU backend — "
                           "serving-LAYER overheads only, never chip "
                           "throughput"}, f, indent=1)
    with open(HISTORY, "a") as f:
        f.write(json.dumps(summary) + "\n")
    print(json.dumps(summary), flush=True)
    failed = [r["preset"] for r in records if "error" in r]
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
