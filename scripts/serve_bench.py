"""Full-stack serving benchmark: TTFT / ITL / throughput through
``in=http out=jax`` (VERDICT r2 #3 — the BASELINE metric is
tokens/sec/chip + p50/p99 TTFT & ITL on 3K-ISL/150-OSL-class workloads,
ref launch/dynamo-run/src/input/batch.rs:180-195).

Spawns one dynamo_run server process, drives N concurrent STREAMING
completions over real HTTP, and measures client-side:

  * TTFT: request start -> first SSE content chunk
  * ITL:  deltas between subsequent token-bearing chunks
  * throughput: total generated tokens / wall time

then scrapes the server's own /metrics histograms for the server-side
view. Writes one JSON line to stdout and (with --artifact) appends a
dated entry to docs/perf_log.md + writes BENCH_serving.json.

No real checkpoint reachable (zero egress)? ``--model-path
llama3-8b-sim`` serves the full Llama-3-8B architecture with random
weights through the byte tokenizer — identical compute/scheduling, fake
text. With a real checkpoint directory, pass its path (weights load via
models/weights.py, tokenizer via llm/tokenizer.HFTokenizer).

Run (TPU):  python scripts/serve_bench.py --model-path llama3-8b-sim \
                --n 32 --isl 3000 --osl 150 --concurrency 8 --artifact
Run (CPU smoke): JAX_PLATFORMS=cpu python scripts/serve_bench.py --cpu \
                --model-path tiny --n 4 --isl 64 --osl 16
"""

from __future__ import annotations

import argparse
import json
import os
import random
import socket
import subprocess
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _words(rng: random.Random, n: int) -> str:
    return " ".join(
        rng.choice(["alpha", "beta", "gamma", "delta", "eps", "zeta",
                    "eta", "theta", "iota", "kappa"])
        for _ in range(n)
    )


def make_workload(n: int, isl: int, osl: int, shared_prefix: float = 0.25,
                  seed: int = 0) -> list[dict]:
    rng = random.Random(seed)
    shared = _words(rng, int(isl * shared_prefix))
    return [
        {
            "prompt": shared + " " + _words(rng, isl - len(shared.split())),
            "max_tokens": osl,
        }
        for _ in range(n)
    ]


def _percentiles(xs: list[float], ps=(50, 99)) -> dict:
    if not xs:
        return {f"p{p}": None for p in ps}
    xs = sorted(xs)
    out = {}
    for p in ps:
        i = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
        out[f"p{p}"] = round(xs[i] * 1e3, 2)  # ms
    return out


def drive_one(port: int, model: str, item: dict, out: dict,
              count_tokens=len) -> None:
    body = json.dumps({
        "model": model,
        "prompt": item["prompt"],
        "max_tokens": item["max_tokens"],
        "temperature": 0.0,
        "stream": True,
        "stream_options": {"include_usage": True},
        # fixed-OSL workload shape (the reference's 3K/150 style): a
        # random-weights model would otherwise hit EOS at arbitrary
        # points and the comparison collapses
        "nvext": {"ignore_eos": True},
    }).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=body, headers={"Content-Type": "application/json"},
    )
    t0 = time.perf_counter()
    ttft = None
    itls: list[float] = []
    last = None
    n_tok = 0
    with urllib.request.urlopen(req, timeout=3600) as r:
        for raw in r:
            if not raw.startswith(b"data:"):
                continue
            payload = raw[5:].strip()
            if payload == b"[DONE]":
                break
            d = json.loads(payload)
            if d.get("usage"):
                # the include_usage summary chunk: the true token count
                # (the incremental detokenizer coalesces multibyte
                # fragments, so chunk count underestimates tokens)
                n_tok = d["usage"].get("completion_tokens", n_tok)
            if not d.get("choices"):
                continue
            text = d["choices"][0].get("text", "")
            if not text:
                continue
            now = time.perf_counter()
            if ttft is None:
                ttft = now - t0
            elif last is not None:
                # (gap, tokens in this chunk): count_tokens recovers the
                # chunk's token count for token-level ITL expansion —
                # len() for the byte tokenizer (one char per token),
                # whitespace-split for the word-level sim tokenizer
                itls.append((now - last, count_tokens(text)))
            last = now
    out["ttft"] = ttft
    out["chunk_itls"] = itls
    out["tokens"] = n_tok
    out["elapsed"] = time.perf_counter() - t0
    out["last"] = last
    # per-token ITL MEAN for this request: decode span / generated tokens
    if ttft is not None and last is not None and n_tok > 1:
        out["itl_token"] = (last - (t0 + ttft)) / (n_tok - 1)


def run_bench(port: int, model: str, work: list[dict],
              concurrency: int, count_tokens=len) -> dict:
    results: list[dict] = [dict() for _ in work]
    sem = threading.Semaphore(concurrency)

    def worker(i: int) -> None:
        with sem:
            try:
                drive_one(port, model, work[i], results[i], count_tokens)
            except Exception as e:  # noqa: BLE001
                results[i]["error"] = f"{type(e).__name__}: {e}"

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker, args=(i,)) for i in range(len(work))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    ok = [r for r in results if "error" not in r and r.get("ttft") is not None]
    errors = [r["error"] for r in results if "error" in r]
    # TOKEN-level ITL samples: each inter-chunk gap is the arrival gap
    # of its chunk's FIRST token; the other k-1 tokens arrived in the
    # same flush (gap ~0). This is the token-arrival distribution a
    # p99-ITL baseline speaks about — percentiling per-request means
    # would average away tail stalls inside requests.
    tok_itl: list[float] = []
    for r in ok:
        for gap, k in r["chunk_itls"]:
            tok_itl.append(gap)
            tok_itl.extend([0.0] * max(0, k - 1))
    req_mean_itl = [r["itl_token"] for r in ok if "itl_token" in r]
    total_tokens = sum(r["tokens"] for r in ok)
    return {
        "requests": len(work),
        "ok": len(ok),
        "errors": errors[:3],
        "wall_s": round(wall, 2),
        "tokens_total": total_tokens,
        "tokens_per_sec": round(total_tokens / wall, 1) if wall else 0,
        "ttft_ms": _percentiles([r["ttft"] for r in ok]),
        # token-level arrival-gap percentiles (the BASELINE ITL metric)
        "itl_ms": _percentiles(tok_itl),
        # per-request mean token ITL, percentiled across requests
        "itl_req_mean_ms": _percentiles(req_mean_itl),
    }


def scrape_metrics(port: int) -> dict:
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            text = r.read().decode()
    except OSError:
        return {}
    out = {}
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        for key in ("first_token_seconds", "inter_token_seconds"):
            if key in line and ("_sum" in line or "_count" in line):
                name, val = line.rsplit(" ", 1)
                out[name.strip()] = float(val)
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--model-path", default="llama3-8b-sim")
    p.add_argument("--n", type=int, default=32)
    p.add_argument("--isl", type=int, default=3000)
    p.add_argument("--osl", type=int, default=150)
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--num-blocks", type=int, default=2048)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--max-batch", type=int, default=16)
    p.add_argument("--decode-window", type=int, default=8)
    p.add_argument("--decode-pipeline", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="chained decode (EngineConfig.decode_pipeline, "
                        "the default); --no-decode-pipeline serves the "
                        "unchained loop — the ablation knob")
    p.add_argument("--quantization", default="none")
    p.add_argument("--kv-cache-dtype", default="model")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (smoke runs)")
    p.add_argument("--sim-tokenizer", action="store_true",
                   help="serve the sim preset through a REAL HF "
                        "(WordLevel+Metaspace) tokenizer sized to the "
                        "model vocab, so TTFT includes tokenization and "
                        "ITL includes detokenization (VERDICT r3 weak "
                        "#3); ISL then counts ~1 token per word")
    p.add_argument("--artifact", action="store_true",
                   help="append docs/perf_log.md + the artifact json")
    p.add_argument("--artifact-name", default="BENCH_serving.json",
                   help="artifact filename (distinct per benched config "
                        "so one config's result can't clobber another's)")
    p.add_argument("--startup-timeout", type=float, default=900.0)
    args = p.parse_args()

    # cleanup must run on TERM too (the suite/watch-loop timeout path):
    # convert it to SystemExit so the finally below tears the server
    # group down instead of leaking a chip-holding process
    import signal

    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    port = _free_port()
    env = os.environ.copy()
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO
    if args.cpu:
        env["JAX_PLATFORMS"] = "cpu"
    count_tokens = len
    tokenizer_args = []
    if args.sim_tokenizer:
        # word-level real-tokenizer fixture sized to the preset's vocab
        # (every id a random-weights model can emit must be decodable)
        sim_vocabs = {"llama3-8b-sim": 128256, "deepseek-8b-sim": 32768,
                      "tiny": 512}
        if args.model_path not in sim_vocabs:
            raise SystemExit(
                "--sim-tokenizer only applies to the sim presets "
                f"{sorted(sim_vocabs)}; real checkpoints carry their own"
            )
        import tempfile

        from make_tokenizer_fixture import make_sim_wordlevel

        tok_dir = make_sim_wordlevel(
            sim_vocabs[args.model_path],
            tempfile.mkdtemp(prefix="dyn_simtok_"),
        )
        tokenizer_args = ["--tokenizer", tok_dir]
        count_tokens = lambda text: max(1, len(text.split()))  # noqa: E731
    # own process group: timeouts/INT must take the server down with
    # this harness, never leak it to hold the chip (watch loop sends
    # SIGINT so the finally below actually runs)
    server = subprocess.Popen(
        [sys.executable, "-m", "dynamo_tpu.launch.dynamo_run",
         "in=http", "out=jax", "--model-path", args.model_path,
         "--host", "127.0.0.1", "--http-port", str(port),
         "--num-blocks", str(args.num_blocks),
         "--block-size", str(args.block_size),
         "--max-batch", str(args.max_batch),
         "--decode-window", str(args.decode_window),
         "--quantization", args.quantization,
         "--kv-cache-dtype", args.kv_cache_dtype,
         *([] if args.decode_pipeline else ["--no-decode-pipeline"]),
         *tokenizer_args],
        env=env, cwd=REPO, start_new_session=True,
    )
    try:
        deadline = time.monotonic() + args.startup_timeout
        model_name = os.path.basename(os.path.normpath(args.model_path))
        while time.monotonic() < deadline:
            if server.poll() is not None:
                raise RuntimeError(f"server exited rc={server.returncode}")
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/models", timeout=2
                ) as r:
                    names = [m["id"] for m in json.loads(r.read())["data"]]
                    if names:
                        model_name = names[0]
                        break
            except OSError:
                pass
            time.sleep(1.0)
        else:
            raise TimeoutError("server never came up")

        # warmup: compile every prefill bucket + the decode window
        warm = make_workload(2, args.isl, min(args.osl, 8), seed=1)
        run_bench(port, model_name, warm, concurrency=1)

        work = make_workload(args.n, args.isl, args.osl)
        result = run_bench(port, model_name, work, args.concurrency,
                           count_tokens)
        result.update({
            "model": args.model_path,
            "tokenizer": "hf_wordlevel" if args.sim_tokenizer else "byte",
            "isl_words": args.isl,
            "osl": args.osl,
            "concurrency": args.concurrency,
            "backend": "cpu" if args.cpu else "tpu",
            "quantization": args.quantization,
            "decode_pipeline": args.decode_pipeline,
            "server_metrics": scrape_metrics(port),
        })
        print(json.dumps(result), flush=True)
        if args.artifact:
            with open(os.path.join(REPO, args.artifact_name), "w") as f:
                json.dump(result, f, indent=1)
            stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
            with open(os.path.join(REPO, "docs", "perf_log.md"), "a") as f:
                f.write(
                    f"\n## serve_bench — {stamp}\n\n```json\n"
                    + json.dumps(result, indent=1) + "\n```\n"
                )
    finally:
        server.terminate()
        try:
            server.wait(timeout=15)
        except subprocess.TimeoutExpired:
            server.kill()
        import signal

        try:  # group sweep: the server may have spawned engine subprocs
            os.killpg(server.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


if __name__ == "__main__":
    main()
