"""The server child and the HTTP client the benchmark drives it with.

``Server``, the ``/metrics`` parser and the tokenizer builder are copies
of ``chip_smoke.py`` / ``scripts/make_tokenizer_fixture.py`` (copied, not
imported: later PRs may change those, and the yardstick must not move).
The streaming client is asyncio on one thread: every request of a run is
a coroutine on one event loop, so the load comes from one process with
one thread and is timed by one clock (``time.monotonic``).

Nothing here imports JAX: the parent must stay off the chip.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

CHAT_TEMPLATE = (
    "{% for message in messages %}"
    "<|{{ message['role'] }}|>{{ message['content'] }}</s>"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>{% endif %}"
)
#: ids 0..5 are control words, 6..15 ten greek words, the rest fillers
N_RESERVED = 16


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print no result."""


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------- tokenizer (copy of make_sim_wordlevel) ----------------


def filler_word(i: int) -> str:
    """The i-th filler word of the generated vocabulary (id 16 + i)."""
    return f"w{i:06d}"


def token_id(word: str) -> int:
    """Vocabulary id of a filler word as the server prints it."""
    w = word.strip().lstrip("\u2581")
    if len(w) == 7 and w[0] == "w" and w[1:].isdigit():
        return N_RESERVED + int(w[1:])
    raise KeyError(word)


def make_sim_wordlevel(vocab_size: int, out_dir: str) -> str:
    """A WordLevel+Metaspace HF tokenizer with EXACTLY ``vocab_size``
    entries: one token per word, every id decodable."""
    from tokenizers import Tokenizer, decoders, models, pre_tokenizers

    words = ["alpha", "beta", "gamma", "delta", "eps", "zeta",
             "eta", "theta", "iota", "kappa"]
    vocab = {"<unk>": 0, "<s>": 1, "</s>": 2,
             "<|user|>": 3, "<|assistant|>": 4, "<|system|>": 5}
    for w in words:
        vocab["\u2581" + w] = len(vocab)
    assert len(vocab) == N_RESERVED
    i = 0
    while len(vocab) < vocab_size:
        vocab["\u2581" + filler_word(i)] = len(vocab)
        i += 1
    tok = Tokenizer(models.WordLevel(vocab, unk_token="<unk>"))
    tok.pre_tokenizer = pre_tokenizers.Metaspace()
    tok.decoder = decoders.Metaspace()
    os.makedirs(out_dir, exist_ok=True)
    tok.save(os.path.join(out_dir, "tokenizer.json"))
    with open(os.path.join(out_dir, "tokenizer_config.json"), "w") as f:
        json.dump(
            {
                "tokenizer_class": "PreTrainedTokenizerFast",
                "bos_token": "<s>", "eos_token": "</s>",
                "unk_token": "<unk>", "chat_template": CHAT_TEMPLATE,
            },
            f, indent=1,
        )
    return out_dir


# ---------------- the server child (copy of chip_smoke.Server) ----------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(url: str, body=None, timeout: float = 600, method=None) -> dict:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"},
        method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise BenchFailure(
            f"{url} answered {e.code}: {e.read()[:500]!r}") from e


def parse_metrics(text: str) -> dict:
    """Prometheus text as {series-with-labels: value}, prefix dropped."""
    out = {}
    for ln in text.splitlines():
        if ln and not ln.startswith("#"):
            key, _, val = ln.rpartition(" ")
            out[key.removeprefix("dynamo_tpu_")] = float(val)
    return out


class Server:
    """One ``dynamo_run in=http out=jax`` child. A context manager: the
    child is stopped (SIGINT, then kill) however the block ends."""

    def __init__(self, repo: str, model_dir: str, flags: list, log_path: str,
                 env: dict, start_timeout_s: float = 1100):
        self.repo = repo
        self.model_name = os.path.basename(model_dir)
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = log_path
        self.env = env
        self.start_timeout_s = start_timeout_s
        self.argv = [
            sys.executable, "-m", "dynamo_tpu.launch.dynamo_run",
            "in=http", "out=jax", "--model-path", model_dir, *flags,
            "--host", "127.0.0.1", "--http-port", str(self.port),
        ]
        self.proc = None
        self.start_s = None

    def __enter__(self):
        say(f"server: {' '.join(self.argv[2:])}")
        self._log = open(self.log_path, "wb")
        t0 = time.monotonic()
        self.proc = subprocess.Popen(
            self.argv, cwd=self.repo, env=self.env, stdout=self._log,
            stderr=subprocess.STDOUT,
        )
        try:
            self._wait_ready(t0 + self.start_timeout_s)
        except BaseException:
            self.__exit__(None, None, None)
            raise
        self.start_s = time.monotonic() - t0
        return self

    def _wait_ready(self, deadline: float) -> None:
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise BenchFailure(
                    f"server exited {self.proc.returncode} before "
                    f"serving:\n{self.log_tail()}")
            try:
                models = http_json(self.base + "/v1/models", timeout=5)
                if any(m["id"] == self.model_name for m in models["data"]):
                    return
            except (OSError, ValueError, BenchFailure):
                pass
            time.sleep(0.5)
        raise BenchFailure(
            f"server not ready in {self.start_timeout_s} s:\n"
            f"{self.log_tail()}")

    def log_tail(self, n: int = 4000) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - n))
            return f.read().decode(errors="replace")

    def __exit__(self, *exc) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        self._log.close()

    def metrics(self) -> dict:
        with urllib.request.urlopen(self.base + "/metrics", timeout=30) as r:
            return parse_metrics(r.read().decode())


# ---------------- the streaming client (asyncio, one thread) ----------------


def chunk_text(chunk: dict) -> str:
    out = []
    for c in chunk.get("choices", []):
        out.append((c.get("delta") or {}).get("content") or c.get("text") or "")
    return "".join(out)


class StreamResult:
    """What one streamed request gave: token arrival times (monotonic
    seconds, one entry per token), usage, and why it failed if it did."""

    __slots__ = ("sent_at", "done_at", "token_times", "usage", "error",
                 "status")

    def __init__(self):
        self.sent_at = None
        self.done_at = None
        self.token_times = []
        self.usage = None
        self.error = None
        self.status = None


async def stream_request(port: int, path: str, body: dict, request_id: str,
                         timeout_s: float = 300.0, res: StreamResult = None,
                         first: asyncio.Event = None) -> StreamResult:
    """POST ``body`` with ``stream: true`` and read the SSE answer into
    ``res`` (the caller may hold it to read what a cancelled stream had
    received). ``first`` is set at the first token. Every failure lands
    in ``res.error``; nothing is raised but cancellation (the drain
    cancels what is still streaming at its end)."""
    res = res or StreamResult()
    payload = json.dumps(body).encode()
    head = (
        f"POST {path} HTTP/1.1\r\nHost: 127.0.0.1\r\n"
        "Content-Type: application/json\r\n"
        f"X-Request-Id: {request_id}\r\n"
        f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
    ).encode()
    writer = None
    try:
        async with asyncio.timeout(timeout_s):
            reader, writer = await asyncio.open_connection("127.0.0.1", port)
            res.sent_at = time.monotonic()
            writer.write(head + payload)
            await writer.drain()
            status_line = await reader.readline()
            parts = status_line.split()
            res.status = int(parts[1]) if len(parts) > 1 else 0
            if res.status != 200:
                rest = await reader.read(2000)
                res.error = f"http {res.status}: {rest[-300:]!r}"
                return res
            event = None
            while True:
                raw = await reader.readline()
                if not raw:
                    res.error = res.error or "stream ended without [DONE]"
                    return res
                line = raw.strip()
                if line.startswith(b"event:"):
                    event = line[6:].strip()
                    continue
                if not line.startswith(b"data:"):
                    continue
                data = line[5:].strip()
                if data == b"[DONE]":
                    return res
                if event == b"error":
                    res.error = f"sse error: {data[:300]!r}"
                    event = None
                    continue
                event = None
                chunk = json.loads(data)
                if chunk.get("usage"):
                    res.usage = chunk["usage"]
                n = len(chunk_text(chunk).split())
                if n:
                    now = time.monotonic()
                    res.token_times.extend([now] * n)
                    if first is not None:
                        first.set()
    except (OSError, TimeoutError, ValueError, asyncio.IncompleteReadError) as e:
        res.error = f"{type(e).__name__}: {e}"
    finally:
        res.done_at = time.monotonic()
        if writer is not None:
            writer.close()
    return res
