"""dynamo-run equivalent CLI: ``in=<source> out=<engine>``.

Re-design of the reference's launcher (launch/dynamo-run/src/{main,lib}.rs:
``dynamo run in=http|text|stdin|batch:f|dyn://… out=echo|<engine>|dyn://…``)
for the TPU stack:

  in=http      OpenAI frontend in this process
  in=text      interactive REPL
  in=stdin     one prompt from stdin, stream to stdout
  in=batch:F   JSONL throughput harness (reports tokens in/out per sec,
               ref input/batch.rs:180-195)
  in=dyn://ns.comp.ep   serve the engine as a distributed endpoint (worker)

  out=echo     token-echo fake engine (testing, ref output/echo_core.rs)
  out=jax      the native JAX/TPU engine
  out=pystr:F  user Python engine, text level (ref engines/python.rs)
  out=pytok:F  user Python engine, token level
  out=dyn://ns.comp.ep  route to discovered remote workers (frontend mode)

Examples:

  python -m dynamo_tpu.launch.dynamo_run in=http out=jax --model-path /models/llama-3-8b
  python -m dynamo_tpu.launch.dynamo_run in=dyn://dyn.worker.generate out=jax \
      --model-path /models/llama-3-8b --hub 10.0.0.1:18500     # worker node
  python -m dynamo_tpu.launch.dynamo_run in=http out=dyn://dyn.worker.generate \
      --hub 10.0.0.1:18500                                      # frontend node
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
import time
from typing import Optional

from ..engine import EngineConfig, JaxEngine
from ..http.discovery import ModelEntry, ModelWatcher, register_model
from ..http.service import HttpService, ModelManager
from ..llm.backend import Backend
from ..llm.model_card import MdcRefresher, ModelDeploymentCard
from ..llm.openai_engine import OpenAIWorkerEngine
from ..llm.preprocessor import OpenAIPreprocessor
from ..llm.tokenizer import ByteTokenizer, load_tokenizer
from ..models.config import ModelConfig
from ..protocols.common import FinishReason, LLMEngineOutput, PreprocessedRequest
from ..protocols.openai import ChatCompletionRequest
from ..runtime import AsyncEngine, Context, DistributedRuntime, link
from ..runtime.hub import HubServer, connect_hub
from .. import tracing

logger = logging.getLogger(__name__)


async def setup_tracing(args, service: str, drt=None, component=None,
                        collector: bool = False):
    """--trace wiring for one process role. Enables the span recorder
    under the given service name; with ``collector=True`` (frontend /
    standalone collector roles) returns a TraceCollector fed by local
    spans AND — when a runtime is given — by remote workers' span batches
    on the trace-events subject(s). Worker roles instead export their
    spans onto their component's trace-events subject."""
    if not getattr(args, "trace", False):
        return None
    tracing.configure(enabled=True, service=service)
    if collector:
        tc = tracing.TraceCollector(drt, component)
        sink = tc.ingest
        if drt is not None:
            await tc.start()
            # ALSO export the frontend's own spans onto the bus: a
            # standalone collector (python -m dynamo_tpu.observability
            # --trace) needs the frontend.request/first_token anchors or
            # its decompositions never resolve. Three-token subject so
            # the *.*.trace-events wildcard matches.
            exporter = tracing.BusExporter(
                drt.bus, f"{service}.http.{tracing.TRACE_EVENTS_SUBJECT}"
            )

            def sink(rec, _ingest=tc.ingest, _export=exporter):  # noqa: F811
                _ingest(rec)
                _export(rec)

        tracing.RECORDER.configure(enabled=True, sink=sink)
        return tc
    if drt is not None and component is not None:
        exporter = tracing.BusExporter(
            drt.bus, component.event_subject(tracing.TRACE_EVENTS_SUBJECT)
        )
        tracing.RECORDER.configure(enabled=True, sink=exporter)
    return None


def _node_rank_default() -> int:
    """Node rank from env, with a StatefulSet hostname fallback.

    The manifests inject DYN_NODE_RANK from the
    ``apps.kubernetes.io/pod-index`` label, which the StatefulSet
    controller only stamps on k8s >= 1.28 (PodIndexLabel gate); on older
    clusters the downward-API env resolves EMPTY and every rank would
    silently become 0 (advisor r3). StatefulSet pod names always end in
    the ordinal (``<group>-<n>``), so the hostname carries the same rank
    on every k8s version.
    """
    raw = os.environ.get("DYN_NODE_RANK", "")
    if raw.strip():
        return int(raw)
    host = os.environ.get("HOSTNAME", "")
    tail = host.rsplit("-", 1)[-1]
    if host and tail.isdigit():
        return int(tail)
    return 0


class EchoEngine(AsyncEngine):
    """Echo prompt tokens back (ref output/echo_core.rs)."""

    async def generate(self, request: Context):
        req: PreprocessedRequest = request.data
        if isinstance(req, dict):
            req = PreprocessedRequest.from_dict(req)
        n = len(req.token_ids)
        maxt = min(req.stop_conditions.max_tokens or n, n)
        for i in range(maxt):
            final = i == maxt - 1
            yield LLMEngineOutput(
                token_ids=[req.token_ids[i]],
                finish_reason=FinishReason.LENGTH if final else None,
                prompt_tokens=n if final else None,
                completion_tokens=i + 1 if final else None,
            )
            await asyncio.sleep(0)


def build_model(args, load_weights: bool = True) -> tuple[ModelConfig, Optional[dict], object, str]:
    """(model config, params-or-None, tokenizer, model name)."""
    cfg, params, tok, name = _build_model(args, load_weights)
    if getattr(args, "tokenizer", None):
        # explicit tokenizer dir override: lets the sim presets (random
        # weights, byte tokenizer by default) serve through a REAL HF /
        # SentencePiece tokenizer so TTFT includes tokenization and ITL
        # includes detokenization (serve_bench --sim-tokenizer)
        tok = load_tokenizer(args.tokenizer)
    return cfg, params, tok, name


def _build_model(args, load_weights: bool):
    if args.model_path in (None, "tiny"):
        cfg = ModelConfig.tiny()
        return cfg, None, ByteTokenizer(), args.model_name or "tiny"
    if args.model_path == "tiny-window":
        # sliding-window (mistral-style) smoke model: exercises windowed
        # attention + windowed speculative decoding through the stack
        cfg = ModelConfig.tiny(sliding_window=16)
        return cfg, None, ByteTokenizer(), args.model_name or "tiny-window"
    if args.model_path == "tiny-moe":
        cfg = ModelConfig.tiny(
            num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32
        )
        return cfg, None, ByteTokenizer(), args.model_name or "tiny-moe"
    if args.model_path == "tiny-mla":
        # DeepSeek-V2/V3-shaped MLA test model (compressed latent cache,
        # absorbed attention, dense-first MoE stack) — config-5's model
        # family servable end to end without a checkpoint
        cfg = ModelConfig.tiny(
            num_heads=4, num_kv_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            q_lora_rank=24, num_experts=4, num_experts_per_tok=2,
            moe_intermediate_size=32, num_shared_experts=1,
            first_dense_layers=1, num_layers=3,
        )
        return cfg, None, ByteTokenizer(), args.model_name or "tiny-mla"
    if args.model_path == "tiny-gptoss":
        # gpt-oss-shaped smoke model: alternating sliding/full layers,
        # attention sinks, biased clamped-SwiGLU MoE
        cfg = ModelConfig.tiny(
            num_layers=4, layer_windows=(16, 0, 16, 0), attn_sinks=True,
            o_bias=True, attention_bias=True, num_experts=4,
            num_experts_per_tok=2, moe_intermediate_size=32,
            moe_act="gptoss_clamp",
        )
        return cfg, None, ByteTokenizer(), args.model_name or "tiny-gptoss"
    if args.model_path == "deepseek-8b-sim":
        # 8B-class dense-MLA architecture with DeepSeek-V3 head geometry
        # (kv_lora 512 + rope 64, q_lora 1536) and random weights: the
        # serving-bench shape for BASELINE config 5's model family when
        # no checkpoint is reachable — compute, latent-cache traffic and
        # scheduling identical to a real dense-MLA model; int8 weights
        # fit one v5e (16 GB HBM)
        cfg = ModelConfig(
            vocab_size=32768, hidden_size=4096, intermediate_size=14336,
            num_layers=30, num_heads=32, num_kv_heads=32,
            max_position_embeddings=8192, dtype="bfloat16",
            kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
            v_head_dim=128, q_lora_rank=1536,
        )
        return cfg, None, ByteTokenizer(), args.model_name or "deepseek-8b-sim"
    if args.model_path == "moe-8x2b-sim":
        # Mixtral-proportioned sparse MoE sized for one v5e: ~4.4B
        # total / ~1.3B active, so the bf16 init + int8 copy PEAK
        # (~13 GB) fits 16 GB HBM during quantization. The on-chip
        # serving shape that drives the grouped-dequant expert kernel
        # (ops/moe_gmm_pallas.py) through the FULL stack — routing,
        # ragged dispatch, int8 expert streams, continuous batching —
        # not just the kernel bench
        cfg = ModelConfig(
            vocab_size=32768, hidden_size=2048, intermediate_size=4096,
            num_layers=20, num_heads=16, num_kv_heads=8, head_dim=128,
            max_position_embeddings=8192, dtype="bfloat16",
            num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=4096,
        )
        return cfg, None, ByteTokenizer(), args.model_name or "moe-8x2b-sim"
    if args.model_path == "llama3-8b-sim":
        # full Llama-3-8B architecture with RANDOM weights + the byte
        # tokenizer: the serving-path TTFT/ITL bench shape for when no
        # real checkpoint is reachable (zero-egress environments) —
        # compute, memory traffic and scheduling are identical to the
        # real model; only the token->text map differs
        cfg = ModelConfig.llama3_8b()
        return cfg, None, ByteTokenizer(), args.model_name or "llama3-8b-sim"
    from ..llm.hub import resolve_model_path

    # the served name comes from the user-facing id (org/name or dir), not
    # the hex snapshot path a cache hit resolves to
    name = args.model_name or os.path.basename(os.path.normpath(args.model_path))
    # local dir, HF-cache snapshot, or hub download (ref hub.rs from_hf)
    args.model_path = resolve_model_path(args.model_path)
    cfg = ModelConfig.from_local_path(args.model_path)
    # tokenizer.json -> HF fast path; tokenizer.model -> SentencePiece
    tokenizer = load_tokenizer(args.model_path)
    params = None
    has_weights = load_weights and any(
        f.endswith(".safetensors") for f in os.listdir(args.model_path)
    )
    if has_weights:
        from ..models.weights import load_llama_params

        from ..parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(tp=args.tp)) if args.tp > 1 else None
        params = load_llama_params(args.model_path, cfg, mesh=mesh)
    return cfg, params, tokenizer, name


def mesh_config(args):
    """MeshConfig from the parallelism flags, or None when trivial."""
    from ..parallel.mesh import MeshConfig

    mc = MeshConfig(dp=args.dp, pp=args.pp, ep=args.ep, tp=args.tp)
    return mc if mc.num_devices > 1 else None


def _adapter_specs(args) -> tuple:
    """``--adapters`` comma list -> spec-string tuple (engine/adapters.py
    parses the ``name:rank[:seed]`` / ``name=/path.npz`` forms)."""
    raw = getattr(args, "adapters", None) or ""
    return tuple(s.strip() for s in raw.split(",") if s.strip())


def _adapter_names(args) -> list[str]:
    """Adapter model names a worker/frontend must answer to (validated
    through the same parser the engine's registry uses, so a bad spec
    dies at launch, not at first request)."""
    specs = _adapter_specs(args)
    if not specs:
        return []
    from ..engine.adapters import parse_adapter_specs

    try:
        return [s.name for s in parse_adapter_specs(specs)]
    except ValueError as e:
        raise SystemExit(f"bad --adapters: {e}") from None


def engine_config(args, cfg: ModelConfig, served_name: str = "") -> EngineConfig:
    adapters = _adapter_specs(args)
    return EngineConfig(
        model=cfg,
        num_blocks=args.num_blocks,
        block_size=args.block_size,
        max_batch_size=args.max_batch,
        max_context=args.max_context or 0,
        mesh=mesh_config(args),
        host_cache_blocks=args.host_cache_blocks,
        disk_cache_blocks=args.disk_blocks,
        disk_cache_path=args.disk_path,
        kv_tier_ttl_s=args.kv_tier_ttl_s,
        quantization=args.quantization,
        kv_cache_dtype=args.kv_cache_dtype,
        kv_quant=getattr(args, "kv_quant", "none"),
        decode_window=args.decode_window,
        decode_pipeline=args.decode_pipeline,
        spec_gamma=args.spec_gamma,
        spec_ngram=args.spec_ngram,
        state_snapshots=getattr(args, "state_snapshots", 0),
        window_blocks=getattr(args, "window_blocks", 0),
        mixed_batch=not args.no_mixed_batch,
        mixed_step_budget=args.mixed_step_budget,
        mixed_max_prefills=args.mixed_max_prefills,
        kv_cost_model=getattr(args, "kv_cost_model", True),
        adapters=adapters,
        # the base model keeps its legacy "" wildcard unless adapters
        # are in play — a single-model worker's load_metrics / request
        # resolution stay byte-identical to pre-multi-model fleets
        served_model_name=served_name if adapters else "",
        max_live_adapters=getattr(args, "max_live_adapters", 0),
    )


def build_core_engine(args, cfg: ModelConfig, params, mirror=None,
                      served_name: str = "") -> AsyncEngine:
    if args.out == "echo":
        return EchoEngine()
    if args.out.startswith(("pystr:", "pytok:")):
        # user-supplied Python engine (ref engines/python.rs);
        # --engine-subprocess isolates it in a child process
        from ..engine.python_engine import build_python_engine

        engine, text_mode = build_python_engine(
            args.out, subprocess_mode=args.engine_subprocess
        )
        engine.text_mode = text_mode
        return engine
    if args.out == "jax":
        return JaxEngine(engine_config(args, cfg, served_name=served_name),
                         params=params, mirror=mirror)
    raise SystemExit(f"unknown out= engine {args.out!r}")


async def maybe_warmup(args, core, decode: bool = True) -> None:
    """--warmup: compile the serving paths before any endpoint/port
    exists, so discovery can never route a request into a cold-bucket
    XLA compile. ``decode=False`` (prefill-only disagg workers) skips
    the decode-window ladder those roles never dispatch."""
    if args.warmup and isinstance(core, JaxEngine):
        t0 = time.monotonic()
        sizes = await core.warmup(decode=decode)
        what = "+ decode window ladder " if decode else "(prefill only) "
        print(f"warmup: compiled prefill buckets {sizes} {what}"
              f"in {time.monotonic() - t0:.1f}s", flush=True)


async def connect_runtime(args) -> DistributedRuntime:
    if args.hub:
        store, bus, _conn = await connect_hub(args.hub)
        return await DistributedRuntime.from_settings(store=store, bus=bus)
    return await DistributedRuntime.from_settings()


# ---------------- in= modes ----------------


def _build_flight(args, collector=None, core=None):
    """SLO observatory flight recorder for a frontend role
    (observability/flight.py). Always-on by default: the ring is
    bounded and a record is a dict append, so the cost is noise. The
    autopsy providers (engine stats / sanitizer counters / XLA compile
    ledger) wire only when the engine runs in-process — a distributed
    frontend's autopsies carry the timeline + decomposition it can
    see."""
    if args.no_flight_recorder:
        return None
    from ..analysis import sanitizer
    from ..observability import FlightRecorder, SloPolicy

    per_class: dict[str, float] = {}
    default_ms = 0.0
    for part in (args.autopsy_ttft_ms or "").split(","):
        part = part.strip()
        if not part:
            continue
        cls, sep, ms = part.partition("=")
        try:
            if sep:
                per_class[cls.strip()] = float(ms)
            else:
                default_ms = float(part)
        except ValueError:
            raise SystemExit(
                f"bad --autopsy-ttft-ms entry {part!r} "
                "(want MS or class=MS[,class=MS...])"
            ) from None
    kw = {}
    if core is not None:
        kw = dict(
            stats_provider=core.load_metrics,
            sanitizer_provider=sanitizer.counters,
            ledger_provider=lambda: core.compile_ledger,
        )
    return FlightRecorder(
        SloPolicy(ttft_ms=per_class, default_ttft_ms=default_ms),
        collector=collector,
        autopsy_dir=args.autopsy_dir,
        **kw,
    )


def _build_admission(args):
    """--admission-rate > 0 -> the frontend overload gate (planner/
    admission.py): token-bucket shedding with SLO classes, so admitted
    requests keep their latency target when offered load exceeds
    capacity. 0 (default) = admit everything (legacy behavior)."""
    if args.admission_rate <= 0:
        return None
    from ..planner import AdmissionGate

    model_classes: dict = {}
    for part in (getattr(args, "model_slo", None) or "").split(","):
        part = part.strip()
        if not part:
            continue
        m, sep, c = part.partition("=")
        if not sep or not m.strip() or not c.strip():
            raise SystemExit(
                f"bad --model-slo entry {part!r} (want model=class)"
            )
        model_classes[m.strip()] = c.strip()
    return AdmissionGate(
        args.admission_rate,
        burst=args.admission_burst if args.admission_burst > 0 else None,
        model_classes=model_classes or None,
    )


async def run_http(args) -> None:
    manager = ModelManager()
    admission = _build_admission(args)
    svc = HttpService(manager, host=args.host, port=args.http_port,
                      admission=admission)
    if args.out.startswith("dyn://") and args.router == "kv":
        # KV-aware frontend: tokenize locally (for prefix hashing), route
        # each request to the worker with the best cache overlap
        from ..kv_router import KvRouter
        from ..kv_router.router import KvRoutedEngine

        ns, comp_name, ep = args.out.removeprefix("dyn://").split(".")
        drt = await connect_runtime(args)
        cfg, _params, tokenizer, name = build_model(args, load_weights=False)
        comp = drt.namespace(ns).component(comp_name)
        client = await comp.endpoint(ep).client().start()
        # model_name rides the prefetch hints (PRESERVE weight
        # pre-stage); scheduler config default = cost-aware routing
        # with overlap-scoring cold-start fallback; tail-aware routing
        # folds each worker's windowed p99 queue-wait+prefill into the
        # cost model's prediction (docs/autopilot.md)
        from ..kv_router.scheduler import SchedulerConfig

        router = await KvRouter(
            drt, comp, block_size=args.block_size, model_name=name,
            config=SchedulerConfig(
                tail_aware=not args.no_tail_aware,
                tail_window_s=args.tail_window_s,
            ),
        ).start()
        dispatch = KvRoutedEngine(router, client)
        if not args.no_migration:
            # transparent in-flight migration (resilience/): worker death
            # mid-stream re-dispatches prompt + tokens-so-far through the
            # same KV router — the client stream never notices
            from ..resilience import MigratingEngine, MigrationPolicy

            dispatch = MigratingEngine(
                dispatch,
                MigrationPolicy(
                    max_migrations=args.max_migrations,
                    deadline_s=args.migration_deadline,
                ),
                client=client,
            )
            svc.metrics.register_source(
                lambda s=dispatch.stats: dict(s)
            )
        engine = link(
            OpenAIPreprocessor(tokenizer),
            Backend(tokenizer),
            dispatch,
        )
        manager.add_chat_model(name, engine)
        manager.add_completion_model(name, engine)
        # adapter names route through the same KV-routed pipeline — the
        # request's model rides PreprocessedRequest.model into the
        # router (hash salting + worker filtering) and the worker
        for aname in _adapter_names(args):
            manager.add_chat_model(aname, engine)
            manager.add_completion_model(aname, engine)
        # wildcard, not pinned to `comp`: disagg prefill workers export
        # on their own {ns}.prefill.trace-events subject and their spans
        # must land in the same timelines as the decode workers'
        svc.tracing = await setup_tracing(
            args, "frontend", drt=drt, collector=True
        )
        flight = _build_flight(args, collector=svc.tracing)
        if flight is not None:
            svc.attach_flight(flight)
        if args.autopilot:
            # fleet autopilot (docs/autopilot.md): the closed loops ride
            # the frontend because the evidence lives here — the flight
            # recorder's per-worker breach attribution, the admission
            # gate's class counters, and the router's scrape view
            from ..autopilot import Autopilot, AutopilotConfig
            from ..planner import TelemetryAggregator

            autopilot = await Autopilot(
                drt, comp,
                telemetry=TelemetryAggregator(
                    metrics_aggregator=router.metrics
                ),
                recorder=flight,
                gate=admission,
                config=AutopilotConfig(
                    interval_s=args.autopilot_tick,
                    prewarm=not args.no_prewarm,
                    quarantine=not args.no_quarantine and flight is not None,
                    headroom=args.autopilot_headroom
                    and admission is not None,
                ),
            ).start()
            svc.metrics.register_source(autopilot.render_stats)
            print(
                "autopilot engaged: prewarm="
                f"{autopilot.cfg.prewarm} quarantine="
                f"{autopilot.cfg.quarantine} headroom="
                f"{autopilot.cfg.headroom} every "
                f"{autopilot.cfg.interval_s}s", flush=True,
            )
    elif args.out.startswith("dyn://"):
        drt = await connect_runtime(args)
        await ModelWatcher(drt, manager).start()
        # no single component to pin to: the collector subscribes the
        # trace-events wildcard and assembles whatever workers export
        svc.tracing = await setup_tracing(
            args, "frontend", drt=drt, collector=True
        )
        flight = _build_flight(args, collector=svc.tracing)
        if flight is not None:
            svc.attach_flight(flight)
    else:
        cfg, params, tokenizer, name = build_model(args)
        core = build_core_engine(args, cfg, params, served_name=name)
        await maybe_warmup(args, core)
        engine = OpenAIWorkerEngine(tokenizer, core)
        manager.add_chat_model(name, engine)
        manager.add_completion_model(name, engine)
        # every adapter is a first-class model name: /v1/models lists
        # it, requests resolve through the same engine (which maps the
        # name to its adapter slot), unknown names keep the clean 404
        for aname in _adapter_names(args):
            manager.add_chat_model(aname, engine)
            manager.add_completion_model(aname, engine)
        # single process: local spans feed the collector directly
        svc.tracing = await setup_tracing(args, "frontend", collector=True)
        flight = _build_flight(
            args, collector=svc.tracing,
            core=core if isinstance(core, JaxEngine) else None,
        )
        if flight is not None:
            svc.attach_flight(flight)
        if isinstance(core, JaxEngine):
            # in-process engine: POST /profile drives jax.profiler on
            # the serving devices (autopsies already carry its stats /
            # sanitizer / compile-ledger snapshots via _build_flight)
            svc.profiler = core.profile
            # ...and /metrics says which device path serves: attention
            # path + reason, compiled program buckets, per-device memory
            svc.metrics.register_source(core.device_path_stats)
    if admission is not None and args.out.startswith("dyn://"):
        # planner capacity watermarks continuously retune the gate's
        # admission rate to the fleet's corrected serving capacity
        # (static --admission-rate until the first watermark arrives)
        from ..planner.admission import start_watermark_follower

        ns, comp_name, _ep = args.out.removeprefix("dyn://").split(".")
        await start_watermark_follower(
            drt, drt.namespace(ns).component(comp_name), admission
        )
    await svc.start()
    print(f"OpenAI server on http://{args.host}:{svc.port} "
          f"(models: {manager.model_names() or 'discovered dynamically'})", flush=True)
    await svc.run()


async def run_endpoint(args) -> None:
    """Worker mode: serve the engine at dyn://ns.comp.ep (ref input/endpoint.rs).

    Multi-node (``--num-nodes N --node-rank R --coordinator host:port``,
    ref flags.rs:59-92 + MultiNodeConfig engines.rs:35-52): every rank
    joins the JAX multi-controller runtime; rank 0 becomes the leader
    (scheduler + hub endpoint + lease) with a StepMirror over the global
    mesh, ranks 1.. run the follower loop (pure SPMD compute, no control
    plane)."""
    from ..parallel import multihost

    target = args.in_.removeprefix("dyn://")
    ns, comp, ep = target.split(".")
    mh = multihost.MultiHostConfig(
        num_nodes=args.num_nodes, node_rank=args.node_rank,
        coordinator=args.coordinator,
    )
    mirror = None
    if mh.enabled:
        assert args.out == "jax", "--num-nodes > 1 requires out=jax"
        # --disagg and --host-cache-blocks compose with multi-host: KV
        # gather/scatter and offload flush/restore are mirrored ops (the
        # leader broadcasts, every rank moves its own cache shards) —
        # BASELINE configs 4-5 (tests/mh_compose_worker.py)
        multihost.initialize(mh)
        mcfg_mesh = mesh_config(args)
        assert mcfg_mesh is not None, (
            "--num-nodes > 1 needs explicit mesh axes (--dp/--pp/--ep/--tp) "
            "whose product equals the global device count"
        )
        if not mh.is_leader:
            cfg, params, _tokenizer, _name = build_model(args)
            multihost.run_follower(engine_config(args, cfg), params=params)
            return
    # build the engine (slow: weight loading, jit warmup) BEFORE taking a
    # lease, so control-plane keepalives aren't starved during init
    cfg, params, tokenizer, name = build_model(args)
    if mh.enabled:
        mirror = multihost.StepMirror(multihost.global_mesh(mcfg_mesh), cfg)
    core = build_core_engine(args, cfg, params, mirror=mirror,
                             served_name=name)
    jax_core = core if isinstance(core, JaxEngine) else None
    await maybe_warmup(args, core)
    drt = await connect_runtime(args)
    transfer_server = None
    if args.disagg == "decode":
        # conditional disaggregation: long uncached prompts offload to
        # prefill workers via the queue + KV transfer plane (disagg/)
        from ..disagg import (
            ConditionalDisaggRouter, DisaggConfig, DisaggEngine,
            KvTransferServer, PrefillQueue,
        )

        assert jax_core is not None, "--disagg decode requires out=jax"
        transfer = KvTransferServer(
            host=args.host, advertise_host=args.advertise_host
        )
        await transfer.start()
        transfer_server = transfer  # shared with the peer-pull listener
        disagg_router = ConditionalDisaggRouter(
            drt, ns, name,
            DisaggConfig(max_local_prefill_length=args.max_local_prefill),
        )
        await disagg_router.start()
        # queue is named by the endpoint's namespace — prefill workers must
        # run with --namespace <same> (run_prefill prints the queue name)
        queue = PrefillQueue(drt.bus, ns)
        disagg_engine = DisaggEngine(
            jax_core, disagg_router, queue, transfer,
            engine_id=drt.primary_lease_id,
            kv_stream=args.kv_stream,
            kv_ici=args.kv_ici,
        )
        engine = OpenAIWorkerEngine(tokenizer, disagg_engine)
        stats = lambda: (  # noqa: E731
            jax_core.load_metrics() | jax_core.stats | disagg_engine.stats
        )
    else:
        engine = OpenAIWorkerEngine(tokenizer, core)
        stats = (
            (lambda: jax_core.load_metrics() | jax_core.stats)
            if jax_core else (lambda: {})
        )
    component = drt.namespace(ns).component(comp)
    await setup_tracing(
        args, f"worker-{drt.primary_lease_id:x}", drt=drt, component=component
    )
    if jax_core is not None:
        from ..kv_router import (
            KvEventPublisher, KvPeerServer, KvPrefetchListener,
        )

        # with an offload tier, demotions keep their radix residency and
        # last-tier drops publish the real removals (fleet prefix cache)
        KvEventPublisher(drt, component, drt.primary_lease_id).attach(
            jax_core.kv.allocator, offload=jax_core.offload
        )
        if jax_core.offload is not None:
            # router-hinted host-tier prefetch: the KV router ships the
            # routed prompt's block-hash chain here the moment it picks
            # this worker; the engine starts the h2d restore before the
            # request itself arrives (engine.prefetch_hint), pulling the
            # continuation from the hinted PEER's tiers first when local
            # tiers fall short. The disagg decode role shares its
            # transfer server for the connect-back; other roles get a
            # lightweight one inside the listener. The handles are kept
            # so the subscriptions/tasks stay referenced for the
            # worker's lifetime (and closeable by embedders).
            prefetch_listener = await KvPrefetchListener(  # noqa: F841
                drt, component, drt.primary_lease_id, jax_core,
                transfer=transfer_server,
            ).start()
            # ...and the serve side: answer peers' kv-peer-fetch
            # requests from this worker's host/disk tiers
            peer_server = await KvPeerServer(  # noqa: F841
                drt, component, drt.primary_lease_id, jax_core
            ).start()
        # elastic live resharding: actuate planner MorphDecisions from
        # the ``reshard`` subject (quiesce/morph/resume — multi-host
        # mirrors fall back to drain-with-handoff inside the listener)
        from ..resilience import ReshardListener

        reshard_listener = await ReshardListener(  # noqa: F841
            drt, component, drt.primary_lease_id, jax_core,
            drain_deadline_s=args.drain_deadline,
        ).start()
        # autopilot actuators (docs/autopilot.md): pre-warm directives
        # run the engine's warmup ladder off the hot path before the
        # router shifts traffic here; health directives mirror this
        # worker's own quarantine state into its scrape surface so
        # operators see WHICH worker the autopilot fenced
        from ..autopilot import WarmupListener
        from ..resilience.quarantine import QuarantineListener

        warmup_listener = await WarmupListener(  # noqa: F841
            drt, component, drt.primary_lease_id, jax_core,
        ).start()
        quarantine_listener = await QuarantineListener(  # noqa: F841
            drt, component, drt.primary_lease_id, jax_core,
        ).start()
    handle = await component.endpoint(ep).serve(engine, stats_handler=stats)
    await register_model(
        drt, ModelEntry(name=name, namespace=ns, component=comp, endpoint=ep,
                        model_type="both"),
    )
    # each adapter registers as its own discoverable model at the SAME
    # endpoint: discovery frontends list it and route its requests here,
    # where the engine resolves the name to its adapter slot
    for aname in _adapter_names(args):
        await register_model(
            drt, ModelEntry(name=aname, namespace=ns, component=comp,
                            endpoint=ep, model_type="both"),
        )
    card = ModelDeploymentCard(
        display_name=name, service_name=name, model_path=args.model_path or "",
        context_length=cfg.max_position_embeddings, kv_block_size=args.block_size,
    )
    await card.publish(drt.bus)
    refresher = MdcRefresher(drt.bus, card)
    refresher.start()
    print(f"worker {drt.worker_id:x} serving {name!r} at dyn://{target}", flush=True)
    # SIGTERM = graceful drain (resilience/drain.py): vanish from
    # discovery, finish or hand off in-flight streams within
    # --drain-deadline, revoke the lease last, then exit
    from ..resilience import DrainCoordinator

    done = asyncio.Event()
    drain = DrainCoordinator(
        drt,
        engines=[jax_core] if jax_core is not None else [],
        handles=[handle],
        deadline_s=args.drain_deadline,
        on_done=done.set,
    )
    drain.install_signal_handlers()
    await done.wait()


async def run_prefill(args) -> None:
    """Prefill-worker mode (`in=prefill`): consume the namespace's prefill
    queue, compute KV + first token, push to the requesting decode worker
    (ref examples/llm/components/prefill_worker.py).

    Composes with --num-nodes: rank 0 leads (queue consumer + mirrored
    prefill/gather dispatch), other ranks replay — the KV extract's
    all-gather is a mirrored op (BASELINE config 5's multi-host MoE
    prefill workers)."""
    from ..disagg import PrefillQueue, PrefillWorker
    from ..parallel import multihost

    ns = args.namespace
    mh = multihost.MultiHostConfig(
        num_nodes=args.num_nodes, node_rank=args.node_rank,
        coordinator=args.coordinator,
    )
    mirror = None
    if mh.enabled:
        multihost.initialize(mh)
        mcfg_mesh = mesh_config(args)
        assert mcfg_mesh is not None, (
            "--num-nodes > 1 needs explicit mesh axes (--dp/--pp/--ep/--tp)"
        )
        if not mh.is_leader:
            cfg, params, _tokenizer, _name = build_model(args)
            multihost.run_follower(engine_config(args, cfg), params=params)
            return
    cfg, params, _tokenizer, name = build_model(args)
    if mh.enabled:
        mirror = multihost.StepMirror(multihost.global_mesh(mcfg_mesh), cfg)
    core = build_core_engine(args, cfg, params, mirror=mirror)
    assert isinstance(core, JaxEngine), "in=prefill requires out=jax"
    await maybe_warmup(args, core, decode=False)
    drt = await connect_runtime(args)
    await setup_tracing(
        args, f"prefill-{drt.primary_lease_id:x}", drt=drt,
        component=drt.namespace(ns).component("prefill"),
    )
    queue = PrefillQueue(drt.bus, ns)
    worker = PrefillWorker(
        core, queue, kv_stream=args.kv_stream,
        segment_blocks=args.kv_segment_blocks,
        concurrency=args.prefill_concurrency,
        kv_ici=args.kv_ici,
    )
    worker.start()
    print(f"prefill worker {drt.worker_id:x} serving {name!r} "
          f"on queue {queue.name}", flush=True)
    # SIGTERM: stop consuming the queue (the in-flight item finishes or
    # redelivers to a surviving prefill worker), revoke the lease last
    from ..resilience import DrainCoordinator

    done = asyncio.Event()
    drain = DrainCoordinator(
        drt, closers=[worker.close], deadline_s=args.drain_deadline,
        on_done=done.set,
    )
    drain.install_signal_handlers()
    await done.wait()


async def _one_shot(engine: AsyncEngine, model: str, prompt: str, max_tokens: int, emit):
    req = ChatCompletionRequest.from_dict(
        {
            "model": model,
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens,
            "stream": True,
        }
    )
    n_out = 0
    async for item in engine.generate(Context(req)):
        data = getattr(item, "data", None)
        if data and data.get("choices"):
            delta = data["choices"][0].get("delta", {})
            if delta.get("content"):
                emit(delta["content"])
                n_out += 1
    return n_out


async def run_text(args) -> None:
    cfg, params, tokenizer, name = build_model(args)
    core = build_core_engine(args, cfg, params)
    await maybe_warmup(args, core)
    engine = OpenAIWorkerEngine(tokenizer, core)
    print(f"interactive mode — model {name!r}; ctrl-d to exit", flush=True)
    loop = asyncio.get_running_loop()
    while True:
        try:
            prompt = await loop.run_in_executor(None, lambda: input("> "))
        except EOFError:
            return
        await _one_shot(engine, name, prompt, args.max_tokens,
                        lambda s: print(s, end="", flush=True))
        print(flush=True)


async def run_stdin(args) -> None:
    cfg, params, tokenizer, name = build_model(args)
    core = build_core_engine(args, cfg, params)
    await maybe_warmup(args, core)
    engine = OpenAIWorkerEngine(tokenizer, core)
    prompt = sys.stdin.read().strip()
    await _one_shot(engine, name, prompt, args.max_tokens,
                    lambda s: print(s, end="", flush=True))
    print(flush=True)


async def run_batch(args, batch_file: str) -> None:
    """Throughput harness (ref input/batch.rs): JSONL with {"text": ...}."""
    cfg, params, tokenizer, name = build_model(args)
    core = build_core_engine(args, cfg, params)
    await maybe_warmup(args, core)  # keep compiles out of the throughput numbers
    pipeline = core if getattr(core, "text_mode", False) else link(Backend(tokenizer), core)

    entries = []
    # dynlint: disable=blocking-disk-io -- one-shot harness setup before any request exists
    with open(batch_file) as f:
        for line in f:
            line = line.strip()
            if line:
                entries.append(json.loads(line))

    results = []
    t0 = time.monotonic()

    async def run_one(entry):
        from ..protocols.common import SamplingOptions, StopConditions

        token_ids = tokenizer.encode(entry["text"], add_special_tokens=True)
        req = PreprocessedRequest(
            token_ids=token_ids,
            stop_conditions=StopConditions(
                max_tokens=entry.get("max_tokens", args.max_tokens), ignore_eos=True
            ),
            sampling_options=SamplingOptions(temperature=0.0),
            model=name,
            # text-level (pystr) engines read the prompt from here
            annotations={"formatted_prompt": entry["text"]},
        )
        t_start = time.monotonic()
        tokens_out = 0
        tokens_in = len(token_ids)
        async for item in pipeline.generate(Context(req)):
            out = getattr(item, "data", None)
            if out is None:
                continue
            # text engines emit deltas without token ids — count each as one
            tokens_out += len(out.token_ids) or (1 if out.text else 0)
        results.append(
            {"tokens_in": tokens_in, "tokens_out": tokens_out,
             "elapsed_ms": (time.monotonic() - t_start) * 1e3}
        )

    concurrency = args.concurrency
    pending = set()
    for entry in entries:
        if len(pending) >= concurrency:
            _done, pending = await asyncio.wait(
                pending, return_when=asyncio.FIRST_COMPLETED
            )
        pending.add(asyncio.get_running_loop().create_task(run_one(entry)))
    if pending:
        await asyncio.wait(pending)

    elapsed = time.monotonic() - t0
    tin = sum(r["tokens_in"] for r in results)
    tout = sum(r["tokens_out"] for r in results)
    print(json.dumps({
        "requests": len(results),
        "elapsed_s": round(elapsed, 3),
        "tokens_in": tin,
        "tokens_out": tout,
        "tokens_in_per_s": round(tin / elapsed, 2),
        "tokens_out_per_s": round(tout / elapsed, 2),
    }), flush=True)


async def run_planner(args) -> None:
    """Standalone SLA planner (``--planner`` / ``in=planner``): the
    control loop that watches the fleet's load/latency telemetry and
    resizes the prefill/decode pools against the TTFT/ITL SLOs
    (docs/planner.md).

    Observes via the same metrics scrape the KV router uses (plus the
    tracing plane's TTFT decomposition when --trace is on), decides
    through the roofline-seeded capacity model + Holt forecaster +
    ScaleGuard rails, and actuates by rewriting replica counts in the
    deploy controller's store (--deploy-root/--deployment; scale-down
    rides the controller's SIGTERM -> graceful drain). Decisions and
    capacity watermarks are published on the worker component's
    ``planner-decisions``/``planner-watermarks`` subjects for the KV
    scheduler, frontends, and the metrics component. Without a deploy
    store target the planner is observe-and-publish only."""
    from ..kv_router.publisher import KvMetricsAggregator
    from ..perf import roofline
    from ..planner import (
        BusPublisher, CapacityModel, GuardConfig, Planner, PlannerConfig,
        SloTargets, StoreScaleDriver, TelemetryAggregator,
    )

    target = (
        args.out if args.out.startswith("dyn://")
        else f"dyn://{args.namespace}.worker.generate"
    )
    ns, comp_name, _ep = target.removeprefix("dyn://").split(".")
    drt = await connect_runtime(args)
    comp = drt.namespace(ns).component(comp_name)
    collector = await setup_tracing(args, "planner", drt=drt, collector=True)
    aggregator = await KvMetricsAggregator(drt, comp).start()
    telemetry = TelemetryAggregator(
        metrics_aggregator=aggregator, trace_collector=collector
    )
    # lost-host evidence, event-driven: a worker's discovery lease
    # expiring halves the missed-scrape debounce for relayout_lost_host
    # (drained departures + still-scraping workers are filtered inside
    # the aggregator — a lease flap alone never re-lays a live pool)
    from ..planner.telemetry import start_lease_watch

    await start_lease_watch(drt, comp, telemetry)
    if args.planner_capacity:
        parts = [float(x) for x in args.planner_capacity.split(",")]
        capacity = CapacityModel(parts[0], parts[1] if len(parts) > 1 else parts[0])
    else:
        sc = next(
            (s for s in roofline.DEFAULT_SCENARIOS
             if s.name == args.planner_scenario), None,
        )
        if sc is None:
            names = ", ".join(s.name for s in roofline.DEFAULT_SCENARIOS)
            raise SystemExit(
                f"unknown --planner-scenario {args.planner_scenario!r} "
                f"(have: {names})"
            )
        capacity = CapacityModel.from_roofline(sc)
    driver = None
    if args.deploy_root and args.deployment:
        from ..deploy.api_server import DeploymentStore

        driver = StoreScaleDriver(
            DeploymentStore(args.deploy_root), args.deployment
        )
    morph = None
    if args.planner_morph:
        from ..planner import MorphConfig

        # elastic live resharding: publish guarded MorphDecisions on
        # the ``reshard`` subject (workers' ReshardListeners actuate)
        morph = MorphConfig(
            tp_min=1, tp_max=args.morph_tp_max,
            grow_prompt_tokens=args.morph_grow_prompt_tokens,
        )
    cfg = PlannerConfig(
        tick_s=args.planner_tick,
        slo=SloTargets(
            ttft_p99_ms=args.slo_ttft_ms, itl_p99_ms=args.slo_itl_ms
        ),
        decode_guard=GuardConfig(
            min_replicas=args.planner_min_replicas,
            max_replicas=args.planner_max_replicas,
        ),
        prefill_guard=GuardConfig(
            min_replicas=0, max_replicas=args.planner_max_replicas
        ),
        prefill_pool=args.planner_pools == "disagg",
        morph=morph,
    )
    planner = Planner(
        telemetry, capacity, cfg,
        scale_driver=driver, publisher=BusPublisher(drt, comp),
    )
    print(
        f"planner watching {target} every {cfg.tick_s}s "
        f"(SLO ttft p99 <= {cfg.slo.ttft_p99_ms:.0f}ms, "
        f"itl p99 <= {cfg.slo.itl_p99_ms:.0f}ms; "
        f"actuator: {'deploy store' if driver else 'publish-only'})",
        flush=True,
    )
    planner.start()
    await asyncio.Event().wait()


async def run_hub(args) -> None:
    hub = HubServer(host=args.host, port=args.hub_port, data_dir=args.data_dir)
    await hub.start()
    print(f"hub listening on {hub.address}", flush=True)
    await asyncio.Event().wait()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        "dynamo_run", description="TPU-native dynamo run: in=<source> out=<engine>"
    )
    p.add_argument("in_out", nargs="*", help="in=... out=... pairs")
    p.add_argument("--model-path", default=None, help="HF model dir or 'tiny'")
    p.add_argument("--model-name", default=None)
    p.add_argument("--hub", default=None, help="hub address host:port")
    p.add_argument("--hub-port", type=int, default=18500)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--max-tokens", type=int, default=128)
    p.add_argument("--concurrency", type=int, default=8)
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel size")
    p.add_argument("--dp", type=int, default=1, help="data-parallel mesh axis")
    p.add_argument("--pp", type=int, default=1, help="pipeline mesh axis")
    p.add_argument("--ep", type=int, default=1, help="expert-parallel mesh axis")
    # multi-node bootstrap (ref MultiNodeConfig engines.rs:35-52 +
    # --num-nodes/--node-rank/--leader-addr flags.rs:59-92). Flag
    # defaults come from the DYN_* env the deployment controller injects
    # per rank (deploy/controller.py) so one command line serves every
    # rank of a multi-host service.
    p.add_argument("--num-nodes", type=int,
                   default=int(os.environ.get("DYN_NUM_NODES", "1")),
                   help="total processes in the multi-host mesh")
    p.add_argument("--node-rank", type=int,
                   default=_node_rank_default(),
                   help="this process's rank (0 = leader)")
    p.add_argument("--coordinator",
                   default=os.environ.get("DYN_COORDINATOR"),
                   help="host:port of rank 0's jax.distributed coordinator")
    p.add_argument("--router", default="round_robin",
                   choices=["round_robin", "random", "kv"])
    p.add_argument("--num-blocks", type=int, default=512)
    p.add_argument("--host-cache-blocks", type=int, default=0,
                   help="host-DRAM KV offload tier capacity (blocks; 0=off)")
    p.add_argument("--disk-blocks", type=int, default=0,
                   help="disk/SSD third KV tier capacity (blocks; 0=off; "
                        "requires --host-cache-blocks — host LRU overflow "
                        "demotes here, restores promote back through host "
                        "DRAM; docs/kv_offload.md)")
    p.add_argument("--disk-path", default=None,
                   help="disk-tier directory (default: a fresh tempdir; "
                        "point a restarted worker at the same path to "
                        "keep its disk tier)")
    p.add_argument("--kv-tier-ttl-s", type=float, default=0.0,
                   help="disk-tier entry TTL in seconds (0 = LRU only): "
                        "stale fleet prefixes age out instead of "
                        "squatting disk capacity")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--data-dir", default=None,
                   help="hub durability dir (in=hub role): the store "
                        "snapshots+WALs its KV/leases and work queues WAL "
                        "here — a restarted hub keeps discovery state and "
                        "queued work, and connected workers/frontends "
                        "resume their sessions without restarting")
    p.add_argument("--tokenizer", default=None,
                   help="tokenizer dir override (tokenizer.json or "
                        "tokenizer.model) — e.g. a real tokenizer for "
                        "the *-sim model presets")
    p.add_argument("--quantization", default="none",
                   choices=["none", "int8", "fp8_e4m3", "int8_native"],
                   help="weight quantization (per-channel; models/quant.py; "
                        "int8_native feeds int8 operands into the fused "
                        "step's GEMMs with f32 accumulation)")
    p.add_argument("--kv-cache-dtype", default="model",
                   choices=["model", "float8_e4m3", "bfloat16", "int8"],
                   help="KV cache storage dtype (float8 = scale-free cast; "
                        "int8 = int8-with-scales device cache, per-(layer, "
                        "page) f32 scale planes — docs/kv_offload.md; "
                        "quantized caches keep the Pallas ragged kernels — "
                        "the dequant fuses into their KV page loads)")
    p.add_argument("--kv-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="per-block KV quantization for the offload tiers "
                        "and the transfer wire (engine/kvquant.py): blocks "
                        "entering host DRAM / disk / peer pulls / disagg "
                        "handoffs ship int8|fp8 + per-layer scales and "
                        "dequantize on the device-side scatter — ~2x tier "
                        "and wire capacity at a measured logprob drift "
                        "(opt in per model; legacy peers transparently "
                        "receive full-width bytes)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--decode-window", type=int, default=4,
                   help="fused decode steps per device dispatch")
    p.add_argument("--decode-pipeline", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="chained decode (the default): enqueue the next "
                        "decode window or mixed step before fetching the "
                        "one before; --no-decode-pipeline is the "
                        "unchained loop, the ablation")
    p.add_argument("--no-mixed-batch", action="store_true",
                   help="disable fused mixed prefill+decode steps (fall "
                        "back to the alternating chunk/window scheduler)")
    p.add_argument("--mixed-step-budget", type=int, default=0,
                   help="prefill tokens per fused mixed step "
                        "(0 = prefill_chunk)")
    p.add_argument("--mixed-max-prefills", type=int, default=4,
                   help="max concurrent prompts packed into one fused "
                        "mixed step (the budget splits across them; "
                        "1 = one prefill at a time)")
    p.add_argument("--state-snapshots", type=int, default=0,
                   help="rows of the state's snapshot pool for a model "
                        "whose per-sequence state is too large for a row a "
                        "KV block (GigaChat 3.5's linear-attention layers: "
                        "16.4 MiB a row); 0 = 64")
    p.add_argument("--window-blocks", type=int, default=0,
                   help="blocks of the window pool of a model with window "
                        "AND full attention layers (Mellum 2: the window "
                        "layers' KV, held by window and not by history); "
                        "0 = derived: every slot's window and chunk, and a "
                        "window a slot of cached tails. More keeps more "
                        "contexts' tails hittable, as --num-blocks keeps "
                        "more history")
    p.add_argument("--spec-gamma", type=int, default=0,
                   help="speculative decoding: proposals per verify (0=off)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="speculative decoding: lookup n-gram length")
    p.add_argument("--max-context", type=int, default=0)
    p.add_argument("--adapters", default=None,
                   help="comma-separated LoRA adapters served next to the "
                        "base model: name:rank[:seed] (seeded synthetic "
                        "weights) or name=/path.npz (stacked A/B arrays); "
                        "each name becomes a served model "
                        "(docs/multi_model.md)")
    p.add_argument("--max-live-adapters", type=int, default=0,
                   help="max adapters resident in the device stack at once "
                        "(0 = all configured adapters stay resident); "
                        "smaller turns on LRU staging + weight pre-stage")
    p.add_argument("--model-slo", default=None,
                   help="per-model admission SLO classes, "
                        "model=class[,model=class...] — routes a model's "
                        "traffic into that class's token-bucket pool "
                        "(requires --admission-rate)")
    p.add_argument("--namespace", default="dynamo",
                   help="in=prefill queue namespace — must match the decode "
                        "workers' dyn:// namespace")
    p.add_argument("--advertise-host", default=None,
                   help="routable address advertised for KV transfer "
                        "connect-back (defaults to this host's IP)")
    p.add_argument("--disagg", default=None, choices=[None, "decode"],
                   help="decode: offload long prompts to prefill workers")
    p.add_argument("--max-local-prefill", type=int, default=512,
                   help="uncached prompt tokens above this go remote")
    p.add_argument("--kv-stream", dest="kv_stream", action="store_true",
                   default=True,
                   help="streamed layer-wise KV handoff: open the "
                        "transfer at prefill start and ship each chunk's "
                        "blocks as its compute finishes (default)")
    p.add_argument("--no-kv-stream", dest="kv_stream", action="store_false",
                   help="force the legacy post-prefill bulk KV handoff "
                        "(decode role stops advertising the streamed "
                        "capability; prefill role stops using it)")
    p.add_argument("--kv-cost-model", dest="kv_cost_model",
                   action="store_true", default=True,
                   help="self-calibrating transfer-cost model (default "
                        "on): observe restore/pull/handoff/prefill "
                        "timings and advertise per-link bandwidths so "
                        "the KV router can route on predicted TTFT")
    p.add_argument("--no-kv-cost-model", dest="kv_cost_model",
                   action="store_false",
                   help="disable cost observation/advertisement (the "
                        "router keeps this worker on overlap scoring)")
    p.add_argument("--kv-ici", dest="kv_ici", action="store_true",
                   default=True,
                   help="ICI same-slice KV fast path (default on): "
                        "decode roles advertise their slice "
                        "fingerprint and same-slice prefill peers "
                        "negotiate it per handoff (disagg/ici.py). "
                        "Engages on ANY channel once fingerprints "
                        "match: in-process LocalKvPipe pairs hand "
                        "segments device-to-device, and launched "
                        "same-slice roles land their wire segments "
                        "through the same compiled per-bucket mover "
                        "programs onto the decode layout (cross-slice "
                        "or mismatched peers keep the plain streamed "
                        "path)")
    p.add_argument("--no-kv-ici", dest="kv_ici", action="store_false",
                   help="disable the ICI fast path (all handoffs take "
                        "the TCP/streamed plane)")
    p.add_argument("--kv-segment-blocks", type=int, default=0,
                   help="cap per-segment block count in the streamed "
                        "handoff (0 = one segment per prefill chunk)")
    p.add_argument("--prefill-concurrency", type=int, default=1,
                   help="in=prefill: concurrent prompts advancing "
                        "chunk-wise on one engine (each streams its own "
                        "KV segments as its chunks land; 1 = serialize "
                        "whole prompts)")
    p.add_argument("--no-migration", action="store_true",
                   help="disable transparent in-flight request migration "
                        "(frontend roles: a worker death then errors its "
                        "streams instead of resuming them elsewhere)")
    p.add_argument("--max-migrations", type=int, default=3,
                   help="re-dispatch attempts per request before the "
                        "failure surfaces to the client")
    p.add_argument("--migration-deadline", type=float, default=30.0,
                   help="wall-clock budget (s) from a request's first "
                        "failure across all its re-dispatches")
    p.add_argument("--drain-deadline", type=float, default=15.0,
                   help="SIGTERM graceful-drain budget (s): in-flight "
                        "requests get this long to finish before being "
                        "handed off to surviving workers")
    p.add_argument("--admission-rate", type=float, default=0.0,
                   help="frontend overload gate: admitted req/s "
                        "(token bucket; planner watermarks retune it "
                        "live; 0 = admit everything). Shed requests "
                        "get 429 + Retry-After before any engine work")
    p.add_argument("--admission-burst", type=float, default=0.0,
                   help="admission gate burst size (0 = max(rate, 1))")
    p.add_argument("--planner", action="store_true",
                   help="run the standalone SLA planner role "
                        "(equivalent to in=planner)")
    p.add_argument("--planner-tick", type=float, default=2.0,
                   help="planner control-loop period (s)")
    p.add_argument("--slo-ttft-ms", type=float, default=2000.0,
                   help="planner SLO: TTFT p99 target (ms)")
    p.add_argument("--slo-itl-ms", type=float, default=200.0,
                   help="planner SLO: inter-token-latency p99 target (ms)")
    p.add_argument("--planner-scenario", default="8b-int8-v5e1",
                   help="roofline scenario seeding the capacity model "
                        "(perf/roofline.py DEFAULT_SCENARIOS name)")
    p.add_argument("--planner-capacity", default=None,
                   help="explicit per-replica capacity seed "
                        "'DECODE_TOK_S[,PREFILL_TOK_S]' (overrides "
                        "--planner-scenario)")
    p.add_argument("--planner-min-replicas", type=int, default=1)
    p.add_argument("--planner-max-replicas", type=int, default=8)
    p.add_argument("--planner-pools", default="aggregated",
                   choices=["aggregated", "disagg"],
                   help="disagg: size a separate prefill pool; "
                        "aggregated: TTFT breaches grow the decode pool")
    p.add_argument("--planner-morph", action="store_true",
                   help="elastic live resharding: publish guarded "
                        "MorphDecisions on the 'reshard' subject — grow "
                        "a pool's TP when long prompts dominate, shrink "
                        "on sustained idle, re-lay survivors after a "
                        "lost host (workers morph in place, zero "
                        "dropped tokens; docs/elastic_resharding.md)")
    p.add_argument("--morph-tp-max", type=int, default=4,
                   help="max tensor-parallel degree the morph policy "
                        "may grow a worker to")
    p.add_argument("--morph-grow-prompt-tokens", type=float, default=512.0,
                   help="windowed mean prompt length at/above which the "
                        "morph policy doubles TP (long-prompt-dominated "
                        "signal)")
    p.add_argument("--deploy-root", default=None,
                   help="planner actuator: deploy controller store root "
                        "(with --deployment; omit for publish-only)")
    p.add_argument("--deployment", default=None,
                   help="planner actuator: deployment name whose "
                        "worker/prefill services the planner resizes")
    p.add_argument("--autopilot", action="store_true",
                   help="fleet autopilot on a KV-routed frontend "
                        "(docs/autopilot.md): compile pre-warm before "
                        "traffic shifts, auto-quarantine of "
                        "breach-spiking workers with probe-based "
                        "reinstatement, and (with --autopilot-headroom) "
                        "measured-headroom admission caps")
    p.add_argument("--autopilot-tick", type=float, default=2.0,
                   help="autopilot control-loop interval in seconds")
    p.add_argument("--no-prewarm", action="store_true",
                   help="autopilot: disable the compile pre-warm loop")
    p.add_argument("--no-quarantine", action="store_true",
                   help="autopilot: disable the auto-quarantine loop")
    p.add_argument("--autopilot-headroom", action="store_true",
                   help="autopilot: cap reserve-bearing admission "
                        "classes at measured headroom (needs "
                        "--admission-rate > 0)")
    p.add_argument("--no-tail-aware", action="store_true",
                   help="KV router: don't fold windowed per-worker p99 "
                        "queue-wait+prefill tails into the cost model's "
                        "predicted TTFT (tail-aware routing is on by "
                        "default; docs/autopilot.md)")
    p.add_argument("--tail-window-s", type=float, default=60.0,
                   help="tail-aware routing: sliding window over the "
                        "scraped cumulative histograms")
    p.add_argument("--engine-subprocess", action="store_true",
                   help="isolate a pystr:/pytok: engine in a child process")
    p.add_argument("--warmup", action="store_true",
                   help="compile every prefill bucket + the decode window "
                        "before serving (first-request TTFT skips the "
                        "20-40s per-bucket XLA compile)")
    p.add_argument("--trace", action="store_true",
                   default=os.environ.get("DYN_TRACE", "") not in ("", "0"),
                   help="distributed request tracing: span propagation "
                        "across frontend/router/workers, /trace/{id} "
                        "timelines + per-request TTFT decomposition "
                        "(also: DYN_TRACE=1)")
    p.add_argument("--no-flight-recorder", action="store_true",
                   help="disable the frontend flight recorder "
                        "(observability/flight.py): request-timeline "
                        "ring + slow-request autopsies at "
                        "/autopsy/{request_id} (on by default; the "
                        "ring is bounded and near-zero-cost)")
    p.add_argument("--autopsy-ttft-ms", default="",
                   help="SLO-breach autopsy thresholds: a TTFT target "
                        "in ms, flat ('2000') or per class "
                        "('interactive=2000,batch=30000'); a request "
                        "whose TTFT exceeds its class target is "
                        "autopsied and counted in slo_breaches_total. "
                        "Empty = autopsy only error finishes")
    p.add_argument("--autopsy-dir", default=None,
                   help="persist autopsy JSONs here (default: in-memory "
                        "ring only)")
    p.add_argument("--sanitize", action="store_true",
                   default=os.environ.get("DYN_SANITIZE", "") not in ("", "0"),
                   help="run the role under the asyncio hot-path sanitizer "
                        "in record mode (analysis/sanitizer.py): loop-stall "
                        "and lock-hold counters flow into load_metrics -> "
                        "fleet gauges (also: DYN_SANITIZE=1; threshold "
                        "DYN_LOOP_STALL_S, default 1.0s)")
    args = p.parse_args(argv)

    # before any device use: every role's programs go through the
    # persistent compile cache (JAX_COMPILATION_CACHE_DIR, else
    # <repo>/.jax_cache), so a restart reads what the last run compiled
    from ..utils.compile_cache import configure_compile_cache

    configure_compile_cache()

    args.in_ = "http"
    args.out = "jax"
    for tok in args.in_out:
        if tok.startswith("in="):
            args.in_ = tok[3:]
        elif tok.startswith("out="):
            args.out = tok[4:]
        elif tok == "hub":
            args.in_ = "hub"
    if args.planner:
        args.in_ = "planner"

    from ..utils.logging import setup_logging
    setup_logging()

    if args.in_ == "hub":
        coro = run_hub(args)
    elif args.in_ == "http":
        coro = run_http(args)
    elif args.in_ == "text":
        coro = run_text(args)
    elif args.in_ == "stdin":
        coro = run_stdin(args)
    elif args.in_.startswith("batch:"):
        coro = run_batch(args, args.in_[len("batch:"):])
    elif args.in_ == "prefill":
        coro = run_prefill(args)
    elif args.in_ == "planner":
        coro = run_planner(args)
    elif args.in_.startswith("dyn://"):
        coro = run_endpoint(args)
    else:
        raise SystemExit(f"unknown in= mode {args.in_!r}")
    if args.sanitize:
        # record mode: never fails the process — it feeds the san_*
        # counters that load_metrics exports and the metrics component
        # turns into per-worker gauges (docs/static_analysis.md)
        from ..analysis.sanitizer import LoopSanitizer

        async def _sanitized(inner):
            san = LoopSanitizer(
                stall_threshold_s=float(
                    os.environ.get("DYN_LOOP_STALL_S", "1.0")
                ),
            )
            san.activate()
            try:
                return await inner
            finally:
                san.before_shutdown()
                san.deactivate()

        coro = _sanitized(coro)
    try:
        asyncio.run(coro)
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
