"""LLM serving with continuous batching + SSE streaming: north-star config 4.

POST /generate {"prompt": "...", "max_tokens": 64, "temperature": 0.7,
"stream": true} -> server-sent events, one JSON per token chunk, then a final
{"done": true} summary. stream=false returns one JSON response.

The model comes from MODEL_PRESET: any preset of any family
gofr_tpu/models/families.py lists (docs/model-families.md says what each
is). Weights boot from a real HF-layout safetensors checkpoint when
WEIGHTS_PATH is set, for a family that has a loader
(models.weights.load_llama_safetensors — streaming, int8 quantize-on-load);
otherwise random-initialised (no checkpoints ship in this environment) with
identical serving/throughput/latency behavior.
"""

import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from gofr_tpu import App, Stream  # noqa: E402
from gofr_tpu.http.errors import InvalidParam, ServiceUnavailable  # noqa: E402
from gofr_tpu.models import families  # noqa: E402
from gofr_tpu.models.tokenizer import (ByteTokenizer, DebugTokenizer,  # noqa: E402
                                       StreamingDecoder)
from gofr_tpu.tpu.device import TPUClient  # noqa: E402
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402
from gofr_tpu.tpu.executor import Executor, enable_compile_cache  # noqa: E402

# {the name MODEL_PRESET takes: a constructor of the family's config}
PRESETS = families.presets()


def _load_tokenizer(path: str):
    """VOCAB_PATH format sniffing: HF tokenizer.json (byte-level BPE, what
    real Llama-3 checkpoints ship), tiktoken .model (Meta's distribution),
    or the framework's own {vocab, merges} JSON."""
    from gofr_tpu.models.tokenizer import BPETokenizer, ByteLevelBPETokenizer

    if path.endswith((".model", ".tiktoken")):
        return ByteLevelBPETokenizer.from_tiktoken(path)
    import json as _json

    with open(path, "r", encoding="utf-8") as fp:
        head = _json.load(fp)
    if "model" in head and "vocab" in head.get("model", {}):
        return ByteLevelBPETokenizer.from_tokenizer_json(path, data=head)
    return BPETokenizer.from_file(path)


def _raise_for_shed(exc: BaseException) -> None:
    """Engine shed errors — anything carrying a duck-typed 503 status_code
    (EngineDrainingError, EngineStalledError, breaker-open DeviceLostError)
    — re-raise as the transport's ServiceUnavailable with a Retry-After
    hint, so load balancers and SDK retry policies treat them as
    retryable instead of a bare 500. Everything else passes through."""
    if getattr(exc, "status_code", None) == 503:
        raise ServiceUnavailable(
            str(exc),
            retry_after_s=getattr(exc, "retry_after_s", None) or 1.0
        ) from exc
    raise exc


def _register_engine_observability(app: App, engine) -> None:
    """The engine's two pull-based surfaces, registered by EVERY
    construction path (built or injected): /.well-known/health reports the
    engine next to the datasources (a wedged device degrades the aggregate
    so load balancers stop routing here, matching submit()'s 503 shed),
    and the stall gauge refreshes at metrics-scrape time (a wedged loop
    cannot push its own metric). Both registrations are name-keyed and
    idempotent."""
    app.container.add_health_contributor("engine", engine.health_check)
    m = app.container.metrics_manager
    if m is not None:
        app.container.add_scrape_hook("engine_stall", lambda: m.set_gauge(
            "app_tpu_engine_stall_seconds", round(engine.stall_seconds, 1)))


def build_engine(app: App,
                 default_sampling_controls: bool = False) -> PagedLLMEngine:
    if not app.config.get_bool("PAGED", True):
        # outside input: an environment that names an engine this server
        # does not have is refused, not served silently from another
        raise ValueError(
            "PAGED=false asks for the dense per-slot engine, which no "
            "longer exists: the page-pool engine is the only one. Unset "
            "PAGED (PAGE_SIZE / N_PAGES size the pool)")
    # before the first jit (weight init below), so that every program of
    # this boot lands in one compile cache. The directory follows
    # executor.compile_cache_dir: JAX_COMPILATION_CACHE_DIR where the
    # machine sets it, else PROGRAM_CACHE_DIR (a deployment's shared
    # directory), else .compile_cache/ in the checkout
    program_cache = enable_compile_cache(
        app.config.get_or_default("PROGRAM_CACHE_DIR", "") or None)
    tpu = TPUClient(app.config)
    app.add_tpu(tpu)
    preset = app.config.get_or_default("MODEL_PRESET", "debug")
    cfg = PRESETS[preset]()
    # ATTN_IMPL: xla | flash (prefill / no-cache forward impl)
    import dataclasses

    attn_impl = app.config.get_or_default("ATTN_IMPL", cfg.attn_impl)
    # KV_DTYPE=int8 halves pool HBM bytes (quantize-on-write, dequant
    # folded into the paged read)
    kv_dtype = app.config.get_or_default("KV_DTYPE", "") or None
    if attn_impl not in ("xla", "flash"):
        raise ValueError(f"ATTN_IMPL must be xla|flash, got {attn_impl!r}")
    if kv_dtype not in (None, "int8"):
        raise ValueError(f"KV_DTYPE must be int8 or unset, got {kv_dtype!r}")
    asked = {"attn_impl": attn_impl, "kv_dtype": kv_dtype}
    has = {f.name for f in dataclasses.fields(cfg)}
    # a family with one prefill read and no lower-precision cache has no
    # such field (models/protocol.py): asked of it, refuse by name
    absent = sorted(k for k, v in asked.items()
                    if k not in has and v not in (None, "xla"))
    if absent:
        raise ValueError(
            f"MODEL_PRESET={preset} has no {', '.join(absent)}: unset "
            f"{', '.join(k.upper() for k in absent)}")
    cfg = dataclasses.replace(
        cfg, **{k: v for k, v in asked.items() if k in has})
    # VOCAB_PATH deploys a real model vocabulary (JSON {vocab, merges},
    # BPETokenizer.from_file — native merge loop when the C++ lib is built);
    # without it the exact-and-reversible byte tokenizer serves
    vocab_path = app.config.get_or_default("VOCAB_PATH", "")
    if vocab_path:
        tokenizer = _load_tokenizer(vocab_path)
        app.logger.infof("loaded vocab from %s (%s, %d tokens)",
                         vocab_path, type(tokenizer).__name__,
                         tokenizer.vocab_size)
    elif cfg.vocab_size > ByteTokenizer.vocab_size:
        # synthetic presets (debug: vocab_size=512) sample ids the byte
        # tokenizer cannot round-trip (>=256 dropped, random bytes form
        # invalid UTF-8); DebugTokenizer decodes every id to one char
        tokenizer = DebugTokenizer(cfg.vocab_size)
    else:
        tokenizer = ByteTokenizer()
    if cfg.vocab_size < tokenizer.vocab_size:
        raise ValueError(f"model vocab ({cfg.vocab_size}) too small for "
                         f"tokenizer ({tokenizer.vocab_size})")
    app.logger.infof("initialising %s (%.2fB params)...", preset,
                     cfg.param_count() / 1e9)
    # WEIGHT_DTYPE=int8 stores weights as per-output-channel int8 — halves
    # weight HBM (llama3-8b: ~15 GiB bf16 -> ~8 GiB, the difference between
    # not fitting and serving on one 16 GiB v5e chip) AND halves the
    # per-step weight read. Init goes straight to int8 leaf-by-leaf so the
    # float tree never has to fit (models.llama.llama_init_quantized).
    weight_dtype = app.config.get_or_default("WEIGHT_DTYPE", "") or None
    if weight_dtype not in (None, "int8"):
        raise ValueError(f"WEIGHT_DTYPE must be int8 or unset, "
                         f"got {weight_dtype!r}")
    # WEIGHTS_PATH boots from a real HF-layout safetensors checkpoint
    # (file, directory, or sharded index) — shapes validated against the
    # preset before any bytes load; WEIGHT_DTYPE=int8 quantizes each leaf
    # on device as it streams in, so the float tree never materializes
    weights_path = app.config.get_or_default("WEIGHTS_PATH", "")
    family = families.family_of(cfg)
    seeded_only = not hasattr(family, "load_checkpoint")
    if seeded_only and (weights_path or weight_dtype):
        raise ValueError(f"the {cfg.paged_model().family} family has no "
                         f"checkpoint loader and no int8 weight path "
                         f"yet: unset WEIGHTS_PATH and WEIGHT_DTYPE")
    if weights_path:
        t_load = time.time()
        params = family.load_checkpoint(cfg, weights_path,
                                        weight_dtype=weight_dtype,
                                        logger=app.logger)
        app.logger.infof("loaded weights from %s in %.1fs (%s)",
                         weights_path, time.time() - t_load,
                         weight_dtype or cfg.dtype)
    elif weight_dtype == "int8":
        params = family.init_quantized(cfg, seed=0)
    else:
        params = family.init(cfg, seed=0)
    # TP_SHARDS>1 serves tensor-parallel over the chip slice (BASELINE
    # config 5: Llama-70B TP=8 on v5e-8) — same engine, sharded mesh
    tp = app.config.get_int("TP_SHARDS", 1)
    mesh = tpu.mesh({"tp": tp}, allow_subset=True) if tp > 1 else None
    # the page pool (block tables + page allocator + scalar-prefetch Pallas
    # read): PAGE_SIZE tokens per page, N_PAGES caps the pool
    paged_kw = {"page_size": app.config.get_int("PAGE_SIZE", 128)}
    n_pages = app.config.get_int("N_PAGES", 0)
    if n_pages:
        paged_kw["n_pages"] = n_pages
    # PREFIX_CACHE shares whole prompt-prefix pages between requests
    # (system prompts re-prefill once, not per request); int8 pools
    # share their scale pages alongside
    # (on by default for a family that can serve it; asked for of one
    # that cannot, the engine refuses it by name)
    paged_kw["prefix_cache"] = app.config.get_bool(
        "PREFIX_CACHE", "prefix_cache" not in cfg.paged_model().refuses)
    # KV_HOST_TIER_BYTES>0 adds a host-RAM tier under the prefix
    # cache: evicted refs==0 pages spill to pinned host blobs and
    # restore via one H2D scatter at admission, so a re-sent prefix
    # pays a copy instead of a re-prefill even after HBM pressure
    # evicted it. KV_REDIS_TIER=true chains a write-behind Redis cold
    # tier below host RAM (blobs versioned + checksummed; any
    # corruption degrades to a miss, never wrong KV)
    tier_bytes = app.config.get_int("KV_HOST_TIER_BYTES", 0)
    if tier_bytes > 0:
        paged_kw["kv_host_tier_bytes"] = tier_bytes
        paged_kw["conversation_pin_s"] = app.config.get_float(
            "CONVERSATION_PIN_S", 600.0)
        if app.config.get_bool("KV_REDIS_TIER", False):
            from gofr_tpu.datasource.kvredis import RedisKVStore

            paged_kw["kv_redis"] = RedisKVStore(
                app.config, app.logger,
                app.container.metrics_manager)
            ttl = app.config.get_float("KV_REDIS_TTL_S", 0.0)
            if ttl > 0:
                paged_kw["kv_redis_ttl_s"] = ttl
    # HBM capacity plan: clamp (MAX_BATCH, MAX_SEQ_LEN) to the device budget
    # before boot instead of discovering RESOURCE_EXHAUSTED mid-serve.
    # Auto-detected from the device (0 on CPU backends = no plan);
    # HBM_BUDGET_BYTES overrides for testing, -1 disables the plan.
    from gofr_tpu.tpu.capacity import device_budget_bytes

    budget_cfg = app.config.get_int("HBM_BUDGET_BYTES", 0)
    budget = (0 if budget_cfg < 0
              else budget_cfg or device_budget_bytes(tpu))
    # DISAGG_MODE splits serving into a prefill pool and a decode pool
    # (tpu/disagg.py): "both" builds the split pair in-process behind a
    # DisaggRouter (the single-host deployment), "prefill"/"decode" build
    # one engine in that role for operator-wired pairs. The hand-off ships
    # KV page blobs.
    disagg_mode = app.config.get_or_default("DISAGG_MODE", "off").lower()
    if disagg_mode not in ("off", "prefill", "decode", "both"):
        raise ValueError(f"DISAGG_MODE must be off|prefill|decode|both, "
                         f"got {disagg_mode!r}")
    if disagg_mode != "off":
        paged_kw["disagg_role"] = ("decode" if disagg_mode == "both"
                                   else disagg_mode)
    engine_kw = dict(
        n_slots=app.config.get_int("MAX_BATCH", 8),
        max_seq_len=app.config.get_int("MAX_SEQ_LEN", 1024),
        budget_bytes=budget or None,
        prefill_buckets=tuple(int(b) for b in app.config.get_or_default(
            "PREFILL_BUCKETS", "16,32,64,128,256").split(",")),
        executor=Executor(tpu, cache_dir=program_cache),
        metrics=app.container.metrics_manager,
        logger=app.logger,
        mesh=mesh,
        tracer=app.container.tracer,
        # >0 splits long prompts into bounded chunk dispatches so decode
        # blocks interleave (TTFT under mixed traffic); must divide the
        # buckets it applies to
        chunk_prefill_tokens=app.config.get_int("CHUNK_PREFILL_TOKENS", 0),
        # >0 enables prompt-lookup speculative decoding: up to N draft
        # tokens verified per dispatch; greedy output is identical, wins
        # come on self-repetitive text (RAG, code edits, summaries)
        speculative_tokens=app.config.get_int("SPECULATIVE_TOKENS", 0),
        # per-request top_p/top_k ([B, 3] row controls; one [B, V] sort
        # per sampled step). Off by default for lean greedy serving; the
        # OpenAI server defaults it ON (it must honor client top_p)
        sampling_controls=app.config.get_bool("SAMPLING_CONTROLS",
                                              default_sampling_controls),
        # crash-only recovery: replay interrupted requests after a device
        # reset (bounded per request), and open the reset-storm breaker
        # (503 DeviceLostError + health DOWN) when resets cluster
        retry_budget=app.config.get_int("ENGINE_RETRY_BUDGET", 2),
        reset_storm_max=app.config.get_int("RESET_STORM_MAX", 3),
        reset_storm_window_s=app.config.get_float("RESET_STORM_WINDOW_S",
                                                  60.0),
        breaker_cooldown_s=app.config.get_float("BREAKER_COOLDOWN_S", 5.0),
        # decode hot-loop host teardown: start D2H token copies at
        # dispatch time (sync becomes a completion check) and run
        # terminal-slot teardown on a bounded off-loop finisher
        # (ENGINE_FINISHER_QUEUE=0 restores fully-inline finishing)
        async_d2h=app.config.get_bool("ENGINE_ASYNC_D2H", True),
        finisher_queue=app.config.get_int("ENGINE_FINISHER_QUEUE", 256),
        **paged_kw,
    )
    engine = PagedLLMEngine(params, cfg, **engine_kw)
    engine.tokenizer = tokenizer
    engine.start()
    # graceful drain: finish active generations (bounded) before the HTTP
    # server goes away; queued requests fail fast so clients can retry
    app.on_shutdown(lambda: (engine.drain(
        app.config.get_float("DRAIN_TIMEOUT", 30.0)), engine.stop()))
    # WARMUP=wide additionally precompiles every fused-admission width per
    # bucket and every decode table width up to MAX_SEQ_LEN, so organic
    # staggered traffic never pays a first-use compile mid-request
    # (amortized by the compile cache)
    warm_mode = app.config.get_or_default("WARMUP", "true").lower()
    # ELASTIC_WARM_BOOT=true makes warmup ASYNC behind a `warming`
    # lifecycle advertisement: the HTTP surface comes up immediately, the
    # fleet router holds traffic until /stats says serving, and warmup
    # rides the replicas' shared compile cache (cache hits, not fresh XLA
    # compiles) plus a KV pre-warm pulled from ELASTIC_PREWARM_PEERS'
    # /debug/kvtier inventories — the seconds-not-minutes boot an
    # autoscaler launch needs
    from gofr_tpu.tpu.migrate import Lifecycle

    warm_boot = app.config.get_bool("ELASTIC_WARM_BOOT", False)
    engine.lifecycle = Lifecycle("warming" if warm_boot else "serving")
    if warm_boot:
        peers = [p.strip() for p in app.config.get_or_default(
            "ELASTIC_PREWARM_PEERS", "").split(",") if p.strip()]
        prewarm_pages = app.config.get_int("ELASTIC_PREWARM_PAGES", 64)

        def _warm_boot():
            from gofr_tpu.tpu.migrate import prewarm_from_peers

            t0 = time.time()
            warmed = 0
            try:
                if warm_mode not in ("false", "0", "no", "off"):
                    engine.warmup(k_variants=warm_mode == "wide")
                if peers:
                    warmed = prewarm_from_peers(engine, peers,
                                                limit=prewarm_pages,
                                                logger=app.logger)
            except Exception as exc:  # noqa: BLE001 - serve cold > never
                app.logger.errorf("warm boot: %s", exc)
            engine.lifecycle.to("serving")
            engine.warm_boot_s = round(time.time() - t0, 3)
            app.logger.infof("warm boot: serving after %.1fs "
                             "(%d pages pre-warmed)",
                             engine.warm_boot_s, warmed)

        threading.Thread(target=_warm_boot, name="warm-boot",
                         daemon=True).start()
    elif warm_mode not in ("false", "0", "no", "off"):
        t0 = time.time()
        engine.warmup(k_variants=warm_mode == "wide")
        app.logger.infof("engine warmed up in %.1fs%s", time.time() - t0,
                         " (wide)" if warm_mode == "wide" else "")
    # WARMUP_SCORE=true pre-compiles the logprobs/embeddings families so
    # the first client request never pays a compile under its deadline
    # (off by default: deployments that never score keep the lean boot)
    if app.config.get_bool("WARMUP_SCORE", False):
        t0 = time.time()
        n = engine.warmup_scoring()
        app.logger.infof("scoring warmed up in %.1fs (%d passes)",
                         time.time() - t0, n)
    if disagg_mode == "both":
        from gofr_tpu.tpu.disagg import (DisaggRouter, PubSubTransport,
                                         register_disagg_metrics)

        # the prefill twin shares the decode pool's params (the same
        # read-only arrays — no second weight copy in HBM) and config;
        # DISAGG_PREFILL_SLOTS sizes its admission width independently
        prefill_kw = dict(engine_kw, disagg_role="prefill")
        n_pre = app.config.get_int("DISAGG_PREFILL_SLOTS", 0)
        if n_pre:
            prefill_kw["n_slots"] = n_pre
        prefill_engine = PagedLLMEngine(params, cfg, **prefill_kw)
        prefill_engine.tokenizer = tokenizer
        prefill_engine.start()
        if warm_mode not in ("false", "0", "no", "off"):
            prefill_engine.warmup(k_variants=warm_mode == "wide")
        # DISAGG_TRANSPORT=pubsub ships hand-offs over the app's broker
        # (commit-to-advance); the default is the bounded in-proc queue
        transport = None
        if app.config.get_or_default("DISAGG_TRANSPORT",
                                     "queue") == "pubsub":
            broker = getattr(app.container, "pubsub", None)
            if broker is not None:
                transport = PubSubTransport(broker)
        router = DisaggRouter(
            prefill_engine, engine,
            metrics=app.container.metrics_manager,
            transport=transport,
            queue_depth=app.config.get_int("DISAGG_QUEUE_DEPTH", 64),
            handoff_timeout_s=app.config.get_float(
                "DISAGG_HANDOFF_TIMEOUT_S", 10.0))
        if app.container.metrics_manager is not None:
            register_disagg_metrics(app.container.metrics_manager)
        router.start()
        # the router is the front door; build_app routes submits through
        # it (and /debug/disagg onto it) whenever the engine carries one
        engine.disagg_router = router
        app.container.add_health_contributor("prefill_engine",
                                             prefill_engine.health_check)
        app.on_shutdown(lambda: (router.stop(), prefill_engine.drain(
            app.config.get_float("DRAIN_TIMEOUT", 30.0)),
            prefill_engine.stop()))
    # /.well-known/health reports the engine next to the datasources: a
    # wedged device (loop stuck in a PJRT call) degrades the aggregate so
    # load balancers stop routing here, matching submit()'s 503 shed.
    # Registered here so every server built on this engine (llm-server,
    # openai-server) gets it, not just the /generate surface.
    _register_engine_observability(app, engine)
    return engine


def build_generate_service(engine, tokenizer):
    """Server-streaming gRPC twin of the SSE /generate endpoint: one
    {"text": ...} message per decoded chunk, then a {"done": true}
    summary — the same payload shapes the SSE stream sends, so a client
    can consume either transport with one parser. Registered by main()
    (reference parity: grpc.go registers streaming protoc services)."""
    import time as _time

    from gofr_tpu.grpcx import GenericService
    from gofr_tpu.models.tokenizer import StreamingDecoder

    def grpc_generate(ctx):
        body = ctx.request.payload or {}
        prompt = body.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            raise ValueError("prompt must be a non-empty string")
        # full parameter parity with the SSE /generate handler — a client
        # switching transports must not silently lose its sampling or
        # admission settings
        request = engine.submit(
            tokenizer.encode(prompt),
            max_new_tokens=int(body.get("max_tokens", 64)),
            temperature=float(body.get("temperature", 0.0)),
            stop_tokens={tokenizer.EOS},
            min_tokens=max(0, int(body.get("min_tokens", 0) or 0)),
            priority=max(0, min(9, int(body.get("priority", 0) or 0))),
            top_p=float(body.get("top_p", 0.0) or 0.0),
            top_k=int(body.get("top_k", 0) or 0))

        def stream():
            decoder = StreamingDecoder(tokenizer)
            count = 0
            start = _time.time()
            try:
                for token in request.stream():
                    count += 1
                    text = decoder.push(token)
                    if text:
                        yield {"text": text}
                tail = decoder.flush()
                if tail:
                    yield {"text": tail}
                yield {"done": True, "tokens": count,
                       "tok_per_s": round(
                           count / max(_time.time() - start, 1e-6), 1)}
            finally:
                request.cancel()   # client disconnect frees the slot

        return stream()

    return GenericService("llm.Generator", {},
                          stream_methods={"Generate": grpc_generate})


def build_app(config=None, engine=None) -> App:
    """App + engine + routes, reusable by tests and the bench harness so
    the MEASURED path is the real handler/SSE encoder, not a re-creation
    (VERDICT r4 missing #2). The engine rides on `app.engine`.

    `engine` wraps an ALREADY-BUILT engine in the serving surface — the
    bench uses this to measure HTTP-boundary latency around its live TPU
    engine without booting a second model into HBM."""
    app = App(config=config)
    if engine is None:
        engine = build_engine(app)
    elif getattr(engine, "tokenizer", None) is None:
        vocab = getattr(getattr(engine, "cfg", None), "vocab_size", 0)
        engine.tokenizer = (DebugTokenizer(vocab)
                            if vocab > ByteTokenizer.vocab_size
                            else ByteTokenizer())
    app.engine = engine
    # idempotent when build_engine already registered them (both are
    # name-keyed); covers the injected-engine path (tests) too
    _register_engine_observability(app, engine)
    # FLIGHT_RECORDER=false opts out of the per-request timeline surface
    # (GET /debug/requests, engine child spans, SLO goodput gauges); an
    # engine injected with its own recorder keeps it — enable_ only wires
    # the app's metrics/tracer sinks and the routes then
    if app.config.get_bool("FLIGHT_RECORDER", True):
        recorder = app.enable_flight_recorder(engine)
        # journey surface: GET /debug/journey[/{id}] assembles this
        # replica's recorder(s) — both halves of a DISAGG both pair —
        # into the same hop waterfall the fleet router serves
        app.enable_journey(engine)
        # traffic observatory: the recorder's request ring re-exported
        # as a replayable loadgen trace at GET /debug/trace
        # (FLIGHT_TRACE_EXPORT=false opts out)
        if app.config.get_bool("FLIGHT_TRACE_EXPORT", True):
            from gofr_tpu.loadgen.capture import \
                install_recorder_trace_route

            install_recorder_trace_route(app, recorder)
    # fleet-level sibling: GET /debug/engine (slots / page pool / compile
    # table / MFU-MBU utilization window) + HBM sampler; ENGINE_SNAPSHOT=
    # false opts out
    if app.config.get_bool("ENGINE_SNAPSHOT", True):
        app.enable_engine_snapshot(engine)
    # step anatomy: GET /debug/steps (per-iteration segment attributions +
    # straggler sentinel) and the exemplar-carrying step histograms;
    # STEP_LEDGER=false opts out, STEP_LEDGER_CAPACITY / STEP_STRAGGLER_K /
    # STEP_BASELINE_* tune the ring and sentinel
    if app.config.get_bool("STEP_LEDGER", True):
        app.enable_step_ledger(engine)
    # performance timeline: GET /debug/timeline renders the ledgers and
    # recorders above as one Perfetto-loadable trace (real threads as
    # named tracks, device busy slices, per-request flow arrows);
    # TIMELINE=false opts out, TIMELINE_STEPS sets the step window
    if app.config.get_bool("TIMELINE", True):
        app.enable_timeline(engine)
    # always-on host sampling profiler: GET /debug/hostprof attributes
    # loop host time to Python frames (bounded collapsed stacks, measured
    # self-overhead); HOSTPROF=false or HOSTPROF_HZ<=0 opts out,
    # HOSTPROF_HZ / HOSTPROF_MAX_STACKS / HOSTPROF_TOP_K tune it
    if app.config.get_bool("HOSTPROF", True):
        app.enable_hostprof(engine)
    # incident autopsy plane: SLO burn-rate engine (GET /debug/slo,
    # app_tpu_slo_burn_rate / app_tpu_slo_alert_state) + anomaly-triggered
    # evidence bundles (GET /debug/incidents); fed by the flight recorder,
    # triggered by burn pages, straggler streaks, breaker opens, and
    # quarantines. INCIDENT_AUTOPSY=false opts out; SLO_BURN_* /
    # INCIDENT_* tune windows, thresholds, and the capture rate limit
    if app.config.get_bool("INCIDENT_AUTOPSY", True):
        burn, _ = app.enable_incident_autopsy(engine)
        # the soak/bench harnesses re-target SLO thresholds mid-run (a
        # CPU-host baseline differs 100x from a TPU pod's); exposing the
        # burn engine keeps that tuning out of the engine's internals
        app.slo_burn = burn
    # chaos plane: POST /debug/faults + engine/executor/device fault hooks.
    # HARD-gated on FAULT_INJECTION=true — disabled (the default) keeps the
    # zero-overhead faults=None fast path and the endpoint 404s
    app.enable_fault_injection(engine)
    # QoS serving plane: tenant classes + burn-actuated shed ladder +
    # batch lane (GET /debug/qos, app_tpu_qos_*). Opt-IN (QOS=true): the
    # ladder actuates on the burn engine above, and default SLO targets
    # are TPU-scale — a CPU test host would page immediately and shed
    # legacy traffic that never asked for QoS semantics
    if app.config.get_bool("QOS", False):
        app.enable_qos(engine)
    # capacity observatory: per-tenant attribution (app_tpu_meter_*) +
    # headroom forecast (app_tpu_capacity_*) at GET /debug/capacity;
    # CAPACITY=false opts out, METER_* / CAPACITY_* tune it
    if app.config.get_bool("CAPACITY", True):
        app.enable_capacity(engine)
    tokenizer: ByteTokenizer = engine.tokenizer
    # disaggregated pair (DISAGG_MODE=both): the router is the front door
    # — prefill pool runs the prompt, decode pool streams the rest — and
    # its hand-off plane reports at GET /debug/disagg. submit() has the
    # engine's signature, so every surface below is split-agnostic
    router = getattr(engine, "disagg_router", None)
    if router is not None:
        from gofr_tpu.tpu.disagg import install_routes as _disagg_routes

        _disagg_routes(app, router)
    submitter = router if router is not None else engine
    # token streaming over gRPC rides the same engine (GRPC_PORT)
    app.register_grpc_service(build_generate_service(submitter, tokenizer))

    # fleet advertisement: routers (gofr_tpu/fleet) probe /stats every
    # FLEET_PROBE_S for load + a bounded digest of served prefix keys —
    # the digest re-warms a restarted router's affinity map, and its
    # per-boot generation id tells routers when THIS replica restarted
    # (KV gone, learned affinity stale)
    from gofr_tpu.fleet.affinity import AffinityRecorder

    affinity = AffinityRecorder(
        block=app.config.get_int("FLEET_AFFINITY_BLOCK", 256))
    app.fleet_affinity = affinity

    # elastic lifecycle + drain-with-migration: every replica advertises
    # warming/serving/draining through /stats (routers gate on it) and
    # serves POST /debug/drain — scale-down migrates still-live sessions
    # to peers over POST /migrate instead of holding the replica for
    # their full generation (DRAIN_MIGRATE=false keeps the surface off)
    app.enable_drain_migration(engine)
    lifecycle = engine.lifecycle

    @app.post("/generate")
    def generate(ctx):
        if lifecycle.state == "draining":
            # new sessions belong on a peer; in-flight streams (and
            # migrations landing on /migrate's submit_handoff path,
            # which outranks admission) are unaffected
            raise ServiceUnavailable("replica is draining",
                                     retry_after_s=1.0)
        body = ctx.bind()
        prompt = body.get("prompt")
        if not isinstance(prompt, str) or not prompt:
            raise InvalidParam(["prompt"])
        max_tokens = int(body.get("max_tokens", 64))
        temperature = float(body.get("temperature", 0.0))
        stream = bool(body.get("stream", True))

        try:
            # lower admits first; clamp so no client can outrank the range
            priority = max(0, min(9, int(body.get("priority", 0))))
            # EOS is ignored until this floor is reached
            min_tokens = max(0, int(body.get("min_tokens", 0) or 0))
            # per-request truncation (needs SAMPLING_CONTROLS=true; the
            # engine 400s them otherwise via the ValueError below)
            top_p = float(body.get("top_p", 0.0) or 0.0)
            top_k = int(body.get("top_k", 0) or 0)
        except (TypeError, ValueError) as exc:
            raise InvalidParam(["priority", "min_tokens", "top_p",
                                "top_k"]) from exc
        # QoS class + tenant: header wins over body; unknown class
        # strings 400 inside submit (tpu/qos.py normalize), never a
        # silent default. With QOS off the values still thread through
        # harmlessly (engine.qos is None → no banding, no gates)
        qos_class = (ctx.request.header("X-QoS-Class")
                     or body.get("class") or None)
        tenant = str(ctx.request.header("X-Tenant")
                     or body.get("tenant") or "")
        try:
            request = submitter.submit(
                tokenizer.encode(prompt), max_new_tokens=max_tokens,
                temperature=temperature, stop_tokens={tokenizer.EOS},
                span=ctx.span,  # batch.id/slot correlation lands on span
                traceparent=ctx.request.traceparent,  # engine child spans
                priority=priority, min_tokens=min_tokens, top_p=top_p,
                top_k=top_k, qos_class=qos_class, tenant=tenant)
        except ValueError as exc:
            raise InvalidParam([str(exc)]) from exc
        except Exception as exc:  # noqa: BLE001 - sheds → 503 + Retry-After
            _raise_for_shed(exc)
        affinity.record(prompt)  # admitted: its prefix now lives here

        if not stream:
            from gofr_tpu.http.errors import RequestTimeout

            start = time.time()
            try:
                tokens = request.result(timeout_s=ctx.remaining())
            except TimeoutError as exc:  # slot already freed by result()
                raise RequestTimeout() from exc
            return {"text": tokenizer.decode(tokens), "tokens": len(tokens),
                    "seconds": round(time.time() - start, 3)}

        def chunks():
            decoder = StreamingDecoder(tokenizer)
            count = 0
            start = time.time()
            for token in request.stream():
                count += 1
                # one SSE event per TOKEN, even when the decoder buffers
                # (mid-codepoint) or the id has no text (junk ids under
                # random weights): the client's first event must mark the
                # first token, or measured TTFT collapses into total time
                # whenever early tokens render empty
                yield {"text": decoder.push(token)}
            tail = decoder.flush()
            if tail:
                yield {"text": tail}
            yield {"done": True, "tokens": count,
                   "tok_per_s": round(count / max(time.time() - start, 1e-6), 1)}

        return Stream(chunks(), sse=True, on_close=request.cancel)

    @app.get("/stats")
    def stats(ctx):
        out = {
            "active_slots": sum(1 for s in engine.slots if s.active),
            "queue_depth": engine._pending.qsize(),
            "compiled_programs": engine.executor.cache_size,
            "stall_seconds": round(engine.stall_seconds, 1),
        }
        if engine.speculative_tokens:
            out["spec"] = {
                "accept_ema": round(engine._spec_accept_ema, 3),
                "cooloff_dispatches": engine._spec_cooloff,
            }
        allocator = getattr(engine, "allocator", None)
        if allocator is not None:
            out["pages"] = {"used": allocator.used_pages,
                            "free": allocator.free_pages,
                            "page_size": allocator.page_size}
        prefix = getattr(engine, "prefix", None)
        if prefix is not None:
            out["prefix_cache"] = prefix.stats()
        kv_tier = getattr(engine, "kv_tier", None)
        if kv_tier is not None:
            tier = kv_tier.stats()
            tier["spilled_pages"] = engine._kv_spilled
            tier["restored_pages"] = engine._kv_restored
            out["kv_tier"] = tier
        recorder = getattr(engine, "recorder", None)
        if recorder is not None:
            out["slo"] = recorder.slo_stats()
        # cheap fleet probe payload: O(k) affinity digest + duty cycle,
        # NOT the full /debug/engine page-pool dump
        fleet = {"affinity": affinity.digest(),
                 "lifecycle": lifecycle.state}
        warm_boot_s = getattr(engine, "warm_boot_s", None)
        if warm_boot_s is not None:
            fleet["warm_boot_s"] = warm_boot_s
        qos_ctl = getattr(engine, "qos", None)
        if qos_ctl is not None:
            # the shed ladder's request_replica rung, fleet-visible: the
            # autoscaler treats it as "add capacity before I shed"
            fleet["qos"] = {"scaleout_wanted": qos_ctl.scaleout_wanted}
        util = getattr(engine, "util", None)
        if util is not None:
            fleet["duty_cycle"] = util.window_stats()["duty_cycle"]
        out["fleet"] = fleet
        return out

    return app


def main() -> None:
    os.chdir(os.path.dirname(os.path.abspath(__file__)))
    build_app().run()


if __name__ == "__main__":
    main()
