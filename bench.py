"""Headline benchmark: continuous-batching serving throughput + TTFT.

Run by the driver on real TPU hardware at the end of each round; prints a
JSON line {"metric", "value", "unit", "vs_baseline", ...}.

A cumulative result line is printed the MOMENT each phase completes, so the
last JSON line on stdout is always the most complete measurement that
actually finished. A run that finds no TPU, cannot boot its engine (a
kernel that does not compile, a config that does not fit) or gets no token
in the headline phase exits non-zero with the traceback: nothing here
falls back to the CPU or to a smaller config. JAX_PLATFORMS=cpu is the
caller's explicit choice of a rehearsal on the debug preset; it prints
counts only (integers, strings, booleans) — a time, a rate or a share from
a CPU run is not a measurement of this system and is dropped.

What it measures (BASELINE.md config 4), three phases on one engine:
  T0 — round-1-comparable decode throughput: 8-token prompts, short
    contexts, small KV allocation. PRIMARY metric for round-over-round
    continuity; vs_baseline = value / 2000 (config-4 per-chip target).
  T1 — honest serving throughput under a REALISTIC prompt mix (64-512
    token prompts, slot turnover, grown cache).
  L  — p50/p99 TTFT under a Poisson arrival process at ~70% of measured
    capacity (queue wait + prefill + pipeline sync, not a burst).
T1/L ride in the same JSON object under "extras", plus HBM-roofline
accounting (tok/s vs the v5e ~819 GB/s bandwidth bound).

Memory discipline: the engine config is pre-flighted through
gofr_tpu.tpu.capacity.plan_capacity against the device's reported
bytes_limit before any allocation (VERDICT r2 missing #2).
"""

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_TOK_S = 2000.0
V5E_HBM_GBPS = 819.0  # v5e HBM bandwidth roofline for decode
BENCH_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1500"))
# Per-token wait inside measurement phases: clears a mid-phase cache-growth
# compile with wide margin; an engine that emits nothing for this long has
# stopped, and the phase fails.
TOKEN_TIMEOUT_S = float(os.environ.get("BENCH_TOKEN_TIMEOUT_S", "420"))
_T0 = time.time()


def _left() -> float:
    return BENCH_BUDGET_S - (time.time() - _T0)


def _spent() -> float:
    return time.time() - _T0


def _prompt_mix(rng, n, vocab, limit):
    """Realistic prompt lengths: log-ish mix over 64-512, weighted to the
    128-256 middle (chat/RAG-shaped), capped to the engine admission limit."""
    lengths = rng.choice([64, 96, 128, 192, 256, 384, 512],
                         size=n, p=[.12, .14, .22, .20, .16, .10, .06])
    return [rng.integers(1, vocab, size=min(int(L), limit)).tolist()
            for L in lengths]


def _percentiles(xs):
    xs = sorted(xs)
    if not xs:
        return 0.0, 0.0
    return xs[len(xs) // 2], xs[min(len(xs) - 1, int(len(xs) * 0.99))]


def run_phase_throughput(engine, prompts, max_new, rounds=1):
    """Saturate the engine with mixed prompts; measure emitted tokens/sec
    from first submit to last completion (includes prefill — the honest
    serving number)."""
    for _ in range(rounds):  # warm: drives cache growth + compiles hot
        warm = [engine.submit(p, max_new_tokens=max_new, temperature=0.0)
                for p in prompts]
        for r in warm:
            r.result(timeout_s=TOKEN_TIMEOUT_S)

    t0 = time.time()
    reqs = [engine.submit(p, max_new_tokens=max_new, temperature=0.0)
            for p in prompts]
    for r in reqs:
        r.result(timeout_s=TOKEN_TIMEOUT_S)
    elapsed = time.time() - t0
    tokens = sum(r.generated for r in reqs)
    ttfts = [r.first_token_at - r.enqueued_at for r in reqs
             if r.first_token_at is not None]
    return tokens / elapsed, tokens, elapsed, ttfts


def run_phase_latency(engine, prompts, max_new, rate_rps, duration_s, rng):
    """Poisson arrivals at rate_rps for duration_s; returns (reqs, span_s).

    Draining sequentially is fine: TTFT is stamped by the engine loop at
    sync time, not by the consumer, and per-request queues are unbounded."""
    reqs = []
    t0 = time.time()
    t_end = t0 + duration_s
    while time.time() < t_end:
        reqs.append(engine.submit(prompts[len(reqs) % len(prompts)],
                                  max_new_tokens=max_new, temperature=0.0))
        time.sleep(float(rng.exponential(1.0 / rate_rps)))
    for r in reqs:
        r.result(timeout_s=TOKEN_TIMEOUT_S)
    finished = max((r.finished_at for r in reqs if r.finished_at), default=0)
    return reqs, max(finished - t0, 1e-9)


def _latency_point(engine, prompts, max_new, rate, duration_s, rng):
    """One Poisson operating point -> {rate, achieved tok/s, ttft p50/p99,
    queue-wait p50} — the load-latency pair the north-star targets
    (BASELINE.md config 4: tok/s AND p50 TTFT are one tradeoff)."""
    reqs, span = run_phase_latency(engine, prompts, max_new, rate,
                                   duration_s, rng)
    ttfts = [r.first_token_at - r.enqueued_at for r in reqs
             if r.first_token_at is not None]
    waits = [r.admitted_at - r.enqueued_at for r in reqs
             if r.admitted_at is not None]
    p50, p99 = _percentiles(ttfts)
    wait_p50, _ = _percentiles(waits)
    out_tok_s = sum(r.generated for r in reqs) / span
    return {"rate_rps": round(rate, 2), "n": len(reqs),
            "out_tok_s": round(out_tok_s, 1),
            "ttft_p50_ms": round(p50 * 1e3, 1),
            "ttft_p99_ms": round(p99 * 1e3, 1),
            "queue_wait_p50_ms": round(wait_p50 * 1e3, 1)}


def _load_example(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"bench_{name.replace('-', '_')}",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "examples", name, "main.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def run_phase_hello(n_threads=8, per_thread=200):
    """BASELINE config 1 (labeled extra, never headline): hello-world
    req/s through the REAL server — router, full middleware chain, JSON
    envelope, real sockets. The microservice half of the identity,
    measured (VERDICT r4 weak #6)."""
    import http.client
    import threading

    from gofr_tpu.config import MockConfig

    module = _load_example("http-server")
    app = module.build_app(config=MockConfig(
        {"HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "bench-hello",
         "KV_ENABLED": "true", "LOG_LEVEL": "ERROR"}))
    app.start()
    errors = [0] * n_threads
    try:
        port = app.http_port

        def worker(w):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            for _ in range(per_thread):
                conn.request("GET", "/hello?name=bench")
                resp = conn.getresponse()
                body = resp.read()
                if resp.status != 200 or b"Hello bench" not in body:
                    errors[w] += 1
            conn.close()

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(n_threads)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        span = time.time() - t0
    finally:
        app.shutdown()
    total = n_threads * per_thread
    return {"http_hello_rps": round(total / max(span, 1e-9), 1),
            "http_hello_errors": sum(errors)}


def run_phase_bert(on_tpu, n_threads=8, per_thread=25):
    """BASELINE config 3 (labeled extra): batched BERT /embed over gRPC
    through the DynamicBatcher — concurrent unary RPCs fuse into padded
    seq-bucket batches on the accelerator. BERT-base on TPU, debug-sized
    on the CPU rehearsal; ONE seq bucket to bound compile budget."""
    import threading

    from gofr_tpu.config import MockConfig
    from gofr_tpu.grpcx import GRPCClient

    module = _load_example("bert-embed")
    from gofr_tpu import App

    app = App(config=MockConfig(
        {"HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0",
         "APP_NAME": "bench-bert", "BERT_PRESET": "base" if on_tpu
         else "debug", "SEQ_BUCKETS": "64", "MAX_BATCH": "32",
         "BATCH_WINDOW_S": "0.003", "LOG_LEVEL": "ERROR"}))
    module.build_app(app)
    app.start()
    errors = [0] * n_threads
    try:
        port = app.grpc_port
        text = "the quick brown fox jumps over the lazy dog " * 1

        def worker(w, timeout_s=120):
            client = GRPCClient(f"127.0.0.1:{port}")
            for _ in range(per_thread):
                out = client.call("EmbedService", "Embed", {"text": text},
                                  timeout_s=timeout_s)
                if not out.get("embedding"):
                    errors[w] += 1
            client.close()

        # warm wave compiles the bucket outside the clock, under its own
        # generous deadline: a first compile can exceed the steady-state one
        worker(0, timeout_s=600)
        errors[0] = 0
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(n_threads)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        span = time.time() - t0
    finally:
        app.shutdown()
    total = n_threads * per_thread
    return {"bert_embed_rps": round(total / max(span, 1e-9), 1),
            "bert_embed_errors": sum(errors)}


def run_phase_http(engine, n_streams, max_new, prompt_chars, rng):
    """HTTP-BOUNDARY measurement (VERDICT r4 missing #2): wrap the LIVE
    engine in the real llm-server app (router, middleware, handler thread,
    SSE encoder, chunked writes over real sockets) and drive n_streams
    concurrent streaming clients. Returns {http_tok_s, http_ttft_p50_ms,
    http_ttft_p99_ms, streams, errors} — boundary TTFT stamps when the
    client READS the first SSE event, so every serving-stack cost the
    engine-direct phases skip is inside the clock."""
    import http.client
    import importlib.util
    import threading

    from gofr_tpu.config import MockConfig

    spec = importlib.util.spec_from_file_location(
        "llm_server_bench",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "examples", "llm-server", "main.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    app = module.build_app(
        config=MockConfig({"HTTP_PORT": "0", "METRICS_PORT": "0",
                           "GRPC_PORT": "0", "APP_NAME": "bench-http",
                           "REQUEST_TIMEOUT": "900",
                           "LOG_LEVEL": "ERROR"}),
        engine=engine)
    app.start()
    results = [dict() for _ in range(n_streams)]
    try:
        port = app.http_port

        def client(i, out):
            text = "".join(chr(32 + int(rng.integers(0, 94)))
                           for _ in range(prompt_chars))
            t0 = time.time()
            try:
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=900)
                conn.request("POST", "/generate",
                             body=json.dumps({"prompt": text,
                                              "max_tokens": max_new,
                                              "stream": True}),
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                if resp.status != 200:
                    out["error"] = f"status {resp.status}"
                    return
                first = None
                tokens = 0
                buf = b""
                while True:
                    chunk = resp.read1(65536)
                    if not chunk:
                        break
                    buf += chunk
                    while b"\n\n" in buf:
                        event, buf = buf.split(b"\n\n", 1)
                        if not event.startswith(b"data: "):
                            continue
                        if first is None:
                            first = time.time()
                        payload = json.loads(event[6:])
                        if payload.get("done"):
                            tokens = payload["tokens"]
                conn.close()
                out.update(ttft=(first - t0) if first else None,
                           done_at=time.time(), tokens=tokens)
            except Exception as exc:  # noqa: BLE001 - recorded, not raised
                out["error"] = f"{type(exc).__name__}"

        # organic (staggered) HTTP arrivals admit in unpredictable fused
        # group sizes; precompile every (bucket, K) so no first-use
        # compile lands inside a measured TTFT — production posture is
        # WARMUP=wide in the llm-server
        # grow=True: programs key on the allocated cache length, so warm
        # AT the length serving will use or the compiles repeat on growth
        try:
            engine.warmup(grow=True, k_variants=True)
        except TypeError:  # engines without the k_variants warmup
            pass
        # warmup wave at the SAME stream count/shapes so shape compiles
        # (grown cache length, decode variants) land outside the clock —
        # the engine-direct phases warm identically (rounds=1)
        warm = [dict() for _ in range(n_streams)]
        threads = [threading.Thread(target=client, args=(i, warm[i]))
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(i, results[i]))
                   for i in range(n_streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
    finally:
        app.shutdown()
    ok = [r for r in results if "error" not in r and r.get("ttft")]
    errors = [r.get("error") for r in results if "error" in r]
    span = max((r["done_at"] for r in ok), default=t0) - t0
    tokens = sum(r.get("tokens", 0) for r in ok)
    p50, p99 = _percentiles(sorted(r["ttft"] for r in ok))
    return {"http_tok_s": round(tokens / max(span, 1e-9), 1),
            "http_ttft_p50_ms": round(p50 * 1e3, 1),
            "http_ttft_p99_ms": round(p99 * 1e3, 1),
            "http_streams": len(ok), "http_errors": len(errors)}


def run_phase_fleet(sessions=6, turns=4, max_tokens=8):
    """Fleet front door (gofr_tpu/fleet): warm-turn TTFT with
    prefix-affinity routing vs round-robin over 2 debug-preset replicas.

    Session-heavy traffic: each session re-sends its growing history
    every turn, so turn N's prompt is a strict prefix-extension of turn
    N-1's. Affinity pins a session to the replica whose paged prefix
    cache already holds those pages; round-robin alternates replicas on
    every request, so a session's consecutive turns land on a replica
    that must re-prefill the whole history cold. Warm turns only (each
    session's first turn prefills cold everywhere and is excluded).
    Both arms run through the REAL examples/router app against the SAME
    replica pair; each arm uses fresh session texts so arm two cannot
    ride arm one's cached prefixes. Returns {fleet_ttft_rr_ms,
    fleet_ttft_affinity_ms, fleet_affinity_ttft_win_ms,
    fleet_affinity_hit_rate}."""
    import random
    import urllib.request

    from gofr_tpu.config import MockConfig

    llm = _load_example("llm-server")
    router_mod = _load_example("router")
    replicas = []
    for i in range(2):
        app = llm.build_app(config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0",
            "APP_NAME": f"bench-replica{i}", "MODEL_PRESET": "debug",
            "PAGED": "true", "PAGE_SIZE": "16", "PREFIX_CACHE": "true",
            "MAX_SEQ_LEN": "512", "MAX_BATCH": "4", "WARMUP": "true",
            "REQUEST_TIMEOUT": "120", "LOG_LEVEL": "ERROR",
            "INCIDENT_AUTOPSY": "false"}))
        app.start()
        replicas.append(app)

    def _ttft(base, prompt):
        """Client clock start → first SSE data event through the router."""
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": prompt, "stream": True,
                             "max_tokens": max_tokens}).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        t0 = time.monotonic()
        first = None
        with urllib.request.urlopen(req, timeout=120) as resp:
            for line in resp:
                if line.startswith(b"data: "):
                    if first is None:
                        first = time.monotonic()
                    if json.loads(line[6:].strip()).get("done"):
                        break
        if first is None:
            raise RuntimeError("stream ended before any token")
        return (first - t0) * 1e3

    def _arm(policy, seed):
        router_app = router_mod.build_app(config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0",
            "APP_NAME": f"bench-router-{policy}",
            "REQUEST_TIMEOUT": "120", "LOG_LEVEL": "ERROR",
            "FLEET_REPLICAS": ",".join(
                f"r{i}=http://127.0.0.1:{a.http_port}"
                for i, a in enumerate(replicas)),
            "FLEET_POLICY": policy, "FLEET_PROBE_S": "0.5",
            "FLEET_AFFINITY_BLOCK": "24", "FLEET_RETRY_BUDGET": "2"}))
        router_app.start()
        base = f"http://127.0.0.1:{router_app.http_port}"
        rng = random.Random(seed)
        alphabet = "abcdefghijklmnopqrstuvwxyz "
        warm_ttfts = []
        try:
            for s in range(sessions):
                # debug replicas admit ~255 prompt tokens; the byte-ish
                # tokenizer makes chars ≈ tokens, so size the trunk +
                # growth to stay under the limit on the last turn
                history = (f"{policy} session {s:02d}: " + "".join(
                    rng.choice(alphabet) for _ in range(100)))
                for t in range(turns):
                    ms = _ttft(base, history)
                    if t > 0:  # first turn prefills cold everywhere
                        warm_ttfts.append(ms)
                    history += f" turn{t} " + "".join(
                        rng.choice(alphabet) for _ in range(24))
            body = json.loads(urllib.request.urlopen(
                base + "/debug/fleet", timeout=10).read())
            snap = body.get("data", body)
            hit_rate = (snap.get("affinity") or {}).get("hit_rate")
        finally:
            router_app.shutdown()
        warm_ttfts.sort()
        return warm_ttfts[len(warm_ttfts) // 2], hit_rate

    try:
        rr_ms, _ = _arm("round_robin", seed=7001)
        aff_ms, hit_rate = _arm("affinity", seed=7002)
    finally:
        for app in replicas:
            app.shutdown()
    return {"fleet_ttft_rr_ms": round(rr_ms, 2),
            "fleet_ttft_affinity_ms": round(aff_ms, 2),
            "fleet_affinity_ttft_win_ms": round(rr_ms - aff_ms, 2),
            "fleet_affinity_hit_rate": hit_rate}


def run_phase_loadgen(rate_rps=6.0, seconds=12.0):
    """Open-loop traffic observatory (gofr_tpu/loadgen): a synthesized
    Poisson trace replayed open-loop — arrivals fire on schedule
    regardless of completions — against 2 debug replicas behind the
    real router, scored by the SLO scorecard.

    Unlike every closed-loop phase above, offered load here is
    independent of service speed, so the offered-vs-served gap and the
    dispatch-lag self-audit are real measurements: worst_lag_ms is the
    generator proving it held the schedule while the system backed up.
    Returns {loadgen_offered, loadgen_ok, loadgen_shed,
    loadgen_ttft_p95_ms, loadgen_worst_lag_ms, loadgen_slo_met}."""
    from gofr_tpu.config import MockConfig
    from gofr_tpu.loadgen import (OpenLoopRunner, build_scorecard,
                                  poisson_arrivals, synthesize)
    from gofr_tpu.loadgen.scorecard import percentile
    import random

    llm = _load_example("llm-server")
    router_mod = _load_example("router")
    replicas = []
    for i in range(2):
        app = llm.build_app(config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0",
            "APP_NAME": f"bench-ol-replica{i}", "MODEL_PRESET": "debug",
            "PAGED": "true", "PAGE_SIZE": "16", "PREFIX_CACHE": "true",
            "MAX_SEQ_LEN": "512", "MAX_BATCH": "4", "WARMUP": "true",
            "REQUEST_TIMEOUT": "120", "LOG_LEVEL": "ERROR",
            "QOS": "true", "PUBSUB_BACKEND": "inproc",
            "INCIDENT_AUTOPSY": "false"}))
        app.start()
        replicas.append(app)
    router_app = router_mod.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "bench-ol-router",
        "REQUEST_TIMEOUT": "120", "LOG_LEVEL": "ERROR",
        "FLEET_REPLICAS": ",".join(
            f"r{i}=http://127.0.0.1:{a.http_port}"
            for i, a in enumerate(replicas)),
        "FLEET_PROBE_S": "0.5", "ELASTIC": "false"}))
    router_app.start()
    base = f"http://127.0.0.1:{router_app.http_port}"
    try:
        # warm-up absorbs the decode-batch compile storms so the phase
        # measures serving, not XLA; word counts stay <= 6 because the
        # debug tokenizer spends ~8 tokens per word against the
        # 64-token admission limit
        warm = synthesize(
            poisson_arrivals(rate_rps, min(seconds, 8.0), random.Random(7)),
            tenants=4, sessions=6, prompt_tokens=(2, 6), max_new=(4, 8),
            seed=7)
        OpenLoopRunner(base, warm, timeout_s=120.0,
                       label="bench-ol-warm").run(drain_timeout_s=240.0)
        events = synthesize(
            poisson_arrivals(rate_rps, seconds, random.Random(8101)),
            tenants=4, sessions=6, session_reuse=0.6,
            prompt_tokens=(2, 6), max_new=(4, 8), seed=8101)
        runner = OpenLoopRunner(base, events, timeout_s=120.0,
                                label="bench-ol")
        rows = runner.run(drain_timeout_s=240.0)
        status = runner.status()
    finally:
        router_app.shutdown()
        for app in replicas:
            app.shutdown()
    card = build_scorecard(rows)
    ok_rows = [r for r in rows if r.get("status") == "ok"]
    p95 = percentile([r["ttft_s"] * 1e3 for r in ok_rows
                      if isinstance(r.get("ttft_s"), (int, float))], 95)
    return {
        "loadgen_offered": len(rows),
        "loadgen_ok": len(ok_rows),
        "loadgen_shed": (status["outcomes"] or {}).get("shed", 0),
        "loadgen_ttft_p95_ms": round(p95, 1) if p95 is not None else None,
        "loadgen_worst_lag_ms": round(
            status["worst_dispatch_lag_s"] * 1e3, 1),
        "loadgen_slo_met": card["slo_met"],
    }


def run_phase_qos(n_requests=12, max_tokens=8, lane_jobs=8,
                  lane_max_tokens=160):
    """QoS serving plane (gofr_tpu/tpu/qos.py): interactive TTFT/TPOT
    with and without a saturating batch lane on ONE QOS=true server.

    Arm A measures interactive latency on a quiet engine. Arm B
    publishes long offline jobs to the batch lane until it is saturated
    (inflight at its cap), then re-measures the SAME interactive
    traffic riding over the busy engine. The delta is what the class
    bands + reserved-slot quota buy: interactive requests jump the
    batch queue instead of waiting behind offline decodes. Per-class
    goodput comes from /debug/qos afterwards. Returns
    {qos_interactive_ttft_quiet_ms, qos_interactive_ttft_saturated_ms,
    qos_interactive_ttft_protect_ms, qos_interactive_tpot_quiet_ms,
    qos_interactive_tpot_saturated_ms, qos_goodput_interactive,
    qos_goodput_batch, qos_lane_completed} plus the capacity
    observatory's measured μ/ρ and top-tenant attribution
    (capacity_mu_tok_s, capacity_rho, capacity_top_tenant,
    capacity_top_tenant_device_s — tpu/meter.py)."""
    import urllib.request

    from gofr_tpu.config import MockConfig

    llm = _load_example("llm-server")
    app = llm.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0",
        "APP_NAME": "bench-qos", "MODEL_PRESET": "debug",
        "PAGED": "true", "PAGE_SIZE": "16", "MAX_SEQ_LEN": "256",
        "PREFILL_BUCKETS": "16,64,256", "MAX_BATCH": "4",
        "WARMUP": "true", "REQUEST_TIMEOUT": "300", "LOG_LEVEL": "ERROR",
        "QOS": "true", "PUBSUB_BACKEND": "inproc",
        "QOS_LANE_MAX_INFLIGHT": "3", "INCIDENT_AUTOPSY": "false"}))
    app.start()
    base = f"http://127.0.0.1:{app.http_port}"
    lane = app.engine.qos.lane
    broker = app.container.pubsub

    def _measure(tag):
        """Client-clock TTFT + TPOT over n_requests streamed calls."""
        ttfts, tpots = [], []
        for i in range(n_requests):
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"prompt": f"{tag} ping {i}",
                                 "stream": True,
                                 "max_tokens": max_tokens}).encode(),
                headers={"Content-Type": "application/json",
                         "X-QoS-Class": "interactive",
                         "X-Tenant": "bench"}, method="POST")
            t0 = time.monotonic()
            first = last = None
            n_tokens = 0
            with urllib.request.urlopen(req, timeout=300) as resp:
                for line in resp:
                    if not line.startswith(b"data: "):
                        continue
                    now = time.monotonic()
                    if first is None:
                        first = now
                    event = json.loads(line[6:].strip())
                    if event.get("done"):
                        break
                    last = now
                    n_tokens += 1
            if first is None:
                raise RuntimeError("stream ended before any token")
            ttfts.append((first - t0) * 1e3)
            if last is not None and n_tokens > 1:
                tpots.append((last - first) * 1e3 / (n_tokens - 1))
        ttfts.sort()
        tpots.sort()
        return (ttfts[len(ttfts) // 2],
                tpots[len(tpots) // 2] if tpots else None)

    try:
        ttft_quiet, tpot_quiet = _measure("quiet")

        for i in range(lane_jobs):
            broker.publish("qos.batch.jobs", json.dumps(
                {"prompt": f"offline shard {i}",
                 "max_tokens": lane_max_tokens,
                 "tenant": "offline", "job_id": i}).encode())
        deadline = time.time() + 30.0
        while time.time() < deadline and lane.stats()["inflight"] < 1:
            time.sleep(0.05)
        if lane.stats()["inflight"] < 1:
            raise RuntimeError("batch lane never picked up a job")

        ttft_sat, tpot_sat = _measure("saturated")

        body = json.loads(urllib.request.urlopen(
            base + "/debug/qos", timeout=10).read())
        snap = body.get("data", body)
        classes = snap.get("classes") or {}
        goodput = {c: (classes.get(c) or {}).get("goodput")
                   for c in ("interactive", "batch")}
        # capacity observatory readout rides along: the measured service
        # rate μ + utilization ρ at the bench's batch shape, and the top
        # tenant's attributed device time (tpu/meter.py)
        body = json.loads(urllib.request.urlopen(
            base + "/debug/capacity", timeout=10).read())
        cap = body.get("data", body)
        forecast = cap.get("forecast") or {}
        top_tenants = cap.get("tenants") or []
        # let the lane drain so shutdown isn't tearing down live decodes
        drain_deadline = time.time() + 120.0
        while time.time() < drain_deadline and lane.depth() > 0:
            time.sleep(0.25)
        completed = lane.stats()["completed"]
    finally:
        app.shutdown()
    return {"qos_interactive_ttft_quiet_ms": round(ttft_quiet, 2),
            "qos_interactive_ttft_saturated_ms": round(ttft_sat, 2),
            "qos_interactive_ttft_protect_ms": round(ttft_sat - ttft_quiet,
                                                     2),
            "qos_interactive_tpot_quiet_ms": (
                round(tpot_quiet, 2) if tpot_quiet is not None else None),
            "qos_interactive_tpot_saturated_ms": (
                round(tpot_sat, 2) if tpot_sat is not None else None),
            "qos_goodput_interactive": goodput["interactive"],
            "qos_goodput_batch": goodput["batch"],
            "qos_lane_completed": completed,
            "capacity_mu_tok_s": forecast.get("mu_tok_s"),
            "capacity_rho": forecast.get("rho"),
            "capacity_top_tenant": (top_tenants[0].get("tenant")
                                    if top_tenants else None),
            "capacity_top_tenant_device_s": (
                top_tenants[0].get("device_s") if top_tenants else None)}


def _counts_only(value):
    """What a CPU rehearsal may print of `value`: integers, strings,
    booleans and containers of them. Floats — every time, rate and share
    the phases compute — are dropped (None), whatever their key is called."""
    if isinstance(value, dict):
        kept = {k: _counts_only(v) for k, v in value.items()}
        return {k: v for k, v in kept.items() if v is not None}
    if isinstance(value, (list, tuple)):
        return [v for v in map(_counts_only, value) if v is not None]
    return value if isinstance(value, (bool, int, str)) else None


class _LabelsOnly:
    """stderr of a counts-only run: a "[bench] phase: numbers" progress
    line keeps its label and loses its numbers (they are CPU timings);
    everything else, tracebacks included, passes through."""

    def __init__(self, stream):
        self._stream = stream

    def write(self, text):
        lines = text.split("\n")
        return self._stream.write("\n".join(
            line.split(":", 1)[0] if line.startswith("[bench] ") else line
            for line in lines))

    def __getattr__(self, name):
        return getattr(self._stream, name)


class _Record:
    """Cumulative result emitter: every update() reprints the full JSON line,
    so a crash after phase N still leaves phase N's line as the last parsable
    stdout record (VERDICT r2 weak #1). On a platform that is not the TPU
    the record carries counts only (_counts_only) and its headline is the
    number of tokens the T0 phase generated."""

    def __init__(self, metric, platform):
        self.counts_only = platform != "tpu"
        self.result = {"metric": metric, "value": 0.0, "unit": "tok/s",
                       "vs_baseline": 0.0, "platform": platform,
                       "extras": {}}
        if self.counts_only:
            self.result = {"metric": "tokens_generated_debug_rehearsal",
                           "value": 0, "unit": "tokens",
                           "platform": platform, "counts_only": True,
                           "extras": {}}

    def update(self, value=None, count=None, rename_metric=None,
               set_metric=None, **extras):
        """value: the headline rate (TPU); count: the tokens it was taken
        over, which is the whole headline of a counts-only run."""
        if self.counts_only:
            if count is not None:
                self.result["value"] = int(count)
            extras = _counts_only(extras)
        else:
            if set_metric is not None:
                self.result["metric"] = set_metric
            if rename_metric is not None:
                old, new = rename_metric
                self.result["metric"] = self.result["metric"].replace(old, new)
            if value is not None:
                self.result["value"] = round(value, 1)
                self.result["vs_baseline"] = round(value / BASELINE_TOK_S, 3)
        self.result["extras"].update(extras)
        sys.stdout.write(json.dumps(self.result) + "\n")
        sys.stdout.flush()

    def rename_slots(self, n_slots):
        """The _bsN tag must reflect the slots the engine's capacity plan
        actually serves."""
        import re

        self.result["metric"] = re.sub(r"_bs\d+_", f"_bs{n_slots}_",
                                       self.result["metric"])


def main() -> None:
    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and "cpu" not in os.environ.get("JAX_PLATFORMS", "").split(","):
        raise SystemExit(
            f"bench.py: JAX found no TPU (platform {platform!r}). A "
            f"rehearsal on the CPU is the caller's explicit choice: set "
            f"JAX_PLATFORMS=cpu (debug preset, counts only)")

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.capacity import (device_budget_bytes, kv_cache_bytes,
                                       kv_scales_bytes, params_bytes)
    from gofr_tpu.tpu.engine import LLMEngine

    def _roofline_tok_s(use_cfg, eng) -> float:
        """Decode reads weights + both caches every step: tok/s ceiling at
        the v5e HBM bandwidth for this engine's ACTUAL allocation."""
        per_step = (params_bytes(use_cfg)
                    + kv_cache_bytes(use_cfg, eng.n_slots, eng._cache_len,
                                     dtype=use_cfg.kv_dtype))
        if use_cfg.kv_dtype == "int8":
            per_step += kv_scales_bytes(use_cfg, eng.n_slots, eng._cache_len)
        return V5E_HBM_GBPS * 1e9 * eng.n_slots / per_step

    import dataclasses

    if on_tpu:
        # flash prefill: full-window Pallas kernel instead of the [T, S]
        # score materialization
        cfg = dataclasses.replace(LlamaConfig.llama1b(), attn_impl="flash")
        n_slots, max_new, max_seq = 128, 128, 1024
        prefill_buckets = (16, 64, 128, 256, 512)
        full_run = True
    else:
        cfg = LlamaConfig.debug()
        n_slots, max_new, max_seq = 8, 32, 256
        prefill_buckets = (16, 64, 128)
        full_run = False

    # HBM budget: the engine pre-flights plan_capacity(budget_bytes=...)
    # at construction and clamps (n_slots, max_seq, buckets) itself — ONE
    # source of truth for what actually serves. device_budget_bytes raises
    # on a TPU that reports no bytes_limit.
    # safety margin: the r5 run showed bytes_limit overstates what the chip
    # actually serves (plan peak 12.79 GiB "fit" a 16 GiB budget yet burst
    # prefills still RESOURCE_EXHAUSTED'd) — XLA reservations and prefill
    # activation transients live outside the plan's accounting
    budget = int(device_budget_bytes() * 0.90)

    print(f"[bench] platform={platform} "
          f"model={cfg.dim}d x {cfg.n_layers}L "
          f"({cfg.param_count()/1e9:.2f}B params) slots={n_slots} "
          f"budget={budget/2**30:.1f}GiB",
          file=sys.stderr)

    record = _Record(
        f"decode_tokens_per_sec_llama1b_bf16_bs{n_slots}_1chip", platform)
    if record.counts_only:
        sys.stderr = _LabelsOnly(sys.stderr)
    record.update()  # a parseable line exists from this point

    # ---- M: microservice extras (BASELINE configs 1 and 3) ----------------
    # Quick, before the LLM engine claims HBM. Labeled extras, never the
    # headline — but the reference IS a microservice framework, so its
    # identity gets a measured number too (VERDICT r4 weak #6).
    try:
        if _left() > 240:
            m1 = run_phase_hello()
            print(f"[bench] M hello-world: {m1['http_hello_rps']} req/s "
                  f"({m1['http_hello_errors']} errors) t={_spent():.0f}s",
                  file=sys.stderr)
            record.update(**m1)
    except Exception as exc:  # noqa: BLE001 - extras never sink the record
        print(f"[bench] M hello failed: {exc}", file=sys.stderr)
        record.update(http_hello_error=f"{type(exc).__name__}"[:80])
    # (BERT /embed — BASELINE config 3 — runs LAST: its compile starved
    # the T3 north-star out of the r5 budget when it ran up front)

    rng = np.random.default_rng(0)
    from gofr_tpu.tpu.executor import Executor, enable_compile_cache

    # compiled programs persist where executor.compile_cache_dir says;
    # enabled before the first jit (weight init) so all of them do
    cache_dir = enable_compile_cache()
    params = llama_init(cfg, seed=0)

    from gofr_tpu.metrics import new_metrics_manager
    from gofr_tpu.tpu.device import BATCH_BUCKETS, TPOT_BUCKETS, TTFT_BUCKETS

    manager = new_metrics_manager()
    for hname, buckets in (("app_tpu_ttft_seconds", TTFT_BUCKETS),
                           ("app_tpu_queue_wait_seconds", TTFT_BUCKETS),
                           ("app_tpu_tpot_seconds", TPOT_BUCKETS),
                           ("app_tpu_execute_seconds", TPOT_BUCKETS),
                           ("app_tpu_batch_size", BATCH_BUCKETS)):
        manager.new_histogram(hname, hname, buckets)
    for cname in ("app_tpu_spec_drafted_total", "app_tpu_spec_accepted_total"):
        manager.new_counter(cname, cname)  # T2's acceptance diagnostics

    def _engine_percentiles():
        """p50s from the engine's own histograms (bucket-edge approx):
        decomposes where serving time goes without a profiler attached."""
        out = {}
        for key, hname in (("tpot_p50_ms", "app_tpu_tpot_seconds"),
                           ("execute_p50_ms", "app_tpu_execute_seconds")):
            hist = manager.get(hname)
            if hist is not None and hist.series:
                out[key] = round(hist.percentile(0.5) * 1e3, 2)
        return out

    def _step_segments(eng):
        """Per-segment share of decode-step wall from the step ledger
        (/debug/steps): where the loop thread spends its time. Keyed into
        the headline extras so host-overhead shifts (async D2H, demux
        vectorization, off-loop finishing) show up in the BENCH trajectory,
        not just interactively."""
        try:
            summary = eng.steps.snapshot()["summary"].get("decode")
        except Exception:  # noqa: BLE001 — diagnostics never fail the bench
            return {}
        if not summary or not summary.get("wall_s"):
            return {}
        wall = summary["wall_s"]
        shares = {seg: round(s / wall, 4)
                  for seg, s in summary["segments"].items()}
        segs = {
            "steps": summary["steps"],
            "wall_s": round(wall, 3),
            "shares": shares,
            # the host tax the tentpole attacks, as one number
            "loop_host_share": round(sum(
                shares.get(k, 0.0)
                for k in ("device_sync", "demux", "emit", "host_prep")), 4),
        }
        # WHICH code the host share is: the sampling profiler's top
        # loop-thread stack (tpu/hostprof.py), leaf-most frames — the
        # attribution next to the number, in the same artifact
        try:
            prof = getattr(eng, "hostprof", None)
            top = prof.top_loop_stacks(1) if prof is not None else []
            if top:
                segs["loop_top_stack"] = {
                    "frames": top[0]["stack"].split(";")[-4:],
                    "samples": top[0]["samples"],
                    "loop_samples": prof.snapshot()["threads"]["loop"][
                        "samples"],
                    "overhead_share": prof.snapshot()["overhead"]["share"],
                }
        except Exception:  # noqa: BLE001 — diagnostics never fail the bench
            pass
        return {"step_segments": segs}

    def make_engine(slots, seq, use_cfg, cls=LLMEngine, **extra):
        # block/depth from a sweep on v5e: small blocks turn finished slots
        # over faster; depth 2 hides dispatch latency without inflating the
        # in-flight margin
        eng = cls(params, use_cfg, n_slots=slots, max_seq_len=seq,
                        prefill_buckets=tuple(b for b in prefill_buckets
                                              if b <= seq),
                        decode_block_size=8, pipeline_depth=2, seed=0,
                        budget_bytes=budget or None, metrics=manager,
                        executor=Executor(cache_dir=cache_dir),
                        **extra)
        eng.start()
        try:
            # grow=False: T0 must run at the small boot-time allocation (the
            # r01 measurement condition); T1's warm round grows on demand
            eng.warmup(grow=False)
        except Exception:
            # a started-but-broken engine pins its HBM buffers via the loop
            # thread; the variant loop goes on to build the next one
            eng.stop()
            raise
        return eng

    t_init = time.time()
    engine = make_engine(n_slots, max_seq, cfg)
    # the engine's capacity plan is the source of truth for what serves —
    # sync the record and local sizing to it
    if engine.plan is not None:
        print(f"[bench] {engine.plan.summary()}", file=sys.stderr)
    n_slots, max_seq = engine.n_slots, engine.max_seq_len
    record.rename_slots(engine.n_slots)
    record.update(attn_impl=cfg.attn_impl)
    print(f"[bench] init+warmup {time.time()-t_init:.1f}s t={_spent():.0f}s",
          file=sys.stderr)

    # ---- T0: round-1-comparable decode throughput (short prompts) ---------
    def phase_t0(eng):
        short_prompts = [rng.integers(1, cfg.vocab_size, size=8).tolist()
                         for _ in range(eng.n_slots)]
        return run_phase_throughput(eng, short_prompts, max_new,
                                    rounds=2 if full_run else 1)

    # host sampling profiler rides T0 so the artifact says WHICH frames
    # the loop_host_share was (stopped right after the phase; its
    # measured self-overhead lands in the loop_top_stack extra)
    from gofr_tpu.tpu.hostprof import HostProfiler

    t0_hostprof = HostProfiler(hz=50.0)
    engine.hostprof = t0_hostprof
    t0_hostprof.start()
    tok_s, tokens, elapsed, t0_ttfts = phase_t0(engine)
    print(f"[bench] T0 short-prompt decode: {tokens} tok in {elapsed:.2f}s = "
          f"{tok_s:.1f} tok/s t={_spent():.0f}s", file=sys.stderr)
    # analytic HBM-roofline context: use the cache length the phase
    # actually ran at (it grows during T0 to cover prompt + max_new +
    # pipeline margin)
    roofline_tok_s = _roofline_tok_s(cfg, engine) if on_tpu else 0.0
    t0_hostprof.stop()
    record.update(value=tok_s, count=tokens,
                  t0_elapsed_s=round(elapsed, 2),
                  slots=engine.n_slots,
                  **_engine_percentiles(),
                  **_step_segments(engine),
                  **({"roofline_tok_s": round(roofline_tok_s, 1),
                      "model_gib": round(params_bytes(cfg) / 2**30, 2),
                      "t0_cache_len": engine._cache_len,
                      "roofline_frac": round(tok_s / roofline_tok_s, 3)}
                     if roofline_tok_s else {}))

    # ---- T0v: decode-path variants -----------------------------------------
    # Measure the Pallas streaming read and the int8 cache against the
    # known-good xla-read baseline ON THE SAME WORKLOAD, take the best as
    # the headline engine. Each variant is fenced: a compile failure or OOM
    # records an error and the baseline result stands (the round's number
    # can only improve). Two engines coexist briefly (params are shared,
    # caches are small at the T0 allocation) — the loser stops immediately.
    best_tag, best_tok_s, best_extra = "xla", tok_s, {}
    if full_run and _left() > 700:
        from gofr_tpu.tpu.paging import PagedLLMEngine

        # paged FIRST: it is the llm-server's serving default (PAGED=true),
        # so its number matters most; the dense kernel/int8 variants are
        # the per-row bandwidth levers. prefix_cache stays OFF here: the
        # bench reuses identical prompt lists across warm/measured rounds,
        # so a content-keyed cache would serve ~100% artificial hits and
        # the variant's T1/L numbers would stop measuring decode at all
        variants = [
            ("paged", cfg, dict(cls=PagedLLMEngine, page_size=128)),
            ("kern", dataclasses.replace(cfg, decode_attn="kernel"), {}),
            ("kern_q8", dataclasses.replace(cfg, decode_attn="kernel",
                                            kv_dtype="int8"), {}),
            ("paged_q8", dataclasses.replace(cfg, kv_dtype="int8"),
             dict(cls=PagedLLMEngine, page_size=128)),
        ]
        for vi, (tag, vcfg, vextra) in enumerate(variants):
            # reserve enough budget that the phases BEHIND the variants
            # (T1/L/H and above all T3's 8B boot, gate 420s) still run —
            # skipped variants are visible so a reader can tell "skipped"
            # from "absent"
            if _left() < 700:
                record.update(**{f"t0_{t}_skipped": "budget"
                                 for t, _, _ in variants[vi:]})
                break
            candidate = None
            try:
                candidate = make_engine(n_slots, max_seq, vcfg, **vextra)
                vtok_s, vtokens, velapsed, _ = phase_t0(candidate)
                print(f"[bench] T0[{tag}]: {vtokens} tok in {velapsed:.2f}s "
                      f"= {vtok_s:.1f} tok/s", file=sys.stderr)
                record.update(**{f"t0_{tag}_tok_s": round(vtok_s, 1)})
            except Exception as exc:  # noqa: BLE001 - baseline stands
                print(f"[bench] T0[{tag}] failed: {exc}", file=sys.stderr)
                record.update(**{f"t0_{tag}_error":
                                 f"{type(exc).__name__}: {exc}"[:160]})
                if candidate is not None:
                    try:
                        candidate.stop()
                    except Exception:  # noqa: BLE001
                        pass
                candidate = None
            if candidate is None:
                continue
            if vtok_s > best_tok_s:
                engine.stop()
                engine, cfg = candidate, vcfg
                best_tag, best_tok_s, best_extra = tag, vtok_s, dict(vextra)
            else:
                candidate.stop()
        if best_tag.startswith("paged"):
            # the dense roofline accounting reads engine._cache_len, which
            # the paged engine pins to max_seq_len for admission purposes —
            # per-step reads actually track LIVE pages, so the dense-derived
            # roofline_frac would overstate; keep the baseline's roofline
            # and say so instead of publishing a wrong fraction
            record.update(value=best_tok_s, decode_impl=best_tag,
                          roofline_note=("paged winner: roofline_frac is "
                                         "the dense baseline's"))
        elif best_tag != "xla":
            # ONE locked emission carries the rename + the winning value +
            # its refreshed roofline: the watchdog can never snapshot the
            # new name against the baseline's value or roofline
            roofline = _roofline_tok_s(cfg, engine)
            record.update(value=best_tok_s, decode_impl=best_tag,
                          rename_metric=(("_bf16", "_int8kv")
                                         if cfg.kv_dtype == "int8" else None),
                          roofline_tok_s=round(roofline, 1),
                          t0_cache_len=engine._cache_len,
                          roofline_frac=round(best_tok_s / roofline, 3))
        else:
            record.update(decode_impl=best_tag)
    elif full_run:
        # the whole variant block was skipped: say so (skipped vs absent)
        record.update(t0_variants_skipped="budget")

    # ---- T1: honest mixed-prompt serving throughput -----------------------
    prompts = _prompt_mix(rng, 2 * engine.n_slots, cfg.vocab_size,
                          engine.admission_limit)
    mean_len = sum(len(p) for p in prompts) / len(prompts)
    mixed_tok_s, burst_ttfts = 0.0, t0_ttfts
    if (_left() > 300 or not full_run):
        try:
            mixed_tok_s, tokens, elapsed, burst_ttfts = run_phase_throughput(
                engine, prompts, max_new, rounds=2 if full_run else 1)
            print(f"[bench] T1 mixed-prompt serve: {tokens} tok in {elapsed:.2f}s "
                  f"= {mixed_tok_s:.1f} tok/s (mean prompt {mean_len:.0f}) "
                  f"t={_spent():.0f}s",
                  file=sys.stderr)
            record.update(mixed_prompt_tok_s=round(mixed_tok_s, 1),
                          mean_prompt_len=round(mean_len, 1))
        except Exception as exc:  # noqa: BLE001 - keep T0's record
            print(f"[bench] T1 failed (T0 result preserved): {exc}",
                  file=sys.stderr)
            record.update(t1_error=f"{type(exc).__name__}: {exc}"[:200])
            try:
                engine.stop()
            except Exception:  # noqa: BLE001
                pass
            engine = None
    else:
        record.update(mixed_prompt_skipped="budget")

    # ---- L: TTFT under Poisson arrivals, two operating points -------------
    # The north-star pairs tok/s WITH p50 TTFT: one saturating point hides
    # the tradeoff (an overloaded queue makes TTFT meaningless, a trivial
    # load makes tok/s meaningless). Report a moderate point (30% of burst
    # capacity in TOTAL-token terms — the provisioned-with-headroom setting
    # the <150ms target describes) and a heavy point (70%).
    try:
        if (engine is not None and full_run and mixed_tok_s
                and _left() > 150):
            # Poisson bursts can queue enough arrivals to fuse a
            # K=slots x bucket-512 prefill whose activation temporaries
            # OOMed the r5 chip (the capacity plan accounts buffers, not
            # XLA transients) — cap burst admission from here on. T0/T1
            # ran uncapped: their fused admission IS the measurement.
            engine.max_prefill_batch = 32
            # capacity in requests/s from the burst measurement, discounted
            # by the prefill share of each request's total token work
            cap_rps = mixed_tok_s / max_new
            for tag, frac in (("moderate", 0.3), ("heavy", 0.7)):
                if _left() < 90:
                    record.update(**{f"ttft_{tag}_skipped": "budget"})
                    continue
                point = _latency_point(engine, prompts, max_new,
                                       frac * cap_rps,
                                       duration_s=min(20.0, _left() - 60),
                                       rng=rng)
                print(f"[bench] L[{tag}] @{point['rate_rps']}rps: "
                      f"{point['out_tok_s']} tok/s out, "
                      f"ttft p50={point['ttft_p50_ms']}ms "
                      f"p99={point['ttft_p99_ms']}ms "
                      f"(queue-wait p50={point['queue_wait_p50_ms']}ms, "
                      f"n={point['n']}) t={_spent():.0f}s", file=sys.stderr)
                record.update(**{f"ttft_{tag}": point})
                if tag == "moderate":
                    # headline TTFT fields keep their round-over-round names;
                    # the moderate point is the SLO-relevant one
                    record.update(ttft_p50_ms=point["ttft_p50_ms"],
                                  ttft_p99_ms=point["ttft_p99_ms"],
                                  ttft_queue_wait_p50_ms=point["queue_wait_p50_ms"],
                                  ttft_arrival_rps=point["rate_rps"],
                                  **_engine_percentiles())
        elif burst_ttfts:
            p50, p99 = _percentiles(burst_ttfts)
            record.update(ttft_p50_ms=round(p50 * 1e3, 1),
                          ttft_p99_ms=round(p99 * 1e3, 1),
                          ttft_arrival="burst")
            print(f"[bench] L ttft@burst: p50={p50*1e3:.0f}ms p99={p99*1e3:.0f}ms",
                  file=sys.stderr)
        else:
            record.update(ttft_skipped="no samples")
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] L failed (earlier results preserved): {exc}",
              file=sys.stderr)
        record.update(l_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- H: the HTTP/SSE boundary around the live engine ------------------
    # Every phase above measures engine.submit() directly; this one wraps
    # the SAME engine in the real llm-server app and stamps TTFT at the
    # moment the CLIENT reads its first SSE event — handler threading, the
    # SSE encoder, and chunked socket writes are all inside the clock
    # (VERDICT r4 missing #2). Burst arrival, so compare against the L
    # burst point, not the Poisson ones.
    try:
        if engine is not None and _left() > 150:
            # slot-matched stream count: every stream admits immediately,
            # so boundary TTFT isolates the SERVING-STACK overhead on top
            # of the engine's own burst TTFT instead of queue wait
            h = run_phase_http(engine, n_streams=engine.n_slots,
                               max_new=min(16, max_new), prompt_chars=96,
                               rng=rng)
            engine_p50 = record.result["extras"].get("ttft_p50_ms")
            if engine_p50 is not None:
                h["http_minus_engine_ttft_p50_ms"] = round(
                    h["http_ttft_p50_ms"] - engine_p50, 1)
            print(f"[bench] H http-boundary: {h['http_tok_s']} tok/s, "
                  f"ttft p50={h['http_ttft_p50_ms']}ms "
                  f"p99={h['http_ttft_p99_ms']}ms "
                  f"({h['http_streams']} streams, {h['http_errors']} errors)",
                  file=sys.stderr)
            record.update(**h)
        elif full_run:
            record.update(http_skipped=("engine lost" if engine is None
                                        else "budget"))
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] H failed (earlier results preserved): {exc}",
              file=sys.stderr)
        record.update(http_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- KV: tiered prefix cache — TTFT on tier hit vs miss (labeled extra)
    # The tentpole claim is "a re-sent prefix pays an H2D copy instead of a
    # re-prefill even after HBM pressure evicted it". Measure exactly that:
    # boot a SMALL paged engine (tiny page pool so eviction is organic, host
    # tier on), TTFT a cold trunk (miss = full prefill), push filler traffic
    # through until the trunk's pages spill to host RAM, then re-send the
    # trunk with a fresh tail (hit = restore + tail-only prefill). Shares
    # params with the live engine, same as the T0v candidates.
    try:
        if full_run and _left() > 300:
            from gofr_tpu.tpu.paging import PagedLLMEngine

            kv_ps = 64
            kv_eng = make_engine(4, min(1024, max_seq), cfg,
                                 cls=PagedLLMEngine, page_size=kv_ps,
                                 n_pages=48, prefix_cache=True,
                                 kv_host_tier_bytes=256 << 20)
            try:
                def _kv_ttft(toks):
                    req = kv_eng.submit(toks, max_new_tokens=8,
                                        temperature=0.0)
                    req.result(timeout_s=TOKEN_TIMEOUT_S)
                    return (req.first_token_at - req.enqueued_at) * 1e3

                trunk = rng.integers(1, cfg.vocab_size,
                                     size=6 * kv_ps).tolist()

                def _tail():
                    return rng.integers(1, cfg.vocab_size, size=16).tolist()

                # warm the prefill bucket + decode programs off the clock
                _kv_ttft(rng.integers(1, cfg.vocab_size,
                                      size=len(trunk) + 16).tolist())
                ttft_miss_ms = _kv_ttft(trunk + _tail())
                # filler rounds cycle the 48-page pool so the idle trunk
                # pages evict -> spill; stop as soon as the spill shows up
                for _ in range(6):
                    fill = [kv_eng.submit(
                        rng.integers(1, cfg.vocab_size,
                                     size=6 * kv_ps + 16).tolist(),
                        max_new_tokens=8, temperature=0.0)
                        for _ in range(4)]
                    for r in fill:
                        r.result(timeout_s=TOKEN_TIMEOUT_S)
                    if kv_eng._kv_spilled >= 6:
                        break
                restored_before = kv_eng._kv_restored
                ttft_hit_ms = _kv_ttft(trunk + _tail())
                restored = kv_eng._kv_restored - restored_before
                tokens_avoided = restored * kv_ps
                # dominant prefill cost is the 2*params matmul work per
                # token; attention's quadratic term is small at this length
                gflops_avoided = 2 * cfg.param_count() * tokens_avoided / 1e9
                tier_stats = kv_eng.kv_tier.stats()
                print(f"[bench] KV tier: ttft miss {ttft_miss_ms:.1f}ms vs "
                      f"hit {ttft_hit_ms:.1f}ms (restored {restored} pages, "
                      f"{tokens_avoided} prefill tok avoided, "
                      f"spilled {kv_eng._kv_spilled}) t={_spent():.0f}s",
                      file=sys.stderr)
                record.update(
                    kv_tier_ttft_miss_ms=round(ttft_miss_ms, 1),
                    kv_tier_ttft_hit_ms=round(ttft_hit_ms, 1),
                    kv_tier_ttft_win_ms=round(ttft_miss_ms - ttft_hit_ms, 1),
                    kv_tier_restored_pages=restored,
                    kv_tier_spilled_pages=kv_eng._kv_spilled,
                    kv_tier_prefill_tokens_avoided=tokens_avoided,
                    kv_tier_prefill_gflops_avoided=round(gflops_avoided, 1),
                    kv_tier_host_hits=tier_stats["hits"],
                    kv_tier_host_used_bytes=tier_stats["used_bytes"])
            finally:
                kv_eng.stop()
        elif full_run:
            record.update(kv_tier_skipped="budget")
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] KV tier phase failed (earlier results preserved): "
              f"{exc}", file=sys.stderr)
        record.update(kv_tier_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- DG: disaggregated prefill/decode — TPOT under prefill churn ------
    # The split's before/after evidence: per-token latency of decode-heavy
    # victim streams while prompt churn runs concurrently, measured
    # client-side the same way on both arms. Colocated interleaves every
    # churn prompt's prefill into the victims' decode loop; the split
    # pair's decode pool never dispatches one (asserted against its step
    # ledger below), so churn costs only kv_handoff admissions.
    try:
        if full_run and _left() > 300:
            from gofr_tpu.tpu.disagg import DisaggRouter
            from gofr_tpu.tpu.paging import PagedLLMEngine

            dg_seq = min(512, max_seq)
            dg_bucket = max(b for b in prefill_buckets if b <= dg_seq)
            churn_len = max(dg_bucket - 16, 8)

            def _victim_tpots_ms(submit_fn):
                """Mean client-observed TPOT of 3 victim streams decoding
                under continuous 2-wide prompt churn."""
                stop = threading.Event()

                def _churn():
                    while not stop.is_set():
                        batch = []
                        for _ in range(2):
                            try:
                                batch.append(submit_fn(
                                    rng.integers(
                                        1, cfg.vocab_size,
                                        size=churn_len).tolist(),
                                    max_new_tokens=2, temperature=0.0))
                            except Exception:  # noqa: BLE001 - shed = wait
                                time.sleep(0.05)
                        for r in batch:
                            try:
                                r.result(timeout_s=TOKEN_TIMEOUT_S)
                            except Exception:  # noqa: BLE001
                                pass

                def _stream(req, out, i):
                    t_first = t_last = None
                    n = 0
                    for _tok in req.stream(timeout_s=TOKEN_TIMEOUT_S):
                        t_last = time.monotonic()
                        if t_first is None:
                            t_first = t_last
                        n += 1
                    if n >= 2:
                        out[i] = (t_last - t_first) / (n - 1) * 1e3

                churner = threading.Thread(target=_churn, daemon=True)
                churner.start()
                time.sleep(0.3)  # churn in flight before victims arrive
                victims = [submit_fn(
                    rng.integers(1, cfg.vocab_size, size=8).tolist(),
                    max_new_tokens=32, temperature=0.0) for _ in range(3)]
                tpots = [None] * len(victims)
                streamers = [threading.Thread(target=_stream,
                                              args=(v, tpots, i),
                                              daemon=True)
                             for i, v in enumerate(victims)]
                for s in streamers:
                    s.start()
                for s in streamers:
                    s.join(timeout=TOKEN_TIMEOUT_S)
                stop.set()
                churner.join(timeout=TOKEN_TIMEOUT_S)
                good = [t for t in tpots if t is not None]
                if not good:
                    raise RuntimeError("no victim stream finished")
                return sum(good) / len(good)

            colo = make_engine(6, dg_seq, cfg, cls=PagedLLMEngine,
                               page_size=64)
            try:
                tpot_colo = _victim_tpots_ms(colo.submit)
            finally:
                colo.stop()
            dg_pre = make_engine(2, dg_seq, cfg, cls=PagedLLMEngine,
                                 page_size=64, disagg_role="prefill")
            dg_dec = make_engine(6, dg_seq, cfg, cls=PagedLLMEngine,
                                 page_size=64, disagg_role="decode")
            router = DisaggRouter(dg_pre, dg_dec, metrics=manager)
            router.start()
            try:
                tpot_disagg = _victim_tpots_ms(router.submit)
                snap = dg_dec.steps.snapshot()
                decode_pool_prefills = sum(
                    1 for s in snap["recent"] if s["phase"] == "prefill")
                dg_handoffs = dg_pre.handoffs_total
                dg_fallbacks = (router.fallbacks_total
                                + dg_pre.handoff_fallbacks_total
                                + dg_dec.handoff_fallbacks_total)
            finally:
                router.stop()
                dg_pre.stop()
                dg_dec.stop()
            print(f"[bench] DG interference: colocated TPOT "
                  f"{tpot_colo:.2f}ms vs disagg {tpot_disagg:.2f}ms "
                  f"({dg_handoffs} handoffs, {dg_fallbacks} fallbacks, "
                  f"{decode_pool_prefills} decode-pool prefill steps) "
                  f"t={_spent():.0f}s", file=sys.stderr)
            record.update(
                tpot_interference_ms_colocated=round(tpot_colo, 2),
                tpot_interference_ms_disagg=round(tpot_disagg, 2),
                disagg_tpot_win_ms=round(tpot_colo - tpot_disagg, 2),
                disagg_handoffs=dg_handoffs,
                disagg_fallbacks=dg_fallbacks,
                disagg_decode_pool_prefill_steps=decode_pool_prefills)
        elif full_run:
            record.update(disagg_skipped="budget")
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] DG phase failed (earlier results preserved): "
              f"{exc}", file=sys.stderr)
        record.update(disagg_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- T2: structured-text speculation (labeled extra, never headline) --
    # Speculative decoding cannot help the random-token phases (no self-
    # repetition to draft from), so measure it on an honest STRUCTURED
    # workload: prompts built by tiling a motif, the shape of RAG answers /
    # code edits. The same workload runs on the current engine first so the
    # comparison is same-hardware same-shapes.
    try:
        # 900s floor: T2 is a labeled extra that boots a second engine
        # (minutes when nothing is cached), while T3 behind it is the
        # NORTH-STAR headline (8B int8 on-chip) needing its 420s gate plus
        # runtime — on the driver's default 1500s budget T2 must yield
        if engine is not None and full_run and _left() > 900:
            def motif_prompts(n):
                out = []
                for _ in range(n):
                    motif = rng.integers(1, cfg.vocab_size, size=24).tolist()
                    out.append((motif * 8)[:engine.admission_limit])
                return out

            sprompts = motif_prompts(engine.n_slots)
            plain_tok_s, _, _, _ = run_phase_throughput(
                engine, sprompts, max_new, rounds=1)
            engine.stop()
            engine = None
            # speculation composes with the kernel read but not (yet) the
            # int8 cache: strip kv_dtype if a q8 variant won T0v. Same
            # ENGINE FAMILY as the plain side (best_extra carries the
            # paged winner's class/page kwargs) — otherwise the plain-vs-
            # spec delta would conflate paged-vs-dense with speculation
            spec_cfg = dataclasses.replace(cfg, kv_dtype=None)
            spec_eng = make_engine(n_slots, max_seq, spec_cfg,
                                   speculative_tokens=4, **best_extra)
            # the L phase capped the plain engine's burst admission; the
            # comparison is only about speculation if both sides admit
            # under the same policy (and the uncapped K=slots x bucket-512
            # prefill re-risks the OOM the cap exists for)
            spec_eng.max_prefill_batch = 32
            try:
                spec_tok_s, _, _, _ = run_phase_throughput(
                    spec_eng, sprompts, max_new, rounds=1)
                drafted = manager.get("app_tpu_spec_drafted_total")
                accepted = manager.get("app_tpu_spec_accepted_total")
                d_total = sum(drafted.series.values()) if drafted else 0
                a_total = sum(accepted.series.values()) if accepted else 0
                print(f"[bench] T2 structured: plain {plain_tok_s:.1f} vs "
                      f"spec {spec_tok_s:.1f} tok/s "
                      f"(accepted {a_total:.0f}/{d_total:.0f} drafts)",
                      file=sys.stderr)
                record.update(
                    t2_structured_plain_tok_s=round(plain_tok_s, 1),
                    t2_structured_spec_tok_s=round(spec_tok_s, 1),
                    t2_spec_accept_rate=round(a_total / d_total, 3)
                    if d_total else 0.0)
            finally:
                spec_eng.stop()
        elif full_run:
            record.update(t2_skipped=("engine lost in an earlier phase"
                                      if engine is None
                                      else "budget reserved for T3"))
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] T2 failed (earlier results preserved): {exc}",
              file=sys.stderr)
        record.update(t2_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- T3: the NORTH-STAR model — Llama-3-8B, int8 weights, one chip ----
    # BASELINE config 4 names Llama-3-8B; its bf16 weights (~15 GiB) cannot
    # fit one 16 GiB v5e chip at all, so this stage serves the int8-weight
    # tree (llama_init_quantized, ~8 GiB, generated leaf-wise so the float
    # tree never exists) with the int8 KV cache and Pallas kernel read. A
    # valid measurement REPLACES the 1B headline — the target model's
    # number is the round's number; the 1B results stay in extras.
    try:
        if full_run and _left() > 420:
            if engine is not None:
                engine.stop()
                engine = None
            params = None  # drop the 1B tree before the 8B init  # noqa: F841
            import gc

            gc.collect()
            from gofr_tpu.models.llama import (llama_init_quantized,
                                               params_nbytes)

            cfg8 = dataclasses.replace(
                LlamaConfig.llama3_8b(), attn_impl=cfg.attn_impl,
                decode_attn="kernel", kv_dtype="int8")
            t8 = time.time()
            params8 = llama_init_quantized(cfg8, seed=0)
            w_bytes = params_nbytes(params8)
            print(f"[bench] T3 8B int8 weights: {w_bytes/2**30:.2f} GiB "
                  f"materialized in {time.time()-t8:.1f}s", file=sys.stderr)
            eng8 = LLMEngine(params8, cfg8, n_slots=64, max_seq_len=512,
                             prefill_buckets=(16, 64, 128, 256),
                             decode_block_size=8, pipeline_depth=2, seed=0,
                             budget_bytes=budget or None, metrics=manager,
                             executor=Executor(cache_dir=cache_dir))
            eng8.start()
            try:
                eng8.warmup(grow=False)
                print(f"[bench] T3 engine up: slots={eng8.n_slots} "
                      f"seq={eng8.max_seq_len} "
                      f"(init+warmup {time.time()-t8:.1f}s) t={_spent():.0f}s", file=sys.stderr)
                prompts8 = [rng.integers(1, cfg8.vocab_size, size=8).tolist()
                            for _ in range(eng8.n_slots)]
                tok8, tokens8, el8, ttfts8 = run_phase_throughput(
                    eng8, prompts8, max_new, rounds=2)
                per_step = (w_bytes
                            + kv_cache_bytes(cfg8, eng8.n_slots,
                                             eng8._cache_len, dtype="int8")
                            + kv_scales_bytes(cfg8, eng8.n_slots,
                                              eng8._cache_len))
                roof8 = V5E_HBM_GBPS * 1e9 * eng8.n_slots / per_step
                p50_8, p99_8 = _percentiles(ttfts8)
                print(f"[bench] T3 8B decode: {tokens8} tok in {el8:.2f}s = "
                      f"{tok8:.1f} tok/s (roofline {roof8:.0f}, "
                      f"frac {tok8/roof8:.3f}) t={_spent():.0f}s", file=sys.stderr)
                record.update(
                    value=tok8,
                    set_metric=(f"decode_tokens_per_sec_llama3_8b_int8w"
                                f"_bs{eng8.n_slots}_1chip"),
                    headline_model="llama3-8b int8-weights int8-kv kernel",
                    llama1b_tok_s=round(best_tok_s, 1),
                    t3_model_gib=round(w_bytes / 2**30, 2),
                    t3_roofline_tok_s=round(roof8, 1),
                    t3_roofline_frac=round(tok8 / roof8, 3),
                    t3_cache_len=eng8._cache_len,
                    t3_slots=eng8.n_slots,
                    t3_ttft_burst_p50_ms=round(p50_8 * 1e3, 1))
                # the config-4 pair is (tok/s, p50 TTFT at a FEASIBLE
                # operating point): measure a moderate Poisson point on
                # the target model and make it the headline TTFT
                if _left() > 120:
                    # Poisson bursts on the 8B model get the same
                    # admission cap as the 1B L phase — a queued burst
                    # fusing K=slots x bucket-256 prefill activations is
                    # the OOM class the cap exists for
                    eng8.max_prefill_batch = 16
                    mix8 = _prompt_mix(rng, 2 * eng8.n_slots,
                                       cfg8.vocab_size,
                                       eng8.admission_limit)
                    point = _latency_point(
                        eng8, mix8, max_new, 0.3 * tok8 / max_new,
                        duration_s=min(20.0, _left() - 60), rng=rng)
                    print(f"[bench] T3 L @{point['rate_rps']}rps: "
                          f"ttft p50={point['ttft_p50_ms']}ms "
                          f"p99={point['ttft_p99_ms']}ms", file=sys.stderr)
                    record.update(t3_ttft_moderate=point,
                                  ttft_p50_ms=point["ttft_p50_ms"],
                                  ttft_p99_ms=point["ttft_p99_ms"],
                                  ttft_queue_wait_p50_ms=point[
                                      "queue_wait_p50_ms"],
                                  ttft_arrival_rps=point["rate_rps"])
                # HTTP boundary around the NORTH-STAR engine: the serving
                # stack measured on the model the headline claims
                if _left() > 150:
                    h8 = run_phase_http(eng8,
                                        n_streams=min(32, eng8.n_slots),
                                        max_new=min(16, max_new),
                                        prompt_chars=96, rng=rng)
                    print(f"[bench] T3 http-boundary: {h8['http_tok_s']} "
                          f"tok/s, ttft p50={h8['http_ttft_p50_ms']}ms",
                          file=sys.stderr)
                    record.update(**{f"t3_{k}": v for k, v in h8.items()})
            finally:
                try:
                    eng8.stop()
                except Exception:  # noqa: BLE001
                    pass
                engine = None
        elif full_run:
            record.update(t3_skipped="budget")
    except Exception as exc:  # noqa: BLE001 - the 1B record stands
        print(f"[bench] T3 failed (earlier results preserved): {exc}",
              file=sys.stderr)
        record.update(t3_error=f"{type(exc).__name__}: {exc}"[:200])

    if engine is not None:
        try:
            engine.stop()
        except Exception:  # noqa: BLE001
            pass
        engine = None

    # ---- FL: fleet router — affinity vs round-robin TTFT (labeled extra) --
    # After T3 on purpose: the headline engines are stopped, so the two
    # debug-preset replica boots cannot starve or OOM the north-star
    # phases. Measures what the router tier buys: warm session turns
    # landing on the replica that already holds the prefix pages.
    try:
        if full_run and _left() > 180:
            fl = run_phase_fleet()
            print(f"[bench] FL fleet: round-robin warm TTFT "
                  f"{fl['fleet_ttft_rr_ms']:.1f}ms vs affinity "
                  f"{fl['fleet_ttft_affinity_ms']:.1f}ms "
                  f"(hit rate {fl['fleet_affinity_hit_rate']}) "
                  f"t={_spent():.0f}s", file=sys.stderr)
            record.update(**fl)
        elif full_run:
            record.update(fleet_skipped="budget")
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] FL phase failed (earlier results preserved): "
              f"{exc}", file=sys.stderr)
        record.update(fleet_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- QS: QoS plane — interactive TTFT under a saturating batch lane ---
    # After FL for the same reason: one debug-preset boot on a freed host.
    # Measures what the class bands buy: how much interactive TTFT
    # degrades when the batch lane keeps every spare slot decoding.
    try:
        if full_run and _left() > 180:
            qs = run_phase_qos()
            print(f"[bench] QS qos: interactive TTFT quiet "
                  f"{qs['qos_interactive_ttft_quiet_ms']:.1f}ms vs "
                  f"saturated {qs['qos_interactive_ttft_saturated_ms']:.1f}"
                  f"ms (protect delta "
                  f"{qs['qos_interactive_ttft_protect_ms']:.1f}ms) "
                  f"t={_spent():.0f}s", file=sys.stderr)
            record.update(**qs)
        elif full_run:
            record.update(qos_skipped="budget")
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] QS phase failed (earlier results preserved): "
              f"{exc}", file=sys.stderr)
        record.update(qos_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- OL: open-loop loadgen — offered-vs-served over the router --------
    # After QS for the same freed-host reason. The one phase whose
    # arrival process does NOT slow down when the system does: dispatch
    # lag proves the schedule held, the scorecard says what the fleet
    # did with the offered load.
    try:
        if full_run and _left() > 150:
            ol = run_phase_loadgen()
            print(f"[bench] OL loadgen: {ol['loadgen_ok']}"
                  f"/{ol['loadgen_offered']} ok, ttft p95 "
                  f"{ol['loadgen_ttft_p95_ms']}ms, worst lag "
                  f"{ol['loadgen_worst_lag_ms']}ms, slo_met="
                  f"{ol['loadgen_slo_met']} t={_spent():.0f}s",
                  file=sys.stderr)
            record.update(**ol)
        elif full_run:
            record.update(loadgen_skipped="budget")
    except Exception as exc:  # noqa: BLE001 - keep earlier phases' record
        print(f"[bench] OL phase failed (earlier results preserved): "
              f"{exc}", file=sys.stderr)
        record.update(loadgen_error=f"{type(exc).__name__}: {exc}"[:200])

    # ---- M2: BERT /embed over gRPC (BASELINE config 3, labeled extra) -----
    # Last on purpose: every LLM engine is stopped, so its HBM is free, and
    # a slow remote compile here can no longer starve the headline phases.
    try:
        if _left() > 90:
            m2 = run_phase_bert(on_tpu,
                                per_thread=5 if on_tpu else 25)
            print(f"[bench] M bert-embed: {m2['bert_embed_rps']} req/s "
                  f"({m2['bert_embed_errors']} errors) t={_spent():.0f}s",
                  file=sys.stderr)
            record.update(**m2)
    except Exception as exc:  # noqa: BLE001 - extras never sink the record
        print(f"[bench] M bert failed: {exc}", file=sys.stderr)
        record.update(bert_embed_error=f"{type(exc).__name__}"[:80])


if __name__ == "__main__":
    main()
