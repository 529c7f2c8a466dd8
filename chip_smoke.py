"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one chip: the main serving path
    python chip_smoke.py --chips 4   four chips: tensor-parallel serving only

One chip. Llama-3.2-1B widths (LlamaConfig.llama1b: vocab 128,256, dim 2048,
16 layers, 32 Q / 8 KV heads of 64, FFN 8192, bf16; random weights from seed
0) are served by examples/llm-server the way a user starts it: its own
configs/.env under the settings in FULL below, build_app() -> build_engine()
-> PagedLLMEngine -> HTTP. It answers a unary POST /generate, an SSE stream,
a prompt that fills the largest prefill bucket and two pages, a repeated
prompt that must hit the prefix cache, and a burst whose second wave is
admitted into a running decode; then it shuts down through app.shutdown()
(drain + stop). Before serving, the kernels are compared with their plain
references on the chip at the same widths.

Four chips (--chips 4; nothing of the above runs). The same model is served
on a tp=4 mesh (TP_SHARDS=4) and on one device; first-step logits and greedy
tokens are compared, and the model is shown to be spread over the devices.

It FAILS — non-zero exit, no final line — when JAX finds no TPU, when any
request fails or returns no token, when a served program lacks its Pallas
kernel or copies the page pool, when a program compiles after warm-up, when
a kernel and its reference disagree, or when any phase raises. Nothing here
falls back to the CPU. A run is a smoke run, not a performance record: the
seconds it prints are set-up costs to plan chip calls by.

Every line it prints is one JSON object. The last one is
{"ok": true, "device": {"platform", "kind", "count"}} as JAX reports the
device; the line before it is the summary.

One process does everything: the chip belongs to one process at a time, and
one process can drive all four chips of a host.
"""

import argparse
import dataclasses
import faulthandler
import http.client
import importlib.util
import json
import os
import random
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
SEED = 0

# kernel vs reference, both from the same bf16 (or int8) inputs, reference in
# float32 at the highest matmul precision: the kernel rounds probabilities
# to bf16 before its second dot and its output to bf16, each 2^-9 relative on
# values of order 1 — 3e-2 absolute is several times that and far below
# what a wrong page, head or mask would show (order 1)
KERNEL_ATOL = 3e-2
# tp=4 vs one device: the same bf16 model with its row-parallel sums taken in
# another order. Logits of a random 16-layer model are of order 1; a sharding
# fault moves them by their own size, rounding by a few percent of it
TP_LOGIT_RTOL = 5e-2
TP_TOKENS = 8                  # greedy tokens compared per prompt


@dataclasses.dataclass(frozen=True)
class Size:
    """What a run is sized for. FULL is what the command line runs; the
    tests drive the same phases at TINY on the CPU (counts only)."""
    preset: str
    max_batch: int
    max_seq_len: int
    buckets: str
    wave1: int                 # streams decoding when wave 2 arrives
    wave1_tokens: int          # what each of them generates
    wave2: int
    tp: int                    # mesh size of the --chips 4 path


# 64 slots x 2048 tokens: the default pool is 64*16+1 pages of 128 tokens,
# 4 GiB of K+V beside 2.8 GiB of weights on a 16 GiB chip. The .env's own
# 8 x 512 is a CI size. WARMUP=wide so that every fused-admission width a
# burst can ask for is compiled before the first request.
FULL = Size("llama1b", 64, 2048, "16,32,64,128,256", wave1=8,
            wave1_tokens=96, wave2=24, tp=4)
# wave 1 at TINY runs 12 decode blocks where 96 tokens were 6: on a CPU
# shared with five other test workers wave 2's HTTP threads must still find
# it decoding
TINY = Size("debug", 4, 256, "16,256", wave1=2, wave1_tokens=192, wave2=4,
            tp=2)

PAGE = 128                     # llm-server's PAGE_SIZE default
LONG_PROMPT = 200              # bytes: the 256 bucket, two pages
REPEAT_PROMPT = 139            # +BOS = 140 tokens: one shared page + a
#                                12-token tail in the smallest bucket
MAX_NEW = 24


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _load_llm_server():
    path = os.path.join(ROOT, "examples", "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location("chip_smoke_llm_server",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _config(size: Size, platform: str, **extra):
    """The example's own .env under this run's settings — what EnvFile
    gives a user who exports them and starts main.py."""
    from gofr_tpu.config import EnvFile

    settings = {
        "MODEL_PRESET": size.preset, "TPU_PLATFORM": platform,
        "MAX_BATCH": str(size.max_batch),
        "MAX_SEQ_LEN": str(size.max_seq_len),
        "PREFILL_BUCKETS": size.buckets,
        # flash prefill, as the last on-chip bench session ran it: the
        # served prefill programs then hold a Pallas kernel to check for
        "ATTN_IMPL": "flash", "WARMUP": "wide",
        "HTTP_PORT": "0", "METRICS_PORT": "0", "GRPC_PORT": "0",
        "LOG_LEVEL": "WARN",
    }
    settings.update(extra)
    return EnvFile(os.path.join(ROOT, "examples", "llm-server", "configs"),
                   environ={**os.environ, **settings})


def _text(rng: random.Random, n_bytes: int) -> str:
    return "".join(rng.choice("abcdefghijklmnopqrstuvwxyz ")
                   for _ in range(n_bytes))


# -- HTTP clients --------------------------------------------------------------
def _post(port: int, body: dict, timeout: float = 180.0) -> dict:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        check(resp.status in (200, 201),
              f"POST /generate -> {resp.status}: {raw[:300]!r}")
        return json.loads(raw)["data"]
    finally:
        conn.close()


def _get(port: int, path: str, timeout: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        raw = resp.read()
        check(resp.status == 200, f"GET {path} -> {resp.status}")
        return json.loads(raw)
    finally:
        conn.close()


def _stream(port: int, prompt: str, max_tokens: int, out: dict,
            timeout: float = 180.0) -> None:
    """One SSE client. Records when each token event arrived; any failure
    lands in out["error"] for the caller to raise on."""
    out.update(events=[], tokens=0, done=False)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        conn.request("POST", "/generate",
                     body=json.dumps({"prompt": prompt, "stream": True,
                                      "max_tokens": max_tokens}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            out["error"] = f"status {resp.status}: {resp.read()[:200]!r}"
            return
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
                payload = json.loads(event[6:])
                if payload.get("done"):
                    out.update(done=True, tokens=payload["tokens"])
                else:
                    out["events"].append(time.monotonic())
        conn.close()
        if not out["done"]:
            out["error"] = "stream ended without its done event"
    except Exception as exc:  # noqa: BLE001 - reported, then raised on
        out["error"] = f"{type(exc).__name__}: {exc}"
    finally:
        out["finished_at"] = time.monotonic()


def _dump_debug(port: int) -> None:
    """An engine that stops answering after a clean warm-up is an engine
    bug to find, not a device to wait for: keep what the loop was doing."""
    os.makedirs(OUT_DIR, exist_ok=True)
    for path in ("/debug/steps", "/debug/hostprof", "/debug/requests",
                 "/debug/engine"):
        try:
            body = _get(port, path, timeout=10.0)
        except Exception as exc:  # noqa: BLE001 - dump what can be had
            body = {"error": f"{type(exc).__name__}: {exc}"}
        name = "smoke_fail" + path.replace("/", "_") + ".json"
        with open(os.path.join(OUT_DIR, name), "w") as fp:
            json.dump(body, fp)


# -- phases ----------------------------------------------------------------------
def check_native() -> dict:
    from gofr_tpu import native

    status = native.status()
    check(status["native"] or not status["toolchain"],
          f"a C++ toolchain is here and the native helpers did not build "
          f"or load: {status['error']}")
    return {"native_helpers": "built" if status["native"]
            else "python fallback (no toolchain)"}


class JaxCacheCounter:
    """Hits and misses of JAX's persistent compilation cache, from its own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def check_kernels() -> dict:
    """Kernel against plain reference ON THIS DEVICE at llama1b widths:
    the paged read over bf16 and int8 pools, the paged write (exact), and
    flash prefill against the XLA attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.ops.paged_attention import quantize_kv
    from gofr_tpu.ops.flash_attention import (attention_reference,
                                              flash_attention)
    from gofr_tpu.ops.paged_attention import (block_tail, paged_attention,
                                              paged_attention_in_block,
                                              paged_attention_reference,
                                              paged_flush_block,
                                              paged_write_decode, tail_put)

    H, Hkv, dh, L, P, NP = 32, 8, 64, 2, 40, 4
    lengths = jnp.asarray([1, 100, 128, 129, 255, 256, 300, 512], jnp.int32)
    B = lengths.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    q = jax.random.normal(keys[0], (B, H, dh), jnp.bfloat16)
    k_pool = jax.random.normal(keys[1], (L, P, Hkv, dh, PAGE), jnp.bfloat16)
    v_pool = jax.random.normal(keys[2], (L, P, Hkv, dh, PAGE), jnp.bfloat16)
    # every row its own pages, none the garbage page 0
    table = (1 + jnp.arange(B * NP, dtype=jnp.int32)).reshape(B, NP)
    layer = jnp.int32(1)
    errors = {}

    def max_err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_attention_reference)(
            q, k_pool[1], v_pool[1], table, lengths)
    out = jax.jit(lambda *a: paged_attention(*a, layer=layer))(
        q, k_pool, v_pool, table, lengths)
    errors["paged_attention_bf16"] = max_err(out, ref)

    k8, ks = quantize_kv(k_pool, axis=-2)
    v8, vs = quantize_kv(v_pool, axis=-2)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_attention_reference)(
            q, k8[1], v8[1], table, lengths, ks[1], vs[1])
    out = jax.jit(lambda *a: paged_attention(*a, layer=layer))(
        q, k8, v8, table, lengths, ks, vs)
    errors["paged_attention_int8"] = max_err(out, ref)

    # the write: the kernel's page read-modify-write against the plain
    # per-token column write, which must leave the same pools
    new_k = jax.random.normal(keys[3], (B, Hkv, dh), jnp.bfloat16)
    new_v = jax.random.normal(keys[4], (B, Hkv, dh), jnp.bfloat16)
    positions = lengths - 1
    pages = table[jnp.arange(B), positions // PAGE]
    want_k = k_pool.at[1, pages, :, :, positions % PAGE].set(new_k)
    want_v = v_pool.at[1, pages, :, :, positions % PAGE].set(new_v)
    got_k, got_v = jax.jit(
        lambda *a: paged_write_decode(*a, layer=layer))(
            k_pool, v_pool, new_k, new_v, table, positions)
    errors["paged_write_decode"] = max(max_err(got_k, want_k),
                                       max_err(got_v, want_v))
    check(errors["paged_write_decode"] == 0.0,
          f"paged_write_decode is not exact: {errors}")

    # a decode block's tail: 8 tokens a row in every layer (two rows cross
    # into their next page, one row holds no request); the eighth put by
    # the read itself, which attends pages and tail: against the reference
    # on a layer that had them written by columns; then flushed into the
    # pages: the same columns, exactly
    starts = jnp.asarray([1, 100, 124, 129, 250, 256, 300, 500], jnp.int32)
    live = jnp.arange(B) != 3
    block_k = jax.random.normal(keys[3], (8, B, Hkv, dh), jnp.bfloat16)
    block_v = jax.random.normal(keys[4], (8, B, Hkv, dh), jnp.bfloat16)
    want_k, want_v = k_pool, v_pool
    tail = block_tail(k_pool, B, 8)
    for t in range(8):
        at = starts + t
        pages = jnp.where(live, table[jnp.arange(B), at // PAGE], 0)
        for l in range(L):
            want_k = want_k.at[l, pages, :, :, at % PAGE].set(block_k[t])
            want_v = want_v.at[l, pages, :, :, at % PAGE].set(block_v[t])
            if (l, t) != (1, 7):     # the read below puts this one itself
                tail = tail_put(*tail, block_k[t], block_v[t], l, t)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(paged_attention_reference)(
            q, want_k[1], want_v[1], table, jnp.where(live, starts + 8, 0))
    out, *tail = jax.jit(lambda q, k, v, kt, vt: paged_attention_in_block(
        q, block_k[7], block_v[7], k, v, kt, vt, table,
        jnp.where(live, starts, 0), jnp.where(live, 8, 0), layer=layer))(
            q, k_pool, v_pool, *tail)
    errors["paged_attention_tail"] = max_err(out, ref)
    got_k, got_v = jax.jit(lambda k, v, kt, vt: paged_flush_block(
        k, v, kt, vt, table, starts, jnp.where(live, 8, 0)))(
            k_pool, v_pool, *tail)
    errors["paged_flush_block"] = max(      # page 0: the idle row's junk
        max_err(got_k[:, 1:], want_k[:, 1:]),
        max_err(got_v[:, 1:], want_v[:, 1:]))
    check(errors["paged_flush_block"] == 0.0,
          f"paged_flush_block is not exact: {errors}")

    T = 256
    fq = jax.random.normal(keys[5], (2, T, H, dh), jnp.bfloat16)
    fk = jax.random.normal(keys[6], (2, T, Hkv, dh), jnp.bfloat16)
    fv = jax.random.normal(keys[7], (2, T, Hkv, dh), jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(attention_reference)(fq, fk, fv)
    out = jax.jit(lambda *a: flash_attention(*a, True))(fq, fk, fv)
    errors["flash_attention"] = max_err(out, ref)

    for name, err in errors.items():
        check(np.isfinite(err) and err <= KERNEL_ATOL,
              f"{name} differs from its reference by {err} "
              f"(tolerance {KERNEL_ATOL})")
    return {"kernel_max_abs_err": {k: round(v, 5) for k, v in errors.items()},
            "kernel_atol": KERNEL_ATOL}


def _programs(engine) -> dict:
    with engine.executor._lock:
        return dict(engine.executor._cache)


def _pool_copies(text: str, pool) -> int:
    """Copies of a whole pool in a compiled program's HLO: a line whose
    RESULT has the pool's type and whose op is copy. The storage layout is
    row-major; the compiler re-laying the pool out for a scatter or a dot
    shows up as exactly this, at twice the pool's bytes (dh 64 on 128
    lanes)."""
    kind = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}[
        str(pool.dtype)]
    result = f" = {kind}[{','.join(map(str, pool.shape))}]{{"
    return sum(1 for line in text.splitlines()
               if result in line and "} copy(" in line)


def check_programs(engine, require_tpu: bool) -> dict:
    """What the engine serves from: every decode and prefill program holds
    its Pallas kernel, and none carries a copy of the page pool."""
    report = {}
    for key, program in _programs(engine).items():
        name = program.name
        if not require_tpu:
            report[name] = {"source": program.source}
            continue
        text = program.compiled.as_text()
        memory = program.compiled.memory_analysis()
        report[name] = {
            "source": program.source,
            "kernels": text.count("tpu_custom_call"),
            "temp_mib": memory.temp_size_in_bytes >> 20,
        }
        # the prefix-cache tail program attends gathered pages in XLA
        wants_kernel = "decode" in name or "-prefill-" in name
        check(not wants_kernel or "tpu_custom_call" in text,
              f"served program {name} holds no Pallas kernel")
        check(_pool_copies(text, engine.k_cache) == 0,
              f"served program {name} copies the page pool "
              f"({memory.temp_size_in_bytes >> 20} MiB of temporaries)")
    return report


def serve(size: Size, require_tpu: bool) -> dict:
    """Boot examples/llm-server, drive the requests, shut it down."""
    import jax

    from gofr_tpu.tpu.capacity import device_budget_bytes
    from gofr_tpu.tpu.paging import PagedLLMEngine
    from gofr_tpu.tpu.utilization import resolve_peaks

    rng = random.Random(SEED)
    module = _load_llm_server()
    # set-up splits into init (weights, pool, servers) and warm-up (the
    # compiles): a span from this file around the engine's own warm-up
    warmup, warmup_s = PagedLLMEngine.warmup, [0.0]

    def timed_warmup(self, *args, **kwargs):
        t0 = time.monotonic()
        try:
            return warmup(self, *args, **kwargs)
        finally:
            warmup_s[0] += time.monotonic() - t0

    PagedLLMEngine.warmup = timed_warmup
    t0 = time.monotonic()
    try:
        app = module.build_app(
            config=_config(size, "tpu" if require_tpu else "cpu"))
    finally:
        PagedLLMEngine.warmup = warmup
    app.start()
    setup_s = time.monotonic() - t0
    engine, port = app.engine, app.http_port
    warm = _programs(engine)
    table = engine.executor.compile_table()
    out = {
        "setup_seconds": round(setup_s, 1),
        "init_seconds": round(setup_s - warmup_s[0], 1),
        "warmup_seconds": round(warmup_s[0], 1),
        "programs": table["distinct_programs"],
        "programs_from_cache": table["disk_hits_total"],
        "compile_seconds": table["compile_seconds_total"],
        "slots": engine.n_slots, "max_seq_len": engine.max_seq_len,
        "pool_pages": engine.allocator.n_pages,
        "pool_mib": engine.pool_bytes() >> 20,
        "plan": engine.plan.summary() if engine.plan is not None else None,
    }
    emit(phase="boot", **out)
    # a request that fails raises, so what is counted here was answered
    answered = tokens = 0

    def count(n_tokens: int, what: str) -> None:
        nonlocal answered, tokens
        check(n_tokens > 0, f"{what} returned no token")
        answered, tokens = answered + 1, tokens + n_tokens

    try:
        health = _get(port, "/.well-known/health")
        check(health["data"]["status"] == "UP",
              f"health is {health['data']['status']}")
        device = health["data"]["details"]["tpu"]["details"]
        check(not require_tpu or device["platform"] == "tpu",
              f"the server reports platform {device['platform']!r}")
        out["served_from"] = {"platform": device["platform"],
                              "kind": device["memory"][0]["kind"],
                              "devices": device["devices"]}

        # the engine answers the same lone greedy request the same way
        tok = engine.tokenizer
        for prompt in (_text(rng, 40), _text(rng, 90)):
            first = engine.submit(tok.encode(prompt), max_new_tokens=MAX_NEW
                                  ).result(timeout_s=180.0)
            again = engine.submit(tok.encode(prompt), max_new_tokens=MAX_NEW
                                  ).result(timeout_s=180.0)
            check(first == again,
                  f"two lone greedy runs of one prompt differ: "
                  f"{first} vs {again}")
            count(len(first), "a lone greedy run")
            count(len(again), "a lone greedy run")

        unary = _post(port, {"prompt": _text(rng, 30), "stream": False,
                             "max_tokens": MAX_NEW})
        long = _post(port, {"prompt": _text(rng, LONG_PROMPT),
                            "stream": False, "max_tokens": MAX_NEW})
        streamed: dict = {}
        _stream(port, _text(rng, 30), MAX_NEW, streamed)
        check("error" not in streamed, f"SSE stream: {streamed.get('error')}")
        check(len(streamed["events"]) == streamed["tokens"] > 0,
              f"SSE stream sent {len(streamed['events'])} token events for "
              f"{streamed['tokens']} tokens")

        repeat = _text(rng, REPEAT_PROMPT)
        before = engine.prefix.stats()["hit_pages"]
        cold = _post(port, {"prompt": repeat, "stream": False,
                            "max_tokens": MAX_NEW})
        hit = _post(port, {"prompt": repeat, "stream": False,
                           "max_tokens": MAX_NEW})
        prefix_hits = engine.prefix.stats()["hit_pages"] - before
        check(prefix_hits >= 1, "the repeated prompt missed the prefix cache")
        for name, reply in (("unary", unary), ("long", long),
                            ("repeat", cold), ("repeat-hit", hit),
                            ("stream", streamed)):
            count(reply["tokens"], f"the {name} request")

        # the burst: wave 2 arrives while wave 1 is decoding, so continuous
        # batching has to admit into a running decode. Prompts are distinct
        # and mostly under one page (no shared prefix among them)
        def launch(n, max_tokens):
            results = [dict() for _ in range(n)]
            threads = []
            fits = [n_bytes for n_bytes in (8, 20, 50, 100, 180)
                    if n_bytes + 1 + max_tokens <= size.max_seq_len]
            for result in results:
                prompt = _text(rng, rng.choice(fits))
                thread = threading.Thread(
                    target=_stream, args=(port, prompt, max_tokens, result))
                thread.start()
                threads.append(thread)
            return results, threads

        before_burst = engine.steps.records()[-1].seq
        wave1, threads1 = launch(size.wave1, size.wave1_tokens)
        deadline = time.monotonic() + 120.0
        while not all(len(r.get("events", ())) >= 4 or "error" in r
                      for r in wave1):
            check(time.monotonic() < deadline,
                  "wave 1 produced no tokens in 120 s")
            time.sleep(0.01)
        wave2, threads2 = launch(size.wave2, 32)
        for thread in threads1 + threads2:
            thread.join(timeout=240.0)
            check(not thread.is_alive(), "a burst stream never finished")
        for result in wave1 + wave2:
            check("error" not in result, f"burst: {result.get('error')}")
            count(result["tokens"], "a burst request")
        # asserted from the loop's own step records, not from the clients'
        # clocks: a turn dispatched a prefill where the step before it had
        # closed with slots decoding and a decode block in flight. (The
        # clients' view, whether a stream of wave 1 ended after wave 2's
        # first token ARRIVED, is reported and not asserted: a prefill is
        # queued behind the decode blocks in flight, and on a CPU shared
        # with other test workers wave 1 may be through by then.)
        records = [r for r in engine.steps.records(recent=1 << 20)
                   if r.seq > before_burst]
        into_decode = sum(
            1 for before, rec in zip(records, records[1:])
            if rec.dispatches.get("prefill") and before.active_slots > 0
            and before.inflight > before.inflight_prefill)
        check(into_decode > 0,
              "no prompt of the burst was admitted into a running decode")
        first_of_wave2 = min(r["events"][0] for r in wave2)
        overlapped = sum(r["finished_at"] > first_of_wave2 for r in wave1)

        late = sorted(p.name for k, p in _programs(engine).items()
                      if k not in warm)
        check(not late, f"programs compiled after warm-up: {late}")
        out["programs_served"] = check_programs(engine, require_tpu)

        stats = [d.memory_stats() or {} for d in engine.executor.tpu.devices]
        out.update(
            requests_sent=answered, requests_answered=answered,
            requests_failed=0, tokens_returned=tokens,
            prefix_cache_hit_pages=prefix_hits,
            burst={"wave1": size.wave1, "wave2": size.wave2,
                   "admissions_into_running_decode": into_decode,
                   "wave1_still_decoding_at_wave2_first_token": overlapped},
            compiled_after_warmup=0,
            bytes_limit=stats[0].get("bytes_limit", 0),
            peak_bytes_in_use=stats[0].get("peak_bytes_in_use", 0))
        if require_tpu:
            check(device_budget_bytes(engine.executor.tpu) > 0,
                  "device_budget_bytes is 0 on a TPU")
            device0 = jax.devices()[0]
            peaks = resolve_peaks(device0.platform, device0.device_kind)
            check(peaks[2] in ("table", "env"), f"peaks come from {peaks[2]}")
            out["peaks_source"] = peaks[2]
    except BaseException:
        _dump_debug(port)
        raise
    finally:
        t0 = time.monotonic()
        app.shutdown()          # on_shutdown: engine.drain() + engine.stop()
        out["shutdown_seconds"] = round(time.monotonic() - t0, 1)
    check(engine._thread is None or not engine._thread.is_alive(),
          "the engine loop outlived shutdown")
    return out


def run_one_chip(size: Size = FULL, require_tpu: bool = True) -> dict:
    from gofr_tpu.tpu.executor import enable_compile_cache

    summary = {"phase": "summary", "mode": "one-chip", "preset": size.preset}
    summary.update(check_native())
    summary["compile_cache_dir"] = enable_compile_cache()
    summary["compile_cache_placed_by_env"] = bool(
        os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    counter = JaxCacheCounter()
    t0 = time.monotonic()
    kernels = check_kernels()
    emit(phase="kernels", seconds=round(time.monotonic() - t0, 1), **kernels)
    summary.update(kernels)
    summary.update(serve(size, require_tpu))
    summary.update(jax_cache_hits=counter.hits,
                   jax_cache_misses=counter.misses)
    return summary


# -- four chips: tensor-parallel serving -----------------------------------------
def _greedy(engine, prompts) -> list:
    tok = engine.tokenizer
    return [engine.submit(tok.encode(p), max_new_tokens=TP_TOKENS
                          ).result(timeout_s=300.0) for p in prompts]


def _next_token_logits(engine, rows):
    """The logits the served prefill samples a row's next token from: the
    prefill program's own forward (llama_prefill_last) on this engine's
    placed params and mesh. rows: token id lists of at most 64."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models.llama import llama_prefill_last

    cfg, T = engine.cfg, 64
    tokens = np.zeros((len(rows), T), np.int32)
    for i, row in enumerate(rows):
        tokens[i, :len(row)] = row
    lengths = jnp.asarray([len(r) for r in rows], jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                 (len(rows), T))
    shape = (cfg.n_layers, len(rows), cfg.n_kv_heads, cfg.head_dim, T)

    def forward(params, tokens, positions, lengths):
        cache = jnp.zeros(shape, dtype=jnp.bfloat16 if cfg.dtype == "bfloat16"
                          else jnp.float32)
        return llama_prefill_last(params, cfg, tokens, positions, lengths,
                                  cache, cache, engine.mesh)[0]

    logits = jax.jit(forward)(engine.params, jnp.asarray(tokens), positions,
                              lengths)
    return np.asarray(logits, np.float32)


def run_tp(size: Size = FULL, require_tpu: bool = True) -> dict:
    """TP=size.tp against one device: the same model through the same
    entry point, compared."""
    import jax
    import numpy as np

    from gofr_tpu.tpu.executor import enable_compile_cache

    summary = {"phase": "summary", "mode": f"tp={size.tp}",
               "preset": size.preset}
    summary["compile_cache_dir"] = enable_compile_cache()
    platform = "tpu" if require_tpu else "cpu"
    devices = jax.devices(platform)
    check(len(devices) >= size.tp,
          f"--chips {size.tp} needs {size.tp} devices, JAX has "
          f"{len(devices)}")
    rng = random.Random(SEED)
    prompts = [_text(rng, n) for n in (12, 30, 50)]
    module = _load_llm_server()
    # both engines live in this one process, and device 0 holds its tp
    # shard AND the whole one-device model. Lone short requests: 16 slots
    # of 256 tokens, two prefill buckets, single-admission warm-up — the
    # fewest programs that still serve (every call here is four chips)
    small = dict(MAX_BATCH="16", MAX_SEQ_LEN="256", PREFILL_BUCKETS="16,64",
                 WARMUP="true")

    def boot(tp: int):
        t0 = time.monotonic()
        app = module.build_app(config=_config(size, platform,
                                              TP_SHARDS=str(tp), **small))
        app.start()
        return app, round(time.monotonic() - t0, 1)

    apps = []
    try:
        app_tp, summary["tp_setup_seconds"] = boot(size.tp)
        apps.append(app_tp)
        engine = app_tp.engine
        # spread: before the one-device engine exists, every chip holds
        # its quarter of the weights (tok_emb is replicated) and the pool
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in devices[:size.tp]]
        summary["bytes_in_use_per_device"] = in_use
        if require_tpu:
            check(min(in_use) > 0 and max(in_use) <= 1.25 * min(in_use),
                  f"per-device bytes_in_use not within 25%: {in_use}")
        for name, leaf in (("wq", engine.params["layers"]["wq"]),
                           ("w_down", engine.params["layers"]["w_down"]),
                           ("lm_head", engine.params["lm_head"]),
                           ("k_pool", engine.k_cache)):
            spans = len(leaf.sharding.device_set)
            check(spans == size.tp and not leaf.sharding.is_fully_replicated,
                  f"{name} is not sharded over {size.tp} devices")
        summary["sharded_over"] = size.tp

        reply = _post(app_tp.http_port, {"prompt": prompts[0],
                                         "stream": False,
                                         "max_tokens": TP_TOKENS})
        check(reply["tokens"] > 0, "the tp server returned no token")
        encoded = [engine.tokenizer.encode(p) for p in prompts]
        tokens_tp = _greedy(engine, prompts)
        logits_tp = _next_token_logits(engine, encoded)
        decode = [p for p in _programs(engine).values()
                  if "decode" in p.name]
        check(bool(decode), "the tp engine compiled no decode program")
        if require_tpu:
            text = decode[0].compiled.as_text()
            check("tpu_custom_call" in text,
                  f"{decode[0].name} holds no Pallas kernel")
            check("all-reduce" in text,
                  f"{decode[0].name} holds no all-reduce")
            summary["tp_decode_program"] = {
                "name": decode[0].name,
                "kernels": text.count("tpu_custom_call"),
                "all_reduces": text.count("all-reduce(")}

        app_one, summary["one_device_setup_seconds"] = boot(1)
        apps.append(app_one)
        one = app_one.engine
        tokens_one = _greedy(one, prompts)
        logits_one = _next_token_logits(one, encoded)

        check(np.all(np.isfinite(logits_tp))
              and np.all(np.isfinite(logits_one)),
              "first-step logits are not finite")
        scale = float(np.max(np.abs(logits_one)))
        err = float(np.max(np.abs(logits_tp - logits_one)))
        summary.update(first_step_logit_max_abs=round(scale, 4),
                       first_step_logit_max_diff=round(err, 5),
                       logit_rtol=TP_LOGIT_RTOL)
        emit(**{**summary, "phase": "tp-logits"})
        check(err <= TP_LOGIT_RTOL * scale,
              f"tp and one-device first-step logits differ by {err} "
              f"(largest logit {scale}, tolerance {TP_LOGIT_RTOL} of it)")
        # tokens: equal, except that two engines may break a TIE
        # differently — with random weights the leading logits lie close,
        # and the largest changes on rounding. A first difference at token
        # n counts as a tie when, given the SAME context (the prompt and
        # the n tokens both agreed on), the one-device logits of the two
        # candidates lie within the logit tolerance; the rows diverge
        # from there by right and are compared no further
        compared, ties = [], []
        for row, (got, want) in enumerate(zip(tokens_tp, tokens_one)):
            check(len(got) > 0 and len(want) > 0,
                  "a greedy run gave no token")
            both = min(len(got), len(want))
            n = next((i for i in range(both) if got[i] != want[i]), both)
            if n < both:
                after = _next_token_logits(one, [encoded[row] + want[:n]])[0]
                margin = abs(float(after[got[n]] - after[want[n]]))
                ties.append({"prompt": row, "token": n,
                             "margin": round(margin, 5)})
                check(margin <= TP_LOGIT_RTOL * scale,
                      f"prompt {row}: tp {got} vs one device {want} differ "
                      f"at token {n} by a logit margin of {margin}: not a "
                      f"tie")
            compared.append(n)
        summary.update(tokens_compared_equal=compared, ties=ties,
                       tokens_per_prompt=TP_TOKENS)
    finally:
        for app in apps:
            app.shutdown()
    return summary


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs the tensor-parallel path and nothing "
                             "else")
    args = parser.parse_args()
    # a hang is a failure with a traceback, inside the driver's time limit
    faulthandler.dump_traceback_later(1150, exit=True)
    sys.path.insert(0, ROOT)
    import jax

    devices = jax.devices("tpu")      # raises where JAX finds no TPU
    check(len(devices) >= args.chips,
          f"--chips {args.chips} on a machine with {len(devices)}")
    t0 = time.monotonic()
    summary = run_one_chip() if args.chips == 1 else run_tp()
    summary.update(total_seconds=round(time.monotonic() - t0, 1), claim=None)
    emit(**summary)
    emit(ok=True, device={"platform": devices[0].platform,
                          "kind": devices[0].device_kind,
                          "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
