"""TPU runtime tier: device client, executor cache, dynamic batcher, LLM engine.

Runs on the virtual CPU backend (conftest) — real compile/execute semantics,
no hardware, per SURVEY.md §4's fake-backend lesson.
"""

import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.config import MockConfig
from gofr_tpu.logging import MockLogger
from gofr_tpu.metrics import Manager
from gofr_tpu.tpu.device import TPUClient
from gofr_tpu.tpu.executor import Executor, next_bucket, pad_to
from gofr_tpu.tpu.scheduler import DynamicBatcher


def make_metrics():
    m = Manager()
    client = TPUClient(MockConfig({}))
    client.use_metrics(m)
    client.use_logger(MockLogger())
    client.connect()
    return m, client


# -- device client ------------------------------------------------------------
def test_tpu_client_connect_and_health():
    metrics, client = make_metrics()
    assert client.device_count == 8  # virtual CPU mesh from conftest
    health = client.health_check()
    assert health.status == "UP"
    assert health.details["devices"] == 8
    assert "app_tpu_ttft_seconds" in metrics.expose()


def test_tpu_client_mesh():
    _, client = make_metrics()
    mesh = client.mesh({"dp": 2, "tp": 4})
    assert mesh.shape == {"dp": 2, "tp": 4}
    mesh = client.mesh({"dp": -1, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    with pytest.raises(ValueError):
        client.mesh({"dp": 3, "tp": 3})


# -- bucketing ----------------------------------------------------------------
def test_next_bucket_and_pad():
    assert next_bucket(1) == 1
    assert next_bucket(5) == 8
    assert next_bucket(8) == 8
    with pytest.raises(ValueError):
        next_bucket(10**9)
    x = np.ones((3, 4))
    padded = pad_to(x, 8, axis=0)
    assert padded.shape == (8, 4)
    assert padded[3:].sum() == 0
    assert pad_to(x, 4, axis=1).shape == (3, 4)
    with pytest.raises(ValueError):
        pad_to(x, 2, axis=0)


# -- executor -----------------------------------------------------------------
def test_executor_compile_cache():
    metrics, client = make_metrics()
    ex = Executor(client)

    def f(x):
        return x * 2.0

    a = jnp.ones((4, 4))
    out1 = ex.run("double", f, a)
    out2 = ex.run("double", f, a)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2))
    assert ex.cache_size == 1
    # different shape -> new compile
    ex.run("double", f, jnp.ones((8, 4)))
    assert ex.cache_size == 2
    text = metrics.expose()
    assert "app_tpu_compile_total 2.0" in text
    assert "app_tpu_compile_cache_hits 1.0" in text


def test_executor_donation():
    ex = Executor()

    def step(state):
        return state + 1.0

    state = jnp.zeros((16,))
    program = ex.compile("step", step, (state,), donate_argnums=(0,))
    state = program(state)
    state = program(state)
    assert float(state[0]) == 2.0


# -- dynamic batcher ----------------------------------------------------------
def test_executor_disk_cache_skips_recompile(tmp_path):
    """A second executor (fresh process analog) loads the persisted PJRT
    executable instead of recompiling (SURVEY §2.5 item 2)."""
    import jax.numpy as jnp

    def double(x):
        return x * 2 + 1

    cache = str(tmp_path / "programs")
    args = (jnp.ones((8,)),)
    ex1 = Executor(cache_dir=cache)
    p1 = ex1.compile("double", double, args)
    assert ex1.disk_hits == 0
    files = list(os.listdir(cache))
    assert len(files) == 1 and files[0].endswith(".jexec")

    ex2 = Executor(cache_dir=cache)  # no in-memory state
    p2 = ex2.compile("double", double, args)
    assert ex2.disk_hits == 1  # boot skipped the recompile
    np.testing.assert_array_equal(np.asarray(p2(*args)), np.asarray(p1(*args)))
    # in-memory cache serves the next request, not the disk
    ex2.compile("double", double, args)
    assert ex2.disk_hits == 1

    # a changed function body with the SAME name+shapes must NOT resurrect
    # the stale executable — including a CONSTANT-only change (identical
    # co_code; only co_consts differs) and a closure-value change, the two
    # edits a bytecode-only fingerprint would miss
    def double_v2(x):
        return x * 2 + 2

    ex3 = Executor(cache_dir=cache)
    p3 = ex3.compile("double", double_v2, args)
    assert ex3.disk_hits == 0
    assert float(np.asarray(p3(*args))[0]) == 4.0

    def make_scaler(c):
        def scaler(x):
            return x * c
        return scaler

    exc1 = Executor(cache_dir=cache)
    pc1 = exc1.compile("scale", make_scaler(3.0), args)
    assert float(np.asarray(pc1(*args))[0]) == 3.0
    exc2 = Executor(cache_dir=cache)
    pc2 = exc2.compile("scale", make_scaler(5.0), args)  # same code, new cell
    assert exc2.disk_hits == 0
    assert float(np.asarray(pc2(*args))[0]) == 5.0
    exc3 = Executor(cache_dir=cache)  # same closure value -> disk hit
    pc3 = exc3.compile("scale", make_scaler(5.0), args)
    assert exc3.disk_hits == 1
    assert float(np.asarray(pc3(*args))[0]) == 5.0

    # corrupted artifact: fall back to compiling, quarantine the file
    bad = os.path.join(cache, files[0])
    with open(bad, "wb") as fp:
        fp.write(b"garbage")
    ex4 = Executor(cache_dir=cache)
    p4 = ex4.compile("double", double, args)
    assert ex4.disk_hits == 0
    assert float(np.asarray(p4(*args))[0]) == 3.0


def test_batcher_batches_and_demuxes():
    metrics, client = make_metrics()
    ex = Executor(client)

    seen_batches = []

    def model(batch):  # [B, D] -> [B]
        seen_batches.append(batch.shape)
        return jnp.sum(batch, axis=-1)

    batcher = DynamicBatcher(model, executor=ex, max_batch=8, window_s=0.05,
                             name="sum")
    batcher.start()
    try:
        futures = [batcher.submit(np.full((4,), float(i))) for i in range(5)]
        results = [f.result(timeout=30) for f in futures]
        assert [float(r) for r in results] == [0.0, 4.0, 8.0, 12.0, 16.0]
        # 5 requests -> one padded batch of 8 (bucket), not 5 separate calls
        assert all(shape[0] in (1, 2, 4, 8) for shape in seen_batches)
        assert len(seen_batches) <= 3
    finally:
        batcher.stop()


def test_batcher_variable_seq_padding():
    ex = Executor()

    def model(batch):  # [B, T] -> [B]
        return jnp.sum(batch, axis=-1)

    batcher = DynamicBatcher(model, executor=ex, max_batch=4, window_s=0.05,
                             seq_axis=0, seq_buckets=(8, 16), name="varlen")
    batcher.start()
    try:
        f1 = batcher.submit(np.ones((3,)))
        f2 = batcher.submit(np.ones((7,)))
        assert float(f1.result(timeout=30)) == 3.0
        assert float(f2.result(timeout=30)) == 7.0
    finally:
        batcher.stop()


def test_batcher_model_error_fails_futures():
    ex = Executor()

    def model(batch):
        raise RuntimeError("device on fire")

    batcher = DynamicBatcher(model, executor=ex, max_batch=2, window_s=0.01)
    batcher.start()
    try:
        future = batcher.submit(np.ones((2,)))
        with pytest.raises(RuntimeError, match="device on fire"):
            future.result(timeout=30)
    finally:
        batcher.stop()


def test_batcher_stop_fails_queued():
    ex = Executor()
    batcher = DynamicBatcher(lambda b: b, executor=ex)
    future = batcher.submit(np.ones((1,)))  # never started
    batcher.stop()
    with pytest.raises(RuntimeError):
        future.result(timeout=5)
    with pytest.raises(RuntimeError):
        batcher.submit(np.ones((1,)))


# -- LLM engine ---------------------------------------------------------------
@pytest.fixture(scope="module", params=["float", "int8"])
def engine(request):
    """The one engine over each pool dtype: floating point (a decode
    block's K and V flushed once a block) and int8 (one page write a
    token, `paged_write_decode`)."""
    import dataclasses

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    if request.param == "int8":
        cfg = dataclasses.replace(cfg, kv_dtype="int8")
    params = llama_init(cfg, seed=0)
    eng = PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=64,
                         prefill_buckets=(8, 16), logger=MockLogger())
    eng.start()
    yield eng
    eng.stop()


def test_engine_generates_deterministically(engine):
    prompt = [1, 2, 3, 4, 5]
    out1 = engine.generate(prompt, max_new_tokens=8, temperature=0.0)
    out2 = engine.generate(prompt, max_new_tokens=8, temperature=0.0)
    assert len(out1) == 8
    assert out1 == out2  # greedy is deterministic
    assert all(0 <= t < engine.cfg.vocab_size for t in out1)


def test_engine_matches_unbatched_reference(engine):
    """Greedy engine output == step-by-step nocache reference decode. Over
    int8 pages the prefill is still full precision, so the first token is
    exact; later reads differ by int8 rounding, where a near-tie may flip."""
    import jax.numpy as jnp

    from gofr_tpu.models.llama import llama_forward_nocache

    prompt = [3, 1, 4, 1, 5]
    got = engine.generate(prompt, max_new_tokens=6, temperature=0.0)

    seq = list(prompt)
    for _ in range(6):
        logits = llama_forward_nocache(engine.params, engine.cfg,
                                       jnp.asarray([seq], dtype=jnp.int32))
        seq.append(int(np.asarray(jnp.argmax(logits[0, -1]))))
    want = seq[len(prompt):]
    if engine.cfg.kv_dtype == "int8":
        assert len(got) == len(want) and got[0] == want[0]
        assert sum(a == b for a, b in zip(got, want)) >= 4
    else:
        assert got == want


def test_engine_concurrent_requests(engine):
    """More requests than slots: continuous batching must serve them all."""
    results = {}

    def run(i):
        results[i] = engine.generate([i + 1, i + 2], max_new_tokens=5,
                                     temperature=0.0)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(7)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 7
    assert all(len(v) == 5 for v in results.values())
    # same prompt -> same output regardless of slot/batch interleaving
    check = engine.generate([1, 2], max_new_tokens=5, temperature=0.0)
    assert results[0] == check


def test_engine_stop_tokens(engine):
    prompt = [1, 2, 3]
    free_run = engine.generate(prompt, max_new_tokens=8, temperature=0.0)
    stopped = engine.generate(prompt, max_new_tokens=8, temperature=0.0,
                              stop_tokens={free_run[2]})
    assert stopped == free_run[:3]  # stop token is emitted, then generation ends


def test_engine_streaming(engine):
    request = engine.submit([5, 6, 7], max_new_tokens=4, temperature=0.0)
    tokens = []
    for token in request.stream(timeout_s=60):
        tokens.append(token)
    assert len(tokens) == 4
    assert request.finished_at is not None


def test_engine_rejects_bad_prompts(engine):
    with pytest.raises(ValueError):
        engine.submit([])
    with pytest.raises(ValueError):
        engine.submit(list(range(100)))  # exceeds largest prefill bucket (16)


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_engine_pipelined_matches_synchronous():
    """block=4/depth=3 pipelined engine emits the same greedy tokens as the
    fully synchronous block=1/depth=1 configuration, including under fused
    multi-request admission."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    prompts = [[1, 2, 3], [7, 8], [4, 5, 6, 9], [2, 2, 2], [11, 12]]

    def run(block, depth):
        eng = PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=64,
                             prefill_buckets=(8,), decode_block_size=block,
                             pipeline_depth=depth)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=7, temperature=0.0)
                    for p in prompts]
            return [r.result(timeout_s=120) for r in reqs]
        finally:
            eng.stop()

    assert run(1, 1) == run(4, 3)


# -- a prefill in flight is not a decode block (PR 30) ---------------------------
STAGGERED_PROMPTS = [[1, 2, 3], [7, 8], [4, 5, 6, 9], [2, 2, 2]]


def _staggered_run(block, depth):
    """One request decodes; then THREE arrive in the same loop turn, so one
    `_admit` dispatches three prefill programs behind the decode blocks in
    flight. The late three are submitted from the loop thread itself, at
    the top of the first turn after the first request's ninth token, so
    the run is the same whatever the host's timing. Returns the greedy
    tokens, the step records, the loop's events in order
    (("admit",) | ("sync", kind read, kind at the head afterwards, the
    read prefill's requests all hold a token)) and the engine's counters."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=64, prefill_buckets=(8,),
                         decode_block_size=block, pipeline_depth=depth)
    events, reqs = [], []
    admit, sync = eng._admit, eng._sync_oldest

    def admit_logged():
        if len(reqs) == 1 and reqs[0].generated >= 9:
            reqs.extend(eng.submit(p, max_new_tokens=9, temperature=0.0)
                        for p in STAGGERED_PROMPTS[1:])
        events.append(("admit",))
        admit()

    def sync_logged():
        entry = eng._inflight[0]
        sync()
        head = eng._inflight[0][0] if eng._inflight else None
        events.append(("sync", entry[0], head, entry[0] != "prefill" or all(
            r.generated >= 1 for _, r in entry[2])))

    eng._admit, eng._sync_oldest = admit_logged, sync_logged
    reqs.append(eng.submit(STAGGERED_PROMPTS[0], max_new_tokens=40,
                           temperature=0.0))
    eng.start()
    try:
        first = reqs[0].result(timeout_s=120)
        assert len(reqs) == 4, "the late three were never submitted"
        tokens = [first] + [r.result(timeout_s=120) for r in reqs[1:]]
    finally:
        eng.stop()
    return {"tokens": tokens, "records": eng.steps.records(recent=1 << 20),
            "events": events, "decode_syncs": eng.decode_syncs_total,
            "dry_syncs": eng.dry_syncs_total}


@pytest.fixture(scope="module")
def staggered():
    runs = {}

    def get(block, depth):
        if (block, depth) not in runs:
            runs[block, depth] = _staggered_run(block, depth)
        return runs[block, depth]

    return get


@pytest.mark.parametrize("depth", [2, 4])
def test_admissions_leave_a_decode_block_queued(staggered, depth):
    """Rule 1: however many prefill entries one `_admit` put into the
    deque, every step that closes with a slot decoding closes with a decode
    block still in flight, and no decode block is read dry."""
    run = staggered(4, depth)
    records = run["records"]
    # the scenario happened: one turn dispatched several prefill programs
    # while a slot was decoding
    assert any(r.dispatches.get("prefill", 0) >= 3 for r in records)
    assert max(r.inflight_prefill for r in records) >= 2
    busy = [r for r in records if r.active_slots > 0]
    assert busy and all(r.inflight - r.inflight_prefill >= 1 for r in busy), \
        [r.summary() for r in busy if r.inflight == r.inflight_prefill]
    assert not any(r.dry_sync for r in records)
    assert run["decode_syncs"] > 0 and run["dry_syncs"] == 0


@pytest.mark.parametrize("depth", [2, 4])
def test_staggered_admissions_match_the_synchronous_engine(staggered, depth):
    """The same greedy tokens as the block 1 / depth 1 engine, which reads
    every dispatch before it makes the next."""
    assert staggered(4, depth)["tokens"] == staggered(1, 1)["tokens"]
    assert [len(t) for t in staggered(1, 1)["tokens"]] == [40, 9, 9, 9]


@pytest.mark.parametrize("depth", [2, 4])
def test_a_prefill_is_read_in_the_turn_it_reaches_the_head(staggered, depth):
    """Rule 2: when a sync leaves a prefill entry at the head of the
    deque, the loop's next act is to read it (no admission, no turn of its
    own), and the read emits the first token of every request it bound."""
    events = staggered(4, depth)["events"]
    followed = 0
    for now, then in zip(events, events[1:]):
        if now[0] == "sync" and now[2] == "prefill":
            assert then[:2] == ("sync", "prefill"), (now, then)
            followed += 1
    assert followed >= 3            # the late three, at least
    assert all(e[3] for e in events if e[0] == "sync")


def test_a_synchronous_engine_reads_every_block_dry(staggered):
    """What the counter counts: at depth 1 nothing is ever queued behind
    the block being read, so every decode block read with a slot still
    decoding is a dry sync."""
    run = staggered(1, 1)
    reads = [r for r in run["records"] if r.phase == "decode"]
    assert reads and run["decode_syncs"] == len(reads)
    assert run["dry_syncs"] == sum(r.dry_sync for r in reads) > 0
    assert all(r.dry_sync == (r.active_slots > 0) for r in reads)


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_stream_ordering_with_cancels_mid_block():
    """Batched emission contract: with block-sized queue entries, pipelined
    dispatches and cancels landing mid-block, every client still receives
    exactly `request.emitted`, in order, with the terminal `None` strictly
    last — the invariant the PR-3 replay ledger and SSE streaming build on."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    eng = PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=64,
                         prefill_buckets=(8,), decode_block_size=4,
                         pipeline_depth=2)
    eng.start()
    try:
        prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
        reqs = [eng.submit(p, max_new_tokens=40, temperature=0.0)
                for p in prompts]
        results, errors = {}, []

        def consume(idx, req, cancel_after):
            # raw out_queue, not stream(): the terminal-None placement and
            # the batched list entries are exactly what's under test
            try:
                got = []
                while True:
                    entry = req.out_queue.get(timeout=120)
                    if entry is None:
                        break
                    got.extend(entry if type(entry) is list else [entry])
                    if cancel_after and len(got) >= cancel_after:
                        req.cancel()
                        cancel_after = 0
                results[idx] = got
            except Exception as exc:  # noqa: BLE001 - surfaced in main thread
                errors.append((idx, exc))

        threads = [threading.Thread(target=consume, args=(i, r, 3 if i % 2 else 0))
                   for i, r in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == len(reqs)
        for i, req in enumerate(reqs):
            # delivered == ledger, element for element and in order
            assert results[i] == req.emitted, f"request {i} stream != emitted"
            assert req.generated == len(req.emitted)
            assert req.finished_at is not None
            # None was terminal: nothing trails it on the queue
            assert req.out_queue.empty()
            if i % 2:  # cancelled mid-block: cut short, but never empty
                assert 1 <= len(results[i]) < 40
            else:
                assert len(results[i]) == 40
        # uncancelled streams carry the true greedy continuation in order
        check = eng.generate(prompts[0], max_new_tokens=40, temperature=0.0)
        assert results[0] == check
    finally:
        eng.stop()


def test_engine_admission_split():
    from gofr_tpu.tpu.engine import _admission_split

    assert _admission_split(11, 64) == [4, 4, 1, 1, 1]
    assert _admission_split(64, 64) == [64]
    assert _admission_split(5, 4) == [4, 1]
    assert _admission_split(1, 8) == [1]
    # a full-slot burst fuses into ONE dispatch even off the pow4 grid
    assert _admission_split(128, 128) == [128]
    assert _admission_split(8, 8) == [8]
    assert _admission_split(100, 128) == [64, 16, 16, 4]


def test_engine_batch_id_trace_correlation():
    """The engine stamps batch.id/tpu.slot/tpu.prefill_bucket on the
    request's span at admission and emits tpu.prefill/tpu.decode dispatch
    spans that close at host sync (SURVEY §5 tracing row)."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine
    from gofr_tpu.tracing import InMemoryExporter, Tracer

    exporter = InMemoryExporter()
    tracer = Tracer(exporter=exporter)
    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(8,), logger=MockLogger(),
                         tracer=tracer)
    eng.start()
    try:
        span = tracer.start_span("POST /generate")
        req = eng.submit([1, 2, 3], max_new_tokens=4, temperature=0.0,
                         span=span)
        req.result(timeout_s=60)
        span.end()
    finally:
        eng.stop()

    assert span.attributes["batch.id"] >= 1
    assert span.attributes["tpu.slot"] in (0, 1)
    assert span.attributes["tpu.prefill_bucket"] == 8
    names = [s.name for s in exporter.spans]
    assert "tpu.prefill" in names and "tpu.decode" in names
    prefill = next(s for s in exporter.spans if s.name == "tpu.prefill")
    assert prefill.attributes["batch.id"] == span.attributes["batch.id"]
    assert prefill.attributes["batch.size"] == 1
    assert prefill.end_time is not None  # closed at host sync
    decode = next(s for s in exporter.spans if s.name == "tpu.decode")
    assert decode.attributes["tpu.block"] == eng.decode_block_size
    # the per-request child span carries the correlation EXPORTED — for
    # streamed responses the parent HTTP span ends before admission, so the
    # child is the reliable record
    gen = next(s for s in exporter.spans if s.name == "tpu.generate")
    assert gen.parent_id == span.span_id
    assert gen.attributes["batch.id"] == span.attributes["batch.id"]
    assert gen.attributes["tpu.prompt_tokens"] == 3
    assert gen.attributes["tpu.tokens"] == 4
    assert gen.end_time is not None


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_engine_flash_prefill_matches_xla():
    """attn_impl="flash" routes serving prefill through the Pallas kernel
    (full-window T == S case); greedy tokens must match the dense path."""
    import dataclasses

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14], [1, 2]]
    outs = {}
    for impl in ("xla", "flash"):
        cfg = dataclasses.replace(LlamaConfig.debug(), attn_impl=impl)
        eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                             max_seq_len=64, prefill_buckets=(8,),
                             logger=MockLogger())
        eng.start()
        try:
            outs[impl] = [eng.generate(p, max_new_tokens=6, temperature=0.0)
                          for p in prompts]
        finally:
            eng.stop()
    assert outs["flash"] == outs["xla"]


def test_engine_host_prep_error_fails_only_that_wave():
    """A host-side failure BEFORE device dispatch fails the one admission
    wave; active requests and device state survive (VERDICT r2 weak #5)."""
    from gofr_tpu import native
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=64,
                         prefill_buckets=(8,), logger=MockLogger())
    eng.start()
    try:
        # a long-running request that must SURVIVE the other wave's failure
        survivor = eng.submit([1, 2, 3], max_new_tokens=40, temperature=0.0)
        while survivor.generated == 0:
            time.sleep(0.01)

        real_pad = native.pad_batch

        def boom(*a, **kw):
            raise RuntimeError("host prep exploded")

        native.pad_batch = boom
        try:
            doomed = eng.submit([4, 5, 6], max_new_tokens=4, temperature=0.0)
            with pytest.raises(RuntimeError, match="host prep exploded"):
                doomed.result(timeout_s=30)
        finally:
            native.pad_batch = real_pad

        # the survivor finishes normally: no engine reset happened
        out = survivor.result(timeout_s=60)
        assert len(out) == 40
        # and the engine still admits new work
        assert len(eng.generate([7, 8], max_new_tokens=3)) == 3
    finally:
        eng.stop()


def test_histogram_record_n_batches():
    from gofr_tpu.metrics import new_metrics_manager

    m = new_metrics_manager()
    m.new_histogram("h", "batched", buckets=(0.1, 1.0))
    m.record_histogram_n("h", 0.05, 7)
    m.record_histogram_n("h", 0.5, 0)  # no-op
    h = m.get("h")
    entry = h.series[tuple()]
    assert entry["count"] == 7
    assert entry["sum"] == pytest.approx(0.35)
    assert entry["counts"][0] == 7


def test_engine_stop_unblocks_active_requests():
    """stop() must fail mid-generation requests, never deadlock their clients."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    # budget far beyond what the post-stop drain (pipeline_depth * block
    # tokens) can finish, so the slot is still active at loop exit
    eng = PagedLLMEngine(params, cfg, n_slots=2, max_seq_len=256,
                         prefill_buckets=(8,), decode_block_size=4,
                         pipeline_depth=2, logger=MockLogger())
    eng.start()
    req = eng.submit([1, 2, 3], max_new_tokens=250, temperature=0.0)
    while req.generated == 0:  # wait until admitted into a slot
        time.sleep(0.01)
    eng.stop()
    with pytest.raises(RuntimeError, match="engine stopped"):
        req.result(timeout_s=30)


def test_engine_drain_finishes_active_rejects_new():
    """drain(): active generations complete with their full token budget,
    queued/new requests fail fast, stop() afterwards is clean."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=2,
                         max_seq_len=128,
                         prefill_buckets=(8,), decode_block_size=4)
    eng.start()
    try:
        active = eng.submit([1, 2, 3], max_new_tokens=24, temperature=0.0)
        # wait for admission so drain sees an ACTIVE slot, not a queued req
        deadline = time.time() + 60
        while active.admitted_at is None and time.time() < deadline:
            time.sleep(0.01)
        assert active.admitted_at is not None
        assert eng.drain(timeout_s=120) is True
        tokens = active.result(timeout_s=10)
        assert len(tokens) == 24, "drained request lost tokens"
        with pytest.raises(RuntimeError, match="draining"):
            eng.submit([4, 5], max_new_tokens=4)
        # a drained engine may be restarted: stop/start clears the flag
        eng.stop()
        eng.start()
        again = eng.submit([7, 8, 9], max_new_tokens=3, temperature=0.0)
        assert len(again.result(timeout_s=120)) == 3
    finally:
        eng.stop()


def test_app_shutdown_hooks_run_lifo():
    from gofr_tpu import App
    from gofr_tpu.config import MockConfig

    app = App(config=MockConfig({"HTTP_PORT": "0", "METRICS_PORT": "0"}))
    order = []
    app.on_shutdown(lambda: order.append("first"))
    app.on_shutdown(lambda: order.append("second"))
    app.on_shutdown(lambda: 1 / 0)  # a failing hook must not block the rest
    app.start()
    app.shutdown()
    assert order == ["second", "first"]


def test_priority_admission_order():
    """A high-priority request queued behind low-priority ones is admitted
    first once a slot frees; running generations are never preempted."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=1,
                         max_seq_len=64,
                         prefill_buckets=(8,), decode_block_size=2)
    eng.start()
    try:
        blocker = eng.submit([1, 2, 3], max_new_tokens=24, temperature=0.0)
        deadline = time.time() + 60
        while blocker.admitted_at is None and time.time() < deadline:
            time.sleep(0.005)
        low = [eng.submit([4 + i], max_new_tokens=2, temperature=0.0,
                          priority=5) for i in range(4)]
        high = eng.submit([9, 9], max_new_tokens=2, temperature=0.0,
                          priority=0)
        for r in [blocker, high] + low:
            r.result(timeout_s=120)
        assert high.admitted_at is not None
        assert all(high.admitted_at <= r.admitted_at for r in low), \
            "high-priority request did not jump the queue"
    finally:
        eng.stop()


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_min_tokens_suppresses_early_stop():
    """stop_tokens are ignored until min_tokens have been emitted; without
    the floor the same stop set ends generation earlier."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(8,), decode_block_size=4)
    eng.start()
    try:
        prompt = [3, 1, 4]
        free = eng.generate(prompt, max_new_tokens=20, temperature=0.0)
        assert len(free) == 20
        # every token the model would emit becomes a stop token: without a
        # floor the request ends at the first one...
        stops = set(free)
        early = eng.generate(prompt, max_new_tokens=20, temperature=0.0,
                             stop_tokens=stops)
        assert len(early) == 1
        # ...with min_tokens=7 exactly 7 are forced out
        floored = eng.generate(prompt, max_new_tokens=20, temperature=0.0,
                               stop_tokens=stops, min_tokens=7)
        assert len(floored) == 7
        assert floored == free[:7]
    finally:
        eng.stop()


def test_executor_persists_multi_device_programs(tmp_path):
    """TP/mesh programs persist WITH their device ordering and reload on a
    matching topology (VERDICT r3 weak #5: multi-device programs used to
    recompile on every boot). A single-device executor with identical
    shapes must NOT resurrect the mesh artifact."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest
    from jax.sharding import NamedSharding, PartitionSpec

    from gofr_tpu.parallel import MeshPlan, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")

    cache = str(tmp_path / "programs")
    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices()[:2])
    sharded = jax.device_put(
        jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
        NamedSharding(mesh, PartitionSpec(None, "tp")))

    def matvec(w, x):
        return (w * 2) @ x

    x = jnp.ones((4,), dtype=jnp.float32)
    ex1 = Executor(cache_dir=cache)
    p1 = ex1.compile("mesh-prog", matvec, (sharded, x))
    want = np.asarray(p1(sharded, x))
    assert len(os.listdir(cache)) == 1, "mesh program was not persisted"

    ex2 = Executor(cache_dir=cache)           # fresh-boot analog
    p2 = ex2.compile("mesh-prog", matvec, (sharded, x))
    assert ex2.disk_hits == 1, "mesh artifact not loaded from disk"
    got = p2(sharded, x)
    np.testing.assert_allclose(np.asarray(got), want)
    # the loaded program still executes SHARDED over the recorded devices
    # (a reload that silently dropped to one device is the exact bug the
    # recorded ordering exists to prevent)
    assert len(got.sharding.device_set) == 2

    # identical shapes on a SINGLE device: different fingerprint, no
    # cross-topology resurrection
    local = jax.device_put(np.arange(16, dtype=np.float32).reshape(4, 4),
                           jax.devices()[0])
    ex3 = Executor(cache_dir=cache)
    p3 = ex3.compile("mesh-prog", matvec, (local, x))
    assert ex3.disk_hits == 0
    np.testing.assert_allclose(np.asarray(p3(local, x)), want)


def test_mesh_device_order_is_part_of_artifact_identity(tmp_path):
    """The same two devices in REVERSED mesh order must not resurrect the
    other order's artifact (its restore pins the recorded order and would
    fail on every call) — each order compiles and persists its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")

    cache = str(tmp_path / "programs")

    def fwd(w, x):
        return (w * 2) @ x

    x = jnp.ones((4,), dtype=jnp.float32)
    outs = []
    for devices in (jax.devices()[:2], jax.devices()[:2][::-1]):
        mesh = Mesh(np.array(devices), axis_names=("tp",))
        w = jax.device_put(jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
                           NamedSharding(mesh, PartitionSpec(None, "tp")))
        ex = Executor(cache_dir=cache)
        program = ex.compile("order-prog", fwd, (w, x))
        assert ex.disk_hits == 0, "reversed order resurrected the artifact"
        outs.append(np.asarray(program(w, x)))
    np.testing.assert_allclose(outs[0], outs[1])
    assert len([f for f in os.listdir(cache)
                if f.endswith(".jexec")]) == 2


def test_prune_removes_stale_tmp_files(tmp_path):
    cache = tmp_path / "programs"
    cache.mkdir()
    stale = cache / "abc.jexec.tmp.999"
    stale.write_bytes(b"partial")
    os.utime(stale, (1, 1))                       # ancient
    fresh = cache / "def.jexec.tmp.1000"
    fresh.write_bytes(b"in-flight")               # now: a live writer
    Executor(cache_dir=str(cache))
    names = set(os.listdir(cache))
    assert "abc.jexec.tmp.999" not in names
    assert "def.jexec.tmp.1000" in names
