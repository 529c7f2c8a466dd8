"""Teacher-forced logprob scoring (tpu/score.py, the OpenAI logprobs path).

The feature's whole premise is that a post-hoc teacher-forced pass
reproduces the decode-time distributions exactly — so the tests check
that premise directly: scored values match a from-scratch full-sequence
log_softmax oracle, greedy generations score their own tokens as top-1,
and the windowed pass equals the single-window one across a window
boundary. Plus the serving-composition cases: paged engine, int8-weight
tree, and scoring while the engine is actively generating.
"""

import numpy as np
import pytest

from gofr_tpu.models.llama import (LlamaConfig, init_kv_cache, llama_init,
                                   llama_prefill, quantize_weights)
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()


@pytest.fixture(scope="module")
def engine():
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=2,
                         max_seq_len=256,
                         prefill_buckets=(16, 32, 64, 256))
    eng.start()
    yield eng
    eng.stop()


def _oracle(params, cfg, seq, P, top):
    """Full-sequence log_softmax reference, no windowing."""
    import jax.numpy as jnp

    toks = jnp.asarray([seq], dtype=jnp.int32)
    k, v = init_kv_cache(cfg, 1, len(seq))
    logits, _, _ = llama_prefill(params, cfg, toks, k, v)
    lsm = np.asarray(logits[0], dtype=np.float64)
    lsm = lsm - np.log(np.exp(lsm - lsm.max(-1, keepdims=True)).sum(-1,
                       keepdims=True)) - lsm.max(-1, keepdims=True)
    rows = lsm[P - 1:len(seq) - 1]
    chosen = rows[np.arange(len(rows)), seq[P:]]
    top_ids = np.argsort(-rows, axis=1)[:, :top]
    top_lps = np.take_along_axis(rows, top_ids, axis=1)
    return chosen, top_ids, top_lps


def test_score_matches_full_sequence_oracle(engine):
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, CFG.vocab_size, size=7).tolist()
    completion = rng.integers(1, CFG.vocab_size, size=9).tolist()

    chosen, ids, lps = engine.score(prompt, completion, top=4)
    want_chosen, want_ids, want_lps = _oracle(
        engine.params, CFG, prompt + completion, len(prompt), 4)

    assert chosen.shape == (9,) and ids.shape == (9, 4)
    np.testing.assert_allclose(chosen, want_chosen, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(lps, want_lps, rtol=1e-4, atol=1e-5)


def test_windowed_scoring_crosses_boundaries(engine):
    """A >128-token sequence forces multiple windows; the result must be
    position-for-position identical to the oracle across the seam."""
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, CFG.vocab_size, size=120).tolist()
    completion = rng.integers(1, CFG.vocab_size, size=40).tolist()

    chosen, ids, lps = engine.score(prompt, completion, top=3)
    want_chosen, want_ids, _ = _oracle(
        engine.params, CFG, prompt + completion, len(prompt), 3)
    assert chosen.shape == (40,)
    np.testing.assert_allclose(chosen, want_chosen, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(ids, want_ids)


def test_greedy_generation_scores_itself_top1(engine):
    prompt = [3, 1, 4, 1, 5]
    tokens = engine.generate(prompt, max_new_tokens=8, temperature=0.0)
    chosen, ids, lps = engine.score(prompt, tokens, top=2)
    # greedy picked the argmax at every step, so the chosen token IS the
    # top-1 alternative and its logprob the maximum
    np.testing.assert_array_equal(ids[:, 0], tokens)
    np.testing.assert_allclose(chosen, lps[:, 0], rtol=1e-5, atol=1e-6)


def test_score_while_engine_is_busy(engine):
    """Scoring dispatches interleave with live decoding — no pause, no
    cross-contamination."""
    reqs = [engine.submit([9, 8, 7], max_new_tokens=24, temperature=0.0)
            for _ in range(2)]
    chosen, ids, _ = engine.score([3, 1, 4, 1, 5], [9, 2, 6], top=2)
    assert chosen.shape == (3,)
    for r in reqs:
        assert len(r.result(timeout_s=120)) == 24
    # identical to the idle-engine answer
    chosen2, ids2, _ = engine.score([3, 1, 4, 1, 5], [9, 2, 6], top=2)
    np.testing.assert_allclose(chosen, chosen2, rtol=1e-6)
    np.testing.assert_array_equal(ids, ids2)


def test_score_paged_and_int8_engines():
    q8 = quantize_weights(llama_init(CFG, seed=0))
    eng = PagedLLMEngine(q8, CFG, n_slots=2, max_seq_len=64,
                         prefill_buckets=(16, 64), page_size=16)
    eng.start()
    try:
        prompt = [3, 1, 4]
        tokens = eng.generate(prompt, max_new_tokens=6, temperature=0.0)
        chosen, ids, lps = eng.score(prompt, tokens, top=3)
        # the scored distribution is the int8-weight model's own — greedy
        # self-consistency must hold for the quantized tree too
        np.testing.assert_array_equal(ids[:, 0], tokens)
        want_chosen, want_ids, _ = _oracle(eng.params, CFG,
                                           prompt + tokens, len(prompt), 3)
        np.testing.assert_allclose(chosen, want_chosen, rtol=1e-3, atol=1e-4)
    finally:
        eng.stop()


def test_score_validation(engine):
    with pytest.raises(ValueError):
        engine.score([1, 2], [], top=3)
    with pytest.raises(ValueError):
        engine.score([], [1], top=3)
    with pytest.raises(ValueError):
        engine.score([1], [2], top=0)
    with pytest.raises(ValueError):
        engine.score([1] * 300, [2], top=3)  # exceeds largest bucket


def test_openai_surface_serves_logprobs():
    """End-to-end /v1 logprobs: completions (tokens/token_logprobs/
    top_logprobs/text_offset) and chat (content[] with bytes), greedy
    self-consistency, and the honest rejections (stream+logprobs,
    top_logprobs without logprobs)."""
    import importlib.util
    import json as _json
    import os
    import urllib.request

    from gofr_tpu.config import MockConfig

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "openai-server", "main.py")
    spec = importlib.util.spec_from_file_location("oai_lp_example", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)

    app = module.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "lp",
        "TPU_PLATFORM": "cpu", "MODEL_PRESET": "debug", "WARMUP": "false",
        "REQUEST_TIMEOUT": "60"}))
    app.start()

    def call(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{app.http_port}{path}", method="POST",
            data=_json.dumps(body).encode())
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, _json.loads(resp.read().decode())
        except urllib.error.HTTPError as err:
            return err.code, _json.loads(err.read().decode() or "null")

    try:
        status, body = call("/v1/completions",
                            {"prompt": "hello", "max_tokens": 5,
                             "temperature": 0, "logprobs": 3})
        assert status == 201, body
        lp = body["choices"][0]["logprobs"]
        assert len(lp["tokens"]) == 5
        assert len(lp["token_logprobs"]) == 5
        # dict keyed by decoded string: byte-level ids can collide, so
        # <= requested (best-probability entry kept per string)
        assert all(1 <= len(t) <= 3 for t in lp["top_logprobs"])
        assert lp["text_offset"][0] == 0
        # greedy: the chosen logprob is the best alternative's
        for chosen, top in zip(lp["token_logprobs"], lp["top_logprobs"]):
            assert chosen == max(top.values())
        assert all(v <= 0.0 for t in lp["top_logprobs"] for v in t.values())

        status, body = call("/v1/chat/completions",
                            {"messages": [{"role": "user", "content": "hi"}],
                             "max_tokens": 4, "temperature": 0,
                             "logprobs": True, "top_logprobs": 2})
        assert status == 201, body
        content = body["choices"][0]["logprobs"]["content"]
        assert len(content) == 4
        for entry in content:
            assert isinstance(entry["bytes"], list)
            assert len(entry["top_logprobs"]) == 2
            assert entry["logprob"] == entry["top_logprobs"][0]["logprob"]

        # chosen-only (completions logprobs=0): no top_logprobs attached
        status, body = call("/v1/completions",
                            {"prompt": "x", "max_tokens": 3,
                             "temperature": 0, "logprobs": 0})
        assert status == 201
        lp = body["choices"][0]["logprobs"]
        assert lp["top_logprobs"] is None and len(lp["token_logprobs"]) == 3

        # stop-string truncation: logprobs describe the RETURNED text.
        # Find a stop string that provably occurs mid-output by generating
        # without one first (greedy => the rerun reproduces it).
        status, full = call("/v1/completions",
                            {"prompt": "align", "max_tokens": 8,
                             "temperature": 0})
        assert status == 201
        full_text = full["choices"][0]["text"]
        printable = [c for c in full_text[2:] if c.isprintable() and c]
        if printable:  # random debug weights CAN emit only control bytes
            status, body = call("/v1/completions",
                                {"prompt": "align", "max_tokens": 8,
                                 "temperature": 0, "logprobs": 0,
                                 "stop": [printable[0]]})
            assert status == 201
            lp = body["choices"][0]["logprobs"]
            text = body["choices"][0]["text"]
            assert len(text) < len(full_text)  # really truncated
            # prefix containment, not equality: full-decode renders torn
            # multi-byte tails as U+FFFD while per-token decode drops them
            assert text.startswith("".join(lp["tokens"]))
            assert len(lp["token_logprobs"]) == len(lp["tokens"])
            assert len(lp["tokens"]) < 8

        # honest rejections
        status, _ = call("/v1/completions",
                         {"prompt": "x", "max_tokens": 2, "stream": True,
                          "logprobs": 1})
        assert status == 400
        # chat-style params on the completions surface
        status, _ = call("/v1/completions",
                         {"prompt": "x", "max_tokens": 2, "logprobs": True})
        assert status == 400
        status, _ = call("/v1/completions",
                         {"prompt": "x", "max_tokens": 2,
                          "top_logprobs": 3})
        assert status == 400
        # un-scoreable at admission: prompt+max_tokens beyond the largest
        # bucket 400s BEFORE generation, not 500 after
        status, body = call("/v1/completions",
                            {"prompt": "x" * 40, "max_tokens": 250,
                             "temperature": 0, "logprobs": 1})
        assert status == 400, body
        status, _ = call("/v1/chat/completions",
                         {"messages": [{"role": "user", "content": "x"}],
                          "top_logprobs": 2})
        assert status == 400
        status, _ = call("/v1/completions",
                         {"prompt": "x", "max_tokens": 2, "logprobs": 9})
        assert status == 400
    finally:
        app.shutdown()


def test_score_under_tensor_parallel_mesh():
    """Scoring on a TP engine: sharded params x replicated scoring cache —
    XLA inserts the collectives; values must match the single-device
    engine's bit-for-bit semantics (same rtol as TP serving parity)."""
    import jax

    from gofr_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices()[:2])
    params = llama_init(CFG, seed=0)
    eng_tp = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                            prefill_buckets=(16, 64), mesh=mesh)
    eng_tp.start()
    eng_1 = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                           prefill_buckets=(16, 64))
    eng_1.start()
    try:
        prompt, completion = [3, 1, 4, 1], [5, 9, 2, 6, 5]
        chosen_tp, ids_tp, lps_tp = eng_tp.score(prompt, completion, top=3)
        chosen_1, ids_1, _ = eng_1.score(prompt, completion, top=3)
        np.testing.assert_allclose(chosen_tp, chosen_1, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(ids_tp, ids_1)
    finally:
        eng_tp.stop()
        eng_1.stop()


def test_embed_matches_hidden_oracle(engine):
    """engine.embed == the last row of llama_forward_hidden, normalized;
    windowing (>128 tokens) must not change it."""
    import jax.numpy as jnp

    from gofr_tpu.models.llama import llama_forward_hidden

    rng = np.random.default_rng(7)
    for L in (5, 140):  # single-window and window-crossing
        toks = rng.integers(1, CFG.vocab_size, size=L).tolist()
        got = engine.embed(toks)

        k, v = init_kv_cache(CFG, 1, L)
        positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (1, L))
        hidden, _, _ = llama_forward_hidden(
            engine.params, CFG, jnp.asarray([toks], dtype=jnp.int32),
            positions, k, v)
        want = np.asarray(hidden[0, -1], dtype=np.float32)
        want = want / np.linalg.norm(want)

        assert got.shape == (CFG.dim,)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.linalg.norm(got), 1.0, rtol=1e-5)

    with pytest.raises(ValueError):
        engine.embed([])


def test_openai_embeddings_endpoint():
    import base64
    import importlib.util
    import json as _json
    import os
    import urllib.request

    from gofr_tpu.config import MockConfig

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "openai-server", "main.py")
    spec = importlib.util.spec_from_file_location("oai_emb_example", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    app = module.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "emb",
        "TPU_PLATFORM": "cpu", "MODEL_PRESET": "debug", "WARMUP": "false",
        "REQUEST_TIMEOUT": "60"}))
    app.start()

    def call(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{app.http_port}/v1/embeddings", method="POST",
            data=_json.dumps(body).encode())
        try:
            with urllib.request.urlopen(req, timeout=30) as resp:
                return resp.status, _json.loads(resp.read().decode())
        except urllib.error.HTTPError as err:
            return err.code, _json.loads(err.read().decode() or "null")

    try:
        status, body = call({"input": ["hello world", "hello world", "bye"]})
        assert status == 201, body
        assert body["object"] == "list" and len(body["data"]) == 3
        d = CFG.dim
        e0, e1, e2 = (body["data"][i]["embedding"] for i in range(3))
        assert len(e0) == d
        assert e0 == e1          # deterministic: same input, same vector
        assert e0 != e2
        assert abs(sum(x * x for x in e0) - 1.0) < 1e-3  # unit length
        assert body["usage"]["total_tokens"] > 0

        # base64 wire format round-trips to the float values
        status, b64body = call({"input": "hello world",
                                "encoding_format": "base64"})
        assert status == 201
        decoded = np.frombuffer(
            base64.b64decode(b64body["data"][0]["embedding"]), dtype="<f4")
        np.testing.assert_allclose(decoded, np.asarray(e0, dtype=np.float32),
                                   atol=1e-6)

        assert call({"input": []})[0] == 400
        assert call({"input": ""})[0] == 400
        assert call({"input": "x", "encoding_format": "int8"})[0] == 400
        assert call({"input": "y" * 4000})[0] == 400  # over the bucket cap
    finally:
        app.shutdown()


def test_warmup_scoring_precompiles_every_bucket():
    """After warmup_scoring, client score/embed calls at any bucket hit
    compiled programs — the executor cache does not grow."""
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(16, 32))
    eng.start()
    try:
        ran = eng.warmup_scoring()
        assert ran == 4  # (score + embed) x 2 buckets
        size = eng.executor.cache_size
        # EVERY client top value must hit the warmed programs (the program
        # always computes the max K; the host slices) — top=1 is the most
        # common client path (chat logprobs without top_logprobs)
        eng.score([1, 2, 3], [4, 5], top=1)
        eng.score([1, 2, 3], [4, 5], top=5)
        eng.score([1] * 20, [9] * 8, top=20)  # second bucket, max top
        eng.embed([7, 8, 9])
        assert eng.executor.cache_size == size  # nothing new compiled
    finally:
        eng.stop()
