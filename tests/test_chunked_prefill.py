"""Chunked prefill: numerics parity with fused admission + interleaving.

Opt-in engine mode (chunk_prefill_tokens > 0): a long prompt is admitted
as several bounded chunk dispatches against bucket-sized job temps (one
page scatter at the final chunk), so decode blocks interleave instead of
stalling behind one huge prefill — the TTFT lever for mixed traffic. These
tests pin the hard invariants on CPU: token-for-token parity with the fused
path (including prompts whose last token falls in an EARLY chunk), and
correctness while another request is mid-decode (the reserved slot's zero
table row keeps lock-step junk in the garbage page).
"""

import time

import pytest

from gofr_tpu.logging import MockLogger
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()


def _make(chunk=0, **kw):
    params = llama_init(CFG, seed=0)
    defaults = dict(n_slots=4, max_seq_len=128, prefill_buckets=(8, 32),
                    decode_block_size=4, page_size=16, logger=MockLogger())
    defaults.update(kw)
    eng = PagedLLMEngine(params, CFG, chunk_prefill_tokens=chunk, **defaults)
    eng.start()
    return eng


PROMPTS = [
    list(range(1, 4)),      # len 3: bucket 8, below chunk size — fused path
    list(range(1, 21)),     # len 20: bucket 32, last token in chunk 3 of 4
    list(range(1, 31)),     # len 30: bucket 32, last token in final chunk
    list(range(40, 49)),    # len 9: bucket 32 via... no, bucket 16 absent ->
                            # next_bucket gives 32; last token in chunk 2
]


def test_chunked_matches_fused_token_for_token():
    fused = _make(chunk=0)
    try:
        want = [fused.generate(p, max_new_tokens=8, temperature=0.0)
                for p in PROMPTS]
    finally:
        fused.stop()

    chunked = _make(chunk=8)
    try:
        got = [chunked.generate(p, max_new_tokens=8, temperature=0.0)
               for p in PROMPTS]
    finally:
        chunked.stop()
    assert got == want


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_chunked_admission_during_active_decode():
    """A chunked admission lands while another request is mid-decode: the
    decoding request's output must be untouched (the reserved slot's zero
    table row diverts junk to the garbage page) and the new request must
    match the fused engine."""
    fused = _make(chunk=0)
    try:
        want_long = fused.generate([5, 6, 7], max_new_tokens=40,
                                   temperature=0.0)
        want_new = fused.generate(list(range(1, 25)), max_new_tokens=8,
                                  temperature=0.0)
    finally:
        fused.stop()

    eng = _make(chunk=8, decode_block_size=2)
    try:
        long_req = eng.submit([5, 6, 7], max_new_tokens=40, temperature=0.0)
        while long_req.generated < 4:   # ensure decode is genuinely running
            time.sleep(0.01)
        new_req = eng.submit(list(range(1, 25)), max_new_tokens=8,
                             temperature=0.0)
        assert new_req.result(timeout_s=120) == want_new
        assert long_req.result(timeout_s=120) == want_long
    finally:
        eng.stop()


def test_chunked_queue_wait_stamped_once():
    """admitted_at is stamped at the FIRST chunk dispatch (queue wait ends
    there) and never overwritten by the final chunk's slot binding."""
    from gofr_tpu.metrics import new_metrics_manager

    manager = new_metrics_manager()
    manager.new_histogram("app_tpu_queue_wait_seconds",
                          "submit-to-admission wait", (0.01, 0.1, 1, 10))
    eng = _make(chunk=8, metrics=manager)
    try:
        req = eng.submit(list(range(1, 30)), max_new_tokens=3,
                         temperature=0.0)
        req.result(timeout_s=120)
        assert req.admitted_at is not None
        assert req.admitted_at <= req.first_token_at
        # exactly ONE queue-wait observation: a re-stamp at final-chunk
        # binding would both overwrite admitted_at and double the histogram
        hist = eng.metrics.get("app_tpu_queue_wait_seconds")
        assert hist is not None
        assert sum(e["count"] for e in hist.series.values()) == 1
    finally:
        eng.stop()


def test_paged_chunked_releases_pages_and_q8_composes():
    """Chunked admission over the INT8 pool (values+scales scatter once at
    the final chunk), and page accounting: all pages return to the free
    list after the chunked requests finish."""
    import dataclasses

    cfg_q8 = dataclasses.replace(CFG, kv_dtype="int8")
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, cfg_q8, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32), decode_block_size=4,
                         page_size=16, chunk_prefill_tokens=8,
                         logger=MockLogger())
    eng.start()
    try:
        out = [eng.submit(p, max_new_tokens=8, temperature=0.0)
               for p in PROMPTS]
        got = [r.result(timeout_s=300) for r in out]
        assert all(len(t) == 8 for t in got)
    finally:
        eng.stop()
    assert eng.allocator.used_pages == 0, "chunked admission leaked pages"


def test_paged_chunk_warmup_compiles_variants():
    eng = _make(chunk=8)
    try:
        eng.warmup()
        names = list(eng.executor.cache_info())
        assert any("llama-paged-chunk-8x1-b32" in n for n in names)
        assert any("llama-paged-chunk-final-8x1-b32" in n for n in names)
        # the fused program for the chunk-routed bucket is NOT warmed
        assert not any("llama-paged-prefill-32x" in n for n in names)
    finally:
        eng.stop()


def test_chunk_size_must_divide_buckets():
    params = llama_init(CFG, seed=0)
    with pytest.raises(ValueError, match="must divide"):
        PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                       prefill_buckets=(8, 24), chunk_prefill_tokens=8 + 8,
                       logger=MockLogger())


def test_chunked_stop_unblocks_mid_prefill_clients():
    """stop() while a chunk job is mid-flight must fail its requests, not
    strand their clients."""
    eng = _make(chunk=8)
    try:
        reqs = [eng.submit(list(range(1, 30)), max_new_tokens=4,
                           temperature=0.0) for _ in range(3)]
    finally:
        eng.stop()
    for req in reqs:
        try:
            out = req.result(timeout_s=30)
            assert len(out) <= 4  # finished before the stop: also fine
        except RuntimeError:
            pass  # "engine stopped" — the required non-hang outcome
