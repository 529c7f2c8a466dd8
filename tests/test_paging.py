"""Paged KV serving: allocator ledger, engine behavior (the kernels'
numerics: tests/test_paging_read.py, tests/test_paging_write.py).

The load-bearing assertions (VERDICT r2 missing #4 "done" criteria):
  - engine output == the plain cached reference's, token for token
  - HBM pool bytes and page usage track the SUM of live contexts, not
    max_seq x n_slots (mixed 16-token and long contexts share one pool)
  - admission defers when the pool is exhausted and resumes on free
"""

import time

import jax
import jax.numpy as jnp
import pytest
from paging_cases import _folding

from gofr_tpu.models.llama import (LlamaConfig, init_kv_cache,
                                   llama_decode_step, llama_init,
                                   llama_prefill)
from gofr_tpu.tpu.paging import PageAllocator, PagedLLMEngine

CFG = LlamaConfig.debug()


class MockLogger:
    def debugf(self, *a): pass
    def infof(self, *a): pass
    def warnf(self, *a): pass
    def errorf(self, *a): pass


# -- allocator ----------------------------------------------------------------
def test_page_allocator_ledger():
    a = PageAllocator(n_pages=9, page_size=16)
    assert a.free_pages == 8  # page 0 reserved as garbage
    assert a.garbage_page == 0
    assert 0 not in a.alloc(8)  # garbage page is never handed out
    a = PageAllocator(n_pages=9, page_size=16)
    assert a.pages_for(1) == 1 and a.pages_for(16) == 1 and a.pages_for(17) == 2
    got = a.alloc(5)
    assert len(got) == 5 and a.free_pages == 3
    assert a.alloc(4) is None          # insufficient: nothing taken
    assert a.free_pages == 3
    a.release(got[:2])
    assert a.free_pages == 5
    assert a.used_pages == 3


# -- engine -------------------------------------------------------------------
def _make_paged(**kw):
    params = llama_init(CFG, seed=0)
    defaults = dict(n_slots=4, max_seq_len=64, prefill_buckets=(8, 16),
                    page_size=8, logger=MockLogger())
    defaults.update(kw)
    eng = PagedLLMEngine(params, CFG, **defaults)
    eng.start()
    return eng


def _reference_greedy(params, prompt, n):
    """The plain cached reference (models/llama.py `llama_prefill` then
    `llama_decode_step` over one contiguous cache): n greedy tokens."""
    k, v = init_kv_cache(CFG, 1, 64)
    logits, k, v = llama_prefill(params, CFG,
                                 jnp.asarray([prompt], jnp.int32), k, v)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n - 1):
        logits, k, v = llama_decode_step(
            params, CFG, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([len(prompt) + i], jnp.int32), k, v)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_paged_engine_matches_the_cached_reference():
    """Token-for-token parity with the plain cached forward under greedy
    decode."""
    params = llama_init(CFG, seed=0)
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [1, 2]]
    want = [_reference_greedy(params, p, 8) for p in prompts]

    paged = _make_paged()
    try:
        got = [paged.generate(p, max_new_tokens=8, temperature=0.0)
               for p in prompts]
    finally:
        paged.stop()
    assert got == want


@pytest.mark.parametrize("block", [1, 4, 16])
def test_every_decode_block_size_serves_the_references_tokens(block):
    """The block's tail is how the decode write works at every block
    size: pages of 8 tokens, so a block of 16 crosses two boundaries and a
    block of 1 flushes one column; the tokens are the cached reference's, and
    `/debug/engine` and the step ledger say how many tokens a page write
    placed."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    params = llama_init(CFG, seed=0)
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [1, 2]]
    want = [_reference_greedy(params, p, 20) for p in prompts]
    paged = _make_paged(decode_block_size=block)
    try:
        requests = [paged.submit(p, max_new_tokens=20, temperature=0.0)
                    for p in prompts]
        got = [r.result(timeout_s=300) for r in requests]
        assert engine_snapshot(paged)["paging"]["write"]["page_writes"] > 0
    finally:
        paged.stop()
    # read once the loop has stopped: the last block's record is closed
    write = paged.paging_snapshot()["write"]
    records = paged.steps.records(recent=256)
    assert got == want
    assert write["tokens"] >= 3 * 19 and write["page_writes"] > 0
    assert write["tokens_per_page_write"] == round(
        write["tokens"] / write["page_writes"], 3)
    # a block of b tokens over pages of 8 is written in 1 + (b - 1) // 8
    # pages, or one more where it started late in its page
    fewest = 1 + (block - 1) // 8
    assert (block / (fewest + 1) - 1e-3 <= write["tokens_per_page_write"]
            <= block / fewest + 1e-3)
    assert sum(r.page_writes for r in records) == write["page_writes"]
    assert all(r.page_writes == 0 for r in records if r.phase != "decode")
    # and the reads: under the table of 8 these requests take a fold is 8
    # of the tiny pages, every step of every block, both layers
    read = paged.paging_snapshot()["read"]
    assert read["pages_per_fold"] == 8
    assert read["folds"] >= CFG.n_layers * write["tokens"]
    assert 0 < read["narrowed_folds"] <= read["folds"]
    assert 0 < read["fold_live_share"] <= 1


def test_paged_engine_concurrent_mixed_lengths():
    """Mixed short/long contexts share the pool; usage tracks the SUM of
    live pages (a short context is NOT billed for the longest's length)."""
    eng = _make_paged(n_slots=4, max_seq_len=64, page_size=8,
                      n_pages=4 * 8 + 1)
    try:
        long_req = eng.submit(list(range(1, 15)), max_new_tokens=24,
                              temperature=0.0)   # 38 tokens -> 5 pages
        short_req = eng.submit([3, 4], max_new_tokens=4,
                               temperature=0.0)  # 6 tokens -> 1 page
        while not (long_req.generated and short_req.generated):
            time.sleep(0.01)
        # while both are live: 5 + 1 pages, not 2 x pages(max_seq)
        assert eng.allocator.used_pages == 6
        short_req.result(timeout_s=60)
        long_req.result(timeout_s=60)
        deadline = time.time() + 5
        while eng.allocator.used_pages and time.time() < deadline:
            time.sleep(0.01)
        assert eng.allocator.used_pages == 0  # everything returned
    finally:
        eng.stop()


def test_paged_pool_bytes_track_budget_not_dense_worstcase():
    """The pool is the explicit budget: sized at n_pages, independent of
    n_slots x max_seq_len."""
    eng = _make_paged(n_slots=4, max_seq_len=64, page_size=8, n_pages=9)
    try:
        dense_equiv = 2 * (CFG.n_layers * 4 * CFG.n_kv_heads * CFG.head_dim
                           * 64 * 4)  # f32 dense cache bytes at max_seq
        assert eng.pool_bytes() < dense_equiv / 3
    finally:
        eng.stop()


def test_paged_admission_defers_until_pages_free():
    """With a pool that fits ONE request's reservation, the second request
    must wait (not fail) and complete after the first releases."""
    # 6 tokens @ ps=8 -> 1 page; pool has 2 usable pages; each request
    # reserves 2 pages (2 + 4 tokens... make it explicit:
    eng = _make_paged(n_slots=4, max_seq_len=64, page_size=8,
                      n_pages=3)  # 2 usable + garbage
    try:
        r1 = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=8,
                        temperature=0.0)  # 16 tokens -> 2 pages (all of them)
        r2 = eng.submit([9, 10], max_new_tokens=4,
                        temperature=0.0)  # 6 tokens -> 1 page: must wait
        out1 = r1.result(timeout_s=120)
        out2 = r2.result(timeout_s=120)
        assert len(out1) == 8 and len(out2) == 4
        # waiting was observed (metric is best-effort; ordering is the test)
        assert r2.finished_at >= r1.finished_at
    finally:
        eng.stop()


def test_paged_submit_rejects_impossible_reservation():
    """A request that could NEVER fit the pool is rejected at submit —
    deferring it would head-of-line-block all later admission forever."""
    eng = _make_paged(n_slots=2, max_seq_len=64, page_size=8, n_pages=3)
    try:
        with pytest.raises(ValueError, match="pool has only 2 usable"):
            eng.submit(list(range(1, 20)), max_new_tokens=32)  # 7 pages
        # a fitting request still serves
        assert len(eng.generate([1, 2], max_new_tokens=3)) == 3
    finally:
        eng.stop()


def test_paged_engine_span_and_budget_plan():
    """submit(span=) carries the trace surface, and a budget makes a plan
    whose only transient is the widest prefill's."""
    from gofr_tpu.tracing import InMemoryExporter, Tracer

    tracer = Tracer(exporter=InMemoryExporter())
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64, page_size=8,
                         prefill_buckets=(8, 16), logger=MockLogger(),
                         tracer=tracer, budget_bytes=64 << 20)
    eng.start()
    try:
        assert eng.plan is not None and eng.plan.peak_bytes == (
            eng.plan.params_bytes + eng.plan.cache_bytes_max
            + eng.plan.prefill_temp_bytes)
        span = tracer.start_span("req")
        out = eng.submit([1, 2, 3], max_new_tokens=4, span=span).result(
            timeout_s=60)
        assert len(out) == 4
        assert span.attributes["tpu.prefill_bucket"] == 8
        assert "batch.id" in span.attributes
    finally:
        eng.stop()


def test_paged_explicit_pool_must_fit_budget():
    """An explicit n_pages bypasses the plan's sizing; the constructor must
    still reject a pool that cannot fit the budget."""
    params = llama_init(CFG, seed=0)
    with pytest.raises(ValueError, match="does not fit the budget"):
        PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64, page_size=8,
                       n_pages=100_000, logger=MockLogger(),
                       budget_bytes=32 << 20)


def test_paged_engine_with_tp_mesh():
    """The pool is a STACKED array; mesh placement must shard its KV-head
    axis whole, not iterate it into per-layer slices."""
    from gofr_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices()[:2])
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64, page_size=8,
                         prefill_buckets=(8,), mesh=mesh, logger=MockLogger())
    eng.start()
    try:
        assert hasattr(eng.k_cache, "shape")  # still one stacked array
        shard = eng.k_cache.sharding.shard_shape(eng.k_cache.shape)
        assert shard[2] == CFG.n_kv_heads // 2
        assert eng.pool_bytes() > 0
        out = eng.generate([1, 2, 3], max_new_tokens=4, temperature=0.0)
        assert len(out) == 4
    finally:
        eng.stop()


def test_paged_engine_streaming_and_stop_tokens():
    eng = _make_paged()
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=16, temperature=0.0)
        toks = list(req.stream(timeout_s=60))
        assert len(toks) == 16
        want = eng.generate([1, 2, 3], max_new_tokens=16, temperature=0.0)
        assert toks == want
        stop = eng.generate([1, 2, 3], max_new_tokens=16, temperature=0.0,
                            stop_tokens={want[2]})
        assert stop == want[:3]
    finally:
        eng.stop()


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_paged_q8_engine_matches_paged_fp_closely():
    """INT8 paged pool: prefill is full-precision into the quantized splice
    (first token exact vs the fp paged engine); decode reads dequant-folded
    pages — near-ties may flip, bulk must agree, and pages must free."""
    import dataclasses

    cfg_q8 = dataclasses.replace(CFG, kv_dtype="int8")
    prompts = [[1, 2, 3, 4, 5], list(range(7, 40)), [9]]

    def serve(use_cfg):
        params = llama_init(CFG, seed=0)
        eng = PagedLLMEngine(params, use_cfg, page_size=16, n_slots=4,
                             max_seq_len=128, prefill_buckets=(8, 64),
                             decode_block_size=4)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=10, temperature=0.0)
                    for p in prompts]
            outs = [r.result(timeout_s=300) for r in reqs]
            import time as _t
            deadline = _t.time() + 10
            while eng.allocator.used_pages and _t.time() < deadline:
                _t.sleep(0.02)
            assert eng.allocator.used_pages == 0, "pages leaked"
            return outs
        finally:
            eng.stop()

    fp = serve(CFG)
    q8 = serve(cfg_q8)
    assert [len(t) for t in q8] == [len(t) for t in fp]
    for f, q in zip(fp, q8):
        assert f[0] == q[0]          # full-precision prefill: exact
    total = sum(len(t) for t in fp)
    agree = sum(a == b for f, q in zip(fp, q8) for a, b in zip(f, q))
    # What int8 KV dequant actually guarantees: per-(vector, axis) scales
    # bound the cache quantization error at ~0.4% of each vector's max
    # (|x - dq(x)| <= scale/2, scale = max|x|/127), which perturbs logits
    # only slightly — but on this random-weight debug model the top-2
    # logit gap is often inside that perturbation, and ONE flipped
    # near-tie argmax changes the whole autoregressive suffix for that
    # stream (divergence compounds; agreement below is positional). So
    # the hard guarantees are structural — exact first token (prefill is
    # full precision), equal lengths, bitwise determinism — and the bulk
    # agreement bound must tolerate one early flip per stream: >30%
    # catches a broken dequant path (near-zero agreement) without flaking
    # on a legitimate near-tie flip.
    assert agree / total > 0.3, f"only {agree}/{total} agree"
    assert q8 == serve(cfg_q8)       # deterministic


def test_the_int8_engine_counts_its_reads_a_token_longer_each_step(
        monkeypatch):
    """`paging.read` where there is no block's tail: step t of a block
    attends what the block found and the t + 1 tokens written since, all
    in pages. Six blocks of 4 over tiny pages under a table of 16, a fold
    given the weight of 8 of them: the row grows from 4 pages to 10, so
    its last fold is computed at 4 pages, then at 8, then (a second fold
    of one page, then two) at 2, counted here a step at a time."""
    import dataclasses

    engine = PagedLLMEngine(
        llama_init(CFG, seed=0), dataclasses.replace(CFG, kv_dtype="int8"),
        page_size=16, n_slots=4, max_seq_len=256, prefill_buckets=(8, 64),
        decode_block_size=4)
    _folding(monkeypatch, [x[0] for x in (engine.k_cache, engine.v_cache,
                                          engine.k_scale, engine.v_scale)],
             8)
    folds = narrowed = tokens = lanes = short = 0
    for found in (60, 63, 112, 126, 130, 150):
        engine.slots[2].length = found
        engine._note_page_reads([(2, None)], 4, 16)
        for attended in range(found + 1, found + 5):
            pages = -(-attended // 16)
            last = pages % 8 or 8
            width = min(w for w in (2, 4, 8) if w >= last)
            folds += -(-pages // 8)
            short += pages <= 8
            narrowed += width < 8
            tokens += attended
            lanes += (pages - last + width) * 16
    assert narrowed == 4 + 1 + 0 + 2 + 4 + 4 and folds == 3 * 4 + 6 + 2 * 8
    read = engine.paging_snapshot()["read"]
    # the four slots are one group of the kernel's, slot 2 its live row:
    # the group walks whenever that row holds a second fold
    assert short == 4 + 4 + 4 + 2
    assert read == {"pages_per_fold": 8, "folds": CFG.n_layers * folds,
                    "narrowed_folds": CFG.n_layers * narrowed,
                    "fold_live_share": round(tokens / lanes, 4),
                    "short_row_share": round(short / 24, 4),
                    "groups_split_share": round(1 - short / 24, 4)}


def test_the_short_rows_and_the_groups_that_walk_are_counted_on_host_arrays():
    """`_groups`: from the pages every kernel row walks a read and which
    rows hold a request, the row reads that took the short rows' step and
    the groups that walked. Twelve rows, two reads each, folds of 8 pages:
    a group whose second read finds a row on its ninth page; a group
    without a request (counted nowhere); a group with a live row that
    holds no page yet (it has no fold to join: the group walks)."""
    import numpy as np

    from gofr_tpu.tpu.paging import _groups

    pages = np.array([[1, 1], [8, 9], [0, 0], [3, 3],
                      [0, 0], [0, 0], [0, 0], [0, 0],
                      [2, 2], [4, 4], [8, 8], [0, 0]])
    held = np.array([1, 1, 0, 1, 0, 0, 0, 0, 1, 1, 1, 1], bool)
    # four rows a grid step: 3 row reads of group 0's first read
    assert _groups(pages, held, 8, 4) == (3, 14, 3, 4)
    # a row a step: every row a group of its own
    assert _groups(pages, held, 8, 1) == (11, 14, 3, 14)
    # two a step: rows 0-1 split on the second read, rows 10-11 on both
    assert _groups(pages, held, 8, 2) == (2 + 2 + 4, 14, 1 + 2, 8)
    # a fold of 2 pages: only the rows of one or two pages fit
    assert _groups(pages, held, 2, 4) == (0, 14, 4, 4)
    assert _groups(pages, held, 2, 1) == (4, 14, 10, 14)


def test_paged_priority_no_head_of_line_inversion():
    """A small high-priority request must admit while a big low-priority
    request stays parked on page exhaustion — and the parked one still
    completes once pages free (no starvation)."""
    params = llama_init(CFG, seed=0)
    # tiny pool: 1 garbage + 6 usable pages of 8 tokens
    eng = PagedLLMEngine(params, CFG, page_size=8, n_pages=7, n_slots=2,
                         max_seq_len=64, prefill_buckets=(8, 32),
                         decode_block_size=2)
    eng.start()
    try:
        # occupy most of the pool: 30 prompt + 10 new = 5 pages
        hog = eng.submit(list(range(1, 31)), max_new_tokens=10,
                         temperature=0.0)
        deadline = time.time() + 60
        while hog.admitted_at is None and time.time() < deadline:
            time.sleep(0.005)
        # big low-priority: needs 5 pages -> parks (1 free page)
        big_low = eng.submit(list(range(1, 29)), max_new_tokens=10,
                             temperature=0.0, priority=5)
        time.sleep(0.3)
        assert big_low.admitted_at is None, "should be parked on pages"
        # small high-priority: needs 1 page -> must NOT wait behind big_low
        small_high = eng.submit([7, 7], max_new_tokens=4, temperature=0.0,
                                priority=0)
        out = small_high.result(timeout_s=120)
        assert len(out) == 4
        assert big_low.admitted_at is None or \
            small_high.admitted_at <= big_low.admitted_at
        # and the parked request eventually runs to completion
        assert len(big_low.result(timeout_s=120)) == 10
    finally:
        eng.stop()
