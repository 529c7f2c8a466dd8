"""Disaggregated prefill/decode (tpu/disagg.py): the two-engine split.

The load-bearing assertions (ISSUE 9 "done" criteria):
  - a hand-off round-trips the transport bit-exactly (envelope + page
    blobs), and the disagg pair's served tokens equal the colocated
    engine's goldens token-for-token
  - the decode pool's step ledger contains ZERO prefill steps on the
    healthy path — the invariant the whole split exists to buy
  - every failure mode (corrupt blob, lost payload, dead prefill worker)
    degrades to a recompute fallback on the decode pool: counted, traced,
    and NEVER a failed stream
"""

import json
import threading
import time
import types

import numpy as np
import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.disagg import (HANDOFF_VERSION, DisaggRouter,
                                 QueueTransport, decode_handoff,
                                 encode_handoff)
from gofr_tpu.tpu.kvtier import PageBlob, decode_blob
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()

PROMPTS = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [1, 2]]


class MockLogger:
    def debugf(self, *a): pass
    def infof(self, *a): pass
    def warnf(self, *a): pass
    def errorf(self, *a): pass


def _engine(role, **kw):
    base = dict(n_slots=4, max_seq_len=64, prefill_buckets=(8, 16),
                page_size=8, logger=MockLogger())
    base.update(kw)
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, disagg_role=role,
                         **base)
    eng.start()
    return eng


@pytest.fixture(scope="module")
def goldens():
    """[(prompt, greedy max_new=8 tokens)] as a COLOCATED engine of the
    same seed and sizes serves them, computed here: a literal list ties
    the file to one JAX version's arithmetic."""
    eng = _engine("")
    try:
        return [(prompt, eng.generate(prompt, max_new_tokens=8,
                                      temperature=0.0))
                for prompt in PROMPTS]
    finally:
        eng.stop()


def _pair(**router_kw):
    pre = _engine("prefill")
    dec = _engine("decode")
    router = DisaggRouter(pre, dec, **router_kw)
    router.start()
    return pre, dec, router


def _teardown(pre, dec, router):
    router.stop()
    if router.worker.alive:
        pre.stop()
    dec.stop()


def _collect(req, timeout_s=120):
    return list(req.stream(timeout_s=timeout_s))


# -- fast no-engine units (`-m disagg` inner loop) ----------------------------


@pytest.mark.disagg
def test_handoff_envelope_round_trips_the_queue():
    rng = np.random.default_rng(0)
    blobs = [PageBlob(tokens=[3, 1, 4, 1, 5],
                      k=rng.normal(size=(2, 2, 4, 8)).astype(np.float32),
                      v=rng.normal(size=(2, 2, 4, 8)).astype(np.float32))
             for _ in range(2)]
    request = types.SimpleNamespace(
        id=7, prompt_tokens=[3, 1, 4, 1, 5], emitted=[9],
        max_new_tokens=16, temperature=0.0, stop_tokens={2},
        priority=1, min_tokens=0, top_p=0.0, top_k=0,
        traceparent="00-" + "ab" * 16 + "-" + "cd" * 8 + "-01",
        gen_span=None)

    transport = QueueTransport(maxsize=4)
    assert transport.publish(encode_handoff(request, blobs, n_ctx=6))
    body = decode_handoff(transport.poll(timeout_s=1.0))

    assert body is not None and body["v"] == HANDOFF_VERSION
    assert body["rid"] == 7 and body["n_ctx"] == 6
    assert body["traceparent"] == request.traceparent
    assert body["spec"]["prompt"] == [3, 1, 4, 1, 5]
    assert body["spec"]["emitted"] == [9]
    assert body["spec"]["stop"] == [2]
    for raw, original in zip(body["blobs"], blobs):
        decoded = decode_blob(raw)
        assert decoded is not None
        assert decoded.tokens == original.tokens
        np.testing.assert_array_equal(decoded.k, original.k)
        np.testing.assert_array_equal(decoded.v, original.v)


@pytest.mark.disagg
def test_decode_handoff_rejects_torn_and_foreign_payloads():
    assert decode_handoff(b"\xff\xfe not json") is None
    assert decode_handoff("[1, 2, 3]") is None
    assert decode_handoff(json.dumps({"v": HANDOFF_VERSION + 1,
                                      "rid": 1, "spec": {}})) is None
    assert decode_handoff(json.dumps({"v": HANDOFF_VERSION,
                                      "spec": {}})) is None


@pytest.mark.disagg
def test_queue_transport_sheds_when_full():
    transport = QueueTransport(maxsize=1)
    assert transport.publish("a")
    assert not transport.publish("b")  # full == False, never blocks
    assert transport.depth() == 1


# -- the split pair on a real (CPU) engine ------------------------------------


def test_disagg_pair_matches_colocated_goldens_with_zero_decode_prefills(
        goldens):
    pre, dec, router = _pair()
    try:
        reqs = [router.submit(prompt, max_new_tokens=len(golden),
                              temperature=0.0)
                for prompt, golden in goldens]
        for (prompt, golden), req in zip(goldens, reqs):
            assert _collect(req) == golden, f"prompt {prompt}"
        assert pre.handoffs_total == len(goldens)
        assert router.coordinator.consumed_total == len(goldens)
        assert (router.fallbacks_total + pre.handoff_fallbacks_total
                + dec.handoff_fallbacks_total) == 0
    finally:
        _teardown(pre, dec, router)
    # the invariant the split buys: the decode pool NEVER ran a prefill
    snap = dec.steps.snapshot(recent=0)
    assert snap["summary"].get("prefill", {}).get("steps", 0) == 0
    assert snap["summary"].get("decode", {}).get("steps", 0) > 0
    # and the prefill pool never burned a decode step on handed-off work
    pre_snap = pre.steps.snapshot(recent=0)
    assert pre_snap["summary"].get("prefill", {}).get("steps", 0) > 0


class _CorruptTransport(QueueTransport):
    """Delivers every hand-off, but flips bytes inside the first page
    blob — crc32 on the decode side must catch it per-page."""

    def publish(self, payload):
        body = json.loads(payload)
        if body.get("blobs"):
            body["blobs"][0] = body["blobs"][0][:-8] + "AAAAAAAA"
        return super().publish(json.dumps(body))


def test_corrupt_blob_degrades_to_recompute_not_failure(goldens):
    pre, dec, router = _pair(transport=_CorruptTransport(maxsize=8))
    try:
        prompt, golden = goldens[0]
        req = router.submit(prompt, max_new_tokens=len(golden),
                            temperature=0.0)
        assert _collect(req) == golden  # recompute serves the SAME tokens
        assert router.fallbacks_total >= 1
    finally:
        _teardown(pre, dec, router)


class _LossyTransport(QueueTransport):
    """Claims success and drops every payload — the stale reaper must
    rescue the request (recompute) before the client notices."""

    def publish(self, payload):
        return True


def test_lost_handoff_rescued_by_stale_reaper(goldens):
    pre, dec, router = _pair(transport=_LossyTransport(),
                             handoff_timeout_s=0.3)
    try:
        prompt, golden = goldens[1]
        req = router.submit(prompt, max_new_tokens=len(golden),
                            temperature=0.0)
        assert _collect(req) == golden
        assert router.fallbacks_total >= 1
        assert router.coordinator.consumed_total == 0  # nothing arrived
    finally:
        _teardown(pre, dec, router)


def test_prefill_worker_death_never_fails_a_stream(goldens):
    pre, dec, router = _pair()
    try:
        in_flight = [router.submit(prompt, max_new_tokens=len(golden),
                                   temperature=0.0)
                     for prompt, golden in goldens * 2]
        router.worker.kill()  # mid-flight: sweep + drain re-route survivors
        post_kill = [router.submit(prompt, max_new_tokens=len(golden),
                                   temperature=0.0)
                     for prompt, golden in goldens]
        for (prompt, golden), req in zip(goldens * 3, in_flight + post_kill):
            assert _collect(req) == golden, f"prompt {prompt}"
            assert req.error is None
        assert router.fallbacks_total >= len(goldens)  # post-kill at least
    finally:
        _teardown(pre, dec, router)


def test_traceparent_survives_the_hop(goldens):
    sent = "00-" + "1234567890abcdef" * 2 + "-" + "fedcba0987654321" + "-01"
    captured = []

    class _Tap(QueueTransport):
        def publish(self, payload):
            captured.append(payload)
            return super().publish(payload)

    pre, dec, router = _pair(transport=_Tap())
    try:
        prompt, golden = goldens[2]
        req = router.submit(prompt, max_new_tokens=len(golden),
                            temperature=0.0, traceparent=sent)
        assert _collect(req) == golden
    finally:
        _teardown(pre, dec, router)
    assert len(captured) == 1
    assert decode_handoff(captured[0])["traceparent"] == sent
