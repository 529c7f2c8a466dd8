"""What a request's record says of the device's queue (ISSUE 37).

`granted_at` splits the wait after pickup into parked (a slot, pages, the
cap) and dispatch (the loop's own prep, lookup and enqueue), so TTFT tiles
four ways; `ahead_steps` / `ahead_prefills` are the deque's contents when
the request's prefill program was enqueued; `overrun_steps` are the decode
steps its row computed after its last token, and they are conserved
against the row-steps the decode blocks computed.
"""

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.flightrecorder import FlightRecorder
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.utilization import engine_snapshot

CFG = LlamaConfig.debug()
STAMPS = ("granted_at", "ahead_steps", "ahead_prefills", "overrun_steps")


def _engine(**kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("decode_block_size", 4)
    kw.setdefault("page_size", 16)
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG,
                         flight_recorder=FlightRecorder(capacity=64), **kw)
    return eng


def _spy_on_binds(eng):
    """Each `_bind_slots` call as (the deque's (kind, steps) entries just
    before it, the wave's requests)."""
    seen = []
    bind = eng._bind_slots

    def spy(slots_idx, batch, *args, **kwargs):
        before = [(e[0], None if e[0] == "prefill"
                   else e[3] + (e[0] == "verify")) for e in eng._inflight]
        bind(slots_idx, batch, *args, **kwargs)
        seen.append((before, list(batch)))

    eng._bind_slots = spy
    return seen


def _serve(eng, budgets, first=2):
    """`first` requests of two buckets queued before the loop starts (one
    turn admits them as two waves), the rest while those decode."""
    prompts = [[1 + i] * (3 if i % 2 else 12) for i in range(len(budgets))]
    requests = [eng.submit(prompts[i], max_new_tokens=budgets[i])
                for i in range(first)]
    eng.start()
    try:
        requests += [eng.submit(prompts[i], max_new_tokens=budgets[i])
                     for i in range(first, len(budgets))]
        for request in requests:
            request.result(timeout_s=120)
    finally:
        eng.stop()
    return requests


@pytest.fixture(scope="module")
def served():
    eng = _engine(pipeline_depth=4)
    binds = _spy_on_binds(eng)
    requests = _serve(eng, [9, 14, 5, 11, 7, 16, 6, 10])
    return eng, binds, requests


def test_ttft_tiles_four_ways(served):
    eng, _, requests = served
    done = {rec.id: rec for rec in eng.recorder._done}
    assert len(done) == len(requests)
    for request in requests:
        rec = done[request.id]
        assert (rec.enqueued_at <= rec.dequeued_at <= rec.granted_at
                <= rec.admitted_at <= rec.first_token_at)
        assert rec.granted_at == request.granted_at
        phases = rec.phases()
        four = (phases["pickup_s"] + phases["parked_s"]
                + phases["dispatch_s"] + phases["prefill_s"])
        assert four == pytest.approx(rec.ttft_s(), abs=1e-6)
        assert phases["parked_s"] == pytest.approx(
            rec.granted_at - rec.dequeued_at, abs=1e-9)
        assert phases["pickup_s"] + phases["parked_s"] \
            + phases["dispatch_s"] == pytest.approx(phases["queue_s"],
                                                    abs=1e-9)
        detail = rec.detail()
        assert detail["phases"]["dispatch_s"] == phases["dispatch_s"]
        for key in STAMPS[1:]:
            assert detail[key] == getattr(rec, key)
        admitted = next(e for e in detail["events"]
                        if e["event"] == "admitted")
        assert admitted["ahead_steps"] == rec.ahead_steps
        assert admitted["ahead_prefills"] == rec.ahead_prefills
    rows = {row["id"]: row for row in eng.recorder.timeline_records()}
    for rec in done.values():
        for key in STAMPS:
            assert rows[rec.id][key] == getattr(rec, key)


def test_a_record_without_the_grant_keeps_the_three_way_split():
    class Request:
        id, prompt_tokens, max_new_tokens, priority = 7, [1, 2], 4, 0
        span = gen_span = traceparent = error = None
        enqueued_at, dequeued_at, admitted_at = 10.0, 10.25, 10.75
        first_token_at, finished_at, generated = 11.0, 12.0, 4

    recorder = FlightRecorder(capacity=4)
    request = Request()
    recorder.record_enqueued(request)
    recorder.record_dequeued(request)
    recorder.record_admitted(request, slot=0, bucket=8)
    recorder.record_first_token(request)
    recorder.record_finished(request, "length")
    (rec,) = recorder._done
    phases = rec.phases()
    assert "dispatch_s" not in phases
    assert phases["parked_s"] == pytest.approx(0.5)
    assert phases["pickup_s"] + phases["parked_s"] + phases["prefill_s"] \
        == pytest.approx(rec.ttft_s(), abs=1e-9)
    summary = rec.summary()
    assert not set(STAMPS) & set(summary)
    (row,) = recorder.timeline_records()
    assert all(row[key] is None for key in STAMPS)
    admitted = next(e for e in rec.detail()["events"]
                    if e["event"] == "admitted")
    assert set(admitted) == {"t", "event", "slot", "bucket"}


@pytest.mark.parametrize("depth", [1, 4])
def test_ahead_is_what_the_deque_held_at_the_append(depth, served):
    if depth == 4:
        eng, binds, requests = served
    else:
        eng = _engine(pipeline_depth=depth)
        binds = _spy_on_binds(eng)
        requests = _serve(eng, [9, 14, 5, 11, 7, 16])
    assert sum(len(batch) for _, batch in binds) == len(requests)
    done = {rec.id: rec for rec in eng.recorder._done}
    for before, batch in binds:
        steps = sum(n for kind, n in before if kind != "prefill")
        prefills = sum(1 for kind, _ in before if kind == "prefill")
        for request in batch:
            assert (request.ahead_steps, request.ahead_prefills) \
                == (steps, prefills)
            rec = done[request.id]
            assert (rec.ahead_steps, rec.ahead_prefills) == (steps, prefills)
    # the two requests queued before the loop started are of two buckets:
    # one turn admits them as two waves, the second behind the first
    first, second = binds[0], binds[1]
    assert first[0] == [] and second[0] == [("prefill", None)]
    assert second[1][0].ahead_prefills == 1
    ahead = [request.ahead_steps for request in requests]
    if depth == 1:
        # a synchronous engine never holds more than one decode block
        assert max(ahead) <= eng.decode_block_size
    else:
        # admitted into a running decode: blocks of 4 (2 while a request
        # waits) queued before it, never more than the depth holds
        assert 0 < max(ahead) <= depth * eng.decode_block_size


def test_overrun_steps_are_conserved_against_the_blocks_row_steps():
    eng = _engine(pipeline_depth=4)
    read = []
    sync = eng._sync_oldest

    def spy():
        entry = eng._inflight[0]
        if entry[0] == "decode":
            read.append((list(entry[2]), entry[3]))
        sync()

    eng._sync_oldest = spy
    # forced output lengths (no stop token): one ends at its prefill, the
    # rest inside a block or on a block's edge
    requests = _serve(eng, [9, 1, 14, 5, 2, 11, 8, 16, 6, 13])
    assert [r.generated for r in requests] == [9, 1, 14, 5, 2, 11, 8, 16,
                                               6, 13]
    ids = {request.id for request in requests}
    row_steps = sum(block for snapshot, block in read
                    for _, request in snapshot if request.id in ids)
    assert row_steps == sum(len(snapshot) * block for snapshot, block in read)
    assert all(request.overrun_steps is not None for request in requests)
    assert sum(r.generated - 1 + r.overrun_steps for r in requests) \
        == row_steps
    # a pipelined engine decodes past a finish: blocks were queued
    assert sum(r.overrun_steps for r in requests) > 0
    assert eng.row_steps_total == row_steps
    assert eng.overrun_steps_total == sum(r.overrun_steps for r in requests)
    done = {rec.id: rec for rec in eng.recorder._done}
    for request in requests:
        assert done[request.id].overrun_steps == request.overrun_steps
    shown = engine_snapshot(eng)["engine"]
    assert shown["row_steps_total"] == row_steps
    assert shown["overrun_share"] == pytest.approx(
        eng.overrun_steps_total / row_steps, abs=1e-4)
