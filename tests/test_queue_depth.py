"""How many decode entries the loop keeps queued (ISSUE 42), and how many
steps each runs (ISSUE 47).

`pipeline_depth` is the cap; the depth in use is worked out a turn from
the loop's own turn against the time on the device of the block the turn
dispatches (`tpu/queuedepth.py`), and stands at the cap without both
estimates, under an admission plane and after the queue ran dry. The
block is half of `decode_block_size` while a request waits and whenever
the depth asked about a half block stays under the cap.
"""

import time

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu import queuedepth
from gofr_tpu.tpu.faults import FaultPlane
from gofr_tpu.tpu.flightrecorder import FlightRecorder
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.queuedepth import QueueDepth, depth_for, turn_parts
from gofr_tpu.tpu.stepledger import StepLedger, StepRecord
from gofr_tpu.tpu.utilization import engine_snapshot

CFG = LlamaConfig.debug()
BLOCK = 4

# the seven cells' `loop_host_ms_per_step` / `decode_step_dev_ms` on the
# ledger's PR 41 lines (change side), and the depth ISSUE 42 expects of each
LEDGER = [
    ("internlm2-1.8b.chat-open", 1.3494, 7.2792, 2),
    ("trinity-large-preview-ep8.mixedlen-closed", 2.1247, 9.7106, 2),
    ("nemotron-3-nano-30b-a3b-ep2.decode-closed", 6.1301, 18.872, 2),
    ("joyai-llm-flash-ep8.longprompt-closed", 6.7934, 15.554, 3),
    ("internlm2-1.8b.decode-closed", 6.142, 13.154, 3),
    ("xing4.0-29b-a4b-ep8.decode-closed", 7.5777, 11.495, 3),
    ("solar-open2-250b-ep8.decode256-closed", 17.075, 21.432, 4),
]


@pytest.mark.parametrize("cell,host_ms,device_ms,depth", LEDGER,
                         ids=[row[0] for row in LEDGER])
def test_the_rule_on_the_ledgers_ratios(cell, host_ms, device_ms, depth):
    assert queuedepth.TAIL_OVER_TYPICAL == 3.0
    assert depth_for(host_ms * 1e-3, device_ms * 1e-3, 4) == depth
    # the same ratio whatever the unit, and never over a lower cap
    assert depth_for(host_ms, device_ms, 4) == depth
    assert depth_for(host_ms, device_ms, 3) == min(depth, 3)
    assert depth_for(host_ms, device_ms, 2) == 2
    assert depth_for(host_ms, device_ms, 1) == 1


@pytest.mark.parametrize("fallback", ["no_turn", "no_entry", "neither",
                                      "a_plane", "after_a_dry_sync"])
@pytest.mark.parametrize("cap", [1, 2, 4, 6])
def test_the_rule_falls_back_to_the_cap(fallback, cap):
    assert depth_for(0.001, 0.1, cap) == min(2, cap)
    host = None if fallback in ("no_turn", "neither") else 0.001
    device = None if fallback in ("no_entry", "neither") else 0.1
    assert depth_for(host, device, cap,
                     mirrored=fallback == "a_plane") == (
        cap if fallback != "after_a_dry_sync" else min(2, cap))
    # the same through the estimator: a fast host, and what takes the
    # depth back to the cap all the same
    queue = QueueDepth(cap, mirrored=fallback == "a_plane")
    for _ in range(queuedepth.MIN_SAMPLES):
        if host is not None:
            queue._turns.append(host)
        if device is not None:
            queue.note_entry(device)
    if fallback == "after_a_dry_sync":
        assert queue.turn() == min(2, cap)
        queue.ran_dry()
        assert queue.depth_now == cap
    assert queue.turn() == cap


@pytest.mark.parametrize("fallback", ["no_turn", "no_step", "neither",
                                      "a_plane", "after_a_dry_sync",
                                      "a_slow_host"])
@pytest.mark.parametrize("cap", [1, 2, 4])
def test_the_block_falls_back_to_what_waits_at_the_dispatch(fallback, cap):
    """Without room the loop can show, the block is the parent's: half
    while a request waits, full otherwise. A cap of 1 or 2 never has
    room: the depth never stays UNDER it."""
    host = None if fallback in ("no_turn", "neither") else 0.001
    if fallback == "a_slow_host":
        host = 1.0
    step = None if fallback in ("no_step", "neither") else 0.01
    queue = QueueDepth(cap, mirrored=fallback == "a_plane", block=16)
    for _ in range(queuedepth.MIN_SAMPLES):
        if host is not None:
            queue._turns.append(host)
        if step is not None:
            queue.note_entry(16 * step, 16)
    if fallback == "after_a_dry_sync":
        queue.turn()
        assert queue.block(False) == (
            (8, queuedepth.HALF_ROOM) if cap > 2 else (16, queuedepth.FULL))
        queue.ran_dry()
        assert queue.block_now == 16 and not queue.host_has_room
    for waits, block, why in ((False, 16, queuedepth.FULL),
                              (True, 8, queuedepth.HALF_WAITS)):
        queue.turn(waits)
        assert not queue.host_has_room
        assert queue.block_now == block and queue.block(waits) == (block, why)
        # the depth is the cap but for the slow host's, which is the
        # rule's own answer and the cap all the same
        assert queue.depth_now == cap
    # a block of one step has no half
    one = _fed(QueueDepth(4, block=1), 0.001, 0.1)
    one.turn(True)
    assert one.block(True) == (1, queuedepth.FULL) and one.depth_now == 2


# `loop_host_ms_per_step` / `decode_step_dev_ms` of the eight cells on the
# ledger's PR 46 lines (change side), the depth PR 42 recorded in each
# (`chat-open` and trinity 2, nemotron 3, joyai 2-3, `decode-closed` 3-4,
# xing and solar the cap; minicpm's cell is younger), and what the rule
# says of a host turn as long as sixteen of the one against the other's
# sixteen or eight: where the depth at eight stays under four, eight
LEDGER_47 = [
    ("internlm2-1.8b.chat-open", 1.0524, 7.3194, 8, 2),
    ("trinity-large-preview-ep8.mixedlen-closed", 1.6915, 9.2336, 8, 3),
    ("minicpm-sala-pp4.longctx-closed", 2.9394, 11.777, 8, 3),
    ("nemotron-3-nano-30b-a3b-ep2.decode-closed", 5.9661, 18.837, 8, 3),
    ("joyai-llm-flash-ep8.longprompt-closed", 5.585, 15.273, 16, 3),
    ("internlm2-1.8b.decode-closed", 6.442, 13.243, 16, 3),
    ("xing4.0-29b-a4b-ep8.decode-closed", 7.578, 11.449, 16, 3),
    ("solar-open2-250b-ep8.decode256-closed", 17.134, 21.636, 16, 4),
]


@pytest.mark.parametrize("cell,host_ms,step_ms,block,depth", LEDGER_47,
                         ids=[row[0] for row in LEDGER_47])
def test_the_block_on_the_ledgers_ratios(cell, host_ms, step_ms, block,
                                         depth):
    queue = _fed(QueueDepth(4, block=16), 16 * host_ms * 1e-3,
                 16 * step_ms * 1e-3, steps=16)
    assert queue.turn() == depth and queue.block_now == block
    assert queue.host_has_room is (block == 8)
    assert queue.block(False) == (block, queuedepth.HALF_ROOM if block == 8
                                  else queuedepth.FULL)
    # a request that waits gets the half block in every cell, at the
    # depth a half block asks for
    assert queue.turn(True) == depth_for(16 * host_ms, 8 * step_ms, 4)
    assert queue.block(True) == (8, queuedepth.HALF_WAITS)
    # half blocks measured by the step give the same answer as full ones
    mixed = QueueDepth(4, block=16)
    for _ in range(queuedepth.MIN_SAMPLES):
        mixed._turns.append(16 * host_ms * 1e-3)
        mixed.note_entry(8 * step_ms * 1e-3, 8)
        mixed.note_entry(16 * step_ms * 1e-3, 16)
    assert (mixed.turn(), mixed.block_now) == (depth, block)


def _record(phase, gap=0.0, **segments):
    """A step record of the given wall seconds a segment; `<name>_cpu`
    gives a segment's CPU seconds (its wall otherwise)."""
    cpu = {name[:-4]: segments.pop(name)
           for name in list(segments) if name.endswith("_cpu")}
    return StepRecord(1, 0.0, sum(segments.values()), gap, phase,
                      dict(segments), {**segments, **cpu})


def test_a_turn_leaves_out_the_devices_waits_and_keeps_the_enqueues_hold():
    rec = _record("decode", gap=0.001, admission=0.002, dispatch=0.010,
                  dispatch_cpu=0.0015, device_sync=0.080, demux=0.0005,
                  emit=0.004)
    before, after = turn_parts(rec)
    # the gap, admission and the enqueue call, the 8.5 ms it stood
    # blocked in the runtime with it (the next entry reaches the device
    # that much later); not the 80 ms on the device
    assert before == pytest.approx(0.001 + 0.002 + 0.010)
    assert after == pytest.approx(0.0045)


def test_turns_run_from_a_decode_reads_return_to_the_next_ones_start():
    queue = QueueDepth(4)
    decode = _record("decode", admission=0.002, device_sync=0.1, emit=0.004)
    prefill = _record("prefill", device_sync=0.013, emit=0.001)
    queue.note_record(decode)          # no read's return began this one
    assert not queue._turns
    queue.note_record(prefill)
    queue.note_record(decode)
    # the first entry's emit, the prefill read behind it less its wait,
    # the next turn's admission
    assert list(queue._turns) == [pytest.approx(0.004 + 0.001 + 0.002)]
    queue.note_park()                  # nothing in flight: no host turn
    queue.note_record(decode)
    queue.note_record(decode)
    assert list(queue._turns) == [pytest.approx(0.007),
                                  pytest.approx(0.006)]


def test_an_entry_is_the_time_between_two_reads_that_waited():
    queue = QueueDepth(4)
    queue.note_read(10.0, 0.05, 1)
    queue.note_read(10.1, 0.05, 1)
    assert list(queue._steps) == [pytest.approx(0.1)]
    queue.note_read(10.2, 0.0, 1)      # found done: no entry's end
    queue.note_read(10.3, 0.05, 1)     # ... and no entry's start before it
    queue.note_read(10.4, 0.05, 0)     # nothing queued behind: may idle
    queue.note_read(10.5, 0.05, 1)
    assert list(queue._steps) == [pytest.approx(0.1), pytest.approx(0.1)]
    queue.note_break()                 # a verify was read
    queue.note_read(10.6, 0.05, 1)
    assert len(queue._steps) == 2
    # an entry's seconds over the steps of the block the read brought
    queue.note_read(10.68, 0.05, 1, 8)
    queue.note_read(10.84, 0.05, 1, 16)
    assert list(queue._steps)[2:] == [pytest.approx(0.01),
                                      pytest.approx(0.01)]


def _fed(queue, host_s, device_s, turns=queuedepth.MIN_SAMPLES, steps=1):
    for _ in range(turns):
        queue._turns.append(host_s)
        queue.note_entry(device_s, steps)
    return queue


def test_debug_engines_queue_against_hand_made_turns():
    queue = QueueDepth(4)
    assert queue.snapshot() == {
        "depth_now": 4, "depth_cap": 4, "block_now": 1,
        "host_turn_ms": None, "device_step_ms": None,
        "device_entry_ms": None, "turns_by_depth": {2: 0, 3: 0, 4: 0},
        "shallow_share": 0.0,
        "blocks": {"full": 0, "half_request_waits": 0, "half_host_room": 0},
        "resets_by_dry_sync": 0}
    assert queue.turn() == 4           # no estimate yet
    _fed(queue, 0.002, 0.114, turns=queuedepth.MIN_SAMPLES - 1)
    assert queue.turn() == 4           # too few turns for one
    _fed(queue, 0.002, 0.114, turns=1)
    assert [queue.turn() for _ in range(6)] == [2] * 6
    shown = queue.snapshot()
    assert shown["host_turn_ms"] == 2.0 and shown["device_entry_ms"] == 114.0
    assert shown["turns_by_depth"] == {2: 6, 3: 0, 4: 2}
    assert shown["shallow_share"] == 0.75 and shown["depth_now"] == 2
    # a slower host: 3 x 50 / 114 = 1.3, two entries behind the one read
    _fed(queue, 0.050, 0.114, turns=queuedepth.RING)
    assert queue.turn() == 3
    # the queue ran dry: the cap, counted once, and both estimates gone
    queue.ran_dry()
    queue.ran_dry()
    shown = queue.snapshot()
    assert shown["resets_by_dry_sync"] == 1 and shown["depth_now"] == 4
    assert shown["host_turn_ms"] is None is shown["device_entry_ms"]
    assert shown["device_step_ms"] is None
    _fed(queue, 0.002, 0.114, turns=queuedepth.MIN_SAMPLES - 1)
    assert queue.turn() == 4
    _fed(queue, 0.002, 0.114, turns=1)
    assert queue.turn() == 2           # and down again as the estimates allow
    assert queue.snapshot()["turns_by_depth"] == {2: 7, 3: 1, 4: 3}


# turns as the loop makes them: (a request waits, blocks the top-up
# dispatched) a turn; host turn and device step in seconds, None = no
# estimate yet
HAND_MADE = {
    "no_estimates": (None, None, [(False, 3), (True, 1), (False, 1)],
                     {"full": 4, "half_request_waits": 1,
                      "half_host_room": 0}, 16, 4),
    "a_fast_host": (0.017, 0.0073, [(False, 1), (True, 1), (False, 1)],
                    {"full": 0, "half_request_waits": 1,
                     "half_host_room": 2}, 8, 2),
    "a_slow_host": (0.105, 0.0132, [(False, 1), (True, 2), (False, 1)],
                    {"full": 2, "half_request_waits": 2,
                     "half_host_room": 0}, 16, 3),
}


@pytest.mark.parametrize("case", sorted(HAND_MADE))
def test_debug_engines_blocks_and_the_records_steps_on_hand_made_turns(case):
    host, step, turns, counts, block, depth = HAND_MADE[case]
    queue = QueueDepth(4, block=16)
    if host is not None:
        _fed(queue, host, 16 * step, steps=16)
    ledger = StepLedger()
    records = []
    for waits, dispatched in turns:
        queue.turn(waits)
        ledger.step_start()
        steps = [queue.dispatched(waits) for _ in range(dispatched)]
        for n in steps:
            ledger.note_dispatch("decode", steps=n)
        ledger.note_sync("decode", tokens=steps[0], block_steps=steps[0])
        records.append((ledger.step_end(active_slots=1), steps))
    shown = queue.snapshot()
    assert shown["blocks"] == counts
    assert (shown["block_now"], shown["depth_now"]) == (block, depth)
    if step is not None:
        assert shown["device_step_ms"] == pytest.approx(step * 1e3)
        assert shown["device_entry_ms"] == pytest.approx(step * block * 1e3)
    for rec, steps in records:
        assert rec.block_steps == steps[0] == rec.summary()["block_steps"]
        assert rec.dispatches == {"decode": len(steps),
                                  "decode_steps": sum(steps)}
    summed = ledger.snapshot()["summary"]["decode"]
    assert summed["block_steps"] == sum(steps[0] for _, steps in records)
    assert summed["steps"] == len(turns)
    # a record that read no decode block says nothing of one
    ledger.step_start()
    ledger.note_dispatch("prefill")
    ledger.note_sync("prefill", tokens=1)
    rec = ledger.step_end(active_slots=1)
    assert rec.block_steps == 0 and "block_steps" not in rec.summary()
    assert rec.dispatches == {"prefill": 1}


def test_reads_that_found_their_entries_done_are_a_dry_queue():
    queue = _fed(QueueDepth(4), 0.050, 0.114, turns=queuedepth.RING)
    assert queue.turn() == 3
    queue.note_read(1.0, 0.0, 2)       # one of the two entries behind: not yet
    assert queue.depth_now == 3
    queue.note_read(1.1, 0.05, 2)
    queue.note_read(1.2, 0.0, 2)
    queue.note_read(1.3, 0.0, 2)       # both: the device had nothing left
    assert queue.depth_now == 4 and queue.resets_by_dry_sync == 1
    assert queue.device_step_s is None and not queue._steps
    assert queue.host_turn_s is None and not queue._turns
    # at the cap a read that did not wait says nothing
    queue.note_read(1.4, 0.0, 3)
    assert queue.resets_by_dry_sync == 1


@pytest.mark.parametrize("found_done,resets", [
    ((False, True, False, True, False), 0),   # one stall, and another
    ((False, True, True), 1),                 # too slow turn after turn
], ids=["one_alone_is_a_stall", "two_in_a_row_are_a_dry_queue"])
def test_at_a_depth_of_two_one_read_found_done_is_not_a_dry_queue(found_done,
                                                                  resets):
    """One entry behind the read, a half block long: any stall of the
    host longer than that finds it done, once. Dropping the estimates
    for it would run full blocks at the cap for the next second."""
    queue = _fed(QueueDepth(4, block=16), 0.014, 16 * 0.0073, steps=16)
    assert queue.turn() == 2 and queue.block_now == 8
    for i, done in enumerate(found_done):
        queue.note_read(1.0 + 0.06 * i, 0.0 if done else 0.04, 1, 8)
    assert queue.resets_by_dry_sync == resets
    assert queue.depth_now == (4 if resets else 2)
    assert queue.turn() == (4 if resets else 2)
    assert queue.block_now == (16 if resets else 8)


def test_a_plane_and_a_synchronous_engine_stay_where_they_were():
    plane = _fed(QueueDepth(4, mirrored=True), 0.002, 0.114)
    assert [plane.turn() for _ in range(3)] == [4, 4, 4]
    assert plane.snapshot()["shallow_share"] == 0.0
    one = _fed(QueueDepth(1), 0.002, 0.114)
    assert one.turn() == 1 and one.snapshot()["turns_by_depth"] == {1: 1}
    one.ran_dry()                      # every read of a depth-1 engine is dry
    assert one.snapshot()["resets_by_dry_sync"] == 0


# -- the engine ------------------------------------------------------------------
def _engine(**kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_seq_len", 256)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("decode_block_size", BLOCK)
    kw.setdefault("page_size", 16)
    kw.setdefault("pipeline_depth", 4)
    return PagedLLMEngine(llama_init(CFG, seed=0), CFG,
                          flight_recorder=FlightRecorder(capacity=64), **kw)


def _slow_device(eng, delay_s=0.05):
    """Every read waits `delay_s`: the device slower than the host."""
    eng.faults = FaultPlane(plan=[{"site": "engine.sync", "action": "delay",
                                   "delay_s": delay_s, "times": 0}])


def _slow_host(eng):
    """Hand the estimator a host whose turn is ten of the device's
    entries, whatever this machine's clocks read."""
    queue = eng.queue

    def note_record(rec):
        if rec.phase in ("decode", "verify"):
            queue._turns.append(1.0)
            queue.note_entry(0.1 * BLOCK, BLOCK)

    queue.note_record = note_record
    queue.note_read = lambda *args: None


def _wait_for(condition, seconds=60.0):
    deadline = time.monotonic() + seconds
    while not condition():
        assert time.monotonic() < deadline, "timed out"
        time.sleep(0.005)


def _serve_with_a_late_prompt(eng, settled):
    """Two requests decode until `settled(eng)`; then a third arrives."""
    first = [eng.submit([1, 2, 3], max_new_tokens=220),
             eng.submit([4, 5], max_new_tokens=220)]
    eng.start()
    try:
        _wait_for(lambda: settled(eng)
                  or all(r.finished_at is not None for r in first))
        assert settled(eng), eng.queue.snapshot()
        depth = eng.queue.depth_now
        late = eng.submit([7, 8, 9], max_new_tokens=6)
        late.result(timeout_s=120)
        for request in first:
            request.cancel()
    finally:
        eng.stop()
    return late, depth


def test_a_slow_device_settles_at_two_and_a_late_prompt_meets_half_a_block():
    eng = _engine()
    _slow_device(eng)
    late, depth = _serve_with_a_late_prompt(
        eng, lambda eng: eng.queue.turns_by_depth[2] >= 6)
    shown = engine_snapshot(eng)["engine"]["queue"]
    assert depth == 2 and shown["depth_cap"] == 4
    assert shown["turns_by_depth"][2] > shown["turns_by_depth"][3] == 0
    assert shown["resets_by_dry_sync"] == 0 == eng.dry_syncs_total
    # every read waits 50 ms whatever its block: a step of a full block
    # is the shortest the ring has seen
    assert shown["device_step_ms"] >= 50.0 / BLOCK
    assert shown["device_entry_ms"] > 3 * shown["host_turn_ms"]
    # nobody waited while the two decoded, and the host had room: half
    # blocks from the first estimates on, full ones before them
    assert shown["block_now"] == BLOCK // 2
    assert shown["blocks"]["half_host_room"] >= 6
    assert shown["blocks"]["full"] >= queuedepth.MIN_SAMPLES
    # behind the one HALF block the device had just started, not behind
    # three full ones it had not
    assert 0 < late.ahead_steps <= BLOCK // 2
    records = eng.steps.records(recent=1 << 20)
    assert {rec.depth_now for rec in records} == {2, 4}
    assert all(rec.inflight - rec.inflight_prefill <= rec.depth_now
               for rec in records)
    assert "depth_now" in records[-1].summary()
    # the ledger shows the mix: what each decode record read, and what
    # the turns enqueued, both as the queue counted them
    read = [rec.block_steps for rec in records if rec.phase == "decode"]
    assert set(read) == {BLOCK, BLOCK // 2}
    assert all(rec.block_steps == 0 for rec in records
               if rec.phase != "decode")
    blocks = sum(rec.dispatches.get("decode", 0) for rec in records)
    assert blocks == sum(shown["blocks"].values())
    assert sum(rec.dispatches.get("decode_steps", 0) for rec in records) \
        == (BLOCK * shown["blocks"]["full"] + BLOCK // 2 * (
            blocks - shown["blocks"]["full"]))


def test_a_slow_host_stays_at_the_cap():
    eng = _engine()
    _slow_host(eng)
    late, depth = _serve_with_a_late_prompt(
        eng, lambda eng: eng.queue.host_turn_s is not None
        and eng.queue.turns_by_depth[4] >= 12)
    shown = engine_snapshot(eng)["engine"]["queue"]
    assert depth == 4 and shown["shallow_share"] == 0.0
    assert shown["host_turn_ms"] == 1000.0
    assert shown["device_step_ms"] == 100.0
    # no room at half blocks, so full ones but where the late prompt
    # waited at a dispatch
    assert shown["blocks"]["half_host_room"] == 0
    assert shown["blocks"]["full"] > shown["blocks"]["half_request_waits"]
    # the deque as it was: up to the cap's blocks ahead of a prompt
    assert late.ahead_steps > BLOCK


def test_a_dry_sync_puts_the_depth_back_to_the_cap():
    eng = _engine()
    _slow_device(eng)
    room, forced = eng._room_for_decode, []

    def no_room_once():
        # one turn in which the top-up queues nothing behind the block
        # the device runs: the next read finds the queue dry
        if eng.queue.turns_by_depth[2] >= 6 and not forced \
                and eng._decode_inflight() == 1:
            forced.append(eng.decode_syncs_total)
            return False
        return room()

    eng._room_for_decode = no_room_once
    request = eng.submit([1, 2, 3], max_new_tokens=200)
    eng.start()
    try:
        _wait_for(lambda: forced and eng.dry_syncs_total)
        assert eng.queue.depth_now == 4
        assert eng.queue.snapshot()["resets_by_dry_sync"] == 1
        # and down again only as the new estimates allow
        _wait_for(lambda: eng.queue.depth_now == 2)
        assert eng.queue.turns_by_depth[4] >= queuedepth.MIN_SAMPLES
        request.cancel()
    finally:
        eng.stop()
    assert eng.dry_syncs_total == 1
    shown = engine_snapshot(eng)["engine"]
    assert shown["queue"]["resets_by_dry_sync"] == 1
    assert shown["dry_syncs_total"] == 1


def test_a_device_reset_starts_the_estimates_again():
    eng = _engine()
    _fed(eng.queue, 0.002, 0.114)
    assert eng.queue.turn() == 2
    eng._reset_device_state(RuntimeError("injected"))
    assert eng.queue.depth_now == 4 and eng.queue.turn() == 4
    assert eng.queue.snapshot()["host_turn_ms"] is None
