"""The loop's spans, CPU time and the request's third stamp (ISSUE 24).

The step ledger's segments are also spans on the profiler's clock
(`loop/step`, `loop/<segment>`, `loop/park`), each segment carries the loop
thread's CPU seconds beside its wall seconds, `_admit` names its program
lookups, its slot binding and its wait for the state lock, a request is
stamped when the loop picks it up (`dequeued_at`), and `Executor.compile`
names the XLA module after the program.
"""

import glob
import os
import threading
import time

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.stepledger import SEGMENTS, StepLedger

CFG = LlamaConfig.debug()
NEW_SEGMENTS = ("program_lookup", "bind", "lock_wait")


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class Spans:
    """An annotation factory that keeps what the ledger opens and closes."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **metadata):
        spans = self

        class Span:
            def __enter__(self):
                spans.log.append(("open", name, metadata,
                                  threading.get_ident()))
                return self

            def __exit__(self, *exc):
                spans.log.append(("close", name, metadata,
                                  threading.get_ident()))
                return False

        return Span()

    def names(self):
        return [(what, name) for what, name, _, _ in self.log]


def _one_step(ledger, clock=None):
    tick = clock.advance if clock is not None else (lambda s: None)
    ledger.step_start()
    with ledger.seg("admission"):
        tick(0.010)
        with ledger.seg("program_lookup"):
            tick(0.020)
        with ledger.seg("dispatch"):
            tick(0.005)
        with ledger.seg("bind"):
            tick(0.003)
    ledger.note_dispatch("prefill")
    return ledger.step_end()


def test_the_new_segments_are_canonical():
    for name in NEW_SEGMENTS:
        assert name in SEGMENTS
    assert SEGMENTS[-1] == "other" and len(set(SEGMENTS)) == len(SEGMENTS)


def test_spans_are_balanced_and_nested_like_the_segments():
    spans, clock = Spans(), FakeClock()
    ledger = StepLedger(clock=clock, annotate=spans)
    rec = _one_step(ledger, clock)
    assert rec is not None
    assert spans.names() == [
        ("open", "loop/step"),
        ("open", "loop/admission"),
        ("open", "loop/program_lookup"), ("close", "loop/program_lookup"),
        ("open", "loop/dispatch"), ("close", "loop/dispatch"),
        ("open", "loop/bind"), ("close", "loop/bind"),
        ("close", "loop/admission"),
        ("close", "loop/step"),
        ("open", "loop/step_close"), ("close", "loop/step_close")]
    assert spans.log[0][2] == {"step": rec.seq}
    # the caller's hooks on the closed record run inside that step_close
    del spans.log[:]
    ledger.step_start()
    ledger.note_dispatch("decode")
    ledger.step_end(closing=lambda rec: spans.log.append(
        ("hook", rec.seq, {}, threading.get_ident())))
    assert spans.names()[-3:] == [("open", "loop/step_close"),
                                  ("hook", 2),
                                  ("close", "loop/step_close")]
    # between two steps: the idle wait has a span, and no segment
    with ledger.between("park"):
        clock.advance(0.050)
    assert spans.names()[-2:] == [("open", "loop/park"),
                                  ("close", "loop/park")]
    # an aborted step closes what it opened, innermost first
    del spans.log[:]
    ledger.step_start()
    ledger.seg("admission").__enter__()
    ledger.step_abort()
    assert spans.names() == [("open", "loop/step"), ("open", "loop/admission"),
                             ("close", "loop/admission"),
                             ("close", "loop/step")]


def test_no_span_from_a_foreign_thread():
    spans = Spans()
    ledger = StepLedger(annotate=spans)
    ledger.step_start()
    owner = threading.get_ident()

    def foreign():
        with ledger.seg("program_lookup"):      # warm-up, scoring
            pass
        with ledger.between("park"):
            pass

    worker = threading.Thread(target=foreign)
    worker.start()
    worker.join()
    ledger.note_dispatch("decode")
    ledger.step_end()
    assert {ident for _, _, _, ident in spans.log} == {owner}
    assert ("open", "loop/program_lookup") not in spans.names()
    assert ("open", "loop/park") not in spans.names()
    # inside an open step `between` is not a span either
    ledger.step_start()
    with ledger.between("park"):
        pass
    assert ("open", "loop/park") not in spans.names()
    ledger.step_abort()


def test_a_ledger_without_spans_records_as_before():
    clock = FakeClock()
    ledger = StepLedger(clock=clock, annotate=None)
    rec = _one_step(ledger, clock)
    assert rec.segments["program_lookup"] == pytest.approx(0.020)
    assert rec.segments["admission"] == pytest.approx(0.010)


def test_the_sum_identity_holds_with_the_new_segments():
    clock = FakeClock()
    ledger = StepLedger(clock=clock, annotate=None)
    ledger.step_start()
    clock.advance(0.001)
    with ledger.seg("lock_wait"):
        clock.advance(0.004)
    rec_open = ledger.seg("admission")
    rec_open.__enter__()
    clock.advance(0.010)
    with ledger.seg("program_lookup"):
        clock.advance(0.020)
        ledger.note_stolen("compile", 0.015)    # a cache miss under it
    with ledger.seg("bind"):
        clock.advance(0.003)
    rec_open.__exit__(None, None, None)
    ledger.note_dispatch("prefill")
    rec = ledger.step_end()
    assert rec.segments == pytest.approx({
        "other": 0.001, "lock_wait": 0.004, "admission": 0.010,
        "program_lookup": 0.005, "compile": 0.015, "bind": 0.003})
    assert sum(rec.segments.values()) == pytest.approx(rec.wall_s, abs=1e-12)
    assert set(rec.segments_cpu) == set(rec.segments)


def test_segments_cpu_tells_working_from_waiting():
    ledger = StepLedger(annotate=None)
    ledger.step_start()
    with ledger.seg("emit"):
        time.sleep(0.25)                         # blocked: wall, no CPU
    with ledger.seg("demux"):
        until = time.thread_time() + 0.25        # working: CPU ~ wall
        while time.thread_time() < until:
            pass
    ledger.note_sync("decode", tokens=1)
    rec = ledger.step_end()
    assert set(rec.segments_cpu) == set(rec.segments)
    for name, wall in rec.segments.items():
        assert 0.0 <= rec.segments_cpu[name] <= wall
    assert rec.segments["emit"] >= 0.25
    assert rec.segments_cpu["emit"] < 0.05
    assert rec.segments_cpu["demux"] >= 0.24
    # against the blocked segment, not against its own wall time: what share
    # of its wall a spinning thread gets is the machine's load, not the
    # ledger's arithmetic
    assert rec.segments_cpu["demux"] >= 5 * rec.segments_cpu["emit"]
    assert 0.24 <= rec.cpu_s <= rec.wall_s
    shown = rec.summary()
    assert set(shown["segments_cpu"]) == set(shown["segments"])
    assert shown["cpu_s"] == pytest.approx(rec.cpu_s, abs=1e-6)
    phase = ledger.snapshot()["summary"]["decode"]
    assert set(phase["segments_cpu"]) == set(phase["segments"])
    assert phase["cpu_s"] == pytest.approx(rec.cpu_s, abs=1e-6)


@pytest.fixture(scope="module")
def burst():
    """A tiny paged engine that admitted a burst: its ledger and recorder."""
    from gofr_tpu.tpu.flightrecorder import FlightRecorder
    from gofr_tpu.tpu.paging import PagedLLMEngine

    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=2,
                         max_seq_len=64, prefill_buckets=(16,),
                         decode_block_size=2, page_size=16)
    eng.recorder = FlightRecorder(capacity=64)
    spans = Spans()
    eng.steps = StepLedger(annotate=spans, capacity=4096)
    eng.start()
    try:
        # more requests than slots: the later ones are picked up at once
        # and wait for a slot, or wait in the queue while none is free
        requests = [eng.submit([1 + i, 2, 3], max_new_tokens=5)
                    for i in range(6)]
        for request in requests:
            request.result(timeout_s=120)
    finally:
        eng.stop()
    return eng, spans


def test_a_burst_is_recorded_under_the_three_new_segments(burst):
    eng, spans = burst
    seen = set()
    for rec in eng.steps.records(recent=4096):
        seen.update(rec.segments)
        assert sum(rec.segments.values()) == pytest.approx(rec.wall_s,
                                                           abs=1e-9)
        assert set(rec.segments_cpu) == set(rec.segments)
        for name, wall in rec.segments.items():
            assert 0.0 <= rec.segments_cpu[name] <= wall
    assert set(NEW_SEGMENTS) <= seen
    opened = {name for what, name in spans.names() if what == "open"}
    assert {"loop/step", "loop/admission", "loop/program_lookup",
            "loop/bind", "loop/lock_wait", "loop/dispatch",
            "loop/device_sync", "loop/step_close"} <= opened
    # every span closed, on the loop thread, innermost first
    stack = []
    for what, name in spans.names():
        if what == "open":
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    assert len({ident for _, _, _, ident in spans.log}) == 1
    # one step_close a recorded step: the engine's hooks run inside the
    # ledger's span, not in a second one beside it
    assert spans.names().count(("open", "loop/step_close")) \
        == eng.steps.snapshot()["steps_total"]


def test_the_three_waits_of_a_request_sum_to_its_ttft(burst):
    eng, _ = burst
    done = list(eng.recorder._done)
    assert len(done) == 6
    for rec in done:
        assert (rec.enqueued_at <= rec.dequeued_at <= rec.granted_at
                <= rec.admitted_at <= rec.first_token_at)
        phases = rec.phases()
        # since ISSUE 37 the second wait ends at `granted` and the loop's
        # own dispatch is a part of its own (tests/test_queue_stamps.py)
        waits = (phases["pickup_s"] + phases["parked_s"]
                 + phases["dispatch_s"])
        assert waits + phases["prefill_s"] == pytest.approx(rec.ttft_s(),
                                                            abs=1e-6)
        assert waits == pytest.approx(phases["queue_s"], abs=1e-9)
        events = [event["event"] for event in rec.detail()["events"]]
        assert events.index("enqueued") < events.index("dequeued") \
            < events.index("admitted") < events.index("first_token")
    rows = {row["id"]: row for row in eng.recorder.timeline_records()}
    for rec in done:
        assert rows[rec.id]["dequeued_at"] == rec.dequeued_at
    # six requests into two slots: some waited for a slot after pickup
    # (parked) or in the queue while no slot was free (pickup)
    assert max(r.admitted_at - r.enqueued_at for r in done) > 0.0


def test_the_xla_module_is_named_after_the_program():
    import jax.numpy as jnp

    from gofr_tpu.tpu.executor import Executor, _named_after

    def decode(x):
        return x + 1

    def prefill(x):
        return x * 2

    assert _named_after(decode, "llama-paged-decode-x16-NP8").__name__ == \
        "decode__x16_NP8"
    assert _named_after(prefill, "llama-paged-prefill-128x4").__name__ == \
        "prefill__128x4"
    # a program that does not carry the function's name keeps all of its own
    assert _named_after(prefill, "llama-paged-prefix-128x4-NP8").__name__ \
        == "prefill__llama_paged_prefix_128x4_NP8"
    executor = Executor()
    x = jnp.zeros((4,), jnp.float32)
    for fn, program, module in (
            (decode, "llama-paged-decode-x16-NP8", "jit_decode__x16_NP8"),
            (prefill, "llama-paged-prefill-128x4", "jit_prefill__128x4")):
        compiled = executor.compile(program, fn, (x,))
        text = compiled.compiled.as_text()
        assert f"HloModule {module}" in text, text[:200]
        assert compiled.name == program
    assert float(executor.compile("llama-paged-decode-x16-NP8", decode,
                                  (x,))(x)[0]) == 1.0
    assert decode.__name__ == "decode"          # the caller's is untouched


def test_a_capture_with_the_programs_options_holds_the_loops_spans(tmp_path):
    """tpu/profiler.py's options (Python tracer off, TraceAnnotations on):
    a real capture on the CPU backend, read back with ProfileData, has
    `loop/step` and its segments on one host line, nested."""
    import jax
    from jax.profiler import ProfileData

    from gofr_tpu.tpu import profiler

    options = profiler.capture_options()
    assert options.python_tracer_level == 0
    assert options.host_tracer_level == 1
    ledger = StepLedger()                       # the real TraceAnnotation
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            ledger.step_start()
            with ledger.seg("admission"):
                with ledger.seg("program_lookup"):
                    time.sleep(0.002)
            ledger.note_dispatch("decode")
            ledger.step_end()
            with ledger.between("park"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert found
    profile = ProfileData.from_file(found[0])
    lines = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for ev in line.events]
             for plane in profile.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    held = [line for line in lines
            if any(name == "loop/step" for name, _, _ in line)]
    assert len(held) == 1
    by_name = {}
    for name, start, stop in held[0]:
        by_name.setdefault(name, []).append((start, stop))
    assert len(by_name["loop/step"]) == 3
    assert len(by_name["loop/park"]) == 3
    for name in ("loop/admission", "loop/program_lookup"):
        for start, stop in by_name[name]:
            assert any(a <= start and stop <= b
                       for a, b in by_name["loop/step"])
    step_meta = [dict(ev.stats) for plane in profile.planes
                 for line in plane.lines for ev in line.events
                 if ev.name == "loop/step"]
    assert sorted(int(m["step"]) for m in step_meta) == [1, 2, 3]
