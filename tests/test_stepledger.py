"""Step anatomy ledger: per-step segment attribution, the straggler
sentinel, /debug/steps, and the exemplar-linked metrics→requests drill.

ISSUE 4's acceptance surface: /debug/steps segment attributions sum to
each step's measured wall-clock within 5% in an end-to-end engine run; a
seeded fault-injected slow sync is flagged by the sentinel with
device_sync as the dominant cause; an OpenMetrics scrape of the TTFT
histogram carries exemplars whose request id resolves via
/debug/requests/{id}; classic exposition carries none.
"""

import importlib.util
import json
import os
import re
import urllib.request

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.stepledger import StepLedger, register_step_metrics

CFG = LlamaConfig.debug()


# -- unit: the segment stack --------------------------------------------------
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def test_segment_nesting_is_exclusive_and_sums_to_wall():
    """Nested segments steal time from their parent; note_stolen
    re-attributes compile out of the enclosing segment; the recorded
    segments tile the step wall-clock EXACTLY (the nothing-hides
    identity)."""
    clock = FakeClock()
    ledger = StepLedger(clock=clock)
    ledger.step_start()
    clock.advance(0.010)                    # -> other
    with ledger.seg("admission"):
        clock.advance(0.020)                # admission own time
        with ledger.seg("page_alloc"):
            clock.advance(0.030)            # page_alloc, NOT admission
        clock.advance(0.005)                # admission again
    with ledger.seg("dispatch"):
        clock.advance(0.100)
        ledger.note_stolen("compile", 0.060)  # compile under dispatch
    ledger.note_dispatch("decode")
    clock.advance(0.002)                    # -> other
    rec = ledger.step_end(active_slots=1, inflight=1, queue_depth=0)
    assert rec is not None
    seg = rec.segments
    assert seg["admission"] == pytest.approx(0.025, abs=1e-9)
    assert seg["page_alloc"] == pytest.approx(0.030, abs=1e-9)
    assert seg["dispatch"] == pytest.approx(0.040, abs=1e-9)
    assert seg["compile"] == pytest.approx(0.060, abs=1e-9)
    assert seg["other"] == pytest.approx(0.012, abs=1e-9)
    assert sum(seg.values()) == pytest.approx(rec.wall_s, abs=1e-9)
    assert rec.phase == "dispatch"
    assert rec.dispatches == {"decode": 1}


def test_idle_iterations_fold_into_next_steps_idle_gap():
    clock = FakeClock()
    ledger = StepLedger(clock=clock)
    # two empty iterations (no dispatch/sync/tokens): dropped
    for _ in range(2):
        ledger.step_start()
        clock.advance(0.050)
        assert ledger.step_end() is None
    ledger.step_start()
    clock.advance(0.001)
    ledger.note_sync("decode", tokens=4, slowest_request_id=9)
    rec = ledger.step_end()
    assert rec is not None
    # the dropped iterations' time shows up as this step's idle gap
    assert rec.idle_gap_s == pytest.approx(0.100, abs=1e-9)
    assert rec.phase == "decode"
    assert rec.tokens == 4
    assert rec.slowest_request_id == 9
    snap = ledger.snapshot()
    assert snap["steps_total"] == 1


def test_foreign_thread_segments_are_ignored():
    """warmup()/scoring compile on the caller thread while the loop owns
    an open step — their seg()/note calls must be no-ops, not races."""
    import threading

    clock = FakeClock()
    ledger = StepLedger(clock=clock)
    ledger.step_start()

    def foreign():
        with ledger.seg("dispatch"):
            pass
        ledger.note_stolen("compile", 5.0)
        ledger.note_dispatch("decode")
        ledger.note_sync("decode", tokens=100)

    t = threading.Thread(target=foreign)
    t.start()
    t.join()
    clock.advance(0.001)
    ledger.note_sync("prefill", tokens=1)
    rec = ledger.step_end()
    assert rec.segments.get("compile") is None
    assert rec.tokens == 1
    assert rec.phase == "prefill"
    assert not rec.dispatches


def test_straggler_sentinel_flags_dominant_cause():
    clock = FakeClock()
    ledger = StepLedger(clock=clock, straggler_k=3.0, min_samples=8)
    for _ in range(10):                      # steady 10 ms decode steps
        ledger.step_start()
        with ledger.seg("dispatch"):
            clock.advance(0.010)
        ledger.note_sync("decode", tokens=1)
        assert ledger.step_end().straggler is False
        clock.advance(0.001)
    # one step dominated by a 200 ms device sync: >3x the ~10 ms baseline
    ledger.step_start()
    with ledger.seg("device_sync"):
        clock.advance(0.200)
    ledger.note_sync("decode", tokens=1, slowest_request_id=3)
    rec = ledger.step_end()
    assert rec.straggler is True
    assert rec.cause == "device_sync"
    assert rec.baseline_s == pytest.approx(0.010, rel=0.2)
    snap = ledger.snapshot()
    assert snap["stragglers_total"] == 1
    assert snap["stragglers"][-1]["cause"] == "device_sync"
    assert snap["stragglers"][-1]["slowest_request_id"] == 3
    # a fresh phase has no baseline: never flagged before min_samples
    ledger.step_start()
    with ledger.seg("dispatch"):
        clock.advance(3.0)
    ledger.note_sync("prefill", tokens=1)
    assert ledger.step_end().straggler is False


def test_step_metrics_published_with_exemplars():
    from gofr_tpu.metrics import Manager

    m = Manager()
    register_step_metrics(m)
    register_step_metrics(m)  # idempotent
    clock = FakeClock()
    ledger = StepLedger(metrics=m, clock=clock)
    ledger.step_start()
    with ledger.seg("dispatch"):
        clock.advance(0.02)
    ledger.note_sync("decode", tokens=2, slowest_request_id=42)
    ledger.step_end()
    om = m.expose(openmetrics=True)
    assert 'app_tpu_step_seconds_bucket{le="0.025",phase="decode",segment="dispatch"}' in om
    assert '# {request_id="42"}' in om
    assert "# {" not in m.expose()  # classic exposition: no exemplars


# -- end-to-end: engine + sentinel + fault injection --------------------------
def _engine(**kw):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("decode_block_size", 1)
    kw.setdefault("pipeline_depth", 1)
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, **kw)
    return eng


def test_engine_steps_sum_to_wall_within_tolerance():
    """The acceptance identity, end to end: every recorded step's segment
    attributions sum to its measured wall-clock within 5%."""
    eng = _engine()
    eng.start()
    try:
        request = eng.submit([1, 2, 3], max_new_tokens=12)
        tokens = request.result(timeout_s=60)
        assert len(tokens) == 12
    finally:
        eng.stop()
    snap = eng.steps.snapshot(recent=128)
    assert snap["steps_total"] >= 3
    phases = set()
    for rec in snap["recent"]:
        total = sum(rec["segments"].values())
        assert total == pytest.approx(rec["wall_s"],
                                      rel=0.05, abs=1e-4), rec
        phases.add(rec["phase"])
    assert "prefill" in phases and "decode" in phases
    # the batch cost-driver rode along for the exemplar link
    synced = [r for r in snap["recent"] if r.get("tokens")]
    assert any(r.get("slowest_request_id") == request.id for r in synced)
    # and the per-phase summary aggregates what the ring holds
    assert snap["summary"]["decode"]["steps"] >= 1
    assert snap["baselines"]["decode"]["samples"] >= 1


def test_fault_injected_slow_sync_flagged_as_device_sync_straggler():
    """The acceptance drill: a seeded engine.sync delay (faults.py delay
    action) must be flagged by the sentinel with device_sync dominant."""
    from gofr_tpu.tpu.faults import FaultPlane

    eng = _engine()
    eng.steps.configure(straggler_k=3.0, min_samples=6,
                        baseline_alpha=0.2)
    # decode_block_size=1 -> one engine.sync hit per generated token; the
    # 20th hit lands well after the 6-sample decode baseline armed.
    # warmup() + a generation that fits the warmed cache keep mid-serve
    # compiles/grows out of the run, so the ONLY outlier is the injected
    # sync delay (a coinciding compile would legitimately dominate it)
    eng.faults = FaultPlane(plan=[{"site": "engine.sync", "action": "delay",
                                   "delay_s": 0.5, "nth": 20}], seed=7)
    eng.start()
    eng.warmup()
    try:
        eng.generate([1, 2, 3], max_new_tokens=25)
    finally:
        eng.stop()
    snap = eng.steps.snapshot()
    assert snap["stragglers_total"] >= 1, snap["baselines"]
    causes = [s["cause"] for s in snap["stragglers"]]
    assert "device_sync" in causes, snap["stragglers"]
    flagged = next(s for s in snap["stragglers"]
                   if s["cause"] == "device_sync")
    assert flagged["segments"]["device_sync"] >= 0.5


def test_straggler_emits_flight_recorder_event():
    from gofr_tpu.tpu.faults import FaultPlane
    from gofr_tpu.tpu.flightrecorder import FlightRecorder

    recorder = FlightRecorder(capacity=16)
    eng = _engine(flight_recorder=recorder)
    eng.steps.configure(straggler_k=3.0, min_samples=6,
                        baseline_alpha=0.2)
    eng.faults = FaultPlane(plan=[{"site": "engine.sync", "action": "delay",
                                   "delay_s": 0.5, "nth": 20}])
    eng.start()
    eng.warmup()
    try:
        eng.generate([1, 2, 3], max_new_tokens=25)
    finally:
        eng.stop()
    events = [e for e in recorder.snapshot()["engine_events"]
              if e["event"] == "step_straggler"]
    assert events, "no step_straggler engine event recorded"
    assert events[0]["cause"] == "device_sync"
    assert events[0]["request_id"] is not None


def test_paged_engine_records_page_alloc_segment():
    from gofr_tpu.tpu.paging import PagedLLMEngine

    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=2,
                         max_seq_len=64, prefill_buckets=(16,),
                         decode_block_size=2, page_size=16)
    eng.start()
    try:
        eng.generate([1, 2, 3], max_new_tokens=6)
    finally:
        eng.stop()
    snap = eng.steps.snapshot(recent=128)
    seen = set()
    for rec in snap["recent"]:
        seen.update(rec["segments"])
        total = sum(rec["segments"].values())
        assert total == pytest.approx(rec["wall_s"], rel=0.05, abs=1e-4)
    assert "page_alloc" in seen
    assert "dispatch" in seen


# -- the dry-sync counter and the split `inflight` (PR 30) ---------------------
def _stubbed_read(kinds, budget=64, **kw):
    """An engine that never started, two slots bound by hand and a deque
    of host arrays standing for dispatches of `kinds`: the oldest is read
    as the loop reads it, one step around one sync."""
    import collections
    import time

    import numpy as np

    from gofr_tpu.tpu.engine import GenerationRequest

    eng = _engine(decode_block_size=4, pipeline_depth=4, **kw)
    reqs = []
    for slot in eng.slots:
        slot.request = GenerationRequest([1, 2, 3], max_new_tokens=budget)
        slot.length, slot.remaining = 3, budget - 1
        reqs.append(slot.request)
    live = list(enumerate(reqs))
    entries = {
        "decode": ("decode", np.full((eng.n_slots, 4), 7, np.int32), live,
                   4, time.monotonic(), None, 4),     # under a table of 4
        "prefill": ("prefill", np.full((eng.n_slots,), 7, np.int32), live,
                    None, time.monotonic()),
    }
    eng._inflight = collections.deque(entries[k] for k in kinds)
    eng.steps.step_start()
    eng._sync_oldest()
    eng._finish_step()
    return eng


@pytest.mark.parametrize("kinds, budget, dry", [
    # the parent's top-up counted every entry, so this is where a closed
    # loop's deque ended: one decode block, then prefills and nothing else
    (("decode", "prefill", "prefill", "prefill"), 64, True),
    (("decode", "prefill", "decode"), 64, False),   # a block queued behind
    (("decode",), 64, True),                        # nothing queued at all
    (("decode",), 4, False),        # nothing left to decode: not a dry run
])
def test_a_decode_read_with_no_block_behind_it_counts_dry(kinds, budget, dry):
    from gofr_tpu.tpu.utilization import engine_snapshot

    eng = _stubbed_read(kinds, budget)
    rec = eng.steps.records()[-1]
    assert rec.phase == "decode" and rec.dry_sync is dry
    left = kinds[1:]
    assert rec.inflight == len(left)
    assert rec.inflight_prefill == left.count("prefill")
    assert (eng.decode_syncs_total, eng.dry_syncs_total) == (1, int(dry))
    # /debug/steps: the record's split and flag, the phase's count
    snap = eng.steps.snapshot()
    shown = snap["recent"][0]
    assert shown["inflight"] == len(left)
    assert shown["inflight_decode"] == left.count("decode")
    assert shown["inflight_prefill"] == left.count("prefill")
    assert shown.get("dry_sync", False) is dry
    assert snap["summary"]["decode"]["dry_syncs"] == int(dry)
    # /debug/engine: what is in flight by kind, the total and the share
    shown = engine_snapshot(eng)["engine"]
    assert shown["inflight_dispatches"] == len(left)
    assert shown["inflight_decode"] == left.count("decode")
    assert shown["inflight_prefill"] == left.count("prefill")
    assert shown["decode_syncs_total"] == 1
    assert shown["dry_syncs_total"] == int(dry)
    assert shown["dry_sync_share"] == float(dry)


def test_a_prefill_read_is_never_a_dry_sync():
    from gofr_tpu.tpu.utilization import engine_snapshot

    eng = _stubbed_read(("prefill", "prefill"))
    rec = eng.steps.records()[-1]
    assert rec.phase == "prefill" and not rec.dry_sync
    assert (rec.inflight, rec.inflight_prefill) == (1, 1)
    assert eng.decode_syncs_total == 0
    assert engine_snapshot(eng)["engine"]["dry_sync_share"] == 0.0


def test_the_queued_blocks_gauge_is_registered_and_set():
    """`app_tpu_decode_blocks_queued`: what each decode read found behind
    it; 0 while slots decode is the dry sync."""
    from gofr_tpu.config import MockConfig
    from gofr_tpu.metrics import Manager
    from gofr_tpu.tpu.device import TPUClient

    metrics = Manager()
    client = TPUClient(MockConfig({}))
    client.use_metrics(metrics)
    client.register_metrics()
    assert metrics.get("app_tpu_decode_blocks_queued") is not None
    for kinds, queued in ((("decode", "prefill", "decode", "decode"), 2),
                          (("decode", "prefill"), 0)):
        _stubbed_read(kinds, metrics=metrics)
        assert f"app_tpu_decode_blocks_queued {queued}" in metrics.expose()


# -- end-to-end: /debug/steps + exemplar drill through the example server ----
EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _load_llm_server():
    path = os.path.join(EXAMPLES, "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location(
        "example_llm_server_stepledger", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, resp.headers.get("Content-Type", ""), \
            resp.read().decode()


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_debug_steps_and_exemplar_drill_e2e():
    """The whole loop on the example server: serve a request, read
    /debug/steps, scrape OpenMetrics, follow a TTFT exemplar's request id
    back into /debug/requests/{id}."""
    from gofr_tpu.config import MockConfig

    module = _load_llm_server()
    app = module.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "TPU_PLATFORM": "cpu",
        "MODEL_PRESET": "debug", "WARMUP": "false",
        "REQUEST_TIMEOUT": "60"}))
    app.start()
    try:
        base = f"http://127.0.0.1:{app.http_port}"
        req = urllib.request.Request(
            f"{base}/generate", method="POST",
            data=json.dumps({"prompt": "hello", "max_tokens": 5,
                             "stream": False}).encode())
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 201

        status, _, body = _get(f"{base}/debug/steps?recent=16")
        assert status == 200
        snap = json.loads(body)["data"]
        assert snap["steps_total"] >= 1
        assert snap["recent"], "step ring empty after a served request"
        for rec in snap["recent"]:
            assert sum(rec["segments"].values()) == pytest.approx(
                rec["wall_s"], rel=0.05, abs=1e-4)
        assert "sentinel" in snap and "baselines" in snap

        metrics_base = f"http://127.0.0.1:{app.metrics_port}/metrics"
        # classic scrape: no exemplars, classic content type
        status, ctype, classic = _get(metrics_base)
        assert status == 200
        assert ctype.startswith("text/plain")
        assert "# {" not in classic
        assert "# EOF" not in classic
        # and the canonical le rendering holds on default buckets
        assert 'le="1.0"' in classic
        assert 'le="1e' not in classic and 'le="2e' not in classic

        # OpenMetrics scrape: exemplars + EOF + negotiated content type
        status, ctype, om = _get(
            metrics_base,
            headers={"Accept": "application/openmetrics-text"})
        assert status == 200
        assert ctype.startswith("application/openmetrics-text")
        assert om.rstrip().endswith("# EOF")
        match = re.search(
            r'app_tpu_ttft_seconds_bucket\{[^}]*\} \d+ '
            r'# \{[^}]*request_id="(\d+)"', om)
        assert match, "no TTFT exemplar in the OpenMetrics scrape"
        request_id = match.group(1)
        assert 'segment="device_sync"' in om  # step histograms landed too

        # the exemplar's request id resolves in the flight recorder
        status, _, detail = _get(f"{base}/debug/requests/{request_id}")
        assert status == 200
        detail = json.loads(detail)["data"]
        assert str(detail["id"]) == request_id
        assert detail["generated"] == 5
    finally:
        app.shutdown()
