"""Flight recorder: per-request lifecycle timelines, engine child spans,
/debug/requests, and SLO goodput gauges.

ISSUE 1's acceptance surface: a request served with a traceparent header
produces engine child spans (queue/prefill/decode) sharing the inbound
trace id; /debug/requests/{id} returns a monotonic, non-overlapping phase
timeline; the ring stays bounded with no lost terminal events under
concurrent submit/abort stress; goodput gauges track the SLO window.
"""

import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.flightrecorder import FlightRecorder
from gofr_tpu.tracing import InMemoryExporter, Tracer

CFG = LlamaConfig.debug()
INBOUND_TRACE = "4bf92f3577b34da6a3ce929d0e0e4736"
TRACEPARENT = f"00-{INBOUND_TRACE}-00f067aa0ba902b7-01"


def _engine(recorder=None, tracer=None, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("decode_block_size", 4)
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, tracer=tracer,
                         flight_recorder=recorder, **kw)
    eng.start()
    return eng


def test_lifecycle_record_and_phase_timings():
    recorder = FlightRecorder(capacity=8)
    eng = _engine(recorder=recorder)
    try:
        request = eng.submit([1, 2, 3], max_new_tokens=6,
                             traceparent=TRACEPARENT)
        tokens = request.result(timeout_s=30)
        assert len(tokens) == 6
    finally:
        eng.stop()

    detail = recorder.lookup(request.id)
    assert detail is not None
    assert detail["outcome"] == "length"  # ran to its token budget
    assert detail["generated"] == 6
    assert detail["trace_id"] == INBOUND_TRACE  # raw header was enough
    # phases: monotonic, non-overlapping, and they tile the total
    phases = detail["phases"]
    for key in ("queue_s", "prefill_s", "decode_s", "total_s"):
        assert phases[key] >= 0.0
    assert (phases["queue_s"] + phases["prefill_s"] + phases["decode_s"]
            == pytest.approx(phases["total_s"], abs=1e-6))
    # the event timeline is ordered and complete
    names = [e["event"] for e in detail["events"]]
    for marker in ("enqueued", "admitted", "first_token", "finished"):
        assert marker in names
    assert names.index("enqueued") < names.index("admitted") \
        < names.index("first_token") < names.index("finished")
    times = [e["t"] for e in detail["events"]]
    assert times == sorted(times)
    # decode events were batched per dispatch sync, never per token:
    # 6 tokens at block 4 is at most 2 decode_block events
    decode_events = [e for e in detail["events"]
                    if e["event"] == "decode_block"]
    assert 1 <= len(decode_events) <= 2
    assert sum(e["tokens"] for e in decode_events) == 5  # first token rode
    # the prefill dispatch, the remaining 5 came from decode blocks


def test_engine_child_spans_share_inbound_trace_id():
    exporter = InMemoryExporter()
    tracer = Tracer(service_name="test", exporter=exporter)
    recorder = FlightRecorder(capacity=8, tracer=tracer)
    eng = _engine(recorder=recorder, tracer=tracer)
    try:
        request = eng.submit([5, 6, 7], max_new_tokens=4,
                             traceparent=TRACEPARENT)
        request.result(timeout_s=30)
    finally:
        eng.stop()

    by_name = {}
    for span in exporter.spans:
        by_name.setdefault(span.name, span)
    for name in ("engine.queue", "engine.prefill", "engine.decode"):
        assert name in by_name, f"missing child span {name}"
        assert by_name[name].trace_id == INBOUND_TRACE
        assert by_name[name].end_time >= by_name[name].start_time
    # non-overlapping, in phase order: each phase starts where the
    # previous one ended
    q, p, d = (by_name["engine.queue"], by_name["engine.prefill"],
               by_name["engine.decode"])
    assert q.end_time == pytest.approx(p.start_time, abs=1e-9)
    assert p.end_time == pytest.approx(d.start_time, abs=1e-9)
    assert d.attributes["tpu.tokens"] == 4
    # the tpu.generate span (same trace) is the children's parent
    gen = by_name.get("tpu.generate")
    assert gen is not None and gen.trace_id == INBOUND_TRACE
    assert q.parent_id == gen.span_id


def test_ring_bounded_no_lost_terminals_under_stress():
    """Concurrent submit/abort: the ring must stay at its cap, every
    request must reach exactly one terminal record, and nothing may be
    left behind as a phantom in-flight entry."""
    recorder = FlightRecorder(capacity=16)
    eng = _engine(recorder=recorder, n_slots=4)
    total, cancel_every = 48, 3
    done = []
    lock = threading.Lock()

    def worker(i):
        try:
            request = eng.submit([1 + i % 7, 2, 3], max_new_tokens=8)
            if i % cancel_every == 0:
                request.cancel()
            try:
                request.result(timeout_s=30)
            except Exception:  # noqa: BLE001 - cancel may surface late
                pass
            with lock:
                done.append(request.id)
        except Exception:  # noqa: BLE001 - shed/stop races count as done
            with lock:
                done.append(None)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(total)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    # cancelled slots free asynchronously; wait for the engine to settle
    deadline = time.time() + 20
    while time.time() < deadline:
        snap = recorder.snapshot()
        if recorder.finished_total >= total and not snap["in_flight"]:
            break
        time.sleep(0.05)
    eng.stop()

    snap = recorder.snapshot()
    assert recorder.finished_total == total  # no lost terminal events
    assert snap["in_flight"] == []           # no phantom live records
    assert len(snap["recent"]) <= 16         # ring stayed bounded
    assert snap["capacity"] == 16
    for rec in snap["recent"]:               # every kept record is terminal
        assert rec["outcome"] in ("length", "stop", "cancelled", "error",
                                  "aborted")


def test_slo_goodput_window_and_gauges():
    from gofr_tpu.metrics import Manager
    from gofr_tpu.tpu.flightrecorder import register_slo_gauges

    class FakeReq:
        def __init__(self, rid, ttft_s, tpot_s, tokens=11):
            self.id = rid
            self.prompt_tokens = [1, 2]
            self.max_new_tokens = tokens
            self.priority = 0
            self.span = None
            self.gen_span = None
            self.traceparent = None
            self.error = None
            self.generated = tokens
            self.enqueued_at = 100.0
            self.admitted_at = 100.0 + ttft_s / 2
            self.first_token_at = 100.0 + ttft_s
            self.finished_at = 100.0 + ttft_s + tpot_s * (tokens - 1)

    metrics = Manager()
    register_slo_gauges(metrics)
    register_slo_gauges(metrics)  # idempotent
    recorder = FlightRecorder(capacity=8, slo_ttft_s=0.150,
                              slo_tpot_s=0.050, metrics=metrics)
    # 3 requests meet the TTFT target, 1 blows it; 2 meet TPOT, 2 miss
    for rid, ttft, tpot in ((1, 0.05, 0.01), (2, 0.10, 0.02),
                            (3, 0.12, 0.40), (4, 0.90, 0.30)):
        req = FakeReq(rid, ttft, tpot)
        recorder.record_enqueued(req)
        recorder.record_admitted(req, slot=0, bucket=16)
        recorder.record_first_token(req)
        recorder.record_finished(req, "stop")

    stats = recorder.slo_stats()
    assert stats["window"] == 4
    assert stats["ttft_goodput"] == pytest.approx(0.75)
    assert stats["tpot_goodput"] == pytest.approx(0.5)
    assert metrics.get("app_tpu_slo_ttft_goodput").series  # gauge was set
    exposition = metrics.expose()
    assert "app_tpu_slo_ttft_goodput 0.75" in exposition
    assert "app_tpu_slo_tpot_goodput 0.5" in exposition


EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _load_llm_server():
    path = os.path.join(EXAMPLES, "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location(
        "example_llm_server_flightrec", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _call(port, path, method="GET", body=None, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None,
        headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read().decode() or "null")
    except urllib.error.HTTPError as err:
        return err.code, json.loads(err.read().decode() or "null")


def test_debug_requests_endpoint_on_llm_server():
    """End-to-end through the example server: a /generate with a
    traceparent header lands in /debug/requests with full phase timings,
    and the detail endpoint 404s for unknown ids."""
    from gofr_tpu.config import MockConfig

    module = _load_llm_server()
    app = module.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "TPU_PLATFORM": "cpu",
        "MODEL_PRESET": "debug", "WARMUP": "false",
        "REQUEST_TIMEOUT": "60", "TRACE_EXPORTER": "memory"}))
    app.start()
    try:
        port = app.http_port
        status, body = _call(port, "/generate", "POST",
                             {"prompt": "hello", "max_tokens": 5,
                              "stream": False},
                             headers={"traceparent": TRACEPARENT})
        assert status == 201 and body["data"]["tokens"] == 5

        status, listing = _call(port, "/debug/requests")
        assert status == 200
        listing = listing["data"]
        for key in ("in_flight", "recent", "slo", "engine_events"):
            assert key in listing
        assert listing["finished_total"] >= 1
        rec = listing["recent"][0]
        assert rec["trace_id"] == INBOUND_TRACE
        assert rec["generated"] == 5

        status, detail = _call(port, f"/debug/requests/{rec['id']}")
        assert status == 200
        detail = detail["data"]
        names = [e["event"] for e in detail["events"]]
        assert names.index("enqueued") < names.index("admitted") \
            < names.index("first_token") < names.index("finished")
        phases = detail["phases"]
        assert (phases["queue_s"] + phases["prefill_s"] + phases["decode_s"]
                == pytest.approx(phases["total_s"], abs=1e-6))

        status, _ = _call(port, "/debug/requests/999999")
        assert status == 404
        status, _ = _call(port, "/debug/requests/not-an-id")
        assert status == 400

        # engine child spans reached the configured exporter with the
        # inbound trace id (the whole point of the propagation)
        exporter = app.container.tracer.exporter
        engine_spans = [s for s in exporter.spans
                        if s.name.startswith("engine.")]
        assert {s.name for s in engine_spans} >= {
            "engine.queue", "engine.prefill", "engine.decode"}
        assert all(s.trace_id == INBOUND_TRACE for s in engine_spans)

        # SLO gauges are registered and live on the metrics manager
        gauge = app.container.metrics_manager.get("app_tpu_slo_ttft_goodput")
        assert gauge is not None and gauge.series
    finally:
        app.shutdown()


def test_score_window_divides_nonstandard_bucket():
    """ADVICE r5: a config-controlled prefill bucket that is not a
    multiple of 128 (here 192) must not push scoring windows past the
    cache — W falls back to gcd(S, 128) so windows always divide S."""
    eng = _engine(prefill_buckets=(16, 192), max_seq_len=256)
    try:
        prompt = [1, 2, 3]
        completion = [(i * 7) % 50 + 1 for i in range(140)]  # spans S=192
        chosen, top_ids, top_lps = eng.score(prompt, completion, top=3)
        assert chosen.shape == (140,)
        assert top_ids.shape == (140, 3)
        import numpy as np

        assert np.all(np.isfinite(chosen))
        assert np.all(chosen <= 0.0)  # log-probabilities
    finally:
        eng.stop()


def test_concurrent_device_health_checks_never_crash():
    """ADVICE r5: two concurrent health polls could double-start the probe
    and unpack a None result (TypeError -> spurious DOWN). Hammer
    health_check from many threads; every answer must be a valid status."""
    from gofr_tpu.tpu.device import TPUClient

    client = TPUClient()
    client.connect()
    client.HEALTH_PROBE_TIMEOUT_S = 1.0
    results, errors = [], []
    lock = threading.Lock()

    def poll():
        for _ in range(5):
            try:
                h = client.health_check()
                with lock:
                    results.append(h.status)
            except Exception as exc:  # noqa: BLE001 - the bug this guards
                with lock:
                    errors.append(exc)

    threads = [threading.Thread(target=poll) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert results and all(s in ("UP", "DEGRADED") for s in results)
