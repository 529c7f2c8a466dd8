"""QoS serving plane (gofr_tpu/tpu/qos.py): class banding, quotas, the
burn-actuated shed ladder, preemption-with-replay, and the batch lane.

Fast units run against stub engines / injected clocks (`-m qos` inner
loop); the engine-integration tests boot the debug model on CPU like the
rest of the suite.
"""

import json
import threading
import time
import types
import urllib.request

import pytest

from gofr_tpu.http.errors import InvalidParam
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.qos import (BatchLane, CLASS_BAND, LEVEL_LABELS,
                              QoSController, QoSShedError, banded_priority,
                              normalize_class)

pytestmark = pytest.mark.qos

CFG = LlamaConfig.debug()


class MockLogger:
    def debugf(self, *a, **k):
        pass

    infof = warnf = errorf = debugf


def _controller(**kw):
    kw.setdefault("burn_probe", lambda: {})
    return QoSController(**kw)


# -- units: class normalization + banding -------------------------------------

def test_normalize_and_banded_priority():
    assert normalize_class(None) is None
    assert normalize_class("") is None
    assert normalize_class("  Batch ") == "batch"
    assert normalize_class("interactive") == "interactive"
    with pytest.raises(InvalidParam):
        normalize_class("premium")
    with pytest.raises(InvalidParam):
        normalize_class(7)
    # unclassified passes priority through untouched (legacy behavior)
    assert banded_priority(None, 3) == 3
    assert banded_priority(None, -1) == -1
    # classes land in disjoint bands, client priority clamped to 0..9
    assert banded_priority("interactive", 0) == 0
    assert banded_priority("interactive", 99) == 9
    assert banded_priority("standard", 0) == CLASS_BAND["standard"]
    assert banded_priority("batch", -5) == CLASS_BAND["batch"]
    # bands never overlap: worst interactive < best standard < best batch
    assert banded_priority("interactive", 9) < banded_priority("standard", 0)
    assert banded_priority("standard", 9) < banded_priority("batch", 0)


def test_unknown_class_rejected_at_every_door():
    """engine.submit and DynamicBatcher.submit both die with the typed
    400 (InvalidParam) for an unknown class string — even with no QoS
    controller attached."""
    from gofr_tpu.tpu.scheduler import DynamicBatcher

    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                         prefill_buckets=(8, 16), logger=MockLogger())
    eng.start()
    try:
        with pytest.raises(InvalidParam):
            eng.submit([1, 2, 3], max_new_tokens=2, qos_class="turbo")
        # known classes band even without a controller? No — they pass
        # through unbanded, but they must VALIDATE
        req = eng.submit([1, 2, 3], max_new_tokens=2, qos_class="batch")
        assert req.result(timeout_s=120)
    finally:
        eng.stop()
    batcher = DynamicBatcher(lambda batch: batch)
    with pytest.raises(InvalidParam):
        batcher.submit([1.0], qos_class="gold-tier")


# -- units: quotas + deadlines against a stub engine --------------------------

def _stub_engine(n_slots=4, active=0):
    slots = []
    for i in range(n_slots):
        slot = types.SimpleNamespace(active=i < active, chunking=None,
                                     request=None, pages=None)
        slots.append(slot)
    return types.SimpleNamespace(slots=slots)


def _stub_request(cls, enqueued_at=0.0, emitted=(), priority=0):
    return types.SimpleNamespace(qos_class=cls, tenant="t",
                                 enqueued_at=enqueued_at,
                                 emitted=list(emitted), priority=priority)


def test_reserved_slot_quota_and_deadlines():
    now = [100.0]
    ctl = _controller(interactive_reserved_slots=1,
                      deadlines={"standard": 5.0},
                      clock=lambda: now[0])
    eng = _stub_engine(n_slots=3, active=1)  # 2 free slots
    # non-interactive with 2 free and 1 reserved: admit (2 > 1) ...
    assert ctl.admission_decision(_stub_request("standard",
                                                enqueued_at=99.0), eng) \
        == "admit"
    # ... but not when this round already claimed one (2 - 1 <= 1)
    assert ctl.admission_decision(_stub_request("batch", enqueued_at=99.0),
                                  eng, taken=1) == "park"
    # interactive ignores the reservation entirely
    assert ctl.admission_decision(_stub_request("interactive",
                                                enqueued_at=99.0),
                                  eng, taken=1) == "admit"
    # unclassified is quota-exempt by contract (legacy preservation)
    assert ctl.admission_decision(_stub_request(None, enqueued_at=99.0),
                                  eng, taken=1) == "admit"
    # a standard request over its 5 s deadline budget expires ...
    assert ctl.admission_decision(_stub_request("standard",
                                                enqueued_at=90.0), eng) \
        == "expire"
    # ... unless it is mid-stream (replay/preemption requeue): zero-loss
    assert ctl.admission_decision(_stub_request("standard", enqueued_at=90.0,
                                                emitted=[7]), eng) == "admit"


def test_batch_parks_at_level_one():
    ctl = _controller(interactive_reserved_slots=0)
    eng = _stub_engine(n_slots=2)
    req = _stub_request("batch", enqueued_at=0.0)
    assert ctl.admission_decision(req, eng) == "admit"
    ctl.force_level(1)
    assert ctl.admission_decision(req, eng) == "park"
    # interactive and standard still admit at park_batch
    assert ctl.admission_decision(_stub_request("interactive"), eng) \
        == "admit"
    assert ctl.admission_decision(_stub_request("standard"), eng) == "admit"


# -- units: the shed ladder with an injected clock ----------------------------

def test_ladder_walk_and_auto_recovery():
    now = [0.0]
    states = {"ttft": "ok"}
    ctl = QoSController(escalate_hold_s=5.0, recover_hold_s=10.0,
                        shed_tracks=("ttft", "tpot"), retry_after_s=3.5,
                        clock=lambda: now[0], burn_probe=lambda: states)
    assert ctl.evaluate() == 0
    # warn arms park_batch immediately
    states["ttft"] = "warn"
    assert ctl.evaluate() == 1
    # page escalates one level per hold dwell
    states["ttft"] = "page"
    assert ctl.evaluate() == 1          # dwell not yet served
    now[0] += 5.0
    assert ctl.evaluate() == 2
    now[0] += 5.0
    # request_replica degrades nothing locally: the door stays open and
    # the fleet sees the ask instead
    assert ctl.evaluate() == 3
    assert ctl.scaleout_wanted
    ctl.check_submit("standard")
    now[0] += 5.0
    assert ctl.evaluate() == 4          # capped at shed_standard
    now[0] += 5.0
    assert ctl.evaluate() == 4
    # shed_standard sheds standard (and unclassified-as-standard) with a
    # duck 503 + Retry-After; interactive and batch always pass the door
    with pytest.raises(QoSShedError) as exc:
        ctl.check_submit("standard")
    assert exc.value.status_code == 503
    assert exc.value.retry_after_s == 3.5
    with pytest.raises(QoSShedError):
        ctl.check_submit(None)
    ctl.check_submit("interactive")
    ctl.check_submit("batch")
    # recovery: one level back down per recover_hold of all-OK
    states["ttft"] = "ok"
    assert ctl.evaluate() == 4
    now[0] += 10.0
    assert ctl.evaluate() == 3
    assert ctl.scaleout_wanted          # still asking while at the rung
    now[0] += 10.0
    assert ctl.evaluate() == 2
    assert not ctl.scaleout_wanted
    now[0] += 10.0
    assert ctl.evaluate() == 1
    now[0] += 10.0
    assert ctl.evaluate() == 0
    ctl.check_submit("standard")        # door open again
    trail = [t["to"] for t in ctl.snapshot()["ladder"]["transitions"]]
    assert trail == ["park_batch", "preempt_batch", "request_replica",
                     "shed_standard", "request_replica", "preempt_batch",
                     "park_batch", "ok"]
    assert [lbl for lbl in LEVEL_LABELS] == ["ok", "park_batch",
                                             "preempt_batch",
                                             "request_replica",
                                             "shed_standard"]


# -- engine integration: class-ordered admission ------------------------------

def test_class_ordered_admission_under_contention():
    """With one slot busy, later-submitted interactive work admits before
    earlier-submitted standard and batch work — the heap's class bands in
    action — while FIFO order holds inside a class."""
    params = llama_init(CFG, seed=0)
    # pages of 16: at the default 128 this pool would be ONE page, and a
    # batch request's share of it (1.0) is over `batch_page_fraction` for
    # good: it would park and never admit
    eng = PagedLLMEngine(params, CFG, n_slots=1, max_seq_len=128,
                         page_size=16, prefill_buckets=(8,),
                         logger=MockLogger())
    eng.qos = _controller(interactive_reserved_slots=0)
    eng.qos.engine = eng
    eng.start()
    try:
        eng.warmup()
        blocker = eng.submit([1, 2, 3], max_new_tokens=64, temperature=0.0)
        while blocker.admitted_at is None:
            time.sleep(0.002)
        batch = eng.submit([4, 5, 6], max_new_tokens=2, qos_class="batch")
        standard = eng.submit([4, 5, 6], max_new_tokens=2,
                              qos_class="standard")
        inter = eng.submit([4, 5, 6], max_new_tokens=2,
                           qos_class="interactive")
        for req in (blocker, inter, standard, batch):
            req.result(timeout_s=300)
        assert inter.admitted_at < standard.admitted_at < batch.admitted_at
    finally:
        eng.qos.stop()
        eng.stop()


# -- engine integration: preemption with replay -------------------------------

@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_preempted_batch_matches_golden_tokens():
    """Ladder level 2 preempts a running batch decode mid-stream; after
    recovery it replays from prompt + emitted and the final token stream
    is IDENTICAL to an unpreempted run — the PR 3 zero-loss contract,
    now exercised by the scheduler instead of a device fault."""
    params = llama_init(CFG, seed=0)
    ctl = _controller(interactive_reserved_slots=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=512,
                         prefill_buckets=(8, 64), page_size=8,
                         logger=MockLogger())
    eng.qos = ctl
    ctl.engine = eng
    eng.start()
    try:
        eng.warmup()
        req = eng.submit([5, 6, 7], max_new_tokens=400, temperature=0.0,
                         qos_class="batch", tenant="acme")
        deadline = time.time() + 120
        while time.time() < deadline and not req.emitted:
            time.sleep(0.002)
        assert req.emitted, "batch decode never started"
        ctl.force_level(2)
        while time.time() < deadline and req.preemptions == 0 \
                and req.finished_at is None:
            time.sleep(0.002)
        assert req.preemptions >= 1, \
            "decode finished before the ladder could preempt (raise " \
            "max_new_tokens if this flakes)"
        ctl.force_level(0)
        preempted_tokens = req.result(timeout_s=300)
        golden = eng.submit([5, 6, 7], max_new_tokens=400, temperature=0.0)
        assert preempted_tokens == golden.result(timeout_s=300)
        snap = ctl.snapshot()
        assert snap["preemptions_total"] >= 1
        assert snap["classes"]["batch"]["preempted"] >= 1
        assert snap["tenants"]["batch"].get("acme") == 1
    finally:
        ctl.stop()
        eng.stop()


# -- engine integration: pubsub -> lane -> result round trip ------------------

def test_batch_lane_round_trip():
    from gofr_tpu.pubsub.inproc import InProcBroker

    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                         prefill_buckets=(8, 16), logger=MockLogger())
    broker = InProcBroker()
    lane = BatchLane(eng, broker, max_inflight=2, poll_s=0.05,
                     logger=MockLogger())
    eng.start()
    lane.start()
    try:
        for i in range(3):
            broker.publish("qos.batch.jobs", json.dumps(
                {"tokens": [1 + i, 2, 3], "max_tokens": 4,
                 "tenant": "acme", "job_id": i}).encode())
        broker.publish("qos.batch.jobs", b"not json at all")  # poison
        results = {}
        deadline = time.time() + 300
        while len(results) < 4 and time.time() < deadline:
            msg = broker.subscribe("qos.batch.results", "test",
                                   timeout_s=1.0)
            if msg is None:
                continue
            payload = json.loads(msg.value.decode())
            results[payload.get("job_id")] = payload
            msg.commit()
        assert len(results) == 4, f"lane stalled: {lane.stats()}"
        for i in range(3):
            assert results[i]["ok"] is True
            assert results[i]["tokens"] == 4
            assert results[i]["tenant"] == "acme"
        assert results[None]["ok"] is False        # the poison job
        assert "bad job payload" in results[None]["error"]
        # every message committed: nothing redelivers to a fresh poll
        assert broker.subscribe("qos.batch.jobs", lane.group,
                                timeout_s=0.1) is None
        stats = lane.stats()
        assert stats["completed"] == 3 and stats["rejected"] == 1
        assert lane.cron_drain()["completed"] == 3
    finally:
        lane.stop()
        eng.stop()


# -- e2e: /debug/qos through the example server -------------------------------

def _get_json(url):
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.status, json.loads(resp.read().decode())


def test_debug_qos_endpoint_e2e():
    """QOS=true llm-server: a classified /generate lands in the class
    ledgers, /debug/qos serves the ladder + per-class payload, and an
    unknown class header dies with the typed 400 at the HTTP door."""
    import importlib.util
    import os
    import urllib.error

    from gofr_tpu.config import MockConfig

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location("example_llm_server_qos",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    app = module.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "TPU_PLATFORM": "cpu",
        "MODEL_PRESET": "debug", "WARMUP": "false",
        "REQUEST_TIMEOUT": "60", "QOS": "true",
        "PUBSUB_BACKEND": "inproc"}))
    app.start()
    try:
        base = f"http://127.0.0.1:{app.http_port}"
        req = urllib.request.Request(
            f"{base}/generate", method="POST",
            data=json.dumps({"prompt": "hello", "max_tokens": 4,
                             "stream": False}).encode(),
            headers={"X-QoS-Class": "interactive", "X-Tenant": "acme"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 201
        bad = urllib.request.Request(
            f"{base}/generate", method="POST",
            data=json.dumps({"prompt": "hello", "max_tokens": 4,
                             "class": "platinum"}).encode())
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=60)
        assert exc.value.code == 400
        status, body = _get_json(f"{base}/debug/qos")
        assert status == 200
        snap = body["data"]
        assert snap["ladder"]["state"] == "ok"
        assert snap["classes"]["interactive"]["submitted"] >= 1
        assert snap["classes"]["interactive"]["finished"] >= 1
        assert snap["tenants"]["interactive"].get("acme", 0) >= 1
        assert "lane" in snap            # QOS_LANE default-on with pubsub
        status, metrics_text = _get_req_text(
            f"http://127.0.0.1:{app.metrics_port}/metrics")
        assert status == 200
        assert "app_tpu_qos_shed_level" in metrics_text
        assert 'app_tpu_qos_submitted_total{class="interactive"}' \
            in metrics_text
    finally:
        app.shutdown()


def _get_req_text(url):
    with urllib.request.urlopen(url, timeout=60) as resp:
        return resp.status, resp.read().decode()
