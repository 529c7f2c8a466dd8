"""Engine wedge detection: stall telemetry, 503 shed, health degradation.

The failure they guard: the device serves normally (boot, warm-up, first
requests), then stops answering — the loop thread blocks forever inside a
PJRT sync, new submits queue behind it, and every client hangs until its
own timeout. These tests simulate that exact shape (a _sync_oldest that never returns until released) and assert
the serving-grade behavior: stall_seconds grows, submit() sheds with
EngineStalledError (503), health reports DEGRADED with the stall age, and
the engine recovers fully when the device answers again.

Reference posture: the breaker fails fast while open instead of queueing
doomed work (/root/reference/pkg/gofr/service/circuit_breaker.go:59-120);
here the "breaker" is host-side loop telemetry because no device-touching
probe can time out of a wedged PJRT call.
"""

import threading
import time

import pytest

from gofr_tpu.container import STATUS_DEGRADED, STATUS_UP
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.engine import EngineStalledError
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()


@pytest.fixture
def engine():
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(16,), decode_block_size=4)
    eng.start()
    yield eng
    eng.stop()


def test_idle_engine_reports_healthy(engine):
    # an idle loop parks in 50ms waits — the heartbeat keeps moving
    time.sleep(0.2)
    assert engine.stall_seconds < 1.0
    assert not engine.wedged()
    h = engine.health_check()
    assert h.status == STATUS_UP
    assert "stall_seconds" not in h.details


def test_stopped_engine_reports_zero_stall():
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(16,))
    assert eng.stall_seconds == 0.0  # never started: nothing to measure
    eng.start()
    eng.stop()
    assert eng.stall_seconds == 0.0  # dead thread cannot be stalled


def test_wedged_engine_sheds_and_degrades_then_recovers(engine):
    gate = threading.Event()
    orig_sync = engine._sync_oldest

    def stuck_sync():
        # the simulated PJRT call that never returns until the device
        # answers; then the real sync completes the dispatched work
        gate.wait(timeout=30)
        return orig_sync()

    engine._sync_oldest = stuck_sync
    engine.STALL_REJECT_S = 0.3

    first = engine.submit([1, 2, 3], max_new_tokens=4)
    deadline = time.time() + 10
    while engine.stall_seconds < 0.6 and time.time() < deadline:
        time.sleep(0.05)
    assert engine.stall_seconds >= 0.6, "loop never blocked in the stuck sync"

    # new traffic sheds immediately with the retry-elsewhere status
    with pytest.raises(EngineStalledError) as ei:
        engine.submit([4, 5, 6], max_new_tokens=4)
    assert ei.value.status_code == 503

    # aggregate health shows DEGRADED + the stall age
    h = engine.health_check()
    assert h.status == STATUS_DEGRADED
    assert h.details["stall_seconds"] >= 0.6

    # device answers again: the blocked dispatch completes, the first
    # request finishes, and the engine takes new work
    gate.set()
    engine._sync_oldest = orig_sync
    assert len(first.result(timeout_s=60)) == 4
    assert len(engine.generate([7, 8], max_new_tokens=3)) == 3
    assert engine.health_check().status == STATUS_UP


def test_container_health_contributor_degrades_aggregate():
    from gofr_tpu import MockConfig, new_mock_container
    from gofr_tpu.datasource import Health

    container = new_mock_container()
    container.add_health_contributor(
        "engine", lambda: Health(status=STATUS_DEGRADED,
                                 details={"stall_seconds": 12.0}))
    # de-flap: one DEGRADED check is visible but NOT yet actionable (a
    # single slow probe must not get the node pulled); the second
    # consecutive one degrades the aggregate
    out = container.health()
    assert out["status"] == STATUS_UP
    assert out["degrading"] is True
    assert out["details"]["engine"]["details"]["stall_seconds"] == 12.0
    out = container.health()
    assert out["status"] == STATUS_DEGRADED
    assert out["details"]["engine"]["details"]["stall_seconds"] == 12.0

    # a contributor that raises is DOWN, and the aggregate stays DEGRADED
    container2 = new_mock_container()
    container2.add_health_contributor(
        "engine", lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    out2 = container2.health()
    assert out2["status"] == STATUS_DEGRADED
    assert out2["details"]["engine"]["details"]["error"] == "boom"

    assert MockConfig  # imported symbol used by sibling tests' idiom


def test_device_health_answers_while_probe_is_stuck():
    """/health must answer even when the device probe blocks forever inside
    a wedged PJRT call: DEGRADED within the probe timeout, single-flight
    (polls reuse the one stuck thread instead of leaking one each)."""
    from gofr_tpu.tpu.device import TPUClient

    client = TPUClient()
    client.connect()
    client.HEALTH_PROBE_TIMEOUT_S = 0.2

    h = client.health_check()
    assert h.status == STATUS_UP  # healthy CPU backend probes fine

    gate = threading.Event()
    client._probe_device = lambda: gate.wait(timeout=30)  # wedged probe

    t0 = time.time()
    h1 = client.health_check()
    assert time.time() - t0 < 2.0  # answered, did not hang
    assert h1.status == STATUS_DEGRADED
    assert "not answering" in h1.details["error"]

    stuck = client._probe_thread
    h2 = client.health_check()
    assert h2.status == STATUS_DEGRADED
    assert client._probe_thread is stuck  # single-flight: same thread reused

    gate.set()
    stuck.join(timeout=5)
    del client._probe_device  # back to the real probe
    assert client.health_check().status == STATUS_UP


def test_grpc_maps_shed_errors_to_unavailable():
    """Duck-typed 503s (draining, stalled) must surface as UNAVAILABLE so
    gRPC clients retry elsewhere, not INTERNAL."""
    grpc = pytest.importorskip("grpc")

    from gofr_tpu.grpcx import GRPCServer
    from gofr_tpu.tpu.engine import EngineDrainingError

    from gofr_tpu import new_mock_container

    container = new_mock_container()
    server = GRPCServer(container, port=0, logger=container.logger)
    assert (server._status_for(EngineStalledError(200.0))
            is grpc.StatusCode.UNAVAILABLE)
    assert (server._status_for(EngineDrainingError())
            is grpc.StatusCode.UNAVAILABLE)
    assert (server._status_for(ValueError("bad"))
            is grpc.StatusCode.INVALID_ARGUMENT)
    assert (server._status_for(RuntimeError("boom"))
            is grpc.StatusCode.INTERNAL)


def test_stall_gauge_refreshes_at_scrape():
    """app_tpu_engine_stall_seconds is pulled by a container scrape hook —
    the one metric the engine loop can never push itself (a wedged loop is
    stuck inside the device call)."""
    from gofr_tpu import new_mock_container

    container = new_mock_container()
    m = container.metrics_manager
    m.new_gauge("app_tpu_engine_stall_seconds", "test")

    class FakeEngine:
        stall_seconds = 0.0

    eng = FakeEngine()
    container.add_scrape_hook("engine_stall", lambda: m.set_gauge(
        "app_tpu_engine_stall_seconds", round(eng.stall_seconds, 1)))
    # idempotent: a second registration under the same name replaces
    container.add_scrape_hook("engine_stall", lambda: m.set_gauge(
        "app_tpu_engine_stall_seconds", round(eng.stall_seconds, 1)))
    assert len(container._scrape_hooks) == 1

    container.refresh_runtime_metrics()
    assert m.get("app_tpu_engine_stall_seconds").series[tuple()] == 0.0
    eng.stall_seconds = 42.2
    container.refresh_runtime_metrics()
    assert m.get("app_tpu_engine_stall_seconds").series[tuple()] == 42.2

    # a broken hook must never break the scrape
    container.add_scrape_hook("broken",
                              lambda: (_ for _ in ()).throw(RuntimeError()))
    container.refresh_runtime_metrics()  # does not raise
