"""Chaos suite: fault injection, replay-after-reset, quarantine, breaker.

The crash-only contract (docs/resilience.md) under deterministic injected
failures on CPU JAX: a mid-decode device reset is INVISIBLE to clients
(streams pause, every delivered position exactly once, within the retry
budget), a poison request is quarantined instead of reset-looping the
engine, a reset storm opens the breaker (submit -> 503 DeviceLostError,
health DOWN) and a half-open probe closes it — and the fault plane itself
is provably absent (one attribute check, no route) when disarmed.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu.container import STATUS_DEGRADED, STATUS_DOWN, STATUS_UP
from gofr_tpu.logging import MockLogger
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.engine import CacheLostError, DeviceLostError
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.faults import (FaultPlane, InjectedFault,
                                 ResetStormBreaker)
from gofr_tpu.tpu.flightrecorder import FlightRecorder

CFG = LlamaConfig.debug()
PARAMS = llama_init(CFG, seed=0)


def _engine(**kw):
    defaults = dict(n_slots=8, max_seq_len=128, prefill_buckets=(16, 32),
                    decode_block_size=4, logger=MockLogger())
    defaults.update(kw)
    return PagedLLMEngine(PARAMS, CFG, **defaults)


# -- fault plane unit behavior ------------------------------------------------
def test_fault_plane_rules_deterministic_and_bounded():
    plane = FaultPlane(plan=[{"site": "engine.decode", "nth": 3}])
    plane.hit("engine.decode")
    plane.hit("engine.decode")
    with pytest.raises(InjectedFault):
        plane.hit("engine.decode")
    plane.hit("engine.decode")  # times defaults to 1: rule exhausted
    snap = plane.snapshot()
    assert snap["hits"]["engine.decode"] == 4
    assert snap["rules"][0]["fired"] == 1
    assert snap["fired"][0]["hit"] == 3

    # delay action sleeps instead of raising
    lag = FaultPlane(plan=[{"site": "engine.sync", "action": "delay",
                            "delay_s": 0.05, "times": 1}])
    t0 = time.time()
    lag.hit("engine.sync")
    assert time.time() - t0 >= 0.04

    # probabilistic rules draw from the seeded RNG: same seed, same pattern
    def pattern(seed):
        p = FaultPlane(plan=[{"site": "s", "prob": 0.5, "times": 0}],
                       seed=seed)
        out = []
        for _ in range(64):
            try:
                p.hit("s")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    assert pattern(42) == pattern(42)
    assert pattern(42) != pattern(43)

    # malformed plans reject without arming
    with pytest.raises(ValueError):
        FaultPlane(plan=[{"site": "s", "action": "explode"}])
    with pytest.raises(ValueError):
        FaultPlane(plan=[{"site": "s", "nth": 1, "every": 2}])


def test_breaker_state_machine():
    t = [0.0]
    br = ResetStormBreaker(max_resets=2, window_s=10.0, cooldown_s=5.0,
                           clock=lambda: t[0])
    assert br.reject_for() is None and not br.blocked()
    assert br.record_reset() is False        # 1 reset: under the threshold
    t[0] = 1.0
    assert br.record_reset() is True         # 2 inside the window: OPEN
    assert br.blocked() and br.state == br.OPEN and br.state_code == 2
    assert br.reject_for() >= 0.5
    assert not br.probe_due()                # cooldown not elapsed
    t[0] = 6.5
    assert br.probe_due()                    # ONCE: open -> half_open
    assert not br.probe_due()
    assert br.reject_for() is not None       # half-open still sheds
    br.probe_failed()
    assert br.state == br.OPEN               # failed probe: fresh cooldown
    t[0] = 12.0
    assert br.probe_due()
    assert br.probe_ok() is True
    assert br.state == br.CLOSED and br.reject_for() is None

    # resets spaced wider than the window never trip
    t[0] = 100.0
    assert br.record_reset() is False
    t[0] = 200.0
    assert br.record_reset() is False

    # a reset landing while half-open goes straight back open, and the
    # stale in-flight probe's verdict is ignored
    t[0] = 300.0
    br.record_reset()
    t[0] = 300.1
    assert br.record_reset() is True
    t[0] = 306.0
    assert br.probe_due()
    assert br.record_reset() is False and br.state == br.OPEN
    assert br.probe_ok() is False
    assert br.state == br.OPEN

    # disabled breaker (max_resets=0) never opens
    off = ResetStormBreaker(max_resets=0)
    assert all(off.record_reset() is False for _ in range(10))
    assert off.reject_for() is None


# -- replay after reset -------------------------------------------------------
def test_concurrent_streams_survive_mid_decode_reset():
    """The acceptance bar: N>=8 concurrent streams ride out an injected
    mid-decode device reset with ZERO client-visible failures — every
    stream delivers exactly its budget of positions (no duplicates, no
    drops), replay events land in the flight recorder."""
    plane = FaultPlane(plan=[{"site": "engine.decode", "nth": 2,
                              "action": "raise"}], seed=7)
    eng = _engine(faults=plane, retry_budget=2)
    eng.recorder = FlightRecorder()
    eng.start()
    N, M = 8, 12
    results, reqs, errors = {}, {}, []

    def client(i):
        try:
            req = eng.submit([1 + i, 2 + i, 3 + i], max_new_tokens=M)
            reqs[i] = req
            results[i] = list(req.stream(timeout_s=120))
        except Exception as exc:  # noqa: BLE001 - the gate below
            errors.append((i, exc))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(N)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    try:
        assert not errors, errors
        for i in range(N):
            assert len(results[i]) == M, (i, len(results[i]))
        assert eng.resets_total >= 1
        assert eng.replays_total >= 1
        events = [e["event"]
                  for e in eng.recorder.snapshot()["engine_events"]]
        assert "device_reset" in events
        replayed = [i for i, req in reqs.items() if req.replays > 0]
        assert replayed, "no request ever replayed"
        detail = eng.recorder.lookup(reqs[replayed[0]].id)
        names = [e["event"] for e in detail["events"]]
        assert "replayed" in names
        assert names.count("finished") == 1  # exactly one terminal event
    finally:
        eng.stop()


def test_paged_engine_replays_and_rereserves_pages():
    """Replay over the paged pool: the reset rebuilds the allocator, the
    survivors re-reserve pages for prompt+emitted at re-admission, and no
    page leaks once every stream completes."""
    plane = FaultPlane(plan=[{"site": "engine.decode", "nth": 2,
                              "action": "raise"}])
    eng = PagedLLMEngine(PARAMS, CFG, n_slots=4, max_seq_len=64,
                         prefill_buckets=(16,), decode_block_size=4,
                         page_size=8, prefix_cache=True,
                         logger=MockLogger(), faults=plane, retry_budget=2)
    eng.recorder = FlightRecorder()
    eng.start()
    shared = list(range(1, 12))
    results, errors = {}, []

    def client(i):
        try:
            req = eng.submit(shared + [40 + i], max_new_tokens=10)
            results[i] = list(req.stream(timeout_s=120))
        except Exception as exc:  # noqa: BLE001
            errors.append((i, exc))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=300)
    try:
        assert not errors, errors
        for i in range(4):
            assert len(results[i]) == 10, (i, len(results[i]))
        assert eng.resets_total >= 1 and eng.replays_total >= 1
        # no leaked pages: drop idle prefix-cache pages, then the pool
        # must be fully free
        eng.allocator.release(eng.prefix.drop_all_idle())
        assert eng.allocator.used_pages == 0
    finally:
        eng.stop()


def test_retry_budget_zero_fails_on_first_reset():
    plane = FaultPlane(plan=[{"site": "engine.decode", "nth": 1}])
    eng = _engine(faults=plane, retry_budget=0)
    eng.start()
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=8)
        with pytest.raises(CacheLostError):
            list(req.stream(timeout_s=60))
        assert eng.replays_total == 0
    finally:
        eng.stop()


def test_poison_request_quarantined_without_third_reset():
    """A request that is the SOLE work in flight across two consecutive
    resets is quarantined (fails with the device error) instead of being
    granted its remaining retry budget — the engine is not reset a third
    time on its behalf."""
    plane = FaultPlane(plan=[{"site": "engine.decode", "every": 1,
                              "times": 5, "action": "raise"}])
    eng = _engine(faults=plane, retry_budget=5)
    eng.recorder = FlightRecorder()
    eng.start()
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=8)
        with pytest.raises(CacheLostError):
            list(req.stream(timeout_s=120))
        assert eng.resets_total == 2, eng.resets_total
        assert eng.quarantined_total == 1
        detail = eng.recorder.lookup(req.id)
        names = [e["event"] for e in detail["events"]]
        assert "replayed" in names and "quarantined" in names
        # the engine itself survives: disarm and serve
        plane.disarm()
        assert len(eng.generate([5, 6], max_new_tokens=3)) == 3
    finally:
        eng.stop()


# -- reset-storm breaker end-to-end -------------------------------------------
def test_reset_storm_opens_breaker_then_half_open_probe_closes():
    plane = FaultPlane(plan=[{"site": "engine.decode", "every": 1,
                              "times": 2, "action": "raise"}])
    eng = _engine(n_slots=4, faults=plane, retry_budget=5,
                  reset_storm_max=2, reset_storm_window_s=60.0,
                  breaker_cooldown_s=0.4)
    eng.recorder = FlightRecorder()
    eng.start()
    try:
        # two concurrent requests so neither is sole-in-flight (no
        # quarantine): both decode dispatches fail -> 2 resets -> OPEN
        r1 = eng.submit([1, 2, 3], max_new_tokens=6)
        r2 = eng.submit([4, 5, 6], max_new_tokens=6)
        deadline = time.time() + 60
        while eng.breaker.state != "open" and time.time() < deadline:
            time.sleep(0.02)
        assert eng.breaker.state == "open"

        # open: submit sheds with the typed 503 + Retry-After hint
        with pytest.raises(DeviceLostError) as ei:
            eng.submit([7, 8], max_new_tokens=2)
        assert ei.value.status_code == 503
        assert ei.value.retry_after_s > 0
        # health reports DOWN with breaker evidence
        health = eng.health_check()
        assert health.status == STATUS_DOWN
        assert health.details["breaker"]["state"] in ("open", "half_open")

        # cooldown elapses -> the loop's half-open probe closes it (the
        # fault rules are exhausted, so the probe dispatch succeeds)
        deadline = time.time() + 60
        while eng.breaker.state != "closed" and time.time() < deadline:
            time.sleep(0.02)
        assert eng.breaker.state == "closed"

        # the interrupted requests were REPLAYED through the storm: both
        # streams complete in full once the breaker closes
        assert len(r1.result(timeout_s=120)) == 6
        assert len(r2.result(timeout_s=120)) == 6
        assert len(eng.generate([9, 10], max_new_tokens=3)) == 3
        assert eng.health_check().status == STATUS_UP

        events = [e["event"]
                  for e in eng.recorder.snapshot()["engine_events"]]
        assert "breaker_open" in events and "breaker_closed" in events
        assert "breaker_shed" in events
    finally:
        eng.stop()


def test_failed_half_open_probe_reopens():
    plane = FaultPlane(plan=[
        {"site": "engine.decode", "every": 1, "times": 2, "action": "raise"},
        # first probe fails -> re-open; second succeeds -> close
        {"site": "engine.probe", "nth": 1, "action": "raise"},
    ])
    eng = _engine(n_slots=4, faults=plane, retry_budget=5,
                  reset_storm_max=2, breaker_cooldown_s=0.2)
    eng.recorder = FlightRecorder()
    eng.start()
    try:
        r1 = eng.submit([1, 2, 3], max_new_tokens=4)
        r2 = eng.submit([4, 5, 6], max_new_tokens=4)
        deadline = time.time() + 60
        while eng.breaker.state != "closed" and time.time() < deadline:
            time.sleep(0.02)
        assert eng.breaker.state == "closed"
        assert len(r1.result(timeout_s=120)) == 4
        assert len(r2.result(timeout_s=120)) == 4
        events = [e["event"]
                  for e in eng.recorder.snapshot()["engine_events"]]
        assert "breaker_probe_failed" in events
        assert "breaker_closed" in events
    finally:
        eng.stop()


# -- other hook sites ---------------------------------------------------------
def test_health_probe_wedge_degrades_then_recovers():
    """'Wedge the health probe': the single-flight probe blocks, /health
    answers DEGRADED within its timeout, and once the wedge expires the
    next poll is healthy again."""
    from gofr_tpu.tpu.device import TPUClient

    client = TPUClient()
    client.connect()
    client.HEALTH_PROBE_TIMEOUT_S = 0.2
    assert client.health_check().status == STATUS_UP

    client.faults = FaultPlane(plan=[{"site": "device.health_probe",
                                      "action": "wedge", "delay_s": 0.6,
                                      "times": 1}])
    h = client.health_check()
    assert h.status == STATUS_DEGRADED
    assert "not answering" in h.details["error"]
    stuck = client._probe_thread
    stuck.join(timeout=10)
    assert client.health_check().status == STATUS_UP

    # a raise-action rule is a DOWN probe, not a crash
    client.faults = FaultPlane(plan=[{"site": "device.health_probe",
                                      "action": "raise", "times": 1}])
    deadline = time.time() + 10
    status = None
    while time.time() < deadline:
        status = client.health_check().status
        if status == STATUS_DOWN:
            break
        time.sleep(0.05)
    assert status == STATUS_DOWN
    client.faults = None


def test_executor_compile_latency_injection():
    import jax.numpy as jnp

    from gofr_tpu.tpu.executor import Executor

    ex = Executor()
    ex.faults = FaultPlane(plan=[{"site": "executor.compile",
                                  "action": "delay", "delay_s": 0.05,
                                  "times": 1}])
    t0 = time.time()
    program = ex.compile("lagged", lambda x: x + 1, (jnp.ones((4,)),))
    assert time.time() - t0 >= 0.04
    assert float(program(jnp.ones((4,)))[0]) == 2.0


# -- zero-overhead + HTTP gating ----------------------------------------------
def test_disarmed_components_hold_no_plane():
    """The zero-overhead contract: every hooked component defaults to
    faults=None, so the per-dispatch cost is ONE attribute check."""
    from gofr_tpu.tpu.device import TPUClient
    from gofr_tpu.tpu.executor import Executor

    eng = _engine()
    assert eng.faults is None
    assert Executor().faults is None
    assert TPUClient().faults is None
    eng.start()
    try:
        assert len(eng.generate([1, 2], max_new_tokens=3)) == 3
    finally:
        eng.stop()


def _call(port, path, method="GET", body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None)
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, dict(resp.headers), \
                json.loads(resp.read().decode() or "null")
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers), \
            json.loads(err.read().decode() or "null")


def _build_llm_app(extra=None):
    import importlib.util
    import os

    from gofr_tpu.config import MockConfig

    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location(
        "example_llm_server_faults", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    conf = {"HTTP_PORT": "0", "METRICS_PORT": "0", "TPU_PLATFORM": "cpu",
            "MODEL_PRESET": "debug", "WARMUP": "false",
            "REQUEST_TIMEOUT": "120"}
    conf.update(extra or {})
    return module.build_app(config=MockConfig(conf))


def test_debug_faults_endpoint_gated_and_drives_a_drill():
    """POST /debug/faults 404s unless FAULT_INJECTION=true in config; when
    enabled, an armed drill plan injects a reset that /generate survives
    invisibly, and the snapshot shows the firing evidence."""
    # disabled (production posture): no route at all
    app = _build_llm_app()
    app.start()
    try:
        status, _, _ = _call(app.http_port, "/debug/faults", "POST",
                             {"plan": []})
        assert status in (403, 404)
        assert app.engine.faults is None
    finally:
        app.shutdown()

    # enabled: the route arms plans and the engine survives the drill
    app2 = _build_llm_app({"FAULT_INJECTION": "true",
                           "FAULT_INJECTION_SEED": "3"})
    app2.start()
    try:
        assert app2.engine.faults is not None
        status, _, body = _call(
            app2.http_port, "/debug/faults", "POST",
            {"plan": [{"site": "engine.decode", "nth": 1,
                       "action": "raise"}], "seed": 3})
        assert status == 201, body
        status, _, resp = _call(app2.http_port, "/generate", "POST",
                                {"prompt": "hello", "max_tokens": 6,
                                 "stream": False})
        assert status == 201 and resp["data"]["tokens"] == 6
        assert app2.engine.resets_total >= 1
        status, _, snap = _call(app2.http_port, "/debug/faults")
        assert status == 200
        snap = snap["data"]
        assert snap["rules"][0]["fired"] == 1
        assert snap["fired"][0]["site"] == "engine.decode"
        # /debug/engine carries the recovery evidence + breaker state
        status, _, es = _call(app2.http_port, "/debug/engine")
        assert status == 200
        es = es["data"]
        assert es["recovery"]["resets_total"] >= 1
        assert es["breaker"]["state"] == "closed"
        # a malformed plan 400s without disturbing the armed state
        status, _, _ = _call(app2.http_port, "/debug/faults", "POST",
                             {"plan": [{"site": "s", "action": "nope"}]})
        assert status == 400
    finally:
        app2.shutdown()


def test_breaker_shed_maps_to_http_503_with_retry_after():
    """An open breaker surfaces through the HTTP boundary as a real 503
    with a Retry-After header (routed through http/errors.py), never a
    bare 500 — same for the other duck-typed sheds."""
    from gofr_tpu.http.errors import ServiceUnavailable
    from gofr_tpu.http.responder import Responder
    from gofr_tpu.tpu.engine import EngineDrainingError, EngineStalledError

    for exc in (DeviceLostError(7.2), EngineDrainingError(),
                EngineStalledError(200.0),
                ServiceUnavailable("backend busy", retry_after_s=3.0)):
        response = Responder("POST").respond(None, exc)
        assert response.status == 503, type(exc).__name__
        assert int(response.headers["Retry-After"]) >= 1, type(exc).__name__

    # the llm-server routes engine sheds through ServiceUnavailable
    app = _build_llm_app()
    app.start()
    try:
        app.engine._draining = True
        status, headers, body = _call(app.http_port, "/generate", "POST",
                                      {"prompt": "hi", "max_tokens": 2,
                                       "stream": False})
        assert status == 503
        assert "Retry-After" in headers
        assert "draining" in body["error"]["message"]
        app.engine._draining = False
    finally:
        app.shutdown()
