"""Journey plane: cross-hop request waterfalls + fleet SLO rollup.

ISSUE 16's acceptance surface: the router records every forwarded
request's route decisions / retries / stream outcome, stitches them to
the replicas' flight-recorder timelines by W3C trace id, and serves one
causally-ordered waterfall at GET /debug/journey/{id} — including for a
retried request — while GET /debug/fleet/slo merges router-observed
burn with every replica's /debug/slo and raises the fleet_burn_hidden
incident when the fleet pages and no replica does.

Stub replicas (the test_fleet.py idiom: real Apps, no engine) fabricate
the replica half of the journey keyed by the traceparent they received,
so assembly/retry/stream-break mechanics run fast; one slow test boots
REAL llm-server replicas — one of them DISAGG_MODE=both — behind the
real router and asserts trace continuity router -> prefill -> hand-off
-> decode on the assembled waterfall.
"""

import importlib.util
import json
import os
import time
import urllib.error
import urllib.request

import pytest

from gofr_tpu import App, Stream
from gofr_tpu.config import MockConfig
from gofr_tpu.datasource import Health, STATUS_UP
from gofr_tpu.fleet.journey import JourneyRecorder
from gofr_tpu.fleet.slo import FleetSLO
from gofr_tpu.http.errors import HTTPError, ServiceUnavailable
from gofr_tpu.tpu.flightrecorder import FlightRecorder
from gofr_tpu.tpu.journey import (hops_from_detail, is_trace_id,
                                  order_hops)

pytestmark = pytest.mark.journey

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")
_HOP_ORDER = ("route", "queue", "prefill", "kv_handoff", "decode",
              "stream", "finish")


def _load(example, alias):
    path = os.path.join(EXAMPLES, example, "main.py")
    spec = importlib.util.spec_from_file_location(alias, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _trace_of(traceparent):
    parts = (traceparent or "").split("-")
    return parts[1] if len(parts) == 4 else None


def _get_json(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read().decode())["data"]


class StubReplica:
    """llm-server-shaped backend without an engine, extended with the
    replica journey surface: /debug/journey/{id} answers with hops
    fabricated for every trace the stub served — what a real replica's
    flight recorder would hold."""

    def __init__(self, name, tokens=3):
        self.name = name
        self.tokens = tokens
        self.state = {"status": STATUS_UP, "queue_depth": 0, "shed": False,
                      "retry_after": 1, "die_after": None}
        self.served = []
        self.journeys = {}
        app = App(config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": name,
            "REQUEST_TIMEOUT": "30", "LOG_LEVEL": "ERROR"}))
        st = self.state

        app.container.add_health_contributor(
            "engine", lambda: Health(status=st["status"], details={}))

        @app.post("/generate")
        def generate(ctx):
            body = ctx.bind()
            if st["shed"]:
                raise ServiceUnavailable("replica shedding",
                                         retry_after_s=st["retry_after"])
            self.served.append(body.get("prompt"))
            trace_id = _trace_of(ctx.request.traceparent)
            if trace_id:
                t = time.time()
                rid = len(self.served)
                hops = []
                for i, hop in enumerate(("queue", "prefill", "decode",
                                         "finish")):
                    hops.append({"hop": hop, "actor": "engine:serve",
                                 "t_start": t + i * 0.001,
                                 "t_end": t + (i + 1) * 0.001,
                                 "duration_s": 0.001, "request_id": rid})
                self.journeys[trace_id] = {
                    "trace_id": trace_id, "source": "replica",
                    "hops": hops,
                    "requests": [{"id": rid, "trace_id": trace_id}]}
            die_after = st["die_after"]
            n = self.tokens

            def chunks():
                for i in range(n):
                    if die_after is not None and i >= die_after:
                        raise RuntimeError("stub replica died mid-stream")
                    yield {"text": f"{self.name}-t{i}"}
                yield {"done": True, "tokens": n}

            return Stream(chunks(), sse=True)

        @app.get("/stats")
        def stats(ctx):  # noqa: ARG001
            return {"queue_depth": st["queue_depth"], "active_slots": 0}

        @app.get("/debug/slo")
        def slo(ctx):  # noqa: ARG001
            return {"slos": {"ttft": {
                "state": "ok",
                "windows": {"fast": {"burn_rate": 0.1},
                            "slow": {"burn_rate": 0.1}}}}}

        @app.get("/debug/journey/{id}")
        def journey(ctx):
            raw = ctx.request.path_param("id")
            payload = self.journeys.get(raw)
            if payload is None:
                raise HTTPError(f"no journey for {raw!r}", status_code=404)
            return payload

        self.app = app

    def start(self):
        self.app.start()
        self.url = f"http://127.0.0.1:{self.app.http_port}"
        return self

    def stop(self):
        self.app.shutdown()


class Harness:
    """N stub replicas behind a REAL examples/router app."""

    def __init__(self, n=2, **cfg):
        self.replicas = [StubReplica(f"r{i}").start() for i in range(n)]
        values = {
            "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "router",
            "REQUEST_TIMEOUT": "30", "LOG_LEVEL": "ERROR",
            "FLEET_REPLICAS": ",".join(f"{r.name}={r.url}"
                                       for r in self.replicas),
            "FLEET_PROBE_S": "0.2", "FLEET_AFFINITY_BLOCK": "8",
            "FLEET_BREAKER_INTERVAL_S": "0.3", "FLEET_RETRY_BUDGET": "2",
            "INCIDENT_DIR": os.path.join(
                os.environ.get("TMPDIR", "/tmp"), "journey_incidents"),
        }
        values.update({k: str(v) for k, v in cfg.items()})
        self.app = _load("router", "journey_router").build_app(
            config=MockConfig(values))
        self.app.start()
        self.port = self.app.http_port

    def replica(self, name):
        return next(r for r in self.replicas if r.name == name)

    def generate(self, prompt, headers=None, timeout=10):
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}/generate",
            data=json.dumps({"prompt": prompt, "stream": True}).encode(),
            headers={"Content-Type": "application/json", **(headers or {})},
            method="POST")
        events = []
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status = resp.status
                for line in resp:
                    line = line.strip()
                    if line.startswith(b"data: "):
                        events.append(json.loads(line[6:]))
        except urllib.error.HTTPError as err:
            return err.code, json.loads(err.read().decode() or "null")
        return status, events

    def journey_index(self):
        return _get_json(f"http://127.0.0.1:{self.port}/debug/journey")

    def journey(self, raw_id):
        return _get_json(
            f"http://127.0.0.1:{self.port}/debug/journey/{raw_id}")

    def close(self):
        self.app.shutdown()
        for r in self.replicas:
            r.stop()


@pytest.fixture()
def fleet():
    harnesses = []

    def build(n=2, **cfg):
        h = Harness(n=n, **cfg)
        harnesses.append(h)
        return h

    yield build
    for h in harnesses:
        h.close()


def _wait_finished(h, n, timeout=5.0):
    """The router finishes a journey AFTER the client drains the stream
    (the pass-through generator's close hook) — poll the index until the
    count lands instead of racing it."""
    deadline = time.monotonic() + timeout
    while True:
        index = h.journey_index()
        if index["finished_total"] >= n:
            return index
        assert time.monotonic() < deadline, (
            f"journey index stuck at {index['finished_total']}/{n}")
        time.sleep(0.02)


def _assert_causal(hops):
    """Hops are ordered: t_start non-decreasing, ties in pipeline rank."""
    starts = [h["t_start"] for h in hops]
    assert starts == sorted(starts)
    assert hops == order_hops(hops)


# -- journey assembly through the real router ---------------------------------
def test_journey_assembly_e2e(fleet):
    h = fleet(n=2)
    status, events = h.generate("assembly prompt one")
    assert status == 200 and events[-1].get("done") is True
    index = _wait_finished(h, 1)
    row = index["recent"][0]
    assert row["outcome"] == "ok"
    assert is_trace_id(row["trace_id"])
    assert row["chunks"] >= 1 and row["ttfb_s"] >= 0.0

    assembled = h.journey(row["id"])
    assert assembled["complete"] is True and assembled["missing"] == []
    assert assembled["trace_id"] == row["trace_id"]
    # one waterfall: the router's route/stream/finish hops + the served
    # replica's queue/prefill/decode/finish hops, causally ordered
    names = [hop["hop"] for hop in assembled["hops"]]
    for hop in ("route", "queue", "prefill", "decode", "stream", "finish"):
        assert hop in names, f"missing {hop} in {names}"
    _assert_causal(assembled["hops"])
    served = row["replica"]
    replica_actors = {hop["actor"] for hop in assembled["hops"]
                      if hop["actor"] != "router"}
    assert replica_actors == {f"{served}:engine:serve"}
    # the replica's records all share the journey's trace id
    for rec in assembled["replicas"][served]["requests"]:
        assert rec["trace_id"] == assembled["trace_id"]
    # trace-id lookup answers the same journey on the same path
    by_trace = h.journey(row["trace_id"])
    assert by_trace["journey_id"] == assembled["journey_id"]


def test_retry_after_failover_shows_both_attempts(fleet):
    h = fleet(n=2, FLEET_POLICY="round_robin")
    shedder = h.replicas[0]
    shedder.state["shed"] = True
    # round-robin lands on the shedder first; the journey must show the
    # shed attempt AND the committed retry as ordered route hops
    for i in range(2):
        status, events = h.generate(f"failover prompt {i}")
        assert status == 200 and events[-1].get("done") is True
    index = _wait_finished(h, 2)
    retried = [r for r in index["recent"]
               if len(r["attempts"]) >= 2 and r["outcome"] == "ok"]
    assert retried, f"no retried journey in {index['recent']}"
    row = retried[0]
    outcomes = [a["outcome"] for a in row["attempts"]]
    assert outcomes[0] == "shed" and outcomes[-1] == "committed"
    assert row["attempts"][0]["replica"] != row["attempts"][-1]["replica"]

    assembled = h.journey(row["id"])
    assert assembled["complete"] is True
    route_hops = [hop for hop in assembled["hops"] if hop["hop"] == "route"]
    assert [hop["outcome"] for hop in route_hops] == outcomes
    _assert_causal(assembled["hops"])


def test_midstream_kill_yields_stream_break_terminal_hop(fleet):
    h = fleet(n=1)
    h.replicas[0].state["die_after"] = 1
    status, events = h.generate("doomed stream prompt")
    assert status == 200
    assert any("error" in e for e in events)
    row = _wait_finished(h, 1)["recent"][0]
    assert row["outcome"] == "stream_break"
    assembled = h.journey(row["id"])
    # the ROUTER's terminal hop is the break (the replica's own finish
    # hop lands within the same millisecond — global order is a race)
    terminal = [hop for hop in assembled["hops"]
                if hop["actor"] == "router"][-1]
    assert terminal["hop"] == "stream_break"
    assert terminal["outcome"] == "stream_break" and terminal.get("error")
    # the stream hop still shows what made it out before the break
    assert any(hop["hop"] == "stream" for hop in assembled["hops"])
    _assert_causal(assembled["hops"])


def test_unknown_journey_id_is_404(fleet):
    h = fleet(n=1)
    with pytest.raises(urllib.error.HTTPError) as err:
        h.journey("999999")
    assert err.value.code == 404


def test_fleet_slo_rollup_endpoint_e2e(fleet):
    h = fleet(n=2)
    for i in range(3):
        status, events = h.generate(f"slo prompt {i}")
        assert status == 200 and events[-1].get("done") is True
    _wait_finished(h, 3)  # observe_journey fires on the finish hook
    snap = _get_json(f"http://127.0.0.1:{h.port}/debug/fleet/slo")
    assert set(snap["fleet_states"]) == {"ttft", "tpot", "availability"}
    # stubs answer /debug/slo: the rollup merges their states per replica
    assert snap["replicas"]["r0"]["ttft"]["state"] == "ok"
    assert snap["replicas_paging"] == [] and snap["hidden_pages"] == 0
    assert snap["classes"]["unclassified"]["goodput"] == 1.0
    # the router serves the per-replica surface shape too (uniformity)
    own = _get_json(f"http://127.0.0.1:{h.port}/debug/slo")
    assert set(own["slos"]) == {"ttft", "tpot", "availability"}


# -- fleet burn: the hidden-page incident -------------------------------------
class _Incidents:
    def __init__(self):
        self.triggered = []

    def trigger(self, kind, **ctx):
        self.triggered.append((kind, ctx))


def _fleet_slo(states_fn, incidents, clock):
    config = MockConfig({
        "FLEET_SLO_MIN_EVENTS": "1", "FLEET_SLO_PAGE_BURN": "1.0",
        "FLEET_SLO_WARN_BURN": "0.5", "FLEET_SLO_FAST_WINDOW_S": "60",
        "FLEET_SLO_SLOW_WINDOW_S": "60"})
    slo = FleetSLO.from_config(config, incidents=incidents,
                               clock=lambda: clock[0])
    slo._replica_states_fn = states_fn
    return slo


def _broken_journey(recorder):
    rec = recorder.begin(None, "interactive", None)
    recorder.finish(rec, "stream_break", error="upstream died")
    return rec


def test_fleet_burn_page_while_replicas_quiet_triggers_incident():
    clock = [100.0]
    incidents = _Incidents()
    slo = _fleet_slo(lambda: {"r0": {"ttft": "ok", "availability": "ok"}},
                     incidents, clock)
    recorder = JourneyRecorder(capacity=8, slo=slo)
    for _ in range(3):
        clock[0] += 1.0
        _broken_journey(recorder)
    assert slo.hidden_pages >= 1
    kinds = [kind for kind, _ in incidents.triggered]
    assert "fleet_burn_hidden" in kinds
    _, ctx = incidents.triggered[0]
    assert ctx["slo"] == "availability"
    assert ctx["replica_states"]["r0"]["availability"] == "ok"
    # goodput accounting saw the broken journeys
    assert slo.class_goodput()["interactive"]["goodput"] == 0.0
    assert slo.rollup()["hidden_pages"] == slo.hidden_pages


def test_fleet_burn_page_not_hidden_when_a_replica_pages_too():
    clock = [100.0]
    incidents = _Incidents()
    slo = _fleet_slo(lambda: {"r0": {"availability": "page"}},
                     incidents, clock)
    recorder = JourneyRecorder(capacity=8, slo=slo)
    for _ in range(3):
        clock[0] += 1.0
        _broken_journey(recorder)
    assert slo.hidden_pages == 0
    assert incidents.triggered == []


# -- fast units ---------------------------------------------------------------
def test_journey_recorder_finish_is_idempotent():
    recorder = JourneyRecorder(capacity=4)
    rec = recorder.begin("0" * 32, None, None)
    recorder.attempt(rec, "r0", "affinity")
    recorder.committed(rec, "r0", 200)
    recorder.first_chunk(rec)
    recorder.chunk(rec)
    recorder.finish(rec, "stream_break", error="died")
    recorder.finish(rec, "ok")  # the on_close path after a break: no-op
    assert rec.outcome == "stream_break"
    assert recorder.finished_total == 1
    hops = rec.router_hops()
    assert [h["hop"] for h in hops] == ["route", "stream", "stream_break"]
    # ring bound holds
    for i in range(8):
        extra = recorder.begin(None, None, None)
        recorder.finish(extra, "ok")
    assert len(recorder.snapshot()["recent"]) == 4


def test_hops_from_detail_roles():
    detail = {"id": 7, "enqueued_at": 10.0, "generated": 4,
              "events": [{"event": "admitted", "t": 10.5},
                         {"event": "first_token", "t": 11.0},
                         {"event": "finished", "t": 12.0}]}
    colocated = [h["hop"] for h in hops_from_detail(detail, "engine:serve")]
    assert colocated == ["queue", "prefill", "decode", "finish"]
    prefill_half = [h["hop"] for h in
                    hops_from_detail(detail, "engine:prefill",
                                     role="prefill")]
    assert prefill_half == ["queue", "prefill"]
    # the decode twin's hand-off record starts where prefill's export
    # ends: its pre-admit window IS the kv_handoff hop
    handoff_detail = {"id": 8, "enqueued_at": 11.2, "generated": 4,
                      "handoff": True,
                      "events": [{"event": "admitted", "t": 11.5},
                                 {"event": "finished", "t": 12.0}]}
    handoff = [h["hop"] for h in
               hops_from_detail(handoff_detail, "engine:decode",
                                role="decode")]
    assert handoff == ["kv_handoff", "decode", "finish"]
    # ordering: a disagg pair's hops interleave into pipeline order
    merged = order_hops(
        hops_from_detail(detail, "engine:prefill", role="prefill")
        + hops_from_detail(handoff_detail, "engine:decode", role="decode"))
    ranks = [_HOP_ORDER.index(h["hop"]) for h in merged]
    assert ranks == sorted(ranks)


def test_flightrecorder_lookup_trace():
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    trace = "4bf92f3577b34da6a3ce929d0e0e4736"
    recorder = FlightRecorder(capacity=8)
    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(16,), flight_recorder=recorder)
    eng.start()
    try:
        first = eng.submit([1, 2, 3], max_new_tokens=3,
                           traceparent=f"00-{trace}-00f067aa0ba902b7-01")
        first.result(timeout_s=30)
        other = eng.submit([4, 5, 6], max_new_tokens=3)
        other.result(timeout_s=30)
    finally:
        eng.stop()
    details = recorder.lookup_trace(trace)
    assert [d["id"] for d in details] == [first.id]
    assert details[0]["trace_id"] == trace
    assert recorder.lookup_trace("f" * 32) == []
    assert recorder.lookup_trace("") == []


# -- the real thing: disagg replica behind the router -------------------------
@pytest.mark.slow
def test_disagg_fleet_journey_trace_continuity(fleet):  # noqa: ARG001
    """Router + two REAL llm-server replicas (r0 split DISAGG_MODE=both,
    r1 colocated), round-robin: the assembled waterfall for a request
    served by r0 shows route -> queue -> prefill -> kv_handoff -> decode
    under ONE trace id, r1's shows the colocated pipeline — the uniform
    surface the drill in docs/observability.md walks."""
    llm = _load("llm-server", "journey_llm_server")
    base_cfg = {
        "HTTP_PORT": "0", "METRICS_PORT": "0", "TPU_PLATFORM": "cpu",
        "MODEL_PRESET": "debug", "WARMUP": "false", "MAX_BATCH": "4",
        "MAX_SEQ_LEN": "64", "PREFILL_BUCKETS": "8,16",
        "PAGE_SIZE": "8", "REQUEST_TIMEOUT": "300", "LOG_LEVEL": "ERROR",
        "INCIDENT_AUTOPSY": "false"}
    replicas = []
    for name, extra in (("r0", {"DISAGG_MODE": "both"}), ("r1", {})):
        app = llm.build_app(config=MockConfig(
            dict(base_cfg, APP_NAME=name, **extra)))
        app.start()
        replicas.append(app)
    router = _load("router", "journey_router_real").build_app(
        config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "router",
            "REQUEST_TIMEOUT": "300", "LOG_LEVEL": "ERROR",
            "FLEET_POLICY": "round_robin", "FLEET_PROBE_S": "0.2",
            "FLEET_REPLICAS": ",".join(
                f"r{i}=http://127.0.0.1:{a.http_port}"
                for i, a in enumerate(replicas)),
            "INCIDENT_DIR": os.path.join(
                os.environ.get("TMPDIR", "/tmp"), "journey_incidents")}))
    router.start()
    base = f"http://127.0.0.1:{router.http_port}"
    try:
        waterfalls = {}
        for i in range(8):
            if len(waterfalls) == 2:
                break
            trace = f"{0xabc0 + i:032x}"
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"prompt": f"hop trace {i}",
                                 "max_tokens": 4,
                                 "stream": True}).encode(),
                headers={"Content-Type": "application/json",
                         "traceparent": f"00-{trace}-00f067aa0ba902b7-01"},
                method="POST")
            with urllib.request.urlopen(req, timeout=300) as resp:
                events = [json.loads(line.strip()[6:]) for line in resp
                          if line.strip().startswith(b"data: ")]
            assert events[-1].get("done") is True
            assembled = _get_json(base + f"/debug/journey/{trace}",
                                  timeout=30)
            served = assembled["journey"]["replica"]
            waterfalls.setdefault(served, assembled)
        assert set(waterfalls) == {"r0", "r1"}, (
            f"round-robin never reached {set(waterfalls) ^ {'r0', 'r1'}}")

        for name, assembled in waterfalls.items():
            assert assembled["complete"] is True
            assert is_trace_id(assembled["trace_id"])
            # ONE trace id across every hop source on the waterfall
            for rec in assembled["replicas"][name]["requests"]:
                assert rec["trace_id"] == assembled["trace_id"]
            starts = [h["t_start"] for h in assembled["hops"]]
            assert starts == sorted(starts)

        split = waterfalls["r0"]
        names = [h["hop"] for h in split["hops"]]
        for hop in ("route", "queue", "prefill", "kv_handoff", "decode",
                    "finish"):
            assert hop in names, f"split waterfall missing {hop}: {names}"
        assert (names.index("queue") < names.index("prefill")
                < names.index("kv_handoff") < names.index("decode"))
        actors = {h["actor"] for h in split["hops"]}
        assert "r0:engine:prefill" in actors
        assert any(a.startswith("r0:engine:") and "prefill" not in a
                   for a in actors)

        colocated = waterfalls["r1"]
        names = [h["hop"] for h in colocated["hops"]]
        for hop in ("route", "queue", "prefill", "decode", "finish"):
            assert hop in names
        assert "kv_handoff" not in names

        # the uniform surface: each replica answers the same path itself
        for i, assembled in ((0, split), (1, colocated)):
            local = _get_json(
                f"http://127.0.0.1:{replicas[i].http_port}"
                f"/debug/journey/{assembled['trace_id']}", timeout=30)
            assert local["source"] == "replica"
            assert local["trace_id"] == assembled["trace_id"]
            assert local["hops"]
    finally:
        router.shutdown()
        for app in replicas:
            app.shutdown()
