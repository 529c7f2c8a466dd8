"""Performance timeline: the trace-event contract, flow normalization,
/debug/timeline, and fleet stitching into one multi-process trace.

ISSUE 20's acceptance surface: exported traces honor the Chrome
trace-event contract (monotone timestamps per track, balanced B/E
nesting, flow ids that resolve to well-formed s→t→f chains); each step
slice's segment children reproduce the ledger's sum identity; and a
replica behind the real router stitches into one multi-pid trace whose
cross-process flow chain is unbroken.
"""

import importlib.util
import json
import os
import urllib.request

import pytest

from gofr_tpu.app import App
from gofr_tpu.config import MockConfig
from gofr_tpu.fleet.timeline import (align_replica, router_events,
                                     stitch_payloads)
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.flightrecorder import FlightRecorder
from gofr_tpu.tpu.timeline import (TimelineExporter,
                                   register_timeline_metrics)

pytestmark = pytest.mark.timeline

CFG = LlamaConfig.debug()
EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _engine(**kw):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq_len", 128)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("decode_block_size", 1)
    kw.setdefault("pipeline_depth", 1)
    return PagedLLMEngine(llama_init(CFG, seed=0), CFG, **kw)


# -- the trace-event contract, asserted structurally --------------------------
def _by_track(events):
    tracks = {}
    for ev in events:
        if ev.get("ph") == "M":
            continue
        tracks.setdefault((ev.get("pid"), ev.get("tid")), []).append(ev)
    return tracks


def _assert_contract(events):
    """Every track's duration events are time-ordered with balanced B/E
    nesting; every flow id resolves to one well-formed chain."""
    for key, track in _by_track(events).items():
        depth, last_ts = 0, None
        for ev in track:
            if ev["ph"] not in ("B", "E", "X"):
                continue
            assert isinstance(ev["ts"], (int, float)), ev
            if last_ts is not None:
                assert ev["ts"] >= last_ts - 1e-6, (
                    f"track {key}: ts went backwards at {ev}")
            last_ts = ev["ts"]
            if ev["ph"] == "B":
                depth += 1
            elif ev["ph"] == "E":
                depth -= 1
                assert depth >= 0, f"track {key}: E without B at {ev}"
        assert depth == 0, f"track {key}: {depth} unclosed B slices"
    _assert_flows_well_formed(events)


def _flow_chains(events):
    chains = {}
    for ev in events:
        if ev.get("cat") == "flow":
            chains.setdefault(ev.get("id"), []).append(ev)
    for chain in chains.values():
        chain.sort(key=lambda e: e["ts"])
    return chains


def _assert_flows_well_formed(events):
    for fid, chain in _flow_chains(events).items():
        phases = [ev["ph"] for ev in chain]
        assert phases.count("s") == 1, f"flow {fid}: {phases}"
        assert phases[0] == "s", f"flow {fid} does not start with s"
        assert phases.count("f") <= 1
        finished = [ev for ev in chain
                    if ev.get("args", {}).get("milestone") == "finished"]
        if finished and chain[-1] is finished[-1]:
            assert phases[-1] == "f", f"flow {fid}: {phases}"
            assert chain[-1].get("bp") == "e"
        for ev in chain[1:-1]:
            assert ev["ph"] == "t", f"flow {fid}: {phases}"


# -- unit: flow normalization over raw event soup -----------------------------
def test_normalize_flows_rewrites_raw_chains():
    """A hand-off pair (or a stitched router+replica merge) contributes
    several raw s/f under one id; normalization leaves exactly one s,
    one f (terminal finished), t between."""
    def flow(ph, ts, milestone, **extra):
        ev = {"ph": ph, "cat": "flow", "id": "abc", "ts": ts,
              "args": {"milestone": milestone}}
        ev.update(extra)
        return ev

    events = [flow("f", 30.0, "finished", bp="e"),
              flow("s", 10.0, "enqueued"),
              flow("s", 18.0, "enqueued"),      # the decode half's raw s
              flow("t", 15.0, "admitted"),
              flow("f", 25.0, "finished", bp="e"),  # prefill half's raw f
              {"ph": "X", "name": "bystander", "ts": 1.0, "dur": 2.0}]
    TimelineExporter._normalize_flows(events)
    _assert_flows_well_formed(events)
    chain = _flow_chains(events)["abc"]
    assert [ev["ph"] for ev in chain] == ["s", "t", "t", "t", "f"]
    assert chain[-1]["ts"] == 30.0 and chain[-1]["bp"] == "e"
    assert events[-1]["ph"] == "X"  # non-flow events untouched


def test_normalize_flows_without_terminal_keeps_last_as_t():
    events = [{"ph": "s", "cat": "flow", "id": "x", "ts": 1.0,
               "args": {"milestone": "enqueued"}},
              {"ph": "f", "cat": "flow", "id": "x", "ts": 2.0, "bp": "e",
               "args": {"milestone": "admitted"}}]
    TimelineExporter._normalize_flows(events)
    # an in-flight request never gets a bogus f: the chain stays open
    assert [ev["ph"] for ev in events] == ["s", "t"]
    assert "bp" not in events[1]


# -- engine-driven export -----------------------------------------------------
def test_export_contract_and_segment_sum_identity():
    """The acceptance identity on a real run: every step slice's segment
    children tile it, reproducing the ledger's segments==wall sum."""
    recorder = FlightRecorder(capacity=32)
    eng = _engine(flight_recorder=recorder)
    exporter = TimelineExporter(eng, process_name="unit")
    eng.start()
    try:
        request = eng.submit([1, 2, 3], max_new_tokens=12)
        assert len(request.result(timeout_s=120)) == 12
    finally:
        eng.stop()
    payload = exporter.export()
    events = payload["traceEvents"]
    assert payload["events_total"] == len(events) > 0
    assert payload["anchor"]["wall0"] > 0
    assert payload["anchor"]["mono0"] > 0
    assert payload["clock_domain"] == "monotonic_us"
    _assert_contract(events)
    # track metadata: the real thread names, the ownership contract
    names = {ev["args"]["name"] for ev in events
             if ev.get("ph") == "M" and ev["name"] == "thread_name"}
    assert "llm-engine" in names and "llm-finisher" in names
    loop_meta = next(ev for ev in events
                     if ev.get("ph") == "M"
                     and ev.get("args", {}).get("name") == "llm-engine")
    assert loop_meta["args"]["loop_only"], "ownership contract missing"
    # the sum identity, read back from the rendered slices
    steps = [ev for ev in events if ev.get("cat") == "step"
             and ev["ph"] == "B"]
    assert steps, "no step slices rendered"
    segments = [ev for ev in events if ev.get("cat") == "segment"
                and ev["ph"] == "B"]
    by_ts = {}
    for seg in segments:
        by_ts.setdefault(seg["tid"], []).append(seg)
    for step in steps:
        children = [seg for seg in by_ts.get(step["tid"], [])
                    if step["ts"] <= seg["ts"]
                    < step["ts"] + step["args"]["wall_s"] * 1e6]
        total = sum(seg["args"]["seconds"] for seg in children)
        assert total == pytest.approx(step["args"]["wall_s"],
                                      rel=0.05, abs=1e-4), step
    # device busy intervals rendered as async pairs
    assert any(ev.get("cat") == "device" and ev["ph"] == "b"
               for ev in events)
    # the finished request's flow chain resolved s→…→f
    chains = _flow_chains(events)
    assert chains, "no request flow events"
    done = [c for c in chains.values()
            if c[-1].get("args", {}).get("milestone") == "finished"]
    assert done, "finished request produced no terminal flow event"
    # export counter rode along
    assert exporter.exports_total == 1


def test_export_steps_window_narrows_and_is_safe_reentrant():
    eng = _engine()
    exporter = TimelineExporter(eng, max_steps=4)
    eng.start()
    try:
        eng.generate([1, 2, 3], max_new_tokens=10)
    finally:
        eng.stop()
    wide = exporter.export(steps=128)
    narrow = exporter.export(steps=2)
    assert narrow["steps_window"] == 2
    n_steps = len([ev for ev in narrow["traceEvents"]
                   if ev.get("cat") == "step" and ev["ph"] == "B"])
    w_steps = len([ev for ev in wide["traceEvents"]
                   if ev.get("cat") == "step" and ev["ph"] == "B"])
    assert n_steps <= 2 < w_steps
    assert exporter.exports_total == 2


def test_compile_hook_chains_and_captures():
    eng = _engine()
    seen = []
    eng.executor.on_compile = lambda name, s: seen.append((name, s))
    exporter = TimelineExporter(eng)
    exporter.note_compile("prefill_16", 0.25)
    eng.executor.on_compile("decode_1", 0.125)  # through the chained hook
    payload = exporter.export()
    compiles = [ev for ev in payload["traceEvents"]
                if ev.get("cat") == "compile"]
    names = {ev["name"] for ev in compiles}
    assert "compile:prefill_16" in names and "compile:decode_1" in names
    for ev in compiles:
        assert ev["ph"] == "X" and ev["dur"] > 0
    assert seen == [("decode_1", 0.125)], "prior hook lost by chaining"


# -- /debug/timeline over HTTP ------------------------------------------------
def test_debug_timeline_route_e2e():
    app = App(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "LOG_LEVEL": "ERROR",
        "TIMELINE_STEPS": "64"}))
    eng = _engine()
    exporter = app.enable_timeline(eng)
    assert exporter is eng.timeline
    assert exporter.max_steps == 64
    prof = app.enable_hostprof(eng)
    assert prof is eng.hostprof and prof.running
    eng.start()
    app.start()
    try:
        eng.generate([1, 2, 3], max_new_tokens=8)
        base = f"http://127.0.0.1:{app.http_port}"
        with urllib.request.urlopen(base + "/debug/timeline?steps=8",
                                    timeout=30) as resp:
            payload = json.loads(resp.read().decode())["data"]
        assert payload["steps_window"] == 8
        assert payload["traceEvents"]
        _assert_contract(payload["traceEvents"])
        with urllib.request.urlopen(base + "/debug/hostprof",
                                    timeout=30) as resp:
            snap = json.loads(resp.read().decode())["data"]
        assert snap["running"] is True and snap["samples_total"] >= 0
        with urllib.request.urlopen(base + "/debug/hostprof?collapsed=1",
                                    timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
    finally:
        eng.stop()
        app.shutdown()
    assert not prof.running, "shutdown hook did not stop the sampler"


def test_hostprof_disabled_by_nonpositive_hz():
    app = App(config=MockConfig({"HTTP_PORT": "0", "METRICS_PORT": "0",
                                 "HOSTPROF_HZ": "0",
                                 "LOG_LEVEL": "ERROR"}))
    assert app.enable_hostprof() is None


def test_register_timeline_metrics_idempotent():
    from gofr_tpu.metrics import Manager

    m = Manager()
    register_timeline_metrics(m)
    register_timeline_metrics(m)
    assert m.get("app_tpu_timeline_exports_total") is not None


# -- fleet stitching: the pure core -------------------------------------------
def _replica_payload(trace_id, wall0=1000.0, mono0=100.0):
    """A minimal well-formed /debug/timeline payload: one step slice and
    a full request flow, monotonic-µs domain with the anchor pair."""
    def ev(ph, ts_mono, **extra):
        base = {"ph": ph, "pid": 1, "tid": 1, "ts": ts_mono * 1e6}
        base.update(extra)
        return base

    return {
        "anchor": {"wall0": wall0, "mono0": mono0},
        "traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 1, "tid": 0,
             "ts": 0, "args": {"name": "llm-server"}},
            ev("B", 100.5, name="step:decode", cat="step",
               args={"wall_s": 0.1}),
            ev("E", 100.6),
            ev("s", 100.45, cat="flow", id=trace_id, name="request",
               args={"milestone": "enqueued"}),
            ev("t", 100.5, cat="flow", id=trace_id, name="request",
               args={"milestone": "admitted"}),
            ev("f", 100.62, cat="flow", id=trace_id, name="request",
               bp="e", args={"milestone": "finished"}),
        ],
    }


def _journey(trace_id):
    summary = {"id": 7, "trace_id": trace_id, "outcome": "ok"}
    hops = [
        {"hop": "route", "actor": "router", "t_start": 1000.40,
         "t_end": 1000.41, "replica": "r0", "outcome": "committed"},
        {"hop": "stream", "actor": "router", "t_start": 1000.45,
         "t_end": 1000.70, "chunks": 3},
        {"hop": "finish", "actor": "router", "t_start": 1000.70,
         "t_end": 1000.70, "outcome": "ok"},
    ]
    return summary, hops


def test_stitch_aligns_clocks_and_joins_flows_across_pids():
    trace_id = "ab" * 16
    summary, hops = _journey(trace_id)
    stitched = stitch_payloads({"r0": _replica_payload(trace_id)},
                               journey=summary, hops=hops,
                               trace_id=trace_id)
    assert stitched["complete"] is True and stitched["missing"] == []
    assert stitched["pids"] == {"r0": 2}
    assert stitched["clock_domain"] == "wall_us"
    events = stitched["traceEvents"]
    _assert_contract(events)
    # the replica's monotonic events landed in the wall epoch: mono
    # 100.5s + (wall0-mono0)=900s shift -> wall 1000.5s
    step = next(ev for ev in events if ev.get("cat") == "step")
    assert step["pid"] == 2
    assert step["ts"] == pytest.approx(1000.5e6, abs=1e3)
    # process metadata renamed to the replica, ts untouched
    meta = next(ev for ev in events if ev.get("ph") == "M"
                and ev["pid"] == 2 and ev["name"] == "process_name")
    assert meta["args"]["name"] == "r0" and meta["ts"] == 0
    # ONE unbroken flow chain across both processes
    chain = _flow_chains(events)[trace_id]
    assert {ev["pid"] for ev in chain} == {1, 2}
    phases = [ev["ph"] for ev in chain]
    assert phases[0] == "s" and phases[-1] == "f"
    assert phases.count("s") == 1 and phases.count("f") == 1
    # the router's route attempt precedes the replica's enqueue: the
    # chain ORIGINATES at the router after the wall alignment
    assert chain[0]["pid"] == 1


def test_stitch_degrades_anchorless_replica_to_missing():
    trace_id = "cd" * 16
    summary, hops = _journey(trace_id)
    bad = _replica_payload(trace_id)
    del bad["anchor"]
    stitched = stitch_payloads(
        {"r0": _replica_payload(trace_id), "r1": bad},
        journey=summary, hops=hops, trace_id=trace_id)
    assert stitched["missing"] == ["r1"]
    assert stitched["complete"] is False
    assert stitched["pids"] == {"r0": 2}
    assert all(ev["pid"] != 3 for ev in stitched["traceEvents"])


def test_align_replica_requires_the_anchor_pair():
    events, ok = align_replica({"traceEvents": [{"ph": "X", "ts": 1}]},
                               pid=5, name="r9")
    assert ok is False and events == []


def test_router_events_mark_terminal_hop_finished():
    summary, hops = _journey("ef" * 16)
    events = router_events(summary, hops)
    flows = [ev for ev in events if ev.get("cat") == "flow"]
    milestones = [ev["args"]["milestone"] for ev in flows]
    assert milestones == ["route", "finished"]
    slices = [ev for ev in events if ev["ph"] == "X"]
    assert [ev["name"] for ev in slices] == ["route", "stream", "finish"]


# -- acceptance e2e: a real replica behind the real router --------------------
def _load(example, alias):
    path = os.path.join(EXAMPLES, example, "main.py")
    spec = importlib.util.spec_from_file_location(alias, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.slow  # two real engines + router; the timeline lane runs it
def test_fleet_timeline_stitches_disagg_replica_e2e():
    """DISAGG_MODE=both replica behind the real router: one request's
    stitched trace is multi-pid (router + replica), the replica's two
    engine halves render their own track blocks, and the cross-process
    flow chain for the journey's trace id is unbroken."""
    llm = _load("llm-server", "timeline_llm_server")
    replica = llm.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "TPU_PLATFORM": "cpu",
        "MODEL_PRESET": "debug", "WARMUP": "false", "MAX_BATCH": "4",
        "MAX_SEQ_LEN": "64", "PREFILL_BUCKETS": "8,16",
        "PAGE_SIZE": "8", "REQUEST_TIMEOUT": "300", "LOG_LEVEL": "ERROR",
        "INCIDENT_AUTOPSY": "false", "DISAGG_MODE": "both",
        "APP_NAME": "r0"}))
    replica.start()
    router = _load("router", "timeline_router").build_app(
        config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "router",
            "REQUEST_TIMEOUT": "300", "LOG_LEVEL": "ERROR",
            "FLEET_PROBE_S": "0.2",
            "FLEET_REPLICAS": f"r0=http://127.0.0.1:{replica.http_port}",
            "INCIDENT_DIR": os.path.join(
                os.environ.get("TMPDIR", "/tmp"), "timeline_incidents")}))
    router.start()
    base = f"http://127.0.0.1:{router.http_port}"
    trace = f"{0xfaded:032x}"
    try:
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": "stitch me", "max_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": f"00-{trace}-00f067aa0ba902b7-01"},
            method="POST")
        with urllib.request.urlopen(req, timeout=300) as resp:
            events = [json.loads(line.strip()[6:]) for line in resp
                      if line.strip().startswith(b"data: ")]
        assert events[-1].get("done") is True

        with urllib.request.urlopen(
                base + f"/debug/fleet/timeline/{trace}",
                timeout=60) as resp:
            stitched = json.loads(resp.read().decode())["data"]
        assert stitched["complete"] is True, stitched["missing"]
        assert stitched["trace_id"] == trace
        assert stitched["pids"] == {"r0": 2}
        trace_events = stitched["traceEvents"]
        _assert_contract(trace_events)
        pids = {ev["pid"] for ev in trace_events}
        assert pids == {1, 2}, f"not multi-process: {pids}"
        # the DISAGG both replica rendered both engine halves' tracks
        names = {ev["args"]["name"] for ev in trace_events
                 if ev.get("ph") == "M" and ev["name"] == "thread_name"}
        assert any(n.startswith("prefill:") for n in names), names
        # the journey's flow chain crosses the process boundary unbroken
        chain = _flow_chains(trace_events).get(trace)
        assert chain, "no flow events for the journey's trace id"
        phases = [ev["ph"] for ev in chain]
        assert phases[0] == "s" and phases.count("s") == 1
        assert phases[-1] == "f" and phases.count("f") == 1
        assert all(ph == "t" for ph in phases[1:-1])
        assert {ev["pid"] for ev in chain} == {1, 2}

        # unknown id is a clean 404, not a stitch of nothing
        try:
            urllib.request.urlopen(base + "/debug/fleet/timeline/999999",
                                   timeout=30)
            raise AssertionError("unknown journey id did not 404")
        except urllib.error.HTTPError as err:
            assert err.code == 404
    finally:
        router.shutdown()
        replica.shutdown()
