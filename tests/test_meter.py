"""Capacity observatory (tpu/meter.py + fleet/capacity.py): attribution
conservation, exact tenant accounting, the λ/μ/ρ forecaster and the
collapse detector, and the fleet rollup's replicas_needed contract.

The load-bearing acceptance tests live here:
  * conservation over a LIVE multi-tenant engine run — per-step
    attributed device-seconds sum to the step ledger's measured device
    segments (±5 %), and tenant totals equal the per-request sums;
  * `GET /debug/fleet/capacity` end-to-end over 2 replicas behind the
    real examples/router app, including `replicas_needed`.
"""

import importlib.util
import json
import math
import os
import types
import urllib.request

import pytest

from gofr_tpu import App
from gofr_tpu.config import MockConfig
from gofr_tpu.fleet.capacity import FleetCapacity
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.meter import (HeadroomForecaster, TPUMeter,
                                register_meter_metrics)
from gofr_tpu.tpu.qos import _MAX_TENANTS, _TENANT_OVERFLOW
from gofr_tpu.tpu.utilization import prefill_flops

pytestmark = pytest.mark.capacity

CFG = LlamaConfig.debug()
EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


class MockLogger:
    def debugf(self, *a, **k):
        pass
    infof = warnf = errorf = fatalf = logf = debugf


def _req(rid, tenant="t0", cls="standard", prompt=8, max_new=4):
    return types.SimpleNamespace(id=rid, tenant=tenant, qos_class=cls,
                                 prompt_tokens=list(range(1, prompt + 1)),
                                 max_new_tokens=max_new, emitted=[])


def _rec(device_sync=0.06, dispatch=0.02, seq=1, wall=0.1):
    return types.SimpleNamespace(
        segments={"device_sync": device_sync, "dispatch": dispatch},
        wall_s=wall, seq=seq)


# -- units: token-weighted apportionment --------------------------------------

def test_token_weighted_apportionment_conserves_per_step():
    meter = TPUMeter(cfg=None)
    ra, rb = _req(1, tenant="a"), _req(2, tenant="b")
    meter.account_step(_rec(0.06, 0.02), "prefill",
                       [(ra, 30, 30), (rb, 10, 10)])
    snap = meter.snapshot()
    # weights 30/40 and 10/40 over the 0.08 s of device segments
    by_tenant = {row["tenant"]: row for row in snap["accounts"]}
    assert by_tenant["a"]["device_s"] == pytest.approx(0.06)
    assert by_tenant["b"]["device_s"] == pytest.approx(0.02)
    # conservation evidence: attributed == measured for the step
    step = snap["steps"][-1]
    assert step["attributed_s"] == pytest.approx(step["device_s"])
    assert step["device_s"] == pytest.approx(0.08)
    assert snap["totals"]["device_s"] == pytest.approx(0.08)


def test_wall_clock_fallback_without_segments():
    meter = TPUMeter(cfg=None)
    rec = types.SimpleNamespace(segments={}, wall_s=0.05, seq=7)
    meter.account_step(rec, "decode", [(_req(1), 4, 16)])
    assert meter.snapshot()["totals"]["device_s"] == pytest.approx(0.05)


def test_analytic_flops_per_row():
    meter = TPUMeter(cfg=CFG)
    ra, rb = _req(1, tenant="a"), _req(2, tenant="b")
    meter.account_step(_rec(), "prefill", [(ra, 8, 8), (rb, 16, 16)])
    by_tenant = {row["tenant"]: row for row in meter.snapshot()["accounts"]}
    assert by_tenant["a"]["flops"] == pytest.approx(prefill_flops(CFG, 8))
    assert by_tenant["b"]["flops"] == pytest.approx(prefill_flops(CFG, 16))


def test_page_seconds_accrue_between_metered_syncs(monkeypatch):
    now = [100.0]
    monkeypatch.setattr("gofr_tpu.tpu.meter.time.monotonic",
                        lambda: now[0])
    meter = TPUMeter(cfg=None, page_tokens=16)
    r = _req(1, tenant="a")
    meter.account_step(_rec(), "prefill", [(r, 8, 8)])   # first sight: 0
    now[0] = 101.0
    meter.account_step(_rec(), "decode", [(r, 4, 32)])   # 2 pages x 1 s
    row = meter.snapshot()["accounts"][0]
    assert row["page_s"] == pytest.approx(2.0)


def test_queue_wait_charged_at_first_service_only():
    meter = TPUMeter(cfg=None)
    r = _req(1, tenant="a")
    meter.account_step(_rec(), "prefill", [(r, 8, 8)], queued=[(r, 0.25)])
    meter.account_step(_rec(), "decode", [(r, 4, 12)])  # no queued rows
    row = meter.snapshot()["accounts"][0]
    assert row["queue_s"] == pytest.approx(0.25)


def test_tenant_table_bounded_with_overflow_pool():
    meter = TPUMeter(cfg=None)
    for i in range(_MAX_TENANTS + 8):
        meter.account_step(_rec(seq=i), "prefill",
                           [(_req(i, tenant=f"tenant{i}"), 8, 8)])
    tenants = {row["tenant"] for row in meter.snapshot()["accounts"]}
    assert _TENANT_OVERFLOW in tenants
    # bounded: _MAX_TENANTS named labels + the overflow pool
    assert len(tenants) == _MAX_TENANTS + 1


def test_snapshot_top_k_and_finished_fold():
    meter = TPUMeter(cfg=None, top_k=2)
    reqs = [_req(i, tenant=f"t{i}") for i in range(4)]
    for i, r in enumerate(reqs):
        meter.account_step(_rec(0.01 * (i + 1), 0.0, seq=i), "prefill",
                           [(r, 8, 8)])
        meter.note_finished(r, ok=True)
    snap = meter.snapshot()
    assert len(snap["tenants"]) == 2          # top-K only
    assert snap["tenants"][0]["tenant"] == "t3"  # sorted by device_s
    assert snap["requests_total"] == 4
    assert all(row["finished"] == 1 for row in snap["accounts"])


def test_register_meter_metrics_idempotent():
    from gofr_tpu.metrics import Manager
    manager = Manager()
    register_meter_metrics(manager)
    register_meter_metrics(manager)
    assert manager.get("app_tpu_meter_device_seconds_total") is not None
    assert manager.get("app_tpu_capacity_rho") is not None


# -- units: the forecaster ----------------------------------------------------

def _stub_engine(busy_s=6.0, prefill_toks=4000, decode_toks=8000, depth=0):
    util = types.SimpleNamespace(window_stats=lambda now=None: {
        "device_busy_s": busy_s,
        "tokens": {"prefill": prefill_toks, "decode": decode_toks}})
    return types.SimpleNamespace(util=util, queue_depth=lambda: depth)


def test_forecaster_lambda_mu_rho_headroom(monkeypatch):
    now = [1000.0]
    monkeypatch.setattr("gofr_tpu.tpu.meter.time.monotonic",
                        lambda: now[0])
    fc = HeadroomForecaster(engine=_stub_engine(depth=10), window_s=60.0)
    for _ in range(4):
        fc.note_arrival(400, 100)
    now[0] = 1002.0
    out = fc.evaluate(now[0])
    # span 2 s: lambda 2 req/s, 1000 tok/s; mu 12000 tok / 6 s = 2000
    assert out["lambda_rps"] == pytest.approx(2.0)
    assert out["lambda_tok_s"] == pytest.approx(1000.0)
    assert out["mu_tok_s"] == pytest.approx(2000.0)
    assert out["rho"] == pytest.approx(0.5)
    assert out["headroom_tok_s"] == pytest.approx(1000.0)
    # no traffic observed yet: backlog uses the default prompt estimate
    assert out["backlog_tokens"] == pytest.approx(10 * 128)
    assert out["predicted_ttft_ms"] == pytest.approx(1280 / 2000 * 1e3)
    # once completions teach the EWMAs, the backlog re-estimates
    fc.note_finished(400, 100)
    fc.note_prefill(0.08)
    out = fc.evaluate(now[0])
    assert out["backlog_tokens"] == pytest.approx(10 * 400)
    assert out["predicted_ttft_ms"] == pytest.approx(
        (0.08 + 4000 / 2000.0) * 1e3)


def test_forecaster_decays_when_idle(monkeypatch):
    now = [1000.0]
    monkeypatch.setattr("gofr_tpu.tpu.meter.time.monotonic",
                        lambda: now[0])
    fc = HeadroomForecaster(engine=_stub_engine(), window_s=10.0)
    fc.note_arrival(100, 10)
    assert fc.evaluate(1001.0)["arrivals"] == 1
    # the arrival window drains: lambda -> 0, rho -> 0
    out = fc.evaluate(1020.0)
    assert out["arrivals"] == 0
    assert out["lambda_tok_s"] == 0.0
    assert out["rho"] == 0.0


def test_collapse_detector_needs_rising_depth_and_high_rho():
    fc = HeadroomForecaster(engine=None, rho_warn=0.85, collapse_evals=3)
    assert fc._eval_collapse(1000.0, 1, 0.95) is False
    assert fc._eval_collapse(1000.3, 2, 0.95) is False
    assert fc._eval_collapse(1000.6, 3, 0.95) is True   # 1<2<3 at rho .95
    assert fc.collapse_events == 1
    assert fc._eval_collapse(1000.9, 3, 0.95) is False  # plateau clears it
    # rising depth alone is NOT collapse while headroom remains
    fc2 = HeadroomForecaster(engine=None, rho_warn=0.85, collapse_evals=3)
    fc2._eval_collapse(1000.0, 1, 0.2)
    fc2._eval_collapse(1000.3, 2, 0.2)
    assert fc2._eval_collapse(1000.6, 3, 0.2) is False
    assert fc2.collapse_events == 0


# -- live engine: the conservation acceptance ---------------------------------

def test_conservation_live_multi_tenant_engine():
    """Per-step attributed device-seconds sum to the step ledger's
    measured device segments (±5 % over the run), and tenant totals
    equal the per-request sums exactly — over a REAL multi-tenant run."""
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=64,
                         prefill_buckets=(8, 16), logger=MockLogger())
    meter = TPUMeter(cfg=CFG, steps_capacity=8192, done_capacity=256)
    meter.forecaster = HeadroomForecaster(engine=eng)
    eng.start()
    try:
        eng.warmup()
        # meter attached post-warmup: only real traffic is attributed
        eng.meter = meter
        reqs = []
        for i in range(12):
            reqs.append(eng.submit(
                [1 + (i % 5), 2, 3, 4 + (i % 3)], max_new_tokens=6,
                qos_class=("interactive", "standard", "batch")[i % 3],
                tenant=f"tenant{i % 4}"))
        for r in reqs:
            r.result(timeout_s=300)
    finally:
        eng.stop()

    steps = list(meter._steps)
    assert steps, "no metered steps over a 12-request run"
    total_attr = sum(s["attributed_s"] for s in steps)
    total_meas = sum(s["device_s"] for s in steps)
    assert total_meas > 0
    assert abs(total_attr - total_meas) <= 0.05 * total_meas
    snap = meter.snapshot()
    assert snap["totals"]["device_s"] == pytest.approx(total_attr, abs=1e-4)
    assert snap["requests_total"] == 12
    assert snap["forecast"]["mu_tok_s"] is None or \
        snap["forecast"]["mu_tok_s"] > 0

    # tenant totals == sum of their request accounts (all finished)
    assert not meter._live
    per = {}
    for acct in meter._done:
        key = (acct.tenant, acct.cls)
        per[key] = per.get(key, 0.0) + acct.device_s
    for key, tacct in meter._accounts.items():
        assert tacct.device_s == pytest.approx(per.get(key, 0.0),
                                               abs=1e-9), key
    # every class label the run used shows up in the accounts
    assert {cls for _, cls in meter._accounts} == {
        "interactive", "standard", "batch"}


# -- fleet rollup -------------------------------------------------------------

def _replica_snap(lam, mu, tenants, collapse=False):
    return {
        "forecast": {"lambda_rps": lam / 500.0, "lambda_tok_s": lam,
                     "mu_tok_s": mu, "rho": (lam / mu) if mu else None,
                     "headroom_tok_s": max(0.0, mu - lam),
                     "predicted_ttft_ms": 140.0, "queue_depth": 3,
                     "collapse_warning": collapse},
        "totals": {"device_s": 10.0},
        "tenants": [{"tenant": name, "device_s": d, "flops": d * 1e9,
                     "page_s": d / 2, "queue_s": 0.1, "requests": 2}
                    for name, d in tenants],
    }


def test_fleet_rollup_merges_and_sizes_the_fleet():
    snaps = {
        "r0": _replica_snap(900.0, 1000.0, [("a", 6.0), ("b", 4.0)]),
        "r1": _replica_snap(600.0, 1000.0, [("a", 3.0), ("c", 1.0)],
                            collapse=True),
        "r2": {"error": "connection refused"},
    }
    fc = FleetCapacity(target_rho=0.75,
                       replica_capacity_fn=lambda: snaps)
    out = fc.rollup()
    fleet = out["fleet"]
    assert fleet["lambda_tok_s"] == pytest.approx(1500.0)
    assert fleet["mu_tok_s"] == pytest.approx(2000.0)
    assert fleet["rho"] == pytest.approx(0.75)
    assert fleet["headroom_tok_s"] == pytest.approx(500.0)
    # ceil(1500 / (0.75 * 1000)) = 2 replicas for the offered load
    assert fleet["replicas_needed"] == 2
    assert fleet["replicas_reporting"] == 2
    assert fleet["replicas_total"] == 3
    assert fleet["collapse_warnings"] == ["r1"]
    # per-tenant fleet-wide spend merged and sorted by device_s
    assert [t["tenant"] for t in out["tenants"]] == ["a", "b", "c"]
    assert out["tenants"][0]["device_s"] == pytest.approx(9.0)
    # the dead replica degrades to an error row, not a crash
    assert out["replicas"]["r2"] == {"error": "connection refused"}


def test_fleet_rollup_cold_fleet_recommends_what_it_has():
    snaps = {"r0": {"forecast": {}, "totals": {}, "tenants": []},
             "r1": {"forecast": {}, "totals": {}, "tenants": []}}
    fc = FleetCapacity(replica_capacity_fn=lambda: snaps)
    fleet = fc.rollup()["fleet"]
    assert fleet["mu_tok_s"] is None
    assert fleet["replicas_needed"] == 2   # no mu evidence: keep what's up


def test_replicas_needed_scales_with_offered_load():
    def mk(lam):
        snaps = {"r0": _replica_snap(lam / 2, 1000.0, []),
                 "r1": _replica_snap(lam / 2, 1000.0, [])}
        return FleetCapacity(target_rho=0.75,
                             replica_capacity_fn=lambda: snaps)
    assert mk(600.0).rollup()["fleet"]["replicas_needed"] == 1
    assert mk(1500.0).rollup()["fleet"]["replicas_needed"] == 2
    assert mk(6000.0).rollup()["fleet"]["replicas_needed"] == \
        math.ceil(6000.0 / 750.0)


# -- e2e: /debug/fleet/capacity over 2 replicas behind the real router --------

class _StubCapacityReplica:
    """llm-server-shaped backend serving a canned /debug/capacity — what
    a real replica's TPUMeter would answer."""

    def __init__(self, name, lam, mu):
        self.name = name
        app = App(config=MockConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": name,
            "REQUEST_TIMEOUT": "30", "LOG_LEVEL": "ERROR"}))
        snap = _replica_snap(lam, mu, [("acme", 5.0), ("zeta", 1.0)])

        @app.get("/debug/capacity")
        def capacity(ctx):  # noqa: ARG001
            return snap

        @app.get("/stats")
        def stats(ctx):  # noqa: ARG001
            return {"queue_depth": 0, "active_slots": 0}

        self.app = app

    def start(self):
        self.app.start()
        self.url = f"http://127.0.0.1:{self.app.http_port}"
        return self

    def stop(self):
        self.app.shutdown()


def test_fleet_capacity_endpoint_e2e_two_replicas():
    path = os.path.join(EXAMPLES, "router", "main.py")
    spec = importlib.util.spec_from_file_location("capacity_router", path)
    router_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(router_mod)

    replicas = [_StubCapacityReplica("r0", 900.0, 1000.0).start(),
                _StubCapacityReplica("r1", 600.0, 1000.0).start()]
    app = router_mod.build_app(config=MockConfig({
        "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": "router",
        "REQUEST_TIMEOUT": "30", "LOG_LEVEL": "ERROR",
        "FLEET_REPLICAS": ",".join(f"{r.name}={r.url}" for r in replicas),
        "FLEET_PROBE_S": "0.2", "FLEET_JOURNEY": "false",
        "FLEET_SLO": "false", "CAPACITY_TARGET_RHO": "0.75",
        "INCIDENT_DIR": os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "capacity_incidents"),
    }))
    app.start()
    try:
        url = (f"http://127.0.0.1:{app.http_port}"
               f"/debug/fleet/capacity")
        with urllib.request.urlopen(url, timeout=10) as resp:
            body = json.loads(resp.read().decode())["data"]
        fleet = body["fleet"]
        assert fleet["lambda_tok_s"] == pytest.approx(1500.0)
        assert fleet["mu_tok_s"] == pytest.approx(2000.0)
        assert fleet["rho"] == pytest.approx(0.75)
        assert fleet["replicas_needed"] == 2
        assert fleet["replicas_reporting"] == 2
        assert body["tenants"][0]["tenant"] == "acme"
        assert body["tenants"][0]["device_s"] == pytest.approx(10.0)
        assert set(body["replicas"]) == {"r0", "r1"}
        assert body["replicas"]["r0"]["rho"] == pytest.approx(0.9)
    finally:
        app.shutdown()
        for r in replicas:
            r.stop()
