"""Utilization ledger: MFU/MBU analytics, duty-cycle accounting, the
compile table, /debug/engine, and the metric-inventory consistency gate.

ISSUE 2's acceptance surface: MFU/MBU validated against hand-computed
analytic values for a toy model config; GET /debug/engine returns
slots/buckets/page-pool/compile-table/utilization-window JSON end-to-end;
the new gauges appear in /metrics after a CPU-backend engine run; and
every app_tpu_* name recorded in gofr_tpu/tpu/*.py is registered and
documented.
"""

import json
import os
import urllib.request

import pytest

from gofr_tpu.metrics import Manager
from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.capacity import kv_token_bytes, params_bytes
from gofr_tpu.tpu.utilization import (UtilizationLedger, decode_bytes,
                                      decode_flops, prefill_bytes,
                                      prefill_flops,
                                      register_utilization_metrics,
                                      resolve_peaks)

CFG = LlamaConfig.debug()


def test_analytic_model_hand_computed():
    """The roofline formulas against fully hand-expanded numbers for the
    debug config (vocab=512, dim=64, L=2, H=4, Hkv=2, ffn=128, f32)."""
    # param_count by hand: embeddings 2*512*64, per layer
    # wq 64*64 + wk+wv 2*64*32 + wo 64*64 + mlp 3*64*128 + norms 2*64,
    # final norm 64
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128 + 128
    p_hand = 2 * 512 * 64 + 2 * per_layer + 64
    assert CFG.param_count() == p_hand == 139584

    assert prefill_flops(CFG, 32) == pytest.approx(2.0 * p_hand * 32,
                                                   abs=1e-6)
    assert decode_flops(CFG, rows=2, steps=4) == pytest.approx(
        2.0 * p_hand * 8, abs=1e-6)
    # one cached token: 2 caches * L * Hkv * dh * 4 bytes (f32)
    assert kv_token_bytes(CFG) == 2 * 2 * 2 * 16 * 4 == 512
    assert params_bytes(CFG) == p_hand * 4
    assert prefill_bytes(CFG, 32) == pytest.approx(
        p_hand * 4 + 32 * 512, abs=1e-6)
    # decode: per step one weight read + live KV read + per-row KV write
    assert decode_bytes(CFG, rows=2, steps=4, kv_tokens=70) == pytest.approx(
        4 * (p_hand * 4 + 70 * 512 + 2 * 512), abs=1e-6)


def test_mfu_mbu_window_hand_computed(monkeypatch):
    monkeypatch.setenv("TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("TPU_PEAK_HBM_BW", "1e11")
    metrics = Manager()
    register_utilization_metrics(metrics)
    register_utilization_metrics(metrics)  # idempotent
    ledger = UtilizationLedger(CFG, metrics=metrics, n_devices=1,
                               window_s=60.0, created_at=100.0,
                               platform="cpu")
    ledger.record_prefill(tokens=32, dispatched_at=100.2, synced_at=100.5,
                          sync_wait_s=0.1)
    ledger.record_decode(rows=2, steps=4, kv_tokens=70,
                         dispatched_at=100.6, synced_at=100.9,
                         sync_wait_s=0.05)
    ledger.note_host(0.05, now=100.95)

    stats = ledger.window_stats(now=101.0)
    assert stats["window_s"] == pytest.approx(1.0)
    assert stats["dispatches"] == 2
    # disjoint [100.2, 100.5] + [100.6, 100.9] = 0.6 s busy over 1 s
    assert stats["device_busy_s"] == pytest.approx(0.6, abs=1e-6)
    assert stats["duty_cycle"] == pytest.approx(0.6, abs=1e-6)
    assert stats["host_overhead_s"] == pytest.approx(0.05, abs=1e-6)
    assert stats["sync_wait_s"] == pytest.approx(0.15, abs=1e-6)
    assert stats["tokens"] == {"prefill": 32, "decode": 8}
    # the acceptance bar: ±1e-6 against the hand-expanded analytic values
    assert stats["mfu"]["prefill"] == pytest.approx(
        2.0 * 139584 * 32 / 1e12, abs=1e-6)
    assert stats["mfu"]["decode"] == pytest.approx(
        2.0 * 139584 * 8 / 1e12, abs=1e-6)
    assert stats["mbu"]["prefill"] == pytest.approx(
        (139584 * 4 + 32 * 512) / 1e11, abs=1e-6)
    assert stats["mbu"]["decode"] == pytest.approx(
        4 * (139584 * 4 + 70 * 512 + 2 * 512) / 1e11, abs=1e-6)
    assert stats["peak_source"] == "env"

    ledger.publish(now=101.0)
    text = metrics.expose()
    assert "app_tpu_device_duty_cycle 0.6" in text
    assert 'app_tpu_mfu{phase="prefill"}' in text
    assert 'app_tpu_mbu{phase="decode"}' in text
    assert "app_tpu_host_overhead_seconds 0.05" in text


def test_duty_cycle_unions_pipelined_dispatches():
    """Overlapping in-flight dispatches must not double-count device
    time: [0.0, 0.5] U [0.2, 0.6] is 0.6 s busy, not 0.9."""
    ledger = UtilizationLedger(CFG, window_s=60.0, created_at=100.0,
                               platform="cpu")
    ledger.record_decode(rows=1, steps=1, kv_tokens=4,
                         dispatched_at=100.0, synced_at=100.5)
    ledger.record_decode(rows=1, steps=1, kv_tokens=4,
                         dispatched_at=100.2, synced_at=100.6)
    stats = ledger.window_stats(now=101.0)
    assert stats["device_busy_s"] == pytest.approx(0.6, abs=1e-6)
    # and the window prunes: 60s later both entries are gone
    stats = ledger.window_stats(now=200.0)
    assert stats["dispatches"] == 0
    assert stats["duty_cycle"] == 0.0


def test_peak_table_resolution(monkeypatch):
    monkeypatch.delenv("TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("TPU_PEAK_HBM_BW", raising=False)
    flops, bw, source = resolve_peaks("tpu", "TPU v5 lite")
    assert (flops, bw, source) == (197e12, 819e9, "table")
    flops, bw, source = resolve_peaks("tpu", "TPU v4")
    assert (flops, bw, source) == (275e12, 1228e9, "table")
    flops, bw, source = resolve_peaks("cpu", None)
    assert source == "default"
    monkeypatch.setenv("TPU_PEAK_FLOPS", "5e13")
    flops, bw, source = resolve_peaks("tpu", "TPU v5 lite")
    assert source == "env"
    assert flops == 5e13
    assert bw == 819e9  # unset half falls back to the table


def test_unknown_tpu_kind_is_an_error_not_a_default(monkeypatch):
    monkeypatch.delenv("TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("TPU_PEAK_HBM_BW", raising=False)
    with pytest.raises(ValueError, match="TPU v99"):
        resolve_peaks("tpu", "TPU v99")
    with pytest.raises(ValueError):
        resolve_peaks("tpu", None)
    # a fleet that knows its chip better than the table says both peaks
    monkeypatch.setenv("TPU_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("TPU_PEAK_HBM_BW", "3e12")
    assert resolve_peaks("tpu", "TPU v99") == (1e15, 3e12, "env")


def test_executor_compile_table():
    import jax.numpy as jnp

    from gofr_tpu.tpu.executor import Executor

    ex = Executor()
    x = jnp.ones((4,), dtype=jnp.float32)
    ex.run("double", lambda a: a * 2, x)
    ex.run("double", lambda a: a * 2, x)   # same shapes: in-memory hit
    table = ex.compile_table()
    assert table["distinct_programs"] == 1
    row = table["programs"][0]
    assert row["name"] == "double"
    assert row["variants"] == 1
    assert row["executions"] == 2
    assert row["cache_hits"] == 1
    assert row["compile_seconds"] >= 0.0
    assert table["cache_hits_total"] == 1
    assert table["hit_ratio"] == pytest.approx(0.5)
    assert table["compile_seconds_total"] == pytest.approx(
        row["compile_seconds"], abs=1e-6)


def _engine(**kw):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("decode_block_size", 4)
    eng = PagedLLMEngine(llama_init(CFG, seed=0), CFG, **kw)
    eng.start()
    return eng


def test_engine_run_populates_ledger_and_gauges():
    metrics = Manager()
    register_utilization_metrics(metrics)
    eng = _engine(metrics=metrics)
    try:
        tokens = eng.generate([1, 2, 3], max_new_tokens=6)
        assert len(tokens) == 6
    finally:
        eng.stop()
    stats = eng.util.window_stats()
    # one prefill + at least one decode dispatch reached the ledger
    assert stats["dispatches"] >= 2
    assert stats["tokens"]["prefill"] == 3
    assert stats["tokens"]["decode"] >= 5
    assert 0.0 < stats["duty_cycle"] <= 1.0
    assert stats["mfu"]["decode"] > 0.0
    assert stats["mbu"]["decode"] > 0.0
    text = metrics.expose()
    for needle in ('app_tpu_mfu{phase="decode"}',
                   'app_tpu_mbu{phase="prefill"}',
                   "app_tpu_device_duty_cycle "):
        assert needle in text, f"missing {needle} in exposition"


def test_engine_snapshot_shape():
    from gofr_tpu.tpu.utilization import engine_snapshot

    eng = _engine()
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)
        snap = engine_snapshot(eng)
    finally:
        eng.stop()
    assert snap["engine"]["n_slots"] == 2
    assert snap["engine"]["prefill_buckets"] == [16]
    assert len(snap["slots"]) == 2
    assert snap["utilization"]["dispatches"] >= 1
    assert snap["compile"]["distinct_programs"] >= 2  # prefill + decode
    names = [r["name"] for r in snap["compile"]["programs"]]
    assert any("prefill" in n for n in names)
    assert any("decode" in n for n in names)


EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def _load_llm_server():
    import importlib.util

    path = os.path.join(EXAMPLES, "llm-server", "main.py")
    spec = importlib.util.spec_from_file_location(
        "example_llm_server_utilization", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _get(url):
    with urllib.request.urlopen(url, timeout=30) as resp:
        return resp.status, resp.read().decode()


def test_debug_engine_endpoint_e2e():
    """End-to-end through the example server (paged engine, CPU backend):
    /debug/engine returns the full snapshot and the utilization gauges
    land in the Prometheus exposition."""
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
        status, _ = _get_req(req)
        assert status == 201

        status, body = _get(f"{base}/debug/engine")
        assert status == 200
        snap = json.loads(body)["data"]
        for key in ("engine", "slots", "utilization", "compile",
                    "page_pool"):
            assert key in snap, f"missing {key} in /debug/engine"
        assert snap["engine"]["queue_depth"] == 0
        # prefix-cache-resident pages may remain after the request
        # finished; the ledger must still balance (page 0 is reserved)
        assert (snap["page_pool"]["used"] + snap["page_pool"]["free"]
                == snap["page_pool"]["n_pages"] - 1)
        assert snap["page_pool"]["free"] > 0
        assert snap["utilization"]["dispatches"] >= 1
        assert snap["utilization"]["mfu"]["decode"] > 0.0
        assert snap["compile"]["distinct_programs"] >= 2

        status, text = _get(
            f"http://127.0.0.1:{app.metrics_port}/metrics")
        assert status == 200
        for needle in ('app_tpu_mfu{phase="decode"}',
                       'app_tpu_mbu{phase="decode"}',
                       "app_tpu_device_duty_cycle ",
                       'app_tpu_hbm_bytes{'):
            assert needle in text, f"missing {needle} in /metrics"
    finally:
        app.shutdown()


def _get_req(req):
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, resp.read().decode()


# -- metric-inventory consistency gate ---------------------------------------
# the extraction itself is shared with graftlint's surface pass — one
# scanner, consumed by both the runtime gate here and the static gate


def test_metric_inventory_consistency():
    """Every app_tpu_* metric RECORDED anywhere in gofr_tpu/tpu/*.py must
    be registered by the runtime's registration paths AND listed in
    docs/observability.md — the gate that catches silent drift like PR 1's
    new gauges landing unregistered/undocumented."""
    from tools.analysis.passes.surface import collect_metric_names

    repo = os.path.join(os.path.dirname(__file__), "..")
    recorded = {name for name in collect_metric_names(repo)
                if name.startswith("app_tpu_")}
    assert recorded, "inventory scan found no recorded metrics (scanner rot?)"
    # the step-anatomy names must be IN the scan (guards scanner rot against
    # the stepledger module's recording style)
    assert "app_tpu_step_seconds" in recorded
    assert "app_tpu_step_stragglers_total" in recorded
    # the tiered-KV family must be IN the scan (guards scanner rot against
    # paging.py's spill/restore recording style)
    assert any(n.startswith("app_tpu_kv_tier_") for n in recorded), \
        "kv tier counters vanished from the inventory scan"
    # the disaggregation family must be IN the scan (guards scanner rot
    # against disagg.py's hand-off recording style)
    assert any(n.startswith("app_tpu_disagg_") for n in recorded), \
        "disagg hand-off counters vanished from the inventory scan"
    # the fleet-router family must be IN the scan (guards scanner rot
    # against gofr_tpu/fleet's recording style)
    assert any(n.startswith("app_tpu_fleet_") for n in recorded), \
        "fleet router counters vanished from the inventory scan"
    # the QoS plane family must be IN the scan (guards scanner rot against
    # tpu/qos.py's recording style)
    assert any(n.startswith("app_tpu_qos_") for n in recorded), \
        "qos plane counters vanished from the inventory scan"
    # the capacity observatory families must be IN the scan (guards
    # scanner rot against tpu/meter.py's batched-delta recording style)
    assert any(n.startswith("app_tpu_meter_") for n in recorded), \
        "meter attribution counters vanished from the inventory scan"
    assert any(n.startswith("app_tpu_capacity_") for n in recorded), \
        "capacity forecast gauges vanished from the inventory scan"
    # the performance-timeline families must be IN the scan (guards
    # scanner rot against timeline.py / hostprof.py's MetricsHook style)
    assert any(n.startswith("app_tpu_timeline_") for n in recorded), \
        "timeline export counters vanished from the inventory scan"
    assert any(n.startswith("app_tpu_hostprof_") for n in recorded), \
        "hostprof sampler metrics vanished from the inventory scan"

    from gofr_tpu.fleet import (register_elastic_metrics,
                                register_fleet_capacity_metrics,
                                register_fleet_metrics,
                                register_fleet_slo_metrics,
                                register_journey_metrics)
    from gofr_tpu.fleet.timeline import register_fleet_timeline_metrics
    from gofr_tpu.tpu.device import TPUClient
    from gofr_tpu.tpu.disagg import register_disagg_metrics
    from gofr_tpu.tpu.flightrecorder import register_slo_gauges
    from gofr_tpu.tpu.hostprof import register_hostprof_metrics
    from gofr_tpu.tpu.incidents import register_incident_metrics
    from gofr_tpu.tpu.meter import register_meter_metrics
    from gofr_tpu.tpu.migrate import register_migration_metrics
    from gofr_tpu.tpu.qos import register_qos_metrics
    from gofr_tpu.tpu.stepledger import register_step_metrics
    from gofr_tpu.tpu.timeline import register_timeline_metrics

    manager = Manager()
    client = TPUClient()
    client.use_metrics(manager)
    client.register_metrics()
    register_slo_gauges(manager)
    register_utilization_metrics(manager)
    register_step_metrics(manager)  # idempotent next to register_metrics
    register_disagg_metrics(manager)
    register_fleet_metrics(manager)
    register_fleet_slo_metrics(manager)
    register_fleet_capacity_metrics(manager)
    register_journey_metrics(manager)
    register_incident_metrics(manager)
    register_qos_metrics(manager)
    register_meter_metrics(manager)
    register_migration_metrics(manager)
    register_elastic_metrics(manager)
    register_timeline_metrics(manager)
    register_hostprof_metrics(manager)
    register_fleet_timeline_metrics(manager)
    registered = set(manager._store)
    missing = recorded - registered
    assert not missing, (
        f"metrics recorded in gofr_tpu/tpu/ but never registered: "
        f"{sorted(missing)}")

    docs = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "observability.md")
    with open(docs, encoding="utf-8") as fp:
        text = fp.read()
    undocumented = {n for n in recorded if n not in text}
    assert not undocumented, (
        f"metrics recorded in gofr_tpu/tpu/ but missing from "
        f"docs/observability.md: {sorted(undocumented)}")


# -- endpoint-inventory consistency gate --------------------------------------
# route registrations: app.get/post defaults and install_routes path
# defaults all carry the literal ("/debug/<name>"); extraction shared
# with graftlint's surface pass


def test_debug_endpoint_inventory_documented():
    """Every /debug/* operator route registered anywhere in gofr_tpu
    (app.py + the tpu modules' install_routes) must appear in
    docs/observability.md — the endpoint sibling of the metric gate, so
    a new operator surface cannot ship undocumented."""
    from tools.analysis.passes.surface import collect_debug_routes

    repo = os.path.join(os.path.dirname(__file__), "..")
    routes = set(collect_debug_routes(repo))
    # scanner-rot guard: the known surfaces must all be in the scan
    for expected in ("/debug/profile", "/debug/requests", "/debug/engine",
                     "/debug/steps", "/debug/faults", "/debug/slo",
                     "/debug/incidents", "/debug/disagg", "/debug/fleet",
                     "/debug/qos", "/debug/capacity",
                     "/debug/fleet/capacity", "/debug/timeline",
                     "/debug/hostprof", "/debug/fleet/timeline"):
        assert expected in routes, f"scan missed {expected} (scanner rot?)"

    docs = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "observability.md")
    with open(docs, encoding="utf-8") as fp:
        text = fp.read()
    undocumented = {r for r in routes if r not in text}
    assert not undocumented, (
        f"/debug routes registered in gofr_tpu but missing from "
        f"docs/observability.md: {sorted(undocumented)}")
