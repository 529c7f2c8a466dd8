#!/usr/bin/env python3
"""Poll the flight recorder + SLO gauges during a soak and append JSONL.

Soak runs (tools/soak.py) record aggregate
throughput; this sidecar records the per-request TAIL evidence next to
it — who is in flight, recent completions' phase timings, the SLO
goodput fractions, and engine events (cache growth, resets, sheds) —
so a blown-tail soak can be diagnosed after the fact instead of
re-reproduced.

Each line also carries the fleet-level `/debug/engine` snapshot (slots,
page pool, utilization window — MFU/MBU/duty-cycle — and compile-cache
totals), the `/debug/steps` anatomy summary (per-phase step-time
baselines, segment totals, recent stragglers), the `/debug/slo`
burn-rate readout (per-SLO fast/slow burn + alert state — the paging
signal), the `/debug/incidents` index (auto-captured evidence
bundles + suppression counts), on split-serving deployments
(DISAGG_MODE=both) the `/debug/disagg` hand-off counters (queue
depth, hand-offs, fallbacks), and — on QOS=true servers — the
`/debug/qos` control-plane readout (shed-ladder level + transition
trail, per-class queue/goodput/preemption counters, batch-lane depth),
so soak artifacts gain efficiency, step-anatomy, error-budget, and
QoS-control axes next to the tail evidence. CAPACITY=true servers add
the `/debug/capacity` observatory line — per-tenant attribution totals
plus the λ/μ/ρ headroom forecast (predicted TTFT, collapse warning).

Router-tier targets additionally contribute the journey plane: the
`/debug/fleet/slo` rollup (fleet burn windows, per-replica SLO states,
hidden-page count) and a `/debug/journey` digest with nearest-rank
p50/p90/p99 over the ring's router-observed TTFB and stream duration —
cross-hop tail evidence next to the per-replica kind — and the
`/debug/fleet/capacity` rollup (fleet ρ/headroom, top fleet-wide
tenants, `replicas_needed`). ELASTIC=true routers add the
`/debug/fleet/elastic` reconciler digest (launcher, launched/draining
sets, scale events, last decisions), and replicas with drain-migration
enabled add the `/debug/drain` ledger (lifecycle, per-session
outcomes/gap_s — the zero-loss evidence).

With --loadgen pointed at a running open-loop generator's StatusServer
(tools/loadgen.py --status-port), every line also carries the traffic
side: offered vs served rps (the gap IS the backlog), per-class
inflight, outcome counts, and the live scorecard verdict — so the
timeline shows what was OFFERED next to what the server did with it.

Every line also carries a `/debug/hostprof` digest (per-class sample
counts, the top loop-thread stacks, and the sampler's measured
self-overhead — WHAT the loop was doing next to how long it took), and
with --timeline a `/debug/timeline` digest (event/flow counts + the
clock anchor) proving the Perfetto export is alive; the full trace
belongs in its own artifact (tools/soak.py archives TIMELINE_*.json).

Usage:
    python tools/obs_dump.py [--server http://127.0.0.1:8000]
                             [--metrics http://127.0.0.1:2121]
                             [--loadgen http://127.0.0.1:9100]
                             [--timeline [STEPS]]
                             [--interval 5] [--count 0]
                             [--out obs_dump.jsonl]

count 0 polls until interrupted. Failures are recorded as error entries
and polling continues — a restarting server must not kill the watcher.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
import urllib.request

SLO_GAUGES = ("app_tpu_slo_ttft_goodput", "app_tpu_slo_tpot_goodput",
              "app_tpu_tokens_per_second", "app_tpu_engine_stall_seconds",
              "app_tpu_active_slots", "app_tpu_queue_depth",
              "app_tpu_device_duty_cycle", "app_tpu_host_overhead_seconds",
              "app_tpu_breaker_state")


def _get(url: str, timeout: float = 5.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _percentiles(values: list) -> dict:
    """p50/p90/p99 by nearest-rank over a small sample (journey rings
    are bounded, so sorting in-process is fine)."""
    vals = sorted(v for v in values if isinstance(v, (int, float)))
    if not vals:
        return {}
    def pick(q: float) -> float:
        return vals[min(len(vals) - 1, int(q * (len(vals) - 1) + 0.5))]
    return {"n": len(vals), "p50": pick(0.50), "p90": pick(0.90),
            "p99": pick(0.99)}


def scrape_gauges(metrics_base: str) -> dict:
    """Pull the SLO/serving gauges out of the Prometheus exposition."""
    text = _get(metrics_base.rstrip("/") + "/metrics")
    out = {}
    for name in SLO_GAUGES:
        # value line: name{optional labels} <float>
        m = re.search(rf"^{re.escape(name)}(?:\{{[^}}]*\}})? (\S+)$",
                      text, re.MULTILINE)
        if m is not None:
            out[name] = float(m.group(1))
    return out


def poll_once(server: str, metrics_base: str,
              loadgen_base: str = "", timeline_steps: int = 0) -> dict:
    entry: dict = {"t": time.time()}
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/requests"))
        flight = body.get("data", body)  # responder envelope or raw
        entry["in_flight"] = flight.get("in_flight", [])
        entry["recent"] = flight.get("recent", [])
        entry["slo"] = flight.get("slo")
        entry["engine_events"] = flight.get("engine_events", [])
        entry["finished_total"] = flight.get("finished_total")
    except Exception as exc:  # noqa: BLE001 - keep polling through restarts
        entry["flight_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/engine"))
        snap = body.get("data", body)
        engine = {"engine": snap.get("engine"),
                  "utilization": snap.get("utilization"),
                  "page_pool": snap.get("page_pool"),
                  # crash-only surfaces: breaker state (open = the server
                  # is shedding with 503s) + reset/replay totals
                  "breaker": snap.get("breaker"),
                  "recovery": snap.get("recovery"),
                  # tiered-KV counters (spill/restore/hit/corrupt) ride in
                  # page_pool.kv_tier; surface them as their own key so a
                  # grep over the JSONL stream finds tier regressions
                  "kv_tier": (snap.get("page_pool") or {}).get("kv_tier")}
        compile_table = snap.get("compile") or {}
        # totals only — the per-program rows would bloat the JSONL stream
        engine["compile"] = {k: compile_table.get(k) for k in (
            "distinct_programs", "compile_seconds_total",
            "cache_hits_total", "disk_hits_total", "hit_ratio")}
        entry["engine"] = engine
    except Exception as exc:  # noqa: BLE001 - older servers lack the route
        entry["engine_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/steps?recent=8"))
        snap = body.get("data", body)
        # summary-level only: baselines + per-phase segment totals +
        # stragglers carry the step-anatomy signal; the full ring would
        # bloat the JSONL stream
        entry["steps"] = {
            "steps_total": snap.get("steps_total"),
            "stragglers_total": snap.get("stragglers_total"),
            "baselines": snap.get("baselines"),
            "summary": snap.get("summary"),
            "stragglers": snap.get("stragglers", [])[-5:],
        }
    except Exception as exc:  # noqa: BLE001 - older servers lack the route
        entry["steps_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/slo"))
        snap = body.get("data", body)
        # per-SLO alert states + burn rates are the paging signal; keep
        # the transitions trail so a flap is reconstructable
        entry["slo_burn"] = {
            "slos": {
                name: {"state": slo.get("state"),
                       "burn_fast": slo["windows"]["fast"].get("burn_rate"),
                       "burn_slow": slo["windows"]["slow"].get("burn_rate")}
                for name, slo in (snap.get("slos") or {}).items()},
            "transitions": snap.get("transitions", [])[-5:],
        }
    except Exception as exc:  # noqa: BLE001 - older servers lack the route
        entry["slo_burn_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/incidents"))
        snap = body.get("data", body)
        entry["incidents"] = {
            "captured_total": snap.get("captured_total"),
            "triggers": snap.get("triggers"),
            "suppressed": snap.get("suppressed"),
            # metadata only — the bundles themselves live in INCIDENT_DIR
            "recent": snap.get("incidents", [])[:5],
        }
    except Exception as exc:  # noqa: BLE001 - older servers lack the route
        entry["incidents_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/disagg"))
        snap = body.get("data", body)
        # counters + depths only; the nested per-pool engine snapshots
        # would duplicate /debug/engine in every line
        entry["disagg"] = {k: snap.get(k) for k in (
            "worker_alive", "queue_depth", "pending_handoffs",
            "handoffs_in_flight", "handoffs_total", "handoffs_consumed",
            "fallbacks_total")}
    except Exception as exc:  # noqa: BLE001 - colocated servers lack the route
        entry["disagg_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/fleet"))
        snap = body.get("data", body)
        # replica table compressed to the routing-relevant columns; the
        # router's counters ride along whole (they're already bounded)
        entry["fleet"] = {
            "policy": snap.get("policy"),
            "available": snap.get("available"),
            "routes": snap.get("routes"),
            "retries": snap.get("retries"),
            "stream_breaks": snap.get("stream_breaks"),
            "affinity": snap.get("affinity"),
            "replicas": [
                {k: r.get(k) for k in (
                    "name", "state", "available", "breaker_open", "shedding",
                    "queue_depth", "inflight", "stream_breaks")}
                for r in snap.get("replicas", [])],
        }
    except Exception as exc:  # noqa: BLE001 - only router-tier processes serve it
        entry["fleet_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/fleet/slo"))
        snap = body.get("data", body)
        # fleet burn + per-replica states carry the rollup signal; the
        # disagreement case (fleet paging, replicas quiet) is the one a
        # post-mortem greps for, so hidden_pages rides along
        entry["fleet_slo"] = {
            "fleet_states": snap.get("fleet_states"),
            "fleet": {
                name: {"state": slo.get("state"),
                       "burn_fast": ((slo.get("windows") or {})
                                     .get("fast") or {}).get("burn_rate"),
                       "burn_slow": ((slo.get("windows") or {})
                                     .get("slow") or {}).get("burn_rate")}
                for name, slo in ((snap.get("fleet") or {})
                                  .get("slos") or {}).items()},
            "classes": snap.get("classes"),
            "replicas": snap.get("replicas"),
            "replicas_paging": snap.get("replicas_paging"),
            "hidden_pages": snap.get("hidden_pages"),
        }
    except Exception as exc:  # noqa: BLE001 - only router-tier processes serve it
        entry["fleet_slo_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/journey"))
        snap = body.get("data", body)
        recent = snap.get("recent", [])
        # hop-latency percentiles over the ring: router-observed TTFB +
        # stream duration are the cross-hop tail evidence a blown-p99
        # soak is diagnosed from
        entry["journeys"] = {
            "finished_total": snap.get("finished_total"),
            "in_flight": len(snap.get("in_flight", [])),
            "ttfb_s": _percentiles([j.get("ttfb_s") for j in recent]),
            "stream_s": _percentiles([j.get("stream_s") for j in recent]),
            "outcomes": {
                outcome: sum(1 for j in recent
                             if j.get("outcome") == outcome)
                for outcome in {j.get("outcome") for j in recent}
                if outcome},
            "recent": recent[:5],
        }
    except Exception as exc:  # noqa: BLE001 - journey plane off or absent
        entry["journeys_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/capacity"))
        snap = body.get("data", body)
        # attribution + forecast only — the accounts/steps evidence rings
        # belong to the endpoint, not every JSONL line
        entry["capacity"] = {
            "totals": snap.get("totals"),
            "tenants": snap.get("tenants", [])[:5],
            "requests_total": snap.get("requests_total"),
            "steps_total": snap.get("steps_total"),
            "forecast": snap.get("forecast"),
        }
    except Exception as exc:  # noqa: BLE001 - CAPACITY=false servers lack it
        entry["capacity_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/fleet/capacity"))
        snap = body.get("data", body)
        # the fleet rollup is already bounded: headline + top tenants +
        # per-replica forecast rows ride along whole
        entry["fleet_capacity"] = {
            "fleet": snap.get("fleet"),
            "tenants": snap.get("tenants", [])[:5],
            "replicas": snap.get("replicas"),
        }
    except Exception as exc:  # noqa: BLE001 - only router-tier processes serve it
        entry["fleet_capacity_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/fleet/elastic"))
        snap = body.get("data", body)
        # reconciler state + the last few decisions — enough to answer
        # "why did/didn't it scale" without replaying the whole trail
        entry["elastic"] = {
            "launcher": snap.get("launcher"),
            "launched": snap.get("launched"),
            "draining": snap.get("draining"),
            "scale_events": snap.get("scale_events"),
            "replicas": snap.get("replicas"),
            "decisions": snap.get("decisions", [])[-4:],
        }
    except Exception as exc:  # noqa: BLE001 - ELASTIC=false routers lack it
        entry["elastic_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/drain"))
        snap = body.get("data", body)
        # replica-side drain ledger: lifecycle + per-session outcomes are
        # the zero-loss evidence a drain post-mortem needs
        entry["drain"] = {
            "lifecycle": snap.get("lifecycle"),
            "drain_started": snap.get("drain_started"),
            "outcomes": snap.get("outcomes"),
            "sessions": snap.get("sessions", [])[:5],
            "migrations_total": snap.get("migrations_total"),
            "drained": snap.get("drained"),
        }
    except Exception as exc:  # noqa: BLE001 - replicas without migration lack it
        entry["drain_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/qos"))
        snap = body.get("data", body)
        # ladder + per-class counters carry the control-plane signal; the
        # transition trail is bounded (deque) so it rides along whole
        entry["qos"] = {
            "ladder": snap.get("ladder"),
            "quotas": snap.get("quotas"),
            "preemptions_total": snap.get("preemptions_total"),
            "classes": {
                cls: {k: row.get(k) for k in (
                    "queued", "active", "submitted", "finished", "errors",
                    "shed", "preempted", "expired", "goodput",
                    "ttft_p50_ms")}
                for cls, row in (snap.get("classes") or {}).items()},
            "lane": snap.get("lane"),
        }
    except Exception as exc:  # noqa: BLE001 - QOS=false servers lack the route
        entry["qos_error"] = str(exc)
    if loadgen_base:
        try:
            body = json.loads(_get(loadgen_base.rstrip("/")
                                   + "/debug/loadgen"))
            snap = body.get("data", body)
            # offered vs served is the open-loop signal: a widening gap
            # with flat served_rps IS queueing collapse, timestamped
            # next to the server-side evidence above
            lg = {k: snap.get(k) for k in (
                "label", "offered_rps", "served_rps", "arrivals_fired",
                "completions", "inflight_total", "inflight", "outcomes",
                "dropped", "worst_dispatch_lag_s", "done", "elapsed_s",
                "verdict")}
            card = snap.get("scorecard")
            if isinstance(card, dict):
                # verdict-level summary only; the full scorecard lives
                # in the run artifact tools/loadgen.py writes
                lg["scorecard"] = {
                    "slo_met": card.get("slo_met"),
                    "classes": {
                        cls: {k: row.get(k) for k in (
                            "goodput", "ttft_ms_p95", "slo_met")}
                        for cls, row in (card.get("classes")
                                         or {}).items()}}
            entry["loadgen"] = lg
        except Exception as exc:  # noqa: BLE001 - generator may be gone
            entry["loadgen_error"] = str(exc)
    try:
        body = json.loads(_get(server.rstrip("/") + "/debug/hostprof"))
        snap = body.get("data", body)
        threads = snap.get("threads") or {}
        # top loop stack + per-class sample counts + the sampler's own
        # measured overhead — "what was the loop doing" on every line
        entry["hostprof"] = {
            "samples_total": snap.get("samples_total"),
            "overhead": snap.get("overhead"),
            "classes": {cls: row.get("samples")
                        for cls, row in threads.items()},
            "loop_top": (threads.get("loop") or {}).get("top", [])[:3],
        }
    except Exception as exc:  # noqa: BLE001 - HOSTPROF=false servers lack it
        entry["hostprof_error"] = str(exc)
    if timeline_steps:
        try:
            body = json.loads(_get(
                server.rstrip("/")
                + f"/debug/timeline?steps={int(timeline_steps)}"))
            snap = body.get("data", body)
            events = snap.get("traceEvents", [])
            phases: dict = {}
            for ev in events:
                ph = ev.get("ph", "?")
                phases[ph] = phases.get(ph, 0) + 1
            # digest only — the full trace belongs in its own artifact
            # (tools/soak.py archives TIMELINE_*.json); the JSONL line
            # carries enough to see the export is alive and flowing
            entry["timeline"] = {
                "events_total": snap.get("events_total", len(events)),
                "steps_window": snap.get("steps_window"),
                "phases": phases,
                "flows": len({ev.get("id") for ev in events
                              if ev.get("cat") == "flow"}),
                "anchor": snap.get("anchor"),
            }
        except Exception as exc:  # noqa: BLE001 - TIMELINE=false servers lack it
            entry["timeline_error"] = str(exc)
    try:
        entry["gauges"] = scrape_gauges(metrics_base)
    except Exception as exc:  # noqa: BLE001
        entry["metrics_error"] = str(exc)
    return entry


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--server", default="http://127.0.0.1:8000",
                    help="app HTTP base (serves /debug/requests)")
    ap.add_argument("--metrics", default="http://127.0.0.1:2121",
                    help="metrics server base (serves /metrics)")
    ap.add_argument("--loadgen", default="",
                    help="loadgen StatusServer base (serves "
                         "/debug/loadgen); empty skips the panel")
    ap.add_argument("--timeline", type=int, nargs="?", const=8, default=0,
                    metavar="STEPS",
                    help="also poll /debug/timeline and record a digest "
                         "(event/flow counts over the last STEPS steps, "
                         "default 8); 0 skips the panel")
    ap.add_argument("--interval", type=float, default=5.0)
    ap.add_argument("--count", type=int, default=0,
                    help="polls before exiting; 0 = until interrupted")
    ap.add_argument("--out", default="obs_dump.jsonl",
                    help="JSONL output path; '-' for stdout")
    args = ap.parse_args()

    fp = sys.stdout if args.out == "-" else open(args.out, "a",
                                                 encoding="utf-8")
    n = 0
    try:
        while True:
            entry = poll_once(args.server, args.metrics,
                              loadgen_base=args.loadgen,
                              timeline_steps=args.timeline)
            fp.write(json.dumps(entry) + "\n")
            fp.flush()
            n += 1
            if args.count and n >= args.count:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0
    finally:
        if fp is not sys.stdout:
            fp.close()


if __name__ == "__main__":
    sys.exit(main())
