"""TPU device client: the Container datasource wrapping the JAX/XLA runtime.

Parity: the reference's injected-datasource provider pattern
(pkg/gofr/datasource/mongo.go:41-74 — New(Config) + UseLogger/UseMetrics/
Connect, wired by externalDB.go:5-12) and its HealthCheck feeding
/.well-known/health (container/health.go:39-59). Where the reference's
datasource boundary is a TCP connection to a database, this one is the
process<->accelerator boundary: device enumeration, HBM usage, mesh
construction, and the TPU metric set (SURVEY.md §5: tokens/sec, TTFT/TPOT,
batch size, HBM bytes, queue depth, compile-cache hits).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from ..datasource import Health, STATUS_DEGRADED, STATUS_DOWN, STATUS_UP

TTFT_BUCKETS = (0.01, 0.025, 0.05, 0.1, 0.15, 0.25, 0.5, 1, 2.5, 5, 10)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1)
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


class TPUClient:
    """Holds the JAX device handles; everything model-facing goes through it."""

    def __init__(self, config=None, platform: Optional[str] = None):
        self.config = config
        self.platform_override = platform or (
            config.get_or_default("TPU_PLATFORM", "") if config is not None else "")
        self.logger = None
        self.metrics = None
        self._devices: List[Any] = []
        self._connected_at: Optional[float] = None
        self._jax = None
        # single-flight health probe state (see health_check); the lock
        # serializes probe start/result reads — without it two concurrent
        # health polls can both observe a dead probe thread, both reset
        # _probe_result, and one then unpacks None after join (spurious
        # DOWN flap, ADVICE r5)
        import threading

        self._probe_lock = threading.Lock()
        self._probe_thread = None
        self._probe_result = None
        # fault-injection plane (tpu/faults.py): None in production; armed
        # deployments can wedge/fail the health probe for chaos drills
        self.faults = None

    # -- provider pattern (mongo.go:142-155) ----------------------------------
    def use_logger(self, logger) -> None:
        self.logger = logger

    def use_metrics(self, metrics) -> None:
        self.metrics = metrics

    def connect(self) -> None:
        import jax

        self._jax = jax
        if self.platform_override:
            # TPU_PLATFORM names the platform this server must run on:
            # jax.devices(<platform>) raises when the process has no such
            # backend, so TPU_PLATFORM=tpu fails the boot on a machine
            # without a chip instead of serving from the CPU
            self._devices = jax.devices(self.platform_override)
        else:
            self._devices = jax.devices()
        self._connected_at = time.monotonic()
        if self.metrics is not None:
            self.register_metrics()
        if self.logger is not None:
            kinds = {d.device_kind for d in self._devices}
            self.logger.infof("connected to %d %s device(s): %s",
                              len(self._devices), self.platform,
                              ", ".join(sorted(kinds)))

    @classmethod
    def from_config(cls, config, logger, metrics) -> "TPUClient":
        client = cls(config)
        client.use_logger(logger)
        client.use_metrics(metrics)
        client.connect()
        return client

    def register_metrics(self) -> None:
        m = self.metrics
        for name, desc in (
            ("app_tpu_compile_total", "XLA compilations performed"),
            ("app_tpu_compile_cache_hits", "executor compile-cache hits"),
            ("app_tpu_compile_disk_hits", "programs loaded from the disk cache"),
            ("app_tpu_execute_total", "device executions dispatched"),
            ("app_tpu_tokens_generated_total", "output tokens generated"),
            ("app_tpu_requests_total", "inference requests admitted"),
            ("app_tpu_spec_drafted_total", "speculative draft tokens proposed"),
            ("app_tpu_spec_accepted_total", "speculative draft tokens accepted"),
            ("app_tpu_page_waits_total", "admissions deferred on page-pool exhaustion"),
            # crash-only recovery (tpu/faults.py + engine replay)
            ("app_tpu_device_resets_total",
             "device-state resets after a failed donated-cache program"),
            ("app_tpu_request_replays_total",
             "interrupted requests requeued for replay after a device reset"),
            ("app_tpu_replayed_tokens_total",
             "already-delivered tokens re-prefilled by replay admissions"),
            ("app_tpu_requests_quarantined_total",
             "poison requests failed after repeatedly reset-looping the engine"),
            # step anatomy ledger (tpu/stepledger.py)
            ("app_tpu_step_stragglers_total",
             "engine steps flagged slower than the rolling per-phase "
             "baseline, by dominant-segment cause"),
            # incident autopsy plane (tpu/incidents.py)
            ("app_tpu_incidents_total",
             "incident evidence bundles captured, by trigger"),
            ("app_tpu_incidents_suppressed_total",
             "incident triggers suppressed by the capture rate limit "
             "(cooldown / max-per-hour), by trigger"),
            # best-effort hook self-observability (tpu/obs.py)
            ("app_obs_dropped_metrics_total",
             "metric recordings swallowed by best-effort hooks, by metric "
             "name (a non-zero series is a wiring bug)"),
        ):
            try:
                m.new_counter(name, desc)
            except Exception:  # noqa: BLE001 - re-registration on reconnect
                pass
        for name, desc in (
            ("app_tpu_queue_depth", "requests waiting for batch assembly"),
            ("app_tpu_active_slots", "occupied continuous-batching slots"),
            ("app_tpu_decode_blocks_queued",
             "decode blocks still in flight behind the one the engine loop "
             "last read (0 while slots decode = a dry sync: the device "
             "idles through that block's demux and emit)"),
            ("app_tpu_hbm_bytes_used", "HBM bytes in use per device"),
            ("app_tpu_hbm_bytes_limit", "HBM bytes available per device"),
            ("app_tpu_tokens_per_second", "rolling decode throughput"),
            ("app_tpu_pages_used", "KV pool pages currently owned by slots"),
            ("app_tpu_engine_stall_seconds",
             "seconds the engine loop has been stuck inside one device "
             "call (0 = healthy); scrape-time, set by a container scrape "
             "hook because a wedged loop cannot push its own metric"),
            ("app_tpu_slo_ttft_goodput",
             "fraction of recent requests meeting the TTFT target "
             "(flight recorder rolling window)"),
            ("app_tpu_slo_tpot_goodput",
             "fraction of recent requests meeting the TPOT target "
             "(flight recorder rolling window)"),
            # SLO burn-rate engine (tpu/incidents.py)
            ("app_tpu_slo_burn_rate",
             "SLO error-budget burn rate (error rate / budget) by slo "
             "and window (fast/slow)"),
            ("app_tpu_slo_alert_state",
             "SLO alert state by slo: 0 ok, 1 warn, 2 page "
             "(both-windows burn rule)"),
            # utilization ledger (tpu/utilization.py): roofline telemetry
            ("app_tpu_device_duty_cycle",
             "fraction of the rolling window the device spent executing "
             "dispatched programs"),
            ("app_tpu_host_overhead_seconds",
             "host/scheduler seconds (admission, prep, demux) in the "
             "rolling utilization window"),
            ("app_tpu_mfu",
             "model FLOPs utilization vs the platform peak, by phase"),
            ("app_tpu_mbu",
             "HBM bandwidth utilization vs the platform peak, by phase"),
            ("app_tpu_hbm_bytes",
             "HBM bytes per device (kind=in_use|limit)"),
            ("app_tpu_kv_pool_pages",
             "KV page-pool occupancy (kind=used|free)"),
            ("app_tpu_pool_pages",
             "pages in use a page group (group=the family's group names: "
             "models/protocol.py `groups`)"),
            ("app_tpu_breaker_state",
             "reset-storm breaker state (0=closed, 1=half_open, 2=open)"),
            ("app_tpu_moe_routing",
             "expert routing of the decode steps since the last reset, an "
             "expert block and step (what=rows_per_step|tokens_per_held_expert_mean|"
             "tokens_per_held_expert_max_over_mean|"
             "experts_touched_per_layer_step|held_pick_share)"),
        ):
            try:
                m.new_gauge(name, desc)
            except Exception:  # noqa: BLE001
                pass
        from .stepledger import STEP_SECONDS_BUCKETS

        for name, desc, buckets in (
            ("app_tpu_ttft_seconds", "time to first token", TTFT_BUCKETS),
            ("app_tpu_queue_wait_seconds", "submit-to-admission wait", TTFT_BUCKETS),
            ("app_tpu_tpot_seconds", "time per output token", TPOT_BUCKETS),
            ("app_tpu_batch_size", "assembled batch sizes", BATCH_BUCKETS),
            ("app_tpu_execute_seconds", "device execution wall time", TPOT_BUCKETS),
            ("app_tpu_step_seconds",
             "engine step time by phase and attributed segment",
             STEP_SECONDS_BUCKETS),
        ):
            try:
                m.new_histogram(name, desc, buckets)
            except Exception:  # noqa: BLE001
                pass

    # -- device surface -------------------------------------------------------
    @property
    def devices(self) -> List[Any]:
        return self._devices

    @property
    def device_count(self) -> int:
        return len(self._devices)

    @property
    def platform(self) -> str:
        return self._devices[0].platform if self._devices else "none"

    def mesh(self, axes: Dict[str, int], allow_subset: bool = False):
        """Build a jax.sharding.Mesh over the client's devices.

        axes: ordered {axis_name: size}; product must equal device_count
        (pass -1 for one axis to infer it). allow_subset=True builds the
        mesh over the FIRST product-many devices instead — for serving
        configs sharded narrower than the visible slice (e.g. TP=2 on an
        8-chip host).
        """
        import numpy as np
        from jax.sharding import Mesh

        names = list(axes.keys())
        sizes = list(axes.values())
        if -1 in sizes:
            known = int(np.prod([s for s in sizes if s != -1]))
            sizes[sizes.index(-1)] = len(self._devices) // known
        total = int(np.prod(sizes))
        if total != len(self._devices) and not (allow_subset
                                                and total < len(self._devices)):
            raise ValueError(f"mesh axes {dict(zip(names, sizes))} need {total} devices, "
                             f"have {len(self._devices)}")
        return Mesh(np.array(self._devices[:total]).reshape(sizes),
                    tuple(names))

    def memory_stats(self) -> List[Dict[str, Any]]:
        out = []
        for d in self._devices:
            try:
                stats = d.memory_stats() or {}
            except Exception:  # noqa: BLE001 - CPU backends have no stats
                stats = {}
            out.append({
                "id": d.id,
                "kind": d.device_kind,
                "bytes_in_use": stats.get("bytes_in_use", 0),
                "bytes_limit": stats.get("bytes_limit", 0),
            })
        return out

    def refresh_memory_metrics(self) -> None:
        if self.metrics is None:
            return
        for s in self.memory_stats():
            dev = str(s["id"])
            self.metrics.set_gauge("app_tpu_hbm_bytes_used", s["bytes_in_use"],
                                   device=dev)
            self.metrics.set_gauge("app_tpu_hbm_bytes_limit", s["bytes_limit"],
                                   device=dev)
            # canonical kind-labeled series (the legacy _used/_limit pair
            # stays for dashboards built on PR 0; see docs/observability.md)
            self.metrics.set_gauge("app_tpu_hbm_bytes", s["bytes_in_use"],
                                   device=dev, kind="in_use")
            self.metrics.set_gauge("app_tpu_hbm_bytes", s["bytes_limit"],
                                   device=dev, kind="limit")

    # -- health (feeds /.well-known/health) -----------------------------------
    # the device round-trip gets this long before the probe is declared
    # stuck; a wedged PJRT call can block FOREVER, and /health must answer
    # regardless (class attr so deployments/tests can tune per instance)
    HEALTH_PROBE_TIMEOUT_S = 3.0

    def _probe_device(self) -> None:
        """The actual device round-trip, run on the single-flight probe
        thread: like the SQL ping (sql/health.go:26-65), but isolated so a
        device that stops answering (a PJRT call that never returns) pins
        ONE daemon thread instead of every health handler."""
        try:
            import jax.numpy as jnp

            if self.faults is not None:  # chaos drills: wedge/fail the probe
                self.faults.hit("device.health_probe")
            ok = float(jnp.asarray(1.0) + 1.0) == 2.0
            self._probe_result = (STATUS_UP if ok else STATUS_DEGRADED, None)
        except Exception as exc:  # noqa: BLE001
            self._probe_result = (STATUS_DOWN, str(exc))

    def health_check(self) -> Health:
        if not self._devices:
            return Health(status=STATUS_DOWN, details={"error": "no devices"})
        import threading

        # single-flight: while one probe is still blocked inside the
        # device, health polls reuse it (reporting DEGRADED) rather than
        # piling up a stuck thread per poll. Start/result are guarded by
        # _probe_lock so concurrent polls cannot double-start a probe or
        # reset the result another poll is about to read
        with self._probe_lock:
            probe = self._probe_thread
            if probe is None or not probe.is_alive():
                self._probe_result = None
                probe = threading.Thread(target=self._probe_device,
                                         name="tpu-health-probe", daemon=True)
                self._probe_thread = probe
                probe.start()
        probe.join(timeout=self.HEALTH_PROBE_TIMEOUT_S)
        with self._probe_lock:
            result = self._probe_result
        if probe.is_alive() or result is None:
            # still blocked inside the device — or finished the join race
            # without a published result yet: degraded, never a crash
            return Health(status=STATUS_DEGRADED, details={
                "platform": self.platform,
                "error": f"device probe stuck for "
                         f">{self.HEALTH_PROBE_TIMEOUT_S:.0f}s "
                         f"(runtime not answering)",
            })
        status, err = result
        if status == STATUS_DOWN:
            return Health(status=STATUS_DOWN, details={"error": err})
        self.refresh_memory_metrics()
        mem = self.memory_stats()
        return Health(status=status, details={
            "platform": self.platform,
            "devices": len(self._devices),
            "memory": mem,
            "uptime_s": round(time.monotonic() - (self._connected_at or time.monotonic()), 1),
        })

    def close(self) -> None:
        self._devices = []
