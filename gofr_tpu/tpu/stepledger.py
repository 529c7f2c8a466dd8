"""Step anatomy ledger: per-engine-step wall-clock attribution.

The flight recorder (tpu/flightrecorder.py) explains where one REQUEST
spent its time; the utilization ledger (tpu/utilization.py) says how far
the engine runs from the roofline. Neither answers the question a blown
step budget raises: **why did THIS engine loop iteration take 90 ms when
the baseline is 12?** This module closes that gap — the per-iteration
sibling of vLLM's iteration logs, feeding the Dapper-style
metrics → exemplar → trace → request drill the exemplar-carrying
histograms enable.

Every engine loop iteration that does work becomes one ``StepRecord`` in
a bounded ring, attributing the step's measured wall-clock to named,
mutually-exclusive segments that SUM to the step's wall time exactly (an
explicit ``other`` residual means nothing can hide):

  ``idle_gap``     time since the previous step ended (loop parked on the
                   wake event, or blocked outside the instrumented body) —
                   kept OUT of the segment sum; a separate field
  ``lock_wait``    the loop acquiring the engine's ``_state_lock`` at the
                   top of an iteration (submitters, drains and debug
                   snapshots hold it too)
  ``admission``    ``_admit``: queue drain, heap ordering, grouping, wave
                   exchange on the multi-controller plane
  ``program_lookup``  every ``Executor.compile`` lookup the loop makes
                   for a step program (prefill / prefix / decode / chunk /
                   verify / restore): describing the arguments (shapes and
                   dtypes: no array is made, so no wait for the device's
                   queue) and hashing the argument tree to find the cached
                   program. A cache MISS's compile time is re-attributed
                   to ``compile``; ``/debug/engine`` counts the lookups
                   (``engine.program_lookup``)
  ``bind``         after a prefill dispatch: slot binding, request
                   stamps, page assignment and the prefix-cache insert
  ``page_alloc``   page reservation / prefix-cache match /
                   eviction inside admission readiness (includes the
                   page-wait path — an exhausted pool shows up here).
                   KV spill to the host tier (D2H fetch of evicted pages)
                   also lands here: it happens inside eviction
  ``kv_restore``   with the tiered KV cache: host/Redis
                   tier lookup plus the H2D scatter that rebuilds evicted
                   prefix pages in the pool at admission, charged
                   separately from ``page_alloc`` (nested segments
                   subtract child time) so "restore is slower than
                   recompute" is attributable from the ledger alone
  ``kv_handoff``   disaggregated serving (tpu/disagg.py): on the prefill
                   pool, the D2H page gather + PageBlob encode that ships
                   a finished prompt's KV to the decode pool; on the
                   decode pool, blob validation + the donated H2D scatter
                   that lands handed-off KV before a slot binds. Charged
                   separately from ``kv_restore`` so tier restores and
                   hand-off restores stay distinguishable in the ledger
  ``host_prep``    batch array prep: padding, lengths, sampling controls,
                   block tables
  ``compile``      executor cache-miss compiles, re-attributed out of
                   whichever segment the compile happened under
  ``dispatch``     device program enqueue calls (prefill / decode /
                   verify / chunk), including fault-injection hooks at
                   those sites
  ``device_sync``  blocking host sync on the oldest in-flight dispatch —
                   the segment that grows when the device (or transport)
                   is the problem. With async D2H (copy_to_host_async at
                   dispatch time, the engine default) this is a transfer
                   COMPLETION check, not the transfer itself
  ``demux``        post-sync token routing math: the vectorized stop-scan
                   / budget / context-cap pass over the synced
                   ``[B, block]`` token matrix that decides how many
                   tokens each live row emits and which slots go terminal
  ``emit``         post-sync delivery: batched per-request out_queue
                   puts, replay-ledger append, recorder/metric callbacks,
                   slot bookkeeping and hot-path slot reset
  ``other``        everything not wrapped above (the residual that makes
                   the sum identity hold)

Each segment also accumulates the loop thread's CPU time
(``time.thread_time()`` at the same boundaries): ``segments_cpu`` has the
keys of ``segments``, and ``wall - cpu`` of a segment is the time the
loop was BLOCKED in it (a lock, the GIL, a full queue, a device round
trip), not computing.

The same boundaries are spans on the profiler's clock: ``step_start`` /
``step_end`` open and close ``jax.profiler.TraceAnnotation("loop/step",
step=<seq>)``, every ``seg(name)`` a ``loop/<name>`` nested inside it, and
``between(name)`` names what the loop does between two steps
(``loop/park``: the idle wait; ``loop/step_close``: the record's
publication and the engine's straggler, meter and incident hooks on it). A TraceAnnotation costs
one object and two calls unless a profiler session with the host tracer
on is running (``tpu/profiler.py``, the benchmark's traced run), and then
the device trace's idle gaps can be laid against what the loop was doing.
JAX is imported when a ledger is built, not with this module; a ledger
built where it cannot be imported records as before.

On top of the ring:

  * a **straggler sentinel** — rolling per-phase baseline (EWMA of step
    wall time + a rolling percentile band); a step slower than
    ``straggler_k`` × the larger of the two is flagged with its dominant
    segment as the cause, counted in
    ``app_tpu_step_stragglers_total{cause}``, and (via the engine)
    emitted as a ``step_straggler`` flight-recorder event;
  * ``app_tpu_step_seconds{phase,segment}`` histograms with request-id
    exemplars, so a bad Grafana bucket deep-links to
    ``/debug/requests/{id}``;
  * ``GET /debug/steps`` (install_routes / App.enable_step_ledger): the
    recent ring + per-phase/segment summary + live baselines + recent
    stragglers.

Threading contract: segment accumulation (step_start / seg / note_*) is
engine-loop-thread-only — the ledger records the owning thread at
step_start and silently ignores calls from any other thread (warmup-time
compiles, scoring passes), so no lock sits on the hot path. Only the
ring/snapshot boundary takes a lock. All clocks are ``time.monotonic()``
— an NTP step can never fabricate a straggler.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, Optional

from .obs import MetricsHook
from .ownership import loop_only

SEGMENTS = ("lock_wait", "admission", "page_alloc", "kv_restore",
            "kv_handoff", "host_prep", "program_lookup", "compile",
            "dispatch", "bind", "device_sync", "demux", "emit",
            "other")

SPAN_PREFIX = "loop/"

# step phases, by what the step synced (one sync a record: a loop turn
# that reads the prefill entries behind its first sync closes a record for
# each) or, sync-less, what it dispatched
PHASES = ("prefill", "decode", "verify", "chunk", "dispatch", "admit")

STEP_SECONDS_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                        0.25, 0.5, 1.0, 2.5, 5.0, 15.0)


class StepRecord:
    """One engine loop iteration's anatomy (see module docstring)."""

    __slots__ = ("seq", "started_at", "wall_s", "idle_gap_s", "phase",
                 "segments", "segments_cpu", "cpu_s", "active_slots",
                 "inflight", "inflight_prefill", "depth_now", "queue_depth",
                 "tokens", "page_writes", "window_pages", "dry_sync",
                 "block_steps", "dispatches",
                 "slowest_request_id",
                 "straggler", "cause", "baseline_s")

    def __init__(self, seq: int, started_at: float, wall_s: float,
                 idle_gap_s: float, phase: str,
                 segments: Dict[str, float],
                 segments_cpu: Optional[Dict[str, float]] = None,
                 cpu_s: float = 0.0):
        self.seq = seq
        self.started_at = started_at          # monotonic; display-only
        self.wall_s = wall_s                  # loop-body time == sum(segments)
        self.idle_gap_s = idle_gap_s
        self.phase = phase
        self.segments = segments
        # the loop thread's CPU seconds inside each segment (same keys,
        # 0 <= cpu <= wall) and over the whole step: wall - cpu is time
        # the loop was blocked, not working
        self.segments_cpu = segments_cpu if segments_cpu is not None else {}
        self.cpu_s = cpu_s
        self.active_slots = 0
        # dispatches still in flight at the step's close, and how many of
        # them are prefills; the rest are decode blocks and verifies,
        # which is what the device has to run while the loop is elsewhere
        self.inflight = 0
        self.inflight_prefill = 0
        # the decode entries the loop was keeping queued this turn, of
        # the `pipeline_depth` it may (tpu/queuedepth.py); 0 = not told
        self.depth_now = 0
        self.queue_depth = 0
        self.tokens = 0
        # pages the synced decode block's flush wrote (the paged engine's
        # floating-point pools: one a live row a block, two where the
        # block crossed a page; 0 where no block was synced)
        self.page_writes = 0
        # pages in use in the page groups that keep a window, as the
        # synced decode block's sync found them (0 for a family without)
        self.window_pages = 0
        # a decode block was read with slots still decoding and no decode
        # block queued behind it: the device ran dry through this step's
        # demux and emit (engine._sync_oldest)
        self.dry_sync = False
        # the steps of the decode block this record read: a full block or
        # a half one (engine._decode_block_now); 0 where it read none
        self.block_steps = 0
        # what the iteration enqueued, by kind; `decode_steps` the steps
        # of its decode blocks together
        self.dispatches: Dict[str, int] = {}
        self.slowest_request_id: Optional[int] = None
        self.straggler = False
        self.cause: Optional[str] = None
        self.baseline_s: Optional[float] = None

    def dominant_segment(self) -> str:
        if not self.segments:
            return "other"
        return max(self.segments.items(), key=lambda kv: kv[1])[0]

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "step": self.seq,
            "phase": self.phase,
            "wall_s": round(self.wall_s, 6),
            "idle_gap_s": round(self.idle_gap_s, 6),
            "cpu_s": round(self.cpu_s, 6),
            "segments": {k: round(v, 6) for k, v in self.segments.items()
                         if v > 0.0},
            "segments_cpu": {k: round(self.segments_cpu.get(k, 0.0), 6)
                             for k, v in self.segments.items() if v > 0.0},
            "active_slots": self.active_slots,
            "inflight": self.inflight,
            "inflight_decode": self.inflight - self.inflight_prefill,
            "inflight_prefill": self.inflight_prefill,
            "depth_now": self.depth_now,
            "queue_depth": self.queue_depth,
            "tokens": self.tokens,
        }
        if self.page_writes:
            out["page_writes"] = self.page_writes
        if self.window_pages:
            out["window_pages"] = self.window_pages
        if self.dry_sync:
            out["dry_sync"] = True
        if self.block_steps:
            out["block_steps"] = self.block_steps
        if self.dispatches:
            out["dispatches"] = dict(self.dispatches)
        if self.slowest_request_id is not None:
            out["slowest_request_id"] = self.slowest_request_id
        if self.straggler:
            out["straggler"] = True
            out["cause"] = self.cause
            if self.baseline_s is not None:
                out["baseline_s"] = round(self.baseline_s, 6)
        return out


class _PhaseBaseline:
    """Per-phase rolling step-time model: EWMA mean + a recent-window
    percentile band. A step is a straggler when it exceeds
    k × max(ewma, p95) after `min_samples` observations. A flagged value
    updates the EWMA CLAMPED to the threshold and never enters the
    percentile window — one outlier must not inflate the band so the next
    straggler escapes, while a genuine regime change still converges (each
    flagged step drags the EWMA up toward the threshold)."""

    __slots__ = ("ewma", "samples", "window")

    WINDOW = 128

    def __init__(self):
        self.ewma: Optional[float] = None
        self.samples = 0
        self.window: "collections.deque" = collections.deque(
            maxlen=self.WINDOW)

    def p95(self) -> Optional[float]:
        if not self.window:
            return None
        ordered = sorted(self.window)
        return ordered[min(len(ordered) - 1, int(0.95 * len(ordered)))]

    def threshold(self, k: float) -> Optional[float]:
        if self.ewma is None:
            return None
        band = self.p95()
        return k * max(self.ewma, band if band is not None else 0.0)

    def update(self, wall_s: float, alpha: float) -> None:
        self.ewma = (wall_s if self.ewma is None
                     else (1.0 - alpha) * self.ewma + alpha * wall_s)
        self.samples += 1
        self.window.append(wall_s)

    def describe(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"samples": self.samples}
        if self.ewma is not None:
            out["ewma_s"] = round(self.ewma, 6)
        band = self.p95()
        if band is not None:
            out["p95_s"] = round(band, 6)
        return out


def _profiler_annotation():
    """jax.profiler.TraceAnnotation, or None where JAX cannot be imported
    (a ledger in a unit test records without it)."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:  # noqa: BLE001 - no JAX, no spans
        return None
    return TraceAnnotation


_PROFILER = object()      # `annotate` default: jax.profiler's, if importable


class StepLedger:
    """Bounded ring of StepRecords + straggler sentinel (module doc)."""

    def __init__(self, capacity: int = 512, metrics=None, logger=None,
                 straggler_k: float = 3.0, baseline_alpha: float = 0.1,
                 min_samples: int = 16, clock=time.monotonic,
                 cpu_clock=time.thread_time, annotate=_PROFILER):
        self.capacity = max(16, int(capacity))
        self.straggler_k = float(straggler_k)
        self.baseline_alpha = float(baseline_alpha)
        self.min_samples = max(1, int(min_samples))
        self._clock = clock
        self._cpu_clock = cpu_clock
        # span factory, `annotate(name, **metadata)` -> context manager:
        # jax.profiler.TraceAnnotation unless one is given (tests); None,
        # or no JAX to import, switches spans off
        self._annotate = (_profiler_annotation() if annotate is _PROFILER
                          else annotate)
        self._obs = MetricsHook(metrics, logger=logger)
        self.logger = logger
        # ring + aggregates, guarded by one short lock (snapshot boundary)
        self._lock = threading.Lock()
        self._ring: "collections.deque[StepRecord]" = collections.deque(
            maxlen=self.capacity)
        self._baselines: Dict[str, _PhaseBaseline] = {}
        self._stragglers: "collections.deque" = collections.deque(maxlen=32)
        self.steps_total = 0
        self.stragglers_total = 0
        # loop-thread-only accumulation state (no lock — see module doc)
        self._owner: Optional[int] = None
        self._seq = 0
        self._t0: Optional[float] = None
        self._last_end: float = clock()
        # [name, started, child_s, cpu_started, child_cpu_s, span]
        self._frames: List[list] = []
        self._segments: Dict[str, float] = {}
        self._segments_cpu: Dict[str, float] = {}
        self._cpu0 = 0.0
        self._dispatches: Dict[str, int] = {}
        self._sync_kind: Optional[str] = None
        self._tokens = 0
        self._page_writes = 0
        self._window_pages = 0
        self._dry_sync = False
        self._block_steps = 0
        self._slowest: Optional[int] = None

    # -- wiring ---------------------------------------------------------------
    def use_metrics(self, metrics) -> None:
        if metrics is not None:
            self._obs = MetricsHook(metrics, logger=self.logger)

    def configure(self, capacity: Optional[int] = None,
                  straggler_k: Optional[float] = None,
                  baseline_alpha: Optional[float] = None,
                  min_samples: Optional[int] = None) -> None:
        """Apply operator config (App.enable_step_ledger). Resizing the
        ring keeps the newest records."""
        with self._lock:
            if capacity is not None and int(capacity) != self.capacity:
                self.capacity = max(16, int(capacity))
                self._ring = collections.deque(self._ring,
                                               maxlen=self.capacity)
            if straggler_k is not None:
                self.straggler_k = float(straggler_k)
            if baseline_alpha is not None:
                self.baseline_alpha = float(baseline_alpha)
            if min_samples is not None:
                self.min_samples = max(1, int(min_samples))

    # -- accumulation (engine loop thread only) -------------------------------
    def _mine(self) -> bool:
        return (self._t0 is not None
                and self._owner == threading.get_ident())

    def _open_span(self, name: str, **metadata):
        """Enter `loop/<name>` on the profiler's clock; None without a
        span factory. Loop thread only: a span closes on the thread that
        opened it, innermost first, which the frame stack guarantees."""
        if self._annotate is None:
            return None
        try:
            span = self._annotate(SPAN_PREFIX + name, **metadata)
            span.__enter__()
            return span
        except Exception:  # noqa: BLE001 - a span never fails a step
            return None

    @staticmethod
    def _close_span(span) -> None:
        if span is not None:
            try:
                span.__exit__(None, None, None)
            except Exception:  # noqa: BLE001
                pass

    @loop_only(fields=("_owner", "_seq", "_t0", "_last_end", "_frames",
                       "_segments", "_segments_cpu", "_cpu0", "_dispatches",
                       "_sync_kind", "_tokens", "_slowest"))
    def step_start(self) -> None:
        """Open a step. The gap since the previous step's end (wake waits,
        anything outside the instrumented body) becomes idle_gap."""
        if self._t0 is not None:       # already open (reset path re-entry)
            return
        self._owner = threading.get_ident()
        span = self._open_span("step", step=self._seq + 1)
        now = self._clock()
        cpu = self._cpu_clock()
        self._t0 = now
        self._cpu0 = cpu
        self._frames = [["other", now, 0.0, cpu, 0.0, span]]
        self._segments = {}
        self._segments_cpu = {}
        self._dispatches = {}
        self._sync_kind = None
        self._tokens = 0
        self._page_writes = 0
        self._window_pages = 0
        self._dry_sync = False
        self._block_steps = 0
        self._slowest = None

    class _Seg:
        __slots__ = ("ledger", "name", "active")

        def __init__(self, ledger: "StepLedger", name: str):
            self.ledger = ledger
            self.name = name
            self.active = False

        def __enter__(self):
            ledger = self.ledger
            if ledger._mine():
                self.active = True
                span = ledger._open_span(self.name)
                ledger._frames.append(
                    [self.name, ledger._clock(), 0.0, ledger._cpu_clock(),
                     0.0, span])
            return self

        def __exit__(self, *exc):
            if self.active and self.ledger._mine():
                self.ledger._pop_frame()
            return False

    def seg(self, name: str) -> "StepLedger._Seg":
        """Context manager attributing the wrapped block's EXCLUSIVE time
        (minus nested segments and re-attributions) to `name`. No-op when
        no step is open or on a foreign thread."""
        return self._Seg(self, name)

    class _Between:
        """A span BETWEEN steps (`loop/park`: the idle wait): on the
        profiler's clock only. The ledger counts that time as the next
        step's idle_gap, as before."""

        __slots__ = ("ledger", "name", "span")

        def __init__(self, ledger: "StepLedger", name: str):
            self.ledger = ledger
            self.name = name
            self.span = None

        def __enter__(self):
            ledger = self.ledger
            if ledger._t0 is None \
                    and ledger._owner == threading.get_ident():
                self.span = ledger._open_span(self.name)
            return self

        def __exit__(self, *exc):
            StepLedger._close_span(self.span)
            return False

    def between(self, name: str) -> "StepLedger._Between":
        """Context manager naming what the loop thread does between two
        steps (`loop/<name>`, span only). No-op inside a step or on a
        foreign thread."""
        return self._Between(self, name)

    @loop_only
    def _pop_frame(self) -> None:
        name, started, child_s, cpu_started, child_cpu, span = \
            self._frames.pop()
        dur = self._clock() - started
        dur_cpu = self._cpu_clock() - cpu_started
        self._close_span(span)
        own = max(0.0, dur - child_s)
        self._segments[name] = self._segments.get(name, 0.0) + own
        self._segments_cpu[name] = (self._segments_cpu.get(name, 0.0)
                                    + max(0.0, dur_cpu - child_cpu))
        if self._frames:
            self._frames[-1][2] += dur
            self._frames[-1][4] += dur_cpu

    @loop_only
    def note_stolen(self, name: str, seconds: float) -> None:
        """Re-attribute `seconds` already elapsing inside the current
        segment to `name` (the executor's compile callback: a cache-miss
        compile under `dispatch` must read as compile, not dispatch)."""
        if seconds <= 0.0 or not self._mine():
            return
        self._segments[name] = self._segments.get(name, 0.0) + seconds
        if self._frames:
            self._frames[-1][2] += seconds

    @loop_only
    def note_dispatch(self, kind: str, steps: int = 0) -> None:
        """One enqueued program of `kind`; `steps`: a decode block's."""
        if self._mine():
            self._dispatches[kind] = self._dispatches.get(kind, 0) + 1
            if steps:
                key = kind + "_steps"
                self._dispatches[key] = self._dispatches.get(key, 0) + steps

    @loop_only
    def note_sync(self, kind: str, tokens: int = 0,
                  slowest_request_id: Optional[int] = None,
                  page_writes: int = 0, dry: bool = False,
                  window_pages: int = 0, block_steps: int = 0) -> None:
        if self._mine():
            self._sync_kind = kind
            self._block_steps = int(block_steps)
            self._tokens += int(tokens)
            self._page_writes += int(page_writes)
            self._window_pages = max(self._window_pages, int(window_pages))
            self._dry_sync = self._dry_sync or bool(dry)
            if slowest_request_id is not None:
                self._slowest = slowest_request_id

    @loop_only
    def step_abort(self) -> None:
        """Discard the open step (device-reset path): a step that died in
        an exception must not feed the baselines, but its time still
        counts toward the next step's idle_gap."""
        if self._t0 is None:
            return
        self._last_end = self._clock()
        self._t0 = None
        if self._owner == threading.get_ident():
            while self._frames:        # spans close where they were opened
                self._close_span(self._frames.pop()[5])
        self._frames = []

    @loop_only
    def step_end(self, active_slots: int = 0, inflight: int = 0,
                 queue_depth: int = 0, closing=None, *,
                 inflight_prefill: int = 0,
                 depth_now: int = 0) -> Optional[StepRecord]:
        """Close the step. Pure-bookkeeping iterations (no dispatch, no
        sync, no tokens) are dropped — their time accumulates into the
        next real step's idle_gap, so an idle engine never floods the
        ring. Returns the record, or None when dropped. `closing(rec)`,
        the engine's hooks on the closed record, runs after publication
        inside the same `loop/step_close` span."""
        if not self._mine():
            return None
        while self._frames:
            self._pop_frame()
        now = self._clock()
        cpu_s = max(0.0, self._cpu_clock() - self._cpu0)
        t0 = self._t0
        self._t0 = None
        if not self._dispatches and self._sync_kind is None \
                and self._tokens == 0:
            # idle iteration: don't record, don't advance _last_end — the
            # whole quiet stretch becomes the next real step's idle_gap
            return None
        idle_gap = max(0.0, t0 - self._last_end)
        self._last_end = now
        wall = max(1e-9, now - t0)
        # the sum identity: segments tile the loop body exactly; clamp the
        # residual into "other" against float drift
        tracked = sum(self._segments.values())
        if tracked < wall:
            self._segments["other"] = (self._segments.get("other", 0.0)
                                       + (wall - tracked))
        if self._sync_kind is not None:
            phase = self._sync_kind
        elif "chunk" in self._dispatches:
            phase = "chunk"
        elif self._dispatches:
            phase = "dispatch"
        else:
            phase = "admit"
        # two clocks: a segment's CPU can read a tick over its wall, and a
        # compile re-attributed by note_stolen took its wall but not its
        # CPU out of the segment it ran under
        segments_cpu = {name: min(self._segments_cpu.get(name, 0.0), seconds)
                        for name, seconds in self._segments.items()}
        self._seq += 1
        rec = StepRecord(self._seq, t0, wall, idle_gap, phase,
                         dict(self._segments), segments_cpu,
                         min(cpu_s, wall))
        rec.active_slots = int(active_slots)
        rec.inflight = int(inflight)
        rec.inflight_prefill = int(inflight_prefill)
        rec.depth_now = int(depth_now)
        rec.queue_depth = int(queue_depth)
        rec.tokens = self._tokens
        rec.page_writes = self._page_writes
        rec.window_pages = self._window_pages
        rec.dry_sync = self._dry_sync
        rec.block_steps = self._block_steps
        rec.dispatches = dict(self._dispatches)
        rec.slowest_request_id = self._slowest
        with self.between("step_close"):
            self._finish(rec)
            if closing is not None:
                closing(rec)
        return rec

    # -- sentinel + publication -----------------------------------------------
    def _finish(self, rec: StepRecord) -> None:
        with self._lock:
            baseline = self._baselines.get(rec.phase)
            if baseline is None:
                baseline = self._baselines[rec.phase] = _PhaseBaseline()
            limit = None
            if baseline.samples >= self.min_samples:
                limit = baseline.threshold(self.straggler_k)
                if limit is not None and rec.wall_s > limit:
                    rec.straggler = True
                    rec.cause = rec.dominant_segment()
                    rec.baseline_s = baseline.ewma
                    self.stragglers_total += 1
                    self._stragglers.append(rec.summary())
            if rec.straggler:
                # bounded influence: clamp to the threshold, skip the band
                baseline.ewma = ((1.0 - self.baseline_alpha) * baseline.ewma
                                 + self.baseline_alpha * limit)
                baseline.samples += 1
            else:
                baseline.update(rec.wall_s, self.baseline_alpha)
            self._ring.append(rec)
            self.steps_total += 1
        # metrics outside the lock: one histogram sample per non-zero
        # segment, exemplar'd with the step's cost-driver request so a bad
        # Grafana bucket deep-links into /debug/requests/{id}
        exemplar = ({"request_id": str(rec.slowest_request_id)}
                    if rec.slowest_request_id is not None else None)
        for segment, seconds in rec.segments.items():
            if seconds > 0.0:
                self._obs.hist("app_tpu_step_seconds", seconds,
                               exemplar=exemplar, phase=rec.phase,
                               segment=segment)
        if rec.straggler:
            self._obs.counter("app_tpu_step_stragglers_total",
                              cause=rec.cause or "other")
            if self.logger is not None:
                try:
                    self.logger.warnf(
                        "step straggler: step %d (%s) took %.1f ms vs "
                        "%.1f ms baseline; dominant segment %s",
                        rec.seq, rec.phase, rec.wall_s * 1e3,
                        (rec.baseline_s or 0.0) * 1e3, rec.cause)
                except Exception:  # noqa: BLE001
                    pass

    # -- operator surface -----------------------------------------------------
    def records(self, recent: int = 64) -> List[StepRecord]:
        """The newest `recent` StepRecords, oldest first. Records are
        immutable after _finish, so handing out the refs is safe — the
        timeline exporter (tpu/timeline.py) needs `started_at`, which
        summary() omits (it is a monotonic stamp, meaningless to a
        human reading /debug/steps)."""
        with self._lock:
            return list(self._ring)[-max(1, int(recent)):]

    def snapshot(self, recent: int = 64) -> Dict[str, Any]:
        """The /debug/steps payload: recent ring (newest first), per-phase
        segment totals over the whole ring, live baselines, stragglers."""
        with self._lock:
            ring = list(self._ring)
            baselines = {phase: b.describe()
                         for phase, b in self._baselines.items()}
            stragglers = list(self._stragglers)
            steps_total = self.steps_total
            stragglers_total = self.stragglers_total
        summary: Dict[str, Dict[str, Any]] = {}
        for rec in ring:
            agg = summary.setdefault(rec.phase, {
                "steps": 0, "wall_s": 0.0, "cpu_s": 0.0, "tokens": 0,
                "idle_gap_s": 0.0, "dry_syncs": 0, "block_steps": 0,
                "segments": {}, "segments_cpu": {}})
            agg["steps"] += 1
            agg["dry_syncs"] += rec.dry_sync
            agg["block_steps"] += rec.block_steps
            agg["wall_s"] += rec.wall_s
            agg["cpu_s"] += rec.cpu_s
            agg["tokens"] += rec.tokens
            agg["idle_gap_s"] += rec.idle_gap_s
            for segment, seconds in rec.segments.items():
                agg["segments"][segment] = (agg["segments"].get(segment, 0.0)
                                            + seconds)
                agg["segments_cpu"][segment] = (
                    agg["segments_cpu"].get(segment, 0.0)
                    + rec.segments_cpu.get(segment, 0.0))
        for agg in summary.values():
            agg["mean_wall_s"] = round(agg["wall_s"] / agg["steps"], 6)
            agg["wall_s"] = round(agg["wall_s"], 6)
            agg["cpu_s"] = round(agg["cpu_s"], 6)
            agg["idle_gap_s"] = round(agg["idle_gap_s"], 6)
            agg["segments"] = {k: round(v, 6)
                               for k, v in sorted(agg["segments"].items(),
                                                  key=lambda kv: -kv[1])}
            agg["segments_cpu"] = {k: round(agg["segments_cpu"][k], 6)
                                   for k in agg["segments"]}
        return {
            "steps_total": steps_total,
            "stragglers_total": stragglers_total,
            "capacity": self.capacity,
            "sentinel": {
                "straggler_k": self.straggler_k,
                "baseline_alpha": self.baseline_alpha,
                "min_samples": self.min_samples,
            },
            "baselines": baselines,
            "summary": summary,
            "stragglers": stragglers,
            "recent": [rec.summary() for rec in
                       reversed(ring[-max(1, int(recent)):])],
        }


def register_step_metrics(metrics) -> None:
    """Register the step-anatomy instruments on a metrics Manager
    (idempotent — TPUClient.register_metrics also registers them)."""
    try:
        if metrics.get("app_tpu_step_seconds") is None:
            metrics.new_histogram(
                "app_tpu_step_seconds",
                "engine step time by phase and attributed segment",
                STEP_SECONDS_BUCKETS)
    except Exception:  # noqa: BLE001 - already registered
        pass
    try:
        if metrics.get("app_tpu_step_stragglers_total") is None:
            metrics.new_counter(
                "app_tpu_step_stragglers_total",
                "engine steps flagged slower than the rolling per-phase "
                "baseline, by dominant-segment cause")
    except Exception:  # noqa: BLE001
        pass


def install_routes(app, ledger: StepLedger,
                   path: str = "/debug/steps") -> None:
    """Register GET /debug/steps on a gofr_tpu App (the flight-recorder /
    engine-snapshot install_routes idiom)."""

    @app.get(path)
    def debug_steps(ctx):  # noqa: ANN001
        try:
            recent = int(ctx.request.param("recent") or 64)
        except (TypeError, ValueError):
            recent = 64
        return ledger.snapshot(recent=recent)
