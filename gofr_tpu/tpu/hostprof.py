"""Always-on host sampling profiler: which Python frames eat the loop.

The step ledger (tpu/stepledger.py) measures HOW MUCH host time each
engine iteration burns — ``loop_host_share`` in bench artifacts, the
``host_prep``/``demux``/``emit`` segments on /debug/steps — but nothing
attributes that time to CODE: when host overhead blows the step budget,
no surface says which frames the loop was sitting in. This module closes
that gap with a stdlib-only sampling profiler, cheap enough to leave on
in production:

  * a daemon thread wakes at ``HOSTPROF_HZ`` (default 50 Hz) and walks
    ``sys._current_frames()`` — one bounded dict read plus pure frame
    traversal, no tracing hooks, no interpreter slowdown between samples;
  * each sampled thread is classified via its name and graftlint's
    ownership registry (tpu/ownership.py): a thread named ``llm-engine``
    — or one whose stack contains any ``@loop_only``-marked function —
    is the engine loop; ``llm-finisher`` the finisher; the HTTP
    acceptor/handler threads http; everything else other;
  * per-class collapsed stacks (``root;caller;leaf``) aggregate into a
    bounded dict (``max_stacks`` distinct stacks per class, overflow
    counted, never grown), so memory stays O(configured) forever;
  * the sampler measures ITS OWN cost — the wall time spent inside
    sampling iterations — and reports it in its output, so "is the
    profiler cheap enough" is answered by the profiler
    (acceptance: < 2% of loop wall-clock at the default rate);
  * the sampler notes how LATE each of its own wake-ups came against the
    interval it asked for. A thread that asks for 20 ms and is given the
    processor 2 s later says the host stood still: the machine's stall,
    not the device's. The longest lateness and a short ring of those
    over 100 ms are in the payload, and the engine puts the lateness
    inside a flagged step's wall on its ``step_straggler`` event
    (``host_late_ms``): a stall with the sampler late too is the
    machine's; one with the sampler on time is the device's or the
    runtime's.

Operator surface (install_routes / App.enable_hostprof):

    GET /debug/hostprof  -> per-class top stacks + sample counts +
         measured self-overhead + collapsed text (``?collapsed=1`` for
         the raw flamegraph.pl / speedscope format)

Incident integration: IncidentManager bundles embed
``top_loop_stacks()`` so a 3 a.m. capture answers "what was the engine
loop doing" without a live process to attach to.

The sampler thread itself holds no engine state and calls no
``@loop_only`` function — it only READS foreign frames — so it is clean
under the ownership pass by construction; its stamps are all
``time.monotonic()`` so the clock pass has nothing to flag.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from .obs import MetricsHook
from .ownership import LOOP_ONLY_REGISTRY

DEFAULT_HZ = 50.0
DEFAULT_MAX_STACKS = 256
DEFAULT_TOP_K = 5
MAX_DEPTH = 32
# the duty-cycle governor's ceiling on self_s/wall: when a sample gets
# expensive (many live threads, GIL contention) the sampler stretches its
# interval so the measured share converges below this, half the 2%
# always-on acceptance bound
OVERHEAD_BUDGET = 0.01
# a wake-up later than this against the interval asked for is kept in the
# ring of late wake-ups (a sampler's ordinary jitter is a millisecond)
LATE_RING_S = 0.1
LATE_RING = 32

CLASSES = ("loop", "finisher", "http", "other")


class HostProfiler:
    """Bounded collapsed-stack sampler over ``sys._current_frames()``.

    start()/stop() follow the MemorySampler idiom (tpu/utilization.py):
    a daemon thread parked on an Event, stopped via App.on_shutdown.
    ``snapshot()`` is safe from any thread; aggregation state is guarded
    by one short lock the sampler holds only while folding a sample."""

    def __init__(self, hz: float = DEFAULT_HZ,
                 max_stacks: int = DEFAULT_MAX_STACKS,
                 top_k: int = DEFAULT_TOP_K, max_depth: int = MAX_DEPTH,
                 overhead_budget: float = OVERHEAD_BUDGET,
                 metrics=None, logger=None):
        self.hz = max(0.1, float(hz))
        self.interval_s = 1.0 / self.hz
        self.overhead_budget = max(1e-4, float(overhead_budget))
        self.max_stacks = max(8, int(max_stacks))
        self.top_k = max(1, int(top_k))
        self.max_depth = max(4, int(max_depth))
        self._obs = MetricsHook(metrics, logger=logger)
        self.logger = logger
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        # class -> {collapsed stack -> samples}, bounded per class
        self._stacks: Dict[str, Dict[str, int]] = {c: {} for c in CLASSES}
        self._class_samples: Dict[str, int] = {c: 0 for c in CLASSES}
        self._dropped: Dict[str, int] = {c: 0 for c in CLASSES}
        self.samples_total = 0
        self._self_s = 0.0
        self._cost_ema = 0.0      # EMA of per-sample cost, feeds the governor
        self._throttled = 0       # intervals the governor stretched
        self._interval_eff = self.interval_s
        self._started_mono: Optional[float] = None
        # how late the sampler's own wake-ups came: when the sleep in
        # progress is due to end, the longest lateness so far, and the
        # (woke at, seconds late) of those over LATE_RING_S
        self._due_at: Optional[float] = None
        self._late_max_s = 0.0
        self._late: "collections.deque" = collections.deque(maxlen=LATE_RING)

    def use_metrics(self, metrics) -> None:
        if metrics is not None:
            self._obs = MetricsHook(metrics, logger=self.logger)

    # -- lifecycle ------------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._started_mono = time.monotonic()
        self._thread = threading.Thread(target=self._run,
                                        name="hostprof-sampler",
                                        daemon=True)
        self._thread.start()

    def stop(self, timeout_s: float = 2.0) -> None:
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout_s)
            self._thread = None

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _sleep(self, seconds: float) -> bool:
        """The sampler's sleep; True once stop() was called (a test holds
        the sampler back here)."""
        return self._stop.wait(seconds)

    def _run(self) -> None:
        while True:
            wait = self._next_interval()
            due = time.monotonic() + wait
            with self._lock:
                self._due_at = due
            stopped = self._sleep(wait)
            self._note_wake(due, time.monotonic())
            if stopped:
                return
            try:
                self.sample_once()
            except Exception as exc:  # noqa: BLE001 - keep sampling
                if self.logger is not None:
                    try:
                        self.logger.debugf("hostprof sample failed: %s",
                                           exc)
                    except Exception:  # noqa: BLE001
                        pass

    def _note_wake(self, due: float, woke: float) -> None:
        late = woke - due
        with self._lock:
            self._due_at = None
            if late > self._late_max_s:
                self._late_max_s = late
            if late >= LATE_RING_S:
                self._late.append((woke, late))

    def late_ms_within(self, t0: float, t1: float) -> float:
        """The sampler's largest lateness, in ms, that overlaps the
        monotonic interval [t0, t1]: of the late wake-ups in the ring,
        each late from when it was due until it woke, and of the sleep
        in progress if it was due before t1 and has not ended. 0.0 when
        the sampler woke on time throughout (or is not running)."""
        now = time.monotonic()
        with self._lock:
            worst = max((late for woke, late in self._late
                         if woke - late <= t1 and woke >= t0), default=0.0)
            if self._due_at is not None and self._due_at <= t1:
                worst = max(worst, now - self._due_at)
        return round(max(0.0, worst) * 1e3, 3)

    def _next_interval(self) -> float:
        """Duty-cycle governor: the sleep that keeps steady-state
        self-overhead at or below the budget even when one sample is
        expensive (many live threads, a contended GIL). At the configured
        hz the duty cycle is cost/interval; when that exceeds the budget,
        stretch the interval so cost/interval == budget."""
        with self._lock:
            cost = self._cost_ema
        wait = self.interval_s
        if cost > 0.0:
            wait = max(wait, cost / self.overhead_budget)
        with self._lock:
            if wait > self.interval_s * 1.01:
                self._throttled += 1
            self._interval_eff = wait
        return wait

    # -- sampling -------------------------------------------------------------
    def _classify(self, name: str, stack: List[str]) -> str:
        if name.startswith("llm-engine"):
            return "loop"
        if name.startswith("llm-finisher"):
            return "finisher"
        if name.startswith(("http-server", "Thread-", "grpc-")):
            return "http"
        # ownership registry fallback: a renamed/embedded engine loop is
        # still recognizable by the @loop_only functions on its stack
        if any(frame in LOOP_ONLY_REGISTRY for frame in stack):
            return "loop"
        return "other"

    def sample_once(self) -> None:
        """One sampling iteration (public so tests can drive the
        aggregation deterministically without the timer thread)."""
        t0 = time.monotonic()
        me = threading.get_ident()
        names = {t.ident: t.name for t in threading.enumerate()}
        frames = sys._current_frames()  # noqa: SLF001 - the documented profiler API
        folded: List[tuple] = []
        for ident, frame in frames.items():
            if ident == me:
                continue  # never profile the profiler
            stack: List[str] = []
            f = frame
            while f is not None and len(stack) < self.max_depth:
                code = f.f_code
                qual = getattr(code, "co_qualname", code.co_name)
                stack.append(f"{f.f_globals.get('__name__', '?')}.{qual}")
                f = f.f_back
            stack.reverse()  # root-first, the collapsed-stack convention
            cls = self._classify(names.get(ident, ""), stack)
            folded.append((cls, ";".join(stack)))
        del frames  # frame refs pin entire stacks; drop them eagerly
        with self._lock:
            for cls, collapsed in folded:
                self._class_samples[cls] += 1
                bucket = self._stacks[cls]
                if collapsed in bucket:
                    bucket[collapsed] += 1
                elif len(bucket) < self.max_stacks:
                    bucket[collapsed] = 1
                else:
                    self._dropped[cls] += 1
            self.samples_total += 1
            dt = time.monotonic() - t0
            self._self_s += dt
            self._cost_ema = (dt if self._cost_ema == 0.0
                              else 0.2 * dt + 0.8 * self._cost_ema)
        self._obs.counter("app_tpu_hostprof_samples_total")

    # -- read-out -------------------------------------------------------------
    def _top_locked(self, cls: str, k: int) -> List[Dict[str, Any]]:
        ranked = sorted(self._stacks[cls].items(), key=lambda kv: -kv[1])
        return [{"stack": stack, "samples": count}
                for stack, count in ranked[:k]]

    def top_loop_stacks(self, k: Optional[int] = None) -> List[Dict[str, Any]]:
        """Top-K loop-thread collapsed stacks (the incident-bundle embed:
        what WAS the engine loop doing)."""
        with self._lock:
            return self._top_locked("loop", k or self.top_k)

    def collapsed(self, per_class: int = 64) -> str:
        """Flamegraph-tool text: one ``class;frame;frame count`` line per
        aggregated stack, heaviest first per class."""
        with self._lock:
            lines = [f"{cls};{entry['stack']} {entry['samples']}"
                     for cls in CLASSES
                     for entry in self._top_locked(cls, per_class)]
        return "\n".join(lines) + ("\n" if lines else "")

    def snapshot(self, top_k: Optional[int] = None) -> Dict[str, Any]:
        """The /debug/hostprof payload: per-class sample counts + top
        stacks, plus the sampler's measured self-overhead — reported by
        the sampler itself so its cost is never a matter of faith."""
        k = top_k or self.top_k
        now = time.monotonic()
        with self._lock:
            wall = (max(1e-9, now - self._started_mono)
                    if self._started_mono is not None else 0.0)
            threads = {cls: {
                "samples": self._class_samples[cls],
                "distinct_stacks": len(self._stacks[cls]),
                "dropped_stacks": self._dropped[cls],
                "top": self._top_locked(cls, k),
            } for cls in CLASSES}
            overhead = {
                "self_s": round(self._self_s, 6),
                "share": (round(self._self_s / wall, 6) if wall else 0.0),
                "budget": self.overhead_budget,
                "interval_s": round(self._interval_eff, 6),
                "throttled": self._throttled,
            }
            # how late the sampler's own wake-ups came (epochs through
            # one anchor read here, like the flight recorder's display)
            anchor = time.time() - now  # lint: clock-ok display of monotonic stamps
            late = {
                "longest_ms": round(self._late_max_s * 1e3, 3),
                "over_ms": LATE_RING_S * 1e3,
                "recent": [{"t": round(anchor + woke, 3),
                            "late_ms": round(seconds * 1e3, 3)}
                           for woke, seconds in self._late],
            }
            samples_total = self.samples_total
        self._obs.gauge("app_tpu_hostprof_overhead_share",
                        overhead["share"])
        return {
            "hz": self.hz,
            "running": self.running,
            "samples_total": samples_total,
            "wall_s": round(wall, 3),
            "max_stacks": self.max_stacks,
            "overhead": overhead,
            "late": late,
            "threads": threads,
        }


def register_hostprof_metrics(metrics) -> None:
    """Idempotent registration (the register_step_metrics idiom)."""
    try:
        if metrics.get("app_tpu_hostprof_samples_total") is None:
            metrics.new_counter(
                "app_tpu_hostprof_samples_total",
                "host sampling-profiler iterations taken")
    except Exception:  # noqa: BLE001 - already registered
        pass
    try:
        if metrics.get("app_tpu_hostprof_overhead_share") is None:
            metrics.new_gauge(
                "app_tpu_hostprof_overhead_share",
                "fraction of wall-clock the sampler spent sampling "
                "(its measured self-overhead)")
    except Exception:  # noqa: BLE001
        pass


def install_routes(app, profiler: HostProfiler,
                   path: str = "/debug/hostprof") -> None:
    """Register GET /debug/hostprof on a gofr_tpu App. ``?collapsed=1``
    returns the raw flamegraph text instead of the JSON snapshot."""
    from ..http.responder import Response

    @app.get(path)
    def debug_hostprof(ctx):  # noqa: ANN001
        if (ctx.request.param("collapsed") or "") in ("1", "true"):
            return Response(
                status=200,
                headers={"Content-Type": "text/plain; charset=utf-8"},
                body=profiler.collapsed().encode())
        try:
            top_k = int(ctx.request.param("top") or 0)
        except (TypeError, ValueError):
            top_k = 0
        return profiler.snapshot(top_k=top_k or None)
