"""Continuous-batching LLM engine, the loop half: queue, admission heap,
pipelined dispatch, sync / demux / emit, failure handling.

`LLMEngine` is the serving loop of the ONE engine, `paging.PagedLLMEngine`,
which is what callers construct: that file holds the device state (page
pools, block tables, per-slot model state) and every compiled program.
This file keeps no cache of its own, compiles no program of its own and
imports no step forward from `models/`; what it needs of the device it
asks through the hooks at the end of the class. Two files, one engine: the
loop below is what the next host-side `perf_opt` rewrites (ROADMAP S2),
and it can be read without the kernels' plumbing beside it.

The TPU-first shape of the problem (SURVEY.md §5 long-context + §7.5):
  - a fixed pool of `n_slots` sequences decodes in lock-step — one compiled
    decode program a table width, static shapes, no per-request recompiles
  - prefills are bucketed by prompt length (powers of two) to bound the
    number of compiled programs, and multiple admissions are fused into ONE
    prefill dispatch ([K, bucket] prompts scattered into K slots, first token
    sampled on device) — admission costs one host→device round-trip, not K
  - the decode program runs a block of steps under lax.scan per dispatch
    (`decode_block_size`, or half of it where a request waits or the
    host keeps the device fed at half blocks: `_decode_block_now`),
    sampling on device each step and returning a [B, M] token block;
    ALL loop state (current tokens, positions, temperatures, rng, the
    pools) stays on device between dispatches
  - dispatches are kept in flight; the host syncs the oldest block while
    the device executes the younger ones, so the host↔device round-trip
    and the Python demux loop are overlapped with device compute. How
    many: as many decode blocks as the host's own turn needs to stay
    ahead of the device, worked out every turn from the turn's length
    against a block's time on the device (`tpu/queuedepth.py`), and never
    more than `pipeline_depth`, where it also stands whenever the loop
    cannot know better. A block queued beyond that is latency for the
    next prompt and nothing else. The depth is a depth of DECODE work: a
    prefill in flight is not a decode block (`_room_for_decode`), so
    admissions passing through the deque never leave the device without
    a block queued behind the one it runs, and a prefill entry is read
    in the loop turn that brings it to the deque's head (`_loop`)
  - requests stream tokens out through per-request queues; new requests are
    admitted into free slots between dispatches (continuous batching)

Safety of speculative decode for freed slots: a freed slot keeps "decoding"
junk inside already-dispatched blocks. Its junk tokens are discarded on sync
(the slot's request identity changed), and its junk KV writes are harmless:
a freed slot's table row is zeros, so they land in the garbage page
(paging.PageAllocator).

The reference's analog is the per-topic subscriber loop + per-request
goroutine bridging (subscriber.go:27-57, handler.go:58-63); here the "broker"
is the admission queue and the "handler" is the decode loop.
"""

from __future__ import annotations

import collections
import functools
import itertools
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence, Set, Tuple

from ..models.llama import LlamaConfig, params_nbytes
from .executor import Executor, next_bucket
from .obs import MetricsHook
from .ownership import loop_only
from . import qos
from .queuedepth import QueueDepth
from .sampling import pack_controls, sample_tokens, temperature_of
from .stepledger import StepLedger
from .utilization import UtilizationLedger


class LookupCount:
    """What the `_<kind>_program` lookups cost (`/debug/engine` ->
    `engine.program_lookup`). A MISS lowered its program or loaded it from
    disk; its seconds are a compile's and are not counted here. The
    seconds and the longest are the warm lookups': host arithmetic over
    shapes, so `longest_ms` stays in the low milliseconds however deep the
    device's queue is. The loop thread and warm-up both look up."""

    def __init__(self):
        self._lock = threading.Lock()
        self.lookups = self.misses = 0
        self.seconds = self.longest = 0.0

    def note(self, seconds: float, missed: bool) -> None:
        with self._lock:
            self.lookups += 1
            if missed:
                self.misses += 1
                return
            self.seconds += seconds
            self.longest = max(self.longest, seconds)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"lookups_total": self.lookups,
                    "misses_total": self.misses,
                    "seconds_total": round(self.seconds, 6),
                    "longest_ms": round(self.longest * 1e3, 3)}


def program_lookup(fn):
    """A `_<kind>_program` method: describing the program's arguments
    (the arrays the engine holds as they are, every other as a
    `jax.ShapeDtypeStruct`) and the Executor.compile lookup that finds the
    (warm) program. It makes no array and issues no device operation
    (graftlint `hotloop` holds that), so it never waits for the device's
    queue. On the loop thread it is the step ledger's `program_lookup`
    segment; elsewhere (warm-up, scoring) the ledger's thread guard makes
    it a plain call. Every one is counted in `self.lookups`."""

    @functools.wraps(fn)
    def lookup(self, *args, **kwargs):
        held = self.executor.cache_size     # a miss adds a program to it
        start = time.monotonic()
        with self.steps.seg("program_lookup"):
            program = fn(self, *args, **kwargs)
        self.lookups.note(time.monotonic() - start,
                          self.executor.cache_size != held)
        return program

    return lookup


class CacheLostError(RuntimeError):
    """A donated-cache program failed after dispatch: the KV cache buffers may
    already be consumed (donation is honored on TPU/GPU), so the engine must
    rebuild device state before serving again."""


class EngineDrainingError(RuntimeError):
    """Submitted while the engine drains for shutdown. status_code is
    duck-typed for the HTTP responder: 503 tells load balancers and SDK
    retry policies to go elsewhere (a bare 500 would not be retried).
    retry_after_s rides along as the Retry-After hint: a draining backend
    is gone for good, so clients should re-resolve immediately."""

    status_code = 503
    retry_after_s = 1.0

    def __init__(self):
        super().__init__("engine draining: not accepting new requests")


class DeviceLostError(RuntimeError):
    """Submitted while the reset-storm breaker is open: the device has
    reset repeatedly inside the storm window and the engine is refusing
    work until a half-open probe proves it sane again. 503 duck-typed
    like the other sheds; retry_after_s carries the breaker's remaining
    cooldown so a well-behaved client backs off exactly that long."""

    status_code = 503

    def __init__(self, retry_after_s: float = 1.0):
        self.retry_after_s = float(retry_after_s)
        super().__init__(
            f"device lost: reset-storm breaker open; retry in "
            f"{self.retry_after_s:.1f}s or on another backend")


class EngineStalledError(RuntimeError):
    """Submitted while the engine loop is stuck inside a device call.

    The failure shape: the device serves normally, then stops answering
    mid-flight — the loop thread blocks forever inside a PJRT sync that no
    Python-level timeout can interrupt. Without this
    shed, every new request queues behind a dispatch that will never
    complete and its client blocks until its own timeout; with it, the
    server answers 503 immediately (the reference's breaker-open posture:
    fail fast toward the load balancer, service/circuit_breaker.go
    analog) while /health reports the engine DEGRADED with the stall age."""

    status_code = 503
    retry_after_s = 15.0

    def __init__(self, stall_s: float):
        super().__init__(
            f"engine loop stuck in a device call for {stall_s:.0f}s "
            f"(device not answering); shedding new requests")

_request_ids = itertools.count(1)


class GenerationRequest:
    def __init__(self, prompt_tokens: Sequence[int], max_new_tokens: int = 128,
                 temperature: float = 0.0, stop_tokens: Optional[Set[int]] = None,
                 span=None, priority: int = 0, min_tokens: int = 0,
                 top_p: float = 0.0, top_k: int = 0,
                 traceparent: Optional[str] = None,
                 qos_class: Optional[str] = None, tenant: str = ""):
        self.id = next(_request_ids)
        # QoS serving plane (tpu/qos.py): canonical class name or None for
        # legacy/unclassified traffic, plus the tenant id for accounting.
        # The class is already folded into `priority` (banded) by submit;
        # it rides here so admission quotas, preemption targeting and
        # per-class goodput can see it without reverse-engineering bands
        self.qos_class = qos_class
        self.tenant = tenant
        # admission priority: LOWER admits first; ties resolve FIFO by id.
        # Purely host-side — it reorders which queued request gets the next
        # free slot, never touching running generations
        self.priority = int(priority)
        # stop_tokens are ignored until this many tokens have been emitted
        # (host-side demux rule; the device never sees stop conditions)
        self.min_tokens = max(0, int(min_tokens))
        self.prompt_tokens = list(prompt_tokens)
        self.max_new_tokens = max_new_tokens
        self.temperature = float(temperature)
        # nucleus / top-k truncation for sampled rows; 0 disables. Honored
        # only by engines built with sampling_controls=True (the [B, 3]
        # row-control plane) — submit() rejects them otherwise
        self.top_p = float(top_p)
        self.top_k = int(top_k)
        self.stop_tokens = stop_tokens or set()
        # the caller's trace span: batch.id/tpu.slot/tpu.prefill_bucket are
        # stamped on it at admission (SURVEY §5 tracing row). For STREAMED
        # responses the HTTP middleware ends this span before admission, so
        # the engine also opens a child "tpu.generate" span (gen_span) that
        # lives from submit to finish and carries the same attributes —
        # exported reliably regardless of when the parent closed.
        self.span = span
        self.gen_span = None
        # raw inbound W3C traceparent (http/middleware stamps it on the
        # Request; servers thread it here) so the flight recorder can
        # parent engine child spans under the caller's trace even when no
        # live span object made it this far (span=None submit paths)
        self.traceparent = traceparent
        self.out_queue: "queue.Queue" = queue.Queue()
        self.cancelled = threading.Event()
        self.error: Optional[BaseException] = None
        # ALL lifecycle stamps are time.monotonic(): queue-wait, TTFT, SLO
        # and step math are interval arithmetic, and an NTP step mid-flight
        # must not corrupt them. Wall-clock appears only where timestamps
        # leave the process (flight-recorder display, synthesized spans —
        # the recorder anchors a wall/monotonic pair per request)
        self.enqueued_at = time.monotonic()
        # first time _admit moved it from the queue into the admission heap
        self.dequeued_at: Optional[float] = None
        # `_admit` took it for this round: a slot, its pages and the
        # admission cap are settled; what follows is the loop's own work
        self.granted_at: Optional[float] = None
        # its prefill program has been enqueued on the device (stamped
        # once the program call has returned)
        self.admitted_at: Optional[float] = None
        # the deque's contents ahead of that program at the enqueue:
        # decode steps (d + 1 a verify) and other prefill programs
        self.ahead_steps: Optional[int] = None
        self.ahead_prefills: Optional[int] = None
        # decode steps its row computed after its last token
        self.overrun_steps: Optional[int] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        self.generated = 0
        # every token already DELIVERED to the client, in order — the
        # replay ledger: after a device reset the request re-admits with
        # prompt + emitted as its prefill window and the remaining budget,
        # so the client's stream pauses instead of failing and no position
        # is ever re-emitted or dropped. len(emitted) == generated always.
        self.emitted: List[int] = []
        # device-reset re-admissions consumed (bounded by the engine's
        # retry_budget; crossing it fails the request instead)
        self.replays = 0
        # QoS preemptions survived (shed-ladder level 2): each one reuses
        # the replay machinery — evacuate the slot, requeue at
        # prompt+emitted — but is counted separately and does NOT consume
        # the crash-recovery retry_budget
        self.preemptions = 0
        # disaggregated serving (tpu/disagg.py): True on requests admitted
        # through submit_handoff — their prefill (and first token) already
        # happened on the prefill pool. handoff_blobs holds the shipped
        # per-page KV (kvtier.PageBlob list) until admission lands it in
        # the pool; None means recompute the resume window (the degraded
        # path for a lost or failed-verification hand-off)
        self.disagg_handoff = False
        self.handoff_blobs = None

    @property
    def resume_tokens(self) -> List[int]:
        """The admission window: prompt + already-delivered tokens. For a
        fresh request this is just the prompt; for a replay-after-reset
        re-admission it is the full context the KV cache must rebuild."""
        if not self.emitted:
            return self.prompt_tokens
        return self.prompt_tokens + self.emitted

    def cancel(self) -> None:
        self.cancelled.set()

    def hit_stop(self, token: int) -> bool:
        """True when `token` ends the generation: a stop token counts only
        once min_tokens have been emitted (generated already includes this
        token at every call site)."""
        return (token in self.stop_tokens
                and self.generated >= self.min_tokens)

    def stream(self, timeout_s: Optional[float] = None) -> Iterator[int]:
        """Yield generated token ids until the engine signals completion.

        timeout_s bounds the wait for EACH token; on expiry the request is
        cancelled (freeing its slot) and TimeoutError raised.

        The engine delivers one queue entry per request per device sync: a
        bare int (single token) or a list of ints (a whole demuxed decode
        block — one put instead of block-size puts), unpacked here in
        order. Entries therefore arrive block-at-a-time; the per-entry
        timeout budget is unchanged because syncs, not tokens, are the
        arrival events."""
        while True:
            try:
                token = self.out_queue.get(timeout=timeout_s)
            except queue.Empty:
                self.cancel()
                raise TimeoutError(
                    f"generation timed out after {timeout_s}s waiting for a token")
            if token is None:
                if self.error is not None:
                    raise self.error
                return
            if type(token) is list:
                yield from token
                continue
            yield token

    def result(self, timeout_s: Optional[float] = None) -> List[int]:
        return list(self.stream(timeout_s=timeout_s))


class _Slot:
    __slots__ = ("request", "length", "remaining", "pages", "more_pages",
                 "chunking", "history")

    def __init__(self):
        self.request: Optional[GenerationRequest] = None
        self.length = 0
        self.remaining = 0
        self.pages: Optional[List[int]] = None  # owned page
        # ids, table order (shared prefix pages first; _finish_slot asks
        # the prefix cache which pages it owns)
        # its pages in the page groups beside the primary one, {group
        # index: page ids in ring-column order} (tpu/paging.py; None for a
        # family of one group)
        self.more_pages = None
        # chunked prefill in progress: the slot is RESERVED (its window
        # is being filled chunk by chunk) but not yet emitting — excluded
        # from the free list and from decode demux until the final chunk
        self.chunking: Optional[GenerationRequest] = None
        # speculative mode only: prompt + emitted tokens, the corpus the
        # prompt-lookup draft proposal searches
        self.history: Optional[List[int]] = None

    @property
    def active(self) -> bool:
        return self.request is not None


class _Finisher:
    """Bounded off-loop worker for terminal-slot teardown.

    _finish_slot on the engine loop is hot-path: every job submitted here
    is the SLOW tail of finishing a request (span export, flight-recorder
    bookkeeping, metric flushes, the client's terminal ``None``) packaged
    as a zero-argument callable with every input precomputed on the loop
    thread — the worker never reads loop-owned state.

    Ordering contract: jobs run FIFO on a single worker thread, and each
    request's job is created AFTER its tokens were enqueued, so a client
    always sees tokens-then-None in order and a returned ``result()``
    implies the recorder already holds the finished record. Backpressure:
    the queue is bounded; when it is full (or the worker died) submit()
    returns False and the caller runs the job inline — jobs are never
    dropped. close() drains everything already queued before returning,
    bounded by its timeout."""

    def __init__(self, maxsize: int):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(maxsize)))
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()

    def submit(self, job) -> bool:
        try:
            self._q.put_nowait(job)
        except queue.Full:
            return False
        if self._thread is None or not self._thread.is_alive():
            with self._lock:
                if self._thread is None or not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._run, name="llm-finisher", daemon=True)
                    self._thread.start()
        return True

    def _run(self) -> None:
        while True:
            job = self._q.get()
            if job is None:  # close() sentinel: queue already drained FIFO
                return
            try:
                job()
            except Exception:  # noqa: BLE001 - terminal teardown is
                pass           # best-effort; never kill the worker

    def close(self, timeout_s: float = 10.0) -> None:
        """Drain queued jobs, then stop the worker. Called with the engine
        loop already joined, so no new submits race the sentinel."""
        thread = self._thread
        if thread is None or not thread.is_alive():
            # worker never started (or died): run the backlog inline
            while True:
                try:
                    job = self._q.get_nowait()
                except queue.Empty:
                    return
                if job is None:
                    continue
                try:
                    job()
                except Exception:  # noqa: BLE001
                    pass
            return
        self._q.put(None)
        thread.join(timeout=timeout_s)


def _pin_standard_layout(*arrays):
    """Constrain arrays to their logical row-major layout (minor dim last).

    XLA's layout assignment is free to reorder physical dims, and for the
    cache einsums it prefers dh minor — which tiles 64 lanes into 128 and
    physically DOUBLES every cache buffer (observed twice in TPU OOM dumps:
    "bf16[16,128,8,64,1024]{3,2,4,1,0}, 2.0x expansion"). Pinning the
    S-minor storage layout at program entry and exit fixes what the program
    receives and returns; it does NOT stop the compiler from carrying a
    buffer in another layout in between and copying at both ends — the
    paged pool's programs avoid that by never giving the compiler a reason
    to (ops/paged_attention's module docstring). No-op on CPU."""
    from jax.experimental.layout import Layout, with_layout_constraint

    out = tuple(with_layout_constraint(a, Layout(tuple(range(a.ndim))))
                for a in arrays)
    return out if len(out) > 1 else out[0]


def _admission_widths(cap: int) -> List[int]:
    """The fused-admission widths K a prefill can dispatch at, descending:
    {cap} + powers of four <= cap. One list for the split below and for
    the warm-ups, so that what is compiled at boot is what admission can
    ask for.

    Powers of four bound the compiled prefill-program variants per prompt
    bucket — with multiple prompt-length buckets the (bucket x K) compile
    product is the boot-time cost that matters. cap (= n_slots) itself is
    always a candidate so a cold full-slot burst still fuses into ONE
    dispatch (measured better on v5e than chunked admission for both TTFT
    and throughput)."""
    widths = {cap}
    k = 1
    while k <= cap:
        widths.add(k)
        k *= 4
    return sorted(widths, reverse=True)


def _admission_split(n: int, cap: int) -> List[int]:
    """Decompose an admission wave of n into descending K-sizes from
    _admission_widths(cap). Steady-state turnover waves are small, so the
    common case is a single small-K dispatch."""
    out: List[int] = []
    for k in _admission_widths(cap):
        while n >= k:
            out.append(k)
            n -= k
    return out


def spec_accept_epilogue(g, logits0, temps, rng, drafts, draft_lens,
                         positions, d: int, top_k: int):
    """Speculative-verify acceptance: sample position 0, accept the greedy
    prefix of matching drafts on greedy-eligible rows, advance loop state.

    g: [B, d+1] device greedy continuations; logits0: [B, V] position-0
    logits; temps: [B] or [B, 3] row controls; drafts/draft_lens: [B, d] /
    [B]. Returns (tokens [B], positions [B], rng, out [B, d+1],
    n_emit [B]): row b emits out[b, :n_emit[b]].
    """
    import jax.numpy as jnp

    B = g.shape[0]
    next0, rng = sample_tokens(logits0, rng, temps, top_k=top_k)
    greedy_row = temperature_of(temps) <= 0.0          # sampling.py rule
    matches = ((drafts == g[:, :d])
               & (jnp.arange(d, dtype=jnp.int32)[None, :]
                  < draft_lens[:, None])
               & greedy_row[:, None])
    prefix = jnp.cumprod(matches.astype(jnp.int32), axis=1)
    accepted = jnp.sum(prefix, axis=1)                 # [B]
    out = g.at[:, 0].set(next0)                        # sampled pos-0
    tokens = out[jnp.arange(B), accepted]
    positions = positions + accepted + 1
    return tokens, positions, rng, out, accepted + 1


class LLMEngine:
    """The loop of the one engine. Constructed only as
    `paging.PagedLLMEngine`, which fills the hooks at the end of this
    class (tools/analysis holds that: rule `oneengine`)."""

    # adaptive-speculation tuning (class attrs so tests can tighten them):
    # EMA smoothing of accepted-per-slot, the floor below which verify
    # dispatches pause, and how many block-decode dispatches a cooloff lasts
    SPEC_EMA_ALPHA = 0.2
    SPEC_MIN_ACCEPT = 0.25
    SPEC_COOLOFF_DISPATCHES = 16
    # probes restart the EMA at 2x the floor: ~4-5 consecutive
    # zero-acceptance verifies before re-cooling, one good one to recover
    SPEC_PROBE_EMA = 0.5

    # submit() sheds (503) once the loop has been stuck inside one device
    # call this long. Must clear any LEGITIMATE in-dispatch pause, and the
    # longest is a program that compiles on the loop thread mid-serve (a
    # table width or a fused-admission width that warm-up left cold). On a
    # v5e with nothing cached the slowest of the 32 programs of a llama1b
    # boot compiled in 4.2 s and its decode programs in 2.9-3.3 s
    # (chip_smoke.py run, PR 21). 60 s clears that many times over.
    # Class attr so deployments and tests can tune it per instance.
    STALL_REJECT_S = 60.0

    def __init__(
        self,
        params,
        cfg: LlamaConfig,
        n_slots: int = 8,
        max_seq_len: Optional[int] = None,
        prefill_buckets: Sequence[int] = (16, 32, 64, 128, 256, 512, 1024),
        top_k: int = 0,
        decode_block_size: int = 16,
        pipeline_depth: int = 4,
        max_prefill_batch: int = 0,
        executor: Optional[Executor] = None,
        metrics=None,
        logger=None,
        seed: int = 0,
        mesh=None,
        budget_bytes: Optional[int] = None,
        tracer=None,
        chunk_prefill_tokens: int = 0,
        speculative_tokens: int = 0,
        sampling_controls: bool = False,
        admission_plane=None,
        flight_recorder=None,
        retry_budget: int = 2,
        reset_storm_max: int = 3,
        reset_storm_window_s: float = 60.0,
        breaker_cooldown_s: float = 5.0,
        faults=None,
        async_d2h: bool = True,
        finisher_queue: int = 256,
        disagg_role: str = "",
        handoff_sink=None,
    ):
        """mesh: optional jax.sharding.Mesh with a "tp" axis. When given, the
        engine serves TENSOR-PARALLEL: params shard per serving_param_specs
        (Megatron column/row split, per-layer collectives compiled by XLA
        onto ICI), the KV cache shards its KV-head axis, and the per-slot
        loop state replicates. The compiled programs are identical Python —
        sharding propagates from the committed inputs (the scaling-book
        recipe), so tp=1 and tp=N run the same code. BASELINE config 5's
        70B TP=8 path is this engine + a tp=8 mesh."""
        import jax
        import jax.numpy as jnp

        from .. import native

        native.available()  # build/load the C++ helpers at boot, not in the
        # serving loop (first pad_batch call must never stall a decode step)
        self.mesh = mesh
        # int8-quantized weight tree (models.llama.quantize_weights): the
        # tree carries companion *_s scale leaves and every matmul routes
        # through the int8 MXU path at trace time — nothing engine-side
        # changes except shard specs and the capacity plan's weight bytes
        self._w8 = isinstance(params, dict) and "lm_head_s" in params
        # sampling_controls widens the per-row sampling state from [B]
        # temperatures to [B, 3] (temperature, top_p, top_k) — per-request
        # nucleus/top-k at the cost of one [B, V] sort per sampled step.
        # Opt-in so lean greedy serving never pays for the sort
        self.sampling_controls = bool(sampling_controls)
        if mesh is not None:
            from ..parallel.sharding import serving_param_specs, shard_params

            tp = mesh.shape.get("tp", 1)
            if cfg.n_kv_heads % tp or cfg.n_heads % tp:
                raise ValueError(
                    f"tp={tp} must divide n_kv_heads={cfg.n_kv_heads} and "
                    f"n_heads={cfg.n_heads} (whole heads per shard)")
            params = shard_params(params, mesh,
                                  serving_param_specs(quantized=self._w8))
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_seq_len = min(max_seq_len or cfg.max_seq_len, cfg.max_seq_len)
        self.prefill_buckets = tuple(b for b in prefill_buckets if b <= self.max_seq_len)
        # HBM budget discipline (VERDICT r2 missing #2): when a budget is
        # known, the capacity plan clamps (n_slots, max_seq_len) so params +
        # pool + prefill transients fit — instead of discovering
        # RESOURCE_EXHAUSTED mid-serve
        self.plan = None
        if budget_bytes is not None and budget_bytes > 0:
            from .capacity import plan_capacity

            from ..models.llama import params_nbytes as _tree_nbytes

            # the plan sums GLOBAL bytes (params_nbytes of a sharded tree
            # and kv_cache_bytes are whole-model numbers), so under a mesh
            # the budget is the whole slice's HBM: per-device bytes_limit x
            # mesh size. Slight over-estimate for replicated leaves
            # (norms, tok_emb, activation temps) — the sharded weight/cache
            # terms dominate by orders of magnitude.
            if mesh is not None:
                budget_bytes *= mesh.size

            self.plan = plan_capacity(cfg, self.n_slots, self.max_seq_len,
                                      budget_bytes,
                                      prefill_buckets=self.prefill_buckets,
                                      params_nbytes=_tree_nbytes(self.params))
            self.n_slots = self.plan.n_slots
            self.max_seq_len = self.plan.max_seq_len
            self.prefill_buckets = self.plan.prefill_buckets
            n_slots = self.n_slots
            if logger is not None:
                (logger.warnf if self.plan.clamped else logger.infof)(
                    "%s", self.plan.summary())
        self.top_k = top_k
        self.decode_block_size = max(1, decode_block_size)
        self.pipeline_depth = max(1, pipeline_depth)
        self.max_prefill_batch = max_prefill_batch
        self.executor = executor or Executor()
        self.metrics = metrics if metrics is not None else self.executor.metrics
        self.logger = logger
        self._seed = seed
        self._reset_counter = itertools.count(seed)

        # what picks a program's code is part of its name: the in-memory
        # compile cache keys on (name, shapes), and an executor shared
        # across engines must not hand one the other's compiled program.
        # "-w8" marks int8-weight trees, "-sc" the widened sampling state:
        # the arg-shape cache key already separates them, but names must
        # too (disk-cache filenames and the "program identity is visible
        # in logs" rule). Every program-name site carries the tag
        self._id_tag = ("-w8" if self._w8 else "") + (
            "-sc" if self.sampling_controls else "")

        # int8 KV pools: halve pool HBM traffic (the decode bandwidth
        # bound) and double context per GiB. Quantize-on-write, dequant
        # folded into the paged kernel's dots. A family with no
        # lower-precision cache has no such field (models/protocol.py)
        kv_dtype = getattr(cfg, "kv_dtype", None)
        if kv_dtype not in (None, "int8", cfg.dtype):
            # a float kv_dtype differing from cfg.dtype would make the
            # capacity plan (which reads kv_dtype) and the allocation
            # (which uses cfg.dtype) disagree — reject until supported
            raise ValueError(f"kv_dtype={kv_dtype!r} not supported; "
                             f"use None or 'int8'")
        self._q8 = kv_dtype == "int8"

        # speculative decoding (prompt-lookup drafting): d > 0 replaces the
        # block-decode dispatch with a VERIFY dispatch scoring each slot's
        # current token + up to d host-proposed draft tokens in one forward.
        # Greedy output is IDENTICAL to plain decode (a draft is accepted
        # only when it equals the model's own choice); wins come from
        # emitting accepted+1 tokens per weight-read on structured text.
        # Verify dispatches cannot be pipelined blind (the next window's
        # start depends on this one's acceptance), so spec mode runs one
        # dispatch at a time.
        self.speculative_tokens = max(0, int(speculative_tokens))
        # bind once at boot: _propose_draft runs per active slot per verify
        # dispatch, so no per-call module lookup on that path
        self._native_propose = (native.propose_draft
                                if native.available() else None)
        # ADAPTIVE speculation: a rolling accepted-tokens-per-slot estimate
        # decides whether the next dispatch is a verify or a plain block
        # decode. Low acceptance (random text) makes verify strictly worse
        # than pipelined block decode — the engine cools off for a stretch
        # of block dispatches, then probes again. Greedy output is
        # identical either way; this only tunes throughput.
        self._spec_accept_ema = float(self.speculative_tokens)  # optimistic
        self._spec_cooloff = 0
        # consecutive verify rounds where NO slot proposed a draft — two in
        # a row triggers cooloff (see _dispatch_verify's zero-draft branch)
        self._spec_no_draft_streak = 0
        if self.speculative_tokens:
            if self._q8:
                raise ValueError("speculative_tokens with kv_dtype='int8' "
                                 "is not supported yet (the verify window "
                                 "needs a dequant cached-attention read)")
            if chunk_prefill_tokens:
                raise ValueError("speculative_tokens with chunked prefill "
                                 "is not supported yet")

        self.slots = [_Slot() for _ in range(n_slots)]
        # priority-ordered admission: entries are (priority, id, request)
        # so equal priorities stay FIFO and requests never compare directly
        self._pending: "queue.PriorityQueue" = queue.PriorityQueue()
        # priority-ordered admission heap: (priority, id, request)
        # entries merged from _pending each loop round; requests parked on
        # free pages stay here — see
        # _admit for the ordering/fairness rules. Loop-thread-only.
        self._admission_heap: List[tuple] = []
        self._wake = threading.Event()
        self._stop = threading.Event()
        # live-traffic multi-host admission (tpu.admission.AdmissionPlane):
        # rank 0 publishes each wave's composition over the coordination
        # KV plane, followers replay it — every rank issues the identical
        # SPMD dispatch sequence without the pre-queued determinism
        # contract. None = single-controller serving, zero overhead.
        self._plane = admission_plane
        if admission_plane is not None:
            admission_plane.stop_event = self._stop
        # drain(): reject new work, let active generations finish
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        # serializes device-state mutation (program dispatch)
        # between the engine loop and boot-time warmup() on the caller thread
        self._state_lock = threading.Lock()
        self._jnp = jnp
        self._obs = MetricsHook(self.metrics, logger=logger)
        # utilization ledger (tpu/utilization.py): always-on roofline
        # accounting — pure host arithmetic, O(1) per dispatch sync, fed
        # from _sync_oldest and the loop's host-time stamps
        self.util = UtilizationLedger(
            cfg, metrics=self.metrics,
            n_devices=mesh.size if mesh is not None else 1,
            params_nbytes=params_nbytes(self.params))
        # resolve the device's peaks at boot: a TPU the peak table does not
        # know raises here, not at the first sync on the loop thread
        self.util.peaks()
        # step anatomy ledger (tpu/stepledger.py): always-on per-iteration
        # wall-clock attribution + straggler sentinel — loop-thread-only
        # accumulation, a handful of monotonic() reads per step
        self.steps = StepLedger(metrics=self.metrics, logger=logger)
        self.executor.on_compile = self._note_compile
        self.tracer = tracer
        # per-request flight recorder (tpu/flightrecorder.py): best-effort
        # like MetricsHook — every hook below is None-guarded and O(1), so
        # serving without a recorder pays one attribute check per site
        self.recorder = flight_recorder
        # fault-injection plane (tpu/faults.py): None in production — every
        # hook site is one attribute check, the zero-overhead contract
        self.faults = faults
        # incident autopsy plane (tpu/incidents.py): None unless
        # App.enable_incident_autopsy wires one — the hooks below (breaker
        # open, quarantine, straggler streak) are one attribute check each
        # and IncidentManager.trigger never blocks the loop (captures run
        # on a daemon thread)
        self.incidents = None
        # host sampling profiler (tpu/hostprof.py): None unless
        # App.enable_hostprof attaches one; a flagged step asks it
        # whether the host stood still, incident bundles for its stacks
        self.hostprof = None
        # QoS serving plane (tpu/qos.py): None unless App.enable_qos wires
        # a QoSController — same zero-overhead contract as the planes
        # above (one attribute check per submit / admission round)
        self.qos = None
        # capacity observatory (tpu/meter.py): None unless
        # App.enable_capacity wires a TPUMeter — same zero-overhead
        # contract. _meter_rows stages one sync's batch rows (loop-thread
        # only) until _finish_step closes the step ledger record whose
        # segment timings the meter apportions
        self.meter = None
        self._meter_rows = None
        # crash-only recovery: replay-after-reset budget + reset-storm
        # breaker (tpu/faults.py). Active requests survive a device reset
        # by re-admitting at prompt+emitted with elevated priority; the
        # breaker sheds submits (503 DeviceLostError) once resets cluster
        from .faults import ResetStormBreaker

        self.retry_budget = max(0, int(retry_budget))
        self.breaker = ResetStormBreaker(max_resets=reset_storm_max,
                                         window_s=reset_storm_window_s,
                                         cooldown_s=breaker_cooldown_s)
        # poison tracking: (request id, consecutive resets) where that
        # request was the SOLE work in flight — two in a row quarantines
        # it rather than letting one bad request reset-loop the engine
        self._sole_reset_id: Optional[int] = None
        self._sole_reset_streak = 0
        # recovery evidence counters (plain ints, loop-thread writes only):
        # the soak/chaos artifacts read these even when metrics is None
        self.resets_total = 0
        self.replays_total = 0
        self.replayed_tokens_total = 0
        self.quarantined_total = 0
        self.preemptions_total = 0
        self._batch_seq = itertools.count(1)
        # chunked prefill (opt-in, 0 = off): prompts in buckets larger than
        # this are admitted as several bounded chunk dispatches, so decode
        # blocks and other admissions interleave instead of stalling behind
        # one huge prefill — the TTFT lever under mixed traffic. The chunk
        # size must divide every bucket it splits (power-of-two sizes do).
        self.chunk_prefill_tokens = max(0, int(chunk_prefill_tokens))
        if self.chunk_prefill_tokens:
            for bucket in self.prefill_buckets:
                if (bucket > self.chunk_prefill_tokens
                        and bucket % self.chunk_prefill_tokens):
                    raise ValueError(
                        f"chunk_prefill_tokens={self.chunk_prefill_tokens} "
                        f"must divide prefill bucket {bucket}")
        self._chunk_jobs: "collections.deque" = collections.deque()

        # disaggregated prefill/decode (tpu/disagg.py): "" = colocated
        # serving (the default, zero overhead on every hot path below),
        # "prefill" = this engine runs prompt ingestion only and EXPORTS
        # each finished prompt's KV to a hand-off sink instead of ever
        # entering decode, "decode" = this engine accepts pre-filled-KV
        # admissions (submit_handoff) and only dispatches a prefill as the
        # lost-hand-off recompute fallback. KV ships page-granular
        # (kvtier.PageBlob).
        self.disagg_role = str(disagg_role or "")
        if self.disagg_role not in ("", "prefill", "decode"):
            raise ValueError(f"disagg_role={disagg_role!r}: "
                             f"use '', 'prefill' or 'decode'")
        if self.disagg_role and admission_plane is not None:
            raise ValueError(
                "disaggregated roles are single-controller only; the "
                "multi-host admission plane cannot mirror hand-offs")
        # prefill role: called on the LOOP thread as sink(request, blobs,
        # n_ctx) right after the first token was emitted; returns True when
        # the hand-off was delivered (False = the sink already arranged the
        # fallback). Set at construction by disagg.PrefillWorker.
        self._handoff_sink = handoff_sink
        # prefill role: a failing request is offered to this hook first
        # (disagg.PrefillWorker wires it); True means the worker took
        # ownership of the stream — fallback recompute on the decode pool
        # — so the engine must NOT set an error or deliver the terminal
        # None (the client's stream continues elsewhere)
        self._handoff_fail = None
        # lifetime hand-off evidence (plain ints, loop-thread writes):
        # /debug/disagg and the soak artifacts read these even when
        # metrics is None
        self.handoffs_total = 0
        self.handoff_fallbacks_total = 0

        # elastic drain-with-migration (fleet/elastic.py): a coordinator
        # requests a one-shot export of every live decode slot. The loop
        # picks it up at a quiesced boundary (no in-flight dispatches),
        # offers each session to the sink as (request, blobs, n_ctx), and
        # evacuates slots the sink took. Sessions the sink refuses keep
        # decoding locally — migration can only improve on the status quo.
        self._migrate_sink = None
        self._migrate_request = False
        self.migrations_total = 0

        # in-flight dispatches awaiting host sync, processed FIFO:
        #   ("decode", out_tokens [B, M] future, [(slot_idx, request)], M,
        #    dispatched at, dispatch span, table width)
        #   ("prefill", first_tokens [K] future, [(slot_idx, request)])
        self._inflight: "collections.deque" = collections.deque()
        # decode blocks read, and those read with slots still decoding and
        # no decode block queued behind them (loop thread writes;
        # /debug/engine reads)
        self.decode_syncs_total = 0
        self.dry_syncs_total = 0
        # how many decode entries the loop keeps queued, of the
        # `pipeline_depth` it may, and whether they are full or half
        # blocks: worked out a turn from the loop's own turn against a
        # step's time on the device (tpu/queuedepth.py)
        self.queue = QueueDepth(self.pipeline_depth,
                                mirrored=admission_plane is not None,
                                block=self.decode_block_size)
        # row-steps the decode blocks and verifies read so far computed
        # (rows of the snapshot x steps), and those of them computed for a
        # row after its request's last token (`_overrun_steps`)
        self.row_steps_total = 0
        self.overrun_steps_total = 0

        # wedge detection: the loop stamps this every iteration; a stamp
        # that stops moving while work is in flight means the thread is
        # stuck inside a device call (stall_seconds / EngineStalledError)
        self._last_step_at = time.monotonic()

        # decode hot-loop host teardown (ISSUE 7): start the D2H copy of
        # dispatch outputs at enqueue time so the sync-side np.asarray is
        # a completion check, and push terminal-slot teardown (span
        # export, record_finished, metric flushes, the client's None)
        # onto a bounded off-loop finisher. finisher_queue=0 keeps the
        # old fully-inline finish path.
        self.async_d2h = bool(async_d2h)
        self._finisher: Optional[_Finisher] = (
            _Finisher(finisher_queue) if finisher_queue > 0 else None)

        # rolling throughput window
        self._tok_window: "collections.deque" = collections.deque()

    def _temps_shape(self, rows: int) -> Tuple[int, ...]:
        """Per-row sampling state, float32: [rows] temperatures, or
        [rows, 3] (temperature, top_p, top_k) under sampling_controls."""
        return (rows, 3) if self.sampling_controls else (rows,)

    def _temps_init(self, rows: int):
        """That state zeroed, on the device."""
        jnp = self._jnp
        return jnp.zeros(self._temps_shape(rows), dtype=jnp.float32)

    # -- public API -----------------------------------------------------------
    @property
    def admission_limit(self) -> int:
        """Longest admissible prompt: the largest prefill bucket, bounded so
        the first decode step's KV write (at position len(prompt)) stays
        inside max_seq_len."""
        bucket_limit = (self.prefill_buckets[-1] if self.prefill_buckets
                        else self.max_seq_len)
        return min(bucket_limit, self.max_seq_len - 1)

    @property
    def stall_seconds(self) -> float:
        """Seconds the loop thread has been stuck inside ONE device call,
        0.0 when healthy. Host-side only — reading it never touches the
        device (a probe that did would hang on the exact failure it is
        meant to detect). An idle engine parks in 50 ms waits, so the stamp
        only stops moving while a dispatch or sync is actually blocked."""
        if self._thread is None or not self._thread.is_alive():
            return 0.0
        return max(0.0, time.monotonic() - self._last_step_at)

    def _stall_over_threshold(self) -> float:
        """THE shed policy, read once: 0.0 when healthy or exempt,
        otherwise the captured stall age (so every consumer — the 503, the
        health report — carries the same measurement that tripped it).

        Multi-controller exemption: loops with an admission plane
        legitimately block inside collectives waiting for peer ranks
        (startup skew, wave sync) for arbitrarily long; host-side stall
        age cannot distinguish that from a dead device, so the shed is
        single-controller only — a genuinely dead device still surfaces
        through the requests' own per-token timeouts."""
        if self._plane is not None:
            return 0.0
        stall = self.stall_seconds
        return stall if stall > self.STALL_REJECT_S else 0.0

    def wedged(self) -> bool:
        return self._stall_over_threshold() > 0.0

    def queue_depth(self) -> int:
        """Requests waiting for a slot — thread-safe; the capacity
        forecaster's backlog input (tpu/meter.py). The loop merges
        _pending into the admission heap every round, so the heap IS
        the backlog most of the time — counting only _pending would
        report ~0 while requests pile up parked on slots or pages."""
        return self._pending.qsize() + len(self._admission_heap)

    def health_check(self):
        """Container health contributor (container.add_health_contributor):
        DEGRADED once the loop stalls past the shed threshold. DEGRADED,
        not DOWN — already-dispatched work could still complete if the
        device recovers, and a load balancer should stop routing here
        either way."""
        from ..container import Health, STATUS_DEGRADED, STATUS_UP

        details = {
            "active_slots": sum(1 for s in self.slots if s.active),
            "queue_depth": self.queue_depth(),
        }
        if self.breaker.blocked():
            # reset storm: DOWN, not DEGRADED — there is no in-flight work
            # that could still complete (the resets failed or requeued it),
            # and the half-open probe, not routed traffic, decides recovery
            details["breaker"] = self.breaker.snapshot()
            from ..container import STATUS_DOWN

            return Health(status=STATUS_DOWN, details=details)
        stall = self._stall_over_threshold()
        if stall:
            details["stall_seconds"] = round(stall, 1)
            return Health(status=STATUS_DEGRADED, details=details)
        return Health(status=STATUS_UP, details=details)

    def submit(self, prompt_tokens: Sequence[int], max_new_tokens: int = 128,
               temperature: float = 0.0,
               stop_tokens: Optional[Set[int]] = None,
               span=None, priority: int = 0,
               min_tokens: int = 0, top_p: float = 0.0,
               top_k: int = 0,
               traceparent: Optional[str] = None,
               qos_class: Optional[str] = None,
               tenant: str = "") -> GenerationRequest:
        """priority: LOWER admits first when slots are contended (ties stay
        FIFO); running generations are never preempted — except batch-class
        requests under the QoS shed ladder, which preempt WITH replay (the
        client stream pauses, nothing is lost). min_tokens: stop tokens
        are ignored until this many tokens have been emitted. top_p/top_k
        truncate the sampled distribution per request (0 = off) — only on
        engines built with sampling_controls=True. traceparent: the
        caller's raw W3C header, for engine child spans when no live span
        object is passed. qos_class: 'interactive'/'standard'/'batch'
        (tpu/qos.py) maps the request onto a priority band and subjects it
        to class quotas/deadlines; None keeps legacy semantics untouched.
        Unknown class strings are rejected with a typed 400, never
        silently defaulted."""
        qos_class = qos.normalize_class(qos_class)
        if self._stop.is_set():
            raise RuntimeError("engine is stopped")
        if self._draining:
            raise EngineDrainingError()
        stall = self._stall_over_threshold()
        if stall:
            if self.recorder is not None:
                self.recorder.record_engine_event("stall_shed",
                                                  stall_s=round(stall, 1))
            raise EngineStalledError(stall)
        retry_after = self.breaker.reject_for()
        if retry_after is not None:
            if self.recorder is not None:
                self.recorder.record_engine_event(
                    "breaker_shed", state=self.breaker.state)
            raise DeviceLostError(retry_after)
        if self._plane is not None and not self._plane.is_leader:
            # multi-controller serving has ONE ingress: rank 0 composes
            # every admission wave; this rank only replays them
            raise RuntimeError(
                "this rank mirrors admission waves from the leader; "
                "submit on process 0")
        if not prompt_tokens:
            raise ValueError("prompt_tokens must be non-empty")
        if (top_p or top_k) and not self.sampling_controls:
            raise ValueError("per-request top_p/top_k need an engine built "
                             "with sampling_controls=True")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        limit = self.admission_limit
        if len(prompt_tokens) > limit:
            raise ValueError(f"prompt of {len(prompt_tokens)} tokens exceeds the "
                             f"admission limit ({limit})")
        if self.qos is not None:
            # shed-ladder door check (level 3 sheds standard with 503 +
            # Retry-After); then fold the class into the admission
            # priority band. Unclassified requests pass through unbanded
            self.qos.check_submit(qos_class, tenant)
            priority = qos.banded_priority(qos_class, priority)
        request = GenerationRequest(prompt_tokens, max_new_tokens, temperature,
                                    stop_tokens, span=span, priority=priority,
                                    min_tokens=min_tokens, top_p=top_p,
                                    top_k=top_k, traceparent=traceparent,
                                    qos_class=qos_class, tenant=tenant)
        if self.tracer is not None:
            request.gen_span = self.tracer.start_span(
                "tpu.generate", parent=span, traceparent=traceparent)
            request.gen_span.set_attribute("tpu.prompt_tokens",
                                           len(request.prompt_tokens))
        if self.recorder is not None:  # after gen_span: it carries the
            self.recorder.record_enqueued(request)  # inbound trace ctx
        self._obs.counter("app_tpu_requests_total")
        if self.qos is not None:
            self.qos.note_submitted(request)
        if self.meter is not None:
            # admission-door arrival stamp (tpu/meter.py): feeds the
            # forecaster's λ window — thread-safe, best-effort
            self.meter.note_arrival(request)
        self._pending.put((request.priority, request.id, request))
        if self._stop.is_set():
            # stop() may have drained _pending between the check above and
            # the put; drain again so this request cannot strand its client
            self._drain_pending(RuntimeError("engine stopped"))
            raise RuntimeError("engine is stopped")
        self._obs.gauge("app_tpu_queue_depth", self.queue_depth())
        self._wake.set()
        return request

    def generate(self, prompt_tokens: Sequence[int], **kw) -> List[int]:
        return self.submit(prompt_tokens, **kw).result()

    def submit_handoff(self, prompt_tokens: Sequence[int],
                       emitted: Sequence[int], *,
                       max_new_tokens: int = 128, temperature: float = 0.0,
                       stop_tokens: Optional[Set[int]] = None,
                       priority: int = 0, min_tokens: int = 0,
                       top_p: float = 0.0, top_k: int = 0,
                       traceparent: Optional[str] = None,
                       out_queue=None, cancelled=None,
                       blobs=None, qos_class: Optional[str] = None,
                       tenant: str = "") -> GenerationRequest:
        """Admit a generation whose prefill (and first token) already ran
        on another engine — the decode half of disaggregated serving
        (tpu/disagg.py), built on the replay-after-reset contract: the
        request admits at ``prompt + emitted`` with its REMAINING budget
        and nothing already delivered is ever re-emitted.

        blobs (one kvtier.PageBlob per full-or-partial prompt page; not on
        a prefill-role engine) short-circuits the prefill recompute:
        admission validates each blob against this pool's shape/dtype,
        lands the KV with the donated H2D scatter under the ``kv_handoff``
        step segment, and the slot binds straight into decode. blobs=None
        is the degraded path — a normal prefill of the resume window
        (exactly a replay), used when a hand-off was lost, corrupt, or
        failed shape verification.

        out_queue: the client-facing token queue (the prefill-side
        request's), shared so the stream continues seamlessly across the
        hop. cancelled: the prefill-side request's cancellation event, so
        a client cancel reaches whichever pool currently owns the slot.
        traceparent keeps both pools' spans on one trace."""
        if self._stop.is_set():
            raise RuntimeError("engine is stopped")
        if self._draining:
            raise EngineDrainingError()
        stall = self._stall_over_threshold()
        if stall:
            if self.recorder is not None:
                self.recorder.record_engine_event("stall_shed",
                                                  stall_s=round(stall, 1))
            raise EngineStalledError(stall)
        retry_after = self.breaker.reject_for()
        if retry_after is not None:
            if self.recorder is not None:
                self.recorder.record_engine_event(
                    "breaker_shed", state=self.breaker.state)
            raise DeviceLostError(retry_after)
        if not prompt_tokens:
            raise ValueError("prompt_tokens must be non-empty")
        if blobs is not None and not self._lands_handoffs:
            raise ValueError("KV blobs cannot land on a prefill-role "
                             "engine")
        if (top_p or top_k) and not self.sampling_controls:
            raise ValueError("per-request top_p/top_k need an engine built "
                             "with sampling_controls=True")
        emitted = list(emitted)
        if max_new_tokens - len(emitted) <= 0:
            raise ValueError("hand-off carries no remaining budget; the "
                             "prefill pool should have finished it")
        if len(prompt_tokens) + len(emitted) > self.admission_limit:
            raise ValueError(
                f"resume window of {len(prompt_tokens) + len(emitted)} "
                f"tokens exceeds the admission limit "
                f"({self.admission_limit})")
        # hand-offs outrank queued fresh arrivals (LOWER admits first,
        # clients are clamped >= 0), mirroring replay: the prompt's
        # prefill was already paid for and its client is mid-stream
        # qos_class/tenant ride through for accounting only — no
        # re-banding: the prefill side already applied class banding and
        # a hand-off outranks everything regardless (its client is
        # mid-stream, same rule as replay)
        request = GenerationRequest(prompt_tokens, max_new_tokens,
                                    temperature, stop_tokens,
                                    priority=min(int(priority), -1),
                                    min_tokens=min_tokens, top_p=top_p,
                                    top_k=top_k, traceparent=traceparent,
                                    qos_class=qos.normalize_class(qos_class),
                                    tenant=tenant)
        request.disagg_handoff = True
        request.handoff_blobs = blobs
        request.generated = len(emitted)
        request.emitted = emitted
        if emitted:
            # the client saw its first token on the PREFILL pool; stamping
            # here keeps TTFT single-counted and anchors this record's
            # decode-side TPOT at hand-off receipt
            request.first_token_at = request.enqueued_at
        if out_queue is not None:
            request.out_queue = out_queue
        if cancelled is not None:
            request.cancelled = cancelled
        if self.tracer is not None:
            request.gen_span = self.tracer.start_span(
                "tpu.generate", traceparent=traceparent)
            request.gen_span.set_attribute("tpu.prompt_tokens",
                                           len(request.prompt_tokens))
            request.gen_span.set_attribute("disagg.handoff", True)
        if self.recorder is not None:  # after gen_span: trace continuity
            self.recorder.record_enqueued(request)
            self.recorder.record_event(
                request.id, "handoff_received",
                pages=len(blobs) if blobs else 0,
                resume_tokens=len(request.resume_tokens))
        self._pending.put((request.priority, request.id, request))
        if self._stop.is_set():
            self._drain_pending(RuntimeError("engine stopped"))
            raise RuntimeError("engine is stopped")
        self._obs.gauge("app_tpu_queue_depth", self.queue_depth())
        self._wake.set()
        return request

    def score(self, prompt_tokens: Sequence[int],
              completion_tokens: Sequence[int], top: int = 5):
        """Teacher-forced per-token logprobs for a completion (the OpenAI
        `logprobs` feature): returns (chosen_lp [C], top_ids [C, top],
        top_lps [C, top]) numpy arrays. Additive post-hoc pass — see
        tpu/score.py for why this reproduces decode-time distributions
        exactly without touching the serving hot path."""
        from .score import score_tokens

        self._llama_only("score")
        return score_tokens(self, prompt_tokens, completion_tokens, top=top)

    def embed(self, tokens: Sequence[int], normalize: bool = True):
        """Last-position final-norm hidden state as a sequence embedding
        (float32 [D], L2-normalized by default) — backs /v1/embeddings.
        Additive post-hoc pass like score(); see tpu/score.py."""
        from .score import embed_tokens

        self._llama_only("embed")
        return embed_tokens(self, tokens, normalize=normalize)

    def _llama_only(self, what: str) -> None:
        """tpu/score.py's post-hoc passes are models/llama.py's no-cache
        forward: another family is refused by name."""
        if not isinstance(self.cfg, LlamaConfig):
            raise ValueError(f"{what}() is models/llama.py's forward; "
                             f"{type(self.cfg).__name__} has none yet")

    def warmup_scoring(self, embeddings: bool = True) -> int:
        """Pre-compile the logprobs/embeddings program families (one
        window program per cache bucket, covering every client top value)
        so the first client request never pays a compile under its
        deadline. Opt-in at boot — the serving warmup() stays lean for
        deployments that never score."""
        from .score import warmup_post_hoc

        return warmup_post_hoc(self, embeddings=embeddings)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._draining = False  # a drained engine may be restarted
        self._thread = threading.Thread(target=self._loop, name="llm-engine", daemon=True)
        self._thread.start()

    # stop() waits this long for the loop thread before declaring it
    # wedged (class attr so tests can tighten it)
    STOP_JOIN_S = 30.0

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=self.STOP_JOIN_S)
            if thread.is_alive():
                # the loop is stuck inside a device call but STILL OWNS the
                # loop-thread-only state (slots, admission heap, chunk
                # jobs): draining here would race its own teardown when the
                # device finally answers, double-completing requests. Leave
                # everything to the live loop and shout — the stop flag is
                # set, so it exits (and fails its requests) the moment the
                # wedged call returns.
                if self.logger is not None:
                    self.logger.errorf(
                        "engine loop thread failed to exit within %.0fs "
                        "(stuck in a device call); leaving teardown to the "
                        "live loop", self.STOP_JOIN_S)
                return
            self._thread = None
        if self._plane is not None:
            # leader: publish the stop sentinel AFTER the loop exits (no
            # further waves can race it) so parked followers unblock
            self._plane.close()
        self._drain_pending(RuntimeError("engine stopped"))
        if self._finisher is not None:
            # the loop is joined, so its shutdown-tail finish jobs are all
            # queued: drain them before returning so callers observe every
            # terminal None / recorder record once stop() completes. (The
            # wedged-loop branch above returns EARLY and leaves the
            # finisher running for the still-live loop.)
            self._finisher.close()

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown, phase 1: stop admitting, fail queued requests
        fast (their clients should retry elsewhere), and let ACTIVE
        generations run to completion, bounded by timeout_s.

        Returns True when every active request finished; False on timeout
        (call stop() either way — it fails whatever remains). The serving
        analog of connection draining on a deregistering backend.

        Only sets the flag and waits: the LOOP thread fails the queued
        requests (its _admit drains them when _draining is set), so queue
        and allocator state are mutated by exactly one thread — calling
        _drain_pending here would race _admit's own pop loop."""
        self._draining = True
        self._wake.set()
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            # under _state_lock: an admission wave mid-flight holds the lock
            # between popping _pending and binding slots — an unlocked poll
            # could observe that window as "idle" and green-light stop()
            # while a just-admitted request is about to bind
            with self._state_lock:
                busy = (any(s.active or s.chunking is not None
                            for s in self.slots)
                        or self._inflight or self._chunk_jobs
                        or self._admission_heap or self._pending.qsize())
            if not busy:
                return True
            time.sleep(0.05)
        return False

    @property
    def _lands_handoffs(self) -> bool:
        """True when this engine restores shipped KV page blobs at
        admission: any role but the prefill disagg role. Decode pools land
        disagg hand-offs; ANY colocated replica lands elastic migration
        exports."""
        return self.disagg_role != "prefill"

    def request_migration(self, sink) -> None:
        """Ask the loop to export every live decode session to ``sink``
        (elastic drain-with-migration, fleet/elastic.py). Thread-safe;
        returns immediately. The loop stops feeding the pipeline and
        waits for in-flight dispatches to sync (at most `pipeline_depth`
        decode blocks, one a loop turn, each with the prefill entries
        behind it), then calls
        ``sink(request, blobs, n_ctx)`` once per active slot at the
        quiesced boundary: True means the sink took ownership of the
        stream (the slot evacuates, nothing further is emitted locally);
        False/raise leaves the slot bound and decoding locally. One-shot:
        slots admitted after the export round are NOT offered — callers
        drain admission first (registry ``draining`` state + engine
        drain()) so nothing new lands mid-migration."""
        if self._plane is not None:
            raise RuntimeError("migration is single-controller only; the "
                               "multi-host admission plane cannot mirror "
                               "slot evacuations")
        self._migrate_sink = sink
        self._migrate_request = True
        self._wake.set()

    @property
    def migration_pending(self) -> bool:
        """True while a requested export round has not yet run — the
        drain coordinator polls this to know the sink is settled."""
        return self._migrate_request

    @loop_only
    def _migrate_active_slots(self) -> None:
        """One migration round at a quiesced step boundary (loop thread,
        under _state_lock, nothing in flight). Export order is slot order;
        each session the sink takes is evacuated with the preemption
        primitive — the request object (and its client stream) lives on,
        owned by the sink.

        _migrate_request clears at the END of the round (the D2H pulls
        take real time): migration_pending is the coordinator's signal
        that every sink call has happened, so clearing it on entry would
        let the poller read a half-built export list."""
        sink, self._migrate_sink = self._migrate_sink, None
        if sink is None:
            self._migrate_request = False
            return
        try:
            for slot in self.slots:
                if not slot.active or slot.chunking is not None:
                    continue
                request = slot.request
                if self._is_cancelled(request):
                    continue  # normal cancel teardown handles it
                if request.max_new_tokens - request.generated <= 0:
                    continue  # finishing this step; migrating buys nothing
                blobs, n_ctx = self._export_slot_kv(slot, request)
                try:
                    took = bool(sink(request, blobs, n_ctx))
                except Exception as exc:  # noqa: BLE001 - a broken sink must not kill serving
                    if self.logger is not None:
                        self.logger.errorf("migration sink failed for %s: %s",
                                           request.id, exc)
                    took = False
                if not took:
                    continue  # slot stays bound: local decode is the floor
                self._release_slot_for_preempt(slot)
                request.finished_at = time.monotonic()
                self.migrations_total += 1
                self._obs.counter("app_tpu_elastic_migrations_total",
                                  phase="export")
                if request.gen_span is not None:
                    request.gen_span.set_attribute("elastic.migrated", True)
                    request.gen_span.set_attribute(
                        "elastic.pages", len(blobs) if blobs else 0)
                    request.gen_span.end()
                    request.gen_span = None
                if self.recorder is not None:
                    self.recorder.record_event(
                        request.id, "migrated",
                        pages=len(blobs) if blobs else 0,
                        emitted=len(request.emitted))
                    self.recorder.record_finished(request, "migrated")
        finally:
            self._migrate_request = False
        self._obs.gauge("app_tpu_active_slots",
                        sum(1 for s in self.slots if s.active))

    # -- compiled programs ----------------------------------------------------

    def _advance_chunk_job(self) -> None:
        """Dispatch ONE chunk of the oldest job; decode dispatches fill the
        pipeline between calls, which is the whole point."""
        if not self._chunk_jobs:
            return
        job = self._chunk_jobs[0]
        if all(self._is_cancelled(r) for r in job["batch"]):
            self._abort_chunk_job(job, None)
            self._chunk_jobs.popleft()
            return
        final = self._dispatch_chunk(job)
        if final:
            self._chunk_jobs.popleft()
            self._finish_chunk_job(job)

    def _finish_chunk_job(self, job) -> None:
        for slot_idx in job["slots_idx"]:
            self.slots[slot_idx].chunking = None
        with self.steps.seg("bind"):
            batch_id = next(self._batch_seq)
            dspan = self._dispatch_span(
                "tpu.prefill", batch_id,
                **{"batch.size": len(job["batch"]),
                   "tpu.prefill_bucket": job["bucket"], "tpu.chunked": True})
            self._bind_slots(job["slots_idx"], job["batch"], job["first_tok"],
                             job["bucket"], batch_id, dspan)

    def _abort_chunk_job(self, job, exc: Optional[BaseException]) -> None:
        for slot_idx in job["slots_idx"]:
            self.slots[slot_idx].chunking = None
        for request in job["batch"]:
            self._fail_request(request, exc)

    # -- speculative decoding (prompt-lookup drafting) ------------------------
    def _propose_draft(self, history: List[int]) -> List[int]:
        """Prompt-lookup draft: find the most recent earlier occurrence of
        the sequence's last bigram and propose the tokens that followed it.
        O(len(history)) host work per slot per dispatch, once per active
        slot at serving dispatch rates — the native scan (gn_propose_draft)
        keeps it out of the interpreter; pure Python is the fallback. Empty
        when the sequence has no self-match (the verify then degrades to an
        ordinary one-token step for that slot)."""
        d = self.speculative_tokens
        if self._native_propose is not None:
            return self._native_propose(history, d)
        n = 2
        if len(history) < n + 1:
            return []
        tail = history[-n:]
        for i in range(len(history) - n - 1, -1, -1):
            if history[i:i + n] == tail:
                return history[i + n: i + n + d]
        return []

    def _dispatch_verify(self) -> None:
        import numpy as np

        jnp = self._jnp
        d = self.speculative_tokens
        drafts = np.zeros((self.n_slots, d), dtype=np.int32)
        lens = np.zeros((self.n_slots,), dtype=np.int32)
        snapshot = []
        with self.steps.seg("host_prep"):
            for i, slot in enumerate(self.slots):
                if not slot.active:
                    continue
                # greedy rows only (acceptance is exact-match against
                # argmax); a temperature row rides the dispatch as a plain
                # 1-token step. Eligibility travels with the snapshot so
                # the sync-side acceptance EMA divides by rows that COULD
                # accept — a batch half full of temperature traffic must
                # not read as 50% rejection and cool speculation off for
                # the greedy half
                eligible = bool(slot.request.temperature <= 0.0
                                and slot.history and slot.remaining > 0)
                snapshot.append((i, slot.request, eligible))
                if eligible:
                    cont = self._propose_draft(slot.history)
                    if cont:
                        drafts[i, :len(cont)] = cont
                        lens[i] = len(cont)
        if lens.sum() == 0:
            # nothing to verify (all-temperature batch, or the proposer
            # found no continuations): a verify dispatch would be a plain
            # unpipelined decode step — strictly worse than a block decode.
            # Zero drafts is zero ACCEPTANCE signal (the EMA is untouched)
            # but a structural one: two draftless rounds in a row cool
            # speculation off so block decodes pipeline again instead of
            # being dispatched one at a time from this branch
            self._spec_no_draft_streak += 1
            if self._spec_no_draft_streak >= 2:
                self._spec_cooloff = self.SPEC_COOLOFF_DISPATCHES
            self._dispatch_decode()
            return
        self._spec_no_draft_streak = 0
        self.steps.note_dispatch("verify")
        start = time.monotonic()
        try:
            with self.steps.seg("dispatch"):
                if self.faults is not None:
                    self.faults.hit("engine.verify")
                out_tokens, n_emit = self._verify_call(jnp.asarray(drafts),
                                                       jnp.asarray(lens))
        except Exception as exc:
            raise CacheLostError(f"verify dispatch failed: {exc}") from exc
        self._start_d2h(out_tokens, n_emit)
        self._obs.counter("app_tpu_spec_drafted_total", float(lens.sum()))
        dspan = self._dispatch_span("tpu.verify", next(self._batch_seq),
                                    **{"batch.size": len(snapshot),
                                       "tpu.draft_tokens": int(lens.sum())})
        # same arity/dspan position as decode entries: _reset_device_state
        # closes dspans by fixed index for non-prefill entries
        self._inflight.append(("verify", (out_tokens, n_emit), snapshot,
                               d, start, dspan))

    # -- engine loop ----------------------------------------------------------
    def _loop(self) -> None:
        while not self._stop.is_set():
            self._last_step_at = time.monotonic()
            try:
                steps = self.steps
                steps.step_start()
                host_t0 = time.monotonic()
                if self.breaker.probe_due():
                    self._breaker_probe()
                # the wait for the lock is a segment of its own; it ends
                # inside the `with` (which graftlint's lock-order pass
                # reads), on the line after the lock is ours
                lock_wait = steps.seg("lock_wait")
                lock_wait.__enter__()
                with self._state_lock:
                    lock_wait.__exit__(None, None, None)
                    if self.qos is not None and self._plane is None:
                        # act on the QoS shed ladder BEFORE admission so
                        # slots freed by a preemption admit this round.
                        # Single-controller only: under an AdmissionPlane
                        # a local preemption would fork the wave replay
                        with steps.seg("qos"):
                            self._qos_actuate()
                    with steps.seg("admission"):
                        self._admit()
                    # one chunk per iteration: decode dispatches below and
                    # the next iteration's admissions interleave with a
                    # long prompt's remaining chunks
                    self._advance_chunk_job()
                    if self._migrate_request and not self._inflight \
                            and not self._chunk_jobs:
                        # quiesced: every dispatch synced, so slot.length
                        # and resume_tokens agree — export is exact
                        with steps.seg("kv_handoff"):
                            self._migrate_active_slots()
                    any_active = any(slot.active for slot in self.slots)
                    if any_active and self._migrate_request:
                        # a migration round is pending: stop feeding the
                        # pipeline so in-flight work drains to the
                        # quiesced boundary within pipeline_depth turns
                        # (a decode block a turn, its prefills with it)
                        any_active = False
                    if any_active and self.disagg_role == "prefill":
                        # slots on a prefill pool evacuate at prefill
                        # sync (_handoff_slot), so decode steps pipelined
                        # behind a pending prefill would demux to nothing
                        # — pure garbage dispatches stealing device time
                        # from the next prompt. Dispatch decode ONLY for
                        # a slot with no prefill in flight: the last-
                        # resort case where a failed export kept the slot
                        # bound and this pool decodes it locally
                        pending = {i for e in self._inflight
                                   if e[0] == "prefill" for i, _ in e[2]}
                        any_active = any(
                            slot.active and i not in pending
                            for i, slot in enumerate(self.slots))
                    if self.speculative_tokens and self._spec_cooloff <= 0:
                        # one verify at a time (the next window's start
                        # depends on this one's acceptance), and NOT until
                        # in-flight cooloff decodes drain — a verify
                        # dispatched over unsynced decodes would propose
                        # drafts from host state that lags the device
                        if any_active and not any(
                                e[0] in ("verify", "decode")
                                for e in self._inflight):
                            self._dispatch_verify()
                    elif any_active:
                        self.queue.turn(self._request_waits())
                        while self._room_for_decode():
                            self._dispatch_decode()
                            if self._spec_cooloff > 0:
                                self._spec_cooloff -= 1
                                if self._spec_cooloff == 0:
                                    # probe window: a few bad verifies
                                    # before re-cooling, one good enough
                                    # to keep going
                                    self._spec_accept_ema = max(
                                        self._spec_accept_ema,
                                        self.SPEC_PROBE_EMA)
                                    break
                # scheduler/prep/enqueue time this iteration (the state-lock
                # block never blocks on the device — syncs happen below).
                # Sub-millisecond idle iterations are noise, not overhead
                host_s = time.monotonic() - host_t0
                if host_s >= 1e-3:
                    self.util.note_host(host_s)
                synced = False
                if self._inflight:
                    with steps.seg("emit"):
                        self._sync_oldest()
                    synced = True
                # close the step BEFORE any idle park below: the wait time
                # belongs to the NEXT step's idle_gap, not this step's wall
                self._finish_step()
                # prefill entries now at the head ran right behind what
                # was just read (6-13 ms each, done before its emit was):
                # read them in this turn, up to the next decode or verify
                # entry, so their first tokens leave now and the deque
                # holds decode blocks again. A record a sync, as before.
                # Decided by the deque's own contents, so every rank of an
                # admission plane reads the same entries in the same turn
                while synced and self._inflight \
                        and self._inflight[0][0] == "prefill":
                    steps.step_start()
                    with steps.seg("emit"):
                        self._sync_oldest()
                    self._finish_step()
                if not synced and not self._chunk_jobs \
                        and not self._inflight:
                    self.queue.note_park()
                    with steps.between("park"):
                        self._wake.wait(timeout=0.05)
                    self._wake.clear()
            except Exception as exc:  # noqa: BLE001 - fail active requests, keep serving
                # a step that died mid-flight must not feed the baselines
                self.steps.step_abort()
                if self.logger is not None:
                    self.logger.errorf("engine step failed: %s", exc)
                self._reset_device_state(exc)
        # graceful shutdown: finish what was already dispatched, then fail
        # requests still mid-generation so no client blocks on result()
        while self._inflight:
            try:
                self._sync_oldest()
            except Exception as exc:  # noqa: BLE001
                self._reset_device_state(exc)
        stop_exc = RuntimeError("engine stopped")
        while self._chunk_jobs:  # mid-prefill requests must not block clients
            self._abort_chunk_job(self._chunk_jobs.popleft(), stop_exc)
        for slot in self.slots:
            if slot.active:
                slot.request.error = stop_exc
                self._finish_slot(slot)

    @loop_only
    def _note_compile(self, name: str, seconds: float) -> None:
        """Executor cache-miss callback: re-attribute compile time out of
        whatever step segment it elapsed under (tpu/stepledger.py). A
        foreign-thread compile (warmup, scoring) is ignored by the ledger's
        thread guard."""
        self.steps.note_stolen("compile", seconds)

    @loop_only
    def _finish_step(self) -> None:
        """Close the step ledger's iteration record and surface a flagged
        straggler as a flight-recorder engine event carrying the dominant
        segment as the cause — the metrics→trace→request drill's anchor."""
        inflight = len(self._inflight)
        self.steps.step_end(
            active_slots=sum(1 for s in self.slots if s.active),
            inflight=inflight,
            inflight_prefill=inflight - self._decode_inflight(),
            depth_now=self.queue.depth_now,
            queue_depth=self.queue_depth(),
            closing=self._step_closed)
        self._meter_rows = None     # a dropped iteration's rows go with it

    @loop_only
    def _step_closed(self, rec) -> None:
        """`step_end`'s hooks on the record it just published, inside its
        `loop/step_close` span."""
        self.queue.note_record(rec)
        staged = self._meter_rows
        if self.meter is not None and staged is not None:
            # attribution happens HERE, not at the sync site: the step
            # ledger record just closed, so the meter apportions the
            # step's measured device segments — conservation against
            # /debug/steps is exact by construction (tpu/meter.py)
            phase, rows, queued = staged
            self.meter.account_step(rec, phase, rows, queued)
        if rec.straggler:
            # did the host itself stand still? The sampler thread's
            # lateness inside this step's wall (tpu/hostprof.py): late too
            # = the machine's stall; on time = the device's or the
            # runtime's. None without a sampler
            host_late_ms = (self.hostprof.late_ms_within(
                rec.started_at, rec.started_at + rec.wall_s)
                if self.hostprof is not None else None)
            if self.recorder is not None:
                self.recorder.record_engine_event(
                    "step_straggler", step=rec.seq, phase=rec.phase,
                    wall_s=round(rec.wall_s, 6), cause=rec.cause,
                    baseline_s=round(rec.baseline_s or 0.0, 6),
                    request_id=rec.slowest_request_id,
                    host_late_ms=host_late_ms)
            if self.incidents is not None:
                # a streak of flagged steps (not one) escalates to an
                # incident; the manager does the streak accounting
                self.incidents.note_straggler(
                    step=rec.seq, phase=rec.phase, cause=rec.cause,
                    wall_s=round(rec.wall_s, 6),
                    request_id=rec.slowest_request_id,
                    host_late_ms=host_late_ms)

    def _breaker_probe(self) -> None:
        """The reset-storm breaker's half-open probe: ONE tiny device
        round-trip decides whether the storm is over. Success closes the
        breaker (admission resumes, parked/replayed requests dispatch);
        failure re-opens it for another cooldown. Runs on the loop thread
        so a wedged probe shows up as a stall, never a new thread leak."""
        try:
            if self.faults is not None:
                self.faults.hit("engine.probe")
            float(self._jnp.asarray(1.0) + 1.0)
        except Exception as exc:  # noqa: BLE001 - device still sick
            self.breaker.probe_failed()
            self._obs.gauge("app_tpu_breaker_state", self.breaker.state_code)
            if self.recorder is not None:
                self.recorder.record_engine_event("breaker_probe_failed",
                                                  error=str(exc))
            if self.logger is not None:
                self.logger.errorf("breaker half-open probe failed: %s", exc)
        else:
            if self.breaker.probe_ok():
                self._obs.gauge("app_tpu_breaker_state",
                                self.breaker.state_code)
                if self.recorder is not None:
                    self.recorder.record_engine_event("breaker_closed")
                if self.logger is not None:
                    self.logger.warnf(
                        "breaker closed: device answered the half-open "
                        "probe; resuming admission")
                self._wake.set()

    def _admit(self) -> None:
        """Fuse pending requests into batched prefill dispatches, one per
        (bucket, K) group.

        max_prefill_batch (0 = unlimited) can cap admission per loop
        round; on this hardware one fused all-slots prefill measured better
        on BOTH TTFT and throughput than chunked admission (chunks queue
        behind interleaved decode blocks), so unlimited is the default.
        With chunk_prefill_tokens set, buckets larger than the chunk size
        go through the chunk-job path instead of one fused dispatch."""
        if self._draining and self._plane is None:
            # drain() already failed the queue; anything racing in after
            # that must not start generating on a server that is going away
            # (multi-controller: the drain must ride a wave instead — the
            # heap clear has to land on every rank at the same iteration)
            self._drain_pending(EngineDrainingError())
            return
        if self._plane is None and self.breaker.blocked():
            # breaker open/half-open: nothing admits (queued and replayed
            # requests stay parked) until the probe closes it — new device
            # work mid-storm would just feed the storm
            return
        free = [i for i, slot in enumerate(self.slots)
                if not slot.active and slot.chunking is None]
        if not free and self._plane is None:
            return
        # multi-controller: the wave exchange must run even with zero free
        # slots — cancels and the drain flag ride waves, and a saturated
        # server is exactly where cancellation must still free capacity
        # ONE priority-ordered admission heap: arrivals from _pending merge
        # with requests parked earlier on free pages.
        # Heap order (priority, id) means a later higher-priority request
        # pops BEFORE a parked lower-priority one (no head-of-line
        # inversion), while same-priority requests stay strictly FIFO —
        # pop-until-first-not-ready then stop, so newer same-priority
        # requests can never leapfrog a parked one and starve it of the
        # resource it is waiting for.
        import heapq

        drained: List[tuple] = []
        while True:
            try:
                drained.append(self._pending.get_nowait())
            except queue.Empty:
                break
        if self._plane is not None:
            if self._draining and drained:
                # a draining leader's local arrivals never enter a wave
                exc = EngineDrainingError()
                for _, _, request in drained:
                    self._fail_request(request, exc)
                drained = []
            # one wave per iteration: the leader freezes this iteration's
            # arrivals (+ cancels + the drain flag) and publishes;
            # followers block for the same wave. has_work must be computed
            # from MIRRORED state only — it decides whether a wave exists
            # at all, so every rank must agree — and it means work that
            # can DISPATCH this iteration: active/chunking slots, programs
            # in flight, and heap-parked requests that now have a free
            # slot (admitting those dispatches an SPMD prefill, so a wave
            # must pace it or followers would still be parked in the KV
            # wait when the collective needs them). A parked request with
            # NO free slot doesn't count: counting it would flood empty
            # waves at loop speed with no collective backpressure bounding
            # the leader's lead over a stalled follower, and nothing can
            # unpark it except a slot freeing (a dispatching iteration) or
            # the composition change the next wave delivers.
            has_work = (any(s.active or s.chunking is not None
                            for s in self.slots)
                        or bool(self._inflight) or bool(self._chunk_jobs)
                        or (bool(self._admission_heap) and bool(free)))
            try:
                drained, drain_synced = self._plane.exchange(
                    drained, has_work, draining=self._draining)
            except Exception as exc:
                # the popped arrivals are in no queue, no heap, no slot —
                # fail them here or their clients block forever (the
                # loop's reset path only fails ACTIVE slots)
                for _, _, request in drained:
                    self._fail_request(request, exc)
                raise
            if self._plane.closed and not self._plane.is_leader:
                # the leader published its stop sentinel: no collective
                # this rank dispatches can ever complete again. Stop at
                # THIS iteration — fail actives loudly, never hang the
                # slice on a half-membership psum.
                self._stop.set()
                raise RuntimeError(
                    "admission leader stopped; follower cannot make "
                    "progress without its collective peer")
            if drain_synced:
                # the drain lands on every rank at THIS wave: parked heap
                # entries fail here, symmetrically, and nothing admits
                self._draining = True
                self._drain_pending(EngineDrainingError())
                return
        if drained:
            # the loop has picked these up: from here a request waits for
            # a slot, pages or the admission cap, no longer for the loop
            now = time.monotonic()
            for entry in drained:
                request = entry[2]
                if request.dequeued_at is None:    # a replay keeps its first
                    request.dequeued_at = now
                    if self.recorder is not None:
                        self.recorder.record_dequeued(request)
                heapq.heappush(self._admission_heap, entry)
        if not free:
            return  # saturated: entries stay parked for the next free slot
        cap = min(len(free), self.max_prefill_batch or len(free))
        taken: List[GenerationRequest] = []
        while self._admission_heap and len(taken) < cap:
            entry = heapq.heappop(self._admission_heap)
            request = entry[2]
            if self._is_cancelled(request):
                self._abort_admission(request)
                self._fail_request(request)
                continue
            if self.qos is not None and self._plane is None:
                # class gates (tpu/qos.py): deadline expiry fails the
                # request before it ever costs a prefill; quota/ladder
                # parks obey the heap's no-leapfrog rule — the entry
                # goes back and the round stops, exactly like a page
                # wait, so admission order stays strict within a band
                decision = self.qos.admission_decision(request, self,
                                                       taken=len(taken))
                if decision == "expire":
                    self._abort_admission(request)
                    self.qos.note_expired(request)
                    if self.recorder is not None:
                        self.recorder.record_event(
                            request.id, "qos_expired",
                            waited_s=round(time.monotonic()
                                           - request.enqueued_at, 2))
                    self._fail_request(request, qos.QoSDeadlineError(
                        qos.effective_class(request),
                        time.monotonic() - request.enqueued_at,
                        self.qos.deadlines.get(
                            qos.effective_class(request), 0.0)))
                    continue
                if decision == "park":
                    heapq.heappush(self._admission_heap, entry)
                    break
            if not self._admission_ready(request):
                heapq.heappush(self._admission_heap, entry)  # stays parked
                break
            # granted: from here to `admitted_at` the request waits for
            # nothing but this round's own prep, lookup and enqueue
            request.granted_at = time.monotonic()
            taken.append(request)
        if not taken:
            return

        # disaggregated decode pool: hand-off arrivals bypass the prefill
        # bucket path entirely — their shipped KV lands under kv_handoff
        # and the slot binds straight into decode (tpu/disagg.py). A
        # fallback inside _admit_handoff re-parks the request blob-less,
        # so the next round admits it below as a normal recompute.
        handed: List[GenerationRequest] = []
        if self._lands_handoffs:
            handed = [r for r in taken if r.handoff_blobs is not None]
            if handed:
                taken = [r for r in taken if r.handoff_blobs is None]

        if self.qos is not None:
            for request in itertools.chain(taken, handed):
                self.qos.note_admitted(request)

        # group by admission bucket (the prefix cache may
        # shrink a request's window to its un-cached tail), then split
        # counts into powers of two
        by_bucket: Dict[int, List[GenerationRequest]] = {}
        for request in taken:
            bucket = self._admission_bucket(request)
            by_bucket.setdefault(bucket, []).append(request)

        free_iter = iter(free)
        dispatched: Set[int] = set()
        try:
            if handed:
                self._admit_handoff(handed, free_iter, dispatched)
            for bucket, group in by_bucket.items():
                offset = 0
                for K in _admission_split(len(group), self.n_slots):
                    batch = group[offset:offset + K]
                    offset += K
                    slots_idx = [next(free_iter) for _ in batch]
                    try:
                        if (self.chunk_prefill_tokens
                                and bucket > self.chunk_prefill_tokens):
                            self._start_chunk_job(bucket, slots_idx, batch)
                        else:
                            self._dispatch_prefill(bucket, slots_idx, batch)
                    except CacheLostError:
                        raise  # device state suspect: caller must reset
                    except Exception as exc:  # noqa: BLE001
                        # host-side prep failed BEFORE any device dispatch
                        # (slot assignment happens after the program call, so
                        # the slots stay free): fail only this wave and keep
                        # serving — a numpy error must not nuke every active
                        # request (VERDICT r2 weak #5)
                        if self.logger is not None:
                            self.logger.errorf(
                                "prefill wave of %d failed pre-dispatch: %s",
                                len(batch), exc)
                        for request in batch:
                            self._abort_admission(request)
                            self._fail_request(request, exc)
                        continue
                    dispatched.update(r.id for r in batch)
        except Exception as exc:
            # fail requests that never reached a dispatch (dispatched ones
            # hold slots and are failed by the caller's device-state reset)
            for request in itertools.chain(taken, handed):
                if request.id not in dispatched:
                    self._abort_admission(request)
                    self._fail_request(request, exc)
            raise

        self._obs.gauge("app_tpu_queue_depth", self.queue_depth())
        self._obs.gauge("app_tpu_active_slots",
                        sum(1 for s in self.slots if s.active))

    def _admission_bucket(self, request: GenerationRequest) -> int:
        """The prefill bucket this request admits under: resume_tokens so a
        replay-after-reset re-admission prefills prompt + already-delivered
        tokens (identical to the prompt for fresh requests). On a prefix
        hit paging.py narrows it to the un-cached TAIL's bucket."""
        return next_bucket(len(request.resume_tokens), self.prefill_buckets)

    def _prep_admission(self, bucket: int, batch: List[GenerationRequest]):
        """Host-side admission arrays: (ptokens [K, bucket], lengths [K],
        temperatures [K]). Windows are resume_tokens — replayed requests
        rebuild their full context."""
        import numpy as np

        from .. import native

        K = len(batch)
        windows = [r.resume_tokens for r in batch]
        ptokens = native.pad_batch(windows, bucket)
        if ptokens is None:  # no C++ toolchain: numpy fallback
            ptokens = np.zeros((K, bucket), dtype=np.int32)
            for row, window in enumerate(windows):
                ptokens[row, :len(window)] = window
        lengths = np.asarray([len(w) for w in windows], dtype=np.int32)
        if self.sampling_controls:
            new_temps = pack_controls(
                [r.temperature for r in batch],
                [r.top_p for r in batch],
                [r.top_k for r in batch])
        else:
            new_temps = np.asarray([r.temperature for r in batch],
                                   dtype=np.float32)
        return ptokens, lengths, new_temps

    def _dispatch_span(self, name: str, batch_id: int, **attrs):
        """Span covering one device dispatch (ends at its host sync)."""
        if self.tracer is None:
            return None
        span = self.tracer.start_span(name)
        span.set_attribute("batch.id", batch_id)
        for key, value in attrs.items():
            span.set_attribute(key, value)
        return span

    def _bind_slots(self, slots_idx: List[int],
                    batch: List[GenerationRequest], first,
                    bucket: int, batch_id: int, dspan=None) -> None:
        """Post-dispatch slot bookkeeping.

        Stamps the trace correlation on each request's span: batch.id (the
        fused dispatch this request rode in), tpu.slot, tpu.prefill_bucket.
        """
        self._start_d2h(first)  # covers every prefill path (fused,
        # prefix, chunk final) — they all bind through here
        admitted = []
        now = time.monotonic()
        # what this wave's program was enqueued behind: the deque's own
        # contents (mirrored state under an admission plane)
        ahead_steps, ahead_prefills = self._queued_ahead()
        for row, request in enumerate(batch):
            request.ahead_steps = ahead_steps
            request.ahead_prefills = ahead_prefills
            if request.admitted_at is None:  # chunk jobs stamped at chunk 1
                request.admitted_at = now
                self._obs.hist("app_tpu_queue_wait_seconds",
                               now - request.enqueued_at)
            slot = self.slots[slots_idx[row]]
            slot.request = request
            # length counts tokens whose KV is in the cache (the admission
            # window — prompt, plus delivered tokens on a replay); the
            # first sampled token is written at `length` by the next decode
            slot.length = len(request.resume_tokens)
            # budget counts EMISSIONS, so a replayed request resumes with
            # what it has left, never a fresh allowance (generated == 0 for
            # fresh requests: identical to max_new_tokens - 1)
            slot.remaining = request.max_new_tokens - request.generated - 1
            if self.speculative_tokens and self._spec_cooloff > 0:
                # fresh traffic probes immediately: the cold streak that
                # engaged this cooloff belonged to DIFFERENT requests, and
                # at block sizes x remaining-cooloff a short request could
                # otherwise complete without speculation ever being tried
                self._spec_cooloff = 0
                self._spec_accept_ema = max(self._spec_accept_ema,
                                            self.SPEC_PROBE_EMA)
            for span in (request.span, request.gen_span):
                if span is not None:
                    span.set_attribute("batch.id", batch_id)
                    span.set_attribute("tpu.slot", slots_idx[row])
                    span.set_attribute("tpu.prefill_bucket", bucket)
            if self.recorder is not None:
                self.recorder.record_admitted(request, slots_idx[row],
                                              bucket, batch_id=batch_id)
            admitted.append((slots_idx[row], request))
        # the trailing timestamp is the dispatch-enqueue time the
        # utilization ledger unions into the device-busy window at sync
        # (monotonic, like every util/step stamp)
        self._inflight.append(("prefill", first, admitted, dspan,
                               time.monotonic()))

    def _decode_inflight(self) -> int:
        """Decode blocks and verifies in flight: the deque less its
        prefill entries."""
        return sum(1 for e in self._inflight if e[0] != "prefill")

    def _queued_ahead(self) -> Tuple[int, int]:
        """(decode steps, prefill programs) the deque holds: what a
        program enqueued now runs behind, but for the part of the oldest
        entry the device has already done. A decode entry's fourth field
        is its block, a verify's its d drafts (d + 1 positions)."""
        steps = prefills = 0
        for entry in self._inflight:
            if entry[0] == "prefill":
                prefills += 1
            else:
                steps += entry[3] + (entry[0] == "verify")
        return steps, prefills

    def _overrun_steps(self, slot_idx: int, request: "GenerationRequest",
                       unread: int) -> None:
        """Decode steps the row `slot_idx` computes for `request` after
        its last token, noted on the request as it finishes: `unread`
        steps of the entry being read, and every step of the decode
        blocks already queued whose snapshot holds it (the module
        docstring's junk decoding of a freed slot)."""
        held = (slot_idx, request)
        steps = unread + sum(e[3] for e in self._inflight
                             if e[0] == "decode" and held in e[2])
        request.overrun_steps = steps
        self.overrun_steps_total += steps

    def _room_for_decode(self) -> bool:
        """Whether the loop's top-up dispatches one more decode block:
        while the deque holds fewer decode entries than this turn's depth
        (`self.queue.depth_now`, worked out once a turn in `_loop` from
        the host's turn against the time on the device of the block the
        turn dispatches: tpu/queuedepth.py). `pipeline_depth` is the cap
        of that depth and, as ever, of the deque's entries of both kinds,
        but for one thing:
        a prefill in flight is not a decode block. Under the entries' cap
        alone every admission took a block's place, and a closed loop
        that admits two or three requests a turn and reads one entry a
        turn ended with a deque of prefill entries and an idle device
        (PERF.md, PR 30); so whatever the deque holds of prefills, a turn
        leaves one decode block queued BEHIND the one the device may be
        running (a depth-1 engine stays synchronous: one).

        At the cap the first clause follows from the second, which is the
        rule as it was: so it stays, to the letter, under an admission
        plane, where the depth IS the cap because only mirrored state
        (the deque's entry kinds) may choose a program and a rank's
        clock is not; before the loop has both estimates; and after the
        queue ran dry. Below the cap the first clause decides: a prompt
        admitted this turn is enqueued behind one decode block the
        device has just started, not behind three it has not."""
        decode = self._decode_inflight()
        return (decode < self.queue.depth_now
                and (len(self._inflight) < self.pipeline_depth
                     or decode < min(2, self.pipeline_depth)))

    def _request_waits(self) -> bool:
        """Whether a request waits to be admitted: parked on the
        admission heap or still in the submit queue."""
        # multi-controller: _pending is leader-local (a submit racing in
        # after this iteration's wave is invisible to followers), so only
        # the mirrored heap may influence the block size — a rank-local
        # block choice would dispatch mismatched SPMD programs
        return bool(self._admission_heap or (self._plane is None
                                             and self._pending.qsize()))

    def _decode_block_now(self) -> int:
        """The block the decode entry being dispatched runs, counted on
        `engine.queue` under why (the rule: tpu/queuedepth.py). Half of
        `decode_block_size` while a request waits, so that the read it
        waits behind comes sooner, chosen by what waits at the dispatch:
        a closed loop whose clients always wait runs half blocks
        throughout. `_admit` has usually just drained the queue, so an
        open loop under its knee would never see one; there the half
        block comes from the turn's estimates, whenever the host keeps
        the device fed at half blocks with room under `pipeline_depth`,
        so a prompt's pickup waits out the half block that was running
        when it arrived and its prefill the half block queued behind
        (PERF.md, `pickup_wait_p95_ms`, `prefill_ahead_steps_mean`); each
        block pays a flush. `decode_block_size` otherwise: without the
        estimates, and under an admission plane unless the mirrored heap
        holds a request. Warm-up compiles these two blocks and no
        other."""
        return self.queue.dispatched(self._request_waits())

    def _start_d2h(self, *outputs) -> None:
        """Kick off the device->host transfer of dispatch OUTPUTS at
        enqueue time (jax.Array.copy_to_host_async): the copy overlaps the
        other in-flight dispatches, so _sync_oldest's np.asarray becomes a
        completion check instead of a transfer. Pure optimization —
        best-effort and correctness-free: outputs without the API (test
        stubs, plain numpy) and backends that reject the call are skipped
        silently, and np.asarray at sync time stays the source of truth."""
        if not self.async_d2h:
            return
        for out in outputs:
            fn = getattr(out, "copy_to_host_async", None)
            if fn is None:
                continue
            try:
                fn()
            except Exception:  # noqa: BLE001 - overlap is optional
                pass

    @loop_only
    def _fetch_host(self, *arrays) -> List[Any]:
        """Blocking device->host fetch that still overlaps the transfers
        with each other: start EVERY copy async first (the KV spill path
        pulls k/v[/scale] page slices together), then materialize. The
        np.asarray is the completion check, same contract as
        _sync_oldest."""
        import numpy as np

        self._start_d2h(*arrays)
        return [np.asarray(a) for a in arrays]

    def _exemplar_of(self, request) -> Dict[str, str]:
        """Histogram exemplar labels for a request: the deep-link payload
        carried into OpenMetrics exposition (request id resolves via
        /debug/requests/{id}; trace id via the configured trace backend)."""
        ex = {"request_id": str(request.id)}
        span = request.gen_span or request.span
        trace_id = getattr(span, "trace_id", None)
        if trace_id:
            ex["trace_id"] = trace_id
        return ex

    def _sync_oldest(self) -> None:
        import numpy as np

        # a read's wait starts here: the sync-site fault's delay is the
        # device's (or the transport's) lateness, as the ledger has it
        sync_t0 = time.monotonic()
        with self.steps.seg("device_sync"):
            if self.faults is not None:
                # sync-site chaos: latency (delay rules) or a simulated PJRT
                # failure (raise rules) at the host sync point
                self.faults.hit("engine.sync")
        entry = self._inflight.popleft()
        if entry[0] == "prefill":
            _, first, admitted, dspan, dispatched_at = entry
            try:
                with self.steps.seg("device_sync"):
                    first_host = np.asarray(first)  # blocks until the device got there
            except Exception as exc:
                if dspan is not None:
                    dspan.set_status(False, str(exc))
                    dspan.end()
                raise CacheLostError(f"prefill execution failed: {exc}") from exc
            if dspan is not None:
                dspan.end()
            now = time.monotonic()
            self.util.record_prefill(
                tokens=sum(len(r.resume_tokens) for _, r in admitted),
                dispatched_at=dispatched_at, synced_at=now,
                sync_wait_s=now - sync_t0)
            # the step's cost driver: the widest admission window in the
            # fused dispatch (prefill cost tracks the bucket its longest
            # prompt selected)
            slowest = max(admitted, key=lambda e: len(e[1].resume_tokens),
                          default=(None, None))[1]
            self.steps.note_sync(
                "prefill", tokens=len(admitted),
                slowest_request_id=slowest.id if slowest else None)
            if self.meter is not None:
                # stage the synced batch for _finish_step's attribution:
                # every dispatched row is billed (a cancel between
                # dispatch and sync still consumed the device), and rows
                # awaiting their first token carry their queue wait
                self._meter_rows = (
                    "prefill",
                    [(r, len(r.resume_tokens), len(r.resume_tokens))
                     for _, r in admitted],
                    [(r, dispatched_at - r.enqueued_at)
                     for _, r in admitted if r.first_token_at is None])
            n_first = 0
            for row, (slot_idx, request) in enumerate(admitted):
                slot = self.slots[slot_idx]
                if slot.request is not request:  # cancelled between dispatch+sync
                    continue
                if request.first_token_at is None:
                    # replay re-admissions must not overwrite the stamp or
                    # double-count TTFT: the client saw its first token on
                    # the ORIGINAL admission
                    request.first_token_at = now
                    if self.recorder is not None:
                        self.recorder.record_first_token(request)
                    self._obs.hist("app_tpu_ttft_seconds",
                                   now - request.enqueued_at,
                                   exemplar=self._exemplar_of(request))
                token = int(first_host[row])
                if self.speculative_tokens:
                    # resume_tokens read BEFORE the emit below appends
                    slot.history = list(request.resume_tokens) + [token]
                self._emit_block(request, [token])
                n_first += 1
                if (request.hit_stop(token) or slot.remaining <= 0
                        or self._is_cancelled(request)):
                    self._overrun_steps(slot_idx, request, 0)
                    self._finish_slot(slot)
                elif self.disagg_role == "prefill":
                    # disaggregated prefill pool: the slot never enters
                    # decode — export the finished prompt's KV and hand
                    # the stream to the decode pool (tpu/disagg.py). The
                    # first token above is this pool's whole TTFT job.
                    self._handoff_slot(slot, request)
            if n_first:
                self._obs.counter("app_tpu_tokens_generated_total",
                                  float(n_first))
            return

        if entry[0] == "verify":
            _, fut, snapshot, d, started, dspan = entry
            out_dev, n_emit_dev = fut
            try:
                with self.steps.seg("device_sync"):
                    out_host = np.asarray(out_dev)         # [B, d+1]
                    n_emit_host = np.asarray(n_emit_dev)   # [B]
            except Exception as exc:
                if dspan is not None:
                    dspan.set_status(False, str(exc))
                    dspan.end()
                raise CacheLostError(f"verify execution failed: {exc}") from exc
            if dspan is not None:
                dspan.end()
            synced = time.monotonic()
            elapsed = synced - started
            self.queue.note_break()
            # a verify scores d+1 positions per row; slot lengths are read
            # BEFORE the demux advances them, i.e. the dispatched context
            live = [(i, r) for i, r, _ in snapshot
                    if self.slots[i].request is r]
            self.util.record_decode(
                rows=len(snapshot), steps=d + 1,
                kv_tokens=sum(self.slots[i].length for i, r in live),
                dispatched_at=started, synced_at=synced,
                sync_wait_s=synced - sync_t0)
            # pre-demux deepest context: the lock-step batch's cost driver
            slowest = max(live, key=lambda e: self.slots[e[0]].length,
                          default=(None, None))[1]
            if self.meter is not None:
                # d+1 positions scored per live row; kv context read
                # pre-demux (the lengths this dispatch actually touched)
                self._meter_rows = (
                    "verify",
                    [(r, d + 1, self.slots[i].length) for i, r in live],
                    None)
            self._obs.hist("app_tpu_execute_seconds", elapsed)
            emitted = 0
            n_active = len(live)
            n_eligible = sum(int(e) for i, r, e in snapshot
                             if self.slots[i].request is r)
            with self.steps.seg("demux"):
                lims = [int(n_emit_host[i]) for i, _ in live]
                counts, finishes = self._demux_plan(
                    out_host, [i for i, _ in live], [r for _, r in live],
                    lims)
            # DEVICE-side acceptance: host emission may truncate at stop
            # tokens / budget, which must not read as rejection
            device_accepted = sum(max(0, n - 1) for n in lims)
            self._obs.counter("app_tpu_spec_accepted_total",
                              float(device_accepted))
            for j, (slot_idx, request) in enumerate(live):
                slot = self.slots[slot_idx]
                n = int(counts[j])
                toks = out_host[slot_idx, :n].tolist()
                slot.length += n
                slot.remaining -= n
                if slot.history is not None:
                    slot.history.extend(toks)
                self._emit_block(request, toks)
                emitted += n
                if self.recorder is not None and n:
                    # ONE batched event per request per verify sync (never
                    # per token), recorded before the slot can go terminal
                    self.recorder.record_decode_block(
                        request.id, n, elapsed / n)
                if finishes[j]:
                    self._overrun_steps(slot_idx, request, d + 1 - n)
                    self._finish_slot(slot)
            if emitted:
                self._obs.counter("app_tpu_tokens_generated_total",
                                  float(emitted))
            # every token in this sync shares one dispatch wall time; the
            # per-token cost is elapsed / (avg tokens per active slot)
            self.steps.note_sync(
                "verify", tokens=emitted,
                slowest_request_id=slowest.id if slowest else None)
            if emitted:
                per_slot = emitted / max(1, n_active)
                self._obs.hist_n(
                    "app_tpu_tpot_seconds", elapsed / per_slot, emitted,
                    exemplar=(self._exemplar_of(slowest) if slowest
                              else None))
            self._obs.hist("app_tpu_batch_size", n_active)
            self._track_throughput(emitted)
            self.row_steps_total += len(snapshot) * (d + 1)
            # adaptive speculation: fold this dispatch's accepted-per-
            # GREEDY-ELIGIBLE-slot into the EMA; a cold streak pauses
            # verifies for a stretch of pipelined block decodes (the loop
            # probes again afterwards). Temperature rows can never accept
            # (greedy-only matching) — dividing by ALL active slots would
            # let mixed traffic push pure-greedy requests into cooloff
            # exactly where speculation works (VERDICT r3 weak #3)
            if n_eligible:
                a = self.SPEC_EMA_ALPHA
                self._spec_accept_ema = ((1 - a) * self._spec_accept_ema
                                         + a * device_accepted / n_eligible)
                if self._spec_accept_ema < self.SPEC_MIN_ACCEPT:
                    self._spec_cooloff = self.SPEC_COOLOFF_DISPATCHES
            return

        _, out_tokens, snapshot, block, started, dspan, n_table = entry
        # what the device has to go on with while this block's demux and
        # emit run on the host
        queued_behind = self._decode_inflight()
        try:
            with self.steps.seg("device_sync"):
                tokens_host = np.asarray(out_tokens)  # [B, block]; device sync point
        except Exception as exc:
            if dspan is not None:
                dspan.set_status(False, str(exc))
                dspan.end()
            raise CacheLostError(f"decode execution failed: {exc}") from exc
        if dspan is not None:
            dspan.end()
        # rows past the slots are the family's counters, carried by the
        # same copy (models/protocol.py); none for models/llama.py
        self._note_model_counts(tokens_host, block)
        synced = time.monotonic()
        step_s = (synced - started) / block
        self._obs.hist("app_tpu_execute_seconds", synced - started)
        # slot lengths are pre-demux here: the live context this dispatch
        # actually read each step (the MBU KV term)
        live = [(i, r) for i, r in snapshot if self.slots[i].request is r]
        page_writes = self._note_page_writes(live, block)
        self._note_page_reads(live, block, n_table)
        self.util.record_decode(
            rows=len(snapshot), steps=block,
            kv_tokens=sum(self.slots[i].length for i, r in live),
            dispatched_at=started, synced_at=synced,
            sync_wait_s=synced - sync_t0)
        self.queue.note_read(synced, synced - sync_t0, queued_behind, block)
        # pre-demux deepest context: the lock-step batch's cost driver
        slowest = max(live, key=lambda e: self.slots[e[0]].length,
                      default=(None, None))[1]
        if self.meter is not None:
            # block positions computed per live row regardless of how
            # many tokens the demux later emits (stops truncate emission,
            # not device work); kv context read pre-demux
            self._meter_rows = (
                "decode",
                [(r, block, self.slots[i].length) for i, r in live],
                None)

        n_active = len(live)
        emitted = 0
        # the routing MATH is one numpy pass over [live, block] (its own
        # ledger segment); delivery below is one batched put per request
        with self.steps.seg("demux"):
            counts, finishes = self._demux_plan(
                tokens_host, [i for i, _ in live], [r for _, r in live],
                [block] * n_active)
        for j, (slot_idx, request) in enumerate(live):
            slot = self.slots[slot_idx]
            n = int(counts[j])
            toks = tokens_host[slot_idx, :n].tolist()
            slot.length += n
            slot.remaining -= n
            if slot.history is not None:
                # adaptive spec's cooloff runs block decodes: the draft
                # context must track THESE tokens too, or the next
                # probe's bigram lookup searches a stale history
                slot.history.extend(toks)
            self._emit_block(request, toks)
            emitted += n
            if self.recorder is not None and n:
                # ONE batched event per request per dispatch sync (never
                # per token), recorded before the slot can go terminal
                self.recorder.record_decode_block(request.id, n, step_s)
            if finishes[j]:
                self._overrun_steps(slot_idx, request, block - n)
                self._finish_slot(slot)
        if emitted:
            self._obs.counter("app_tpu_tokens_generated_total",
                              float(emitted))
        # a DRY sync: slots still decode and no decode block was queued
        # behind this one, so the device runs out of work (at most the
        # prefills behind it) while the host is here
        dry = queued_behind == 0 and any(s.active for s in self.slots)
        self.decode_syncs_total += 1
        self.dry_syncs_total += dry
        if dry:
            self.queue.ran_dry()
        self.row_steps_total += len(snapshot) * block
        self._obs.gauge("app_tpu_decode_blocks_queued", queued_behind)
        # every token in this sync shares one measured step time: record the
        # TPOT histogram ONCE per sync, not per token (VERDICT r2 weak #9)
        self.steps.note_sync(
            "decode", tokens=emitted,
            slowest_request_id=slowest.id if slowest else None,
            page_writes=page_writes, dry=dry,
            window_pages=self._window_pages_used(), block_steps=block)
        self._obs.hist_n(
            "app_tpu_tpot_seconds", step_s, emitted,
            exemplar=(self._exemplar_of(slowest) if slowest else None))
        self._obs.hist("app_tpu_batch_size", n_active)
        self._track_throughput(emitted)

    def _fail_request(self, request: GenerationRequest,
                      exc: Optional[BaseException] = None) -> None:
        """Terminate a request that never reached (or lost) a slot: close
        its generation span and unblock its consumer.

        Disaggregated prefill pool: the failure is offered to the hand-off
        fail hook first (disagg.PrefillWorker). When the hook takes it, the
        stream is NOT over — the worker re-routes it to the decode pool as
        a recompute from prompt + emitted — so no error lands on the
        request object (the client shares it) and no terminal None is
        delivered; the prefill-side span and flight record still close."""
        handled = (self._handoff_fail is not None
                   and self._handoff_fail(request, exc))
        if exc is not None and not handled:
            request.error = exc
        if request.finished_at is None:  # terminal either way: consumers
            request.finished_at = time.monotonic()  # and the admission
            # plane's live-registry prune both treat this request as over
        if request.gen_span is not None and request.gen_span.end_time is None:
            if request.error is not None:
                request.gen_span.set_status(False, str(request.error))
            elif request.cancelled.is_set():
                request.gen_span.set_attribute("cancelled", True)
            if handled:
                request.gen_span.set_attribute("disagg.fallback", True)
            request.gen_span.end()
        if self.recorder is not None:
            self.recorder.record_finished(
                request, "handoff" if handled
                else ("error" if request.error is not None
                      else ("cancelled" if request.cancelled.is_set()
                            else "aborted")))
        if not handled:
            if self.qos is not None:
                self.qos.note_finished(request, ok=request.error is None)
            if self.meter is not None:
                self.meter.note_finished(request,
                                         ok=request.error is None)
            request.out_queue.put(None)

    @loop_only
    def _emit_block(self, request: GenerationRequest,
                    tokens: List[int]) -> None:
        """Deliver one request's demuxed tokens for this sync in a SINGLE
        queue operation (stream() unpacks a list entry in order), with the
        replay ledger extended BEFORE the put — loop-thread-only writes,
        so request.emitted stays exact for replay-after-reset. The token
        counter is NOT bumped here: sync sites record it once per sync."""
        if not tokens:
            return
        request.generated += len(tokens)
        request.emitted.extend(tokens)  # the replay ledger (resume_tokens)
        request.out_queue.put(tokens[0] if len(tokens) == 1 else tokens)

    def _demux_plan(self, tokens_host, rows: List[int],
                    requests: List[GenerationRequest], limits):
        """Vectorized demux: per-row emit counts + finish flags for one
        synced token matrix in one numpy pass, replacing the former
        per-token Python loop (int() -> put -> counter, per token per
        row). Semantics are EXACTLY the old emit-then-check loop's:

          * the loop body ran before any terminal check, so every row
            with device tokens emits at least min(limit, 1);
          * a stop token counts only once min_tokens emissions exist
            (GenerationRequest.hit_stop), and the stop token ITSELF is
            emitted — count = first eligible hit + 1;
          * budget (slot.remaining) and context (max_seq_len - 1) caps
            emit the capping token, then finish;
          * a cancelled row emits exactly one token, then finishes.

        rows/requests/limits are parallel per LIVE row; tokens_host is
        the full [B, W] synced matrix (rows index into it); limits is the
        per-row token bound (the block size for decode, the device's
        n_emit for verify). Returns (counts [R] int64, finish [R] bool).
        """
        import numpy as np

        n = len(rows)
        if n == 0:
            return (np.zeros(0, dtype=np.int64), np.zeros(0, dtype=bool))
        toks = tokens_host[np.asarray(rows, dtype=np.int64)]
        W = toks.shape[1]
        lim = np.minimum(np.asarray(limits, dtype=np.int64), W)
        budget = np.array([self.slots[i].remaining for i in rows],
                          dtype=np.int64)
        ctx = np.array([self.max_seq_len - 1 - self.slots[i].length
                        for i in rows], dtype=np.int64)
        gen0 = np.array([r.generated for r in requests], dtype=np.int64)
        min_t = np.array([r.min_tokens for r in requests], dtype=np.int64)
        cancelled = np.array([self._is_cancelled(r) for r in requests],
                             dtype=bool)

        # stop-token scan, one vectorized isin per DISTINCT stop set
        # (requests overwhelmingly share one), gated by min_tokens
        # eligibility and the per-row device limit. stop_cap is the
        # 1-based emit count that includes the stop token; W + 1 = none
        pos1 = np.arange(1, W + 1, dtype=np.int64)
        stop_cap = np.full(n, W + 1, dtype=np.int64)
        groups: Dict[frozenset, List[int]] = {}
        for j, r in enumerate(requests):
            if r.stop_tokens:
                groups.setdefault(frozenset(r.stop_tokens), []).append(j)
        for stops, idxs in groups.items():
            hit = np.isin(toks[idxs],
                          np.array(sorted(stops), dtype=np.int64))
            hit &= (gen0[idxs, None] + pos1[None, :]) >= min_t[idxs, None]
            hit &= pos1[None, :] <= lim[idxs, None]
            any_hit = hit.any(axis=1)
            stop_cap[idxs] = np.where(any_hit, hit.argmax(axis=1) + 1,
                                      W + 1)

        counts = np.minimum(np.minimum(lim, stop_cap),
                            np.minimum(budget, ctx))
        counts = np.where(cancelled, np.minimum(counts, 1), counts)
        counts = np.maximum(counts, np.minimum(lim, 1))
        finish = ((cancelled & (counts >= 1))
                  | (counts == stop_cap)      # stop_cap <= lim <= W when hit
                  | (counts >= budget)        # remaining exhausted
                  | (counts >= ctx))          # length hits max_seq_len - 1
        return counts, finish

    def _finish_slot(self, slot: _Slot) -> None:
        request = slot.request
        # terminal reason, read from slot state BEFORE it resets: error >
        # cancel > token budget / context cap ("length", the OpenAI
        # finish_reason) > stop token
        reason = None
        if request is not None:
            if request.error is not None:
                reason = "error"
            elif request.cancelled.is_set() or self._is_cancelled(request):
                reason = "cancelled"
            elif (slot.remaining <= 0
                  or slot.length >= self.max_seq_len - 1):
                reason = "length"
            else:
                reason = "stop"
        slot.request = None
        slot.length = 0
        slot.remaining = 0
        slot.history = None
        if (self.sampling_controls and request is not None
                and (request.top_p or request.top_k)):
            # zero the freed slot's device-side control row: the sampler
            # gates its [B, V] sort on ANY row's top_p/top_k, so a stale
            # row would keep every later all-greedy batch paying the sort
            idx = next((i for i, s in enumerate(self.slots) if s is slot),
                       None)
            if idx is not None:
                self._temps = self._temps.at[idx].set(0.0)
        if request is None:
            self._obs.gauge("app_tpu_active_slots",
                            sum(1 for s in self.slots if s.active))
            return
        # stamped HERE, not in the finisher job: _fail_request's
        # double-finish guard and the admission plane's live-registry
        # prune read finished_at synchronously
        request.finished_at = time.monotonic()
        # the SLOW terminal tail (span export, flight-recorder record,
        # metric flush, the client's terminal None) runs off-loop: every
        # input is captured now, on the loop thread, so the job never
        # reads loop-owned state. The None goes LAST, after
        # record_finished — a returned result() implies the recorder
        # already holds the finished record, and FIFO on the finisher +
        # tokens enqueued before this job preserves tokens-then-None
        active_now = sum(1 for s in self.slots if s.active)
        self._run_off_loop(
            self._finish_request_job(request, reason, active_now))

    def _finish_request_job(self, request: GenerationRequest,
                            reason: str, active_now: int):
        def job() -> None:
            if request.gen_span is not None:
                request.gen_span.set_attribute("tpu.tokens",
                                               request.generated)
                if request.error is not None:
                    request.gen_span.set_status(False, str(request.error))
                request.gen_span.end()
            if self.recorder is not None:
                self.recorder.record_finished(request, reason)
            if self.qos is not None:
                self.qos.note_finished(request, ok=request.error is None)
            if self.meter is not None:
                self.meter.note_finished(request,
                                         ok=request.error is None)
            self._obs.gauge("app_tpu_active_slots", active_now)
            request.out_queue.put(None)
        return job

    def _run_off_loop(self, job) -> None:
        """Hand a terminal-teardown job to the finisher; run it inline
        when the finisher is disabled (finisher_queue=0) or its bounded
        queue is full. Jobs are never dropped, and per-request ordering
        is unaffected by the inline fallback: each request has exactly
        one terminal job, and its tokens were enqueued before the job
        was built — a full queue just degrades THIS request's teardown
        to the old inline behavior."""
        if self._finisher is None or not self._finisher.submit(job):
            job()

    def _reset_device_state(self, exc: BaseException) -> None:
        """Rebuild all device state after a failed donated-cache program
        (donation means the old buffers may be deleted on TPU/GPU), then
        REPLAY the interrupted requests instead of failing them: the host
        still holds each one's prompt and every token it already delivered
        (GenerationRequest.emitted), so survivors re-admit at prompt +
        emitted with their remaining budget and elevated priority — the
        client's stream pauses, no position is re-emitted or dropped.
        Bounded by retry_budget, with poison quarantine (a request that
        was sole-in-flight across >= 2 consecutive resets fails instead of
        reset-looping the engine) and the reset-storm breaker counting
        every pass through here."""
        self.resets_total += 1
        self._obs.counter("app_tpu_device_resets_total")
        if self.recorder is not None:
            self.recorder.record_engine_event("device_reset", error=str(exc))
        if self.breaker.record_reset():
            if self.recorder is not None:
                self.recorder.record_engine_event(
                    "breaker_open", **self.breaker.snapshot())
            if self.incidents is not None:
                # the autopsy closes here: the storm's evidence (step
                # ring, engine snapshot, slowest requests) is captured
                # off-thread while it is still in the bounded rings
                self.incidents.trigger("breaker_open", error=str(exc),
                                       breaker=self.breaker.snapshot())
            if self.logger is not None:
                self.logger.errorf(
                    "reset storm: %d resets inside %.0fs — breaker OPEN, "
                    "shedding submits until the half-open probe passes",
                    self.breaker.max_resets, self.breaker.window_s)
        self._obs.gauge("app_tpu_breaker_state", self.breaker.state_code)
        with self._state_lock:
            # close the dispatch spans of everything in flight — the trace
            # record matters MOST for the window a device error destroyed
            for entry in self._inflight:
                dspan = entry[3] if entry[0] == "prefill" else entry[5]
                if dspan is not None:
                    dspan.set_status(False, str(exc))
                    dspan.end()
            self._inflight.clear()
            self.queue.reset()
            survivors: List[GenerationRequest] = []
            while self._chunk_jobs:  # mid-prefill KV rows died with the
                job = self._chunk_jobs.popleft()  # cache; nothing emitted
                for slot_idx in job["slots_idx"]:  # yet, so they replay too
                    self.slots[slot_idx].chunking = None
                survivors.extend(job["batch"])
            for slot in self.slots:
                if slot.active:
                    survivors.append(slot.request)
                    # evacuate WITHOUT terminating: no out_queue sentinel,
                    # no span end — the request lives on in the replay
                    # queue. Pages are not released (the allocator
                    # is rebuilt wholesale by _init_device_state below)
                    slot.request = None
                    slot.length = 0
                    slot.remaining = 0
                    slot.history = None
                    slot.pages = slot.more_pages = None
            self._init_device_state()
            self._replay_or_fail(survivors, exc)

    @loop_only
    def _replay_or_fail(self, survivors: List[GenerationRequest],
                        exc: BaseException) -> None:
        """Requeue each reset survivor for replay, or fail it when it is
        out of budget / poisoned / cancelled / no longer admissible.
        Loop-thread-only, under the state lock, after device state was
        rebuilt (the admission heap is loop-thread state)."""
        import heapq

        if len(survivors) == 1 and self._sole_reset_id == survivors[0].id:
            self._sole_reset_streak += 1
        else:
            self._sole_reset_id = (survivors[0].id if len(survivors) == 1
                                   else None)
            self._sole_reset_streak = 1 if self._sole_reset_id else 0
        for request in survivors:
            if self._plane is not None:
                # multi-controller: a replay requeue would have to ride an
                # admission wave to stay SPMD-symmetric across ranks; until
                # that exists, fail loudly (the pre-replay behavior)
                self._fail_request(request, exc)
                continue
            if self._is_cancelled(request):
                self._fail_request(request)
                continue
            poisoned = (request.id == self._sole_reset_id
                        and self._sole_reset_streak >= 2)
            if poisoned:
                self.quarantined_total += 1
                self._obs.counter("app_tpu_requests_quarantined_total")
                if self.recorder is not None:
                    self.recorder.record_event(
                        request.id, "quarantined",
                        consecutive_sole_resets=self._sole_reset_streak)
                if self.incidents is not None:
                    self.incidents.trigger(
                        "quarantine", request_id=request.id,
                        consecutive_sole_resets=self._sole_reset_streak)
                if self.logger is not None:
                    self.logger.errorf(
                        "request %d quarantined: sole in-flight work "
                        "across %d consecutive device resets",
                        request.id, self._sole_reset_streak)
                self._fail_request(request, exc)
                continue
            budget_left = request.max_new_tokens - request.generated
            if (request.replays >= self.retry_budget or budget_left <= 0
                    or len(request.resume_tokens) > self.admission_limit):
                self._fail_request(request, exc)
                continue
            request.replays += 1
            # replays outrank queued arrivals (priority is LOWER-first and
            # clients are clamped to >= 0): an interrupted stream resumes
            # before fresh work starts
            request.priority = min(request.priority, -1)
            request.admitted_at = None  # re-stamped at re-admission
            self.replays_total += 1
            self.replayed_tokens_total += len(request.emitted)
            self._obs.counter("app_tpu_request_replays_total")
            self._obs.counter("app_tpu_replayed_tokens_total",
                              float(len(request.emitted)))
            if self.recorder is not None:
                self.recorder.record_event(
                    request.id, "replayed", attempt=request.replays,
                    replayed_tokens=len(request.emitted))
            heapq.heappush(self._admission_heap,
                           (request.priority, request.id, request))
        self._wake.set()

    def _qos_actuate(self) -> None:
        """Act on the QoS shed ladder (tpu/qos.py) from the engine loop,
        under the state lock, immediately before admission. Level >= 2
        (preempt_batch) evacuates running batch-class generations via the
        replay contract so the slots (and their pages) free for
        the interactive work the ladder is protecting. Levels 0/1/3 need
        no loop-side action: parking and standard-shed happen at the
        admission gate and the submit door."""
        if self.qos.level < 2:
            return
        self._preempt_slots(("batch",))

    def _preempt_slots(self, classes) -> int:
        """Preempt every running generation in `classes` that can legally
        resume: evacuate the slot WITHOUT terminating (no out_queue
        sentinel, no span end — the reset-survivor recipe) and requeue at
        prompt + emitted with the request's OWN banded priority, so a
        preempted batch request waits behind interactive work instead of
        outranking it the way crash replays do. Zero client-visible loss:
        the stream pauses, nothing is re-emitted or dropped. In-flight
        dispatches that still reference the slot are discarded by the
        same `slot.request is not request` guards that make cancel+free
        safe. Skips: chunked-mid-prefill slots (nothing emitted yet and
        the chunk job owns the slot), exhausted budgets, resume windows
        over the admission limit, and prefill-pool slots (they evacuate
        at prefill sync anyway). Returns the number preempted."""
        import heapq

        preempted = 0
        for slot in self.slots:
            request = slot.request
            if request is None or slot.chunking is not None:
                continue
            if getattr(request, "qos_class", None) not in classes:
                continue
            if self._is_cancelled(request):
                continue  # the demux finish path owns cancellation
            if request.max_new_tokens - request.generated <= 0:
                continue  # about to finish naturally; let it
            if len(request.resume_tokens) > self.admission_limit:
                continue  # could never re-admit; finishing is cheaper
            if self.disagg_role == "prefill":
                continue
            self._release_slot_for_preempt(slot)
            request.preemptions += 1
            request.admitted_at = None  # re-stamped at re-admission
            self.preemptions_total += 1
            preempted += 1
            self._obs.counter("app_tpu_qos_preempted_total",
                              **{"class": request.qos_class})
            self.qos.note_preempted(request)
            if self.recorder is not None:
                self.recorder.record_event(
                    request.id, "preempted",
                    emitted=len(request.emitted),
                    preemptions=request.preemptions)
            heapq.heappush(self._admission_heap,
                           (request.priority, request.id, request))
        if preempted:
            if self.recorder is not None:
                self.recorder.record_engine_event(
                    "qos_preempt", preempted=preempted,
                    level=self.qos.level)
            if self.logger is not None:
                self.logger.warnf(
                    "qos ladder level %d: preempted %d batch generation(s) "
                    "for replay", self.qos.level, preempted)
            self._obs.gauge("app_tpu_active_slots",
                            sum(1 for s in self.slots if s.active))
        return preempted

    def _release_slot_for_preempt(self, slot: _Slot) -> None:
        """Evacuate one slot for preemption: the reset-survivor recipe
        (request lives on, stream stays open) plus the freed-row control
        zeroing from _finish_slot. paging.py releases the slot's pages
        first — unlike a device reset, the allocator is NOT rebuilt, so
        pages must be returned explicitly."""
        request = slot.request
        slot.request = None
        slot.length = 0
        slot.remaining = 0
        slot.history = None
        slot.pages = slot.more_pages = None
        if (self.sampling_controls and request is not None
                and (request.top_p or request.top_k)):
            idx = next((i for i, s in enumerate(self.slots) if s is slot),
                       None)
            if idx is not None:
                self._temps = self._temps.at[idx].set(0.0)

    def _is_cancelled(self, request: GenerationRequest) -> bool:
        """Cancellation as the DISPATCH path must see it. Single-controller:
        the live event. Multi-controller: membership in the plane's synced
        set — a cancel takes effect only at the wave that broadcast it, so
        every rank frees the slot at the same loop iteration (a rank-local
        early free would desynchronize the SPMD dispatch sequence)."""
        if self._plane is not None:
            return request.id in self._plane.synced_cancelled
        return request.cancelled.is_set()

    @loop_only
    def _handoff_fallback(self, request: GenerationRequest,
                          reason: str) -> None:
        """A hand-off this pool cannot land (torn content, wrong shape,
        failed restore) degrades to local recompute — NEVER a failed
        stream: drop the blobs, release the reservation, and re-park the
        request; the next admission round prefills its resume window like
        a replay (PR 3's contract). Loop-thread only (heap access)."""
        import heapq

        self._abort_admission(request)
        request.handoff_blobs = None
        self.handoff_fallbacks_total += 1
        self._obs.counter("app_tpu_disagg_fallback_total", reason=reason)
        if self.recorder is not None:
            self.recorder.record_event(request.id, "disagg_fallback",
                                       reason=reason)
        heapq.heappush(self._admission_heap,
                       (request.priority, request.id, request))

    def _drain_pending(self, exc: BaseException) -> None:
        while self._admission_heap:
            _, _, request = self._admission_heap.pop()
            self._abort_admission(request)
            self._fail_request(request, exc)
        while True:
            try:
                _, _, request = self._pending.get_nowait()
            except queue.Empty:
                return
            self._fail_request(request, exc)

    def _track_throughput(self, tokens: int) -> None:
        now = time.monotonic()
        self._tok_window.append((now, tokens))
        cutoff = now - 5.0
        while self._tok_window and self._tok_window[0][0] < cutoff:
            self._tok_window.popleft()
        if len(self._tok_window) >= 2:
            span = now - self._tok_window[0][0]
            total = sum(t for _, t in self._tok_window)
            if span > 0:
                self._obs.gauge("app_tpu_tokens_per_second", total / span)

    # -- hooks: the device half, filled by paging.PagedLLMEngine ------------
    # The loop above calls these and holds no body for them: pools, tables
    # and programs live in paging.py.
    def _init_device_state(self) -> None:
        """(Re)build everything the device holds: pools, allocator, loop
        state. paging.py calls it at construction; _reset_device_state
        calls it after a device loss."""
        raise NotImplementedError

    def _admission_ready(self, request: GenerationRequest) -> bool:
        """Reserve the request's pages before it can join an admission
        wave. False defers it FIFO."""
        raise NotImplementedError

    def _abort_admission(self, request: GenerationRequest) -> None:
        """Release _admission_ready's reservation for a request that exits
        without reaching a dispatch."""
        raise NotImplementedError

    def _dispatch_prefill(self, bucket: int, slots_idx: List[int],
                          batch: List[GenerationRequest]) -> None:
        """One fused K-way prefill dispatch, then _bind_slots."""
        raise NotImplementedError

    def _start_chunk_job(self, bucket: int, slots_idx: List[int],
                         batch: List[GenerationRequest]) -> None:
        """Prep a chunked-prefill job, dispatch its FIRST chunk and
        register it in _chunk_jobs. Host-prep failures before the dispatch
        leave no reservation behind, so _admit's per-wave handler holds."""
        raise NotImplementedError

    def _dispatch_chunk(self, job) -> bool:
        """Run the job's next chunk program; True when it was the final
        chunk (job['first_tok'] then holds the sampled tokens)."""
        raise NotImplementedError

    def _dispatch_decode(self) -> None:
        """One decode-block dispatch appended to _inflight."""
        raise NotImplementedError

    def _verify_call(self, drafts, lens):
        """Compile-or-hit + run the verify program, splicing device state;
        returns (out_tokens [B, d+1], n_emit [B]) futures."""
        raise NotImplementedError

    def _admit_handoff(self, batch: List[GenerationRequest], free_iter,
                       dispatched: Set[int]) -> None:
        """Bind hand-off requests whose KV arrived as page blobs straight
        into decode slots."""
        raise NotImplementedError

    def _handoff_slot(self, slot: _Slot, request: GenerationRequest) -> None:
        """Prefill role: export a freshly-prefilled slot's KV to the
        hand-off sink and release the slot WITHOUT terminating the
        stream."""
        raise NotImplementedError

    def _export_slot_kv(self, slot: _Slot, request: GenerationRequest):
        """(blobs, n_ctx) of a live decode slot for a migration export;
        blobs=None means the peer replays prompt+emitted."""
        raise NotImplementedError

    def _note_model_counts(self, tokens_host, block: int) -> None:
        """Fold a synced decode block's counter rows into the totals."""
        raise NotImplementedError

    def _window_pages_used(self) -> int:
        """Pages in use in the page groups that keep a window (the step
        record's `window_pages`)."""
        raise NotImplementedError

    def _note_page_writes(self, live, block: int) -> int:
        """Count a synced decode block's page writes; returns them for
        the step ledger's record."""
        raise NotImplementedError

    def _note_page_reads(self, live, block: int, n_table: int) -> None:
        """Count the folds a synced decode block's reads made under a
        table `n_table` wide."""
        raise NotImplementedError
