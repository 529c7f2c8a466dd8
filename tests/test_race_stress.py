"""Concurrency stress tier: many threads hammering shared components.

SURVEY §5 race-detection row: the reference runs no -race tier; this build
adds one. Python has no TSan, so the tier drives the REAL lock-protected
paths from many threads at once and asserts invariants that break under
lost updates or torn state (counts exact, no deadlocks, no cross-request
token leakage). Failures here are race symptoms even without a sanitizer.
"""

import threading
import time

import pytest

import numpy as np

from gofr_tpu.config import MockConfig
from gofr_tpu.logging import MockLogger
from gofr_tpu.metrics import new_metrics_manager


def _hammer(n_threads, fn):
    errors = []
    barrier = threading.Barrier(n_threads)

    def run(i):
        try:
            barrier.wait(timeout=30)
            fn(i)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:3]
    assert not any(t.is_alive() for t in threads), "deadlocked threads"


def test_kvstore_concurrent_increments_are_exact():
    from gofr_tpu.datasource.kvstore import KVStore

    kv = KVStore(MockConfig({}), MockLogger(), None)
    N, PER = 16, 500

    def work(i):
        for _ in range(PER):
            kv.incr("counter")

    _hammer(N, work)
    assert kv.get("counter") == N * PER


def test_metrics_concurrent_recording_is_exact():
    m = new_metrics_manager()
    m.new_counter("c", "races")
    m.new_histogram("h", "races", buckets=(1.0,))
    N, PER = 12, 400

    def work(i):
        for _ in range(PER):
            m.increment_counter("c")
            m.record_histogram_n("h", 0.5, 2)

    _hammer(N, work)
    assert m.get("c").series[tuple()] == N * PER
    assert m.get("h").series[tuple()]["count"] == N * PER * 2


def test_broker_concurrent_publish_consume_no_loss_no_dup():
    from gofr_tpu.pubsub.inproc import InProcBroker

    broker = InProcBroker(MockConfig({}), MockLogger(), None)
    N_PUB, PER = 8, 50
    seen = []
    seen_lock = threading.Lock()
    done = threading.Event()

    def consume():
        misses = 0
        while misses < 2:  # two consecutive empty polls after done = drained
            msg = broker.subscribe("t", group="g", timeout_s=0.2)
            if msg is None:
                misses += 1 if done.is_set() else 0
                continue
            misses = 0
            with seen_lock:
                seen.append(msg.value)
            if msg.commit is not None:
                msg.commit()

    consumers = [threading.Thread(target=consume) for _ in range(4)]
    for t in consumers:
        t.start()

    def publish(i):
        for j in range(PER):
            broker.publish("t", f"{i}:{j}".encode())

    _hammer(N_PUB, publish)
    done.set()
    for t in consumers:
        t.join(timeout=60)
    assert sorted(seen) == sorted(f"{i}:{j}".encode()
                                  for i in range(N_PUB) for j in range(PER))


def _engine_submit_cancel_stress(engine_kwargs, prompts, max_new,
                                 n_threads, rounds, cancel_mod,
                                 cls=None, on_done=None):
    """Shared body: many client threads submitting/streaming/cancelling
    against one engine — every request either completes with its own
    deterministic tokens or raises cleanly; no cross-request leakage.
    on_done(engine) runs after the hammer, before stop (leak gates)."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = (cls or PagedLLMEngine)(llama_init(cfg, seed=0), cfg,
                             logger=MockLogger(), **engine_kwargs)
    eng.start()
    try:
        golden = {i: eng.generate(p, max_new_tokens=max_new, temperature=0.0)
                  for i, p in prompts.items()}

        def work(i):
            prompt = prompts[i % len(prompts)]
            for round_no in range(rounds):
                req = eng.submit(prompt, max_new_tokens=max_new,
                                 temperature=0.0)
                if (i + round_no) % cancel_mod == 0:
                    req.cancel()
                    try:
                        req.result(timeout_s=90)
                    except Exception:  # noqa: BLE001 - cancel may race finish
                        pass
                else:
                    out = req.result(timeout_s=90)
                    assert out == golden[i % len(prompts)], \
                        f"cross-request leakage for {i}"

        _hammer(n_threads, work)
        if on_done is not None:
            on_done(eng)
    finally:
        eng.stop()


def test_engine_concurrent_submit_stream_cancel():
    _engine_submit_cancel_stress(
        dict(n_slots=4, max_seq_len=64, prefill_buckets=(8,)),
        prompts={i: [1 + i, 2 + i, 3 + i] for i in range(6)},
        max_new=6, n_threads=12, rounds=4, cancel_mod=3)


def test_executor_concurrent_compile_single_program():
    """Racing threads compiling the same key get ONE cached program."""
    import jax.numpy as jnp

    from gofr_tpu.tpu.executor import Executor

    ex = Executor()
    results = []

    def work(i):
        program = ex.compile("race", lambda x: x + 1, (jnp.ones((4,)),))
        results.append(program)

    _hammer(8, work)
    assert ex.cache_size == 1
    assert all(p is results[0] for p in results)
    np.testing.assert_array_equal(np.asarray(results[0](jnp.ones((4,)))),
                                  np.full((4,), 2.0))


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_spec_engine_concurrent_submit_cancel():
    """The speculative engine's extra host state (histories, EMA, cooloff)
    under the same hammering."""
    _engine_submit_cancel_stress(
        dict(n_slots=4, max_seq_len=128, prefill_buckets=(8, 16),
             speculative_tokens=3),
        prompts={i: [5 + i, 6 + i] * 3 for i in range(4)},
        max_new=8, n_threads=10, rounds=3, cancel_mod=4)


def test_drain_races_concurrent_submitters():
    """drain() firing while many threads submit: every submit either
    completes fully or fails with the draining error — nothing hangs,
    nothing half-generates."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.engine import EngineDrainingError
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=64,
                         prefill_buckets=(8,), logger=MockLogger())
    eng.start()
    outcomes = []
    lock = threading.Lock()
    try:
        eng.generate([1, 2, 3], max_new_tokens=4, temperature=0.0)  # warm

        stop_submitting = threading.Event()

        def work(i):
            if i == 0:
                # the drainer: let submitters get going, then drain
                import time as _t
                _t.sleep(0.3)
                drained = eng.drain(timeout_s=120)
                stop_submitting.set()
                assert drained, "drain timed out: busy state leaked"
                return
            while not stop_submitting.is_set():
                try:
                    req = eng.submit([1 + i, 2, 3], max_new_tokens=4,
                                     temperature=0.0)
                except EngineDrainingError:
                    with lock:
                        outcomes.append("rejected")
                    return
                try:
                    out = req.result(timeout_s=120)
                    with lock:
                        outcomes.append(len(out))
                except EngineDrainingError:
                    with lock:
                        outcomes.append("failed-queued")

        _hammer(8, work)
    finally:
        eng.stop()
    # every completed generation is FULL length; partial outputs would mean
    # drain cut an active request short
    assert all(o == 4 for o in outcomes if isinstance(o, int)), outcomes
    assert outcomes, "no submitter ever ran"


def test_drain_submit_cancel_race_every_client_terminal():
    """Concurrent drain() + submit() + cancel(): EVERY client observes a
    terminal outcome — a full token stream, a 503 EngineDrainingError, or
    a clean cancel — and no future/request is left hanging (queue, heap,
    and slots all empty after the dust settles)."""
    import time

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.engine import EngineDrainingError
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=64,
                         prefill_buckets=(8,), logger=MockLogger())
    eng.start()
    outcomes = []
    lock = threading.Lock()
    stop_submitting = threading.Event()
    try:
        eng.generate([1, 2, 3], max_new_tokens=4)  # warm the programs

        def work(i):
            if i == 0:
                time.sleep(0.25)
                drained = eng.drain(timeout_s=120)
                stop_submitting.set()
                assert drained, "drain timed out: busy state leaked"
                return
            rng_cancel = i % 3 == 0
            while not stop_submitting.is_set():
                try:
                    req = eng.submit([1 + i, 2, 3], max_new_tokens=4)
                except EngineDrainingError:
                    with lock:
                        outcomes.append("rejected")
                    return
                if rng_cancel:
                    req.cancel()
                try:
                    out = req.result(timeout_s=120)
                    with lock:
                        outcomes.append("cancelled" if rng_cancel
                                        else len(out))
                except EngineDrainingError:
                    # queued behind the drain: failed fast, still terminal
                    with lock:
                        outcomes.append("failed-queued")

        _hammer(10, work)
        # nothing hangs: every structure the clients touched is empty
        assert eng._pending.qsize() == 0
        assert not eng._admission_heap
        assert not any(s.active or s.chunking is not None for s in eng.slots)
    finally:
        eng.stop()
    # completed generations are FULL length (drain never truncates), and
    # at least one client actually exercised each path class
    assert all(o == 4 for o in outcomes if isinstance(o, int)), outcomes
    assert outcomes, "no submitter ever ran"


def test_dynamic_batcher_stop_does_not_race_live_loop():
    """stop() timing out while the loop is mid-batch must NOT null the
    thread and double-complete queued futures — the live loop keeps
    ownership, completes the in-flight batch, and drains the queue itself
    on exit (scheduler.py stop/is_alive race)."""
    import time

    from gofr_tpu.tpu.scheduler import DynamicBatcher, _WorkItem

    gate = threading.Event()
    entered = threading.Event()

    def model_fn(batch):
        entered.set()
        gate.wait(timeout=30)
        return batch

    batcher = DynamicBatcher(model_fn, max_batch=2, window_s=0.01,
                             logger=MockLogger())
    batcher.STOP_JOIN_S = 0.2
    batcher.start()
    fut = batcher.submit(np.zeros((2,), dtype=np.float32))
    assert entered.wait(timeout=30), "loop never entered the batch"
    # anything racing in behind the in-flight batch stays queued
    batcher._queue.put(_WorkItem(np.ones((2,), dtype=np.float32)))
    batcher.stop()  # join times out: loop still alive inside model_fn
    assert batcher._thread is not None, "stop() nulled a live thread"
    assert not fut.done(), "stop() completed a future the loop still owns"
    gate.set()
    np.testing.assert_array_equal(np.asarray(fut.result(timeout=30)),
                                  np.zeros((2,), dtype=np.float32))
    # the LOOP drained the stragglers on exit — exactly once, no race
    deadline = time.time() + 30
    while batcher._queue.qsize() and time.time() < deadline:
        time.sleep(0.02)
    assert batcher._queue.qsize() == 0


def test_engine_stop_with_wedged_loop_leaves_state_to_live_loop():
    """PagedLLMEngine.stop() timing out against a loop stuck in a device call
    must not mutate loop-owned state (engine.py stop/is_alive race): the
    thread stays registered, and when the device answers the loop finishes
    its own teardown."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=2,
                         max_seq_len=64,
                         prefill_buckets=(8,), logger=MockLogger())
    eng.STOP_JOIN_S = 0.2
    eng.start()
    eng.generate([1, 2, 3], max_new_tokens=3)  # warm
    # quiesce: the warm request's surplus pipelined decodes are still in
    # flight when generate() returns; they must drain BEFORE the wedge is
    # armed, or the wedged iteration holds only junk entries and the new
    # request's decodes never dispatch (the old flake: whether result()
    # below sees 4 tokens then depended on where stop() landed)
    deadline = time.time() + 30
    while eng._inflight and time.time() < deadline:
        time.sleep(0.01)
    assert not eng._inflight, "warm-up dispatches never drained"

    gate = threading.Event()
    entered = threading.Event()
    orig_sync = eng._sync_oldest

    def stuck_sync():
        entered.set()   # the loop is now provably INSIDE the device call
        gate.wait(timeout=30)
        return orig_sync()

    eng._sync_oldest = stuck_sync
    req = eng.submit([4, 5, 6], max_new_tokens=4)
    # deterministic wedge: wait for the loop to ENTER the gated sync (the
    # same iteration already dispatched the request's prefill + pipelined
    # decodes), not for _inflight to appear — stop() could otherwise land
    # on a not-yet-wedged loop and join cleanly
    assert entered.wait(timeout=30), "loop never reached the gated sync"

    eng.stop()  # join times out against the gated sync
    assert eng._thread is not None, "stop() nulled a live loop thread"
    gate.set()
    eng._sync_oldest = orig_sync
    # the LIVE loop finishes the dispatched work and fails nothing mid-air
    assert len(req.result(timeout_s=60)) == 4
    eng._thread.join(timeout=30)
    assert not eng._thread.is_alive()
    eng._thread = None
    eng.stop()  # now a clean no-op drain


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_prefix_cache_engine_concurrent_submit_cancel():
    """Prefix-cache bookkeeping (match refs, owner-insert, leaf-first
    eviction under pool pressure, unref at finish AND at cancel-abort)
    hammered by concurrent clients sharing a 2-page prompt prefix over a
    deliberately small pool. Gate: after the hammer, dropping idle cache
    pages leaves ZERO used pages — any refcount imbalance leaks."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    base = list(range(1, 17))             # 16 tokens = 2 full pages at ps=8

    def assert_no_leaks(eng):
        freed = eng.prefix.drop_all_idle()
        eng.allocator.release(freed)
        assert eng.allocator.used_pages == 0, \
            f"{eng.allocator.used_pages} pages leaked (refs stuck)"
        assert eng.prefix.hit_pages > 0, "stress never exercised a hit"

    _engine_submit_cancel_stress(
        dict(n_slots=4, max_seq_len=64, prefill_buckets=(8, 32),
             page_size=8, prefix_cache=True, n_pages=21),
        prompts={i: base + [30 + i] for i in range(6)},
        max_new=6, n_threads=10, rounds=4, cancel_mod=3,
        cls=PagedLLMEngine, on_done=assert_no_leaks)


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_paged_engine_tiered_kv_concurrent_submit_cancel():
    """Spill/restore racing the submit/stream/cancel hammer: prompts
    DIVERGE in the first page so every one caches its own full pages, and
    the pool is sized so cached-idle + active demand overflows it — prefix
    eviction (host-tier spill) and admission-time restore run mid-traffic.
    Golden-output equality is the correctness gate: a restore that
    rebuilt the wrong KV breaks bit-equality; the leak gate catches any
    refcount imbalance on the restored pages' insert/unref cycle."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    base = list(range(1, 17))             # 16 tokens = 2 full pages at ps=8

    def assert_no_leaks_and_spilled(eng):
        freed = eng.prefix.drop_all_idle()
        eng.allocator.release(freed)
        assert eng.allocator.used_pages == 0, \
            f"{eng.allocator.used_pages} pages leaked (refs stuck)"
        assert eng._kv_spilled > 0, \
            "pool never spilled — the tier path went unexercised"

    _engine_submit_cancel_stress(
        dict(n_slots=4, max_seq_len=64, prefill_buckets=(8, 32),
             page_size=8, prefix_cache=True, n_pages=15,
             kv_host_tier_bytes=16 << 20),
        prompts={i: [30 + i] + base for i in range(6)},
        max_new=6, n_threads=10, rounds=4, cancel_mod=3,
        cls=PagedLLMEngine, on_done=assert_no_leaks_and_spilled)


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_wedge_recovery_races_concurrent_submitters():
    """Submitters racing wedge onset and recovery: every request must end
    terminal (tokens, EngineStalledError shed, or a cancel) — no client
    stranded, no deadlock, and the engine serves normally afterwards.

    The wedge is a device that stops answering: the loop blocks inside one
    device sync. Simulated by gating _sync_oldest; threads submit across
    the healthy->wedged->recovered transitions."""
    import time

    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.engine import EngineStalledError
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=64,
                         prefill_buckets=(8,), decode_block_size=4)
    eng.STALL_REJECT_S = 0.2
    eng.start()
    # warm so the wedge window isn't spent compiling
    eng.generate([1, 2, 3], max_new_tokens=4)

    gate = threading.Event()
    gate.set()  # healthy to start
    orig_sync = eng._sync_oldest

    def gated_sync():
        gate.wait(timeout=30)
        return orig_sync()

    eng._sync_oldest = gated_sync
    outcomes = {"ok": 0, "shed": 0, "timeout": 0}
    tally = threading.Lock()
    done = threading.Event()

    def submitter(i):
        r = 0
        # keep traffic flowing until the toggler has PROVEN both wedge
        # cycles engaged — fixed-round submitters can finish before the
        # first gate.clear() on a fast machine, passing vacuously. The
        # result timeout is SHORT on purpose: a wedged wave strands its
        # waiters, and a stranded client's timeout->cancel->resubmit is
        # exactly the retry that must then hit the shed.
        while not done.is_set():
            r += 1
            try:
                req = eng.submit([1 + (i + r) % 5, 2, 3], max_new_tokens=4)
                tokens = req.result(timeout_s=3.0)
                with tally:
                    outcomes["ok"] += 1
                assert len(tokens) == 4
            except EngineStalledError:
                with tally:
                    outcomes["shed"] += 1
                time.sleep(0.05)
            except TimeoutError:
                # result() already cancelled the request (stream() contract)
                with tally:
                    outcomes["timeout"] += 1

    def _await(cond, what, deadline_s=90):
        # event-driven pacing: under a fully-loaded CI box every step just
        # takes longer — fixed sleeps flake, conditions don't
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if cond():
                return
            time.sleep(0.02)
        raise AssertionError(f"timed out waiting for {what}")

    def toggler(_):
        try:
            for cycle in range(2):
                with tally:
                    ok_before = outcomes["ok"]
                    shed_before = outcomes["shed"]
                # healthy traffic flowing before the wedge engages
                _await(lambda: outcomes["ok"] > ok_before,
                       f"cycle {cycle}: healthy completion")
                gate.clear()  # wedge: next sync blocks
                # deterministic engagement PER CYCLE: the stall passed the
                # shed threshold AND a submitter was shed in THIS cycle (a
                # cumulative check would make cycle 2 vacuous, never
                # proving recovery-then-re-wedge sheds)
                _await(lambda: (eng.stall_seconds > eng.STALL_REJECT_S
                                and outcomes["shed"] > shed_before),
                       f"cycle {cycle}: wedge engagement")
                gate.set()  # device answers again
        finally:
            done.set()

    # local runner, not _hammer: the event-driven waits above tolerate a
    # fully-loaded box by design (up to 4x90s), which needs a longer join
    # than the shared helper's 120s
    errors = []
    barrier = threading.Barrier(9)

    def run(i):
        try:
            barrier.wait(timeout=60)
            (toggler if i == 0 else submitter)(i)
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)
            done.set()  # a failed toggler must release the submitters

    threads = [threading.Thread(target=run, args=(i,)) for i in range(9)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=420)
    # if the toggler died mid-wedge the gate may be left cleared; the
    # gated sync's own 30s timeout unblocks the engine loop regardless
    gate.set()
    assert not errors, errors[:3]
    assert not any(t.is_alive() for t in threads), "deadlocked threads"

    eng._sync_oldest = orig_sync
    assert outcomes["ok"] > 0, outcomes
    assert outcomes["shed"] > 0, outcomes
    # after recovery the engine serves normally and health is clean
    assert len(eng.generate([9, 8, 7], max_new_tokens=5)) == 5
    assert eng.health_check().status == "UP"
    eng.stop()
