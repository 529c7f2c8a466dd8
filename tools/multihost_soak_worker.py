"""Worker for the multi-host live-traffic serving SOAK.

The test tier (tests/multihost_live_worker.py) proves the admission plane
mirrors five staggered arrivals and one cancel; this worker is the
soak-grade version: a Poisson traffic loop at rank 0 — randomized prompt
lengths, budgets, priorities, and mid-stream cancels, all arriving WHILE
the tp=2 engine loop dispatches — mirrored by rank 1 from the wave stream
alone, then checked three ways: (a) every rank-0 request matches a
single-device oracle replay (cancelled ones as strict prefixes), (b) the
two ranks' served streams checksum identically, (c) every request is
terminal with zero unexpected errors.

Usage: python multihost_soak_worker.py <rank> <coordinator_port> <seconds> <seed>
"""

import json
import os
import random
import sys
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from gofr_tpu.config import MockConfig  # noqa: E402
from gofr_tpu.models.llama import LlamaConfig, llama_init  # noqa: E402
from gofr_tpu.parallel import MeshPlan, make_mesh  # noqa: E402
from gofr_tpu.parallel.multihost import initialize_from_config  # noqa: E402
from gofr_tpu.tpu.admission import AdmissionPlane  # noqa: E402
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

CFG = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=2,
                  n_kv_heads=2, ffn_dim=64, max_seq_len=256, dtype="float32")


def _engine(mesh, plane):
    return PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=4,
                          max_seq_len=256, prefill_buckets=(8, 16),
                          decode_block_size=4, page_size=16, mesh=mesh,
                          admission_plane=plane)


def _checksum(streams):
    # order-sensitive over (request order, position, token)
    return sum(t * (i + 1) * (j + 1) for i, toks in enumerate(streams)
               for j, t in enumerate(toks))


def _lead(mesh, seconds, seed):
    rng = random.Random(seed)
    eng = _engine(mesh, AdmissionPlane(kv=None))
    eng.start()

    records = []  # (request, prompt, budget, cancel_at, tokens, lock-free: filled by reader)
    readers = []
    try:
        deadline = time.time() + seconds
        while time.time() < deadline:
            prompt = [rng.randrange(1, CFG.vocab_size)
                      for _ in range(rng.randrange(1, 13))]
            budget = rng.randrange(4, 25)
            cancel_at = (rng.randrange(1, max(2, budget // 2))
                         if rng.random() < 0.2 else None)
            req = eng.submit(prompt, max_new_tokens=budget, temperature=0.0,
                             priority=rng.randrange(0, 3))
            rec = {"req": req, "prompt": prompt, "budget": budget,
                   "cancel_at": cancel_at, "tokens": [], "error": None}
            records.append(rec)

            def read(rec=rec):
                try:
                    for tok in rec["req"].stream(timeout_s=300):
                        rec["tokens"].append(tok)
                        if (rec["cancel_at"] is not None
                                and len(rec["tokens"]) == rec["cancel_at"]):
                            rec["req"].cancel()
                except Exception as exc:  # noqa: BLE001 - tallied below
                    rec["error"] = f"{type(exc).__name__}: {exc}"

            t = threading.Thread(target=read)
            t.start()
            readers.append(t)
            time.sleep(rng.expovariate(1.0 / 0.08))  # ~12.5 req/s Poisson
        for t in readers:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in readers), "stranded reader"
    finally:
        eng.stop()  # publishes the stop sentinel for rank 1

    errors = [r["error"] for r in records if r["error"]]
    assert not errors, errors[:3]

    # oracle replay: single-device, no plane, same greedy params
    oracle_eng = _engine(None, None)
    oracle_eng.start()
    try:
        for rec in records:
            want = oracle_eng.generate(rec["prompt"],
                                       max_new_tokens=rec["budget"],
                                       temperature=0.0)
            got = rec["tokens"]
            if rec["cancel_at"] is None:
                assert got == want, (rec["prompt"], got, want)
            else:
                # the cancel wave lands within a few dispatches of the
                # reader's cancel() call; the stream must be a strict
                # prefix no shorter than the cancel point
                assert rec["cancel_at"] <= len(got) <= rec["budget"], rec
                assert got == want[:len(got)], (got, want)
    finally:
        oracle_eng.stop()

    served = [r["tokens"] for r in sorted(records, key=lambda r: r["req"].id)]
    stats = {"requests": len(records),
             "cancelled": sum(1 for r in records if r["cancel_at"] is not None),
             "tokens": sum(len(s) for s in served)}
    return served, stats


def _follow(mesh):
    plane = AdmissionPlane(kv=None)
    shadows = []
    plane.on_shadow = shadows.append
    eng = _engine(mesh, plane)
    eng.start()
    try:
        deadline = time.time() + 600
        while time.time() < deadline:
            if plane.closed and shadows and all(
                    s.finished_at is not None for s in shadows):
                break
            time.sleep(0.05)
        assert plane.closed, "leader never closed the plane"
        by_order = sorted(shadows, key=lambda s: s.id)
        served = [list(s.stream(timeout_s=5)) for s in by_order]
    finally:
        eng.stop()
    return served, {"requests": len(shadows),
                    "tokens": sum(len(s) for s in served)}


def main() -> None:
    rank, port = int(sys.argv[1]), sys.argv[2]
    seconds, seed = float(sys.argv[3]), int(sys.argv[4])
    spec = initialize_from_config(MockConfig({
        "JAX_COORDINATOR_ADDR": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(rank),
        "JAX_COORDINATOR_TIMEOUT_S": "150",
    }))
    assert spec is not None and spec.process_id == rank
    assert jax.process_count() == 2

    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices())
    served, stats = (_lead(mesh, seconds, seed) if rank == 0
                     else _follow(mesh))
    print(f"RANK{rank}_SOAK_OK checksum={_checksum(served)} "
          f"stats={json.dumps(stats)}", flush=True)
    from jax._src import distributed

    distributed.global_state.client.wait_at_barrier("soak-worker-exit",
                                                    300_000)
    # hard-exit past interpreter teardown (see multihost_live_worker.py:
    # the asymmetric shutdown leaves distributed-runtime threads in states
    # its destructor aborts on)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
