"""Worker for the LIVE-TRAFFIC multi-host serving test.

The successor to multihost_serving_worker.py's determinism contract: here
NOTHING is pre-queued. Rank 0 is the only ingress — a submitter thread
feeds it requests WHILE the tp=2 engine loop runs (staggered arrivals, a
mid-flight cancel) — and every wave's composition reaches rank 1 over the
jax.distributed coordination-service KV store (tpu/admission.py), the same
DCN plane that formed the global device set. Rank 1 reconstructs shadow
requests from the waves alone and must mirror the leader token-for-token;
rank 0 additionally checks itself against a pre-computed single-device
oracle. VERDICT r4 next-round #4.

Usage: python multihost_live_worker.py <rank> <coordinator_port>
"""

import os
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

PROMPTS = [[1, 2, 3, 4], [9, 8, 7], [5], [11, 12, 13, 14], [3, 1]]
CANCEL_INDEX = 3          # cancelled after its 2nd token, mid-generation
# the victim gets a DEEP budget: under CPU contention the canceling
# consumer thread can lag many decode blocks behind the engine, and the
# cancel must still provably cut the generation short
BUDGETS = [6, 6, 6, 96, 6]
CFG = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=2,
                  n_kv_heads=2, ffn_dim=64, max_seq_len=128, dtype="float32")


def _engine(mesh, plane):
    return PagedLLMEngine(llama_init(CFG, seed=0), CFG, n_slots=4,
                          max_seq_len=128, prefill_buckets=(8,),
                          decode_block_size=4, mesh=mesh,
                          admission_plane=plane)


def _checksum(token_lists):
    return sum(t * (i + 1) for i, toks in enumerate(token_lists)
               for t in toks)


def _lead(mesh):
    # construct the TP engine FIRST: sharded placement forms the
    # cross-process collective context, and rank 1 builds its twin at
    # process start — running the slow oracle first would leave rank 1
    # alone at the rendezvous until its connect timeout (observed: Gloo
    # context initialization failure under host load)
    eng = _engine(mesh, AdmissionPlane(kv=None))

    # oracle: single-device, no plane — the expected token streams
    oracle_eng = _engine(None, None)
    oracle_eng.start()
    try:
        oracle = [oracle_eng.generate(p, max_new_tokens=budget,
                                      temperature=0.0)
                  for p, budget in zip(PROMPTS, BUDGETS)]
    finally:
        oracle_eng.stop()

    eng.start()
    requests = []
    try:
        def submitter():
            for p, budget in zip(PROMPTS, BUDGETS):
                requests.append(eng.submit(p, max_new_tokens=budget,
                                           temperature=0.0))
                time.sleep(0.15)  # arrivals land across many live waves

        t = threading.Thread(target=submitter)
        t.start()
        t.join()
        victim = requests[CANCEL_INDEX]
        got_victim = []
        for tok in victim.stream(timeout_s=240):
            got_victim.append(tok)
            if len(got_victim) == 2:
                victim.cancel()
        served = [got_victim if i == CANCEL_INDEX
                  else r.result(timeout_s=240)
                  for i, r in enumerate(requests)]
        # uncancelled requests must match the oracle exactly; the victim
        # must be a strict prefix, cut short
        for i, toks in enumerate(served):
            if i == CANCEL_INDEX:
                assert 2 <= len(toks) < BUDGETS[i], toks
                assert toks == oracle[i][:len(toks)], (toks, oracle[i])
            else:
                assert toks == oracle[i], (i, toks, oracle[i])
        return served
    finally:
        eng.stop()  # publishes the stop sentinel for rank 1


def _follow(mesh):
    plane = AdmissionPlane(kv=None)
    shadows = []
    plane.on_shadow = shadows.append
    eng = _engine(mesh, plane)
    eng.start()
    try:
        deadline = time.time() + 240
        while time.time() < deadline:
            if plane.closed and len(shadows) == len(PROMPTS) and all(
                    s.finished_at is not None for s in shadows):
                break
            time.sleep(0.05)
        assert len(shadows) == len(PROMPTS), len(shadows)
        by_order = sorted(shadows, key=lambda s: s.id)
        return [list(s.stream(timeout_s=5)) for s in by_order]
    finally:
        eng.stop()


def main() -> None:
    rank, port = int(sys.argv[1]), sys.argv[2]
    spec = initialize_from_config(MockConfig({
        "JAX_COORDINATOR_ADDR": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(rank),
        # generous: under a fully-loaded CI box (the whole suite runs in
        # parallel with 8-device compiles) rank startup skew alone has
        # blown a 60s rendezvous
        "JAX_COORDINATOR_TIMEOUT_S": "150",
    }))
    assert spec is not None and spec.process_id == rank
    assert jax.process_count() == 2

    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices())
    served = _lead(mesh) if rank == 0 else _follow(mesh)
    print(f"RANK{rank}_LIVE_OK checksum={_checksum(served)}", flush=True)
    # exit barrier: unlike the pre-queued worker, the two ranks finish at
    # different times here (rank 0 stops first) — if rank 0's process (it
    # hosts the coordination service) exits while rank 1 is still busy,
    # rank 1's distributed-shutdown handshake aborts the interpreter
    from jax._src import distributed

    distributed.global_state.client.wait_at_barrier("live-worker-exit",
                                                    120_000)
    # hard-exit past interpreter teardown: the asymmetric shutdown (the
    # leader stops serving before the follower finishes mirroring) leaves
    # the distributed runtime's internal threads in states its destructor
    # aborts on (pthread-cancel of a parked poller -> "exception not
    # rethrown"). Both ranks have printed and synced; nothing of value
    # runs after this line.
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
