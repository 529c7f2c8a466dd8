"""Worker for the multi-host TP SERVING test (test_multihost_exec.py).

Serves the SAME prompts twice inside one 2-process jax.distributed job:
once on a single local device (the per-process oracle), once TENSOR-
PARALLEL over a tp=2 mesh whose two devices live in DIFFERENT processes —
the per-layer Megatron all-reduces cross the process boundary over
localhost DCN. Token-for-token equality proves the serving engine's
multi-host path end to end (config 5's DCN story), not just a bare
all-reduce.

Determinism contract: both ranks run identical Python; all requests are
queued BEFORE the engine loop starts, so the dispatch sequence (admission
wave, block decodes, syncs) is identical in both processes — the
multi-controller requirement.

Usage: python multihost_serving_worker.py <rank> <coordinator_port>
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from gofr_tpu.config import MockConfig  # noqa: E402
from gofr_tpu.models.llama import LlamaConfig, llama_init  # noqa: E402
from gofr_tpu.parallel import MeshPlan, make_mesh  # noqa: E402
from gofr_tpu.parallel.multihost import initialize_from_config  # noqa: E402
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

PROMPTS = [[1, 2, 3, 4], [9, 8, 7], [5]]


def _serve(mesh):
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=2,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=64,
                      dtype="float32")
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=64,
                         prefill_buckets=(8,), decode_block_size=4, mesh=mesh)
    # queue everything BEFORE the loop starts: deterministic dispatch order
    reqs = [eng.submit(p, max_new_tokens=6, temperature=0.0)
            for p in PROMPTS]
    eng.start()
    try:
        return [r.result(timeout_s=240) for r in reqs]
    finally:
        eng.stop()


def main() -> None:
    rank, port = int(sys.argv[1]), sys.argv[2]
    spec = initialize_from_config(MockConfig({
        "JAX_COORDINATOR_ADDR": f"127.0.0.1:{port}",
        "JAX_NUM_PROCESSES": "2",
        "JAX_PROCESS_ID": str(rank),
        "JAX_COORDINATOR_TIMEOUT_S": "150",
    }))
    assert spec is not None and spec.process_id == rank
    assert jax.process_count() == 2
    assert len(jax.devices()) == 2        # one virtual CPU device per rank
    assert len(jax.local_devices()) == 1

    oracle = _serve(None)                  # local single-device engine
    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices())
    served = _serve(mesh)                  # tp spans BOTH processes
    assert served == oracle, (served, oracle)
    checksum = sum(t * (i + 1) for i, toks in enumerate(served)
                   for t in toks)
    print(f"RANK{rank}_SERVING_OK checksum={checksum}", flush=True)


if __name__ == "__main__":
    main()
