"""Tensor-parallel serving engine: sharded decode == single-device decode.

Runs on the virtual 8-device CPU mesh (conftest). The TP engine is the
BASELINE config-5 mechanism (70B TP=8): same engine code, params sharded
Megatron-style, page pools sharded over KV heads, XLA-inserted collectives.
Greedy decode must match the unsharded engine token-for-token, over
floating-point pages and over int8 pages (scale pools shard beside them).
"""

import dataclasses

import jax
import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.parallel import MeshPlan, make_mesh
from gofr_tpu.tpu.paging import PagedLLMEngine

# 4 KV heads so tp=4 still gives every shard a whole head; float32 so the
# sharded reduction order cannot flip an argmax tie at test tolerance
CFG = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4,
                  ffn_dim=128, max_seq_len=128, dtype="float32")

PROMPTS = [[1, 2, 3, 4, 5], [7, 7, 7], [11, 3, 9, 2, 6, 5, 8, 1], [42]]


def run_engine(mesh, kv_dtype=None, n_slots=4):
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, dataclasses.replace(CFG, kv_dtype=kv_dtype),
                         n_slots=n_slots, max_seq_len=64,
                         prefill_buckets=(8,), mesh=mesh, seed=0)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=8, temperature=0.0)
                for p in PROMPTS]
        return [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def reference_outputs():
    return {kv_dtype: run_engine(mesh=None, kv_dtype=kv_dtype)
            for kv_dtype in (None, "int8")}


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("tp", [2, 4])
def test_tp_decode_matches_single_device(tp, kv_dtype, reference_outputs):
    mesh = make_mesh(MeshPlan(tp=tp), devices=jax.devices()[:tp])
    got = run_engine(mesh, kv_dtype)
    assert got == reference_outputs[kv_dtype], f"tp={tp} diverged from tp=1"


def test_tp_rejects_indivisible_heads():
    mesh = make_mesh(MeshPlan(tp=8), devices=jax.devices())
    params = llama_init(CFG, seed=0)  # 4 kv heads cannot split over tp=8
    with pytest.raises(ValueError, match="tp=8 must divide"):
        PagedLLMEngine(params, CFG, n_slots=2, mesh=mesh)


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_tp_cache_is_sharded_over_kv_heads(kv_dtype):
    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices()[:2])
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, dataclasses.replace(CFG, kv_dtype=kv_dtype),
                         n_slots=2, max_seq_len=64, prefill_buckets=(8,),
                         mesh=mesh)

    def half_the_heads():
        # stacked [L, P, Hkv, dh, ps] pools, and [L, P, Hkv, ps] scale
        # pools beside int8 ones: each device holds half the KV heads
        held = [eng.k_cache, eng.v_cache] + (
            [eng.k_scale, eng.v_scale] if kv_dtype else [])
        return all(a.sharding.shard_shape(a.shape)[2] == CFG.n_kv_heads // 2
                   for a in held)

    assert half_the_heads()
    # params: wq column-parallel, wo row-parallel
    wq = eng.params["layers"]["wq"]
    assert wq.sharding.shard_shape(wq.shape)[2] == wq.shape[2] // 2
    wo = eng.params["layers"]["wo"]
    assert wo.sharding.shard_shape(wo.shape)[1] == wo.shape[1] // 2
    # the donated pools come back from a served request as they went in
    eng.start()
    try:
        assert len(eng.generate([1, 2, 3], max_new_tokens=4)) == 4
    finally:
        eng.stop()
    assert half_the_heads()
