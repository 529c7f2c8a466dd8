"""INT8 KV pages: the engine over int8 pools vs over floating-point pools.

The int8 path quantizes K/V on write (per-token per-head scales) and
dequantizes inside the paged kernel's dots; the prefill forward is
full-precision (temps quantize only at the splice), so the FIRST sampled
token must match the fp engine exactly. Later tokens may drift where two
logits are near-ties — asserted as high agreement, plus determinism.
"""

import dataclasses

import pytest

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()
CFG_Q8 = dataclasses.replace(CFG, kv_dtype="int8")

PROMPTS = [list(range(1, 9)), [7, 5, 3], list(range(20, 50)), [11]]


def _serve(cfg, prompts, max_new=12):
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32), decode_block_size=4)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=max_new, temperature=0.0)
                for p in prompts]
        return [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()


def test_q8_engine_serves_and_matches_fp_closely():
    fp = _serve(CFG, PROMPTS)
    q8 = _serve(CFG_Q8, PROMPTS)
    assert [len(t) for t in q8] == [len(t) for t in fp]
    # prefill is full-precision in both: first sampled token identical
    for fp_toks, q8_toks in zip(fp, q8):
        assert fp_toks[0] == q8_toks[0]
    # decode reads differ only by int8 rounding: near-ties may flip, the
    # bulk must agree
    total = sum(len(t) for t in fp)
    agree = sum(a == b for fp_t, q8_t in zip(fp, q8)
                for a, b in zip(fp_t, q8_t))
    assert agree / total > 0.7, f"only {agree}/{total} tokens agree"


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_q8_engine_deterministic():
    a = _serve(CFG_Q8, PROMPTS)
    b = _serve(CFG_Q8, PROMPTS)
    assert a == b


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_q8_chunked_prefill_matches_fused():
    """Chunked admission over the int8 pools: same lengths and (near) the
    fused-q8 tokens; lengths, determinism, and bulk agreement are the
    contract."""
    fused = _serve(CFG_Q8, PROMPTS)

    def serve_chunked():
        params = llama_init(CFG, seed=0)
        eng = PagedLLMEngine(params, CFG_Q8, n_slots=4, max_seq_len=128,
                             prefill_buckets=(8, 32), decode_block_size=4,
                             chunk_prefill_tokens=8)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=12, temperature=0.0)
                    for p in PROMPTS]
            return [r.result(timeout_s=300) for r in reqs]
        finally:
            eng.stop()

    chunked = serve_chunked()
    assert [len(t) for t in chunked] == [len(t) for t in fused]
    assert chunked == serve_chunked()          # deterministic
    total = sum(len(t) for t in fused)
    agree = sum(a == b for f, c in zip(fused, chunked)
                for a, b in zip(f, c))
    assert agree / total > 0.6, f"only {agree}/{total} tokens agree"


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_q8_engine_tp_mesh_matches_single_device():
    """int8 KV under a tp mesh: values shard KV heads (kv_cache_spec),
    scales shard alongside (kv_scale_pool_spec); greedy decode must match
    the single-device q8 engine token-for-token."""
    import jax

    from gofr_tpu.parallel import MeshPlan, make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    cfg = dataclasses.replace(
        LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=8,
                    n_kv_heads=8, ffn_dim=128, max_seq_len=128,
                    dtype="float32"),
        kv_dtype="int8")
    mesh = make_mesh(MeshPlan(tp=8))
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [17]]

    def serve(m):
        params = llama_init(dataclasses.replace(cfg, kv_dtype=None), seed=0)
        eng = PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=64,
                             prefill_buckets=(8,), mesh=m)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=6, temperature=0.0)
                    for p in prompts]
            return [r.result(timeout_s=240) for r in reqs]
        finally:
            eng.stop()

    assert serve(mesh) == serve(None)
