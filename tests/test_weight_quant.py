"""INT8 weight quantization: storage, numerics, serving, capacity, TP.

Weights quantize to per-output-channel int8 (models.llama.quantize_weights /
llama_init_quantized); every matmul site routes through _mm/_embed/_head,
which switch on the weight leaf's dtype at trace time — activations quantize
per row and the dot runs int8 x int8 -> int32 (the MXU-native form), so the
weight HBM read genuinely halves instead of materializing a dequant copy.
This is the path that fits Llama-3-8B (~15 GiB bf16) on one 16 GiB v5e chip
(VERDICT r3 missing #1 / BASELINE config 4).
"""

import dataclasses

import pytest

import jax
import jax.numpy as jnp

from gofr_tpu.models.llama import (
    LlamaConfig,
    _q_matmul,
    quantize_leaf,
    llama_forward_nocache,
    llama_init,
    llama_init_quantized,
    params_nbytes,
    quantize_weights,
)
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()
PROMPTS = [list(range(1, 9)), [7, 5, 3], list(range(20, 50)), [11]]


def _qtree():
    return quantize_weights(llama_init(CFG, seed=0))


def test_quantized_tree_structure():
    q = _qtree()
    L, D, F, V = CFG.n_layers, CFG.dim, CFG.ffn_dim, CFG.vocab_size
    H, Hkv, dh = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    layers = q["layers"]
    for name, out_dim in [("wq", H * dh), ("wk", Hkv * dh), ("wv", Hkv * dh),
                          ("wo", D), ("w_gate", F), ("w_up", F),
                          ("w_down", D)]:
        assert layers[name].dtype == jnp.int8
        assert layers[name + "_s"].shape == (L, out_dim)
        assert layers[name + "_s"].dtype == jnp.float32
    assert q["tok_emb"].dtype == jnp.int8
    assert q["tok_emb_s"].shape == (V,)
    assert q["lm_head"].dtype == jnp.int8
    assert q["lm_head_s"].shape == (V,)
    # norms stay float (tiny, precision-critical)
    assert layers["attn_norm"].dtype != jnp.int8
    assert q["final_norm"].dtype != jnp.int8


def test_init_quantized_matches_quantize_at_load():
    """llama_init_quantized never materializes the float tree but must be
    numerically equivalent to quantizing a llama_init tree: int8 codes
    bitwise identical, scales to float-fusion tolerance (the jit fuses
    generate+quantize, so a scale may land 1 ulp off the eager path)."""
    a = _qtree()
    b = llama_init_quantized(CFG, seed=0)
    flat_a = jax.tree_util.tree_leaves_with_path(a)
    flat_b = dict(jax.tree_util.tree_leaves_with_path(b))
    assert len(flat_a) == len(flat_b)
    for path, leaf in flat_a:
        other = flat_b[path]
        if leaf.dtype == jnp.int8:
            assert jnp.array_equal(leaf, other), f"int8 mismatch at {path}"
        else:
            assert jnp.allclose(leaf, other, rtol=1e-6), f"mismatch at {path}"


def test_quantize_consumes_input_tree():
    """quantize_weights pops float leaves as it goes — the documented
    peak-HBM contract (float tree + ONE int8 leaf, never two trees)."""
    fp = llama_init(CFG, seed=0)
    quantize_weights(fp)
    assert "tok_emb" not in fp and "lm_head" not in fp
    assert "wq" not in fp["layers"]


def test_q_matmul_close_to_dequant_reference():
    """The int8 dot + rescale matches the mathematical dequant matmul to
    activation-quantization error (~1/127 per element)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 64), dtype=jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 128),
                          dtype=jnp.float32) * 0.1
    w8, s = quantize_leaf(w, -2)
    ref = x @ (w8.astype(jnp.float32) * s[None, :])
    out = _q_matmul(x, w8, s)
    rel = jnp.linalg.norm(ref - out) / jnp.linalg.norm(ref)
    assert rel < 2e-2, f"relative error {rel}"


def test_logits_close_to_float_model():
    """End-to-end forward: quantized logits track the float model — the
    'logits-close test vs bf16 on the debug preset' (VERDICT r3 next #1)."""
    fp = llama_init(CFG, seed=0)
    q = _qtree()
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                              CFG.vocab_size)
    lf = llama_forward_nocache(fp, CFG, toks)
    lq = llama_forward_nocache(q, CFG, toks)
    assert lq.dtype == jnp.float32
    cos = jnp.sum(lf * lq, -1) / (jnp.linalg.norm(lf, axis=-1)
                                  * jnp.linalg.norm(lq, axis=-1))
    assert float(cos.min()) > 0.99, f"cosine {float(cos.min())}"
    agree = float((jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean())
    assert agree > 0.8, f"top-1 agreement {agree}"


def _serve(params, cfg=CFG, **kw):
    eng = PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32), decode_block_size=4, **kw)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=12, temperature=0.0)
                for p in PROMPTS]
        return [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()


def test_engine_serves_quantized_weights():
    """The serving engine takes an int8 tree unchanged (the weights' dtype
    is the switch): full generations, deterministic, tracking the float
    engine's greedy output closely."""
    out_q = _serve(_qtree())
    assert [len(t) for t in out_q] == [12] * len(PROMPTS)
    assert out_q == _serve(_qtree())           # deterministic
    out_f = _serve(llama_init(CFG, seed=0))
    total = sum(len(t) for t in out_f)
    agree = sum(a == b for f, q in zip(out_f, out_q) for a, b in zip(f, q))
    assert agree / total > 0.5, f"only {agree}/{total} tokens agree"


def test_engine_plan_uses_actual_quantized_bytes():
    """The capacity plan must budget the MEASURED int8 tree, not the
    analytic cfg-dtype estimate (4x larger for an f32-config debug model)."""
    q = _qtree()
    eng = PagedLLMEngine(q, CFG, n_slots=2, max_seq_len=128,
                         prefill_buckets=(8,),
                         budget_bytes=1 << 30)
    assert eng.plan is not None
    assert eng.plan.params_bytes == params_nbytes(q)
    assert eng.plan.params_bytes < CFG.param_count() * 2


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_quantized_tp_mesh_matches_single_device():
    """int8 weights under a tp mesh: scale vectors shard with their weight's
    output axis (serving_param_specs(quantized=True)); the int32 dot
    accumulation is exact under the contraction split, so greedy decode
    matches the single-device quantized engine token-for-token."""
    from gofr_tpu.parallel import MeshPlan, make_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")
    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=8,
                      n_kv_heads=8, ffn_dim=128, max_seq_len=128,
                      dtype="float32")
    mesh = make_mesh(MeshPlan(tp=8))
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [17]]

    def serve(m):
        params = quantize_weights(llama_init(cfg, seed=0))
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


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_quantized_composes_with_int8_kv():
    """Weight quant (HBM for params) and KV quant (HBM for cache) are
    independent axes — both on must still serve deterministically."""
    cfg = dataclasses.replace(CFG, kv_dtype="int8")
    out = _serve(_qtree(), cfg=cfg)
    assert [len(t) for t in out] == [12] * len(PROMPTS)
    assert out == _serve(_qtree(), cfg=cfg)
