"""Llama-family decoder: GQA + RoPE + RMSNorm + SwiGLU, cache-aware forward.

Built TPU-first rather than ported: weights are stacked [n_layers, ...] and
consumed by lax.scan (single-layer trace -> fast XLA compiles, natural
pipeline sharding axis); matmuls stay bfloat16 for the MXU with float32
softmax/norm accumulation; the KV cache is an explicit argument so serving
code can donate it for in-place HBM updates (no torch-style module state).

The unified `llama_forward` serves both phases of LLM serving:
  - prefill: T>1 tokens written at positions [0..T), causal within the window
  - decode:  T=1 token written at its absolute position, attending the cache
Masking needs only `j <= q_pos` because cache slots are written contiguously
from 0 — slot index IS absolute position.

Config presets cover the BASELINE.md north-star ladder (debug CI model,
1B bench model, Llama-3-8B, Llama-3-70B).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    # "xla" | "flash" — selects the attention impl for the no-cache forward
    # (training/eval) AND the serving prefill (full-window T == S case in
    # _attention_block)
    attn_impl: str = "xla"
    # None (= cfg.dtype) | "int8" — the page pool's storage dtype. int8
    # halves pool HBM bytes (the decode bandwidth bound) and doubles
    # context capacity per GiB; values quantize on write with per-token
    # per-head scales and the paged kernel dequant-folds inside its dots
    kv_dtype: Optional[str] = None

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def kv_layers(self) -> int:
        """Every block keeps K and V in pages."""
        return self.n_layers

    state_bytes_per_slot = 0    # a sequence's only cached state is pages

    def paged_model(self):
        """The paged engine's view of this family (models/protocol.py):
        the paged floating-point path, unchanged in arithmetic."""
        from .protocol import PagedModel, kv_planes, one_group

        def prefill(params, tokens, lengths, mesh=None):
            last, k, v, rows = llama_prefill_paged(params, self, tokens,
                                                   lengths, mesh)
            return last, (k, v), rows

        return PagedModel(
            family="llama_like", program_tag="llama",
            planes=kv_planes(self.n_kv_heads, self.head_dim),
            groups=one_group(self.n_layers), state_shapes=lambda slots: (),
            prefill=prefill,
            decode=lambda params, tokens, positions, pools, table, state,
            tail, step, mesh=None: (*llama_decode_step_paged(
                params, self, tokens, positions, *pools, table, tail, step,
                mesh), state, None))

    @classmethod
    def debug(cls) -> "LlamaConfig":
        """CI-sized model: compiles in seconds on CPU."""
        return cls(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                   ffn_dim=128, max_seq_len=256, dtype="float32")

    @classmethod
    def llama1b(cls) -> "LlamaConfig":
        """Llama-3.2-1B shape: the single-v5e-chip bench model."""
        return cls(vocab_size=128256, dim=2048, n_layers=16, n_heads=32,
                   n_kv_heads=8, ffn_dim=8192, max_seq_len=8192)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, ffn_dim=14336, max_seq_len=8192)

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(vocab_size=128256, dim=8192, n_layers=80, n_heads=64,
                   n_kv_heads=8, ffn_dim=28672, max_seq_len=8192)

    def param_count(self) -> int:
        embed = self.vocab_size * self.dim
        per_layer = (self.dim * self.n_heads * self.head_dim          # wq
                     + 2 * self.dim * self.n_kv_heads * self.head_dim  # wk, wv
                     + self.n_heads * self.head_dim * self.dim         # wo
                     + 3 * self.dim * self.ffn_dim                     # gate/up/down
                     + 2 * self.dim)                                   # norms
        return 2 * embed + self.n_layers * per_layer + self.dim


def llama_init(cfg: LlamaConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init params pytree with stacked [L, ...] layer weights."""
    import jax
    import jax.numpy as jnp

    dtype = np_dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 8)
    L, D, H, Hkv, dh, F, V = (cfg.n_layers, cfg.dim, cfg.n_heads, cfg.n_kv_heads,
                              cfg.head_dim, cfg.ffn_dim, cfg.vocab_size)

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    return {
        "tok_emb": init(keys[0], (V, D), D),
        "layers": {
            "wq": init(keys[1], (L, D, H * dh), D),
            "wk": init(keys[2], (L, D, Hkv * dh), D),
            "wv": init(keys[3], (L, D, Hkv * dh), D),
            "wo": init(keys[4], (L, H * dh, D), H * dh),
            "w_gate": init(keys[5], (L, D, F), D),
            "w_up": init(keys[6], (L, D, F), D),
            "w_down": init(keys[7], (L, F, D), F),
            "attn_norm": jnp.ones((L, D), dtype=dtype),
            "ffn_norm": jnp.ones((L, D), dtype=dtype),
        },
        "final_norm": jnp.ones((D,), dtype=dtype),
        "lm_head": init(keys[0], (D, V), D),
    }


def init_kv_cache(cfg: LlamaConfig, batch: int, seq_len: Optional[int] = None,
                  dtype: Optional[str] = None) -> Tuple[Any, Any]:
    """Zeroed (k, v) caches shaped [L, B, Hkv, dh, S].

    S is the MINOR axis on purpose: TPU tiles the two minor dims to
    (8 sublanes, 128 lanes), so a [.., Hkv, dh=64]-minor cache pads dh
    64->128 and physically DOUBLES every cache buffer in HBM (measured in
    the round-2 OOM dump: 4.00G padded vs 2.00G unpadded per buffer).
    With [.., dh, S] minor, S is always a multiple of 128 in serving
    (power-of-two buckets >= 128; smaller allocations are tiny) and dh=64
    divides the 8-sublane tile — zero padding waste, and the decode
    einsums contract/broadcast directly on this layout.
    """
    import jax.numpy as jnp

    S = seq_len or cfg.max_seq_len
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.head_dim, S)
    dt = np_dtype(dtype or cfg.dtype)
    return jnp.zeros(shape, dtype=dt), jnp.zeros(shape, dtype=dt)


import jax  # noqa: E402  (after dataclass defs so module import stays light)
import jax.numpy as jnp  # noqa: E402

from .blocks import attended_in_block, np_dtype, rms_norm, rope  # noqa: E402


# ---------------------------------------------------------------------------
# INT8 weight quantization (weight-only storage, W8A8-dynamic compute)
#
# The north-star model (Llama-3-8B, BASELINE.md config 4) is ~15 GiB in bf16
# — it does not fit one 16 GiB v5e chip at all. Per-output-channel int8
# weights halve that to ~8 GiB AND halve the per-step weight HBM read, which
# is the other half of the decode bandwidth bound next to the KV cache.
#
# Design (TPU-first, not a dequant-copy):
#   - storage: W -> int8 with per-output-channel scales s = absmax/127.
#     A "dequantize then matmul" lowering would materialize a bf16 copy of
#     the weight as a fusion output every step — MORE HBM traffic than bf16
#     weights. Instead activations quantize dynamically per row (absmax
#     over the contraction dim) and the dot runs int8 x int8 -> int32 on
#     the MXU natively (2x bf16 peak on v5e), reading the int8 weights
#     straight from HBM. Output rescales by (row_scale ⊗ channel_scale).
#   - mode selection: the weights' dtype IS the switch. Every matmul site
#     goes through _mm/_embed/_head, which branch on `w.dtype == int8` at
#     trace time — no config plumbing, and a bf16 tree serves identically
#     to before.
#   - norms stay float (tiny); embedding gathers int8 rows and rescales
#     per token (a [B, T, D] elementwise — negligible).
# ---------------------------------------------------------------------------


def _q_matmul(x, w8, s, out_dtype=None):
    """Weight-only int8 matmul with dynamic per-row activation quantization.

    x: [..., Din] float; w8: [Din, Dout] int8; s: [Dout] f32 per-output-
    channel weight scales. Returns [..., Dout] in out_dtype (default
    x.dtype). Under tensor parallelism the row absmax over a tp-sharded
    contraction dim lowers to a tiny [rows, 1] collective max — XLA
    propagates the sharding; no manual collectives here.
    """
    xf = x.astype(jnp.float32)
    ax = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True), 1e-12)
    x8 = jnp.round(xf * (127.0 / ax)).astype(jnp.int8)
    acc = jax.lax.dot_general(
        x8, w8, (((x8.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (ax / 127.0) * s
    return out.astype(out_dtype or x.dtype)


def _mm(x, tree, name):
    """x @ tree[name], through the int8 path when the weight is quantized."""
    w = tree[name]
    if w.dtype == jnp.int8:
        return _q_matmul(x, w, tree[name + "_s"])
    return x @ w


def _embed(params, cfg: LlamaConfig, tokens):
    """Token embedding gather; dequantizes per-row when tok_emb is int8."""
    e = params["tok_emb"][tokens]
    if e.dtype == jnp.int8:
        scale = params["tok_emb_s"][tokens]          # [...,] f32 per row
        return (e.astype(jnp.float32) * scale[..., None]).astype(
            np_dtype(cfg.dtype))
    return e


def _head(x, params):
    """lm_head projection to float32 logits (int8-aware)."""
    w = params["lm_head"]
    if w.dtype == jnp.int8:
        return _q_matmul(x, w, params["lm_head_s"], out_dtype=jnp.float32)
    return (x @ w).astype(jnp.float32)


def quantize_leaf(w, axis: int):
    """Symmetric per-channel int8: returns (w8, scale) with scale shaped as
    w minus `axis` (the contraction dim)."""
    wf = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(wf), axis=axis), 1e-12) / 127.0
    w8 = jnp.clip(jnp.round(wf / jnp.expand_dims(s, axis)), -127, 127
                  ).astype(jnp.int8)
    return w8, s


# weight name -> contraction axis reduced away by its scale. Layer weights
# are stacked [L, in, out]; tok_emb [V, D] scales per row (gather dim);
# lm_head [D, V] per output channel. Norm vectors stay float.
QUANT_AXES = {"wq": -2, "wk": -2, "wv": -2, "wo": -2,
               "w_gate": -2, "w_up": -2, "w_down": -2}


def quantize_weights(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize a bf16/f32 params tree to int8 storage, leaf by leaf.

    CONSUMES the input tree: each float leaf is popped out of the nested
    dicts as its int8 twin is built, so (given the caller holds no other
    references to the leaves) peak HBM is the float tree plus ONE leaf's
    int8 copy — not two full trees. For models whose float tree already
    crowds the chip, use llama_init_quantized, which never materializes
    the float tree at all.
    """
    q = jax.jit(quantize_leaf, static_argnums=1)

    out_layers = {}
    layers = params["layers"]
    for name in list(QUANT_AXES):
        w8, s = q(layers.pop(name), QUANT_AXES[name])
        jax.block_until_ready(w8)
        out_layers[name] = w8
        out_layers[name + "_s"] = s
    out_layers["attn_norm"] = layers["attn_norm"]
    out_layers["ffn_norm"] = layers["ffn_norm"]
    tok8, tok_s = q(params.pop("tok_emb"), -1)
    jax.block_until_ready(tok8)   # embed-sized float temps must not overlap
    head8, head_s = q(params.pop("lm_head"), -2)
    return {
        "tok_emb": tok8, "tok_emb_s": tok_s,
        "layers": out_layers,
        "final_norm": params["final_norm"],
        "lm_head": head8, "lm_head_s": head_s,
    }


def llama_init_quantized(cfg: LlamaConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init DIRECTLY to int8 storage, one leaf at a time.

    Generates each float leaf inside a jit whose only outputs are the int8
    weight and its scales, so the float tensor is a program temporary —
    peak HBM is the accumulated int8 tree plus one float leaf (~13 GiB for
    8B vs ~17 GiB for init-then-quantize, which OOMs a 16 GiB chip).
    Numerically identical to quantize_weights(llama_init(cfg, seed)).
    """
    dtype = np_dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 8)
    L, D, H, Hkv, dh, F, V = (cfg.n_layers, cfg.dim, cfg.n_heads,
                              cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
                              cfg.vocab_size)

    import functools

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def gen_q(k, shape, fan_in, axis):
        w = (jax.random.normal(k, shape, dtype=jnp.float32)
             * (1.0 / math.sqrt(fan_in))).astype(dtype)
        return quantize_leaf(w, axis)

    # (key, shape, fan_in, scale axis) — mirrors llama_init's spec table
    spec = {
        "wq": (keys[1], (L, D, H * dh), D, -2),
        "wk": (keys[2], (L, D, Hkv * dh), D, -2),
        "wv": (keys[3], (L, D, Hkv * dh), D, -2),
        "wo": (keys[4], (L, H * dh, D), H * dh, -2),
        "w_gate": (keys[5], (L, D, F), D, -2),
        "w_up": (keys[6], (L, D, F), D, -2),
        "w_down": (keys[7], (L, F, D), F, -2),
    }
    layers: Dict[str, Any] = {}
    for name, (k, shape, fan, axis) in spec.items():
        w8, s = gen_q(k, shape, fan, axis)
        jax.block_until_ready(w8)    # keep at most one float temp live
        layers[name] = w8
        layers[name + "_s"] = s
    layers["attn_norm"] = jnp.ones((L, D), dtype=dtype)
    layers["ffn_norm"] = jnp.ones((L, D), dtype=dtype)
    tok8, tok_s = gen_q(keys[0], (V, D), D, -1)
    jax.block_until_ready(tok8)   # embed-sized float temps must not overlap
    head8, head_s = gen_q(keys[0], (D, V), D, -2)
    return {
        "tok_emb": tok8, "tok_emb_s": tok_s,
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype=dtype),
        "lm_head": head8, "lm_head_s": head_s,
    }


def params_nbytes(params) -> int:
    """Actual HBM bytes of a params tree (int8-aware, unlike the analytic
    cfg-based estimate in tpu/capacity.params_bytes)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
               if hasattr(leaf, "nbytes"))


def _attention_block(x, layer, k_cache_l, v_cache_l, positions, cfg: LlamaConfig,
                     mesh=None):
    """One attention sublayer with cache write + masked read.

    x: [B, T, D]; k/v_cache_l: [B, Hkv, dh, S] (S-minor, see init_kv_cache);
    positions: [B, T]. Returns (out [B, T, D], k_cache_l, v_cache_l).

    When T == S (a full-window prefill: positions are arange over the
    window, so the cache after the write IS this chunk's k/v) and
    cfg.attn_impl == "flash", attention runs through the Pallas flash
    kernel on the fresh k/v tensors — no [T, S] score materialization in
    HBM and no layout shuffling of the cache.

    mesh: the engine's tensor-parallel mesh, or None. Every cache-aware
    step function below takes it and hands it to the Pallas kernels, which
    run per tp shard under shard_map (ops/paged_attention.paged_attention
    says why); the XLA attention paths partition under plain jit.
    """
    B, T, D = x.shape
    S = k_cache_l.shape[-1]
    H, Hkv, dh, G = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv

    normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q = _mm(normed, layer, "wq").reshape(B, T, H, dh)
    k = _mm(normed, layer, "wk").reshape(B, T, Hkv, dh)
    v = _mm(normed, layer, "wv").reshape(B, T, Hkv, dh)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    # scatter this chunk's k/v into the cache at its absolute positions
    # (advanced indexing on dims 0+3 puts the [B, T] index dims first, so
    # the value shape is [B, T, Hkv, dh] — k/v as produced, no transpose)
    batch_idx = jnp.arange(B)[:, None]
    k_cache_l = k_cache_l.at[batch_idx, :, :, positions].set(k)
    v_cache_l = v_cache_l.at[batch_idx, :, :, positions].set(v)

    if T == S and cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention

        attn = flash_attention(q, k, v, True, mesh=mesh)  # [B, T, H, dh]
        out = _mm(attn.reshape(B, T, H * dh), layer, "wo")
        return out, k_cache_l, v_cache_l

    # GQA attention over the cache: q grouped [B, T, Hkv, G, dh].
    # Keep the matmul inputs in the cache dtype (bf16 on the MXU's fast
    # path) and accumulate f32 via preferred_element_type — upcasting the
    # INPUTS would force a full-f32 matmul at a fraction of MXU throughput.
    qg = q.reshape(B, T, Hkv, G, dh)
    scores = jnp.einsum("bthgd,bhds->bhgts", qg, k_cache_l,
                        preferred_element_type=jnp.float32) / math.sqrt(dh)
    # mask: query at absolute pos p sees cache slot j iff j <= p
    cache_pos = jnp.arange(S)[None, None, :]                  # [1, 1, S]
    visible = cache_pos <= positions[:, :, None]              # [B, T, S]
    scores = jnp.where(visible[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bhds->bthgd", probs.astype(v_cache_l.dtype),
                     v_cache_l,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    out = _mm(out.reshape(B, T, H * dh), layer, "wo")
    return out, k_cache_l, v_cache_l


def ffn_block(x, layer, cfg: LlamaConfig):
    normed = rms_norm(x, layer["ffn_norm"], cfg.rms_eps)
    gate = jax.nn.silu(_mm(normed, layer, "w_gate"))
    up = _mm(normed, layer, "w_up")
    return _mm(gate * up, layer, "w_down")


def llama_forward_hidden(params, cfg: LlamaConfig, tokens, positions, k_cache,
                         v_cache, mesh=None):
    """Cache-writing forward returning final-norm hidden states, NOT logits.

    tokens: [B, T] int32; positions: [B, T] absolute positions (row-wise
    monotonic); k/v_cache: [L, B, Hkv, dh, S] (S-minor).
    Returns (hidden [B, T, D], k_cache, v_cache).

    The lm_head projection is split out so callers that only need a few
    positions (serving prefill samples ONE token per row) can gather those
    hidden rows first and project [K, D] @ [D, V] instead of materializing
    [B, T, V] float32 logits — at Llama-3 vocab (128256) the full-logits
    buffer is GBs per fused admission and the dominant prefill FLOP waste.
    """
    x = _embed(params, cfg, tokens)

    def body(x, scan_in):
        layer, k_l, v_l = scan_in
        attn_out, k_l, v_l = _attention_block(x, layer, k_l, v_l, positions,
                                              cfg, mesh)
        x = x + attn_out
        x = x + ffn_block(x, layer, cfg)
        return x, (k_l, v_l)

    x, (k_cache, v_cache) = jax.lax.scan(
        body, x, (params["layers"], k_cache, v_cache))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x, k_cache, v_cache


def llama_forward(params, cfg: LlamaConfig, tokens, positions, k_cache, v_cache,
                  mesh=None):
    """Cache-writing forward over a token chunk.

    tokens: [B, T] int32; positions: [B, T] absolute positions (row-wise
    monotonic); k/v_cache: [L, B, Hkv, dh, S] (S-minor).
    Returns (logits [B, T, V] float32, k_cache, v_cache).
    """
    x, k_cache, v_cache = llama_forward_hidden(params, cfg, tokens, positions,
                                               k_cache, v_cache, mesh)
    logits = _head(x, params)
    return logits, k_cache, v_cache


def llama_prefill_last(params, cfg: LlamaConfig, tokens, positions, lengths,
                       k_cache, v_cache, mesh=None):
    """Prefill forward that projects ONLY each row's last prompt position.

    tokens: [B, T]; positions: [B, T]; lengths: [B] true prompt lengths.
    Returns (last_logits [B, V] float32, k_cache, v_cache).

    Gathering the [B, D] last-position hidden rows BEFORE the lm_head matmul
    keeps the vocab projection at [B, D] @ [D, V] — no [B, T, V] buffer, no
    T× wasted head FLOPs (VERDICT r2 missing #3).
    """
    hidden, k_cache, v_cache = llama_forward_hidden(
        params, cfg, tokens, positions, k_cache, v_cache, mesh)
    B = hidden.shape[0]
    last = hidden[jnp.arange(B), lengths - 1]  # [B, D]
    logits = _head(last, params)
    return logits, k_cache, v_cache


def llama_prefill_paged(params, cfg: LlamaConfig, tokens, lengths, mesh=None):
    """The protocol's prefill (models/protocol.py): a [K, bucket] window
    from an empty cache into window-sized temporaries the engine's page
    writer scatters. Returns (last logits, k, v [L, K, Hkv, dh, bucket],
    ()): no state beside the pages."""
    K, bucket = tokens.shape
    tmp_k = jnp.zeros((cfg.n_layers, K, cfg.n_kv_heads, cfg.head_dim, bucket),
                      dtype=np_dtype(cfg.dtype))
    pos_grid = jnp.broadcast_to(
        jnp.arange(bucket, dtype=jnp.int32)[None, :], (K, bucket))
    last, tmp_k, tmp_v = llama_prefill_last(
        params, cfg, tokens, pos_grid, lengths, tmp_k, jnp.zeros_like(tmp_k),
        mesh)
    return last, tmp_k, tmp_v, ()


def llama_prefill(params, cfg: LlamaConfig, tokens, k_cache, v_cache):
    """Prefill from empty cache: positions are [0..T) for every row."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    return llama_forward(params, cfg, tokens, positions, k_cache, v_cache)


def llama_decode_step(params, cfg: LlamaConfig, tokens, positions, k_cache,
                      v_cache):
    """One decode step for every batch row.

    tokens: [B] current token per row; positions: [B] its absolute position.
    Returns (logits [B, V], k_cache, v_cache).
    """
    logits, k_cache, v_cache = llama_forward(
        params, cfg, tokens[:, None], positions[:, None], k_cache, v_cache)
    return logits[:, 0, :], k_cache, v_cache


def llama_prefill_chunk(params, cfg: LlamaConfig, tokens, positions,
                        k_layers, v_layers, slots, project_last=None,
                        mesh=None):
    """One CHUNK of a cached prefill over per-layer window buffers (the
    paged engine's per-job temps, scattered into pages at the final chunk).

    tokens: [K, C] the chunk's token ids; positions: [K, C] their absolute
    positions (a later chunk attends the earlier chunks' KV already written
    in the rows — the mask `j <= q_pos` needs nothing more);
    k/v_layers: per-layer tuples ([B, Hkv, dh, S]); slots: [K] row
    ids. Gathers the K rows, runs the cache-aware attention for the chunk,
    scatters the rows back.

    project_last: int32 [K] of within-chunk last indices — gathers those
    hidden rows and projects [K, V] logits. The engine passes it for EVERY
    chunk (a short row's true last position may fall in any chunk; the
    carried `selected` buffer keeps the right one). None skips the lm_head
    projection entirely for callers that only need the cache side effect.

    This is the building block for chunked prefill: a long prompt is
    admitted as several bounded dispatches so decode blocks (and other
    admissions) interleave instead of stalling behind one huge prefill —
    the TTFT lever for mixed traffic.
    Returns (logits [K, V] or None, k_layers, v_layers).
    """
    k_out = list(k_layers)
    v_out = list(v_layers)
    x = _embed(params, cfg, tokens)                        # [K, C, D]
    for l in range(cfg.n_layers):
        layer = jax.tree_util.tree_map(lambda w: w[l], params["layers"])
        k_rows = k_out[l][slots]                           # [K, Hkv, dh, S]
        v_rows = v_out[l][slots]
        attn, k_rows, v_rows = _attention_block(x, layer, k_rows, v_rows,
                                                positions, cfg, mesh)
        x = x + attn
        x = x + ffn_block(x, layer, cfg)
        k_out[l] = k_out[l].at[slots].set(k_rows)
        v_out[l] = v_out[l].at[slots].set(v_rows)
    if project_last is None:
        return None, tuple(k_out), tuple(v_out)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    K = x.shape[0]
    last = x[jnp.arange(K), project_last]                  # [K, D]
    logits = _head(last, params)
    return logits, tuple(k_out), tuple(v_out)


def _attended_lengths(table, positions):
    """What each row of a per-token paged decode step attends (the int8
    pools' step): its context with the token just written, or NOTHING for
    a row that holds no request (ops/paged_attention `holds_request`):
    read as a length, such a row's stale position would walk the garbage
    page up to the table's width, every layer of every step."""
    from ..ops.paged_attention import holds_request

    return jnp.where(holds_request(table), positions + 1, 0)


def llama_decode_step_paged(params, cfg: LlamaConfig, tokens, positions,
                            k_pool, v_pool, table, tail, step, mesh=None):
    """One decode step of a block against a PAGED KV cache.

    tokens: [B]; positions: [B] absolute positions of these tokens;
    k/v_pool: [L, P, Hkv, dh, page_size] as they were when the block began,
    read only; table: [B, NP] page ids per slot (entries past a slot's live
    pages are not read; a row that starts at page 0 holds no request and
    attends nothing); tail: the block's (k_tail, v_tail), `step` this
    step's index in the block.
    Returns (logits [B, V] float32, tail).

    Per-layer, ONE kernel call (paged_attention_in_block): this token's
    K/V go into the tail at `step`, and attention reads the row's pages
    through the block table and the tail's first step + 1 tokens in one
    softmax — per-step HBM traffic tracks the live pages, not a dense
    [B, S] allocation, and no page is written until the block is over
    (`paged_flush_block`, the engine's).

    The STACKED pools and tails are handed whole to the kernel with the
    layer index; neither a slice of one layer nor an XLA scatter ever
    touches the pools (ops/paged_attention's module docstring says what
    either costs on the chip).
    """
    from ..ops.paged_attention import paged_attention_in_block

    B = tokens.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = _embed(params, cfg, tokens)[:, None]               # [B, 1, D]
    pos_grid = positions[:, None]                          # [B, 1]
    lengths, tail_lens = attended_in_block(table, positions, step)

    def layer_body(l, state):
        x, k_tail, v_tail = state
        layer = jax.tree_util.tree_map(lambda w: w[l], params["layers"])
        normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = rope(_mm(normed, layer, "wq").reshape(B, 1, H, dh), pos_grid,
                 cfg.rope_theta)
        k = rope(_mm(normed, layer, "wk").reshape(B, 1, Hkv, dh), pos_grid,
                 cfg.rope_theta)
        v = _mm(normed, layer, "wv").reshape(B, 1, Hkv, dh)
        attn, k_tail, v_tail = paged_attention_in_block(
            q[:, 0], k[:, 0], v[:, 0], k_pool, v_pool, k_tail, v_tail,
            table, lengths, tail_lens, layer=l, mesh=mesh)
        x = x + _mm(attn.reshape(B, 1, H * dh), layer, "wo")
        x = x + ffn_block(x, layer, cfg)
        return x, k_tail, v_tail

    x, k_tail, v_tail = jax.lax.fori_loop(
        0, cfg.n_layers, layer_body, (x, *tail))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _head(x[:, 0], params)
    return logits, (k_tail, v_tail)


def llama_decode_step_paged_q8(params, cfg: LlamaConfig, tokens, positions,
                               k_pool, v_pool, ks_pool, vs_pool, table,
                               mesh=None):
    """One decode step against an INT8 paged KV pool.

    MIRRORS llama_decode_step_paged with per-token scales: k/v_pool are
    [L, P, Hkv, dh, ps] int8, ks/vs_pool [L, P, Hkv, ps] float32. The new
    token's K/V quantize on write; the paged kernel reads the int8 pages
    with dequant folded into its dots — pool HBM bytes halve, so both the
    per-step read AND the page capacity per GiB double.
    Returns (logits [B, V] f32, k_pool, v_pool, ks_pool, vs_pool).
    """
    from ..ops.paged_attention import quantize_kv
    from ..ops.paged_attention import paged_attention, paged_write_decode

    B = tokens.shape[0]
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    x = _embed(params, cfg, tokens)[:, None]               # [B, 1, D]
    pos_grid = positions[:, None]
    lengths = _attended_lengths(table, positions)

    def layer_body(l, state):
        x, k_pool, v_pool, ks_pool, vs_pool = state
        layer = jax.tree_util.tree_map(lambda w: w[l], params["layers"])
        normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = rope(_mm(normed, layer, "wq").reshape(B, 1, H, dh), pos_grid,
                 cfg.rope_theta)
        k = rope(_mm(normed, layer, "wk").reshape(B, 1, Hkv, dh), pos_grid,
                 cfg.rope_theta)
        v = _mm(normed, layer, "wv").reshape(B, 1, Hkv, dh)
        k8, ks = quantize_kv(k[:, 0], axis=-1)             # [B,Hkv,dh],[B,Hkv]
        v8, vs = quantize_kv(v[:, 0], axis=-1)
        k_pool, v_pool, ks_pool, vs_pool = paged_write_decode(
            k_pool, v_pool, k8, v8, table, positions, ks_pool, vs_pool,
            ks, vs, layer=l, mesh=mesh)
        attn = paged_attention(q[:, 0], k_pool, v_pool, table, lengths,
                               ks_pool, vs_pool, layer=l, mesh=mesh)
        x = x + _mm(attn.reshape(B, 1, H * dh), layer, "wo")
        x = x + ffn_block(x, layer, cfg)
        return x, k_pool, v_pool, ks_pool, vs_pool

    x, k_pool, v_pool, ks_pool, vs_pool = jax.lax.fori_loop(
        0, cfg.n_layers, layer_body, (x, k_pool, v_pool, ks_pool, vs_pool))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = _head(x[:, 0], params)
    return logits, k_pool, v_pool, ks_pool, vs_pool


def llama_verify_step_paged(params, cfg: LlamaConfig, tokens, drafts,
                            positions, k_pool, v_pool, table, mesh=None):
    """Speculative-decode VERIFY against the PAGED pool.

    Scores each slot's current (already-sampled) token plus d drafted
    tokens in ONE cache-writing forward (junk draft rows allowed:
    acceptance is decided by the caller against `greedy`):

      - the window's K/V scatter into pages via paged_write_decode, one
        window position at a time — positions past a slot's reservation
        map to zero table entries, i.e. the garbage page, so overrun junk
        can never land in a live page (the allocator invariant)
      - the window attention gathers each slot's pages into contiguous
        [B, Hkv, dh, NP*ps] rows (ONE pool read per layer — the paged
        kernel is a T=1 read; d+1 kernel calls would re-stream the live
        pages d+1 times) and runs the masked einsum over them.
        Page j of a slot's table covers absolute positions [j*ps, (j+1)*ps),
        so gathered offset IS absolute position and the `j <= q_pos` mask
        carries over unchanged.

    Junk-safety: rejected window positions hold
    junk that the eventual real occupant overwrites before any query
    attends it (lock-step invariant), and garbage-page content is only
    reachable at offsets the mask already excludes for live queries.

    tokens: [B]; drafts: [B, d]; positions: [B]; k/v_pool:
    [L, P, Hkv, dh, ps]; table: [B, NP].
    Returns (greedy [B, d+1] int32, logits0 [B, V] f32, k_pool, v_pool).
    """
    from ..ops.paged_attention import paged_write_decode

    B, d = drafts.shape
    H, Hkv, dh, G = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    ps = k_pool.shape[-1]
    NP = table.shape[1]
    S = NP * ps
    window = jnp.concatenate([tokens[:, None], drafts], axis=1)  # [B, d+1]
    pos_grid = positions[:, None] + jnp.arange(d + 1, dtype=jnp.int32)[None, :]
    x = _embed(params, cfg, window)

    def layer_body(l, state):
        x, k_pool, v_pool = state
        layer = jax.tree_util.tree_map(lambda w: w[l], params["layers"])
        normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = rope(_mm(normed, layer, "wq").reshape(B, d + 1, H, dh),
                 pos_grid, cfg.rope_theta)
        k = rope(_mm(normed, layer, "wk").reshape(B, d + 1, Hkv, dh),
                 pos_grid, cfg.rope_theta)
        v = _mm(normed, layer, "wv").reshape(B, d + 1, Hkv, dh)
        # window write BEFORE the gather so the gathered rows already
        # contain this window's fresh K/V
        for i in range(d + 1):
            k_pool, v_pool = paged_write_decode(
                k_pool, v_pool, k[:, i], v[:, i], table, positions + i,
                layer=l, mesh=mesh)
        k_rows = jnp.moveaxis(k_pool[l, table], 1, 3).reshape(B, Hkv, dh, S)
        v_rows = jnp.moveaxis(v_pool[l, table], 1, 3).reshape(B, Hkv, dh, S)
        qg = q.reshape(B, d + 1, Hkv, G, dh)
        scores = jnp.einsum("bthgd,bhds->bhgts", qg, k_rows,
                            preferred_element_type=jnp.float32
                            ) / math.sqrt(dh)
        cache_pos = jnp.arange(S)[None, None, :]                 # [1, 1, S]
        visible = cache_pos <= pos_grid[:, :, None]              # [B, d+1, S]
        scores = jnp.where(visible[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhgts,bhds->bthgd", probs.astype(v_rows.dtype),
                          v_rows,
                          preferred_element_type=jnp.float32).astype(x.dtype)
        x = x + _mm(attn.reshape(B, d + 1, H * dh), layer, "wo")
        x = x + ffn_block(x, layer, cfg)
        return x, k_pool, v_pool

    x, k_pool, v_pool = jax.lax.fori_loop(
        0, cfg.n_layers, layer_body, (x, k_pool, v_pool))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)           # [B, d+1, D]
    greedy_cols = []
    logits0 = None
    for i in range(d + 1):
        logits_i = _head(x[:, i], params)
        if i == 0:
            logits0 = logits_i
        greedy_cols.append(jnp.argmax(logits_i, axis=-1).astype(jnp.int32))
    greedy = jnp.stack(greedy_cols, axis=1)                      # [B, d+1]
    return greedy, logits0, k_pool, v_pool


def llama_prefill_paged_prefix(params, cfg: LlamaConfig, tokens, prefix_lens,
                               lengths, k_pool, v_pool, table, project_last):
    """Prefill ONLY a prompt's un-cached TAIL against the paged pool.

    The prefix-cache hit path: each row's first `prefix_lens[k]` tokens
    (a whole number of pages) are already in shared pages referenced by
    its block table, so this forward computes K/V for the tail window
    alone — prefill FLOPs and writes scale with the UNSHARED tail, which
    is the entire point of prefix caching.

    tokens: [K, T] tail token ids (row k's tail starts at absolute
    position prefix_lens[k]); prefix_lens: [K] int32 multiples of the
    page size; lengths: [K] FULL prompt lengths; k/v_pool:
    [L, P, Hkv, dh, ps]; table: [K, NP] page ids (shared prefix pages
    first, then the row's fresh pages); project_last: [K] within-window
    index of each row's last prompt token.

    Per layer: the tail's K/V are written as whole pages into the row's
    fresh pages (paged_write_window; the tail starts on a page boundary),
    then the tail queries attend the GATHERED pages ([K, Hkv, dh, NP*ps]
    contiguous rows, one pool read per layer — the same shape trick as
    llama_verify_step_paged) under the standard `j <= q_pos` mask, which
    covers the shared prefix and the tail's own causal window in one rule.

    Returns (last_logits [K, V] float32, k_pool, v_pool).
    """
    from ..ops.paged_attention import paged_write_window

    K, T = tokens.shape
    H, Hkv, dh, G = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    ps = k_pool.shape[-1]
    NP = table.shape[1]
    S = NP * ps
    pos_grid = prefix_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x = _embed(params, cfg, tokens)

    def layer_body(l, state):
        x, k_pool, v_pool = state
        layer = jax.tree_util.tree_map(lambda w: w[l], params["layers"])
        normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = rope(_mm(normed, layer, "wq").reshape(K, T, H, dh),
                 pos_grid, cfg.rope_theta)
        k = rope(_mm(normed, layer, "wk").reshape(K, T, Hkv, dh),
                 pos_grid, cfg.rope_theta)
        v = _mm(normed, layer, "wv").reshape(K, T, Hkv, dh)
        # [K, T, Hkv, dh] -> the writer's [1, K, Hkv, dh, T] window
        k_pool = paged_write_window(k_pool, k.transpose(0, 2, 3, 1)[None],
                                    table, prefix_lens, lengths, layer=l)
        v_pool = paged_write_window(v_pool, v.transpose(0, 2, 3, 1)[None],
                                    table, prefix_lens, lengths, layer=l)
        k_rows = jnp.moveaxis(k_pool[l, table], 1, 3).reshape(K, Hkv, dh, S)
        v_rows = jnp.moveaxis(v_pool[l, table], 1, 3).reshape(K, Hkv, dh, S)
        qg = q.reshape(K, T, Hkv, G, dh)
        scores = jnp.einsum("bthgd,bhds->bhgts", qg, k_rows,
                            preferred_element_type=jnp.float32
                            ) / math.sqrt(dh)
        cache_pos = jnp.arange(S)[None, None, :]
        visible = cache_pos <= pos_grid[:, :, None]             # [K, T, S]
        scores = jnp.where(visible[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhgts,bhds->bthgd", probs.astype(v_rows.dtype),
                          v_rows,
                          preferred_element_type=jnp.float32).astype(x.dtype)
        x = x + _mm(attn.reshape(K, T, H * dh), layer, "wo")
        x = x + ffn_block(x, layer, cfg)
        return x, k_pool, v_pool

    x, k_pool, v_pool = jax.lax.fori_loop(
        0, cfg.n_layers, layer_body, (x, k_pool, v_pool))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = x[jnp.arange(K), project_last]                       # [K, D]
    logits = _head(last, params)
    return logits, k_pool, v_pool


def llama_prefill_paged_prefix_q8(params, cfg: LlamaConfig, tokens,
                                  prefix_lens, lengths, k_pool, v_pool,
                                  ks_pool, vs_pool, table, project_last):
    """llama_prefill_paged_prefix over INT8 pools with per-token scales.

    MIRRORS the fp variant with quantized storage: the tail's K/V quantize
    on write (so the pages hold exactly what later decode reads), then the
    gathered rows dequantize [K, Hkv, dh, NP*ps] for the tail window's
    attention — prefix pages keep the DONOR's quantization (no requantize
    drift).

    k/v_pool: [L, P, Hkv, dh, ps] int8; ks/vs_pool: [L, P, Hkv, ps] f32.
    Returns (last_logits [K, V] f32, k_pool, v_pool, ks_pool, vs_pool).
    """
    from ..ops.paged_attention import quantize_kv
    from ..ops.paged_attention import paged_write_window

    K, T = tokens.shape
    H, Hkv, dh, G = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    ps = k_pool.shape[-1]
    NP = table.shape[1]
    S = NP * ps
    dt = np_dtype(cfg.dtype)
    pos_grid = prefix_lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    x = _embed(params, cfg, tokens)

    def write(pool, window, l):
        return paged_write_window(pool, window[None], table, prefix_lens,
                                  lengths, layer=l)

    def layer_body(l, state):
        x, k_pool, v_pool, ks_pool, vs_pool = state
        layer = jax.tree_util.tree_map(lambda w: w[l], params["layers"])
        normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = rope(_mm(normed, layer, "wq").reshape(K, T, H, dh),
                 pos_grid, cfg.rope_theta)
        k = rope(_mm(normed, layer, "wk").reshape(K, T, Hkv, dh),
                 pos_grid, cfg.rope_theta)
        v = _mm(normed, layer, "wv").reshape(K, T, Hkv, dh)
        k8, ks = quantize_kv(k, axis=-1)           # [K,T,Hkv,dh], [K,T,Hkv]
        v8, vs = quantize_kv(v, axis=-1)
        k_pool = write(k_pool, k8.transpose(0, 2, 3, 1), l)
        v_pool = write(v_pool, v8.transpose(0, 2, 3, 1), l)
        ks_pool = write(ks_pool, ks.transpose(0, 2, 1), l)
        vs_pool = write(vs_pool, vs.transpose(0, 2, 1), l)
        k_rows = jnp.moveaxis(k_pool[l, table], 1, 3).reshape(K, Hkv, dh, S)
        v_rows = jnp.moveaxis(v_pool[l, table], 1, 3).reshape(K, Hkv, dh, S)
        ks_rows = jnp.moveaxis(ks_pool[l, table], 1, 2).reshape(K, Hkv, S)
        vs_rows = jnp.moveaxis(vs_pool[l, table], 1, 2).reshape(K, Hkv, S)
        k_deq = (k_rows.astype(jnp.float32)
                 * ks_rows[:, :, None, :]).astype(dt)
        v_deq = (v_rows.astype(jnp.float32)
                 * vs_rows[:, :, None, :]).astype(dt)
        qg = q.reshape(K, T, Hkv, G, dh)
        scores = jnp.einsum("bthgd,bhds->bhgts", qg, k_deq,
                            preferred_element_type=jnp.float32
                            ) / math.sqrt(dh)
        cache_pos = jnp.arange(S)[None, None, :]
        visible = cache_pos <= pos_grid[:, :, None]
        scores = jnp.where(visible[:, None, None, :, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        attn = jnp.einsum("bhgts,bhds->bthgd", probs.astype(v_deq.dtype),
                          v_deq,
                          preferred_element_type=jnp.float32).astype(x.dtype)
        x = x + _mm(attn.reshape(K, T, H * dh), layer, "wo")
        x = x + ffn_block(x, layer, cfg)
        return x, k_pool, v_pool, ks_pool, vs_pool

    x, k_pool, v_pool, ks_pool, vs_pool = jax.lax.fori_loop(
        0, cfg.n_layers, layer_body, (x, k_pool, v_pool, ks_pool, vs_pool))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = x[jnp.arange(K), project_last]
    logits = _head(last, params)
    return logits, k_pool, v_pool, ks_pool, vs_pool


def attention_block_nocache(x, layer, positions, cfg: LlamaConfig,
                             attn_fn=None, mesh=None):
    """Plain causal attention sublayer (no cache). x: [B, T, D] -> [B, T, D].

    attn_fn overrides the attention primitive (q, k, v) -> [B, T, H, dh] —
    how the sequence-parallel forward swaps in ring/Ulysses attention while
    sharing every projection with the dense path."""
    B, T, _ = x.shape
    H, Hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    normed = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q = rope(_mm(normed, layer, "wq").reshape(B, T, H, dh), positions, cfg.rope_theta)
    k = rope(_mm(normed, layer, "wk").reshape(B, T, Hkv, dh), positions, cfg.rope_theta)
    v = _mm(normed, layer, "wv").reshape(B, T, Hkv, dh)
    if attn_fn is not None:
        attn = attn_fn(q, k, v)
    elif cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention

        attn = flash_attention(q, k, v, True, mesh=mesh)
    else:
        from ..ops.flash_attention import attention_reference

        attn = attention_reference(q, k, v, causal=True)
    return _mm(attn.reshape(B, T, H * dh), layer, "wo")


def forward_nocache_at(params, cfg: LlamaConfig, tokens, positions,
                       attn_fn=None, mesh=None):
    """Cache-free forward over a token chunk at explicit absolute positions.

    The shared body behind llama_forward_nocache and the sequence-parallel
    forward (parallel/longcontext.py), which calls it per device with its
    chunk's position offset and a collective attention primitive."""
    x = _embed(params, cfg, tokens)

    def body(x, layer):
        x = x + attention_block_nocache(x, layer, positions, cfg, attn_fn,
                                         mesh)
        x = x + ffn_block(x, layer, cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return _head(x, params)


def llama_forward_nocache(params, cfg: LlamaConfig, tokens, mesh=None):
    """Training/eval forward without a cache: plain causal attention.

    Kept separate from the serving path so the training step doesn't carry
    cache plumbing; shares every sublayer weight and math with llama_forward.
    """
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    return forward_nocache_at(params, cfg, tokens, positions, mesh=mesh)


# for models/families.py; the last two are the checkpoint loader and the int8
# weight path, which this family alone has
PRESETS = {"debug": LlamaConfig.debug, "llama1b": LlamaConfig.llama1b,
           "llama3-8b": LlamaConfig.llama3_8b,
           "llama3-70b": LlamaConfig.llama3_70b}   # TP_SHARDS=8 territory
init = llama_init
init_quantized = llama_init_quantized
from .weights import load_llama_safetensors as load_checkpoint  # noqa: E402
