"""BERT-family encoder: bidirectional transformer for embeddings/classification.

North-star config 3 in BASELINE.md: a BERT-base `/embed` endpoint behind the
dynamic batcher. Built TPU-first like the Llama decoder (models/llama.py):
stacked [n_layers, ...] weights consumed by lax.scan (one-layer trace, fast
XLA compiles), bfloat16 matmuls for the MXU with float32 LayerNorm/softmax
accumulation, and an explicit padding mask so the batcher's sequence-bucket
padding is numerically invisible (padded rows attend nothing, pooling masks
them out) — no data-dependent shapes anywhere.

Reference parity: the reference framework (pure-Go microservice toolkit) has
no models at all (SURVEY.md §2); this file is new TPU-native capability that
the BASELINE.md target ladder requires.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    dim: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_dim: int = 3072
    max_seq_len: int = 512
    n_segments: int = 2
    layer_norm_eps: float = 1e-12
    pad_id: int = 0
    dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @classmethod
    def debug(cls) -> "BertConfig":
        """CI-sized model: compiles in seconds on CPU."""
        return cls(vocab_size=512, dim=64, n_layers=2, n_heads=4, ffn_dim=128,
                   max_seq_len=128, dtype="float32")

    @classmethod
    def base(cls) -> "BertConfig":
        return cls()

    @classmethod
    def large(cls) -> "BertConfig":
        return cls(dim=1024, n_layers=24, n_heads=16, ffn_dim=4096)

    def param_count(self) -> int:
        embed = (self.vocab_size + self.max_seq_len + self.n_segments) * self.dim
        per_layer = (4 * self.dim * self.dim          # wq wk wv wo
                     + 2 * self.dim * self.ffn_dim    # ffn in/out
                     + 4 * self.dim                   # 2 LayerNorms (scale+bias)
                     + 4 * self.dim + self.ffn_dim + self.dim)  # biases
        pooler = self.dim * self.dim + self.dim
        return embed + 2 * self.dim + self.n_layers * per_layer + pooler


def bert_init(cfg: BertConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init params pytree with stacked [L, ...] layer weights."""
    import jax
    import jax.numpy as jnp

    from .blocks import np_dtype

    dtype = np_dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 10)
    L, D, F = cfg.n_layers, cfg.dim, cfg.ffn_dim

    def init(k, shape, fan_in):
        return (jax.random.normal(k, shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    return {
        "tok_emb": init(keys[0], (cfg.vocab_size, D), D),
        "pos_emb": init(keys[1], (cfg.max_seq_len, D), D),
        "seg_emb": init(keys[2], (cfg.n_segments, D), D),
        "emb_norm_scale": jnp.ones((D,), dtype=dtype),
        "emb_norm_bias": jnp.zeros((D,), dtype=dtype),
        "layers": {
            "wq": init(keys[3], (L, D, D), D),
            "bq": jnp.zeros((L, D), dtype=dtype),
            "wk": init(keys[4], (L, D, D), D),
            "bk": jnp.zeros((L, D), dtype=dtype),
            "wv": init(keys[5], (L, D, D), D),
            "bv": jnp.zeros((L, D), dtype=dtype),
            "wo": init(keys[6], (L, D, D), D),
            "bo": jnp.zeros((L, D), dtype=dtype),
            "attn_norm_scale": jnp.ones((L, D), dtype=dtype),
            "attn_norm_bias": jnp.zeros((L, D), dtype=dtype),
            "w_in": init(keys[7], (L, D, F), D),
            "b_in": jnp.zeros((L, F), dtype=dtype),
            "w_out": init(keys[8], (L, F, D), F),
            "b_out": jnp.zeros((L, D), dtype=dtype),
            "ffn_norm_scale": jnp.ones((L, D), dtype=dtype),
            "ffn_norm_bias": jnp.zeros((L, D), dtype=dtype),
        },
        "pooler_w": init(keys[9], (D, D), D),
        "pooler_b": jnp.zeros((D,), dtype=dtype),
    }


import jax  # noqa: E402  (after dataclass defs so module import stays light)
import jax.numpy as jnp  # noqa: E402


def layer_norm(x, scale, bias, eps: float):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    normed = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (normed * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _encoder_layer(x, layer, attn_bias, cfg: BertConfig):
    """Post-LN encoder layer. x: [B, T, D]; attn_bias: [B, 1, 1, T] f32."""
    B, T, D = x.shape
    H, dh = cfg.n_heads, cfg.head_dim

    q = (x @ layer["wq"] + layer["bq"]).reshape(B, T, H, dh)
    k = (x @ layer["wk"] + layer["bk"]).reshape(B, T, H, dh)
    v = (x @ layer["wv"] + layer["bv"]).reshape(B, T, H, dh)
    scores = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(dh)
    probs = jax.nn.softmax(scores + attn_bias, axis=-1)
    attn = jnp.einsum("bhts,bshd->bthd", probs,
                      v.astype(jnp.float32)).astype(x.dtype)
    attn = attn.reshape(B, T, D) @ layer["wo"] + layer["bo"]
    x = layer_norm(x + attn, layer["attn_norm_scale"], layer["attn_norm_bias"],
                   cfg.layer_norm_eps)

    h = jax.nn.gelu(x @ layer["w_in"] + layer["b_in"], approximate=True)
    h = h @ layer["w_out"] + layer["b_out"]
    return layer_norm(x + h, layer["ffn_norm_scale"], layer["ffn_norm_bias"],
                      cfg.layer_norm_eps)


def bert_encode(params, cfg: BertConfig, tokens, segments=None):
    """Full encoder stack. tokens: [B, T] int32 (pad_id marks padding).

    Returns hidden states [B, T, D] in cfg.dtype. Padded positions carry
    garbage activations but are masked out of attention reads and pooling.
    """
    B, T = tokens.shape
    mask = tokens != cfg.pad_id                                  # [B, T]
    attn_bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)[:, None, None, :]

    positions = jnp.arange(T, dtype=jnp.int32)
    seg = segments if segments is not None else jnp.zeros_like(tokens)
    x = (params["tok_emb"][tokens]
         + params["pos_emb"][positions][None, :, :]
         + params["seg_emb"][seg])
    x = layer_norm(x, params["emb_norm_scale"], params["emb_norm_bias"],
                   cfg.layer_norm_eps)

    def body(x, layer):
        return _encoder_layer(x, layer, attn_bias, cfg), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return x


def bert_embed(params, cfg: BertConfig, tokens):
    """Masked mean-pooled sentence embedding, L2-normalised.

    The /embed endpoint's model_fn: [B, T] int32 -> [B, D] float32. Pooling
    weights only non-pad positions, so a sequence padded to a longer bucket by
    the dynamic batcher embeds identically to the unpadded one.
    """
    hidden = bert_encode(params, cfg, tokens).astype(jnp.float32)  # [B, T, D]
    mask = (tokens != cfg.pad_id).astype(jnp.float32)[:, :, None]  # [B, T, 1]
    summed = jnp.sum(hidden * mask, axis=1)
    counts = jnp.maximum(jnp.sum(mask, axis=1), 1.0)
    pooled = summed / counts
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


def bert_pool_cls(params, cfg: BertConfig, tokens):
    """Classic BERT pooler: tanh(W @ h[CLS]). [B, T] -> [B, D]."""
    hidden = bert_encode(params, cfg, tokens)
    cls = hidden[:, 0, :]
    return jnp.tanh((cls @ params["pooler_w"] + params["pooler_b"])
                    .astype(jnp.float32))
