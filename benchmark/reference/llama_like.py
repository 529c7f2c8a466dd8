"""Plain reference for the llama-like decoder block (InternLM2, Mistral):
RMSNorm, rotate-half RoPE, grouped-query attention, SwiGLU, no biases, an
untied output head. Written from the published descriptions (the models'
modelling files on huggingface.co), straightforward jax.numpy in float32 at
the highest matmul precision: no kernel, no cache, no batching, one sequence
at a time, one layer at a time. It imports nothing of the program.

Departure noted: InternLM2 stores q, k and v as one fused `wqkv`; the three
matrices here are the same mathematics on seeded random weights.

Weights are the benchmark's own (harness/weights.py makes them from the
seed): {"tok_emb" [V, D], "layers": {"wq" [L, D, H*dh], "wk", "wv"
[L, D, Hkv*dh], "wo" [L, H*dh, D], "w_gate", "w_up" [L, D, F], "w_down"
[L, F, D], "attn_norm", "ffn_norm" [L, D]}, "final_norm" [D], "lm_head"
[D, V]}, in the dtype they are served in; each layer is upcast as it is
used.

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix rounded to int8 per output channel (the embedding per row),
the nearest precision below bfloat16 that a later PR could be tempted by.
"""

import functools
import math

import jax
import jax.numpy as jnp

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def dims_of(config: dict) -> dict:
    """The sizes the forward needs, from a config.json's own keys."""
    heads = int(config["num_attention_heads"])
    return {
        "V": int(config["vocab_size"]), "D": int(config["hidden_size"]),
        "L": int(config["num_hidden_layers"]), "H": heads,
        "Hkv": int(config["num_key_value_heads"]),
        "dh": int(config.get("head_dim") or config["hidden_size"] // heads),
        "F": int(config["intermediate_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
    }


def _int8(w, axis: int):
    """Symmetric int8 per channel, scales over the contraction axis `axis`,
    returned as float32 again."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _weight(w, lower, axis: int = 0):
    w = w.astype(jnp.float32)
    return _int8(w, axis) if lower == "int8" else w


def rms_norm(x, weight, eps: float):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight.astype(jnp.float32))


def rope(x, positions, theta: float):
    """x [T, heads, dh]: pairs (i, i + dh/2) rotate by position * theta^(-2i/dh)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def layer(x, w: dict, dims: dict, lower=None):
    """One decoder layer over one sequence. x [T, D] float32."""
    T = x.shape[0]
    H, Hkv, dh = dims["H"], dims["Hkv"], dims["dh"]
    positions = jnp.arange(T)
    m = {name: _weight(w[name], lower) for name in MATRICES}
    h = rms_norm(x, w["attn_norm"], dims["eps"])
    q = rope((h @ m["wq"]).reshape(T, H, dh), positions, dims["theta"])
    k = rope((h @ m["wk"]).reshape(T, Hkv, dh), positions, dims["theta"])
    v = (h @ m["wv"]).reshape(T, Hkv, dh)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    causal = positions[None, :] <= positions[:, None]          # [t, s]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    x = x + attn.reshape(T, H * dh) @ m["wo"]
    h = rms_norm(x, w["ffn_norm"], dims["eps"])
    return x + (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


@functools.partial(jax.jit, static_argnames=("lower",))
def _embed(tok_emb, tokens, lower=None):
    return _weight(tok_emb, lower, axis=1)[tokens]


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _layer_at(x, layers, index, dims, lower=None):
    with jax.default_matmul_precision("highest"):
        w = {k: jax.lax.dynamic_index_in_dim(v, index, 0, keepdims=False)
             for k, v in layers.items()}
        return layer(x, w, dict(dims), lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, lm_head, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ _weight(lm_head, lower)


def logits(params: dict, dims: dict, tokens, lower=None):
    """[T, V] float32 logits of one token sequence [T]: row t scores the
    token that follows tokens[:t + 1]."""
    frozen = tuple(sorted(dims.items()))
    x = _embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32), lower=lower)
    for index in range(dims["L"]):
        x = _layer_at(x, params["layers"], index, frozen, lower=lower)
    return _head(x, params["final_norm"], params["lm_head"], dims["eps"],
                 lower=lower)
