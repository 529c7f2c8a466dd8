"""Mixture-of-Experts Llama variant: top-k routed FFN, expert-parallel ready.

Expert parallelism (the "EP" strategy, SURVEY.md §2.5): expert weights carry a
leading E axis sharded over the "ep" mesh axis (parallel/sharding.py). Routing
uses the dense-dispatch formulation — every expert computes every token,
gating weights zero the non-selected — which keeps shapes static and lets XLA
shard the E axis with a psum-style combine; capacity-based sparse dispatch is
a later optimisation, not a semantic change.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .blocks import np_dtype, rms_norm
from .llama import LlamaConfig, attention_block_nocache


@dataclasses.dataclass(frozen=True)
class MoELlamaConfig(LlamaConfig):
    n_experts: int = 8
    experts_per_token: int = 2

    @classmethod
    def debug(cls) -> "MoELlamaConfig":
        return cls(vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                   ffn_dim=128, max_seq_len=256, dtype="float32",
                   n_experts=4, experts_per_token=2)


def moe_llama_init(cfg: MoELlamaConfig, seed: int = 0) -> Dict[str, Any]:
    dtype = np_dtype(cfg.dtype)
    key = jax.random.PRNGKey(seed)
    keys = jax.random.split(key, 9)
    L, D, H, Hkv, dh, F, V, E = (cfg.n_layers, cfg.dim, cfg.n_heads,
                                 cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim,
                                 cfg.vocab_size, cfg.n_experts)

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
            "w_router": init(keys[8], (L, D, E), D),
            "w_gate": init(keys[5], (L, E, D, F), D),
            "w_up": init(keys[6], (L, E, D, F), D),
            "w_down": init(keys[7], (L, E, F, D), F),
            "attn_norm": jnp.ones((L, D), dtype=dtype),
            "ffn_norm": jnp.ones((L, D), dtype=dtype),
        },
        "final_norm": jnp.ones((D,), dtype=dtype),
        "lm_head": init(keys[0], (D, V), D),
    }


def moe_ffn(x, layer, cfg: MoELlamaConfig):
    """Top-k routed SwiGLU experts, dense dispatch.

    x: [B, T, D] -> [B, T, D]. Also returns the router's load-balancing
    auxiliary loss (Switch-style: E * sum_e f_e * p_e).
    """
    E, K = cfg.n_experts, cfg.experts_per_token
    normed = rms_norm(x, layer["ffn_norm"], cfg.rms_eps)

    router_logits = (normed @ layer["w_router"]).astype(jnp.float32)  # [B,T,E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, K)                        # [B,T,K]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(top_idx, E, dtype=probs.dtype)
                    * top_vals[..., None], axis=-2)                    # [B,T,E]

    # dense dispatch: every expert processes every token, gate weights select
    gate_proj = jnp.einsum("btd,edf->betf", normed, layer["w_gate"])
    up_proj = jnp.einsum("btd,edf->betf", normed, layer["w_up"])
    expert_out = jnp.einsum("betf,efd->betd",
                            jax.nn.silu(gate_proj) * up_proj, layer["w_down"])
    out = jnp.einsum("betd,bte->btd", expert_out, gates.astype(expert_out.dtype))

    # load-balancing aux loss: fraction of tokens routed vs router mass
    me = jnp.mean(gates > 0, axis=(0, 1)).astype(jnp.float32)  # routed fraction
    ce = jnp.mean(probs, axis=(0, 1))
    aux_loss = E * jnp.sum(me * ce)
    return out.astype(x.dtype), aux_loss


def moe_llama_forward_nocache(params, cfg: MoELlamaConfig, tokens):
    """Training forward: causal attention + MoE FFN. Returns (logits, aux_loss)."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
    x = params["tok_emb"][tokens]

    def body(carry, layer):
        x, aux = carry
        x = x + attention_block_nocache(x, layer, positions, cfg)
        ffn_out, layer_aux = moe_ffn(x, layer, cfg)
        x = x + ffn_out
        return (x, aux + layer_aux), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = (x @ params["lm_head"]).astype(jnp.float32)
    return logits, aux / cfg.n_layers
