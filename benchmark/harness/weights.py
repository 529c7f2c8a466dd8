"""Seeded random weights, made on the device in one jitted call, in the
dtype they are served in. The benchmark makes them, not the program, so
that the program and the plain reference are handed the same arrays and
neither takes anything the other has made.

Normal(0, 1/fan_in) matrices and unit norms: what a freshly initialised
llama-like model has, and what keeps logits of order 1.
"""

import math

import jax
import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
           "float16": jnp.float16}


def key_of(seed: int):
    """Any whole seed, also above 2**31, to a PRNG key."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def param_shapes(dims: dict) -> dict:
    L, D, F, V = dims["L"], dims["D"], dims["F"], dims["V"]
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    return {"tok_emb": (V, D), "final_norm": (D,), "lm_head": (D, V),
            "layers": {"wq": (L, D, q), "wk": (L, D, kv), "wv": (L, D, kv),
                       "wo": (L, q, D), "w_gate": (L, D, F),
                       "w_up": (L, D, F), "w_down": (L, F, D),
                       "attn_norm": (L, D), "ffn_norm": (L, D)}}


def make_params(dims: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The params pytree."""
    dt = _DTYPES[dtype]
    shapes = param_shapes(dims)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def one_layer(key):
        keys = jax.random.split(key, 7)
        out = {}
        for k, name in zip(keys, ("wq", "wk", "wv", "wo", "w_gate", "w_up",
                                  "w_down")):
            shape = shapes["layers"][name][1:]
            out[name] = normal(k, shape, shape[0])
        return out

    def init(key):
        k_emb, k_head, k_layers = jax.random.split(key, 3)
        # layer by layer, so that the float32 temporaries are one layer's
        layers = jax.lax.map(one_layer,
                             jax.random.split(k_layers, dims["L"]))
        layers["attn_norm"] = jnp.ones(shapes["layers"]["attn_norm"], dt)
        layers["ffn_norm"] = jnp.ones(shapes["layers"]["ffn_norm"], dt)
        return {"tok_emb": normal(k_emb, shapes["tok_emb"], dims["D"]),
                "layers": layers,
                "final_norm": jnp.ones(shapes["final_norm"], dt),
                "lm_head": normal(k_head, shapes["lm_head"], dims["D"])}

    return jax.jit(init)(key_of(seed))
