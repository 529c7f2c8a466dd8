"""What every family's forward shares, whatever its blocks are: the norm,
the rotation, the head, what a decode step's rows attend, and the scaffold
of a seeded init. A family module (models/families.py names them) imports
these and `models/experts.py`, never another family.

Nothing here knows a family: a function that would have to ask which one
called it belongs in that family's module.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp


def np_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16, "int8": jnp.int8}[name]


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    normed = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (normed * weight.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotate-half RoPE. x: [B, T, H, dh]; positions: [B, T] int32."""
    dh = x.shape[-1]
    half = dh // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, half]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, T, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def head(x, params, eps: float):
    """The final RMSNorm and the untied head: logits in float32."""
    x = rms_norm(x, params["final_norm"], eps)
    return (x @ params["lm_head"]).astype(jnp.float32)


def attended_in_block(table, positions, step):
    """What each row attends at step `step` of a decode block whose new
    tokens wait in the block's tail (ops/paged_attention `block_tail`):
    (tokens attended in pages: the row's context when the block began;
    tokens attended in the tail: this step's and the block's earlier ones),
    both 0 for a row that holds no request (ops/paged_attention
    `holds_request`): read as a length, such a row's stale position would
    walk the garbage page up to the table's width, every layer of every
    step."""
    from ..ops.paged_attention import holds_request

    live = holds_request(table)
    return (jnp.where(live, positions - step, 0),
            jnp.where(live, step + 1, 0))


def live_and_attended(table, positions, step):
    """(live [B] bool: the rows that hold a request; then
    `attended_in_block`'s two): how a family with per-slot state or
    counters opens its decode step, since both are kept to the live rows."""
    from ..ops.paged_attention import holds_request

    return (holds_request(table), *attended_in_block(table, positions, step))


# -- seeded weights -----------------------------------------------------------
def matrix(key, shape, fan_in: int, dtype):
    """Normal draws over `shape`, scaled by 1 / sqrt(fan_in)."""
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def seeded_block(key, shapes: Dict[str, tuple], dtype,
                 own: Callable = lambda name, shape, keys: None):
    """One block's leaves by the rule the gated-expert families share: as
    many keys as leaves, taken in the leaves' order by those that draw;
    norms ones; the router's bias zeros in float32; every other leaf a
    matrix scaled by its fan-in. `own(name, shape, keys)` answers a leaf the
    family draws its own way (taking `next(keys)` if it draws), None for
    the rest."""
    keys = iter(jax.random.split(key, len(shapes)))
    out = {}
    for name, shape in shapes.items():
        leaf = own(name, shape, keys)
        if leaf is not None:
            out[name] = leaf
        elif name.endswith("norm"):
            out[name] = jnp.ones(shape, dtype)
        elif name == "router_bias":
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            # the experts' matrices are [held, out, in] (w2: [.., in,
            # out]): fan-in is D for up and gate, F for down
            fan_in = (shape[1] if name == "w2" else shape[-1]
                      if len(shape) == 3 else shape[0])
            out[name] = matrix(next(keys), shape, fan_in, dtype)
    return out


def init_blocks(cfg, seed: int, kinds: Sequence, make: Callable
                ) -> Dict[str, Any]:
    """Random-init params of a family: {"tok_emb" [V, D], "layers": [one
    dict a block, `make(key, kind)`], "final_norm" [D], "lm_head" [D, V]},
    matrices [in, out] but the routed experts' ([held, F, D]: models/
    experts.py). Per-block leaves, never stacked: the blocks differ in kind,
    the layer loop is unrolled, and a static slice of a stack feeding a
    matmul may be copied (1.3 GB for a block's experts). `kinds` names each
    block's kind in order (hashable: `make` is jitted with it static, a
    call a block); block i draws from `fold_in(key, 16 + i)`, the embedding
    and the head from keys 1 and 2."""
    dtype = np_dtype(cfg.dtype)
    make = jax.jit(make, static_argnums=1)
    outer = jax.jit(matrix, static_argnums=(1, 2, 3))
    key = jax.random.PRNGKey(seed)
    return {
        "tok_emb": outer(jax.random.fold_in(key, 1),
                         (cfg.vocab_size, cfg.dim), cfg.dim, dtype),
        "layers": [make(jax.random.fold_in(key, 16 + i), kind)
                   for i, kind in enumerate(kinds)],
        "final_norm": jnp.ones((cfg.dim,), dtype),
        "lm_head": outer(jax.random.fold_in(key, 2),
                         (cfg.dim, cfg.vocab_size), cfg.dim, dtype),
    }
