"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head exchange.

The second long-context strategy next to ring attention (SURVEY.md §5
"ring / blockwise ... context-parallel attention"): instead of rotating K/V
blocks around a ring (sp-1 hops, O(T/sp) memory, compute overlapped), one
`all_to_all` re-shards activations from sequence-sharded [B, T/sp, H, dh] to
head-sharded [B, T, H/sp, dh], each device runs *full-sequence* attention
over its head slice, and a second all-to-all restores sequence sharding.

Trade-off vs ring: two collectives total (bandwidth-optimal on ICI's
all-to-all-friendly torus) and an unmodified attention kernel between them —
but heads must divide by sp and each device materialises the full sequence
length for its heads, so ring wins when T/sp is the HBM limit and Ulysses
wins when kernel simplicity / fewer comm phases dominate. Serving frameworks
ship both; the model layer picks per deployment.

GQA: K/V heads are repeated up to the query head count before the exchange
when sp would not divide Hkv — correctness first; the all-to-all then moves
H/sp query heads and H/sp (repeated) KV heads per device.

Differentiable: all_to_all is its own transpose; jax AD traces through.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention


def ulysses_attention(q, k, v, axis_name: str = "sp"):
    """Causal attention with all-to-all sequence<->head re-sharding.

    Must be called inside shard_map with q/k/v sequence-sharded:
    q: [B, T_local, H, dh], k/v: [B, T_local, Hkv, dh]; H divisible by the
    axis size. Returns [B, T_local, H, dh] in q.dtype.
    """
    H = q.shape[2]
    Hkv = k.shape[2]
    sp = jax.lax.axis_size(axis_name)
    if H % sp != 0:
        raise ValueError(f"query heads ({H}) must divide by |{axis_name}|={sp}")
    if Hkv % sp != 0:  # GQA with fewer KV heads than devices: replicate up
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    def seq_to_head(x):  # [B, T/sp, h, dh] -> [B, T, h/sp, dh]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    q, k, v = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    # unmodified single-device kernel between the two exchanges: the pallas
    # flash kernel on TPU (O(T) memory — the long-context point), exact
    # oracle fallback elsewhere
    out = flash_attention(q, k, v, causal=True)
    # [B, T, H/sp, dh] -> [B, T/sp, H, dh]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)
