"""Causal ring attention over the "sp" mesh axis.

Long-context sequence parallelism (SURVEY.md §5): the sequence is sharded
[B, T/sp, ...] per device; K/V blocks rotate around the ring via ppermute
while each device keeps its Q block, merging partial attention with the
online-softmax (flash) recurrence. Communication is sp-1 point-to-point hops
on ICI instead of an all-gather of the full K/V — memory stays O(T/sp) per
chip, enabling sequences that exceed one chip's HBM.

Causality across blocks: with every device holding sequence chunk index
c = axis_index(sp), a KV block with chunk index c_kv contributes
  - fully        if c_kv < c_q
  - causal-mask  if c_kv == c_q
  - nothing      if c_kv > c_q   (still computed — static shapes — but masked)

Differentiable: jax AD traces through lax.scan + ppermute (ppermute's
transpose is the inverse permutation), so the same op serves training.
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp


def _block_attend(q, k, v, scale, mask):
    """q: [B,Tq,H,dh]; k/v: [B,Tk,Hkv,dh]; mask: [Tq,Tk] bool.
    Returns (numerator [B,Tq,H,dh] f32, row_max [B,H,Tq] f32, row_sum)."""
    B, Tq, H, dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, Tq, Hkv, G, dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(mask[None, None, None, :, :], scores, -jnp.inf)
    m = jnp.max(scores, axis=-1)                          # [B,Hkv,G,Tq]
    # guard fully-masked rows (m = -inf -> exp(nan)); they contribute zero
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(jnp.isfinite(scores), p, 0.0)
    l = jnp.sum(p, axis=-1)                               # [B,Hkv,G,Tq]
    num = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    return num.reshape(B, Tq, H, dh), m_safe, l, jnp.isfinite(m)


def ring_attention(q, k, v, axis_name: str = "sp"):
    """Causal attention with K/V rotating over `axis_name`.

    Must be called inside shard_map with q/k/v sequence-sharded:
    q,k,v: [B, T_local, H(kv), dh]. Returns [B, T_local, H, dh] in q.dtype.
    """
    B, T, H, dh = q.shape
    sp = jax.lax.axis_size(axis_name)
    my_chunk = jax.lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(dh)

    local_mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    full_mask = jnp.ones((T, T), dtype=bool)
    none_mask = jnp.zeros((T, T), dtype=bool)

    def step(carry, s):
        k_blk, v_blk, acc, m_run, l_run = carry
        # the block arriving at step s originated at chunk (my_chunk - s) mod sp
        kv_chunk = (my_chunk - s) % sp
        mask = jnp.where(kv_chunk < my_chunk, full_mask,
                         jnp.where(kv_chunk == my_chunk, local_mask, none_mask))
        num, m_blk, l_blk, valid = _block_attend(q, k_blk, v_blk, scale, mask)
        Hkv = k_blk.shape[2]
        G = H // Hkv
        # online-softmax merge (flash recurrence) in [B,Hkv,G,Tq] space
        m_new = jnp.maximum(m_run, jnp.where(valid, m_blk, -jnp.inf))
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        scale_run = jnp.exp(jnp.where(jnp.isfinite(m_run), m_run, -jnp.inf) - m_new_safe)
        scale_run = jnp.where(jnp.isfinite(m_run), scale_run, 0.0)
        scale_blk = jnp.exp(jnp.where(valid, m_blk, -jnp.inf) - m_new_safe)
        scale_blk = jnp.where(valid, scale_blk, 0.0)

        def bc(x):  # [B,Hkv,G,Tq] -> [B,Tq,H,1]
            return x.transpose(0, 3, 1, 2).reshape(B, T, H)[..., None]

        acc = acc * bc(scale_run) + num * bc(scale_blk)
        l_run = l_run * scale_run + l_blk * scale_blk
        # rotate K/V to the next device on the ring
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, acc, m_new, l_run), None

    Hkv = k.shape[2]
    G = H // Hkv
    acc0 = jnp.zeros((B, T, H, dh), dtype=jnp.float32)
    m0 = jnp.full((B, Hkv, G, T), -jnp.inf, dtype=jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, T), dtype=jnp.float32)
    (_, _, acc, _, l_run), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(sp))

    denom = l_run.transpose(0, 3, 1, 2).reshape(B, T, H)[..., None]
    out = acc / jnp.maximum(denom, 1e-20)
    return out.astype(q.dtype)
