"""Decode-step attention kernel: T=1 GQA read over the serving cache.

Why a kernel: the decode step is pure HBM bandwidth — read every live cache
position once — but XLA's dot for [B,Hkv,G*1,dh] x [B,Hkv,dh,S] wants the
cache in a dh-minor layout that tile-pads 64->128 lanes (2x bytes) and,
when denied, reads the S-minor storage at a fraction of DMA peak (measured
~36 GB/s marginal on v5e at S=1024 vs ~819 GB/s peak). A Pallas kernel
reads the cache IN ITS STORAGE LAYOUT ([B, Hkv, dh, S], S minor) with one
[dh, block_s] DMA per grid step, so traffic is the unpadded cache bytes at
streaming bandwidth.

Grid design (the first paged kernel's mistake, corrected): COARSE. One grid
step covers ALL Hkv heads x one S block — grid (B, S/block_s) — so a
B=128, S=1024 Llama-1B decode is 256 grid steps/layer, not the 16k of a
(B, Hkv, page) grid whose per-step launch overhead dominated. Per-head dots
([G, dh] x [dh, block_s]) unroll in Python inside the kernel body. (The
paged read took the lesson one step further: ops/paged_attention's grid is
one step a ROW, with the row's live pages streamed by the kernel's own
copies, because a dense cache has no dead blocks to walk past and a block
table mostly has.)

Online softmax carries (m, l, acc) in VMEM scratch across the S axis
(innermost), masked by per-row lengths via scalar prefetch — identical
math to ops/flash_attention's streaming kernel.

Status: measured on v5e (B=128, S=1024, Llama-1B) this kernel matched the
stacked-cache XLA path but LOST to per-layer cache buffers with the plain
XLA einsum (~35 ms/step unrolled vs ~160 ms/step either stacked variant) —
the stacked-cache slicing, not the attention read, was the bottleneck. The
serving engine therefore uses llama_decode_step_unrolled; this kernel is
kept (tested against its reference) as the building block for reads that
CANNOT be expressed as a dense einsum over a per-layer buffer — e.g. a
future fused write+read decode kernel or block-sparse/windowed attention.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .scopes import kernel_scope

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def decode_attention_reference(q, k_cache, v_cache, lengths, k_scale=None,
                               v_scale=None):
    """Oracle in XLA. q: [B, H, dh]; k/v_cache: [B, Hkv, dh, S] (S-minor);
    lengths: [B] live positions (query attends [0, lengths)). -> [B, H, dh].

    k/v_scale: optional [B, Hkv, S] per-token dequant scales for int8
    caches (dequant value = int8 * scale).

    A row with lengths[b] == 0 returns ZEROS (there is nothing to attend);
    a plain masked softmax would instead emit the uniform mean of junk v —
    the kernel and this oracle agree on the zeros convention."""
    B, H, dh = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[-1]
    G = H // Hkv
    k = k_cache.astype(jnp.float32)
    v = v_cache.astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[:, :, None, :].astype(jnp.float32)
    if v_scale is not None:
        v = v * v_scale[:, :, None, :].astype(jnp.float32)
    qg = q.reshape(B, Hkv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhds->bhgs", qg, k) / math.sqrt(dh)
    pos = jnp.arange(S)[None, :]
    s = jnp.where((pos < lengths[:, None])[:, None, None, :], s,
                  DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhds->bhgd", p, v)
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(B, H, dh).astype(q.dtype)


def _decode_kernel(len_ref, *refs, block_s: int, n_kv: int, scale: float,
                   quantized: bool):
    """One (b, j) grid step: fold S block j into every head's online softmax.

    quantized=False refs: (q, k, v, o, m, l, acc)
    quantized=True  refs: (q, k, v, k_scale, v_scale, o, m, l, acc) — k/v are
    int8; dequant is FOLDED, never materialized: k's per-token scale
    multiplies the score matrix after the q·k dot (a row scale), and v's
    folds into the probabilities before the p·v dot."""
    from jax.experimental import pallas as pl

    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None

    b = pl.program_id(0)
    j = pl.program_id(1)                                   # S block (innermost)
    n_j = pl.num_programs(1)
    length = len_ref[b]
    Hkv, G = q_ref.shape[1], q_ref.shape[2]
    dh = q_ref.shape[3]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_s < length)
    def _compute():
        kv_pos = j * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (G, block_s), 1)
        mask = kv_pos < length
        for h in range(n_kv):                              # unrolled heads
            q = q_ref[0, h]                                # [G, dh]
            k = k_ref[0, h]                                # [dh, bs]
            v = v_ref[0, h]
            if quantized:
                k = k.astype(jnp.bfloat16)                 # in-VMEM upcast
            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks_ref[0, h][None, :].astype(jnp.float32)
            s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
            row = slice(h * G, (h + 1) * G)
            m_prev = m_scr[row]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[row] = m_new
            l_scr[row] = l_scr[row] * alpha + jnp.sum(p, axis=-1,
                                                      keepdims=True)
            if quantized:
                p = p * vs_ref[0, h][None, :].astype(jnp.float32)
                v = v.astype(jnp.bfloat16)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_scr[row] = acc_scr[row] * alpha + pv

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).reshape(Hkv, G, dh).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, lengths, k_scale=None, v_scale=None,
                     *, block_s: int = 512, mesh=None, interpret=None):
    """Pallas decode attention. q: [B, H, dh]; k/v_cache: [B, Hkv, dh, S];
    lengths: [B] int32. Returns [B, H, dh] in q.dtype.

    k/v_scale: optional [B, Hkv, S] per-token dequant scales — pass both to
    read int8 caches (the int8 bytes are what cross HBM; dequant folds into
    the existing dots, see _decode_kernel).

    mesh: the serving mesh when the caches are sharded over its "tp" axis
    (parallel/sharding.kv_cache_layer_spec); each shard then runs the
    kernel on its own heads under shard_map, as in ops/paged_attention."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as P

        operands = [q, k_cache, v_cache, lengths]
        specs = [P(None, "tp", None), P(None, "tp", None, None),
                 P(None, "tp", None, None), P()]
        if k_scale is not None and v_scale is not None:
            operands += [k_scale, v_scale]
            specs += [P(None, "tp", None), P(None, "tp", None)]
        return jax.shard_map(
            functools.partial(decode_attention, block_s=block_s,
                              interpret=interpret),
            mesh=mesh, in_specs=tuple(specs), out_specs=P(None, "tp", None),
            check_vma=False)(*operands)

    B, H, dh = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[-1]
    G = H // Hkv
    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"S={S} must divide by block_s={block_s}")

    qg = q.reshape(B, Hkv, G, dh)
    kernel = functools.partial(_decode_kernel, block_s=block_s, n_kv=Hkv,
                               scale=1.0 / math.sqrt(dh),
                               quantized=quantized)

    def kv_index(b, j, lens):
        # LIVE-LENGTH DMA CLAMP: blocks past a row's live length re-select
        # its last live block. Pallas skips the copy when consecutive grid
        # steps map to the same block, so per-row HBM traffic tracks
        # ceil(length / block_s) blocks, not S / block_s — dead blocks cost
        # nothing. Their compute is already skipped via pl.when; which block
        # sits in VMEM then is irrelevant.
        last_live = jnp.maximum((lens[b] + block_s - 1) // block_s - 1, 0)
        return (b, 0, 0, jnp.minimum(j, last_live))

    def scale_index(b, j, lens):
        last_live = jnp.maximum((lens[b] + block_s - 1) // block_s - 1, 0)
        return (b, 0, jnp.minimum(j, last_live))

    in_specs = [
        pl.BlockSpec((1, Hkv, G, dh), lambda b, j, lens: (b, 0, 0, 0)),
        pl.BlockSpec((1, Hkv, dh, block_s), kv_index),
        pl.BlockSpec((1, Hkv, dh, block_s), kv_index),
    ]
    operands = [lengths, qg, k_cache, v_cache]
    if quantized:
        in_specs += [pl.BlockSpec((1, Hkv, block_s), scale_index),
                     pl.BlockSpec((1, Hkv, block_s), scale_index)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # lengths
        grid=(B, S // block_s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, dh), lambda b, j, lens: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv * G, 1), jnp.float32),
            pltpu.VMEM((Hkv * G, 1), jnp.float32),
            pltpu.VMEM((Hkv * G, dh), jnp.float32),
        ],
    )
    with kernel_scope("decode_attention"):
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
            interpret=interpret,
        )(*operands)
    return out.reshape(B, H, dh)


def quantize_kv(x, axis: int = -2):
    """Symmetric int8 quantization along `axis` (the dh axis of a
    [..., dh, S]-shaped cache entry): returns (int8 values, scale) with
    dequant = int8 * scale and scale shaped like x minus `axis`.

    Per-token-per-head scales keep the quantization error of any one token
    independent of its neighbors — the property that makes int8 KV safe for
    long-context serving."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q8 = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                  ).astype(jnp.int8)
    return q8, jnp.squeeze(scale, axis=axis)
