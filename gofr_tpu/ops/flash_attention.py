"""Flash attention for TPU: blocked online-softmax Pallas kernel.

Why a kernel at all: XLA's stock attention materialises the [T, S] score
matrix in HBM per head — at long context that is the bandwidth bottleneck
(SURVEY.md §5 long-context row). This kernel streams K/V through VMEM one
block at a time with a running (max, sum, acc) online softmax, so VMEM
holds O(block_kv·dh) of K/V at any moment (long contexts fit) and HBM
traffic per q block is one pass over K/V with the two matmuls per block
hitting the MXU back to back.

Design notes (pallas_guide.md):
  - two kernels behind one dispatch. When K+V for one head fit a VMEM
    budget, the RESIDENT kernel holds them whole and fori-loops kv blocks —
    K/V are fetched once per (batch, kv-head) grid walk, so GQA heads and
    all q blocks reuse them (fastest, the serving regime). Beyond the
    budget, the STREAMING kernel makes the kv axis the innermost grid
    dimension with the online-softmax carry (m, l, acc) in VMEM scratch
    that persists across kv steps (reset at j == 0, output written at the
    last j) — VMEM holds only O(block_kv·dh) of K/V, so 64k+ contexts
    compile and run.
  - GQA without materialising repeated heads: the K/V BlockSpec index map
    folds query head h onto kv head h // (H // Hkv).
  - causal skipping: kv blocks fully above the diagonal are skipped — the
    resident kernel bounds its fori_loop, the streaming kernel predicates
    compute with pl.when (the block fetch still occurs there; block
    scheduling is static).
  - a window (`window=W`, causal): query i sees keys (i - W, i], W with
    its own. kv blocks wholly behind the window are skipped as those above
    the diagonal are: the resident kernel starts its fori_loop at the
    first block a row of the q block can see, the streaming kernel
    predicates compute and its K/V index maps stay on the nearest block
    inside the band, so a skipped step fetches nothing new.
  - padding is static: wrappers pad T/S to block multiples at trace time and
    the mask closes over the true lengths as Python ints — no SMEM scalars,
    no dynamic shapes.
  - bf16 operands into the MXU (preferred_element_type=f32 accumulation);
    only softmax statistics and the accumulator stay f32.
  - two widths: q and k share one (dh, which sets the scale), v and the
    output may have another (dv). Latent attention's published form has
    keys of 192 (128 + a rotated 64) and values of 128; padding v to 192
    would cost half again of the P V product and of v's bytes.

Training uses flash_attention (custom_vjp): the backward pass recomputes
standard attention under jax.vjp — residuals are just (q, k, v), so the
FORWARD is O(T·dh) memory, but the recompute-backward materialises the
[T, S] probabilities like stock attention does (a blocked backward kernel
is the known fix and is future work); at long context prefer
jax.checkpoint/remat granularity or ring attention (ops/ring_attention.py)
for the backward-heavy regime.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .scopes import kernel_scope

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# K+V bytes per head from which the streaming kernel takes over: the
# resident kernel holds them twice (double-buffered) beside its q, output and
# score blocks, in the 16 MiB of VMEM the compiler gives a kernel unasked. At
# exactly 6 MiB (12,288 keys of 128 + 128 in bfloat16, blocks of 512) that
# came to 16.14 MiB and the chip's compiler refused the kernel
VMEM_KV_BUDGET_BYTES = 6 * 1024 * 1024


def _kernel_resident(q_ref, k_ref, v_ref, o_ref, *, causal: bool,
                     kv_len: int, block_kv: int, scale: float,
                     window: Optional[int] = None):
    """K/V whole-sequence resident in VMEM; fori_loop over kv blocks."""
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[2]
    dv = v_ref.shape[3]
    i = pl.program_id(2)
    q = q_ref[0, 0]                                        # [bq, dh], model dtype
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)

    n_kv = k_ref.shape[2] // block_kv
    if causal:
        # highest kv block any row of this q block can see
        hi = jnp.minimum((i * block_q + block_q + block_kv - 1) // block_kv, n_kv)
    else:
        hi = n_kv
    # lowest kv block any row of this q block can see
    lo = 0 if window is None else jnp.maximum(
        i * block_q - window + 1, 0) // block_kv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(j * block_kv, block_kv), :]
        v = v_ref[0, 0, pl.ds(j * block_kv, block_kv), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = kv_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kv_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, kv_pos > q_pos - window)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if window is not None:
            # a row may see nothing of the first block of its band
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return m_new, l, acc * alpha + pv

    m0 = jnp.full((block_q, 1), DEFAULT_MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, dv), jnp.float32)
    _, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _kernel_streaming(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                      causal: bool, kv_len: int, block_kv: int, scale: float,
                      window: Optional[int] = None):
    from jax.experimental import pallas as pl

    block_q = q_ref.shape[2]
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (innermost: carry lives in scratch)
    n_kv = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def compute():
        q = q_ref[0, 0]                                    # [bq, dh], model dtype
        k = k_ref[0, 0]                                    # [bkv, dh]
        v = v_ref[0, 0]
        # bf16 operands into the MXU, f32 accumulation out of it
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kv_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = kv_pos < kv_len
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            mask = jnp.logical_and(mask, kv_pos <= q_pos)
            if window is not None:
                mask = jnp.logical_and(mask, kv_pos > q_pos - window)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))  # [bq,1]
        p = jnp.exp(s - m_new)
        if window is not None:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv

    if causal and window is not None:
        # skip kv blocks fully above the diagonal or wholly behind the window
        @pl.when(jnp.logical_and(
            j * block_kv <= i * block_q + block_q - 1,
            j * block_kv + block_kv - 1 > i * block_q - window))
        def _():
            compute()
    elif causal:
        # skip kv blocks fully above the diagonal
        @pl.when(j * block_kv <= i * block_q + block_q - 1)
        def _():
            compute()
    else:
        compute()

    @pl.when(j == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(o_ref.dtype)


def _flash_bhtd(q, k, v, *, causal: bool, block_q: int, block_kv: int,
                interpret: Optional[bool], window: Optional[int] = None,
                scale: Optional[float] = None):
    """Core call on [B, H, T, dh] q, [B, Hkv, S, dh] k and [B, Hkv, S, dv]
    v layouts. `scale` of the scores: 1 / sqrt(dh) unless given."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, dh = q.shape
    _, Hkv, S, dv = v.shape
    G = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if scale is None:
        scale = 1.0 / math.sqrt(dh)

    block_q = min(block_q, _ceil_to(T, 16))
    block_kv = min(block_kv, _ceil_to(S, 16))
    Tp, Sp = _ceil_to(T, block_q), _ceil_to(S, block_kv)
    if Tp != T:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sp - S), (0, 0)))

    # `window` reaches the kernels only where it is given: a call without
    # one is the program it was
    windowed = {} if window is None else {"window": window}
    resident = Sp * (dh + dv) * q.dtype.itemsize < VMEM_KV_BUDGET_BYTES
    if resident:
        kernel = functools.partial(
            _kernel_resident, causal=causal, kv_len=S, block_kv=block_kv,
            scale=scale, **windowed)
        with kernel_scope("flash_prefill"):
            out = pl.pallas_call(
                kernel,
                grid=(B, H, Tp // block_q),
                in_specs=[
                    pl.BlockSpec((1, 1, block_q, dh), lambda b, h, i: (b, h, i, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, 1, Sp, dh), lambda b, h, i: (b, h // G, 0, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, 1, Sp, dv), lambda b, h, i: (b, h // G, 0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((1, 1, block_q, dv), lambda b, h, i: (b, h, i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((B, H, Tp, dv), q.dtype),
                interpret=interpret,
            )(q, k, v)
        return out[:, :, :T, :]

    kernel = functools.partial(
        _kernel_streaming, causal=causal, kv_len=S, block_kv=block_kv,
        scale=scale, **windowed)

    def kv_block(i, j):
        """The kv block step (i, j) fetches: j, or under a window the
        nearest block inside q block i's band (a step outside it computes
        nothing, and a block index that repeats is not fetched again)."""
        if window is None:
            return j
        lo = jnp.maximum(i * block_q - window + 1, 0) // block_kv
        hi = (i * block_q + block_q - 1) // block_kv
        return jnp.clip(j, lo, hi)
    with kernel_scope("flash_prefill"):
        out = pl.pallas_call(
            kernel,
            grid=(B, H, Tp // block_q, Sp // block_kv),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, dh), lambda b, h, i, j: (b, h, i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, block_kv, dh),
                             lambda b, h, i, j: (b, h // G, kv_block(i, j), 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, 1, block_kv, dv),
                             lambda b, h, i, j: (b, h // G, kv_block(i, j), 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, block_q, dv), lambda b, h, i, j: (b, h, i, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((B, H, Tp, dv), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),    # running max
                pltpu.VMEM((block_q, 1), jnp.float32),    # running sum
                pltpu.VMEM((block_q, dv), jnp.float32),   # output accumulator
            ],
            interpret=interpret,
        )(q, k, v)
    return out[:, :, :T, :]


def attention_reference(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None):
    """Unblocked GQA attention in f32 — the numerics oracle and the recompute
    target for the backward pass. Layout [B, T, H, dh] / [B, S, Hkv, dh]
    (v and the output [.., dv]).
    When T < S under causal, queries are the LAST T positions. `window` W
    (causal): query i sees keys (i - W, i]. `scale` of the scores:
    1 / sqrt(dh) unless given."""
    B, T, H, dh = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bthgd,bshd->bhgts", qg, k.astype(jnp.float32))
    s = s / math.sqrt(dh) if scale is None else s * scale
    if causal:
        at = jnp.arange(T)[:, None] + (S - T)
        mask = jnp.arange(S)[None, :] <= at
        if window is not None:
            mask = jnp.logical_and(mask, jnp.arange(S)[None, :] > at - window)
        s = jnp.where(mask[None, None, None, :, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, H, v.shape[-1]).astype(q.dtype)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_kv: int = 128, interpret: Optional[bool] = None,
                    *, mesh=None, window: Optional[int] = None,
                    scale: Optional[float] = None):
    """Flash attention on [B, T, H, dh] q, [B, S, Hkv, dh] k and
    [B, S, Hkv, dv] v (GQA folds query head h onto kv head h // (H // Hkv)).
    Returns [B, T, H, dv] in q.dtype. `window` W (causal only): query i
    sees keys (i - W, i]; kv blocks wholly outside are skipped. `scale` of
    the scores: 1 / sqrt(dh) unless given (YaRN's mscale: models/mla_moe.py).

    mesh: the serving mesh when q/k/v are sharded over its "tp" axis on
    their head dims (column-parallel wq/wk/wv); each shard then runs the
    kernel on its own heads under shard_map, as in ops/paged_attention."""
    if window is not None and not causal:
        raise ValueError("a window is a causal mask's")
    local = functools.partial(_flash_local, causal=causal, block_q=block_q,
                              block_kv=block_kv, interpret=interpret,
                              window=window, scale=scale)
    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as P

        heads = P(None, None, "tp", None)
        return jax.shard_map(local, mesh=mesh, in_specs=(heads, heads, heads),
                             out_specs=heads, check_vma=False)(q, k, v)
    return local(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_local(q, k, v, causal: bool = True, block_q: int = 128,
                 block_kv: int = 128, interpret: Optional[bool] = None,
                 window: Optional[int] = None, scale: Optional[float] = None):
    if causal and q.shape[1] != k.shape[1]:
        # mixed-length causal needs the position offset folded into the mask;
        # the kernel path covers the hot shapes (T==S full-causal, and any
        # non-causal read) — everything else takes the exact oracle
        return attention_reference(q, k, v, causal=causal, window=window,
                                   scale=scale)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash_bhtd(qt, kt, vt, causal=causal, block_q=block_q,
                      block_kv=block_kv, interpret=interpret, window=window,
                      scale=scale)
    return out.transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, causal, block_q, block_kv, interpret, window, scale):
    return (_flash_local(q, k, v, causal, block_q, block_kv, interpret,
                         window, scale), (q, k, v))


def _flash_bwd(causal, block_q, block_kv, interpret, window, scale,
               residuals, g):
    q, k, v = residuals
    _, vjp = jax.vjp(lambda q, k, v: attention_reference(
        q, k, v, causal=causal, window=window, scale=scale), q, k, v)
    return vjp(g)


_flash_local.defvjp(_flash_fwd, _flash_bwd)
