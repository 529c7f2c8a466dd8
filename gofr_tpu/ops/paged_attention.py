"""Paged attention for TPU decode: block-table indirection via scalar prefetch.

Dense serving caches allocate [B, S_max] for every slot, so one long context
inflates every slot's footprint and per-step read cost (VERDICT r2 missing
#4; SURVEY.md §5 long-context row: "paged or ring-buffer KV cache in HBM").
Paging fixes both: K/V live in a fixed pool of fixed-size pages
[P, Hkv, dh, page_size] (S-minor tile-aligned layout, see
models/llama.init_kv_cache) and each slot owns just the pages its context
needs, mapped by a block table [B, NP] of page indices.

The TPU-native read is a Pallas kernel with SCALAR PREFETCH: the block
table and per-slot lengths ride in SMEM ahead of the grid walk, and the
K/V BlockSpec index_map reads table[b, p] to choose WHICH page the next
grid step DMAs from HBM — hardware-paced gather with no materialized
gathered cache (an XLA gather would copy the whole live cache every step).
Online softmax (m, l, acc) carries in VMEM scratch across the page axis,
exactly like ops/flash_attention's streaming kernel.

Grid: COARSE (B, NP) with NP innermost — one grid step covers ALL Hkv
heads of one page (per-head dots unroll in Python inside the body), the
lesson ops/decode_attention's module docstring records: a (B, Hkv, page)
grid's per-step launch overhead dominated the tiny per-step compute.
Pages past a slot's live length re-select its LAST live page in the
index map; Pallas skips the copy when consecutive steps map to the same
block, so per-row HBM traffic tracks live pages, and their compute is
skipped with pl.when.

The pool is STACKED over layers ([L, P, Hkv, dh, ps]) and carried whole
through the step programs' layer loops, so everything here takes the
stacked pool plus a `layer` index and never a per-layer slice: the kernels
receive the layer as one more prefetched scalar and their index maps pick
(layer, page). A dynamic slice of one layer would be a copy of that layer's
whole slab per layer per step.

Writes keep the storage layout. The TPU compiler lays a scatter's operand
out with the scattered window's dims minor; a per-token window is
[Hkv, dh], so a per-token XLA scatter into the pool makes the compiler
carry the WHOLE pool dh-minor (64 lanes padded to 128: twice the bytes,
plus a copy in and out of every program — llama1b widths with a 4 GiB
pool did not compile for a 16 GiB chip). So the decode write is a Pallas
read-modify-write of the row's current page (`paged_write_decode`), and
prefill windows are written as WHOLE pages (`paged_write_window`), whose
[Hkv, dh, ps] window is the storage layout's own minor dims.

The XLA `paged_attention_reference` (gather-based) is the numerics oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def paged_attention_reference(q, k_pool, v_pool, table, lengths,
                              k_scale=None, v_scale=None):
    """Gather-based oracle. q: [B, H, dh]; pools: [P, Hkv, dh, ps];
    table: [B, NP] int32 page ids; lengths: [B] live tokens per slot
    (including the current token). k/v_scale: optional [P, Hkv, ps]
    per-token dequant scales for int8 pools. Returns [B, H, dh] in
    q.dtype."""
    B, H, dh = q.shape
    P, Hkv, _, ps = k_pool.shape
    NP = table.shape[1]
    G = H // Hkv

    k = k_pool[table].astype(jnp.float32)  # [B, NP, Hkv, dh, ps]
    v = v_pool[table].astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale[table][:, :, :, None, :].astype(jnp.float32)
    if v_scale is not None:
        v = v * v_scale[table][:, :, :, None, :].astype(jnp.float32)
    k = jnp.moveaxis(k, 1, 3).reshape(B, Hkv, dh, NP * ps)
    v = jnp.moveaxis(v, 1, 3).reshape(B, Hkv, dh, NP * ps)

    qg = q.reshape(B, Hkv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bhgd,bhds->bhgs", qg, k) / math.sqrt(dh)
    pos = jnp.arange(NP * ps)[None, :]                    # [1, S]
    s = jnp.where((pos < lengths[:, None])[:, None, None, :], s,
                  DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhds->bhgd", p, v)
    return out.reshape(B, H, dh).astype(q.dtype)


def _paged_kernel(layer_ref, table_ref, len_ref, *refs, page_size: int,
                  n_kv: int, scale: float, quantized: bool):
    """One (b, p) grid step: fold page p (ALL heads) into the online
    softmax. Heads unroll in Python — the coarse grid keeps per-step
    launch overhead amortized over Hkv head-dots.

    quantized=False refs: (q, k, v, o, m, l, acc)
    quantized=True  refs: (q, k, v, k_scale, v_scale, o, m, l, acc) — int8
    pages with per-token scales; dequant FOLDS into the dots exactly like
    ops/decode_attention's quantized kernel (k's scale multiplies score
    rows, v's folds into the probabilities)."""
    from jax.experimental import pallas as pl

    if quantized:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None

    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    length = len_ref[b]
    G = q_ref.shape[2]
    dh = q_ref.shape[3]

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(p * page_size < length)
    def _compute():
        kv_pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (G, page_size), 1)
        mask = kv_pos < length
        for h in range(n_kv):                             # unrolled heads
            q = q_ref[0, h]                               # [G, dh]
            k = k_ref[0, h]                               # [dh, ps]
            v = v_ref[0, h]
            if quantized:
                k = k.astype(jnp.bfloat16)                # in-VMEM upcast
            s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if quantized:
                s = s * ks_ref[0, h][None, :].astype(jnp.float32)
            s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
            row = slice(h * G, (h + 1) * G)
            m_prev = m_scr[row]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            pr = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[row] = m_new
            l_scr[row] = l_scr[row] * alpha + jnp.sum(pr, axis=-1,
                                                      keepdims=True)
            if quantized:
                pr = pr * vs_ref[0, h][None, :].astype(jnp.float32)
                v = v.astype(jnp.bfloat16)
            pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                     (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_scr[row] = acc_scr[row] * alpha + pv

    @pl.when(p == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                    ).reshape(n_kv, G, dh).astype(o_ref.dtype)


def _stacked(pool, layer):
    """A pool as the kernels take it, stacked over layers: given with a
    layer index it already is; one layer's [P, ...] array (layer=None:
    tests, single-layer callers) is a free reshape to a one-layer stack."""
    return pool[None] if layer is None else pool


def _layer_operand(layer):
    """The layer index as the int32[1] scalar-prefetch operand."""
    if layer is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.reshape(layer, (1,)).astype(jnp.int32)


def _tp(mesh) -> bool:
    """Whether the kernels must run per tp shard: the compiler cannot
    partition a Mosaic kernel, and heads are independent, so under a tp
    mesh each shard runs the kernel on its own heads inside shard_map,
    with no collective."""
    return mesh is not None and mesh.shape.get("tp", 1) > 1


def _heads_spec(ndim: int, axis: int):
    """PartitionSpec sharding dim `axis` (the KV-head dim) over "tp"."""
    from jax.sharding import PartitionSpec

    return PartitionSpec(*("tp" if i == axis else None for i in range(ndim)))


def paged_attention(q, k_pool, v_pool, table, lengths, k_scale=None,
                    v_scale=None, *, layer=None, mesh=None, interpret=None):
    """Paged decode attention. q: [B, H, dh]; pools: [L, P, Hkv, dh, ps]
    with `layer` the int32 layer to read (or one layer's [P, Hkv, dh, ps]
    with layer=None); table: [B, NP] int32; lengths: [B] int32.
    Returns [B, H, dh].

    k/v_scale: optional [L, P, Hkv, ps] (or [P, Hkv, ps]) per-token dequant
    scales — pass both to read int8 pools (the int8 bytes are what cross
    HBM).

    mesh: the serving mesh when the pools are sharded over its "tp" axis
    (parallel/sharding.kv_cache_spec): q splits on H, pools and scales on
    Hkv, table, lengths and layer replicate.

    Dead table entries (p*ps >= lengths[b]) must hold a VALID page id
    (0 is fine); the index map re-selects the row's last live page for
    them, so consecutive dead steps skip their DMA entirely and their
    compute is skipped via pl.when.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized = k_scale is not None
    if quantized != (v_scale is not None):
        raise ValueError("pass both k_scale and v_scale or neither")
    layer_arr = _layer_operand(layer)
    k_pool, v_pool = _stacked(k_pool, layer), _stacked(v_pool, layer)
    if quantized:
        k_scale, v_scale = _stacked(k_scale, layer), _stacked(v_scale, layer)

    if _tp(mesh):
        from jax.sharding import PartitionSpec

        rep = PartitionSpec()
        operands = [q, k_pool, v_pool, table, lengths, layer_arr]
        specs = [_heads_spec(3, 1), _heads_spec(5, 2), _heads_spec(5, 2),
                 rep, rep, rep]
        if quantized:
            operands += [k_scale, v_scale]
            specs += [_heads_spec(4, 2), _heads_spec(4, 2)]

        def local(q, k_pool, v_pool, table, lengths, layer_arr, *scales):
            return paged_attention(q, k_pool, v_pool, table, lengths,
                                   *scales, layer=layer_arr[0],
                                   interpret=interpret)

        return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                             out_specs=_heads_spec(3, 1),
                             check_vma=False)(*operands)

    B, H, dh = q.shape
    _, _, Hkv, _, ps = k_pool.shape
    NP = table.shape[1]
    G = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if interpret:
        # the interpreter carries each operand whole through its grid
        # loop; one layer's slab (a copy the chip must never make) halves
        # a CPU decode step against handing it the stack
        def one_layer(pool):
            return jax.lax.dynamic_index_in_dim(pool, layer_arr[0], 0)

        k_pool, v_pool = one_layer(k_pool), one_layer(v_pool)
        if quantized:
            k_scale, v_scale = one_layer(k_scale), one_layer(v_scale)
        layer_arr = jnp.zeros((1,), jnp.int32)

    qg = q.reshape(B, Hkv, G, dh)
    kernel = functools.partial(_paged_kernel, page_size=ps, n_kv=Hkv,
                               scale=1.0 / math.sqrt(dh),
                               quantized=quantized)

    def live_page(b, p, table, lens):
        # LIVE-PAGE DMA CLAMP (see ops/decode_attention.kv_index): dead
        # steps re-select the last live page; equal consecutive block
        # indices skip the copy
        last_live = jnp.maximum((lens[b] + ps - 1) // ps - 1, 0)
        return table[b, jnp.minimum(p, last_live)]

    def page_index(b, p, layer, table, lens):
        return (layer[0], live_page(b, p, table, lens), 0, 0, 0)

    def scale_index(b, p, layer, table, lens):
        return (layer[0], live_page(b, p, table, lens), 0, 0)

    def row_index(b, p, layer, table, lens):
        return (b, 0, 0, 0)

    # the layer dim is squeezed out of the kernel's refs (None), so the
    # body indexes [page, head] exactly as it would one layer's pool
    in_specs = [
        pl.BlockSpec((1, Hkv, G, dh), row_index),
        pl.BlockSpec((None, 1, Hkv, dh, ps), page_index),
        pl.BlockSpec((None, 1, Hkv, dh, ps), page_index),
    ]
    operands = [layer_arr, table, lengths, qg, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((None, 1, Hkv, ps), scale_index),
                     pl.BlockSpec((None, 1, Hkv, ps), scale_index)]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, table, lengths
        grid=(B, NP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, G, dh), row_index),
        scratch_shapes=[
            pltpu.VMEM((Hkv * G, 1), jnp.float32),
            pltpu.VMEM((Hkv * G, 1), jnp.float32),
            pltpu.VMEM((Hkv * G, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        interpret=interpret,
    )(*operands)
    return out.reshape(B, H, dh)


def _write_kernel(layer_ref, page_ref, off_ref, *refs, n_kv: int,
                  quantized: bool):
    """One grid step = one row: read the row's current page (ALL heads)
    of each pool, put the new token's column at lane `off`, write the page
    back. refs: (new_k, new_v[, new_ks, new_vs], k_page, v_page[, ks_page,
    vs_page], k_out, v_out[, ks_out, vs_out]) — the out pages alias the
    pools, so the call updates them in place.

    The new values arrive [Hkv, dh] (dh on lanes) and a page wants them
    down its dh SUBLANES at one lane. The transposed-lhs dot
    new[Hkv, dh]^T x onehot[Hkv, ps] moves them there on the MXU, exactly:
    each product is a value times 1.0 or 0.0, accumulated in f32."""
    from jax.experimental import pallas as pl

    n = 4 if quantized else 2
    news, pages, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    off = off_ref[pl.program_id(0)]
    Hkv, dh = news[0].shape[1:]
    ps = outs[0].shape[-1]
    head = jax.lax.broadcasted_iota(jnp.int32, (Hkv, ps), 0)
    at_off = jax.lax.broadcasted_iota(jnp.int32, (Hkv, ps), 1) == off
    col_at_off = jax.lax.broadcasted_iota(jnp.int32, (dh, ps), 1) == off
    for new_ref, page, out in zip(news[:2], pages[:2], outs[:2]):
        new = new_ref[0]                                  # [Hkv, dh]
        # bf16 products with 1.0 are exact as they are; f32 values need
        # the full-precision passes or the MXU rounds them to bf16
        precision = (jax.lax.Precision.HIGHEST if new.dtype == jnp.float32
                     else None)
        for h in range(n_kv):                             # unrolled heads
            onehot = jnp.logical_and(head == h, at_off).astype(new.dtype)
            col = jax.lax.dot_general(
                new, onehot, (((0,), (0,)), ((), ())), precision=precision,
                preferred_element_type=jnp.float32)       # [dh, ps]
            out[0, h] = jnp.where(col_at_off, col.astype(out.dtype),
                                  page[0, h])
    for new_ref, page, out in zip(news[2:], pages[2:], outs[2:]):
        out[0] = jnp.where(at_off, new_ref[0], page[0])   # [Hkv, 1] lanes


def paged_write_decode(k_pool, v_pool, k, v, table, positions, k_scale=None,
                       v_scale=None, ks=None, vs=None, *, layer=None,
                       mesh=None, interpret=None):
    """Write one decode step's K/V into the pool, in place.

    k/v_pool: [L, P, Hkv, dh, ps] with `layer` the int32 layer to write
    (or one layer's [P, Hkv, dh, ps] with layer=None); k/v: [B, Hkv, dh]
    new entries; table: [B, NP]; positions: [B] absolute write positions.
    int8 pools also take their scale pools ([L, P, Hkv, ps]) and the new
    entries' scales ks/vs [B, Hkv]. Returns the updated pools in the
    order given: (k_pool, v_pool[, k_scale, v_scale]).

    Rows whose table entry is the garbage page (inactive slots) all write
    page 0; which of them wins is unspecified and nobody reads it.

    interpret=None picks by the backend: on the TPU the Pallas kernel; off
    it the plain per-token column write (`_write_columns`), which is the
    kernel's reference and, on the CPU, several times faster than the
    kernel interpreted — the layout fault the kernel exists for is the TPU
    compiler's. Pass True or False to run the kernel itself either way.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized = k_scale is not None
    stacked = layer is not None
    layer_arr = _layer_operand(layer)
    pools = [_stacked(pool, layer) for pool in
             [k_pool, v_pool] + ([k_scale, v_scale] if quantized else [])]
    news = [k, v] + ([ks, vs] if quantized else [])
    n = len(pools)
    if interpret is None and jax.default_backend() != "tpu":
        return _unstack(_write_columns(pools, news, table, positions,
                                       layer_arr[0]), stacked)

    if _tp(mesh):
        from jax.sharding import PartitionSpec

        rep = PartitionSpec()
        pool_specs = [_heads_spec(pool.ndim, 2) for pool in pools]
        specs = (pool_specs + [_heads_spec(new.ndim, 1) for new in news]
                 + [rep, rep, rep])

        def local(*args):
            pools, news = args[:n], args[n:2 * n]
            table, positions, layer_arr = args[2 * n:]
            return paged_write_decode(
                pools[0], pools[1], news[0], news[1], table, positions,
                *pools[2:], *news[2:], layer=layer_arr[0],
                interpret=interpret)

        out = jax.shard_map(
            local, mesh=mesh, in_specs=tuple(specs),
            out_specs=tuple(pool_specs), check_vma=False)(
                *pools, *news, table, positions, layer_arr)
        return _unstack(out, stacked)

    if k_pool.dtype == jnp.int8:
        # int8 values are exact in bf16, and the kernel's dot wants floats
        news[:2] = [new.astype(jnp.bfloat16) for new in news[:2]]
    news[2:] = [scale[..., None] for scale in news[2:]]   # [B, Hkv, 1]

    B, Hkv, dh = k.shape
    ps = k_pool.shape[-1]
    interpret = bool(interpret)
    page_ids = table[jnp.arange(B), positions // ps]       # [B]
    offsets = positions % ps                               # [B]

    def page_block(pool):
        zeros = (0,) * (pool.ndim - 2)
        return pl.BlockSpec(
            (None, 1) + pool.shape[2:],
            lambda b, layer, pages, offs: (layer[0], pages[b]) + zeros)

    def new_block(new):
        return pl.BlockSpec((1,) + new.shape[1:],
                            lambda b, layer, pages, offs: (b, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # layer, page ids, offsets
        grid=(B,),
        in_specs=[new_block(x) for x in news] + [page_block(x) for x in pools],
        out_specs=[page_block(x) for x in pools],
    )
    out = pl.pallas_call(
        functools.partial(_write_kernel, n_kv=Hkv, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools],
        # operand order: 3 scalars, n news, n pools -> pool i aliases out i
        input_output_aliases={3 + n + i: i for i in range(n)},
        interpret=interpret,
    )(layer_arr, page_ids, offsets, *news, *pools)
    return _unstack(out, stacked)


def _write_columns(pools, news, table, positions, layer):
    """The decode write as plain per-token scatters: new[b] goes to column
    positions[b] % ps of page table[b, positions[b] // ps] of `layer`, in
    every pool (value pools [L, P, Hkv, dh, ps] take [B, Hkv, dh], scale
    pools [L, P, Hkv, ps] take [B, Hkv])."""
    ps = pools[0].shape[-1]
    page_ids = table[jnp.arange(table.shape[0]), positions // ps]
    offsets = positions % ps
    mid = {5: (slice(None), slice(None)), 4: (slice(None),)}
    return [pool.at[(layer, page_ids) + mid[pool.ndim] + (offsets,)].set(new)
            for pool, new in zip(pools, news)]


def _unstack(pools, stacked: bool):
    return tuple(pools) if stacked else tuple(pool[0] for pool in pools)


def _row_major(x):
    """Pin x to its row-major storage layout (a no-op off the TPU)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(x, Layout(tuple(range(x.ndim))))


def paged_write_window(pool, window, table, starts, lengths, *, layer=None):
    """Write a prefill window into the pool as WHOLE pages.

    pool: [L, P, *page, ps] (a value pool's page is [Hkv, dh], a scale
    pool's [Hkv]); window: [Lw, K, *page, T] fresh entries for absolute
    positions [starts[k], starts[k] + T) of row k, starts[k] a multiple of
    ps (0 for a prompt prefilled whole, the shared prefix's length for a
    prefix-cache tail); table: [K, NP] the rows' page ids; lengths: [K]
    full prompt lengths. layer=None writes every layer (Lw == L), an int32
    layer writes that one (Lw == 1).

    The window's pages are the row's own fresh pages, so nothing live is
    overwritten: entries at positions >= lengths[k] are written as zeros,
    and a page lying wholly past the prompt diverts to the reserved
    GARBAGE page (pool page 0, the PageAllocator invariant). ONE
    implementation on purpose — values and scales must land by the
    identical rule or dequantization silently mismatches.
    """
    ps = pool.shape[-1]
    Lw, K, T = window.shape[0], window.shape[1], window.shape[-1]
    n_src = -(-T // ps)
    pos = starts[:, None] + jnp.arange(n_src * ps, dtype=jnp.int32)[None, :]
    if n_src * ps != T:
        window = jnp.pad(window, [(0, 0)] * (window.ndim - 1)
                         + [(0, n_src * ps - T)])
    live = (pos < lengths[:, None]).reshape(
        (1, K) + (1,) * (window.ndim - 3) + (n_src * ps,))
    window = jnp.where(live, window, jnp.zeros((), window.dtype))
    # [Lw, K, *page, n_src, ps] -> [Lw, K * n_src, *page, ps]
    pages = window.reshape(window.shape[:-1] + (n_src, ps))
    pages = jnp.moveaxis(pages, -2, 2)
    pages = pages.reshape((Lw, K * n_src) + pages.shape[3:])
    slot = starts[:, None] // ps + jnp.arange(n_src, dtype=jnp.int32)[None, :]
    page_ids = jnp.take_along_axis(
        table, jnp.clip(slot, 0, table.shape[1] - 1), axis=1)  # [K, n_src]
    page_ids = jnp.where(slot * ps < lengths[:, None], page_ids,
                         jnp.int32(0)).reshape(K * n_src)
    pages = _row_major(pages.astype(pool.dtype))
    if layer is None:
        return pool.at[:, page_ids].set(pages)
    return pool.at[layer, page_ids].set(pages[0])


def paged_write_prefill_stacked(k_pool, v_pool, tmp_k, tmp_v, table, lengths):
    """A whole-prompt prefill window's K/V into the stacked page pool.

    k/v_pool: [L, P, Hkv, dh, ps]; tmp_k/v: [L, K, Hkv, dh, T] fresh window
    entries at positions [0..T) (the serving prefill's tmp-cache layout);
    table: [K, NP]; lengths: [K] true prompt lengths.
    Returns updated (k_pool, v_pool).
    """
    starts = jnp.zeros_like(lengths)
    return (paged_write_window(k_pool, tmp_k, table, starts, lengths),
            paged_write_window(v_pool, tmp_v, table, starts, lengths))


def paged_write_prefill_scales(s_pool, tmp_s, table, lengths):
    """A prefill window's per-token dequant scales into the stacked scale
    pool. s_pool: [L, P, Hkv, ps]; tmp_s: [L, K, Hkv, T]; table: [K, NP];
    lengths: [K]. Shares the value writer's rule (paged_write_window)."""
    return paged_write_window(s_pool, tmp_s, table, jnp.zeros_like(lengths),
                              lengths)


def paged_write_prefill(k_pool, v_pool, k, v, table, lengths):
    """Single-layer convenience over paged_write_prefill_stacked.

    k/v_pool: [P, Hkv, dh, ps]; k/v: [K, T, Hkv, dh] fresh entries at
    positions [0..T). Returns updated (k_pool, v_pool).
    """
    kp, vp = paged_write_prefill_stacked(
        k_pool[None], v_pool[None],
        k.transpose(0, 2, 3, 1)[None], v.transpose(0, 2, 3, 1)[None],
        table, lengths)
    return kp[0], vp[0]
