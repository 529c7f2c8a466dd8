"""Block-sparse softmax attention with a learned choice (InfLLM-V2, the
`minicpm4` mixer of MiniCPM4, arXiv:2506.07900): a query past `dense_len`
attends the tokens of `topk` key blocks of `block_size` tokens, chosen a
step, a KV head, by scoring the query against COMPRESSED keys.

    c_j = mean(k_i, stride j <= i < stride j + kernel)   kernel = 2 stride
          visible to the query at t iff stride j + kernel - 1 <= t
    p_hj = softmax_j(q_th . c_j / sqrt(d)) over the visible j, float32
    r_j  = sum of p_hj over the query heads h of the KV head
    s_b  = max(r_j : per b - 1 <= j <= per b + per - 1, j visible)
           per = block_size / stride compressed keys start in a block
    forced: the first `init_blocks` blocks and the `window_blocks` that end
           at t's own; chosen = forced + the topk - |forced| best of the
           other blocks at or before t's by s_b, ties to the lower index
    a query at t + 1 <= dense_len attends every token <= t

What is kept for it (models/sparse_linear.py, tpu/paging.py): the
compressed keys, a COLUMN for every `stride` tokens, in a plane of their own
beside K and V (models/protocol.py `Plane.stride`: [blocks, pages, heads,
page_size / stride, width], width-minor, written at prefill and whenever a
decode block's tail completes a stride), and, a slot, the sums of the two
half-windows of `stride` keys that the next compressed key will be made of
(`half_sums`: a key belongs to two overlapping windows), so that no step
reads a key back from the pages to make one.

The step's work is mixed. The choice (`sparse_select`) is latency-bound: a
gather of the row's compressed keys through its page table (XLA), one
kernel a block for the scores, the softmax and the sum over the heads, then
the pooling, the top-k and the page lists in XLA again (fusions, which a
trace does not name). The read (`sparse_read`) is bandwidth-bound and is a
FORM of ops/paged_attention.py's `_paged_kernel`, not a kernel of its own:
each (row, KV head) becomes a row of that kernel over pools seen as
[blocks, pages x heads, 1, d, page_size], its table the LIST of the pages
that hold a chosen block and, a listed page, a bit a block that says which
of the page's blocks the row attends. A page is copied whole (a block of 64
tokens is half a page's lanes, and a copy's window is whole tiles), so a
far block costs its page's other half too: up to 1.5x the chosen bytes.

A prompt whose queries past `dense_len` each choose their own blocks
(`sparse_prefill`): the same choice applied as a mask inside a streaming
flash kernel over the fresh window, a key tile that no query of the tile
chose skipped (its fetch too: the index map stays on the last tile that
was needed). The scores are computed TRANSPOSED, keys down the sublanes and
queries along the lanes, so that a query's bits arrive as a row over the
lanes and a key block is a range of sublanes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .scopes import kernel_scope

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# -- compressed keys ----------------------------------------------------------
def half_sums_prefill(k, lengths, stride: int):
    """k [K, T, Hkv, dh] of a fresh window, lengths [K]. Returns (the
    compressed keys [K, T / stride, Hkv, dh] in k's dtype, column j the mean
    of tokens [stride j, stride j + 2 stride), zeros where the window is not
    complete inside the row's length; the half-window sums a slot keeps
    [K, 2, Hkv, dh] float32 as of each row's length: [0] the last complete
    half-window's, [1] the running one's)."""
    K, T, Hkv, dh = k.shape
    n = T // stride
    real = jnp.arange(T)[None, :] < lengths[:, None]
    halves = jnp.where(real[:, :, None, None], k.astype(jnp.float32), 0.0)
    halves = halves.reshape(K, n, stride, Hkv, dh).sum(axis=2)
    pairs = halves + jnp.concatenate(
        [halves[:, 1:], jnp.zeros_like(halves[:, :1])], axis=1)
    complete = (jnp.arange(n)[None, :] + 2) * stride <= lengths[:, None]
    ck = jnp.where(complete[:, :, None, None], pairs / (2 * stride), 0.0)
    at = lengths // stride                       # the running half-window
    rows = jnp.arange(K)
    running = jnp.where((lengths % stride != 0)[:, None, None],
                        halves[rows, jnp.minimum(at, n - 1)], 0.0)
    last = jnp.where((at >= 1)[:, None, None],
                     halves[rows, jnp.maximum(at - 1, 0)], 0.0)
    return ck.astype(k.dtype), jnp.stack([last, running], axis=1)


def half_sums_step(sums, layer: int, k, positions, live, stride: int):
    """One decode step. sums [L, S, 2, Hkv, dh] float32; k [S, Hkv, dh] the
    token at positions [S]. Returns (sums, c [S, Hkv, dh] float32: the
    compressed key this token completes, completes [S] bool)."""
    last, running = sums[layer, :, 0], sums[layer, :, 1]
    running = running + k.astype(jnp.float32)
    ends = (positions + 1) % stride == 0
    completes = jnp.logical_and(jnp.logical_and(
        ends, positions >= 2 * stride - 1), live)
    c = (last + running) / (2 * stride)
    turn = jnp.logical_and(ends, live)[:, None, None]
    keep = live[:, None, None]
    new = jnp.stack([jnp.where(turn, running, last),
                     jnp.where(turn, 0.0, running)], axis=1)
    sums = sums.at[layer].set(jnp.where(keep[:, None], new, sums[layer]))
    return sums, c, completes


def columns(tokens, stride: int, kernel: int):
    """Compressed keys complete in the first `tokens` tokens."""
    return jnp.maximum(tokens - kernel + stride, 0) // stride


# -- the choice ---------------------------------------------------------------
def choose(r, t, *, per: int, topk: int, init_blocks: int,
           window_blocks: int, dense_len: int, block_size: int):
    """r [..., N] float32, the head-summed probabilities of the compressed
    keys, -inf where one is not visible; t [...] int32 the query's
    position. Returns chosen [..., N / per] bool: the blocks the query
    attends (every block at or before its own where t + 1 <= dense_len)."""
    N = r.shape[-1]
    n_blocks = N // per
    lead = r.shape[:-1]
    low = jnp.full(lead + (1,), -jnp.inf, r.dtype)
    padded = jnp.concatenate([low, r] + [low] * (per - 1), axis=-1)
    rows = padded.reshape(lead + (n_blocks + 1, per))
    score = jnp.maximum(jnp.max(rows[..., :-1, :], axis=-1),
                        rows[..., 1:, 0])
    block = jnp.arange(n_blocks, dtype=jnp.int32)
    own = (t // block_size)[..., None]
    forced = jnp.logical_or(block < init_blocks, block > own - window_blocks)
    reachable = block <= own
    if n_blocks <= topk:        # a table this narrow holds no more blocks
        return jnp.broadcast_to(reachable, score.shape)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(reachable, score, -jnp.inf)
    kth = -jnp.sort(-score, axis=-1)[..., topk - 1:topk]
    over = score > kth
    tied = score == kth
    room = topk - jnp.sum(over, axis=-1, keepdims=True)
    chosen = jnp.logical_or(over, jnp.logical_and(
        tied, jnp.cumsum(tied, axis=-1) <= room))
    dense = (t + 1 <= dense_len)[..., None]
    return jnp.logical_and(jnp.logical_or(chosen, dense), reachable)


def select_scores_reference(q, ck, n_visible):
    """q [B, H, dh]; ck [B, Hkv, N, dh]; n_visible [B] int32. Returns
    r [B, Hkv, N] float32: each compressed key's softmax probability summed
    over the query heads of its KV head, -inf where it is not visible."""
    B, H, dh = q.shape
    Hkv, N = ck.shape[1:3]
    qg = q.reshape(B, Hkv, H // Hkv, dh)
    s = jnp.einsum("bghd,bgnd->bghn", qg, ck,
                   preferred_element_type=jnp.float32) / math.sqrt(dh)
    visible = jnp.arange(N)[None, :] < n_visible[:, None]
    s = jnp.where(visible[:, None, None, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    r = jnp.sum(p, axis=2)
    return jnp.where(visible[:, None, :], r, -jnp.inf)


def _select_kernel(n_ref, q_ref, c_ref, r_ref, *, scale: float):
    from jax.experimental import pallas as pl

    n = n_ref[pl.program_id(0)]
    s = scale * jax.lax.dot_general(
        q_ref[0], c_ref[0], (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)                # [Hkv, G, N]
    visible = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) < n
    s = jnp.where(visible, s, DEFAULT_MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(visible, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    seen = jax.lax.broadcasted_iota(jnp.int32, r_ref.shape[1:], 1) < n
    r_ref[0] = jnp.where(seen, jnp.sum(p, axis=1), -jnp.inf)


def select_scores(q, ck, n_visible, *, interpret=None):
    """`select_scores_reference` as one kernel call (`sparse_select`): a
    grid step a row, both KV heads' products batched."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, dh = q.shape
    Hkv, N = ck.shape[1:3]
    G = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, Hkv, G, dh), lambda b, n: (b, 0, 0, 0)),
                  pl.BlockSpec((1, Hkv, N, dh), lambda b, n: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, Hkv, N), lambda b, n: (b, 0, 0)))
    with kernel_scope("sparse_select"):
        return pl.pallas_call(
            functools.partial(_select_kernel, scale=1.0 / math.sqrt(dh)),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, Hkv, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(n_visible.astype(jnp.int32), q.reshape(B, Hkv, G, dh), ck)


def gather_compressed(ck_pool, layer: int, table, ck_tail, n_pool, n_tail):
    """A row's compressed keys side by side, [B, Hkv, NP x cols, dh]: the
    pool's columns through the row's page table (column j of the row in
    page j // cols), then the decode block's own (ck_tail [L, B, Hkv, n,
    dh]: the ones its steps completed so far) at columns n_pool ..
    n_pool + n_tail - 1."""
    B, NP = table.shape
    pages = ck_pool[layer, table]                  # [B, NP, Hkv, cols, dh]
    Hkv, cols, dh = pages.shape[2:]
    ck = pages.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, NP * cols, dh)
    at = jnp.arange(NP * cols, dtype=jnp.int32)[None, :]
    for i in range(ck_tail.shape[3]):
        here = jnp.logical_and(at == (n_pool + i)[:, None],
                               (i < n_tail)[:, None])
        ck = jnp.where(here[:, None, :, None],
                       ck_tail[layer, :, :, i][:, :, None, :], ck)
    return ck


# -- the decode read ----------------------------------------------------------
def page_lists(chosen, table, lengths, page_size: int, block_size: int,
               width: int):
    """What `sparse_read` takes, from chosen [B, Hkv, n_blocks] bool, the
    row's page table [B, NP] and the tokens it holds in pages, lengths [B]:
    (pages [B, Hkv, width] int32, the pages that hold a chosen block with a
    token under `lengths`, in order, the garbage page past them; bits
    [B, Hkv, width] int32, bit i of an entry its page's block i; held
    [B, Hkv] int32, the tokens the list spans: whole pages but a last one
    cut at the row's length)."""
    B, Hkv, n_blocks = chosen.shape
    per = page_size // block_size
    NP = n_blocks // per
    start = jnp.arange(n_blocks, dtype=jnp.int32) * block_size
    chosen = jnp.logical_and(chosen, start[None, None, :] < lengths[:, None, None])
    bits = jnp.sum(chosen.reshape(B, Hkv, NP, per).astype(jnp.int32)
                   << jnp.arange(per, dtype=jnp.int32), axis=-1)
    listed = bits > 0
    order = jnp.argsort(jnp.logical_not(listed), axis=-1, stable=True)
    order = order[..., :width].astype(jnp.int32)
    count = jnp.minimum(jnp.sum(listed, axis=-1), width).astype(jnp.int32)
    inside = jnp.arange(order.shape[-1], dtype=jnp.int32) < count[..., None]
    logical = jnp.minimum(order, table.shape[1] - 1)
    pages = jnp.take_along_axis(
        jnp.broadcast_to(table[:, None, :], (B, Hkv, table.shape[1])),
        logical, axis=-1)
    pages = jnp.where(inside, pages, 0)
    bits = jnp.where(inside, jnp.take_along_axis(bits, order, axis=-1), 0)
    # the page a row's length falls inside is its last chosen one (the
    # forced window holds it): the list stops at the length, not the page
    cut = jnp.where(lengths % page_size != 0,
                    page_size - lengths % page_size, 0)
    held = jnp.maximum(count * page_size - cut[:, None], 0)
    return pages, bits, jnp.where(count > 0, held, 0).astype(jnp.int32)


def sparse_read(q, k, v, k_pool, v_pool, k_tail, v_tail, pages, bits, held,
                tail_lens, *, layer: int, block_size: int, interpret=None):
    """One step's block-sparse attention inside a decode block: row b's new
    k, v [B, Hkv, dh] become token tail_lens[b] - 1 of its tail, and each
    of its KV heads attends the chosen blocks of the pages listed for it
    (`page_lists`), then the first tail_lens[b] tokens of its tail, in one
    softmax. q [B, H, dh]; pools [L, P, Hkv, dh, ps]; tails [L, B, Hkv, T,
    dh'] (ops/paged_attention `plane_tail`). Returns (attention [B, H, dh],
    k_tail, v_tail). A (row, KV head) is a row of ops/paged_attention's
    kernel over the pools seen a head a page."""
    from .paged_attention import _paged_read

    B, H, dh = q.shape
    L, P, Hkv, _, ps = k_pool.shape
    G = H // Hkv
    head = jnp.arange(Hkv, dtype=jnp.int32)[None, :, None]

    def a_head(pool):
        return pool.reshape(L, P * Hkv, 1, dh, ps)

    def a_head_tail(tail):
        return tail.reshape(L, B * Hkv, 1, *tail.shape[3:])

    width = pages.shape[-1]
    out, k_out, v_out = _paged_read(
        q.reshape(B * Hkv, G, dh), [a_head(k_pool), a_head(v_pool)],
        (pages * Hkv + head).reshape(B * Hkv, width),
        held.reshape(B * Hkv),
        (k.reshape(B * Hkv, 1, dh), v.reshape(B * Hkv, 1, dh),
         a_head_tail(k_tail), a_head_tail(v_tail),
         jnp.repeat(tail_lens, Hkv)),
        layer, None, interpret, scope="sparse_read",
        sub=(bits.reshape(B * Hkv, width), block_size))
    return (out.reshape(B, H, dh), k_out.reshape(k_tail.shape),
            v_out.reshape(v_tail.shape))


def sparse_read_reference(q, k_pool, v_pool, k_tail, v_tail, table, chosen,
                          lengths, tail_lens, *, layer: int,
                          block_size: int):
    """The read's numerics oracle, gather-based: q [B, H, dh]; the tails
    already hold the step's token (`tail_put`); chosen [B, Hkv, n_blocks]
    bool; the row attends the tokens under lengths[b] of its chosen blocks
    in pages and the first tail_lens[b] of its tail. Returns [B, H, dh]."""
    B, H, dh = q.shape
    Hkv, ps = k_pool.shape[2], k_pool.shape[-1]
    G = H // Hkv
    NP = table.shape[1]

    def gathered(pool):                       # [B, Hkv, NP * ps, dh]
        pages = pool[layer][table]            # [B, NP, Hkv, dh, ps]
        return pages.transpose(0, 2, 1, 4, 3).reshape(B, Hkv, NP * ps, dh)

    T = k_tail.shape[3]
    keys = jnp.concatenate(
        [gathered(k_pool), k_tail[layer][..., :dh]], axis=2)
    values = jnp.concatenate(
        [gathered(v_pool), v_tail[layer][..., :dh]], axis=2)
    at = jnp.arange(NP * ps)
    blocks = jnp.minimum(at // block_size, chosen.shape[-1] - 1)
    in_pages = jnp.logical_and(jnp.take(chosen, blocks, axis=-1),
                               (at[None, :] < lengths[:, None])[:, None, :])
    in_tail = jnp.broadcast_to(
        (jnp.arange(T)[None, :] < tail_lens[:, None])[:, None, :],
        (B, Hkv, T))
    seen = jnp.concatenate([in_pages, in_tail], axis=-1)   # [B, Hkv, S]
    qg = q.reshape(B, Hkv, G, dh).astype(jnp.float32)
    s = jnp.einsum("bghd,bgsd->bghs", qg, keys.astype(jnp.float32))
    s = jnp.where(seen[:, :, None, :], s / math.sqrt(dh), DEFAULT_MASK_VALUE)
    p = jnp.where(seen[:, :, None, :], jax.nn.softmax(s, axis=-1), 0.0)
    out = jnp.einsum("bghs,bgsd->bghd", p, values.astype(jnp.float32))
    return out.reshape(B, H, dh).astype(q.dtype)


# -- the prefill --------------------------------------------------------------
def prefill_choice(q, ck, start: int, *, stride: int, kernel: int,
                   tile: int = 512, **rule):
    """The blocks each query of a fresh window chooses. q [K, Tq, H, dh],
    the queries at positions start .. start + Tq - 1; ck [K, N, Hkv, dh]
    the window's compressed keys (`half_sums_prefill`); `rule` what
    `choose` takes. Returns chosen [K, Hkv, Tq, N / per] bool, a tile of
    queries at a time (the scores of 512 queries against 768 compressed
    keys are 50 MB in float32 at 32 heads)."""
    K, Tq, H, dh = q.shape
    N, Hkv = ck.shape[1:3]
    G = H // Hkv
    tile = min(tile, Tq)
    keys = ck.transpose(0, 2, 1, 3)                        # [K, Hkv, N, dh]

    def one(inputs):
        q_t, t = inputs                       # [K, tile, H, dh], [tile]
        s = jnp.einsum("ktghd,kgnd->kgthn",
                       q_t.reshape(K, tile, Hkv, G, dh), keys,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        visible = (jnp.arange(N)[None, :]
                   < columns(t + 1, stride, kernel)[:, None])  # [tile, N]
        s = jnp.where(visible[None, None, :, None, :], s, DEFAULT_MASK_VALUE)
        r = jnp.sum(jax.nn.softmax(s, axis=-1), axis=3)    # [K, Hkv, tile, N]
        r = jnp.where(visible[None, None], r, -jnp.inf)
        return choose(r, jnp.broadcast_to(t, r.shape[:-1]), **rule)

    n = -(-Tq // tile)
    pad = n * tile - Tq
    qs = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qs = qs.reshape(K, n, tile, H, dh).transpose(1, 0, 2, 3, 4)
    ts = start + jnp.arange(n * tile, dtype=jnp.int32).reshape(n, tile)
    chosen = jax.lax.map(one, (qs, ts))            # [n, K, Hkv, tile, NB]
    chosen = chosen.transpose(1, 2, 0, 3, 4).reshape(K, Hkv, n * tile, -1)
    return chosen[:, :, :Tq]


def sparse_prefill_reference(q, k, v, chosen, start: int, block_size: int):
    """q [K, Tq, H, dh] the queries at positions start ..; k, v [K, S, Hkv,
    dh]; chosen [K, Hkv, Tq, n_blocks]. Unblocked, float32: the oracle."""
    K, Tq, H, dh = q.shape
    S, Hkv = k.shape[1:3]
    G = H // Hkv
    at = jnp.arange(S)
    seen = jnp.take(chosen, jnp.minimum(at // block_size,
                                        chosen.shape[-1] - 1), axis=-1)
    seen = jnp.logical_and(
        seen, at[None, :] <= (start + jnp.arange(Tq))[:, None])
    s = jnp.einsum("ktghd,ksgd->kgths",
                   q.reshape(K, Tq, Hkv, G, dh).astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(dh)
    s = jnp.where(seen[:, :, :, None, :], s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("kgths,ksgd->ktghd", p, v.astype(jnp.float32))
    return out.reshape(K, Tq, H, dh).astype(q.dtype)


def _prefill_kernel(need_ref, fetch_ref, q_ref, k_ref, v_ref, bits_ref,
                    o_ref, m_scr, l_scr, acc_scr, *, start: int, kv_len: int,
                    block_size: int, scale: float, groups: int):
    from jax.experimental import pallas as pl

    b, h = pl.program_id(0), pl.program_id(1)
    i, j = pl.program_id(2), pl.program_id(3)
    n_i, n_j = pl.num_programs(2), pl.num_programs(3)
    bq, bkv = q_ref.shape[2], k_ref.shape[2]
    per = bkv // block_size

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, DEFAULT_MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    g = h // groups
    flat = ((b * (pl.num_programs(1) // groups) + g) * n_i + i) * n_j + j

    @pl.when(need_ref[flat] > 0)
    def _compute():
        # scores transposed: keys down the sublanes, queries along the lanes
        s = scale * jax.lax.dot_general(
            k_ref[0, 0], q_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [bkv, bq]
        key = j * bkv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        query = start + i * bq + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        inside = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // block_size
        bits = bits_ref[0, 0, 0]                           # [1, bq]
        chosen = jnp.zeros(s.shape, bool)
        for c in range(per):
            chosen = jnp.logical_or(chosen, jnp.logical_and(
                inside == c, (bits >> c) & 1 == 1))
        seen = jnp.logical_and(jnp.logical_and(key <= query, key < kv_len),
                               chosen)
        s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)                    # [1, bq]
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=0, keepdims=True)
        v = v_ref[0, 0]                                    # [bkv, dv]
        pv = jax.lax.dot_general(v, p.astype(v.dtype),
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv               # [dv, bq]

    @pl.when(j == n_j - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
                       ).astype(o_ref.dtype)


def sparse_prefill(q, k, v, chosen, start: int, block_size: int, *,
                   block_q: int = 512, block_kv: int = 512, interpret=None):
    """`sparse_prefill_reference` as a streaming flash kernel
    (`sparse_prefill`). Tq and S multiples of the tiles (the caller's
    window is a bucket); block_kv a multiple of block_size."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, Tq, H, dh = q.shape
    S, Hkv = k.shape[1:3]
    G = H // Hkv
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # tiles of 512 x 512 where the window has them: a grid step costs its
    # fixed ~0.35 us whatever it computes, and at 128 x 128 a 12,288-token
    # window of 32 heads is 98k of them
    bq, bkv = math.gcd(block_q, Tq), math.gcd(block_kv, S)
    if Tq % bq or S % bkv or bkv % block_size:
        raise ValueError(f"window {Tq} x {S} is not whole tiles of "
                         f"{bq} x {bkv}, or a tile not whole blocks")
    per = bkv // block_size
    n_i, n_j = Tq // bq, S // bkv
    n_blocks = n_j * per
    chosen = chosen[..., :n_blocks]
    chosen = jnp.pad(chosen, ((0, 0),) * 3
                     + ((0, n_blocks - chosen.shape[-1]),))
    # bit c of bits[k, g, j, 0, t]: query t attends block j per + c
    bits = jnp.sum(chosen.reshape(K, Hkv, Tq, n_j, per).astype(jnp.int32)
                   << jnp.arange(per, dtype=jnp.int32), axis=-1)
    bits = bits.transpose(0, 1, 3, 2)[:, :, :, None, :]   # [K,Hkv,n_j,1,Tq]
    # a key tile some query of the q tile chose, at or under its diagonal
    need = jnp.any(bits.reshape(K, Hkv, n_j, n_i, bq) > 0, axis=-1)
    need = need.transpose(0, 1, 3, 2)                      # [K,Hkv,n_i,n_j]
    under = (jnp.arange(n_j)[None, :] * bkv
             <= start + jnp.arange(n_i)[:, None] * bq + bq - 1)
    need = jnp.logical_and(need, under[None, None])
    # a tile that is not needed fetches what the last needed one did
    at = jnp.where(need, jnp.arange(n_j, dtype=jnp.int32), -1)
    fetch = jnp.maximum(jax.lax.cummax(at, axis=3), 0)
    need = need.astype(jnp.int32).reshape(-1)
    fetch = fetch.astype(jnp.int32).reshape(-1)

    def flat(b, h, i, j):
        return ((b * Hkv + h // G) * n_i + i) * n_j + j

    def kv_tile(b, h, i, j, need, fetch):
        return (b, h // G, fetch[flat(b, h, i, j)], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K, H, n_i, n_j),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b, h, i, j, *_: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, dh), kv_tile),
            pl.BlockSpec((1, 1, bkv, dh), kv_tile),
            pl.BlockSpec((1, 1, 1, 1, bq),
                         lambda b, h, i, j, *_: (b, h // G, j, 0, i))],
        out_specs=pl.BlockSpec((1, 1, dh, bq),
                               lambda b, h, i, j, *_: (b, h, 0, i)),
        scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32),
                        pltpu.VMEM((1, bq), jnp.float32),
                        pltpu.VMEM((dh, bq), jnp.float32)])
    kernel = functools.partial(
        _prefill_kernel, start=start, kv_len=S, block_size=block_size,
        scale=1.0 / math.sqrt(dh), groups=G)
    with kernel_scope("sparse_prefill"):
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((K, H, dh, Tq), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 4),
            interpret=interpret,
        )(need, fetch, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
          v.transpose(0, 2, 1, 3), bits)
    return out.transpose(0, 3, 1, 2)
