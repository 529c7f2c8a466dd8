"""Paged attention for TPU decode: block-table indirection via scalar prefetch.

Dense serving caches allocate [B, S_max] for every slot, so one long context
inflates every slot's footprint and per-step read cost (VERDICT r2 missing
#4; SURVEY.md §5 long-context row: "paged or ring-buffer KV cache in HBM").
Paging fixes both: K/V live in a fixed pool of fixed-size pages
[P, Hkv, dh, page_size] (S-minor tile-aligned layout, see
models/llama.init_kv_cache) and each slot owns just the pages its context
needs, mapped by a block table [B, NP] of page indices.

The TPU-native read is a Pallas kernel with SCALAR PREFETCH: the layer,
the block table and the per-slot lengths ride in SMEM ahead of the grid,
the pools stay in HBM un-blocked, and the kernel's own async copies fetch
page [layer, table[b, i]] of each pool into VMEM — a gather with no
materialized gathered cache (an XLA gather would copy the whole live cache
every step). Online softmax (m, l, acc) carries in f32 across a row's
pages, exactly like ops/flash_attention's streaming kernel.

Grid: (B,), ONE STEP A ROW, sequential. Inside it a loop over the row's
ceil(length / page_size) live pages and no others, each page ALL Hkv heads
at once (the dots batch over the KV heads), a FOLD of C consecutive pages
a turn (`pages_per_fold`: C from the page's bytes and the table's
width), double-buffered: fold i + 1 is in flight while fold i
goes into the softmax. The walk is by row because a grid
over (row, table column) pays for every column of the table, live or not
(on the v5e about half a microsecond each, which at a table a quarter
full was two thirds of the kernel's time); by row, time follows the live
tokens. A row is only a few folds, so a DMA queue that drained at every
row boundary would idle for a large part of each row: before a row folds
its last fold it starts the first fold of the next row that has one, and
which buffer that is carries over in SMEM scratch. Table entries past a
row's live pages are never read. A fold's WIDTH is what it copied: every
fold of a row but its last holds C pages and is computed over C; the last
holds 1 to C and is computed over the least power of two of pages that
covers them, but no less than C / 4 (`fold_branch`: at most C / 2 pages,
it runs after the loop in a copy of the turn's body that wide, chosen by
a scalar the kernel already holds), so a row of one page under a fold of
8 pays for two pages' products, not for eight pages' of masked lanes.

The pool is STACKED over layers ([L, P, Hkv, dh, ps]) and carried whole
through the step programs' layer loops, so everything here takes the
stacked pool plus a `layer` index and never a per-layer slice: the kernels
receive the layer as one more prefetched scalar and address (layer, page)
themselves, the read in its copies and the write in its index maps. A
dynamic slice of one layer would be a copy of that layer's whole slab per
layer per step.

Writes keep the storage layout. The TPU compiler lays a scatter's operand
out with the scattered window's dims minor; a per-token window is
[Hkv, dh], so a per-token XLA scatter into the pool makes the compiler
carry the WHOLE pool dh-minor (64 lanes padded to 128: twice the bytes,
plus a copy in and out of every program — llama1b widths with a 4 GiB
pool did not compile for a 16 GiB chip). And the pool is token-minor: one
token's column touches every tile of its page, so placing it is a Pallas
read-modify-write of the WHOLE page, 2 x 256 KiB moved to place 2 x 2 KiB
at 8 heads of 128. What can be chosen is how often. The decode write of
the floating-point pools is a FLUSH A BLOCK: a decode program's new K and
V wait in a small tail beside the pool (`block_tail`, token-major a head:
the step's read puts each row's new token there itself), the read attends
each row's pages as the block found them plus the tail's tokens so far in
one online softmax (`paged_attention_in_block`), and when the block's
steps are over `paged_flush_block` reads each live row's page, places the
block's columns and writes it back: once a block, not once a token; a row
that holds no request moves nothing. The int8 pools and the speculative
verify window still place one token a call (`paged_write_decode`, the
same read-modify-write, every row). Prefill windows are written as WHOLE
pages (`paged_write_window`), whose [Hkv, dh, ps] window is the storage
layout's own minor dims.

A WINDOW block (models/afmoe.py: sliding attention) attends the last W
tokens only, and its group's table is a RING (tpu/paging.py: logical page j
in column j % ring, W / page_size + 2 columns). The same kernel, told one
more scalar a row: the LOWER BOUND, the first position the row still sees.
The walk starts at the page that holds it (pages wholly before it are never
copied), tokens before it are masked, in the pages and in the block's tail
alike, and the kernel is named `window_read`; the flush finds a token's
page through the ring too. A read without a bound is the kernel it was, to
the instruction.

A BLOCK-SPARSE read (models/sparse_linear.py through
ops/sparse_attention.py `sparse_read`) is the same kernel too, told one more
scalar a (row, listed page): the row's table is the LIST of the pages that
hold a block it chose, and a bit a block of `sub_block` tokens says which of
a listed page's blocks it attends; the rest of the page is masked as tokens
past a row's length are, and the kernel is named `sparse_read`. Each (row,
KV head) is a row of the kernel there (the pools seen a head a page), since
the KV heads choose apart.

A plane with a STRIDE (models/protocol.py `Plane.stride`: a column of
heads x width values every `stride` tokens, the compressed keys of that
family) lies width-minor, [L, P, heads, page_size / stride, width], and is
read and written a whole column at a time by plain gathers and scatters
(`column_tail`, `flush_columns`, `paged_write_columns`): a column's window
is the storage layout's own minor dim, so none of what the paragraphs above
say of token-minor pages applies to it.

The XLA `paged_attention_reference` (gather-based) is the numerics oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .scopes import kernel_scope

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


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


def paged_attention_reference(q, k_pool, v_pool, table, lengths,
                              k_scale=None, v_scale=None):
    """Gather-based oracle. q: [B, H, dh]; pools: [P, Hkv, dh, ps];
    table: [B, NP] int32 page ids; lengths: [B] live tokens per slot
    (including the current token). k/v_scale: optional [P, Hkv, ps]
    per-token dequant scales for int8 pools. Returns [B, H, dh] in
    q.dtype; a row with lengths[b] == 0 returns zeros."""
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
    # nothing to attend is zeros, not the uniform mean of junk v: the
    # kernel's convention too
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0.0)
    return out.reshape(B, H, dh).astype(q.dtype)


# What one fold's pages should weigh at least. A turn of the read's loop
# costs about 0.3 us whatever it folds (the copies' wait, the MXU's fill and
# drain, the cross-lane max and the carry that chains the turns, none of
# which a second page in the same turn pays again), so a fold's copy should
# last a few times that: 1 MiB is 1.28 us at a v5e's 819 GB/s. Measured
# there (tools/bench_paged_read.py, PERF.md section 5): a latent page of
# 144 KiB reads 1,815 us a call at folds of one page, 916 at 4, 800 at 8
# and 821 at 16; K and V of 2 heads (128 KiB) 231, 150 at 4, 143 at 8; of
# 8 heads (512 KiB) 330, 317 at 2.
_FOLD_BYTES = 1 << 20


def pages_per_fold(page_bytes: int, table_width: int) -> int:
    """C, the pages one turn of the read's loop folds (see `_paged_kernel`),
    from what a call can see and nothing else: the bytes of one page over
    all the call's pools, scale planes included, which is what the page's
    heads and widths come to (pages are folded until a fold weighs
    `_FOLD_BYTES`), and the table's width (a fold is never wider than a row
    can be). A power of two, so a fold's lanes stay whole tiles at any page
    size; a fold is under 2 x `_FOLD_BYTES`, so the two buffers a pool that
    hold it take under 4 MiB of the 16 MiB of VMEM the compiler gives a
    kernel unasked. The benchmark's cells: a latent page (144 KiB) -> 8;
    K and V of 2 heads (nemotron, 128 KiB) -> 8; of 8 heads (internlm2,
    512 KiB) -> 2. The int8 pools follow the same rule, their scale planes
    riding the fold as [heads, C x ps]."""
    c = 1
    while c * page_bytes < _FOLD_BYTES and 2 * c <= table_width:
        c *= 2
    return c


def fold_of(pools, table_width: int, mesh=None) -> int:
    """`pages_per_fold` of stacked pools [L, P, heads, ...]: what the read
    of these pools under a table this wide folds a turn. Under a tp `mesh`
    each shard runs the kernel on its own share of the heads."""
    shards = mesh.shape.get("tp", 1) if mesh is not None else 1
    page_bytes = sum(math.prod(pool.shape[2:]) * pool.dtype.itemsize
                     for pool in pools)
    return pages_per_fold(page_bytes // shards, table_width)


# The widths a fold is computed at: C, C / 2 and C / 4, no narrower. Each
# width is one more copy of a turn's body to trace and lower in every read of
# every unrolled block, and each halving wins half of what the one before
# did: at xing's rows a latent read of one page a row takes 153 us a call
# computed at 8 pages, 125 at 4, 112 at 2 and 105 at 1 (v5e,
# tools/bench_paged_read.py), while a fourth width took the cold lowering of
# that cell's decode program from 10 % over the parent's to 16 % (PERF.md
# section 6, PR 40).
_FOLD_WIDTHS = 3


def fold_widths(fold: int) -> tuple:
    """The widths, in pages, a fold of C = `fold` pages can be computed
    at: C first, then its halves down to a quarter of it (8, 4, 2; 2, 1 of
    a fold of 2). The read's kernel holds one copy of a turn's body for
    each: the loop's for C, one after the loop for each narrower width."""
    return tuple(fold >> i for i in range(min(_FOLD_WIDTHS,
                                              fold.bit_length())))


def fold_branch(live, fold: int):
    """Which of `fold_widths(fold)` a fold that holds `live` of its C pages
    is computed at: the narrowest of them that covers what was copied (the
    least power of two of pages that does, but no less than C / 4), so a
    full fold (and any of more than C / 2 pages) takes the first. Plain
    comparisons and sums, so the kernel calls it on a scalar it holds in
    SMEM and the host's counter (tpu/paging.py `_note_page_reads`) on
    arrays of page counts."""
    return sum((live <= width) * 1 for width in fold_widths(fold)[1:])


def _paged_kernel(layer_ref, table_ref, len_ref, *refs, scale: float,
                  quantized: bool, tailed: bool, fold: int,
                  value_width=None, ring=None, sub_block=None):
    """One grid step = one row b: stream the row's live pages (ALL heads of
    a page at a time) through two VMEM buffers a pool and fold them into
    the online softmax, the dots batched over the KV heads.

    A FOLD is `fold` = C consecutive pages of the row (`pages_per_fold`),
    copied side by side into one buffer [Hkv, dh, C x ps] (page c of the
    fold at lanes [c x ps, (c + 1) x ps)), and one turn of the loop folds
    one: ONE score product [G, dh] x [dh, C x ps] a head, ONE max / exp /
    sum / rescale over its C x ps tokens, ONE value product, while the
    next fold's C copies are in flight. A turn's steps depend on each other
    (the copy's wait, the MXU's fill and drain, the cross-lane max, the
    carry (m, l, acc) that chains the turns), so their latencies are paid
    once a fold, not once a page: they come to about 0.3 us a turn on a v5e,
    beside which a latent page of 144 KiB is 0.18 us of DMA and a K and V
    page of 8 heads 0.64 (`_FOLD_BYTES` has the measurements). Only live
    pages are copied, and a fold is computed as wide as what it copied:
    w pages, the least of `fold_widths` that covers them (`fold_branch` on
    `pages_of(b)`, a scalar in SMEM). The folds before a row's last hold
    C pages each, and so does w for a last fold more than half live: all
    of those run in the loop, whose turn is computed at C and chooses
    nothing. A last fold of at most C / 2 pages runs after the loop over
    the first w x ps lanes of its buffer, in one of the copies of a turn's
    body kept for C / 2 and C / 4 pages, and ends the row itself (writes the
    output), so no branch hands the softmax's carry on; at `fold` 1 there
    is one width and no choice. With 32 queries of a latent page the
    two products of a full fold are 0.57 us of a one-fold row's 1.65, and a
    row of two pages is 1.24 (tools/bench_paged_read.py `only=short`). The
    lanes of a narrowed fold that hold no live page (3 pages are computed
    at 4, one at 2 under a fold of 8) keep what an earlier fold left
    there. Their scores are masked
    (they lie past the row's length) and their probabilities are 0.0, but
    0.0 x NaN is NaN in the value product: the buffers are zeroed before
    the first copy of a call, and what a live page holds is finite. A lane
    that is not computed adds nothing either: the sums lose only zeros.

    With a `ring` (a window group's, tpu/paging.py) the row attends from a
    LOWER BOUND on (one more scalar a row, `lower`: the first position it
    still sees): the walk starts at the page that holds it (pages wholly
    before it are never copied), tokens before it are masked, in the pages
    and in the tail alike, and logical page j of the row lies in table
    column j % `ring`.

    `tailed`, the row is in a decode block: its new k and v are put into
    its tail as token tail_len[b] - 1, the tail goes back where it came
    from, and its first tail_len[b] tokens are one more segment of the same
    softmax. `value_width` (ops/mla_read.py), the page holds ONE plane and
    a token's value is the first `value_width` of its key's dh values: one
    pool, one tail, the output [Hkv, G, value_width].

    refs: [tail_len,] [lower (SMEM, with the other scalars),] q, [the row's new k,
    v [Hkv, 1, dh'],] the n stacked pools left in HBM (k, v[, k_scale,
    v_scale]), [the two stacked tails left where they are,] o, [the tails
    again: the outputs alias them,] the pools' n VMEM buffers
    [2, *page[:-1], C x ps], [the tails' two [2, Hkv, T, dh'],] DMA
    semaphores [n, 2, C], [the tails' [2, 2] in and [2] out,] and
    `first_slot` (SMEM: the buffer this row's first fold was started in).
    int8 pages carry per-token scales; dequant FOLDS into the dots (k's
    scale multiplies score rows, v's folds into the probabilities)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    tail_len_ref = refs.pop(0) if tailed else None
    windowed = ring is not None
    lower_ref = refs.pop(0) if windowed else None
    bits_ref = refs.pop(0) if sub_block else None
    q_ref = refs.pop(0)
    latent = value_width is not None
    news = [refs.pop(0) for _ in range((1 if latent else 2) * tailed)]
    n = 1 if latent else 4 if quantized else 2
    pools = [refs.pop(0) for _ in range(n)]
    tails = [refs.pop(0) for _ in news]
    o_ref = refs.pop(0)
    tails_out = [refs.pop(0) for _ in news]
    bufs = [refs.pop(0) for _ in range(n)]
    tail_bufs = [refs.pop(0) for _ in news]
    sems = refs.pop(0)
    tail_sems, put_sems = (refs.pop(0), refs.pop(0)) if tailed else (None,
                                                                    None)
    first_slot, = refs
    k_buf, v_buf = bufs[0], None if latent else bufs[1]
    ks_buf, vs_buf = bufs[2:] if quantized else (None, None)

    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    layer = layer_ref[0]
    length = len_ref[b]
    n_kv, G, dh = q_ref.shape[1:]
    dv = value_width or dh
    page_size = k_buf.shape[-1] // fold

    def first_page(row):
        """The page a row's walk starts at: the one its lower bound is
        in."""
        return lower_ref[row] // page_size if windowed else 0

    def pages_of(row):
        """Pages a row's walk takes, from `first_page` on."""
        pages = (len_ref[row] + page_size - 1) // page_size
        if not windowed:
            return jnp.minimum(pages, table_ref.shape[1])
        return jnp.clip(pages - first_page(row), 0, ring)

    def column(row, walked):
        """The table column of the `walked`-th page of a row's walk."""
        if not windowed:
            return walked
        return (first_page(row) + walked) % ring

    n_pages = pages_of(b)
    n_folds = (n_pages + fold - 1) // fold

    def each_copy(row, f, slot, do):
        """Start or wait for the copies of fold f of `row` into buffer
        `slot`: its first page, which a fold always has, and those of the
        C - 1 after it that are live."""
        live = pages_of(row)
        for c in range(fold):
            def _page(c=c):
                page = table_ref[row, column(row, f * fold + c)]
                for j, (pool, buf) in enumerate(zip(pools, bufs)):
                    window = (slot,) + (slice(None),) * (buf.ndim - 2) + (
                        pl.ds(c * page_size, page_size),)
                    do(pltpu.make_async_copy(
                        pool.at[layer, page], buf.at[window],
                        sems.at[j, slot, c]))

            if c:
                pl.when(f * fold + c < live)(_page)
            else:
                _page()

    def start_fold(row, f, slot):
        each_copy(row, f, slot, lambda copy: copy.start())

    def start_first_fold_after(row, slot):
        # the next row that HAS a page: a row of length 0 owns none
        def live_or_end(r):
            if windowed:    # a bound past a row's pages leaves it none
                return jnp.logical_or(
                    r > last_row, pages_of(jnp.minimum(r, last_row)) > 0)
            return jnp.logical_or(r > last_row,
                                  len_ref[jnp.minimum(r, last_row)] > 0)

        nxt, _ = jax.lax.while_loop(
            lambda c: jnp.logical_not(c[1]),
            lambda c: (c[0] + 1, live_or_end(c[0] + 1)),
            (row + 1, live_or_end(row + 1)))

        @pl.when(nxt <= last_row)
        def _start():
            start_fold(nxt, 0, slot)

    @pl.when(b == 0)
    def _first_row():
        if fold > 1:
            # lanes a short fold leaves uncopied are read (masked): never
            # whatever VMEM held
            for buf in bufs:
                buf[...] = jnp.zeros(buf.shape, buf.dtype)
        first_slot[0] = 0
        start_first_fold_after(-1, 0)

    q = q_ref[0]                                          # [Hkv, G, dh]
    slot0 = first_slot[0]
    folded = (jnp.full((n_kv, G, 1), DEFAULT_MASK_VALUE, jnp.float32),
              jnp.zeros((n_kv, G, 1), jnp.float32),
              jnp.zeros((n_kv, G, dv), jnp.float32))

    if tailed:
        # The tail folds FIRST, while the row's first fold (started by the
        # row before) and its second (started here) stream in: folded last,
        # it would leave the copy queue one fold deep at every row's end.
        # Its own copy was started by the row before, into the buffer of
        # this row's parity, so nothing waits for it either.
        tail_len = tail_len_ref[b]

        def tail_copies(row):
            return [pltpu.make_async_copy(
                tail.at[layer, row], buf.at[row % 2], tail_sems.at[j, row % 2])
                for j, (tail, buf) in enumerate(zip(tails, tail_bufs))]

        put_copies = [
            pltpu.make_async_copy(buf.at[b % 2], tail.at[layer, b],
                                  put_sems.at[j])
            for j, (tail, buf) in enumerate(zip(tails_out, tail_bufs))]

        def start_tail(row):
            @pl.when(tail_len_ref[jnp.minimum(row, last_row)] > 0)
            def _start():
                for copy in tail_copies(row):
                    copy.start()

        @pl.when(b == 0)
        def _first_tail():
            start_tail(b)

        @pl.when(n_folds > 1)
        def _second_fold():
            start_fold(b, 1, 1 - slot0)

        @pl.when(b < last_row)
        def _next_tail():
            start_tail(b + 1)

        def fold_tail(carry):
            m_prev, l_prev, acc = carry
            for copy in tail_copies(b):
                copy.wait()
            # the step's token joins the tail: here, for this row's read,
            # and where the tail lives, for the steps to come (the copy
            # back runs under the page loop; a whole tail, since one
            # token's row of a packed tile cannot be copied alone)
            token = jax.lax.broadcasted_iota(
                jnp.int32, tail_bufs[0].shape[1:], 1)
            for buf, new in zip(tail_bufs, news):
                buf[b % 2] = jnp.where(token == tail_len - 1, new[0],
                                       buf[b % 2])
            for copy in put_copies:
                copy.start()
            # the tail is token-major a head ([Hkv, T, dh], dh on lanes,
            # less the lanes that pad a narrower head): its scores are the
            # plain q . k^T, batched over the KV heads
            k = tail_bufs[0][b % 2][:, :, :dh]
            v = k[:, :, :dv] if latent else tail_bufs[1][b % 2][:, :, :dh]
            s = scale * jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            held = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
                    < tail_len)
            if windowed:
                # tail token i is at position length + i
                held = jnp.logical_and(
                    held, jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
                    >= lower_ref[b] - length)
            m_new = jnp.maximum(m_prev, jnp.max(
                jnp.where(held, s, DEFAULT_MASK_VALUE), axis=-1,
                keepdims=True))
            pr = jnp.where(held, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                     (((2,), (1,)), ((0,), (0,))),
                                     preferred_element_type=jnp.float32)
            return (m_new,
                    l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True),
                    acc * alpha + pv)

        # a row that holds no request has no tail either: it reads nothing
        folded = jax.lax.cond(tail_len > 0, fold_tail, lambda c: c, folded)

    # tokens the walk reaches: it stops at the table's width whatever the
    # length says, and a fold's lanes past them were not copied this turn
    reached = jnp.minimum(length, n_pages * page_size)
    if windowed:
        # the walk starts at the page the row's lower bound is in
        walk_from = first_page(b) * page_size
        reached = jnp.minimum(length, walk_from + n_pages * page_size)

    def fold_lanes(f, slot, pages: int, carry):
        """Fold the first `pages` pages' lanes of buffer `slot`, which
        holds fold f of the row, into the softmax."""
        m_prev, l_prev, acc = carry
        lanes = pl.ds(0, pages * page_size)
        k = k_buf[slot, :, :, lanes]                      # [Hkv, dh, w ps]
        v = k[:, :dv] if latent else v_buf[slot, :, :, lanes]
        if quantized:
            k = k.astype(jnp.bfloat16)                    # in-VMEM upcast
        # every head's [G, dh] x [dh, w ps], batched over the KV heads
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * ks_buf[slot, :, lanes][:, None, :].astype(jnp.float32)
        kv_pos = f * (fold * page_size) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        if windowed:
            kv_pos = kv_pos + walk_from
        seen = kv_pos < reached
        if windowed:
            seen = jnp.logical_and(seen, kv_pos >= lower_ref[b])
        if sub_block:
            # the blocks of each listed page that the row chose
            # (a row of lanes, broadcast over the heads afterwards)
            row = (1, 1, s.shape[2])
            block = jax.lax.broadcasted_iota(jnp.int32, row, 2) // sub_block
            per = page_size // sub_block
            chosen = jnp.zeros(row, bool)
            for c in range(pages):
                bits = bits_ref[b, f * fold + c]
                for i in range(per):
                    chosen = jnp.logical_or(chosen, jnp.logical_and(
                        block == c * per + i, (bits >> i) & 1 == 1))
            seen = jnp.logical_and(seen, chosen)
        s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        if windowed or sub_block:
            # a fold may hold no token the row still sees (its bound lies
            # in the tail): exp(mask - mask) is 1.0, not 0.0
            pr = jnp.where(seen, pr, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        if quantized:
            pr = pr * vs_buf[slot, :, lanes][:, None, :].astype(jnp.float32)
            v = v.astype(jnp.bfloat16)
        pv = jax.lax.dot_general(pr.astype(v.dtype), v,
                                 (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    def fold_pages(f, carry):
        """One turn of the loop: a fold computed at all C pages."""
        slot = (slot0 + f) % 2

        # keep the DMA queue fed before waiting: this row's next fold (the
        # second is under way already where a tail folded first) or, from
        # its last fold, the first fold of the next row that has one
        @pl.when(jnp.logical_and(f + 1 < n_folds, f >= int(tailed)))
        def _next_fold():
            start_fold(b, f + 1, 1 - slot)

        @pl.when(f + 1 == n_folds)
        def _next_row():
            start_first_fold_after(b, 1 - slot)

        each_copy(b, f, slot, lambda copy: copy.wait())
        return fold_lanes(f, slot, fold, carry)

    def finish(carry):
        _, l, acc = carry
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    def narrow_last_fold(pages: int, carry):
        """The row's last fold where it holds at most C / 2 pages: its turn
        over the first `pages` pages' lanes alone, after the loop, ending
        the row itself (no branch hands the softmax's carry on)."""
        f = n_folds - 1
        slot = (slot0 + f) % 2
        start_first_fold_after(b, 1 - slot)
        each_copy(b, f, slot, lambda copy: copy.wait())
        finish(fold_lanes(f, slot, pages, carry))

    # a fold is computed as wide as what it copied: the loop takes every
    # fold computed at C (all but the last, and the last too where over
    # half of it is live), and a narrower last fold runs after it at its
    # own width; a row without a page holds C of a fold it never had,
    # and ends as its tail left it
    narrowed = fold_branch(n_pages - (n_folds - 1) * fold, fold)
    folded = jax.lax.fori_loop(0, n_folds - (narrowed > 0) * 1, fold_pages,
                               folded)
    jax.lax.switch(narrowed, [finish] + [
        functools.partial(narrow_last_fold, pages)
        for pages in fold_widths(fold)[1:]], folded)
    first_slot[0] = (slot0 + n_folds) % 2
    if tailed:
        @pl.when(tail_len > 0)
        def _tail_is_back():
            for copy in put_copies:
                copy.wait()


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

    Table entries past a row's ceil(lengths[b] / ps) live pages are not
    read, and a row of length 0 reads nothing and returns zeros. The walk
    stops at the table's width whatever the length says.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("pass both k_scale and v_scale or neither")
    scales = [] if k_scale is None else [k_scale, v_scale]
    return _paged_read(q, [k_pool, v_pool] + scales, table, lengths, None,
                       layer, mesh, interpret)


def paged_attention_in_block(q, k, v, k_pool, v_pool, k_tail, v_tail, table,
                             lengths, tail_lens, *, layer=None, mesh=None,
                             interpret=None, window=None, ring=None):
    """One step's attention inside a decode block (floating-point pools):
    row b's new k, v [B, Hkv, dh] become token tail_lens[b] - 1 of its tail
    (`block_tail`, stacked like the pools: [L, B, Hkv, T, dh'], or one
    layer's with layer=None), and the row attends its lengths[b] tokens in
    pages, then the first tail_lens[b] tokens of its tail, in one softmax.
    A row with tail_lens[b] == 0 (`holds_request`: lengths[b] is 0 too)
    puts nothing, reads nothing and returns zeros.
    Returns (attention [B, H, dh], k_tail, v_tail), the tails updated in
    place. Keys past tail_lens[b] may hold anything; values there must be
    finite (`block_tail` makes zeros): they meet a probability of 0.0.
    q, pools, table, layer, mesh: as `paged_attention` has them; the tails
    and k, v split on Hkv under a tp mesh.

    `window` W (a window block; `ring` the columns of its group's ring,
    tpu/paging.py): the row's token at position p = lengths[b] +
    tail_lens[b] - 1 attends (p - W, p], W keys with its own: the read is
    told the lower bound lengths + tail_lens - W, finds logical page j in
    table column j % ring, and runs under the scope `window_read`."""
    if window is None:
        return _paged_read(q, [k_pool, v_pool], table, lengths,
                           (k, v, k_tail, v_tail, tail_lens), layer, mesh,
                           interpret)
    lower = jnp.maximum(lengths + tail_lens - window, 0).astype(jnp.int32)
    return _paged_read(q, [k_pool, v_pool], table, lengths,
                       (k, v, k_tail, v_tail, tail_lens), layer, mesh,
                       interpret, lower=lower, ring=ring,
                       scope="window_read")


def _paged_read(q, pools, table, lengths, block, layer, mesh, interpret, *,
                value_width=None, scale=None, scope: str = "paged_read",
                lower=None, ring=None, sub=None):
    """The reads' one call. pools: (k, v[, k_scale, v_scale]); block: None
    or (k, v, k_tail, v_tail, tail_lens) of `paged_attention_in_block`.
    With `value_width` (ops/mla_read.py) the one pool of a one-plane page,
    block (new, tail, tail_lens), the scores scaled by `scale` and the
    kernel named `scope`. `lower` [B] int32: the first position each row
    still sees, and `ring` the columns of the ring its table holds: given
    together. `sub` (bits [B, NP] int32, the tokens of a block): the table
    lists the pages each row chose and bit i of bits[b, j] says whether it
    attends block i of its j-th listed page."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized, tailed = len(pools) == 4, block is not None
    stacked = layer is not None
    layer_arr = _layer_operand(layer)
    pools = [_stacked(pool, layer) for pool in pools]
    news, tails, tail_lens = [], [], []
    if tailed:
        m = len(pools)
        news, tail_lens = list(block[:m]), [block[2 * m]]
        tails = [_stacked(tail, layer) for tail in block[m:2 * m]]

    if _tp(mesh) and (lower is not None or sub is not None):
        raise NotImplementedError(
            "no windowed or block-sparse read under a tp mesh yet")
    if _tp(mesh):
        from jax.sharding import PartitionSpec

        rep = PartitionSpec()
        heads = _heads_spec(3, 1)
        tail_specs = [_heads_spec(5, 2)] * len(tails)
        specs = ([heads] + [_heads_spec(pool.ndim, 2) for pool in pools]
                 + [rep, rep, rep] + [heads] * len(news) + tail_specs
                 + [rep] * len(tail_lens))
        n = len(pools)

        def local(q, *rest):
            pools, (table, lengths, layer_arr) = rest[:n], rest[n:n + 3]
            block = rest[n + 3:] or None
            return _paged_read(q, list(pools), table, lengths, block,
                               layer_arr[0], None, interpret)

        out = jax.shard_map(
            local, mesh=mesh, in_specs=tuple(specs),
            out_specs=(heads, *tail_specs) if tailed else heads,
            check_vma=False)(q, *pools, table, lengths, layer_arr, *news,
                             *tails, *tail_lens)
        return out if not tailed else (out[0], *_unstack(out[1:], stacked))

    B, H, dh = q.shape
    Hkv = pools[0].shape[2]
    G, dv = H // Hkv, value_width or dh
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # the row's new k, v as the tail holds a token: [Hkv, 1, dh'] a row
    news = [jnp.pad(new.astype(tail.dtype),
                    ((0, 0), (0, 0), (0, tail.shape[-1] - dh)))[:, :, None]
            for new, tail in zip(news, tails)]

    scalars = [layer_arr, table, lengths] + tail_lens
    fold = fold_of(pools, table.shape[1])
    static = dict(scale=scale or 1.0 / math.sqrt(dh), quantized=quantized,
                  tailed=tailed, fold=fold, value_width=value_width)
    if lower is not None:
        scalars.append(lower)
        static.update(ring=int(ring))
    if sub is not None:
        scalars.append(sub[0])
        static.update(sub_block=int(sub[1]))
    kernel = functools.partial(_paged_kernel, **static)

    def row_index(b, *scalars):
        return (b, 0, 0, 0)

    # the pools and tails stay where they are, whole: the kernel copies
    # [layer, page] and [layer, row] (a tail is small enough that the
    # compiler may keep all of it in VMEM)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    q_row = pl.BlockSpec((1, Hkv, G, dh), row_index)
    out_row = pl.BlockSpec((1, Hkv, G, dv), row_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),  # layer, table, lengths[, tail][, lower][, bits]
        grid=(B,),
        in_specs=[q_row]
        + [pl.BlockSpec((1,) + new.shape[1:], row_index) for new in news]
        + [whole] * (len(pools) + len(tails)),
        out_specs=[out_row] + [whole] * len(tails),
        # two buffers a pool, each a fold wide: C pages side by side
        scratch_shapes=[pltpu.VMEM((2,) + pool.shape[2:-1]
                                   + (fold * pool.shape[-1],), pool.dtype)
                        for pool in pools]
        + [pltpu.VMEM((2,) + x.shape[2:], x.dtype) for x in tails]
        + [pltpu.SemaphoreType.DMA((len(pools), 2, fold))]
        + [pltpu.SemaphoreType.DMA((len(tails), 2)),
           pltpu.SemaphoreType.DMA((len(tails),))] * tailed
        + [pltpu.SMEM((1,), jnp.int32)],
    )
    first_tail = len(scalars) + 1 + len(news) + len(pools)
    with kernel_scope(scope):
        attended, *tails = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, Hkv, G, dv), q.dtype)]
            + [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in tails],
            # the tails are updated where they lie
            input_output_aliases={first_tail + i: 1 + i
                                  for i in range(len(tails))},
            # sequential rows: a row starts its successor's first fold
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(*scalars, q.reshape(B, Hkv, G, dh), *news, *pools, *tails)
    attended = attended.reshape(B, H, dv)
    return attended if not tailed else (attended,
                                        *_unstack(tails, stacked))


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
    with kernel_scope("paged_write"):
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


# -- a decode block's tail ----------------------------------------------------
# A decode program runs `block` steps between two looks at the pool. Its new
# K and V wait in a tail beside the pool, [L, B, Hkv, T, dh] each (token-major
# a head, dh on lanes: the read's dots take it as it lies), made inside the
# program and dead when it returns; `paged_attention_in_block` fills and
# attends it, a token a step, and `paged_flush_block` puts it into the pages
# once, when the block is over.
def holds_request(table):
    """[B] bool: which rows of a block table hold a request. A row that
    holds none starts at page 0, the PageAllocator's garbage page, which
    is never handed out; its position is whatever its last request left,
    advanced by every step since. The ONE place that reads this fact: the
    read's lengths and the flush's counts both come from it, so such a row
    attends nothing and flushes nothing."""
    return table[:, 0] > 0


def plane_tail(pool, rows: int, block: int, mesh=None):
    """One plane's tail: zeros [L, rows, heads, T, w'] in the pool's dtype
    for a stacked pool [L, P, heads, w, ps]; under a tp mesh sharded on
    the heads as the pools are. T >= block and w' >= w are whole tiles (16
    tokens, 128 lanes): the kernels copy a row's [heads, T, w'] out of the
    stack, and a copy's window has to be whole tiles. The padding is never
    attended and never placed."""
    L, _, heads, width, _ = pool.shape
    tail = jnp.zeros((L, rows, heads, -(-block // 16) * 16,
                      -(-width // 128) * 128), pool.dtype)
    if _tp(mesh):
        from jax.sharding import NamedSharding

        tail = jax.lax.with_sharding_constraint(
            tail, NamedSharding(mesh, _heads_spec(5, 2)))
    return tail


def block_tail(k_pool, rows: int, block: int, mesh=None):
    """(k_tail, v_tail) of pools whose planes are K and V: `plane_tail`,
    twice."""
    tail = plane_tail(k_pool, rows, block, mesh)
    return tail, tail


def tail_put(k_tail, v_tail, k, v, layer, step):
    """New k, v [B, Hkv, dh] as token `step` of every row of `layer`'s
    tail, as a plain window update: the reference of the put that
    `paged_attention_in_block` does in its kernel, and what tests and
    tools fill a tail with. (Not the serving path: on the chip the window
    is one sublane row in each of B x Hkv tiles, 11 us a pool a layer
    inside the decode program: PERF.md, PR 28.)"""
    def put(tail, new):
        zero = jnp.zeros((), jnp.int32)
        return jax.lax.dynamic_update_slice(
            tail, new.astype(tail.dtype)[None, :, :, None, :],
            (jnp.asarray(layer, jnp.int32), zero, zero,
             jnp.asarray(step, jnp.int32), zero))

    return put(k_tail, k), put(v_tail, v)


def _flush_kernel(page_ref, row_ref, lane_ref, count_ref, *refs):
    """One grid step = one (layer, item): put the tokens of one row's tail
    that land in one page at their lanes and write the page back, plane by
    plane (refs: the planes' tails, their pages, the pages out). An item
    (`_flush_items`) is a (row, page its block reaches) that has tokens to
    place; its scalars are the page id, the row, the lane of the tail's
    token 0 in this page's frame (below 0 in a page the block crossed
    into) and how many tokens the tail holds. The items come first; the
    steps left over (count 0) name the last item's page and row again, so
    nothing is copied for them, and leave the page alone.

    The tail's tokens lie [T, dh] a head and a page wants them down its dh
    sublanes at `count` lanes: the transposed-lhs dot
    tail[T, dh]^T x selection[T, ps] moves them there on the MXU, exactly
    (each product is a value times 1.0 or 0.0, accumulated in f32), as
    `_write_kernel` does for one column."""
    from jax.experimental import pallas as pl

    n = len(refs) // 3
    tails, pages, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    item = pl.program_id(1)
    count, lane0 = count_ref[item], lane_ref[item]
    T, ps = tails[0].shape[2], outs[0].shape[-1]

    @pl.when(count > 0)
    def _place():
        token = jax.lax.broadcasted_iota(jnp.int32, (T, ps), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (T, ps), 1)
        selection = jnp.logical_and(lane == lane0 + token, token < count)
        for tail, page, out in zip(tails, pages, outs):
            n_kv, dh = out.shape[1:3]
            lanes = jax.lax.broadcasted_iota(jnp.int32, (dh, ps), 1)
            placed = jnp.logical_and(lanes >= lane0, lanes < lane0 + count)
            # bf16 products with 1.0 are exact as they are; f32 values need
            # the full-precision passes or the MXU rounds them to bf16
            precision = (jax.lax.Precision.HIGHEST
                         if tail.dtype == jnp.float32 else None)
            for h in range(n_kv):                         # unrolled heads
                cols = jax.lax.dot_general(
                    tail[0, h, :, :dh], selection.astype(tail.dtype),
                    (((0,), (0,)), ((), ())), precision=precision,
                    preferred_element_type=jnp.float32)   # [dh, ps]
                out[0, h] = jnp.where(placed, cols.astype(out.dtype),
                                      page[0, h])

    # no item at all (no row holds a request): every step names the same
    # page, which goes back as it came (never unwritten VMEM)
    @pl.when(jnp.logical_and(count == 0, item == 0))
    def _keep():
        for page, out in zip(pages, outs):
            out[...] = page[...]


def _ring_column(slots, ring, width: int):
    """Table columns of logical pages `slots`: column j % ring of a window
    group's ring, column j (inside the table) otherwise."""
    if ring:
        return slots % ring
    return jnp.clip(slots, 0, width - 1)


def _flush_items(table, starts, counts, ps: int, spans: int, ring=None):
    """The flush's scalars (see `_flush_kernel`), [B * spans] each: one
    item a (row, page its block reaches), the items with tokens to place
    first, in row order, so that consecutive grid steps move consecutive
    pages and the steps left over move nothing."""
    B, NP = table.shape
    span = jnp.arange(spans, dtype=jnp.int32)[None, :]
    slots = _ring_column(starts[:, None] // ps + span, ring, NP)
    pages = jnp.take_along_axis(table, slots, axis=1)      # [B, spans]
    off = (starts % ps)[:, None]
    live = jnp.logical_and(counts[:, None] > 0,
                           off + counts[:, None] > span * ps)
    rows = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None],
                            (B, spans))
    first = jnp.argsort(jnp.logical_not(live).reshape(-1), stable=True)
    # a step past the last item repeats it, with nothing to place (with no
    # item at all every step names one page, which `_keep` puts back)
    n_items, step = jnp.sum(live), jnp.arange(B * spans)
    at = first[jnp.minimum(step, jnp.maximum(n_items - 1, 0))]

    def of(x):
        return x.reshape(-1)[at].astype(jnp.int32)

    counts = jnp.broadcast_to(counts[:, None], (B, spans))
    return (of(pages), of(rows), of(off - span * ps),
            jnp.where(step < n_items, of(counts), 0))


def flush_planes(pools, tails, table, starts, counts, *, mesh=None,
                 interpret=None, ring=None):
    """Put a decode block's tail into the pages, in place, every layer and
    every plane at once: token i < counts[b] of row b's tail goes to
    absolute position starts[b] + i, i.e. column (starts[b] + i) % ps of
    page table[b, (starts[b] + i) // ps]. A row's page is read and written
    once (once more for each page boundary its block crossed) whatever
    counts[b] is; a row with counts[b] == 0 moves nothing. `ring`: the
    table holds a window group's ring, logical page j in column j % ring.

    pools: a [L, P, heads, w, ps] a plane; tails: a [L, B, heads, T, w']
    a plane (`plane_tail`); table: [B, NP]; starts, counts: [B] int32,
    counts <= T. Returns the pools, a tuple. `interpret` as
    `paged_write_decode` has it: off the TPU, None takes the plain scatter
    (`_flush_columns`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pools, tails = tuple(pools), tuple(tails)
    n = len(pools)
    if interpret is None and jax.default_backend() != "tpu":
        return _flush_columns(pools, tails, table, starts, counts, ring)
    if _tp(mesh):
        from jax.sharding import PartitionSpec

        rep = PartitionSpec()
        heads = _heads_spec(5, 2)
        return jax.shard_map(
            lambda *a: flush_planes(a[:n], a[n:2 * n], *a[2 * n:],
                                    interpret=interpret, ring=ring),
            mesh=mesh, in_specs=(heads,) * (2 * n) + (rep,) * 3,
            out_specs=(heads,) * n, check_vma=False)(
                *pools, *tails, table, starts, counts)

    L, ps = pools[0].shape[0], pools[0].shape[-1]
    B, T = tails[0].shape[1], tails[0].shape[3]
    spans = (T + ps - 2) // ps + 1    # pages T tokens can reach: 2 at ps=128

    def page_block(pool):
        return pl.BlockSpec(
            (None, 1) + pool.shape[2:],
            lambda l, i, pages, rows, lanes, counts: (l, pages[i], 0, 0, 0))

    def tail_block(tail):
        return pl.BlockSpec(
            (None, 1) + tail.shape[2:],
            lambda l, i, pages, rows, lanes, counts: (l, rows[i], 0, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # page ids, rows, first lanes, counts
        grid=(L, B * spans),
        in_specs=[tail_block(tail) for tail in tails]
        + [page_block(pool) for pool in pools],
        out_specs=[page_block(pool) for pool in pools],
    )
    with kernel_scope("paged_write"):
        return tuple(pl.pallas_call(
            _flush_kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in pools],
            # operand order: 4 scalars, n tails, n pools -> pool i = out i
            input_output_aliases={4 + n + i: i for i in range(n)},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 2),
            interpret=bool(interpret),
        )(*_flush_items(table, starts, counts, ps, spans, ring), *tails,
          *pools))


def paged_flush_block(k_pool, v_pool, k_tail, v_tail, table, starts, counts,
                      *, mesh=None, interpret=None):
    """`flush_planes` of pools whose planes are K and V. Returns
    (k_pool, v_pool)."""
    return flush_planes((k_pool, v_pool), (k_tail, v_tail), table, starts,
                        counts, mesh=mesh, interpret=interpret)


def _flush_columns(pools, tails, table, starts, counts, ring=None):
    """The flush as one plain scatter a pool: the kernel's reference, and
    what runs off the TPU. A token past its row's count is dropped."""
    P, ps = pools[0].shape[1], pools[0].shape[-1]
    T = tails[0].shape[3]
    positions = starts[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    slots = _ring_column(positions // ps, ring, table.shape[1])
    pages = jnp.take_along_axis(table, slots, axis=1)         # [B, T]
    held = jnp.arange(T)[None, :] < counts[:, None]
    pages = jnp.where(held, pages, P)                         # P: dropped
    return tuple(
        pool.at[:, pages, :, :, positions % ps].set(
            jnp.transpose(tail[..., :pool.shape[3]], (1, 3, 0, 2, 4)),
            mode="drop")
        for pool, tail in zip(pools, tails))


# -- a plane with a stride ----------------------------------------------------
# models/protocol.py `Plane.stride`: a COLUMN of heads x width values for
# every `stride` tokens, the pool [L, P, heads, page_size / stride, width],
# width-minor: a column is read whole (a gather through the page table) and
# written whole, so its writes are plain scatters whose window is the
# storage layout's own minor dim, and nothing here needs a kernel.
def column_tail(pool, rows: int, columns: int):
    """A strided plane's tail: zeros [L, rows, heads, columns, width], the
    columns a decode block's steps complete, in order."""
    L, _, heads, _, width = pool.shape
    return jnp.zeros((L, rows, heads, columns, width), pool.dtype)


def flush_columns(pool, tail, table, starts, counts):
    """Put the columns a decode block completed into the pages, in place:
    column i < counts[b] of row b's tail is column starts[b] + i of the row,
    i.e. column (starts[b] + i) % cols of page table[b, (starts[b] + i) //
    cols]. pool [L, P, heads, cols, width]; tail [L, B, heads, n, width];
    table [B, NP]; starts, counts [B] int32. A scatter a (block, head): its
    window is one column's [width]."""
    L, P, heads, cols, _ = pool.shape
    n = tail.shape[3]
    column = starts[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
    pages = jnp.take_along_axis(
        table, jnp.clip(column // cols, 0, table.shape[1] - 1), axis=1)
    held = jnp.arange(n)[None, :] < counts[:, None]
    pages = jnp.where(held, pages, P)                         # P: dropped
    for layer in range(L):
        for head in range(heads):
            pool = pool.at[layer, pages, head, column % cols].set(
                tail[layer, :, head], mode="drop")
    return pool


def paged_write_columns(pool, window, table, counts):
    """Write a prefill window's columns into a strided plane's pool as
    WHOLE pages. pool [L, P, heads, cols, width]; window [L, K, heads, n,
    width], the columns 0 .. n - 1 of row k from the start of its prompt;
    table [K, NP]; counts [K] the columns that exist. A column past its
    row's count is written as zeros, and a page wholly past it diverts to
    the garbage page, as `paged_write_window` has it."""
    L, _, heads, cols, width = pool.shape
    K, n = window.shape[1], window.shape[3]
    n_src = -(-n // cols)
    if n_src * cols != n:
        window = jnp.pad(window, ((0, 0),) * 3 + ((0, n_src * cols - n),
                                                   (0, 0)))
    at = jnp.arange(n_src * cols, dtype=jnp.int32)
    live = (at[None, :] < counts[:, None])[None, :, None, :, None]
    window = jnp.where(live, window, jnp.zeros((), window.dtype))
    # [L, K, heads, n_src, cols, width] -> [L, K * n_src, heads, cols, width]
    pages = window.reshape(L, K, heads, n_src, cols, width)
    pages = jnp.moveaxis(pages, 3, 2).reshape(L, K * n_src, heads, cols,
                                              width)
    slot = jnp.arange(n_src, dtype=jnp.int32)[None, :]
    page_ids = jnp.take_along_axis(
        table, jnp.clip(jnp.broadcast_to(slot, (K, n_src)), 0,
                        table.shape[1] - 1), axis=1)
    page_ids = jnp.where(slot * cols < counts[:, None], page_ids,
                         jnp.int32(0)).reshape(K * n_src)
    return pool.at[:, page_ids].set(_row_major(pages.astype(pool.dtype)))


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
