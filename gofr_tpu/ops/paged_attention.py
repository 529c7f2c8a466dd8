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

Grid: (B / R,), ONE STEP A GROUP OF R CONSECUTIVE ROWS, sequential
(`rows_a_step`: R from the bytes of a fold, whose buffers are kept for two
groups, and from B, which it divides; 4 at the benchmark's shapes). A row
is walked over its ceil(length / page_size) live pages and no others, each
page ALL Hkv heads at once (the dots batch over the KV heads), a FOLD of C
consecutive pages a softmax step (`pages_per_fold`: C from the page's bytes
and the table's width). The walk is by row because a grid over (row, table
column) pays for every column of the table, live or not (on the v5e about
half a microsecond each, which at a table a quarter full was two thirds of
the kernel's time); by row, time follows the live tokens. It is by SEVERAL
rows a step because a grid step is paid whatever its row holds (its blocks
handed over, its bookkeeping: a third of a microsecond), and a row of one
fold is little else: at xing's rows (1-9 latent pages of 144 KiB) a row's
own chain was 1.08 us beside 0.071 us a page computed. A group's first
folds, and its rows' tails, are started by the group before, so the DMA
queue never drains between rows. Where every row of a group holds ONE fold
at most, the step takes them in turn with a row's tail and its fold in ONE
softmax step (`short_row`: no carry, no rescale, nothing handed from row to
row); a group that holds a longer row walks row by row (`walk_row`): the
tail first, then a loop over the row's folds, double-buffered (fold i + 1
is in flight while fold i goes into the softmax), and before the group's
last row folds its last fold it starts the next group's. Table entries past
a row's live pages are never read. A fold's WIDTH is what it copied: every
fold of a row but its last holds C pages and is computed over C; the last
holds 1 to C and is computed over the least power of two of pages that
covers them, but no less than C / 4 (`fold_branch`: at most C / 2 pages,
it runs in a copy of the step's body that wide, chosen by a scalar the
kernel already holds), so a row of one page under a fold of 8 pays for two
pages' products, not for eight pages' of masked lanes.

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
    return pages_per_fold(_page_bytes(pools, mesh), table_width)


# The widths a fold is computed at: C, C / 2 and C / 4, no narrower. Each
# width is one more copy of a turn's body to trace and lower in every read of
# every unrolled block, and each halving wins half of what the one before
# did: at xing's rows a latent read of one page a row takes 153 us a call
# computed at 8 pages, 125 at 4, 112 at 2 and 105 at 1 (v5e,
# tools/bench_paged_read.py), while a fourth width took the cold lowering of
# that cell's decode program from 10 % over the parent's to 16 % (PERF.md
# section 6, PR 40).
_FOLD_WIDTHS = 3


# What the buffers of the read's folds may take of the 16 MiB of VMEM the
# compiler gives a kernel unasked (the rest: the blocks of q and of the
# output, the tails' buffers, what the products keep between them), and the
# most rows a grid step walks whatever they weigh. At xing's rows (1-9
# latent pages, v5e, tools/bench_paged_read.py `only=short`) a call takes
# 122.0 us at one row a step, 118.2 at 2, 118.8 at 3 and 118.2 at 4: past
# two rows a group's size buys nothing, so the budget is what fits, not a
# knob (PERF.md section 5, PR 48).
_GROUP_BYTES = 11 << 20
_GROUP_ROWS = 8
# and of its DMA semaphores, one a copy (a page of a pool) a buffer: a core
# holds 512 for everything
_GROUP_COPIES = 384


def rows_a_step(fold_bytes: int, fold_copies: int, rows: int) -> int:
    """R, the consecutive rows one grid step of the read walks (see
    `_paged_kernel`), from what a call can see and nothing else: the
    bytes of one fold over all the call's pools and the copies that bring
    it (a page of a pool each; the kernel keeps 2 R + 1 buffers that wide,
    inside `_GROUP_BYTES`, and a semaphore a copy, inside `_GROUP_COPIES`),
    and the call's rows, which R divides, so that every group is whole and
    no block reaches past an array. 1 where nothing else divides them or
    fits."""
    return max(r for r in range(1, _GROUP_ROWS + 1)
               if rows % r == 0 and (r == 1 or (
                   (2 * r + 1) * fold_bytes <= _GROUP_BYTES
                   and (2 * r + 1) * fold_copies <= _GROUP_COPIES)))


def _page_bytes(pools, mesh=None) -> int:
    """The bytes of one page over stacked pools [L, P, heads, ...]; under
    a tp `mesh`, of a shard's share of the heads."""
    shards = mesh.shape.get("tp", 1) if mesh is not None else 1
    return sum(math.prod(pool.shape[2:]) * pool.dtype.itemsize
               for pool in pools) // shards


def group_of(pools, table_width: int, rows: int, mesh=None) -> int:
    """`rows_a_step` of stacked pools [L, P, heads, ...] read under a
    table this wide by a call of `rows` rows."""
    fold = fold_of(pools, table_width, mesh)
    return rows_a_step(fold * _page_bytes(pools, mesh), fold * len(pools),
                       rows)


# Whether a row of one fold takes its tail and its fold in ONE softmax step
# (`_paged_kernel` `short_row`). Not a setting: tools/bench_paged_read.py
# stands in for it to time the two steps apart.
_JOIN_ONE_FOLD = True


def _short_groups(lengths, tail_lens, lower, page_size: int, fold: int,
                  group: int, width: int):
    """[B / R] int32: which groups of R = `group` consecutive rows take the
    short rows' step of `_paged_kernel`: those whose rows each hold at
    most one fold and, if a page at all, a tail to join it (a row that
    holds no request has neither; one whose tokens are all in its tail
    still walks: it has no fold to join; a read without tails joins
    nothing and asks for the one fold alone). From the scalars the kernel
    is given, by the kernel's own count of a row's pages (`pages_of`:
    `width` the table's, or the ring's where a row has a `lower` bound),
    once a call instead of once a grid step and a hand-over."""
    pages = (lengths + page_size - 1) // page_size
    if lower is None:
        pages = jnp.minimum(pages, width)
    else:
        pages = jnp.clip(pages - lower // page_size, 0, width)
    short = pages <= fold
    if tail_lens is not None:
        short = jnp.logical_and(short, (pages > 0) == (tail_lens > 0))
    if not _JOIN_ONE_FOLD:
        short = jnp.zeros_like(short)
    return jnp.all(short.reshape(-1, group), axis=1).astype(jnp.int32)


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
                  quantized: bool, tailed: bool, fold: int, group: int,
                  value_width=None, ring=None, sub_block=None):
    """One grid step = one GROUP of R = `group` consecutive rows
    (`rows_a_step`): stream each row's live pages (ALL heads of a page at
    a time) through VMEM buffers and fold them into the online softmax,
    the dots batched over the KV heads.

    A FOLD is `fold` = C consecutive pages of the row (`pages_per_fold`),
    copied side by side into one buffer [Hkv, dh, C x ps] (page c of the
    fold at lanes [c x ps, (c + 1) x ps)), and one softmax step folds
    one: ONE score product [G, dh] x [dh, C x ps] a head, ONE max / exp /
    sum / rescale over its C x ps tokens, ONE value product. A step's parts
    depend on each other (the copy's wait, the MXU's fill and drain, the
    cross-lane max, the carry (m, l, acc) that chains the steps), so their
    latencies are paid once a fold, not once a page: they come to about
    0.3 us a step on a v5e, beside which a latent page of 144 KiB is 0.18 us
    of DMA and a K and V page of 8 heads 0.64 (`_FOLD_BYTES` has the
    measurements). Only live pages are copied, and a fold is computed as
    wide as what it copied: w pages, the least of `fold_widths` that covers
    them (`fold_branch` on `pages_of(row)`, a scalar in SMEM).

    THE BUFFERS are 2 R + 1 a pool, a fold wide each: row r of a group of
    parity p (the grid step's, 0 or 1) OWNS buffer p R + r, where its
    first fold lands, and one SPARE is every row's in turn. What a step
    does depends on what its rows hold, which the call works out once
    from the scalars (`_short_groups`: a flag a group, in SMEM):

    SHORT ROWS. Where every row of the group holds at most ONE fold
    (`pages_of(row) <= C`; a row without a request holds none), all its
    rows' first folds and tails are in flight when its step begins (what
    came before started them: `hand_on`). The step starts what the read
    needs after it at once, then takes its rows in turn (`short_row`): the
    tail's scores and the fold's lanes go into ONE softmax step (one max,
    one exp, one sum over both, two value products, no carry and no
    rescale), computed at the fold's width w, and the output is written;
    the tails go back together when the last row is done. A row pays no
    grid step of its own, no hand-over from the row before, and one
    softmax step instead of two (`tools/bench_paged_read.py only=short`
    has what each part gave).

    A GROUP THAT HOLDS A LONGER ROW walks row by row as the kernel always
    did (`walk_row`), each row handing on to the next: the tail folds
    FIRST (while the row's first fold, started by the row before, and its
    second, started at once into the spare, stream in: folded last, it
    would leave the copy queue one fold deep at every row's end), then a
    loop over the folds, fold f + 1 in flight (in the buffer fold f - 1
    left) while fold f goes into the softmax at C pages; a last fold of at
    most C / 2 pages runs after the loop over the first w x ps lanes of its
    buffer, in one of the copies of a step's body kept for C / 2 and C / 4
    pages, which ends the row itself (no branch hands the softmax's carry
    on); at `fold` 1 there is one width and no choice. Before a row waits for its last fold it
    starts what the read needs next (`hand_on`): the next row's first
    fold, or from the group's last row the next group's (its first row's
    alone if that group walks too, every row's if it takes the short rows'
    step), so the copy queue never drains at a row's end or a group's.

    The lanes of a narrowed fold that hold no live page (3 pages are
    computed at 4, one at 2 under a fold of 8) keep what an earlier fold
    left there. Their scores are masked (they lie past the row's length)
    and their probabilities are 0.0, but 0.0 x NaN is NaN in the value
    product: the buffers are zeroed before the first copy of a call, and
    what a live page holds is finite. A lane that is not computed adds
    nothing either: the sums lose only zeros.

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

    refs: [tail_len,] [lower,] [bits,] short (`_short_groups`: which
    groups take the short rows' step; SMEM, with the other scalars), q
    [R, Hkv, G, dh], [the rows' new k, v [R, Hkv, 1, dh'],] the n stacked
    pools left in HBM (k, v[, k_scale, v_scale]), [the two stacked tails
    left where they are,] o, [the tails again: the outputs alias them,]
    the pools' n VMEM buffers [2 R + 1, *page[:-1], C x ps], [the tails'
    two [2 R, Hkv, T, dh'],] DMA semaphores [n, 2 R + 1, C], [the tails'
    [2, 2 R] in and [2, R] out].
    int8 pages carry per-token scales; dequant FOLDS into the dots (k's
    scale multiplies score rows, v's folds into the probabilities)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    tail_len_ref = refs.pop(0) if tailed else None
    windowed = ring is not None
    lower_ref = refs.pop(0) if windowed else None
    bits_ref = refs.pop(0) if sub_block else None
    short_ref = refs.pop(0)
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
    tail_sems, put_sems = refs if tailed else (None, None)
    k_buf, v_buf = bufs[0], None if latent else bufs[1]
    ks_buf, vs_buf = bufs[2:] if quantized else (None, None)

    R = group
    g = pl.program_id(0)
    last_group = pl.num_programs(0) - 1
    base, spare = g * R, 2 * R
    layer = layer_ref[0]
    n_kv, G, dh = q_ref.shape[1:]
    dv = value_width or dh
    page_size = k_buf.shape[-1] // fold

    def own_of(row):
        """The buffer a row owns: its place in its group, in the half of
        the buffers its group's parity names."""
        return row // R % 2 * R + row % R

    def each_row(body):
        """`body(r)` for the R rows of a group: one copy of it, whatever
        R is."""
        if R == 1:
            return body(0)
        jax.lax.fori_loop(0, R, lambda r, _: body(r) or 0, 0)

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

    def each_copy(row, f, slot, do):
        """Start or wait for the copies of fold f of `row` into buffer
        `slot`: its first page, which a fold always has, and those of the
        C - 1 after it that are live."""
        def _page(c, _):
            page = table_ref[row, column(row, f * fold + c)]
            lane = pl.multiple_of(c * page_size, page_size)
            for j, (pool, buf) in enumerate(zip(pools, bufs)):
                window = (slot,) + (slice(None),) * (buf.ndim - 2) + (
                    pl.ds(lane, page_size),)
                do(pltpu.make_async_copy(
                    pool.at[layer, page], buf.at[window],
                    sems.at[j, slot, c]))
            return 0

        jax.lax.fori_loop(
            0, jnp.clip(pages_of(row) - f * fold, 1, fold), _page, 0)

    def start_fold(row, f, slot):
        each_copy(row, f, slot, lambda copy: copy.start())

    def wait_fold(row, f, slot):
        each_copy(row, f, slot, lambda copy: copy.wait())

    def tail_copies(row, slot):
        return [pltpu.make_async_copy(
            tail.at[layer, row], buf.at[slot], tail_sems.at[j, slot])
            for j, (tail, buf) in enumerate(zip(tails, tail_bufs))]

    def put_copies(row, r, slot):
        return [pltpu.make_async_copy(
            buf.at[slot], tail.at[layer, row], put_sems.at[j, r])
            for j, (tail, buf) in enumerate(zip(tails_out, tail_bufs))]

    def start_tail(row):
        @pl.when(tail_len_ref[row] > 0)
        def _start():
            for copy in tail_copies(row, own_of(row)):
                copy.start()

    def hand_on(row):
        """What the read needs first after `row`, started into the buffers
        of the rows it belongs to: inside a group that walks, the next
        row's first fold (its tail is under way: `walk_row`); at a group's
        end, the next group's first row's first fold and tail or, where
        that group takes the short rows' step, all its rows'. A row of
        length 0 owns no page, a bound past a row's pages leaves it none,
        and a row that holds no request has no tail either."""
        first = row + 1
        new_group = first % R == 0
        # (the last group's flag again where there is no next)
        count = jnp.where(jnp.logical_and(new_group, short_ref[
            jnp.minimum(first // R, last_group)] > 0), R, 1)

        def _row(i, _):
            nxt = first + i

            @pl.when(pages_of(nxt) > 0)
            def _first_fold():
                start_fold(nxt, 0, own_of(nxt))

            if tailed:
                pl.when(new_group)(lambda: start_tail(nxt))
            return 0

        jax.lax.fori_loop(
            0, jnp.where(first < (last_group + 1) * R, count, 0), _row, 0)

    short = short_ref[g] > 0

    def zero(slots):
        # lanes a short fold leaves uncopied are read (masked): never
        # whatever VMEM held
        if fold > 1:
            for buf in bufs:
                buf[slots] = jnp.zeros((slots.size,) + buf.shape[1:],
                                       buf.dtype)

    def _before_the_rows(i, _):
        """What a step starts before anything else: the first, what the
        call needs first (into buffers zeroed just before: the others are
        zeroed while those copies fly); one of short rows, what the read
        needs after them (a group that walks hands on from its last row's
        last fold)."""
        hand_on(jnp.where(i == 0, -1, base + R - 1))
        pl.when(i == 0)(lambda: zero(pl.ds(R, R + 1)))
        return 0

    pl.when(g == 0)(lambda: zero(pl.ds(0, R)))
    jax.lax.fori_loop(jnp.where(g == 0, 0, 1), jnp.where(short, 2, 1),
                      _before_the_rows, 0)

    def take_tail(row, r, slot):
        """The row's tail is here (the group before started its copy): the
        step's token joins it, for this row's read, and where the tail
        lives, for the steps to come (the copy back runs under the row's
        folds; a whole tail, since one token's row of a packed tile cannot
        be copied alone)."""
        for copy in tail_copies(row, slot):
            copy.wait()
        token = jax.lax.broadcasted_iota(
            jnp.int32, tail_bufs[0].shape[1:], 1)
        for buf, new in zip(tail_bufs, news):
            buf[slot] = jnp.where(token == tail_len_ref[row] - 1, new[r],
                                  buf[slot])
        for copy in put_copies(row, r, slot):
            copy.start()

    def tail_is_back(row, r, slot):
        @pl.when(tail_len_ref[row] > 0)
        def _wait():
            for copy in put_copies(row, r, slot):
                copy.wait()

    # What a softmax step attends, a part a segment: (scores with what the
    # row does not see at the mask's value, which tokens it sees where a
    # part may hold none of them (None where it always holds one: their
    # probabilities underflow to 0.0 by themselves), the values, the
    # dimensions the value product contracts, the values' scales or None).
    def tail_part(row, q, slot):
        """The first tail_len tokens of the row's tail."""
        # the tail is token-major a head ([Hkv, T, dh], dh on lanes, less
        # the lanes that pad a narrower head): its scores are the plain
        # q . k^T, batched over the KV heads
        k = tail_bufs[0][slot][:, :, :dh]
        v = k[:, :, :dv] if latent else tail_bufs[1][slot][:, :, :dh]
        s = scale * jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        token = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        held = token < tail_len_ref[row]
        if windowed:
            # tail token i is at position length + i
            held = jnp.logical_and(
                held, token >= lower_ref[row] - len_ref[row])
        # (the step's own token is always held: a tail has one to see)
        return (jnp.where(held, s, DEFAULT_MASK_VALUE), None, v,
                (((2,), (1,)), ((0,), (0,))), None)

    def lanes_part(row, q, f, slot, pages: int):
        """The first `pages` pages' lanes of buffer `slot`, which holds
        fold f of the row."""
        n_pages = pages_of(row)
        # tokens the walk reaches: it stops at the table's width whatever
        # the length says, and a fold's lanes past them were not copied
        # this turn; it starts at the page the row's lower bound is in
        walk_from = first_page(row) * page_size
        reached = jnp.minimum(len_ref[row], walk_from + n_pages * page_size)
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
            seen = jnp.logical_and(seen, kv_pos >= lower_ref[row])
        if sub_block:
            # the blocks of each listed page that the row chose
            # (a row of lanes, broadcast over the heads afterwards)
            lane_row = (1, 1, s.shape[2])
            block = jax.lax.broadcasted_iota(
                jnp.int32, lane_row, 2) // sub_block
            per = page_size // sub_block
            chosen = jnp.zeros(lane_row, bool)
            for c in range(pages):
                bits = bits_ref[row, f * fold + c]
                for i in range(per):
                    chosen = jnp.logical_or(chosen, jnp.logical_and(
                        block == c * per + i, (bits >> i) & 1 == 1))
            seen = jnp.logical_and(seen, chosen)
        v_scale = None
        if quantized:
            v_scale = vs_buf[slot, :, lanes][:, None, :].astype(jnp.float32)
            v = v.astype(jnp.bfloat16)
        # a fold may hold no token the row still sees (its bound lies in
        # the tail): exp(mask - mask) is 1.0, not 0.0
        return (jnp.where(seen, s, DEFAULT_MASK_VALUE),
                seen if windowed or sub_block else None, v,
                (((2,), (2,)), ((0,), (0,))), v_scale)

    def attend(carry, parts):
        """One online-softmax step over `parts`: one max, one exp and one
        sum over all of them, a value product each. `carry` (m, l, acc) of
        the steps before, or None for a row's only step."""
        m_new = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=-1, keepdims=True) for s, *_ in parts])
        if carry is not None:
            m_new = jnp.maximum(carry[0], m_new)
        l_new = acc_new = None
        for s, seen, v, dims, v_scale in parts:
            pr = jnp.exp(s - m_new)
            if seen is not None:
                pr = jnp.where(seen, pr, 0.0)
            l_part = jnp.sum(pr, axis=-1, keepdims=True)
            if v_scale is not None:
                pr = pr * v_scale
            pv = jax.lax.dot_general(pr.astype(v.dtype), v, dims,
                                     preferred_element_type=jnp.float32)
            l_new = l_part if l_new is None else l_new + l_part
            acc_new = pv if acc_new is None else acc_new + pv
        if carry is not None:
            alpha = jnp.exp(carry[0] - m_new)
            l_new = carry[1] * alpha + l_new
            acc_new = carry[2] * alpha + acc_new
        return m_new, l_new, acc_new

    def finish(r, carry):
        _, l, acc = carry
        o_ref[r] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    def walk_row(r):
        """Row r of the group, however many folds it holds."""
        row = base + r
        own = own_of(row)
        q = q_ref[r]                                      # [Hkv, G, dh]
        n_pages = pages_of(row)
        n_folds = (n_pages + fold - 1) // fold

        def slot_of(f):
            return jnp.where(f % 2 == 0, own, spare)

        folded = (jnp.full((n_kv, G, 1), DEFAULT_MASK_VALUE, jnp.float32),
                  jnp.zeros((n_kv, G, 1), jnp.float32),
                  jnp.zeros((n_kv, G, dv), jnp.float32))
        if tailed:
            @pl.when(n_folds > 1)
            def _second_fold():
                start_fold(row, 1, spare)

            if R > 1:
                # the next row's tail, a row ahead (a group's first row's
                # comes with its first fold: `hand_on`)
                pl.when(r < R - 1)(lambda: start_tail(row + 1))

            def fold_tail(carry):
                take_tail(row, r, own)
                return attend(carry, [tail_part(row, q, own)])

            # a row that holds no request has no tail either: it reads
            # nothing
            folded = jax.lax.cond(tail_len_ref[row] > 0, fold_tail,
                                  lambda c: c, folded)

        # a fold is computed as wide as what it copied: the loop takes
        # every fold computed at C (all but the last, and the last too
        # where over half of it is live), and a narrower last fold runs
        # after it at its own width and ends the row; a row without a page
        # holds C of a fold it never had, and ends as its tail left it
        narrowed = jnp.int32(0) + fold_branch(
            n_pages - (n_folds - 1) * fold, fold)
        last = n_folds - 1

        def fold_pages(f, carry):
            """One turn of the loop: a fold computed at all C pages."""
            slot = slot_of(f)

            # keep the DMA queue fed before waiting: this row's next fold
            # (the second is under way already where a tail folded first)
            # or, from its last fold, what the read needs after this row
            @pl.when(jnp.logical_and(f < last, f >= int(tailed)))
            def _next_fold():
                start_fold(row, f + 1, own + spare - slot)

            pl.when(f == last)(lambda: hand_on(row))
            wait_fold(row, f, slot)
            return attend(carry, [lanes_part(row, q, f, slot, fold)])

        folded = jax.lax.fori_loop(0, n_folds - (narrowed > 0) * 1,
                                   fold_pages, folded)

        @pl.when(jnp.logical_or(narrowed > 0, n_folds == 0))
        def _last_fold_is_narrow_or_none():
            hand_on(row)
            pl.when(narrowed > 0)(
                lambda: wait_fold(row, last, slot_of(last)))

        jax.lax.switch(narrowed, [functools.partial(finish, r)] + [
            lambda carry, pages=pages: finish(r, attend(carry, [lanes_part(
                row, q, last, slot_of(last), pages)]))
            for pages in fold_widths(fold)[1:]], folded)
        if tailed:
            tail_is_back(row, r, own)

    def short_row(r):
        """Row r of a group whose rows hold one fold each at most: tail
        and fold in one softmax step."""
        row = base + r
        own = own_of(row)
        n_pages = pages_of(row)

        def live():
            q, parts = q_ref[r], []
            if tailed:
                take_tail(row, r, own)
                parts = [tail_part(row, q, own)]
            wait_fold(row, 0, own)
            jax.lax.switch(fold_branch(n_pages, fold), [
                lambda pages=pages: finish(r, attend(None, parts + [
                    lanes_part(row, q, 0, own, pages)]))
                for pages in fold_widths(fold)])

        def dead():
            o_ref[r] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

        # a row that holds no request reads nothing
        jax.lax.cond(n_pages > 0, live, dead)

    def short_rows():
        each_row(short_row)
        if tailed:
            each_row(lambda r: tail_is_back(base + r, r, own_of(base + r)))

    jax.lax.cond(short, short_rows, lambda: each_row(walk_row))


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
    group = group_of(pools, table.shape[1], B)
    static = dict(scale=scale or 1.0 / math.sqrt(dh), quantized=quantized,
                  tailed=tailed, fold=fold, group=group,
                  value_width=value_width)
    if lower is not None:
        scalars.append(lower)
        static.update(ring=int(ring))
    if sub is not None:
        scalars.append(sub[0])
        static.update(sub_block=int(sub[1]))
    scalars.append(_short_groups(
        lengths, tail_lens[0] if tailed else None, lower,
        pools[0].shape[-1], fold, group, ring or table.shape[1]))
    kernel = functools.partial(_paged_kernel, **static)

    def group_index(g, *scalars):
        return (g, 0, 0, 0)

    # the pools and tails stay where they are, whole: the kernel copies
    # [layer, page] and [layer, row] (a tail is small enough that the
    # compiler may keep all of it in VMEM)
    whole = pl.BlockSpec(memory_space=pl.ANY)
    q_rows = pl.BlockSpec((group, Hkv, G, dh), group_index)
    out_rows = pl.BlockSpec((group, Hkv, G, dv), group_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, table, lengths[, tail][, lower][, bits], short
        num_scalar_prefetch=len(scalars),
        grid=(B // group,),
        in_specs=[q_rows]
        + [pl.BlockSpec((group,) + new.shape[1:], group_index)
           for new in news]
        + [whole] * (len(pools) + len(tails)),
        out_specs=[out_rows] + [whole] * len(tails),
        # a buffer a pool for each row of two groups and one to spare, each
        # a fold wide: C pages side by side
        scratch_shapes=[pltpu.VMEM((2 * group + 1,) + pool.shape[2:-1]
                                   + (fold * pool.shape[-1],), pool.dtype)
                        for pool in pools]
        + [pltpu.VMEM((2 * group,) + x.shape[2:], x.dtype) for x in tails]
        + [pltpu.SemaphoreType.DMA((len(pools), 2 * group + 1, fold))]
        + [pltpu.SemaphoreType.DMA((len(tails), 2 * group)),
           pltpu.SemaphoreType.DMA((len(tails), group))] * tailed,
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
            # sequential groups: a group starts its successor's first folds
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
