"""What the paged kernels' tests share (tests/test_paging_read.py: the read
and its folds; tests/test_paging_write.py: the flush and the writes;
tests/test_paging.py: the allocator and the engine on its pages): the
geometry tables and the cases built from them. Three files, so that
`--dist loadfile` can give each a worker."""

import jax.numpy as jnp
import numpy as np

from gofr_tpu.ops import paged_attention as paged_attention_module
from gofr_tpu.ops.paged_attention import (_write_columns, block_tail,
                                          fold_of, plane_tail, tail_put)

# -- kernel -------------------------------------------------------------------
# The read walks each row's live pages: one loop iteration a page, the
# first page of the next row that has one started from the row before.
# Every edge of that loop, at the two head geometries the chip serves in
# miniature (G = 2 like internlm2, G = 4 like llama1b).
PS, NP_TABLE, N_LAYERS = 8, 4, 3
RAGGED = [PS + 1, 0, NP_TABLE * PS, 0, 0, 1, PS, PS - 1]
ROW_LENGTHS = {"0": [0] * 8, "1": [1] * 8, "ps-1": [PS - 1] * 8,
               "ps": [PS] * 8, "ps+1": [PS + 1] * 8,
               "full-table": [NP_TABLE * PS] * 8, "ragged": RAGGED}
GEOMETRY = {"G2": (4, 2, 32), "G4": (8, 2, 16)}          # H, Hkv, dh
# and ONE KV head (every query head reads the same page rows), where the
# walk's edges are asked for rather than the sweep
EDGE_GEOMETRY = {**GEOMETRY, "MQA": (4, 1, 32)}


# and a table 16 wide, where a turn of the read's loop folds C pages
# (`pages_per_fold`): the widths the chip's three page shapes take, and 1.
# Pages this small weigh nothing, so the rule alone would fold a table's
# width (as it does in the tests above): `_folding` gives it the weight
# that makes a fold the pages a case names.
FOLD_TABLE = 16
FOLD_GEOMETRY = {"Hkv8": (16, 8, 16), "Hkv2": (4, 2, 32), "MQA": (4, 1, 32)}
FOLD_CASES = [("Hkv8", 1, "edges"), ("Hkv8", 2, "edges"),
              ("Hkv2", 8, "edges"), ("MQA", 4, "edges"),
              ("Hkv8", 8, "narrowed"), ("Hkv2", 8, "narrowed"),
              ("MQA", 8, "narrowed")]


def _folding(monkeypatch, pools, pages):
    """The rule folds `pages` pages of these pools [P, ...] a turn."""
    page_bytes = sum(x[0].nbytes for x in pools)
    monkeypatch.setattr(paged_attention_module, "_FOLD_BYTES",
                        pages * page_bytes)
    assert fold_of([x[None] for x in pools], FOLD_TABLE) == pages


def _fold_edges(c, ps):
    """Row lengths at the edges of a fold of c pages: exactly c pages,
    c + 1 (a last fold of one page after a full one), one page, one token,
    a row of length 0 between two live rows, a last fold of one token, no
    row again, two full folds less eleven tokens (room for a block of 8
    under a table of 2 c pages)."""
    return [c * ps, (c + 1) * ps, ps, 1, 0, c * ps + 1, 0, 2 * c * ps - 11]


def _narrowed_folds(c, ps):
    """Row lengths whose last folds are computed at every width of a
    fold of c = 8 pages (`fold_branch`: 2, 4, 8): one page, then c + 1 pages (a
    full fold and a last one of one page: the turn after a wide one must
    not take what it left for live), one token, last folds of 2, 3, 4, 5,
    7 and 8 live pages with their last page full, nearly full or holding
    one token, a row of length 0, and c + 3 pages less eleven tokens
    (room for a block of 8 under a table of 2 c pages)."""
    return [ps, (c + 1) * ps, 1, 2 * ps - 3, 2 * ps + 1, 4 * ps, 5 * ps - 1,
            6 * ps + 1, 8 * ps, 0, (c + 3) * ps - 11]


FOLD_ROWS = {"edges": _fold_edges, "narrowed": _narrowed_folds}


def _paged_case(geometry, dtype, lengths, seed=0, n_table=NP_TABLE):
    """q, one layer's pools, a table of DISTINCT pages (page 0 kept as the
    dead entries' target) and the lengths."""
    H, Hkv, dh = {**EDGE_GEOMETRY, **FOLD_GEOMETRY}[geometry]
    rng = np.random.default_rng(seed)
    B = len(lengths)
    n_pool_pages = max(40, 1 + B * n_table)
    q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype=dtype)
    k_pool, v_pool = (
        jnp.asarray(rng.normal(size=(n_pool_pages, Hkv, dh, PS)), dtype=dtype)
        for _ in range(2))
    table = np.zeros((B, n_table), np.int32)
    free = iter(rng.permutation(np.arange(1, n_pool_pages)))
    for b, n in enumerate(lengths):
        for i in range(-(-n // PS)):
            table[b, i] = next(free)
    return (q, k_pool, v_pool, jnp.asarray(table),
            jnp.asarray(lengths, dtype=jnp.int32))


def _dead_pages(n_pool_pages, table, lengths, ps):
    """[P] bool: the pages no live token sits in (page 0, which every dead
    table entry names, among them)."""
    live = np.zeros(n_pool_pages, bool)
    for b, n in enumerate(np.asarray(lengths)):
        live[np.asarray(table)[b, :-(-int(n) // ps)]] = True
    assert not live[0]
    return jnp.asarray(~live)


def _in_layer(pool, layer):
    """`pool` as layer `layer` of a stack whose other layers are junk."""
    if layer is None:
        return pool
    junk = jnp.full((N_LAYERS,) + pool.shape, 7, pool.dtype)
    return junk.at[layer].set(pool)


# -- a decode block's tail ----------------------------------------------------
# Pages of 128 tokens as the chip serves them. Rows: a block that starts at
# lane 0 of a fresh page, one at lane 120 (it crosses into the next page
# after 8 tokens), one inside a page, a row that holds no request (length
# 0, its table row kept real so that "untouched" can be seen), a row whose
# pages are still empty, and one on its fourth page.
TAIL_PS = 128
TAIL_STARTS = [TAIL_PS, 120, 37, 0, 0, 3 * TAIL_PS + 77]
TAIL_IDLE = 3
TAIL_GEOMETRY = {"Hkv8": (16, 8, 16), "Hkv2": (8, 2, 32)}   # H, Hkv, dh


def _tail_case(geometry, dtype, block, seed=0, layers=2):
    """Stacked pools holding each row's context, the table, the block's
    new K and V [block, L, B, Hkv, dh] and the starts."""
    H, Hkv, dh = TAIL_GEOMETRY[geometry]
    rng = np.random.default_rng(seed)
    B, n_table, n_pool_pages = len(TAIL_STARTS), 5, 40
    k_pool, v_pool = (jnp.asarray(rng.normal(
        size=(layers, n_pool_pages, Hkv, dh, TAIL_PS)), dtype=dtype)
        for _ in range(2))
    free = iter(rng.permutation(np.arange(1, n_pool_pages)))
    table = np.zeros((B, n_table), np.int32)
    for b, start in enumerate(TAIL_STARTS):
        for i in range((start + block - 1) // TAIL_PS + 1):
            table[b, i] = next(free)
    news = [jnp.asarray(rng.normal(size=(block, layers, B, Hkv, dh)),
                        dtype=dtype) for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(B, H, dh)), dtype=dtype)
    live = np.arange(B) != TAIL_IDLE
    return (q, k_pool, v_pool, jnp.asarray(table), news,
            jnp.asarray(TAIL_STARTS, jnp.int32), jnp.asarray(live))


def _written_by_columns(k_pool, v_pool, news, table, starts, live, steps):
    """The pools after `steps` per-token column writes of the live rows
    (an idle row's go to page 0 of a table row of zeros, as the engine's
    do): what the parent's decode write left."""
    table = jnp.where(live[:, None], table, 0)
    for t in range(steps):
        for layer in range(k_pool.shape[0]):
            k_pool, v_pool = _write_columns(
                [k_pool, v_pool], [news[0][t, layer], news[1][t, layer]],
                table, starts + t, layer)
    return k_pool, v_pool


def _tail_of(k_pool, news, steps, block):
    tail = block_tail(k_pool, news[0].shape[2], block)
    for t in range(steps):
        for layer in range(k_pool.shape[0]):
            tail = tail_put(*tail, news[0][t, layer], news[1][t, layer],
                            layer, t)
    return tail


# -- several short rows a grid step -------------------------------------------
# The read walks R consecutive rows a grid step (`rows_a_step`), and where
# each of them holds one fold at most, takes them in the short rows' step
# (the tail and the fold in one softmax step). Pages of 8 tokens, folds of
# C = 4 pages under a table 16 wide, groups of R = 4 rows (the rule is given
# the cap that makes it so), 12 rows: three groups, so that a group's
# buffers are used again by the group after next. Rows, by the pages they
# hold: all one page; 1..C pages mixed; rows that hold no request inside a
# group; ONE row of several folds (its group walks row by row between two
# groups that do not); no request at all; several folds in every row (no
# group takes the short rows' step); and 9 rows, which 4 does not divide
# (the rule takes 3).
GROUP_FOLD, GROUP_ROWS, GROUP_BLOCK = 4, 4, 16
GROUP_PAGES = {
    "one-page": [1] * 12,
    "mixed": [1, 2, 3, 4, 4, 1, 3, 2, 2, 4, 1, 3],
    "dead-inside": [1, 0, 3, 4, 4, 1, 0, 0, 2, 4, 1, 3],
    "one-long": [1, 2, 3, 4, 4, 9, 3, 2, 2, 4, 1, 3],
    "all-dead": [0] * 12,
    "all-long": [5, 9, 6, 12, 7, 5, 10, 8, 9, 6, 11, 5],
    "ragged": [1, 2, 0, 4, 9, 1, 3, 2, 2]}
# H, Hkv, dh[, value width]: the latent page's one head of 576 read by 32
# queries, 2 KV heads x 16 queries, 8 KV heads x 2
GROUP_GEOMETRY = {"latent": (32, 1, 576, 512), "Hkv2xG16": (32, 2, 32),
                  "Hkv8xG2": (16, 8, 16)}


def _grouping(monkeypatch, rows: int, most: int = GROUP_ROWS):
    """The rule walks the most rows a step that divide `rows`, up to
    `most`. Returns them."""
    monkeypatch.setattr(paged_attention_module, "_GROUP_ROWS", most)
    return max(r for r in range(1, most + 1) if rows % r == 0)


def _group_lengths(rows: str, seed=0):
    """Tokens each row holds in pages: its last page filled to anywhere,
    a whole page now and then."""
    rng = np.random.default_rng(seed)
    pages = np.asarray(GROUP_PAGES[rows])
    last = np.where(np.arange(len(pages)) % 5 == 4, PS,
                    rng.integers(1, PS + 1, size=len(pages)))
    return np.where(pages > 0, (pages - 1) * PS + last, 0)


def _group_case(geometry, rows, t, seed=0, layers=2):
    """A decode block's step t over the rows `rows` names: stacked pools
    (K alone for the latent geometry) that hold each row's context, a
    table with room for the block, the block's first t + 1 tokens a plane
    [t + 1, B, Hkv, dh], q, the rows' starts and which of them hold a
    request."""
    H, Hkv, dh = GROUP_GEOMETRY[geometry][:3]
    rng = np.random.default_rng(seed)
    starts = _group_lengths(rows, seed)
    B, planes = len(starts), 1 if geometry == "latent" else 2
    n_pool_pages = 1 + B * FOLD_TABLE
    pools = [jnp.asarray(rng.normal(
        size=(layers, n_pool_pages, Hkv, dh, PS)), jnp.float32)
        for _ in range(planes)]
    live = starts > 0
    table = np.zeros((B, FOLD_TABLE), np.int32)
    free = iter(rng.permutation(np.arange(1, n_pool_pages)))
    for b in np.flatnonzero(live):
        for i in range((starts[b] + GROUP_BLOCK - 1) // PS + 1):
            table[b, i] = next(free)
    news = [jnp.asarray(rng.normal(size=(t + 1, B, Hkv, dh)), jnp.float32)
            for _ in range(planes)]
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    return (q, pools, jnp.asarray(table), news,
            jnp.asarray(starts, jnp.int32), jnp.asarray(live))


def _group_written(pools, news, table, starts, live, layer):
    """`layer` of the pools once the block's tokens so far are written
    column by column (a row without a request writes to page 0)."""
    table = jnp.where(live[:, None], table, 0)
    pools = [pool[layer][None] for pool in pools]
    for t in range(news[0].shape[0]):
        pools = _write_columns(pools, [new[t] for new in news], table,
                               starts + t, 0)
    return [pool[0] for pool in pools]


def _group_tails(pools, news, layer, rows):
    """The planes' tails holding all but the last of `news` in `layer`
    (the last is the step's own token, which the read puts)."""
    tails = [plane_tail(pool, rows, GROUP_BLOCK) for pool in pools]
    for t in range(news[0].shape[0] - 1):
        tails = [_token_put(tail, new[t], layer, t)
                 for tail, new in zip(tails, news)]
    return tails


def _token_put(tail, new, layer, t):
    """new [B, Hkv, dh] as token t of every row of `layer`'s tail."""
    dh = new.shape[-1]
    return tail.at[layer, :, :, t, :dh].set(new.astype(tail.dtype))
