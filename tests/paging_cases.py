"""What the paged kernels' tests share (tests/test_paging_read.py: the read
and its folds; tests/test_paging_write.py: the flush and the writes;
tests/test_paging.py: the allocator and the engine on its pages): the
geometry tables and the cases built from them. Three files, so that
`--dist loadfile` can give each a worker."""

import jax.numpy as jnp
import numpy as np

from gofr_tpu.ops import paged_attention as paged_attention_module
from gofr_tpu.ops.paged_attention import (_write_columns, block_tail,
                                          fold_of, tail_put)

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
