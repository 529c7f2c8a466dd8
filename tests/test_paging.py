"""Paged KV serving: kernel numerics, allocator ledger, engine behavior.

The load-bearing assertions (VERDICT r2 missing #4 "done" criteria):
  - engine output == the plain cached reference's, token for token
  - HBM pool bytes and page usage track the SUM of live contexts, not
    max_seq x n_slots (mixed 16-token and long contexts share one pool)
  - admission defers when the pool is exhausted and resumes on free
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.llama import (LlamaConfig, init_kv_cache,
                                   llama_decode_step, llama_init,
                                   llama_prefill)
from gofr_tpu.ops import paged_attention as paged_attention_module
from gofr_tpu.ops.paged_attention import (_write_columns, block_tail,
                                          fold_branch, fold_of, fold_widths,
                                          pages_per_fold,
                                          paged_attention,
                                          paged_attention_in_block,
                                          paged_attention_reference,
                                          paged_flush_block,
                                          paged_write_decode,
                                          paged_write_prefill, quantize_kv,
                                          tail_put)
from gofr_tpu.tpu.paging import PageAllocator, PagedLLMEngine

CFG = LlamaConfig.debug()


class MockLogger:
    def debugf(self, *a): pass
    def infof(self, *a): pass
    def warnf(self, *a): pass
    def errorf(self, *a): pass


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


_read = jax.jit(paged_attention)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", [0, N_LAYERS - 1, None],
                         ids=["first-layer", "last-layer", "one-layer"])
@pytest.mark.parametrize("geometry", list(GEOMETRY))
@pytest.mark.parametrize("lengths", list(ROW_LENGTHS))
def test_paged_attention_kernel_matches_reference(lengths, geometry, layer,
                                                  dtype):
    q, k_pool, v_pool, table, lens = _paged_case(geometry, dtype,
                                                 ROW_LENGTHS[lengths])
    ref = paged_attention_reference(q.astype(jnp.float32), k_pool, v_pool,
                                    table, lens)
    out = _read(q, _in_layer(k_pool, layer), _in_layer(v_pool, layer), table,
                lens, layer=None if layer is None else jnp.int32(layer))
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)
    # a row with nothing to attend reads nothing and answers zeros
    empty = np.asarray(lens) == 0
    assert not np.asarray(out, dtype=np.float32)[empty].any()


@pytest.mark.parametrize("geometry", list(EDGE_GEOMETRY))
def test_paged_attention_reads_live_pages_only(geometry):
    """Every page no live token sits in is NaN, and so is the page every
    dead table entry names: the kernel dereferences neither."""
    q, k_pool, v_pool, table, lens = _paged_case(geometry, jnp.float32,
                                                 RAGGED, seed=3)
    ref = paged_attention_reference(q, k_pool, v_pool, table, lens)
    live = np.zeros(k_pool.shape[0], bool)
    for b, n in enumerate(RAGGED):
        live[np.asarray(table)[b, :-(-n // PS)]] = True
    assert not live[0] and live.sum() == sum(-(-n // PS) for n in RAGGED)
    poison = jnp.asarray(~live)[:, None, None, None]
    out = _read(q, jnp.where(poison, jnp.nan, k_pool),
                jnp.where(poison, jnp.nan, v_pool), table, lens)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_the_fold_is_worked_out_from_what_a_call_sees():
    """`pages_per_fold`: the bytes of a page over the call's pools and the
    table's width; nothing else. The benchmark's three page shapes, the
    widths a narrow table leaves, and two buffers of C pages inside a
    quarter of the kernel's 16 MiB of VMEM."""
    latent = 1 * 576 * 128 * 2                  # joyai: one plane, bf16
    nemotron = 2 * 2 * 128 * 128 * 2            # K and V of 2 heads
    internlm2 = 2 * 8 * 128 * 128 * 2           # K and V of 8 heads
    assert pages_per_fold(latent, 64) == 8
    assert pages_per_fold(nemotron, 16) == 8
    assert pages_per_fold(internlm2, 16) == 2
    # a fold is never wider than a row can be
    assert [pages_per_fold(latent, n) for n in (1, 2, 3, 4, 9, 16)] == [
        1, 2, 2, 4, 8, 8]
    # nor its two buffers larger than 4 MiB, whatever a page weighs
    for page_bytes in (1, 1000, latent, 600 << 10, (1 << 20) - 1, 1 << 20,
                       3 << 20):
        c = pages_per_fold(page_bytes, 1 << 20)
        assert c == 1 or 2 * c * page_bytes < 4 << 20
        assert c * page_bytes >= 1 << 20
    # from the pools themselves: int8 pages count their scale planes, and
    # under a tp mesh a shard's bytes are what its kernel sees
    pool = jnp.zeros((2, 5, 8, 128, 128), jnp.int8)
    scale = jnp.zeros((2, 5, 8, 128), jnp.float32)
    assert fold_of([pool, pool, scale, scale], 64) == pages_per_fold(
        2 * (8 * 128 * 128 + 8 * 128 * 4), 64) == 4

    class TwoShards:
        shape = {"tp": 2}

    assert fold_of([pool, pool, scale, scale], 64, TwoShards()) == 8


@pytest.mark.parametrize("c", [1, 2, 4, 8, 16])
def test_a_fold_is_computed_at_the_least_power_of_two_that_covers_it(c):
    """`fold_branch`: which of `fold_widths(c)` (c, c / 2, c / 4) a fold of
    n live pages is computed at. Never narrower than what was copied, a
    power of two, never wider than the fold, and the least such that is
    no less than a quarter of the fold; the same answer for a Python int,
    an array of the host's counter and a traced scalar of the kernel's."""
    widths = fold_widths(c)
    assert widths == tuple(w for w in (c, c // 2, c // 4) if w)
    live = np.arange(1, c + 1)
    computed = [widths[fold_branch(int(n), c)] for n in live]
    for n, w in zip(live, computed):
        assert n <= w <= c and w & (w - 1) == 0
        assert w == widths[-1] or w // 2 < n
    if c == 8:
        assert computed == [2, 2, 4, 4, 8, 8, 8, 8]
    if c == 1:      # one width: nothing to choose, for anybody
        return
    assert np.take(widths, fold_branch(live, c)).tolist() == computed
    traced = jax.jit(jax.vmap(lambda n: fold_branch(n, c)))(jnp.asarray(live))
    assert np.take(widths, np.asarray(traced)).tolist() == computed


def _width_choices(jaxpr, in_loop=False):
    """[(branches, inside a loop)] of every `cond` of more than two
    branches in a jaxpr, kernels' included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and len(eqn.params["branches"]) > 2:
            found.append((len(eqn.params["branches"]), in_loop))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _width_choices(
                        sub, in_loop or eqn.primitive.name == "while")
    return found


@pytest.mark.parametrize("c", [1, 4, 8])
def test_only_a_rows_last_fold_chooses_its_width(c, monkeypatch):
    """The kernel holds ONE choice among a fold's widths, outside the
    loop over a row's full folds (a full fold's turn is computed at C
    pages and branches on no width), and none at folds of one page."""
    q, k, v, table, lens = _paged_case(
        "Hkv2", jnp.float32, _fold_edges(c, PS), n_table=FOLD_TABLE)
    _folding(monkeypatch, (k, v), c)
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention(*a, interpret=True))(
        q, k, v, table, lens).jaxpr
    assert _width_choices(jaxpr) == ([(len(fold_widths(c)), False)]
                                     if c > 1 else [])


@pytest.mark.parametrize("pool", ["f32", "int8"])
@pytest.mark.parametrize("geometry,c,rows", FOLD_CASES)
def test_paged_attention_folds_ragged_rows(geometry, c, rows, pool,
                                           monkeypatch):
    """A fold's edges (`_fold_edges`) at folds of 1, 2, 4 and 8 pages, and
    last folds of every width a fold of 8 is computed at
    (`_narrowed_folds`) at 8, 2 and 1 KV heads, every dead page NaN (the
    int8 pools': its scales): a short last fold reads no page it does not
    own, and the lanes it leaves uncopied or does not compute do not reach
    the value product."""
    q, k, v, table, lens = _paged_case(
        geometry, jnp.float32, FOLD_ROWS[rows](c, PS), seed=11,
        n_table=FOLD_TABLE)
    dead = _dead_pages(k.shape[0], table, lens, PS)
    if pool == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        scales = [ks, vs]
        given = [k, v] + [jnp.where(dead[:, None, None], jnp.nan, x)
                          for x in scales]
    else:
        scales = []
        given = [jnp.where(dead[:, None, None, None], jnp.nan, x)
                 for x in (k, v)]
    _folding(monkeypatch, given, c)
    ref = paged_attention_reference(q, k, v, table, lens, *scales)
    # its own jit: `_read` keeps a trace by shapes, whatever the fold was
    out = np.asarray(jax.jit(lambda *a: paged_attention(*a))(
        q, *given[:2], table, lens, *given[2:]))
    tol = 5e-2 if pool == "int8" else 2e-5
    np.testing.assert_allclose(out, np.asarray(ref), rtol=tol, atol=tol)
    assert not out[np.asarray(lens) == 0].any()


def test_decode_step_row_without_request_attends_nothing():
    """An idle slot's row of the table is zeros (the garbage page) and its
    position is stale and still advancing: the step hands the read a
    length of 0 for it, in pages and in the block's tail, so it walks no
    page — here the garbage page is NaN, and the idle row's stale position
    lies far past the table."""
    from gofr_tpu.models.llama import llama_decode_step_paged

    params = llama_init(CFG, seed=0)
    ps, n_pool_pages = 8, 6
    shape = (CFG.n_layers, n_pool_pages, CFG.n_kv_heads, CFG.head_dim, ps)
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.normal(size=shape), dtype=jnp.float32)
    poisoned = pool.at[:, 0].set(jnp.nan)
    table = jnp.asarray([[2, 3, 0, 0], [0, 0, 0, 0], [4, 0, 0, 0]],
                        dtype=jnp.int32)
    tokens = jnp.asarray([5, 6, 7], dtype=jnp.int32)
    positions = jnp.asarray([11, 10_000, 3], dtype=jnp.int32)
    step = jax.jit(lambda k, v: llama_decode_step_paged(
        params, CFG, tokens, positions, k, v, table,
        block_tail(k, 3, 4), jnp.int32(0))[0])
    logits = np.asarray(step(poisoned, poisoned))
    assert np.isfinite(logits).all()
    np.testing.assert_allclose(logits[[0, 2]],
                               np.asarray(step(pool, pool))[[0, 2]],
                               rtol=1e-5, atol=1e-5)


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


_read_in_block = jax.jit(paged_attention_in_block)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("geometry", list(TAIL_GEOMETRY))
@pytest.mark.parametrize("t", [0, 7, 15])
def test_paged_attention_over_pages_and_tail_matches_reference(t, geometry,
                                                               dtype):
    """Step t of a block of 16: the step's token put into a tail that
    holds t, and the read over the pages as the block found them plus the
    tail's first t + 1 tokens, against the plain put and the reference on
    a pool that had the same tokens written column by column."""
    q, k_pool, v_pool, table, news, starts, live = _tail_case(
        geometry, dtype, 16)
    k_ref, v_ref = _written_by_columns(k_pool, v_pool, news, table, starts,
                                       live, t + 1)
    layer = k_pool.shape[0] - 1
    ref = paged_attention_reference(
        q.astype(jnp.float32), k_ref[layer], v_ref[layer], table,
        jnp.where(live, starts + t + 1, 0))
    out, k_tail, v_tail = _read_in_block(
        q, news[0][t, layer], news[1][t, layer], k_pool, v_pool,
        *_tail_of(k_pool, news, t, 16), table, jnp.where(live, starts, 0),
        jnp.where(live, t + 1, 0), layer=jnp.int32(layer))
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)
    assert not np.asarray(out, dtype=np.float32)[TAIL_IDLE].any()
    # the tail it returns: the plain put's in this layer's live rows, the
    # other layers and the row without a request as they were
    want = _tail_of(k_pool, news, t + 1, 16)
    before = _tail_of(k_pool, news, t, 16)
    live = np.asarray(live)
    for got, put, was in zip((k_tail, v_tail), want, before):
        got, put, was = np.asarray(got), np.asarray(put), np.asarray(was)
        np.testing.assert_array_equal(got[layer][live], put[layer][live])
        np.testing.assert_array_equal(got[layer][~live], was[layer][~live])
        np.testing.assert_array_equal(got[:layer], was[:layer])


@pytest.mark.parametrize("t", [0, 5])
@pytest.mark.parametrize("geometry,c,rows", [
    ("Hkv8", 2, "edges"), ("Hkv2", 8, "edges"), ("Hkv2", 1, "edges"),
    ("Hkv8", 8, "narrowed"), ("Hkv2", 8, "narrowed"),
    ("MQA", 8, "narrowed")])
def test_paged_attention_in_block_folds_ragged_rows(geometry, c, rows, t,
                                                    monkeypatch):
    """The read inside a decode block at a fold's edges (`_fold_edges`)
    and at last folds of every width (`_narrowed_folds`), pages of 8
    tokens under a table 16 wide, step t of a block of 8 with every dead
    page NaN: against the reference on a pool that had the block's tokens
    written column by column. The rows of length 0 hold no request."""
    H, Hkv, dh = FOLD_GEOMETRY[geometry]
    block, layers = 8, 2
    starts = FOLD_ROWS[rows](c, PS)
    rng = np.random.default_rng(13)
    B, n_pool_pages = len(starts), 1 + len(starts) * FOLD_TABLE
    k_pool, v_pool = (jnp.asarray(rng.normal(
        size=(layers, n_pool_pages, Hkv, dh, PS)), jnp.float32)
        for _ in range(2))
    _folding(monkeypatch, (k_pool[0], v_pool[0]), c)
    live = np.asarray(starts) > 0
    table = np.zeros((B, FOLD_TABLE), np.int32)
    free = iter(rng.permutation(np.arange(1, n_pool_pages)))
    for b in np.flatnonzero(live):
        for i in range((starts[b] + block - 1) // PS + 1):
            table[b, i] = next(free)
    table, starts = jnp.asarray(table), jnp.asarray(starts, jnp.int32)
    news = [jnp.asarray(rng.normal(size=(block, layers, B, Hkv, dh)),
                        jnp.float32) for _ in range(2)]
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    live = jnp.asarray(live)
    k_ref, v_ref = _written_by_columns(k_pool, v_pool, news, table, starts,
                                       live, t + 1)
    layer = layers - 1
    ref = paged_attention_reference(
        q, k_ref[layer], v_ref[layer], table,
        jnp.where(live, starts + t + 1, 0))
    # what the block found in pages: the pages past it are dead, the
    # block's own among them (its tokens wait in the tail)
    dead = _dead_pages(n_pool_pages, table, starts, PS)[
        None, :, None, None, None]
    out, _, _ = jax.jit(lambda *a, **kw: paged_attention_in_block(*a, **kw))(
        q, news[0][t, layer], news[1][t, layer],
        jnp.where(dead, jnp.nan, k_pool), jnp.where(dead, jnp.nan, v_pool),
        *_tail_of(k_pool, news, t, block), table, jnp.where(live, starts, 0),
        jnp.where(live, t + 1, 0), layer=jnp.int32(layer))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~np.asarray(live)].any()


def test_paged_attention_reads_no_tail_of_a_row_without_request():
    """The idle row's tail is NaN, and so is every key past a live row's
    count: neither is attended. (A value past the count meets a
    probability of 0.0: `block_tail` makes it zero and nothing else writes
    there.)"""
    q, k_pool, v_pool, table, news, starts, live = _tail_case(
        "Hkv2", jnp.float32, 16)
    k_tail, v_tail = _tail_of(k_pool, news, 3, 16)
    args = (q, news[0][3, 0], news[1][3, 0], k_pool, v_pool)
    rest = (table, jnp.where(live, starts, 0), jnp.where(live, 4, 0))
    want = _read_in_block(*args, k_tail, v_tail, *rest, layer=jnp.int32(0))[0]
    idle = ~live[None, :, None, None, None]
    unheld = jnp.arange(16)[None, None, None, :, None] >= 4
    got = _read_in_block(
        *args, jnp.where(jnp.logical_or(idle, unheld), jnp.nan, k_tail),
        jnp.where(idle, jnp.nan, v_tail), *rest, layer=jnp.int32(0))[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("path", ["kernel", "plain"])
@pytest.mark.parametrize("dtype,tp", [("float32", 1), ("bfloat16", 1),
                                      ("bfloat16", 2)])
@pytest.mark.parametrize("block", [1, 8, 16])
def test_paged_flush_equals_the_column_writes(block, dtype, tp, path):
    """One flush of a block's tail (the Pallas kernel interpreted: what
    the chip runs; the plain scatter: what the CPU runs) against `block`
    per-token column writes: the same pools, exactly, every layer, the row
    that crosses a page written in both, the idle row's page untouched —
    one device and heads sharded over a tp mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    q, k_pool, v_pool, table, news, starts, live = _tail_case(
        "Hkv2", dtype, block, seed=5)
    want = _written_by_columns(k_pool, v_pool, news, table, starts, live,
                               block)
    tail = _tail_of(k_pool, news, block, block)
    mesh = None
    if tp > 1:
        mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
        heads = NamedSharding(mesh, PartitionSpec(None, None, "tp"))
        k_pool, v_pool, *tail = (jax.device_put(x, heads) for x in
                                 (k_pool, v_pool, *tail))
    got = jax.jit(lambda k, v, kt, vt: paged_flush_block(
        k, v, kt, vt, table, starts, jnp.where(live, block, 0), mesh=mesh,
        interpret=True if path == "kernel" else None))(k_pool, v_pool, *tail)
    crossing = np.asarray(table)[1, :2]
    for g, w, before in zip(got, want, (k_pool, v_pool)):
        g, w, before = np.asarray(g), np.asarray(w), np.asarray(before)
        np.testing.assert_array_equal(g[:, 1:], w[:, 1:])  # 0: the garbage
        idle = np.asarray(table)[TAIL_IDLE, 0]
        np.testing.assert_array_equal(g[:, idle], before[:, idle])
        assert not np.array_equal(g[:, crossing[0]], before[:, crossing[0]])
        assert (block <= 8) == np.array_equal(g[:, crossing[1]],
                                              before[:, crossing[1]])


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_paged_flush_reaches_every_page_a_long_block_crosses(path):
    """Pages of 8 tokens and a block of 16 from lane 7: three pages of one
    row, each written once."""
    rng = np.random.default_rng(2)
    L, P, Hkv, dh, ps, B = 2, 9, 2, 16, 8, 2
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(L, P, Hkv, dh, ps)),
                                  jnp.float32) for _ in range(2))
    news = [jnp.asarray(rng.normal(size=(16, L, B, Hkv, dh)), jnp.float32)
            for _ in range(2)]
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7]], jnp.int32)
    starts, live = jnp.asarray([7, 8], jnp.int32), jnp.asarray([True, True])
    want = _written_by_columns(k_pool, v_pool, news, table, starts, live, 16)
    got = paged_flush_block(
        k_pool, v_pool, *_tail_of(k_pool, news, 16, 16), table, starts,
        jnp.full((B,), 16, jnp.int32),
        interpret=True if path == "kernel" else None)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g)[:, 1:],
                                      np.asarray(w)[:, 1:])
    assert not np.array_equal(np.asarray(got[0])[:, 3],
                              np.asarray(k_pool)[:, 3])


def test_paged_flush_with_no_live_row_changes_nothing():
    """Every step of the flush names the garbage page then, and the page
    goes back as it came."""
    q, k_pool, v_pool, table, news, starts, live = _tail_case(
        "Hkv2", jnp.float32, 8)
    tail = _tail_of(k_pool, news, 8, 8)
    for interpret in (True, None):
        got = paged_flush_block(k_pool, v_pool, *tail, table, starts,
                                jnp.zeros_like(starts), interpret=interpret)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(k_pool))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(v_pool))


def test_paged_writes_round_trip():
    rng = np.random.default_rng(1)
    Hkv, dh, ps, P = 2, 16, 8, 12
    k_pool = jnp.zeros((P, Hkv, dh, ps), dtype=jnp.float32)
    v_pool = jnp.zeros_like(k_pool)

    # prefill: 11 tokens over pages [2, 3]; junk past length=11 -> garbage
    K, T = 1, 16
    kpre = jnp.asarray(rng.normal(size=(K, T, Hkv, dh)), dtype=jnp.float32)
    table = jnp.asarray([[2, 3]], dtype=jnp.int32)
    lens = jnp.asarray([11], dtype=jnp.int32)
    kp, vp = paged_write_prefill(k_pool, v_pool, kpre, kpre, table, lens)
    np.testing.assert_array_equal(np.asarray(kp[2, :, :, 5]),
                                  np.asarray(kpre[0, 5]))
    np.testing.assert_array_equal(np.asarray(kp[3, :, :, 2]),
                                  np.asarray(kpre[0, 10]))
    assert np.all(np.asarray(kp[3, :, :, 3:]) == 0)  # junk went to garbage

    # decode write at position 11 -> page 3, offset 3
    knew = jnp.asarray(rng.normal(size=(1, Hkv, dh)), dtype=jnp.float32)
    kp, vp = paged_write_decode(kp, vp, knew, knew, table,
                                jnp.asarray([11], dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(kp[3, :, :, 3]),
                                  np.asarray(knew[0]))


@pytest.mark.parametrize("dtype,tp", [("float32", 1), ("bfloat16", 1),
                                      ("int8", 1), ("bfloat16", 2),
                                      ("int8", 2)])
def test_paged_write_kernel_equals_the_column_write(dtype, tp):
    """The decode write's Pallas kernel (what the chip runs: a page
    read-modify-write, in place) against the plain per-token column write
    (what the CPU runs, and the kernel's reference): the same pools,
    exactly — values and int8 scales, the written layer and the others,
    one device and heads sharded over a tp mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    rng = np.random.default_rng(3)
    L, P, Hkv, dh, ps, B, NP = 3, 12, 4, 16, 8, 5, 2
    quantized = dtype == "int8"

    def pool(shape, dt):
        values = rng.integers(-100, 100, size=shape) if dt == "int8" \
            else rng.normal(size=shape)
        return jnp.asarray(values, dtype=dt)

    pools = [pool((L, P, Hkv, dh, ps), dtype) for _ in range(2)]
    news = [pool((B, Hkv, dh), dtype) for _ in range(2)]
    if quantized:
        pools += [pool((L, P, Hkv, ps), "float32") for _ in range(2)]
        news += [pool((B, Hkv), "float32") for _ in range(2)]
    # distinct live pages per row, plus two inactive rows on the garbage page
    table = jnp.asarray([[1, 2], [3, 4], [5, 6], [0, 0], [0, 0]], jnp.int32)
    positions = jnp.asarray([0, 7, 11, 3, 3], jnp.int32)
    mesh = None
    if tp > 1:
        mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
        heads = {5: PartitionSpec(None, None, "tp", None, None),
                 4: PartitionSpec(None, None, "tp", None),
                 3: PartitionSpec(None, "tp", None),
                 2: PartitionSpec(None, "tp")}
        pools = [jax.device_put(x, NamedSharding(mesh, heads[x.ndim]))
                 for x in pools]
        news = [jax.device_put(x, NamedSharding(mesh, heads[x.ndim]))
                for x in news]

    def write(interpret):
        def fn(pools, news):
            return paged_write_decode(
                pools[0], pools[1], news[0], news[1], table, positions,
                *pools[2:], *news[2:], layer=jnp.int32(1), mesh=mesh,
                interpret=interpret)
        return jax.jit(fn)(pools, news)

    got, want = write(True), write(None)
    assert len(got) == len(want) == len(pools)
    for g, w, before in zip(got, want, pools):
        g, w, before = np.asarray(g), np.asarray(w), np.asarray(before)
        live = np.arange(P) != 0      # the garbage page holds whichever won
        np.testing.assert_array_equal(g[:, live], w[:, live])
        assert not np.array_equal(w[1], before[1])       # layer 1 written
        np.testing.assert_array_equal(w[[0, 2]], before[[0, 2]])


# -- allocator ----------------------------------------------------------------
def test_page_allocator_ledger():
    a = PageAllocator(n_pages=9, page_size=16)
    assert a.free_pages == 8  # page 0 reserved as garbage
    assert a.garbage_page == 0
    assert 0 not in a.alloc(8)  # garbage page is never handed out
    a = PageAllocator(n_pages=9, page_size=16)
    assert a.pages_for(1) == 1 and a.pages_for(16) == 1 and a.pages_for(17) == 2
    got = a.alloc(5)
    assert len(got) == 5 and a.free_pages == 3
    assert a.alloc(4) is None          # insufficient: nothing taken
    assert a.free_pages == 3
    a.release(got[:2])
    assert a.free_pages == 5
    assert a.used_pages == 3


# -- engine -------------------------------------------------------------------
def _make_paged(**kw):
    params = llama_init(CFG, seed=0)
    defaults = dict(n_slots=4, max_seq_len=64, prefill_buckets=(8, 16),
                    page_size=8, logger=MockLogger())
    defaults.update(kw)
    eng = PagedLLMEngine(params, CFG, **defaults)
    eng.start()
    return eng


def _reference_greedy(params, prompt, n):
    """The plain cached reference (models/llama.py `llama_prefill` then
    `llama_decode_step` over one contiguous cache): n greedy tokens."""
    k, v = init_kv_cache(CFG, 1, 64)
    logits, k, v = llama_prefill(params, CFG,
                                 jnp.asarray([prompt], jnp.int32), k, v)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n - 1):
        logits, k, v = llama_decode_step(
            params, CFG, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([len(prompt) + i], jnp.int32), k, v)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_paged_engine_matches_the_cached_reference():
    """Token-for-token parity with the plain cached forward under greedy
    decode."""
    params = llama_init(CFG, seed=0)
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [1, 2]]
    want = [_reference_greedy(params, p, 8) for p in prompts]

    paged = _make_paged()
    try:
        got = [paged.generate(p, max_new_tokens=8, temperature=0.0)
               for p in prompts]
    finally:
        paged.stop()
    assert got == want


@pytest.mark.parametrize("block", [1, 4, 16])
def test_every_decode_block_size_serves_the_references_tokens(block):
    """The block's tail is how the decode write works at every block
    size: pages of 8 tokens, so a block of 16 crosses two boundaries and a
    block of 1 flushes one column; the tokens are the cached reference's, and
    `/debug/engine` and the step ledger say how many tokens a page write
    placed."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    params = llama_init(CFG, seed=0)
    prompts = [[5, 6, 7], [9, 10, 11, 12, 13, 14, 15, 16, 17], [1, 2]]
    want = [_reference_greedy(params, p, 20) for p in prompts]
    paged = _make_paged(decode_block_size=block)
    try:
        requests = [paged.submit(p, max_new_tokens=20, temperature=0.0)
                    for p in prompts]
        got = [r.result(timeout_s=300) for r in requests]
        assert engine_snapshot(paged)["paging"]["write"]["page_writes"] > 0
    finally:
        paged.stop()
    # read once the loop has stopped: the last block's record is closed
    write = paged.paging_snapshot()["write"]
    records = paged.steps.records(recent=256)
    assert got == want
    assert write["tokens"] >= 3 * 19 and write["page_writes"] > 0
    assert write["tokens_per_page_write"] == round(
        write["tokens"] / write["page_writes"], 3)
    # a block of b tokens over pages of 8 is written in 1 + (b - 1) // 8
    # pages, or one more where it started late in its page
    fewest = 1 + (block - 1) // 8
    assert (block / (fewest + 1) - 1e-3 <= write["tokens_per_page_write"]
            <= block / fewest + 1e-3)
    assert sum(r.page_writes for r in records) == write["page_writes"]
    assert all(r.page_writes == 0 for r in records if r.phase != "decode")
    # and the reads: under the table of 8 these requests take a fold is 8
    # of the tiny pages, every step of every block, both layers
    read = paged.paging_snapshot()["read"]
    assert read["pages_per_fold"] == 8
    assert read["folds"] >= CFG.n_layers * write["tokens"]
    assert 0 < read["narrowed_folds"] <= read["folds"]
    assert 0 < read["fold_live_share"] <= 1


def test_paged_engine_concurrent_mixed_lengths():
    """Mixed short/long contexts share the pool; usage tracks the SUM of
    live pages (a short context is NOT billed for the longest's length)."""
    eng = _make_paged(n_slots=4, max_seq_len=64, page_size=8,
                      n_pages=4 * 8 + 1)
    try:
        long_req = eng.submit(list(range(1, 15)), max_new_tokens=24,
                              temperature=0.0)   # 38 tokens -> 5 pages
        short_req = eng.submit([3, 4], max_new_tokens=4,
                               temperature=0.0)  # 6 tokens -> 1 page
        while not (long_req.generated and short_req.generated):
            time.sleep(0.01)
        # while both are live: 5 + 1 pages, not 2 x pages(max_seq)
        assert eng.allocator.used_pages == 6
        short_req.result(timeout_s=60)
        long_req.result(timeout_s=60)
        deadline = time.time() + 5
        while eng.allocator.used_pages and time.time() < deadline:
            time.sleep(0.01)
        assert eng.allocator.used_pages == 0  # everything returned
    finally:
        eng.stop()


def test_paged_pool_bytes_track_budget_not_dense_worstcase():
    """The pool is the explicit budget: sized at n_pages, independent of
    n_slots x max_seq_len."""
    eng = _make_paged(n_slots=4, max_seq_len=64, page_size=8, n_pages=9)
    try:
        dense_equiv = 2 * (CFG.n_layers * 4 * CFG.n_kv_heads * CFG.head_dim
                           * 64 * 4)  # f32 dense cache bytes at max_seq
        assert eng.pool_bytes() < dense_equiv / 3
    finally:
        eng.stop()


def test_paged_admission_defers_until_pages_free():
    """With a pool that fits ONE request's reservation, the second request
    must wait (not fail) and complete after the first releases."""
    # 6 tokens @ ps=8 -> 1 page; pool has 2 usable pages; each request
    # reserves 2 pages (2 + 4 tokens... make it explicit:
    eng = _make_paged(n_slots=4, max_seq_len=64, page_size=8,
                      n_pages=3)  # 2 usable + garbage
    try:
        r1 = eng.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=8,
                        temperature=0.0)  # 16 tokens -> 2 pages (all of them)
        r2 = eng.submit([9, 10], max_new_tokens=4,
                        temperature=0.0)  # 6 tokens -> 1 page: must wait
        out1 = r1.result(timeout_s=120)
        out2 = r2.result(timeout_s=120)
        assert len(out1) == 8 and len(out2) == 4
        # waiting was observed (metric is best-effort; ordering is the test)
        assert r2.finished_at >= r1.finished_at
    finally:
        eng.stop()


def test_paged_submit_rejects_impossible_reservation():
    """A request that could NEVER fit the pool is rejected at submit —
    deferring it would head-of-line-block all later admission forever."""
    eng = _make_paged(n_slots=2, max_seq_len=64, page_size=8, n_pages=3)
    try:
        with pytest.raises(ValueError, match="pool has only 2 usable"):
            eng.submit(list(range(1, 20)), max_new_tokens=32)  # 7 pages
        # a fitting request still serves
        assert len(eng.generate([1, 2], max_new_tokens=3)) == 3
    finally:
        eng.stop()


def test_paged_engine_span_and_budget_plan():
    """submit(span=) carries the trace surface, and a budget makes a plan
    whose only transient is the widest prefill's."""
    from gofr_tpu.tracing import InMemoryExporter, Tracer

    tracer = Tracer(exporter=InMemoryExporter())
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64, page_size=8,
                         prefill_buckets=(8, 16), logger=MockLogger(),
                         tracer=tracer, budget_bytes=64 << 20)
    eng.start()
    try:
        assert eng.plan is not None and eng.plan.peak_bytes == (
            eng.plan.params_bytes + eng.plan.cache_bytes_max
            + eng.plan.prefill_temp_bytes)
        span = tracer.start_span("req")
        out = eng.submit([1, 2, 3], max_new_tokens=4, span=span).result(
            timeout_s=60)
        assert len(out) == 4
        assert span.attributes["tpu.prefill_bucket"] == 8
        assert "batch.id" in span.attributes
    finally:
        eng.stop()


def test_paged_explicit_pool_must_fit_budget():
    """An explicit n_pages bypasses the plan's sizing; the constructor must
    still reject a pool that cannot fit the budget."""
    params = llama_init(CFG, seed=0)
    with pytest.raises(ValueError, match="does not fit the budget"):
        PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64, page_size=8,
                       n_pages=100_000, logger=MockLogger(),
                       budget_bytes=32 << 20)


def test_paged_engine_with_tp_mesh():
    """The pool is a STACKED array; mesh placement must shard its KV-head
    axis whole, not iterate it into per-layer slices."""
    from gofr_tpu.parallel import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(tp=2), devices=jax.devices()[:2])
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64, page_size=8,
                         prefill_buckets=(8,), mesh=mesh, logger=MockLogger())
    eng.start()
    try:
        assert hasattr(eng.k_cache, "shape")  # still one stacked array
        shard = eng.k_cache.sharding.shard_shape(eng.k_cache.shape)
        assert shard[2] == CFG.n_kv_heads // 2
        assert eng.pool_bytes() > 0
        out = eng.generate([1, 2, 3], max_new_tokens=4, temperature=0.0)
        assert len(out) == 4
    finally:
        eng.stop()


def test_paged_engine_streaming_and_stop_tokens():
    eng = _make_paged()
    try:
        req = eng.submit([1, 2, 3], max_new_tokens=16, temperature=0.0)
        toks = list(req.stream(timeout_s=60))
        assert len(toks) == 16
        want = eng.generate([1, 2, 3], max_new_tokens=16, temperature=0.0)
        assert toks == want
        stop = eng.generate([1, 2, 3], max_new_tokens=16, temperature=0.0,
                            stop_tokens={want[2]})
        assert stop == want[:3]
    finally:
        eng.stop()


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_paged_q8_engine_matches_paged_fp_closely():
    """INT8 paged pool: prefill is full-precision into the quantized splice
    (first token exact vs the fp paged engine); decode reads dequant-folded
    pages — near-ties may flip, bulk must agree, and pages must free."""
    import dataclasses

    cfg_q8 = dataclasses.replace(CFG, kv_dtype="int8")
    prompts = [[1, 2, 3, 4, 5], list(range(7, 40)), [9]]

    def serve(use_cfg):
        params = llama_init(CFG, seed=0)
        eng = PagedLLMEngine(params, use_cfg, page_size=16, n_slots=4,
                             max_seq_len=128, prefill_buckets=(8, 64),
                             decode_block_size=4)
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=10, temperature=0.0)
                    for p in prompts]
            outs = [r.result(timeout_s=300) for r in reqs]
            import time as _t
            deadline = _t.time() + 10
            while eng.allocator.used_pages and _t.time() < deadline:
                _t.sleep(0.02)
            assert eng.allocator.used_pages == 0, "pages leaked"
            return outs
        finally:
            eng.stop()

    fp = serve(CFG)
    q8 = serve(cfg_q8)
    assert [len(t) for t in q8] == [len(t) for t in fp]
    for f, q in zip(fp, q8):
        assert f[0] == q[0]          # full-precision prefill: exact
    total = sum(len(t) for t in fp)
    agree = sum(a == b for f, q in zip(fp, q8) for a, b in zip(f, q))
    # What int8 KV dequant actually guarantees: per-(vector, axis) scales
    # bound the cache quantization error at ~0.4% of each vector's max
    # (|x - dq(x)| <= scale/2, scale = max|x|/127), which perturbs logits
    # only slightly — but on this random-weight debug model the top-2
    # logit gap is often inside that perturbation, and ONE flipped
    # near-tie argmax changes the whole autoregressive suffix for that
    # stream (divergence compounds; agreement below is positional). So
    # the hard guarantees are structural — exact first token (prefill is
    # full precision), equal lengths, bitwise determinism — and the bulk
    # agreement bound must tolerate one early flip per stream: >30%
    # catches a broken dequant path (near-zero agreement) without flaking
    # on a legitimate near-tie flip.
    assert agree / total > 0.3, f"only {agree}/{total} agree"
    assert q8 == serve(cfg_q8)       # deterministic


def test_the_int8_engine_counts_its_reads_a_token_longer_each_step(
        monkeypatch):
    """`paging.read` where there is no block's tail: step t of a block
    attends what the block found and the t + 1 tokens written since, all
    in pages. Six blocks of 4 over tiny pages under a table of 16, a fold
    given the weight of 8 of them: the row grows from 4 pages to 10, so
    its last fold is computed at 4 pages, then at 8, then (a second fold
    of one page, then two) at 2, counted here a step at a time."""
    import dataclasses

    engine = PagedLLMEngine(
        llama_init(CFG, seed=0), dataclasses.replace(CFG, kv_dtype="int8"),
        page_size=16, n_slots=4, max_seq_len=256, prefill_buckets=(8, 64),
        decode_block_size=4)
    _folding(monkeypatch, [x[0] for x in (engine.k_cache, engine.v_cache,
                                          engine.k_scale, engine.v_scale)],
             8)
    folds = narrowed = tokens = lanes = 0
    for found in (60, 63, 112, 126, 130, 150):
        engine.slots[2].length = found
        engine._note_page_reads([(2, None)], 4, 16)
        for attended in range(found + 1, found + 5):
            pages = -(-attended // 16)
            last = pages % 8 or 8
            width = min(w for w in (2, 4, 8) if w >= last)
            folds += -(-pages // 8)
            narrowed += width < 8
            tokens += attended
            lanes += (pages - last + width) * 16
    assert narrowed == 4 + 1 + 0 + 2 + 4 + 4 and folds == 3 * 4 + 6 + 2 * 8
    read = engine.paging_snapshot()["read"]
    assert read == {"pages_per_fold": 8, "folds": CFG.n_layers * folds,
                    "narrowed_folds": CFG.n_layers * narrowed,
                    "fold_live_share": round(tokens / lanes, 4)}


def test_quantize_kv_roundtrip_error_bounded():
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(2, 3, 16, 8)) * 5, dtype=jnp.float32)
    q8, scale = quantize_kv(x)
    restored = q8.astype(jnp.float32) * scale[:, :, None, :]
    err = np.max(np.abs(np.asarray(restored - x)))
    amax = np.max(np.abs(np.asarray(x)), axis=2)
    assert err <= np.max(amax) / 127.0 + 1e-6


@pytest.mark.parametrize("lengths", ["ragged", "ps"])
@pytest.mark.parametrize("geometry", list(EDGE_GEOMETRY))
def test_paged_attention_int8_matches_reference(geometry, lengths):
    """Ragged rows (none, one token, a page less one, a page, a page and
    one, the whole table) and every row ending at its page's end."""
    q, k, v, table, lens = _paged_case(geometry, jnp.float32,
                                       ROW_LENGTHS[lengths], seed=5)
    k8, ks = quantize_kv(k)     # axis=-2 (dh) -> scales [P, Hkv, ps]
    v8, vs = quantize_kv(v)
    ref = paged_attention_reference(q, k8, v8, table, lens, ks, vs)
    out = paged_attention(q, k8, v8, table, lens, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)
    # close to the full-precision read too
    exact = paged_attention_reference(q, k, v, table, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exact),
                               rtol=0.15, atol=0.15)
    # and the same through a stack, whose scale pools ride with it
    last = N_LAYERS - 1
    stacked = paged_attention(q, _in_layer(k8, last), _in_layer(v8, last),
                              table, lens, _in_layer(ks, last),
                              _in_layer(vs, last), layer=jnp.int32(last))
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(out))


def test_paged_priority_no_head_of_line_inversion():
    """A small high-priority request must admit while a big low-priority
    request stays parked on page exhaustion — and the parked one still
    completes once pages free (no starvation)."""
    params = llama_init(CFG, seed=0)
    # tiny pool: 1 garbage + 6 usable pages of 8 tokens
    eng = PagedLLMEngine(params, CFG, page_size=8, n_pages=7, n_slots=2,
                         max_seq_len=64, prefill_buckets=(8, 32),
                         decode_block_size=2)
    eng.start()
    try:
        # occupy most of the pool: 30 prompt + 10 new = 5 pages
        hog = eng.submit(list(range(1, 31)), max_new_tokens=10,
                         temperature=0.0)
        deadline = time.time() + 60
        while hog.admitted_at is None and time.time() < deadline:
            time.sleep(0.005)
        # big low-priority: needs 5 pages -> parks (1 free page)
        big_low = eng.submit(list(range(1, 29)), max_new_tokens=10,
                             temperature=0.0, priority=5)
        time.sleep(0.3)
        assert big_low.admitted_at is None, "should be parked on pages"
        # small high-priority: needs 1 page -> must NOT wait behind big_low
        small_high = eng.submit([7, 7], max_new_tokens=4, temperature=0.0,
                                priority=0)
        out = small_high.result(timeout_s=120)
        assert len(out) == 4
        assert big_low.admitted_at is None or \
            small_high.admitted_at <= big_low.admitted_at
        # and the parked request eventually runs to completion
        assert len(big_low.result(timeout_s=120)) == 10
    finally:
        eng.stop()
