"""The paged read (ops/paged_attention.py): the kernel against its
reference at every edge of its walk, the folds, the read over pages and a
decode block's tail, and the int8 pools' read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from paging_cases import (EDGE_GEOMETRY, FOLD_CASES, FOLD_GEOMETRY,
                          FOLD_ROWS, FOLD_TABLE, GEOMETRY, GROUP_BLOCK,
                          GROUP_FOLD, GROUP_GEOMETRY, GROUP_PAGES, N_LAYERS,
                          PS, RAGGED, ROW_LENGTHS, TAIL_GEOMETRY, TAIL_IDLE,
                          _dead_pages, _fold_edges, _folding, _group_case,
                          _group_lengths, _group_tails, _group_written,
                          _grouping, _in_layer, _paged_case, _tail_case,
                          _tail_of, _token_put, _written_by_columns)

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.ops import paged_attention as paged_attention_module
from gofr_tpu.ops import sparse_attention as sparse
from gofr_tpu.ops.mla_read import mla_read, mla_read_reference
from gofr_tpu.ops.paged_attention import (block_tail, fold_branch, fold_of,
                                          fold_widths, group_of,
                                          pages_per_fold, paged_attention,
                                          paged_attention_in_block,
                                          paged_attention_reference,
                                          plane_tail, quantize_kv,
                                          rows_a_step, tail_put)

CFG = LlamaConfig.debug()

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
    """The kernel holds TWO choices among a fold's widths, the short
    rows' step's and the walk's after its loop, both outside the loop over
    a row's full folds (a full fold's turn is computed at C pages and
    branches on no width), and none at folds of one page."""
    q, k, v, table, lens = _paged_case(
        "Hkv2", jnp.float32, _fold_edges(c, PS), n_table=FOLD_TABLE)
    _folding(monkeypatch, (k, v), c)
    jaxpr = jax.make_jaxpr(lambda *a: paged_attention(*a, interpret=True))(
        q, k, v, table, lens).jaxpr
    assert _width_choices(jaxpr) == ([(len(fold_widths(c)), False)] * 2
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


# -- several short rows a grid step -------------------------------------------
def test_the_rows_a_step_are_worked_out_from_what_a_call_sees():
    """`rows_a_step`: the bytes of a fold over the call's pools and the
    copies that bring it, against what 2 R + 1 buffers may take, and the
    call's rows, which R divides. The benchmark's shapes, rows that
    nothing divides, and a fold too heavy or of too many copies for more
    than a row."""
    latent = 8 * 576 * 128 * 2                  # a fold of 8 latent pages
    internlm2 = 2 * 2 * 8 * 128 * 128 * 2       # of 2 pages of K and V
    for rows in (96, 128, 32, 256, 64):
        assert rows_a_step(latent, 8, rows) == 4
        assert rows_a_step(internlm2, 4, rows) == 4
    assert rows_a_step(latent, 8, 97) == 1      # a prime: every row alone
    assert rows_a_step(latent, 8, 9) == 3 and rows_a_step(latent, 8, 6) == 3
    assert rows_a_step(1, 1, 12) == 6 and rows_a_step(1, 1, 16) == 8
    for fold_bytes in (1, latent, internlm2, 3 << 20, 5 << 20):
        for copies in (1, 8, 64, 200):
            for rows in (1, 7, 12, 96):
                r = rows_a_step(fold_bytes, copies, rows)
                assert rows % r == 0 and 1 <= r <= 8
                assert r == 1 or ((2 * r + 1) * fold_bytes <= 11 << 20
                                  and (2 * r + 1) * copies <= 384)
    # from the pools themselves, as `fold_of` has it
    pool = jnp.zeros((2, 5, 1, 576, 128), jnp.bfloat16)
    assert fold_of([pool], 16) == 8 and group_of([pool], 16, 96) == 4
    assert group_of([pool], 16, 50) == 2


GROUP_CASES = (
    [("latent", rows, t) for rows in GROUP_PAGES for t in (0, 7, 15)]
    + [("Hkv2xG16", rows, 7) for rows in GROUP_PAGES]
    + [("Hkv8xG2", rows, t) for rows in ("mixed", "dead-inside", "one-long")
       for t in (0, 15)])


def _read_a_group_case(geometry, q, pools, tails, table, news, starts, live,
                       t, layer):
    """(the read's output, the tails it returns) at step t of a block."""
    paged, counts = jnp.where(live, starts, 0), jnp.where(live, t + 1, 0)
    if geometry == "latent":
        out, tail = jax.jit(lambda *a: mla_read(
            *a, value_width=GROUP_GEOMETRY[geometry][3], scale=0.07,
            layer=jnp.int32(layer)))(
                q, news[0][t], pools[0], tails[0], table, paged, counts)
        return out, [tail]
    out, *tails = jax.jit(lambda *a: paged_attention_in_block(
        *a, layer=jnp.int32(layer)))(
            q, news[0][t], news[1][t], *pools, *tails, table, paged, counts)
    return out, tails


@pytest.mark.parametrize("geometry,rows,t", GROUP_CASES)
def test_rows_walked_several_a_grid_step_match_reference(geometry, rows, t,
                                                         monkeypatch):
    """Step t of a block of 16 over the rows `GROUP_PAGES` names, four
    rows a grid step (three where four does not divide them) at folds of
    4 pages, every dead page NaN: against the reference on a pool that had
    the block's tokens written column by column; the tails come back with
    the step's token put and nothing else changed."""
    q, pools, table, news, starts, live = _group_case(geometry, rows, t,
                                                      seed=17)
    _folding(monkeypatch, [pool[0] for pool in pools], GROUP_FOLD)
    B, layer = q.shape[0], 1
    assert _grouping(monkeypatch, B) == (3 if rows == "ragged" else 4)
    assert group_of(pools, FOLD_TABLE, B) == _grouping(monkeypatch, B)
    written = _group_written(pools, news, table, starts, live, layer)
    after = jnp.where(live, starts + t + 1, 0)
    if geometry == "latent":
        want = mla_read_reference(q, written[0], table, after,
                                  value_width=GROUP_GEOMETRY[geometry][3],
                                  scale=0.07)
    else:
        want = paged_attention_reference(q, *written, table, after)
    # what the block found in pages: the pages past it are dead, the
    # block's own among them (its tokens wait in the tail)
    dead = _dead_pages(pools[0].shape[1], table, starts, PS)[
        None, :, None, None, None]
    tails = _group_tails(pools, news, layer, B)
    got, tails_out = _read_a_group_case(
        geometry, q, [jnp.where(dead, jnp.nan, pool) for pool in pools],
        tails, table, news, starts, live, t, layer)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    idle = ~np.asarray(live)
    assert not np.asarray(got)[idle].any()
    for tail_out, tail, new in zip(tails_out, tails, news):
        put = np.asarray(_token_put(tail, new[t], layer, t))
        np.testing.assert_array_equal(np.asarray(tail_out)[:, ~idle],
                                      put[:, ~idle])
        np.testing.assert_array_equal(np.asarray(tail_out)[:, idle],
                                      np.asarray(tail)[:, idle])


def test_rows_of_several_folds_walk_as_a_row_a_step_does(monkeypatch):
    """A table whose rows all hold several folds, and one with a single
    such row: a group that holds one walks row by row through the loop a
    row a step runs, so its rows' outputs are the same BITS at four rows a
    step, at one, and with the short rows' step taken out of the kernel;
    the rows of the groups that took that step are the same numbers."""
    for rows, walked in (("all-long", slice(None)), ("one-long", slice(4, 8))):
        q, pools, table, news, starts, live = _group_case(
            "Hkv2xG16", rows, 7, seed=23)
        _folding(monkeypatch, [pool[0] for pool in pools], GROUP_FOLD)
        tails = _group_tails(pools, news, 1, q.shape[0])
        outs = []
        for most, joined in ((4, True), (1, True), (4, False)):
            _grouping(monkeypatch, q.shape[0], most)
            monkeypatch.setattr(paged_attention_module, "_JOIN_ONE_FOLD",
                                joined)
            outs.append(np.asarray(_read_a_group_case(
                "Hkv2xG16", q, pools, tails, table, news, starts, live, 7,
                1)[0]))
        # (at one row a step a row of one fold is a group of its own)
        for other in outs[1 if rows == "all-long" else 2:]:
            np.testing.assert_array_equal(outs[0][walked], other[walked])
        for other in outs[1:]:
            np.testing.assert_allclose(outs[0], other, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("rows", ["mixed", "dead-inside", "one-long",
                                  "all-dead", "ragged"])
def test_rows_walked_several_a_grid_step_over_int8_pools(rows, monkeypatch):
    """The same rows over int8 pools (no tail: the step reads pages
    alone), every dead page's scales NaN."""
    q, k, v, table, lens = _paged_case(
        "Hkv2", jnp.float32, [int(n) for n in _group_lengths(rows, 19)],
        seed=19, n_table=FOLD_TABLE)
    (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
    dead = _dead_pages(k.shape[0], table, lens, PS)[:, None, None]
    given = [k, v] + [jnp.where(dead, jnp.nan, x) for x in (ks, vs)]
    _folding(monkeypatch, given, GROUP_FOLD)
    assert _grouping(monkeypatch, q.shape[0]) in (3, 4)
    ref = paged_attention_reference(q, k, v, table, lens, ks, vs)
    out = np.asarray(jax.jit(lambda *a: paged_attention(*a))(
        q, k, v, table, lens, *given[2:]))
    np.testing.assert_allclose(out, np.asarray(ref), rtol=5e-2, atol=5e-2)
    assert not out[np.asarray(lens) == 0].any()


RING_LENGTHS = {
    # every context inside the window: the bound is 0 or in an early page
    "inside": [5, 30, 12, 0, 24, 17, 9, 26, 31, 0, 20, 3],
    # contexts the window has left behind: the walk starts pages in
    "past": [100, 64, 41, 0, 77, 50, 33, 200, 90, 58, 0, 129]}


@pytest.mark.parametrize("t", [0, 7, 15])
@pytest.mark.parametrize("lengths,c", [("inside", 4), ("past", 4),
                                       ("inside", 2)])
def test_rows_walked_several_a_grid_step_through_a_ring(lengths, c, t,
                                                        monkeypatch):
    """A window of 24 tokens over a ring of 5 pages of 8, four rows a grid
    step, step t of a block of 16: each row attends (p - 24, p] of its own
    history, through the pages the ring still holds and the tail. At folds
    of 4 pages every row is one fold; at folds of 2 the rows of three and
    four pages walk in groups of their own."""
    W, ring, T, Hkv, G, dh = 24, 5, GROUP_BLOCK, 2, 4, 16
    lens = np.asarray(RING_LENGTHS[lengths])
    B = len(lens)
    rng = np.random.default_rng(29)
    K, V = (rng.normal(size=(B, lens.max() + T, Hkv, dh)).astype(np.float32)
            for _ in range(2))
    table = np.zeros((B, ring), np.int32)
    k_pool, v_pool = (np.full((2, 1 + B * ring, Hkv, dh, PS), 99.0,
                              np.float32) for _ in range(2))
    for b in np.flatnonzero(lens):
        table[b] = 1 + b * ring + rng.permutation(ring)
        pages = -(-lens[b] // PS)
        for j in range(max(0, pages - ring), pages):
            n = min(PS, lens[b] - j * PS)
            for pool, X in ((k_pool, K), (v_pool, V)):
                pool[1, table[b, j % ring], :, :, :n] = X[
                    b, j * PS:j * PS + n].transpose(1, 2, 0)
    k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)
    _folding(monkeypatch, (k_pool[0], v_pool[0]), c)
    monkeypatch.setattr(paged_attention_module, "pages_per_fold",
                        lambda *_: c)
    assert _grouping(monkeypatch, B) == 4
    live = lens > 0
    at = np.arange(B)[:, None], lens[:, None] + np.arange(t)[None, :]
    tails = [plane_tail(k_pool, B, T).at[1, :, :, :t, :dh].set(
        jnp.asarray(X[at].transpose(0, 2, 1, 3))) for X in (K, V)]
    q = rng.normal(size=(B, Hkv * G, dh)).astype(np.float32)
    out, _, _ = jax.jit(lambda *a: paged_attention_in_block(
        *a, layer=jnp.int32(1), window=W, ring=ring))(
            jnp.asarray(q), jnp.asarray(K[np.arange(B), lens + t]),
            jnp.asarray(V[np.arange(B), lens + t]), k_pool, v_pool, *tails,
            jnp.asarray(table), jnp.asarray(np.where(live, lens, 0),
                                            jnp.int32),
            jnp.asarray(np.where(live, t + 1, 0), jnp.int32))
    out = np.asarray(out)
    assert not out[~live].any()
    for b in np.flatnonzero(live):
        p = lens[b] + t
        lo = max(0, p - W + 1)
        s = np.einsum("hgd,shd->hgs", q[b].reshape(Hkv, G, dh),
                      K[b, lo:p + 1]) / np.sqrt(dh)
        pr = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hgs,shd->hgd", pr / pr.sum(-1, keepdims=True),
                         V[b, lo:p + 1])
        np.testing.assert_allclose(out[b].reshape(Hkv, G, dh), want,
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [0, 7])
@pytest.mark.parametrize("seed", [7, 8])
def test_rows_walked_several_a_grid_step_over_chosen_blocks(seed, t,
                                                            monkeypatch):
    """`sparse_read`: each (row, KV head) is a row of the kernel (12 of
    them, four a grid step), its table the list of the pages that hold a
    block it chose, at folds of 4 listed pages: lists of one fold and of
    two, a row that holds no request, step t of a block."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 12))
    B, H, Hkv, dh, ps, bs, NP, P, layer = 6, 4, 2, 16, 16, 8, 8, 60, 1
    k_pool, v_pool = (jax.random.normal(next(keys), (2, P, Hkv, dh, ps))
                      for _ in range(2))
    monkeypatch.setattr(paged_attention_module, "_FOLD_BYTES",
                        GROUP_FOLD * 2 * dh * ps * 4)
    assert _grouping(monkeypatch, B * Hkv) == 4
    table = np.random.default_rng(seed).permutation(
        np.arange(1, P))[:B * NP].reshape(B, NP).astype(np.int32)
    table[2] = 0                                  # holds no request
    table = jnp.asarray(table)
    lengths = jnp.asarray([100, 37, 0, 16, 128, 9], jnp.int32)
    tail_lens = jnp.where(lengths > 0, t + 1, 0).astype(jnp.int32)
    k_tail, v_tail = (jnp.zeros_like(plane_tail(k_pool, B, 8)).at[
        ..., :dh].set(jax.random.normal(next(keys), (2, B, Hkv, 16, dh)))
        for _ in range(2))
    q = jax.random.normal(next(keys), (B, H, dh))
    k, v = (jax.random.normal(next(keys), (B, Hkv, dh)) for _ in range(2))
    n_blocks = NP * ps // bs
    block = jnp.arange(n_blocks)[None, None, :]
    chosen = jax.random.bernoulli(next(keys), 0.5, (B, Hkv, n_blocks))
    own = ((lengths - 1) // bs)[:, None, None]
    chosen = jnp.logical_or(jnp.logical_or(chosen, block == 0),
                            block >= own - 1)
    chosen = jnp.logical_and(chosen, block * bs < lengths[:, None, None])
    put = tail_put(k_tail, v_tail, k, v, layer, t)
    want = sparse.sparse_read_reference(
        q, k_pool, v_pool, *put, table, chosen, lengths, tail_lens,
        layer=layer, block_size=bs)
    pages, bits, held = sparse.page_lists(chosen, table, lengths, ps, bs, NP)
    got, k_out, v_out = sparse.sparse_read(
        q, k, v, k_pool, v_pool, k_tail, v_tail, pages, bits, held,
        tail_lens, layer=layer, block_size=bs, interpret=True)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~live].any()
    for mine, theirs in ((k_out, put[0]), (v_out, put[1])):
        np.testing.assert_array_equal(
            np.asarray(mine[layer, live, ..., :dh]),
            np.asarray(theirs[layer, live, ..., :dh]))
