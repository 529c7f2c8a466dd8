"""The paged read (ops/paged_attention.py): the kernel against its
reference at every edge of its walk, the folds, the read over pages and a
decode block's tail, and the int8 pools' read."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from paging_cases import (EDGE_GEOMETRY, FOLD_CASES, FOLD_GEOMETRY,
                          FOLD_ROWS, FOLD_TABLE, GEOMETRY, N_LAYERS, PS,
                          RAGGED, ROW_LENGTHS, TAIL_GEOMETRY, TAIL_IDLE,
                          _dead_pages, _fold_edges, _folding, _in_layer,
                          _paged_case, _tail_case, _tail_of,
                          _written_by_columns)

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.ops.paged_attention import (block_tail, fold_branch, fold_of,
                                          fold_widths, pages_per_fold,
                                          paged_attention,
                                          paged_attention_in_block,
                                          paged_attention_reference,
                                          quantize_kv)

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
