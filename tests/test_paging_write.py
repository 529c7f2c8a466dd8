"""The paged writes (ops/paged_attention.py): a decode block's flush
against the per-token column writes it replaced, and the prefill and
per-token write kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from paging_cases import (TAIL_IDLE, _tail_case, _tail_of,
                          _written_by_columns)

from gofr_tpu.ops.paged_attention import (paged_flush_block,
                                          paged_write_decode,
                                          paged_write_prefill)


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
