"""Sparse experts on the serving path: the matmuls over the experts HELD.

An expert layer routes every token over all E experts and this chip
holds a range of them. What a chip computes is its own experts' part of
each token's weighted sum. An expert comes in two forms, and which one is
a property of the family's weights, not a setting: an up and a down matrix
(nemotron_h: down(relu(up x)^2)), or with a gate matrix beside the up
(mla_moe: down(silu(gate x) * up x), three matrices an expert). `wg=None`
is the first; the gate is held as the up is, [held, F, D].

The up matrices are held [held, F, D], out by in as the checkpoint stores a
linear layer, and the kernel contracts the minor dims (x W1^T, the MXU's
native transposed right-hand side). Held [held, D, F] the chip would lay
them out with D minor anyway (F = 1856 is no multiple of 128 lanes, and the
compiler prefers the layout without padding for a parameter), and every
call of the kernel, which takes its operands row-major, would first copy
all of them: 630 MB a block a step (compile-only, PR 27).

One kernel serves both phases, as a list of STEPS: step s multiplies one
block of `tm` rows by one expert's two matrices and adds the result,
weighted a row, into that row block's output,

    out[rows[s]] (+)= w[wsel[s]] * W2[e[s]] relu(x[rows[s]] W1[e[s]])^2
    (gated: ... W2[e[s]] (silu(x[rows[s]] Wg[e[s]]) * x[rows[s]] W1[e[s]]))

with e, rows and wsel scalar-prefetched, so each step's blocks are fetched
by index and a step whose expert is the one before it does not fetch the
matrices again. Steps past `n_steps` do nothing and are mapped by the
caller onto the last live step's blocks, so they move nothing either.

- DECODE (`decode_experts`): the row block is the whole batch, a step an
  expert that a live row picked, the weights zero where a row did not pick
  it. At <= ~240 rows reading every touched expert's matrices once is what
  bounds the layer (96 rows x 64 experts is 0.12 TFLOP against 1.3 GB), so
  gathering rows by expert would buy nothing; experts no live row picked
  are not read.
- PREFILL (`prefill_experts`): the (token, pick) pairs that fell on held
  experts are sorted by expert into blocks of `tm` rows, a block one
  expert's, so a token meets only the experts it picked: a grouped product,
  not every token through every expert (64/3 of the flops). The layout is
  sized by the pairs the experts HELD can expect, not by every pair: a
  PIECE is `piece_blocks` row blocks, `T k held / total` pairs with a
  fixed margin and a ragged end an expert (a chip with 32 of 256 experts
  lays out 72 blocks for a window of 4,096 x 8 where every pair would
  need 288; a chip that holds every expert gets what every pair needs). A
  call walks the sorted held pairs a piece at a time, in one loop with as
  many turns as the pairs that did fall here need: a gather of the piece's
  rows, one call of the kernel on a grid of the piece's blocks, its rows
  added onto their tokens in float32 (where a window has fewer pairs than
  a piece has rows, many experts and few tokens, the piece's pairs go
  back, each from the row it sits in). One turn where the routing is near
  uniform, about `total / held` where every pair is held, none where none
  is: no pair is dropped whatever the routing, and there is no capacity to
  set.

A block is a TILE of an expert's width: `tile` of its F rows of each
matrix, [tile, D], and the grid is (steps, F / tile) with a step's output
accumulated over its tiles (the hidden activation is elementwise over F,
so a tile's up and gate products meet only that tile's rows of the down
matrix). `width_tile` takes the whole of F wherever an expert's matrices,
double-buffered, fit the kernel's VMEM: two of 10 MB at 1856 x 2688 in
bfloat16 (nemotron_h), three of 3.1 MB at 768 x 2048 (mla_moe): one tile, a
grid of steps alone, the programs they were. Three of 18.9 MB at 3072 x
3072 (afmoe) do not fit twice over and go in two tiles of 1536. Odd steps
walk the tiles backwards, so two steps of one expert share the tile between
them and a prefill's second row block of an expert fetches one tile, not
two.

`experts_reference` is the numerics oracle for both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .scopes import kernel_scope

# what the kernel asks of VMEM (a v5e has 128 MiB): a tile of each of an
# expert's matrices twice (double-buffered), the row block in and its
# float32 output
VMEM_LIMIT = 100 * 1024 * 1024
# the share of it the matrices' tiles may take
_MATRIX_BYTES = VMEM_LIMIT * 4 // 5


def width_tile(F: int, D: int, matrices: int, itemsize: int) -> int:
    """The rows of an expert's width a block holds: all F where the
    `matrices` [F, D] matrices fit `_MATRIX_BYTES` double-buffered, else
    the largest divisor of F in whole 128-row tiles that does."""
    def fits(tile):
        return 2 * matrices * tile * D * itemsize <= _MATRIX_BYTES

    if fits(F):
        return F
    for parts in range(2, F // 128 + 1):
        if F % (parts * 128) == 0 and fits(F // parts):
            return F // parts
    raise ValueError(f"no tile of an expert's width {F} x {D} fits VMEM")


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def _hidden(up, gate):
    """An expert's hidden activation from its up product (and its gate
    product, where the family's experts are gated), float32."""
    return relu2(up) if gate is None else jax.nn.silu(gate) * up


def experts_reference(x, w1, w2, combine, wg=None):
    """x [T, D]; w1, w2[, wg] [held, F, D]; combine [T, held] float32
    (zero where a token did not pick the expert). Every token through
    every expert: [T, D] float32."""
    def product(w):
        return jnp.einsum("td,efd->etf", x, w,
                          preferred_element_type=jnp.float32)

    h = _hidden(product(w1), None if wg is None else product(wg))
    y = jnp.einsum("etf,efd->etd", h.astype(x.dtype), w2,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", y, combine)


def _kernel(e_ref, rows_ref, wsel_ref, n_ref, x_ref, w1_ref, *refs,
            tiled: bool = False):
    from jax.experimental import pallas as pl

    # refs: [the gate matrix,] the down matrix, the weights, the output
    wg_ref = refs[0] if len(refs) == 4 else None
    w2_ref, w_ref, o_ref = refs[-3:]

    s = pl.program_id(0)
    fresh = jnp.logical_or(
        s == 0, rows_ref[s] != rows_ref[jnp.maximum(s - 1, 0)])
    if tiled:
        # a row block's first visit is its first step's first tile
        fresh = jnp.logical_and(fresh, pl.program_id(1) == 0)

    @pl.when(jnp.logical_and(s == 0, n_ref[0] == 0))
    def _nothing_routed():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(s < n_ref[0])
    def _step():
        x = x_ref[...]

        def product(w_ref):
            return jax.lax.dot_general(
                x, w_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        h = _hidden(product(w1_ref),
                    None if wg_ref is None else product(wg_ref))
        y = jnp.dot(h.astype(x.dtype), w2_ref[0],
                    preferred_element_type=jnp.float32) * w_ref[0]

        @pl.when(fresh)
        def _first_visit():
            o_ref[...] = y

        @pl.when(jnp.logical_not(fresh))
        def _again():
            o_ref[...] += y


def expert_steps(x, w1, w2, weights, experts, rows, wsel, n_steps, tm: int,
                 *, wg=None, interpret=None):
    """The kernel. x [R * tm, D]; weights [Wn, tm, 1] float32; experts,
    rows, wsel [S] int32 (row blocks in non-decreasing visiting order);
    n_steps int32 scalar. Returns [R * tm, D] float32; a row block that no
    live step names is left unwritten. A block holds `width_tile`'s rows
    of an expert's width."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (T, D), F = x.shape, w1.shape[1]
    S = experts.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # steps past the last live one stay on its blocks: nothing moves
    at = jnp.minimum(jnp.arange(S), jnp.maximum(n_steps - 1, 0))
    experts, rows, wsel = (a.astype(jnp.int32)[at]
                           for a in (experts, rows, wsel))
    matrices = [w1, w2] if wg is None else [w1, wg, w2]
    tile = width_tile(F, D, len(matrices), w1.dtype.itemsize)
    tiles = F // tile
    if tiles == 1:
        # the whole width a block: a grid of steps alone
        grid, kernel = (S,), _kernel
        matrix = pl.BlockSpec((1, F, D), lambda s, e, r, w, n: (e[s], 0, 0))

        def by_step(of):
            return lambda s, e, r, w, n: of(s, e, r, w)
    else:
        grid, kernel = (S, tiles), functools.partial(_kernel, tiled=True)
        # odd steps walk the tiles backwards: the tile two steps of one
        # expert meet at is fetched once. A step past the last live one
        # stays on the tile that one ended at
        def tile_of(s, f, n):
            def walked(step, f):
                return jnp.where(step % 2 == 1, tiles - 1 - f, f)

            return jnp.where(s < n[0], walked(s, f),
                             walked(jnp.maximum(n[0] - 1, 0), tiles - 1))

        matrix = pl.BlockSpec(
            (1, tile, D),
            lambda s, f, e, r, w, n: (e[s], tile_of(s, f, n), 0))

        def by_step(of):
            return lambda s, f, e, r, w, n: of(s, e, r, w)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,                   # experts, rows, wsel, n
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, D), by_step(lambda s, e, r, w: (r[s], 0))),
            *[matrix] * len(matrices),
            pl.BlockSpec((1, tm, 1),
                         by_step(lambda s, e, r, w: (w[s], 0, 0)))],
        out_specs=pl.BlockSpec((tm, D),
                               by_step(lambda s, e, r, w: (r[s], 0))),
    )
    with kernel_scope("moe_experts"):
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((T, D), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * len(grid),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(experts, rows, wsel, jnp.reshape(n_steps, (1,)).astype(jnp.int32),
          x, *matrices, weights)


def decode_experts(x, w1, w2, combine, *, wg=None, interpret=None):
    """One decode step. x [B, D]; combine [B, held] float32, zero for a
    pick that is not held and for every pick of a row that holds no
    request. Experts nobody picked are neither read nor computed."""
    held = w1.shape[0]
    touched = jnp.any(combine != 0.0, axis=0)                    # [held]
    order = jnp.argsort(jnp.logical_not(touched), stable=True)
    zeros = jnp.zeros((held,), jnp.int32)
    return expert_steps(x, w1, w2, combine.T[:, :, None], order, zeros,
                        order, jnp.sum(touched), x.shape[0], wg=wg,
                        interpret=interpret)


# the room a piece keeps over the pairs uniform routing sends here: a chip
# whose experts draw 5/4 of the mean still walks one piece (the ragged ends
# are sized at a block an expert and fill half of one on average, which is
# as much room again: tools/bench_prefill_experts.py, PERF.md section 5)
_PIECE_MARGIN = (5, 4)


def piece_blocks(T: int, k: int, held: int, total: int, tm: int) -> int:
    """The row blocks of `tm` a prefill's grouped product lays out at a
    time, from the shapes alone: the (token, pick) pairs expected on
    `held` of `total` experts with `_PIECE_MARGIN`'s room, and a ragged
    end a held expert; never more than every pair needs (which is what
    a chip that holds every expert gets)."""
    over, under = _PIECE_MARGIN
    expected = -(-T * k * held * over // (total * under * tm))
    return min(expected, -(-T * k // tm)) + held


def prefill_experts(x, w1, w2, picks, pick_weights, lo: int, total: int,
                    tm: int = 128, *, wg=None, interpret=None):
    """A prefill window. x [T, D]; picks [T, k] int32 expert ids over ALL
    the router's `total` experts; pick_weights [T, k] float32, zero for a
    token that is padding; the held experts are [lo, lo + held). Returns
    [T, D] float32: each token's sum over its picks that are held.

    The pairs that fell on held experts, sorted by expert, are walked a
    PIECE of `piece_blocks` row blocks at a time: their rows gathered,
    one `expert_steps` call, its rows (or its pairs, where the window has
    fewer of them) added onto their tokens. As many pieces as the pairs
    that did fall here need: one where the routing is near uniform,
    ~total / held where every pair is held, none where none is. No pair
    is dropped whatever the routing."""
    T, D = x.shape
    held, k = w1.shape[0], picks.shape[1]
    pairs, C = T * k, piece_blocks(T, k, held, total, tm)
    if (held + 1) * pairs >= 2 ** 31:
        raise ValueError(f"{held} experts x {pairs} (token, pick) pairs do "
                         f"not share an int32 sort key")
    local = jnp.logical_and(
        jnp.logical_and(picks >= lo, picks < lo + held), pick_weights != 0.0)
    key = jnp.where(local, picks - lo, held).reshape(-1)         # [T * k]
    # one sort of (expert, pair) in one int32 (no two alike, so no need
    # of a stable one), the weights riding along: the held pairs come
    # first, an expert's together, in token order
    by_expert, weight_sorted = jax.lax.sort(
        (key * pairs + jnp.arange(pairs, dtype=jnp.int32),
         pick_weights.reshape(-1)), num_keys=1, is_stable=False)
    token_sorted = by_expert % pairs // k
    experts = jnp.arange(held, dtype=jnp.int32)
    sizes = jnp.sum(key[:, None] == experts, axis=0, dtype=jnp.int32)
    blocks = (sizes + tm - 1) // tm                              # a group
    ends = jnp.cumsum(blocks)
    first_block, live = ends - blocks, ends[-1]
    group_ends = jnp.cumsum(sizes)
    first_sorted = group_ends - sizes
    # the way back costs a row what it costs live or dead (~0.1 us of a
    # v5e): no more rows go back than the window has pairs
    back = min(C * tm, pairs)
    steps = jnp.arange(C, dtype=jnp.int32)
    row = jnp.arange(tm, dtype=jnp.int32)

    def piece(carry):
        at, out = carry
        block = at * C + steps
        # a block knows its expert, the expert its first block and its
        # first sorted pair (tables of `held`, read by comparison)
        expert = jnp.minimum(
            jnp.sum(ends[None, :] <= block[:, None], axis=1), held - 1)
        mine = expert[:, None] == experts

        def of_expert(table):
            return jnp.sum(jnp.where(mine, table, 0), axis=1)

        before = (block - of_expert(first_block)) * tm   # rows of the group
        first = of_expert(first_sorted) + before
        n_rows = jnp.where(block < live, of_expert(sizes) - before, 0)
        real = (row < n_rows[:, None]).reshape(-1)               # [C * tm]
        sorted_at = (first[:, None] + row).reshape(-1)
        # a row past its group's end belongs to token T, which is nobody
        token = jnp.where(real, token_sorted[sorted_at], T)
        weight = jnp.where(real, weight_sorted[sorted_at], 0.0)
        y = expert_steps(
            x[jnp.minimum(token, T - 1)], w1, w2, weight.reshape(C, tm, 1),
            expert, steps, steps, jnp.minimum(live - at * C, C), tm, wg=wg,
            interpret=interpret)
        if back < C * tm:
            # fewer pairs than a piece has rows (many experts, few
            # tokens: ragged ends all): the piece's sorted pairs go
            # back, each from the row it sits in, not the piece's rows
            pair = first[0] + jnp.arange(back, dtype=jnp.int32)
            its = jnp.minimum(jnp.sum(
                group_ends[None, :] <= pair[:, None], axis=1), held - 1)
            its = its[:, None] == experts
            block_of = jnp.sum(jnp.where(its, first_block, 0), axis=1)
            sorted_of = jnp.sum(jnp.where(its, first_sorted, 0), axis=1)
            at_row = (block_of - at * C) * tm + pair - sorted_of
            here = jnp.logical_and(pair < group_ends[-1], at_row < C * tm)
            token = jnp.where(here, token_sorted[pair], T)
            y = y[jnp.minimum(at_row, C * tm - 1)]
        return at + 1, out.at[token].add(y, mode="drop")

    return jax.lax.while_loop(
        lambda carry: carry[0] * C < live, piece,
        (jnp.int32(0), jnp.zeros((T, D), jnp.float32)))[1]
