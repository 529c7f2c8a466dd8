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
  not every token through every expert (64/3 of the flops).

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


def prefill_experts(x, w1, w2, picks, pick_weights, lo: int, tm: int = 128,
                    *, wg=None, interpret=None):
    """A prefill window. x [T, D]; picks [T, k] int32 expert ids over ALL
    the router's experts; pick_weights [T, k] float32, zero for a token
    that is padding; the held experts are [lo, lo + held). Returns
    [T, D] float32: each token's sum over its picks that are held."""
    T, D = x.shape
    held, k = w1.shape[0], picks.shape[1]
    local = jnp.logical_and(
        jnp.logical_and(picks >= lo, picks < lo + held), pick_weights != 0.0)
    key = jnp.where(local, picks - lo, held).reshape(-1)         # [T * k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held]
    blocks = (sizes + tm - 1) // tm                              # a group
    first_block = jnp.cumsum(blocks) - blocks
    first_sorted = jnp.cumsum(sizes) - sizes
    n_blocks = -(-T * k // tm) + held          # the most the pairs can need
    # where each sorted pair sits: its group's first row + its rank there
    group = key[order]
    rank = jnp.arange(T * k) - first_sorted[jnp.minimum(group, held - 1)]
    dest = jnp.where(group < held,
                     first_block[jnp.minimum(group, held - 1)] * tm + rank,
                     n_blocks * tm)                              # dropped
    token = jnp.full((n_blocks * tm,), T, jnp.int32).at[dest].set(
        (order // k).astype(jnp.int32), mode="drop")
    weight = jnp.zeros((n_blocks * tm,), jnp.float32).at[dest].set(
        pick_weights.reshape(-1)[order], mode="drop")
    x_sorted = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])[token]
    block_expert = jnp.searchsorted(jnp.cumsum(blocks), jnp.arange(n_blocks),
                                    side="right")
    steps = jnp.arange(n_blocks, dtype=jnp.int32)
    y_sorted = expert_steps(
        x_sorted, w1, w2, weight.reshape(n_blocks, tm, 1),
        jnp.minimum(block_expert, held - 1), steps, steps, jnp.sum(blocks),
        tm, wg=wg, interpret=interpret)
    # back to tokens by gather: pair (t, j) reads its row, or nothing
    where = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.minimum(dest, n_blocks * tm - 1).astype(jnp.int32))
    rows = y_sorted[where].reshape(T, k, D)
    return jnp.sum(jnp.where(local[:, :, None], rows, 0.0), axis=1)
