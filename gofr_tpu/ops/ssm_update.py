"""Mamba-2 decode state update: one token a row, the state in place.

A Mamba-2 layer keeps, for every sequence, a recurrent state h of
[heads, P, N] float32 (2 MiB at 64 x 64 x 128): a SLOT's worth, fixed in
size, beside the page pool. One decode step reads it, decays it, adds the
new token's outer product and writes it back:

    h <- exp(dt A) h + (dt x) (x) B        y = h C            (a head)

so a step moves the whole state twice and the kernel is bound by that.

Layout. The engine holds the state STACKED over the model's Mamba-2 layers
and transposed, [layers, slots, N, heads * P]: the state index n rides the
sublanes and (head, p) the lanes. Then everything the update broadcasts is
in the layout it arrives in: `dt x` and the decay are rows over (head, p),
which broadcast down the sublanes; B and C of the head's GROUP are columns
over n, which broadcast along the lanes; y is a sum down the sublanes and
leaves as a row. [heads, P, N] would want x down the sublanes of each
head's tile: a relayout a head a row in the kernel, or an MXU outer
product of contraction 1.

Grid: (lane tiles, rows), sequential, the rows inner. A row that holds no
request is skipped: the scalar-prefetched `order` lists the live rows first
and maps every later step of a tile's pass onto the last live row's block,
which the pipeline neither fetches nor writes again while the block index
stands still (so the rows must be the inner axis). The state is aliased in
and out, so only live rows' blocks move; a dead row's state is whatever it
was or, with no live row at all, what the first block's buffer held: its
prefill rewrites it whole at admission.

`ssm_update_reference` is the numerics oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .scopes import kernel_scope

LANE_TILES = 2      # a block is [N, heads * P / 2] float32: 1 MiB at 128 x 2048


def live_rows_first(live):
    """live [S] bool -> (order [S] int32, n_live int32): the live rows
    first; every later step stays on the last live row, whose blocks the
    pipeline then leaves alone (ops/kda_update.py skips dead rows the same
    way)."""
    S = live.shape[0]
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n_live = jnp.sum(live).astype(jnp.int32)
    return order[jnp.minimum(jnp.arange(S), jnp.maximum(n_live - 1, 0))], n_live


def ssm_update_reference(state, layer, decay, xdt, B, C, live):
    """state [L, S, N, HP] float32; layer int; decay, xdt [S, HP] float32
    (exp(dt A) and dt x, a value a (head, p)); B, C [S, G, N] float32; live
    [S] bool. Returns (y [S, HP] float32, state): dead rows keep their
    state and give y = 0."""
    S, N, HP = state.shape[1:]
    G = B.shape[1]
    per_group = HP // G
    h = state[layer]                                           # [S, N, HP]
    Bl = jnp.repeat(jnp.swapaxes(B, 1, 2), per_group, axis=2)  # [S, N, HP]
    Cl = jnp.repeat(jnp.swapaxes(C, 1, 2), per_group, axis=2)
    new = decay[:, None, :] * h + Bl * xdt[:, None, :]
    y = jnp.sum(new * Cl, axis=1)
    keep = live[:, None, None]
    state = state.at[layer].set(jnp.where(keep, new, h))
    return jnp.where(live[:, None], y, 0.0), state


def _kernel(layer_ref, order_ref, n_live_ref, rows_ref, b_ref, c_ref, s_ref,
            y_ref, o_ref, *, groups: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < n_live_ref[0])
    def _live_row():
        width = s_ref.shape[-1] // groups
        for j in range(groups):                   # static: aligned lane spans
            span = slice(j * width, (j + 1) * width)
            decay = rows_ref[0, 0:1, span]                     # [1, width]
            xdt = rows_ref[0, 1:2, span]
            b = b_ref[0, 0, :, j:j + 1]                        # [N, 1]
            c = c_ref[0, 0, :, j:j + 1]
            new = (decay * s_ref[0, 0, :, span].astype(jnp.float32)
                   + b * xdt)                                  # [N, width]
            o_ref[0, 0, :, span] = new.astype(o_ref.dtype)
            y_ref[0, :, span] = jnp.sum(new * c, axis=0, keepdims=True)


def ssm_update(state, layer, decay, xdt, B, C, live, *, interpret=None):
    """One decode step of one Mamba-2 layer over every slot, in place.

    Shapes as `ssm_update_reference`; `layer` may be traced. Returns
    (y [S, HP] float32, state) with `state` the donated input updated at
    the live rows of `layer`; dead rows give y = 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, N, HP = state.shape
    G = B.shape[1]
    tiles = LANE_TILES if G % LANE_TILES == 0 else 1
    groups, width = G // tiles, HP // tiles
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    order, n_live = live_rows_first(live)
    # the two row operands share one 8-sublane tile; B and C arrive as
    # columns over n, a lane tile's groups side by side
    rows = jnp.zeros((S, 8, HP), jnp.float32)
    rows = rows.at[:, 0].set(decay).at[:, 1].set(xdt)

    def columns(m):                                # [S, G, N] -> [S, t, N, g]
        return jnp.swapaxes(m.reshape(S, tiles, groups, N), 2, 3)

    def row_tile(t, i, layer, order, n_live):
        return (order[i], 0, t)

    def column_tile(t, i, layer, order, n_live):
        return (order[i], t, 0, 0)

    def state_tile(t, i, layer, order, n_live):
        return (layer[0], order[i], 0, t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # layer, order, n_live
        grid=(tiles, S),
        in_specs=[pl.BlockSpec((1, 8, width), row_tile),
                  pl.BlockSpec((1, 1, N, groups), column_tile),
                  pl.BlockSpec((1, 1, N, groups), column_tile),
                  pl.BlockSpec((1, 1, N, width), state_tile)],
        out_specs=[pl.BlockSpec((1, 1, width), row_tile),
                   pl.BlockSpec((1, 1, N, width), state_tile)],
    )
    with kernel_scope("ssm_update"):
        y, state = pl.pallas_call(
            functools.partial(_kernel, groups=groups),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((S, 1, HP), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operand 6 (after the three scalars) is the state: in place
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(jnp.reshape(layer, (1,)).astype(jnp.int32), order,
          jnp.reshape(n_live, (1,)), rows, columns(B), columns(C), state)
    return jnp.where(live[:, None], y[:, 0], 0.0), state
