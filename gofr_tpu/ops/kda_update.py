"""Kimi Delta Attention decode state update: one token a row, the state in
place.

A KDA block (models/kda_moe.py) keeps, for every sequence, a matrix state
S of [heads, d_k, d_v] float32 (4 MiB at 64 x 128 x 128): a SLOT's worth,
fixed in size, beside the page pool. One decode step, a head:

    S' = a (.) S             the rows of S scaled by the decay, a in (0, 1)^d_k
    u  = S'^T k              what the state already answers for this key
    S  = S' + b k (v - u)^T  the delta rule: write what is missing, b in (0, 2)
    o  = S^T q

a reduction down the state, a rank-one write that DEPENDS on it, and a
second reduction: the state is read once and written once and the kernel is
bound by that (8 MiB a row a block), but unlike ops/ssm_update.py's
multiply-add the second half cannot start before the first reduction is
over, so a head's whole [d_k, d_v] tile is in registers twice.

Layout. The engine holds the state STACKED over the model's KDA blocks,
[blocks, slots, heads, d_k, d_v]: d_k rides the sublanes and d_v the lanes,
a head a [128, 128] tile. The decay is a value a CHANNEL of d_k (Mamba-2's
is one a (head, p) lane), so here the decay, k, b k and q are COLUMNS over
d_k that broadcast along the lanes, v and the two answers u and o are rows
over d_v, and both reductions run down the sublanes and leave as rows. The
transpose ([d_v, d_k], the decay a row) would make u and o lane reductions
that leave as columns: a relayout a head a row on the way out. The four
columns of a head tile's heads arrive side by side in ONE [d_k, 4 x heads]
tile (a, k, b k, q: at 32 heads exactly 128 lanes, so the operand is not
padded in HBM), laid out by XLA outside the kernel: 64 KB a row a tile
against 2 MiB of state.

Grid: (head tiles, rows), sequential, the rows inner. A row that holds no
request is skipped as ops/ssm_update.py skips it: the scalar-prefetched
`order` lists the live rows first and maps every later step of a tile's
pass onto the last live row's block, which the pipeline neither fetches nor
writes again while the block index stands still. The state is aliased in
and out, so only live rows' blocks move; a dead row's state is whatever it
was or, with no live row at all, what the first block's buffer held: its
prefill rewrites it whole at admission.

`kda_update_reference` is the same arithmetic in jax.numpy (elementwise
products and sums, no matrix product whose precision a backend may lower):
the numerics oracle, and what the family's decode step runs where it is
told `attn_impl: "xla"`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .scopes import kernel_scope
from .ssm_update import live_rows_first

HEAD_TILES = 2      # a block is [heads / 2, d_k, d_v] float32: 2 MiB at 32 x 128 x 128
VMEM_LIMIT = 32 << 20   # the state's block in and out, double-buffered, is 8 MiB


def kda_update_reference(state, layer, a, k, q, v, b, live):
    """state [L, S, H, dk, dv] float32; layer int; a (the decay, exp of the
    log-decay), k, q [S, H, dk] float32; v [S, H, dv] float32; b [S, H]
    float32; live [S] bool. Returns (o [S, H, dv] float32, state): dead rows
    keep their state and give o = 0."""
    h = state[layer]                                           # [S, H, dk, dv]
    decayed = a[..., None] * h
    u = jnp.sum(decayed * k[..., None], axis=2)                # [S, H, dv]
    new = decayed + (b[..., None] * k)[..., None] * (v - u)[:, :, None, :]
    o = jnp.sum(new * q[..., None], axis=2)
    state = state.at[layer].set(
        jnp.where(live[:, None, None, None], new, h))
    return jnp.where(live[:, None, None], o, 0.0), state


def _kernel(layer_ref, order_ref, n_live_ref, cols_ref, v_ref, s_ref, o_ref,
            out_ref, *, heads: int):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) < n_live_ref[0])
    def _live_row():
        dv = s_ref.shape[-1]
        for j in range(heads):                    # static: a head a tile
            a, k, bk, q = (cols_ref[0, 0, :, i * heads + j:i * heads + j + 1]
                           for i in range(4))                  # [dk, 1]
            span = slice(j * dv, (j + 1) * dv)
            decayed = a * s_ref[0, 0, j].astype(jnp.float32)   # [dk, dv]
            u = jnp.sum(decayed * k, axis=0, keepdims=True)    # [1, dv]
            new = decayed + bk * (v_ref[0, :, span] - u)
            out_ref[0, 0, j] = new.astype(out_ref.dtype)
            o_ref[0, :, span] = jnp.sum(new * q, axis=0, keepdims=True)


def kda_update(state, layer, a, k, q, v, b, live, *, interpret=None):
    """One decode step of one KDA block over every slot, in place.

    Shapes as `kda_update_reference`; `layer` may be traced. Returns
    (o [S, H, dv] float32, state) with `state` the donated input updated at
    the live rows of `layer`; dead rows give o = 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, H, dk, dv = state.shape
    tiles = HEAD_TILES if H % HEAD_TILES == 0 else 1
    heads = H // tiles
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    order, n_live = live_rows_first(live)
    # the four columns of a tile's heads side by side: [S, t, dk, 4 * heads]
    cols = jnp.stack([a, k, b[..., None] * k, q], axis=1)      # [S, 4, H, dk]
    cols = cols.reshape(S, 4, tiles, heads, dk).transpose(0, 2, 4, 1, 3)
    cols = cols.reshape(S, tiles, dk, 4 * heads).astype(jnp.float32)

    def col_tile(t, i, layer, order, n_live):
        return (order[i], t, 0, 0)

    def row_tile(t, i, layer, order, n_live):
        return (order[i], 0, t)

    def state_tile(t, i, layer, order, n_live):
        return (layer[0], order[i], t, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                     # layer, order, n_live
        grid=(tiles, S),
        in_specs=[pl.BlockSpec((1, 1, dk, 4 * heads), col_tile),
                  pl.BlockSpec((1, 1, heads * dv), row_tile),
                  pl.BlockSpec((1, 1, heads, dk, dv), state_tile)],
        out_specs=[pl.BlockSpec((1, 1, heads * dv), row_tile),
                   pl.BlockSpec((1, 1, heads, dk, dv), state_tile)],
    )
    with kernel_scope("kda_update"):
        o, state = pl.pallas_call(
            functools.partial(_kernel, heads=heads),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((S, 1, H * dv), jnp.float32),
                       jax.ShapeDtypeStruct(state.shape, state.dtype)],
            # operand 5 (after the three scalars) is the state: in place
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
        )(jnp.reshape(layer, (1,)).astype(jnp.int32), order,
          jnp.reshape(n_live, (1,)), cols,
          v.reshape(S, 1, H * dv).astype(jnp.float32), state)
    return jnp.where(live[:, None, None], o.reshape(S, H, dv), 0.0), state
