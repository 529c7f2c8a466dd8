"""Lightning linear attention (Lightning Attention-2, arXiv:2401.04658, in
MiniMax-01's form): a matrix state a head with a decay that is a CONSTANT of
the model, a value a head, no erase and no gate on the write.

    S_t = lambda_h S_{t-1} + k_t^T v_t        S [d_k, d_v] float32, S_0 = 0
    o_t = q_t S_t                             the token itself included

`lightning_update` is the decode step, one token a row with the state in
place; `lightning_chunk` the prefill of a fresh window with no scan over its
tokens. It is not ops/kda_update.py at other numbers (there b = 0 kills the
write, and the decay is a value a channel computed from the token), but the
state is laid out the same way and the kernel walks it the same way.

Layout. The engine holds the state STACKED over the model's lightning
blocks, [blocks, slots, heads, d_k, d_v]: d_k rides the sublanes and d_v
the lanes, a head a [128, 128] tile. k, q and the decay are COLUMNS over
d_k that broadcast along the lanes, v and the answer o are rows over d_v;
the reduction runs down the sublanes and leaves as a row. The three columns
of a head tile's heads arrive side by side in one [d_k, 4 x heads] tile
(decay, k, q and a column of zeros: at 32 heads exactly 128 lanes, so the
operand is not padded in HBM), laid out by XLA outside the kernel.

Grid: (head tiles, rows), sequential, the rows inner; a row that holds no
request is skipped as ops/ssm_update.py skips it (`live_rows_first`). The
state is aliased in and out, so only live rows' blocks move: each live
row's state is read once and written once, which is what bounds the kernel
(4 MiB a row a block at 32 x 128 x 128).

The chunkwise prefill builds its decay matrix from DIFFERENCES of
cumulative logs (`exp(G_i - G_j)`, i >= j), never as a quotient
lambda^i / lambda^j: the fastest head's lambda^-128 is over float32's
range. A padded position is given log-decay 0 and k = 0 by the caller's
`real` mask: it decays nothing and writes nothing, so the state returned
is the state as of each row's last real token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .scopes import kernel_scope
from .ssm_update import live_rows_first

HEADS_A_TILE = 32       # a block is [32, d_k, d_v] float32: 2 MiB at 128 x 128
VMEM_LIMIT = 32 << 20   # the state's block in and out, double-buffered, is 8 MiB
CHUNK = 128


def lightning_update_reference(state, layer, decay, k, q, v, live):
    """state [L, S, H, dk, dv] float32; layer int; decay [H] float32 (the
    block's lambda a head); k, q [S, H, dk], v [S, H, dv] float32 (q
    already scaled); live [S] bool. Returns (o [S, H, dv] float32, state):
    dead rows keep their state and give o = 0."""
    h = state[layer]                                           # [S, H, dk, dv]
    new = decay[None, :, None, None] * h + k[..., None] * v[:, :, None, :]
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
            a, k, q = (cols_ref[0, 0, :, i * heads + j:i * heads + j + 1]
                       for i in range(3))                      # [dk, 1]
            span = slice(j * dv, (j + 1) * dv)
            new = a * s_ref[0, 0, j].astype(jnp.float32) + k * v_ref[0, :, span]
            out_ref[0, 0, j] = new.astype(out_ref.dtype)
            o_ref[0, :, span] = jnp.sum(new * q, axis=0, keepdims=True)


def lightning_update(state, layer, decay, k, q, v, live, *, interpret=None):
    """One decode step of one lightning block over every slot, in place.

    Shapes as `lightning_update_reference`; `layer` may be traced. Returns
    (o [S, H, dv] float32, state) with `state` the donated input updated at
    the live rows of `layer`; dead rows give o = 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, S, H, dk, dv = state.shape
    heads = HEADS_A_TILE if H % HEADS_A_TILE == 0 else H
    tiles = H // heads
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    order, n_live = live_rows_first(live)
    # the columns of a tile's heads side by side: [S, t, dk, 4 * heads]
    a = jnp.broadcast_to(decay[None, :, None], k.shape)
    cols = jnp.stack([a, k, q, jnp.zeros_like(k)], axis=1)     # [S, 4, H, dk]
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
    with kernel_scope("lightning_update"):
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


def lightning_chunk(q, k, v, log_decay, real, chunk: int = CHUNK):
    """q, k [K, T, H, dk], v [K, T, H, dv] float32 (q already scaled);
    log_decay [H] float32 (log lambda, < 0); real [K, T] bool, the padding
    on the right. From an empty state. Returns (o [K, T, H, dv], state
    [K, H, dk, dv] as of each row's last real token)."""
    K, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"window {T} is not a multiple of the chunk {C}")
    n = T // C

    def chunks(x):                              # [K, T, H, d] -> [n, K, H, C, d]
        return x.reshape(K, n, C, H, -1).transpose(1, 0, 3, 2, 4)

    with jax.named_scope("lightning_chunk"), \
            jax.default_matmul_precision("highest"):
        k = jnp.where(real[:, :, None, None], k, 0.0)
        g = jnp.where(real[:, :, None], log_decay[None, None, :], 0.0)
        q, k, v = chunks(q), chunks(k), chunks(v)
        G = jnp.cumsum(chunks(g[..., None]), axis=-2)          # [n,K,H,C,1]
        total = G[..., -1:, :]                                 # [n,K,H,1,1]
        # inside a chunk, every chunk at once: i >= j, exp of a difference
        # that is <= 0
        lower = jnp.tril(jnp.ones((C, C), bool))
        decay = jnp.exp(jnp.where(lower, G - jnp.swapaxes(G, -1, -2),
                                  -jnp.inf))
        inside = (jnp.einsum("nkhid,nkhjd->nkhij", q, k) * decay) @ v
        q_in = jnp.exp(G) * q
        k_out = jnp.swapaxes(k * jnp.exp(total - G), -1, -2)   # [n,K,H,dk,C]

        # between chunks: only the two products with the state are carried
        def carry(S, inputs):
            q_c, k_c, v_c, total_c = inputs
            return jnp.exp(total_c) * S + k_c @ v_c, q_c @ S

        state, before = jax.lax.scan(
            carry, jnp.zeros((K, H, dk, dv), jnp.float32),
            (q_in, k_out, v, total))
        o = inside + before
    return o.transpose(1, 0, 3, 2, 4).reshape(K, T, H, dv), state
