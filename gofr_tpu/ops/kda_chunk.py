"""Kimi Delta Attention over a fresh window, chunkwise (Kimi Linear,
arXiv:2510.26692): the prefill of ops/kda_update.py's recurrence with no
scan over a prompt's tokens.

The recurrence, a head, from S_0 = 0 (g the log-decay a channel, <= 0):

    S_t = Diag(exp g_t) S_{t-1} + k_t w_t^T,   w_t = b_t (v_t - u_t),
    u_t = (Diag(exp g_t) S_{t-1})^T k_t,       o_t = S_t^T q_t

Inside a chunk of C tokens that starts from the carried state S, with G_t
the cumulative log-decay from the chunk's start (G_t = g_1 + ... + g_t):

    A_ts = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   s <  t   [C, C]
    B_ts = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])   s <= t
    (I + Diag(b) A) W = Diag(b) (V - (exp G (.) K) S)          the UT / WY
    O = (exp G (.) Q) S + B W                                  transform
    S <- Diag(exp G_C) S + (K (.) exp(G_C - G))^T W

so W = U~ - W~ S with U~, W~ the solution of ONE unit lower-triangular
system a chunk a head for the right-hand sides Diag(b) V and Diag(b)
(exp G (.) K): every chunk's A, B, U~ and W~ are computed at once, and only
the three products with S are carried from chunk to chunk.

Decays are `exp` of DIFFERENCES of cumulative logs, never ratios of
exponentials (exp(-G_s) overflows float32 after a few tokens of a fast
channel). A chunk is cut into sub-blocks of `SUB` tokens: inside a
sub-block the differences are taken a (t, s, channel) triple; between
sub-blocks they go through the log at the later sub-block's start, R:
exp(G_t - R) exp(R - G_s), both exponents <= 0, and the sum over channels
is a matrix product.

A padded position is given g = 0 and b = 0 by the caller: it decays
nothing and writes nothing, so the state returned is the state as of each
row's last real token. Everything here is float32 at the highest matmul
precision: the delta rule multiplies the state by (I - b k k^T) every
token, b up to 2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

CHUNK = 64      # the paper's
SUB = 16


def _intra(q, k, G, sub: int):
    """q, k, G [..., C, dk] (G cumulative inside the chunk). Returns
    (A [..., C, C] strictly lower, B [..., C, C] lower)."""
    C, dk = k.shape[-2:]
    m = C // sub
    lead = k.shape[:-2]
    qs, ks, Gs = (x.reshape(*lead, m, sub, dk) for x in (q, k, G))
    # inside a sub-block: a (t, s, channel) triple's own difference
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(lower[:, :, None],
                              Gs[..., :, None, :] - Gs[..., None, :, :],
                              -jnp.inf))                   # [.., m, t, s, dk]
    a_diag = jnp.sum(ks[..., :, None, :] * ks[..., None, :, :] * decay, -1)
    b_diag = jnp.sum(qs[..., :, None, :] * ks[..., None, :, :] * decay, -1)
    a_diag = jnp.where(jnp.tril(lower, -1), a_diag, 0.0)
    a_rows, b_rows = [], []
    for i in range(m):
        a_row, b_row = [a_diag[..., i, :, :]], [b_diag[..., i, :, :]]
        if i:
            # against the earlier sub-blocks, through the log at this one's
            # start
            R = G[..., i * sub - 1, :][..., None, :]
            after = jnp.exp(Gs[..., i, :, :] - R)               # [.., sub, dk]
            before = k[..., :i * sub, :] * jnp.exp(R - G[..., :i * sub, :])
            a_row.insert(0, jnp.einsum("...tc,...sc->...ts",
                                       ks[..., i, :, :] * after, before))
            b_row.insert(0, jnp.einsum("...tc,...sc->...ts",
                                       qs[..., i, :, :] * after, before))
        ahead = jnp.zeros((*lead, sub, C - (i + 1) * sub), k.dtype)
        a_rows.append(jnp.concatenate(a_row + [ahead], axis=-1))
        b_rows.append(jnp.concatenate(b_row + [ahead], axis=-1))
    return jnp.concatenate(a_rows, axis=-2), jnp.concatenate(b_rows, axis=-2)


def kda_chunk(q, k, v, g, b, chunk: int = CHUNK, sub: int = SUB):
    """q, k, g [K, T, H, dk], v [K, T, H, dv], b [K, T, H], all float32; g
    the log-decay (<= 0), zero with b at a padded position. From an empty
    state. Returns (o [K, T, H, dv], state [K, H, dk, dv] as of the last
    position)."""
    K, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    sub = min(sub, C)
    if T % C or C % sub:
        raise ValueError(f"window {T} is not a multiple of the chunk {C}, "
                         f"or the chunk of its sub-block {sub}")
    n = T // C

    def chunks(x):                                  # [K, T, H, d] -> [K, n, H, C, d]
        return x.reshape(K, n, C, H, -1).transpose(0, 1, 3, 2, 4)

    with jax.named_scope("kda_chunk"), \
            jax.default_matmul_precision("highest"):
        q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
        b = chunks(b[..., None])                                  # [K,n,H,C,1]
        G = jnp.cumsum(g, axis=-2)
        A, B = _intra(q, k, G, sub)
        from_start = jnp.exp(G)
        rhs = b * jnp.concatenate([v, from_start * k], axis=-1)
        solved = jax.lax.linalg.triangular_solve(
            jnp.eye(C, dtype=A.dtype) + b * A, rhs, left_side=True,
            lower=True, unit_diagonal=True)
        U, Wk = solved[..., :dv], solved[..., dv:]
        q_in = from_start * q
        total = G[..., -1:, :]                                    # [K,n,H,1,dk]
        k_out = k * jnp.exp(total - G)

        def carry(S, inputs):
            U_c, Wk_c, q_c, B_c, k_c, total_c = inputs
            W = U_c - Wk_c @ S                                    # [K,H,C,dv]
            o = q_c @ S + B_c @ W
            S = jnp.swapaxes(jnp.exp(total_c), -1, -2) * S \
                + jnp.swapaxes(k_c, -1, -2) @ W
            return S, o

        state, o = jax.lax.scan(
            carry, jnp.zeros((K, H, dk, dv), jnp.float32),
            tuple(jnp.moveaxis(x, 1, 0)
                  for x in (U, Wk, q_in, B, k_out, total)))
        o = jnp.moveaxis(o, 0, 1)                                 # [K,n,H,C,dv]
    return o.transpose(0, 1, 3, 2, 4).reshape(K, T, H, dv), state
