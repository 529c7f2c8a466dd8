"""The decode read of latent attention (MLA) in its absorbed form.

A token of an MLA block keeps ONE narrow vector in its page: the normed
latent c_kv (r values) followed by the rotated key k_r that all heads
share (dr values): a page plane of 1 x (r + dr), [L, P, 1, r + dr, ps]
(models/mla_moe.py; models/protocol.py `planes`). With W_kvb's key half
folded into the query, q'_h = [q_nope,h W^K_h^T | q_rope,h], every head's
score against a token is q'_h . [c_kv | k_r] and its value is c_kv itself,
the first r values of that same vector: one "KV head" of key width r + dr
whose value is a prefix of its key, read ONCE for all H query heads.

That is the paged read (ops/paged_attention.py `_paged_kernel`: a row's
live pages streamed through two VMEM buffers, the decode block's tail
folded first and the token put into it, one online softmax) with one pool
in place of two, G = H queries on the one head, and the value taken as a
slice of the key's buffer: the same kernel body, told `value_width`, under
its own name `mla_read`. At 32 heads of 512 + 64 in bfloat16 a page of 128
tokens is 147 KB, 0.18 us of DMA on a v5e, and meets 9.0 MFLOP (60 flop a
byte) in two products of one KV head: a loop turn that folded ONE such page
took 0.49 us, most of it the latencies of a chain in which every step
waits for the one before (copy, scores, max, exp, value product, rescale),
and the read sat at 37 % of its byte roofline (PR 31). A turn folds a
FOLD of pages (`pages_per_fold`: 8 of these, 1.2 MB, one score product of
8 output tiles, one softmax step over 1,024 tokens), the chain is paid
once a fold, and the call at the benchmark cell's shape went from 1,815 to
800 us, 84 % of the roofline over whole live pages (v5e,
tools/bench_paged_read.py, PR 32). A fold's width is what it copied (PR
40): a row's last fold is computed over the least power of two of pages
that covers its live ones (no less than a quarter of the fold), because with 32 query rows the two products of
8 pages are 0.57 us whatever the pages hold. At rows of 16-40 pages the
copies hide that (800 us still); at rows of 1-9 pages (xing's
`decode-closed`: 4.2 a row) a call went from 156.4 to 143.3 us, 1.68 to
1.54 us a row, of which 1.1 is the row's own chain of waits whatever its
width (`only=short`). That chain is what PR 48 works on: a grid step of
the kernel walks several consecutive rows (`rows_a_step`: four of these),
so a row pays no grid step of its own, and a row of one fold takes its
tail and its fold in ONE softmax step (`_paged_kernel` `short_row`).

`mla_read_reference` (gather-based) is the numerics oracle.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .paged_attention import DEFAULT_MASK_VALUE, _paged_read


def mla_read(q, new, pool, tail, table, lengths, tail_lens, *,
             value_width: int, scale: float, layer=None, interpret=None):
    """One step's latent attention inside a decode block. q [B, H, w]
    (absorbed queries, w = r + dr); new [B, 1, w] the rows' tokens, put
    into the tail as token tail_lens[b] - 1; pool [L, P, 1, w, ps] with
    `layer` (or one layer's with layer=None); tail [L, B, 1, T, w']
    (`plane_tail`); table [B, NP]; lengths [B] tokens attended in pages,
    tail_lens [B] in the tail. Scores are scaled by `scale` (the
    NON-absorbed head's 1 / sqrt(qk_head_dim), not 1 / sqrt(w)). A row
    with tail_lens[b] == 0 puts nothing, reads nothing, returns zeros.
    Returns (out [B, H, value_width] in q's dtype: the attention-weighted
    latent a head, tail)."""
    out, tail = _paged_read(q, [pool], table, lengths,
                            (new, tail, tail_lens), layer, None, interpret,
                            value_width=value_width, scale=scale,
                            scope="mla_read")
    return out, tail


def mla_read_reference(q, pool, table, lengths, *, value_width: int,
                       scale: float):
    """Gather-based oracle over pages alone. q [B, H, w]; pool
    [P, 1, w, ps]; table [B, NP]; lengths [B]. Returns [B, H, value_width]
    float32; zeros for a row of length 0."""
    B, NP = table.shape
    ps = pool.shape[-1]
    keys = jnp.moveaxis(pool[table][:, :, 0].astype(jnp.float32), 1, 2
                        ).reshape(B, pool.shape[2], NP * ps)     # [B, w, S]
    s = scale * jnp.einsum("bhw,bws->bhs", q.astype(jnp.float32), keys)
    live = jnp.arange(NP * ps)[None, :] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None], s, DEFAULT_MASK_VALUE), -1)
    out = jnp.einsum("bhs,bvs->bhv", p, keys[:, :value_width])
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)
