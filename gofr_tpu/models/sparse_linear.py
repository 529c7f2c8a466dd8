"""sparse_linear family (OpenBMB's MiniCPM-SALA, `model_type` minicpm_sala,
is the published model the benchmark runs): blocks of two kinds in one
stack, one BLOCK-SPARSE softmax block (`minicpm4`: InfLLM-V2, MiniCPM4,
arXiv:2506.07900) to every three LIGHTNING linear-attention blocks
(Lightning Attention-2, arXiv:2401.04658, in MiniMax-01's form), a dense
SwiGLU FFN in every block, and MiniCPM's scaled residual form. With `RMS_x`
an RMSNorm with its own weight and c = scale_depth / sqrt(depth), `depth`
the PUBLISHED number of blocks whatever part of the stack is held:

    h_0 = scale_emb Embed(token)
    h = x + c Mixer_l(RMS_mixer(x));  y = h + c FFN(RMS_ffn(h))
    logits = (RMS_final(h_L) / (dim / dim_model_base)) W_head

Sparse mixer (32 query, 2 key/value heads of 128; no rotation):
    q = RMS_head(u W_q; g_q), k = RMS_head(u W_k; g_k), v = u W_v
    a query at t + 1 <= dense_len attends every token <= t; a later one the
    tokens <= t of `topk` blocks of `block_size`, chosen a KV head through
    compressed keys (ops/sparse_attention.py has the rule)
    out = (sigmoid(u W_z) (.) attn) W_o, a gate an element
Lightning mixer (H heads of d):
    q = rope(RMS_head(u W_q)), k = rope(RMS_head(u W_k)), v = u W_v
    S_t = lambda_h S_{t-1} + k_t^T v_t (float32),  o_t = (q_t / sqrt(d)) S_t
    lambda_h = exp(-2^(-8h/H) (1 - l/(depth-1) + 1e-5)), h = 1..H, l the
    block's PUBLISHED index: a constant of the model, not a weight
    out = (RMS_head(o_t; g_o) (.) sigmoid(u W_z)) W_o

`dense_len` is a rule a QUERY POSITION (the published code switches on the
call's length): a sequence's logits do not depend on where the engine cut
it, and no switch chooses between sparse and dense.

On the serving path a sequence holds THREE kinds of cached state: pages of
K and V for the sparse blocks; in the same pages a third plane, the
COMPRESSED KEYS the choice scores (a column of 2 x 128 every 16 tokens:
models/protocol.py `Plane.stride`), kept by the engine like K and V and
never recomputed from them; and a SLOT's worth beside the pool: every
lightning block's matrix state [H, d, d] float32 (2 MiB a block) and, for
every sparse block, the two half-window sums the next compressed key is
made of (ops/sparse_attention.py `half_sums_step`, 4 KB a block).

- `prefill`: a [K, bucket] window, right-padded, from an empty state. The
  lightning blocks run the chunkwise form (ops/lightning.py); the sparse
  blocks dense flash attention for the queries under `dense_len` and, for
  the rest, the choice a query (`prefill_choice`) applied as a mask
  (`sparse_prefill`).
- `decode_step`: one token a row. Inside a decode block the choice at a
  step sees every compressed key that is complete at that position: the
  pool's, and the one the block's own steps completed, which waits in the
  plane's tail (it changes each head's normaliser, so it moves the ranking
  of far blocks too).

Weights: the tree models/blocks.py `init_blocks` lays out, `layer_shapes`
a block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .blocks import (init_blocks, live_and_attended, np_dtype, rms_norm, rope,
                     seeded_block)

SPARSE, LIGHTNING = "sparse", "lightning"

# what a decode step counts, summed over the blocks of each kind: rows that
# chose their blocks, rows under `dense_len`, the blocks the choosing rows
# read and the blocks they held (a KV head each), the lightning updates
COUNTERS = ("sparse_rows", "dense_rows", "blocks_read", "blocks_held",
            "lightning_rows")


@dataclasses.dataclass(frozen=True)
class SparseLinearConfig:
    vocab_size: int = 73448
    dim: int = 4096
    mixers: Tuple[str, ...] = (SPARSE,) + (LIGHTNING,) * 3   # a block each
    layer_ids: Tuple[int, ...] = (0, 1, 2, 3)   # their published indices
    depth: int = 32                             # the published stack
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    ffn_dim: int = 16384
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0             # the lightning blocks'
    kernel_size: int = 32                   # tokens a compressed key means
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    chunk_size: int = 128
    max_seq_len: int = 524288
    rms_eps: float = 1e-6
    dtype: str = "bfloat16"
    attn_impl: str = "xla"      # "xla" | "flash": jax.numpy or the kernels

    def __post_init__(self):
        if len(self.mixers) != len(self.layer_ids) or any(
                m not in (SPARSE, LIGHTNING) for m in self.mixers):
            raise ValueError(f"mixers {self.mixers} and layer_ids "
                             f"{self.layer_ids} do not name the same blocks")
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError("a compressed key is kept as two half-window "
                             "sums: kernel_size must be 2 kernel_stride")
        if self.block_size % self.kernel_stride or (
                self.window_size % self.block_size
                or self.dense_len % self.block_size):
            raise ValueError("block_size, window_size and dense_len are "
                             "whole strides and blocks")
        if self.init_blocks + self.window_blocks > self.topk:
            raise ValueError("the forced blocks are over topk")

    @property
    def n_layers(self) -> int:
        return len(self.mixers)

    @property
    def kv_layers(self) -> int:
        """The sparse blocks: the ones that keep pages."""
        return sum(m == SPARSE for m in self.mixers)

    @property
    def lightning_layers(self) -> int:
        return self.n_layers - self.kv_layers

    @property
    def window_blocks(self) -> int:
        return self.window_size // self.block_size

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.depth)

    @property
    def lightning_dim(self) -> int:
        return self.lightning_heads * self.lightning_head_dim

    @property
    def lightning_state_bytes(self) -> int:
        return (self.lightning_layers * self.lightning_dim
                * self.lightning_head_dim * 4)

    @property
    def half_sums_bytes(self) -> int:
        return self.kv_layers * 2 * self.n_kv_heads * self.head_dim * 4

    @property
    def state_bytes_per_slot(self) -> int:
        """What a sequence holds beside its pages (tpu/capacity.py)."""
        return self.lightning_state_bytes + self.half_sums_bytes

    def choice_rule(self) -> Dict[str, int]:
        """What ops/sparse_attention.py `choose` takes."""
        return {"per": self.block_size // self.kernel_stride,
                "topk": self.topk, "init_blocks": self.init_blocks,
                "window_blocks": self.window_blocks,
                "dense_len": self.dense_len, "block_size": self.block_size}

    def decay(self, layer: int):
        """[H] float32: lambda a head of the lightning block at position
        `layer` of the stack held."""
        H = self.lightning_heads
        rate = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
        depth = 1.0 - self.layer_ids[layer] / (self.depth - 1) + 1e-5
        return jnp.exp(-rate * depth)

    @classmethod
    def debug(cls) -> "SparseLinearConfig":
        """CI-sized: two periods (sparse, three lightning), the sparse
        rule scaled so that a 256-token sequence crosses `dense_len` and
        has more blocks than `topk`."""
        return cls(vocab_size=512, dim=64,
                   mixers=((SPARSE,) + (LIGHTNING,) * 3) * 2,
                   layer_ids=tuple(range(8)), depth=8, n_heads=4,
                   n_kv_heads=2, head_dim=16, lightning_heads=4,
                   lightning_head_dim=16, ffn_dim=128, dim_model_base=16,
                   kernel_size=4, kernel_stride=2, block_size=8, topk=6,
                   init_blocks=1, window_size=24, dense_len=64,
                   chunk_size=16, max_seq_len=256, dtype="float32")

    @classmethod
    def minicpm_sala_pp4(cls) -> "SparseLinearConfig":
        """MiniCPM-SALA at its published widths, cut to one v5e chip as
        benchmark/configs/minicpm-sala-pp4.json states: one pipeline stage
        of four, the published blocks 9-16 (`minicpm4`, six
        `lightning-attn`, `minicpm4`), whole embedding and head."""
        return cls(mixers=(SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,),
                   layer_ids=tuple(range(9, 17)), chunk_size=256,
                   max_seq_len=15360)

    def matrix_params(self) -> Dict[str, int]:
        D = self.dim
        q = self.n_heads * self.head_dim
        return {"sparse": 3 * D * q + 2 * D * self.n_kv_heads * self.head_dim,
                "lightning": 5 * D * self.lightning_dim,
                "ffn": 3 * D * self.ffn_dim}

    def param_count(self) -> int:
        """The parameters a token meets (the utilization ledger's 2 P
        flops a token)."""
        m = self.matrix_params()
        return (self.kv_layers * m["sparse"]
                + self.lightning_layers * m["lightning"]
                + self.n_layers * m["ffn"] + self.dim * self.vocab_size)

    def planes(self):
        from .protocol import Plane, kv_planes

        return kv_planes(self.n_kv_heads, self.head_dim) + (
            Plane("compressed_k", self.n_kv_heads, self.head_dim,
                  stride=self.kernel_stride, span=self.kernel_size),)

    def paged_model(self):
        from .protocol import PagedModel, one_group

        return PagedModel(
            family="sparse_linear", program_tag="sparse-linear",
            planes=self.planes(), groups=one_group(self.kv_layers),
            state_shapes=lambda slots: state_shapes(self, slots),
            prefill=lambda params, tokens, lengths, mesh=None: prefill(
                params, self, tokens, lengths),
            decode=lambda params, tokens, positions, pools, table, state,
            tail, step, mesh=None: decode_step(
                params, self, tokens, positions, pools, table, state, tail,
                step),
            counters=COUNTERS,
            describe=lambda counts, steps: describe(self, counts, steps),
            refuses=REFUSES)


# what the family cannot do yet, refused by name at construction
_SNAPSHOT = ("a lightning state cannot be rebuilt from pages: it needs a "
             "snapshot of the matrix state (2 MiB a block) and of the "
             "half-window sums at the page boundary")
REFUSES = {
    "prefix_cache": _SNAPSHOT,
    "kv_host_tier": _SNAPSHOT,
    "disagg": "a hand-off ships K and V page blobs; the compressed keys, "
              "the lightning state and the half-window sums have no blob yet",
    "speculative_tokens": "a rejected draft would have to roll the "
                          "lightning state back: no snapshot yet",
    "chunk_prefill_tokens": "the lightning state a chunk ends in is not "
                            "carried into the next job's prefill, nor are "
                            "the compressed keys of the chunks before",
    "int8_weights": "no int8 weight path for this family",
    "kv_dtype": "the choice scores compressed keys in the pages' dtype and "
                "the int8 read has no block list; no lower-precision pool",
    "mesh": "the block list and the lightning state's heads have no tp "
            "form yet",
}


def describe(cfg: SparseLinearConfig, counts: Dict[str, int], steps: int):
    """`/debug/engine` "model": the blocks by kind, what a slot holds, the
    sparse rule, and how the choices and the lightning updates of `steps`
    decode steps fell."""
    out = {"blocks": {SPARSE: cfg.kv_layers, LIGHTNING: cfg.lightning_layers},
           "layer_ids": list(cfg.layer_ids),
           "state_bytes_per_slot": cfg.state_bytes_per_slot,
           "lightning_state_bytes_per_slot": cfg.lightning_state_bytes,
           "half_sums_bytes_per_slot": cfg.half_sums_bytes,
           "lightning_state_dtype": "float32",
           "sparse": {"block_size": cfg.block_size, "topk": cfg.topk,
                      "dense_len": cfg.dense_len,
                      "window_size": cfg.window_size,
                      "kernel_stride": cfg.kernel_stride}}
    if steps:
        block_bytes = (2 * cfg.block_size * cfg.head_dim
                       * (2 if cfg.dtype != "float32" else 4))
        out["sparse"].update(
            sparse_rows_per_step=counts["sparse_rows"] / steps,
            dense_rows_per_step=counts["dense_rows"] / steps,
            blocks_read=counts["blocks_read"],
            blocks_held=counts["blocks_held"],
            read_share=(counts["blocks_read"] / counts["blocks_held"]
                        if counts["blocks_held"] else None),
            read_bytes_per_step=counts["blocks_read"] * block_bytes / steps)
        out["lightning_rows_per_step"] = counts["lightning_rows"] / steps
    return out


def layer_shapes(cfg: SparseLinearConfig, mixer: str) -> Dict[str, tuple]:
    D = cfg.dim
    ffn = {"ffn_norm": (D,), "w_gate": (D, cfg.ffn_dim),
           "w_up": (D, cfg.ffn_dim), "w_down": (cfg.ffn_dim, D)}
    if mixer == SPARSE:
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {"mixer_norm": (D,), "wq": (D, q), "wk": (D, kv),
                "wv": (D, kv), "q_norm": (cfg.head_dim,),
                "k_norm": (cfg.head_dim,), "attn_gate": (D, q),
                "wo": (q, D), **ffn}
    c = cfg.lightning_dim
    d = cfg.lightning_head_dim
    return {"mixer_norm": (D,), "wq": (D, c), "wk": (D, c), "wv": (D, c),
            "q_norm": (d,), "k_norm": (d,), "o_norm": (d,),
            "out_gate": (D, c), "wo": (c, D), **ffn}


def sparse_linear_init(cfg: SparseLinearConfig, seed: int = 0
                       ) -> Dict[str, Any]:
    """Random-init params, a jitted call a block."""
    return init_blocks(
        cfg, seed, list(cfg.mixers),
        lambda key, mixer: seeded_block(key, layer_shapes(cfg, mixer),
                                        np_dtype(cfg.dtype)))


# for models/families.py
PRESETS = {"sparse-linear-debug": SparseLinearConfig.debug,
           "minicpm-sala-pp4": SparseLinearConfig.minicpm_sala_pp4}
init = sparse_linear_init


def state_shapes(cfg: SparseLinearConfig, slots: int):
    """((shape, dtype), ...) of the per-slot arrays, the slot axis second:
    the lightning state (float32 whatever the weights are held in: the
    decay multiplies it every token) and the sparse blocks' half-window
    sums (float32: sums of up to `kernel_stride` keys)."""
    return (((cfg.lightning_layers, slots, cfg.lightning_heads,
              cfg.lightning_head_dim, cfg.lightning_head_dim), jnp.float32),
            ((cfg.kv_layers, slots, 2, cfg.n_kv_heads, cfg.head_dim),
             jnp.float32))


# -- what both kinds share ----------------------------------------------------
def _heads(x, w, norm, heads: int, eps: float):
    """(x W) a head, normed a head where `norm` names its gain."""
    y = (x @ w).reshape(*x.shape[:-1], heads, -1)
    return y if norm is None else rms_norm(y, norm, eps)


def _ffn(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _head(x, params, cfg: SparseLinearConfig):
    """The final norm, MiniCPM's division by dim / dim_model_base, and the
    untied head: logits in float32."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    x = (x.astype(jnp.float32) / (cfg.dim / cfg.dim_model_base)
         ).astype(x.dtype)
    return (x @ params["lm_head"]).astype(jnp.float32)


def _embed(params, tokens, cfg: SparseLinearConfig):
    x = params["tok_emb"][tokens]
    return (x.astype(jnp.float32) * cfg.scale_emb).astype(x.dtype)


# -- the lightning mixer ------------------------------------------------------
def _lightning_qkv(u, w, positions, cfg: SparseLinearConfig):
    """u [K, T, D] (normed), positions [K, T] -> q (scaled), k, v
    [K, T, H, d] float32."""
    H, eps = cfg.lightning_heads, cfg.rms_eps
    q = rope(_heads(u, w["wq"], w["q_norm"], H, eps), positions,
             cfg.rope_theta)
    k = rope(_heads(u, w["wk"], w["k_norm"], H, eps), positions,
             cfg.rope_theta)
    v = _heads(u, w["wv"], None, H, eps)
    scale = 1.0 / math.sqrt(cfg.lightning_head_dim)
    return (q.astype(jnp.float32) * scale, k.astype(jnp.float32),
            v.astype(jnp.float32))


def _lightning_out(o, u, w, cfg: SparseLinearConfig):
    """o [.., H, d] float32 -> (RMS_head(o) (.) sigmoid gate) W_o."""
    normed = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps) \
        * w["o_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid((u @ w["out_gate"]).astype(jnp.float32))
    gated = normed.reshape(*u.shape[:-1], cfg.lightning_dim) * gate
    return gated.astype(u.dtype) @ w["wo"]


def lightning_prefill(u, w, lengths, layer: int, cfg: SparseLinearConfig):
    """u [K, T, D] (normed), right-padded. Returns (out [K, T, D], state
    [K, H, d, d] float32 as of each row's last real token)."""
    from ..ops.lightning import lightning_chunk

    K, T, _ = u.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T))
    q, k, v = _lightning_qkv(u, w, positions, cfg)
    real = positions < lengths[:, None]
    o, state = lightning_chunk(q, k, v, jnp.log(cfg.decay(layer)), real,
                               chunk=cfg.chunk_size)
    return _lightning_out(o, u, w, cfg), state


def lightning_decode(u, w, state, at: int, layer: int, positions, live,
                     cfg: SparseLinearConfig):
    """u [B, D] (normed); state [Ll, B, H, d, d]; `at` this block's index
    among the lightning blocks, `layer` in the stack. Returns (out,
    state)."""
    from ..ops.lightning import lightning_update, lightning_update_reference

    q, k, v = (x[:, 0] for x in _lightning_qkv(
        u[:, None], w, positions[:, None], cfg))
    update = (lightning_update_reference if cfg.attn_impl == "xla"
              else lightning_update)
    o, state = update(state, at, cfg.decay(layer), k, q, v, live)
    return _lightning_out(o, u, w, cfg), state


# -- the sparse mixer ---------------------------------------------------------
def _sparse_qkvg(x, w, cfg: SparseLinearConfig):
    """x [.., D] (normed) -> q [.., H, dh], k, v [.., Hkv, dh], gate
    [.., H dh] float32 (the sigmoid taken): q and k normed, not turned."""
    eps = cfg.rms_eps
    q = _heads(x, w["wq"], w["q_norm"], cfg.n_heads, eps)
    k = _heads(x, w["wk"], w["k_norm"], cfg.n_kv_heads, eps)
    v = _heads(x, w["wv"], None, cfg.n_kv_heads, eps)
    gate = jax.nn.sigmoid((x @ w["attn_gate"]).astype(jnp.float32))
    return q, k, v, gate


def sparse_prefill_block(x, w, lengths, cfg: SparseLinearConfig):
    """x [K, T, D] (normed), right-padded. Returns (out, k, v [K, Hkv, dh,
    T] the page writer's layout, compressed keys [K, Hkv, T / stride, dh],
    half-window sums [K, 2, Hkv, dh] float32)."""
    from ..ops import sparse_attention as sparse
    from ..ops.flash_attention import attention_reference, flash_attention

    K, T, _ = x.shape
    q, k, v, gate = _sparse_qkvg(x, w, cfg)
    ck, sums = sparse.half_sums_prefill(k, lengths, cfg.kernel_stride)
    flash = cfg.attn_impl == "flash"
    dense = min(T, cfg.dense_len)
    # the queries under dense_len: causal over the window's first tokens
    if flash:
        # blocks of 512: at 128 the resident kernel's loop turns, not its
        # products, are what 8,192 keys of 128 cost
        attn = flash_attention(q[:, :dense], k[:, :dense], v[:, :dense], True,
                               block_q=512, block_kv=512)
    else:
        attn = attention_reference(q[:, :dense], k[:, :dense], v[:, :dense],
                                   causal=True)
    if T > dense:
        # the rest choose, a query a KV head
        chosen = sparse.prefill_choice(
            q[:, dense:], ck, dense, stride=cfg.kernel_stride,
            kernel=cfg.kernel_size, **cfg.choice_rule())
        rest = (sparse.sparse_prefill if flash
                else sparse.sparse_prefill_reference)(
            q[:, dense:], k, v, chosen, dense, cfg.block_size)
        attn = jnp.concatenate([attn, rest], axis=1)
    attn = (attn.reshape(K, T, -1).astype(jnp.float32) * gate).astype(x.dtype)
    return (attn @ w["wo"], k.transpose(0, 2, 3, 1), v.transpose(0, 2, 3, 1),
            ck.transpose(0, 2, 1, 3), sums)


def _list_width(cfg: SparseLinearConfig, page_size: int, table_width: int):
    """Entries of a (row, KV head)'s page list: a page a chosen block, or
    every page of a row still under dense_len; a power of two (the read
    folds whole powers), never over the table."""
    most = max(cfg.topk, -(-cfg.dense_len // page_size))
    width = 1
    while width < most:
        width *= 2
    return min(width, table_width)


def sparse_decode(x, w, pools, table, lengths, tails, tail_lens, sums,
                  at: int, positions, live, step, cfg: SparseLinearConfig):
    """x [B, D] (normed); `at` this block's index among the sparse blocks
    = the pools', the tails' and the sums' leading axis. The token's K and
    V go into the block's tail; the compressed key it completes, if any,
    into the third plane's. Returns (out, tails, sums, counted [4])."""
    from ..ops import sparse_attention as sparse
    from ..ops.paged_attention import tail_put

    k_pool, v_pool, ck_pool = pools
    k_tail, v_tail, ck_tail = tails
    ps = k_pool.shape[-1]
    stride, kernel = cfg.kernel_stride, cfg.kernel_size
    q, k, v, gate = _sparse_qkvg(x, w, cfg)
    # the compressed key this token completes joins the block's own
    sums, c, completes = sparse.half_sums_step(sums, at, k, positions, live,
                                               stride)
    n_pool = sparse.columns(lengths, stride, kernel)
    n_seen = jnp.where(live, sparse.columns(positions + 1, stride, kernel), 0)
    n_tail = n_seen - n_pool
    slot = jnp.arange(ck_tail.shape[3])[None, :] == (n_tail - 1)[:, None]
    put = jnp.logical_and(slot, completes[:, None])[:, None, :, None]
    ck_tail = ck_tail.at[at].set(jnp.where(
        put, c.astype(ck_tail.dtype)[:, :, None, :], ck_tail[at]))
    # the choice, a KV head
    ck = sparse.gather_compressed(ck_pool, at, table, ck_tail, n_pool, n_tail)
    scores = (sparse.select_scores_reference if cfg.attn_impl == "xla"
              else sparse.select_scores)
    chosen = sparse.choose(scores(q, ck, n_seen),
                           jnp.broadcast_to(positions[:, None],
                                            (x.shape[0], cfg.n_kv_heads)),
                           **cfg.choice_rule())
    chosen = jnp.logical_and(chosen, live[:, None, None])
    if cfg.attn_impl == "xla":
        k_tail, v_tail = tail_put(k_tail, v_tail, k, v, at, step)
        attn = sparse.sparse_read_reference(
            q, k_pool, v_pool, k_tail, v_tail, table, chosen, lengths,
            tail_lens, layer=at, block_size=cfg.block_size)
    else:
        pages, bits, held = sparse.page_lists(
            chosen, table, lengths, ps, cfg.block_size,
            _list_width(cfg, ps, table.shape[1]))
        attn, k_tail, v_tail = sparse.sparse_read(
            q, k, v, k_pool, v_pool, k_tail, v_tail, pages, bits, held,
            tail_lens, layer=at, block_size=cfg.block_size)
    attn = (attn.reshape(x.shape[0], -1).astype(jnp.float32)
            * gate).astype(x.dtype)
    # what the rows that chose read and what they held, a KV head
    chose = jnp.logical_and(live, positions + 1 > cfg.dense_len)
    read = jnp.sum(jnp.where(chose[:, None, None], chosen, False),
                   dtype=jnp.int32)
    held_blocks = jnp.sum(jnp.where(
        chose, positions // cfg.block_size + 1, 0), dtype=jnp.int32)
    counted = jnp.stack([
        jnp.sum(chose, dtype=jnp.int32),
        jnp.sum(jnp.logical_and(live, jnp.logical_not(chose)),
                dtype=jnp.int32),
        read, held_blocks * cfg.n_kv_heads])
    return attn @ w["wo"], (k_tail, v_tail, ck_tail), sums, counted


# -- the stack ----------------------------------------------------------------
def prefill(params, cfg: SparseLinearConfig, tokens, lengths):
    """tokens [K, T] right-padded; lengths [K]. Returns (last logits
    [K, V] float32, (k, v [kv_layers, K, Hkv, dh, T], compressed keys
    [kv_layers, K, Hkv, T / stride, dh]), (lightning state
    [lightning_layers, K, H, d, d], half-window sums [kv_layers, K, 2, Hkv,
    dh]))."""
    K, T = tokens.shape
    c = cfg.residual_scale
    x = _embed(params, tokens, cfg)
    ks, vs, cks, sums, states = [], [], [], [], []
    for layer, (mixer, w) in enumerate(zip(cfg.mixers, params["layers"])):
        normed = rms_norm(x, w["mixer_norm"], cfg.rms_eps)
        if mixer == SPARSE:
            out, k, v, ck, half = sparse_prefill_block(normed, w, lengths, cfg)
            ks.append(k)
            vs.append(v)
            cks.append(ck)
            sums.append(half)
        else:
            out, state = lightning_prefill(normed, w, lengths, layer, cfg)
            states.append(state)
        x = x + (c * out.astype(jnp.float32)).astype(x.dtype)
        out = _ffn(rms_norm(x, w["ffn_norm"], cfg.rms_eps), w)
        x = x + (c * out.astype(jnp.float32)).astype(x.dtype)
    last = x[jnp.arange(K), lengths - 1]

    def stacked(parts, empty, dtype):
        # a stack may lack a kind: its stack is then empty, not missing
        return jnp.stack(parts) if parts else jnp.zeros(empty, dtype)

    kv = (0, K, cfg.n_kv_heads, cfg.head_dim, T)
    ck = (0, K, cfg.n_kv_heads, T // cfg.kernel_stride, cfg.head_dim)
    (state_like, _), (sums_like, _) = state_shapes(cfg, K)
    return (_head(last, params, cfg),
            (stacked(ks, kv, x.dtype), stacked(vs, kv, x.dtype),
             stacked(cks, ck, x.dtype)),
            (stacked(states, state_like, jnp.float32),
             stacked(sums, sums_like, jnp.float32)))


def decode_step(params, cfg: SparseLinearConfig, tokens, positions, pools,
                table, state, tails, step):
    """One token a row, step `step` of a decode block. tokens, positions
    [B]; pools (k, v [kv_layers, P, Hkv, dh, ps], compressed keys
    [kv_layers, P, Hkv, ps / stride, dh]) as the block found them, read
    only; table [B, NP] (a row that starts at page 0 holds no request);
    state = (lightning, half-window sums); tails the block's, a plane each
    (models/protocol.py). Returns (logits [B, V] float32, tails, state,
    counters [len(COUNTERS)] int32)."""
    lightning, sums = state
    live, lengths, tail_lens = live_and_attended(table, positions, step)
    c = cfg.residual_scale
    x = _embed(params, tokens, cfg)
    counted = jnp.zeros((4,), jnp.int32)
    s = l = 0
    for layer, (mixer, w) in enumerate(zip(cfg.mixers, params["layers"])):
        normed = rms_norm(x, w["mixer_norm"], cfg.rms_eps)
        if mixer == SPARSE:
            out, tails, sums, seen = sparse_decode(
                normed, w, pools, table, lengths, tails, tail_lens, sums, s,
                positions, live, step, cfg)
            counted = counted + seen
            s += 1
        else:
            out, lightning = lightning_decode(
                normed, w, lightning, l, layer, positions, live, cfg)
            l += 1
        x = x + (c * out.astype(jnp.float32)).astype(x.dtype)
        out = _ffn(rms_norm(x, w["ffn_norm"], cfg.rms_eps), w)
        x = x + (c * out.astype(jnp.float32)).astype(x.dtype)
    rows = jnp.sum(live, dtype=jnp.int32)
    counters = jnp.concatenate([counted,
                                (rows * cfg.lightning_layers)[None]])
    return _head(x, params, cfg), tails, (lightning, sums), counters
