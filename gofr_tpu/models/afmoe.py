"""afmoe family (Arcee's Trinity; Trinity-Large-Preview is the published
model the benchmark runs): gated grouped-query attention whose blocks are
of two kinds, sparse experts, and four norms a block. With `RMS_x` an
RMSNorm with its own weight and `h = E[token] * sqrt(D)`:

    a = RMS_in(h)
    q = W_q a [H, dh]  k = W_k a [Hkv, dh]  v = W_v a [Hkv, dh]  g = W_g a [H dh]
    q = RMS_qn(q), k = RMS_kn(k)        over a head's dh, one weight of dh each
    sliding block: q, k turned by RoPE (rotate-half pairs (i, i + dh / 2));
    full block: NOT turned, no position signal at all
    token i sees j <= i, on a sliding block also j > i - W (W keys, its own
    among them)
    o = softmax(q . k / sqrt(dh)) v;  o = o * sigmoid(g)
    h = h + RMS_post_attn(W_o o)
    m = RMS_pre_mlp(h)
    y = dense SwiGLU(m)  (the first `first_dense` blocks), else
        shared(m) + sum over the k picks of w_e expert_e(m)
    h = h + RMS_post_mlp(y)

and `logits = W_head RMS_final(h)`. The FFN, dense or routed, is
models/experts.py's in its gated form (ops/moe_experts.py's blocks tile an
expert's width where three matrices of 3072 x 3072 do not fit VMEM twice
over), and so are the counters.

What a token keeps is K (after its norm and, on a sliding block, its
rotation) and V of Hkv heads a block, in TWO PAGE GROUPS
(models/protocol.py `groups`): `full`, the blocks that attend everything,
where a sequence holds a page for every 128 tokens, and `window`, the
sliding blocks, where it holds a ring of W / 128 + 2 pages however long it
grows (tpu/paging.py). At W = 4096 a 13k-token sequence keeps 104 + 4 x 34
pages a period of four blocks where one group would keep 4 x 104.

- `prefill`: causal flash attention over the fresh window, told the window
  on sliding blocks (ops/flash_attention.py skips key blocks wholly behind
  it); the token-wise half of a block (the dense FFN, the experts) runs in
  pieces of at most `PIECE` tokens, so that the grouped experts' glue and
  the dense block's hidden are sized by a piece, not by a 12k prompt.
- `decode_step`: the paged read a block, a full block's under the scope
  `paged_read`, a sliding block's under `window_read` with the lower bound
  position - W + 1 a row and its table a ring
  (ops/paged_attention.py `paged_attention_in_block`).

Weights: the tree models/blocks.py `init_blocks` lays out, `layer_shapes`
a block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import experts
from .blocks import (head, init_blocks, live_and_attended, np_dtype, rms_norm,
                     rope, seeded_block)
from .experts import COUNTERS, FLOAT32_LEAVES, ffn_decode, ffn_prefill

__all__ = ["AfmoeConfig", "afmoe_init", "prefill", "decode_step",
           "COUNTERS", "FLOAT32_LEAVES"]

SLIDING, FULL = "sliding_attention", "full_attention"

# (block_q, block_kv) of the prefill's flash kernel: this family's windows
# run to 12k tokens, so the kernel's time is its blocks' MXU passes
# (PERF.md section 6, PR 31, has the measurements at 4,096 tokens)
FLASH_BLOCKS = (512, 512)

# the most tokens the token-wise half of a prefill block takes at once
PIECE = 4096


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(experts.HeldExperts):
    vocab_size: int = 200192
    dim: int = 3072
    n_layers: int = 60
    first_dense: int = 6                    # leading blocks with a dense FFN
    # a block's kind, in order: sliding_attention | full_attention
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 15
    n_heads: int = 48
    n_kv_heads: int = 8
    head_dim: int = 128
    window: int = 4096
    dense_dim: int = 12288
    n_experts: int = 256                    # the router's outputs
    experts_held: Tuple[int, int] = (0, 256)    # the range this chip holds
    experts_per_token: int = 4
    expert_dim: int = 3072
    shared_dim: int = 3072
    routed_scale: float = 2.448
    rope_theta: float = 10000.0
    max_seq_len: int = 262144
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    attn_impl: str = "xla"      # "xla" | "flash": the prefill window

    def __post_init__(self):
        self.check_experts_held()
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError("first_dense counts leading blocks")
        if len(self.layer_types) != self.n_layers or any(
                kind not in (SLIDING, FULL) for kind in self.layer_types):
            raise ValueError(f"layer_types names {self.n_layers} blocks, "
                             f"each {SLIDING} or {FULL}")

    @property
    def kv_layers(self) -> int:
        """Every block keeps K and V in pages."""
        return self.n_layers

    @property
    def window_layers(self) -> int:
        return sum(kind == SLIDING for kind in self.layer_types)

    @property
    def expert_layers(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def ffn_dim(self) -> int:
        """The widest activation a block makes (the capacity plan's prefill
        temporaries): the dense FFN, or the queries and their gate."""
        return max(self.dense_dim if self.first_dense else 0,
                   2 * self.n_heads * self.head_dim)

    state_bytes_per_slot = 0    # a sequence's only cached state is pages

    @classmethod
    def debug(cls) -> "AfmoeConfig":
        """CI-sized: compiles in seconds on the CPU. Held: all 8 experts;
        a window of 24 tokens, so a test at page_size 8 wraps its ring."""
        return cls(vocab_size=512, dim=64, n_layers=5, first_dense=1,
                   layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
                   n_heads=4, n_kv_heads=2, head_dim=16, window=24,
                   dense_dim=128, n_experts=8, experts_held=(0, 8),
                   experts_per_token=2, expert_dim=32, shared_dim=32,
                   max_seq_len=256, dtype="float32")

    @classmethod
    def trinity_large_preview_ep8(cls) -> "AfmoeConfig":
        """Trinity-Large-Preview at its published widths, cut to one v5e
        chip as benchmark/configs/trinity-large-preview-ep8.json states:
        eight chips share each layer, this one holds experts 0-31 of 256
        and an eighth of the vocabulary; block 0 (dense, sliding) and one
        whole period of the expert blocks (3 sliding : 1 full)."""
        return cls(vocab_size=25024, n_layers=5, first_dense=1,
                   layer_types=(SLIDING, SLIDING, SLIDING, SLIDING, FULL),
                   experts_held=(0, 32), max_seq_len=13312)

    def matrix_params(self) -> Dict[str, int]:
        """Matrix parameters of a block's attention, of the dense FFN, and
        of an expert FFN as held and as a token meets it (router, shared
        expert, its k picks)."""
        D, q = self.dim, self.n_heads * self.head_dim
        return {
            "attention": 3 * D * q + 2 * D * self.n_kv_heads * self.head_dim,
            "dense": 3 * D * self.dense_dim,
            **experts.expert_params(self),
        }

    def param_count(self) -> int:
        """The parameters a TOKEN meets (the utilization ledger's 2 P flops
        a token): attention, the dense FFN, the router, the shared expert
        and the share of its k picks that falls on held experts."""
        m = self.matrix_params()
        return (self.n_layers * m["attention"]
                + self.first_dense * m["dense"]
                + self.expert_layers * m["experts_met"]
                + self.dim * self.vocab_size)

    def page_groups(self):
        """(`full`, `window`): the blocks of each kind, a group each (a
        kind no block has is left out). `full` first: the engine's
        `allocator` is the first group's without a window."""
        from .protocol import PageGroup

        full = self.n_layers - self.window_layers
        groups = ((PageGroup("full", full),) if full else ()) + (
            (PageGroup("window", self.window_layers, self.window),)
            if self.window_layers else ())
        return groups

    def group_of(self, layer: int) -> Tuple[int, int]:
        """(the group of block `layer`, its index among that group's
        blocks)."""
        kind = self.layer_types[layer]
        names = [group.name for group in self.page_groups()]
        group = names.index("window" if kind == SLIDING else "full")
        return group, sum(k == kind for k in self.layer_types[:layer])

    def paged_model(self):
        from .protocol import PagedModel, kv_planes

        def paged_prefill(params, tokens, lengths, mesh=None):
            last, windows = prefill(params, self, tokens, lengths)
            return last, windows, ()

        def paged_decode(params, tokens, positions, pools, table, state,
                         tail, step, mesh=None):
            # a family of one group is handed its one table as it is
            tables = table if isinstance(table, tuple) else (table,)
            logits, tail, counters = decode_step(
                params, self, tokens, positions, pools, tables, tail, step)
            return logits, tail, state, counters

        return PagedModel(
            family="afmoe", program_tag="afmoe",
            planes=kv_planes(self.n_kv_heads, self.head_dim),
            groups=self.page_groups(), state_shapes=lambda slots: (),
            prefill=paged_prefill, decode=paged_decode, counters=COUNTERS,
            describe=lambda counts, steps: describe(self, counts, steps),
            refuses=REFUSES)


# what the family cannot do yet, refused by name at construction: each needs
# a window group's ring where today one table of whole sequences is assumed
_RING = ("a window group keeps a ring of pages, not a page for every 128 "
         "tokens of the prompt: ")
REFUSES = {
    "prefix_cache": _RING + "a shared prefix's pages are whole prefixes, "
                    "and the tail's prefill has no windowed form",
    "kv_host_tier": _RING + "the page blob (tpu/kvtier.py PageBlob) ships "
                    "one group's K and V a page",
    "disagg": _RING + "the hand-off ships one group's pages and lands them "
              "in table order",
    "speculative_tokens": "the verify window attends gathered pages of one "
                          "table, with no lower bound",
    "chunk_prefill_tokens": "a chunk attends the chunks before it through "
                            "one table; no windowed chunk program",
    "int8_weights": "no int8 weight path for this family",
    "kv_dtype": "the int8 read has no lower bound and no ring",
    "mesh": "no exchange of the expert and vocabulary shares yet, and the "
            "windowed read has no tp form",
}


def describe(cfg: AfmoeConfig, counts: Dict[str, int], steps: int):
    """`/debug/engine` "model": the window, the experts held and how the
    routing of `steps` decode steps fell."""
    return {"window": cfg.window, "window_layers": cfg.window_layers,
            **experts.describe(cfg, counts, steps)}


def layer_shapes(cfg: AfmoeConfig, dense: bool) -> Dict[str, tuple]:
    D, q = cfg.dim, cfg.n_heads * cfg.head_dim
    kv = cfg.n_kv_heads * cfg.head_dim
    shapes = {"in_norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
              "attn_gate": (D, q), "q_norm": (cfg.head_dim,),
              "k_norm": (cfg.head_dim,), "wo": (q, D),
              "post_attn_norm": (D,), "pre_mlp_norm": (D,),
              "post_mlp_norm": (D,)}
    if dense:
        return {**shapes, "w_gate": (D, cfg.dense_dim),
                "w_up": (D, cfg.dense_dim), "w_down": (cfg.dense_dim, D)}
    return {**shapes, **experts.expert_shapes(cfg)}


def afmoe_init(cfg: AfmoeConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init params, a jitted call a block."""
    return init_blocks(
        cfg, seed, [i < cfg.first_dense for i in range(cfg.n_layers)],
        lambda key, dense: seeded_block(key, layer_shapes(cfg, dense),
                                        np_dtype(cfg.dtype)))


# for models/families.py
PRESETS = {"afmoe-debug": AfmoeConfig.debug,
           "trinity-large-preview-ep8": AfmoeConfig.trinity_large_preview_ep8}
init = afmoe_init


# -- attention ----------------------------------------------------------------
def _embed(params, tokens, cfg: AfmoeConfig):
    """E[token] * sqrt(D) (`mup_enabled`), in the model's dtype."""
    x = params["tok_emb"][tokens]
    return (x.astype(jnp.float32) * math.sqrt(cfg.dim)).astype(x.dtype)


def _qkvg(x, w, positions, sliding: bool, cfg: AfmoeConfig):
    """x [K, T, D] (normed), positions [K, T] -> q [K, T, H, dh], k, v
    [K, T, Hkv, dh], gate [K, T, H dh] float32 (the sigmoid taken): q and
    k normed a head, and turned on a sliding block only."""
    lead = x.shape[:-1]
    q = (x @ w["wq"]).reshape(*lead, cfg.n_heads, cfg.head_dim)
    k = (x @ w["wk"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ w["wv"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    q = rms_norm(q, w["q_norm"], cfg.rms_eps)
    k = rms_norm(k, w["k_norm"], cfg.rms_eps)
    if sliding:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    gate = jax.nn.sigmoid((x @ w["attn_gate"]).astype(jnp.float32))
    return q, k, v, gate


def attention_prefill(x, w, sliding: bool, cfg: AfmoeConfig):
    """x [K, T, D] (normed): causal attention over the fresh window (the
    padding is on the right, so no real token sees it), within the window
    on a sliding block, gated. Returns (out [K, T, D] before its norm,
    k, v [K, Hkv, dh, T]: the layout the page writer takes)."""
    K, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T))
    q, k, v, gate = _qkvg(x, w, positions, sliding, cfg)
    window = cfg.window if sliding and cfg.window < T else None
    if cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention

        attn = flash_attention(q, k, v, True, *FLASH_BLOCKS, window=window)
    else:
        from ..ops.flash_attention import attention_reference

        attn = attention_reference(q, k, v, causal=True, window=window)
    attn = (attn.reshape(K, T, -1).astype(jnp.float32) * gate).astype(x.dtype)
    return attn @ w["wo"], k.transpose(0, 2, 3, 1), v.transpose(0, 2, 3, 1)


def attention_decode(x, w, positions, sliding: bool, pools, table, lengths,
                     tail, tail_lens, layer: int, cfg: AfmoeConfig):
    """x [B, D] (normed); pools, tail (k, v) of the block's GROUP, `layer`
    its index there, table that group's. The token's K and V go into the
    decode block's tail as token tail_lens[b] - 1; the read attends
    lengths[b] tokens in pages and tail_lens[b] in the tail, on a sliding
    block from position - window + 1 on, through the ring.
    Returns (out [B, D] before its norm, tail)."""
    from ..ops.paged_attention import paged_attention_in_block
    from .protocol import ring_pages

    q, k, v, gate = _qkvg(x[:, None], w, positions[:, None], sliding, cfg)
    windowed = {}
    if sliding:
        windowed = {"window": cfg.window,
                    "ring": ring_pages(cfg.window, pools[0].shape[-1])}
    attn, *tail = paged_attention_in_block(
        q[:, 0], k[:, 0], v[:, 0], *pools, *tail, table, lengths, tail_lens,
        layer=layer, **windowed)
    attn = (attn.reshape(x.shape[0], -1).astype(jnp.float32)
            * gate[:, 0]).astype(x.dtype)
    return attn @ w["wo"], tuple(tail)


# -- the stack ----------------------------------------------------------------
def _pieces(T: int, most: int = PIECE) -> int:
    """How many equal pieces a window of T tokens is cut into so that none
    is over `most` tokens."""
    n = -(-T // most)
    while T % n:
        n += 1
    return n


def _ffn_in_pieces(x, w, real, cfg: AfmoeConfig):
    """`ffn_prefill` over a window's tokens a piece at a time, one piece
    after another: its temporaries (the dense hidden, the grouped experts'
    sorted rows and their float32 products) are a piece's."""
    K, T, D = x.shape
    n = _pieces(T)
    if n == 1:
        return ffn_prefill(x, w, real, cfg)
    cut = (x.reshape(K, n, T // n, D).swapaxes(0, 1),
           real.reshape(K, n, T // n).swapaxes(0, 1))
    out = jax.lax.map(lambda piece: ffn_prefill(piece[0], w, piece[1], cfg),
                      cut)
    return out.swapaxes(0, 1).reshape(K, T, D)


def prefill(params, cfg: AfmoeConfig, tokens, lengths):
    """tokens [K, T] right-padded; lengths [K]. Returns (last logits
    [K, V] float32, the windows group-major as the pools lie: k, v
    [group layers, K, Hkv, dh, T] a group)."""
    K, T = tokens.shape
    real = jnp.arange(T)[None, :] < lengths[:, None]
    x = _embed(params, tokens, cfg)
    kept = [([], []) for _ in cfg.page_groups()]
    for layer, w in enumerate(params["layers"]):
        sliding = cfg.layer_types[layer] == SLIDING
        out, k, v = attention_prefill(
            rms_norm(x, w["in_norm"], cfg.rms_eps), w, sliding, cfg)
        group, _ = cfg.group_of(layer)
        kept[group][0].append(k)
        kept[group][1].append(v)
        x = x + rms_norm(out, w["post_attn_norm"], cfg.rms_eps)
        out = _ffn_in_pieces(rms_norm(x, w["pre_mlp_norm"], cfg.rms_eps), w,
                             real, cfg)
        x = x + rms_norm(out, w["post_mlp_norm"], cfg.rms_eps)
    last = x[jnp.arange(K), lengths - 1]
    windows = tuple(jnp.stack(plane) for planes in kept for plane in planes)
    return head(last, params, cfg.rms_eps), windows


def decode_step(params, cfg: AfmoeConfig, tokens, positions, pools, tables,
                tail, step):
    """One token a row, step `step` of a decode block. tokens, positions
    [B]; pools (k, v a group, group-major) as the block found them, read
    only; tables a [B, NP] a group (a row that starts at page 0 holds no
    request); tail the block's tails, as the pools lie
    (models/protocol.py). Returns (logits [B, V] float32, tail, counters
    [len(COUNTERS)] int32)."""
    live, lengths, tail_lens = live_and_attended(tables[0], positions, step)
    x = _embed(params, tokens, cfg)
    tail = list(tail)
    counted = jnp.zeros((3,), jnp.int32)
    for layer, w in enumerate(params["layers"]):
        group, index = cfg.group_of(layer)
        mine = slice(2 * group, 2 * group + 2)
        out, tail[mine] = attention_decode(
            rms_norm(x, w["in_norm"], cfg.rms_eps), w, positions,
            cfg.layer_types[layer] == SLIDING, pools[mine], tables[group],
            lengths, tail[mine], tail_lens, index, cfg)
        x = x + rms_norm(out, w["post_attn_norm"], cfg.rms_eps)
        out, seen = ffn_decode(rms_norm(x, w["pre_mlp_norm"], cfg.rms_eps), w,
                               live, cfg)
        counted = counted + seen
        x = x + rms_norm(out, w["post_mlp_norm"], cfg.rms_eps)
    counters = experts.step_counters(live, counted)
    return head(x, params, cfg.rms_eps), tuple(tail), counters
