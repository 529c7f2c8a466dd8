"""kda_moe family (Upstage's Solar Open 2; Solar-Open2-250B is the published
model the benchmark runs): blocks of two kinds in one stack, three Kimi
Delta Attention (KDA: a gated delta rule with a decay a CHANNEL, Kimi
Linear, arXiv:2510.26692) blocks to every gated grouped-query attention
block, sparse experts in every block, no rotary embedding anywhere. With
`RMS_x` an RMSNorm with its own weight, block l:

    h = x + Mixer_l(RMS_mixer(x));  y = h + MoE(RMS_ffn(h))

the mixer GQA where l is in `gqa_layers`, else KDA.

KDA (H heads of d_k = d_v, a convolution of W taps, a channel each):
    q~, k~, v~ = silu(conv(x W_qkv))            q, k, v of H heads each
    q = l2norm_head(q~) / sqrt(d_k), k = l2norm_head(k~), v = v~
    g = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)   float32, a channel
    b = 2 sigmoid(x W_b)                                  a head
    S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T;  o_t = S_t^T q
    out = (sigmoid((x W_ga) W_gb) (.) RMS_head(o)) W_o
GQA: q, k, v = x W_q, x W_k, x W_v; causal softmax(q k^T / sqrt(dh)), not
    turned, not normed; out = (sigmoid(x W_gate) (.) attn) W_o
MoE: models/experts.py's in its gated form (SwiGLU experts through
    ops/moe_experts.py and one shared expert).

On the serving path a sequence holds TWO kinds of cached state: pages of K
and V for the GQA blocks (one block in four) and, for every KDA block, the
matrix state S [H, d_k, d_v] float32 and the convolution's tail (the last
W - 1 columns of x W_qkv), a SLOT's worth beside the pool: 4 MiB and 144
KB a block at the published widths, the largest thing on the chip after
the weights (ops/kda_update.py says how the state is laid out).

- `prefill`: a [K, bucket] window, right-padded, from an empty state. The
  KDA blocks run the chunkwise form (ops/kda_chunk.py, jax.numpy); a padded
  position gets g = 0 and b = 0, so the state is the state as of each
  row's LAST REAL token, and the tail is taken at lengths - (W - 1) ...
  lengths - 1 (ops/short_conv.py).
- `decode_step`: one token a row; the state updated in place by
  ops/kda_update.py, live rows only (`attn_impl: "xla"`: its jax.numpy
  form), the GQA block's read through `paged_attention_in_block`.

Weights: the tree models/blocks.py `init_blocks` lays out, `layer_shapes`
a block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.short_conv import conv_decode, conv_prefill
from . import experts
from .blocks import (head, init_blocks, live_and_attended, np_dtype, rms_norm,
                     seeded_block)
from .experts import ffn_decode, ffn_prefill

# leaves held in float32 whatever `dtype` is: the decay's constants and the
# router's bias
FLOAT32_LEAVES = ("A_log", "dt_bias") + experts.FLOAT32_LEAVES

# what a decode step counts: the expert layer's four and the live rows
# whose KDA state was updated, summed over the KDA blocks
COUNTERS = experts.COUNTERS + ("kda_rows",)


@dataclasses.dataclass(frozen=True)
class KdaMoeConfig(experts.HeldExperts):
    vocab_size: int = 196608
    dim: int = 4096
    n_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))   # the softmax blocks
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128                 # d_k = d_v
    conv_kernel: int = 4
    gate_rank: int = 128                    # W_fa, W_ga: D x rank
    chunk_size: int = 64
    n_experts: int = 320                    # the router's outputs
    experts_held: Tuple[int, int] = (0, 320)    # the range this chip holds
    experts_per_token: int = 8
    expert_dim: int = 1280
    shared_dim: int = 1280
    routed_scale: float = 1.0
    max_seq_len: int = 1048576
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    attn_impl: str = "xla"      # "xla" | "flash": the prefill window's
                                # attention and the decode update's kernel

    def __post_init__(self):
        self.check_experts_held()
        if any(not 0 <= l < self.n_layers for l in self.gqa_layers):
            raise ValueError(f"gqa_layers {self.gqa_layers} names blocks "
                             f"of {self.n_layers}")

    @property
    def kv_layers(self) -> int:
        """The GQA blocks: the ones that keep K and V in pages."""
        return len(set(self.gqa_layers))

    @property
    def kda_layers(self) -> int:
        return self.n_layers - self.kv_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layers

    @property
    def kda_dim(self) -> int:
        """Channels of q (and of k, of v): heads x d_k."""
        return self.kda_heads * self.kda_head_dim

    @property
    def conv_dim(self) -> int:
        """q, k and v are all convolved."""
        return 3 * self.kda_dim

    @property
    def ffn_dim(self) -> int:
        """The widest activation a block makes (the capacity plan's prefill
        temporaries): q, k and v side by side."""
        return self.conv_dim

    @property
    def kda_state_bytes(self) -> int:
        return self.kda_layers * self.kda_dim * self.kda_head_dim * 4

    @property
    def conv_tail_bytes(self) -> int:
        return self.kda_layers * (self.conv_kernel - 1) * self.conv_dim * (
            2 if self.dtype != "float32" else 4)

    @property
    def state_bytes_per_slot(self) -> int:
        """What a sequence holds beside its pages (tpu/capacity.py)."""
        return self.kda_state_bytes + self.conv_tail_bytes

    @classmethod
    def debug(cls) -> "KdaMoeConfig":
        """CI-sized: compiles in seconds on the CPU. One period (GQA, KDA,
        KDA, KDA), all 8 experts held."""
        return cls(vocab_size=512, dim=64, n_layers=4, gqa_layers=(0,),
                   n_heads=4, n_kv_heads=2, head_dim=16, kda_heads=4,
                   kda_head_dim=16, gate_rank=16, chunk_size=16, n_experts=8,
                   experts_held=(0, 8), experts_per_token=2, expert_dim=32,
                   shared_dim=32, max_seq_len=256, dtype="float32")

    @classmethod
    def solar_open2_250b_ep8(cls) -> "KdaMoeConfig":
        """Solar-Open2-250B at its published widths, cut to one v5e chip as
        benchmark/configs/solar-open2-250b-ep8.json states: eight chips
        share each layer, this one holds experts 0-39 of 320 and an eighth
        of the vocabulary; the first pipeline stage's one period of four
        blocks (GQA, KDA, KDA, KDA) of 48."""
        return cls(vocab_size=24576, n_layers=4, gqa_layers=(0,),
                   experts_held=(0, 40), max_seq_len=1280)

    def matrix_params(self) -> Dict[str, int]:
        """Matrix parameters of a mixer of each kind, and of the expert FFN
        as held and as a token meets it (router, shared expert, its k
        picks)."""
        D, r = self.dim, self.gate_rank
        q = self.n_heads * self.head_dim
        return {
            "kda": 4 * D * self.kda_dim + 2 * r * (D + self.kda_dim)
            + D * self.kda_heads + self.conv_kernel * self.conv_dim,
            "attention": 3 * D * q + 2 * D * self.n_kv_heads * self.head_dim,
            **experts.expert_params(self),
        }

    def param_count(self) -> int:
        """The parameters a TOKEN meets (the utilization ledger's 2 P flops
        a token): the mixers, the router, the shared expert and the share
        of its k picks that falls on held experts."""
        m = self.matrix_params()
        return (self.kda_layers * m["kda"] + self.kv_layers * m["attention"]
                + self.expert_layers * m["experts_met"]
                + self.dim * self.vocab_size)

    def paged_model(self):
        from .protocol import PagedModel, kv_planes, one_group

        def paged_prefill(params, tokens, lengths, mesh=None):
            last, k, v, rows = prefill(params, self, tokens, lengths)
            return last, (k, v), rows

        return PagedModel(
            family="kda_moe", program_tag="kda-moe",
            planes=kv_planes(self.n_kv_heads, self.head_dim),
            groups=one_group(self.kv_layers),
            state_shapes=lambda slots: state_shapes(self, slots),
            prefill=paged_prefill,
            decode=lambda params, tokens, positions, pools, table, state,
            tail, step, mesh=None: decode_step(
                params, self, tokens, positions, *pools, table, state, tail,
                step),
            counters=COUNTERS,
            describe=lambda counts, steps: describe(self, counts, steps),
            refuses=REFUSES)


# what the family cannot do yet, refused by name at construction
_SNAPSHOT = ("a KDA state cannot be rebuilt from pages: it needs a snapshot "
             "of the matrix state (4 MiB a block) at the page boundary")
REFUSES = {
    "prefix_cache": _SNAPSHOT,
    "kv_host_tier": _SNAPSHOT,
    "disagg": "a hand-off ships page blobs; the KDA state and the "
              "convolution tail have no blob yet",
    "speculative_tokens": "a rejected draft would have to roll the KDA "
                          "state back: no snapshot yet",
    "chunk_prefill_tokens": "the KDA state a chunk ends in is not carried "
                            "into the next job's prefill",
    "int8_weights": "no int8 weight path for this family",
    "kv_dtype": "the KDA state is float32 and the int8 read has no gate; "
                "no lower-precision pool for one block in four",
    "mesh": "the expert and vocabulary shares have no exchange yet, and "
            "the KDA state's heads no tp form",
}


def describe(cfg: KdaMoeConfig, counts: Dict[str, int], steps: int):
    """`/debug/engine` "model": the blocks by kind, what a slot holds, the
    experts held, and how the routing and the KDA updates of `steps` decode
    steps fell."""
    out = {"blocks": {"kda": cfg.kda_layers, "gqa": cfg.kv_layers},
           "state_bytes_per_slot": cfg.state_bytes_per_slot,
           "kda_state_bytes_per_slot": cfg.kda_state_bytes,
           "conv_tail_bytes_per_slot": cfg.conv_tail_bytes,
           "kda_state_dtype": str(jnp.dtype(state_shapes(cfg, 1)[0][1])),
           **experts.describe(cfg, counts, steps)}
    if steps:
        out["kda_rows_per_step"] = counts["kda_rows"] / steps
    return out


def layer_shapes(cfg: KdaMoeConfig, gqa: bool) -> Dict[str, tuple]:
    D, r = cfg.dim, cfg.gate_rank
    ffn = {"ffn_norm": (D,), **experts.expert_shapes(cfg)}
    if gqa:
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {"mixer_norm": (D,), "wq": (D, q), "wk": (D, kv),
                "wv": (D, kv), "attn_gate": (D, q), "wo": (q, D), **ffn}
    return {"mixer_norm": (D,), "wqkv": (D, cfg.conv_dim),
            "conv_w": (cfg.conv_kernel, cfg.conv_dim),
            "f_a": (D, r), "f_b": (r, cfg.kda_dim),
            "dt_bias": (cfg.kda_dim,), "A_log": (cfg.kda_heads,),
            "w_beta": (D, cfg.kda_heads), "g_a": (D, r),
            "g_b": (r, cfg.kda_dim), "o_norm": (cfg.kda_head_dim,),
            "wo": (cfg.kda_dim, D), **ffn}


def kda_moe_init(cfg: KdaMoeConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init params, a jitted call a block. The decay's constants so
    that a channel's half-life runs from tens to thousands of tokens: A
    uniform in [1, 16] a head, the step log-uniform in [1e-4, 1e-2] a
    channel through the inverse softplus."""
    def decay_leaf(name, shape, keys):
        if name == "A_log":
            return jnp.log(jax.random.uniform(
                next(keys), shape, jnp.float32, 1.0, 16.0))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                next(keys), shape, jnp.float32, math.log(1e-4),
                math.log(1e-2)))
            return step + jnp.log(-jnp.expm1(-step))
        return None

    return init_blocks(
        cfg, seed, [i in cfg.gqa_layers for i in range(cfg.n_layers)],
        lambda key, gqa: seeded_block(key, layer_shapes(cfg, gqa),
                                      np_dtype(cfg.dtype), decay_leaf))


# for models/families.py
PRESETS = {"kda-moe-debug": KdaMoeConfig.debug,
           "solar-open2-250b-ep8": KdaMoeConfig.solar_open2_250b_ep8}
init = kda_moe_init


def state_shapes(cfg: KdaMoeConfig, slots: int):
    """((shape, dtype), ...) of the per-slot arrays, the slot axis second:
    the KDA state (float32 whatever the weights are held in: the delta rule
    multiplies it by I - b k k^T every token) and the convolution tail."""
    return (((cfg.kda_layers, slots, cfg.kda_heads, cfg.kda_head_dim,
              cfg.kda_head_dim), jnp.float32),
            ((cfg.kda_layers, slots, cfg.conv_kernel - 1, cfg.conv_dim),
             np_dtype(cfg.dtype)))


# -- the KDA mixer ------------------------------------------------------------
L2_EPS = 1e-6


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _qkv_proj(u, w):
    """x W_qkv in float32: q and k are normalised and feed a recurrence,
    where bfloat16's 8 bits would compound."""
    return jnp.dot(u, w["wqkv"], preferred_element_type=jnp.float32)


def _heads(conv, cfg: KdaMoeConfig):
    """silu of the convolved [.., 3 H dk] -> q, k (normalised a head, q
    scaled) and v, each [.., H, dk], float32."""
    lead = conv.shape[:-1]
    q, k, v = (x.reshape(*lead, cfg.kda_heads, cfg.kda_head_dim)
               for x in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    return _l2norm(q) / math.sqrt(cfg.kda_head_dim), _l2norm(k), v


def _low_rank(u, a, b):
    """(u W_a) W_b, the second product out in float32."""
    return jnp.dot(u @ a, b, preferred_element_type=jnp.float32)


def _decay_and_beta(u, w, cfg: KdaMoeConfig):
    """(g [.., H, dk] the log-decay a channel, <= 0; b [.., H] in (0, 2)),
    float32."""
    lead = u.shape[:-1]
    step = jax.nn.softplus(_low_rank(u, w["f_a"], w["f_b"]) + w["dt_bias"])
    g = -jnp.exp(w["A_log"])[:, None] * step.reshape(
        *lead, cfg.kda_heads, cfg.kda_head_dim)
    b = 2.0 * jax.nn.sigmoid(jnp.dot(u, w["w_beta"],
                                     preferred_element_type=jnp.float32))
    return g, b


def _kda_out(o, u, w, cfg: KdaMoeConfig):
    """o [.., H, dv] float32 -> (sigmoid gate (.) RMS_head(o)) W_o."""
    normed = o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_eps) \
        * w["o_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid(_low_rank(u, w["g_a"], w["g_b"]))
    gated = normed.reshape(*u.shape[:-1], cfg.kda_dim) * gate
    return gated.astype(u.dtype) @ w["wo"]


def kda_prefill(u, w, lengths, cfg: KdaMoeConfig):
    """u [K, T, D] (normed), right-padded to T; lengths [K]. The chunkwise
    form from an empty state. Returns (out [K, T, D], state [K, H, dk, dv]
    float32 as of each row's last real token, tail [K, W - 1, 3 H dk])."""
    from ..ops.kda_chunk import kda_chunk

    T = u.shape[1]
    real = jnp.arange(T)[None, :] < lengths[:, None]              # [K, T]
    conv, tail = conv_prefill(_qkv_proj(u, w), w["conv_w"], lengths, u.dtype)
    q, k, v = _heads(conv, cfg)
    g, b = _decay_and_beta(u, w, cfg)
    # padding neither decays nor writes
    g = jnp.where(real[:, :, None, None], g, 0.0)
    b = jnp.where(real[:, :, None], b, 0.0)
    o, state = kda_chunk(q, k, v, g, b, chunk=cfg.chunk_size)
    return _kda_out(o, u, w, cfg), state, tail


def kda_decode(u, w, state, tail, layer: int, live, cfg: KdaMoeConfig):
    """u [B, D] (normed); state [Lk, B, H, dk, dv]; tail [Lk, B, W - 1,
    3 H dk]; `layer` this block's index among the KDA blocks; live [B].
    Returns (out [B, D], state, tail)."""
    from ..ops.kda_update import kda_update, kda_update_reference

    conv, tail = conv_decode(tail, layer, _qkv_proj(u, w), w["conv_w"])
    q, k, v = _heads(conv, cfg)
    g, b = _decay_and_beta(u, w, cfg)
    update = kda_update_reference if cfg.attn_impl == "xla" else kda_update
    o, state = update(state, layer, jnp.exp(g), k, q, v, b, live)
    return _kda_out(o, u, w, cfg), state, tail


# -- the GQA mixer ------------------------------------------------------------
def _qkvg(x, w, cfg: KdaMoeConfig):
    """x [.., D] (normed) -> q [.., H, dh], k, v [.., Hkv, dh], gate
    [.., H dh] float32 (the sigmoid taken): not turned, not normed."""
    lead = x.shape[:-1]
    q = (x @ w["wq"]).reshape(*lead, cfg.n_heads, cfg.head_dim)
    k = (x @ w["wk"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ w["wv"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    gate = jax.nn.sigmoid((x @ w["attn_gate"]).astype(jnp.float32))
    return q, k, v, gate


def attention_prefill(x, w, cfg: KdaMoeConfig):
    """x [K, T, D] (normed): causal attention over the fresh window (the
    padding is on the right, so no real token sees it), gated. Returns
    (out, k, v [K, Hkv, dh, T]: the layout the page writer takes)."""
    K, T, _ = x.shape
    q, k, v, gate = _qkvg(x, w, cfg)
    if cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention

        attn = flash_attention(q, k, v, True)
    else:
        from ..ops.flash_attention import attention_reference

        attn = attention_reference(q, k, v, causal=True)
    attn = (attn.reshape(K, T, -1).astype(jnp.float32) * gate).astype(x.dtype)
    return attn @ w["wo"], k.transpose(0, 2, 3, 1), v.transpose(0, 2, 3, 1)


def attention_decode(x, w, k_pool, v_pool, table, lengths, tail, tail_lens,
                     layer: int, cfg: KdaMoeConfig):
    """x [B, D] (normed); `layer` this block's index among the GQA blocks
    = the pools' and the tail's leading axis. The token's K and V go into
    the decode block's tail as token tail_lens[b] - 1; the read attends
    lengths[b] tokens in pages and tail_lens[b] in the tail. Returns (out,
    tail)."""
    from ..ops.paged_attention import paged_attention_in_block

    q, k, v, gate = _qkvg(x, w, cfg)
    attn, *tail = paged_attention_in_block(
        q, k, v, k_pool, v_pool, *tail, table, lengths, tail_lens,
        layer=layer)
    attn = (attn.reshape(x.shape[0], -1).astype(jnp.float32)
            * gate).astype(x.dtype)
    return attn @ w["wo"], tuple(tail)


# -- the stack ----------------------------------------------------------------
def prefill(params, cfg: KdaMoeConfig, tokens, lengths):
    """tokens [K, T] right-padded; lengths [K]. Returns (last logits
    [K, V] float32, k, v [kv_layers, K, Hkv, dh, T], (state [kda_layers, K,
    H, dk, dv], tail [kda_layers, K, W - 1, 3 H dk]))."""
    K, T = tokens.shape
    real = jnp.arange(T)[None, :] < lengths[:, None]
    x = params["tok_emb"][tokens]
    ks, vs, states, tails = [], [], [], []
    for layer, w in enumerate(params["layers"]):
        normed = rms_norm(x, w["mixer_norm"], cfg.rms_eps)
        if layer in cfg.gqa_layers:
            out, k, v = attention_prefill(normed, w, cfg)
            ks.append(k)
            vs.append(v)
        else:
            out, state, tail = kda_prefill(normed, w, lengths, cfg)
            states.append(state)
            tails.append(tail)
        x = x + out
        x = x + ffn_prefill(rms_norm(x, w["ffn_norm"], cfg.rms_eps), w, real,
                            cfg)
    last = x[jnp.arange(K), lengths - 1]

    def stacked(parts, empty):
        # a stack may lack a kind: its stack is then empty, not missing
        return jnp.stack(parts) if parts else jnp.zeros(empty, x.dtype)

    kv = (0, K, cfg.n_kv_heads, cfg.head_dim, T)
    (state_like, _), (tail_like, _) = state_shapes(cfg, K)
    return (head(last, params, cfg.rms_eps), stacked(ks, kv), stacked(vs, kv),
            (stacked(states, state_like), stacked(tails, tail_like)))


def decode_step(params, cfg: KdaMoeConfig, tokens, positions, k_pool, v_pool,
                table, state, kv_tail, step):
    """One token a row, step `step` of a decode block. tokens, positions
    [B]; pools [kv_layers, P, Hkv, dh, ps] as the block found them, read
    only; table [B, NP] (a row that starts at page 0 holds no request);
    state = (kda, tail); kv_tail the block's (k_tail, v_tail)
    (models/protocol.py). Returns (logits [B, V] float32, kv_tail, state,
    counters [len(COUNTERS)] int32)."""
    kda, tail = state
    live, lengths, tail_lens = live_and_attended(table, positions, step)
    x = params["tok_emb"][tokens]
    counted = jnp.zeros((3,), jnp.int32)
    d = a = 0
    for layer, w in enumerate(params["layers"]):
        normed = rms_norm(x, w["mixer_norm"], cfg.rms_eps)
        if layer in cfg.gqa_layers:
            out, kv_tail = attention_decode(
                normed, w, k_pool, v_pool, table, lengths, kv_tail,
                tail_lens, a, cfg)
            a += 1
        else:
            out, kda, tail = kda_decode(normed, w, kda, tail, d, live, cfg)
            d += 1
        x = x + out
        out, seen = ffn_decode(rms_norm(x, w["ffn_norm"], cfg.rms_eps), w,
                               live, cfg)
        counted = counted + seen
        x = x + out
    rows = jnp.sum(live, dtype=jnp.int32)
    counters = jnp.concatenate([rows[None], counted,
                                (rows * cfg.kda_layers)[None]])
    return head(x, params, cfg.rms_eps), kv_tail, (kda, tail), counters
