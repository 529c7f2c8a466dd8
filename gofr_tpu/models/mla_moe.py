"""mla_moe family (DeepSeek-V3's form; JoyAI-LLM-Flash is the published
model the benchmark runs): blocks `h = x + MLA(RMSNorm(x))`,
`y = h + FFN(RMSNorm(h))`, the first `first_dense` blocks' FFN a dense
SwiGLU and every later one sparse experts plus one shared expert; after
the last block a final RMSNorm and an untied head.

Latent attention (MLA), H heads: `c_q = RMSNorm(x W_qa)`;
`[q_nope | q_rope]_h = c_q W_qb`; `[c_kv | k_r] = x W_kva`;
`c_kv <- RMSNorm(c_kv)`; `q_rope, k_r <- RoPE` (interleaved pairs, k_r ONE
vector shared by all heads); `[k_nope | v]_h = c_kv W_kvb`;
`o_h = softmax((q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)) v_h`;
out `concat_h(o_h) W_o`. `rope_scaling` None (JoyAI-LLM-Flash): plain RoPE and
that scale. A `YarnScaling` (Xing4.0-29B-A4B), as DeepSeek-V3's modelling
code has it: the pair i turns by `theta_i (1 - r_i) + (theta_i / factor) r_i`,
`r_i` a ramp from 0 at pair `low` to 1 at pair `high` (the pairs that turn
`beta_fast` and `beta_slow` times over the original context); cos and sin
unscaled where `mscale == mscale_all_dim`; the scores' scale times
`(0.1 mscale_all_dim ln factor + 1)^2`, in BOTH forms below.

What a token keeps is `c_kv` after its norm and `k_r` after its rotation:
ONE plane of 1 x (kv_rank + rope_dim) a block in the engine's pages
(models/protocol.py `planes`), 576 values against 32 heads of K (192) and
V (128). The two phases compute the same attention in two forms:

- `prefill`: the published, NON-absorbed form over the fresh window: K and
  V of every head made from the window's latents, causal flash attention
  with key width 192 and value width 128 (ops/flash_attention.py takes the
  two widths), the window's `[c_kv | k_r]` out to the page writer.
- `decode_step`: the absorbed form. W_kvb's key half is folded into the
  query (`q'_h = q_nope,h W^K_h^T`, kv_rank wide), scores are
  `q'_h . c_kv + q_rope,h . k_r`, the weighted sum is taken over `c_kv`
  and only then put through `W^V_h`: ops/mla_read.py, one shared head read
  once for all H queries. `W^K_h` and `W^V_h` are views of `W_kvb`
  ([kv_rank, H, nope + v] sliced in the einsum), not second copies.

The FFN, dense or routed, is models/experts.py's in its gated form
(`y = sum_e w_e down_e(silu(gate_e x) * up_e x) + shared(x)`), the block
told which experts it holds (`experts_held`).

The residual path. `hc_mult` 1: one stream, `h = x + F(RMSNorm(x))` as
above. `hc_mult` n > 1 (manifold-constrained hyper-connections, mHC;
ops/mhc.py has the equations and the two kernels): the stream is n copies,
X [.., n D]; the embedding fans out to n equal copies; every sublayer (a
block's MLA, then its FFN) reads `u = sum_i H_pre[i] X[i]` through its own
input norm and writes `X'[i] = sum_j H_res[i, j] X[j] + H_post[i] F(..)`,
the three mappings made from the stream itself in float32 (H_res brought
near a doubly stochastic matrix by `hc_sinkhorn_iters` Sinkhorn rounds);
after the last block the copies are summed before the final norm. Six
float32 leaves a block: `attn_hc_phi` / `ffn_hc_phi` [2n + n^2, n D],
`.._hc_scale` [3], `.._hc_bias` [2n + n^2]. The kernels where `attn_impl`
is "flash", the same arithmetic in jax.numpy where it is "xla".

Weights: the tree models/blocks.py `init_blocks` lays out, `layer_shapes`
a block.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import experts
from .blocks import (head, init_blocks, live_and_attended, np_dtype, rms_norm,
                     seeded_block)
from .experts import COUNTERS, ffn_decode, ffn_prefill

__all__ = ["MlaMoeConfig", "YarnScaling", "mla_moe_init", "prefill",
           "decode_step", "COUNTERS", "HC_COUNTERS", "FLOAT32_LEAVES"]

# the mix's leaves of an `hc_mult` > 1 block, a sublayer: float32 always
HC_LEAVES = tuple(f"{sub}_hc_{leaf}" for sub in ("attn", "ffn")
                  for leaf in ("phi", "scale", "bias"))
FLOAT32_LEAVES = experts.FLOAT32_LEAVES + HC_LEAVES
# what an `hc_mult` > 1 decode step counts beside COUNTERS: (row, sublayer)
# pairs of live rows whose H_res logits met the clamp
HC_COUNTERS = COUNTERS + ("hc_clamped",)


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """`rope_scaling` of type "yarn", under the config.json's own keys."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def correction_range(self, rope_dim: int, theta: float):
        """(low, high): the pairs between which the frequencies blend."""
        def pair(turns):
            return (rope_dim * math.log(self.original_max_position_embeddings
                                        / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))
        return (max(math.floor(pair(self.beta_fast)), 0),
                min(math.ceil(pair(self.beta_slow)), rope_dim - 1))

    def blend(self, inv_freq, rope_dim: int, theta: float):
        """inv_freq [rope_dim / 2] plain -> blended with inv_freq / factor."""
        low, high = self.correction_range(rope_dim, theta)
        ramp = jnp.clip((jnp.arange(rope_dim // 2, dtype=jnp.float32) - low)
                        / max(high - low, 0.001), 0.0, 1.0)
        return inv_freq * (1.0 - ramp) + inv_freq / self.factor * ramp

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0

    @property
    def score_scale(self) -> float:
        """What the softmax scale is multiplied by."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0

    @property
    def rotation_scale(self) -> float:
        """What cos and sin are multiplied by: 1 where the two mscales are
        equal."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig(experts.HeldExperts):
    vocab_size: int = 129280
    dim: int = 2048
    n_layers: int = 40
    first_dense: int = 1                    # leading blocks with a dense FFN
    n_heads: int = 32
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    dense_dim: int = 7168
    n_experts: int = 256                    # the router's outputs
    experts_held: Tuple[int, int] = (0, 256)    # the range this chip holds
    experts_per_token: int = 8
    expert_dim: int = 768
    shared_dim: int = 768
    routed_scale: float = 2.5
    rope_theta: float = 32e6
    rope_scaling: Optional[YarnScaling] = None
    max_seq_len: int = 131072
    rms_eps: float = 1e-6
    # the residual path: copies of the stream (1: the plain path), and the
    # mix's Sinkhorn rounds, its eps and the clamp of H_res's logits
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    dtype: str = "bfloat16"
    # "xla" | "flash": the prefill window's attention and the residual mix
    attn_impl: str = "xla"

    def __post_init__(self):
        self.check_experts_held()
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError("first_dense counts leading blocks")
        if self.hc_mult < 1:
            raise ValueError("hc_mult counts the stream's copies")
        scaling = self.rope_scaling
        if scaling is not None and scaling.rotation_scale != 1.0:
            raise ValueError("YaRN with mscale != mscale_all_dim scales cos "
                             "and sin: not written down here")

    @property
    def kv_layers(self) -> int:
        """Every block keeps a latent plane in pages."""
        return self.n_layers

    @property
    def expert_layers(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def latent_dim(self) -> int:
        """What a token keeps a block: its normed latent and its rotated
        shared key."""
        return self.kv_rank + self.rope_dim

    @property
    def ffn_dim(self) -> int:
        """The widest activation a block makes (the capacity plan's
        prefill temporaries): the dense FFN, every head's q and k, or the
        residual stream's `hc_mult` copies."""
        return max(self.dense_dim if self.first_dense else 0,
                   self.n_heads * self.qk_dim,
                   self.hc_mult * self.dim if self.hc_mult > 1 else 0)

    @property
    def softmax_scale(self) -> float:
        """Of the scores, in both forms of the attention."""
        scale = 1.0 / math.sqrt(self.qk_dim)
        if self.rope_scaling is None:
            return scale
        return scale * self.rope_scaling.score_scale

    @property
    def hc_columns(self) -> int:
        """Values a sublayer's mix makes of the stream: H_pre, H_post,
        H_res (ops/mhc.py `columns`)."""
        from ..ops.mhc import columns

        return columns(self.hc_mult)

    state_bytes_per_slot = 0    # a sequence's only cached state is pages

    @classmethod
    def debug(cls) -> "MlaMoeConfig":
        """CI-sized: compiles in seconds on the CPU. Held: all 8 experts."""
        return cls(vocab_size=512, dim=64, n_layers=3, n_heads=4, q_rank=48,
                   kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
                   dense_dim=128, n_experts=8, experts_held=(0, 8),
                   experts_per_token=2, expert_dim=32, shared_dim=32,
                   rope_theta=10000.0, max_seq_len=256, dtype="float32")

    @classmethod
    def joyai_llm_flash_ep8(cls) -> "MlaMoeConfig":
        """JoyAI-LLM-Flash at its published widths, cut to one v5e chip as
        benchmark/configs/joyai-llm-flash-ep8.json states: eight chips
        share each layer, this one holds experts 0-31 of 256 and an eighth
        of the vocabulary; the dense block and 11 of the 39 expert
        blocks."""
        return cls(vocab_size=16160, n_layers=12, experts_held=(0, 32),
                   max_seq_len=5120)

    @classmethod
    def debug_hc(cls) -> "MlaMoeConfig":
        """`debug` with Xing4.0's two departures: four copies of the
        stream and YaRN (factor 4 over 64 positions)."""
        return dataclasses.replace(
            cls.debug(), hc_mult=4, first_dense=2, n_layers=4,
            rope_scaling=YarnScaling(4.0, 64, mscale=1.0,
                                     mscale_all_dim=1.0))

    @classmethod
    def xing4_0_29b_a4b_ep8(cls) -> "MlaMoeConfig":
        """Xing4.0-29B-A4B at its published widths, cut to one v5e chip as
        benchmark/configs/xing4.0-29b-a4b-ep8.json states: two pipeline
        stages of 20 blocks, eight chips sharing each layer; this one holds
        blocks 0-19 (2 dense, 18 expert), experts 0-7 of 64 and an eighth
        of the vocabulary."""
        return cls(vocab_size=16384, dim=3584, n_layers=20, first_dense=2,
                   q_rank=768, dense_dim=9216, n_experts=64,
                   experts_held=(0, 8), experts_per_token=4,
                   expert_dim=1024, shared_dim=1024, routed_scale=2.0,
                   rope_theta=10000.0,
                   rope_scaling=YarnScaling(64.0, 4096, 32.0, 1.0, 1.0, 1.0),
                   max_seq_len=1280, hc_mult=4)

    def matrix_params(self) -> Dict[str, int]:
        """Matrix parameters of a block's attention, of the dense FFN, and
        of an expert FFN as held and as a token meets it (router, shared
        expert, its k picks)."""
        D, H = self.dim, self.n_heads
        return {
            "attention": D * self.q_rank + self.q_rank * H * self.qk_dim
            + D * self.latent_dim
            + self.kv_rank * H * (self.nope_dim + self.v_dim)
            + H * self.v_dim * D,
            "dense": 3 * D * self.dense_dim,
            **experts.expert_params(self),
            # the two sublayers' phi of an `hc_mult` > 1 block (float32)
            "mix": 2 * self.hc_columns * self.hc_mult * D
            if self.hc_mult > 1 else 0,
        }

    def param_count(self) -> int:
        """The parameters a TOKEN meets (the utilization ledger's 2 P flops
        a token): attention, the dense FFN, the router, the shared expert
        and the share of its k picks that falls on held experts."""
        m = self.matrix_params()
        return (self.n_layers * (m["attention"] + m["mix"])
                + self.first_dense * m["dense"]
                + self.expert_layers * m["experts_met"]
                + self.dim * self.vocab_size)

    def paged_model(self):
        from .protocol import PagedModel, Plane, one_group

        def paged_prefill(params, tokens, lengths, mesh=None):
            last, latent = prefill(params, self, tokens, lengths)
            return last, (latent,), ()

        def paged_decode(params, tokens, positions, pools, table, state,
                         tail, step, mesh=None):
            logits, latent_tail, counters = decode_step(
                params, self, tokens, positions, pools[0], table, tail[0],
                step)
            return logits, (latent_tail,), state, counters

        return PagedModel(
            family="mla_moe", program_tag="mla-moe",
            planes=(Plane("latent", 1, self.latent_dim),),
            groups=one_group(self.n_layers), state_shapes=lambda slots: (),
            prefill=paged_prefill, decode=paged_decode,
            counters=COUNTERS if self.hc_mult == 1 else HC_COUNTERS,
            describe=lambda counts, steps: describe(self, counts, steps),
            refuses=REFUSES)


# what the family cannot do yet, refused by name at construction
_BLOB = ("the page blob (tpu/kvtier.py PageBlob) ships K and V of one "
         "shape: a one-plane latent page needs a blob version of its own")
REFUSES = {
    "prefix_cache": "a prefix hit prefills the prompt's tail against cached "
                    "pages (llama_prefill_paged_prefix): no such prefill in "
                    "MLA form yet",
    "kv_host_tier": _BLOB,
    "disagg": _BLOB,
    "speculative_tokens": "the verify window attends gathered K and V "
                          "pages; the checkpoint's own drafting block "
                          "(num_nextn_predict_layers) is not loaded",
    "chunk_prefill_tokens": "a chunk's temporaries are K and V a layer; "
                            "the latent window has no chunk program",
    "int8_weights": "no int8 weight path for this family",
    "kv_dtype": "the int8 pools quantize K and V a head with a scale "
                "each; the latent plane has no quantized read",
    "mesh": "no exchange of the expert and vocabulary shares yet, and the "
            "one latent head cannot split over tp",
}


def describe(cfg: MlaMoeConfig, counts: Dict[str, int], steps: int):
    """`/debug/engine` "model": the experts held, how the routing of
    `steps` decode steps fell, and the residual path where the stream has
    copies."""
    out = experts.describe(cfg, counts, steps)
    if cfg.hc_mult > 1:
        out["residual"] = {"streams": cfg.hc_mult,
                           "sinkhorn_iters": cfg.hc_sinkhorn_iters,
                           "clamp": list(cfg.hc_clamp)}
        mixes = counts.get("rows", 0) * 2 * cfg.n_layers
        if mixes:
            # the share of (live row, sublayer) mixes whose H_res logits
            # met the clamp
            out["residual"]["clamped_share"] = counts["hc_clamped"] / mixes
    return out


def layer_shapes(cfg: MlaMoeConfig, dense: bool) -> Dict[str, tuple]:
    D, H = cfg.dim, cfg.n_heads
    shapes = {"attn_norm": (D,), "wq_a": (D, cfg.q_rank),
              "q_norm": (cfg.q_rank,), "wq_b": (cfg.q_rank, H * cfg.qk_dim),
              "wkv_a": (D, cfg.latent_dim), "kv_norm": (cfg.kv_rank,),
              "wkv_b": (cfg.kv_rank, H * (cfg.nope_dim + cfg.v_dim)),
              "wo": (H * cfg.v_dim, D), "ffn_norm": (D,)}
    if cfg.hc_mult > 1:
        C = cfg.hc_columns
        for sub in ("attn", "ffn"):
            shapes.update({f"{sub}_hc_phi": (C, cfg.hc_mult * D),
                           f"{sub}_hc_scale": (3,), f"{sub}_hc_bias": (C,)})
    if dense:
        return {**shapes, "w_gate": (D, cfg.dense_dim),
                "w_up": (D, cfg.dense_dim), "w_down": (cfg.dense_dim, D)}
    return {**shapes, **experts.expert_shapes(cfg)}


def mla_moe_init(cfg: MlaMoeConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init params, a jitted call a block."""
    def mix_leaf(name, shape, keys):
        if name.endswith("_hc_phi"):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(shape[1]))
        if name.endswith("_hc_scale"):
            return jnp.ones(shape, jnp.float32)
        if name.endswith("_hc_bias"):
            return 0.5 * jax.random.normal(next(keys), shape, jnp.float32)
        return None

    return init_blocks(
        cfg, seed, [i < cfg.first_dense for i in range(cfg.n_layers)],
        lambda key, dense: seeded_block(key, layer_shapes(cfg, dense),
                                        np_dtype(cfg.dtype), mix_leaf))


# for models/families.py
PRESETS = {"mla-moe-debug": MlaMoeConfig.debug,
           "joyai-llm-flash-ep8": MlaMoeConfig.joyai_llm_flash_ep8,
           "mla-moe-hc-debug": MlaMoeConfig.debug_hc,
           "xing4.0-29b-a4b-ep8": MlaMoeConfig.xing4_0_29b_a4b_ep8}
init = mla_moe_init


# -- attention ----------------------------------------------------------------
# (block_q, block_kv) of the prefill's flash kernel, clamped to the window:
# this family's windows are thousands of tokens and every head has K and V of
# its own, so the kernel's time is its blocks' MXU passes. On a v5e at 4,096
# tokens, 32 heads, widths 192 / 128: (128, 128) 7.3 ms a block of the model,
# (512, 128) 4.0, (512, 256) 2.8, (512, 512) 2.05, (1024, 256) 2.8 (PERF.md
# section 6, PR 31). The kernel's own default stays what the short windows of
# the other families were measured with.
FLASH_BLOCKS = (512, 512)


def rope_pairs(x, positions, theta: float,
               scaling: Optional[YarnScaling] = None):
    """RoPE over interleaved pairs: (x[2i], x[2i + 1]) turns by
    position * theta^(-2i / d), the frequencies blended where `scaling`
    (YaRN) is given. x [..., d]; positions broadcast against x's leading
    dims. (The published code permutes q and k alike to the half-split
    order first: every q . k is the same.)"""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if scaling is not None:
        inv_freq = scaling.blend(inv_freq, x.shape[-1], theta)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], half, 2)
    even, odd = pairs[..., 0], pairs[..., 1]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def _queries(x, w, positions, cfg: MlaMoeConfig):
    """(q_nope [..., H, nope], q_rope [..., H, rope] rotated); positions
    shaped as x's leading dims."""
    c_q = rms_norm(x @ w["wq_a"], w["q_norm"], cfg.rms_eps)
    q = (c_q @ w["wq_b"]).reshape(*x.shape[:-1], cfg.n_heads, cfg.qk_dim)
    q_nope, q_rope = q[..., :cfg.nope_dim], q[..., cfg.nope_dim:]
    return q_nope, rope_pairs(q_rope, positions[..., None], cfg.rope_theta,
                              cfg.rope_scaling)


def _latent(x, w, positions, cfg: MlaMoeConfig):
    """[..., kv_rank + rope]: what a token keeps, c_kv normed | k_r
    rotated."""
    kv = x @ w["wkv_a"]
    c_kv = rms_norm(kv[..., :cfg.kv_rank], w["kv_norm"], cfg.rms_eps)
    k_r = rope_pairs(kv[..., cfg.kv_rank:], positions, cfg.rope_theta,
                     cfg.rope_scaling)
    return jnp.concatenate([c_kv, k_r], axis=-1)


def _kv_b(w, cfg: MlaMoeConfig):
    """W_kvb as [kv_rank, H, nope + v]: its key half W^K and its value
    half W^V are slices of this view."""
    return w["wkv_b"].reshape(cfg.kv_rank, cfg.n_heads,
                              cfg.nope_dim + cfg.v_dim)


def attention_prefill(x, w, cfg: MlaMoeConfig):
    """x [K, T, D] (normed): the published form over the fresh window,
    causal (the padding is on the right, so no real token sees it).
    Returns (out [K, T, D], latent [K, 1, w, T]: the layout the page
    writer takes)."""
    K, T, _ = x.shape
    H = cfg.n_heads
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (K, T))
    q_nope, q_rope = _queries(x, w, positions, cfg)
    latent = _latent(x, w, positions, cfg)                   # [K, T, w]
    kv = jnp.einsum("ktr,rhn->kthn", latent[..., :cfg.kv_rank],
                    _kv_b(w, cfg)).astype(x.dtype)
    k_r = jnp.broadcast_to(latent[:, :, None, cfg.kv_rank:],
                           (K, T, H, cfg.rope_dim))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)           # [K,T,H,192]
    k = jnp.concatenate([kv[..., :cfg.nope_dim], k_r], axis=-1)
    v = kv[..., cfg.nope_dim:]                               # [K,T,H,128]
    # the kernels' own scale is 1 / sqrt(qk_dim): named only where YaRN
    # changes it, so a config without it traces the call it always did
    scaled = ({} if cfg.rope_scaling is None
              else {"scale": cfg.softmax_scale})
    if cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention

        attn = flash_attention(q, k, v, True, *FLASH_BLOCKS, **scaled)
    else:
        from ..ops.flash_attention import attention_reference

        attn = attention_reference(q, k, v, causal=True, **scaled)
    out = attn.reshape(K, T, H * cfg.v_dim) @ w["wo"]
    return out, latent.transpose(0, 2, 1)[:, None]


def attention_decode(x, w, positions, pool, table, lengths, tail, tail_lens,
                     layer: int, cfg: MlaMoeConfig):
    """x [B, D] (normed): the absorbed form. The token's latent goes into
    the decode block's tail as token tail_lens[b] - 1; the read attends
    lengths[b] tokens in pages and tail_lens[b] in the tail.
    Returns (out [B, D], tail)."""
    from ..ops.mla_read import mla_read

    q_nope, q_rope = _queries(x, w, positions, cfg)
    new = _latent(x, w, positions, cfg)[:, None]             # [B, 1, w]
    kv_b = _kv_b(w, cfg)
    folded = jnp.einsum("bhn,rhn->bhr", q_nope, kv_b[..., :cfg.nope_dim]
                        ).astype(x.dtype)                    # q' [B, H, r]
    attended, tail = mla_read(
        jnp.concatenate([folded, q_rope], axis=-1), new, pool, tail, table,
        lengths, tail_lens, value_width=cfg.kv_rank,
        scale=cfg.softmax_scale, layer=layer)
    heads = jnp.einsum("bhr,rhv->bhv", attended, kv_b[..., cfg.nope_dim:]
                       ).astype(x.dtype)
    return heads.reshape(x.shape[0], -1) @ w["wo"], tail


# -- the residual path --------------------------------------------------------
def _fan_out(x, cfg: MlaMoeConfig):
    """The embedding as the stream: `hc_mult` equal copies, [.., n D]."""
    return x if cfg.hc_mult == 1 else jnp.tile(x, cfg.hc_mult)


def _mix_forms(cfg: MlaMoeConfig):
    """(pre, post) of ops/mhc.py: the kernels where `attn_impl` is "flash",
    the same arithmetic in jax.numpy where it is "xla"."""
    from ..ops import mhc

    if cfg.attn_impl == "flash":
        return mhc.mhc_pre, mhc.mhc_post
    return mhc.mhc_pre_reference, mhc.mhc_post_reference


def _mix_in(x, w, sub: str, cfg: MlaMoeConfig):
    """What the sublayer `sub` ("attn" | "ffn") reads of the stream x, and
    what `_mix_out` needs to write back: (u [.., D], h). The plain path:
    the stream itself and nothing."""
    if cfg.hc_mult == 1:
        return x, None
    return _mix_forms(cfg)[0](
        x, w[f"{sub}_hc_phi"], w[f"{sub}_hc_scale"], w[f"{sub}_hc_bias"],
        n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
        clamp=cfg.hc_clamp, rms_eps=cfg.rms_eps)


def _mix_out(x, out, h, cfg: MlaMoeConfig):
    """The stream after a sublayer whose result is `out`."""
    if h is None:
        return x + out
    return _mix_forms(cfg)[1](x, out, h, n=cfg.hc_mult)


def residual_maps(x, w, cfg: MlaMoeConfig):
    """The mappings of one block's two sublayers on ONE stream x [rows, n D]:
    [2, rows, HW] float32 (ops/mhc.py: H_pre | H_post | H_res | the clamp's
    flag). Through the `_mix_in` that `prefill` and `decode_step` call: what
    a check holds against a reference's mappings, since no program hands its
    own out."""
    return jnp.stack([_mix_in(x, w, sub, cfg)[1] for sub in ("attn", "ffn")])


def _fan_in(x, cfg: MlaMoeConfig):
    """The stream's copies summed, [.., D], before the final norm."""
    if cfg.hc_mult == 1:
        return x
    copies = x.astype(jnp.float32).reshape(*x.shape[:-1], cfg.hc_mult, cfg.dim)
    return jnp.sum(copies, axis=-2).astype(x.dtype)


def _clamped(h, live, cfg: MlaMoeConfig):
    """How many live rows' H_res logits met the clamp in one mix: the flag
    that follows the mappings in h (ops/mhc.py), int32."""
    return jnp.sum(jnp.where(live, h[:, cfg.hc_columns], 0.0)
                   ).astype(jnp.int32)


def prefill(params, cfg: MlaMoeConfig, tokens, lengths):
    """tokens [K, T] right-padded; lengths [K]. Returns (last logits
    [K, V] float32, latent [n_layers, K, 1, w, T])."""
    K, T = tokens.shape
    real = jnp.arange(T)[None, :] < lengths[:, None]
    x = _fan_out(params["tok_emb"][tokens], cfg)
    latents = []
    for w in params["layers"]:
        u, h = _mix_in(x, w, "attn", cfg)
        out, latent = attention_prefill(
            rms_norm(u, w["attn_norm"], cfg.rms_eps), w, cfg)
        latents.append(latent)
        x = _mix_out(x, out, h, cfg)
        u, h = _mix_in(x, w, "ffn", cfg)
        x = _mix_out(x, ffn_prefill(rms_norm(u, w["ffn_norm"], cfg.rms_eps),
                                    w, real, cfg), h, cfg)
    last = _fan_in(x[jnp.arange(K), lengths - 1], cfg)
    return head(last, params, cfg.rms_eps), jnp.stack(latents)


def decode_step(params, cfg: MlaMoeConfig, tokens, positions, pool, table,
                tail, step):
    """One token a row, step `step` of a decode block. tokens, positions
    [B]; pool [n_layers, P, 1, w, ps] as the block found it, read only;
    table [B, NP] (a row that starts at page 0 holds no request); tail the
    block's latent tail (models/protocol.py). Returns (logits [B, V]
    float32, tail, counters int32: COUNTERS, or HC_COUNTERS where the
    stream has copies)."""
    live, lengths, tail_lens = live_and_attended(table, positions, step)
    x = _fan_out(params["tok_emb"][tokens], cfg)
    counted = jnp.zeros((3,), jnp.int32)
    clamped = 0
    for layer, w in enumerate(params["layers"]):
        u, h = _mix_in(x, w, "attn", cfg)
        out, tail = attention_decode(
            rms_norm(u, w["attn_norm"], cfg.rms_eps), w, positions, pool,
            table, lengths, tail, tail_lens, layer, cfg)
        x = _mix_out(x, out, h, cfg)
        u, g = _mix_in(x, w, "ffn", cfg)
        out, seen = ffn_decode(rms_norm(u, w["ffn_norm"], cfg.rms_eps), w,
                               live, cfg)
        counted = counted + seen
        x = _mix_out(x, out, g, cfg)
        if h is not None:
            clamped = clamped + _clamped(h, live, cfg) + _clamped(g, live, cfg)
    counters = experts.step_counters(
        live, counted, *((clamped,) if cfg.hc_mult > 1 else ()))
    return head(_fan_in(x, cfg), params, cfg.rms_eps), tail, counters
