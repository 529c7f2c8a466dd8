"""nemotron_h family (NVIDIA Nemotron-H / Nemotron 3 Nano): a stack of blocks
`x <- x + mixer(RMSNorm(x))`, one mixer a block, its kind given by a pattern
string: `M` Mamba-2, `E` sparse experts, `*` grouped-query attention (no
rotary embedding: the state-space blocks carry position). After the last
block a final RMSNorm and an untied head.

On the serving path a sequence holds TWO kinds of cached state: pages of K
and V for the attention blocks (the paged engine's pool, whose leading axis
counts attention blocks, not blocks) and, for every Mamba-2 block, a
recurrent state [N, heads * P] float32 and the convolution's tail (the last
W - 1 columns of xBC): fixed in size, a SLOT's worth, beside the pool
(ops/ssm_update.py says why the state is held transposed). The model
protocol (models/protocol.py) hands both to the engine:

- `prefill`: a [K, bucket] window, right-padded, from an empty state. The
  Mamba-2 blocks run the chunked (SSD) form in jax.numpy; a padded position
  gets dt = 0, so it decays nothing and adds nothing and the final state is
  the state as of each row's LAST REAL token; the tail is taken at
  lengths - (W - 1) ... lengths - 1. Returns the last real position's
  logits, K and V of the attention blocks, and each row's states.
- `decode_step`: one token a row over the pool and the per-slot states,
  the state updated in place by ops/ssm_update.py, live rows only.

An expert block is models/experts.py's in its ungated form (relu^2 experts
and shared expert): it routes over all `n_experts` and is told which it
holds (`experts_held`), the other chip of the pair adding its share.

Weights: the tree models/blocks.py `init_blocks` lays out, `layer_shapes`
a block; the experts' up matrices "w1" [held, F, D] are kept [out, in] as
the checkpoint stores them (ops/moe_experts.py says why).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.short_conv import conv_decode, conv_prefill
from . import experts
from .blocks import (head, init_blocks, live_and_attended, matrix, np_dtype,
                     rms_norm)
from .experts import COUNTERS, ffn_decode, ffn_prefill

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}

# leaves held in float32 whatever `dtype` is, as the published checkpoint
# keeps them: the state-space constants and the router's bias
FLOAT32_LEAVES = ("A_log", "D", "dt_bias") + experts.FLOAT32_LEAVES


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(experts.HeldExperts):
    vocab_size: int = 131072
    dim: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    n_experts: int = 128                    # the router's outputs
    experts_held: Tuple[int, int] = (0, 128)    # the range this chip holds
    experts_per_token: int = 6
    expert_dim: int = 1856
    shared_dim: int = 3712
    routed_scale: float = 2.5
    max_seq_len: int = 262144
    rms_eps: float = 1e-5
    dtype: str = "bfloat16"
    attn_impl: str = "xla"      # "xla" | "flash": the prefill window

    def __post_init__(self):
        self.check_experts_held()
        if set(self.pattern) - set(KINDS):
            raise ValueError(f"pattern {self.pattern!r}: use M, E and *")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def kv_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def mamba_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def expert_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    @property
    def in_proj_dim(self) -> int:
        return self.d_inner + self.conv_dim + self.mamba_heads

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def ffn_dim(self) -> int:
        """The widest activation a block makes (the capacity plan's
        prefill temporaries)."""
        return max(self.in_proj_dim, self.shared_dim)

    @property
    def state_bytes_per_slot(self) -> int:
        """What a sequence holds beside its pages (tpu/capacity.py)."""
        tail = self.conv_dim * (self.conv_kernel - 1) * (
            2 if self.dtype != "float32" else 4)
        return self.mamba_layers * (
            self.d_inner * self.state_size * 4 + tail)

    @classmethod
    def debug(cls) -> "NemotronHConfig":
        """CI-sized: compiles in seconds on the CPU. Held: all 8 experts."""
        return cls(vocab_size=512, dim=64, pattern="ME*E", n_heads=4,
                   n_kv_heads=2, head_dim=16, mamba_heads=4,
                   mamba_head_dim=16, n_groups=2, state_size=16,
                   chunk_size=16, n_experts=8, experts_held=(0, 8),
                   experts_per_token=2, expert_dim=32, shared_dim=64,
                   max_seq_len=256, dtype="float32")

    @classmethod
    def nano_30b_a3b_ep2(cls) -> "NemotronHConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B at its published widths, cut to
        one v5e chip as benchmark/configs/nemotron-3-nano-30b-a3b-ep2.json
        states: two chips share each layer, this one holds experts 0-63 of
        128 and half the vocabulary; the first 16 of 52 blocks."""
        return cls(vocab_size=65536, pattern="MEMEM*EMEMEM*EME",
                   experts_held=(0, 64), max_seq_len=2048)

    def matrix_params(self) -> Dict[str, int]:
        """Matrix parameters of one block of each kind, and of what a token
        meets in an expert block (router, shared expert, its k picks)."""
        D = self.dim
        return {
            "mamba": D * self.in_proj_dim + self.d_inner * D
            + self.conv_kernel * self.conv_dim,
            "attention": 2 * D * (self.n_heads + self.n_kv_heads)
            * self.head_dim,
            **experts.expert_params(self, gated=False),
        }

    def param_count(self) -> int:
        """The parameters a TOKEN meets (the utilization ledger's 2 P flops
        a token): the mixers, the router, the shared expert and the share of
        its k picks that falls on held experts; not the held experts it
        does not pick."""
        m = self.matrix_params()
        return (self.mamba_layers * m["mamba"]
                + self.kv_layers * m["attention"]
                + self.expert_layers * m["experts_met"]
                + self.dim * self.vocab_size)

    def paged_model(self):
        from .protocol import PagedModel, kv_planes, one_group

        def paged_prefill(params, tokens, lengths, mesh=None):
            last, k, v, rows = prefill(params, self, tokens, lengths)
            return last, (k, v), rows

        return PagedModel(
            family="nemotron_h", program_tag="nemotron-h",
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
_SNAPSHOT = ("a Mamba-2 state cannot be rebuilt from pages: it needs a "
             "snapshot of the recurrent state at the page boundary")
REFUSES = {
    "prefix_cache": _SNAPSHOT,
    "kv_host_tier": _SNAPSHOT,
    "disagg": "a hand-off ships page blobs; the recurrent state and the "
              "convolution tail have no blob yet",
    "speculative_tokens": "a rejected draft would have to roll the "
                          "recurrent state back: no snapshot yet",
    "chunk_prefill_tokens": "the state a chunk ends in is not carried into "
                            "the next job's prefill",
    "int8_weights": "no int8 weight path for this family",
    "mesh": "the expert and vocabulary shares have specs "
            "(parallel/sharding.py) but no exchange yet",
}


def describe(cfg: NemotronHConfig, counts: Dict[str, int], steps: int):
    """`/debug/engine` "model": what a slot holds, the experts held, and
    how the routing of `steps` decode steps fell (COUNTERS' sums), an
    expert block and step."""
    return {"state_bytes_per_slot": cfg.state_bytes_per_slot,
            **experts.describe(cfg, counts, steps)}


def layer_shapes(cfg: NemotronHConfig, kind: str) -> Dict[str, tuple]:
    D = cfg.dim
    if kind == "mamba":
        return {"norm": (D,), "in_proj": (D, cfg.in_proj_dim),
                "conv_w": (cfg.conv_kernel, cfg.conv_dim),
                "conv_b": (cfg.conv_dim,), "dt_bias": (cfg.mamba_heads,),
                "A_log": (cfg.mamba_heads,), "D": (cfg.mamba_heads,),
                "gate_norm": (cfg.d_inner,), "out_proj": (cfg.d_inner, D)}
    if kind == "experts":
        return {"norm": (D,), **experts.expert_shapes(cfg, gated=False)}
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return {"norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
            "wo": (q, D)}


def nemotron_h_init(cfg: NemotronHConfig, seed: int = 0) -> Dict[str, Any]:
    """Random-init params, a jitted call a block (a block's experts are
    1.3 GB at the published widths). The state-space constants as Mamba-2
    initialises them: A uniform in [1, 16], dt log-uniform in [1e-3, 1e-1]
    through the inverse softplus, D ones."""
    dt = np_dtype(cfg.dtype)

    def make(key, kind):
        shapes = layer_shapes(cfg, kind)
        keys = iter(jax.random.split(key, 8))
        out = {}
        for name, shape in shapes.items():
            if name in ("norm", "gate_norm"):
                out[name] = jnp.ones(shape, dt)
            elif name == "conv_b":
                out[name] = jnp.zeros(shape, dt)
            elif name == "D":
                out[name] = jnp.ones(shape, jnp.float32)
            elif name == "A_log":
                out[name] = jnp.log(jax.random.uniform(
                    next(keys), shape, jnp.float32, 1.0, 16.0))
            elif name == "dt_bias":
                step = jnp.exp(jax.random.uniform(
                    next(keys), shape, jnp.float32, math.log(1e-3),
                    math.log(1e-1)))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif name == "router_bias":
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = matrix(next(keys), shape,
                                   shape[-1] if name == "w1" else shape[-2],
                                   dt)
        return out

    return init_blocks(cfg, seed, [KINDS[mark] for mark in cfg.pattern], make)


# for models/families.py
PRESETS = {"nemotron-h-debug": NemotronHConfig.debug,
           "nemotron-3-nano-30b-a3b-ep2": NemotronHConfig.nano_30b_a3b_ep2}
init = nemotron_h_init


def state_shapes(cfg: NemotronHConfig, slots: int):
    """((shape, dtype), ...) of the per-slot arrays, the slot axis second:
    the recurrent state (float32 whatever the weights are held in: the
    vendor's serving note for the family asks for it) and the convolution
    tail."""
    return (((cfg.mamba_layers, slots, cfg.state_size, cfg.d_inner),
             jnp.float32),
            ((cfg.mamba_layers, slots, cfg.conv_kernel - 1, cfg.conv_dim),
             np_dtype(cfg.dtype)))


# -- mixers -------------------------------------------------------------------
def _gated_norm(y, z, weight, cfg: NemotronHConfig):
    """RMSNorm over each group of d_inner / G of y silu(z), float32."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = y.shape
    grouped = y.reshape(*shape[:-1], cfg.n_groups, cfg.d_inner // cfg.n_groups)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.rms_eps)
    return grouped.reshape(shape) * weight.astype(jnp.float32)


def _in_proj(u, w):
    """[z | xBC | dt] in float32: dt and B feed an exponential and a
    recurrence, where bfloat16's 8 bits would compound."""
    return jnp.dot(u, w["in_proj"], preferred_element_type=jnp.float32)


def _split_proj(proj, cfg: NemotronHConfig):
    return jnp.split(proj, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)


def _split_xbc(xBC, cfg: NemotronHConfig):
    gn = cfg.n_groups * cfg.state_size
    x, B, C = jnp.split(xBC, [cfg.d_inner, cfg.d_inner + gn], axis=-1)
    groups = (*xBC.shape[:-1], cfg.n_groups, cfg.state_size)
    return x, B.reshape(groups), C.reshape(groups)


def mamba_prefill(u, w, lengths, cfg: NemotronHConfig):
    """u [K, T, D] (normed), right-padded to T; lengths [K]. The chunked
    (SSD) form from an empty state. Returns (out [K, T, D], state
    [K, N, heads * P] float32 as of each row's last real token, tail
    [K, W - 1, conv_dim])."""
    K, T, _ = u.shape
    H, P, G, N = (cfg.mamba_heads, cfg.mamba_head_dim, cfg.n_groups,
                  cfg.state_size)
    Q = min(cfg.chunk_size, T)
    if T % Q:
        raise ValueError(f"window {T} is not a multiple of the chunk {Q}")
    z, xBC, dt = _split_proj(_in_proj(u, w), cfg)
    real = jnp.arange(T)[None, :] < lengths[:, None]              # [K, T]
    # the tail decode continues from: xBC (before the convolution) at
    # lengths - (W - 1) ... lengths - 1, zeros before the sequence's start
    conv, tail = conv_prefill(xBC, w["conv_w"], lengths, u.dtype)
    x, B, C = _split_xbc(jax.nn.silu(conv + w["conv_b"]), cfg)
    x = x.reshape(K, T, H, P).astype(jnp.float32)
    B, C = B.astype(jnp.float32), C.astype(jnp.float32)
    # padding neither decays nor adds: dt = 0 there
    dt = jnp.where(real[:, :, None], jax.nn.softplus(
        dt.astype(jnp.float32) + w["dt_bias"]), 0.0)              # [K, T, H]
    a = dt * -jnp.exp(w["A_log"])                                 # <= 0
    nc = T // Q
    x, dt, a = (v.reshape(K, nc, Q, *v.shape[2:]) for v in (x, dt, a))
    B, C = (v.reshape(K, nc, Q, G, N) for v in (B, C))
    cs = jnp.cumsum(a, axis=2)                                    # [K,c,Q,H]
    xdt = x * dt[..., None]                                       # [K,c,Q,H,P]
    per = H // G
    # within a chunk: y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s
    cb = jnp.einsum("kctgn,kcsgn->kcgts", C, B)                   # [K,c,G,Q,Q]
    decay = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None],
        cs[:, :, :, None, :] - cs[:, :, None, :, :], -jnp.inf))   # [K,c,t,s,H]
    scores = jnp.repeat(cb, per, axis=2).transpose(0, 1, 3, 4, 2) * decay
    y = jnp.einsum("kctsh,kcshp->kcthp", scores, xdt)
    # what a chunk adds to the state, and the state carried between chunks
    to_end = jnp.exp(cs[:, :, -1:, :] - cs)                       # [K,c,Q,H]
    Bh = jnp.repeat(B, per, axis=3)                               # [K,c,Q,H,N]
    added = jnp.einsum("kcsh,kcshp,kcshn->kchpn", to_end, xdt, Bh)
    total = jnp.exp(cs[:, :, -1, :])                              # [K,c,H]

    def carry(h, inputs):
        added_c, total_c = inputs
        return total_c[:, :, None, None] * h + added_c, h         # h BEFORE

    last, before = jax.lax.scan(
        carry, jnp.zeros((K, H, P, N), jnp.float32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(total, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)                           # [K,c,H,P,N]
    Ch = jnp.repeat(C, per, axis=3)
    y = y + jnp.einsum("kcth,kcthn,kchpn->kcthp", jnp.exp(cs), Ch, before)
    y = (y + w["D"][:, None] * x).reshape(K, T, H * P)
    out = _gated_norm(y, z, w["gate_norm"], cfg).astype(u.dtype) \
        @ w["out_proj"]
    state = last.transpose(0, 3, 1, 2).reshape(K, N, H * P)
    return out, state, tail


def mamba_decode(u, w, state, tail, layer: int, live, cfg: NemotronHConfig):
    """u [B, D] (normed); state [Lm, B, N, heads * P]; tail
    [Lm, B, W - 1, conv_dim]; `layer` this block's index among the Mamba-2
    blocks; live [B]. Returns (out [B, D], state, tail)."""
    from ..ops.ssm_update import ssm_update

    H, P = cfg.mamba_heads, cfg.mamba_head_dim
    z, xBC, dt = _split_proj(_in_proj(u, w), cfg)
    conv, tail = conv_decode(tail, layer, xBC, w["conv_w"])
    x, B, C = _split_xbc(jax.nn.silu(conv + w["conv_b"]), cfg)
    x = x.reshape(-1, H, P).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"])   # [B, H]
    decay = jnp.exp(dt * -jnp.exp(w["A_log"]))
    y, state = ssm_update(
        state, layer, jnp.repeat(decay, P, axis=1),
        (x * dt[:, :, None]).reshape(-1, H * P),
        B.astype(jnp.float32), C.astype(jnp.float32), live)
    y = y + (w["D"][None, :, None] * x).reshape(-1, H * P)
    out = _gated_norm(y, z, w["gate_norm"], cfg).astype(u.dtype) \
        @ w["out_proj"]
    return out, state, tail


def _qkv(x, w, cfg: NemotronHConfig):
    lead = x.shape[:-1]
    q = (x @ w["wq"]).reshape(*lead, cfg.n_heads, cfg.head_dim)
    k = (x @ w["wk"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ w["wv"]).reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def attention_prefill(x, w, cfg: NemotronHConfig):
    """x [K, T, D] (normed): causal attention over the fresh window (the
    padding is on the right, so no real token sees it). Returns (out, k, v
    [K, Hkv, dh, T]: the layout the page writer takes)."""
    K, T, _ = x.shape
    H, Hkv, dh, G = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.q_per_kv
    q, k, v = _qkv(x, w, cfg)
    if cfg.attn_impl == "flash":
        from ..ops.flash_attention import flash_attention

        attn = flash_attention(q, k, v, True)                     # [K,T,H,dh]
    else:
        scores = jnp.einsum("kthgd,kshd->khgts", q.reshape(K, T, Hkv, G, dh),
                            k, preferred_element_type=jnp.float32
                            ) / math.sqrt(dh)
        causal = jnp.tril(jnp.ones((T, T), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        attn = jnp.einsum("khgts,kshd->kthgd", probs.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(x.dtype)
    out = attn.reshape(K, T, H * dh) @ w["wo"]
    return out, k.transpose(0, 2, 3, 1), v.transpose(0, 2, 3, 1)


def attention_decode(x, w, k_pool, v_pool, table, lengths, tail, tail_lens,
                     layer: int, cfg: NemotronHConfig):
    """x [B, D] (normed); `layer` this block's index among the attention
    blocks = the pools' and the tail's leading axis. The token's K and V
    go into the decode block's tail as token tail_lens[b] - 1; the read
    attends lengths[b] tokens in pages and tail_lens[b] in the tail.
    Returns (out, tail)."""
    from ..ops.paged_attention import paged_attention_in_block

    q, k, v = _qkv(x, w, cfg)
    attn, *tail = paged_attention_in_block(
        q, k, v, k_pool, v_pool, *tail, table, lengths, tail_lens,
        layer=layer)
    return attn.reshape(x.shape[0], -1) @ w["wo"], tuple(tail)


def prefill(params, cfg: NemotronHConfig, tokens, lengths):
    """tokens [K, T] right-padded; lengths [K]. Returns (last logits
    [K, V] float32, k, v [kv_layers, K, Hkv, dh, T], (state
    [mamba_layers, K, N, heads * P], tail [mamba_layers, K, W - 1, c]))."""
    K, T = tokens.shape
    real = jnp.arange(T)[None, :] < lengths[:, None]
    x = params["tok_emb"][tokens]
    ks, vs, states, tails = [], [], [], []
    for mark, w in zip(cfg.pattern, params["layers"]):
        normed = rms_norm(x, w["norm"], cfg.rms_eps)
        if mark == "M":
            out, state, tail = mamba_prefill(normed, w, lengths, cfg)
            states.append(state)
            tails.append(tail)
        elif mark == "E":
            out = ffn_prefill(normed, w, real, cfg)
        else:
            out, k, v = attention_prefill(normed, w, cfg)
            ks.append(k)
            vs.append(v)
        x = x + out
    last = x[jnp.arange(K), lengths - 1]

    def stacked(parts, empty):
        # a pattern may lack a kind: its stack is then empty, not missing
        return jnp.stack(parts) if parts else jnp.zeros(empty, x.dtype)

    kv = (0, K, cfg.n_kv_heads, cfg.head_dim, T)
    (state_like, _), (tail_like, _) = state_shapes(cfg, K)
    return (head(last, params, cfg.rms_eps), stacked(ks, kv), stacked(vs, kv),
            (stacked(states, state_like), stacked(tails, tail_like)))


def decode_step(params, cfg: NemotronHConfig, tokens, positions, k_pool,
                v_pool, table, state, kv_tail, step):
    """One token a row, step `step` of a decode block. tokens, positions
    [B]; pools [kv_layers, P, Hkv, dh, ps] as the block found them, read
    only; table [B, NP] (a row that starts at page 0 holds no request);
    state = (ssm, tail); kv_tail the block's (k_tail, v_tail)
    (models/protocol.py). Returns (logits [B, V] float32, kv_tail, state,
    counters [len(COUNTERS)] int32)."""
    ssm, tail = state
    live, lengths, tail_lens = live_and_attended(table, positions, step)
    x = params["tok_emb"][tokens]
    counted = jnp.zeros((3,), jnp.int32)
    m = a = 0
    for mark, w in zip(cfg.pattern, params["layers"]):
        normed = rms_norm(x, w["norm"], cfg.rms_eps)
        if mark == "M":
            out, ssm, tail = mamba_decode(normed, w, ssm, tail, m, live, cfg)
            m += 1
        elif mark == "E":
            out, seen = ffn_decode(normed, w, live, cfg)
            counted = counted + seen
        else:
            out, kv_tail = attention_decode(
                normed, w, k_pool, v_pool, table, lengths, kv_tail,
                tail_lens, a, cfg)
            a += 1
        x = x + out
    counters = experts.step_counters(live, counted)
    return head(x, params, cfg.rms_eps), kv_tail, (ssm, tail), counters
