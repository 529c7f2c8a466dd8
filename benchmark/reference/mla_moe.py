"""Plain reference for the mla_moe family (DeepSeek-V3's form: JoyAI-LLM-
Flash, `model_type` joyai_llm_flash): blocks `h = x + MLA(RMSNorm(x))`,
`y = h + FFN(RMSNorm(h))`; the first `first_k_dense_replace` blocks' FFN a
dense SwiGLU, every later one sparse experts plus shared experts; a final
RMSNorm and an untied head. Written from the published config's keys and
the DeepSeek-V3 modelling code's description, straightforward jax.numpy in
float32 at the highest matmul precision: NON-absorbed attention, no kernel,
no cache, no batching, one sequence at a time, one block at a time, a head
and an expert at a time. It imports nothing of the program.

Latent attention (MLA), H heads: `c_q = RMSNorm(x W_qa)`;
`[q_nope | q_rope]_h = c_q W_qb` (nope + rope a head);
`[c_kv | k_r] = x W_kva` (kv_lora_rank + rope); `c_kv <- RMSNorm(c_kv)`;
`q_rope, k_r <- RoPE`: interleaved pairs (2i, 2i + 1) turn by
`position * theta^(-2i / rope)`, `k_r` ONE vector shared by all heads;
`[k_nope | v]_h = c_kv W_kvb` (nope + v a head);
`o_h = softmax((q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)) v_h`,
causal; out `concat_h(o_h) W_o`. `rope_scaling` is null: no YaRN, no
mscale. What a token has to keep for later tokens is `c_kv` after its norm
and `k_r` after its rotation: kv_lora_rank + rope values a block
(`facts`: `cache_bytes_per_token`, `mla_read_bytes`).

Expert FFN: `s = sigmoid(x W_r)` in float32; the `k` largest of `s + bias`
are picked (`topk_method` noaux_tc: the bias chooses and does not weigh;
`n_group = topk_group = 1`: no group limit); `w = s[picked]`,
`w <- scale w / sum(w)`; `y = sum_e w_e down_e(silu(gate_e x) * up_e x)
+ shared(x)`, the shared expert of the same gated form. The configuration
states which of the router's experts this chip HOLDS (`experts_held`, a
range): the router keeps every output and its k picks a token, the sum
runs over the picked experts that are held, and what the others would add
is left out (model-configs guide, section 4). `held=` of `expert_ffn`
takes any range, so a test adds the shares up.

Departures, each noted where it is made: the drafting block
(`num_nextn_predict_layers`) is not loaded (next-token serving does not
use it); the seeded weights depart from Normal(0, 1/fan_in) in two draws
(`_make_layer`).

Weights are the benchmark's own (`make_params`), {"tok_emb" [V, D],
"layers": [one dict a block], "final_norm" [D], "lm_head" [D, V]}; every
matrix [in, out] but the routed experts' three, "w1" (up), "wg" (gate),
"w2" (down), each [held, F, D]: up and gate [out, in] as the published
checkpoint stores a linear layer, down [in, out], so all three have D
minor. Matrices in the dtype they are served in, upcast as they are used;
the router's bias in float32.

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix rounded to int8 per output channel (the embedding per row).
"""

import functools
import math

import jax
import jax.numpy as jnp
from harness import weights


def dims_of(config: dict) -> dict:
    """The sizes the forward needs, from the config.json's own keys and the
    file's statement of what is held here."""
    lo, hi = config["experts_held"]
    if hi - lo != int(config["n_routed_experts"]):
        raise ValueError("experts_held and n_routed_experts differ")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("group-limited routing is not written down here")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not written down here")
    nope, rope_dim = (int(config["qk_nope_head_dim"]),
                      int(config["qk_rope_head_dim"]))
    if nope + rope_dim != int(config["qk_head_dim"]):
        raise ValueError("qk_head_dim is not nope + rope")
    return {
        "V": int(config["vocab_size"]), "D": int(config["hidden_size"]),
        "L": int(config["num_hidden_layers"]),
        "dense": int(config["first_k_dense_replace"]),
        "eps": float(config["rms_norm_eps"]),
        "H": int(config["num_attention_heads"]),
        "rq": int(config["q_lora_rank"]), "r": int(config["kv_lora_rank"]),
        "nope": nope, "rope": rope_dim, "dv": int(config["v_head_dim"]),
        "Fd": int(config["intermediate_size"]),
        "E": int(config["n_routed_experts_published"]), "lo": int(lo),
        "hi": int(hi), "k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "Fs": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "scale": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
    }


def is_dense(dims: dict, index: int) -> bool:
    return index < dims["dense"]


def layer_shapes(dims: dict, dense: bool) -> dict:
    D, H = dims["D"], dims["H"]
    shapes = {"attn_norm": (D,), "wq_a": (D, dims["rq"]),
              "q_norm": (dims["rq"],),
              "wq_b": (dims["rq"], H * (dims["nope"] + dims["rope"])),
              "wkv_a": (D, dims["r"] + dims["rope"]), "kv_norm": (dims["r"],),
              "wkv_b": (dims["r"], H * (dims["nope"] + dims["dv"])),
              "wo": (H * dims["dv"], D), "ffn_norm": (D,)}
    if dense:
        return {**shapes, "w_gate": (D, dims["Fd"]), "w_up": (D, dims["Fd"]),
                "w_down": (dims["Fd"], D)}
    expert = (dims["hi"] - dims["lo"], dims["F"], D)
    return {**shapes, "router": (D, dims["E"]), "router_bias": (dims["E"],),
            "w1": expert, "wg": expert, "w2": expert,
            "shared_gate": (D, dims["Fs"]), "shared_up": (D, dims["Fs"]),
            "shared_down": (dims["Fs"], D)}


def param_shapes(dims: dict) -> dict:
    return {"tok_emb": (dims["V"], dims["D"]), "final_norm": (dims["D"],),
            "lm_head": (dims["D"], dims["V"]),
            "layers": [layer_shapes(dims, is_dense(dims, i))
                       for i in range(dims["L"])]}


# The two departures from Normal(0, 1/fan_in), both in the DRAW and none in
# the forward (PERF.md section 2: the reasons were measured on nemotron_h,
# whose router does the same arithmetic):
# - A routed expert's down matrix at an eighth of the gain. The router
#   reads bfloat16 activations as the published code has it, so the program
#   and a float32 reference disagree on a token's last pick now and then;
#   with independent experts at full gain each swap parts the hidden
#   states and the gaps measure the routing, not the arithmetic.
# - Queries (W_qb) at TWICE the gain. With unit-variance scores a softmax
#   over thousands of keys is near uniform and attention adds little to
#   the stream: a row that attends another row's pages would serve nearly
#   the same tokens. nemotron_h draws its queries at gain 4, for 2
#   attention blocks of 16; here all 12 blocks attend, and at gain 4 a
#   rounding's error in the stream grows through every block (each
#   softmax turns a relative error e of its input into about 5 e of its
#   output): the first chip run read gap_mean 0.42 with 72 % of served
#   tokens not the reference's first (PERF.md section 6, PR 31). At gain 2
#   the growth is a quarter of that and a page table off by one still
#   moves the logits by several times what rounding does.
# The third of nemotron_h's (matrices after a never-negative activation
# centred over their inputs) is not needed: silu(gate x) * up x has both
# signs.
ROUTED_GAIN = 0.125
QUERY_GAIN = 2.0


def _make_layer(key, shapes: dict, dt):
    """One block's weights: Normal(0, 1/fan_in) matrices but for the two
    departures above, unit norms, a small router bias so that picking by
    `s + bias` and weighting by `s` differ."""
    keys = iter(jax.random.split(key, 16))
    out = {}
    for name, shape in shapes.items():
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dt)
        elif name == "router_bias":
            out[name] = 0.02 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)
        elif name in ("w1", "wg", "w2"):
            # an expert at a time: the float32 draws are one expert's
            fan_in = shape[1] if name == "w2" else shape[2]
            gain = ROUTED_GAIN if name == "w2" else 1.0
            out[name] = jax.lax.map(
                lambda k: (gain * weights.normal(
                    k, shape[1:], fan_in, jnp.float32)).astype(dt),
                jax.random.split(next(keys), shape[0]))
        elif name == "wq_b":
            out[name] = (QUERY_GAIN * weights.normal(
                next(keys), shape, shape[0], jnp.float32)).astype(dt)
        else:
            out[name] = weights.normal(next(keys), shape, shape[0], dt)
    return out


def make_params(dims: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The params pytree, on the device: one jitted call a block."""
    dt = weights.DTYPES[dtype]
    shapes = param_shapes(dims)
    k_emb, k_head, k_layers = jax.random.split(weights.key_of(seed), 3)
    make = jax.jit(lambda key, dense: _make_layer(
        key, layer_shapes(dims, dense), dt), static_argnums=1)
    matrix = jax.jit(weights.normal, static_argnums=(1, 2, 3))
    layers = [make(jax.random.fold_in(k_layers, index), is_dense(dims, index))
              for index in range(dims["L"])]
    return {"tok_emb": matrix(k_emb, shapes["tok_emb"], dims["D"], dt),
            "layers": layers, "final_norm": jnp.ones(shapes["final_norm"], dt),
            "lm_head": matrix(k_head, shapes["lm_head"], dims["D"], dt)}


# -- shape facts ------------------------------------------------------------
def latent_width(dims: dict) -> int:
    """Values a token keeps a block: its normed latent and its rotated
    shared key."""
    return dims["r"] + dims["rope"]


def expert_bytes(dims: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * dims["D"] * dims["F"] * itemsize


def weight_bytes(dims: dict, itemsize: int = 2) -> int:
    """The matrices a full decode step reads once: every block and the head
    (the embedding is a gather of `rows` rows, left out)."""
    D, H = dims["D"], dims["H"]
    attention = (D * dims["rq"] + dims["rq"] * H * (dims["nope"] + dims["rope"])
                 + D * latent_width(dims)
                 + dims["r"] * H * (dims["nope"] + dims["dv"])
                 + H * dims["dv"] * D)
    experts = (D * dims["E"] + 3 * D * dims["Fs"]
               + (dims["hi"] - dims["lo"]) * 3 * D * dims["F"])
    return itemsize * (dims["L"] * attention + dims["dense"] * 3 * D * dims["Fd"]
                       + (dims["L"] - dims["dense"]) * experts + D * dims["V"])


def experts_touched(dims: dict, rows: float) -> float:
    """How many of a block's held experts one decode step over `rows` live
    rows is EXPECTED to touch under the near-uniform routing the seeded
    weights give: a row misses a given expert with 1 - k/E, and all `rows`
    miss it with that to the power `rows`; never more than every held
    expert. The program's own count is `/debug/engine`'s
    `experts_touched_per_layer_step`."""
    held = dims["hi"] - dims["lo"]
    return held * (1.0 - (1.0 - dims["k"] / dims["E"]) ** rows)


def moe_experts_bytes(dims: dict, rows: float, itemsize: int = 2) -> float:
    """One decode step, every expert block: the three matrices of each held
    expert a live row picked, once (`experts_touched`), the rows' inputs in
    and their routed sums out."""
    acts = rows * dims["D"] * (itemsize + 4)
    return (dims["L"] - dims["dense"]) * (
        experts_touched(dims, rows) * expert_bytes(dims, itemsize) + acts)


def mla_read_bytes(dims: dict, rows: float, tokens: float,
                   cache_itemsize: int = 2, act_itemsize: int = 2) -> float:
    """One decode step, every block: each live token's latent plane once
    (it serves all H heads), each row's absorbed queries in (H of
    kv_lora_rank + rope) and its attended latents out (H of
    kv_lora_rank)."""
    w = latent_width(dims)
    acts = rows * dims["H"] * (w + dims["r"]) * act_itemsize
    return dims["L"] * (tokens * w * cache_itemsize + acts)


def latent_write_bytes(dims: dict, rows: float, cache_itemsize: int = 2):
    """One decode step, every block: each live row's new latent plane."""
    return dims["L"] * rows * latent_width(dims) * cache_itemsize


def facts(config: dict, dims: dict) -> dict:
    """The shape facts the harness and the readers ask for (PERF.md section
    3): the vocabulary the traffic draws ids from (the slice held here),
    cache bytes a token, the weights a decode step reads, and for each
    kernel scope the decode program launches, calls a step and the least
    bytes of one step (all its calls) over `rows` live rows holding
    `tokens` live tokens."""
    precision = config["precision"]
    cache = jnp.dtype(precision["pages"]).itemsize
    width = jnp.dtype(precision["weights"]).itemsize
    L = dims["L"]
    return {
        "vocab": dims["V"],
        "cache_bytes_per_token": L * latent_width(dims) * cache,
        "state_bytes_per_slot": 0,
        "decode_weight_bytes": weight_bytes(dims, width),
        "kernels": {
            "mla_read": {
                "calls_per_step": L,
                "least_bytes": lambda rows, tokens: mla_read_bytes(
                    dims, rows, tokens, cache, width)},
            "paged_write": {
                "calls_per_step": L,
                "least_bytes": lambda rows, tokens: latent_write_bytes(
                    dims, rows, cache)},
            "moe_experts": {
                "calls_per_step": L - dims["dense"],
                "least_bytes": lambda rows, tokens: moe_experts_bytes(
                    dims, rows, width)}}}


# -- the forward --------------------------------------------------------------
def _int8(w, axis: int):
    """Symmetric int8 per channel, scales over the contraction axis `axis`,
    returned as float32 again."""
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True),
                        1e-12) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def _weight(w, lower, axis: int = 0):
    w = w.astype(jnp.float32)
    return _int8(w, axis) if lower == "int8" else w


def rms_norm(x, weight, eps: float):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight.astype(jnp.float32))


def rope(x, positions, theta: float):
    """x [T, ..., d]: the pair (x[2i], x[2i + 1]) turns by
    positions[t] * theta^(-2i / d). (The published code first permutes q
    and k alike into the half-split order and rotates there; every q . k is
    the same.)"""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq    # [T, half]
    angles = angles.reshape(x.shape[0], *(1,) * (x.ndim - 2), half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(x, w: dict, dims: dict, lower=None):
    """x [T, D] (normed) -> [T, D]: the published, non-absorbed form, a
    head at a time."""
    T = x.shape[0]
    H, nope, dr, dv = dims["H"], dims["nope"], dims["rope"], dims["dv"]
    positions = jnp.arange(T)
    c_q = rms_norm(x @ _weight(w["wq_a"], lower), w["q_norm"], dims["eps"])
    q = (c_q @ _weight(w["wq_b"], lower)).reshape(T, H, nope + dr)
    kv = x @ _weight(w["wkv_a"], lower)
    c_kv = rms_norm(kv[:, :dims["r"]], w["kv_norm"], dims["eps"])
    k_r = rope(kv[:, dims["r"]:], positions, dims["theta"])       # [T, dr]
    kv_b = (c_kv @ _weight(w["wkv_b"], lower)).reshape(T, H, nope + dv)
    q_r = rope(q[:, :, nope:], positions, dims["theta"])          # [T, H, dr]
    causal = positions[None, :] <= positions[:, None]             # [t, s]

    def head(inputs):
        q_n, q_rope, k_n, v = inputs                              # [T, .]
        scores = (q_n @ k_n.T + q_rope @ k_r.T) / math.sqrt(nope + dr)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v                # [T, dv]

    heads = jax.lax.map(head, (
        jnp.moveaxis(q[:, :, :nope], 1, 0), jnp.moveaxis(q_r, 1, 0),
        jnp.moveaxis(kv_b[:, :, :nope], 1, 0),
        jnp.moveaxis(kv_b[:, :, nope:], 1, 0)))                   # [H, T, dv]
    return jnp.moveaxis(heads, 0, 1).reshape(T, H * dv) \
        @ _weight(w["wo"], lower)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, w: dict, dims: dict, lower=None):
    """[T, E] float32 combine weights: zero but at a token's k picks."""
    s = jax.nn.sigmoid(x @ _weight(w["router"], lower))
    _, picked = jax.lax.top_k(s + w["router_bias"], dims["k"])
    chosen = jnp.take_along_axis(s, picked, axis=-1)
    chosen = dims["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True)
                                       + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, picked].set(chosen)


def expert_ffn(x, w: dict, dims: dict, lower=None, held=None,
               shared: bool = True):
    """x [T, D] -> [T, D]: the share of the experts `held` = (lo, hi) that
    `w["w1"]`, `w["wg"]`, `w["w2"]` hold (the configuration's own range by
    default), with the shared expert unless `shared` is False. Every token
    meets every held expert here and its weight is zero where it did not
    pick it: the plain form; an expert's matrices are upcast as it is
    used."""
    lo, hi = held or (dims["lo"], dims["hi"])
    combine = route(x, w, dims, lower)[:, lo:hi]                  # [T, held]

    def one(total, inputs):
        up, gate, down, weight = inputs
        y = swiglu(x, _weight(gate, lower, axis=1).T,
                   _weight(up, lower, axis=1).T, _weight(down, lower))
        return total + weight[:, None] * y, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (w["w1"], w["wg"], w["w2"], combine.T))
    if shared:
        y = y + swiglu(x, _weight(w["shared_gate"], lower),
                       _weight(w["shared_up"], lower),
                       _weight(w["shared_down"], lower))
    return y


def ffn(x, w: dict, dims: dict, lower=None):
    if "router" in w:
        return expert_ffn(x, w, dims, lower)
    return swiglu(x, _weight(w["w_gate"], lower), _weight(w["w_up"], lower),
                  _weight(w["w_down"], lower))


def block(x, w: dict, dims: dict, lower=None):
    """One block over one sequence. x [T, D] float32."""
    x = x + attention(rms_norm(x, w["attn_norm"], dims["eps"]), w, dims,
                      lower)
    return x + ffn(rms_norm(x, w["ffn_norm"], dims["eps"]), w, dims, lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def _embed(tok_emb, tokens, lower=None):
    return _weight(tok_emb, lower, axis=1)[tokens]


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _block(x, w, dims, lower=None):
    with jax.default_matmul_precision("highest"):
        return block(x, w, dict(dims), lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, lm_head, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ _weight(lm_head, lower)


def logits(params: dict, dims: dict, tokens, lower=None):
    """[T, V] float32 logits of one token sequence [T]: row t scores the
    token that follows tokens[:t + 1]."""
    frozen = tuple(sorted(dims.items()))
    x = _embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32), lower=lower)
    for w in params["layers"]:
        x = _block(x, w, frozen, lower=lower)
    return _head(x, params["final_norm"], params["lm_head"], dims["eps"],
                 lower=lower)
