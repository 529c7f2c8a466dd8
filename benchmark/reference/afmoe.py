"""Plain reference for the afmoe family (Arcee's Trinity: Trinity-Large-
Preview, `model_type` afmoe). Written from the published config's keys and
the afmoe modelling code's description, straightforward jax.numpy in
float32 at the highest matmul precision: no kernel, no cache, no batching,
one sequence at a time, one block at a time, a head and an expert at a
time. It imports nothing of the program.

With `RMS_x` an RMSNorm with its own weight, D = hidden_size, H query heads
and Hkv key/value heads of dh, `h = E[token] * sqrt(D)` (`mup_enabled`),
block l of kind `layer_types[l]`:

    a = RMS_in(h)
    q = W_q a [H, dh]  k = W_k a [Hkv, dh]  v = W_v a [Hkv, dh]  g = W_g a [H dh]
    q = RMS_qn(q), k = RMS_kn(k)     over a head's dh, one weight of dh each
    sliding_attention: q, k turned by RoPE (theta, the whole head, rotate-
    half pairs (i, i + dh / 2)); full_attention: NOT turned
    s_ij = q_i . k_j / sqrt(dh); visible j <= i, on a sliding block also
    j > i - sliding_window (that many keys, the token's own among them)
    o = softmax(s) v [H dh];  o = o * sigmoid(g);  h = h + RMS_post_attn(W_o o)
    m = RMS_pre_mlp(h)
    l < num_dense_layers:  y = W_down(silu(W_gate m) * W_up m)
    else:  r = sigmoid(W_r m) in float32;  picks = top-k of (r + b);
           w = r[picks];  w = route_scale * w / (sum(w) + 1e-20)
           y = shared(m) + sum over picks of w_e expert_e(m), SwiGLU both
    h = h + RMS_post_mlp(y)

`logits = W_head RMS_final(h)`. `n_group = topk_group = 1`: no group limit.
What the config.json does not say (the head norms, the gate and where it
is applied, no rotation on full blocks, the four norms and their places,
the bias used for the pick and not for the weight, the pairing, the window
counting the token itself) is listed under `assumed` in the configuration's
file.

The configuration states which of the router's experts this chip HOLDS
(`experts_held`, a range): the router keeps every output and its k picks a
token, the sum runs over the picked experts that are held, and what the
others would add is left out (model-configs guide, section 4). `held=` of
`expert_ffn` takes any range, so a test adds the shares up. An expert is
met by the tokens that picked it, gathered up to a capacity of an eighth
of the sequence (eight times what uniform routing sends it); over it, by
every token: the same sum either way.

A forward runs in blocks of `ROWS` query rows a head, so a 13k-token
sequence's scores are [ROWS, T] and not [T, T].

Weights are the benchmark's own (`make_params`), {"tok_emb" [V, D],
"layers": [one dict a block], "final_norm" [D], "lm_head" [D, V]}; every
matrix [in, out] but the routed experts' three, "w1" (up), "wg" (gate),
"w2" (down), each [held, F, D]: up and gate [out, in], down [in, out].
Matrices in the dtype they are served in, upcast as they are used; the
router's bias in float32. The seeded draw departs from Normal(0, 1/fan_in)
in two places (`_make_layer`).

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix rounded to int8 per output channel (the embedding per row).
"""

import functools
import math

import jax
import jax.numpy as jnp
from harness import bytes_fns, weights

SLIDING, FULL = "sliding_attention", "full_attention"
ROWS = 2048         # query rows of one head's scores at a time


def dims_of(config: dict) -> dict:
    """The sizes the forward needs, from the config.json's own keys and the
    file's statement of what is held here."""
    lo, hi = config["experts_held"]
    if hi - lo != int(config["num_experts"]):
        raise ValueError("experts_held and num_experts differ")
    if int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise ValueError("group-limited routing is not written down here")
    if config.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not written down here")
    if config.get("score_func") != "sigmoid" or not config.get("route_norm"):
        raise ValueError("only normalised sigmoid routing is written down")
    kinds = tuple(config["layer_types"])
    if len(kinds) != int(config["num_hidden_layers"]) or any(
            kind not in (SLIDING, FULL) for kind in kinds):
        raise ValueError("layer_types names every block's kind")
    return {
        "V": int(config["vocab_size"]), "D": int(config["hidden_size"]),
        "L": int(config["num_hidden_layers"]),
        "dense": int(config["num_dense_layers"]), "kinds": kinds,
        "eps": float(config["rms_norm_eps"]),
        "H": int(config["num_attention_heads"]),
        "Hkv": int(config["num_key_value_heads"]),
        "dh": int(config["head_dim"]), "W": int(config["sliding_window"]),
        "Fd": int(config["intermediate_size"]),
        "E": int(config["num_experts_published"]), "lo": int(lo),
        "hi": int(hi), "k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "Fs": int(config["moe_intermediate_size"])
        * int(config["num_shared_experts"]),
        "scale": float(config["route_scale"]),
        "theta": float(config["rope_theta"]),
        "mup": bool(config["mup_enabled"]),
    }


def is_dense(dims: dict, index: int) -> bool:
    return index < dims["dense"]


def layer_shapes(dims: dict, dense: bool) -> dict:
    D, q, kv = dims["D"], dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    shapes = {"in_norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
              "attn_gate": (D, q), "q_norm": (dims["dh"],),
              "k_norm": (dims["dh"],), "wo": (q, D), "post_attn_norm": (D,),
              "pre_mlp_norm": (D,), "post_mlp_norm": (D,)}
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
# the forward (PERF.md section 2 has the reasons, measured on nemotron_h
# and mla_moe, whose routers do the same arithmetic):
# - A routed expert's down matrix at an eighth of the gain: the router
#   reads bfloat16 activations, so the program and a float32 reference
#   disagree on a token's last pick now and then, and with experts at full
#   gain each swap parts the hidden states.
# - Queries (W_q) at TWICE the gain: with unit-variance scores a softmax
#   over thousands of keys is near uniform, attention adds little to the
#   stream, and a row that attends another row's pages, or the tokens
#   behind its window, would serve nearly the same tokens. The head norm
#   of q would undo a gain on W_q, so it is the q norm's WEIGHT that is
#   drawn at 2 (a norm's weight is a parameter like any other).
ROUTED_GAIN = 0.125
QUERY_GAIN = 2.0


def _make_layer(key, shapes: dict, dt):
    """One block's weights: Normal(0, 1/fan_in) matrices but for the two
    departures above, unit norms (the q norm at QUERY_GAIN), a small router
    bias so that picking by `r + bias` and weighting by `r` differ."""
    keys = iter(jax.random.split(key, 16))
    out = {}
    for name, shape in shapes.items():
        if name == "q_norm":
            out[name] = jnp.full(shape, QUERY_GAIN, dt)
        elif name.endswith("norm"):
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
def blocks_of(dims: dict, kind: str) -> int:
    return sum(k == kind for k in dims["kinds"])


def token_bytes(dims: dict, itemsize: int = 2) -> int:
    """K and V a token keeps in ONE block."""
    return 2 * dims["Hkv"] * dims["dh"] * itemsize


def expert_bytes(dims: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * dims["D"] * dims["F"] * itemsize


def weight_bytes(dims: dict, itemsize: int = 2) -> int:
    """The matrices a full decode step reads once: every block and the head
    (the embedding is a gather of `rows` rows, left out)."""
    D = dims["D"]
    attention = 3 * D * dims["H"] * dims["dh"] + 2 * D * dims["Hkv"] * dims["dh"]
    experts = (D * dims["E"] + 3 * D * dims["Fs"]
               + (dims["hi"] - dims["lo"]) * 3 * D * dims["F"])
    return itemsize * (dims["L"] * attention + dims["dense"] * 3 * D * dims["Fd"]
                       + (dims["L"] - dims["dense"]) * experts + D * dims["V"])


def experts_touched(dims: dict, rows: float) -> float:
    """How many of a block's held experts one decode step over `rows` live
    rows is EXPECTED to touch under the near-uniform routing the seeded
    weights give (reference/mla_moe.py has the argument); never more than
    every held expert."""
    held = dims["hi"] - dims["lo"]
    return held * (1.0 - (1.0 - dims["k"] / dims["E"]) ** rows)


def moe_experts_bytes(dims: dict, rows: float, itemsize: int = 2) -> float:
    """One decode step, every expert block: the three matrices of each held
    expert a live row picked, once, the rows' inputs in and their routed
    sums out."""
    acts = rows * dims["D"] * (itemsize + 4)
    return (dims["L"] - dims["dense"]) * (
        experts_touched(dims, rows) * expert_bytes(dims, itemsize) + acts)


def full_read_bytes(dims: dict, rows: float, tokens: float,
                    cache_itemsize: int = 2, act_itemsize: int = 2) -> float:
    """One decode step, the full_attention blocks: every live token's K
    and V once, each row's queries in and its output out."""
    return bytes_fns.paged_read_bytes(
        tokens, rows, blocks_of(dims, FULL), dims["Hkv"], dims["H"],
        dims["dh"], cache_itemsize, act_itemsize)


def window_read_bytes(dims: dict, rows: float, window_tokens: float,
                      cache_itemsize: int = 2, act_itemsize: int = 2):
    """One decode step, the sliding_attention blocks: `window_tokens` = the
    sum over the live rows of min(context, sliding_window), the tokens a
    row still sees; each one's K and V once, queries in, outputs out."""
    return bytes_fns.paged_read_bytes(
        window_tokens, rows, blocks_of(dims, SLIDING), dims["Hkv"],
        dims["H"], dims["dh"], cache_itemsize, act_itemsize)


def facts(config: dict, dims: dict) -> dict:
    """The shape facts the harness and the readers ask for (PERF.md section
    3): the vocabulary the traffic draws ids from (the slice held here),
    cache bytes a token a page group, the weights a decode step reads, and
    for each kernel scope the decode program launches, calls a step and
    the least bytes of one step (all its calls) over `rows` live rows
    holding `tokens` live tokens. `window_read`'s third argument is the
    rows' tokens INSIDE the window, which the reader sums itself
    (layer_metrics/window_read_roofline.py); told the whole contexts it
    answers for at most a window a row."""
    precision = config["precision"]
    cache = jnp.dtype(precision["pages"]).itemsize
    width = jnp.dtype(precision["weights"]).itemsize
    L, W = dims["L"], dims["W"]
    full, sliding = blocks_of(dims, FULL), blocks_of(dims, SLIDING)
    per_block = token_bytes(dims, cache)
    return {
        "vocab": dims["V"],
        "window": W,
        "cache_bytes_per_token": L * per_block,
        "cache_bytes_per_token_by_group": {"full": full * per_block,
                                           "window": sliding * per_block},
        "state_bytes_per_slot": 0,
        "decode_weight_bytes": weight_bytes(dims, width),
        "kernels": {
            "paged_read": {
                "calls_per_step": full,
                "least_bytes": lambda rows, tokens: full_read_bytes(
                    dims, rows, tokens, cache, width)},
            "window_read": {
                "calls_per_step": sliding,
                "least_bytes": lambda rows, tokens, inside=None: (
                    window_read_bytes(
                        dims, rows,
                        min(tokens, rows * W) if inside is None else inside,
                        cache, width))},
            "paged_write": {
                "calls_per_step": 2,        # a flush a page group
                "least_bytes": lambda rows, tokens: (
                    bytes_fns.paged_write_bytes(rows, L, dims["Hkv"],
                                                dims["dh"], cache))},
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
    """x [T, heads, d]: the pair (x[i], x[i + d / 2]) turns by
    positions[t] * theta^(-2i / d)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    first, second = x[..., :half], x[..., half:]
    return jnp.concatenate([first * cos - second * sin,
                            first * sin + second * cos], axis=-1)


def attention(x, w: dict, dims: dict, kind: str, lower=None):
    """x [T, D] (normed) -> [T, D] before its norm: a head and ROWS query
    rows at a time."""
    T = x.shape[0]
    H, Hkv, dh = dims["H"], dims["Hkv"], dims["dh"]
    positions = jnp.arange(T)
    q = (x @ _weight(w["wq"], lower)).reshape(T, H, dh)
    k = (x @ _weight(w["wk"], lower)).reshape(T, Hkv, dh)
    v = (x @ _weight(w["wv"], lower)).reshape(T, Hkv, dh)
    gate = jax.nn.sigmoid(x @ _weight(w["attn_gate"], lower))     # [T, H dh]
    q = rms_norm(q, w["q_norm"], dims["eps"])
    k = rms_norm(k, w["k_norm"], dims["eps"])
    if kind == SLIDING:
        q, k = (rope(q, positions, dims["theta"]),
                rope(k, positions, dims["theta"]))
    rows = next(n for n in range(min(ROWS, T), 0, -1) if T % n == 0)

    def some_rows(inputs):
        head, start = inputs
        mine = jax.lax.dynamic_slice_in_dim(
            jax.lax.dynamic_index_in_dim(q, head, 1, False), start, rows)
        keys = jax.lax.dynamic_index_in_dim(k, head // (H // Hkv), 1, False)
        values = jax.lax.dynamic_index_in_dim(v, head // (H // Hkv), 1, False)
        at = start + jnp.arange(rows)[:, None]                    # [rows, 1]
        seen = positions[None, :] <= at
        if kind == SLIDING:
            seen = jnp.logical_and(seen, positions[None, :] > at - dims["W"])
        scores = jnp.where(seen, mine @ keys.T / math.sqrt(dh), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ values           # [rows, dh]

    heads, starts = jnp.meshgrid(jnp.arange(H), jnp.arange(0, T, rows),
                                 indexing="ij")
    out = jax.lax.map(some_rows, (heads.reshape(-1), starts.reshape(-1)))
    out = out.reshape(H, T, dh).transpose(1, 0, 2).reshape(T, H * dh)
    return (out * gate) @ _weight(w["wo"], lower)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def route(x, w: dict, dims: dict, lower=None):
    """[T, E] float32 combine weights: zero but at a token's k picks."""
    r = jax.nn.sigmoid(x @ _weight(w["router"], lower))
    _, picked = jax.lax.top_k(r + w["router_bias"], dims["k"])
    chosen = jnp.take_along_axis(r, picked, axis=-1)
    chosen = dims["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True)
                                       + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(r).at[rows, picked].set(chosen)


def expert_ffn(x, w: dict, dims: dict, lower=None, held=None,
               shared: bool = True):
    """x [T, D] -> [T, D]: the share of the experts `held` = (lo, hi) that
    `w["w1"]`, `w["wg"]`, `w["w2"]` hold (the configuration's own range by
    default), with the shared expert unless `shared` is False. An expert's
    matrices are upcast as it is used, and it is met by the tokens that
    picked it: up to T / 8 of them gathered (eight times a uniform
    router's share at k of E = 1 / 64), every token where more did."""
    lo, hi = held or (dims["lo"], dims["hi"])
    combine = route(x, w, dims, lower)[:, lo:hi]                  # [T, held]
    T = x.shape[0]
    most = max(1, T // 8)

    def one(total, inputs):
        up, gate, down, weight = inputs
        up, gate = (_weight(up, lower, axis=1).T,
                    _weight(gate, lower, axis=1).T)
        down = _weight(down, lower)

        def gathered(total):
            rows, = jnp.nonzero(weight, size=most, fill_value=T)
            at = jnp.minimum(rows, T - 1)
            y = swiglu(x[at], gate, up, down) * weight[at][:, None]
            return total.at[rows].add(y, mode="drop")

        def everyone(total):
            return total + weight[:, None] * swiglu(x, gate, up, down)

        return jax.lax.cond(jnp.sum(weight != 0.0) <= most, gathered,
                            everyone, total), None

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


def block(x, w: dict, dims: dict, kind: str, lower=None):
    """One block over one sequence. x [T, D] float32."""
    eps = dims["eps"]
    x = x + rms_norm(attention(rms_norm(x, w["in_norm"], eps), w, dims, kind,
                               lower), w["post_attn_norm"], eps)
    return x + rms_norm(ffn(rms_norm(x, w["pre_mlp_norm"], eps), w, dims,
                            lower), w["post_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("scale", "lower"))
def _embed(tok_emb, tokens, scale, lower=None):
    return _weight(tok_emb, lower, axis=1)[tokens] * scale


@functools.partial(jax.jit, static_argnames=("dims", "kind", "lower"))
def _block(x, w, dims, kind, lower=None):
    with jax.default_matmul_precision("highest"):
        return block(x, w, dict(dims), kind, lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, lm_head, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ _weight(lm_head, lower)


def logits(params: dict, dims: dict, tokens, lower=None):
    """[T, V] float32 logits of one token sequence [T]: row t scores the
    token that follows tokens[:t + 1]."""
    frozen = tuple(sorted(dims.items()))
    scale = math.sqrt(dims["D"]) if dims["mup"] else 1.0
    x = _embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32), scale,
               lower=lower)
    for kind, w in zip(dims["kinds"], params["layers"]):
        x = _block(x, w, frozen, kind, lower=lower)
    return _head(x, params["final_norm"], params["lm_head"], dims["eps"],
                 lower=lower)
