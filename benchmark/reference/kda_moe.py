"""Plain reference for the kda_moe family (Upstage's Solar Open 2:
Solar-Open2-250B, `model_type` solar_open2). Written from the published
config's keys and, for the linear-attention blocks, from Kimi Linear
(arXiv:2510.26692, Kimi Delta Attention), straightforward jax.numpy in
float32 at the highest matmul precision: no kernel, no cache, no batching,
NO CHUNKING of the mathematics, one sequence at a time, one block at a
time, the delta-rule recurrence one token at a time. It imports nothing of
the program.

With `RMS_x` an RMSNorm with its own weight, D = hidden_size, block l:

    h = x + Mixer_l(RMS_mixer(x));   y = h + MoE(RMS_ffn(h))

the mixer GQA where l is in `gqa_layers`, else KDA; no rotary embedding
anywhere (`use_rope: false`).

KDA (`linear_attn_config`: H heads of d_k = d_v = head_dim, W taps):
    q~, k~, v~ = silu(conv_W(x W_q)), silu(conv_W(x W_k)), silu(conv_W(x W_v))
        conv_W a causal depthwise convolution, a channel each, no bias;
        `num_kv_heads: null`: k and v have H heads. The three projections
        and the three convolutions are held side by side as ONE matrix
        `wqkv` [D, 3 H d_k] and one `conv_w` [W, 3 H d_k]
    q = q~ / sqrt(sum q~^2 + 1e-6) / sqrt(d_k) a head, k likewise unscaled, v = v~
    g = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)    a channel of d_k
    a = exp(g) in (0, 1);   b = 2 sigmoid(x W_b) a head, in (0, 2)
    S_0 = 0 [d_k, d_v] a head
    S' = a_t (.) S_{t-1} (rows scaled);  u = S'^T k_t
    S_t = S' + b_t k_t (v_t - u)^T;      o_t = S_t^T q_t
        (= (I - b k k^T) Diag(a) S_{t-1} + b k v^T)
    out = (sigmoid((x W_ga) W_gb) (.) RMS_head(o_t)) W_o   one gain of d_v
GQA (H query, Hkv key/value heads of dh): q, k, v = x W_q, x W_k, x W_v;
    causal softmax(q k^T / sqrt(dh)), not turned, not normed;
    out = (sigmoid(x W_gate) (.) attn) W_o, a gate an element
MoE: r = sigmoid(x W_r); picks = top-k of (r + bias); w = r[picks];
    w = scale w / (sum(w) + 1e-20); y = shared(x) + sum over picks of
    w_e expert_e(x), SwiGLU both (`norm_topk_prob`, one shared expert)
`logits = W_head RMS_final(y)`, the head untied.

What the config.json does not say (the low-rank pairs and their rank, the
factor 2 of b, the l2 norm and its epsilon, the gates' forms, the router's
scoring, the state's precision) is listed under `assumed` in the
configuration's file.

The configuration states which of the router's experts this chip HOLDS
(`experts_held`, a range): the router keeps every output and its k picks a
token, the sum runs over the picked experts that are held, and what the
others would add is left out (model-configs guide, section 4). `held=` of
`expert_ffn` takes any range, so a test adds the shares up.

Weights are the benchmark's own (`make_params`), {"tok_emb" [V, D],
"layers": [one dict a block], "final_norm" [D], "lm_head" [D, V]}; every
matrix [in, out] but the routed experts' three, "w1" (up), "wg" (gate),
"w2" (down), each [held, F, D]: up and gate [out, in], down [in, out].
Matrices in the dtype they are served in, upcast as they are used (an
expert at a time); `A_log`, `dt_bias` and the router's bias in float32. The
seeded draw departs from Normal(0, 1/fan_in) where `_make_layer` says.

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix rounded to int8 per output channel (the embedding per row).
"""

import functools
import math

import jax
import jax.numpy as jnp
from harness import bytes_fns, weights

L2_EPS = 1e-6


def dims_of(config: dict) -> dict:
    """The sizes the forward needs, from the config.json's own keys and the
    file's statement of what is held here."""
    lo, hi = config["experts_held"]
    if hi - lo != int(config["n_routed_experts"]):
        raise ValueError("experts_held and n_routed_experts differ")
    if config.get("use_rope") or int(config["first_k_dense_replace"]):
        raise ValueError("rotary embeddings and dense leading blocks are "
                         "not written down here")
    if not config.get("use_gqa_gate") or not config.get("norm_topk_prob"):
        raise ValueError("only gated GQA and normalised routing are written "
                         "down")
    if config.get("kda_use_full_proj") or not config.get(
            "kda_allow_neg_eigval"):
        raise ValueError("only the low-rank gates and b in (0, 2) are "
                         "written down")
    linear = config["linear_attn_config"]
    if linear.get("num_kv_heads") is not None:
        raise ValueError("grouped keys in the KDA blocks are not written down")
    gqa = tuple(int(l) for l in config["gqa_layers"])
    L = int(config["num_hidden_layers"])
    if any(not 0 <= l < L for l in gqa):
        raise ValueError("gqa_layers names blocks the stack lacks")
    return {
        "V": int(config["vocab_size"]), "D": int(config["hidden_size"]),
        "L": L, "gqa": gqa, "eps": float(config["rms_norm_eps"]),
        "H": int(config["num_attention_heads"]),
        "Hkv": int(config["num_key_value_heads"]),
        "dh": int(config["head_dim"]),
        "Hk": int(linear["num_heads"]), "dk": int(linear["head_dim"]),
        "W": int(linear["short_conv_kernel_size"]),
        "r": int(linear["head_dim"]),       # the low-rank gates' rank: assumed
        "E": int(config["n_routed_experts_published"]), "lo": int(lo),
        "hi": int(hi), "k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "Fs": int(config["moe_intermediate_size"])
        * int(config["n_shared_experts"]),
        "scale": float(config["routed_scaling_factor"]),
    }


def is_gqa(dims: dict, index: int) -> bool:
    return index in dims["gqa"]


def blocks(dims: dict) -> dict:
    """How many blocks of each kind."""
    gqa = len(set(dims["gqa"]))
    return {"gqa": gqa, "kda": dims["L"] - gqa}


def layer_shapes(dims: dict, gqa: bool) -> dict:
    D, r = dims["D"], dims["r"]
    expert = (dims["hi"] - dims["lo"], dims["F"], D)
    ffn = {"ffn_norm": (D,), "router": (D, dims["E"]),
           "router_bias": (dims["E"],), "w1": expert, "wg": expert,
           "w2": expert, "shared_gate": (D, dims["Fs"]),
           "shared_up": (D, dims["Fs"]), "shared_down": (dims["Fs"], D)}
    if gqa:
        q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
        return {"mixer_norm": (D,), "wq": (D, q), "wk": (D, kv),
                "wv": (D, kv), "attn_gate": (D, q), "wo": (q, D), **ffn}
    c = dims["Hk"] * dims["dk"]
    return {"mixer_norm": (D,), "wqkv": (D, 3 * c),
            "conv_w": (dims["W"], 3 * c), "f_a": (D, r), "f_b": (r, c),
            "dt_bias": (c,), "A_log": (dims["Hk"],),
            "w_beta": (D, dims["Hk"]), "g_a": (D, r), "g_b": (r, c),
            "o_norm": (dims["dk"],), "wo": (c, D), **ffn}


def param_shapes(dims: dict) -> dict:
    return {"tok_emb": (dims["V"], dims["D"]), "final_norm": (dims["D"],),
            "lm_head": (dims["D"], dims["V"]),
            "layers": [layer_shapes(dims, is_gqa(dims, index))
                       for index in range(dims["L"])]}


# The departures from Normal(0, 1/fan_in), all in the DRAW and none in the
# forward:
# - A routed expert's down matrix at an eighth of the gain, as the other
#   expert configurations have it (reference/nemotron_h.py has the
#   readings): the router reads bfloat16 activations, so the program and a
#   float32 reference part on a token's last pick in a few per cent of
#   (token, block) pairs, and at full gain the gaps would measure the
#   routing and not the arithmetic.
# - Queries of the GQA block at four times the gain (as nemotron_h's, which
#   has no q norm either): with unit-variance scores a softmax over
#   hundreds of keys is near uniform, and a row that attends another row's
#   pages would serve nearly the same tokens.
# - The decay's constants so that a channel's half-life runs from tens to
#   thousands of tokens: A uniform in [1, 4] a head, the step log-uniform
#   in [2e-4, 1e-2] a channel through the inverse softplus; the low-rank
#   pair adds a unit-variance term to the step's logit. The configuration's
#   `notes` has the mean decay these give.
# - `w_beta` is Normal(0, 1/D) like any matrix: b = 2 sigmoid(unit normal)
#   is over 1 in half of the (token, head) pairs, so the negative
#   eigenvalue is exercised without a departure.
ROUTED_GAIN = 0.125
QUERY_GAIN = 4.0
A_RANGE = (1.0, 4.0)
STEP_RANGE = (2e-4, 1e-2)


def _make_layer(key, shapes: dict, dt):
    """One block's weights: Normal(0, 1/fan_in) matrices but for the
    departures above, unit norms, a small router bias so that picking by
    `r + bias` and weighting by `r` differ."""
    keys = iter(jax.random.split(key, 24))
    out = {}
    for name, shape in shapes.items():
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dt)
        elif name == "router_bias":
            out[name] = 0.02 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)
        elif name == "A_log":
            out[name] = jnp.log(jax.random.uniform(
                next(keys), shape, jnp.float32, *A_RANGE))
        elif name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                next(keys), shape, jnp.float32, math.log(STEP_RANGE[0]),
                math.log(STEP_RANGE[1])))
            out[name] = step + jnp.log(-jnp.expm1(-step))
        elif name in ("w1", "wg", "w2"):
            # an expert at a time: the float32 draws are one expert's
            fan_in = shape[1] if name == "w2" else shape[2]
            gain = ROUTED_GAIN if name == "w2" else 1.0
            out[name] = jax.lax.map(
                lambda k: (gain * weights.normal(
                    k, shape[1:], fan_in, jnp.float32)).astype(dt),
                jax.random.split(next(keys), shape[0]))
        elif name == "wq":
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
    make = jax.jit(lambda key, gqa: _make_layer(
        key, layer_shapes(dims, gqa), dt), static_argnums=1)
    matrix = jax.jit(weights.normal, static_argnums=(1, 2, 3))
    layers = [make(jax.random.fold_in(k_layers, index), is_gqa(dims, index))
              for index in range(dims["L"])]
    return {"tok_emb": matrix(k_emb, shapes["tok_emb"], dims["D"], dt),
            "layers": layers, "final_norm": jnp.ones(shapes["final_norm"], dt),
            "lm_head": matrix(k_head, shapes["lm_head"], dims["D"], dt)}


# -- shape facts ------------------------------------------------------------
def state_bytes_per_slot(dims: dict, state_itemsize: int = 4,
                         tail_itemsize: int = 2) -> int:
    """What one sequence holds beside its pages: a KDA block's matrix state
    [heads, d_k, d_v] and its convolution tail, the last W - 1 columns of
    x W_qkv, for every KDA block."""
    c = dims["Hk"] * dims["dk"]
    return blocks(dims)["kda"] * (c * dims["dk"] * state_itemsize
                                  + 3 * c * (dims["W"] - 1) * tail_itemsize)


def expert_bytes(dims: dict, itemsize: int = 2) -> int:
    """One routed expert's three matrices."""
    return 3 * dims["D"] * dims["F"] * itemsize


def weight_bytes(dims: dict, itemsize: int = 2) -> int:
    """The matrices a full decode step reads once: every block and the head
    (the embedding is a gather of `rows` rows, left out)."""
    D, r, n = dims["D"], dims["r"], blocks(dims)
    c = dims["Hk"] * dims["dk"]
    kda = 4 * D * c + 2 * r * (D + c) + D * dims["Hk"] + 3 * c * dims["W"]
    gqa = 3 * D * dims["H"] * dims["dh"] + 2 * D * dims["Hkv"] * dims["dh"]
    experts = (D * dims["E"] + 3 * D * dims["Fs"]
               + (dims["hi"] - dims["lo"]) * 3 * D * dims["F"])
    return itemsize * (n["kda"] * kda + n["gqa"] * gqa + dims["L"] * experts
                       + D * dims["V"])


def experts_touched(dims: dict, rows: float) -> float:
    """How many of a block's held experts one decode step over `rows` live
    rows is EXPECTED to touch under the near-uniform routing the seeded
    weights give (reference/nemotron_h.py has the argument); never more
    than every held expert."""
    held = dims["hi"] - dims["lo"]
    return held * (1.0 - (1.0 - dims["k"] / dims["E"]) ** rows)


def moe_experts_bytes(dims: dict, rows: float, itemsize: int = 2) -> float:
    """One decode step, every block: the three matrices of each held expert
    a live row picked, once, the rows' inputs in and their routed sums
    out."""
    acts = rows * dims["D"] * (itemsize + 4)
    return dims["L"] * (
        experts_touched(dims, rows) * expert_bytes(dims, itemsize) + acts)


def kda_update_bytes(dims: dict, rows: float, state_itemsize: int = 4,
                     act_itemsize: int = 2) -> float:
    """One decode step, every KDA block: each live row's matrix state read
    and written once, its q, k, v and decay in and its o out, b a head. It
    counts the algorithm's need, whatever implements it."""
    c = dims["Hk"] * dims["dk"]
    state = 2 * c * dims["dk"] * state_itemsize
    acts = 5 * c * act_itemsize + dims["Hk"] * 4
    return blocks(dims)["kda"] * rows * (state + acts)


def facts(config: dict, dims: dict) -> dict:
    """The shape facts the harness and the readers ask for (PERF.md section
    3): the vocabulary the traffic draws ids from (the slice held here),
    cache bytes a token over the blocks that HAVE softmax attention, state
    bytes a slot, the weights a decode step reads, and for each kernel
    scope the decode program launches, calls a step and the least bytes of
    one step (all its calls) over `rows` live rows holding `tokens` live
    tokens."""
    n = blocks(dims)
    precision = config["precision"]
    kv = jnp.dtype(precision["pages"]).itemsize
    width = jnp.dtype(precision["weights"]).itemsize
    state = jnp.dtype(precision["kda_state"]).itemsize
    tail = jnp.dtype(precision["conv_tail"]).itemsize
    H, Hkv, dh, La = dims["H"], dims["Hkv"], dims["dh"], n["gqa"]
    return {
        "vocab": dims["V"],
        "cache_bytes_per_token": 2 * La * Hkv * dh * kv,
        "state_bytes_per_slot": state_bytes_per_slot(dims, state, tail),
        "decode_weight_bytes": weight_bytes(dims, width),
        "kernels": {
            "paged_read": {
                "calls_per_step": La,
                "least_bytes": lambda rows, tokens: bytes_fns.paged_read_bytes(
                    tokens, rows, La, Hkv, H, dh, kv, width)},
            "paged_write": {
                "calls_per_step": La,
                "least_bytes": lambda rows, tokens: bytes_fns.paged_write_bytes(
                    rows, La, Hkv, dh, kv)},
            "kda_update": {
                "calls_per_step": n["kda"],
                "least_bytes": lambda rows, tokens: kda_update_bytes(
                    dims, rows, state, width)},
            "moe_experts": {
                "calls_per_step": dims["L"],
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


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_mixer(x, w: dict, dims: dict, lower=None, beta_factor: float = 2.0):
    """x [T, D] (normed) -> [T, D]: the recurrence one token at a time.
    `beta_factor` is 2 for this family (`kda_allow_neg_eigval`); 1 shows
    what the plain delta rule would give."""
    T = x.shape[0]
    H, dk, W = dims["Hk"], dims["dk"], dims["W"]
    c = H * dk
    # depthwise causal convolution: y_t = sum_j w[j] x_{t - (W-1) + j}
    taps = w["conv_w"].astype(jnp.float32)                        # [W, 3c]
    proj = x @ _weight(w["wqkv"], lower)
    padded = jnp.concatenate([jnp.zeros((W - 1, 3 * c), jnp.float32), proj])
    qkv = jax.nn.silu(sum(taps[j] * padded[j:j + T] for j in range(W)))
    q, k, v = (part.reshape(T, H, dk) for part in jnp.split(qkv, 3, axis=-1))
    q, k = l2norm(q) / math.sqrt(dk), l2norm(k)
    step = jax.nn.softplus((x @ _weight(w["f_a"], lower))
                           @ _weight(w["f_b"], lower) + w["dt_bias"])
    g = -jnp.exp(w["A_log"])[:, None] * step.reshape(T, H, dk)    # <= 0
    b = beta_factor * jax.nn.sigmoid(x @ _weight(w["w_beta"], lower))

    def one(S, inputs):
        q_t, k_t, v_t, g_t, b_t = inputs
        S = jnp.exp(g_t)[:, :, None] * S                          # [H, dk, dv]
        u = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - u)[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(one, jnp.zeros((H, dk, dk), jnp.float32),
                        (q, k, v, g, b))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + dims["eps"]) * w["o_norm"].astype(jnp.float32)
    gate = jax.nn.sigmoid((x @ _weight(w["g_a"], lower))
                          @ _weight(w["g_b"], lower))
    return (o.reshape(T, c) * gate) @ _weight(w["wo"], lower)


def gqa_mixer(x, w: dict, dims: dict, lower=None):
    """x [T, D] (normed) -> [T, D]: a head at a time."""
    T = x.shape[0]
    H, Hkv, dh = dims["H"], dims["Hkv"], dims["dh"]
    positions = jnp.arange(T)
    q = (x @ _weight(w["wq"], lower)).reshape(T, H, dh)
    k = (x @ _weight(w["wk"], lower)).reshape(T, Hkv, dh)
    v = (x @ _weight(w["wv"], lower)).reshape(T, Hkv, dh)
    gate = jax.nn.sigmoid(x @ _weight(w["attn_gate"], lower))     # [T, H dh]
    seen = positions[None, :] <= positions[:, None]               # [t, s]

    def one(head):
        mine = jax.lax.dynamic_index_in_dim(q, head, 1, False)
        keys = jax.lax.dynamic_index_in_dim(k, head // (H // Hkv), 1, False)
        values = jax.lax.dynamic_index_in_dim(v, head // (H // Hkv), 1, False)
        scores = jnp.where(seen, mine @ keys.T / math.sqrt(dh), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ values           # [T, dh]

    out = jax.lax.map(one, jnp.arange(H))                         # [H, T, dh]
    out = out.transpose(1, 0, 2).reshape(T, H * dh)
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
    picked it: up to T / 8 of them gathered (five times a uniform router's
    share at k of E = 1 / 40), every token where more did: the same sum
    either way."""
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


def block(x, w: dict, dims: dict, gqa: bool, lower=None):
    """One block over one sequence. x [T, D] float32."""
    eps = dims["eps"]
    mixer = gqa_mixer if gqa else kda_mixer
    x = x + mixer(rms_norm(x, w["mixer_norm"], eps), w, dims, lower)
    return x + expert_ffn(rms_norm(x, w["ffn_norm"], eps), w, dims, lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def _embed(tok_emb, tokens, lower=None):
    return _weight(tok_emb, lower, axis=1)[tokens]


@functools.partial(jax.jit, static_argnames=("dims", "gqa", "lower"))
def _block(x, w, dims, gqa, lower=None):
    with jax.default_matmul_precision("highest"):
        return block(x, w, dict(dims), gqa, lower)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, final_norm, lm_head, eps, lower=None):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ _weight(lm_head, lower)


def logits(params: dict, dims: dict, tokens, lower=None):
    """[T, V] float32 logits of one token sequence [T]: row t scores the
    token that follows tokens[:t + 1]."""
    frozen = tuple(sorted(dims.items()))
    x = _embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32), lower=lower)
    for index, w in enumerate(params["layers"]):
        x = _block(x, w, frozen, is_gqa(dims, index), lower=lower)
    return _head(x, params["final_norm"], params["lm_head"], dims["eps"],
                 lower=lower)
