"""Plain reference for the nemotron_h family (NVIDIA Nemotron-H / Nemotron 3
Nano): a stack of blocks, each `x <- x + mixer(RMSNorm(x))` with ONE mixer
a block and no separate feed-forward, the kind of mixer given by a pattern
string (`M` Mamba-2, `E` sparse experts, `*` attention); after the last
block a final RMSNorm and an untied head. Written from the published
modelling code's description (huggingface.co, `model_type` nemotron_h),
straightforward jax.numpy in float32 at the highest matmul precision: no
kernel, no cache, no batching, no chunking, one sequence at a time, one
block at a time, the state-space recurrence one token at a time. It imports
nothing of the program.

Mamba-2 mixer (heads of `P`, `G` groups, state `N`, conv width `W`, no
projection bias, conv bias): `[z | xBC | dt] = W_in u`; `xBC =
silu(causal_conv1d(xBC) + b)`; `x, B, C = split(xBC)` (head h uses group
h // (heads / G)); `dt = softplus(dt + dt_bias)`; `A = -exp(A_log)` a head;
`h_t = exp(dt A) h_{t-1} + dt x_t (x) B_t`; `y_t = h_t C_t + D x_t`;
`y = RMSNorm_grouped(y silu(z))` over groups of d_inner / G; `out = W_out y`.

Expert mixer: `s = sigmoid(W_r x)`; the `k` largest of `s + bias` are
picked (one routing group: no group limit); `w = s[picked]`, `w <- scale
w / sum(w)`; `y = sum_e w_e W2_e relu(W1_e x)^2 + W2_s relu(W1_s x)^2`.
The configuration states which of the router's experts this chip HOLDS
(`experts_held`, a range): the router keeps every output and its k picks a
token, the sum runs over the picked experts that are held, and what the
others would add is left out (model-configs guide, section 4). `held=`
of `expert_mixer` takes any range, so a test adds the shares up.

Attention mixer: grouped-query, causal, scale 1/sqrt(dh), no bias.
Departure noted: the family applies NO rotary embedding in its attention
layers (the state-space layers carry position; the published config's
`rope_theta` is unused by the modelling code). `rotary=True` of
`attention_mixer` is there so that a reader can see what is left out.

Weights are the benchmark's own (`make_params`, from the seed by
harness/weights.py's shared rule but for three departures, each so that
the output check can see what it is there to see: `_make_layer` has them),
{"tok_emb" [V, D], "layers": [one dict a
block, by kind], "final_norm" [D], "lm_head" [D, V]}; every matrix is
[in, out] but the experts' up matrices, "w1" [held, F, D], which stay
[out, in] as the published checkpoint stores a linear layer: both of an
expert's matrices then have D minor, and 1856 is no multiple of a chip's
128 lanes. Matrices in the dtype
they are served in, upcast as they are used (a layer's experts one at a
time, so that the forward fits beside 10.6 GB of weights); `A_log`, `D`,
`dt_bias` and the router's bias in float32 as the published checkpoint
keeps them.

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix rounded to int8 per output channel (the embedding per row).
"""

import functools
import math

import jax
import jax.numpy as jnp
from harness import bytes_fns, weights

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def dims_of(config: dict) -> dict:
    """The sizes the forward needs, from the config.json's own keys and the
    file's statement of what is held here."""
    heads, P = int(config["mamba_num_heads"]), int(config["mamba_head_dim"])
    pattern = str(config["hybrid_override_pattern"])
    if len(pattern) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern and num_hidden_layers differ")
    lo, hi = config["experts_held"]
    if hi - lo != int(config["n_routed_experts"]):
        raise ValueError("experts_held and n_routed_experts differ")
    return {
        "V": int(config["vocab_size"]), "D": int(config["hidden_size"]),
        "pattern": pattern, "eps": float(config["layer_norm_epsilon"]),
        "Hm": heads, "P": P, "G": int(config["n_groups"]),
        "N": int(config["ssm_state_size"]), "W": int(config["conv_kernel"]),
        "H": int(config["num_attention_heads"]),
        "Hkv": int(config["num_key_value_heads"]),
        "dh": int(config["head_dim"]),
        "E": int(config["n_routed_experts_published"]), "lo": int(lo),
        "hi": int(hi), "k": int(config["num_experts_per_tok"]),
        "F": int(config["moe_intermediate_size"]),
        "Fs": int(config["moe_shared_expert_intermediate_size"])
        * int(config["n_shared_experts"]),
        "scale": float(config["routed_scaling_factor"]),
        "theta": float(config["rope_theta"]),
    }


def counts(dims: dict) -> dict:
    """How many blocks of each kind the pattern holds."""
    return {kind: dims["pattern"].count(mark) for mark, kind in KINDS.items()}


def _sizes(dims: dict):
    d_inner = dims["Hm"] * dims["P"]
    conv = d_inner + 2 * dims["G"] * dims["N"]
    return d_inner, conv, 2 * d_inner + 2 * dims["G"] * dims["N"] + dims["Hm"]


def layer_shapes(dims: dict, kind: str) -> dict:
    D = dims["D"]
    if kind == "mamba":
        d_inner, conv, proj = _sizes(dims)
        return {"norm": (D,), "in_proj": (D, proj),
                "conv_w": (dims["W"], conv), "conv_b": (conv,),
                "dt_bias": (dims["Hm"],), "A_log": (dims["Hm"],),
                "D": (dims["Hm"],), "gate_norm": (d_inner,),
                "out_proj": (d_inner, D)}
    if kind == "experts":
        held = dims["hi"] - dims["lo"]
        return {"norm": (D,), "router": (D, dims["E"]),
                "router_bias": (dims["E"],), "w1": (held, dims["F"], D),
                "w2": (held, dims["F"], D), "shared_w1": (D, dims["Fs"]),
                "shared_w2": (dims["Fs"], D)}
    q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
    return {"norm": (D,), "wq": (D, q), "wk": (D, kv), "wv": (D, kv),
            "wo": (q, D)}


def param_shapes(dims: dict) -> dict:
    return {"tok_emb": (dims["V"], dims["D"]), "final_norm": (dims["D"],),
            "lm_head": (dims["D"], dims["V"]),
            "layers": [layer_shapes(dims, KINDS[mark])
                       for mark in dims["pattern"]]}


# The three departures from Normal(0, 1/fan_in), all in the DRAW and none
# in the forward (PERF.md section 2 has the readings with and without):
# - A routed expert's down matrix at an eighth of the gain. The router's
#   input is bfloat16 as the published code has it, so the program and a
#   float32 reference disagree on a token's sixth pick in 3-5 % of (token,
#   block) pairs at the first expert block and 13-20 % at the seventh (a
#   float32 forward with activations rounded to bfloat16, on the CPU),
#   whatever the router's gain (a gain moves no score past another: at 8x
#   it only saturates the sigmoid); with independent experts at full gain each such swap moved
#   the stream by a fifth, the hidden states parted, and the gaps measured
#   the routing and not the arithmetic. At an eighth a swap moves the
#   logits by what bfloat16 rounding does.
# - Queries at four times the gain. With unit-variance scores a softmax
#   over hundreds of keys is near uniform and an attention block adds 0.26
#   to a stream of 2.4-3.5: a row that attends another row's pages would
#   serve nearly the same tokens. At gain 4 the block adds 0.7 and the
#   keys matter.
# - Matrices that follow a never-negative activation (relu^2 in the
#   experts, silu's gate in Mamba-2) have zero mean over their inputs:
#   else every token carries one common direction, the router scores it the
#   same for all, and the busiest held expert sees four times the mean.
ROUTED_GAIN = 0.125
QUERY_GAIN = 4.0


def _centred(key, shape, dt, gain: float = 1.0):
    """One [in, out] matrix after a never-negative activation: Normal(0,
    1/in) draws less their mean over `in`, times `gain`."""
    draws = jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    return (gain * (draws - draws.mean(axis=0, keepdims=True))).astype(dt)


def _make_layer(key, shapes: dict, kind: str, dt):
    """One block's weights: Normal(0, 1/fan_in) matrices but for the three
    departures above, unit norms; the state-space constants as Mamba-2
    initialises them (A uniform in [1, 16], dt log-uniform in [0.001, 0.1]
    through the inverse softplus, D ones); a small router bias so that
    picking by `s + bias` and weighting by `s` differ."""
    keys = iter(jax.random.split(key, 8))
    out = {"norm": jnp.ones(shapes["norm"], dt)}
    if kind == "mamba":
        out["in_proj"] = weights.normal(next(keys), shapes["in_proj"],
                                        shapes["in_proj"][0], dt)
        out["out_proj"] = _centred(next(keys), shapes["out_proj"], dt)
        out["conv_w"] = weights.normal(next(keys), shapes["conv_w"],
                                       shapes["conv_w"][0], dt)
        out["conv_b"] = jnp.zeros(shapes["conv_b"], dt)
        heads = shapes["A_log"]
        out["A_log"] = jnp.log(jax.random.uniform(
            next(keys), heads, jnp.float32, 1.0, 16.0))
        step = jnp.exp(jax.random.uniform(
            next(keys), heads, jnp.float32, math.log(0.001), math.log(0.1)))
        out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
        out["D"] = jnp.ones(heads, jnp.float32)
        out["gate_norm"] = jnp.ones(shapes["gate_norm"], dt)
    elif kind == "experts":
        out["router"] = weights.normal(next(keys), shapes["router"],
                                       shapes["router"][0], dt)
        out["router_bias"] = 0.02 * jax.random.normal(
            next(keys), shapes["router_bias"], jnp.float32)
        # an expert at a time: the float32 draws are one expert's
        held, up = shapes["w1"][0], shapes["w1"][1:]
        out["w1"] = jax.lax.map(
            lambda k: weights.normal(k, up, up[1], dt),
            jax.random.split(next(keys), held))
        out["w2"] = jax.lax.map(
            lambda k: _centred(k, shapes["w2"][1:], dt, ROUTED_GAIN),
            jax.random.split(next(keys), held))
        out["shared_w1"] = weights.normal(next(keys), shapes["shared_w1"],
                                          shapes["shared_w1"][0], dt)
        out["shared_w2"] = _centred(next(keys), shapes["shared_w2"], dt)
    else:
        out["wq"] = (QUERY_GAIN * weights.normal(
            next(keys), shapes["wq"], shapes["wq"][0], jnp.float32)
        ).astype(dt)
        for name in ("wk", "wv", "wo"):
            out[name] = weights.normal(next(keys), shapes[name],
                                       shapes[name][0], dt)
    return out


def make_params(dims: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The params pytree, on the device: one jitted call a block (a call
    for all of them would let the compiler hold several blocks' float32
    draws at once, and one expert block is 1.3 GB in bfloat16)."""
    dt = weights.DTYPES[dtype]
    shapes = param_shapes(dims)
    k_emb, k_head, k_layers = jax.random.split(weights.key_of(seed), 3)
    make = jax.jit(lambda key, kind: _make_layer(
        key, layer_shapes(dims, kind), kind, dt), static_argnums=1)
    matrix = jax.jit(weights.normal, static_argnums=(1, 2, 3))
    layers = [make(jax.random.fold_in(k_layers, index), KINDS[mark])
              for index, mark in enumerate(dims["pattern"])]
    return {"tok_emb": matrix(k_emb, shapes["tok_emb"], dims["D"], dt),
            "layers": layers, "final_norm": jnp.ones(shapes["final_norm"], dt),
            "lm_head": matrix(k_head, shapes["lm_head"], dims["D"], dt)}


# -- shape facts ------------------------------------------------------------
def state_bytes_per_slot(dims: dict, state_itemsize: int = 4,
                         tail_itemsize: int = 2) -> int:
    """What one sequence holds beside its pages: a Mamba-2 block's
    recurrent state [heads, P, N] and its convolution tail, the last W - 1
    columns of xBC, for every Mamba-2 block."""
    _, conv, _ = _sizes(dims)
    return counts(dims)["mamba"] * (
        dims["Hm"] * dims["P"] * dims["N"] * state_itemsize
        + conv * (dims["W"] - 1) * tail_itemsize)


def expert_bytes(dims: dict, itemsize: int = 2) -> int:
    """One routed expert's two matrices."""
    return 2 * dims["D"] * dims["F"] * itemsize


def weight_bytes(dims: dict, itemsize: int = 2) -> int:
    """The matrices a full decode step reads once: every block and the head
    (the embedding is a gather of `rows` rows, left out)."""
    n = counts(dims)
    d_inner, conv, proj = _sizes(dims)
    mamba = dims["D"] * proj + d_inner * dims["D"] + dims["W"] * conv
    experts = (dims["D"] * dims["E"] + 2 * dims["D"] * dims["Fs"]) \
        + (dims["hi"] - dims["lo"]) * 2 * dims["D"] * dims["F"]
    attention = 2 * dims["D"] * (dims["H"] + dims["Hkv"]) * dims["dh"]
    return itemsize * (n["mamba"] * mamba + n["experts"] * experts
                       + n["attention"] * attention + dims["D"] * dims["V"])


def experts_touched(dims: dict, rows: float) -> float:
    """How many of a block's held experts one decode step over `rows` live
    rows is EXPECTED to touch under the near-uniform routing the seeded
    weights give: a row puts its k picks on E experts, so it misses a given
    one with 1 - k/E, and all `rows` miss it with that to the power `rows`.
    At the cell's 93 rows this is 63.3 of 64; never more than every held
    expert. The program's own count is `/debug/engine`'s
    `experts_touched_per_layer_step` (PERF.md section 5 sets them side by
    side)."""
    held = dims["hi"] - dims["lo"]
    return held * (1.0 - (1.0 - dims["k"] / dims["E"]) ** rows)


def ssm_update_bytes(dims: dict, rows: float, state_itemsize: int = 4,
                     act_itemsize: int = 2) -> float:
    """One decode step, every Mamba-2 block: each live row's state read
    and written once, its x, B, C and dt in and its y out."""
    d_inner = dims["Hm"] * dims["P"]
    state = 2 * dims["Hm"] * dims["P"] * dims["N"] * state_itemsize
    acts = (2 * d_inner + 2 * dims["G"] * dims["N"]) * act_itemsize \
        + dims["Hm"] * 4
    return counts(dims)["mamba"] * rows * (state + acts)


def moe_experts_bytes(dims: dict, rows: float, itemsize: int = 2) -> float:
    """One decode step, every expert block: the two matrices of each held
    expert a live row picked, once (`experts_touched`: the expectation under
    uniform routing, from the live rows), the rows' inputs in and their
    routed sums out."""
    acts = rows * dims["D"] * (itemsize + 4)
    return counts(dims)["experts"] * (
        experts_touched(dims, rows) * expert_bytes(dims, itemsize) + acts)


def facts(config: dict, dims: dict) -> dict:
    """The shape facts the harness and the readers ask for (PERF.md section
    3): the vocabulary the traffic draws ids from (the slice held here),
    cache bytes a token over the blocks that HAVE attention, state bytes a
    slot, the weights a decode step reads, and for each kernel scope the
    decode program launches, calls a step and the least bytes of one step
    (all its calls) over `rows` live rows holding `tokens` live tokens."""
    n = counts(dims)
    precision = config["precision"]
    kv = jnp.dtype(precision["pages"]).itemsize
    width = jnp.dtype(precision["weights"]).itemsize
    state = jnp.dtype(precision["ssm_state"]).itemsize
    tail = jnp.dtype(precision["conv_tail"]).itemsize
    H, Hkv, dh, La = dims["H"], dims["Hkv"], dims["dh"], n["attention"]
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
            "ssm_update": {
                "calls_per_step": n["mamba"],
                "least_bytes": lambda rows, tokens: ssm_update_bytes(
                    dims, rows, state, width)},
            "moe_experts": {
                "calls_per_step": n["experts"],
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


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba_mixer(u, w: dict, dims: dict, lower=None):
    """u [T, D] float32 -> [T, D]: the recurrence one token at a time."""
    T = u.shape[0]
    Hm, P, G, N, W = (dims[k] for k in ("Hm", "P", "G", "N", "W"))
    d_inner, conv, _ = _sizes(dims)
    proj = u @ _weight(w["in_proj"], lower)
    z, xBC, dt = jnp.split(proj, [d_inner, d_inner + conv], axis=-1)
    # depthwise causal convolution: y_t = sum_j w[j] x_{t - (W-1) + j} + b
    taps = w["conv_w"].astype(jnp.float32)                        # [W, conv]
    padded = jnp.concatenate([jnp.zeros((W - 1, conv), jnp.float32), xBC])
    xBC = jax.nn.silu(sum(taps[j] * padded[j:j + T] for j in range(W))
                      + w["conv_b"].astype(jnp.float32))
    x, B, C = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)
    x = x.reshape(T, Hm, P)
    # head h reads group h // (Hm / G)
    B = jnp.repeat(B.reshape(T, G, N), Hm // G, axis=1)           # [T, Hm, N]
    C = jnp.repeat(C.reshape(T, G, N), Hm // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])                       # [T, Hm]
    A = -jnp.exp(w["A_log"])                                      # [Hm]

    def step(h, inputs):
        x_t, B_t, C_t, dt_t = inputs
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, C_t)

    _, y = jax.lax.scan(step, jnp.zeros((Hm, P, N), jnp.float32),
                        (x, B, C, dt))
    y = (y + w["D"][None, :, None] * x).reshape(T, d_inner)
    y = y * jax.nn.silu(z)
    # RMSNorm over each of the G groups of d_inner / G, one gain a channel
    grouped = y.reshape(T, G, d_inner // G)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + dims["eps"])
    y = grouped.reshape(T, d_inner) * w["gate_norm"].astype(jnp.float32)
    return y @ _weight(w["out_proj"], lower)


def route(x, w: dict, dims: dict, lower=None):
    """[T, E] float32 combine weights: zero but at a token's k picks."""
    s = jax.nn.sigmoid(x @ _weight(w["router"], lower))
    _, picked = jax.lax.top_k(s + w["router_bias"], dims["k"])
    chosen = jnp.take_along_axis(s, picked, axis=-1)
    chosen = dims["scale"] * chosen / (jnp.sum(chosen, -1, keepdims=True)
                                       + 1e-20)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, picked].set(chosen)


def expert_mixer(x, w: dict, dims: dict, lower=None, held=None,
                 shared: bool = True):
    """x [T, D] -> [T, D]: the share of the experts `held` = (lo, hi) that
    `w["w1"]`, `w["w2"]` hold (the configuration's own range by default),
    with the shared expert unless `shared` is False. Every token meets
    every held expert here and its weight is zero where it did not pick
    it: the plain form; an expert's matrices are upcast as it is used."""
    lo, hi = held or (dims["lo"], dims["hi"])
    combine = route(x, w, dims, lower)[:, lo:hi]                  # [T, held]

    def one(total, inputs):
        w1, w2, weight = inputs
        h = relu2(x @ _weight(w1, lower, axis=1).T)
        return total + weight[:, None] * (h @ _weight(w2, lower)), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (w["w1"], w["w2"], combine.T))
    if shared:
        y = y + relu2(x @ _weight(w["shared_w1"], lower)) \
            @ _weight(w["shared_w2"], lower)
    return y


def rope(x, positions, theta: float):
    """x [T, heads, dh]: pairs (i, i + dh/2) rotate by position * theta^(-2i/dh)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def attention_mixer(x, w: dict, dims: dict, lower=None, rotary: bool = False):
    """x [T, D] -> [T, D]. `rotary` is False for this family (see the
    module's departure note); True shows what the family leaves out."""
    T = x.shape[0]
    H, Hkv, dh = dims["H"], dims["Hkv"], dims["dh"]
    positions = jnp.arange(T)
    q = (x @ _weight(w["wq"], lower)).reshape(T, H, dh)
    k = (x @ _weight(w["wk"], lower)).reshape(T, Hkv, dh)
    v = (x @ _weight(w["wv"], lower)).reshape(T, Hkv, dh)
    if rotary:
        q, k = rope(q, positions, dims["theta"]), rope(k, positions,
                                                       dims["theta"])
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(dh)
    causal = positions[None, :] <= positions[:, None]             # [t, s]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return attn.reshape(T, H * dh) @ _weight(w["wo"], lower)


MIXERS = {"mamba": mamba_mixer, "experts": expert_mixer,
          "attention": attention_mixer}


def block(x, w: dict, dims: dict, kind: str, lower=None):
    """One block over one sequence. x [T, D] float32."""
    return x + MIXERS[kind](rms_norm(x, w["norm"], dims["eps"]), w, dims,
                            lower)


@functools.partial(jax.jit, static_argnames=("lower",))
def _embed(tok_emb, tokens, lower=None):
    return _weight(tok_emb, lower, axis=1)[tokens]


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
    x = _embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32), lower=lower)
    for mark, w in zip(dims["pattern"], params["layers"]):
        x = _block(x, w, frozen, KINDS[mark], lower=lower)
    return _head(x, params["final_norm"], params["lm_head"], dims["eps"],
                 lower=lower)
