"""Plain reference for the sparse_linear family (OpenBMB's MiniCPM-SALA,
`model_type` minicpm_sala). Written from the published config's keys, from
MiniCPM4 (arXiv:2506.07900: InfLLM-V2, the `minicpm4` mixer) and from
Lightning Attention-2 (arXiv:2401.04658, in MiniMax-01's form: the
`lightning-attn` mixer), straightforward jax.numpy in float32 at the highest
matmul precision: no kernel, no cache, no batching, one sequence at a time,
one block at a time, the lightning recurrence one token at a time, the
softmax blocks a block of queries at a time so that 16k tokens fit. It
imports nothing of the program.

With `RMS_x` an RMSNorm with its own weight, D = hidden_size and
c = scale_depth / sqrt(depth), `depth` the PUBLISHED num_hidden_layers (32)
whatever part of the stack the configuration holds:

    h_0 = scale_emb Embed(token)
    h = x + c Mixer_l(RMS_mixer(x));   y = h + c FFN(RMS_ffn(h))
    FFN(u) = (silu(u W_g) (.) u W_u) W_d
    logits = (RMS_final(h_L) / (D / dim_model_base)) W_head

Sparse mixer (`minicpm4`; H query, Hkv key/value heads of d; NoPE):
    q = RMS_d(u W_q; g_q), k = RMS_d(u W_k; g_k), v = u W_v
    c_j = mean(k_i, s j <= i < s j + K)   (K = kernel_size, s = kernel_stride)
          visible to the query at t iff s j + K - 1 <= t
    t + 1 <= dense_len: A_t = {0..t}
    else, a KV head g (its H / Hkv query heads choose together):
      p_hj = softmax_j(q_th . c_j / sqrt(d)) over the visible j
      r_j = sum over h in g of p_hj
      s_b = max(r_j : 4b - 1 <= j <= 4b + 3, j visible)   a block of 64 tokens
      forced: block 0 and the window_size / block_size blocks that end at
      t's own; chosen = forced + the topk - |forced| best of the rest by
      s_b (ties to the lower index); A_t = the tokens <= t of the chosen
    o_th = sum over i in A_t of softmax_i(q_th . k_i / sqrt(d)) v_i
    out = (sigmoid(u W_z) (.) o) W_o
Lightning mixer (`lightning-attn`; Hl heads of dl):
    q = rope(RMS_d(u W_q; g_q)), k = rope(RMS_d(u W_k; g_k)), v = u W_v
    S_t = lambda_h S_{t-1} + k_t^T v_t  (S_0 = 0),  o_t = (q_t / sqrt(dl)) S_t
    lambda_h = exp(-2^(-8h/Hl) (1 - l/(depth-1) + 1e-5)), h = 1..Hl, l the
    block's PUBLISHED index
    out = (RMS_d(o_t; g_o) (.) sigmoid(u W_z)) W_o

What the config.json does not say (the sparse block's sizes, the decay's
schedule, the gates' forms) is listed under `assumed` in the
configuration's file, with the two stated departures from the published
code: `dense_len` is a rule a QUERY POSITION, not a switch on a call's
length, and the first softmax is exact (the published kernel approximates
its normaliser from a coarser pooling).

Weights are the benchmark's own (`make_params`), {"tok_emb" [V, D],
"layers": [one dict a block], "final_norm" [D], "lm_head" [D, V]}, every
matrix [in, out], in the dtype they are served in, upcast as they are
used. The seeded draw departs from Normal(0, 1/fan_in) where `_make_layer`
says.

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix rounded to int8 per output channel (the embedding per row).
"""

import functools
import math

import jax
import jax.numpy as jnp
from harness import bytes_fns, weights

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def dims_of(config: dict) -> dict:
    """The sizes the forward needs, from the config.json's own keys, the
    `sparse_config` group the file carries under `assumed`, and the file's
    statement of which published blocks are held."""
    mixers = tuple(config["mixer_types"])
    layer_ids = tuple(int(l) for l in config["layer_ids"])
    L = int(config["num_hidden_layers"])
    if len(mixers) != L or len(layer_ids) != L or any(
            m not in (SPARSE, LIGHTNING) for m in mixers):
        raise ValueError("mixer_types and layer_ids do not name "
                         "num_hidden_layers blocks")
    if config.get("attn_use_rope") or not config.get("lightning_use_rope"):
        raise ValueError("only NoPE sparse blocks and rotated lightning "
                         "blocks are written down")
    if not (config.get("qk_norm") and config.get("use_output_norm")
            and config.get("use_output_gate")
            and config.get("attn_use_output_gate")):
        raise ValueError("only normed q and k and gated, normed outputs are "
                         "written down")
    if int(config["lightning_nkv"]) != int(config["lightning_nh"]):
        raise ValueError("grouped keys in the lightning blocks are not "
                         "written down")
    sparse = config["sparse_config"]
    if int(sparse["kernel_size"]) != 2 * int(sparse["kernel_stride"]):
        raise ValueError("a compressed key is the mean of two strides")
    return {
        "V": int(config["vocab_size"]), "D": int(config["hidden_size"]),
        "L": L, "mixers": mixers, "layer_ids": layer_ids,
        "depth": int(config["published"]["num_hidden_layers"]),
        "eps": float(config["rms_norm_eps"]),
        "H": int(config["num_attention_heads"]),
        "Hkv": int(config["num_key_value_heads"]),
        "dh": int(config["head_dim"]),
        "Hl": int(config["lightning_nh"]),
        "dl": int(config["lightning_head_dim"]),
        "F": int(config["intermediate_size"]),
        "scale_emb": float(config["scale_emb"]),
        "scale_depth": float(config["scale_depth"]),
        "base": int(config["dim_model_base"]),
        "theta": float(config["rope_theta"]),
        "kernel": int(sparse["kernel_size"]),
        "stride": int(sparse["kernel_stride"]),
        "block": int(sparse["block_size"]), "topk": int(sparse["topk"]),
        "init": int(sparse["init_blocks"]),
        "window": int(sparse["window_size"]),
        "dense_len": int(sparse["dense_len"]),
    }


def blocks(dims: dict) -> dict:
    """How many blocks of each kind."""
    sparse = sum(m == SPARSE for m in dims["mixers"])
    return {"sparse": sparse, "lightning": dims["L"] - sparse}


def layer_shapes(dims: dict, mixer: str) -> dict:
    D, F = dims["D"], dims["F"]
    ffn = {"ffn_norm": (D,), "w_gate": (D, F), "w_up": (D, F),
           "w_down": (F, D)}
    if mixer == SPARSE:
        q, kv = dims["H"] * dims["dh"], dims["Hkv"] * dims["dh"]
        return {"mixer_norm": (D,), "wq": (D, q), "wk": (D, kv),
                "wv": (D, kv), "q_norm": (dims["dh"],),
                "k_norm": (dims["dh"],), "attn_gate": (D, q), "wo": (q, D),
                **ffn}
    c = dims["Hl"] * dims["dl"]
    return {"mixer_norm": (D,), "wq": (D, c), "wk": (D, c), "wv": (D, c),
            "q_norm": (dims["dl"],), "k_norm": (dims["dl"],),
            "o_norm": (dims["dl"],), "out_gate": (D, c), "wo": (c, D), **ffn}


def param_shapes(dims: dict) -> dict:
    return {"tok_emb": (dims["V"], dims["D"]), "final_norm": (dims["D"],),
            "lm_head": (dims["D"], dims["V"]),
            "layers": [layer_shapes(dims, mixer) for mixer in dims["mixers"]]}


# The departures from Normal(0, 1/fan_in) and unit gains, all in the DRAW and
# none in the forward:
# - The sparse blocks' g_q and g_k (they are parameters: `qk_norm`) uniform in
#   [1.5, 2.5] an element: with unit gains q . k / sqrt(d) is a unit normal
#   and a softmax over 4,096 chosen tokens is flat, so that a dropped or a
#   foreign block moves no logit; at a gain product of ~4 the scores have a
#   standard deviation of ~4 and a few tokens a head hold most of the mass,
#   as a trained block's do.
# - The head at D / dim_model_base times the gain, so that the division of
#   the head's input by that factor leaves logits of order 1, as a trained
#   checkpoint's head does; at Normal(0, 1/D) every logit would be under 0.3
#   and the gaps would be measured in its fourth decimal.
QK_GAIN = (1.5, 2.5)


def _make_layer(key, shapes: dict, dt, sparse: bool):
    """One block's weights: Normal(0, 1/fan_in) matrices, unit norms but
    for the sparse blocks' q and k gains."""
    keys = iter(jax.random.split(key, 16))
    out = {}
    for name, shape in shapes.items():
        if sparse and name in ("q_norm", "k_norm"):
            out[name] = jax.random.uniform(
                next(keys), shape, jnp.float32, *QK_GAIN).astype(dt)
        elif name.endswith("norm"):
            out[name] = jnp.ones(shape, dt)
        else:
            out[name] = weights.normal(next(keys), shape, shape[0], dt)
    return out


def make_params(dims: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The params pytree, on the device: one jitted call a block."""
    dt = weights.DTYPES[dtype]
    shapes = param_shapes(dims)
    k_emb, k_head, k_layers = jax.random.split(weights.key_of(seed), 3)
    make = jax.jit(lambda key, mixer: _make_layer(
        key, layer_shapes(dims, mixer), dt, mixer == SPARSE),
        static_argnums=1)
    matrix = jax.jit(weights.normal, static_argnums=(1, 2, 3))
    layers = [make(jax.random.fold_in(k_layers, index), mixer)
              for index, mixer in enumerate(dims["mixers"])]
    # fan_in D / gain^2: the head at D / dim_model_base times the gain
    head_fan = max(1, dims["base"] ** 2 // dims["D"])
    return {"tok_emb": matrix(k_emb, shapes["tok_emb"], dims["D"], dt),
            "layers": layers, "final_norm": jnp.ones(shapes["final_norm"], dt),
            "lm_head": matrix(k_head, shapes["lm_head"], head_fan, dt)}


# -- shape facts ------------------------------------------------------------
def state_bytes_per_slot(dims: dict, state_itemsize: int = 4,
                         sums_itemsize: int = 4) -> int:
    """What one sequence holds beside its pages: a lightning block's matrix
    state [heads, d, d], and for a sparse block the two half-window sums
    its next compressed key is made of."""
    n = blocks(dims)
    return (n["lightning"] * dims["Hl"] * dims["dl"] * dims["dl"]
            * state_itemsize
            + n["sparse"] * 2 * dims["Hkv"] * dims["dh"] * sums_itemsize)


def cache_bytes_per_token(dims: dict, itemsize: int = 2) -> float:
    """K and V a token a sparse block, and its share of a compressed key
    (one of Hkv x d for every `kernel_stride` tokens)."""
    a_block = dims["Hkv"] * dims["dh"] * itemsize
    return blocks(dims)["sparse"] * (2 * a_block + a_block // dims["stride"])


def weight_bytes(dims: dict, itemsize: int = 2) -> int:
    """The matrices a full decode step reads once: every block and the head
    (the embedding is a gather of `rows` rows, left out)."""
    D, n = dims["D"], blocks(dims)
    sparse = 3 * D * dims["H"] * dims["dh"] + 2 * D * dims["Hkv"] * dims["dh"]
    lightning = 5 * D * dims["Hl"] * dims["dl"]
    return itemsize * (n["sparse"] * sparse + n["lightning"] * lightning
                       + dims["L"] * 3 * D * dims["F"] + D * dims["V"])


def chosen_tokens(dims: dict, rows: float, tokens: float) -> float:
    """The tokens a decode step's rows attend in ONE sparse block's pages, a
    KV head: all of a row's under dense_len, topk blocks past it. From the
    mean context (the rows of one cell are all on one side of dense_len or
    nearly so)."""
    if not rows:
        return 0.0
    mean = tokens / rows
    return rows * (mean if mean <= dims["dense_len"]
                   else min(mean, dims["topk"] * dims["block"]))


def sparse_read_bytes(dims: dict, rows: float, tokens: float,
                      kv_itemsize: int = 2, act_itemsize: int = 2) -> float:
    """One decode step, every sparse block: the chosen blocks' K and V once
    a KV head, each row's q in and its output out. It counts the algorithm's
    need: a chosen block's 64 tokens, not the page they lie in."""
    kv = 2 * chosen_tokens(dims, rows, tokens) * dims["Hkv"] * dims["dh"] \
        * kv_itemsize
    q_and_out = 2 * rows * dims["H"] * dims["dh"] * act_itemsize
    return blocks(dims)["sparse"] * (kv + q_and_out)


def sparse_select_bytes(dims: dict, rows: float, tokens: float,
                        kv_itemsize: int = 2, act_itemsize: int = 2) -> float:
    """One decode step, every sparse block: every live token's share of a
    compressed key once, each row's q in and its block scores out."""
    keys = tokens / dims["stride"] * dims["Hkv"] * dims["dh"] * kv_itemsize
    acts = rows * (dims["H"] * dims["dh"] * act_itemsize
                   + tokens / max(rows, 1.0) / dims["stride"] * dims["Hkv"]
                   * 4)
    return blocks(dims)["sparse"] * (keys + acts)


def lightning_update_bytes(dims: dict, rows: float, state_itemsize: int = 4,
                           act_itemsize: int = 2) -> float:
    """One decode step, every lightning block: each live row's matrix state
    read and written once, its q, k, v in and its o out. It counts the
    algorithm's need, whatever implements it."""
    c = dims["Hl"] * dims["dl"]
    state = 2 * c * dims["dl"] * state_itemsize
    acts = 4 * c * act_itemsize
    return blocks(dims)["lightning"] * rows * (state + acts)


def facts(config: dict, dims: dict) -> dict:
    """The shape facts the harness and the readers ask for (PERF.md section
    3): the vocabulary the traffic draws ids from, cache bytes a token over
    the blocks that HAVE softmax attention (K, V and the compressed keys),
    state bytes a slot, the weights a decode step reads, and for each kernel
    scope the decode program launches, calls a step and the least bytes of
    one step (all its calls) over `rows` live rows holding `tokens` live
    tokens."""
    n = blocks(dims)
    precision = config["precision"]
    kv = jnp.dtype(precision["pages"]).itemsize
    width = jnp.dtype(precision["weights"]).itemsize
    state = jnp.dtype(precision["lightning_state"]).itemsize
    sums = jnp.dtype(precision["half_sums"]).itemsize
    return {
        "vocab": dims["V"],
        "cache_bytes_per_token": cache_bytes_per_token(dims, kv),
        "state_bytes_per_slot": state_bytes_per_slot(dims, state, sums),
        "decode_weight_bytes": weight_bytes(dims, width),
        "kernels": {
            "lightning_update": {
                "calls_per_step": n["lightning"],
                "least_bytes": lambda rows, tokens: lightning_update_bytes(
                    dims, rows, state, width)},
            "sparse_read": {
                "calls_per_step": n["sparse"],
                "least_bytes": lambda rows, tokens: sparse_read_bytes(
                    dims, rows, tokens, kv, width)},
            "sparse_select": {
                "calls_per_step": n["sparse"],
                "least_bytes": lambda rows, tokens: sparse_select_bytes(
                    dims, rows, tokens, kv, width)},
            "paged_write": {
                "calls_per_step": n["sparse"],
                "least_bytes": lambda rows, tokens: bytes_fns.paged_write_bytes(
                    rows, n["sparse"], dims["Hkv"], dims["dh"], kv)}}}


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


def rope(x, theta: float):
    """Rotate-half over the whole head. x [T, H, d], token t at position
    t."""
    T, _, d = x.shape
    half = d // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def decay(dims: dict, index: int):
    """[Hl] lambda a head of the block at position `index` of the stack
    held, from its PUBLISHED index."""
    H = dims["Hl"]
    rate = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=jnp.float32) / H)
    depth = 1.0 - dims["layer_ids"][index] / (dims["depth"] - 1) + 1e-5
    return jnp.exp(-rate * depth)


def lightning_mixer(x, w: dict, dims: dict, index: int, lower=None,
                    layer_factor: bool = True):
    """x [T, D] (normed) -> [T, D]: the recurrence one token at a time.
    `layer_factor` False shows the decay without its block's factor."""
    T = x.shape[0]
    H, d, eps = dims["Hl"], dims["dl"], dims["eps"]
    q = rope(rms_norm((x @ _weight(w["wq"], lower)).reshape(T, H, d),
                      w["q_norm"], eps), dims["theta"]) / math.sqrt(d)
    k = rope(rms_norm((x @ _weight(w["wk"], lower)).reshape(T, H, d),
                      w["k_norm"], eps), dims["theta"])
    v = (x @ _weight(w["wv"], lower)).reshape(T, H, d)
    lam = decay(dims, index) if layer_factor else decay(
        {**dims, "layer_ids": (0,) * dims["L"]}, index)

    def one(S, inputs):
        q_t, k_t, v_t = inputs
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(one, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    o = rms_norm(o, w["o_norm"], eps)
    gate = jax.nn.sigmoid(x @ _weight(w["out_gate"], lower))
    return (o.reshape(T, H * d) * gate) @ _weight(w["wo"], lower)


def compressed_keys(k, dims: dict):
    """k [T, Hkv, d] -> c [N, Hkv, d]: c_j the mean of the `kernel` keys
    from stride j on, for every j whose keys all lie inside T (the sum of
    the keys at each offset inside the window, a strided slice each)."""
    s, K = dims["stride"], dims["kernel"]
    N = max((k.shape[0] - K) // s + 1, 0)
    return sum(k[i:i + s * N:s] for i in range(K)) / K


def chosen_blocks(q, ck, first: int, dims: dict, far: bool = True):
    """The blocks the queries q [n, H, d] at positions first .. first + n - 1
    choose, a KV head: [n, Hkv, NB] bool over the NB blocks that the last
    of them can see. `far` False leaves the chosen blocks out: the forced
    ones only."""
    n, H, d = q.shape
    Hkv, B, s, K = dims["Hkv"], dims["block"], dims["stride"], dims["kernel"]
    per = B // s
    t = first + jnp.arange(n)
    NB = -(-(first + n) // B)
    N = ck.shape[0]
    scores = jnp.einsum("nghd,jgd->nghj", q.reshape(n, Hkv, H // Hkv, d),
                        ck) / math.sqrt(d)
    visible = s * jnp.arange(N)[None, :] + K - 1 <= t[:, None]     # [n, N]
    scores = jnp.where(visible[:, None, None, :], scores, -jnp.inf)
    p = jnp.where(visible[:, None, None, :],
                  jax.nn.softmax(scores, axis=-1), 0.0)
    r = jnp.where(visible[:, None, :], jnp.sum(p, axis=2), -jnp.inf)
    # a block's score: the max over the compressed keys 4b - 1 .. 4b + 3
    j = per * jnp.arange(NB)[:, None] + jnp.arange(-1, per)[None, :]
    inside = jnp.logical_and(j >= 0, j < N)
    pooled = jnp.where(inside[None, None], r[:, :, jnp.clip(j, 0, N - 1)],
                       -jnp.inf)
    score = jnp.max(pooled, axis=-1)                              # [n,Hkv,NB]
    block = jnp.arange(NB)[None, :]
    own = (t // B)[:, None]
    forced = jnp.logical_and(block <= own, jnp.logical_or(
        block < dims["init"], block > own - dims["window"] // B))
    free = jnp.logical_and(block <= own, jnp.logical_not(forced))
    score = jnp.where(free[:, None, :], score, -jnp.inf)
    room = dims["topk"] - jnp.sum(forced, axis=-1)                # [n]
    best = min(dims["topk"], NB)
    _, picks = jax.lax.top_k(score, best)           # ties: the lower index
    counted = jnp.logical_and(
        jnp.arange(best)[None, None, :] < room[:, None, None],
        jnp.take_along_axis(jnp.broadcast_to(free[:, None, :], score.shape),
                            picks, axis=-1))
    rows = jnp.arange(n)[:, None, None]
    heads = jnp.arange(Hkv)[None, :, None]
    chosen = jnp.zeros(score.shape, jnp.int32).at[rows, heads, picks].add(
        counted.astype(jnp.int32) if far else 0) > 0
    return jnp.logical_or(chosen, forced[:, None, :])


def sparse_mixer(x, w: dict, dims: dict, lower=None, far: bool = True,
                 tile: int = 512):
    """x [T, D] (normed) -> [T, D], `tile` queries at a time. `far` False
    leaves the chosen blocks out (the forced blocks only)."""
    T = x.shape[0]
    H, Hkv, d, eps = dims["H"], dims["Hkv"], dims["dh"], dims["eps"]
    G, B = H // Hkv, dims["block"]
    q = rms_norm((x @ _weight(w["wq"], lower)).reshape(T, H, d),
                 w["q_norm"], eps)
    k = rms_norm((x @ _weight(w["wk"], lower)).reshape(T, Hkv, d),
                 w["k_norm"], eps)
    v = (x @ _weight(w["wv"], lower)).reshape(T, Hkv, d)
    gate = jax.nn.sigmoid(x @ _weight(w["attn_gate"], lower))
    ck = compressed_keys(k, dims)
    outs = []
    for start in range(0, T, tile):
        S = min(start + tile, T)                # the keys this tile can see
        t = jnp.arange(start, S)
        mine = q[start:S].reshape(-1, Hkv, G, d)
        keys = jnp.arange(S)
        seen = keys[None, :] <= t[:, None]                        # [n, S]
        seen = jnp.broadcast_to(seen[:, None, :], (S - start, Hkv, S))
        if S > dims["dense_len"]:
            chosen = chosen_blocks(q[start:S], ck, start, dims, far)
            sparse = jnp.take(chosen, keys // B, axis=-1)         # [n,Hkv,S]
            dense = (t + 1 <= dims["dense_len"])[:, None, None]
            seen = jnp.logical_and(seen, jnp.logical_or(sparse, dense))
        scores = jnp.einsum("nghd,sgd->nghs", mine, k[:S]) / math.sqrt(d)
        scores = jnp.where(seen[:, :, None, :], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("nghs,sgd->nghd", p, v[:S]).reshape(-1, H * d))
    return (jnp.concatenate(outs) * gate) @ _weight(w["wo"], lower)


def ffn(x, w: dict, lower=None):
    return (jax.nn.silu(x @ _weight(w["w_gate"], lower))
            * (x @ _weight(w["w_up"], lower))) @ _weight(w["w_down"], lower)


def block(x, w: dict, dims: dict, index: int, lower=None, **fault):
    """One block over one sequence. x [T, D] float32. `fault`: `far` for a
    sparse block, `layer_factor` for a lightning block (the faults of
    benchmark/tests/sparse_linear_faults.py, as the reference would compute
    them)."""
    eps = dims["eps"]
    c = dims["scale_depth"] / math.sqrt(dims["depth"])
    u = rms_norm(x, w["mixer_norm"], eps)
    if dims["mixers"][index] == SPARSE:
        mixed = sparse_mixer(u, w, dims, lower, far=fault.get("far", True))
    else:
        mixed = lightning_mixer(u, w, dims, index, lower,
                                layer_factor=fault.get("layer_factor", True))
    x = x + c * mixed
    return x + c * ffn(rms_norm(x, w["ffn_norm"], eps), w, lower)


@functools.partial(jax.jit, static_argnames=("scale", "lower"))
def _embed(tok_emb, tokens, scale, lower=None):
    return scale * _weight(tok_emb, lower, axis=1)[tokens]


@functools.partial(jax.jit, static_argnames=("dims", "index", "lower",
                                             "fault"))
def _block(x, w, dims, index, lower=None, fault=()):
    with jax.default_matmul_precision("highest"):
        return block(x, w, dict(dims), index, lower, **dict(fault))


@functools.partial(jax.jit, static_argnames=("eps", "shrink", "lower"))
def _head(x, final_norm, lm_head, eps, shrink, lower=None):
    with jax.default_matmul_precision("highest"):
        return (rms_norm(x, final_norm, eps) / shrink) @ _weight(lm_head,
                                                                 lower)


def logits(params: dict, dims: dict, tokens, lower=None, **fault):
    """[T, V] float32 logits of one token sequence [T]: row t scores the
    token that follows tokens[:t + 1]."""
    frozen = tuple(sorted(dims.items()))
    x = _embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32),
               dims["scale_emb"], lower=lower)
    for index, w in enumerate(params["layers"]):
        x = _block(x, w, frozen, index, lower=lower,
                   fault=tuple(sorted(fault.items())))
    return _head(x, params["final_norm"], params["lm_head"], dims["eps"],
                 dims["D"] / dims["base"], lower=lower)
