"""Plain reference for the mla_moe family with its two departures of
Xing4.0-29B-A4B (`model_type` xing4_0): a residual stream of `hc_mult`
copies mixed around every sublayer (manifold-constrained hyper-connections,
mHC: arXiv:2512.24880) and YaRN-scaled rotary frequencies. Latent attention,
the dense blocks, the router and the experts are benchmark/reference/
mla_moe.py's, imported from there; what differs is written here, in
straightforward jax.numpy, float32, at the highest matmul precision, one
sequence and one block at a time. It imports nothing of the program.

The residual path, n = `hc_mult` copies of width D, X [T, n, D], all equal
to the token's embedding before block 0. Each sublayer F (a block's MLA,
then its FFN) has float32 leaves `phi` [2n + n^2, n D] ([out, in], as a
checkpoint stores a linear layer), `scale` [3] = (a_pre, a_post, a_res) and
`bias` [2n + n^2] = (b_pre [n], b_post [n], b_res [n, n] row-major):

    xf = vec(X); m = (xf * rsqrt(mean(xf^2) + rms_norm_eps)) phi^T
    H_pre = sigmoid(a_pre m[:n] + b_pre); H_post = 2 sigmoid(a_post m[n:2n] + b_post)
    M = exp(clip(a_res mat(m[2n:]) + b_res, clamp_min, clamp_max)), then
        `hc_sinkhorn_iters` times M <- M / (rowsum(M) + hc_eps),
        M <- M / (colsum(M) + hc_eps);  H_res = M
    u = sum_i H_pre[i] X[i];  f = F(RMSNorm_w(u))     (the block's own norm)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] f

and after the last block `h = sum_i X[i]`, the final norm and the head.

YaRN, as DeepSeek-V3's modelling code has it under the config's keys: the
pair i of a rotary vector of width d turns by `position * inv_freq_i`,
`theta_i = rope_theta^(-2i / d)`, `c(b) = d ln(original / (2 pi b)) /
(2 ln rope_theta)`, `low = floor(c(beta_fast))`, `high = ceil(c(beta_slow))`,
`r_i = clip((i - low) / (high - low), 0, 1)`,
`inv_freq_i = theta_i (1 - r_i) + (theta_i / factor) r_i`; cos and sin are
unscaled (mscale / mscale_all_dim = 1 is the only ratio written down here)
and the scores are scaled by `(nope + rope)^-0.5 (0.1 mscale_all_dim ln
factor + 1)^2`. Interleaved pairs, as the family's reference rotates them.

Assumed, each noted in the configuration's file: no learned gain in the
mix's own norm; rows before columns and `hc_eps` in the denominators; the
copies summed at the end; interleaved pairs; the drafting block not
loaded; the seeded draws (`_make_mix`, `QUERY_GAIN`).

`lower="int8"` is the CONTROL, not the reference: the same forward with
every matrix (phi among them) rounded to int8 per output channel.
"""

import functools
import math

import jax
import jax.numpy as jnp
from reference import mla_moe as base


def dims_of(config: dict) -> dict:
    """The family's sizes and the two departures', flat (the dims are a
    jitted function's static argument)."""
    yarn = config["rope_scaling"]
    if yarn is None or yarn.get("type") != "yarn":
        raise ValueError("rope_scaling of type yarn is what is written "
                         "down here")
    if float(yarn["mscale"]) != float(yarn["mscale_all_dim"]):
        raise ValueError("mscale != mscale_all_dim scales cos and sin: not "
                         "written down here")
    nope, rope_dim = (int(config["qk_nope_head_dim"]),
                      int(config["qk_rope_head_dim"]))
    dims = base.dims_of({**config, "rope_scaling": None,
                         "qk_head_dim": nope + rope_dim})
    return {**dims, "n": int(config["hc_mult"]),
            "iters": int(config["hc_sinkhorn_iters"]),
            "hc_eps": float(config["hc_eps"]),
            "clamp_lo": float(config["mhc_h_res_clamp_min"]),
            "clamp_hi": float(config["mhc_h_res_clamp_max"]),
            "factor": float(yarn["factor"]),
            "original": int(yarn["original_max_position_embeddings"]),
            "beta_fast": float(yarn["beta_fast"]),
            "beta_slow": float(yarn["beta_slow"]),
            "mscale_all_dim": float(yarn["mscale_all_dim"])}


def mix_columns(dims: dict) -> int:
    """Values a sublayer's mix makes of the stream: H_pre, H_post, H_res."""
    return 2 * dims["n"] + dims["n"] ** 2


SUBLAYERS = ("attn", "ffn")


def mix_shapes(dims: dict) -> dict:
    C = mix_columns(dims)
    shapes = {}
    for sub in SUBLAYERS:
        shapes.update({f"{sub}_hc_phi": (C, dims["n"] * dims["D"]),
                       f"{sub}_hc_scale": (3,), f"{sub}_hc_bias": (C,)})
    return shapes


def layer_shapes(dims: dict, dense: bool) -> dict:
    return {**base.layer_shapes(dims, dense), **mix_shapes(dims)}


def param_shapes(dims: dict) -> dict:
    return {**base.param_shapes(dims),
            "layers": [layer_shapes(dims, base.is_dense(dims, i))
                       for i in range(dims["L"])]}


# The mix's draw. phi at Normal(0, 1 / (n D)): the stream is normed to unit
# mean square before the product, so each of m's values is about standard
# normal. a_pre = a_post = 1 with small biases: H_pre about 0.5 +- 0.2 a
# copy, H_post about 1 +- 0.4. H_res is to be measurably neither the
# identity nor uniform, and the arithmetic that makes it is to MATTER to the
# logits without multiplying the stream's own rounding: a_res = 0.5 (a
# token moves its logits by +- 0.5, so an error of 1 % in m moves an entry
# of M by 0.5 %) and a static b_res = 3 I + 1.5 Normal(0, 1): a preference
# for a copy's own row, entries spread over some e^6, so that the Sinkhorn
# rounds converge slowly (5 rounds for 20 leave entries off by 0.002-0.013
# in the mean, 10-40 times what 1 % of noise in m does; rows sum to 1 within
# a few % after the 20 published rounds, columns exactly). Mean off-diagonal
# mass of H_res (a row's share outside its own copy): about 0.2, by block
# 0.05-0.5.
A_PRE_POST = 1.0
A_RES = 0.5
OWN_COPY = 3.0
RES_SPREAD = 1.5
PRE_POST_SPREAD = 0.5


def _make_mix(key, dims: dict) -> dict:
    n, C = dims["n"], mix_columns(dims)
    out = {}
    for sub, k in zip(SUBLAYERS, jax.random.split(key, len(SUBLAYERS))):
        k_phi, k_bias, k_res = jax.random.split(k, 3)
        out[f"{sub}_hc_phi"] = (
            jax.random.normal(k_phi, (C, n * dims["D"]), jnp.float32)
            / math.sqrt(n * dims["D"]))
        out[f"{sub}_hc_scale"] = jnp.asarray([A_PRE_POST, A_PRE_POST, A_RES],
                                             jnp.float32)
        res = (OWN_COPY * jnp.eye(n, dtype=jnp.float32) + RES_SPREAD
               * jax.random.normal(k_res, (n, n), jnp.float32))
        out[f"{sub}_hc_bias"] = jnp.concatenate([
            PRE_POST_SPREAD * jax.random.normal(k_bias, (2 * n,), jnp.float32),
            res.reshape(n * n)])
    return out


# Queries (W_qb) at HALF the gain of Normal(0, 1/fan_in), where the family's
# draw has twice (reference/mla_moe.py: QUERY_GAIN 2, for 12 blocks under a
# scale of 192^-0.5). YaRN's mscale^2 doubles the scores again, and 20 blocks
# attend: as the family draws them the scores have a deviation of 4, every
# softmax multiplies the stream's rounding (reference/mla_moe.py tells that
# story of its own gain 4), and at the published widths the program's logits
# (deviation 1.0) lay 0.37 rms from this reference's, the reference at int8
# 0.77 (my chip run, PR 39: the first run of the cell read gap_mean 0.41 with
# 68 % of served tokens not the reference's first). At gain 1 they lie 0.046
# apart (int8 0.164), at gain 0.5 0.016 (int8 0.070): scores of deviation 1.0
# over contexts of 100-1,150 tokens, where a row that attends another row's
# pages still serves other tokens (the cell's `limits_from`). 0.5 / 2 = 1/4
# is exact in bfloat16: the family's draw times a power of two.
QUERY_GAIN = 0.5


def make_mix(dims: dict, seed: int) -> list:
    """The mix's leaves of every block, from a key of their own: one jitted
    call a block."""
    k_mix = jax.random.fold_in(base.weights.key_of(seed), 0x6d6863)
    make = jax.jit(lambda key: _make_mix(key, dims))
    return [make(jax.random.fold_in(k_mix, index))
            for index in range(dims["L"])]


def make_params(dims: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The family's own draw with W_qb at QUERY_GAIN, and the mix's leaves."""
    params = base.make_params(dims, seed, dtype)
    quieter = QUERY_GAIN / base.QUERY_GAIN
    layers = [{**w, "wq_b": w["wq_b"] * jnp.asarray(quieter, w["wq_b"].dtype),
               **mix}
              for w, mix in zip(params["layers"], make_mix(dims, seed))]
    return {**params, "layers": layers}


# -- shape facts ------------------------------------------------------------
def mix_phi_bytes(dims: dict) -> int:
    """One sublayer's phi, float32."""
    return mix_columns(dims) * dims["n"] * dims["D"] * 4


def facts(config: dict, dims: dict) -> dict:
    """The family's facts with phi among the weights a step reads. The
    mix's two kernels (ops/mhc.py, two calls a block a step each) get no
    least bytes here: a decode step's stream of some hundred rows stays on
    the chip between the step's operations, so the bytes of a stream that
    goes through HBM (ISSUE 39's count) read 112 % of a roofline that is
    not theirs, and phi alone is a constant over the kernels' time, which
    `mhc_share_pct` reads already (PERF.md section 6, PR 39)."""
    out = base.facts(config, dims)
    out["decode_weight_bytes"] += 2 * dims["L"] * mix_phi_bytes(dims)
    return out


# -- the forward --------------------------------------------------------------
def yarn_range(dims: dict):
    """(low, high): the pairs between which the frequencies blend."""
    d, theta = dims["rope"], dims["theta"]

    def pair(turns):
        return (d * math.log(dims["original"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(pair(dims["beta_fast"])), 0),
            min(math.ceil(pair(dims["beta_slow"])), d - 1))


def inv_freq(dims: dict):
    """[rope / 2]: theta_i blended with theta_i / factor over the ramp."""
    half = dims["rope"] // 2
    theta_i = dims["theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    low, high = yarn_range(dims)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return theta_i * (1.0 - ramp) + theta_i / dims["factor"] * ramp


def score_scale(dims: dict) -> float:
    mscale = 0.1 * dims["mscale_all_dim"] * math.log(dims["factor"]) + 1.0
    return mscale * mscale / math.sqrt(dims["nope"] + dims["rope"])


def rope(x, positions, freqs):
    """x [T, ..., d]: the pair (x[2i], x[2i + 1]) turns by
    positions[t] * freqs[i]."""
    angles = positions.astype(jnp.float32)[:, None] * freqs       # [T, half]
    angles = angles.reshape(x.shape[0], *(1,) * (x.ndim - 2), freqs.shape[0])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def attention(x, w: dict, dims: dict, lower=None):
    """x [T, D] (normed) -> [T, D]: the family's non-absorbed form, a head
    at a time, with the blended frequencies and the scaled scores."""
    T = x.shape[0]
    H, nope, dr, dv = dims["H"], dims["nope"], dims["rope"], dims["dv"]
    positions, freqs, scale = jnp.arange(T), inv_freq(dims), score_scale(dims)
    weight, norm = base._weight, base.rms_norm
    c_q = norm(x @ weight(w["wq_a"], lower), w["q_norm"], dims["eps"])
    q = (c_q @ weight(w["wq_b"], lower)).reshape(T, H, nope + dr)
    kv = x @ weight(w["wkv_a"], lower)
    c_kv = norm(kv[:, :dims["r"]], w["kv_norm"], dims["eps"])
    k_r = rope(kv[:, dims["r"]:], positions, freqs)               # [T, dr]
    kv_b = (c_kv @ weight(w["wkv_b"], lower)).reshape(T, H, nope + dv)
    q_r = rope(q[:, :, nope:], positions, freqs)                  # [T, H, dr]
    causal = positions[None, :] <= positions[:, None]             # [t, s]

    def head(inputs):
        q_n, q_rope, k_n, v = inputs                              # [T, .]
        scores = (q_n @ k_n.T + q_rope @ k_r.T) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v                # [T, dv]

    heads = jax.lax.map(head, (
        jnp.moveaxis(q[:, :, :nope], 1, 0), jnp.moveaxis(q_r, 1, 0),
        jnp.moveaxis(kv_b[:, :, :nope], 1, 0),
        jnp.moveaxis(kv_b[:, :, nope:], 1, 0)))                   # [H, T, dv]
    return jnp.moveaxis(heads, 0, 1).reshape(T, H * dv) \
        @ weight(w["wo"], lower)


def mappings(X, w: dict, sub: str, dims: dict, lower=None):
    """X [T, n, D] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of the
    sublayer `sub`."""
    T, n, D = X.shape
    xf = X.reshape(T, n * D)
    normed = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                                + dims["eps"])
    m = normed @ base._weight(w[f"{sub}_hc_phi"].T, lower)
    a, b = w[f"{sub}_hc_scale"], w[f"{sub}_hc_bias"]
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    M = jnp.exp(jnp.clip(
        a[2] * m[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n),
        dims["clamp_lo"], dims["clamp_hi"]))
    for _ in range(dims["iters"]):
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + dims["hc_eps"])
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + dims["hc_eps"])
    return pre, post, M


def sublayer(X, w: dict, sub: str, F, dims: dict, lower=None):
    """X [T, n, D] -> X' [T, n, D] around the sublayer F: [T, D] -> [T, D]
    (which takes the block's own input norm of u)."""
    pre, post, res = mappings(X, w, sub, dims, lower)
    u = jnp.einsum("ti,tid->td", pre, X)
    f = F(base.rms_norm(u, w[f"{sub}_norm"], dims["eps"]))
    return (jnp.einsum("tij,tjd->tid", res, X)
            + post[:, :, None] * f[:, None, :])


def block(X, w: dict, dims: dict, lower=None):
    """One block over one sequence. X [T, n, D] float32."""
    X = sublayer(X, w, "attn", lambda x: attention(x, w, dims, lower), dims,
                 lower)
    return sublayer(X, w, "ffn", lambda x: base.ffn(x, w, dims, lower), dims,
                    lower)


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _block(X, w, dims, lower=None):
    with jax.default_matmul_precision("highest"):
        return block(X, w, dict(dims), lower)


def logits(params: dict, dims: dict, tokens, lower=None):
    """[T, V] float32 logits of one token sequence [T]: row t scores the
    token that follows tokens[:t + 1]."""
    frozen = tuple(sorted(dims.items()))
    x = base._embed(params["tok_emb"], jnp.asarray(tokens, jnp.int32),
                    lower=lower)
    X = jnp.broadcast_to(x[:, None, :], (x.shape[0], dims["n"], x.shape[1]))
    for w in params["layers"]:
        X = _block(X, w, frozen, lower=lower)
    return base._head(jnp.sum(X, axis=1), params["final_norm"],
                      params["lm_head"], dims["eps"], lower=lower)
