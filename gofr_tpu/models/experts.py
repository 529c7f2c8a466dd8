"""The held-expert layer: what a block's FFN is when the block routes, for
every family that has one (models/families.py names them).

`s = sigmoid(x W_r)` over all `n_experts` in float32; the k largest of
`s + bias` are picked (the bias chooses and does not weigh; one routing
group, no group limit); weights `s_picked / sum(s_picked) * routed_scale`;
`y = sum_e w_e expert_e(x) + shared(x)`. The block is told which experts it
holds (`experts_held`): the tree carries only those ([held, F, D] a
matrix), and what the others would add is left out, here and in the
benchmark's reference alike (the other chips of the group add their
shares; on one chip there is no exchange and nothing stands in for it).
Rows that hold no request and tokens that are padding are kept out of the
routing's weights and of the counters.

What a block is, is read from its leaves, not from an option:

- no `router`: a dense SwiGLU (`w_gate`, `w_up`, `w_down`), no counters;
- `router` and `wg`: gated experts `down_e(silu(gate_e x) * up_e x)`
  (`w1` up, `wg` gate, `w2` down) and a SwiGLU shared expert
  (`shared_gate`, `shared_up`, `shared_down`);
- `router` without `wg`: ungated experts `down_e(relu(up_e x)^2)` and a
  shared expert of the same form (`shared_w1`, `shared_w2`): Nemotron's.

The grouped products are ops/moe_experts.py's, which has the same switch
(`wg=`). The router, the dense block and the shared expert are XLA's.

Config fields read: `dim`, `n_experts`, `experts_held`, `held`
(`HeldExperts` answers it), `experts_per_token`, `routed_scale`,
`expert_dim`, `shared_dim`, and for the counters `expert_layers`.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

# what a decode step counts, a row of int32 a step (summed over the expert
# blocks): live rows, picks that fell on held experts, held experts touched,
# the busiest held expert's tokens
COUNTERS = ("rows", "held_picks", "experts_touched", "busiest_expert_tokens")

# the layer's leaves held in float32 whatever `dtype` is, as the published
# checkpoints keep them
FLOAT32_LEAVES = ("router_bias",)


class HeldExperts:
    """What a config with `n_experts` and `experts_held` answers of the
    range this chip holds."""

    @property
    def held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def check_experts_held(self) -> None:
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_experts} experts")


def expert_shapes(cfg, gated: bool = True) -> Dict[str, tuple]:
    """The leaves of a block that routes (beside its norm)."""
    D = cfg.dim
    expert = (cfg.held, cfg.expert_dim, D)
    router = {"router": (D, cfg.n_experts), "router_bias": (cfg.n_experts,)}
    if gated:
        return {**router, "w1": expert, "wg": expert, "w2": expert,
                "shared_gate": (D, cfg.shared_dim),
                "shared_up": (D, cfg.shared_dim),
                "shared_down": (cfg.shared_dim, D)}
    return {**router, "w1": expert, "w2": expert,
            "shared_w1": (D, cfg.shared_dim),
            "shared_w2": (cfg.shared_dim, D)}


def expert_params(cfg, gated: bool = True) -> Dict[str, int]:
    """Matrix parameters of an expert FFN as held (`experts_held`) and as a
    token meets it (`experts_met`: the router, the shared expert and the
    share of its k picks that falls on held experts): three matrices an
    expert where the block is gated, two where it is not."""
    shapes = expert_shapes(cfg, gated)
    per_expert = sum(math.prod(shape[1:]) for shape in shapes.values()
                     if len(shape) == 3)
    outside = sum(math.prod(shape) for shape in shapes.values()
                  if len(shape) == 2)
    return {"experts_held": outside + cfg.held * per_expert,
            "experts_met": outside + cfg.experts_per_token * per_expert
            * cfg.held // cfg.n_experts}


# -- routing ------------------------------------------------------------------
def route(x, w, cfg):
    """(picks [..., k] int32 over ALL experts, weights [..., k] float32):
    sigmoid scores in float32 at full matmul precision (as the published
    code), the k largest of score + bias picked, weighted by their scores
    normalised and scaled."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, picks = jax.lax.top_k(s + w["router_bias"], cfg.experts_per_token)
    chosen = jnp.take_along_axis(s, picks, axis=-1)
    chosen = cfg.routed_scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return picks.astype(jnp.int32), chosen


def combine_held(x, w, live, cfg):
    """A decode step's routing over the experts held: (combine [B, held]
    float32, zero where a row did not pick the expert and for every pick
    of a row that holds no request; counters [3] int32 of COUNTERS less
    `rows`)."""
    lo, hi = cfg.experts_held
    picks, weights = route(x, w, cfg)
    mine = (picks >= lo) & (picks < hi) & live[:, None]           # [B, k]
    rows = jnp.arange(x.shape[0])[:, None]
    combine = jnp.zeros((x.shape[0], cfg.held + 1), jnp.float32).at[
        rows, jnp.where(mine, picks - lo, cfg.held)].set(
            jnp.where(mine, weights, 0.0))[:, :cfg.held]
    tokens = jnp.sum(combine != 0.0, axis=0)                      # an expert
    return combine, jnp.stack([jnp.sum(mine), jnp.sum(tokens > 0),
                               jnp.max(tokens)]).astype(jnp.int32)


# -- the block's FFN ----------------------------------------------------------
def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _dense(x, w):
    return _swiglu(x, w["w_gate"], w["w_up"], w["w_down"])


def _with_shared(routed, x, w):
    """The held experts' sum (float32) in x's dtype plus the block's shared
    expert over x. The two forms keep the order each has always traced in
    (the cast after the gated form's products, before the ungated's), so
    that a family's program is the one it was."""
    if "wg" in w:
        shared = _swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"])
        return routed.astype(x.dtype) + shared
    from ..ops.moe_experts import relu2

    return routed.astype(x.dtype) + relu2(x @ w["shared_w1"]) @ w["shared_w2"]


def ffn_prefill(x, w, real, cfg):
    """x [K, T, D] (normed); real [K, T] marks tokens that are not
    padding. The dense FFN, or the held experts by a grouped product over
    the (token, pick) pairs sorted by expert plus the shared expert over
    every token."""
    if "router" not in w:
        return _dense(x, w)
    from ..ops.moe_experts import prefill_experts

    K, T, D = x.shape
    flat = x.reshape(K * T, D)
    picks, weights = route(flat, w, cfg)
    weights = jnp.where(real.reshape(K * T, 1), weights, 0.0)
    routed = prefill_experts(flat, w["w1"], w["w2"], picks, weights,
                             cfg.experts_held[0], cfg.n_experts,
                             tm=min(128, max(8, K * T)), wg=w.get("wg"))
    return _with_shared(routed, flat, w).reshape(K, T, D)


def ffn_decode(x, w, live, cfg):
    """x [B, D] (normed); live [B]. Returns (out [B, D], counters [3]
    int32 of COUNTERS less `rows`, zeros for the dense block)."""
    if "router" not in w:
        return _dense(x, w), jnp.zeros((3,), jnp.int32)
    from ..ops.moe_experts import decode_experts

    combine, counted = combine_held(x, w, live, cfg)
    routed = decode_experts(x, w["w1"], w["w2"], combine, wg=w.get("wg"))
    return _with_shared(routed, x, w), counted


# -- what the counters say ----------------------------------------------------
def step_counters(live, counted, *more):
    """A decode step's row of COUNTERS: the live rows, then `counted` (the
    expert blocks' sum of `ffn_decode`'s three), then the family's own
    int32 scalars."""
    return jnp.concatenate([jnp.sum(live, dtype=jnp.int32)[None], counted,
                            *(count[None] for count in more)])


def routing_summary(cfg, counts: Dict[str, int], steps: int):
    """What COUNTERS' sums over `steps` decode steps say of the routing,
    an expert block (`cfg.expert_layers` of them) and step; None before
    any live step."""
    layer_steps = steps * cfg.expert_layers
    if not layer_steps or not counts["rows"]:
        return None
    mean = counts["held_picks"] / (layer_steps * cfg.held)
    return {
        "rows_per_step": counts["rows"] / steps,
        "tokens_per_held_expert_mean": mean,
        "tokens_per_held_expert_max_over_mean": (
            counts["busiest_expert_tokens"] / layer_steps / mean
            if mean else 0.0),
        "experts_touched_per_layer_step":
            counts["experts_touched"] / layer_steps,
        "held_pick_share": counts["held_picks"] / (
            counts["rows"] * cfg.experts_per_token * cfg.expert_layers)}


def describe(cfg, counts: Dict[str, int], steps: int):
    """The layer's part of `/debug/engine` "model": the experts held and,
    once a live step was counted, how the routing fell."""
    out = {"experts_held": cfg.held, "experts_total": cfg.n_experts}
    routing = routing_summary(cfg, counts, steps)
    if routing:
        out["routing"] = routing
    return out
