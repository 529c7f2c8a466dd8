"""Sharding rules: PartitionSpecs for model params and batches.

Megatron-style tensor parallelism for the Llama block: attention heads and
FFN hidden dim shard over "tp" (column-parallel wq/wk/wv/gate/up, row-parallel
wo/down — XLA inserts the reduce-scatter/all-gather pairs), vocab shards the
embedding/lm_head over "tp", the stacked layer axis shards over "pp", MoE
expert axis over "ep" (training: `llama_param_specs(moe=True)`; serving,
with the vocabulary share: `expert_share_specs`). Batches shard [B, T] as ("dp", "sp") — sequence
parallelism for long context; the attention implementation decides whether
the sp collectives are all-gather (XLA auto) or a ring (ops/ring_attention).
"""

from __future__ import annotations

from typing import Any, Dict


def _P(*names):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*names)


def llama_param_specs(moe: bool = False) -> Dict[str, Any]:
    """PartitionSpec pytree matching llama_init's params structure."""
    layers = {
        # [L, D, H*dh]: heads are column-parallel over tp; L pipelines over pp
        "wq": _P("pp", None, "tp"),
        "wk": _P("pp", None, "tp"),
        "wv": _P("pp", None, "tp"),
        # [L, H*dh, D]: row-parallel (contraction dim sharded)
        "wo": _P("pp", "tp", None),
        "w_gate": _P("pp", None, "tp"),
        "w_up": _P("pp", None, "tp"),
        "w_down": _P("pp", "tp", None),
        "attn_norm": _P("pp", None),
        "ffn_norm": _P("pp", None),
    }
    if moe:
        layers.update({
            # router [L, D, E] replicated over tp (tiny); experts over ep
            "w_router": _P("pp", None, None),
            # [L, E, D, F]
            "w_gate": _P("pp", "ep", None, "tp"),
            "w_up": _P("pp", "ep", None, "tp"),
            "w_down": _P("pp", "ep", "tp", None),
        })
    return {
        "tok_emb": _P("tp", None),   # vocab-sharded embedding
        "layers": layers,
        "final_norm": _P(None),
        "lm_head": _P(None, "tp"),   # column-parallel output projection
    }


def serving_param_specs(quantized: bool = False) -> Dict[str, Any]:
    """PartitionSpec pytree for the SERVING engine: tp only.

    No pp axis — the stacked [L, ...] layer axis stays whole so the decode
    lax.scan runs every layer on every tp shard (Megatron-style: per-layer
    all-reduce rides ICI). Contiguous-block head sharding means splitting
    the flattened H*dh / Hkv*dh projection axis over tp yields whole heads
    per shard, matching the KV cache's Hkv shard (kv_cache_spec). tok_emb
    is replicated (token-id gather at arbitrary ids beats a vocab-sharded
    gather+psum for decode's tiny T); lm_head stays column-parallel.

    quantized=True matches an int8 tree (models.llama.quantize_weights):
    each per-output-channel scale vector shards exactly like its weight's
    OUTPUT axis — column-parallel weights get tp-sharded scales, row-
    parallel weights (wo/w_down, contraction sharded) keep whole scales.
    """
    layers = {
        "wq": _P(None, None, "tp"),
        "wk": _P(None, None, "tp"),
        "wv": _P(None, None, "tp"),
        "wo": _P(None, "tp", None),
        "w_gate": _P(None, None, "tp"),
        "w_up": _P(None, None, "tp"),
        "w_down": _P(None, "tp", None),
        "attn_norm": _P(None, None),
        "ffn_norm": _P(None, None),
    }
    if quantized:
        layers.update({
            "wq_s": _P(None, "tp"), "wk_s": _P(None, "tp"),
            "wv_s": _P(None, "tp"), "wo_s": _P(None, None),
            "w_gate_s": _P(None, "tp"), "w_up_s": _P(None, "tp"),
            "w_down_s": _P(None, None),
        })
    out = {
        "tok_emb": _P(None, None),
        "layers": layers,
        "final_norm": _P(None),
        "lm_head": _P(None, "tp"),
    }
    if quantized:
        out["tok_emb_s"] = _P(None)      # per-row scales ride the gather
        out["lm_head_s"] = _P("tp")      # column scales follow the vocab split
    return out


def expert_share_specs(pattern: str) -> Dict[str, Any]:
    """PartitionSpec pytree for models/nemotron_h.py's tree (one dict a
    block of `pattern`, per-block leaves): the routed experts' axis and the
    vocabulary split over "ep", everything else on every chip alike.

    This is the deployment a cut configuration states (two chips share each
    layer: each holds half the experts and half the vocabulary; tokens,
    mixers, router and shared expert on both alike): on an "ep" mesh each
    shard's `experts_held` is its slice of w1 / w2, each computes its own
    experts' part, and the parts are summed across "ep"; the head's logits
    are gathered across it. The exchange is not written yet: the engine
    refuses a mesh for the family, and on one chip the layer runs without.
    """
    rep = _P()

    def block(mark: str) -> Dict[str, Any]:
        if mark == "M":
            return dict.fromkeys(("norm", "in_proj", "conv_w", "conv_b",
                                  "dt_bias", "A_log", "D", "gate_norm",
                                  "out_proj"), rep)
        if mark == "E":
            return {"norm": rep, "router": rep, "router_bias": rep,
                    "w1": _P("ep", None, None), "w2": _P("ep", None, None),
                    "shared_w1": rep, "shared_w2": rep}
        return dict.fromkeys(("norm", "wq", "wk", "wv", "wo"), rep)

    return {"tok_emb": _P("ep", None), "layers": [block(m) for m in pattern],
            "final_norm": rep, "lm_head": _P(None, "ep")}


def kv_cache_spec():
    """Stacked KV cache/pool [L, B|P, Hkv, dh, S]: KV heads shard over tp,
    matching the column split of wk/wv so each shard writes and reads only
    its heads."""
    return _P(None, None, "tp", None, None)


def kv_cache_layer_spec():
    """One per-layer window buffer [K, Hkv, dh, S] (a chunked prefill's
    per-job temps, tpu/paging.py): KV heads over tp."""
    return _P(None, "tp", None, None)


def kv_scale_pool_spec():
    """Stacked paged scale pool [L, P, Hkv, ps]: KV heads over tp,
    row-aligned with kv_cache_spec."""
    return _P(None, None, "tp", None)


def batch_spec():
    """Token batches [B, T]: batch over dp, sequence over sp."""
    return _P("dp", "sp")


def shard_params(params, mesh, specs=None):
    """device_put the params pytree onto the mesh with NamedSharding.

    Downstream jits need no explicit in_shardings — committed input shardings
    propagate and XLA inserts the collectives (the scaling-book recipe).
    """
    import jax
    from jax.sharding import NamedSharding

    if specs is None:
        specs = llama_param_specs()

    def place(leaf, spec):
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map(place, params, specs)


def unsharded_like(tree):
    """Fully-replicated specs with the same structure (for small states)."""
    import jax

    return jax.tree_util.tree_map(lambda _: _P(), tree)
