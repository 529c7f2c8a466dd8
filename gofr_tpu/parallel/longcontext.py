"""Sequence-parallel Llama forward: activations stay sharded [B, T/sp, ...].

SURVEY.md §5 "Long-context / sequence parallelism": when the sequence
exceeds one chip's HBM, annotations alone don't help — XLA would all-gather
K/V to run attention. This forward runs the WHOLE layer stack inside
shard_map over the sp axis, so every projection, norm, and FFN touches only
the device's T/sp chunk, and the one position-dependent op — attention —
goes through a collective primitive:

  - "ring":    ops/ring_attention — K/V blocks rotate via ppermute, memory
               O(T/sp) per chip, sp-1 hops overlapped with compute
  - "ulysses": ops/ulysses — two all_to_alls re-shard to head-parallel and
               back, unmodified flash kernel in between

RoPE stays correct because each device computes its chunk's ABSOLUTE
positions from axis_index(sp). Params are replicated (sp shards
activations — the HBM term that grows with T — not weights; see
sp_llama_forward's docstring for the tp-composition constraint).
Differentiable end-to-end: the same function serves the long-context
training step.
"""

from __future__ import annotations

from typing import Optional


def sp_llama_forward(params, cfg, tokens, mesh, attn: str = "ring",
                     dp_axis: str = "dp", sp_axis: str = "sp"):
    """Causal LM forward with sequence parallelism over `sp_axis`.

    tokens: [B, T] with T divisible by the sp axis size (pad to the sequence
    bucket first — the scheduler's rule anyway). Returns logits [B, T, V]
    sequence-sharded ("dp", "sp", None).

    Params are REPLICATED across the mesh inside this path (in_specs P()):
    the shard_map body contains no tensor-parallel collectives, so weight
    sharding cannot be expressed here — combining sp with tp-sharded
    weights means adding the row-parallel psums to the body (future work)
    or using the annotation-based forward, where XLA inserts them but
    all-gathers K/V over sp. sp here shards ACTIVATIONS, which is the HBM
    term that grows with T; weights are O(1) in sequence length.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..models.llama import forward_nocache_at
    from ..ops.ring_attention import ring_attention
    from ..ops.ulysses import ulysses_attention

    if attn == "ring":
        attn_impl = ring_attention
    elif attn == "ulysses":
        attn_impl = ulysses_attention
    else:
        raise ValueError(f"unknown sequence-parallel attention {attn!r} "
                         "(supported: ring, ulysses)")
    sp = mesh.shape[sp_axis]
    T = tokens.shape[1]
    if T % sp != 0:
        raise ValueError(f"sequence length {T} must divide by |{sp_axis}|={sp}")

    def local(params, tokens):
        B, T_local = tokens.shape
        chunk = jax.lax.axis_index(sp_axis)
        positions = jnp.broadcast_to(
            chunk * T_local + jnp.arange(T_local, dtype=jnp.int32)[None, :],
            (B, T_local))
        return forward_nocache_at(
            params, cfg, tokens, positions,
            attn_fn=lambda q, k, v: attn_impl(q, k, v, axis_name=sp_axis))

    pspecs = jax.tree_util.tree_map(lambda _: P(), params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspecs, P(dp_axis, sp_axis)),
        out_specs=P(dp_axis, sp_axis, None),
        check_vma=False)(params, tokens)


def make_sp_forward(cfg, mesh, attn: str = "ring"):
    """Bind (cfg, mesh, attn) into a forward_fn for train.make_train_step."""
    def forward(params, tokens):
        return sp_llama_forward(params, cfg, tokens, mesh, attn=attn)

    return forward
