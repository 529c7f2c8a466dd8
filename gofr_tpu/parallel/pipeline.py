"""Pipeline parallelism: GPipe microbatch schedule inside one jit.

Layers are stacked [L, ...] and sharded over the "pp" mesh axis; inside
shard_map each rank holds L/pp contiguous layers and runs them as one stage.
Microbatches flow through the wavefront: at step s, rank r processes
microbatch s - r (when 0 <= s - r < n_micro); activations hop to the next
rank via ppermute each step. The whole schedule is a lax.scan, so it compiles
to a single XLA program and is differentiable end to end (ppermute's
transpose is the reverse permutation — backward pipelines in the opposite
direction automatically).

Junk-compute note: ranks process zero-filled activations outside their valid
window (static shapes — compute is not data-dependent); outputs are recorded
only on the last rank inside the valid window, so junk never reaches the
loss. The bubble cost is the usual (pp - 1) / (n_micro + pp - 1).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def gpipe(stage_fn: Callable, local_params, x_micro, axis_name: str = "pp"):
    """Run the pipeline schedule. Must be called inside shard_map over `axis_name`.

    stage_fn(local_params, x) -> x' applies this rank's layer stack.
    x_micro: [n_micro, mb, ...] microbatched input (meaningful on rank 0;
    other ranks receive activations over the ring).
    Returns [n_micro, mb, ...] outputs (replicated across the pp axis).
    """
    pp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    n_micro = x_micro.shape[0]
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def step_fn(carry, step):
        inbuf, outputs = carry
        idx = jnp.clip(step, 0, n_micro - 1)
        is_first = (rank == 0)
        x_in = jnp.where(is_first, x_micro[idx], inbuf)
        h = stage_fn(local_params, x_in)
        out_idx = step - (pp - 1)
        record = (rank == pp - 1) & (out_idx >= 0) & (out_idx < n_micro)
        safe_idx = jnp.clip(out_idx, 0, n_micro - 1)
        outputs = jnp.where(record, outputs.at[safe_idx].set(h), outputs)
        inbuf_next = jax.lax.ppermute(h, axis_name, perm)
        return (inbuf_next, outputs), None

    inbuf0 = jnp.zeros_like(x_micro[0])
    outputs0 = jnp.zeros_like(x_micro)
    n_steps = n_micro + pp - 1
    (_, outputs), _ = jax.lax.scan(step_fn, (inbuf0, outputs0),
                                   jnp.arange(n_steps))
    # replicate the last rank's outputs across the pp group
    return jax.lax.psum(jnp.where(rank == pp - 1, outputs, 0.0), axis_name)


def pipelined_llama_forward(params, cfg, tokens, mesh, n_microbatches: int = 4):
    """Full Llama forward with the layer stack pipelined over "pp".

    Embedding and the LM head run outside the pipeline (they belong to the
    first/last stage in a by-hand split; here they are replicated — cheap at
    the sizes where pp matters less than the block stack). Differentiable:
    usable directly in a training step.
    """
    from jax.sharding import PartitionSpec as P

    from ..models.blocks import rms_norm

    B, T = tokens.shape
    if B % n_microbatches != 0:
        raise ValueError(f"batch {B} not divisible by {n_microbatches} microbatches")
    x = params["tok_emb"][tokens]  # [B, T, D]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))

    def stage_fn(local_layers, h):
        # h: [mb, T, D]; local_layers: pytree with leading local-L axis
        from ..models.llama import attention_block_nocache, ffn_block

        def body(h, layer):
            attn = attention_block_nocache(h, layer, positions[:h.shape[0]], cfg)
            h = h + attn
            h = h + ffn_block(h, layer, cfg)
            return h, None

        h, _ = jax.lax.scan(body, h, local_layers)
        return h

    mb = B // n_microbatches
    x_micro = x.reshape(n_microbatches, mb, T, x.shape[-1])

    layer_specs = jax.tree_util.tree_map(
        lambda leaf: P(*(("pp",) + (None,) * (leaf.ndim - 1))), params["layers"])
    piped = jax.shard_map(
        lambda lp, xm: gpipe(stage_fn, lp, xm, axis_name="pp"),
        mesh=mesh,
        in_specs=(layer_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = piped(params["layers"], x_micro)
    x = out.reshape(B, T, -1).astype(x.dtype)
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return (x @ params["lm_head"]).astype(jnp.float32)
