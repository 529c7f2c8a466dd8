"""The causal short convolution of the recurrent families and the tail a
sequence keeps of it: a depthwise convolution of `W` taps, a channel each,
`y_t = sum_j taps[j] x_{t - (W - 1) + j}` (zeros before the sequence's
start), before any bias and activation. Mamba-2 (models/nemotron_h.py)
convolves xBC, Kimi Delta Attention (models/kda_moe.py) q, k and v; both
keep, a slot, the last W - 1 columns of the convolution's INPUT, which is
all a decode step needs of the past.

Written once: `conv_prefill` over a right-padded window, the tail taken as
of each row's last real token; `conv_decode` one token a row over the tail.
"""

from __future__ import annotations

import jax.numpy as jnp


def conv_prefill(x, taps, lengths, dtype):
    """x [K, T, c] right-padded to T, taps [W, c], lengths [K]. Returns
    (conv [K, T, c], tail [K, W - 1, c] in `dtype`: x at lengths - (W - 1)
    ... lengths - 1, zeros before the sequence's start)."""
    W, T = taps.shape[0], x.shape[1]
    at = lengths[:, None] - (W - 1) + jnp.arange(W - 1)[None, :]  # [K, W-1]
    tail = jnp.where((at >= 0)[:, :, None], jnp.take_along_axis(
        x, jnp.maximum(at, 0)[:, :, None], axis=1), 0).astype(dtype)
    padded = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + T] for j in range(W)), tail


def conv_decode(tail, layer: int, x, taps):
    """tail [L, B, W - 1, c]; `layer` this block's index there; x [B, c]
    the new column, float32; taps [W, c]. Returns (conv [B, c] float32,
    tail with the block's window moved on by one column)."""
    window = jnp.concatenate([tail[layer].astype(jnp.float32),
                              x[:, None]], axis=1)                # [B, W, c]
    tail = tail.at[layer].set(window[:, 1:].astype(tail.dtype))
    return jnp.sum(taps[None] * window, axis=1), tail
