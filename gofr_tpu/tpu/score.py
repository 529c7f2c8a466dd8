"""Post-hoc model passes: per-token logprobs (OpenAI `logprobs`) and
sequence embeddings (`/v1/embeddings`).

Design: additive passes instead of plumbing through the serving hot path.
For this engine's decoding (greedy / temperature / top-k/p are all draws
from the position's distribution), the distribution at completion position
i conditions only on the tokens before it — so a teacher-forced forward
over prompt+completion reproduces the decode-time distributions exactly,
and one additive program family delivers chosen-token logprobs + top-K
alternatives with ZERO changes to the prefill/decode/speculative programs
or their signatures. The cost model matches how the features are used:
nothing on the default path, one bucketed forward per request that asks.

Both passes share ONE windowed-cache driver (`_window_pass`): W tokens per
dispatch against a bucket-sized running cache, so the scoring pass's
logits buffer is [1, W, V] (~64 MB at Llama-3 vocab) instead of
[1, S, V], and the embedding pass never materializes logits at all. Top-K
reduces on device; only [W, K+1] floats (or one [D] row) cross to the
host per window.

Parity: the reference returns exactly what its upstream surface promises
rather than approximations (responder envelope discipline,
/root/reference/pkg/gofr/http/responder.go:24-50); here the promise is
OpenAI's `logprobs` / `embeddings` contracts on the /v1 surface.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def _window_pass(engine, length: int, program_name: str, make_fn,
                 window_args, collect, work_length=None) -> None:
    """Shared windowed-cache driver for the post-hoc passes.

    Owns the mechanics both passes must agree on — bucket selection, fp
    cache init (the plain model forward, independent of the engine's
    serving kv_dtype), W-sized zero-padded windows, broadcast positions,
    and executor compilation with donated caches — so the passes cannot
    silently diverge. Runs independently of the serving loop (no engine
    state is touched; device execution interleaves with serving dispatches
    under JAX's own serialization), so a busy server scores/embeds without
    pausing decode.

    make_fn(cfg, W, mesh) builds the window program (signature
    (params, *extra, positions, k, v) -> (k, v, *outputs));
    window_args(w0, n, W) returns the pass-specific extra arrays for the
    window starting at w0 holding n live tokens; collect(w0, n, W, outs)
    receives the outputs past (new_k, new_v). Padded tail positions
    produce garbage the collectors slice away — causality guarantees they
    cannot contaminate earlier positions.
    """
    import math

    import jax.numpy as jnp

    from ..models.llama import init_kv_cache
    from .executor import next_bucket

    S = next_bucket(length, engine.prefill_buckets)
    # W must DIVIDE S: prefill buckets are config-controlled (the
    # llm-server parses arbitrary ints), and a bucket like 192 would give
    # the final 128-wide window positions past the S-length cache —
    # "working" only by JAX's out-of-bounds scatter-drop while attention
    # reads garbage. gcd(S, 128) always divides S; power-of-two buckets
    # keep the full W=128 window (ADVICE r5).
    W = math.gcd(S, 128)
    k, v = init_kv_cache(engine.cfg, 1, S)
    fn = make_fn(engine.cfg, W, engine.mesh)
    # work_length < length lets a pass skip trailing positions it never
    # reads (scoring: position L-1 has no target, so an L ≡ 1 (mod W)
    # sequence must not dispatch a whole discarded window for it)
    for w0 in range(0, work_length or length, W):
        n = min(W, length - w0)
        positions = jnp.broadcast_to(
            jnp.arange(w0, w0 + W, dtype=jnp.int32), (1, W))
        args = (engine.params, *window_args(w0, n, W), positions, k, v)
        program = engine.executor.compile(
            f"{program_name}-{S}x{W}", fn, args,
            donate_argnums=(len(args) - 2, len(args) - 1))
        k, v, *outs = program(*args)
        collect(w0, n, W, outs)


def make_score_fn(cfg, W: int, K: int, mesh=None):
    """Window program: forward W tokens against the running cache, emit
    (new_k, new_v, chosen_lp [W], top_ids [W, K], top_lps [W, K]).

    `targets[j]` is the NEXT token after window position j (what the model
    was asked to predict there)."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import llama_forward

    def fn(params, toks, targets, positions, k, v):
        logits, k, v = llama_forward(params, cfg, toks, positions, k, v,
                                     mesh)
        lsm = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        top_lps, top_ids = jax.lax.top_k(lsm, K)
        chosen = jnp.take_along_axis(lsm, targets[0][:, None], axis=1)[:, 0]
        return k, v, chosen, top_ids, top_lps

    return fn


def make_embed_fn(cfg, W: int, mesh=None):
    """Window program for embeddings: forward W tokens against the running
    cache, emit (new_k, new_v, hidden [W, D]) — the final-norm hidden
    states (llama_forward_hidden); the host takes the last live position's
    row. No vocab projection at all: the [1, W, V] logits buffer never
    exists on this pass."""
    from ..models.llama import llama_forward_hidden

    def fn(params, toks, positions, k, v):
        hidden, k, v = llama_forward_hidden(params, cfg, toks, positions,
                                            k, v, mesh)
        return k, v, hidden[0]

    return fn


# every scoring program computes the MAXIMUM top-K and the host slices to
# the requested `top`: the extra lanes cost nothing next to the forward,
# and it keeps the program family keyed by bucket alone — so one warmup
# pass per bucket covers every client top value (no per-top cache misses)
_SCORE_K = 20


def score_tokens(engine, prompt_tokens: Sequence[int],
                 completion_tokens: Sequence[int], top: int = 5,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-token logprobs for `completion_tokens` given `prompt_tokens`.

    Returns (chosen_lp [C], top_ids [C, top], top_lps [C, top]) as numpy.
    Compiles one program per (cache bucket, window) pair through the
    engine's executor — bounded like every other program family.
    """
    import jax.numpy as jnp

    if not completion_tokens:
        raise ValueError("completion_tokens must be non-empty")
    if not 1 <= top <= _SCORE_K:
        raise ValueError(f"top must be in [1, {_SCORE_K}], got {top}")
    seq = list(prompt_tokens) + list(completion_tokens)
    P, L = len(prompt_tokens), len(seq)
    if P < 1:
        raise ValueError("prompt_tokens must be non-empty")
    if L > engine.prefill_buckets[-1]:
        raise ValueError(f"prompt+completion of {L} tokens exceeds the "
                         f"largest scoring bucket "
                         f"({engine.prefill_buckets[-1]})")

    chosen_parts: List[np.ndarray] = []
    ids_parts: List[np.ndarray] = []
    lps_parts: List[np.ndarray] = []

    def window_args(w0, n, W):
        toks = np.zeros((1, W), dtype=np.int32)
        targets = np.zeros((1, W), dtype=np.int32)
        toks[0, :n] = seq[w0:w0 + n]
        m = min(W, L - 1 - w0)  # positions with a real target
        targets[0, :m] = seq[w0 + 1:w0 + 1 + m]
        return jnp.asarray(toks), jnp.asarray(targets)

    def collect(w0, n, W, outs):
        m = min(W, L - 1 - w0)
        if m <= 0:
            return
        chosen, top_ids, top_lps = outs
        chosen_parts.append(np.asarray(chosen)[:m])
        ids_parts.append(np.asarray(top_ids)[:m])
        lps_parts.append(np.asarray(top_lps)[:m])

    _window_pass(engine, L, "score",
                 lambda cfg, W, mesh: make_score_fn(cfg, W, _SCORE_K, mesh),
                 window_args, collect, work_length=L - 1)

    chosen = np.concatenate(chosen_parts)[P - 1:L - 1]
    ids = np.concatenate(ids_parts)[P - 1:L - 1, :top]
    lps = np.concatenate(lps_parts)[P - 1:L - 1, :top]
    return chosen, ids, lps


def embed_tokens(engine, tokens: Sequence[int],
                 normalize: bool = True) -> np.ndarray:
    """Sequence embedding: the final-norm hidden state at the LAST
    position (the causal summary of the whole sequence — the pooling
    E5-Mistral-style decoder embedders use), optionally L2-normalized
    (the OpenAI /v1/embeddings convention: unit-length vectors). Returns
    float32 [D]."""
    import jax.numpy as jnp

    if not tokens:
        raise ValueError("tokens must be non-empty")
    L = len(tokens)
    if L > engine.prefill_buckets[-1]:
        raise ValueError(f"input of {L} tokens exceeds the largest "
                         f"embedding bucket ({engine.prefill_buckets[-1]})")
    out = {}

    def window_args(w0, n, W):
        toks = np.zeros((1, W), dtype=np.int32)
        toks[0, :n] = tokens[w0:w0 + n]
        return (jnp.asarray(toks),)

    def collect(w0, n, W, outs):
        if w0 + W >= L:  # the window holding position L-1
            out["last"] = np.asarray(outs[0][L - 1 - w0], dtype=np.float32)

    _window_pass(engine, L, "embed", make_embed_fn, window_args, collect)
    last = out["last"]
    if normalize:
        norm = float(np.linalg.norm(last))
        if norm > 0.0:
            last = last / norm
    return last


def warmup_post_hoc(engine, embeddings: bool = True) -> int:
    """Pre-compile the scoring (and optionally embedding) program families
    — one window program per cache bucket — so the first client logprobs/
    embeddings request never pays a compile under its REQUEST_TIMEOUT
    (docs/serving.md's warm-at-boot recipe, as an API). Covers EVERY
    client `top` value: the scoring program always computes _SCORE_K lanes
    and the host slices (see _SCORE_K). Returns the number of passes run.
    Cost: one bucket-length forward per bucket per family, once per boot,
    amortized across boots by PROGRAM_CACHE_DIR."""
    ran = 0
    for S in engine.prefill_buckets:
        score_tokens(engine, [1] * max(1, S - 1), [1])
        ran += 1
        if embeddings:
            embed_tokens(engine, [1] * S)
            ran += 1
    return ran
