"""Per-request top_p / top_k: the [B, 3] row-control sampling plane.

sampling_controls=True widens the engine's per-row sampling state from [B]
temperatures to [B, 3] (temperature, top_p, top_k) — every program signature
is unchanged (the state travels as one array), and a row's 0 disables that
control. Key deterministic property used throughout: top_k=1 (or a
vanishingly small top_p) at ANY temperature must reproduce greedy output
exactly, because the truncated distribution has one survivor.
"""

import dataclasses

import pytest

import jax
import jax.numpy as jnp
import numpy as np

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.tpu.paging import PagedLLMEngine
from gofr_tpu.tpu.sampling import pack_controls, sample_tokens, temperature_of

CFG = LlamaConfig.debug()
PROMPTS = [[5, 6, 7, 8, 5, 6, 7, 8], [9, 8, 7], list(range(20, 50)), [11]]


def test_sampler_per_row_top_k_one_is_greedy():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(1), (6, 64),
                               dtype=jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    samp = jnp.asarray(pack_controls(
        temperature=[1.0] * 6, top_p=[0.0] * 6, top_k=[1] * 6))
    toks, _ = sample_tokens(logits, rng, samp)
    assert jnp.array_equal(toks, greedy)


def test_sampler_per_row_tiny_top_p_is_greedy():
    rng = jax.random.PRNGKey(0)
    logits = jax.random.normal(jax.random.PRNGKey(2), (6, 64),
                               dtype=jnp.float32) * 4.0
    greedy = jnp.argmax(logits, axis=-1)
    samp = jnp.asarray(pack_controls(
        temperature=[0.9] * 6, top_p=[1e-4] * 6, top_k=[0] * 6))
    toks, _ = sample_tokens(logits, rng, samp)
    assert jnp.array_equal(toks, greedy)


def test_sampler_rows_are_independent():
    """One dispatch, mixed rows: greedy row, top_k=1 row, unrestricted
    sampled row — each row's control applies to that row only."""
    rng = jax.random.PRNGKey(3)
    logits = jax.random.normal(jax.random.PRNGKey(4), (3, 256),
                               dtype=jnp.float32)
    greedy = jnp.argmax(logits, axis=-1)
    samp = jnp.asarray(pack_controls(
        temperature=[0.0, 1.0, 50.0],   # row 2: near-uniform sampling
        top_p=[0.0, 0.0, 0.0],
        top_k=[0, 1, 0]))
    toks, _ = sample_tokens(logits, rng, samp)
    assert toks[0] == greedy[0]
    assert toks[1] == greedy[1]
    # row 2 at temperature 50 over 256 logits: overwhelmingly unlikely to
    # hit the argmax across several rng draws — prove it CAN differ
    differed = False
    r = rng
    for _ in range(8):
        t, r = sample_tokens(logits, r, samp)
        differed = differed or int(t[2]) != int(greedy[2])
    assert differed, "unrestricted sampled row never left the argmax"


def test_temperature_of_both_shapes():
    flat = jnp.asarray([0.0, 0.7])
    wide = jnp.asarray(pack_controls([0.0, 0.7], [0.5, 0.0], [3, 0]))
    assert jnp.array_equal(temperature_of(flat), flat)
    assert jnp.array_equal(temperature_of(wide), flat)


def _serve(controls=True, submits=None, **kw):
    params = llama_init(CFG, seed=0)
    kw.setdefault("page_size", 16)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32), decode_block_size=4,
                         sampling_controls=controls, **kw)
    eng.start()
    try:
        reqs = [eng.submit(p, **(s or {"max_new_tokens": 10,
                                      "temperature": 0.0}))
                for p, s in zip(PROMPTS, submits or [None] * len(PROMPTS))]
        return [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()


def test_controls_engine_greedy_parity():
    """Pure-greedy traffic must be identical with and without the widened
    sampling state (the [B, 3] plane changes nothing for temperature 0)."""
    assert _serve(controls=True) == _serve(controls=False)


def test_top_k_one_matches_greedy_end_to_end():
    """temperature 1.0 + top_k=1 leaves one survivor per step: the served
    tokens must equal the greedy run's token-for-token."""
    want = _serve(controls=False)
    sub = [{"max_new_tokens": 10, "temperature": 1.0, "top_k": 1}
           for _ in PROMPTS]
    assert _serve(submits=sub) == want


@pytest.mark.slow  # tier-1 wall-clock budget; lighter in-lane representative kept
def test_tiny_top_p_matches_greedy_end_to_end():
    want = _serve(controls=False)
    sub = [{"max_new_tokens": 10, "temperature": 0.8, "top_p": 1e-4}
           for _ in PROMPTS]
    assert _serve(submits=sub) == want


def test_submit_validation():
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                         prefill_buckets=(8,))
    with pytest.raises(ValueError, match="sampling_controls"):
        eng.submit([1, 2], top_p=0.5)
    with pytest.raises(ValueError, match="sampling_controls"):
        eng.submit([1, 2], top_k=5)
    eng2 = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                          prefill_buckets=(8,), sampling_controls=True)
    with pytest.raises(ValueError, match="top_p"):
        eng2.submit([1, 2], top_p=1.5)
    with pytest.raises(ValueError, match="top_k"):
        eng2.submit([1, 2], top_k=-1)


@pytest.mark.parametrize("page_size", [128, 16])
def test_speculative_composes_with_controls(page_size):
    """The exact OpenAI-server default stack: page pool + speculation +
    sampling controls. Greedy rows still match the plain engine exactly
    (the verify's greedy-row rule reads temperature through
    temperature_of; r4 review repro: the acceptance used a raw
    `temps <= 0.0` against [B, 3] controls and crashed on the first
    proposed draft)."""
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=4, max_seq_len=128,
                         prefill_buckets=(8, 32), page_size=page_size,
                         speculative_tokens=4, sampling_controls=True)
    eng.start()
    try:
        reqs = [eng.submit(p, max_new_tokens=12, temperature=0.0)
                for p in PROMPTS]
        got = [r.result(timeout_s=300) for r in reqs]
    finally:
        eng.stop()
    assert got == _serve(controls=False, submits=[
        {"max_new_tokens": 12, "temperature": 0.0} for _ in PROMPTS])


def test_control_row_clears_when_slot_frees():
    """A finished top_p/top_k request must not leave its device-side
    control row behind — the sampler gates its [B, V] sort on ANY row's
    controls, so a stale row would tax every later all-greedy batch."""
    params = llama_init(CFG, seed=0)
    eng = PagedLLMEngine(params, CFG, n_slots=2, max_seq_len=64,
                         prefill_buckets=(8,), sampling_controls=True)
    eng.start()
    try:
        eng.submit([1, 2, 3], max_new_tokens=4, temperature=0.9,
                   top_p=0.5, top_k=3).result(timeout_s=300)
        deadline = 300
        import time as _t
        end = _t.time() + deadline
        while any(s.active for s in eng.slots) and _t.time() < end:
            _t.sleep(0.01)
        controls = np.asarray(eng._temps)[:, 1:]
        assert (controls == 0.0).all(), controls
    finally:
        eng.stop()


def test_row_top_k_then_top_p_composition():
    """ADVICE r4: when a row sets BOTH filters, the nucleus mass must be
    computed over the top_k-FILTERED renormalized distribution (HF/vLLM
    composition). Construct logits where the two orders provably differ:
    probs ~ [0.4, 0.3, 0.2, 0.1]; top_k=2 renormalizes to [0.571, 0.429];
    top_p=0.5 must then keep ONLY token 0 (0.571 >= 0.5) — whereas top_p
    over the unfiltered distribution keeps tokens {0, 1} (0.4 < 0.5).
    Sampling at any seed must therefore always return token 0."""
    p = np.array([0.4, 0.3, 0.2, 0.1] + [1e-9] * 60)
    logits = jnp.asarray(np.log(p / p.sum()), dtype=jnp.float32)[None, :]
    samp = jnp.asarray(pack_controls(temperature=[1.0], top_p=[0.5],
                                     top_k=[2]))
    rng = jax.random.PRNGKey(0)
    for _ in range(20):
        toks, rng = sample_tokens(logits, rng, samp)
        assert int(toks[0]) == 0


def test_row_top_p_alone_keeps_small_prefix():
    """Same distribution, top_p=0.5 with no top_k: nucleus over the raw
    distribution is {0, 1} (0.4 < 0.5 <= 0.7) — token 2 never samples."""
    p = np.array([0.4, 0.3, 0.2, 0.1] + [1e-9] * 60)
    logits = jnp.asarray(np.log(p / p.sum()), dtype=jnp.float32)[None, :]
    samp = jnp.asarray(pack_controls(temperature=[1.0], top_p=[0.5],
                                     top_k=[0]))
    rng = jax.random.PRNGKey(0)
    seen = set()
    for _ in range(40):
        toks, rng = sample_tokens(logits, rng, samp)
        seen.add(int(toks[0]))
    assert seen <= {0, 1} and 0 in seen
