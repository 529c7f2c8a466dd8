"""The kda_moe family on the serving path (ISSUE 41), at tiny widths in
float32 on the CPU, seeded: the decode update's kernel (interpret mode) and
its jax.numpy form against the token-by-token recurrence, the chunkwise
prefill against the recurrence, the program's prefill and decode through
pools and per-slot state against benchmark/reference/kda_moe.py's plain
full forward (logits compared), the expert layer's shares adding up to the
uncut block, what the family refuses, the engine end to end, and
nemotron_h's programs unchanged by the shared convolution helper."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import data  # noqa: E402

from gofr_tpu.models import experts, kda_moe  # noqa: E402
from gofr_tpu.models.kda_moe import (COUNTERS, KdaMoeConfig, REFUSES,  # noqa: E402
                                     decode_step, kda_moe_init, prefill,
                                     state_shapes)
from gofr_tpu.ops.kda_chunk import kda_chunk  # noqa: E402
from gofr_tpu.ops.kda_update import kda_update, kda_update_reference  # noqa: E402
from gofr_tpu.ops.paged_attention import (block_tail, paged_flush_block,  # noqa: E402
                                          paged_write_prefill_stacked)
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

reference = data.reference_for({"family": "kda_moe"})

CONFIG = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=4, gqa_layers=[0],
    rms_norm_eps=1e-5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, linear_attn_config=dict(
        short_conv_kernel_size=4, head_dim=16, num_heads=4,
        num_kv_heads=None),
    use_rope=False, first_k_dense_replace=0, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True, norm_topk_prob=True,
    n_routed_experts=4, n_routed_experts_published=8, experts_held=[0, 4],
    num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1,
    routed_scaling_factor=1.0)


def program_config(held=(0, 4), **kw):
    return KdaMoeConfig(
        vocab_size=512, dim=64, n_layers=4, gqa_layers=(0,), n_heads=4,
        n_kv_heads=2, head_dim=16, kda_heads=4, kda_head_dim=16,
        gate_rank=16, chunk_size=16, n_experts=8, experts_held=held,
        experts_per_token=2, expert_dim=32, shared_dim=32, max_seq_len=256,
        dtype="float32", **kw)


@pytest.fixture(scope="module")
def seeded():
    dims = reference.dims_of(CONFIG)
    return dims, reference.make_params(dims, 7, "float32")


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, size=n).tolist()


def _reference_logits(params, dims, tokens, pad_to=72):
    """The full forward, every sequence padded on the right to one length
    (causal: no real position sees the padding), so that the reference's
    blocks compile once for the file."""
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, dims, padded))[:len(tokens)]


# -- the decode update --------------------------------------------------------
def _update_inputs(seed, L, S, H, dk, dv):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return (jax.random.normal(keys[0], (L, S, H, dk, dv), jnp.float32),
            jax.random.uniform(keys[1], (S, H, dk), jnp.float32, 0.2, 1.0),
            unit(jax.random.normal(keys[2], (S, H, dk), jnp.float32)),
            unit(jax.random.normal(keys[3], (S, H, dk), jnp.float32)) / 4,
            jax.random.normal(keys[4], (S, H, dv), jnp.float32),
            2 * jax.nn.sigmoid(jax.random.normal(keys[5], (S, H))))


def _recurrence(state, a, k, q, v, b):
    """Tentpole 1's equations written out a row and a head at a time, in
    numpy float64: what both forms of the update are held to."""
    state, a, k, q, v, b = (np.asarray(x, np.float64)
                            for x in (state, a, k, q, v, b))
    new, out = np.empty_like(state), np.empty_like(v)
    for s in range(state.shape[0]):
        for h in range(state.shape[1]):
            S = a[s, h][:, None] * state[s, h]
            u = S.T @ k[s, h]
            S = S + b[s, h] * np.outer(k[s, h], v[s, h] - u)
            new[s, h], out[s, h] = S, S.T @ q[s, h]
    return out, new


@pytest.mark.parametrize("form", ["kernel", "jax.numpy"])
@pytest.mark.parametrize("live", [[True, False, True, True, False],
                                  [False] * 5, [True] * 5])
def test_kda_update_is_the_recurrence(form, live):
    """Both forms against the recurrence: live rows of the block move, dead
    rows and the other block keep their state, b > 1 is present."""
    state, a, k, q, v, b = _update_inputs(3, 2, 5, 4, 16, 16)
    assert float((b > 1).mean()) > 0.2
    live = jnp.asarray(live)
    want_o, want = _recurrence(state[1], a, k, q, v, b)
    if form == "kernel":
        got_o, got = jax.jit(lambda *x: kda_update(*x, interpret=True))(
            state, jnp.int32(1), a, k, q, v, b, live)
    else:
        got_o, got = kda_update_reference(state, 1, a, k, q, v, b, live)
    rows = np.asarray(live)
    # float32 sums of 16 products of order 1: 1e-5 is a few ulps of them
    assert np.abs(np.asarray(got_o)[rows] - want_o[rows]).max(
        initial=0.0) < 1e-5
    assert not np.asarray(got_o)[~rows].any()
    assert np.abs(np.asarray(got[1])[rows] - want[rows]).max(
        initial=0.0) < 1e-5
    assert np.array_equal(np.asarray(got[0]), np.asarray(state[0]))
    if rows.any():      # with no live row at all one dead block is junk
        assert np.array_equal(np.asarray(got[1])[~rows],
                              np.asarray(state[1])[~rows])


# -- the chunkwise prefill ----------------------------------------------------
@pytest.mark.parametrize("T,lengths", [(128, (128, 70)), (64, (64, 3)),
                                       (32, (17, 32)), (192, (150, 129))])
def test_the_chunkwise_prefill_is_the_recurrence(T, lengths):
    """Lengths that are and are not multiples of the chunk (64), of its
    sub-block (16) and of the bucket; a channel whose log-decay reaches -20
    a token (exp(-G) would overflow float32 inside a chunk); a padded
    position updates nothing: the state is as of the last real token."""
    K, H, dk = len(lengths), 2, 16
    keys = jax.random.split(jax.random.PRNGKey(T), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (K, T, H, dk))) / 4
    k = unit(jax.random.normal(keys[1], (K, T, H, dk)))
    v = jax.random.normal(keys[2], (K, T, H, dk))
    g = -jnp.exp(jax.random.uniform(keys[3], (K, T, H, dk), minval=-6.0,
                                    maxval=3.0))
    b = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (K, T, H)))
    real = jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None]
    g = jnp.where(real[:, :, None, None], g, 0.0)
    b = jnp.where(real[:, :, None], b, 0.0)
    o, last = jax.jit(kda_chunk)(q, k, v, g, b)
    state = np.zeros((K, H, dk, dk))
    for t in range(T):
        want_o, state = _recurrence(state, jnp.exp(g[:, t]), k[:, t],
                                    q[:, t], v[:, t], b[:, t])
        at = np.asarray(real[:, t])
        # float32 rounding of sums over a chunk: outputs and state of
        # order 1
        assert np.abs(np.asarray(o[:, t]) - want_o)[at].max(
            initial=0.0) < 5e-6
    assert np.abs(np.asarray(last) - state).max() < 1e-5
    # the state stopped at each row's last real token
    short = int(np.argmin(lengths))
    alone = jax.jit(kda_chunk)(*(x[short:short + 1, :T] for x in (q, k, v)),
                               g[short:short + 1], b[short:short + 1])[1]
    assert np.abs(np.asarray(alone[0] - last[short])).max() < 1e-6


# -- prefill, then decode, against the full forward ---------------------------
@functools.lru_cache(maxsize=None)
def _programs(cfg):
    """The family's two functions jitted once a configuration, whichever
    test drives them."""
    return (jax.jit(lambda p, t, n: prefill(p, cfg, t, n)),
            jax.jit(lambda p, t, pos, k, v, tb, st, tail, at: decode_step(
                p, cfg, t, pos, k, v, tb, st, tail, at)))


class Served:
    """Pools, a block table and per-slot state as the engine holds them,
    driven by the model's two functions directly so that LOGITS can be
    compared (the engine hands out tokens only). Dead slots hold junk.
    Decode runs in blocks of BLOCK steps as the engine's program does."""

    BLOCK = 5       # a block ends inside a page, at its edge and across it

    def __init__(self, cfg, params, slots=4, page=16, pages_a_slot=4,
                 kept_in=jnp.float32):
        self.cfg, self.params, self.page = cfg, params, page
        self.kv_tail, self.at = None, 0
        # the KDA state is rounded through `kept_in` after every program:
        # float32 is what the engine holds
        self.kept_in = kept_in
        (s1, d1), (s2, d2) = state_shapes(cfg, slots)
        self.state = (jnp.full(s1, 7.0, d1), jnp.full(s2, 3.0, d2))
        n_pages = slots * pages_a_slot + 1
        self.k = jnp.zeros((cfg.kv_layers, n_pages, cfg.n_kv_heads,
                            cfg.head_dim, page))
        self.v = jnp.zeros_like(self.k)
        self.table = np.zeros((slots, pages_a_slot), np.int32)
        self.own = {s: [1 + s * pages_a_slot + i for i in range(pages_a_slot)]
                    for s in range(slots)}
        self.pos = np.zeros((slots,), np.int32)
        self._prefill, self._step = _programs(cfg)

    def flush(self):
        if self.kv_tail is not None:
            table, began = self._block
            self.k, self.v = paged_flush_block(
                self.k, self.v, *self.kv_tail, jnp.asarray(table),
                jnp.asarray(began),
                jnp.where(jnp.asarray(table[:, 0] > 0), self.at, 0))
            self.kv_tail, self.at = None, 0

    def admit(self, rows, bucket):
        """rows: {slot: prompt}. Returns {slot: last-position logits}."""
        self.flush()
        slots = sorted(rows)
        window = np.zeros((len(slots), bucket), np.int32)
        for i, s in enumerate(slots):
            window[i, :len(rows[s])] = rows[s]
        lengths = jnp.asarray([len(rows[s]) for s in slots], jnp.int32)
        with jax.default_matmul_precision("highest"):
            last, k, v, fresh = self._prefill(self.params,
                                              jnp.asarray(window), lengths)
        for s in slots:
            self.table[s] = self.own[s]
            self.pos[s] = len(rows[s])
        ptable = jnp.asarray(self.table[slots][:, :-(-bucket // self.page)])
        self.k, self.v = paged_write_prefill_stacked(self.k, self.v, k, v,
                                                     ptable, lengths)
        at = jnp.asarray(slots)
        self.state = tuple(held.at[:, at].set(row)
                           for held, row in zip(self.state, fresh))
        self._round()
        return {s: np.asarray(last[i]) for i, s in enumerate(slots)}

    def _round(self):
        # committed to the device, as a program's outputs are: the step
        # program is then compiled once, not once for each
        kda, tail = self.state
        self.state = jax.device_put(
            (kda.astype(self.kept_in).astype(kda.dtype), tail),
            jax.devices()[0])

    def retire(self, slot):
        self.flush()
        self.table[slot] = 0

    def step(self, tokens):
        """tokens: {slot: token}. Returns ({slot: logits}, counters)."""
        fed = np.zeros_like(self.pos)
        for s, t in tokens.items():
            fed[s] = t
        if self.kv_tail is None:
            self.kv_tail = jax.device_put(
                block_tail(self.k, len(self.pos), self.BLOCK),
                jax.devices()[0])
            self._block = (self.table.copy(), self.pos.copy())
        with jax.default_matmul_precision("highest"):
            logits, self.kv_tail, self.state, counted = self._step(
                self.params, jnp.asarray(fed), jnp.asarray(self.pos), self.k,
                self.v, jnp.asarray(self._block[0]), self.state,
                self.kv_tail, jnp.int32(self.at))
        self._round()
        self.pos = self.pos + 1
        self.at += 1
        if self.at == self.BLOCK:
            self.flush()
        return {s: np.asarray(logits[s]) for s in tokens}, np.asarray(counted)


def _follow(served, want, sequence, slot, steps):
    """Teacher-forced decode of `sequence` in `slot`; the worst |logit|
    difference against the reference's full forward."""
    worst = 0.0
    for _ in range(steps):
        at = int(served.pos[slot])
        got, _ = served.step({slot: sequence[at]})
        worst = max(worst, float(np.abs(got[slot] - want[at]).max()))
    return worst


# float32 throughout, logits of order 3: 2e-5 after a prefill and 5e-5 after
# tens of decode steps is float32 rounding through four blocks (the
# nemotron_h tests' tolerances); a bfloat16 KDA state reads 100x that
# (the last case below)
AFTER_PREFILL, AFTER_DECODE = 2e-5, 5e-5


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_prefill_then_decode_steps_match_the_full_forward(seeded, impl):
    """`xla`: the update's jax.numpy form; `flash`: the kernel (interpret
    mode here) and the flash prefill."""
    dims, params = seeded
    steps = 32 if impl == "xla" else 7
    a, b = _tokens(70, 1), _tokens(70, 2)
    want_a = _reference_logits(params, dims, a)
    want_b = _reference_logits(params, dims, b)
    served = Served(program_config(attn_impl=impl), params)
    last = served.admit({1: a[:21], 3: b[:32]}, bucket=32)
    assert np.abs(last[1] - want_a[20]).max() < AFTER_PREFILL
    assert np.abs(last[3] - want_b[31]).max() < AFTER_PREFILL
    worst = 0.0
    for _ in range(steps):
        got, counted = served.step({1: a[served.pos[1]], 3: b[served.pos[3]]})
        worst = max(worst,
                    np.abs(got[1] - want_a[served.pos[1] - 1]).max(),
                    np.abs(got[3] - want_b[served.pos[3] - 1]).max())
    assert worst < AFTER_DECODE
    # two live rows of four: the junk rows are out of the counters
    assert dict(zip(COUNTERS, counted))["rows"] == 2
    assert dict(zip(COUNTERS, counted))["kda_rows"] == 2 * 3


def test_a_slot_reused_by_a_second_request_gives_its_own_logits(seeded):
    dims, params = seeded
    long, short = _tokens(60, 3), _tokens(40, 4)
    served = Served(program_config(), params)
    served.admit({2: long[:30]}, bucket=32)
    assert _follow(served, _reference_logits(params, dims, long), long, 2,
                   12) < AFTER_DECODE
    served.retire(2)
    served.admit({2: short[:9]}, bucket=16)
    assert _follow(served, _reference_logits(params, dims, short), short, 2,
                   20) < AFTER_DECODE


def test_a_padded_bucket_leaves_what_the_exact_length_leaves(seeded):
    """The state as of the last REAL token, the tail at lengths - 3 ...
    lengths - 1: a window of exactly the prompt's length, and the same
    prompt right-padded to two chunks, give the same logits and state."""
    _, params = seeded
    prefill, _ = _programs(program_config())
    prompt = _tokens(16, 5)
    with jax.default_matmul_precision("highest"):
        exact = prefill(params, jnp.asarray([prompt]),
                        jnp.asarray([16], jnp.int32))
        padded = prefill(params, jnp.asarray([prompt + [9] * 16]),
                         jnp.asarray([16], jnp.int32))
    assert np.abs(np.asarray(exact[0]) - np.asarray(padded[0])).max() < 2e-5
    for got, want in zip(padded[3], exact[3]):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    # and a prompt shorter than the convolution's tail pads it with zeros
    with jax.default_matmul_precision("highest"):
        _, _, _, (_, tail) = prefill(
            params, jnp.asarray([prompt]), jnp.asarray([2], jnp.int32))
    assert not np.asarray(tail)[:, 0, 0].any()
    assert np.asarray(tail)[:, 0, 1:].any()


def test_a_bfloat16_state_at_a_float32_configuration_fails(seeded):
    """The tolerances above are tight enough: the same program with the KDA
    state held in bfloat16 between steps is 100x over them."""
    dims, params = seeded
    a = _tokens(70, 1)
    served = Served(program_config(), params, kept_in=jnp.bfloat16)
    served.admit({1: a[:21]}, bucket=32)
    assert _follow(served, _reference_logits(params, dims, a), a, 1,
                   12) > 100 * AFTER_DECODE


# -- the chip's share ---------------------------------------------------------
def test_the_eight_shares_add_up_to_the_uncut_block(seeded):
    """Model-configs guide, section 4: eight chips share a layer; the eight
    shares' routed parts with the shared expert and the mixer counted ONCE
    are the uncut reference's block; and the program's share is the
    reference's share, in both phases."""
    dims, _ = seeded
    dims = {**dims, "E": 16, "lo": 0, "hi": 16}
    w = reference._make_layer(jax.random.PRNGKey(11),
                              reference.layer_shapes(dims, False),
                              jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(12), (24, 64), jnp.float32)

    def share(i):
        lo, hi = 2 * i, 2 * i + 2
        return {**w, **{name: w[name][lo:hi] for name in ("w1", "wg", "w2")}
                }, (lo, hi)

    frozen = tuple(sorted(dims.items()))

    def after_the_mixer(x, w):
        h = x + reference.kda_mixer(
            reference.rms_norm(x, w["mixer_norm"], dims["eps"]), w,
            dict(frozen))
        return h, reference.rms_norm(h, w["ffn_norm"], dims["eps"])

    part = jax.jit(lambda x, held_w, held, shared: reference.expert_ffn(
        x, held_w, dict(frozen), held=held, shared=shared),
        static_argnums=(2, 3))
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda x, w: reference.block(x, w, dict(frozen),
                                                     False))(x, w)
        h, normed = jax.jit(after_the_mixer)(x, w)
        parts = [part(normed, *share(i), i == 0) for i in range(8)]
        assert np.abs(np.asarray(h + sum(parts) - whole)).max() < 1e-5
        assert np.abs(np.asarray(h + parts[0] - whole)).max() > 1e-3  # a cut
        live = jnp.ones((24,), bool)
        for i in (0, 5):
            held_w, held = share(i)
            want = part(normed, held_w, held, True)
            cfg = dataclasses.replace(program_config(), n_experts=16,
                                      experts_held=held)
            got, _ = jax.jit(lambda x, w, live: experts.ffn_decode(
                x, w, live, cfg))(normed, held_w, live)
            assert np.abs(np.asarray(got - want)).max() < 1e-5
            got = jax.jit(lambda x, w, real: experts.ffn_prefill(
                x, w, real, cfg))(normed.reshape(2, 12, 64), held_w,
                                  jnp.ones((2, 12), bool))
            assert np.abs(np.asarray(got.reshape(24, 64) - want)).max() < 1e-5


# -- the engine ---------------------------------------------------------------
def _engine(cfg, params, **kw):
    kw.setdefault("prefix_cache", False)
    return PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=128,
                          page_size=16, n_pages=33, prefill_buckets=(32,),
                          decode_block_size=4, **kw)


def test_every_refusal_but_the_weights_names_the_kda_state():
    """tests/test_families.py asks the engine for each; here, what only
    this family's reasons say."""
    assert all("KDA state" in reason for feature, reason in REFUSES.items()
               if feature != "int8_weights")


def test_the_engine_serves_the_family_on_its_normal_path(seeded):
    """Admission, page allocator, loop, demux: more requests than slots, so
    slots are reused by prompts of other lengths; every served token is the
    reference's first choice (float32: no near-ties), and /debug/engine
    says what the model holds and how the routing and the updates fell."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    dims, params = seeded
    cfg = program_config()
    engine = _engine(cfg, params)
    assert engine.k_cache.shape[0] == cfg.kv_layers == 1
    assert [a.shape for a in engine.state] == [(3, 4, 4, 16, 16),
                                               (3, 4, 3, 192)]
    engine.start()
    try:
        prompts = [_tokens(n, 20 + n) for n in (5, 17, 30, 9, 23, 3, 12)]
        requests = [engine.submit(p, max_new_tokens=14) for p in prompts]
        served = [r.result(timeout_s=300) for r in requests]
        snapshot = engine_snapshot(engine)["model"]
    finally:
        engine.stop()
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 14
        want = _reference_logits(params, dims, prompt + tokens)
        first = np.argmax(want[len(prompt) - 1:-1], axis=-1)
        assert tokens == first.tolist()
    assert snapshot["family"] == "kda_moe" and snapshot["kv_layers"] == 1
    assert snapshot["blocks"] == {"kda": 3, "gqa": 1}
    assert snapshot["kda_state_bytes_per_slot"] == 3 * 4 * 16 * 16 * 4
    assert snapshot["conv_tail_bytes_per_slot"] == 3 * 3 * 192 * 4
    assert snapshot["state_bytes_per_slot"] == cfg.state_bytes_per_slot \
        == 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert snapshot["state_bytes"] == 4 * cfg.state_bytes_per_slot
    assert snapshot["kda_state_dtype"] == "float32"
    assert (snapshot["experts_held"], snapshot["experts_total"]) == (4, 8)
    assert 0 < snapshot["kda_rows_per_step"] <= 3 * 4
    routing = snapshot["routing"]
    assert 0 < routing["rows_per_step"] <= 4
    assert snapshot["kda_rows_per_step"] == 3 * routing["rows_per_step"]
    assert 0 <= routing["held_pick_share"] <= 1


def test_what_a_token_meets_and_what_a_slot_holds():
    """tpu/utilization.py counts 2 P flops a token with P what a token
    MEETS; tpu/capacity.py counts the state a slot beside the pages; the
    published configuration's counts are ISSUE 41's."""
    from gofr_tpu.tpu.capacity import kv_token_bytes, plan_capacity

    whole = KdaMoeConfig()
    m = whole.matrix_params()
    assert (whole.kda_layers, whole.kv_layers) == (36, 12)
    assert round(m["kda"] / 1e6, 1) == 137.7
    assert round(m["attention"] / 1e6, 1) == 109.1
    total = (36 * m["kda"] + 12 * m["attention"] + 48 * m["experts_held"]
             + 2 * 4096 * 196608)
    assert 249e9 < total < 251e9
    cfg = KdaMoeConfig.solar_open2_250b_ep8()
    assert (cfg.n_layers, cfg.kda_layers, cfg.kv_layers, cfg.held) \
        == (4, 3, 1, 40)
    assert cfg.kda_state_bytes == 3 * 64 * 128 * 128 * 4 == 12_582_912
    assert cfg.conv_tail_bytes == 3 * 3 * 24576 * 2 == 442_368
    assert kv_token_bytes(cfg) == 2 * 8 * 128 * 2 == 4096
    held = (3 * m["kda"] + m["attention"]
            + 4 * cfg.matrix_params()["experts_held"] + 2 * 4096 * 24576)
    assert 6.6e9 < 2 * held < 6.65e9            # 6.62 GB of weights
    plan = plan_capacity(cfg, 256, 1280, 16 << 30, prefill_buckets=(64, 128),
                         params_nbytes=2 * held, clamp=False)
    assert plan.cache_bytes_max == 256 * 1280 * 4096 \
        + 256 * cfg.state_bytes_per_slot


def test_the_program_and_the_reference_name_the_same_leaves(seeded):
    dims, params = seeded
    cfg = program_config()
    for gqa in (True, False):
        assert kda_moe.layer_shapes(cfg, gqa) == reference.layer_shapes(
            dims, gqa)
    mine = kda_moe_init(cfg, 0)
    assert jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), mine) \
        == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), params)


# -- nemotron_h's programs are the parent's ------------------------------------
def test_nemotron_h_traces_the_program_it_was():
    """The causal short convolution and its tail moved to ops/short_conv.py,
    which both families call; nemotron_h's Mamba-2 prefill and decode,
    written out here as PR 40 had them, give the same jaxpr."""
    from gofr_tpu.models import nemotron_h as nh
    from gofr_tpu.ops.ssm_update import ssm_update

    cfg = nh.NemotronHConfig.debug()
    w = nh.nemotron_h_init(cfg, 3)["layers"][0]
    assert cfg.pattern[0] == "M"

    def conv_prefill_was(xBC, conv_w, lengths, dtype):
        W, T = cfg.conv_kernel, xBC.shape[1]
        at = lengths[:, None] - (W - 1) + jnp.arange(W - 1)[None, :]
        tail = jnp.where((at >= 0)[:, :, None], jnp.take_along_axis(
            xBC, jnp.maximum(at, 0)[:, :, None], axis=1), 0).astype(dtype)
        padded = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
        conv = sum(conv_w[j] * padded[:, j:j + T] for j in range(W))
        return conv, tail

    def decode_was(u, w, state, tail, live):
        H, P = cfg.mamba_heads, cfg.mamba_head_dim
        z, xBC, dt = nh._split_proj(nh._in_proj(u, w), cfg)
        window = jnp.concatenate([tail[0].astype(jnp.float32),
                                  xBC[:, None]], axis=1)
        tail = tail.at[0].set(window[:, 1:].astype(tail.dtype))
        conv = jnp.sum(w["conv_w"][None] * window, axis=1) + w["conv_b"]
        x, B, C = nh._split_xbc(jax.nn.silu(conv), cfg)
        x = x.reshape(-1, H, P).astype(jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"])
        decay = jnp.exp(dt * -jnp.exp(w["A_log"]))
        y, state = ssm_update(
            state, 0, jnp.repeat(decay, P, axis=1),
            (x * dt[:, :, None]).reshape(-1, H * P),
            B.astype(jnp.float32), C.astype(jnp.float32), live)
        y = y + (w["D"][None, :, None] * x).reshape(-1, H * P)
        out = nh._gated_norm(y, z, w["gate_norm"], cfg).astype(u.dtype) \
            @ w["out_proj"]
        return out, state, tail

    (s1, d1), (s2, d2) = nh.state_shapes(cfg, 3)
    args = (jnp.ones((3, cfg.dim)), w, jnp.zeros(s1, d1), jnp.zeros(s2, d2),
            jnp.asarray([True, False, True]))
    now = jax.make_jaxpr(lambda u, w, s, t, live: nh.mamba_decode(
        u, w, s, t, 0, live, cfg))(*args)
    assert str(now) == str(jax.make_jaxpr(decode_was)(*args))
    # the prefill: the parent's convolution lines in the helper's place
    import gofr_tpu.models.nemotron_h as module

    u = jax.random.normal(jax.random.PRNGKey(0), (2, 32, cfg.dim))
    lengths = jnp.asarray([32, 9], jnp.int32)
    now = jax.make_jaxpr(lambda u, w, n: nh.mamba_prefill(u, w, n, cfg))(
        u, w, lengths)
    helper = module.conv_prefill
    module.conv_prefill = conv_prefill_was
    try:
        was = jax.make_jaxpr(lambda u, w, n: nh.mamba_prefill(u, w, n, cfg))(
            u, w, lengths)
    finally:
        module.conv_prefill = helper
    assert str(now) == str(was)
