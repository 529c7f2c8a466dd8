"""The nemotron_h family on the serving path (ISSUE 27), at tiny widths in
float32 on the CPU, seeded: the program's prefill and decode through pools
and per-slot state against benchmark/reference/nemotron_h.py's plain full
forward (logits compared), the two kernels in interpret mode against their
jax.numpy oracles, the expert layer's shares adding up to the uncut layer,
what the family refuses, and the engine end to end."""

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

from gofr_tpu.models.llama import LlamaConfig, llama_init  # noqa: E402
from gofr_tpu.models.experts import ffn_decode, ffn_prefill  # noqa: E402
from gofr_tpu.models.nemotron_h import (NemotronHConfig,  # noqa: E402
                                        decode_step, nemotron_h_init,
                                        prefill, state_shapes)
from gofr_tpu.ops.moe_experts import (decode_experts, experts_reference,  # noqa: E402
                                      prefill_experts)
from gofr_tpu.ops.paged_attention import (block_tail, paged_flush_block,  # noqa: E402
                                          paged_write_prefill_stacked)
from gofr_tpu.ops.ssm_update import ssm_update, ssm_update_reference  # noqa: E402
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

reference = data.reference_for({"family": "nemotron_h"})

PATTERN = "ME*EME"
CONFIG = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=len(PATTERN),
    hybrid_override_pattern=PATTERN, layer_norm_epsilon=1e-5,
    mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=16,
    conv_kernel=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    n_routed_experts=4, n_routed_experts_published=8, experts_held=[0, 4],
    num_experts_per_tok=2, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, n_shared_experts=1,
    routed_scaling_factor=2.5, rope_theta=10000)


def program_config(held=(0, 4), pattern=PATTERN):
    return NemotronHConfig(
        vocab_size=512, dim=64, pattern=pattern, n_heads=4, n_kv_heads=2,
        head_dim=16, mamba_heads=4, mamba_head_dim=16, n_groups=2,
        state_size=16, chunk_size=16, n_experts=8, experts_held=held,
        experts_per_token=2, expert_dim=32, shared_dim=64, max_seq_len=256,
        dtype="float32")


@pytest.fixture(scope="module")
def seeded():
    dims = reference.dims_of(CONFIG)
    return dims, reference.make_params(dims, 7, "float32")


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, size=n).tolist()


def _reference_logits(params, dims, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, dims, tokens))


class Served:
    """Pools, a block table and per-slot state as the engine holds them,
    driven by the model's two functions directly so that LOGITS can be
    compared (the engine hands out tokens only). Dead slots hold junk.
    Decode runs in blocks of BLOCK steps as the engine's program does: the
    new K and V wait in the block's tail and reach the pages when the
    block is over, or when an admission needs the pool."""

    BLOCK = 5       # a block ends inside a page, at its edge and across it

    def __init__(self, cfg, params, slots=4, page=16, pages_a_slot=4):
        self.cfg, self.params, self.page = cfg, params, page
        self.kv_tail, self.at = None, 0
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
        self._prefill = jax.jit(lambda p, t, n: prefill(p, cfg, t, n))
        self._step = jax.jit(lambda p, t, pos, k, v, tb, st, tail, at:
                             decode_step(p, cfg, t, pos, k, v, tb, st, tail,
                                         at))

    def flush(self):
        """End the open block: its tail into the pages of the rows that
        held a request when it began."""
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
        return {s: np.asarray(last[i]) for i, s in enumerate(slots)}

    def retire(self, slot):
        self.flush()
        self.table[slot] = 0

    def step(self, tokens):
        """tokens: {slot: token}. Returns ({slot: logits}, counters)."""
        fed = np.zeros_like(self.pos)
        for s, t in tokens.items():
            fed[s] = t
        if self.kv_tail is None:
            self.kv_tail = block_tail(self.k, len(self.pos), self.BLOCK)
            self._block = (self.table.copy(), self.pos.copy())
        with jax.default_matmul_precision("highest"):
            logits, self.kv_tail, self.state, counted = self._step(
                self.params, jnp.asarray(fed), jnp.asarray(self.pos), self.k,
                self.v, jnp.asarray(self._block[0]), self.state,
                self.kv_tail, jnp.int32(self.at))
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


def test_prefill_then_32_decode_steps_match_the_full_forward(seeded):
    dims, params = seeded
    a, b = _tokens(70, 1), _tokens(70, 2)
    want_a = _reference_logits(params, dims, a)
    want_b = _reference_logits(params, dims, b)
    served = Served(program_config(), params)
    last = served.admit({1: a[:21], 3: b[:32]}, bucket=32)
    assert np.abs(last[1] - want_a[20]).max() < 2e-5
    assert np.abs(last[3] - want_b[31]).max() < 2e-5
    worst = 0.0
    for _ in range(32):
        got, counted = served.step({1: a[served.pos[1]], 3: b[served.pos[3]]})
        worst = max(worst,
                    np.abs(got[1] - want_a[served.pos[1] - 1]).max(),
                    np.abs(got[3] - want_b[served.pos[3] - 1]).max())
    assert worst < 5e-5
    # two live rows of four: the junk rows are out of the counters
    assert counted[0] == 2
    assert counted[1] <= 2 * 2 * 3 and counted[2] <= 4 * 3


def test_a_slot_reused_by_a_shorter_prompt_starts_from_its_own_state(seeded):
    dims, params = seeded
    long, short = _tokens(60, 3), _tokens(40, 4)
    served = Served(program_config(), params)
    served.admit({2: long[:30]}, bucket=32)
    assert _follow(served, _reference_logits(params, dims, long), long, 2,
                   12) < 5e-5
    served.retire(2)
    served.admit({2: short[:9]}, bucket=16)
    assert _follow(served, _reference_logits(params, dims, short), short, 2,
                   20) < 5e-5


def test_a_padded_bucket_leaves_what_the_exact_length_leaves(seeded):
    """The state as of the last REAL token, the tail at lengths - 3 ...
    lengths - 1: a window of exactly the prompt's length, and the same
    prompt right-padded to two chunks, give the same logits and state."""
    _, params = seeded
    cfg = program_config()
    prompt = _tokens(16, 5)
    with jax.default_matmul_precision("highest"):
        exact = prefill(params, cfg, jnp.asarray([prompt]),
                        jnp.asarray([16], jnp.int32))
        padded = prefill(params, cfg, jnp.asarray([prompt + [9] * 16]),
                         jnp.asarray([16], jnp.int32))
    assert np.abs(np.asarray(exact[0]) - np.asarray(padded[0])).max() < 2e-5
    for got, want in zip(padded[3], exact[3]):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    # and a prompt shorter than the convolution's tail pads it with zeros
    with jax.default_matmul_precision("highest"):
        _, _, _, (_, tail) = prefill(
            params, cfg, jnp.asarray([prompt]), jnp.asarray([2], jnp.int32))
    assert not np.asarray(tail)[:, 0, 0].any()
    assert np.asarray(tail)[:, 0, 1:].any()


def test_the_two_shares_of_the_expert_layer_add_up_to_the_whole(seeded):
    """Model-configs guide, section 4: held 0-3 and 4-7, the shared expert
    counted once, add up to the uncut reference's whole layer; and the
    program's share is the reference's share."""
    dims, params = seeded
    whole_dims = {**dims, "lo": 0, "hi": 8}
    shapes = reference.layer_shapes(whole_dims, "experts")
    w = reference._make_layer(jax.random.PRNGKey(11), shapes, "experts",
                              jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(12), (24, 64), jnp.float32)
    halves = [{**w, "w1": w["w1"][lo:hi], "w2": w["w2"][lo:hi]}
              for lo, hi in ((0, 4), (4, 8))]
    with jax.default_matmul_precision("highest"):
        whole = reference.expert_mixer(x, w, whole_dims)
        low = reference.expert_mixer(x, halves[0], dims, held=(0, 4))
        high = reference.expert_mixer(x, halves[1], dims, held=(4, 8),
                                      shared=False)
        assert np.abs(np.asarray(low + high - whole)).max() < 1e-5
        assert np.abs(np.asarray(low - whole)).max() > 1e-3     # a real cut
        live = jnp.ones((24,), bool)
        for half, held in zip(halves, ((0, 4), (4, 8))):
            want = reference.expert_mixer(x, half, dims, held=held)
            cfg = program_config(held)
            got, _ = ffn_decode(x, half, live, cfg)
            assert np.abs(np.asarray(got - want)).max() < 1e-5
            got = ffn_prefill(x.reshape(2, 12, 64), half,
                              jnp.ones((2, 12), bool), cfg)
            assert np.abs(np.asarray(got.reshape(24, 64) - want)).max() < 1e-5


@pytest.mark.parametrize("live", [[True, False, True, True, False],
                                  [False] * 5, [True] * 5])
def test_ssm_update_in_interpret_mode_is_its_oracle(live):
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    L, S, N, G, HP = 2, 5, 16, 2, 256
    state = jax.random.normal(keys[0], (L, S, N, HP), jnp.float32)
    decay = jax.random.uniform(keys[1], (S, HP), jnp.float32, 0.5, 1.0)
    xdt = jax.random.normal(keys[2], (S, HP), jnp.float32)
    B = jax.random.normal(keys[3], (S, G, N), jnp.float32)
    C = jax.random.normal(keys[4], (S, G, N), jnp.float32)
    live = jnp.asarray(live)
    want_y, want = ssm_update_reference(state, 1, decay, xdt, B, C, live)
    got_y, got = jax.jit(lambda *a: ssm_update(*a, interpret=True))(
        state, jnp.int32(1), decay, xdt, B, C, live)
    assert np.abs(np.asarray(got_y - want_y)).max() < 1e-5
    rows = np.asarray(live)
    # live rows of the layer move; dead rows and the other layer do not
    assert np.abs(np.asarray(got - want))[:, rows].max(initial=0.0) < 1e-5
    assert np.array_equal(np.asarray(got[0]), np.asarray(state[0]))
    if rows.any():      # with no live row at all one dead block is junk
        assert np.array_equal(np.asarray(got[1])[~rows],
                              np.asarray(state[1])[~rows])


@pytest.mark.parametrize("rows", [0, 1, 6])
def test_moe_experts_decode_in_interpret_mode_is_its_oracle(rows):
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    held, D, F, B = 4, 32, 24, 6
    x = jax.random.normal(keys[0], (B, D), jnp.float32)
    w1 = jax.random.normal(keys[1], (held, F, D), jnp.float32) / 6
    w2 = jax.random.normal(keys[2], (held, F, D), jnp.float32) / 5
    # each live row picks one expert; expert 2 is never picked
    combine = np.zeros((B, held), np.float32)
    for r in range(rows):
        combine[r, (0, 1, 3)[r % 3]] = 0.5 + r
    got = jax.jit(lambda *a: decode_experts(*a, interpret=True))(
        x, w1, w2, jnp.asarray(combine))
    want = experts_reference(x, w1, w2, jnp.asarray(combine))
    assert np.abs(np.asarray(got - want)).max() < 1e-4


@pytest.mark.parametrize("tm", [8, 16])
def test_moe_experts_prefill_sorts_by_expert_and_is_its_oracle(tm):
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    held, lo, D, F, T, k = 4, 2, 32, 24, 21, 2
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    w1 = jax.random.normal(keys[1], (held, F, D), jnp.float32) / 6
    w2 = jax.random.normal(keys[2], (held, F, D), jnp.float32) / 5
    picks = jnp.stack([jax.random.permutation(kk, 8)[:k] for kk in
                       jax.random.split(keys[3], T)]).astype(jnp.int32)
    weights = jax.random.uniform(keys[4], (T, k), jnp.float32, 0.2, 1.0)
    weights = weights.at[17:].set(0.0)                 # padding tokens
    got = jax.jit(lambda *a: prefill_experts(*a, lo, 8, tm=tm, interpret=True))(
        x, w1, w2, picks, weights)
    combine = np.zeros((T, 8), np.float32)
    combine[np.arange(T)[:, None], np.asarray(picks)] = np.asarray(weights)
    want = experts_reference(x, w1, w2, jnp.asarray(combine[:, lo:lo + held]))
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert not np.asarray(got)[17:].any()


# -- the engine ---------------------------------------------------------------
def _engine(cfg, params, **kw):
    kw.setdefault("prefix_cache", False)
    return PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=128,
                          page_size=16, n_pages=33,
                          prefill_buckets=(16, 32), decode_block_size=4,
                          **kw)


def test_the_post_hoc_passes_refuse_the_family():
    engine = _engine(program_config(), nemotron_h_init(program_config(), 0))
    with pytest.raises(ValueError, match=r"score\(\) is models/llama"):
        engine.score([1, 2], [3])
    with pytest.raises(ValueError, match=r"embed\(\) is models/llama"):
        engine.embed([1, 2])


def test_the_engine_serves_the_family_on_its_normal_path(seeded):
    """Admission, page allocator, loop, demux: more requests than slots, so
    slots are reused by prompts of other lengths; every served token is the
    reference's first choice (float32: no near-ties), and /debug/engine
    says what the model holds and how the routing fell."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    dims, params = seeded
    cfg = program_config()
    engine = _engine(cfg, params)
    assert engine.k_cache.shape[0] == cfg.kv_layers == 1
    assert [a.shape for a in engine.state] == [(2, 4, 16, 64), (2, 4, 3, 128)]
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
    assert snapshot["family"] == "nemotron_h" and snapshot["kv_layers"] == 1
    assert snapshot["state_bytes_per_slot"] == cfg.state_bytes_per_slot \
        == 2 * (16 * 64 * 4 + 3 * 128 * 4)
    assert snapshot["state_bytes"] == 4 * cfg.state_bytes_per_slot
    assert (snapshot["experts_held"], snapshot["experts_total"]) == (4, 8)
    routing = snapshot["routing"]
    assert 0 < routing["rows_per_step"] <= 4
    assert 0 <= routing["held_pick_share"] <= 1
    assert routing["tokens_per_held_expert_max_over_mean"] >= 1
    assert 0 < routing["experts_touched_per_layer_step"] <= 4


def test_llama_behind_the_protocol_serves_the_references_tokens():
    """models/llama.py's paged path sits behind the protocol unchanged in
    arithmetic: the tokens are the plain cached forward's (`llama_prefill`,
    `llama_decode_step` over one contiguous cache), computed here."""
    from gofr_tpu.models.llama import (init_kv_cache, llama_decode_step,
                                       llama_prefill)

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    engine = _engine(cfg, params)
    assert engine.state == () and engine.model.counters == ()
    assert engine.k_cache.shape[0] == cfg.n_layers
    prompts = [_tokens(n, 40 + n) for n in (5, 17, 30, 9, 23)]
    engine.start()
    try:
        requests = [engine.submit(p, max_new_tokens=12) for p in prompts]
        served = [r.result(timeout_s=300) for r in requests]
    finally:
        engine.stop()

    def reference(prompt):
        k, v = init_kv_cache(cfg, 1, 128)
        logits, k, v = llama_prefill(params, cfg,
                                     jnp.asarray([prompt], jnp.int32), k, v)
        out = [int(jnp.argmax(logits[0, -1]))]
        for i in range(11):
            logits, k, v = llama_decode_step(
                params, cfg, jnp.asarray([out[-1]], jnp.int32),
                jnp.asarray([len(prompt) + i], jnp.int32), k, v)
            out.append(int(jnp.argmax(logits[0])))
        return out

    assert served == [reference(p) for p in prompts]


def test_what_a_token_meets_and_what_a_slot_holds():
    """tpu/utilization.py counts 2 P flops a token with P what a token
    MEETS; tpu/capacity.py counts the state a slot beside the pages."""
    from gofr_tpu.tpu.capacity import kv_token_bytes, plan_capacity

    cfg = NemotronHConfig.nano_30b_a3b_ep2()
    assert (cfg.n_layers, cfg.mamba_layers, cfg.expert_layers,
            cfg.kv_layers) == (16, 7, 7, 2)
    assert cfg.in_proj_dim == 10304 and cfg.conv_dim == 6144
    m = cfg.matrix_params()
    assert m["experts_met"] == 2688 * 128 + 2 * 2688 * 3712 \
        + 3 * 2 * 2688 * 1856
    assert m["experts_held"] - m["experts_met"] == 61 * 2 * 2688 * 1856
    met = cfg.param_count()
    assert met == 7 * m["mamba"] + 2 * m["attention"] \
        + 7 * m["experts_met"] + 2688 * 65536
    assert 0.8e9 < met < 0.9e9            # of 5.3e9 parameters held here
    assert cfg.state_bytes_per_slot == 7 * (64 * 64 * 128 * 4
                                            + 6144 * 3 * 2)
    assert kv_token_bytes(cfg) == 2 * 2 * 2 * 128 * 2
    budget = 16 << 30
    plan = plan_capacity(cfg, 96, 2048, budget, prefill_buckets=(64, 128),
                         params_nbytes=10_570_000_000)
    assert plan.cache_bytes_max == 96 * 2048 * 2048 \
        + 96 * cfg.state_bytes_per_slot
    llama = LlamaConfig.llama1b()
    assert llama.kv_layers == llama.n_layers
    assert llama.state_bytes_per_slot == 0


def test_the_expert_and_vocabulary_shares_have_specs():
    from jax.sharding import PartitionSpec as P

    from gofr_tpu.parallel.sharding import expert_share_specs

    cfg = program_config()
    specs = expert_share_specs(cfg.pattern)
    params = nemotron_h_init(cfg, 0)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, params)) \
        == jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda _: 0, specs, is_leaf=lambda s: isinstance(s, P)))
    assert specs["layers"][1]["w1"] == specs["layers"][1]["w2"] \
        == P("ep", None, None)
    assert specs["tok_emb"] == P("ep", None)
    assert specs["lm_head"] == P(None, "ep")
    assert specs["layers"][0]["in_proj"] == specs["layers"][1]["router"] \
        == specs["layers"][1]["shared_w1"] == P()
