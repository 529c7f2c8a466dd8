"""The mla_moe family on the serving path (ISSUE 31), at tiny widths in
float32 on the CPU, seeded: the program's prefill (non-absorbed) and decode
(absorbed, through the latent page plane and the block's tail) against
benchmark/reference/mla_moe.py's plain full forward (logits compared), the
kernels in interpret mode against their jax.numpy oracles, the absorbed form
against the non-absorbed, the eight expert shares adding up to the uncut
layer, what the family refuses, and the engine end to end.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (absorbed against not, grouped experts against every token
through every expert), so logits of order 1 agree to a few float32
roundings a block: 2e-5 after a prefill, 5e-5 over decode steps. The same
comparison with the matrices and the latent plane in bfloat16 reads over
1e-3 (`test_a_bfloat16_run_of_this_float32_configuration_fails`)."""

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

from gofr_tpu.models.experts import ffn_decode, ffn_prefill  # noqa: E402
from gofr_tpu.models.mla_moe import (COUNTERS, MlaMoeConfig,  # noqa: E402
                                     attention_decode, attention_prefill,
                                     decode_step, mla_moe_init, prefill)
from gofr_tpu.ops.flash_attention import (attention_reference,  # noqa: E402
                                          flash_attention)
from gofr_tpu.ops.mla_read import mla_read, mla_read_reference  # noqa: E402
from gofr_tpu.ops.moe_experts import (decode_experts, experts_reference,  # noqa: E402
                                      prefill_experts)
from gofr_tpu.ops import paged_attention  # noqa: E402
from gofr_tpu.ops.paged_attention import (flush_planes, paged_write_window,  # noqa: E402
                                          plane_tail)
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

reference = data.reference_for({"family": "mla_moe"})

CONFIG = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=3,
    first_k_dense_replace=1, rms_norm_eps=1e-6, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    qk_head_dim=24, v_head_dim=16, intermediate_size=128,
    n_routed_experts=4, n_routed_experts_published=8, experts_held=[0, 4],
    num_experts_per_tok=2, moe_intermediate_size=32, n_shared_experts=1,
    routed_scaling_factor=2.5, rope_theta=10000, rope_scaling=None,
    n_group=1, topk_group=1)


def program_config(held=(0, 4), dtype="float32"):
    return MlaMoeConfig(
        vocab_size=512, dim=64, n_layers=3, first_dense=1, n_heads=4,
        q_rank=48, kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16,
        dense_dim=128, n_experts=8, experts_held=held, experts_per_token=2,
        expert_dim=32, shared_dim=32, rope_theta=10000.0, max_seq_len=256,
        dtype=dtype)


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
    """The latent pool, a block table and the block's tail as the engine
    holds them, driven by the model's two functions directly so that
    LOGITS can be compared (the engine hands out tokens only). Decode runs
    in blocks of BLOCK steps as the engine's program does."""

    BLOCK = 5       # a block ends inside a page, at its edge and across it

    def __init__(self, cfg, params, slots=4, page=16, pages_a_slot=4,
                 dtype=jnp.float32):
        self.cfg, self.params, self.page = cfg, params, page
        self.tail, self.at = None, 0
        n_pages = slots * pages_a_slot + 1
        self.pool = jnp.zeros((cfg.n_layers, n_pages, 1, cfg.latent_dim,
                               page), dtype)
        self.table = np.zeros((slots, pages_a_slot), np.int32)
        self.own = {s: [1 + s * pages_a_slot + i for i in range(pages_a_slot)]
                    for s in range(slots)}
        self.pos = np.zeros((slots,), np.int32)
        self._prefill = jax.jit(lambda p, t, n: prefill(p, cfg, t, n))
        self._step = jax.jit(lambda p, t, pos, pool, tb, tail, at:
                             decode_step(p, cfg, t, pos, pool, tb, tail, at))

    def flush(self):
        if self.tail is not None:
            table, began = self._block
            self.pool, = flush_planes(
                (self.pool,), (self.tail,), jnp.asarray(table),
                jnp.asarray(began),
                jnp.where(jnp.asarray(table[:, 0] > 0), self.at, 0))
            self.tail, self.at = None, 0

    def admit(self, rows, bucket):
        """rows: {slot: prompt}. Returns {slot: last-position logits}."""
        self.flush()
        slots = sorted(rows)
        window = np.zeros((len(slots), bucket), np.int32)
        for i, s in enumerate(slots):
            window[i, :len(rows[s])] = rows[s]
        lengths = jnp.asarray([len(rows[s]) for s in slots], jnp.int32)
        with jax.default_matmul_precision("highest"):
            last, latent = self._prefill(self.params, jnp.asarray(window),
                                         lengths)
        for s in slots:
            self.table[s] = self.own[s]
            self.pos[s] = len(rows[s])
        ptable = jnp.asarray(self.table[slots][:, :-(-bucket // self.page)])
        self.pool = paged_write_window(self.pool, latent, ptable,
                                       jnp.zeros_like(lengths), lengths)
        return {s: np.asarray(last[i]) for i, s in enumerate(slots)}

    def retire(self, slot):
        self.flush()
        self.table[slot] = 0

    def step(self, tokens):
        """tokens: {slot: token}. Returns ({slot: logits}, counters)."""
        fed = np.zeros_like(self.pos)
        for s, t in tokens.items():
            fed[s] = t
        if self.tail is None:
            self.tail = plane_tail(self.pool, len(self.pos), self.BLOCK)
            self._block = (self.table.copy(), self.pos.copy())
        with jax.default_matmul_precision("highest"):
            logits, self.tail, counted = self._step(
                self.params, jnp.asarray(fed), jnp.asarray(self.pos),
                self.pool, jnp.asarray(self._block[0]), self.tail,
                jnp.int32(self.at))
        self.pos = self.pos + 1
        self.at += 1
        if self.at == self.BLOCK:
            self.flush()
        return ({s: np.asarray(logits[s], np.float32) for s in tokens},
                np.asarray(counted))


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
    # two live rows of four: the junk rows are out of the counters; two
    # expert blocks, two picks a row, four experts held
    assert counted[0] == 2
    assert counted[1] <= 2 * 2 * 2 and counted[2] <= 4 * 2


def test_a_slot_reused_by_a_shorter_prompt_reads_its_own_pages(seeded):
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


def test_a_bfloat16_run_of_this_float32_configuration_fails(seeded):
    """The tolerances above are tight enough: the same comparison with the
    matrices and the latent plane held in bfloat16 is outside them by two
    orders."""
    dims, params = seeded
    a = _tokens(50, 5)
    want = _reference_logits(params, dims, a)
    lower = jax.tree_util.tree_map(
        lambda leaf: leaf.astype(jnp.bfloat16) if leaf.ndim >= 2 else leaf,
        params)
    served = Served(program_config(dtype="bfloat16"), lower,
                    dtype=jnp.bfloat16)
    last = served.admit({0: a[:20]}, bucket=32)
    assert np.abs(last[0].astype(np.float32) - want[19]).max() > 1e-3
    assert _follow(served, want, a, 0, 12) > 1e-3


def test_a_padded_bucket_leaves_what_the_exact_length_leaves(seeded):
    _, params = seeded
    cfg = program_config()
    prompt = _tokens(16, 5)
    with jax.default_matmul_precision("highest"):
        exact = prefill(params, cfg, jnp.asarray([prompt]),
                        jnp.asarray([16], jnp.int32))
        padded = prefill(params, cfg, jnp.asarray([prompt + [9] * 16]),
                         jnp.asarray([16], jnp.int32))
    assert np.abs(np.asarray(exact[0]) - np.asarray(padded[0])).max() < 2e-5
    assert exact[1].shape == (3, 1, 1, 40, 16)
    assert np.abs(np.asarray(exact[1])
                  - np.asarray(padded[1])[..., :16]).max() < 2e-5


def test_the_absorbed_form_is_the_non_absorbed_form(seeded):
    """One block's attention over the same 20 tokens: the published form
    over the window (K and V of every head made from the latents) and the
    absorbed form token 19 takes against pages that hold tokens 0-18."""
    _, params = seeded
    cfg, w = program_config(), params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 20, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, latent = attention_prefill(x, w, cfg)
        pool = paged_write_window(
            jnp.zeros((1, 3, 1, cfg.latent_dim, 16)), latent[None],
            jnp.asarray([[1, 2]]), jnp.zeros((1,), jnp.int32),
            jnp.asarray([19], jnp.int32))
        absorbed, tail = attention_decode(
            x[:, 19], w, jnp.asarray([19]), pool, jnp.asarray([[1, 2]]),
            jnp.asarray([19]), plane_tail(pool, 1, 4), jnp.asarray([1]), 0,
            cfg)
    assert np.abs(np.asarray(absorbed[0] - out[0, 19])).max() < 1e-5
    # the token's own latent is what the tail now holds, and what the
    # window's writer was handed for position 19
    assert np.abs(np.asarray(tail[0, 0, 0, 0, :cfg.latent_dim]
                             - latent[0, 0, :, 19])).max() < 1e-6


def test_the_eight_shares_of_the_expert_layer_add_up_to_the_whole():
    """Model-configs guide, section 4: eight chips share a layer. Each
    share's routed part, and the shared expert counted once, add up to the
    uncut reference's whole layer; and the program's share is the
    reference's share, in both phases."""
    dims = {**reference.dims_of(CONFIG), "E": 16, "lo": 0, "hi": 16}
    w = reference._make_layer(jax.random.PRNGKey(11),
                              reference.layer_shapes(dims, False), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(12), (24, 64), jnp.float32)

    def share(i):
        lo, hi = 2 * i, 2 * i + 2
        return {**w, **{name: w[name][lo:hi] for name in ("w1", "wg", "w2")}
                }, (lo, hi)

    with jax.default_matmul_precision("highest"):
        whole = reference.expert_ffn(x, w, dims)
        parts = [reference.expert_ffn(x, held_w, dims, held=held,
                                      shared=(i == 0))
                 for i, (held_w, held) in enumerate(map(share, range(8)))]
        assert np.abs(np.asarray(sum(parts) - whole)).max() < 1e-5
        assert np.abs(np.asarray(parts[0] - whole)).max() > 1e-3   # a cut
        live = jnp.ones((24,), bool)
        for i in (0, 5):
            held_w, held = share(i)
            want = reference.expert_ffn(x, held_w, dims, held=held)
            cfg = MlaMoeConfig(
                vocab_size=512, dim=64, n_layers=2, n_heads=4, q_rank=48,
                kv_rank=32, nope_dim=16, rope_dim=8, v_dim=16, dense_dim=128,
                n_experts=16, experts_held=held, experts_per_token=2,
                expert_dim=32, shared_dim=32, dtype="float32")
            got, _ = ffn_decode(x, held_w, live, cfg)
            assert np.abs(np.asarray(got - want)).max() < 1e-5
            got = ffn_prefill(x.reshape(2, 12, 64), held_w,
                              jnp.ones((2, 12), bool), cfg)
            assert np.abs(np.asarray(got.reshape(24, 64) - want)).max() < 1e-5


# Pages of 16 tokens. A page this small weighs nothing, so the rule alone
# would fold a table's width: each case says how many pages a fold is to
# hold, and the rule is given the weight that makes it so. The rows of the
# cases at folds of 4 are a fold's edges: exactly C pages, C + 1 (a last
# fold of one page after a full one), one page, one token, a row of length
# 0 between two live rows, a row whose pages are still empty, two full
# folds, a last fold of one page and a token. The rows of the cases under
# a table of 16 are last folds of every width a fold of 8 is computed at
# (`fold_branch`): 1, 2, 3, 4, 5, 7 and 8 live pages, a row of one token,
# and a row of 9 pages (a full fold, then one page) after a row of one.
@pytest.mark.parametrize("n_table,fold,lengths,tail_lens", [
    (3, 2, [37, 0, 16, 5], [3, 0, 1, 4]), (3, 2, [0, 0, 0, 0], [0, 0, 0, 0]),
    (3, 2, [48, 48, 1, 33], [1, 2, 3, 4]), (3, 1, [37, 0, 16, 5], [3, 0, 1, 4]),
    (8, 4, [64, 80, 16, 0, 1, 128, 65], [1, 8, 2, 0, 5, 3, 4]),
    (8, 4, [1, 0, 0, 113, 0, 64, 0], [1, 0, 3, 2, 0, 8, 0]),
    (8, 4, [128, 17, 0, 96, 49, 0, 4], [8, 1, 0, 7, 2, 0, 3]),
    (8, 8, [128, 17, 0, 96, 49, 0, 4], [8, 1, 0, 7, 2, 0, 3]),
    (16, 8, [16, 144, 1, 29, 48, 0, 65], [1, 8, 2, 3, 5, 0, 4]),
    (16, 8, [80, 112, 0, 128, 3, 130, 17], [3, 1, 0, 8, 2, 6, 7]),
    (16, 8, [1, 241, 16, 0, 33, 256, 2], [8, 8, 1, 0, 4, 3, 1])])
def test_mla_read_in_interpret_mode_is_its_oracle(n_table, fold, lengths,
                                                  tail_lens, monkeypatch):
    """Pages and the block's tail in one softmax, the value the first 32
    of the key's 40 values, all 4 heads on the one latent head; a row that
    holds no request reads nothing and puts nothing. Every page outside
    the rows' live ranges is NaN, and every table entry past a row's live
    pages names one: nothing dead is read, and nothing masked reaches the
    value product."""
    keys = jax.random.split(jax.random.PRNGKey(6), 5)
    L, B, H, w, r, ps, T = 2, len(lengths), 4, 40, 32, 16, 8
    monkeypatch.setattr(paged_attention, "_FOLD_BYTES", fold * w * ps * 4)
    assert paged_attention.pages_per_fold(w * ps * 4, n_table) == fold
    pool = jax.random.normal(keys[0], (L, 1 + B * n_table, 1, w, ps),
                             jnp.float32)
    tail = jax.random.normal(keys[1], (L, B, 1, T, 128), jnp.float32)
    q = jax.random.normal(keys[2], (B, H, w), jnp.float32)
    new = jax.random.normal(keys[3], (B, 1, w), jnp.float32)
    table = 1 + np.arange(B * n_table).reshape(B, n_table)
    live = np.zeros(pool.shape[1], bool)
    for b, n in enumerate(lengths):
        live[table[b, :-(-n // ps)]] = True
    poisoned = jnp.where(jnp.asarray(live)[None, :, None, None, None], pool,
                         jnp.nan)
    table = jnp.asarray(table, jnp.int32)
    lengths, tail_lens = jnp.asarray(lengths), jnp.asarray(tail_lens)
    got, tail_out = jax.jit(lambda *a: mla_read(
        *a, value_width=r, scale=0.2, layer=jnp.int32(1), interpret=True))(
        q, new, poisoned, tail, table, lengths, tail_lens)
    # the oracle: the row's pages, then the tail's first tokens with the
    # new one put, laid out as one more run of pages
    put = np.array(tail[1, :, 0, :, :w])
    for b, n in enumerate(np.asarray(tail_lens)):
        if n:
            put[b, n - 1] = np.asarray(new[b, 0])
    keys_all = np.zeros((B, (n_table + 1) * ps, w), np.float32)
    total = np.asarray(lengths) + np.asarray(tail_lens)
    for b in range(B):
        n = int(lengths[b])
        flat = np.moveaxis(np.asarray(pool[1])[np.asarray(table[b]), 0], 1, 0
                           ).reshape(w, n_table * ps).T
        keys_all[b, :n] = flat[:n]
        keys_all[b, n:n + int(tail_lens[b])] = put[b, :int(tail_lens[b])]
    flat_pool = jnp.asarray(np.moveaxis(
        keys_all.reshape(B * (n_table + 1), ps, w), 1, 2)[:, None])
    want = mla_read_reference(
        q, flat_pool, jnp.arange(B * (n_table + 1), dtype=jnp.int32).reshape(
            B, n_table + 1), jnp.asarray(total), value_width=r, scale=0.2)
    assert got.shape == (B, H, r)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    rows = np.asarray(tail_lens) > 0
    assert not np.asarray(got)[~rows].any()
    assert np.array_equal(np.asarray(tail_out[0]), np.asarray(tail[0]))
    assert np.abs(np.asarray(tail_out[1, :, 0, :, :w])[rows]
                  - put[rows]).max(initial=0.0) < 1e-6
    assert np.array_equal(np.asarray(tail_out[1])[~rows],
                          np.asarray(tail[1])[~rows])


@pytest.mark.parametrize("rows", [0, 1, 6])
def test_gated_moe_experts_decode_in_interpret_mode_is_its_oracle(rows):
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    held, D, F, B = 4, 32, 24, 6
    x = jax.random.normal(keys[0], (B, D), jnp.float32)
    w1 = jax.random.normal(keys[1], (held, F, D), jnp.float32) / 6
    wg = jax.random.normal(keys[3], (held, F, D), jnp.float32) / 6
    w2 = jax.random.normal(keys[2], (held, F, D), jnp.float32) / 5
    combine = np.zeros((B, held), np.float32)
    for r in range(rows):
        combine[r, (0, 1, 3)[r % 3]] = 0.5 + r
    got = jax.jit(lambda *a: decode_experts(*a[:4], wg=a[4], interpret=True))(
        x, w1, w2, jnp.asarray(combine), wg)
    want = experts_reference(x, w1, w2, jnp.asarray(combine), wg)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    # and the gate is not a no-op: the ungated form gives another answer
    if rows:
        plain = experts_reference(x, w1, w2, jnp.asarray(combine))
        assert np.abs(np.asarray(plain - want)).max() > 1e-2


@pytest.mark.parametrize("tm", [8, 16])
def test_gated_moe_experts_prefill_is_its_oracle(tm):
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    held, lo, D, F, T, k = 4, 2, 32, 24, 21, 2
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    w1 = jax.random.normal(keys[1], (held, F, D), jnp.float32) / 6
    wg = jax.random.normal(keys[5], (held, F, D), jnp.float32) / 6
    w2 = jax.random.normal(keys[2], (held, F, D), jnp.float32) / 5
    picks = jnp.stack([jax.random.permutation(kk, 8)[:k] for kk in
                       jax.random.split(keys[3], T)]).astype(jnp.int32)
    weights = jax.random.uniform(keys[4], (T, k), jnp.float32, 0.2, 1.0)
    weights = weights.at[17:].set(0.0)                 # padding tokens
    got = jax.jit(lambda *a: prefill_experts(
        *a[:5], lo, 8, tm=tm, wg=a[5], interpret=True))(
        x, w1, w2, picks, weights, wg)
    combine = np.zeros((T, 8), np.float32)
    combine[np.arange(T)[:, None], np.asarray(picks)] = np.asarray(weights)
    want = experts_reference(x, w1, w2,
                             jnp.asarray(combine[:, lo:lo + held]), wg)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    assert not np.asarray(got)[17:].any()


@pytest.mark.parametrize("T,blocks", [(24, (128, 128)), (160, (128, 128)),
                                      (160, (64, 32)), (160, (32, 64))])
def test_flash_attention_takes_a_key_width_and_a_value_width(T, blocks):
    """Keys of 24 (16 + a rotated 8), values of 16, as MLA's published form
    has them; the scale is the key width's; q and kv blocks of unlike
    sizes, as the family's prefill asks for."""
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(keys[0], (2, T, 4, 24), jnp.float32)
    k = jax.random.normal(keys[1], (2, T, 4, 24), jnp.float32)
    v = jax.random.normal(keys[2], (2, T, 4, 16), jnp.float32)
    got = flash_attention(q, k, v, True, *blocks, interpret=True)
    want = attention_reference(q, k, v, causal=True)
    assert got.shape == (2, T, 4, 16)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


# -- the engine ---------------------------------------------------------------
def _engine(cfg, params, **kw):
    kw.setdefault("prefix_cache", False)
    return PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=128,
                          page_size=16, n_pages=33,
                          prefill_buckets=(16, 32), decode_block_size=4,
                          **kw)


def test_the_engine_serves_the_family_on_its_normal_path(seeded):
    """Admission, page allocator, loop, demux: more requests than slots, so
    slots are reused by prompts of other lengths; every served token is the
    reference's first choice (float32: no near-ties), and /debug/engine
    says what a page holds and how the routing fell."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    dims, params = seeded
    cfg = program_config()
    engine = _engine(cfg, params)
    assert [pool.shape for pool in engine.pools] == [(3, 33, 1, 40, 16)]
    assert engine.state == () and engine.model.counters == COUNTERS
    assert engine.pool_bytes() == 3 * 33 * 40 * 16 * 4
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
    assert snapshot["family"] == "mla_moe" and snapshot["kv_layers"] == 3
    assert snapshot["planes"] == [{"name": "latent", "heads": 1,
                                   "width": 40}]
    assert snapshot["cache_bytes_per_token"] == 3 * 40 * 4
    assert (snapshot["experts_held"], snapshot["experts_total"]) == (4, 8)
    routing = snapshot["routing"]
    assert 0 < routing["rows_per_step"] <= 4
    assert 0 <= routing["held_pick_share"] <= 1
    assert routing["tokens_per_held_expert_max_over_mean"] >= 1
    assert 0 < routing["experts_touched_per_layer_step"] <= 4


def test_the_engine_counts_the_folds_its_reads_made(seeded):
    """`/debug/engine` -> `paging.read` after three decode blocks of one
    request, then three of another: under the table of 4 these requests
    take a fold is 4 of the tiny pages; every step of a block, every
    layer, the read walks the pages of what the block found (its own
    tokens wait in the tail) in ONE fold, computed as wide as the pages it
    copied: 2 pages of 30 tokens at 2, 3 pages at 4, one page at 1."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    _, params = seeded
    engine = _engine(program_config(), params)
    assert engine.paging_snapshot()["read"] == {
        "pages_per_fold": None, "folds": 0, "narrowed_folds": 0,
        "fold_live_share": None, "short_row_share": None,
        "groups_split_share": None}
    engine.start()
    try:
        for n in (30, 5):
            # the prefill's token, then three blocks of 4
            engine.submit(_tokens(n, n), max_new_tokens=13).result(
                timeout_s=300)
        read = engine_snapshot(engine)["paging"]["read"]
    finally:
        engine.stop()
    layers, block = 3, 4
    found = [n + block * k for n in (30, 5) for k in range(3)]
    assert all(-(-n // 16) <= 4 for n in found)          # one fold each
    assert read["pages_per_fold"] == 4
    assert read["folds"] == layers * block * len(found)
    assert [-(-n // 16) for n in found] == [2, 3, 3, 1, 1, 1]
    assert read["narrowed_folds"] == layers * block * 4
    assert read["fold_live_share"] == round(
        sum(found) / ((2 + 4 + 4 + 1 + 1 + 1) * 16), 4)
    # every row one fold: each read took the short rows' step
    assert read["short_row_share"] == 1.0
    assert read["groups_split_share"] == 0.0


def test_the_two_plane_families_say_k_and_v():
    """What `/debug/engine` shows of a page for the families whose planes
    are K and V, and the engine's two names for their pools."""
    from gofr_tpu.models.llama import LlamaConfig, llama_init
    from gofr_tpu.tpu.utilization import engine_snapshot

    cfg = LlamaConfig.debug()
    engine = _engine(cfg, llama_init(cfg, seed=0))
    model = engine_snapshot(engine)["model"]
    assert model["planes"] == [
        {"name": "k", "heads": 2, "width": 16},
        {"name": "v", "heads": 2, "width": 16}]
    assert model["cache_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    assert engine.k_cache is engine.pools[0]
    assert engine.v_cache is engine.pools[1]
    assert engine.pool_bytes() == 2 * engine.k_cache.size * 4


def test_what_a_token_meets_and_what_a_page_holds():
    """tpu/utilization.py counts 2 P flops a token with P what a token
    MEETS; tpu/capacity.py counts a token's bytes from the page's planes:
    576 values a block, 1,152 bytes in bfloat16, against 20,480 for 32
    heads of K (192) and V (128)."""
    from gofr_tpu.tpu.capacity import (kv_token_bytes, plan_capacity,
                                       prefill_temp_bytes)

    cfg = MlaMoeConfig.joyai_llm_flash_ep8()
    assert (cfg.n_layers, cfg.expert_layers, cfg.held) == (12, 11, 32)
    assert cfg.latent_dim == 576 and cfg.qk_dim == 192
    assert kv_token_bytes(cfg) == 12 * 576 * 2 == 13824
    m = cfg.matrix_params()
    assert m["attention"] == 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 2048
    assert 26.3e6 < m["attention"] < 26.5e6
    assert m["experts_held"] == 2048 * 256 + 3 * 2048 * 768 \
        + 32 * 3 * 2048 * 768
    assert m["experts_met"] == 2048 * 256 + 3 * 2048 * 768 + 3 * 2048 * 768
    held = 12 * m["attention"] + m["dense"] + 11 * m["experts_held"] \
        + 2 * 2048 * 16160
    assert 2.1e9 < held < 2.2e9            # 4.29 GB in bfloat16
    assert cfg.param_count() == 12 * m["attention"] + m["dense"] \
        + 11 * m["experts_met"] + 2048 * 16160
    plan = plan_capacity(cfg, 128, 5120, 16 << 30,
                         prefill_buckets=(2048, 3072, 4096),
                         params_nbytes=2 * held, clamp=False)
    assert plan.cache_bytes_max == 128 * 5120 * 13824
    assert prefill_temp_bytes(cfg, 2, 4096) == 13824 * 2 * 4096 \
        + 4 * 2 * 4096 * 7168 * 2


def test_the_debug_preset_builds_and_steps():
    cfg = MlaMoeConfig.debug()
    params = mla_moe_init(cfg, 0)
    assert params["layers"][0]["w_gate"].shape == (64, 128)
    assert params["layers"][1]["wg"].shape == (8, 32, 64)
    engine = _engine(cfg, params)
    engine.start()
    try:
        out = engine.submit(_tokens(9, 1), max_new_tokens=6).result(
            timeout_s=300)
    finally:
        engine.stop()
    assert len(out) == 6
