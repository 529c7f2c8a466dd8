"""The sparse_linear family on the serving path (ISSUE 46), at tiny widths
in float32 on the CPU, seeded: the lightning update's kernel (interpret
mode) and its jax.numpy form and the chunkwise prefill against the
token-by-token recurrence; the block choice against a brute-force one; the
choice's, the read's and the sparse prefill's kernels against their
references; the compressed keys a slot's half-window sums give against the
means they stand for; the program's prefill and decode through pages,
compressed keys and per-slot state against
benchmark/reference/sparse_linear.py's plain full forward (logits
compared), across a page, a stride, a block of steps and `dense_len`, the
sequence cut at different places; what the family refuses, what the engine
plans and reports for the third plane, and the engine end to end."""

import dataclasses
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

from gofr_tpu.models.protocol import Plane  # noqa: E402
from gofr_tpu.models.sparse_linear import (COUNTERS, REFUSES,  # noqa: E402
                                           SparseLinearConfig, decode_step,
                                           layer_shapes, prefill,
                                           sparse_linear_init, state_shapes)
from gofr_tpu.ops import sparse_attention as sparse  # noqa: E402
from gofr_tpu.ops.lightning import (lightning_chunk, lightning_update,  # noqa: E402
                                    lightning_update_reference)
from gofr_tpu.ops.paged_attention import (column_tail, flush_columns,  # noqa: E402
                                          flush_planes, paged_write_columns,
                                          paged_write_window, plane_tail,
                                          tail_put)
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

reference = data.reference_for({"family": "sparse_linear"})

MIXERS = ["minicpm4"] + ["lightning-attn"] * 2 + ["minicpm4"]
CONFIG = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=4, mixer_types=MIXERS,
    layer_ids=[1, 2, 3, 4], published={"num_hidden_layers": 8},
    rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    intermediate_size=128, scale_emb=12, scale_depth=1.4, dim_model_base=16,
    rope_theta=10000, attn_use_rope=False, lightning_use_rope=True,
    qk_norm=True, use_output_norm=True, use_output_gate=True,
    attn_use_output_gate=True,
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=6,
                       init_blocks=1, window_size=24, dense_len=48))
RULE = dict(per=4, topk=6, init_blocks=1, window_blocks=3, dense_len=48,
            block_size=8)


def program_config(**kw):
    return SparseLinearConfig(
        vocab_size=512, dim=64,
        mixers=("sparse", "lightning", "lightning", "sparse"),
        layer_ids=(1, 2, 3, 4), depth=8, n_heads=4, n_kv_heads=2,
        head_dim=16, lightning_heads=4, lightning_head_dim=16, ffn_dim=128,
        dim_model_base=16, kernel_size=4, kernel_stride=2, block_size=8,
        topk=6, init_blocks=1, window_size=24, dense_len=48, chunk_size=16,
        max_seq_len=256, dtype="float32", **kw)


@pytest.fixture(scope="module")
def seeded():
    dims = reference.dims_of(CONFIG)
    return dims, reference.make_params(dims, 7, "float32")


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 512, size=n).tolist()


def _reference_logits(params, dims, tokens, pad_to=128):
    """The full forward, every sequence padded on the right to one length
    (causal: no real position sees the padding), so that the reference's
    blocks compile once for the file."""
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    return np.asarray(reference.logits(params, dims, padded))[:len(tokens)]


# -- the lightning state ------------------------------------------------------
def _recurrence(state, decay, k, q, v):
    """S <- lambda S + k^T v; o = q S: one token of one row, numpy."""
    new = decay[:, None, None] * state + k[:, :, None] * v[:, None, :]
    return np.einsum("hkv,hk->hv", new, q), new


@pytest.mark.parametrize("form", ["kernel", "jax.numpy"])
@pytest.mark.parametrize("live", [[True, False, True, True, False],
                                  [False] * 5])
def test_lightning_update_is_the_recurrence(form, live):
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    L, S, H, d = 2, 5, 4, 16
    state = jax.random.normal(keys[0], (L, S, H, d, d))
    decay = jnp.exp(-jax.random.uniform(keys[1], (H,)))
    k, q, v = (jax.random.normal(key, (S, H, d)) for key in keys[2:])
    update = (lightning_update_reference if form == "jax.numpy"
              else lambda *a: lightning_update(*a, interpret=True))
    o, new = update(state, 1, decay, k, q, v, jnp.asarray(live))
    for row, alive in enumerate(live):
        if alive:
            want_o, want = _recurrence(*(np.asarray(x) for x in (
                state[1, row], decay, k[row], q[row], v[row])))
            # float32 sums of 16 products of unit normals: 1e-5
            assert np.abs(np.asarray(o[row]) - want_o).max() < 1e-5
            assert np.abs(np.asarray(new[1, row]) - want).max() < 1e-5
        else:
            assert not np.asarray(o[row]).any()
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))


@pytest.mark.parametrize("T,lengths", [(64, (64, 23)), (32, (32, 1)),
                                       (16, (9, 16))])
def test_the_chunkwise_prefill_is_the_recurrence(T, lengths):
    """Chunks of 16 against one token at a time, the fastest head's decay
    0.37 a token (lambda^-16 would be 8e6, lambda^-128 over float32's
    range): a padded row's state is the state as of its last real token."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    K, H, d = len(lengths), 4, 16
    q, k, v = (jax.random.normal(key, (K, T, H, d)) for key in keys)
    decay = np.exp(-np.array([1.0, 0.5, 0.1, 0.01], np.float32))
    real = jnp.arange(T)[None, :] < jnp.asarray(lengths)[:, None]
    o, state = lightning_chunk(q, k, v, jnp.log(decay), real, chunk=16)
    for row, n in enumerate(lengths):
        S = np.zeros((H, d, d), np.float32)
        for t in range(n):
            want_o, S = _recurrence(S, decay, *(np.asarray(x[row, t])
                                                for x in (k, q, v)))
            assert np.abs(np.asarray(o[row, t]) - want_o).max() < 2e-4
        assert np.abs(np.asarray(state[row]) - S).max() < 2e-4


# -- the choice ---------------------------------------------------------------
def _brute_choice(r, t):
    """The rule of ISSUE 46 section 1, one query, one KV head, in loops:
    r [N] the head-summed probabilities (-inf where not visible)."""
    per, topk = RULE["per"], RULE["topk"]
    n_blocks = len(r) // per
    own = t // RULE["block_size"]
    if t + 1 <= RULE["dense_len"]:
        return [b <= own for b in range(n_blocks)]
    forced = {b for b in range(n_blocks) if b <= own and (
        b < RULE["init_blocks"] or b > own - RULE["window_blocks"])}
    scored = []
    for b in range(own + 1):
        if b in forced:
            continue
        js = [j for j in range(per * b - 1, per * b + per) if 0 <= j < len(r)]
        scored.append((-max([r[j] for j in js], default=-np.inf), b))
    far = [b for _, b in sorted(scored)[:topk - len(forced)]]
    return [b in forced or b in far for b in range(n_blocks)]


@pytest.mark.parametrize("t", [40, 47, 48, 63, 64, 100, 127])
def test_the_choice_is_the_brute_force_one(t):
    """Under, at and past dense_len; scores with exact ties (rounded to one
    decimal) so that ties to the lower index are exercised."""
    rng = np.random.default_rng(t)
    N = 64                                       # 16 blocks of 4
    r = np.round(rng.random((3, 2, N)), 1).astype(np.float32)
    visible = 2 * np.arange(N) + 3 <= t
    r = np.where(visible, r, -np.inf)
    chosen = np.asarray(sparse.choose(
        jnp.asarray(r), jnp.full((3, 2), t, jnp.int32), **RULE))
    for row in range(3):
        for head in range(2):
            assert chosen[row, head].tolist() == _brute_choice(
                r[row, head], t), (row, head)
    if t + 1 > RULE["dense_len"]:
        assert (chosen.sum(-1) == RULE["topk"]).all()


def test_the_choice_kernel_is_its_reference():
    keys = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(keys[0], (3, 4, 16))
    ck = jax.random.normal(keys[1], (3, 2, 32, 16))
    visible = jnp.asarray([32, 7, 0], jnp.int32)
    want = np.asarray(sparse.select_scores_reference(q, ck, visible))
    got = np.asarray(sparse.select_scores(q, ck, visible, interpret=True))
    finite = np.isfinite(want)
    assert np.array_equal(np.isfinite(got), finite)
    assert np.abs(np.where(finite, want, 0.0)
                  - np.where(finite, got, 0.0)).max() < 1e-6
    # a head's probabilities sum to 1: two heads a KV head
    assert np.allclose(np.where(finite, got, 0.0).sum(-1)[:2], 2.0, atol=1e-5)


def test_the_half_window_sums_give_the_means_they_stand_for():
    """A prompt's compressed keys, then one a stride as tokens arrive: each
    the mean of its 4 keys, whatever length the prompt was cut at."""
    k = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 2, 16))
    want = np.stack([np.asarray(k[0, 2 * j:2 * j + 4]).mean(0)
                     for j in range(19)])
    for cut in (0, 1, 7, 8, 16):
        ck, sums = sparse.half_sums_prefill(
            k[:, :16], jnp.asarray([cut], jnp.int32), 2)
        n = max(cut - 2, 0) // 2
        assert np.allclose(np.asarray(ck[0, :n]), want[:n], atol=1e-6)
        assert not np.asarray(ck[0, n:]).any()
        state = jnp.zeros((1, 1, 2, 2, 16)).at[0].set(sums)
        for t in range(cut, 40):
            state, c, completes = sparse.half_sums_step(
                state, 0, k[:, t], jnp.asarray([t]), jnp.asarray([True]), 2)
            assert bool(completes[0]) == (t % 2 == 1 and t >= 3)
            if completes[0]:
                assert np.abs(np.asarray(c[0]) - want[(t - 3) // 2]).max() \
                    < 1e-6


# -- the read and the sparse prefill ------------------------------------------
@pytest.mark.parametrize("layer", [0, 1])
def test_sparse_read_is_its_reference(layer):
    """Rows of 100 and 37 tokens and a dead one over scattered pages; the
    lists name half pages, whole pages and a last page cut at the row's
    length; the step's token joins the tail."""
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 12))
    B, H, Hkv, dh, ps, bs, NP, P = 3, 4, 2, 16, 16, 8, 8, 40
    k_pool, v_pool = (jax.random.normal(next(keys), (2, P, Hkv, dh, ps))
                      for _ in range(2))
    table = np.random.default_rng(1).permutation(
        np.arange(1, P))[:B * NP].reshape(B, NP).astype(np.int32)
    table[2] = 0                                  # holds no request
    table = jnp.asarray(table)
    lengths = jnp.asarray([100, 37, 0], jnp.int32)
    tail_lens = jnp.asarray([3, 3, 0], jnp.int32)
    k_tail, v_tail = (jnp.zeros_like(plane_tail(k_pool, B, 8)).at[
        ..., :dh].set(jax.random.normal(next(keys), (2, B, Hkv, 16, dh)))
        for _ in range(2))
    q = jax.random.normal(next(keys), (B, H, dh))
    k, v = (jax.random.normal(next(keys), (B, Hkv, dh)) for _ in range(2))
    n_blocks = NP * ps // bs
    block = jnp.arange(n_blocks)[None, None, :]
    chosen = jax.random.bernoulli(next(keys), 0.5, (B, Hkv, n_blocks))
    own = ((lengths - 1) // bs)[:, None, None]
    chosen = jnp.logical_or(jnp.logical_or(chosen, block == 0),
                            block >= own - 1)
    chosen = jnp.logical_and(chosen, block * bs < lengths[:, None, None])
    put = tail_put(k_tail, v_tail, k, v, layer, 2)
    want = sparse.sparse_read_reference(
        q, k_pool, v_pool, *put, table, chosen, lengths, tail_lens,
        layer=layer, block_size=bs)
    pages, bits, held = sparse.page_lists(chosen, table, lengths, ps, bs, 8)
    got, k_out, v_out = sparse.sparse_read(
        q, k, v, k_pool, v_pool, k_tail, v_tail, pages, bits, held,
        tail_lens, layer=layer, block_size=bs, interpret=True)
    assert np.abs(np.asarray(want[:2] - got[:2])).max() < 1e-6
    assert not np.asarray(got[2]).any()
    for mine, theirs in ((k_out, put[0]), (v_out, put[1])):
        assert np.abs(np.asarray(mine[layer, :2, ..., :dh]
                                 - theirs[layer, :2, ..., :dh])).max() == 0


def test_the_sparse_prefill_kernel_is_its_reference():
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    T, start = 96, 48
    q = jax.random.normal(keys[0], (1, T - start, 4, 16))
    k, v = (jax.random.normal(key, (1, T, 2, 16)) for key in keys[1:])
    ck, _ = sparse.half_sums_prefill(k, jnp.asarray([T]), 2)
    chosen = sparse.prefill_choice(q, ck, start, stride=2, kernel=4, tile=32,
                                   **RULE)
    assert (np.asarray(chosen).sum(-1) == RULE["topk"]).all()
    want = sparse.sparse_prefill_reference(q, k, v, chosen, start, 8)
    got = sparse.sparse_prefill(q, k, v, chosen, start, 8, block_q=16,
                                block_kv=16, interpret=True)
    assert np.abs(np.asarray(want - got)).max() < 2e-6


# -- the program against the reference ----------------------------------------
PLANES = program_config().planes()


class Served:
    """The three pools, a block table and per-slot state as the engine holds
    them, driven by the model's two functions directly so that LOGITS can be
    compared (the engine hands out tokens only), with the engine's own
    writers: a prefill's windows, a decode block's tails and its flush.
    Dead slots hold junk. Decode runs in blocks of BLOCK steps."""

    BLOCK = 5       # a block ends inside a page, at its edge and across it

    def __init__(self, cfg, params, slots=4, page=16, pages_a_slot=8):
        self.cfg, self.params, self.page = cfg, params, page
        self.tails, self.at = None, 0
        self.state = tuple(jnp.full(shape, 7.0, dtype)
                           for shape, dtype in state_shapes(cfg, slots))
        n_pages = slots * pages_a_slot + 1
        self.pools = [jnp.zeros(plane.pool_shape(cfg.kv_layers, n_pages,
                                                 page)) for plane in PLANES]
        self.table = np.zeros((slots, pages_a_slot), np.int32)
        self.own = {s: [1 + s * pages_a_slot + i for i in range(pages_a_slot)]
                    for s in range(slots)}
        self.pos = np.zeros((slots,), np.int32)
        self._prefill = jax.jit(lambda p, t, n: prefill(p, cfg, t, n))
        self._step = jax.jit(
            lambda p, t, pos, pools, tb, st, tails, at: decode_step(
                p, cfg, t, pos, pools, tb, st, tails, at))

    def flush(self):
        if self.tails is None:
            return
        table, began = (jnp.asarray(x) for x in self._block)
        counts = jnp.where(table[:, 0] > 0, self.at, 0)
        self.pools[:2] = flush_planes(self.pools[:2], self.tails[:2], table,
                                      began, counts)
        start = PLANES[2].columns(began)
        self.pools[2] = flush_columns(
            self.pools[2], self.tails[2], table, start, jnp.where(
                table[:, 0] > 0, PLANES[2].columns(began + self.at) - start,
                0))
        self.tails, self.at = None, 0

    def admit(self, rows, bucket):
        """rows: {slot: prompt}. Returns {slot: last-position logits}."""
        self.flush()
        slots = sorted(rows)
        window = np.zeros((len(slots), bucket), np.int32)
        for i, s in enumerate(slots):
            window[i, :len(rows[s])] = rows[s]
        lengths = jnp.asarray([len(rows[s]) for s in slots], jnp.int32)
        last, windows, fresh = self._prefill(self.params,
                                             jnp.asarray(window), lengths)
        for s in slots:
            self.table[s] = self.own[s]
            self.pos[s] = len(rows[s])
        ptable = jnp.asarray(self.table[slots][:, :-(-bucket // self.page)])
        zero = jnp.zeros_like(lengths)
        self.pools = [
            paged_write_window(pool, w, ptable, zero, lengths)
            if plane.stride == 1
            else paged_write_columns(pool, w, ptable, plane.columns(lengths))
            for plane, pool, w in zip(PLANES, self.pools, windows)]
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
        if self.tails is None:
            rows = len(self.pos)
            self.tails = tuple(
                plane_tail(pool, rows, self.BLOCK) if plane.stride == 1
                else column_tail(pool, rows, -(-self.BLOCK // plane.stride))
                for plane, pool in zip(PLANES, self.pools))
            self._block = (self.table.copy(), self.pos.copy())
        logits, self.tails, self.state, counted = self._step(
            self.params, jnp.asarray(fed), jnp.asarray(self.pos),
            tuple(self.pools), jnp.asarray(self._block[0]), self.state,
            self.tails, jnp.int32(self.at))
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


# float32 throughout, logits of order 4: 2e-5 after a prefill and 5e-5
# after tens of decode steps is float32 rounding through four blocks (the
# kda_moe and nemotron_h tests' tolerances). A block chosen otherwise than
# the reference chose it, a compressed key one step late or a state one
# token stale reads 1e-2 and more (the faults of
# benchmark/tests/test_sparse_linear.py)
AFTER_PREFILL, AFTER_DECODE = 2e-5, 5e-5


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_prefill_then_decode_steps_match_the_full_forward(seeded, impl):
    """`xla`: the jax.numpy forms; `flash`: the kernels (interpret mode
    here). Row 1 is admitted under dense_len (21 tokens) and row 3 at 43:
    its decode crosses dense_len (48) at its 6th step, a page (16) and a
    stride (2) on the way, in blocks of 5 steps, and from there chooses 6
    of its 7 and more blocks."""
    dims, params = seeded
    steps = 40 if impl == "xla" else 9
    a, b = _tokens(100, 1), _tokens(100, 2)
    want_a = _reference_logits(params, dims, a)
    want_b = _reference_logits(params, dims, b)
    served = Served(program_config(attn_impl=impl), params)
    last = served.admit({1: a[:21], 3: b[:43]}, bucket=48)
    assert np.abs(last[1] - want_a[20]).max() < AFTER_PREFILL
    assert np.abs(last[3] - want_b[42]).max() < AFTER_PREFILL
    worst, chose = 0.0, 0
    for _ in range(steps):
        got, counted = served.step({1: a[served.pos[1]], 3: b[served.pos[3]]})
        worst = max(worst,
                    np.abs(got[1] - want_a[served.pos[1] - 1]).max(),
                    np.abs(got[3] - want_b[served.pos[3] - 1]).max())
        chose += dict(zip(COUNTERS, counted))["sparse_rows"]
    assert worst < AFTER_DECODE
    counted = dict(zip(COUNTERS, counted))
    # two live rows of four, two blocks of each kind: the junk rows are
    # out of the counters
    assert counted["lightning_rows"] == 2 * 2
    assert counted["sparse_rows"] + counted["dense_rows"] == 2 * 2
    assert chose > 0
    if impl == "xla":
        # both rows past dense_len by now: 6 blocks a KV head a sparse block
        assert counted["sparse_rows"] == 4
        assert counted["blocks_read"] == 4 * 2 * 6
        assert counted["blocks_held"] > counted["blocks_read"]


@pytest.mark.parametrize("impl,bucket", [("xla", 96), ("flash", 64)])
def test_a_prompt_that_crosses_dense_len_chooses_inside_its_prefill(
        seeded, impl, bucket):
    """A prompt past dense_len: its later queries each choose their own
    blocks inside the prefill (`flash`: the masked flash kernel), and the
    decode goes on from the compressed keys the prefill left."""
    dims, params = seeded
    a = _tokens(110, 3)
    want = _reference_logits(params, dims, a)
    n = bucket - 5
    served = Served(program_config(attn_impl=impl), params)
    last = served.admit({0: a[:n]}, bucket=bucket)
    assert np.abs(last[0] - want[n - 1]).max() < AFTER_PREFILL
    assert _follow(served, want, a, 0, 7 if impl == "flash" else 19) \
        < AFTER_DECODE


@pytest.mark.parametrize("cut", [17, 31, 32, 47, 48, 49, 63, 80])
def test_a_sequence_cut_anywhere_gives_the_same_logits(seeded, cut):
    """Prefill to `cut`, decode from there: the logit at position 90 is the
    full forward's whatever `cut` was (dense_len is a rule a query
    position, not a switch on the call's length)."""
    dims, params = seeded
    a = _tokens(100, 4)
    want = _reference_logits(params, dims, a)
    served = Served(program_config(), params)
    bucket = -(-cut // 16) * 16
    last = served.admit({2: a[:cut]}, bucket=bucket)
    assert np.abs(last[2] - want[cut - 1]).max() < AFTER_PREFILL
    assert _follow(served, want, a, 2, 91 - cut) < AFTER_DECODE


def test_a_slot_reused_by_a_second_request_gives_its_own_logits(seeded):
    dims, params = seeded
    long, short = _tokens(90, 5), _tokens(60, 6)
    served = Served(program_config(), params)
    served.admit({2: long[:60]}, bucket=64)
    assert _follow(served, _reference_logits(params, dims, long), long, 2,
                   12) < AFTER_DECODE
    served.retire(2)
    served.admit({2: short[:9]}, bucket=16)
    assert _follow(served, _reference_logits(params, dims, short), short, 2,
                   45) < AFTER_DECODE


def test_a_padded_bucket_leaves_what_the_exact_length_leaves(seeded):
    _, params = seeded
    run = jax.jit(lambda p, t, n: prefill(p, program_config(), t, n))
    prompt = _tokens(64, 7)
    exact = run(params, jnp.asarray([prompt]), jnp.asarray([64], jnp.int32))
    padded = run(params, jnp.asarray([prompt + [9] * 32]),
                 jnp.asarray([64], jnp.int32))
    assert np.abs(np.asarray(exact[0]) - np.asarray(padded[0])).max() < 2e-5
    for got, want in zip(padded[2], exact[2]):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < 2e-5
    # the compressed keys of the padding are not there
    assert not np.asarray(padded[1][2])[:, :, :, 31:].any()


# -- the plane, the plan and the engine ---------------------------------------
def test_a_plane_with_a_stride_counts_columns_not_tokens():
    plane = Plane("compressed_k", 2, 128, stride=16, span=32)
    assert [plane.columns(n) for n in (0, 31, 32, 47, 48, 8192)] == [
        0, 0, 1, 1, 2, 511]
    assert plane.pool_shape(2, 7681, 128) == (2, 7681, 2, 8, 128)
    assert np.asarray(plane.columns(jnp.asarray([10, 32, 100]))).tolist() \
        == [0, 1, 5]
    assert Plane("k", 2, 128).columns(77) == 77
    assert Plane("k", 2, 128).pool_shape(2, 9, 128) == (2, 9, 2, 128, 128)


def test_the_third_plane_is_planned_and_what_a_slot_holds():
    from gofr_tpu.tpu import capacity

    cfg = SparseLinearConfig.minicpm_sala_pp4()
    model = cfg.paged_model()
    assert [p.name for p in model.planes] == ["k", "v", "compressed_k"]
    # a token: K and V of 2 x 128 in two blocks, and 1/16 of a column
    assert capacity.kv_token_bytes(cfg) == 2 * (2 * 512 + 32) == 2112
    assert cfg.state_bytes_per_slot == 6 * 32 * 128 * 128 * 4 + 2 * 2048
    m = cfg.matrix_params()
    assert m["sparse"] == 52_428_800 and m["lightning"] == 83_886_080
    assert m["ffn"] == 201_326_592
    assert 2 * cfg.param_count() == 2 * (2 * (m["sparse"] + m["ffn"]) + 6 * (
        m["lightning"] + m["ffn"]) + 4096 * 73448)
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    # the decay: the fastest head of published block 10 forgets in ~2
    # tokens, the slowest keeps 99.7 % a token; in published block 0 the
    # fastest head's lambda^-128 is over float32's range
    decay = np.asarray(cfg.decay(1), np.float64)
    assert 0.56 < decay[0] < 0.57 and 0.997 < decay[-1] < 0.998
    first = dataclasses.replace(cfg, layer_ids=(0,) + cfg.layer_ids[1:])
    assert float(first.decay(0)[0]) ** -128.0 > 3.4e38


def test_every_refusal_names_what_it_lacks():
    assert set(REFUSES) == {"prefix_cache", "kv_host_tier", "disagg",
                            "speculative_tokens", "chunk_prefill_tokens",
                            "int8_weights", "kv_dtype", "mesh"}
    assert "compressed keys" in REFUSES["disagg"]
    assert "lightning state" in REFUSES["chunk_prefill_tokens"]


def test_the_program_and_the_reference_name_the_same_leaves(seeded):
    dims, params = seeded
    cfg = program_config()
    mine = sparse_linear_init(cfg, 0)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(params)
    for mixer, theirs in zip(cfg.mixers, dims["mixers"]):
        assert layer_shapes(cfg, mixer) == reference.layer_shapes(dims,
                                                                  theirs)


def _engine(cfg, params, **kw):
    kw.setdefault("prefix_cache", False)
    return PagedLLMEngine(params, cfg, n_slots=4, max_seq_len=128,
                          page_size=16, n_pages=33, prefill_buckets=(32, 64),
                          decode_block_size=4, **kw)


def test_the_engine_serves_the_family_on_its_normal_path(seeded):
    """Admission, page allocator, loop, demux: more requests than slots, so
    slots are reused by prompts of other lengths, under and past dense_len;
    every served token is the reference's first choice (float32: no
    near-ties), and /debug/engine says what the model holds, the third
    plane and how the choices fell."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    dims, params = seeded
    cfg = program_config()
    engine = _engine(cfg, params)
    assert [pool.shape for pool in engine.pools] == [
        (2, 33, 2, 16, 16), (2, 33, 2, 16, 16), (2, 33, 2, 8, 16)]
    assert [a.shape for a in engine.state] == [(2, 4, 4, 16, 16),
                                               (2, 4, 2, 2, 16)]
    engine.start()
    try:
        prompts = [_tokens(n, 20 + n) for n in (5, 47, 60, 9, 33, 50, 12)]
        requests = [engine.submit(p, max_new_tokens=18) for p in prompts]
        served = [r.result(timeout_s=300) for r in requests]
        snapshot = engine_snapshot(engine)["model"]
    finally:
        engine.stop()
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 18
        want = _reference_logits(params, dims, prompt + tokens)
        first = np.argmax(want[len(prompt) - 1:-1], axis=-1)
        assert tokens == first.tolist()
    assert snapshot["family"] == "sparse_linear"
    assert snapshot["kv_layers"] == 2
    assert snapshot["planes"][2] == {"name": "compressed_k", "heads": 2,
                                     "width": 16, "stride": 2, "span": 4}
    # K and V of 2 x 16 and half a column, two blocks, float32
    assert snapshot["cache_bytes_per_token"] == 2 * (2 * 32 + 16) * 4
    assert snapshot["blocks"] == {"sparse": 2, "lightning": 2}
    assert snapshot["state_bytes_per_slot"] == cfg.state_bytes_per_slot \
        == 2 * 4 * 16 * 16 * 4 + 2 * 2 * 2 * 16 * 4
    assert snapshot["state_bytes"] == 4 * cfg.state_bytes_per_slot
    chosen = snapshot["sparse"]
    assert chosen["sparse_rows_per_step"] > 0
    assert chosen["dense_rows_per_step"] > 0
    assert 0 < chosen["read_share"] < 1
    assert chosen["blocks_read"] < chosen["blocks_held"]
    assert snapshot["lightning_rows_per_step"] > 0


def test_an_engine_refuses_what_the_family_cannot_serve(seeded):
    _, params = seeded
    with pytest.raises(ValueError, match="sparse_linear family refuses "
                                         "prefix_cache"):
        _engine(program_config(), params, prefix_cache=True)


def test_a_cut_that_holds_one_kind_of_block_still_serves(seeded):
    """A pipeline stage of lightning blocks alone (the even split of 32
    gives stages of 1, 1, 3 and 3 sparse blocks; a finer one has none):
    the pools are empty stacks and the decode runs on the state alone."""
    cfg = dataclasses.replace(program_config(), mixers=("lightning",) * 2,
                              layer_ids=(1, 2))
    params = sparse_linear_init(cfg, 1)
    run = jax.jit(lambda p, t, n: prefill(p, cfg, t, n))
    last, windows, rows = run(params, jnp.asarray([_tokens(16, 8)]),
                              jnp.asarray([16], jnp.int32))
    assert [w.shape[0] for w in windows] == [0, 0, 0]
    assert rows[0].shape == (2, 1, 4, 16, 16) and rows[1].shape[0] == 0
    assert np.isfinite(np.asarray(last)).all()
