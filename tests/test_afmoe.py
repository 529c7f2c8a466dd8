"""The afmoe family on the serving path (ISSUE 33), at tiny widths in
float32 on the CPU, seeded: the program's prefill and decode through BOTH
page groups (the full blocks' pages and the sliding blocks' ring) against
benchmark/reference/afmoe.py's plain full forward (logits compared), on
contexts below, across and beyond a tiny window; the kernels in interpret
mode against their jax.numpy oracles (the windowed read through a ring, the
flush through a ring, windowed flash attention, the expert kernel with its
width in tiles); the eight expert shares adding up to the uncut layer; what
the family refuses; the engine end to end with its groups' reservations;
and the three older families' reservations, tables and pool shapes, which
are what they were.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (grouped experts against gathered ones, an online softmax
over pages against a softmax over a row), so logits of order 1 agree to a
few float32 roundings a block: 3e-5 after a prefill, 6e-5 over decode
steps. The same comparison with a fault (the window ignored, a full block
turned, the gate left out) reads over 1e-2."""

import functools
import math
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

from gofr_tpu.models import afmoe  # noqa: E402
from gofr_tpu.models.afmoe import (FULL, REFUSES, SLIDING,  # noqa: E402
                                   AfmoeConfig, decode_step, prefill)
from gofr_tpu.models.llama import LlamaConfig, llama_init  # noqa: E402
from gofr_tpu.models.experts import ffn_decode, ffn_prefill  # noqa: E402
from gofr_tpu.models.mla_moe import MlaMoeConfig  # noqa: E402
from gofr_tpu.models.mla_moe import mla_moe_init  # noqa: E402
from gofr_tpu.models.nemotron_h import NemotronHConfig, nemotron_h_init  # noqa: E402
from gofr_tpu.ops.flash_attention import (attention_reference,  # noqa: E402
                                          flash_attention)
flash_module = sys.modules["gofr_tpu.ops.flash_attention"]
from gofr_tpu.ops import moe_experts  # noqa: E402
from gofr_tpu.ops.moe_experts import (decode_experts, experts_reference,  # noqa: E402
                                      prefill_experts, width_tile)
from gofr_tpu.ops.paged_attention import (flush_planes,  # noqa: E402
                                          paged_attention_in_block,
                                          paged_write_window, plane_tail)
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

reference = data.reference_for({"family": "afmoe"})

KINDS = [SLIDING, SLIDING, SLIDING, SLIDING, FULL]
WINDOW, PAGE = 24, 8            # a ring of 24 / 8 + 2 = 5 pages
CONFIG = dict(
    vocab_size=512, hidden_size=64, num_hidden_layers=5, num_dense_layers=1,
    layer_types=KINDS, rms_norm_eps=1e-5, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, sliding_window=WINDOW,
    intermediate_size=128, num_experts=4, num_experts_published=8,
    experts_held=[0, 4], num_experts_per_tok=2, moe_intermediate_size=32,
    num_shared_experts=1, route_scale=2.448, rope_theta=10000,
    rope_scaling=None, n_group=1, topk_group=1, score_func="sigmoid",
    route_norm=True, mup_enabled=True)


def program_config(held=(0, 4), dtype="float32", **changed):
    return AfmoeConfig(**{**dict(
        vocab_size=512, dim=64, n_layers=5, first_dense=1,
        layer_types=tuple(KINDS), n_heads=4, n_kv_heads=2, head_dim=16,
        window=WINDOW, dense_dim=128, n_experts=8, experts_held=held,
        experts_per_token=2, expert_dim=32, shared_dim=32, max_seq_len=256,
        dtype=dtype), **changed})


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
    """The pools of both page groups, a table each and the block's tails
    as the engine holds them, driven by the model's two functions directly
    so that LOGITS can be compared (the engine hands out tokens only). The
    full group's table is a page a PAGE tokens; the window group's is a
    ring: logical page j in column j % ring, the prefill writing the
    prompt's last ring of pages only. Decode runs in blocks of BLOCK steps
    as the engine's program does."""

    BLOCK = 5       # a block ends inside a page, at its edge and across it

    def __init__(self, cfg, params, slots=3, pages_a_slot=16):
        self.cfg, self.params = cfg, params
        self.groups = cfg.page_groups()
        self.tail, self.at = None, 0
        self.ring = self.groups[1].ring(PAGE)
        self.widths = [pages_a_slot, self.ring]
        self.pools = []
        for group, width in zip(self.groups, self.widths):
            shape = (group.layers, slots * width + 1, cfg.n_kv_heads,
                     cfg.head_dim, PAGE)
            self.pools += [jnp.zeros(shape), jnp.zeros(shape)]
        self.tables = [np.zeros((slots, width), np.int32)
                       for width in self.widths]
        self.pos = np.zeros((slots,), np.int32)
        self._prefill = jax.jit(lambda p, t, n: prefill(p, cfg, t, n))
        self._step = jax.jit(lambda p, t, pos, pools, tables, tail, at:
                             decode_step(p, cfg, t, pos, pools, tables, tail,
                                         at))

    def own(self, group, slot):
        width = self.widths[group]
        return 1 + slot * width + np.arange(width)

    def flush(self):
        if self.tail is None:
            return
        tables, began = self._block
        for g, ring in enumerate((None, self.ring)):
            mine = slice(2 * g, 2 * g + 2)
            self.pools[mine] = flush_planes(
                self.pools[mine], self.tail[mine], jnp.asarray(tables[g]),
                jnp.asarray(began),
                jnp.where(jnp.asarray(tables[g][:, 0] > 0), self.at, 0),
                ring=ring)
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
            last, windows = self._prefill(self.params, jnp.asarray(window),
                                          lengths)
        n_ptable = -(-bucket // PAGE)
        ptables = [np.zeros((len(slots), n_ptable), np.int32)
                   for _ in self.groups]
        for i, s in enumerate(slots):
            self.tables[0][s] = self.own(0, s)
            self.tables[1][s] = self.own(1, s)
            self.pos[s] = len(rows[s])
            ptables[0][i] = self.tables[0][s][:n_ptable]
            last_page = -(-len(rows[s]) // PAGE)
            for j in range(max(0, last_page - self.ring), last_page):
                ptables[1][i, j] = self.tables[1][s][j % self.ring]
        for i, written in enumerate(windows):
            self.pools[i] = paged_write_window(
                self.pools[i], written, jnp.asarray(ptables[i // 2]),
                jnp.zeros_like(lengths), lengths)
        return {s: np.asarray(last[i]) for i, s in enumerate(slots)}

    def step(self, tokens):
        """tokens: {slot: token}. Returns ({slot: logits}, counters)."""
        fed = np.zeros_like(self.pos)
        for s, t in tokens.items():
            fed[s] = t
        if self.tail is None:
            self.tail = tuple(plane_tail(pool, len(self.pos), self.BLOCK)
                              for pool in self.pools)
            self._block = ([t.copy() for t in self.tables], self.pos.copy())
        with jax.default_matmul_precision("highest"):
            logits, self.tail, counted = self._step(
                self.params, jnp.asarray(fed), jnp.asarray(self.pos),
                tuple(self.pools),
                tuple(jnp.asarray(t) for t in self._block[0]), self.tail,
                jnp.int32(self.at))
        self.tail = list(self.tail)
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


# prompts below the window (10 of 24), across it (20, decode crosses 24)
# and beyond it (61: the prompt alone is past two windows, and 60 decode
# steps wrap the ring of 5 pages more than once: 121 tokens are 16 pages)
@pytest.mark.parametrize("prompt,steps", [(10, 8), (20, 30), (61, 60)])
def test_prefill_then_decode_through_both_groups_match_the_full_forward(
        seeded, prompt, steps):
    dims, params = seeded
    a, b = _tokens(prompt + steps, prompt), _tokens(prompt + steps, 99)
    want_a = _reference_logits(params, dims, a)
    want_b = _reference_logits(params, dims, b)
    served = Served(program_config(), params)
    bucket = -(-prompt // 16) * 16
    last = served.admit({0: a[:prompt], 2: b[:prompt - 3]}, bucket=bucket)
    assert np.abs(last[0] - want_a[prompt - 1]).max() < 3e-5
    assert np.abs(last[2] - want_b[prompt - 4]).max() < 3e-5
    worst = 0.0
    for _ in range(steps):
        got, counted = served.step({0: a[served.pos[0]], 2: b[served.pos[2]]})
        worst = max(worst,
                    np.abs(got[0] - want_a[served.pos[0] - 1]).max(),
                    np.abs(got[2] - want_b[served.pos[2] - 1]).max())
    assert worst < 6e-5
    assert counted[0] == 2      # two live rows of three


@pytest.mark.parametrize("fault", ["window_ignored", "full_block_turned",
                                   "gate_left_out"])
def test_each_fault_of_the_block_is_far_outside_the_tolerances(
        seeded, fault, monkeypatch):
    """What the benchmark's check must catch reads orders over 6e-5 here:
    a sliding block attending everything, a full block rotated, the
    attention output not gated."""
    dims, params = seeded
    a = _tokens(60, 5)
    want = _reference_logits(params, dims, a)
    cfg = program_config()
    if fault == "window_ignored":
        cfg = program_config(window=4096)
    elif fault == "full_block_turned":
        cfg = program_config(layer_types=(SLIDING,) * 5, window=4096)
    else:
        def ungated(x, w, positions, sliding, c, inner=afmoe._qkvg):
            q, k, v, gate = inner(x, w, positions, sliding, c)
            return q, k, v, jnp.ones_like(gate)

        monkeypatch.setattr(afmoe, "_qkvg", ungated)
    with jax.default_matmul_precision("highest"):
        last, _ = prefill(params, cfg, jnp.asarray([a[:40]]),
                          jnp.asarray([40], jnp.int32))
    assert np.abs(np.asarray(last[0]) - want[39]).max() > 1e-2


def test_a_padded_bucket_leaves_what_the_exact_length_leaves(seeded):
    _, params = seeded
    cfg = program_config()
    prompt = _tokens(16, 5)
    with jax.default_matmul_precision("highest"):
        exact = prefill(params, cfg, jnp.asarray([prompt]),
                        jnp.asarray([16], jnp.int32))
        padded = prefill(params, cfg, jnp.asarray([prompt + [9] * 16]),
                         jnp.asarray([16], jnp.int32))
    assert np.abs(np.asarray(exact[0]) - np.asarray(padded[0])).max() < 3e-5
    # full k, v [1 block], window k, v [4 blocks]
    assert [w.shape for w in exact[1]] == [(1, 1, 2, 16, 16)] * 2 + [
        (4, 1, 2, 16, 16)] * 2


def test_the_prefill_in_pieces_is_the_prefill_whole(seeded, monkeypatch):
    _, params = seeded
    cfg = program_config()
    tokens = jnp.asarray([_tokens(48, 8), _tokens(48, 9)])
    lengths = jnp.asarray([48, 31], jnp.int32)
    assert afmoe._pieces(12288) == 3 and afmoe._pieces(10240) == 4
    assert afmoe._pieces(6144) == 2 and afmoe._pieces(4096) == 1
    with jax.default_matmul_precision("highest"):
        whole = prefill(params, cfg, tokens, lengths)[0]
        monkeypatch.setattr(afmoe, "PIECE", 16)
        pieces = prefill(params, cfg, tokens, lengths)[0]
    assert np.abs(np.asarray(whole - pieces)).max() < 3e-5


def test_the_eight_shares_of_the_expert_layer_add_up_to_the_whole():
    """Model-configs guide, section 4: eight chips share a layer. Each
    share's routed part, and the shared expert counted once, add up to the
    uncut reference's whole layer; the program's share is the reference's
    share, in both phases; and the reference's gathered form is its
    every-token form."""
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
        combine = reference.route(x, w, dims)
        plain = sum(combine[:, e:e + 1] * reference.swiglu(
            x, w["wg"][e].T, w["w1"][e].T, w["w2"][e]) for e in range(16))
        plain = plain + reference.swiglu(x, w["shared_gate"], w["shared_up"],
                                         w["shared_down"])
        assert np.abs(np.asarray(plain - whole)).max() < 1e-5
        live = jnp.ones((24,), bool)
        for i in (0, 5):
            held_w, held = share(i)
            want = reference.expert_ffn(x, held_w, dims, held=held)
            cfg = program_config(held=held, n_experts=16)
            got, _ = ffn_decode(x, held_w, live, cfg)
            assert np.abs(np.asarray(got - want)).max() < 1e-5
            got = ffn_prefill(x.reshape(2, 12, 64), held_w,
                              jnp.ones((2, 12), bool), cfg)
            assert np.abs(np.asarray(got.reshape(24, 64) - want)).max() < 1e-5


# -- the kernels, in interpret mode, against their oracles --------------------
@pytest.mark.parametrize("gated", [False, True])
@pytest.mark.parametrize("tile", [None, 128, 256])
def test_the_tiled_expert_kernel_is_experts_reference(gated, tile,
                                                      monkeypatch):
    """An expert's width in tiles (4 and 2 of a width of 512, and the
    whole): decode (experts nobody picked are skipped, steps past the last
    live one move nothing) and prefill (row blocks of 8, so an expert has
    several steps and odd steps walk the tiles backwards). Tiny matrices
    weigh nothing, so the rule is handed the room that gives the tile."""
    rng = np.random.default_rng(3)
    held, F, D, B = 6, 512, 64, 8
    if tile:
        monkeypatch.setattr(moe_experts, "_MATRIX_BYTES",
                            2 * (3 if gated else 2) * tile * D * 4)
        assert width_tile(F, D, 3 if gated else 2, 4) == tile
    w1 = jnp.asarray(rng.standard_normal((held, F, D)) / 8, jnp.float32)
    wg = (jnp.asarray(rng.standard_normal((held, F, D)) / 8, jnp.float32)
          if gated else None)
    w2 = jnp.asarray(rng.standard_normal((held, F, D)) / 22, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
    combine = np.zeros((B, held), np.float32)
    for b in range(B - 1):              # the last row holds no request
        combine[b, rng.choice(4, 2, replace=False)] = rng.random(2)
    combine = jnp.asarray(combine)      # experts 4 and 5: nobody's
    want = experts_reference(x, w1, w2, combine, wg)
    got = decode_experts(x, w1, w2, combine, wg=wg, interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 5e-6
    T, k = 40, 2
    xp = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    picks = jnp.asarray(np.stack([rng.choice(8, k, replace=False)
                                  for _ in range(T)]), jnp.int32)
    weights = jnp.asarray(rng.random((T, k)), jnp.float32).at[-3:].set(0.0)
    dense = np.zeros((T, held), np.float32)
    for t in range(T):
        for j in range(k):
            e = int(picks[t, j]) - 1            # held: experts 1-6 of 8
            if 0 <= e < held:
                dense[t, e] += float(weights[t, j])
    want = experts_reference(xp, w1, w2, jnp.asarray(dense), wg)
    got = prefill_experts(xp, w1, w2, picks, weights, 1, 8, tm=8, wg=wg,
                          interpret=True)
    assert np.abs(np.asarray(got - want)).max() < 5e-6


def test_the_width_tile_is_the_whole_width_where_it_fits():
    """nemotron's and joyai's experts stay one block (their calls are the
    programs they were); Trinity's 3072 x 3072 go in two tiles."""
    assert width_tile(1856, 2688, 2, 2) == 1856
    assert width_tile(768, 2048, 3, 2) == 768
    assert width_tile(3072, 3072, 3, 2) == 1536


@pytest.mark.parametrize("T,window,bq,bkv,budget", [
    (96, 40, 16, 16, None), (96, 40, 32, 16, 0), (200, 64, 32, 32, 0),
    (64, 100, 16, 32, None)])
def test_windowed_flash_is_masked_attention(T, window, bq, bkv, budget,
                                            monkeypatch):
    """Both kernels (K and V resident, and streamed: budget 0), windows
    that are no multiple of a block, a window wider than the sequence."""
    if budget is not None:
        monkeypatch.setattr(flash_module, "VMEM_KV_BUDGET_BYTES", budget)
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(keys[0], (2, T, 4, 32), jnp.float32)
    k = jax.random.normal(keys[1], (2, T, 2, 32), jnp.float32)
    v = jax.random.normal(keys[2], (2, T, 2, 32), jnp.float32)
    got = flash_attention(q, k, v, True, bq, bkv, True, window=window)
    want = attention_reference(q, k, v, causal=True, window=window)
    assert np.abs(np.asarray(got - want)).max() < 5e-6
    plain = attention_reference(q, k, v, causal=True)
    assert (np.abs(np.asarray(plain - want)).max() > 0.1) == (window < T)


@pytest.mark.parametrize("W,fold", [(40, 4), (104, 8)])
def test_the_windowed_read_through_a_ring_is_attention_over_the_window(
        W, fold):
    """Rows below, at and far beyond the window (163 tokens: the ring of 5
    pages wrapped twice), a row that holds no request, every step of a
    block of 16 (so the lower bound crosses a page's edge inside the
    block); what the ring's pages hold beyond a row's length is junk; then
    the flush through the ring, plain and as the kernel. Under a window of
    104 the ring is 9 pages and a fold 8: the walks of 1, 3, 7, 8 and 9
    pages end in last folds computed at 2, 4 and 8 pages."""
    from gofr_tpu.ops.paged_attention import fold_of

    rng = np.random.default_rng(0)
    ps, Hkv, G, dh, B, T, L = 16, 2, 3, 32, 5, 16, 2
    ring = -(-W // ps) + 2
    assert fold_of([np.zeros((L, 1, Hkv, dh, ps), np.float32)] * 2,
                   ring) == fold
    lengths = np.array([0, 7, 40, 97, 163])
    K = rng.standard_normal((L, B, 200, Hkv, dh)).astype(np.float32)
    V = rng.standard_normal((L, B, 200, Hkv, dh)).astype(np.float32)
    k_pool = np.zeros((L, B * ring + 1, Hkv, dh, ps), np.float32)
    v_pool = np.zeros_like(k_pool)
    table = np.zeros((B, ring), np.int32)
    for b in range(1, B):
        table[b] = 1 + b * ring + np.arange(ring)
        pages = -(-lengths[b] // ps)
        for j in range(max(0, pages - ring), pages):
            n = min(ps, lengths[b] - j * ps)
            page = table[b, j % ring]
            k_pool[:, page, :, :, :n] = K[:, b, j * ps:j * ps + n].transpose(
                0, 2, 3, 1)
            v_pool[:, page, :, :, :n] = V[:, b, j * ps:j * ps + n].transpose(
                0, 2, 3, 1)
            k_pool[:, page, :, :, n:] = 99.0
    k_pool, v_pool = jnp.asarray(k_pool), jnp.asarray(v_pool)
    k_tail, v_tail = plane_tail(k_pool, B, T), plane_tail(v_pool, B, T)
    live = table[:, 0] > 0
    worst = 0.0
    # one trace for the block's 16 steps x 2 layers
    read = jax.jit(functools.partial(paged_attention_in_block, window=W,
                                     ring=ring, interpret=True))
    for step in range(T):
        q = rng.standard_normal((B, Hkv * G, dh)).astype(np.float32)
        for layer in range(L):
            at = lengths + step
            out, k_tail, v_tail = read(
                jnp.asarray(q), jnp.asarray(K[layer, np.arange(B), at]),
                jnp.asarray(V[layer, np.arange(B), at]), k_pool, v_pool,
                k_tail, v_tail, jnp.asarray(table),
                jnp.asarray(np.where(live, lengths, 0), jnp.int32),
                jnp.asarray(np.where(live, step + 1, 0), jnp.int32),
                layer=jnp.int32(layer))
            assert np.all(np.asarray(out[0]) == 0.0)
            for b in range(1, B):
                lo = max(0, at[b] - W + 1)
                s = np.einsum("hgd,shd->hgs", q[b].reshape(Hkv, G, dh),
                              K[layer, b, lo:at[b] + 1]) / math.sqrt(dh)
                p = np.exp(s - s.max(-1, keepdims=True))
                want = np.einsum("hgs,shd->hgd", p / p.sum(-1, keepdims=True),
                                 V[layer, b, lo:at[b] + 1])
                worst = max(worst, np.abs(np.asarray(out[b]).reshape(
                    Hkv, G, dh) - want).max())
    assert worst < 5e-6
    for interpret in (None, True):
        flushed, _ = flush_planes(
            (k_pool, v_pool), (k_tail, v_tail), jnp.asarray(table),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(np.where(live, T, 0), jnp.int32), ring=ring,
            interpret=interpret)
        for b in range(1, B):
            for i in range(T):
                at = lengths[b] + i
                got = flushed[:, table[b, (at // ps) % ring], :, :, at % ps]
                assert np.allclose(np.asarray(got), K[:, b, at])


# -- the engine ---------------------------------------------------------------
def test_the_family_refuses_by_name_what_it_cannot_serve():
    """What it refuses (tests/test_families.py asks the engine for each),
    and a decode block that would cross more than one page of a ring."""
    cfg = AfmoeConfig.debug()
    params = afmoe.afmoe_init(cfg, seed=1)
    assert set(REFUSES) == {"prefix_cache", "kv_host_tier", "disagg",
                            "speculative_tokens", "chunk_prefill_tokens",
                            "int8_weights", "kv_dtype", "mesh"}
    with pytest.raises(ValueError, match="over page_size"):
        PagedLLMEngine(params, cfg, n_slots=2, max_seq_len=64, page_size=8,
                       decode_block_size=16)


def test_the_engine_serves_through_both_groups_what_the_reference_puts_first(
        seeded):
    """End to end at tiny widths: greedy tokens through the engine (blocks
    of 4, pages of 8, a ring of 5 pages wrapped by the long request) are
    the reference's first at every position its margin is not a rounding;
    a sequence reserves pages_for(total) in the full group and at most the
    ring in the window group; `/debug/engine` shows the groups."""
    dims, params = seeded
    cfg = program_config(max_seq_len=128)
    engine = PagedLLMEngine(params, cfg, n_slots=3, max_seq_len=128,
                            page_size=PAGE, n_pages=40,
                            prefill_buckets=(16, 32, 64),
                            decode_block_size=4, pipeline_depth=2)
    assert [p.shape for p in engine.pools] == [(1, 40, 2, 16, 8)] * 2 + [
        (4, 3 * 5 + 1, 2, 16, 8)] * 2
    assert engine.allocator is engine.allocators[0]
    engine.start()
    try:
        prompts = {"long": _tokens(50, 21), "short": _tokens(9, 22)}
        new = {"long": 60, "short": 11}
        requests = {name: engine.submit(prompt, max_new_tokens=new[name])
                    for name, prompt in prompts.items()}
        served = {name: r.result(timeout_s=300)
                  for name, r in requests.items()}
        snapshot = engine.paging_snapshot()
    finally:
        engine.stop()
    for name, prompt in prompts.items():
        assert len(served[name]) == new[name]
        want = _reference_logits(params, dims, prompt + served[name])
        rows = want[len(prompt) - 1:len(prompt) - 1 + new[name]]
        gap = rows.max(-1) - rows[np.arange(new[name]), served[name]]
        assert gap.max() < 1e-3, (name, gap.max())
    full, window = snapshot["groups"]
    assert (full["name"], full["layers"], full["window"]) == ("full", 1, None)
    assert (window["name"], window["layers"], window["window"]) == (
        "window", 4, WINDOW)
    assert full["pages"] == 39 and window["pages"] == 15
    # 110 tokens are 14 pages, 20 tokens 3: (14 + 3) / 2 in the full
    # group, (the ring of 5 + 3) / 2 in the window group
    assert full["reserved_per_sequence_mean"] == 8.5
    assert window["reserved_per_sequence_mean"] == 4.0
    assert full["used"] == 0 and window["used"] == 0
    assert window["read"]["folds"] > 0 and full["read"]["folds"] > 0
    for read in (window["read"], full["read"]):
        assert 0 < read["narrowed_folds"] <= read["folds"]
        assert 0 < read["fold_live_share"] <= 1
    model = engine.model_snapshot()
    assert model["family"] == "afmoe" and model["kv_layers"] == 5
    assert model["cache_bytes_per_token"] == 5 * 2 * 2 * 16 * 4
    # 128 tokens: every one in the full block, a ring of 40 in four blocks
    assert model["cache_bytes_per_sequence"] == (128 + 4 * 40) * 2 * 2 * 16 * 4
    assert model["routing"]["rows_per_step"] > 0


def test_a_long_request_holds_only_the_ring_in_the_window_group(seeded):
    """A request of 120 tokens at pages of 8 reserves 15 pages in the full
    group and 5 (the ring) in the window group, and a pool of three rings
    never makes admission wait."""
    _, params = seeded
    cfg = program_config(max_seq_len=128)
    engine = PagedLLMEngine(params, cfg, n_slots=3, max_seq_len=128,
                            page_size=PAGE, n_pages=64,
                            prefill_buckets=(64,), decode_block_size=4)
    request = engine.submit(_tokens(60, 31), max_new_tokens=60)
    assert engine._reserve_pages(request)
    assert len(engine._reservations[request.id]) == 15
    assert {i: len(p) for i, p in
            engine._more_reservations[request.id].items()} == {1: 5}
    assert engine._window_pages_used() == 5
    engine._abort_admission(request)
    assert engine.allocators[1].used_pages == 0
    assert engine.allocator.used_pages == 0
    engine.stop()


@pytest.mark.parametrize("family", ["llama_like", "nemotron_h", "mla_moe"])
def test_the_older_families_hold_what_they_held(family):
    """One group without a window: one allocator, one table, a reservation
    of pages_for(prompt + max_new), pools [kv_layers, pages, heads, width,
    page_size] a plane, and programs that take ONE table."""
    cfg, init = {"llama_like": (LlamaConfig.debug(), llama_init),
                 "nemotron_h": (NemotronHConfig.debug(), nemotron_h_init),
                 "mla_moe": (MlaMoeConfig.debug(), mla_moe_init)}[family]
    engine = PagedLLMEngine(init(cfg, seed=0), cfg, n_slots=2,
                            max_seq_len=64, page_size=16, n_pages=9,
                            prefill_buckets=(32,))
    model = engine.model
    assert len(model.groups) == 1 and model.groups[0].window is None
    assert model.groups[0].layers == model.kv_layers == cfg.kv_layers
    assert engine.allocators == [engine.allocator]
    assert [pool.shape for pool in engine.pools] == [
        (cfg.kv_layers, 9, plane.heads, plane.width, 16)
        for plane in model.planes]
    request = engine.submit(list(range(1, 21)), max_new_tokens=20)
    assert engine._reserve_pages(request)
    assert len(engine._reservations[request.id]) == 3      # 40 tokens
    assert engine._more_reservations == {}
    assert engine._table_widths(4) == [4]
    assert [t.tolist() for t in engine._prefill_tables([request], 2)] == [
        [engine._reservations[request.id][:2]]]
    assert engine._window_pages_used() == 0
    assert engine.paging_snapshot()["groups"][0]["name"] == "pages"
    engine.stop()


def test_the_config_and_the_capacity_plan_count_what_the_cut_holds():
    """ISSUE 33's table at the published widths: 8.64 GB of weights, 4,096
    bytes of K and V a token a block, and bytes a SEQUENCE (every token in
    the full block, a ring of 34 pages in each sliding block), not bytes a
    token times a length."""
    from gofr_tpu.tpu.capacity import (kv_cache_bytes, kv_sequence_bytes,
                                       kv_token_bytes, plan_capacity)

    cfg = AfmoeConfig.trinity_large_preview_ep8()
    model = cfg.paged_model()
    assert [(g.name, g.layers, g.window, g.ring(128)) for g in model.groups
            ] == [("full", 1, None, None), ("window", 4, 4096, 34)]
    assert [cfg.group_of(i) for i in range(5)] == [
        (1, 0), (1, 1), (1, 2), (1, 3), (0, 0)]
    assert kv_token_bytes(cfg) == 5 * 4096
    assert kv_sequence_bytes(cfg, 2048) == 2048 * 5 * 4096
    assert kv_sequence_bytes(cfg, 13312) == (13312 + 4 * 34 * 128) * 4096
    m = cfg.matrix_params()
    assert 62.9e6 < m["attention"] < 63.0e6
    assert m["dense"] == 3 * 3072 * 12288
    held = 5 * m["attention"] + m["dense"] + 4 * m["experts_held"] \
        + 2 * 3072 * 25024
    assert 4.31e9 < held < 4.33e9           # 8.64 GB in bfloat16
    plan = plan_capacity(cfg, 32, 13312, 16 << 30, prefill_buckets=(12288,),
                         params_nbytes=2 * held, clamp=False)
    assert plan.cache_bytes_max == kv_cache_bytes(cfg, 32, 13312) == (
        32 * (13312 + 4 * 4352) * 4096)
    # one group of five blocks would be 8.7 GB and fit no chip with these
    # weights; as two groups the worst case is 4.0 GB
    assert 32 * 13312 * 5 * 4096 > 8.7e9 > 4.1e9 > plan.cache_bytes_max


def test_the_front_door_shows_the_family_s_two_page_groups():
    """examples/llm-server builds the family's engine from MODEL_PRESET
    (tests/test_families.py: every family's) with no prefix cache, which
    the family refuses, and `/debug/engine`'s sections show the page
    groups."""
    import gofr_tpu
    from test_examples import _cfg, _load

    module = _load("llm-server")
    engine = module.build_engine(gofr_tpu.App(config=_cfg(
        TPU_PLATFORM="cpu", MODEL_PRESET="afmoe-debug", WARMUP="false",
        MAX_BATCH="2", MAX_SEQ_LEN="128", PAGE_SIZE="16")))
    try:
        assert engine.model.family == "afmoe" and engine.prefix is None
        request = engine.submit(engine.tokenizer.encode("hello there, afmoe"),
                                max_new_tokens=70)
        assert len(request.result(timeout_s=300)) == 70
        groups = engine.paging_snapshot()["groups"]
        assert [g["name"] for g in groups] == ["full", "window"]
        # ~90 tokens are 6 pages of 16; the ring of a window of 24 is 4
        assert groups[0]["reserved_per_sequence_mean"] >= 5.0
        assert groups[1]["reserved_per_sequence_mean"] == 4.0
    finally:
        engine.stop()
