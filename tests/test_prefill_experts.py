"""A prefill's grouped experts (ops/moe_experts.py `prefill_experts`): the
layout is sized by the pairs the held experts can expect and walked in as
many pieces as the pairs that did fall there need, so no routing drops a
pair. CPU, the kernel in interpret mode, against `experts_reference`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import moe_experts
from gofr_tpu.ops.moe_experts import (experts_reference, piece_blocks,
                                      prefill_experts, width_tile)

HELD, LO, K, TM, D = 4, 8, 2, 8, 32
# windows: of 48 tokens a piece has fewer rows (6 blocks of 8) than the
# window has pairs and its rows go back to the tokens; of 18 tokens it has
# more (5 blocks for 36 pairs, ragged ends all) and its pairs go back
WINDOWS = (48, 18)
FORMS = {"ungated": (24, False, None), "gated": (24, True, None),
         "gated_two_tiles": (256, True, 128)}
ROUTINGS = ("uniform", "all_held", "none_held", "one_expert", "padded_tail",
            "holds_every_expert")


def routed(routing: str, T: int, seed: int = 0):
    """(picks [T, K], weights [T, K], lo, total) of one routing."""
    rng = np.random.default_rng(seed)
    lo, total = LO, 8 * HELD
    weights = rng.uniform(0.2, 1.0, size=(T, K))
    if routing == "all_held":           # the worst case: several pieces
        picks = np.stack([lo + rng.permutation(HELD)[:K] for _ in range(T)])
    elif routing == "none_held":
        picks = np.stack([rng.permutation(lo)[:K] for _ in range(T)])
    elif routing == "one_expert":       # one held expert takes every pair
        picks = np.stack([[lo + 1, rng.integers(0, lo)] for _ in range(T)])
    else:
        if routing == "holds_every_expert":
            lo, total = 0, HELD
        picks = np.stack([rng.permutation(total)[:K] for _ in range(T)])
        if routing == "padded_tail":
            weights[T - 11:] = 0.0
    return picks.astype(np.int32), weights.astype(np.float32), lo, total


def live_blocks(picks, weights, lo) -> int:
    here = (picks >= lo) & (picks < lo + HELD) & (weights != 0.0)
    sizes = np.bincount(picks[here] - lo, minlength=HELD)
    return int(np.sum(-(-sizes // TM)))


@pytest.fixture
def pieces(monkeypatch):
    """The live steps of every `expert_steps` call a run made, in order,
    and the rows each was handed."""
    seen, real = {"steps": [], "rows": set()}, moe_experts.expert_steps

    def spy(x, w1, w2, weights, experts, rows, wsel, n_steps, tm, **kw):
        seen["rows"].add(x.shape[0])
        jax.debug.callback(lambda n: seen["steps"].append(int(n)), n_steps)
        return real(x, w1, w2, weights, experts, rows, wsel, n_steps, tm,
                    **kw)

    monkeypatch.setattr(moe_experts, "expert_steps", spy)
    return seen


def matrices(form: str, T: int, monkeypatch):
    F, gated, tile = FORMS[form]
    if tile:
        # tiny matrices weigh nothing: the rule is handed the room that
        # gives the tile
        monkeypatch.setattr(moe_experts, "_MATRIX_BYTES",
                            2 * 3 * tile * D * 4)
        assert width_tile(F, D, 3, 4) == tile
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(keys[0], (T, D), jnp.float32)
    w1 = jax.random.normal(keys[1], (HELD, F, D), jnp.float32) / 6
    w2 = jax.random.normal(keys[2], (HELD, F, D), jnp.float32) / (F / 5)
    wg = (jax.random.normal(keys[3], (HELD, F, D), jnp.float32) / 6
          if gated else None)
    return x, w1, w2, wg


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("T", WINDOWS)
def test_every_held_pair_is_computed_whatever_the_routing(T, form, routing,
                                                          monkeypatch):
    x, w1, w2, wg = matrices(form, T, monkeypatch)
    picks, weights, lo, total = routed(routing, T)
    got = jax.jit(lambda x, picks, weights: prefill_experts(
        x, w1, w2, picks, weights, lo, total, tm=TM, wg=wg, interpret=True))(
            x, jnp.asarray(picks), jnp.asarray(weights))
    combine = np.zeros((T, total), np.float32)
    np.add.at(combine, (np.arange(T)[:, None], picks), weights)
    want = experts_reference(x, w1, w2,
                             jnp.asarray(combine[:, lo:lo + HELD]), wg)
    assert np.abs(np.asarray(got - want)).max() < 1e-4
    if routing == "none_held":
        assert not np.asarray(got).any()
    elif routing == "padded_tail":
        assert not np.asarray(got)[T - 11:].any()
    else:
        assert np.abs(np.asarray(want)).max() > 1e-2


@pytest.mark.parametrize("T,routing,trips", [
    (48, "uniform", 1), (48, "all_held", 3), (48, "none_held", 0),
    (48, "one_expert", 1), (48, "padded_tail", 1),
    (48, "holds_every_expert", 1), (18, "uniform", 1), (18, "all_held", 2),
    (18, "none_held", 0), (18, "holds_every_expert", 1)])
def test_a_call_walks_as_many_pieces_as_its_live_blocks_need(T, routing,
                                                             trips, pieces,
                                                             monkeypatch):
    x, w1, w2, _ = matrices("ungated", T, monkeypatch)
    picks, weights, lo, total = routed(routing, T)
    C = piece_blocks(T, K, HELD, total, TM)
    jax.block_until_ready(prefill_experts(
        x, w1, w2, jnp.asarray(picks), jnp.asarray(weights), lo, total,
        tm=TM, interpret=True))
    jax.effects_barrier()
    live = live_blocks(picks, weights, lo)
    assert len(pieces["steps"]) == -(-live // C) == trips
    # every piece but the last is full, and together they are every block
    assert all(n == C for n in pieces["steps"][:-1])
    assert sum(pieces["steps"]) == live
    # one program whatever the routing: each piece is C x tm sorted rows
    assert pieces["rows"] == {C * TM}


@pytest.mark.parametrize("tokens,k,held,total,tm,blocks", [
    (4096, 8, 32, 256, 128, 72),     # joyai: 288 for every pair
    (3072, 8, 32, 256, 128, 62),
    (2048, 8, 32, 256, 128, 52),
    (4096, 4, 32, 256, 128, 52),     # trinity, a piece: 160
    (2048, 4, 32, 256, 128, 42),
    (128, 6, 64, 128, 128, 68),      # nemotron: 70
    (512, 6, 64, 128, 128, 79),      # 88
    (21, 2, 4, 8, 8, 8),
    (4096, 8, 256, 256, 128, 512),   # every expert held: the parent's
    (16, 2, 8, 8, 16, 10),
    (5, 2, 8, 8, 8, 10)])
def test_a_piece_is_sized_by_the_shapes_alone(tokens, k, held, total, tm,
                                              blocks):
    every_pair = -(-tokens * k // tm) + held
    assert piece_blocks(tokens, k, held, total, tm) == blocks <= every_pair
    if held == total:
        assert blocks == every_pair
    else:
        # room for the share uniform routing sends here and a ragged end
        # an expert, and that room is kept
        assert blocks * tm >= tokens * k * held // total + held
