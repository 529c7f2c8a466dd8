"""The mla_moe family's two departures of Xing4.0-29B-A4B on the serving
path (ISSUE 39), at tiny widths in float32 on the CPU, seeded: the residual
mix's two kernels (ops/mhc.py) in interpret mode against the jax.numpy form
and against benchmark/reference/mla_moe_hc.py's own arithmetic; the
program's prefill and decode (a stream of four mixed copies, YaRN) against
the reference's plain full forward, logits compared; YaRN's numbers at the
published keys; `hc_mult == 1` as the program it was; the eight shares
through H_post; the capacity plan over the stream; `/debug/engine`; the new
cell's `--tiny` rehearsal.

Tolerances. Program and reference compute the same float32 arithmetic in
another order (absorbed against not, grouped experts against every token
through every expert, (x . phi) rsqrt against (x rsqrt) . phi), so logits
of order 1 (largest 4.4) agree to a few float32 roundings a block:
test_mla_moe.py's 2e-5 after a prefill and 5e-5 over decode steps hold
through the mix as well (read here: 5.5e-6 and 1.7e-5). The same comparison
with 5 Sinkhorn rounds for 20 or the mappings in bfloat16 reads 1.4e-2 and
more, without mscale^2 0.7 (`test_the_mix_matters_to_the_logits`)."""

import argparse
import dataclasses
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
from test_mla_moe import Served, _follow, _tokens  # noqa: E402

from gofr_tpu.models import mla_moe  # noqa: E402
from gofr_tpu.models.experts import ffn_decode, ffn_prefill  # noqa: E402
from gofr_tpu.models.mla_moe import (HC_COUNTERS, MlaMoeConfig,  # noqa: E402
                                     YarnScaling, decode_step, mla_moe_init,
                                     prefill)
from gofr_tpu.ops import mhc  # noqa: E402
from gofr_tpu.tpu import capacity  # noqa: E402

CELL = "xing4.0-29b-a4b-ep8.decode-closed"
MIX = dict(n=4, iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6)


def _tiny():
    """(config, reference, dims, the program's config) of the cell's own
    --tiny section: 2 dense blocks and 1 expert block of width 64, four
    copies, YaRN of factor 4 over 64 positions."""
    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    cfg = data.family_for(config).model_config(config, dims)
    return config, reference, dims, dataclasses.replace(cfg, max_seq_len=256)


@pytest.fixture(scope="module")
def seeded():
    _, reference, dims, cfg = _tiny()
    return reference, dims, cfg, reference.make_params(dims, 7, "float32")


def _faults():
    """benchmark/tests/mla_moe_hc_faults.py: the faults the chip run proves
    the cell's limits with."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mla_moe_hc_faults",
        os.path.join(BENCH, "tests", "mla_moe_hc_faults.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reference_logits(reference, params, dims, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.logits(params, dims, tokens))


def _mix_inputs(rows, D, dtype, spread=1.0, seed=0):
    C = mhc.columns(4)
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = (1.5 * jax.random.normal(k[0], (rows, 4 * D), jnp.float32)
         ).astype(dtype)
    phi = jax.random.normal(k[1], (C, 4 * D), jnp.float32) / math.sqrt(4 * D)
    scale = jnp.asarray([1.0, 0.7, spread], jnp.float32)
    bias = 0.5 * jax.random.normal(k[2], (C,), jnp.float32)
    f = jax.random.normal(k[3], (rows, D), jnp.float32).astype(dtype)
    return x, phi, scale, bias, f


# -- ops/mhc.py ---------------------------------------------------------------
@pytest.mark.parametrize("rows,D,dtype", [
    (4, 64, jnp.float32),       # one block of 16 rows, the `highest` product
    (96, 128, jnp.bfloat16),    # a decode step's rows, phi in three parts
    (100, 64, jnp.float32),     # one block: `mhc_post`'s of 112 rows, padded
    (300, 64, jnp.float32),     # two tiles and 44 rows: padded to three
    (130, 128, jnp.bfloat16),
    (160, 128, jnp.bfloat16),   # the llm-server preset's 160 slots
    (192, 64, jnp.bfloat16),    # a prefill of 3 x 64
    (256, 64, jnp.float32)])    # whole tiles: nothing is padded
def test_the_mix_kernels_in_interpret_mode_are_their_oracle(rows, D, dtype):
    """More rows than a tile that are no multiple of it are padded with
    zeros to one (`_whole_tiles`): no call makes a ragged last block."""
    x, phi, scale, bias, f = _mix_inputs(rows, D, dtype)
    want_u, want_h = mhc.mhc_pre_reference(x, phi, scale, bias, **MIX)
    got_u, got_h = mhc.mhc_pre(x, phi, scale, bias, **MIX)
    # the mappings are float32 whatever the stream's dtype: a few roundings
    assert got_h.dtype == jnp.float32 and got_u.dtype == dtype
    assert np.abs(np.asarray(got_h - want_h)).max() < 3e-6
    one_ulp = 2e-6 if dtype == jnp.float32 else 2.0 ** -6
    assert np.abs(np.asarray(got_u, np.float32)
                  - np.asarray(want_u, np.float32)).max() <= one_ulp
    want = mhc.mhc_post_reference(x, f, want_h, n=4)
    got = mhc.mhc_post(x, f, want_h, n=4)
    assert got.shape == x.shape and got.dtype == dtype
    assert np.abs(np.asarray(got, np.float32)
                  - np.asarray(want, np.float32)).max() <= 2 * one_ulp


def test_h_res_is_doubly_stochastic_and_the_clamp_keeps_it_finite():
    x, phi, scale, bias, _ = _mix_inputs(64, 64, jnp.float32, spread=0.5)
    for pre in (mhc.mhc_pre_reference, mhc.mhc_pre):
        _, h = pre(x, phi, scale, bias, **MIX)
        res = np.asarray(h[:, 8:24]).reshape(64, 4, 4)
        # 20 rounds on logits of +-1: rows and columns both sum to 1
        assert np.abs(res.sum(-1) - 1).max() < 1e-5
        assert np.abs(res.sum(-2) - 1).max() < 1e-5
        assert (np.asarray(h[:, :4]) > 0).all() and (np.asarray(h[:, :4]) < 1).all()
        assert (np.asarray(h[:, 4:8]) > 0).all() and (np.asarray(h[:, 4:8]) < 2).all()
        assert not np.asarray(h[:, 24]).any()           # no logit at the clamp
        # logits of +-3,000: without the clamp exp overflows; with it the
        # result is finite, the rows' flag says the clamp was met
        _, wild = pre(x, phi, scale.at[2].set(3000.0), bias, **MIX)
        assert np.isfinite(np.asarray(wild)).all()
        assert np.asarray(wild[:, 24]).all()
        assert np.abs(np.asarray(wild[:, 8:24]).reshape(64, 4, 4).sum(-2)
                      - 1).max() < 1e-5
    assert mhc.columns(4) == 24 and mhc.h_width(4) == 32


def test_the_mix_is_the_references_arithmetic(seeded):
    """ops/mhc.py against benchmark/reference/mla_moe_hc.py, which imports
    nothing of the program: one sublayer around a stand-in F."""
    reference, dims, cfg, params = seeded
    w = params["layers"][1]
    X = jax.random.normal(jax.random.PRNGKey(3), (24, 4, 64), jnp.float32)
    F = lambda u: jnp.tanh(u) * 0.5     # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = reference.sublayer(X, w, "ffn", F, dims)
        pre, post, res = reference.mappings(X, w, "ffn", dims)
        for impl in ("xla", "flash"):
            c = dataclasses.replace(cfg, attn_impl=impl)
            u, h = mla_moe._mix_in(X.reshape(24, 256), w, "ffn", c)
            assert np.abs(np.asarray(h[:, :4] - pre)).max() < 2e-6
            assert np.abs(np.asarray(h[:, 4:8] - post)).max() < 2e-6
            assert np.abs(np.asarray(h[:, 8:24].reshape(24, 4, 4)
                                     - res)).max() < 2e-6
            got = mla_moe._mix_out(X.reshape(24, 256), F(mla_moe.rms_norm(
                u, w["ffn_norm"], c.rms_eps)), h, c)
            assert np.abs(np.asarray(got.reshape(24, 4, 64) - want)
                          ).max() < 1e-5


# -- the program against the reference's full forward --------------------------
def test_prefill_then_32_decode_steps_match_the_full_forward(seeded):
    reference, dims, cfg, params = seeded
    a, b = _tokens(70, 1), _tokens(70, 2)
    want_a = _reference_logits(reference, params, dims, a)
    want_b = _reference_logits(reference, params, dims, b)
    served = Served(cfg, params)
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
    # two live rows of four; one expert block; no logit at the clamp
    assert len(counted) == len(HC_COUNTERS) and counted[0] == 2
    assert counted[1] <= 2 * 2 and counted[4] == 0


def test_the_kernels_serve_what_the_jax_numpy_form_serves(seeded):
    """`attn_impl: "flash"` runs the mix's kernels (and the flash prefill)
    where "xla" runs jax.numpy: the same logits through prefill and a
    decode block."""
    reference, dims, cfg, params = seeded
    a = _tokens(40, 6)
    want = _reference_logits(reference, params, dims, a)
    served = Served(dataclasses.replace(cfg, attn_impl="flash"), params)
    last = served.admit({0: a[:20]}, bucket=32)
    assert np.abs(last[0] - want[19]).max() < 2e-5
    assert _follow(served, want, a, 0, 7) < 5e-5


@pytest.mark.parametrize("fault", ["sinkhorn_5", "mix_bfloat16", "no_mscale",
                                   "plain_rope", "one_copy_read"])
def test_the_mix_matters_to_the_logits(seeded, fault, monkeypatch):
    """The tolerances above are tight enough: five rounds for twenty, the
    mappings in bfloat16, the scores without mscale^2, frequencies not
    blended, or u read from copy 0 alone, each is outside them by two
    orders."""
    install = _faults().install
    reference, dims, cfg, params = seeded
    a = _tokens(50, 5)
    want = _reference_logits(reference, params, dims, a)
    if fault in ("sinkhorn_5", "mix_bfloat16", "no_mscale"):
        install(fault, monkeypatch)
        if fault == "sinkhorn_5":
            cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=5)
    elif fault == "plain_rope":
        cfg = dataclasses.replace(cfg, rope_scaling=YarnScaling(
            1.0, 64, mscale=1.0, mscale_all_dim=1.0))
    else:
        sound = mhc.mhc_pre_reference
        monkeypatch.setattr(mhc, "mhc_pre_reference", lambda x, *a, **kw: (
            x[..., :x.shape[-1] // 4], sound(x, *a, **kw)[1]))
    served = Served(cfg, params)
    last = served.admit({0: a[:20]}, bucket=32)
    off = max(np.abs(last[0] - want[19]).max(),
              _follow(served, want, a, 0, 8))
    assert off > 5e-3


# -- YaRN ---------------------------------------------------------------------
def test_yarns_numbers_at_the_published_keys():
    cfg = MlaMoeConfig.xing4_0_29b_a4b_ep8()
    yarn = cfg.rope_scaling
    assert yarn.correction_range(64, 10000.0) == (10, 23)
    assert yarn.score_scale == pytest.approx(2.0047, abs=1e-4)
    assert yarn.rotation_scale == 1.0
    assert cfg.softmax_scale == pytest.approx(2.004740 / math.sqrt(192),
                                              rel=1e-6)
    plain = 10000.0 ** (-jnp.arange(32, dtype=jnp.float32) / 32)
    blended = np.asarray(yarn.blend(plain, 64, 10000.0))
    # pairs up to `low` turn as they did, pairs from `high` 64 times slower
    assert np.allclose(blended[:11], np.asarray(plain[:11]), rtol=1e-6)
    assert np.allclose(blended[23:], np.asarray(plain[23:]) / 64, rtol=1e-6)
    assert blended[0] == 1.0
    assert blended[31] == pytest.approx(10000.0 ** (-31 / 32) / 64, rel=1e-6)
    halfway = (plain[16] * (1 - 6 / 13) + plain[16] / 64 * (6 / 13))
    assert blended[16] == pytest.approx(float(halfway), rel=1e-6)
    # the reference computes the same numbers from the config's own keys
    config = data.load_cell(CELL)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    assert reference.yarn_range(dims) == (10, 23)
    assert np.allclose(np.asarray(reference.inv_freq(dims)), blended,
                       rtol=1e-6)
    assert reference.score_scale(dims) == pytest.approx(cfg.softmax_scale,
                                                        rel=1e-6)
    with pytest.raises(ValueError, match="cos and sin"):
        MlaMoeConfig(rope_scaling=YarnScaling(64.0, 4096, mscale=0.707,
                                              mscale_all_dim=1.0))


# -- hc_mult == 1 -------------------------------------------------------------
def test_one_copy_without_yarn_is_the_program_it_was():
    """A configuration without the two departures traces the parent's
    program: its prefill and decode step written out here as PR 38 had
    them (x + F(norm(x)), the scale 192^-0.5 where the kernels keep it)
    give the same jaxpr and the same logits bit for bit, and no leaf, no
    counter and no operation of the mix is in them."""
    from gofr_tpu.models.blocks import attended_in_block, head, rms_norm
    from gofr_tpu.models.mla_moe import (COUNTERS, attention_decode,
                                         attention_prefill, layer_shapes)
    from gofr_tpu.ops.paged_attention import holds_request, plane_tail

    cfg = MlaMoeConfig.debug()
    assert cfg.hc_mult == 1 and cfg.rope_scaling is None
    assert cfg.paged_model().counters == COUNTERS
    assert not [k for k in layer_shapes(cfg, False) if "hc" in k]
    assert cfg.softmax_scale == 1.0 / math.sqrt(cfg.qk_dim)
    params = mla_moe_init(cfg, seed=3)
    tokens = jnp.asarray([_tokens(16, 1), _tokens(16, 2)])
    lengths = jnp.asarray([16, 9], jnp.int32)

    def prefill_was(params, tokens, lengths):
        K, T = tokens.shape
        real = jnp.arange(T)[None, :] < lengths[:, None]
        x = params["tok_emb"][tokens]
        latents = []
        for w in params["layers"]:
            out, latent = attention_prefill(
                rms_norm(x, w["attn_norm"], cfg.rms_eps), w, cfg)
            latents.append(latent)
            x = x + out
            x = x + ffn_prefill(rms_norm(x, w["ffn_norm"], cfg.rms_eps), w,
                                real, cfg)
        last = x[jnp.arange(K), lengths - 1]
        return head(last, params, cfg.rms_eps), jnp.stack(latents)

    def decode_was(params, tokens, positions, pool, table, tail, step):
        live = holds_request(table)
        lengths, tail_lens = attended_in_block(table, positions, step)
        x = params["tok_emb"][tokens]
        counted = jnp.zeros((3,), jnp.int32)
        for layer, w in enumerate(params["layers"]):
            out, tail = attention_decode(
                rms_norm(x, w["attn_norm"], cfg.rms_eps), w, positions, pool,
                table, lengths, tail, tail_lens, layer, cfg)
            x = x + out
            out, seen = ffn_decode(rms_norm(x, w["ffn_norm"], cfg.rms_eps),
                                   w, live, cfg)
            counted = counted + seen
            x = x + out
        counters = jnp.concatenate([jnp.sum(live, dtype=jnp.int32)[None],
                                    counted])
        return head(x, params, cfg.rms_eps), tail, counters

    now = jax.make_jaxpr(lambda p, t, n: prefill(p, cfg, t, n))(
        params, tokens, lengths)
    was = jax.make_jaxpr(prefill_was)(params, tokens, lengths)
    assert str(now) == str(was)
    got, want = prefill(params, cfg, tokens, lengths), prefill_was(
        params, tokens, lengths)
    assert all(bool((a == b).all()) for a, b in zip(got, want))
    pool = jnp.zeros((cfg.n_layers, 9, 1, cfg.latent_dim, 16), jnp.float32)
    args = (params, jnp.asarray([3, 4]), jnp.asarray([16, 9]), pool,
            jnp.asarray([[1, 2], [3, 4]]), plane_tail(pool, 2, 4),
            jnp.int32(0))
    now = jax.make_jaxpr(lambda p, *a: decode_step(p, cfg, *a))(*args)
    assert str(now) == str(jax.make_jaxpr(decode_was)(*args))


# -- the chip's share ---------------------------------------------------------
def test_the_eight_shares_add_up_through_h_post(seeded):
    """Model-configs guide, section 4, through the residual mix: eight
    chips share a layer and every one computes the mappings alike, so the
    eight shares' routed parts and the shared expert, each put through
    H_post, with H_res X counted ONCE, are the uncut layer's stream; and
    the program's share is the reference's share, in both phases."""
    reference, dims, cfg, _ = seeded
    base = reference.base
    dims = {**dims, "E": 16, "lo": 0, "hi": 16}
    shapes = reference.layer_shapes(dims, False)
    w = {**base._make_layer(jax.random.PRNGKey(11), base.layer_shapes(
        dims, False), jnp.float32), **reference._make_mix(
            jax.random.PRNGKey(12), dims)}
    assert set(w) == set(shapes)
    X = jax.random.normal(jax.random.PRNGKey(13), (24, 4, 64), jnp.float32)

    def share(i):
        lo, hi = 2 * i, 2 * i + 2
        return {**w, **{name: w[name][lo:hi] for name in ("w1", "wg", "w2")}
                }, (lo, hi)

    def through(held_w, held, shared):
        return reference.sublayer(
            X, held_w, "ffn", lambda x: base.expert_ffn(
                x, held_w, dims, held=held, shared=shared), dims)

    with jax.default_matmul_precision("highest"):
        whole = through(w, (0, 16), True)
        _, _, res = reference.mappings(X, w, "ffn", dims)
        kept = jnp.einsum("tij,tjd->tid", res, X)
        parts = [through(*share(i), shared=(i == 0)) for i in range(8)]
        assert np.abs(np.asarray(sum(parts) - 7 * kept - whole)).max() < 2e-5
        assert np.abs(np.asarray(parts[0] - whole)).max() > 1e-3   # a cut
        live = jnp.ones((24,), bool)
        flat = X.reshape(24, 256)
        for i in (0, 5):
            held_w, held = share(i)
            want = through(held_w, held, True).reshape(24, 256)
            c = dataclasses.replace(cfg, n_experts=16, experts_held=held)
            u, h = mla_moe._mix_in(flat, held_w, "ffn", c)
            normed = mla_moe.rms_norm(u, held_w["ffn_norm"], c.rms_eps)
            got = mla_moe._mix_out(flat, ffn_decode(normed, held_w, live,
                                                    c)[0], h, c)
            assert np.abs(np.asarray(got - want)).max() < 2e-5
            got = mla_moe._mix_out(flat, ffn_prefill(
                normed.reshape(2, 12, 64), held_w, jnp.ones((2, 12), bool),
                c).reshape(24, 64), h, c)
            assert np.abs(np.asarray(got - want)).max() < 2e-5


def test_the_seeded_mix_is_neither_the_identity_nor_uniform(seeded):
    """The draw of benchmark/reference/mla_moe_hc.py `_make_mix` at the
    PUBLISHED width of the stream: H_res's mean off-diagonal mass (a row's
    share outside its own copy) lies between the identity's 0 and the
    uniform matrix's 0.75, by sublayer and over ten of them."""
    reference, dims, _, _ = seeded
    dims = {**dims, "D": 3584}
    X = jax.random.normal(jax.random.PRNGKey(5), (32, 4, 3584), jnp.float32)
    masses = []
    for seed in range(10):
        w = reference._make_mix(jax.random.PRNGKey(seed), dims)
        _, _, res = reference.mappings(X, w, "attn", dims)
        masses.append(float(1 - jnp.mean(jnp.trace(res, axis1=1, axis2=2)) / 4))
    assert all(0.01 < m < 0.7 for m in masses)
    assert 0.1 < sum(masses) / len(masses) < 0.4


# -- the plan, the debug surface, the preset ----------------------------------
def test_the_capacity_plan_counts_the_stream():
    cfg = MlaMoeConfig.xing4_0_29b_a4b_ep8()
    assert cfg.ffn_dim == 4 * 3584 > cfg.dense_dim
    plain = dataclasses.replace(cfg, hc_mult=1)
    assert plain.ffn_dim == cfg.dense_dim == 9216
    # a [K, T, 4, D] stream: the activations of a 16 x 128 prefill grow
    # with it, the latent window does not
    more = (capacity.prefill_temp_bytes(cfg, 16, 128)
            - capacity.prefill_temp_bytes(plain, 16, 128))
    assert more == 4 * 16 * 128 * (4 * 3584 - 9216) * 2
    assert capacity.kv_token_bytes(cfg) == 20 * 576 * 2 == 23040
    # what a token meets: 28.41 M of MLA a block, phi's 2 x 24 x 14,336
    m = cfg.matrix_params()
    assert m["attention"] == 28_409_856 and m["mix"] == 2 * 24 * 14336
    assert m["dense"] == 3 * 3584 * 9216
    joyai = MlaMoeConfig.joyai_llm_flash_ep8()
    assert joyai.matrix_params()["mix"] == 0 and joyai.ffn_dim == 7168


def test_debug_engine_shows_the_residual_path(seeded):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    _, _, cfg, params = seeded
    engine = PagedLLMEngine(params, cfg, n_slots=2, max_seq_len=64,
                            page_size=16, n_pages=9, prefill_buckets=(16,),
                            decode_block_size=4)
    try:
        engine.start()
        out = engine.generate(_tokens(9, 2), max_new_tokens=9,
                              temperature=0.0)
        assert len(out) == 9
        model = engine.model_snapshot()
        assert model["family"] == "mla_moe"
        assert model["residual"] == {"streams": 4, "sinkhorn_iters": 20,
                                     "clamp": [-30.0, 30.0],
                                     "clamped_share": 0.0}
        assert "routing" in model
    finally:
        engine.stop()
    plain = MlaMoeConfig.debug().paged_model().describe({}, 0)
    assert "residual" not in plain


def test_the_debug_preset_builds_and_steps():
    cfg = MlaMoeConfig.debug_hc()
    params = mla_moe_init(cfg, seed=1)
    assert params["layers"][0]["attn_hc_phi"].shape == (24, 256)
    assert params["layers"][0]["attn_hc_phi"].dtype == jnp.float32
    assert params["layers"][3]["ffn_hc_bias"].shape == (24,)
    logits, latent = prefill(params, cfg, jnp.asarray([_tokens(8, 1)]),
                             jnp.asarray([8], jnp.int32))
    assert logits.shape == (1, 512) and np.isfinite(np.asarray(logits)).all()
    assert latent.shape == (4, 1, 1, 40, 8)


def test_the_new_cell_is_correct_at_tiny_size(monkeypatch, tmp_path, capsys):
    """benchmark/run.py --tiny on the new cell: the whole path (the
    llm-server's front door, PagedLLMEngine, the family's files, the
    check) at debug widths. The compile caches are keyed to `tmp_path`:
    the executor's `.jexec` keys hold a program's name and shapes, not the
    config's values, and YaRN changes values only (PERF.md section 7)."""
    import gofr_tpu.tpu.executor as executor
    import run as bench_run

    monkeypatch.setattr(executor, "enable_compile_cache",
                        lambda override=None: str(tmp_path))
    monkeypatch.chdir(tmp_path)
    line = bench_run.one_run(argparse.Namespace(
        workload=CELL, seed=3, seconds=3.0, trace=0, tiny=True, control=None))
    assert '"phase": "check"' in capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
    assert line["compared"]["state_not_as_stated"]["value"] == 0
