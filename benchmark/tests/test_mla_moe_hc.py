"""The files of the mla_moe family's configuration with a mixed residual
stream and YaRN (ISSUE 39): the configuration against the catalog row, the
shape facts and the reader by hand at the published widths, the reference's
own departures, and, at --tiny size on the CPU, the adapter's probe of the
mix's mappings, the faults of benchmark/tests/mla_moe_hc_faults.py and a
phi kept in fewer bits, each of which turns `correct` false. (The sound run of the new cell at --tiny size
is tier-1's: tests/test_mla_moe_hc.py.)"""

import argparse
import os
import sys

import jax.numpy as jnp
import pytest

import run as bench_run
from harness import data

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import mla_moe_hc_faults as faults  # noqa: E402 - beside this file

CELL = faults.CELL


def _family(tiny=False):
    config = data.load_cell(CELL, tiny)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    return config, reference, dims, reference.facts(config, dims)


def test_the_configuration_keeps_every_published_width():
    import json
    import os

    config, _, dims, _ = _family()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides")
    with open(catalog) as fp:
        row = next(r for r in map(json.loads, fp)
                   if r["source_url"] == config["source"])
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(config["reduced"])
    assert config["published"] == {k: row["config"][k] for k in differ}
    assert (dims["D"], dims["H"], dims["rq"], dims["r"], dims["nope"],
            dims["rope"], dims["dv"], dims["Fd"], dims["F"], dims["Fs"],
            dims["E"], dims["k"], dims["scale"], dims["theta"]) == (
        3584, 32, 768, 512, 128, 64, 128, 9216, 1024, 1024, 64, 4, 2.0, 1e4)
    assert (dims["n"], dims["iters"], dims["hc_eps"], dims["clamp_lo"],
            dims["clamp_hi"]) == (4, 20, 1e-6, -30.0, 30.0)
    assert (dims["factor"], dims["original"], dims["beta_fast"],
            dims["beta_slow"], dims["mscale_all_dim"]) == (64, 4096, 32, 1, 1)
    # the floors of the model-configs guide, section 4
    assert dims["dense"] == 2 and dims["L"] - dims["dense"] == 18 >= 4
    assert dims["hi"] - dims["lo"] == 8
    assert dims["V"] * 8 >= row["config"]["vocab_size"]
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert config["deployment"]["pipeline_stages"] == 2
    assert len(config["assumed"]) >= 6


def test_the_familys_shape_facts_by_hand():
    config, reference, dims, facts = _family()
    assert facts["vocab"] == 16384
    # 576 values a block, 1,152 bytes in bfloat16, 20 blocks
    assert facts["cache_bytes_per_token"] == 20 * 576 * 2 == 23040
    assert facts["state_bytes_per_slot"] == 0
    # ISSUE 39's arithmetic: 5.4 GB of weights, all but the embedding
    assert 5.25e9 < facts["decode_weight_bytes"] < 5.32e9
    assert reference.mix_phi_bytes(dims) == 24 * 14336 * 4
    kernels = facts["kernels"]
    # the kernels that have least bytes written down are the family's (the
    # mix's two have none: reference/mla_moe_hc.py `facts`)
    assert {k: v["calls_per_step"] for k, v in kernels.items()} == {
        "mla_read": 20, "paged_write": 20, "moe_experts": 18}
    plain = reference.base.facts(config, dims)["decode_weight_bytes"]
    assert facts["decode_weight_bytes"] - plain == 40 * 24 * 14336 * 4
    # 8 held of 64 at 4 a token: 93 rows touch nearly every held expert
    touched = reference.base.experts_touched(dims, 93)
    assert touched == pytest.approx(8 * (1 - (1 - 4 / 64) ** 93))
    assert 7.9 < touched <= 8.0
    assert kernels["mla_read"]["least_bytes"](93, 5e4) == 20 * (
        5e4 * 576 * 2 + 93 * 32 * (576 + 512) * 2)


def _traced_run(kernels):
    _, _, _, facts = _family()
    return {"facts": facts, "device": {"kind": "TPU v5 lite"},
            "trace": {"devices": 1, "t0": 0.0, "t1": 10.0,
                      "decode": {"seconds": 5.0, "calls": 40.0},
                      "kernels": kernels}}


def test_the_reader_reads_the_mixs_kernels():
    share = data.layer_metrics()["mhc_share_pct"]
    assert (share.UNIT, share.BETTER, share.LAYER, share.SOURCE,
            share.MOVES) == ("%", "lower", "kernels", "device_trace",
                             "out_tok_s")
    # 500 steps in the window: 20 calls of the read a step, 40 of each mix
    run = _traced_run({"mla_read": {"seconds": 1.0, "calls": 10000.0},
                       "mhc_pre": {"seconds": 0.3, "calls": 20000.0},
                       "mhc_post": {"seconds": 0.2, "calls": 20000.0}})
    assert share.read(run) == pytest.approx(100 * 0.5 / 5.0)
    # a program without the scopes (the parent), a run without a trace:
    # nothing, and no error
    assert share.read(_traced_run({"mla_read": {"seconds": 1.0,
                                               "calls": 10000.0}})) is None
    assert share.read(_traced_run({})) is None
    assert share.read({**run, "trace": None}) is None
    assert share.read({**run, "trace": {"devices": 0}}) is None
    declared = {m["name"]: m for m in data.benchmark_json()["per_layer"]}
    assert declared[share.NAME]["workloads"] == [CELL]
    assert declared[share.NAME]["better"] == share.BETTER
    # the share of a roofline went after review: no bytes bound these kernels
    assert "mhc_mix_roofline" not in declared


@pytest.mark.parametrize("case", [
    ("sound", "xla"), ("sound", "flash"), ("sinkhorn_5", "xla"),
    ("sinkhorn_5", "flash"), ("mix_bfloat16", "xla"),
    ("mix_bfloat16", "flash")])
def test_the_mix_probe_tells_a_fault_from_rounding(case):
    """What `held` makes of every live engine (families/mla_moe_hc.py
    `mix_off`), at --tiny size: the mappings at the rows of both programs,
    as jax.numpy and as the kernels (interpret mode), lie within a few of
    float32's roundings of the reference's; with five Sinkhorn rounds for
    twenty, or made in bfloat16, they lie two orders over
    `precision.mix_within`."""
    fault, impl = case
    within = data.load_cell(CELL, True)["config"]["precision"]["mix_within"]
    off = faults.probe(fault, 23, tiny=True, attn_impl=impl)
    assert set(off) == {"prefill", "decode"}
    for value in off.values():
        if fault == "sound":
            assert value < within / 20
        else:
            assert value > within * 20


def test_the_limit_of_the_mix_is_the_published_files_own():
    published = data.load_cell(CELL)["config"]["precision"]
    tiny = data.load_cell(CELL, True)["config"]["precision"]
    assert published["mix"] == tiny["mix"] == "float32"
    assert published["mix_within"] == tiny["mix_within"] == 1e-4
    assert "mix_within" in data.load_cell(CELL)["cell"]["check"][
        "limits_from"]["state_not_as_stated"]


def test_the_reference_at_int8_differs_and_yarn_blends():
    config, reference, dims, _ = _family(tiny=True)
    params = reference.make_params(dims, 3, "float32")
    tokens = list(range(3, 35))
    sound = reference.logits(params, dims, tokens)
    lower = reference.logits(params, dims, tokens, lower="int8")
    assert 1e-3 < float(jnp.abs(sound - lower).mean()) < 0.3
    # rope of 8 over 64 original positions at factor 4: the ramp runs over
    # pairs 0-2 of 4; the last pairs turn 4 times slower
    assert reference.yarn_range(dims) == (0, 2)
    plain = 10000.0 ** (-jnp.arange(4) / 4)
    assert jnp.allclose(reference.inv_freq(dims),
                        plain * jnp.asarray([1.0, 0.625, 0.25, 0.25]))
    assert reference.score_scale(dims) == pytest.approx(
        (0.1 * jnp.log(4.0) + 1) ** 2 / 24 ** 0.5)
    with pytest.raises(ValueError, match="yarn"):
        reference.dims_of({**config, "rope_scaling": None})


def test_the_seeded_mix_is_drawn_from_a_key_of_its_own():
    import jax

    _, reference, dims, _ = _family(tiny=True)
    params = reference.make_params(dims, 11, "float32")
    plain = reference.base.make_params(dims, 11, "float32")
    for ours, theirs in zip(params["layers"], plain["layers"]):
        # the family's own leaves are its draw, bit for bit, but W_qb: a
        # quarter of the family's (gain 0.5 for 2)
        assert all(bool((ours[k] == theirs[k]).all()) for k in theirs
                   if k != "wq_b")
        assert bool((ours["wq_b"] == theirs["wq_b"] * 0.25).all())
        assert set(ours) - set(theirs) == set(reference.mix_shapes(dims))
    w = params["layers"][2]
    assert w["ffn_hc_phi"].shape == (24, 256)
    assert w["ffn_hc_phi"].dtype == jnp.float32
    assert float(w["ffn_hc_phi"].std()) == pytest.approx(1 / 16, rel=0.05)
    assert w["attn_hc_scale"].tolist() == [1.0, 1.0, reference.A_RES]
    res = w["attn_hc_bias"][8:].reshape(4, 4)
    assert float(jnp.trace(res)) / 4 > float(res.sum() - jnp.trace(res)) / 12
    again = reference.make_params(dims, 11, "float32")
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))
    assert not bool((params["layers"][1]["attn_hc_phi"]
                     == w["attn_hc_phi"]).all())


def _run(monkeypatch, tmp_path, capsys, seed=3):
    """One --tiny run with the compile caches in a directory of its own:
    another configuration's tiny programs have the same shapes and names,
    and the executor's key holds no config VALUE (PERF.md section 7)."""
    import gofr_tpu.tpu.executor as executor

    monkeypatch.setattr(executor, "enable_compile_cache",
                        lambda override=None: str(tmp_path))
    monkeypatch.chdir(tmp_path)
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=3.0, trace=0,
                              tiny=True, control=None)
    line = bench_run.one_run(args)
    assert '"phase": "check"' in capsys.readouterr().out
    return line


def test_a_phi_in_bfloat16_is_not_as_stated(monkeypatch, tmp_path, capsys):
    """The mix's phi kept in fewer bits than the configuration states for
    it (float32): `state_not_as_stated` counts each of the 6 leaves and
    `correct` is false, whatever the gaps say."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    init = PagedLLMEngine.__init__

    def lower(self, params, cfg, **kw):
        layers = [{k: v.astype(jnp.bfloat16) if k.endswith("_hc_phi") else v
                   for k, v in w.items()} for w in params["layers"]]
        init(self, {**params, "layers": layers}, cfg, **kw)

    monkeypatch.setattr(PagedLLMEngine, "__init__", lower)
    line = _run(monkeypatch, tmp_path, capsys, seed=4)
    # the 6 leaves; the arithmetic over them is sound (`mix_off` hands the
    # reference the leaves the engine holds)
    assert line["compared"]["state_not_as_stated"]["value"] == 6
    assert line["correct"] is False


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_fault_in_the_program_is_not_correct(monkeypatch, tmp_path, capsys,
                                               fault):
    """One slot's page table off by one; 5 Sinkhorn rounds for 20; the
    mappings in bfloat16; the scores without mscale^2: everything else is
    sound and `correct` is false. The two faults of the mix are each
    program's mappings not as stated (`state_not_as_stated` 2), whatever
    the gaps say; the other two leave the state as stated."""
    faults.install(fault, monkeypatch)
    line = _run(monkeypatch, tmp_path, capsys, seed=5)
    assert line["correct"] is False
    assert line["compared"]["state_not_as_stated"]["value"] == (
        2 if fault in faults.MIX else 0)


def test_the_program_has_no_lower_precision_control():
    assert data.family_for(data.load_cell(CELL)["config"]).CONTROLS == ()
    args = argparse.Namespace(workload=CELL, seed=1, seconds=1.0, trace=0,
                              tiny=True, control="int8-kv")
    with pytest.raises(SystemExit, match="offers"):
        bench_run.one_run(args)
