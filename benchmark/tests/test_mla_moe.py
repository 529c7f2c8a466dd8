"""The mla_moe family's files (ISSUE 31): the configuration against the
catalog row, the shape facts by hand at the published widths, the new cell
at --tiny size on the CPU, and faults that turn `correct` false there: a
latent plane kept in fewer bits than stated, a page table off by one, a
layer's weights off."""

import argparse

import jax.numpy as jnp
import pytest

import run as bench_run
from harness import data

CELL = "joyai-llm-flash-ep8.longprompt-closed"


def _family():
    config = data.load_cell(CELL)["config"]
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
        2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 768, 256, 8, 2.5, 32e6)
    # the floors of the model-configs guide, section 4
    assert dims["L"] - dims["dense"] >= 4 and dims["hi"] - dims["lo"] >= 8
    assert dims["V"] * 8 >= row["config"]["vocab_size"]
    assert config["deployment"]["chips_sharing_a_layer"] == 8


def test_the_familys_shape_facts_by_hand():
    config, reference, dims, facts = _family()
    assert facts["vocab"] == 16160
    # 576 values a block, 1,152 bytes in bfloat16, 12 blocks
    assert reference.latent_width(dims) == 576
    assert facts["cache_bytes_per_token"] == 12 * 576 * 2 == 13824
    assert facts["state_bytes_per_slot"] == 0
    # ISSUE 31's arithmetic: 4.29 GB of weights, all but the embedding
    assert 4.20e9 < facts["decode_weight_bytes"] < 4.26e9
    assert reference.expert_bytes(dims) == 3 * 2048 * 768 * 2
    kernels = facts["kernels"]
    assert {k: v["calls_per_step"] for k, v in kernels.items()} == {
        "mla_read": 12, "paged_write": 12, "moe_experts": 11}
    # each live token's latent plane once a block, the rows' absorbed
    # queries in (32 heads of 576) and attended latents out (32 of 512)
    assert kernels["mla_read"]["least_bytes"](120, 440000) == 12 * (
        440000 * 576 * 2 + 120 * 32 * (576 + 512) * 2)
    assert kernels["paged_write"]["least_bytes"](120, 0) == 12 * 120 * 576 * 2
    touched = reference.experts_touched(dims, 120)
    assert touched == pytest.approx(32 * (1 - (1 - 8 / 256) ** 120))
    assert 31.2 < touched < 31.4               # 98 % of the 32 held
    assert kernels["moe_experts"]["least_bytes"](120, 0) == pytest.approx(
        11 * (touched * 3 * 2048 * 768 * 2 + 120 * 2048 * 6))
    assert kernels["moe_experts"]["least_bytes"](1e6, 0) <= 11 * (
        32 * 3 * 2048 * 768 * 2 + 1e6 * 2048 * 6)


def test_the_reference_at_int8_differs_and_the_rotation_is_by_pairs():
    import jax

    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    params = reference.make_params(dims, 3, "float32")
    tokens = list(range(3, 35))
    sound = reference.logits(params, dims, tokens)
    lower = reference.logits(params, dims, tokens, lower="int8")
    assert 1e-3 < float(jnp.abs(sound - lower).mean()) < 0.3
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    turned = reference.rope(x, jnp.arange(5), 10000.0)
    # position 0 turns by nothing; a pair keeps its length; pair 0 of
    # position 1 turns by one radian
    assert float(jnp.abs(turned[0] - x[0]).max()) < 1e-6
    pairs = lambda v: v.reshape(5, 3, 4, 2)     # noqa: E731
    assert float(jnp.abs(jnp.linalg.norm(pairs(turned), axis=-1)
                         - jnp.linalg.norm(pairs(x), axis=-1)).max()) < 1e-5
    a, b = pairs(x)[1, 0, 0]
    assert float(jnp.abs(pairs(turned)[1, 0, 0] - jnp.asarray(
        [a * jnp.cos(1.0) - b * jnp.sin(1.0),
         a * jnp.sin(1.0) + b * jnp.cos(1.0)])).max()) < 1e-6


def test_the_seeded_weights_depart_in_two_draws():
    import jax

    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    params = reference.make_params(dims, 11, "float32")
    dense, experts = params["layers"][0], params["layers"][1]
    assert "w_gate" in dense and "router" not in dense
    F, D = experts["w2"].shape[1:]
    assert float(experts["w2"].std()) == pytest.approx(
        reference.ROUTED_GAIN / F ** 0.5, rel=0.05)
    assert float(experts["w1"].std()) == pytest.approx(1 / D ** 0.5, rel=0.05)
    assert float(experts["wq_b"].std()) == pytest.approx(
        reference.QUERY_GAIN / experts["wq_b"].shape[0] ** 0.5, rel=0.05)
    assert float(experts["wkv_b"].std()) == pytest.approx(
        1 / experts["wkv_b"].shape[0] ** 0.5, rel=0.05)
    again = reference.make_params(dims, 11, "float32")
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))


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


def test_the_new_cell_is_correct_at_tiny_size(monkeypatch, tmp_path, capsys):
    line = _run(monkeypatch, tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
    assert line["compared"]["state_not_as_stated"]["value"] == 0


def test_a_latent_plane_in_bfloat16_is_not_as_stated(
        monkeypatch, tmp_path, capsys):
    """The pool kept in fewer bits than the configuration states for
    pages (float32 at --tiny): `state_not_as_stated` counts it and
    `correct` is false, whatever the gaps say."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    init = PagedLLMEngine._init_device_state

    def lower(self):
        init(self)
        self.pools = [pool.astype(jnp.bfloat16) for pool in self.pools]

    monkeypatch.setattr(PagedLLMEngine, "_init_device_state", lower)
    line = _run(monkeypatch, tmp_path, capsys, seed=4)
    assert line["compared"]["state_not_as_stated"]["value"] > 0
    assert line["correct"] is False


def test_one_slots_page_table_off_by_one_is_not_correct(
        monkeypatch, tmp_path, capsys):
    """A fault tied to ONE slot: its rows attend another page's latents,
    everything else is sound."""
    import numpy as np
    from gofr_tpu.tpu.paging import PagedLLMEngine

    build = PagedLLMEngine._build_table

    def shifted(self):
        table = np.array(build(self))
        row = table[2]
        table[2] = np.where(row > 0, np.maximum(row - 1, 1), row)
        return table

    monkeypatch.setattr(PagedLLMEngine, "_build_table", shifted)
    line = _run(monkeypatch, tmp_path, capsys, seed=5)
    assert line["correct"] is False
    assert line["compared"]["state_not_as_stated"]["value"] == 0


def test_one_layers_weights_off_is_not_correct(monkeypatch, tmp_path, capsys):
    """The program served from another matrix than the reference is given:
    one block's W_kvb scaled by 1.5 on the program's side only."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    init = PagedLLMEngine.__init__

    def off(self, params, cfg, **kw):
        layers = list(params["layers"])
        layers[1] = {**layers[1], "wkv_b": layers[1]["wkv_b"] * 1.5}
        init(self, {**params, "layers": layers}, cfg, **kw)

    monkeypatch.setattr(PagedLLMEngine, "__init__", off)
    line = _run(monkeypatch, tmp_path, capsys, seed=6)
    assert line["correct"] is False


def test_the_program_has_no_lower_precision_control():
    assert data.family_for(data.load_cell(CELL)["config"]).CONTROLS == ()
    args = argparse.Namespace(workload=CELL, seed=1, seconds=1.0, trace=0,
                              tiny=True, control="int8-kv")
    with pytest.raises(SystemExit, match="offers"):
        bench_run.one_run(args)
