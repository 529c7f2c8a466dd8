"""The nemotron_h family's files (ISSUE 27): the shape facts by hand at the
published widths, the new cell at --tiny size on the CPU, and the state it
is served from held to the precision the configuration states by KIND: a
recurrent state kept in bfloat16 is not as stated, whatever the gaps say."""

import argparse

import jax.numpy as jnp
import pytest

import run as bench_run
from harness import data

CELL = "nemotron-3-nano-30b-a3b-ep2.decode-closed"


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
    assert (dims["D"], dims["Hm"], dims["P"], dims["G"], dims["N"], dims["W"],
            dims["H"], dims["Hkv"], dims["dh"], dims["F"], dims["Fs"],
            dims["E"], dims["k"], dims["scale"]) == (
        2688, 64, 64, 8, 128, 4, 32, 2, 128, 1856, 3712, 128, 6, 2.5)
    assert config["hybrid_override_pattern"] \
        == row["config"]["hybrid_override_pattern"][:16]


def test_the_familys_shape_facts_by_hand():
    config, reference, dims, facts = _family()
    assert reference.counts(dims) == {"mamba": 7, "experts": 7,
                                      "attention": 2}
    assert facts["vocab"] == 65536
    assert facts["cache_bytes_per_token"] == 2 * 2 * 2 * 128 * 2 == 2048
    assert facts["state_bytes_per_slot"] == 7 * (64 * 64 * 128 * 4
                                                 + 6144 * 3 * 2)
    # ISSUE 27's table: 10.57 GB of weights, all but the embedding's gather
    assert 10.1e9 < facts["decode_weight_bytes"] < 10.3e9
    assert reference.expert_bytes(dims) == 2 * 2688 * 1856 * 2
    kernels = facts["kernels"]
    assert {k: v["calls_per_step"] for k, v in kernels.items()} == {
        "paged_read": 2, "paged_write": 2, "ssm_update": 7, "moe_experts": 7}
    # a state read and written a live row a Mamba-2 block, and its operands
    assert kernels["ssm_update"]["least_bytes"](93, 0) == 7 * 93 * (
        2 * 64 * 64 * 128 * 4 + (2 * 4096 + 2 * 1024) * 2 + 64 * 4)
    # the experts a step is expected to touch under uniform routing
    touched = reference.experts_touched(dims, 93)
    assert touched == pytest.approx(64 * (1 - (1 - 6 / 128) ** 93))
    assert 63.2 < touched < 63.3
    assert reference.experts_touched(dims, 1) == pytest.approx(3.0)
    assert kernels["moe_experts"]["least_bytes"](93, 0) == pytest.approx(
        7 * (touched * 2 * 2688 * 1856 * 2 + 93 * 2688 * 6))
    # never more than every held expert's matrices, whatever the rows
    assert kernels["moe_experts"]["least_bytes"](1e6, 0) <= 7 * (
        64 * 2 * 2688 * 1856 * 2 + 1e6 * 2688 * 6)


def test_the_reference_at_int8_differs_and_rotary_is_a_flag():
    """The control rounds every matrix; `rotary=True` is what the family
    leaves out, kept so that a reader sees it."""
    import jax

    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    params = reference.make_params(dims, 3, "float32")
    tokens = list(range(3, 35))
    sound = reference.logits(params, dims, tokens)
    lower = reference.logits(params, dims, tokens, lower="int8")
    # (an int8 router flips picks at these widths, so judge the mean)
    assert 1e-3 < float(jnp.abs(sound - lower).mean()) < 0.3
    x = jax.random.normal(jax.random.PRNGKey(0), (8, dims["D"]))
    w = params["layers"][dims["pattern"].index("*")]
    plain = reference.attention_mixer(x, w, dims)
    rotated = reference.attention_mixer(x, w, dims, rotary=True)
    assert float(jnp.abs(plain - rotated).max()) > 1e-3
    # position 0 rotates by nothing
    assert float(jnp.abs(plain[0] - rotated[0]).max()) < 1e-5


def _run(monkeypatch, tmp_path, capsys, seed=3):
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


def test_a_recurrent_state_in_bfloat16_is_not_as_stated(
        monkeypatch, tmp_path, capsys):
    """The state kept in fewer bits than the configuration states for its
    kind: `state_not_as_stated` counts it and `correct` is false."""
    import gofr_tpu.models.nemotron_h as model

    sound = model.state_shapes

    def lower(cfg, slots):
        (shape, _), tail = sound(cfg, slots)
        return ((shape, jnp.bfloat16), tail)

    monkeypatch.setattr(model, "state_shapes", lower)
    line = _run(monkeypatch, tmp_path, capsys, seed=4)
    assert line["compared"]["state_not_as_stated"]["value"] > 0
    assert line["correct"] is False


def test_one_slots_page_table_off_by_one_is_not_correct(
        monkeypatch, tmp_path, capsys):
    """A fault tied to ONE slot: its rows attend another page's keys in the
    attention blocks (2 of 16 at full size), everything else is sound.
    PERF.md section 2 has the same fault's reading on the chip."""
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


def test_the_seeded_weights_route_evenly_and_every_kind_of_block_counts():
    """What the three departures of `make_params` are for (the reference's
    `_make_layer`): matrices after a never-negative activation add no
    common direction, a routed expert's down matrix is drawn at
    ROUTED_GAIN, queries at QUERY_GAIN."""
    import jax

    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    params = reference.make_params(dims, 11, "float32")
    by_kind = {mark: w for mark, w in zip(dims["pattern"], params["layers"])}
    experts, mamba, attention = by_kind["E"], by_kind["M"], by_kind["*"]
    for matrix, axis in ((experts["w2"], 1), (experts["shared_w2"], 0),
                         (mamba["out_proj"], 0)):
        assert float(jnp.abs(matrix.sum(axis=axis)).max()) < 1e-5
    F, D = experts["w2"].shape[1:]
    assert float(experts["w2"].std()) == pytest.approx(
        reference.ROUTED_GAIN / F ** 0.5, rel=0.05)
    assert float(experts["shared_w2"].std()) == pytest.approx(
        1 / experts["shared_w2"].shape[0] ** 0.5, rel=0.05)
    assert float(attention["wq"].std()) == pytest.approx(
        reference.QUERY_GAIN / D ** 0.5, rel=0.05)
    assert float(attention["wk"].std()) == pytest.approx(1 / D ** 0.5,
                                                         rel=0.05)
    # the same seed gives the same weights
    again = reference.make_params(dims, 11, "float32")
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))


def test_the_program_has_no_lower_precision_control():
    """--control is checked against the cell's family before any weights:
    this family offers the reference's own and no other."""
    assert data.family_for(data.load_cell(CELL)["config"]).CONTROLS == ()
    args = argparse.Namespace(workload=CELL, seed=1, seconds=1.0, trace=0,
                              tiny=True, control="int8-kv")
    with pytest.raises(SystemExit, match="offers"):
        bench_run.one_run(args)
