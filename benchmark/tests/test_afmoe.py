"""The afmoe family's files (ISSUE 33): the configuration against the
catalog row, the shape facts by hand at the published widths, the new
reader, the new cell at --tiny size on the CPU, and faults that turn
`correct` false there: a pool of either page group kept in fewer bits than
stated, and the faults of afmoe_faults.py (which runs them at the published
widths on the chip): one slot's page table off by one in EACH group, the
window ignored, a full block rotated, the gate left out, a layer's weights
off."""

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import pytest

import run as bench_run
from harness import data

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import afmoe_faults  # noqa: E402 - beside this file

CELL = "trinity-large-preview-ep8.mixedlen-closed"


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
    assert {k: v for k, v in config["published"].items()
            if k != "layer_types"} == {
        k: row["config"][k] for k in differ if k != "layer_types"}
    assert (dims["D"], dims["H"], dims["Hkv"], dims["dh"], dims["W"],
            dims["Fd"], dims["F"], dims["Fs"], dims["E"], dims["k"],
            dims["scale"], dims["theta"], dims["eps"]) == (
        3072, 48, 8, 128, 4096, 12288, 3072, 3072, 256, 4, 2.448, 10000.0,
        1e-5)
    # one whole period of the published pattern, after the dense block
    assert list(dims["kinds"][1:]) == row["config"]["layer_types"][8:12]
    assert dims["kinds"][0] == row["config"]["layer_types"][0]
    # the floors of the model-configs guide, section 4
    assert dims["L"] - dims["dense"] >= 4 and dims["hi"] - dims["lo"] >= 8
    assert dims["V"] * 8 >= row["config"]["vocab_size"]
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    assert len(config["assumed"]) >= 8


def test_the_familys_shape_facts_by_hand():
    config, reference, dims, facts = _family()
    assert facts["vocab"] == 25024 and facts["window"] == 4096
    # K and V of 8 x 128 a block, 4,096 bytes in bfloat16
    assert reference.token_bytes(dims) == 4096
    assert facts["cache_bytes_per_token"] == 5 * 4096
    assert facts["cache_bytes_per_token_by_group"] == {
        "full": 4096, "window": 4 * 4096}
    assert facts["state_bytes_per_slot"] == 0
    # ISSUE 33's arithmetic: 8.64 GB of weights, all but the embedding's
    # eighth (0.154 GB)
    assert 8.45e9 < facts["decode_weight_bytes"] < 8.52e9
    assert reference.expert_bytes(dims) == 3 * 3072 * 3072 * 2
    kernels = facts["kernels"]
    assert {k: v["calls_per_step"] for k, v in kernels.items()} == {
        "paged_read": 1, "window_read": 4, "paged_write": 2,
        "moe_experts": 4}
    rows, tokens = 31, 31 * 7000
    # the full block: every live token's K and V, queries in, outputs out
    assert kernels["paged_read"]["least_bytes"](rows, tokens) == (
        tokens * 4096 + 2 * rows * 48 * 128 * 2)
    # four sliding blocks: the tokens inside the window, told or at most a
    # window a row
    inside = 31 * 3500
    assert kernels["window_read"]["least_bytes"](rows, tokens, inside) == 4 * (
        inside * 4096 + 2 * rows * 48 * 128 * 2)
    assert kernels["window_read"]["least_bytes"](rows, tokens) == 4 * (
        31 * 4096 * 4096 + 2 * rows * 48 * 128 * 2)
    assert kernels["paged_write"]["least_bytes"](rows, 0) == 5 * rows * 4096
    touched = reference.experts_touched(dims, rows)
    assert touched == pytest.approx(32 * (1 - (1 - 4 / 256) ** 31))
    assert 12.2 < touched < 12.5                # ISSUE 33: 12.4 of 32
    assert kernels["moe_experts"]["least_bytes"](rows, 0) == pytest.approx(
        4 * (touched * 3 * 3072 * 3072 * 2 + rows * 3072 * 6))


def test_the_window_reader_sums_the_tokens_inside_the_window():
    metric = data.layer_metrics()["window_read_roofline"]
    assert (metric.UNIT, metric.LAYER, metric.SOURCE, metric.MOVES) == (
        "%", "kernels", "device_trace", "out_tok_s")
    _, reference, dims, facts = _family()
    records = [
        {"t_first": 0.0, "t_last": 10.0, "prompt_tokens": 999,
         "tokens": list(range(101))},           # context 1000-1100: inside
        {"t_first": 0.0, "t_last": 10.0, "prompt_tokens": 8999,
         "tokens": list(range(101))},           # far past: the window
        {"t_first": None, "t_last": None, "prompt_tokens": 5, "tokens": []}]
    run = {"facts": facts, "result": {"records": records},
           "device": {"kind": "TPU v5 lite"},
           "trace": {"devices": 1, "t0": 4.0, "t1": 6.0, "kernels": {
               "window_read": {"seconds": 0.4, "calls": 4 * 100}}}}
    rows, inside = metric.inside(run, 4096)
    assert rows == pytest.approx(2.0)
    assert inside == pytest.approx(1050.0 + 4096.0)
    least = reference.window_read_bytes(dims, 2.0, inside)
    assert metric.read(run) == pytest.approx(
        100.0 * (least / 819e9) / (0.4 / 100))
    # a program without the scope (the parent), a family without the fact
    assert metric.read({**run, "trace": {**run["trace"], "kernels": {}}}
                       ) is None
    assert metric.read({**run, "facts": {"kernels": {}}}) is None


def test_the_reference_at_int8_differs_and_the_rotation_is_by_halves():
    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    params = reference.make_params(dims, 3, "float32")
    tokens = list(range(3, 67))
    sound = reference.logits(params, dims, tokens)
    lower = reference.logits(params, dims, tokens, lower="int8")
    assert 1e-3 < float(jnp.abs(sound - lower).mean()) < 0.3
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    turned = reference.rope(x, jnp.arange(5), 10000.0)
    # position 0 turns by nothing; a pair (i, i + 4) keeps its length; pair
    # 0 of position 1 turns by one radian
    assert float(jnp.abs(turned[0] - x[0]).max()) < 1e-6
    pairs = lambda v: jnp.stack([v[..., :4], v[..., 4:]], -1)   # noqa: E731
    assert float(jnp.abs(jnp.linalg.norm(pairs(turned), axis=-1)
                         - jnp.linalg.norm(pairs(x), axis=-1)).max()) < 1e-5
    a, b = x[1, 0, 0], x[1, 0, 4]
    assert float(jnp.abs(jnp.asarray([turned[1, 0, 0], turned[1, 0, 4]])
                         - jnp.asarray(
        [a * jnp.cos(1.0) - b * jnp.sin(1.0),
         a * jnp.sin(1.0) + b * jnp.cos(1.0)])).max()) < 1e-6


def test_the_reference_sees_the_window_the_turn_and_the_gate():
    """Each of the block's own features moves the reference's logits: a
    token past the window does not see token 0 on a sliding block, a full
    block is not turned, the gate is applied."""
    config = data.load_cell(CELL, tiny=True)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    w = reference._make_layer(jax.random.PRNGKey(5),
                              reference.layer_shapes(dims, True), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (40, dims["D"]))
    other = x.at[0].set(-x[0])
    with jax.default_matmul_precision("highest"):
        def moved(kind):
            a = reference.attention(x, w, dims, kind)
            b = reference.attention(other, w, dims, kind)
            return jnp.abs(a - b).max(axis=-1)
        sliding, full = moved(reference.SLIDING), moved(reference.FULL)
        # window 24: tokens 24.. no longer see token 0 on a sliding block
        assert float(sliding[24:].max()) == 0.0 and float(sliding[23]) > 0
        assert float(full[24:].min()) > 0
        assert float(jnp.abs(
            reference.attention(x, w, dims, reference.SLIDING)
            - reference.attention(x, w, dims, reference.FULL)).max()) > 1e-2
        # a gate of zeros is sigmoid 0.5 on every value: twice that is the
        # heads' outputs as they are, which the gate changes
        ungated = {**w, "attn_gate": jnp.zeros_like(w["attn_gate"])}
        assert float(jnp.abs(
            2.0 * reference.attention(x, ungated, dims, reference.FULL)
            - reference.attention(x, w, dims, reference.FULL)).max()) > 1e-2


def test_the_seeded_weights_depart_in_two_draws():
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
    assert bool((experts["q_norm"] == reference.QUERY_GAIN).all())
    assert bool((experts["k_norm"] == 1.0).all())
    assert float(experts["wq"].std()) == pytest.approx(1 / D ** 0.5, rel=0.05)
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


@pytest.mark.parametrize("group", [0, 1])
def test_a_pool_of_either_group_in_bfloat16_is_not_as_stated(
        group, monkeypatch, tmp_path, capsys):
    """A group's pools kept in fewer bits than the configuration states
    for pages (float32 at --tiny): `state_not_as_stated` counts them and
    `correct` is false, whatever the gaps say."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    init = PagedLLMEngine._init_device_state

    def lower(self):
        init(self)
        for i in (2 * group, 2 * group + 1):
            self.pools[i] = self.pools[i].astype(jnp.bfloat16)

    monkeypatch.setattr(PagedLLMEngine, "_init_device_state", lower)
    line = _run(monkeypatch, tmp_path, capsys, seed=4)
    assert line["compared"]["state_not_as_stated"]["value"] > 0
    assert line["correct"] is False


@pytest.mark.parametrize("fault", ["table_full", "table_window",
                                   "table_full_scattered"])
def test_one_slots_page_table_off_by_one_in_a_group_is_not_correct(
        fault, monkeypatch, tmp_path, capsys):
    """A fault tied to ONE slot and ONE page group: its rows attend another
    page's K and V in the full block or through the ring of the sliding
    blocks, everything else is sound (afmoe_faults.py, which also runs
    them at the published widths on the chip)."""
    afmoe_faults.install(fault, monkeypatch)
    line = _run(monkeypatch, tmp_path, capsys, seed=5)
    assert line["correct"] is False
    assert line["compared"]["state_not_as_stated"]["value"] == 0


@pytest.mark.parametrize("fault", afmoe_faults.BLOCKS)
def test_a_fault_in_the_block_is_not_correct(fault, monkeypatch, tmp_path,
                                             capsys):
    """The program computing another block than the reference: a sliding
    block attending everything, the full block rotated, the attention
    output not gated, one block's W_v with its columns moved by one."""
    afmoe_faults.install(fault, monkeypatch)
    line = _run(monkeypatch, tmp_path, capsys, seed=6)
    assert line["correct"] is False


def test_the_fault_drivers_sample_is_the_faulty_slots():
    """At chip size the faulty slot is chosen by what it serves (the first
    prompt of at most `short` tokens seen) and the check's sample is drawn
    from that slot's requests, shortest first."""
    from types import SimpleNamespace as NS

    def slot(n):
        return NS(active=n is not None,
                  request=NS(resume_tokens=[0] * (n or 0)))

    engine = NS(slots=[slot(9000), slot(None), slot(3000)])
    who = afmoe_faults.OneSlot(short=2048)
    assert who.of(engine) is None
    engine.slots[1] = slot(1500)
    assert who.of(engine) == 1
    engine.slots[0] = slot(1100)
    assert who.of(engine) == 1                  # chosen once
    assert afmoe_faults.OneSlot(fixed=2).of(engine) == 2
    records = [{"index": i, "prompt_tokens": n, "tokens": [1] * t}
               for i, (n, t) in enumerate([(1500, 40), (9000, 40), (1200, 8),
                                           (1900, 1), (1100, 40), (1300, 40)])]
    records[5]["error"] = "cut"
    slots = {0: 1, 1: 1, 2: 1, 3: 1, 4: 0, 5: 1}
    seen = []
    pick = afmoe_faults.sampled(who, lambda n: n <= 2048, seen)
    got = pick(records, slots, 7, {})
    assert [(r["index"], n) for r, n in got] == [(2, 8), (0, 16)]
    assert seen == [1200, 1500]
    assert afmoe_faults.sampled(afmoe_faults.OneSlot(short=1), lambda n: True,
                                [])(records, slots, 7, {}) == []
    assert set(afmoe_faults.TABLES) | set(afmoe_faults.BLOCKS) == set(
        afmoe_faults.FAULTS)


def test_the_program_has_no_lower_precision_control():
    assert data.family_for(data.load_cell(CELL)["config"]).CONTROLS == ()
    args = argparse.Namespace(workload=CELL, seed=1, seconds=1.0, trace=0,
                              tiny=True, control="int8-kv")
    with pytest.raises(SystemExit, match="offers"):
        bench_run.one_run(args)
