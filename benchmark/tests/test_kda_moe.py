"""The kda_moe family's files (ISSUE 41): the configuration against the
catalog, the shape facts by hand at the published widths, the new readers
on a made-up run, the reference's control and its one flag, the new cell at
--tiny size on the CPU, and the five faults its limits are held against
(benchmark/tests/kda_moe_faults.py), each failing by a limit."""

import argparse
import json
import os

import jax.numpy as jnp
import pytest

import kda_moe_faults as faults
import run as bench_run
from harness import data

CELL = "solar-open2-250b-ep8.decode256-closed"


def _family(tiny=False):
    config = data.load_cell(CELL, tiny)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    return config, reference, dims, reference.facts(config, dims)


def test_the_configuration_keeps_every_published_width():
    config, _, dims, _ = _family()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guides")
    with open(catalog) as fp:
        row = next(r for r in map(json.loads, fp)
                   if r["source_url"] == config["source"])
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(config["reduced"]) == [
        "gqa_layers", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in differ}
    assert (dims["D"], dims["H"], dims["Hkv"], dims["dh"], dims["Hk"],
            dims["dk"], dims["W"], dims["r"], dims["F"], dims["Fs"],
            dims["E"], dims["k"], dims["scale"]) == (
        4096, 64, 8, 128, 64, 128, 4, 128, 1280, 1280, 320, 8, 1.0)
    # one whole period of the published pattern, its first
    assert config["gqa_layers"] == [
        l for l in row["config"]["gqa_layers"] if l < 4]
    assert config["deployment"]["chips_sharing_a_layer"] \
        == dims["E"] // (dims["hi"] - dims["lo"]) == 8
    declared = next(c for c in data.benchmark_json()["configs"]
                    if c["name"] == config["name"])
    assert declared["reduced"] == config["reduced"]
    assert declared["source"] == config["source"]


def test_the_familys_shape_facts_by_hand():
    config, reference, dims, facts = _family()
    assert reference.blocks(dims) == {"gqa": 1, "kda": 3}
    assert facts["vocab"] == 24576
    assert facts["cache_bytes_per_token"] == 2 * 1 * 8 * 128 * 2 == 4096
    assert facts["state_bytes_per_slot"] == 3 * (64 * 128 * 128 * 4
                                                 + 3 * 24576 * 2) == 13025280
    # ISSUE 41's count: 6.62 GB of weights, all but the embedding's gather
    assert 6.40e9 < facts["decode_weight_bytes"] < 6.43e9
    assert reference.expert_bytes(dims) == 3 * 4096 * 1280 * 2
    kernels = facts["kernels"]
    assert {k: v["calls_per_step"] for k, v in kernels.items()} == {
        "paged_read": 1, "paged_write": 1, "kda_update": 3, "moe_experts": 4}
    # a matrix state read and written a live row a KDA block, its operands
    assert kernels["kda_update"]["least_bytes"](240, 0) == 3 * 240 * (
        2 * 64 * 128 * 128 * 4 + 5 * 8192 * 2 + 64 * 4)
    assert 6.4e9 < kernels["kda_update"]["least_bytes"](256, 0) < 6.6e9
    # at 240 rows every held expert is touched
    touched = reference.experts_touched(dims, 240)
    assert touched == pytest.approx(40 * (1 - (1 - 8 / 320) ** 240))
    assert 39.9 < touched <= 40
    assert kernels["moe_experts"]["least_bytes"](240, 0) == pytest.approx(
        4 * (touched * 3 * 4096 * 1280 * 2 + 240 * 4096 * 6))
    assert kernels["paged_read"]["least_bytes"](240, 100000) \
        == 2 * 100000 * 8 * 128 * 2 + 2 * 240 * 64 * 128 * 2


def _traced_run(kernels_seen):
    """A run as run.py leaves it, as far as the two readers look: 10 decode
    steps in the capture, 240 rows decoding through it."""
    config, _, _, facts = _family()
    records = [{"index": i, "t_first": 0.0, "t_last": 10.0,
                "prompt_tokens": 100, "tokens": [1] * 500}
               for i in range(240)]
    return {"facts": facts, "device": {"kind": "TPU v5 lite"},
            "result": {"records": records},
            "trace": {"devices": 1, "t0": 4.0, "t1": 6.0,
                      "decode": {"seconds": 0.2, "calls": 1},
                      "kernels": kernels_seen}}


def test_the_two_readers_read_their_kernel_and_nothing_without_it():
    readers = data.layer_metrics()
    share, roofline = readers["kda_share_pct"], readers["kda_update_roofline"]
    run = _traced_run({"paged_read": {"seconds": 0.01, "calls": 10},
                       "kda_update": {"seconds": 0.09, "calls": 30}})
    assert share.read(run) == pytest.approx(45.0)
    least = 3 * 240 * (2 * 64 * 128 * 128 * 4 + 5 * 8192 * 2 + 64 * 4)
    assert roofline.read(run) == pytest.approx(
        100 * (least / 819e9) / 0.009)
    assert roofline.read(run) < 100
    # the parent's program, another family's cell, an untraced run
    bare = _traced_run({"paged_read": {"seconds": 0.01, "calls": 10}})
    assert share.read(bare) is None and roofline.read(bare) is None
    assert share.read({**run, "trace": None}) is None
    assert roofline.read({**run, "trace": None}) is None
    declared = {m["name"]: m for m in data.benchmark_json()["per_layer"]}
    for module in (share, roofline):
        entry = declared[module.NAME]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["layer"],
                entry["source"], entry["moves"]) == (
            module.UNIT, module.BETTER, module.LAYER, module.SOURCE,
            module.MOVES)


def test_the_cell_reports_what_nemotrons_cell_reports_but_its_kernel():
    """ISSUE 41: the cell is appended to every metric nemotron's
    decode-closed cell is in, `ssm_update_roofline` apart."""
    nemotron = "nemotron-3-nano-30b-a3b-ep2.decode-closed"
    bench = data.benchmark_json()
    for metric in bench["per_layer"] + bench["end_to_end"]:
        cells = metric.get("workloads")
        if cells is None or metric["name"] == "ssm_update_roofline":
            continue
        if nemotron in cells:
            assert cells[-1] == CELL, metric["name"]
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    mix = data.load_cell(CELL)["mix"]
    assert (mix["loop"], mix["clients"], mix["grid"], mix["sharing"],
            mix["stream"], mix["temperature"]) == (
        "closed", 256, 256, "none", True, 0.0)
    base = data.load_cell("internlm2-1.8b.decode-closed")["mix"]
    assert all(mix[k] == base[k] for k in ("prompt_tokens", "output_tokens",
                                           "ramp"))


def test_the_reference_at_int8_differs_and_the_factor_2_is_a_flag():
    """The control rounds every matrix; `beta_factor=1` is the plain delta
    rule the family departs from, kept so that a reader sees it."""
    import jax

    _, reference, dims, _ = _family(tiny=True)
    params = reference.make_params(dims, 3, "float32")
    tokens = list(range(3, 35))
    sound = reference.logits(params, dims, tokens)
    lower = reference.logits(params, dims, tokens, lower="int8")
    assert 1e-3 < float(jnp.abs(sound - lower).mean()) < 0.3
    x = jax.random.normal(jax.random.PRNGKey(0), (8, dims["D"]))
    w = params["layers"][1]
    twice = reference.kda_mixer(x, w, dims)
    once = reference.kda_mixer(x, w, dims, beta_factor=1.0)
    assert float(jnp.abs(twice - once).max()) > 1e-3


def test_the_seeded_draw_is_what_the_notes_say():
    """A routed expert's down matrix at ROUTED_GAIN, the GQA block's
    queries at QUERY_GAIN, the decay's constants inside their ranges, b over
    1 in about half the pairs; the same seed gives the same weights."""
    import jax

    _, reference, dims, _ = _family(tiny=True)
    params = reference.make_params(dims, 11, "float32")
    gqa, kda = params["layers"][0], params["layers"][1]
    F, D = gqa["w2"].shape[1:]
    assert float(gqa["w2"].std()) == pytest.approx(
        reference.ROUTED_GAIN / F ** 0.5, rel=0.05)
    assert float(gqa["wq"].std()) == pytest.approx(
        reference.QUERY_GAIN / D ** 0.5, rel=0.05)
    assert float(gqa["wk"].std()) == pytest.approx(1 / D ** 0.5, rel=0.05)
    A = jnp.exp(kda["A_log"])
    assert reference.A_RANGE[0] <= float(A.min()) \
        and float(A.max()) <= reference.A_RANGE[1]
    step = jax.nn.softplus(kda["dt_bias"])
    assert reference.STEP_RANGE[0] * 0.99 <= float(step.min()) \
        and float(step.max()) <= reference.STEP_RANGE[1] * 1.01
    x = jax.random.normal(jax.random.PRNGKey(1), (512, D))
    b = 2 * jax.nn.sigmoid(x @ kda["w_beta"])
    assert 0.4 < float((b > 1).mean()) < 0.6
    again = reference.make_params(dims, 11, "float32")
    assert all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(again)))


def test_the_program_has_no_lower_precision_control():
    assert data.family_for(data.load_cell(CELL)["config"]).CONTROLS == ()
    args = argparse.Namespace(workload=CELL, seed=1, seconds=1.0, trace=0,
                              tiny=True, control="int8-kv")
    with pytest.raises(SystemExit, match="offers"):
        bench_run.one_run(args)


def _run(monkeypatch, tmp_path, capsys, seed=3, control=None):
    import gofr_tpu.tpu.executor as executor

    monkeypatch.setattr(executor, "enable_compile_cache",
                        lambda override=None: str(tmp_path))
    monkeypatch.chdir(tmp_path)
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=3.0, trace=0,
                              tiny=True, control=control)
    line = bench_run.one_run(args)
    assert '"phase": "check"' in capsys.readouterr().out
    return line


def test_the_new_cell_is_correct_at_tiny_size(monkeypatch, tmp_path, capsys):
    line = _run(monkeypatch, tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
    assert line["compared"]["state_not_as_stated"]["value"] == 0


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_a_fault_in_the_program_is_not_correct(fault, monkeypatch, tmp_path,
                                               capsys):
    """Each fault of kda_moe_faults.py fails by a limit: the state held in
    bfloat16 by `state_not_as_stated` alone, the others by the gaps with
    the state as stated."""
    from harness import check

    faults.install(fault, monkeypatch)
    if fault in faults.ONE_SLOT:
        monkeypatch.setattr(check, "pick", faults.sampled(faults.SLOT, []))
    line = _run(monkeypatch, tmp_path, capsys, seed=5)
    assert line["correct"] is False
    held = line["compared"]["state_not_as_stated"]["value"]
    assert (held > 0) == (fault == "state_bfloat16")
