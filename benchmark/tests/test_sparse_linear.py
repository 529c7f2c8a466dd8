"""The sparse_linear family's files (ISSUE 46): the configuration against
the catalog, the shape facts by hand at the published widths, the new
readers on a made-up run, the reference's control and its two flags, the
seeded draw, the new cell at --tiny size on the CPU, and the faults its
limits are held against (benchmark/tests/sparse_linear_faults.py), each
failing by a limit."""

import argparse
import json
import os

import jax.numpy as jnp
import pytest

import run as bench_run
import sparse_linear_faults as faults
from harness import data

CELL = "minicpm-sala-pp4.longctx-closed"


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
        "mixer_types", "num_hidden_layers"]
    assert config["published"] == {k: row["config"][k] for k in differ}
    assert (dims["D"], dims["H"], dims["Hkv"], dims["dh"], dims["Hl"],
            dims["dl"], dims["F"], dims["V"], dims["base"],
            dims["scale_emb"], dims["scale_depth"]) == (
        4096, 32, 2, 128, 32, 128, 16384, 73448, 256, 12.0, 1.4)
    # eight consecutive published blocks that keep the model's 1 : 3
    held = config["layer_ids"]
    assert held == list(range(9, 17)) == config["deployment"]["blocks"]
    assert config["mixer_types"] == [row["config"]["mixer_types"][l]
                                     for l in held]
    whole = row["config"]["mixer_types"]
    assert (whole.count("minicpm4"), whole.count("lightning-attn")) == (8, 24)
    assert config["mixer_types"].count("minicpm4") == 2
    # the even split's own stages hold 1, 1, 3 and 3 sparse blocks
    assert [whole[8 * s:8 * s + 8].count("minicpm4") for s in range(4)] \
        == [1, 1, 3, 3] == config["deployment"]["even_split_sparse_blocks"]
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    assert config["deployment"]["pipeline_stages"] == 4
    declared = next(c for c in data.benchmark_json()["configs"]
                    if c["name"] == config["name"])
    assert declared["reduced"] == config["reduced"]
    assert declared["source"] == config["source"]


def test_the_familys_shape_facts_by_hand():
    config, reference, dims, facts = _family()
    assert reference.blocks(dims) == {"sparse": 2, "lightning": 6}
    assert facts["vocab"] == 73448
    # K and V of 2 x 128 and 1/16 of a compressed key, two blocks, bfloat16
    assert facts["cache_bytes_per_token"] == 2 * (2 * 512 + 32) == 2112
    assert facts["state_bytes_per_slot"] == 6 * 32 * 128 * 128 * 4 \
        + 2 * 2 * 2 * 128 * 4 == 12587008
    # ISSUE 46's count: 5.64 GB of weights, all but the embedding's gather
    assert 5.03e9 < facts["decode_weight_bytes"] < 5.05e9
    engine = config["engine"]
    pool = engine["n_pages"] * engine["page_size"] \
        * facts["cache_bytes_per_token"]
    state = engine["n_slots"] * facts["state_bytes_per_slot"]
    assert 2.07e9 < pool < 2.08e9 and 0.80e9 < state < 0.81e9
    resident = facts["decode_weight_bytes"] + 4096 * 73448 * 2 + pool + state
    assert 8.4e9 < resident < 8.6e9          # about half of 16 GB
    kernels = facts["kernels"]
    assert {k: v["calls_per_step"] for k, v in kernels.items()} == {
        "lightning_update": 6, "sparse_read": 2, "sparse_select": 2,
        "paged_write": 2}
    # a matrix state read and written a live row a lightning block
    assert kernels["lightning_update"]["least_bytes"](64, 0) == 6 * 64 * (
        2 * 32 * 128 * 128 * 4 + 4 * 4096 * 2)
    assert 1.6e9 < kernels["lightning_update"]["least_bytes"](64, 0) < 1.63e9
    # 64 rows of 11,000 tokens: 64 blocks of 64 tokens a KV head, K and V
    read = kernels["sparse_read"]["least_bytes"](64, 64 * 11000)
    assert read == 2 * (2 * 64 * 4096 * 2 * 128 * 2 + 2 * 64 * 4096 * 2)
    assert 0.53e9 < read < 0.55e9
    # under dense_len a row reads everything it has
    assert kernels["sparse_read"]["least_bytes"](2, 2 * 5000) == 2 * (
        2 * 2 * 5000 * 2 * 128 * 2 + 2 * 2 * 4096 * 2)
    select = kernels["sparse_select"]["least_bytes"](64, 64 * 11000)
    assert select == pytest.approx(2 * (
        64 * 11000 / 16 * 2 * 128 * 2 + 64 * (4096 * 2 + 11000 / 16 * 8)))


def _traced_run(kernels_seen):
    """A run as run.py leaves it, as far as the readers look: 10 decode
    steps in the capture, 60 rows of ~10,250 tokens decoding through it."""
    _, _, _, facts = _family()
    records = [{"index": i, "t_first": 0.0, "t_last": 10.0,
                "prompt_tokens": 10000, "tokens": [1] * 500}
               for i in range(60)]
    return {"facts": facts, "device": {"kind": "TPU v5 lite"},
            "result": {"records": records},
            "trace": {"devices": 1, "t0": 4.0, "t1": 6.0,
                      "decode": {"seconds": 0.1, "calls": 1},
                      "kernels": kernels_seen}}


def test_the_four_readers_read_their_kernels_and_nothing_without_them():
    readers = data.layer_metrics()
    names = ("sparse_read_roofline", "lightning_update_roofline",
             "sparse_select_share_pct", "lightning_share_pct")
    read, update, select, share = (readers[name] for name in names)
    run = _traced_run({"lightning_update": {"seconds": 0.03, "calls": 60},
                       "sparse_read": {"seconds": 0.02, "calls": 20},
                       "sparse_select": {"seconds": 0.005, "calls": 20}})
    assert select.read(run) == pytest.approx(5.0)
    assert share.read(run) == pytest.approx(30.0)
    state = 6 * 60 * (2 * 32 * 128 * 128 * 4 + 4 * 4096 * 2)
    assert update.read(run) == pytest.approx(100 * (state / 819e9) / 0.003)
    chosen = 2 * (2 * 60 * 4096 * 2 * 128 * 2 + 2 * 60 * 4096 * 2)
    assert read.read(run) == pytest.approx(100 * (chosen / 819e9) / 0.002)
    assert read.read(run) < 100 and update.read(run) < 100
    # the parent's program, another family's cell, an untraced run
    bare = _traced_run({"paged_write": {"seconds": 0.01, "calls": 20}})
    for module in (read, update, select, share):
        assert module.read(bare) is None
        assert module.read({**run, "trace": None}) is None
    declared = {m["name"]: m for m in data.benchmark_json()["per_layer"]}
    for module in (read, update, select, share):
        entry = declared[module.NAME]
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["layer"],
                entry["source"], entry["moves"]) == (
            module.UNIT, module.BETTER, module.LAYER, module.SOURCE,
            module.MOVES)
    order = [m["name"] for m in data.benchmark_json()["per_layer"]]
    assert sorted(names, key=order.index) == list(names)
    assert order.index(names[0]) > order.index("kda_share_pct")


def test_the_cell_reports_what_solars_cell_reports_but_its_kernels():
    """ISSUE 46: the cell is appended to every metric solar's cell is in,
    but the kernels it does not launch (the dense paged read, the experts,
    the KDA update)."""
    solar = "solar-open2-250b-ep8.decode256-closed"
    absent = {"paged_read_roofline", "moe_experts_roofline",
              "kda_update_roofline", "kda_share_pct"}
    bench = data.benchmark_json()
    for metric in bench["per_layer"] + bench["end_to_end"]:
        cells = metric.get("workloads")
        if cells is None or solar not in cells:
            continue
        assert (CELL not in cells) == (metric["name"] in absent), \
            metric["name"]
        if CELL in cells:       # after it: a later PR appends after both
            assert cells.index(CELL) > cells.index(solar)
    assert [w["chips"] for w in bench["workloads"] if w["name"] == CELL] \
        == [1]
    loaded = data.load_cell(CELL)
    mix, cell = loaded["mix"], loaded["cell"]
    assert (mix["loop"], mix["clients"], mix["grid"], mix["sharing"],
            mix["stream"], mix["temperature"]) == (
        "closed", 64, 64, "none", True, 0.0)
    assert mix["prompt_tokens"] == {"dist": "uniform", "lo": 8192,
                                    "hi": 12288}
    assert mix["output_tokens"] == {"dist": "uniform", "lo": 1536,
                                    "hi": 3072}
    base = data.load_cell("trinity-large-preview-ep8.mixedlen-closed")["mix"]
    assert {**mix["ramp"], "max_s": 0} == {**base["ramp"], "max_s": 0}
    assert mix["ramp"]["max_s"] == 180
    assert cell["prefill_buckets"] == [10240, 12288]
    assert cell["max_prefill_batch"] == 1
    # every prompt crosses dense_len inside its prefill
    assert mix["prompt_tokens"]["lo"] >= loaded["config"]["sparse_config"][
        "dense_len"] == cell["shortest_context"]
    assert mix["prompt_tokens"]["hi"] + mix["output_tokens"]["hi"] \
        == loaded["config"]["engine"]["max_seq_len"]


def test_the_reference_at_int8_differs_and_the_faults_are_flags():
    """The control rounds every matrix; `far=False` (the forced blocks
    only) and `layer_factor=False` (the decay without its block's factor)
    are the faults as the reference would compute them."""
    _, reference, dims, _ = _family(tiny=True)
    params = reference.make_params(dims, 3, "float32")
    tokens = list(range(3, 131))                  # twice dense_len
    sound = reference.logits(params, dims, tokens)
    lower = reference.logits(params, dims, tokens, lower="int8")
    assert 1e-3 < float(jnp.abs(sound - lower).mean()) < 0.3
    near = reference.logits(params, dims, tokens, far=False)
    flat = reference.logits(params, dims, tokens, layer_factor=False)
    # under dense_len nothing is chosen: the rows before it are the same
    dense = dims["dense_len"]
    assert float(jnp.abs(sound - near)[:dense].max()) == 0.0
    assert float(jnp.abs(sound - near)[dense + 32:].max()) > 1e-3
    assert float(jnp.abs(sound - flat).max()) > 1e-3


def test_the_seeded_draw_is_what_the_notes_say():
    """The sparse blocks' q and k gains inside QK_GAIN, every other gain
    one, the head at hidden_size / dim_model_base times the gain; the same
    seed gives the same weights."""
    import jax

    _, reference, dims, _ = _family(tiny=True)
    params = reference.make_params(dims, 11, "float32")
    sparse, lightning = params["layers"][0], params["layers"][1]
    for name in ("q_norm", "k_norm"):
        assert reference.QK_GAIN[0] <= float(sparse[name].min()) \
            and float(sparse[name].max()) <= reference.QK_GAIN[1]
        assert float(jnp.abs(lightning[name] - 1).max()) == 0.0
    D = dims["D"]
    assert float(sparse["wq"].std()) == pytest.approx(1 / D ** 0.5, rel=0.05)
    assert float(params["lm_head"].std()) == pytest.approx(
        (D / dims["base"]) / D ** 0.5, rel=0.05)
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
    """Each fault of sparse_linear_faults.py fails by a limit: the state
    held in bfloat16 by `state_not_as_stated` alone, the others by the gaps
    with the state as stated."""
    from harness import check

    faults.install(fault, monkeypatch)
    if fault in faults.ONE_SLOT:
        monkeypatch.setattr(check, "pick", faults.sampled(faults.SLOT, []))
    line = _run(monkeypatch, tmp_path, capsys, seed=5)
    assert line["correct"] is False
    held = line["compared"]["state_not_as_stated"]["value"]
    assert (held > 0) == (fault == "state_bfloat16")
