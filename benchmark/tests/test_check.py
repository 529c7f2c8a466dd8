"""The check, at --tiny size on the CPU: a whole run with the look for a
chip skipped. It passes on the sound program and comes out false with the
timed path broken underneath: an int8 page pool, one layer's weights off,
a page table off by one in every slot or in one, a token altered where it
is produced."""

import argparse

import numpy as np
import pytest

import run as bench_run

CELL = "internlm2-1.8b.decode-closed"


def _run(monkeypatch, tmp_path, capsys, seed=3, control=None, cell=CELL):
    # the executor's store of compiled programs, in a place of this test's
    # own: an artifact is keyed by its outermost function, so a program
    # compiled before a fault was patched in must not be loaded after
    import gofr_tpu.tpu.executor as executor

    monkeypatch.setattr(executor, "enable_compile_cache",
                        lambda override=None: str(tmp_path))
    monkeypatch.chdir(tmp_path)           # incidents/ and the like land here
    args = argparse.Namespace(workload=cell, seed=seed, seconds=3.0, trace=0,
                              tiny=True, control=control)
    line = bench_run.one_run(args)
    out = capsys.readouterr().out
    assert '"phase": "check"' in out
    return line


def test_the_sound_program_is_correct(monkeypatch, tmp_path, capsys):
    line = _run(monkeypatch, tmp_path, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_s", "tpot_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}


def test_an_int8_page_pool_is_not_correct(monkeypatch, tmp_path, capsys):
    """At tiny size the configuration states float32, so the gaps fail too;
    at the cells' size only `state_not_as_stated` holds an int8 pool out
    (PERF.md section 2), which is what the second half pins."""
    assert _run(monkeypatch, tmp_path, capsys,
                control="int8-kv", seed=4)["correct"] is False
    from harness import check

    dims = {"L": 24, "Hkv": 8, "dh": 128}
    pool = {"dtype": "bfloat16", "shape": [24, 769, 8, 128, 128],
            "nbytes": 24 * 769 * 8 * 128 * 128 * 2}
    weights = {"['lm_head']": {"dtype": "bfloat16", "shape": [8, 8], "nbytes": 128},
               "['final_norm']": {"dtype": "float32", "shape": [8], "nbytes": 32}}
    sound = {"pools": {"k_cache": pool, "v_cache": pool},
             "pool_tokens": 769 * 128, "weights": weights}
    assert check.not_as_stated(sound, "bfloat16", dims) == []
    q8 = {**pool, "dtype": "int8", "nbytes": pool["nbytes"] // 2}
    scale = {"dtype": "float32", "shape": [24, 769, 8, 128], "nbytes": 1}
    int8_pool = {**sound, "pools": {"k_cache": q8, "v_cache": q8,
                                    "k_scale": scale, "v_scale": scale}}
    assert len(check.not_as_stated(int8_pool, "bfloat16", dims)) == 5
    w8 = {**sound, "weights": {**weights, "['lm_head']": {
        "dtype": "int8", "shape": [8, 8], "nbytes": 64}}}
    assert check.not_as_stated(w8, "bfloat16", dims) == [
        "weight ['lm_head'] is int8"]


def test_one_layers_weights_off_is_not_correct(monkeypatch, tmp_path, capsys):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    init = PagedLLMEngine.__init__

    def perturbed(self, params, cfg, **kw):
        layers = dict(params["layers"])
        layers["wo"] = layers["wo"].at[1].multiply(1.25)
        init(self, {**params, "layers": layers}, cfg, **kw)

    monkeypatch.setattr(PagedLLMEngine, "__init__", perturbed)
    assert _run(monkeypatch, tmp_path, capsys)["correct"] is False


def test_a_page_table_off_by_one_is_not_correct(monkeypatch, tmp_path, capsys):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    build = PagedLLMEngine._build_table

    def shifted(self):
        table = build(self)
        live = table > 0
        return np.where(live, np.maximum(table - 1, 1), table)

    monkeypatch.setattr(PagedLLMEngine, "_build_table", shifted)
    assert _run(monkeypatch, tmp_path, capsys)["correct"] is False


def test_one_slots_page_table_off_by_one_is_not_correct(
        monkeypatch, tmp_path, capsys):
    """A fault tied to ONE slot: every slot the engine filled has a request
    in the sample, so it cannot miss it."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    build = PagedLLMEngine._build_table

    def shifted(self):
        table = np.array(build(self))
        row = table[2]
        table[2] = np.where(row > 0, np.maximum(row - 1, 1), row)
        return table

    monkeypatch.setattr(PagedLLMEngine, "_build_table", shifted)
    line = _run(monkeypatch, tmp_path, capsys)
    assert line["correct"] is False


def test_the_sample_covers_every_slot_and_the_longest():
    from harness import check

    records = [{"index": i, "done": True, "error": None, "prompt_tokens": 40,
                "tokens": list(range(300, 300 + 50 + i % 7))}
               for i in range(400)]
    slots = {i: i % 96 for i in range(400)}
    sample = {"full": 3, "per_slot": 96, "slot_tokens": 32}
    picked = check.pick(records, slots, 7, sample)
    assert picked == check.pick(records, slots, 7, sample)
    assert picked != check.pick(records, slots, 8, sample)
    full, by_slot = picked[:3], picked[3:]
    assert full[0][0]["index"] == 6 and full[0][1] == 56     # the longest
    assert all(n == len(r["tokens"]) for r, n in full)
    assert {slots[r["index"]] for r, _ in by_slot} == set(range(96))
    assert all(n == 32 for _, n in by_slot)
    indices = [r["index"] for r, _ in picked]
    assert len(set(indices)) == len(indices)
    assert [check.padded_length(n, 1152) for n in (60, 128, 129, 600, 1100)
            ] == [128, 128, 256, 1024, 1152]


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, tmp_path, capsys):
    import gofr_tpu.tpu.sampling as sampling

    sample = sampling.sample_tokens

    def altered(logits, rng, temps, top_k=0):
        tokens, rng = sample(logits, rng, temps, top_k=top_k)
        return (tokens + 1) % logits.shape[-1], rng

    monkeypatch.setattr(sampling, "sample_tokens", altered)
    assert _run(monkeypatch, tmp_path, capsys)["correct"] is False


def test_the_reference_at_int8_fails_the_limits_the_program_passes():
    """The control, at tiny size: at the same positions, the token the
    int8 reference puts first lies further under the float32 reference's
    best than any the limits allow."""
    from harness import check, data, weights

    loaded = data.load_cell(CELL, tiny=True)
    reference = data.reference_for(loaded["config"])
    dims = reference.dims_of(loaded["config"])
    limits = loaded["cell"]["check"]["limits"]
    worst = []
    for seed in (1, 2, 3):
        params = weights.make_params(dims, seed, "float32")
        tokens = [257] + list(np.random.RandomState(seed).randint(259, 512, 95))
        found = check.gaps(reference, params, dims, tokens, 32, 64, 128,
                           control="int8")
        assert max(found["gap"]) >= 0.0
        worst.append(check.summarize([found["control_gap"]]))
    assert min(w["gap_mean"] for w in worst) > 3 * limits["gap_mean"]
    assert min(w["gap_max"] for w in worst) > 3 * limits["gap_max"]


def test_the_reference_agrees_with_the_programs_forward():
    import jax.numpy as jnp

    from gofr_tpu.models.llama import llama_forward_nocache
    from harness import data, serve, weights

    loaded = data.load_cell(CELL, tiny=True)
    reference = data.reference_for(loaded["config"])
    dims = reference.dims_of(loaded["config"])
    params = weights.make_params(dims, 2 ** 31 + 5, "float32")
    tokens = np.random.RandomState(0).randint(259, 512, size=40)
    ours = reference.logits(params, dims, tokens)
    theirs = llama_forward_nocache(
        params, serve.llama_config(loaded["config"], dims),
        jnp.asarray(tokens)[None])[0]
    assert float(jnp.abs(ours - theirs).max()) < 1e-4
