"""Driven by data: BENCHMARK.json and the files under benchmark/ say the
same, and a new cell, mix and per-layer metric are added as new files, in a
copy, without touching a file that is there."""

import json
import os
import shutil
import subprocess
import sys

from harness import data

ROOT = os.path.dirname(data.BENCH_DIR)


def test_benchmark_json_and_the_files_agree():
    bench = data.benchmark_json()
    names = {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        loaded = data.load_cell(cell["name"])
        for key in ("name", "config", "traffic", "chips", "why"):
            assert loaded["cell"][key] == cell[key], (cell["name"], key)
        assert cell["config"] in names
        assert loaded["config"]["deployment"]["chips"] == cell["chips"]
        assert len(cell["why"]) <= 200
    for config in bench["configs"]:
        with open(os.path.join(ROOT, config["file"])) as fp:
            held = json.load(fp)
        assert held["source"] == config["source"]
        assert held["reduced"] == config["reduced"]
    ends = {m["name"]: m for m in bench["end_to_end"]}
    from harness import stats
    assert all(stats.UNITS[n] == m["unit"] for n, m in ends.items())
    modules = data.layer_metrics()
    for metric in bench["per_layer"]:
        module = modules[metric["name"]]
        for key, attr in (("unit", "UNIT"), ("better", "BETTER"),
                          ("layer", "LAYER"), ("source", "SOURCE"),
                          ("moves", "MOVES")):
            assert metric[key] == getattr(module, attr), (metric["name"], key)
        assert metric["moves"] in ends
        for name in metric["workloads"]:
            cell = data.load_cell(name)
            assert metric["moves"] in cell["cell"]["end_to_end"]
            loop = getattr(module, "LOOP", None)
            assert loop in (None, cell["mix"]["loop"])
    for cell in bench["workloads"]:
        listed = set(data.load_cell(cell["name"])["cell"]["end_to_end"])
        wanted = {n for n, m in ends.items()
                  if cell["name"] in m.get("workloads", [cell["name"]])}
        assert listed == wanted, cell["name"]


def test_a_cell_a_mix_and_a_metric_are_added_as_new_files(tmp_path):
    copy = tmp_path / "benchmark"
    shutil.copytree(data.BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {str(p.relative_to(copy)): p.read_bytes()
              for p in copy.rglob("*") if p.is_file()}
    (copy / "traffic" / "short-burst.json").write_text(json.dumps({
        "name": "short-burst", "who": "a test", "loop": "closed", "clients": 3,
        "grid": 6, "prompt_tokens": {"dist": "uniform", "lo": 8, "hi": 12},
        "output_tokens": {"dist": "fixed", "value": 6},
        "ramp": {"max_s": 60}}))
    cell = json.loads((copy / "workloads" /
                       "internlm2-1.8b.decode-closed.json").read_text())
    cell.update(name="internlm2-1.8b.short-burst", traffic="short-burst")
    (copy / "workloads" / "internlm2-1.8b.short-burst.json").write_text(
        json.dumps(cell))
    (copy / "layer_metrics" / "ledger_records.py").write_text(
        'NAME, UNIT, BETTER = "ledger_records", "count", "higher"\n'
        'LAYER, SOURCE, MOVES = "engine loop", "program_counter", "out_tok_s"\n'
        '\n\ndef read(run):\n    return float(len(run["steps"]))\n')
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
    done = subprocess.run(
        [sys.executable, str(copy / "run.py"), "--workload",
         "internlm2-1.8b.short-burst", "--seed", "9", "--seconds", "2",
         "--trace", "1", "--tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["ledger_records"]["value"] > 0
    assert "decode_rows_mean" in line["metrics"]
    assert "gen_lag_p95_ms" not in line["metrics"]        # an open-loop metric
    after = {str(p.relative_to(copy)): p.read_bytes()
             for p in copy.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "layer_metrics/ledger_records.py", "traffic/short-burst.json",
        "workloads/internlm2-1.8b.short-burst.json"]


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: another exit code than 0, and no result."""
    shutil.copytree(data.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "internlm2-1.8b.decode-closed", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout


def test_benchmark_json_keeps_the_contracts_form():
    import re

    bench = data.benchmark_json()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert 1 <= bench["run_seconds"] <= 51
    for config in bench["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert name.match(config["name"]) and len(config["why"]) <= 200
        assert config["file"].startswith("benchmark/")
    for cell in bench["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(cell["name"]) and name.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
    four = sum(1 for c in bench["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)
    seen = set()
    for metric in bench["end_to_end"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "bound", "source"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for metric in bench["per_layer"]:
        assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                               "source", "layer", "moves"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert 1 <= len(metric["layer"]) <= 200
    for metric in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(metric["name"]) and unit.match(metric["unit"])
        assert metric["better"] in ("lower", "higher")
        assert metric["name"] not in seen
        seen.add(metric["name"])
    assert "setup_s" in seen
    for folder, _, files in os.walk(data.BENCH_DIR):
        for fname in files:
            if "__pycache__" not in folder:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", fname), fname
