"""Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric sits in a file of its own, and the harness finds it
by the name in BENCHMARK.json by looking into its directory. No table in
code names a cell, a mix or a metric: a later PR adds files.

    benchmark/configs/<configuration>.json
    benchmark/traffic/<mix>.json
    benchmark/workloads/<cell>.json
    benchmark/layer_metrics/<metric>.py
    benchmark/reference/<family>.py
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(folder: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, folder, name + ".json")
    if not os.path.isfile(path):
        have = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, folder))
                      if f.endswith(".json"))
        raise SystemExit(f"no {folder}/{name}.json; there are: {have}")
    with open(path) as fp:
        return json.load(fp)


def _merged(base: dict, tiny: bool) -> dict:
    """The file as it is run. `--tiny` lays the file's own "tiny" section
    over it (nested groups merge one level deep): the CPU rehearsal."""
    out = {k: v for k, v in base.items() if k != "tiny"}
    if tiny:
        for key, value in base.get("tiny", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def load_cell(name: str, tiny: bool = False) -> dict:
    """{"cell", "config", "mix"} of one workload, by its name."""
    cell = _merged(_json("workloads", name), tiny)
    config = _merged(_json("configs", cell["config"]), tiny)
    mix = _merged(_json("traffic", cell["traffic"]), tiny)
    config["engine"] = {**config["engine"], **cell.get("engine", {})}
    return {"cell": cell, "config": config, "mix": mix}


def _module(folder: str, name: str):
    path = os.path.join(BENCH_DIR, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_for(config: dict):
    return _module("reference", config["family"])


def layer_metrics() -> dict:
    """{name: module} of every per-layer metric reader. Each module has
    NAME, UNIT, BETTER, LAYER, SOURCE, MOVES and read(run) -> number or
    None; a reader that finds nothing to read returns None and the metric
    is left out of the line."""
    folder = os.path.join(BENCH_DIR, "layer_metrics")
    out = {}
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".py") and not fname.startswith("_"):
            module = _module("layer_metrics", fname[:-3])
            out[module.NAME] = module
    return out


def benchmark_json() -> dict:
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as fp:
        return json.load(fp)
