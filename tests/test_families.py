"""The list of families (models/families.py): the seam between the family
modules, the front door from each debug preset, and every refusal by name.
One parametrised test a question; a new family is a case of each from its
row in `families.FAMILIES`."""

import ast
import dataclasses
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from gofr_tpu.models import families  # noqa: E402
from gofr_tpu.tpu.paging import PagedLLMEngine  # noqa: E402

MODELS = os.path.join(ROOT, "gofr_tpu", "models")


# -- the seam -----------------------------------------------------------------
def _imports(path):
    """(module, names) of every import statement of a file, at top level
    or inside a function."""
    with open(path, encoding="utf-8") as fp:
        tree = ast.parse(fp.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield node.module or "", [a.name for a in node.names]
        elif isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, []


def test_no_family_imports_another_and_no_outsider_reads_a_private_name():
    """Parsed, not imported: the modules families.py names import blocks,
    experts, protocol and ops/* only, never one another; and nothing
    outside gofr_tpu/models/ imports an underscore name from one."""
    with open(os.path.join(MODELS, "families.py"), encoding="utf-8") as fp:
        names = next(ast.literal_eval(node.value)
                     for node in ast.parse(fp.read()).body
                     if isinstance(node, ast.Assign)
                     and node.targets[0].id == "FAMILIES")
    assert names == families.FAMILIES and len(names) >= 5

    def family_of(module: str):
        """The family a dotted module path ends in, None for any other."""
        last = module.rsplit(".", 1)[-1]
        return last if last in names and module in (
            last, f"models.{last}", f"gofr_tpu.models.{last}") else None

    crossed = []
    for name in names:
        for module, imported in _imports(
                os.path.join(MODELS, f"{name}.py")):
            others = {family_of(module)} | {
                n for n in imported if n in names and module in (
                    "", "models", "gofr_tpu.models")}
            crossed += [f"{name}.py imports {other}"
                        for other in others - {None, name}]
    assert not crossed, crossed

    private = []
    for top in ("gofr_tpu", "examples", "tools"):
        for folder, _, files in os.walk(os.path.join(ROOT, top)):
            if os.path.abspath(folder) == MODELS:
                continue
            for file in files:
                if not file.endswith(".py"):
                    continue
                path = os.path.join(folder, file)
                private += [
                    f"{os.path.relpath(path, ROOT)}: {module}.{n}"
                    for module, imported in _imports(path)
                    if family_of(module) for n in imported
                    if n.startswith("_")]
    assert not private, private


# -- the front door -----------------------------------------------------------
DEBUG_PRESETS = sorted(name for name in families.presets()
                       if name.endswith("debug"))


@pytest.mark.parametrize("preset", DEBUG_PRESETS)
def test_the_front_door_starts_the_family_from_its_preset(preset):
    """examples/llm-server builds the family's engine from MODEL_PRESET as
    it builds Llama's, refuses by name a variable whose field the preset's
    config does not have, and a weight path the family does not have."""
    import gofr_tpu
    from test_examples import _cfg, _load

    module = _load("llm-server")
    cfg = module.PRESETS[preset]()
    family = families.family_of(cfg)
    settings = dict(TPU_PLATFORM="cpu", MODEL_PRESET=preset, WARMUP="false",
                    MAX_BATCH="2", MAX_SEQ_LEN="128", PAGE_SIZE="16")
    if "kv_dtype" not in {f.name for f in dataclasses.fields(cfg)}:
        with pytest.raises(ValueError, match=f"{preset} has no kv_dtype"):
            module.build_engine(gofr_tpu.App(config=_cfg(
                **settings, KV_DTYPE="int8")))
    if not hasattr(family, "load_checkpoint"):
        with pytest.raises(ValueError, match=f"{cfg.paged_model().family} "
                                             f"family has no checkpoint"):
            module.build_engine(gofr_tpu.App(config=_cfg(
                **settings, WEIGHT_DTYPE="int8")))
    engine = module.build_engine(gofr_tpu.App(config=_cfg(**settings)))
    try:
        assert engine.model.family == cfg.paged_model().family
        assert type(engine.cfg) is type(cfg)
        request = engine.submit(engine.tokenizer.encode("hello"),
                                max_new_tokens=4)
        assert len(request.result(timeout_s=120)) == 4
    finally:
        engine.stop()


# -- the refusals -------------------------------------------------------------
# how an engine is asked for each feature a family may refuse: the
# constructor's arguments, the params' leaves, the config's extra fields
ASKED = {
    "prefix_cache": {"kw": {"prefix_cache": True}},
    "kv_host_tier": {"kw": {"kv_host_tier_bytes": 1 << 20}},
    "disagg": {"kw": {"disagg_role": "decode"}},
    "speculative_tokens": {"kw": {"speculative_tokens": 2}},
    "chunk_prefill_tokens": {"kw": {"chunk_prefill_tokens": 16}},
    "int8_weights": {"params": {"lm_head_s": 0}},
    # no refusing family's config has the field; one that carries it
    "kv_dtype": {"fields": {"kv_dtype": "int8"}},
    "mesh": {"kw": {"mesh": object()}},
}


def _refusals():
    for module in families.modules():
        make = next(iter(module.PRESETS.values()))     # its debug preset
        for feature in sorted(make().paged_model().refuses):
            yield pytest.param(make, feature,
                               id=f"{module.__name__.rsplit('.', 1)[-1]}-"
                                  f"{feature}")


@pytest.mark.parametrize("make, feature", list(_refusals()))
def test_each_feature_a_family_cannot_serve_is_refused_by_name(make, feature):
    """At construction, before a weight is read, with the family's
    reason."""
    cfg, asked = make(), ASKED[feature]
    model = cfg.paged_model()
    if "fields" in asked:
        cfg = type("Carrying", (type(cfg),), asked["fields"])(
            **{f.name: getattr(cfg, f.name)
               for f in dataclasses.fields(cfg)})
    kw = {"prefix_cache": False, **asked.get("kw", {})}
    with pytest.raises(ValueError, match=re.escape(
            f"the {model.family} family refuses {feature}=")) as raised:
        PagedLLMEngine(asked.get("params", {}), cfg, n_slots=2,
                       max_seq_len=64, page_size=16, **kw)
    assert model.refuses[feature] in str(raised.value)
