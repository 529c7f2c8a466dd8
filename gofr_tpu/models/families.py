"""The model families the paged engine serves: the one list of them.

A family is one module under gofr_tpu/models/ that states

- a config class whose `paged_model()` answers models/protocol.py,
- `PRESETS`: {the name MODEL_PRESET takes: a constructor of that config},
- `init(cfg, seed)`: seeded weights,
- and, if it has them, `load_checkpoint(cfg, path, weight_dtype, logger)`
  and `init_quantized(cfg, seed)`: a checkpoint loader and an int8 weight
  path (today `llama` alone).

A family module imports models/blocks.py (what every forward shares),
models/experts.py (the held-expert layer), models/protocol.py and ops/*,
never another family (tests/test_families.py holds that). Adding a family
is one module and one name in `FAMILIES`; docs/model-families.md says what
each family is.
"""

from __future__ import annotations

import importlib
from typing import Callable, Dict

FAMILIES = ("llama", "nemotron_h", "mla_moe", "afmoe", "kda_moe",
            "sparse_linear")


def modules():
    return [importlib.import_module(f"{__package__}.{name}")
            for name in FAMILIES]


def presets() -> Dict[str, Callable]:
    """{preset: its config's constructor}, every family's."""
    out: Dict[str, Callable] = {}
    for module in modules():
        taken = out.keys() & module.PRESETS.keys()
        if taken:
            raise ValueError(f"{module.__name__} names presets another "
                             f"family has: {sorted(taken)}")
        out.update(module.PRESETS)
    return out


def family_of(cfg):
    """The module of the family `cfg` (or a class it derives from) is a
    config of."""
    mine = {module.__name__: module for module in modules()}
    for cls in type(cfg).__mro__:
        if cls.__module__ in mine:
            return mine[cls.__module__]
    raise ValueError(f"{type(cfg).__name__} is no family's config")

