"""oneengine: tpu/engine.py is the loop half of ONE engine.

PR 29 took the dense per-slot engine out of ``gofr_tpu/tpu/engine.py``:
what is left there is the serving loop, and every device array and
compiled program is ``tpu/paging.py``'s. Three findings keep it so:

- ``gofr_tpu/tpu/engine.py`` imports a function from ``gofr_tpu/models/``
  (at top level or inside a function). Configs and helpers are not
  forwards: names in :data:`ALLOWED_MODEL_IMPORTS` pass, classes
  (CamelCase) pass. Scoring's no-cache forward lives in ``tpu/score.py``,
  which this rule does not read; should the loop ever need it, it is
  excepted BY NAME here, not by widening the rule.
- a call ``LLMEngine(...)`` anywhere but ``gofr_tpu/tpu/paging.py``: the
  loop alone holds no pools and fills none of its device hooks, so it is
  constructed only as ``PagedLLMEngine``.
- a method of ``LLMEngine`` that ``PagedLLMEngine`` replaces without
  calling ``super()`` and that has a body: a body the serving path never
  runs is a second engine growing back. What the loop asks of the device
  is a HOOK: a docstring and ``raise NotImplementedError``, filled in
  paging.py. A hook that paging.py does not fill is the same finding.
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Project, _resolve_relative
from ..findings import Finding

RULE = "oneengine"
BIT = 32

ENGINE = "gofr_tpu/tpu/engine.py"
PAGING = "gofr_tpu/tpu/paging.py"
MODELS = "gofr_tpu.models"
# what the loop may take from models/: byte counting for the utilization
# ledger, and scoring's forward should the loop ever call it itself
ALLOWED_MODEL_IMPORTS = ("params_nbytes", "llama_forward_nocache")


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    engine = project.modules.get(ENGINE)
    if engine is not None:
        for node in ast.walk(engine.tree):
            if not isinstance(node, ast.ImportFrom):
                continue
            src = (_resolve_relative(engine.module, node.level, node.module,
                                     False)
                   if node.level else node.module) or ""
            if not (src == MODELS or src.startswith(MODELS + ".")):
                continue
            for alias in node.names:
                name = alias.name
                if name in ALLOWED_MODEL_IMPORTS or name[:1].isupper():
                    continue
                findings.append(Finding(
                    RULE, ENGINE, "<module>", name,
                    "tpu/engine.py imports a model function: step "
                    "forwards and cache makers belong to tpu/paging.py "
                    "(through models/protocol.py); the loop compiles no "
                    "program of its own", node.lineno))
    findings.extend(_hooks(project))
    for relpath in sorted(project.modules):
        if relpath == PAGING:
            continue
        mod = project.modules[relpath]
        for node in ast.walk(mod.tree):
            if (isinstance(node, ast.Call)
                    and ((isinstance(node.func, ast.Name)
                          and node.func.id == "LLMEngine")
                         or (isinstance(node.func, ast.Attribute)
                             and node.func.attr == "LLMEngine"))):
                findings.append(Finding(
                    RULE, relpath, "<module>", "LLMEngine(",
                    "LLMEngine is the loop half of the one engine and "
                    "holds no device state: construct "
                    "tpu.paging.PagedLLMEngine", node.lineno))
    return findings


def _is_hook(node) -> bool:
    """A docstring and `raise NotImplementedError`, nothing else."""
    body = [n for n in node.body
            if not (isinstance(n, ast.Expr)
                    and isinstance(n.value, ast.Constant))]
    return (len(body) == 1 and isinstance(body[0], ast.Raise)
            and "NotImplementedError" in ast.unparse(body[0]))


def _calls_super(node, name: str) -> bool:
    return any(isinstance(n, ast.Attribute) and n.attr == name
               and isinstance(n.value, ast.Call)
               and isinstance(n.value.func, ast.Name)
               and n.value.func.id == "super" for n in ast.walk(node))


def _hooks(project: Project) -> List[Finding]:
    loop = project.classes.get("gofr_tpu.tpu.engine.LLMEngine")
    paged = project.classes.get("gofr_tpu.tpu.paging.PagedLLMEngine")
    if loop is None or paged is None:
        return []
    findings: List[Finding] = []
    for name, method in sorted(loop.methods.items()):
        filled = paged.methods.get(name)
        if _is_hook(method.node):
            if filled is None:
                findings.append(Finding(
                    RULE, ENGINE, method.qualname, name,
                    "a hook of the loop that tpu/paging.py does not fill",
                    method.lineno))
        elif filled is not None and not _calls_super(filled.node, name):
            findings.append(Finding(
                RULE, ENGINE, method.qualname, name,
                "PagedLLMEngine replaces this method without super(): its "
                "body here never serves. Keep a hook (docstring + raise "
                "NotImplementedError) or move the body to where it runs",
                method.lineno))
    return findings
