"""hotloop: no host synchronization in the engine's hot loop.

PR 6 tore the host work out of the decode loop (async D2H at dispatch,
vectorized demux, off-loop finishing); this pass keeps it out. Roots are
the engine-loop entry points — every function named ``_loop`` or
matching ``_dispatch_*`` / ``_sync_*`` defined under ``gofr_tpu/tpu/`` —
and the checked set is everything reachable from them through the call
graph. Inside that set we flag:

- ``x.item()``                   — a device scalar pull is a full sync
- ``np.asarray(x)`` / ``np.array(x)`` where ``x`` is device-tainted —
  blocks until the buffer lands on host (host-side list conversions are
  fine and common; the taint gate keeps them out)
- ``jax.device_get(x)``, ``jax.block_until_ready(x)``
- ``x.block_until_ready()``
- ``float(x)`` / ``int(x)`` / ``bool(x)`` where ``x`` was assigned from a
  ``jax``/``jnp`` call or an ``executor.run(...)`` in the same function —
  the implicit ``__float__`` on a DeviceArray syncs just as hard as
  ``.item()``

A second check holds the program lookups (PR 36): inside a method
decorated ``@program_lookup`` (`tpu/paging.py`'s ``_<kind>_program``),
a call rooted at ``jax`` / ``jnp`` / ``self._jnp`` (or a local name bound
to it) is a finding unless it only DESCRIBES an array
(``jax.ShapeDtypeStruct``, ``jnp.dtype``): ``jnp.zeros`` there runs an
executable on the device's stream and returned when everything queued
before it had run, 70-545 ms at a time, to tell the executor a shape.

The loop necessarily syncs SOMEWHERE — the designated sync points
(`_sync_oldest`'s completion check, the hand-off fetch) carry
``# lint: hotloop-ok <reason>`` pragmas; everything else is a
regression. Over-approximation note: reachability follows subclass
overrides, so a finding in a paged override reached only from the dense
loop is still reported — that is the point.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import List

from ..core import ModuleInfo, Project
from ..findings import Finding

RULE = "hotloop"
BIT = 1

ROOT_PATTERNS = ("_loop", "_dispatch_*", "_sync_*")
ROOT_DIR = "gofr_tpu/tpu/"

# dotted roots (post-alias-resolution) that produce device values
_DEVICE_ROOTS = ("jax", "jax.numpy")
_NUMPY_ROOTS = ("numpy",)
_SYNC_JAX_FNS = ("device_get", "block_until_ready")
_NUMPY_SYNC_FNS = ("asarray", "array")
_COERCIONS = ("float", "int", "bool")


# a lookup may describe an array; it may not make one
LOOKUP_DECORATOR = "program_lookup"
_DESCRIBES = ("ShapeDtypeStruct", "dtype")


def is_root(fn_name: str, relpath: str) -> bool:
    return relpath.startswith(ROOT_DIR) and any(
        fnmatch.fnmatchcase(fn_name, pat) for pat in ROOT_PATTERNS)


def _is_device_root(root) -> bool:
    return root in _DEVICE_ROOTS or (root or "").startswith("jax.")


def _device_tainted_names(project: Project, mod: ModuleInfo,
                          fn_node) -> set:
    """Names assigned (directly) from a device-producing call within this
    function: `x = jnp.argmax(...)`, `out = self.executor.run(...)`."""
    tainted = set()
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Assign):
            continue
        val = node.value
        if not isinstance(val, ast.Call):
            continue
        produced = False
        fn = val.func
        root = project.alias_root(mod, fn)
        if _is_device_root(root):
            produced = True
        elif isinstance(fn, ast.Attribute) and fn.attr == "run":
            owner = fn.value
            owner_name = owner.attr if isinstance(owner, ast.Attribute) \
                else getattr(owner, "id", "")
            if "executor" in owner_name:
                produced = True
        if produced:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    tainted.add(tgt.id)
                elif isinstance(tgt, ast.Tuple):
                    tainted.update(e.id for e in tgt.elts
                                   if isinstance(e, ast.Name))
    return tainted


def _device_arg(project: Project, mod: ModuleInfo, arg: ast.expr,
                tainted: set) -> bool:
    """Is this np.asarray/np.array argument a device value? Tainted name,
    slice of a tainted name, or a direct jax/jnp-producing call. Host
    list/tuple conversions — the overwhelmingly common case — stay out."""
    if isinstance(arg, ast.Name):
        return arg.id in tainted
    if isinstance(arg, ast.Subscript):
        return isinstance(arg.value, ast.Name) and arg.value.id in tainted
    if isinstance(arg, ast.Call):
        return _is_device_root(project.alias_root(mod, arg.func))
    return False


def _is_lookup(fn_node) -> bool:
    for dec in getattr(fn_node, "decorator_list", ()):
        name = dec.attr if isinstance(dec, ast.Attribute) \
            else getattr(dec, "id", None)
        if name == LOOKUP_DECORATOR:
            return True
    return False


def _is_engine_jnp(node: ast.expr) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "_jnp"
            and isinstance(node.value, ast.Name) and node.value.id == "self")


def _through_engine_jnp(node: ast.expr) -> bool:
    """`self._jnp.zeros`, `self._jnp.linalg.norm`: some link of the
    attribute chain is the engine's `self._jnp`."""
    while isinstance(node, ast.Attribute):
        if _is_engine_jnp(node):
            return True
        node = node.value
    return False


def _jnp_names(fn_node) -> set:
    """Local names bound to the engine's `self._jnp`: `jnp = self._jnp`,
    `model, jnp = self.model, self._jnp`."""
    names = set()
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            pairs = [(tgt, node.value)]
            if (isinstance(tgt, ast.Tuple)
                    and isinstance(node.value, ast.Tuple)
                    and len(tgt.elts) == len(node.value.elts)):
                pairs = list(zip(tgt.elts, node.value.elts))
            names.update(t.id for t, v in pairs
                         if isinstance(t, ast.Name) and _is_engine_jnp(v))
    return names


def _lookup_findings(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for key in sorted(project.functions):
        fn = project.functions[key]
        if not _is_lookup(fn.node):
            continue
        mod = project.modules[fn.relpath]
        local = _jnp_names(fn.node)
        for node in ast.walk(fn.node):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            root = project.alias_root(mod, node.func)
            on_device = (_through_engine_jnp(node.func) or root in local
                         or _is_device_root(root))
            if on_device and node.func.attr not in _DESCRIBES:
                findings.append(Finding(
                    RULE, fn.relpath, fn.qualname, f"lookup.{node.func.attr}",
                    "a program lookup makes an array (%s): it waits for "
                    "everything queued on the device to learn a shape; "
                    "describe it with jax.ShapeDtypeStruct"
                    % node.func.attr, node.lineno))
    return findings


def run(project: Project) -> List[Finding]:
    roots = [fn.key for fn in project.functions.values()
             if is_root(fn.name, fn.relpath)]
    hot = project.reachable(sorted(roots))
    findings: List[Finding] = _lookup_findings(project)
    for key in sorted(hot):
        fn = project.functions[key]
        mod = project.modules[fn.relpath]
        tainted = _device_tainted_names(project, mod, fn.node)
        for node in ast.walk(fn.node):
            if not isinstance(node, ast.Call):
                continue
            callee = node.func
            if isinstance(callee, ast.Attribute):
                if callee.attr == "item" and not node.args:
                    findings.append(Finding(
                        RULE, fn.relpath, fn.qualname, ".item",
                        "device scalar pull (.item()) in a hot-loop-"
                        "reachable function forces a host sync",
                        node.lineno))
                    continue
                if callee.attr == "block_until_ready":
                    findings.append(Finding(
                        RULE, fn.relpath, fn.qualname,
                        ".block_until_ready",
                        "explicit device sync in a hot-loop-reachable "
                        "function", node.lineno))
                    continue
                root = project.alias_root(mod, callee)
                if (root in _NUMPY_ROOTS and callee.attr in _NUMPY_SYNC_FNS
                        and node.args
                        and _device_arg(project, mod, node.args[0],
                                        tainted)):
                    findings.append(Finding(
                        RULE, fn.relpath, fn.qualname,
                        f"np.{callee.attr}",
                        "np.%s() on a device value blocks until the "
                        "buffer lands on host" % callee.attr,
                        node.lineno))
                    continue
                if root in _DEVICE_ROOTS and callee.attr in _SYNC_JAX_FNS:
                    findings.append(Finding(
                        RULE, fn.relpath, fn.qualname,
                        f"jax.{callee.attr}",
                        "jax.%s() in a hot-loop-reachable function is a "
                        "host sync" % callee.attr, node.lineno))
                    continue
            elif isinstance(callee, ast.Name):
                if (callee.id in _COERCIONS and len(node.args) == 1
                        and isinstance(node.args[0], ast.Name)
                        and node.args[0].id in tainted):
                    findings.append(Finding(
                        RULE, fn.relpath, fn.qualname,
                        f"{callee.id}()",
                        "%s() coercion of a device value (implicit "
                        "__%s__ sync) in a hot-loop-reachable function"
                        % (callee.id, callee.id), node.lineno))
    return findings
