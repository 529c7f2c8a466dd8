"""The one rule for where compiled programs persist
(executor.compile_cache_dir / enable_compile_cache).

JAX reads JAX_COMPILATION_CACHE_DIR when it is imported, so each case that
sets or unsets it runs in a process of its own.
"""

import json
import os
import subprocess
import sys

import pytest

from gofr_tpu.tpu.executor import (DEFAULT_COMPILE_CACHE_DIR,
                                   compile_cache_dir)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what every child does: resolve the rule, build an Executor on it, compile
# one program through it, report what it saw
CHILD = """
import json, os, sys
sys.path.insert(0, {repo!r})
import jax
import jax.numpy as jnp
from gofr_tpu.tpu import executor

if {pretend_tpu!r}:
    jax.default_backend = lambda: "tpu"
before = jax.config.jax_compilation_cache_dir
path = executor.enable_compile_cache({override!r})
ex = executor.Executor(cache_dir=path)
ex.compile("rule-probe", lambda x: x * 3 + 1, (jnp.ones((4,)),))
print(json.dumps({{
    "resolved": executor.compile_cache_dir({override!r}),
    "enabled_at": path,
    "jax_before": before,
    "jax_after": jax.config.jax_compilation_cache_dir,
    "disk_hits": ex.disk_hits,
    "jexec": sorted(f for f in os.listdir(path) if f.endswith(".jexec")),
}}))
"""


def _child(env_dir=None, override=None, pretend_tpu=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(repo=REPO, override=override,
                                            pretend_tpu=pretend_tpu)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def placed_by_env(tmp_path_factory):
    """Two boots on one directory the environment placed: (first, second)."""
    env_dir = tmp_path_factory.mktemp("placed")
    first = _child(env_dir=env_dir, override="/an/override/that/must/lose")
    second = _child(env_dir=env_dir)
    return str(env_dir), first, second


def test_env_places_both_caches_and_the_code_sets_no_other(placed_by_env):
    env_dir, first, _ = placed_by_env
    # the executor's artifacts live where the environment said ...
    assert first["resolved"] == first["enabled_at"] == env_dir
    assert len(first["jexec"]) == 1
    # ... and so does JAX's own cache, which JAX set up from the variable:
    # enable_compile_cache left its config exactly as it found it
    assert first["jax_before"] == first["jax_after"] == env_dir


def test_second_executor_on_the_directory_loads_what_the_first_saved(
        placed_by_env):
    _, first, second = placed_by_env
    assert first["disk_hits"] == 0
    assert second["disk_hits"] == 1          # a fresh process, no recompile
    assert second["jexec"] == first["jexec"]  # found under the same name


def test_unset_the_path_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    here = compile_cache_dir()
    assert here == DEFAULT_COMPILE_CACHE_DIR == os.path.join(
        REPO, ".compile_cache")
    # identical in another process: nothing of a pid, a time or a temp
    # name is in it (the path is part of JAX's cache key)
    assert _child()["resolved"] == here
    # a deployment's explicit directory (PROGRAM_CACHE_DIR) overrides the
    # default, never the environment's placement
    assert compile_cache_dir("/srv/programs") == "/srv/programs"
    with open(os.path.join(REPO, ".gitignore")) as fp:
        assert ".compile_cache/" in fp.read().split()


def test_unset_jax_cache_follows_the_rule_on_an_accelerator_only(tmp_path):
    """Unset, enable_compile_cache points JAX's cache at the same
    directory — except on the CPU backend, whose reloaded executables
    jaxlib 0.9.0 cannot run beside each other (see its docstring)."""
    on_cpu = _child(override=str(tmp_path / "cpu"))
    assert on_cpu["enabled_at"] == str(tmp_path / "cpu")
    assert on_cpu["jax_after"] is None
    as_tpu = _child(override=str(tmp_path / "tpu"), pretend_tpu=True)
    assert as_tpu["enabled_at"] == as_tpu["jax_after"] == str(
        tmp_path / "tpu")
