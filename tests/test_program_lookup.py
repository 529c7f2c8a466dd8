"""A program lookup touches no device (ISSUE 36).

The six `_<kind>_program` methods of `tpu/paging.py` describe the arguments
the engine does not hold as `jax.ShapeDtypeStruct`s: a warm lookup makes no
array (so it waits for nothing queued on the device), the executor's key
is the one concrete example arrays gave (so no artifact on disk is
orphaned), a cold lookup lowers from the structs and serves the
reference's tokens, and `/debug/engine` -> `engine.program_lookup` counts
them.
"""

import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.models.llama import (LlamaConfig, init_kv_cache,
                                   llama_decode_step, llama_init,
                                   llama_prefill)
from gofr_tpu.parallel import MeshPlan, make_mesh
from gofr_tpu.tpu import paging
from gofr_tpu.tpu.engine import LookupCount
from gofr_tpu.tpu.executor import Executor
from gofr_tpu.tpu.paging import PagedLLMEngine

CFG = LlamaConfig.debug()

# what each engine is built with, and the lookups the tiny engine can
# reach on it: (engine, method, arguments). `q8` runs the int8 branches
ENGINES = {
    "plain": dict(prefix_cache=True, chunk_prefill_tokens=8),
    "spec": dict(speculative_tokens=2),
    "q8": dict(prefix_cache=True, chunk_prefill_tokens=8, kv_dtype="int8"),
}
LOOKUPS = {
    "restore": ("plain", "_restore_program", (2,)),
    "prefill": ("plain", "_prefill_program", (8, 1)),
    "decode": ("plain", "_decode_program_paged", (4, 2)),
    "chunk": ("plain", "_chunk_program_paged", (8, 1, 24, False)),
    "chunk-final": ("plain", "_chunk_program_paged", (8, 1, 24, True)),
    "prefix": ("plain", "_prefix_program", (8, 1, 4)),
    "verify": ("spec", "_verify_program", (4,)),
    "restore-q8": ("q8", "_restore_program", (2,)),
    "prefill-q8": ("q8", "_prefill_program", (8, 1)),
    "decode-q8": ("q8", "_decode_program_paged", (4, 2)),
    "chunk-final-q8": ("q8", "_chunk_program_paged", (8, 1, 24, True)),
    "prefix-q8": ("q8", "_prefix_program", (8, 1, 4)),
}
_built = {}


def _engine(kind, placement="one-device"):
    """The tiny engine of a kind, built once a module (its loop is never
    started: a lookup needs the engine's state, not its thread)."""
    if (kind, placement) not in _built:
        kw = dict(ENGINES[kind])
        cfg = dataclasses.replace(CFG, kv_dtype=kw.pop("kv_dtype", None))
        mesh = (make_mesh(MeshPlan(tp=2), devices=jax.devices()[:2])
                if placement == "mesh" else None)
        _built[kind, placement] = PagedLLMEngine(
            llama_init(CFG, seed=0), cfg, n_slots=2, max_seq_len=64,
            prefill_buckets=(8, 24), decode_block_size=2, page_size=8,
            mesh=mesh, **kw)
    return _built[kind, placement]


class NoArrays:
    """`jax.numpy` with the constructors a lookup used to call refusing."""

    def __getattr__(self, name):
        if name in ("zeros", "ones", "full", "asarray", "zeros_like",
                    "ones_like", "empty", "array"):
            def refuse(*args, **kwargs):
                raise AssertionError(f"a program lookup called jnp.{name}")
            return refuse
        return getattr(jnp, name)


@pytest.mark.parametrize("case", sorted(LOOKUPS))
def test_a_warm_lookup_makes_no_array(case, monkeypatch):
    kind, method, args = LOOKUPS[case]
    engine = _engine(kind)
    lookup = getattr(engine, method)
    warm = lookup(*args)                     # the warm-up's: may compile
    before = engine.lookups.snapshot()
    hits = warm.hits
    refusing = NoArrays()
    monkeypatch.setattr(engine, "_jnp", refusing)
    for name in ("zeros", "ones", "full", "asarray"):
        monkeypatch.setattr(jnp, name, getattr(refusing, name))
    monkeypatch.setattr(engine, "_temps_init", refusing.zeros)
    again = lookup(*args)
    monkeypatch.undo()
    assert again is warm
    assert warm.hits == hits + 1
    after = engine.lookups.snapshot()
    assert after["lookups_total"] == before["lookups_total"] + 1
    assert after["misses_total"] == before["misses_total"]
    assert after["seconds_total"] > before["seconds_total"]


def _zeros(shape, dtype=jnp.int32):
    """What the lookups made before: a concrete array on the default
    device, uncommitted. Kept here as the oracle of the executor's key."""
    return jnp.zeros(shape, dtype=dtype)


@pytest.mark.parametrize("placement", ["one-device", "mesh"])
@pytest.mark.parametrize("case", sorted(LOOKUPS))
def test_the_key_is_the_one_concrete_arrays_gave(case, placement,
                                                 monkeypatch):
    """Name, (shape, dtype) of every leaf, donation and the device
    signature: a lookup over structs and one over example arrays are
    served the SAME program by the executor, nothing lowered for the
    second, so the `.jexec` path (a digest of that key) is the same too."""
    kind, method, args = LOOKUPS[case]
    engine = _engine(kind, placement)
    lookup = getattr(engine, method)
    from_structs = lookup(*args)
    programs = engine.executor.cache_size
    monkeypatch.setattr(paging, "_shaped", _zeros)
    from_arrays = lookup(*args)
    assert from_arrays is from_structs
    assert from_arrays.key == from_structs.key
    assert engine.executor.cache_size == programs    # nothing lowered
    # the device signature comes from what the engine holds
    devices = from_structs.key[-1]
    assert devices == ((0, 1) if placement == "mesh" else (0,))


def _reference_greedy(params, prompt, n):
    k, v = init_kv_cache(CFG, 1, 64)
    logits, k, v = llama_prefill(params, CFG,
                                 jnp.asarray([prompt], jnp.int32), k, v)
    out = [int(jnp.argmax(logits[0, -1]))]
    for i in range(n - 1):
        logits, k, v = llama_decode_step(
            params, CFG, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([len(prompt) + i], jnp.int32), k, v)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_a_cold_lookup_lowers_from_structs_and_serves_the_reference(tmp_path):
    """A fresh executor over an empty cache directory: prefill, decode,
    the chunk pair and the prefix tail each lower once from structs, run
    with the real arguments and serve the cached reference's tokens; the
    counter's misses are exactly those programs. A second executor over
    the same directory loads every one of them back."""
    from gofr_tpu.tpu.utilization import engine_snapshot

    params = llama_init(CFG, seed=0)
    shared = [3, 1, 4, 1, 5, 9, 2, 6]            # one whole page
    prompts = [shared + [5, 3], shared + [8, 9, 7],      # a prefix hit
               list(range(1, 20)),                       # chunked: > 8
               [7, 7]]
    want = [_reference_greedy(params, p, 6) for p in prompts]

    def serve(executor):
        engine = PagedLLMEngine(
            params, CFG, n_slots=2, max_seq_len=64, prefill_buckets=(8, 24),
            decode_block_size=2, page_size=8, prefix_cache=True,
            chunk_prefill_tokens=8, executor=executor)
        engine.start()
        try:
            got = [engine.generate(p, max_new_tokens=6, temperature=0.0)
                   for p in prompts]
        finally:
            engine.stop()
        return got, engine_snapshot(engine)["engine"]["program_lookup"]

    cold = Executor(cache_dir=str(tmp_path))
    got, counted = serve(cold)
    assert got == want
    names = set(cold.cache_info())
    assert any("-paged-prefill-" in n for n in names)
    assert any("-paged-decode-" in n for n in names)
    assert any("-paged-chunk-final-" in n for n in names)
    assert any("-paged-prefix-" in n for n in names)
    assert cold.disk_hits == 0
    assert counted["misses_total"] == cold.cache_size
    assert counted["lookups_total"] > counted["misses_total"]
    # a warm lookup is host arithmetic: nowhere near a compile
    assert 0.0 < counted["longest_ms"] < 250.0
    assert len(glob.glob(os.path.join(str(tmp_path), "*.jexec"))) \
        == cold.cache_size

    again = Executor(cache_dir=str(tmp_path))
    got, counted = serve(again)
    assert got == want
    assert again.disk_hits == again.cache_size == cold.cache_size
    assert counted["misses_total"] == again.cache_size


def test_a_miss_is_counted_and_its_seconds_are_not():
    count = LookupCount()
    count.note(0.002, missed=False)
    count.note(30.0, missed=True)                # a compile's seconds
    count.note(0.004, missed=False)
    assert count.snapshot() == {"lookups_total": 3, "misses_total": 1,
                                "seconds_total": 0.006, "longest_ms": 4.0}


# -- the device's time in prefill programs is read by this name (ISSUE 37) ----
@pytest.mark.parametrize("case,name,module,donated", [
    ("prefill", "llama-paged-prefill-8x1", "jit_prefill__8x1",
     (1, 2, 7, 8, 9)),
    ("prefix", "llama-paged-prefix-8x1-NP4",
     "jit_prefill__llama_paged_prefix_8x1_NP4", (1, 2, 8, 9, 10)),
])
def test_a_prefill_programs_module_name_starts_jit_prefill(case, name,
                                                           module, donated):
    """`benchmark/layer_metrics/prefill_dev_*` select the trace's XLA
    modules on the prefix `jit_prefill`: the plain and the prefix prefill
    program both carry it, under the program name and the donation that
    key their artifacts (a renamed program compiles cold)."""
    kind, method, args = LOOKUPS[case]
    program = getattr(_engine(kind), method)(*args)
    assert f"HloModule {module}" in program.compiled.as_text()[:400]
    assert program.name == program.key[0] == name
    assert program.key[3] == donated
