"""Compile-only checks against a DESCRIBED v5e: what the chip's compiler
refuses here costs no chip time (on-chip-measurement guide §2.3).

The TPU compiler is installed in the sandbox and compiles for a topology
that is described, not attached. Nothing executes: these tests say that the
serving path's kernels and step programs lower for the chip at real widths
(Mosaic accepts the kernel, the program fits the chip's memory, the pool is
updated in place, a mesh program holds its collectives) — never that a
result is right or how long anything takes.

Interpret mode is what every other test runs the kernels in; it cannot see
a misaligned slice, a kernel the compiler cannot partition, or a scatter
that makes the compiler re-lay the whole page pool out. Each of those was
found by a compile like the ones below before the first chip call.
"""

import dataclasses
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from gofr_tpu.models.llama import LlamaConfig, llama_init
from gofr_tpu.ops.flash_attention import flash_attention
from gofr_tpu.ops.paged_attention import (block_tail, paged_attention,
                                          paged_attention_in_block,
                                          paged_flush_block,
                                          paged_write_decode)
from gofr_tpu.parallel.sharding import (kv_cache_spec, kv_scale_pool_spec,
                                        serving_param_specs)

# published head geometry of the two presets the chip serves
WIDTHS = {"llama1b": (32, 8, 64), "llama3-8b": (32, 8, 128),
          "internlm2": (16, 8, 128)}
PAGE = 128
GIB = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no TPU compiler installed here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without the chip: the next run would warn and
    compile again. Keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


class Chips:
    """Shapes placed on n described chips: one device, or a tp mesh."""

    def __init__(self, topo, n: int):
        self.mesh = (Mesh(np.array(topo.devices[:n]), ("tp",))
                     if n > 1 else None)
        self._one = SingleDeviceSharding(topo.devices[0])

    def shape(self, dims, dtype, spec=P()):
        sharding = (NamedSharding(self.mesh, spec) if self.mesh is not None
                    else self._one)
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def _compile(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _pools(chips, Hkv, dh, dtype, n_pages=513, layers=2):
    """(k_pool, v_pool[, k_scale, v_scale]) stacked shapes, heads over tp."""
    pool = chips.shape((layers, n_pages, Hkv, dh, PAGE), dtype,
                       kv_cache_spec())
    if dtype != jnp.int8:
        return (pool, pool)
    scale = chips.shape((layers, n_pages, Hkv, PAGE), jnp.float32,
                        kv_scale_pool_spec())
    return (pool, pool, scale, scale)


# -- the serving path's kernels, one chip -------------------------------------
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("preset", list(WIDTHS))
def test_paged_attention_compiles(topo, preset, dtype):
    H, Hkv, dh = WIDTHS[preset]
    chips = Chips(topo, 1)
    B, NP = 64, 8
    q = chips.shape((B, H, dh), jnp.bfloat16)
    k_pool, v_pool, *scales = _pools(chips, Hkv, dh, dtype)
    table = chips.shape((B, NP), jnp.int32)
    lengths = chips.shape((B,), jnp.int32)

    def read(q, k_pool, v_pool, table, lengths, *scales):
        return paged_attention(q, k_pool, v_pool, table, lengths, *scales,
                               layer=jnp.int32(1), interpret=False)

    compiled = _compile(read, q, k_pool, v_pool, table, lengths, *scales)
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("preset", list(WIDTHS))
def test_paged_write_decode_compiles_in_place(topo, preset, dtype):
    _, Hkv, dh = WIDTHS[preset]
    chips = Chips(topo, 1)
    B, NP = 64, 8
    pools = _pools(chips, Hkv, dh, dtype)
    new = chips.shape((B, Hkv, dh), dtype)
    new_scale = chips.shape((B, Hkv), jnp.float32)
    table = chips.shape((B, NP), jnp.int32)
    positions = chips.shape((B,), jnp.int32)
    n = len(pools)

    def write(*args):
        pools, (table, positions, new, new_scale) = args[:n], args[n:]
        extra = (pools[2], pools[3], new_scale, new_scale) if n == 4 else ()
        return paged_write_decode(pools[0], pools[1], new, new, table,
                                  positions, *extra, layer=jnp.int32(1),
                                  interpret=False)

    compiled = _compile(write, *pools, table, positions, new, new_scale,
                        donate=tuple(range(n)))
    assert _kernels(compiled) == 1
    mem = compiled.memory_analysis()
    pool_bytes = sum(np.prod(p.shape) * p.dtype.itemsize for p in pools)
    # every pool is updated where it lies: aliased out, nothing copied
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


def _tails(chips, pool, rows, block):
    """A decode block's (k_tail, v_tail) shapes for `pool`, heads over tp."""
    like = jax.eval_shape(lambda: block_tail(
        jax.ShapeDtypeStruct(pool.shape, pool.dtype), rows, block))
    return tuple(chips.shape(x.shape, x.dtype, kv_cache_spec()) for x in like)


# a full block, the half block the engine takes while requests wait, and the
# block of one token: the tail is padded to whole tiles for each
@pytest.mark.parametrize("block", [16, 8, 1])
@pytest.mark.parametrize("preset", list(WIDTHS))
def test_block_tail_kernels_compile_in_place(topo, preset, block):
    """The decode path of the floating-point pools: the read that puts
    the step's token into the block's tail (in place) and attends pages
    and tail, and the flush that writes each row's page once a block,
    where the pools lie (llama1b's heads of 64 pad the tail's lanes to
    128: a copy's window is whole tiles)."""
    H, Hkv, dh = WIDTHS[preset]
    chips = Chips(topo, 1)
    B, NP = 64, 8
    q = chips.shape((B, H, dh), jnp.bfloat16)
    new = chips.shape((B, Hkv, dh), jnp.bfloat16)
    pools = _pools(chips, Hkv, dh, jnp.bfloat16)
    tails = _tails(chips, pools[0], B, block)
    table = chips.shape((B, NP), jnp.int32)
    rows = chips.shape((B,), jnp.int32)

    def read(k_tail, v_tail, q, new, k_pool, v_pool, table, lengths,
             tail_lens):
        return paged_attention_in_block(
            q, new, new, k_pool, v_pool, k_tail, v_tail, table, lengths,
            tail_lens, layer=jnp.int32(1), interpret=False)

    compiled = _compile(read, *tails, q, new, *pools, table, rows, rows,
                        donate=(0, 1))
    assert _kernels(compiled) == 1
    tail_bytes = sum(np.prod(t.shape) * t.dtype.itemsize for t in tails)
    assert compiled.memory_analysis().alias_size_in_bytes == tail_bytes

    def flush(k_pool, v_pool, k_tail, v_tail, table, starts, counts):
        return paged_flush_block(k_pool, v_pool, k_tail, v_tail, table,
                                 starts, counts, interpret=False)

    compiled = _compile(flush, *pools, *tails, table, rows, rows,
                        donate=(0, 1))
    assert _kernels(compiled) == 1
    mem = compiled.memory_analysis()
    pool_bytes = sum(np.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 100


# the smoke's prefill buckets at llama1b widths; the largest at llama3-8b
@pytest.mark.parametrize("preset,T", [("llama1b", 16), ("llama1b", 32),
                                      ("llama1b", 64), ("llama1b", 128),
                                      ("llama1b", 256), ("llama3-8b", 256)])
def test_flash_attention_resident_compiles(topo, preset, T):
    H, Hkv, dh = WIDTHS[preset]
    chips = Chips(topo, 1)
    q = chips.shape((4, T, H, dh), jnp.bfloat16)
    kv = chips.shape((4, T, Hkv, dh), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=False),
        q, kv, kv)
    assert _kernels(compiled) == 1


@pytest.mark.parametrize("preset", list(WIDTHS))
def test_flash_attention_streaming_compiles(topo, preset):
    """K+V of one head past the resident kernel's VMEM budget: the
    streaming kernel (kv innermost, carry in scratch) takes over."""
    from gofr_tpu.ops.flash_attention import VMEM_KV_BUDGET_BYTES

    H, Hkv, dh = WIDTHS[preset]
    T = 32768
    assert T * dh * 2 * 2 > VMEM_KV_BUDGET_BYTES
    chips = Chips(topo, 1)
    q = chips.shape((1, T, H, dh), jnp.bfloat16)
    kv = chips.shape((1, T, Hkv, dh), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=False),
        q, kv, kv)
    assert _kernels(compiled) == 1


# -- the four-chip tp mesh ------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("preset", ["llama1b", "internlm2"])
def test_paged_kernels_compile_sharded_over_tp(topo, preset, dtype):
    """The pool sharded over KV heads on a 4-chip tp mesh, as
    PagedLLMEngine._place_state places it. Under plain jit the compiler
    refuses ("Mosaic kernels cannot be automatically partitioned"); the
    kernels run per shard under shard_map when handed the mesh."""
    H, Hkv, dh = WIDTHS[preset]
    chips = Chips(topo, 4)
    B, NP = 64, 8
    heads = P(None, "tp", None)
    q = chips.shape((B, H, dh), jnp.bfloat16, heads)
    new = chips.shape((B, Hkv, dh), dtype, heads)
    new_scale = chips.shape((B, Hkv), jnp.float32, P(None, "tp"))
    pools = _pools(chips, Hkv, dh, dtype)
    table = chips.shape((B, NP), jnp.int32)
    positions = chips.shape((B,), jnp.int32)
    n = len(pools)

    def step(*args):
        pools, (table, positions, q, new, new_scale) = args[:n], args[n:]
        extra = (pools[2], pools[3], new_scale, new_scale) if n == 4 else ()
        pools = paged_write_decode(pools[0], pools[1], new, new, table,
                                   positions, *extra, layer=jnp.int32(1),
                                   mesh=chips.mesh, interpret=False)
        out = paged_attention(q, pools[0], pools[1], table, positions + 1,
                              *pools[2:], layer=jnp.int32(1),
                              mesh=chips.mesh, interpret=False)
        return out, pools

    compiled = _compile(step, *pools, table, positions, q, new, new_scale,
                        donate=tuple(range(n)))
    assert _kernels(compiled) == 2
    # heads are independent: no collective inside either kernel's shard_map
    assert "all-reduce(" not in compiled.as_text()


@pytest.mark.parametrize("preset", ["llama1b", "internlm2"])
def test_block_tail_kernels_compile_sharded_over_tp(topo, preset):
    """The same mesh for the decode block's tail: it follows the pools'
    head sharding, and the read over pages and tail and the flush run per
    shard with no collective."""
    H, Hkv, dh = WIDTHS[preset]
    chips = Chips(topo, 4)
    B, NP = 64, 8
    q = chips.shape((B, H, dh), jnp.bfloat16, P(None, "tp", None))
    new = chips.shape((B, Hkv, dh), jnp.bfloat16, P(None, "tp", None))
    pools = _pools(chips, Hkv, dh, jnp.bfloat16)
    tails = _tails(chips, pools[0], B, 16)
    table = chips.shape((B, NP), jnp.int32)
    rows = chips.shape((B,), jnp.int32)

    def step(k_pool, v_pool, k_tail, v_tail, table, starts, q, new):
        out, k_tail, v_tail = paged_attention_in_block(
            q, new, new, k_pool, v_pool, k_tail, v_tail, table, starts,
            starts % 16 + 1, layer=jnp.int32(1), mesh=chips.mesh,
            interpret=False)
        return out, paged_flush_block(
            k_pool, v_pool, k_tail, v_tail, table, starts,
            jnp.full_like(starts, 16), mesh=chips.mesh, interpret=False)

    compiled = _compile(step, *pools, *tails, table, rows, q, new,
                        donate=(0, 1))
    assert _kernels(compiled) == 2
    assert "all-reduce(" not in compiled.as_text()


def test_flash_prefill_compiles_sharded_over_tp(topo):
    """The flash prefill's call site under a mesh (heads of q/k/v over
    tp)."""
    H, Hkv, dh = WIDTHS["llama1b"]
    chips = Chips(topo, 4)
    heads4 = P(None, None, "tp", None)
    q = chips.shape((4, 256, H, dh), jnp.bfloat16, heads4)
    kv = chips.shape((4, 256, Hkv, dh), jnp.bfloat16, heads4)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, True, interpret=False,
                                        mesh=chips.mesh), q, kv, kv)
    assert _kernels(compiled) == 1


# -- the paged engine's step programs at llama1b widths -------------------------
# The engine picks interpret mode from the PROCESS's backend, which is the
# CPU here. The test steers that (not an option of the program): with the
# backend reported as "tpu" the step lowers the real kernels, so its
# memory_analysis() is the chip's.
N_SLOTS, N_PAGES = 128, 2049      # 128 slots x 2048 tokens: an 8 GiB pool


@pytest.fixture()
def as_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _engine_shell(cls, cfg, mesh):
    """The program factories read only these attributes; no device state."""
    engine = cls.__new__(cls)
    engine.cfg, engine.mesh, engine.top_k = cfg, mesh, 0
    engine._jnp, engine.sampling_controls = jnp, False
    return engine


def _params(chips, cfg):
    shapes = jax.eval_shape(lambda: llama_init(cfg, 0))
    return jax.tree_util.tree_map(
        lambda a, spec: chips.shape(a.shape, a.dtype, spec),
        shapes, serving_param_specs())


def _loop_state(chips, rows):
    return (chips.shape((rows,), jnp.int32), chips.shape((rows,), jnp.int32),
            chips.shape((rows,), jnp.float32))


def _assert_pool_in_place(compiled, pools):
    pool_bytes = sum(np.prod(p.shape) * p.dtype.itemsize for p in pools)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # a re-laid-out copy of one pool would be half of this or more
    assert mem.temp_size_in_bytes < pool_bytes // 8, (
        f"temp {mem.temp_size_in_bytes / GIB:.2f} GiB beside a "
        f"{pool_bytes / GIB:.2f} GiB pool: the program copies the pool")


def _served(preset: str):
    """(config, slots, pages, decode block, table width): llama1b as the
    smoke serves it, internlm2-1.8b as the benchmark's cells do
    (benchmark/configs/internlm2-1.8b.json, `jit_decode__x16_NP16`)."""
    if preset == "llama1b":
        return LlamaConfig.llama1b(), N_SLOTS, N_PAGES, 8, 4
    H, Hkv, dh = WIDTHS["internlm2"]
    cfg = LlamaConfig(vocab_size=92544, dim=H * dh, n_layers=24, n_heads=H,
                      n_kv_heads=Hkv, ffn_dim=8192, max_seq_len=2048,
                      rope_theta=1e6)
    return cfg, 96, 769, 16, 16


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
@pytest.mark.parametrize("preset", ["llama1b", "internlm2"])
def test_paged_decode_step_compiles_in_place(topo, as_tpu, preset, kv_dtype):
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg, n_slots, n_pages, block, n_table = _served(preset)
    cfg = dataclasses.replace(cfg, kv_dtype=kv_dtype)
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim,
                   jnp.int8 if kv_dtype else jnp.bfloat16, n_pages,
                   cfg.n_layers)
    tokens, positions, temps = _loop_state(chips, n_slots)
    table = chips.shape((n_slots, n_table), jnp.int32)
    rng = chips.shape((2,), jnp.uint32)
    fn = (engine._decode_fn_paged_q8 if kv_dtype else engine._decode_fn_paged)
    compiled = _compile(fn(block, n_table), _params(chips, cfg), *pools,
                        table, tokens, positions, temps, rng,
                        donate=tuple(range(1, 1 + len(pools))))
    assert _kernels(compiled) == 2      # the page write and the paged read
    _assert_pool_in_place(compiled, pools)


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["bf16", "int8"])
def test_paged_prefill_step_compiles_in_place(topo, as_tpu, kv_dtype):
    """int8: the window quantizes once at the scatter, values and scale
    pools both written in place (the harness's `int8-kv` control boots it)."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(LlamaConfig.llama1b(), attn_impl="flash",
                              kv_dtype=kv_dtype)
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim,
                   jnp.int8 if kv_dtype else jnp.bfloat16, N_PAGES,
                   cfg.n_layers)
    tokens, positions, temps = _loop_state(chips, N_SLOTS)
    bucket, K = 256, 1
    rows = chips.shape((K,), jnp.int32)
    fn = engine._prefill_fn_q8 if kv_dtype else engine._prefill_fn
    first = 1 + len(pools) + 4          # the loop state, after the window
    compiled = _compile(
        fn(bucket, K), _params(chips, cfg), *pools,
        chips.shape((K, bucket), jnp.int32),
        chips.shape((K, bucket // PAGE), jnp.int32), rows, rows,
        tokens, positions, temps, chips.shape((K,), jnp.float32),
        chips.shape((2,), jnp.uint32),
        donate=tuple(range(1, 1 + len(pools))) + (first, first + 1,
                                                  first + 2))
    assert _kernels(compiled) == 1      # flash over the fresh window
    _assert_pool_in_place(compiled, pools)


def _kernel_calls(compiled):
    """[(instruction name, returns a tuple, op_name)] of the Pallas kernels."""
    import re

    out = []
    for line in compiled.as_text().splitlines():
        if "tpu_custom_call" not in line:
            continue
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        op_name = re.search(r'op_name="([^"]*)"', line)
        out.append((name.lstrip("%"), rest.startswith("("),
                    op_name.group(1) if op_name else ""))
    return out


def _computation_of(compiled, instruction: str) -> str:
    """The name of the HLO computation that holds `instruction`."""
    computation = None
    for line in compiled.as_text().splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            computation = line.removeprefix("ENTRY ").split()[0].lstrip("%")
        elif line.strip().removeprefix("ROOT ").startswith(
                f"%{instruction} = "):
            return computation
    raise AssertionError(f"{instruction} is in no computation")


def _while_bodies(compiled) -> set:
    import re

    return set(re.findall(r"while\(.*body=%([\w.\-]+)", compiled.as_text()))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_step_programs_name_their_module_and_kernels(topo, as_tpu, program):
    """What a profiler trace shows of a step program (ISSUE 24): the XLA
    module carries the table width and block, and each Pallas kernel's
    instruction is named after its scope, bare (the `closed_call_` prefix
    went in PR 27: the benchmark's readers select by name), the page write
    (since PR 28 the flush of the block's tail, once a program, outside
    every loop) is still the custom-call that returns the two pools, and
    the paged read is one custom-call in the layer loop's body (since PR
    28 it returns the block's tail beside the attention: the step's token
    is put there by the read itself)."""
    from gofr_tpu.tpu.executor import _named_after
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(LlamaConfig.llama1b(), n_layers=2,
                              attn_impl="flash")
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16, 129,
                   cfg.n_layers)
    tokens, positions, temps = _loop_state(chips, 8)
    rng = chips.shape((2,), jnp.uint32)
    if program == "decode":
        compiled = _compile(
            _named_after(engine._decode_fn_paged(8, 4),
                         "llama-paged-decode-x8-NP4"),
            _params(chips, cfg), *pools, chips.shape((8, 4), jnp.int32),
            tokens, positions, temps, rng, donate=(1, 2))
        assert "HloModule jit_decode__x8_NP4," in compiled.as_text()
        calls = sorted(_kernel_calls(compiled))
        # what benchmark/harness/readers relies on: ONE read a layer-loop
        # body a step (its calls count the steps), both found by name
        assert [name.rsplit(".", 1)[0] for name, _, _ in calls] == [
            "paged_read", "paged_write"]
        assert _computation_of(compiled, calls[0][0]) in _while_bodies(
            compiled)
        assert _computation_of(compiled, calls[1][0]) not in _while_bodies(
            compiled)
    else:
        K, bucket = 1, 256
        rows = chips.shape((K,), jnp.int32)
        compiled = _compile(
            _named_after(engine._prefill_fn(bucket, K),
                         "llama-paged-prefill-256x1"),
            _params(chips, cfg), *pools, chips.shape((K, bucket), jnp.int32),
            chips.shape((K, bucket // PAGE), jnp.int32), rows, rows,
            tokens, positions, temps, chips.shape((K,), jnp.float32), rng,
            donate=(1, 2, 7, 8, 9))
        assert "HloModule jit_prefill__256x1," in compiled.as_text()
        calls = _kernel_calls(compiled)
        assert [name.rsplit(".", 1)[0] for name, _, _ in calls] == [
            "flash_prefill"]
    for name, _, op_name in calls:
        assert op_name.endswith("/pallas_call")


def test_paged_prefix_prefill_step_compiles_in_place(topo, as_tpu):
    """The prefix-cache hit path: a 16-token tail behind one shared page."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.llama1b()
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
                   N_PAGES, cfg.n_layers)
    tokens, positions, temps = _loop_state(chips, N_SLOTS)
    K, bucket, n_table = 1, 16, 2
    rows = chips.shape((K,), jnp.int32)
    compiled = _compile(
        engine._prefix_fn(bucket, K, n_table), _params(chips, cfg), *pools,
        chips.shape((K, bucket), jnp.int32),
        chips.shape((K, n_table), jnp.int32), rows, rows, rows,
        tokens, positions, temps, chips.shape((K,), jnp.float32),
        chips.shape((2,), jnp.uint32), donate=(1, 2, 8, 9, 10))
    _assert_pool_in_place(compiled, pools)


def test_paged_decode_step_compiles_on_tp_mesh(topo, as_tpu):
    """TP serving on the default engine: params Megatron-split, the pool's
    KV heads over tp, both kernels per shard, XLA's all-reduces between."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.llama1b()
    chips = Chips(topo, 4)
    engine = _engine_shell(PagedLLMEngine, cfg, chips.mesh)
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
                   N_PAGES, cfg.n_layers)
    tokens, positions, temps = _loop_state(chips, N_SLOTS)
    compiled = _compile(
        engine._decode_fn_paged(8, 4), _params(chips, cfg), *pools,
        chips.shape((N_SLOTS, 4), jnp.int32), tokens, positions, temps,
        chips.shape((2,), jnp.uint32), donate=(1, 2))
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert "all-reduce(" in text        # the row-parallel wo / w_down sums
    # memory_analysis is per device: a quarter of the pool lives on each
    quarter = [jax.ShapeDtypeStruct(
        (p.shape[0], p.shape[1], p.shape[2] // 4) + p.shape[3:], p.dtype)
        for p in pools]
    _assert_pool_in_place(compiled, quarter)


# -- the nemotron_h family's step programs (ISSUE 27) ---------------------------
def _nemotron_programs(topo, pattern="ME*ME", slots=96, n_pages=961):
    """(engine shell, abstract params, pools, state, loop state, rng) at the
    published widths, a few blocks deep: every kind of block, two of the
    kinds that hold state."""
    from gofr_tpu.models.nemotron_h import (FLOAT32_LEAVES, KINDS,
                                            NemotronHConfig, layer_shapes)
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(NemotronHConfig.nano_30b_a3b_ep2(),
                              pattern=pattern, attn_impl="flash")
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    params = {
        "tok_emb": chips.shape((cfg.vocab_size, cfg.dim), jnp.bfloat16),
        "final_norm": chips.shape((cfg.dim,), jnp.bfloat16),
        "lm_head": chips.shape((cfg.dim, cfg.vocab_size), jnp.bfloat16),
        "layers": [{name: chips.shape(shape, jnp.float32 if name in FLOAT32_LEAVES
                                      else jnp.bfloat16)
                    for name, shape in layer_shapes(cfg, KINDS[m]).items()}
                   for m in pattern]}
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
                   n_pages, cfg.kv_layers)
    state = tuple(chips.shape(shape, dtype)
                  for shape, dtype in engine.model.state_shapes(slots))
    return (cfg, chips, engine, params, pools, state,
            _loop_state(chips, slots), chips.shape((2,), jnp.uint32))


def test_nemotron_h_decode_step_compiles_with_its_state_in_place(topo,
                                                                as_tpu):
    """The cell's decode program shape (96 slots, 961 pages, table 16 wide)
    over Mamba-2, expert and attention blocks at the published widths: the
    per-slot state (a 2 MiB recurrent state a slot a Mamba-2 block) and the
    pools are aliased, no state-sized or expert-sized copy is made (the up
    matrices held [held, D, F] were copied whole, 630 MB a block: compile-
    only, PR 27), the module is named `jit_decode...` and each new kernel's
    instruction after its scope, in the scan's body."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, state, loop, rng = \
        _nemotron_programs(topo)
    compiled = _compile(
        _named_after(engine._decode_fn_paged(16, 16),
                     "nemotron-h-paged-decode-x16-NP16"),
        params, *pools, chips.shape((96, 16), jnp.int32), *loop, rng, *state,
        donate=(1, 2, 8, 9))
    assert "HloModule jit_decode__x16_NP16," in compiled.as_text()
    names = sorted(name.rsplit(".", 1)[0]
                   for name, _, _ in _kernel_calls(compiled))
    assert names == ["moe_experts", "moe_experts", "paged_read",
                     "paged_write", "ssm_update", "ssm_update"]
    held = sum(np.prod(a.shape) * a.dtype.itemsize for a in pools + state)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    expert = cfg.held * cfg.dim * cfg.expert_dim * 2
    assert mem.temp_size_in_bytes < min(expert, held) // 8, (
        f"{mem.temp_size_in_bytes / GIB:.2f} GiB of temporaries: a copy of "
        f"the state or of a block's experts")


def test_nemotron_h_prefill_compiles_with_its_state_in_place(topo, as_tpu):
    """The cell's widest admission (16 x 128): the slots' state rows are
    written into the donated state, the experts run as a grouped product
    over sorted (token, pick) pairs (one kernel a block), attention as the
    flash kernel."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, state, loop, rng = \
        _nemotron_programs(topo)
    K, bucket = 16, 128
    rows = chips.shape((K,), jnp.int32)
    compiled = _compile(
        _named_after(engine._prefill_fn(bucket, K),
                     "nemotron-h-paged-prefill-128x16"),
        params, *pools, chips.shape((K, bucket), jnp.int32),
        chips.shape((K, bucket // PAGE), jnp.int32), rows, rows, *loop,
        chips.shape((K,), jnp.float32), rng, *state,
        donate=(1, 2, 7, 8, 9, 12, 13))
    assert "HloModule jit_prefill__128x16," in compiled.as_text()
    names = sorted(name.rsplit(".", 1)[0]
                   for name, _, _ in _kernel_calls(compiled))
    assert names.count("moe_experts") == 2 and "flash_prefill" in names
    held = sum(np.prod(a.shape) * a.dtype.itemsize for a in pools + state)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 1 * GIB


# -- the mla_moe family's step programs (ISSUE 31) ------------------------------
def _mla_moe_programs(topo, n_layers=3, slots=128, n_pages=4600,
                      preset="joyai_llm_flash_ep8"):
    """(config, chips, engine shell, abstract params, the one latent pool,
    loop state, rng) at JoyAI-LLM-Flash's published widths, the dense block
    and two expert blocks deep, the pool as the cell sizes it (or another
    `preset` of the family's)."""
    from gofr_tpu.models.mla_moe import (FLOAT32_LEAVES, MlaMoeConfig,
                                         layer_shapes)
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(getattr(MlaMoeConfig, preset)(),
                              n_layers=n_layers, attn_impl="flash")
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    params = {
        "tok_emb": chips.shape((cfg.vocab_size, cfg.dim), jnp.bfloat16),
        "final_norm": chips.shape((cfg.dim,), jnp.bfloat16),
        "lm_head": chips.shape((cfg.dim, cfg.vocab_size), jnp.bfloat16),
        "layers": [{name: chips.shape(shape, jnp.float32 if name in FLOAT32_LEAVES
                                      else jnp.bfloat16)
                    for name, shape in layer_shapes(
                        cfg, i < cfg.first_dense).items()}
                   for i in range(n_layers)]}
    pools = tuple(chips.shape((cfg.kv_layers, n_pages, plane.heads,
                               plane.width, PAGE), jnp.bfloat16)
                  for plane in engine.model.planes)
    return (cfg, chips, engine, params, pools, _loop_state(chips, slots),
            chips.shape((2,), jnp.uint32))


def test_mla_moe_decode_step_compiles_with_its_one_pool_in_place(topo,
                                                                as_tpu):
    """The cell's decode program shape (128 slots, 4,600 pages, table 64
    wide) at the published widths: ONE pool (a latent plane of 1 x 576), its
    tail and its flush; the absorbed read a kernel of its own name a block,
    the gated experts the kernel nemotron_h names, the flush once, outside
    the scan; the pool aliased and nothing pool-sized or expert-sized
    copied."""
    from gofr_tpu.tpu.executor import _named_after

    from gofr_tpu.ops.paged_attention import fold_of, fold_widths

    cfg, chips, engine, params, pools, loop, rng = _mla_moe_programs(topo)
    assert [p.shape for p in pools] == [(3, 4600, 1, 576, PAGE)]
    # a fold of 8 latent pages, a row's last one computed at 8, 4 or 2 (PR
    # 40): three copies of a turn's body in each read, for the chip
    assert fold_widths(fold_of(pools, 64)) == (8, 4, 2)
    compiled = _compile(
        _named_after(engine._decode_fn_paged(16, 64),
                     "mla-moe-paged-decode-x16-NP64"),
        params, *pools, chips.shape((128, 64), jnp.int32), *loop, rng,
        donate=(1,))
    assert "HloModule jit_decode__x16_NP64," in compiled.as_text()
    calls = _kernel_calls(compiled)
    names = sorted(name.rsplit(".", 1)[0] for name, _, _ in calls)
    assert names == ["mla_read"] * 3 + ["moe_experts"] * 2 + ["paged_write"]
    bodies = _while_bodies(compiled)
    for name, _, _ in calls:
        inside = _computation_of(compiled, name) in bodies
        assert inside == (not name.startswith("paged_write")), name
    _assert_pool_in_place(compiled, pools)
    expert = cfg.held * cfg.dim * cfg.expert_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < expert


@pytest.mark.parametrize("bucket", [2048, 4096])
def test_mla_moe_prefill_compiles_with_two_widths(topo, as_tpu, bucket):
    """One prompt of the cell's buckets: flash attention with keys of 192
    and values of 128 (resident at both), the gated experts as a grouped
    product, the window's latent plane written into the donated pool."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, loop, rng = _mla_moe_programs(topo)
    rows = chips.shape((1,), jnp.int32)
    compiled = _compile(
        _named_after(engine._prefill_fn(bucket, 1),
                     f"mla-moe-paged-prefill-{bucket}x1"),
        params, *pools, chips.shape((1, bucket), jnp.int32),
        chips.shape((1, bucket // PAGE), jnp.int32), rows, rows, *loop,
        chips.shape((1,), jnp.float32), rng, donate=(1, 6, 7, 8))
    assert f"HloModule jit_prefill__{bucket}x1," in compiled.as_text()
    names = sorted(name.rsplit(".", 1)[0]
                   for name, _, _ in _kernel_calls(compiled))
    assert names == ["flash_prefill"] * 3 + ["moe_experts"] * 2
    pool_bytes = sum(np.prod(p.shape) * p.dtype.itemsize for p in pools)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # the window's temporaries (0.34 GiB at 2,048, 0.6 at 4,096), never a
    # copy of the 1.9 GiB pool
    assert mem.temp_size_in_bytes < 1 * GIB


# -- the mla_moe family with a mixed residual stream and YaRN (ISSUE 39) --------
def _xing_programs(topo):
    """Xing4.0-29B-A4B's published widths (4 copies of 3,584), the two
    dense blocks and one expert block deep, 96 slots and 865 pages as the
    cell sizes them."""
    return _mla_moe_programs(topo, slots=96, n_pages=865,
                             preset="xing4_0_29b_a4b_ep8")


def test_the_residual_mix_compiles_two_kernels_a_sublayer(topo, as_tpu):
    """The cell's decode program shape (96 slots, table 16 wide): around
    each of a block's two sublayers the stream of [96, 14336] goes through
    `mhc_pre` and `mhc_post`, kernels of their own names inside the step
    loop beside the latent read and the experts, ONE block each (no pad,
    no ragged last block); the pool aliased, nothing pool-sized copied."""
    from gofr_tpu.tpu.executor import _named_after

    from gofr_tpu.ops.paged_attention import fold_of, fold_widths

    cfg, chips, engine, params, pools, loop, rng = _xing_programs(topo)
    assert cfg.hc_mult == 4 and cfg.first_dense == 2
    # rows of 1-9 pages under a fold of 8: the read's width branches
    assert fold_widths(fold_of(pools, 16)) == (8, 4, 2)
    assert params["layers"][2]["ffn_hc_phi"].shape == (24, 14336)
    assert params["layers"][2]["ffn_hc_phi"].dtype == jnp.float32
    compiled = _compile(
        _named_after(engine._decode_fn_paged(16, 16),
                     "mla-moe-paged-decode-x16-NP16"),
        params, *pools, chips.shape((96, 16), jnp.int32), *loop, rng,
        donate=(1,))
    calls = _kernel_calls(compiled)
    names = sorted(name.rsplit(".", 1)[0] for name, _, _ in calls)
    assert names == (["mhc_post"] * 6 + ["mhc_pre"] * 6 + ["mla_read"] * 3
                     + ["moe_experts"] + ["paged_write"])
    bodies = _while_bodies(compiled)
    for name, _, _ in calls:
        inside = _computation_of(compiled, name) in bodies
        assert inside == (not name.startswith("paged_write")), name
    assert all("bf16[96,14336]" in line.partition(" = ")[2][:40]
               for line in _instructions(compiled, "mhc_post"))
    _assert_pool_in_place(compiled, pools)


def _instructions(compiled, kernel: str) -> list:
    found = [line for line in compiled.as_text().splitlines()
             if line.strip().removeprefix("ROOT ").startswith("%" + kernel)]
    assert len(found) == 6
    return found


@pytest.mark.parametrize("K,bucket", [
    (16, 128),      # the cell's widest: [2048, 14336] (59 MB), 16 tiles
    (1, 64),        # 64 rows: one block
    (3, 64)])       # 192 rows: a tile and a half, padded to two
def test_the_residual_mix_compiles_in_a_prefill(topo, as_tpu, K, bucket):
    """The stream of a prefill is mixed by the two kernels a tile of 128
    rows at a time, and more rows than a tile that are no multiple of it
    are padded to one (ops/mhc.py `_whole_tiles`): no shape makes a ragged
    last block, which once stopped the chip (PR 39). Flash attention with
    YaRN's scale; the window's latent plane written into the donated
    pool."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, loop, rng = _xing_programs(topo)
    rows = chips.shape((K,), jnp.int32)
    compiled = _compile(
        _named_after(engine._prefill_fn(bucket, K),
                     f"mla-moe-paged-prefill-{bucket}x{K}"),
        params, *pools, chips.shape((K, bucket), jnp.int32),
        chips.shape((K, 1), jnp.int32), rows, rows, *loop,
        chips.shape((K,), jnp.float32), rng, donate=(1, 6, 7, 8))
    calls = _kernel_calls(compiled)
    names = sorted(name.rsplit(".", 1)[0] for name, _, _ in calls)
    assert names == (["flash_prefill"] * 3 + ["mhc_post"] * 6
                     + ["mhc_pre"] * 6 + ["moe_experts"])
    # every block of `mhc_post` is whole: the rows themselves up to a tile,
    # whole tiles of the padded stream above
    rows = K * bucket
    padded = rows if rows <= 128 else -(-rows // 128) * 128
    assert all(f"bf16[{padded},14336]" in line.partition(" = ")[2][:40]
               for line in _instructions(compiled, "mhc_post"))
    assert compiled.memory_analysis().temp_size_in_bytes < 1 * GIB


# -- the afmoe family's step programs (ISSUE 33) --------------------------------
def _afmoe_programs(topo, slots=32, n_pages=2300):
    """(config, chips, engine shell, abstract params, the pools of both page
    groups, loop state, rng) at Trinity-Large-Preview's published widths,
    three blocks deep (dense and sliding, experts and sliding, experts and
    full), the pools as the cell sizes them: the full group's as configured,
    the window group's every slot's ring of 34 pages."""
    from gofr_tpu.models.afmoe import (FLOAT32_LEAVES, FULL, SLIDING,
                                       AfmoeConfig, layer_shapes)
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(
        AfmoeConfig.trinity_large_preview_ep8(), n_layers=3,
        layer_types=(SLIDING, SLIDING, FULL), attn_impl="flash")
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    params = {
        "tok_emb": chips.shape((cfg.vocab_size, cfg.dim), jnp.bfloat16),
        "final_norm": chips.shape((cfg.dim,), jnp.bfloat16),
        "lm_head": chips.shape((cfg.dim, cfg.vocab_size), jnp.bfloat16),
        "layers": [{name: chips.shape(shape, jnp.float32 if name in FLOAT32_LEAVES
                                      else jnp.bfloat16)
                    for name, shape in layer_shapes(
                        cfg, i < cfg.first_dense).items()}
                   for i in range(cfg.n_layers)]}
    model = engine.model
    assert [(g.name, g.layers, g.ring(PAGE)) for g in model.groups] == [
        ("full", 1, None), ("window", 2, 34)]
    pools = tuple(chips.shape((group.layers,
                               n_pages if group.window is None
                               else slots * group.ring(PAGE) + 1,
                               plane.heads, plane.width, PAGE), jnp.bfloat16)
                  for group in model.groups for plane in model.planes)
    return (cfg, chips, engine, params, pools, _loop_state(chips, slots),
            chips.shape((2,), jnp.uint32))


def test_afmoe_decode_step_compiles_with_both_groups_in_place(topo, as_tpu):
    """The cell's decode program shape (32 slots, a full-group table 128
    wide, the window group's ring of 34) at the published widths: the full
    block's read under `paged_read`, the sliding blocks' under
    `window_read` (a lower bound a row, its table a ring), the tiled gated
    experts (two tiles of 1536 of an expert's 3072: three matrices of 18.9
    MB do not fit VMEM twice over), a flush a page group outside the scan;
    the four pools aliased and nothing pool-sized or expert-sized copied."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, loop, rng = _afmoe_programs(topo)
    assert [p.shape for p in pools] == [(1, 2300, 8, 128, PAGE)] * 2 + [
        (2, 1089, 8, 128, PAGE)] * 2
    compiled = _compile(
        _named_after(engine._decode_fn_paged(16, 128),
                     "afmoe-paged-decode-x16-NP128"),
        params, *pools, chips.shape((32, 128), jnp.int32),
        chips.shape((32, 34), jnp.int32), *loop, rng, donate=(1, 2, 3, 4))
    assert "HloModule jit_decode__x16_NP128," in compiled.as_text()
    calls = _kernel_calls(compiled)
    names = sorted(name.rsplit(".", 1)[0] for name, _, _ in calls)
    assert names == ["moe_experts"] * 2 + ["paged_read"] + [
        "paged_write"] * 2 + ["window_read"] * 2
    bodies = _while_bodies(compiled)
    for name, _, _ in calls:
        inside = _computation_of(compiled, name) in bodies
        assert inside == (not name.startswith("paged_write")), name
    _assert_pool_in_place(compiled, pools)
    expert = cfg.held * cfg.dim * cfg.expert_dim * 2
    assert compiled.memory_analysis().temp_size_in_bytes < expert


def test_afmoe_prefill_compiles_windowed_and_in_pieces(topo, as_tpu):
    """One prompt of the cell's widest bucket (12,288 tokens: K and V a head
    stream, 6.3 MB): flash attention told the window on the sliding blocks,
    the token-wise half in three pieces of 4,096 tokens, the tiled experts
    as a grouped product, both groups' windows written into the donated
    pools through a table a group."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, loop, rng = _afmoe_programs(topo)
    rows, bucket = chips.shape((1,), jnp.int32), 12288
    tables = [chips.shape((1, bucket // PAGE), jnp.int32)] * 2
    compiled = _compile(
        _named_after(engine._prefill_fn(bucket, 1),
                     f"afmoe-paged-prefill-{bucket}x1"),
        params, *pools, chips.shape((1, bucket), jnp.int32), *tables, rows,
        rows, *loop, chips.shape((1,), jnp.float32), rng,
        donate=(1, 2, 3, 4, 10, 11, 12))
    assert f"HloModule jit_prefill__{bucket}x1," in compiled.as_text()
    names = sorted(name.rsplit(".", 1)[0]
                   for name, _, _ in _kernel_calls(compiled))
    assert names == ["flash_prefill"] * 3 + ["moe_experts"] * 2
    pool_bytes = sum(np.prod(p.shape) * p.dtype.itemsize for p in pools)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes
    # the window's temporaries, never a copy of a pool (1.1 and 1.06 GiB)
    # nor the grouped experts' rows for every (token, pick) pair of 12k
    assert mem.temp_size_in_bytes < 2 * GIB


# -- the kda_moe family's step programs (ISSUE 41) ------------------------------
def _kda_moe_programs(topo, slots=256, n_pages=2561):
    """(config, chips, engine shell, abstract params, pools, state, loop
    state, rng) of the cell `solar-open2-250b-ep8.decode256-closed`: the
    whole cut, one period of four blocks at the published widths."""
    from gofr_tpu.models.kda_moe import (FLOAT32_LEAVES, KdaMoeConfig,
                                         layer_shapes)
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(KdaMoeConfig.solar_open2_250b_ep8(),
                              attn_impl="flash")
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    params = {
        "tok_emb": chips.shape((cfg.vocab_size, cfg.dim), jnp.bfloat16),
        "final_norm": chips.shape((cfg.dim,), jnp.bfloat16),
        "lm_head": chips.shape((cfg.dim, cfg.vocab_size), jnp.bfloat16),
        "layers": [{name: chips.shape(shape, jnp.float32 if name in FLOAT32_LEAVES
                                      else jnp.bfloat16)
                    for name, shape in layer_shapes(
                        cfg, i in cfg.gqa_layers).items()}
                   for i in range(cfg.n_layers)]}
    pools = _pools(chips, cfg.n_kv_heads, cfg.head_dim, jnp.bfloat16,
                   n_pages, cfg.kv_layers)
    state = tuple(chips.shape(shape, dtype)
                  for shape, dtype in engine.model.state_shapes(slots))
    return (cfg, chips, engine, params, pools, state,
            _loop_state(chips, slots), chips.shape((2,), jnp.uint32))


def test_kda_moe_decode_step_compiles_with_its_state_in_place(topo, as_tpu):
    """The cell's decode program shape (256 slots, 2,561 pages, table 16
    wide) over one GQA and three KDA blocks at the published widths: the
    per-slot state (a 4 MiB matrix state a slot a KDA block, 3.3 GB in all)
    and the pools are aliased, no state-sized or expert-sized copy is made,
    the module is named `jit_decode...` and the new kernel's instruction
    after its scope, in the scan's body."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, state, loop, rng = \
        _kda_moe_programs(topo)
    compiled = _compile(
        _named_after(engine._decode_fn_paged(16, 16),
                     "kda-moe-paged-decode-x16-NP16"),
        params, *pools, chips.shape((256, 16), jnp.int32), *loop, rng, *state,
        donate=(1, 2, 8, 9))
    assert "HloModule jit_decode__x16_NP16," in compiled.as_text()
    calls = _kernel_calls(compiled)
    names = sorted(name.rsplit(".", 1)[0] for name, _, _ in calls)
    assert names == ["kda_update"] * 3 + ["moe_experts"] * 4 + [
        "paged_read", "paged_write"]
    bodies = _while_bodies(compiled)
    assert all(_computation_of(compiled, name) in bodies
               for name, _, _ in calls if name.startswith("kda_update"))
    held = sum(np.prod(a.shape) * a.dtype.itemsize for a in pools + state)
    assert held > 4.5e9
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    expert = cfg.held * cfg.dim * cfg.expert_dim * 2
    assert mem.temp_size_in_bytes < expert // 2, (
        f"{mem.temp_size_in_bytes / GIB:.2f} GiB of temporaries: a copy of "
        f"the state or of a block's experts")


def test_kda_moe_prefill_compiles_with_its_state_in_place(topo, as_tpu):
    """The cell's widest admission (16 x 128): the slots' state rows (16 x
    12.6 MB) are written into the donated state, the KDA blocks run the
    chunkwise form (no kernel of their own, no scan over tokens), the
    experts as a grouped product (one kernel a block), the GQA block as the
    flash kernel; the temporaries fit beside 11.3 GB."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, state, loop, rng = \
        _kda_moe_programs(topo)
    K, bucket = 16, 128
    rows = chips.shape((K,), jnp.int32)
    compiled = _compile(
        _named_after(engine._prefill_fn(bucket, K),
                     "kda-moe-paged-prefill-128x16"),
        params, *pools, chips.shape((K, bucket), jnp.int32),
        chips.shape((K, bucket // PAGE), jnp.int32), rows, rows, *loop,
        chips.shape((K,), jnp.float32), rng, *state,
        donate=(1, 2, 7, 8, 9, 12, 13))
    assert "HloModule jit_prefill__128x16," in compiled.as_text()
    names = sorted(name.rsplit(".", 1)[0]
                   for name, _, _ in _kernel_calls(compiled))
    assert names.count("moe_experts") == 4 and "flash_prefill" in names
    assert "kda_update" not in names
    held = sum(np.prod(a.shape) * a.dtype.itemsize for a in pools + state)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 3 * GIB


# -- the sparse_linear family (ISSUE 46) ---------------------------------------
def _sparse_linear_programs(topo, slots=64, n_pages=7681):
    """(config, chips, engine shell, abstract params, pools, state, loop
    state, rng) of the cell `minicpm-sala-pp4.longctx-closed`: the whole
    cut, published blocks 9-16 at the published widths, three pools."""
    from gofr_tpu.models.sparse_linear import (SparseLinearConfig,
                                               layer_shapes)
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = dataclasses.replace(SparseLinearConfig.minicpm_sala_pp4(),
                              attn_impl="flash")
    chips = Chips(topo, 1)
    engine = _engine_shell(PagedLLMEngine, cfg, None)
    params = {
        "tok_emb": chips.shape((cfg.vocab_size, cfg.dim), jnp.bfloat16),
        "final_norm": chips.shape((cfg.dim,), jnp.bfloat16),
        "lm_head": chips.shape((cfg.dim, cfg.vocab_size), jnp.bfloat16),
        "layers": [{name: chips.shape(shape, jnp.bfloat16)
                    for name, shape in layer_shapes(cfg, mixer).items()}
                   for mixer in cfg.mixers]}
    model = engine.model
    pools = tuple(chips.shape(plane.pool_shape(model.kv_layers, n_pages,
                                               PAGE), jnp.bfloat16)
                  for plane in model.planes)
    state = tuple(chips.shape(shape, dtype)
                  for shape, dtype in model.state_shapes(slots))
    return (cfg, chips, engine, params, pools, state,
            _loop_state(chips, slots), chips.shape((2,), jnp.uint32))


def test_sparse_linear_decode_step_compiles_with_three_pools_in_place(
        topo, as_tpu):
    """The cell's decode program shape (64 slots, 7,681 pages, table 128
    wide) over two sparse and six lightning blocks at the published
    widths: the three pools (K, V and the compressed keys, a column every
    16 tokens) and the per-slot state (a 2 MiB matrix a slot a lightning
    block, 0.8 GB in all) are aliased, no pool-sized or state-sized copy is
    made, the module is named `jit_decode...`, and the choice, the read and
    the update are kernels named after their scopes, in the scan's body;
    one flush of K and V outside it."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, state, loop, rng = \
        _sparse_linear_programs(topo)
    compiled = _compile(
        _named_after(engine._decode_fn_paged(16, 128),
                     "sparse-linear-paged-decode-x16-NP128"),
        params, *pools, chips.shape((64, 128), jnp.int32), *loop, rng, *state,
        donate=(1, 2, 3, 9, 10))
    assert "HloModule jit_decode__x16_NP128," in compiled.as_text()
    calls = _kernel_calls(compiled)
    names = sorted(name.rsplit(".", 1)[0] for name, _, _ in calls)
    assert names == ["lightning_update"] * 6 + ["paged_write"] + [
        "sparse_read"] * 2 + ["sparse_select"] * 2
    bodies = _while_bodies(compiled)
    assert all((_computation_of(compiled, name) in bodies)
               == (not name.startswith("paged_write"))
               for name, _, _ in calls)
    held = sum(np.prod(a.shape) * a.dtype.itemsize for a in pools + state)
    assert 2.8e9 < held < 2.9e9
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    # the gathered compressed keys, the logits and the step's activations;
    # a copy of a K or V pool would be 1 GB, of the state 0.8
    assert mem.temp_size_in_bytes < 0.7 * GIB, (
        f"{mem.temp_size_in_bytes / GIB:.2f} GiB of temporaries: a copy of "
        f"a pool or of the state")


def test_sparse_linear_prefill_compiles_dense_and_chosen(topo, as_tpu):
    """The cell's widest admission (1 x 12,288): the queries under
    dense_len run the flash kernel, the 4,096 past it the masked flash
    kernel over the blocks each chose (a kernel of its own name a sparse
    block), the lightning blocks the chunkwise form (no kernel of their
    own, no scan over tokens); the slot's state row and three windows are
    written into the donated state and pools; the temporaries (a 16,384-wide
    FFN over 12,288 tokens) fit beside 8.5 GB."""
    from gofr_tpu.tpu.executor import _named_after

    cfg, chips, engine, params, pools, state, loop, rng = \
        _sparse_linear_programs(topo)
    K, bucket = 1, 12288
    rows = chips.shape((K,), jnp.int32)
    compiled = _compile(
        _named_after(engine._prefill_fn(bucket, K),
                     "sparse-linear-paged-prefill-12288x1"),
        params, *pools, chips.shape((K, bucket), jnp.int32),
        chips.shape((K, bucket // PAGE), jnp.int32), rows, rows, *loop,
        chips.shape((K,), jnp.float32), rng, *state,
        donate=(1, 2, 3, 8, 9, 10, 13, 14))
    assert "HloModule jit_prefill__12288x1," in compiled.as_text()
    names = sorted(name.rsplit(".", 1)[0]
                   for name, _, _ in _kernel_calls(compiled))
    assert names == ["flash_prefill"] * 2 + ["sparse_prefill"] * 2
    held = sum(np.prod(a.shape) * a.dtype.itemsize for a in pools + state)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 3 * GIB
