"""Compile-only rehearsal: each cell's decode program and its widest prefill
program, for a DESCRIBED v5e:2x2 (nothing attached, nothing executed).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [--workload <cell>]

Prints, for each program, memory_analysis() (bytes on one device) and how
many Pallas kernels (tpu_custom_call) and all-reduces the compiler kept.
What the chip's compiler refuses here costs no chip time. A compile that
passes is not a chip run: no time, rate or share comes from this script.
"""

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from harness import data, serve, weights  # noqa: E402

GIB = float(1 << 30)


def rehearse(name: str, topo) -> None:
    from gofr_tpu.parallel.sharding import kv_cache_spec, serving_param_specs
    from gofr_tpu.tpu.engine import _admission_widths
    from gofr_tpu.tpu.paging import PagedLLMEngine, _pow2_at_least

    loaded = data.load_cell(name)
    config, cell = loaded["config"], loaded["cell"]
    dims = data.reference_for(config).dims_of(config)
    cfg = serve.llama_config(config, dims)
    sizing = config["engine"]
    tp = int(config["deployment"]["tp"])
    mesh = Mesh(np.array(topo.devices[:tp]), ("tp",)) if tp > 1 else None
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims_, dtype, spec=P()):
        sharding = NamedSharding(mesh, spec) if mesh is not None else one
        return jax.ShapeDtypeStruct(dims_, dtype, sharding=sharding)

    engine = PagedLLMEngine.__new__(PagedLLMEngine)
    engine.cfg, engine.mesh, engine.top_k = cfg, mesh, 0
    engine._jnp, engine.sampling_controls = jnp, False
    dt = getattr(jnp, config["torch_dtype"])
    params = jax.tree_util.tree_map(
        lambda dims_, spec: shape(dims_, dt, spec),
        weights.param_shapes(dims), serving_param_specs(),
        is_leaf=lambda x: isinstance(x, tuple))
    rows, ps = int(sizing["n_slots"]), int(sizing["page_size"])
    pool = shape((dims["L"], int(sizing["n_pages"]), dims["Hkv"], dims["dh"],
                  ps), dt, kv_cache_spec())
    state = (shape((rows,), jnp.int32), shape((rows,), jnp.int32),
             shape((rows,), jnp.float32))
    rng = shape((2,), jnp.uint32)
    width = _pow2_at_least(-(-int(sizing["max_seq_len"]) // ps) + 1)
    bucket = max(cell["prefill_buckets"])
    cap = int(cell.get("max_prefill_batch", 0)) or rows
    K = max(k for k in _admission_widths(rows) if k <= cap)
    krows = shape((K,), jnp.int32)
    programs = {
        f"decode x{sizing['decode_block_size']} NP{width}": (
            engine._decode_fn_paged(int(sizing["decode_block_size"]), width),
            (params, pool, pool, shape((rows, width), jnp.int32), *state, rng),
            (1, 2)),
        f"prefill {K}x{bucket}": (
            engine._prefill_fn(bucket, K),
            (params, pool, pool, shape((K, bucket), jnp.int32),
             shape((K, -(-bucket // ps)), jnp.int32), krows, krows, *state,
             shape((K,), jnp.float32), rng), (1, 2, 7, 8, 9)),
    }
    for label, (fn, args, donate) in programs.items():
        compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
        mem, text = compiled.memory_analysis(), compiled.as_text()
        print(json.dumps({
            "cell": name, "program": label, "chips": tp,
            "argument_gib": mem.argument_size_in_bytes / GIB,
            "alias_gib": mem.alias_size_in_bytes / GIB,
            "temp_gib": mem.temp_size_in_bytes / GIB,
            "output_gib": mem.output_size_in_bytes / GIB,
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "all_reduces": text.count("all-reduce(")}), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", action="append")
    args = parser.parse_args()
    names = args.workload or sorted(
        f[:-5] for f in os.listdir(os.path.join(BENCH_DIR, "workloads")))
    # the engine picks interpret mode from the process's backend, which is
    # the CPU here: steer it, in this script, so the real kernels lower
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in names:
        rehearse(name, topo)


if __name__ == "__main__":
    main()
