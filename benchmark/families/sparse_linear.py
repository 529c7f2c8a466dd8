"""The sparse_linear family's adapter: the one file of the benchmark that
imports the program for this family (gofr_tpu/models/sparse_linear.py under
gofr_tpu/tpu/paging.py's PagedLLMEngine, through models/protocol.py). The
harness asks it for the engine, the warm-up, the state the engine serves
from and how to free it, and the abstract programs of the compile-only
rehearsal; the program-free half (the tree, the weights, the shape facts,
the plain forward) is benchmark/reference/sparse_linear.py. PERF.md section 3
lists who asks what.

The engine holds three kinds of cached state and `held` declares each
under the dtype the configuration's `precision` mapping states for it: the
K and V page pools of the sparse blocks and, in the same pages, the
compressed keys the choice scores (`pages`: three pools), and, a slot, the
lightning blocks' matrix state and the sparse blocks' half-window sums
(`slot_state`).

The program's private names leaned on here are the ones
benchmark/families/llama_like.py leans on, for the same reason (PERF.md
section 7): `_prefill_program`, `_decode_program_paged`,
`_admission_widths`, `_pow2_at_least`, `_state_lock`, `_decode_fn_paged`,
`_prefill_fn`.
"""

import jax

# at the top, not in the functions: a checkout whose program lacks the
# family (the parent of the PR that brought it) then fails on the cell's
# name at once, before a device is touched or a weight is made
from gofr_tpu.models.sparse_linear import (LIGHTNING, SPARSE,
                                           SparseLinearConfig)

# the program has no lower-precision path for this family (it refuses int8
# pages and int8 weights by name); the reference's own control,
# reference-int8, is the harness's and is offered for every family
CONTROLS = ()

POOLS = ("k", "v", "compressed_k")              # engine.pools, in this order
STATE = ("lightning_state", "half_sums")        # engine.state, in this order


def warm(engine, cell: dict) -> None:
    """The cell's own programs and no others: its prefill buckets at the
    admission widths its cap can produce, and the decode table widths from
    its SHORTEST context (the cell's `shortest_context`: a row's table is
    as wide as its prompt and its answer need, and a cell of long prompts
    never meets the narrow tables; each is 20 s of compiling, twice) up to
    its longest, at the full and the half block."""
    from gofr_tpu.tpu.engine import _admission_widths
    from gofr_tpu.tpu.paging import _pow2_at_least

    cap = int(cell.get("max_prefill_batch", 0)) or engine.n_slots
    with engine._state_lock:
        for bucket in engine.prefill_buckets:
            for k in sorted(_admission_widths(engine.n_slots)):
                if k <= cap:
                    engine._prefill_program(bucket, k)
        pages_for = engine.allocator.pages_for
        first = pages_for(int(cell.get("shortest_context", 1)))
        for width in sorted({_pow2_at_least(p + 1) for p in range(
                first, pages_for(engine.max_seq_len) + 1)}):
            engine._decode_program_paged(width)
            if engine.decode_block_size > 1:
                engine._decode_program_paged(
                    width, max(1, engine.decode_block_size // 2))


def model_config(config: dict, dims: dict):
    return SparseLinearConfig(
        vocab_size=dims["V"], dim=dims["D"],
        mixers=tuple(SPARSE if m == "minicpm4" else LIGHTNING
                     for m in dims["mixers"]),
        layer_ids=dims["layer_ids"], depth=dims["depth"],
        n_heads=dims["H"], n_kv_heads=dims["Hkv"], head_dim=dims["dh"],
        lightning_heads=dims["Hl"], lightning_head_dim=dims["dl"],
        ffn_dim=dims["F"], scale_emb=dims["scale_emb"],
        scale_depth=dims["scale_depth"], dim_model_base=dims["base"],
        rope_theta=dims["theta"], kernel_size=dims["kernel"],
        kernel_stride=dims["stride"], block_size=dims["block"],
        topk=dims["topk"], init_blocks=dims["init"],
        window_size=dims["window"], dense_len=dims["dense_len"],
        chunk_size=int(config["engine"]["chunk_size"]),
        max_seq_len=int(config["engine"]["max_seq_len"]),
        rms_eps=dims["eps"], dtype=config["torch_dtype"],
        attn_impl=config["engine"]["attn_impl"])


def build(params: dict, config: dict, dims: dict, cell: dict, control,
          services: dict):
    """The engine on the program's normal path, not started. `services`
    are the executor, metrics, logger and tracer the harness made."""
    from gofr_tpu.tpu.paging import PagedLLMEngine

    sizing = config["engine"]
    return PagedLLMEngine(
        params, model_config(config, dims), n_slots=int(sizing["n_slots"]),
        max_seq_len=int(sizing["max_seq_len"]),
        page_size=int(sizing["page_size"]), n_pages=int(sizing["n_pages"]),
        prefix_cache=bool(sizing["prefix_cache"]),
        prefill_buckets=tuple(cell["prefill_buckets"]),
        max_prefill_batch=int(cell.get("max_prefill_batch", 0)),
        decode_block_size=int(sizing["decode_block_size"]),
        pipeline_depth=int(sizing["pipeline_depth"]), **services)


def held(engine, config: dict, facts: dict) -> dict:
    """Every device array the live engine serves from: the three page
    pools, the per-slot state arrays, every weight leaf, each under the
    dtype the configuration states for its kind. Matrices are stated,
    vectors (norm gains) are held in whatever the checkpoint keeps them
    in."""
    precision = config["precision"]
    arrays = [{"name": name, "kind": "pages", "array": pool,
               "stated": precision["compressed_k" if name == "compressed_k"
                                   else "pages"]}
              for name, pool in zip(POOLS, engine.pools)]
    arrays += [{"name": name, "kind": "slot_state", "array": array,
                "stated": precision[name]}
               for name, array in zip(STATE, engine.state)]
    arrays += [{"name": jax.tree_util.keystr(path), "kind": "weights",
                "array": leaf,
                "stated": precision["weights"] if leaf.ndim >= 2 else "any"}
               for path, leaf in
               jax.tree_util.tree_leaves_with_path(engine.params)]
    return {"arrays": arrays, "kinds": {
        "pages": {"unit": "token",
                  "units": engine.allocator.n_pages * engine.page_size,
                  "least_bytes": facts["cache_bytes_per_token"]},
        "slot_state": {"unit": "slot", "units": engine.n_slots,
                       "least_bytes": facts["state_bytes_per_slot"]}}}


def free(engine) -> None:
    """Give the pools and the per-slot state back, so that the reference
    runs in a freed device and `memory_peak_bytes` stays the program's."""
    for array in (*engine.pools, *engine.state):
        array.delete()
    engine.pools, engine.state = [], ()


def rehearsal(config: dict, dims: dict, cell: dict, shapes: dict, mesh,
              shape) -> dict:
    """{label: (function, abstract arguments, donated argument numbers)}:
    the cell's decode program and its widest prefill program, for
    rehearse_compile.py. One chip: `mesh` is None."""
    import jax.numpy as jnp

    from gofr_tpu.tpu.engine import _admission_widths
    from gofr_tpu.tpu.paging import PagedLLMEngine, _pow2_at_least

    sizing = config["engine"]
    engine = PagedLLMEngine.__new__(PagedLLMEngine)
    engine.cfg, engine.top_k = model_config(config, dims), 0
    engine.mesh, engine._jnp, engine.sampling_controls = mesh, jnp, False
    dt = getattr(jnp, config["torch_dtype"])
    params = jax.tree_util.tree_map(
        lambda dims_: shape(dims_, dt), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    rows, ps = int(sizing["n_slots"]), int(sizing["page_size"])
    model = engine.model
    pools = tuple(shape(plane.pool_shape(model.kv_layers,
                                         int(sizing["n_pages"]), ps), dt)
                  for plane in model.planes)
    state = tuple(shape(dims_, dtype)
                  for dims_, dtype in model.state_shapes(rows))
    loop = (shape((rows,), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,), jnp.float32))
    rng = shape((2,), jnp.uint32)
    width = _pow2_at_least(-(-int(sizing["max_seq_len"]) // ps) + 1)
    bucket = max(cell["prefill_buckets"])
    cap = int(cell.get("max_prefill_batch", 0)) or rows
    K = max(k for k in _admission_widths(rows) if k <= cap)
    krows = shape((K,), jnp.int32)
    return {
        f"decode x{sizing['decode_block_size']} NP{width}": (
            engine._decode_fn_paged(int(sizing["decode_block_size"]), width),
            (params, *pools, shape((rows, width), jnp.int32), *loop, rng,
             *state), (1, 2, 3, 9, 10)),
        f"prefill {K}x{bucket}": (
            engine._prefill_fn(bucket, K),
            (params, *pools, shape((K, bucket), jnp.int32),
             shape((K, -(-bucket // ps)), jnp.int32), krows, krows, *loop,
             shape((K,), jnp.float32), rng, *state),
            (1, 2, 3, 8, 9, 10, 13, 14)),
    }
