"""The afmoe family's adapter: the one file of the benchmark that imports
the program for this family (gofr_tpu/models/afmoe.py under
gofr_tpu/tpu/paging.py's PagedLLMEngine, through models/protocol.py). The
harness asks it for the engine, the warm-up, the state the engine serves
from and how to free it, and the abstract programs of the compile-only
rehearsal; the program-free half (the tree, the weights, the shape facts,
the plain forward) is benchmark/reference/afmoe.py. PERF.md section 3
lists who asks what.

The engine keeps TWO PAGE GROUPS (models/protocol.py `groups`): `full`,
the full_attention blocks, a page a 128 tokens of a sequence, sized by the
configuration's `n_pages`; and `window`, the sliding_attention blocks, a
ring of sliding_window / 128 + 2 pages a sequence, sized by the engine for
every slot's whole ring. A pool a plane (K, V) a group, group-major in
`engine.pools`. `held` declares the pools of BOTH groups, a kind each
(`pages:full`, `pages:window`), under the dtype the configuration's
`precision` states for pages and with the least bytes a token of that
group's blocks: a pool kept in fewer bits than stated is
`state_not_as_stated`, whichever group's it is.

The program's private names leaned on here are the ones
benchmark/families/llama_like.py leans on, for the same reason (PERF.md
section 7): `_prefill_program`, `_decode_program_paged`,
`_admission_widths`, `_pow2_at_least`, `_state_lock`, `_decode_fn_paged`,
`_prefill_fn`; and `allocators`, the groups' page ledgers.
"""

import jax

# at the top, not in the functions: a checkout whose program lacks the
# family (the parent of the PR that brought it) then fails on the cell's
# name at once, before a device is touched or a weight is made
from gofr_tpu.models.afmoe import FLOAT32_LEAVES, AfmoeConfig

# the program has no lower-precision path for this family (it refuses int8
# pages and int8 weights by name); the reference's own control,
# reference-int8, is the harness's and is offered for every family
CONTROLS = ()


def model_config(config: dict, dims: dict):
    return AfmoeConfig(
        vocab_size=dims["V"], dim=dims["D"], n_layers=dims["L"],
        first_dense=dims["dense"], layer_types=tuple(dims["kinds"]),
        n_heads=dims["H"], n_kv_heads=dims["Hkv"], head_dim=dims["dh"],
        window=dims["W"], dense_dim=dims["Fd"], n_experts=dims["E"],
        experts_held=(dims["lo"], dims["hi"]), experts_per_token=dims["k"],
        expert_dim=dims["F"], shared_dim=dims["Fs"],
        routed_scale=dims["scale"], rope_theta=dims["theta"],
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


def warm(engine, cell: dict) -> None:
    """The cell's own programs and no others: its prefill buckets at the
    admission widths its cap can produce, its decode table widths (the
    full group's: the window group's table is its ring, one width) up to
    its longest context, at the full and the half block."""
    from gofr_tpu.tpu.engine import _admission_widths
    from gofr_tpu.tpu.paging import _pow2_at_least

    cap = int(cell.get("max_prefill_batch", 0)) or engine.n_slots
    with engine._state_lock:
        for bucket in engine.prefill_buckets:
            for k in sorted(_admission_widths(engine.n_slots)):
                if k <= cap:
                    engine._prefill_program(bucket, k)
        reach = engine.allocator.pages_for(engine.max_seq_len)
        for width in sorted({_pow2_at_least(p + 1)
                             for p in range(1, reach + 1)}):
            engine._decode_program_paged(width)
            if engine.decode_block_size > 1:
                engine._decode_program_paged(
                    width, max(1, engine.decode_block_size // 2))


def held(engine, config: dict, facts: dict) -> dict:
    """Every device array the live engine serves from: a pool a plane a
    page group, every weight leaf, each under the dtype the configuration
    states for its kind. Matrices are stated, vectors (norm gains, the
    router's bias) are held in whatever the checkpoint keeps them in."""
    precision = config["precision"]
    model = engine.model
    planes = len(model.planes)
    arrays, kinds = [], {}
    for index, (group, allocator) in enumerate(zip(model.groups,
                                                   engine.allocators)):
        kind = f"pages:{group.name}"
        arrays += [{"name": f"pool:{group.name}:{plane.name}", "kind": kind,
                    "array": pool, "stated": precision["pages"]}
                   for plane, pool in zip(
                       model.planes,
                       engine.pools[index * planes:(index + 1) * planes])]
        kinds[kind] = {
            "unit": "token", "units": allocator.n_pages * engine.page_size,
            "least_bytes": facts["cache_bytes_per_token_by_group"][group.name]}
    arrays += [{"name": jax.tree_util.keystr(path), "kind": "weights",
                "array": leaf,
                "stated": precision["weights"] if leaf.ndim >= 2 else "any"}
               for path, leaf in
               jax.tree_util.tree_leaves_with_path(engine.params)]
    return {"arrays": arrays, "kinds": kinds}


def free(engine) -> None:
    """Give the pools back, so that the reference runs in a freed device
    and `memory_peak_bytes` stays the program's."""
    for pool in engine.pools:
        pool.delete()
    engine.pools = []


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
    params = jax.tree_util.tree_map_with_path(
        lambda path, dims_: shape(
            dims_, jnp.float32 if path[-1].key in FLOAT32_LEAVES else dt),
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    rows, ps = int(sizing["n_slots"]), int(sizing["page_size"])
    model = engine.model
    rings = [group.ring(ps) for group in model.groups]
    pools = tuple(
        shape((group.layers,
               int(sizing["n_pages"]) if ring is None else rows * ring + 1,
               plane.heads, plane.width, ps), dt)
        for group, ring in zip(model.groups, rings) for plane in model.planes)
    n, g = len(pools), len(rings)
    loop = (shape((rows,), jnp.int32), shape((rows,), jnp.int32),
            shape((rows,), jnp.float32))
    rng = shape((2,), jnp.uint32)
    width = _pow2_at_least(-(-int(sizing["max_seq_len"]) // ps) + 1)
    tables = tuple(shape((rows, width if ring is None else ring), jnp.int32)
                   for ring in rings)
    bucket = max(cell["prefill_buckets"])
    cap = int(cell.get("max_prefill_batch", 0)) or rows
    K = max(k for k in _admission_widths(rows) if k <= cap)
    krows = shape((K,), jnp.int32)
    at = n + g - 1
    return {
        f"decode x{sizing['decode_block_size']} NP{width}": (
            engine._decode_fn_paged(int(sizing["decode_block_size"]), width),
            (params, *pools, *tables, *loop, rng),
            tuple(range(1, 1 + n))),
        f"prefill {K}x{bucket}": (
            engine._prefill_fn(bucket, K),
            (params, *pools, shape((K, bucket), jnp.int32),
             *[shape((K, -(-bucket // ps)), jnp.int32) for _ in rings],
             krows, krows, *loop, shape((K,), jnp.float32), rng),
            tuple(range(1, 1 + n)) + (at + 5, at + 6, at + 7)),
    }
