"""The kda_moe family's adapter: the one file of the benchmark that
imports the program for this family (gofr_tpu/models/kda_moe.py under
gofr_tpu/tpu/paging.py's PagedLLMEngine, through models/protocol.py). The
harness asks it for the engine, the warm-up, the state the engine serves
from and how to free it, and the abstract programs of the compile-only
rehearsal; the program-free half (the tree, the weights, the shape facts,
the plain forward) is benchmark/reference/kda_moe.py. The warm-up and the
way the pools and the state are given back are
benchmark/families/nemotron_h.py's, imported from there. PERF.md section 3
lists who asks what.

The engine holds two kinds of cached state and `held` declares both, each
under the dtype the configuration's `precision` mapping states for it:
the K and V page pools of the GQA blocks (`pages`) and, a slot, the KDA
blocks' matrix state and the convolution tail (`slot_state`).

The program's private names leaned on here are the ones
benchmark/families/llama_like.py leans on, for the same reason (PERF.md
section 7): `_admission_widths`, `_pow2_at_least`, `_decode_fn_paged`,
`_prefill_fn`.
"""

import jax
from families import nemotron_h as sibling

# at the top, not in the functions: a checkout whose program lacks the
# family (the parent of the PR that brought it) then fails on the cell's
# name at once, before a device is touched or a weight is made
from gofr_tpu.models.kda_moe import FLOAT32_LEAVES, KdaMoeConfig

# the program has no lower-precision path for this family (it refuses int8
# pages and int8 weights by name); the reference's own control,
# reference-int8, is the harness's and is offered for every family
CONTROLS = ()

STATE = ("kda_state", "conv_tail")      # engine.state, in this order

# the same engine over the same two kinds of state (pages and a slot's
# arrays): the warm-up and the way they are given back are nemotron_h's
warm, free = sibling.warm, sibling.free


def model_config(config: dict, dims: dict):
    return KdaMoeConfig(
        vocab_size=dims["V"], dim=dims["D"], n_layers=dims["L"],
        gqa_layers=dims["gqa"], n_heads=dims["H"], n_kv_heads=dims["Hkv"],
        head_dim=dims["dh"], kda_heads=dims["Hk"], kda_head_dim=dims["dk"],
        conv_kernel=dims["W"], gate_rank=dims["r"], n_experts=dims["E"],
        experts_held=(dims["lo"], dims["hi"]), experts_per_token=dims["k"],
        expert_dim=dims["F"], shared_dim=dims["Fs"],
        routed_scale=dims["scale"],
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
    """Every device array the live engine serves from: the page pools, the
    per-slot state arrays, every weight leaf, each under the dtype the
    configuration states for its kind. Matrices are stated, vectors (norm
    gains, the decay's constants, the router's bias) are held in
    whatever the checkpoint keeps them in."""
    precision = config["precision"]
    arrays = [{"name": name, "kind": "pages", "array": getattr(engine, name),
               "stated": precision["pages"]}
              for name in ("k_cache", "v_cache")]
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
    pool = shape((model.kv_layers, int(sizing["n_pages"]), dims["Hkv"],
                  dims["dh"], ps), dt)
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
            (params, pool, pool, shape((rows, width), jnp.int32), *loop, rng,
             *state), (1, 2, 8, 9)),
        f"prefill {K}x{bucket}": (
            engine._prefill_fn(bucket, K),
            (params, pool, pool, shape((K, bucket), jnp.int32),
             shape((K, -(-bucket // ps)), jnp.int32), krows, krows, *loop,
             shape((K,), jnp.float32), rng, *state),
            (1, 2, 7, 8, 9, 12, 13)),
    }
