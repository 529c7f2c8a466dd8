"""The adapter of the mla_moe family with a residual stream of several mixed
copies and YaRN (Xing4.0-29B-A4B): the one file of the benchmark that
imports the program for it. The program is the mla_moe family's
(gofr_tpu/models/mla_moe.py under gofr_tpu/tpu/paging.py's PagedLLMEngine,
through models/protocol.py) with `hc_mult` > 1 and a `YarnScaling`; the
program-free half is benchmark/reference/mla_moe_hc.py. The warm-up and the
way the pools are given back are benchmark/families/mla_moe.py's, imported
from there; what names the configuration's own keys is here. PERF.md
section 3 lists who asks what.

`held` declares one pool a plane (the one latent plane) under the dtype the
configuration states for pages, every matrix under the one it states for
weights, and the mix's `phi` under the one it states for the mix (float32:
a phi kept in bfloat16 is `state_not_as_stated`). It also holds the mix's
ARITHMETIC to what the configuration states (`mix_off`): the logits of
served tokens cannot tell five Sinkhorn rounds from twenty, nor mappings
made in bfloat16 from float32 ones (PERF.md section 2: 2.4x and 1.75x the
sound `gap_mean`, under a limit that leaves the sound runs their room), so
the mappings of every sublayer, as the program computes them from the live
engine's own leaves and configuration at the rows of a prefill program's
tiles and at the rows of the decode program, are compared with the
reference's on a seeded stream, and mappings that lie further off than
`precision.mix_within` are one more array that is not as stated.

The program's private names leaned on here are the ones
benchmark/families/mla_moe.py leans on, for the same reason (PERF.md
section 7): `_decode_fn_paged`, `_prefill_fn`, `_admission_widths`,
`_pow2_at_least`.
"""

import functools
import json

import jax
import jax.numpy as jnp
from families import mla_moe as family
from reference import mla_moe_hc as reference

# at the top, not in the functions: a checkout whose program lacks the
# residual path (the parent of the PR that brought it) then fails on the
# cell's name at once, before a device is touched or a weight is made
from gofr_tpu.models.mla_moe import (FLOAT32_LEAVES, HC_LEAVES, MlaMoeConfig,
                                     YarnScaling, residual_maps)

# the program has no lower-precision path for this family; the reference's
# own control, reference-int8, is the harness's. The program's faults that
# prove the cell's limits are benchmark/tests/mla_moe_hc_faults.py's
CONTROLS = ()

warm = family.warm

PREFILL_ROWS = 256      # two of the kernels' tiles
_probed = {}            # id(engine) -> what `mix_off` read of it


def model_config(config: dict, dims: dict):
    return MlaMoeConfig(
        vocab_size=dims["V"], dim=dims["D"], n_layers=dims["L"],
        first_dense=dims["dense"], n_heads=dims["H"], q_rank=dims["rq"],
        kv_rank=dims["r"], nope_dim=dims["nope"], rope_dim=dims["rope"],
        v_dim=dims["dv"], dense_dim=dims["Fd"], n_experts=dims["E"],
        experts_held=(dims["lo"], dims["hi"]), experts_per_token=dims["k"],
        expert_dim=dims["F"], shared_dim=dims["Fs"],
        routed_scale=dims["scale"], rope_theta=dims["theta"],
        rope_scaling=YarnScaling(
            factor=dims["factor"],
            original_max_position_embeddings=dims["original"],
            beta_fast=dims["beta_fast"], beta_slow=dims["beta_slow"],
            mscale=dims["mscale_all_dim"],
            mscale_all_dim=dims["mscale_all_dim"]),
        max_seq_len=int(config["engine"]["max_seq_len"]),
        rms_eps=dims["eps"], hc_mult=dims["n"],
        hc_sinkhorn_iters=dims["iters"], hc_eps=dims["hc_eps"],
        hc_clamp=(dims["clamp_lo"], dims["clamp_hi"]),
        dtype=config["torch_dtype"], attn_impl=config["engine"]["attn_impl"])


def build(params: dict, config: dict, dims: dict, cell: dict, control,
          services: dict):
    """The engine on the program's normal path, not started."""
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


def probe_stream(rows: int, dims: dict, dtype):
    """[rows, n, D]: a token's copies share a part and differ by half as
    much again, as a stream some blocks deep does."""
    shared, own = jax.random.split(jax.random.PRNGKey(0x6d6978))
    n, D = dims["n"], dims["D"]
    return (jax.random.normal(shared, (rows, 1, D), jnp.float32)
            + 0.5 * jax.random.normal(own, (rows, n, D), jnp.float32)
            ).astype(dtype)


def mix_off(cfg, layers, dims: dict, dtype, decode_rows: int) -> dict:
    """{program: (how far the mappings lie from the reference's at that
    program's rows, the mappings of the worst block)}: the largest
    difference of an entry of H_pre, H_post or H_res over every sublayer
    and every row of one seeded stream, of PREFILL_ROWS rows (whole tiles,
    as a prefill program's) and of `decode_rows` (the decode program's one
    block). The program's side is models/mla_moe.py `residual_maps` (the
    `_mix_in` that `prefill` and `decode_step` call, under `cfg`'s own
    rounds, clamp and form) over the leaves the engine serves from; the
    reference's is its float32 `mappings` at the highest precision, from
    the same values of the stream."""
    n, C = dims["n"], reference.mix_columns(dims)
    maps = jax.jit(functools.partial(residual_maps, cfg=cfg))

    @jax.jit
    def wanted(X, w):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([jnp.concatenate(
                [pre, post, res.reshape(-1, n * n)], axis=-1)
                for pre, post, res in (
                    reference.mappings(X.astype(jnp.float32), w, sub, dims)
                    for sub in reference.SUBLAYERS)])

    out = {}
    for program, rows in (("prefill", PREFILL_ROWS), ("decode", decode_rows)):
        X = probe_stream(rows, dims, dtype)
        worst, kept = -1.0, None
        for w in layers:
            w = {name: w[name] for name in HC_LEAVES}
            got = maps(X.reshape(rows, -1), w)
            off = float(jnp.max(jnp.abs(got[..., :C] - wanted(X, w))))
            if off > worst:
                worst, kept = off, got
        out[program] = (worst, kept)
    return out


def held(engine, config: dict, facts: dict) -> dict:
    """The family's declaration, with the mix's leaves under the precision
    the configuration states for them (`phi` is a matrix and is stated, the
    scalars and biases are vectors like the norms' gains) and one entry a
    program for the mix's arithmetic: the mappings at its rows are float32
    within `precision.mix_within` of the reference's, or not as stated."""
    precision = config["precision"]
    declared = family.held(engine, config, facts)
    for entry in declared["arrays"]:
        if entry["name"].endswith("_hc_phi']"):
            entry["stated"] = precision["mix"]
    if id(engine) not in _probed:
        _probed[id(engine)] = mix_off(
            engine.cfg, engine.params["layers"], reference.dims_of(config),
            config["torch_dtype"], int(config["engine"]["n_slots"]))
        print(json.dumps({"phase": "mix", "within": precision["mix_within"],
                          "off": {program: off for program, (off, _)
                                  in _probed[id(engine)].items()}}),
              flush=True)
    for program, (off, maps) in _probed[id(engine)].items():
        within = off <= float(precision["mix_within"])
        declared["arrays"].append({
            "name": f"mappings of the {program} program", "kind": "mix",
            "array": maps, "stated": precision["mix"] if within else (
                f"{precision['mix']} within {precision['mix_within']:g} of "
                f"the reference's, and lie {off:.3g} off")})
    return declared


def free(engine) -> None:
    _probed.pop(id(engine), None)
    family.free(engine)


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
    pools = tuple(shape((model.kv_layers, int(sizing["n_pages"]),
                         plane.heads, plane.width, ps), dt)
                  for plane in model.planes)
    n = len(pools)
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
            (params, *pools, shape((rows, width), jnp.int32), *loop, rng),
            tuple(range(1, 1 + n))),
        f"prefill {K}x{bucket}": (
            engine._prefill_fn(bucket, K),
            (params, *pools, shape((K, bucket), jnp.int32),
             shape((K, -(-bucket // ps)), jnp.int32), krows, krows, *loop,
             shape((K,), jnp.float32), rng),
            tuple(range(1, 1 + n)) + (n + 5, n + 6, n + 7)),
    }
