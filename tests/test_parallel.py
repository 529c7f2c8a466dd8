"""Multi-chip tier on the 8-device virtual CPU mesh: sharding, ring attention,
sharded training step, MoE. Real compiles, real collectives, no hardware."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from gofr_tpu.models.llama import LlamaConfig, llama_forward_nocache, llama_init
from gofr_tpu.models.moe import MoELlamaConfig, moe_llama_forward_nocache, moe_llama_init
from gofr_tpu.parallel import (MeshPlan, batch_spec, llama_param_specs,
                               make_mesh, shard_params)
from gofr_tpu.train import make_train_step


def test_mesh_plan_factorize():
    assert MeshPlan.factorize(8) == MeshPlan(dp=2, sp=2, tp=2)
    assert MeshPlan.factorize(4) == MeshPlan(sp=2, tp=2)
    assert MeshPlan.factorize(2) == MeshPlan(tp=2)
    assert MeshPlan.factorize(1) == MeshPlan()
    assert MeshPlan.factorize(6).n_devices == 6


def test_make_mesh_all_axes_present():
    mesh = make_mesh(MeshPlan(dp=2, sp=2, tp=2))
    assert set(mesh.axis_names) == {"dp", "pp", "sp", "tp", "ep"}
    assert mesh.shape["tp"] == 2 and mesh.shape["pp"] == 1
    with pytest.raises(ValueError):
        make_mesh(MeshPlan(dp=16))


CFG = LlamaConfig.debug()


def test_tp_sharded_forward_matches_single_device():
    """TP=2/dp=2/sp=2 sharded forward must be numerically the single-device
    program — XLA inserts the collectives; the math cannot change."""
    params = llama_init(CFG, seed=0)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 16)), dtype=jnp.int32)

    expected = llama_forward_nocache(params, CFG, tokens)

    mesh = make_mesh(MeshPlan(dp=2, sp=2, tp=2))
    sharded_params = shard_params(params, mesh, llama_param_specs())
    sharded_tokens = jax.device_put(tokens, NamedSharding(mesh, batch_spec()))

    fwd = jax.jit(lambda p, t: llama_forward_nocache(p, CFG, t))
    got = fwd(sharded_params, sharded_tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


def test_sharded_train_step_runs_and_learns():
    params = llama_init(CFG, seed=0)
    mesh = make_mesh(MeshPlan(dp=2, sp=2, tp=2))
    params = shard_params(params, mesh, llama_param_specs())

    init_opt, train_step = make_train_step(
        lambda p, t: llama_forward_nocache(p, CFG, t))
    opt_state = init_opt(params)

    step = jax.jit(train_step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, CFG.vocab_size, (4, 16)), dtype=jnp.int32)
    data = jax.device_put(data, NamedSharding(mesh, batch_spec()))
    tokens, targets = data[:, :-1], data[:, 1:]

    losses = []
    for _ in range(5):
        params, opt_state, metrics = step(params, opt_state, tokens, targets)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]  # same batch -> loss must fall
    assert np.isfinite(losses).all()
    # params stayed sharded (no silent full replication); size-1 axes may be
    # normalized away, so assert the tp dim specifically
    wq = params["layers"]["wq"]
    assert wq.sharding.spec[-1] == "tp" 


def test_ring_attention_matches_full_attention():
    from gofr_tpu.ops.ring_attention import ring_attention

    B, T, H, Hkv, dh = 2, 32, 4, 2, 16
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, dh)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, dh)), dtype=jnp.float32)

    # reference: plain causal GQA attention
    import math

    G = H // Hkv
    qg = q.reshape(B, T, Hkv, G, dh)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), dtype=bool))
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    expected = jnp.einsum("bhgts,bshd->bthgd", probs, v).reshape(B, T, H, dh)

    mesh = make_mesh(MeshPlan(sp=8))
    spec = PartitionSpec(None, "sp", None, None)
    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))
    got = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_differentiable():
    from gofr_tpu.ops.ring_attention import ring_attention

    B, T, H, dh = 1, 16, 2, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)

    mesh = make_mesh(MeshPlan(sp=8))
    spec = PartitionSpec(None, "sp", None, None)

    def loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert float(jnp.abs(grads[0]).sum()) > 0


MOE_CFG = MoELlamaConfig.debug()


def test_moe_forward_and_aux_loss():
    params = moe_llama_init(MOE_CFG, seed=0)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, MOE_CFG.vocab_size, (2, 8)), dtype=jnp.int32)
    logits, aux = moe_llama_forward_nocache(params, MOE_CFG, tokens)
    assert logits.shape == (2, 8, MOE_CFG.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()
    # balanced-ish router on random init: aux near 1 (its minimum is 1)
    assert 0.5 < float(aux) < 4.0


@pytest.mark.slow  # heavyweight shard_map train-step compile: the
# forward/parity coverage for this topology stays in tier-1; the
# train step runs in the slow lane
def test_moe_ep_sharded_train_step():
    """MoE train step with experts sharded over ep: compiles + loss falls."""
    params = moe_llama_init(MOE_CFG, seed=0)
    mesh = make_mesh(MeshPlan(dp=2, ep=4))
    params = shard_params(params, mesh, llama_param_specs(moe=True))

    init_opt, train_step = make_train_step(
        lambda p, t: moe_llama_forward_nocache(p, MOE_CFG, t),
        has_aux_loss=True)
    opt_state = init_opt(params)
    step = jax.jit(train_step, donate_argnums=(0, 1))

    data = jnp.asarray(np.random.default_rng(0).integers(
        0, MOE_CFG.vocab_size, (4, 16)), dtype=jnp.int32)
    data = jax.device_put(data, NamedSharding(mesh, PartitionSpec("dp", None)))
    tokens, targets = data[:, :-1], data[:, 1:]

    losses = []
    for _ in range(4):
        params, opt_state, metrics = step(params, opt_state, tokens, targets)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    spec = params["layers"]["w_gate"].sharding.spec
    assert len(spec) >= 2 and spec[1] == "ep" 


@pytest.mark.slow  # heavyweight shard_map train-step compile: the
# forward/parity coverage for this topology stays in tier-1; the
# train step runs in the slow lane
def test_pipeline_forward_matches_and_trains():
    """pp=4 GPipe forward == plain forward; grads flow through the pipeline."""
    from gofr_tpu.parallel.pipeline import pipelined_llama_forward

    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=4, n_heads=2,
                      n_kv_heads=2, ffn_dim=64, max_seq_len=64, dtype="float32")
    params = llama_init(cfg, seed=0)
    mesh = make_mesh(MeshPlan(pp=4, tp=2))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 12)), dtype=jnp.int32)

    expected = llama_forward_nocache(params, cfg, tokens)
    got = jax.jit(lambda p, t: pipelined_llama_forward(p, cfg, t, mesh,
                                                       n_microbatches=4))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)

    # grads through the pipeline schedule
    init_opt, train_step = make_train_step(
        lambda p, t: pipelined_llama_forward(p, cfg, t, mesh, n_microbatches=4),
        remat=False)
    opt_state = init_opt(params)
    step = jax.jit(train_step, donate_argnums=(0, 1))
    losses = []
    for _ in range(3):
        params, opt_state, metrics = step(params, opt_state,
                                          tokens[:, :-1], tokens[:, 1:])
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


# -- Ulysses all-to-all sequence parallelism ----------------------------------
def test_ulysses_attention_matches_full_attention():
    from gofr_tpu.ops.flash_attention import attention_reference
    from gofr_tpu.ops.ulysses import ulysses_attention

    B, T, H, Hkv, dh = 2, 32, 8, 4, 16  # GQA: Hkv=4 < sp=8 -> repeat path
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, Hkv, dh)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, Hkv, dh)), dtype=jnp.float32)
    expected = attention_reference(q, k, v, causal=True)

    mesh = make_mesh(MeshPlan(sp=8))
    spec = PartitionSpec(None, "sp", None, None)
    fn = jax.jit(jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))
    np.testing.assert_allclose(np.asarray(fn(q, k, v)), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_matches_ring():
    from gofr_tpu.ops.ring_attention import ring_attention
    from gofr_tpu.ops.ulysses import ulysses_attention

    B, T, H, dh = 1, 64, 8, 8
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)

    mesh = make_mesh(MeshPlan(sp=4, dp=2))
    spec = PartitionSpec(None, "sp", None, None)

    def wrap(fn):
        return jax.jit(jax.shard_map(
            lambda q, k, v: fn(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))

    np.testing.assert_allclose(np.asarray(wrap(ulysses_attention)(q, k, v)),
                               np.asarray(wrap(ring_attention)(q, k, v)),
                               rtol=1e-5, atol=1e-5)


def test_ulysses_differentiable():
    from gofr_tpu.ops.ulysses import ulysses_attention

    B, T, H, dh = 1, 16, 8, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, H, dh)), dtype=jnp.float32)

    mesh = make_mesh(MeshPlan(sp=8))
    spec = PartitionSpec(None, "sp", None, None)

    def loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)
        return jnp.sum(out ** 2)

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)
    assert float(jnp.abs(grads[0]).sum()) > 0


def test_ulysses_rejects_indivisible_heads():
    from gofr_tpu.ops.ulysses import ulysses_attention

    mesh = make_mesh(MeshPlan(sp=8))
    spec = PartitionSpec(None, "sp", None, None)
    q = jnp.ones((1, 16, 6, 8))  # 6 heads not divisible by sp=8
    with pytest.raises(ValueError, match="divide"):
        jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, axis_name="sp"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, q, q)


# -- sequence-parallel llama forward ------------------------------------------
def test_sp_llama_forward_matches_dense():
    from gofr_tpu.parallel.longcontext import sp_llama_forward

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                         dtype=jnp.int32)
    expected = llama_forward_nocache(params, cfg, tokens)
    mesh = make_mesh(MeshPlan(dp=2, sp=4))
    for attn in ("ring", "ulysses"):
        got = jax.jit(lambda p, t, a=attn: sp_llama_forward(
            p, cfg, t, mesh, attn=a))(params, tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=2e-4, atol=2e-4, err_msg=attn)


@pytest.mark.slow  # heavyweight shard_map train-step compile: the
# forward/parity coverage for this topology stays in tier-1; the
# train step runs in the slow lane
def test_sp_llama_forward_trains():
    from gofr_tpu.parallel.longcontext import make_sp_forward

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    mesh = make_mesh(MeshPlan(sp=8))
    init_opt, train_step = make_train_step(make_sp_forward(cfg, mesh),
                                           remat=False)
    opt_state = init_opt(params)
    step = jax.jit(train_step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 33)),
                       dtype=jnp.int32)
    losses = []
    for _ in range(3):
        params, opt_state, metrics = step(params, opt_state,
                                          data[:, :-1], data[:, 1:])
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_sp_llama_forward_rejects_indivisible_seq():
    from gofr_tpu.parallel.longcontext import sp_llama_forward

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    mesh = make_mesh(MeshPlan(sp=8))
    with pytest.raises(ValueError, match="divide"):
        sp_llama_forward(params, cfg, jnp.ones((1, 30), dtype=jnp.int32), mesh)


# -- multi-host launcher ------------------------------------------------------
def test_multihost_spec_parsing():
    from gofr_tpu.config import MockConfig
    from gofr_tpu.parallel.multihost import MultiHostSpec, initialize_from_config

    # unconfigured -> no-op (single-process path)
    assert MultiHostSpec.from_config(MockConfig({})) is None
    assert initialize_from_config(MockConfig({})) is None

    spec = MultiHostSpec.from_config(MockConfig({
        "JAX_COORDINATOR_ADDR": "10.0.0.1:1234",
        "JAX_NUM_PROCESSES": "4",
        "JAX_PROCESS_ID": "2",
        "JAX_LOCAL_DEVICE_IDS": "0, 1",
    }))
    assert spec.coordinator == "10.0.0.1:1234"
    assert (spec.num_processes, spec.process_id) == (4, 2)
    assert spec.local_device_ids == [0, 1]

    with pytest.raises(ValueError, match="out of range"):
        MultiHostSpec.from_config(MockConfig({
            "JAX_COORDINATOR_ADDR": "x:1", "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": "2"}))


def test_process_local_batch_single_process():
    from gofr_tpu.parallel.multihost import global_mesh, process_local_batch

    mesh = global_mesh(dp=2, sp=2, tp=2)
    data = np.arange(4 * 8, dtype=np.int32).reshape(4, 8)
    arr = process_local_batch(data, mesh)
    assert arr.shape == (4, 8)
    np.testing.assert_array_equal(np.asarray(arr), data)
    assert arr.sharding.spec == PartitionSpec("dp", "sp")
