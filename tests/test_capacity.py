"""HBM capacity planner: analytic fit, clamping, engine integration.

The planner is the guard the round-2 bench lacked (RESOURCE_EXHAUSTED at
boot config): params + caches + transients vs a device budget, clamping
(n_slots, max_seq_len) until the config fits. Pure arithmetic — testable
with a fake 16 GB budget and no device allocation.
"""

import pytest

from gofr_tpu.models.llama import LlamaConfig
from gofr_tpu.tpu.capacity import (CapacityPlan, kv_cache_bytes, params_bytes,
                                   plan_capacity, prefill_temp_bytes)

GIB = 1 << 30


def test_kv_cache_bytes_formula():
    cfg = LlamaConfig.llama1b()  # L=16, Hkv=8, dh=64, bf16
    # 2 caches * 16L * 8B * 1024S * 8Hkv * 64dh * 2 bytes
    assert kv_cache_bytes(cfg, 8, 1024) == 2 * 16 * 8 * 1024 * 8 * 64 * 2


def test_params_bytes_matches_param_count():
    cfg = LlamaConfig.llama1b()
    assert params_bytes(cfg) == cfg.param_count() * 2  # bf16


def test_plan_fits_small_config():
    cfg = LlamaConfig.llama1b()
    plan = plan_capacity(cfg, n_slots=8, max_seq_len=512, budget_bytes=16 * GIB,
                         prefill_buckets=(16, 64, 128, 256, 512))
    assert plan.fits and not plan.clamped
    assert plan.n_slots == 8 and plan.max_seq_len == 512
    assert plan.peak_bytes < 16 * GIB


def test_plan_clamps_oversized_config():
    """Round-2's fatal config (128 slots x 1024 seq, Llama-1B, 16GB) must be
    clamped to something that fits rather than served as-is."""
    cfg = LlamaConfig.llama1b()
    plan = plan_capacity(cfg, n_slots=128, max_seq_len=8192,
                         budget_bytes=16 * GIB,
                         prefill_buckets=(16, 64, 128, 256, 512, 1024))
    assert plan.fits and plan.clamped
    assert plan.peak_bytes <= int(16 * GIB * 0.92)
    assert plan.n_slots >= 1 and plan.max_seq_len >= 128
    # buckets beyond the clamped seq len are dropped
    assert all(b <= plan.max_seq_len for b in plan.prefill_buckets)


def test_plan_unclamped_reports_misfit():
    cfg = LlamaConfig.llama3_8b()
    plan = plan_capacity(cfg, n_slots=256, max_seq_len=8192,
                         budget_bytes=16 * GIB, clamp=False)
    assert not plan.fits and not plan.clamped
    assert plan.n_slots == 256  # untouched


def test_plan_raises_when_model_cannot_fit():
    cfg = LlamaConfig.llama3_70b()  # ~141 GiB of bf16 params
    with pytest.raises(ValueError, match="cannot serve"):
        plan_capacity(cfg, n_slots=8, max_seq_len=512, budget_bytes=16 * GIB)


def test_plan_zero_budget_passthrough():
    """CPU/unknown backends report no limit: trust the caller's config."""
    cfg = LlamaConfig.debug()
    plan = plan_capacity(cfg, n_slots=64, max_seq_len=256, budget_bytes=0)
    assert plan.fits and not plan.clamped
    assert plan.n_slots == 64


def test_plan_prefers_shedding_expensive_axis():
    """A long-context config sheds sequence before slots."""
    cfg = LlamaConfig.llama1b()
    plan = plan_capacity(cfg, n_slots=4, max_seq_len=8192,
                         budget_bytes=4 * GIB, prefill_buckets=(128,))
    assert plan.fits
    assert plan.n_slots >= 2  # slots survived; sequence took the cuts
    assert plan.max_seq_len < 8192


def test_engine_routes_through_plan():
    """PagedLLMEngine(budget_bytes=...) clamps its own config at
    construction."""
    from gofr_tpu.models.llama import llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    params = llama_init(cfg, seed=0)
    # a budget sized so the debug model fits only with a shrunken config:
    # debug cache at 64 slots x 256 seq = 2*2*64*256*2*16*4 bytes = 16 MiB
    eng = PagedLLMEngine(params, cfg, n_slots=64, max_seq_len=256,
                         prefill_buckets=(16, 64), budget_bytes=6 << 20)
    assert eng.plan is not None and eng.plan.fits
    assert (eng.n_slots, eng.max_seq_len) != (64, 256)  # clamped
    assert eng.plan.peak_bytes <= int((6 << 20) * 0.92)
    # the engine still serves correctly at the clamped config
    eng.start()
    try:
        out = eng.generate([1, 2, 3], max_new_tokens=4, temperature=0.0)
        assert len(out) == 4
    finally:
        eng.stop()


def test_engine_no_budget_keeps_config():
    from gofr_tpu.models.llama import llama_init
    from gofr_tpu.tpu.paging import PagedLLMEngine

    cfg = LlamaConfig.debug()
    eng = PagedLLMEngine(llama_init(cfg, seed=0), cfg, n_slots=4,
                         max_seq_len=128,
                         prefill_buckets=(16,))
    assert eng.plan is None and eng.n_slots == 4


def test_plan_summary_is_loggable():
    cfg = LlamaConfig.llama1b()
    plan = plan_capacity(cfg, 8, 512, budget_bytes=16 * GIB,
                         prefill_buckets=(128,))
    s = plan.summary()
    assert "slots=8" in s and "fits=True" in s


def test_int8_kv_plan_fits_more():
    """int8 cache (1 byte + f32 scales) plans smaller than bf16 (2 bytes):
    the same budget admits more slots/sequence."""
    import dataclasses

    from gofr_tpu.models.llama import LlamaConfig
    from gofr_tpu.tpu.capacity import plan_capacity

    cfg = LlamaConfig.llama1b()
    cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
    budget = 16 << 30
    plan_bf16 = plan_capacity(cfg, 256, 2048, budget,
                              prefill_buckets=(512,))
    plan_q8 = plan_capacity(cfg8, 256, 2048, budget,
                            prefill_buckets=(512,))
    # the same budget admits strictly more token capacity...
    assert (plan_q8.n_slots * plan_q8.max_seq_len
            > plan_bf16.n_slots * plan_bf16.max_seq_len)
    # ...because at equal shapes the int8 cache (1 byte + f32 scales per
    # dh=64 token vector) costs about half the bf16 cache
    from gofr_tpu.tpu.capacity import kv_cache_bytes

    bf16_bytes = kv_cache_bytes(cfg, 128, 2048)
    q8_bytes = (kv_cache_bytes(cfg8, 128, 2048, dtype="int8")
                + 2 * cfg.n_layers * 128 * cfg.n_kv_heads * 2048 * 4)
    assert q8_bytes < 0.6 * bf16_bytes


def test_llama3_8b_int8_weights_fit_one_v5e_chip():
    """BASELINE config 4 feasibility: 8B bf16 weights (~15 GiB) cannot fit
    a 16 GiB chip with any KV at all, but the int8 tree (~8 GiB) plans a
    real serving config."""
    import dataclasses

    from gofr_tpu.models.llama import LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.llama3_8b(), kv_dtype="int8")
    w8_bytes = cfg.param_count() * 1 + 4 * (
        # per-output-channel f32 scales: one per output column per matmul
        cfg.vocab_size * 2 + cfg.n_layers * (
            cfg.n_heads * cfg.head_dim + 2 * cfg.n_kv_heads * cfg.head_dim
            + cfg.dim + 2 * cfg.ffn_dim + cfg.dim))
    budget = 16 << 30
    plan = plan_capacity(cfg, n_slots=64, max_seq_len=512,
                         budget_bytes=budget,
                         prefill_buckets=(16, 64, 128, 256),
                         params_nbytes=w8_bytes)
    assert plan.fits
    assert plan.n_slots >= 32, plan.summary()       # real batch, not a toy
    assert plan.max_seq_len >= 256, plan.summary()
    # and the bf16 tree genuinely cannot serve at all on this budget
    with pytest.raises(ValueError, match="cannot serve"):
        plan_capacity(dataclasses.replace(cfg, kv_dtype=None),
                      n_slots=1, max_seq_len=128, budget_bytes=budget,
                      min_slots=1, min_seq=128)


def test_llama3_70b_int8_weights_fit_tp8_slice():
    """BASELINE config 5 feasibility: 70B int8 weights (~65 GiB) + an int8
    pool plan inside a v5e-8 slice's aggregate HBM (8 x 16 GiB), which is
    how the engine budgets under a mesh (per-device bytes x mesh size)."""
    import dataclasses

    from gofr_tpu.models.llama import LlamaConfig

    cfg = dataclasses.replace(LlamaConfig.llama3_70b(), kv_dtype="int8")
    w8_bytes = cfg.param_count()                  # int8: ~1 byte per param
    budget = 8 * (16 << 30)
    plan = plan_capacity(cfg, n_slots=64, max_seq_len=2048,
                         budget_bytes=budget,
                         prefill_buckets=(64, 256, 512),
                         params_nbytes=w8_bytes)
    assert plan.fits
    assert plan.n_slots * plan.max_seq_len >= 64 * 512, plan.summary()


class _Device:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "fake", stats

    def memory_stats(self):
        return self._stats


class _Client:
    def __init__(self, device):
        self.devices = [device]


def test_device_budget_is_the_devices_limit_and_zero_only_off_the_tpu():
    from gofr_tpu.tpu.capacity import device_budget_bytes

    limit = 16 << 30
    assert device_budget_bytes(
        _Client(_Device("tpu", {"bytes_limit": limit}))) == limit
    # the CPU reports no stats: 0 = "no plan", as the tests run
    assert device_budget_bytes(_Client(_Device("cpu", None))) == 0
    assert device_budget_bytes() == 0
    # a TPU that reports none must not boot unplanned
    with pytest.raises(RuntimeError, match="bytes_limit"):
        device_budget_bytes(_Client(_Device("tpu", {})))
