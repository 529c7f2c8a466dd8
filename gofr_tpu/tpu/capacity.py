"""HBM capacity planner: fit the serving config to the device budget.

The reference never plans memory — a Go microservice trusts the heap. A
TPU serving engine cannot: params + KV pool + per-slot state + prefill
temporaries must fit a fixed HBM budget (16 GB on v5e) or the program dies
with RESOURCE_EXHAUSTED mid-serve (the round-2 bench failure mode). This
module is the fit calculation the engine runs at construction, the analog of
the reference validating its config before boot (SURVEY.md §5 failure row;
§7 hard parts "KV-cache paging/eviction in HBM").

All sizes are computed from the model config analytically — no device
allocation happens here, so the planner is unit-testable with a fake budget.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def _dtype_bytes(name: str) -> int:
    return _DTYPE_BYTES.get(name, 4)


@dataclasses.dataclass(frozen=True)
class CapacityPlan:
    """The fit decision for one serving config on one device budget."""

    n_slots: int
    max_seq_len: int
    prefill_buckets: Tuple[int, ...]
    budget_bytes: int
    params_bytes: int
    cache_bytes_max: int        # both pools at the planned max_seq_len
    prefill_temp_bytes: int      # worst fused-admission temporaries
    fits: bool
    clamped: bool                # True if the requested config was shrunk

    @property
    def peak_bytes(self) -> int:
        """Worst simultaneous residency the plan accounts for."""
        return (self.params_bytes + self.cache_bytes_max
                + self.prefill_temp_bytes)

    def summary(self) -> str:
        gb = 1 << 30
        return (f"capacity plan: slots={self.n_slots} max_seq={self.max_seq_len} "
                f"params={self.params_bytes / gb:.2f}GiB "
                f"kv={self.cache_bytes_max / gb:.2f}GiB "
                f"transient={self.prefill_temp_bytes / gb:.2f}GiB "
                f"peak={self.peak_bytes / gb:.2f}GiB "
                f"budget={self.budget_bytes / gb:.2f}GiB "
                f"fits={self.fits} clamped={self.clamped}")


def kv_token_bytes(cfg, dtype: Optional[str] = None) -> int:
    """HBM bytes one cached token occupies across the planes of its
    family's page (models/protocol.py `planes`): K and V, 2 * kv_layers *
    n_kv_heads * head_dim * itemsize, over the blocks that keep them (all
    of models/llama.py's, 6 in 52 of nemotron_h's); one latent plane of
    576 a block for mla_moe. The per-token unit of the utilization
    ledger's bandwidth model, and of the capacity plan for a family whose
    blocks all keep every token (`kv_cache_bytes` counts a sequence)."""
    return (cfg.paged_model().token_values
            * _dtype_bytes(dtype or getattr(cfg, "kv_dtype", None)
                           or cfg.dtype))


def kv_sequence_bytes(cfg, seq_len: int, dtype: Optional[str] = None,
                      page_size: int = 128) -> int:
    """HBM bytes ONE sequence of `seq_len` tokens occupies in pages: bytes
    a sequence, not bytes a token times a length. A page group without a
    window (models/protocol.py `groups`) keeps every token; a window group
    keeps at most its ring of pages, however long the sequence grows: the
    afmoe family's 13,312-token sequence keeps 13,312 tokens in its full
    block and 34 x 128 in each of four sliding blocks."""
    return (cfg.paged_model().sequence_values(seq_len, page_size)
            * _dtype_bytes(dtype or getattr(cfg, "kv_dtype", None)
                           or cfg.dtype))


def kv_cache_bytes(cfg, n_slots: int, seq_len: int,
                   dtype: Optional[str] = None) -> int:
    """The pages of `n_slots` sequences of `seq_len` tokens, every plane
    and block (K and V: 2 * [L, B, Hkv, dh, S] in the cache dtype).

    Exact HBM bytes: the S-minor layout is tile-aligned on TPU (no padding
    expansion — see init_kv_cache), so element count × itemsize is the
    physical footprint."""
    return n_slots * kv_sequence_bytes(cfg, seq_len,
                                       dtype=dtype or cfg.dtype)


def params_bytes(cfg) -> int:
    return cfg.param_count() * _dtype_bytes(cfg.dtype)


def kv_scales_bytes(cfg, n_slots: int, seq_len: int) -> int:
    """The int8 cache's f32 dequant-scale buffers: 2 * [L, B, Hkv, S]."""
    return 2 * cfg.kv_layers * n_slots * cfg.n_kv_heads * seq_len * 4


def prefill_temp_bytes(cfg, k_max: int, bucket_max: int) -> int:
    """Worst-case fused-admission temporaries for a [K, bucket] prefill.

    Dominant terms: the window a plane ([L, K, heads, width, bucket]: K and
    V, or one latent plane) the prefill writes before splicing, plus
    per-layer activations (~4 live
    [K, bucket, max(D, F)] tensors inside the scanned layer body — XLA keeps
    a small constant number live, not n_layers). The lm_head buffer is gone:
    prefill projects only [K, D] last-position rows (llama_prefill_last).
    """
    dt = _dtype_bytes(cfg.dtype)
    tmp_kv = cfg.paged_model().token_values * k_max * bucket_max * dt
    acts = 4 * k_max * bucket_max * max(cfg.dim, cfg.ffn_dim) * dt
    return tmp_kv + acts


def plan_capacity(cfg, n_slots: int, max_seq_len: int,
                  budget_bytes: int,
                  prefill_buckets: Sequence[int] = (),
                  safety_frac: float = 0.92,
                  clamp: bool = True,
                  min_slots: int = 1,
                  min_seq: int = 128,
                  params_nbytes: Optional[int] = None) -> CapacityPlan:
    """Compute the fit; optionally shrink (n_slots, max_seq_len) until it fits.

    budget_bytes: the device's bytes_limit (TPUClient.memory_stats()). A
    safety fraction keeps headroom for XLA scratch + fragmentation. The
    pool is allocated once at its planned size and never carried whole, so
    the only transient is the widest prefill's.

    Clamping halves whichever of (max_seq_len, n_slots) currently costs more
    cache bytes, so a long-context config sheds sequence first and a
    wide-batch config sheds slots first. Raises ValueError if even the
    minimum config cannot fit (serving would be impossible, matching the
    reference's fail-fast on unusable config).

    params_nbytes: the ACTUAL weight-tree bytes when known (the engine
    measures its tree) — overrides the analytic cfg-dtype estimate, which
    is 2x wrong for int8-quantized weights.
    """
    p_known = params_nbytes if params_nbytes else params_bytes(cfg)
    if budget_bytes <= 0:
        # CPU/unknown backends report no limit: trust the caller's config
        buckets = tuple(b for b in prefill_buckets if b <= max_seq_len)
        return CapacityPlan(n_slots, max_seq_len, buckets, 0,
                            p_known, kv_cache_bytes(cfg, n_slots, max_seq_len),
                            0, fits=True, clamped=False)

    p_bytes = p_known
    usable = int(budget_bytes * safety_frac)
    requested = (n_slots, max_seq_len)

    def peak(slots: int, seq: int) -> Tuple[int, int]:
        kv_dtype = getattr(cfg, "kv_dtype", None)
        # pages, and what each slot holds beside them (a recurrent state a
        # slot is fixed in size: it scales with slots, not with seq)
        cache = (kv_cache_bytes(cfg, slots, seq, dtype=kv_dtype)
                 + slots * cfg.state_bytes_per_slot)
        if kv_dtype == "int8":
            cache += kv_scales_bytes(cfg, slots, seq)
        bucket_max = max((b for b in prefill_buckets if b <= seq), default=0)
        ptmp = prefill_temp_bytes(cfg, slots, bucket_max) if bucket_max else 0
        return cache, ptmp

    while True:
        cache, ptmp = peak(n_slots, max_seq_len)
        total = p_bytes + cache + ptmp
        if total <= usable:
            break
        if not clamp:
            buckets = tuple(b for b in prefill_buckets if b <= max_seq_len)
            return CapacityPlan(n_slots, max_seq_len, buckets, budget_bytes,
                                p_bytes, cache, ptmp,
                                fits=False, clamped=False)
        if n_slots <= min_slots and max_seq_len <= min_seq:
            raise ValueError(
                f"model cannot serve within budget: params {p_bytes >> 20} MiB "
                f"+ minimum cache {cache >> 20} MiB exceed "
                f"{usable >> 20} MiB usable of {budget_bytes >> 20} MiB")
        # shed whichever axis is currently more expensive, respecting floors
        if (max_seq_len > min_seq
                and (max_seq_len >= 2 * min_seq and max_seq_len * min_slots
                     >= n_slots * min_seq or n_slots <= min_slots)):
            max_seq_len = max(min_seq, max_seq_len // 2)
        else:
            n_slots = max(min_slots, n_slots // 2)

    buckets = tuple(b for b in prefill_buckets if b <= max_seq_len)
    return CapacityPlan(n_slots, max_seq_len, buckets, budget_bytes,
                        p_bytes, cache, ptmp,
                        fits=True, clamped=(n_slots, max_seq_len) != requested)


def device_budget_bytes(tpu_client=None) -> int:
    """The first device's bytes_limit. A CPU backend reports none and gets
    0, which callers read as "no capacity plan". A TPU that reports none
    is an error: planning nothing there would let the first burst find the
    chip's memory limit instead of the boot."""
    if tpu_client is not None:
        device = tpu_client.devices[0] if tpu_client.devices else None
    else:
        import jax

        device = jax.devices()[0]
    if device is None:
        return 0
    try:
        stats = device.memory_stats() or {}
    except Exception:  # noqa: BLE001 - CPU backends have no stats
        stats = {}
    limit = int(stats.get("bytes_limit", 0))
    if not limit and device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind} reports no bytes_limit "
            f"(memory_stats: {sorted(stats)}): cannot plan its memory")
    return limit
