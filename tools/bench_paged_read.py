"""The paged read kernel alone, at the benchmark cells' shapes, on the chip.

    chiprun -- python tools/bench_paged_read.py [label=path/to/paged_attention.py ...]

Times `paged_attention` of this tree, and of any other copy of the module
given as label=path (a parent's unpacked under build/, an experiment), over
the stacked pool of `internlm2-1.8b`'s cells (24 layers x 769 pages of 128
tokens, 96 rows, a table 16 wide) under two tables: `closed` (93 live rows
of about 480 tokens, as `decode-closed` holds) and `chat` (50 live rows and
46 of one page, as `chat-open` held before PR 25). One JSON line a
(kernel, pool dtype, table): microseconds a call (best of 5 runs of a
24-layer loop x 8), the share of the device's peak bytes/s over the WHOLE
live pages, and the largest error against `paged_attention_reference` on
one layer.

It is not the benchmark: it says what a kernel costs alone, never what a
cell gains (PERF.md section 5 keeps its table). It refuses a device that
is not in the benchmark's table of peaks: a CPU timing of the interpreter
is no kernel time.
"""

import importlib.util
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import gofr_tpu.ops.paged_attention  # noqa: E402
from harness import peaks  # noqa: E402  (the one table of peaks)

L, P, HKV, DH, PS, B, NP, H = 24, 769, 8, 128, 128, 96, 16, 16
STEPS = 8


def load(label: str, path: str):
    """Another copy of ops/paged_attention.py as a sibling module, so its
    relative imports resolve against this tree's package."""
    spec = importlib.util.spec_from_file_location(
        f"gofr_tpu.ops._bench_{label}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def table_of(name: str, rng):
    """(table [B, NP], lengths [B], whole live pages)."""
    if name == "closed":
        lengths = rng.integers(60, 900, size=B)
        lengths[5] = 1152
        lengths[[17, 40, 77]] = 1
    else:
        lengths = np.ones(B, np.int64)
        live = rng.permutation(B)[:50]
        lengths[live] = rng.integers(40, 700, size=50)
        lengths[live[0]] = 1100
    n_pages = -(-lengths // PS)
    table = np.zeros((B, NP), np.int32)
    free = list(rng.permutation(np.arange(1, P)))
    for b in np.flatnonzero(lengths > 1):
        table[b, :n_pages[b]] = [free.pop() for _ in range(n_pages[b])]
    return (jnp.asarray(table), jnp.asarray(lengths, jnp.int32),
            int(n_pages.sum()))


def pools_of(dtype):
    """(k_pool, v_pool, scales): one random layer tiled over the stack."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    stack = jax.jit(lambda x: jnp.tile(x[None], (L,) + (1,) * x.ndim))
    if dtype == jnp.int8:
        make = lambda k: stack(jax.random.randint(           # noqa: E731
            k, (P, HKV, DH, PS), -127, 128, jnp.int32).astype(jnp.int8))
        scales = [stack(jax.random.uniform(k, (P, HKV, PS), jnp.float32,
                                           0.005, 0.02))
                  for k in jax.random.split(k3)]
    else:
        make = lambda k: stack(jax.random.normal(            # noqa: E731
            k, (P, HKV, DH, PS), jnp.float32).astype(jnp.bfloat16))
        scales = []
    return make(k1), make(k2), scales


def time_one(module, args) -> float:
    """Microseconds a call, from the best of five 24-layer loops x STEPS."""
    def loop(q, *rest):
        def layer(l, acc):
            return acc + module.paged_attention(
                q, *rest, layer=l).astype(jnp.float32)

        return jax.lax.fori_loop(
            0, STEPS, lambda _, acc: jax.lax.fori_loop(0, L, layer, acc),
            jnp.zeros(q.shape, jnp.float32))

    fn = jax.jit(loop)
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(5):
        start = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - start)
    return best / (L * STEPS) * 1e6


def main(argv) -> None:
    device = jax.devices()[0]
    peak_bytes_s = peaks.of(device.device_kind)["hbm_bytes_per_s"]
    kernels = {"tree": gofr_tpu.ops.paged_attention}
    kernels.update((label, load(label, path)) for label, path in
                   (arg.split("=", 1) for arg in argv))
    reference = gofr_tpu.ops.paged_attention.paged_attention_reference
    q = jax.random.normal(jax.random.PRNGKey(1), (B, H, DH), jnp.bfloat16)
    for dtype in (jnp.bfloat16, jnp.int8):
        k_pool, v_pool, scales = pools_of(dtype)
        for name in ("closed", "chat"):
            table, lengths, pages = table_of(name, np.random.default_rng(1))
            args = (q, k_pool, v_pool, table, lengths, *scales)
            floor_us = (pages * 2 * HKV * DH * PS * k_pool.dtype.itemsize
                        / peak_bytes_s * 1e6)
            want = np.asarray(jax.jit(lambda q, k, v, t, n, *s: reference(
                q.astype(jnp.float32), k[L - 1], v[L - 1], t, n,
                *[x[L - 1] for x in s]))(*args))
            for label, module in kernels.items():
                us = time_one(module, args)
                got = jax.jit(lambda *a: module.paged_attention(
                    *a, layer=jnp.int32(L - 1)))(*args)
                print(json.dumps({
                    "device": device.device_kind, "kernel": label,
                    "pool": str(jnp.dtype(dtype)), "table": name,
                    "live_pages": pages, "us_per_call": round(us, 1),
                    "whole_pages_share_of_peak_pct":
                        round(100 * floor_us / us, 1),
                    "max_abs_err": float(np.max(np.abs(
                        np.asarray(got, np.float32) - want)))}), flush=True)
        del k_pool, v_pool, scales, args


if __name__ == "__main__":
    main(sys.argv[1:])
