"""The residual mix (ops/mhc.py) alone, at the cell's shapes, on the chip.

    chiprun -- python tools/bench_mhc.py [rows=96,1024,2048] [tile=128]

Times a chain of SUBLAYERS sublayers' mixes (`mhc_pre`, a stand-in for F
that hands u on, `mhc_post`) at Xing4.0-29B-A4B's widths (4 copies of
3,584, bfloat16 stream, 20 Sinkhorn rounds), in three forms: the two
kernels, the same arithmetic in jax.numpy (`mhc_pre_reference` /
`mhc_post_reference`, its product with phi at `highest`), and jax.numpy
with that product at XLA's default precision (one bfloat16 pass: NOT the
configuration's arithmetic, the price of the `highest` passes beside it).
Rows: 96 is a decode step of `xing4.0-29b-a4b-ep8.decode-closed`, 1,024
and 2,048 its prefill programs of 16 prompts in the 64 and 128 buckets.

One JSON line a (rows, form): microseconds a sublayer (best of five runs of
the chain), the bytes a sublayer moves where the stream comes from HBM and
goes back to it (`mhc_pre`: the stream and phi in, u out; `mhc_post`: the
stream and f in, the stream out: so it is in this chain, whose scan carries
the stream through HBM and slices phi out of a stack; NOT in the unrolled
decode program, where 96 rows' stream stays on the chip and the kernels
take 11.6 us a sublayer for the tool's 28: PERF.md section 5) over 819 GB/s
as a share of that time, and the largest difference of the kernels' stream and
mappings from the jax.numpy form's after ONE sublayer. The kernels are
also timed each alone.

It is not the benchmark: it says what the mix costs alone, never what a
cell gains (PERF.md section 5). It refuses a device that is not in the
benchmark's table of peaks.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "tools")]
from bench_paged_read import best_of_five  # noqa: E402
from gofr_tpu.ops import mhc  # noqa: E402
from harness import peaks  # noqa: E402  (the one table of peaks)

N, D, SUBLAYERS = 4, 3584, 40
MIX = dict(n=N, iters=20, eps=1e-6, clamp=(-30.0, 30.0), rms_eps=1e-6)


FORMS = {"kernels": (mhc.mhc_pre, mhc.mhc_post),
         "jax.numpy": (mhc.mhc_pre_reference, mhc.mhc_post_reference),
         "jax.numpy, default precision": (
             functools.partial(mhc.mhc_pre_reference, precision=None),
             mhc.mhc_post_reference)}


def leaves(seed: int):
    """SUBLAYERS sublayers' (phi, scale, bias): phi at Normal(0, 1 / (n D))
    as the benchmark's reference draws it, unit scalars, small biases."""
    C = mhc.columns(N)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    phi = jax.random.normal(keys[0], (SUBLAYERS, C, N * D),
                            jnp.float32) / (N * D) ** 0.5
    bias = 0.5 * jax.random.normal(keys[1], (SUBLAYERS, C), jnp.float32)
    return phi, jnp.ones((SUBLAYERS, 3), jnp.float32), bias


def chain(pre, post):
    def run(x, phi, scale, bias):
        def one(x, w):
            u, h = pre(x, *w, **MIX)
            return post(x, u, h, n=N), None
        return jax.lax.scan(one, x, (phi, scale, bias))[0]
    return jax.jit(run)


def least_bytes(rows: int) -> dict:
    stream = rows * N * D * 2
    return {"mhc_pre": stream + mhc.columns(N) * N * D * 4 + rows * D * 2,
            "mhc_post": 2 * stream + rows * D * 2}


def main(argv) -> int:
    options = dict(a.split("=", 1) for a in argv)
    kind = jax.devices()[0].device_kind
    peak = peaks.of(kind)["hbm_bytes_per_s"]
    if "tile" in options:
        mhc.ROW_TILE = int(options["tile"])
    weights = leaves(0)
    for rows in [int(r) for r in options.get("rows", "96,1024,2048").split(",")]:
        x = jax.random.normal(jax.random.PRNGKey(rows), (rows, N * D),
                              jnp.float32).astype(jnp.bfloat16)
        first = tuple(w[0] for w in weights)
        want_u, want_h = mhc.mhc_pre_reference(x, *first, **MIX)
        got_u, got_h = mhc.mhc_pre(x, *first, **MIX)
        want_x = mhc.mhc_post_reference(x, want_u, want_h, n=N)
        got_x = mhc.mhc_post(x, want_u, want_h, n=N)
        err = {"h": float(jnp.max(jnp.abs(want_h - got_h))),
               "u": float(jnp.max(jnp.abs(want_u.astype(jnp.float32)
                                          - got_u.astype(jnp.float32)))),
               "stream": float(jnp.max(jnp.abs(
                   want_x.astype(jnp.float32) - got_x.astype(jnp.float32))))}
        need = least_bytes(rows)
        for name, (pre, post) in FORMS.items():
            us = best_of_five(chain(pre, post), x, *weights) / SUBLAYERS * 1e6
            print(json.dumps({
                "rows": rows, "form": name, "us_per_sublayer": us,
                "roofline_pct": 100.0 * sum(need.values()) / peak / (us / 1e6),
                "device": kind,
                **({"largest_error": err} if name == "kernels" else {})}),
                flush=True)
        only_pre = jax.jit(lambda x, phi, scale, bias: jax.lax.scan(
            lambda x, w: (x + jnp.tile(mhc.mhc_pre(x, *w, **MIX)[0], N)
                          * jnp.bfloat16(1e-3), None),
            x, (phi, scale, bias))[0])
        h = want_h
        only_post = jax.jit(lambda x, u: jax.lax.scan(
            lambda x, _: (mhc.mhc_post(x, u, h, n=N), None), x, None,
            length=SUBLAYERS)[0])
        for name, fn, args in (("mhc_pre", only_pre, (x, *weights)),
                               ("mhc_post", only_post, (x, want_u))):
            us = best_of_five(fn, *args) / SUBLAYERS * 1e6
            print(json.dumps({
                "rows": rows, "form": name + " alone"
                + (" (with an add of the stream)" if name == "mhc_pre" else ""),
                "us_per_call": us,
                "roofline_pct": 100.0 * need[name] / peak / (us / 1e6)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
