"""Kimi Delta Attention's two halves alone, at Solar-Open2-250B's widths, on
the chip.

    chiprun -- python tools/bench_kda.py [rows=96,256] [tiles=2] [tokens=128,1024,4096] [only=update,chunk]

`update`: a decode step's state updates (ops/kda_update.py: 3 KDA blocks,
64 heads of 128 x 128 float32 a row) over `rows` live rows of as many slots
(96 is `decode-closed`'s batch, 256 the cell
`solar-open2-250b-ep8.decode256-closed`'s), as the kernel at each head
tiling in `tiles` (head tiles a row: 1, 2 or 4; the module's own is the
first reported) and once as the jax.numpy form of the same arithmetic (what
the kernel answers). One JSON line a (rows, form): microseconds a block's
call (best of five runs of STEPS steps), the least bytes a call moves
(benchmark/reference/kda_moe.py `kda_update_bytes`: the state read and
written once, q, k, v, the decay in, o out) over 819 GB/s as a share of
that time, and the kernel's largest difference from the jax.numpy form
after one call.

`chunk`: the chunkwise prefill (ops/kda_chunk.py, jax.numpy at float32
`highest`) of ONE block over one row of `tokens` tokens: milliseconds a
call and the share of the MXU's bf16 peak its matrix products reach,
counted as the chunkwise algorithm needs them a chunk of C a head
(A and B C^2 d_k each over the lower triangle, the triangular solve
C^2 (d_k + d_v), the three products with the state 2 C d_k d_v each, B W
2 C^2 d_v): a float32 product at `highest` is six bf16 passes, so a share
of a sixth is the MXU busy.

It is not the benchmark: it says what the mechanism costs alone, never what
a cell gains (PERF.md section 5). It refuses a device that is not in the
benchmark's table of peaks.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "tools")]
from bench_paged_read import best_of_five  # noqa: E402
from gofr_tpu.ops import kda_chunk, kda_update  # noqa: E402
from harness import data, peaks  # noqa: E402  (the one table of peaks)

CELL = "solar-open2-250b-ep8.decode256-closed"
STEPS = 8


def operands(key, rows: int, H: int, dk: int):
    """A step's a, k, q, v, b over `rows` rows, as the model makes them:
    unit k, q scaled, a decay near one, b in (0, 2)."""
    keys = jax.random.split(key, 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return (jnp.exp(-jnp.exp(jax.random.uniform(
                keys[0], (rows, H, dk), jnp.float32, -9.0, -2.0))),
            unit(jax.random.normal(keys[1], (rows, H, dk), jnp.float32)),
            unit(jax.random.normal(keys[2], (rows, H, dk), jnp.float32))
            / dk ** 0.5,
            jax.random.normal(keys[3], (rows, H, dk), jnp.float32),
            2 * jax.nn.sigmoid(jax.random.normal(keys[4], (rows, H))))


def steps_of(update, blocks: int, steps: int):
    """`steps` decode steps' updates of every block, the state carried."""
    def run(state, a, k, q, v, b, live):
        def step(_, carry):
            state, total = carry
            for layer in range(blocks):
                o, state = update(state, layer, a, k, q, v, b, live)
                total = total + o
            return state, total
        return jax.lax.fori_loop(0, steps, step, (state, jnp.zeros_like(v)))
    return jax.jit(run, donate_argnums=0)


def bench_update(dims, reference, rows_list, tilings, peak):
    H, dk, blocks = dims["Hk"], dims["dk"], reference.blocks(dims)["kda"]
    for rows in rows_list:
        args = operands(jax.random.PRNGKey(rows), rows, H, dk)
        live = jnp.ones((rows,), bool)

        def fresh():
            return 0.1 * jax.random.normal(
                jax.random.PRNGKey(1), (blocks, rows, H, dk, dk), jnp.float32)

        need = reference.kda_update_bytes(dims, rows) / blocks
        errors = {}
        state = fresh()
        want_o, want = kda_update.kda_update_reference(state, 1, *args, live)
        for tiles in tilings:
            kda_update.HEAD_TILES = tiles
            got_o, got = jax.jit(kda_update.kda_update)(
                state, jnp.int32(1), *args, live)
            errors[tiles] = {"o": float(jnp.max(jnp.abs(got_o - want_o))),
                             "state": float(jnp.max(jnp.abs(got - want)))}
            del got
        del state, want
        forms = [(f"kernel, {t} head tiles a row", t) for t in tilings]
        for name, tiles in forms + [("jax.numpy", None)]:
            line = {"part": "update", "rows": rows, "form": name}
            update, steps = kda_update.kda_update_reference, 2
            if tiles is not None:
                kda_update.HEAD_TILES = tiles
                update, steps = kda_update.kda_update, STEPS
                line["largest_error"] = errors[tiles]
            seconds = best_of_five(steps_of(update, blocks, steps), fresh(),
                                   *args, live, donated=(0,))
            us = seconds / (steps * blocks) * 1e6
            print(json.dumps({**line, "us_per_call": us,
                              "least_mb_per_call": need / 1e6,
                              "roofline_pct": 100.0 * need / peak
                              / (us / 1e6)}), flush=True)


def chunk_flops(T: int, H: int, dk: int, dv: int, C: int) -> float:
    per_chunk = (2 * C * C * dk + C * C * (dk + dv) + 6 * C * dk * dv
                 + 2 * C * C * dv)
    return T / C * H * per_chunk


def bench_chunk(dims, tokens_list, peak_flops):
    H, dk = dims["Hk"], dims["dk"]
    fn = jax.jit(kda_chunk.kda_chunk)
    for T in tokens_list:
        a, k, q, v, b = operands(jax.random.PRNGKey(T), T, H, dk)
        args = [x[None] for x in (q, k, v, jnp.log(a), b)]
        seconds = best_of_five(fn, *args)
        flops = chunk_flops(T, H, dk, dk, kda_chunk.CHUNK)
        print(json.dumps({
            "part": "chunk", "tokens": T, "ms_per_block": seconds * 1e3,
            "us_per_token": seconds / T * 1e6, "gflop": flops / 1e9,
            "mxu_peak_pct": 100.0 * flops / seconds / peak_flops}),
            flush=True)


def main(argv) -> int:
    options = dict(a.split("=", 1) for a in argv)
    peak = peaks.of(jax.devices()[0].device_kind)
    config = data.load_cell(CELL)["config"]
    reference = data.reference_for(config)
    dims = reference.dims_of(config)
    numbers = lambda name, default: [  # noqa: E731
        int(x) for x in options.get(name, default).split(",")]
    only = options.get("only", "update,chunk").split(",")
    if "update" in only:
        bench_update(dims, reference, numbers("rows", "96,256"),
                     numbers("tiles", str(kda_update.HEAD_TILES)),
                     peak["hbm_bytes_per_s"])
    if "chunk" in only:
        bench_chunk(dims, numbers("tokens", "128,1024,4096"),
                    peak["bf16_flops"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
