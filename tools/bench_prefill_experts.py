"""A prefill's grouped experts alone, at the cells' shapes, on the chip.

    chiprun -- python tools/bench_prefill_experts.py \
        [label=path/to/moe_experts.py ...] [only=<shape>] [ops=<n>]

Times `prefill_experts` of this tree, and of any other copy of
`ops/moe_experts.py` given as label=path (a parent's unpacked under build/,
an experiment), at the shapes the three expert cells call it with:

- `joyai` (`joyai-llm-flash-ep8.longprompt-closed`): windows of 4,096 and
  3,072 tokens, 8 picks a token, 32 of 256 experts held, rows of 2,048,
  gated experts 768 wide;
- `trinity` (`trinity-large-preview-ep8.mixedlen-closed`): a piece of 4,096
  tokens, 4 picks, 32 of 256 held, rows of 3,072, gated experts 3,072 wide
  in two tiles;
- `nemotron` (`nemotron-3-nano-30b-a3b-ep2.decode-closed`): windows of 128
  and 512 tokens, 6 picks, 64 of 128 held, rows of 2,688, experts 1,856 wide.

The last 14 % of a window's tokens are padding (weight 0), as the cells'
buckets leave on average. Two seeded draws of the picks a shape: `uniform`
(a token's picks are distinct experts, every expert as likely) and `skewed`
(this chip's experts 1.5 times as likely as the mean).

One JSON line a (module, shape, window, draw): microseconds a call (best of
five runs of a loop of 8 calls, each fed by the one before), split by a
device trace of one such loop into the `moe_experts` kernel and the rest
(every other operation of the call: the sort, the gathers, the way back to
tokens); `pieces` (kernel calls a call: 1 before PR 38, which laid out
every pair at once); `rows_sized` (the sorted rows one piece lays out) over
`rows_live` (the pairs that fell on held experts); and the largest error
against `experts_reference` on the first 256 tokens (all of a shorter
window). `ops=<n>` adds the n
longest operations of the rest, microseconds a call each.

It is not the benchmark: it says what the function costs alone, never what
a cell gains (PERF.md section 5 keeps its table). It refuses a device that
is not in the benchmark's table of peaks: a CPU timing of the interpreter
is no kernel time.
"""

import glob
import inspect
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark"),
                os.path.join(ROOT, "tools")]
import gofr_tpu.ops.moe_experts  # noqa: E402
from bench_paged_read import best_of_five, load  # noqa: E402
from harness import peaks, tracered  # noqa: E402  (the one table of peaks)

STEPS = 8
PADDING = 0.14
CHECKED = 256          # tokens held against the reference
SHAPES = {
    "joyai": {"windows": (4096, 3072), "k": 8, "D": 2048, "F": 768,
              "held": 32, "total": 256, "gated": True},
    "trinity": {"windows": (4096,), "k": 4, "D": 3072, "F": 3072,
                "held": 32, "total": 256, "gated": True},
    "nemotron": {"windows": (128, 512), "k": 6, "D": 2688, "F": 1856,
                 "held": 64, "total": 128, "gated": False},
}
DRAWS = {"uniform": 1.0, "skewed": 1.5}
TRACE_DIR = os.path.join(ROOT, "chiprun_out", "_prefill_experts_trace")


def routing(T: int, shape: dict, share: float, seed: int):
    """(picks [T, k] int32 over all experts, weights [T, k] float32): k
    distinct experts a token, the held ones `share` times as likely as the
    mean (Gumbel top-k); the window's last tokens are padding."""
    rng = np.random.default_rng(seed)
    held, total, k = shape["held"], shape["total"], shape["k"]
    likely = np.full(total, (total - share * held) / max(total - held, 1))
    likely[:held] = share
    picks = np.argsort(-(np.log(likely) + rng.gumbel(size=(T, total))),
                       axis=1)[:, :k]
    weights = rng.uniform(0.2, 1.0, size=(T, k))
    weights[int(T * (1 - PADDING)):] = 0.0
    return picks.astype(np.int32), weights.astype(np.float32)


def traced(fn, args, where: str, n_ops: int):
    """One run of `fn` under the device tracer: (the `moe_experts`
    kernel's seconds, its calls, the n longest other operations as
    [name, seconds])."""
    shutil.rmtree(where, ignore_errors=True)
    with jax.profiler.trace(where):
        jax.block_until_ready(fn(*args))
    found = sorted(glob.glob(os.path.join(
        where, "plugins", "profile", "*", "*.xplane.pb")))
    trace = tracered.load(found[-1])
    shutil.rmtree(where, ignore_errors=True)
    kernel, calls, rest = 0.0, 0, {}
    for plane in tracered.device_planes(trace):
        for name, _, dur in tracered.line_of(plane, tracered.OPS_LINE):
            op, kind, result = tracered.parts(name)
            if kind in tracered.CONTAINERS:
                continue              # its body's operations are counted
            if kind == "custom-call" and \
                    tracered.kernel_name(op) == "moe_experts":
                kernel, calls = kernel + dur / 1e9, calls + 1
            else:
                key = f"{tracered.kernel_name(op) or op} {kind} {result[:40]}"
                rest[key] = rest.get(key, 0.0) + dur / 1e9
    return kernel, calls, sorted(rest.items(), key=lambda kv: -kv[1])[:n_ops]


def lines(label: str, module, name: str, shape: dict, device, n_ops: int):
    """One JSON line a (window, draw) of one module at one shape."""
    D, F, held, total = (shape[key] for key in ("D", "F", "held", "total"))
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    make = jax.jit(lambda key: (jax.random.normal(
        key, (held, F, D), jnp.float32) / D ** 0.5).astype(jnp.bfloat16))
    w1, w2 = make(keys[0]), make(keys[1])
    wg = make(keys[2]) if shape["gated"] else None
    told = "total" in inspect.signature(module.prefill_experts).parameters
    for T in shape["windows"]:
        tm = min(128, max(8, T))
        x = jax.random.normal(keys[3], (T, D), jnp.float32).astype(
            jnp.bfloat16)
        # what one piece lays out: every pair's blocks before PR 38
        blocks = (module.piece_blocks(T, shape["k"], held, total, tm)
                  if hasattr(module, "piece_blocks")
                  else -(-T * shape["k"] // tm) + held)

        # the matrices as arguments: closed over, a program would carry
        # them as constants
        def call(x, picks, weights, w1, w2, wg):
            lead = (x, w1, w2, picks, weights, 0)
            return module.prefill_experts(
                *lead, *((total,) if told else ()), tm=tm, wg=wg)

        def loop(x, *rest):
            # STEPS calls in one program, each fed by the one before
            def one(_, acc):
                fed = x + (acc[:, :1] * 0.0).astype(x.dtype)
                return acc + call(fed, *rest)

            return jax.lax.fori_loop(0, STEPS, one,
                                     jnp.zeros((T, D), jnp.float32))

        once, fn = jax.jit(call), jax.jit(loop)
        for draw, share in DRAWS.items():
            picks, weights = routing(T, shape, share, seed=T + len(draw))
            here = (picks < held) & (weights != 0.0)
            args = (x, jnp.asarray(picks), jnp.asarray(weights), w1, w2, wg)
            us = best_of_five(fn, *args) / STEPS * 1e6
            kernel_s, calls, rest = traced(fn, args, TRACE_DIR, n_ops)
            n = min(CHECKED, T)
            combine = np.zeros((n, total), np.float32)
            np.add.at(combine, (np.arange(n)[:, None], picks[:n]),
                      weights[:n])
            want = jax.jit(gofr_tpu.ops.moe_experts.experts_reference)(
                x[:n], w1, w2, jnp.asarray(combine[:, :held]), wg)
            got = once(*args)[:n]
            kernel_us = kernel_s / STEPS * 1e6
            print(json.dumps({
                "device": device.device_kind, "module": label, "shape": name,
                "tokens": T, "draw": draw, "us": round(us, 1),
                "kernel_us": round(kernel_us, 1),
                "rest_us": round(us - kernel_us, 1),
                "pieces": round(calls / STEPS, 2),
                "rows_sized": blocks * tm, "rows_live": int(here.sum()),
                "max_abs_err": float(np.max(np.abs(
                    np.asarray(got) - np.asarray(want)))),
                **({"rest_ops_us": [[op, round(s / STEPS * 1e6, 1)]
                                    for op, s in rest]} if n_ops else {})}),
                flush=True)


def main(argv) -> None:
    device = jax.devices()[0]
    peaks.of(device.device_kind)      # a device without peaks is refused
    modules, only, n_ops = {"tree": gofr_tpu.ops.moe_experts}, None, 0
    for label, value in (arg.split("=", 1) for arg in argv):
        if label == "only":
            only = value
        elif label == "ops":
            n_ops = int(value)
        else:
            modules[label] = load(label, value)
    for name, shape in SHAPES.items():
        if only in (None, name):
            for label, module in modules.items():
                lines(label, module, name, shape, device, n_ops)


if __name__ == "__main__":
    main(sys.argv[1:])
