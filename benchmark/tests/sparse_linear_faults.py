"""python3 benchmark/tests/sparse_linear_faults.py [--seconds 20] [--seed0 n] [fault ...]

ONE fault at a time in the sparse_linear program, for proving the limits of
the cell `minicpm-sala-pp4.longctx-closed` (its file's `limits_from`;
PERF.md section 2). benchmark/tests/test_sparse_linear.py installs the same
faults at --tiny size on the CPU; run as a script this is the cell's whole
run at the published widths on the chip, a seed a fault, all in one
process, each fault printed as one {"phase": "fault", ...} line after its
run's own lines. Never part of the driver's command. (The sixth thing the
cell's limits are held against, the reference at int8, is the harness's
own: `run.py --control reference-int8`.)

The faults (`install`):

- `forced_only`: the chosen blocks left out: a row past dense_len attends
  block 0 and its window's blocks and nothing it chose.
- `frozen_keys`: the compressed keys frozen at the prompt's end: what a
  decode block completes is never flushed into the third plane, so a later
  block's choice scores zeros where the answer's compressed keys belong.
- `table`: ONE slot's page table off by one: every page id of its row one
  lower, so its sparse blocks attend another's keys and score another's
  compressed keys.
- `decay_flat`: the lightning decay without its block's factor
  (1 - l / (depth - 1)): every block decays as published block 0 does.
- `stale_state`: ONE slot's admission scatter skipped: a request admitted
  there decodes from the lightning state and the half-window sums the slot
  held before (zeros for the slot's first request, the previous request's
  after).
- `state_bfloat16`: the lightning state HELD in bfloat16 (`state_shapes`
  says so): an array not as the configuration states, whatever the gaps
  read.

`table` and `stale_state` are tied to one slot, and at chip size the check's
sample is drawn from that slot (`sampled`); the others change what every
row computes and are judged on the cell's own sample.
"""

import argparse
import json
import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

CELL = "minicpm-sala-pp4.longctx-closed"
ONE_SLOT = ("table", "stale_state")
FAULTS = ("forced_only", "frozen_keys", "table", "decay_flat", "stale_state",
          "state_bfloat16")
SLOT = 2


def install(fault: str, patch, slot: int = SLOT) -> None:
    """Put `fault` into the program through `patch.setattr` (pytest's
    monkeypatch, or a `pytest.MonkeyPatch()` of the caller's to undo)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from gofr_tpu.models import sparse_linear
    from gofr_tpu.ops import sparse_attention
    from gofr_tpu.tpu import paging
    from gofr_tpu.tpu.paging import PagedLLMEngine

    if fault == "forced_only":
        sound = sparse_attention.choose

        def forced(r, t, *, topk, init_blocks, window_blocks, **rule):
            return sound(r, t, topk=init_blocks + window_blocks,
                         init_blocks=init_blocks,
                         window_blocks=window_blocks, **rule)

        patch.setattr(sparse_attention, "choose", forced)
    elif fault == "frozen_keys":
        patch.setattr(paging, "flush_columns",
                      lambda pool, tail, table, starts, counts: pool)
    elif fault == "table":
        build = PagedLLMEngine._build_table

        def shifted(self):
            table = np.array(build(self))
            row = table[slot]
            table[slot] = np.where(row > 0, np.maximum(row - 1, 1), row)
            return table

        patch.setattr(PagedLLMEngine, "_build_table", shifted)
    elif fault == "decay_flat":
        sound = sparse_linear.SparseLinearConfig.decay

        def flat(self, layer):
            return sound(dataclasses.replace(
                self, layer_ids=(0,) * len(self.layer_ids)), layer)

        patch.setattr(sparse_linear.SparseLinearConfig, "decay", flat)
    elif fault == "stale_state":
        make = PagedLLMEngine._prefill_fn

        def skipping(self, bucket, K):
            inner, n = make(self, bucket, K), len(self.state)

            def prefill(params, *rest):
                out = inner(params, *rest)
                slots = rest[len(self.pools) + 1 + len(self.allocators)]
                mine = jnp.any(slots == slot)
                kept = tuple(
                    jax.lax.cond(mine, lambda new, old: new.at[:, slot].set(
                        old[:, slot]), lambda new, old: new, new, old)
                    for new, old in zip(out[-n:], rest[-n:]))
                return (*out[:-n], *kept)
            return prefill

        patch.setattr(PagedLLMEngine, "_prefill_fn", skipping)
    elif fault == "state_bfloat16":
        sound = sparse_linear.state_shapes

        def lower(cfg, slots):
            (shape, _), sums = sound(cfg, slots)
            return ((shape, jnp.bfloat16), sums)

        patch.setattr(sparse_linear, "state_shapes", lower)
    else:
        raise SystemExit(f"unknown fault {fault}: one of {FAULTS}")


def sampled(slot: int, seen: list, tokens: int = 32, most: int = 4):
    """`check.pick` for a fault tied to one slot: up to `most` of the
    requests that slot served, in the order it served them, over their
    first `tokens` tokens; how many are left in `seen`."""
    def pick(records, slots, seed, sample):
        mine = sorted((r for r in records
                       if slots.get(r["index"]) == slot
                       and not r.get("error")
                       and len(r.get("tokens") or ()) >= 2),
                      key=lambda r: r["index"])[:most]
        seen[:] = [r["index"] for r in mine]
        return [(r, min(tokens, len(r["tokens"]))) for r in mine]
    return pick


def fresh_programs(patch) -> None:
    """A fault that changes what is traced, not a program's name: the
    executor's own artifacts (keyed by name, code object and package
    digest) would hand back the sound program."""
    import gofr_tpu.tpu.executor as executor

    fresh = tempfile.mkdtemp(prefix="jexec_")
    patch.setattr(executor, "enable_compile_cache",
                  lambda override=None: fresh)


def main(argv=None) -> int:
    import gc

    import pytest

    import run as bench_run
    from harness import check

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed0", type=int, default=2147493000)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("faults", nargs="*", default=list(FAULTS))
    args = parser.parse_args(argv)
    for i, fault in enumerate(args.faults):
        gc.collect()
        patch, seen = pytest.MonkeyPatch(), []
        install(fault, patch)
        if fault != "table":
            fresh_programs(patch)
        if fault in ONE_SLOT:
            patch.setattr(check, "pick", sampled(SLOT, seen))
        seed = args.seed0 + 17 * i
        try:
            line = bench_run.one_run(argparse.Namespace(
                workload=CELL, seed=seed, seconds=args.seconds, trace=0,
                tiny=args.tiny, control=None))
        except BaseException as exc:  # noqa: BLE001 - the other faults count
            line = {"error": f"{type(exc).__name__}: {exc}"}
        finally:
            patch.undo()
        print(json.dumps({"phase": "fault", "fault": fault, "seed": seed,
                          "slot": SLOT if fault in ONE_SLOT else None,
                          "sampled_requests": seen, **{
            k: line.get(k) for k in ("correct", "attempted", "failed",
                                     "compared", "error")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
