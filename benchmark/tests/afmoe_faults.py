"""python3 benchmark/tests/afmoe_faults.py [--seconds 20] [--seed0 n] [fault ...]

ONE fault at a time in the afmoe program, for proving the limits of the
cell `trinity-large-preview-ep8.mixedlen-closed` (its file's `limits_from`;
PERF.md section 2). benchmark/tests/test_afmoe.py installs the same faults
at --tiny size on the CPU; run as a script this is the cell's whole run at
the published widths on the chip, a seed a fault, all in one process, each
fault printed as one {"phase": "fault", ...} line after its run's own lines.
Never part of the driver's command.

The faults (`install`):

- `table_full`, `table_window`: ONE slot's page table off by one in the
  full group (the full_attention block) or in the window group (the ring
  of the sliding blocks): every page id of its row one lower.
- `table_full_scattered`: `table_full` with the full group's free list
  shuffled when the engine is made. A fresh allocator hands out
  consecutive pages, so a row off by one attends its own keys a page late,
  and a block without a position signal gives the same softmax over the
  same keys in another order: only one foreign page in and the row's last
  page out differ. An allocator that has served for a while is not in that
  state, and shuffled, every page of the faulty row is another's.
- `window_ignored` (a sliding block's prefill attends everything),
  `full_turned` (the full block rotated), `gate_out` (the attention output
  not gated), `weights_off` (one block's W_v with its columns moved by one
  on the program's side only: a scale would be undone by the norm that
  follows the attention's output).

At chip size the check's sample is drawn from the faulty slot (`sampled`):
the cell's own sample takes one request from each of 8 of the 32 slots. A
table fault in the full group is judged on a SHORT request (one page is 6-12
% of the keys at 1k-2k tokens, 1 % at 12k): the faulty slot is then the
first one seen serving a prompt of at most `short` tokens (PERF.md section
2: even there it read under both limits at the published widths, and only
the scattered form is caught; `--tokens 1024` checks whole answers). The window
group's is judged on requests past the window, where the ring has wrapped,
and the scattered form on the slot's longest requests.
"""

import argparse
import dataclasses
import json
import os
import random
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

CELL = "trinity-large-preview-ep8.mixedlen-closed"
TABLES = {"table_full": 0, "table_full_scattered": 0, "table_window": 1}
BLOCKS = ("window_ignored", "full_turned", "gate_out", "weights_off")
FAULTS = tuple(TABLES) + BLOCKS


class OneSlot:
    """The one faulty slot: `fixed`, or the first slot seen serving a
    prompt of at most `short` tokens."""

    def __init__(self, fixed=None, short=None):
        self.index, self.short = fixed, short

    def of(self, engine):
        if self.index is None:
            self.index = next(
                (i for i, slot in enumerate(engine.slots) if slot.active
                 and len(slot.request.resume_tokens) <= self.short), None)
        return self.index


def install(fault: str, patch, who: OneSlot = None) -> None:
    """Put `fault` into the program through `patch.setattr` (pytest's
    monkeypatch, or a `pytest.MonkeyPatch()` of the caller's to undo)."""
    import jax.numpy as jnp

    from gofr_tpu.models import afmoe
    from gofr_tpu.tpu.paging import PagedLLMEngine

    who = who or OneSlot(fixed=2)
    init = PagedLLMEngine.__init__
    if fault in TABLES:
        build, group = PagedLLMEngine._build_tables, TABLES[fault]

        def shifted(self):
            tables = [np.array(table) for table in build(self)]
            slot = who.of(self)
            if slot is not None:
                row = tables[group][slot]
                tables[group][slot] = np.where(row > 0,
                                               np.maximum(row - 1, 1), row)
            return tables

        patch.setattr(PagedLLMEngine, "_build_tables", shifted)
        if fault == "table_full_scattered":
            def scattered(self, params, cfg, **kw):
                init(self, params, cfg, **kw)
                random.Random(0).shuffle(self.allocator._free)

            patch.setattr(PagedLLMEngine, "__init__", scattered)
    elif fault == "window_ignored":
        # the ring, sized for the true window, still holds what a sequence
        # needs at decode: the prompt's keys behind the window are attended
        patch.setattr(
            afmoe, "attention_prefill",
            lambda x, w, sliding, c, inner=afmoe.attention_prefill:
            inner(x, w, sliding, dataclasses.replace(c, window=1 << 20)))
    elif fault == "full_turned":
        patch.setattr(
            afmoe, "_qkvg",
            lambda x, w, positions, sliding, c, inner=afmoe._qkvg:
            inner(x, w, positions, True, c))
    elif fault == "gate_out":
        # a gate of ones, not of zeros: a uniform gate of 0.5 is undone by
        # the norm that follows the attention's output
        def ungated(x, w, positions, sliding, c, inner=afmoe._qkvg):
            q, k, v, gate = inner(x, w, positions, sliding, c)
            return q, k, v, jnp.ones_like(gate)

        patch.setattr(afmoe, "_qkvg", ungated)
    elif fault == "weights_off":
        def off(self, params, cfg, **kw):
            layers = list(params["layers"])
            layers[2] = {**layers[2],
                         "wv": jnp.roll(layers[2]["wv"], 1, axis=1)}
            init(self, {**params, "layers": layers}, cfg, **kw)

        patch.setattr(PagedLLMEngine, "__init__", off)
    else:
        raise SystemExit(f"unknown fault {fault}: one of {FAULTS}")


def sampled(who: OneSlot, keep, seen: list, longest: bool = False,
            tokens: int = 16):
    """`check.pick` for a fault tied to one slot: up to 3 of the requests
    the faulty slot served that `keep` admits, shortest first (or
    `longest`), over their first `tokens` tokens; their prompts' lengths
    are left in `seen`."""
    def pick(records, slots, seed, sample):
        mine = sorted((r for r in records
                       if who.index is not None
                       and slots.get(r["index"]) == who.index
                       and not r.get("error")
                       and len(r.get("tokens") or ()) >= 2
                       and keep(r["prompt_tokens"])),
                      key=lambda r: (r["prompt_tokens"], r["index"]),
                      reverse=longest)[:3]
        seen[:] = [r["prompt_tokens"] for r in mine]
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
    from harness import check, data

    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed0", type=int, default=2147493000)
    parser.add_argument("--short", type=int, default=2048,
                        help="table_full: the faulty slot is the first "
                        "seen serving a prompt of at most this")
    parser.add_argument("--tokens", type=int, default=16,
                        help="served tokens checked of a sampled request")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("faults", nargs="*", default=list(FAULTS))
    args = parser.parse_args(argv)
    window = int(data.load_cell(CELL, args.tiny)["config"]["sliding_window"])
    for i, fault in enumerate(args.faults):
        gc.collect()
        patch, seen = pytest.MonkeyPatch(), []
        who = OneSlot(short=args.short) if fault == "table_full" else OneSlot(2)
        install(fault, patch, who)
        if fault in BLOCKS:
            fresh_programs(patch)
        else:
            keep = {"table_full": lambda n: n <= args.short,
                    "table_window": lambda n: n > window + window // 16,
                    }.get(fault, lambda n: True)
            patch.setattr(check, "pick", sampled(
                who, keep, seen, longest=fault == "table_full_scattered",
                tokens=args.tokens))
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
                          "slot": who.index, "sampled_prompts": seen, **{
            k: line.get(k) for k in ("correct", "attempted", "failed",
                                     "compared", "error")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
