"""python3 benchmark/tests/mla_moe_hc_faults.py [--seconds 20] [--seed0 n] [--probe] [fault ...]

ONE fault at a time in the program, for proving the limits of the cell
`xing4.0-29b-a4b-ep8.decode-closed` (its file's `limits_from`; PERF.md
section 2). benchmark/tests/test_mla_moe_hc.py installs the same faults at
--tiny size on the CPU; run as a script this is the cell's whole run at the
published widths on the chip, a seed a fault, all in one process, each
fault printed as one {"phase": "fault", ...} line after its run's own
lines. Never part of the driver's command.

The faults (`install`):

- `table`: ONE slot's page table off by one: every page id of its row one
  lower, so the row attends another row's latents. The check's sample is
  drawn from that slot's requests (`sampled`).
- `sinkhorn_5`: the residual mix makes H_res with 5 Sinkhorn rounds where
  the configuration says 20.
- `mix_bfloat16`: the mix's mappings (the norm of the stream, the product
  with phi, the sigmoids, the exponential and the Sinkhorn rounds) computed
  in bfloat16 where the configuration states float32.
- `no_mscale`: the scores scaled by 192^-0.5 alone, in both forms of the
  attention, where YaRN's mscale^2 = 2.0047 belongs.

The last three change what a program traces and not its name, so each
compiles its programs anew into a directory of its own (`fresh_programs`).

`--probe` runs no cell: it reads, for the sound program (`sound`) and for
each fault of the mix, how far the mappings lie from the reference's at the
rows of the two programs and the published widths (`probe`: the adapter's `mix_off`,
which every run of the cell makes of its live engine and which decides
`state_not_as_stated`; the mix's seeded leaves alone, no other weight), a
seed a reading: the two readings of `precision.mix_within`'s `limits_from`.
"""

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

CELL = "xing4.0-29b-a4b-ep8.decode-closed"
MIX = ("sinkhorn_5", "mix_bfloat16")
BLOCKS = MIX + ("no_mscale",)
FAULTS = ("table",) + BLOCKS
SLOT = 2        # the one faulty slot of `table`


def mappings_in_bfloat16(x, phi, scale, bias, *, n, iters, eps, clamp,
                         rms_eps):
    """ops/mhc.py `mhc_pre_reference` with every value of the mappings'
    arithmetic kept in bfloat16; u is mixed as the sound program mixes it
    (float32 products of the stream with H_pre)."""
    import jax
    import jax.numpy as jnp

    from gofr_tpu.ops import mhc

    low = jnp.bfloat16
    D, C = x.shape[-1] // n, mhc.columns(n)
    xl = x.astype(low)
    normed = xl * jax.lax.rsqrt(jnp.mean(xl * xl, axis=-1, keepdims=True)
                                + low(rms_eps))
    m = jnp.einsum("...d,cd->...c", normed, phi.astype(low),
                   preferred_element_type=low)
    scale, bias = scale.astype(low), bias.astype(low)
    pre = jax.nn.sigmoid(scale[0] * m[..., :n] + bias[:n])
    post = low(2.0) * jax.nn.sigmoid(scale[1] * m[..., n:2 * n]
                                     + bias[n:2 * n])
    logits = scale[2] * m[..., 2 * n:] + bias[2 * n:]
    M = jnp.exp(jnp.clip(logits, low(clamp[0]), low(clamp[1])))
    res = mhc.sinkhorn(M.reshape(*M.shape[:-1], n, n), iters, low(eps))
    h = jnp.concatenate(
        [pre, post, res.reshape(*M.shape[:-1], n * n),
         jnp.zeros((*M.shape[:-1], mhc.h_width(n) - C), low)],
        -1).astype(jnp.float32)
    copies = x.astype(jnp.float32).reshape(*x.shape[:-1], n, D)
    u = jnp.einsum("...i,...id->...d", h[..., :n], copies)
    return u.astype(x.dtype), h


def install(fault: str, patch) -> None:
    """Put `fault` into the program through `patch.setattr` (pytest's
    monkeypatch, or a `pytest.MonkeyPatch()` of the caller's to undo)."""
    from gofr_tpu.models.mla_moe import MlaMoeConfig
    from gofr_tpu.ops import mhc
    from gofr_tpu.tpu.paging import PagedLLMEngine

    if fault == "table":
        build = PagedLLMEngine._build_table

        def shifted(self):
            table = np.array(build(self))
            row = table[SLOT]
            table[SLOT] = np.where(row > 0, np.maximum(row - 1, 1), row)
            return table

        patch.setattr(PagedLLMEngine, "_build_table", shifted)
    elif fault == "sinkhorn_5":
        init = PagedLLMEngine.__init__
        patch.setattr(
            PagedLLMEngine, "__init__", lambda self, params, cfg, **kw: init(
                self, params, dataclasses.replace(cfg, hc_sinkhorn_iters=5),
                **kw))
    elif fault == "mix_bfloat16":
        patch.setattr(mhc, "mhc_pre", mappings_in_bfloat16)
        patch.setattr(mhc, "mhc_pre_reference", mappings_in_bfloat16)
    elif fault == "no_mscale":
        patch.setattr(MlaMoeConfig, "softmax_scale", property(
            lambda self: 1.0 / math.sqrt(self.qk_dim)))
    else:
        raise SystemExit(f"unknown fault {fault}: one of {FAULTS}")


def probe(fault, seed: int, tiny: bool = False, attn_impl=None) -> dict:
    """{program: how far its mappings lie from the reference's} with `fault`
    ("sound": none) in the program: the adapter's `mix_off` over the mix's
    seeded leaves, under the configuration the adapter makes of the cell's
    files (`attn_impl`: another form than the files say, for the CPU)."""
    import pytest

    from harness import data

    config = data.load_cell(CELL, tiny)["config"]
    reference = data.reference_for(config)
    family = data.family_for(config)
    dims = reference.dims_of(config)
    cfg = family.model_config(config, dims)
    if attn_impl is not None:
        cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    with pytest.MonkeyPatch.context() as patch:
        if fault == "sinkhorn_5":       # what `install` does to an engine's
            cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=5)
        elif fault != "sound":
            install(fault, patch)
        found = family.mix_off(cfg, reference.make_mix(dims, seed), dims,
                               config["torch_dtype"],
                               int(config["engine"]["n_slots"]))
    return {program: off for program, (off, _) in found.items()}


def sampled(seen: list, tokens: int = 32):
    """`check.pick` for the fault tied to SLOT: up to 6 of the requests it
    served, over their first `tokens` tokens; their indices are left in
    `seen`."""
    def pick(records, slots, seed, sample):
        mine = sorted((r for r in records
                       if slots.get(r["index"]) == SLOT
                       and not r.get("error")
                       and len(r.get("tokens") or ()) >= 2),
                      key=lambda r: r["index"])[:6]
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
    parser.add_argument("--seed0", type=int, default=2147495000)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--probe", action="store_true")
    parser.add_argument("faults", nargs="*", default=None)
    args = parser.parse_args(argv)
    if args.probe:
        for i, fault in enumerate(args.faults or ("sound",) + MIX):
            seed = args.seed0 + 17 * i
            print(json.dumps({"phase": "probe", "fault": fault, "seed": seed,
                              "off": probe(fault, seed, args.tiny)}),
                  flush=True)
        return 0
    for i, fault in enumerate(args.faults or FAULTS):
        gc.collect()
        patch, seen = pytest.MonkeyPatch(), []
        install(fault, patch)
        if fault in BLOCKS:
            fresh_programs(patch)
        else:
            patch.setattr(check, "pick", sampled(seen))
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
                          "sampled": seen, **{
            k: line.get(k) for k in ("correct", "attempted", "failed",
                                     "compared", "error")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
