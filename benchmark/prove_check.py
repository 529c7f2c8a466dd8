"""python3 benchmark/prove_check.py --workload <cell> --seeds 500-515 --seconds 20 [--control <which>]

Proving the check, never part of the driver's command: the cell's whole
run (run.py's `one_run`: weights from the seed, server, ramp, a short window
at the cell's own load, the check) over many seeds in ONE process, so the
chip is attached and the programs are loaded once. Prints each run's lines
and, per seed, one {"phase": "proved", ...} line with the check's numbers;
PERF.md section 2 has the readings the limits were set from. With
--control reference-int8 one pass reads the sound numbers and the
control's.
"""

import argparse
import gc
import json
import sys

import run as bench_run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="first-last")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--control", choices=bench_run.CONTROLS)
    parser.add_argument("--tiny", action="store_true")
    args = parser.parse_args(argv)
    first, last = (int(s) for s in args.seeds.split("-"))
    for seed in range(first, last + 1):
        gc.collect()       # the last seed's weights, before the next are made
        try:
            line = bench_run.one_run(argparse.Namespace(
                workload=args.workload, seed=seed, seconds=args.seconds,
                trace=0, tiny=args.tiny, control=args.control))
        except Exception as exc:  # noqa: BLE001 - the other seeds still count
            line = {"error": f"{type(exc).__name__}: {exc}"}
        print(json.dumps({"phase": "proved", "seed": seed, **{
            k: line.get(k) for k in ("correct", "attempted", "failed",
                                     "error")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
