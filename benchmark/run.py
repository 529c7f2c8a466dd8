"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json: make the weights from the seed, boot
examples/llm-server around an engine of the cell's sizing, warm the cell's
programs, let the load generator (a child process) ramp, measure for
--seconds, shut the server down, free it, and judge a sample of what was
served against the plain reference. Every line printed is one JSON object;
the last one is the contract's: correct, attempted, failed, metrics, device
and, traced, breakdown. --trace 0 reports the cell's end-to-end metrics,
--trace 1 its per-layer metrics.

It exits non-zero and prints no result when JAX finds no accelerator or
too few chips, when the device is not in the table of peaks, when the load
generator cannot finish its ramp, or when a program compiled inside the
window. --tiny is the rehearsal on the CPU (debug-sized widths from the
files' own "tiny" sections: counts and the check only, no device metric).
--control is for proving the check (prove_check.py runs it over many seeds)
and is never in the driver's command.
"""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
if os.path.isdir(os.path.join(os.path.dirname(BENCH_DIR), "gofr_tpu")):
    sys.path.insert(0, os.path.dirname(BENCH_DIR))

from harness import check, data, serve, stats, tracered, traffic  # noqa: E402

CONTROLS = ("reference-int8", "int8-weights", "int8-kv")


def parse(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--control", choices=CONTROLS)
    return parser.parse_args(argv)


def pad_length(mix: dict) -> int:
    """The longest padded length of a cell: its longest prompt with its
    longest answer, up to the next multiple of 128."""
    longest = (traffic.upper(mix["prompt_tokens"])
               + traffic.upper(mix["output_tokens"]))
    return -(-longest // 128) * 128


def layer_values(run: dict, cell: dict) -> dict:
    out = {}
    for name, module in data.layer_metrics().items():
        if module.MOVES not in cell["end_to_end"]:
            continue
        if getattr(module, "LOOP", None) not in (None, run["loaded"]["mix"]["loop"]):
            continue
        value = module.read(run)
        if value is not None:
            out[name] = {"value": value, "unit": module.UNIT}
    return out


def one_run(args) -> dict:
    """Everything but the printing of the last line: the tests drive this
    with the look for a chip skipped (--tiny)."""
    import jax

    loaded = data.load_cell(args.workload, args.tiny)
    cell, mix = loaded["cell"], loaded["mix"]
    if "check" not in cell:
        raise SystemExit(f"{args.workload} has no check limits: the cell has "
                         f"not been proven on the chip (PERF.md section 7)")
    devices = serve.find_devices(int(cell["chips"]), args.tiny)
    lower = None if args.control == "reference-int8" else args.control
    server = serve.Server(loaded, args.seed, control=lower)
    booted_s = time.monotonic() - T0
    serve.emit(phase="boot", attach_and_imports_s=booted_s - (
        server.init_s + server.warmup_s + server.app_s),
        init_s=server.init_s, warmup_s=server.warmup_s, app_s=server.app_s,
               **{k: server.compile_table()[k] for k in (
                   "distinct_programs", "disk_hits_total",
                   "compile_seconds_total")})
    try:
        run = serve.measure(server, args.seed, args.seconds, bool(args.trace))
    finally:
        reference_params, dims = server.params, server.dims
        stop_s = server.stop()
    setup_s = run["t_open"] - T0
    ends = stats.end_to_end(run["result"], mix["loop"])
    ends["metrics"]["setup_s"] = setup_s
    compiled = (run["close_table"]["distinct_programs"]
                - run["setup_table"]["distinct_programs"])
    serve.emit(phase="window", setup_s=setup_s, ramp_s=setup_s - booted_s,
               stop_s=stop_s,
        compiled_in_window=compiled, counts=ends["counts"],
        attempted=ends["attempted"], failed=ends["failed"],
        end_to_end=ends["metrics"],
        generator_late_p95_ms=ends["generator_late_p95_ms"],
        in_flight_at_mid=run["result"].get("in_flight_at_mid"),
        in_flight_at_close=run["result"].get("in_flight_at_close"),
        first_errors=[r["error"] for r in run["result"]["records"]
                      if r.get("error")][:3])
    if args.trace:
        serve.emit(phase="ledger", **serve.ledger_totals(run["steps"]))
        traced = run["traced"]
        t0 = time.monotonic()
        run["trace"] = {**tracered.reduce_dir(traced["dir"],
                                              traced["t1"] - traced["t0"]),
                        "t0": traced["t0"], "t1": traced["t1"]}
        serve.emit(phase="trace", reduce_s=time.monotonic() - t0,
                   devices=run["trace"]["devices"])
    if compiled:
        raise SystemExit(f"{compiled} programs compiled inside the window: "
                         f"the warm-up does not cover this cell's shapes")
    t0 = time.monotonic()
    verdict = check.judge(
        data.reference_for(loaded["config"]), reference_params, dims, run,
        args.seed, cell["check"], loaded["config"]["precision"],
        pad_length(mix),
        control="int8" if args.control == "reference-int8" else None)
    serve.emit(phase="check", seconds=time.monotonic() - t0, **verdict)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": run["memory"]["peak"]}
    line = {"correct": verdict["correct"], "attempted": ends["attempted"],
            "failed": ends["failed"], "device": device}
    if args.trace:
        run["device"] = device
        line["metrics"] = layer_values(run, cell)
        trace = run.get("trace") or {}
        if trace.get("devices"):
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
    else:
        line["metrics"] = {name: {"value": value, "unit": stats.UNITS[name]}
                           for name, value in ends["metrics"].items()
                           if name in cell["end_to_end"]}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    line = one_run(args)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
