"""The device's time in prefill programs: the time the XLA modules named
`jit_prefill...` ran on the device over the traced window. Beside it
`prefill_share_pct` is the LOOP THREAD's wall in records whose sync was a
prefill, which in a pipelined loop is mostly the wait for whatever the
device was running."""
from harness import readers

NAME, UNIT, BETTER = "prefill_dev_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "step programs", "device_trace", "out_tok_s"


def read(run):
    trace = readers.trace_of(run) or {}
    busy = [row["busy_s"] for name, row in (trace.get("modules") or {}).items()
            if name.startswith("jit_prefill")]
    if not busy or not trace.get("window_s"):
        return None
    return 100.0 * sum(busy) / trace["window_s"]
