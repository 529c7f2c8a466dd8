"""Device time of one prefill program: the time the XLA modules named
`jit_prefill...` (the plain and the prefix prefill alike) ran on the
device in the traced window, over their calls (the trace's seconds are a
chip's, its counts all chips')."""
from harness import readers

NAME, UNIT, BETTER = "prefill_dev_ms_per_call", "ms", "lower"
LAYER, SOURCE, MOVES = "step programs", "device_trace", "out_tok_s"


def read(run):
    trace = readers.trace_of(run) or {}
    rows = [row for name, row in (trace.get("modules") or {}).items()
            if name.startswith("jit_prefill")]
    calls = sum(row["count"] for row in rows)
    if not calls:
        return None
    return sum(row["busy_s"] for row in rows) / (calls / trace["devices"]) * 1e3
