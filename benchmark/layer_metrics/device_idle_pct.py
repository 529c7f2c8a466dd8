"""The share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals / window, mean over chips."""
from harness import readers

NAME, UNIT, BETTER = "device_idle_pct", "%", "lower"
LAYER, SOURCE, MOVES = "device", "device_trace", "out_tok_s"


def read(run):
    trace = readers.trace_of(run)
    if trace is None or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
