"""Device time of one decode step: the time the decode programs ran on the
device in the traced window / the decode steps they made (one paged-read
kernel call a layer a step)."""
from harness import readers

NAME, UNIT, BETTER = "decode_step_dev_ms", "ms", "lower"
LAYER, SOURCE, MOVES = "step programs", "device_trace", "out_tok_s"


def read(run):
    steps = readers.decode_steps_traced(run)
    if not steps:
        return None
    return readers.trace_of(run)["kernels"]["decode"]["seconds"] / steps * 1e3
