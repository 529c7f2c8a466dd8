"""The KDA state update's share of a decode step's device time: the traced
seconds a step of the kernel `kda_update` (ops/kda_update.py: one call a
KDA block a step) over the device time of a decode step as
`decode_step_dev_ms` takes it (the decode programs' time over the steps
they made). What is left of the step is the weight stream, the experts and
the paged read. It says whether a cell is the state-bound cell it is meant
to be: the update's bytes grow with the live ROWS and with nothing else. A
family whose program launches no such kernel, and a program that has no
such scope, report nothing."""
from harness import readers

NAME, UNIT, BETTER = "kda_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    trace = readers.trace_of(run)
    steps = readers.decode_steps_traced(run) if trace else 0.0
    traced = readers.kernel(run, "kda_update")[0]
    if not steps or not traced or not trace["decode"]["seconds"]:
        return None
    return 100.0 * traced["seconds"] / trace["decode"]["seconds"]
