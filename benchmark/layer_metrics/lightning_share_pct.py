"""The lightning state update's share of a decode step's device time: the
traced seconds a step of the kernel `lightning_update` (ops/lightning.py:
one call a lightning block a step) over the device time of a decode step as
`decode_step_dev_ms` takes it. Its bytes grow with the live ROWS and with
nothing else: beside `sparse_select_share_pct` and the read it says how a
step of mixed work divides. A family whose program launches no such
kernel, and a program that has no such scope, report nothing."""
from harness import readers

NAME, UNIT, BETTER = "lightning_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    trace = readers.trace_of(run)
    steps = readers.decode_steps_traced(run) if trace else 0.0
    traced = readers.kernel(run, "lightning_update")[0]
    if not steps or not traced or not trace["decode"]["seconds"]:
        return None
    return 100.0 * traced["seconds"] / trace["decode"]["seconds"]
