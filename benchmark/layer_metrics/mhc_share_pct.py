"""The residual mix's share of a decode step's device time: the traced
seconds a step of the two kernels of ops/mhc.py, `mhc_pre` and `mhc_post`
(two calls a block a step each), over the device time of a decode step as
`decode_step_dev_ms` takes it (the decode programs' time over the steps
they made). What is left of the step is the weight stream, the latent read
and the experts. A family whose program launches no such kernel, and a
program that has no such scope, report nothing."""
from harness import readers

NAME, UNIT, BETTER = "mhc_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"
KERNELS = ("mhc_pre", "mhc_post")


def read(run):
    trace = readers.trace_of(run)
    steps = readers.decode_steps_traced(run) if trace else 0.0
    found = [readers.kernel(run, name)[0] for name in KERNELS]
    if not steps or not all(found) or not trace["decode"]["seconds"]:
        return None
    mix = sum(traced["seconds"] for traced in found) / steps
    return 100.0 * mix / (trace["decode"]["seconds"] / steps)
