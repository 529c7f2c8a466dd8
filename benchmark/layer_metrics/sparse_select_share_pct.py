"""The block choice's share of a decode step's device time: the traced
seconds a step of the kernel `sparse_select` (ops/sparse_attention.py: the
scores of a row's compressed keys, their softmax a head and the sum over a
KV head's heads, one call a sparse block a step) over the device time of a
decode step as `decode_step_dev_ms` takes it. The gather of the compressed
keys before the kernel and the pooling, the top-k and the page lists after
it are XLA fusions, which a trace does not name: this share is the
kernel's alone (PERF.md section 3). A family whose program launches no such
kernel, and a program that has no such scope, report nothing."""
from harness import readers

NAME, UNIT, BETTER = "sparse_select_share_pct", "%", "lower"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    trace = readers.trace_of(run)
    steps = readers.decode_steps_traced(run) if trace else 0.0
    traced = readers.kernel(run, "sparse_select")[0]
    if not steps or not traced or not trace["decode"]["seconds"]:
        return None
    return 100.0 * traced["seconds"] / trace["decode"]["seconds"]
