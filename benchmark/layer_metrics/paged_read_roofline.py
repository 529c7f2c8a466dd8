"""The paged read kernel's share of its roofline, bound by bytes: the least
bytes a decode step's attention has to read (every live token's K and V
once, q in, output out: harness/bytes_fns.py) over the chip's 819 GB/s,
divided by the kernel's device time a step. Live tokens are what the
clients had been sent, per chip under tensor parallelism."""
from harness import bytes_fns, peaks, readers

NAME, UNIT, BETTER = "paged_read_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    steps = readers.decode_steps_traced(run)
    if not steps:
        return None
    dims, chips = run["loaded"]["dims"], readers.trace_of(run)["devices"]
    rows, tokens = readers.live(run)
    least = bytes_fns.paged_read_bytes(
        tokens, rows, dims["L"], dims["Hkv"], dims["H"], dims["dh"]) / chips
    seconds = readers.trace_of(run)["kernels"]["read"]["seconds"] / steps
    peak = peaks.of(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / seconds
