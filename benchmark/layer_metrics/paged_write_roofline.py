"""The decode page-write kernel's share of its roofline, bound by bytes:
the new token's K and V values only (harness/bytes_fns.py) over 819 GB/s,
divided by the kernel's device time a step. The page read-modify-write the
kernel does today is its own cost, not the algorithm's need, so this reads
very low until the write is fused into the read."""
from harness import bytes_fns, peaks, readers

NAME, UNIT, BETTER = "paged_write_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    steps = readers.decode_steps_traced(run)
    kernel = (readers.trace_of(run) or {}).get("kernels", {}).get("write")
    if not steps or not kernel or not kernel["seconds"]:
        return None
    dims, chips = run["loaded"]["dims"], readers.trace_of(run)["devices"]
    rows, _ = readers.live(run)
    least = bytes_fns.paged_write_bytes(
        rows, dims["L"], dims["Hkv"], dims["dh"]) / chips
    peak = peaks.of(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (least / peak) / (kernel["seconds"] / steps)
