"""The lightning decode state update's share of its roofline, bound by
bytes: the least bytes a decode step's state updates have to move (each live
row's matrix state read and written once in every lightning block, its q, k
and v in and its o out: benchmark/reference/sparse_linear.py
`lightning_update_bytes`, through the family's `facts`) over the chip's 819
GB/s, divided by the device time a step of the kernel named
`lightning_update`. A family whose program launches no such kernel reports
nothing."""
from harness import readers

NAME, UNIT, BETTER = "lightning_update_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    return readers.roofline_pct(run, "lightning_update")
