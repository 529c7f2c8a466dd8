"""The block-sparse decode read's share of its roofline, bound by bytes: the
least bytes a decode step's reads have to move (the chosen blocks' K and V
once a KV head in every sparse block, each row's q in and its output out:
benchmark/reference/sparse_linear.py `sparse_read_bytes`, through the
family's `facts`) over the chip's 819 GB/s, divided by the device time a
step of the kernel named `sparse_read`. The bytes are the algorithm's: a
chosen block's 64 tokens, not the whole page the kernel copies to reach
them. A family whose program launches no such kernel reports nothing."""
from harness import readers

NAME, UNIT, BETTER = "sparse_read_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    return readers.roofline_pct(run, "sparse_read")
