"""The held experts' matmuls' share of their roofline, bound by bytes: the
least bytes a decode step's expert blocks have to read (the two matrices of
every held expert that a live row picked, once, the rows in and their
routed sums out: benchmark/reference/nemotron_h.py `moe_experts_bytes`,
whose count of touched experts is the expectation under uniform routing,
never more than all the held ones) over the chip's 819 GB/s, divided by the
device time a step of the kernel named `moe_experts`. A family whose
program launches no such kernel reports nothing."""
from harness import readers

NAME, UNIT, BETTER = "moe_experts_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    return readers.roofline_pct(run, "moe_experts")
