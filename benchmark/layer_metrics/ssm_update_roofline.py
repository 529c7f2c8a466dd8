"""The Mamba-2 decode state update's share of its roofline, bound by bytes:
the least bytes a decode step's state updates have to move (each live row's
recurrent state read and written once in every Mamba-2 block, its x, B, C
and dt in and its y out: benchmark/reference/nemotron_h.py
`ssm_update_bytes`, through the family's `facts`) over the chip's 819 GB/s,
divided by the device time a step of the kernel named `ssm_update`. A
family whose program launches no such kernel reports nothing."""
from harness import readers

NAME, UNIT, BETTER = "ssm_update_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    return readers.roofline_pct(run, "ssm_update")
