"""The KDA decode state update's share of its roofline, bound by bytes: the
least bytes a decode step's state updates have to move (each live row's
matrix state read and written once in every KDA block, its q, k, v and
decay in and its o out: benchmark/reference/kda_moe.py `kda_update_bytes`,
through the family's `facts`) over the chip's 819 GB/s, divided by the
device time a step of the kernel named `kda_update`. A family whose program
launches no such kernel reports nothing."""
from harness import readers

NAME, UNIT, BETTER = "kda_update_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    return readers.roofline_pct(run, "kda_update")
