"""The latent decode read's share of its roofline, bound by bytes: the
least bytes a decode step's MLA blocks have to move (each live token's one
latent plane a block, 576 values, once for all heads; each row's absorbed
queries in and its attended latents out: benchmark/reference/mla_moe.py
`mla_read_bytes`) over the chip's 819 GB/s, divided by the device time a
step of the kernel named `mla_read`. At 60 flop a byte the kernel is bound
by bytes on a v5e. A family whose program launches no such kernel (and a
program that has no such scope) reports nothing."""
from harness import readers

NAME, UNIT, BETTER = "mla_read_roofline", "%", "higher"
LAYER, SOURCE, MOVES = "kernels", "device_trace", "out_tok_s"


def read(run):
    return readers.roofline_pct(run, "mla_read")
