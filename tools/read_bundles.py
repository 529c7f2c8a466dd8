"""What the TPU compiler schedules for the paged read's kernel, without a chip.

    JAX_PLATFORMS=cpu python tools/read_bundles.py [shape=xing|joyai] [label=path/to/paged_attention.py ...]

Compiles the latent read (`mla_read`'s call of `_paged_read`, a decode
block's step) for a DESCRIBED v5e at a benchmark cell's shape, with the
compiler told to write its final schedule, and prints the kernel's control
flow as the schedule has it: every conditional region and loop with the
BUNDLES it spans (a bundle is one VLIW instruction word, one issue slot of
the core's clock; the waits a schedule knows of are in it as empty bundles,
the ones it cannot know, a copy not yet landed, are not). For the tree's
module and any other copy given as label=path (a parent's under build/).

It is a COUNT of instructions, never a time: nothing runs. What it is good
for is sizing a change to the kernel before a chip is to be had: the path a
row takes is the bundles outside every region it skips, and on the parent
of PR 48 that sum came within 5 % of what `tools/bench_paged_read.py` had
measured a row (1,101 bundles + ~100 a grid step for a one-page row where
the tool read 1.21 us = 1,137 cycles at 940 MHz; 1,695 for a row of eight
pages where it read 1.85 us = 1,740). PERF.md section 6 (PR 48) has the
reading of both kernels.

Each compile runs in a child process (the dump flags are the process's, and
libtpu aborts at exit under them); the dump goes to a temporary directory
and is removed.
"""

import glob
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SHAPES = {"xing": (20, 865, 96, 16), "joyai": (12, 4600, 128, 64)}

CHILD = r"""
import importlib.util, os, sys
path, out, L, P, B, NP = sys.argv[1], sys.argv[2], *map(int, sys.argv[3:7])
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TPU_LOG_DIR"] = "disabled"
os.environ["LIBTPU_INIT_ARGS"] = (
    f"--xla_jf_dump_llo_text=true --xla_jf_dump_to={out}")
sys.path.insert(0, sys.argv[7])
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
import gofr_tpu.ops.paged_attention
spec = importlib.util.spec_from_file_location("gofr_tpu.ops._bundles", path)
m = importlib.util.module_from_spec(spec)
sys.modules[spec.name] = m
spec.loader.exec_module(m)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
bf, i32 = jnp.bfloat16, jnp.int32
args = (S((B, 32, 576), bf), S((B, 1, 576), bf), S((L, P, 1, 576, 128), bf),
        S((L, B, 1, 16, 640), bf), S((B, NP), i32), S((B,), i32),
        S((B,), i32))
read = lambda q, new, pool, tail, table, lens, tails: m._paged_read(
    q, [pool], table, lens, (new, tail, tails), jnp.int32(3), None, False,
    value_width=512, scale=192 ** -0.5, scope="mla_read")
jax.jit(read, donate_argnums=(3,)).lower(*args).compile()
"""


def bundles(path: str, shape) -> list:
    """[(address, line)] of the kernel's final bundles."""
    with tempfile.TemporaryDirectory() as out:
        subprocess.run([sys.executable, "-c", CHILD, path, out,
                        *map(str, shape), ROOT], capture_output=True)
        found = [f for f in glob.glob(os.path.join(out, "*mla_read*"))
                 if re.search(r"-\d+-final_bundles\.txt$", f)]
        if not found:
            raise SystemExit(f"the compiler wrote no schedule for {path}")
        rows = []
        for line in open(found[0]):
            m = re.match(r"\s*(0x[0-9a-f]+|\d+)\s", line)
            if m:
                rows.append((int(m.group(1), 0), line))
        return rows


def regions(rows):
    """(address of the branch, 'if' | 'loop', bundles it spans, nesting):
    a forward branch skips to the mark of its region ahead; a backward one
    closes the loop whose body (`LB`) carries its region's mark."""
    ahead, bodies = {}, {}
    for addr, line in rows:
        loop_body = re.match(r"\s*\S+\s+LB:", line)
        for n in re.findall(r"(?:Start/End empty|Start|End) region (\d+)",
                            line):
            (bodies if loop_body else ahead).setdefault(int(n), []).append(
                addr)
    out = []
    for addr, line in rows:
        depth = len(re.match(r"[^{]*?:\s*(>*)", line).group(1))
        for n in re.findall(r"sbr\.rel \(!?%p\w+\) target bundleno = \d+ "
                            r"\(0x[0-9a-f]+\), region = (\d+)", line):
            body = [a for a in bodies.get(int(n), []) if a <= addr]
            skip = [a for a in ahead.get(int(n), []) if a > addr]
            if body:
                out.append((max(body), "loop", addr - max(body), depth))
            elif skip:
                out.append((addr, "if", min(skip) - addr, depth))
    return sorted(out)


def main(argv) -> None:
    shape = SHAPES[next((a[6:] for a in argv if a.startswith("shape=")),
                        "xing")]
    modules = {"tree": os.path.join(ROOT, "gofr_tpu", "ops",
                                    "paged_attention.py")}
    modules.update(a.split("=", 1) for a in argv
                   if not a.startswith("shape="))
    for label, path in modules.items():
        rows = bundles(path, shape)
        print(f"{label}: {rows[-1][0] + 1} bundles")
        for addr, kind, span, depth in regions(rows):
            print(f"  {'  ' * depth}{kind:4s} @{addr:5d} spans {span:5d}")


if __name__ == "__main__":
    main(sys.argv[1:])
