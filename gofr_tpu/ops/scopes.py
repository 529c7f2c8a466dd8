"""Names on the device: the scope around each Pallas kernel call.

XLA names a custom-call after the innermost scope of its op_name, so the
scope here is what a profiler trace shows as the kernel's instruction name
("%paged_read.41 = ... custom-call(...)"). Unscoped, a kernel inside a
layer loop is named after the loop body's own scope, "closed_call.<n>",
which says nothing; an XLA fusion is never named after a scope, so only a
kernel of its own gives a layer a time in a trace (PERF.md section 3).

The benchmark reads kernels by this name (benchmark/harness/tracered.py
`kernel_name`, which also still reads the `closed_call_` prefix the names
carried until PR 27), and tests/test_chip_compile.py pins the names.
"""

import jax


def kernel_scope(name: str):
    """`with kernel_scope("paged_read"): pl.pallas_call(...)(...)`."""
    return jax.named_scope(name)
