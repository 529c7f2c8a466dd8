"""The one table of peaks, keyed by the device kind as JAX reports it. A
device that is not here is an error, not a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s a chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def of(kind: str) -> dict:
    if kind not in PEAKS:
        raise SystemExit(f"device kind {kind!r} is not in the table of peaks "
                         f"(benchmark/harness/peaks.py has: {sorted(PEAKS)})")
    return PEAKS[kind]
