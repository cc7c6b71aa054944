"""Published peak rates of the chips this benchmark may run on.

The benchmark's own copy of `tpu_render_cluster.obs.profiling.CHIP_PEAKS`,
kept here so that a roofline share cannot move because the program's table
did. Keyed by JAX's `device_kind`. A kind that is not here is an error,
not a default: a share taken of the wrong peak is worse than none.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s.
No f32 VPU peak is published, which is why this PR reports no roofline
share for the f32 ray kernels (PERF.md, Open questions).
"""

from __future__ import annotations

CHIP_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def chip_peaks(device_kind: str) -> dict[str, float]:
    if device_kind not in CHIP_PEAKS:
        raise KeyError(
            f"no peak rates for device_kind {device_kind!r}: add a row to "
            "benchmark/lib/peaks.py from the part's datasheet"
        )
    return CHIP_PEAKS[device_kind]
