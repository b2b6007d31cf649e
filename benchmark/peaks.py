"""Published peaks of the devices the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, never a
default: a roofline share against a guessed peak means nothing."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
    # 819 GB/s per chip. No f32 matrix peak is published; the GLM kernel's
    # arithmetic runs on the VPU in f32 and is bandwidth-bound (PERF.md 5).
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "hbm_bytes": 16e9, "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add them to "
            "benchmark/peaks.py with their source")
    return PEAKS[device_kind]
