"""Published peaks of the accelerators the benchmark may run on, keyed by
the `device_kind` JAX reports. A device that is not here is an error,
never a default."""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e" system architecture page: 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
# (Copied from seldon_tpu/servers/cost_model._PEAK_TABLE's "v5 lite" row.)
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}: add a row "
            f"to benchmark/peaks.py with its source") from None
