"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error: a
share of a peak is never taken against a guessed one."""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interchip links
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bytes_per_s": 1600e9 / 8,
                    "source": "Google Cloud TPU documentation, TPU v5e"},
}


def peaks(device_kind: str) -> Dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a device
    the table does not hold."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
