"""Published peaks of the chips this benchmark runs on, keyed by the
`device_kind` JAX reports. A kind that is not here is an error, never a
default: a roofline share against the wrong peak is worse than none.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one chip
has 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s and
1,600 Gbit/s of chip-to-chip interconnect. JAX names the chip `TPU v5 lite`
(libtpu 0.0.34); `TPU v5e` is kept for builds that use the product name.
"""

from __future__ import annotations

_V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e system architecture"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"benchmark/peaks.py with its source") from None
