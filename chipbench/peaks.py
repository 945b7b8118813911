"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default: a roofline share against the wrong chip's peak means nothing."""
from __future__ import annotations

#: device_kind -> peaks of ONE chip. Source: Google Cloud documentation,
#: "TPU v5e" (system architecture page): 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table of ``device_kind``; raises KeyError for an unknown
    chip."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
