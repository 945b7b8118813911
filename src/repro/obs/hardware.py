"""Hardware peak specs — the single source of truth for roofline math.

The offline roofline report (``benchmarks/roofline.py``), the live serving
profiler (``repro.serving.obs.profile``) and the analytic memory model
(``benchmarks/analytic_model``) all read these numbers.

``PEAKS`` is keyed by ``jax.Device.device_kind``:

* ``"TPU v5 lite"`` → ``TPU_V5E``, the paper's deployment target. Published
  per-chip peaks (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16,
  819 GB/s HBM, 16 GiB HBM; ~50 GB/s per ICI link is a conservative
  single-link figure.
* ``"cpu"`` → ``CPU_HOST``, order-of-magnitude host numbers so the profiler
  can classify memory- vs compute-bound under ``JAX_PLATFORMS=cpu``. Its
  name says ``cpu-host``: efficiencies against it are not device numbers.

``detect()`` looks the first device's kind up and raises on a kind the
table does not hold — an unknown accelerator never borrows another's peaks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Peak rates for one chip (or one host, for the CPU entry)."""

    name: str
    peak_flops: float       # FLOP/s (bf16 on TPU)
    hbm_bw: float           # bytes/s main-memory bandwidth
    ici_link_bw: float      # bytes/s per interconnect link
    hbm_bytes: int          # main-memory capacity, bytes

    @property
    def ridge_intensity(self) -> float:
        """FLOP/byte at the roofline ridge: below it a kernel is
        bandwidth-limited, above it compute-limited."""
        return self.peak_flops / self.hbm_bw

    def roof_flops(self, intensity: float) -> float:
        """Attainable FLOP/s at a given operational intensity."""
        if intensity <= 0.0:
            return self.hbm_bw  # degenerate: pure-memory op, 1 flop/byte roof
        return min(self.peak_flops, intensity * self.hbm_bw)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "peak_flops": self.peak_flops,
            "hbm_bw": self.hbm_bw,
            "ici_link_bw": self.ici_link_bw,
            "hbm_bytes": self.hbm_bytes,
            "ridge_intensity": self.ridge_intensity,
        }


#: TPU v5e, per chip.
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_link_bw=50e9,
    hbm_bytes=16 * 1024 ** 3,
)

#: Rough single-socket host: ~100 GFLOP/s sustained f32, ~20 GB/s DRAM.
#: Deliberately conservative round numbers, not a measurement.
CPU_HOST = HardwareSpec(
    name="cpu-host",
    peak_flops=100e9,
    hbm_bw=20e9,
    ici_link_bw=1e9,
    hbm_bytes=8 * 1024 ** 3,
)

#: Peak table keyed by ``jax.Device.device_kind``.
PEAKS: Dict[str, HardwareSpec] = {
    "TPU v5 lite": TPU_V5E,
    "cpu": CPU_HOST,
}


def detect() -> HardwareSpec:
    """Spec for the first jax device's kind. Raises ``KeyError`` for a kind
    with no entry in ``PEAKS``."""
    import jax
    device_kind = jax.devices()[0].device_kind
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak specs for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
