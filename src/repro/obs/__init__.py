"""Cross-cutting observability primitives shared by benchmarks and serving.

``repro.serving.obs`` holds the serving-loop instrumentation (tracer, Prom,
energy, profiler); this package holds the pieces that are *not* tied to the
serving loop — currently the hardware peak specs that roofline math is
computed against.
"""
from repro.obs.hardware import (CPU_HOST, PEAKS, TPU_V5E, HardwareSpec,
                                detect)

__all__ = ["CPU_HOST", "PEAKS", "TPU_V5E", "HardwareSpec", "detect"]
