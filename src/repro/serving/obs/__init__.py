"""Serving observability: per-tick phase tracing, Prometheus text
exposition, live energy/power-gating gauges and performance attribution.

Dependency-free (stdlib + the repo's own analytical models). Five pieces,
each usable alone:

  * `obs.tracer` — `Tracer`: nested per-tick phase spans (tick → the
    phases of `repro.obs.names.PHASES`),
    per-request lifecycle tracks (queued → prefilling → decoding → done,
    with preempt/cancel edges) and jit-recompile instants, exported as
    Chrome ``trace_event`` JSON(L) loadable in Perfetto. A ring-buffer mode
    bounds memory on long soaks; disabled (the default in the engine) it
    allocates nothing per span.
  * `obs.prom` — renders the gateway `Metrics` registry in the standard
    Prometheus text exposition format (``# TYPE`` lines, cumulative
    histogram buckets incl. ``+Inf``) and writes it atomically.
  * `obs.energy` — `EnergyMonitor`: drives `core.powergate.GatingSchedule`
    from live engine state every tick (device-busy fraction, SRAM
    residency) and integrates the paper's Fig-12 power model into
    `energy_per_token_j` / `gated_bank_fraction` / `chip_power_w` gauges —
    the measurement half of the ROADMAP power-gating item.
  * `obs.profile` — `ProfileRegistry`: roofline placement for every
    compiled serving function. Rides the engine's ``_dispatch`` probe;
    captures loop-weighted structural FLOPs/bytes (cross-checked against
    XLA ``cost_analysis``/``memory_analysis``) per (fn, shape signature)
    and combines them with blocked wall times into achieved FLOP/s & GB/s
    vs the `repro.obs.hardware` peaks — memory- vs compute-bound, % of
    roof, top recompile offenders.
  * `obs.slo` — `SLOAttribution`: per-request wall-time decomposition
    (queue_wait / prefill / decode / decode_stall / preempted) whose
    components sum exactly to request wall time; the gateway turns closed
    tracks into per-phase p95 histograms and attributed
    ``slo_violation__<phase>`` counters.
"""
from repro.serving.obs.energy import EnergyMonitor
from repro.serving.obs.profile import (FnProfile, ProfileRegistry,
                                       attribution_report, classify,
                                       validate_report)
from repro.serving.obs.prom import render_text, write_prom
from repro.serving.obs.slo import PHASES as SLO_PHASES
from repro.serving.obs.slo import SLOAttribution
from repro.serving.obs.tracer import (NULL_TRACER, CompileWatch, Tracer,
                                      load_trace, validate_trace)

__all__ = ["CompileWatch", "EnergyMonitor", "FnProfile", "NULL_TRACER",
           "ProfileRegistry", "SLOAttribution", "SLO_PHASES", "Tracer",
           "attribution_report", "classify", "load_trace", "render_text",
           "validate_report", "validate_trace", "write_prom"]
