"""repro.obs — run-wide observability: spans, layerwise telemetry,
profiler windows.

Four legs, one goal — make a whole run explainable after the fact:

    trace      low-overhead span tracer (monotonic clocks, bounded
               event ring, trace-v1 JSONL through MetricsSink) —
               where host time goes: data_wait / dispatch / resolve /
               probe / controller; each span is also a profiler
               annotation on the device trace's clock
    layerwise  the paper's per-layer (w_norm, g_norm, trust_ratio)
               stream, plumbed out of the fused step's existing trust
               table (zero extra pallas_calls) + decimating history
    profiler   jax.profiler start/stop step windows
    scopes     the names of the training step's layers, opened as
               ``jax.named_scope``s so a device trace's ops map to them

``tools/render_trace.py`` renders a trace JSONL as a Chrome/Perfetto
timeline; ``tools/obs_report.py`` prints the per-phase breakdown and
the top-k sharpest trust-ratio layers.
"""
from repro.obs import layerwise, profiler, scopes, trace
from repro.obs.layerwise import LayerwiseHistory
from repro.obs.profiler import StepProfiler, profile
from repro.obs.trace import NULL, Tracer, phase_summary

__all__ = [
    "LayerwiseHistory", "NULL", "StepProfiler", "Tracer", "layerwise",
    "phase_summary", "profile", "profiler", "scopes", "trace",
]
