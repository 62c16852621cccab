"""End-to-end observability for the TPU verification pipeline.

The pieces (docs/OBSERVABILITY.md):

- tracing.py — span tracer with explicit SpanContext propagation across
  the flow state machine, verifier service, SignatureBatcher threads,
  messaging, notary, and raft. No-op by default (``NOOP_TRACER``);
  ``enable_tracing()`` turns it on.
- ring.py — the bounded in-memory span buffer behind a live tracer, with
  JSONL export and the /traces endpoint's query surface.
- profiling.py — the kernel flight recorder: compile-cache accounting,
  device dispatch/wait wall time, batch occupancy, prep/device overlap;
  always-on, exported through /metrics and /debug/profile.
- slog.py — structured JSON log lines correlated by trace_id.
- federation.py — node-side accumulator for worker metric snapshots
  (per-worker labeled families + Fleet.agg.* merges on /metrics).
- lifecycle.py — bounded per-request event timelines (/debug/requests).
- slo.py — availability/latency objectives, error budgets, multi-window
  burn-rate alerts (surfaced on /readyz as ``degraded.slo``).
- critpath.py — tail forensics: critical-path (blocking chain) extraction
  over stitched span trees, wait_kind blame attribution and the
  /debug/critpath payload.
- timeseries.py — the retained time-series plane: memory-bounded,
  downsampled history (fine recent rings cascading into coarse older
  rings) behind /api/timeseries and the consensus_stat CLI.
- consensus_obs.py — the consensus observatory: raft stats pooling
  (/debug/raft), Raft.* metric families, growth watchdogs.
- resprof.py — the resource accounting plane (per-structure size probes
  → ``Resource.*`` series → ``bounded | growing | leaking`` verdicts),
  the subsystem CPU sampling profiler and the /debug/soak payload over
  both.

The package imports nothing of corda_tpu but ``utils``
(tests/test_layering.py). The Histogram metric type itself lives in
utils/metrics.py with the rest of the registry.
"""
from .consensus_obs import (ATTRIBUTION_COMPONENTS, GrowthWatch,
                            install_raft_collector, raft_report,
                            sample_timeseries)
from .critpath import (COMPONENTS, WAIT_KINDS, aggregate_critpaths,
                       component_of, critical_path, critpath_report,
                       flow_kind)
from .federation import FleetMetricsFederation
from .lifecycle import RequestLog
from .profiling import (KernelProfiler, OverlapTracker, get_profiler,
                        set_profiler)
from .resprof import (COMMIT_PATH_COMPONENTS, CPU_COMPONENTS,
                      ResourceRegistry, SubsystemProfiler, classify_stack,
                      get_resources, leak_verdict, process_rss_bytes,
                      set_resources, soak_report, theil_sen_slope)
from .ring import SpanRing
from .slog import jlog
from .slo import DEFAULT_OBJECTIVES, SLObjective, SLOTracker
from .timeseries import (TimeSeries, TimeSeriesStore, get_timeseries,
                         set_timeseries)
from .tracing import (NOOP_SPAN, NOOP_TRACER, NoopTracer, Span, SpanContext,
                      Tracer, disable_tracing, enable_tracing, get_tracer,
                      make_span_dict, set_tracer)

__all__ = [
    "ATTRIBUTION_COMPONENTS", "COMMIT_PATH_COMPONENTS", "COMPONENTS",
    "CPU_COMPONENTS", "DEFAULT_OBJECTIVES", "FleetMetricsFederation",
    "GrowthWatch", "KernelProfiler", "NOOP_SPAN", "NOOP_TRACER",
    "NoopTracer", "OverlapTracker", "RequestLog", "ResourceRegistry",
    "SLObjective", "SLOTracker", "Span", "SpanContext", "SpanRing",
    "SubsystemProfiler", "TimeSeries", "TimeSeriesStore", "Tracer",
    "WAIT_KINDS", "aggregate_critpaths", "classify_stack", "component_of",
    "critical_path", "critpath_report", "disable_tracing",
    "enable_tracing", "flow_kind", "get_profiler", "get_resources",
    "get_timeseries", "get_tracer", "install_raft_collector", "jlog",
    "leak_verdict", "make_span_dict", "process_rss_bytes", "raft_report",
    "sample_timeseries", "set_profiler", "set_resources",
    "set_timeseries", "set_tracer", "soak_report", "theil_sen_slope",
]
