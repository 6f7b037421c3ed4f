"""repro.obs — end-to-end observability for the Compass stack.

Four surfaces (DESIGN.md §Observability), all off by default and all
bitwise-invariant to search results:

* **registry** — host-side counters/gauges/fixed-bucket histograms with
  Prometheus-text + JSON exporters and a validateable schema; device
  ``SearchStats`` fold in only at existing sync points
  (:func:`record_search_stats`).  Enable with ``REPRO_OBS=1`` or
  :func:`set_enabled`.
* **trace** — per-query explain traces: ``compass_search(...,
  explain=True)`` returns :class:`QueryTrace` records rendered by
  :func:`explain` (re-exported as ``repro.compass.explain``).
* **profiling** — ``compass/<stage>`` named scopes on the engine's stages
  and every Pallas kernel (HLO metadata only), ``TraceAnnotation`` host
  spans around the serving micro-batch and its phases, the record of the
  programs served (``SERVED``) for joining a device trace to them, an
  ``REPRO_OBS_PROFILE`` XPlane capture helper, and trace-time
  kernel/fallback/autotune counters that stay on even when the registry
  is disabled (one dict add per *compile*).
* **events** — a structured lifecycle log (compactions, epoch swaps,
  delta overflows, write errors, codebook retrains, executable compiles)
  with an optional JSONL sink (``REPRO_OBS_EVENTS=<path>``).

PR 9 adds the *continuous* layer on top — point-in-time becomes
over-time:

* **timeseries** — a bounded ring of registry snapshots with windowed
  delta/rate/quantile reads and the ``repro.obs.timeseries/v1`` export.
* **slo** — declarative objectives evaluated as multi-window burn rates
  (``SloSpec``, ``evaluate_slos``) publishing ``compass_slo_*`` gauges.
* **health** — drift/debt/skew watchdogs and the :class:`Monitor` that
  ``SearchService.step()`` ticks; ``python -m repro.obs.report`` renders
  any of it as a text dashboard.
"""
from . import events, health, profiling, registry, slo, timeseries, trace  # noqa: F401 — keep the
# submodules addressable as attributes: the convenience re-exports below
# must NOT shadow them (``repro.obs.registry`` stays the module; the
# accessor for the global MetricsRegistry is :func:`get_registry`)
from .events import EVENTS, EventLog, emit
from .health import DEFAULT_WATCHDOGS, HealthCheck, HealthReport, Monitor
from .profiling import (
    KERNELS,
    SERVED,
    annotate,
    kernel_scope,
    profile_capture,
    stage_scope,
    staged,
)
from .registry import (
    LATENCY_BUCKETS_S,
    RECALL_BUCKETS,
    SCHEMA,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    enabled,
    record_search_stats,
    reset,
    set_enabled,
    validate_export,
    validate_file,
)
from .registry import registry as get_registry
from .slo import SloSpec, SloWindow, default_slos, evaluate_slos
from .timeseries import (
    Snapshotter,
    TimeSeriesRing,
    quantile_from_counts,
    validate_timeseries_export,
)
from .trace import QueryTrace, ShardedQueryTrace, build_traces, explain, format_trace

__all__ = [
    "Counter",
    "DEFAULT_WATCHDOGS",
    "EVENTS",
    "EventLog",
    "Gauge",
    "HealthCheck",
    "HealthReport",
    "Histogram",
    "KERNELS",
    "LATENCY_BUCKETS_S",
    "MetricsRegistry",
    "Monitor",
    "QueryTrace",
    "RECALL_BUCKETS",
    "SCHEMA",
    "SERVED",
    "ShardedQueryTrace",
    "SloSpec",
    "SloWindow",
    "Snapshotter",
    "TimeSeriesRing",
    "annotate",
    "build_traces",
    "default_slos",
    "emit",
    "enabled",
    "evaluate_slos",
    "events",
    "explain",
    "format_trace",
    "get_registry",
    "health",
    "kernel_scope",
    "profile_capture",
    "profiling",
    "quantile_from_counts",
    "record_search_stats",
    "registry",
    "reset",
    "set_enabled",
    "slo",
    "stage_scope",
    "staged",
    "timeseries",
    "trace",
    "validate_export",
    "validate_file",
    "validate_timeseries_export",
]
